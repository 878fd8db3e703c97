def read(run, spec):
    trace, steps = run.get("trace"), run["facts"].get("traced_steps")
    if not trace or not trace["busy_s"] or not steps:
        return None
    return (run["trace_window_s"] - trace["busy_s"]) / steps * 1e3
