def read(run, spec):
    trace, facts, peaks = run.get("trace"), run["facts"], run.get("peaks")
    steps = facts.get("traced_steps")
    per_token = facts.get("train_flops_per_token")
    tokens = facts.get("tokens_per_step_per_chip")
    if not trace or not trace.get("busy_s") or not peaks \
            or not steps or not per_token or not tokens:
        return None
    # over train_step_device_ms as its reader computes it, so that the
    # line's two numbers agree to the last digit
    step_ms = trace["busy_s"] / steps * 1e3
    return 100.0 * (per_token * tokens) \
        / (peaks["bf16_flops_per_s"] * step_ms / 1e3)
