from lib import stats, xtrace


def read(run, spec):
    trace = run.get("trace")
    if not trace:
        return None
    runs = []
    for plane, events in trace["per_device"].items():
        runs += xtrace.module_runs_holding(trace["modules"].get(plane, []),
                                           events, spec["events"])
    return stats.percentile(runs, 50) * 1e3 if runs else None
