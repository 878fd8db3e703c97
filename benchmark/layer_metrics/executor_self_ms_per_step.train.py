from lib import spanclock


def read(run, spec):
    steps = run["facts"].get("traced_steps")
    # a call's outermost executor span: run_steps opens no run span
    calls = [s for s in run["spans"] if s["name"] in spec["spans"]
             and s["parent_id"] is None]
    selfs = spanclock.minus_busy(run, calls)
    if not selfs or not steps:
        return None
    return sum(selfs.values()) / steps * 1e3
