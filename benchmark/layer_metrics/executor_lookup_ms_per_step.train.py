from lib import readers


def read(run, spec):
    """The spec's ``span_sum_per`` reduction, on a run that has a device
    trace: a per-layer metric is a reading of the traced stretch, and a
    run without one (a rehearsal on the CPU) reports none."""
    trace = run.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    return readers.generic(run, spec)
