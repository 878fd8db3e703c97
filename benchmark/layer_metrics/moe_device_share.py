from lib import decode_ops


def read(run, spec):
    """Device time of the ops whose scope holds one of ``events`` over
    the device's busy time in the traced part, in %."""
    secs = decode_ops.op_seconds(run, spec["events"])
    trace = run.get("trace")
    if secs is None or not trace or not trace.get("busy_s"):
        return None
    return 100.0 * secs / trace["busy_s"]
