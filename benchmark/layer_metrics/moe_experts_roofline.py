from lib import decode_ops, models


def read(run, spec):
    """Least time of the traced runs of one executable for the named op
    (per run: the mean of the span attribute ``per_run_attr`` x the
    adapter's ``bytes_of`` x ``passes``, at the HBM peak) over the op's
    device time inside those runs, in %."""
    found = decode_ops.op_seconds_in_runs(run, spec["events"],
                                          spec["holding"])
    units = decode_ops.span_attr_mean(run, spec["span"],
                                      spec["per_run_attr"])
    if not found or not units or not run.get("peaks"):
        return None
    secs, runs = found
    cfg = run["config"]
    unit_bytes = getattr(models.adapter_of(cfg), spec["bytes_of"])(cfg)
    least = runs * units * unit_bytes * spec.get("passes", 1) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / secs
