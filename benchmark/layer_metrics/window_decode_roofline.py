from lib import decode_ops, models


def read(run, spec):
    """Least time of a window-attention op of the traced part over its
    device time, in %: the units the traced spans say ONE run of its
    executable had to take (``spec["units"]``, an attribute of the spans
    called ``spec["span"]``, their mean) x the traced runs of that
    executable x the adapter's bytes a unit at the HBM peak or its FLOPs
    a unit at the bf16 peak, the larger of those the spec names; over the
    device time of the events named like ``spec["events"]`` inside the
    runs of the executable that holds ``spec["holding"]`` (the events
    themselves where the spec names none).  Runs x the spans' mean, not
    the spans' sum: the spans and the device's runs of one traced stretch
    need not be equally many.  None where the spans carry no such
    attribute, the trace no such scope or the adapter no such count (a
    program without window layers: the parent of the PR that brought
    them)."""
    units = decode_ops.span_attr_mean(run, spec["span"], spec["units"])
    found = decode_ops.op_seconds_in_runs(
        run, spec["events"], spec.get("holding") or spec["events"])
    peaks = run.get("peaks")
    if not units or not found or not peaks:
        return None
    secs, runs = found
    cfg = run["config"]
    adapter = models.adapter_of(cfg)
    least = []
    for key, peak in (("bytes_per_unit", "hbm_bytes_per_s"),
                      ("flops_per_unit", "bf16_flops_per_s")):
        count = getattr(adapter, spec.get(key) or "", None)
        if count is not None:
            least.append(runs * units * count(cfg) / peaks[peak])
    return 100.0 * max(least) / secs if least else None
