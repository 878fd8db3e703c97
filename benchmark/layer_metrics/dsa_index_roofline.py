from lib import decode_ops, models, spanattrs


def read(run, spec):
    """Least time of a sparse-attention op of the traced decode steps over
    its device time inside the decode executable's runs, in %: the rows
    the steps' spans say it had to take (``spec["rows"]``, summed; the
    attribute counts a row once a layer that holds an indexer, the
    adapter's counts are a row over all layers) x the adapter's bytes a
    row at the HBM peak or its FLOPs a row at the bf16 peak, the larger.
    None where the spans carry no such attribute, the trace no such scope
    or the adapter no such count."""
    rows = spanattrs.span_attr_sum(run, spec["span"], spec["rows"])
    found = decode_ops.op_seconds_in_runs(run, spec["events"],
                                          spec["holding"])
    cfg = run["config"]
    adapter = models.adapter_of(cfg)
    full = getattr(adapter, "full_layers", None)
    peaks = run.get("peaks")
    if not rows or not found or full is None or not peaks:
        return None
    rows /= len(full(cfg))
    least = max(rows * getattr(adapter, spec["bytes_per_row"])(cfg)
                / peaks["hbm_bytes_per_s"],
                rows * getattr(adapter, spec["flops_per_row"])(cfg)
                / peaks["bf16_flops_per_s"])
    return 100.0 * least / found[0]
