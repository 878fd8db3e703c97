from lib import decode_ops, models


def read(run, spec):
    """Least time of the latent kernel over the traced live rows (the
    larger of the rows' bytes at the HBM peak and the kernel's FLOPs at
    the bf16 peak, both from the adapter) over the kernel's device time
    inside the decode executable's runs, in %.  None where the trace has
    no such kernel or the adapter no such count."""
    rows = run["facts"].get("traced_live_rows")
    found = decode_ops.op_seconds_in_runs(run, spec["events"],
                                          spec["holding"])
    cfg = run["config"]
    adapter = models.adapter_of(cfg)
    flops = getattr(adapter, "mla_decode_flops_per_row", None)
    peaks = run.get("peaks")
    if not rows or not found or flops is None or not peaks:
        return None
    least = max(rows * adapter.kv_bytes_per_row(cfg)
                / peaks["hbm_bytes_per_s"],
                rows * flops(cfg) / peaks["bf16_flops_per_s"])
    return 100.0 * least / found[0]
