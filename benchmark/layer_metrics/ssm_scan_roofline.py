from lib import decode_ops, models


def read(run, spec):
    """Least time of the traced prefills' chunked scans (per prefill: the
    mean of the ``gen.prefill`` spans' real prompt rows; the larger of
    the algorithm's FLOPs at the matmul peak and its bytes at the HBM
    peak) over the scan ops' device time inside the prefill
    executables' runs, in %."""
    found = decode_ops.op_seconds_in_runs(run, spec["events"],
                                          spec["holding"])
    rows = decode_ops.span_attr_mean(run, spec["span"], spec["per_run_attr"])
    peaks = run.get("peaks")
    if not found or not rows or not peaks:
        return None
    secs, runs = found
    cfg = run["config"]
    adapter = models.adapter_of(cfg)
    least = runs * max(
        adapter.ssm_scan_flops(cfg, rows) / peaks["bf16_flops_per_s"],
        adapter.ssm_scan_bytes(cfg, rows) / peaks["hbm_bytes_per_s"])
    return 100.0 * least / secs
