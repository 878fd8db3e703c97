from lib import counts, readers


def read(run, spec):
    rows = run["facts"].get("traced_live_rows")
    secs = readers.device_seconds(run, spec["events"])
    if not secs or not rows or not run.get("peaks"):
        return None
    least = counts.paged_attention_bytes_per_step(run["config"], rows) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / secs
