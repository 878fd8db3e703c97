from lib import decode_ops, models, readers


def read(run, spec):
    """Least time of the traced decode steps (the adapter's
    ``decode_step_bytes`` of what the steps' spans say they touched, at
    the HBM peak) over the time their executable ran on the device, in %.
    None where the spans carry no such attribute or the adapter no such
    count."""
    facts = run["facts"]
    rows, steps = facts.get("traced_live_rows"), \
        facts.get("traced_decode_steps")
    touched = decode_ops.span_attr_mean(run, spec["span"], spec["touched"])
    live = decode_ops.span_attr_mean(run, spec["span"], spec["live"])
    cfg = run["config"]
    count = getattr(models.adapter_of(cfg), "decode_step_bytes", None)
    if not rows or not steps or not touched or not live or count is None \
            or not run.get("peaks"):
        return None
    runs = readers.executable_runs(run, spec["events"])
    if not runs:
        return None
    least = count(cfg, touched, live, rows / steps) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (sum(runs) / len(runs))
