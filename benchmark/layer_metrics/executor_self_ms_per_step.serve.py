from lib import spanclock, stats


def read(run, spec):
    by_id = {s["span_id"]: s for s in run["spans"]}
    phases = [s for s in run["spans"] if s["name"] in spec["spans"]]
    selfs = spanclock.minus_busy(run, phases)
    steps = {}      # a decode step's span id -> its phases' self seconds
    for s in phases:
        call = by_id.get(s["parent_id"])            # executor.run
        step = by_id.get(call["parent_id"]) if call else None
        if step and step["name"] == spec["under"] and s["span_id"] in selfs:
            steps.setdefault(step["span_id"], []).append(selfs[s["span_id"]])
    whole = [sum(v) for v in steps.values() if len(v) == len(spec["spans"])]
    return stats.percentile(whole, 50) * 1e3 if whole else None
