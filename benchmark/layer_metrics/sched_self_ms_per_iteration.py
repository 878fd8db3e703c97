from lib import stats


def read(run, spec):
    """Median over the spans called ``spec["span"]`` of the span less its
    direct children called one of ``spec["minus"]``, in ms."""
    children = {}
    for s in run["spans"]:
        if s["name"] in spec["minus"] and s["parent_id"] is not None:
            children[s["parent_id"]] = \
                children.get(s["parent_id"], 0.0) + s["dur"]
    selfs = [s["dur"] - children.get(s["span_id"], 0.0)
             for s in run["spans"] if s["name"] == spec["span"]]
    return stats.percentile(selfs, 50) * 1e3 if selfs else None
