from lib import spanattrs


def read(run, spec):
    """Rows read over rows a program without the window would read, both
    summed over the traced decode steps' spans, in %; None where the
    spans carry no such attributes."""
    read_ = [spanattrs.span_attr_sum(run, spec["span"], attr)
             for attr in spec["read"]]
    of = spanattrs.span_attr_sum(run, spec["span"], spec["of"])
    if not of or any(r is None for r in read_):
        return None
    return 100.0 * sum(read_) / of
