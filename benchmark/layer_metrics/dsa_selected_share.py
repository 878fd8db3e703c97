from lib import spanattrs


def read(run, spec):
    """Rows selected over rows scored, both summed over the traced decode
    steps' spans, in %; None where the spans carry no such attributes."""
    selected = spanattrs.span_attr_sum(run, spec["span"], spec["selected"])
    scored = spanattrs.span_attr_sum(run, spec["span"], spec["scored"])
    return 100.0 * selected / scored if selected and scored else None
