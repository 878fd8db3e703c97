from lib import decode_ops


def read(run, spec):
    """Assignments that landed on held experts over held experts touched,
    both summed over the traced decode steps' spans."""
    landed = decode_ops.span_attr_mean(run, spec["span"], spec["landed"])
    touched = decode_ops.span_attr_mean(run, spec["span"], spec["touched"])
    return landed / touched if landed and touched else None
