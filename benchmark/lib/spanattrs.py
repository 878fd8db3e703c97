"""Sums of a span attribute over the traced spans (``lib/decode_ops.py``
has the mean): for per-layer metrics that hold a traced op's device time
against what the program's spans say it had to do in those same
seconds."""

from __future__ import annotations


def span_attr_sum(run, span, attr):
    """Sum of ``attr`` over the traced spans called ``span`` that carry
    it; None when none does (a program without the attribute: the parent
    of the PR that adds it)."""
    values = [s["attrs"][attr] for s in run.get("spans") or ()
              if s["name"] == span and s.get("attrs", {}).get(attr)
              is not None]
    return sum(values) if values else None
