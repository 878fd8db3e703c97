"""Device time a trained step spends in ONE group of the program's ops,
read from the scope path the executor wrote into each instruction's
``op_name`` (``pt_step/<role>/<name scope...>/ptop_<type>__<output>``, the
trace's ``tf_op`` stat): ``framework.name_scope`` says which part of the
model an op belongs to, ``op_role`` whether it is backward (``bwd``) or
optimizer (``opt``) work.

A metric's spec names its group by ``events`` (needles, any of which the
event's instruction name or scope path holds) and ``except`` (needles none
of which it may hold).  The groups of a partition exclude each other by
their ``except`` lists, so an event is counted once; the metric whose spec
has ``minus`` is the remainder: the traced busy time less the groups it
names, never below 0 (leaf events of one chip can overlap by a little).
An instruction the compiler made from several (a copy between two ops)
carries their paths joined by ``;``: the path named most often counts, the
first on a tie, as in the program's own ``profiler.parse_scope_path``.
``none_is_zero`` (the overlay of recomputed instructions) reads 0 where
the trace is there and holds no such event.
"""

import collections
import json
import os

from lib import decode_ops

HERE = os.path.dirname(os.path.abspath(__file__))


def main_path(scope):
    """The ONE scope path of an event: of the ``;``-joined paths (each
    ``<op_name>:<op type>``) the one named most often, the first on a
    tie; those under a ``ptop_`` scope before any other."""
    paths = [p.split(":", 1)[0] for p in scope.split(";") if p]
    scoped = [p for p in paths if "ptop_" in p] or paths
    return collections.Counter(scoped).most_common(1)[0][0] if scoped else ""


def _rows(run):
    """``(chips, [(seconds, lowered "<instruction name> <main path>")])``
    of the run's leaf events, made once a run: every metric of the family
    reads the same events."""
    if "_train_scope_rows" not in run:
        planes = decode_ops.scoped_planes(run) or {}
        run["_train_scope_rows"] = (len(planes), [
            ((end - start) / 1e9, (name + " " + main_path(scope)).lower())
            for leaves, _ in planes.values()
            for start, end, name, scope in leaves])
    return run["_train_scope_rows"]


def group_seconds(run, spec):
    """Device seconds of the leaf events in the spec's group, mean over
    the cell's chips; None without a device trace or without a match (a
    program that writes no such scope: the parent of the PR that adds
    one)."""
    chips, rows = _rows(run)
    if not chips:
        return None
    events = [n.lower() for n in spec["events"]]
    excepts = [n.lower() for n in spec.get("except", ())]
    total = sum(secs for secs, low in rows
                if any(n in low for n in events)
                and not any(n in low for n in excepts))
    return total / chips if total else None


def _spec_of(name):
    with open(os.path.join(HERE, name + ".json")) as f:
        return json.load(f)


def read(run, spec):
    trace, steps = run.get("trace"), run["facts"].get("traced_steps")
    if not trace or not trace.get("busy_s") or not steps:
        return None
    if "minus" in spec:
        parts = [group_seconds(run, _spec_of(name)) for name in spec["minus"]]
        if any(p is None for p in parts):
            return None
        return max(trace["busy_s"] - sum(parts), 0.0) / steps * 1e3
    secs = group_seconds(run, spec)
    if secs is None and spec.get("none_is_zero") and _rows(run)[0]:
        secs = 0.0
    return None if secs is None else secs / steps * 1e3
