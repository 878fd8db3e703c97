"""Device time ONE decode step spends in one group of the decode
program's ops: a partition of the decode executable's busy time.

The decode executable is found by its program's own label: the runs
(``XLA Modules`` events) that hold a leaf event whose scope path holds
``holding`` (``/gen_decode/``, the role ``models/decoder.program_role``
writes; ``lib/scoperuns.py``).  Of the leaf events that START inside such
a run, a metric's spec names its group by ``events`` (needles, any of
which the event's instruction name or main scope path holds), ``except``
(needles none of which it may hold) and ``except_name`` (needles the
instruction NAME may not hold, whatever its path: a kernel's own name).
The groups of the partition exclude each other by their ``except`` lists,
so an event is counted once; the metric whose spec has ``minus`` is the
remainder: those runs' busy time (the union of their leaf events) less
the groups it names, never below 0.  All per run, over the cell's chips.

``none_is_zero``: a group the cell's program does not have reads 0 where
the decode runs are there (a partition's member that every cell lists);
without it such a group reads None and the line leaves the metric out.
"""

import json
import os

from lib import scoperuns

HERE = os.path.dirname(os.path.abspath(__file__))


def _rows(run, holding):
    """``(runs, busy seconds, [(seconds, name, path)])`` of the leaf
    events inside the runs that hold ``holding``, summed over the cell's
    chips; None without such a run.  Made once a run: every metric of the
    family reads the same events."""
    key = "_decode_scope_rows:" + "|".join(holding)
    if key not in run:
        found = scoperuns.runs_holding(run, holding)
        run[key] = found and (
            sum(len(plane) for plane in found),
            sum(scoperuns.busy_seconds(r[2]) for plane in found
                for r in plane),
            [((ev[1] - ev[0]) / 1e9, ev[2], ev[3]) for plane in found
             for r in plane for ev in r[2]])
    return run[key]


def group_seconds(rows, spec):
    """Seconds of the rows in the spec's group; None without a match."""
    events = [n.lower() for n in spec["events"]]
    excepts = [n.lower() for n in spec.get("except", ())]
    not_named = [n.lower() for n in spec.get("except_name", ())]
    total = sum(secs for secs, name, path in rows
                if any(n in name or n in path for n in events)
                and not any(n in name or n in path for n in excepts)
                and not any(n in name for n in not_named))
    return total or None


def _spec_of(name):
    with open(os.path.join(HERE, name + ".json")) as f:
        return json.load(f)


def read(run, spec):
    found = _rows(run, spec["holding"])
    if not found:
        return None
    n_runs, busy, rows = found
    if "minus" in spec:
        parts = [group_seconds(rows, _spec_of(name)) or 0.0
                 for name in spec["minus"]]
        return max(busy - sum(parts), 0.0) / n_runs * 1e3
    secs = group_seconds(rows, spec)
    if secs is None and spec.get("none_is_zero"):
        secs = 0.0
    return None if secs is None else secs / n_runs * 1e3
