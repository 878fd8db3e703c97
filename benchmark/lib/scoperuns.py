"""The device trace split by EXECUTABLE RUN and by the scope path of each
leaf event, for the per-layer metrics that read the serving programs'
roles and groups (``paddle_tpu/models/decoder.py``: ``ROLES``, ``GROUPS``;
the predictor's ``gen_turn`` / ``gen_seed``; docs/observability.md).

An executable is found by the ROLE its program wrote into its
instructions' scope paths (``.../pt_step/gen_decode/attn/ptop_...``), not
by a kernel's name: ``readers.executable_runs`` matches event names, and a
fusion has none to match.  Everything returns None where the trace is not
there (a rehearsal on the CPU) or holds no such scope (the parent of the
PR that wrote the roles).
"""

from __future__ import annotations

import bisect
import collections

from lib import decode_ops, xtrace


def main_path(scope):
    """The ONE scope path of an event, by the rule of
    ``layer_metrics/train_scope_device_ms.main_path``: of the ``;``-joined
    paths (each ``<op_name>:<op type>``) the one named most often, the
    first on a tie; those under a ``ptop_`` scope before any other."""
    paths = [p.split(":", 1)[0] for p in scope.split(";") if p]
    scoped = [p for p in paths if "ptop_" in p] or paths
    return collections.Counter(scoped).most_common(1)[0][0] if scoped else ""


def runs(run):
    """``[[(start_ns, end_ns, [(start_ns, end_ns, lowered name, lowered
    main path), ...]), ...], ...]``: per device plane of the cell, every
    run of an executable (``XLA Modules`` event) with the leaf events of
    the ``XLA Ops`` line that START inside it.  None without a device
    trace.  Made once a run."""
    if "_scope_runs" not in run:
        planes = decode_ops.scoped_planes(run)
        out = None
        if planes:
            out = []
            for leaves, modules in planes.values():
                starts = [m[0] for m in modules]
                held = [[] for _ in modules]
                for start, end, name, scope in leaves:
                    k = bisect.bisect_right(starts, start) - 1
                    if k >= 0 and start < modules[k][1]:
                        held[k].append((start, end, name.lower(),
                                        main_path(scope).lower()))
                out.append([(m[0], m[1], events)
                            for m, events in zip(modules, held)])
        run["_scope_runs"] = out
    return run["_scope_runs"]


def runs_holding(run, roles):
    """Of :func:`runs`, per plane, those that hold a leaf event whose
    main path holds one of ``roles`` (needles such as ``/gen_decode/``);
    None without a device trace or where no run holds one."""
    planes = runs(run)
    if not planes:
        return None
    roles = [r.lower() for r in roles]
    found = [[r for r in plane
              if any(role in ev[3] for ev in r[2] for role in roles)]
             for plane in planes]
    return found if any(found) else None


def busy_seconds(events):
    """Union of the events' intervals, in seconds."""
    return sum(b - a for a, b in xtrace.union_intervals(
        [(ev[0], ev[1]) for ev in events])) / 1e9
