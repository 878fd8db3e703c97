"""Reductions for per-layer metrics that hold a NAMED op's device time
against what the program's spans say the op had to do.

An op the executor lowers through XLA (not a Pallas kernel) is not named
in the trace by its instruction (``fusion.14``): the op scope the executor
opened (``ptop_<op type>__<output>``) is in the ``tf_op`` stat of the
event's METADATA, which ``jax.profiler.ProfileData`` does not hand out.
So the trace file is read once more here, as the raw ``XSpace`` (the
protobuf module that ships inside the installed TensorFlow, loaded by its
path, without importing TensorFlow), and every leaf event of the ``XLA
Ops`` line gets its scope.  Everything returns None where the trace, the
op or the span attribute is not there (a program without them: the parent
of the PR that adds one).  A trace that IS there and cannot be read,
because the installation lacks that protobuf module, raises: the harness
reports it as ``trace_failed`` instead of a line that silently lacks the
metrics.
"""

from __future__ import annotations

import bisect
import functools
import importlib.util
import os

from lib import xtrace


@functools.lru_cache(maxsize=None)
def _xplane_pb2():
    try:
        spec = importlib.util.find_spec("tensorflow")
        path = os.path.join(os.path.dirname(spec.origin), "tsl", "profiler",
                            "protobuf", "xplane_pb2.py")
        inner = importlib.util.spec_from_file_location("_xplane_pb2", path)
        module = importlib.util.module_from_spec(inner)
        inner.loader.exec_module(module)
        return module
    except Exception as e:
        raise RuntimeError(
            "the trace's op scopes need tsl/profiler/protobuf/xplane_pb2.py "
            f"of the installed TensorFlow, which did not load: {e!r}") from e


def scoped_planes(run):
    """``{plane: (leaf events, module runs)}`` of the cell's device planes:
    events ``(start_ns, end_ns, name, scope)`` of the ``XLA Ops`` line that
    enclose no other, ``scope`` the op-scope path the compiler recorded
    for the instruction (``""`` where it recorded none); module runs
    ``(start_ns, end_ns)`` of the ``XLA Modules`` line.  Read once a run."""
    if "_scoped_planes" in run:
        return run["_scoped_planes"]
    path = (run.get("session") or {}).get("xplane")
    if not path or not os.path.exists(path):
        run["_scoped_planes"] = None
        return None
    space = _xplane_pb2().XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    planes = {}
    for plane in space.planes:
        if not plane.name.startswith(xtrace.DEVICE_PREFIX):
            continue
        scope_stat = {k for k, m in plane.stat_metadata.items()
                      if m.name == "tf_op"}
        scopes = {}
        for key, meta in plane.event_metadata.items():
            scopes[key] = next(
                (st.str_value or plane.stat_metadata[st.ref_value].name
                 for st in meta.stats if st.metadata_id in scope_stat), "")
        ops, modules = [], []
        for line in plane.lines:
            if line.name not in (xtrace.OP_LINE, xtrace.MODULE_LINE):
                continue
            for ev in line.events:
                start = line.timestamp_ns + ev.offset_ps / 1e3
                end = start + ev.duration_ps / 1e3
                if line.name == xtrace.MODULE_LINE:
                    modules.append((start, end))
                else:
                    name = plane.event_metadata[ev.metadata_id].name
                    ops.append((start, end, xtrace.short_name(name),
                                scopes.get(ev.metadata_id, "")))
        ops.sort(key=lambda e: (e[0], -e[1]))
        planes[plane.name] = (xtrace.leaf_events(ops), sorted(modules))
    names = sorted(planes)[:run.get("chips") or None]
    run["_scoped_planes"] = {p: planes[p] for p in names} or None
    return run["_scoped_planes"]


def _named(event, needles):
    low = (event[2] + " " + event[3]).lower()
    return any(n in low for n in needles)


def op_seconds(run, events):
    """Device seconds of leaf events whose name or scope holds one of
    ``events``, mean over the cell's devices; None when none does."""
    planes = scoped_planes(run)
    if not planes:
        return None
    needles = [n.lower() for n in events]
    total = sum((ev[1] - ev[0]) / 1e9 for leaves, _ in planes.values()
                for ev in leaves if _named(ev, needles))
    return total / len(planes) if total else None


def op_seconds_in_runs(run, events, holding):
    """``(seconds, runs)``: device seconds of leaf events named like one
    of ``events`` that START inside a run (``XLA Modules`` event) of the
    executable holding an op named like one of ``holding``, and the
    number of such runs, both means over the cell's devices; None
    without them."""
    planes = scoped_planes(run)
    if not planes:
        return None
    events = [n.lower() for n in events]
    holding = [n.lower() for n in holding]
    total, n_runs = 0.0, 0
    for leaves, modules in planes.values():
        marks = sorted(ev[0] for ev in leaves if _named(ev, holding))
        inside = []
        for start, end in modules:
            k = bisect.bisect_left(marks, start)
            if k < len(marks) and marks[k] < end:
                inside.append((start, end))
        starts = [a for a, _ in inside]
        n_runs += len(inside)
        for ev in leaves:
            if not _named(ev, events):
                continue
            k = bisect.bisect_right(starts, ev[0]) - 1
            if k >= 0 and ev[0] < inside[k][1]:
                total += (ev[1] - ev[0]) / 1e9
    if not total or not n_runs:
        return None
    return total / len(planes), n_runs / len(planes)


def span_attr_mean(run, span, attr):
    """Mean of ``attr`` over the traced spans called ``span`` that carry
    it; None when none does."""
    values = [s["attrs"][attr] for s in run.get("spans") or ()
              if s["name"] == span and s.get("attrs", {}).get(attr)
              is not None]
    return sum(values) / len(values) if values else None
