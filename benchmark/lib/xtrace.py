"""Reduce a ``jax.profiler`` trace (``*.xplane.pb``) to device numbers.

Reads with ``jax.profiler.ProfileData`` alone.  Per device plane
(``/device:TPU:<n>``) the line named ``XLA Ops`` is the chip's op
timeline: busy time is the UNION of its events' intervals (a ``while``
or ``call`` event encloses its body's events, so a sum would count the
body twice), per-name time is the sum over the LEAF events (those that
enclose no other), and idle gaps are the complement of the union inside
the window.
"""

from __future__ import annotations

import bisect
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"     # one event per run of an executable


class TracePartFailed(RuntimeError):
    """A failure of a ``--trace 2`` run's traced stretch that knows which
    part it is, for the run's ``trace_failed`` line: ``part`` is
    ``"drain"`` (traffic that did not end inside its bound), ``"reader"``
    (the reduction of the trace, or one metric's reader) or ``"profiler"``
    (the program's tracing control).  What carries no ``part`` is the
    traffic module's own."""

    def __init__(self, part, what):
        super().__init__(f"{part}: {what}")
        self.part = part


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load_device_lines(path, device_prefix=DEVICE_PREFIX):
    """``{plane name: {line name: [(start_ns, end_ns, name, text), ...]
    sorted by start}}`` for every device plane.  The trace names an op by
    its whole HLO instruction; ``name`` is the part before `` = `` without
    the ``%`` (``fusion.4868``), ``text`` the whole of it (what a kernel's
    reader searches, since a Pallas kernel's own name is in there)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {}
    for plane in data.planes:
        if not plane.name.startswith(device_prefix):
            continue
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for ev in line.events:
                start = float(ev.start_ns)
                events.append((start, start + float(ev.duration_ns),
                               short_name(ev.name), ev.name))
        for events in lines.values():
            events.sort(key=lambda e: (e[0], -e[1]))
    return out


def load_device_events(path, device_prefix=DEVICE_PREFIX, op_line=OP_LINE):
    """``{plane name: events}`` of every device plane's op line."""
    return {plane: lines.get(op_line, []) for plane, lines
            in load_device_lines(path, device_prefix).items()}


def short_name(text):
    return text.split(" = ", 1)[0].lstrip("%")[:120]


def union_intervals(intervals):
    """Merge ``(start, end)`` pairs; returns the merged list, sorted."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def busy_seconds(events, window=None):
    """Union of the events' intervals in seconds, clipped to ``window``
    = ``(start_ns, end_ns)`` when given."""
    spans = []
    for start, end, *_ in events:
        if window is not None:
            start, end = max(start, window[0]), min(end, window[1])
        if end > start:
            spans.append((start, end))
    return sum(b - a for a, b in union_intervals(spans)) / 1e9


def leaf_events(events):
    """Events that enclose no other event (``events`` sorted by start,
    longer first on ties).  An enclosing ``while``/``call``/``conditional``
    is dropped, its body kept: the leaves' sum never exceeds the union by
    more than genuine overlap."""
    leaves = []
    n = len(events)
    for i, event in enumerate(events):
        start, end = event[0], event[1]
        j = i + 1
        encloses = j < n and events[j][0] < end and events[j][1] <= end \
            and (events[j][0] > start or events[j][1] < end)
        if not encloses:
            leaves.append(event)
    return leaves


def seconds_by_name(events):
    """``{name: seconds}`` summed over leaf events."""
    out = {}
    for start, end, name, *_ in leaf_events(events):
        out[name] = out.get(name, 0.0) + (end - start) / 1e9
    return out


def seconds_matching(events, needles):
    """Seconds of leaf events whose short name contains any of
    ``needles`` (case-insensitive), and how many matched.  The whole
    instruction text is not searched: a fusion that consumes a kernel's
    output carries the kernel's name among its operands."""
    needles = [n.lower() for n in needles]
    total, count = 0.0, 0
    for start, end, *names in leaf_events(events):
        low = names[0].lower()
        if any(n in low for n in needles):
            total += (end - start) / 1e9
            count += 1
    return total, count


def idle_gaps(events, window=None, top=10):
    """The ``top`` longest gaps of the union, as ``(start_ns, seconds)``,
    longest first.  With ``window`` the lead-in and tail count as gaps."""
    spans = union_intervals([(e[0], e[1]) for e in events])
    if window is not None:
        spans = [(max(a, window[0]), min(b, window[1])) for a, b in spans
                 if min(b, window[1]) > max(a, window[0])]
    gaps = []
    cursor = window[0] if window is not None else (
        spans[0][0] if spans else 0.0)
    for a, b in spans:
        if a > cursor:
            gaps.append((cursor, (a - cursor) / 1e9))
        cursor = max(cursor, b)
    if window is not None and window[1] > cursor:
        gaps.append((cursor, (window[1] - cursor) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    return gaps[:top]


def module_runs_holding(modules, events, needles):
    """Durations (seconds) of the ``modules`` events (runs of whole
    executables) inside which a leaf op event named like one of
    ``needles`` ran: the runs of the executable that holds that op."""
    needles = [n.lower() for n in needles]
    marks = sorted(ev[0] for ev in leaf_events(events)
                   if any(n in ev[2].lower() for n in needles))
    out = []
    for start, end, *_ in modules:
        k = bisect.bisect_left(marks, start)
        if k < len(marks) and marks[k] < end:
            out.append((end - start) / 1e9)
    return out


def summarize(path, n_devices=None, top=10):
    """What the harness needs from one trace:

    ``busy_s``      union of op intervals, mean over device planes
    ``per_device``  plane -> events (for the metric readers)
    ``modules``     plane -> events of the ``XLA Modules`` line
    ``lines``       plane -> {line name: number of events}
    ``device_ops``  top leaf op names by seconds, mean over planes
    """
    lines = load_device_lines(path)
    planes = sorted(lines)
    if n_devices is not None:
        planes = planes[:n_devices]
    used = {p: lines[p][OP_LINE] for p in planes if lines[p].get(OP_LINE)}
    if not used:
        return {"busy_s": 0.0, "per_device": {}, "modules": {},
                "device_ops": []}
    busy = sum(busy_seconds(ev) for ev in used.values()) / len(used)
    names = {}
    for ev in used.values():
        for name, secs in seconds_by_name(ev).items():
            names[name] = names.get(name, 0.0) + secs / len(used)
    ops = sorted(names.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy, "per_device": used,
            "modules": {p: lines[p].get(MODULE_LINE, []) for p in used},
            "lines": {p: {name: len(ev) for name, ev in lines[p].items()}
                      for p in used},
            "device_ops": [[n, s] for n, s in ops]}
