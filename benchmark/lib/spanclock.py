"""Program spans on the device trace's clock.

The program's tracing control returns ``span_to_trace_ns`` with every
device trace (``run["session"]``): a span's ``ts`` (seconds) times 1e9
plus that offset is its start in the trace's nanoseconds.  With it a
span can be laid over the device's op timeline: how long the chip was
busy inside a span, which span the host was in during an idle gap, and
whether the two clocks agree at all.

Only spans that are on their thread's stack are used for a thread's
timeline: those with a parent or a child.  A span recorded after the
fact (a request's queue wait) has neither, and overlaps the others.
"""

from __future__ import annotations

import bisect
import collections

from lib import xtrace


def offset_ns(run):
    """``span_to_trace_ns`` of the run's device trace, or None."""
    return (run.get("session") or {}).get("span_to_trace_ns")


def to_trace(span, offset):
    """``(start_ns, end_ns)`` of a span dict on the trace's clock."""
    start = span["ts"] * 1e9 + offset
    return start, start + span["dur"] * 1e9


def trace_window(run):
    """The traced stretch on the trace's clock: first to second mark."""
    session, offset = run.get("session") or {}, offset_ns(run)
    if offset is None or session.get("t_stop") is None:
        return None
    return (session["t_start"] * 1e9 + offset,
            session["t_stop"] * 1e9 + offset)


def inside_session(spans, session):
    """The spans that lie wholly between the session's two clock marks
    (all of them when there is no such session)."""
    if not session or session.get("t_stop") is None:
        return spans
    return [s for s in spans if session["t_start"] <= s["ts"]
            and s["ts"] + s["dur"] <= session["t_stop"]]


class Busy:
    """The union of one device's op intervals, indexed so that the busy
    nanoseconds inside any ``[a, b]`` cost two bisections."""

    def __init__(self, events):
        self.merged = xtrace.union_intervals([(e[0], e[1]) for e in events])
        self.starts = [a for a, _ in self.merged]
        self.cum = [0.0]
        for a, b in self.merged:
            self.cum.append(self.cum[-1] + (b - a))

    def _until(self, t):
        k = bisect.bisect_right(self.starts, t)
        if k == 0:
            return 0.0
        a, b = self.merged[k - 1]
        return self.cum[k - 1] + (min(t, b) - a)

    def inside(self, a, b):
        return max(0.0, self._until(b) - self._until(a))

    def gaps(self, window):
        """Idle intervals inside ``window``, lead-in and tail included."""
        out, cursor = [], window[0]
        for a, b in self.merged:
            if b <= window[0]:
                continue
            if a >= window[1]:
                break
            if a > cursor:
                out.append((cursor, a))
            cursor = max(cursor, b)
        if window[1] > cursor:
            out.append((cursor, window[1]))
        return out


def busy_per_device(run):
    """One :class:`Busy` per device plane of the run's trace, built once
    a run (several readers ask)."""
    trace = run.get("trace")
    if not trace or not trace.get("per_device"):
        return []
    if "busy_index" not in trace:
        trace["busy_index"] = [Busy(ev) for _, ev
                               in sorted(trace["per_device"].items())]
    return trace["busy_index"]


def device_busy_inside(busies, a, b):
    """Busy nanoseconds inside ``[a, b]``, mean over the devices."""
    return sum(x.inside(a, b) for x in busies) / len(busies)


def dispatch_thread(spans, span_name="executor.dispatch"):
    """The thread that launches the device's work: the one with the most
    spans called ``span_name``; None when there is none."""
    tids = collections.Counter(s["tid"] for s in spans
                               if s["name"] == span_name)
    return tids.most_common(1)[0][0] if tids else None


def stack_spans(spans, tid):
    """The spans of thread ``tid`` that are on its stack."""
    parents = {s["parent_id"] for s in spans if s["parent_id"] is not None}
    return [s for s in spans if s["tid"] == tid and
            (s["parent_id"] is not None or s["span_id"] in parents)]


def innermost_timeline(spans, offset):
    """``[(start_ns, end_ns, name), ...]``, sorted and disjoint: for every
    instant some span of ``spans`` covers, the innermost one's name.
    ``spans`` nest properly (one thread's stack)."""
    out, stack = [], []      # stack of (end_ns, name)

    def emit(a, b, name):
        if b > a:
            out.append((a, b, name))

    cursor = None
    for s in sorted(spans, key=lambda s: (s["ts"], -s["dur"])):
        start, end = to_trace(s, offset)
        while stack and stack[-1][0] <= start:
            top_end, top_name = stack.pop()
            emit(cursor, top_end, top_name)
            cursor = top_end
        if stack:
            emit(cursor, start, stack[-1][1])
        cursor = start
        stack.append((end, s["name"]))
    while stack:
        top_end, top_name = stack.pop()
        emit(cursor, top_end, top_name)
        cursor = max(cursor, top_end)
    return out


def host_timeline(run):
    """:func:`innermost_timeline` of the run's dispatching thread on the
    trace's clock; None without spans or the clock offset."""
    offset = offset_ns(run)
    tid = dispatch_thread(run["spans"])
    if offset is None or tid is None:
        return None
    return innermost_timeline(stack_spans(run["spans"], tid), offset)


def name_gaps(gaps, timeline):
    """For each idle gap ``(a, b)`` the nanoseconds of it under each
    innermost span name (None = under no span): a list of dicts."""
    starts = [seg[0] for seg in timeline]
    out = []
    for a, b in gaps:
        under, covered = {}, 0.0
        k = max(0, bisect.bisect_right(starts, a) - 1)
        while k < len(timeline) and timeline[k][0] < b:
            s0, s1, name = timeline[k]
            lap = min(b, s1) - max(a, s0)
            if lap > 0:
                under[name] = under.get(name, 0.0) + lap
                covered += lap
            k += 1
        if b - a > covered:
            under[None] = (b - a) - covered
        out.append(under)
    return out


def idle_by_span(run):
    """Device-idle seconds of the traced stretch by the innermost span
    the dispatching thread was in (None = no span), on the first device;
    None without a device trace, spans and the clock offset."""
    window, busies = trace_window(run), busy_per_device(run)
    timeline = host_timeline(run)
    if window is None or not busies or timeline is None:
        return None
    totals = {}
    for under in name_gaps(busies[0].gaps(window), timeline):
        for name, ns in under.items():
            totals[name] = totals.get(name, 0.0) + ns / 1e9
    return totals


def minus_busy(run, spans):
    """``{span_id: seconds}`` for each of ``spans`` that lies inside the
    traced stretch: its length minus the device's busy time inside it
    (mean over the chips), on ONE clock.  Empty without a device trace
    or the clock offset."""
    offset, window = offset_ns(run), trace_window(run)
    busies = busy_per_device(run)
    if offset is None or window is None or not busies:
        return {}
    out = {}
    for s in spans:
        a, b = to_trace(s, offset)
        if window[0] <= a and b <= window[1]:
            out[s["span_id"]] = \
                ((b - a) - device_busy_inside(busies, a, b)) / 1e9
    return out


def agreement(run):
    """Do the clocks agree?  ``run["facts"]["clock_proof"]`` names a span
    and the device ops that mark the executable it launches and waits
    for (``{"span": ..., "holding": [needles]}``).  Every run of an
    executable holding such an op is paired with the span it starts
    in; mapped onto the trace's clock the span should contain it.
    Returns the share of spans that do and the largest overhang, or
    None when there is nothing to compare."""
    proof, offset = run["facts"].get("clock_proof"), offset_ns(run)
    trace, window = run.get("trace"), trace_window(run)
    if not proof or offset is None or not trace or window is None \
            or not trace.get("per_device"):
        return None
    plane = min(trace["per_device"])
    needles = [n.lower() for n in proof["holding"]]
    marks = sorted(ev[0] for ev in trace["per_device"][plane]
                   if any(n in ev[2].lower() for n in needles))
    runs = []
    for start, end, *_ in trace["modules"].get(plane, []):
        k = bisect.bisect_left(marks, start)
        if k < len(marks) and marks[k] < end:
            runs.append((start, end))
    spans = sorted(to_trace(s, offset) for s in run["spans"]
                   if s["name"] == proof["span"])
    spans = [(a, b) for a, b in spans
             if a >= window[0] and b <= window[1]]
    starts = [a for a, _ in spans]
    contained, paired, worst = 0, 0, 0.0
    for r0, r1 in runs:
        k = bisect.bisect_right(starts, r1) - 1
        # the span the run belongs to: the last that started before the
        # run ended (a run may start a little before its span by a clock
        # error, never after the span's end)
        if k < 0:
            continue
        a, b = spans[k]
        if r0 >= b:
            continue
        paired += 1
        over = max(a - r0, r1 - b, 0.0)
        worst = max(worst, over)
        contained += over == 0.0
    if not paired:
        return None
    return {"span": proof["span"], "spans_in_window": len(spans),
            "runs_paired": paired, "contained": contained,
            "contained_share": contained / paired,
            "largest_overhang_us": worst / 1e3,
            "mark_width_us": (run["session"].get("mark_width_ns") or 0)
            / 1e3,
            "drift_us": (run["session"].get("drift_ns") or 0) / 1e3}
