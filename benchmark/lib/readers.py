"""Generic reductions a per-layer metric's ``.json`` can ask for, so that
most metrics need no code of their own.  ``run`` is what the harness
hands every reader: ``spans`` (the program's host spans of the window),
``counters`` (deltas over the window), ``facts`` (shapes and counts from
the traffic module), ``trace`` (the device trace's summary, or None),
``trace_window_s``, ``peaks``, ``config``, ``workload``, ``chips``.

A reduction that finds nothing to read returns None.
"""

from __future__ import annotations

from lib import stats, xtrace


def span_durations(run, name, under=None):
    """Durations (seconds) of the spans called ``name``; with ``under``
    only those that lie inside a span of that name on the same thread."""
    spans = run["spans"]
    picked = [s for s in spans if s["name"] == name]
    if under is None:
        return [s["dur"] for s in picked]
    parents = {}
    for s in spans:
        if s["name"] == under:
            parents.setdefault(s["tid"], []).append((s["ts"],
                                                     s["ts"] + s["dur"]))
    out = []
    for s in picked:
        for a, b in parents.get(s["tid"], ()):
            if a <= s["ts"] and s["ts"] + s["dur"] <= b:
                out.append(s["dur"])
                break
    return out


def device_seconds(run, needles):
    """Mean over the cell's devices of the leaf-event seconds whose name
    contains one of ``needles``; None when no event matches."""
    trace = run.get("trace")
    if not trace or not trace["per_device"]:
        return None
    total, matched = 0.0, 0
    for events in trace["per_device"].values():
        secs, n = xtrace.seconds_matching(events, needles)
        total += secs
        matched += n
    if not matched:
        return None
    return total / len(trace["per_device"])


def generic(run, spec):
    how = spec.get("reduce", {})
    kind = how.get("kind")
    scale = float(how.get("scale", 1.0))
    if kind == "span_percentile":
        durs = span_durations(run, how["span"], how.get("under"))
        if len(durs) < int(how.get("min_samples", 1)):
            return None
        return stats.percentile(durs, how["q"]) * scale
    if kind == "fact_percentile":
        values = run["facts"].get(how["fact"])
        return stats.percentile(values, how["q"]) * scale if values else None
    if kind == "request_wait_percentile":
        # (start of a request's span - when the request was due), joined
        # by the request id the client set; due times on the span clock
        due = run["facts"].get("due_ts_by_request")
        if not due:
            return None
        waits = [s["ts"] - due[s["trace_id"]] for s in run["spans"]
                 if s["name"] == how["span"] and s.get("trace_id") in due]
        return stats.percentile(waits, how["q"]) * scale if waits else None
    if kind == "span_sum_per":
        # summed durations of several spans over a count from ``facts``
        # or over the number of spans called ``per_span``
        total, seen = 0.0, 0
        for name in how["spans"]:
            durs = span_durations(run, name, how.get("under"))
            total += sum(durs)
            seen += len(durs)
        if not seen:
            return None
        if "per_span" in how:
            denom = len(span_durations(run, how["per_span"]))
        else:
            denom = run["facts"].get(how["per_fact"])
        return total / denom * scale if denom else None
    if kind == "device_share":
        secs = device_seconds(run, how["events"])
        trace = run.get("trace")
        if secs is None or not trace or not trace["busy_s"]:
            return None
        over = trace["busy_s"] if how.get("of") == "busy" \
            else run["trace_window_s"]
        return secs / over * scale
    raise ValueError(f"metric {spec.get('name')}: unknown reduction "
                     f"{kind!r}")
