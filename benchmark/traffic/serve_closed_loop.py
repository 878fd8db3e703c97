"""Traffic kind ``serve_closed_loop``: token streams from one
``InferenceServer`` replica (``lib/serving_rig.py``) under a closed loop
of ``clients`` callers (``lib/closedloop.py``): every client has one
stream open at a time and sends its next request on the last token of
the one before, until the window's seconds are over.  The streams still
running then are drained; their tokens after the window's end do not
count.  The window opens when the clients start, no ramp is cut off.

Parameters (``benchmark/workloads/<cell>.json``): ``clients``, ``prompt``
and ``output`` (lognormal ``median``/``sigma``/``min``/``cap``),
``sample_seed``, ``drain_timeout_s``, ``reference_prompts``,
``logits_tol``, ``served_check`` (``streams``, ``limit``: what the window
served against the reference, ``lib/served.py``) and ``trace_seconds``.  ``--seed`` draws the weights and
the prompts' tokens, never the lengths.

End-to-end: ``saturated_tokens_per_s`` = output tokens that reached their
clients inside the window, of streams that ended well or were still
running well at its end, over the window's seconds: the replica's decode
capacity with as many streams as clients; ``gap_p99_ms`` = 99th
percentile of the gaps between consecutive tokens of one stream, over
every gap that ended inside the window: what a stream's reader feels when
an admission (or a batch of tokens held back) comes between two of its
tokens.  TTFT (from the send; a closed loop has no due time) and the
other gap percentiles go on the ``observed`` note line.
"""

from __future__ import annotations

import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from lib import closedloop, serving_rig as rig, stats  # noqa: E402
from lib.serving_rig import LEAD_S, close, verify  # noqa: E402,F401

# the least the traced stretch's running streams are given to end once
# the profiler has stopped; ``traced_drain_s`` follows the cell from there
TRACED_DRAIN_S = 40.0


def traced_drain_s(wl, gap_p50_ms):
    """How long the traced stretch's streams may take to end: a stream
    that a client sent just before the clients stop runs the workload's
    output cap of turns, so the bound is one and a half times that cap
    at the measured window's median token gap (the clients go on sending
    while the profiler stops, so the drain's clock starts behind it),
    at least ``TRACED_DRAIN_S`` (which also holds where tokens come in
    batches and the median gap reads near 0) and never more than the
    file's ``drain_timeout_s``, which bounds the measured window's own
    drain."""
    longest = 1.5 * wl["output"]["cap"] * (gap_p50_ms or 0.0) / 1e3
    return min(max(TRACED_DRAIN_S, longest),
               float(wl.get("drain_timeout_s", 120)))


def warm_requests(wl, sv, page_buckets):
    """``(prompt_len, max_new)`` pairs: one short request that decodes
    in each page bucket a stream of this traffic can reach, and at least
    one per prompt bucket its prompts can fall in."""
    page_len, buckets = sv["page_len"], sorted(sv["prompt_buckets"])
    rows = min(sv["max_len"], wl["prompt"]["cap"] + wl["output"]["cap"])
    pairs, lower = [], 0
    for edge in sorted(page_buckets):
        # the decode steps of (p, m) see p + 1 .. p + m - 1 rows: the
        # first of them lies just above ``lower`` pages
        p = max(1, min(page_len * lower, buckets[-1]))
        pairs.append((p, page_len * lower + 3 - p))
        lower = edge
        if page_len * edge >= rows:
            break
    covered = {min(b for b in buckets if b >= p) for p, _ in pairs}
    last = min(b for b in buckets
               if b >= min(wl["prompt"]["cap"], buckets[-1]))
    return pairs + [(b, 2) for b in buckets
                    if b <= last and b not in covered]


def setup(ctx):
    if ctx["traced"]:
        raise RuntimeError("serve_closed_loop has no --trace 1 run: "
                           "use --trace 2")
    wl, sv = ctx["workload"], ctx["config"]["serving"]
    return rig.setup(ctx, lambda predictor: warm_requests(
        wl, sv, predictor.page_buckets))


def window(state, ctx):
    wl, seconds = ctx["workload"], ctx["seconds"]
    state["sample"] = closedloop.Sample(wl)
    watch = rig.Watch(state["metrics"])
    loop = closedloop.ClosedLoop(state["sample"],
                                 rig.sender(state, ctx, "req"),
                                 wl["clients"])
    loop.start()
    t_end = loop.t_start + seconds
    time.sleep(max(0.0, t_end - time.perf_counter()))   # the whole window
    loop.stop()
    watched = watch.close()     # the window's, not the drain's
    records = loop.drain(float(wl.get("drain_timeout_s", 120)))
    raw, seen = rig.reduce_window(records, len(records), t_end, seconds,
                                  *watched)
    state["window_times"] = (loop.t_start,
                             [r["times"] for r in seen["ok"]])
    gaps = [b - a for r in seen["ok"]
            for a, b in zip(r["times"], r["times"][1:]) if b <= t_end]
    raw["end_to_end"] = {"saturated_tokens_per_s": seen["tokens_per_s"],
                         "gap_p99_ms": stats.percentile(gaps, 99) * 1e3
                         if gaps else None}
    raw["spans"] = []
    raw["observed"]["clients"] = wl["clients"]
    state["window_gap_p50_ms"] = raw["observed"]["gap_p50_ms"]
    return raw


def traced(state, ctx):
    """The traced stretch of a ``--trace 2`` run, after the measured
    window has drained: the same loop again on fresh request ids,
    continuing the sample; ``LEAD_S`` seconds with the program's spans
    on (a closed loop is in its steady state after one stream's life),
    then the device profiler for the cell's ``trace_seconds``."""
    wl = ctx["workload"]
    length = LEAD_S + float(wl.get("trace_seconds", 5.0))
    loop = closedloop.ClosedLoop(state["sample"],
                                 rig.sender(state, ctx, "trace"),
                                 wl["clients"])
    drain_s = traced_drain_s(wl, state["window_gap_p50_ms"])
    ok, spans, facts = rig.traced_stretch(
        state, ctx, LEAD_S, loop.start, lambda: loop.drain(drain_s))
    mine = [r["times"] for r in ok]
    w_start, w_times = state["window_times"]
    return {"spans": spans, "facts": facts,
            "observed": {
                "traced_requests": len(ok), "lead_s": LEAD_S,
                "traced_drain_bound_s": drain_s,
                "lead_gap_p50_ms": rig.gap_p50_ms(mine, loop.t_start, 0,
                                                  LEAD_S),
                "traced_gap_p50_ms": rig.gap_p50_ms(mine, loop.t_start,
                                                    LEAD_S, length),
                "gap_p50_ms_in_window": rig.gap_p50_ms(
                    w_times, w_start, LEAD_S, ctx["seconds"])}}
