"""An admission as a run of chunks between decode steps (``gen/
scheduler.py``), with ``tests/fake_gen_predictor.FakeChunkPredictor``
standing for a bundle whose prefill continues a slot's rows in place
(``models/window_moe.py``): what the device would see, in order."""

import threading
import time

import pytest

from paddle_tpu import profiler
from paddle_tpu.gen import GenScheduler
from paddle_tpu.obs import trace as ptrace

from fake_gen_predictor import FakeChunkPredictor, FakeGenPredictor

LONG = [1 + i % 6 for i in range(36)]    # 36 tokens: nine chunks of 4
COUNTERS = ["gen.prefill.turns_interleaved", "gen.prefill.turns_alone",
            "gen.prefill.admissions_chunked", "gen.admissions"]


def _counters():
    return [profiler.runtime_metrics.counter(n) for n in COUNTERS]


def _gained(before):
    return [b - a for a, b in zip(before, _counters())]


def _take(stream, n):
    out = []
    while len(out) < n:
        event = stream.next_event(5)
        assert event and event[0] == "token", event
        out.append(event[1])
    return out


@pytest.fixture()
def served():
    predictor = FakeChunkPredictor()
    sched = GenScheduler(predictor)
    yield predictor, sched
    sched.close()


def _live_pair(sched):
    """Two streams decoding, each past its first tokens, for longer
    than any test lasts (the fake's step takes microseconds)."""
    pair = [sched.submit([1, 2, 3], max_new_tokens=1 << 19)
            for _ in range(2)]
    for s in pair:
        _take(s, 2)
    return pair


def _chunks_of(events, slot):
    return [i for i, e in enumerate(events)
            if e[0] == "chunk" and e[1] == slot]


def test_every_live_stream_steps_between_any_two_chunks(served):
    predictor, sched = served
    before = _counters()
    pair = _live_pair(sched)
    long = sched.submit(LONG, max_new_tokens=3)
    assert list(long) == [7, 7, 7]
    events = list(predictor.events)
    slot = next(e[1] for e in events if e[0] == "chunk" and e[3] == 4
                and e[2] == 4)
    at = _chunks_of(events, slot)
    assert [events[i][2:] for i in at] == [(4 * j, 4) for j in range(9)]
    for a, b in zip(at, at[1:]):
        # exactly one decode step between two chunks, and both live
        # streams are in it: neither waits through more than a chunk
        between = events[a + 1:b]
        assert [e[0] for e in between] == ["step"], events[:b + 2]
        assert between[0][1] == (0, 1)
    # the admitting slot joins the steps only after its last chunk
    first_step_with = next(i for i, e in enumerate(events)
                           if e[0] == "step" and slot in e[1])
    assert first_step_with > at[-1]
    assert all(slot not in e[1] for e in events[:at[-1]] if e[0] == "step")
    for s in pair:
        _take(s, 9)
        s.cancel()
    # nine turns ran a step AND a chunk; the pair's own chunks ran alone
    # or beside each other's steps
    gained = _gained(before)
    assert gained[0] >= 9 and gained[2:] == [3, 3]
    assert gained[0] + gained[1] == 11


def test_an_explicit_budget_buys_more_chunks_a_turn():
    """``prefill_budget`` 250 at 100 a chunk: a turn's first chunk is
    free, two more fit the budget, a fourth would not: three chunks
    behind every step, and still a step between any two runs."""
    predictor = FakeChunkPredictor()
    sched = GenScheduler(predictor, prefill_budget=250.0)
    try:
        pair = _live_pair(sched)
        long = sched.submit(LONG, max_new_tokens=2)
        assert list(long) == [7, 7]
        events = list(predictor.events)
        slot = next(e[1] for e in events if e[0] == "chunk" and e[2] == 4)
        at = _chunks_of(events, slot)
        assert [events[i][2] for i in at] == [4 * j for j in range(9)]
        runs = [b - a for a, b in zip(at, at[1:])]
        # chunks of one turn lie side by side, a step before the next's
        assert runs == [1, 1, 2, 1, 1, 2, 1, 1]
        assert all(events[i + 1][0] == "step" for i in (at[2], at[5]))
        for s in pair:
            s.cancel()
    finally:
        sched.close()


def test_two_long_prompts_are_admitted_in_order(served):
    predictor, sched = served
    pair = _live_pair(sched)
    first = sched.submit(LONG, max_new_tokens=2)
    second = sched.submit(LONG[:20], max_new_tokens=2)
    assert list(first) == [7, 7] and list(second) == [7, 7]
    chunks = [e for e in predictor.events if e[0] == "chunk" and e[1] >= 2]
    slots = [e[1] for e in chunks]
    # first come first served, one request's chunks before the next's
    assert slots == [slots[0]] * 9 + [slots[-1]] * 5
    assert slots[0] != slots[-1]
    assert [e[2] for e in chunks] == [4 * j for j in range(9)] \
        + [4 * j for j in range(5)]
    for s in pair:
        s.cancel()


def test_with_no_stream_live_the_chunks_run_back_to_back(served):
    predictor, sched = served
    before = _counters()
    alone = sched.submit(LONG, max_new_tokens=4)
    assert list(alone) == [7, 7, 7, 7]
    kinds = [e[0] for e in predictor.events]
    assert kinds[:9] == ["chunk"] * 9 and set(kinds[9:]) == {"step"}
    assert _gained(before) == [0, 9, 1, 1]
    assert not predictor.held        # its pages went back at its end


@pytest.mark.parametrize("how", ["cancelled", "failed"])
def test_an_admitting_stream_that_ends_gives_back_its_slot_and_pages(
        served, how):
    predictor, sched = served
    restarts = profiler.runtime_metrics.counter("gen.scheduler_restarts")
    pair = _live_pair(sched)
    if how == "cancelled":
        predictor.gate, predictor.reached = (threading.Event(),
                                             threading.Event())
        predictor.gate_at = 8
    else:
        predictor.fail_at = 8
    long = sched.submit(LONG, max_new_tokens=3)
    if how == "cancelled":
        assert predictor.reached.wait(5)
        long.cancel()           # its reader is gone mid-admission
        predictor.gate.set()
        assert list(long) == [] and long.finish_reason == "disconnect"
    else:
        with pytest.raises(RuntimeError, match="device call failed"):
            list(long)
    slot = next(e[1] for e in predictor.events
                if e[0] == "chunk" and e[2] == 4)
    ran = [e[2] for e in predictor.events
           if e[0] == "chunk" and e[1] == slot]
    assert ran == ([0, 4, 8] if how == "cancelled" else [0, 4])
    deadline = time.monotonic() + 5
    while slot in predictor.held and time.monotonic() < deadline:
        time.sleep(0.005)
    assert slot in predictor.freed and slot not in predictor.held
    # a reader that left has its slot's rows cleared, as an evicted one
    assert (slot in predictor.cleared) == (how == "cancelled")
    # the slot is whole again, the scheduler never crashed, the live
    # streams never noticed
    predictor.gate = predictor.fail_at = None
    again = sched.submit(LONG[:6], max_new_tokens=2)
    assert list(again) == [7, 7]
    assert profiler.runtime_metrics.counter("gen.scheduler_restarts") \
        == restarts
    for s in pair:
        _take(s, 3)
        s.cancel()


def test_a_drain_hands_an_admitting_stream_back_with_no_token(served):
    predictor, sched = served
    pair = _live_pair(sched)
    predictor.gate, predictor.reached = threading.Event(), threading.Event()
    predictor.gate_at = 8
    long = sched.submit(LONG, max_new_tokens=3)
    assert predictor.reached.wait(5)
    done = []
    drain = threading.Thread(
        target=lambda: done.append(sched.drain(deadline_s=0)))
    drain.start()
    # the scheduler thread is held inside the third chunk until the
    # drain has asked for the hand-back
    deadline = time.monotonic() + 10
    while not sched._migrate_req and time.monotonic() < deadline:
        time.sleep(0.002)
    predictor.gate.set()
    drain.join(30)
    assert not drain.is_alive() and len(done[0]) == 3
    mine = [c for c in done[0] if c["prompt"] == LONG]
    assert mine and mine[0]["tokens"] == [] \
        and mine[0]["remaining_tokens"] == 3
    assert long.finish_reason == "migrated"
    assert not predictor.held and sched.active_slots == 0
    assert all(c["tokens"] for c in done[0] if c["prompt"] != LONG)
    assert all(s.finish_reason == "migrated" for s in pair)


def test_a_bundle_without_a_chunk_program_is_admitted_whole_as_before():
    predictor = FakeGenPredictor()
    assert not getattr(predictor, "prefill_chunks", ())
    before = _counters()
    sched = GenScheduler(predictor)
    ptrace.enable(1 << 12)
    ptrace.clear()
    try:
        with ptrace.trace_context("whole-1"):
            stream = sched.submit([1, 2, 3, 4, 5], max_new_tokens=3)
        assert list(stream) == [7, 7, 7]
        spans = [s for s in ptrace.snapshot_spans()
                 if s["trace_id"] == "whole-1"]
    finally:
        ptrace.disable()
        sched.close()
    assert predictor.prefill_calls == [(1, 2, 3, 4, 5)]
    by_name = {s["name"]: s for s in spans}
    # span for span: the wait, then gen.admit around the whole-prompt
    # prefill's first token and the compiled seed
    assert set(by_name) == {"gen.queue_wait", "gen.admit", "gen.first_token",
                            "gen.seed_slot"}
    admit = by_name["gen.admit"]
    assert by_name["gen.first_token"]["parent_id"] == admit["span_id"]
    assert by_name["gen.seed_slot"]["parent_id"] == admit["span_id"]
    assert by_name["gen.seed_slot"]["attrs"]["compiled_calls"] == 1
    assert "chunks" not in admit["attrs"]
    assert _gained(before) == [0, 0, 0, 1]
