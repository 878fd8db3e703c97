"""An admission as a run of chunks between decode steps (``gen/
scheduler.py``): what the device would see, in order.  First with
``tests/fake_gen_predictor.FakeChunkPredictor`` standing for a bundle
whose prefill continues a slot's rows in place, then with the REAL toy
bundles of every builder that exports a chunk program
(``models/window_moe.py``; ``models/latent_moe.py`` with and without
learned sparse attention), their device calls recorded in order."""

import json
import os
import threading
import time
import types

import numpy as np
import pytest

from paddle_tpu import profiler
from paddle_tpu.gen import GenPredictor, GenScheduler
from paddle_tpu.models import decoder, latent_moe, window_moe
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


# -- the same, through the real chunk bundles -----------------------------------

KINDS = ["window", "latent", "latent_sparse"]
PROMPT = [1 + i % 50 for i in range(36)]    # 36 rows: 8 + 8 + 8 + 8 + 4


def _export(kind, path):
    """A toy bundle of ``kind`` with chunk rungs of 4 and 8 rows: 4
    slots of 64 rows in pages of 4, float32, the startup program's own
    weights."""
    if kind == "window":
        hp, export = window_moe.WindowMoEConfig(), \
            window_moe.export_window_model
    else:
        hp, export = latent_moe.LatentMoEConfig(), \
            latent_moe.export_latent_model
        if kind == "latent_sparse":
            hp.index_topk, hp.indexer_types = 8, ["full", "shared", "full"]
    hp.dtype = "float32"
    was, decoder.CHUNK_ROWS = decoder.CHUNK_ROWS, 8
    try:
        export(path, hp, num_slots=4, prompt_buckets=[8, 16, 48],
               page_len=4, page_buckets=[4, 16])
    finally:
        decoder.CHUNK_ROWS = was
    return path


class _Recorded:
    """A real predictor whose device calls are written down in order, as
    ``FakeChunkPredictor.events``: ``("chunk", slot, start, rows)`` and
    ``("step", live slots)``; ``gate`` / ``gate_at`` / ``reached`` hold
    the scheduler thread inside the chunk that starts at ``gate_at``."""

    def __init__(self, predictor):
        self.predictor, self.events = predictor, []
        self.gate = self.gate_at = self.reached = None
        self._chunk, self._turn = (predictor.prefill_chunk,
                                   predictor.dispatch_turn)
        predictor.prefill_chunk = self.prefill_chunk
        predictor.dispatch_turn = self.dispatch_turn

    def prefill_chunk(self, slot, ids, start, **more):
        self.events.append(("chunk", slot, start, len(ids)))
        if self.gate is not None and start == self.gate_at:
            self.reached.set()
            assert self.gate.wait(30)
        return self._chunk(slot, ids, start, **more)

    def dispatch_turn(self, tokens, positions, lens):
        self.events.append(
            ("step", tuple(int(i) for i in np.flatnonzero(lens))))
        return self._turn(tokens, positions, lens)

    def close(self):
        self.predictor.prefill_chunk = self._chunk
        self.predictor.dispatch_turn = self._turn


@pytest.fixture(scope="module", params=KINDS)
def bundle(request, tmp_path_factory):
    p = GenPredictor(_export(
        request.param, str(tmp_path_factory.mktemp("adm") / request.param)))
    assert p.prefill_chunks == [4, 8]
    p.warmup()
    return request.param, p


@pytest.fixture()
def real(bundle):
    kind, predictor = bundle
    rec = _Recorded(predictor)
    sched = GenScheduler(predictor)
    yield kind, rec, sched
    sched.close()
    rec.close()
    assert predictor.free_pages == predictor.num_pages


def _live_real(sched, n=2):
    """``n`` streams decoding for longer than an admission of ``PROMPT``
    lasts (their slots hold 3 + 50 of 64 rows)."""
    pair = [sched.submit([1 + i, 2, 3], max_new_tokens=50) for i in range(n)]
    for s in pair:
        _take(s, 2)
    return pair


def test_a_real_bundles_live_streams_step_between_any_two_chunks(real):
    kind, rec, sched = real
    names = ["gen.prefill.chunks", "gen.prefill.rows",
             "gen.prefill.pad_rows"] + COUNTERS
    before = [profiler.runtime_metrics.counter(n) for n in names]
    pair = _live_real(sched)
    settled = len(rec.events)
    ptrace.enable(1 << 14)
    ptrace.clear()
    try:
        with ptrace.trace_context("long-1"):
            long = sched.submit(PROMPT, max_new_tokens=3)
        tokens = list(long)
        spans = [s for s in ptrace.snapshot_spans()
                 if s["trace_id"] == "long-1"]
    finally:
        ptrace.disable()
    assert len(tokens) == 3
    events = rec.events[settled:]
    slot = next(e[1] for e in events if e[0] == "chunk")
    at = _chunks_of(events, slot)
    assert [events[i][2:] for i in at] == [(0, 8), (8, 8), (16, 8), (24, 8),
                                           (32, 4)]
    for a, b in zip(at, at[1:]):
        # exactly one decode step between two chunks, and both live
        # streams are in it: neither waits through more than a chunk
        between = events[a + 1:b]
        assert [e[0] for e in between] == ["step"], events[:b + 2]
        assert len(between[0][1]) == 2 and slot not in between[0][1]
    # the admitting slot joins the steps only after its last chunk
    first_with = next(i for i, e in enumerate(events)
                      if e[0] == "step" and slot in e[1])
    assert first_with > at[-1]
    for s in pair:
        s.cancel()
    gained = [b - a for a, b in zip(
        before, [profiler.runtime_metrics.counter(n) for n in names])]
    # the pair's prompts are a chunk of 3 (of 4) each, the long one's 36
    # rows four chunks of 8 and one of 4: 7 chunks, 42 rows, 2 pad rows;
    # five turns ran a step AND a chunk
    assert gained[:3] == [7, 42, 2]
    assert gained[3] >= 5 and gained[5:] == [3, 3]
    # one gen.prefill span a chunk, with ITS rows and pages
    chunks = [s["attrs"] for s in spans if s["name"] == "gen.prefill"]
    assert [(c["start"], c["tokens"], c["rows"], c["pages"])
            for c in chunks] == [(0, 8, 8, 4), (8, 8, 8, 4), (16, 8, 8, 16),
                                 (24, 8, 8, 16), (32, 4, 4, 16)]
    admit = next(s for s in spans if s["name"] == "gen.admit")
    assert admit["attrs"]["chunks"] == 5
    seed = next(s for s in spans if s["name"] == "gen.seed_slot")
    assert seed["attrs"]["compiled_calls"] == 0
    if kind == "latent_sparse":
        # two indexers: every row scores the rows through its own and
        # keeps 8 of them, all while there are no more
        assert sum(c["dsa_rows_scored"] for c in chunks) == 2 * 36 * 37 // 2
        assert sum(c["dsa_rows_selected"] for c in chunks) \
            == 2 * (36 + 28 * 8)
    else:
        assert all("dsa_rows_scored" not in c for c in chunks)
    assert ("causal_pairs" in chunks[0]) == (kind == "window")


def test_a_real_bundles_admissions_run_in_order(real):
    _, rec, sched = real
    pair = _live_real(sched)
    settled = len(rec.events)
    first = sched.submit(PROMPT, max_new_tokens=2)
    second = sched.submit(PROMPT[:20], max_new_tokens=2)
    assert len(list(first)) == 2 and len(list(second)) == 2
    chunks = [e for e in rec.events[settled:] if e[0] == "chunk"]
    slots = [e[1] for e in chunks]
    # first come first served, one request's chunks before the next's
    assert slots == [slots[0]] * 5 + [slots[-1]] * 3
    assert slots[0] != slots[-1]
    assert [e[2] for e in chunks] == [0, 8, 16, 24, 32, 0, 8, 16]
    for s in pair:
        s.cancel()


def test_a_real_bundles_drain_hands_an_admitting_stream_back(real):
    _, rec, sched = real
    pair = _live_real(sched)
    rec.gate, rec.reached, rec.gate_at = (threading.Event(),
                                          threading.Event(), 16)
    long = sched.submit(PROMPT, max_new_tokens=3)
    assert rec.reached.wait(30)
    done = []
    drain = threading.Thread(
        target=lambda: done.append(sched.drain(deadline_s=0)))
    drain.start()
    deadline = time.monotonic() + 10
    while not sched._migrate_req and time.monotonic() < deadline:
        time.sleep(0.002)
    rec.gate.set()
    drain.join(60)
    assert not drain.is_alive() and len(done[0]) == 3
    mine = [c for c in done[0] if c["prompt"] == PROMPT]
    assert mine and mine[0]["tokens"] == [] \
        and mine[0]["remaining_tokens"] == 3
    assert long.finish_reason == "migrated"
    assert sched.active_slots == 0
    assert all(s.finish_reason == "migrated" for s in pair)


# -- which (rung, page bucket) pairs a published bundle warms --------------------------

@pytest.mark.parametrize("name, pairs", [
    # the smallest page bucket already covers both rungs: a rung over
    # every bucket up to the longest prompt's, as before this rule
    ("mimo_v2_flash", [(c, P) for c in (512, 1024)
                       for P in (64, 128, 192, 256)]),
    ("k_exaone_236b_a23b", [(c, P) for c in (512, 1024)
                            for P in (16, 32, 48, 64)]),
    # prompt buckets from 2048 rows: no half rung (its pad rows would be
    # a few hundredths of such a prompt's), five executables
    ("glm_5.2", [(1024, P) for P in (32, 64, 128, 192, 256)]),
    # pages of 16 rows under power-of-two buckets: a rung never runs
    # over fewer pages than its own rows take, nor past the 2048-row
    # prompt's 128
    ("kimi_k2.6_text", [(512, 32), (512, 64), (512, 128), (1024, 64),
                        (1024, 128)]),
])
def test_a_published_bundle_warms_the_pairs_a_chunk_can_run_at(name, pairs):
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "benchmark", "configs",
                           name + ".json")) as f:
        serving = json.load(f)["serving"]
    page_len, max_len = serving["page_len"], serving["max_len"]
    pps = -(-max_len // page_len)
    buckets = serving["page_buckets"]
    if isinstance(buckets, str):
        buckets = decoder.default_page_buckets(pps)
    p = types.SimpleNamespace(
        page_len=page_len, pages_per_slot=pps, page_buckets=buckets,
        max_prompt_len=min(max(serving["prompt_buckets"]), max_len))
    p.prefill_chunks = decoder.chunk_rows(
        page_len, serving["prompt_buckets"], max_len)
    p._pages_bucket = lambda rows: GenPredictor._pages_bucket(p, rows)
    assert GenPredictor._chunk_shapes(p) == pairs
    # no chunk of a prompt the bundle admits runs at another pair
    p._chunk_shape = lambda a, n: GenPredictor._chunk_shape(p, a, n)
    top = p.prefill_chunks[-1]
    seen = {p._chunk_shape(a, min(top, n - a))
            for n in range(1, p.max_prompt_len + 1, 37)
            for a in range(0, n, top)}
    seen |= {p._chunk_shape(a, min(top, p.max_prompt_len - a))
             for a in range(0, p.max_prompt_len, top)}
    assert seen <= set(pairs)
