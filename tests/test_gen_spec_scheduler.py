"""``GenScheduler`` over a bundle that drafts (``predictor.speculative``:
a turn yields a slot a RUN of one or two tokens and the device advances
the slot by it; the host learns the run one turn late), without a model:
``DraftingFake`` keeps the device's side of the state, as
``GenPredictor``'s compiled turn does, and decides by position alone
whether a draft is kept.  Runs are emitted in order and cut at
``max_new_tokens``; advances of one and two mix in one pool under the
look-ahead dispatch; a row computed for a stream that had ended is
thrown away and lies inside the pages the request holds; drain hands
back what the client received; an EOS inside a run ends the stream
there."""

import os
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from fake_gen_predictor import FakeChunkPredictor          # noqa: E402
from gen_lookahead import settle                             # noqa: E402

from paddle_tpu import profiler                             # noqa: E402
from paddle_tpu.gen import GenScheduler                     # noqa: E402

PAGE_LEN = 4


def token_at(q):
    """The model's greedy token at position ``q`` (> the prompt's rows)."""
    return (q * 5 + 1) % 7


def kept(p):
    """Whether the draft behind the committed token at ``p`` is kept."""
    return p % 3 != 0


def stream_of(prompt_len, n):
    """What a request serves: the prefill's pick (7), then the model's
    tokens at the positions behind it."""
    return [7] + [token_at(prompt_len + j) for j in range(1, n)]


class DraftingFake(FakeChunkPredictor):
    """A chunk bundle that drafts: ``dispatch_turn`` advances its OWN
    per-slot state by 1 or 2 and ``read_turn`` hands back the runs."""
    max_len, max_prompt_len = 64, 48
    speculative = {"rows": 2, "draft_var": "d", "feed": "gen_spec"}
    spec_rows = 2

    def __init__(self):
        super().__init__()
        self.pos = np.zeros(self.num_slots, np.int64)
        self.live = np.zeros(self.num_slots, bool)
        self.pages = {}
        self.prompt_rows = {}   # slot -> its prompt's rows (the fixture's)
        self.turns = []         # the lengths of each turn's runs
        self.stall = 0.0        # seconds a turn's dispatch takes

    def pages_needed(self, prompt_len, max_new_tokens=1):
        rows = min(self.max_len, prompt_len + max(max_new_tokens, 1) + 1)
        return -(-rows // PAGE_LEN)

    def alloc_slot_pages(self, slot, n):
        self.pages[slot] = n
        return super().alloc_slot_pages(slot, n)

    def prefill_chunk(self, slot, ids, start, after=None):
        # the token behind the chunk, or None where it ends the prompt
        assert (after is None) == (start + len(ids) == self.prompt_rows[slot])
        return super().prefill_chunk(slot, ids, start)

    def dispatch_turn(self, tokens, positions, lens):
        super().dispatch_turn(tokens, positions, lens)
        time.sleep(self.stall)
        runs = []
        for s in range(self.num_slots):
            if tokens[s] >= 0:              # the host seats a stream
                self.pos[s], self.live[s] = positions[s], lens[s] > 0
                assert lens[s] == positions[s] + 1
            elif lens[s] == 0:
                self.live[s] = False        # the slot left
            else:
                # a slot that goes on: the host's rows are a bound
                assert self.live[s] and positions[s] >= self.pos[s]
            if not self.live[s]:
                runs.append([])
                continue
            p = int(self.pos[s])
            two = kept(p) and p + 1 < self.max_len
            # the committed row and the draft's lie inside the slot's pages
            assert p + 1 < self.pages[s] * PAGE_LEN or p + 1 >= self.max_len
            runs.append([token_at(p + 1)] + [token_at(p + 2)] * two)
            self.pos[s] += 1 + two
        self.turns.append([len(r) for r in runs if r])
        return runs

    def read_turn(self, read):
        return read, {}


@pytest.fixture
def fake():
    return DraftingFake()


def _submit(sched, prompt_len, cap, **kw):
    return sched.submit([1 + i % 6 for i in range(prompt_len)],
                        max_new_tokens=cap, **kw)


@pytest.fixture
def sched(fake, monkeypatch):
    # every slot's prompt length, for the fake's check of a chunk's
    # ``after`` token
    begin = GenScheduler._begin_admission

    def noted(self, slot_idx, stream):
        fake.prompt_rows[slot_idx] = len(stream.prompt)
        return begin(self, slot_idx, stream)
    monkeypatch.setattr(GenScheduler, "_begin_admission", noted)
    s = GenScheduler(fake, queue_size=16)
    yield s
    s.close()


@pytest.mark.parametrize("prompt_len, cap", [
    (5, 1), (5, 2), (6, 3), (7, 9), (8, 10), (9, 11), (13, 30), (21, 40)])
def test_runs_are_emitted_in_order_and_cut_at_the_cap(sched, fake,
                                                      prompt_len, cap):
    got = list(_submit(sched, prompt_len, cap))
    assert got == stream_of(prompt_len, cap)
    settle(sched)
    assert not fake.held       # every page went back


def test_advances_of_one_and_two_mix_in_one_pool(sched, fake):
    m = profiler.runtime_metrics
    names = ("drafted", "accepted", "emitted", "slot_turns")
    before = {k: m.counter("gen.spec." + k) for k in names}
    discarded = m.counter("gen.decode.rows_discarded")
    asks = [(5, 17), (6, 9), (7, 30), (8, 2), (9, 25), (10, 12), (11, 40),
            (12, 3), (14, 21)]
    streams = [_submit(sched, n, cap) for n, cap in asks]
    for (n, cap), s in zip(asks, streams):
        assert list(s) == stream_of(n, cap), (n, cap)
    settle(sched)
    # both advances met in one turn, many times
    assert sum(1 for t in fake.turns if {1, 2} <= set(t)) > 5
    gained = {k: m.counter("gen.spec." + k) - v for k, v in before.items()}
    assert gained["drafted"] == gained["slot_turns"]
    assert gained["emitted"] == gained["slot_turns"] + gained["accepted"]
    assert 0 < gained["accepted"] < gained["drafted"]
    # the turn dispatched ahead of a stream's last read is thrown away:
    # some streams end on a run the host could not foresee
    assert m.counter("gen.decode.rows_discarded") > discarded
    hist = m.snapshot()["histograms"]["gen.spec.run"]
    assert set(hist) <= {"1", "2", 1, 2} and len(hist) == 2


def test_an_eos_inside_a_run_ends_the_stream_there(sched, fake):
    # the first run behind a prompt of 7 rows is (token_at(8), token_at(9))
    eos = token_at(9)
    assert kept(7) and token_at(8) != eos
    got = list(_submit(sched, 7, 20, eos_id=eos))
    assert got == [7, token_at(8), eos]
    settle(sched)
    assert not fake.held


def test_drain_hands_back_what_the_client_received_mid_run(fake, sched):
    """A checkpoint's tokens are a prefix of the stream and its remaining
    count is what is left of the cap, whatever run the step in flight
    yielded: a resumed stream continues at the next index."""
    fake.stall = 0.01
    streams = [_submit(sched, n, 40) for n in (5, 6, 7)]
    for s in streams:
        assert s.next_event(timeout=10)[0] == "token"
    checkpoints = sched.drain(deadline_s=0.05)
    assert len(checkpoints) == 3
    for ckpt in checkpoints:
        n = len(ckpt["prompt"])
        assert ckpt["tokens"] == stream_of(n, 40)[:len(ckpt["tokens"])]
        assert 1 <= len(ckpt["tokens"]) < 40
        assert ckpt["remaining_tokens"] == 40 - len(ckpt["tokens"])
    assert not fake.held


def test_the_look_ahead_never_dispatches_a_stream_that_ends_for_certain(
        sched, fake):
    """A stream one token short of its cap ends with the step in flight
    whatever it yields: no row is computed for it beyond."""
    m = profiler.runtime_metrics
    before = m.counter("gen.decode.rows_discarded")
    # cap 2: the seat emits one token, ONE turn the other
    assert list(_submit(sched, 5, 2)) == stream_of(5, 2)
    settle(sched)
    assert m.counter("gen.decode.rows_discarded") == before
    assert len(fake.turns) == 1
