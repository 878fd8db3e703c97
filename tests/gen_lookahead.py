"""Drills of the scheduler's one-step lookahead (``gen/scheduler.py``: the
next decode step is dispatched before the last one's tokens are read),
shared by ``test_gen.py`` (the ``gen_lm`` bundle, on a roomy and on a
tight page pool) and ``test_hybrid_moe.py`` (a bundle with
``state_vars``).

Each drill takes a warmed predictor with 4 slots and ``max_len`` 64 and
``ref(prompt, n)``, the cache-free greedy reference, and holds every
stream's tokens to it.  ``gen.decode.stall`` (a sleep at each dispatch)
paces the steps where a drill has to act while one is in flight."""

import contextlib
import time

from paddle_tpu import profiler
from paddle_tpu.fault import chaos
from paddle_tpu.gen import GenScheduler
from paddle_tpu.serving import BatcherCrashed

PROMPTS = [[5, 9, 3, 17], [2, 11, 29], [40, 7], [8, 8, 8], [21, 4, 33, 6, 1]]
COUNTERS = ("gen.tokens", "gen.decode.steps", "gen.decode.steps_ahead",
            "gen.decode.rows_discarded")


@contextlib.contextmanager
def scheduler(predictor, stall=None):
    """A scheduler over ``predictor`` and what the lookahead's counters
    gained by the time it was idle again: ``(sched, gained)``, ``gained``
    filled on exit."""
    m = profiler.runtime_metrics
    before = {k: m.counter(k) for k in COUNTERS}
    gained = {}
    if stall:
        chaos.inject("gen.decode.stall", delay=stall)
    sched = GenScheduler(predictor, queue_size=8)
    try:
        yield sched, gained
        settle(sched)
    finally:
        chaos.clear()
        sched.close()
        # read after the loop thread has ended: the turn that collects the
        # last step clears ``_in_flight`` BEFORE it counts that step's
        # rows, so ``settle`` can return inside that turn
        gained.update({k: m.counter(k) - before[k] for k in COUNTERS})


def settle(sched, timeout=30.0):
    """Wait until nothing is seated, queued or in flight."""
    deadline = time.monotonic() + timeout
    while sched.active_slots or sched.queue_depth or \
            sched._in_flight is not None:
        assert time.monotonic() < deadline, "scheduler did not go idle"
        time.sleep(0.01)


def take(stream, n, timeout=60.0):
    """The stream's next ``n`` tokens."""
    out = []
    while len(out) < n:
        event = stream.next_event(timeout=timeout)
        assert event is not None and event[0] == "token", event
        out.append(event[1])
    return out


def rest(stream, timeout=60.0):
    """``(tokens, closing event)``: what is left of the stream."""
    out = []
    while True:
        event = stream.next_event(timeout=timeout)
        assert event is not None
        if event[0] != "token":
            return out, event
        out.append(event[1])


def pool_is_whole(predictor):
    return predictor.free_pages == predictor.num_pages


def eos_beside_live_neighbours(predictor, ref):
    """(a) One stream meets its EOS in mid-stream while its neighbours go
    on: the step dispatched ahead carried its slot, and that row is thrown
    away, neither emitted nor counted."""
    prompts = PROMPTS[:3]
    want = [ref(p, 8) for p in prompts]
    # the first token of the middle stream that it has not produced
    # before, past the prefill's
    k = next(i for i in range(1, 8) if want[1][i] not in want[1][:i])
    with scheduler(predictor) as (sched, gained):
        streams = [sched.submit(p, max_new_tokens=8,
                                eos_id=want[1][k] if i == 1 else None)
                   for i, p in enumerate(prompts)]
        got = [list(s) for s in streams]
    assert got == [want[0], want[1][:k + 1], want[2]]
    assert [s.finish_reason for s in streams] == ["length", "eos", "length"]
    assert gained["gen.decode.rows_discarded"] >= 1
    assert gained["gen.tokens"] == sum(len(g) for g in got)
    assert pool_is_whole(predictor)


def cancel_then_readmit(predictor, ref):
    """(b) A client goes away while a step that carries its slot is in
    flight; the slot, the only free one, is seated again at once."""
    prompts = PROMPTS[:4]
    with scheduler(predictor, stall=0.03) as (sched, gained):
        streams = [sched.submit(p, max_new_tokens=40 if i == 2 else 12)
                   for i, p in enumerate(prompts)]
        victim = streams[2]
        seen = take(victim, 2)
        victim.cancel()
        late = sched.submit(PROMPTS[4], max_new_tokens=6)
        assert list(late) == ref(PROMPTS[4], 6)
        for i in (0, 1, 3):
            assert list(streams[i]) == ref(prompts[i], 12)
    assert victim.finish_reason == "disconnect"
    assert victim.tokens[:2] == seen
    assert victim.tokens == ref(prompts[2], len(victim.tokens))
    assert len(victim.tokens) < 40
    assert gained["gen.decode.rows_discarded"] >= 1
    assert gained["gen.tokens"] == sum(
        len(s.tokens) for s in streams + [late])
    assert pool_is_whole(predictor)


def admission_in_flight(predictor, ref):
    """(c) A request admitted between two turns, a step in flight: its
    first token reaches its first step through the override."""
    with scheduler(predictor, stall=0.03) as (sched, gained):
        long_s = sched.submit(PROMPTS[0], max_new_tokens=20)
        head = take(long_s, 2)
        short_s = sched.submit(PROMPTS[1], max_new_tokens=5)
        assert list(short_s) == ref(PROMPTS[1], 5)
        assert head + list(long_s) == ref(PROMPTS[0], 20)
    assert gained["gen.decode.steps_ahead"] >= 10
    assert gained["gen.decode.rows_discarded"] == 0
    assert gained["gen.tokens"] == 25


def drain_and_abort_in_flight(predictor, ref):
    """(d) The whole-token boundaries: a drain's checkpoints hold what the
    client received (the step in flight is collected first), a kill drops
    it, and either way the pool comes back whole and serves on."""
    prompts = PROMPTS[:3]
    with scheduler(predictor, stall=0.03) as (sched, _):
        streams = [sched.submit(p, max_new_tokens=50) for p in prompts]
        heads = [take(s, 2) for s in streams]
        checkpoints = {tuple(c["prompt"]): c
                       for c in sched.drain(deadline_s=0.05)}
        assert len(checkpoints) == 3
        for p, s, head in zip(prompts, streams, heads):
            tail, (kind, ckpt) = rest(s)
            assert kind == "migrate" and ckpt == checkpoints[tuple(p)]
            assert head + tail == ckpt["tokens"] == ref(p, len(head + tail))
            assert ckpt["remaining_tokens"] == 50 - len(ckpt["tokens"])
    assert pool_is_whole(predictor)
    with scheduler(predictor, stall=0.03) as (sched, _):
        streams = [sched.submit(p, max_new_tokens=50) for p in prompts]
        heads = [take(s, 2) for s in streams]
        sched.abort_streams()
        for p, s, head in zip(prompts, streams, heads):
            tail, (kind, err) = rest(s)
            assert kind == "error" and isinstance(err, BatcherCrashed)
            assert head + tail == ref(p, len(head + tail))
        settle(sched)
        assert pool_is_whole(predictor)
        chaos.clear()
        # the dropped step's writes did not outlive the reset
        assert list(sched.submit(PROMPTS[3], max_new_tokens=6)) \
            == ref(PROMPTS[3], 6)


def length_endings_cost_no_row(predictor, ref):
    """(e) Endings the host can foresee: a stream that reaches
    ``max_new_tokens`` or ``max_len`` is left out of the step dispatched
    ahead, so no row is computed for nothing."""
    # as near ``max_len`` as a prompt may start
    long_prompt = [3 + i % 50 for i in range(
        min(predictor.max_prompt_len, predictor.max_len - 3))]
    # the prefill's token, then one for each position left to write
    to_max_len = 1 + predictor.max_len - len(long_prompt)
    asks = [(PROMPTS[0], 2), (PROMPTS[1], 3), (PROMPTS[2], 7),
            (long_prompt, to_max_len + 100)]
    with scheduler(predictor) as (sched, gained):
        streams = [sched.submit(p, max_new_tokens=n) for p, n in asks]
        got = [list(s) for s in streams]
    assert got[:3] == [ref(p, n) for p, n in asks[:3]]
    assert got[3] == ref(long_prompt, to_max_len)
    assert all(s.finish_reason == "length" for s in streams)
    assert gained["gen.decode.rows_discarded"] == 0
    assert gained["gen.decode.steps_ahead"] >= 1
    assert gained["gen.tokens"] == sum(len(g) for g in got)
    assert pool_is_whole(predictor)


DRILLS = (eos_beside_live_neighbours, cancel_then_readmit,
          admission_in_flight, drain_and_abort_in_flight,
          length_endings_cost_no_row)
