"""The decode TURN (``gen/predictor.py``: ``dispatch_turn`` /
``read_turn``): positions, rows and the page table live on the device and
the step advances them, so a turn in which nothing changed is one
compiled call that takes nothing from the host, and one read.

Held on a toy bundle of each of the six decoder builders: what the
scheduler serves is, token for token, what the blocking ``decode_step``
loop gives, through an admission, an EOS, a length end, a cancel, a
migration and an abort, with the device's state equal to the host's
mirror after every turn; the calls a turn costs are counted exactly; a
block bundle's view of a step from the device's integers is the host's;
and nothing compiles after the warm-up, whatever the mix of turns and
page buckets."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import gen_lookahead
import test_block_moe
import test_glm_dsa
import test_hybrid_moe
import test_latent_moe
import test_window_moe
from paddle_tpu import profiler
from paddle_tpu.gen import GenPredictor
from paddle_tpu.gen import predictor as predictor_mod
from paddle_tpu.models import (block_moe, gen_lm, hybrid_moe, latent_moe,
                               window_moe)

SLOTS, PAGE_LEN, BUCKETS = 4, 8, [8, 16, 32]
TURN_COUNTERS = ("gen.decode.steps", "gen.decode.turns_steady",
                 "gen.decode.turns_patched", "gen.decode.host_calls")


def _seeded(module, cfg, export, config_cls, path):
    """A float32 bundle of ``module``'s toy configuration with its
    adapter's seeded weights in place: the six builders' streams differ
    from token to token, which a fresh export's do not."""
    hp = config_cls.from_dict(cfg)
    hp.dtype, hp.max_len = "float32", 64
    export(path, hp, num_slots=SLOTS, prompt_buckets=BUCKETS,
           page_len=PAGE_LEN)
    p = GenPredictor(path)
    for name, value in module.adapter.seeded_weights(cfg, 21).items():
        assert p._scope.find_var(name) is not None, name
        p._scope.set_var(name, jnp.asarray(value, jnp.float32))
    return p


def _gen_lm(path):
    gen_lm.export_gen_model(path, gen_lm.GenConfig(), num_slots=SLOTS)
    return GenPredictor(path)


BUILDERS = {
    "gen_lm": _gen_lm,
    "hybrid_moe": lambda path: _seeded(
        test_hybrid_moe, test_hybrid_moe.toy_config(),
        hybrid_moe.export_hybrid_model, hybrid_moe.HybridConfig, path),
    "latent_moe": lambda path: _seeded(
        test_latent_moe, test_latent_moe.toy_config(),
        latent_moe.export_latent_model, latent_moe.LatentMoEConfig, path),
    "block_moe": lambda path: _seeded(
        test_block_moe, test_block_moe.toy_config(),
        block_moe.export_block_model, block_moe.BlockMoEConfig, path),
    "glm_dsa": lambda path: _seeded(
        test_glm_dsa, test_glm_dsa.toy_config(),
        latent_moe.export_latent_model, latent_moe.LatentMoEConfig, path),
    "window_moe": lambda path: _seeded(
        test_window_moe, test_window_moe.toy_config(),
        window_moe.export_window_model, window_moe.WindowMoEConfig, path),
}


@pytest.fixture(scope="module", params=list(BUILDERS))
def predictor(request, tmp_path_factory):
    p = BUILDERS[request.param](
        str(tmp_path_factory.mktemp(request.param) / "bundle"))
    p.warmup()
    return p


@pytest.fixture(scope="module")
def lm(tmp_path_factory):
    """A ``gen_lm`` predictor of this file's own (page buckets 1, 2, 4)."""
    p = _gen_lm(str(tmp_path_factory.mktemp("turn_lm") / "bundle"))
    p.warmup()
    return p


def _blocking_ref(p):
    """``ref(prompt, n)``: the prefill's token, then the blocking
    ``decode_step`` loop's, the stream alone in slot 0."""
    memo = {}

    def ref(prompt, n):
        key = tuple(prompt)
        if len(memo.get(key, ())) >= n:
            return memo[key][:n]
        logits, kv = p.prefill(prompt)
        out, pos = [int(np.argmax(logits))], len(prompt)
        p.alloc_slot_pages(0, p.pages_needed(pos, n))
        try:
            p.write_slot(0, kv, pos)
            while len(out) < n:
                tokens, at, lens = (np.zeros(p.num_slots, np.int32)
                                    for _ in range(3))
                tokens[0], at[0], lens[0] = out[-1], pos, pos + 1
                out.append(int(np.argmax(
                    p.decode_step(tokens, at, lens=lens)[0])))
                pos += 1
        finally:
            p.free_slot_pages(0)
        memo[key] = out
        return out
    return ref


def _device_state(p):
    """The device's positions, rows and page table, read."""
    _, pos, lens, table = p._dev_state
    return (np.asarray(pos).reshape(-1), np.asarray(lens).reshape(-1),
            np.asarray(table))


class Watched:
    """The predictor, with the device's decode state held to the host's
    mirror after every turn the scheduler dispatches."""

    def __init__(self, predictor):
        self._p, self.turns = predictor, 0

    def __getattr__(self, name):
        return getattr(self._p, name)

    def dispatch_turn(self, tokens, positions, lens):
        p = self._p
        read = p.dispatch_turn(tokens, positions, lens)
        with p._lock:
            pos, rows, table = _device_state(p)
            assert np.array_equal(pos, p._dev_pos)
            assert np.array_equal(rows, p._dev_lens)
            # a slot being admitted chunk by chunk holds pages and no
            # live row yet: its table row travels with the turn that
            # seats it; every other row is the mirror's
            waiting = sorted(p._stale_rows)
            assert all(rows[s] == 0 and s in p._slot_pages for s in waiting)
            assert not waiting or p.prefill_chunks
            sent = np.setdiff1d(np.arange(p.num_slots), waiting)
            assert np.array_equal(table[sent], p._page_table[sent])
        # what the step ran at is what the scheduler asked for
        advance = (np.asarray(lens) > 0).astype(np.int32)
        assert np.array_equal(pos, np.asarray(positions) + advance)
        assert np.array_equal(rows, np.asarray(lens) + advance)
        self.turns += 1
        return read


# -- (a) the scheduler's turns serve the blocking loop's tokens ----------------

def test_streams_are_the_blocking_loops_through_every_kind_of_change(
        predictor):
    """An admission while a step is in flight, an EOS, an ending by
    length and a cancel beside live neighbours, then (the lookahead's
    drill) a migration and an abort: every stream's tokens are the
    blocking ``decode_step`` loop's, and the device's state is the
    mirror's after every turn."""
    prompts = gen_lookahead.PROMPTS
    ref = _blocking_ref(predictor)
    want = [ref(p, n) for p, n in zip(prompts, (12, 12, 30, 10, 6))]
    # the first token of the second stream that it has not produced before
    k = next((i for i in range(1, 12) if want[1][i] not in want[1][:i]),
             None)
    watched = Watched(predictor)
    with gen_lookahead.scheduler(watched, stall=0.02) as (sched, gained):
        ends = sched.submit(prompts[0], max_new_tokens=12)
        stops = sched.submit(prompts[1], max_new_tokens=12,
                             eos_id=None if k is None else want[1][k])
        victim = sched.submit(prompts[2], max_new_tokens=30)
        stays = sched.submit(prompts[3], max_new_tokens=10)
        seen = gen_lookahead.take(victim, 2)
        victim.cancel()
        late = sched.submit(prompts[4], max_new_tokens=6)   # in flight
        assert list(late) == want[4]
        assert list(ends) == want[0]
        assert list(stops) == (want[1] if k is None else want[1][:k + 1])
        assert list(stays) == want[3]
    assert victim.finish_reason == "disconnect"
    assert victim.tokens[:2] == seen
    assert victim.tokens == want[2][:len(victim.tokens)]
    assert ends.finish_reason == "length"
    assert stops.finish_reason == ("length" if k is None else "eos")
    assert gen_lookahead.pool_is_whole(predictor)
    assert watched.turns == gained["gen.decode.steps"] >= 11
    gen_lookahead.drain_and_abort_in_flight(watched, ref)


class Rolled(Watched):
    """The predictor with a slot mix-up planted where a turn's ids come
    back: every slot is handed its neighbour's token."""

    def dispatch_turn(self, tokens, positions, lens):
        return self._p.dispatch_turn(tokens, positions, lens)

    def read_turn(self, read):
        ids, counts = self._p.read_turn(read)
        return ids[1:] + ids[:1], counts


def test_a_slot_mix_up_in_the_read_changes_the_streams(lm):
    """The fault that only several live slots show, planted in
    ``read_turn`` (the pick is inside the turn's executable, so there is
    no ``decode_step`` of the scheduler's to plant it in): streams served
    side by side no longer give the blocking loop's tokens, where the
    same streams through the predictor as it is do."""
    prompts = gen_lookahead.PROMPTS[:3]
    ref = _blocking_ref(lm)
    want = [ref(p, 10) for p in prompts]
    assert len({tuple(w[1:]) for w in want}) > 1    # else nothing to mix up

    def served(predictor):
        with gen_lookahead.scheduler(predictor, stall=0.02) as (sched, _):
            streams = [sched.submit(p, max_new_tokens=10) for p in prompts]
            return [list(s) for s in streams]

    assert served(lm) == want
    mixed = served(Rolled(lm))
    assert [len(t) for t in mixed] == [10, 10, 10]
    assert mixed != want
    assert gen_lookahead.pool_is_whole(lm)


# -- (b) what a turn costs the calling thread ----------------------------------

def test_a_steady_turn_is_one_call_and_one_read(lm, monkeypatch):
    """One stream of 8 tokens: the prefill's, then 7 steps.  The first
    turn carries the admission in its patch; the six after it take
    nothing from the host; the last only reads.  Every turn is ONE
    launch of the compiled turn, no ``jax.device_put`` beside it, and
    one read."""
    launches, puts, turns = [], [], dict(lm._turns)
    for pages, fn in turns.items():
        lm._turns[pages] = (lambda *args, _fn=fn, _pages=pages:
                            (launches.append(_pages), _fn(*args))[1])
    real_put = jax.device_put
    m = profiler.runtime_metrics
    before = {k: m.counter(k) for k in TURN_COUNTERS}
    try:
        with gen_lookahead.scheduler(lm) as (sched, _):
            stream = sched.submit([5, 9, 3, 17], max_new_tokens=8)
            first = gen_lookahead.take(stream, 1)
            monkeypatch.setattr(
                jax, "device_put",
                lambda *a, **kw: (puts.append(a), real_put(*a, **kw))[1])
            rest, _ = gen_lookahead.rest(stream)
    finally:
        monkeypatch.setattr(jax, "device_put", real_put)
        lm._turns.update(turns)
    gained = {k: m.counter(k) - before[k] for k in TURN_COUNTERS}
    assert len(first + rest) == 8
    assert gained == {"gen.decode.steps": 7, "gen.decode.turns_patched": 1,
                      "gen.decode.turns_steady": 6,
                      "gen.decode.host_calls": 14}
    assert len(launches) == 7
    assert puts == []


class _CountsReads:
    """``numpy`` as the predictor's module sees it, with the reads of a
    device array through ``asarray`` listed."""

    def __init__(self, reads):
        self._reads = reads

    def __getattr__(self, name):
        return getattr(np, name)

    def asarray(self, a, *args, **kw):
        if isinstance(a, jax.Array):
            self._reads.append(a.shape)
        return np.asarray(a, *args, **kw)


def test_host_calls_is_the_launches_transfers_and_reads_made(
        lm, monkeypatch):
    """``gen.decode.host_calls`` against the calls themselves, counted
    from outside: a blocking step (a launch, the read of its result and
    of its logits), a patched turn and two steady ones (a launch and a
    read each), and no ``jax.device_put`` among them.  A transfer or a
    read that came back beside ``_host_call`` would part the two."""
    launches, puts, reads, turns = [], [], [], dict(lm._turns)
    for pages, fn in turns.items():
        lm._turns[pages] = (lambda *args, _fn=fn, _pages=pages:
                            (launches.append(_pages), _fn(*args))[1])
    real_put = jax.device_put
    monkeypatch.setattr(
        jax, "device_put",
        lambda *a, **kw: (puts.append(a), real_put(*a, **kw))[1])
    monkeypatch.setattr(predictor_mod, "np", _CountsReads(reads))
    m = profiler.runtime_metrics
    before = m.counter("gen.decode.host_calls")
    S = lm.num_slots
    tokens, at, lens = (np.zeros(S, np.int32) for _ in range(3))
    tokens[1], lens[1] = 5, 1
    lm.alloc_slot_pages(1, 1)
    try:
        lm.decode_step(tokens, at, lens=lens)
        at[1], lens[1] = 1, 2
        lm.read_turn(lm.dispatch_turn(tokens, at, lens))        # patched
        for _ in range(2):                                      # steady
            at[1], lens[1] = at[1] + 1, lens[1] + 1
            lm.read_turn(lm.dispatch_turn(np.full(S, -1, np.int32), at,
                                          lens))
    finally:
        lm.free_slot_pages(1)
        monkeypatch.undo()
        lm._turns.update(turns)
    assert (len(launches), puts, len(reads)) == (4, [], 5)
    assert m.counter("gen.decode.host_calls") - before == 9


def test_the_turn_spans_hang_under_the_step(lm):
    """``gen.dispatch`` is a SIBLING of ``executor.run`` and
    ``gen.collect`` under ``gen.decode_step`` (the readers of the
    executor's phases walk phase -> ``executor.run`` ->
    ``gen.decode_step`` by ``parent_id``) that holds ``executor.run`` in
    time, and says whether the turn was patched."""
    from paddle_tpu.obs import trace
    trace.enable()
    trace.clear()
    try:
        with gen_lookahead.scheduler(lm) as (sched, _):
            assert len(list(sched.submit([5, 9, 3], max_new_tokens=6))) == 6
        spans = trace.snapshot_spans()
    finally:
        trace.disable()
        trace.clear()
    by_id = {s["span_id"]: s for s in spans}
    steps = [s for s in spans if s["name"] == "gen.decode_step"]
    dispatches = [s for s in spans if s["name"] == "gen.dispatch"]
    assert len(dispatches) == 5
    assert [s["attrs"]["patched"] for s in sorted(
        dispatches, key=lambda s: s["ts"])] == [1, 0, 0, 0, 0]
    for d in dispatches:
        step = by_id[d["parent_id"]]
        assert step["name"] == "gen.decode_step"
        (run,) = [s for s in spans if s["name"] == "executor.run"
                  and s["parent_id"] == step["span_id"]]
        assert d["ts"] <= run["ts"]
        assert run["ts"] + run["dur"] <= d["ts"] + d["dur"] + 1e-9
        phases = {s["name"] for s in spans if s["parent_id"] == run["span_id"]}
        assert phases == {"executor.feed", "executor.dispatch",
                          "executor.fetch"}
    assert sum(1 for s in spans if s["name"] == "gen.collect"
               and by_id[s["parent_id"]] in steps) == 5


# -- (c) a block bundle's view of a step, on the device and on the host --------

@pytest.mark.parametrize("block", [2, 4])
def test_the_block_view_of_the_device_is_the_hosts(block):
    """``_block_view`` over traced integers against ``_block_end``'s
    arithmetic on the host, at every position modulo the block, beside a
    free slot."""
    positions = np.arange(0, 3 * block + 1, dtype=np.int32)
    lens = positions + 1
    lens[-1] = 0                                    # a free slot
    fed = -(-lens // block) * block                 # GenPredictor._block_end
    fused = (positions + 1 == fed) & (lens > 0)
    got = jax.jit(lambda p, n: predictor_mod._block_view(p, n, block))(
        jnp.asarray(positions), jnp.asarray(lens))
    host = predictor_mod._block_view(positions, lens, block)
    for dev, here, want in zip(got, host, (fed, fused, fed + block * fused)):
        assert np.array_equal(np.asarray(dev), want)
        assert np.array_equal(here, want)
    assert fused[block - 1] and not fused[block] and not fused[-1]


def test_a_block_bundles_device_state_gives_the_hosts_view(tmp_path):
    """Blocking steps through a whole block and into the next: the view
    of the NEXT step from the device's advanced state is the view from
    the host's mirror, ``_block_end``'s."""
    p = BUILDERS["block_moe"](str(tmp_path / "bundle"))
    L = p.block_length
    prompt = gen_lookahead.PROMPTS[0]
    logits, kv = p.prefill(prompt)
    tok, pos = int(np.argmax(logits)), len(prompt)
    p.alloc_slot_pages(1, p.pages_needed(pos, 3 * L))
    try:
        p.write_slot(1, kv, pos)
        seen = set()
        for _ in range(2 * L + 1):
            tokens, at, lens = (np.zeros(p.num_slots, np.int32)
                                for _ in range(3))
            tokens[1], at[1], lens[1] = tok, pos, pos + 1
            tok = int(np.argmax(p.decode_step(tokens, at, lens=lens)[1]))
            pos += 1
            dev_pos, dev_lens, _ = _device_state(p)
            assert dev_pos[1] == pos and dev_lens[1] == pos + 1
            dev = predictor_mod._block_view(dev_pos, dev_lens, L)
            host = predictor_mod._block_view(p._dev_pos, p._dev_lens, L)
            for a, b in zip(dev, host):
                assert np.array_equal(a, b)
            assert dev[0][1] == p._block_end(pos + 1)
            assert dev[2][1] == dev[0][1] + L * ((pos + 1) % L == 0)
            seen.add(pos % L)
        assert seen == set(range(L))
    finally:
        p.free_slot_pages(1)


# -- (d) nothing compiles after the warm-up ------------------------------------

@pytest.mark.parametrize("capture", ["1", "0"], ids=["aot", "jit"])
def test_no_turn_compiles_after_the_warm_up(tmp_path, monkeypatch, capture):
    """Steady and patched turns and blocking steps in every page bucket,
    through the compile records' executables (``PADDLE_TPU_PERF`` on, the
    default) and through plain ``jax.jit``: no compile event, and the jit
    caches keep the entries the warm-up made."""
    monkeypatch.setenv("PADDLE_TPU_PERF", capture)
    profiler.install_jax_compile_listeners()
    p = _gen_lm(str(tmp_path / "bundle"))
    p.warmup()
    assert sorted(p._turns) == p.page_buckets and len(p.page_buckets) > 1
    sizes = {pages: fn._cache_size() for pages, fn in p._turns.items()
             if hasattr(fn, "_cache_size")}
    assert (capture == "0") == bool(sizes)
    events = profiler.runtime_metrics.counter("compile.events")
    m = profiler.runtime_metrics
    before = {k: m.counter(k) for k in TURN_COUNTERS}
    p.alloc_slot_pages(2, p.pages_per_slot)
    S = p.num_slots
    try:
        for pages in p.page_buckets:
            start = (pages - 1) * p.page_len + 1    # the bucket's first row
            tokens, at, lens = (np.zeros(S, np.int32) for _ in range(3))
            tokens[2], at[2], lens[2] = 7, start - 1, start
            assert p._page_bucket(lens) == pages
            p.decode_step(tokens, at, lens=lens)            # every row
            at[2], lens[2] = start, start + 1
            ids, _ = p.read_turn(p.dispatch_turn(tokens, at, lens))
            for _ in range(3):                              # steady
                at[2], lens[2] = at[2] + 1, lens[2] + 1
                ids, _ = p.read_turn(p.dispatch_turn(
                    np.full(S, -1, np.int32), at, lens))
            assert len(ids) == S
    finally:
        p.free_slot_pages(2)
    gained = {k: m.counter(k) - before[k] for k in TURN_COUNTERS}
    n = len(p.page_buckets)
    # a blocking step's rows are all in its patch; the turn after it is
    # steady but for the token the host sets
    assert gained["gen.decode.turns_patched"] == n
    assert gained["gen.decode.turns_steady"] == 3 * n
    # a blocking step launches, reads its result and reads the logits
    assert gained["gen.decode.host_calls"] == (3 + 2 * 4) * n
    assert profiler.runtime_metrics.counter("compile.events") == events
    assert sizes == {pages: fn._cache_size() for pages, fn
                     in p._turns.items() if hasattr(fn, "_cache_size")}


def test_a_replaced_weight_is_let_go_of_at_once(tmp_path):
    """The turn keeps the arrays it resolved, but not past a write of the
    scope: a weight replaced there (a reload, the rig's
    ``install_weights``) is held by nothing of the compiled step from
    that moment, with no further turn, so old and new never both stay on
    the device; the step's own write-back of the pools keeps what it
    resolved."""
    import gc
    import weakref
    p = _gen_lm(str(tmp_path / "bundle"))
    tokens, at, lens = (np.zeros(p.num_slots, np.int32) for _ in range(3))
    tokens[0], lens[0] = 5, 1
    p.alloc_slot_pages(0, 1)
    try:
        p.decode_step(tokens, at, lens=lens)
        ro, inout = p._step._state
        assert len(ro) == len(p._step.ro_names) and inout
        name = p._step.ro_names[0]
        old = weakref.ref(p._scope.find_var(name))
        assert any(a is old() for a in ro)
        del ro, inout
        p._scope.set_var(name, old() * 0)
        gc.collect()
        assert p._step._state is None and old() is None
    finally:
        p.free_slot_pages(0)


def test_a_weight_load_reaches_the_next_turn(tmp_path):
    """The turn keeps the arrays it resolved from the scope and looks
    again when the scope was written: a parameter replaced between two
    steps is the one the next step computes with."""
    p = _gen_lm(str(tmp_path / "bundle"))
    tokens, at, lens = (np.zeros(p.num_slots, np.int32) for _ in range(3))
    tokens[0], lens[0] = 5, 1
    p.alloc_slot_pages(0, 1)
    try:
        first = p.decode_step(tokens, at, lens=lens)
        again = p.decode_step(tokens, at, lens=lens)
        assert np.array_equal(first, again)
        name = next(n for n in p._step.ro_names
                    if np.asarray(p._scope.find_var(n)).ndim == 2)
        old = p._scope.find_var(name)
        p._scope.set_var(name, old * 0)
        changed = p.decode_step(tokens, at, lens=lens)
        assert not np.array_equal(first, changed)
        p._scope.set_var(name, old)
        assert np.array_equal(first, p.decode_step(tokens, at, lens=lens))
    finally:
        p.free_slot_pages(0)
