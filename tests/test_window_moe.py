"""Sliding-window layers with a sink beside full-attention layers on the
paged serving path (``ops/window_ops.py``, ``models/window_moe.py``): the
exported bundle (prefill, the compiled seed of pages AND rings, cached
decode steps over both kinds of cache) against the plain reference's
full forward (``benchmark/reference/mimo_v2_flash_ref.py``), each
mechanism caught when it is dropped, the share arithmetic, a ring's
bytes whatever the stream's length, the contract and the rules.  (The
kernels against their composed forms, the ops' type rules and the
published keys take no bundle and live in ``tests/test_window_ops.py``,
a file of its own so that another worker of ``--dist loadfile`` takes
them.)  Toy widths: d 64, 4 heads of 24 / 16 over 1 (full) or 2
(window) K/V heads, window 8 = ring 8, 8 of 24 lanes rotated, the
published pattern's first seven layers (full + dense, four window, full,
window), contexts of 5-40 rows."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import profiler
from paddle_tpu.analysis import cost
from paddle_tpu.gen import GenPredictor
from paddle_tpu.models import decoder, window_moe
from paddle_tpu.ops import moe_ops

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from models import mimo_v2_flash as adapter             # noqa: E402
from reference import mimo_v2_flash_ref as ref          # noqa: E402

SLOTS, PAGE_LEN, BUCKETS, WINDOW = 3, 8, [8, 16, 32, 48], 8
TOL = 2e-4          # float32 program against the float32 reference


def toy_config(**over):
    cfg = {"hidden_size": 64, "num_hidden_layers": 7, "layer_offset": 0,
           "vocab_size": 64, "layernorm_epsilon": 1e-5,
           "num_attention_heads": 4, "num_key_value_heads": 1,
           "head_dim": 24, "v_head_dim": 16, "rope_theta": 5000000,
           "swa_num_attention_heads": 4, "swa_num_key_value_heads": 2,
           "swa_head_dim": 24, "swa_v_head_dim": 16, "swa_rope_theta": 10000,
           "sliding_window": WINDOW, "partial_rotary_factor": 0.334,
           "attention_value_scale": 0.707,
           "add_swa_attention_sink_bias": True,
           "add_full_attention_sink_bias": False,
           "hybrid_layer_pattern": [0, 1, 1, 1, 1, 0, 1, 1, 1, 1, 1, 0],
           "moe_layer_freq": [0] + [1] * 11,
           "intermediate_size": 96, "moe_intermediate_size": 32,
           "n_routed_experts": 16, "num_experts_per_tok": 2,
           "routed_scaling_factor": None, "norm_topk_prob": True,
           "experts_held": 16, "expert_offset": 0}
    cfg.update(over)
    return cfg


def _hp(cfg, dtype="float32", max_len=64):
    hp = window_moe.WindowMoEConfig.from_dict(cfg)
    hp.dtype, hp.max_len = dtype, max_len
    return hp


def _install(predictor, weights):
    for name, value in weights.items():
        old = predictor._scope.find_var(name)
        assert old is not None and tuple(old.shape) == tuple(value.shape), \
            name
        predictor._scope.set_var(name, value)


def _weights(cfg):
    # the seeded bfloat16 VALUES held in float32; the router's offset row is
    # taken out (it is made for the published widths), the sinks sit
    # where a toy window's summed weights do and the correction bias is
    # wide enough to change which experts a row takes
    w = {k: v.astype(jnp.float32)
         for k, v in adapter.seeded_weights(cfg, 7).items()}
    for i in adapter.moe_layers(cfg):
        w[f"win{i}_gate.w"] = w[f"win{i}_gate.w"].at[0].set(0.0)
        w[f"win{i}_gate.bias"] = jax.random.uniform(
            jax.random.PRNGKey(i), (16,), jnp.float32, -0.3, 0.3)
    for i in adapter.window_layers(cfg):
        w[f"win{i}_sink"] = w[f"win{i}_sink"] - 2.0
    return w


@pytest.fixture(scope="module", params=[24, 32], ids=["stored24", "stored32"])
def cfg(request):
    """Key heads stored as they are, and padded to 32 lanes."""
    return toy_config(key_head_stored=request.param)


@pytest.fixture(scope="module")
def weights(cfg):
    return _weights(cfg)


@pytest.fixture(scope="module")
def predictor(tmp_path_factory, cfg, weights):
    path = str(tmp_path_factory.mktemp("win") / "bundle")
    window_moe.export_window_model(path, _hp(cfg), num_slots=SLOTS,
                                   prompt_buckets=BUCKETS,
                                   page_len=PAGE_LEN)
    p = GenPredictor(path)
    _install(p, weights)
    p.warmup()
    p.bundle_dir = path
    return p


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(1, 64, size=n).tolist()


def _ref_logits(weights, cfg, ids, positions, **kw):
    return np.asarray(ref.forward_logits(
        weights, cfg, jnp.asarray(ids, jnp.int32),
        jnp.asarray(positions, jnp.int32), **kw))


def _err(got, want):
    return float(np.abs(np.asarray(got) - want).max()) \
        / float(want.max() - want.min())


def _step(predictor, live):
    tokens, pos, lens = (np.zeros(SLOTS, np.int32) for _ in range(3))
    for slot, (tok, rows) in live.items():
        tokens[slot], pos[slot], lens[slot] = tok, rows, rows + 1
    return predictor.decode_step(tokens, pos, lens=lens)


def _admit(predictor, slot, prompt, horizon=24):
    logits, kv = predictor.prefill(prompt)
    predictor.alloc_slot_pages(slot, predictor.pages_needed(len(prompt),
                                                            horizon))
    assert predictor.write_slot(slot, kv, len(prompt)) == 0
    return logits


def _decode(predictor, slot, ids, steps):
    """``steps`` cached steps of ``slot`` fed ``ids``' own tokens; yields
    each step's logits row."""
    n = len(ids) - steps
    for t in range(n, n + steps):
        yield t, _step(predictor, {slot: (ids[t], t)})[slot]


# -- prefill and cached decode against the reference ------------------------------

@pytest.mark.parametrize("n", [5, 8, 13, 29])
def test_prefill_then_steps_through_both_caches_match_the_reference(
        predictor, weights, cfg, n):
    """Prompts shorter than, equal to and longer than the window, then
    18 cached steps: more than two wraps of the 8-row ring."""
    steps = 18
    ids = _prompt(n + steps, seed=n)
    want = _ref_logits(weights, cfg, ids, list(range(n - 1, n + steps)))
    try:
        logits = _admit(predictor, 1, ids[:n])
        assert _err(logits, want[0]) < TOL
        for t, row in _decode(predictor, 1, ids, steps):
            assert _err(row, want[t - n + 1]) < TOL, t
    finally:
        predictor.free_slot_pages(1)


def test_a_freed_and_reseeded_slot_reads_no_stale_ring_row(predictor,
                                                           weights, cfg):
    """A long stream fills slot 0's rings and pages; the slot is freed
    and a SHORT prompt seeded there: its steps see its own 5-9 rows."""
    long_ids = _prompt(40, seed=3)
    ids = _prompt(9, seed=4)
    want = _ref_logits(weights, cfg, ids, list(range(4, 9)))
    try:
        _admit(predictor, 0, long_ids[:30])
        for _ in _decode(predictor, 0, long_ids, 10):
            pass
        predictor.free_slot_pages(0)
        predictor.clear_slot(0)
        assert _err(_admit(predictor, 0, ids[:5]), want[0]) < TOL
        for t, row in _decode(predictor, 0, ids, 4):
            assert _err(row, want[t - 4]) < TOL
    finally:
        predictor.free_slot_pages(0)


def test_two_slots_of_different_lengths_keep_their_own_rings(predictor,
                                                             weights, cfg):
    a, b = _prompt(21, seed=5), _prompt(7, seed=6)
    want_a = _ref_logits(weights, cfg, a, [20])
    want_b = _ref_logits(weights, cfg, b, [6])
    try:
        _admit(predictor, 0, a[:20])
        _admit(predictor, 2, b[:6])
        out = _step(predictor, {0: (a[20], 20), 2: (b[6], 6)})
        assert _err(out[0], want_a[0]) < TOL
        assert _err(out[2], want_b[0]) < TOL
    finally:
        predictor.free_slot_pages(0)
        predictor.free_slot_pages(2)


# -- each mechanism is caught when it is dropped -----------------------------------

def _swap_kv_heads(weights, cfg):
    """Every window layer's second K/V head made a copy of its first:
    what a program computes that reads one K/V head for all query
    heads."""
    w = dict(weights)
    for i in adapter.window_layers(cfg):
        for name, width in (("k.w", 24), ("v.w", 16)):
            m = w[f"win{i}_{name}"]
            w[f"win{i}_{name}"] = m.at[:, width:].set(m[:, :width])
    return w


DROPPED = {
    "window": dict(kw=dict(window=False)),
    "sink": dict(kw=dict(sink=False)),
    "rotary": dict(kw=dict(rotary=False)),
    "partial_rotary": dict(cfg=dict(partial_rotary_factor=1.0)),
    "window_theta": dict(cfg=dict(swa_rope_theta=5000000)),
    "full_theta": dict(cfg=dict(rope_theta=10000)),
    "value_scale": dict(cfg=dict(attention_value_scale=1.0)),
    "correction_bias": dict(kw=dict(bias=False)),
    "kv_heads_by_kind": dict(weights=_swap_kv_heads),
}


@pytest.mark.parametrize("what", sorted(DROPPED))
def test_the_reference_without_a_mechanism_is_not_the_program(
        predictor, weights, cfg, what):
    """The program's prefill and a cached step agree with the reference
    and DISAGREE, by far more than the tolerance, with the reference
    that drops one mechanism: the window, the sink, the rotary, its
    share of a head, either theta, the value scale, the router's
    correction bias, the K/V heads of a window layer."""
    n = 30
    ids = _prompt(n + 1, seed=11)
    drop = DROPPED[what]
    other = _ref_logits(
        drop["weights"](weights, cfg) if "weights" in drop else weights,
        dict(cfg, **drop.get("cfg", {})), ids, [n - 1, n],
        **drop.get("kw", {}))
    want = _ref_logits(weights, cfg, ids, [n - 1, n])
    try:
        got = [_admit(predictor, 1, ids[:n]),
               _step(predictor, {1: (ids[n], n)})[1]]
    finally:
        predictor.free_slot_pages(1)
    for j in range(2):
        assert _err(got[j], want[j]) < TOL
        assert _err(got[j], other[j]) > 50 * TOL, (what, j)


# -- a prompt as a run of chunks against the same prompt in one -----------------

@pytest.fixture(scope="module")
def chunked(tmp_path_factory, cfg, weights):
    """The bundle with chunk rungs of 8 and 16 rows (``predictor``'s are
    24 and 48: every prompt here is ONE chunk there): a prompt of 45
    rows is three chunks, wraps the 8-row ring twice inside each, and
    walks page buckets of 2, 4 and 8 pages."""
    path = str(tmp_path_factory.mktemp("win") / "chunked")
    was, decoder.CHUNK_ROWS = decoder.CHUNK_ROWS, 16
    try:
        window_moe.export_window_model(path, _hp(cfg), num_slots=SLOTS,
                                       prompt_buckets=BUCKETS,
                                       page_len=PAGE_LEN)
    finally:
        decoder.CHUNK_ROWS = was
    p = GenPredictor(path)
    assert p.prefill_chunks == [8, 16]
    _install(p, weights)
    p.warmup()
    return p


@pytest.mark.parametrize("n", [5, 16, 17, 29, 45], ids=[
    "one_chunk", "a_chunk_edge", "a_row_past_it", "the_ring_wraps",
    "every_page_bucket"])
def test_a_prompt_in_chunks_is_the_prompt_in_one(predictor, chunked, n):
    """The last row's logits, every full layer's page rows, every ring
    and the next cached step's logits, chunk by chunk (8- and 16-row
    rungs) against the single pass (one 24- or 48-row chunk)."""
    prompt = _prompt(n, seed=100 + n)
    spans = chunked.chunk_spans(n)
    assert len(spans) == -(-n // 16) and len(predictor.chunk_spans(n)) == 1
    assert [chunked._chunk_shape(a, b - a) for a, b in spans][-1] \
        == (8 if (n - 1) % 16 < 8 else 16,
            next(p for p in (1, 2, 4, 8) if p * PAGE_LEN >= n))
    whole, parts = predictor.prefill(prompt), chunked.prefill(prompt)
    assert np.abs(parts[0] - whole[0]).max() < 2e-5 * np.ptp(whole[0])
    assert len(parts[1]) == len(whole[1]) == 2 * 2 + 2 * 5
    # (a ring row no position of this prompt landed in keeps what the
    # borrowed slot held before: nothing reads it)
    landed = sorted({p % WINDOW for p in range(max(n - WINDOW, 0), n)})
    for j, (got, want) in enumerate(zip(parts[1], whole[1])):
        assert got.shape == want.shape
        rows = slice(None) if j < 4 else landed
        assert np.allclose(got[0, rows], want[0, rows], atol=2e-5)
    # pages hold the prompt's rows and zeros behind; a ring the last 8
    assert np.asarray(parts[1][0])[0, :n].any(axis=-1).all()
    assert not np.asarray(parts[1][0])[0, n:].any()
    tok, steps = int(np.argmax(whole[0])), []
    for p, (_, kv) in ((predictor, whole), (chunked, parts)):
        p.alloc_slot_pages(1, p.pages_needed(n, 2))
        try:
            assert p.write_slot(1, kv, n) == 0
            steps.append(_step(p, {1: (tok, n)})[1])
        finally:
            p.free_slot_pages(1)
    assert np.abs(steps[1] - steps[0]).max() < 2e-5 * np.ptp(steps[0])
    # nothing was left allocated by either borrowing prefill
    assert chunked.free_pages == chunked.num_pages


def test_streams_admitted_in_chunks_decode_where_the_chunks_wrote(
        predictor, chunked, weights, cfg):
    """Through the scheduler nothing seeds the slot: the chunks write
    pages and rings, the decode steps read them.  Three streams of 45,
    17 and 5 rows, admitted beside each other, emit the tokens of the
    reference's greedy forward; one traced admission of N chunks yields
    N ``gen.prefill`` spans whose pairs sum to the prompt's."""
    from paddle_tpu.gen import GenScheduler
    from paddle_tpu.obs import trace as ptrace
    names = ["gen.prefill.chunks", "gen.prefill.rows", "gen.prefill.pad_rows",
             "gen.prefill.admissions_chunked", "gen.seed.compiled_calls"]
    before = [profiler.runtime_metrics.counter(n) for n in names]
    prompts = [_prompt(n, seed=200 + n) for n in (45, 17, 5)]
    sched = GenScheduler(chunked)
    ptrace.enable(1 << 14)
    ptrace.clear()
    try:
        streams = []
        for i, p in enumerate(prompts):
            with ptrace.trace_context(f"request-{i}"):
                streams.append(sched.submit(p, max_new_tokens=5))
        served = [list(s) for s in streams]
        spans = ptrace.snapshot_spans()
    finally:
        ptrace.disable()
        sched.close()
    for prompt, tokens in zip(prompts, served):
        ids = prompt + tokens
        want = _ref_logits(weights, cfg, ids,
                           list(range(len(prompt) - 1, len(ids) - 1)))
        assert tokens == [int(t) for t in np.argmax(want, axis=-1)]
    after = [profiler.runtime_metrics.counter(n) for n in names]
    # 3 + 2 + 1 chunks; 45 = 16 + 16 + 13 (of 16), 17 = 16 + 1 (of 8),
    # 5 (of 8): 67 real rows and 3 + 7 + 3 pads; and no compiled seed
    assert [b - a for a, b in zip(before, after)] == [6, 67, 13, 3, 0]
    by_trace = {}
    for s in spans:
        if s["name"] == "gen.prefill":
            by_trace.setdefault(s["trace_id"], []).append(s["attrs"])
    chunks = by_trace[streams[0].trace_id]
    assert [(c["start"], c["tokens"], c["rows"]) for c in chunks] \
        == [(0, 16, 16), (16, 16, 16), (32, 13, 16)]
    assert sum(c["causal_pairs"] for c in chunks) == 45 * 46 // 2
    assert sum(c["band_pairs"] for c in chunks) == 36 + (45 - 8) * 8
    assert [c["pages"] for c in chunks] == [2, 4, 8]
    admits = [s for s in spans if s["name"] == "gen.admit"]
    assert sorted(s["attrs"]["chunks"] for s in admits) == [1, 2, 3]
    seeds = [s["attrs"] for s in spans if s["name"] == "gen.seed_slot"]
    assert [a["compiled_calls"] for a in seeds] == [0, 0, 0]
    assert chunked.free_pages == chunked.num_pages


# -- two kinds of cache in one bundle ----------------------------------------------

def test_a_ring_is_a_constant_of_the_bundle_and_the_pool_follows_max_len(
        cfg):
    """A window layer's cache bytes are the same at ``max_len`` 16 and
    16,000; the full layers' pool alone scales."""
    shapes = {}
    for max_len in (16, 16000):
        hp = _hp(cfg, max_len=max_len)
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            window_moe.build_paged_decode_program(
                hp, SLOTS, PAGE_LEN, SLOTS * -(-max_len // PAGE_LEN))
        block = main.global_block()
        shapes[max_len] = {n: tuple(block.var(n).shape) for n in
                           window_moe.paged_cache_var_names(hp)
                           + window_moe.ring_var_names(hp)}
    short, long_ = shapes[16], shapes[16000]
    stored = cfg["key_head_stored"]
    assert short["win1_ring_k"] == long_["win1_ring_k"] \
        == (SLOTS, 8, 2 * stored)
    assert short["win6_ring_v"] == long_["win6_ring_v"] == (SLOTS, 8, 32)
    assert short["win0_paged_k"] == (SLOTS * 2, PAGE_LEN, stored)
    assert long_["win5_paged_v"] == (SLOTS * 2000, PAGE_LEN, 16)
    assert sorted(short) == sorted(
        [f"win{i}_paged_{r}" for i in (0, 5) for r in "kv"]
        + [f"win{i}_ring_{r}" for i in (1, 2, 3, 4, 6) for r in "kv"])


def test_the_predictor_prices_and_counts_the_two_kinds_apart(predictor):
    from paddle_tpu.obs import trace as ptrace
    stored = predictor.meta["window_attention"]["row_bytes"][0] // 4 - 32
    win = predictor.window_attention
    assert win["layers"] == [1, 2, 3, 4, 6] and win["full_layers"] == [0, 5]
    assert (win["window"], win["ring"]) == (8, 8)
    assert predictor.cache_vars == [f"win{i}_paged_{r}" for i in (0, 5)
                                    for r in "kv"]
    assert predictor.state_vars == win["ring_vars"]
    # pages are the FULL layers' alone: 2 x (1 head x stored + 16) floats
    assert predictor.cache_row_bytes == 2 * (stored // 2 + 16) * 4
    assert predictor.pages_needed(10, 30) == 5
    assert predictor.ring_bytes() == SLOTS * 8 * 5 * (stored + 32) * 4
    assert profiler.runtime_metrics.gauge("gen.window.ring_bytes") \
        == predictor.ring_bytes()
    names = ["gen.window.rows_read", "gen.window.rows_saved"]
    before = [profiler.runtime_metrics.counter(n) for n in names]
    logits = _admit(predictor, 0, _prompt(5, seed=41))
    _admit(predictor, 1, _prompt(30, seed=42))
    ptrace.enable(1 << 10)
    ptrace.clear()
    try:
        _step(predictor, {0: (int(np.argmax(logits)), 5), 1: (3, 30)})
        predictor.prefill(_prompt(20, seed=43))
        spans = ptrace.snapshot_spans()
    finally:
        ptrace.disable()
        predictor.free_slot_pages(0)
        predictor.free_slot_pages(1)
    after = [profiler.runtime_metrics.counter(n) for n in names]
    # slots of 6 and 31 rows: five window layers read 6 + 8, two full
    # layers all 37
    assert [b - a for a, b in zip(before, after)] == [5 * 14, 5 * 23]
    step = next(s for s in spans if s["name"] == "gen.decode_step")
    assert step["attrs"]["full_rows"] == 2 * 37
    assert step["attrs"]["window_rows"] == 5 * 14
    assert step["attrs"]["ring_bytes"] == 14 * 5 * (stored + 32) * 4
    pre = next(s for s in spans if s["name"] == "gen.prefill")
    # 20 rows: 1 + ... + 8 + 12 x 8 pairs in the band, 210 under the
    # diagonal; toy buckets run the composed form (no key block)
    assert pre["attrs"]["band_pairs"] == 36 + 96
    assert pre["attrs"]["causal_pairs"] == 210
    assert pre["attrs"]["band_key_blocks"] == 0
    assert pre["attrs"]["causal_key_blocks"] == 0
    assert (pre["attrs"]["window_layers"], pre["attrs"]["full_layers"]) \
        == (5, 2)


def test_the_scheduler_says_how_many_ring_rows_an_admission_writes(
        predictor):
    from paddle_tpu.gen import GenScheduler
    from paddle_tpu.obs import trace as ptrace
    sched = GenScheduler(predictor)
    ptrace.enable(1 << 12)
    ptrace.clear()
    try:
        for n in (5, 20):
            stream = sched.submit(_prompt(n, seed=n), max_new_tokens=4)
            assert len(list(stream)) == 4
        spans = ptrace.snapshot_spans()
    finally:
        ptrace.disable()
        sched.close()
    seeds = [s["attrs"] for s in spans if s["name"] == "gen.seed_slot"]
    assert [a["ring_rows"] for a in seeds] == [5 * 5, 5 * 8]
    steps = [s["attrs"] for s in spans if s["name"] == "gen.decode_step"
             and "window_rows" in s["attrs"]]
    assert steps and all(a["full_rows"] >= a["window_rows"] * 2 // 5
                         for a in steps)


# -- the contract and the rules -----------------------------------------------------

def test_the_bundle_checks_and_every_new_op_has_its_rules(predictor):
    from paddle_tpu.analysis import check_gen_bundle, typecheck
    from paddle_tpu.analysis.analyzer import lint_program
    from paddle_tpu.analysis.distributed import load_saved_program
    new = {"rope_partial", "window_attention", "window_attention_step",
           "gqa_flash_attention_chunk"}
    assert new <= set(typecheck._RULES) and new <= cost.covered_op_types()
    assert "gqa_flash_attention" in cost.covered_op_types()
    bundle_dir = predictor.bundle_dir
    pre = load_saved_program(os.path.join(bundle_dir, "prefill"))
    dec = load_saved_program(os.path.join(bundle_dir, "decode"))
    with open(os.path.join(bundle_dir, "gen_meta.json")) as f:
        meta = json.load(f)
    assert check_gen_bundle(pre, dec, meta) == []
    seen = set()
    for prog, feeds, fetches in (pre, dec):
        result = lint_program(prog, feed_names=feeds, fetch_names=fetches)
        assert not result.errors, [d.message for d in result.errors]
        seen |= {op.type for op in prog.global_block().ops}
    assert new | {"paged_attention"} <= seen
    # a step's window layers are priced by the window, its full layers by
    # the live rows: 4 heads x (stored + 16) x 2 FLOPs a row read
    stored = dec[0].global_block().var("win1_ring_k").shape[-1] // 2

    def flops(live, op_type):
        return cost.estimate(dec[0], paged_live_rows=live) \
            .by_op_type()[op_type]["flops"]

    a_row = 2 * 4 * (stored + 16) * SLOTS
    assert flops(5, "window_attention_step") == 5 * 5 * a_row
    assert flops(10, "window_attention_step") \
        == flops(10000, "window_attention_step") == 5 * 8 * a_row
    assert flops(40, "paged_attention") == 2 * 40 * a_row
    assert flops(20, "paged_attention") * 2 == flops(40, "paged_attention")
    assert not cost.estimate(dec[0], paged_live_rows=24).uncovered
    # a chunk of the prefill: a band grows with its rows, a full layer's
    # pairs with its rows AND the rows of the page bucket under them
    block = pre[0].global_block()

    def chunk_report(rows, pages):
        return cost.estimate_at(pre[0], {
            n: [d if d >= 0 else pages if n == "gen_page_table" else rows
                for d in block.var(n).shape] for n in pre[1]})

    def chunk_flops(rows, pages, op_type):
        return chunk_report(rows, pages).by_op_type()[op_type]["flops"]

    a_pair = 2 * 4 * (stored + 16)
    assert chunk_flops(32, 4, "window_attention") == 5 * 32 * 8 * a_pair
    assert chunk_flops(64, 8, "window_attention") == 5 * 64 * 8 * a_pair
    assert chunk_flops(32, 4, "gqa_flash_attention_chunk") \
        == 2 * (32 * 33 // 2) * a_pair
    assert chunk_flops(16, 6, "gqa_flash_attention_chunk") \
        == 2 * (16 * 17 // 2 + 16 * 32) * a_pair
    # and it is charged the rows it touches (its own twice, the page
    # bucket's once), not the pools whole
    report = chunk_report(16, 6)
    assert report.by_op_type()["gqa_flash_attention_chunk"]["bytes"] == 2 * 4 \
        * (16 * 4 * (stored + 16) + (2 * 16 + 48) * (stored + 16))
    # the predictor prices a chunk by (rung, page bucket) from the same
    # rules, and a prompt by its chunks
    assert predictor.prefill_chunks == meta["prefill_chunks"] == [24, 48]
    assert predictor._chunk_shape(0, 20) == predictor._chunk_shape(0, 24) \
        == (24, 4)
    assert predictor.chunk_cost(0, 20) == predictor.chunk_cost(0, 24) \
        == float(chunk_report(24, 4).total_flops)
    # (a rung runs over no fewer pages than its own rows take, and no
    # chunk ends past the longest prompt: nothing else is warmed)
    assert predictor._chunk_shape(0, 30) == (48, 8)
    assert predictor.chunk_cost(0, 30) \
        == float(chunk_report(48, 8).total_flops)
    assert predictor._chunk_shapes() == [(24, 4), (24, 8), (48, 8)]
    with pytest.raises(ValueError, match="max prompt length"):
        predictor._chunk_shape(24, 30)
    assert predictor.prefill_cost(40) == predictor.chunk_cost(0, 40)


@pytest.mark.parametrize("fault", ["paged_by_length", "not_a_state",
                                   "ring_too_short", "no_entry"])
def test_a_window_layer_paged_by_the_streams_length_is_refused(predictor,
                                                               fault):
    from paddle_tpu.analysis import check_gen_bundle
    from paddle_tpu.analysis.distributed import load_saved_program
    bundle_dir = predictor.bundle_dir
    pre = load_saved_program(os.path.join(bundle_dir, "prefill"))
    dec = load_saved_program(os.path.join(bundle_dir, "decode"))
    with open(os.path.join(bundle_dir, "gen_meta.json")) as f:
        meta = json.load(f)
    ring = "win2_ring_k"
    if fault == "paged_by_length":
        meta["cache_vars"] = meta["cache_vars"] + [ring]
        meta["state_vars"] = [n for n in meta["state_vars"] if n != ring]
        want = "grow with the stream"
    elif fault == "not_a_state":
        meta["state_vars"] = [n for n in meta["state_vars"] if n != ring]
        want = "neither seeded nor cleared"
    elif fault == "ring_too_short":
        var = dec[0].global_block().var(ring)
        var.shape = (var.shape[0], WINDOW - 1, var.shape[2])
        want = "ring >= window 8"
    else:
        del meta["window_attention"]
        want = "no window_attention entry"
    found = [d.message for d in check_gen_bundle(pre, dec, meta)
             if d.code == "PTA019"]
    assert any(want in m for m in found), found


@pytest.mark.parametrize("fault", ["not_whole_pages", "fetches_rows",
                                   "no_slot_feed", "another_pool"])
def test_a_chunk_prefill_that_cannot_continue_a_slot_is_refused(predictor,
                                                                fault):
    from paddle_tpu.analysis import check_gen_bundle
    from paddle_tpu.analysis.distributed import load_saved_program
    bundle_dir = predictor.bundle_dir
    prog, feeds, fetches = load_saved_program(
        os.path.join(bundle_dir, "prefill"))
    dec = load_saved_program(os.path.join(bundle_dir, "decode"))
    with open(os.path.join(bundle_dir, "gen_meta.json")) as f:
        meta = json.load(f)
    if fault == "not_whole_pages":
        meta["prefill_chunks"] = [12, 48]
        want = "multiples of page_len 8"
    elif fault == "fetches_rows":
        fetches = list(fetches) + ["win0_paged_k"]
        want = "not the logits alone"
    elif fault == "no_slot_feed":
        feeds = [n for n in feeds if n != "gen_slot"]
        want = "does not feed `gen_slot`"
    else:
        var = prog.global_block().var("win0_paged_v")
        var.shape = (var.shape[0] + 1,) + tuple(var.shape[1:])
        want = "would not share one array"
    found = [d.message for d in check_gen_bundle((prog, feeds, fetches), dec,
                                                 meta) if d.code == "PTA019"]
    assert any(want in m for m in found), found


# -- the share, the zoo, the published keys -----------------------------------------

def test_the_shares_of_one_layer_add_up_to_the_uncut_layer(weights):
    """The routed parts of all four shares are the uncut reference's
    layer (there is no shared expert to count once)."""
    full = toy_config()
    h = jax.random.normal(jax.random.PRNGKey(3), (9, 64))
    p = lambda name, cast=True: weights[f"win1_{name}"]
    want = ref.moe(h, p, full, jnp.float32)
    idx, w = moe_ops.moe_route(h, p("gate.w"), p("gate.bias"), 2, 1.0, True)
    total, landed = 0.0, 0
    for share in range(4):
        sl = slice(4 * share, 4 * share + 4)
        part, stats = moe_ops.moe_experts_gated(
            h, idx, w, p("wg")[sl], p("wu")[sl], p("wd")[sl],
            expert_offset=4 * share, routed=True)
        cut = dict(full, experts_held=4, expert_offset=4 * share)
        cut_p = lambda name, cast=True, sl=sl: (
            weights[f"win1_{name}"][sl] if name in ("wg", "wu", "wd")
            else weights[f"win1_{name}"])
        assert np.allclose(part, ref.moe(h, cut_p, cut, jnp.float32),
                           atol=2e-5)
        total = total + np.asarray(part)
        landed += int(stats[0])
    assert landed == 9 * 2                  # every assignment, once
    assert np.allclose(total, want, atol=5e-5)


def test_a_sink_on_a_full_layer_is_refused_not_dropped(cfg):
    hp = _hp(dict(cfg, add_full_attention_sink_bias=True))
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        with pytest.raises(NotImplementedError, match="sink"):
            window_moe.build_chunk_program(hp, SLOTS, PAGE_LEN, 24)
    short = _hp(cfg)
    short.ring = 4
    with pytest.raises(ValueError, match="cannot hold a window"):
        window_moe.export_window_model("/nonexistent", short)
