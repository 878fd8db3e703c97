"""Sliding-window layers with a sink beside full-attention layers on the
paged serving path (``ops/window_ops.py``, ``models/window_moe.py``): the
exported bundle (prefill, the compiled seed of pages AND rings, cached
decode steps over both kinds of cache) against the plain reference's
full forward (``benchmark/reference/mimo_v2_flash_ref.py``), each
mechanism caught when it is dropped, the banded and the grouped prefill
kernels and the paged kernel with key and value heads of different
widths in interpret mode against their composed forms, the share
arithmetic, a ring's bytes whatever the stream's length, the contract
and the rules.  Toy widths: d 64, 4 heads of 24 / 16 over 1 (full) or 2
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
from paddle_tpu.models import window_moe
from paddle_tpu.ops import attention_ops, moe_ops, window_ops

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
    was, window_moe.CHUNK_ROWS = window_moe.CHUNK_ROWS, 16
    try:
        window_moe.export_window_model(path, _hp(cfg), num_slots=SLOTS,
                                       prompt_buckets=BUCKETS,
                                       page_len=PAGE_LEN)
    finally:
        window_moe.CHUNK_ROWS = was
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


# -- the kernels in interpret mode against the composed forms ----------------------

@pytest.mark.parametrize("start", [0, WINDOW - 1, WINDOW, 3 * 64 + 17],
                         ids=["whole", "under_window", "a_window", "far"])
@pytest.mark.parametrize("window, sink, hkv, dk, blocks", [
    (8, True, 2, 24, (16, 8)),      # the band, two key blocks a query block
    (8, True, 2, 32, (32, 8)),      # four own blocks and the lead-in
    (5, True, 2, 32, (16, 8)),      # a window that is not a block
    (8, False, 2, 32, (16, 8)),     # a band without a sink
    (0, False, 1, 24, (16, 16)),    # causal, one K/V head for all
    (0, False, 2, 32, (16, 32)),    # causal, key blocks wider than query's
], ids=["band", "band_wide", "band_odd", "band_no_sink", "causal",
        "causal_wide"])
def test_the_flash_kernel_is_the_composed_attention(window, sink, hkv, dk,
                                                    blocks, start):
    """A chunk of 64 rows at position ``start`` of a sequence (0: a whole
    prompt) against the composed attention over the WHOLE sequence: the
    causal kernel over all the keys with its diagonal shifted by
    ``start`` (pad keys behind them), the banded one with the rows
    before the chunk led in from where a ring would hold them."""
    rng = np.random.RandomState(window + dk)
    C, H, dv = 64, 4, 16
    T = start + C
    q, k, v = (jnp.asarray(rng.randn(T, w), jnp.float32)
               for w in (H * dk, hkv * dk, hkv * dv))
    b = jnp.asarray(rng.randn(H), jnp.float32) if sink else None
    want = window_ops.composed_attention(q, k, v, H, hkv, 0.2, window, b)
    kernel = dict(n_head=H, n_kv_head=hkv, scale=0.2, window=window,
                  interpret=True, blocks=blocks)
    if window:
        lead = -(-(window - 1) // blocks[1]) * blocks[1]
        before = [jnp.concatenate([jnp.zeros((lead, x.shape[1])), x])
                  [start:start + lead] for x in (k, v)]
        got = window_ops.flash_attention(
            q[start:], k[start:], v[start:], b,
            before=(*before, min(start, lead)), **kernel)
    else:
        # whole key blocks, and one more that no row may see
        pad = -T % blocks[1] + blocks[1]
        keys, vals = (jnp.concatenate([x, jnp.full((pad, x.shape[1]), 9.0)])
                      for x in (k, v))
        got = window_ops.flash_attention(q[start:], keys, vals, None,
                                         jnp.int32(start), **kernel)
    assert np.allclose(got, want[start:], atol=2e-5)
    # the composed form is the reference's: rows see what it says
    if window:
        alone = window_ops.composed_attention(
            q[-window:], k[-window:], v[-window:], H, hkv, 0.2, window, b)
        assert np.allclose(got[-1], alone[-1], atol=2e-5)
    # and its own chunk form is the kernel's
    if window:
        again = window_ops.composed_attention(
            q[start:], jnp.concatenate([before[0], k[start:]]),
            jnp.concatenate([before[1], v[start:]]), H, hkv, 0.2, window, b,
            start=lead, first=lead - min(start, lead))
    else:
        again = window_ops.composed_attention(q[start:], keys, vals, H, hkv,
                                              0.2, 0, None, start=start)
    assert np.allclose(again, want[start:], atol=2e-5)


def test_a_ring_leads_a_chunk_in_and_takes_its_last_rows():
    """Chunks of 5, 16, 3, 1 and 11 real rows (of 16 run) through a ring
    of 8: before each, ``ring_lead`` hands out the 7 rows before the
    chunk in order (zeros before position 0) and says how many are real;
    after each, the ring is what ``ring_of`` makes of the whole prefix;
    pad rows go nowhere."""
    R, W = 8, 3
    rows = jnp.arange(40 * W, dtype=jnp.float32).reshape(40, W) + 1
    ring, pos = jnp.zeros((R, W)), 0
    for n in (5, 16, 3, 1, 11):
        lead, real = window_ops.ring_lead(ring, pos, R - 1)
        assert np.array_equal(lead, jnp.concatenate(
            [jnp.zeros((R - 1, W)), rows])[pos:pos + R - 1])
        assert int(real) == min(pos, R - 1)
        chunk = jnp.concatenate([rows[pos:pos + n],
                                 jnp.full((16 - n, W), -1.0)])
        ring = window_ops.ring_after(ring, chunk, pos, n)
        pos += n
        assert np.array_equal(ring, window_ops.ring_of(rows, pos - 1, R))
    assert np.array_equal(window_ops.ring_after(ring, chunk, pos, 0), ring)
    # a lead longer than the ring holds: what fell out reads zeros
    lead, real = window_ops.ring_lead(ring, pos, 12)
    assert int(real) == R and not np.asarray(lead[:4]).any()
    assert np.array_equal(lead[4:], rows[pos - R:pos])


def test_the_band_computes_the_blocks_that_meet_it_and_no_more():
    # 16384 rows, 8 query heads a K/V head: 64 query blocks of 256 rows,
    # each its two own key blocks of 128 and the one before
    blocks, rows = window_ops.key_blocks_computed(16384, 8, 128)
    assert (blocks, rows) == (64 * 3 - 1, 128)
    # causal, 16 heads a K/V head: query blocks of 128 under key blocks
    # of 512: 4 query blocks a diagonal block
    blocks, rows = window_ops.key_blocks_computed(16384, 16, 0)
    assert (blocks, rows) == (4 * sum(range(1, 33)), 512)
    assert window_ops.key_blocks_computed(40, 8, 128) == (0, 0)
    # a chunk of 1024 rows: at position 0 its first query block has no
    # lead-in, further on every block has; a full layer's walks the keys
    # to its own diagonal, shifted by where it stands
    assert window_ops.key_blocks_computed(1024, 8, 128, start=0)[0] == 11
    assert window_ops.key_blocks_computed(1024, 8, 128, start=64)[0] == 12
    assert window_ops.key_blocks_computed(1024, 8, 128, start=4096)[0] == 12
    assert window_ops.key_blocks_computed(1024, 16, 0, start=0, keys=4096) \
        == (4 * (1 + 2), 512)
    assert window_ops.key_blocks_computed(
        1024, 16, 0, start=8192, keys=12288)[0] == 4 * (17 + 18)
    assert window_ops.lead_rows(1024, 8, 128) == 128
    assert window_ops.lead_rows(40, 8, 128) == 127


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_paged_kernel_takes_value_heads_of_their_own_width(dtype):
    rng = np.random.RandomState(2)
    S, P, PL, NP, H, hkv, dk, dv = 3, 4, 8, 16, 4, 2, 32, 16
    kc, vc, q = (jnp.asarray(rng.randn(*shape), dtype) for shape in (
        (NP, PL, hkv * dk), (NP, PL, hkv * dv), (S, 1, H * dk)))
    pt = jnp.asarray(rng.permutation(NP)[:S * P].reshape(S, P), jnp.int32)
    lens = jnp.asarray([[5], [0], [29]], jnp.int32)
    want = attention_ops._xla_paged_attention(q, kc, vc, pt, lens, H, 0.2)
    got = attention_ops._pallas_paged_attention(q, kc, vc, pt, lens, H, 0.2,
                                                interpret=True)
    assert got.shape == (S, 1, H * dv)
    live = np.asarray([0, 2])
    assert np.allclose(np.asarray(got, np.float32)[live],
                       np.asarray(want, np.float32)[live],
                       atol=1e-5 if dtype == "float32" else 2e-2)
    # on the chip the gate wants whole vregs of BOTH widths
    ok = attention_ops._paged_kernel_ok
    assert ok(64, 64 * 256, 64, False, 4 * 256, 2, None, 4 * 128)
    assert not ok(64, 64 * 192, 64, False, 4 * 192, 2, None, 4 * 128)
    assert not ok(64, 64 * 256, 64, False, 4 * 256, 2, None, 4 * 64)
    # heads of their own (no groups) keep one width
    assert not ok(4, 4 * 256, 64, True, 4 * 256, 2, None, 4 * 128)


def test_rope_partial_turns_the_leading_lanes_and_pads_behind():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(5, 2 * 24), jnp.float32)
    pos = jnp.asarray([0, 1, 7, 300, 9000], jnp.int32)
    got = np.asarray(window_ops.rope_partial(x, pos, 2, 8, 10000.0, 32))
    want = np.asarray(ref._rope(x.reshape(5, 2, 24), pos, 8, 10000.0))
    got = got.reshape(5, 2, 32)
    assert np.allclose(got[..., :24], want, atol=1e-5)
    assert not got[..., 24:].any()
    assert np.array_equal(got[0, :, :24], np.asarray(x[0]).reshape(2, 24))
    assert np.array_equal(got[..., 8:24],
                          np.asarray(x).reshape(5, 2, 24)[..., 8:])


@pytest.mark.parametrize("kernel", [None, True], ids=["composed", "kernel"])
def test_the_ring_step_is_the_window_of_the_composed_attention(kernel):
    """Both forms of the decode step over the ring: the composed one and
    the Pallas kernel (interpret mode)."""
    rng = np.random.RandomState(1)
    H, hkv, dk, dv, R, T, n0 = 4, 2, 24, 16, 11, 40, 13
    q, k, v = (jnp.asarray(rng.randn(T, w), jnp.float32)
               for w in (H * dk, hkv * dk, hkv * dv))
    b = jnp.asarray(rng.randn(H), jnp.float32)
    want = window_ops.composed_attention(q, k, v, H, hkv, 0.2, WINDOW, b)
    # a ring LONGER than the window (11 rows for 8), a free slot beside
    rings = [jnp.stack([window_ops.ring_of(x[:n0], n0 - 1, R),
                        jnp.full((R, x.shape[1]), 7.0)]) for x in (k, v)]
    for t in range(n0, T):
        two = lambda x: jnp.stack([x[t], x[t]])
        out, *rings = window_ops.ring_step(
            two(q), two(k), two(v), *rings, jnp.asarray([t + 1, 0]), b, H,
            0.2, WINDOW, kernel=kernel)
        assert np.allclose(out[0], want[t], atol=2e-5), t
        assert not np.asarray(out[1]).any()
    assert float(rings[0][1].min()) == 7.0      # the free slot's: untouched


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
    assert predictor._chunk_shape(24, 30) == (48, 8)
    assert predictor.chunk_cost(24, 30) \
        == float(chunk_report(48, 8).total_flops)
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


def test_mismatched_heads_and_rings_are_type_errors():
    from paddle_tpu.analysis.analyzer import lint_program
    from paddle_tpu.models.hybrid_moe import _data, _op
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        q = _data("q", [2, 1, 4 * 24])
        k = _data("k", [2, 1, 2 * 24])
        v = _data("v", [2, 1, 2 * 16])
        lens = _data("lens", [2, 1])                 # not an integer
        sink = _data("sink", [3])                    # not one a head
        block = main.global_block()
        rings = []
        for name, shape in (("rk", [2, 4, 48]), ("rv", [2, 8, 40])):
            r = block.create_var(name=name, shape=shape, dtype="float32")
            r.persistable = True
            rings.append(r)
        out = _op("window_attention_step",
                  {"Q": q, "K": k, "V": v, "KRing": rings[0],
                   "VRing": rings[1], "Lens": lens, "Sink": sink},
                  {"Out": "float32", "KRingOut": rings[0],
                   "VRingOut": rings[1]},
                  {"n_head": 4, "scale": 1.0, "window": 8})["Out"]
    result = lint_program(main, feed_names=["q", "k", "v", "lens", "sink"],
                          fetch_names=[out.name])
    messages = " | ".join(d.message for d in result.errors)
    assert "fewer than the window" in messages      # a ring of 4 rows
    assert "lanes a row (V's)" in messages          # 40 for 32
    assert "must be an integer" in messages
    assert "sink logits" in messages
    # one chunk of a prefill over the same caches is held to them too
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        q, k, v = (_data(n, [1, 16, w]) for n, w in
                   (("q", 4 * 24), ("k", 2 * 24), ("v", 2 * 16)))
        pos, mask = _data("pos", [1, 16]), _data("mask", [1, 16])
        slot = _data("slot", [1, 1], "int32")
        table = _data("table", [1, 4], "int32")
        block = main.global_block()
        held = {}
        for name, shape in (("rk", [2, 4, 48]), ("rv", [2, 8, 32]),
                            ("pk", [6, 8, 48]), ("pv", [6, 8, 40])):
            held[name] = block.create_var(name=name, shape=shape,
                                          dtype="float32")
            held[name].persistable = True
        attrs = {"n_head": 4, "n_kv_head": 2, "scale": 1.0}
        band = _op("window_attention",
                   {"Q": q, "K": k, "V": v, "KRing": held["rk"],
                    "VRing": held["rv"], "Slot": slot, "Pos": pos,
                    "Mask": mask},
                   {"Out": "float32", "KRingOut": held["rk"],
                    "VRingOut": held["rv"]}, {**attrs, "window": 8})["Out"]
        full = _op("gqa_flash_attention_chunk",
                   {"Q": q, "K": k, "V": v, "KCache": held["pk"],
                    "VCache": held["pv"], "PageTable": table, "Pos": pos,
                    "Mask": mask},
                   {"Out": "float32", "KCacheOut": held["pk"],
                    "VCacheOut": held["pv"]}, attrs)["Out"]
    result = lint_program(
        main, feed_names=["q", "k", "v", "pos", "mask", "slot", "table"],
        fetch_names=[band.name, full.name])
    messages = " | ".join(d.message for d in result.errors)
    assert "KRing holds 4 rows a slot, fewer than the window" in messages
    assert "VCache" in messages and "lanes a row (V's)" in messages
    assert "Pos `pos` must be an integer" in messages


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


def test_config_takes_the_published_keys():
    with open(os.path.join(BENCH, "configs", "mimo_v2_flash.json")) as f:
        published = json.load(f)
    hp = window_moe.WindowMoEConfig.from_dict(published)
    assert (hp.hidden_size, hp.intermediate_size,
            hp.moe_intermediate_size) == (4096, 16384, 2048)
    assert hp.attention(0) == (64, 4, 192, 128, 5000000.0, False)
    assert hp.attention(1) == (64, 8, 192, 128, 10000.0, True)
    assert (hp.sliding_window, hp.ring_rows, hp.eps) == (128, 128, 1e-5)
    assert hp.row_widths(0) == (4 * 256, 4 * 128)
    assert hp.row_widths(1) == (8 * 256, 8 * 128)
    assert hp.held == 8 and hp.n_routed_experts == 256
    assert hp.window_layers == [1, 2, 3, 4, 6] and hp.full_layers == [0, 5]
    assert hp.moe_layers == [1, 2, 3, 4, 5, 6]
    assert window_moe.paged_cache_var_names(hp) == [
        "win0_paged_k", "win0_paged_v", "win5_paged_k", "win5_paged_v"]
    assert adapter.kv_bytes_per_row(published) == 2 * 4 * (256 + 128) * 2
    assert adapter.window_bytes_per_row(published) == 8 * (256 + 128) * 2
    # 2.22B parameters, as the issue's arithmetic has them
    assert round(adapter.param_count(published) / 1e7) == 222


def test_a_sink_on_a_full_layer_is_refused_not_dropped(cfg):
    hp = _hp(dict(cfg, add_full_attention_sink_bias=True))
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        with pytest.raises(NotImplementedError, match="sink"):
            window_moe.build_chunk_program(hp, SLOTS, PAGE_LEN, 24)
    short = _hp(cfg)
    short.ring = 4
    with pytest.raises(ValueError, match="cannot hold a window"):
        window_moe.export_window_model("/nonexistent", short)
