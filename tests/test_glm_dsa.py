"""Learned sparse attention on the paged serving path
(``ops/dsa_ops.py``; ``models/latent_moe.py`` with ``index_topk``): the
indexer and the exact top-k against the plain reference
(``benchmark/reference/glm_dsa_ref.py``: ``jax.lax.top_k``) and against
numbers worked by hand (the tie rule, the identity up to ``index_topk``
rows), the latent kernel under a selection against the gather, the
exported bundle (a prompt as a run of chunks over BOTH pools, each
chunk's rows selecting among the rows before them, cached decode steps)
against the reference's full forward with a selection that decides, a
re-used slot, the share arithmetic, the contract and the rules.  Toy widths: d 64, 4 heads x (16 | 8), latent 32 + rope 8, 4 index
heads x 16, ``index_topk`` 8, the published layers 2-6 (dense + full,
three sparse + shared, sparse + full), contexts of 24-48 rows."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import latent_chunks
import paddle_tpu as fluid
from paddle_tpu import profiler
from paddle_tpu.analysis import cost
from paddle_tpu.gen import GenPredictor
from paddle_tpu.models import decoder, latent_moe
from paddle_tpu.ops import attention_ops, dsa_ops, mla_ops, moe_ops

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from models import glm_dsa as adapter             # noqa: E402
from reference import glm_dsa_ref as ref          # noqa: E402

SLOTS, PAGE_LEN, BUCKETS, TOPK = 4, 8, [8, 16, 32, 48], 8


def toy_config(**over):
    cfg = {"hidden_size": 64, "num_hidden_layers": 5,
           "first_k_dense_replace": 1, "layer_offset": 2, "vocab_size": 64,
           "rms_norm_eps": 1e-5, "num_attention_heads": 4,
           "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
           "qk_rope_head_dim": 8, "v_head_dim": 16,
           "rope_parameters": {"rope_theta": 8000000,
                               "rope_type": "default"},
           "index_topk": TOPK, "index_n_heads": 4, "index_head_dim": 16,
           "indexer_types": ["full"] * 3 + ["shared"] * 3 + ["full"]
           + ["shared"] * 3,
           "mlp_layer_types": ["dense"] * 3 + ["sparse"] * 7,
           "intermediate_size": 96, "moe_intermediate_size": 32,
           "n_routed_experts": 16, "n_shared_experts": 1,
           "num_experts_per_tok": 2, "routed_scaling_factor": 2.5,
           "norm_topk_prob": True, "experts_held": 16, "expert_offset": 0}
    cfg.update(over)
    return cfg


def _hp(cfg, dtype="float32"):
    hp = latent_moe.LatentMoEConfig.from_dict(cfg)
    hp.dtype, hp.max_len = dtype, 64
    return hp


def _install(predictor, weights):
    for name, value in weights.items():
        old = predictor._scope.find_var(name)
        assert old is not None and tuple(old.shape) == tuple(value.shape), \
            name
        predictor._scope.set_var(name, value)


@pytest.fixture(scope="module")
def cfg():
    return toy_config()


@pytest.fixture(scope="module")
def weights(cfg):
    # the seeded bfloat16 VALUES held in float32; the router's offset
    # row is taken out (it is made for the published widths)
    w = {k: v.astype(jnp.float32)
         for k, v in adapter.seeded_weights(cfg, 7).items()}
    for i in adapter.sparse_layers(cfg):
        w[f"lat{i}_gate.w"] = w[f"lat{i}_gate.w"].at[0].set(0.0)
    return w


@pytest.fixture(scope="module")
def bundle_dir(tmp_path_factory, cfg):
    path = str(tmp_path_factory.mktemp("dsa") / "bundle")
    latent_moe.export_latent_model(path, _hp(cfg), num_slots=SLOTS,
                                   prompt_buckets=BUCKETS,
                                   page_len=PAGE_LEN)
    return path


@pytest.fixture(scope="module")
def predictor(bundle_dir, weights):
    p = GenPredictor(bundle_dir)
    _install(p, weights)
    p.warmup()
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


def _admit(predictor, slot, prompt, horizon=8):
    logits, kv = predictor.prefill(prompt)
    predictor.alloc_slot_pages(slot, predictor.pages_needed(len(prompt),
                                                            horizon))
    assert predictor.write_slot(slot, kv, len(prompt)) == 0
    return logits


def _chunks(predictor, slot, prompt, horizon=8):
    """``slot``'s pages, then the prompt's chunks one by one, as the
    scheduler admits: a generator that yields after every chunk; the
    last chunk's logits land in ``_chunks.logits[slot]``."""
    predictor.alloc_slot_pages(slot, predictor.pages_needed(len(prompt),
                                                            horizon))
    for a, b in predictor.chunk_spans(len(prompt)):
        _chunks.logits[slot] = np.asarray(
            predictor.prefill_chunk(slot, prompt[a:b], a))[0]
        yield a


_chunks.logits = {}


# -- the selection ----------------------------------------------------------------

def test_the_selection_is_the_references_top_k_with_its_tie_rule():
    scores = jnp.asarray([[3., 1., 3., 0., -0., 2., 3., 1., 5.]])
    seen = jnp.ones((1, 9), bool)
    # k 3: 5, then two of the three 3s, the leftmost two
    want = [[1, 0, 1, 0, 0, 0, 0, 0, 1]]
    assert np.array_equal(dsa_ops.select_mask(scores, seen, 3), want)
    assert np.array_equal(ref.select_rows(scores, seen, 3), want)
    # -0.0 ties with 0.0, and the lower position wins
    got = dsa_ops.select_mask(scores, seen, 8)
    assert np.array_equal(got, [[1, 1, 1, 1, 0, 1, 1, 1, 1]])
    assert np.array_equal(ref.select_rows(scores, seen, 8), got)
    # a row that is not seen is never chosen, whatever it scores
    seen = seen.at[0, 8].set(False).at[0, 0].set(False)
    got = dsa_ops.select_mask(scores, seen, 2)
    assert np.array_equal(got, [[0, 0, 1, 0, 0, 0, 1, 0, 0]])
    assert np.array_equal(ref.select_rows(scores, seen, 2), got)


@pytest.mark.parametrize("seed", range(4))
def test_the_bitwise_search_agrees_with_a_sort_on_random_scores(seed):
    rng = np.random.RandomState(seed)
    # few distinct values: ties everywhere, negative ones among them
    scores = jnp.asarray(rng.randint(-4, 5, size=(6, 40)) / 4.0,
                         jnp.float32)
    if seed % 2:
        scores = jnp.asarray(rng.randn(6, 40), jnp.float32)
    seen = jnp.asarray(rng.rand(6, 40) < 0.7)
    for k in (1, 5, 17, 39):
        got = np.asarray(dsa_ops.select_mask(scores, seen, k))
        assert np.array_equal(got, ref.select_rows(scores, seen, k))
        assert np.array_equal(got.sum(-1),
                              np.minimum(np.asarray(seen).sum(-1), k))


def test_up_to_top_k_rows_the_selection_is_the_identity():
    scores = jnp.asarray(np.random.RandomState(0).randn(1, 8, 8),
                         jnp.float32)
    mask = jnp.asarray([[1, 1, 1, 1, 1, 1, 0, 0]], jnp.float32)
    got = np.asarray(dsa_ops.causal_select(scores[0], mask[0], 8))
    assert np.array_equal(got, np.tril(np.ones((8, 8))) * np.asarray(mask))
    # past it a row keeps exactly k of the real rows before it
    scores = jnp.asarray(np.random.RandomState(1).randn(32, 32),
                         jnp.float32)
    got = np.asarray(dsa_ops.causal_select(scores, jnp.ones(32), 8,
                                           block=8))
    assert np.array_equal(got.sum(-1), np.minimum(np.arange(32) + 1, 8))
    assert not np.triu(got, 1).any()


@pytest.mark.parametrize("start, n", [(0, 16), (16, 16), (32, 13), (8, 5)])
def test_a_chunks_selection_is_the_whole_prompts_rows(start, n):
    """A chunk of 16 rows at ``start`` over a bucket of 64 key rows, of
    which ``n`` are real: row ``r`` keeps the top 8 of the real rows ``s
    <= start + r`` as the reference does on those very scores, and never
    a row behind its own query, a pad row or a row past the slot's (the
    table's tail is whatever page 0 holds: another slot's)."""
    C, T, K = 16, 64, 8
    scores = jnp.asarray(np.random.RandomState(start + n).randn(C, T),
                         jnp.float32)
    mask = jnp.asarray(np.arange(C) < n, jnp.float32)
    got = np.asarray(dsa_ops.causal_select(scores, mask, K, block=8,
                                           start=start))
    assert got.shape == (C, T) and got.dtype == np.int8
    rows, cols = np.arange(C)[:, None], np.arange(T)[None]
    seen = (cols <= start + rows) & (cols < start + n)
    assert not got[~seen].any()
    assert np.array_equal(got.sum(-1), np.minimum(seen.sum(-1), K))
    assert np.array_equal(got, ref.select_rows(scores, jnp.asarray(seen), K))
    # the whole prompt is the chunk at 0 over its own rows
    square = jnp.asarray(np.random.RandomState(5).randn(T, T), jnp.float32)
    whole = np.asarray(dsa_ops.causal_select(square, jnp.ones(T), K))
    part = np.asarray(dsa_ops.causal_select(
        square[start:start + C], jnp.ones(C), K, start=start))
    assert np.array_equal(part, whole[start:start + C])


@pytest.mark.parametrize("kernel", [True, False],
                         ids=["kernel", "composed"])
@pytest.mark.parametrize("start, n", latent_chunks.STARTS,
                         ids=latent_chunks.START_IDS)
def test_a_chunk_under_its_selection_is_the_whole_sequences_rows(start, n,
                                                                 kernel):
    """``mla_attention_chunk`` at this configuration's heads (192 | 64
    lanes of key, 256 of value: whole tiles already) under the top-300
    of seeded index scores, the chunk's selection made over the page
    bucket: the kernel's form, the selection's int8 blocks beside the
    latent blocks it expands, and the composed one, against
    ``mla_attention`` over the whole prompt under the whole prompt's
    selection."""
    latent_chunks.chunk_is_the_whole_sequence(192, 64, 256, start, n,
                                              top_k=300, kernel=kernel)


def test_the_chunk_kernel_under_a_selection_is_the_masked_softmax():
    """``window_ops``'s flash kernel under a selection (interpret
    mode): ONE K/V head under 4 query heads, 32 query rows at
    key index 64 of 128, the selection's int8 blocks beside the keys';
    against the composed form, and both against the softmax by hand."""
    from paddle_tpu.ops import window_ops
    C, T, H, W, L, start = 32, 128, 4, 128, 64, 64
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(C, H * W), jnp.float32)
    keys = jnp.asarray(rng.randn(T, W), jnp.float32)
    scores = jnp.asarray(rng.randn(C, T), jnp.float32)
    scores = scores.at[5, :32].set(-50.0)       # row 5 keeps rows >= 32
    select = dsa_ops.causal_select(scores, jnp.ones(C), 24, start=start)
    assert not np.asarray(select[5, :32]).any()
    want = window_ops.composed_attention(q, keys, keys[:, :L], H, 1, 0.3,
                                         start=start, select=select)
    got = window_ops.flash_attention(
        q, keys, keys[:, :L], None, start, None, select, n_head=H,
        n_kv_head=1, scale=0.3, interpret=True, blocks=(16, 32))
    assert np.allclose(got, want, atol=2e-5)
    sc = np.einsum("qhw,tw->hqt", np.asarray(q).reshape(C, H, W),
                   np.asarray(keys)) * 0.3
    sc = np.where(np.asarray(select)[None] > 0, sc, -1e30)
    pr = np.exp(sc - sc.max(-1, keepdims=True))
    pr /= pr.sum(-1, keepdims=True)
    assert np.allclose(want, np.einsum(
        "hqt,tl->qhl", pr, np.asarray(keys)[:, :L]).reshape(C, H * L),
        atol=2e-5)
    # without one the call is what it was: the shifted diagonal
    plain = window_ops.flash_attention(
        q, keys, keys[:, :L], None, start, n_head=H, n_kv_head=1,
        scale=0.3, interpret=True, blocks=(16, 32))
    assert np.allclose(plain, window_ops.composed_attention(
        q, keys, keys[:, :L], H, 1, 0.3, start=start), atol=2e-5)
    with pytest.raises(ValueError, match="causal form alone"):
        window_ops.flash_attention(
            q, keys[:C], keys[:C, :L], None, 0, None, select[:, :C],
            n_head=H, n_kv_head=1, scale=0.3, window=8, interpret=True,
            blocks=(16, 16))


def test_the_indexer_scores_are_the_references(cfg, weights):
    T = 24
    h = jax.random.normal(jax.random.PRNGKey(1), (T, 64))
    c_q = jax.random.normal(jax.random.PRNGKey(2), (T, 48))
    p = lambda name: weights[f"lat0_{name}"]
    q, k, w = dsa_ops.index_projections(
        c_q, h, jnp.arange(T), p("idx_qb.w"), p("idx_k.w"),
        p("idx_knorm.scale"), p("idx_knorm.bias") + 0.25, p("idx_w.w"), 4,
        8, 8e6)
    pb = lambda name: p(name) + (0.25 if name == "idx_knorm.bias" else 0)
    with jax.default_matmul_precision("highest"):
        qr, kr, wr = ref.index_parts(h, c_q, pb, cfg, jnp.float32)
        want = jnp.sum(jax.nn.relu(jnp.einsum("qhd,td->qht", qr, kr))
                       * wr[:, :, None], axis=1)
    assert np.allclose(k, kr, atol=2e-5) and np.allclose(q, qr, atol=2e-5)
    assert np.allclose(w, wr, atol=1e-6)
    got = dsa_ops.index_scores(q, k, w)
    assert np.allclose(got, want, atol=2e-5)
    # only the first 8 lanes of a head turn with the position
    q0, k0, _ = dsa_ops.index_projections(
        c_q, h, jnp.zeros(T, jnp.int32), p("idx_qb.w"), p("idx_k.w"),
        p("idx_knorm.scale"), p("idx_knorm.bias") + 0.25, p("idx_w.w"), 4,
        8, 8e6)
    assert np.array_equal(k[:, 8:], k0[:, 8:])
    assert np.array_equal(q[..., 8:], q0[..., 8:])
    assert not np.allclose(k[1:, :8], k0[1:, :8])


# -- the latent kernel under a selection -------------------------------------------

def test_the_latent_kernel_masks_what_the_selection_leaves_out():
    S, NP, PL, P, H, W, V = 3, 12, 16, 4, 4, 128, 64
    rng = np.random.RandomState(0)
    cache = jnp.asarray(rng.randn(NP, PL, W), jnp.float32)
    q = jnp.asarray(rng.randn(S, 1, H * W), jnp.float32)
    table = jnp.asarray(rng.permutation(NP).reshape(S, P), jnp.int32)
    lens = jnp.asarray([[37], [0], [64]], jnp.int32)
    select = jnp.asarray(rng.rand(S, 1, P * PL) < 0.3, jnp.int32)
    # a whole chunk of the first slot with nothing selected
    select = select.at[0, 0, :16].set(0).at[0, 0, 20].set(1)
    want = attention_ops._xla_latent_attention(q, cache, table, lens, H, V,
                                               0.3, select=select)
    for block_pages in (1, 2, None):
        got = attention_ops._pallas_paged_attention(
            q, cache, None, table, lens, H, 0.3, interpret=True,
            block_pages=block_pages, v_width=V, select=select)
        assert np.allclose(got, want, atol=2e-5)
    assert not np.asarray(want[1]).any()
    everything = attention_ops._xla_latent_attention(q, cache, table, lens,
                                                     H, V, 0.3)
    assert not np.allclose(want[0], everything[0], atol=1e-3)
    # the gather by hand: slot 0's selected live rows and no other
    rows = np.asarray(cache)[np.asarray(table[0])].reshape(P * PL, W)
    keep = np.flatnonzero(np.asarray(select[0, 0])[:37])
    sc = np.asarray(q[0, 0]).reshape(H, W) @ rows[keep].T * 0.3
    pr = np.exp(sc - sc.max(-1, keepdims=True))
    pr /= pr.sum(-1, keepdims=True)
    assert np.allclose(np.asarray(want[0, 0]).reshape(H, V),
                       pr @ rows[keep][:, :V], atol=2e-5)


def test_the_prefill_attention_under_a_selection_is_the_masked_softmax():
    T, H, nope, R, vd, L = 16, 2, 8, 4, 8, 16
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(T, H * (nope + R)), jnp.float32)
    latent = jnp.asarray(rng.randn(T, 128), jnp.float32)
    w_kvb = jnp.asarray(rng.randn(L, H * (nope + vd)) * 0.3, jnp.float32)
    mask = jnp.ones(T)
    select = jnp.asarray(np.tril(rng.rand(T, T) < 0.5) | np.eye(T, dtype=bool),
                         jnp.int8)
    got = mla_ops.mla_attention(q, latent, w_kvb, mask, H, nope, R, vd,
                                0.25, select=select, block=4)
    w = np.asarray(w_kvb).reshape(L, H, nope + vd)
    k = np.concatenate(
        [np.einsum("tl,lhd->thd", latent[:, :L], w[..., :nope]),
         np.broadcast_to(np.asarray(latent[:, None, L:L + R]), (T, H, R))],
        -1)
    v = np.einsum("tl,lhd->thd", latent[:, :L], w[..., nope:])
    sc = np.einsum("qhd,thd->hqt", np.asarray(q).reshape(T, H, -1), k) * 0.25
    sc = np.where(np.asarray(select)[None] > 0, sc, -1e30)
    pr = np.exp(sc - sc.max(-1, keepdims=True))
    pr /= pr.sum(-1, keepdims=True)
    want = np.einsum("hqt,thd->qhd", pr, v).reshape(T, H * vd)
    assert np.allclose(got, want, atol=2e-5)


def test_the_select_flash_kernel_is_the_masked_softmax():
    """The TPU's prefill form (interpret mode here) against plain XLA
    under the same selection: two query blocks of 512, a row of the
    second block with nothing marked in its first key block, pad rows
    at the end."""
    T, H, nope, R, vd, L = 1024, 2, 8, 4, 8, 16
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(T, H * (nope + R)), jnp.float32)
    latent = jnp.asarray(rng.randn(T, 128), jnp.float32)
    w_kvb = jnp.asarray(rng.randn(L, H * (nope + vd)) * 0.3, jnp.float32)
    mask = jnp.asarray(np.arange(T) < 1000, jnp.float32)
    scores = jnp.asarray(rng.randn(T, T), jnp.float32)
    scores = scores.at[700, :512].set(-50.0)    # row 700 keeps rows >= 512
    select = dsa_ops.causal_select(scores, mask, 64)
    assert not np.asarray(select[700, :512]).any()
    args = (q, latent, w_kvb, mask, H, nope, R, vd, 0.25)
    want = mla_ops.mla_attention(*args, select=select, flash=False)
    got = mla_ops.mla_attention(*args, select=select, flash=True,
                                interpret=True)
    assert np.allclose(got[:1000], want[:1000], atol=2e-5)
    assert np.isfinite(np.asarray(got)).all()
    # not whole blocks of 512 rows: plain XLA, whatever was asked
    assert dsa_ops.selected_attention(
        jnp.zeros((H, 96, 12)), jnp.zeros((H, 96, 12)),
        jnp.zeros((H, 96, 8)), jnp.ones((96, 96), jnp.int8), scale=1.0,
        interpret=True) is None


# -- the bundle against the reference ------------------------------------------------

@pytest.mark.parametrize("n", [6, 20, 40])
def test_prefill_both_pools_and_cached_steps_match_the_reference(
        predictor, weights, cfg, n):
    """Every bucket: the identity (6 rows), past ``index_topk`` (20, 40).
    The program reads 1e-6 of the logits' range; the SAME reference with
    the selection switched off reads 0.2 and more wherever a row has more
    than ``index_topk`` rows before it: the selection decides."""
    prompt = _prompt(n, seed=n)
    logits = _admit(predictor, 1, prompt)
    try:
        want = _ref_logits(weights, cfg, prompt, [n - 1])
        dense = _ref_logits(weights, cfg, prompt, [n - 1], select=False)
        assert _err(logits, want[0]) < 2e-4
        assert (_err(dense[0], want[0]) > 0.1) == (n > TOPK)
        toks, tok = list(prompt), int(np.argmax(logits))
        for _ in range(3):
            out = _step(predictor, {1: (tok, len(toks))})[1]
            toks.append(tok)
            at = [len(toks) - 1]
            want = _ref_logits(weights, cfg, toks, at)[0]
            assert _err(out, want) < 2e-4
            if len(toks) > TOPK:
                dense = _ref_logits(weights, cfg, toks, at, select=False)[0]
                assert _err(dense, want) > 0.1
            tok = int(np.argmax(out))
    finally:
        predictor.free_slot_pages(1)


@pytest.fixture(scope="module")
def chunked(tmp_path_factory, cfg, weights):
    """The bundle with chunk rungs of 8 and 16 rows (``predictor``'s are
    24 and 48: every prompt here is ONE chunk there): a prompt of 40
    rows is three chunks, and the second one's first row already has
    twice ``index_topk`` rows before it."""
    path = str(tmp_path_factory.mktemp("dsa") / "chunked")
    was, decoder.CHUNK_ROWS = decoder.CHUNK_ROWS, 16
    try:
        latent_moe.export_latent_model(path, _hp(cfg), num_slots=SLOTS,
                                       prompt_buckets=BUCKETS,
                                       page_len=PAGE_LEN)
    finally:
        decoder.CHUNK_ROWS = was
    p = GenPredictor(path)
    assert p.prefill_chunks == [8, 16]
    _install(p, weights)
    p.warmup()
    return p


@pytest.mark.parametrize("n", [6, 17, 40, 45])
def test_a_prompt_in_chunks_selects_what_the_whole_prompt_would(
        predictor, chunked, weights, cfg, n):
    """Chunk by chunk (8- and 16-row rungs) against the single pass (one
    24- or 48-row chunk) and against the reference: the last row's
    logits, BOTH pools' rows, and three cached steps over the rows the
    chunks wrote in place; the selection decides wherever a row has more
    than ``index_topk`` rows before it."""
    prompt = _prompt(n, seed=100 + n)
    assert len(chunked.chunk_spans(n)) == -(-n // 16)
    whole, parts = predictor.prefill(prompt), chunked.prefill(prompt)
    want = _ref_logits(weights, cfg, prompt, [n - 1])[0]
    dense = _ref_logits(weights, cfg, prompt, [n - 1], select=False)[0]
    assert _err(whole[0], want) < 2e-4 and _err(parts[0], want) < 2e-4
    assert (_err(dense, want) > 0.1) == (n > TOPK)
    # five latent pools, then the two full layers' index keys
    assert len(parts[1]) == len(whole[1]) == 7
    for got, row in zip(parts[1], whole[1]):
        assert got.shape == row.shape
        assert np.allclose(got, row, atol=2e-5)
        assert np.asarray(got)[0, :n].any(axis=-1).all()
        assert not np.asarray(got)[0, n:].any()
    assert chunked.free_pages == chunked.num_pages
    list(_chunks(chunked, 1, prompt))
    try:
        assert _err(_chunks.logits[1], want) < 2e-4
        toks, tok = list(prompt), int(np.argmax(_chunks.logits[1]))
        for _ in range(3):
            out = _step(chunked, {1: (tok, len(toks))})[1]
            toks.append(tok)
            assert _err(out, _ref_logits(weights, cfg, toks,
                                         [len(toks) - 1])[0]) < 2e-4
            tok = int(np.argmax(out))
    finally:
        chunked.free_slot_pages(1)


def test_a_shared_layer_takes_the_full_layers_selection_inside_a_chunk(cfg):
    """In the chunk program, as in the decode step: a ``full`` layer's
    ``dsa_select`` output is the ``Select`` of its own attention and of
    every ``shared`` layer's up to the next ``full`` one; only a full
    layer holds an indexer, and it writes its own key pool."""
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        feeds, fetches = latent_moe.build_chunk_program(_hp(cfg), 4, 8, 32)
    assert feeds == ["gen_ids", "gen_pos", "gen_mask", "gen_last",
                     "gen_page_table"] and len(fetches) == 1
    ops = main.global_block().ops
    selects = [op.output("Select")[0] for op in ops
               if op.type == "dsa_select"]
    under = [op.input("Select")[0] for op in ops
             if op.type == "mla_attention_chunk"]
    assert len(selects) == 2 and len(under) == 5
    assert under == [selects[0]] * 4 + [selects[1]]
    index = [op for op in ops if op.type == "dsa_index_chunk"]
    assert [op.output("CacheOut")[0] for op in index] == [
        "lat0_paged_ik", "lat4_paged_ik"]
    assert all(op.input("Pos") and op.input("Mask") for op in ops
               if op.type == "dsa_select")
    # nothing a decode metric could take for a step's attention
    assert not any(op.type.startswith("paged_attention") for op in ops)
    assert {op.type for op in ops if op.type.startswith(("mla_", "dsa_"))} \
        == {"mla_attention_chunk", "dsa_index_chunk", "dsa_select"}


def test_two_slots_admitted_alternately_select_for_themselves(chunked,
                                                              weights, cfg):
    """Prompts of 40 and 21 rows whose chunks alternate: each chunk
    scores, selects and attends its own slot's rows and no other's."""
    prompts = {0: _prompt(40, seed=301), 3: _prompt(21, seed=302)}
    runs = {slot: _chunks(chunked, slot, p) for slot, p in prompts.items()}
    try:
        order = []
        while runs:
            for slot in list(runs):
                start = next(runs[slot], None)
                if start is None:
                    del runs[slot]
                else:
                    order.append((slot, start))
        assert order == [(0, 0), (3, 0), (0, 16), (3, 16), (0, 32)]
        toks = {s: list(p) for s, p in prompts.items()}
        last = dict(_chunks.logits)
        for slot, p in prompts.items():
            assert _err(last[slot], _ref_logits(weights, cfg, p,
                                                [len(p) - 1])[0]) < 2e-4
        for _ in range(2):
            for slot in toks:
                toks[slot].append(int(np.argmax(last[slot])))
            out = _step(chunked, {s: (toks[s][-1], len(toks[s]) - 1)
                                  for s in toks})
            for slot in toks:
                last[slot] = out[slot]
                assert _err(out[slot], _ref_logits(
                    weights, cfg, toks[slot],
                    [len(toks[slot]) - 1])[0]) < 2e-4
    finally:
        for slot in prompts:
            chunked.free_slot_pages(slot)


def test_a_chunk_reads_no_stale_latent_row_or_index_key(chunked, weights,
                                                        cfg):
    """Whatever a former owner left in the pages (here: every row of
    every pool set to 7, as a long stream's rows and keys would be): a
    chunk writes its REAL rows into both pools and nothing else (a pad
    row lands nowhere, nobody else's page is touched), its indexer
    scores the bucket's rows but selects among the slot's own, and the
    steps behind it read rows under ``lens`` alone."""
    for name in chunked.cache_vars:
        old = chunked._scope.find_var(name)
        chunked._scope.set_var(name, jnp.full(old.shape, 7.0, old.dtype))
    prompt = _prompt(21, seed=303)          # 16 + 5 (of 8: 3 pad rows)
    try:
        list(_chunks(chunked, 2, prompt, horizon=6))
        assert _err(_chunks.logits[2],
                    _ref_logits(weights, cfg, prompt, [20])[0]) < 2e-4
        mine = chunked._slot_pages[2]
        for name in chunked.cache_vars:
            pool = np.asarray(chunked._scope.find_var(name))
            rows = pool[mine].reshape(-1, pool.shape[-1])
            assert (rows[:21] != 7.0).any(axis=-1).all()
            assert (rows[21:] == 7.0).all()
            others = [i for i in range(pool.shape[0]) if i not in mine]
            assert (pool[others] == 7.0).all()
        toks, tok = list(prompt), int(np.argmax(_chunks.logits[2]))
        for _ in range(3):
            out = _step(chunked, {2: (tok, len(toks))})[2]
            toks.append(tok)
            assert _err(out, _ref_logits(weights, cfg, toks,
                                         [len(toks) - 1])[0]) < 2e-4
            tok = int(np.argmax(out))
    finally:
        chunked.free_slot_pages(2)
        for name in chunked.cache_vars:
            old = chunked._scope.find_var(name)
            chunked._scope.set_var(name, jnp.zeros(old.shape, old.dtype))


def test_a_shared_layer_attends_under_the_full_layers_selection(weights,
                                                                cfg):
    """The reference's own account: layers 1-3 attend under layer 0's
    selection (switching layer 4's indexer off alone leaves them as they
    were), and a layer that is told ``full`` where it was ``shared`` needs
    weights the model does not have."""
    ids = _prompt(30, seed=3)
    found = []
    _ref_logits(weights, cfg, ids, [29], selections=found)
    assert len(found) == 2 and found[0].shape == (30, 30)
    assert np.array_equal(np.asarray(found[0]).sum(-1),
                          np.minimum(np.arange(30) + 1, TOPK))
    assert not np.array_equal(found[0], found[1])
    hp = _hp(cfg)
    assert [hp.indexer(i) for i in range(5)] == [
        "full", "shared", "shared", "shared", "full"]
    assert hp.full_layers == [0, 4] and hp.moe_layers == [1, 2, 3, 4]
    all_full = toy_config(indexer_types=["full"] * 10)
    with pytest.raises(KeyError):
        _ref_logits(weights, all_full, ids, [29])
    # a shared layer with no full layer before it among the layers held
    # attends every row
    late = _hp(toy_config(layer_offset=3, num_hidden_layers=4))
    assert [late.indexer(i) for i in range(4)] == [None, None, None, "full"]


def test_two_slots_of_different_lengths_select_for_themselves(
        predictor, weights, cfg):
    a, b = _prompt(11, seed=21), _prompt(37, seed=22)
    la, lb = _admit(predictor, 0, a), _admit(predictor, 3, b)
    try:
        ta, tb = int(np.argmax(la)), int(np.argmax(lb))
        out = _step(predictor, {0: (ta, 11), 3: (tb, 37)})
        assert _err(out[0], _ref_logits(weights, cfg, a + [ta],
                                        [11])[0]) < 2e-4
        assert _err(out[3], _ref_logits(weights, cfg, b + [tb],
                                        [37])[0]) < 2e-4
    finally:
        predictor.free_slot_pages(0)
        predictor.free_slot_pages(3)


def test_a_freed_and_reused_slot_reads_no_stale_index_keys(predictor,
                                                           weights, cfg):
    """Whatever a former owner left in the pages (here: every row of
    every pool set to 7, as a long stream's keys would be somewhere): the
    seed writes a slot's pages whole, the steps score and attend rows
    under ``lens`` alone."""
    for name in predictor.cache_vars:
        old = predictor._scope.find_var(name)
        predictor._scope.set_var(name, jnp.full(old.shape, 7.0, old.dtype))
    short = _prompt(13, seed=32)
    # (the prefill runs the prompt's chunk on two pages it borrows from
    # the free list and hands back)
    borrowed = list(predictor._free_list[:2])
    logits = _admit(predictor, 2, short, horizon=30)
    try:
        toks, tok = list(short), int(np.argmax(logits))
        for _ in range(3):
            out = _step(predictor, {2: (tok, len(toks))})[2]
            toks.append(tok)
            assert _err(out, _ref_logits(weights, cfg, toks,
                                         [len(toks) - 1])[0]) < 2e-4
            tok = int(np.argmax(out))
        # the pages past the prompt were seeded with zeros, keys too, and
        # nobody else's page was touched
        pool = np.asarray(predictor._scope.find_var("lat0_paged_ik"))
        mine = predictor._slot_pages[2]
        assert not pool[mine[-1]].any()
        others = [i for i in range(pool.shape[0])
                  if i not in mine and i not in borrowed]
        assert (pool[others] == 7.0).all()
    finally:
        predictor.free_slot_pages(2)


def test_the_pools_hold_a_latent_row_a_layer_and_a_key_a_full_layer(
        predictor, cfg):
    assert predictor.cache_vars == [f"lat{i}_paged_c" for i in range(5)] \
        + ["lat0_paged_ik", "lat4_paged_ik"]
    assert predictor.cache_row_bytes == (5 * 128 + 2 * 16) * 4
    assert predictor.sparse_attention == {"top_k": TOPK, "indexers": 2}
    block = predictor._dec_prog.global_block()
    assert block.var("lat4_paged_ik").shape == (SLOTS * 8, PAGE_LEN, 16)
    assert not block.has_var("lat1_paged_ik")


def test_decode_steps_and_prefills_count_their_selections(predictor):
    from paddle_tpu.obs import trace as ptrace
    logits = _admit(predictor, 0, _prompt(5, seed=41))
    _admit(predictor, 1, _prompt(30, seed=42))
    names = ["gen.dsa.rows_scored", "gen.dsa.rows_selected",
             "gen.dsa.selections", "gen.dsa.identity_selections"]
    before = [profiler.runtime_metrics.counter(n) for n in names]
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
    # two indexers: slot 0 scores 6 rows and keeps them all, slot 1 scores
    # 31 and keeps 8
    assert [b - a for a, b in zip(before, after)] == [
        2 * 37, 2 * 14, 2 * 2, 2 * 1]
    step = next(s for s in spans if s["name"] == "gen.decode_step")
    assert step["attrs"]["dsa_rows_scored"] == 74
    assert step["attrs"]["dsa_rows_selected"] == 28
    pre = next(s for s in spans if s["name"] == "gen.prefill")
    # 20 rows, one chunk here: 1 + 2 + ... + 20 scored, 1 + ... + 8 + 12
    # x 8 kept
    assert pre["attrs"]["dsa_rows_scored"] == 2 * 210
    assert pre["attrs"]["dsa_rows_selected"] == 2 * (36 + 96)
    # a prompt's chunks sum to its whole triangle
    parts = [predictor._chunk_selections(a, b - a)
             for a, b in ((0, 16), (16, 32), (32, 37))]
    assert sum(p["dsa_rows_scored"] for p in parts) == 2 * 37 * 38 // 2
    assert sum(p["dsa_rows_selected"] for p in parts) \
        == 2 * (36 + 29 * 8)


# -- a configuration without an indexer builds what it built ------------------------

def _op_list(program):
    return [(op.type, sorted(op.inputs), sorted(op.outputs),
             sorted((k, repr(v)) for k, v in op.attrs.items()))
            for op in program.global_block().ops]


def test_a_configuration_without_indexer_keys_builds_the_same_programs():
    """``kimi_k2.6_text``'s path: no ``index_topk``, no sparse op, no
    second pool, no ``Select`` input, whatever else the config carries."""
    plain = {k: v for k, v in toy_config().items()
             if not k.startswith("index") and k != "layer_offset"
             and k != "mlp_layer_types"}
    hp = _hp(plain)
    assert hp.full_layers == [] and hp.indexer(0) is None
    assert latent_moe.paged_cache_var_names(hp) == [
        f"lat{i}_paged_c" for i in range(5)]
    built = []
    for build in (lambda: latent_moe.build_chunk_program(hp, 4, 8, 32),
                  lambda: latent_moe.build_paged_decode_program(
                      hp, 4, 8, 32)):
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            build()
        ops = _op_list(main)
        assert not any(t.startswith("dsa_") for t, *_ in ops)
        assert not any("Select" in ins for _, ins, *_ in ops)
        assert not any("select_top_k" in dict(a) for *_, a in ops)
        built.append([t for t, *_ in ops])
    # and the sparse one differs from it by the sparse ops alone
    sparse = _hp(toy_config(mlp_layer_types=None, layer_offset=0,
                            indexer_types=["full"] + ["shared"] * 4))
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        latent_moe.build_chunk_program(sparse, 4, 8, 32)
    types = [t for t, *_ in _op_list(main)]
    kept = [t for t in types if not t.startswith("dsa_")]
    assert types.count("dsa_index_chunk") == types.count("dsa_select") == 1
    assert kept == built[0]


# -- contract, typecheck, cost ---------------------------------------------------------

def test_the_bundle_checks_and_every_new_op_has_its_rules(bundle_dir):
    import json
    from paddle_tpu.analysis import check_gen_bundle, typecheck
    from paddle_tpu.analysis.analyzer import lint_program
    from paddle_tpu.analysis.distributed import load_saved_program
    new = {"dsa_index_chunk", "dsa_index_paged", "dsa_select"}
    assert new | {"dsa_index"} <= set(typecheck._RULES)
    assert new | {"dsa_index"} <= cost.covered_op_types()
    pre = load_saved_program(os.path.join(bundle_dir, "prefill"))
    dec = load_saved_program(os.path.join(bundle_dir, "decode"))
    with open(os.path.join(bundle_dir, "gen_meta.json")) as f:
        meta = json.load(f)
    assert check_gen_bundle(pre, dec, meta) == []
    # a key pool that drifted from its indexer's width fails the contract
    drifted = dict(meta, cache_vars=meta["cache_vars"][:-1])
    assert any(d.code == "PTA019"
               for d in check_gen_bundle(pre, dec, drifted))
    seen = set()
    for prog, feeds, fetches in (pre, dec):
        result = lint_program(prog, feed_names=feeds, fetch_names=fetches)
        assert not result.errors, [d.message for d in result.errors]
        seen |= {op.type for op in prog.global_block().ops}
    assert new <= seen
    assert not cost.estimate(dec[0], paged_live_rows=24).uncovered
    # a chunk's index scores and selection grow with its rows x the
    # page bucket's rows, past top_k rows in the bucket
    def flops(rows, pages, only):
        by_type = cost.estimate_at(
            pre[0], {n: [1, pages if n == "gen_page_table" else rows]
                     for n in pre[1]}).by_op_type()
        return sum(by_type[t]["flops"] for t in only)

    sparse = ("dsa_index_chunk", "dsa_select")
    proj = 2 * (48 * 64 + 64 * 16 + 64 * 4)
    assert flops(8, 1, sparse) == 2 * 8 * proj    # the identity: no pairs
    for rows, pages in ((8, 2), (16, 2), (16, 6)):
        # two indexers: 4 heads x 16 lanes, 70 a score
        assert flops(rows, pages, sparse) - 2 * rows * proj \
            == 2 * rows * pages * PAGE_LEN * (2 * 4 * 16 + 70)
    # the whole-sequence form stays the training forward's, with its rules
    train = fluid.Program()
    with fluid.program_guard(train, fluid.Program()):
        latent_moe.latent_moe_train_program(
            16, latent_moe.LatentMoEConfig.from_dict(toy_config()))
    assert {"dsa_index", "dsa_select", "mla_attention"} <= {
        op.type for op in train.global_block().ops}
    assert not lint_program(train).errors


def test_a_selection_of_the_wrong_width_is_a_type_error():
    from paddle_tpu.analysis.analyzer import lint_program
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        data = lambda n, s, d="float32": fluid.layers.data(
            name=n, shape=s, dtype=d, append_batch_size=False)
        block = main.global_block()
        pool = block.create_var(name="pool", shape=[32, 8, 128],
                                dtype="float32")
        pool.persistable = True
        sel = decoder.op(
            "dsa_select", {"Scores": data("scores", [4, 1, 24]),
                           "Lens": data("lens", [4, 1], "int32")},
            {"Select": "int32"}, {"top_k": 8})["Select"]
        decoder.op(
            "paged_attention_latent",
            {"Q": data("q", [4, 1, 2 * 128]), "Row": data("row", [4, 1, 128]),
             "Cache": pool, "PageTable": data("table", [4, 2], "int32"),
             "Lens": block.var("lens"), "Select": sel},
            {"Out": "float32", "CacheOut": pool},
            {"n_head": 2, "v_width": 32, "scale": 1.0, "select_top_k": 8})
    result = lint_program(main)
    assert any(d.code == "PTA006" and "Select" in d.message
               for d in result.errors), [d.message for d in result.errors]


# -- the share of an expert-parallel deployment ------------------------------------------

def test_the_shares_of_one_layer_add_up_to_the_uncut_layer(weights):
    """The routed parts of all four shares, plus the shared expert once,
    are the uncut reference's layer."""
    full = toy_config()
    h = jax.random.normal(jax.random.PRNGKey(3), (9, 64))
    p = lambda name, cast=True: weights[f"lat1_{name}"]
    want = ref.moe(h, p, full, jnp.float32)
    shared = ref._gated(h, p("sh_gate.w"), p("sh_up.w"), p("sh_down.w"))
    idx, w = moe_ops.moe_route(h, p("gate.w"), p("gate.bias"), 2, 2.5, True)
    total, landed = np.asarray(shared), 0
    for share in range(4):
        sl = slice(4 * share, 4 * share + 4)
        part, stats = moe_ops.moe_experts_gated(
            h, idx, w, p("wg")[sl], p("wu")[sl], p("wd")[sl],
            expert_offset=4 * share, routed=True)
        cut = dict(full, experts_held=4, expert_offset=4 * share)
        cut_p = lambda name, cast=True, sl=sl: (
            weights[f"lat1_{name}"][sl] if name in ("wg", "wu", "wd")
            else weights[f"lat1_{name}"])
        assert np.allclose(part, ref.moe(h, cut_p, cut, jnp.float32,
                                         shared=False), atol=2e-5)
        total = total + np.asarray(part)
        landed += int(stats[0])
    assert landed == 9 * 2                  # every assignment, once
    assert np.allclose(total, want, atol=5e-5)


def test_config_takes_the_published_keys():
    import json
    with open(os.path.join(BENCH, "configs", "glm_5.2.json")) as f:
        published = json.load(f)
    hp = latent_moe.LatentMoEConfig.from_dict(published)
    assert (hp.hidden_size, hp.num_attention_heads, hp.kv_lora_rank,
            hp.qk_rope_head_dim, hp.q_lora_rank) == (6144, 64, 512, 64, 2048)
    assert (hp.qk_nope_head_dim, hp.v_head_dim) == (192, 256)
    assert (hp.index_topk, hp.index_n_heads, hp.index_head_dim) == (
        2048, 32, 128)
    assert hp.held == 8 and hp.n_routed_experts == 256
    assert hp.rope_theta == 8000000 and hp.rope_attrs["factor"] == 1.0
    assert hp.softmax_scale == pytest.approx(256 ** -0.5)
    assert [hp.indexer(i) for i in range(5)] == [
        "full", "shared", "shared", "shared", "full"]
    assert hp.moe_layers == [1, 2, 3, 4] and hp.latent_row == 640
    assert latent_moe.paged_cache_var_names(hp) == [
        f"lat{i}_paged_c" for i in range(5)] + ["lat0_paged_ik",
                                                "lat4_paged_ik"]
