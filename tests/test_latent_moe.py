"""Latent-attention / shared-expert MoE LM on the paged serving path
(``models/latent_moe.py``): the new ops against numbers worked by hand
and against each other (the absorbed decode = the expanded form, the
routed product = the dense one), the exported bundle (a prompt as a run
of chunks over the slot's own pool of ONE latent row a layer, cached
decode steps) against the plain NON-absorbed reference
(``benchmark/reference/kimi_k2_ref.py``) on seeded weights, the bundle
contract, the typecheck and cost rules, and the expert-parallel share
arithmetic.  Toy widths: d 64, 4 heads x (16 |
8), latent 32 + rope 8 (stored 128 wide), 16 experts top-2, 3 layers of
which the first dense."""

import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import latent_chunks
import paddle_tpu as fluid
from paddle_tpu import profiler
from paddle_tpu.analysis import cost, typecheck
from paddle_tpu.gen import GenPredictor, GenScheduler
from paddle_tpu.models import decoder, latent_moe
from paddle_tpu.ops import attention_ops, mla_ops, moe_ops

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from models import kimi_k2 as adapter             # noqa: E402
from reference import kimi_k2_ref as ref          # noqa: E402

SLOTS, PAGE_LEN, BUCKETS = 4, 8, [8, 16, 32, 48]
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}


def toy_config(**over):
    cfg = {"hidden_size": 64, "num_hidden_layers": 3,
           "first_k_dense_replace": 1, "vocab_size": 64,
           "rms_norm_eps": 1e-5, "num_attention_heads": 4,
           "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
           "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_theta": 50000,
           "rope_scaling": dict(YARN), "intermediate_size": 96,
           "moe_intermediate_size": 32, "n_routed_experts": 16,
           "n_shared_experts": 1, "num_experts_per_tok": 2,
           "routed_scaling_factor": 2.827, "norm_topk_prob": True,
           "experts_held": 16, "expert_offset": 0}
    cfg.update(over)
    return cfg


def _export(path, cfg, dtype="float32"):
    hp = latent_moe.LatentMoEConfig.from_dict(cfg)
    hp.dtype, hp.max_len = dtype, 64
    latent_moe.export_latent_model(path, hp, num_slots=SLOTS,
                                   prompt_buckets=BUCKETS,
                                   page_len=PAGE_LEN)
    return path


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
    # the seeded bfloat16 VALUES, held in float32: program and reference
    # then compute the same function to float32 rounding.  The toy's
    # correction bias is made to matter (the seeded one sits on the
    # sigmoid's foot at the published widths)
    w = {k: v.astype(jnp.float32)
         for k, v in adapter.seeded_weights(cfg, 7).items()}
    for i in (1, 2):
        w[f"lat{i}_gate.w"] = w[f"lat{i}_gate.w"].at[0].set(0.0)
        w[f"lat{i}_gate.bias"] = jax.random.uniform(
            jax.random.PRNGKey(i), (16,), jnp.float32, -0.05, 0.05)
    return w


@pytest.fixture(scope="module")
def bundle_dir(tmp_path_factory, cfg):
    return _export(str(tmp_path_factory.mktemp("latent") / "bundle"), cfg)


@pytest.fixture(scope="module")
def predictor(bundle_dir, weights):
    p = GenPredictor(bundle_dir)
    _install(p, weights)
    p.warmup()
    return p


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(1, 64, size=n).tolist()


def _ref_logits(weights, cfg, ids, positions):
    return np.asarray(ref.forward_logits(
        weights, cfg, jnp.asarray(ids, jnp.int32),
        jnp.asarray(positions, jnp.int32)))


def _close(got, want, tol=2e-4):
    """Float32 program against the float32 reference on the same values:
    what is left is the order of float32 sums (the absorbed form adds
    the latent's 32 lanes where the expanded one adds a head's 16), a
    few 1e-6 of the logits' range; 2e-4 leaves two orders of room and is
    twenty times under what a wrong rotary pair or a dropped bias
    reads."""
    spread = float(want.max() - want.min())
    assert float(np.abs(np.asarray(got) - want).max()) <= tol * spread


def _step(predictor, live):
    """One decode step; ``live`` maps slot -> (token, rows so far)."""
    tokens, pos, lens = (np.zeros(SLOTS, np.int32) for _ in range(3))
    for slot, (tok, rows) in live.items():
        tokens[slot], pos[slot], lens[slot] = tok, rows, rows + 1
    return predictor.decode_step(tokens, pos, lens=lens)


def _admit(predictor, slot, prompt, horizon=16):
    logits, kv = predictor.prefill(prompt)
    predictor.alloc_slot_pages(slot, predictor.pages_needed(len(prompt),
                                                            horizon))
    assert predictor.write_slot(slot, kv, len(prompt)) == 0
    return logits


def _chunks(predictor, slot, prompt, horizon=8):
    """``slot``'s pages, then the prompt's chunks one by one, as the
    scheduler admits: a generator that yields after every chunk but the
    last and returns nothing; ``list()`` it to run it whole.  The last
    chunk's logits land in ``_chunks.logits[slot]``."""
    predictor.alloc_slot_pages(slot, predictor.pages_needed(len(prompt),
                                                            horizon))
    for a, b in predictor.chunk_spans(len(prompt)):
        _chunks.logits[slot] = np.asarray(
            predictor.prefill_chunk(slot, prompt[a:b], a))[0]
        yield a


_chunks.logits = {}


# -- numbers worked by hand -----------------------------------------------------

def test_yarn_frequencies_and_scale_against_numbers_worked_by_hand():
    f = mla_ops.yarn_frequencies(64, 50000.0, 64.0, 4096, 32.0, 1.0)
    # the pairs that make 32 and 1 turns in 4096 positions: 64 ln(4096 /
    # (32 x 2 pi)) / (2 ln 50000) = 8.91 -> 8 and 19.16 -> 20
    assert f.shape == (32,) and f[0] == 1.0
    assert f[8] == pytest.approx(50000 ** -0.25)             # ramp 0
    assert f[8] == pytest.approx(0.066874, rel=1e-4)
    assert f[14] == pytest.approx(0.0087939 * (0.5 + 0.5 / 64), rel=1e-4)
    assert f[20] == pytest.approx(50000 ** (-40 / 64) / 64)  # ramp 1
    assert f[31] == pytest.approx(50000 ** (-62 / 64) / 64)
    assert np.allclose(f, ref.yarn_frequencies(
        {"qk_rope_head_dim": 64, "rope_theta": 50000,
         "rope_scaling": YARN}))
    # no scaling: the plain ladder
    assert np.allclose(mla_ops.yarn_frequencies(8, 10000.0),
                       10000.0 ** (-np.arange(4) / 4))
    hp = latent_moe.LatentMoEConfig.from_dict(
        {"qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
         "rope_scaling": YARN})
    # 192^-1/2 x (0.1 ln 64 + 1)^2 = 0.0721688 x 1.415888^2
    assert mla_ops.yarn_mscale(64, 1) == pytest.approx(1.415888, rel=1e-6)
    assert hp.softmax_scale == pytest.approx(0.144680, rel=1e-5)
    assert hp.rope_attrs["mscale"] == 1.0
    assert hp.latent_row == 128
    hp.kv_lora_rank, hp.qk_rope_head_dim = 512, 64
    assert hp.latent_row == 640


def test_rope_turns_the_pairs_of_the_trailing_slice_only():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 2 * 12))
    pos = jnp.asarray([0, 1, 5])
    freqs = mla_ops.yarn_frequencies(4, 100.0)            # [1, 0.1]
    out = np.asarray(mla_ops.rope(x, pos, 2, 4, freqs)).reshape(3, 2, 12)
    xs = np.asarray(x).reshape(3, 2, 12)
    assert np.array_equal(out[..., :8], xs[..., :8])      # nope lanes
    assert np.allclose(out[0], xs[0])                     # position 0
    for t, p in enumerate([0, 1, 5]):
        for i, fr in enumerate([1.0, 0.1]):               # pair (i, i+2)
            a, b = xs[t, :, 8 + i], xs[t, :, 10 + i]
            c, s = math.cos(p * fr), math.sin(p * fr)
            assert np.allclose(out[t, :, 8 + i], a * c - b * s, atol=1e-6)
            assert np.allclose(out[t, :, 10 + i], b * c + a * s, atol=1e-6)


def test_swiglu_is_silu_of_the_gate_times_up():
    g = jnp.asarray([[-2.0, 0.0, 3.0]])
    u = jnp.asarray([[1.5, 7.0, -1.0]])
    want = np.asarray(g) / (1 + np.exp(-np.asarray(g))) * np.asarray(u)
    assert np.allclose(mla_ops.swiglu(g, u), want, atol=1e-6)


# -- the two forms of the attention -----------------------------------------------

def _mla_case(T=13, H=4, L=32, nope=16, R=8, vd=16, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(k[0], (T, H * (nope + R)))
    latent = jnp.pad(jax.random.normal(k[1], (T, L + R)),
                     ((0, 0), (0, 128 - L - R)))
    w_kvb = jax.random.normal(k[2], (L, H * (nope + vd))) * 0.2
    return q, latent, w_kvb, dict(n_head=H, nope=nope, v_dim=vd)


@pytest.mark.parametrize("path", ["xla", "kernel"])
def test_the_absorbed_decode_equals_the_expanded_form(path):
    """The last row of the prefill's expanded attention = that row's
    absorbed query over the cached latent rows, through the pages."""
    T, PL, P = 13, 8, 2
    q, latent, w_kvb, dims = _mla_case(T)
    want = mla_ops.mla_attention(q, latent, w_kvb, jnp.ones(T), 4, 16, 8,
                                 16, 0.3, block=4)[-1]
    pool = jnp.zeros((6, PL, 128)).at[jnp.asarray([4, 1])].set(
        jnp.pad(latent, ((0, P * PL - T), (0, 0))).reshape(P, PL, 128))
    table = jnp.asarray([[0, 0], [4, 1]], jnp.int32)
    lens = jnp.asarray([[0], [T]], jnp.int32)
    q_lat = mla_ops.mla_absorb(jnp.stack([q[0], q[-1]]), w_kvb, side="q",
                               pad=128 - 40, **dims)
    assert q_lat.shape == (2, 4 * 128)
    q_lat = q_lat[:, None]
    if path == "xla":
        ctx = attention_ops._xla_latent_attention(q_lat, pool, table, lens,
                                                  4, 32, 0.3)
    else:
        ctx = attention_ops._pallas_paged_attention(
            q_lat, pool, None, table, lens, 4, 0.3, interpret=True,
            v_width=32, block_pages=1)
    assert not np.asarray(ctx[0]).any()                   # the free slot
    got = mla_ops.mla_absorb(ctx[1], w_kvb, side="o", **dims)[0]
    assert np.allclose(got, want, atol=2e-5)


def test_the_flash_prefill_equals_the_composed():
    """The flash kernel over the expanded heads (keys wider than
    values, pad rows masked) = the XLA form, on the real rows."""
    q, latent, w_kvb, _ = _mla_case(T=32)
    mask = (jnp.arange(32) < 21).astype(jnp.float32)
    args = (q, latent, w_kvb, mask, 4, 16, 8, 16, 0.3)
    composed = mla_ops.mla_attention(*args, flash=False)
    flash = mla_ops.mla_attention(*args, flash=True, interpret=True)
    assert np.allclose(composed[:21], flash[:21], atol=2e-6)


@pytest.mark.parametrize("kernel", [True, False],
                         ids=["kernel", "composed"])
@pytest.mark.parametrize("start, n", latent_chunks.STARTS,
                         ids=latent_chunks.START_IDS)
def test_a_chunk_through_the_op_is_the_whole_sequences_rows(start, n, kernel):
    """``mla_attention_chunk`` at heads of 128 | 64 lanes and values of
    128, no selection, EXPANDED (the causal flash kernel at one query
    head a K/V head, a (head, key block) made from the block's latent
    rows in the kernel; and the composed form over the expanded bucket)
    against ``mla_attention`` over the whole prompt."""
    latent_chunks.chunk_is_the_whole_sequence(128, 64, 128, start, n,
                                              kernel=kernel)


def test_the_chunk_kernels_matrices_pass_the_rotary_key_as_it_is():
    """``chunk_weights``: a cached row times head ``h``'s columns is
    ``[c_kv W_k[h] | k_rope | 0]`` to the bit of ``mla_expand``'s, in
    whole 128-lane tiles, whatever the row's pad lanes' neighbours."""
    T, H, L, nope, R, vd, W = 9, 3, 32, 16, 8, 16, 128
    _, latent, w_kvb, _ = _mla_case(T, H, L, nope, R, vd)
    w_k, w_v = mla_ops.chunk_weights(w_kvb, H, nope, R, vd, W)
    Dk = 128
    assert (w_k.shape, w_v.shape) == ((W, H * Dk), (L, H * vd))
    k_nope, k_rope, v = mla_ops.mla_expand(latent, w_kvb, H, nope, R, vd,
                                           jnp.float32)
    keys = (latent @ w_k).reshape(T, H, Dk)
    assert np.allclose(keys[..., :nope], k_nope, atol=1e-6)
    assert np.array_equal(keys[..., nope:nope + R],
                          np.broadcast_to(k_rope[:, None], (T, H, R)))
    assert not np.asarray(keys[..., nope + R:]).any()
    assert np.allclose((latent[:, :L] @ w_v).reshape(T, H, vd), v,
                       atol=1e-6)


def test_the_latent_kernel_reads_one_pool_in_bfloat16():
    S, H, W, V, PL, P = 3, 4, 128, 32, 16, 4
    k = jax.random.split(jax.random.PRNGKey(1), 2)
    pool = jax.random.normal(k[0], (S * P, PL, W)).astype(jnp.bfloat16)
    q = jax.random.normal(k[1], (S, 1, H * W)).astype(jnp.bfloat16)
    table = jnp.asarray(np.random.RandomState(0).permutation(S * P)
                        .reshape(S, P), jnp.int32)
    lens = jnp.asarray([[37], [0], [64]], jnp.int32)
    want = attention_ops._xla_latent_attention(q, pool, table, lens, H, V,
                                               0.2)
    for block_pages in (None, 2):
        got = attention_ops._pallas_paged_attention(
            q, pool, None, table, lens, H, 0.2, interpret=True, v_width=V,
            block_pages=block_pages)
        assert got.shape == (S, 1, H * V) and got.dtype == jnp.bfloat16
        # the weights are rounded to bfloat16 for the second product
        assert float(jnp.abs(got.astype(jnp.float32)
                             - want.astype(jnp.float32)).max()) < 0.02
    ok = attention_ops._paged_kernel_ok
    assert ok(64, 64 * 640, 16, False, 640, 2, v_width=512)
    assert not ok(64, 64 * 576, 16, False, 576, 2, v_width=512)  # 4.5 vregs
    assert not ok(64, 64 * 640, 8, False, 640, 2, v_width=512)   # half tile
    assert attention_ops._paged_blocking(256, 16, 640, 2, True) == (32, 512)


# -- the routed experts -------------------------------------------------------------

def _experts_case(T=21, d=64, F=48, E=4, all_experts=16, k=2, seed=0):
    key = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(key[0], (T, d))
    wg, wu = (jax.random.normal(key[i], (E, d, F)) * 0.1 for i in (1, 2))
    wd = jax.random.normal(key[3], (E, F, d)) * 0.1
    idx, w = moe_ops.moe_route(x, jax.random.normal(key[4], (d, all_experts)),
                               jnp.zeros(all_experts), k, 2.5, True)
    return x, idx, w, wg, wu, wd


@pytest.mark.parametrize("offset,T", [(0, 21), (4, 21), (12, 21), (4, 700)])
def test_the_routed_product_is_the_dense_one(offset, T):
    """700 rows x top-2 are 1400 sorted rows: the trips are 128-row
    tiles over the ones that landed on a held expert, no trip for the
    rest."""
    x, idx, w, wg, wu, wd = _experts_case(T=T)
    live = jnp.arange(T) % 5 != 0
    dense, s0 = moe_ops.moe_experts_gated(x, idx, w, wg, wu, wd, offset,
                                          live, routed=False)
    routed, s1 = moe_ops.moe_experts_gated(x, idx, w, wg, wu, wd, offset,
                                           live, routed=True)
    assert np.allclose(dense, routed, atol=4e-6)
    assert np.array_equal(s0, s1)
    if T > 21:
        return
    # the plain sum over assignments
    want = np.zeros((21, 64), np.float32)
    for t in range(21):
        for e, wt in zip(np.asarray(idx[t]) - offset, np.asarray(w[t])):
            if 0 <= e < 4 and bool(live[t]):
                h = jax.nn.silu(x[t] @ wg[e]) * (x[t] @ wu[e])
                want[t] += wt * np.asarray(h @ wd[e])
    assert np.allclose(routed, want, atol=2e-5)


def test_an_expert_with_no_token_and_a_token_with_no_expert_held():
    x, _, _, wg, wu, wd = _experts_case(T=6)
    # experts 1 and 3 of the 4 held get no token; token 2 chooses absent
    # experts only; token 4 chooses expert 0 twice over two slots? no:
    # a router never does; it chooses 0 and 2
    idx = jnp.asarray([[4, 6], [6, 9], [9, 11], [4, 15], [4, 6], [15, 6]],
                      jnp.int32)
    w = jnp.full((6, 2), 0.5)
    out, stats = moe_ops.moe_experts_gated(x, idx, w, wg, wu, wd, 4,
                                           routed=True)
    dense, _ = moe_ops.moe_experts_gated(x, idx, w, wg, wu, wd, 4,
                                         routed=False)
    assert np.allclose(out, dense, atol=2e-6)
    assert not np.asarray(out[2]).any()
    assert np.asarray(out[0]).any()
    # held experts 4..7: assignments to 4 (x3) and 6 (x4)
    assert stats.tolist() == [7, 2, 4]


def test_the_correction_bias_moves_the_choice_and_not_the_weights():
    d, E = 16, 8
    x = jax.random.normal(jax.random.PRNGKey(0), (5, d))
    gate = jax.random.normal(jax.random.PRNGKey(1), (d, E)) * 0.3
    idx0, w0 = moe_ops.moe_route(x, gate, jnp.zeros(E), 2, 2.0, True)
    bias = jnp.zeros(E).at[5].set(10.0)       # expert 5 always chosen
    idx1, w1 = moe_ops.moe_route(x, gate, bias, 2, 2.0, True)
    assert (np.asarray(idx1) == 5).any(axis=1).all()
    assert not (np.asarray(idx0) == 5).any(axis=1).all()
    scores = np.asarray(jax.nn.sigmoid(x @ gate))
    for t in range(5):
        chosen = scores[t, np.asarray(idx1[t])]
        # weights from the scores alone: the bias is not in them
        assert np.allclose(w1[t], 2.0 * chosen / chosen.sum(), rtol=1e-5)
    ref_idx, ref_w = ref.route(x, lambda n: {"gate.w": gate,
                                             "gate.bias": bias}[n],
                               {"num_experts_per_tok": 2,
                                "norm_topk_prob": True,
                                "routed_scaling_factor": 2.0})
    assert np.array_equal(ref_idx, idx1) and np.allclose(ref_w, w1)


# -- the bundle on the serving path -----------------------------------------------------

@pytest.mark.parametrize("n", [5, 8, 11, 20, 32])
def test_prefill_matches_the_reference_in_every_bucket(predictor, weights,
                                                       cfg, n):
    prompt = _prompt(n, seed=n)
    logits, kv = predictor.prefill(prompt)
    _close(logits, _ref_logits(weights, cfg, prompt, [n - 1])[0])
    # ONE latent row a layer, stored 128 wide, zeros on the pad rows
    assert len(kv) == len(predictor.cache_vars) == 3
    bucket = predictor._bucket(n)
    for row in kv:
        assert row.shape == (1, bucket, 128)
        assert not np.asarray(row[0, n:]).any()
        assert not np.asarray(row[0, :, 40:]).any()
        assert np.asarray(row[0, :n, :40]).any()


def test_prefill_seed_and_cached_steps_match_the_reference_everywhere(
        predictor, weights, cfg):
    prompt, steps = _prompt(11, seed=1), 14
    logits = _admit(predictor, 1, prompt, steps + 1)
    try:
        seq = list(prompt)
        got = [logits]
        for _ in range(steps):
            seq.append(int(np.argmax(got[-1])))
            got.append(_step(predictor, {1: (seq[-1], len(seq) - 1)})[1])
        want = _ref_logits(weights, cfg, seq,
                           range(len(prompt) - 1, len(seq)))
        for g, w in zip(got, want):
            _close(g, w)
    finally:
        predictor.free_slot_pages(1)


def test_a_program_that_forgets_the_bias_fails_the_comparison(
        predictor, weights, cfg):
    prompt = _prompt(20, seed=3)
    no_bias = {k: (jnp.zeros_like(v) if k.endswith("gate.bias") else v)
               for k, v in weights.items()}
    want = _ref_logits(no_bias, cfg, prompt, [19])[0]
    logits, _ = predictor.prefill(prompt)
    spread = float(want.max() - want.min())
    assert float(np.abs(logits - want).max()) > 4e-3 * spread


def test_two_slots_of_different_lengths_share_the_pool(predictor, weights,
                                                       cfg):
    prompts = {0: _prompt(19, seed=2), 3: _prompt(6, seed=3)}
    seqs, last = {}, {}
    try:
        for slot, prompt in prompts.items():
            last[slot] = _admit(predictor, slot, prompt, 8)
            seqs[slot] = list(prompt)
        for _ in range(6):
            for slot in seqs:
                seqs[slot].append(int(np.argmax(last[slot])))
            out = _step(predictor, {s: (seqs[s][-1], len(seqs[s]) - 1)
                                    for s in seqs})
            for slot in seqs:
                last[slot] = out[slot]
                _close(out[slot], _ref_logits(
                    weights, cfg, seqs[slot], [len(seqs[slot]) - 1])[0])
    finally:
        for slot in prompts:
            predictor.free_slot_pages(slot)


def test_a_freed_and_readmitted_slot_carries_nothing_over(predictor, weights,
                                                          cfg):
    long = _prompt(30, seed=4)
    _admit(predictor, 2, long, 20)
    for t in range(5):
        _step(predictor, {2: (t + 1, len(long) + t)})
    predictor.free_slot_pages(2)
    short = _prompt(7, seed=5)
    logits = _admit(predictor, 2, short, 8)
    try:
        tok = int(np.argmax(logits))
        step = _step(predictor, {2: (tok, len(short))})[2]
        _close(step, _ref_logits(weights, cfg, short + [tok],
                                 [len(short)])[0])
    finally:
        predictor.free_slot_pages(2)


def test_the_pool_holds_one_latent_row_a_token_a_layer(predictor):
    from paddle_tpu.obs import perf
    census = perf.hbm_census(scope=predictor._scope)
    # slots x rows x layers x the stored row x float32 here, + the table
    pool = SLOTS * 64 * 3 * 128 * 4
    assert census["kv_pages"] == pool + predictor._page_table.nbytes
    assert census["gen_state"] == 0 and not predictor.state_vars
    assert predictor.cache_row_bytes == 3 * 128 * 4
    block = predictor._dec_prog.global_block()
    # no K or V expanded to heads is ever persisted: the pools are the
    # only state of the decode program beside its parameters
    assert sorted(n for n, v in block.vars.items()
                  if v.persistable and "paged" in n) == \
        sorted(predictor.cache_vars)
    # a step writes this token's row and no other
    before = [np.asarray(predictor._scope.find_var(n)).copy()
              for n in predictor.cache_vars]
    _admit(predictor, 0, _prompt(9, seed=6), 4)
    try:
        seeded = [np.asarray(predictor._scope.find_var(n)).copy()
                  for n in predictor.cache_vars]
        _step(predictor, {0: (3, 9)})
        page = predictor._slot_pages[0][1]
        for b, s, name in zip(before, seeded, predictor.cache_vars):
            now = np.asarray(predictor._scope.find_var(name))
            changed = np.argwhere((now != s).any(axis=-1))
            assert changed.tolist() == [[page, 1]]        # row 9
            assert not now[page, 1, 40:].any()
    finally:
        predictor.free_slot_pages(0)


def test_decode_step_counts_the_experts_it_touched(predictor):
    from paddle_tpu.obs import trace
    m = profiler.runtime_metrics
    _admit(predictor, 0, _prompt(5, seed=10), 4)
    _admit(predictor, 2, _prompt(9, seed=11), 4)
    before = m.counter("gen.moe.assignments")
    trace.enable(256)
    trace.clear()
    try:
        _step(predictor, {0: (3, 5), 2: (4, 9)})
        spans = [s for s in trace.snapshot_spans()
                 if s["name"] == "gen.decode_step"]
    finally:
        trace.disable()
        trace.clear()
        predictor.free_slot_pages(0)
        predictor.free_slot_pages(2)
    attrs = spans[-1]["attrs"]
    # every expert is held: both live slots' top-2 land in both expert
    # layers, the free slots' none
    assert attrs["live"] == 2 and attrs["moe_assignments"] == 8
    assert 2 <= attrs["moe_experts_touched"] <= 8
    assert 1 <= attrs["moe_max_load"] <= 2
    assert m.counter("gen.moe.assignments") - before == 8


def _ref_greedy(weights, cfg, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(np.argmax(_ref_logits(weights, cfg, seq,
                                             [len(seq) - 1])[0])))
    return seq[len(prompt):]


def test_scheduler_streams_the_references_greedy_tokens(predictor, weights,
                                                        cfg):
    from paddle_tpu.obs import trace
    sched = GenScheduler(predictor)
    trace.enable(1024)
    trace.clear()
    try:
        prompts = [_prompt(7, seed=20), _prompt(18, seed=21)]
        streams = [sched.submit(p, max_new_tokens=6) for p in prompts]
        for p, s in zip(prompts, streams):
            assert list(s) == _ref_greedy(weights, cfg, p, 6)
        seeds = [s for s in trace.snapshot_spans()
                 if s["name"] == "gen.seed_slot"]
    finally:
        trace.disable()
        trace.clear()
        sched.close()
    assert len(seeds) == 2
    for s in seeds:
        assert s["attrs"]["pages"] >= 1
        assert s["attrs"]["row_bytes"] == predictor.cache_row_bytes


# -- a prompt as a run of chunks over the slot's own pool -------------------------------

@pytest.fixture(scope="module")
def chunked(tmp_path_factory, cfg, weights):
    """The bundle with chunk rungs of 8 and 16 rows (``predictor``'s are
    24 and 48: every prompt here is ONE chunk there): a prompt of 45
    rows is three chunks and walks page buckets of 2, 4 and 8 pages."""
    was, decoder.CHUNK_ROWS = decoder.CHUNK_ROWS, 16
    try:
        path = _export(str(tmp_path_factory.mktemp("latent") / "chunked"),
                       cfg)
    finally:
        decoder.CHUNK_ROWS = was
    p = GenPredictor(path)
    assert p.prefill_chunks == [8, 16]
    _install(p, weights)
    p.warmup()
    return p


@pytest.mark.parametrize("n", [5, 16, 17, 29, 45], ids=[
    "one_chunk", "a_chunk_edge", "a_row_past_it", "two_chunks",
    "every_page_bucket"])
def test_a_prompt_in_chunks_is_the_prompt_in_one(predictor, chunked, weights,
                                                 cfg, n):
    """The last row's logits against the reference and every layer's
    page rows, chunk by chunk (8- and 16-row rungs) against the single
    pass (one 24- or 48-row chunk); then cached steps over the rows the
    chunks wrote, in place."""
    prompt = _prompt(n, seed=100 + n)
    spans = chunked.chunk_spans(n)
    assert len(spans) == -(-n // 16) and len(predictor.chunk_spans(n)) == 1
    assert [chunked._chunk_shape(a, b - a) for a, b in spans][-1] \
        == (8 if (n - 1) % 16 < 8 else 16,
            next(p for p in (1, 2, 4, 8) if p * PAGE_LEN >= n))
    whole, parts = predictor.prefill(prompt), chunked.prefill(prompt)
    want = _ref_logits(weights, cfg, prompt, [n - 1])[0]
    _close(whole[0], want)
    _close(parts[0], want)
    assert len(parts[1]) == len(whole[1]) == 3
    for got, row in zip(parts[1], whole[1]):
        assert got.shape == row.shape
        assert np.allclose(got, row, atol=2e-5)
        # the prompt's rows and zeros behind; 40 lanes of 128 hold values
        assert np.asarray(got)[0, :n, :40].any(axis=-1).all()
        assert not np.asarray(got)[0, n:].any()
    assert chunked.free_pages == chunked.num_pages
    list(_chunks(chunked, 1, prompt, horizon=4))
    try:
        _close(_chunks.logits[1], want)
        seq = list(prompt)
        tok = int(np.argmax(_chunks.logits[1]))
        for _ in range(3):
            out = _step(chunked, {1: (tok, len(seq))})[1]
            seq.append(tok)
            _close(out, _ref_logits(weights, cfg, seq, [len(seq) - 1])[0])
            tok = int(np.argmax(out))
    finally:
        chunked.free_slot_pages(1)


def test_two_slots_admitted_alternately_share_the_pool(chunked, weights,
                                                       cfg):
    """Two prompts of 45 and 21 rows whose chunks alternate, as two
    admissions beside each other never do but any two slots' pages must
    allow: each chunk reads its own slot's rows and no other's."""
    prompts = {0: _prompt(45, seed=301), 3: _prompt(21, seed=302)}
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
        seqs = {s: list(p) for s, p in prompts.items()}
        last = dict(_chunks.logits)
        for slot, p in prompts.items():
            _close(last[slot], _ref_logits(weights, cfg, p,
                                           [len(p) - 1])[0])
        for _ in range(3):
            for slot in seqs:
                seqs[slot].append(int(np.argmax(last[slot])))
            out = _step(chunked, {s: (seqs[s][-1], len(seqs[s]) - 1)
                                  for s in seqs})
            for slot in seqs:
                last[slot] = out[slot]
                _close(out[slot], _ref_logits(
                    weights, cfg, seqs[slot], [len(seqs[slot]) - 1])[0])
    finally:
        for slot in prompts:
            chunked.free_slot_pages(slot)


def test_a_chunk_writes_its_real_rows_and_reads_no_stale_one(chunked,
                                                             weights, cfg):
    """Whatever a former owner left in the pages (here every row of
    every pool set to 7): a chunk writes its REAL rows at their places
    and nothing else (a pad row lands nowhere, nobody else's page is
    touched), and neither it nor the steps behind it read a row past
    the slot's own."""
    for name in chunked.cache_vars:
        old = chunked._scope.find_var(name)
        chunked._scope.set_var(name, jnp.full(old.shape, 7.0, old.dtype))
    prompt = _prompt(21, seed=303)           # 16 + 5 (of 8: 3 pad rows)
    try:
        list(_chunks(chunked, 2, prompt, horizon=4))
        _close(_chunks.logits[2], _ref_logits(weights, cfg, prompt,
                                              [20])[0])
        mine = chunked._slot_pages[2]
        for name in chunked.cache_vars:
            pool = np.asarray(chunked._scope.find_var(name))
            rows = pool[mine].reshape(-1, pool.shape[-1])
            assert (rows[:21] != 7.0).any(axis=-1).all()
            assert (rows[21:] == 7.0).all()
            others = [i for i in range(pool.shape[0]) if i not in mine]
            assert (pool[others] == 7.0).all()
        seq, tok = list(prompt), int(np.argmax(_chunks.logits[2]))
        for _ in range(2):
            out = _step(chunked, {2: (tok, len(seq))})[2]
            seq.append(tok)
            _close(out, _ref_logits(weights, cfg, seq, [len(seq) - 1])[0])
            tok = int(np.argmax(out))
    finally:
        chunked.free_slot_pages(2)
        for name in chunked.cache_vars:
            old = chunked._scope.find_var(name)
            chunked._scope.set_var(name, jnp.zeros(old.shape, old.dtype))


def test_a_chunk_past_the_longest_prompt_is_refused_not_compiled(chunked):
    """The warm-up holds a rung over the page buckets from its own rows'
    to the longest prompt's and no other pair: what could run past them
    (a compile inside a serving window) raises instead."""
    assert chunked.max_prompt_len == 48 and chunked.page_buckets == [
        1, 2, 4, 8]
    assert chunked._chunk_shapes() == [(8, 1), (8, 2), (8, 4), (8, 8),
                                       (16, 2), (16, 4), (16, 8)]
    # a chunk's bucket is never smaller than its own rung
    assert chunked._chunk_shape(0, 3) == (8, 1)
    assert chunked._chunk_shape(0, 9) == (16, 2)
    assert chunked._chunk_shape(32, 16) == (16, 8)
    with pytest.raises(ValueError, match="max prompt length"):
        chunked._chunk_shape(48, 1)
    with pytest.raises(ValueError, match="largest"):
        chunked._chunk_shape(0, 17)


# -- the bundle's contract, typecheck and cost rules ----------------------------------------

def _bundle_parts(bundle_dir):
    from paddle_tpu.analysis.distributed import load_saved_program
    with open(os.path.join(bundle_dir, "gen_meta.json")) as f:
        meta = json.load(f)
    return (load_saved_program(os.path.join(bundle_dir, "prefill")),
            load_saved_program(os.path.join(bundle_dir, "decode")), meta)


def test_gen_meta_names_one_pool_a_layer_and_the_bundle_checks(bundle_dir):
    from paddle_tpu.analysis import check_gen_bundle
    pre, dec, meta = _bundle_parts(bundle_dir)
    assert meta["cache_vars"] == ["lat0_paged_c", "lat1_paged_c",
                                  "lat2_paged_c"]
    assert meta["state_vars"] == [] and len(meta["decode_stats"]) == 3
    # the prefill is ONE CHUNK of a prompt over the slot's own pages; the
    # rungs come from the bundle's shapes (no state a slot: no gen_slot)
    assert pre[1] == ["gen_ids", "gen_pos", "gen_mask", "gen_last",
                      "gen_page_table"] and len(pre[2]) == 1
    assert meta["prefill_chunks"] == [24, 48]
    assert dec[1] == ["gen_token", "gen_pos", "gen_page_table", "gen_lens"]
    assert check_gen_bundle(pre, dec, meta) == []
    drifted = dict(meta, cache_vars=meta["cache_vars"][:2])
    assert any(d.code == "PTA019"
               for d in check_gen_bundle(pre, dec, drifted))
    block = dec[0].global_block()
    block.var("lat1_paged_c").shape = (SLOTS * 8, PAGE_LEN, 64)
    assert any(d.code == "PTA019" and "share one array" in d.message
               for d in check_gen_bundle(pre, dec, meta))


def test_both_programs_typecheck_and_every_new_op_has_its_rules(bundle_dir):
    from paddle_tpu.analysis.analyzer import lint_program
    new = {"rope", "swiglu", "mla_attention", "mla_attention_chunk",
           "mla_absorb", "paged_attention_latent", "moe_experts_gated"}
    assert new <= set(typecheck._RULES) and new <= cost.covered_op_types()
    pre, dec, _ = _bundle_parts(bundle_dir)
    # the whole-sequence form stays the training forward's
    train = fluid.Program()
    with fluid.program_guard(train, fluid.Program()):
        _, train_feeds = latent_moe.latent_moe_train_program(
            8, latent_moe.LatentMoEConfig.from_dict(toy_config()))
    seen = set()
    for prog, feeds, fetches in (pre, dec, (train, train_feeds, None)):
        result = lint_program(prog, feed_names=feeds, fetch_names=fetches)
        assert not result.errors, [d.message for d in result.errors]
        seen |= {op.type for op in prog.global_block().ops}
    assert new <= seen
    assert "mla_attention" not in {op.type
                                   for op in pre[0].global_block().ops}
    # a chunk is charged its rows over the page bucket's, never a pool
    shapes = {n: [1, 16] for n in pre[1]}
    by_bucket = [cost.estimate_at(
        pre[0], dict(shapes, gen_page_table=[1, pages])).by_op_type()[
            "mla_attention_chunk"] for pages in (2, 4)]
    h, row, lat = 4, 128, 32
    for pages, got in zip((2, 4), by_bucket):
        pairs = 16 * 17 // 2 + 16 * (pages * PAGE_LEN - 16)
        assert got["flops"] == 3 * (2 * pages * PAGE_LEN * lat * h * 32
                                    + 2 * pairs * h * (24 + 16))
        assert got["bytes"] == 3 * 4 * (16 * h * (24 + 16)
                                        + (pages * PAGE_LEN + 32) * row)
    # the table's width is the one dynamic dim: priced at live rows
    report = cost.estimate(dec[0], paged_live_rows=24)
    assert not report.uncovered


def _latent_op_program(table_width):
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        layers = fluid.layers
        data = lambda n, s, d="float32": layers.data(
            name=n, shape=s, dtype=d, append_batch_size=False)
        q = data("q", [4, 1, 2 * 128])
        row = data("row", [4, 1, 128])
        table = data("table", [4, table_width], "int32")
        lens = data("lens", [4, 1], "int32")
        block = main.global_block()
        pool = block.create_var(name="pool", shape=[32, 8, 128],
                                dtype="float32")
        pool.persistable = True
        decoder.op("paged_attention_latent",
                   {"Q": q, "Row": row, "Cache": pool,
                    "PageTable": table, "Lens": lens},
                   {"Out": "float32", "CacheOut": pool},
                   {"n_head": 2, "v_width": 32, "scale": 1.0})
    return main


def test_the_paged_rules_charge_the_live_rows_when_they_are_known():
    narrow = cost.estimate(_latent_op_program(2)).total_flops
    wide = cost.estimate(_latent_op_program(8)).total_flops
    # from the program alone: slots x the bucket x heads x (row + value)
    assert narrow == 2 * 4 * 16 * 2 * (128 + 32)
    assert wide == 4 * narrow
    live = [cost.estimate(_latent_op_program(width),
                          paged_live_rows=10).total_flops
            for width in (2, 8, -1)]    # -1: the table's width unknown
    assert live == [2 * 4 * 10 * 2 * (128 + 32)] * 3
    # never more than the bucket holds
    assert cost.estimate(_latent_op_program(2),
                         paged_live_rows=99).total_flops == narrow


def test_a_latent_row_that_does_not_fit_the_pool_is_a_type_error():
    from paddle_tpu.analysis.analyzer import lint_program
    main = _latent_op_program(2)
    main.global_block().var("row").shape = (4, 1, 96)
    result = lint_program(main)
    assert any(d.code == "PTA006" and "pool's row" in d.message
               for d in result.errors)


def test_plan_page_buckets_prices_the_table_not_the_reads(predictor):
    edges = predictor.plan_page_buckets([5, 9, 17, 33, 60], max_edges=3)
    assert edges == sorted(edges) and 1 <= len(edges) <= 3
    assert edges[-1] == predictor.pages_per_slot
    cheap, dear = predictor.prefill_cost(5), predictor.prefill_cost(30)
    assert 0 < cheap < dear


def test_a_bfloat16_bundle_keeps_a_bfloat16_pool(tmp_path, cfg):
    path = _export(str(tmp_path / "bf16"), cfg, dtype="bfloat16")
    p = GenPredictor(path)
    w = adapter.seeded_weights(cfg, 7)
    _install(p, w)
    for name in p.cache_vars:
        assert str(p._scope.find_var(name).dtype) == "bfloat16"
    assert p.cache_row_bytes == 3 * 128 * 2
    prompt = _prompt(12, seed=8)
    logits = _admit(p, 0, prompt, 4)
    for row in (p._scope.find_var(n) for n in p.cache_vars):
        assert row.dtype == jnp.bfloat16
    tok = int(np.argmax(logits))
    step = _step(p, {0: (tok, 12)})[0]
    want = _ref_logits(w, cfg, prompt + [tok], [11, 12])
    # bfloat16 activations against the float32 reference: 1e-2 of the
    # range at these widths (read 3e-3 to 6e-3)
    _close(logits, want[0], tol=2e-2)
    _close(step, want[1], tol=2e-2)


# -- the share of an expert-parallel deployment -------------------------------------------------

def test_the_shares_of_one_layer_add_up_to_the_uncut_layer(weights):
    """The routed parts of all four shares, plus the shared expert
    once, are the uncut reference's layer."""
    full = toy_config()
    h = jax.random.normal(jax.random.PRNGKey(3), (9, 64))
    p = lambda name, cast=True: weights[f"lat1_{name}"]
    want = ref.moe(h, p, full, jnp.float32)
    shared = ref._gated(h, p("sh_gate.w"), p("sh_up.w"), p("sh_down.w"))
    idx, w = moe_ops.moe_route(h, p("gate.w"), p("gate.bias"), 2, 2.827,
                               True)
    total = np.asarray(shared)
    landed = 0
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
    with open(os.path.join(BENCH, "configs", "kimi_k2.6_text.json")) as f:
        published = json.load(f)
    hp = latent_moe.LatentMoEConfig.from_dict(published)
    assert (hp.hidden_size, hp.num_attention_heads, hp.kv_lora_rank,
            hp.qk_rope_head_dim, hp.q_lora_rank) == (7168, 64, 512, 64, 1536)
    assert hp.held == 12 and hp.n_routed_experts == 384
    assert hp.moe_layers == [1, 2, 3, 4] and hp.eps == 1e-5
    assert hp.latent_row == 640
    assert hp.softmax_scale == pytest.approx(0.144680, rel=1e-5)
    assert latent_moe.paged_cache_var_names(hp) == [
        f"lat{i}_paged_c" for i in range(5)]
