"""Hybrid state-space / attention / mixture-of-experts LM on the paged
serving path (``models/hybrid_moe.py``): the ops against their sequential
forms, the exported bundle (prefill, the compiled seed of pages AND
per-slot state, cached decode steps) against the plain reference
(``benchmark/reference/hybrid_moe_ref.py``) on seeded weights, grouped-query
``paged_attention``, the bundle contract, re-prefill failover, and the
expert-parallel share arithmetic.  Toy widths: d 64, 4 mamba heads x 16,
state 16, 2 groups, 16 experts top-4, latent 32, pattern ``EM*``."""

import json
import os
import sys
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import profiler
from paddle_tpu.gen import GenPredictor, GenScheduler
from paddle_tpu.models import gen_lm, hybrid_moe
from paddle_tpu.ops import attention_ops, moe_ops, ssm_ops
from paddle_tpu.serving import InferenceServer, ServingClient

import gen_lookahead

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from models import hybrid_moe as adapter          # noqa: E402
from reference import hybrid_moe_ref as ref       # noqa: E402

SLOTS, PAGE_LEN, BUCKETS = 4, 8, [8, 16, 32]


def toy_config(**over):
    cfg = {"hidden_size": 64, "hybrid_override_pattern": "EM*",
           "vocab_size": 64, "layer_norm_epsilon": 1e-5,
           "mamba_num_heads": 4, "mamba_head_dim": 16, "n_groups": 2,
           "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 8,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "head_dim": 16, "n_routed_experts": 16, "num_experts_per_tok": 4,
           "moe_latent_size": 32, "moe_intermediate_size": 48,
           "moe_shared_expert_intermediate_size": 96,
           "routed_scaling_factor": 2.5, "norm_topk_prob": True,
           "experts_held": 16, "expert_offset": 0,
           "time_step_min": 0.001, "time_step_max": 0.1,
           "time_step_floor": 1e-4}
    cfg.update(over)
    return cfg


def _export(path, cfg, dtype="float32"):
    hp = hybrid_moe.HybridConfig.from_dict(cfg)
    hp.dtype, hp.max_len = dtype, 64
    hybrid_moe.export_hybrid_model(path, hp, num_slots=SLOTS,
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
    # then compute the same function to float32 rounding
    return {k: v.astype(jnp.float32)
            for k, v in adapter.seeded_weights(cfg, 7).items()}


@pytest.fixture(scope="module")
def bundle_dir(tmp_path_factory, cfg):
    return _export(str(tmp_path_factory.mktemp("hybrid") / "bundle"), cfg)


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


# -- the ops against their sequential forms ---------------------------------

def _scan_inputs(T, seed=0, H=4, P=16, G=2, N=16):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    xbc = jax.random.normal(k[0], (T, H * P + 2 * G * N))
    dt = jax.random.normal(k[1], (T, H))
    a_log = jnp.log(jax.random.uniform(k[2], (H,), minval=1., maxval=16.))
    d = jax.random.normal(k[3], (H,))
    dt_bias = jax.random.normal(k[4], (H,)) - 2.0
    return xbc, dt, a_log, d, dt_bias


@pytest.mark.parametrize("T,real,chunk", [(16, 16, 8), (24, 13, 8),
                                          (8, 5, 128), (32, 32, 16)])
def test_ssm_scan_equals_the_token_by_token_update(T, real, chunk):
    dims = dict(n_head=4, head_dim=16, n_groups=2, state=16)
    xbc, dt, a_log, d, dt_bias = _scan_inputs(T)
    mask = (jnp.arange(T) < real).astype(jnp.float32)
    y, h = ssm_ops.ssm_scan(xbc, dt, a_log, d, dt_bias, mask, chunk=chunk,
                            **dims)
    state = jnp.zeros((1, 4, 16, 16))
    for t in range(real):
        y_t, state = ssm_ops.ssm_update(xbc[t:t + 1], dt[t:t + 1], a_log, d,
                                        dt_bias, state, jnp.asarray([True]),
                                        **dims)
        np.testing.assert_allclose(y[t], y_t[0], rtol=2e-4, atol=2e-5)
    # pad rows froze the state: it is the one after the last real row
    np.testing.assert_allclose(h, state[0], rtol=2e-4, atol=2e-5)


def test_ssm_update_leaves_a_slot_that_is_not_live_untouched():
    dims = dict(n_head=4, head_dim=16, n_groups=2, state=16)
    xbc, dt, a_log, d, dt_bias = _scan_inputs(3)
    state = jax.random.normal(jax.random.PRNGKey(9), (3, 4, 16, 16))
    live = jnp.asarray([True, False, True])
    _, new = ssm_ops.ssm_update(xbc, dt, a_log, d, dt_bias, state, live,
                                **dims)
    assert np.array_equal(new[1], state[1])
    assert not np.array_equal(new[0], state[0])


def test_conv_scan_hands_out_the_window_the_update_continues_from():
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(k[0], (12, 10))
    w, b = jax.random.normal(k[1], (4, 10)), jax.random.normal(k[2], (10,))
    full, _ = ssm_ops.conv_scan(x, w, b, jnp.int32(12))
    for real in (1, 2, 7):
        _, window = ssm_ops.conv_scan(x, w, b, jnp.int32(real))
        out, shifted = ssm_ops.conv_update(x[real:real + 1], window[None],
                                           w, b, jnp.asarray([True]))
        np.testing.assert_allclose(out[0], full[real], rtol=1e-5, atol=1e-6)
        kept = ssm_ops.conv_update(x[real:real + 1], window[None], w, b,
                                   jnp.asarray([False]))[1]
        assert np.array_equal(kept[0], window)
        assert np.array_equal(shifted[0, -1], x[real])


def test_moe_experts_drops_absent_experts_and_counts_what_landed():
    k = jax.random.split(jax.random.PRNGKey(5), 4)
    u = jax.random.normal(k[0], (6, 32))
    w1 = jax.random.normal(k[1], (4, 32, 48)) * 0.1
    w2 = jax.random.normal(k[2], (4, 48, 32)) * 0.1
    idx = jnp.asarray([[0, 9], [4, 5], [5, 5 + 2], [6, 15], [7, 1], [4, 4 + 3]],
                      jnp.int32)
    wgt = jax.random.uniform(k[3], (6, 2)) + 0.5
    out, stats = moe_ops.moe_experts(u, idx, wgt, w1, w2, expert_offset=4)
    want = np.zeros((6, 32), np.float32)
    for t in range(6):
        for j in range(2):
            e = int(idx[t, j]) - 4
            if 0 <= e < 4:
                h = np.maximum(np.asarray(u[t] @ w1[e]), 0) ** 2
                want[t] += float(wgt[t, j]) * np.asarray(h @ w2[e])
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-5)
    # held experts 4..7: landed 4,5 | 5,7 | 6 | 7 | 4,7 = 8 on 4 experts,
    # the fullest (7) has 3
    assert stats.tolist() == [8, 4, 3]
    live = jnp.asarray([True, True, False, True, True, False])
    out2, stats2 = moe_ops.moe_experts(u, idx, wgt, w1, w2, 4, live)
    assert stats2.tolist() == [4, 4, 1]
    assert not np.asarray(out2[2]).any()


def _relu2_case(T, offset, spread, E=8, k=6, L=64, F=48, seed=3):
    """``E`` experts held from ``offset`` on; the router's choices are
    ``k`` distinct experts of ``spread`` consecutive ones from 0."""
    key = jax.random.split(jax.random.PRNGKey(seed), 5)
    u = jax.random.normal(key[0], (T, L))
    w1 = jax.random.normal(key[1], (E, L, F)) * 0.1
    w2 = jax.random.normal(key[2], (E, F, L)) * 0.1
    _, idx = jax.lax.top_k(jax.random.uniform(key[3], (T, spread)), k)
    wgt = jax.random.uniform(key[4], (T, k)) + 0.5
    return u, idx.astype(jnp.int32), wgt, w1, w2, offset


@pytest.mark.parametrize("case", [
    "offset_0_decode_rows", "a_middle_share_decode_rows",
    "offset_0_a_prefill_bucket", "a_middle_share_a_prefill_bucket",
    "no_assignment_lands_here", "every_held_expert_is_touched"])
def test_the_routed_relu2_product_is_the_dense_one(case):
    """``moe_experts`` through the routed core (the grouped kernel in
    interpret mode) against its dense form and the plain sum over the
    assignments, with the same ``Stats``; a third of the rows are not
    live.  40 rows x top-6 are two row tiles of sorted rows."""
    u, idx, wgt, w1, w2, offset = {
        "offset_0_decode_rows": lambda: _relu2_case(4, 0, 32),
        "a_middle_share_decode_rows": lambda: _relu2_case(4, 8, 32),
        "offset_0_a_prefill_bucket": lambda: _relu2_case(40, 0, 32),
        "a_middle_share_a_prefill_bucket": lambda: _relu2_case(40, 16, 32),
        # the router chooses among experts 0..15, this share holds 16..23
        "no_assignment_lands_here": lambda: _relu2_case(4, 16, 16),
        # it chooses 6 of the 8 held for each of 40 rows
        "every_held_expert_is_touched": lambda: _relu2_case(40, 0, 8),
    }[case]()
    T, E = u.shape[0], w1.shape[0]
    live = jnp.arange(T) % 3 != 1
    dense, s0 = moe_ops.moe_experts(u, idx, wgt, w1, w2, offset, live,
                                    routed=False)
    routed, s1 = moe_ops.moe_experts(u, idx, wgt, w1, w2, offset, live,
                                     routed=True)
    want, load = np.zeros(u.shape, np.float32), np.zeros(E, int)
    for t in range(T):
        for e, wt in zip(np.asarray(idx[t]) - offset, np.asarray(wgt[t])):
            if 0 <= e < E and bool(live[t]):
                h = np.maximum(np.asarray(u[t] @ w1[e]), 0) ** 2
                want[t] += wt * np.asarray(h @ w2[e])
                load[e] += 1
    np.testing.assert_allclose(routed, want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(routed, dense, atol=4e-6)
    assert s0.tolist() == s1.tolist() == [load.sum(), (load > 0).sum(),
                                          load.max()]
    assert not np.asarray(routed)[1::3].any()
    if case == "no_assignment_lands_here":
        assert s1.tolist() == [0, 0, 0] and not np.asarray(routed).any()
    if case == "every_held_expert_is_touched":
        assert s1[1] == E


class _Lowering:
    """What ``moe_experts_lower`` asks of a lower context."""

    def __init__(self, training, **inputs):
        self.training, self.inputs, self.out = training, inputs, {}

    def input(self, slot):
        return self.inputs.get(slot)

    def attr(self, name, default=None):
        return default

    def set_output(self, slot, value):
        self.out[slot] = value


def test_a_training_and_a_cpu_lowering_are_dense_and_counted(monkeypatch):
    """The routed form is a served program's on a TPU: a training graph
    (its loop to a traced bound has no reverse mode) and any lowering
    off the TPU take the dense form, and ``gen.moe.dense_lowerings``
    says so; decode rows on a TPU go to the routed core."""
    u, idx, wgt, w1, w2, _ = _relu2_case(4, 0, 32)
    feeds = dict(X=u, TopkIdx=idx, TopkWeight=wgt, W1=w1, W2=w2)
    count = profiler.runtime_metrics.counter

    def routed(*a, **kw):
        raise AssertionError("the routed core")
    monkeypatch.setattr(moe_ops, "_routed_experts", routed)
    was = count("gen.moe.dense_lowerings")
    ctx = _Lowering(False, **feeds)             # served, on the CPU
    moe_ops.moe_experts_lower(ctx)
    assert ctx.out["Out"].shape == u.shape and ctx.out["Stats"].shape == (1, 3)
    assert count("gen.moe.dense_lowerings") == was + 1
    monkeypatch.setattr(attention_ops, "_use_interpret", lambda: False)
    moe_ops.moe_experts_lower(_Lowering(True, **feeds))   # trained, "TPU"
    assert count("gen.moe.dense_lowerings") == was + 2
    assert count("gen.moe.dense_lowerings.moe_experts") >= 2
    with pytest.raises(AssertionError, match="the routed core"):
        moe_ops.moe_experts_lower(_Lowering(False, **feeds))
    assert count("gen.moe.dense_lowerings") == was + 2
    assert count("gen.moe.routed_lowerings.moe_experts") >= 1
    # the row counts at which the dense product was measured faster
    lo, hi = moe_ops._RELU2_DENSE_ROWS[0], moe_ops._RELU2_DENSE_ROWS[-1]
    for rows, dense in ((lo - 1, 0), (lo, 1), (hi, 1), (hi + 1, 0)):
        wide = dict(zip(("X", "TopkIdx", "TopkWeight", "W1", "W2"),
                        _relu2_case(rows, 0, 32)))
        was = count("gen.moe.dense_lowerings")
        if dense:
            moe_ops.moe_experts_lower(_Lowering(False, **wide))
        else:
            with pytest.raises(AssertionError, match="the routed core"):
                moe_ops.moe_experts_lower(_Lowering(False, **wide))
        assert count("gen.moe.dense_lowerings") == was + dense


def test_moe_route_is_the_references():
    k = jax.random.split(jax.random.PRNGKey(6), 2)
    x, wg = jax.random.normal(k[0], (9, 64)), jax.random.normal(k[1],
                                                                (64, 16))
    cfg = toy_config()
    idx, w = moe_ops.moe_route(x, wg, jnp.zeros(16), 4, 2.5, True)
    r_idx, r_w = ref.route(x, lambda n: {"gate.w": wg,
                                         "gate.bias": jnp.zeros(16)}[n], cfg)
    assert np.array_equal(idx, r_idx)
    np.testing.assert_allclose(w, r_w, rtol=1e-5)
    np.testing.assert_allclose(w.sum(-1), 2.5, rtol=1e-5)


# -- grouped-query paged attention -------------------------------------------

def _paged_case(S=3, H=4, Hkv=2, D=16, PL=8, P=3, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    NP = S * P + 2
    kc = jax.random.normal(k[0], (NP, PL, Hkv * D))
    vc = jax.random.normal(k[1], (NP, PL, Hkv * D))
    q = jax.random.normal(k[2], (S, 1, H * D))
    table = jax.random.permutation(k[3], NP)[:S * P].reshape(S, P) \
        .astype(jnp.int32)
    lens = jnp.asarray([[5], [0], [P * PL]][:S], jnp.int32)
    return q, kc, vc, table, lens


def _composed(q, kc, vc, table, lens, H, scale):
    """Every slot's live rows gathered, each query head against its K/V
    head, plain softmax."""
    S, P = table.shape
    PL, HDkv = kc.shape[1:]
    D = q.shape[-1] // H
    g = H // (HDkv // D)
    out = np.zeros((S, H, D), np.float32)
    for s in range(S):
        n = int(lens[s, 0])
        if not n:
            continue
        rows_k = np.asarray(kc[table[s]]).reshape(P * PL, -1, D)[:n]
        rows_v = np.asarray(vc[table[s]]).reshape(P * PL, -1, D)[:n]
        for h in range(H):
            sc = rows_k[:, h // g] @ np.asarray(q[s, 0]).reshape(H, D)[h] \
                * scale
            pr = np.exp(sc - sc.max())
            out[s, h] = (pr / pr.sum()) @ rows_v[:, h // g]
    return out.reshape(S, 1, H * D)


@pytest.mark.parametrize("path", ["xla", "kernel"])
def test_grouped_query_paged_attention_equals_the_composed(path):
    q, kc, vc, table, lens = _paged_case()
    if path == "xla":
        got = attention_ops._xla_paged_attention(q, kc, vc, table, lens, 4,
                                                 0.25)
    else:
        got = attention_ops._pallas_paged_attention(q, kc, vc, table, lens,
                                                    4, 0.25, interpret=True)
    want = _composed(q, kc, vc, table, lens, 4, 0.25)
    live = np.asarray(lens[:, 0]) > 0
    np.testing.assert_allclose(np.asarray(got)[live], want[live], rtol=2e-5,
                               atol=2e-6)


def test_paged_attention_with_as_many_kv_heads_is_bit_equal_to_before():
    """H = Hkv (``gen_lm``): the gather lowering against the formula it
    had before grouped heads, bit for bit.  The kernel against a pool
    with the K/V heads copied out: to float32 rounding since PR 30
    (grouped heads share their K/V head's rows in one product, a head of
    its own sums lane by lane: two orders of one sum)."""
    q, kc, vc, table, lens = _paged_case(Hkv=4)
    S, P = table.shape
    PL, H, D = 8, 4, 16
    kg = kc[table].reshape(S, P * PL, H, D)
    vg = vc[table].reshape(S, P * PL, H, D)
    sc = jnp.einsum("shd,sthd->sht", q.reshape(S, H, D), kg,
                    preferred_element_type=jnp.float32) * 0.25
    col = jax.lax.broadcasted_iota(jnp.int32, (S, 1, P * PL), 2)
    sc = jnp.where(col < lens[:, :, None], sc, attention_ops.NEG_INF)
    before = jnp.einsum("sht,sthd->shd", jax.nn.softmax(sc, axis=-1), vg,
                        preferred_element_type=jnp.float32).reshape(q.shape)
    after = attention_ops._xla_paged_attention(q, kc, vc, table, lens, H,
                                               0.25)
    assert np.array_equal(np.asarray(before), np.asarray(after))
    # the kernel: grouped heads read the very lanes a copied-out pool holds
    q2, kc2, vc2, table2, lens2 = _paged_case(Hkv=2)
    wide = lambda c: jnp.repeat(c.reshape(c.shape[:2] + (2, D)), 2,
                                axis=2).reshape(c.shape[:2] + (H * D,))
    grouped = attention_ops._pallas_paged_attention(
        q2, kc2, vc2, table2, lens2, H, 0.25, interpret=True)
    copied = attention_ops._pallas_paged_attention(
        q2, wide(kc2), wide(vc2), table2, lens2, H, 0.25, interpret=True)
    live = np.asarray(lens2[:, 0]) > 0
    np.testing.assert_allclose(np.asarray(grouped)[live],
                               np.asarray(copied)[live], rtol=2e-5,
                               atol=2e-6)


def test_paged_kernel_gate_takes_grouped_heads():
    ok = attention_ops._paged_kernel_ok
    assert ok(32, 4096, 16, False) and ok(32, 4096, 16, False, 256)
    assert not ok(32, 4096, 16, False, 384)      # 3 K/V heads for 32
    assert not ok(4, 64, 8, False, 32)           # 16-lane heads, on chip
    assert ok(4, 64, 8, True, 32)


# -- the bundle against the reference ----------------------------------------

@pytest.mark.parametrize("n", [5, 8, 11, 20, 32])
def test_prefill_matches_the_reference_in_every_bucket(predictor, weights,
                                                       cfg, n):
    prompt = _prompt(n, seed=n)
    logits, kv = predictor.prefill(prompt)
    _close(logits, _ref_logits(weights, cfg, prompt, [n - 1])[0])
    assert len(kv) == len(predictor.cache_vars) + len(predictor.state_vars)


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


def _slot_state(predictor, slot):
    return [np.asarray(predictor._scope.find_var(n))[slot].copy()
            for n in predictor.state_vars]


def test_a_freed_and_readmitted_slot_carries_nothing_over(predictor, weights,
                                                          cfg):
    """Pages AND state: a long stream, then a short one in the same slot
    (and, the free list being a queue, on pages the first one dirtied)."""
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


def test_clear_slot_zeroes_pages_and_state(predictor):
    _admit(predictor, 0, _prompt(9, seed=6), 4)
    assert any(s.any() for s in _slot_state(predictor, 0))
    predictor.clear_slot(0)
    assert not any(s.any() for s in _slot_state(predictor, 0))
    pages = predictor._slot_pages[0]
    for name in predictor.cache_vars:
        assert not np.asarray(predictor._scope.find_var(name))[pages].any()
    predictor.free_slot_pages(0)


def test_a_step_with_lens_0_leaves_a_slots_state_untouched(predictor):
    _admit(predictor, 1, _prompt(10, seed=7), 4)
    try:
        before = _slot_state(predictor, 1)
        _admit(predictor, 3, _prompt(4, seed=8), 4)
        _step(predictor, {3: (5, 4)})           # slot 1 sits this one out
        for a, b in zip(before, _slot_state(predictor, 1)):
            assert np.array_equal(a, b)
        assert any(not np.array_equal(a, b) for a, b in zip(
            before, _slot_state(predictor, 3)))
    finally:
        predictor.free_slot_pages(1)
        predictor.free_slot_pages(3)


def test_an_admission_is_one_compiled_call_and_no_eager_op(predictor):
    m = profiler.runtime_metrics
    calls, eager = (m.counter("gen.seed.compiled_calls"),
                    m.counter("gen.seed.eager_ops"))
    _admit(predictor, 0, _prompt(12, seed=9), 4)
    predictor.free_slot_pages(0)
    assert m.counter("gen.seed.compiled_calls") - calls == 1
    assert m.counter("gen.seed.eager_ops") - eager == 0


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
        predictor.free_slot_pages(0)
        predictor.free_slot_pages(2)
    attrs = spans[-1]["attrs"]
    # every expert is held: both live slots' top-4 land, free slots' none
    assert attrs["live"] == 2 and attrs["moe_assignments"] == 8
    assert 4 <= attrs["moe_experts_touched"] <= 8
    assert 1 <= attrs["moe_max_load"] <= 2
    assert m.counter("gen.moe.assignments") - before == 8
    assert m.snapshot()["histograms"]["gen.moe.max_load"]


def test_the_state_is_its_own_collection_of_the_hbm_census(predictor):
    from paddle_tpu.obs import perf
    census = perf.hbm_census(scope=predictor._scope)
    want = sum(np.asarray(predictor._scope.find_var(n)).nbytes
               for n in predictor.state_vars)
    assert census["gen_state"] == want > 0
    assert census["kv_pages"] > 0


def test_prefill_cost_prices_the_real_program_and_the_state(predictor):
    cheap, dear = predictor.prefill_cost(5), predictor.prefill_cost(30)
    assert 0 < cheap < dear
    assert predictor._page_write_cost(5) > 4 * 16 * 16   # the state rows


# -- scheduler, server, failover ----------------------------------------------

def _ref_greedy(weights, cfg, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(np.argmax(_ref_logits(weights, cfg, seq,
                                             [len(seq) - 1])[0])))
    return seq[len(prompt):]


def test_scheduler_streams_the_references_greedy_tokens(predictor, weights,
                                                        cfg):
    sched = GenScheduler(predictor, queue_size=8)
    try:
        prompts = [_prompt(n, seed=20 + n) for n in (4, 13, 7, 21, 9)]
        streams = [sched.submit(p, max_new_tokens=6) for p in prompts]
        for p, s in zip(prompts, streams):
            assert list(s) == _ref_greedy(weights, cfg, p, 6)
    finally:
        sched.close()


@pytest.mark.parametrize("drill", gen_lookahead.DRILLS,
                         ids=lambda drill: drill.__name__)
def test_the_lookahead_keeps_the_state_rows_with_their_streams(
        drill, predictor, weights, cfg):
    """The scheduler runs one step ahead of the host (``gen_lookahead.py``):
    beside the pages a slot here has recurrent-state rows, which a
    discarded row has written and the next seed must overwrite."""
    memo = {}

    def ref_greedy(prompt, n):
        # the reference is causal: padded to one length (one shape to
        # compile), a position's logits are those of the bare sequence;
        # and greedy tokens are a prefix of any longer run's
        seq = memo.setdefault(tuple(prompt), list(prompt))
        while len(seq) < len(prompt) + n:
            padded = seq + [0] * (predictor.max_len - len(seq))
            seq.append(int(np.argmax(_ref_logits(
                weights, cfg, padded, [len(seq) - 1])[0])))
        return seq[len(prompt):len(prompt) + n]

    drill(predictor, ref_greedy)


def test_reprefill_failover_on_the_state_bundle(bundle_dir, weights, cfg):
    """``resume_from``: a second request carrying prompt + the tokens
    already delivered rebuilds pages AND state from tokens alone."""
    server = InferenceServer(bundle_dir, port=0, warmup=True,
                             request_timeout=60.0)
    server.start_background()
    try:
        assert server.wait_until_ready(300)
        _install(server.gen_predictor, weights)
        addr = "%s:%d" % tuple(server.addr[:2])
        prompt = _prompt(10, seed=30)
        want = _ref_greedy(weights, cfg, prompt, 8)
        events = list(ServingClient(addr, timeout=60.0).generate(
            prompt, max_new_tokens=8))
        assert [e["token"] for e in events if "token" in e] == want
        assert server.gen_predictor.can_resume(len(prompt) + 3)
        req = urllib.request.Request(
            f"http://{addr}/generate", method="POST",
            data=json.dumps({"prompt": prompt + want[:3], "stream": False,
                             "max_new_tokens": 5,
                             "resume_from": 3}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as resp:
            body = json.loads(resp.read())
        assert body["tokens"] == want[3:]
    finally:
        server.shutdown()


# -- the bundle contract --------------------------------------------------------

def _bundle_parts(bundle_dir):
    from paddle_tpu.analysis.distributed import load_saved_program
    with open(os.path.join(bundle_dir, "gen_meta.json")) as f:
        meta = json.load(f)
    return (load_saved_program(os.path.join(bundle_dir, "prefill")),
            load_saved_program(os.path.join(bundle_dir, "decode")), meta)


def test_gen_meta_names_the_state_beside_the_pool(bundle_dir):
    _, _, meta = _bundle_parts(bundle_dir)
    assert meta["cache_vars"] == ["hyb2_paged_k", "hyb2_paged_v"]
    assert meta["state_vars"] == ["hyb1_conv_state", "hyb1_ssm_state"]
    assert [c["name"] for c in meta["decode_stats"]] == [
        "moe_assignments", "moe_experts_touched", "moe_max_load"]


@pytest.mark.parametrize("drift,match", [
    (lambda m: m["state_vars"].append("hyb9_ssm_state"), "state"),
    (lambda m: m["state_vars"].pop(), "fetches"),
    (lambda m: m.update(num_slots=SLOTS + 1), "one row per slot"),
    (lambda m: m["state_vars"].reverse(), "misshapen state"),
    (lambda m: m.update(decode_stats=[]), None),
])
def test_check_gen_bundle_refuses_drifted_state_vars(bundle_dir, drift,
                                                     match):
    from paddle_tpu.analysis import check_gen_bundle
    prefill, decode, meta = _bundle_parts(bundle_dir)
    assert not [d for d in check_gen_bundle(prefill, decode, meta)
                if d.code == "PTA019"]
    drift(meta)
    found = [d.message for d in check_gen_bundle(prefill, decode, meta)
             if d.code == "PTA019"]
    if match is None:
        assert not found
    else:
        assert any(match in m for m in found), found


def test_a_gen_lm_bundle_has_no_state_and_serves_as_before(tmp_path):
    d = str(tmp_path / "genlm")
    gen_lm.export_gen_model(d, gen_lm.GenConfig(), num_slots=2)
    p = GenPredictor(d)
    assert p.state_vars == [] and p.decode_stats == []
    prompt = [3, 9, 4, 1, 7]
    logits, kv = p.prefill(prompt)
    assert len(kv) == len(p.cache_vars)
    p.alloc_slot_pages(0, p.pages_needed(len(prompt), 2))
    assert p.write_slot(0, kv, len(prompt)) == 0
    tok = int(np.argmax(logits))
    step = p.decode_step([tok, 0], [len(prompt), 0],
                         lens=[len(prompt) + 1, 0])[0]
    again, _ = p.prefill(prompt + [tok])
    np.testing.assert_allclose(step, again, rtol=2e-4, atol=2e-5)


def test_bfloat16_parameters_survive_the_bundle(tmp_path, cfg):
    d = _export(str(tmp_path / "bf16"), cfg, dtype="bfloat16")
    p = GenPredictor(d)
    assert str(p._scope.find_var("hyb1_in.w").dtype) == "bfloat16"
    assert str(p._scope.find_var("hyb0_w1").dtype) == "bfloat16"
    assert str(p._scope.find_var("hyb1_a_log").dtype) == "float32"
    assert str(p._scope.find_var("hyb1_ssm_state").dtype) == "float32"
    seeded = adapter.seeded_weights(cfg, 11)
    _install(p, seeded)
    prompt = _prompt(9, seed=40)
    logits, kv = p.prefill(prompt)
    assert logits.dtype == np.float32 and str(kv[0].dtype) == "bfloat16"
    want = _ref_logits(seeded, cfg, prompt, [8])[0]
    _close(logits, want, tol=0.05)      # bfloat16 activations, 3 layers


# -- the seeded router (benchmark/configs: assumed.router) ----------------------

def test_the_seeded_router_keeps_its_offset_and_the_order_of_its_scores(
        cfg, weights):
    """Channel 0 of the residual stream is a constant no layer writes to,
    row 0 of each router matrix turns it into one offset under every
    expert's logit, and the offset changes no choice: the experts chosen
    are those of the matrix's other rows alone, while the chosen carry
    unequal weights (a marginal expert next to none)."""
    d = cfg["hidden_size"]
    assert np.all(np.asarray(weights["hyb_emb"][:, 0]) == d ** 0.5 / 2)
    for name, value in weights.items():
        if name.endswith(("out.w", "o.w", "up.w", "sh2.w")):
            assert not np.asarray(value[:, 0]).any(), name
    gate = weights["hyb0_gate.w"]
    assert np.all(np.asarray(gate[0]) == gate[0, 0]) and gate[0, 0] < 0
    x = weights["hyb_emb"][jnp.asarray(_prompt(40, seed=3))]
    h = ref._rms(x, weights["hyb0_norm.scale"], cfg["layer_norm_epsilon"])
    p = lambda name, cast=True: weights[f"hyb0_{name}"]
    idx, w = ref.route(h, p, cfg)
    plain = jnp.matmul(h[:, 1:], gate[1:])          # the offset left out
    _, want = jax.lax.top_k(plain, cfg["num_experts_per_tok"])
    assert np.array_equal(np.sort(idx, -1), np.sort(want, -1))
    logits = jnp.matmul(h, gate)
    assert float(logits.max()) < -5.0               # the sigmoid's foot
    w = np.sort(np.asarray(w), -1)
    assert np.allclose(w.sum(-1), cfg["routed_scaling_factor"], rtol=1e-4)
    assert np.median(w[:, 0] / w[:, -1]) < 0.1      # smallest of the largest
    # the constant survives every layer of the reference
    ids = jnp.asarray(_prompt(12, seed=4), jnp.int32)
    seen = []
    real = ref._rms
    try:
        ref._rms = lambda x, scale, eps: (seen.append(x[:, 0]),
                                          real(x, scale, eps))[1]
        ref.forward_logits(weights, cfg, ids, jnp.arange(12))
    finally:
        ref._rms = real
    assert len(seen) == len(cfg["hybrid_override_pattern"]) + 1
    assert all(np.all(np.asarray(c) == d ** 0.5 / 2) for c in seen[:-1])


def test_the_float8_control_stores_a_column_of_zeros(cfg, weights):
    """The matrices that write the residual have a zero column: the
    control's one-scale-a-channel storage must not divide by it."""
    ids = jnp.asarray(_prompt(9, seed=5), jnp.int32)
    got = adapter.control_logits(weights, cfg, ids, jnp.arange(9), "fp8")
    want = _ref_logits(weights, cfg, _prompt(9, seed=5), list(range(9)))
    assert np.isfinite(np.asarray(got)).all()
    err = np.abs(np.asarray(got, np.float32) - want).max() \
        / (want.max() - want.min())
    assert 0 < err < 0.5


# -- one chip's share of the experts --------------------------------------------

def test_the_shares_of_one_layer_add_up_to_the_uncut_layer(weights):
    """8 shares of 2 experts each, the shared expert counted once, add up
    to the layer with all 16 experts (reference and op alike)."""
    whole = toy_config()
    k = jax.random.split(jax.random.PRNGKey(1), 2)
    h = jax.random.normal(k[0], (13, 64))
    p = lambda name, cast=True: weights[f"hyb0_{name}"]
    uncut = np.asarray(ref.moe(h, p, whole, jnp.float32))
    shared = np.asarray(ref._relu2(h @ p("sh1.w")) @ p("sh2.w"))
    idx, wgt = moe_ops.moe_route(h, p("gate.w"), p("gate.bias"), 4, 2.5)
    total_ref = np.zeros_like(uncut)
    total_op = np.zeros_like(uncut)
    landed = 0
    for share in range(8):
        part = dict(whole, experts_held=2, expert_offset=2 * share)
        cut = lambda name, cast=True, s=share: (
            weights[f"hyb0_{name}"][2 * s:2 * s + 2]
            if name in ("w1", "w2") else weights[f"hyb0_{name}"])
        total_ref += np.asarray(ref.moe(h, cut, part, jnp.float32)) - shared
        out, stats = moe_ops.moe_experts(
            h @ p("down.w"), idx, wgt, cut("w1"), cut("w2"), 2 * share)
        total_op += np.asarray(out @ p("up.w"))
        landed += int(stats[0])
    np.testing.assert_allclose(total_ref + shared, uncut, rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_allclose(total_op + shared, uncut, rtol=2e-4,
                               atol=2e-5)
    assert landed == 13 * 4         # every assignment landed on one share


def test_config_takes_the_published_keys():
    hp = hybrid_moe.HybridConfig.from_dict(dict(
        toy_config(hybrid_override_pattern="EMEM*"), experts_held=4,
        expert_offset=8))
    assert hp.pattern == "EMEM*" and hp.held == 4 and hp.expert_offset == 8
    assert hp.layers_of("M") == [1, 3] and hp.conv_dim == 64 + 2 * 2 * 16
    assert hybrid_moe.state_var_names(hp) == [
        "hyb1_conv_state", "hyb1_ssm_state", "hyb3_conv_state",
        "hyb3_ssm_state"]
