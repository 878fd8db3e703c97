"""Block-diffusion MoE LM on the paged serving path
(``models/block_moe.py``): the generalised ops against numbers worked
densely (``paged_attention`` with a block of rows a slot, ``gqa_attention``
with a block width, ``moe_route``'s softmax scoring, ``block_rows``), the
exported bundle (block-causal prefill, the compiled seed, cached block
steps through the paged pool) against the plain reference
(``benchmark/reference/sdar_ref.py``) on seeded weights, and
``GenScheduler``'s streams (a completed block is stored by the forward
that opens the next: every turn yields) against the reference's
published generation loop.  Toy widths: d 64, 4 query / 2 K/V
heads of 16, 8 experts top-2, 2 layers, blocks of 4."""

import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import gen_lookahead
from paddle_tpu import profiler
from paddle_tpu.gen import GenPredictor, GenScheduler
from paddle_tpu.models import block_moe
from paddle_tpu.obs import trace as ptrace
from paddle_tpu.ops import attention_ops, block_ops, moe_ops

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from models import sdar as adapter                # noqa: E402
from reference import sdar_ref as ref             # noqa: E402

SLOTS, PAGE_LEN, BUCKETS, L, MASK = 4, 8, [8, 16, 32], 4, 63


def toy_config(**over):
    cfg = {"hidden_size": 64, "num_hidden_layers": 2, "vocab_size": 64,
           "head_dim": 16, "num_attention_heads": 4,
           "num_key_value_heads": 2, "rope_theta": 1000000,
           "rms_norm_eps": 1e-6, "num_experts": 8, "num_experts_per_tok": 2,
           "moe_intermediate_size": 32, "norm_topk_prob": True,
           "block_length": L, "denoising_steps": L, "mask_token_id": MASK}
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def cfg():
    return toy_config()


@pytest.fixture(scope="module")
def weights(cfg):
    # the seeded bfloat16 VALUES, held in float32: program and reference
    # then compute the same function to float32 rounding
    return {k: v.astype(jnp.float32)
            for k, v in adapter.seeded_weights(cfg, 21).items()}


@pytest.fixture(scope="module")
def predictor(tmp_path_factory, cfg, weights):
    hp = block_moe.BlockMoEConfig.from_dict(cfg)
    hp.dtype, hp.max_len = "float32", 64
    path = str(tmp_path_factory.mktemp("block") / "bundle")
    block_moe.export_block_model(path, hp, num_slots=SLOTS,
                                 prompt_buckets=BUCKETS, page_len=PAGE_LEN)
    p = GenPredictor(path)
    for name, value in weights.items():
        old = p._scope.find_var(name)
        assert old is not None and tuple(old.shape) == tuple(value.shape), \
            name
        p._scope.set_var(name, value)
    p.warmup()
    return p


@pytest.fixture()
def loop(cfg, weights):
    """``(prompt, n)`` -> the published generation loop's tokens."""
    memo = {}

    def generate(prompt, n):
        key = tuple(prompt)
        if len(memo.get(key, ())) < n:
            memo[key] = ref.generate(weights, cfg, list(prompt), n)
        return memo[key][:n]
    return generate


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(0, 63, size=n).tolist()


def _ref_logits(weights, cfg, ids, positions):
    return np.asarray(ref.forward_logits(
        weights, cfg, jnp.asarray(ids, jnp.int32),
        jnp.asarray(positions, jnp.int32)))


def _close(got, want, tol=2e-5):
    """Float32 program against the float32 reference on the same values:
    what is left is the order of float32 sums, a few 1e-7 of the logits'
    range; 2e-5 leaves two orders of room and is far under what a wrong
    mask, a stale row or a dropped norm reads (> 1e-2)."""
    spread = float(want.max() - want.min())
    assert float(np.abs(np.asarray(got) - want).max()) <= tol * spread


def _step(predictor, live):
    """One blocking decode step; ``live`` maps slot -> (token, its
    position)."""
    tokens, pos, lens = (np.zeros(SLOTS, np.int32) for _ in range(3))
    for slot, (tok, at) in live.items():
        tokens[slot], pos[slot], lens[slot] = tok, at, at + 1
    return predictor.decode_step(tokens, pos, lens=lens)


def _admit(predictor, slot, prompt, horizon=16):
    logits, kv = predictor.prefill(prompt)
    predictor.alloc_slot_pages(slot, predictor.pages_needed(len(prompt),
                                                            horizon))
    assert predictor.write_slot(slot, kv, len(prompt)) == 0
    return logits


# -- the ops -------------------------------------------------------------------

@pytest.mark.parametrize("norm_topk", [True, False])
def test_moe_route_softmax_is_the_plain_softmax_router(norm_topk):
    rng = np.random.RandomState(3)
    x = rng.randn(9, 16).astype("float32")
    w = rng.randn(16, 12).astype("float32")
    idx, weight = moe_ops.moe_route(jnp.asarray(x), jnp.asarray(w), None, 3,
                                    norm_topk=norm_topk, scoring="softmax")
    logits = x.astype("float64") @ w.astype("float64")
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    order = np.argsort(-probs, axis=-1)[:, :3]
    chosen = np.take_along_axis(probs, order, axis=-1)
    if norm_topk:
        chosen = chosen / chosen.sum(-1, keepdims=True)
    assert np.array_equal(np.asarray(idx), order)
    np.testing.assert_allclose(np.asarray(weight), chosen, rtol=2e-5)
    if norm_topk:
        np.testing.assert_allclose(np.asarray(weight).sum(-1), 1.0,
                                   rtol=1e-5)


def test_moe_route_sigmoid_is_unchanged():
    rng = np.random.RandomState(4)
    x, w = rng.randn(9, 16).astype("float32"), \
        rng.randn(16, 12).astype("float32")
    bias = rng.randn(12).astype("float32") * 0.3
    idx, weight = moe_ops.moe_route(jnp.asarray(x), jnp.asarray(w),
                                    jnp.asarray(bias), 3, scaling=2.5)
    scores = 1.0 / (1.0 + np.exp(-(x.astype("float64") @ w)))
    order = np.argsort(-(scores + bias), axis=-1)[:, :3]
    chosen = np.take_along_axis(scores, order, axis=-1)
    assert np.array_equal(np.asarray(idx), order)
    np.testing.assert_allclose(
        np.asarray(weight), 2.5 * chosen / chosen.sum(-1, keepdims=True),
        rtol=2e-5)
    # the default scoring is the sigmoid, and no bias is a zero bias
    a = moe_ops.moe_route(jnp.asarray(x), jnp.asarray(w), None, 3)
    b = moe_ops.moe_route(jnp.asarray(x), jnp.asarray(w), jnp.zeros(12), 3,
                          scoring="sigmoid")
    assert all(np.array_equal(np.asarray(u), np.asarray(v))
               for u, v in zip(a, b))


@pytest.mark.parametrize("rows, chunk", [(132, 0), (132, None), (20, 0),
                                         (300, 0), (300, 128)])
def test_the_routed_product_in_one_chunk_is_the_dense_one(rows, chunk):
    """A layer that holds every expert takes its sorted rows through the
    grouped product in ONE trip (``chunk_rows`` 0); any chunk gives the
    dense product's numbers and loads."""
    rng = np.random.RandomState(rows)
    d, F, E, k = 32, 16, 16, 4
    x = jnp.asarray(rng.randn(rows, d), jnp.float32)
    wg, wu = (jnp.asarray(rng.randn(E, d, F) * 0.1, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.randn(E, F, d) * 0.1, jnp.float32)
    live = jnp.arange(rows) < rows - 7
    idx, w = moe_ops.moe_route(x, jnp.asarray(rng.randn(d, E), jnp.float32),
                               None, k, scoring="softmax")
    got, loads = moe_ops.moe_experts_gated(
        x, idx, w, wg, wu, wd, live=live, routed=True, interpret=True,
        chunk_rows=chunk)
    want, dense_loads = moe_ops.moe_experts_gated(x, idx, w, wg, wu, wd,
                                                  live=live, routed=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    assert np.asarray(loads).tolist() == np.asarray(dense_loads).tolist()
    assert int(loads[0]) == (rows - 7) * k


def _dense_attention(q, k, v, seen, n_head, n_kv, scale):
    """[T, H*D] queries over [C, Hkv*D] keys, ``seen`` [T, C]."""
    T, C = seen.shape
    D = q.shape[-1] // n_head
    qh = np.asarray(q, "float64").reshape(T, n_head, D)
    kh = np.asarray(k, "float64").reshape(C, n_kv, D)
    vh = np.asarray(v, "float64").reshape(C, n_kv, D)
    out = np.zeros((T, n_head, D))
    for h in range(n_head):
        sc = qh[:, h] @ kh[:, h // (n_head // n_kv)].T * scale
        sc = np.where(seen, sc, -np.inf)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        out[:, h] = p / p.sum(-1, keepdims=True) \
            @ vh[:, h // (n_head // n_kv)]
    return out.reshape(T, n_head * D)


@pytest.mark.parametrize("block", [1, 4])
def test_gqa_attention_block_width(block):
    rng = np.random.RandomState(5)
    T, H, Hkv, D = 14, 4, 2, 8
    q = rng.randn(T, H * D).astype("float32")
    k, v = (rng.randn(T, Hkv * D).astype("float32") for _ in range(2))
    mask = np.array([1.0] * 12 + [0.0] * 2, "float32")
    got = attention_ops.gqa_attention(*map(jnp.asarray, (q, k, v, mask)),
                                      H, Hkv, 0.3, block=block)
    i, j = np.arange(T)[:, None], np.arange(T)[None, :]
    seen = (j // block <= i // block) & (mask > 0)[None, :]
    want = _dense_attention(q, k, v, seen, H, Hkv, 0.3)
    np.testing.assert_allclose(np.asarray(got)[:12], want[:12], atol=2e-6)
    if block == 1:
        # the width's default is the causal mask the op always had
        plain = attention_ops.gqa_attention(
            *map(jnp.asarray, (q, k, v, mask)), H, Hkv, 0.3)
        assert np.array_equal(np.asarray(plain), np.asarray(got))
        assert np.array_equal(seen, (j <= i) & (mask > 0)[None, :])


def _paged_case(rows, group, seed=6):
    """4 slots (one free), ``rows`` query rows a slot, ``group`` query
    heads a K/V head, pages of 8 rows, lens multiples of ``rows``."""
    rng = np.random.RandomState(seed)
    S, Hkv, D, PL, P, NP = 4, 2, 8, 8, 3, 16
    H = Hkv * group
    q = rng.randn(S, rows, H * D).astype("float32") * 0.5
    kc, vc = (rng.randn(NP, PL, Hkv * D).astype("float32") * 0.5
              for _ in range(2))
    pt = rng.permutation(NP)[:S * P].reshape(S, P).astype("int32")
    lens = np.array([[20], [8], [4], [0]], "int32")
    return q, kc, vc, pt, lens, H, Hkv


@pytest.mark.parametrize("path", ["xla", "kernel"])
@pytest.mark.parametrize("rows, group", [(1, 8), (4, 8), (4, 1)])
def test_paged_attention_with_a_block_of_rows(rows, group, path):
    """Every one of a slot's rows reads every live row: the kernel (in
    interpret mode) = the XLA form = the dense product."""
    q, kc, vc, pt, lens, H, Hkv = _paged_case(rows, group)
    args = tuple(map(jnp.asarray, (q, kc, vc, pt, lens)))
    if path == "xla":
        got = attention_ops._xla_paged_attention(*args, H, 0.35)
    else:
        got = attention_ops._pallas_paged_attention(*args, H, 0.35,
                                                    interpret=True)
        assert got is not None
    got = np.asarray(got)
    assert got.shape == q.shape
    for s in range(3):
        n = int(lens[s, 0])
        keys = kc[pt[s]].reshape(-1, kc.shape[-1])[:n]
        vals = vc[pt[s]].reshape(-1, vc.shape[-1])[:n]
        want = _dense_attention(q[s], keys, vals, np.ones((rows, n), bool),
                                H, Hkv, 0.35)
        np.testing.assert_allclose(got[s], want, atol=3e-6)


@pytest.mark.parametrize("rows", [1, 4])
def test_grouped_heads_over_a_bfloat16_pool(rows):
    """A pool narrower than float32 under grouped heads: the kernel takes
    its rows as they are (one pass for the scores, the weights in two
    parts) and agrees with the float32 product of the same values to the
    rounding of its own bfloat16 output."""
    q, kc, vc, pt, lens, H, Hkv = _paged_case(rows, 8, seed=10)
    bf = lambda a: jnp.asarray(a).astype(jnp.bfloat16)
    got = attention_ops._pallas_paged_attention(
        bf(q), bf(kc), bf(vc), jnp.asarray(pt), jnp.asarray(lens), H, 0.35,
        interpret=True)
    assert got.dtype == jnp.bfloat16
    f = lambda a: np.asarray(bf(a).astype(jnp.float32))
    for s in range(3):
        n = int(lens[s, 0])
        keys = f(kc)[pt[s]].reshape(-1, kc.shape[-1])[:n]
        vals = f(vc)[pt[s]].reshape(-1, vc.shape[-1])[:n]
        want = _dense_attention(f(q)[s], keys, vals, np.ones((rows, n), bool),
                                H, Hkv, 0.35)
        # the output itself is rounded to bfloat16: 2^-8 of its size
        np.testing.assert_allclose(np.asarray(got[s].astype(jnp.float32)),
                                   want, atol=6e-3, rtol=4e-3)


def _two_block_case(group, seed=11):
    """``_paged_case`` with ``2L`` rows a slot and a limit a row: slot 0
    stores a block under 16 rows and opens the next under 20, slot 1
    forwards its block under 8 with a dead second half, slot 2 stores its
    FIRST block under 4 and opens the second under 8, slot 3 is free."""
    q, kc, vc, pt, _, H, Hkv = _paged_case(2 * L, group, seed=seed)
    row_lens = np.array([[16] * L + [20] * L, [8] * L + [0] * L,
                         [4] * L + [8] * L, [0] * (2 * L)], "int32")
    return q, kc, vc, pt, row_lens, H, Hkv


@pytest.mark.parametrize("pool", ["float32", "bfloat16"])
@pytest.mark.parametrize("path", ["xla", "kernel"])
@pytest.mark.parametrize("group", [8, 1])
def test_paged_attention_with_a_limit_a_row(group, path, pool):
    """Two blocks a slot in one call (``2L`` rows: at ``group`` 8 a K/V
    head has ``L * G`` = 32 query rows a block, 64 in all): each row
    reads the rows under ITS limit, the walk follows the largest, a dead
    row reads zeros.  The kernel (in interpret mode) = the XLA form = the
    dense product."""
    q, kc, vc, pt, row_lens, H, Hkv = _two_block_case(group)
    cast = (lambda a: jnp.asarray(a).astype(jnp.bfloat16)) \
        if pool == "bfloat16" else jnp.asarray
    walk = jnp.asarray(row_lens.max(1, keepdims=True))
    args = (cast(q), cast(kc), cast(vc), jnp.asarray(pt), walk, H, 0.35)
    if path == "xla":
        got = attention_ops._xla_paged_attention(
            *args, row_lens=jnp.asarray(row_lens))
    else:
        got = attention_ops._pallas_paged_attention(
            *args, interpret=True, row_lens=jnp.asarray(row_lens))
        assert got is not None
    assert got.shape == q.shape and got.dtype == args[0].dtype
    got = np.asarray(got.astype(jnp.float32))
    f = lambda a: np.asarray(cast(a).astype(jnp.float32))
    # a bfloat16 output is rounded to 2^-8 of its size
    tol = dict(atol=3e-6) if pool == "float32" else dict(atol=6e-3,
                                                         rtol=4e-3)
    for s in range(4):
        keys = f(kc)[pt[s]].reshape(-1, kc.shape[-1])
        vals = f(vc)[pt[s]].reshape(-1, vc.shape[-1])
        seen = np.arange(keys.shape[0])[None, :] < row_lens[s][:, None]
        live = row_lens[s] > 0
        assert not got[s][~live].any()
        if live.any():
            want = _dense_attention(f(q)[s][live], keys, vals, seen[live],
                                    H, Hkv, 0.35)
            np.testing.assert_allclose(got[s][live], want, **tol)


@pytest.mark.parametrize("path", ["xla", "kernel"])
def test_two_blocks_in_one_call_are_the_two_calls_they_replace(path):
    """A store pass (the completed block's rows under ``n``) followed by
    an opening pass (the next block's under ``n + L``), each a call of
    ``L`` rows with no limit, leave the pool rows and the outputs that
    ONE call of ``2L`` rows with limits leaves."""
    q, kc, vc, pt, row_lens, H, Hkv = _two_block_case(8, seed=12)
    rng = np.random.RandomState(13)
    k, v = (jnp.asarray(rng.randn(4, 2 * L, Hkv * 8).astype("float32"))
            for _ in range(2))
    pools = (jnp.asarray(kc), jnp.asarray(vc))
    q, pt, limits = jnp.asarray(q), jnp.asarray(pt), jnp.asarray(row_lens)
    fn = attention_ops._xla_paged_attention if path == "xla" else \
        (lambda *a, **kw: attention_ops._pallas_paged_attention(
            *a, interpret=True, **kw))
    # two calls: half A's rows end at its limit, half B's at its own
    two, outs = pools, []
    for half in (slice(0, L), slice(L, 2 * L)):
        lens = limits[:, half][:, :1]
        two = attention_ops._paged_cache_update(
            two, (k[:, half], v[:, half]), pt, lens)
        outs.append(fn(q[:, half], *two, pt, lens, H, 0.35))
    # one call: the 2L rows end where half B would, live or dead
    end = jnp.where(limits[:, :1] > 0, limits[:, :1] + L, 0)
    one = attention_ops._paged_cache_update(pools, (k, v), pt, end, limits)
    got = fn(q, *one, pt, jnp.max(limits, axis=1, keepdims=True), H, 0.35,
             row_lens=limits)
    for a, b in zip(one, two):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    live = np.asarray(limits) > 0
    want = np.concatenate([np.asarray(o) for o in outs], axis=1)
    np.testing.assert_allclose(np.asarray(got)[live], want[live], atol=3e-6)


def _fingerprint(jaxpr, out=None):
    """Primitive and output types of every equation, in order, through
    every nested jaxpr (a kernel's body); source positions left out."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        out.append(f"{eqn.primitive.name}:"
                   f"{[str(v.aval) for v in eqn.outvars]}")
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _fingerprint(sub, out)
    return out


def _one_row_call(kind):
    """The cache update, the kernel and the XLA form of a call that
    decodes one row a slot, and its argument shapes."""
    f32, bf16, i32 = jnp.float32, jnp.bfloat16, jnp.int32
    S, P, NP, PL, D = 4, 4, 32, 16, 128
    z = jax.ShapeDtypeStruct
    if kind == "latent":
        def call(q, row, cache, pt, lens):
            cache, = attention_ops._paged_cache_update((cache,), (row,), pt,
                                                       lens)
            return (attention_ops._pallas_paged_attention(
                        q, cache, None, pt, lens, 8, 0.5, v_width=512),
                    attention_ops._xla_latent_attention(q, cache, pt, lens,
                                                        8, 512, 0.5))
        return call, (z((S, 1, 8 * 640), bf16), z((S, 1, 640), bf16),
                      z((NP, PL, 640), bf16), z((S, P), i32),
                      z((S, 1), i32))
    H, Hkv = (16, 2) if kind == "grouped heads" else (4, 4)

    def call(q, k, v, kc, vc, pt, lens):
        kc, vc = attention_ops._paged_cache_update((kc, vc), (k, v), pt,
                                                   lens)
        return (attention_ops._pallas_paged_attention(q, kc, vc, pt, lens,
                                                      H, 0.5),
                attention_ops._xla_paged_attention(q, kc, vc, pt, lens, H,
                                                   0.5))
    # the configuration with heads of their own feeds two-axis queries
    q = (S, 1, H * D) if Hkv != H else (S, H * D)
    return call, (z(q, f32), z((S, 1, Hkv * D), f32),
                  z((S, 1, Hkv * D), f32), z((NP, PL, Hkv * D), f32),
                  z((NP, PL, Hkv * D), f32), z((S, P), i32), z((S, 1), i32))


@pytest.mark.parametrize("kind, equations, digest", [
    ("heads of their own", 413, "02d0c5961d3cb409"),
    ("grouped heads", 337, "70479ee8605ad095"),
    ("latent", 289, "ba1c08a8e9d9a1ab")])
def test_a_one_row_call_lowers_to_the_jaxpr_it_had(kind, equations, digest):
    """The three configurations that decode a token a slot a step share
    ``paged_attention``'s update, kernel and XLA form with the block
    bundle: a call without row limits traces to the equations it traced
    to before limits existed (the numbers are commit ``dae0d84``'s, by
    this test's own ``_fingerprint`` under the installed JAX; after a JAX
    upgrade read them anew from a checkout of that commit)."""
    call, shapes = _one_row_call(kind)
    lines = _fingerprint(jax.make_jaxpr(call)(*shapes).jaxpr)
    assert len(lines) == equations
    assert hashlib.sha256("\n".join(lines).encode()).hexdigest()[:16] \
        == digest


@pytest.mark.parametrize("path", ["xla", "kernel"])
def test_one_row_a_slot_is_the_call_as_it_was(path):
    """``[S, 1, H*D]`` and the two-axis ``[S, H*D]`` queries of the
    callers that decode a token a step give the same numbers."""
    q, kc, vc, pt, lens, H, _ = _paged_case(1, 8, seed=8)
    rest = tuple(map(jnp.asarray, (kc, vc, pt, lens)))
    fn = attention_ops._xla_paged_attention if path == "xla" else \
        (lambda *a: attention_ops._pallas_paged_attention(*a,
                                                          interpret=True))
    three = np.asarray(fn(jnp.asarray(q), *rest, H, 0.35))
    two = np.asarray(fn(jnp.asarray(q[:, 0]), *rest, H, 0.35))
    assert np.array_equal(three[:, 0], two)


def test_the_cache_update_writes_a_block_over_the_rows_before():
    rng = np.random.RandomState(9)
    NP, PL, W = 6, 8, 4
    pool = jnp.asarray(rng.randn(NP, PL, W).astype("float32"))
    rows = jnp.asarray(rng.randn(3, 4, W).astype("float32"))
    table = jnp.asarray([[2, 5], [1, 0], [3, 4]], jnp.int32)
    lens = jnp.asarray([[12], [4], [0]], jnp.int32)
    new, = attention_ops._paged_cache_update((pool,), (rows,), table, lens)
    want = np.asarray(pool).copy()
    want[5, 0:4] = np.asarray(rows[0])      # rows 8-11: page 1 of slot 0
    want[1, 0:4] = np.asarray(rows[1])      # rows 0-3
    assert np.array_equal(np.asarray(new), want)    # the free slot: nothing
    # one row a slot lands at lens - 1, as it always did
    one, = attention_ops._paged_cache_update((pool,), (rows[:, :1],), table,
                                             lens)
    want = np.asarray(pool).copy()
    want[5, 3], want[1, 3] = np.asarray(rows[0, 0]), np.asarray(rows[1, 0])
    assert np.array_equal(np.asarray(one), want)
    # a limit a row: a row whose limit is 0 lands nowhere, also where its
    # place lies past the table that is fed (slot 0: rows 16-17)
    limits = jnp.asarray([[12, 12, 0, 0], [4, 0, 4, 4], [0] * 4], jnp.int32)
    dead, = attention_ops._paged_cache_update(
        (pool,), (rows,), table, jnp.asarray([[18], [4], [0]], jnp.int32),
        limits)
    want = np.asarray(pool).copy()
    want[5, 6:8] = np.asarray(rows[0, :2])  # rows 14-15
    want[1, 0], want[1, 2:4] = np.asarray(rows[1, 0]), np.asarray(rows[1, 2:])
    assert np.array_equal(np.asarray(dead), want)


def test_block_rows_commits_and_stores_with_the_opening():
    state = jnp.asarray([[11, 12, 13, 14], [21, 22, 23, 24],
                         [31, 32, 33, 34], [41, 42, 43, 44],
                         [51, 52, 53, 54]], jnp.int32)
    token = jnp.asarray([[7], [8], [9], [5], [6]], jnp.int32)
    #          commit at 1  at 2   completes (3)  first row   free
    pos = jnp.asarray([[9], [14], [15], [4], [0]], jnp.int32)
    lens = jnp.asarray([[12], [16], [16], [8], [0]], jnp.int32)
    ids, row_pos, row_lens, pick, end, new = map(
        np.asarray, block_ops.block_rows(token, pos, lens, state, MASK))
    M = [MASK] * 4
    # half A: the slot's block, committed through ``at``; half B: masked
    assert ids[:4].tolist() == [[11, 7, MASK, MASK] + M,
                                [21, 22, 8, MASK] + M,
                                [31, 32, 33, 9] + M, [5, MASK, MASK, MASK] + M]
    assert new.tolist() == [[11, 7, 13, 14], [21, 22, 8, 24],
                            [31, 32, 33, 9], [5, 42, 43, 44],
                            [51, 52, 53, 54]]
    assert row_pos[:4].tolist() == [list(range(8, 16)), list(range(12, 20)),
                                    list(range(12, 20)), list(range(4, 12))]
    # the leftmost masked row: where the block is complete, the first of
    # the block behind it
    assert pick.argmax(-1).tolist()[:4] == [2, 3, 4, 1]
    # half B is live (and sees half A) only behind a completed block
    assert row_lens.reshape(5, 8).tolist() == [
        [12] * 4 + [0] * 4, [16] * 4 + [0] * 4, [16] * 4 + [20] * 4,
        [8] * 4 + [0] * 4, [0] * 8]
    assert end.reshape(-1).tolist() == [16, 20, 20, 12, 0]
    tail = block_ops.block_tail(jnp.arange(100, 120, dtype=jnp.int32),
                                jnp.zeros(20).at[9].set(1.0), 4)
    assert np.asarray(tail).tolist() == [108, 109, 110, 111]


# -- the bundle against the reference ----------------------------------------

def test_the_bundle_says_it_carries_blocks(predictor):
    assert predictor.block_length == L and predictor.mask_token_id == MASK
    assert predictor.state_vars == [block_moe.STATE_VAR]
    assert [c["name"] for c in predictor.decode_stats] == [
        "moe_assignments", "moe_experts_touched", "moe_max_load"]
    # pages through the END of the block the last token lies in
    assert predictor.pages_needed(5, 1) == 1        # rows 0-7
    assert predictor.pages_needed(7, 1) == 1
    assert predictor.pages_needed(7, 2) == 2        # position 8: rows 8-11
    assert predictor.pages_needed(60, 100) == 8     # never past max_len


@pytest.mark.parametrize("n", [8, 9, 10, 11, 17, 32])
def test_prefill_matches_the_reference(predictor, cfg, weights, n):
    """The prompt's full blocks, its tail (``n % 4`` = 0, 1, 2, 3) and the
    mask rows behind it in one forward under the block-causal mask: the
    logits are row ``n``'s own."""
    prompt = _prompt(n, seed=n)
    logits, kv = predictor.prefill(prompt)
    _close(logits, _ref_logits(weights, cfg, prompt + [0], [n - 1])[0])
    tail = np.asarray(kv[-1])[0].tolist()
    assert tail[:n % L] == prompt[n - n % L:] and len(tail) == L
    # the full blocks' K/V saw nothing of the open block: they are final
    start = n - n % L
    if start:
        again, kv_full = predictor.prefill(prompt[:start])
        for a, b in zip(kv[:-1], kv_full[:-1]):
            np.testing.assert_allclose(np.asarray(a)[0, :start],
                                       np.asarray(b)[0, :start], atol=1e-6)


def test_a_prompt_may_hold_the_mask_token(predictor, cfg, weights):
    """Masked-ness is a matter of position: token 63 in a prompt is a
    token like any other, in the full blocks and in the tail."""
    prompt = _prompt(10, seed=2)
    prompt[3] = prompt[9] = MASK
    logits, _ = predictor.prefill(prompt)
    _close(logits, _ref_logits(weights, cfg, prompt + [0], [9])[0])
    other = list(prompt)
    other[9] = 5
    assert np.abs(logits - predictor.prefill(other)[0]).max() > 1e-3


@pytest.mark.parametrize("n", [8, 9, 10, 11])
def test_cached_steps_match_the_reference(predictor, cfg, weights, n):
    """Prefill, the compiled seed, then seven cached steps through the
    paged pool, across a block boundary (the step whose token completes
    a block stores it and opens the next in one forward): every step's
    logits are the reference's for the next position given everything
    before it."""
    seq = _prompt(n, seed=20 + n)
    tok = int(np.argmax(_admit(predictor, 1, seq)))
    try:
        for _ in range(7):
            seq.append(tok)
            got = _step(predictor, {1: (tok, len(seq) - 1)})[1]
            _close(got, _ref_logits(weights, cfg, seq, [len(seq) - 1])[0])
            tok = int(np.argmax(got))
    finally:
        predictor.free_slot_pages(1)


def test_slots_at_different_offsets_share_a_step(predictor, cfg, weights):
    """Three slots whose newest tokens sit at offsets 0, 2 and 3 of their
    blocks (the last one's step stores its block and opens the next; the
    others' second halves are dead) in the same steps."""
    seqs = {0: _prompt(8, seed=31), 2: _prompt(14, seed=32),
            3: _prompt(11, seed=33)}
    toks = {s: int(np.argmax(_admit(predictor, s, seq)))
            for s, seq in seqs.items()}
    try:
        for _ in range(5):
            for s in seqs:
                seqs[s].append(toks[s])
            got = _step(predictor, {s: (toks[s], len(seq) - 1)
                                    for s, seq in seqs.items()})
            for s, seq in seqs.items():
                _close(got[s], _ref_logits(weights, cfg, seq,
                                           [len(seq) - 1])[0])
                toks[s] = int(np.argmax(got[s]))
    finally:
        for s in seqs:
            predictor.free_slot_pages(s)


def _slot_rows(predictor, slot, name, n):
    """The first ``n`` rows that ``slot`` holds in pool ``name``."""
    pool = np.asarray(predictor._scope.find_var(name))
    pages = predictor._slot_pages[slot]
    return pool[pages].reshape(-1, pool.shape[-1])[:n]


def test_a_fused_step_stores_what_a_store_pass_stored(predictor):
    """The K/V that a step's first half leaves in the pool are the
    block's FINAL ones: those of a forward over the block with every
    token committed under the block-causal mask, which is what a prefill
    of the same tokens computes for its full blocks (and what the store
    pass wrote).  Two blocks are completed here, each by one step that
    also opened the next."""
    seq = _prompt(9, seed=45)
    tok = int(np.argmax(_admit(predictor, 2, seq)))
    try:
        for _ in range(7):                  # positions 9 .. 15
            seq.append(tok)
            tok = int(np.argmax(_step(predictor, {2: (tok, len(seq) - 1)})[2]))
        assert len(seq) == 16
        _, kv = predictor.prefill(seq)
        for name, want in zip(predictor.cache_vars, kv):
            np.testing.assert_allclose(
                _slot_rows(predictor, 2, name, 16), np.asarray(want)[0, :16],
                atol=2e-5)
    finally:
        predictor.free_slot_pages(2)


def test_a_dead_half_writes_no_row_and_takes_no_expert(predictor):
    """A slot whose token does not complete its block: the rows behind
    the block (where a dead second half WOULD land) stay as they were,
    and the step's assignments are those of the block's L rows; the
    step that completes the block writes them and routes 2L rows."""
    k = 2                                   # toy_config: top-2, 2 layers
    seq = _prompt(9, seed=46)
    tok = int(np.argmax(_admit(predictor, 0, seq)))
    name = predictor.cache_vars[0]
    try:
        before = _slot_rows(predictor, 0, name, 24).copy()
        _step(predictor, {0: (tok, 9)})     # at 1 of block 8-11
        stats = predictor.count_decode_stats(predictor.last_decode_stats)
        after = _slot_rows(predictor, 0, name, 24)
        assert np.array_equal(after[:8], before[:8])
        assert np.array_equal(after[12:], before[12:])  # rows 12-15: none
        assert not np.array_equal(after[8:12], before[8:12])
        assert stats["moe_assignments"] == L * k * 2
        _step(predictor, {0: (5, 10)})
        _step(predictor, {0: (6, 11)})      # completes 8-11, opens 12-15
        stats = predictor.count_decode_stats(predictor.last_decode_stats)
        last = _slot_rows(predictor, 0, name, 24)
        assert not np.array_equal(last[12:16], before[12:16])
        assert np.array_equal(last[16:], before[16:])
        assert stats["moe_assignments"] == 2 * L * k * 2
    finally:
        predictor.free_slot_pages(0)


def test_opening_a_block_needs_its_pages(predictor):
    """A blocking step whose token completes a block opens the next one
    in the same forward: without pages for it the step is refused, not
    written to page 0."""
    prompt = _prompt(7, seed=40)
    logits, kv = predictor.prefill(prompt)
    predictor.alloc_slot_pages(0, predictor.pages_needed(7, 1))
    try:
        predictor.write_slot(0, kv, 7)
        with pytest.raises(RuntimeError, match="too few"):
            _step(predictor, {0: (int(np.argmax(logits)), 7)})
    finally:
        predictor.free_slot_pages(0)


def test_the_reference_loop_is_its_single_token_conditionals(cfg, weights,
                                                             loop):
    """The published loop (denoising passes, then the store pass, a block)
    commits, at every position, the greedy token of 'the logits for
    position t + 1 given every position <= t': what the harness's two
    comparisons hold a served stream to."""
    prompt = _prompt(6, seed=50)
    tokens = loop(prompt, 9)
    seq = list(prompt) + tokens
    got = _ref_logits(weights, cfg, seq[:-1] + [0],
                      list(range(5, 5 + 9))).argmax(-1)
    assert got.tolist() == tokens


# -- the scheduler ---------------------------------------------------------------

@pytest.mark.parametrize("n, m", [(5, 9), (8, 6), (10, 13), (11, 1),
                                  (12, 7), (3, 2), (17, 20)])
def test_streams_are_the_published_loops(predictor, loop, n, m):
    """Exactly ``m`` tokens, ``m`` a multiple of the block or not, the
    prompt's tail opening the first block or not."""
    prompt = _prompt(n, seed=60 + n)
    with gen_lookahead.scheduler(predictor) as (sched, gained):
        stream = sched.submit(prompt, max_new_tokens=m)
        got = list(stream)
    assert got == loop(prompt, m) and stream.finish_reason == "length"
    assert gained["gen.tokens"] == m
    assert gained["gen.decode.rows_discarded"] == 0
    assert gen_lookahead.pool_is_whole(predictor)


def test_every_turn_yields_and_fused_slots_are_counted(predictor, loop):
    """Four streams in one pool: ``gen.tokens`` counts tokens emitted,
    ``gen.block.*`` the forwards, those that stored a block and opened
    the next, and the live rows; the ``gen.decode_step`` spans say what
    each collected step carried: no turn without a token."""
    m = profiler.runtime_metrics
    names = ("gen.block.forwards", "gen.block.fused", "gen.block.rows")
    asks = [(_prompt(n, seed=70 + n), k)
            for n, k in [(8, 12), (9, 12), (10, 8), (11, 9)]]
    before = {k: m.counter(k) for k in names}
    ptrace.enable(1 << 12)
    ptrace.clear()
    try:
        with gen_lookahead.scheduler(predictor) as (sched, gained):
            streams = [sched.submit(p, max_new_tokens=k) for p, k in asks]
            got = [list(s) for s in streams]
        spans = [s["attrs"] for s in ptrace.snapshot_spans()
                 if s["name"] == "gen.decode_step" and "live" in s["attrs"]]
    finally:
        ptrace.disable()
    assert got == [loop(p, k) for p, k in asks]
    tokens = sum(k for _, k in asks)
    assert gained["gen.tokens"] == tokens
    assert gained["gen.decode.rows_discarded"] == 0
    forwards, fused, rows = (m.counter(k) - before[k] for k in names)
    # the prefill gives a stream's first token, a forward every other one
    assert forwards == tokens - len(asks)
    # a forward that feeds position p with p + 1 a multiple of L is fused
    assert fused == sum(
        1 for p, k in asks for at in range(len(p), len(p) + k - 1)
        if (at + 1) % L == 0) >= 8
    assert rows == (forwards + fused) * L
    assert sum(a["live"] for a in spans) == forwards \
        == sum(a["yielded"] + a["discarded"] for a in spans)
    assert sum(a["fused"] for a in spans) == fused
    assert all(a["stored"] == 0 for a in spans)
    assert all(a["block_rows"] == (a["live"] + a["fused"]) * L
               for a in spans)
    assert all("moe_experts_touched" in a for a in spans)


def test_a_discarded_row_is_a_forward_without_a_token(predictor, loop):
    """An EOS the host could not foresee: the step dispatched ahead
    carried the slot, so over the stream's spans ``live`` = ``yielded``
    + ``discarded`` with one row discarded."""
    prompt = _prompt(9, seed=75)
    want = loop(prompt, 9)
    k = next(i for i in range(1, 9) if want[i] not in want[:i])
    ptrace.enable(1 << 12)
    ptrace.clear()
    try:
        with gen_lookahead.scheduler(predictor) as (sched, gained):
            got = list(sched.submit(prompt, max_new_tokens=9,
                                    eos_id=want[k]))
        spans = [s["attrs"] for s in ptrace.snapshot_spans()
                 if s["name"] == "gen.decode_step" and "live" in s["attrs"]]
    finally:
        ptrace.disable()
    assert got == want[:k + 1]
    assert gained["gen.decode.rows_discarded"] == 1
    assert sum(a["discarded"] for a in spans) == 1
    assert sum(a["yielded"] for a in spans) == k
    assert sum(a["live"] for a in spans) == k + 1


@pytest.mark.parametrize("drill", [
    gen_lookahead.eos_beside_live_neighbours,
    gen_lookahead.cancel_then_readmit,
    gen_lookahead.admission_in_flight,
    gen_lookahead.drain_and_abort_in_flight],
    ids=lambda d: d.__name__)
def test_lookahead_drill(drill, predictor, loop):
    """The lookahead's drills on a bundle whose steps carry blocks (the
    device commits its own pick to the block, also where that completes
    it): an EOS or a cancel in mid-block beside live neighbours (the
    pages come back), an admission with a step in flight, a drain's
    checkpoints and a kill at a whole-token boundary."""
    drill(predictor, loop)


def test_a_stream_ends_where_the_pool_does(predictor, loop):
    """The last position a block bundle can commit is ``max_len - 1``:
    the token behind it would open a block past the pool's end."""
    prompt = _prompt(32, seed=80)
    with gen_lookahead.scheduler(predictor) as (sched, gained):
        streams = [sched.submit(prompt, max_new_tokens=100),
                   sched.submit(prompt[:31], max_new_tokens=100)]
        got = [list(s) for s in streams]
    assert [len(g) for g in got] == [32, 33]
    assert got == [loop(prompt, 32), loop(prompt[:31], 33)]
    assert all(s.finish_reason == "length" for s in streams)
    assert gained["gen.decode.rows_discarded"] == 0
    assert gen_lookahead.pool_is_whole(predictor)


@pytest.mark.parametrize("taken", [1, 2, 3, 4, 6])
def test_a_resume_in_mid_block_continues_token_identically(predictor, loop,
                                                           taken):
    """A checkpoint at a token boundary inside a block, resumed as a
    prefill of prompt + emitted tokens whose tail opens the block."""
    prompt = _prompt(9, seed=90)
    with gen_lookahead.scheduler(predictor, stall=0.03) as (sched, _):
        stream = sched.submit(prompt, max_new_tokens=14)
        head = gen_lookahead.take(stream, taken)
        ckpt, = sched.drain(deadline_s=0.0)
        tail, (kind, _) = gen_lookahead.rest(stream)
    assert kind == "migrate" and ckpt["tokens"] == head + tail
    assert gen_lookahead.pool_is_whole(predictor)
    with gen_lookahead.scheduler(predictor) as (sched, _):
        resumed = list(sched.submit(ckpt["prompt"] + ckpt["tokens"],
                                    max_new_tokens=ckpt["remaining_tokens"]))
    assert ckpt["tokens"] + resumed == loop(prompt, 14)


def test_a_served_stream_over_http_is_the_loop(tmp_path, cfg, weights, loop):
    """``InferenceServer /generate`` on the same scheduler, predictor and
    page pool as every other bundle."""
    from paddle_tpu.serving import InferenceServer, ServingClient
    hp = block_moe.BlockMoEConfig.from_dict(cfg)
    hp.dtype, hp.max_len = "float32", 64
    path = block_moe.export_block_model(
        str(tmp_path / "bundle"), hp, num_slots=SLOTS,
        prompt_buckets=BUCKETS, page_len=PAGE_LEN)
    server = InferenceServer(path, port=0, warmup=True)
    server.start_background()
    try:
        assert server.wait_until_ready(120)
        for name, value in weights.items():
            server.gen_predictor._scope.set_var(name, value)
        client = ServingClient("%s:%d" % tuple(server.addr[:2]))
        prompt = _prompt(10, seed=95)
        events = list(client.generate(prompt, max_new_tokens=11))
        tokens = [e["token"] for e in events if "token" in e]
        assert [e["index"] for e in events if "token" in e] \
            == list(range(11))
        assert tokens == loop(prompt, 11)
        assert events[-1].get("finish_reason") == "length"
    finally:
        server.shutdown()
