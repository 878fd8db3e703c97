"""Hyper-connections and the latent drafting turn on the paged serving
path (``ops/mhc_ops.py``, ``decoder.hc_sublayer`` / ``draft_turn``,
``models/latent_moe.py`` with ``hc_mult`` and
``num_nextn_predict_layers``, ``paged_attention_latent`` under a limit a
row): the exported bundle against the plain reference
(``benchmark/reference/xing4_ref.py``: the main model's forward and,
separately, the MTP module's teacher-forced draft logits) through
prefill, chunks and cached turns; the drafted stream token for token the
greedy stream; the wrapper's properties; each control of the benchmark's
limit caught; the share arithmetic; the new ops' type and cost rules.
Toy widths, float32: d 64, 4 heads, the published layers 1-2 (dense
first) and the MTP block, 4 streams, 20 Sinkhorn rounds, 16 experts top-2
+ a shared one, chunks of 16 rows, pages of 8."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import profiler
from paddle_tpu.analysis import cost, typecheck
from paddle_tpu.gen import GenPredictor, GenScheduler
from paddle_tpu.models import decoder, latent_moe
from paddle_tpu.ops import attention_ops, mhc_ops

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from models import xing4 as adapter                     # noqa: E402
from reference import xing4_ref as ref                  # noqa: E402

SLOTS, PAGE_LEN, BUCKETS, V = 3, 8, [16, 32, 48], 64
TOL = 2e-4          # float32 program against the float32 reference


def toy_config(**over):
    cfg = {"hidden_size": 64, "num_hidden_layers": 2, "layer_offset": 1,
           "first_k_dense_replace": 1, "vocab_size": V,
           "rms_norm_eps": 1e-6, "num_attention_heads": 4,
           "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
           "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_theta": 10000,
           "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 4,
                            "mscale": 1, "mscale_all_dim": 1,
                            "original_max_position_embeddings": 16,
                            "type": "yarn"},
           "intermediate_size": 96, "moe_intermediate_size": 32,
           "n_routed_experts": 16, "n_shared_experts": 1,
           "num_experts_per_tok": 2, "routed_scaling_factor": 2,
           "norm_topk_prob": True, "experts_held": 16, "expert_offset": 0,
           "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
           "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
           "num_nextn_predict_layers": 1}
    cfg.update(over)
    return cfg


def _weights(cfg, seed=7):
    """The adapter's seeded VALUES held in float32; the router's offset
    row is taken out (it is made for the published widths) and its bias
    is wide enough to change which experts a row takes."""
    w = {k: v.astype(jnp.float32)
         for k, v in adapter.seeded_weights(cfg, seed).items()}
    for i in adapter.moe_layers(cfg):
        w[f"lat{i}_gate.w"] = w[f"lat{i}_gate.w"].at[0].set(0.0)
        w[f"lat{i}_gate.bias"] = jax.random.uniform(
            jax.random.PRNGKey(len(str(i)) * 31 + seed), (16,), jnp.float32,
            -0.3, 0.3)
    return w


@pytest.fixture(scope="module")
def cfg():
    return toy_config()


@pytest.fixture(scope="module")
def weights(cfg):
    return _weights(cfg)


@pytest.fixture(scope="module")
def predictor(tmp_path_factory, cfg, weights):
    path = str(tmp_path_factory.mktemp("xing4") / "bundle")
    hp = latent_moe.LatentMoEConfig.from_dict(cfg)
    hp.dtype, hp.max_len = "float32", 48
    was, decoder.CHUNK_ROWS = decoder.CHUNK_ROWS, 16
    try:
        latent_moe.export_latent_model(path, hp, num_slots=SLOTS,
                                       prompt_buckets=BUCKETS,
                                       page_len=PAGE_LEN)
    finally:
        decoder.CHUNK_ROWS = was
    p = GenPredictor(path)
    for name, value in weights.items():
        old = p._scope.find_var(name)
        assert old is not None and tuple(old.shape) == tuple(value.shape), \
            name
        p._scope.set_var(name, value)
    return p


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(1, V, size=n).tolist()


_JITTED = {}
PAD_TO = 48     # every sequence here is no longer: ONE compiled reference


def _padded(fn, weights, cfg, ids, positions, **controls):
    """``fn`` of the sequence padded behind its end (where a causal model
    does not look), jitted once a function and set of controls."""
    key = (fn.__name__, tuple(sorted(controls.items())))
    if key not in _JITTED:
        _JITTED[key] = jax.jit(lambda w, ids, at: fn(w, cfg, ids, at,
                                                     **controls))
    ids = list(ids) + [0] * (PAD_TO - len(ids))
    return np.asarray(_JITTED[key](weights, jnp.asarray(ids, jnp.int32),
                                   jnp.asarray(positions, jnp.int32)))


def _main(weights, cfg, ids, positions, **controls):
    return _padded(ref.forward_logits, weights, cfg, ids, positions,
                   **controls)


def _draft(weights, cfg, ids, positions):
    return _padded(ref.draft_logits, weights, cfg, ids, positions)


def _err(got, want):
    return float(np.abs(np.asarray(got) - want).max()) \
        / float(want.max() - want.min())


def _greedy(weights, cfg, prompt, n):
    """The undrafted greedy stream, by the cache-free reference."""
    seq, out = list(prompt), []
    for _ in range(n):
        out.append(int(np.argmax(_main(weights, cfg, seq, [len(seq) - 1]))))
        seq.append(out[-1])
    return out


def _draft_of(predictor, slot):
    return int(np.asarray(predictor._scope.find_var(
        latent_moe.DRAFT_VAR))[slot, 0])


# -- the bundle ----------------------------------------------------------------

def test_the_bundle_says_its_streams_and_that_it_drafts(predictor):
    meta = predictor.meta
    assert meta["hyper_connections"] == {"streams": 4, "sinkhorn_iters": 20,
                                         "wrappers": 6}
    assert meta["speculative"] == {"rows": 2, "feed": "gen_spec",
                                   "draft_var": latent_moe.DRAFT_VAR}
    assert predictor.spec_rows == 2 and predictor.prefill_chunks == [8, 16]
    # the MTP block pages like a layer; the draft is a state array
    assert meta["cache_vars"] == ["lat0_paged_c", "lat1_paged_c",
                                  "lat_mtp_paged_c"]
    assert meta["state_vars"] == [latent_moe.DRAFT_VAR]
    assert {"gen_slot", "gen_next_ids"} <= set(predictor._pre_feeds)
    assert predictor._dec_feeds[-1] == "gen_spec"
    assert len(predictor._dec_fetch) == 3
    # every wrapper op lies under ``mhc`` INSIDE its sublayer's group,
    # the MTP block's under ``mtp`` too
    ops = predictor._dec_prog.global_block().ops
    scopes = {op.attrs.get("op_namescope") for op in ops
              if op.type in ("mhc_pre", "mhc_post")}
    assert scopes == {"gen_decode/attn/mhc", "gen_decode/dense/mhc",
                      "gen_decode/experts/mhc", "gen_decode/mtp/attn/mhc",
                      "gen_decode/mtp/experts/mhc"}
    assert sum(op.type == "mhc_pre" for op in ops) == 6
    latent = [op for op in ops if op.type == "paged_attention_latent"]
    assert len(latent) == 3 and all(op.input("RowLens") for op in latent)
    # the chunk's span says what its wrappers moved
    assert predictor._chunk_wrapped(16) == {"mhc_rows": 96}


def test_a_drafter_beside_a_ring_or_an_indexer_is_not_loaded():
    """GLM-5.2's and dots3-note's published files name an MTP module too:
    under an indexer or beside window layers it stays unloaded, as before
    this builder could draft, and their programs are what they were."""
    for more in ({"index_topk": 4},
                 {"layer_types": ["full_attention"] * 4,
                  "sliding_window_size": 8}):
        hp = latent_moe.LatentMoEConfig.from_dict(toy_config(**more))
        assert not hp.drafts and hp.blocks == [0, 1]
    with pytest.raises(NotImplementedError, match="deeper"):
        latent_moe.LatentMoEConfig.from_dict(
            toy_config(num_nextn_predict_layers=2))


# -- program against reference -------------------------------------------------

@pytest.mark.parametrize("n", [13, 29], ids=["one-chunk", "two-chunks"])
def test_prefill_cached_turns_and_drafts_are_the_references(
        predictor, cfg, weights, n):
    """A prompt of one or two chunks, then six BLOCKING turns (the draft
    row off: the committed token's logits) across page boundaries; behind
    every one the draft the MTP module left in the slot's state is the
    reference's teacher-forced pick."""
    prompt = _prompt(n, seed=n)
    logits, kv = predictor.prefill(prompt)
    assert _err(logits, _main(weights, cfg, prompt, [n - 1])[0]) < TOL
    predictor.alloc_slot_pages(1, predictor.pages_needed(n, 8))
    try:
        predictor.write_slot(1, kv, n)
        seq, tok = list(prompt), int(np.argmax(logits))
        assert _draft_of(predictor, 1) == int(np.argmax(
            _draft(weights, cfg, seq + [tok], [n - 1])))
        for _ in range(6):
            tokens, pos, lens = (np.zeros(SLOTS, np.int32) for _ in range(3))
            tokens[1], pos[1], lens[1] = tok, len(seq), len(seq) + 1
            got = predictor.decode_step(tokens, pos, lens=lens)[1]
            seq.append(tok)
            assert _err(got, _main(weights, cfg, seq,
                                   [len(seq) - 1])[0]) < TOL
            tok = int(np.argmax(got))
            assert _draft_of(predictor, 1) == int(np.argmax(
                _draft(weights, cfg, seq + [tok], [len(seq) - 1])))
    finally:
        predictor.free_slot_pages(1)


REQUESTS = [(5, 9), (13, 12), (21, 1), (30, 14), (9, 2)]


def test_the_drafted_stream_is_the_greedy_stream(predictor, cfg, weights):
    """Five requests through the scheduler over three slots: admissions
    by chunks between turns, advances of one and two mixed in one pool:
    every served token is the MAIN model's greedy token, some drafts are
    kept and some are not, and the counters add up."""
    m = profiler.runtime_metrics
    before = {k: m.counter("gen.spec." + k)
              for k in ("drafted", "accepted", "emitted", "slot_turns")}
    sched = GenScheduler(predictor)
    try:
        requests = [(_prompt(n, seed=100 + n), cap) for n, cap in REQUESTS]
        streams = [sched.submit(p, max_new_tokens=cap)
                   for p, cap in requests]
        got = [list(s) for s in streams]
    finally:
        sched.close()
    for (prompt, cap), tokens in zip(requests, got):
        assert tokens == _greedy(weights, cfg, prompt, cap), (len(prompt),
                                                              cap)
    gained = {k: m.counter("gen.spec." + k) - v for k, v in before.items()}
    assert gained["drafted"] == gained["slot_turns"] > 0
    assert gained["emitted"] == gained["slot_turns"] + gained["accepted"]
    assert 0 < gained["accepted"] < gained["drafted"], gained
    assert predictor.free_pages == predictor.num_pages


@pytest.mark.parametrize("control", ["mhc_static", "sinkhorn_1",
                                     "streams_mean", "fp8", "draft"])
def test_each_control_fails_the_tolerance(cfg, weights, control):
    """What the cell's limit is set against: alpha = 0, one Sinkhorn
    round for twenty, H_res = 1 / n, the matrices in float8 and the
    module's pick served unverified all read far above what the program
    is held to."""
    prompt = _prompt(40, seed=3)
    want = _main(weights, cfg, prompt, [38, 39])
    got = np.asarray(jax.jit(lambda w, ids: adapter.control_logits(
        w, cfg, ids, jnp.asarray([38, 39]), control))(
            weights, jnp.asarray(prompt + [0] * (PAD_TO - 40), jnp.int32)))
    assert min(_err(got[j], want[j]) for j in (0, 1)) > 10 * TOL


# -- the wrapper -----------------------------------------------------------------

def _wrapper(n, c, rows=37, seed=0, spread=1.5):
    rng = np.random.RandomState(seed)
    draw = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)
    return (draw(rows, n, c), draw(n * c, n * (n + 2)) / (n * c) ** 0.5,
            jnp.asarray([1.0, 1.0, 1.0]),
            jnp.asarray(rng.uniform(-spread, spread, n * (n + 2)),
                        jnp.float32))


def test_h_res_is_doubly_stochastic_after_twenty_rounds_not_after_one():
    x, phi, alpha, bias = _wrapper(4, 64, spread=0.5)
    alpha = alpha * 0.5
    sums = lambda res: np.abs(np.concatenate(
        [np.asarray(res.sum(-1)), np.asarray(res.sum(-2))]) - 1.0).max()
    twenty = mhc_ops.mhc_coefficients(x, phi, alpha, bias, 20, 1e-6, -30.,
                                      30., 1e-6, kernel=False)[2]
    one = mhc_ops.mhc_coefficients(x, phi, alpha, bias, 1, 1e-6, -30., 30.,
                                   1e-6, kernel=False)[2]
    assert sums(twenty) < 1e-5 and float(twenty.min()) >= 0
    assert sums(one) > 1e-3 > 100 * sums(twenty)
    # the mappings move with the token
    assert float(jnp.std(twenty[:, 0, 0])) > 0.05


def test_the_kernel_is_the_composed_form():
    """The coefficients' Pallas kernel (interpret mode) against the
    composed form, rows that fill no lane tile and rows past one grid
    step; and the reference's plain loop."""
    for rows in (5, 700):
        x, phi, alpha, bias = _wrapper(4, 32, rows=rows, seed=rows)
        args = (x, phi, alpha, bias, 20, 1e-6, -30., 30., 1e-6)
        composed = mhc_ops.mhc_coefficients(*args, kernel=False)
        kernel = mhc_ops.mhc_coefficients(*args, kernel="interpret")
        for a, b in zip(composed, kernel):
            np.testing.assert_allclose(a, b, atol=2e-6)
    hc = {"hc_sinkhorn_iters": 20, "hc_eps": 1e-6, "rms_norm_eps": 1e-6,
          "mhc_h_res_clamp_min": -30., "mhc_h_res_clamp_max": 30.}
    plain = ref.mappings(x, phi, alpha, bias, hc)
    for a, b in zip(composed, plain):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_one_stream_with_the_mappings_at_one_is_the_plain_residual():
    """``hc_mult`` 1, H_pre = H_post = H_res = 1: ``x + F(x)``, what
    ``decoder_layer`` builds without hyper-connections."""
    x, phi, _, _ = _wrapper(1, 64)
    alpha = jnp.zeros(3)
    bias = jnp.asarray([40.0, 0.0, 5.0])    # sigmoid -> 1, 2 sigmoid(0) = 1
    u, post, res = mhc_ops.mhc_pre(x, phi, alpha, bias, 20, 1e-6)
    np.testing.assert_allclose(u, x[:, 0], atol=1e-6)
    f = lambda h: jnp.tanh(h) * 3.0
    out = mhc_ops.mhc_post(x, f(u), post, res)
    np.testing.assert_allclose(out[:, 0], x[:, 0] + f(x[:, 0]), atol=1e-4)
    # and the library passes a one-stream model through untouched
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        latent_moe.build_paged_decode_program(latent_moe.LatentMoEConfig(),
                                              2, 8, 16)
    assert not any(op.type.startswith("mhc_") or "mhc" in str(
        op.attrs.get("op_namescope")) for op in main.global_block().ops)


def test_the_shares_add_up_to_the_uncut_layer(cfg, weights):
    """Guide section 4: the routed parts that all four shares of the
    experts give, the shared expert and everything outside the experts
    counted ONCE, add up to the uncut reference's layer."""
    h = jax.random.normal(jax.random.PRNGKey(0), (24, 64), jnp.float32)
    value = ref._values(weights, jnp.float32, None)
    p = lambda name, cast=True: value(f"lat1_{name}", cast)
    whole = ref.moe(h, p, cfg, jnp.float32)
    held = 4

    def share(k):
        pk = lambda name, cast=True: (
            value(f"lat1_{name}", cast)[k * held:(k + 1) * held]
            if name in ("wg", "wu", "wd") else value(f"lat1_{name}", cast))
        return ref.moe(h, pk, {**cfg, "experts_held": held,
                               "expert_offset": k * held}, jnp.float32,
                       shared=False)

    only_shared = whole - ref.moe(h, p, cfg, jnp.float32, shared=False)
    total = sum(share(k) for k in range(16 // held)) + only_shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(only_shared).max()) > 0.1


# -- two rows a slot through the latent kernel ---------------------------------

@pytest.mark.parametrize("L", [1, 2])
def test_latent_rows_under_their_own_limits_are_the_composed_form(L):
    """``paged_attention_latent``'s kernel (interpret mode) with ``L``
    rows a slot, uneven limits, a dead row (limit 0: zeros) and a free
    slot, against the gather form; at ``L`` = 2 the committed row does
    not see the draft's."""
    rng = np.random.RandomState(L)
    S, P, PL, H, W, Vw = 4, 3, 8, 4, 128, 64
    draw = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)
    cache, q = draw(S * P, PL, W), draw(S, L, H * W)
    table = jnp.asarray(rng.permutation(S * P).reshape(S, P), jnp.int32)
    limits = jnp.asarray([[20, 21], [5, 0], [0, 0], [17, 18]],
                         jnp.int32)[:, :L]
    walk = jnp.max(limits, axis=1, keepdims=True)
    want = attention_ops._xla_latent_attention(
        q, cache, table, walk, H, Vw, 0.3, row_lens=limits)
    got = attention_ops._pallas_latent_rows(
        q, cache, table, walk, H, 0.3, limits, True, Vw)
    assert got is not None
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert not np.asarray(want[2]).any()
    if L == 2:
        assert not np.asarray(want[1, 1]).any()
        assert np.abs(np.asarray(want[1, 0])).min() > 0
        # the committed row under ITS limit alone is what it reads here
        alone = attention_ops._xla_latent_attention(
            q[:, :1], cache, table, limits[:, :1], H, Vw, 0.3)
        np.testing.assert_allclose(want[:, :1], alone, atol=1e-6)


# -- type and cost rules ---------------------------------------------------------

def _wrapped_program(phi_rows=256):
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        x = decoder.data("x", [6, 4, 64])
        hp = latent_moe.LatentMoEConfig()
        hp.dtype = "float32"
        decoder.hc_sublayer(x, hp, "t_hc", lambda u: (u, None))
        if phi_rows != 256:
            main.global_block().var("t_hc.phi").shape = (phi_rows, 24)
    return main


def test_the_new_ops_are_typed_and_priced(predictor):
    assert {"mhc_pre", "mhc_post"} <= set(typecheck._RULES)
    assert {"mhc_pre", "mhc_post"} <= cost.covered_op_types()
    main = _wrapped_program()
    diags, uncovered = typecheck.check_types(main)
    assert not diags and not uncovered, diags
    bad, _ = typecheck.check_types(_wrapped_program(phi_rows=200))
    assert any("streams x" in d.message for d in bad)
    block = main.global_block()
    pre = next(op for op in block.ops if op.type == "mhc_pre")
    assert tuple(block.var(pre.output("U")[0]).shape) == (6, 64)
    assert tuple(block.var(pre.output("Res")[0]).shape) == (6, 4, 4)
    priced = cost.estimate(main)
    by_type = priced.by_op_type()
    assert not priced.uncovered
    assert by_type["mhc_pre"]["flops"] > by_type["mhc_post"]["flops"] > 0
    # the least form: n streams in, y in, n streams out
    assert by_type["mhc_post"]["bytes"] == 6 * (9 * 64 * 4 + 4 * 4 * 5)
    # the served programs: nothing unpriced, and a turn's two rows a
    # slot each score the live rows
    dec = cost.estimate(predictor._dec_prog, paged_live_rows=24)
    assert not [t for t in dec.uncovered if not t.startswith("spec_")]
    assert dec.by_op_type()["paged_attention_latent"]["flops"] \
        == 3 * 2 * SLOTS * 2 * 24 * 4 * (128 + 32)
