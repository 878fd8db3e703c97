"""Solar-Open2's layout on the paged serving path (``models/hybrid_moe.py``
with ``K``, ``G`` and ``S`` sublayers): the KDA ops against the recurrence
one token at a time, the exported bundle (a prompt chunk by chunk with the
matrix state and the conv window carried in the slot, then cached decode
steps) against the plain reference
(``benchmark/reference/solar_open2_ref.py``) on seeded weights, two
streams in neighbouring slots, the scheduler's chunked admissions, the
bundle contract and the expert-parallel share arithmetic.  Toy widths: d
64, 4 KDA heads x 16, 4 / 2 softmax heads x 16, 16 experts top-4, four
published layers ``GLLL``."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import profiler
from paddle_tpu.gen import GenPredictor, GenScheduler
from paddle_tpu.models import hybrid_moe
from paddle_tpu.ops import kda_ops, moe_ops, ssm_ops

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from models import solar_open2 as adapter          # noqa: E402
from reference import solar_open2_ref as ref       # noqa: E402

SLOTS, PAGE_LEN, BUCKETS = 4, 8, [16, 32, 48]


def toy_config(**over):
    cfg = {"hidden_size": 64, "vocab_size": 64, "num_hidden_layers": 4,
           "layer_offset": 0, "gqa_layers": [0, 4, 8], "use_gqa_gate": True,
           "use_rope": False, "kda_use_full_proj": False,
           "kda_allow_neg_eigval": True, "first_k_dense_replace": 0,
           "linear_attn_config": {"short_conv_kernel_size": 4,
                                  "head_dim": 16, "num_heads": 4,
                                  "num_kv_heads": None},
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "head_dim": 16, "rms_norm_eps": 1e-5, "n_routed_experts": 16,
           "n_shared_experts": 1, "num_experts_per_tok": 4,
           "moe_intermediate_size": 48, "routed_scaling_factor": 1,
           "norm_topk_prob": True, "experts_held": 16, "expert_offset": 0}
    cfg.update(over)
    return cfg


def _hp(cfg, dtype="float32", max_len=64):
    hp = hybrid_moe.HybridConfig.from_dict(cfg)
    hp.dtype, hp.max_len = dtype, max_len
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
    # the seeded bfloat16 VALUES, held in float32: program and reference
    # then compute the same function to float32 rounding
    return {k: v.astype(jnp.float32)
            for k, v in adapter.seeded_weights(cfg, 7).items()}


@pytest.fixture(scope="module")
def predictor(tmp_path_factory, cfg, weights):
    # rungs of 8 and 16 rows: a prompt of 45 rows is three chunks
    hp = _hp(cfg)
    hp.prefill_chunk_rows = 16
    path = str(tmp_path_factory.mktemp("solar") / "bundle")
    hybrid_moe.export_hybrid_model(path, hp, num_slots=SLOTS,
                                   prompt_buckets=BUCKETS,
                                   page_len=PAGE_LEN)
    p = GenPredictor(path)
    p.bundle_dir = path
    _install(p, weights)
    p.warmup()
    return p


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(1, 64, size=n).tolist()


_REF = {}


def _ref_logits(weights, cfg, ids, positions, **kw):
    """The reference at ``positions`` of ``ids``, through ONE compiled
    program a configuration: the model is causal, so the sequence is
    padded behind its end to 64 rows and the positions to 8."""
    key = (json.dumps(cfg, sort_keys=True), tuple(sorted(kw.items())))
    if key not in _REF:
        _REF[key] = jax.jit(lambda w, seq, at: ref.forward_logits(
            w, cfg, seq, at, **kw))
    seq = list(ids) + [0] * (64 - len(ids))
    at = list(positions) + [positions[-1]] * (8 - len(positions))
    return np.asarray(_REF[key](
        weights, jnp.asarray(seq, jnp.int32),
        jnp.asarray(at, jnp.int32)))[:len(positions)]


def _close(got, want, tol=3e-4):
    spread = float(want.max() - want.min())
    assert float(np.abs(np.asarray(got) - want).max()) <= tol * spread


def _step(predictor, live):
    """One decode step; ``live`` maps slot -> (token, rows so far)."""
    tokens, pos, lens = (np.zeros(SLOTS, np.int32) for _ in range(3))
    for slot, (tok, rows) in live.items():
        tokens[slot], pos[slot], lens[slot] = tok, rows, rows + 1
    return predictor.decode_step(tokens, pos, lens=lens)


def _admit(predictor, slot, prompt, horizon=8):
    """``prompt`` chunk by chunk into ``slot``; the last chunk's logits."""
    predictor.alloc_slot_pages(slot, predictor.pages_needed(len(prompt),
                                                            horizon))
    for a, b in predictor.chunk_spans(len(prompt)):
        logits = predictor.prefill_chunk(slot, prompt[a:b], a)
    return np.asarray(logits)[0]


# -- the ops against the recurrence, a token at a time ----------------------

def _recurrence_inputs(T, H=3, D=16, seed=0, decay=1.0, beta_shift=0.0,
                       same_keys=False):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = kda_ops.l2norm(jax.random.normal(k[0], (T, H, D))) * D ** -0.5
    key = kda_ops.l2norm(jax.random.normal(k[1], (T, H, D)))
    if same_keys:
        key = jnp.broadcast_to(key[:1], key.shape)
    v = jax.random.normal(k[2], (T, H, D))
    g = -decay * jax.nn.softplus(jax.random.normal(k[3], (T, H, D)))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(k[4], (T, H)) + beta_shift)
    return q, key, v, g, beta, jax.random.normal(k[5], (H, D, D))


@pytest.mark.parametrize("T,real,how", [
    (16, 16, {}), (40, 29, {}), (200, 137, {}), (8, 5, {}),
    (64, 64, dict(decay=30.0)),                     # decays near 0
    (130, 130, dict(decay=80.0)),
    (128, 100, dict(decay=1e-3, beta_shift=6.0)),   # near 1; beta near 2
    (96, 96, dict(decay=1e-4, beta_shift=6.0, same_keys=True))],
    ids=["one_sub_block", "ragged", "blocks_of_64", "short", "decay_near_0",
         "decay_near_0_long", "decay_near_1_beta_near_2", "repeated_keys"])
def test_kda_scan_equals_the_recurrence_a_token_at_a_time(T, real, how):
    """Ragged rows, a non-zero state coming in, beta near 2, decays near 0
    (where exp(-G) alone overflows) and near 1, keys that repeat (where a
    series in powers of the pair matrix outgrows float32)."""
    q, k, v, g, beta, S0 = _recurrence_inputs(T, **how)
    mask = (jnp.arange(T) < real).astype(jnp.float32)
    o, S = jax.jit(kda_ops.kda_scan)(q, k, v, g, beta, S0, mask)
    assert bool(jnp.isfinite(o).all())
    state, want = jax.jit(lambda *rows: jax.lax.scan(
        lambda S, row: kda_ops.kda_step(S, *row)[::-1], S0, rows))(
        *(a[:real] for a in (q, k, v, g, beta)))
    want = np.asarray(want)
    assert np.abs(np.asarray(o[:real]) - want).max() \
        <= 3e-5 * np.abs(want).max()
    # pad rows are the identity: the state is the one after the last real
    assert np.abs(np.asarray(S - state)).max() \
        <= 3e-5 * np.abs(np.asarray(state)).max()


def _raw_inputs(T, H, D, seed=0, decay=1.0, beta_shift=0.0, same_keys=False):
    """What ``kda_scan_lower`` is handed, so that ``prepare`` gives what
    ``_recurrence_inputs`` gives: X [T, 3 * H * D], F [T, H * D], B [T,
    H], ALog [H], DtBias [H * D] and a three-slot state."""
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    q, key, v = (jax.random.normal(k[j], (T, H, D)) for j in range(3))
    if same_keys:
        key = jnp.broadcast_to(key[:1], key.shape)
    x = jnp.concatenate([a.reshape(T, -1) for a in (q, key, v)], axis=1)
    return (x, jax.random.normal(k[3], (T, H * D)),
            jax.random.normal(k[4], (T, H)) + beta_shift,
            jnp.full((H,), np.log(decay), jnp.float32),
            0.1 * jax.random.normal(k[6], (H * D,)),
            jax.random.normal(k[5], (3, H, D, D)))


def _token_at_a_time(raw, H, S0, real):
    x, f, b, a_log, dt_bias, _ = raw
    rows = kda_ops.prepare(x, f, b, a_log, dt_bias, H, 2.0)
    state, want = jax.jit(lambda *rows: jax.lax.scan(
        lambda S, row: kda_ops.kda_step(S, *row)[::-1], S0, rows))(
        *(a[:real] for a in rows))
    return np.asarray(want).reshape(real, -1), np.asarray(state)


def _kernel_is_the_recurrence(T, real, how, H=2, first=0):
    raw = _raw_inputs(T, H, 128, **how)
    x, state = raw[0], raw[-1]
    assert kda_ops.scan_kernel_ok(x, state)
    mask = (jnp.arange(T) < real).astype(jnp.float32)
    o, new = kda_ops.kda_scan_kernel(
        *raw, jnp.int32(1), jnp.int32(first), mask, beta_scale=2.0,
        interpret=True)
    assert bool(jnp.isfinite(o).all())
    want, last = _token_at_a_time(raw, H, state[1] * (1 - first), real)
    assert np.abs(np.asarray(o[:real]) - want).max() \
        <= 3e-5 * np.abs(want).max()
    assert np.abs(np.asarray(new[1]) - last).max() \
        <= 3e-5 * np.abs(last).max()
    # only the chunk's slot is written
    assert np.array_equal(new[0], state[0]) \
        and np.array_equal(new[2], state[2])


@pytest.mark.parametrize("T,real,how", [
    (128, 128, {}), (128, 93, {}), (256, 137, {}), (128, 5, {}),
    (128, 128, dict(decay=30.0)),                   # decays near 0
    (256, 256, dict(decay=80.0)),
    (128, 100, dict(decay=1e-3, beta_shift=6.0)),   # near 1; beta near 2
    (128, 96, dict(decay=1e-4, beta_shift=6.0, same_keys=True))],
    ids=["whole_blocks", "ragged", "four_blocks", "short", "decay_near_0",
         "decay_near_0_long", "decay_near_1_beta_near_2", "repeated_keys"])
def test_the_scan_kernel_equals_the_recurrence_a_token_at_a_time(T, real,
                                                                 how):
    """The Pallas kernel (interpret mode here; compiled for the chip in
    ``tests/test_tpu_compile.py``) at the widths its gate takes, heads
    of 128, rungs of whole blocks with pad rows, a non-zero state coming
    in, on the raw activations: the same eight kinds of input as the XLA
    form above, at the same 3e-5."""
    _kernel_is_the_recurrence(T, real, how)


def test_the_scan_kernel_carries_the_state_from_stretch_to_stretch(
        monkeypatch):
    """More rows than a grid step holds (``_SCAN_ROWS``, 64 here): the
    state rides in scratch across the grid's second axis, and more head
    groups than one (of two heads here); a prompt's first chunk starts
    from zeros whatever the slot held."""
    monkeypatch.setattr(kda_ops, "_SCAN_ROWS", 64)
    monkeypatch.setattr(kda_ops, "_SCAN_HEADS", 2)
    jax.clear_caches()
    try:
        _kernel_is_the_recurrence(192, 150, dict(seed=3), H=4)
        _kernel_is_the_recurrence(192, 192, dict(seed=4), H=4, first=1)
    finally:
        jax.clear_caches()


def _scan_op(T, H, D, slots=3):
    """A program of ONE ``kda_scan`` op over feeds and the persistable
    state ``st``: ``(main, out)``."""
    from paddle_tpu.models.decoder import data, op, persistable
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        state = persistable("st", [slots, H, D, D], "float32")
        out = op("kda_scan",
                 {"X": data("x", [1, T, 3 * H * D]),
                  "F": data("f", [1, T, H * D]), "B": data("b", [1, T, H]),
                  "ALog": data("a_log", [H]), "DtBias": data("dt", [H * D]),
                  "State": state, "Slot": data("slot", [1, 1], "int32"),
                  "Pos": data("pos", [1, T], "int32"),
                  "Mask": data("mask", [1, T])},
                 {"Out": "float32", "StateOut": state},
                 {"n_head": H, "beta_scale": 2.0})["Out"]
    return main, out


def _run_scan_op(T, real, H, D, start):
    raw = _raw_inputs(T, H, D, seed=5)
    x, f, b, a_log, dt_bias, state = raw
    state = np.asarray(state)           # the executor donates the scope's
    main, out = _scan_op(T, H, D)
    scope = fluid.Scope()
    scope.set_var("st", jnp.array(state))
    exe = fluid.Executor(fluid.CPUPlace())
    o, = exe.run(main, scope=scope, fetch_list=[out], feed={
        "x": np.asarray(x)[None], "f": np.asarray(f)[None],
        "b": np.asarray(b)[None], "a_log": np.asarray(a_log),
        "dt": np.asarray(dt_bias),
        "slot": np.asarray([[1]], np.int32),
        "pos": (start + np.arange(T, dtype=np.int32))[None],
        "mask": (np.arange(T) < real).astype(np.float32)[None]})
    want, last = _token_at_a_time(
        raw, H, jnp.asarray(state[1] * (1.0 if start else 0.0)), real)
    assert np.abs(np.asarray(o)[0, :real] - want).max() \
        <= 3e-5 * np.abs(want).max()
    new = np.asarray(scope.find_var("st"))
    assert np.abs(new[1] - last).max() <= 3e-5 * np.abs(last).max()
    assert np.array_equal(new[0], state[0]) \
        and np.array_equal(new[2], state[2])


def _scan_lowerings():
    return {form: profiler.runtime_metrics.counter(
        f"gen.kda.scan_lowerings.{form}") for form in ("kernel", "xla")}


def test_the_gate_refuses_toy_widths_and_a_chunk_under_one_block():
    """``scan_kernel_ok`` takes heads of 128 and rungs of whole blocks;
    where it refuses, the op lowers as the XLA form, says so, and is the
    recurrence all the same."""
    ok = lambda T, H, D: kda_ops.scan_kernel_ok(
        jnp.zeros((T, 3 * H * D)), jnp.zeros((3, H, D, D)))
    assert ok(64, 4, 128) and ok(512, 64, 128) and ok(256, 3, 256)
    assert not ok(64, 4, 16)            # the toy widths
    assert not ok(32, 4, 128)           # a chunk under one block
    assert not ok(96, 4, 128)           # not whole blocks
    assert not kda_ops.scan_kernel_ok(jnp.zeros((64, 3 * 4 * 128)),
                                      jnp.zeros((3, 4, 128, 64)))
    for T, real, H, D, start in ((64, 50, 4, 16, 0), (32, 32, 2, 128, 7)):
        before = _scan_lowerings()
        _run_scan_op(T, real, H, D, start)
        after = _scan_lowerings()
        assert after["xla"] == before["xla"] + 1
        assert after["kernel"] == before["kernel"]


def test_the_lowering_counts_which_form_it_took(predictor):
    """``gen.kda.scan_lowerings.kernel`` / ``.xla``, once a compiled
    signature: the op at kernel widths (a chunk that continues its slot's
    state, through the executor, in place) counts the first, the toy
    bundle's chunk executables the second."""
    assert _scan_lowerings()["xla"] >= 1        # the fixture's warm-up
    before = _scan_lowerings()
    _run_scan_op(128, 100, 2, 128, 64)
    after = _scan_lowerings()
    assert after["kernel"] == before["kernel"] + 1
    assert after["xla"] == before["xla"]


def test_kda_update_leaves_a_slot_that_is_not_live_untouched():
    q, k, v, g, beta, _ = _recurrence_inputs(3, H=4)
    state = jax.random.normal(jax.random.PRNGKey(9), (3, 4, 16, 16))
    _, new = kda_ops.kda_step(state, q, k, v, g, beta)
    kept = jnp.where(jnp.asarray([True, False, True])[:, None, None, None],
                     new, state)
    assert np.array_equal(kept[1], state[1])
    assert not np.array_equal(kept[0], state[0])


def test_the_update_kernel_is_the_recurrence_in_one_pass():
    """The Pallas kernel (interpret mode here; compiled for the chip in
    ``tests/test_tpu_compile.py``) at the widths its gate takes: 32 heads
    a grid step, a head's [128, 128] state; a free slot keeps its state
    and reads zeros."""
    S, H, D = 3, 32, 128
    q, k, v, g, beta, _ = _recurrence_inputs(S, H=H, D=D, seed=4)
    state = jax.random.normal(jax.random.PRNGKey(9), (S, H, D, D))
    lens = jnp.asarray([5, 0, 9], jnp.int32)
    assert kda_ops.update_kernel_ok(state, True)
    assert not kda_ops.update_kernel_ok(state[:, :4], True)
    assert not kda_ops.update_kernel_ok(state[..., :16], False)
    o, new = kda_ops.kda_update_kernel(state, q, k, v, g, beta, lens,
                                       interpret=True)
    want_o, want = kda_ops.kda_step(state, q, k, v, g, beta)
    for slot in (0, 2):
        np.testing.assert_allclose(o[slot], want_o[slot], rtol=1e-5,
                                   atol=1e-6)
        np.testing.assert_allclose(new[slot], want[slot], rtol=1e-5,
                                   atol=1e-6)
    assert np.array_equal(new[1], state[1]) and not np.asarray(o[1]).any()


def test_the_chunk_conv_continues_the_window_it_was_handed():
    k = jax.random.split(jax.random.PRNGKey(3), 2)
    x, w = jax.random.normal(k[0], (20, 10)), jax.random.normal(k[1], (4, 10))
    full, _ = ssm_ops.conv_scan(x, w, None, jnp.int32(20))
    _, window = ssm_ops.conv_scan(x[:12], w, None, jnp.int32(9))
    assert np.array_equal(window, x[6:9])
    rest, after = ssm_ops.conv_scan(x[9:], w, None, jnp.int32(11),
                                    before=window)
    np.testing.assert_allclose(rest, full[9:], rtol=1e-5, atol=1e-6)
    assert np.array_equal(after, x[17:20])
    # no real row: the window stands
    _, stood = ssm_ops.conv_scan(x[9:], w, None, jnp.int32(0), before=window)
    assert np.array_equal(stood, window)


# -- the bundle against the reference ---------------------------------------

def test_gen_meta_names_both_kinds_of_cache_and_the_chunks(predictor, cfg):
    with open(os.path.join(predictor.bundle_dir, "gen_meta.json")) as f:
        meta = json.load(f)
    assert hybrid_moe.HybridConfig.from_dict(cfg).pattern == "GSKSKSKS"
    assert meta["cache_vars"] == ["hyb0_paged_k", "hyb0_paged_v"]
    assert meta["state_vars"] == [
        f"hyb{i}_{r}" for i in (2, 4, 6)
        for r in ("conv_state", "kda_state")]
    assert meta["prefill_chunks"] == [8, 16] and meta["n_layer"] == 8
    assert len(meta["decode_stats"]) == 3
    block = predictor._dec_prog.global_block()
    assert tuple(block.var("hyb2_kda_state").shape) == (SLOTS, 4, 16, 16)
    assert tuple(block.var("hyb2_conv_state").shape) == (SLOTS, 3, 192)
    assert str(block.var("hyb2_kda_state").dtype) == "float32"
    # a slot's state: three mixers of 4 x 16 x 16 and 3 x 192, float32
    assert predictor.state_bytes_per_slot == 3 * (4 * 16 * 16 + 3 * 192) * 4


@pytest.mark.parametrize("n", [5, 16, 17, 45], ids=[
    "one_chunk", "a_chunk_edge", "a_row_past_it", "three_chunks"])
def test_chunks_then_cached_steps_match_the_references_forward(
        predictor, weights, cfg, n):
    prompt = _prompt(n, seed=100 + n)
    assert len(predictor.chunk_spans(n)) == -(-n // 16)
    try:
        logits = _admit(predictor, 1, prompt)
        seq = list(prompt)
        want = _ref_logits(weights, cfg, seq, [n - 1])
        _close(logits, want[0])
        for _ in range(3):
            tok = int(np.argmax(logits))
            logits = _step(predictor, {1: (tok, len(seq))})[1]
            seq.append(tok)
            _close(logits, _ref_logits(weights, cfg, seq, [len(seq) - 1])[0])
    finally:
        predictor.free_slot_pages(1)


def test_prefill_and_write_slot_keep_their_contract(predictor, weights, cfg):
    """The borrowing ``prefill`` hands the slot's rows AND its state out,
    ``write_slot`` seats them in another slot: the set-up check's path."""
    prompt = _prompt(21, seed=5)
    logits, kv = predictor.prefill(prompt)
    assert len(kv) == 2 + 6
    _close(logits, _ref_logits(weights, cfg, prompt, [20])[0])
    predictor.alloc_slot_pages(2, predictor.pages_needed(21, 2))
    try:
        assert predictor.write_slot(2, kv, 21) == 0
        tok = int(np.argmax(logits))
        step = _step(predictor, {2: (tok, 21)})[2]
        _close(step, _ref_logits(weights, cfg, prompt + [tok], [21])[0])
    finally:
        predictor.free_slot_pages(2)
    assert predictor.free_pages == predictor.num_pages


def test_two_slots_of_different_lengths_keep_their_own_state(
        predictor, weights, cfg):
    """Neighbouring slots, 37 and 9 rows, chunks interleaved: a state or a
    window read from the wrong slot fails."""
    a, b = _prompt(37, seed=1), _prompt(9, seed=2)
    try:
        for slot, p in ((1, a), (2, b)):
            predictor.alloc_slot_pages(slot, predictor.pages_needed(len(p),
                                                                    4))
        predictor.prefill_chunk(1, a[:16], 0)
        lb = np.asarray(predictor.prefill_chunk(2, b, 0))[0]
        predictor.prefill_chunk(1, a[16:32], 16)
        la = np.asarray(predictor.prefill_chunk(1, a[32:], 32))[0]
        _close(la, _ref_logits(weights, cfg, a, [36])[0])
        _close(lb, _ref_logits(weights, cfg, b, [8])[0])
        ta, tb = int(np.argmax(la)), int(np.argmax(lb))
        out = _step(predictor, {1: (ta, 37), 2: (tb, 9)})
        _close(out[1], _ref_logits(weights, cfg, a + [ta], [37])[0])
        _close(out[2], _ref_logits(weights, cfg, b + [tb], [9])[0])
    finally:
        predictor.free_slot_pages(1)
        predictor.free_slot_pages(2)


def test_a_readmitted_slot_starts_from_zeros_whatever_it_held(
        predictor, weights, cfg):
    """The first chunk does not read the state and window the slot's last
    stream left there; a warm-up's chunk of pad rows leaves them alone."""
    long, short = _prompt(40, seed=11), _prompt(6, seed=12)
    for prompt in (long, short):
        try:
            logits = _admit(predictor, 3, prompt)
        finally:
            predictor.free_slot_pages(3)
    _close(logits, _ref_logits(weights, cfg, short, [5])[0])
    held = np.asarray(predictor._scope.find_var("hyb2_kda_state"))[3]
    assert np.abs(held).max() > 0
    predictor.warmup()          # zero feeds: slot 0, position 0, no real row
    now = np.asarray(predictor._scope.find_var("hyb2_kda_state"))
    assert np.array_equal(now[3], held)


def test_without_the_decay_the_reference_is_not_the_program(weights, cfg):
    """The control the cell's limits have to fail: alpha = 1."""
    prompt = _prompt(40, seed=3)
    want = _ref_logits(weights, cfg, prompt, [39])
    off = _ref_logits(weights, cfg, prompt, [39], decay=False)
    assert np.abs(off - want).max() > 0.02 * np.ptp(want)


def test_streams_admitted_in_chunks_carry_their_state_through_the_scheduler(
        predictor, weights, cfg):
    """Three streams of 45, 17 and 5 rows admitted beside each other emit
    the reference's greedy tokens; the admissions' chunks and the bytes
    of state their slots took and gave back are counted."""
    names = ["gen.prefill.chunks", "gen.prefill.admissions_chunked",
             "gen.seed.compiled_calls", "gen.state.bytes_seeded",
             "gen.state.bytes_freed"]
    before = [profiler.runtime_metrics.counter(n) for n in names]
    prompts = [_prompt(n, seed=200 + n) for n in (45, 17, 5)]
    sched = GenScheduler(predictor)
    try:
        served = [list(s) for s in
                  [sched.submit(p, max_new_tokens=5) for p in prompts]]
    finally:
        sched.close()
    for prompt, tokens in zip(prompts, served):
        ids = prompt + tokens
        want = _ref_logits(weights, cfg, ids,
                           list(range(len(prompt) - 1, len(ids) - 1)))
        assert tokens == [int(t) for t in np.argmax(want, axis=-1)]
    after = [profiler.runtime_metrics.counter(n) for n in names]
    per_slot = predictor.state_bytes_per_slot
    assert [b - a for a, b in zip(before, after)] \
        == [6, 3, 0, 3 * per_slot, 3 * per_slot]
    assert predictor.free_pages == predictor.num_pages


# -- the contract, the rules, the shares -------------------------------------

def test_the_bundle_checks_and_every_new_op_has_its_rules(predictor):
    from paddle_tpu.analysis import (check_gen_bundle, cost, lint_program,
                                     typecheck)
    from paddle_tpu.analysis.distributed import load_saved_program
    new = {"kda_scan", "kda_update", "kda_gated_norm", "ssm_chunk_conv",
           "attention_out_gate"}
    assert new <= set(typecheck._RULES) and new <= cost.covered_op_types()
    pre = load_saved_program(os.path.join(predictor.bundle_dir, "prefill"))
    dec = load_saved_program(os.path.join(predictor.bundle_dir, "decode"))
    with open(os.path.join(predictor.bundle_dir, "gen_meta.json")) as f:
        meta = json.load(f)
    assert check_gen_bundle(pre, dec, meta) == []
    seen = set()
    for prog, feeds, fetches in (pre, dec):
        result = lint_program(prog, feed_names=feeds, fetch_names=fetches)
        assert not [d for d in result.diagnostics
                    if d.severity == "error"], result.diagnostics
        seen |= {op.type for op in prog.global_block().ops}
    assert new <= seen
    # a decode step reads and writes every slot's matrix state once
    report = cost.estimate(dec[0])
    assert report.by_op_type()["kda_update"]["bytes"] \
        >= 3 * 2 * SLOTS * 4 * 16 * 16 * 4
    # a chunk program that drops a mixer's state is refused
    meta["state_vars"] = meta["state_vars"][:-1]
    assert any("hyb6_kda_state" in d.message
               for d in check_gen_bundle(pre, dec, meta))


def test_a_wrong_state_shape_or_type_is_reported(cfg):
    from paddle_tpu.analysis import lint_program
    from paddle_tpu.models.decoder import data, op, persistable, vector
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        out = op("kda_update",
                 {"X": data("x", [2, 1, 3 * 64]), "F": data("f", [2, 1, 64]),
                  "B": data("b", [2, 1, 4]), "ALog": vector("a", 4, 0.0),
                  "DtBias": vector("dt", 64, 0.0),
                  "State": persistable("st", [2, 4, 16, 8], "bfloat16"),
                  "Lens": data("lens", [2, 1], "int32")},
                 {"Out": "float32", "StateOut": "float32"},
                 {"n_head": 4, "beta_scale": 2.0})["Out"]
        result = lint_program(fluid.default_main_program(),
                              feed_names=["x", "f", "b", "lens"],
                              fetch_names=[out.name])
    said = " ".join(d.message for d in result.diagnostics)
    assert "expected [slots, 4, 16, 16]" in said
    assert "keeps its state in float32" in said


def test_a_pattern_with_a_mixer_of_each_kind_has_no_chunk_form():
    hp = hybrid_moe.HybridConfig()
    hp.pattern = "KM*"
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        with pytest.raises(NotImplementedError, match="no chunk form"):
            hybrid_moe.build_chunk_program(hp, 2, 8, 4)
        with pytest.raises(NotImplementedError, match="no backward"):
            hybrid_moe.hybrid_moe_train_program(8, hp)
    with pytest.raises(NotImplementedError, match="use_rope"):
        hybrid_moe.HybridConfig.from_dict(toy_config(use_rope=True))


def test_a_pattern_without_k_trains_through_the_gate_and_the_shared_moe():
    """``G`` and ``S`` beside the older kinds in the whole-prompt and the
    training program: one SGD step on the toy moves the loss."""
    hp = hybrid_moe.HybridConfig()
    hp.pattern, hp.dtype, hp.routed_scaling_factor = "GSM*E", "float32", 1.0
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        loss, feeds = hybrid_moe.hybrid_moe_train_program(12, hp)
        fluid.optimizer.SGD(learning_rate=0.5).minimize(loss)
    assert {"attention_out_gate", "moe_experts_gated", "swiglu",
            "attention_out_gate_grad"} \
        <= {op.type for op in main.global_block().ops}
    ids = np.random.RandomState(0).randint(1, 64, size=(1, 12)) \
        .astype("int32")
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor()
        exe.run(startup)
        feed = {"gen_ids": ids, "gen_labels": np.roll(ids, -1, axis=1)}
        losses = [float(exe.run(main, feed=feed, fetch_list=[loss])[0])
                  for _ in range(3)]
    assert np.isfinite(losses).all() and losses[2] < losses[0]
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        feeds, fetches = hybrid_moe.build_prefill_program(hp)
        # K/V of the two attention layers, window and state of the mixer
        assert len(fetches) == 1 + 2 * 2 + 2


def test_the_page_pool_takes_the_configurations_type(cfg):
    for kind, want in ((None, "float32"), ("bfloat16", "bfloat16")):
        hp = _hp(dict(cfg, **({"pool_dtype": kind} if kind else {})),
                 dtype="bfloat16")
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            hybrid_moe.build_paged_decode_program(hp, 2, 8, 4)
            block = fluid.default_main_program().global_block()
            assert str(block.var("hyb0_paged_k").dtype) == want
            assert str(block.var("hyb2_kda_state").dtype) == "float32"
            assert str(block.var("hyb2_conv_state").dtype) == "float32"


def test_the_shares_of_one_layer_add_up_to_the_uncut_layer(weights):
    """8 shares of 2 experts each, their routed parts, plus the shared
    expert counted ONCE, are the layer with all 16 experts (reference and
    op alike)."""
    whole = toy_config()
    h = jax.random.normal(jax.random.PRNGKey(1), (13, 64))
    p = lambda name, cast=True: weights[f"hyb1_{name}"]
    uncut = np.asarray(ref.moe(h, p, whole, jnp.float32))
    shared = np.asarray(ref._gated(h, p("sh_gate.w"), p("sh_up.w"),
                                   p("sh_down.w")))
    idx, wgt = moe_ops.moe_route(h, p("gate.w"), p("gate.bias"), 4, 1.0,
                                 True)
    total_ref, total_op, landed = 0.0, 0.0, 0
    for share in range(8):
        sl = slice(2 * share, 2 * share + 2)
        part = dict(whole, experts_held=2, expert_offset=2 * share)
        cut = lambda name, cast=True, sl=sl: (
            weights[f"hyb1_{name}"][sl] if name in ("wg", "wu", "wd")
            else weights[f"hyb1_{name}"])
        total_ref = total_ref + np.asarray(
            ref.moe(h, cut, part, jnp.float32, shared=False))
        out, stats = moe_ops.moe_experts_gated(
            h, idx, wgt, cut("wg"), cut("wu"), cut("wd"),
            expert_offset=2 * share)
        total_op = total_op + np.asarray(out)
        landed += int(stats[0])
    np.testing.assert_allclose(total_ref + shared, uncut, rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_allclose(total_op + shared, uncut, rtol=2e-4,
                               atol=2e-5)
    assert landed == 13 * 4         # every assignment landed on one share
