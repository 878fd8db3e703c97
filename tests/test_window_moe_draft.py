"""Self-speculative decoding by a model's own multi-token-prediction
module on the paged serving path (``ops/spec_ops.py``,
``decoder.mtp_module``, ``models/window_moe.py`` in its ``exaone_moe``
layout, ``gen/predictor.py``, ``gen/scheduler.py``): the exported bundle
against the plain reference (``benchmark/reference/k_exaone_ref.py``: the
main model's forward and, separately, the MTP module's teacher-forced
draft logits) for prefill, chunks and cached turns; the DRAFTED stream
token for token the undrafted greedy stream whatever the drafter is
worth; each mechanism caught when it is dropped; the share arithmetic;
the contract.  Toy widths: d 64, 4 query / 2 K/V heads of 16, window 8 in
a ring of 16, layers 0-4 of the published pattern ('LLLG' + L, dense
first) and the MTP block, 16 experts top-2 + a shared one, chunks of 16
rows, pages of 8."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import profiler
from paddle_tpu.analysis import check_gen_bundle
from paddle_tpu.gen import GenPredictor, GenScheduler
from paddle_tpu.models import decoder, window_moe
from paddle_tpu.ops import spec_ops, window_ops

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
for p in (BENCH, os.path.join(BENCH, "tools")):
    if p not in sys.path:
        sys.path.insert(0, p)
from models import k_exaone as adapter                  # noqa: E402
from reference import k_exaone_ref as ref               # noqa: E402

SLOTS, PAGE_LEN, BUCKETS, WINDOW, RING, V = 3, 8, [8, 16, 32, 48], 8, 16, 64
TOL = 2e-4          # float32 program against the float32 reference


def toy_config(**over):
    cfg = {"hidden_size": 64, "num_hidden_layers": 5, "layer_offset": 0,
           "vocab_size": V, "rms_norm_eps": 1e-5, "num_attention_heads": 4,
           "num_key_value_heads": 2, "head_dim": 16,
           "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
           "sliding_window": WINDOW, "ring": RING,
           "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 2,
           "mlp_layer_types": ["dense"] + ["sparse"] * 7,
           "mtp_layer_types": ["full_attention"],
           "num_nextn_predict_layers": 1, "intermediate_size": 96,
           "moe_intermediate_size": 32, "num_experts": 16,
           "num_experts_per_tok": 2, "num_shared_experts": 1,
           "routed_scaling_factor": 2.5, "norm_topk_prob": True,
           "qk_norm": True, "full_attention_rotary": False,
           "experts_held": 16, "expert_offset": 0}
    cfg.update(over)
    return cfg


def _weights(cfg, seed=7, drafter="seeded"):
    """The adapter's seeded VALUES held in float32; the router's offset
    row is taken out (it is made for the published widths) and its bias
    is wide enough to change which experts a row takes.  ``drafter``:
    ``seeded`` (the adapter's construction, its head leaning harder at
    toy widths), ``oracle`` (a spike no layer overturns, layers that write
    little: the main model follows the seeded successor and the module
    drafts it) or ``random``
    (a Xavier projection: the module knows nothing)."""
    names = ("FOLLOW", "SKIP", "HEAD_ALIGN", "MTP_PASS")
    was = [getattr(adapter, n) for n in names]
    for n, value in zip(names, (40.0, 0.0, 6.0, 30.0)
                        if drafter == "oracle" else (7.0, 6.0, 6.0, 3.0)):
        setattr(adapter, n, value)
    try:
        w = {k: v.astype(jnp.float32)
             for k, v in adapter.seeded_weights(cfg, seed).items()}
    finally:
        for n, value in zip(names, was):
            setattr(adapter, n, value)
    for i in adapter.moe_layers(cfg):
        w[f"win{i}_gate.w"] = w[f"win{i}_gate.w"].at[0].set(0.0)
        w[f"win{i}_gate.bias"] = jax.random.uniform(
            jax.random.PRNGKey(len(str(i)) * 31 + seed), (16,), jnp.float32,
            -0.3, 0.3)
    if drafter == "oracle":
        # at 64 channels another token's leaning column reads a third of
        # a token's own: the layers write little, so that the residual
        # stays its token's embedding and the successor always wins
        for name in w:
            if name.endswith(("o.w", "down.w", "_wd")):
                w[name] = w[name] * 0.1
    if drafter == "random":
        d = cfg["hidden_size"]
        w["win_mtp_proj.w"] = jax.random.uniform(
            jax.random.PRNGKey(3), (2 * d, d), jnp.float32, -0.2, 0.2)
    return w


def _hp(cfg, max_len=64):
    hp = window_moe.WindowMoEConfig.from_dict(cfg)
    hp.dtype, hp.max_len = "float32", max_len
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
    return _weights(cfg)


@pytest.fixture(scope="module")
def predictor(tmp_path_factory, cfg, weights):
    path = str(tmp_path_factory.mktemp("draft") / "bundle")
    was, decoder.CHUNK_ROWS = decoder.CHUNK_ROWS, 16
    try:
        window_moe.export_window_model(path, _hp(cfg), num_slots=SLOTS,
                                       prompt_buckets=BUCKETS,
                                       page_len=PAGE_LEN)
    finally:
        decoder.CHUNK_ROWS = was
    p = GenPredictor(path)
    _install(p, weights)
    p.warmup()
    p.bundle_dir = path
    return p


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(1, V, size=n).tolist()


_JITTED = {}
PAD_TO = 80     # every sequence here is shorter: ONE compiled reference


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


def _step(predictor, slot, token, rows):
    tokens, pos, lens = (np.zeros(SLOTS, np.int32) for _ in range(3))
    tokens[slot], pos[slot], lens[slot] = token, rows, rows + 1
    return predictor.decode_step(tokens, pos, lens=lens)[slot]


def _draft_of(predictor, slot):
    return int(np.asarray(predictor._scope.find_var(
        window_moe.DRAFT_VAR))[slot, 0])


# -- the bundle ------------------------------------------------------------------

def test_the_bundle_says_that_it_drafts(predictor, cfg):
    meta = predictor.meta
    assert meta["speculative"] == {"rows": 2, "feed": "gen_spec",
                                   "draft_var": window_moe.DRAFT_VAR}
    assert predictor.spec_rows == 2 and predictor.prefill_chunks == [8, 16]
    # the MTP block pages like the full layer; the draft is a state array
    assert meta["cache_vars"] == ["win3_paged_k", "win3_paged_v",
                                  "win_mtp_paged_k", "win_mtp_paged_v"]
    assert meta["state_vars"][-1] == window_moe.DRAFT_VAR
    assert meta["window_attention"]["full_layers"] == [3, 5]
    assert meta["window_attention"]["layers"] == [0, 1, 2, 4]
    assert "gen_next_ids" in predictor._pre_feeds
    assert predictor._dec_feeds[-1] == "gen_spec"
    assert len(predictor._dec_fetch) == 3
    # the module's ops carry a name scope of their own, between the
    # program's role and their sublayer's group
    scoped = [op.type for op in predictor._dec_prog.global_block().ops
              if op.attrs.get("op_namescope", "").startswith(
                  "gen_decode/mtp/")]
    assert "paged_attention" in scoped and "moe_experts_gated" in scoped
    assert scoped.count("rms_norm") >= 5     # h, e, two sublayers, final


def test_a_ring_needs_the_windows_rows_and_the_drafts(tmp_path, cfg):
    with pytest.raises(ValueError, match="a draft's row"):
        window_moe.export_window_model(
            str(tmp_path / "b"), _hp(toy_config(ring=WINDOW)),
            num_slots=SLOTS, prompt_buckets=BUCKETS, page_len=PAGE_LEN)


def test_an_mtp_block_of_another_kind_is_refused():
    with pytest.raises(NotImplementedError, match="full-attention"):
        _hp(toy_config(mtp_layer_types=["sliding_attention"]))


def test_the_contract_holds_the_yield_and_the_draft(predictor):
    pair = lambda prog, feeds, fetch: (prog, feeds, fetch)
    args = (pair(predictor._pre_prog, predictor._pre_feeds,
                 predictor._pre_fetch),
            pair(predictor._dec_prog, predictor._dec_feeds,
                 predictor._dec_fetch))
    assert check_gen_bundle(*args, predictor.meta) == []
    meta = dict(predictor.meta, state_vars=predictor.meta["state_vars"][:-1])
    assert any("draft_var" in d.message
               for d in check_gen_bundle(*args, meta))
    meta = dict(predictor.meta)
    del meta["speculative"]
    assert any("fetches 3" in d.message
               for d in check_gen_bundle(*args, meta))


# -- program against reference --------------------------------------------------

@pytest.mark.parametrize("n", [5, 13, 21, 37],
                         ids=["one-page", "in-the-ring", "two-chunks",
                              "three-chunks-wrapped"])
def test_prefill_cached_turns_and_drafts_are_the_references(
        predictor, cfg, weights, n):
    """A prompt of one to three chunks, then eight BLOCKING turns (the
    draft row off: the committed token's logits) across the ring's wrap
    and page boundaries; behind every one of them the draft the MTP
    module left in the slot's state is the reference's teacher-forced
    pick."""
    prompt = _prompt(n, seed=n)
    logits, kv = predictor.prefill(prompt)
    assert _err(logits, _main(weights, cfg, prompt, [n - 1])[0]) < TOL
    predictor.alloc_slot_pages(1, predictor.pages_needed(n, 10))
    try:
        predictor.write_slot(1, kv, n)
        seq, tok = list(prompt), int(np.argmax(logits))
        # the draft the prefill's last chunk seeded
        assert _draft_of(predictor, 1) == int(np.argmax(
            _draft(weights, cfg, seq + [tok], [n - 1])))
        for _ in range(8):
            got = _step(predictor, 1, tok, len(seq))
            seq.append(tok)
            assert _err(got, _main(weights, cfg, seq,
                                   [len(seq) - 1])[0]) < TOL
            tok = int(np.argmax(got))
            assert _draft_of(predictor, 1) == int(np.argmax(
                _draft(weights, cfg, seq + [tok], [len(seq) - 1])))
    finally:
        predictor.free_slot_pages(1)


def test_the_draft_logits_are_the_references(predictor, cfg, weights):
    """``benchmark/tools/draft_readings.py``'s program side at toy
    widths: the decode program's draft logits, fetched, behind a prefill
    and one cached turn."""
    import draft_readings
    read = draft_readings.program_readings(
        adapter, cfg, {"reference_prompts": [13, 30, 45]}, weights, 7,
        predictor)
    assert read["draft_err_of_range"] < TOL
    assert read["seeded_draft_is_references"]
    seen = draft_readings.reference_readings(adapter, cfg, weights,
                                             _prompt(48), 7)
    assert len(seen["residual_rms_behind_layer"]) == 5
    assert 0 <= seen["mtp_pick_is_mains_next_pick_share"] <= 1


@pytest.mark.parametrize("control", ["qk_norm", "shared", "scaling",
                                     "rotary_by_kind", "window"])
def test_a_dropped_mechanism_fails(predictor, cfg, weights, control):
    """QK-norm, the shared expert, the 2.5 scaling, the rotary by layer
    kind or the window dropped from the REFERENCE reads far above the
    tolerance against the program, prefill and a cached turn."""
    prompt = _prompt(29, seed=3)
    logits, kv = predictor.prefill(prompt)
    off = {control: False}
    assert _err(logits, _main(weights, cfg, prompt, [28], **off)[0]) \
        > 50 * TOL
    predictor.alloc_slot_pages(0, predictor.pages_needed(29, 2))
    try:
        predictor.write_slot(0, kv, 29)
        tok = int(np.argmax(logits))
        got = _step(predictor, 0, tok, 29)
    finally:
        predictor.free_slot_pages(0)
    assert _err(got, _main(weights, cfg, prompt + [tok], [29])[0]) < TOL
    assert _err(got, _main(weights, cfg, prompt + [tok], [29],
                           **off)[0]) > 50 * TOL


def test_the_shares_add_up_to_the_uncut_layer(cfg, weights):
    """Guide section 4: over all the shares of the experts the routed
    parts, with the shared expert counted ONCE, add up to the uncut
    reference's layer."""
    h = jax.random.normal(jax.random.PRNGKey(0), (24, 64), jnp.float32)
    value = ref._values(weights, jnp.float32, None)
    p = lambda name, cast=True: value(f"win2_{name}", cast)
    whole = ref.moe(h, p, cfg, jnp.float32)
    held = 4

    def share(k):
        pk = lambda name, cast=True: (
            value(f"win2_{name}", cast)[k * held:(k + 1) * held]
            if name in ("wg", "wu", "wd") else value(f"win2_{name}", cast))
        return ref.moe(h, pk, cfg, jnp.float32, shared=False, held=held,
                       first=k * held)

    only_shared = ref.moe(h, p, cfg, jnp.float32) \
        - ref.moe(h, p, cfg, jnp.float32, shared=False)
    total = sum(share(k) for k in range(16 // held)) + only_shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(only_shared).max()) > 0.1


# -- the drafted stream is the greedy stream ------------------------------------

REQUESTS = [(5, 9), (13, 20), (21, 1), (37, 18), (9, 2), (30, 25), (17, 3),
            (40, 24)]


@pytest.mark.parametrize("drafter, low, high", [
    ("random", 0.0, 0.25), ("oracle", 0.8, 1.0), ("seeded", 0.1, 0.9)])
def test_the_drafted_stream_is_the_greedy_stream(predictor, cfg, drafter,
                                                 low, high):
    """Eight requests through the scheduler over three slots: admissions
    by chunks between turns, advances of one and two mixed in one pool,
    the ring's wrap and page boundaries inside the streams, runs cut at
    ``max_new_tokens``.  Every stream is the reference's greedy stream
    whether nearly every draft is rejected, nearly every one accepted, or
    some."""
    weights = _weights(cfg, drafter=drafter)
    _install(predictor, weights)
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
        _install(predictor, _weights(cfg))
    for (prompt, cap), tokens in zip(requests, got):
        assert tokens == _greedy(weights, cfg, prompt, cap), (len(prompt),
                                                              cap)
    gained = {k: m.counter("gen.spec." + k) - v for k, v in before.items()}
    assert gained["drafted"] == gained["slot_turns"] > 0
    assert gained["emitted"] == gained["slot_turns"] + gained["accepted"]
    assert low <= gained["accepted"] / gained["drafted"] <= high, gained
    assert predictor.free_pages == predictor.num_pages


@pytest.mark.parametrize("drafter", ["oracle", "seeded"])
def test_every_turns_draft_is_the_references(predictor, cfg, drafter):
    """Twelve DRAFTING turns of one slot, driven as the scheduler drives
    them (the device's own state behind the seat): every run is the
    reference's greedy tokens, a kept draft was the first row's pick, and
    the draft each turn leaves, after a kept draft (the module's second
    row) and after a rejected one (its first), is the reference's
    teacher-forced pick: a drafter that broke would only lower the
    acceptance rate, the served tokens would stay right."""
    weights = _weights(cfg, drafter=drafter)
    _install(predictor, weights)
    prompt = _prompt(13, seed=4)
    logits, kv = predictor.prefill(prompt)
    predictor.alloc_slot_pages(1, predictor.pages_needed(13, 30))
    tokens, positions, lens = (np.zeros(SLOTS, np.int32) for _ in range(3))
    try:
        predictor.write_slot(1, kv, 13)
        seq, tok, kept = list(prompt), int(np.argmax(logits)), 0
        tokens[:] = -1
        tokens[1], positions[1], lens[1] = tok, 13, 14
        for _ in range(12):
            drafted = _draft_of(predictor, 1)
            runs, _ = predictor.read_turn(
                predictor.dispatch_turn(tokens, positions, lens))
            assert not runs[0] and not runs[2]      # free slots
            for token in runs[1]:
                seq.append(tok)
                assert token == int(np.argmax(
                    _main(weights, cfg, seq, [len(seq) - 1])))
                tok = token
            if len(runs[1]) == 2:
                kept += 1
                assert drafted == runs[1][0]
            assert _draft_of(predictor, 1) == int(np.argmax(
                _draft(weights, cfg, seq + [tok], [len(seq) - 1])))
            # the slot goes on from the device's own state
            tokens[1], positions[1], lens[1] = -1, len(seq), len(seq) + 1
    finally:
        predictor.free_slot_pages(1)
        _install(predictor, _weights(cfg))
    assert kept == 12 if drafter == "oracle" else 0 < kept < 12


def test_a_resumed_stream_and_steady_turns(predictor, cfg, weights):
    """A stream cut mid-run and resumed by deterministic re-prefill
    (prompt + what the client received) continues token for token; and
    between admissions a turn takes NOTHING from the host (PR 41's
    contract: the device advances a slot by its own yield)."""
    prompt = _prompt(11, seed=9)
    want = _greedy(weights, cfg, prompt, 30)
    m = profiler.runtime_metrics
    steady, patched = (m.counter("gen.decode.turns_" + k)
                       for k in ("steady", "patched"))
    sched = GenScheduler(predictor)
    try:
        assert list(sched.submit(prompt, max_new_tokens=30)) == want
        turns = m.counter("gen.decode.turns_steady") - steady
        assert turns >= 10
        # the seat and the ending are the host's: a turn or two each
        assert m.counter("gen.decode.turns_patched") - patched <= 3
        for k in (1, 8, 17):
            assert predictor.can_resume(len(prompt) + k)
            assert list(sched.submit(prompt + want[:k],
                                     max_new_tokens=30 - k)) == want[k:]
    finally:
        sched.close()


def test_pages_needed_covers_the_row_a_draft_overshoots(predictor):
    # prompt 10 + 6 new tokens: the last committed token sits at row 15,
    # the look-ahead turn's draft at row 16: a third page
    assert predictor.pages_needed(10, 6) == 3
    assert predictor.pages_needed(10, 5) == 2
    assert predictor.pages_needed(60, 40) == predictor.pages_per_slot


def test_a_stream_at_max_len_takes_no_draft_at_its_last_row(predictor, cfg,
                                                            weights):
    """``spec_rows`` turns the draft row off where it would lie at
    ``max_len``: nothing is written past the pool and the turn yields
    one token."""
    rows = spec_ops.spec_rows(
        *(jnp.asarray([[v]], jnp.int32) for v in (7, 9, 63, 64, 1)), 64)
    assert rows[3].reshape(-1).tolist() == [64, 0]
    rows = spec_ops.spec_rows(
        *(jnp.asarray([[v]], jnp.int32) for v in (7, 9, 62, 63, 1)), 64)
    assert rows[3].reshape(-1).tolist() == [63, 64]
    assert rows[0].tolist() == [[7, 9]] and rows[1].tolist() == [[62, 63]]
    # a stream cut by max_len ends there, token for token greedy
    prompt = _prompt(44, seed=5)
    sched = GenScheduler(predictor)
    try:
        got = list(sched.submit(prompt, max_new_tokens=40))
    finally:
        sched.close()
    assert got == _greedy(weights, cfg, prompt, 64 - 44 + 1)


# -- the ops ---------------------------------------------------------------------

def test_the_verify_keeps_a_draft_that_is_the_first_rows_pick():
    logits = jnp.zeros((8, 5)).at[jnp.arange(8),
                                  jnp.asarray([1, 2, 3, 4, 0, 1, 2, 3])].set(1.)
    ids = jnp.asarray([[9, 1], [9, 0], [9, 0], [9, 2]], jnp.int32)
    row_lens = jnp.asarray([6, 7, 6, 7, 0, 0, 4, 0], jnp.int32)[:, None]
    out, nxt, end, mtp = spec_ops.spec_verify(logits, ids, row_lens)
    # accepted; rejected; a free slot; the draft row off
    assert out.tolist() == [[1, 2, 2], [3, -1, 1], [0, -1, 0], [2, -1, 1]]
    assert nxt.tolist() == [[1, 2], [3, 4], [0, 1], [2, 3]]
    assert end.reshape(-1).tolist() == [7, 7, 0, 5]
    assert mtp.reshape(-1).tolist() == [6, 7, 6, 0, 0, 0, 4, 0]


@pytest.mark.parametrize("kernel", [None, True], ids=["composed", "kernel"])
@pytest.mark.parametrize("sink", [False, True])
def test_two_rows_through_the_ring_are_two_steps(kernel, sink):
    """A committed row and a draft's in ONE ring step read what two
    steps in turn read (the committed row does not see the draft's), a
    dead row lands nowhere, and the kernel is the composed form."""
    rng = np.random.RandomState(0)
    S, R, H, Hkv, D, L = 3, 16, 4, 2, 8, 2
    draw = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)
    kr, vr = draw(S, R, Hkv * D), draw(S, R, Hkv * D)
    q, k, v = draw(S, L, H * D), draw(S, L, Hkv * D), draw(S, L, Hkv * D)
    sk = draw(H) if sink else None
    lens = jnp.asarray([[20, 21], [5, 0], [0, 0]], jnp.int32)
    out, k2, v2 = window_ops.ring_rows_step(q, k, v, kr, vr, lens, sk, H,
                                            0.3, 8, kernel=kernel)
    a, ka, va = window_ops.ring_step(q[:, 0], k[:, 0], v[:, 0], kr, vr,
                                     lens[:, 0], sk, H, 0.3, 8)
    b, kb, vb = window_ops.ring_step(q[:, 1], k[:, 1], v[:, 1], ka, va,
                                     lens[:, 1], sk, H, 0.3, 8)
    np.testing.assert_allclose(out[:, 0], a, atol=2e-6)
    np.testing.assert_allclose(out[:, 1], b, atol=2e-6)
    np.testing.assert_array_equal(k2, kb)
    np.testing.assert_array_equal(v2, vb)
    assert float(jnp.abs(out[0]).min()) > 0 and not out[2].any()
    assert not out[1, 1].any()
