"""Window layers of latent attention beside full layers under an indexer
each, a head-wise gate and the latents' rescale, on the paged serving path
(``models/latent_moe.py`` with ``layer_types``; ``ops/mla_ops.py``'s
``latent_window_attention`` / ``latent_window_step`` / ``head_gate``): the
exported bundle (a prompt as a run of chunks over pools AND rings, cached
decode steps through both) against the plain reference's full forward
(``benchmark/reference/dots3_note_ref.py``), each mechanism's control
failing the same comparison, the kernel forms against the composed ones,
the counters, the share arithmetic, the contract and the rules.  Toy
widths: d 64; full layers 4 heads x (16 | 8), latent 32, 4 index heads x
16, ``index_topk`` 12; sliding layers 2 heads x (24 | 8), latent 48, a
window of 9 rows in a ring of 12; layers F S S F (a dense full one, two
sliding MoE ones, a full MoE one); contexts of 6-48 rows."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import latent_chunks
import paddle_tpu as fluid
from paddle_tpu.analysis import cost
from paddle_tpu.gen import GenPredictor
from paddle_tpu.models import decoder, latent_moe
from paddle_tpu.obs import trace as ptrace
from paddle_tpu.ops import mla_ops, moe_ops, window_ops

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from models import dots3_note as adapter            # noqa: E402
from reference import dots3_note_ref as ref         # noqa: E402

SLOTS, PAGE_LEN, BUCKETS, TOPK, WINDOW, RING = 4, 8, [8, 16, 32, 48], 12, 9, 12
CONTROLS = ("select", "window", "gate", "rescale", "theta")


def toy_config(**over):
    cfg = {"hidden_size": 64, "num_hidden_layers": 4,
           "first_k_dense_replace": 1, "vocab_size": 64,
           "rms_norm_eps": 1e-5, "num_attention_heads": 4,
           "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
           "qk_rope_head_dim": 8, "v_head_dim": 16,
           "rope_theta": 80000000, "rope_scaling": None,
           "layer_types": ["full_attention"]
           + ["sliding_attention"] * 2 + ["full_attention"],
           "sliding_window_size": WINDOW, "ring": RING,
           "swa_num_attention_heads": 2, "swa_q_lora_rank": 40,
           "swa_kv_lora_rank": 48, "swa_qk_nope_head_dim": 24,
           "swa_qk_rope_head_dim": 8, "swa_v_head_dim": 16,
           "swa_rope_theta": 50000,
           "attention_gate_type": "headwise",
           "swa_attention_gate_type": "headwise",
           "apply_mla_qkv_lora_rescale": True,
           "index_topk": TOPK, "index_n_heads": 4, "index_head_dim": 16,
           "intermediate_size": 96, "moe_intermediate_size": 32,
           "n_routed_experts": 16, "n_shared_experts": 1,
           "num_experts_per_tok": 2, "routed_scaling_factor": 1,
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


def _export(path, cfg, chunk=None):
    was = decoder.CHUNK_ROWS
    decoder.CHUNK_ROWS = chunk or was
    try:
        latent_moe.export_latent_model(path, _hp(cfg), num_slots=SLOTS,
                                       prompt_buckets=BUCKETS,
                                       page_len=PAGE_LEN)
    finally:
        decoder.CHUNK_ROWS = was
    return path


@pytest.fixture(scope="module")
def bundle_dir(tmp_path_factory, cfg):
    return _export(str(tmp_path_factory.mktemp("dots") / "bundle"), cfg)


@pytest.fixture(scope="module")
def predictor(bundle_dir, weights):
    """Chunk rungs of 24 and 48 rows: every prompt here is ONE chunk."""
    p = GenPredictor(bundle_dir)
    assert p.prefill_chunks == [24, 48]
    _install(p, weights)
    p.warmup()
    return p


@pytest.fixture(scope="module")
def chunked(tmp_path_factory, cfg, weights):
    """Chunk rungs of 8 and 16 rows: a prompt of 40 rows is three chunks,
    every edge inside some row's window of 9."""
    p = GenPredictor(_export(
        str(tmp_path_factory.mktemp("dots") / "chunked"), cfg, chunk=16))
    assert p.prefill_chunks == [8, 16]
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


def _in_chunks(predictor, slot, prompt, horizon=8):
    """``slot``'s pages, then the prompt's chunks one by one, as the
    scheduler admits; returns the last chunk's logits."""
    predictor.alloc_slot_pages(slot, predictor.pages_needed(len(prompt),
                                                            horizon))
    for a, b in predictor.chunk_spans(len(prompt)):
        logits = predictor.prefill_chunk(slot, prompt[a:b], a)
    return np.asarray(logits)[0]


def _decides(control, rows):
    """Does dropping ``control`` change what a row with ``rows`` rows at
    or before it computes?"""
    return {"select": rows > TOPK, "window": rows > WINDOW}.get(control,
                                                                True)


# -- the bundle against the reference ---------------------------------------------

def _served(predictor, slot, out, prompt, steps=3):
    """``steps`` cached decode steps behind a prompt whose last row gave
    ``out``: ``(the tokens, the logits of rows n - 1 .. n - 1 + steps)``,
    greedy."""
    toks, outs = list(prompt), [np.asarray(out)]
    for _ in range(steps):
        tok = int(np.argmax(outs[-1]))
        outs.append(np.asarray(
            _step(predictor, {slot: (tok, len(toks))})[slot]))
        toks.append(tok)
    return toks, outs


@pytest.mark.parametrize("n", [6, 20, 40])
def test_prefill_rings_pools_and_cached_steps_match_the_reference(
        predictor, weights, cfg, n):
    """Shorter than the window (6 rows: the ring not wrapped, the
    selection the identity), past the window with the ring wrapped (20 >
    ring 12 >= ``index_topk``), far past both (40); the prefill's last
    row, then three cached steps through pools and rings.  The program
    reads 1e-6 of the logits' range; the SAME reference with the
    selection, the band, the gate or the rescale dropped, or the two
    rotary bases swapped, reads 0.01 and more wherever the mechanism has
    something to decide."""
    prompt = _prompt(n, seed=n)
    try:
        toks, outs = _served(predictor, 1, _admit(predictor, 1, prompt),
                             prompt)
    finally:
        predictor.free_slot_pages(1)
    at = list(range(n - 1, n + 3))
    want = _ref_logits(weights, cfg, toks, at)
    for out, row in zip(outs, want):
        assert _err(out, row) < 2e-4
    for control in CONTROLS:
        off = _ref_logits(weights, cfg, toks, at, drop=(control,))
        for rows, got, row in zip(at, off, want):
            assert (_err(got, row) > 2e-3) == _decides(control, rows + 1), \
                (control, rows + 1)


@pytest.mark.parametrize("n", [6, 17, 45])
def test_a_chunk_edge_inside_the_window_changes_nothing(
        predictor, chunked, weights, cfg, n):
    """Chunk by chunk (8- and 16-row rungs: a row's window of 9 reaches
    over the edge into the ring's rows) against the single pass (one 24-
    or 48-row chunk) and against the reference: the last row's logits,
    the pools' and the rings' rows, and three cached steps over what the
    chunks left in place."""
    prompt = _prompt(n, seed=100 + n)
    assert len(chunked.chunk_spans(n)) == -(-n // 16)
    whole, parts = predictor.prefill(prompt), chunked.prefill(prompt)
    want = _ref_logits(weights, cfg, prompt, [n - 1])[0]
    assert _err(whole[0], want) < 2e-4 and _err(parts[0], want) < 2e-4
    # two latent pools and two index-key pools, then the two rings:
    # position p at row p mod ring for the last min(n, ring) positions (a
    # borrowed slot's other rows are whatever its last stream left)
    assert len(parts[1]) == len(whole[1]) == 6
    for j, (got, row) in enumerate(zip(parts[1], whole[1])):
        assert got.shape == row.shape
        got, row = (np.asarray(a)[0, :min(n, RING) if j >= 4 else None]
                    for a in (got, row))
        assert np.allclose(got, row, atol=2e-5)
        if j >= 4:
            assert got.any(axis=-1).all()
    assert chunked.free_pages == chunked.num_pages
    try:
        toks, outs = _served(chunked, 1, _in_chunks(chunked, 1, prompt),
                             prompt)
    finally:
        chunked.free_slot_pages(1)
    want = _ref_logits(weights, cfg, toks, list(range(n - 1, n + 3)))
    for out, row in zip(outs, want):
        assert _err(out, row) < 2e-4


def test_two_slots_keep_their_own_rings_and_a_reused_slot_no_stale_row(
        chunked, weights, cfg):
    """Slot 1 holds 40 rows and slot 2 admits 13 beside it, both step
    together; then slot 1 is freed and takes a prompt SHORTER than the
    ring, whose other rows are the old stream's and must not be seen."""
    long, short, again = _prompt(40, seed=1), _prompt(13, seed=2), \
        _prompt(5, seed=3)
    try:
        a = _in_chunks(chunked, 1, long)
        b = _in_chunks(chunked, 2, short)
        ta, tb = int(np.argmax(a)), int(np.argmax(b))
        out = _step(chunked, {1: (ta, 40), 2: (tb, 13)})
        chunked.free_slot_pages(1)
        lc, outs = _served(chunked, 1, _in_chunks(chunked, 1, again), again)
    finally:
        chunked.free_slot_pages(1)
        chunked.free_slot_pages(2)
    assert _err(out[1], _ref_logits(weights, cfg, long + [ta],
                                    [40])[0]) < 2e-4
    assert _err(out[2], _ref_logits(weights, cfg, short + [tb],
                                    [13])[0]) < 2e-4
    want = _ref_logits(weights, cfg, lc, list(range(4, 8)))
    for got, row in zip(outs, want):
        assert _err(got, row) < 2e-4


# -- the kernel forms (interpret mode) against the composed forms -------------------

@pytest.mark.parametrize("lens", [[0, 5, 40, 13], [12, 1, 300, 130]])
def test_the_ring_kernel_over_latent_rows_is_the_composed_step(lens):
    """ONE ring of latent rows, the values its leading lanes: slots free,
    short of the window, past it and past the ring's wrap."""
    S, H, W, L, R, window = 4, 8, 256, 128, 32, 21
    key = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(key[0], (S, H * W), jnp.float32)
    row = jax.random.normal(key[1], (S, W), jnp.float32)
    ring = jax.random.normal(key[2], (S, R, W), jnp.float32)
    lens = jnp.asarray(lens, jnp.int32)
    want, ring_c = mla_ops.latent_ring_step(q, row, ring, lens, H, L, 0.07,
                                            window)
    got, ring_k = mla_ops.latent_ring_step(q, row, ring, lens, H, L, 0.07,
                                           window, kernel=True)
    assert np.array_equal(ring_c, ring_k)
    assert got.shape == (S, H * L)
    assert np.allclose(got, want, atol=2e-5)
    # a live slot's row went to (lens - 1) mod ring, a free slot's nowhere
    for s in range(S):
        if int(lens[s]):
            assert np.array_equal(ring_c[s, (int(lens[s]) - 1) % R], row[s])
        else:
            assert np.array_equal(ring_c[s], ring[s])
            assert not np.asarray(got)[s].any()


@pytest.mark.parametrize("nope, vd", [(96, 128), (24, 16)],
                         ids=["lane_tiles", "toy_lanes"])
@pytest.mark.parametrize("start, n", [(0, 128), (7, 100), (30, 128),
                                      (300, 128)],
                         ids=["first", "pad_rows", "in_window", "past_wrap"])
def test_the_banded_kernel_over_a_ring_is_the_composed_chunk(nope, vd,
                                                             start, n):
    """A chunk of 128 rows, EXPANDED, over the ring's lead rows and its
    own: the banded flash kernel with every head its own K/V head (heads
    of whole 128-lane tiles: its blocks are read where the rows lie;
    toy lanes: head-major copies) against the composed form; the first
    chunk, one with a short lead and pad rows, one whose lead lies
    inside the window, and one past the ring's wrap."""
    C, H, L, R, window, ring_rows = 128, 4, 96, 32, 40, 48
    assert window_ops.flash_blocks(C, 1, window) == (128, 128)
    assert window_ops.lead_rows(C, 1, window) == 128
    key = jax.random.split(jax.random.PRNGKey(nope + start), 4)
    q = jax.random.normal(key[0], (C, H * (nope + R)), jnp.float32) * 0.3
    row = jax.random.normal(key[1], (C, 128), jnp.float32)
    w_kvb = jax.random.normal(key[2], (L, H * (nope + vd)), jnp.float32) \
        * 0.1
    ring = jax.random.normal(key[3], (3, ring_rows, 128), jnp.float32)
    args = (q, row, w_kvb, ring, 1, jnp.int32(start), jnp.int32(n), H, nope,
            R, vd, 0.125, window)
    blocks = window_ops.flash_blocks
    try:
        window_ops.flash_blocks = lambda *a, **k: None
        assert window_ops.lead_rows(C, 1, window) == window - 1
        want, ring_c = mla_ops.latent_window_chunk(*args)
    finally:
        window_ops.flash_blocks = blocks
    got, ring_k = mla_ops.latent_window_chunk(*args, interpret=True)
    assert np.array_equal(ring_c, ring_k)
    assert np.allclose(np.asarray(got)[:n], np.asarray(want)[:n], atol=2e-4)
    # the other slots' rings are left alone
    assert np.array_equal(ring_c[0], ring[0])
    assert np.array_equal(ring_c[2], ring[2])


def test_the_expanded_chunk_is_the_absorbed_steps_attention():
    """The chunk form expands K and V where the decode step absorbs
    W_kvb into the query and out of the context: one row's attention
    over the same ring rows is the same either way."""
    H, L, R, nope, vd, W, window, ring_rows = 2, 48, 8, 24, 16, 128, 9, 12
    key = jax.random.split(jax.random.PRNGKey(9), 4)
    rows = jax.random.normal(key[0], (30, W), jnp.float32)
    q = jax.random.normal(key[1], (1, H * (nope + R)), jnp.float32) * 0.3
    w_kvb = jax.random.normal(key[2], (L, H * (nope + vd)), jnp.float32) \
        * 0.1
    ring = window_ops.ring_of(rows[:29], 28, ring_rows)[None]
    chunk, ring_c = mla_ops.latent_window_chunk(
        q, rows[29:], w_kvb, ring, 0, jnp.int32(29), jnp.int32(1), H, nope,
        R, vd, 0.2, window)
    ctx, ring_s = mla_ops.latent_ring_step(
        mla_ops.mla_absorb(q, w_kvb, H, nope, vd, "q", pad=W - L - R),
        rows[29:], ring, jnp.asarray([30], jnp.int32), H, L, 0.2, window)
    step = mla_ops.mla_absorb(ctx, w_kvb, H, nope, vd, "o")
    assert np.array_equal(ring_c, ring_s)
    assert np.allclose(chunk, step, atol=2e-5)


def _cell_chunk_program(config="dots3_note_prev"):
    """A latent cell's chunk program (its adapter's configuration,
    BUILT, no weight allocated; the long-document cell's by default) ->
    its block and the rows of a chunk."""
    from lib import models as adapters
    from paddle_tpu.framework import unique_name_scope
    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        published = json.load(f)
    kept = {}
    export = latent_moe.export_latent_model
    try:
        latent_moe.export_latent_model = lambda path, hp, **kw: kept.update(
            hp=hp, **kw)
        adapters.adapter_of(published).export("unused", published)
    finally:
        latent_moe.export_latent_model = export
    hp, page_len = kept["hp"], kept["page_len"]
    rows = decoder.chunk_rows(page_len, kept["prompt_buckets"],
                              hp.max_len)[-1]
    main = fluid.Program()
    with unique_name_scope(""), fluid.program_guard(main, fluid.Program()):
        latent_moe.build_chunk_program(
            hp, kept["num_slots"], page_len,
            kept["num_slots"] * hp.max_len // page_len)
    return main.global_block(), rows


def _trace_op(block, op, rows, pages=1):
    """One op's lowering traced over shapes alone: a chunk of ``rows``
    rows over a page bucket of ``pages`` pages (a dynamic dim is the
    chunk's rows, but the page table's pages and a selection's key
    rows)."""
    from paddle_tpu.ops import registry
    names = list(op.input_arg_names)
    keys = sum(pages * block.var(n).shape[1] for n in op.input("Cache"))

    def shape(name):
        dims = list(block.var(name).shape)
        if name in op.input("PageTable") + op.input("Select"):
            dims[-1] = pages if name in op.input("PageTable") else keys
        return tuple(rows if d < 0 else d for d in dims)

    def lower(*arrays):
        ctx = registry.LowerContext(op, dict(zip(names, arrays)), block)
        registry.lookup(op.type).lower(ctx)
        return ctx.outputs

    return jax.eval_shape(lower, *(jax.ShapeDtypeStruct(
        shape(n), jnp.dtype(str(block.var(n).dtype))) for n in names))


def test_a_chunk_program_at_the_cells_widths_counts_six_kernels():
    """``attention.latent_window_kernel`` / ``..._composed`` count, once
    a lowering, which form a window layer's chunk took: the long-document
    cell's chunk program (its six ``latent_window_attention`` ops traced
    over shapes alone, 1024 rows a chunk) takes the banded kernel six
    times and the composed form never."""
    from paddle_tpu.profiler import runtime_metrics
    block, rows = _cell_chunk_program()
    ops = [op for op in block.ops if op.type == "latent_window_attention"]
    assert rows == 1024 and len(ops) == 6
    counted = ("attention.latent_window_kernel",
               "attention.latent_window_composed")
    before = [runtime_metrics.counter(n) for n in counted]
    for op in ops:
        out = _trace_op(block, op, rows)
        assert out[op.output("Out")[0]].shape == (1, rows, 64 * 128)
        assert out[op.output("RingOut")[0]].shape == (16, 640, 1152)
    assert [runtime_metrics.counter(n) - b
            for n, b in zip(counted, before)] == [6, 0]
    # a chunk the block rule refuses is counted as composed
    q = jnp.zeros((24, 2 * 32), jnp.float32)
    mla_ops.latent_window_chunk(
        q, jnp.zeros((24, 128)), jnp.zeros((48, 2 * 40)),
        jnp.zeros((1, 12, 128)), 0, jnp.int32(0), jnp.int32(24), 2, 24, 8,
        16, 0.2, 9)
    assert [runtime_metrics.counter(n) - b
            for n, b in zip(counted, before)] == [6, 1]


@pytest.mark.parametrize("start, n", latent_chunks.STARTS,
                         ids=latent_chunks.START_IDS)
def test_a_full_layers_chunk_under_its_selection_is_the_whole_sequences(
        start, n):
    """A FULL layer's chunk at this configuration's heads (128 | 64
    lanes of key, laid out 256 wide; values of 128) under the top-300 of
    seeded index scores, three heads: the kernel's form against
    ``mla_attention`` over the whole prompt."""
    latent_chunks.chunk_is_the_whole_sequence(128, 64, 128, start, n,
                                              top_k=300, n_head=3, seed=7)


@pytest.mark.parametrize("config, layers, width, shapes", [
    ("dots3_note_prev", 3, 128 * 128, [(1024, 32), (1024, 288)]),
    ("glm_5.2", 5, 64 * 256, [(1024, 32), (1024, 288)]),
    ("kimi_k2.6_text", 5, 64 * 128, [(512, 32), (1024, 64), (1024, 256)]),
])
def test_the_latent_cells_chunk_programs_count_full_layer_kernels(
        config, layers, width, shapes):
    """``attention.latent_chunk_kernel`` / ``..._composed`` count, once a
    lowering, which form a full layer's chunk took: every
    ``mla_attention_chunk`` op of the three latent cells' chunk programs,
    traced over shapes alone at (rows a chunk, pages of the bucket) from
    the smallest bucket a rung takes to the largest (with and without a
    selection), takes the kernel and the composed form never; toy rows
    take the composed form."""
    from paddle_tpu.profiler import runtime_metrics
    block, rows = _cell_chunk_program(config)
    ops = [op for op in block.ops if op.type == "mla_attention_chunk"]
    assert rows == 1024 and len(ops) == layers
    counted = ("attention.latent_chunk_kernel",
               "attention.latent_chunk_composed")
    before = [runtime_metrics.counter(n) for n in counted]
    for chunk, pages in shapes:
        for op in ops:
            out = _trace_op(block, op, chunk, pages)
            assert out[op.output("Out")[0]].shape == (1, chunk, width)
    assert [runtime_metrics.counter(n) - b
            for n, b in zip(counted, before)] == [layers * len(shapes), 0]
    mla_ops.mla_attention_chunk(
        jnp.zeros((24, 2 * 32)), jnp.zeros((24, 128)),
        jnp.zeros((48, 2 * 40)), jnp.zeros((6, 8, 128)),
        jnp.zeros((1, 4), jnp.int32), jnp.int32(0), jnp.ones((1, 24), bool),
        2, 24, 8, 16, 0.2)
    assert [runtime_metrics.counter(n) - b
            for n, b in zip(counted, before)] == [layers * len(shapes), 1]


def test_the_whole_sequence_form_is_the_chunk_forms_band():
    """The training forward's attention under the band equals the
    serving chunk's over an empty ring."""
    T, H, L, R, nope, vd, window = 24, 2, 48, 8, 24, 16, 9
    key = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(key[0], (T, H * (nope + R)), jnp.float32) * 0.3
    row = jax.random.normal(key[1], (T, 128), jnp.float32)
    w_kvb = jax.random.normal(key[2], (L, H * (nope + vd)), jnp.float32) \
        * 0.1
    whole = mla_ops.latent_window_attention(q, row, w_kvb, H, nope, R, vd,
                                            0.2, window)
    chunk, _ = mla_ops.latent_window_chunk(
        q, row, w_kvb, jnp.zeros((1, 12, 128), jnp.float32), 0,
        jnp.int32(0), jnp.int32(T), H, nope, R, vd, 0.2, window)
    assert np.allclose(whole, chunk, atol=2e-5)


def test_the_gate_scales_each_head_by_its_own_sigmoid():
    x = jnp.arange(2 * 3 * 4, dtype=jnp.float32).reshape(2, 12)
    gate = jnp.asarray([[0.0, 100.0, -100.0], [1.0, -1.0, 0.0]])
    got = np.asarray(mla_ops.head_gate(x, gate, 3)).reshape(2, 3, 4)
    want = np.asarray(x).reshape(2, 3, 4)
    assert np.allclose(got[0, 0], 0.5 * want[0, 0])
    assert np.allclose(got[0, 1], want[0, 1]) and not got[0, 2].any()
    assert np.allclose(got[1, 0], want[1, 0] / (1 + np.exp(-1.0)))


# -- what is counted ---------------------------------------------------------------

def test_one_step_counts_ring_rows_and_selections_together(predictor):
    """ONE ``gen.decode_step`` span carries the window counts AND the
    selections', a chunk's span the band's pairs AND its selections; the
    always-on ``gen.window.*`` and ``gen.dsa.*`` count for this bundle."""
    from paddle_tpu.profiler import runtime_metrics
    assert predictor.sparse_attention == {"top_k": TOPK, "indexers": 2}
    win = predictor.window_attention
    assert (win["window"], win["ring"], win["layers"], win["full_layers"]) \
        == (WINDOW, RING, [1, 2], [0, 3])
    assert win["row_bytes"] == [128 * 4] * 2
    assert predictor.ring_bytes() == SLOTS * RING * 2 * 128 * 4
    assert predictor.state_vars == [f"lat{i}_ring_c" for i in (1, 2)]
    assert predictor.cache_vars == [f"lat{i}_paged_c" for i in (0, 3)] \
        + [f"lat{i}_paged_ik" for i in (0, 3)]
    ptrace.enable(1 << 12)
    ptrace.clear()
    before = {n: runtime_metrics.counter(n) for n in (
        "gen.window.rows_read", "gen.window.rows_saved",
        "gen.dsa.rows_scored", "gen.dsa.rows_selected")}
    try:
        _admit(predictor, 1, _prompt(20, seed=9))
        _admit(predictor, 2, _prompt(5, seed=10))
        _step(predictor, {1: (3, 20), 2: (4, 5)})
        spans = ptrace.snapshot_spans()
    finally:
        ptrace.disable()
        predictor.free_slot_pages(1)
        predictor.free_slot_pages(2)
    step = [s for s in spans if s["name"] == "gen.decode_step"][-1]["attrs"]
    # rows held: 21 and 6; two window layers, two full ones
    assert step["window_rows"] == 2 * (WINDOW + 6)
    assert step["full_rows"] == 2 * 27 and step["all_rows"] == 4 * 27
    assert step["ring_bytes"] == 2 * 128 * 4 * (WINDOW + 6)
    assert step["dsa_rows_scored"] == 2 * 27
    assert step["dsa_rows_selected"] == 2 * (TOPK + 6)
    assert step["dsa_selections"] == 4
    chunk = [s for s in spans if s["name"] == "gen.prefill"][0]["attrs"]
    band = WINDOW * (WINDOW + 1) // 2 + (20 - WINDOW) * WINDOW
    assert chunk["band_pairs"] == band and chunk["causal_pairs"] == 210
    assert chunk["dsa_rows_scored"] == 2 * 210
    after = {n: runtime_metrics.counter(n) for n in before}
    assert after["gen.window.rows_read"] - before["gen.window.rows_read"] \
        == step["window_rows"]
    assert after["gen.window.rows_saved"] - before["gen.window.rows_saved"] \
        == 2 * (21 - WINDOW)
    assert after["gen.dsa.rows_selected"] > before["gen.dsa.rows_selected"]


# -- the contract and the rules ------------------------------------------------------

def test_the_bundle_checks_and_every_new_op_has_its_rules(bundle_dir):
    from paddle_tpu.analysis import check_gen_bundle, typecheck
    from paddle_tpu.analysis.analyzer import lint_program
    from paddle_tpu.analysis.distributed import load_saved_program
    new = {"latent_window_attention", "latent_window_step", "head_gate"}
    assert new <= set(typecheck._RULES)
    assert new <= cost.covered_op_types()
    pre = load_saved_program(os.path.join(bundle_dir, "prefill"))
    dec = load_saved_program(os.path.join(bundle_dir, "decode"))
    with open(os.path.join(bundle_dir, "gen_meta.json")) as f:
        meta = json.load(f)
    assert check_gen_bundle(pre, dec, meta) == []
    assert "gen_slot" in pre[1]
    seen = {}
    for name, (prog, feeds, fetches) in (("pre", pre), ("dec", dec)):
        result = lint_program(prog, feed_names=feeds, fetch_names=fetches)
        assert not result.errors, [d.message for d in result.errors]
        seen[name] = [op.type for op in prog.global_block().ops]
    assert seen["pre"].count("latent_window_attention") == 2
    assert seen["dec"].count("latent_window_step") == 2
    assert seen["dec"].count("paged_attention_latent") == 2
    assert seen["pre"].count("head_gate") == seen["dec"].count("head_gate") \
        == 4
    assert seen["dec"].count("dsa_index_paged") == 2
    assert not cost.estimate(dec[0], paged_live_rows=24).uncovered
    by_type = cost.estimate(dec[0], paged_live_rows=24).by_op_type()
    # a slot's window: 9 rows x 2 heads x (128 + 48) lanes x 2, two layers
    assert by_type["latent_window_step"]["flops"] \
        == 2 * SLOTS * WINDOW * 2 * (128 + 48) * 2
    # a chunk of 16 rows, expanded: K and V of 2 heads from its 16 rows
    # and the 8 before them (48 x 80 of W_kvb a row), then 16 x 9 pairs
    # x 2 heads x (24 + 8 + 16) lanes, two layers
    chunk = cost.estimate_at(pre[0], {
        n: [1, 16] for n in ("gen_ids", "gen_pos", "gen_mask", "gen_last")})
    assert chunk.by_op_type()["latent_window_attention"]["flops"] \
        == 2 * (2 * (16 + WINDOW - 1) * 48 * 80
                + 2 * 16 * WINDOW * 2 * 48)
    # the whole-sequence form is the training forward's, with its rules
    train = fluid.Program()
    with fluid.program_guard(train, fluid.Program()):
        latent_moe.latent_moe_train_program(16, _hp(toy_config()))
    types = [op.type for op in train.global_block().ops]
    assert types.count("latent_window_attention") == 2
    assert types.count("mla_attention") == 2
    assert not lint_program(train).errors


def test_a_ring_of_the_wrong_width_is_a_type_error():
    from paddle_tpu.analysis.analyzer import lint_program
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        data = lambda n, s, d="float32": fluid.layers.data(
            name=n, shape=s, dtype=d, append_batch_size=False)
        ring = main.global_block().create_var(
            name="ring", shape=[4, 12, 96], dtype="float32")
        ring.persistable = True
        decoder.op(
            "latent_window_step",
            {"Q": data("q", [4, 1, 2 * 128]), "Row": data("row", [4, 1, 128]),
             "Ring": ring, "Lens": data("lens", [4, 1], "int32")},
            {"Out": "float32", "RingOut": ring},
            {"n_head": 2, "v_width": 32, "scale": 1.0, "window": 9})
    result = lint_program(main)
    assert any(d.code == "PTA006" and "Ring" in d.message
               for d in result.errors), [d.message for d in result.errors]


def test_a_configuration_without_the_new_keys_builds_no_ring():
    """``kimi_k2.6_text`` and ``glm_5.2`` read none of this PR's keys:
    no window layer, no gate, no rescale, no state a slot (their
    programs' digests are ``tests/test_gen_bundle_programs.py``'s)."""
    for name in ("kimi_k2.6_text", "glm_5.2"):
        with open(os.path.join(BENCH, "configs", name + ".json")) as f:
            hp = latent_moe.LatentMoEConfig.from_dict(json.load(f))
        assert hp.window_layers == [] and not hp.apply_mla_qkv_lora_rescale
        assert latent_moe.ring_var_names(hp) == []
        assert all(hp.attention(i)["gate"] is None and
                   hp.attention(i)["window"] == 0
                   for i in range(int(hp.num_hidden_layers)))
        assert len(latent_moe.paged_cache_var_names(hp)) \
            == int(hp.num_hidden_layers) + len(hp.full_layers)


# -- the share of an expert-parallel deployment ------------------------------------------

def test_the_shares_of_one_layer_add_up_to_the_uncut_layer(weights):
    """The routed parts of all four shares, plus the shared expert once,
    are the uncut reference's layer (``routed_scaling_factor`` 1)."""
    full = toy_config()
    h = jax.random.normal(jax.random.PRNGKey(3), (9, 64))
    p = lambda name, cast=True: weights[f"lat1_{name}"]
    want = ref.moe(h, p, full, jnp.float32)
    shared = ref._gated(h, p("sh_gate.w"), p("sh_up.w"), p("sh_down.w"))
    idx, w = moe_ops.moe_route(h, p("gate.w"), p("gate.bias"), 2, 1.0, True)
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
    with open(os.path.join(BENCH, "configs", "dots3_note_prev.json")) as f:
        published = json.load(f)
    hp = latent_moe.LatentMoEConfig.from_dict(published)
    full, win = hp.attention(0), hp.attention(2)
    assert (hp.hidden_size, hp.num_hidden_layers) == (5120, 9)
    assert (full["H"], full["q_rank"], full["L"], full["nope"], full["R"],
            full["vd"], full["theta"], full["row"], full["window"]) == (
        128, 1024, 512, 128, 64, 128, 8e7, 640, 0)
    assert (win["H"], win["q_rank"], win["L"], win["nope"], win["R"],
            win["vd"], win["theta"], win["row"], win["window"]) == (
        64, 1024, 1024, 192, 64, 128, 5e4, 1152, 513)
    assert full["gate"] == win["gate"] == "headwise"
    assert hp.apply_mla_qkv_lora_rescale and hp.ring_rows == 640
    assert hp.scale_of(0) == pytest.approx(192 ** -0.5)
    assert hp.scale_of(2) == pytest.approx(1 / 16)
    assert hp.rope_of(2)["theta"] == 5e4 and hp.rope_of(0)["factor"] == 1.0
    assert (hp.index_topk, hp.index_n_heads, hp.index_head_dim) == (
        2048, 64, 128)
    assert [hp.indexer(i) for i in range(9)] == [
        "full", "full", None, None, None, "full", None, None, None]
    assert hp.window_layers == [2, 3, 4, 6, 7, 8]
    assert hp.held == 8 and hp.n_routed_experts == 256
    assert hp.moe_layers == list(range(1, 9))
    assert latent_moe.paged_cache_var_names(hp) == [
        f"lat{i}_paged_c" for i in (0, 1, 5)] + [
        f"lat{i}_paged_ik" for i in (0, 1, 5)]
    assert latent_moe.ring_var_names(hp) == [
        f"lat{i}_ring_c" for i in (2, 3, 4, 6, 7, 8)]
    # a window layer's chunk goes expanded, every head its own K/V head,
    # and the banded kernel's block rule admits its 1024 rows
    hp.dtype = "bfloat16"
    assert latent_moe._window_section(hp)["heads"] == [64, 64]
    assert window_ops.flash_blocks(1024, 1, 513) is not None
    assert window_ops.lead_rows(1024, 1, 513) == 512
    assert adapter.param_count(published) == pytest.approx(3.09e9, rel=0.01)
