"""The ops and kernels under ``models/window_moe.py``, each alone (no
bundle, no predictor; ``tests/test_window_moe.py`` has the exported
bundle against the plain reference): the banded and the grouped prefill
kernels, the ring step and the paged kernel with key and value heads of
different widths in interpret mode against their composed forms, a
ring's lead-in and what a chunk leaves in it, the count of key blocks a
band computes, the partial rotary, the typecheck rules of the ring and
chunk ops, and the configuration's published keys."""

import hashlib
import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.models import window_moe
from paddle_tpu.ops import attention_ops, window_ops

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from models import mimo_v2_flash as adapter             # noqa: E402
from reference import mimo_v2_flash_ref as ref          # noqa: E402

WINDOW = 8


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


# -- the block rule at every shape the serving bundles hand the kernels ------------

PINNED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "golden",
                      "flash_blocks_pinned.json")
#: the ops that reach ``flash_blocks`` -> (its K/V heads: an attribute's
#: name, or their number; whether the op carries a band)
KERNEL_OPS = {"window_attention": ("n_kv_head", True),
              "gqa_flash_attention_chunk": ("n_kv_head", False),
              "mla_attention_chunk": ("n_head", False)}
#: sha256 (16 hex digits) of the rows of the configurations that build no
#: ``mla_attention_chunk``, as ``7f3a514``'s file held them
AT_7F3A514 = {"mimo_v2_flash": "96dd2fa4ca0bc36c",
              "k_exaone_236b_a23b": "c077b0cb5086c3a3",
              "phi4_mini_flash": "cf5fa69a162c6b0f",
              "solar_open2_250b": "4f3d7c582ce2647f"}
PINNED_CONFIGS = ["mimo_v2_flash", "k_exaone_236b_a23b", "phi4_mini_flash",
                  "glm_5.2", "kimi_k2.6_text", "solar_open2_250b",
                  "dots3_note_prev"]


def _kernel_calls(name):
    """``[T, group, window, keys]`` of every call a published serving
    configuration's chunk programs make into the flash kernel: the
    configuration's adapter runs with ``export_bundle`` replaced by one
    that keeps the exporter's meta and BUILDS its chunk programs (no
    startup runs: no weight is allocated); group and window are read off
    the programs' ops, the chunk rungs and page buckets off the meta."""
    import importlib
    from lib import models as adapters
    from paddle_tpu.framework import unique_name_scope
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        cfg = json.load(f)
    kept = {}

    def keep(dirname, hp, where, build_prefill, build_decode, cache_vars,
             n_layer, num_slots=8, prompt_buckets=None, page_len=64,
             num_pages=None, page_buckets=None, state_vars=None,
             sections=None, more_programs=None):
        meta = {"page_len": int(page_len),
                "prompt_buckets": list(prompt_buckets),
                "page_buckets": list(page_buckets)}
        meta.update(sections(meta) if callable(sections) else sections or {})
        kept.update(meta=meta, pool=(
            num_slots, page_len,
            num_pages or num_slots * -(-int(hp.max_len) // page_len)),
            builds=[build_prefill] + list((more_programs or {}).values()))

    builders = [importlib.import_module("paddle_tpu.models." + m) for m in
                ("hybrid_moe", "latent_moe", "window_moe", "hybrid_decoder")]
    olds = [m.export_bundle for m in builders]
    for m in builders:
        m.export_bundle = keep
    try:
        adapters.adapter_of(cfg).export("unused", cfg)
    finally:
        for m, old in zip(builders, olds):
            m.export_bundle = old
    calls = set()
    for build in kept["builds"]:
        main = fluid.Program()
        with unique_name_scope(""), fluid.program_guard(main,
                                                        fluid.Program()):
            build(*kept["pool"])
        for op in main.global_block().ops:
            if op.type in KERNEL_OPS:
                hkv, banded = KERNEL_OPS[op.type]
                if isinstance(hkv, str):
                    hkv = int(op.attr(hkv))
                calls.add((int(op.attr("n_head")) // hkv,
                           int(op.attr("window")) if banded else 0))
    meta = kept["meta"]
    return [[T, group, window, keys]
            for T in meta["prefill_chunks"] for group, window in sorted(calls)
            for keys in ([None] if window else
                         [p * meta["page_len"] for p in meta["page_buckets"]])]


def _block_rule_at(T, group, window, keys):
    """What the three functions of the rule say of one call: the blocks,
    the lead rows of a band, and the key blocks computed where the chunk
    stands at 0, at 64 and at the end of its keys."""
    blocks = window_ops.flash_blocks(T, group, window, keys)
    return [blocks and list(blocks),
            window_ops.lead_rows(T, group, window) if window else None,
            [list(window_ops.key_blocks_computed(T, group, window, start,
                                                 keys))
             for start in (0, 64, (keys or 8192) - T)]]


@pytest.mark.parametrize("name", PINNED_CONFIGS)
def test_the_block_rule_is_the_recorded_one_at_every_serving_shape(name):
    """``flash_blocks``, ``lead_rows`` and ``key_blocks_computed`` at
    every (rows, heads a K/V head, window, key rows) a serving bundle
    hands the kernel, the latent builder's full layers among them,
    against ``tests/golden/flash_blocks_pinned.json`` (``PYTHONPATH=.
    python tests/test_window_ops.py`` rewrites it from the tree it runs
    on).  A shape the rule refuses is recorded as refused: its chunk
    runs the composed form and goes on doing so.  The file was recorded
    at ``717d37d`` and again when the latent builder's full layers went
    from ONE K/V head under all query heads to every head its own: the
    rows of the other builders' four configurations are still
    ``7f3a514``'s (their digests), and every row of the three latent
    configurations is a full layer's chunk at ONE query head a K/V
    head."""
    with open(PINNED) as f:
        golden = json.load(f)[name]
    seen = [call + _block_rule_at(*call) for call in _kernel_calls(name)]
    assert seen and seen == golden
    if name in AT_7F3A514:
        assert hashlib.sha256(json.dumps(golden).encode()).hexdigest()[
            :16] == AT_7F3A514[name]
    else:
        assert all(group == 1 and not window
                   for _, group, window, *_ in golden)


def test_the_block_rule_admits_one_query_head_a_kv_head():
    """Every head its own K/V head (an expanded latent chunk) has no
    heads to stack into 2048 left rows: the rule, refused there before,
    hands the chunk's own rows out as blocks, square under a band, ONE
    query block over the causal key block under none; with heads to
    stack it is as it was."""
    blocks = window_ops.flash_blocks(1024, 1, 513)
    assert blocks is not None and 1024 % blocks[0] == 0 \
        and blocks[0] % blocks[1] == 0
    assert window_ops.lead_rows(1024, 1, 513) == 512
    assert blocks == (512, 512)
    assert window_ops.key_blocks_computed(1024, 1, 513, start=0) == (3, 512)
    assert window_ops.key_blocks_computed(1024, 1, 513, start=64) == (4, 512)
    assert window_ops.flash_blocks(128, 1, 40) == (128, 128)
    assert window_ops.flash_blocks(2048, 1, 513) == (2048, 128)
    # causal: a full layer's chunk of 512 or 1024 rows over its bucket
    assert window_ops.flash_blocks(1024, 1, 0) == (1024, 1024)
    assert window_ops.flash_blocks(512, 1, 0, keys=4096) == (512, 1024)
    assert window_ops.flash_blocks(512, 1, 0) == (512, 512)
    assert window_ops.flash_blocks(1024, 1, 0, keys=1536) == (1024, 512)
    assert window_ops.flash_blocks(2048, 1, 0) == (2048, 512)
    assert window_ops.key_blocks_computed(1024, 1, 0, start=4096,
                                          keys=8192) == (5, 1024)
    for refused in ((1024, 1, 0, 256), (256, 1, 0), (768, 1, 0),
                    (256, 4, 512), (512, 2, 513), (384, 1, 513),
                    (64, 1, 513), (40, 1, 128)):
        assert window_ops.flash_blocks(*refused) is None, refused


@pytest.mark.parametrize("start", [0, 24, 64], ids=["first", "off_a_block",
                                                    "bucket_end"])
@pytest.mark.parametrize("form", ["columns", "head_major", "expanded"])
def test_the_causal_kernel_at_one_head_a_kv_head_takes_a_selection(form,
                                                                    start):
    """Every head its own K/V head, ONE query block of 64 rows over key
    blocks of 32 (the shape of the block rule's answer for a full latent
    chunk), under a selection that keeps half the keys under each row:
    a head's blocks read as columns of the rows as they lie (heads of
    whole lane tiles), from head-major copies (toy lanes), and made in
    the kernel from LATENT rows and the head's columns of two matrices
    (``expand``); against the composed attention."""
    rng = np.random.RandomState(start)
    C, T, H = 64, 128, 3
    dk, dv = (24, 16) if form == "head_major" else (128, 128)
    q = jnp.asarray(rng.randn(C, H * dk), jnp.float32) * 0.3
    cols, rows = np.arange(T)[None], start + np.arange(C)[:, None]
    select = jnp.asarray(((rng.rand(C, T) < 0.5) | (cols == rows))
                         & (cols <= rows), jnp.int8)
    kernel = dict(n_head=H, n_kv_head=H, scale=0.2, interpret=True,
                  blocks=(64, 32))
    if form == "expanded":
        latent = jnp.asarray(rng.randn(T, 256), jnp.float32)
        w_k = jnp.asarray(rng.randn(256, H * dk), jnp.float32) * 0.1
        w_v = jnp.asarray(rng.randn(128, H * dv), jnp.float32) * 0.1
        k, v = latent @ w_k, latent[:, :128] @ w_v
        got = window_ops.flash_attention(q, latent, None, None, start, None,
                                         select, (w_k, w_v), **kernel)
    else:
        k = jnp.asarray(rng.randn(T, H * dk), jnp.float32)
        v = jnp.asarray(rng.randn(T, H * dv), jnp.float32)
        got = window_ops.flash_attention(q, k, v, None, start, None, select,
                                         **kernel)
    want = window_ops.composed_attention(q, k, v, H, H, 0.2, start=start,
                                         select=select)
    assert np.allclose(got, want, atol=2e-4 if form == "expanded" else 2e-5)
    # no selection: the shifted diagonal alone
    if form == "expanded":
        plain = window_ops.flash_attention(q, latent, None, None, start,
                                           expand=(w_k, w_v), **kernel)
        with pytest.raises(ValueError, match="causal form alone"):
            window_ops.flash_attention(
                q, latent[:C], None, expand=(w_k, w_v),
                **dict(kernel, window=8, blocks=(32, 8)))
    else:
        plain = window_ops.flash_attention(q, k, v, None, start, **kernel)
    assert np.allclose(plain, window_ops.composed_attention(
        q, k, v, H, H, 0.2, start=start), atol=2e-4)


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


# -- the rules, the published keys ----------------------------------------------------

def test_mismatched_heads_and_rings_are_type_errors():
    from paddle_tpu.analysis.analyzer import lint_program
    from paddle_tpu.models.decoder import data, op
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        q = data("q", [2, 1, 4 * 24])
        k = data("k", [2, 1, 2 * 24])
        v = data("v", [2, 1, 2 * 16])
        lens = data("lens", [2, 1])                 # not an integer
        sink = data("sink", [3])                    # not one a head
        block = main.global_block()
        rings = []
        for name, shape in (("rk", [2, 4, 48]), ("rv", [2, 8, 40])):
            r = block.create_var(name=name, shape=shape, dtype="float32")
            r.persistable = True
            rings.append(r)
        out = op("window_attention_step",
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
        q, k, v = (data(n, [1, 16, w]) for n, w in
                   (("q", 4 * 24), ("k", 2 * 24), ("v", 2 * 16)))
        pos, mask = data("pos", [1, 16]), data("mask", [1, 16])
        slot = data("slot", [1, 1], "int32")
        table = data("table", [1, 4], "int32")
        block = main.global_block()
        held = {}
        for name, shape in (("rk", [2, 4, 48]), ("rv", [2, 8, 32]),
                            ("pk", [6, 8, 48]), ("pv", [6, 8, 40])):
            held[name] = block.create_var(name=name, shape=shape,
                                          dtype="float32")
            held[name].persistable = True
        attrs = {"n_head": 4, "n_kv_head": 2, "scale": 1.0}
        band = op("window_attention",
                  {"Q": q, "K": k, "V": v, "KRing": held["rk"],
                   "VRing": held["rv"], "Slot": slot, "Pos": pos,
                   "Mask": mask},
                  {"Out": "float32", "KRingOut": held["rk"],
                   "VRingOut": held["rv"]}, {**attrs, "window": 8})["Out"]
        full = op("gqa_flash_attention_chunk",
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


if __name__ == "__main__":
    with open(PINNED, "w") as f:
        json.dump({name: [call + _block_rule_at(*call)
                          for call in _kernel_calls(name)]
                   for name in PINNED_CONFIGS}, f, indent=1)
        f.write("\n")
    print(f"{len(PINNED_CONFIGS)} configurations -> {PINNED}")
