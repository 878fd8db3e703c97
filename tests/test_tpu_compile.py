"""What the chip's compiler must accept, checked without the chip, and the
control flow of ``chip_smoke.py``, rehearsed on the CPU.

The Pallas kernels of the main path are compiled at real widths for a
DESCRIBED ``v5e:2x2`` topology (libtpu's compiler is installed; no device
is attached): interpret-mode tests cannot see a lane-misaligned slice, a
``dot_general`` Mosaic refuses or a VMEM overflow, this can.  Nothing
runs, so nothing here says anything about results or times.

All such compiles live in THIS file, and the topology is described only
inside the module-scoped fixture below — never at import, in a ``skipif``
or in ``parametrize`` arguments: one process at a time may load the TPU
library, xdist workers each import every test file, and only the worker
that is handed this file may load it.  The kernel builders are called
with ``interpret=False`` directly (``_use_interpret()`` sees the CPU
under a described device), in this process, with the persistent
compilation cache off (an entry written for a described device cannot be
read back without a chip).
"""

import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def topo():
    """The described v5e:2x2; compile cache off around the module's
    compiles."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    """A sharding on one described v5e chip."""
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    """Compile ``fn`` for the described chip; returns the HLO text."""
    import jax
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _flash_loss(q, k, v, mask):
    import jax.numpy as jnp
    from paddle_tpu.ops import attention_ops as A
    out, _ = A._pallas_attention(q, k, v, mask, True,
                                 q.shape[-1] ** -0.5, interpret=False)
    return jnp.sum(out.astype(jnp.float32))


def _flash_fwd_bwd(q, k, v, mask):
    """Forward + both backward kernels, as the sdpa grad op calls them."""
    from paddle_tpu.ops import attention_ops as A
    scale = q.shape[-1] ** -0.5
    out, lse = A._pallas_attention(q, k, v, mask, True, scale,
                                   interpret=False)
    return A._pallas_attention_bwd(q, k, v, mask, out, lse, out, True,
                                   scale, interpret=False)


@pytest.mark.parametrize("fn,bhsd", [
    (_flash_loss, (32, 8, 1024, 64)),
    (_flash_fwd_bwd, (32, 8, 1024, 64)),
    (_flash_fwd_bwd, (8, 8, 2048, 64)),
], ids=["fwd-S1024", "fwd+bwd-S1024", "fwd+bwd-S2048"])
def test_flash_attention_compiles_for_v5e(one_chip, fn, bhsd):
    import jax.numpy as jnp
    B, H, S, D = bhsd
    qkv = (bhsd, jnp.bfloat16)
    hlo = _compile(fn, one_chip, qkv, qkv, qkv, ((B, S), jnp.bfloat16))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("causal", [False, True],
                         ids=["full", "causal"])
def test_packed_attention_compiles_for_v5e_lane_dense(one_chip, causal):
    """The packed forward and backward at the long training cell's shape,
    ``[32, 1024, 8 * 64]``: the chip's compiler takes them (the in-kernel
    transposes of the statistics, the scoped VMEM the calls ask for), and
    no array that crosses a call has a last dimension under 128 lanes:
    the ``[.., S, 2]`` residual and ``[.., S, 1]`` mask and delta of the
    ``[B, H, S, D]`` kernels are padded 64-128x in HBM by the (8, 128)
    tiling, 134 MB an array at this shape, and must not come back."""
    import re
    import jax.numpy as jnp
    from paddle_tpu.ops import attention_packed as P

    def fn(q, k, v, mask, g):
        blocks = P.plan(q.shape, k.shape, v.shape, 8, causal)
        assert blocks is not None
        out, res = P.attention(q, k, v, mask, causal, 0.125, 8, blocks)
        return P.attention_bwd(q, k, v, mask, out, res, g, causal, 0.125,
                               8, blocks)

    x = ((32, 1024, 512), jnp.bfloat16)
    hlo = _compile(fn, one_chip, x, x, x, ((32, 1024), jnp.bfloat16), x)
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2, hlo
    for line in calls:
        line = line.split("frontend_attributes")[0]
        shapes = [tuple(int(d) for d in dims.split(","))
                  for dims in re.findall(r"(?:bf16|f32|s32)\[([\d,]+)\]",
                                         line)]
        assert len(shapes) >= 5, line
        # (rank 1: the prefetched scalars of the causal calls, SMEM)
        narrow = [sh for sh in shapes if len(sh) > 1 and sh[-1] < 128]
        assert not narrow, (narrow, line)
        assert (32, 4, 8, 1024) in shapes       # the residual, 4 MB


@pytest.mark.parametrize("shape,names", [
    ((4,), ("data",)), ((4, 1), ("data", "model"))],
    ids=["data4", "data4-model1"])
def test_packed_attention_on_a_data_mesh_compiles_per_shard(topo, shape,
                                                            names,
                                                            monkeypatch):
    """The dp4 cell's attention at its real size, as the op lowers it on
    a mesh (``attention_ops._per_shard``): global batch 1024 over the
    described chips' ``data`` axis.  Mosaic refuses a kernel the
    partitioner would have to split ("cannot be automatically
    partitioned"), under a ``shard_map`` that leaves an axis to it too,
    so every call has to arrive inside one over the WHOLE mesh: each
    ``tpu_custom_call`` then works on its shard's rows and nothing gathers
    its operands."""
    import re
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from paddle_tpu.ops import attention_ops as A
    monkeypatch.setattr(A, "_use_interpret", lambda: False)
    mesh = Mesh(np.asarray(topo.devices).reshape(shape), names)
    B, S, HD, heads = 1024, 256, 512, 8

    def fn(q, k, v, mask, g):
        outs = []
        for causal in (False, True):
            out, res = A._attention_forward(q, k, v, mask, causal, 0.125,
                                            heads, True, mesh)
            outs += A._attention_backward(q, k, v, mask, out, res, g,
                                          causal, 0.125, heads, True, mesh)
        return outs

    rows = NamedSharding(mesh, PartitionSpec("data"))
    x = jax.ShapeDtypeStruct((B, S, HD), jnp.bfloat16, sharding=rows)
    mask = jax.ShapeDtypeStruct((B, S), jnp.bfloat16, sharding=rows)
    hlo = jax.jit(fn).lower(x, x, x, mask, x).compile().as_text()
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 4, hlo
    local = B // shape[0]
    for line in calls:
        line = line.split("frontend_attributes")[0]
        dims = {tuple(int(d) for d in dims.split(","))[0]
                for dims in re.findall(r"(?:bf16|f32|s32)\[([\d,]+)\]",
                                       line)}
        assert dims == {local}, line
    assert "all-gather" not in hlo and "all-to-all" not in hlo


def _ragged_lens(S, P, PL):
    """A ``lens`` that leaves blocks dead: a free slot, one row, lengths
    ending inside a page and on a page's edge, one stream filling the
    bucket (a constant of the compiled program: the kernel reads its trip
    counts from it at run time either way)."""
    import numpy as np
    import jax.numpy as jnp
    lens = np.array([0, 1, PL + 3, 2 * PL, P * PL] * S)[:S]
    return jnp.asarray(np.minimum(lens, P * PL).reshape(S, 1), jnp.int32)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_paged_decode_compiles_for_v5e(one_chip, dtype):
    """The decode kernel at chip_smoke phase 2's shapes: 8 slots, an
    8-page bucket of a 512-page pool, page_len 16, 8 heads x 128."""
    import jax.numpy as jnp
    from paddle_tpu.ops import attention_ops as A
    S, P, NP, PL, H, D = 8, 8, 512, 16, 8, 128
    dt = jnp.dtype(dtype)

    def fn(q, kc, vc, pt):
        out = A._pallas_paged_attention(q, kc, vc, pt,
                                        _ragged_lens(S, P, PL), H,
                                        D ** -0.5, interpret=False)
        assert out is not None, "shape gate refused the smoke's shapes"
        return out

    hlo = _compile(fn, one_chip, ((S, 1, H * D), dt),
                   ((NP, PL, H * D), dt), ((NP, PL, H * D), dt),
                   ((S, P), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_paged_decode_compiles_for_v5e_at_the_widest_rows(one_chip):
    """The decode kernel where its double buffer is largest: the serving
    cell's 16 slots, 32 heads x 128 on 4096-wide float32 rows, the
    64-page bucket of a 2048-page pool (4 pages a block: 2 x 2 x 1 MB of
    VMEM), with a ``lens`` that leaves blocks dead."""
    import jax.numpy as jnp
    from paddle_tpu.ops import attention_ops as A
    S, P, NP, PL, H, D = 16, 64, 2048, 16, 32, 128
    assert A._paged_blocking(P, PL, H * D, 4, False) == (4, 64)

    def fn(q, kc, vc, pt):
        return A._pallas_paged_attention(q, kc, vc, pt,
                                         _ragged_lens(S, P, PL), H,
                                         D ** -0.5, interpret=False)

    hlo = _compile(fn, one_chip, ((S, 1, H * D), jnp.float32),
                   ((NP, PL, H * D), jnp.float32),
                   ((NP, PL, H * D), jnp.float32), ((S, P), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("bucket", [128, 1024])
def test_page_seed_compiles_for_v5e_in_place(one_chip, bucket):
    """Admission's compiled seed (``gen/predictor.py:_seed_pool``) at the
    serving cell's widths: 8 pools of 2048 pages x 16 rows x 4096 f32
    (537 MB each), a prefill bucket's K/V, a 128-entry page list.  Every
    pool input is aliased to an output (donated: updated in place), no
    pool-shaped copy is in the program, and the compiler's temporaries
    stay far under ONE pool."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.gen.predictor import _seed_pool
    NP, PL, HD, PPS, N = 2048, 16, 4096, 128, 8
    pool_bytes = NP * PL * HD * 4

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pools = tuple(sds((NP, PL, HD), jnp.float32) for _ in range(N))
    kv = tuple(sds((1, bucket, HD), jnp.float32) for _ in range(N))
    compiled = _seed_pool.lower(
        pools, kv, sds((PPS,), jnp.int32), sds((), jnp.int32),
        max_rows=PPS * PL).compile()
    hlo = compiled.as_text()
    header = hlo.split("\n", 1)[0]
    assert header.count("may-alias") + header.count("must-alias") == N, \
        header
    pool_shape = f"f32[{NP},{PL},{HD}]"
    copies = [ln for ln in hlo.splitlines()
              if " copy(" in ln and ln.split("=", 1)[1].split()[0]
              .startswith(pool_shape)]
    assert not copies, copies[:2]
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < pool_bytes // 8, mem
    assert mem.alias_size_in_bytes == N * pool_bytes, mem


@pytest.mark.parametrize("pages", [8, 128])
def test_grouped_paged_decode_compiles_for_v5e(one_chip, pages):
    """The decode kernel with grouped query heads at the hybrid serving
    cell's shapes: 32 slots, 32 bfloat16 query heads x 128 over a float32
    pool whose rows hold 2 K/V heads (256 wide), page_len 16."""
    import jax.numpy as jnp
    from paddle_tpu.ops import attention_ops as A
    S, NP, PL, H, HKV, D = 32, 4096, 16, 32, 2, 128

    def fn(q, kc, vc, pt):
        out = A._pallas_paged_attention(q, kc, vc, pt,
                                        _ragged_lens(S, pages, PL), H,
                                        D ** -0.5, interpret=False)
        assert out is not None, "shape gate refused grouped heads"
        return out

    hlo = _compile(fn, one_chip, ((S, 1, H * D), jnp.bfloat16),
                   ((NP, PL, HKV * D), jnp.float32),
                   ((NP, PL, HKV * D), jnp.float32),
                   ((S, pages), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("pages", [8, 256])
def test_block_paged_decode_compiles_for_v5e(one_chip, pages):
    """The decode kernel handed a BLOCK of 4 rows a slot at the
    block-diffusion serving cell's shapes: 32 slots x 4 rows x 32
    bfloat16 query heads x 128 over a bfloat16 pool whose rows hold 4 K/V
    heads (512 wide), page_len 16: 32 query rows share a K/V head's
    copy."""
    import jax.numpy as jnp
    from paddle_tpu.ops import attention_ops as A
    S, L, NP, PL, H, HKV, D = 32, 4, 8192, 16, 32, 4, 128

    def fn(q, kc, vc, pt):
        out = A._pallas_paged_attention(q, kc, vc, pt,
                                        _ragged_lens(S, pages, PL), H,
                                        D ** -0.5, interpret=False)
        assert out is not None, "shape gate refused a block of rows"
        return out

    hlo = _compile(fn, one_chip, ((S, L, H * D), jnp.bfloat16),
                   ((NP, PL, HKV * D), jnp.bfloat16),
                   ((NP, PL, HKV * D), jnp.bfloat16),
                   ((S, pages), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("pages", [8, 256])
def test_two_block_paged_decode_compiles_for_v5e(one_chip, pages):
    """The decode kernel handed TWO blocks of 4 rows a slot with a limit
    a row (the block that is stored sees 4 rows fewer than the block that
    is opened; a slot that opens none has 4 dead rows) at the
    block-diffusion serving cell's shapes: 64 query rows share a K/V
    head's copy, under a ``[64, 1]`` column of limits."""
    import jax.numpy as jnp
    from paddle_tpu.ops import attention_ops as A
    S, L, NP, PL, H, HKV, D = 32, 4, 8192, 16, 32, 4, 128

    def fn(q, kc, vc, pt):
        lens = _ragged_lens(S, pages, PL)
        half = jnp.arange(2 * L)[None, :] // L
        # every other slot opens a block; the others' second half is dead
        opens = (jnp.arange(S) % 2 == 0)[:, None]
        row_lens = jnp.where(half == 0, jnp.maximum(lens - L, 0),
                             jnp.where(opens, lens, 0))
        out = A._pallas_paged_attention(
            q, kc, vc, pt, jnp.max(row_lens, axis=1, keepdims=True), H,
            D ** -0.5, interpret=False, row_lens=row_lens)
        assert out is not None, "shape gate refused two blocks of rows"
        return out

    hlo = _compile(fn, one_chip, ((S, 2 * L, H * D), jnp.bfloat16),
                   ((NP, PL, HKV * D), jnp.bfloat16),
                   ((NP, PL, HKV * D), jnp.bfloat16),
                   ((S, pages), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_seed_of_pages_and_state_compiles_for_v5e_in_place(one_chip):
    """Admission's compiled seed with per-slot state beside the pool, at
    the hybrid serving cell's widths: 2 pools of 4096 pages x 16 rows x
    256 f32 and five mixers' conv windows [32, 3, 10240] and recurrent
    states [32, 128, 64, 128] (134 MB each).  Every pool and state input
    is aliased to an output, and the temporaries stay far under one
    state array: only the slot's row is touched."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.gen.predictor import _seed_pool
    S, NP, PL, ROW, PPS, bucket = 32, 4096, 16, 256, 128, 1024
    shapes = [(S, 3, 10240), (S, 128, 64, 128)] * 5

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pools = tuple(sds((NP, PL, ROW), jnp.float32) for _ in range(2))
    kv = tuple(sds((1, bucket, ROW), jnp.bfloat16) for _ in range(2))
    states = tuple(sds(shape, jnp.float32) for shape in shapes)
    new = tuple(sds((1,) + shape[1:], jnp.float32) for shape in shapes)
    compiled = _seed_pool.lower(
        pools, kv, sds((PPS,), jnp.int32), sds((), jnp.int32), states, new,
        sds((), jnp.int32), max_rows=PPS * PL).compile()
    header = compiled.as_text().split("\n", 1)[0]
    assert header.count("may-alias") + header.count("must-alias") == 12, \
        header
    mem = compiled.memory_analysis()
    state_bytes = S * 128 * 64 * 128 * 4
    assert mem.temp_size_in_bytes < state_bytes // 8, mem
    assert mem.alias_size_in_bytes >= 5 * state_bytes, mem


def test_hybrid_decode_ops_compile_for_v5e(one_chip):
    """The one-token state update and the held experts' product at the
    hybrid serving cell's widths (32 slots; 128 heads x 64 x state 128;
    64 experts of 1024 x 2688): plain XLA, a state-sized or expert-sized
    temporary would double the step's memory traffic."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import moe_ops, ssm_ops
    S, H, C = 32, 128, 10240
    bf, f32 = jnp.bfloat16, jnp.float32

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def update(x, dt, a, d, b, h, live):
        return ssm_ops.ssm_update(x, dt, a, d, b, h, live, n_head=H,
                                  head_dim=64, n_groups=8, state=128)

    mem = jax.jit(update, donate_argnums=5).lower(
        sds((S, C), bf), sds((S, H), bf), sds((H,), f32), sds((H,), f32),
        sds((H,), f32), sds((S, H, 64, 128), f32),
        sds((S,), jnp.bool_)).compile().memory_analysis()
    assert mem.temp_size_in_bytes < 8 << 20, mem

    def experts(u, idx, w, w1, w2, live):
        return moe_ops.moe_experts(u, idx, w, w1, w2, 0, live)

    mem = jax.jit(experts).lower(
        sds((S, 1024), bf), sds((S, 22), jnp.int32), sds((S, 22), f32),
        sds((64, 1024, 2688), bf), sds((64, 2688, 1024), bf),
        sds((S,), jnp.bool_)).compile().memory_analysis()
    assert mem.temp_size_in_bytes < 64 << 20, mem


@pytest.mark.parametrize("pages", [1, 32, 256])
def test_latent_paged_decode_compiles_for_v5e(one_chip, pages):
    """The decode kernel's latent form at the latent-attention serving
    cell's shapes: 32 slots, 64 absorbed query heads over ONE bfloat16
    pool whose 640-wide row (512 latent | 64 rotary | 64 zeros) is every
    head's key and, in its first 512 lanes, their value; page_len 16,
    the smallest, a middle and the widest page bucket of the 8192-page
    pool (32 pages a block, chunks of 512 rows)."""
    import jax.numpy as jnp
    from paddle_tpu.ops import attention_ops as A
    S, NP, PL, H, W, V = 32, 8192, 16, 64, 640, 512
    if pages == 256:
        assert A._paged_blocking(pages, PL, W, 2, True) == (32, 512)

    def fn(q, cache, pt):
        out = A._pallas_paged_attention(q, cache, None, pt,
                                        _ragged_lens(S, pages, PL), H,
                                        0.1447, interpret=False, v_width=V)
        assert out is not None, "shape gate refused the latent row"
        return out

    hlo = _compile(fn, one_chip, ((S, 1, H * W), jnp.bfloat16),
                   ((NP, PL, W), jnp.bfloat16), ((S, pages), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_a_576_wide_latent_row_is_refused_by_the_gate_not_the_compiler():
    """What the gate stands for: the chip's DMA takes whole vregs, so
    the published 576 values a row are stored 640 wide (the compiler
    lays a 576-wide array out 640 wide in HBM anyway)."""
    from paddle_tpu.ops import attention_ops as A
    assert not A._paged_kernel_ok(64, 64 * 576, 16, False, 576, 2,
                                  v_width=512)
    assert A._paged_kernel_ok(64, 64 * 640, 16, False, 640, 2, v_width=512)


@pytest.mark.parametrize("rows", [32, 2048])
def test_routed_gated_experts_compile_for_v5e(one_chip, rows):
    """The routed product of gated experts (three grouped matrix
    products, ``megablox.gmm``) at the latent-attention serving cell's
    shapes: 12 held experts of 7168 x 2048 in bfloat16, a decode step's
    32 rows and the widest prompt bucket's 2048, top-8 of 384.  No
    temporary the size of the held experts (1.06 GB): an expert's
    matrices are read where they lie."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import moe_ops
    bf = jnp.bfloat16

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def experts(x, idx, w, wg, wu, wd, live):
        return moe_ops.moe_experts_gated(x, idx, w, wg, wu, wd, 0, live,
                                         routed=True, interpret=False)

    compiled = jax.jit(experts).lower(
        sds((rows, 7168), bf), sds((rows, 8), jnp.int32),
        sds((rows, 8), jnp.float32), sds((12, 7168, 2048), bf),
        sds((12, 7168, 2048), bf), sds((12, 2048, 7168), bf),
        sds((rows,), jnp.bool_)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3
    # a trip's 128 or 512 sorted rows and their products, whatever the rows
    assert compiled.memory_analysis().temp_size_in_bytes < 96 << 20, \
        compiled.memory_analysis()


@pytest.mark.parametrize("rows", [32, 1024])
def test_routed_relu2_experts_compile_for_v5e(one_chip, rows):
    """``moe_experts`` through the same routed core at the hybrid
    serving cell's shapes: 64 held experts of 1024 x 2688 in bfloat16, a
    decode step's 32 rows and the widest prompt bucket's 1024, top-22 of
    512: two grouped products a layer and no temporary the size of the
    held experts (0.70 GB)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import moe_ops
    bf = jnp.bfloat16

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def experts(u, idx, w, w1, w2, live):
        return moe_ops.moe_experts(u, idx, w, w1, w2, 0, live,
                                   routed=True, interpret=False)

    compiled = jax.jit(experts).lower(
        sds((rows, 1024), bf), sds((rows, 22), jnp.int32),
        sds((rows, 22), jnp.float32), sds((64, 1024, 2688), bf),
        sds((64, 2688, 1024), bf), sds((rows,), jnp.bool_)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2
    assert compiled.memory_analysis().temp_size_in_bytes < 32 << 20, \
        compiled.memory_analysis()


@pytest.mark.parametrize("rows", [128, 1024, 2048])
def test_latent_prefill_attention_compiles_for_v5e(one_chip, rows):
    """The prefill form of latent attention through the flash kernel at
    the latent-attention serving cell's shapes: 64 heads expanded from a
    512-wide latent, keys 192 wide (128 | the shared rotary 64), values
    128, bfloat16; the smallest, a middle and the widest prompt
    bucket."""
    import jax.numpy as jnp
    from paddle_tpu.ops import mla_ops

    def fn(q, latent, w_kvb, mask):
        return mla_ops.mla_attention(q, latent, w_kvb, mask, 64, 128, 64,
                                     128, 0.1447, flash=True,
                                     interpret=False)

    hlo = _compile(fn, one_chip, ((rows, 64 * 192), jnp.bfloat16),
                   ((rows, 640), jnp.bfloat16),
                   ((512, 64 * 256), jnp.bfloat16), ((rows,), jnp.float32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("pages", [64, 288])
def test_selected_latent_paged_decode_compiles_for_v5e(one_chip, pages):
    """The latent decode kernel under a SELECTION (learned sparse
    attention, ``ops/dsa_ops.py``) at the sparse-attention serving cell's
    shapes: 16 slots, 64 absorbed query heads over a 640-wide bfloat16
    row, page_len 64, the first bucket past ``index_topk`` rows and the
    widest of the 4608-page pool (8 pages a block, chunks of 512 rows);
    the int32 mask a slot is sliced a chunk at a time on its lanes."""
    import jax.numpy as jnp
    from paddle_tpu.ops import attention_ops as A
    S, NP, PL, H, W, V = 16, 4608, 64, 64, 640, 512
    assert A._paged_blocking(pages, PL, W, 2, True) == (8, 512)

    def fn(q, cache, pt, select):
        out = A._pallas_paged_attention(q, cache, None, pt,
                                        _ragged_lens(S, pages, PL), H,
                                        0.0625, interpret=False, v_width=V,
                                        select=select)
        assert out is not None, "shape gate refused the latent row"
        return out

    hlo = _compile(fn, one_chip, ((S, 1, H * W), jnp.bfloat16),
                   ((NP, PL, W), jnp.bfloat16), ((S, pages), jnp.int32),
                   ((S, 1, pages * PL), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_sparse_decode_indexer_and_selection_compile_for_v5e(one_chip):
    """The decode step's indexer and its exact top-2048 at the
    sparse-attention serving cell's widest bucket: 16 slots score 18432
    rows of a 128-lane bfloat16 key pool with 32 index heads; the
    selection's 32 counting passes and its cumulative sum need no sort
    and little scratch."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import dsa_ops
    S, NP, PL, P, Hi, Di, K = 16, 4608, 64, 288, 32, 128, 2048

    def fn(q, w, cache, pt, lens):
        rows = cache[pt].reshape(S, P * PL, Di)
        scores = dsa_ops.index_scores(q[:, None], rows, w[:, None])
        cols = jax.lax.broadcasted_iota(jnp.int32, (1, 1, P * PL), 2)
        return dsa_ops.select_mask(scores, cols < lens[:, :, None], K) \
            .astype(jnp.int32)

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    compiled = jax.jit(fn).lower(
        sds((S, Hi, Di), jnp.bfloat16), sds((S, Hi), jnp.float32),
        sds((NP, PL, Di), jnp.bfloat16), sds((S, P), jnp.int32),
        sds((S, 1), jnp.int32)).compile()
    assert "sort" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20, \
        compiled.memory_analysis()


@pytest.mark.parametrize("rows", [4096, 16384])
def test_sparse_prefill_selection_and_attention_compile_for_v5e(one_chip,
                                                                rows):
    """The prefill of a layer that holds an indexer at the
    sparse-attention serving cell's widths, its first bucket past
    ``index_topk`` rows and its widest: index scores of 32 heads x 128, the
    exact top-2048 a query row (int8 [T, T]), then latent attention
    expanded to 64 heads (keys 256 wide, values 256) under that selection
    in the select flash kernel (blocks of 512 x 512, the selection's int8
    block beside the keys'); everything fits beside the 7.4 GB the serving
    window holds."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import dsa_ops, mla_ops
    T, Hi, Di, K = rows, 32, 128, 2048

    def fn(qi, ki, wi, q, latent, w_kvb, mask):
        block = dsa_ops._query_block(T, dsa_ops.DSA_QUERY_BLOCK)
        part = lambda a, i: jax.lax.dynamic_slice_in_dim(a, i * block,
                                                         block, 0)
        scores = jax.lax.map(
            lambda i: dsa_ops.index_scores(part(qi, i), ki, part(wi, i)),
            jnp.arange(T // block)).reshape(T, T)
        select = dsa_ops.causal_select(scores, mask, K)
        return mla_ops.mla_attention(q, latent, w_kvb, mask, 64, 192, 64,
                                     256, 0.0625, select=select,
                                     flash=True, interpret=False)

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    compiled = jax.jit(fn).lower(
        sds((T, Hi, Di), jnp.bfloat16), sds((T, Di), jnp.bfloat16),
        sds((T, Hi), jnp.float32), sds((T, 64 * 256), jnp.bfloat16),
        sds((T, 640), jnp.bfloat16), sds((512, 64 * 448), jnp.bfloat16),
        sds((T,), jnp.float32)).compile()
    assert "sort" not in compiled.as_text()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 7 << 30, \
        compiled.memory_analysis()


def test_latent_prefill_attention_compiles_for_v5e_at_sparse_widths(one_chip):
    """The flash kernel at the sparse-attention configuration's head
    widths (keys 192 | 64 = 256, values 256) in its 2048-row bucket, where
    the selection is the identity and is skipped."""
    import jax.numpy as jnp
    from paddle_tpu.ops import mla_ops

    def fn(q, latent, w_kvb, mask):
        return mla_ops.mla_attention(q, latent, w_kvb, mask, 64, 192, 64,
                                     256, 0.0625, flash=True,
                                     interpret=False)

    hlo = _compile(fn, one_chip, ((2048, 64 * 256), jnp.bfloat16),
                   ((2048, 640), jnp.bfloat16),
                   ((512, 64 * 448), jnp.bfloat16), ((2048,), jnp.float32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("kind, pages", [("full", 64), ("full", 288),
                                         ("window", 0)])
def test_a_prefill_chunk_compiles_for_v5e_in_place(one_chip, kind, pages):
    """One layer of the CHUNK prefill at the published widths, 1024 rows
    at position ``start`` (traced): the projections, the partial rotary,
    then a full layer's rows into the slot's pages and the key-offset
    causal kernel over the page bucket's rows (the smallest bucket and
    the whole slot), or a window layer's ring rows led in front of the
    chunk, the banded kernel and the ring's update; pools and rings
    donated.  It holds NO copy of a projection matrix (PR 40's ``W_q``
    finding: ``rope_partial`` keeps its barrier) and no temporary of a
    pool's size: the gathered rows of ONE slot and the head-major copies
    around the kernel."""
    import re
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import window_ops
    C, S, PL = 1024, 32, 64
    n_kv, theta = (4, 5e6) if kind == "full" else (8, 1e4)

    def fn(h, wq, wk, wv, wo, pos, mask, kc, vc, where, sink):
        q, k = jnp.matmul(h, wq), jnp.matmul(h, wk)
        v = jnp.matmul(h, wv) * jnp.bfloat16(0.707)
        q = window_ops.rope_partial(q, pos, 64, 64, theta, 256)[0]
        k = window_ops.rope_partial(k, pos, n_kv, 64, theta, 256)[0]
        start, n = pos[0, 0], jnp.sum(mask > 0).astype(jnp.int32)
        if kind == "full":
            ctx, kc, vc = window_ops.chunk_over_pages(
                q, k, v[0], kc, vc, where, start, mask > 0, 64, n_kv,
                192 ** -0.5, interpret=False)
        else:
            ctx, kc, vc = window_ops.chunk_over_ring(
                q, k, v[0], sink, kc, vc, where[0, 0], start, n, 64, n_kv,
                192 ** -0.5, 128, interpret=False)
        return h + jnp.matmul(ctx, wo)[None], kc, vc

    sds = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    caches = [sds((S * 288, PL, n_kv * 256)), sds((S * 288, PL, n_kv * 128)),
              sds((1, pages), jnp.int32)] if kind == "full" else \
        [sds((S, 128, n_kv * 256)), sds((S, 128, n_kv * 128)),
         sds((1, 1), jnp.int32)]
    compiled = jax.jit(fn, donate_argnums=(7, 8)).lower(
        sds((1, C, 4096)), sds((4096, 64 * 192)), sds((4096, n_kv * 192)),
        sds((4096, n_kv * 128)), sds((64 * 128, 4096)),
        sds((1, C), jnp.int32), sds((1, C), jnp.float32), *caches,
        sds((64,), jnp.float32)).compile()
    hlo, memory = compiled.as_text(), compiled.memory_analysis()
    assert "tpu_custom_call" in hlo
    # in place: both caches alias their outputs
    assert memory.alias_size_in_bytes >= sum(
        int(jnp.prod(jnp.asarray(c.shape))) * 2 for c in caches[:2])
    matrices = {"4096,12288", "12288,4096", f"4096,{n_kv * 192}",
                "8192,4096", "4096,8192"}
    copied = [m for m in re.findall(r"= bf16\[([0-9,]+)\]\S* copy\(", hlo)
              if m in matrices]
    assert not copied, copied
    assert memory.temp_size_in_bytes < 160 << 20, memory


@pytest.mark.parametrize("pages, sparse", [(32, True), (128, True),
                                           (288, True), (64, False)])
def test_a_latent_prefill_chunk_compiles_for_v5e_in_place(one_chip, pages,
                                                          sparse):
    """The attention of ONE CHUNK of the latent builder's prefill, 1024
    rows at position ``start`` (traced), at the sparse-attention serving
    cell's widths (64 heads of 192 | 64, values 256, rows stored 640
    wide in pages of 64; the indexer's 32 heads x 128 and its exact
    top-2048 a query row over the page bucket's rows: the identity in the
    smallest bucket, the whole slot in the widest) and at the latent
    cell's without an indexer (heads of 128 | 64 laid out 256 wide,
    values 128, pages of 16): the chunk's rows into the slot's pages of
    both pools, then the key-offset flash kernel with every head its own
    K/V head, EXPANDED a (head, key block) at a time in the kernel from
    the cached rows, under the selection's int8 blocks.  Pools donated;
    no sort; no temporary of a pool's size and no K or V of the bucket
    (the gathered rows of ONE slot, ``W_kvb`` laid out for the kernel,
    the padded queries and the selection)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import dsa_ops, mla_ops
    from paddle_tpu.ops.attention_ops import _paged_cache_update
    C, S = 1024, 16
    nope, vd, PL = (192, 256, 64) if sparse else (128, 128, 16)
    T = pages * PL

    def fn(q, row, w_kvb, pool, table, pos, mask, qi, ki, wi, keys):
        start, select = pos[0, 0], None
        if sparse:
            keys, = _paged_cache_update(
                (keys,), (ki[None],), table, (start + C).reshape(1, 1),
                row_lens=mask > 0)
            if T > 2048:
                rows = keys[table[0]].reshape(T, 128)
                scores = dsa_ops._query_blocks(
                    lambda qb, wb: dsa_ops.index_scores(qb, rows, wb), C,
                    qi, wi)
                select = dsa_ops.causal_select(scores, mask[0], 2048,
                                               start=start)
        out, pool = mla_ops.mla_attention_chunk(
            q, row, w_kvb, pool, table, start, mask > 0, 64, nope, 64, vd,
            0.0625, select=select, interpret=False)
        return out, pool, keys

    sds = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    pools = [sds((S * 288, PL, 640)), sds((S * 288, PL, 128))]
    compiled = jax.jit(fn, donate_argnums=(3, 10)).lower(
        sds((C, 64 * (nope + 64))), sds((C, 640)),
        sds((512, 64 * (nope + vd))), pools[0], sds((1, pages), jnp.int32),
        sds((1, C), jnp.int32), sds((1, C), jnp.float32),
        sds((C, 32, 128)), sds((C, 128)), sds((C, 32), jnp.float32),
        pools[1]).compile()
    hlo, memory = compiled.as_text(), compiled.memory_analysis()
    assert "tpu_custom_call" in hlo and "sort" not in hlo
    # in place: both pools alias their outputs
    assert memory.alias_size_in_bytes >= sum(
        int(jnp.prod(jnp.asarray(p.shape))) * 2 for p in pools)
    assert memory.temp_size_in_bytes < 320 << 20, memory


# -- sliding-window / full attention at key heads of 192 (stored 256) and
# value heads of 128 (``ops/window_ops.py``; ``models/window_moe.py``) ------

@pytest.mark.parametrize("rows", [512, 16384])
@pytest.mark.parametrize("kind", ["band", "causal"])
def test_window_and_full_prefill_kernels_compile_for_v5e(one_chip, kind,
                                                         rows):
    """The banded kernel with its sink (8 K/V heads, 8 query heads each)
    and the causal grouped one (4 K/V heads, 16 each) in the smallest and
    the largest prompt bucket; at 16384 rows the head-major copies around
    a kernel are its temporaries: 1.07 GB by the compiler's account."""
    import functools
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import window_ops
    n_kv, window = (8, 128) if kind == "band" else (4, 0)
    assert window_ops.flash_blocks(rows, 64 // n_kv, window) is not None
    fn = functools.partial(window_ops.flash_attention, n_head=64,
                           n_kv_head=n_kv, scale=192 ** -0.5, window=window,
                           interpret=False)
    sds = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    args = [sds((rows, 64 * 256)), sds((rows, n_kv * 256)),
            sds((rows, n_kv * 128))]
    if window:
        args.append(sds((64,), jnp.float32))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1200 << 20, \
        compiled.memory_analysis()


@pytest.mark.parametrize("pages", [64, 288])
def test_paged_decode_with_narrower_value_heads_compiles_for_v5e(one_chip,
                                                                 pages):
    """A full layer's decode step: 64 query heads over 4 K/V heads, key
    heads stored 256 lanes wide beside value heads of 128, 32 slots."""
    import jax.numpy as jnp
    from paddle_tpu.ops import attention_ops as A
    S, PL = 32, 64

    def fn(q, kc, vc, pt, lens):
        out = A._pallas_paged_attention(q, kc, vc, pt, lens, 64,
                                        192 ** -0.5, interpret=False)
        assert out is not None, "the gate refused the published widths"
        assert out.shape == (S, 1, 64 * 128)
        return out

    hlo = _compile(fn, one_chip, ((S, 1, 64 * 256), jnp.bfloat16),
                   ((S * 288, PL, 4 * 256), jnp.bfloat16),
                   ((S * 288, PL, 4 * 128), jnp.bfloat16),
                   ((S, pages), jnp.int32), ((S, 1), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_window_decode_step_compiles_for_v5e_in_place(one_chip):
    """A window layer's decode step over its rings (32 slots x 128 rows,
    8 K/V heads of 256 | 128): the row's scatter and the ring kernel,
    rings donated: no copy of a ring, a handful of MB of temporaries."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import window_ops
    S = 32

    def fn(q, k, v, k_ring, v_ring, lens, sink):
        assert window_ops._ring_kernel_ok(q, k_ring, v_ring, 64, False)
        return window_ops.ring_step(q, k, v, k_ring, v_ring, lens, sink, 64,
                                    192 ** -0.5, 128, kernel=False)

    sds = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    compiled = jax.jit(fn, donate_argnums=(3, 4)).lower(
        sds((S, 64 * 256)), sds((S, 8 * 256)), sds((S, 8 * 128)),
        sds((S, 128, 8 * 256)), sds((S, 128, 8 * 128)),
        sds((S,), jnp.int32), sds((64,), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= S * 128 * 8 * (256 + 128) * 2
    assert memory.temp_size_in_bytes < 64 << 20, memory


def test_a_full_layers_decode_attention_copies_no_projection_matrix(one_chip):
    """One full layer of a decode step at the published widths: the q /
    k / v projections, the partial rotary, the pool's update, the paged
    kernel, the output projection.  Left to itself XLA served the
    rotary's lane slices by TRANSPOSING W_q, a 100 MB copy a layer every
    step (0.14-0.31 ms on the chip); ``rope_partial`` takes the projection's
    output behind an optimization barrier, and the step holds no
    temporary of a matrix's size."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import attention_ops as A, window_ops
    S = 32

    def fn(h, wq, wk, wv, wo, pos, kc, vc, pt, lens):
        q, k = jnp.matmul(h, wq), jnp.matmul(h, wk)
        v = jnp.matmul(h, wv) * jnp.bfloat16(0.707)
        q = window_ops.rope_partial(q, pos, 64, 64, 5e6, 256)
        k = window_ops.rope_partial(k, pos, 4, 64, 5e6, 256)
        kc, vc = A._paged_cache_update((kc, vc), (k, v), pt, lens, None)
        ctx = A._pallas_paged_attention(q, kc, vc, pt, lens, 64, 192 ** -0.5,
                                        interpret=False)
        return h + jnp.matmul(ctx, wo), kc, vc

    sds = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    compiled = jax.jit(fn, donate_argnums=(6, 7)).lower(
        sds((S, 1, 4096)), sds((4096, 64 * 192)), sds((4096, 4 * 192)),
        sds((4096, 4 * 128)), sds((64 * 128, 4096)), sds((S, 1), jnp.int32),
        sds((S * 288, 64, 4 * 256)), sds((S * 288, 64, 4 * 128)),
        sds((S, 288), jnp.int32), sds((S, 1), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20, \
        compiled.memory_analysis()


# ---------------------------------------------------------------------------
# chip_smoke.py's control flow at toy sizes on the CPU.  The script has no
# CPU mode; the test stubs the ONE function every chip-only assertion goes
# through, so a refactor of the phases cannot break the script unnoticed.
# ---------------------------------------------------------------------------

@pytest.fixture()
def smoke(monkeypatch):
    monkeypatch.syspath_prepend(REPO)
    import chip_smoke
    monkeypatch.setattr(chip_smoke, "expect_chip", lambda cond, what: None)
    import jax
    was = jax.config.jax_default_matmul_precision
    yield chip_smoke
    jax.config.update("jax_default_matmul_precision", was)  # phase 2 sets it


# dropout off: at a few dozen tokens its noise outweighs three windows of
# learning, and "the loss falls" is one of the checks being rehearsed
_TINY = dict(d_model=32, d_inner_hid=64, n_layer=1, n_head=2, d_key=16,
             d_value=16, src_vocab_size=128, trg_vocab_size=128,
             dropout=0.0)


def test_chip_smoke_phases_rehearse_on_cpu(smoke, capsys):
    # the "long" sub-phase at a shape the packed kernels' plan admits (128
    # tokens, two heads of 64), so the model builds the fused op of its
    # own accord and the kernels run (interpret mode here)
    from paddle_tpu.profiler import runtime_metrics
    packed0 = runtime_metrics.counter("attention.packed_kernel")
    smoke.phase_trainer(batch=2, seq=16, steps=3, calls=3, long_batch=1,
                        long_seq=128,
                        hp_overrides=dict(_TINY, d_model=128, d_key=64,
                                          d_value=64))
    # the windows (S 16) built the composed ops; the long build lowered
    # 3 + 3 fused ops, its use_flash=False twin none
    assert runtime_metrics.counter("attention.packed_kernel") == packed0 + 6
    smoke.phase_server(n_head=2, d_head=16, d_ffn=64, n_layer=1,
                       vocab_size=64, max_len=32, num_slots=2, page_len=8,
                       prompt_buckets=(8, 32), prompt_lens=(3, 12),
                       new_tokens=4)
    import json
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    phases = {l["phase"] for l in lines}
    assert {"trainer", "trainer_long", "flash_kernel", "server",
            "server_stream", "server_stats"} <= phases
    assert all(l["matches_reference"] for l in lines
               if l["phase"] == "server_stream")


def test_chip_smoke_refuses_the_cpu(monkeypatch):
    """No CPU mode: phase 0 fails before anything else is touched."""
    monkeypatch.syspath_prepend(REPO)
    import chip_smoke
    with pytest.raises(chip_smoke.SmokeFailure, match="no TPU"):
        chip_smoke.main([])


# -- two query rows a slot a turn (self-speculative decoding:
# ``ops/spec_ops.py``) at K-EXAONE's widths: 64 query heads over 8 K/V heads
# of 128, 32 slots ---------------------------------------------------------

@pytest.mark.parametrize("rows", [1, 2])
def test_ring_step_of_a_committed_row_and_a_draft_compiles_for_v5e(one_chip,
                                                                   rows):
    """A window layer's decode turn over rings of 256 rows (the window's
    128 + the draft's row, what tiles): the rows' scatter and the ring
    kernel with ``rows`` query rows a slot, each at its own position."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import window_ops
    S, R, H, n_kv, D = 32, 256, 64, 8, 128

    def fn(q, k, v, k_ring, v_ring, lens):
        if rows == 1:
            return window_ops.ring_step(
                q[:, 0], k[:, 0], v[:, 0], k_ring, v_ring, lens[:, 0], None,
                H, D ** -0.5, 128, kernel=False)
        return window_ops.ring_rows_step(q, k, v, k_ring, v_ring, lens, None,
                                         H, D ** -0.5, 128, kernel=False)

    sds = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    compiled = jax.jit(fn, donate_argnums=(3, 4)).lower(
        sds((S, rows, H * D)), sds((S, rows, n_kv * D)),
        sds((S, rows, n_kv * D)), sds((S, R, n_kv * D)),
        sds((S, R, n_kv * D)), sds((S, rows), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= 2 * S * R * n_kv * D * 2


@pytest.mark.parametrize("pages", [16, 96])
def test_paged_decode_of_a_committed_row_and_a_draft_compiles_for_v5e(
        one_chip, pages):
    """A full layer's decode turn: two query rows a slot, each under its
    own limit (the draft's row sees the committed one), 16 query rows to
    a K/V head in one product."""
    import jax.numpy as jnp
    from paddle_tpu.ops import attention_ops as A
    S, PL, H, n_kv, D = 32, 64, 64, 8, 128

    def fn(q, kc, vc, pt, lens, row_lens):
        out = A._pallas_paged_attention(q, kc, vc, pt, lens, H, D ** -0.5,
                                        interpret=False, row_lens=row_lens)
        assert out is not None, "the gate refused the published widths"
        return out

    hlo = _compile(fn, one_chip, ((S, 2, H * D), jnp.bfloat16),
                   ((S * 96, PL, n_kv * D), jnp.bfloat16),
                   ((S * 96, PL, n_kv * D), jnp.bfloat16),
                   ((S, pages), jnp.int32), ((S, 1), jnp.int32),
                   ((S, 2), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_kda_update_and_chunk_scan_compile_for_v5e_in_place(one_chip):
    """The KDA mixer's two forms at the linear-attention cell's widths (64
    slots, 64 heads of 128: a matrix state [64, 64, 128, 128] float32,
    268 MB a layer): the one-token update, as the Pallas kernel (Mosaic
    takes its lane slices and broadcasts) and as plain XLA, over the
    state aliased in place with no state-sized temporary; a chunk of the
    chunk-wise scan at the cell's two rungs (256 and 512 rows) as the
    Pallas kernel (Mosaic takes its column blocks of the 2-D activations,
    the sublane rolls of the running sum, the transposed product of the
    state's advance and four heads' blocks in VMEM) writes ONE slot's
    state in place with no temporary at all, and as plain XLA (what the
    gate's refusals run) keeps its pair-by-pair decays far under two
    state arrays."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import kda_ops
    S, H, D = 64, 64, 128
    bf, f32 = jnp.bfloat16, jnp.float32
    state_bytes = S * H * D * D * 4

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def prepared(qkv, f, b, a_log, dt_bias):
        return kda_ops.prepare(qkv, f, b, a_log, dt_bias, H, 2.0)

    def update(qkv, f, b, a_log, dt_bias, state, lens):
        o, new = kda_ops.kda_step(state, *prepared(qkv, f, b, a_log,
                                                   dt_bias))
        return o.astype(bf), jnp.where((lens > 0)[:, None, None, None],
                                       new, state)

    def kernel(qkv, f, b, a_log, dt_bias, state, lens):
        assert kda_ops.update_kernel_ok(state, False)
        o, new = kda_ops.kda_update_kernel(
            state, *prepared(qkv, f, b, a_log, dt_bias), lens,
            interpret=False)
        return o.astype(bf), new

    for fn in (kernel, update):
        compiled = jax.jit(fn, donate_argnums=5).lower(
            sds((S, 3 * H * D), bf), sds((S, H * D), f32), sds((S, H), f32),
            sds((H,), f32), sds((H * D,), f32), sds((S, H, D, D), f32),
            sds((S,), jnp.int32)).compile()
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes < 16 << 20, mem
        assert mem.alias_size_in_bytes >= state_bytes, mem
        assert ("tpu_custom_call" in compiled.as_text()) == (fn is kernel)

    def chunk(qkv, f, b, a_log, dt_bias, state, mask):
        o, last = kda_ops.kda_scan(*prepared(qkv, f, b, a_log, dt_bias),
                                   state[3], mask)
        return o.astype(bf), state.at[3].set(last)

    def chunk_kernel(qkv, f, b, a_log, dt_bias, state, slot, first, mask):
        assert kda_ops.scan_kernel_ok(qkv, state)
        return kda_ops.kda_scan_kernel(qkv, f, b, a_log, dt_bias, state,
                                       slot, first, mask, beta_scale=2.0,
                                       interpret=False)

    for T in (256, 512):
        compiled = jax.jit(chunk_kernel, donate_argnums=5).lower(
            sds((T, 3 * H * D), bf), sds((T, H * D), f32), sds((T, H), f32),
            sds((H,), f32), sds((H * D,), f32), sds((S, H, D, D), f32),
            sds((), jnp.int32), sds((), jnp.int32), sds((T,), f32)).compile()
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes < 1 << 20, mem
        assert mem.alias_size_in_bytes >= state_bytes, mem
        assert "tpu_custom_call" in compiled.as_text()

    T = 512
    mem = jax.jit(chunk, donate_argnums=5).lower(
        sds((T, 3 * H * D), bf), sds((T, H * D), f32), sds((T, H), f32),
        sds((H,), f32), sds((H * D,), f32), sds((S, H, D, D), f32),
        sds((T,), f32)).compile().memory_analysis()
    assert mem.temp_size_in_bytes < state_bytes, mem
    assert mem.alias_size_in_bytes >= state_bytes, mem


# -- the decoder-hybrid-decoder cell's ops at its published widths ----------
# (models/hybrid_decoder.py: 40 padded query heads of 128 lanes over 10 K/V
# pairs, 32 slots, pages of 64 rows, rings of 512, a Mamba-1 state [16,
# 5120] a slot)

def _hyd_sds(one_chip):
    import jax
    import jax.numpy as jnp
    return lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)


@pytest.mark.parametrize("form", ["update", "scan"])
def test_mamba1_update_and_chunk_scan_compile_for_v5e_in_place(one_chip,
                                                               form):
    """The selective scan's two forms over the per-slot state [32, 16,
    5120] float32 (10.5 MB a layer): the one-token update of every slot,
    and a 512-row chunk of ONE slot (the recurrence itself, 8 rows a trip
    of the loop); plain XLA both, the state aliased in place and no
    temporary of its size."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import ssm_ops
    sds = _hyd_sds(one_chip)
    S, CH, N, T = 32, 5120, 16, 512
    f32, i32 = jnp.float32, jnp.int32
    params = [sds((N, CH), f32), sds((CH,), f32), sds((CH,), f32)]

    def update(x, dt, b, c, a, d, bias, state, lens):
        return ssm_ops.mamba_update(x, dt, b, c, a, d, bias, state, lens > 0)

    def scan(x, dt, b, c, a, d, bias, states, mask, slot):
        held = jax.lax.dynamic_index_in_dim(states, slot, 0, keepdims=False)
        y, end = ssm_ops.mamba_scan(x, dt, b, c, a, d, bias, mask, held)
        return y, jax.lax.dynamic_update_index_in_dim(states, end, slot, 0)

    rows = S if form == "update" else T
    last = [sds((S,), i32)] if form == "update" \
        else [sds((T,), f32), sds((), i32)]
    compiled = jax.jit(update if form == "update" else scan,
                       donate_argnums=7).lower(
        sds((rows, CH)), sds((rows, CH), f32), sds((rows, N)),
        sds((rows, N)), *params, sds((S, N, CH), f32), *last).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= S * N * CH * 4, mem
    assert mem.temp_size_in_bytes < 8 << 20, mem
    assert "tpu_custom_call" not in compiled.as_text()


@pytest.mark.parametrize("slots, pages", [(32, 152), (1, 152)], ids=[
    "the_decode_steps_six_readers", "a_prompts_last_row"])
def test_paged_decode_of_four_query_heads_a_kv_head_compiles_for_v5e(
        one_chip, slots, pages):
    """Differential attention's padded queries: 40 heads of 128 lanes
    over 10 K/V heads, FOUR query rows to a K/V head.  Mosaic slices the
    kernel's query scratch by whole sublane tiles of 8, so the wrapper
    fills a group up with rows of zeros (refused before PR 54: 'cannot
    statically prove that index in dimension 0 is a multiple of 8')."""
    import jax.numpy as jnp
    from paddle_tpu.ops import attention_ops
    H, HKV, D, PL, NP = 40, 10, 128, 64, 32 * 152
    hlo = _compile(
        lambda q, kc, vc, pt, lens: attention_ops._pallas_paged_attention(
            q, kc, vc, pt, lens, H, 0.125, interpret=False), one_chip,
        ((slots, 1, H * D), jnp.bfloat16), ((NP, PL, HKV * D), jnp.bfloat16),
        ((NP, PL, HKV * D), jnp.bfloat16), ((slots, pages), jnp.int32),
        ((slots, 1), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("rows", [1, 256, 512])
def test_ring_attention_of_paired_heads_compiles_for_v5e_in_place(one_chip,
                                                                  rows):
    """A window layer of the same heads over rings of 512 rows: the decode
    step (``rows`` 1: the ring kernel) and a chunk of 256 rows (the
    composed form: no whole blocks) or 512 (the banded flash kernel),
    rings aliased in place."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import window_ops
    sds = _hyd_sds(one_chip)
    S, H, HKV, D, R = 32, 40, 10, 128, 512
    i32 = jnp.int32
    rings = [sds((S, R, HKV * D)), sds((S, R, HKV * D))]
    if rows == 1:
        fn = lambda q, k, v, kr, vr, lens: window_ops.ring_step(
            q, k, v, kr, vr, lens, None, H, 0.125, 512, kernel=False)
        args = [sds((S, H * D)), sds((S, HKV * D)), sds((S, HKV * D)),
                *rings, sds((S,), i32)]
    else:
        fn = lambda q, k, v, kr, vr, slot, start, n: \
            window_ops.chunk_over_ring(q, k, v, None, kr, vr, slot, start, n,
                                       H, HKV, 0.125, 512, interpret=False)
        args = [sds((rows, H * D)), sds((rows, HKV * D)),
                sds((rows, HKV * D)), *rings, sds((), i32), sds((), i32),
                sds((), i32)]
    compiled = jax.jit(fn, donate_argnums=(3, 4)).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * S * R * HKV * D * 2, mem
    assert ("tpu_custom_call" in compiled.as_text()) == (rows != 256)


# -- window layers of latent attention over a RING of latent rows beside full
# layers of 128 heads under 64 index heads (``ops/mla_ops.py``'s
# ``latent_window_*``; ``models/latent_moe.py`` with ``layer_types``) -------

@pytest.mark.parametrize("form", ["step", "chunk"])
def test_latent_ring_step_and_chunk_compile_for_v5e_in_place(one_chip, form):
    """A window layer of latent attention at the long-document serving
    cell's widths: 16 slots, a ring of 640 rows of 1152 lanes (1024
    latent | 64 rotary | 64 zeros), 64 absorbed query heads, a window of
    513.  The decode step: the row's scatter and the ring kernel with ONE
    ring that is key and, its leading 1024 lanes, value.  A chunk of 1024
    rows, EXPANDED: the ring's 512 lead rows, K and V of 64 heads from
    the 1536 rows, the banded flash kernel with every head its own K/V
    head (512 x 512 blocks read where the rows lie: no head-major copy,
    no composed ``[64, 1, 1024, 1536]`` float32 scores), the ring's
    update.  The ring is donated: no copy of it, and no temporary the
    size of every slot's."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import mla_ops
    S, R, W, L, H, C = 16, 640, 1152, 1024, 64, 1024
    i32 = jnp.int32
    sds = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    ring = sds((S, R, W))
    if form == "step":
        fn = lambda q, row, ring, lens: mla_ops.latent_ring_step(
            q, row, ring, lens, H, L, 1 / 16, 513, kernel=False)
        args, donate, limit = [sds((S, H * W)), sds((S, W)), ring,
                               sds((S,), i32)], 2, 16 << 20
    else:
        fn = lambda q, row, w, ring, slot, start, n: \
            mla_ops.latent_window_chunk(q, row, w, ring, slot, start, n, H,
                                        192, 64, 128, 1 / 16, 513,
                                        interpret=False)
        args, donate, limit = [sds((C, H * 256)), sds((C, W)),
                               sds((L, H * 320)), ring, sds((), i32),
                               sds((), i32), sds((), i32)], 3, 96 << 20
    compiled = jax.jit(fn, donate_argnums=(donate,)).lower(*args).compile()
    memory = compiled.memory_analysis()
    assert "tpu_custom_call" in compiled.as_text()
    assert "f32[64,1,1024," not in compiled.as_text()
    assert memory.alias_size_in_bytes >= S * R * W * 2, memory
    assert memory.temp_size_in_bytes < limit, memory


@pytest.mark.parametrize("pages", [64, 288])
def test_latent_decode_of_128_heads_under_64_index_heads_compiles_for_v5e(
        one_chip, pages):
    """A full layer of the same cell: 128 absorbed query heads over the
    640-wide row under a selection (twice the sparse-attention cell's
    heads), and the decode step's indexer of 64 heads with its exact
    top-2048 over the widest bucket."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import attention_ops as A, dsa_ops
    S, NP, PL, H, W, V = 16, 4608, 64, 128, 640, 512

    def fn(q, cache, pt, select):
        out = A._pallas_paged_attention(q, cache, None, pt,
                                        _ragged_lens(S, pages, PL), H,
                                        192 ** -0.5, interpret=False,
                                        v_width=V, select=select)
        assert out is not None, "shape gate refused the latent row"
        return out

    hlo = _compile(fn, one_chip, ((S, 1, H * W), jnp.bfloat16),
                   ((NP, PL, W), jnp.bfloat16), ((S, pages), jnp.int32),
                   ((S, 1, pages * PL), jnp.int32))
    assert "tpu_custom_call" in hlo
    if pages != 288:
        return
    Hi, Di, K = 64, 128, 2048

    def index(q, w, cache, pt, lens):
        rows = cache[pt].reshape(S, pages * PL, Di)
        scores = dsa_ops.index_scores(q[:, None], rows, w[:, None])
        cols = jax.lax.broadcasted_iota(jnp.int32, (1, 1, pages * PL), 2)
        return dsa_ops.select_mask(scores, cols < lens[:, :, None], K) \
            .astype(jnp.int32)

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    compiled = jax.jit(index).lower(
        sds((S, Hi, Di), jnp.bfloat16), sds((S, Hi), jnp.float32),
        sds((NP, PL, Di), jnp.bfloat16), sds((S, pages), jnp.int32),
        sds((S, 1), jnp.int32)).compile()
    assert "sort" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20, \
        compiled.memory_analysis()


def test_a_full_layers_chunk_of_128_heads_compiles_for_v5e_in_place(one_chip):
    """A full layer's chunk of the same cell, 1024 rows over the widest
    page bucket (288 pages of 64 rows): 128 heads of 128 | 64, each its
    own K/V head, expanded in the kernel from the 640-wide row as
    cached, the indexer's 64 heads x 128 and its exact top-2048 a query
    row.  Pools donated; no sort; no K or V of the bucket (1.8 GB if
    made whole)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import dsa_ops, mla_ops
    from paddle_tpu.ops.attention_ops import _paged_cache_update
    C, S, PL, pages = 1024, 16, 64, 288
    T = pages * PL

    def fn(q, row, w_kvb, pool, table, pos, mask, qi, ki, wi, keys):
        start = pos[0, 0]
        keys, = _paged_cache_update(
            (keys,), (ki[None],), table, (start + C).reshape(1, 1),
            row_lens=mask > 0)
        rows = keys[table[0]].reshape(T, 128)
        scores = dsa_ops._query_blocks(
            lambda qb, wb: dsa_ops.index_scores(qb, rows, wb), C, qi, wi)
        select = dsa_ops.causal_select(scores, mask[0], 2048, start=start)
        out, pool = mla_ops.mla_attention_chunk(
            q, row, w_kvb, pool, table, start, mask > 0, 128, 128, 64, 128,
            192 ** -0.5, select=select, interpret=False)
        return out, pool, keys

    sds = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    pools = [sds((S * 288, PL, 640)), sds((S * 288, PL, 128))]
    compiled = jax.jit(fn, donate_argnums=(3, 10)).lower(
        sds((C, 128 * 192)), sds((C, 640)), sds((512, 128 * 256)), pools[0],
        sds((1, pages), jnp.int32), sds((1, C), jnp.int32),
        sds((1, C), jnp.float32), sds((C, 64, 128)), sds((C, 128)),
        sds((C, 64), jnp.float32), pools[1]).compile()
    hlo, memory = compiled.as_text(), compiled.memory_analysis()
    assert "tpu_custom_call" in hlo and "sort" not in hlo
    assert memory.alias_size_in_bytes >= sum(
        int(jnp.prod(jnp.asarray(p.shape))) * 2 for p in pools)
    assert memory.temp_size_in_bytes < 384 << 20, memory


@pytest.mark.parametrize("pages", [32, 288])
def test_latent_decode_of_a_committed_row_and_a_draft_compiles_for_v5e(
        one_chip, pages):
    """A latent layer's decode TURN at the drafting latent cell's shapes:
    32 slots x two query rows, each under its own limit (the draft's row
    sees the committed one), 2 x 32 absorbed heads side by side against
    the ONE 640-wide bfloat16 row a token; page_len 64, the smallest and
    the widest page bucket."""
    import jax.numpy as jnp
    from paddle_tpu.ops import attention_ops as A
    S, PL, H, W, V, L = 32, 64, 32, 640, 512, 2

    def fn(q, cache, pt, walk, row_lens):
        out = A._pallas_latent_rows(q, cache, pt, walk, H, 0.1447, row_lens,
                                    False, V)
        assert out is not None, "the gate refused the published widths"
        return out

    hlo = _compile(fn, one_chip, ((S, L, H * W), jnp.bfloat16),
                   ((S * 288, PL, W), jnp.bfloat16), ((S, pages), jnp.int32),
                   ((S, 1), jnp.int32), ((S, L), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("rows", [64, 1024], ids=["turn", "chunk"])
def test_a_wrapper_lowers_to_the_sinkhorn_kernel_and_a_few_fusions(one_chip,
                                                                   rows):
    """A hyper-connection wrapper at the published widths (4 streams of
    3584, 20 Sinkhorn rounds) around a sublayer that does nothing: the
    chain of rounds is ONE Pallas kernel, and what stands around it (the
    statistic, the product with phi, the aggregate, the distribution) a
    stated few fusions (13, the kernel, and the two layout copies of
    this test's own entry and exit): a decode turn's 20 wrappers are
    under 300 launches, not the 1,600 and more of a round a launch."""
    import jax.numpy as jnp
    from paddle_tpu.ops import mhc_ops
    n, c = 4, 3584

    def fn(x, phi, alpha, bias):
        u, post, res = mhc_ops.mhc_pre(x, phi, alpha, bias, 20, 1e-6, -30.0,
                                       30.0, 1e-6, kernel=True)
        return mhc_ops.mhc_post(x, u, post, res)

    hlo = _compile(fn, one_chip, ((rows, n, c), jnp.bfloat16),
                   ((n * c, n * (n + 2)), jnp.float32), ((3,), jnp.float32),
                   ((n * (n + 2),), jnp.float32))
    assert hlo.count("tpu_custom_call") >= 1
    body = hlo[hlo.index("ENTRY"):]
    launches = sum(body.count(f" {kind}(") for kind in (
        "fusion", "custom-call", "convolution", "dot", "copy", "transpose"))
    assert launches <= 16, launches
    assert " while(" not in body


@pytest.mark.parametrize("pages", [32, 288])
def test_a_latent_chunk_of_32_heads_in_pages_of_64_compiles_for_v5e(one_chip,
                                                                    pages):
    """The attention of one 1024-row chunk at the four-stream latent
    cell's widths (32 heads of 128 | 64, values 128, rows stored 640 wide
    in pages of 64, 32 slots of 288 pages): the rows into the slot's
    pages, then the key-offset flash kernel EXPANDED a (head, key block)
    at a time; the pool donated and aliased, no temporary of its size."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import mla_ops
    C, S, PL, H = 1024, 32, 64, 32

    def fn(q, row, w_kvb, pool, table, pos, mask):
        return mla_ops.mla_attention_chunk(
            q, row, w_kvb, pool, table, pos[0, 0], mask > 0, H, 128, 64,
            128, 0.1447, interpret=False)

    sds = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    pool = sds((S * 288, PL, 640))
    compiled = jax.jit(fn, donate_argnums=(3,)).lower(
        sds((C, H * 192)), sds((C, 640)), sds((512, H * 256)), pool,
        sds((1, pages), jnp.int32), sds((1, C), jnp.int32),
        sds((1, C), jnp.float32)).compile()
    hlo, memory = compiled.as_text(), compiled.memory_analysis()
    assert "tpu_custom_call" in hlo
    assert memory.alias_size_in_bytes >= S * 288 * PL * 640 * 2
    assert memory.temp_size_in_bytes < 320 << 20, memory
