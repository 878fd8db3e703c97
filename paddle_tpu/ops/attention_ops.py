"""Fused scaled-dot-product attention with Pallas TPU kernels.

The reference composes attention from mul/softmax/matmul graph ops
(``python/paddle/fluid/nets.py`` scaled_dot_product_attention;
``test_parallel_executor.py`` transformer).  On TPU the [B,H,S,S] score
tensor is the HBM-bandwidth hot spot, so the fused op runs
QK^T -> mask -> softmax -> AV with the scores only ever in VMEM.

An op gets one of THREE lowerings, and ``attention_lowering`` alone says
which, from what it can observe: the operands' shapes, ``n_head``,
``causal``, the op's ``use_flash``, the step's mesh and batch axis, TPU
or interpret.  Shape inference (the shape of ``Lse``), the forward and
the grad lowering, ``fused_attention`` and the model's gate
(``models.transformer.multi_head_attention``) ask it; none derives the
answer again.

  * ``packed``: PACKED operands ``[B, S, H*D]`` with an ``n_head``
    attribute, as the transformer's projections emit them, S a multiple
    of 128 up to 1024, ``S_q == S_k``: the packed single-pass kernels of
    ``attention_packed`` (no head transposes, lane-dense side arrays;
    counted by ``attention.packed_kernel``).
  * ``streaming``: ``[B, H, S, D]`` operands, and packed ones the packed
    kernels refuse (unpacked and packed again in ``_layout``), at every
    length ``_flash_blocks`` can tile.  K/V stream through VMEM one block
    at a time with an online softmax (VMEM use independent of sequence
    length); the backward runs as two kernels (dq; dk+dv) from the saved
    residual, fully masked causal blocks skipped.  Their residual
    ``[B, H, S, 2]``, mask ``[B, 1, S]`` and delta ``[B, H, S, 1]`` are
    padded 64-128x by the (8, 128) tiling (134 MB an array at
    B32 x S1024 x H8): what the packed kernels were written to avoid.
    ``ops/mla_ops.py``'s training prefill calls the forward, one
    sequence a call.
  * ``reference``: plain XLA (``_reference_attention``) where
    ``use_flash`` is off, no kernel tiles the lengths, or the mesh does
    not fit; counted by ``attention.flash_fallback`` where a kernel was
    asked for.

Measured on a v5e (``bench_attention.py`` -> ``BENCH_ATTENTION.md``, one
module forward + backward, bf16, H8 x D64, operands and result in the
projections' layout so the ``[B, H, S, D]`` path pays its transposes;
my chip run, PR 39): B32 x S1024 packed 2.79 ms (causal 2.13), composed
XLA 10.19; B64 x S512 1.51 / 5.20; B256 x S256 1.89 / 5.56; above
S 1024 the streaming kernels 8.09 ms against 19.61 composed at
B16 x S2048.  The model builds this op wherever the packed kernels take
the shapes or the keys are ``FUSED_MIN_KEYS`` or longer, and the
composed ops elsewhere: no name in the environment chooses.

On a mesh (``ctx.aux["mesh"]``, the ``ParallelExecutor``'s) every kernel
call runs PER SHARD of the batch over the ``data`` axis (``_per_shard``):
the partitioner has no rule for a ``tpu_custom_call`` (Mosaic refuses to
lower one it would have to split), and a replicated call would hand each
chip the gathered global batch.  A mesh the batch does not fit
(``_kernels_fit``) takes the reference.

Masking model (matches the transformer workloads):
  * ``k_mask`` [B, S_k] with 1 = attend / 0 = padding, optional;
  * ``causal`` flag for decoder self-attention.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from paddle_tpu.ops import attention_packed
from paddle_tpu.ops.attention_packed import NEG_INF
from paddle_tpu.ops.registry import (
    register_op, LowerContext, ShapeInferenceSkip, infer_shape_unary)


def _reference_attention(q, k, v, k_mask, causal, scale):
    """Plain-XLA attention; also the vjp path for the Pallas forward.

    Dtype-stable: scores/softmax in f32, output in ``q.dtype`` — so the
    fallback path and the Pallas kernel (out dtype = q.dtype) agree, and
    vjp cotangents always match the forward output dtype (bf16 under AMP).
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if k_mask is not None:
        s = s + (1.0 - k_mask[:, None, None, :].astype(jnp.float32)) \
            * NEG_INF
    if causal:
        S_q, S_k = q.shape[2], k.shape[2]
        row = jax.lax.broadcasted_iota(jnp.int32, (S_q, S_k), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (S_q, S_k), 1)
        s = s + jnp.where(col > row, NEG_INF, 0.0)[None, None]
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_M_INIT = -1e30


def _causal_bias(i, j, block_q, block_k):
    row = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) \
        + i * block_q
    col = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) \
        + j * block_k
    return jnp.where(col > row, NEG_INF, 0.0)


def _block_scores(q, k, mask, scale, causal, i, j, block_q, block_k):
    """f32 [Bq, Bk] masked scaled scores for q block i vs k block j."""
    s = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    s = s + (1.0 - mask.astype(jnp.float32))[None, :] * NEG_INF
    if causal:
        s = s + _causal_bias(i, j, block_q, block_k)
    return s


def _flash_fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                      acc, m_scr, l_scr, *, causal, scale, block_q,
                      block_k):
    """Online-softmax forward: K/V stream through VMEM one [Bk, D] block
    per grid step (sequential innermost axis), so VMEM use is O(Bq*Bk) —
    independent of sequence length."""
    i = pl.program_id(2)
    j = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, _M_INIT)
        l_scr[...] = jnp.zeros_like(l_scr)

    # causal: blocks entirely above the diagonal contribute nothing —
    # skip their MXU work (roughly halves the causal grid's compute)
    live = (j * block_k <= (i + 1) * block_q - 1) if causal else True

    @pl.when(live)
    def _():
        q = q_ref[0, 0]                   # [Bq, D]
        k = k_ref[0, 0]                   # [Bk, D]
        v = v_ref[0, 0]                   # [Bk, Dv]
        s = _block_scores(q, k, mask_ref[0, 0], scale, causal, i, j,
                          block_q, block_k)
        m_prev = m_scr[...]               # [Bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)            # [Bq, Bk] f32
        alpha = jnp.exp(m_prev - m_new)   # [Bq, 1]
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc[...] = acc[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == nk - 1)
    def _():
        # l > 0 always: each row's running max contributes exp(0) = 1, and
        # fully-masked rows softmax over the -1e9-shifted scores exactly
        # like _reference_attention
        l = l_scr[...]
        o_ref[0, 0] = (acc[...] / l).astype(o_ref.dtype)
        # residual saved as (m, log l) SEPARATELY: on fully-masked rows
        # m ~ -1e9 and fl(m + log l) == m in f32 (ulp(1e9) = 64), which
        # would make bwd's p = exp(s - lse) = 1 per entry instead of 1/n
        lse_ref[0, 0] = jnp.concatenate([m_scr[...], jnp.log(l)], axis=1)


def _flash_dkdv_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref,
                       delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                       causal, scale, block_q, block_k):
    """One (b, h, k-block); inner sequential axis streams q blocks."""
    i = pl.program_id(3)
    j = pl.program_id(2)
    nq = pl.num_programs(3)

    @pl.when(i == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    live = (j * block_k <= (i + 1) * block_q - 1) if causal else True

    @pl.when(live)
    def _():
        q = q_ref[0, 0]                   # [Bq, D]
        k = k_ref[0, 0]                   # [Bk, D]
        v = v_ref[0, 0]                   # [Bk, Dv]
        do = do_ref[0, 0]                 # [Bq, Dv]
        m = lse_ref[0, 0][:, 0:1]         # [Bq, 1]
        logl = lse_ref[0, 0][:, 1:2]      # [Bq, 1]
        delta = delta_ref[0, 0]           # [Bq, 1]
        s = _block_scores(q, k, mask_ref[0, 0], scale, causal, i, j,
                          block_q, block_k)
        # (s - m) first so the +-1e9 magnitudes cancel exactly, THEN the
        # O(1) log-denominator — true softmax probs, f32
        p = jnp.exp((s - m) - logl)
        # dv += p^T @ do
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # dp = do @ v^T ; ds = p * (dp - delta) * scale
        dp = jax.lax.dot_general(
            do, v, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        # dk += ds^T @ q
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(i == nq - 1)
    def _():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_dq_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref,
                     delta_ref, dq_ref, dq_acc, *, causal, scale, block_q,
                     block_k):
    """One (b, h, q-block); inner sequential axis streams k blocks."""
    i = pl.program_id(2)
    j = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    live = (j * block_k <= (i + 1) * block_q - 1) if causal else True

    @pl.when(live)
    def _():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        m = lse_ref[0, 0][:, 0:1]         # [Bq, 1]
        logl = lse_ref[0, 0][:, 1:2]      # [Bq, 1]
        delta = delta_ref[0, 0]           # [Bq, 1]
        s = _block_scores(q, k, mask_ref[0, 0], scale, causal, i, j,
                          block_q, block_k)
        p = jnp.exp((s - m) - logl)
        dp = jax.lax.dot_general(
            do, v, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


def _pick_block(s, prefer=None):
    """Largest block size tiling ``s`` evenly (TPU wants the sublane dim a
    multiple of 8); None = no even tiling -> use the reference path.
    1024-blocks are the measured VMEM sweet spot (see _flash_blocks);
    a full-array block up to 1024 is the last resort."""
    if prefer is None:
        prefer = _BLOCK_PREFER
    for cand in prefer:
        if s % cand == 0:
            return cand
    return s if s <= 1024 else None  # full-array block as last resort


_BLOCK_PREFER = (1024, 512, 256, 128, 64, 32, 16, 8)


def _flash_blocks(S_q, S_k, interpret=False):
    # 1024-first: measured on v5e (fwd+bwd causal bf16, 64k tokens) —
    # (1024,1024) beats the old (256,512) by 27-30% at S>=2048 (smaller
    # S picks its own full-array block); (2048,2048) exceeds VMEM.
    block_q = _pick_block(S_q)
    block_k = _pick_block(S_k)
    if not interpret:
        # real TPU lowering: a block's last dim must be a multiple of 128
        # or equal to the array dim (the mask block's last dim is block_k)
        if block_k is not None and block_k % 128 and block_k != S_k:
            block_k = None
        if block_q is not None and block_q % 8 and block_q != S_q:
            block_q = None
    return block_q, block_k


def _pallas_attention(q, k, v, k_mask, causal, scale, interpret=False,
                      blocks=None):
    """The streaming forward on ``[B, H, S, D]``.  Returns (out, res);
    res [B,H,S_q,2] packs the softmax running max and log-denominator,
    the residual consumed by the flash backward.  None where
    ``_flash_blocks`` (asked here if the caller brings no ``blocks``)
    cannot tile the lengths."""
    B, H, S_q, D_k = q.shape
    S_k = k.shape[2]
    D_v = v.shape[3]
    block_q, block_k = blocks or _flash_blocks(S_q, S_k, interpret)
    if block_q is None or block_k is None:
        return None
    grid = (B, H, S_q // block_q, S_k // block_k)
    kernel = functools.partial(_flash_fwd_kernel, causal=causal,
                               scale=scale, block_q=block_q,
                               block_k=block_k)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D_k),
                         lambda b, h, i, j: (b, h, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_k, D_k),
                         lambda b, h, i, j: (b, h, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_k, D_v),
                         lambda b, h, i, j: (b, h, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_k), lambda b, h, i, j: (b, 0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D_v),
                         lambda b, h, i, j: (b, h, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q, 2),
                         lambda b, h, i, j: (b, h, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S_q, D_v), q.dtype),
            jax.ShapeDtypeStruct((B, H, S_q, 2), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D_v), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, k_mask[:, None, :])
    return out, lse


def _pallas_attention_bwd(q, k, v, k_mask, o, res, g, causal, scale,
                          interpret=False, blocks=None):
    B, H, S_q, D_k = q.shape
    S_k = k.shape[2]
    D_v = v.shape[3]
    block_q, block_k = blocks or _flash_blocks(S_q, S_k, interpret)
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)        # [B, H, S_q, 1]
    mask3 = k_mask[:, None, :]

    common_in = [q, k, v, mask3, g, res, delta]
    in_specs = [
        pl.BlockSpec((1, 1, block_q, D_k), lambda b, h, i, j: (b, h, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, block_k, D_k), lambda b, h, i, j: (b, h, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, block_k, D_v), lambda b, h, i, j: (b, h, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, block_k), lambda b, h, i, j: (b, 0, j),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, block_q, D_v), lambda b, h, i, j: (b, h, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, block_q, 2), lambda b, h, i, j: (b, h, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i, j: (b, h, i, 0),
                     memory_space=pltpu.VMEM),
    ]

    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k),
        grid=(B, H, S_q // block_q, S_k // block_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, block_q, D_k),
                               lambda b, h, i, j: (b, h, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D_k), jnp.float32)],
        interpret=interpret,
    )(*common_in)

    # grid axes 2/3 swap roles: k-block outer, q-block inner (sequential)
    in_specs_kv = [
        pl.BlockSpec((1, 1, block_q, D_k), lambda b, h, j, i: (b, h, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, block_k, D_k), lambda b, h, j, i: (b, h, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, block_k, D_v), lambda b, h, j, i: (b, h, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, block_k), lambda b, h, j, i: (b, 0, j),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, block_q, D_v), lambda b, h, j, i: (b, h, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, block_q, 2), lambda b, h, j, i: (b, h, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, block_q, 1), lambda b, h, j, i: (b, h, i, 0),
                     memory_space=pltpu.VMEM),
    ]
    dk, dv = pl.pallas_call(
        functools.partial(_flash_dkdv_kernel, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k),
        grid=(B, H, S_k // block_k, S_q // block_q),
        in_specs=in_specs_kv,
        out_specs=[
            pl.BlockSpec((1, 1, block_k, D_k),
                         lambda b, h, j, i: (b, h, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_k, D_v),
                         lambda b, h, j, i: (b, h, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D_k), jnp.float32),
            pltpu.VMEM((block_k, D_v), jnp.float32),
        ],
        interpret=interpret,
    )(*common_in)
    return dq, dk, dv


def _use_interpret():
    return not any(d.platform == "tpu" for d in jax.devices())


def _count_flash_fallback():
    """A flash-requested attention lowered as the plain-XLA reference:
    ``_flash_blocks`` refused its lengths or its batch does not fit the
    mesh (fires at trace time, once per compiled signature)."""
    from paddle_tpu.profiler import runtime_metrics
    runtime_metrics.inc("attention.flash_fallback")


def _count_packed_kernel():
    """An attention op (forward or grad) lowered to the packed
    ``[B, S, H*D]`` kernels (fires at trace time, once per op per
    compiled signature).  A step built for them that reads 0 has taken
    the ``[B, H, S, D]`` path in silence."""
    from paddle_tpu.profiler import runtime_metrics
    runtime_metrics.inc("attention.packed_kernel")


def _unpack_heads(x, n_head):
    """[B, S, H*D] -> [B, H, S, D]"""
    B, S, HD = x.shape
    return x.reshape(B, S, n_head, HD // n_head).transpose(0, 2, 1, 3)


def _pack_heads(x):
    """[B, H, S, D] -> [B, S, H*D]"""
    B, H, S, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B, S, H * D)


def _kernels_fit(mesh, batch, batch_axis=0):
    """Whether a kernel can run in a step jitted over ``mesh``.  Mosaic
    refuses a kernel the partitioner would have to split, so on more than
    one device every call sits in a ``shard_map`` over the WHOLE mesh
    (``_per_shard``), which cuts the batch over ``data``.  That fits where
    ``data`` is the only populated axis and cuts the operands' leading
    dimension evenly.  Elsewhere (``model`` / ``seq`` / ``pipe``
    populated: a kernel would need the features or the rows regathered;
    a batch the axis does not divide, or an executor that shards another
    dimension than the first: it would need the GLOBAL batch on every
    chip) the op takes ``_reference_attention``, which the partitioner
    can split any way."""
    from paddle_tpu.parallel.mesh import DATA_AXIS
    if mesh is None or mesh.size == 1:
        return True
    n = mesh.shape.get(DATA_AXIS, 1)
    return n == mesh.size and batch_axis == 0 and batch % n == 0


PACKED, STREAMING, REFERENCE = "packed", "streaming", "reference"
# The keys from which this op beats the composed ops on a shape the
# packed kernels refuse (unequal lengths, S > 1024, odd head widths): the
# rule the streaming kernels were measured under (``BENCH_ATTENTION.md``).
FUSED_MIN_KEYS = 512


class Lowering(NamedTuple):
    kind: str               # PACKED | STREAMING | REFERENCE
    blocks: tuple | None    # the chosen kernels' blocking
    # what a model that can still build the composed ops asks: True where
    # the packed kernels take the shapes or the keys are FUSED_MIN_KEYS or
    # longer; short or odd lengths and narrow heads read False (XLA folds
    # the transposes into the projection matmuls and the [S, S] round
    # trip is cheap)
    beats_composed: bool


def attention_lowering(q_shape, k_shape, v_shape, n_head=None, causal=False,
                       use_flash=True, mesh=None, batch_axis=0,
                       interpret=False):
    """THE choice of lowering for a ``scaled_dot_product_attention`` on
    ``[B, H, S, D]`` operands, or PACKED ``[B, S, H*D]`` ones with
    ``n_head``.  ``mesh`` / ``batch_axis``: those of the step being
    lowered, if any.  ``interpret``: the kernels will run interpreted
    (any block tiles); a caller that builds a program and cannot know
    leaves the chip's rule in force."""
    q_shape, k_shape, v_shape = map(tuple, (q_shape, k_shape, v_shape))
    packed = attention_packed.plan(q_shape, k_shape, v_shape, n_head,
                                   causal)
    beats = packed is not None or k_shape[-2] >= FUSED_MIN_KEYS
    if not use_flash or not _kernels_fit(mesh, q_shape[0], batch_axis):
        return Lowering(REFERENCE, None, beats)
    if packed is not None:
        return Lowering(PACKED, packed, beats)
    blocks = _flash_blocks(q_shape[-2], k_shape[-2], interpret)
    if None in blocks:
        return Lowering(REFERENCE, None, beats)
    return Lowering(STREAMING, blocks, beats)


def _layout(n_head, kind):
    """``(to_kernel, from_kernel)``: only the packed kernels take PACKED
    operands (``n_head`` set) as they are; under the other two lowerings
    a packed op runs on ``[B, H, S, D]`` and its results are packed
    again."""
    if n_head and kind != PACKED:
        return functools.partial(_unpack_heads, n_head=n_head), _pack_heads
    return (lambda x: x), (lambda x: x)


def _per_shard(kernel, mesh, *arrays):
    """``kernel(*arrays)``, every array and every result cut over the
    mesh's ``data`` axis on its leading (batch) dimension: each chip runs
    the Pallas call on its own rows, nothing is gathered and nothing
    reduced (attention never mixes batch rows).  With no mesh, or one of
    a single device, this is the plain call, not a ``shard_map`` over one
    device.  ``attention_lowering`` has asked ``_kernels_fit``."""
    if mesh is None or mesh.size == 1:
        return kernel(*arrays)
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.parallel.mesh import DATA_AXIS
    rows = P(DATA_AXIS)
    # check_vma: a pallas_call has no rule for the varying-axes check
    return shard_map(kernel, mesh=mesh, in_specs=rows, out_specs=rows,
                     check_vma=False)(*arrays)


def _attention_forward(q, k, v, k_mask, causal, scale, n_head, use_flash,
                       mesh, batch_axis=0):
    """``(out, res)`` by the lowering ``attention_lowering`` chooses: the
    kernel's forward per shard of the batch and the residual its backward
    reads, or the reference and None.  ``out`` is in the operands' own
    layout."""
    interpret = _use_interpret()
    kind, blocks, _ = attention_lowering(
        q.shape, k.shape, v.shape, n_head, causal, use_flash, mesh,
        batch_axis, interpret)
    to_kernel, from_kernel = _layout(n_head, kind)
    q, k, v = to_kernel(q), to_kernel(k), to_kernel(v)
    if kind == REFERENCE:
        if use_flash:
            _count_flash_fallback()
        return from_kernel(_reference_attention(q, k, v, k_mask, causal,
                                                scale)), None
    if kind == PACKED:
        _count_packed_kernel()

        def kernel(q, k, v, mask):
            return attention_packed.attention(
                q, k, v, mask, causal, scale, n_head, blocks,
                interpret=interpret)
    else:
        def kernel(q, k, v, mask):
            return _pallas_attention(q, k, v, mask, causal, scale,
                                     interpret, blocks)
    out, res = _per_shard(kernel, mesh, q, k, v, k_mask)
    return from_kernel(out), res


def _attention_backward(q, k, v, k_mask, o, res, g, causal, scale, n_head,
                        use_flash, mesh, batch_axis=0):
    """dq, dk, dv in the operands' layout, from what ``_attention_forward``
    handed back: the chosen kernel's backward from the saved output and
    residual, on the same shards; the reference's ``vjp`` where it saved
    none (``res`` None)."""
    interpret = _use_interpret()
    kind, blocks, _ = attention_lowering(
        q.shape, k.shape, v.shape, n_head, causal, use_flash, mesh,
        batch_axis, interpret)
    if res is None:     # nothing saved: the forward was the reference's
        kind = REFERENCE
    to_kernel, from_kernel = _layout(n_head, kind)
    q, k, v, g = (to_kernel(x) for x in (q, k, v, g.astype(q.dtype)))
    if kind == REFERENCE:
        _, vjp_fn = jax.vjp(
            lambda q_, k_, v_: _reference_attention(q_, k_, v_, k_mask,
                                                    causal, scale),
            q, k, v)
        return tuple(from_kernel(x) for x in vjp_fn(g))
    if kind == PACKED:
        _count_packed_kernel()

        def kernel(q, k, v, mask, o, res, g):
            return attention_packed.attention_bwd(
                q, k, v, mask, o, res, g, causal, scale, n_head, blocks,
                interpret=interpret)
    else:
        def kernel(q, k, v, mask, o, res, g):
            return _pallas_attention_bwd(q, k, v, mask, o, res, g, causal,
                                         scale, interpret, blocks)
    grads = _per_shard(kernel, mesh, q, k, v, k_mask, to_kernel(o), res, g)
    return tuple(from_kernel(x) for x in grads)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def fused_attention(q, k, v, k_mask, causal, scale, use_pallas,
                    n_head=None, mesh=None):
    """Differentiable fused attention.  ``q, k, v`` are ``[B, H, S, D]``,
    or PACKED ``[B, S, H*D]`` with ``n_head`` given (the output is then
    packed too).  ``mesh``: the mesh of the jitted step this is traced
    in, if any.  ``attention_lowering`` says which lowering runs."""
    return _fused_fwd(q, k, v, k_mask, causal, scale, use_pallas, n_head,
                      mesh)[0]


def _fused_fwd(q, k, v, k_mask, causal, scale, use_pallas, n_head, mesh):
    out, res = _attention_forward(q, k, v, k_mask, causal, scale, n_head,
                                  use_pallas, mesh)
    return out, (q, k, v, k_mask, out, res)


def _fused_bwd(causal, scale, use_pallas, n_head, mesh, saved, g):
    q, k, v, k_mask, o, res = saved
    return _attention_backward(q, k, v, k_mask, o, res, g, causal, scale,
                               n_head, use_pallas, mesh) + (None,)


fused_attention.defvjp(_fused_fwd, _fused_bwd)


# ---------------------------------------------------------------------------
# IR op
# ---------------------------------------------------------------------------

def _infer_attn(op, block):
    q = block.var(op.input("Q")[0])
    k = block.var(op.input("K")[0])
    v = block.var(op.input("V")[0])
    out = block.var(op.output("Out")[0])
    if q.shape is None or v.shape is None:
        raise ShapeInferenceSkip()
    n_head = op.attr("n_head", 0)
    out.shape = tuple(q.shape[:-1]) + (v.shape[-1],)
    out.dtype = q.dtype
    lse_names = op.output("Lse")
    if lse_names:
        lse = block.var(lse_names[0])
        if k.shape is not None and attention_lowering(
                q.shape, k.shape, v.shape, n_head).kind == PACKED:
            lse.shape = attention_packed.res_shape(*q.shape)
        else:
            # [B, H, S, 2] residual of the streaming kernels: (softmax
            # running max, log denominator)
            lse.shape = ((q.shape[0], n_head, q.shape[1])
                         if len(q.shape) == 3 else tuple(q.shape[:3])) + (2,)
        lse.dtype = "float32"


def _attn_operands(ctx, amp_cast=True):
    """(q, k, v, k_mask, n_head) of an attention op or its grad;
    ``n_head`` is set only where the op is PACKED: rank-3 ``[B, S, H*D]``
    ``Q`` with an ``n_head`` attribute."""
    get = ctx.input if amp_cast \
        else (lambda slot: ctx.env[ctx.op.input(slot)[0]])
    q, k, v = get("Q"), get("K"), get("V")
    mask_names = ctx.op.input("KMask")
    k_mask = ctx.env[mask_names[0]] if mask_names else None
    n_head = int(ctx.attr("n_head", 0)) if q.ndim == 3 else None
    if k_mask is None:
        k_mask = jnp.ones((q.shape[0], k.shape[1 if n_head else 2]),
                          q.dtype)
    return q, k, v, k_mask, n_head


def _attn_grad_lower(ctx: LowerContext):
    qe, ke, ve, k_mask, n_head = _attn_operands(ctx, amp_cast=False)
    # mirror the forward's AMP cast so the vjp's output dtype matches the
    # cotangent coming back from (possibly bf16) downstream consumers;
    # emitted grads are cast back to the primal env dtypes
    amp = bool(ctx.aux.get("amp"))

    def cast_in(x):
        return x.astype(jnp.bfloat16) \
            if amp and x.dtype == jnp.float32 else x

    # if the forward saved its residuals (Out + Lse), reuse them — the
    # backward kernels run directly, no forward recompute
    out_names = ctx.op.input("Out")
    lse_names = ctx.op.input("Lse")
    o = ctx.env.get(out_names[0]) if out_names else None
    lse = ctx.env.get(lse_names[0]) if lse_names else None
    grads = _attention_backward(
        cast_in(qe), cast_in(ke), cast_in(ve), k_mask, o,
        lse if o is not None else None,
        ctx.env[ctx.op.input("Out@GRAD")[0]], ctx.attr("causal", False),
        float(ctx.attr("scale", 1.0)), n_head,
        bool(ctx.attr("use_flash", True)), ctx.aux.get("mesh"),
        ctx.aux.get("batch_axis", 0))
    for slot, val, prim in zip(("Q@GRAD", "K@GRAD", "V@GRAD"), grads,
                               (qe, ke, ve)):
        names = ctx.op.output(slot)
        if names and names[0]:
            ctx.outputs[names[0]] = val.astype(prim.dtype)


@register_op("scaled_dot_product_attention", infer_shape=_infer_attn,
             grad_lower=_attn_grad_lower, no_grad_inputs=("KMask",),
             amp_cast=("Q", "K", "V"))
def sdpa_lower(ctx: LowerContext):
    """Q,K,V: [B, H, S, D]; KMask: [B, S_k] (1=attend); Out: [B, H, Sq, D].
    PACKED: Q,K,V [B, S, H*D] with the ``n_head`` attribute, as projection
    ``fc``s emit them; Out [B, Sq, H*Dv].  ``attention_lowering`` says
    which lowering the op gets.

    attrs: causal (bool), scale (float), use_flash (bool, default True),
    n_head (int, packed form only).
    """
    q, k, v, k_mask, n_head = _attn_operands(ctx)
    # no lowering has attention-weight dropout; the graph builder builds
    # the composed ops when dropout is requested in training
    out, res = _attention_forward(
        q, k, v, k_mask, ctx.attr("causal", False),
        float(ctx.attr("scale", 1.0)), n_head,
        bool(ctx.attr("use_flash", True)), ctx.aux.get("mesh"),
        ctx.aux.get("batch_axis", 0))
    ctx.set_output("Out", out)
    if res is not None:
        # saved residual; consumed by the grad op (the kernel's backward)
        ctx.set_output("Lse", res)


# ---------------------------------------------------------------------------
# ring_attention IR op — sequence/context parallelism (SURVEY.md §2.8:
# the reference has none; this supersedes its LoD-ragged long-sequence
# story).  Falls back to single-device attention when the executor's mesh
# has no populated sequence axis, so the same program runs anywhere.
# ---------------------------------------------------------------------------

def _infer_ring_attn(op, block):
    q = block.var(op.input("Q")[0])
    v = block.var(op.input("V")[0])
    out = block.var(op.output("Out")[0])
    if q.shape is None or v.shape is None:
        raise ShapeInferenceSkip()
    out.shape = tuple(q.shape[:-1]) + (v.shape[-1],)
    out.dtype = q.dtype


@register_op("ring_attention", infer_shape=_infer_ring_attn)
def ring_attention_lower(ctx):
    from paddle_tpu.parallel.ring_attention import ring_attention
    q, k, v = ctx.input("Q"), ctx.input("K"), ctx.input("V")
    causal = ctx.attr("causal", False)
    scale = ctx.attr("scale", None)
    seq_axis = ctx.attr("seq_axis", "seq")
    mesh = ctx.aux.get("mesh")
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape)) \
        if mesh is not None else {}
    if axis_sizes.get(seq_axis, 1) > 1 and \
            q.shape[2] % axis_sizes[seq_axis] == 0:
        out = ring_attention(q, k, v, mesh, axis=seq_axis, causal=causal,
                             scale=scale)
    else:
        out = _reference_attention(q, k, v, None, causal,
                                   scale if scale is not None
                                   else float(q.shape[-1]) ** -0.5)
    ctx.set_output("Out", out)


# ---------------------------------------------------------------------------
# paged_attention IR op — occupancy-proportional decode reads over the gen
# KV pool (ROADMAP item 3).  The gen cache lives as [num_pages, page_len,
# Hkv*D] pages plus a per-slot page table; each decode step appends the new
# token's K/V row into its slot's tail page, then attends ONLY the rows
# [0, len).  The page-table feed is bucketed by the predictor so the decode
# jit key stays constant per bucket; the bucket sets the WIDTH of the table
# that is fed, not the work.  Two lowerings share one contract.
#
# The Pallas kernel (PR 30) is taken on a TPU whenever ``_paged_kernel_ok``
# admits the shape (head width a multiple of 128 lanes, page_len whole
# sublane tiles).  Grid = (slots,); the pools stay in HBM, the page table
# and ``lens`` are scalar-prefetched.  Trip count: a slot makes
# ``cdiv(lens, block rows)`` trips of an in-kernel loop, a free slot none;
# neither the pages a slot holds past its length nor the table's ``0``
# tail is visited.  A trip copies the NEXT block's live pages (one
# ``make_async_copy`` a page; past a slot's last block, the first block of
# the next live slot) into the other half of a double buffer, waits for
# its own, and updates the online softmax chunk by chunk over the live
# rows only.  Block choice (``_paged_blocking``): as many pages as come to
# ~1 MB of K, so 4 pages of 4096-wide float32 rows and 64 of 256-wide; a
# chunk is 64 rows where every query head has a K/V head of its own (a
# VPU multiply + lane reduction for the scores, a sublane reduction for
# PV: exact float32) and 512 where ``G = H / Hkv`` query heads share one:
# those take their K/V head's rows in two float32 ``HIGHEST`` products
# ``[G, D] x [D, rows]``, ``[G, rows] x [rows, D]`` on the MXU (over a
# pool narrower than float32, PR 33: the rows stay in the pool's type,
# one pass for the scores, which are then exact, and two for the weights
# split ``hi + lo``).  With ``L`` rows a slot a step (block decoding) the
# kernel is handed ``L * H`` query heads, ``L * G`` to a K/V head, and
# (PR 34) a limit a query row where the call brings one (``RowLens``: a
# step that carries two blocks a slot under the block-causal mask): one
# more compare on the ``[L * G, rows]`` scores, the walk to the largest
# limit; a call without limits is the kernel it was.  The K/V heads are
# never copied out to ``H``; softmax state is float32.
#
# Measured on a v5e, the kernel alone (my chip runs, PR 30; the kernel it
# replaced, grid (slots, page bucket) with one 16-row page a grid step,
# in brackets): 16 slots x 32 heads x 128 on 4096-wide float32 rows in
# the 64-page bucket at 5024 live rows 0.235 ms [0.565], 86% of what the
# live K/V bytes take at 819 GB/s; 3 live slots of 16 in the 32-page
# bucket 0.040 [0.231]; every slot filling the bucket 0.715 [0.768], 92%;
# 32 slots x 32 query heads over 2 K/V heads (256-wide rows) in the
# 128-page bucket at 20258 live rows 0.145 [1.641].  Block bytes of 0.5,
# 1 and 2 MB and VPU chunks of 32, 64 and 128 rows read the same; the
# grouped chunk reads 0.211 / 0.160 / 0.145 ms at 128 / 256 / 512 rows.
# The K/V heads are walked by a loop that writes out 4 a trip
# (``_PAGED_HEAD_UNROLL``): all 32 written out read 0.229 ms but cost
# every decode executable 3.1 s more to trace and lower, 25 s of a warm
# server start; one a trip reads 0.407 ms.
# ``PERF.md`` section 6 has the serving cells.  tests/test_tpu_compile.py
# holds it to the v5e compiler at both configurations' shapes, in float32
# and bfloat16.  The shipped ``GenConfig`` (d_head 16) FAILS the gate: on
# a TPU it decodes through the XLA gather below, counted by
# ``gen.paged.fallback``.  Off-TPU the gather is the default too —
# interpret-mode execution re-runs the kernel per call (unlike trace-once
# XLA), so tests opt in via PADDLE_TPU_PAGED_INTERPRET=1 instead.
# ---------------------------------------------------------------------------

def _paged_cache_update(pools, rows, page_table, lens, row_lens=None):
    """Scatter this step's rows of every pool (K and V, or the one
    latent row) into each live slot's pages.

    ``rows`` are ``[S, L, width]``: ``L`` rows a slot a step (1 where a
    step decodes one token a slot, the rows of two blocks under block
    decoding).  ``lens`` [S, 1] counts rows THROUGH the step's last, so
    row ``j`` lands at position ``lens - L + j``, over whatever an
    earlier step wrote there; ``lens == 0`` marks a free slot
    and maps to an out-of-range page that ``mode="drop"`` discards —
    zero-filled warmup feeds therefore write nothing.  ``row_lens``
    [S, L] (``paged_attention``'s ``RowLens``): a row whose entry is 0
    lands nowhere either.
    """
    NP, PL, _ = pools[0].shape
    S, L = rows[0].shape[:2]
    # [S * L], a slot's rows in order
    at = (lens[:, :1] - L + jnp.arange(L, dtype=lens.dtype)).reshape(-1)
    idx = jnp.clip(at, 0)
    # (a dead row's place may lie past the table that is fed: whatever
    # the gather returns there is replaced)
    page = jnp.take_along_axis(page_table, (idx // PL).reshape(S, L),
                               axis=1).reshape(-1)
    lands = at >= 0
    if row_lens is not None:
        lands &= row_lens.reshape(-1) > 0
    page = jnp.where(lands, page, NP)
    row = idx % PL
    return tuple(
        pool.at[page, row].set(x.reshape(S * L, -1).astype(pool.dtype),
                               mode="drop")
        for pool, x in zip(pools, rows))


def _xla_paged_attention(q, kc, vc, page_table, lens, n_head, scale,
                         row_lens=None):
    """Gather-based fallback: same contract as the kernel.  Reads only
    the ``P`` table-listed pages per slot ([S, P*PL] keys, not
    [S, max_len]) — still occupancy-proportional, just without the
    VMEM-resident online softmax.  ``q`` [S, L, H*D] (or [S, H*D]: one
    row a slot): each of a slot's ``L`` query rows reads every live
    row, or with ``row_lens`` [S, L] the rows under its own limit (a
    row whose limit is 0 reads zeros)."""
    S, P = page_table.shape
    NP, PL, HDkv = kc.shape
    H = n_head
    L = 1 if q.ndim == 2 else q.shape[1]
    D = q.shape[-1] // H
    Hkv = HDkv // D
    T = P * PL
    kg = kc[page_table].reshape(S, T, Hkv, D)
    # value heads may be narrower than key heads (their pool's own rows)
    vg = vc[page_table].reshape(S, T, Hkv, vc.shape[-1] // Hkv)
    if Hkv != H:
        kg = jnp.repeat(kg, H // Hkv, axis=2)
        vg = jnp.repeat(vg, H // Hkv, axis=2)
    if L > 1:
        # a slot's L rows as L x H heads, each row's H over the same K/V
        kg, vg = jnp.tile(kg, (1, 1, L, 1)), jnp.tile(vg, (1, 1, L, 1))
    qh = q.reshape(S, L * H, D).astype(jnp.float32)
    sc = jnp.einsum("shd,sthd->sht", qh, kg.astype(jnp.float32),
                    preferred_element_type=jnp.float32) * scale
    col = jax.lax.broadcasted_iota(jnp.int32, (S, 1, T), 2)
    limit = lens[:, :, None] if row_lens is None \
        else jnp.repeat(row_lens, H, axis=1)[:, :, None]
    sc = jnp.where(col < limit, sc, NEG_INF)
    probs = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("sht,sthd->shd", probs, vg.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    if row_lens is not None:
        out = jnp.where(limit > 0, out, 0.0)
    return out.reshape(q.shape[:-1] + (-1,)).astype(q.dtype)


# One block of the kernel's double buffer holds about this many bytes of K
# (and as many of V): 4 pages of 4096-wide float32 rows, 64 of 256-wide.
_PAGED_BLOCK_BYTES = 1 << 20
# Rows of one online-softmax update.  A head of its own: what keeps its
# [rows, D] tiles in vector registers.  Grouped heads: the two MXU
# products read fastest at 512 (128 / 256 / 512 measured: comment above).
_PAGED_CHUNK_ROWS = 64
_PAGED_GROUPED_CHUNK_ROWS = 512
# K/V heads a trip of the kernel's head loop takes, written out: the
# scheduler needs a few independent heads to hide a head's reductions
# (1 / 2 / 4 / 8 / all 32 read 0.407 / 0.254 / 0.235 / 0.231 / 0.229 ms),
# and every head written out is traced and lowered again for each decode
# executable (all 32: +3.1 s on each of a warm start's eight).
_PAGED_HEAD_UNROLL = 4


def _paged_blocking(P, PL, HDkv, itemsize, grouped, block_pages=None):
    """(pages a block, rows a chunk) from the shapes: a block is the unit
    of the double buffer, as many pages as come to ``_PAGED_BLOCK_BYTES``
    (never more than the table holds); a chunk the unit of the online
    softmax, the largest whole number of pages that divides the block
    and stays within ``_PAGED_CHUNK_ROWS`` (``_PAGED_GROUPED_CHUNK_ROWS``
    where query heads share a K/V head)."""
    if block_pages is None:
        # a power of two: 32 pages of the 576-wide latent row, not 56
        block_pages = _PAGED_BLOCK_BYTES // (PL * HDkv * itemsize)
        block_pages = 1 << max(block_pages.bit_length() - 1, 0)
    block_pages = max(1, min(int(block_pages), P))
    rows = _PAGED_GROUPED_CHUNK_ROWS if grouped else _PAGED_CHUNK_ROWS
    chunk_pages = max(d for d in range(1, block_pages + 1)
                      if block_pages % d == 0 and (d * PL <= rows or d == 1))
    return block_pages, chunk_pages * PL


def _paged_decode_kernel(pt_ref, lens_ref, q_ref, *refs, page_len,
                         block_pages, chunk_rows, head_unroll, scale,
                         latent=False, row_limits=False, select=False):
    """One slot of the grid: the online softmax over the slot's LIVE rows.

    The pools stay in HBM.  A slot makes ``cdiv(lens, block rows)`` trips
    of an in-kernel loop and no more (a free slot none); each trip copies
    the NEXT block's live pages (of this slot or, past its last block, of
    the next live slot: ``ahead_ref`` carries that across grid steps)
    into the other half of ``kbuf`` / ``vbuf`` before it waits for its
    own, then updates the softmax chunk by chunk over the rows that are
    live, K/V head by K/V head (a loop of ``head_unroll`` heads a trip).
    Q arrives and Out leaves as ``[H, D]``, a slot's heads on sublanes
    (laying them out inside the kernel read 0.08 ms a step slower);
    ``m`` / ``l`` are kept replicated over a head's ``D`` lanes.  With
    as many K/V heads as query heads a head's scores are a VPU multiply
    + lane reduction and its PV product a sublane reduction, as before;
    grouped query heads share their K/V head's rows in two float32
    ``HIGHEST`` products ``[G, D] x [D, rows]`` and
    ``[G, rows] x [rows, D]``.  Grouped heads may have value heads of
    another width than their key heads (``vbuf``'s rows hold as many
    heads, ``acc_ref``'s width each): head ``g``'s value is then its own
    lanes of the V row.

    ``latent``: ONE pool, whose row is the key of all ``H`` heads and
    whose leading ``Dv`` lanes (``acc_ref``'s width) are their value too
    (``paged_attention_latent``).  The row is copied once and both
    products read it from the buffer, in the pool's own type with
    float32 accumulation, one MXU pass each: ``[H, Dk] x [Dk, rows]``
    and, the weights rounded to that type, ``[H, rows] x [rows, Dv]``
    (121 FLOP a cached byte at 64 heads: two ``HIGHEST`` float32
    products, six passes each, would bound the kernel by the MXU at a
    third of the chip's bandwidth).

    ``row_limits`` (grouped heads, or the latent form, whose one row is
    the K/V head of all ``G`` = ``H`` query rows): a ``[G, 1]`` int32
    column comes first among ``refs``, the rows each of a K/V head's
    ``G`` query rows sees, the same for every K/V head: a row is masked
    past its own limit, the walk still follows ``lens`` (the largest),
    and a query row whose limit is 0 leaves zeros.

    ``select`` (the latent form only): a ``[1, 1, rows]`` int32 mask of
    the slot's rows comes next among ``refs`` (``dsa_select``'s; padded
    to whole blocks): a row whose entry is 0 is masked like a row past
    ``lens``.  Every live page is still walked."""
    lim_ref = sel_ref = None
    if row_limits:
        lim_ref, *refs = refs
    if select:
        sel_ref, *refs = refs
    if latent:
        k_hbm, o_ref, kbuf, sems, ahead_ref, qs_ref, m_ref, l_ref, \
            acc_ref = refs
        vbuf, pools = kbuf, ((k_hbm, kbuf),)
    else:
        k_hbm, v_hbm, o_ref, kbuf, vbuf, sems, ahead_ref, qs_ref, m_ref, \
            l_ref, acc_ref = refs
        pools = ((k_hbm, kbuf), (v_hbm, vbuf))
    s = pl.program_id(0)
    S, P = pt_ref.shape
    PL, CR = page_len, chunk_rows
    BR = block_pages * PL
    H, D = qs_ref.shape
    Dv = acc_ref.shape[-1]
    G = H // (kbuf.shape[-1] // D)
    f32 = jnp.float32
    # grouped heads over a pool narrower than float32: both products take
    # the rows from the buffer in the pool's own type (see ``attend``)
    narrow = not latent and G > 1 and kbuf.dtype != f32

    def rows_of(slot):
        return jnp.minimum(lens_ref[slot, 0], P * PL)

    def block_copies(slot, blk, buf, start):
        """Start, or wait for, the copies of the live pages of a block."""
        live = jnp.minimum(pl.cdiv(rows_of(slot) - blk * BR, PL),
                           block_pages)

        def page(j, _):
            src = pt_ref[slot, blk * block_pages + j]
            dst = pl.ds(pl.multiple_of(j * PL, PL), PL)
            for x, (hbm, buffer) in enumerate(pools):
                copy = pltpu.make_async_copy(
                    hbm.at[src], buffer.at[buf, dst], sems.at[buf, x])
                copy.start() if start else copy.wait()
            return 0

        jax.lax.fori_loop(0, live, page, 0)

    def attend(buf, rows, valid, base):
        """One chunk: ``valid`` of its rows are live (may exceed it);
        it starts at the slot's row ``base`` (given with row limits or
        a selection)."""
        if G == 1:
            live = jax.lax.broadcasted_iota(jnp.int32, (CR, 1), 0) < valid
        elif lim_ref is None:
            live = jax.lax.broadcasted_iota(jnp.int32, (1, CR), 1) < valid
            if sel_ref is not None:
                live &= sel_ref[0, :, pl.ds(pl.multiple_of(base, CR),
                                            CR)] > 0
        else:
            # [G, CR]: each query row under its own limit (<= lens)
            live = jax.lax.broadcasted_iota(jnp.int32, (1, CR), 1) \
                < lim_ref[0] - base

        def head(g):
            hs = pl.ds(pl.multiple_of(g * G, G), G)
            kv = pl.ds(pl.multiple_of(g * D, D), D)
            m_prev = m_ref[hs, :]                        # [G, Dv]
            if latent:
                k = kbuf[buf, rows, :]                   # [CR, Dk]
                sc = jax.lax.dot_general(
                    qs_ref[...], k, (((1,), (1,)), ((), ())),
                    preferred_element_type=f32) * scale  # [H, CR]
                sc = jnp.where(live, sc, NEG_INF)
                m_new = jnp.maximum(
                    m_prev, jnp.max(sc, axis=1, keepdims=True))
                e = jnp.exp(sc - m_new[:, :1])
                e_sum = jnp.sum(e, axis=1, keepdims=True)
                pv = jax.lax.dot_general(
                    e.astype(k.dtype), k[:, :Dv], (((1,), (0,)), ((), ())),
                    preferred_element_type=f32)          # [H, Dv]
                alpha = jnp.exp(m_prev - m_new)
                l_ref[...] = l_ref[...] * alpha + e_sum
                acc_ref[...] = acc_ref[...] * alpha + pv
                m_ref[...] = m_new
                return
            # value heads of their own width: their own lanes of the row
            vs = kv if Dv == D else pl.ds(pl.multiple_of(g * Dv, Dv), Dv)
            k, v = kbuf[buf, rows, kv], vbuf[buf, rows, vs]  # [CR, D]
            if not narrow:
                k, v = k.astype(f32), v.astype(f32)
            if G == 1:
                sc = jnp.sum(qs_ref[hs, :] * k, axis=1, keepdims=True)
                sc = jnp.where(live, sc, NEG_INF)        # [CR, 1]
                m_new = jnp.maximum(
                    m_prev, jnp.max(sc, axis=0, keepdims=True))
                e = jnp.exp(sc - m_new)                  # [CR, D]
                e_sum = jnp.sum(e, axis=0, keepdims=True)
                pv = jnp.sum(e * v, axis=0, keepdims=True)
            else:
                # narrow: the query and the rows are exact in the pool's
                # type, so ONE pass gives the float32 scores exactly, and
                # the weights go through in two parts of that type (``e =
                # hi + lo`` to 16 bits): three passes where float32
                # ``HIGHEST`` products of rows cast up take six each
                exact = {} if narrow else {
                    "precision": jax.lax.Precision.HIGHEST}
                sc = jax.lax.dot_general(
                    qs_ref[hs, :], k, (((1,), (1,)), ((), ())),
                    preferred_element_type=f32, **exact)  # [G, CR]
                if narrow:
                    sc = sc * scale
                sc = jnp.where(live, sc, NEG_INF)
                m_new = jnp.maximum(
                    m_prev, jnp.max(sc, axis=1, keepdims=True))
                e = jnp.exp(sc - m_new[:, :1])           # [G, CR]
                e_sum = jnp.sum(e, axis=1, keepdims=True)
                weigh = lambda part: jax.lax.dot_general(
                    part, v, (((1,), (0,)), ((), ())),
                    preferred_element_type=f32, **exact)  # [G, D]
                if narrow:
                    hi = e.astype(v.dtype)
                    pv = weigh(hi) + weigh((e - hi.astype(f32))
                                           .astype(v.dtype))
                else:
                    pv = weigh(e)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[hs, :] = l_ref[hs, :] * alpha + e_sum
            acc_ref[hs, :] = acc_ref[hs, :] * alpha + pv
            m_ref[hs, :] = m_new

        def heads(i, _):
            for u in range(head_unroll):
                head(i * head_unroll + u)
            return 0

        jax.lax.fori_loop(0, H // G // head_unroll, heads, 0)

    n = rows_of(s)
    n_blocks = pl.cdiv(n, BR)

    @pl.when(s == 0)
    def _first():
        ahead_ref[0] = 0        # the half the next block is copied into
        ahead_ref[1] = 0        # 1: its copies are already in flight
        # a page that is never copied leaves its rows of the buffer as
        # they were: masked rows weigh 0, and 0 x NaN would still be NaN
        vbuf[...] = jnp.zeros_like(vbuf)

    @pl.when(n_blocks == 0)
    def _free():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n_blocks > 0)
    def _live():
        buf0 = ahead_ref[0]

        @pl.when(ahead_ref[1] == 0)
        def _():
            block_copies(s, 0, buf0, True)

        nxt = jax.lax.fori_loop(
            s + 1, S, lambda i, at: jnp.where(
                (at == S) & (lens_ref[i, 0] > 0), i, at), S)
        # the latent and the narrow form scale the float32 scores, not the
        # stored query
        qs_ref[...] = q_ref[0] if latent or narrow \
            else q_ref[0].astype(f32) * scale
        m_ref[...] = jnp.full_like(m_ref, _M_INIT)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def block(b, buf):
            last = b == n_blocks - 1

            @pl.when(jnp.logical_not(last))
            def _():
                block_copies(s, b + 1, 1 - buf, True)

            @pl.when(last & (nxt < S))
            def _():
                block_copies(nxt, 0, 1 - buf, True)

            block_copies(s, b, buf, False)
            left = n - b * BR

            def chunk(c, _):
                r0 = pl.multiple_of(c * CR, CR)
                attend(buf, pl.ds(r0, CR), left - r0,
                       None if lim_ref is None and sel_ref is None
                       else b * BR + r0)
                return 0

            jax.lax.fori_loop(0, pl.cdiv(jnp.minimum(left, BR), CR),
                              chunk, 0)
            return 1 - buf

        ahead_ref[0] = jax.lax.fori_loop(0, n_blocks, block, buf0)
        ahead_ref[1] = jnp.where(nxt < S, 1, 0)
        if lim_ref is None:
            o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)
        else:
            seen = lim_ref[0] > 0
            for g in range(H // G):
                hs = pl.ds(g * G, G)
                o_ref[0, hs, :] = jnp.where(
                    seen, acc_ref[hs, :] / l_ref[hs, :], 0.0
                ).astype(o_ref.dtype)


def _paged_kernel_ok(n_head, HD, PL, interpret, HDkv=None, itemsize=4,
                     v_width=None, HDv=None):
    """Shape gate of the paged kernel: heads must split Q's ``H*D``
    evenly and whole K/V heads the pool's row, the query heads divide
    evenly over them, and on the chip a head must cover whole 128-lane
    vregs and a page whole sublane tiles of the pool's type (8 rows of
    float32, 16 of bfloat16): the kernel slices refs at ``h*D`` lanes
    and copies a page to ``j*PL`` rows.  The latent form (``v_width``:
    one row that is every head's key) copies and reads its row whole:
    the row and the value's lanes, its head, must both be whole vregs.
    ``HDv``: the V pool's row where it is not the K pool's (value heads
    of another width: grouped heads only, as many heads, whole vregs)."""
    if HD % n_head:
        return False
    D = HD // n_head
    HDkv = HD if HDkv is None else HDkv
    if HDkv % D or n_head % (HDkv // D):
        return False
    if HDv is not None and HDv != HDkv:
        n_kv = HDkv // D
        if v_width is not None or n_kv == n_head or HDv % n_kv:
            return False
        if not interpret and HDv // n_kv % 128:
            return False
    if v_width is not None:
        if HDkv != HD // n_head or not 0 < v_width <= HDkv:
            return False
        # the row is copied whole: a 576-wide row is 4.5 vregs, which
        # the chip's DMA refuses
        return interpret or not (v_width % 128 or HDkv % 128
                                 or PL % (32 // itemsize))
    return interpret or not (D % 128 or PL % (32 // itemsize))


def _pallas_paged_attention(q, kc, vc, page_table, lens, n_head, scale,
                            interpret=False, block_pages=None,
                            v_width=None, row_lens=None, select=None):
    """Returns None when ``_paged_kernel_ok`` refuses the shape; any
    lowering error past that gate surfaces to the caller.
    ``block_pages`` is for the tests: the kernel reads it from the
    shapes (``_paged_blocking``).  ``vc`` None with ``v_width``: the
    latent form over the one pool ``kc``.

    ``q`` [S, L, H*D] with ``L`` > 1 (block decoding: ``L`` rows a slot,
    each reading every live row): the kernel is handed ``L * H`` query
    heads, the ``L * G`` that share a K/V head side by side, so a K/V
    head's rows are copied once for all ``L`` rows and its two products
    are ``[L * G, D] x [D, rows]`` and ``[L * G, rows] x [rows, D]``.
    ``L`` = 1 is the call as it was.  ``row_lens`` [S, L] (with ``L`` >
    1): what each of a slot's rows sees, ``lens`` the largest of them;
    the kernel takes them as a column of ``L * G`` limits.

    ``select`` [S, 1, P * page_len] int (the latent form): 0 masks the
    slot's row at that position."""
    P = page_table.shape[1]
    NP, PL, HDkv = kc.shape
    HD = q.shape[-1]
    itemsize = kc.dtype.itemsize
    L = q.shape[1] if q.ndim == 3 and v_width is None else 1
    HDv = None if vc is None else vc.shape[-1]
    if L > 1:
        if HDv != HDkv:
            return None
        if not _paged_kernel_ok(n_head, HD, PL, interpret, HDkv, itemsize):
            return None
        S, D = q.shape[0], HD // n_head
        n_kv = HDkv // D
        # [S, L, Hkv, G, D] -> [S, Hkv, L, G, D]: the kernel's head h
        # reads K/V head h // (L * G)
        grouped = (S, n_kv, L, n_head // n_kv, D)
        qk = q.reshape(S, L, n_kv, n_head // n_kv, D).transpose(0, 2, 1, 3, 4)
        if row_lens is not None:
            row_lens = jnp.repeat(row_lens.astype(jnp.int32),
                                  n_head // n_kv, axis=1)[:, :, None]
        out = _pallas_paged_attention(
            qk.reshape(S, 1, L * HD), kc, vc, page_table, lens, L * n_head,
            scale, interpret=interpret, block_pages=block_pages,
            row_lens=row_lens)
        return out.reshape(grouped).transpose(0, 2, 1, 3, 4).reshape(q.shape)
    if not _paged_kernel_ok(n_head, HD, PL, interpret, HDkv, itemsize,
                            v_width, HDv):
        return None
    n_kv = HDkv // (HD // n_head)
    G = n_head // n_kv
    if v_width is None and G > 1 and G % 8 and row_lens is None:
        # the kernel takes a K/V head's G query rows out of its scratch
        # at a head it walks to, and Mosaic slices sublanes by whole
        # tiles of 8: a group that is not one is filled up with rows of
        # zeros, whose results are dropped (the MXU and the softmax work
        # on whole tiles either way)
        D, filled = HD // n_head, -(-G // 8) * 8
        lead = q.shape[:-1]
        qg = q.reshape(lead + (n_kv, G, D))
        qg = jnp.pad(qg, [(0, 0)] * len(lead)
                     + [(0, 0), (0, filled - G), (0, 0)])
        out = _pallas_paged_attention(
            qg.reshape(lead + (n_kv * filled * D,)), kc, vc, page_table,
            lens, n_kv * filled, scale, interpret=interpret,
            block_pages=block_pages)
        return out.reshape(lead + (n_kv, filled, -1))[..., :G, :] \
            .reshape(lead + (-1,))
    block_pages, chunk_rows = _paged_blocking(
        P, PL, HDkv, itemsize, HDkv != HD, block_pages)
    head_unroll = max(u for u in range(1, _PAGED_HEAD_UNROLL + 1)
                      if n_kv % u == 0)
    return _paged_kernel_call(
        q, kc, vc, page_table, lens, n_head=n_head, scale=scale,
        interpret=interpret, block_pages=block_pages,
        chunk_rows=chunk_rows, head_unroll=head_unroll, v_width=v_width,
        row_limits=row_lens, select=select)


# inline: the call leaves no trace in the program (the kernel's event keeps
# the op scope's name), but a model's layers, which call it with the same
# shapes, share ONE trace and ONE lowering of the kernel's body
@functools.partial(jax.jit, inline=True, static_argnames=(
    "n_head", "scale", "interpret", "block_pages", "chunk_rows",
    "head_unroll", "v_width"))
def _paged_kernel_call(q, kc, vc, page_table, lens, *, n_head, scale,
                       interpret, block_pages, chunk_rows, head_unroll,
                       v_width=None, row_limits=None, select=None):
    S = page_table.shape[0]
    NP, PL, HDkv = kc.shape
    D = q.shape[-1] // n_head
    latent = vc is None
    Dv = v_width if latent else vc.shape[-1] // (HDkv // D)
    pools = (kc,) if latent else (kc, vc)
    kernel = functools.partial(_paged_decode_kernel, page_len=PL,
                               block_pages=block_pages,
                               chunk_rows=chunk_rows,
                               head_unroll=head_unroll, scale=scale,
                               latent=latent,
                               row_limits=row_limits is not None,
                               select=select is not None)
    # [S, G, 1]: the limits of the query rows that share a K/V head
    limits = [] if row_limits is None else [row_limits]
    if select is not None:
        # [S, 1, whole blocks of rows]: the walk's last block may pass
        # the table's rows
        rows = -(-select.shape[-1] // (block_pages * PL)) * block_pages * PL
        limits.append(jnp.pad(select.astype(jnp.int32), (
            (0, 0), (0, 0), (0, rows - select.shape[-1]))))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S,),
            in_specs=[pl.BlockSpec((1, n_head, D),
                                   lambda s, pt, ln: (s, 0, 0))]
            + [pl.BlockSpec((1,) + lim.shape[1:],
                            lambda s, pt, ln: (s, 0, 0)) for lim in limits]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
            out_specs=pl.BlockSpec((1, n_head, Dv),
                                   lambda s, pt, ln: (s, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, block_pages * PL, pool.shape[-1]), pool.dtype)
                for pool in pools] + [
                pltpu.SemaphoreType.DMA((2, len(pools))),
                pltpu.SMEM((2,), jnp.int32),
                # the query: scaled float32, or as it is (the latent
                # form; grouped heads over a pool narrower than float32)
                pltpu.VMEM((n_head, D), kc.dtype
                           if latent or (HDkv != n_head * D
                                         and kc.dtype != jnp.float32)
                           else jnp.float32),
            ] + [pltpu.VMEM((n_head, Dv), jnp.float32)] * 3,
        ),
        out_shape=jax.ShapeDtypeStruct((S, n_head, Dv), kc.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(page_table, lens, q.reshape(S, n_head, D).astype(kc.dtype), *limits,
      *pools)
    return out.reshape(q.shape[:-1] + (n_head * Dv,)).astype(q.dtype)


def _paged_kernel_enabled(interpret):
    if not interpret:
        return True
    import os
    return os.environ.get("PADDLE_TPU_PAGED_INTERPRET", "0") == "1"


def paged_read(q, kc, vc, page_table, walk, n_head, scale, row_lens=None):
    """The read half of ``paged_attention``: ``q`` [S, L, H * D] against
    the pools' rows under ``walk`` [S, 1] (``row_lens`` [S, L]: a limit a
    row), through the kernel where its gate admits the shape, else the
    gather, counted by ``gen.paged.fallback``."""
    out = None
    interpret = _use_interpret()
    if _paged_kernel_enabled(interpret):
        out = _pallas_paged_attention(q, kc, vc, page_table, walk, n_head,
                                      scale, interpret=interpret,
                                      row_lens=row_lens)
    if out is None:
        # fires at trace time, once per compiled signature, whenever a
        # decode bucket lowered without the Pallas kernel
        from paddle_tpu.profiler import runtime_metrics
        runtime_metrics.inc("gen.paged.fallback")
        out = _xla_paged_attention(q, kc, vc, page_table, walk, n_head,
                                   scale, row_lens=row_lens)
    return out


def _infer_paged_attn(op, block):
    q = block.var(op.input("Q")[0])
    out = block.var(op.output("Out")[0])
    if q.shape is None:
        raise ShapeInferenceSkip()
    out.shape = tuple(q.shape)
    kc, vc = (block.var(op.input(s)[0]) for s in ("KCache", "VCache"))
    if kc.shape is not None and vc.shape is not None and \
            kc.shape[-1] != vc.shape[-1] and kc.shape[-1] > 0:
        # value heads narrower (or wider) than key heads
        out.shape = tuple(q.shape[:-1]) + (
            q.shape[-1] * vc.shape[-1] // kc.shape[-1],)
    out.dtype = q.dtype
    # KCacheOut/VCacheOut alias the persistable cache vars (in-place
    # update idiom) — their shapes are already declared


@register_op("paged_attention", infer_shape=_infer_paged_attn,
             no_gradient=True,
             stateful_outputs=("KCacheOut", "VCacheOut"))
def paged_attention_lower(ctx: LowerContext):
    """Q: [S, L, H*D], K/V: [S, L, Hkv*D] this step's projections, ``L``
    rows a slot (1 where a step decodes one token a slot, the rows of
    two blocks under block decoding); KCache/VCache: [num_pages,
    page_len, Hkv*D] persistable pool (Hkv = H unless the model groups
    its query heads; V and VCache may hold heads of another width,
    Hkv*Dv, and Out is then [S, L, H*Dv]: grouped heads, ``L`` = 1); PageTable: [S, P] int32 (P = the step's page
    bucket); Lens: [S, 1] int32 rows THROUGH the step's last (0 = free
    slot).  The ``L`` rows are written at positions ``Lens - L .. Lens -
    1`` of the slot's pages, over whatever an earlier step wrote there,
    and every one of the ``L`` query rows reads every live row, its own
    ``L`` included: no mask inside the step.  Out: [S, L, H*D];
    KCacheOut/VCacheOut name the cache vars themselves (in-place
    update).

    RowLens (optional; ``L`` > 1): [S * L, 1] int32, a limit for each
    row: row ``j`` reads the rows under ITS limit, so a step can carry
    two blocks a slot under the block-causal mask (the first block's
    rows ``L / 2`` fewer than the second's); a row whose limit is 0 is
    dead: written nowhere, its output zeros.  The slot's pages are
    walked to the largest limit.  A call without it lowers as it always
    did.

    Without K and V (and without KCacheOut / VCacheOut) the pools are
    read as they stand and nothing is written: a layer that attends the
    rows ANOTHER layer caches, which that layer's op has written earlier
    in the step (``models/hybrid_decoder.py``'s cross layers).

    attrs: n_head (int), scale (float).
    """
    q = ctx.input("Q")
    kc = ctx.input("KCache")
    vc = ctx.input("VCache")
    pt = ctx.input("PageTable")
    lens = ctx.input("Lens")
    n_head = int(ctx.attr("n_head", 1))
    scale = float(ctx.attr("scale", 1.0))
    row_lens, walk = None, lens
    if ctx.has_input("RowLens"):
        row_lens = ctx.input("RowLens").reshape(q.shape[:2])
        walk = jnp.max(row_lens, axis=1, keepdims=True)
    # without K and V the pool is ANOTHER layer's, which has written this
    # step's rows already: read, not written
    owns = ctx.has_input("K")
    if owns:
        kc, vc = _paged_cache_update(
            (kc, vc), (ctx.input("K"), ctx.input("V")), pt, lens, row_lens)
    ctx.set_output("Out", paged_read(q, kc, vc, pt, walk, n_head, scale,
                                     row_lens))
    if owns:
        ctx.set_output("KCacheOut", kc)
        ctx.set_output("VCacheOut", vc)


# ---------------------------------------------------------------------------
# paged_attention_latent IR op: the decode step of multi-head latent
# attention (``ops/mla_ops.py``) over ONE pool whose row a token is
# ``[c_kv | k_rope]``: the key of every head and, in its leading
# ``v_width`` lanes, their value.  The query arrives absorbed
# (``mla_absorb``), the context leaves in the latent.  Same page table,
# same walk of the live pages, same kernel (``latent=True``); the row is
# stored and copied once.
# ---------------------------------------------------------------------------

def _xla_latent_attention(q, cache, page_table, lens, n_head, v_width,
                          scale, select=None, row_lens=None):
    """Gather-based fallback of the latent form, float32.  ``select`` [S,
    1, P * page_len]: 0 masks the slot's row at that position.  ``q`` [S,
    L, H * W] with ``row_lens`` [S, L]: each of a slot's ``L`` query rows
    reads the rows under its own limit (0: zeros)."""
    S, P = page_table.shape
    NP, PL, W = cache.shape
    T = P * PL
    rows = cache[page_table].reshape(S, T, W).astype(jnp.float32)
    qh = q.reshape(S, -1, W).astype(jnp.float32)       # [S, L * H, W]
    sc = jnp.einsum("shw,stw->sht", qh, rows,
                    preferred_element_type=jnp.float32) * scale
    col = jax.lax.broadcasted_iota(jnp.int32, (S, 1, T), 2)
    # (made where it is used, twice: a call without ``row_lens`` traces
    # to the equations it traced to before limits existed)
    limit = lambda: lens[:, :, None] if row_lens is None \
        else jnp.repeat(row_lens, n_head, axis=1)[:, :, None]
    seen = col < limit()
    if select is not None:
        seen &= select > 0
    probs = jax.nn.softmax(jnp.where(seen, sc, NEG_INF), axis=-1)
    out = jnp.einsum("sht,stv->shv", probs, rows[..., :v_width],
                     preferred_element_type=jnp.float32)
    # a free slot (a dead row) reads zeros, as the kernel writes them
    out = jnp.where(limit() > 0, out, 0.0)
    return out.reshape(q.shape[:-1] + (n_head * v_width,)).astype(q.dtype)


def _pallas_latent_rows(q, cache, page_table, walk, n_head, scale,
                        row_lens, interpret, v_width):
    """The latent kernel under ``L`` rows a slot, ``q`` [S, L, H * W]
    with ``row_lens`` [S, L]: the kernel is handed the slot's ``L * H``
    absorbed query rows side by side, all against the one cached row (its
    products are ``[L * H, W] x [W, rows]`` and ``[L * H, rows] x [rows,
    v]``: the pool's rows are copied once for all ``L``), and a column of
    ``L * H`` limits; the walk follows ``walk``, the largest."""
    S, L, HW = q.shape
    limits = jnp.repeat(row_lens.astype(jnp.int32), n_head,
                        axis=1)[:, :, None]
    out = _pallas_paged_attention(
        q.reshape(S, 1, L * HW), cache, None, page_table, walk, L * n_head,
        scale, interpret=interpret, v_width=v_width, row_lens=limits)
    return None if out is None else out.reshape(S, L, n_head * v_width)


def _infer_paged_latent(op, block):
    q = block.var(op.input("Q")[0])
    out = block.var(op.output("Out")[0])
    if q.shape is None:
        raise ShapeInferenceSkip()
    out.shape = tuple(q.shape[:-1]) + (
        int(op.attr("n_head")) * int(op.attr("v_width")),)
    out.dtype = q.dtype


@register_op("paged_attention_latent", infer_shape=_infer_paged_latent,
             no_gradient=True, stateful_outputs=("CacheOut",))
def paged_attention_latent_lower(ctx: LowerContext):
    """Q: [S, L, H*W] the absorbed queries (W = the pool's row width;
    ``L`` rows a slot, 1 where a step decodes one token a slot); Row:
    [S, L, W] this step's latent rows; Cache: [num_pages, page_len, W]
    persistable pool; PageTable, Lens as ``paged_attention`` (Lens: rows
    THROUGH the step's last).  Out: [S, L, H*v_width], the context in
    the latent; CacheOut names the cache var itself.  attrs: n_head,
    v_width, scale.

    RowLens (optional; ``L`` > 1): [S * L, 1] int32, a limit for each
    row, as ``paged_attention``'s: row ``j`` reads the rows under ITS
    limit (a turn's draft row sees the committed row, not the other way
    round); a row whose limit is 0 is dead: written nowhere, its output
    zeros.  The slot's pages are walked to the largest limit.  Not with
    Select.

    Select (optional, with attr select_top_k; ``ops/dsa_ops.py``): [S, 1,
    P * page_len] int32, 0 = the slot's row at that position is left out
    of the softmax.  Every live page is still walked.  A bucket of no
    more than ``select_top_k`` rows cannot leave one out: the input is
    then not read."""
    q = ctx.input("Q")
    pt, lens = ctx.input("PageTable"), ctx.input("Lens")
    n_head, v_width = int(ctx.attr("n_head")), int(ctx.attr("v_width"))
    scale = float(ctx.attr("scale", 1.0))
    row_lens, walk = None, lens
    if ctx.has_input("RowLens"):
        if ctx.has_input("Select"):
            raise NotImplementedError(
                "paged_attention_latent: a limit a row under a selection")
        row_lens = ctx.input("RowLens").reshape(q.shape[:2])
        walk = jnp.max(row_lens, axis=1, keepdims=True)
    cache, = _paged_cache_update((ctx.input("Cache"),), (ctx.input("Row"),),
                                 pt, lens, row_lens)
    select = None
    if ctx.has_input("Select") and pt.shape[1] * cache.shape[1] \
            > int(ctx.attr("select_top_k", 0)):
        select = ctx.input("Select")
    out = None
    interpret = _use_interpret()
    if _paged_kernel_enabled(interpret):
        if row_lens is not None:
            out = _pallas_latent_rows(q, cache, pt, walk, n_head, scale,
                                      row_lens, interpret, v_width)
        else:
            out = _pallas_paged_attention(q, cache, None, pt, lens, n_head,
                                          scale, interpret=interpret,
                                          v_width=v_width, select=select)
    if out is None:
        from paddle_tpu.profiler import runtime_metrics
        runtime_metrics.inc("gen.paged.fallback")
        out = _xla_latent_attention(q, cache, pt, walk, n_head, v_width,
                                    scale, select=select, row_lens=row_lens)
    ctx.set_output("Out", out)
    ctx.set_output("CacheOut", cache)


# ---------------------------------------------------------------------------
# gqa_attention IR op: causal self-attention over ONE prompt with grouped
# query heads (H query heads over Hkv K/V heads), the prefill-side partner of
# a grouped paged_attention.  Plain XLA; scores and softmax in float32.
# With a block width the mask is BLOCK-causal: a row sees every row of its
# own block of ``block`` positions and of the blocks before it.
# ---------------------------------------------------------------------------

def gqa_attention(q, k, v, mask, n_head, n_kv_head, scale, block=1):
    """``q`` [T, H*D]; ``k``, ``v`` [T, Hkv*D]; ``mask`` [T] (0 = pad
    row, never attended).  Query head ``h`` reads K/V head
    ``h // (H / Hkv)``.  Row ``i`` sees column ``j`` iff ``j // block <=
    i // block``: causal at ``block`` 1, bidirectional inside a block of
    ``block`` rows above it.  Returns [T, H*D] in ``q``'s type."""
    T = q.shape[0]
    D = q.shape[-1] // n_head
    g = n_head // n_kv_head
    qh = q.reshape(T, n_kv_head, g, D)
    kh, vh = k.reshape(T, n_kv_head, D), v.reshape(T, n_kv_head, D)
    sc = jnp.einsum("qkgd,tkd->kgqt", qh, kh,
                    preferred_element_type=jnp.float32) * scale
    rows = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
    if block > 1:
        rows, cols = rows // block, cols // block
    seen = (cols <= rows) & (mask > 0)[None, :]
    probs = jax.nn.softmax(jnp.where(seen, sc, NEG_INF), axis=-1)
    out = jnp.einsum("kgqt,tkd->qkgd", probs.astype(v.dtype), vh,
                     preferred_element_type=jnp.float32)
    return out.reshape(q.shape).astype(q.dtype)


@register_op("gqa_attention", infer_shape=infer_shape_unary("Q"),
             no_grad_inputs=("Mask",))
def gqa_attention_lower(ctx: LowerContext):
    """Q [1, T, H*D]; K, V [1, T, Hkv*D]; Mask [1, T].  attrs n_head,
    n_kv_head, scale, block (1: causal; L: block-causal, a row sees its
    own block of L positions whole).  Out [1, T, H*D]."""
    out = gqa_attention(ctx.input("Q")[0], ctx.input("K")[0],
                        ctx.input("V")[0], ctx.input("Mask")[0],
                        int(ctx.attr("n_head")), int(ctx.attr("n_kv_head")),
                        float(ctx.attr("scale", 1.0)),
                        int(ctx.attr("block", 1)))
    ctx.set_output("Out", out[None])


@register_op("attention_out_gate", infer_shape=infer_shape_unary())
def attention_out_gate_lower(ctx: LowerContext):
    """The element-wise output gate of an attention sublayer: X (the
    context, beside the paged kernel or the prefill's) and Gate [..., H *
    D] (a projection of the sublayer's input).  Out = X * sigmoid(Gate),
    the sigmoid in float32."""
    x = ctx.input("X")
    gate = jax.nn.sigmoid(ctx.input("Gate").astype(jnp.float32))
    ctx.set_output("Out", (x.astype(jnp.float32) * gate).astype(x.dtype))
