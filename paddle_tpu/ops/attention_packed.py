"""Single-pass attention kernels over PACKED operands, ``[B, S, H*D]``.

The projection ``fc``s of ``models.transformer.multi_head_attention`` emit
``[B, S, H*D]`` and the output projection reads ``[B, S, H*Dv]``.  The
``[B, H, S, D]`` kernels of ``attention_ops`` force four transposes a
module (and their four mirrors in the backward), hand every kernel block
a 64-wide last dimension, and keep their side arrays (residual, key mask,
``delta``) with a last dimension of 1 or 2, which the ``(8, 128)`` tiling
pads 64-128x in HBM (134 MB an array at B32 x S1024 x H8 instead of 1-2).

These kernels take the packed layout as it is.  A program owns one batch
row, ``rows`` query rows and a block of whole 128-lane GROUPS of the
feature axis; a group holds ``G = 128 // D`` heads (2 at D = 64).  A
head's products run on the whole 128-lane slice with the other heads'
lanes zeroed on ONE operand: at D < 128 the MXU's depth is padded to 128
anyway, so this costs what a 64-wide product costs and no slice ever
leaves the lane tiling.  Results are put together with lane selects and
stored 128 lanes wide.

Side arrays, all with S on the lane axis:
  * key mask ``[B, 1, S]``, read once a program, not broadcast over heads;
  * residual ``[B, H*D/128, 8, S]`` float32: sublane ``2j`` is head ``j``
    of the group's running max, ``2j + 1`` its log-denominator (kept
    apart for the reason the streaming kernel gives: on a fully masked
    row ``m ~ -1e9`` swallows ``log l``); 4 MB at B32 x S1024 x H8;
  * ``delta = sum(do * o)`` is computed inside the backward program from
    the blocks it holds: no array.

``causal``: a row block's keys end at its diagonal, so a program works on
``[rows, (i + 1) * rows]`` scores (static shapes: the row-block index is
matched against its ``n`` possible values with ``pl.when``), which skips
the key blocks whose probabilities are exactly 0.  They are exactly 0
only on a row that has a live key: the masks are ADDITIVE (``-1e9`` a
mask, as ``_reference_attention``), so a row whose every permitted key
is padding softmaxes over everything that carries ONE mask, the keys
above its diagonal among them.  Such a row exists only where key 0 is
padding (under ``causal`` every row permits key 0), which the program
reads from a prefetched scalar: that batch row takes the whole square,
and comes out as the reference gives it.

Same arithmetic as the ``[B, H, S, D]`` streaming kernels: scores and
softmax float32, products bfloat16 (the operands' type) with float32
accumulation, ``p = exp((s - m) - log l)`` in the backward.
"""

from __future__ import annotations

import functools

import jax
import jax.extend
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e9
_LANES = 128
MAX_S = 1024
# The shortest sequence the kernels are taken for: one lane tile of keys.
# Measured, not a knob (``bench_attention.py``, B512 x S128 x H8 x D64, one
# module forward + backward on a v5e, my chip run, PR 56;
# ``BENCH_ATTENTION.md``): packed 1.90 ms against 3.00 composed and 6.32
# for the [B, H, S, D] kernels, so nothing the tiling admits is left out.
MIN_S = 128
# Query rows a program (a [rows, S] float32 score tile and its three
# backward companions stay inside VMEM) and feature lanes a program (whole
# 128-lane groups: more groups = fewer grid steps, and the mask bias
# built once for more heads).  Measured on a v5e, forward + backward of
# one module at B32 x S1024 x H8 x D64 (my chip runs, PR 39,
# ``bench_attention.py --blocks``; ``PERF.md`` section 6; rows:lanes,
# ms): not causal 512:512 2.79, 512:256 2.84, 256:512 2.91, 512:128 2.96,
# 1024:512 3.05, 256:128 3.26; causal 256:256 2.13, 512:256 2.26, 512:128
# 2.35, 256:128 2.36, 1024:512 3.05 (nothing skipped), 512:512 3.72,
# 256:512 4.72.  A causal program carries one text a row block (and one
# for the whole square), every head of its lane block written out in
# each: at S 1024 the widest lane block's text is what costs (at S 512
# the same blocking is the fastest: 256:512 1.32 against 256:256 1.48),
# so causal calls above ``CAUSAL_WIDE_MAX_S`` take half of it.
ROWS = 512
CAUSAL_ROWS = 256       # finer: more of the dead half is skipped
LANE_BLOCK = 512
CAUSAL_WIDE_MAX_S = 512
_VMEM_LIMIT = 64 << 20


def plan(q_shape, k_shape, v_shape, n_head, causal=False):
    """``(rows, lane block)`` if the packed kernels take these
    ``[B, S, H*D]`` shapes, else None (``attention_ops.attention_lowering``,
    the one caller, then unpacks to ``[B, H, S, D]`` for the streaming
    kernels or the reference)."""
    if len(q_shape) != 3 or not n_head:
        return None
    B, S, HD = q_shape
    if k_shape != q_shape or v_shape != q_shape:   # S_q == S_k, D_k == D_v
        return None
    if S % _LANES or not MIN_S <= S <= MAX_S or HD % n_head \
            or HD % _LANES:
        return None
    if HD // n_head not in (32, 64, 128):
        return None
    prefer = CAUSAL_ROWS if causal else ROWS
    rows = next(r for r in (prefer, 256, _LANES) if S % r == 0)
    widest = LANE_BLOCK
    if causal and S > CAUSAL_WIDE_MAX_S:
        widest = max(LANE_BLOCK // 2, _LANES)
    lane_block = next(w for w in (widest, 256, _LANES)
                      if w <= widest and HD % w == 0)
    return rows, lane_block


def _bias(mask_ref, causal, i, rows, L):
    """float32 additive bias for query rows ``[i * rows, +rows)`` over
    keys ``[0, L)``: ``[1, L]``, or ``[rows, L]`` with the triangle."""
    bias = (1.0 - mask_ref[0][:, :L].astype(jnp.float32)) * NEG_INF
    if causal:
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, L), 0) + i * rows
        col = jax.lax.broadcasted_iota(jnp.int32, (rows, L), 1)
        bias = bias + jax.lax.select(
            col > row, jnp.full((rows, L), NEG_INF, jnp.float32),
            jnp.zeros((rows, L), jnp.float32))
    return bias


def _head_lanes(lane, D):
    """Lane selectors, ``lane``'s shape, of the ``128 // D`` heads of a
    group (``[None]`` where a head fills the group).  Full-shape masks and
    ``lax.select`` throughout: Mosaic broadcasts a bool through an int
    compare, and every ``jnp.where`` is a nested jit to trace, which at 8
    heads a program is seconds of every process start."""
    if D == _LANES:
        return [None]
    return [(lane >= j * D) & (lane < (j + 1) * D)
            for j in range(_LANES // D)]


def _pick(sel, x, other=None):
    """``x`` on the selected lanes, ``other`` (zeros) elsewhere."""
    if sel is None:
        return x
    return jax.lax.select(sel, x,
                          jnp.zeros_like(x) if other is None else other)


_NT = (((1,), (1,)), ((), ()))      # a @ b^T
_NN = (((1,), (0,)), ((), ()))      # a @ b
_TN = (((0,), (0,)), ((), ()))      # a^T @ b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dimension_numbers=dims,
                               preferred_element_type=jnp.float32)


def _each_row_block(first_ref, n_rb, rows, S, body):
    """Run ``body(i, L)`` for this program's row block ``i`` over its
    keys ``[0, L)``.  Under ``causal`` (``first_ref`` given: key 0's
    mask a batch row) the extent ends at the diagonal, so each value of
    ``i`` is a program text of its own; a batch row whose key 0 is
    padding takes the whole square (module docstring)."""
    i = pl.program_id(2)
    if first_ref is None:
        body(i, S)
        return
    live = (first_ref[pl.program_id(0)] != 0) if n_rb > 1 else True
    for i_s in range(n_rb):
        pl.when(live & (i == i_s))(
            functools.partial(body, i_s, (i_s + 1) * rows))
    if n_rb > 1:    # else the one text above is the whole square already
        pl.when(jnp.logical_not(live))(functools.partial(body, i, S))


def _fwd_kernel(*refs, causal, scale, rows, n_rb, S, D, groups):
    first_ref = refs[0] if causal else None
    q_ref, k_ref, v_ref, mask_ref, o_ref, res_ref = refs[int(causal):]

    def body(i, L):
        bias = _bias(mask_ref, causal, i, rows, L)
        lane = jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1)
        sels = _head_lanes(lane, D)
        wide = (rows, _LANES)
        for g in range(groups):
            lanes = slice(g * _LANES, (g + 1) * _LANES)
            q = q_ref[0, :, lanes]                    # [rows, 128]
            k = k_ref[0, :L, lanes]                   # [L, 128]
            v = v_ref[0, :L, lanes]
            o = None
            stats = jnp.zeros(wide, jnp.float32)
            for j, sel in enumerate(sels):
                s = _dot(_pick(sel, q), k, _NT) * scale + bias
                m = jnp.max(s, axis=-1, keepdims=True)
                p = jnp.exp(s - m)
                l = jnp.sum(p, axis=-1, keepdims=True)
                oh = _dot(p.astype(v.dtype), v, _NN) / l
                o = oh if o is None else _pick(sel, oh, o)
                stats = jax.lax.select(lane == 2 * j,
                                       jnp.broadcast_to(m, wide), stats)
                stats = jax.lax.select(lane == 2 * j + 1,
                                       jnp.broadcast_to(jnp.log(l), wide),
                                       stats)
            o_ref[0, :, lanes] = o.astype(o_ref.dtype)
            # the per-row columns leave lane-dense: [rows, 128] -> [128,
            # rows], of which the first 8 sublanes carry the statistics
            res_ref[0, g] = stats.T[:8]

    _each_row_block(first_ref, n_rb, rows, S, body)


def _bwd_kernel(*refs, causal, scale, rows, n_rb, S, D, groups):
    first_ref = refs[0] if causal else None
    (q_ref, k_ref, v_ref, mask_ref, o_ref, do_ref, res_ref,
     dq_ref, dk_ref, dv_ref, dk_acc, dv_acc) = refs[int(causal):]
    i = pl.program_id(2)

    if n_rb > 1:
        @pl.when(i == 0)
        def _():
            dk_acc[...] = jnp.zeros_like(dk_acc)
            dv_acc[...] = jnp.zeros_like(dv_acc)

    def body(i, L):
        bias = _bias(mask_ref, causal, i, rows, L)
        sels = _head_lanes(
            jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1), D)
        pad = jnp.zeros((_LANES - 8, rows), jnp.float32)
        for g in range(groups):
            lanes = slice(g * _LANES, (g + 1) * _LANES)
            q = q_ref[0, :, lanes]
            do = do_ref[0, :, lanes]
            k = k_ref[0, :L, lanes]
            v = v_ref[0, :L, lanes]
            prod = do.astype(jnp.float32) * \
                o_ref[0, :, lanes].astype(jnp.float32)
            # [8, rows] statistics back to per-row columns
            stats = jnp.concatenate([res_ref[0, g], pad], axis=0).T
            dq = dk = dv = None
            for j, sel in enumerate(sels):
                qh, doh = _pick(sel, q), _pick(sel, do)
                m = stats[:, 2 * j:2 * j + 1]
                logl = stats[:, 2 * j + 1:2 * j + 2]
                delta = jnp.sum(_pick(sel, prod), axis=-1, keepdims=True)
                s = _dot(qh, k, _NT) * scale + bias
                # (s - m) first so the +-1e9 magnitudes cancel exactly
                p = jnp.exp((s - m) - logl)
                dvh = _dot(p.astype(do.dtype), doh, _TN)     # [L, 128]
                dp = _dot(doh, v, _NT)
                ds = (p * (dp - delta) * scale).astype(q.dtype)
                dqh = _dot(ds, k, _NN)                       # [rows, 128]
                dkh = _dot(ds, qh, _TN)                      # [L, 128]
                # dvh, dkh are zero outside the head's lanes already
                dq = dqh if dq is None else _pick(sel, dqh, dq)
                dk = dkh if dk is None else dk + dkh
                dv = dvh if dv is None else dv + dvh
            dq_ref[0, :, lanes] = dq.astype(dq_ref.dtype)
            if n_rb == 1:
                dk_ref[0, :, lanes] = dk.astype(dk_ref.dtype)
                dv_ref[0, :, lanes] = dv.astype(dv_ref.dtype)
            else:
                dk_acc[:L, lanes] += dk
                dv_acc[:L, lanes] += dv

    _each_row_block(first_ref, n_rb, rows, S, body)

    if n_rb > 1:
        @pl.when(i == n_rb - 1)
        def _():
            dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
            dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _specs(S, rows, lane_block):
    groups = lane_block // _LANES

    def vm(shape, index):
        return pl.BlockSpec(shape, index, memory_space=pltpu.VMEM)

    # (*_: the prefetched scalar ref of the causal calls)
    row_blk = vm((1, rows, lane_block), lambda b, c, i, *_: (b, i, c))
    all_keys = vm((1, S, lane_block), lambda b, c, i, *_: (b, 0, c))
    mask = vm((1, 1, S), lambda b, c, i, *_: (b, 0, 0))
    res = vm((1, groups, 8, rows), lambda b, c, i, *_: (b, c, 0, i))
    return row_blk, all_keys, mask, res


def res_shape(B, S, HD):
    return (B, HD // _LANES, 8, S)


@functools.lru_cache(maxsize=64)
def _program(backward, shape, dtype, mask_dtype, causal, scale, n_head,
             blocks, interpret):
    """One kernel's ``pallas_call`` at one signature, traced ONCE to a
    closed jaxpr.  A program text holds every head of its lane block
    written out, and a model calls the same kernel a dozen times (12 + 6
    modules of the Transformer): tracing it at each call site was seconds
    of every process start, compile cache or no.  Evaluating the jaxpr at
    a call site binds the same ``pallas_call`` equation under THAT site's
    name scopes, so the device trace still names each call by its op."""
    B, S, HD = shape
    rows, lane_block = blocks
    row_blk, all_keys, mask, res = _specs(S, rows, lane_block)
    x = jax.ShapeDtypeStruct(shape, dtype)
    operands = [x, x, x, jax.ShapeDtypeStruct((B, 1, S), mask_dtype)]
    in_specs = [row_blk, all_keys, all_keys, mask]
    scratch = []
    if backward:
        operands += [x, x, jax.ShapeDtypeStruct(res_shape(*shape),
                                                jnp.float32)]
        in_specs += [row_blk, row_blk, res]
        out_specs, out_shape = [row_blk, all_keys, all_keys], [x, x, x]
        # dk, dv accumulate in float32 over the row blocks
        acc = (S, lane_block) if S > rows else (8, _LANES)
        scratch = [pltpu.VMEM(acc, jnp.float32)] * 2
    else:
        out_specs = [row_blk, res]
        out_shape = [x, jax.ShapeDtypeStruct(res_shape(*shape),
                                             jnp.float32)]
    if causal:      # key 0's mask a batch row, read as a scalar
        operands.insert(0, jax.ShapeDtypeStruct((B,), jnp.int32))
    call = pl.pallas_call(
        functools.partial(
            _bwd_kernel if backward else _fwd_kernel, causal=causal,
            scale=scale, rows=rows, n_rb=S // rows, S=S, D=HD // n_head,
            groups=lane_block // _LANES),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=int(causal),
            grid=(B, HD // lane_block, S // rows),
            in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret)
    return jax.extend.core.jaxpr_as_fun(jax.make_jaxpr(call)(*operands))


def _run(backward, q, k_mask, rest, causal, scale, n_head, blocks,
         interpret):
    program = _program(backward, q.shape, jnp.dtype(q.dtype),
                       jnp.dtype(k_mask.dtype), causal, float(scale),
                       n_head, blocks, interpret)
    first = ((k_mask[:, 0] != 0).astype(jnp.int32),) if causal else ()
    return program(*first, q, *rest[:2], k_mask[:, None, :], *rest[2:])


def attention(q, k, v, k_mask, causal, scale, n_head, blocks,
              interpret=False):
    """``q, k, v`` ``[B, S, H*D]``, ``k_mask`` ``[B, S]`` (1 = attend);
    returns ``(out [B, S, H*D], res)``.  ``blocks`` from ``plan``."""
    return _run(False, q, k_mask, (k, v), causal, scale, n_head, blocks,
                interpret)


def attention_bwd(q, k, v, k_mask, o, res, g, causal, scale, n_head,
                  blocks, interpret=False):
    """dq, dk, dv ``[B, S, H*D]`` in ONE kernel from the saved output and
    residual."""
    return _run(True, q, k_mask, (k, v, o, g, res), causal, scale, n_head,
                blocks, interpret)
