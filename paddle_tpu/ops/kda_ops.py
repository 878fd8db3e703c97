"""Gated delta-rule linear attention with a per-channel decay (KDA, the
layer of Kimi Linear, arXiv:2510.26692) for the mixers of
``models/hybrid_moe.py``.

A KDA layer's cache is not rows of a page pool but a FIXED per-slot
state: a matrix ``S`` a head, ``[slots, heads, key, value]`` (float32),
beside the window of the causal conv in front of it (``ssm_ops``).  Per
head, with ``q`` and ``k`` unit vectors (``q`` times ``key^-1/2``)

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t
    alpha_t = exp(g_t)   g_t = -exp(A_log) softplus(f_t + dt_bias)  [key]
    beta_t = beta_scale sigmoid(b_t)        (2: eigenvalues in [-1, 1])

Two forms of the same recurrence

* ``kda_scan`` (prefill, ONE CHUNK of one prompt): the chunk-wise form
  in blocks of ``BLOCK`` rows.  With ``G`` the running sum of ``g``
  inside a block, ``A_ij = (k_i e^{G_i}) . (k_j e^{-G_j})`` for ``j < i``
  and ``B_ij`` the same with ``q_i`` for ``j <= i``, the block's
  pseudo-values ``W`` solve ``(I + Diag(beta) A) W = Diag(beta) (V - (K
  e^G) S_0)``, and ``O = (Q e^G) S_0 + B W``, ``S_end = Diag(e^{G_end})
  S_0 + (K e^{G_end - G})^T W``.  Everything but ``S_0`` is computed for
  all blocks at once; a scan over the blocks carries the state.  ``e^{-G}``
  alone overflows under a strong decay, so a pair's decay is always
  formed as ONE exponential of a non-positive number: inside a sub-block
  of ``SUB`` rows pair by pair, across sub-blocks against the running
  sum at the later sub-block's start.  The unit triangular system is
  solved by substitution (rows inside a sub-block, then sub-blocks), not
  by a series in powers of ``A``, whose terms outgrow float32 when keys
  repeat and ``beta`` is near 2.  Pad rows of the chunk's rung get ``g``
  = 0 and ``beta`` = 0, which is the identity on the state.  The chunk
  takes the slot's state from the persistable array and leaves its own
  there; the prompt's FIRST chunk (position 0) starts from zeros whatever
  the slot held.
* ``kda_update`` (decode, one token for every slot): the recurrence
  itself on the persistable state, in place; a slot with ``lens`` 0 keeps
  its state.  A Pallas kernel where the state's shape allows
  (:func:`update_kernel_ok`): plain XLA reads the state twice (once for
  ``S^T k``, once to update it and for ``S^T q``) and writes it once;
  the kernel holds ``_KERNEL_HEADS`` heads of one slot in VMEM and reads
  and writes it once (0.82 ms a layer for 537 MB at the cell's widths,
  where XLA's two fusions take 1.18: PERF.md section 6, PR 49), with the
  same float32 arithmetic in the same order, bit for bit.

The state, the decay and every sum of the recurrence are float32
whatever the activations' type; the chunk form's products run at
"highest" precision (a few percent of a chunk's FLOPs).  The scan is a
plain XLA lowering; the executor's op scope names both
``ptop_kda_scan*`` / ``ptop_kda_update*`` on the device trace.  Neither
has a gradient (training through the scan is not written).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.registry import (ShapeInferenceSkip, infer_shape_unary,
                                     register_op)

_HI = jax.lax.Precision.HIGHEST

#: rows of a block of the chunk-wise form, and of the sub-blocks inside
#: which a pair's decay is formed pair by pair
BLOCK, SUB = 64, 16
#: blocks whose own terms are computed in one go: the fewer, the less a
#: row costs on the chip (1024 rows, one layer: 6.77 / 6.09 / 5.21 / 4.92
#: ms at 16 / 8 / 4 / 2; my chip runs, PR 49)
GROUP = 2


def l2norm(x, eps=1e-6):
    """``x / sqrt(sum(x^2) + eps)`` over the last axis, float32."""
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True) + eps)


def prepare(qkv, f, b, a_log, dt_bias, n_head, beta_scale):
    """What the recurrence takes, from the layer's activations, float32:
    ``qkv`` [..., 3 * H * D] (after the conv; q | k | v), ``f`` [..., H *
    D] (the decay's projection), ``b`` [..., H].  Returns ``q`` (unit,
    times ``D^-1/2``), ``k`` (unit), ``v``, ``g`` (log-decay, <= 0), each
    [..., H, D], and ``beta`` [..., H]."""
    lead, H = qkv.shape[:-1], n_head
    D = qkv.shape[-1] // (3 * H)
    q, k, v = (qkv[..., j * H * D:(j + 1) * H * D].reshape(lead + (H, D))
               for j in range(3))
    rate = jnp.exp(a_log.astype(jnp.float32))[:, None]
    g = -rate * jax.nn.softplus(
        f.astype(jnp.float32).reshape(lead + (H, D))
        + dt_bias.astype(jnp.float32).reshape(H, D))
    beta = beta_scale * jax.nn.sigmoid(b.astype(jnp.float32))
    return (l2norm(q) * D ** -0.5, l2norm(k), v.astype(jnp.float32), g, beta)


def kda_step(S, q, k, v, g, beta):
    """The recurrence, one token: ``S`` [..., K, V]; ``q``, ``k``, ``g``
    [..., K]; ``v`` [..., V]; ``beta`` [...].  Returns ``(o [..., V],
    S_new)``."""
    Sd = S * jnp.exp(g)[..., :, None]
    u = v - jnp.sum(Sd * k[..., :, None], axis=-2)
    S_new = Sd + (beta[..., None] * k)[..., :, None] * u[..., None, :]
    return jnp.sum(S_new * q[..., :, None], axis=-2), S_new


#: heads a grid step of the update kernel takes: their four columns (decay,
#: k, beta k, q) fill the 128 lanes of one side array
_KERNEL_HEADS = 32


def _update_kernel(lens_ref, cols_ref, v_ref, s_ref, o_ref, so_ref, *, heads):
    """One slot's ``heads`` heads: ``cols_ref`` [1, 1, K, 4 * heads] (a
    head's decay, k, beta k and q as COLUMNS, the key channel on the
    sublanes as in the state), ``v_ref`` / ``o_ref`` [1, heads, V],
    ``s_ref`` / ``so_ref`` [1, heads, K, V].  Every contraction runs
    over the key channel, down the sublanes, so each product is a
    broadcast and a sum of whole vregs; the state is read once and
    written once."""
    live = lens_ref[pl.program_id(0)] > 0

    @pl.when(live)
    def _():
        K, V = s_ref.shape[2], s_ref.shape[3]
        for j in range(heads):
            col = lambda i: jnp.broadcast_to(
                cols_ref[0, 0, :, 4 * j + i:4 * j + i + 1], (K, V))
            Sd = s_ref[0, j] * col(0)
            u = v_ref[0, j:j + 1, :] - jnp.sum(Sd * col(1), axis=0,
                                               keepdims=True)
            Sn = Sd + col(2) * u
            so_ref[0, j] = Sn
            o_ref[0, j:j + 1, :] = jnp.sum(Sn * col(3), axis=0,
                                           keepdims=True)

    @pl.when(jnp.logical_not(live))
    def _():
        so_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def update_kernel_ok(state, interpret):
    """The kernel takes ``_KERNEL_HEADS`` heads a grid step and a head's
    [K, V] state as whole (8, 128) tiles."""
    _, H, K, V = state.shape
    return not H % _KERNEL_HEADS and (
        interpret or not (K % 8 or V % 128))


@functools.partial(jax.jit, inline=True, static_argnames=("interpret",))
def kda_update_kernel(state, q, k, v, g, beta, lens, *, interpret=False):
    """:func:`kda_step` over every slot as ONE pass over the state:
    ``state`` [S, H, K, V] float32 (aliased to the new state), ``q``,
    ``k``, ``g`` [S, H, K], ``v`` [S, H, V], ``beta`` [S, H], ``lens``
    [S] int32 (0 = free slot: its state stands, its output is zeros).
    Returns ``(o [S, H, V], new state)``."""
    S, H, K, V = state.shape
    hb = _KERNEL_HEADS
    cols = jnp.stack([jnp.exp(g), k, beta[..., None] * k, q], axis=-1)
    cols = cols.reshape(S, H // hb, hb, K, 4).transpose(0, 1, 3, 2, 4) \
        .reshape(S, H // hb, K, 4 * hb)
    o, new = pl.pallas_call(
        functools.partial(_update_kernel, heads=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S, H // hb),
            in_specs=[
                pl.BlockSpec((1, 1, K, 4 * hb), lambda s, h, ln: (s, h, 0, 0)),
                pl.BlockSpec((1, hb, V), lambda s, h, ln: (s, h, 0)),
                pl.BlockSpec((1, hb, K, V), lambda s, h, ln: (s, h, 0, 0))],
            out_specs=[
                pl.BlockSpec((1, hb, V), lambda s, h, ln: (s, h, 0)),
                pl.BlockSpec((1, hb, K, V), lambda s, h, ln: (s, h, 0, 0))]),
        out_shape=[jax.ShapeDtypeStruct((S, H, V), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # (lens, cols, v, state): the state is updated in place
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name="kda_update",
    )(lens.astype(jnp.int32), cols, v, state)
    return o, new


def _solve_unit_lower(L, rhs):
    """``(I + L)^-1 rhs`` for ``L`` [..., n, c, n, c] strictly lower
    triangular as a ``(n c) x (n c)`` matrix (what lies on or above its
    diagonal is not read) and ``rhs`` [..., n, c, d]: substitution row by
    row inside the diagonal sub-blocks (all of them at once), then
    sub-block by sub-block."""
    n, c = L.shape[-2], L.shape[-1]
    diag = jnp.stack([L[..., b, :, b, :] for b in range(n)], axis=-3)
    # the inverse of every diagonal sub-block: its rows, one at a time
    eye = jnp.eye(c, dtype=L.dtype)
    rows = [jnp.broadcast_to(eye[0], diag.shape[:-2] + (c,))]
    for i in range(1, c):
        rows.append(eye[i] - sum(diag[..., i, j, None] * rows[j]
                                 for j in range(i)))
    inv = jnp.stack(rows, axis=-2)
    out = []
    for b in range(n):
        r = rhs[..., b, :, :]
        for a in range(b):
            r = r - jnp.matmul(L[..., b, :, a, :], out[a], precision=_HI)
        out.append(jnp.matmul(inv[..., b, :, :], r, precision=_HI))
    return jnp.stack(out, axis=-3)


def _block_terms(q, k, v, g, beta):
    """Of every block at once (``q``, ``k``, ``g`` [N, H, Q, K], ``v`` [N,
    H, Q, V], ``beta`` [N, H, Q]) all that does not need the carried
    state: ``(U [.., Q, V], Wk [.., Q, K], Qg [.., Q, K], B [.., Q, Q], Kend
    [.., Q, K], through [.., K])`` with ``W = U - Wk S_0``, ``O = Qg S_0 +
    B W``, ``S_end = through * S_0 + Kend^T W``."""
    N, H, Q, K = k.shape
    n, c = Q // SUB, SUB
    G = jnp.cumsum(g, axis=2)                               # <= 0
    sub = lambda a: a.reshape(N, H, n, c, a.shape[-1])
    Gs, ks, qs = sub(G), sub(k), sub(q)
    # inside a sub-block, pair by pair: exp(G_i - G_j) for j <= i
    rows = jnp.arange(c)
    lower = (rows[:, None] >= rows[None, :])[..., None]     # [c, c, 1]
    dec = jnp.exp(jnp.where(lower, Gs[..., :, None, :] - Gs[..., None, :, :],
                            -jnp.inf))                      # [.., c, c, K]
    a_in = jnp.sum(ks[..., :, None, :] * ks[..., None, :, :] * dec, axis=-1)
    b_in = jnp.sum(qs[..., :, None, :] * ks[..., None, :, :] * dec, axis=-1)
    # across sub-blocks: both factors against the running sum where the
    # LATER sub-block starts, each the exponential of a number <= 0
    ref = jnp.concatenate([jnp.zeros_like(Gs[:, :, :1, -1]),
                           Gs[:, :, :-1, -1]], axis=2)      # [N, H, n, K]
    late = jnp.exp(Gs - ref[..., None, :])                  # [.., n, c, K]
    before = (jnp.arange(Q)[None, :] < (jnp.arange(n) * c)[:, None])
    early = k[:, :, None] * jnp.exp(jnp.where(
        before[..., None], ref[..., None, :] - G[:, :, None], -jnp.inf))
    cross = lambda x: jnp.einsum("zhbik,zhbjk->zhbij", x * late, early,
                                 precision=_HI).reshape(N, H, n, c, n, c)
    same = jnp.eye(n, dtype=k.dtype)[:, None, :, None]      # [n, 1, n, 1]
    strict = (rows[:, None] > rows[None, :])
    A = cross(ks) + (a_in * strict)[..., None, :] * same
    B = cross(qs) + (b_in * lower[..., 0])[..., None, :] * same
    e = jnp.exp(G)
    rhs = sub(beta[..., None] * jnp.concatenate([v, k * e], axis=-1))
    sol = _solve_unit_lower(sub(beta[..., None])[..., None, :] * A, rhs)
    sol = sol.reshape(N, H, Q, -1)
    V = v.shape[-1]
    end = G[:, :, -1:, :]
    return (sol[..., :V], sol[..., V:], q * e, B.reshape(N, H, Q, Q),
            k * jnp.exp(end - G), jnp.exp(end[:, :, 0]))


def kda_scan(q, k, v, g, beta, S0, mask):
    """The chunk-wise form over one chunk.  ``q``, ``k``, ``g`` [T, H,
    K], ``v`` [T, H, V], ``beta`` [T, H] (as :func:`prepare` gives
    them), ``S0`` [H, K, V] float32, ``mask`` [T] (0 = pad row: the state
    stands still).  Returns ``o`` [T, H, V] float32 and the state after
    the last real row."""
    T, H, K = k.shape
    real = (mask.astype(jnp.float32) > 0)
    g = g * real[:, None, None]
    beta = beta * real[:, None]
    Q = BLOCK if T > BLOCK else -(-T // SUB) * SUB
    pad = -T % Q
    N = (T + pad) // Q

    def blocks(a):      # [T, H, ...] -> [N, H, Q, ...]
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        a = a.reshape((N, Q) + a.shape[1:])
        return jnp.moveaxis(a, 1, 2)

    # the blocks' own terms, GROUP blocks at a time (all of a long
    # chunk's at once cost more a row: PERF.md section 6, PR 49); blocks
    # of zeros fill the last group up
    per = min(GROUP, N)

    def groups(a):      # [N, H, Q, ...] -> [N / per, per, H, Q, ...]
        a = jnp.pad(a, ((0, -N % per),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape((-1, per) + a.shape[1:])

    terms = jax.lax.map(lambda part: _block_terms(*part),
                        tuple(groups(blocks(a)) for a in (q, k, v, g, beta)))
    terms = tuple(a.reshape((-1,) + a.shape[2:])[:N] for a in terms)

    def carry(S, blk):
        U, Wk, Qg, B, Kend, through = blk
        W = U - jnp.matmul(Wk, S, precision=_HI)
        o = jnp.matmul(Qg, S, precision=_HI) + jnp.matmul(B, W,
                                                           precision=_HI)
        S = through[..., None] * S + jnp.einsum("hjk,hjv->hkv", Kend, W,
                                                precision=_HI)
        return S, o

    S_end, o = jax.lax.scan(carry, S0.astype(jnp.float32), terms)
    o = jnp.moveaxis(o, 1, 2).reshape(N * Q, H, -1)[:T]
    return o, S_end


def gated_head_rms_norm(o, gate, scale, n_head, eps):
    """``RMSNorm_head(o) * scale * sigmoid(gate)``: the last axis is
    normalised a head at a time (``scale`` [head width], shared by the
    heads), then gated."""
    lead = o.shape[:-1]
    x = o.astype(jnp.float32).reshape(lead + (n_head, -1))
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)
    return (x.reshape(o.shape)
            * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(o.dtype)


# ---------------------------------------------------------------------------
# IR ops
# ---------------------------------------------------------------------------

def _prepared(ctx, qkv, f, b):
    return prepare(qkv, f, b, ctx.input("ALog"), ctx.input("DtBias"),
                   int(ctx.attr("n_head")), float(ctx.attr("beta_scale", 1.0)))


def _infer_kda(op, block):
    x = block.var(op.input("X")[0])
    if x.shape is None:
        raise ShapeInferenceSkip()
    out = block.var(op.output("Out")[0])
    out.shape = tuple(x.shape[:-1]) + (x.shape[-1] // 3,)
    out.dtype = x.dtype
    # StateOut aliases the persistable state (in-place update)


@register_op("kda_scan", infer_shape=_infer_kda, no_gradient=True,
             stateful_outputs=("StateOut",))
def kda_scan_lower(ctx):
    """ONE CHUNK of a prompt.  X [1, C, 3 * H * D] (conv output: q | k |
    v); F [1, C, H * D] (the decay's projection); B [1, C, H]; ALog [H];
    DtBias [H * D]; State [num_slots, H, D, D] persistable float32; Slot
    [1, 1] int32; Pos [1, C] int32 the rows' positions ``start ..``; Mask
    [1, C] (1 = a real row, real rows first).  attrs n_head, beta_scale.
    The chunk starts from the slot's state (from zeros where it is the
    prompt's first: position 0 and a real row) and leaves the state after
    its last real row there.  Out [1, C, H * D]; StateOut names the state
    array itself."""
    from paddle_tpu.ops.ssm_ops import chunk_slot_state
    x, state = ctx.input("X"), ctx.input("State")
    q, k, v, g, beta = _prepared(ctx, x[0], ctx.input("F")[0],
                                 ctx.input("B")[0])
    slot, held = chunk_slot_state(ctx, state)
    o, S = kda_scan(q, k, v, g, beta, held, ctx.input("Mask")[0])
    ctx.set_output("Out", o.reshape(x.shape[:2] + (-1,)).astype(x.dtype))
    ctx.set_output("StateOut", jax.lax.dynamic_update_index_in_dim(
        state, S.astype(state.dtype), slot, 0))


@register_op("kda_update", infer_shape=_infer_kda, no_gradient=True,
             stateful_outputs=("StateOut",))
def kda_update_lower(ctx):
    """One token for every slot.  X [S, 1, 3 * H * D]; F [S, 1, H * D]; B
    [S, 1, H]; ALog, DtBias; State [S, H, D, D] persistable float32; Lens
    [S, 1] int32 (0 = free slot: its state stands).  Out [S, 1, H * D];
    StateOut names the state array itself (in place)."""
    x = ctx.input("X")
    S = x.shape[0]
    q, k, v, g, beta = _prepared(
        ctx, x.reshape(S, -1), ctx.input("F").reshape(S, -1),
        ctx.input("B").reshape(S, -1))
    state, lens = ctx.input("State"), ctx.input("Lens")[:, 0]
    from paddle_tpu.ops.attention_ops import _use_interpret
    if update_kernel_ok(state, _use_interpret()):
        o, new = kda_update_kernel(state, q, k, v, g, beta, lens,
                                   interpret=_use_interpret())
    else:
        o, new = kda_step(state, q, k, v, g, beta)
        new = jnp.where((lens > 0)[:, None, None, None], new, state)
    ctx.set_output("Out", o.reshape(S, 1, -1).astype(x.dtype))
    ctx.set_output("StateOut", new)


@register_op("kda_gated_norm", infer_shape=infer_shape_unary(),
             no_gradient=True)
def kda_gated_norm_lower(ctx):
    """X, Gate [..., H * D], Scale [D]; attrs n_head, epsilon."""
    ctx.set_output("Out", gated_head_rms_norm(
        ctx.input("X"), ctx.input("Gate"), ctx.input("Scale"),
        int(ctx.attr("n_head")), float(ctx.attr("epsilon", 1e-5))))
