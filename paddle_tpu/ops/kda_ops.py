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
  S_0 + (K e^{G_end - G})^T W``.  ``e^{-G}``
  alone overflows under a strong decay, so a pair's decay is always
  formed as ONE exponential of a non-positive number: inside a sub-block
  pair by pair, across sub-blocks against the running
  sum at the later sub-block's start.  The unit triangular system is
  solved by substitution (rows inside a sub-block, then sub-blocks), not
  by a series in powers of ``A``, whose terms outgrow float32 when keys
  repeat and ``beta`` is near 2.  Pad rows of the chunk's rung get ``g``
  = 0 and ``beta`` = 0, which is the identity on the state.  The chunk
  takes the slot's state from the persistable array and leaves its own
  there; the prompt's FIRST chunk (position 0) starts from zeros whatever
  the slot held.  ONE algorithm, two lowerings, chosen by the chunk's
  shapes alone (:func:`scan_kernel_ok`; ``gen.kda.scan_lowerings.kernel``
  / ``.xla`` count which, once a compiled signature):

  - a Pallas kernel (:func:`kda_scan_kernel`) where a head is a multiple
    of 128 wide and the rung whole blocks: grid (group of
    ``_SCAN_HEADS`` heads, one after the other; stretch of rows), a
    head's q, k, v, decay
    projection and output read and written as 128-lane COLUMN BLOCKS of
    the 2-D activations where the conv and the projections left them (no
    head-major copy on either side), :func:`prepare`'s element-wise work
    done in the kernel on the block it has just read (outside it, q, k,
    v and g would be written and read back as float32: 134 MB a layer
    and chunk, twice what the kernel moves), the pair decays, both pair
    matrices and the substitution formed in VMEM, the head's [K, V]
    state carried in scratch from block to block and written ONCE, into
    the slot's row of the persistable array in place.  One layer's
    512-row chunk at the linear-attention cell's widths: 0.71 ms where
    the XLA form takes 2.13 (PERF.md section 6, PR 50), bounded by a
    block's chain of dependent matrix products, six passes each
    ("highest"), and substitution steps.
  - plain XLA (:func:`kda_scan`) everywhere else (the toy widths of the
    CPU tests, a chunk under one block), and the oracle the kernel is
    tested against: everything but ``S_0`` for all blocks at once,
    sub-blocks of ``SUB`` rows, a scan over the blocks carrying the state.
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
"highest" precision in both lowerings (a few percent of a chunk's
FLOPs).  The executor's op scope names both ops ``ptop_kda_scan*`` /
``ptop_kda_update*`` on the device trace, kernels included.  Neither
has a gradient (training through the scan is not written).
"""

from __future__ import annotations

import functools
import math

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


# ---------------------------------------------------------------------------
# the chunk-wise form as a kernel
# ---------------------------------------------------------------------------

#: rows of a chunk a grid step of the scan kernel holds in VMEM (both
#: rungs of the linear-attention cell in one), the kernel's sub-block
#: (blocks of ``BLOCK`` rows as the XLA form's; a larger sub-block is
#: fewer, larger matrix products a row: one layer, 512 rows, four heads
#: unrolled, 0.64 / 0.60 / 0.79 ms at 16 / 32 / 64, and at 32 the closest
#: of the three to the recurrence where keys repeat; blocks of 128 rows:
#: 0.60 / 0.56 / 0.75 but twice as far from it there; my chip runs, PR
#: 50), and the heads a grid step takes, one after the other in a loop:
#: unrolled, their chains of dependent products interleave (0.60 ms
#: against the loop's 0.71, two and two 0.65) but every chunk executable
#: then lowers four copies of the block's ~2,500 operations, 7 s each on
#: the chip's host, and a warm start of the cell read 22 s over the
#: parent's
_SCAN_ROWS, _SCAN_SUB, _SCAN_HEADS = 512, 32, 4


def scan_kernel_ok(x, state):
    """The scan kernel reads a head's q, k, v, decay projection and
    output as 128-lane column blocks of the 2-D activations and a head's
    [K, V] state as whole tiles, a chunk as whole blocks of
    ``BLOCK`` rows: ``x`` [C, 3 * H * D], ``state`` [slots, H, D,
    D]."""
    C, (_, H, K, V) = x.shape[0], state.shape
    return (K == V and not K % 128 and x.shape[1] == 3 * H * K
            and C >= BLOCK and not C % BLOCK)


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims, precision=_HI,
                               preferred_element_type=jnp.float32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _running_sum(g):
    """The running sum down the rows of ``g`` [Q, K] on the vector unit,
    plain float32: inside a tile of 8 rows by three shifted adds, then
    tile after tile on the row before it."""
    row, out = _iota((8, 1), 0), []
    for t in range(0, g.shape[0], 8):
        a = g[t:t + 8]
        for s in (1, 2, 4):
            a = a + jnp.where(row >= s, pltpu.roll(a, s, 0), 0.0)
        out.append(a + out[-1][7:8] if out else a)
    return jnp.concatenate(out, axis=0)


@jax.jit
def _scan_block(q, k, v, G, beta, S0):
    """One head, one block of ``BLOCK`` rows, on values in VMEM (jitted
    so that its ~2,500 operations are traced ONCE a process, whatever
    the rung and the executable; PERF.md section 6, PR 50, has what
    tracing and lowering them over and over cost a warm start):
    ``q`` (unit, scaled), ``k`` (unit), ``G`` (the running sum of the
    log-decay, <= 0) [Q, K], ``v`` [Q, V], ``beta`` [Q, 1], ``S0`` [K,
    V].  Returns ``(o [Q, V], the state after the block)``: the pair
    matrices column by column inside a sub-block (ONE exponential of a
    number <= 0 a pair; 8-row tiles, so a column costs only the tiles at
    or below its row) and against the later sub-block's start across
    them, the substitution, the products with the carried state."""
    f32 = jnp.float32
    Q, c, D = BLOCK, _SCAN_SUB, k.shape[1]
    tiles = lambda a: [a[t:t + 8] for t in range(0, a.shape[0], 8)]
    row_t, col_t = _iota((8, 1), 0), _iota((8, Q), 1)
    e = jnp.exp(G)
    both = _dot(jnp.concatenate([k * e, q * e], axis=0), S0)
    R = beta * (v - both[:Q])
    W, B = [], []
    for n in range(Q // c):
        sub = slice(n * c, (n + 1) * c)
        Gs, ks, qs, bs = G[sub], k[sub], q[sub], beta[sub]
        Rn, Bn = R[sub], jnp.zeros((c, Q), f32)
        if n:
            # against the running sum where THIS sub-block starts
            ref = G[n * c - 1:n * c]
            late = jnp.exp(Gs - ref)
            early = jnp.concatenate(
                [k[:n * c] * jnp.exp(ref - G[:n * c]),
                 jnp.zeros((Q - n * c, D), f32)], axis=0)
            cross = _dot(jnp.concatenate([ks * late, qs * late], axis=0),
                         early, (((1,), (1,)), ((), ())))       # [2c, Q]
            solved = jnp.concatenate(
                W + [jnp.zeros((Q - n * c, v.shape[1]), f32)], axis=0)
            Rn = Rn - _dot(bs * cross[:c], solved)
            Bn = cross[c:]
        # inside the sub-block, a column (one earlier row) at a time
        Gs, ks, qs, bs, Rn, Bn = (tiles(a) for a in (Gs, ks, qs, bs, Rn, Bn))
        cols = []
        for j in range(c):
            at, i = divmod(j, 8)
            col = []
            for t in range(at, c // 8):
                pair = jnp.exp(jnp.minimum(Gs[t] - Gs[at][i:i + 1], 0.0)) \
                    * ks[at][i:i + 1]
                a = bs[t] * jnp.sum(ks[t] * pair, axis=1, keepdims=True)
                b = jnp.sum(qs[t] * pair, axis=1, keepdims=True)
                if t == at:
                    a = jnp.where(row_t > i, a, 0.0)
                    b = jnp.where(row_t >= i, b, 0.0)
                col.append(a)
                Bn[t] = jnp.where(col_t == n * c + j, b, Bn[t])
            cols.append(col)
        # the substitution: row j is final once the columns before it
        # have been taken off
        for j in range(c - 1):
            at, i = divmod(j, 8)
            for t in range(at, c // 8):
                Rn[t] = Rn[t] - cols[j][t - at] * Rn[at][i:i + 1]
        W += Rn
        B += Bn
    W = jnp.concatenate(W, axis=0)
    o = both[Q:] + _dot(jnp.concatenate(B, axis=0), W)
    end = G[Q - 1:Q]
    through = jnp.sum(jnp.where(_iota((D, D), 0) == _iota((D, D), 1),
                                jnp.exp(end), 0.0),
                      axis=1, keepdims=True)                    # [K, 1]
    return o, through * S0 + _dot(k * jnp.exp(end - G), W,
                                  (((0,), (0,)), ((), ())))


def _scan_kernel(where_ref, q_ref, k_ref, v_ref, f_ref, rate_ref, bias_ref,
                 beta_ref, real_ref, s_ref, o_ref, so_ref, S, G, *, heads):
    """``heads`` heads, one after the other, ``rows`` rows of the chunk
    (grid: head group, stretch of rows; the heads' states ride in ``S``
    from stretch to stretch).  ``q_ref``, ``k_ref``, ``v_ref`` [rows,
    heads * D] are the heads' column blocks of the conv output,
    ``f_ref`` of the decay's
    projection, ``rate_ref`` / ``bias_ref`` [1, heads * D] of
    ``exp(A_log)`` a channel and ``dt_bias``; ``beta_ref`` [rows, H]
    (every head's, 0 on pad rows), ``real_ref`` [rows, 1]; ``s_ref`` /
    ``so_ref`` [1, heads, K, V] the slot's state; ``where_ref`` (slot,
    the prompt's first chunk).  A block of ``BLOCK`` rows at a
    time: :func:`prepare`'s element-wise work and the running sum of the
    log-decay, then :func:`_scan_block` a head.  The running sum goes
    through the scratch ``G``: every use has to see ONE rounding of it
    (a difference of two roundings of a sum in the thousands is a decay
    wrong in the fourth digit, and a compiler that recomputes a value
    where it is used, as XLA's does in interpret mode, makes two).
    Nothing but ``o`` and the final state leaves VMEM."""
    first, r = pl.program_id(0) * heads, pl.program_id(1)
    Q, D = BLOCK, s_ref.shape[2]

    @pl.when(r == 0)
    def _():
        S[...] = jnp.where(where_ref[1] > 0, 0.0, s_ref[0])

    def head(rows, real, betas, j):
        lanes = pl.ds(pl.multiple_of(j * D, D), D)
        g = -rate_ref[:, lanes] * real * jax.nn.softplus(
            f_ref[rows, lanes] + bias_ref[:, lanes])
        beta = jnp.sum(jnp.where(_iota(betas.shape, 1) == first + j,
                                 betas, 0.0), axis=1, keepdims=True)
        G[j] = _running_sum(g)                                  # <= 0
        o, S[j] = _scan_block(
            l2norm(q_ref[rows, lanes]) * D ** -0.5,
            l2norm(k_ref[rows, lanes]),
            v_ref[rows, lanes].astype(jnp.float32), G[j], beta, S[j])
        o_ref[rows, lanes] = o.astype(o_ref.dtype)

    def block(i, carry):
        rows = pl.ds(pl.multiple_of(i * Q, Q), Q)
        real, betas = real_ref[rows, :], beta_ref[rows, :]

        def one(j, carry):
            head(rows, real, betas, j)
            return carry

        return jax.lax.fori_loop(0, heads, one, carry)

    jax.lax.fori_loop(0, q_ref.shape[0] // Q, block, 0)

    @pl.when(r == pl.num_programs(1) - 1)
    def _():
        so_ref[0] = S[...]


@functools.partial(jax.jit, inline=True,
                   static_argnames=("beta_scale", "interpret"))
def kda_scan_kernel(x, f, b, a_log, dt_bias, state, slot, first, mask, *,
                    beta_scale, interpret=False):
    """:func:`prepare` and :func:`kda_scan` over one chunk as ONE kernel
    on the activations where they lie: ``x`` [C, 3 * H * D] (q | k | v),
    ``f`` [C, H * D], ``b`` [C, H], ``a_log`` [H], ``dt_bias`` [H * D],
    ``state`` [slots, H, D, D] float32 (aliased to the new state: only
    ``slot``'s row is written), ``first`` (the chunk starts from zeros),
    ``mask`` [C].  Returns ``(o [C, H * D] as x, new state)``."""
    C, (_, H, D, _) = x.shape[0], state.shape
    # a wider head, fewer of them a grid step: the same bytes in VMEM
    rows = math.gcd(C, _SCAN_ROWS)
    hb = math.gcd(H, max(1, _SCAN_HEADS * 128 // D))
    real = (mask.astype(jnp.float32) > 0).astype(jnp.float32)[:, None]
    beta = beta_scale * jax.nn.sigmoid(b.astype(jnp.float32)) * real
    rate = jnp.repeat(jnp.exp(a_log.astype(jnp.float32)), D)[None]
    where = jnp.stack([slot, first]).astype(jnp.int32)
    head = lambda base: pl.BlockSpec(
        (rows, hb * D), lambda h, r, w: (r, base // hb + h))
    param = pl.BlockSpec((1, hb * D), lambda h, r, w: (0, h))
    held = pl.BlockSpec((1, hb, D, D), lambda h, r, w: (w[0], h, 0, 0))
    o, new = pl.pallas_call(
        functools.partial(_scan_kernel, heads=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(H // hb, C // rows),
            in_specs=[head(0), head(H), head(2 * H), head(0), param, param,
                      pl.BlockSpec((rows, H), lambda h, r, w: (r, 0)),
                      pl.BlockSpec((rows, 1), lambda h, r, w: (r, 0)),
                      held],
            out_specs=[head(0), held],
            scratch_shapes=[pltpu.VMEM((hb, D, D), jnp.float32),
                            pltpu.VMEM((hb, BLOCK, D), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((C, H * D), x.dtype),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # (where, x, x, x, f, rate, dt_bias, beta, real, state): the
        # slot's state is updated in place
        input_output_aliases={9: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name="kda_scan",
    )(where, x, x, x, f.astype(jnp.float32), rate,
      dt_bias.astype(jnp.float32)[None], beta, real, state)
    return o, new


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
    from paddle_tpu.ops.attention_ops import _use_interpret
    from paddle_tpu.ops.ssm_ops import chunk_slot, chunk_slot_state
    from paddle_tpu.profiler import runtime_metrics
    x, state = ctx.input("X"), ctx.input("State")
    kernel = scan_kernel_ok(x[0], state)
    # which form this lowering took (fires at trace time, once per
    # compiled signature, as ``gen.moe.*_lowerings`` do)
    runtime_metrics.inc("gen.kda.scan_lowerings.kernel" if kernel
                        else "gen.kda.scan_lowerings.xla")
    if kernel:
        slot, first = chunk_slot(ctx)
        o, new = kda_scan_kernel(
            x[0], ctx.input("F")[0], ctx.input("B")[0], ctx.input("ALog"),
            ctx.input("DtBias"), state, slot, first, ctx.input("Mask")[0],
            beta_scale=float(ctx.attr("beta_scale", 1.0)),
            interpret=_use_interpret())
        ctx.set_output("Out", o[None])
        ctx.set_output("StateOut", new)
        return
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
