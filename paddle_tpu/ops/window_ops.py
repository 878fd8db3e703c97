"""Sliding-window attention with a sink beside full causal attention, for
a model whose layers are of two kinds (``models/window_moe.py``: the
``mimo_v2_flash`` layout), with key heads and value heads of different
widths and a rotary on the LEADING lanes of a head.

* ``rope_partial`` turns the first ``rope_dim`` lanes of every head by
  the row's position (pair ``i`` = lanes ``(i, i + rope_dim / 2)``, plain
  ``theta^(-2i/rope_dim)``), passes the rest, and can lay each head out
  ``pad_to`` lanes wide (zeros behind): the width a key head is STORED
  at where the chip wants whole 128-lane groups.
* A **window** layer's row ``t`` sees rows ``u <= t`` with ``t - u <
  window`` and a learnable sink logit a head ``b_h`` that joins the
  softmax's denominator and carries no value::

      P_h(t, u) = exp(s_h(t, u)) / (exp(b_h) + sum_u' exp(s_h(t, u')))

  ``window_attention`` is the prefill form over one prompt: a BANDED
  flash forward kernel that computes only the key blocks that meet the
  band and starts its running sum from the sink (``m = b_h``, ``l = 1``,
  ``acc = 0``).  It also hands back the slot's RING: the prompt's last
  ``ring`` rows of K and V, position ``p`` at row ``p mod ring``.
  ``window_attention_step`` is the decode form over that bounded
  per-slot cache ``[S, ring, Hkv * D]``: it writes this step's row at
  ``(Lens - 1) mod ring`` and attends over the rows of the ring that lie
  in the window; keys are rotated before they are cached, so the order
  of rows inside the ring does not matter.  The ring is a fixed shape
  with no page table: the row's write is a scatter in plain XLA and the
  attention a small Pallas kernel, a slot a grid step, whose two rings
  arrive as whole blocks, so that the op's device time holds all of its
  reads (the composed XLA form's events read 138% of the ring's bytes
  at the HBM peak on the chip: part of its reads lay outside them); off
  the chip the composed form runs.
* A **full** layer's prefill is ``gqa_flash_attention``, the same kernel
  without band or sink (causal, key blocks above the diagonal neither
  computed nor copied); its decode step is ``paged_attention``
  (``ops/attention_ops.py``) over pools whose K rows and V rows differ
  in width.

Both prefill ops take ``[1, T, H * Dk]`` queries over ``Hkv`` K/V heads
(query head ``h`` reads K/V head ``h // (H / Hkv)``); the ``G = H / Hkv``
query heads of a K/V head share its key blocks in one ``[G * rows, Dk] x
[Dk, keys]`` product.  Where ``T`` is not whole blocks the composed
``[Hkv, G, T, T]`` form runs (toy sizes, and what the tests hold the
kernel to).

Op scopes on the device trace: ``ptop_window_attention__*`` (a window
layer's prefill), ``ptop_window_attention_step*`` (its decode step),
``ptop_gqa_flash_attention*`` (a full layer's prefill),
``ptop_rope_partial*``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.mla_ops import yarn_frequencies
from paddle_tpu.ops.registry import ShapeInferenceSkip, register_op

NEG_INF = -1e30
# rows of the products' left side: G query heads x this many query rows
FLASH_LEFT_ROWS = 2048
# key rows a block: the band's (its first block is the window's lead-in)
# and the causal kernel's
BAND_KEY_BLOCK = 128
CAUSAL_KEY_BLOCK = 512
_VMEM_LIMIT = 64 << 20


# ---------------------------------------------------------------------------
# rotary on the leading lanes of a head
# ---------------------------------------------------------------------------

def rope_partial(x, pos, n_head, rope_dim, theta, pad_to=0):
    """``x`` [..., n_head * D]; ``pos`` int, one per row.  Rotates the
    first ``rope_dim`` lanes of every head; angles, cos and sin in
    float32.  ``pad_to`` > D: every head comes back ``pad_to`` lanes
    wide, zeros behind its D.  Returns ``x``'s type."""
    # the projection's output is taken as it is: left to itself XLA
    # serves the slices below by transposing the whole projection matrix,
    # a 100 MB copy a layer EVERY step at the published widths (the v5e
    # compiler's account; 0.14-0.31 ms a layer on the chip)
    x = jax.lax.optimization_barrier(x)
    lead = x.shape[:-1]
    D = x.shape[-1] // n_head
    half = rope_dim // 2
    xh = x.reshape(lead + (n_head, D))
    a, b = (xh[..., :half].astype(jnp.float32),
            xh[..., half:rope_dim].astype(jnp.float32))
    ang = pos.reshape(lead).astype(jnp.float32)[..., None, None] \
        * jnp.asarray(yarn_frequencies(rope_dim, float(theta)), jnp.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    parts = [(a * cos - b * sin).astype(x.dtype),
             (b * cos + a * sin).astype(x.dtype), xh[..., rope_dim:]]
    width = max(int(pad_to), D)
    if width > D:
        parts.append(jnp.zeros(lead + (n_head, width - D), x.dtype))
    return jnp.concatenate(parts, axis=-1).reshape(lead + (n_head * width,))


def _infer_rope_partial(op, block):
    x = block.var(op.input("X")[0])
    if x.shape is None:
        raise ShapeInferenceSkip()
    out = block.var(op.output("Out")[0])
    n_head, width = int(op.attr("n_head")), x.shape[-1]
    if width > 0 and int(op.attr("pad_to", 0)) > width // n_head:
        width = n_head * int(op.attr("pad_to"))
    out.shape, out.dtype = tuple(x.shape[:-1]) + (width,), x.dtype


@register_op("rope_partial", infer_shape=_infer_rope_partial,
             no_grad_inputs=("Pos",))
def rope_partial_lower(ctx):
    """X [..., n_head * D]; Pos int32, one per row of X.  attrs n_head,
    rope_dim (the leading lanes turned), theta, pad_to (0: heads stay D
    wide).  Out [..., n_head * max(D, pad_to)]."""
    x = ctx.input("X")
    ctx.set_output("Out", rope_partial(
        x, ctx.input("Pos").reshape(x.shape[:-1]), int(ctx.attr("n_head")),
        int(ctx.attr("rope_dim")), float(ctx.attr("theta", 10000.0)),
        int(ctx.attr("pad_to", 0))))


# ---------------------------------------------------------------------------
# the composed form (toy sizes; what the kernel is held to)
# ---------------------------------------------------------------------------

def _seen(T, window):
    rows = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
    seen = cols <= rows
    return seen & (rows - cols < window) if window else seen


def _with_sink(sc, sink):
    """Softmax of ``sc`` [..., keys] whose denominator also holds
    ``exp(sink)`` (``sink`` broadcastable to ``sc[..., :1]``; None: a
    plain softmax)."""
    if sink is None:
        return jax.nn.softmax(sc, axis=-1)
    m = jnp.maximum(jnp.max(sc, axis=-1, keepdims=True), sink)
    e = jnp.exp(sc - m)
    return e / (jnp.exp(sink - m) + jnp.sum(e, axis=-1, keepdims=True))


def composed_attention(q, k, v, n_head, n_kv_head, scale, window=0,
                       sink=None):
    """``q`` [T, H * Dk]; ``k`` [T, Hkv * Dk]; ``v`` [T, Hkv * Dv];
    ``sink`` [H] float32 or None.  Causal, inside ``window`` rows where
    that is not 0.  Scores and softmax in float32.  Returns [T, H * Dv]
    in ``q``'s type."""
    T = q.shape[0]
    g = n_head // n_kv_head
    qh = q.reshape(T, n_kv_head, g, -1)
    kh, vh = k.reshape(T, n_kv_head, -1), v.reshape(T, n_kv_head, -1)
    sc = jnp.einsum("qkgd,tkd->kgqt", qh, kh,
                    preferred_element_type=jnp.float32) * scale
    sc = jnp.where(_seen(T, window), sc, NEG_INF)
    if sink is not None:
        sink = sink.astype(jnp.float32).reshape(n_kv_head, g, 1, 1)
    probs = _with_sink(sc, sink)
    out = jnp.einsum("kgqt,tkd->qkgd", probs.astype(v.dtype), vh,
                     preferred_element_type=jnp.float32)
    return out.reshape(T, -1).astype(q.dtype)


# ---------------------------------------------------------------------------
# the flash forward kernel: banded with a sink, or causal
# ---------------------------------------------------------------------------

def flash_blocks(T, group, window):
    """(query rows, key rows) a block at ``T`` rows and ``group`` query
    heads a K/V head; None where ``T`` is not whole blocks of both."""
    bq = max(FLASH_LEFT_ROWS // group, 16)
    bk = BAND_KEY_BLOCK if window else CAUSAL_KEY_BLOCK
    if T % bq or T % bk or bq % 16:
        return None
    if window and bq % bk:
        return None
    return bq, bk


def key_blocks_computed(T, group, window):
    """Key blocks the kernel computes for ``T`` rows of ONE K/V head,
    and the rows of a block; (0, 0) where the composed form runs."""
    blocks = flash_blocks(T, group, window)
    if blocks is None:
        return 0, 0
    bq, bk = blocks
    n_q = T // bq
    if window:
        lead, per = -(-(window - 1) // bk), bq // bk
        return sum(min(i * per + per, lead + per) for i in range(n_q)), bk
    return sum((i * bq + bq - 1) // bk + 1 for i in range(n_q)), bk


def _flash_kernel(*refs, scale, window, bq, bk, lead, sink):
    """One (K/V head, query block); the key blocks that meet the band
    (``window`` > 0: ``lead`` blocks before the query block's own) or
    lie at or under the diagonal stream through VMEM along the
    innermost, sequential grid axis with an online softmax.  The ``G``
    query heads that share the K/V head are the rows of ONE product."""
    if sink:
        sink_ref, *refs = refs
    q_ref, k_ref, v_ref, o_ref, acc, m_scr, l_scr = refs
    i, j = pl.program_id(1), pl.program_id(2)
    G = q_ref.shape[1]
    # the key block this step holds (before the caller's clamp)
    kb = i * (bq // bk) - lead + j if window else j

    @pl.when(j == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)
        if sink:
            m_scr[...] = sink_ref[0]
            l_scr[...] = jnp.ones_like(l_scr)
        else:
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)

    def update(masked):
        q = q_ref[0].reshape(G * bq, q_ref.shape[-1])
        k, v = k_ref[0], v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if masked:
            t = i * bq + (jax.lax.broadcasted_iota(
                jnp.int32, (G * bq, bk), 0) & (bq - 1))
            u = kb * bk + jax.lax.broadcasted_iota(
                jnp.int32, (G * bq, bk), 1)
            seen = u <= t
            if window:
                seen &= t - u < window
            s = jnp.where(seen, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc[...] = acc[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    if window:
        pl.when(kb >= 0)(lambda: update(True))
    else:
        # under the diagonal a block is seen whole
        whole = kb * bk + bk - 1 <= i * bq
        pl.when(whole)(lambda: update(False))
        pl.when(jnp.logical_not(whole) & (kb * bk <= i * bq + bq - 1))(
            lambda: update(True))

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        o_ref[0] = (acc[...] / l_scr[...]).reshape(o_ref.shape[1:]) \
            .astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "n_head", "n_kv_head", "scale", "window", "interpret", "blocks"))
def flash_attention(q, k, v, sink=None, *, n_head, n_kv_head, scale,
                    window=0, interpret=False, blocks=None):
    """``q`` [T, H * Dk]; ``k`` [T, Hkv * Dk]; ``v`` [T, Hkv * Dv];
    ``sink`` [H] or None -> [T, H * Dv] in ``q``'s type, as
    ``composed_attention``.  ``blocks`` is for the tests: the kernel
    reads it from the shapes (``flash_blocks``); ``T`` must be whole
    blocks."""
    T = q.shape[0]
    G = n_head // n_kv_head
    Dk, Dv = k.shape[-1] // n_kv_head, v.shape[-1] // n_kv_head
    bq, bk = blocks or flash_blocks(T, G, window)
    if bq & (bq - 1):
        raise ValueError(f"query block of {bq} rows is not a power of two")
    per = bq // bk if window else 0
    lead = -(-(window - 1) // bk) if window else 0
    n_j = lead + per if window else T // bk
    # head-major: a K/V head's G query heads side by side
    qh = q.reshape(T, n_kv_head, G, Dk).transpose(1, 2, 0, 3)
    kh = k.reshape(T, n_kv_head, Dk).transpose(1, 0, 2)
    vh = v.reshape(T, n_kv_head, Dv).transpose(1, 0, 2)
    if window:
        kv = lambda h, i, j: (h, jnp.maximum(i * per - lead + j, 0), 0)
    else:
        # a block above the diagonal is not computed: hand the kernel the
        # diagonal's again, which is not copied a second time
        kv = lambda h, i, j: (h, jnp.minimum(j, (i * bq + bq - 1) // bk), 0)
    operands, in_specs = [], []
    if sink is not None:
        # the sink a row of the left side: [Hkv, G * bq, 1]
        operands.append(jnp.repeat(
            sink.astype(jnp.float32).reshape(n_kv_head, G), bq,
            axis=1)[..., None])
        in_specs.append(pl.BlockSpec((1, G * bq, 1),
                                     lambda h, i, j: (h, 0, 0)))
    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, window=window, bq=bq,
                          bk=bk, lead=lead, sink=sink is not None),
        grid=(n_kv_head, T // bq, n_j),
        in_specs=in_specs + [
            pl.BlockSpec((1, G, bq, Dk), lambda h, i, j: (h, 0, i, 0)),
            pl.BlockSpec((1, bk, Dk), kv),
            pl.BlockSpec((1, bk, Dv), kv)],
        out_specs=pl.BlockSpec((1, G, bq, Dv), lambda h, i, j: (h, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_kv_head, G, T, Dv), q.dtype),
        scratch_shapes=[pltpu.VMEM((G * bq, Dv), jnp.float32),
                        pltpu.VMEM((G * bq, 1), jnp.float32),
                        pltpu.VMEM((G * bq, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(*operands, qh, kh, vh)
    return out.transpose(2, 0, 1, 3).reshape(T, n_head * Dv)


def prefill_attention(q, k, v, sink, n_head, n_kv_head, scale, window):
    """The kernel where ``T`` is whole blocks, else the composed form."""
    from paddle_tpu.ops.attention_ops import _use_interpret
    if flash_blocks(q.shape[0], n_head // n_kv_head, window) is None:
        return composed_attention(q, k, v, n_head, n_kv_head, scale,
                                  window, sink)
    return flash_attention(q, k, v, sink, n_head=n_head,
                           n_kv_head=n_kv_head, scale=scale, window=window,
                           interpret=_use_interpret())


# ---------------------------------------------------------------------------
# the ring: a window layer's bounded per-slot cache
# ---------------------------------------------------------------------------

def ring_of(rows, last, ring):
    """``rows`` [T, W] a prompt's K (or V) rows; ``last`` the position of
    its last real row -> [ring, W]: position ``p`` at row ``p mod ring``
    for the last ``min(last + 1, ring)`` positions, zeros elsewhere."""
    r = jnp.arange(ring, dtype=jnp.int32)
    p = last - jnp.mod(last - r, ring)
    taken = jnp.take(rows, jnp.maximum(p, 0), axis=0)
    return jnp.where((p >= 0)[:, None], taken, 0).astype(rows.dtype)


def _ring_kernel(lens_ref, q_ref, *refs, scale, window, n_kv, sink):
    """One slot of the grid: its rings arrive whole (``[R, Hkv * D]``
    blocks, copied in while the slot before computes).  Row ``r`` holds
    position ``pos - ((pos - r) mod R)``: seen if that is not negative
    and inside the window.  ALL query heads take their scores in one
    product: the queries are laid out block-diagonally (``[H, Hkv *
    Dk]``: head ``h`` in the lanes of its own K/V head, zeros in the
    others'), so ``[H, Hkv * Dk] x [Hkv * Dk, R]`` is each head over its
    own keys; likewise ``[H, R] x [R, Hkv * Dv]`` holds each head's
    context in the lanes of its K/V head, which are then picked out (a
    product a K/V head of 8 query rows each took 0.11 ms a layer where
    this takes under 0.04 with the row's write: the MXU's latency, not
    its work; my chip runs, PR 40).  The weights go through the second
    product in two parts of the ring's type (``hi + lo``), as the paged
    kernel's."""
    if sink:
        sink_ref, *refs = refs
    k_ref, v_ref, o_ref = refs
    H, Dk = q_ref.shape[1:]
    R, Dv = k_ref.shape[1], o_ref.shape[2]
    G = H // n_kv
    f32 = jnp.float32
    pos = lens_ref[pl.program_id(0)] - 1
    r = jax.lax.broadcasted_iota(jnp.int32, (1, R), 1)
    back = jax.lax.rem(pos - r + R, R)          # pos >= 0 > r - R
    seen = (back <= pos) & (back < window)      # none where pos < 0
    own = lambda width: (
        jax.lax.broadcasted_iota(jnp.int32, (H, n_kv * width), 0) // G
        == jax.lax.broadcasted_iota(jnp.int32, (H, n_kv * width), 1)
        // width)
    q = q_ref[0]
    q_wide = jnp.where(own(Dk), jnp.concatenate([q] * n_kv, axis=1),
                       jnp.zeros((), q.dtype))
    sc = jax.lax.dot_general(q_wide, k_ref[0], (((1,), (1,)), ((), ())),
                             preferred_element_type=f32) * scale
    sc = jnp.where(seen, sc, NEG_INF)                            # [H, R]
    m = jnp.max(sc, axis=-1, keepdims=True)
    if sink:
        m = jnp.maximum(m, sink_ref[...])
    e = jnp.where(seen, jnp.exp(sc - m), 0.0)
    den = jnp.sum(e, axis=-1, keepdims=True)
    if sink:
        den = den + jnp.exp(sink_ref[...] - m)
    p = e / jnp.where(den > 0, den, 1.0)
    v = v_ref[0]
    hi = p.astype(v.dtype)
    weigh = lambda part: jax.lax.dot_general(
        part, v, (((1,), (0,)), ((), ())), preferred_element_type=f32)
    wide = jnp.where(own(Dv), weigh(hi)
                     + weigh((p - hi.astype(f32)).astype(v.dtype)), 0.0)
    out = wide[:, :Dv]
    for g in range(1, n_kv):
        out = out + wide[:, g * Dv:(g + 1) * Dv]
    o_ref[0] = out.astype(o_ref.dtype)


def _ring_kernel_ok(q, k_ring, v_ring, n_head, interpret):
    """The kernel slices a ring's row at whole heads: on the chip a head
    must cover whole 128-lane vregs and the ring whole sublane tiles."""
    Dk = q.shape[-1] // n_head
    n_kv = k_ring.shape[-1] // Dk
    Dv = v_ring.shape[-1] // n_kv
    return interpret or not (Dk % 128 or Dv % 128
                             or k_ring.shape[1] % (32 // k_ring.dtype.itemsize))


@functools.partial(jax.jit, inline=True, static_argnames=(
    "n_head", "scale", "window", "interpret"))
def ring_attention(q, k_ring, v_ring, lens, sink=None, *, n_head, scale,
                   window, interpret=False):
    """The decode step's attention over rings that already hold this
    step's row: ``q`` [S, H * Dk]; rings [S, R, Hkv * D]; ``lens`` [S]
    int32 -> [S, H * Dv] in ``q``'s type (a free slot: zeros)."""
    S, R, _ = k_ring.shape
    Dk = q.shape[-1] // n_head
    n_kv = k_ring.shape[-1] // Dk
    Dv = v_ring.shape[-1] // n_kv
    operands, in_specs = [], []
    if sink is not None:
        operands.append(sink.astype(jnp.float32).reshape(n_head, 1))
        in_specs.append(pl.BlockSpec((n_head, 1), lambda s, ln: (0, 0)))
    out = pl.pallas_call(
        functools.partial(_ring_kernel, scale=scale, window=window,
                          n_kv=n_kv, sink=sink is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S,),
            in_specs=[pl.BlockSpec((1, n_head, Dk),
                                   lambda s, ln: (s, 0, 0))] + in_specs + [
                pl.BlockSpec((1, R, n_kv * Dk), lambda s, ln: (s, 0, 0)),
                pl.BlockSpec((1, R, n_kv * Dv), lambda s, ln: (s, 0, 0))],
            out_specs=pl.BlockSpec((1, n_head, Dv),
                                   lambda s, ln: (s, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((S, n_head, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(lens.astype(jnp.int32), q.reshape(S, n_head, Dk).astype(k_ring.dtype),
      *operands, k_ring, v_ring)
    return out.reshape(S, n_head * Dv)


def ring_step(q, k, v, k_ring, v_ring, lens, sink, n_head, scale, window,
              kernel=None):
    """One decode step over the rings.  ``q`` [S, H * Dk]; ``k`` [S, Hkv
    * Dk]; ``v`` [S, Hkv * Dv]; rings [S, R, Hkv * D]; ``lens`` [S] rows
    INCLUDING this step's (0 = free slot: nothing written, zeros out);
    ``sink`` [H] or None.  ``kernel``: None = the composed form; else the
    Pallas kernel's ``interpret`` flag.  Returns ``(out [S, H * Dv],
    k_ring, v_ring)``."""
    S, R, _ = k_ring.shape
    Hkv = k_ring.shape[-1] // (q.shape[-1] // n_head)
    G = n_head // Hkv
    slot = jnp.arange(S, dtype=jnp.int32)
    pos = lens.astype(jnp.int32) - 1
    # a free slot's row lands nowhere
    at = jnp.where(pos >= 0, jnp.mod(pos, R), R)
    k_ring = k_ring.at[slot, at].set(k.astype(k_ring.dtype), mode="drop")
    v_ring = v_ring.at[slot, at].set(v.astype(v_ring.dtype), mode="drop")
    if kernel is not None and _ring_kernel_ok(q, k_ring, v_ring, n_head,
                                              kernel):
        out = ring_attention(q, k_ring, v_ring, lens, sink, n_head=n_head,
                             scale=scale, window=window, interpret=kernel)
        return out, k_ring, v_ring
    # row r holds position pos - ((pos - r) mod R), if that is not negative
    back = jnp.mod(pos[:, None] - jnp.arange(R, dtype=jnp.int32)[None], R)
    seen = (back <= pos[:, None]) & (back < window)              # [S, R]
    qh = q.reshape(S, Hkv, G, -1)
    sc = jnp.einsum("skgd,srkd->skgr", qh.astype(k_ring.dtype),
                    k_ring.reshape(S, R, Hkv, -1),
                    preferred_element_type=jnp.float32) * scale
    sc = jnp.where(seen[:, None, None, :], sc, NEG_INF)
    if sink is not None:
        sink = sink.astype(jnp.float32).reshape(1, Hkv, G, 1)
    probs = _with_sink(sc, sink)
    out = jnp.einsum("skgr,srkd->skgd", probs,
                     v_ring.reshape(S, R, Hkv, -1).astype(jnp.float32),
                     preferred_element_type=jnp.float32,
                     precision=jax.lax.Precision.HIGHEST)
    out = jnp.where((pos >= 0)[:, None, None, None], out, 0.0)
    return out.reshape(S, -1).astype(q.dtype), k_ring, v_ring


# ---------------------------------------------------------------------------
# IR ops
# ---------------------------------------------------------------------------

def _infer_prefill(op, block):
    q = block.var(op.input("Q")[0])
    v = block.var(op.input("V")[0])
    if q.shape is None or v.shape is None:
        raise ShapeInferenceSkip()
    n_head, n_kv = int(op.attr("n_head")), int(op.attr("n_kv_head"))
    out = block.var(op.output("Out")[0])
    out.shape = tuple(q.shape[:-1]) + (v.shape[-1] // n_kv * n_head,)
    out.dtype = q.dtype
    for slot, src in (("KRing", "K"), ("VRing", "V")):
        if op.output(slot):
            x = block.var(op.input(src)[0])
            ring = block.var(op.output(slot)[0])
            ring.shape = (1, int(op.attr("ring")), x.shape[-1])
            ring.dtype = x.dtype


def _prefill_lower(ctx, window):
    q, k, v = ctx.input("Q")[0], ctx.input("K")[0], ctx.input("V")[0]
    sink = ctx.input("Sink") if ctx.has_input("Sink") else None
    out = prefill_attention(q, k, v, sink, int(ctx.attr("n_head")),
                            int(ctx.attr("n_kv_head")),
                            float(ctx.attr("scale", 1.0)), window)
    ctx.set_output("Out", out[None])
    return k, v


@register_op("gqa_flash_attention", infer_shape=_infer_prefill)
def gqa_flash_attention_lower(ctx):
    """A full layer's prefill.  Q [1, T, H * Dk]; K [1, T, Hkv * Dk]; V
    [1, T, Hkv * Dv]: causal (real rows first: a pad row is seen by no
    real row).  attrs n_head, n_kv_head, scale.  Out [1, T, H * Dv]."""
    _prefill_lower(ctx, 0)


@register_op("window_attention", infer_shape=_infer_prefill,
             no_grad_inputs=("Last",),
             stop_gradient_outputs=("KRing", "VRing"))
def window_attention_lower(ctx):
    """A window layer's prefill.  Q, K, V as ``gqa_flash_attention``;
    Sink [H] float32 (optional); Last [1, T] (optional: the one-hot of
    the last real row).  attrs n_head, n_kv_head, scale, window, ring.
    Out [1, T, H * Dv]; with Last, KRing [1, ring, Hkv * Dk] and VRing
    [1, ring, Hkv * Dv]: the slot's ring after the prompt."""
    k, v = _prefill_lower(ctx, int(ctx.attr("window")))
    if ctx.has_input("Last"):
        last = jnp.argmax(ctx.input("Last")[0]).astype(jnp.int32)
        ring = int(ctx.attr("ring"))
        ctx.set_output("KRing", ring_of(k, last, ring)[None])
        ctx.set_output("VRing", ring_of(v, last, ring)[None])


def _infer_step(op, block):
    q = block.var(op.input("Q")[0])
    v = block.var(op.input("V")[0])
    k = block.var(op.input("K")[0])
    if q.shape is None or v.shape is None or k.shape is None:
        raise ShapeInferenceSkip()
    n_head = int(op.attr("n_head"))
    n_kv = k.shape[-1] // (q.shape[-1] // n_head)
    out = block.var(op.output("Out")[0])
    out.shape = tuple(q.shape[:-1]) + (v.shape[-1] // n_kv * n_head,)
    out.dtype = q.dtype
    # KRingOut/VRingOut alias the persistable rings (in-place update)


@register_op("window_attention_step", infer_shape=_infer_step,
             no_gradient=True, stateful_outputs=("KRingOut", "VRingOut"))
def window_attention_step_lower(ctx):
    """A window layer's decode step.  Q [S, 1, H * Dk]; K [S, 1, Hkv *
    Dk]; V [S, 1, Hkv * Dv]; KRing [S, ring, Hkv * Dk], VRing [S, ring,
    Hkv * Dv] persistable; Lens [S, 1] int32 rows INCLUDING this step's
    (0 = free slot); Sink [H] (optional).  attrs n_head, scale, window
    (<= ring).  Out [S, 1, H * Dv]; KRingOut/VRingOut name the rings
    themselves."""
    from paddle_tpu.ops.attention_ops import _use_interpret
    q = ctx.input("Q")
    sink = ctx.input("Sink") if ctx.has_input("Sink") else None
    # the kernel on the chip; off it the composed form
    out, k_ring, v_ring = ring_step(
        q[:, 0], ctx.input("K")[:, 0], ctx.input("V")[:, 0],
        ctx.input("KRing"), ctx.input("VRing"),
        ctx.input("Lens").reshape(q.shape[0]), sink,
        int(ctx.attr("n_head")), float(ctx.attr("scale", 1.0)),
        int(ctx.attr("window")), kernel=None if _use_interpret() else False)
    ctx.set_output("Out", out[:, None])
    ctx.set_output("KRingOut", k_ring)
    ctx.set_output("VRingOut", v_ring)
