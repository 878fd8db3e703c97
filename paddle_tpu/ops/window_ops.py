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

  ``window_attention`` is the prefill form: a BANDED flash forward
  kernel that computes only the key blocks that meet the band and starts
  its running sum from the sink (``m = b_h``, ``l = 1``, ``acc = 0``).
  A slot's RING holds the last ``ring`` rows of K and V that went
  through it, position ``p`` at row ``p mod ring``.
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
* A prompt can run as a sequence of CHUNKS, each reading the slot's
  earlier rows from where the decode step reads them and writing its own
  there.  A full layer's chunk is ``gqa_flash_attention_chunk``: it
  writes the chunk's K/V rows into the slot's pages and attends the
  chunk's queries (positions ``P .. P + C - 1``) over the pages' rows
  ``0 .. P + C - 1``: the causal kernel with more key rows than query
  rows and its diagonal shifted by ``P``, a scalar the kernel is handed
  before its grid runs (the latent builder's chunk is the same kernel
  with every head its own K/V head, EXPANDED a key block at a time in
  VMEM from the slot's latent rows, and where a layer selects its rows
  the selection's int8 blocks beside the keys':
  ``mla_ops.mla_attention_chunk``).  A window layer's is
  ``window_attention`` with the rings as inputs: its keys are the slot's
  ring rows of positions
  ``P - lead .. P - 1`` (``ring_lead``) followed by the chunk's own, and
  it leaves the chunk's last ``ring`` rows in the ring (``ring_after``).
  ``P`` = 0 is a prompt's first chunk, or all of it.

Both prefill ops take ``[1, T, H * Dk]`` queries over ``Hkv`` K/V heads
(query head ``h`` reads K/V head ``h // (H / Hkv)``); the ``G = H / Hkv``
query heads of a K/V head share its key blocks in one ``[G * rows, Dk] x
[Dk, keys]`` product, over head-major copies of the operands; with ONE
query head a K/V head and heads of whole 128-lane tiles (an expanded
latent chunk, ``mla_ops.latent_window_attention``) a head's blocks are
columns of the operands as they lie and nothing is copied; with
``expand=`` (a full layer's latent chunk) the keys are the LATENT rows
and a head's key and value block is made from them where it is used.
Where ``T``
is not whole blocks (``flash_blocks``) the composed ``[Hkv, G, T, T]``
form runs (toy sizes, and what the tests hold the kernel to).

Op scopes on the device trace: ``ptop_window_attention__*`` (a window
layer's prefill, whole or a chunk), ``ptop_window_attention_step*`` (its
decode step), ``ptop_gqa_flash_attention*`` (a full layer's prefill,
whole or a chunk), ``ptop_rope_partial*``.
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

def _seen(Tq, Tk, window, start=0, first=0):
    """[Tq, Tk]: query row ``r`` stands at key index ``start + r`` and
    sees the keys at or before it, from index ``first`` on, inside
    ``window`` rows where that is not 0."""
    rows = start + jax.lax.broadcasted_iota(jnp.int32, (Tq, Tk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (Tq, Tk), 1)
    seen = (cols <= rows) & (cols >= first)
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
                       sink=None, start=0, first=0, select=None):
    """``q`` [Tq, H * Dk]; ``k`` [Tk, Hkv * Dk]; ``v`` [Tk, Hkv * Dv];
    ``sink`` [H] float32 or None.  Query row ``r`` stands at key index
    ``start + r`` (int or traced scalar; a whole prompt: 0 and ``Tk`` =
    ``Tq``): causal from key ``first`` on, inside ``window`` rows where
    that is not 0, and under ``select`` [Tq, Tk] (0 = the row leaves the
    key out) where there is one.  Scores and softmax in float32.
    Returns [Tq, H * Dv] in ``q``'s type."""
    Tq, Tk = q.shape[0], k.shape[0]
    g = n_head // n_kv_head
    qh = q.reshape(Tq, n_kv_head, g, -1)
    kh, vh = k.reshape(Tk, n_kv_head, -1), v.reshape(Tk, n_kv_head, -1)
    sc = jnp.einsum("qkgd,tkd->kgqt", qh, kh,
                    preferred_element_type=jnp.float32) * scale
    seen = _seen(Tq, Tk, window, start, first)
    if select is not None:
        seen &= select > 0
    sc = jnp.where(seen, sc, NEG_INF)
    if sink is not None:
        sink = sink.astype(jnp.float32).reshape(n_kv_head, g, 1, 1)
    probs = _with_sink(sc, sink)
    out = jnp.einsum("kgqt,tkd->qkgd", probs.astype(v.dtype), vh,
                     preferred_element_type=jnp.float32)
    return out.reshape(Tq, -1).astype(q.dtype)


# ---------------------------------------------------------------------------
# the flash forward kernel: banded with a sink, or causal
# ---------------------------------------------------------------------------

def flash_blocks(T, group, window, keys=None):
    """(query rows, key rows) a block at ``T`` query rows and ``group``
    query heads a K/V head, over ``keys`` key rows where the call is
    causal (None: ``T`` of them); None where they are not whole blocks
    of both.  ``FLASH_LEFT_ROWS`` left rows a step over the kind's key
    block, but with ONE query head a K/V head and a power of two of rows
    short of that: square blocks under a band, the chunk's own rows the
    query block (over key blocks twice as long where the keys allow)
    under none."""
    bq = max(FLASH_LEFT_ROWS // group, 16)
    bk = BAND_KEY_BLOCK if window else CAUSAL_KEY_BLOCK
    if group == 1 and bk <= T < bq and not T & (T - 1):
        # every head its own K/V head (an expanded latent chunk) and
        # fewer rows than a left side: no heads to stack
        if window:
            # square blocks of up to 512 rows: a grid step rescales its
            # [bq, Dv] sums whatever the key block's width, so at 1024
            # rows under a window of 513 the chip took 0.58 ms with 512
            # x 512 (1024 keys a row computed, 256 steps), 0.89 with 256
            # x 256, 1.38 with 256 x 128 and 2.13 with 1024 x 128
            bq = bk = min(T, 512)
        else:
            # ONE query block: a (head, key block) that is expanded
            # where it is used (``flash_attention``'s ``expand``) is
            # expanded once.  Key blocks of 1024 rows where the keys are
            # whole blocks of that: 1024 rows of 128 heads at position
            # 4096 under a selection took the chip 6.2 ms with 1024 x
            # 1024, 7.5 with 1024 x 2048, 8.5 with 1024 x 512, 9.6 with
            # 512 x 512 (every key block expanded twice) and 13.4 with
            # 1024 x 256 or 1024 x 4096
            bq = T
            if not (T if keys is None else keys) % (2 * bk):
                bk *= 2
    if T % bq or bq % 16:
        return None
    if bq % bk if window else (T if keys is None else keys) % bk:
        return None
    return bq, bk


def lead_rows(T, group, window):
    """Key rows that stand BEFORE a chunk's own in a window call: whole
    key blocks that cover ``window - 1`` rows where the kernel runs,
    ``window - 1`` rows where the composed form does."""
    blocks = flash_blocks(T, group, window)
    if blocks is None:
        return window - 1
    return -(-(window - 1) // blocks[1]) * blocks[1]


def key_blocks_computed(T, group, window, start=0, keys=None):
    """Key blocks the kernel computes for ``T`` query rows of ONE K/V
    head that stand at positions ``start ..`` (0: a whole prompt, or its
    first chunk) over ``keys`` key rows (causal; None: ``T``), and the
    rows of a block; (0, 0) where the composed form runs."""
    blocks = flash_blocks(T, group, window, keys)
    if blocks is None:
        return 0, 0
    bq, bk = blocks
    n_q = T // bq
    if window:
        lead, per = -(-(window - 1) // bk), bq // bk
        # lead-in blocks that hold no row: before position 0
        dead = (lead * bk - min(start, lead * bk)) // bk
        return sum(lead + per - max(0, min(lead + per, dead - i * per))
                   for i in range(n_q)), bk
    return sum((start + i * bq + bq - 1) // bk + 1 for i in range(n_q)), bk


def _flash_kernel(s_ref, *refs, scale, window, bq, bk, lead, sink,
                  select=False, flat=False, expand=False):
    """One (K/V head, query block); the key blocks that meet the band
    (``window`` > 0: the keys begin with ``lead`` blocks of the rows
    before the chunk, of which those from index ``s`` on are real) or
    lie at or under the diagonal (the chunk's first row stands at key
    index ``s``) stream through VMEM along the innermost, sequential
    grid axis with an online softmax.  The ``G`` query heads that share
    the K/V head are the rows of ONE product.  ``select`` (causal): an
    int8 block ``[bq, bk]`` of a selection comes beside the key block,
    and a score counts where it marks the pair (the selection is causal
    already), for every one of the ``G`` heads.  ``flat`` (``G`` = 1):
    the blocks are ``[rows, lanes]`` columns of the operands as they lie
    (``[T, H * D]``), not ``[1, (G,) rows, lanes]`` of head-major
    copies.  ``expand`` (``flat``): the key block is ``[bk, W]`` LATENT
    rows, and the head's key and value block are made from it here, in
    VMEM, by the head's columns of the two expansion matrices
    (``flash_attention``'s ``expand``), rounded to the rows' type."""
    if sink:
        sink_ref, *refs = refs
    q_ref, k_ref, *refs = refs
    if expand:
        w_k_ref, w_v_ref, *refs = refs
    else:
        v_ref, *refs = refs
    if select:
        sel_ref, *refs = refs
    o_ref, acc, m_scr, l_scr = refs
    i, j = pl.program_id(1), pl.program_id(2)
    G = 1 if flat else q_ref.shape[1]
    block = (lambda ref: ref[...]) if flat else (lambda ref: ref[0])
    s = s_ref[0]
    # the key block this step holds (before the caller's clamp), and the
    # key index the query block's first row stands at
    kb = i * (bq // bk) + j if window else j
    t0 = lead * bk + i * bq if window else s + i * bq

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
        q = block(q_ref).reshape(G * bq, q_ref.shape[-1])
        if expand:
            rows = k_ref[...]
            made = lambda x, w_ref: jax.lax.dot_general(
                x, w_ref[...], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(rows.dtype)
            k = made(rows, w_k_ref)
            v = made(rows[:, :w_v_ref.shape[0]], w_v_ref)
        else:
            k, v = block(k_ref), block(v_ref)
        sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        if select:
            # (int8 is widened before it is compared: the v5e compiler
            # refuses the narrow comparison)
            marked = jnp.broadcast_to(
                sel_ref[...].astype(jnp.int32)[None], (G, bq, bk))
            sc = jnp.where(marked.reshape(G * bq, bk) > 0, sc, NEG_INF)
        elif masked:
            t = t0 + (jax.lax.broadcasted_iota(
                jnp.int32, (G * bq, bk), 0) & (bq - 1))
            u = kb * bk + jax.lax.broadcasted_iota(
                jnp.int32, (G * bq, bk), 1)
            seen = u <= t
            if window:
                seen &= (t - u < window) & (u >= s)
            sc = jnp.where(seen, sc, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc[...] = acc[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    if window:
        # a lead-in block none of whose rows is real is not computed
        pl.when(kb * bk + bk > s)(lambda: update(True))
    else:
        # under the diagonal a block is seen whole
        whole = kb * bk + bk - 1 <= t0
        pl.when(whole)(lambda: update(False))
        pl.when(jnp.logical_not(whole) & (kb * bk <= t0 + bq - 1))(
            lambda: update(True))

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        out = acc[...] / l_scr[...]
        if flat:
            o_ref[...] = out.astype(o_ref.dtype)
        else:
            o_ref[0] = out.reshape(o_ref.shape[1:]).astype(o_ref.dtype)


def _led(k, v, before, Tq):
    """``k`` / ``v`` with the rows ``before`` = ``(k rows, v rows, n)``
    led in front of them (``(None, None, n)``: ``k`` / ``v`` hold them
    in front of the ``Tq`` own rows already), and the first key index
    that is real (the last ``n`` of the rows before are)."""
    if before[0] is None:
        return k, v, k.shape[0] - Tq - before[2]
    return (jnp.concatenate([before[0].astype(k.dtype), k]),
            jnp.concatenate([before[1].astype(v.dtype), v]),
            before[0].shape[0] - before[2])


@functools.partial(jax.jit, static_argnames=(
    "n_head", "n_kv_head", "scale", "window", "interpret", "blocks"))
def flash_attention(q, k, v, sink=None, start=0, before=None, select=None,
                    expand=None, *, n_head, n_kv_head, scale, window=0,
                    interpret=False, blocks=None):
    """``q`` [Tq, H * Dk]; ``sink`` [H] or None -> [Tq, H * Dv] in
    ``q``'s type, as ``composed_attention``.

    Causal (``window`` 0): ``k`` [Tk, Hkv * Dk], ``v`` [Tk, Hkv * Dv]
    hold EVERY key row, the queries' own among them: query row ``r``
    stands at key index ``start + r`` (an int32 scalar, traced or not;
    ``Tk`` whole key blocks).  Banded: ``k`` / ``v`` [Tq, ...] are the
    chunk's own rows and ``before`` = ``(k rows, v rows, n)`` the
    ``lead_rows`` rows that stand before them, of which the LAST ``n``
    (traced or not) are real; None: none is; ``(None, None, n)``: ``k``
    / ``v`` [``lead_rows`` + Tq, ...] hold them in front of the chunk's
    own already.  ``select`` (causal alone)
    [Tq, Tk] int8: query row ``r`` attends the keys it marks and no
    other, whatever its head (a selection holds no key behind its row:
    the diagonal is not looked at again).  ``expand`` (causal, every
    head its own K/V head) = ``(w_k [W, H * Dk], w_v [L, H * Dv])``:
    ``k`` [Tk, W] holds LATENT rows and ``v`` is None; head ``h``'s key
    block is the rows' block times ``w_k``'s columns of ``h``, its value
    block the rows' leading ``L`` lanes times ``w_v``'s, float32 sums
    rounded to the rows' type, made in VMEM where a (head, key block)
    is used and never all at once (one query block: once).  ``blocks``
    is for the tests: the kernel reads it from the shapes
    (``flash_blocks``); ``Tq`` must be whole blocks."""
    Tq = q.shape[0]
    G = n_head // n_kv_head
    if expand is None:
        Dk, Dv = k.shape[-1] // n_kv_head, v.shape[-1] // n_kv_head
    else:
        Dk, Dv = (w.shape[-1] // n_head for w in expand)
    bq, bk = blocks or flash_blocks(Tq, G, window, keys=k.shape[0])
    if bq & (bq - 1):
        raise ValueError(f"query block of {bq} rows is not a power of two")
    if window and select is not None:
        raise ValueError("a selection comes with the causal form alone")
    if expand is not None and (window or G > 1):
        raise ValueError("rows are expanded in the causal form alone, "
                         "every head its own K/V head")
    per = bq // bk if window else 0
    lead = -(-(window - 1) // bk) if window else 0
    if window:
        if before is None:
            before = (jnp.zeros((lead * bk, k.shape[-1]), k.dtype),
                      jnp.zeros((lead * bk, v.shape[-1]), v.dtype), 0)
        k, v, start = _led(k, v, before, Tq)
    n_k = k.shape[0] // bk
    n_j = lead + per if window else n_k
    if window:
        kb = lambda i, j, s: i * per + j
    else:
        # a block above the diagonal is not computed: hand the kernel the
        # diagonal's again, which is not copied a second time
        kb = lambda i, j, s: jnp.minimum(jnp.minimum(
            j, (s[0] + i * bq + bq - 1) // bk), n_k - 1)
    # one query head a K/V head, whole lane tiles a head: a head's blocks
    # are columns of the rows as they lie
    flat = expand is not None or G == 1 and not (Dk % 128 or Dv % 128)
    if flat:
        q_spec, o_spec = (pl.BlockSpec((bq, D), lambda h, i, j, s: (i, h))
                          for D in (Dk, Dv))
        if expand is None:
            operands = [q, k, v]
            kv_specs = [pl.BlockSpec(
                (bk, D), lambda h, i, j, s: (kb(i, j, s), h))
                for D in (Dk, Dv)]
        else:
            # the latent block is every head's; a head's columns of the
            # two matrices stay where they are while its key blocks pass
            operands = [q, k, *expand]
            kv_specs = [pl.BlockSpec(
                (bk, k.shape[-1]), lambda h, i, j, s: (kb(i, j, s), 0))] + [
                pl.BlockSpec((w.shape[0], D), lambda h, i, j, s: (0, h))
                for w, D in zip(expand, (Dk, Dv))]
        out_shape = (Tq, n_head * Dv)
    else:
        # head-major: a K/V head's G query heads side by side
        operands = [q.reshape(Tq, n_kv_head, G, Dk).transpose(1, 2, 0, 3),
                    k.reshape(-1, n_kv_head, Dk).transpose(1, 0, 2),
                    v.reshape(-1, n_kv_head, Dv).transpose(1, 0, 2)]
        kv = lambda h, i, j, s: (h, kb(i, j, s), 0)
        q_spec, o_spec = (
            pl.BlockSpec((1, G, bq, D), lambda h, i, j, s: (h, 0, i, 0))
            for D in (Dk, Dv))
        kv_specs = [pl.BlockSpec((1, bk, Dk), kv),
                    pl.BlockSpec((1, bk, Dv), kv)]
        out_shape = (n_kv_head, G, Tq, Dv)
    in_specs = [q_spec] + kv_specs
    if sink is not None:
        # the sink a row of the left side: [Hkv, G * bq, 1]
        operands.insert(0, jnp.repeat(
            sink.astype(jnp.float32).reshape(n_kv_head, G), bq,
            axis=1)[..., None])
        in_specs.insert(0, pl.BlockSpec((1, G * bq, 1),
                                        lambda h, i, j, s: (h, 0, 0)))
    if select is not None:
        operands.append(select.astype(jnp.int8))
        in_specs.append(pl.BlockSpec(
            (bq, bk), lambda h, i, j, s: (i, kb(i, j, s))))
    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, window=window, bq=bq,
                          bk=bk, lead=lead, sink=sink is not None,
                          select=select is not None, flat=flat,
                          expand=expand is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n_kv_head, Tq // bq, n_j),
            in_specs=in_specs, out_specs=o_spec,
            scratch_shapes=[pltpu.VMEM((G * bq, Dv), jnp.float32),
                            pltpu.VMEM((G * bq, 1), jnp.float32),
                            pltpu.VMEM((G * bq, 1), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(out_shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(jnp.asarray(start, jnp.int32).reshape(1), *operands)
    if flat:
        return out
    return out.transpose(2, 0, 1, 3).reshape(Tq, n_head * Dv)


def prefill_attention(q, k, v, sink, n_head, n_kv_head, scale, window,
                      start=0, before=None, interpret=None, select=None):
    """The kernel where the rows are whole blocks, else the composed
    form; arguments as ``flash_attention``'s (``interpret`` None: as the
    backend has it)."""
    from paddle_tpu.ops.attention_ops import _use_interpret
    if flash_blocks(q.shape[0], n_head // n_kv_head, window,
                    keys=k.shape[0]) is not None:
        return flash_attention(
            q, k, v, sink, start, before, select, n_head=n_head,
            n_kv_head=n_kv_head, scale=scale, window=window,
            interpret=_use_interpret() if interpret is None else interpret)
    first = 0
    if before is not None:
        k, v, first = _led(k, v, before, q.shape[0])
        start = k.shape[0] - q.shape[0]
    return composed_attention(q, k, v, n_head, n_kv_head, scale, window,
                              sink, start, first, select)


# ---------------------------------------------------------------------------
# the ring: a window layer's bounded per-slot cache
# ---------------------------------------------------------------------------

def ring_after(ring, rows, start, n):
    """A slot's ring ``[R, W]`` once the first ``n`` of ``rows`` [T, W],
    which stand at positions ``start ..``, have gone through it:
    position ``p`` at row ``p mod R`` for the last ``min(n, R)`` of
    them, the ring's own row elsewhere (``n`` = 0: all of it)."""
    R = ring.shape[0]
    r = jnp.arange(R, dtype=jnp.int32)
    last = start + n - 1
    p = last - jnp.mod(last - r, R)
    taken = jnp.take(rows, jnp.clip(p - start, 0, rows.shape[0] - 1), axis=0)
    return jnp.where((p >= start)[:, None], taken.astype(ring.dtype), ring)


def ring_of(rows, last, ring):
    """``rows`` [T, W] a prompt's K (or V) rows; ``last`` the position of
    its last real row -> [ring, W]: position ``p`` at row ``p mod ring``
    for the last ``min(last + 1, ring)`` positions, zeros elsewhere."""
    return ring_after(jnp.zeros((ring, rows.shape[-1]), rows.dtype), rows,
                      0, last + 1)


def ring_lead(ring, start, rows):
    """The ``rows`` rows that stand before position ``start``, in order,
    out of a slot's ring ``[R, W]``, and how many of them are real (the
    last ones: a position under 0 or one the ring no longer holds reads
    zeros): ``([rows, W], n)``."""
    R = ring.shape[0]
    p = start - rows + jnp.arange(rows, dtype=jnp.int32)
    real = (p >= 0) & (p >= start - R)
    taken = jnp.take(ring, jnp.mod(p, R), axis=0)
    return jnp.where(real[:, None], taken, 0), jnp.minimum(
        jnp.minimum(start, R), rows)


def _ring_kernel(lens_ref, q_ref, *refs, scale, window, n_kv, sink, rows=1,
                 v_width=0):
    """One slot of the grid: its rings arrive whole (``[R, Hkv * D]``
    blocks, copied in while the slot before computes).  ``v_width`` > 0:
    ONE ring comes, of ONE K/V head, and the values are the leading
    ``v_width`` lanes of its rows (a ring of latent rows,
    ``mla_ops.latent_ring_step``: the block is read once for both
    products).  Row ``r`` holds
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
    kernel's.

    ``rows`` > 1: the slot brings that many query rows, ``rows * heads``
    query heads row-major, and ``lens_ref`` is ``[S, rows]``: row ``j``
    stands at position ``lens_ref[s, j] - 1`` (0: a dead row, which sees
    nothing and leaves zeros); the ring holds the rows through the
    largest of them, and each query row sees the ``window`` rows that
    end at its own."""
    if sink:
        sink_ref, *refs = refs
    k_ref, *v_ref, o_ref = refs
    H, Dk = q_ref.shape[1:]
    R, Dv = k_ref.shape[1], o_ref.shape[2]
    G = H // rows // n_kv
    f32 = jnp.float32
    r = jax.lax.broadcasted_iota(jnp.int32, (1, R), 1)
    head = lambda shape: jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    if rows == 1:
        pos = lens_ref[pl.program_id(0)] - 1
        back = jax.lax.rem(pos - r + R, R)          # pos >= 0 > r - R
        seen = (back <= pos) & (back < window)      # none where pos < 0
    else:
        s = pl.program_id(0)
        mine = [lens_ref[s, j] - 1 for j in range(rows)]
        top = functools.reduce(jnp.maximum, mine)
        at = head((H, 1)) // (H // rows)
        pos = functools.reduce(
            lambda acc, j: jnp.where(at == j, mine[j], acc),
            range(1, rows), jnp.full((H, 1), mine[0], jnp.int32))
        back = jax.lax.rem(jnp.maximum(top, 0) - r + R, R)   # [1, R]
        ago = back - (top - pos)                    # rows behind ITS position
        seen = (ago >= 0) & (ago < window) & (back <= top) & (pos >= 0)
    # query head ``h`` of a row reads K/V head ``h // G``
    of_row = (lambda x: x) if rows == 1 else (lambda x: x % (H // rows))
    own = lambda width: (
        of_row(head((H, n_kv * width))) // G
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
    v = v_ref[0][0] if v_ref else k_ref[0][:, :v_width]
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
    "n_head", "scale", "window", "interpret", "v_width"))
def ring_attention(q, k_ring, v_ring, lens, sink=None, *, n_head, scale,
                   window, interpret=False, v_width=0):
    """The decode step's attention over rings that already hold this
    step's row: ``q`` [S, H * Dk]; rings [S, R, Hkv * D]; ``lens`` [S]
    int32 -> [S, H * Dv] in ``q``'s type (a free slot: zeros).  ``q``
    [S, L, H * Dk] with ``lens`` [S, L]: ``L`` rows a slot, each at its
    own position (``_ring_kernel``'s ``rows``) -> [S, L, H * Dv].
    ``v_ring`` None: ``k_ring`` [S, R, Dk] is a ring of ONE head whose
    leading ``v_width`` lanes are the values."""
    S, R, _ = k_ring.shape
    rows = 1 if q.ndim == 2 else q.shape[1]
    Dk, heads = q.shape[-1] // n_head, n_head
    n_kv = k_ring.shape[-1] // Dk
    Dv = v_width if v_ring is None else v_ring.shape[-1] // n_kv
    v_rings = [] if v_ring is None else [v_ring]
    operands, in_specs = [], []
    if sink is not None:
        operands.append(jnp.tile(sink.astype(jnp.float32), rows)
                        .reshape(rows * n_head, 1))
        in_specs.append(pl.BlockSpec((rows * n_head, 1),
                                     lambda s, ln: (0, 0)))
    n_head = rows * heads
    out = pl.pallas_call(
        functools.partial(_ring_kernel, scale=scale, window=window,
                          n_kv=n_kv, sink=sink is not None, rows=rows,
                          v_width=0 if v_rings else v_width),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S,),
            in_specs=[pl.BlockSpec((1, n_head, Dk),
                                   lambda s, ln: (s, 0, 0))] + in_specs + [
                pl.BlockSpec((1, R, n_kv * Dk), lambda s, ln: (s, 0, 0))] + [
                pl.BlockSpec((1, R, n_kv * Dv), lambda s, ln: (s, 0, 0))
                for _ in v_rings],
            out_specs=pl.BlockSpec((1, n_head, Dv),
                                   lambda s, ln: (s, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((S, n_head, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(lens.astype(jnp.int32), q.reshape(S, n_head, Dk).astype(k_ring.dtype),
      *operands, k_ring, *v_rings)
    return out.reshape(q.shape[:-1] + (heads * Dv,))


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


def ring_rows_step(q, k, v, k_ring, v_ring, row_lens, sink, n_head, scale,
                   window, kernel=None):
    """One decode step of ``L`` rows a slot over the rings (a committed
    token and the drafts behind it: ``ops/spec_ops.py``).  ``q`` [S, L, H
    * Dk]; ``k`` [S, L, Hkv * Dk]; ``v`` [S, L, Hkv * Dv]; ``row_lens``
    [S, L]: row ``j`` stands at position ``row_lens[s, j] - 1`` (0 = a
    dead row: written nowhere, zeros out) and sees the ``window`` rows
    that end at its own; the live rows' positions are consecutive, so a
    ring of ``window + L - 1`` rows still holds what the first of them
    sees once the last is written.  ``kernel`` as ``ring_step``'s.
    Returns ``(out [S, L, H * Dv], k_ring, v_ring)``."""
    S, R, _ = k_ring.shape
    L = q.shape[1]
    Hkv = k_ring.shape[-1] // (q.shape[-1] // n_head)
    G = n_head // Hkv
    slot = jnp.arange(S, dtype=jnp.int32)
    pos = row_lens.astype(jnp.int32) - 1                          # [S, L]
    for j in range(L):
        # a dead row lands nowhere
        at = jnp.where(pos[:, j] >= 0, jnp.mod(pos[:, j], R), R)
        k_ring = k_ring.at[slot, at].set(k[:, j].astype(k_ring.dtype),
                                         mode="drop")
        v_ring = v_ring.at[slot, at].set(v[:, j].astype(v_ring.dtype),
                                         mode="drop")
    if kernel is not None and _ring_kernel_ok(q, k_ring, v_ring, n_head,
                                              kernel):
        out = ring_attention(q, k_ring, v_ring, row_lens, sink,
                             n_head=n_head, scale=scale, window=window,
                             interpret=kernel)
        return out, k_ring, v_ring
    top = jnp.max(pos, axis=1)                                    # [S]
    back = jnp.mod(jnp.maximum(top, 0)[:, None]
                   - jnp.arange(R, dtype=jnp.int32)[None], R)     # [S, R]
    ago = back[:, None, :] - (top[:, None] - pos)[:, :, None]     # [S, L, R]
    seen = (ago >= 0) & (ago < window) & (back <= top[:, None])[:, None, :] \
        & (pos >= 0)[:, :, None]
    qh = q.reshape(S, L, Hkv, G, -1)
    sc = jnp.einsum("slkgd,srkd->slkgr", qh.astype(k_ring.dtype),
                    k_ring.reshape(S, R, Hkv, -1),
                    preferred_element_type=jnp.float32) * scale
    sc = jnp.where(seen[:, :, None, None, :], sc, NEG_INF)
    if sink is not None:
        sink = sink.astype(jnp.float32).reshape(1, 1, Hkv, G, 1)
    probs = _with_sink(sc, sink)
    out = jnp.einsum("slkgr,srkd->slkgd", probs,
                     v_ring.reshape(S, R, Hkv, -1).astype(jnp.float32),
                     preferred_element_type=jnp.float32,
                     precision=jax.lax.Precision.HIGHEST)
    out = jnp.where((pos >= 0)[:, :, None, None, None], out, 0.0)
    return out.reshape(S, L, -1).astype(q.dtype), k_ring, v_ring


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
    # KRingOut/VRingOut and KCacheOut/VCacheOut alias the persistable
    # rings and pools (in-place update)


def _qkv(ctx):
    sink = ctx.input("Sink") if ctx.has_input("Sink") else None
    return (ctx.input("Q")[0], ctx.input("K")[0], ctx.input("V")[0], sink,
            int(ctx.attr("n_head")), int(ctx.attr("n_kv_head")),
            float(ctx.attr("scale", 1.0)))


def _chunk_rows(ctx):
    """``(start, n)`` of a chunk: the position of its first row and how
    many of its rows are real (they come first)."""
    return (ctx.input("Pos").reshape(-1)[0].astype(jnp.int32),
            jnp.sum(ctx.input("Mask") > 0).astype(jnp.int32))


def chunk_over_pages(q, k, v, kc, vc, table, start, real, n_head, n_kv_head,
                     scale, interpret=None):
    """A full layer's attention of ONE CHUNK over the slot's pages.  ``q``
    [C, H * Dk]; ``k`` [C, Hkv * Dk]; ``v`` [C, Hkv * Dv] the chunk's
    rows, at positions ``start ..``; ``real`` [1, C] bool (real rows
    first); pools ``[num_pages, page_len, Hkv * D]``; ``table`` [1, P]
    the slot's pages.  The real rows are written at their positions, then
    the chunk attends the pages' rows ``0 ..`` under the diagonal shifted
    by ``start``.  Returns ``(out [C, H * Dv], kc, vc)``."""
    from paddle_tpu.ops.attention_ops import _paged_cache_update
    kc, vc = _paged_cache_update(
        (kc, vc), (k[None], v[None]), table,
        (start + q.shape[0]).reshape(1, 1), row_lens=real)
    keys, vals = (pool[table[0]].reshape(-1, pool.shape[-1])
                  for pool in (kc, vc))
    out = prefill_attention(q, keys.astype(k.dtype), vals.astype(v.dtype),
                            None, n_head, n_kv_head, scale, 0, start=start,
                            interpret=interpret)
    return out, kc, vc


def chunk_over_ring(q, k, v, sink, k_ring, v_ring, slot, start, n, n_head,
                    n_kv_head, scale, window, interpret=None):
    """A window layer's attention of ONE CHUNK whose first ``n`` rows
    are real, over slot ``slot``'s rows of the rings ``[num_slots, R,
    Hkv * D]``: the ring's rows before ``start`` lead the chunk's own
    in, and the chunk's last real rows go through the ring.  Returns
    ``(out [C, H * Dv], k_ring, v_ring)``."""
    rings = (k_ring, v_ring)
    own = [jax.lax.dynamic_index_in_dim(r, slot, 0, keepdims=False)
           for r in rings]
    rows = lead_rows(q.shape[0], n_head // n_kv_head, window)
    (k_lead, held), (v_lead, _) = (ring_lead(r, start, rows) for r in own)
    out = prefill_attention(
        q, k, v, sink, n_head, n_kv_head, scale, window,
        before=(k_lead.astype(k.dtype), v_lead.astype(v.dtype), held),
        interpret=interpret)
    return (out,) + tuple(
        jax.lax.dynamic_update_index_in_dim(
            ring, ring_after(mine, x, start, n), slot, 0)
        for ring, mine, x in zip(rings, own, (k, v)))


@register_op("gqa_flash_attention", infer_shape=_infer_prefill)
def gqa_flash_attention_lower(ctx):
    """A full layer's prefill.  Q [1, T, H * Dk]; K [1, T, Hkv * Dk]; V
    [1, T, Hkv * Dv]: causal (real rows first: a pad row is seen by no
    real row).  attrs n_head, n_kv_head, scale.  Out [1, T, H * Dv]."""
    q, k, v, _, *heads = _qkv(ctx)
    ctx.set_output("Out", prefill_attention(q, k, v, None, *heads, 0)[None])


@register_op("gqa_flash_attention_chunk", infer_shape=_infer_prefill,
             no_gradient=True, stateful_outputs=("KCacheOut", "VCacheOut"))
def gqa_flash_attention_chunk_lower(ctx):
    """A full layer's prefill of ONE CHUNK of a prompt, over the slot's
    pages.  Q [1, C, H * Dk]; K [1, C, Hkv * Dk]; V [1, C, Hkv * Dv] the
    chunk's projections; KCache / VCache [num_pages, page_len, Hkv * D]
    persistable pools; PageTable [1, P] int32 the slot's row (P a page
    bucket that covers the chunk's last real row); Pos [1, C] int32 the
    rows' positions ``start .. start + C - 1``; Mask [1, C] (1 = a real
    row, real rows first).  The real rows are written at their positions
    of the slot's pages, over whatever was there, and the chunk's
    queries attend rows ``0 ..`` of the pages under the diagonal shifted
    by ``start`` (a pad row is written nowhere and seen by no real row).
    attrs n_head, n_kv_head, scale.  Out [1, C, H * Dv];
    KCacheOut/VCacheOut name the pools themselves."""
    q, k, v, _, *heads = _qkv(ctx)
    out, kc, vc = chunk_over_pages(
        q, k, v, ctx.input("KCache"), ctx.input("VCache"),
        ctx.input("PageTable"), _chunk_rows(ctx)[0], ctx.input("Mask") > 0,
        *heads)
    ctx.set_output("Out", out[None])
    ctx.set_output("KCacheOut", kc)
    ctx.set_output("VCacheOut", vc)


@register_op("window_attention", infer_shape=_infer_prefill,
             no_grad_inputs=("KRing", "VRing", "Slot", "Pos", "Mask"),
             stateful_outputs=("KRingOut", "VRingOut"))
def window_attention_lower(ctx):
    """A window layer's prefill.  Q, K, V as ``gqa_flash_attention``;
    Sink [H] float32 (optional).  attrs n_head, n_kv_head, scale,
    window.  Out [1, T, H * Dv].  With no further input: a WHOLE
    sequence, nothing cached (the training forward).

    ONE CHUNK of a prompt: KRing / VRing [num_slots, ring, Hkv * D]
    persistable; Slot [1, 1] int32; Pos and Mask as
    ``gqa_flash_attention_chunk``.  The chunk attends the ring's rows of
    the ``window - 1`` positions before ``start`` followed by its own,
    and its last real rows go through the slot's ring; KRingOut /
    VRingOut name the rings themselves."""
    q, k, v, sink, *heads = _qkv(ctx)
    window = int(ctx.attr("window"))
    if not ctx.has_input("KRing"):
        ctx.set_output("Out", prefill_attention(q, k, v, sink, *heads,
                                                window)[None])
        return
    out, k_ring, v_ring = chunk_over_ring(
        q, k, v, sink, ctx.input("KRing"), ctx.input("VRing"),
        ctx.input("Slot").reshape(-1)[0].astype(jnp.int32),
        *_chunk_rows(ctx), *heads, window)
    ctx.set_output("Out", out[None])
    ctx.set_output("KRingOut", k_ring)
    ctx.set_output("VRingOut", v_ring)


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
    themselves.

    RowLens (optional) [S * L, 1] int32: the step brings ``L`` rows a
    slot, Q [S, L, H * Dk] and K, V alike; row ``j`` stands at position
    ``RowLens - 1`` (0 = a dead row) and sees the window that ends at its
    own (``ring_rows_step``; ring >= window + L - 1).  Out [S, L, H *
    Dv]."""
    from paddle_tpu.ops.attention_ops import _use_interpret
    q = ctx.input("Q")
    sink = ctx.input("Sink") if ctx.has_input("Sink") else None
    if ctx.has_input("RowLens"):
        out, k_ring, v_ring = ring_rows_step(
            q, ctx.input("K"), ctx.input("V"), ctx.input("KRing"),
            ctx.input("VRing"), ctx.input("RowLens").reshape(q.shape[:2]),
            sink, int(ctx.attr("n_head")), float(ctx.attr("scale", 1.0)),
            int(ctx.attr("window")),
            kernel=None if _use_interpret() else False)
        ctx.set_output("Out", out)
        ctx.set_output("KRingOut", k_ring)
        ctx.set_output("VRingOut", v_ring)
        return
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
