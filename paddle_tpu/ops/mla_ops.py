"""Multi-head latent attention (``models/latent_moe.py``): a rotary
embedding with YaRN-scaled frequencies on a slice of each head, the
gated feed-forward's activation, and the two forms of the attention
itself over ONE compressed row a token a layer, ``[c_kv | k_rope]``.

``rope`` rotates the trailing ``rope_dim`` lanes of every head by the
row's position; pair ``i`` of a head's slice is lanes ``(i, i +
rope_dim / 2)`` and turns by ``pos * f_i``.  ``yarn_frequencies`` gives
``f_i``: between ``theta^(-2i/d)`` and that over ``factor``, blended by
the linear ramp between the pairs whose wavelengths make ``beta_fast``
and ``beta_slow`` turns in the original context.

``mla_attention`` is the prefill form: K and V of every head are
EXPANDED from the latent (``[k_nope | v] = c_kv W_kvb``), causal softmax
in float32, query rows taken a block at a time.  The decode form keeps
the latent as it is cached: ``mla_absorb`` folds ``W_kvb``'s key half
into the query (``side`` ``"q"``: ``[q_nope W_k^T | q_rope]``, 576 wide
a head) and its value half out of the context (``side`` ``"o"``), and
``paged_attention_latent`` (``ops/attention_ops.py``) attends over the
cached rows between the two.

``mla_attention_chunk`` is the serving prefill's form: ONE CHUNK of a
prompt over the slot's own pages of the latent pool.  It writes the
chunk's rows where the decode step reads them, then attends the chunk's
queries (positions ``P .. P + C - 1``) over the pages' rows ``0 .. P + C
- 1``, EXPANDED as ``mla_attention``: every head its own key and value
(``window_ops``'s causal flash kernel at one query head a K/V head, the
diagonal shifted by ``P``, the selection's blocks beside the keys' where
there is one), a (head, key block) made in VMEM from the block's latent
rows as they are cached and the head's columns of ``W_kvb``
(``chunk_weights``) where it is used.  No key block above the diagonal
is expanded, so a chunk's work follows the rows under its diagonal and
not its page bucket; a pair of rows costs a head (nope + rope + v) lanes
where the absorbed form's costs 2 L + rope, which is why the chunk (a
thousand query rows a key row) expands and the decode step (one) does
not.

A WINDOW layer of latent attention (``models/latent_moe.py`` with
``layer_types``: row ``t`` sees rows ``u <= t`` with ``t - u < window``)
keeps, where a full layer keeps pages, a RING of latent rows a slot
(``[num_slots, ring, W]``, position ``p`` at row ``p mod ring``), under
``window_ops``'s kernels.  ``latent_window_step`` is the decode step,
ABSORBED: the row's scatter and the ring kernel handed ONE ring, whose
row is every head's key and, in its leading ``v_width`` lanes, their
value (one row a slot over a ring read once: there the absorbed form is
the cheap one).  ``latent_window_attention`` is the prefill, EXPANDED
as ``mla_attention`` (``mla_expand``: (nope + rope + v) lanes a head a
pair where the absorbed form takes 2 L + rope): a whole sequence, or
one chunk, whose K and V of every head are made in one product from
the ring's rows before the chunk and the chunk's own, under the banded
flash kernel with every head its own K/V head.
``head_gate`` multiplies head ``j``'s attention output by ``sigmoid`` of
the gate's ``j``-th logit.

Op scopes on the device trace: ``ptop_rope*``, ``ptop_swiglu*``,
``ptop_mla_attention*`` (whole sequence and chunk), ``ptop_mla_absorb*``,
``ptop_latent_window_attention*``, ``ptop_latent_window_step*``,
``ptop_head_gate*``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ops.registry import (ShapeInferenceSkip, infer_shape_unary,
                                     register_op)

NEG_INF = -1e30
# query rows of one block of the prefill's scores: 64 heads x 512 x 2048
# float32 scores are 268 MB, where all 2048 rows at once are 1.07 GB
MLA_QUERY_BLOCK = 512


# ---------------------------------------------------------------------------
# rotary embedding, YaRN frequencies
# ---------------------------------------------------------------------------

def yarn_mscale(factor, mscale=1.0):
    """YaRN's attention temperature ``0.1 mscale ln(factor) + 1``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(dim, theta, factor=1.0, original_max=4096,
                     beta_fast=32.0, beta_slow=1.0):
    """The ``dim / 2`` rotary frequencies (radians a position), float64
    numpy.  ``factor`` <= 1 gives the plain ``theta^(-2i/dim)``."""
    half = dim // 2
    plain = theta ** (-np.arange(half, dtype=np.float64) * 2.0 / dim)
    if factor <= 1:
        return plain

    def pair_of(turns):
        # the pair whose wavelength makes ``turns`` turns in original_max
        return dim * math.log(original_max / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    # ramp 0: the pair turns fast enough to keep its frequency; 1: it is
    # interpolated (divided by factor)
    return plain / factor * ramp + plain * (1.0 - ramp)


def rope(x, pos, n_head, rope_dim, freqs, mscale=1.0):
    """``x`` [..., n_head * D]; ``pos`` broadcastable to ``x``'s leading
    axes (int).  Rotates the last ``rope_dim`` lanes of every head;
    angles, cos and sin in float32.  Returns ``x``'s type."""
    lead = x.shape[:-1]
    D = x.shape[-1] // n_head
    half = rope_dim // 2
    xh = x.reshape(lead + (n_head, D))
    keep, a, b = (xh[..., :D - rope_dim],
                  xh[..., D - rope_dim:D - half].astype(jnp.float32),
                  xh[..., D - half:].astype(jnp.float32))
    ang = pos.reshape(lead).astype(jnp.float32)[..., None, None] \
        * jnp.asarray(freqs, jnp.float32)
    cos, sin = jnp.cos(ang) * mscale, jnp.sin(ang) * mscale
    out = jnp.concatenate(
        [keep, (a * cos - b * sin).astype(x.dtype),
         (b * cos + a * sin).astype(x.dtype)], axis=-1)
    return out.reshape(x.shape)


@register_op("rope", infer_shape=infer_shape_unary(),
             no_grad_inputs=("Pos",))
def rope_lower(ctx):
    """X [..., n_head * D]; Pos int32, one per row of X (any shape with
    as many elements as X's leading axes).  attrs n_head, rope_dim,
    theta, factor, original_max, beta_fast, beta_slow, mscale (cos and
    sin are scaled by it)."""
    x = ctx.input("X")
    rope_dim = int(ctx.attr("rope_dim"))
    freqs = yarn_frequencies(
        rope_dim, float(ctx.attr("theta", 10000.0)),
        float(ctx.attr("factor", 1.0)), int(ctx.attr("original_max", 4096)),
        float(ctx.attr("beta_fast", 32.0)), float(ctx.attr("beta_slow", 1.0)))
    ctx.set_output("Out", rope(x, ctx.input("Pos").reshape(x.shape[:-1]),
                               int(ctx.attr("n_head", 1)), rope_dim, freqs,
                               float(ctx.attr("mscale", 1.0))))


# ---------------------------------------------------------------------------
# gated feed-forward activation
# ---------------------------------------------------------------------------

def swiglu(gate, up):
    """``silu(gate) * up`` in float32, result in ``gate``'s type."""
    return (jax.nn.silu(gate.astype(jnp.float32))
            * up.astype(jnp.float32)).astype(gate.dtype)


@register_op("swiglu", infer_shape=infer_shape_unary())
def swiglu_lower(ctx):
    """X (the gate's pre-activation), Y (the up projection), alike."""
    ctx.set_output("Out", swiglu(ctx.input("X"), ctx.input("Y")))


# ---------------------------------------------------------------------------
# latent attention: prefill (expanded) and the absorbed projections
# ---------------------------------------------------------------------------

def _split_kvb(w_kvb, n_head, nope, v_dim):
    """``W_kvb`` [L, H * (nope + v)] -> ``W_k`` [L, H, nope], ``W_v``
    [L, H, v]."""
    w = w_kvb.reshape(w_kvb.shape[0], n_head, nope + v_dim)
    return w[..., :nope], w[..., nope:]


def mla_expand(latent, w_kvb, n_head, nope, rope_dim, v_dim, dtype):
    """K and V of every head EXPANDED from latent rows: ``latent`` [T,
    >= L + rope] (``c_kv`` after its norm | the rotated shared key |
    lanes that pad the cached row, not read) -> ``k_nope`` [T, H, nope],
    ``k_rope`` [T, rope] (every head's), ``v`` [T, H, v], in ``dtype``;
    the products accumulate in float32."""
    L = w_kvb.shape[0]
    w_k, w_v = _split_kvb(w_kvb, n_head, nope, v_dim)
    c_kv = latent[:, :L]
    k_nope = jnp.einsum("tl,lhd->thd", c_kv, w_k,
                        preferred_element_type=jnp.float32).astype(dtype)
    v = jnp.einsum("tl,lhd->thd", c_kv, w_v,
                   preferred_element_type=jnp.float32).astype(dtype)
    return k_nope, latent[:, L:L + rope_dim].astype(dtype), v


def _keys(k_nope, k_rope):
    """Every head's key: the shared rotary key behind its ``k_nope``."""
    return jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope[:, None], k_nope.shape[:2] + k_rope.shape[-1:])], axis=-1)


def mla_attention(q, latent, w_kvb, mask, n_head, nope, rope_dim, v_dim,
                  scale, block=MLA_QUERY_BLOCK, flash=None, interpret=None,
                  select=None):
    """``q`` [T, H * (nope + rope)] (rotated); ``latent`` [T, >= L +
    rope] (``c_kv`` after its norm | the rotated shared key | lanes that
    pad the cached row, not read); ``mask`` [T] (0 = pad row, never
    attended).  Returns [T, H * v] in ``q``'s type.

    On the TPU (``flash``) the causal flash kernel of
    ``ops/attention_ops.py`` takes the expanded heads (keys 192 wide,
    values 128: it never asked them to be alike) and skips the blocks
    above the diagonal; elsewhere, and where its gate refuses the
    length, plain XLA a block of query rows at a time.

    ``select`` [T, T] int8 (``ops/dsa_ops.py``; 0 = query row ``t``
    leaves row ``s`` out of its softmax; causal and free of pad rows
    already): on the TPU ``dsa_ops.selected_attention``, a flash forward
    kernel that takes the selection's blocks beside the keys' and skips
    the blocks above the diagonal; elsewhere plain XLA over every key
    with the selection in the mask, the query block sized so that a
    block's float32 scores stay near 512 MB."""
    from paddle_tpu.ops import attention_ops
    if interpret is None:
        interpret = attention_ops._use_interpret()
    T = q.shape[0]
    k_nope, k_rope, v = mla_expand(latent, w_kvb, n_head, nope, rope_dim,
                                   v_dim, q.dtype)
    qh = q.reshape(T, n_head, nope + rope_dim)
    if not interpret if flash is None else flash:
        heads = lambda a: a.transpose(1, 0, 2)            # [H, T, D]
        k = _keys(k_nope, k_rope)
        if select is not None:
            from paddle_tpu.ops import dsa_ops
            out = dsa_ops.selected_attention(
                heads(qh), heads(k), heads(v), select.astype(jnp.int8),
                scale=float(scale), interpret=interpret)
        else:
            out = attention_ops._pallas_attention(
                heads(qh)[None], heads(k)[None], heads(v)[None],
                mask[None].astype(jnp.float32), True, scale,
                interpret=interpret)
            out = None if out is None else out[0][0]
        if out is not None:
            return out.transpose(1, 0, 2).reshape(T, n_head * v_dim)
    if select is not None:
        # a power of two (the buckets are multiples of 2048 rows: the
        # halving below then stops at once)
        block = min(block, 1 << max(
            3, ((1 << 27) // (n_head * T)).bit_length() - 1))
    block = min(int(block), T)
    while T % block:
        block //= 2
    seen_col = (mask > 0)[None, :]
    cols = jax.lax.broadcasted_iota(jnp.int32, (block, T), 1)

    def rows(i):
        qb = jax.lax.dynamic_slice_in_dim(qh, i * block, block, 0)
        sc = jnp.einsum("qhd,thd->hqt", qb[..., :nope], k_nope,
                        preferred_element_type=jnp.float32) \
            + jnp.einsum("qhd,td->hqt", qb[..., nope:], k_rope,
                         preferred_element_type=jnp.float32)
        row = i * block + jax.lax.broadcasted_iota(
            jnp.int32, (block, T), 0)
        seen = (cols <= row) & seen_col
        if select is not None:
            seen &= jax.lax.dynamic_slice_in_dim(select, i * block, block,
                                                 0) > 0
        probs = jax.nn.softmax(jnp.where(seen, sc * scale, NEG_INF), axis=-1)
        return jnp.einsum("hqt,thd->qhd", probs.astype(v.dtype), v,
                          preferred_element_type=jnp.float32).astype(q.dtype)

    out = jax.lax.map(rows, jnp.arange(T // block))
    return out.reshape(T, n_head * v_dim)


def _infer_mla_attention(op, block):
    q = block.var(op.input("Q")[0])
    if q.shape is None:
        raise ShapeInferenceSkip()
    out = block.var(op.output("Out")[0])
    out.shape = tuple(q.shape[:-1]) + (
        int(op.attr("n_head")) * int(op.attr("v_dim")),)
    out.dtype = q.dtype


@register_op("mla_attention", infer_shape=_infer_mla_attention,
             no_grad_inputs=("Mask", "Select"))
def mla_attention_lower(ctx):
    """Q [1, T, H * (nope + rope)]; Latent [1, T, >= L + rope]; Wkvb [L,
    H * (nope + v)]; Mask [1, T].  attrs n_head, nope_dim, rope_dim,
    v_dim, scale.  Out [1, T, H * v].

    Select (optional, with attr select_top_k): [1, T, T] int8, row ``t``
    attends the rows it marks and no other.  Up to ``select_top_k`` rows
    the selection is the identity and the input is not read."""
    q = ctx.input("Q")[0]
    select = None
    if ctx.has_input("Select") and q.shape[0] > int(
            ctx.attr("select_top_k", 0)):
        select = ctx.input("Select")[0]
    out = mla_attention(
        q, ctx.input("Latent")[0], ctx.input("Wkvb"),
        ctx.input("Mask")[0], int(ctx.attr("n_head")),
        int(ctx.attr("nope_dim")), int(ctx.attr("rope_dim")),
        int(ctx.attr("v_dim")), float(ctx.attr("scale", 1.0)),
        select=select)
    ctx.set_output("Out", out[None])


def mla_absorb(x, w_kvb, n_head, nope, v_dim, side, pad=0):
    """``side`` ``"q"``: ``x`` [R, H * (nope + rope)] -> [R, H * (L +
    rope + pad)], each head's ``q_nope`` taken through ``W_k^T`` into
    the latent, its rotary part kept behind it, then ``pad`` zero lanes
    (the cached row's).  ``side`` ``"o"``: ``x`` [R, H * L] (the context
    in the latent) -> [R, H * v] through ``W_v``."""
    R, L = x.shape[0], w_kvb.shape[0]
    w_k, w_v = _split_kvb(w_kvb, n_head, nope, v_dim)
    if side == "o":
        out = jnp.einsum("rhl,lhd->rhd", x.reshape(R, n_head, L), w_v,
                         preferred_element_type=jnp.float32)
        return out.astype(x.dtype).reshape(R, n_head * v_dim)
    xh = x.reshape(R, n_head, -1)
    lat = jnp.einsum("rhd,lhd->rhl", xh[..., :nope], w_k,
                     preferred_element_type=jnp.float32).astype(x.dtype)
    zeros = jnp.zeros((R, n_head, int(pad)), x.dtype)
    return jnp.concatenate([lat, xh[..., nope:], zeros],
                           axis=-1).reshape(R, -1)


def _infer_mla_absorb(op, block):
    x = block.var(op.input("X")[0])
    w = block.var(op.input("Wkvb")[0])
    if x.shape is None or w.shape is None:
        raise ShapeInferenceSkip()
    H, nope = int(op.attr("n_head")), int(op.attr("nope_dim"))
    if op.attr("side") == "o":
        width = H * int(op.attr("v_dim"))
    else:
        width = int(x.shape[-1]) + H * (
            int(w.shape[0]) - nope + int(op.attr("pad") or 0))
    out = block.var(op.output("Out")[0])
    out.shape, out.dtype = tuple(x.shape[:-1]) + (width,), x.dtype


@register_op("mla_absorb", infer_shape=_infer_mla_absorb)
def mla_absorb_lower(ctx):
    """X [..., width]; Wkvb [L, H * (nope + v)].  attrs n_head,
    nope_dim, v_dim, side ("q" | "o"), pad (side "q")."""
    x = ctx.input("X")
    out = mla_absorb(x.reshape(-1, x.shape[-1]), ctx.input("Wkvb"),
                     int(ctx.attr("n_head")), int(ctx.attr("nope_dim")),
                     int(ctx.attr("v_dim")), str(ctx.attr("side")),
                     int(ctx.attr("pad", 0)))
    ctx.set_output("Out", out.reshape(x.shape[:-1] + out.shape[-1:]))


def chunk_weights(w_kvb, n_head, nope, rope_dim, v_dim, width):
    """``W_kvb`` [L, H * (nope + v)] as the chunk kernel multiplies a
    block of cached rows ``[c_kv | k_rope | pad]`` (``width`` lanes) by
    it: ``(w_k [width, H * Dk], w_v [L, H * v])``, the ``expand`` of
    ``window_ops.flash_attention``.  Head ``h``'s columns of ``w_k``
    take ``c_kv`` through ``W_k`` into the key's leading ``nope`` lanes
    and pass the shared rotary key as it is into the ``rope_dim`` lanes
    behind them (ones: the float32 sum of one value is that value), then
    zeros up to ``Dk``, whole 128-lane tiles; the row's pad lanes meet
    zeros."""
    L = w_kvb.shape[0]
    w_k, w_v = _split_kvb(w_kvb, n_head, nope, v_dim)
    Dk = -(-(nope + rope_dim) // 128) * 128
    passed = jnp.zeros((width - L, Dk), w_kvb.dtype).at[
        jnp.arange(rope_dim), nope + jnp.arange(rope_dim)].set(1)
    w_k = jnp.concatenate([
        jnp.pad(w_k, ((0, 0), (0, 0), (0, Dk - nope))),
        jnp.broadcast_to(passed[:, None], (width - L, n_head, Dk))])
    return w_k.reshape(width, -1), w_v.reshape(L, -1)


def mla_attention_chunk(q, row, w_kvb, pool, table, start, real, n_head,
                        nope, rope_dim, v_dim, scale, select=None,
                        interpret=None):
    """ONE CHUNK of a prompt over the slot's pages.  ``q`` [C, H * (nope
    + rope)] (rotated) and ``row`` [C, W] (the latent rows as cached)
    stand at positions ``start ..``; ``real`` [1, C] bool (real rows
    first); ``pool`` [num_pages, page_len, W]; ``table`` [1, P] the
    slot's pages; ``select`` [C, P * page_len] int8 or None.  The real
    rows are written at their positions, then the chunk attends the
    pages' rows ``0 ..`` under the diagonal shifted by ``start`` (and
    under the selection), EXPANDED: every head its own K/V head in
    ``window_ops``'s causal flash kernel, which makes a (head, key
    block) from the block's latent rows where it uses it
    (``chunk_weights``; the queries laid out as the keys are, ``Dk``
    lanes a head) and none above the diagonal.  Where the block rule
    refuses the rows, or on the chip the lanes are not whole tiles, the
    composed form over the expanded bucket (toy sizes); counted once a
    lowering, ``attention.latent_chunk_kernel`` /
    ``attention.latent_chunk_composed``.  Returns ``(out [C, H * v],
    pool)``."""
    from paddle_tpu.ops.attention_ops import (_paged_cache_update,
                                              _use_interpret)
    from paddle_tpu.ops.window_ops import (composed_attention,
                                           flash_attention, flash_blocks)
    from paddle_tpu.profiler import runtime_metrics
    if interpret is None:
        interpret = _use_interpret()
    C, L, W = q.shape[0], w_kvb.shape[0], pool.shape[-1]
    pool, = _paged_cache_update((pool,), (row[None],), table,
                                (start + C).reshape(1, 1), row_lens=real)
    rows = pool[table[0]].reshape(-1, W).astype(q.dtype)
    T = rows.shape[0]
    kernel = flash_blocks(C, 1, 0, keys=T) is not None and (
        interpret or not (W % 128 or L % 128 or v_dim % 128))
    runtime_metrics.inc("attention.latent_chunk_kernel" if kernel
                        else "attention.latent_chunk_composed")
    if not kernel:
        k_nope, k_rope, v = mla_expand(rows, w_kvb, n_head, nope, rope_dim,
                                       v_dim, q.dtype)
        return composed_attention(
            q, _keys(k_nope, k_rope).reshape(T, -1), v.reshape(T, -1),
            n_head, n_head, scale, start=start, select=select), pool
    expand = chunk_weights(w_kvb.astype(q.dtype), n_head, nope, rope_dim,
                           v_dim, W)
    q = jnp.pad(q.reshape(C, n_head, -1), ((0, 0), (0, 0), (
        0, expand[0].shape[1] // n_head - nope - rope_dim)))
    return flash_attention(
        q.reshape(C, -1), rows, None, None, start, None, select, expand,
        n_head=n_head, n_kv_head=n_head, scale=scale,
        interpret=interpret), pool


@register_op("mla_attention_chunk", infer_shape=_infer_mla_attention,
             no_gradient=True, stateful_outputs=("CacheOut",))
def mla_attention_chunk_lower(ctx):
    """Q [1, C, H * (nope + rope)]; Latent [1, C, W] the chunk's rows as
    they are cached; Wkvb [L, H * (nope + v)]; Cache [num_pages,
    page_len, W] the persistable latent pool; PageTable [1, P] int32
    the slot's row (P a page bucket that covers the chunk's last real
    row); Pos [1, C] int32 the rows' positions ``start .. start + C -
    1``; Mask [1, C] (1 = a real row, real rows first).  attrs n_head,
    nope_dim, rope_dim, v_dim, scale.  Out [1, C, H * v]; CacheOut names
    the pool itself.  A pad row is written nowhere and seen by no real
    row.

    Select (optional, with attr select_top_k): [1, C, P * page_len] int8
    over the slot's rows in order; up to ``select_top_k`` rows in the
    bucket the selection is the identity and the input is not read."""
    q = ctx.input("Q")[0]
    pool, table = ctx.input("Cache"), ctx.input("PageTable")
    select = None
    if ctx.has_input("Select") and table.shape[1] * pool.shape[1] > int(
            ctx.attr("select_top_k", 0)):
        select = ctx.input("Select")[0]
    out, pool = mla_attention_chunk(
        q, ctx.input("Latent")[0], ctx.input("Wkvb"), pool, table,
        ctx.input("Pos").reshape(-1)[0].astype(jnp.int32),
        ctx.input("Mask") > 0, int(ctx.attr("n_head")),
        int(ctx.attr("nope_dim")), int(ctx.attr("rope_dim")),
        int(ctx.attr("v_dim")), float(ctx.attr("scale", 1.0)),
        select=select)
    ctx.set_output("Out", out[None])
    ctx.set_output("CacheOut", pool)


# ---------------------------------------------------------------------------
# latent attention inside a window: a RING of latent rows a slot
# ---------------------------------------------------------------------------

def head_gate(x, gate, n_head):
    """``x`` [..., H * v] (a layer's attention output before ``W_o``),
    ``gate`` [..., H] (the gate's logits): head ``j``'s lanes times
    ``sigmoid(gate_j)``, the sigmoid and the product in float32.
    Returns ``x``'s type."""
    g = jax.nn.sigmoid(gate.astype(jnp.float32))[..., None]
    xh = x.reshape(x.shape[:-1] + (n_head, -1)).astype(jnp.float32)
    return (xh * g).astype(x.dtype).reshape(x.shape)


@register_op("head_gate", infer_shape=infer_shape_unary())
def head_gate_lower(ctx):
    """X [..., H * v]; Gate [..., H] the gate's logits, a row for every
    row of X.  attrs n_head.  Out = X, head ``j`` times
    ``sigmoid(Gate_j)``."""
    ctx.set_output("Out", head_gate(ctx.input("X"), ctx.input("Gate"),
                                    int(ctx.attr("n_head"))))


def latent_window_attention(q, latent, w_kvb, n_head, nope, rope_dim, v_dim,
                            scale, window, interpret=None):
    """A WHOLE sequence under the band, nothing cached (the training
    forward): ``q`` [T, H * (nope + rope)] (rotated), ``latent`` [T, >=
    L + rope]; K and V of every head EXPANDED from the latent
    (``mla_expand``), then ``window_ops``'s banded attention with every
    head its own K/V head (row ``t`` sees rows ``u <= t`` with ``t - u <
    window``; real rows first, so no real row sees a pad row).  Returns
    [T, H * v] in ``q``'s type."""
    from paddle_tpu.ops.window_ops import prefill_attention
    T = q.shape[0]
    k_nope, k_rope, v = mla_expand(latent, w_kvb, n_head, nope, rope_dim,
                                   v_dim, q.dtype)
    return prefill_attention(q, _keys(k_nope, k_rope).reshape(T, -1),
                             v.reshape(T, -1), None, n_head, n_head, scale,
                             window, interpret=interpret)


def latent_window_chunk(q, row, w_kvb, ring, slot, start, n, n_head, nope,
                        rope_dim, v_dim, scale, window, interpret=None):
    """ONE CHUNK of a prompt through a window layer of latent attention.
    ``q`` [C, H * (nope + rope)] (rotated) and ``row`` [C, W] (the latent
    rows as cached) stand at positions ``start ..``, the first ``n`` of
    them real; ``ring`` [num_slots, R, W].  EXPANDED, as the whole
    sequence: K and V of every head are made from the slot's ring rows
    of the positions before ``start`` (``window_ops.ring_lead``) and the
    chunk's own rows in ONE product, and the queries go as they come
    under the band, every head its own K/V head (``window_ops``'s banded
    flash kernel where its block rule admits the rows, else the composed
    form: counted once a lowering, ``attention.latent_window_kernel`` /
    ``attention.latent_window_composed``); the chunk's last real rows go
    through the ring.  Returns ``(out [C, H * v], ring)``."""
    from paddle_tpu.ops.window_ops import (flash_blocks, lead_rows,
                                           prefill_attention, ring_after,
                                           ring_lead)
    from paddle_tpu.profiler import runtime_metrics
    C = q.shape[0]
    runtime_metrics.inc(
        "attention.latent_window_composed"
        if flash_blocks(C, 1, window) is None
        else "attention.latent_window_kernel")
    own = jax.lax.dynamic_index_in_dim(ring, slot, 0, keepdims=False)
    lead, held = ring_lead(own, start, lead_rows(C, 1, window))
    k_nope, k_rope, v = mla_expand(
        jnp.concatenate([lead, row.astype(lead.dtype)]), w_kvb, n_head,
        nope, rope_dim, v_dim, q.dtype)
    rows = k_nope.shape[0]
    out = prefill_attention(
        q, _keys(k_nope, k_rope).reshape(rows, -1), v.reshape(rows, -1),
        None, n_head, n_head, scale, window, before=(None, None, held),
        interpret=interpret)
    ring = jax.lax.dynamic_update_index_in_dim(
        ring, ring_after(own, row, start, n), slot, 0)
    return out, ring


def _infer_latent_window(op, block):
    _infer_mla_attention(op, block)
    # RingOut aliases the persistable ring (in-place update)


@register_op("latent_window_attention", infer_shape=_infer_latent_window,
             no_grad_inputs=("Mask", "Ring", "Slot", "Pos"),
             stateful_outputs=("RingOut",))
def latent_window_attention_lower(ctx):
    """A window layer of latent attention, its prefill.  Q [1, T, H *
    (nope + rope)]; Latent [1, T, W] the rows as they are cached; Wkvb
    [L, H * (nope + v)]; Mask [1, T] (1 = a real row, real rows first).
    attrs n_head, nope_dim, rope_dim, v_dim, scale, window.  Out [1, T,
    H * v].  With no further input: a WHOLE sequence, expanded, nothing
    cached (the training forward).

    ONE CHUNK of a prompt: Ring [num_slots, ring, W] persistable; Slot
    [1, 1] int32; Pos [1, T] int32 the rows' positions ``start ..``.
    The chunk attends the ring's rows of the ``window - 1`` positions
    before ``start`` followed by its own, expanded alike, and its last
    real rows go through the slot's ring; RingOut names the ring
    itself."""
    q, latent, w_kvb = ctx.input("Q")[0], ctx.input("Latent")[0], \
        ctx.input("Wkvb")
    sizes = (int(ctx.attr("n_head")), int(ctx.attr("nope_dim")),
             int(ctx.attr("rope_dim")), int(ctx.attr("v_dim")),
             float(ctx.attr("scale", 1.0)), int(ctx.attr("window")))
    if not ctx.has_input("Ring"):
        ctx.set_output("Out", latent_window_attention(
            q, latent, w_kvb, *sizes)[None])
        return
    out, ring = latent_window_chunk(
        q, latent, w_kvb, ctx.input("Ring"),
        ctx.input("Slot").reshape(-1)[0].astype(jnp.int32),
        ctx.input("Pos").reshape(-1)[0].astype(jnp.int32),
        jnp.sum(ctx.input("Mask") > 0).astype(jnp.int32), *sizes)
    ctx.set_output("Out", out[None])
    ctx.set_output("RingOut", ring)


def latent_ring_step(q, row, ring, lens, n_head, v_width, scale, window,
                     kernel=None):
    """One decode step over a ring of latent rows.  ``q`` [S, H * W]
    (absorbed: ``mla_absorb`` side ``"q"``); ``row`` [S, W] this step's
    latent row; ``ring`` [S, R, W]; ``lens`` [S] rows INCLUDING this
    step's (0 = free slot: nothing written, zeros out).  The row is
    written at ``(lens - 1) mod R`` and every head attends the ring's
    rows inside the window, ONE row serving as key and, its leading
    ``v_width`` lanes, as value.  ``kernel``: None = the composed form;
    else the Pallas kernel's ``interpret`` flag (``window_ops``'s ring
    kernel, one K/V head, the ring read once).  Returns ``(out [S, H *
    v_width], ring)``."""
    from paddle_tpu.ops.window_ops import ring_attention
    S, R, W = ring.shape
    pos = lens.astype(jnp.int32) - 1
    # a free slot's row lands nowhere
    at = jnp.where(pos >= 0, jnp.mod(pos, R), R)
    ring = ring.at[jnp.arange(S, dtype=jnp.int32), at].set(
        row.astype(ring.dtype), mode="drop")
    if kernel is not None and (kernel or not (
            W % 128 or v_width % 128 or R % (32 // ring.dtype.itemsize))):
        return ring_attention(q, ring, None, lens, n_head=n_head,
                              scale=scale, window=window, interpret=kernel,
                              v_width=v_width), ring
    # row r holds position pos - ((pos - r) mod R), if that is not negative
    back = jnp.mod(pos[:, None] - jnp.arange(R, dtype=jnp.int32)[None], R)
    seen = (back <= pos[:, None]) & (back < window)              # [S, R]
    sc = jnp.einsum("shd,srd->shr", q.reshape(S, n_head, W).astype(
        ring.dtype), ring, preferred_element_type=jnp.float32) * scale
    probs = jax.nn.softmax(jnp.where(seen[:, None], sc, NEG_INF), axis=-1)
    out = jnp.einsum("shr,srd->shd", probs,
                     ring[..., :v_width].astype(jnp.float32),
                     preferred_element_type=jnp.float32,
                     precision=jax.lax.Precision.HIGHEST)
    out = jnp.where((pos >= 0)[:, None, None], out, 0.0)
    return out.reshape(S, -1).astype(q.dtype), ring


def _infer_latent_window_step(op, block):
    q = block.var(op.input("Q")[0])
    if q.shape is None:
        raise ShapeInferenceSkip()
    out = block.var(op.output("Out")[0])
    out.shape = tuple(q.shape[:-1]) + (
        int(op.attr("n_head")) * int(op.attr("v_width")),)
    out.dtype = q.dtype
    # RingOut aliases the persistable ring (in-place update)


@register_op("latent_window_step", infer_shape=_infer_latent_window_step,
             no_gradient=True, stateful_outputs=("RingOut",))
def latent_window_step_lower(ctx):
    """A window layer of latent attention, its decode step.  Q [S, 1, H
    * W] the absorbed queries; Row [S, 1, W] this step's latent row as
    cached; Ring [S, ring, W] persistable; Lens [S, 1] int32 rows
    INCLUDING this step's (0 = free slot).  attrs n_head, v_width (the
    row's leading lanes that are the value), scale, window (<= ring).
    Out [S, 1, H * v_width] the context in the latent; RingOut names the
    ring itself."""
    from paddle_tpu.ops.attention_ops import _use_interpret
    q = ctx.input("Q")
    # the kernel on the chip; off it the composed form
    out, ring = latent_ring_step(
        q[:, 0], ctx.input("Row")[:, 0], ctx.input("Ring"),
        ctx.input("Lens").reshape(q.shape[0]), int(ctx.attr("n_head")),
        int(ctx.attr("v_width")), float(ctx.attr("scale", 1.0)),
        int(ctx.attr("window")), kernel=None if _use_interpret() else False)
    ctx.set_output("Out", out[:, None])
    ctx.set_output("RingOut", ring)
