"""Learned sparse attention (the DeepSeek-V3.2 "lightning indexer" that
``glm_moe_dsa`` follows; ``models/latent_moe.py`` with ``index_topk``): a
small second attention that SCORES every cached row for a query row, and
an exact top-``k`` selection of them; latent attention then runs its
softmax over the selected rows and nowhere else (``mla_attention`` and
``paged_attention_latent`` take the selection as ``Select``).

Indexer of one layer, ``h`` the normed residual row and ``c_q`` the
query's low-rank latent after its norm::

    q_j = (c_q W_qb)_j          j < n_head heads of head_dim lanes, the
                                FIRST rope_dim lanes rotated
    k   = LayerNorm(h W_k)      head_dim lanes, scale and bias, the first
                                rope_dim lanes rotated; CACHED, one row a
                                token (a second page pool under the latent
                                pool's page table)
    w   = (h W_w) n_head^-1/2 head_dim^-1/2
    I(t, s) = sum_j w_j(t) ReLU(q_j(t) . k(s))             float32

``dsa_index`` is the whole-sequence form (one prompt, ``Scores`` [1, T,
T]); ``dsa_index_chunk`` the serving prefill's (ONE CHUNK of a prompt:
writes the chunk's key rows into the slot's pages of the pool, scores
its C query rows against the page bucket's rows: ``Scores`` [1, C, P *
page_len]); ``dsa_index_paged`` the decode step's (writes this step's
key row into the pool, scores the page bucket's rows: ``Scores`` [S, 1,
P * page_len]); both paged forms in the slot's own row order.
``dsa_select`` keeps, a query row, the ``top_k`` largest scores among
the rows the query may see (causal and real in the prefill, whole or by
chunks; ``< Lens`` in the decode step), ties to the lower position as
``jax.lax.top_k`` has them, ALL of them while there are no more than
``top_k``: ``Select`` is a 0/1 mask, int8 [1, T, T] or [1, C, P *
page_len], or int32 [S, 1, P * page_len].  A row's selection depends on
that row's query and the keys at or before it alone, so a prompt's
chunks select what the whole prompt would.  Where the rows cannot pass
``top_k`` (a bucket of no more rows) the selection is the identity:
nothing is scored, the mask is what the query may see, and the attention
ops skip it.

The selection is exact and takes no sort: the ``top_k``-th largest score
of a row is found bit by bit (32 counting passes over an
order-preserving integer image of the float32 scores), then the ties at
that value are taken from the left (a running count made of two small
triangular products, not a cumulative sum over the whole row).

A whole sequence's attention under a selection is
``selected_attention``, a flash forward kernel that takes the
selection's int8 blocks beside the keys' (``mla_ops.mla_attention``
calls it on the TPU); a chunk's is ``window_ops``'s causal kernel with
``select=`` beside the latent blocks it expands a head at a time
(``mla_ops.mla_attention_chunk``); the decode step's is the latent paged
kernel with ``select=``.

Op scopes on the device trace: ``ptop_dsa_index*`` (projections, rotary
lanes, scores), ``ptop_dsa_select*`` (the top-k).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.mla_ops import yarn_frequencies
from paddle_tpu.ops.registry import ShapeInferenceSkip, register_op

# query rows of one block of the prefill's index scores and selection:
# 32 heads x 256 x 16384 float32 products are 537 MB
DSA_QUERY_BLOCK = 256
LAYER_NORM_EPS = 1e-6


# ---------------------------------------------------------------------------
# the indexer's projections
# ---------------------------------------------------------------------------

def rope_leading(x, pos, n_head, rope_dim, freqs):
    """``x`` [R, n_head * D] float32; rotates the FIRST ``rope_dim`` lanes
    of every head by the row's position, pair ``i`` being lanes ``(i, i +
    rope_dim / 2)``.  Returns float32."""
    R = x.shape[0]
    half = rope_dim // 2
    xh = x.reshape(R, n_head, -1)
    a, b, keep = xh[..., :half], xh[..., half:rope_dim], xh[..., rope_dim:]
    ang = pos.reshape(R).astype(jnp.float32)[:, None, None] \
        * jnp.asarray(freqs, jnp.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, keep],
                           axis=-1).reshape(x.shape)


def index_projections(c_q, h, pos, w_qb, w_k, k_scale, k_bias, w_w, n_head,
                      rope_dim, theta):
    """``c_q`` [R, q_lora], ``h`` [R, d], ``pos`` [R] -> the indexer's
    queries [R, n_head, D] and key rows [R, D] in ``h``'s type, and the
    head weights [R, n_head] in float32."""
    f32 = jnp.float32
    D = w_k.shape[-1]
    freqs = yarn_frequencies(rope_dim, float(theta))
    q = jnp.dot(c_q, w_qb, preferred_element_type=f32)
    q = rope_leading(q, pos, n_head, rope_dim, freqs).astype(h.dtype)
    k = jnp.dot(h, w_k, preferred_element_type=f32)
    mean = jnp.mean(k, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(k - mean), axis=-1, keepdims=True)
    k = (k - mean) * jax.lax.rsqrt(var + LAYER_NORM_EPS) \
        * k_scale.astype(f32) + k_bias.astype(f32)
    k = rope_leading(k, pos, 1, rope_dim, freqs).astype(h.dtype)
    w = jnp.dot(h, w_w, preferred_element_type=f32) \
        * (float(n_head) ** -0.5 * float(D) ** -0.5)
    return q.reshape(q.shape[0], n_head, D), k, w


def index_scores(q, k, w):
    """``q`` [..., Q, H, D], ``k`` [..., T, D], ``w`` [..., Q, H] ->
    ``I`` [..., Q, T] float32."""
    s = jnp.einsum("...qhd,...td->...qht", q, k,
                   preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(s) * w[..., None], axis=-2)


# ---------------------------------------------------------------------------
# exact top-k as a mask
# ---------------------------------------------------------------------------

def _ordered(x):
    """float32 -> uint32, order-preserving (a larger float is a larger
    integer); no finite float maps to 0."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(1 << 31))


def _running_count(x, chunk=128):
    """Inclusive running count of the True entries of ``x`` [..., T]
    along its last axis, int32: two small triangular products (inside
    chunks of ``chunk`` lanes, then over the chunks' totals), exact in
    float32 accumulation; a cumulative sum over thousands of lanes lowers
    to work that grows with their square."""
    T = x.shape[-1]
    n = -(-T // chunk)
    lead = x.shape[:-1]
    xs = jnp.pad(x, [(0, 0)] * len(lead) + [(0, n * chunk - T)]) \
        .reshape(lead + (n, chunk)).astype(jnp.bfloat16)
    upper = lambda m, k: jnp.triu(jnp.ones((m, m), jnp.bfloat16), k)
    inner = jnp.einsum("...nc,cd->...nd", xs, upper(chunk, 0),
                       preferred_element_type=jnp.float32)
    # a chunk's total is at most ``chunk``: exact in bfloat16
    before = jnp.einsum("...n,nm->...m",
                        inner[..., -1].astype(jnp.bfloat16), upper(n, 1),
                        preferred_element_type=jnp.float32)
    return (inner + before[..., None]).reshape(lead + (n * chunk,))[
        ..., :T].astype(jnp.int32)


def select_mask(scores, valid, k):
    """``scores`` [..., T] float32, ``valid`` [..., T] bool: True at the
    ``k`` largest valid scores of every row (ties to the lower position),
    at every valid one where there are no more than ``k``."""
    if scores.shape[-1] <= k:
        return valid
    # -0.0 and 0.0 are one value to a comparison of floats
    x = jnp.where(scores == 0, 0.0, scores.astype(jnp.float32))
    key = jnp.where(valid, _ordered(x), jnp.uint32(0))

    def bit(i, t):
        cand = t | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        n = jnp.sum(key >= cand[..., None], axis=-1, dtype=jnp.int32)
        return jnp.where(n >= k, cand, t)

    # the k-th largest key (0 where fewer than k are valid)
    t = jax.lax.fori_loop(0, 32, bit,
                          jnp.zeros(scores.shape[:-1], jnp.uint32))
    above = key > t[..., None]
    ties = (key == t[..., None]) & valid
    need = k - jnp.sum(above, axis=-1, dtype=jnp.int32)
    rank = _running_count(ties)
    return above | (ties & (rank <= need[..., None]))


def _query_block(T, block):
    block = min(int(block), T)
    while T % block:
        block //= 2
    return block


def causal_select(scores, mask, k, block=DSA_QUERY_BLOCK, start=0):
    """The prefill's selection: ``scores`` [C, T] of C query rows,
    which stand at key rows ``start ..`` (traced or not), over T key
    rows; ``mask`` [C] (0 = pad row; real rows first, so the real key
    rows are those before ``start`` and the real query rows' own) ->
    int8 [C, T], row ``r`` selecting among the real rows ``s <= start +
    r``.  A whole prompt is ``start`` 0 with ``C`` = ``T``."""
    C, T = scores.shape
    real = jnp.arange(T, dtype=jnp.int32)[None, :] \
        < start + jnp.sum(mask > 0).astype(jnp.int32)
    block = _query_block(C, block)
    cols = jax.lax.broadcasted_iota(jnp.int32, (block, T), 1)

    def rows(i):
        row = start + i * block \
            + jax.lax.broadcasted_iota(jnp.int32, (block, T), 0)
        valid = (cols <= row) & real
        sc = jax.lax.dynamic_slice_in_dim(scores, i * block, block, 0)
        return select_mask(sc, valid, k).astype(jnp.int8)

    return jax.lax.map(rows, jnp.arange(C // block)).reshape(C, T)


# ---------------------------------------------------------------------------
# the prefill's attention under a selection: a flash forward kernel
# ---------------------------------------------------------------------------

SELECT_FLASH_BLOCK = 512
_NEG, _M_INIT = -1e9, -1e30


def _select_flash_kernel(q_ref, k_ref, v_ref, sel_ref, o_ref, acc, m_scr,
                         l_scr, *, scale):
    """One (head, query block); the key blocks stream through VMEM along
    the innermost, sequential grid axis with an online softmax, as the
    causal flash kernel of ``ops/attention_ops.py`` has it; a score
    counts where the selection's int8 block marks it (the selection is
    causal already).  Key blocks above the diagonal are not computed
    (and, their block index clamped by the caller, not copied)."""
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, _M_INIT)
        l_scr[...] = jnp.zeros_like(l_scr)

    @pl.when(j <= i)
    def _():
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(sel_ref[...].astype(jnp.int32) > 0, s, _NEG)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # a block that marks nothing for a row leaves weights of 1 behind
        # (exp(0)); the first real score's alpha = exp(-1e9 - m) = 0 wipes
        # them, and every real row selects at least one row
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc[...] = acc[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        o_ref[0] = (acc[...] / l_scr[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def selected_attention(q, k, v, select, *, scale, interpret=False):
    """``q``, ``k`` [H, T, D], ``v`` [H, T, Dv], ``select`` [T, T] int8
    (row ``t`` attends the rows it marks, none after ``t``) -> [H, T, Dv]
    in ``q``'s type.  None where ``T`` is not whole blocks of
    ``SELECT_FLASH_BLOCK`` rows (the caller then takes plain XLA)."""
    H, T, D = q.shape
    Dv, B = v.shape[-1], SELECT_FLASH_BLOCK
    if T % B:
        return None
    n = T // B
    # a key block above the diagonal is not computed: hand the kernel the
    # diagonal's again, which is not copied a second time
    kv = lambda h, i, j: (h, jnp.minimum(j, i), 0)
    return pl.pallas_call(
        functools.partial(_select_flash_kernel, scale=scale),
        grid=(H, n, n),
        in_specs=[pl.BlockSpec((1, B, D), lambda h, i, j: (h, i, 0)),
                  pl.BlockSpec((1, B, D), kv),
                  pl.BlockSpec((1, B, Dv), kv),
                  pl.BlockSpec((B, B),
                               lambda h, i, j: (i, jnp.minimum(j, i)))],
        out_specs=pl.BlockSpec((1, B, Dv), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((H, T, Dv), q.dtype),
        scratch_shapes=[pltpu.VMEM((B, Dv), jnp.float32),
                        pltpu.VMEM((B, 1), jnp.float32),
                        pltpu.VMEM((B, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, select)


# ---------------------------------------------------------------------------
# IR ops
# ---------------------------------------------------------------------------

def _projections(ctx, c_q, h, pos):
    return index_projections(
        c_q, h, pos, ctx.input("Wq"), ctx.input("Wk"), ctx.input("KScale"),
        ctx.input("KBias"), ctx.input("Ww"), int(ctx.attr("n_head")),
        int(ctx.attr("rope_dim")), float(ctx.attr("theta", 10000.0)))


def _query_blocks(fn, C, *arrays):
    """``fn`` over blocks of ``DSA_QUERY_BLOCK`` of the ``C`` leading
    rows of ``arrays``, the blocks' results side by side again."""
    block = _query_block(C, DSA_QUERY_BLOCK)
    part = lambda a, i: jax.lax.dynamic_slice_in_dim(a, i * block, block, 0)
    out = jax.lax.map(lambda i: fn(*(part(a, i) for a in arrays)),
                      jnp.arange(C // block))
    return out.reshape((C,) + out.shape[2:])


def _infer_dsa_index(op, block):
    h = block.var(op.input("X")[0])
    wk = block.var(op.input("Wk")[0])
    if h.shape is None or wk.shape is None:
        raise ShapeInferenceSkip()
    key = block.var(op.output("Key")[0])
    key.shape, key.dtype = tuple(h.shape[:-1]) + (int(wk.shape[-1]),), h.dtype
    sc = block.var(op.output("Scores")[0])
    sc.shape, sc.dtype = tuple(h.shape[:-1]) + (h.shape[-2],), "float32"


@register_op("dsa_index", infer_shape=_infer_dsa_index, no_gradient=True)
def dsa_index_lower(ctx):
    """Cq [1, T, q_lora]; X [1, T, d] (the normed residual); Pos [1, T];
    Wq [q_lora, H * D]; Wk [d, D]; KScale, KBias [D]; Ww [d, H].  attrs
    n_head, rope_dim, theta, top_k.  Key [1, T, D] (the rows that seed
    the index-key pool: the caller zeroes its pad rows); Scores [1, T, T]
    float32, zeros where ``T <= top_k`` (nothing is selected there, and
    nothing scored)."""
    c_q, h = ctx.input("Cq")[0], ctx.input("X")[0]
    T = h.shape[0]
    q, k, w = _projections(ctx, c_q, h, ctx.input("Pos").reshape(T))
    ctx.set_output("Key", k[None])
    if T <= int(ctx.attr("top_k")):
        ctx.set_output("Scores", jnp.zeros((1, T, T), jnp.float32))
        return
    ctx.set_output("Scores", _query_blocks(
        lambda qb, wb: index_scores(qb, k, wb), T, q, w)[None])


def _infer_dsa_index_paged(op, block):
    h = block.var(op.input("X")[0])
    pt = block.var(op.input("PageTable")[0])
    cache = block.var(op.input("Cache")[0])
    if h.shape is None or pt.shape is None or cache.shape is None:
        raise ShapeInferenceSkip()
    P = pt.shape[-1]
    sc = block.var(op.output("Scores")[0])
    # the decode step: a row a slot; a chunk: its rows, of ONE slot
    lead = (h.shape[0], 1) if op.type == "dsa_index_paged" \
        else tuple(h.shape[:2])
    sc.shape = lead + (P * int(cache.shape[1]) if P > 0 else -1,)
    sc.dtype = "float32"


@register_op("dsa_index_chunk", infer_shape=_infer_dsa_index_paged,
             no_gradient=True, stateful_outputs=("CacheOut",))
def dsa_index_chunk_lower(ctx):
    """The indexer of ONE CHUNK of a prompt.  Cq [1, C, q_lora]; X [1,
    C, d]; Pos [1, C] the rows' positions ``start .. start + C - 1``;
    Mask [1, C] (1 = a real row, real rows first); the weights as
    ``dsa_index``; Cache [num_pages, page_len, D] the persistable
    index-key pool; PageTable [1, P] the slot's row (P a page bucket
    that covers the chunk's last real row).  The real rows' keys are
    written at their positions of the slot's pages, then the chunk's
    query rows score the bucket's ``P * page_len`` rows.  Scores [1, C,
    P * page_len] float32, in the slot's row order (what lies behind a
    query's own row, or behind the chunk's last real row, is whatever
    the pages hold: ``dsa_select`` never looks); zeros where the bucket
    cannot pass ``top_k`` rows.  CacheOut names the pool itself."""
    from paddle_tpu.ops.attention_ops import _paged_cache_update
    c_q, h = ctx.input("Cq")[0], ctx.input("X")[0]
    C = h.shape[0]
    pos, table = ctx.input("Pos").reshape(C), ctx.input("PageTable")
    q, k, w = _projections(ctx, c_q, h, pos)
    cache, = _paged_cache_update(
        (ctx.input("Cache"),), (k[None],), table,
        (pos[0] + C).astype(jnp.int32).reshape(1, 1),
        row_lens=ctx.input("Mask") > 0)
    ctx.set_output("CacheOut", cache)
    T = table.shape[1] * cache.shape[1]
    if T <= int(ctx.attr("top_k")):
        ctx.set_output("Scores", jnp.zeros((1, C, T), jnp.float32))
        return
    rows = cache[table[0]].reshape(T, cache.shape[-1]).astype(k.dtype)
    ctx.set_output("Scores", _query_blocks(
        lambda qb, wb: index_scores(qb, rows, wb), C, q, w)[None])


@register_op("dsa_index_paged", infer_shape=_infer_dsa_index_paged,
             no_gradient=True, stateful_outputs=("CacheOut",))
def dsa_index_paged_lower(ctx):
    """The decode step's indexer.  Cq [S, 1, q_lora]; X [S, 1, d]; Pos [S,
    1]; the weights as ``dsa_index``; Cache [num_pages, page_len, D] the
    persistable index-key pool; PageTable [S, P]; Lens [S, 1] (rows
    through this step's; 0 = free slot: nothing is written).  This
    step's key row is written at ``Lens - 1`` of the slot's pages, then
    the bucket's ``P * page_len`` rows are scored.  Scores [S, 1, P *
    page_len] float32, in the slot's row order (what lies at or past
    ``Lens`` is whatever the pages hold: ``dsa_select`` never looks);
    zeros where the bucket cannot pass ``top_k`` rows.  CacheOut names
    the pool itself."""
    from paddle_tpu.ops.attention_ops import _paged_cache_update
    c_q, h = ctx.input("Cq"), ctx.input("X")
    S = h.shape[0]
    pt, lens = ctx.input("PageTable"), ctx.input("Lens")
    q, k, w = _projections(ctx, c_q[:, 0], h[:, 0],
                           ctx.input("Pos").reshape(S))
    cache, = _paged_cache_update((ctx.input("Cache"),), (k[:, None],), pt,
                                 lens)
    ctx.set_output("CacheOut", cache)
    T = pt.shape[1] * cache.shape[1]
    if T <= int(ctx.attr("top_k")):
        ctx.set_output("Scores", jnp.zeros((S, 1, T), jnp.float32))
        return
    rows = cache[pt].reshape(S, T, cache.shape[-1])
    ctx.set_output("Scores", index_scores(q[:, None], rows, w[:, None]))


def _infer_dsa_select(op, block):
    sc = block.var(op.input("Scores")[0])
    if sc.shape is None:
        raise ShapeInferenceSkip()
    out = block.var(op.output("Select")[0])
    out.shape = tuple(sc.shape)
    out.dtype = "int32" if op.input("Lens") else "int8"


@register_op("dsa_select", infer_shape=_infer_dsa_select, no_gradient=True)
def dsa_select_lower(ctx):
    """Scores [1, T, T] with Mask [1, T] (a whole prompt: row ``t``
    selects among the real rows ``s <= t``; Select int8 [1, T, T]);
    Scores [1, C, T] with Mask [1, C] and Pos [1, C] (ONE CHUNK of a
    prompt over the slot's ``T`` rows: row ``r`` among the real rows
    ``s <= Pos[r]``, which are those before the chunk and the chunk's
    own; Select int8 [1, C, T]); or Scores [S, 1, T] with Lens [S, 1]
    (the decode step: among the rows ``< Lens``; Select int32 [S, 1,
    T]).  attr top_k."""
    scores, k = ctx.input("Scores"), int(ctx.attr("top_k"))
    if ctx.has_input("Lens"):
        T = scores.shape[-1]
        cols = jax.lax.broadcasted_iota(jnp.int32, (1, 1, T), 2)
        valid = cols < ctx.input("Lens")[:, :, None]
        ctx.set_output("Select",
                       select_mask(scores, valid, k).astype(jnp.int32))
    else:
        start = ctx.input("Pos").reshape(-1)[0].astype(jnp.int32) \
            if ctx.has_input("Pos") else 0
        ctx.set_output("Select", causal_select(
            scores[0], ctx.input("Mask")[0], k, start=start)[None])
