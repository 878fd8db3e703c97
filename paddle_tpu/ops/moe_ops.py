"""Expert routing and the routed experts' feed-forward, for a layer that
HOLDS a contiguous share of a model's experts (one chip's part of an
expert-parallel deployment; ``models/hybrid_moe.py``).

``moe_route`` scores every token against ALL the model's experts (the
router is whole on every chip), in float32: sigmoid scores, or a softmax
over all the experts (``scoring``), the ``top_k`` largest of ``score +
bias`` (``bias`` moves the choice only; a router may have none), weights
= the chosen scores, normalised over the chosen and scaled.

``moe_experts`` computes, for experts ``expert_offset ..
expert_offset + held - 1`` (``held`` = the leading axis of its weights),
``sum_i w_i * W2_i . relu(W1_i u)^2`` over the assignments that landed on
them.  Assignments to experts held elsewhere are dropped, not remapped;
no token is dropped for capacity.  Its second output counts what landed:
``[assignments, distinct experts touched, largest load of one expert]``.
``moe_experts_gated`` is the same layer for GATED experts of three
matrices, ``W_d (silu(W_g x) * W_u x)``.

Both are ONE algorithm with the expert's body as its parameter (the up
matrices, the activation that joins their products, the down matrix),
in two forms.  ROUTED, a served program's on a TPU: the assignments that
landed here are sorted by expert and each expert's rows go through its
own matrices and no others (a grouped matrix product,
``jax.experimental.pallas.ops.tpu.megablox.gmm``: a Pallas kernel whose
grid covers the row tiles that hold a group and reads the matrices of
the experts that have a row).  Exact: no capacity, no token dropped; an
expert with no row is not read.  The row bookkeeping around the kernels
(the gather of a trip's rows, the masks, the weights, the sum of its
outputs into their tokens) follows the rows that LANDED on the experts
held, rounded up to one trip (a row tile at decode sizes), not the
``T x k`` the layer sorts: a loop of trips to a traced bound (``trip_rows``;
``gen.moe.row_chunk.<rows>`` says which an executable took,
``gen.moe.rows_landed`` / ``gen.moe.rows_carried`` how full they ran).
DENSE, off the TPU, in a training graph (the routed form's loop to a
traced bound has no reverse mode) and at the row counts where it was
measured faster (``_RELU2_DENSE_ROWS``): one product over all held
experts, every token through every held expert with the combine weight,
zero where the router did not choose it, applied before the down
product, which contracts experts and hidden units together.
``routed=True`` takes the kernel in interpret mode, for the tests.
Which form a lowering took is counted, once per compiled signature:
``gen.moe.routed_lowerings`` / ``gen.moe.dense_lowerings`` (and
``<counter>.<op>``).

The executor's op scope names them ``ptop_moe_route*`` /
``ptop_moe_experts*`` on the device trace.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from paddle_tpu.ops.mla_ops import swiglu
from paddle_tpu.ops.registry import ShapeInferenceSkip, register_op
from paddle_tpu.ops.ssm_ops import relu2


def moe_route(x, w_gate, bias, top_k, scaling=1.0, norm_topk=True,
              scoring="sigmoid"):
    """``x`` [T, d], ``w_gate`` [d, E], ``bias`` [E] or None.  ``scoring``
    ``"sigmoid"``: each expert's score by itself; ``"softmax"``: a
    float32 softmax over all ``E`` experts.  Returns ``idx`` [T, k] int32
    (the ``top_k`` largest of ``score + bias``) and ``weights`` [T, k]
    float32 (the chosen scores, over their sum where ``norm_topk``,
    times ``scaling``)."""
    logits = jnp.matmul(x.astype(jnp.float32), w_gate.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.softmax(logits, axis=-1) if scoring == "softmax" \
        else jax.nn.sigmoid(logits)
    chosen_by = scores if bias is None else scores + bias.astype(jnp.float32)
    _, idx = jax.lax.top_k(chosen_by, int(top_k))
    weights = jnp.take_along_axis(scores, idx, axis=-1)
    if norm_topk:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + 1e-20)
    return idx.astype(jnp.int32), weights * float(scaling)


# Row tile of the grouped product: a row tile holds rows of one or more
# experts and each expert in it reads its matrices whole, so the tile is
# as small as the MXU's 128 rows.  Contraction and output tiles of 1024
# stream an expert's matrix in 2 MB blocks.
_GMM_ROW_TILE = 128
_GMM_TILE = 1024
# Sorted rows a trip takes at most, in a layer that sorts more than two
# such trips' rows (a prompt's chunk): few trips, because every trip's
# edge splits an expert whose matrices are then read twice (50 MB = 0.06
# ms at 4096 x 2048) and every trip pays what the compiler copies in
# front of its kernels: a whole prompt's ~2800 landed rows at top-22
# over 64 of 512 experts are 1.53 ms a layer in 6 trips and 1.70 in 22
# (my chip run, PR 52).  All 16384 sorted rows of a 2048-row prompt at
# once, for the ~512 that landed on 12 of 384 experts, cost 8.1 ms a
# layer where the experts' read takes 1.3 (my chip run, PR 31)
_GMM_CHUNK_ROWS = 512
# ... and no more rows than keep a trip's float32 outputs (rows x width)
# under this: the scatter-add of a trip's rows runs out of fast memory
# while they fit and row by row from HBM once they do not (512 rows of
# 7168: 0.78 ms, 256 rows 0.74, two tiles 0.065 together; 512 rows of
# 6144 0.27, four tiles 0.11; at 4096 a 256-row trip for the ~128 rows
# that land of a 512-row chunk took 0.5 ms off a chunk of eight layers
# and 512 rows 0.10 a layer; at 1024 nothing to speak of; my chip runs,
# PR 52)
_TRIP_BYTES = 4 << 20
# Token rows up to which a trip's weighted outputs are summed into their
# tokens by a one-hot product on the MXU (float32 at 'highest': exact
# but for the order of a token's additions) and not by a scatter-add: a
# decode step's.  9-18 us a layer of the 25-45 a tile-sized trip's
# bookkeeping takes at 16-64 rows; at 256 rows x 2048 assignments it
# saves 8 of 207 and at 1028 rows it costs 520 more (my chip run, PR 52)
_ONEHOT_TOKENS = 128


def _load_stats(load):
    return jnp.stack([jnp.sum(load), jnp.sum(load > 0, dtype=jnp.int32),
                      jnp.max(load)])


def _tile(n, want):
    """The largest multiple of 128 that divides ``n`` and is at most
    ``want`` (``n`` itself where none does)."""
    for t in range(min(want, n) // 128 * 128, 0, -128):
        if n % t == 0:
            return t
    return n


def _count_lowering(form, op, chunk=None):
    """Which form a lowering took, and the rows a trip of a routed one
    takes (fires at trace time, once per compiled signature, as
    ``gen.paged.fallback`` does)."""
    from paddle_tpu.profiler import runtime_metrics
    runtime_metrics.inc(f"gen.moe.{form}_lowerings")
    runtime_metrics.inc(f"gen.moe.{form}_lowerings.{op}")
    if chunk is not None:
        runtime_metrics.inc(f"gen.moe.row_chunk.{chunk}")


def _held(idx, expert_offset, E, live):
    """Each assignment's expert counted from the first one held, and
    whether it landed on one of the ``E`` held by a live row."""
    local = idx.astype(jnp.int32) - int(expert_offset)
    held = (local >= 0) & (local < E)
    if live is not None:
        held = held & live[:, None]
    return local, held


def _dense_experts(x, idx, weights, up, act, down, expert_offset, live):
    """Every row through every held expert; the combine weight, zero
    where the router did not choose, is applied before the down
    product, which contracts experts and hidden units together."""
    E = down.shape[0]
    local, held = _held(idx, expert_offset, E, live)
    here = held[..., None] & (local[..., None] == jnp.arange(E))
    combine = jnp.sum(jnp.where(here, weights[..., None], 0.0), axis=1)
    stats = _load_stats(jnp.sum(here, axis=(0, 1), dtype=jnp.int32))
    h = act(*(jnp.einsum("td,edf->etf", x, w,
                         preferred_element_type=jnp.float32) for w in up))
    h = h * combine.T[:, :, None]
    out = jnp.einsum("etf,efd->td", h.astype(x.dtype), down,
                     preferred_element_type=jnp.float32)
    return out.astype(x.dtype), stats


# behind a ``jit`` so that a model's layers, and its executables of one
# row count, share ONE trace of the core, and an executable lowers it
# once: traced and lowered a layer at a time, thirteen executables of
# five layers added 9 s to a warm server start (my chip run, PR 32)
@functools.partial(jax.jit, static_argnames=(
    "act", "expert_offset", "interpret", "chunk"))
def _routed_experts(x, idx, weights, up, act, down, expert_offset, live,
                    interpret, chunk):
    """The assignments that landed here sorted by expert, each expert's
    rows through its own matrices and no others (``megablox.gmm``): an
    expert with no row is not read.  ``up`` the [E, d, F] matrices whose
    products ``act`` joins into the hidden rows, ``down`` [E, F, d].
    ``chunk``: the sorted rows a trip of the loop takes
    (:func:`trip_rows`); the trips cover the rows that landed, rounded
    up to one of them."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    E, d = down.shape[0], x.shape[1]
    T, k = idx.shape
    local, held = _held(idx, expert_offset, E, live)
    A = T * k
    tm = min(_GMM_ROW_TILE, chunk)
    # assignments sorted by the expert held here; the others last, in
    # no group: the trips take the sorted rows from the front and stop
    # behind the last one that LANDED, so the work follows those and
    # not ``T x k``
    key = jnp.where(held, local, E).reshape(A)
    # ONE sort carries each assignment's token and weight along (a
    # gather by the order, and a scatter for the loads, cost more than
    # the sort at 16384 assignments)
    _, tok, w_sorted = jax.lax.sort(
        (key, jnp.arange(A, dtype=jnp.int32) // k,
         jnp.where(held, weights, 0.0).reshape(A)), num_keys=1)
    sizes = jnp.sum(key[:, None] == jnp.arange(E, dtype=key.dtype),
                    axis=0, dtype=jnp.int32)
    ends = jnp.cumsum(sizes)
    starts, n_held = ends - sizes, ends[-1]
    pad = -(-A // chunk) * chunk - A
    tok, w_sorted = jnp.pad(tok, (0, pad)), jnp.pad(w_sorted, (0, pad))

    def product(lhs, rhs, group_sizes):
        kk, n = rhs.shape[1:]
        return gmm(lhs, rhs, group_sizes,
                   preferred_element_type=jnp.float32,
                   tiling=(tm, _tile(kk, _GMM_TILE), _tile(n, _GMM_TILE)),
                   interpret=interpret)

    def one_chunk(c, out):
        at = c * chunk
        rows_of = jax.lax.dynamic_slice_in_dim(tok, at, chunk)
        w = jax.lax.dynamic_slice_in_dim(w_sorted, at, chunk)
        here = jnp.clip(jnp.minimum(ends, at + chunk)
                        - jnp.maximum(starts, at), 0)
        # a row outside every group is never written by the kernel
        in_group = (jnp.arange(chunk) < n_held - at)[:, None]
        rows = x[rows_of]
        h = jnp.where(in_group, act(*(product(rows, m, here) for m in up)),
                      0.0).astype(x.dtype)
        y = jnp.where(in_group, product(h, down, here), 0.0)
        if T > _ONEHOT_TOKENS:
            return out.at[rows_of].add(y * w[:, None])
        mix = jnp.where(rows_of[:, None] == jnp.arange(T), w[:, None], 0.0)
        return out + jax.lax.dot_general(
            mix, y, (((0,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    # as many trips as hold a row (a branch in a fixed number of trips
    # copied the [T, d] sum every trip: 5.0 ms a layer at 2048 rows).  A
    # loop to a traced bound has no reverse mode: a training graph takes
    # the dense form
    out = jax.lax.fori_loop(0, rows_carried(n_held, chunk) // chunk,
                            one_chunk, jnp.zeros((T, d), jnp.float32))
    return out.astype(x.dtype), _load_stats(sizes)


def trip_rows(assignments, width, chunk_rows=None):
    """The sorted rows a trip of the routed form takes over a layer's
    ``assignments`` (``T x k``) of ``width`` features, a multiple of the
    grouped product's row tile.  ``chunk_rows`` None: ONE row tile, so
    that the trips cover the rows that landed rounded up to a tile, but
    in a layer that sorts more than two ``_GMM_CHUNK_ROWS`` (a prompt's
    chunk; up to that many rows, tiles are few trips even when every
    row lands) as many tiles as ``_TRIP_BYTES`` and ``_GMM_CHUNK_ROWS``
    allow; 0: all the sorted rows in one trip (a layer that holds every
    expert); else that many rows."""
    tm = _GMM_ROW_TILE if assignments >= _GMM_ROW_TILE \
        else -(-assignments // 16) * 16
    whole = -(-assignments // tm) * tm
    if chunk_rows is not None:
        return min(-(-(int(chunk_rows) or whole) // tm) * tm, whole)
    if whole <= 2 * _GMM_CHUNK_ROWS:
        return tm
    return max(tm, min(_GMM_CHUNK_ROWS,
                       _TRIP_BYTES // (4 * width) // tm * tm))


def rows_carried(landed, chunk):
    """Sorted rows the trips of one layer gather, mask and sum when
    ``landed`` assignments (a count, traced or not) landed on the
    experts held: ``landed`` rounded up to a trip (the core's bound, and
    what ``gen.moe.rows_carried`` counts)."""
    return -(-landed // chunk) * chunk


def _experts(op, x, idx, weights, up, act, down, expert_offset, live,
             routed, interpret, dense_rows=(), chunk_rows=None):
    """The one switch between the two forms, read from the operands and
    the platform: ``routed`` None takes the routed form on a TPU, but
    for ``dense_rows`` (the row counts at which the op's dense form was
    measured faster), and the dense one off it (the
    kernel in interpret mode is for the tests); a lowering passes False
    for a training graph."""
    if interpret is None:
        from paddle_tpu.ops.attention_ops import _use_interpret
        interpret = _use_interpret()
    if routed is None:
        routed = not interpret and idx.shape[0] not in dense_rows
    if not routed:
        _count_lowering("dense", op)
        return _dense_experts(x, idx, weights, up, act, down,
                              expert_offset, live)
    chunk = trip_rows(idx.shape[0] * idx.shape[1], x.shape[1], chunk_rows)
    _count_lowering("routed", op, chunk)
    return _routed_experts(x, idx, weights, up, act, down,
                           int(expert_offset), live, bool(interpret), chunk)


# Rows at which the dense ``relu2`` product beats the routed one on a
# TPU, measured at 64 held experts of 1024 x 2688 under a top-22 of 512
# (my chip runs, PR 32; PERF.md section 6): every held expert has a row
# and the dense product is still bound by the weights' read, so there is
# nothing to skip and it wins by 0.01-0.08 ms a layer (and an executable
# without the grouped kernels is 0.35-0.5 s sooner ready).  Under 64 rows a
# third of the experts have no row (routed 0.85 against 1.07 ms); over
# 256 the dense product is bound by its FLOPs, 23 times the routed ones
# (1.35 against 2.06 ms at 512 rows, 1.65 against 3.96 at 1024).  The
# gated op has no such range: its dense form was slower wherever it was
# measured (PR 31)
_RELU2_DENSE_ROWS = range(64, 257)


def moe_experts(u, idx, weights, w1, w2, expert_offset=0, live=None,
                routed=None, interpret=None):
    """``u`` [T, L]; ``idx``/``weights`` [T, k]; ``w1`` [E, L, F]; ``w2``
    [E, F, L]; ``live`` [T] bool (rows that are not live have no
    assignment).  Returns ``out`` [T, L] in ``u``'s type and ``stats``
    [3] int32."""
    return _experts("moe_experts", u, idx, weights, (w1,), relu2, w2,
                    expert_offset, live, routed, interpret,
                    dense_rows=_RELU2_DENSE_ROWS)


def moe_experts_gated(x, idx, weights, wg, wu, wd, expert_offset=0,
                      live=None, routed=None, interpret=None,
                      chunk_rows=None):
    """``x`` [T, d]; ``idx``/``weights`` [T, k]; ``wg``, ``wu`` [E, d, F];
    ``wd`` [E, F, d]; ``live`` [T] bool.  Returns ``out`` [T, d] in
    ``x``'s type (``sum_i w_i W_d^i (silu(W_g^i x) * W_u^i x)`` over the
    assignments to the E experts held) and ``stats`` [3] int32."""
    return _experts("moe_experts_gated", x, idx, weights, (wg, wu),
                    swiglu, wd, expert_offset, live, routed, interpret,
                    chunk_rows=chunk_rows)


def _rows(x):
    return x.reshape((-1, x.shape[-1]))


def _infer_route(op, block):
    x = block.var(op.input("X")[0])
    if x.shape is None:
        raise ShapeInferenceSkip()
    k = int(op.attr("top_k"))
    for slot, dtype in (("TopkIdx", "int32"), ("TopkWeight", "float32")):
        v = block.var(op.output(slot)[0])
        v.shape, v.dtype = tuple(x.shape[:-1]) + (k,), dtype


@register_op("moe_route", infer_shape=_infer_route,
             stop_gradient_outputs=("TopkIdx",))
def moe_route_lower(ctx):
    """X [..., d]; W [d, E]; Bias [E], optional.  attrs top_k, scaling,
    norm_topk, scoring ("sigmoid" | "softmax" over all E).  TopkIdx
    [..., k] int32; TopkWeight [..., k] float32."""
    x = ctx.input("X")
    idx, w = moe_route(_rows(x), ctx.input("W"), ctx.input("Bias"),
                       int(ctx.attr("top_k")),
                       float(ctx.attr("scaling", 1.0)),
                       bool(ctx.attr("norm_topk", True)),
                       str(ctx.attr("scoring", "sigmoid")))
    lead = x.shape[:-1]
    ctx.set_output("TopkIdx", idx.reshape(lead + idx.shape[-1:]))
    ctx.set_output("TopkWeight", w.reshape(lead + w.shape[-1:]))


def _infer_experts(op, block):
    x = block.var(op.input("X")[0])
    if x.shape is None:
        raise ShapeInferenceSkip()
    out = block.var(op.output("Out")[0])
    out.shape, out.dtype = tuple(x.shape), x.dtype
    stats = block.var(op.output("Stats")[0])
    stats.shape, stats.dtype = (1, 3), "int32"


@register_op("moe_experts", infer_shape=_infer_experts,
             no_grad_inputs=("TopkIdx", "Lens"),
             stop_gradient_outputs=("Stats",))
def moe_experts_lower(ctx):
    """X [..., L]; TopkIdx, TopkWeight [..., k]; W1 [E, L, F]; W2
    [E, F, L]; Lens [rows, 1] int32, optional (a row with 0 has no
    assignment).  attr expert_offset.  Out [..., L]; Stats [1, 3] int32
    (assignments landed, held experts touched, largest load)."""
    x = ctx.input("X")
    idx, w = ctx.input("TopkIdx"), ctx.input("TopkWeight")
    lens = ctx.input("Lens")
    out, stats = moe_experts(
        _rows(x), _rows(idx), _rows(w), ctx.input("W1"), ctx.input("W2"),
        int(ctx.attr("expert_offset", 0)),
        None if lens is None else lens.reshape(-1) > 0,
        routed=False if ctx.training else None)
    ctx.set_output("Out", out.reshape(x.shape))
    ctx.set_output("Stats", stats[None])


@register_op("moe_experts_gated", infer_shape=_infer_experts,
             no_grad_inputs=("TopkIdx", "Lens"),
             stop_gradient_outputs=("Stats",))
def moe_experts_gated_lower(ctx):
    """X [..., d]; TopkIdx, TopkWeight [..., k]; Wg, Wu [E, d, F]; Wd
    [E, F, d]; Lens [rows, 1] int32, optional.  attrs expert_offset,
    chunk_rows (sorted rows a trip of the routed form takes; absent:
    ``_GMM_CHUNK_ROWS``, 0: all in one, for a layer that holds every
    expert).  Out [..., d]; Stats [1, 3] int32, as ``moe_experts``."""
    x = ctx.input("X")
    lens = ctx.input("Lens")
    out, stats = moe_experts_gated(
        _rows(x), _rows(ctx.input("TopkIdx")), _rows(ctx.input("TopkWeight")),
        ctx.input("Wg"), ctx.input("Wu"), ctx.input("Wd"),
        int(ctx.attr("expert_offset", 0)),
        None if lens is None else lens.reshape(-1) > 0,
        routed=False if ctx.training else None,
        chunk_rows=ctx.attr("chunk_rows", None))
    ctx.set_output("Out", out.reshape(x.shape))
    ctx.set_output("Stats", stats[None])
