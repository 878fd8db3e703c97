"""Linear-algebra / elementwise / reduction ops.

Reference op set: ``paddle/fluid/operators/{mul,matmul,elementwise_*,scale,
sum,mean,reduce_op,cumsum,...}``.  Each lowering is a pure jax.numpy
function; XLA maps matmuls onto the MXU and fuses the elementwise ops.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ops.registry import (
    register_op, register_grad_lower, infer_shape_unary, ShapeInferenceSkip)


# ---------------------------------------------------------------------------
# mul / matmul  (reference: mul_op.cc, matmul_op.cc, math/matmul.h)
# ---------------------------------------------------------------------------

def _flatten_to_2d(x, num_col_dims):
    lead = int(np.prod(x.shape[:num_col_dims])) if num_col_dims > 0 else 1
    return x.reshape(lead, -1)


def _infer_mul(op, block):
    x = block.var(op.input("X")[0])
    y = block.var(op.input("Y")[0])
    if x.shape is None or y.shape is None:
        raise ShapeInferenceSkip()
    xn = op.attr("x_num_col_dims", 1)
    yn = op.attr("y_num_col_dims", 1)
    out = block.var(op.output("Out")[0])
    out.shape = tuple(x.shape[:xn]) + tuple(y.shape[yn:])
    out.dtype = x.dtype
    out.lod_level = x.lod_level


@register_op("mul", infer_shape=_infer_mul, amp_cast=("X", "Y"))
def mul_lower(ctx):
    x, y = ctx.input("X"), ctx.input("Y")
    xn = ctx.attr("x_num_col_dims", 1)
    yn = ctx.attr("y_num_col_dims", 1)
    x2 = _flatten_to_2d(x, xn)
    y2 = y.reshape(int(np.prod(y.shape[:yn])), -1)
    out = jnp.matmul(x2, y2)
    out = out.reshape(tuple(x.shape[:xn]) + tuple(y.shape[yn:]))
    ctx.set_output("Out", out)


def _infer_matmul(op, block):
    x = block.var(op.input("X")[0])
    y = block.var(op.input("Y")[0])
    if x.shape is None or y.shape is None:
        raise ShapeInferenceSkip()
    tx, ty = op.attr("transpose_X", False), op.attr("transpose_Y", False)
    xs = list(x.shape)
    ys = list(y.shape)
    if len(xs) >= 2 and tx:
        xs[-1], xs[-2] = xs[-2], xs[-1]
    if len(ys) >= 2 and ty:
        ys[-1], ys[-2] = ys[-2], ys[-1]
    if len(xs) == 1 and len(ys) == 1:
        shape = (1,)
    elif len(xs) == 1:
        shape = tuple(ys[:-2]) + (ys[-1],)
    elif len(ys) == 1:
        shape = tuple(xs[:-1])
    else:
        batch = xs[:-2] if len(xs) > len(ys) else ys[:-2]
        shape = tuple(batch) + (xs[-2], ys[-1])
    out = block.var(op.output("Out")[0])
    out.shape = shape
    out.dtype = op.attr("out_dtype", None) or x.dtype


@register_op("matmul", infer_shape=_infer_matmul, amp_cast=("X", "Y"))
def matmul_lower(ctx):
    """attr ``out_dtype`` (optional): the type the products accumulate
    into and the result keeps, e.g. float32 logits from bfloat16
    operands; absent, the operands' own."""
    x, y = ctx.input("X"), ctx.input("Y")
    if ctx.attr("transpose_X", False):
        x = jnp.swapaxes(x, -1, -2) if x.ndim >= 2 else x
    if ctx.attr("transpose_Y", False):
        y = jnp.swapaxes(y, -1, -2) if y.ndim >= 2 else y
    out = jnp.matmul(x, y,
                     preferred_element_type=ctx.attr("out_dtype", None))
    alpha = ctx.attr("alpha", 1.0)
    if alpha != 1.0:
        out = out * alpha
    if out.ndim == 0:
        out = out.reshape(1)
    ctx.set_output("Out", out)


# ---------------------------------------------------------------------------
# elementwise family  (reference: elementwise_op_function.h broadcast engine)
# ---------------------------------------------------------------------------

def _elementwise_broadcast(x, y, axis):
    """Paddle broadcast: Y's shape aligns to X starting at ``axis``."""
    if y.ndim == x.ndim:
        return y
    if axis == -1:
        axis = x.ndim - y.ndim
    shape = [1] * x.ndim
    for i, d in enumerate(y.shape):
        shape[axis + i] = d
    return y.reshape(shape)


def _infer_ew(op, block):
    x = block.var(op.input("X")[0])
    out = block.var(op.output("Out")[0])
    out.shape = x.shape
    out.dtype = x.dtype
    out.lod_level = x.lod_level


def _make_elementwise(name, fn):
    @register_op("elementwise_" + name, infer_shape=_infer_ew)
    def lower(ctx):
        x, y = ctx.input("X"), ctx.input("Y")
        yb = _elementwise_broadcast(x, y, ctx.attr("axis", -1))
        ctx.set_output("Out", fn(x, yb))
    lower.__name__ = f"elementwise_{name}_lower"
    return lower


_make_elementwise("add", jnp.add)
_make_elementwise("sub", jnp.subtract)
_make_elementwise("mul", jnp.multiply)
_make_elementwise("div", jnp.divide)
_make_elementwise("max", jnp.maximum)
_make_elementwise("min", jnp.minimum)
_make_elementwise("pow", jnp.power)
_make_elementwise("mod", jnp.mod)
_make_elementwise("floordiv", jnp.floor_divide)


# ---------------------------------------------------------------------------
# scale / sum / mean / minus / sign / clip
# ---------------------------------------------------------------------------

@register_op("scale", infer_shape=infer_shape_unary())
def scale_lower(ctx):
    x = ctx.input("X")
    scale = ctx.attr("scale", 1.0)
    bias = ctx.attr("bias", 0.0)
    bias_after = ctx.attr("bias_after_scale", True)
    if bias_after:
        ctx.set_output("Out", x * scale + bias)
    else:
        ctx.set_output("Out", (x + bias) * scale)


@register_op("sum", infer_shape=infer_shape_unary(),
             selected_rows_inputs=("X",))
def sum_lower(ctx):
    """Reference sum_op.cc: sums LoDTensors and/or SelectedRows.  All-sparse
    inputs concatenate into one SelectedRows (duplicate rows are fine —
    consumers scatter-add or merge); mixed inputs densify."""
    from paddle_tpu.selected_rows import SelectedRows, is_selected_rows
    xs = ctx.inputs("X")
    if any(is_selected_rows(x) for x in xs):
        if all(is_selected_rows(x) for x in xs):
            rows = jnp.concatenate([x.rows for x in xs])
            vals = jnp.concatenate([x.value for x in xs])
            ctx.set_output("Out", SelectedRows(rows, vals, xs[0].height))
            return
        xs = [x.to_dense() if is_selected_rows(x) else x for x in xs]
    out = xs[0]
    for x in xs[1:]:
        out = out + x
    ctx.set_output("Out", out)


def _infer_mean(op, block):
    out = block.var(op.output("Out")[0])
    out.shape = (1,)
    out.dtype = block.var(op.input("X")[0]).dtype


@register_op("mean", infer_shape=_infer_mean)
def mean_lower(ctx):
    x = ctx.input("X")
    lod = ctx.input_lod("X")
    from paddle_tpu.lod import DynLoD
    if isinstance(lod, DynLoD):
        # bucketed dynamic-LoD rows: average over the REAL rows only —
        # rows past splits[-1] are zero padding (their values, e.g. the
        # clamped cross-entropy of an all-zero softmax row, are noise)
        splits = lod.splits(ctx.env)
        n_real = splits[-1]
        r = jnp.arange(x.shape[0])
        mask = (r < n_real).astype(x.dtype).reshape(
            (-1,) + (1,) * (x.ndim - 1))
        per_row = int(np.prod(x.shape[1:])) if x.ndim > 1 else 1
        total = jnp.sum(x * mask)
        count = jnp.maximum(n_real.astype(x.dtype) * per_row, 1)
        ctx.set_output("Out", (total / count).reshape(1))
        return
    ctx.set_output("Out", jnp.mean(x).reshape(1))


@register_op("minus", infer_shape=infer_shape_unary())
def minus_lower(ctx):
    ctx.set_output("Out", ctx.input("X") - ctx.input("Y"))


@register_op("sign", infer_shape=infer_shape_unary())
def sign_lower(ctx):
    ctx.set_output("Out", jnp.sign(ctx.input("X")))


@register_op("clip", infer_shape=infer_shape_unary())
def clip_lower(ctx):
    ctx.set_output("Out", jnp.clip(ctx.input("X"), ctx.attr("min"),
                                   ctx.attr("max")))


@register_op("clip_by_norm", infer_shape=infer_shape_unary())
def clip_by_norm_lower(ctx):
    x = ctx.input("X")
    max_norm = ctx.attr("max_norm")
    norm = jnp.sqrt(jnp.sum(x * x))
    scale = jnp.where(norm > max_norm, max_norm / jnp.maximum(norm, 1e-12),
                      1.0).astype(x.dtype)
    ctx.set_output("Out", x * scale)


# ---------------------------------------------------------------------------
# reductions  (reference: reduce_op.cc functor family, cum_op.h)
# ---------------------------------------------------------------------------

def _infer_reduce(op, block):
    x = block.var(op.input("X")[0])
    if x.shape is None:
        raise ShapeInferenceSkip()
    dim = op.attr("dim", [0])
    if isinstance(dim, int):
        dim = [dim]
    keep = op.attr("keep_dim", False)
    reduce_all = op.attr("reduce_all", False)
    out = block.var(op.output("Out")[0])
    if reduce_all:
        out.shape = tuple([1] * len(x.shape)) if keep else (1,)
    else:
        dims = [d % len(x.shape) for d in dim]
        if keep:
            out.shape = tuple(1 if i in dims else d
                              for i, d in enumerate(x.shape))
        else:
            shape = tuple(d for i, d in enumerate(x.shape) if i not in dims)
            out.shape = shape if shape else (1,)
    out.dtype = x.dtype


def _make_reduce(name, fn):
    @register_op("reduce_" + name, infer_shape=_infer_reduce)
    def lower(ctx):
        x = ctx.input("X")
        dim = ctx.attr("dim", [0])
        if isinstance(dim, int):
            dim = [dim]
        keep = ctx.attr("keep_dim", False)
        if ctx.attr("reduce_all", False):
            out = fn(x, axis=None, keepdims=keep)
            if not keep:
                out = out.reshape(1)
        else:
            axes = tuple(d % x.ndim for d in dim)
            out = fn(x, axis=axes, keepdims=keep)
            if out.ndim == 0:
                out = out.reshape(1)
        ctx.set_output("Out", out)
    lower.__name__ = f"reduce_{name}_lower"
    return lower


_make_reduce("sum", jnp.sum)
_make_reduce("mean", jnp.mean)
_make_reduce("max", jnp.max)
_make_reduce("min", jnp.min)
_make_reduce("prod", jnp.prod)


@register_op("cumsum", infer_shape=infer_shape_unary())
def cumsum_lower(ctx):
    x = ctx.input("X")
    axis = ctx.attr("axis", -1)
    exclusive = ctx.attr("exclusive", False)
    reverse = ctx.attr("reverse", False)
    if reverse:
        x = jnp.flip(x, axis)
    out = jnp.cumsum(x, axis=axis)
    if exclusive:
        out = out - (jnp.flip(ctx.input("X"), axis) if reverse
                     else ctx.input("X"))
    if reverse:
        out = jnp.flip(out, axis)
    ctx.set_output("Out", out)


# ---------------------------------------------------------------------------
# norms / similarity
# ---------------------------------------------------------------------------

def _infer_scalar_out(op, block):
    out = block.var(op.output("Out")[0])
    out.shape = (1,)
    out.dtype = block.var(op.input("X")[0]).dtype


@register_op("squared_l2_norm", infer_shape=_infer_scalar_out)
def squared_l2_norm_lower(ctx):
    x = ctx.input("X")
    ctx.set_output("Out", jnp.sum(x * x).reshape(1))


@register_op("l1_norm", infer_shape=_infer_scalar_out)
def l1_norm_lower(ctx):
    ctx.set_output("Out", jnp.sum(jnp.abs(ctx.input("X"))).reshape(1))


def _infer_norm(op, block):
    x = block.var(op.input("X")[0])
    out = block.var(op.output("Out")[0])
    out.shape = x.shape
    out.dtype = x.dtype


@register_op("norm", infer_shape=_infer_norm)
def norm_lower(ctx):
    x = ctx.input("X")
    axis = ctx.attr("axis", 1)
    eps = ctx.attr("epsilon", 1e-10)
    norm = jnp.sqrt(jnp.sum(x * x, axis=axis, keepdims=True) + eps)
    ctx.set_output("Out", x / norm)
    ctx.set_output("Norm", norm)


def _infer_cos_sim(op, block):
    x = block.var(op.input("X")[0])
    if x.shape is None:
        raise ShapeInferenceSkip()
    out = block.var(op.output("Out")[0])
    out.shape = (x.shape[0], 1)
    out.dtype = x.dtype


@register_op("cos_sim", infer_shape=_infer_cos_sim)
def cos_sim_lower(ctx):
    x, y = ctx.input("X"), ctx.input("Y")
    xn = jnp.sqrt(jnp.sum(x * x, axis=1, keepdims=True))
    yn = jnp.sqrt(jnp.sum(y * y, axis=1, keepdims=True))
    dot = jnp.sum(x * y, axis=1, keepdims=True)
    ctx.set_output("Out", dot / (xn * yn))
    ctx.set_output("XNorm", xn)
    ctx.set_output("YNorm", yn)


# ---------------------------------------------------------------------------
# dot / outer helpers used by layers
# ---------------------------------------------------------------------------

def _infer_bilinear(op, block):
    x = block.var(op.input("X")[0])
    w = block.var(op.input("Weight")[0])
    if x.shape is None or w.shape is None:
        raise ShapeInferenceSkip()
    out = block.var(op.output("Out")[0])
    out.shape = (x.shape[0], w.shape[0])
    out.dtype = x.dtype


@register_op("bilinear_tensor_product", infer_shape=_infer_bilinear)
def bilinear_tensor_product_lower(ctx):
    x, y, w = ctx.input("X"), ctx.input("Y"), ctx.input("Weight")
    # x: (B, M), y: (B, N), w: (S, M, N) -> out (B, S)
    out = jnp.einsum("bm,smn,bn->bs", x, w, y)
    b = ctx.input("Bias")
    if b is not None:
        out = out + b
    ctx.set_output("Out", out)
