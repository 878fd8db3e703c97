"""Shape & dtype inference pass: dataflow over the Program IR.

Unlike the build-time ``registry.infer_shape`` hooks (best-effort hints
that mutate the Variables as layers are appended), this pass trusts
NOTHING it cannot prove.  It seeds a shadow environment from the
program's declared roots — ``is_data`` feeds, Parameters and other
persistables (whose shapes/dtypes the user or the initializer pinned) —
and propagates shapes/dtypes forward through per-op-type **rules**
registered with :func:`rule`.  An op type without a rule propagates
*unknown* for its outputs and lands on the warn-list
(``TypeEnv.uncovered``) instead of guessing; a rule only reports a
mismatch (PTA005/PTA006) when every participating dim/dtype is
statically known.  That is the zero-false-positive contract: silence is
allowed, wrong noise is not.

Registering a rule for a new op::

    from paddle_tpu.analysis import typecheck

    @typecheck.rule("my_op")
    def _my_op(op, tc):
        x = tc.info(op.input("X")[0])
        if x.dtype is not None and x.dtype not in ("float32", "bfloat16"):
            tc.report("PTA005", f"my_op needs a float X, got {x.dtype}",
                      op=op, var=op.input("X")[0])
        tc.set_output(op, "Out", shape=x.shape, dtype=x.dtype)

``-1``/``None`` dims mean *unknown* and match anything; ``dtype=None``
likewise.  PTA010 (int64 → i32 lane truncation) also lives here: the
``fill_constant``/``fill`` rules prove from the literal attr value that
a device-side int64 constant exceeds int32 range — under JAX's default
x64-off mode (and on the pipeline transpiler's typed i32 carrier lane)
such a value silently wraps.
"""

from __future__ import annotations

import logging

import numpy as np

from paddle_tpu import framework
from paddle_tpu.analysis.diagnostics import Diagnostic

logger = logging.getLogger(__name__)

__all__ = ["rule", "check_types", "TypeEnv", "VarInfo", "covered_op_types",
           "INT32_MAX", "INT32_MIN", "int64_fits_i32_lane"]

INT32_MAX = np.iinfo(np.int32).max
INT32_MIN = np.iinfo(np.int32).min

_RULES = {}

_INT_DTYPES = ("int8", "uint8", "int16", "int32", "int64", "bool")


def rule(*op_types):
    """Decorator registering ``fn(op, tc)`` as the inference rule for
    one or more op types (the analysis-side analog of
    ``registry.register_op``'s ``infer_shape``)."""

    def deco(fn):
        for t in op_types:
            _RULES[t] = fn
        return fn

    return deco


def covered_op_types():
    return set(_RULES)


def int64_fits_i32_lane(values):
    """True when every value is exactly representable in int32 — the
    contract of the pipeline transpiler's i32 carrier lane and of JAX's
    x64-off int handling."""
    a = np.asarray(values)
    if a.size == 0:
        return True
    return bool(a.max() <= INT32_MAX and a.min() >= INT32_MIN)


class VarInfo:
    __slots__ = ("shape", "dtype")

    def __init__(self, shape=None, dtype=None):
        # normalize: unknown dims -> -1; unknown shape -> None
        self.shape = None if shape is None else tuple(
            -1 if d is None or int(d) < 0 else int(d) for d in shape)
        self.dtype = dtype

    def __repr__(self):
        return f"VarInfo(shape={self.shape}, dtype={self.dtype})"


_UNKNOWN = VarInfo()


class TypeEnv:
    """Shadow (shape, dtype) environment threaded through one block."""

    def __init__(self, block, diags, uncovered, op_index=None):
        self.block = block
        self.diags = diags
        self.uncovered = uncovered
        self.op_index = op_index
        self._env = {}

    # -- reads -------------------------------------------------------------
    def info(self, name):
        if not name:
            return _UNKNOWN
        if name in self._env:
            return self._env[name]
        # trusted roots: declared feeds and persistable state carry
        # user/initializer-pinned metadata; scratch vars do not (their
        # declared dtype is just the auto-declare default)
        try:
            v = self.block.var(name)
        except KeyError:
            return _UNKNOWN
        if getattr(v, "is_data", False) or getattr(v, "persistable", False):
            return VarInfo(v.shape, v.dtype)
        return _UNKNOWN

    def input_info(self, op, slot):
        names = op.input(slot)
        return self.info(names[0]) if names else _UNKNOWN

    # -- writes ------------------------------------------------------------
    def set(self, name, shape=None, dtype=None):
        if name:
            self._env[name] = VarInfo(shape, dtype)

    def set_output(self, op, slot, shape=None, dtype=None):
        for n in op.output(slot):
            self.set(n, shape=shape, dtype=dtype)

    def copy_unary(self, op, in_slot="X", out_slot="Out"):
        x = self.input_info(op, in_slot)
        self.set_output(op, out_slot, shape=x.shape, dtype=x.dtype)

    # -- reporting ---------------------------------------------------------
    def report(self, code, message, op=None, var=None):
        self.diags.append(Diagnostic(
            code, message, block_idx=self.block.idx,
            op_index=self.op_index,
            op_type=op.type if op is not None else None, var=var,
            site=getattr(op, "creation_site", None)))


def _dims_conflict(a, b):
    """Both known and different (the provable-mismatch predicate)."""
    return a != -1 and b != -1 and a != b


def check_types(program):
    """Run the inference pass over every block reachable from block 0.

    Returns ``(diagnostics, uncovered_op_types)`` where the second item
    is the warn-list: op types seen in the program that have no
    registered inference rule (their outputs propagated as unknown)."""
    diags = []
    uncovered = set()
    _check_block(program.global_block(), diags, uncovered, parent_env=None)
    return diags, uncovered


def _check_block(block, diags, uncovered, parent_env):
    tc = TypeEnv(block, diags, uncovered)
    if parent_env is not None:
        tc._env.update(parent_env)
    for i, op in enumerate(block.ops):
        if op.type in ("feed", "fetch"):
            continue
        tc.op_index = i
        fn = _RULES.get(op.type)
        if fn is None:
            uncovered.add(op.type)
            for n in op.output_arg_names:
                tc.set(n)  # unknown stops propagation, never misreports
        else:
            try:
                fn(op, tc)
            except Exception:  # lint must never crash on the malformed
                # programs it exists to diagnose (e.g. an op that lost a
                # required input slot): degrade this op to no-rule
                # behavior — outputs unknown, op on the warn-list — and
                # let the structural pass name the actual defect
                logger.warning(
                    "analysis rule for op %r failed; treating the op as "
                    "uncovered", op.type, exc_info=True)
                uncovered.add(op.type)
                for n in op.output_arg_names:
                    tc.set(n)
        for a in op.attrs.values():
            if isinstance(a, framework.Block):
                _check_block(a, diags, uncovered, parent_env=tc._env)
    return tc


# ---------------------------------------------------------------------------
# core rules
# ---------------------------------------------------------------------------

_UNARY_OPS = (
    "relu", "sigmoid", "tanh", "exp", "log", "sqrt", "abs", "square",
    "softmax", "softsign", "softplus", "relu6", "leaky_relu", "elu",
    "gelu", "hard_sigmoid", "swish", "brelu", "pow", "reciprocal",
    "floor", "ceil", "round", "sin", "cos", "clip", "scale", "assign",
    "dropout", "label_smooth", "sequence_softmax", "fill_zeros_like",
)


@rule(*_UNARY_OPS)
def _r_unary(op, tc):
    tc.copy_unary(op)


@rule("mul")
def _r_mul(op, tc):
    x = tc.input_info(op, "X")
    y = tc.input_info(op, "Y")
    xn = op.attr("x_num_col_dims", 1)
    yn = op.attr("y_num_col_dims", 1)
    out_shape = None
    if x.dtype is not None and y.dtype is not None and x.dtype != y.dtype:
        tc.report("PTA005",
                  f"mul operands disagree on dtype: X `{op.input('X')[0]}` "
                  f"is {x.dtype}, Y `{op.input('Y')[0]}` is {y.dtype}",
                  op=op, var=op.input("X")[0])
    if x.shape is not None and y.shape is not None and \
            len(x.shape) >= xn and len(y.shape) >= yn:
        k_x = _prod(x.shape[xn:])
        k_y = _prod(y.shape[:yn])
        if k_x is not None and k_y is not None and k_x != k_y:
            tc.report("PTA006",
                      f"mul inner dimensions differ: X "
                      f"`{op.input('X')[0]}` {x.shape} flattens to "
                      f"[*, {k_x}] but Y `{op.input('Y')[0]}` {y.shape} "
                      f"flattens to [{k_y}, *]",
                      op=op, var=op.input("X")[0])
        out_shape = tuple(x.shape[:xn]) + tuple(y.shape[yn:])
    tc.set_output(op, "Out", shape=out_shape, dtype=x.dtype)


def _prod(dims):
    n = 1
    for d in dims:
        if d == -1:
            return None
        n *= d
    return n


@rule("matmul")
def _r_matmul(op, tc):
    x = tc.input_info(op, "X")
    y = tc.input_info(op, "Y")
    if x.dtype is not None and y.dtype is not None and x.dtype != y.dtype:
        tc.report("PTA005",
                  f"matmul operands disagree on dtype: {x.dtype} vs "
                  f"{y.dtype}", op=op, var=op.input("X")[0])
    out_shape = None
    if x.shape is not None and y.shape is not None and \
            len(x.shape) >= 2 and len(y.shape) >= 2:
        xs = list(x.shape)
        ys = list(y.shape)
        if op.attr("transpose_X", False):
            xs[-1], xs[-2] = xs[-2], xs[-1]
        if op.attr("transpose_Y", False):
            ys[-1], ys[-2] = ys[-2], ys[-1]
        if _dims_conflict(xs[-1], ys[-2]):
            tc.report("PTA006",
                      f"matmul contraction dims differ: X "
                      f"`{op.input('X')[0]}` {x.shape} contracts "
                      f"{xs[-1]} against Y `{op.input('Y')[0]}` "
                      f"{y.shape}'s {ys[-2]}",
                      op=op, var=op.input("X")[0])
        batch = xs[:-2] if len(xs) >= len(ys) else ys[:-2]
        out_shape = tuple(batch) + (xs[-2], ys[-1])
    tc.set_output(op, "Out", shape=out_shape,
                  dtype=op.attr("out_dtype", None) or x.dtype)


@rule("elementwise_add", "elementwise_sub", "elementwise_mul",
      "elementwise_div", "elementwise_max", "elementwise_min",
      "elementwise_pow")
def _r_elementwise(op, tc):
    x = tc.input_info(op, "X")
    y = tc.input_info(op, "Y")
    if x.dtype is not None and y.dtype is not None and x.dtype != y.dtype:
        tc.report("PTA005",
                  f"{op.type} operands disagree on dtype: X "
                  f"`{op.input('X')[0]}` is {x.dtype}, Y "
                  f"`{op.input('Y')[0]}` is {y.dtype} (insert a cast)",
                  op=op, var=op.input("Y")[0])
    if x.shape is not None and y.shape is not None:
        axis = op.attr("axis", -1)
        if axis == -1:
            axis = len(x.shape) - len(y.shape)
        ok = 0 <= axis and axis + len(y.shape) <= len(x.shape)
        if ok:
            for i, dy in enumerate(y.shape):
                dx = x.shape[axis + i]
                if dy != 1 and _dims_conflict(dx, dy):
                    ok = False
                    break
        if not ok:
            tc.report("PTA006",
                      f"{op.type}: Y `{op.input('Y')[0]}` {y.shape} does "
                      f"not broadcast into X `{op.input('X')[0]}` "
                      f"{x.shape} at axis {op.attr('axis', -1)}",
                      op=op, var=op.input("Y")[0])
    tc.set_output(op, "Out", shape=x.shape, dtype=x.dtype)


@rule("sum")
def _r_sum(op, tc):
    infos = [tc.info(n) for n in op.input("X")]
    shape = None
    dtype = None
    for n, inf in zip(op.input("X"), infos):
        if inf.dtype is not None:
            if dtype is not None and inf.dtype != dtype:
                tc.report("PTA005",
                          f"sum inputs disagree on dtype: `{n}` is "
                          f"{inf.dtype}, earlier inputs are {dtype}",
                          op=op, var=n)
            dtype = dtype or inf.dtype
        if inf.shape is not None:
            if shape is not None and len(shape) == len(inf.shape) and \
                    any(_dims_conflict(a, b)
                        for a, b in zip(shape, inf.shape)):
                tc.report("PTA006",
                          f"sum inputs disagree on shape: `{n}` is "
                          f"{inf.shape}, earlier inputs are {shape}",
                          op=op, var=n)
            shape = shape or inf.shape
    tc.set_output(op, "Out", shape=shape, dtype=dtype)


@rule("cast")
def _r_cast(op, tc):
    x = tc.input_info(op, "X")
    tc.set_output(op, "Out", shape=x.shape,
                  dtype=op.attr("out_dtype", op.attr("dtype")))


@rule("mean")
def _r_mean(op, tc):
    x = tc.input_info(op, "X")
    tc.set_output(op, "Out", shape=(1,), dtype=x.dtype)


@rule("reduce_sum", "reduce_mean", "reduce_max", "reduce_min",
      "reduce_prod")
def _r_reduce(op, tc):
    x = tc.input_info(op, "X")
    shape = None
    if x.shape is not None:
        dims = op.attr("dim")
        keep = op.attr("keep_dim", False)
        if op.attr("reduce_all", False) or dims is None:
            shape = (1,) * len(x.shape) if keep else (1,)
        else:
            dims = [d % len(x.shape) for d in
                    (dims if isinstance(dims, (list, tuple)) else [dims])]
            shape = tuple(1 if i in dims else d
                          for i, d in enumerate(x.shape)) if keep else \
                tuple(d for i, d in enumerate(x.shape) if i not in dims) \
                or (1,)
    tc.set_output(op, "Out", shape=shape, dtype=x.dtype)


@rule("cross_entropy")
def _r_cross_entropy(op, tc):
    x = tc.input_info(op, "X")
    label = tc.input_info(op, "Label")
    if not op.attr("soft_label", False) and label.dtype is not None and \
            label.dtype not in ("int32", "int64"):
        tc.report("PTA005",
                  f"cross_entropy with hard labels needs an integer "
                  f"Label, got {label.dtype} for "
                  f"`{op.input('Label')[0]}`",
                  op=op, var=op.input("Label")[0])
    if x.shape is not None and label.shape is not None and \
            len(x.shape) == len(label.shape) and \
            _dims_conflict(x.shape[0], label.shape[0]):
        tc.report("PTA006",
                  f"cross_entropy batch dims differ: X {x.shape} vs "
                  f"Label {label.shape}", op=op, var=op.input("X")[0])
    shape = None
    if x.shape is not None:
        shape = tuple(x.shape[:-1]) + (1,)
    tc.set_output(op, "Out", shape=shape, dtype=x.dtype)


@rule("softmax_with_cross_entropy")
def _r_softmax_xent(op, tc):
    x = tc.input_info(op, "Logits")
    tc.set_output(op, "Softmax", shape=x.shape, dtype=x.dtype)
    shape = tuple(x.shape[:-1]) + (1,) if x.shape is not None else None
    tc.set_output(op, "Loss", shape=shape, dtype=x.dtype)


@rule("accuracy")
def _r_accuracy(op, tc):
    out = tc.input_info(op, "Out")
    label = tc.input_info(op, "Label")
    if label.dtype is not None and label.dtype not in ("int32", "int64"):
        tc.report("PTA005",
                  f"accuracy needs an integer Label, got {label.dtype}",
                  op=op, var=op.input("Label")[0])
    if out.shape is not None and label.shape is not None and \
            _dims_conflict(out.shape[0], label.shape[0]):
        tc.report("PTA006",
                  f"accuracy batch dims differ: Out {out.shape} vs "
                  f"Label {label.shape}", op=op, var=op.input("Out")[0])
    tc.set_output(op, "Accuracy", shape=(1,), dtype="float32")
    tc.set_output(op, "Correct", shape=(1,), dtype="int64")
    tc.set_output(op, "Total", shape=(1,), dtype="int64")


@rule("top_k")
def _r_top_k(op, tc):
    x = tc.input_info(op, "X")
    k = op.attr("k", 1)
    shape = tuple(x.shape[:-1]) + (k,) if x.shape is not None else None
    tc.set_output(op, "Out", shape=shape, dtype=x.dtype)
    tc.set_output(op, "Indices", shape=shape, dtype="int64")


@rule("lookup_table")
def _r_lookup_table(op, tc):
    ids = tc.input_info(op, "Ids")
    w = tc.input_info(op, "W")
    if ids.dtype is not None and ids.dtype not in ("int32", "int64"):
        tc.report("PTA005",
                  f"lookup_table Ids `{op.input('Ids')[0]}` must be "
                  f"integer, got {ids.dtype}",
                  op=op, var=op.input("Ids")[0])
    shape = None
    if ids.shape is not None and w.shape is not None and \
            len(w.shape) == 2:
        lead = ids.shape[:-1] if ids.shape and ids.shape[-1] == 1 \
            else ids.shape
        shape = tuple(lead) + (w.shape[1],)
    tc.set_output(op, "Out", shape=shape, dtype=w.dtype)


# -- sparse / CTR family (ops/sparse_ops.py) --------------------------------
#
# SelectedRows values flow through ordinary variables; their static
# type is the LOGICAL dense shape ([height, dim]) — the same convention
# ``lookup_table_grad``'s mirror rule applies to its SelectedRows
# cotangent (W@GRAD gets W's [vocab, dim] shape regardless of how many
# rows the batch touched), so the optimizer Param/Grad agreement check
# sees through the sparse path unchanged.

@rule("merge_selected_rows", "get_tensor_from_selected_rows")
def _r_selected_rows_unary(op, tc):
    x = tc.input_info(op, "X")
    tc.set_output(op, "Out", shape=x.shape, dtype=x.dtype)


@rule("split_ids")
def _r_split_ids(op, tc):
    ids = tc.input_info(op, "Ids")
    if ids.dtype is not None and ids.dtype not in ("int32", "int64"):
        tc.report("PTA005",
                  f"split_ids Ids `{op.input('Ids')[0]}` must be "
                  f"integer, got {ids.dtype}",
                  op=op, var=op.input("Ids")[0])
    n = None
    if ids.shape is not None:
        n = 1
        for d in ids.shape:
            if d is None or d < 0:
                n = -1
                break
            n *= int(d)
    for name in op.output("Out"):
        tc.set(name, shape=None if n is None else (n, 1),
               dtype=ids.dtype)


@rule("split_selected_rows")
def _r_split_selected_rows(op, tc):
    x = tc.input_info(op, "X")
    sections = op.attr("height_sections", []) or []
    names = op.output("Out")
    for i, name in enumerate(names):
        shape = None
        if x.shape is not None and len(x.shape) >= 2 and \
                i < len(sections):
            shape = (int(sections[i]),) + tuple(x.shape[1:])
        tc.set(name, shape=shape, dtype=x.dtype)


@rule("nce")
def _r_nce(op, tc):
    x = tc.input_info(op, "Input")
    label = tc.input_info(op, "Label")
    if label.dtype is not None and label.dtype not in ("int32", "int64"):
        tc.report("PTA005",
                  f"nce Label `{op.input('Label')[0]}` must be "
                  f"integer, got {label.dtype}",
                  op=op, var=op.input("Label")[0])
    n = x.shape[0] if x.shape is not None else None
    num_true = (label.shape[1] if label.shape is not None and
                len(label.shape) == 2 else 1)
    num_sampled = num_true + int(op.attr("num_neg_samples", 10))
    tc.set_output(op, "Cost", shape=None if n is None else (n, 1),
                  dtype=x.dtype)
    for slot, dt in (("SampleLogits", x.dtype),
                     ("SampleLabels", "int64")):
        if op.output(slot):
            tc.set(op.output(slot)[0],
                   shape=None if n is None else (n, num_sampled),
                   dtype=dt)


@rule("fill_constant", "fill")
def _r_fill_constant(op, tc):
    dtype = op.attr("dtype", "float32")
    shape = op.attr("shape")
    value = op.attr("value", 0.0)
    if dtype in ("int64",) and value is not None:
        try:
            fits = int64_fits_i32_lane(value)
        except (TypeError, ValueError):
            fits = True
        if not fits:
            name = op.output("Out")[0] if op.output("Out") else None
            tc.report("PTA010",
                      f"{op.type} writes int64 value(s) outside int32 "
                      f"range into `{name}` — under JAX x64-off (and on "
                      f"the pipeline i32 carrier lane) the value "
                      f"silently wraps; keep ids within int32 range or "
                      f"stage them host-side",
                      op=op, var=name)
    tc.set_output(op, "Out", shape=shape, dtype=dtype)


@rule("uniform_random", "gaussian_random")
def _r_random_init(op, tc):
    tc.set_output(op, "Out", shape=op.attr("shape"),
                  dtype=op.attr("dtype", "float32"))


@rule("fill_constant_batch_size_like")
def _r_fill_batch_like(op, tc):
    x = tc.input_info(op, "Input")
    shape = list(op.attr("shape") or ())
    if shape:
        out_idx = op.attr("output_dim_idx", 0)
        in_idx = op.attr("input_dim_idx", 0)
        if x.shape is not None and in_idx < len(x.shape) and \
                out_idx < len(shape):
            shape[out_idx] = x.shape[in_idx]
    tc.set_output(op, "Out", shape=shape or None,
                  dtype=op.attr("dtype", "float32"))


@rule("reshape", "reshape2")
def _r_reshape(op, tc):
    x = tc.input_info(op, "X")
    shape = list(op.attr("shape") or ())
    if shape and x.shape is not None:
        n_in = _prod(x.shape)
        unknown = sum(1 for d in shape if d in (-1, 0))
        if n_in is not None and unknown == 0:
            n_out = _prod(shape)
            if n_out is not None and n_out != n_in:
                tc.report("PTA006",
                          f"reshape of `{op.input('X')[0]}` {x.shape} "
                          f"({n_in} elements) to {tuple(shape)} "
                          f"({n_out} elements) changes the element "
                          f"count", op=op, var=op.input("X")[0])
    tc.set_output(op, "Out", shape=shape or None, dtype=x.dtype)


@rule("unsqueeze")
def _r_unsqueeze(op, tc):
    x = tc.input_info(op, "X")
    shape = None
    if x.shape is not None:
        shape = list(x.shape)
        for a in sorted(op.attr("axes") or ()):
            shape.insert(a, 1)
    tc.set_output(op, "Out", shape=shape and tuple(shape), dtype=x.dtype)


@rule("expand")
def _r_expand(op, tc):
    x = tc.input_info(op, "X")
    times = list(op.attr("expand_times") or ())
    shape = None
    if x.shape is not None:
        if len(times) != len(x.shape):
            tc.report("PTA006",
                      f"expand of `{op.input('X')[0]}` {x.shape} by "
                      f"{len(times)} factors", op=op, var=op.input("X")[0])
        else:
            shape = tuple(d * t if d is not None and d >= 0 else -1
                          for d, t in zip(x.shape, times))
    tc.set_output(op, "Out", shape=shape, dtype=x.dtype)


@rule("transpose", "transpose2")
def _r_transpose(op, tc):
    x = tc.input_info(op, "X")
    perm = op.attr("axis") or op.attr("perm")
    shape = None
    if x.shape is not None and perm and len(perm) == len(x.shape):
        shape = tuple(x.shape[p] for p in perm)
    tc.set_output(op, "Out", shape=shape, dtype=x.dtype)


@rule("concat")
def _r_concat(op, tc):
    infos = [tc.info(n) for n in op.input("X")]
    axis = op.attr("axis", 0)
    shape = None
    dtype = None
    known = [i for i in infos if i.shape is not None]
    for n, inf in zip(op.input("X"), infos):
        if inf.dtype is not None:
            if dtype is not None and inf.dtype != dtype:
                tc.report("PTA005",
                          f"concat inputs disagree on dtype: `{n}` is "
                          f"{inf.dtype}, earlier inputs are {dtype}",
                          op=op, var=n)
            dtype = dtype or inf.dtype
    if known and all(len(i.shape) == len(known[0].shape) for i in known):
        rank = len(known[0].shape)
        ax = axis % rank if rank else 0
        for d in range(rank):
            if d == ax:
                continue
            dims = {i.shape[d] for i in known if i.shape[d] != -1}
            if len(dims) > 1:
                tc.report("PTA006",
                          f"concat inputs disagree on non-concat dim "
                          f"{d}: {sorted(dims)}", op=op,
                          var=op.input("X")[0])
                break
        if len(known) == len(infos):
            cat = 0
            for i in known:
                if i.shape[ax] == -1:
                    cat = -1
                    break
                cat += i.shape[ax]
            shape = tuple(cat if d == ax else known[0].shape[d]
                          for d in range(rank))
    tc.set_output(op, "Out", shape=shape, dtype=dtype)


@rule("conv2d")
def _r_conv2d(op, tc):
    x = tc.input_info(op, "Input")
    w = tc.input_info(op, "Filter")
    shape = None
    if x.shape is not None and w.shape is not None and \
            len(x.shape) == 4 and len(w.shape) == 4:
        if _dims_conflict(x.shape[1],
                          w.shape[1] * op.attr("groups", 1)):
            tc.report("PTA006",
                      f"conv2d channel mismatch: Input "
                      f"`{op.input('Input')[0]}` has {x.shape[1]} "
                      f"channels but Filter `{op.input('Filter')[0]}` "
                      f"expects {w.shape[1] * op.attr('groups', 1)}",
                      op=op, var=op.input("Input")[0])
        stride = _pair(op.attr("strides", [1, 1]))
        pad = _pair(op.attr("paddings", [0, 0]))
        dil = _pair(op.attr("dilations", [1, 1]))
        hw = []
        for i in (0, 1):
            d_in = x.shape[2 + i]
            if d_in == -1 or w.shape[2 + i] == -1:
                hw.append(-1)
            else:
                k = dil[i] * (w.shape[2 + i] - 1) + 1
                hw.append((d_in + 2 * pad[i] - k) // stride[i] + 1)
        shape = (x.shape[0], w.shape[0], hw[0], hw[1])
    tc.set_output(op, "Output", shape=shape, dtype=x.dtype)


def _pair(v):
    return (v, v) if isinstance(v, int) else tuple(v)


@rule("pool2d")
def _r_pool2d(op, tc):
    x = tc.input_info(op, "X")
    shape = None
    if x.shape is not None and len(x.shape) == 4:
        if op.attr("global_pooling", False):
            shape = (x.shape[0], x.shape[1], 1, 1)
        else:
            k = _pair(op.attr("ksize", [1, 1]))
            stride = _pair(op.attr("strides", [1, 1]))
            pad = _pair(op.attr("paddings", [0, 0]))
            ceil = op.attr("ceil_mode", False)
            hw = []
            for i in (0, 1):
                d_in = x.shape[2 + i]
                if d_in == -1:
                    hw.append(-1)
                    continue
                num = d_in + 2 * pad[i] - k[i]
                hw.append((num + stride[i] - 1) // stride[i] + 1 if ceil
                          else num // stride[i] + 1)
            shape = (x.shape[0], x.shape[1], hw[0], hw[1])
    tc.set_output(op, "Out", shape=shape, dtype=x.dtype)


@rule("batch_norm")
def _r_batch_norm(op, tc):
    x = tc.input_info(op, "X")
    tc.set_output(op, "Y", shape=x.shape, dtype=x.dtype)


@rule("layer_norm")
def _r_layer_norm(op, tc):
    x = tc.input_info(op, "X")
    tc.set_output(op, "Y", shape=x.shape, dtype=x.dtype)


# ---------------------------------------------------------------------------
# gradient-op rules: the single largest warn-list family.  Every
# ``<type>_grad`` op built by ``registry.default_grad_maker`` follows
# one slot convention — inputs carry the forward slots (same names) and
# outputs carry ``<slot>@GRAD`` per differentiable forward input — and
# the cotangent of a tensor always has THAT TENSOR's shape and dtype.
# So one mirror rule covers the family soundly: each ``<slot>@GRAD``
# output copies the shape/dtype of the forward input it differentiates,
# index-aligned within the slot (nothing is ever *reported* here —
# propagation only, so downstream rules like the optimizer Param/Grad
# agreement can see through backward chains).
# ---------------------------------------------------------------------------

_GRAD_MIRROR_OPS = tuple(
    t + "_grad" for t in _UNARY_OPS + (
        "mul", "matmul", "elementwise_add", "elementwise_sub",
        "elementwise_mul", "elementwise_div", "elementwise_max",
        "elementwise_min", "elementwise_pow", "sum", "mean", "concat",
        "reduce_sum", "reduce_mean", "reduce_max", "reduce_min",
        "reduce_prod", "cross_entropy", "softmax_with_cross_entropy",
        "lookup_table", "nce", "reshape", "reshape2", "unsqueeze", "expand",
        "transpose", "transpose2", "conv2d", "pool2d", "batch_norm", "layer_norm",
        "sequence_pool", "lstm", "write_to_array", "read_from_array",
        "array_to_lod_tensor", "lod_tensor_to_array",
        "reorder_lod_tensor_by_rank",
    ))


@rule(*_GRAD_MIRROR_OPS)
def _r_grad_mirror(op, tc):
    for slot, names in op.outputs.items():
        if not slot.endswith(framework.GRAD_SUFFIX):
            # auxiliary outputs (saved state, scratch): unknown
            tc.set_output(op, slot)
            continue
        fwd = op.input(slot[:-len(framework.GRAD_SUFFIX)])
        for i, n in enumerate(names):
            src = tc.info(fwd[i]) if i < len(fwd) else _UNKNOWN
            tc.set(n, shape=src.shape, dtype=src.dtype)


@rule("increment")
def _r_increment(op, tc):
    tc.copy_unary(op)


@rule("assign_value")
def _r_assign_value(op, tc):
    tc.set_output(op, "Out", shape=op.attr("shape"),
                  dtype=op.attr("dtype", "float32"))


@rule("max_sequence_len")
def _r_max_sequence_len(op, tc):
    tc.set_output(op, "Out", shape=(1,), dtype="int64")


@rule("sequence_expand")
def _r_sequence_expand(op, tc):
    # row count follows the LoD expansion (unknown statically);
    # feature dims and dtype carry through
    x = tc.input_info(op, "X")
    shape = (-1,) + tuple(x.shape[1:]) if x.shape is not None else None
    tc.set_output(op, "Out", shape=shape, dtype=x.dtype)


@rule("less_than", "less_equal", "greater_than", "greater_equal",
      "equal", "not_equal")
def _r_compare(op, tc):
    x = tc.input_info(op, "X")
    tc.set_output(op, "Out", shape=x.shape, dtype="bool")


@rule("sequence_pool")
def _r_sequence_pool(op, tc):
    # rows collapse per sequence: the batch dim is LoD-dependent
    # (unknown statically), the feature dims and dtype carry through
    x = tc.input_info(op, "X")
    shape = (-1,) + tuple(x.shape[1:]) if x.shape is not None else None
    tc.set_output(op, "Out", shape=shape, dtype=x.dtype)
    tc.set_output(op, "MaxIndex", shape=shape, dtype="int32")


# ---------------------------------------------------------------------------
# LoD/array plumbing + recurrent ops: coverage the cost model rides
# (shape inference is the prerequisite for bytes costing).  Row counts
# are LoD-dependent (unknown statically, -1); trailing feature dims and
# dtypes carry through exactly — propagation only, nothing reported.
# ---------------------------------------------------------------------------

@rule("write_to_array", "read_from_array", "array_to_lod_tensor",
      "lod_tensor_to_array", "reorder_lod_tensor_by_rank")
def _r_lod_array_plumbing(op, tc):
    x = tc.input_info(op, "X")
    shape = (-1,) + tuple(x.shape[1:]) if x.shape is not None else None
    tc.set_output(op, "Out", shape=shape, dtype=x.dtype)


@rule("lod_rank_table")
def _r_lod_rank_table(op, tc):
    # produces a rank-table object, not a tensor: nothing to propagate,
    # but the op is KNOWN (off the warn-list) — consumers' rules treat
    # the table input as unknown by construction
    tc.set_output(op, "Out")


@rule("lstm")
def _r_lstm(op, tc):
    x = tc.input_info(op, "Input")
    w = tc.input_info(op, "Weight")
    hidden = None
    if w.shape is not None and len(w.shape) == 2 and w.shape[0] != -1:
        hidden = w.shape[0]
    rows = x.shape[0] if x.shape is not None else -1
    shape = (rows, hidden) if hidden is not None else None
    tc.set_output(op, "Hidden", shape=shape, dtype=x.dtype)
    tc.set_output(op, "Cell", shape=shape, dtype=x.dtype)
    tc.set_output(op, "BatchGate")
    tc.set_output(op, "BatchCellPreAct")


@rule("sgd", "momentum", "adam", "adamax", "adagrad", "adadelta",
      "decayed_adagrad", "rmsprop", "ftrl", "lars_momentum")
def _r_optimizer(op, tc):
    p = tc.input_info(op, "Param")
    g = tc.input_info(op, "Grad")
    if p.shape is not None and g.shape is not None and \
            (len(p.shape) != len(g.shape) or
             any(_dims_conflict(a, b) for a, b in zip(p.shape, g.shape))):
        tc.report("PTA006",
                  f"{op.type}: Param `{op.input('Param')[0]}` {p.shape} "
                  f"and Grad `{op.input('Grad')[0]}` {g.shape} differ "
                  f"in shape", op=op, var=op.input("Param")[0])
    if p.dtype is not None and g.dtype is not None and p.dtype != g.dtype:
        tc.report("PTA005",
                  f"{op.type}: Param dtype {p.dtype} differs from Grad "
                  f"dtype {g.dtype}", op=op, var=op.input("Param")[0])
    tc.set_output(op, "ParamOut", shape=p.shape, dtype=p.dtype)


@rule("paged_attention", "shared_kv_attention")
def _r_paged_attention(op, tc):
    q = tc.input_info(op, "Q")
    kc = tc.input_info(op, "KCache")
    vc = tc.input_info(op, "VCache")
    # RowLens: the optional limit a row (a step of two blocks a slot)
    index_slots = ["PageTable", "Lens"] + \
        (["RowLens"] if op.input("RowLens") else [])
    for slot in index_slots:
        inf = tc.input_info(op, slot)
        if inf.dtype is not None and inf.dtype not in ("int32", "int64"):
            tc.report("PTA005",
                      f"paged_attention {slot} "
                      f"`{op.input(slot)[0]}` must be an integer index "
                      f"tensor, got {inf.dtype}",
                      op=op, var=op.input(slot)[0])
    # the pools share pages; their rows may differ (value heads of their
    # own width), which the per-slot width checks below hold to the heads
    if kc.shape is not None and vc.shape is not None and \
            (len(kc.shape) != len(vc.shape) or
             any(_dims_conflict(a, b)
                 for a, b in zip(kc.shape[:-1], vc.shape[:-1]))):
        tc.report("PTA006",
                  f"paged_attention K/V pools disagree on geometry: "
                  f"KCache `{op.input('KCache')[0]}` {kc.shape} vs "
                  f"VCache `{op.input('VCache')[0]}` {vc.shape}",
                  op=op, var=op.input("KCache")[0])
    # the pool's rows hold the K/V heads: n_kv_head head widths (grouped
    # query heads), as many as Q's n_head when the attr is absent
    n_head = op.attr("n_head", None)
    n_kv = op.attr("n_kv_head", None) or n_head
    k_row = kc.shape[-1] if kc.shape is not None else -1
    v_row = vc.shape[-1] if vc.shape is not None else -1
    if k_row > 0 and v_row > 0 and k_row != v_row and \
            (not n_kv or int(n_kv) == int(n_head or 0)
             or k_row % int(n_kv) or v_row % int(n_kv)):
        tc.report("PTA006",
                  f"paged_attention K/V pools disagree on geometry: "
                  f"KCache rows of {k_row} vs VCache rows of {v_row} do "
                  f"not hold the same {n_kv} grouped K/V heads",
                  op=op, var=op.input("VCache")[0])
    # K and V are absent where the pool is another layer's: read only
    owns = bool(op.input("K"))
    for slot in ("Q", "K", "V") if owns else ("Q",):
        row = v_row if slot == "V" else k_row
        inf = tc.input_info(op, slot)
        width = inf.shape[-1] if inf.shape is not None else -1
        if slot == "Q" and n_head and width > 0 and \
                width % int(n_head) == 0:
            width = width // int(n_head) * int(n_kv)
        if row > 0 and width > 0 and width != row:
            tc.report("PTA006",
                      f"paged_attention {slot} `{op.input(slot)[0]}` "
                      f"feature dim {inf.shape[-1]} does not fit the page "
                      f"pool's rows of {row} (n_head={n_head}, "
                      f"n_kv_head={n_kv}) — the scatter would write "
                      f"misshapen rows", op=op, var=op.input(slot)[0])
    if n_head and q.shape is not None and q.shape[-1] > 0 and \
            q.shape[-1] % int(n_head):
        tc.report("PTA006",
                  f"paged_attention feature dim {q.shape[-1]} is not "
                  f"divisible by n_head={n_head}",
                  op=op, var=op.input("Q")[0])
    out = q.shape
    if out is not None and k_row > 0 and v_row > 0 and k_row != v_row \
            and out[-1] > 0:
        out = tuple(out[:-1]) + (out[-1] * v_row // k_row,)
    tc.set_output(op, "Out", shape=out, dtype=q.dtype)
    if owns:
        tc.set_output(op, "KCacheOut", shape=kc.shape, dtype=kc.dtype)
        tc.set_output(op, "VCacheOut", shape=vc.shape, dtype=vc.dtype)


# -- hybrid blocks: norms, state-space mixer, expert routing ---------------

def _same_as(op, tc, slot="X", out="Out"):
    x = tc.input_info(op, slot)
    tc.set_output(op, out, shape=x.shape, dtype=x.dtype)
    return x


def _last_dim_is(op, tc, slot, want, what):
    inf = tc.input_info(op, slot)
    if inf.shape is not None and want is not None and want > 0 and \
            inf.shape[-1] > 0 and inf.shape[-1] != want:
        tc.report("PTA006",
                  f"{op.type} {slot} `{op.input(slot)[0]}` has "
                  f"{inf.shape[-1]} {what}, expected {want}",
                  op=op, var=op.input(slot)[0])


def _int_index(op, tc, slot):
    if not op.input(slot):
        return
    inf = tc.input_info(op, slot)
    if inf.dtype is not None and inf.dtype not in ("int32", "int64"):
        tc.report("PTA005",
                  f"{op.type} {slot} `{op.input(slot)[0]}` must be an "
                  f"integer tensor, got {inf.dtype}",
                  op=op, var=op.input(slot)[0])


@rule("relu2")
def _r_relu2(op, tc):
    _same_as(op, tc)


@rule("rms_norm", "gated_group_rms_norm")
def _r_rms_norm(op, tc):
    x = _same_as(op, tc)
    width = x.shape[-1] if x.shape is not None else None
    _last_dim_is(op, tc, "Scale", width, "scales")
    if op.type == "gated_group_rms_norm":
        _last_dim_is(op, tc, "Gate", width, "gate features")
        groups = int(op.attr("groups", 1))
        if width and width > 0 and width % groups:
            tc.report("PTA006",
                      f"gated_group_rms_norm width {width} does not "
                      f"split into {groups} groups", op=op,
                      var=op.input("X")[0])


def _ssm_widths(op):
    h, p = int(op.attr("n_head")), int(op.attr("head_dim"))
    g, n = int(op.attr("n_groups")), int(op.attr("state"))
    return h, p, g, n


@rule("ssm_scan_conv", "ssm_update_conv")
def _r_ssm_conv(op, tc):
    x = _same_as(op, tc)
    w = tc.input_info(op, "W")
    chans = x.shape[-1] if x.shape is not None else None
    _last_dim_is(op, tc, "W", chans, "channels")
    _last_dim_is(op, tc, "Bias", chans, "channels")
    if op.type == "ssm_update_conv":
        _int_index(op, tc, "Lens")
        win = tc.input_info(op, "Window")
        _last_dim_is(op, tc, "Window", chans, "channels")
        tc.set_output(op, "WindowOut", shape=win.shape, dtype=win.dtype)
    else:
        taps = w.shape[0] - 1 if w.shape is not None and w.shape[0] > 0 \
            else -1
        tc.set_output(op, "Window", dtype="float32",
                      shape=None if chans is None else (1, taps, chans))


@rule("ssm_chunk_conv")
def _r_ssm_chunk_conv(op, tc):
    x = _same_as(op, tc)
    chans = x.shape[-1] if x.shape is not None else None
    for slot in ("W", "Bias", "Window"):
        _last_dim_is(op, tc, slot, chans, "channels")
    for slot in ("Slot", "Pos"):
        _int_index(op, tc, slot)
    win = tc.input_info(op, "Window")
    tc.set_output(op, "WindowOut", shape=win.shape, dtype=win.dtype)


@rule("kda_scan", "kda_update")
def _r_kda(op, tc):
    """X holds q | k | v of ``n_head`` heads of one width D; the state is
    [slots, n_head, D, D] float32 and comes back as it went in."""
    h = int(op.attr("n_head"))
    x = tc.input_info(op, "X")
    width = x.shape[-1] if x.shape is not None else None
    d = None
    if width is not None and width > 0:
        if width % (3 * h):
            tc.report("PTA006", f"{op.type}: {width} features do not "
                      f"split into q | k | v of {h} heads", op=op,
                      var=op.input("X")[0])
        else:
            d = width // (3 * h)
    _last_dim_is(op, tc, "F", d and h * d, "decay channels")
    _last_dim_is(op, tc, "DtBias", d and h * d, "decay channels")
    _last_dim_is(op, tc, "B", h, "heads")
    _last_dim_is(op, tc, "ALog", h, "heads")
    for slot in ("Slot", "Pos", "Lens"):
        _int_index(op, tc, slot)
    st = tc.input_info(op, "State")
    if d and st.shape is not None and len(st.shape) == 4 and \
            all(n > 0 for n in st.shape[1:]) and \
            tuple(st.shape[1:]) != (h, d, d):
        tc.report("PTA006", f"{op.type} State `{op.input('State')[0]}` is "
                  f"{st.shape}, expected [slots, {h}, {d}, {d}]", op=op,
                  var=op.input("State")[0])
    if st.dtype is not None and st.dtype != "float32":
        tc.report("PTA005", f"{op.type} keeps its state in float32, got "
                  f"{st.dtype}", op=op, var=op.input("State")[0])
    shape = None if x.shape is None else \
        tuple(x.shape[:-1]) + (h * d if d else -1,)
    tc.set_output(op, "Out", shape=shape, dtype=x.dtype)
    tc.set_output(op, "StateOut", shape=st.shape, dtype=st.dtype)


@rule("mamba_scan", "mamba_update")
def _r_mamba(op, tc):
    """X and Dt hold one value a channel; the state is [slots, N,
    channels] float32 and comes back as it went in."""
    x = _same_as(op, tc)
    chans = x.shape[-1] if x.shape is not None else None
    if not op.input("State"):       # a whole sequence from zeros
        return
    st = tc.input_info(op, "State")
    for slot in ("Dt", "ALog", "D", "DtBias"):
        _last_dim_is(op, tc, slot, chans, "channels")
    n = st.shape[1] if st.shape is not None and len(st.shape) == 3 else None
    for slot in ("B", "C"):
        _last_dim_is(op, tc, slot, n, "state rows")
    for slot in ("Slot", "Pos", "Lens"):
        _int_index(op, tc, slot)
    if st.shape is not None and chans and chans > 0 and \
            (len(st.shape) != 3 or 0 < st.shape[2] != chans):
        tc.report("PTA006", f"{op.type} State `{op.input('State')[0]}` is "
                  f"{st.shape}, expected [slots, N, {chans}]", op=op,
                  var=op.input("State")[0])
    if st.dtype is not None and st.dtype != "float32":
        tc.report("PTA005", f"{op.type} keeps its state in float32, got "
                  f"{st.dtype}", op=op, var=op.input("State")[0])
    tc.set_output(op, "StateOut", shape=st.shape, dtype=st.dtype)


@rule("diff_attention_pad")
def _r_diff_attention_pad(op, tc):
    x = tc.input_info(op, "X")
    h = int(op.attr("n_head"))
    width = x.shape[-1] if x.shape is not None else -1
    if width > 0 and (h % 2 or width % h):
        tc.report("PTA006", f"diff_attention_pad: {width} features do not "
                  f"split into {h} heads in pairs", op=op,
                  var=op.input("X")[0])
    tc.set_output(op, "Out", dtype=x.dtype, shape=None if x.shape is None
                  else tuple(x.shape[:-1]) + (2 * width if width > 0
                                              else -1,))


@rule("diff_attention_out")
def _r_diff_attention_out(op, tc):
    x = tc.input_info(op, "X")
    h = int(op.attr("n_head"))
    width = x.shape[-1] if x.shape is not None else -1
    wide = width // h if width > 0 and width % h == 0 else None
    _last_dim_is(op, tc, "Scale", wide, "lanes of a pair's value")
    for slot in ("LambdaQ1", "LambdaK1", "LambdaQ2", "LambdaK2"):
        _last_dim_is(op, tc, slot, wide and wide // 2, "lanes of a head")
    tc.set_output(op, "Out", dtype=x.dtype, shape=None if x.shape is None
                  else tuple(x.shape[:-1]) + (width // 2 if width > 0
                                              else -1,))


@rule("paged_kv_write")
def _r_paged_kv_write(op, tc):
    for slot, cache in (("K", "KCache"), ("V", "VCache")):
        pool = tc.input_info(op, cache)
        _last_dim_is(op, tc, slot, pool.shape[-1] if pool.shape is not None
                     else None, "lanes of the pool's row")
        tc.set_output(op, cache + "Out", shape=pool.shape, dtype=pool.dtype)
    for slot in ("PageTable", "Pos"):
        _int_index(op, tc, slot)
    tc.set_output(op, "Lens", shape=(1, 1), dtype="int32")


@rule("kda_gated_norm")
def _r_kda_gated_norm(op, tc):
    x = _same_as(op, tc)
    h = int(op.attr("n_head"))
    width = x.shape[-1] if x.shape is not None else None
    _last_dim_is(op, tc, "Gate", width, "gate features")
    if width and width > 0:
        if width % h:
            tc.report("PTA006", f"kda_gated_norm width {width} does not "
                      f"split into {h} heads", op=op, var=op.input("X")[0])
        else:
            _last_dim_is(op, tc, "Scale", width // h, "scales (one head's)")


@rule("attention_out_gate")
def _r_attention_out_gate(op, tc):
    x = _same_as(op, tc)
    _last_dim_is(op, tc, "Gate", x.shape[-1] if x.shape is not None
                 else None, "gate features")


@rule("ssm_scan", "ssm_update")
def _r_ssm(op, tc):
    h, p, g, n = _ssm_widths(op)
    x = tc.input_info(op, "X")
    if h % g:
        tc.report("PTA006", f"{op.type}: {h} heads do not split into {g} "
                  f"groups", op=op, var=op.input("X")[0])
    _last_dim_is(op, tc, "X", h * p + 2 * g * n, "features (x | B | C)")
    _last_dim_is(op, tc, "Dt", h, "heads")
    for slot in ("ALog", "D", "DtBias"):
        _last_dim_is(op, tc, slot, h, "heads")
    shape = None if x.shape is None else tuple(x.shape[:-1]) + (h * p,)
    tc.set_output(op, "Out", shape=shape, dtype=x.dtype)
    if op.type == "ssm_update":
        _int_index(op, tc, "Lens")
        st = tc.input_info(op, "State")
        if st.shape is not None and len(st.shape) == 4 and \
                all(d > 0 for d in st.shape[1:]) and \
                tuple(st.shape[1:]) != (h, p, n):
            tc.report("PTA006",
                      f"ssm_update State `{op.input('State')[0]}` is "
                      f"{st.shape}, expected [slots, {h}, {p}, {n}]",
                      op=op, var=op.input("State")[0])
        if st.dtype is not None and st.dtype != "float32":
            tc.report("PTA005", f"ssm_update keeps its state in float32, "
                      f"got {st.dtype}", op=op, var=op.input("State")[0])
        tc.set_output(op, "StateOut", shape=st.shape, dtype=st.dtype)
    else:
        tc.set_output(op, "State", shape=(1, h, p, n), dtype="float32")


@rule("moe_route")
def _r_moe_route(op, tc):
    x = tc.input_info(op, "X")
    w = tc.input_info(op, "W")
    if x.shape is not None and w.shape is not None and len(w.shape) == 2:
        _last_dim_is(op, tc, "X", w.shape[0], "features")
        _last_dim_is(op, tc, "Bias", w.shape[1], "experts")
        k = int(op.attr("top_k"))
        if 0 < w.shape[1] < k:
            tc.report("PTA006", f"moe_route picks {k} of only "
                      f"{w.shape[1]} experts", op=op, var=op.input("W")[0])
    k = int(op.attr("top_k"))
    shape = None if x.shape is None else tuple(x.shape[:-1]) + (k,)
    tc.set_output(op, "TopkIdx", shape=shape, dtype="int32")
    tc.set_output(op, "TopkWeight", shape=shape, dtype="float32")


@rule("moe_experts")
def _r_moe_experts(op, tc):
    x = _same_as(op, tc)
    w1, w2 = tc.input_info(op, "W1"), tc.input_info(op, "W2")
    _int_index(op, tc, "TopkIdx")
    _int_index(op, tc, "Lens")
    if w1.shape is not None and w2.shape is not None:
        if len(w1.shape) != 3 or len(w2.shape) != 3 or \
                any(_dims_conflict(a, b) for a, b in zip(
                    w1.shape, (w2.shape[0], w2.shape[2], w2.shape[1]))):
            tc.report("PTA006",
                      f"moe_experts W1 {w1.shape} / W2 {w2.shape} are not "
                      f"[held, latent, hidden] / [held, hidden, latent]",
                      op=op, var=op.input("W1")[0])
        elif x.shape is not None:
            _last_dim_is(op, tc, "X", w1.shape[1], "latent features")
    tc.set_output(op, "Stats", shape=(1, 3), dtype="int32")


@rule("moe_experts_gated")
def _r_moe_experts_gated(op, tc):
    x = _same_as(op, tc)
    wg, wu, wd = (tc.input_info(op, s) for s in ("Wg", "Wu", "Wd"))
    _int_index(op, tc, "TopkIdx")
    _int_index(op, tc, "Lens")
    if None not in (wg.shape, wu.shape, wd.shape):
        if any(len(w.shape) != 3 for w in (wg, wu, wd)) or \
                any(_dims_conflict(a, b) for w in (wg, wu)
                    for a, b in zip(w.shape, (wd.shape[0], wd.shape[2],
                                              wd.shape[1]))):
            tc.report("PTA006",
                      f"moe_experts_gated Wg {wg.shape} / Wu {wu.shape} / "
                      f"Wd {wd.shape} are not [held, features, hidden] x 2 "
                      f"/ [held, hidden, features]",
                      op=op, var=op.input("Wg")[0])
        elif x.shape is not None:
            _last_dim_is(op, tc, "X", wg.shape[1], "features")
    tc.set_output(op, "Stats", shape=(1, 3), dtype="int32")


# -- latent attention: rotary slice, gated activation, the two forms -------

@rule("pad")
def _r_pad(op, tc):
    x = tc.input_info(op, "X")
    p = list(op.attr("paddings") or ())
    shape = None
    if x.shape is not None:
        if len(p) != 2 * len(x.shape):
            tc.report("PTA006", f"pad: {len(p)} paddings for a tensor of "
                      f"{len(x.shape)} dims (two a dim)", op=op,
                      var=op.input("X")[0])
        else:
            shape = tuple(d if d < 0 else d + p[2 * i] + p[2 * i + 1]
                          for i, d in enumerate(x.shape))
    tc.set_output(op, "Out", shape=shape, dtype=x.dtype)


@rule("rope")
def _r_rope(op, tc):
    x = _same_as(op, tc)
    _int_index(op, tc, "Pos")
    h, r = int(op.attr("n_head", 1)), int(op.attr("rope_dim"))
    if r % 2 or (x.shape is not None and x.shape[-1] > 0 and
                 (x.shape[-1] % h or x.shape[-1] // h < r)):
        tc.report("PTA006",
                  f"rope: a slice of {r} lanes (pairs) does not fit "
                  f"{h} head(s) over {x.shape[-1] if x.shape else '?'} "
                  f"features", op=op, var=op.input("X")[0])


@rule("swiglu")
def _r_swiglu(op, tc):
    x = _same_as(op, tc)
    y = tc.input_info(op, "Y")
    if x.shape is not None and y.shape is not None and \
            (len(x.shape) != len(y.shape) or any(
                _dims_conflict(a, b) for a, b in zip(x.shape, y.shape))):
        tc.report("PTA006", f"swiglu gate {x.shape} and up projection "
                  f"{y.shape} differ", op=op, var=op.input("Y")[0])


def _mla_widths(op, tc):
    """(heads, nope, v) and W_kvb's latent width, W_kvb checked."""
    h, nope, v = (int(op.attr(a)) for a in ("n_head", "nope_dim", "v_dim"))
    w = tc.input_info(op, "Wkvb")
    latent = None
    if w.shape is not None and len(w.shape) == 2:
        latent = w.shape[0]
        _last_dim_is(op, tc, "Wkvb", h * (nope + v),
                     "columns (heads x (nope + v))")
    return h, nope, v, latent


def _paged_rows_of(op, tc, cache_slot="Cache"):
    """Rows a paged op addresses a slot: its page table's width x the
    pool's page length (None while either is unknown)."""
    pt, cache = tc.input_info(op, "PageTable"), tc.input_info(op, cache_slot)
    if pt.shape is not None and cache.shape is not None and \
            pt.shape[-1] > 0 and cache.shape[1] > 0:
        return pt.shape[-1] * cache.shape[1]
    return None


@rule("mla_attention", "mla_attention_chunk", "latent_window_attention")
def _r_mla_attention(op, tc):
    h, nope, v, latent = _mla_widths(op, tc)
    r = int(op.attr("rope_dim"))
    q = tc.input_info(op, "Q")
    _last_dim_is(op, tc, "Q", h * (nope + r), "features (heads x (nope + "
                                              "rope))")
    lat = tc.input_info(op, "Latent")
    if latent and lat.shape is not None and 0 < lat.shape[-1] < latent + r:
        tc.report("PTA006",
                  f"{op.type} Latent `{op.input('Latent')[0]}` is "
                  f"{lat.shape[-1]} wide, under c_kv {latent} + rope {r}",
                  op=op, var=op.input("Latent")[0])
    shape = None if q.shape is None else tuple(q.shape[:-1]) + (h * v,)
    tc.set_output(op, "Out", shape=shape, dtype=q.dtype)
    if op.type == "latent_window_attention":
        if op.input("Ring"):
            # one chunk of a prompt: the slot's ring takes the chunk's rows
            _int_index(op, tc, "Slot")
            _int_index(op, tc, "Pos")
            _ring_holds(op, tc, None if lat.shape is None
                        else lat.shape[-1])
        return
    if op.type == "mla_attention":
        if q.shape is not None and len(q.shape) >= 2:
            _select_matches(op, tc, q.shape[-2])
        return
    # one chunk of a prompt over the slot's pages: the pool holds the
    # chunk's rows as they come, and passes through under its own name
    cache = tc.input_info(op, "Cache")
    if cache.shape is not None and len(cache.shape) == 3:
        _last_dim_is(op, tc, "Latent", cache.shape[-1],
                     "lanes (the pool's row)")
    _int_index(op, tc, "PageTable")
    _int_index(op, tc, "Pos")
    rows = _paged_rows_of(op, tc)
    if rows is not None:
        _select_matches(op, tc, rows)
    tc.set_output(op, "CacheOut", shape=cache.shape, dtype=cache.dtype)


@rule("mla_absorb")
def _r_mla_absorb(op, tc):
    h, nope, v, latent = _mla_widths(op, tc)
    x = tc.input_info(op, "X")
    width = -1
    if op.attr("side") == "o":
        _last_dim_is(op, tc, "X", h * latent if latent else None,
                     "features (heads x latent)")
        width = h * v
    elif x.shape is not None and x.shape[-1] > 0 and latent:
        if x.shape[-1] % h or x.shape[-1] // h < nope:
            tc.report("PTA006", f"mla_absorb: {x.shape[-1]} query features "
                      f"do not hold {h} heads of nope {nope} + rope",
                      op=op, var=op.input("X")[0])
        width = x.shape[-1] + h * (latent - nope + int(op.attr("pad", 0)))
    shape = None if x.shape is None else tuple(x.shape[:-1]) + (width,)
    tc.set_output(op, "Out", shape=shape, dtype=x.dtype)


@rule("paged_attention_latent")
def _r_paged_attention_latent(op, tc):
    q = tc.input_info(op, "Q")
    cache = tc.input_info(op, "Cache")
    _int_index(op, tc, "PageTable")
    _int_index(op, tc, "Lens")
    h, v = int(op.attr("n_head")), int(op.attr("v_width"))
    row = cache.shape[-1] if cache.shape is not None else -1
    _last_dim_is(op, tc, "Row", row, "features (the pool's row)")
    _last_dim_is(op, tc, "Q", h * row if row > 0 else None,
                 "features (heads x the pool's row)")
    if 0 < row < v:
        tc.report("PTA006", f"paged_attention_latent reads a value of {v} "
                  f"lanes from rows of {row}", op=op,
                  var=op.input("Cache")[0])
    rows = _paged_rows_of(op, tc)
    if rows is not None:
        _select_matches(op, tc, rows)
    _int_index(op, tc, "RowLens")
    if op.input("RowLens") and op.input("Select"):
        tc.report("PTA006", "paged_attention_latent takes a limit a row "
                  "(RowLens) or a selection (Select), not both", op=op,
                  var=op.input("RowLens")[0])
    shape = None if q.shape is None else tuple(q.shape[:-1]) + (h * v,)
    tc.set_output(op, "Out", shape=shape, dtype=q.dtype)
    tc.set_output(op, "CacheOut", shape=cache.shape, dtype=cache.dtype)


def _ring_holds(op, tc, row):
    """A ring of latent rows ``Ring`` [slots, ring, row] held to the row
    that goes through it and to the window; passes through as RingOut."""
    ring = tc.input_info(op, "Ring")
    if ring.shape is not None and len(ring.shape) == 3:
        if row is not None and row > 0:
            _last_dim_is(op, tc, "Ring", row, "lanes a row (the latent "
                                              "row's)")
        if 0 < ring.shape[1] < int(op.attr("window")):
            tc.report("PTA006", f"{op.type}: Ring holds {ring.shape[1]} "
                      f"rows a slot, fewer than the window of "
                      f"{op.attr('window')}", op=op, var=op.input("Ring")[0])
    tc.set_output(op, "RingOut", shape=ring.shape, dtype=ring.dtype)


@rule("latent_window_step")
def _r_latent_window_step(op, tc):
    q, row = tc.input_info(op, "Q"), tc.input_info(op, "Row")
    _int_index(op, tc, "Lens")
    h, v = int(op.attr("n_head")), int(op.attr("v_width"))
    width = row.shape[-1] if row.shape is not None else -1
    _ring_holds(op, tc, width)
    _last_dim_is(op, tc, "Q", h * width if width > 0 else None,
                 "features (heads x the ring's row)")
    if 0 < width < v:
        tc.report("PTA006", f"latent_window_step reads a value of {v} "
                  f"lanes from rows of {width}", op=op,
                  var=op.input("Row")[0])
    shape = None if q.shape is None else tuple(q.shape[:-1]) + (h * v,)
    tc.set_output(op, "Out", shape=shape, dtype=q.dtype)


@rule("head_gate")
def _r_head_gate(op, tc):
    x = _same_as(op, tc)
    h = int(op.attr("n_head"))
    _last_dim_is(op, tc, "Gate", h, "logits (one a head)")
    if x.shape is not None and x.shape[-1] > 0 and x.shape[-1] % h:
        tc.report("PTA006", f"head_gate: {x.shape[-1]} features do not "
                  f"divide over {h} heads", op=op, var=op.input("X")[0])


# hyper-connections (ops/mhc_ops.py)

def _mhc_streams(op, tc):
    """``(lead, n, C)`` of the streams ``X`` [..., n, C], or None."""
    x = tc.input_info(op, "X")
    if x.shape is None or len(x.shape) < 2:
        return None
    return tuple(x.shape[:-2]), x.shape[-2], x.shape[-1]


@rule("mhc_pre")
def _r_mhc_pre(op, tc):
    x, found = tc.input_info(op, "X"), _mhc_streams(op, tc)
    if found is None:
        for slot in ("U", "Post", "Res"):
            tc.set_output(op, slot, shape=None, dtype=None)
        return
    lead, n, c = found
    if n > 0:
        _last_dim_is(op, tc, "Phi", n * (n + 2),
                     "columns (pre, post and res: n (n + 2))")
        _last_dim_is(op, tc, "Bias", n * (n + 2), "entries (n (n + 2))")
        phi = tc.input_info(op, "Phi")
        if c > 0 and phi.shape is not None and len(phi.shape) == 2 \
                and phi.shape[0] > 0 and phi.shape[0] != n * c:
            tc.report("PTA006", f"mhc_pre Phi `{op.input('Phi')[0]}` has "
                      f"{phi.shape[0]} rows, expected {n} streams x {c}",
                      op=op, var=op.input("Phi")[0])
    _last_dim_is(op, tc, "Alpha", 3, "gains (pre, post, res)")
    for slot in ("Phi", "Alpha", "Bias"):
        inf = tc.input_info(op, slot)
        if inf.dtype is not None and inf.dtype != "float32":
            tc.report("PTA005", f"mhc_pre {slot} `{op.input(slot)[0]}` "
                      f"must be float32 (the coefficients' type), got "
                      f"{inf.dtype}", op=op, var=op.input(slot)[0])
    tc.set_output(op, "U", shape=lead + (c,), dtype=x.dtype)
    tc.set_output(op, "Post", shape=lead + (n,), dtype="float32")
    tc.set_output(op, "Res", shape=lead + (n, n), dtype="float32")


@rule("mhc_post")
def _r_mhc_post(op, tc):
    x, found = _same_as(op, tc), _mhc_streams(op, tc)
    if found is None:
        return
    _, n, c = found
    _last_dim_is(op, tc, "Y", c, "features (a stream's width)")
    _last_dim_is(op, tc, "Post", n, "entries (one a stream)")
    _last_dim_is(op, tc, "Res", n, "columns (one a stream)")
    y = tc.input_info(op, "Y")
    if y.dtype is not None and x.dtype is not None and y.dtype != x.dtype:
        tc.report("PTA005", f"mhc_post Y `{op.input('Y')[0]}` is "
                  f"{y.dtype}, the streams {x.dtype}", op=op,
                  var=op.input("Y")[0])


# learned sparse attention (ops/dsa_ops.py)

def _dsa_index_widths(op, tc):
    """The indexer's weights held to each other: Wq [q_lora, H * D], Wk
    [d, D], KScale / KBias [D], Ww [d, H].  Returns D (or None)."""
    h = int(op.attr("n_head"))
    wk = tc.input_info(op, "Wk")
    d_idx = wk.shape[-1] if wk.shape is not None and len(wk.shape) == 2 \
        else None
    r = int(op.attr("rope_dim"))
    if d_idx is not None and d_idx > 0:
        _last_dim_is(op, tc, "Wq", h * d_idx, "columns (index heads x the "
                                               "key's lanes)")
        _last_dim_is(op, tc, "KScale", d_idx, "lanes (the key's)")
        _last_dim_is(op, tc, "KBias", d_idx, "lanes (the key's)")
        if r % 2 or r > d_idx:
            tc.report("PTA006", f"{op.type}: a rotary slice of {r} lanes "
                      f"(pairs) does not fit an index head of {d_idx}",
                      op=op, var=op.input("Wk")[0])
    _last_dim_is(op, tc, "Ww", h, "columns (index heads)")
    x, cq = tc.input_info(op, "X"), tc.input_info(op, "Cq")
    if x.shape is not None and wk.shape is not None and len(wk.shape) == 2:
        _last_dim_is(op, tc, "X", wk.shape[0], "features (Wk's rows)")
    wq = tc.input_info(op, "Wq")
    if cq.shape is not None and wq.shape is not None and len(wq.shape) == 2:
        _last_dim_is(op, tc, "Cq", wq.shape[0], "features (Wq's rows)")
    _int_index(op, tc, "Pos")
    return d_idx


@rule("dsa_index")
def _r_dsa_index(op, tc):
    d_idx = _dsa_index_widths(op, tc)
    x = tc.input_info(op, "X")
    lead = None if x.shape is None else tuple(x.shape[:-1])
    tc.set_output(op, "Key", dtype=x.dtype, shape=None if lead is None
                  else lead + (d_idx if d_idx else -1,))
    tc.set_output(op, "Scores", dtype="float32", shape=None
                  if lead is None else lead + (lead[-1],))


@rule("dsa_index_paged", "dsa_index_chunk")
def _r_dsa_index_paged(op, tc):
    d_idx = _dsa_index_widths(op, tc)
    _int_index(op, tc, "PageTable")
    if op.type == "dsa_index_paged":
        _int_index(op, tc, "Lens")
    x, cache = tc.input_info(op, "X"), tc.input_info(op, "Cache")
    if cache.shape is not None and d_idx:
        _last_dim_is(op, tc, "Cache", d_idx, "lanes (the index key's)")
    rows = _paged_rows_of(op, tc) or -1
    # the decode step: a row a slot; a chunk: its rows, of one slot
    lead = None if x.shape is None else (x.shape[0], 1) \
        if op.type == "dsa_index_paged" else tuple(x.shape[:2])
    tc.set_output(op, "Scores", dtype="float32",
                  shape=None if lead is None else lead + (rows,))
    tc.set_output(op, "CacheOut", shape=cache.shape, dtype=cache.dtype)


@rule("dsa_select")
def _r_dsa_select(op, tc):
    sc = tc.input_info(op, "Scores")
    if sc.dtype is not None and sc.dtype != "float32":
        tc.report("PTA005", f"dsa_select Scores `{op.input('Scores')[0]}` "
                  f"must be float32 (the selection compares their bits), "
                  f"got {sc.dtype}", op=op, var=op.input("Scores")[0])
    _int_index(op, tc, "Lens")
    _int_index(op, tc, "Pos")
    if int(op.attr("top_k")) < 1:
        tc.report("PTA006", "dsa_select keeps top_k >= 1 rows", op=op,
                  var=op.input("Scores")[0])
    tc.set_output(op, "Select", shape=sc.shape,
                  dtype="int32" if op.input("Lens") else "int8")


def _select_matches(op, tc, rows):
    """An attention op's optional Select: one entry a row it may read."""
    if op.input("Select"):
        _last_dim_is(op, tc, "Select", rows, "rows (one a row read)")


@rule("gqa_attention")
def _r_gqa_attention(op, tc):
    q = _same_as(op, tc, "Q")
    h, hkv = int(op.attr("n_head")), int(op.attr("n_kv_head"))
    if h % hkv:
        tc.report("PTA006", f"gqa_attention: {h} query heads do not "
                  f"divide over {hkv} K/V heads", op=op,
                  var=op.input("Q")[0])
    elif q.shape is not None and q.shape[-1] > 0 and q.shape[-1] % h == 0:
        for slot in ("K", "V"):
            _last_dim_is(op, tc, slot, q.shape[-1] // h * hkv,
                         "K/V features")


@rule("rope_partial")
def _r_rope_partial(op, tc):
    x = tc.input_info(op, "X")
    _int_index(op, tc, "Pos")
    h, r = int(op.attr("n_head", 1)), int(op.attr("rope_dim"))
    shape = x.shape
    if x.shape is not None and x.shape[-1] > 0:
        if r % 2 or x.shape[-1] % h or x.shape[-1] // h < r:
            tc.report("PTA006",
                      f"rope_partial: {r} leading lanes (pairs) do not "
                      f"fit {h} head(s) over {x.shape[-1]} features",
                      op=op, var=op.input("X")[0])
        else:
            width = max(int(op.attr("pad_to", 0)), x.shape[-1] // h)
            shape = tuple(x.shape[:-1]) + (h * width,)
    tc.set_output(op, "Out", shape=shape, dtype=x.dtype)


def _window_heads(op, tc, n_kv=None):
    """Hold Q / K / V of a grouped attention with key and value heads
    of their own widths to its head counts; returns Out's shape."""
    q, k, v = (tc.input_info(op, s) for s in ("Q", "K", "V"))
    h = int(op.attr("n_head"))
    if q.shape is None or q.shape[-1] <= 0 or q.shape[-1] % h:
        return None
    dk = q.shape[-1] // h
    if n_kv is None and k.shape is not None and k.shape[-1] > 0:
        n_kv = k.shape[-1] // dk
    if not n_kv or h % n_kv:
        tc.report("PTA006", f"{op.type}: {h} query heads do not divide "
                  f"over {n_kv} K/V heads", op=op, var=op.input("Q")[0])
        return None
    _last_dim_is(op, tc, "K", n_kv * dk, "K features")
    if op.input("Sink"):
        _last_dim_is(op, tc, "Sink", h, "sink logits (one a head)")
    if v.shape is None or v.shape[-1] <= 0 or v.shape[-1] % n_kv:
        return None
    return tuple(q.shape[:-1]) + (v.shape[-1] // n_kv * h,)


@rule("gqa_flash_attention", "gqa_flash_attention_chunk", "window_attention")
def _r_window_attention(op, tc):
    q = tc.input_info(op, "Q")
    out = _window_heads(op, tc, int(op.attr("n_kv_head")))
    tc.set_output(op, "Out", shape=out, dtype=q.dtype)
    # one chunk of a prompt over the slot's own caches: they are held to
    # the chunk's K and V, and pass through under their own names
    caches = (("KCache", "K"), ("VCache", "V")) \
        if op.type == "gqa_flash_attention_chunk" else \
        (("KRing", "K"), ("VRing", "V")) if op.input("KRing") else ()
    for slot, src in caches:
        cache, x = tc.input_info(op, slot), tc.input_info(op, src)
        if cache.shape is not None and len(cache.shape) == 3 and \
                x.shape is not None:
            _last_dim_is(op, tc, slot, x.shape[-1], f"lanes a row ({src}'s)")
        tc.set_output(op, slot + "Out", shape=cache.shape, dtype=cache.dtype)
    if caches:
        _int_index(op, tc, "Pos")
        _int_index(op, tc, "PageTable" if op.input("PageTable") else "Slot")
    if op.type == "window_attention":
        window = int(op.attr("window"))
        held = tc.input_info(op, "KRing").shape if caches else None
        if held is not None and len(held) == 3 and 0 < held[1] < window:
            tc.report("PTA006", f"window_attention: KRing holds {held[1]} "
                      f"rows a slot, fewer than the window of {window}",
                      op=op, var=op.input("KRing")[0])


@rule("window_attention_step")
def _r_window_attention_step(op, tc):
    q = tc.input_info(op, "Q")
    _int_index(op, tc, "Lens")
    out = _window_heads(op, tc)
    for ring_slot, src in (("KRing", "K"), ("VRing", "V")):
        ring, x = tc.input_info(op, ring_slot), tc.input_info(op, src)
        if ring.shape is not None and len(ring.shape) == 3:
            if x.shape is not None:
                _last_dim_is(op, tc, ring_slot, x.shape[-1],
                             f"lanes a row ({src}'s)")
            if 0 < ring.shape[1] < int(op.attr("window")):
                tc.report("PTA006",
                          f"window_attention_step: {ring_slot} holds "
                          f"{ring.shape[1]} rows a slot, fewer than the "
                          f"window of {op.attr('window')}", op=op,
                          var=op.input(ring_slot)[0])
        tc.set_output(op, ring_slot + "Out", shape=ring.shape,
                      dtype=ring.dtype)
    tc.set_output(op, "Out", shape=out, dtype=q.dtype)


@rule("split")
def _r_split(op, tc):
    x = tc.input_info(op, "X")
    outs = op.output("Out")
    axis = int(op.attr("axis", -1))
    sections = list(op.attr("sections", None) or ())
    for i, name in enumerate(outs):
        shape = None
        if x.shape is not None:
            ax = axis % len(x.shape)
            part = sections[i] if sections else (
                x.shape[ax] // len(outs) if x.shape[ax] > 0 else -1)
            shape = tuple(x.shape[:ax]) + (part,) + tuple(x.shape[ax + 1:])
        tc.set(name, shape=shape, dtype=x.dtype)


# the auto-vjp grads of the differentiable ops above follow the default
# grad maker's slot convention
rule("split_grad", "relu2_grad", "rms_norm_grad",
     "gated_group_rms_norm_grad", "ssm_scan_conv_grad", "ssm_scan_grad",
     "moe_route_grad", "moe_experts_grad", "moe_experts_gated_grad",
     "gqa_attention_grad", "rope_grad", "swiglu_grad", "pad_grad",
     "mla_attention_grad", "latent_window_attention_grad",
     "head_gate_grad", "mhc_pre_grad", "mhc_post_grad",
     "rope_partial_grad", "window_attention_grad",
     "gqa_flash_attention_grad", "attention_out_gate_grad",
     "mamba_scan_grad", "diff_attention_pad_grad",
     "diff_attention_out_grad")(_r_grad_mirror)
