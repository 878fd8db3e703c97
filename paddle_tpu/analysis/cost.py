"""Static FLOPs/bytes cost model over the Program IR.

Per-op ``@cost.rule`` functions ride the typecheck pass's shape
inference (``analysis/typecheck.py``): :func:`estimate` propagates
shapes/dtypes from the program's trusted roots exactly like
``check_types`` and hands each cost rule the resolved
:class:`~paddle_tpu.analysis.typecheck.VarInfo` of the op's operands.
A rule returns ``(flops, bytes)``; an op type without a rule (or with
unknown shapes) contributes zero and lands on the report's
``uncovered`` list rather than guessing — the same silence-over-noise
contract the type checker holds.

The model is cross-checked against PR 12's captured XLA
``cost_analysis()`` on compiled zoo programs
(``tests/test_perf.py::TestAnalyticalFlopsCrossCheck``), so three
accountings stay mutually anchored: the bench formula
(``models/transformer.train_flops_per_token``), these per-op rules, and
XLA itself.

Three consumers:

* ``lod.select_bucket_edges`` — :func:`row_cost_fn` fits cost as a
  function of batch rows so bucket edges minimize expected padded
  FLOPs instead of defaulting to powers of two;
* ``gen.GenScheduler`` — :meth:`GenPredictor.prefill_cost` prices a
  prompt's prefill from the bundle's prefill program, and the
  scheduler's per-iteration admission budget weighs admissions by it;
* ``parallel.pipeline_transpiler`` — stage balancing cuts at quantiles
  of :func:`op_flops` instead of its private three-op analytic table.

Registering a rule for a new op::

    from paddle_tpu.analysis import cost

    @cost.rule("my_op")
    def _my_op(op, info):
        x = info(op.input("X")[0])
        n = cost.numel(x.shape)
        if n is None:
            return None          # unknown shapes -> uncovered
        return 3 * n, cost.io_bytes(op, info)
"""

from __future__ import annotations


import numpy as np

from paddle_tpu.analysis import typecheck
from paddle_tpu.analysis.typecheck import TypeEnv, VarInfo, _UNKNOWN

__all__ = ["rule", "covered_op_types", "estimate", "op_flops",
           "numel", "io_bytes", "estimate_at", "CostReport",
           "validate_cost_report", "row_cost_fn", "REPORT_KEYS"]

_RULES = {}

_DTYPE_BYTES = {
    "float64": 8, "int64": 8, "float32": 4, "int32": 4, "float16": 2,
    "bfloat16": 2, "int16": 2, "int8": 1, "uint8": 1, "bool": 1,
}


def rule(*op_types):
    """Decorator registering ``fn(op, info) -> (flops, bytes) | None``
    as the cost rule for one or more op types.  ``info(name)`` resolves
    a variable to its inferred :class:`VarInfo`.  Returning None (or
    raising) degrades the op to the uncovered list."""

    def deco(fn):
        for t in op_types:
            _RULES[t] = fn
        return fn

    return deco


def covered_op_types():
    return set(_RULES)


def numel(shape, default_dim=1):
    """Element count of a static shape; unknown (-1) dims count as
    ``default_dim`` so batch-relative costs stay comparable; ``None``
    shape -> None."""
    if shape is None:
        return None
    n = 1
    for d in shape:
        n *= default_dim if d is None or d < 0 else int(d)
    return n


def _var_bytes(inf, default_dim=1):
    n = numel(inf.shape, default_dim)
    if n is None:
        return None
    return n * _DTYPE_BYTES.get(str(inf.dtype), 4)


def io_bytes(op, info, default_dim=1):
    """Bytes moved through the op's known-shape inputs and outputs —
    the default bytes estimate every rule can fall back on.  Unknown
    operands contribute zero (undercount, never a guess)."""
    total = 0
    for names in list(op.inputs.values()) + list(op.outputs.values()):
        for n in names:
            b = _var_bytes(info(n), default_dim)
            if b:
                total += b
    return total


# ---------------------------------------------------------------------------
# estimation walk (rides the typecheck rules for shape propagation)
# ---------------------------------------------------------------------------

class CostReport:
    """Per-program cost estimate: total flops/bytes, a per-op table,
    and the uncovered op-type list (coverage gap, not a claim)."""

    def __init__(self, total_flops, total_bytes, per_op, uncovered):
        self.total_flops = int(total_flops)
        self.total_bytes = int(total_bytes)
        self.per_op = list(per_op)
        self.uncovered = sorted(uncovered)

    def by_op_type(self):
        out = {}
        for row in self.per_op:
            agg = out.setdefault(row["op_type"],
                                 {"flops": 0, "bytes": 0, "count": 0})
            agg["flops"] += row["flops"]
            agg["bytes"] += row["bytes"]
            agg["count"] += 1
        return out

    def to_dict(self):
        return {"format": 1, "total_flops": self.total_flops,
                "total_bytes": self.total_bytes,
                "per_op": self.per_op, "uncovered": self.uncovered}

    def __repr__(self):
        return (f"CostReport(flops={self.total_flops:,}, "
                f"bytes={self.total_bytes:,}, "
                f"uncovered={len(self.uncovered)})")


REPORT_KEYS = ("format", "total_flops", "total_bytes", "per_op",
               "uncovered")


def validate_cost_report(obj):
    """Schema problems of a ``CostReport.to_dict()`` body (the
    selfcheck ``opt`` section's gate) as a list of strings."""
    problems = []
    if not isinstance(obj, dict):
        return [f"cost report must be an object, got "
                f"{type(obj).__name__}"]
    for k in REPORT_KEYS:
        if k not in obj:
            problems.append(f"missing key {k!r}")
    if problems:
        return problems
    if obj["format"] != 1:
        problems.append(f"format must be 1, got {obj['format']!r}")
    for k in ("total_flops", "total_bytes"):
        v = obj[k]
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            problems.append(f"{k} must be a non-negative integer")
    if not isinstance(obj["uncovered"], list):
        problems.append("uncovered must be a list")
    if not isinstance(obj["per_op"], list):
        return problems + ["per_op must be a list"]
    for i, row in enumerate(obj["per_op"]):
        where = f"per_op[{i}]"
        if not isinstance(row, dict):
            problems.append(f"{where}: must be an object")
            continue
        for k in ("op_index", "op_type", "flops", "bytes"):
            if k not in row:
                problems.append(f"{where}: missing key {k!r}")
                continue
            if k != "op_type" and (not isinstance(row[k], int)
                                   or isinstance(row[k], bool)
                                   or row[k] < 0):
                problems.append(f"{where}: {k} must be a non-negative "
                                f"integer")
    return problems


def estimate(program, paged_live_rows=None):
    """Walk the global block with typecheck shape propagation and price
    each op through its cost rule (unknown dims count as 1 — totals
    undercount rather than guess).  ``paged_live_rows``: the live rows
    a slot holds, where the caller knows them; the paged-attention
    rules then charge those (never more than the step's page bucket
    holds) instead of the bucket: the kernel walks the live pages, so a
    wider bucket costs a wider table feed and a jit key, not reads.
    Returns a :class:`CostReport`."""
    from paddle_tpu import profiler as _profiler
    block = program.global_block()
    diags = []
    tc_uncovered = set()
    tc = TypeEnv(block, diags, tc_uncovered)
    total_flops = 0
    total_bytes = 0
    per_op = []
    uncovered = set()
    for i, op in enumerate(block.ops):
        if op.type in ("feed", "fetch"):
            continue
        tc.op_index = i

        def info(name, _tc=tc):
            inf = _tc.info(name)
            if inf.shape is None and name:
                # fall back to the build-time declared shape (the
                # pipeline transpiler's source of truth) when dataflow
                # could not prove one
                try:
                    v = block.var(name)
                except KeyError:
                    return inf
                if v.shape is not None:
                    return VarInfo(v.shape, v.dtype)
            return inf

        info.paged_live_rows = paged_live_rows

        flops_bytes = None
        fn = _RULES.get(op.type)
        if fn is not None:
            try:
                flops_bytes = fn(op, info)
            except Exception:
                flops_bytes = None
        if flops_bytes is None:
            uncovered.add(op.type)
            flops, nbytes = 0, 0
        else:
            flops, nbytes = flops_bytes
            flops = max(int(flops), 0)
            nbytes = max(int(nbytes), 0)
        per_op.append({"op_index": i, "op_type": op.type,
                       "flops": flops, "bytes": nbytes})
        total_flops += flops
        total_bytes += nbytes
        # propagate shapes through the typecheck rule so downstream
        # cost rules see resolved operand shapes
        tfn = typecheck._RULES.get(op.type)
        if tfn is None:
            for n in op.output_arg_names:
                tc.set(n)
        else:
            try:
                tfn(op, tc)
            except Exception:
                for n in op.output_arg_names:
                    tc.set(n)
    _profiler.runtime_metrics.inc("cost.estimates")
    return CostReport(total_flops, total_bytes, per_op, uncovered)


def op_flops(op, block, default=None):
    """FLOPs of one op priced from the BLOCK's declared var shapes (the
    build-time ``infer_shape`` metadata) — the pipeline transpiler's
    stage-balancing weight.  Falls back to ``default`` (or 0) when the
    op has no rule or unknown shapes."""

    def info(name):
        if not name:
            return _UNKNOWN
        try:
            v = block.var(name)
        except KeyError:
            return _UNKNOWN
        return VarInfo(v.shape, v.dtype) if v.shape is not None \
            else _UNKNOWN

    fn = _RULES.get(op.type)
    if fn is None:
        return default
    try:
        out = fn(op, info)
    except Exception:
        return default
    if out is None:
        return default
    return max(int(out[0]), 0)


def estimate_at(program, shapes, **kwargs):
    """:func:`estimate` with the declared shapes of the vars ``shapes``
    names (feeds whose dynamic dims a caller knows) set for the walk and
    put back after it.  Callers serialize: the program is mutated
    meanwhile."""
    block = program.global_block()
    saved = {name: block.var(name).shape for name in shapes}
    try:
        for name, shape in shapes.items():
            block.var(name).shape = tuple(int(d) for d in shape)
        return estimate(program, **kwargs)
    finally:
        for name, shape in saved.items():
            block.var(name).shape = shape


def row_cost_fn(program, batch_var=None, dim=0, probe_rows=(8, 16)):
    """Fit ``flops(size)`` as an affine function of dim ``dim`` of
    ``batch_var`` (default: the program's first ``is_data`` var):
    estimate the program at two sizes and interpolate.  The returned
    callable prices a padded bucket for
    ``lod.select_bucket_edges`` — batch-size buckets probe the row
    dim, the gen prefill's prompt buckets probe the length dim."""
    block = program.global_block()
    if batch_var is None:
        for v in block.vars.values():
            if getattr(v, "is_data", False):
                batch_var = v.name
                break
    if batch_var is None:
        return lambda rows: float(rows)
    declared = block.var(batch_var).shape
    points = []
    for rows in probe_rows:
        shape = list(declared or (-1,))
        shape[dim] = int(rows)
        points.append((rows, estimate_at(
            program, {batch_var: shape}).total_flops))
    (r0, f0), (r1, f1) = points
    if r1 == r0 or f1 <= f0:
        return lambda rows: float(max(f0, 1)) * rows / max(r0, 1)
    slope = (f1 - f0) / (r1 - r0)
    const = f0 - slope * r0

    def fn(rows):
        return max(const + slope * rows, 0.0)

    return fn


# ---------------------------------------------------------------------------
# rules — the compute-dominant families first (matmul/conv), then the
# per-element families, mirroring the typecheck rule layout
# ---------------------------------------------------------------------------

def _shape(info, op, slot):
    names = op.input(slot)
    return info(names[0]).shape if names else None


@rule("mul")
def _c_mul(op, info):
    x = info(op.input("X")[0]) if op.input("X") else _UNKNOWN
    y = info(op.input("Y")[0]) if op.input("Y") else _UNKNOWN
    if x.shape is None or y.shape is None:
        return None
    xn = op.attr("x_num_col_dims", 1)
    yn = op.attr("y_num_col_dims", 1)
    m = numel(x.shape[:xn])
    k = numel(x.shape[xn:])
    n = numel(y.shape[yn:])
    if None in (m, k, n):
        return None
    return 2 * m * k * n, io_bytes(op, info)


@rule("matmul")
def _c_matmul(op, info):
    x = info(op.input("X")[0]) if op.input("X") else _UNKNOWN
    y = info(op.input("Y")[0]) if op.input("Y") else _UNKNOWN
    if x.shape is None or y.shape is None or len(x.shape) < 2 or \
            len(y.shape) < 2:
        return None
    xs, ys = list(x.shape), list(y.shape)
    if op.attr("transpose_X", False):
        xs[-1], xs[-2] = xs[-2], xs[-1]
    if op.attr("transpose_Y", False):
        ys[-1], ys[-2] = ys[-2], ys[-1]
    batch = numel(xs[:-2]) if len(xs) >= len(ys) else numel(ys[:-2])
    m, k, n = xs[-2], xs[-1], ys[-1]
    if any(d is None or d < 0 for d in (m, k, n)) or batch is None:
        return None
    return 2 * batch * m * k * n, io_bytes(op, info)


# grads of a dot: dX = dOut @ Y^T and dY = X^T @ dOut — two dots of the
# forward's geometry, so 2x the forward FLOPs (the standard 2N fwd / 4N
# bwd split behind the bench's 6N accounting)
@rule("mul_grad")
def _c_mul_grad(op, info):
    fwd = _c_mul(op, info)
    return None if fwd is None else (2 * fwd[0], io_bytes(op, info))


@rule("matmul_grad")
def _c_matmul_grad(op, info):
    fwd = _c_matmul(op, info)
    return None if fwd is None else (2 * fwd[0], io_bytes(op, info))


@rule("conv2d", "depthwise_conv2d")
def _c_conv2d(op, info):
    w = info(op.input("Filter")[0]) if op.input("Filter") else _UNKNOWN
    # on the _grad op the forward's Output arrives as an INPUT slot
    outs = op.output("Output") or op.input("Output")
    o = info(outs[0]) if outs else _UNKNOWN
    if w.shape is None or o.shape is None or len(w.shape) != 4 or \
            len(o.shape) != 4:
        return None
    co, ci, kh, kw = w.shape
    n, _, ho, wo = o.shape
    if any(d < 0 for d in (co, ci, kh, kw, ho, wo)):
        return None
    n = 1 if n < 0 else n
    return 2 * n * ho * wo * co * ci * kh * kw, io_bytes(op, info)


@rule("conv2d_grad", "depthwise_conv2d_grad")
def _c_conv2d_grad(op, info):
    fwd = _c_conv2d(op, info)
    return None if fwd is None else (2 * fwd[0], io_bytes(op, info))


@rule("scaled_dot_product_attention")
def _c_sdpa(op, info):
    q = info(op.input("Q")[0]) if op.input("Q") else _UNKNOWN
    if q.shape is None or len(q.shape) not in (3, 4):
        return None
    if len(q.shape) == 3:       # packed [B, S, H*D]: the same count
        b, s, hd = q.shape
        h, d = 1, hd
    else:
        b, h, s, d = q.shape
    if any(x < 0 for x in (h, s, d)):
        return None
    b = 1 if b < 0 else b
    return 4 * b * h * s * s * d, io_bytes(op, info)


def _paged_rows(op, info, cache_slot):
    """(slots, rows charged a slot, the pool's VarInfo) of a paged op."""
    q = info(op.input("Q")[0]) if op.input("Q") else _UNKNOWN
    pool = info(op.input(cache_slot)[0]) if op.input(cache_slot) \
        else _UNKNOWN
    pt = info(op.input("PageTable")[0]) if op.input("PageTable") \
        else _UNKNOWN
    if q.shape is None or pool.shape is None or pt.shape is None or \
            len(pool.shape) != 3 or len(pt.shape) != 2:
        return None
    hd, pl, p = pool.shape[-1], pool.shape[1], pt.shape[1]
    if any(x < 0 for x in (hd, pl)):
        return None
    s = q.shape[0] if q.shape[0] > 0 else 1
    # rows charged a slot: the step's whole page bucket (all a program
    # alone says: an upper bound, and what the XLA gather fallback
    # reads), or the LIVE rows where the caller knows them
    # (``estimate(paged_live_rows=)``): the kernel reads those whatever
    # the bucket
    rows, live = (p * pl if p > 0 else None), info.paged_live_rows
    if live is not None:
        rows = live if rows is None else min(rows, live)
    return None if rows is None else (s, rows, q, pool)


@rule("paged_attention", "shared_kv_attention")
def _c_paged_attention(op, info):
    """Paged decode attention prices the rows a step ADDRESSES, not the
    full pool: the step's page bucket ([S, P] -> S*P*page_len rows of K
    and V) from the program alone, the live rows under
    ``paged_live_rows`` (the kernel's reads follow those)."""
    found = _paged_rows(op, info, "KCache")
    if found is None:
        return None
    s, t, q, kc = found
    hd = kc.shape[-1]
    # grouped query heads: Q is wider than the pool's rows of K/V heads
    hq = q.shape[-1] if q.shape[-1] > 0 else hd
    item = _DTYPE_BYTES.get(str(kc.dtype), 4)
    # rows a slot a step: 1, or a block's L (each reads every live row;
    # the K/V pages are still read once a slot)
    rows = q.shape[1] if len(q.shape) == 3 and q.shape[1] > 0 else 1
    vc = info(op.input("VCache")[0]) if op.input("VCache") else _UNKNOWN
    if vc.shape is not None and 0 < vc.shape[-1] != hd:
        # value heads of their own width: the V pool's rows, and Out
        hv = vc.shape[-1]
        hqv = hq * hv // hd
        flops = 2 * s * t * (hq + hqv) * rows
        bytes_ = (s * t * (hd + hv) + s * rows * (hq + hqv)
                  + 2 * s * rows * (hd + hv)) * item
        return int(flops), int(bytes_)
    flops = 4 * s * t * hq * rows                # QK^T + PV per head-row
    bytes_ = (2 * s * t * hd                 # K/V pages read
              + 2 * s * rows * (hq + hd)     # q, k, v rows in + out
              + 2 * s * rows * hd) * item    # the rows' scatter (k + v)
    return int(flops), int(bytes_)


@rule("window_attention_step")
def _c_window_attention_step(op, info):
    """A window layer's decode step reads its RING, never more than the
    window's rows a slot and never more than the live rows the caller
    knows (``estimate(paged_live_rows=)``): a constant of the bundle,
    whatever the stream's length."""
    q, kr, vr = (_shape(info, op, s) for s in ("Q", "KRing", "VRing"))
    if q is None or kr is None or vr is None or len(kr) != 3 or \
            not _known(q[0], q[-1], kr[1], kr[2], vr[2]):
        return None
    s, hq = q[0], q[-1]
    rows = min(kr[1], int(op.attr("window")))
    if info.paged_live_rows is not None:
        rows = min(rows, max(int(info.paged_live_rows), 1))
    hqv = hq * vr[2] // kr[2]
    item = _DTYPE_BYTES.get(str(info(op.input("KRing")[0]).dtype), 4)
    flops = 2 * s * rows * (hq + hqv)
    bytes_ = (s * rows * (kr[2] + vr[2]) + s * (hq + hqv)
              + 2 * s * (kr[2] + vr[2])) * item
    return int(flops), int(bytes_)


@rule("paged_attention_latent")
def _c_paged_attention_latent(op, info):
    """The latent form: ONE row a token is read once and is every
    head's key and, in its leading ``v_width`` lanes, their value: 2
    FLOPs a head a lane for the scores, 2 a value lane for the
    context."""
    found = _paged_rows(op, info, "Cache")
    if found is None:
        return None
    s, t, q, pool = found
    w, h, v = pool.shape[-1], int(op.attr("n_head")), int(op.attr("v_width"))
    # rows a slot a step (a turn that carries a draft: 2): every one of
    # them scores the live rows, which are still read once
    rows = q.shape[1] if len(q.shape) == 3 and q.shape[1] > 0 else 1
    item = _DTYPE_BYTES.get(str(pool.dtype), 4)
    flops = 2 * s * rows * t * h * (w + v)
    bytes_ = (s * t * w                       # the live rows, once
              + s * rows * h * (w + v)        # queries in, context out
              + 2 * s * rows * w) * item      # the step's rows in + written
    return int(flops), int(bytes_)


def _per_element(mult):
    def fn(op, info):
        n = None
        for slot in ("X", "Logits", "Out"):
            names = op.input(slot)
            if names:
                n = numel(info(names[0]).shape)
                break
        if n is None:
            # grad ops / odd slot names: the largest known operand
            # (grads mirror their primal's geometry)
            for name in op.input_arg_names:
                m = numel(info(name).shape)
                if m is not None:
                    n = m if n is None else max(n, m)
        if n is None:
            return None
        return mult * n, io_bytes(op, info)

    return fn


#: cheap elementwise families: ~1 FLOP per element
_ELEMENTWISE_1X = (
    "relu", "abs", "square", "scale", "clip", "floor", "ceil", "round",
    "cast", "assign", "fill_zeros_like", "elementwise_add",
    "elementwise_sub", "elementwise_mul", "elementwise_div",
    "elementwise_max", "elementwise_min", "dropout", "label_smooth",
    "sum", "mean", "increment", "less_than", "less_equal",
    "greater_than", "greater_equal", "equal", "not_equal",
    "reduce_sum", "reduce_mean", "reduce_max", "reduce_min",
    "reduce_prod", "sequence_pool", "sequence_expand", "top_k",
    "accuracy", "transpose", "transpose2", "reshape", "reshape2",
    "unsqueeze", "expand", "concat", "lod_reset",
)

#: transcendental elementwise families: ~10 FLOPs per element (exp/log/
#: div chains — the conventional softmax/activation accounting)
_ELEMENTWISE_10X = (
    "sigmoid", "tanh", "exp", "log", "sqrt", "softsign", "softplus",
    "relu6", "leaky_relu", "elu", "gelu", "hard_sigmoid", "swish",
    "brelu", "pow", "reciprocal", "sin", "cos", "softmax",
    "sequence_softmax", "cross_entropy", "softmax_with_cross_entropy",
    "layer_norm", "batch_norm",
)

rule(*_ELEMENTWISE_1X)(_per_element(1))
rule(*_ELEMENTWISE_10X)(_per_element(10))

# the per-element families' grads move ~the same element counts
rule(*[t + "_grad" for t in _ELEMENTWISE_1X
       if t not in ("less_than", "less_equal", "greater_than",
                    "greater_equal", "equal", "not_equal", "accuracy",
                    "increment", "assign")])(_per_element(2))
rule(*[t + "_grad" for t in _ELEMENTWISE_10X])(_per_element(10))


@rule("lookup_table")
def _c_lookup_table(op, info):
    ids = info(op.input("Ids")[0]) if op.input("Ids") else _UNKNOWN
    w = info(op.input("W")[0]) if op.input("W") else _UNKNOWN
    n = numel(ids.shape)
    if n is None or w.shape is None or len(w.shape) != 2:
        return None
    width = w.shape[1]
    if width < 0:
        return None
    # a gather: no FLOPs, ids*width elements moved
    return 0, n * width * _DTYPE_BYTES.get(str(w.dtype), 4)


@rule("lookup_table_grad")
def _c_lookup_table_grad(op, info):
    fwd = _c_lookup_table(op, info)
    if fwd is None:
        return None
    # scatter-add back into the table: one add per gathered element
    return fwd[1] // 4, 2 * fwd[1]


@rule("merge_selected_rows")
def _c_merge_selected_rows(op, info):
    x = info(op.input("X")[0]) if op.input("X") else _UNKNOWN
    n = numel(x.shape)
    if n is None:
        return None
    # sort rows + segment-sum the values: one add per element, values
    # read once and written once (the static-shape merge keeps the full
    # row set, so the logical [height, dim] numel is the honest bound)
    item = _DTYPE_BYTES.get(str(x.dtype), 4)
    return n, 2 * n * item


@rule("get_tensor_from_selected_rows")
def _c_get_tensor_from_selected_rows(op, info):
    x = info(op.input("X")[0]) if op.input("X") else _UNKNOWN
    n = numel(x.shape)
    if n is None:
        return None
    # scatter-add into a zeroed [height, dim] tensor
    item = _DTYPE_BYTES.get(str(x.dtype), 4)
    return n, 2 * n * item


@rule("split_ids")
def _c_split_ids(op, info):
    ids = info(op.input("Ids")[0]) if op.input("Ids") else _UNKNOWN
    n = numel(ids.shape)
    if n is None:
        return None
    shards = max(len(op.output("Out")), 1)
    # one mod-compare per (id, shard) pair; padded outputs move n ids
    # per shard
    item = _DTYPE_BYTES.get(str(ids.dtype), 8)
    return n * shards, (1 + shards) * n * item


@rule("split_selected_rows")
def _c_split_selected_rows(op, info):
    x = info(op.input("X")[0]) if op.input("X") else _UNKNOWN
    n = numel(x.shape)
    if n is None:
        return None
    shards = max(len(op.output("Out")), 1)
    item = _DTYPE_BYTES.get(str(x.dtype), 4)
    return n * shards, (1 + shards) * n * item


@rule("nce")
def _c_nce(op, info):
    x = info(op.input("Input")[0]) if op.input("Input") else _UNKNOWN
    label = info(op.input("Label")[0]) if op.input("Label") else _UNKNOWN
    if x.shape is None or len(x.shape) != 2:
        return None
    rows, d = x.shape
    rows = rows if rows >= 0 else 1
    if d < 0:
        return None
    num_true = (label.shape[1] if label.shape is not None and
                len(label.shape) == 2 else 1)
    s = num_true + int(op.attr("num_neg_samples", 10))
    # per (row, sample): a D-dot + ~10-FLOP sigmoid/log chain
    return rows * s * (2 * d + 10), io_bytes(op, info)


@rule("nce_grad")
def _c_nce_grad(op, info):
    fwd = _c_nce(op, info)
    return None if fwd is None else (2 * fwd[0], io_bytes(op, info))


@rule("fill_constant", "fill", "fill_constant_batch_size_like",
      "assign_value", "uniform_random", "gaussian_random",
      "shape", "max_sequence_len", "lod_rank_table")
def _c_fill(op, info):
    outs = op.output("Out")
    o = info(outs[0]) if outs else _UNKNOWN
    n = numel(o.shape)
    if n is None:
        n = numel(op.attr("shape")) or 0
    return 0, n * _DTYPE_BYTES.get(str(o.dtype), 4)


@rule("sgd", "momentum", "adam", "adamax", "adagrad", "adadelta",
      "decayed_adagrad", "rmsprop", "ftrl", "lars_momentum")
def _c_optimizer(op, info):
    p = info(op.input("Param")[0]) if op.input("Param") else _UNKNOWN
    n = numel(p.shape)
    if n is None:
        return None
    # Adam-class updates: ~10 FLOPs per parameter (two moment EMAs,
    # bias correction, the update itself); SGD-class overcounts
    # harmlessly (the step is bandwidth-bound either way)
    return 10 * n, io_bytes(op, info)


@rule("pool2d")
def _c_pool2d(op, info):
    outs = op.output("Out") or op.input("Out")
    o = info(outs[0]) if outs else _UNKNOWN
    n = numel(o.shape)
    if n is None:
        return None
    k = op.attr("ksize", [1, 1])
    kk = int(np.prod(k)) if isinstance(k, (list, tuple)) else int(k) ** 2
    return n * max(kk, 1), io_bytes(op, info)


@rule("pool2d_grad")
def _c_pool2d_grad(op, info):
    fwd = _c_pool2d(op, info)
    return None if fwd is None else (2 * fwd[0], io_bytes(op, info))


@rule("lstm")
def _c_lstm(op, info):
    x = info(op.input("Input")[0]) if op.input("Input") else _UNKNOWN
    w = info(op.input("Weight")[0]) if op.input("Weight") else _UNKNOWN
    if x.shape is None or w.shape is None or len(w.shape) != 2:
        return None
    rows = x.shape[0] if x.shape[0] >= 0 else 1
    hidden = w.shape[0]
    if hidden < 0:
        return None
    # per row: input projection rides a separate mul op; here the
    # recurrent 4H x H dot + gate activations
    return rows * (2 * hidden * 4 * hidden + 40 * hidden), \
        io_bytes(op, info)


@rule("lstm_grad")
def _c_lstm_grad(op, info):
    fwd = _c_lstm(op, info)
    return None if fwd is None else (2 * fwd[0], io_bytes(op, info))


# -- hybrid blocks: norms, state-space mixer, expert routing ---------------

rule("relu2")(_per_element(2))
rule("rms_norm", "gated_group_rms_norm")(_per_element(10))


def _known(*dims):
    return all(d is not None and d > 0 for d in dims)


@rule("mla_attention")
def _c_mla_attention(op, info):
    """Prefill: K and V of every head expanded from the latent (2 L per
    expanded lane), then causal-less scores and context over T rows."""
    q, w = _shape(info, op, "Q"), _shape(info, op, "Wkvb")
    if q is None or w is None or len(q) != 3 or not _known(q[1], *w):
        return None
    t, h = q[1], int(op.attr("n_head"))
    qk = int(op.attr("nope_dim")) + int(op.attr("rope_dim"))
    flops = 2 * t * w[0] * w[1] + 2 * t * t * h * (qk + int(op.attr("v_dim")))
    return flops, io_bytes(op, info)


@rule("mla_attention_chunk")
def _c_mla_attention_chunk(op, info):
    """ONE CHUNK of a prompt over the slot's pages, EXPANDED: K and V of
    every head from the rows it attends (2 L a lane of W_kvb a row),
    then (nope + rope + v) lanes a head a pair, charged the rows and the
    pairs of the chunk's LAST position, the end of its page bucket
    (``C`` rows over the bucket's, less the triangle above the
    diagonal).  Bytes: the rows it attends, once, and its own; never the
    pool."""
    q, w = _shape(info, op, "Q"), _shape(info, op, "Wkvb")
    found = _paged_rows(op, info, "Cache")
    if q is None or w is None or len(q) != 3 or found is None or \
            not _known(q[1], q[2], *w):
        return None
    c, h = q[1], int(op.attr("n_head"))
    rows, pool = max(found[1], c), found[3]
    row = pool.shape[-1]
    pairs = c * (c + 1) // 2 + c * (rows - c)
    lanes = int(op.attr("nope_dim")) + int(op.attr("rope_dim")) \
        + int(op.attr("v_dim"))
    flops = 2 * rows * w[0] * w[1] + 2 * pairs * h * lanes
    item = _DTYPE_BYTES.get(str(pool.dtype), 4)
    bytes_ = (c * (q[2] + h * int(op.attr("v_dim")))
              + (rows + 2 * c) * row) * item
    return int(flops), int(bytes_)


@rule("mla_absorb")
def _c_mla_absorb(op, info):
    """One half of W_kvb against every row: 2 x latent x heads x (nope
    | v) FLOPs a row."""
    x, w = _shape(info, op, "X"), _shape(info, op, "Wkvb")
    rows = numel(x[:-1]) if x is not None else None
    if rows is None or w is None or not _known(*w):
        return None
    part = int(op.attr("v_dim" if op.attr("side") == "o" else "nope_dim"))
    return 2 * rows * w[0] * int(op.attr("n_head")) * part, \
        io_bytes(op, info)


@rule("latent_window_attention")
def _c_latent_window_attention(op, info):
    """A window layer of latent attention over ``T`` rows, ``T x
    min(window, T)`` pairs, EXPANDED: K and V of every head from the
    latent rows (2 L a lane of W_kvb a row) and (nope + rope + v) lanes
    a head a pair.  ONE CHUNK over the slot's ring expands the ``window
    - 1`` rows before it beside its own; it reads of the ring the
    window's rows and writes its own, never every slot's ring."""
    q, w, lat = (_shape(info, op, s) for s in ("Q", "Wkvb", "Latent"))
    if q is None or w is None or lat is None or len(q) != 3 or \
            not _known(q[1], q[2], lat[2], *w):
        return None
    t, h, window = q[1], int(op.attr("n_head")), int(op.attr("window"))
    lanes = int(op.attr("nope_dim")) + int(op.attr("rope_dim")) \
        + int(op.attr("v_dim"))
    attend = 2 * t * min(window, t) * h * lanes
    if not op.input("Ring"):
        return 2 * t * w[0] * w[1] + attend, io_bytes(op, info)
    item = _DTYPE_BYTES.get(str(info(op.input("Latent")[0]).dtype), 4)
    bytes_ = (t * (q[2] + h * int(op.attr("v_dim"))) + w[0] * w[1]
              + (2 * t + min(window, t)) * lat[2]) * item
    return int(2 * (t + window - 1) * w[0] * w[1] + attend), int(bytes_)


@rule("latent_window_step")
def _c_latent_window_step(op, info):
    """A window layer's decode step over its ring of latent rows: ONE row
    a token is every head's key and, in its leading ``v_width`` lanes,
    their value; never more than the window's rows a slot nor than the
    live rows the caller knows (``estimate(paged_live_rows=)``)."""
    q, ring = _shape(info, op, "Q"), _shape(info, op, "Ring")
    if q is None or ring is None or len(ring) != 3 or \
            not _known(q[0], ring[1], ring[2]):
        return None
    s, h, v = q[0], int(op.attr("n_head")), int(op.attr("v_width"))
    rows = min(ring[1], int(op.attr("window")))
    if info.paged_live_rows is not None:
        rows = min(rows, max(int(info.paged_live_rows), 1))
    item = _DTYPE_BYTES.get(str(info(op.input("Ring")[0]).dtype), 4)
    flops = 2 * s * rows * h * (ring[2] + v)
    bytes_ = (s * rows * ring[2] + s * h * (ring[2] + v)
              + 2 * s * ring[2]) * item
    return int(flops), int(bytes_)


rule("head_gate")(_per_element(12))


# hyper-connections (ops/mhc_ops.py): a wrapper's two halves, priced in
# their LEAST form (the streams read once a half)

def _mhc_sizes(op, info):
    x = _shape(info, op, "X")
    if x is None or len(x) < 2 or not _known(x[-2], x[-1]):
        return None
    item = _DTYPE_BYTES.get(str(info(op.input("X")[0]).dtype), 4)
    return numel(x[:-2]), x[-2], x[-1], item


@rule("mhc_pre")
def _c_mhc_pre(op, info):
    """The statistic (2 a value), the product with ``phi`` (2 n (n + 2)
    a value), the aggregate (2 a value) and ``sinkhorn_iters`` rounds of
    4 n^2 a row; reads the streams and ``phi``, writes ``u`` and the
    float32 coefficients."""
    found = _mhc_sizes(op, info)
    if found is None:
        return None
    rows, n, c, item = found
    coef = n * (n + 2)
    flops = rows * (n * c * (4 + 2 * coef)
                    + 4 * n * n * int(op.attr("sinkhorn_iters")))
    bytes_ = rows * (n * c + c) * item + 4 * (n * c * coef + rows * coef)
    return int(flops), int(bytes_)


@rule("mhc_post")
def _c_mhc_post(op, info):
    """``H_res x + H_post^T y``: 2 n a value of the n streams, + 2; reads
    the streams and ``y``, writes the streams."""
    found = _mhc_sizes(op, info)
    if found is None:
        return None
    rows, n, c, item = found
    return (int(rows * n * c * (2 * n + 2)),
            int(rows * ((2 * n + 1) * c * item + 4 * n * (n + 1))))


# learned sparse attention (ops/dsa_ops.py): what grows with the SQUARE of
# a prefill's rows beside the attention itself

def _dsa_projection_flops(op, info, rows):
    wq, wk, ww = (_shape(info, op, s) for s in ("Wq", "Wk", "Ww"))
    if None in (wq, wk, ww) or not _known(*wq, *wk, *ww):
        return None
    return 2 * rows * (wq[0] * wq[1] + wk[0] * wk[1] + ww[0] * ww[1])


@rule("dsa_index")
def _c_dsa_index(op, info):
    """The indexer's three projections a row, and past ``top_k`` rows
    every index head's product of every query row with every key row."""
    x, wk = _shape(info, op, "X"), _shape(info, op, "Wk")
    if x is None or len(x) != 3 or not _known(x[1]) or wk is None:
        return None
    t = x[1]
    flops = _dsa_projection_flops(op, info, t)
    if flops is None:
        return None
    if t > int(op.attr("top_k")):
        flops += 2 * t * t * int(op.attr("n_head")) * wk[-1]
    return flops, io_bytes(op, info)


@rule("dsa_index_paged", "dsa_index_chunk")
def _c_dsa_index_paged(op, info):
    """The decode step's: the projections a slot, then every index head
    over the rows a slot ADDRESSES (the page bucket; the live rows where
    the caller knows them), past ``top_k`` rows.  ONE CHUNK of a prompt:
    the same with the chunk's rows for slots, every one over the page
    bucket's rows."""
    x = _shape(info, op, "X")
    pool = info(op.input("Cache")[0]) if op.input("Cache") else _UNKNOWN
    pt = _shape(info, op, "PageTable")
    chunk = op.type == "dsa_index_chunk"
    if x is None or pool.shape is None or pt is None or \
            not _known(x[1 if chunk else 0], pool.shape[1], pool.shape[2]):
        return None
    s, d_idx = x[1 if chunk else 0], pool.shape[2]
    flops = _dsa_projection_flops(op, info, s)
    if flops is None:
        return None
    rows = pt[1] * pool.shape[1] if pt[1] > 0 else None
    if info.paged_live_rows is not None and not chunk:
        rows = info.paged_live_rows if rows is None \
            else min(rows, info.paged_live_rows)
    item = _DTYPE_BYTES.get(str(pool.dtype), 4)
    bytes_ = io_bytes(op, info) - (_var_bytes(pool, 1) or 0) * 2
    if rows is not None and rows > int(op.attr("top_k")):
        flops += 2 * s * rows * int(op.attr("n_head")) * d_idx
        # (a chunk's rows are one slot's: the bucket's rows once)
        bytes_ += (1 if chunk else s) * rows * d_idx * item
    return int(flops), int(max(bytes_, 0))


@rule("dsa_select")
def _c_dsa_select(op, info):
    """An exact top-k without a sort: 32 counting passes over the
    scores and a running count of the ties (about 70 operations a
    score), nothing up to ``top_k`` rows."""
    sc = _shape(info, op, "Scores")
    n = numel(sc) if sc is not None else None
    if n is None:
        return None
    flops = 70 * n if sc[-1] > int(op.attr("top_k")) else 0
    return flops, io_bytes(op, info)


rule("pad", "pad_grad")(_per_element(1))
rule("swiglu")(_per_element(6))
rule("rope", "rope_partial")(_per_element(6))


@rule("moe_experts_gated")
def _c_moe_experts_gated(op, info):
    """A ROUTED product: a row goes through the experts it chose among
    the held ones, on average ``top_k x held / experts`` of them, which
    only the router knows; charged here as ``top_k`` gated experts a row
    (an upper bound: every choice landing on this share), 6 x features x
    hidden FLOPs each.  Bytes: the operands, every held expert once."""
    x, wg = _shape(info, op, "X"), _shape(info, op, "Wg")
    idx = _shape(info, op, "TopkIdx")
    rows = numel(x[:-1]) if x is not None else None
    if rows is None or wg is None or len(wg) != 3 or not _known(*wg) \
            or idx is None or not _known(idx[-1]):
        return None
    per_row = min(idx[-1], wg[0])
    return 6 * rows * per_row * wg[1] * wg[2], io_bytes(op, info)


@rule("gqa_attention")
def _c_gqa_attention(op, info):
    q = _shape(info, op, "Q")
    if q is None or len(q) != 3 or not _known(q[1], q[2]):
        return None
    return 4 * q[1] * q[1] * q[2], io_bytes(op, info)


def _c_prefill_attention(op, info):
    """Grouped attention over one prompt with key and value heads of
    their own widths: ``T x T / 2`` pairs under the causal mask, ``T x
    window`` inside a band.  ONE CHUNK of a prompt over the slot's pages
    (``gqa_flash_attention_chunk``) is charged the pairs of its LAST
    position, the end of its page bucket: ``T`` rows over the bucket's
    rows, less the triangle above the diagonal."""
    q, k, v = (_shape(info, op, s) for s in ("Q", "K", "V"))
    if q is None or k is None or v is None or len(q) != 3 or \
            not _known(q[1], q[2], k[2], v[2]):
        return None
    t, window = q[1], int(op.attr("window", 0))
    pairs = t * min(window, t) if window else t * (t + 1) // 2
    hqv = q[2] * v[2] // k[2]
    if not op.input("KCache") and not op.input("KRing"):
        return 2 * pairs * (q[2] + hqv), io_bytes(op, info)
    # a chunk reads of its caches the rows it attends and writes its
    # own, never the whole pool or every slot's ring
    rows = min(window, t) if window else 0
    found = _paged_rows(op, info, "KCache") if not window else None
    if found is not None and found[1] > t:
        rows = found[1]
        pairs += t * (rows - t)
    item = _DTYPE_BYTES.get(str(info(op.input("K")[0]).dtype), 4)
    bytes_ = (t * (q[2] + hqv) + (2 * t + rows) * (k[2] + v[2])) * item
    return 2 * pairs * (q[2] + hqv), int(bytes_)


rule("gqa_flash_attention", "gqa_flash_attention_chunk",
     "window_attention")(_c_prefill_attention)


@rule("ssm_scan_conv", "ssm_update_conv")
def _c_ssm_conv(op, info):
    x, w = _shape(info, op, "X"), _shape(info, op, "W")
    n = numel(x)
    if n is None or w is None or not _known(w[0]):
        return None
    return 2 * n * w[0] + 10 * n, io_bytes(op, info)


rule("ssm_chunk_conv")(_c_ssm_conv)


def _kda_dims(op, info):
    """``(rows, heads, head width)`` of a KDA op from its X (q | k | v)."""
    x = _shape(info, op, "X")
    rows = numel(x[:-1]) if x is not None else None
    if rows is None or not _known(x[-1]):
        return None
    h = int(op.attr("n_head"))
    return rows, h, x[-1] // (3 * h)


@rule("kda_scan")
def _c_kda_scan(op, info):
    """The chunk-wise form in blocks of 64 rows: per row and head the
    block's two pair matrices (2 * 2 q d), the solve against v | k (q
    (d + d)), the three products with the carried state (3 * 2 d d) and
    the block's own with its pseudo-values (2 q d)."""
    dims = _kda_dims(op, info)
    if dims is None:
        return None
    rows, h, d = dims
    q = 64      # ops/kda_ops.BLOCK
    return rows * h * (8 * q * d + 6 * d * d), io_bytes(op, info)


@rule("kda_update")
def _c_kda_update(op, info):
    """One step: the whole state is read and written (io_bytes counts
    State and StateOut), ~8 FLOPs an element of it."""
    n = numel(_shape(info, op, "State"))
    return None if n is None else (8 * n, io_bytes(op, info))


rule("kda_gated_norm")(_per_element(12))


@rule("mamba_scan")
def _c_mamba_scan(op, info):
    """The recurrence a row: ~9 FLOPs an element of the [N, channels]
    state (the decay's exponential counted as one)."""
    x, a = _shape(info, op, "X"), _shape(info, op, "ALog")
    rows = numel(x[:-1]) if x is not None else None
    if rows is None or a is None or not _known(a[0], a[1]):
        return None
    return 9 * rows * a[0] * a[1], io_bytes(op, info)


@rule("mamba_update")
def _c_mamba_update(op, info):
    """One step: the whole state is read and written (io_bytes counts
    State and StateOut), ~9 FLOPs an element of it."""
    n = numel(_shape(info, op, "State"))
    return None if n is None else (9 * n, io_bytes(op, info))


rule("diff_attention_pad", "diff_attention_pad_grad")(_per_element(1))
rule("diff_attention_out", "diff_attention_out_grad")(_per_element(6))


@rule("paged_kv_write")
def _c_paged_kv_write(op, info):
    """The chunk's rows once in and once out; the pools are updated in
    place and not read."""
    k, v = _shape(info, op, "K"), _shape(info, op, "V")
    if k is None or v is None or numel(k) is None or numel(v) is None:
        return None
    item = _DTYPE_BYTES.get(str(info(op.input("KCache")[0]).dtype), 4)
    return 0, int(2 * (numel(k) + numel(v)) * item)
rule("attention_out_gate", "attention_out_gate_grad")(_per_element(6))


@rule("ssm_scan")
def _c_ssm_scan(op, info):
    """The chunked algorithm: per row, per head, C.B and the masked
    product over the chunk's rows (2 q n / heads-per-group + 2 q p), the
    chunk's own state and the read of the carried one (2 * 2 p n)."""
    x = _shape(info, op, "X")
    if x is None or len(x) != 3 or not _known(x[1]):
        return None
    h, p = int(op.attr("n_head")), int(op.attr("head_dim"))
    g, n = int(op.attr("n_groups")), int(op.attr("state"))
    q = min(int(op.attr("chunk", 128)), x[1])
    rows = (x[0] if x[0] > 0 else 1) * x[1]
    flops = rows * (2 * q * n * g + h * (2 * q * p + 4 * p * n))
    return flops, io_bytes(op, info)


@rule("ssm_update")
def _c_ssm_update(op, info):
    """One step: the whole state is read and written (io_bytes counts
    State and StateOut), ~6 FLOPs an element of it."""
    st = _shape(info, op, "State")
    n = numel(st)
    return None if n is None else (6 * n, io_bytes(op, info))


@rule("moe_route")
def _c_moe_route(op, info):
    x, w = _shape(info, op, "X"), _shape(info, op, "W")
    rows = numel(x[:-1]) if x is not None else None
    if rows is None or w is None or not _known(*w):
        return None
    return 2 * rows * w[0] * w[1] + 10 * rows * w[1], io_bytes(op, info)


@rule("moe_experts")
def _c_moe_experts(op, info):
    """Every row through every HELD expert (the lowering's dense
    product): 4 * latent * hidden FLOPs a row an expert."""
    x, w1 = _shape(info, op, "X"), _shape(info, op, "W1")
    rows = numel(x[:-1]) if x is not None else None
    if rows is None or w1 is None or len(w1) != 3 or not _known(*w1):
        return None
    return 4 * rows * w1[0] * w1[1] * w1[2], io_bytes(op, info)


def _twice(fwd):
    """A grad op built by the default grad maker carries the forward's
    slots: about twice the forward's FLOPs."""
    def fn(op, info):
        cost = fwd(op, info)
        return None if cost is None else (2 * cost[0], io_bytes(op, info))
    return fn


rule("split", "split_grad")(_per_element(1))
rule("relu2_grad")(_per_element(2))
rule("rms_norm_grad", "gated_group_rms_norm_grad")(_per_element(10))
rule("gqa_attention_grad")(_twice(_c_gqa_attention))
rule("gqa_flash_attention_grad", "window_attention_grad")(
    _twice(_c_prefill_attention))
rule("rope_partial_grad")(_per_element(6))
rule("ssm_scan_conv_grad")(_twice(_c_ssm_conv))
rule("ssm_scan_grad")(_twice(_c_ssm_scan))
rule("mamba_scan_grad")(_twice(_c_mamba_scan))
rule("moe_route_grad")(_twice(_c_moe_route))
rule("moe_experts_grad")(_twice(_c_moe_experts))
rule("moe_experts_gated_grad")(_twice(_c_moe_experts_gated))
rule("mla_attention_grad")(_twice(_c_mla_attention))
rule("latent_window_attention_grad")(_twice(_c_latent_window_attention))
rule("head_gate_grad")(_per_element(12))
rule("mhc_pre_grad")(_twice(_c_mhc_pre))
rule("mhc_post_grad")(_twice(_c_mhc_post))
rule("swiglu_grad", "rope_grad")(_per_element(6))
