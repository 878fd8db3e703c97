"""IR-level pipeline partitioning: split a Program's op list into P
balanced stages and run it as a GPipe pipeline (VERDICT r3 item 3 —
completes ``parallel/pipeline.py``'s primitive into a framework feature).

The reference has no pipeline parallelism; SURVEY.md §2.8 names PP as a
beyond-reference row.  Design:

* ``split_program``: walk the global block's ops in program order,
  weight them with the same analytic FLOP model the benchmarks use
  (conv/matmul dominate), and cut at the P-quantiles of cumulative
  cost.  Any cut is legal: everything produced before the cut and
  consumed after it becomes part of the boundary *carrier*.  Ops that
  carry sub-blocks (while/cond/DynamicRNN) are atomic — they are never
  split across a cut, and their lowerings recurse into their sub-block
  the same way the executor's ``lower_block`` does.
* Stages are NON-homogeneous (different ops, params, shapes).  Each
  stage's parameters are flat-packed into TYPED LANES — one flat vector
  per dtype class (``f32``, ``bf16``, ``i32``) — padded to a common
  per-lane length and stacked [P, L_lane], sharded over the ``pipe``
  mesh axis so each device stores only its own stage's weights.  Inside
  ``shard_map`` a ``lax.switch`` on the device's stage index unpacks
  its slices and runs its stage's traced IR ops.
* Activations/feeds cross boundaries the same way: one flat carrier per
  lane of uniform (max-boundary) length.  Integer values ride the i32
  lane EXACTLY (the r4 design packed them as f32, silently rounding
  ids >= 2^24; host-side int64 values beyond int32 range are rejected
  loudly rather than wrapped); bf16 values keep bf16 width on the
  wire; floats ride f32.  Lanes that no boundary/parameter uses are dropped from the
  pytree, so ``jax.grad`` over the packed params needs ``allow_int``
  only when an integer parameter actually exists.
* Microbatches feed STAGE 0 ONLY (the refinement pipeline.py:70-73
  names): the per-lane [M, L] ingest tensors are sharded over ``pipe``
  in contiguous blocks of B = M/P; after every B ticks the local blocks
  rotate one hop toward stage 0 on the ICI ring, arriving exactly when
  stage 0 needs them — devices never hold the full microbatch set.
* The whole schedule is differentiable: ``jax.grad`` w.r.t. the packed
  lane dict yields the reverse pipeline, and ``unpack_grads`` scatters
  it back to named parameters (parameters used by several stages get
  their contributions summed).
* AMP: the stage branches honor the program's mixed-precision flag
  (``Program.amp``), so a bf16-AMP program pipelines with the same op-
  level cast discipline as the executor.  A boundary cut inserts an
  exact bf16→f32→bf16 round-trip for values that are bf16 at runtime
  (value-preserving; see test_pipeline_transpiler.py AMP parity).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.framework import Parameter

from jax import shard_map

__all__ = ["pipeline_transpiler", "PipelinedProgram"]

_SKIP = ("feed", "fetch")

_LANE_DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16, "i32": jnp.int32}


def _lane_of(dtype):
    """Which carrier lane a dtype rides: bf16 keeps its width, other
    floats ride f32 (f16 upcast losslessly; f64 is already f32 under
    JAX's default x64-off), ints/bools ride i32 exactly."""
    name = str(np.dtype(dtype).name) if not isinstance(dtype, str) \
        else dtype
    if name == "bfloat16":
        return "bf16"
    if name.startswith("float"):
        return "f32"
    return "i32"


def _np_dtype(dtype):
    """np dtype for restore; 'bfloat16' restores via jnp."""
    if str(dtype) == "bfloat16":
        return jnp.bfloat16
    return np.dtype(dtype)


def _op_cost(op, block):
    """Per-op stage-balancing weight: the shared static cost model
    (``analysis/cost.op_flops`` — the same per-op rules the optimizer
    pipeline and GenScheduler admission ride, replacing this module's
    former private three-op table, so the accountings can't drift).
    Sub-block ops (while/cond/DynamicRNN) are atomic: weighed by their
    body so the quantile cuts see the FLOPs inside."""
    from paddle_tpu.analysis import cost as _cost
    flops = _cost.op_flops(op, block, default=0)
    inner = sum(_op_cost(sub, blk)
                for blk in _sub_blocks(op) for sub in blk.ops)
    return 1 + flops + inner


def _all_input_names(op, recurse=False):
    names = [n for vs in op.inputs.values() for n in vs]
    if recurse:
        for blk in _sub_blocks(op):
            for sub in blk.ops:
                names += _all_input_names(sub, recurse=True)
    return names


def _all_output_names(op, recurse=False):
    names = [n for vs in op.outputs.values() for n in vs]
    if recurse:
        for blk in _sub_blocks(op):
            for sub in blk.ops:
                names += _all_output_names(sub, recurse=True)
    return names


def _sub_blocks(op):
    return [a for a in op.attrs.values()
            if a.__class__.__name__ == "Block"]


def split_program(program, n_stages, feed_names, fetch_names):
    """Balanced cut points + per-stage op/param/boundary metadata."""
    block = program.global_block()
    ops = [op for op in block.ops if op.type not in _SKIP]

    costs = [_op_cost(op, block) for op in ops]
    total = float(sum(costs))
    # cut after reaching each quantile of cumulative cost
    cuts, acc, next_q = [], 0.0, 1
    for i, c in enumerate(costs):
        acc += c
        if next_q < n_stages and acc >= total * next_q / n_stages:
            cuts.append(i + 1)
            next_q += 1
    while len(cuts) < n_stages - 1:   # degenerate tails
        cuts.append(len(ops))
    stage_ops = []
    lo = 0
    for cut in cuts + [len(ops)]:
        stage_ops.append(ops[lo:cut])
        lo = cut

    def is_param(name):
        v = block.var(name) if name in block.vars else None
        return v is not None and (isinstance(v, Parameter)
                                  or getattr(v, "persistable", False))

    produced_by = {}
    for s, sops in enumerate(stage_ops):
        for op in sops:
            for n in _all_output_names(op):
                produced_by.setdefault(n, s)

    # sub-block ops are atomic; their inner reads of outer params/vars
    # count toward the owning stage (recurse=True)
    stage_params = []
    for sops in stage_ops:
        names = []
        for op in sops:
            for n in _all_input_names(op, recurse=True):
                if is_param(n) and n not in names:
                    names.append(n)
        stage_params.append(names)

    # boundary b carries everything still needed past it and produced
    # before it: inputs of stage >= b ops, plus fetch targets already
    # produced (they must ride through to the final boundary); feeds
    # count as produced before stage 0
    feed_set = set(feed_names)
    boundaries = []
    for b in range(n_stages + 1):
        need = set()
        for n in fetch_names:
            src = produced_by.get(n)
            # a fetched feed (src None) must ride EVERY boundary — no
            # stage re-produces it, wherever its consumers sit
            if b == n_stages or (src is not None and src < b) or \
                    (src is None and n in feed_set):
                need.add(n)
        for s in range(b, n_stages):
            for op in stage_ops[s]:
                for n in _all_input_names(op, recurse=True):
                    if is_param(n):
                        continue
                    src = produced_by.get(n)
                    if (src is None and n in feed_set) or \
                            (src is not None and src < b):
                        need.add(n)
        boundaries.append(sorted(need))

    # carriers are flat dense vectors; a TensorArray (or reader/channel)
    # cannot cross a cut.  The cut placement is cost-driven, so reject
    # loudly with the remedy instead of crashing in _Layout.pack.
    for b, names in enumerate(boundaries):
        for n in names:
            v = block.var(n) if n in block.vars else None
            vtype = getattr(v, "type", None)
            if vtype in ("tensor_array", "reader", "channel"):
                where = ("the feed carrier" if b == 0 else
                         "the fetch carrier" if b == len(boundaries) - 1
                         else f"the cut before stage {b}")
                raise ValueError(
                    f"pipeline_transpiler: {where} would carry {n!r} "
                    f"(a {vtype}), which cannot ride a flat carrier; "
                    f"keep its producers and consumers in one stage "
                    f"and fetch/feed dense tensors only — fewer "
                    f"stages, or hoist the control-flow region so the "
                    f"quantile cut lands outside it")
    return block, stage_ops, stage_params, boundaries


class _Layout:
    """Typed flat-packing layout for a list of named tensors: one flat
    vector per dtype lane (f32 / bf16 / i32); ``pack`` -> {lane: vec},
    ``unpack`` restores original dtypes/shapes."""

    def __init__(self, names, shapes, dtypes):
        self.names = list(names)
        self.shapes = [tuple(s) for s in shapes]
        self.dtypes = list(dtypes)
        self.lanes = [_lane_of(d) for d in self.dtypes]
        self.sizes = [int(np.prod(s)) if s else 1 for s in self.shapes]
        self.offsets = []          # per-name offset within its lane
        self.lengths = {}          # lane -> total length
        for lane, size in zip(self.lanes, self.sizes):
            self.offsets.append(self.lengths.get(lane, 0))
            self.lengths[lane] = self.lengths.get(lane, 0) + size

    @staticmethod
    def _check_i32_range(name, v):
        """Range-check any CONCRETE int64-typed value before it rides
        the i32 lane — a >= 2^31 id must fail loudly, not wrap.  Keyed
        on the value's DTYPE, not ``isinstance(np.ndarray)``: numpy
        scalars and x64-enabled jax arrays are int64-typed without
        being ndarrays, and must not bypass the guard (ADVICE r5).
        Abstract tracers are exempt: they cannot be concretized, and
        under JAX's default x64-off no tracer is int64 anyway."""
        dt = getattr(v, "dtype", None)
        if dt is None or np.dtype(dt) != np.int64 \
                or isinstance(v, jax.core.Tracer):
            return
        a = np.asarray(v)
        if a.size and (a.max() > np.iinfo(np.int32).max or
                       a.min() < np.iinfo(np.int32).min):
            raise ValueError(
                f"pipeline_transpiler: {name!r} holds int64 values "
                f"outside int32 range; the i32 carrier lane cannot "
                f"carry them exactly")

    def pack(self, values, lanes):
        """values {name: array} -> {lane: flat vec} over ``lanes``;
        int64 values are range-guarded by :meth:`_check_i32_range`
        (the static half of the same contract is the analyzer's PTA010
        int64-lane lint, ``analysis.check_pipeline_carriers``)."""
        flats = {lane: [] for lane in lanes}
        for n, lane in zip(self.names, self.lanes):
            v = values[n]
            if lane == "i32":
                self._check_i32_range(n, v)
            flats[lane].append(
                jnp.ravel(v).astype(_LANE_DTYPES[lane]))
        return {
            lane: (jnp.concatenate(fs) if fs
                   else jnp.zeros((0,), _LANE_DTYPES[lane]))
            for lane, fs in flats.items()}

    def unpack(self, vecs):
        """{lane: vec} -> {name: array} with original dtype/shape."""
        out = {}
        for n, shape, dtype, lane, off, size in zip(
                self.names, self.shapes, self.dtypes, self.lanes,
                self.offsets, self.sizes):
            out[n] = jax.lax.slice(vecs[lane], (off,), (off + size,)) \
                .reshape(shape).astype(_np_dtype(dtype))
        return out


def _pad_lanes(vecs, lengths):
    return {
        lane: (jnp.pad(v, (0, lengths[lane] - v.shape[0]))
               if v.shape[0] < lengths[lane] else v)
        for lane, v in vecs.items()}


class PipelinedProgram:
    """A Program split into P pipeline stages; call :meth:`run_fn` (or
    differentiate through it) with per-microbatch feeds."""

    def __init__(self, program, n_stages, feed_names, fetch_names, mesh,
                 axis="pipe"):
        from paddle_tpu.ops import registry as _registry
        from paddle_tpu.executor import _amp_enabled
        self._registry = _registry
        self.mesh = mesh
        self.axis = axis
        self.n_stages = n_stages
        self.feed_names = list(feed_names)
        self.fetch_names = list(fetch_names)
        self.amp = _amp_enabled(program)
        # post-transpile contract: the program must be structurally
        # well-formed BEFORE it is cut into stages (a bad rewrite fails
        # here, named, instead of inside the shard_map trace), and no
        # int64 constant provably outside int32 range may cross a stage
        # boundary on the i32 carrier lane (the static half of
        # _Layout.pack's runtime range guard)
        from paddle_tpu.analysis import (AnalysisResult,
                                         check_pipeline_carriers,
                                         check_stage_set,
                                         verify_transpiled)
        verify_transpiled(program, where="pipeline_transpiler")
        (self.block, self.stage_ops, self.stage_param_names,
         self.boundaries) = split_program(program, n_stages, feed_names,
                                          fetch_names)
        check_pipeline_carriers(self.block, self.boundaries)
        # cross-stage contract (analysis/distributed.py): every consumed
        # upstream value rides its boundary carrier, and the stages —
        # run as lax.switch branches on the SAME devices — emit matching
        # collective sequences (a branch-local collective its peers
        # don't run would deadlock the mesh: PTA011/PTA015)
        AnalysisResult(check_stage_set(
            self.block, self.stage_ops, self.boundaries,
            feed_names=self.feed_names)) \
            .raise_on_errors(where="pipeline_transpiler")

        def check_rng(op):
            opdef = _registry.lookup(op.type)
            if opdef is not None and opdef.uses_rng:
                raise ValueError(
                    f"pipeline_transpiler: op {op.type!r} uses the "
                    f"rng stream; run with dropout/sampling disabled "
                    f"in the pipelined region")
            for blk in _sub_blocks(op):
                for sub in blk.ops:
                    check_rng(sub)

        for sops in self.stage_ops:
            for op in sops:
                check_rng(op)

    # -- layouts (need var shapes; resolved against scope values) -------
    def _var_meta(self, name, scope_vals):
        v = self.block.var(name) if name in self.block.vars else None
        if name in scope_vals:
            arr = np.asarray(scope_vals[name])
            return arr.shape, arr.dtype
        if v is None or v.shape is None:
            raise ValueError(f"pipeline_transpiler: no shape for {name!r}")
        shape = tuple(int(d) for d in v.shape)
        return shape, v.dtype

    def build(self, scope, microbatch_feeds):
        """Finalize layouts from the startup-initialized ``scope`` and a
        SAMPLE microbatch feed dict (fixes the microbatch shapes)."""
        sample = {k: np.asarray(v) for k, v in microbatch_feeds.items()}
        self._param_layouts = []
        param_values = []     # local: only needed to build packed_params
        for names in self.stage_param_names:
            vals = {n: np.asarray(scope.find_var(n)) for n in names}
            lay = _Layout(names, [vals[n].shape for n in names],
                          [vals[n].dtype for n in names])
            self._param_layouts.append(lay)
            param_values.append(vals)

        self._carrier_layouts = []
        for names in self.boundaries:
            shapes, dtypes = [], []
            for n in names:
                if n in sample:
                    shapes.append(sample[n].shape)
                    dtypes.append(sample[n].dtype)
                else:
                    s, d = self._var_meta(n, {})
                    shapes.append(s)
                    dtypes.append(d)
            self._carrier_layouts.append(_Layout(names, shapes, dtypes))

        # active lanes: fixed pytree structure across boundaries/stages
        self.carrier_lanes = tuple(
            lane for lane in _LANE_DTYPES
            if any(lay.lengths.get(lane) for lay in self._carrier_layouts))
        if not self.carrier_lanes:
            self.carrier_lanes = ("f32",)
        self.param_lanes = tuple(
            lane for lane in _LANE_DTYPES
            if any(lay.lengths.get(lane) for lay in self._param_layouts))
        if not self.param_lanes:
            self.param_lanes = ("f32",)
        self.carrier_len = {
            lane: max(lay.lengths.get(lane, 0)
                      for lay in self._carrier_layouts)
            for lane in self.carrier_lanes}
        self.param_len = {
            lane: max(lay.lengths.get(lane, 0)
                      for lay in self._param_layouts)
            for lane in self.param_lanes}

        # packed parameter buffers {lane: [P, L_lane]}
        rows = {lane: [] for lane in self.param_lanes}
        for lay, vals in zip(self._param_layouts, param_values):
            vecs = lay.pack(vals, self.param_lanes)
            padded = _pad_lanes(vecs, self.param_len)
            for lane in self.param_lanes:
                rows[lane].append(np.asarray(padded[lane]))
        self.packed_params = {
            lane: jnp.asarray(np.stack(rows[lane]))
            for lane in self.param_lanes}
        return self

    def pack_microbatch(self, feed):
        """feed dict -> {lane: [L_lane]} carrier for boundary 0.

        Values pass to ``pack`` RAW (numpy) — converting to jnp first
        would silently wrap int64 to int32 under x64-off before the
        range guard could fire."""
        lay = self._carrier_layouts[0]
        vecs = lay.pack({k: np.asarray(v) if not hasattr(v, "aval")
                         else v for k, v in feed.items()},
                        self.carrier_lanes)
        return _pad_lanes(vecs, self.carrier_len)

    def stack_microbatches(self, feeds):
        """[feed dicts] -> {lane: [M, L_lane]} ingest tensors."""
        packed = [self.pack_microbatch(f) for f in feeds]
        return {lane: jnp.stack([p[lane] for p in packed])
                for lane in self.carrier_lanes}

    def unpack_outputs(self, vecs):
        """One final-boundary carrier {lane: [L_lane]} -> fetch dict."""
        lay = self._carrier_layouts[-1]
        return lay.unpack({lane: vecs[lane][:lay.lengths.get(lane, 0)]
                           for lane in self.carrier_lanes})

    def select_fetch(self, outs, name):
        """{lane: [M, L]} stacked outputs -> [M, ...] values of one
        fetch target (lane-aware replacement for manual offset math)."""
        lay = self._carrier_layouts[-1]
        i = lay.names.index(name)
        lane, off, size = lay.lanes[i], lay.offsets[i], lay.sizes[i]
        sl = outs[lane][:, off:off + size]
        return sl.reshape((sl.shape[0],) + lay.shapes[i]) \
            .astype(_np_dtype(lay.dtypes[i]))

    def unpack_grads(self, packed_grads):
        """{lane: [P, L]} grads -> {param_name: grad} (multi-stage
        placements summed; integer-lane cotangents — float0 under
        ``jax.grad(..., allow_int=True)`` — are skipped)."""
        out = {}
        for s, lay in enumerate(self._param_layouts):
            for n, shape, dtype, lane, off, size in zip(
                    lay.names, lay.shapes, lay.dtypes, lay.lanes,
                    lay.offsets, lay.sizes):
                if lane == "i32":
                    continue
                g = packed_grads.get(lane)
                if g is None:
                    continue
                ga = np.asarray(g[s])
                if ga.dtype == object or ga.size == 0:  # float0 / empty
                    continue
                v = ga[off:off + size].reshape(shape)
                out[n] = out.get(n, 0) + np.asarray(v, np.float64)
        return out

    # -- stage functions ------------------------------------------------
    def _stage_branch(self, s):
        """carrier {lane: [L]} -> carrier {lane: [L]} for stage ``s``,
        given its packed param vectors; traced IR ops via the op
        registry (sub-block ops recurse through executor.lower_block)."""
        in_lay = self._carrier_layouts[s]
        out_lay = self._carrier_layouts[s + 1]
        p_lay = self._param_layouts[s]
        ops = self.stage_ops[s]
        registry = self._registry
        block = self.block
        amp = self.amp
        carrier_lanes = self.carrier_lanes
        carrier_len = self.carrier_len

        def branch(pvecs, carrier):
            env = p_lay.unpack(
                {lane: pvecs.get(lane, jnp.zeros((0,),
                                                 _LANE_DTYPES[lane]))
                 [:p_lay.lengths.get(lane, 0)]
                 for lane in set(p_lay.lanes)})
            env.update(in_lay.unpack(
                {lane: carrier[lane][:in_lay.lengths.get(lane, 0)]
                 for lane in set(in_lay.lanes)}))
            from paddle_tpu.executor import lower_block
            aux = {"rng_counter": 0, "amp": amp, "interpret": False,
                   "lod": {}, "block": block, "lower_block": lower_block}
            for op in ops:
                opdef = registry.resolve_lowering(op.type)
                ctx = registry.LowerContext(op, env, block, rng_key=None,
                                            training=True, aux=aux)
                opdef.lower(ctx)
                env.update(ctx.outputs)
            out = out_lay.pack(env, carrier_lanes)
            return _pad_lanes(out, carrier_len)

        return branch

    # -- the pipelined schedule ----------------------------------------
    def run_fn(self, data_axis=None):
        """Returns ``fn(packed_params {lane: [P, Lp]}, xs {lane: [M, L]})
        -> {lane: [M, L]}`` (final-boundary carriers per microbatch),
        jit/grad-able (``allow_int=True`` if an integer param exists).

        ``data_axis``: optional mesh axis name for dp x pp composition —
        microbatches are sharded over ``(data_axis, pipe_axis)`` and each
        data row runs an independent pipeline over its own microbatch
        block (params replicated across rows); outputs come back stacked
        in global microbatch order."""
        P = self.n_stages
        axis = self.axis
        mesh = self.mesh
        branches = [self._stage_branch(s) for s in range(P)]
        lanes = self.carrier_lanes
        L = self.carrier_len

        def per_device(params_local, xs_local):
            my_stage = jax.lax.axis_index(axis)
            pvecs = {lane: params_local[lane][0]
                     for lane in params_local}
            B = next(iter(xs_local.values())).shape[0]  # M / P block
            M = B * P
            n_ticks = M + P - 1
            outer = math.ceil(n_ticks / B)
            perm_fwd = [(i, (i + 1) % P) for i in range(P)]
            perm_ingest = [((i + 1) % P, i) for i in range(P)]

            def run_stage(carrier):
                return jax.lax.switch(
                    my_stage, [lambda c, b=b: b(pvecs, c)
                               for b in branches], carrier)

            def tick(t, state):
                buf, received, outputs = state
                mb_idx = t - my_stage
                active = (mb_idx >= 0) & (mb_idx < M)
                fresh = {
                    lane: jax.lax.dynamic_index_in_dim(
                        buf[lane], jnp.mod(t, B), axis=0, keepdims=False)
                    for lane in lanes}
                inp = {lane: jnp.where(my_stage == 0, fresh[lane],
                                       received[lane])
                       for lane in lanes}
                # double-where: bubble ticks must not FEED garbage into
                # the stage — a zero carrier can produce inf/nan (e.g. a
                # loss normalizer dividing by a zero token count) whose
                # cotangent poisons the masked output's gradient
                inp = {lane: jnp.where(active, v, jnp.ones_like(v))
                       for lane, v in inp.items()}
                out = run_stage(inp)
                out = {lane: jnp.where(active, v, jnp.zeros_like(v))
                       for lane, v in out.items()}
                outputs = jax.lax.cond(
                    active & (my_stage == P - 1),
                    lambda o: {
                        lane: jax.lax.dynamic_update_index_in_dim(
                            o[lane], out[lane],
                            jnp.clip(mb_idx, 0, M - 1), axis=0)
                        for lane in lanes},
                    lambda o: o, outputs)
                received = {
                    lane: jax.lax.ppermute(out[lane], axis, perm_fwd)
                    for lane in lanes}
                return buf, received, outputs

            received = {lane: jnp.zeros((L[lane],), _LANE_DTYPES[lane])
                        for lane in lanes}
            outputs = {lane: jnp.zeros((M, L[lane]), _LANE_DTYPES[lane])
                       for lane in lanes}
            buf = xs_local
            t0 = 0
            for _ in range(outer):
                def inner(i, state, t0=t0):
                    return tick(t0 + i, state)
                buf, received, outputs = jax.lax.fori_loop(
                    0, B, inner, (buf, received, outputs))
                # rotate ingest blocks one hop toward stage 0: after k
                # rotations device 0 holds block k, exactly when ticks
                # [kB, (k+1)B) consume it
                buf = {lane: jax.lax.ppermute(buf[lane], axis,
                                              perm_ingest)
                       for lane in lanes}
                t0 += B
            return {lane: jax.lax.psum(outputs[lane], axis)
                    for lane in lanes}

        from jax.sharding import PartitionSpec as PS
        mb_axes = (data_axis, axis) if data_axis else axis
        param_specs = {lane: PS(axis) for lane in self.param_lanes}
        xs_specs = {lane: PS(mb_axes) for lane in lanes}
        out_specs = {lane: PS(data_axis) if data_axis else PS()
                     for lane in lanes}
        fn = shard_map(per_device, mesh=mesh,
                       in_specs=(param_specs, xs_specs),
                       out_specs=out_specs,
                       check_vma=False)
        return fn


def pipeline_transpiler(program, n_stages, feed_names, fetch_names,
                        mesh, axis="pipe"):
    """Split ``program`` into ``n_stages`` balanced pipeline stages.

    Returns a :class:`PipelinedProgram`; call ``.build(scope,
    sample_microbatch)`` after running the startup program, then
    ``.run_fn()`` for the differentiable pipelined step."""
    return PipelinedProgram(program, n_stages, feed_names, fetch_names,
                            mesh, axis)
