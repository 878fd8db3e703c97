"""ParallelExecutor: data-parallel training over the device mesh.

Reference semantics (``python/paddle/fluid/parallel_executor.py:23`` over
``paddle/fluid/framework/parallel_executor.cc:53``): replicate the program
per GPU, scatter the batch, all-reduce gradients with NCCL, keep parameters
replicated.

TPU-native realization: the SAME lowered step function as ``Executor``,
jit-compiled with explicit shardings over a ``Mesh`` —
  feeds            -> PartitionSpec('data', ...)   (batch split over ICI)
  params/state     -> PartitionSpec()              (replicated), or a
                      tensor-parallel spec from ``param_shardings``
  written state    -> same as its input sharding (forces XLA to insert the
                      gradient all-reduce / reduce-scatter)
No SSA graph, no op handles, no per-device scopes: GSPMD partitions the one
XLA computation and the collectives ride the ICI mesh.

Tensor parallelism (the reference has only layer-device placement,
``ParallelNeuralNetwork.h``): pass ``param_shardings`` as a list of
``(regex, PartitionSpec)`` rules; the first rule matching a state var's
name gives its spec, and GSPMD propagates through the computation
(Megatron-style column/row splits come from the specs alone — see
``paddle_tpu.models.transformer.tp_shardings``).
"""

from __future__ import annotations

import re

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu import framework
from paddle_tpu.executor import (Executor, _CompiledBlock, _amp_enabled,
                                 lower_block)
from paddle_tpu.framework import default_main_program
from paddle_tpu.scope import global_scope
from paddle_tpu.parallel.mesh import default_mesh, DATA_AXIS

__all__ = ["ParallelExecutor"]


class ParallelExecutor(Executor):
    def __init__(self, use_cuda=True, loss_name=None, main_program=None,
                 share_vars_from=None, num_threads=None, mesh=None,
                 batch_axis=0, param_shardings=None, zero=False):
        super().__init__()
        self.mesh = mesh if mesh is not None else default_mesh()
        self.loss_name = loss_name
        self.batch_axis = batch_axis
        self._main_program = main_program
        # [(compiled regex, PartitionSpec)] — first match wins
        self.param_shardings = [(re.compile(pat), spec)
                                for pat, spec in (param_shardings or [])]
        # ZeRO optimizer-state sharding: partition the accumulators over
        # the data axis (params stay replicated).  The plan is emitted
        # as IR-level sharding facts and PROVED by the PTA016/PTA017
        # pass here — before anything compiles, let alone runs.  User
        # param_shardings rules keep precedence (first match wins), so
        # TP-ruled state never double-shards.
        self.zero_plan = None
        if zero:
            from paddle_tpu.parallel.zero import zero_plan
            axis = zero if isinstance(zero, str) else DATA_AXIS
            program = main_program or default_main_program()
            skip = (lambda name: any(pat.search(name) for pat, _ in
                                     self.param_shardings)) \
                if self.param_shardings else None
            plan = zero_plan(program, self.mesh, axis=axis, skip=skip)
            plan.verify()
            self.zero_plan = plan
            self.param_shardings += [(re.compile(pat), spec)
                                     for pat, spec in plan.rules()]
        if share_vars_from is not None:
            pass  # scope is global; parity no-op

    def _state_sharding(self, name, shape=None):
        for pat, spec in self.param_shardings:
            if pat.search(name):
                if shape is None or _spec_fits(spec, shape, self.mesh):
                    return NamedSharding(self.mesh, spec)
                break  # rule matched but shape can't shard (e.g. the
                # scalar beta-pow accumulator of a sharded bias)
        return NamedSharding(self.mesh, P())

    @property
    def device_count(self):
        return int(np.prod(self.mesh.devices.shape))

    def run(self, fetch_list=None, feed=None, feed_dict=None,
            program=None, return_numpy=True, scope=None, sentinel=None):
        feed = feed if feed is not None else (feed_dict or {})
        program = program or self._main_program or default_main_program()
        return super().run(program=program, feed=feed,
                           fetch_list=fetch_list, scope=scope,
                           return_numpy=return_numpy, sentinel=sentinel)

    # -- sharding-aware compile ----------------------------------------
    def _get_compiled(self, program, block, feed_arrays, fetch_names, scope,
                      donate=True):
        from paddle_tpu.executor import _freeze_lod
        feed_lods = tuple(sorted(
            (n, _freeze_lod(scope.find_lod(n))) for n in feed_arrays
            if scope.find_lod(n) is not None))
        from paddle_tpu import profiler as _profiler
        sig = ("pexe", id(program), program._version, block.idx,
               tuple(sorted((n, str(a.dtype), a.shape)
                            for n, a in feed_arrays.items())),
               feed_lods,
               fetch_names, donate, _amp_enabled(program))
        if sig in self._cache:
            self._cache[sig] = self._cache.pop(sig)  # LRU bump
            _profiler.runtime_metrics.inc("jit_cache.hits")
            return self._cache[sig]
        # count the sharded-wrapper miss HERE: super() below also counts
        # its base-signature lookup, and that one can legitimately hit
        # while this level re-jits (each parallel program holds two
        # cache entries — base step + sharded wrapper)
        _profiler.runtime_metrics.inc("jit_cache.misses")

        base = super()._get_compiled(program, block, feed_arrays,
                                     fetch_names, scope, donate=donate)
        mesh = self.mesh
        repl = NamedSharding(mesh, P())
        data_size = dict(zip(mesh.axis_names,
                             mesh.devices.shape)).get(DATA_AXIS, 1)

        def feed_sharding(name, arr):
            # batch-shard data along the batch axis over the 'data' mesh
            # axis when divisible
            if arr.ndim > 0 and data_size > 1 and \
                    arr.shape[self.batch_axis] % data_size == 0:
                spec = [None] * arr.ndim
                spec[self.batch_axis] = DATA_AXIS
                return NamedSharding(mesh, P(*spec))
            return repl

        def shape_of(n):
            v = scope.find_var(n)
            return getattr(v, "shape", None) if v is not None else None

        state_shardings = {n: self._state_sharding(n, shape_of(n))
                           for n in (*base.ro_names, *base.inout_names)}
        out_state_names = list(dict.fromkeys(
            list(base.inout_names) + _written_persistables(block)))
        for n in out_state_names:
            state_shardings.setdefault(
                n, self._state_sharding(n, shape_of(n)))

        in_shardings = (
            {n: feed_sharding(n, a) for n, a in feed_arrays.items()},
            {n: state_shardings[n] for n in base.ro_names},
            {n: state_shardings[n] for n in base.inout_names},
            repl,  # rng key
        )
        training = not program._is_inference
        # the SAME mixed-precision switch as Executor._prepare: a program
        # marked amp must not silently train in f32 once it meets a mesh
        amp = _amp_enabled(program)
        from paddle_tpu.lod import DynLoD, SPLITS_SUFFIX
        lod_map = {}
        for n, lod in feed_lods:
            if isinstance(lod, tuple) and lod and lod[0] == "dyn":
                lod_map[n] = DynLoD(n + SPLITS_SUFFIX, lod[1], lod[2])
            else:
                lod_map[n] = [list(level) for level in lod]

        def step(feeds, ro_state, inout_state, rng_key):
            env = {}
            env.update(feeds)
            env.update(ro_state)
            env.update(inout_state)
            aux = {"rng_counter": 0, "scope": scope,
                   "lower_block": lower_block, "mesh": mesh,
                   "batch_axis": self.batch_axis,
                   "lod": dict(lod_map), "amp": amp,
                   # opt-pipeline fact (see Executor._prepare): key-
                   # free ops skip their per-op fold_in at trace time
                   "rng_plan": True
                   if getattr(program, "_opt_rng_plan", False)
                   else None}
            # the whole-step scope Executor._prepare opens: a scope path
            # reads pt_step/<role>/<scope...>/ptop_... on the mesh too
            with jax.named_scope("pt_step"):
                lower_block(block, env, rng_key, training, aux)
                fetches = [env[n] for n in fetch_names]
                new_state = {n: env[n] for n in out_state_names
                             if n in env}
            return fetches, new_state

        # trace once abstractly to learn which state names actually get
        # produced, so out_shardings matches the returned dict exactly
        out_shardings = (None, {n: state_shardings[n]
                                for n in out_state_names})
        jitted = jax.jit(step, in_shardings=in_shardings,
                         out_shardings=out_shardings,
                         donate_argnums=(2,) if donate else ())
        from paddle_tpu.obs import perf as _perf
        if _perf.capture_enabled():
            # cost/memory capture on the sharded executable: the
            # recorded FLOPs cover the WHOLE mesh, so note_step divides
            # by device_count when deriving the live MFU gauge
            jitted = _perf.instrument_jit(
                jitted, label=_perf.jit_label(
                    feed_arrays, fetch_names,
                    tag=f"mesh{tuple(mesh.devices.shape)}"))
        feed_shardings = in_shardings[0]

        def place_args(span, feeds, ro_state, inout_state, rng_key):
            """The step's arguments under the executable's shardings
            (``executor.place``: ``arrays`` looked at, ``moved`` put by
            a ``device_put``, ``bytes`` those held)."""
            moved = [0, 0]

            def place(a, sharding):
                # skip the device_put dispatch when already placed (state
                # is sharded after the first step; only feeds arrive fresh)
                if getattr(a, "sharding", None) == sharding:
                    return a
                moved[0] += 1
                moved[1] += int(getattr(a, "nbytes", 0))
                return jax.device_put(a, sharding)

            feeds = {n: place(a, feed_shardings[n])
                     for n, a in feeds.items()}
            ro_state = {n: place(a, state_shardings[n])
                        for n, a in ro_state.items()}
            inout_state = {n: place(a, state_shardings[n])
                           for n, a in inout_state.items()}
            rng_key = place(rng_key, repl)
            span.set(arrays=len(feeds) + len(ro_state) + len(inout_state)
                     + 1, moved=moved[0], bytes=moved[1])
            return feeds, ro_state, inout_state, rng_key

        compiled = _CompiledBlock(jitted, base.feed_names, base.ro_names,
                                  base.inout_names, tuple(fetch_names), True,
                                  place=place_args)
        compiled.donated = donate
        compiled.perf = getattr(jitted, "perf", None)
        self._cache_insert(sig, compiled)
        return compiled

    def _feed_device(self):
        return None


def _spec_fits(spec, shape, mesh):
    """True when every sharded dim of ``shape`` divides evenly by the
    product of its mesh axis sizes."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if len(spec) > len(shape):
        return False
    for dim, ax in zip(shape, tuple(spec)):
        if ax is None:
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        k = 1
        for a in axes:
            k *= sizes.get(a, 1)
        if dim is None or dim < 0 or dim % k:
            return False
    return True


def _written_persistables(block):
    from paddle_tpu.executor import _SKIP_OPS
    out = []
    for op in block.ops:
        if op.type in _SKIP_OPS:  # reader vars hold host objects, not state
            continue
        for n in op.output_arg_names:
            try:
                var = block.var(n)
            except KeyError:
                continue
            if var.persistable and n not in out:
                out.append(n)
    return out
