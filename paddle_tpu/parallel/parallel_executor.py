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

from paddle_tpu.executor import Executor, _captured, _host_value
from paddle_tpu.framework import default_main_program
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
    def _interprets(self, program, block):
        """Never: a mesh runs the compiled step, whatever the program
        holds (the host-op warning is the plain executor's)."""
        super()._interprets(program, block)
        return False

    def _step_aux(self):
        return {"mesh": self.mesh, "batch_axis": self.batch_axis}

    def _jit_step(self, parts, feed_arrays, fetch_names, scope, donate):
        """The SAME classified step as ``Executor``'s, jitted with explicit
        shardings over the mesh; the record places its arguments under
        them (``executor.place``)."""
        mesh = self.mesh
        repl = NamedSharding(mesh, P())
        data_size = dict(zip(mesh.axis_names,
                             mesh.devices.shape)).get(DATA_AXIS, 1)

        def feed_sharding(arr):
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

        ro_names, inout_names = parts["ro_names"], parts["inout_names"]
        written = inout_names + parts["create_state"]
        state_shardings = {n: self._state_sharding(n, shape_of(n))
                           for n in (*ro_names, *written)}
        feed_shardings = {n: feed_sharding(a)
                          for n, a in feed_arrays.items()}
        in_shardings = (
            feed_shardings,
            {n: state_shardings[n] for n in ro_names},
            {n: state_shardings[n] for n in inout_names},
            repl,  # rng key
        )
        # written state keeps its input's sharding (which forces XLA to
        # insert the gradient all-reduce / reduce-scatter)
        out_shardings = (None, {n: state_shardings[n] for n in written})
        jitted = jax.jit(parts["step"], in_shardings=in_shardings,
                         out_shardings=out_shardings,
                         donate_argnums=(2,) if donate else ())
        # cost/memory capture on the sharded executable: the recorded
        # FLOPs cover the WHOLE mesh, so note_step divides by device_count
        # when deriving the live MFU gauge
        jitted = _captured(jitted, feed_arrays, fetch_names,
                           tag=f"mesh{tuple(mesh.devices.shape)}")
        return jitted, (feed_shardings, state_shardings, repl)

    def _feed_device(self):
        return None

    def _feed_array(self, value, dtype):
        """Host memory stays on the host, in the dtype the device would
        hold: ``executor.place`` puts it under its sharding in one
        ``device_put``, not on device 0 first and then across."""
        value = _host_value(value, dtype)
        if isinstance(value, (np.ndarray, np.generic, list, tuple)):
            value = np.asarray(value)
            return value.astype(jax.dtypes.canonicalize_dtype(value.dtype),
                                copy=False)
        return jnp.asarray(value)


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
