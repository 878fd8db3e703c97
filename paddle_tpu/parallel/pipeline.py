"""Pipeline parallelism: GPipe-style microbatched stage pipeline over a
mesh axis.

The reference has NO pipeline parallelism (its §2.8 inventory is
dp/pserver); this is a TPU-native forward-looking primitive completing the
parallelism set (dp = batch sharding, tp = weight PartitionSpecs, sp =
ring_attention, ep = vocab-sharded tables, pp = this module).

Design (the "pipelined scan" from the public scaling-book recipe):

* P homogeneous stages live on the ``pipe`` mesh axis; stage parameters
  are STACKED on a leading [P] axis sharded over that axis, so each
  device holds exactly its stage's weights.
* One ``lax.fori_loop`` runs M + P - 1 ticks.  At tick t, stage p works
  on microbatch t - p (a masked bubble otherwise); activations hop
  p -> p+1 on the ICI ring with ``ppermute``.
* The whole schedule is a pure differentiable function: ``jax.grad``
  through it yields the reverse pipeline automatically (ppermute's
  transpose is the reverse ppermute) — no hand-written backward schedule.

``gpipe`` is the generic primitive (stage_fn + stacked params); see
``tests/test_pipeline.py`` for the loss/grad equality proof against the
sequential computation on an 8-device CPU mesh.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

__all__ = ["gpipe", "stack_stage_params"]


def stack_stage_params(per_stage_params):
    """[{name: array}, ...] per stage -> {name: [P, ...] stacked} (shard
    the leading axis over the ``pipe`` mesh axis before calling gpipe)."""
    keys = per_stage_params[0].keys()
    for p in per_stage_params[1:]:
        if p.keys() != keys:
            raise ValueError("pipeline stages must be homogeneous "
                             "(same parameter names/shapes)")
        for k in keys:
            if p[k].shape != per_stage_params[0][k].shape:
                raise ValueError(
                    f"pipeline stages must be homogeneous: param {k!r} "
                    f"has shape {p[k].shape} vs "
                    f"{per_stage_params[0][k].shape}")
    return {k: jnp.stack([p[k] for p in per_stage_params])
            for k in keys}


def gpipe(stage_fn, stacked_params, microbatches, mesh: Mesh,
          axis: str = "pipe"):
    """Run ``microbatches`` [M, mb, ...] through P pipelined stages.

    ``stage_fn(params, x) -> y`` is one stage's computation (same shape
    in and out); ``stacked_params`` is a pytree whose leaves have a
    leading [P] stage axis.  Returns [M, mb, ...] outputs (the last
    stage's results, gathered).  Fully differentiable — take ``jax.grad``
    of a loss over the returned outputs w.r.t. ``stacked_params``.

    Memory note: microbatch inputs are replicated across stages (every
    device holds [M, mb, ...]); in the deepest-memory regimes the next
    refinement is feeding stage 0 only (shard the M axis + an ingest
    ppermute) at the cost of schedule complexity.
    """
    if axis not in mesh.shape:
        raise ValueError(f"gpipe: mesh has no axis {axis!r} "
                         f"(axes: {list(mesh.shape)})")
    p_size = mesh.shape[axis]
    m = microbatches.shape[0]
    leading = {leaf.shape[0] for leaf in
               jax.tree_util.tree_leaves(stacked_params)}
    if leading != {p_size}:
        raise ValueError(
            f"gpipe: stacked stage params have leading dim(s) "
            f"{sorted(leading)} but the {axis!r} mesh axis has {p_size} "
            f"devices — one stage per device (got a divisible-but-wrong "
            f"stage count? shard_map would silently drop stages)")

    def per_device(params, xs):
        # params: leaves [1, ...] (this stage); xs [M, mb, ...] replicated
        params = jax.tree_util.tree_map(lambda a: a[0], params)
        my_stage = jax.lax.axis_index(axis)
        mb_shape = xs.shape[1:]
        perm_fwd = [(i, (i + 1) % p_size) for i in range(p_size)]

        def tick(t, carry):
            received, outputs = carry
            mb_idx = t - my_stage
            active = (mb_idx >= 0) & (mb_idx < m)
            # stage 0 ingests a fresh microbatch; others take the ring
            fresh = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, m - 1), axis=0, keepdims=False)
            inp = jnp.where(my_stage == 0, fresh, received)
            out = stage_fn(params, inp)
            out = jnp.where(active, out, jnp.zeros_like(out))
            # last stage banks its finished microbatch
            outputs = jax.lax.cond(
                active & (my_stage == p_size - 1),
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, out, jnp.clip(mb_idx, 0, m - 1), axis=0),
                lambda o: o, outputs)
            received = jax.lax.ppermute(out, axis, perm_fwd)
            return received, outputs

        received0 = jnp.zeros(mb_shape, xs.dtype)
        outputs0 = jnp.zeros((m,) + mb_shape, xs.dtype)
        _, outputs = jax.lax.fori_loop(0, m + p_size - 1, tick,
                                       (received0, outputs0))
        # every device returns the SAME gathered outputs: only the last
        # stage holds real values, so a psum broadcasts them (zeros
        # elsewhere) — keeps the caller mesh-agnostic
        return jax.lax.psum(outputs, axis)

    spec_params = jax.tree_util.tree_map(
        lambda _: P(axis), stacked_params)
    fn = shard_map(per_device, mesh=mesh,
                   in_specs=(spec_params, P()), out_specs=P(),
                   check_vma=False)
    return fn(stacked_params, microbatches)
