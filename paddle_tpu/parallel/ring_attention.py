"""Ring attention: exact attention over sequences sharded across devices.

The reference has NO sequence/context parallelism (SURVEY.md §2.8 — its
long-sequence story is LoD ragged batching); this is the TPU-native
superseding design: shard the sequence axis over a mesh axis, keep Q local,
and rotate K/V shards around the ICI ring with ``ppermute`` while
accumulating an online (flash-style) softmax — memory per chip is
O(S/p * S/p) and the K/V transfer overlaps with compute on real hardware.

Reference pattern: Liu et al., "Ring Attention with Blockwise Transformers
for Near-Infinite Context" (public); built here on jax shard_map.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["ring_attention"]

NEG_INF = -1e30


def _local_block(q, k, v, q_off, k_off, causal, scale):
    """Scores of a local [Sq,D] x [Sk,D] block with global-position causal
    masking; returns (scores [B,H,Sq,Sk])."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        S_q, S_k = q.shape[2], k.shape[2]
        row = jax.lax.broadcasted_iota(jnp.int32, (S_q, S_k), 0) + q_off
        col = jax.lax.broadcasted_iota(jnp.int32, (S_q, S_k), 1) + k_off
        s = jnp.where((col > row)[None, None], NEG_INF, s)
    return s


def ring_attention(q, k, v, mesh: Mesh, axis: str = "seq", causal=False,
                   scale=None):
    """Exact attention with q, k, v [B, H, S, D] sharded on S over
    ``axis`` of ``mesh``.  Returns [B, H, S, D] with the same sharding."""
    if scale is None:
        scale = float(q.shape[-1]) ** -0.5
    p = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    S = q.shape[2]
    assert S % p == 0, f"seq len {S} not divisible by mesh axis {axis}={p}"
    s_local = S // p

    spec = P(None, None, axis, None)

    def local_fn(q_l, k_l, v_l):
        # q_l/k_l/v_l: [B, H, S/p, D] local shards
        idx = jax.lax.axis_index(axis)
        q_off = idx * s_local
        B, H, Sq, D = q_l.shape
        Dv = v_l.shape[3]

        m0 = jnp.full((B, H, Sq, 1), NEG_INF, q_l.dtype)
        l0 = jnp.zeros((B, H, Sq, 1), q_l.dtype)
        acc0 = jnp.zeros((B, H, Sq, Dv), q_l.dtype)

        def body(carry, step):
            # lax.scan (not fori_loop/while) so jax.vjp can differentiate
            # the ring — training runs through this path
            m, l, acc, k_cur, v_cur = carry
            # the shard we hold at ``step`` originated at device idx-step
            src = (idx - step) % p
            k_off = src * s_local
            s = _local_block(q_l, k_cur, v_cur, q_off, k_off, causal,
                             scale)
            blk_m = jnp.max(s, axis=-1, keepdims=True)
            new_m = jnp.maximum(m, blk_m)
            # renormalize the running accumulator to the new max
            correction = jnp.exp(m - new_m)
            probs = jnp.exp(s - new_m)
            l_new = l * correction + probs.sum(-1, keepdims=True)
            acc_new = acc * correction + jnp.einsum(
                "bhqk,bhkd->bhqd", probs, v_cur)
            perm = [(j, (j + 1) % p) for j in range(p)]
            k_next = jax.lax.ppermute(k_cur, axis, perm)
            v_next = jax.lax.ppermute(v_cur, axis, perm)
            return (new_m, l_new, acc_new, k_next, v_next), None

        (m, l, acc, _, _), _ = jax.lax.scan(
            body, (m0, l0, acc0, k_l, v_l), jnp.arange(p))
        # rows with no unmasked keys (fully-causal top rows never happen
        # since diagonal always visible) — safe divide
        return acc / jnp.maximum(l, 1e-30)

    fn = shard_map(local_fn, mesh=mesh,
                   in_specs=(spec, spec, spec), out_specs=spec,
                   check_vma=False)
    return fn(q, k, v)
