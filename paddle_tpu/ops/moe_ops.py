"""Expert routing and the routed experts' feed-forward, for a layer that
HOLDS a contiguous share of a model's experts (one chip's part of an
expert-parallel deployment; ``models/hybrid_moe.py``).

``moe_route`` scores every token against ALL the model's experts (the
router is whole on every chip), in float32: sigmoid scores, the ``top_k``
largest of ``score + bias`` (``bias`` moves the choice only), weights =
the chosen scores, normalised over the chosen and scaled.

``moe_experts`` computes, for experts ``expert_offset ..
expert_offset + held - 1`` (``held`` = the leading axis of its weights),
``sum_i w_i * W2_i . relu(W1_i u)^2`` over the assignments that landed on
them.  Assignments to experts held elsewhere are dropped, not remapped;
no token is dropped for capacity.  One product over all held experts
(no per-expert loop): every token is pushed through every held expert
and the combine weight, zero where the router did not choose it, is
applied before the second product, which contracts experts and hidden
units together.  Its second output counts what landed:
``[assignments, distinct experts touched, largest load of one expert]``.

The executor's op scope names them ``ptop_moe_route*`` /
``ptop_moe_experts*`` on the device trace.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.ops.registry import ShapeInferenceSkip, register_op


def moe_route(x, w_gate, bias, top_k, scaling=1.0, norm_topk=True):
    """``x`` [T, d], ``w_gate`` [d, E], ``bias`` [E].  Returns ``idx``
    [T, k] int32 and ``weights`` [T, k] float32."""
    logits = jnp.matmul(x.astype(jnp.float32), w_gate.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + bias.astype(jnp.float32), int(top_k))
    weights = jnp.take_along_axis(scores, idx, axis=-1)
    if norm_topk:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + 1e-20)
    return idx.astype(jnp.int32), weights * float(scaling)


def moe_experts(u, idx, weights, w1, w2, expert_offset=0, live=None):
    """``u`` [T, L]; ``idx``/``weights`` [T, k]; ``w1`` [E, L, F]; ``w2``
    [E, F, L]; ``live`` [T] bool (rows that are not live have no
    assignment).  Returns ``out`` [T, L] in ``u``'s type and ``stats``
    [3] int32."""
    E = w1.shape[0]
    here = (idx - int(expert_offset))[..., None] \
        == jnp.arange(E, dtype=idx.dtype)               # [T, k, E]
    if live is not None:
        here = here & live[:, None, None]
    combine = jnp.sum(jnp.where(here, weights[..., None], 0.0), axis=1)
    load = jnp.sum(here, axis=(0, 1), dtype=jnp.int32)  # [E]
    stats = jnp.stack([jnp.sum(load), jnp.sum(load > 0, dtype=jnp.int32),
                       jnp.max(load)])
    h = jnp.einsum("tl,elf->etf", u, w1,
                   preferred_element_type=jnp.float32)
    h = jnp.square(jnp.maximum(h, 0.0)) * combine.T[:, :, None]
    out = jnp.einsum("etf,efl->tl", h.astype(u.dtype), w2,
                     preferred_element_type=jnp.float32)
    return out.astype(u.dtype), stats


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
    """X [..., d]; W [d, E]; Bias [E].  attrs top_k, scaling,
    norm_topk.  TopkIdx [..., k] int32; TopkWeight [..., k] float32."""
    x = ctx.input("X")
    idx, w = moe_route(_rows(x), ctx.input("W"), ctx.input("Bias"),
                       int(ctx.attr("top_k")),
                       float(ctx.attr("scaling", 1.0)),
                       bool(ctx.attr("norm_topk", True)))
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
        None if lens is None else lens.reshape(-1) > 0)
    ctx.set_output("Out", out.reshape(x.shape))
    ctx.set_output("Stats", stats[None])
