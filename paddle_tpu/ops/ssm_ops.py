"""State-space (Mamba-2) mixer ops and the norms / activation of the
hybrid blocks built on them (``models/hybrid_moe.py``).

A mixer layer's cache is not rows of a page pool but a FIXED per-slot
state: the recurrent state ``h`` ``[slots, heads, head_dim, state]``
(float32) and the causal conv's window, the last ``kernel - 1`` rows
of its input ``[slots, kernel - 1, channels]``.  Two forms of the same
recurrence

    h_t = exp(dt_t * A) * h_{t-1} + dt_t * x_t (x) B_t
    y_t = h_t . C_t + D * x_t            dt = softplus(dt_raw + dt_bias)

* ``ssm_scan`` (prefill, one prompt): the chunked ("SSD") form, all
  matrix products over chunks of ``chunk`` rows plus a scan over the
  chunks' end states.  Pad rows of the prompt's bucket get ``dt`` = 0,
  which freezes the state, so the state handed out is the one after the
  prompt's last token.  ``ssm_scan_conv`` is the causal depthwise conv
  before it and hands out the prompt's last ``kernel - 1`` input rows.
* ``ssm_update`` (decode, one token for every slot): the recurrence
  itself on the persistable state, in place; a slot with ``lens`` 0 keeps
  its state (and, ``ssm_update_conv``, its window) untouched.

The state, ``dt``, ``exp(dt * A)`` and every sum of the recurrence are
float32 whatever the activations' type; the chunked form's products run
at "highest" precision (they are a few percent of a prefill's FLOPs).
The prefill-side ops and the norms differentiate through the registry's
auto-vjp (the training graph of ``models/hybrid_moe.py``); the decode-side
updates are inference state and have no gradient.  All are plain XLA
lowerings: the executor's op scope names them
``ptop_ssm_scan*`` / ``ptop_ssm_update*`` on the device trace.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.ops.registry import (ShapeInferenceSkip, infer_shape_unary,
                                     register_op)

_HI = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# norms and activation
# ---------------------------------------------------------------------------

def rms_norm(x, scale, eps):
    """``x / sqrt(mean(x^2) + eps) * scale`` over the last axis,
    statistics in float32, result in ``x``'s type."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)
            * scale.astype(jnp.float32)).astype(x.dtype)


def gated_group_rms_norm(y, gate, scale, groups, eps):
    """``RMSNorm_groups(y * silu(gate)) * scale``: the last axis is
    normalised in ``groups`` equal parts, each by its own statistics."""
    v = y.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))
    g = v.reshape(v.shape[:-1] + (groups, v.shape[-1] // groups))
    var = jnp.mean(jnp.square(g), axis=-1, keepdims=True)
    g = g * jax.lax.rsqrt(var + eps)
    return (g.reshape(v.shape) * scale.astype(jnp.float32)).astype(y.dtype)


def relu2(x):
    r = jnp.maximum(x.astype(jnp.float32), 0.0)
    return (r * r).astype(x.dtype)


@register_op("rms_norm", infer_shape=infer_shape_unary())
def rms_norm_lower(ctx):
    """X [..., d], Scale [d]; attr epsilon."""
    ctx.set_output("Out", rms_norm(ctx.input("X"), ctx.input("Scale"),
                                   float(ctx.attr("epsilon", 1e-5))))


@register_op("gated_group_rms_norm", infer_shape=infer_shape_unary())
def gated_group_rms_norm_lower(ctx):
    """X, Gate [..., d], Scale [d]; attrs groups, epsilon."""
    ctx.set_output("Out", gated_group_rms_norm(
        ctx.input("X"), ctx.input("Gate"), ctx.input("Scale"),
        int(ctx.attr("groups", 1)), float(ctx.attr("epsilon", 1e-5))))


@register_op("relu2", infer_shape=infer_shape_unary())
def relu2_lower(ctx):
    ctx.set_output("Out", relu2(ctx.input("X")))


# ---------------------------------------------------------------------------
# causal depthwise conv with a carried window
# ---------------------------------------------------------------------------

def conv_scan(x, w, b, n_real, before=None):
    """``x`` [T, C]; ``w`` [K, C] (tap K-1 multiplies the current row);
    ``b`` [C] or None.  Returns ``silu(conv(x) + b)`` [T, C] in ``x``'s
    type and the window after ``n_real`` rows: rows ``n_real-K+1 ..
    n_real-1`` of ``x``, float32 [K-1, C].  ``before`` [K-1, C]: the rows
    that precede ``x`` (a chunk that continues a sequence); None: zeros,
    the sequence's start."""
    K = w.shape[0]
    xf = x.astype(jnp.float32)
    xf = jnp.pad(xf, ((K - 1, 0), (0, 0))) if before is None else \
        jnp.concatenate([before.astype(jnp.float32), xf], axis=0)
    T = x.shape[0]
    acc = 0.0 if b is None else b.astype(jnp.float32)[None, :]
    for k in range(K):
        acc = acc + xf[k:k + T] * w[k].astype(jnp.float32)[None, :]
    window = jax.lax.dynamic_slice_in_dim(xf, n_real, K - 1, axis=0)
    return jax.nn.silu(acc).astype(x.dtype), window


def conv_update(x, window, w, b, live):
    """One row per slot: ``x`` [S, C], ``window`` [S, K-1, C] float32,
    ``b`` [C] or None.
    Returns ``silu(conv + b)`` [S, C] and the shifted window; slots
    where ``live`` [S] is false keep their window."""
    xf = x.astype(jnp.float32)
    full = jnp.concatenate([window, xf[:, None, :]], axis=1)   # [S, K, C]
    acc = jnp.sum(full * w.astype(jnp.float32)[None], axis=1)
    if b is not None:
        acc = acc + b.astype(jnp.float32)[None, :]
    new = jnp.where(live[:, None, None], full[:, 1:], window)
    return jax.nn.silu(acc).astype(x.dtype), new


def _infer_scan_conv(op, block):
    x = block.var(op.input("X")[0])
    w = block.var(op.input("W")[0])
    if x.shape is None or w.shape is None:
        raise ShapeInferenceSkip()
    out = block.var(op.output("Out")[0])
    out.shape, out.dtype = tuple(x.shape), x.dtype
    win = block.var(op.output("Window")[0])
    win.shape = (1, int(w.shape[0]) - 1, int(x.shape[-1]))
    win.dtype = "float32"


def _bias(ctx):
    return ctx.input("Bias") if ctx.has_input("Bias") else None


def chunk_slot(ctx):
    """Where ONE CHUNK of a prompt keeps its per-slot state: ``(slot,
    whether the chunk is the prompt's first)`` (Pos starts at 0 and the
    chunk has a real row: a warm-up's chunk of pad rows leaves the slot
    alone).  Reads the op's Slot [1, 1], Pos [1, C] and Mask [1, C]."""
    slot = ctx.input("Slot").reshape(-1)[0].astype(jnp.int32)
    first = (ctx.input("Pos").reshape(-1)[0] == 0) \
        & (ctx.input("Mask").reshape(-1)[0] > 0)
    return slot, first


def chunk_slot_state(ctx, array):
    """What ONE CHUNK of a prompt starts from, of a per-slot state
    ``array`` [num_slots, ...]: ``(slot, the slot's row)``, the row zeros
    where the chunk is the prompt's first (:func:`chunk_slot`)."""
    slot, first = chunk_slot(ctx)
    held = jax.lax.dynamic_index_in_dim(array, slot, 0, keepdims=False)
    return slot, jnp.where(first, 0.0, held)


@register_op("ssm_scan_conv", infer_shape=_infer_scan_conv,
             no_grad_inputs=("Mask",), stop_gradient_outputs=("Window",))
def ssm_scan_conv_lower(ctx):
    """X [1, T, C]; W [K, C]; Bias [C] (optional); Mask [1, T] (1 = real
    row, real rows first).  Out [1, T, C]; Window [1, K-1, C] float32."""
    x, mask = ctx.input("X"), ctx.input("Mask")
    n_real = jnp.sum(mask[0] > 0).astype(jnp.int32)
    out, window = conv_scan(x[0], ctx.input("W"), _bias(ctx), n_real)
    ctx.set_output("Out", out[None])
    ctx.set_output("Window", window[None])


@register_op("ssm_chunk_conv", infer_shape=infer_shape_unary(),
             no_gradient=True, stateful_outputs=("WindowOut",))
def ssm_chunk_conv_lower(ctx):
    """The conv over ONE CHUNK of a prompt, continuing the slot's window.
    X [1, C, ch]; W [K, ch]; Bias [ch] (optional); Window [num_slots,
    K-1, ch] persistable float32; Slot [1, 1] int32; Pos [1, C] int32
    the rows' positions ``start ..``; Mask [1, C] (1 = a real row, real
    rows first).  The slot's window leads the chunk's rows in (zeros
    where the chunk is the prompt's first: position 0 and a real row) and
    the window after the chunk's last real row is left there.  Out [1, C,
    ch]; WindowOut names the window array itself."""
    x, windows = ctx.input("X"), ctx.input("Window")
    slot, held = chunk_slot_state(ctx, windows)
    out, window = conv_scan(
        x[0], ctx.input("W"), _bias(ctx),
        jnp.sum(ctx.input("Mask") > 0).astype(jnp.int32), before=held)
    ctx.set_output("Out", out[None])
    ctx.set_output("WindowOut", jax.lax.dynamic_update_index_in_dim(
        windows, window.astype(windows.dtype), slot, 0))


@register_op("ssm_update_conv", infer_shape=infer_shape_unary(),
             no_gradient=True, stateful_outputs=("WindowOut",))
def ssm_update_conv_lower(ctx):
    """X [S, 1, C]; Window [S, K-1, C] persistable float32; W; Bias
    (optional); Lens [S, 1] int32 (0 = free slot).  Out [S, 1, C];
    WindowOut names the window var itself (in-place update)."""
    x = ctx.input("X")
    live = ctx.input("Lens")[:, 0] > 0
    out, new = conv_update(x.reshape(x.shape[0], x.shape[-1]),
                           ctx.input("Window"), ctx.input("W"),
                           _bias(ctx), live)
    ctx.set_output("Out", out.reshape(x.shape))
    ctx.set_output("WindowOut", new)


# ---------------------------------------------------------------------------
# the recurrence
# ---------------------------------------------------------------------------

def _split_xbc(xbc, n_head, head_dim, n_groups, state):
    """``xbc`` [..., H*P + 2*G*N] -> x [..., G, H/G, P], B, C [..., G, N]
    (head h reads group h // (H/G)), float32."""
    lead = xbc.shape[:-1]
    hp, gn = n_head * head_dim, n_groups * state
    f = xbc.astype(jnp.float32)
    x = f[..., :hp].reshape(lead + (n_groups, n_head // n_groups, head_dim))
    B = f[..., hp:hp + gn].reshape(lead + (n_groups, state))
    C = f[..., hp + gn:].reshape(lead + (n_groups, state))
    return x, B, C


def _dt_a(dt_raw, dt_bias, a_log, n_groups):
    """softplus(dt_raw + dt_bias) [..., G, R] and A = -exp(A_log) [G, R]."""
    H = a_log.shape[0]
    shape = (n_groups, H // n_groups)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + dt_bias.astype(jnp.float32))
    return (dt.reshape(dt.shape[:-1] + shape),
            -jnp.exp(a_log.astype(jnp.float32)).reshape(shape))


def ssm_scan(xbc, dt_raw, a_log, d_skip, dt_bias, mask, *, n_head, head_dim,
             n_groups, state, chunk):
    """Chunked scan over one prompt.  ``xbc`` [T, H*P + 2*G*N] (after the
    conv), ``dt_raw`` [T, H], ``mask`` [T] (0 = pad row: dt = 0, the state
    stands still).  Returns ``y`` [T, H*P] in ``xbc``'s type and the state
    after the last real row, float32 [H, P, N]."""
    T = xbc.shape[0]
    G, R, P, N = n_groups, n_head // n_groups, head_dim, state
    x, B, C = _split_xbc(xbc, n_head, head_dim, n_groups, state)
    dt, A = _dt_a(dt_raw, dt_bias, a_log, n_groups)
    dt = dt * (mask.astype(jnp.float32) > 0)[:, None, None]
    Q = min(int(chunk), T)
    pad = -T % Q
    if pad:
        x, B, C, dt = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                       for a in (x, B, C, dt))
    nc = (T + pad) // Q
    # heads lead and the chunk's rows are the minor axes: [nc, G, R, Q, .]
    x = x.reshape(nc, Q, G, R, P).transpose(0, 2, 3, 1, 4)
    B, C = B.reshape(nc, Q, G, N), C.reshape(nc, Q, G, N)
    dt = dt.reshape(nc, Q, G, R).transpose(0, 2, 3, 1)
    cs = jnp.cumsum(dt * A[..., None], axis=-1)        # [nc, G, R, Q] <= 0
    # inside a chunk: y_i += sum_{j<=i} C_i.B_j exp(cs_i - cs_j) dt_j x_j
    seg = cs[..., :, None] - cs[..., None, :]          # [nc, G, R, Qi, Qj]
    decay = jnp.exp(jnp.where(jnp.tril(jnp.ones((Q, Q), bool)), seg,
                              -jnp.inf))
    cb = jnp.einsum("cign,cjgn->cgij", C, B, precision=_HI)
    m = cb[:, :, None] * decay * dt[..., None, :]
    y = jnp.einsum("cgrij,cgrjp->cgrip", m, x, precision=_HI)
    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(cs[..., -1:] - cs) * dt           # [nc, G, R, Q]
    s_own = jnp.einsum("cgrj,cgrjp,cjgn->cgrpn", to_end, x, B,
                       precision=_HI)
    through = jnp.exp(cs[..., -1])                     # [nc, G, R]

    def carry(h, inp):
        own, thr = inp
        return h * thr[..., None, None] + own, h

    h_end, h_before = jax.lax.scan(
        carry, jnp.zeros((G, R, P, N), jnp.float32), (s_own, through))
    y = y + jnp.einsum("cign,cgrpn,cgri->cgrip", C, h_before, jnp.exp(cs),
                       precision=_HI)
    y = y + x * d_skip.astype(jnp.float32).reshape(G, R)[:, :, None, None]
    y = y.transpose(0, 3, 1, 2, 4)                     # [nc, Q, G, R, P]
    y = y.reshape(nc * Q, n_head * head_dim)[:T]
    return y.astype(xbc.dtype), h_end.reshape(n_head, P, N)


def ssm_update(xbc, dt_raw, a_log, d_skip, dt_bias, h, live, *, n_head,
               head_dim, n_groups, state):
    """One token for every slot.  ``xbc`` [S, H*P + 2*G*N], ``dt_raw``
    [S, H], ``h`` [S, H, P, N] float32, ``live`` [S] bool.  Returns ``y``
    [S, H*P] and the new state; slots that are not live keep theirs."""
    S = xbc.shape[0]
    G, R, P, N = n_groups, n_head // n_groups, head_dim, state
    x, B, C = _split_xbc(xbc, n_head, head_dim, n_groups, state)
    dt, A = _dt_a(dt_raw, dt_bias, a_log, n_groups)
    hg = h.reshape(S, G, R, P, N)
    new = hg * jnp.exp(dt * A)[..., None, None] \
        + (dt[..., None] * x)[..., None] * B[:, :, None, None, :]
    y = jnp.sum(new * C[:, :, None, None, :], axis=-1) \
        + x * d_skip.astype(jnp.float32).reshape(G, R)[..., None]
    new = jnp.where(live[:, None, None, None, None], new, hg)
    return (y.reshape(S, n_head * head_dim).astype(xbc.dtype),
            new.reshape(h.shape))


def _ssm_attrs(ctx):
    return dict(n_head=int(ctx.attr("n_head")),
                head_dim=int(ctx.attr("head_dim")),
                n_groups=int(ctx.attr("n_groups")),
                state=int(ctx.attr("state")))


def _infer_ssm(op, block):
    x = block.var(op.input("X")[0])
    if x.shape is None:
        raise ShapeInferenceSkip()
    hp = int(op.attr("n_head")) * int(op.attr("head_dim"))
    out = block.var(op.output("Out")[0])
    out.shape, out.dtype = tuple(x.shape[:-1]) + (hp,), x.dtype
    if op.output("State"):
        st = block.var(op.output("State")[0])
        st.shape = (1, int(op.attr("n_head")), int(op.attr("head_dim")),
                    int(op.attr("state")))
        st.dtype = "float32"


@register_op("ssm_scan", infer_shape=_infer_ssm, no_grad_inputs=("Mask",),
             stop_gradient_outputs=("State",))
def ssm_scan_lower(ctx):
    """X [1, T, H*P + 2*G*N] (conv output); Dt [1, T, H]; ALog, D, DtBias
    [H]; Mask [1, T].  attrs n_head, head_dim, n_groups, state, chunk.
    Out [1, T, H*P]; State [1, H, P, N] float32."""
    y, h = ssm_scan(ctx.input("X")[0], ctx.input("Dt")[0],
                    ctx.input("ALog"), ctx.input("D"), ctx.input("DtBias"),
                    ctx.input("Mask")[0], chunk=int(ctx.attr("chunk", 128)),
                    **_ssm_attrs(ctx))
    ctx.set_output("Out", y[None])
    ctx.set_output("State", h[None])


@register_op("ssm_update", infer_shape=_infer_ssm, no_gradient=True,
             stateful_outputs=("StateOut",))
def ssm_update_lower(ctx):
    """X [S, 1, H*P + 2*G*N]; Dt [S, 1, H]; ALog, D, DtBias [H]; State
    [S, H, P, N] persistable float32; Lens [S, 1] int32 (0 = free slot).
    Out [S, 1, H*P]; StateOut names the state var itself (in place)."""
    x, dt = ctx.input("X"), ctx.input("Dt")
    S = x.shape[0]
    y, h = ssm_update(x.reshape(S, x.shape[-1]), dt.reshape(S, dt.shape[-1]),
                      ctx.input("ALog"), ctx.input("D"),
                      ctx.input("DtBias"), ctx.input("State"),
                      ctx.input("Lens")[:, 0] > 0, **_ssm_attrs(ctx))
    ctx.set_output("Out", y.reshape(x.shape[:-1] + (y.shape[-1],)))
    ctx.set_output("StateOut", h)
