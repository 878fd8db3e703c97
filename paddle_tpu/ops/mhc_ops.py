"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, on
Hyper-Connections, arXiv:2409.19606): a decoder whose residual is ``n``
STREAMS of the model's width, ``x in R^{n x C}`` a row, and whose every
sublayer ``F`` is wrapped::

    x'      = RMSNorm(vec(x))                 over the n C values, no scale
    H~_pre  = a_pre  (x' phi_pre)  + b_pre    [n]
    H~_post = a_post (x' phi_post) + b_post   [n]
    H~_res  = a_res  mat(x' phi_res) + b_res  [n, n]
    H_pre   = sigmoid(H~_pre)      H_post = 2 sigmoid(H~_post)
    H_res   = SK(H~_res)           (Sinkhorn-Knopp: doubly stochastic)
    u       = H_pre x              [C]         y = F(u)
    x_next  = H_res x + H_post^T y            [n, C]

``SK``: ``M = exp(clamp(H~_res, lo, hi))``, then ``iters`` times the
columns divided by their sums and then the rows by theirs, ``eps`` added
to each sum.  The two halves are two ops:

* ``mhc_pre``: the streams -> ``u`` and the coefficients ``H_post``,
  ``H_res`` (the statistic, the three products, the sigmoids and the
  Sinkhorn rounds in float32; ``u`` in the streams' type);
* ``mhc_post``: the streams, ``y`` and the coefficients -> the streams.

The three ``phi`` are ONE matrix ``[n C, n (n + 2)]`` (columns: pre, post,
res row-major), the three ``b`` one vector, the three ``a`` one ``[3]``:
a sublayer's wrapper is three parameters.  RMSNorm has no learned scale,
so ``x' phi = (x phi) / rms(x)``: the product takes the streams as they
are stored.

Where a half's time goes differs by program.  A prompt's chunk moves
the streams (29 MB a read at 1024 rows): the aggregate ``u`` and the
distribution are one pass over the streams each, composed XLA.  A decode
turn (64 rows) moves nothing to speak of and is a CHAIN: the sigmoids,
``exp`` and the Sinkhorn rounds, ``iters`` x (2 n sums + 2 n^2
divisions) of ``n x n`` values a row, each round depending on the one
before.  On the chip that chain is ONE Pallas kernel
(:func:`_coefficients_kernel`: the rows on lanes, every one of the ``n
(n + 2)`` coefficients a row of sublanes, the rounds a loop inside the
kernel on ``n x n`` separate vectors, so a sum is ``n - 1`` additions and
nothing leaves VMEM between rounds); unrolled into XLA it is 25,000
elementwise instructions a program (minutes of compile for every
executable of a bundle), as a ``while`` loop a launch a round.  The
composed form (:func:`sinkhorn`: a ``fori_loop`` of whole-array sums)
stands beside it for the CPU and is what the tests hold the kernel to.

Op scopes on the device trace: ``ptop_mhc_pre*``, ``ptop_mhc_post*``
(under the name scope ``mhc`` of ``models/decoder.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from paddle_tpu.ops.registry import ShapeInferenceSkip, register_op

_F32 = jnp.float32
#: rows (lanes) of one grid step of the coefficients' kernel
_KERNEL_ROWS = 512


def sinkhorn(h_res, iters, eps, lo, hi):
    """``SK`` of ``h_res`` [..., n, n] float32, composed: ``exp`` of the
    clamped entries, then ``iters`` rounds of (columns over their sums,
    rows over theirs), ``eps`` added to each sum."""
    def one_round(_, m):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
        return m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
    return jax.lax.fori_loop(0, int(iters), one_round,
                             jnp.exp(jnp.clip(h_res, lo, hi)))


def _coefficients_kernel(h_ref, o_ref, *, n, iters, eps, lo, hi):
    """``h_ref`` / ``o_ref`` [n (n + 2), rows]: a row's ``H~`` (pre, post,
    res row-major) down a column of sublanes -> ``H_pre``, ``H_post``,
    ``H_res`` in their place."""
    row = lambda k: h_ref[pl.ds(k, 1), :]
    for k in range(n):
        o_ref[pl.ds(k, 1), :] = jax.nn.sigmoid(row(k))
        o_ref[pl.ds(n + k, 1), :] = 2.0 * jax.nn.sigmoid(row(n + k))
    m = tuple(jnp.exp(jnp.clip(row(2 * n + k), lo, hi))
              for k in range(n * n))

    def one_round(_, m):
        cols = [sum(m[i * n + j] for i in range(n)) + eps for j in range(n)]
        m = [m[i * n + j] / cols[j] for i in range(n) for j in range(n)]
        rows = [sum(m[i * n + j] for j in range(n)) + eps for i in range(n)]
        return tuple(m[i * n + j] / rows[i]
                     for i in range(n) for j in range(n))

    m = jax.lax.fori_loop(0, iters, one_round, m)
    for k in range(n * n):
        o_ref[pl.ds(2 * n + k, 1), :] = m[k]


@functools.partial(jax.jit, inline=True, static_argnames=(
    "n", "iters", "eps", "lo", "hi", "interpret"))
def coefficients_kernel(h, *, n, iters, eps, lo, hi, interpret=False):
    """``h`` [rows, n (n + 2)] float32 (``H~`` a row) -> the three
    mappings, flat as ``h``, through :func:`_coefficients_kernel` (rows
    padded to whole lane tiles)."""
    rows, width = h.shape
    tile = min(_KERNEL_ROWS, -(-rows // 128) * 128)
    padded = -(-rows // tile) * tile
    ht = jnp.pad(h.T, ((0, 0), (0, padded - rows)))
    out = pl.pallas_call(
        functools.partial(_coefficients_kernel, n=n, iters=iters, eps=eps,
                          lo=lo, hi=hi),
        grid=(padded // tile,),
        in_specs=[pl.BlockSpec((width, tile), lambda r: (0, r))],
        out_specs=pl.BlockSpec((width, tile), lambda r: (0, r)),
        out_shape=jax.ShapeDtypeStruct((width, padded), _F32),
        interpret=interpret,
    )(ht)
    return out[:, :rows].T


def mhc_coefficients(x, phi, alpha, bias, iters, eps, lo, hi, rms_eps,
                     kernel=None):
    """``x`` [..., n, C] (the streams); ``phi`` [n C, n (n + 2)], ``alpha``
    [3], ``bias`` [n (n + 2)], float32.  Returns ``(H_pre [..., n], H_post
    [..., n], H_res [..., n, n])``, float32.  ``kernel``: the chain behind
    the product through the Pallas kernel (None: on a TPU; "interpret":
    the kernel in interpret mode, for the tests off the chip)."""
    n, c = x.shape[-2:]
    lead = x.shape[:-2]
    rows = x.reshape(lead + (n * c,))
    square = jnp.mean(jnp.square(rows.astype(_F32)), axis=-1, keepdims=True)
    h = jnp.dot(rows.astype(_F32), phi.astype(_F32),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=_F32) * jax.lax.rsqrt(square + rms_eps)
    gain = jnp.repeat(alpha.astype(_F32), np.array([n, n, n * n]),
                      total_repeat_length=n * (n + 2))
    h = h * gain + bias.astype(_F32)
    if kernel is None:
        # (the one place that says whether this process drives a TPU)
        from paddle_tpu.ops.attention_ops import _use_interpret
        kernel = not _use_interpret()
    if kernel:
        made = coefficients_kernel(
            h.reshape(-1, n * (n + 2)), n=n, iters=int(iters),
            eps=float(eps), lo=float(lo), hi=float(hi),
            interpret=kernel == "interpret").reshape(h.shape)
        return (made[..., :n], made[..., n:2 * n],
                made[..., 2 * n:].reshape(lead + (n, n)))
    pre = jax.nn.sigmoid(h[..., :n])
    post = 2.0 * jax.nn.sigmoid(h[..., n:2 * n])
    res = sinkhorn(h[..., 2 * n:].reshape(lead + (n, n)), iters, eps, lo, hi)
    return pre, post, res


def mhc_pre(x, phi, alpha, bias, iters=20, eps=1e-6, lo=-30.0, hi=30.0,
            rms_eps=1e-6, kernel=None):
    """The wrapper's first half: ``(u [..., C] in x's type, H_post [...,
    n], H_res [..., n, n])``."""
    pre, post, res = mhc_coefficients(x, phi, alpha, bias, iters, eps, lo,
                                      hi, rms_eps, kernel=kernel)
    # (a stream is cast up where it is used: a shared float32 copy of the
    # streams would be written out, twice their bytes)
    u = sum(pre[..., j, None] * x[..., j, :].astype(_F32)
            for j in range(x.shape[-2]))
    return u.astype(x.dtype), post, res


def mhc_post(x, y, post, res):
    """The wrapper's second half: ``H_res x + H_post^T y`` [..., n, C] in
    ``x``'s type; the sums in float32, stream by stream (``n`` is static:
    no product op, one pass over the streams)."""
    n = x.shape[-2]
    yf = y.astype(_F32)
    # (each stream cast up where it is read and rounded before the
    # streams are laid side by side: no float32 copy is written out)
    out = [(sum(res[..., i, j, None] * x[..., j, :].astype(_F32)
                for j in range(n))
            + post[..., i, None] * yf).astype(x.dtype) for i in range(n)]
    return jnp.stack(out, axis=-2)


def _infer_mhc_pre(op, block):
    x = block.var(op.input("X")[0])
    if x.shape is None:
        raise ShapeInferenceSkip()
    lead, n = tuple(x.shape[:-2]), int(x.shape[-2])
    u = block.var(op.output("U")[0])
    u.shape, u.dtype = lead + (int(x.shape[-1]),), x.dtype
    for slot, shape in (("Post", lead + (n,)), ("Res", lead + (n, n))):
        v = block.var(op.output(slot)[0])
        v.shape, v.dtype = shape, "float32"


@register_op("mhc_pre", infer_shape=_infer_mhc_pre)
def mhc_pre_lower(ctx):
    """X [..., n, C] the streams; Phi [n C, n (n + 2)], Alpha [3], Bias
    [n (n + 2)] float32.  attrs sinkhorn_iters, eps, clamp_min,
    clamp_max, rms_eps.  U [..., C] (X's type): the sublayer's input;
    Post [..., n], Res [..., n, n] float32: what ``mhc_post`` takes."""
    u, post, res = mhc_pre(
        ctx.input("X"), ctx.input("Phi"), ctx.input("Alpha"),
        ctx.input("Bias"), int(ctx.attr("sinkhorn_iters", 20)),
        float(ctx.attr("eps", 1e-6)), float(ctx.attr("clamp_min", -30.0)),
        float(ctx.attr("clamp_max", 30.0)), float(ctx.attr("rms_eps", 1e-6)))
    ctx.set_output("U", u)
    ctx.set_output("Post", post)
    ctx.set_output("Res", res)


def _infer_mhc_post(op, block):
    x = block.var(op.input("X")[0])
    out = block.var(op.output("Out")[0])
    out.shape, out.dtype = x.shape, x.dtype


@register_op("mhc_post", infer_shape=_infer_mhc_post)
def mhc_post_lower(ctx):
    """X [..., n, C] the streams; Y [..., C] the sublayer's output; Post
    [..., n], Res [..., n, n] (``mhc_pre``'s).  Out [..., n, C]."""
    ctx.set_output("Out", mhc_post(ctx.input("X"), ctx.input("Y"),
                                   ctx.input("Post"), ctx.input("Res")))
