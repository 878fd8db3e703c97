"""Experiment: ResNet-50 train-step layout A/B (round-4).

Per-shape xplane profiling (exp_resnet_conv.py) showed XLA's TPU convs
at 97% of peak for C>=128 but only 24% (NCHW) / 42% (NHWC) at the
C=64 stage and ~7% on the K=64 1x1s — so the model-level question is
layout + backward shapes, not kernel quality.  This benchmarks a
PURE-JAX ResNet-50 training step (conv+BN+ReLU+residual+pool+fc, SGD)
in NCHW vs NHWC, bf16 activations / f32 params, one jit, and reports
median wall step plus the xplane device total.  Whatever wins bounds
what the IR lowering should target.
"""

from __future__ import annotations

import functools
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

from bench import measure_trials

BATCH = 256
BLOCKS = {2: 3, 3: 4, 4: 6, 5: 3}        # resnet-50


def init_params(rng):
    params = {}

    def conv(name, cin, cout, k):
        params[name + ".w"] = (rng.randn(k, k, cin, cout)
                               * (2.0 / (k * k * cin)) ** 0.5
                               ).astype("float32")
        params[name + ".g"] = np.ones(cout, "float32")
        params[name + ".b"] = np.zeros(cout, "float32")

    conv("stem", 3, 64, 7)
    cin = 64
    for stage, n in BLOCKS.items():
        width = 64 * 2 ** (stage - 2)
        for i in range(n):
            base = f"s{stage}b{i}"
            conv(base + ".a", cin, width, 1)
            conv(base + ".b", width, width, 3)
            conv(base + ".c", width, width * 4, 1)
            if cin != width * 4:
                conv(base + ".sc", cin, width * 4, 1)
            cin = width * 4
    params["fc.w"] = (rng.randn(2048, 1000) * 0.02).astype("float32")
    params["fc.b"] = np.zeros(1000, "float32")
    return {k: jnp.asarray(v) for k, v in params.items()}


REAL_BN = False    # set by main(): training-BN statistics variant


def conv_bn_relu(params, name, x, stride, nhwc, relu=True):
    w = params[name + ".w"].astype(jnp.bfloat16)
    if nhwc:
        dn = ("NHWC", "HWIO", "NHWC")
    else:
        dn = ("NCHW", "HWIO", "NCHW")
    k = w.shape[0]
    pad = "SAME" if k > 1 else "VALID"
    # bf16 in/out (a f32 preferred output would make the conv vjp mix
    # dtypes, which lax rejects; the MXU accumulates f32 internally);
    # BN math in f32
    out = jax.lax.conv_general_dilated(
        x, w, (stride, stride), pad, dimension_numbers=dn)
    caxis = 3 if nhwc else 1
    shape = [1, 1, 1, 1]
    shape[caxis] = -1
    # inference-style folded BN (scale+shift); training-BN statistics are
    # elementwise reductions that fuse either way and don't change the
    # layout question
    out = out.astype(jnp.float32)
    if REAL_BN:
        axes = (0, 1, 2) if nhwc else (0, 2, 3)
        mean = jnp.mean(out, axis=axes, keepdims=True)
        var = jnp.mean(jnp.square(out - mean), axis=axes, keepdims=True)
        out = (out - mean) * jax.lax.rsqrt(var + 1e-5)
    out = out * params[name + ".g"].reshape(shape) \
        + params[name + ".b"].reshape(shape)
    if relu:
        out = jnp.maximum(out, 0.0)
    return out.astype(jnp.bfloat16)


def resnet50(params, x, nhwc):
    x = conv_bn_relu(params, "stem", x, 2, nhwc)
    window = [1, 3, 3, 1] if nhwc else [1, 1, 3, 3]
    strides = [1, 2, 2, 1] if nhwc else [1, 1, 2, 2]
    # pool in f32 with a literal -inf init: the max-pool monoid matcher
    # (which makes reduce_window differentiable) wants the literal
    x = jax.lax.reduce_window(
        x.astype(jnp.float32), -jnp.inf, jax.lax.max, window, strides,
        "SAME").astype(jnp.bfloat16)
    cin = 64
    for stage, n in BLOCKS.items():
        width = 64 * 2 ** (stage - 2)
        for i in range(n):
            base = f"s{stage}b{i}"
            stride = 2 if (i == 0 and stage > 2) else 1
            sc = x
            if cin != width * 4:
                sc = conv_bn_relu(params, base + ".sc", x, stride, nhwc,
                                  relu=False)
            h = conv_bn_relu(params, base + ".a", x, stride, nhwc)
            h = conv_bn_relu(params, base + ".b", h, 1, nhwc)
            h = conv_bn_relu(params, base + ".c", h, 1, nhwc, relu=False)
            x = jnp.maximum(h + sc, 0.0).astype(jnp.bfloat16)
            cin = width * 4
    x = jnp.mean(x.astype(jnp.float32), axis=(1, 2) if nhwc else (2, 3))
    logits = x @ params["fc.w"] + params["fc.b"]
    return logits


def loss_fn(params, x, labels, nhwc):
    logits = resnet50(params, x, nhwc)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=1)[:, 0]
    return jnp.mean(lse - picked)


def make_step(nhwc):
    @jax.jit
    def step(params, x, labels):
        # named_scope: device-time reads match THIS program's events only
        with jax.named_scope("resnet_train_step"):
            l, g = jax.value_and_grad(loss_fn)(params, x, labels, nhwc)
            new = jax.tree_util.tree_map(lambda p, gr: p - 0.1 * gr,
                                         params, g)
        return l, new

    return step


def main():
    global REAL_BN, BATCH
    rng = np.random.RandomState(0)
    import os
    variants = [
        # (batch, nhwc, real_bn)
        (256, False, False), (256, True, False),
        (256, True, True), (512, True, False),
    ]
    if os.environ.get("RESNET_VARIANT"):       # e.g. "256,1,1" = one only
        b, h, r = os.environ["RESNET_VARIANT"].split(",")
        variants = [(int(b), h == "1", r == "1")]
    for BATCH, nhwc, REAL_BN in variants:
        params = init_params(rng)
        labels = jnp.asarray(rng.randint(0, 1000, BATCH))
        flops_fwd = 7.72e9 * BATCH  # analytic conv+fc fwd GFLOPs/img
        x = jnp.asarray(rng.rand(BATCH, 224, 224, 3).astype("float32"))
        if not nhwc:
            x = jnp.transpose(x, (0, 3, 1, 2))
        x = x.astype(jnp.bfloat16)
        step = make_step(nhwc)
        l, params2 = step(params, x, labels)
        float(l)  # compile + settle

        def run_once():
            out = step(params, x, labels)
            float(out[0])

        dt, trials = measure_trials(run_once, n_trials=5)

        # ground truth: total DEVICE seconds of one step off the xplane
        # trace (wall clock carries ~100ms of dispatch+sync latency)
        from paddle_tpu.profiler import measure_device_seconds
        dev_s = measure_device_seconds(run_once,
                                       scope="resnet_train_step")

        mfu = flops_fwd * 3 / dev_s / 197e12
        print(json.dumps({
            "layout": "NHWC" if nhwc else "NCHW",
            "batch": BATCH, "real_bn": REAL_BN,
            "step_ms": round(dt * 1e3, 1),
            "device_ms": round(dev_s * 1e3, 1),
            "img_per_s_device": round(BATCH / dev_s, 1),
            "mfu_3x_device": round(mfu, 3),
            "trials_ms": [round(t * 1e3, 1) for t in trials],
        }))
        sys.stdout.flush()


if __name__ == "__main__":
    main()
