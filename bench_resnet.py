"""ResNet-50 images/sec/chip benchmark (reference ``benchmark/fluid/resnet.py``
+ ``run.sh`` protocol), the conv half of the BASELINE.json north star.

Prints ONE JSON line:
  {"metric": "resnet50_images_per_sec_per_chip", "value": N,
   "unit": "images/sec", "vs_baseline": R}

``vs_baseline`` is achieved MFU / 0.45.  FLOPs are counted analytically by
walking the built program's conv2d/mul ops (2*MACs fwd, x3 for training:
the filter-grad and input-grad passes each cost about one forward conv) —
elementwise/batch-norm/pool ops are excluded, the standard convnet MFU
convention.  Timing is the median of ``PADDLE_TPU_BENCH_TRIALS`` (default
5) trials of a device-side ``run_steps`` loop after warmup, same
robustness discipline as ``bench.py``.

Run directly (``python bench_resnet.py``), or via ``bench.py`` with
``PADDLE_TPU_BENCH_MODEL=resnet`` (transformer stays the first/default
metric the driver parses).
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

from bench import measure_trials, peak_flops_per_chip


def program_matmul_flops(block):
    """Forward FLOPs of one pass: sum over conv2d (2*N*Ho*Wo*Co*Ci*kh*kw)
    and mul/matmul (2*M*K*N) ops, from the IR's inferred var shapes."""
    flops = 0
    for op in block.ops:
        if op.type in ("conv2d", "depthwise_conv2d"):
            filt = block.var(op.input("Filter")[0])
            out = block.var(op.output("Output")[0])
            # filter is [Co, Ci/groups, kh, kw] — ci is already the
            # per-group fan-in, so no further division by groups
            co, ci, kh, kw = filt.shape
            n, _, ho, wo = out.shape
            flops += 2 * n * ho * wo * co * ci * kh * kw
        elif op.type in ("mul", "matmul"):
            x = block.var(op.input("X")[0])
            y = block.var(op.input("Y")[0])
            k, n = y.shape[-2], y.shape[-1]
            m = int(np.prod(x.shape)) // k
            flops += 2 * m * k * n
    return flops


def main():
    import jax
    prec = os.environ.get("PADDLE_TPU_MATMUL_PRECISION")
    if prec:
        jax.config.update("jax_default_matmul_precision", prec)
    import paddle_tpu as fluid
    from paddle_tpu.models import resnet as R

    on_tpu = any(d.platform != "cpu" for d in jax.devices())
    if on_tpu:
        batch = int(os.environ.get("PADDLE_TPU_BENCH_BATCH", "256"))
        image_shape, class_dim, depth = (3, 224, 224), 1000, 50
        # 24 steps/dispatch: one dispatch+sync round trip is amortized
        # over the window (its cost on the v5e host: not measured)
        warmup_calls, steps = 2, int(
            os.environ.get("PADDLE_TPU_BENCH_STEPS", "24"))
    else:  # tiny smoke config for dev machines
        batch, image_shape, class_dim, depth = 4, (3, 32, 32), 10, 18
        warmup_calls, steps = 1, 2

    main_prog = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main_prog, startup):
        avg_cost, acc, feeds = R.resnet_train_program(
            batch, class_dim=class_dim, depth=depth,
            image_shape=image_shape)
        opt = fluid.optimizer.Momentum(learning_rate=0.01, momentum=0.9)
        opt.minimize(avg_cost)
    fwd_flops = program_matmul_flops(main_prog.global_block())
    main_prog.amp = on_tpu  # bf16 compute, f32 master weights

    rng = np.random.RandomState(0)
    stacked = {
        "image": rng.rand(steps, batch, *image_shape).astype("float32"),
        "label": rng.randint(0, class_dim,
                             size=(steps, batch, 1)).astype("int64"),
    }

    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        stacked = {k: jax.device_put(v) for k, v in stacked.items()}
        for _ in range(warmup_calls):
            exe.run_steps(main_prog, feed=stacked,
                          fetch_list=[avg_cost.name], steps=steps)

        last = [None]

        def run_once():
            # run_steps returns numpy (blocks on device) — no extra sync
            # needed before the clock
            last[0] = exe.run_steps(main_prog, feed=stacked,
                                    fetch_list=[avg_cost.name], steps=steps)

        dt, trial_dts = measure_trials(run_once)
        loss = np.asarray(last[0][0])[-1]
        # scope-attributed whole-step device time (executor pt_step scope);
        # best-effort — the headline wall metric must survive a host
        # without the xplane protobuf package
        dev_s = 0.0
        if on_tpu:
            try:
                from paddle_tpu import profiler
                dev_s = profiler.measure_device_seconds(run_once,
                                                        scope="pt_step")
            except Exception as e:
                print(f"# device-time probe unavailable: {e!r}",
                      file=sys.stderr)

    images = batch * steps
    images_per_sec = images / dt
    flops_per_image = 3 * fwd_flops / batch  # fwd + dfilter + dinput convs
    mfu = images_per_sec * flops_per_image / peak_flops_per_chip()

    print(json.dumps({
        "metric": "resnet50_images_per_sec_per_chip",
        "value": round(images_per_sec, 2),
        "unit": "images/sec",
        "vs_baseline": round(mfu / 0.45, 4),
    }))
    step_mss = ", ".join(f"{t / steps * 1e3:.1f}" for t in trial_dts)
    dev_ms = dev_s / steps * 1e3 if dev_s else float("nan")
    print(f"# loss={float(np.asarray(loss).reshape(()))}"
          f" mfu={mfu:.3f} fwd_gflops_per_image={fwd_flops / batch / 1e9:.2f}"
          f" step_ms_median={dt / steps * 1e3:.1f}"
          f" device_ms={dev_ms:.1f}"
          f" trials=[{step_mss}]", file=sys.stderr)


if __name__ == "__main__":
    main()
