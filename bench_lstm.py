"""Stacked dynamic-LSTM text-classification benchmark — the reference's
RNN anchor (``benchmark/README.md:112-118``: 2xLSTM+fc, IMDB, dict 30k,
seq len 100, batch 64; K40m: 83 / 184 / 641 ms/batch at hidden
256 / 512 / 1280) on one TPU chip, through the BUCKETED dynamic-LoD
path (lod.py) — the distinctive ragged-tensor workload this framework
carries a LoD subsystem for.

Methodology: every batch has fresh random lengths (2..100); a WINDOW of
``WINDOW`` batches pads to one bucket signature and runs as ONE
``run_steps`` device dispatch (the executor's streaming ragged mode,
r5), so the host's dispatch+sync round trip is paid once per window
instead of once per batch.  Wall per batch is reported over the window;
the bucketed-vs-exact-static masking tax is measured in scope-attributed
DEVICE time (profiler.scope_device_seconds) since the static path must
run per-batch.

Prints one JSON line (driver convention) for hidden=512 — the middle
anchor — and the other operating points to stderr:
  {"metric": "stacked_lstm_ms_per_batch_h512", ...,
   "vs_baseline": K40m_ms / our_ms}

Model config mirrors ``benchmark/fluid/stacked_dynamic_lstm.py``
(emb 512, Adam) with the README table's 2-layer stack; peepholes on
(the README calls out peephole lstmemory).
"""

from __future__ import annotations

import json
import sys

import numpy as np

DICT, EMB, LAYERS, BATCH, SEQ = 30000, 512, 2, 64, 100
WINDOW = 16
K40M_MS = {256: 83.0, 512: 184.0, 1280: 641.0}


def _ragged_batches(n, seed):
    from paddle_tpu.models.stacked_lstm import fake_batch
    return [fake_batch(BATCH, SEQ, DICT, seed=seed + i) for i in range(n)]


def _build(hidden, bucketed):
    import paddle_tpu as fluid
    from paddle_tpu.models.stacked_lstm import stacked_lstm_net
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        avg_cost, acc, _ = stacked_lstm_net(
            DICT, emb_dim=EMB, hidden_dim=hidden, n_layers=LAYERS)
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(avg_cost)
    main.lod_buckets = bucketed
    return main, startup, avg_cost


def bench_dynamic(hidden, n_windows=4):
    """Bucketed streaming: wall ms/batch over run_steps windows, plus
    one traced window's device ms/batch."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu import profiler
    import bench

    main, startup, avg_cost = _build(hidden, bucketed=True)
    windows = [_ragged_batches(WINDOW, seed=100 * w)
               for w in range(n_windows)]
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)

        def feed_of(w):
            return {
                "words": [b["words"] for b in windows[w]],
                "label": np.stack([b["label"] for b in windows[w]]),
            }

        for w in range(n_windows):       # compile every window signature
            exe.run_steps(main, feed=feed_of(w),
                          fetch_list=[avg_cost.name], steps=WINDOW)
        k = [0]

        def run_once():
            exe.run_steps(main, feed=feed_of(k[0] % n_windows),
                          fetch_list=[avg_cost.name], steps=WINDOW)
            k[0] += 1

        dt, _ = bench.measure_trials(run_once, n_trials=5)
        dev_s = profiler.measure_device_seconds(run_once, scope="ptop_")
    return dt * 1e3 / WINDOW, dev_s * 1e3 / WINDOW


def bench_static_device(hidden, n_meas=6):
    """Exact static LoD (all sequences SEQ tokens, one compile):
    scope-attributed device ms/batch — the masking-tax reference point."""
    import paddle_tpu as fluid
    from paddle_tpu import profiler

    main, startup, avg_cost = _build(hidden, bucketed=False)
    rng = np.random.RandomState(11)
    splits = [int(s) for s in np.arange(BATCH + 1) * SEQ]
    feeds = [{
        "words": (rng.randint(0, DICT, (BATCH * SEQ, 1)).astype("int64"),
                  [splits]),
        "label": rng.randint(0, 2, (BATCH, 1)).astype("int64"),
    } for _ in range(n_meas)]
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        for b in feeds[:2]:
            exe.run(main, feed=b, fetch_list=[avg_cost.name])

        def run_all():
            for b in feeds:
                exe.run(main, feed=b, fetch_list=[avg_cost.name])

        dev_s = profiler.measure_device_seconds(run_all, scope="ptop_")
    return dev_s * 1e3 / n_meas


def main():
    import os
    import jax
    global DICT, EMB, BATCH, SEQ, WINDOW
    hiddens = tuple(int(h) for h in os.environ.get(
        "PADDLE_TPU_LSTM_HIDDENS", "256,512,1280").split(","))
    if not any(d.platform != "cpu" for d in jax.devices()):
        DICT, EMB, BATCH, SEQ, WINDOW = 1000, 32, 8, 12, 4
        hiddens = (32,)
    for hidden in hiddens:
        dyn_ms, dyn_dev = bench_dynamic(hidden)
        static_dev = bench_static_device(hidden)
        base = K40M_MS.get(hidden)
        line = {
            "metric": f"stacked_lstm_ms_per_batch_h{hidden}",
            "value": round(dyn_ms, 3), "unit": "ms/batch",
            "vs_baseline": round(base / dyn_ms, 2) if base else None,
            "device_ms": round(dyn_dev, 3),
            "static_device_ms": round(static_dev, 3),
            "masking_tax": round(dyn_dev / static_dev, 3)
            if static_dev else None,
        }
        print(json.dumps(line),
              file=sys.stdout if hidden == 512 else sys.stderr)


if __name__ == "__main__":
    main()
