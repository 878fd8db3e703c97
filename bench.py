"""Benchmark driver: Transformer-base training throughput on one chip.

Prints ONE JSON line:
  {"metric": "transformer_base_tokens_per_sec_per_chip", "value": N,
   "unit": "tokens/sec", "vs_baseline": R}

``vs_baseline`` is achieved MFU / 0.45 — the BASELINE.json north-star target
(Transformer-base >=45% MFU).  MFU uses 6*matmul_params + attention FLOPs
per token against the chip's peak, where matmul_params excludes the input
embeddings (gather, not matmul) and layernorm scale/bias — see
``models.transformer.matmul_param_count``.  Timing is the median of
``PADDLE_TPU_BENCH_TRIALS`` (default 5) measured trials after warmup; when
the trial spread exceeds 3x (a transient hit the chip) a second round is
run and merged before taking the median.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np


def peak_flops_per_chip():
    """Best-effort peak (bf16) FLOP/s for the local accelerator.

    Lives in the library now (``paddle_tpu.obs.perf`` — the live
    ``train.mfu`` gauge and this bench must share one denominator);
    kept here as a delegate for the sibling bench scripts.  The CPU
    fallback value is finite but meaningless — every recorded run is
    tagged with its ``mfu_basis`` and ``bench check`` refuses to
    compare records across bases."""
    from paddle_tpu.obs.perf import peak_flops_per_chip as _peak
    return _peak()


def measure_trials(run_once, n_trials=None):
    """Robust wall-clock measurement shared by all benchmarks: time
    ``n_trials`` calls of ``run_once`` (default from PADDLE_TPU_BENCH_TRIALS,
    5); when the spread exceeds 3x (a transient on the host), run one
    more round and merge before taking the median.  ``run_once`` must
    block until device completion.  Returns (median_seconds, all_trials).
    """
    import os
    if n_trials is None:
        n_trials = int(os.environ.get("PADDLE_TPU_BENCH_TRIALS", "5"))

    def one_round():
        dts = []
        for _ in range(max(1, n_trials)):
            t0 = time.perf_counter()
            run_once()
            dts.append(time.perf_counter() - t0)
        return dts

    trial_dts = one_round()
    if len(trial_dts) >= 2 and max(trial_dts) > 3 * min(trial_dts):
        trial_dts += one_round()
    return float(np.median(trial_dts)), trial_dts


def main():
    import argparse
    import os

    model = os.environ.get("PADDLE_TPU_BENCH_MODEL", "transformer") \
        or "transformer"
    if model != "transformer":
        import importlib
        modules = {"resnet": "bench_resnet", "lstm": "bench_lstm",
                   "seq2seq": "bench_seq2seq"}
        if model not in modules:
            raise SystemExit(
                f"PADDLE_TPU_BENCH_MODEL={model!r}: valid values are "
                f"transformer, {', '.join(modules)}")
        importlib.import_module(modules[model]).main()
        return
    from paddle_tpu.obs import bench_history
    parser = argparse.ArgumentParser(description="transformer training "
                                                 "throughput bench")
    bench_history.add_record_args(parser)
    args, _unknown = parser.parse_known_args()
    import jax
    # optional precision override (measured per-chip; f32 already uses the
    # MXU via bf16 passes on TPU)
    prec = os.environ.get("PADDLE_TPU_MATMUL_PRECISION")
    if prec:
        jax.config.update("jax_default_matmul_precision", prec)
    import paddle_tpu as fluid
    from paddle_tpu.executor import enable_compile_cache
    from paddle_tpu.models import transformer as T

    enable_compile_cache(entry_point=True)
    on_tpu = any(d.platform != "cpu" for d in jax.devices())
    hp = T.ModelHyperParams()
    if on_tpu:
        # operating-point overrides (the model takes the fused attention
        # op wherever the packed kernels admit the shape, S 256 included:
        # BENCH_ATTENTION.md)
        batch = int(os.environ.get("PADDLE_TPU_BENCH_BATCH", "256"))
        seq = int(os.environ.get("PADDLE_TPU_BENCH_SEQ", "256"))
        hp.max_length = max(hp.max_length, seq)
        warmup_calls, steps = 2, 16
    else:  # tiny smoke config for dev machines
        hp.d_model, hp.d_inner_hid, hp.n_layer = 64, 128, 2
        hp.n_head, hp.d_key, hp.d_value = 4, 16, 16
        hp.src_vocab_size = hp.trg_vocab_size = 1000
        batch, seq = 4, 32
        warmup_calls, steps = 1, 4

    # input mode: "memory" (default) stages pre-stacked device arrays;
    # "recordio" exercises the full reader-op pipeline (recordio file ->
    # open_recordio_file -> double_buffer -> read ops feeding run_steps)
    input_mode = os.environ.get("PADDLE_TPU_BENCH_INPUT", "memory")

    main_prog = fluid.Program()
    startup = fluid.Program()
    batches = [T.fake_batch(batch, seq, seq, hp, seed=s)
               for s in range(steps)]
    keys = ["src_word", "trg_word", "src_mask", "lbl_word", "lbl_weight"]
    recordio_path = None
    if input_mode == "recordio":
        import tempfile
        from paddle_tpu.recordio_writer import (
            convert_reader_to_recordio_file)
        recordio_path = os.path.join(tempfile.mkdtemp(), "bench.recordio")

        def _samples():
            # one record per STEP batch; the file holds warmup_calls+1
            # passes and the reader's pass_num=10**6 REWINDS it, which is
            # what keeps measured trials 2..N supplied with data
            for _ in range(warmup_calls + 1):
                for b in batches:
                    yield tuple(b[k] for k in keys)

        # RAW chunks: zlib decode of ~20MB/call would dominate the host
        # side of the pipeline
        convert_reader_to_recordio_file(recordio_path, _samples,
                                        compressor=0)

    with fluid.program_guard(main_prog, startup):
        input_vars = None
        if input_mode == "recordio":
            from paddle_tpu import layers as L
            reader = L.open_recordio_file(
                filename=recordio_path,
                shapes=[(batch, seq), (batch, seq), (batch, seq),
                        (batch, seq), (batch, seq)],
                lod_levels=[0] * 5,
                dtypes=["int32", "int32", "float32", "int32", "float32"],
                pass_num=10**6)
            reader = L.double_buffer(reader, capacity=steps + 2)
            input_vars = L.read_file(reader)
        avg_cost, _ = T.transformer(batch, seq, seq, hp,
                                    input_vars=input_vars)
        opt = fluid.optimizer.Adam(learning_rate=1e-4)
        opt.minimize(avg_cost)
    # bf16 compute with f32 master weights (mixed precision)
    main_prog.amp = on_tpu

    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        # distinct batches, stacked on a leading step axis and staged to
        # the device ONCE; the training loop then runs on-device
        # (Executor.run_steps = lax.scan over the step with donated state),
        # so per-step host->device latency is off the measured path — the
        # double-buffered-reader discipline of the reference
        # (operators/reader/create_double_buffer_reader_op.cc), TPU-style.
        if input_mode == "recordio":
            stacked = {}
        else:
            stacked = {k: jax.device_put(np.stack([b[k] for b in batches]))
                       for k in batches[0]}
        for _ in range(warmup_calls):
            exe.run_steps(main_prog, feed=stacked,
                          fetch_list=[avg_cost.name], steps=steps)
        # Robustness: a single-trial wall clock can be poisoned by a
        # transient on the host.  Run several trials and report the
        # median; print per-trial stats to stderr.
        last_losses = [None]

        def run_once():
            # run_steps returns numpy (return_numpy=True), which blocks
            # on the device — no extra sync needed before the clock.
            last_losses[0] = exe.run_steps(
                main_prog, feed=stacked,
                fetch_list=[avg_cost.name], steps=steps)

        dt, trial_dts = measure_trials(run_once)
        loss = np.asarray(last_losses[0][0])[-1]

    tokens = batch * seq * steps  # target-side tokens, the NMT convention
    tokens_per_sec = tokens / dt

    # FLOPs/token: the analytical 6N-matmul + attention accounting,
    # shared with the library (models.transformer.train_flops_per_token
    # — the cross-check test in tests/test_perf.py holds it against the
    # XLA cost_analysis FLOPs of the compiled step).  With src_len ==
    # trg_len, each counted (target) token pairs with one source token,
    # so encoder work per counted token is the full encoder stack.
    from paddle_tpu.obs import perf as _perf
    n_params = T.param_count(hp)
    n_matmul = T.matmul_param_count(hp)
    flops_per_token = T.train_flops_per_token(hp, seq)
    peak, mfu_basis = _perf.peak_flops_info()
    mfu = tokens_per_sec * flops_per_token / peak
    # the DEVICE-side view of the same run: the live gauge derived from
    # the compiled step's cost-analysis FLOPs, and the compile wall
    # time this cold process paid (both guarded by `bench check`)
    from paddle_tpu.profiler import runtime_metrics
    measured_mfu = runtime_metrics.gauge("train.mfu")
    compile_seconds = _perf.total_compile_seconds()

    print(json.dumps({
        "metric": "transformer_base_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 2),
        "unit": "tokens/sec",
        "vs_baseline": round(mfu / 0.45, 4),
    }))
    step_mss = ", ".join(f"{t / steps * 1e3:.1f}" for t in trial_dts)
    print(f"# loss={float(np.asarray(loss).reshape(()))}"
          f" mfu={mfu:.3f} mfu_basis={mfu_basis}"
          f" measured_mfu={'-' if measured_mfu is None else round(measured_mfu, 4)}"
          f" compile_s={compile_seconds:.1f}"
          f" params={n_params / 1e6:.1f}M"
          f" matmul_params={n_matmul / 1e6:.1f}M"
          f" step_ms_median={dt / steps * 1e3:.1f}"
          f" trials=[{step_mss}]", file=sys.stderr)
    summary = {"tokens_per_sec_per_chip": tokens_per_sec, "mfu": mfu,
               "measured_mfu": measured_mfu,
               "compile_seconds": compile_seconds}
    bench_history.record_from_args("train_transformer", summary, args,
                                   source="bench.py",
                                   mfu_basis=mfu_basis)


if __name__ == "__main__":
    main()
