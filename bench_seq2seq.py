"""Attention seq2seq (NMT) benchmark — the reference's
``benchmark/fluid/machine_translation.py`` workload (bi-LSTM encoder +
DynamicRNN decoder with additive attention; emb/enc/dec 512, dict 30k,
batch 16) on one TPU chip through the bucketed dynamic-LoD path.

BASELINE.md carries no GPU anchor for this workload (the reference's
README only tables the LSTM classifier), so the JSON line reports
absolute target-tokens/sec; the point of the bench is that the
DISTINCTIVE ragged pipeline — DynamicRNN with runtime row-splits,
sequence_expand/softmax/pool attention per step — holds a production
number on chip.  Same windowed run_steps methodology as bench_lstm.py
(per-batch run() walls pay one dispatch+sync round trip per batch).
"""

from __future__ import annotations

import json
import sys

import numpy as np

SRC_DICT = TRG_DICT = 30000
EMB = ENC = DEC = 512
BATCH, SRC_MAX, TRG_MAX = 16, 50, 50
WINDOW = 8


def main():
    import os
    import jax
    global SRC_DICT, TRG_DICT, EMB, ENC, DEC, BATCH, SRC_MAX, TRG_MAX
    global WINDOW
    on_tpu = any(d.platform != "cpu" for d in jax.devices())
    if not on_tpu:
        SRC_DICT = TRG_DICT = 500
        EMB = ENC = DEC = 16
        BATCH, SRC_MAX, TRG_MAX, WINDOW = 4, 10, 10, 3

    import paddle_tpu as fluid
    from paddle_tpu.models.seq2seq import seq_to_seq_net, fake_batch
    import bench

    def run_point(batch):
        main_prog, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main_prog, startup):
            avg_cost, _ = seq_to_seq_net(SRC_DICT, TRG_DICT, emb_dim=EMB,
                                         encoder_size=ENC,
                                         decoder_size=DEC)
            fluid.optimizer.Adam(learning_rate=1e-3).minimize(avg_cost)
        main_prog.lod_buckets = True

        n_windows = 3
        windows = [[fake_batch(batch, SRC_MAX, TRG_MAX, SRC_DICT,
                               TRG_DICT, seed=50 * w + i)
                    for i in range(WINDOW)] for w in range(n_windows)]

        def feed_of(w):
            return {k: [b[k] for b in windows[w]]
                    for k in ("src_word", "trg_word", "label")}

        def trg_tokens(w):
            return sum(b["trg_word"][1][0][-1] for b in windows[w])

        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup)
            for w in range(n_windows):
                exe.run_steps(main_prog, feed=feed_of(w),
                              fetch_list=[avg_cost.name], steps=WINDOW)
            k = [0]

            def run_once():
                exe.run_steps(main_prog, feed=feed_of(k[0] % n_windows),
                              fetch_list=[avg_cost.name], steps=WINDOW)
                k[0] += 1

            dt, _ = bench.measure_trials(run_once, n_trials=4)
        toks = np.mean([trg_tokens(w) for w in range(n_windows)])
        return toks / dt, dt * 1e3 / WINDOW

    # the reference operating point (batch 16) on stdout; batch 64 shows
    # the same program is batch-scalable (the 16-point is latency-bound
    # by the serial decoder, not a framework ceiling)
    for batch in [BATCH] + ([BATCH * 4] if BATCH >= 16 else []):
        tps, mspb = run_point(batch)
        line = json.dumps({
            "metric": f"seq2seq_attention_tokens_per_sec_per_chip"
                      + ("" if batch == BATCH else f"_b{batch}"),
            "value": round(tps, 2), "unit": "tokens/sec",
            "vs_baseline": None,
            "ms_per_batch": round(mspb, 3), "batch": batch,
        })
        print(line, file=sys.stdout if batch == BATCH else sys.stderr)


if __name__ == "__main__":
    main()
