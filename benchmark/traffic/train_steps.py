"""Traffic kind ``train_steps``: train one configuration on seeded batches
staged once on the device, in blocking calls repeated for the window.

Parameters (``benchmark/workloads/<cell>.json``):

``batch``            sequences per step PER CHIP
``seq``              tokens per sequence (source and target side alike)
``steps_per_call``   training steps per blocking call
``staged_batches``   distinct seeded batches, cycled
``runner``           ``run_steps``: ``Executor.run_steps`` (one dispatch,
                     ``steps_per_call`` steps on the device);
                     ``parallel_run``: ``ParallelExecutor.run`` once per
                     step over all the cell's chips on the ``data`` axis
                     (global batch = ``batch`` x chips)
``reference_rows``   sequences compared with the plain reference
``loss_rtol``        tolerance of that comparison (reason in the file)
``trace_calls``      blocking calls inside the traced part of a
                     ``--trace 1`` run, and in the traced stretch after
                     the measured window of a ``--trace 2`` run

End-to-end: ``train_tokens_per_s_per_chip`` = target-side tokens of every
call finished in the window over the window's seconds (the clock stops
when the last call returns), per chip.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from lib import counts  # noqa: E402
from reference import transformer_ref  # noqa: E402

FEEDS = ("src_word", "trg_word", "src_mask", "lbl_word", "lbl_weight")


def make_batch(cfg, batch, seq, seed):
    """One dense batch of full-length random sequences from ``seed``."""
    rng = np.random.RandomState(seed % (2 ** 32))
    word = lambda vocab: rng.randint(1, vocab, size=(batch, seq)) \
        .astype("int32")
    return {"src_word": word(cfg["src_vocab_size"]),
            "trg_word": word(cfg["trg_vocab_size"]),
            "src_mask": np.ones((batch, seq), "float32"),
            "lbl_word": word(cfg["trg_vocab_size"]),
            "lbl_weight": np.ones((batch, seq), "float32")}


def _hyper_params(cfg, seq):
    from paddle_tpu.models import transformer as T
    hp = T.ModelHyperParams()
    for key in ("d_model", "d_inner_hid", "n_head", "d_key", "d_value",
                "n_layer", "dropout", "attention_dropout",
                "src_vocab_size", "trg_vocab_size"):
        setattr(hp, key, cfg[key])
    hp.max_length = seq
    return hp


def _executables(exe):
    """The compiled executables ``exe`` holds (the program keeps them
    beside its jit cache entries)."""
    for entry in getattr(exe, "_cache", {}).values():
        holder = getattr(entry, "perf", None)
        if holder and holder.get("exec") is not None:
            yield holder["exec"]


def _hlo_has(exe, needle):
    """Whether any executable's HLO text holds ``needle``; None when the
    program kept no executable to ask."""
    texts = [e.as_text() for e in _executables(exe)]
    return any(needle in t for t in texts) if texts else None


def _program_peak_bytes(exe):
    """The compiler's account of the largest executable ``exe`` holds:
    arguments + outputs + temporaries - aliased bytes, per device."""
    totals = []
    for e in _executables(exe):
        m = e.memory_analysis()
        totals.append(int(m.argument_size_in_bytes + m.output_size_in_bytes
                          + m.temp_size_in_bytes - m.alias_size_in_bytes))
    return max(totals) if totals else None


def setup(ctx):
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer as T
    from paddle_tpu.profiler import runtime_metrics
    from paddle_tpu.obs import trace as ptrace

    cfg, wl, say = ctx["config"], ctx["workload"], ctx["say"]
    chips = ctx["chips"]
    per_chip, seq = int(wl["batch"]), int(wl["seq"])
    batch = per_chip * (chips if wl["runner"] == "parallel_run" else 1)
    steps = int(wl["steps_per_call"])
    hp = _hyper_params(cfg, seq)
    checks = {}

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = ctx["seed31"]
    with fluid.program_guard(main, startup):
        avg_cost, _ = T.transformer(batch, seq, seq, hp)
        test = main.clone(for_test=True)
        fluid.optimizer.Adam(learning_rate=cfg["learning_rate"]) \
            .minimize(avg_cost)
    main.amp = test.amp = True      # bf16 compute over f32 masters
    block = test.global_block()
    token_loss = next(op for op in block.ops
                      if op.type == "softmax_with_cross_entropy") \
        .output("Loss")[0]
    layer_norms = [(op.input("Scale")[0], op.input("Bias")[0])
                   for op in block.ops if op.type == "layer_norm"]

    batches = [make_batch(cfg, batch, seq, ctx["seed31"] + 7919 * i)
               for i in range(int(wl["staged_batches"]))]
    scope = fluid.Scope()
    state = {"scope": scope, "steps": steps, "batch": batch, "seq": seq, "checks": checks,
             "fallback0": runtime_metrics.counter("attention.flash_fallback"),
             "fluid": fluid, "metrics": runtime_metrics, "ptrace": ptrace}
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        if wl["runner"] == "parallel_run":
            from paddle_tpu.parallel import ParallelExecutor
            from paddle_tpu.parallel.mesh import make_mesh
            devices = ctx["devices"]
            checks["distinct_devices"] = \
                len({d.id for d in devices}) == chips
            mesh = make_mesh((chips,), ("data",), devices=devices)
            runner = ParallelExecutor(loss_name=avg_cost.name,
                                      main_program=main, mesh=mesh)
            tester = ParallelExecutor(main_program=test, mesh=mesh)
            forward = lambda feed: tester.run(feed=feed,
                                              fetch_list=[token_loss])

            def call(i):
                losses = []
                for s in range(steps):
                    feed = batches[(i * steps + s) % len(batches)]
                    losses.append(runner.run(feed=feed,
                                             fetch_list=[avg_cost.name])[0])
                return np.asarray(losses, np.float64).reshape(-1)
        else:
            runner = exe
            forward = lambda feed: exe.run(test, feed=feed,
                                           fetch_list=[token_loss])
            device = exe.place.jax_device()
            order = [i % len(batches) for i in range(steps)]
            stacked = {k: jax.device_put(
                np.stack([batches[i][k] for i in order]), device)
                for k in FEEDS}

            def call(i):
                (losses,) = exe.run_steps(main, feed=stacked,
                                          fetch_list=[avg_cost.name],
                                          steps=steps)   # numpy: blocks
                return np.asarray(losses, np.float64).reshape(-1)
        state["call"], state["runner"] = call, runner
        # the span that launches and waits for the step's executable,
        # and an op found only in it (lib/spanclock.agreement)
        state["clock_proof"] = \
            {"span": "executor.run", "holding": ["all-reduce"]} \
            if wl["runner"] == "parallel_run" \
            else {"span": "executor.run_steps", "holding": ["while"]}

        # -- correctness, outside the window: the test-mode forward of
        # the program against the plain reference on the first rows
        rows = min(int(wl["reference_rows"]), batch)
        got = np.asarray(forward(batches[0])[0], np.float64) \
            .reshape(batch, seq)[:rows].mean(axis=1)
        names = [p.name for p in main.global_block().all_parameters()]
        params = {n: scope.find_var(n) for n in names}
        head = {k: batches[0][k][:rows] for k in FEEDS}
        want, _ = jax.jit(
            lambda p, b: transformer_ref.forward_loss(p, layer_norms, cfg, b)
        )(params, head)
        want = np.asarray(want, np.float64)
        err = float(np.max(np.abs(got - want) / np.abs(want)))
        checks["reference_rel_err"] = err
        checks["reference_ok"] = bool(err <= float(wl["loss_rtol"]))
        checks["params_on_chip"] = ctx["rehearsal"] or all(
            d.platform == "tpu" for n in names
            for d in scope.find_var(n).devices())
        say("reference", rows=rows, program=got.tolist(),
            reference=want.tolist(), max_rel_err=err,
            tolerance=wl["loss_rtol"])

        # -- warm-up: the one shape the window uses
        t0 = time.perf_counter()
        warm = call(0)
        say("warmup", seconds=time.perf_counter() - t0,
            loss_first=float(warm[0]), loss_last=float(warm[-1]))
        state["warm_losses"] = warm
        # reported, never required: which lowering carries the step is
        # the program's choice, and the arithmetic is held by the checks
        # ``verify`` needs
        checks["kernel_in_hlo"] = _hlo_has(runner, "tpu_custom_call")
        if wl["runner"] == "parallel_run":
            checks["all_reduce_in_hlo"] = _hlo_has(runner, "all-reduce")
            w = scope.find_var("enc0_ffn1.w")
            checks["replicated_on"] = sorted(
                {s.device.id for s in w.addressable_shards})
    if ctx["traced"]:
        ptrace.enable(1 << 16)
        ptrace.clear()
    return state


def window(state, ctx):
    wl, tracer = ctx["workload"], ctx["tracer"]
    fluid, metrics = state["fluid"], state["metrics"]
    seconds, steps = ctx["seconds"], state["steps"]
    tokens_per_call = state["batch"] * state["seq"] * steps
    trace_calls = int(wl.get("trace_calls", 2))
    compile0 = metrics.counter("compile.events")
    miss0 = metrics.counter("compile_cache.misses")
    means, durations, traced_calls = [], [], 0
    with fluid.scope_guard(state["scope"]):
        t0 = time.perf_counter()
        i = 0
        while True:
            if ctx["traced"] and i == 1:
                tracer.start()
            t_call = time.perf_counter()
            losses = state["call"](i + 1)
            now = time.perf_counter()
            durations.append(now - t_call)
            means.append(float(losses.mean()))
            if not np.all(np.isfinite(losses)):
                raise FloatingPointError(f"non-finite loss {losses}")
            i += 1
            if ctx["traced"] and tracer.t_start is not None \
                    and tracer.t_stop is None:
                traced_calls += 1
                if traced_calls >= trace_calls:
                    tracer.stop()
            if now - t0 >= seconds:
                break
        elapsed = now - t0
    calls = i
    chips = ctx["chips"]
    rate = calls * tokens_per_call / elapsed / chips
    spans = state["ptrace"].snapshot_spans() if ctx["traced"] else []
    cfg = ctx["config"]
    return {
        "end_to_end": {"train_tokens_per_s_per_chip": rate},
        "attempted": calls * steps, "failed": 0,
        "program_peak_bytes": _program_peak_bytes(state["runner"]),
        "spans": spans,
        "counters": {
            "compile.events": metrics.counter("compile.events") - compile0,
            "compile_cache.misses":
                metrics.counter("compile_cache.misses") - miss0,
            "attention.flash_fallback":
                metrics.counter("attention.flash_fallback")
                - state["fallback0"]},
        "facts": {"batch_per_chip": int(wl["batch"]),
                  "global_batch": state["batch"], "seq": state["seq"],
                  "clock_proof": state["clock_proof"],
                  "steps_per_call": steps, "traced_calls": traced_calls,
                  "traced_steps": traced_calls * steps,
                  "tokens_per_step_per_chip": int(wl["batch"]) * state["seq"],
                  "train_flops_per_token":
                      counts.transformer_train_flops_per_token(cfg,
                                                               state["seq"]),
                  "attention_flops_per_step_per_chip":
                      counts.flash_attention_train_flops_per_step(
                          cfg, int(wl["batch"]), state["seq"])},
        "window_means": means,
        "observed": {"calls": calls, "elapsed_s": elapsed,
                     "step_ms_median": 1e3 * float(np.median(durations))
                     / steps,
                     "call_seconds": durations[:12],
                     "window_loss_means": means[:6]},
    }


def traced(state, ctx):
    """The traced stretch of a ``--trace 2`` run: ``trace_calls`` more
    blocking calls of the window's own ``call``, with the program's
    spans and the device profiler on."""
    ptrace, tracer = state["ptrace"], ctx["tracer"]
    calls = int(ctx["workload"].get("trace_calls", 2))
    ptrace.enable(1 << 16)
    ptrace.clear()
    try:
        with state["fluid"].scope_guard(state["scope"]):
            tracer.start()
            t0 = time.perf_counter()
            for i in range(calls):
                losses = state["call"](i + 1)
            elapsed = time.perf_counter() - t0
            tracer.stop()
        spans = ptrace.snapshot_spans()
    finally:
        ptrace.disable()
    if not np.all(np.isfinite(losses)):
        raise FloatingPointError(f"non-finite loss {losses}")
    return {"spans": spans,
            "facts": {"traced_calls": calls,
                      "traced_steps": calls * state["steps"],
                      "clock_proof": state["clock_proof"]},
            "observed": {"traced_step_ms": 1e3 * elapsed
                         / (calls * state["steps"])}}


def verify(state, ctx, raw):
    checks = dict(state["checks"])
    means = [float(state["warm_losses"].mean())] + raw["window_means"]
    checks["loss_means"] = means[:4]
    checks["loss_falls"] = len(means) >= 3 and means[2] < means[0]
    checks["no_compile_in_window"] = (
        raw["counters"]["compile.events"] == 0
        and raw["counters"]["compile_cache.misses"] == 0)
    checks["no_flash_fallback"] = \
        raw["counters"]["attention.flash_fallback"] == 0
    need = ["reference_ok", "params_on_chip", "no_compile_in_window",
            "no_flash_fallback"]
    if not ctx["rehearsal"]:    # a toy model at lr 1e-4 falls too slowly
        need.append("loss_falls")
    if ctx["workload"]["runner"] == "parallel_run":
        need += ["distinct_devices", "all_reduce_in_hlo"]
        checks["replicated_everywhere"] = \
            len(checks.get("replicated_on", [])) == ctx["chips"]
        need.append("replicated_everywhere")
    checks["correct"] = all(bool(checks.get(k)) for k in need)
    counted = raw["counters"]
    checks["compared"] = {
        "reference_rel_err": [checks.get("reference_rel_err"),
                              float(ctx["workload"]["loss_rtol"])],
        "loss_third_call_over_first": [
            means[2] / means[0] if len(means) >= 3 else None, 1.0],
        "compiles_in_window": [counted["compile.events"]
                               + counted["compile_cache.misses"], 0],
        "flash_fallbacks_in_window": [
            counted["attention.flash_fallback"], 0],
        "kernel_in_hlo": [checks.get("kernel_in_hlo"), None]}
    return checks


def close(state):
    state["ptrace"].disable()
