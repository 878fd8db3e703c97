#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that paddle_tpu still starts on the chip.

One command, ONE process that holds the chip from start to end (the server
runs in-process on threads through the same constructor the ``serve``
subcommand uses; no child process ever wants the device):

    python chip_smoke.py               # one TPU chip: phases 0, 1, 2
    python chip_smoke.py --four-chips  # four chips: the mesh phase ONLY

Phases
  0  device     jax sees a TPU whose ``device_kind`` is in the peak table
  1  trainer    Transformer-base (512/6+6/8/2048, vocab 10000), Adam, bf16
                AMP over f32 masters, batch 256 x seq 256, ``run_steps``
                windows: losses finite and falling, parameters on the TPU;
                then batch 32 x seq 1024, where the in-model flash forward
                AND backward kernels must be compiled (``tpu_custom_call``
                in the step's HLO) and agree with the composed-XLA build
  2  server     ``export_gen_model`` at a width the paged kernel admits
                (8 heads x 128, d_model 1024, 8 layers, vocab 32000) ->
                ``InferenceServer(warmup=True)`` -> concurrent
                ``ServingClient.generate`` streams, token-identical to the
                cache-free greedy reference, ``gen.paged.fallback == 0``,
                the decode executable holding the Pallas kernel
  4c mesh       (``--four-chips`` only) Transformer-base, 3 Adam steps on a
                dp2 x tp2 ``ParallelExecutor`` vs one device, same seed

The script has no CPU mode: without a TPU it exits non-zero at phase 0 and
prints no result.  The first failing phase raises and the exit code is
non-zero.  Everything observed (losses, step seconds, compile seconds,
counters) goes on earlier stdout lines, one JSON object each; the LAST line
is exactly ``{"ok": true, "device": {"platform", "kind", "count"}}``.

Sizes are arguments of the phase functions, so a CPU test
(``tests/test_tpu_compile.py``) drives the same control flow at toy sizes
with ``expect_chip`` stubbed.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import tempfile
import threading
import time

import numpy as np


class SmokeFailure(AssertionError):
    """A phase's check failed; ``main`` lets it propagate (exit != 0)."""


def check(cond, what):
    """A correctness check that holds on any backend."""
    if not cond:
        raise SmokeFailure(what)


def expect_chip(cond, what):
    """A check only the chip can satisfy (device placement, compiled
    Pallas kernels, fallback counters at real widths).  The CPU rehearsal
    test stubs exactly this function and nothing else."""
    if not cond:
        raise SmokeFailure(f"[chip] {what}")


def say(phase, **facts):
    """One observation line (never the last line of the run)."""
    print(json.dumps({"phase": phase, **facts}), flush=True)


def _counter(name):
    from paddle_tpu.profiler import runtime_metrics
    return runtime_metrics.counter(name)


def _hlo_texts(exe, also=()):
    """HLO text of every executable ``exe`` has compiled (and of the
    compiled functions in ``also``: a predictor's decode turns), keyed by
    the compile record's label (feed shapes + fetches)."""
    out = {}
    for entry in list(exe._cache.values()) + list(also):
        holder = getattr(entry, "perf", None)
        if holder and holder.get("exec") is not None:
            out[holder["label"]] = holder["exec"].as_text()
    return out


def _on_tpu(arr):
    devs = getattr(arr, "devices", None)
    return devs is not None and all(d.platform == "tpu" for d in devs())


# ---------------------------------------------------------------------------
# phase 0 — device
# ---------------------------------------------------------------------------

def phase_device(want_count):
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    expect_chip(info["platform"] == "tpu",
                f"jax found no TPU: {info}")
    expect_chip(info["count"] == want_count,
                f"this mode needs {want_count} chip(s), jax sees "
                f"{info['count']}")
    from paddle_tpu.obs import perf
    peak, basis = perf.peak_flops_info()  # raises on an unknown TPU kind
    expect_chip(basis == "tpu-peak", f"peak basis {basis!r}")
    say("device", **info, peak_bf16_flops=peak)
    return info


# ---------------------------------------------------------------------------
# phase 1 — trainer
# ---------------------------------------------------------------------------

def _transformer_hp(overrides=None):
    from paddle_tpu.models import transformer as T
    hp = T.ModelHyperParams()     # Transformer-base as the model file has it
    for k, v in (overrides or {}).items():
        setattr(hp, k, v)
    return hp


def _build_train(hp, batch, seq, seed, lr):
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer as T
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        avg_cost, _ = T.transformer(batch, seq, seq, hp)
        fluid.optimizer.Adam(learning_rate=lr).minimize(avg_cost)
    return main, startup, avg_cost


def phase_trainer(batch=256, seq=256, steps=8, calls=3,
                  long_batch=32, long_seq=1024, hp_overrides=None, seed=7):
    """Each sub-phase is its own frame and is collected before the next:
    at these sizes one training step's temporaries take ~14 of the
    chip's 16 GB, so nothing of the previous build may stay resident."""
    hp = _transformer_hp(hp_overrides)
    _trainer_windows(hp, batch, seq, steps, calls, seed)
    gc.collect()
    _trainer_long(hp_overrides, long_batch, long_seq, seed)
    gc.collect()
    _flash_kernel_parity(min(long_batch, 4), hp.n_head, long_seq, hp.d_key)


def _trainer_windows(hp, batch, seq, steps, calls, seed):
    """1a: ``run_steps`` windows at the flagship operating point."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer as T
    from paddle_tpu.place import TPUPlace

    main, startup, avg_cost = _build_train(hp, batch, seq, seed, lr=1e-4)
    main.amp = True     # bf16 compute, f32 master weights
    batches = [T.fake_batch(batch, seq, seq, hp, seed=s)
               for s in range(steps)]
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        expect_chip(isinstance(exe.place, TPUPlace),
                    f"Executor() chose {exe.place!r}")
        exe.run(startup)
        stacked = {k: jax.device_put(np.stack([b[k] for b in batches]),
                                     exe.place.jax_device())
                   for k in batches[0]}
        means = []
        for call in range(calls):
            t0 = time.perf_counter()
            (losses,) = exe.run_steps(main, feed=stacked,
                                      fetch_list=[avg_cost.name],
                                      steps=steps)  # numpy: blocks
            dt = time.perf_counter() - t0
            losses = np.asarray(losses, np.float64).reshape(-1)
            check(np.all(np.isfinite(losses)), f"non-finite loss {losses}")
            means.append(float(losses.mean()))
            say("trainer", call=call, steps=steps, batch=batch, seq=seq,
                amp="bf16", window_seconds=dt, step_seconds=dt / steps,
                includes_compile=(call == 0),
                loss_first=float(losses[0]), loss_last=float(losses[-1]))
        check(means[-1] < means[0],
              f"loss did not fall over {calls} windows: {means}")
        params = [p.name for p in main.global_block().all_parameters()]
        check(params, "program has no parameters")
        off = [n for n in params if not _on_tpu(scope.find_var(n))]
        expect_chip(not off, f"parameters not on the TPU: {off[:5]}")
        gates = {"attention.flash_fallback":
                 _counter("attention.flash_fallback")}
        say("trainer", params=len(params), window_loss_means=means, **gates)


def _trainer_long(hp_overrides, long_batch, long_seq, seed):
    """1b: a long sequence takes the in-model flash forward AND backward
    kernels; compared with the composed-XLA build of the same step.
    Dropout is off in BOTH builds: the two op sequences draw their
    dropout keys at different op indices, so parity must come from the
    math."""
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer as T

    fb0 = _counter("attention.flash_fallback")
    feed = T.fake_batch(long_batch, long_seq, long_seq,
                        _transformer_hp(hp_overrides), seed=seed)
    got = {}
    for use_flash in (True, False):
        hpl = _transformer_hp(hp_overrides)
        hpl.max_length = long_seq
        hpl.use_flash = use_flash
        hpl.dropout = 0.0
        m, s, cost = _build_train(hpl, long_batch, long_seq, seed, lr=1e-4)
        m.amp = True
        sc = fluid.Scope()
        with fluid.scope_guard(sc):
            ex = fluid.Executor()   # fresh: both builds see run counter 1
            ex.run(s)
            t0 = time.perf_counter()
            traj = [float(np.asarray(ex.run(m, feed=feed,
                                            fetch_list=[cost.name])[0])
                          .reshape(())) for _ in range(2)]
            dt = time.perf_counter() - t0
            hlo = "\n".join(_hlo_texts(ex).values())
        del sc, ex
        gc.collect()    # the composed build needs the room
        kernel = "tpu_custom_call" in hlo
        got[use_flash] = traj
        say("trainer_long", use_flash=use_flash, batch=long_batch,
            seq=long_seq, losses=traj, seconds_with_compile=dt,
            tpu_custom_call=kernel)
        check(np.all(np.isfinite(traj)), f"non-finite loss {traj}")
        expect_chip(kernel == use_flash,
                    f"use_flash={use_flash} but tpu_custom_call in the "
                    f"step's HLO is {kernel}")
    check(_counter("attention.flash_fallback") == fb0,
          "the flash block gate refused the long shape (XLA path taken)")
    # the second loss follows one Adam update, so it also sees the
    # backward kernels; bf16 tolerance
    np.testing.assert_allclose(got[True], got[False], rtol=1e-2,
                               err_msg="flash vs composed loss")


def _flash_kernel_parity(B, H, S, D):
    """Kernel-level fwd+bwd parity of the flash path against plain XLA on
    random bf16 inputs (what tests/test_flash_tpu.py used to run in a
    child process on the chip)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.attention_ops import fused_attention
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (B, H, S, D),
                                 jnp.bfloat16) for i in range(3))
    mask = jnp.ones((B, S), jnp.bfloat16)

    def loss(use_pallas, q, k, v):
        out = fused_attention(q, k, v, mask, True, D ** -0.5, use_pallas)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    vals, grads = {}, {}
    for use in (True, False):
        f = jax.jit(jax.value_and_grad(
            lambda q, k, v, use=use: loss(use, q, k, v), argnums=(0, 1, 2)))
        vals[use], grads[use] = f(q, k, v)
    np.testing.assert_allclose(float(vals[True]), float(vals[False]),
                               rtol=2e-2)
    worst = 0.0
    for a, b in zip(grads[True], grads[False]):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        scale = np.abs(b).max()
        np.testing.assert_allclose(a, b, rtol=1e-1, atol=0.1 * scale)
        # bf16 accumulation-order noise: bound the tail, not each element
        frac = float(np.mean(np.abs(a - b) > 0.02 * scale))
        check(frac < 1e-3, f"flash grad differs on {frac:.2%} of elements")
        worst = max(worst, frac)
    say("flash_kernel", shape=[B, H, S, D], loss_flash=float(vals[True]),
        loss_xla=float(vals[False]), grad_off_fraction=worst)


# ---------------------------------------------------------------------------
# phase 2 — server, paged decode
# ---------------------------------------------------------------------------

def _ref_greedy(predictor, prompt, n):
    """Cache-free reference: re-run the prefill program over the growing
    sequence (tests/test_gen.py's reference, same process)."""
    seq, out = list(prompt), []
    for _ in range(n):
        logits, _ = predictor.prefill(seq)
        t = int(np.argmax(logits))
        out.append(t)
        seq.append(t)
    return out


def _cached_decode_parity(predictor, prompt):
    """Logit-level companion of the token check (random weights can decode
    to a repeated token, which a broken cache might reproduce by luck):
    seed a slot from the prompt's prefill, take ONE cached decode step
    through the paged pool, and compare its logits with the re-prefill of
    the same sequence.  Returns the max abs difference over the logits'
    range."""
    n = len(prompt)
    logits, kv = predictor.prefill(prompt)
    tok = int(np.argmax(logits))
    predictor.alloc_slot_pages(0, predictor.pages_needed(n, 1))
    try:
        predictor.write_slot(0, kv, n)
        tokens, pos, lens = (np.zeros(predictor.num_slots, np.int32)
                             for _ in range(3))
        tokens[0], pos[0], lens[0] = tok, n, n + 1
        got = predictor.decode_step(tokens, pos, lens=lens)[0]
    finally:
        predictor.free_slot_pages(0)
    want, _ = predictor.prefill(list(prompt) + [tok])
    check(np.all(np.isfinite(got)) and got.shape == want.shape,
          f"decode logits {got.shape} vs {want.shape}")
    return float(np.abs(got - want).max() / (want.max() - want.min()))


def phase_server(n_head=8, d_head=128, d_ffn=4096, n_layer=8,
                 vocab_size=32000, max_len=1024, num_slots=8, page_len=16,
                 prompt_buckets=(32, 256, 1024), prompt_lens=(17, 200, 700),
                 new_tokens=32, seed=11):
    import jax
    from paddle_tpu.models import gen_lm
    from paddle_tpu.serving import InferenceServer, ServingClient

    # f32 weights: at the TPU's default precision an f32 matmul is ONE
    # bf16 pass, and the cached decode (VPU-exact paged kernel) and the
    # re-prefill reference (MXU scores) then differ by ~1e-3 — enough to
    # flip a greedy argmax over 32000 random logits.  "highest" makes
    # both paths f32-accurate, so token equality is a fair demand.
    jax.config.update("jax_default_matmul_precision", "highest")

    hp = gen_lm.GenConfig()
    hp.n_head, hp.d_head, hp.d_model = n_head, d_head, n_head * d_head
    hp.d_ffn, hp.n_layer = d_ffn, n_layer
    hp.vocab_size, hp.max_len = vocab_size, max_len
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(1, vocab_size, size=n).tolist()
               for n in prompt_lens]
    fb0 = _counter("gen.paged.fallback")

    with tempfile.TemporaryDirectory(prefix="chip_smoke_gen_") as tmp:
        t0 = time.perf_counter()
        bundle = gen_lm.export_gen_model(
            tmp + "/bundle", hp, num_slots=num_slots,
            prompt_buckets=list(prompt_buckets), page_len=page_len)
        t_export = time.perf_counter() - t0
        # what `paddle_tpu serve --model D --warmup` builds (cli._cmd_serve
        # -> serving.serve -> InferenceServer), minus serve_forever's block
        t0 = time.perf_counter()
        server = InferenceServer(bundle, port=0, warmup=True,
                                 request_timeout=600.0)
        server.start_background()
        try:
            check(server.wait_until_ready(900), "server not ready in 900 s")
            t_ready = time.perf_counter() - t0
            addr = "%s:%d" % tuple(server.addr[:2])
            say("server", export_seconds=t_export, ready_seconds=t_ready,
                d_model=hp.d_model, n_head=n_head, d_head=d_head,
                n_layer=n_layer, vocab=vocab_size, max_len=max_len,
                num_slots=num_slots, page_len=page_len,
                matmul_precision="highest")

            streams = [None] * len(prompts)
            errors = []

            def run_stream(i):
                try:
                    t_start = time.perf_counter()
                    toks, idxs, t_first, done = [], [], None, None
                    client = ServingClient(addr, timeout=300.0)
                    for ev in client.generate(prompts[i],
                                              max_new_tokens=new_tokens):
                        if "token" in ev:
                            if t_first is None:
                                t_first = time.perf_counter() - t_start
                            toks.append(int(ev["token"]))
                            idxs.append(int(ev["index"]))
                        elif ev.get("error"):
                            raise SmokeFailure(f"stream {i}: {ev['error']}")
                        elif ev.get("done"):
                            done = ev.get("finish_reason")
                    streams[i] = dict(tokens=toks, indices=idxs, done=done,
                                      first_token_seconds=t_first,
                                      seconds=time.perf_counter() - t_start)
                except Exception as e:   # re-raised in the main thread
                    errors.append(e)

            threads = [threading.Thread(target=run_stream, args=(i,),
                                        daemon=True)
                       for i in range(len(prompts))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(600)
            if errors:
                raise errors[0]
            check(not any(t.is_alive() for t in threads),
                  "a generate stream did not finish in 600 s")

            predictor = server.gen_predictor
            for i, (prompt, st) in enumerate(zip(prompts, streams)):
                want = _ref_greedy(predictor, prompt, new_tokens)
                say("server_stream", stream=i, prompt_len=len(prompt),
                    new_tokens=len(st["tokens"]), finish=st["done"],
                    first_token_seconds=st["first_token_seconds"],
                    stream_seconds=st["seconds"],
                    matches_reference=(st["tokens"] == want),
                    head=st["tokens"][:8])
                check(st["indices"] == list(range(new_tokens)),
                      f"stream {i}: token_index {st['indices']}")
                check(st["tokens"] == want,
                      f"stream {i}: tokens differ from the cache-free "
                      f"greedy reference:\n got  {st['tokens']}\n want "
                      f"{want}")

            # streams are done and evicted: the scheduler is idle, the
            # pool is ours for one direct step
            worst = max(_cached_decode_parity(predictor, p)
                        for p in prompts)
            say("server_logits", cached_vs_reprefill_max_rel_diff=worst)
            check(worst < 1e-3, f"cached decode logits differ from the "
                                f"re-prefill by {worst:.2e} of their range")

            stats = ServingClient(addr, timeout=60.0).stats()
            fallback = stats["counters"].get("gen.paged.fallback", 0) - fb0
            decode = {label: text for label, text
                      in _hlo_texts(predictor._exe,
                                    predictor._turns.values()).items()
                      if "gen_page_table" in label}
            kernels = {label: "tpu_custom_call" in text
                       for label, text in decode.items()}
            warm = stats["server"].get("warmup") or []
            say("server_stats", paged_fallback=fallback,
                decode_executables=len(decode),
                decode_with_kernel=sum(kernels.values()),
                warmup_programs=len(warm),
                warmup_seconds=sum(float(b.get("seconds") or 0.0)
                                   for b in warm),
                warmup_cache=sorted({str(b.get("cache")) for b in warm}),
                gen_tokens=stats["counters"].get("gen.tokens"))
            check(decode, "no decode executable was captured")
            expect_chip(fallback == 0,
                        f"gen.paged.fallback moved by {fallback}: a decode "
                        f"bucket lowered as the XLA gather")
            expect_chip(all(kernels.values()),
                        f"decode executables without tpu_custom_call: "
                        f"{[k for k, v in kernels.items() if not v]}")
        finally:
            server.shutdown()


# ---------------------------------------------------------------------------
# --four-chips — dp2 x tp2 ParallelExecutor vs one device
# ---------------------------------------------------------------------------

def phase_four_chips(batch=256, seq=256, n_steps=3, hp_overrides=None,
                     seed=1234):
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer as T
    from paddle_tpu.parallel import ParallelExecutor
    from paddle_tpu.parallel.mesh import make_mesh

    devs = jax.devices()
    expect_chip(len({d.id for d in devs[:4]}) == 4
                and all(d.platform == "tpu" for d in devs[:4]),
                f"need four distinct TPU devices, have {devs}")
    mesh = make_mesh((2, 2), ("data", "model"), devices=devs[:4])
    hp = _transformer_hp(hp_overrides)
    feeds = [T.fake_batch(batch, seq, seq, hp, seed=s)
             for s in range(n_steps)]

    def run(sharded):
        main, startup, cost = _build_train(hp, batch, seq, seed, lr=1e-3)
        main.amp = True     # as phase 1; f32 does not fit one chip here
        scope = fluid.Scope()
        facts = {}
        with fluid.scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup)
            # a fresh runner either way: both trajectories then draw
            # their dropout keys from run counters 1..n_steps
            if sharded:
                runner = ParallelExecutor(
                    loss_name=cost.name, main_program=main, mesh=mesh,
                    param_shardings=T.tp_shardings())
                step = lambda f: runner.run(feed=f, fetch_list=[cost.name])
            else:
                runner = fluid.Executor()
                step = lambda f: runner.run(main, feed=f,
                                            fetch_list=[cost.name])
            t0 = time.perf_counter()
            losses = [float(np.asarray(step(f)[0]).reshape(()))
                      for f in feeds]
            facts["seconds_with_compile"] = time.perf_counter() - t0
            if sharded:
                w = scope.find_var("enc0_ffn1.w")   # [d_model, d_inner]/tp
                shards = w.addressable_shards
                facts["shard_devices"] = sorted(
                    {s.device.id for s in shards})
                facts["shard_shape"] = list(shards[0].data.shape)
                facts["full_shape"] = list(w.shape)
                hlo = "\n".join(_hlo_texts(runner).values())
                facts["all_reduce"] = "all-reduce" in hlo
        return losses, facts

    sharded, facts = run(True)
    gc.collect()    # the single-device step needs ~15 of device 0's 16 GB
    single, sfacts = run(False)
    say("four_chips", mesh="data2 x model2", batch=batch, seq=seq, amp="bf16",
        losses_dp2_tp2=sharded, losses_single_device=single,
        single_seconds_with_compile=sfacts["seconds_with_compile"],
        **facts)
    check(np.all(np.isfinite(sharded)), f"non-finite loss {sharded}")
    # GSPMD reduces in a different tree order than one device: the
    # tolerance __graft_entry__.dryrun_multichip uses
    np.testing.assert_allclose(sharded, single, rtol=3e-3, atol=1e-4,
                               err_msg="dp2 x tp2 vs single device")
    check(len(facts["shard_devices"]) > 1,
          f"tensor-parallel weight sits on {facts['shard_devices']}")
    check(facts["shard_shape"] == [facts["full_shape"][0],
                                  facts["full_shape"][1] // 2],
          f"shard {facts['shard_shape']} of {facts['full_shape']}")
    check(facts["all_reduce"], "no all-reduce in the sharded step's HLO")


# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run ONLY the dp2 x tp2 mesh phase and its "
                         "single-device comparison (needs four chips)")
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    device = phase_device(4 if args.four_chips else 1)
    from paddle_tpu.executor import enable_compile_cache, \
        resolve_compile_cache_dir
    enable_compile_cache(entry_point=True)
    if args.four_chips:
        phase_four_chips()
    else:
        phase_trainer()
        phase_server()
    from paddle_tpu.obs import perf
    say("compile", cache_dir=resolve_compile_cache_dir(entry_point=True),
        cache_hits=_counter("compile_cache.hits"),
        cache_misses=_counter("compile_cache.misses"),
        captured_compile_seconds=perf.total_compile_seconds(),
        total_seconds=time.perf_counter() - t_start)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
