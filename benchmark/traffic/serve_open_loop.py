"""Traffic kind ``serve_open_loop``: token streams from one
``InferenceServer`` replica under an open-loop schedule (the cell's sample;
weights and prompt tokens from the seed).

The server runs on threads of this process (the one process that holds the
chip), built as ``paddle_tpu serve --warmup`` builds it; clients go through
``ServingClient.generate`` over the loopback socket.

Parameters (``benchmark/workloads/<cell>.json``): ``rate_per_s``, ``prompt``
and ``output`` (lognormal ``median``/``sigma``/``min``/``cap``) and
``sample_seed`` -- all read by ``lib/openloop.build_schedule`` -- and
``reference_prompts`` (lengths), ``logits_tol`` (with its reason),
``trace_from`` (share of the window at which the traced part of a
``--trace 1`` run starts) and ``trace_seconds``.  A ``--trace 2`` run
replays that part of the schedule after its measured window has drained
(``traced``), ``LEAD_S`` seconds of lead-in before it.

End-to-end, all from the client's side on the host's clock
(``BENCHMARK.json`` says which of them a cell is judged by):
``ttft_p50_ms``, ``ttft_p95_ms``  of (first token - DUE time) over ALL the
                 window's requests.  On the v5e the 95th percentile read
                 189-445 ms between runs of ONE schedule (PERF.md section
                 6), so the first cell judges the median and records the
                 tail as a per-layer metric;
``gap_p95_ms``   95th percentile of the gaps between consecutive tokens of
                 one stream, over all completed streams' tokens;
``out_tokens_per_s``  output tokens that reached their clients inside the
                 window, of requests that ended well, over the window's
                 seconds (a failed or refused request contributes nothing).
"""

from __future__ import annotations

import gc
import hashlib
import json
import os
import shutil
import sys
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from lib import openloop, stats  # noqa: E402
from reference import gen_lm_ref  # noqa: E402

# lead-in of a --trace 2 run's replayed slice: the schedule from this long
# before the traced part, sent with spans on and the profiler still off,
# so that the slots are as full as they were at that point of the window
# (a stream lives ~1.2 s on the v5e)
LEAD_S = 5.0
# how long the replayed slice may take to drain after its last due time (a
# stream lives ~1.2 s): a replay that stalls fails the traced stretch and
# must not hold up the line with the measured numbers
REPLAY_DRAIN_S = 10.0


# ---------------------------------------------------------------------------
# the bundle (shapes only: the weights in it are replaced from the seed)
# ---------------------------------------------------------------------------

def _gen_config(cfg):
    from paddle_tpu.models import gen_lm
    hp = gen_lm.GenConfig()
    hp.d_model = cfg["hidden_size"]
    hp.n_head = cfg["num_attention_heads"]
    hp.d_head = cfg["hidden_size"] // cfg["num_attention_heads"]
    hp.d_ffn = cfg["ffn_dim"]
    hp.n_layer = cfg["num_hidden_layers"]
    hp.vocab_size = cfg["vocab_size"]
    hp.max_len = cfg["serving"]["max_len"]
    return hp


def ensure_bundle(ctx):
    """The exported bundle of this configuration's geometry, under
    ``benchmark/cache/bundles/``: only a checkout's first run exports.
    One bundle per geometry, not per seed (a bundle is 8-20 GB; the
    weights of a run are made on the device from ``--seed`` afterwards)."""
    from paddle_tpu.models import gen_lm
    cfg = ctx["config"]
    sv = cfg["serving"]
    shape = {k: cfg[k] for k in ("hidden_size", "ffn_dim",
                                 "num_attention_heads", "num_hidden_layers",
                                 "vocab_size")}
    key = hashlib.sha256(json.dumps([shape, sv], sort_keys=True)
                         .encode()).hexdigest()[:12]
    path = os.path.join(ctx["cache_root"], "bundles", f"{cfg['name']}-{key}")
    if os.path.exists(os.path.join(path, "gen_meta.json")):
        return path, False
    tmp = path + ".partial"
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    gen_lm.export_gen_model(tmp, _gen_config(cfg),
                            num_slots=sv["num_slots"],
                            prompt_buckets=list(sv["prompt_buckets"]),
                            paged=True, page_len=sv["page_len"])
    gc.collect()    # the exporter's scope sits in a reference cycle: its
    # parameters must leave the device before the server loads its own
    os.replace(tmp, path)
    return path, True


# ---------------------------------------------------------------------------
# weights from the seed, on the device
# ---------------------------------------------------------------------------

def _xavier(key, shape):
    import jax
    import jax.numpy as jnp
    limit = (6.0 / (shape[0] + shape[1])) ** 0.5
    return jax.random.uniform(key, shape, jnp.float32, -limit, limit)


def seeded_weights(cfg, seed31):
    """Every trainable parameter of the model, drawn on the device: one
    jitted call per layer (one program for all layers) and one for the
    embedding and head.  Returns ``{name: array}``."""
    import jax
    import jax.numpy as jnp
    d, dff, v = cfg["hidden_size"], cfg["ffn_dim"], cfg["vocab_size"]

    @jax.jit
    def layer(key):
        k = jax.random.split(key, 8)
        return {"q.w": _xavier(k[0], (d, d)), "k.w": _xavier(k[1], (d, d)),
                "v.w": _xavier(k[2], (d, d)),
                "attnout.w": _xavier(k[3], (d, d)),
                "ffn1.w": _xavier(k[4], (d, dff)),
                "ffn2.w": _xavier(k[5], (dff, d)),
                "ffn1.b": jax.random.uniform(k[6], (dff,), jnp.float32,
                                             -0.02, 0.02),
                "ffn2.b": jax.random.uniform(k[7], (d,), jnp.float32,
                                             -0.02, 0.02),
                "ln1.scale": jnp.ones((d,), jnp.float32),
                "ln1.bias": jnp.zeros((d,), jnp.float32),
                "ln2.scale": jnp.ones((d,), jnp.float32),
                "ln2.bias": jnp.zeros((d,), jnp.float32)}

    @jax.jit
    def ends(key):
        k = jax.random.split(key, 2)
        return {"genlm_word_emb": _xavier(k[0], (v, d)),
                "genlm_logits.w": _xavier(k[1], (d, v))}

    root = jax.random.PRNGKey(seed31)
    out = {}
    for i in range(cfg["num_hidden_layers"]):
        for name, arr in layer(jax.random.fold_in(root, i)).items():
            out[f"genlm{i}_{name}"] = arr
    out.update(ends(jax.random.fold_in(root, 1 << 20)))
    return out


def install_weights(predictor, weights):
    """Replace the loaded bundle's parameters in the predictor's scope,
    one at a time so that old and new never both stay on the device."""
    scope = predictor._scope
    with predictor._lock:
        for name in sorted(weights):
            old = scope.find_var(name)
            if old is None or tuple(old.shape) != tuple(weights[name].shape):
                raise KeyError(f"the bundle has no parameter {name} of "
                               f"shape {weights[name].shape}")
            scope.set_var(name, weights[name])


# ---------------------------------------------------------------------------
# requests
# ---------------------------------------------------------------------------

def _prompt(cfg, seed31, index, length):
    rng = np.random.RandomState((seed31 * 1000003 + index) % (2 ** 32))
    return rng.randint(1, cfg["vocab_size"], size=int(length)).tolist()


def _stream(client_cls, addr, ptrace, rid, prompt, max_new):
    """One request through ``ServingClient.generate``; the arrival time of
    every token on this side of the socket."""
    times, indices, finish = [], [], None
    with ptrace.trace_context(rid):
        client = client_cls(addr, timeout=300.0)
        for ev in client.generate(prompt, max_new_tokens=int(max_new)):
            now = time.perf_counter()
            if "token" in ev:
                times.append(now)
                indices.append(int(ev["index"]))
            elif ev.get("error"):
                raise RuntimeError(f"{rid}: {ev['error']}")
            elif ev.get("done"):
                finish = ev.get("finish_reason")
    return {"times": times, "indices": indices, "finish": finish}


def page_counts(schedule, page_len, max_len):
    return sorted({-(-min(max_len, r["prompt_len"] + max(r["max_new"], 1))
                     // page_len) for r in schedule})


def warm_requests(schedules, sv):
    """One short request per distinct page count of the schedules (the
    program seeds a slot's pages with eager operations shaped by their
    number) and at least one per prompt bucket: ``(prompt_len, max_new)``
    pairs."""
    page_len, cap = sv["page_len"], max(sv["prompt_buckets"])
    counts = set()
    for schedule in schedules:
        counts.update(page_counts(schedule, page_len, sv["max_len"]))
    pairs = []
    for n in sorted(counts):
        p = max(1, min(page_len * n - 2, cap))
        pairs.append((p, page_len * n - p))
    covered = {min(b for b in sv["prompt_buckets"] if b >= p)
               for p, _ in pairs}
    for b in sv["prompt_buckets"]:
        if b not in covered:
            pairs.append((b, 2))
    return pairs


# ---------------------------------------------------------------------------
# the kind's four entry points
# ---------------------------------------------------------------------------

def setup(ctx):
    import jax
    from paddle_tpu.obs import trace as ptrace
    from paddle_tpu.profiler import runtime_metrics
    from paddle_tpu.serving import InferenceServer, ServingClient

    cfg, wl, say = ctx["config"], ctx["workload"], ctx["say"]
    sv = cfg["serving"]
    t0 = time.perf_counter()
    bundle, exported = ensure_bundle(ctx)
    t_bundle = time.perf_counter() - t0

    t0 = time.perf_counter()
    server = InferenceServer(bundle, port=0, warmup=True,
                             request_timeout=600.0)
    server.start_background()
    state = {"server": server, "ptrace": ptrace, "metrics": runtime_metrics,
             "client_cls": ServingClient, "checks": {}}
    try:
        if not server.wait_until_ready(1100):
            raise RuntimeError("server not ready in 1100 s")
        t_ready = time.perf_counter() - t0
        addr = "%s:%d" % tuple(server.addr[:2])
        state["addr"] = addr
        predictor = server.gen_predictor

        t0 = time.perf_counter()
        weights = seeded_weights(cfg, ctx["seed31"])
        install_weights(predictor, weights)
        jax.block_until_ready(list(weights.values()))
        t_weights = time.perf_counter() - t0

        schedules = ctx.get("schedules") or [
            openloop.build_schedule(ctx["seconds"], wl)]
        state["schedules"] = schedules

        # -- logits against the plain reference, scheduler idle
        t0 = time.perf_counter()
        state["checks"].update(_reference_check(ctx, predictor, weights))
        del weights
        t_reference = time.perf_counter() - t0

        # -- warm every shape the window's traffic uses, through the server
        t0 = time.perf_counter()
        pairs = warm_requests(schedules, sv)
        errors = []

        def warm(chunk):
            for j, (p, m) in chunk:
                try:
                    rec = _stream(ServingClient, addr, ptrace, f"warm-{j}",
                                  _prompt(cfg, ctx["seed31"], 10 ** 6 + j, p),
                                  m)
                    if len(rec["times"]) != m:
                        raise RuntimeError(f"warm {p}+{m}: got "
                                           f"{len(rec['times'])} tokens")
                except Exception as e:   # re-raised on the main thread
                    errors.append(e)
        lanes = 4
        jobs = list(enumerate(pairs))
        threads = [threading.Thread(target=warm, args=(jobs[k::lanes],),
                                    daemon=True) for k in range(lanes)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(900)
        if errors:
            raise errors[0]
        if any(t.is_alive() for t in threads):
            raise RuntimeError("warm requests did not finish in 900 s")
        t_warm = time.perf_counter() - t0
        say("setup", bundle=bundle, exported=exported,
            bundle_seconds=t_bundle, ready_seconds=t_ready,
            weights_seconds=t_weights, reference_seconds=t_reference,
            warm_requests=len(pairs), warm_seconds=t_warm)
    except BaseException:
        server.shutdown()
        raise
    if ctx["traced"]:
        ptrace.enable(1 << 18)
    return state


def _reference_check(ctx, predictor, weights):
    """Prefill, then ONE cached decode step through the paged pool,
    against the reference's forward over the whole sequence."""
    import jax
    import jax.numpy as jnp
    cfg, wl = ctx["config"], ctx["workload"]
    ref = jax.jit(lambda p, ids: gen_lm_ref.forward_logits(
        p, cfg, ids, jnp.asarray([ids.shape[0] - 2, ids.shape[0] - 1])))
    worst_prefill = worst_decode = 0.0
    for j, n in enumerate(wl["reference_prompts"]):
        prompt = _prompt(cfg, ctx["seed31"], 2 * 10 ** 6 + j, n)
        logits, kv = predictor.prefill(prompt)
        tok = int(np.argmax(logits))
        predictor.alloc_slot_pages(0, predictor.pages_needed(n, 1))
        try:
            predictor.write_slot(0, kv, n)
            tokens, pos, lens = (np.zeros(predictor.num_slots, np.int32)
                                 for _ in range(3))
            tokens[0], pos[0], lens[0] = tok, n, n + 1
            step = predictor.decode_step(tokens, pos, lens=lens)[0]
        finally:
            predictor.free_slot_pages(0)
        want = np.asarray(ref(weights, jnp.asarray(prompt + [tok],
                                                   jnp.int32)))
        spread = float(want.max() - want.min())
        worst_prefill = max(worst_prefill, float(
            np.abs(np.asarray(logits) - want[0]).max()) / spread)
        worst_decode = max(worst_decode, float(
            np.abs(np.asarray(step) - want[1]).max()) / spread)
    tol = float(wl["logits_tol"])
    ctx["say"]("reference", prompts=wl["reference_prompts"],
               prefill_err_of_range=worst_prefill,
               decode_err_of_range=worst_decode, tolerance=tol)
    return {"prefill_err_of_range": worst_prefill,
            "decode_err_of_range": worst_decode,
            "reference_ok": worst_prefill <= tol and worst_decode <= tol}


def _series(metrics, name):
    entry = metrics.snapshot()["series"].get(name) or {}
    return entry.get("count") or 0, entry.get("total") or 0.0


def _samples_since(metrics, name, count0):
    """The samples of an always-on series since its count read
    ``count0``; none from a program without that series or its reader."""
    read = getattr(metrics, "samples", None)
    n = _series(metrics, name)[0] - count0
    return read(name, last=n) if read is not None and n > 0 else []


def _trace_part(ctx, metrics, after_s, traced):
    """On a thread of its own: ``after_s`` seconds from now, the device
    profiler on for the cell's ``trace_seconds``; the paged kernel's
    page counter read on either side."""
    time.sleep(after_s)
    traced["pages0"] = _series(metrics, "gen.paged.pages_touched")
    ctx["tracer"].start()
    time.sleep(float(ctx["workload"].get("trace_seconds", 5.0)))
    traced["pages1"] = _series(metrics, "gen.paged.pages_touched")
    ctx["tracer"].stop()


def _traced_facts(cfg, ts_of, ok, traced):
    """Facts the span and device-trace readers want: every request's due
    time on the span clock (``ts_of`` maps a ``perf_counter`` reading
    onto it), and the decode steps and live K/V rows of the traced
    part."""
    # the span that launches and waits for the decode executable, and an
    # op found only in it (lib/spanclock.agreement)
    facts = {"clock_proof": {"span": "gen.decode_step",
                             "holding": ["ptop_paged_attention"]}}
    if ts_of is not None:
        facts["due_ts_by_request"] = {
            r["rid"]: ts_of(r["request"]["due_t"]) for r in ok}
    if "pages1" in traced:
        facts["traced_decode_steps"] = \
            traced["pages1"][0] - traced["pages0"][0]
        facts["traced_live_rows"] = \
            (traced["pages1"][1] - traced["pages0"][1]) \
            * cfg["serving"]["page_len"]
    return facts


def _sender(state, ctx, prefix, schedule):
    """``send(request)`` for :class:`openloop.OpenLoop`: the schedule's
    prompts are made beforehand, the request id is ``prefix`` + index."""
    cfg = ctx["config"]
    prompts = {r["index"]: _prompt(cfg, ctx["seed31"], r["index"],
                                   r["prompt_len"]) for r in schedule}

    def send(request):
        rid = f"{prefix}-{ctx['seed']}-{request['index']}"
        rec = _stream(state["client_cls"], state["addr"], state["ptrace"],
                      rid, prompts[request["index"]], request["max_new"])
        rec["rid"] = rid
        return rec
    return send


def window(state, ctx, schedule=None):
    cfg, wl, tracer = ctx["config"], ctx["workload"], ctx["tracer"]
    metrics, ptrace = state["metrics"], state["ptrace"]
    seconds = ctx["seconds"]
    schedule = schedule if schedule is not None else state["schedules"][0]
    send = _sender(state, ctx, "req", schedule)

    counter_names = ("compile.events", "compile_cache.misses",
                     "gen.paged.fallback", "gen.tokens", "gen.admissions")
    before = {n: metrics.counter(n) for n in counter_names}
    hist0 = dict(metrics.snapshot()["histograms"]
                 .get("gen.slot_occupancy", {}))
    waits0 = _series(metrics, "gen.queue_wait_seconds")[0]
    traced = {}
    tracer_thread = None
    if ctx["traced"]:
        ptrace.clear()
        with ptrace.span("bench.clock_mark"):
            mark_t = time.perf_counter()
        if tracer.enabled:
            tracer_thread = threading.Thread(
                target=_trace_part, daemon=True,
                args=(ctx, metrics, float(wl.get("trace_from", 0.3))
                      * seconds, traced))
            tracer_thread.start()

    loop = openloop.OpenLoop(schedule, send)
    records = loop.run(drain_timeout=float(wl.get("drain_timeout_s", 120)))
    t_end = loop.t_start + seconds
    time.sleep(max(0.0, t_end - time.perf_counter()))   # the whole window
    if tracer_thread is not None:
        tracer_thread.join(60)

    after = {n: metrics.counter(n) for n in counter_names}
    slot_waits = _samples_since(metrics, "gen.queue_wait_seconds", waits0)
    hist1 = metrics.snapshot()["histograms"].get("gen.slot_occupancy", {})
    counters = {n: after[n] - before[n] for n in counter_names}
    counters["hist:gen.slot_occupancy"] = {
        k: v - hist0.get(k, 0) for k, v in hist1.items()
        if v - hist0.get(k, 0) > 0}

    ok = [r for r in records if r.get("ok")]
    state["window_times"] = (loop.t_start, [r["times"] for r in ok])
    bad_shape = [r["rid"] for r in ok
                 if r["indices"] != list(range(r["request"]["max_new"]))]
    ttft = [r["times"][0] - r["request"]["due_t"] for r in ok if r["times"]]
    gaps = [b - a for r in ok for a, b in zip(r["times"], r["times"][1:])]
    done_in = [r for r in ok if r["times"] and r["times"][-1] <= t_end]
    out_tokens = sum(1 for r in ok for t in r["times"] if t <= t_end)
    backlog = sum(1 for r in ok if r["times"] and r["times"][-1] > t_end)
    end_to_end = {"out_tokens_per_s": out_tokens / seconds}
    if ttft:
        end_to_end.update({f"ttft_p{q}_ms": stats.percentile(ttft, q) * 1e3
                           for q in (50, 95)})
    if gaps:
        end_to_end["gap_p95_ms"] = stats.percentile(gaps, 95) * 1e3

    # slot_wait_s: the program's own account of every wait for a slot in
    # the window, from the stream's creation (always on)
    spans, facts = [], {"lateness_s": openloop.OpenLoop.lateness(records),
                        "ttft_s": ttft, "slot_wait_s": slot_waits}
    if ctx["traced"]:
        spans = ptrace.snapshot_spans()
        mark = next((s for s in spans if s["name"] == "bench.clock_mark"),
                    None)
        # span clock = perf_counter - offset
        facts.update(_traced_facts(
            cfg, (lambda t: t - (mark_t - mark["ts"])) if mark else None,
            ok, traced))
    return {
        "end_to_end": end_to_end,
        "attempted": len(schedule),
        "failed": len(records) - len(ok) + (len(schedule) - len(records)),
        "spans": spans, "counters": counters, "facts": facts,
        "bad_shape": bad_shape,
        "errors": [r.get("error") for r in records if not r.get("ok")][:5],
        "observed": {
            "requests": len(schedule), "completed": len(ok),
            "completed_in_window": len(done_in),
            "backlog_at_window_end": backlog,
            "offered_out_tokens_per_s":
                sum(r["max_new"] for r in schedule) / seconds,
            **{f"ttft_p{q}_ms": stats.percentile(ttft, q) * 1e3
               for q in (50, 90, 95, 99) if ttft},
            "ttft_mean_ms": sum(ttft) / len(ttft) * 1e3 if ttft else None,
            "ttft_max_ms": max(ttft) * 1e3 if ttft else None,
            "ttft_samples": len(ttft),
            "completed_request_tokens_per_s":
                sum(len(r["times"]) for r in done_in) / seconds,
            "gap_p50_ms": None if not gaps
                else stats.percentile(gaps, 50) * 1e3,
            "gap_samples": len(gaps),
            "generator_late_p95_ms": stats.percentile(
                facts["lateness_s"], 95) * 1e3 if facts["lateness_s"]
                else None,
            "drain_s": time.perf_counter() - t_end,
            "slot_waits": len(slot_waits),
            **{f"slot_wait_p{q}_ms": stats.percentile(slot_waits, q) * 1e3
               for q in (50, 95) if slot_waits},
            "counters": {k: v for k, v in counters.items()
                         if not k.startswith("hist:")}},
    }


def traced(state, ctx):
    """The traced stretch of a ``--trace 2`` run, after the measured
    window has drained: the slice of the cell's own schedule that a
    ``--trace 1`` run traces (``trace_from`` x seconds, for
    ``trace_seconds``) with ``LEAD_S`` seconds before it, replayed open
    loop from its due times with the same prompts and fresh request ids.
    The program's spans are on for the whole slice, the device profiler
    after the lead-in."""
    cfg, wl = ctx["config"], ctx["workload"]
    metrics, ptrace = state["metrics"], state["ptrace"]
    t_from = float(wl.get("trace_from", 0.3)) * ctx["seconds"]
    lead = min(LEAD_S, t_from)
    length = lead + float(wl.get("trace_seconds", 5.0))
    piece = [dict(r, due=r["due"] - (t_from - lead))
             for r in state["schedules"][0]
             if t_from - lead <= r["due"] < t_from - lead + length]

    part, sent = {}, {}
    loop = openloop.OpenLoop(piece, _sender(state, ctx, "trace", piece))
    ptrace.enable(1 << 18)
    ptrace.clear()
    try:
        # the requests go out from a thread and the profiler is started
        # and stopped on this one, the harness's main thread, where
        # stopping is three times as fast (PERF.md)
        sender = threading.Thread(
            target=lambda: sent.update(records=loop.run(
                drain_timeout=REPLAY_DRAIN_S)), daemon=True)
        sender.start()
        _trace_part(ctx, metrics, lead, part)
        sender.join(length + 2 * REPLAY_DRAIN_S)
        if "records" not in sent:
            raise RuntimeError("the replayed slice did not drain")
        spans = ptrace.snapshot_spans()
    finally:
        ptrace.disable()
    records = sent["records"]
    ok = [r for r in records if r.get("ok")]
    if len(ok) != len(piece):
        raise RuntimeError(f"{len(piece) - len(ok)} of {len(piece)} "
                           f"replayed requests failed")
    # what tracing costs, like for like: the median token gap of the
    # lead-in (spans on) and of the traced part (spans + profiler) beside
    # the same stretches of the measured window (all tracing off)
    def gap_p50_ms(times, t0, a, b):
        gaps = [y - x for ts in times for x, y in zip(ts, ts[1:])
                if t0 + a <= x and y < t0 + b]
        return stats.percentile(gaps, 50) * 1e3 if gaps else None

    mine = [r["times"] for r in ok]
    w_start, w_times = state["window_times"]
    return {"spans": spans,
            "facts": _traced_facts(cfg, ptrace.ts_of, ok, part),
            "observed": {
                "replayed_requests": len(piece), "lead_s": lead,
                "lead_gap_p50_ms": gap_p50_ms(mine, loop.t_start, 0, lead),
                "lead_gap_p50_ms_in_window": gap_p50_ms(
                    w_times, w_start, t_from - lead, t_from),
                "traced_gap_p50_ms": gap_p50_ms(mine, loop.t_start, lead,
                                                length),
                "traced_gap_p50_ms_in_window": gap_p50_ms(
                    w_times, w_start, t_from, t_from - lead + length)}}


def verify(state, ctx, raw):
    checks = dict(state["checks"])
    c = raw["counters"]
    checks["streams_well_formed"] = not raw["bad_shape"]
    checks["none_failed"] = raw["failed"] == 0
    checks["errors"] = raw["errors"]
    checks["no_paged_fallback"] = c["gen.paged.fallback"] == 0
    checks["no_compile_in_window"] = (c["compile.events"] == 0
                                      and c["compile_cache.misses"] == 0)
    checks["compile_events_in_window"] = c["compile.events"]
    checks["correct"] = all(checks[k] for k in (
        "reference_ok", "streams_well_formed", "none_failed",
        "no_paged_fallback", "no_compile_in_window"))
    return checks


def close(state):
    state["ptrace"].disable()
    state["server"].shutdown()
