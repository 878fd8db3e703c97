"""What any serving cell does whatever its arrivals are: one
``InferenceServer`` replica on threads of this process (the one process
that holds the chip), built as ``paddle_tpu serve --warmup`` builds it,
its weights drawn from the seed, checked against the plain reference and
warmed; clients go through ``ServingClient.generate`` over the loopback
socket.  The traffic kinds (``traffic/serve_*.py``) add the arrivals.

It names no model: the configuration's adapter (``lib/models.py``) exports
the bundle, draws the weights and is the reference.
"""

from __future__ import annotations

import gc
import hashlib
import json
import os
import shutil
import threading
import time

import numpy as np

from lib import hoststat, models, served, stats, xtrace

# lead-in of a --trace 2 run's traced stretch: traffic sent with spans on
# and the profiler still off, so that the slots are as full as in the
# window when the profiler starts
LEAD_S = 5.0
COUNTERS = ("compile.events", "compile_cache.misses", "gen.paged.fallback",
            "gen.tokens", "gen.admissions")


def ensure_bundle(ctx, adapter):
    """The exported bundle of this configuration's geometry, under
    ``benchmark/cache/bundles/``: only a checkout's first run exports.
    One bundle per geometry, not per seed (a bundle is 8-20 GB; the
    weights of a run are made on the device from ``--seed`` afterwards)."""
    cfg = ctx["config"]
    key = hashlib.sha256(json.dumps(adapter.bundle_key(cfg), sort_keys=True)
                         .encode()).hexdigest()[:12]
    path = os.path.join(ctx["cache_root"], "bundles", f"{cfg['name']}-{key}")
    if os.path.exists(os.path.join(path, "gen_meta.json")):
        return path, False
    tmp = path + ".partial"
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    adapter.export(tmp, cfg)
    gc.collect()    # the exporter's scope sits in a reference cycle: its
    # parameters must leave the device before the server loads its own
    os.replace(tmp, path)
    return path, True


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


def _prompt(cfg, seed31, index, length):
    rng = np.random.RandomState((seed31 * 1000003 + index) % (2 ** 32))
    return rng.randint(1, cfg["vocab_size"], size=int(length)).tolist()


def _stream(client_cls, addr, ptrace, rid, prompt, max_new):
    """One request through ``ServingClient.generate``; the arrival time of
    every token on this side of the socket."""
    times, indices, tokens, finish = [], [], [], None
    with ptrace.trace_context(rid):
        client = client_cls(addr, timeout=300.0)
        for ev in client.generate(prompt, max_new_tokens=int(max_new)):
            now = time.perf_counter()
            if "token" in ev:
                times.append(now)
                indices.append(int(ev["index"]))
                tokens.append(int(ev["token"]))
            elif ev.get("error"):
                raise RuntimeError(f"{rid}: {ev['error']}")
            elif ev.get("done"):
                finish = ev.get("finish_reason")
    return {"times": times, "indices": indices, "tokens": tokens,
            "finish": finish}


def sender(state, ctx, prefix, beforehand=()):
    """``send(request)`` for a load generator: the request's prompt is
    drawn from ``--seed`` and its index (those of the requests
    ``beforehand`` are made now), the request id is ``prefix`` + seed +
    index."""
    made = {r["index"]: _prompt(ctx["config"], ctx["seed31"], r["index"],
                                r["prompt_len"]) for r in beforehand}

    def send(request):
        rid = f"{prefix}-{ctx['seed']}-{request['index']}"
        prompt = made.get(request["index"]) or _prompt(
            ctx["config"], ctx["seed31"], request["index"],
            request["prompt_len"])
        rec = _stream(state["client_cls"], state["addr"], state["ptrace"],
                      rid, prompt, request["max_new"])
        rec["rid"] = rid
        return rec
    return send


def setup(ctx, warm_pairs):
    """Bundle, server, weights from the seed, reference check, warm
    requests, in that order.  ``warm_pairs(predictor)`` gives the
    ``(prompt_len, max_new)`` pairs that warm the kind's traffic."""
    from paddle_tpu.obs import trace as ptrace
    from paddle_tpu.profiler import runtime_metrics
    from paddle_tpu.serving import InferenceServer, ServingClient

    say = ctx["say"]
    t0 = time.perf_counter()
    bundle, exported = ensure_bundle(ctx, models.adapter_of(ctx["config"]))
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
        state["addr"] = "%s:%d" % tuple(server.addr[:2])
        seeded = seed_model(state, ctx)

        # -- warm every shape the window's traffic uses, through the server
        t0 = time.perf_counter()
        pairs = warm_pairs(server.gen_predictor)
        _warm(state, ctx, pairs)
        say("setup", bundle=bundle, exported=exported,
            bundle_seconds=t_bundle, ready_seconds=t_ready, **seeded,
            warm_requests=len(pairs), warm_seconds=time.perf_counter() - t0)
    except BaseException:
        server.shutdown()
        raise
    return state


def seed_model(state, ctx):
    """The model of ``ctx``'s seed in the running server: the weights are
    drawn, put in the predictor's place of the ones it holds, and checked
    against the plain reference with the scheduler idle.  ``state`` keeps
    the arrays it drew (the very ones the program now computes with: no
    second copy) for the check of what the window served.  Returns the
    seconds each part took."""
    import jax
    adapter = models.adapter_of(ctx["config"])
    predictor = state["server"].gen_predictor
    state.pop("weights", None)
    t0 = time.perf_counter()
    weights = adapter.seeded_weights(ctx["config"], ctx["seed31"])
    t_draw = time.perf_counter() - t0
    install_weights(predictor, weights)
    jax.block_until_ready(list(weights.values()))
    t_weights = time.perf_counter() - t0
    t0 = time.perf_counter()
    state["checks"] = _reference_check(ctx, adapter, predictor, weights)
    state["weights"] = weights
    return {"weights_seconds": t_weights, "weights_draw_seconds": t_draw,
            "reference_seconds": time.perf_counter() - t0}


def _warm(state, ctx, pairs, lanes=4):
    errors = []

    def warm(chunk):
        for j, (p, m) in chunk:
            try:
                rec = _stream(state["client_cls"], state["addr"],
                              state["ptrace"], f"warm-{j}",
                              _prompt(ctx["config"], ctx["seed31"],
                                      10 ** 6 + j, p), m)
                if len(rec["times"]) != m:
                    raise RuntimeError(f"warm {p}+{m}: got "
                                       f"{len(rec['times'])} tokens")
            except Exception as e:   # re-raised on the main thread
                errors.append(e)
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


def _reference_check(ctx, adapter, predictor, weights):
    """Prefill, then ONE cached decode step through the paged pool,
    against the reference's forward over the whole sequence."""
    import jax
    import jax.numpy as jnp
    cfg, wl = ctx["config"], ctx["workload"]
    ref = jax.jit(lambda p, ids: adapter.reference_logits(
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


class Watch:
    """The program's always-on counters over one window: read when made,
    and again by ``close()``, which returns their increase
    (``hist:gen.slot_occupancy`` and the ``host``'s own among them) and the window's samples of
    ``gen.queue_wait_seconds`` (none from a program without that series
    or its reader)."""

    def __init__(self, metrics):
        self.metrics = metrics
        self.before = {n: metrics.counter(n) for n in COUNTERS}
        self.hist0 = dict(metrics.snapshot()["histograms"]
                          .get("gen.slot_occupancy", {}))
        self.waits0 = _series(metrics, "gen.queue_wait_seconds")[0]
        self.host0 = hoststat.counters()

    def close(self):
        metrics = self.metrics
        host = hoststat.counters()
        after = {n: metrics.counter(n) for n in COUNTERS}
        read = getattr(metrics, "samples", None)
        n = _series(metrics, "gen.queue_wait_seconds")[0] - self.waits0
        slot_waits = read("gen.queue_wait_seconds", last=n) \
            if read is not None and n > 0 else []
        hist1 = metrics.snapshot()["histograms"].get("gen.slot_occupancy", {})
        counters = {n: after[n] - self.before[n] for n in COUNTERS}
        counters["host"] = {k: round(host[k] - self.host0[k], 6)
                            for k in host if k in self.host0}
        counters["hist:gen.slot_occupancy"] = {
            k: v - self.hist0.get(k, 0) for k, v in hist1.items()
            if v - self.hist0.get(k, 0) > 0}
        return counters, slot_waits


def reduce_window(records, attempted, t_end, seconds, counters, slot_waits):
    """Stream records into ``(raw, seen)``.  ``raw`` is a traffic module's
    ``window`` result but for its ``end_to_end`` pick and its ``spans``;
    ``seen`` is what that pick is made from, of requests that ended well:
    ``ok`` (their records), ``ttft_s`` (first token - the request's DUE
    time), ``gaps_s`` (between consecutive tokens of a stream) and
    ``tokens_per_s`` (tokens that reached their clients by ``t_end``)."""
    ok = [r for r in records if r.get("ok")]
    ttft = [r["times"][0] - r["request"]["due_t"] for r in ok if r["times"]]
    gaps = [b - a for r in ok for a, b in zip(r["times"], r["times"][1:])]
    done_in = [r for r in ok if r["times"] and r["times"][-1] <= t_end]
    hist = counters["hist:gen.slot_occupancy"]
    steps = sum(hist.values())
    pct = lambda xs, q: stats.percentile(xs, q) * 1e3 if xs else None
    # the longest silences of the whole replica: gaps between consecutive
    # token arrivals of ANY stream inside the window (a stall of the
    # host shows here whatever the median gap reads)
    arrivals = sorted(t for r in ok for t in r["times"] if t <= t_end)
    silences = sorted((b - a for a, b in zip(arrivals, arrivals[1:])),
                      reverse=True)
    seen = {"ok": ok, "ttft_s": ttft, "gaps_s": gaps,
            "tokens_per_s": sum(1 for r in ok for t in r["times"]
                                if t <= t_end) / seconds}
    return {
        "attempted": attempted, "failed": attempted - len(ok),
        "counters": counters,
        # slot_wait_s: the program's own account of every wait for a slot
        # in the window, from the stream's creation (always on)
        "facts": {"ttft_s": ttft, "slot_wait_s": slot_waits},
        "bad_shape": [r["rid"] for r in ok if r["indices"]
                      != list(range(r["request"]["max_new"]))],
        "errors": [r.get("error") for r in records if not r.get("ok")][:5],
        # the streams the window finished, for the check of what it served
        "finished": done_in,
        "observed": {
            "requests": attempted, "completed": len(ok),
            "completed_in_window": len(done_in),
            "backlog_at_window_end": sum(
                1 for r in ok if r["times"] and r["times"][-1] > t_end),
            **{f"ttft_p{q}_ms": pct(ttft, q) for q in (50, 90, 95, 99)
               if ttft},
            "ttft_mean_ms": sum(ttft) / len(ttft) * 1e3 if ttft else None,
            "ttft_max_ms": max(ttft) * 1e3 if ttft else None,
            "ttft_samples": len(ttft),
            "completed_request_tokens_per_s":
                sum(len(r["times"]) for r in done_in) / seconds,
            "gap_p50_ms": pct(gaps, 50), "gap_p95_ms": pct(gaps, 95),
            "gap_samples": len(gaps), "gap_p99_ms": pct(gaps, 99),
            "silence_max_ms": silences[0] * 1e3 if silences else None,
            "silences_over_100ms": sum(1 for x in silences if x > 0.1),
            "silences_over_100ms_s": sum(x for x in silences if x > 0.1),
            "slot_occupancy_mean": sum(int(k) * v for k, v in hist.items())
            / steps if steps else None,
            "drain_s": time.perf_counter() - t_end,
            "slot_waits": len(slot_waits),
            **{f"slot_wait_p{q}_ms": pct(slot_waits, q) for q in (50, 95)
               if slot_waits},
            "counters": {k: v for k, v in counters.items()
                         if not k.startswith("hist:")}},
    }, seen


def _trace_part(ctx, metrics, after_s, traced):
    """``after_s`` seconds from now, the device profiler on for the
    cell's ``trace_seconds``; the paged kernel's page counter read on
    either side.  Blocks: call it on the thread that may start and stop
    the profiler."""
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


def traced_stretch(state, ctx, lead, start, finish, what="traced"):
    """The traced stretch of a ``--trace 2`` run: ``start()`` sets the
    kind's traffic going from threads of its own, the program's spans
    are on from then, the device profiler after ``lead`` seconds, for
    the cell's ``trace_seconds``, started and stopped on this, the
    harness's main thread, where stopping is three times as fast
    (PERF.md); ``finish()`` returns the stretch's records once they have
    drained, within a bound of its own.  Returns ``(ok records, spans,
    facts)``; a request that failed, or was still open when ``finish``'s
    bound passed, fails the stretch (its part: ``drain``)."""
    ptrace, part = state["ptrace"], {}
    ptrace.enable(1 << 18)
    ptrace.clear()
    try:
        start()
        _trace_part(ctx, state["metrics"], lead, part)
        records = finish()
        spans = ptrace.snapshot_spans()
    finally:
        ptrace.disable()
    ok = [r for r in records if r.get("ok")]
    if len(ok) != len(records):
        raise xtrace.TracePartFailed(
            "drain", f"{len(records) - len(ok)} of {len(records)} {what} "
            f"requests failed: "
            f"{[r.get('error') for r in records if not r.get('ok')][:3]}")
    return ok, spans, _traced_facts(ctx["config"], ptrace.ts_of, ok, part)


def gap_p50_ms(times, t0, a, b):
    """Median token gap of the streams' gaps inside ``[t0+a, t0+b)``."""
    gaps = [y - x for ts in times for x, y in zip(ts, ts[1:])
            if t0 + a <= x and y < t0 + b]
    return stats.percentile(gaps, 50) * 1e3 if gaps else None


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
    musts = ["reference_ok", "streams_well_formed", "none_failed",
             "no_paged_fallback", "no_compile_in_window"]
    if "served_check" in ctx["workload"]:
        checks.update(served.check(
            ctx, state["weights"], raw["finished"], lambda r: _prompt(
                ctx["config"], ctx["seed31"], r["request"]["index"],
                r["request"]["prompt_len"])))
        musts.append("served_ok")
    checks["correct"] = all(checks[k] for k in musts)
    tol = float(ctx["workload"]["logits_tol"])
    checks["compared"] = {
        "prefill_err_of_range": [checks.get("prefill_err_of_range"), tol],
        "decode_err_of_range": [checks.get("decode_err_of_range"), tol],
        **({"served_gap_of_range": [checks["served_gap_of_range"],
                                    checks["served_limit"]]}
           if "served_ok" in musts else {}),
        "failed_requests": [raw["failed"], 0],
        "misshapen_streams": [len(raw["bad_shape"]), 0],
        "paged_fallbacks_in_window": [c["gen.paged.fallback"], 0],
        "compiles_in_window": [c["compile.events"]
                               + c["compile_cache.misses"], 0]}
    return checks


def close(state):
    state["ptrace"].disable()
    state["server"].shutdown()
