"""Autoscale benchmark: the closed control loop under open-loop load.

The drill the fleet controller exists for: a 5× traffic step from the
replay harness (``paddle_tpu.fleet.traffic``) hits a 1-replica fleet.
Fixed-N rides the queue into SLO breach; the controller fleet senses
the p99 pressure, engages the admission ladder (429 + Retry-After —
never a silent drop or a deadline-burning queue wait), and promotes
warm standbys — pre-warmed through the persistent XLA compile cache,
so scale-up is a lease registration, not a compile.  A chaos variant
hard-kills a replica mid-ramp (``fleet.replica.kill``) and counts
lost *accepted* requests, which must be zero.

Device work is MODELED WITH A SLEEP — the ``serving.predict``
failpoint (armed ``delay:SECS``) fires inside the predictor lock, so
each replica serves serially at a fixed service time (the bench-host
cost model shared with ``bench_fleet.py``).

    python bench_autoscale.py --duration 8 --out BENCH_AUTOSCALE.json
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import tempfile
import threading
import time

import numpy as np

from bench_fleet import build_model


def _slo_spec(p99_slo_ms, interval=0.1):
    return {
        "version": 1,
        "interval_seconds": interval,
        "sustained_breaches": 2,
        "objectives": [
            {"name": "request-p99", "kind": "quantile",
             "series": "fleet.request_seconds", "quantile": "p99",
             "max": p99_slo_ms / 1000.0},
        ],
    }


def _policy(max_replicas, standby_pool, tick=0.15):
    return {
        "version": 1,
        "interval_seconds": tick,
        "min_replicas": 1,
        "max_replicas": max_replicas,
        "standby_pool": standby_pool,
        "ready_timeout_seconds": 120.0,
        # react on the pressure MARGIN, well before the p99 nears the
        # threshold: by the time the p99 signal itself breaches, the
        # queue already holds requests that blew the budget — scaling
        # at 35% of the SLO keeps the step transient inside it
        "scale_up": {"pressure_ratio": 0.35, "sustained_ticks": 2,
                     "cooldown_seconds": 0.8},
        "scale_down": {"idle_rps_per_replica": 0.0,
                       "sustained_ticks": 1000,
                       "cooldown_seconds": 1000.0},
        # the ladder is the FAST line of defense: requests already
        # queued when new capacity lands still finish late (shedding
        # never shortens an existing queue), so the whole-run p99 is
        # ~the queue wait at engage time — engage at 25% of the SLO
        # and shed half the arrivals at the first rung while the
        # promotion is in flight
        "degrade": {"ladder": [0.0, 0.5, 0.75], "engage_ratio": 0.25,
                    "recover_ticks": 4, "retry_after_seconds": 0.5},
    }


def _send_factory(router_addr, payload_bytes, deadline_ms):
    """One open-loop request: raw HTTP POST, no client-side retry —
    the replay measures what the FLEET returns, outcome by outcome."""
    import http.client
    host, port = router_addr

    def send(i):
        conn = http.client.HTTPConnection(
            host, port, timeout=deadline_ms / 1000.0 + 5.0)
        try:
            conn.request("POST", "/predict", payload_bytes,
                         {"Content-Type": "application/json",
                          "X-Deadline-Ms": str(int(deadline_ms))})
            resp = conn.getresponse()
            resp.read()
            return {"status": resp.status,
                    "retry_after": resp.getheader("Retry-After")}
        finally:
            conn.close()

    return send


def run_autoscale(model_dir, controller_on, duration=8.0,
                  service_ms=40.0, base_rps=5.0, peak_rps=25.0,
                  step_at=None, p99_slo_ms=500.0, deadline_ms=2000.0,
                  seed=7, kill_mid_ramp=False, fixed_replicas=1,
                  max_replicas=3, standby_pool=2, feature_dim=4):
    """One mode of the drill: master + router (+SLO watchdog) + a
    starting fleet, open-loop step traffic for ``duration`` seconds;
    with ``controller_on`` a :class:`FleetController` with a prewarmed
    standby pool closes the loop.  Returns a stats dict."""
    from paddle_tpu import profiler
    from paddle_tpu.fault import chaos
    from paddle_tpu.fleet import FleetController, FleetReplica, \
        FleetRouter
    from paddle_tpu.fleet.traffic import TrafficReplay, step
    from paddle_tpu.parallel.master import MasterServer, MasterService
    from paddle_tpu.serving import ServingClient

    profiler.runtime_metrics.reset()
    chaos.clear()
    chaos.inject("serving.predict", delay=service_ms / 1000.0)
    if step_at is None:
        step_at = duration * 0.25
    svc = MasterService(replica_ttl=5.0)
    master = MasterServer(svc, port=0)
    master.start_background()
    maddr = f"{master.addr[0]}:{master.addr[1]}"

    def make_replica(rid):
        return FleetReplica(model_dir, maddr, replica_id=rid,
                            lease_ttl=5.0, heartbeat_interval=0.2,
                            warmup=True, warmup_batch_sizes=(1,),
                            request_timeout=30.0)

    replicas = [make_replica(f"fix{i}").start()
                for i in range(fixed_replicas)]
    router = FleetRouter(master_addr=maddr, poll_interval=0.1,
                         slo_spec=_slo_spec(p99_slo_ms))
    router.start_background()
    controller = None
    killer = None
    counters = profiler.runtime_metrics.counter
    try:
        wait_until = time.time() + 30
        while len(router.live_replicas()) < fixed_replicas and \
                time.time() < wait_until:
            time.sleep(0.05)
        payload = {"feeds": {"x": np.random.RandomState(0)
                             .rand(1, feature_dim).astype("float32")
                             .tolist()}}
        payload_bytes = json.dumps(payload).encode()
        warm = ServingClient(router.addr)
        for _ in range(fixed_replicas * 2):  # touch replicas pre-clock
            warm.predict({"x": np.random.RandomState(0)
                          .rand(1, feature_dim).astype("float32")})

        cache_before = (counters("compile_cache.hits"),
                        counters("compile_cache.misses"))
        if controller_on:
            sb = itertools.count()
            controller = FleetController(
                router,
                policy=_policy(max_replicas, standby_pool),
                standby_factory=lambda: make_replica(f"sb{next(sb)}"))
            controller.prewarm()
            controller.start()
        cache_after_warm = (counters("compile_cache.hits"),
                            counters("compile_cache.misses"))

        if kill_mid_ramp:
            killer = threading.Timer(
                step_at + 1.0,
                lambda: chaos.inject("fleet.replica.kill", error=True,
                                     times=1))
            killer.daemon = True
            killer.start()

        replay = TrafficReplay(
            _send_factory(router.addr, payload_bytes, deadline_ms),
            step(base_rps, peak_rps, step_at),
            duration, seed=seed, max_inflight=256)
        traffic = replay.run()

        killed = [r.replica_id for r in replicas if r.killed]
        state = controller.state() if controller is not None else None
        if controller is not None:
            with controller._lock:
                killed += [r.replica_id for r in controller._owned
                           if r.killed]
        return {
            "mode": "controller" if controller_on else "fixed",
            "replicas_start": fixed_replicas,
            "replicas_end": len(router.live_replicas()),
            "traffic": traffic,
            "p99_ms": traffic["latency_ms"]["p99"],
            "slo_p99_ms": p99_slo_ms,
            "held_slo": (traffic["latency_ms"]["p99"] or 0.0)
            <= p99_slo_ms,
            "scale_ups": counters("controller.scale_ups"),
            "scale_downs": counters("controller.scale_downs"),
            "admission_sheds": counters("fleet.admission_shed"),
            "router_sheds": counters("fleet.shed"),
            "standby_compile_cache": {
                "hits_delta": cache_after_warm[0] - cache_before[0],
                "misses_delta": cache_after_warm[1] - cache_before[1],
            },
            "killed": killed,
            "controller": state,
        }
    finally:
        if killer is not None:
            killer.cancel()
        chaos.clear()
        if controller is not None:
            controller.shutdown(drain_owned=True)
        for r in replicas:
            if not r.killed:
                r.drain()
        router.shutdown()
        master.shutdown()


def run_bench(duration=8.0, service_ms=40.0, base_rps=6.0,
              peak_rps=30.0, p99_slo_ms=500.0, deadline_ms=2000.0,
              seed=7, model_dir=None, max_replicas=3, standby_pool=2):
    """Fixed-1 vs controller fleet under the same seeded 5× step, then
    the mid-ramp kill drill on the controller fleet; returns the
    JSON-ready summary.  The persistent compile cache is on for the whole
    run at the dir ``executor.resolve_compile_cache_dir`` gives an entry
    point (a fixed path — it is part of the cache key), so the fixed pass
    populates it at the latest and every standby warm afterwards must
    HIT it."""
    own = model_dir is None
    if own:
        model_dir = build_model(
            tempfile.mkdtemp(prefix="ptauto_") + "/model")
    from paddle_tpu.executor import resolve_compile_cache_dir
    prev_cache = os.environ.get("PADDLE_TPU_COMPILE_CACHE")
    os.environ["PADDLE_TPU_COMPILE_CACHE"] = \
        resolve_compile_cache_dir(entry_point=True)
    try:
        kw = dict(duration=duration, service_ms=service_ms,
                  base_rps=base_rps, peak_rps=peak_rps,
                  p99_slo_ms=p99_slo_ms, deadline_ms=deadline_ms,
                  seed=seed, max_replicas=max_replicas,
                  standby_pool=standby_pool)
        fixed = run_autoscale(model_dir, controller_on=False, **kw)
        ctrl = run_autoscale(model_dir, controller_on=True, **kw)
        drill = run_autoscale(model_dir, controller_on=True,
                              kill_mid_ramp=True, **kw)
    finally:
        if prev_cache is None:
            os.environ.pop("PADDLE_TPU_COMPILE_CACHE", None)
        else:
            os.environ["PADDLE_TPU_COMPILE_CACHE"] = prev_cache
    sheds_without = sum(m["traffic"]["shed_without_hint"]
                       for m in (fixed, ctrl, drill))
    return {
        "duration_sec": duration,
        "service_ms": service_ms,
        "base_rps": base_rps,
        "peak_rps": peak_rps,
        "slo_p99_ms": p99_slo_ms,
        "deadline_ms": deadline_ms,
        "seed": seed,
        "modes": {"fixed": fixed, "controller": ctrl},
        "kill_drill": drill,
        "sheds_without_retry_after": sheds_without,
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--duration", type=float, default=8.0)
    ap.add_argument("--service-ms", type=float, default=40.0)
    ap.add_argument("--base-rps", type=float, default=5.0)
    ap.add_argument("--peak-rps", type=float, default=25.0)
    ap.add_argument("--slo-p99-ms", type=float, default=500.0)
    ap.add_argument("--deadline-ms", type=float, default=2000.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--max-replicas", type=int, default=3)
    ap.add_argument("--standby-pool", type=int, default=2)
    ap.add_argument("--out", default=None, help="write the JSON summary")
    from paddle_tpu.obs import bench_history
    bench_history.add_record_args(ap)
    args = ap.parse_args(argv)
    summary = run_bench(duration=args.duration,
                        service_ms=args.service_ms,
                        base_rps=args.base_rps, peak_rps=args.peak_rps,
                        p99_slo_ms=args.slo_p99_ms,
                        deadline_ms=args.deadline_ms, seed=args.seed,
                        max_replicas=args.max_replicas,
                        standby_pool=args.standby_pool)
    text = json.dumps(summary, indent=2, sort_keys=True)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    bench_history.record_from_args("autoscale", summary, args,
                                   "bench_autoscale.py")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
