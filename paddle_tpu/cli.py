"""Command-line entry points.

Reference L6 surface: the ``paddle_trainer`` CLI
(``paddle/trainer/TrainerMain.cpp:32``), the ``paddle`` shell wrapper
(``paddle/scripts/submit_local.sh.in``), the Go master binary
(``go/cmd/master/master.go``), and the cluster launcher
(``paddle/scripts/cluster_train/paddle.py``).

Usage: ``python -m paddle_tpu <command> ...``

  train   --config SCRIPT [--num-passes N]   run a training script
  infer   --model DIR --feed name=path.npy   load + run an inference model
  master  --files GLOB --port P              serve the task-dispatch master
  launch  --nproc N SCRIPT [args...]         spawn an N-process cluster on
                                             this host (jax.distributed)
  serve   --model DIR --port P               HTTP inference server
                                             (--batch --warmup
                                             --compile-cache DIR;
                                             --master HOST:PORT enrolls
                                             the replica in a fleet)
  router  --master HOST:PORT --port P        health-aware fleet router
                                             (or --replicas a,b,c)
  controller --master H:P --model DIR        router + closed-loop
                                             autoscaler: warm-standby
                                             scale-up, idle drain,
                                             admission backpressure
                                             (--policy POLICY.json or
                                             PADDLE_TPU_AUTOSCALE)
  stats   --addr HOST:PORT                   runtime metrics snapshot of
                                             a serving replica (/stats);
                                             --local for this process;
                                             --prom for Prometheus text
  trace   dump [--addr HOST:PORT|--local]    Chrome trace-event JSON of
                                             the span ring (PADDLE_TPU_
                                             TRACE); load in Perfetto;
                                             --fleet assembles the whole
                                             fleet's rings via the
                                             router (one pid/process)
  fleet-stats --router HOST:PORT             federated fleet metrics:
          | --master H:P | --replicas a,b    one exposition, per-replica
                                             labels, rollup rates,
                                             stale-marked corpses
  bench   check [--dry] | record             bench-trajectory gate over
                                             BENCH_TRAJECTORY.json:
                                             newest run vs recorded
                                             baseline per-metric
                                             tolerance bands; exit 1
                                             on regression
  replay  BUNDLE.pkl [--localize]            re-execute a sentinel-
                                             quarantined step on CPU and
                                             report whether the numerical
                                             fault reproduces (exit 0 =
                                             reproduced, 1 = clean);
                                             --localize probes every op
                                             and names the FIRST one
                                             producing a non-finite
                                             output, with its Python
                                             creation site + stat trail
  runs    tail|show DIR | compare A B        run-ledger readers: tail
                                             the last step rows of a
                                             ledger dir (-n N), digest
                                             a whole run, or compare
                                             two runs field by field
  lint    MODEL_DIR | --zoo NAME|all         static-analyze a program:
                                             def-before-use, shape/dtype
                                             inference, dead ops, donation
                                             hazards, int64 truncation —
                                             rustc-style diagnostics with
                                             stable PTA*** codes
                                             (docs/static_analysis.md);
                                             exit 1 on errors.  Multi-
                                             program: a gen-bundle dir
                                             lints prefill+decode as one
                                             unit; --pair T P lints a
                                             transpiled trainer/pserver
                                             pair; --pipeline N verifies
                                             an N-stage split; --dot OUT
                                             renders the program as a
                                             GraphViz graph
  opt     MODEL_DIR | --zoo NAME|all         run the Program-IR
                                             optimization pipeline
                                             offline: per-pass
                                             diff/stats report, cost
                                             before/after, donation
                                             plan, amortization-gate
                                             verdict (what
                                             PADDLE_TPU_OPT=1 does
                                             in-executor); exit 1 when
                                             any pass was sandwich-
                                             aborted
  ckpt    inspect DIR | verify DIR           checkpoint-dir survey:
                                             committed steps, per-shard
                                             manifest status, saved mesh
                                             topology, latest/last-good
                                             pointers; verify re-hashes
                                             every file and exits 1 on
                                             corruption (operator
                                             restorability probe — no
                                             program load, no device)
  selfcheck                                  strict zoo lint (single- and
                                             multi-program) + every
                                             scanner-enforced registry +
                                             SLO-spec and bench-
                                             trajectory schemas in one
                                             exit-coded pass
  profile [--model transformer|resnet ...]   per-op device-time table of
                                             one compiled training step
  version
"""

from __future__ import annotations

import argparse
import glob
import os
import runpy
import subprocess
import sys

__all__ = ["main"]

VERSION = "0.2.0"


def _cmd_version(args):
    import jax
    try:
        backend = jax.default_backend()
    except Exception as e:  # backend init can fail off-accelerator hosts
        backend = f"unavailable ({type(e).__name__})"
    print(f"paddle_tpu {VERSION} (jax {jax.__version__}, "
          f"backend {backend})")
    return 0


def _cmd_train(args):
    """Run a training script — the ``paddle_trainer --config`` analog.
    The script sees PADDLE_NUM_PASSES etc. like the reference's gflags."""
    if args.num_passes is not None:
        os.environ["PADDLE_NUM_PASSES"] = str(args.num_passes)
    from paddle_tpu.executor import enable_compile_cache
    enable_compile_cache(entry_point=True)
    if args.checkpoint_dir is not None:
        # consumed by fault.manager_from_env() in training scripts
        # (the paddle_trainer --save_dir analog)
        os.environ["PADDLE_TPU_CKPT_DIR"] = args.checkpoint_dir
        os.environ["PADDLE_TPU_CKPT_KEEP"] = str(args.keep_checkpoints)
    sys.argv = [args.config] + (args.script_args or [])
    runpy.run_path(args.config, run_name="__main__")
    return 0


def _cmd_infer(args):
    """Load a saved inference model and run it on .npy feeds
    (the C++ ``inference::Load`` + run flow, ``inference/io.h:35``)."""
    import numpy as np
    import paddle_tpu as fluid

    exe = fluid.Executor()
    program, feed_names, fetch_targets = \
        fluid.io.load_inference_model(args.model, exe)
    feed = {}
    for spec in args.feed or []:
        name, path = spec.split("=", 1)
        feed[name] = np.load(path)
    missing = [n for n in feed_names if n not in feed]
    if missing:
        print(f"missing feeds: {missing}; expected {feed_names}",
              file=sys.stderr)
        return 2
    outs = exe.run(program, feed=feed, fetch_list=fetch_targets)
    for target, value in zip(fetch_targets, outs):
        name = target.name if hasattr(target, "name") else str(target)
        arr = np.asarray(value)
        print(f"{name}: shape={arr.shape}")
        if args.output:
            np.save(os.path.join(args.output, f"{name}.npy"), arr)
    return 0


def _cmd_master(args):
    """Serve the fault-tolerant task master (go master binary analog)."""
    from paddle_tpu.parallel.master import (MasterServer, MasterService,
                                            partition_files)
    files = sorted(glob.glob(args.files))
    if not files:
        print(f"no files match {args.files!r}", file=sys.stderr)
        return 2
    tasks = partition_files(files, args.chunks_per_task)
    service = MasterService(tasks, timeout=args.timeout,
                            failure_max=args.failure_max,
                            snapshot_path=args.snapshot,
                            heartbeat_timeout=args.heartbeat_timeout)
    server = MasterServer(service, host=args.host, port=args.port)
    print(f"master serving {len(tasks)} tasks on "
          f"{server.addr[0]}:{server.addr[1]}", flush=True)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    return 0


def _cmd_serve(args):
    """HTTP inference server over a saved model (L6 serving runtime).
    With --master the replica enrolls in the serving fleet: register on
    readiness, heartbeat-renew the lease, drain cleanly on SIGTERM."""
    from paddle_tpu.executor import enable_compile_cache
    from paddle_tpu.serving import serve
    # before the predictor's Executor exists, so its compiles persist
    enable_compile_cache(args.compile_cache, entry_point=True)
    warmup_sizes = None
    if args.warmup_batch_sizes:
        warmup_sizes = [int(s) for s in args.warmup_batch_sizes.split(",")]
    server_kwargs = dict(
        async_load=args.async_load,
        max_inflight=args.max_inflight,
        request_timeout=args.request_timeout, batching=args.batch,
        max_batch_size=args.max_batch_size,
        max_batch_delay=args.max_batch_delay,
        batch_queue_size=args.batch_queue_size, warmup=args.warmup,
        warmup_batch_sizes=warmup_sizes,
        gen_admission=args.gen_admission,
        gen_queue_size=args.gen_queue_size)
    if args.master:
        from paddle_tpu.fault import GracefulShutdown
        from paddle_tpu.fleet import FleetReplica
        replica = FleetReplica(args.model, args.master,
                               replica_id=args.replica_id,
                               host=args.host, port=args.port,
                               lease_ttl=args.lease_ttl,
                               advertise_host=args.advertise_host,
                               **server_kwargs)
        replica.start()
        print(f"fleet replica {replica.replica_id} serving {args.model} "
              f"on {replica.addr[0]}:{replica.addr[1]} "
              f"(master {args.master})", flush=True)
        # rolling restart contract: SIGTERM -> deregister (router stops
        # routing), finish in-flight, release the lease, exit 0
        with GracefulShutdown() as stop:
            stop.wait()
        migrated = replica.drain(deadline_s=args.drain_deadline_s)
        if migrated:
            print(f"drained: {len(migrated)} active session(s) "
                  f"checkpoint-migrated to survivors", flush=True)
        return 0
    serve(args.model, host=args.host, port=args.port, **server_kwargs)
    return 0


def _cmd_generate(args):
    """Streaming generation client: POST /generate and print tokens as
    the chunks arrive (directly against a replica, or through a fleet
    router — both stream incrementally)."""
    from paddle_tpu.serving import ServingClient
    prompt = [int(t) for t in args.prompt.replace(",", " ").split()]
    client = ServingClient(args.addr, timeout=args.timeout,
                           deadline=args.deadline)
    tokens = []
    for ev in client.generate(prompt, max_new_tokens=args.max_new,
                              eos_id=args.eos_id,
                              stream=not args.no_stream,
                              session_id=args.session_id,
                              resume=not args.no_resume):
        if "token" in ev:
            tokens.append(ev["token"])
            print(ev["token"], flush=True)
        elif ev.get("error"):
            err = ev["error"]
            print(f"error: {err.get('type')}: {err.get('message')}",
                  flush=True)
            return 1
        elif ev.get("done"):
            if ev.get("tokens") and not tokens:
                # stream=false: the buffered reply carries them all
                print(" ".join(str(t) for t in ev["tokens"]), flush=True)
            print(f"# done ({ev.get('finish_reason')})", flush=True)
    return 0


def _cmd_router(args):
    """Serve the health-aware fleet router (master-discovered or static
    replica list)."""
    from paddle_tpu.fleet import FleetRouter
    replicas = [a for a in (args.replicas or "").split(",") if a]
    router = FleetRouter(master_addr=args.master or None,
                         replicas=replicas or None,
                         host=args.host, port=args.port,
                         default_deadline=args.default_deadline,
                         poll_interval=args.poll_interval,
                         slo_spec=args.slo or None)
    n = len(router.live_replicas())
    print(f"fleet router on {router.addr[0]}:{router.addr[1]} "
          f"({'master ' + args.master if args.master else 'static'}; "
          f"{n} replica(s) live)", flush=True)
    try:
        router.serve_forever()
    except KeyboardInterrupt:
        pass
    return 0


def _cmd_controller(args):
    """Serve the fleet router WITH the closed control loop in-process:
    a FleetController senses SLO pressure / scraper rollups and scales
    a warm standby pool of replicas built from --model (pre-warmed
    through PADDLE_TPU_COMPILE_CACHE when set)."""
    import itertools

    from paddle_tpu.fault import GracefulShutdown
    from paddle_tpu.fleet import FleetController, FleetReplica, \
        FleetRouter
    from paddle_tpu.executor import enable_compile_cache
    # before any standby's Executor exists, so warms hit the cache
    enable_compile_cache(args.compile_cache, entry_point=True)
    router = FleetRouter(master_addr=args.master,
                         host=args.host, port=args.port,
                         default_deadline=args.default_deadline,
                         poll_interval=args.poll_interval,
                         slo_spec=args.slo or None)
    router.start_background()
    seq = itertools.count()

    def factory():
        return FleetReplica(args.model, args.master,
                            replica_id=f"auto-{os.getpid()}-{next(seq)}",
                            lease_ttl=args.lease_ttl, warmup=True)

    controller = FleetController(router, policy=args.policy or None,
                                 standby_factory=factory)
    warmed = controller.prewarm(raise_on_failure=False)
    controller.start()
    print(f"fleet controller on {router.addr[0]}:{router.addr[1]} "
          f"(master {args.master}; policy "
          f"{controller.policy.source or 'defaults'}; "
          f"{warmed} standby(s) warm)", flush=True)
    try:
        with GracefulShutdown() as stop:
            stop.wait()
    except KeyboardInterrupt:
        pass
    controller.shutdown(drain_owned=True)
    router.shutdown()
    return 0


def _cmd_stats(args):
    """Fetch and render a server's /stats metrics snapshot (or this
    process's own registry with --local — the datapipe/executor counters
    of an in-process run)."""
    import json as _json

    if args.prom:
        # Prometheus text exposition (the /metrics body) — what a
        # node-exporter-style scraper or a debugging curl wants
        if args.local:
            from paddle_tpu.obs.prom import render_prometheus
            print(render_prometheus(), end="")
        elif args.addr:
            from paddle_tpu.serving import ServingClient
            print(ServingClient(args.addr).prom_metrics(), end="")
        else:
            print("stats: need --addr HOST:PORT or --local",
                  file=sys.stderr)
            return 2
        return 0
    if args.local:
        from paddle_tpu.profiler import runtime_metrics
        snap = runtime_metrics.snapshot()
    elif args.addr:
        from paddle_tpu.serving import ServingClient
        snap = ServingClient(args.addr).stats()
    else:
        print("stats: need --addr HOST:PORT or --local", file=sys.stderr)
        return 2
    if args.json:
        print(_json.dumps(snap, indent=2, sort_keys=True))
        return 0
    for name, v in sorted((snap.get("counters") or {}).items()):
        print(f"{name:<36}{v:>12}")
    for name, s in sorted((snap.get("series") or {}).items()):
        p50, p95, p99 = s.get("p50"), s.get("p95"), s.get("p99")
        fmt = (lambda x: f"{x * 1e3:.2f}ms" if isinstance(x, (int, float))
               else "-")
        print(f"{name:<36}count={s.get('count', 0):<8}"
              f"p50={fmt(p50):<10}p95={fmt(p95):<10}p99={fmt(p99)}")
    for name, v in sorted((snap.get("gauges") or {}).items()):
        print(f"{name:<36}{v:>12g}")
    for name, hist in sorted((snap.get("histograms") or {}).items()):
        print(f"{name}: " + " ".join(f"{k}:{v}" for k, v in hist.items()))
    srv = snap.get("server") or {}
    if srv:
        print("server: " + " ".join(f"{k}={v}"
                                    for k, v in sorted(srv.items())))
    return 0


def _cmd_fleet_stats(args):
    """Fleet-level federated metrics: scrape every replica's /stats and
    render ONE Prometheus exposition with per-replica labels + rollups
    (dead replicas marked stale, never fatal).  Three target modes:
    --router proxies the router's own /metrics?fleet=1 (the router's
    scraper keeps rate state between pulls); --master discovers the
    lease table and scrapes in-process; --replicas scrapes a static
    list."""
    import json as _json
    import urllib.request

    from paddle_tpu.obs import aggregate

    if args.router:
        url = f"http://{args.router}/metrics?fleet=1"
        with urllib.request.urlopen(url, timeout=args.timeout) as r:
            print(r.read().decode(), end="")
        return 0
    if args.master:
        from paddle_tpu.parallel.master import MasterClient
        client = MasterClient(args.master)
        try:
            targets = [(r["addr"], r["id"])
                       for r in client.list_replicas()]
        finally:
            client.close()
    elif args.replicas:
        targets = [(a, a) for a in args.replicas.split(",") if a]
    else:
        print("fleet-stats: need --router, --master, or --replicas",
              file=sys.stderr)
        return 2
    scraper = aggregate.FleetScraper(lambda: targets,
                                     timeout=args.timeout)
    text, scrapes = scraper.federate()
    if args.json:
        print(_json.dumps(
            {"replicas": [{k: s[k] for k in
                           ("addr", "id", "ok", "error", "rtt_s")}
                          for s in scrapes]},
            indent=2, sort_keys=True))
    else:
        print(text, end="")
    return 0


def _cmd_bench(args):
    """Bench trajectory gate: `bench check` compares each bench's
    newest BENCH_TRAJECTORY.json run against its recorded baseline
    under per-metric tolerance bands (exit 1 on regression or schema
    problem); `bench record` imports a bench summary JSON (e.g.
    BENCH_DECODE.json) as a new trajectory run."""
    import json as _json

    from paddle_tpu.obs import bench_history

    if args.action == "record":
        if not args.bench or not args.summary:
            print("bench record: need --bench NAME --summary FILE",
                  file=sys.stderr)
            return 2
        try:
            with open(args.summary) as f:
                summary = _json.load(f)
            metrics = bench_history.summary_metrics(args.bench, summary)
            entry = bench_history.record(
                args.bench, metrics, path=args.trajectory,
                baseline=args.baseline, source=args.summary)
        except (OSError, ValueError, KeyError) as e:
            print(f"bench record: {e}", file=sys.stderr)
            return 2
        print(_json.dumps(entry, indent=2, sort_keys=True))
        return 0
    report = bench_history.check(path=args.trajectory, dry=args.dry)
    if args.json:
        print(_json.dumps(report, indent=2, sort_keys=True))
    else:
        for line in report["problems"]:
            print(f"schema: {line}")
        for bench, b in sorted(report.get("benches", {}).items()):
            for row in b["comparisons"]:
                mark = "ok  " if row["ok"] else "FAIL"
                print(f"[{mark}] {bench}.{row['metric']}: "
                      f"newest={row['newest']:g} vs "
                      f"baseline={row['baseline']:g} "
                      f"({row['direction']}, band={row['band']:g}, "
                      f"bound={row['bound']:g})")
        verdict = "PASS" if report["ok"] else "FAIL"
        what = "schema" if args.dry else "regression gate"
        print(f"bench check ({what}): {verdict} [{report['path']}]")
    return 0 if report["ok"] else 1


def _ckpt_report(dirname, step=None, deep=False):
    """The ``paddle_tpu ckpt`` survey of a checkpoint directory — pure
    directory/manifest reads (no executor, no program, no device):
    committed steps with per-step manifest status (and per-shard file
    presence for shard-format checkpoints), the saved mesh topology,
    the latest/last-good pointers, and quarantined dirs.  ``deep``
    re-hashes every file (``verify``); shallow reads manifests only."""
    from paddle_tpu.fault import checkpoint as ckpt_mod
    from paddle_tpu.fault import shard_ckpt
    from paddle_tpu.fault.checkpoint import (CorruptCheckpoint,
                                             GOOD_POINTER_NAME)

    report = {"dir": os.path.abspath(dirname), "steps": [],
              "latest": None, "last_good": None, "quarantined": [],
              "ok": True}
    for pointer, key in (("latest", "latest"),
                         (GOOD_POINTER_NAME, "last_good")):
        try:
            with open(os.path.join(dirname, pointer)) as f:
                report[key] = int(f.read().strip())
        except (OSError, ValueError):
            pass
    steps = []
    for name in sorted(os.listdir(dirname)):
        if name.endswith(".corrupt"):
            report["quarantined"].append(name)
            continue
        if not name.startswith("ckpt-") or \
                not name[len("ckpt-"):].isdigit():
            continue
        steps.append(int(name[len("ckpt-"):]))
    for s in sorted(steps):
        if step is not None and s != int(step):
            continue
        path = os.path.join(dirname, f"ckpt-{s}")
        row = {"step": s, "format": "legacy", "status": "unverifiable",
               "topology": None, "shards": None}
        manifest = shard_ckpt.read_manifest(path)
        if manifest is not None:
            row["format"] = "manifest"
            topo = manifest.get("topology")
            if topo is not None:
                row["format"] = "sharded"
                shards = topo.get("shards") or {}
                counts = [r.get("num_shards", 1) for r in shards.values()]
                row["topology"] = {
                    "mesh_shape": topo.get("mesh_shape"),
                    "axis_names": topo.get("axis_names"),
                    "processes": topo.get("processes"),
                }
                row["shards"] = {
                    "vars": len(shards),
                    "sharded_vars": sum(1 for c in counts if c > 1),
                    "shard_files": sum(counts),
                }
            try:
                if deep:
                    ckpt_mod.verify_checkpoint(path)
                else:
                    # shallow: file presence + size + topology
                    # self-consistency, no re-hash
                    for rel, want in manifest.get("files", {}).items():
                        p = os.path.join(path, rel)
                        if not os.path.exists(p):
                            raise CorruptCheckpoint(
                                f"{path}: missing file {rel!r}")
                        if os.path.getsize(p) != want["size"]:
                            raise CorruptCheckpoint(
                                f"{path}: {rel!r} size mismatch")
                    if topo is not None:
                        problems = shard_ckpt.validate_topology(manifest)
                        if problems:
                            raise CorruptCheckpoint("; ".join(problems))
                row["status"] = "verified" if deep else "present"
            except CorruptCheckpoint as e:
                row["status"] = "CORRUPT"
                row["error"] = str(e)
                report["ok"] = False
        report["steps"].append(row)
    if step is not None and not report["steps"]:
        report["ok"] = False
        report["error"] = f"no committed ckpt-{int(step)} in {dirname}"
    return report


def _cmd_ckpt(args):
    """Operator-facing checkpoint survey: ``inspect`` prints steps,
    per-shard manifest status, the saved mesh topology, and the
    latest/last-good pointers; ``verify`` re-hashes every file of every
    committed step (or ``--step N``) and exit-codes on corruption — so
    restorability is checkable from a cron job without loading a
    program or touching a device."""
    import json as _json

    if not os.path.isdir(args.dir):
        print(f"ckpt {args.action}: no such directory {args.dir!r}",
              file=sys.stderr)
        return 2
    deep = args.action == "verify"
    report = _ckpt_report(args.dir, step=args.step, deep=deep)
    if args.json:
        print(_json.dumps(report, indent=2, sort_keys=True))
    else:
        print(f"checkpoint dir: {report['dir']}")
        print(f"latest: {report['latest']}   "
              f"last_good: {report['last_good']}")
        for row in report["steps"]:
            line = (f"  ckpt-{row['step']}: {row['status']} "
                    f"[{row['format']}]")
            topo = row.get("topology")
            if topo:
                line += (f" mesh={topo['mesh_shape']}"
                         f"{topo['axis_names']}")
            sh = row.get("shards")
            if sh:
                line += (f" vars={sh['vars']} "
                         f"sharded={sh['sharded_vars']} "
                         f"shard_files={sh['shard_files']}")
            print(line)
            if row.get("error"):
                print(f"    {row['error']}")
        for q in report["quarantined"]:
            print(f"  {q}: quarantined")
        if report.get("error"):
            print(f"ckpt {args.action}: {report['error']}",
                  file=sys.stderr)
        verdict = "PASS" if report["ok"] else "FAIL"
        print(f"ckpt {args.action}: {verdict}")
    return 0 if report["ok"] else 1


def _cmd_trace(args):
    """Dump the span ring as Chrome trace-event JSON — this process's
    ring with --local (enable PADDLE_TPU_TRACE first), a serving
    replica's via its /trace endpoint, or (--fleet, against a router)
    the ASSEMBLED fleet timeline: every process's spans merged onto one
    clock with a distinct pid row per process.  The output loads
    directly in Perfetto (ui.perfetto.dev) or chrome://tracing."""
    import json as _json

    if args.action != "dump":
        print(f"trace: unknown action {args.action!r} (want: dump)",
              file=sys.stderr)
        return 2
    if args.fleet:
        import urllib.request
        if not args.addr:
            print("trace dump --fleet: need --addr ROUTER_HOST:PORT",
                  file=sys.stderr)
            return 2
        url = f"http://{args.addr}/trace?fleet=1"
        with urllib.request.urlopen(url, timeout=60) as r:
            obj = _json.loads(r.read())
    elif args.addr:
        from paddle_tpu.serving import ServingClient
        obj = ServingClient(args.addr).trace()
    else:
        from paddle_tpu.obs import trace as _trace
        obj = _trace.chrome_trace()
    body = _json.dumps(obj)
    if args.output:
        with open(args.output, "w") as f:
            f.write(body)
        print(f"wrote {len(obj['traceEvents'])} span(s) to {args.output}")
    else:
        print(body)
    return 0


def _cmd_replay(args):
    """Re-execute a quarantined training step from its repro bundle
    (``fault.Sentinel`` quarantine output) under the CPU platform — the
    offline debugging loop for a numerical fault seen on the chip.
    ``--localize`` re-executes op by op with per-op tensor-stat probes
    and names the FIRST op whose output went non-finite, with its
    Python creation site and the stat trail of the ops before it.
    Exit code 0 when the fault reproduces/localizes, 1 when the step
    replays clean, 2 on a malformed bundle."""
    import json as _json

    # pin the CPU platform BEFORE any backend initializes: the bundle
    # replays on CPU regardless of what killed the TPU run — even when
    # the launcher environment exported JAX_PLATFORMS=tpu.  The env
    # override is restored afterwards so in-process callers don't leak
    # it into subprocesses they spawn later.
    prev_platform = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        import jax
        try:
            jax.config.update("jax_platforms", "cpu")
        except Exception:
            pass  # backend already initialized (in-process use): keep it
        try:
            if args.localize:
                from paddle_tpu.obs.numerics import localize_bundle
                report = localize_bundle(args.bundle)
            else:
                from paddle_tpu.fault.sentinel import replay_bundle
                report = replay_bundle(args.bundle)
        except (OSError, ValueError, KeyError) as e:
            print(f"replay: cannot load bundle {args.bundle!r}: {e}",
                  file=sys.stderr)
            return 2
    finally:
        if prev_platform is None:
            os.environ.pop("JAX_PLATFORMS", None)
        else:
            os.environ["JAX_PLATFORMS"] = prev_platform
    if args.localize:
        return _report_localize(report, json_out=args.json)
    if args.json:
        print(_json.dumps(report, indent=2, sort_keys=True))
    elif report["reproduced"]:
        bad = ", ".join(report["bad"][:6]) or "(loss spike)"
        print(f"step {report['step']}: fault REPRODUCED "
              f"({report['reason']}) in: {bad}"
              + (" [chaos-injected]" if report["injected"] else ""))
    else:
        print(f"step {report['step']}: replayed CLEAN — the fault did "
              f"not reproduce on CPU (suspect hardware/nondeterminism)")
    return 0 if report["reproduced"] else 1


def _report_localize(report, json_out=False):
    """Print a ``numerics.localize_bundle`` report; exit 0 = localized,
    1 = every op produced finite outputs."""
    import json as _json

    if json_out:
        print(_json.dumps(report, indent=2, sort_keys=True))
        return 0 if report["localized"] else 1
    if not report["localized"]:
        print(f"step {report['step']}: all {report['ops_probed']} op "
              f"execution(s) produced finite outputs — nothing to "
              f"localize (suspect hardware/nondeterminism)")
        return 1
    bad = report["first_bad_op"]
    site = bad.get("creation_site")
    where = f"{site[0]}:{site[1]}" if site else "(unknown site)"
    tag = " [chaos-injected]" if report["injected"] else ""
    print(f"step {report['step']}: first non-finite output at op "
          f"#{bad['index']} `{bad['type']}` created at {where}{tag}")
    for name, stats in (bad.get("outputs") or {}).items():
        print(f"  out {name}: {stats}")
    for name, stats in (bad.get("inputs") or {}).items():
        print(f"  in  {name}: {stats}")
    trail = bad.get("trail") or []
    if trail:
        print(f"  trail (last {len(trail)} op(s) before the fault):")
        for row in trail:
            outs = ", ".join(row.get("outputs", {}))
            print(f"    #{row['index']} {row['type']} -> {outs}")
    return 0


def _fmt_cell(value):
    if value is None:
        return "-"
    if isinstance(value, float):
        return f"{value:.6g}"
    return str(value)


def _cmd_runs(args):
    """Read-side of the run ledger (``obs.ledger``): ``tail`` prints
    the last N step rows of a ledger directory, ``show`` a whole-run
    digest (row/segment counts, per-field first/last/min/max),
    ``compare`` two runs side by side with last-value deltas.  Pure
    file readers — no executor, no device, usable while the training
    process is still appending.  Exit 2 on an unreadable ledger."""
    import json as _json

    from paddle_tpu.obs import ledger as _ledger

    try:
        if args.action == "tail":
            rows = _ledger.tail_rows(args.dir, n=args.n)
            if args.json:
                print(_json.dumps(rows, indent=2, sort_keys=True))
                return 0
            fields = [f for f in _ledger.ROW_FIELDS
                      if any(r.get(f) is not None for r in rows)]
            header = ["step", "time_unix"] + fields
            print("  ".join(header))
            for r in rows:
                print("  ".join(_fmt_cell(r.get(k)) for k in header))
            return 0
        if args.action == "show":
            body = _ledger.summarize(args.dir)
            if args.json:
                print(_json.dumps(body, indent=2, sort_keys=True))
                return 0
            print(f"{body['dir']}: {body['rows']} row(s) in "
                  f"{body['segments']} segment(s), steps "
                  f"{body['first_step']}..{body['last_step']}")
            for field, s in sorted(body["fields"].items()):
                print(f"  {field}: first={_fmt_cell(s['first'])} "
                      f"last={_fmt_cell(s['last'])} "
                      f"min={_fmt_cell(s['min'])} "
                      f"max={_fmt_cell(s['max'])} "
                      f"({s['samples']} sample(s))")
            return 0
        # compare
        if not args.dir_b:
            print("runs compare: need two ledger directories",
                  file=sys.stderr)
            return 2
        body = _ledger.compare(args.dir, args.dir_b)
        if args.json:
            print(_json.dumps(body, indent=2, sort_keys=True))
            return 0
        print(f"A: {body['a']['dir']} ({body['a']['rows']} row(s), "
              f"last step {body['a']['last_step']})")
        print(f"B: {body['b']['dir']} ({body['b']['rows']} row(s), "
              f"last step {body['b']['last_step']})")
        for field, d in sorted(body["deltas"].items()):
            print(f"  {field}: A last={_fmt_cell(d['a_last'])}  "
                  f"B last={_fmt_cell(d['b_last'])}  "
                  f"delta={_fmt_cell(d['delta_last'])}")
        return 0
    except ValueError as e:
        print(f"runs: {e}", file=sys.stderr)
        return 2


def _load_saved_program(target):
    """(program, feeds, fetches) from a save_inference_model dir or a
    ``__model__`` json file; raises the loader errors."""
    from paddle_tpu.analysis.distributed import load_saved_program
    return load_saved_program(target)


def _cmd_lint(args):
    """Static analysis over a Program IR (``paddle_tpu.analysis``):
    lint a saved inference model (its ``__model__`` program, no params
    or executor needed — the analysis is static) or a model-zoo
    program built forward+backward.  Multi-program modes lint a whole
    transpiled FAMILY as one unit: a dir with ``gen_meta.json`` lints
    the prefill+decode pair plus the cross-program signature checks, a
    ``--pair trainer pserver`` lints Send/Recv matching and split
    reassembly, ``--pipeline N`` splits the program into N stages and
    verifies boundary carriers and cross-stage collective sync.
    Prints rustc-style diagnostics with stable ``PTA***`` codes; exit
    0 = clean, 1 = findings (errors always; warnings only under
    --strict), 2 = bad target."""
    import json as _json

    from paddle_tpu import analysis

    # ---- multi-program modes: results come pre-analyzed ----
    results = None  # list of (label, AnalysisResult)
    if args.pair:
        members = []
        for role, target in zip(("trainer", "pserver"), args.pair):
            try:
                program, feeds, fetches = _load_saved_program(target)
            except (OSError, ValueError, KeyError) as e:
                print(f"lint: cannot load a program from {target!r}: "
                      f"{e}", file=sys.stderr)
                return 2
            members.append((role, program, feeds, fetches))
        results = [(label, analysis.lint_program(
            program, feed_names=feeds, fetch_names=fetches))
            for label, program, feeds, fetches in members]
        results.append(("pair", analysis.lint_pair(
            (members[0][0], members[0][1]),
            [(members[1][0], members[1][1])])))
    elif args.target and os.path.isdir(args.target) and \
            os.path.isfile(os.path.join(args.target, "gen_meta.json")):
        try:
            results = analysis.lint_gen_bundle(args.target)
        except (OSError, ValueError, KeyError) as e:
            print(f"lint: cannot load the gen bundle at "
                  f"{args.target!r}: {e}", file=sys.stderr)
            return 2
    if results is not None:
        if args.dot:
            print("lint: --dot renders exactly one main program "
                  "(not a --pair / gen-bundle family)", file=sys.stderr)
            return 2
        return _report_lint(results, args)

    targets = []  # (label, program, feed_names, fetch_names)
    if args.zoo:
        from paddle_tpu.models import ZOO_MODELS, build_train_program
        names = ZOO_MODELS if args.zoo == "all" else [args.zoo]
        for name in names:
            try:
                main, startup, feeds, fetches = build_train_program(
                    name, backward=not args.no_backward)
            except ValueError as e:
                print(f"lint: {e}", file=sys.stderr)
                return 2
            targets.append((name, main, feeds, fetches))
            targets.append((f"{name}/startup", startup, None, None))
    elif args.target:
        try:
            program, feeds, fetches = _load_saved_program(args.target)
        except (OSError, ValueError, KeyError) as e:
            print(f"lint: cannot load a program from "
                  f"{args.target!r}: {e}", file=sys.stderr)
            return 2
        targets.append((args.target, program, feeds, fetches))
    else:
        print("lint: need a MODEL_DIR, --zoo NAME|all, or --pair "
              "TRAINER PSERVER", file=sys.stderr)
        return 2

    # --feed/--fetch override the MAIN programs only: the auto-added
    # */startup companions have neither feeds nor the main's fetch vars
    if args.feed:
        feed_override = [s for s in args.feed.split(",") if s]
        targets = [(lbl, p,
                    fd if lbl.endswith("/startup") else feed_override, ft)
                   for lbl, p, fd, ft in targets]
    if args.fetch:
        fetch_override = [s for s in args.fetch.split(",") if s]
        targets = [(lbl, p, fd,
                    ft if lbl.endswith("/startup") else fetch_override)
                   for lbl, p, fd, ft in targets]

    if args.dot:
        mains = [(lbl, p) for lbl, p, _, _ in targets
                 if not lbl.endswith("/startup")]
        if len(mains) != 1:
            print(f"lint: --dot renders exactly one main program, got "
                  f"{len(mains)} (use one MODEL_DIR or --zoo NAME, not "
                  f"--zoo all)", file=sys.stderr)
            return 2
        from paddle_tpu.analysis.visualize import program_dot
        program_dot(mains[0][1], path=args.dot)
        print(f"wrote {args.dot} ({mains[0][0]})")

    results = []
    for label, program, feeds, fetches in targets:
        results.append((label, analysis.lint_program(
            program, feed_names=feeds, fetch_names=fetches)))
        # like --feed/--fetch, --pipeline applies to MAIN programs
        # only: splitting a */startup initializer into "stages"
        # verifies nothing and its host-op shape could abort the run
        if args.pipeline and not label.endswith("/startup"):
            try:
                results.append((f"{label}/pipeline{args.pipeline}",
                                analysis.lint_pipeline(
                                    program, args.pipeline, feeds,
                                    fetches)))
            except ValueError as e:
                # the split itself rejected the program (e.g. a
                # tensor_array would cross a cut) — a target problem,
                # not a diagnostic
                print(f"lint: {label}: {e}", file=sys.stderr)
                return 2
    return _report_lint(results, args)


def _cmd_opt(args):
    """Offline run of the ``analysis/opt`` pass pipeline: optimize a
    saved model (or zoo programs) and print the per-pass diff/stats
    report — what ``PADDLE_TPU_OPT=1`` would do to this program inside
    the executor, inspectable without running anything.  Exit 0 on a
    clean run, 1 when any pass was sandwich-aborted, 2 on a bad
    target."""
    import json as _json

    from paddle_tpu.analysis import cost
    from paddle_tpu.analysis.opt import optimize_program

    targets = []  # (label, program, feeds, fetches)
    if args.zoo:
        from paddle_tpu.models import ZOO_MODELS, build_train_program
        names = ZOO_MODELS if args.zoo == "all" else [args.zoo]
        for name in names:
            try:
                main, startup, feeds, fetches = build_train_program(
                    name, backward=not args.no_backward)
            except ValueError as e:
                print(f"opt: {e}", file=sys.stderr)
                return 2
            targets.append((name, main, feeds, fetches))
            targets.append((f"{name}/startup", startup, None, None))
    elif args.target:
        try:
            program, feeds, fetches = _load_saved_program(args.target)
        except (OSError, ValueError, KeyError) as e:
            print(f"opt: cannot load a program from {args.target!r}: "
                  f"{e}", file=sys.stderr)
            return 2
        targets.append((args.target, program, feeds, fetches))
    else:
        print("opt: need a MODEL_DIR or --zoo NAME|all",
              file=sys.stderr)
        return 2

    passes = None
    if args.passes:
        passes = [s for s in args.passes.split(",") if s]

    aborted = 0
    reports = []
    for label, program, feeds, fetches in targets:
        try:
            optimized, report = optimize_program(
                program, feed_names=feeds, fetch_names=fetches,
                passes=passes)
        except ValueError as e:
            print(f"opt: {e}", file=sys.stderr)
            return 2
        aborted += len(report.aborted_passes)
        if args.json:
            body = report.to_dict()
            body["target"] = label
            plan = getattr(optimized, "_donation_plan", None)
            body["donation_plan"] = plan.to_dict() if plan else None
            body["interpret"] = bool(getattr(optimized,
                                             "_opt_interpret", False))
            reports.append(body)
        else:
            print(f"== {label}")
            print(report.format())
            if report.flops_before is not None:
                print(f"  cost: {report.flops_before:,} -> "
                      f"{report.flops_after:,} static FLOPs")
            if getattr(optimized, "_opt_interpret", False):
                print("  amortization gate: run-once initializer — "
                      "will interpret instead of compile")
            plan = getattr(optimized, "_donation_plan", None)
            if plan is not None:
                print("  " + plan.report().splitlines()[0])
    if args.json:
        print(_json.dumps({"targets": reports}, indent=2))
    return 1 if aborted else 0


def _report_lint(results, args):
    """Shared tail of ``paddle_tpu lint``: print (or JSON-dump) a list
    of ``(label, AnalysisResult)`` and map findings to the exit code."""
    import json as _json

    n_err = sum(len(r.errors) for _, r in results)
    n_warn = sum(len(r.warnings) for _, r in results)
    uncovered = set()
    for _, r in results:
        uncovered.update(r.uncovered_op_types)
    if args.json:
        reports = [{
            "target": label,
            "diagnostics": [d.to_dict() for d in r.diagnostics],
            "uncovered_op_types": r.uncovered_op_types}
            for label, r in results]
        print(_json.dumps({"targets": reports, "errors": n_err,
                           "warnings": n_warn}, indent=2))
    else:
        for label, r in results:
            for d in r.diagnostics:
                print(f"[{label}] {d.format()}")
        print(f"lint: {len(results)} program(s): {n_err} error(s), "
              f"{n_warn} warning(s)")
        if uncovered and args.verbose:
            print(f"  warn-list ({len(uncovered)} op type(s) without an "
                  f"inference rule — shapes/dtypes not propagated "
                  f"through them): {', '.join(sorted(uncovered))}")
    return 1 if n_err or (args.strict and n_warn) else 0


def _cmd_selfcheck(args):
    """One exit-coded pass over every static gate (the pre-merge /
    pre-deploy command CI runs): strict lint of the whole model zoo in
    single-program AND multi-program (distribute-transpiled, pipeline-
    split, gen-exported) modes, plus the scanner-enforced registries —
    diagnostics, metrics, failpoints — that keep docs and code in
    lockstep.  Exit 0 = everything green, 1 = any section failed."""
    import json as _json

    from paddle_tpu.analysis.selfcheck import run_selfcheck

    report = run_selfcheck()
    if args.json:
        print(_json.dumps(report, indent=2, sort_keys=True))
    else:
        for section in report["sections"]:
            mark = "ok  " if section["ok"] else "FAIL"
            print(f"[{mark}] {section['name']}: {section['detail']}")
            for line in section.get("failures", []):
                print(f"       {line}")
        print(f"selfcheck: {'PASS' if report['ok'] else 'FAIL'} "
              f"({sum(s['ok'] for s in report['sections'])}/"
              f"{len(report['sections'])} sections green)")
    return 0 if report["ok"] else 1


def _cmd_launch(args):
    """Spawn an N-process jax.distributed cluster on this host (the
    cluster_train launcher analog; each process gets the reference's
    TRAINER_ID / TRAINERS env convention)."""
    port = args.port
    procs = []
    for rank in range(args.nproc):
        env = dict(os.environ)
        env["PADDLE_COORDINATOR"] = f"127.0.0.1:{port}"
        env["PADDLE_TRAINER_ID"] = str(rank)
        env["PADDLE_TRAINERS"] = str(args.nproc)
        procs.append(subprocess.Popen(
            [sys.executable, args.script] + (args.script_args or []),
            env=env))
    rc = 0
    for p in procs:
        p.wait()
        rc = rc or p.returncode
    return rc


def _profile_build(args):
    """Shared model-building head of the ``profile op|step`` modes:
    returns ``(exe, main_prog, startup, feed, cost_name)``."""
    import numpy as np

    import paddle_tpu as fluid

    if args.model == "transformer":
        from paddle_tpu.models import transformer as T
        hp = T.ModelHyperParams()
        hp.d_model, hp.d_inner_hid, hp.n_layer = args.d_model, \
            2 * args.d_model, args.layers
        hp.n_head = max(1, args.d_model // 64)
        hp.d_key = hp.d_value = args.d_model // hp.n_head
        hp.src_vocab_size = hp.trg_vocab_size = 1000
        hp.max_length = max(64, args.seq)
        main_prog, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main_prog, startup):
            cost, _ = T.transformer(args.batch, args.seq, args.seq, hp)
            fluid.optimizer.Adam(learning_rate=1e-4).minimize(cost)
        feed = T.fake_batch(args.batch, args.seq, args.seq, hp)
    elif args.model == "resnet":
        from paddle_tpu.models import resnet as R
        main_prog, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main_prog, startup):
            cost, _, _ = R.resnet_train_program(
                args.batch, class_dim=1000, depth=50,
                image_shape=(3, args.seq, args.seq))
            fluid.optimizer.Momentum(learning_rate=0.01,
                                     momentum=0.9).minimize(cost)
        rng = np.random.RandomState(0)
        feed = {"image": rng.rand(args.batch, 3, args.seq,
                                  args.seq).astype("float32"),
                "label": rng.randint(0, 1000, (args.batch, 1))
                .astype("int64")}
    else:
        raise SystemExit(f"unknown --model {args.model!r}")
    exe = fluid.Executor()
    exe.run(startup)
    return exe, main_prog, startup, feed, cost.name


def _fmt_bytes(n):
    return "-" if n is None else f"{n / 1e6:.2f}MB"


def _fmt_ms(s):
    return "-" if s is None else f"{s * 1e3:.1f}ms"


def _profile_zoo_compile(args):
    """Fresh-compile a zoo model (startup + one synthetic train step)
    so every jit key lands a cost/memory record; returns the scope (for
    ``profile memory``'s census)."""
    from paddle_tpu.models import ZOO_MODELS, compile_zoo_step

    name = args.zoo or "mnist"
    if name not in ZOO_MODELS:
        raise SystemExit(f"unknown --zoo {name!r}; expected one of "
                         f"{ZOO_MODELS}")
    return compile_zoo_step(name)


def _cmd_profile_compile(args):
    """``paddle_tpu profile compile``: fresh-compile a zoo model and
    print the per-jit-key table — XLA cost-analysis FLOPs and bytes,
    the memory_analysis breakdown, and the trace/lower/backend phase
    wall times the compile actually paid."""
    import json as _json

    from paddle_tpu.obs import perf

    _profile_zoo_compile(args)
    report = perf.compile_report()
    if args.json:
        print(_json.dumps(report, indent=2, sort_keys=True))
        return 0
    print(f"backend={report['backend']} "
          f"peak={report['peak_flops_per_chip']:.3g} FLOP/s "
          f"({report['mfu_basis']})")
    print(f"{'key':<10}{'GFLOPs':>10}{'bytes':>12}{'arg':>10}"
          f"{'out':>10}{'temp':>10}{'trace':>9}{'lower':>9}"
          f"{'compile':>9}  label")
    for r in report["records"]:
        mem = r["memory"] or {}
        ph = r["phases"]
        flops = "-" if r["flops"] is None else f"{r['flops'] / 1e9:.3f}"
        print(f"{r['key']:<10}{flops:>10}"
              f"{_fmt_bytes(r['bytes_accessed']):>12}"
              f"{_fmt_bytes(mem.get('argument_bytes')):>10}"
              f"{_fmt_bytes(mem.get('output_bytes')):>10}"
              f"{_fmt_bytes(mem.get('temp_bytes')):>10}"
              f"{_fmt_ms(ph['trace_seconds']):>9}"
              f"{_fmt_ms(ph['lower_seconds']):>9}"
              f"{_fmt_ms(ph['backend_seconds']):>9}  {r['label']}")
    return 0


def _cmd_profile_memory(args):
    """``paddle_tpu profile memory``: the HBM census — live device
    bytes attributed to params / optimizer state / KV slots / prefetch
    / other, plus the high watermark and (when the backend or
    PADDLE_TPU_HBM_LIMIT_BYTES declares a limit) the headroom."""
    import json as _json

    from paddle_tpu.obs import perf

    scope = _profile_zoo_compile(args)
    census = perf.hbm_census(scope)
    if args.json:
        print(_json.dumps(census, indent=2, sort_keys=True))
        return 0
    for key in ("params", "optimizer", "kv_pages", "prefetch", "other",
                "total", "high_watermark", "limit", "headroom"):
        if key in census:
            print(f"hbm.{key:<16}{census[key]:>14} bytes")
    return 0


def _cmd_profile_step(args):
    """``paddle_tpu profile step``: N measured steps with the per-step
    breakdown armed (feed / dispatch / device-wait / fetch series) —
    composed with the jax.profiler plumbing via ``--trace-dir`` for an
    XProf/Perfetto device timeline of the same window, reduced to the
    device-time table by role / name scope / op type — plus the live MFU
    the window sustained."""
    from paddle_tpu import profiler
    from paddle_tpu.obs import perf

    exe, main_prog, _startup, feed, cost_name = _profile_build(args)
    exe.run(main_prog, feed=feed, fetch_list=[cost_name])  # compile
    perf.enable_step_phases()
    try:
        if args.trace_dir:
            profiler.start_profiler(profile_path=args.trace_dir)
        for _ in range(args.steps):
            exe.run(main_prog, feed=feed, fetch_list=[cost_name])
    finally:
        if args.trace_dir:
            profiler.stop_profiler()
        perf.disable_step_phases()
    m = profiler.runtime_metrics
    print(f"{'phase':<14}{'p50':>10}{'p95':>10}")
    for phase in ("feed", "dispatch", "device_wait", "fetch"):
        p = m.percentiles(f"perf.step.{phase}_seconds", qs=(50, 95))
        print(f"{phase:<14}{_fmt_ms(p['p50']):>10}"
              f"{_fmt_ms(p['p95']):>10}")
    mfu = m.gauge("train.mfu")
    basis = perf.peak_flops_info()[1]
    if mfu is not None:
        print(f"train.mfu={mfu:.4f} ({basis})")
    if args.trace_dir:
        # where the device's time went, by role / name scope / op type
        # (leaf events, mean over the chips: profiler.compiled_op_groups)
        table, _ = profiler.compiled_op_table(
            args.trace_dir, args.sorted_by, by=("role", "scope", "type"))
        print(table)
        print(f"device trace written under {args.trace_dir} "
              f"(TensorBoard/XProf or Perfetto)")
    return 0


def _cmd_profile(args):
    """The ``paddle_tpu profile`` family: ``op`` (default) prints the
    per-IR-op device-time table of a compiled training step; ``compile``
    the per-jit-key cost/memory/phase table; ``memory`` the HBM census;
    ``step`` the N-step feed/dispatch/device-wait/fetch breakdown."""
    if args.action == "compile":
        return _cmd_profile_compile(args)
    if args.action == "memory":
        return _cmd_profile_memory(args)
    if args.action == "step":
        return _cmd_profile_step(args)
    from paddle_tpu import profiler
    exe, main_prog, _startup, feed, cost_name = _profile_build(args)
    exe.run(main_prog, feed=feed, fetch_list=[cost_name])  # compile
    with profiler.compiled_profiler(sorted_key=args.sorted_by):
        for _ in range(args.steps):
            exe.run(main_prog, feed=feed, fetch_list=[cost_name])
    return 0


def main(argv=None):
    parser = argparse.ArgumentParser(prog="paddle_tpu", description=__doc__)
    sub = parser.add_subparsers(dest="command", required=True)

    p = sub.add_parser("version", help="print version info")
    p.set_defaults(fn=_cmd_version)

    p = sub.add_parser("train", help="run a training script")
    p.add_argument("--config", required=True, help="python training script")
    p.add_argument("--num-passes", type=int, default=None)
    p.add_argument("--checkpoint-dir", default=None,
                   help="export PADDLE_TPU_CKPT_DIR for the script's "
                        "fault.CheckpointManager")
    p.add_argument("--keep-checkpoints", type=int, default=5)
    p.add_argument("script_args", nargs="*")
    p.set_defaults(fn=_cmd_train)

    p = sub.add_parser("infer", help="run a saved inference model")
    p.add_argument("--model", required=True, help="save_inference_model dir")
    p.add_argument("--feed", action="append",
                   help="name=path.npy (repeatable)")
    p.add_argument("--output", default=None, help="dir for output .npy")
    p.set_defaults(fn=_cmd_infer)

    p = sub.add_parser("master", help="serve the data-task master")
    p.add_argument("--files", required=True, help="glob of input files")
    p.add_argument("--chunks-per-task", type=int, default=1)
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8037)
    p.add_argument("--timeout", type=float, default=60.0)
    p.add_argument("--failure-max", type=int, default=3)
    p.add_argument("--snapshot", default=None,
                   help="snapshot file for restart recovery")
    p.add_argument("--heartbeat-timeout", type=float, default=None,
                   help="reclaim leases of trainers silent this long "
                        "(default: lease timeout only)")
    p.set_defaults(fn=_cmd_master)

    p = sub.add_parser("serve", help="HTTP inference server")
    p.add_argument("--model", required=True, help="save_inference_model dir")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8866)
    p.add_argument("--async-load", action="store_true",
                   help="serve /healthz immediately; load the model in "
                        "the background (/readyz gates traffic)")
    p.add_argument("--max-inflight", type=int, default=32,
                   help="concurrent /predict slots before 503 "
                        "load-shedding")
    p.add_argument("--request-timeout", type=float, default=None,
                   help="per-request deadline waiting on the predictor "
                        "(504 when exceeded)")
    p.add_argument("--batch", action="store_true",
                   help="coalesce concurrent /predict requests into "
                        "padded row-bucketed micro-batches")
    p.add_argument("--max-batch-size", type=int, default=8,
                   help="max requests coalesced into one dispatch")
    p.add_argument("--max-batch-delay", type=float, default=0.005,
                   help="seconds the batcher lingers for co-batchable "
                        "requests after the first arrives")
    p.add_argument("--batch-queue-size", type=int, default=128,
                   help="bounded batch queue depth before 503 "
                        "load-shedding")
    p.add_argument("--warmup", action="store_true",
                   help="AOT-compile declared feed shapes / serving "
                        "buckets before /readyz reports ready")
    p.add_argument("--warmup-batch-sizes", default=None,
                   help="comma-separated batch sizes to warm "
                        "(default: the batcher's bucket edges)")
    p.add_argument("--compile-cache", default=None,
                   help="persistent XLA compilation cache dir "
                        "(PADDLE_TPU_COMPILE_CACHE; default "
                        "<checkout>/.jax_cache; JAX_COMPILATION_CACHE_DIR "
                        "wins over both): restarts reuse compiled "
                        "executables instead of recompiling")
    p.add_argument("--master", default=None,
                   help="HOST:PORT of the fleet master: register this "
                        "replica for discovery and heartbeat-renew its "
                        "lease (SIGTERM drains cleanly)")
    p.add_argument("--replica-id", default=None,
                   help="stable replica id (default: generated)")
    p.add_argument("--lease-ttl", type=float, default=5.0,
                   help="fleet lease TTL seconds; missing renews this "
                        "long drops the replica from routing")
    p.add_argument("--drain-deadline-s", type=float, default=30.0,
                   help="rolling-restart drain bound: seconds in-flight "
                        "generative streams may run to completion "
                        "before the rest are checkpoint-migrated to "
                        "survivors")
    p.add_argument("--advertise-host", default=None,
                   help="host other machines should dial (default: the "
                        "bind host)")
    p.add_argument("--gen-admission", default="continuous",
                   choices=("continuous", "batch"),
                   help="generation-bundle scheduler policy: admit into "
                        "free KV slots between decode steps "
                        "(continuous) or only between whole batches "
                        "(batch — the request-level baseline)")
    p.add_argument("--gen-queue-size", type=int, default=64,
                   help="bounded /generate admission queue depth before "
                        "503 load-shedding")
    p.set_defaults(fn=_cmd_serve)

    p = sub.add_parser("generate", help="stream tokens from a "
                                        "generation server's /generate")
    p.add_argument("--addr", required=True,
                   help="host:port of a serving replica or fleet router")
    p.add_argument("--prompt", required=True,
                   help="prompt token ids (space/comma separated)")
    p.add_argument("--max-new", type=int, default=16,
                   help="max tokens to generate")
    p.add_argument("--eos-id", type=int, default=None,
                   help="per-request EOS token override")
    p.add_argument("--timeout", type=float, default=60.0)
    p.add_argument("--deadline", type=float, default=None,
                   help="end-to-end budget seconds (sent as "
                        "X-Deadline-Ms)")
    p.add_argument("--no-stream", action="store_true",
                   help="buffered reply instead of chunked streaming")
    p.add_argument("--session-id", default=None,
                   help="resumable-session id (default: minted per "
                        "request; reuse one to resume after a failure)")
    p.add_argument("--no-resume", action="store_true",
                   help="disable mid-stream resume: a dead replica "
                        "surfaces as a terminal error event instead")
    p.set_defaults(fn=_cmd_generate)

    p = sub.add_parser("router", help="health-aware fleet router over "
                                      "serving replicas")
    p.add_argument("--master", default=None,
                   help="HOST:PORT of the fleet master (live replica "
                        "discovery)")
    p.add_argument("--replicas", default=None,
                   help="comma-separated host:port list (static fleet, "
                        "no master)")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8868)
    p.add_argument("--default-deadline", type=float, default=30.0,
                   help="end-to-end budget seconds for requests without "
                        "an X-Deadline-Ms header")
    p.add_argument("--poll-interval", type=float, default=0.25,
                   help="master discovery poll interval seconds")
    p.add_argument("--slo", default=None, metavar="SPEC.json",
                   help="SLO spec to evaluate in-router (breach "
                        "counters + post-mortem on sustained breach; "
                        "default: PADDLE_TPU_SLO when set)")
    p.set_defaults(fn=_cmd_router)

    p = sub.add_parser("controller",
                       help="fleet router + closed-loop autoscaler "
                            "(warm-standby scale-up, idle drain, "
                            "admission-control backpressure)")
    p.add_argument("--master", required=True,
                   help="HOST:PORT of the fleet master (replica "
                        "discovery AND standby enrollment)")
    p.add_argument("--model", required=True,
                   help="save_inference_model dir standbys serve")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8868)
    p.add_argument("--policy", default=None, metavar="POLICY.json",
                   help="autoscaler policy (default: "
                        "PADDLE_TPU_AUTOSCALE when set, else the "
                        "documented defaults; `paddle_tpu selfcheck` "
                        "validates the schema)")
    p.add_argument("--slo", default=None, metavar="SPEC.json",
                   help="SLO spec the controller steers by (default: "
                        "PADDLE_TPU_SLO when set)")
    p.add_argument("--default-deadline", type=float, default=30.0,
                   help="end-to-end budget seconds for requests without "
                        "an X-Deadline-Ms header")
    p.add_argument("--poll-interval", type=float, default=0.25,
                   help="master discovery poll interval seconds")
    p.add_argument("--lease-ttl", type=float, default=5.0,
                   help="fleet lease TTL seconds for promoted standbys")
    p.add_argument("--compile-cache", default=None,
                   help="persistent XLA compilation cache dir "
                        "(PADDLE_TPU_COMPILE_CACHE; default "
                        "<checkout>/.jax_cache; JAX_COMPILATION_CACHE_DIR "
                        "wins over both): standby warms reuse compiled "
                        "executables — scale-up is a lease "
                        "registration, not a compile")
    p.set_defaults(fn=_cmd_controller)

    p = sub.add_parser("stats", help="fetch a serving replica's /stats "
                                     "metrics snapshot")
    p.add_argument("--addr", default=None, help="host:port of the server")
    p.add_argument("--local", action="store_true",
                   help="this process's own metrics registry instead of "
                        "a remote server (datapipe/executor counters)")
    p.add_argument("--json", action="store_true",
                   help="raw JSON instead of the formatted table")
    p.add_argument("--prom", action="store_true",
                   help="Prometheus text exposition format (the /metrics "
                        "body) instead of the snapshot table")
    p.set_defaults(fn=_cmd_stats)

    p = sub.add_parser("trace", help="dump the span ring as Chrome "
                                     "trace-event JSON (Perfetto)")
    p.add_argument("action", choices=["dump"])
    p.add_argument("--addr", default=None,
                   help="host:port of a serving replica (/trace) or, "
                        "with --fleet, of the fleet router; "
                        "default: this process's ring (--local)")
    p.add_argument("--local", action="store_true",
                   help="this process's span ring (the default when "
                        "--addr is not given)")
    p.add_argument("--fleet", action="store_true",
                   help="assembled fleet timeline via the router's "
                        "/trace?fleet=1: every process's spans merged "
                        "onto one clock, one pid row per process")
    p.add_argument("--output", default=None,
                   help="write the JSON here instead of stdout")
    p.set_defaults(fn=_cmd_trace)

    p = sub.add_parser("fleet-stats",
                       help="federated fleet metrics: one Prometheus "
                            "exposition over every replica's registry "
                            "(per-replica labels + rollups; dead "
                            "replicas marked stale)")
    p.add_argument("--router", default=None,
                   help="host:port of the fleet router (proxies its "
                        "/metrics?fleet=1 — keeps rate state between "
                        "pulls)")
    p.add_argument("--master", default=None,
                   help="HOST:PORT of the fleet master: scrape the "
                        "current lease table in-process")
    p.add_argument("--replicas", default=None,
                   help="comma-separated host:port list to scrape "
                        "(static fleet, no master)")
    p.add_argument("--timeout", type=float, default=5.0,
                   help="per-replica scrape timeout seconds")
    p.add_argument("--json", action="store_true",
                   help="per-replica scrape health instead of the "
                        "exposition text")
    p.set_defaults(fn=_cmd_fleet_stats)

    p = sub.add_parser("bench",
                       help="bench trajectory: record runs into "
                            "BENCH_TRAJECTORY.json and gate on "
                            "regressions vs the recorded baseline")
    p.add_argument("action", choices=["check", "record"])
    p.add_argument("--trajectory", default=None,
                   help="trajectory file (default: the repo's "
                        "BENCH_TRAJECTORY.json)")
    p.add_argument("--dry", action="store_true",
                   help="with check: validate the schema only (the "
                        "selfcheck gate), no regression comparison")
    p.add_argument("--bench", default=None,
                   help="with record: bench name (serving|datapipe|"
                        "fleet|decode)")
    p.add_argument("--summary", default=None,
                   help="with record: the bench's summary JSON to "
                        "import (e.g. BENCH_DECODE.json)")
    p.add_argument("--baseline", action="store_true",
                   help="with record: flag the run as the bench's "
                        "comparison baseline")
    p.add_argument("--json", action="store_true",
                   help="machine-readable report")
    p.set_defaults(fn=_cmd_bench)

    p = sub.add_parser("replay", help="re-execute a sentinel-quarantined "
                                      "step on CPU (exit 0 = fault "
                                      "reproduced)")
    p.add_argument("bundle", help="pickled repro bundle from the "
                                  "sentinel's quarantine dir")
    p.add_argument("--localize", action="store_true",
                   help="re-execute op by op with per-op tensor-stat "
                        "probes and name the FIRST op producing a "
                        "non-finite output (creation site + stat "
                        "trail); exit 0 = localized, 1 = clean")
    p.add_argument("--json", action="store_true",
                   help="machine-readable report instead of prose")
    p.set_defaults(fn=_cmd_replay)

    p = sub.add_parser("runs",
                       help="run-ledger readers (obs.ledger JSONL "
                            "step series): tail the last rows, digest "
                            "a whole run, or compare two runs")
    p.add_argument("action", choices=["tail", "show", "compare"])
    p.add_argument("dir", help="ledger directory (RunLedger dirname)")
    p.add_argument("dir_b", nargs="?", default=None,
                   help="second ledger directory (compare only)")
    p.add_argument("-n", type=int, default=10,
                   help="with tail: number of rows (default 10)")
    p.add_argument("--json", action="store_true",
                   help="machine-readable report")
    p.set_defaults(fn=_cmd_runs)

    p = sub.add_parser("lint", help="static-analyze a program IR "
                                    "(PTA*** diagnostics; "
                                    "docs/static_analysis.md)")
    p.add_argument("target", nargs="?", default=None,
                   help="save_inference_model dir (or a __model__ json "
                        "file) to lint; a dir with gen_meta.json lints "
                        "the whole generation bundle (prefill + decode "
                        "+ cross-program signature checks)")
    p.add_argument("--pair", nargs=2, metavar=("TRAINER", "PSERVER"),
                   default=None,
                   help="lint a transpiled trainer/pserver pair as one "
                        "unit: Send/Recv matching, split reassembly, "
                        "collective sync (PTA011-PTA014)")
    p.add_argument("--pipeline", type=int, default=None, metavar="N",
                   help="also split each linted program into N "
                        "pipeline stages and verify boundary carriers "
                        "+ cross-stage collective sync (PTA011/PTA015)")
    p.add_argument("--zoo", default=None,
                   help="lint a built-in model's forward+backward "
                        "program instead (mnist|resnet|vgg|transformer|"
                        "seq2seq|stacked_lstm|all)")
    p.add_argument("--no-backward", action="store_true",
                   help="with --zoo: lint the forward program only")
    p.add_argument("--feed", default=None,
                   help="comma-separated feed names (default: the "
                        "model's declared feeds)")
    p.add_argument("--fetch", default=None,
                   help="comma-separated fetch names (default: the "
                        "model's declared fetch targets)")
    p.add_argument("--strict", action="store_true",
                   help="exit 1 on warnings too, not just errors")
    p.add_argument("--dot", default=None, metavar="OUT",
                   help="also render the (single) main program as a "
                        "GraphViz .dot graph here: blocks as clusters, "
                        "gradients/donation annotated, op creation "
                        "sites as tooltips")
    p.add_argument("--json", action="store_true",
                   help="machine-readable diagnostics")
    p.add_argument("--verbose", action="store_true",
                   help="also print the warn-list of op types without "
                        "an inference rule")
    p.set_defaults(fn=_cmd_lint)

    p = sub.add_parser("opt", help="run the Program-IR optimization "
                                   "pipeline offline and print the "
                                   "per-pass diff/stats report "
                                   "(docs/static_analysis.md)")
    p.add_argument("target", nargs="?", default=None,
                   help="save_inference_model dir (or a __model__ json "
                        "file) to optimize")
    p.add_argument("--zoo", default=None,
                   help="optimize a built-in model's forward+backward "
                        "program instead (mnist|...|all)")
    p.add_argument("--no-backward", action="store_true",
                   help="with --zoo: the forward program only")
    p.add_argument("--passes", default=None,
                   help="comma-separated pass subset (default: the "
                        "full pipeline)")
    p.add_argument("--json", action="store_true",
                   help="machine-readable report")
    p.set_defaults(fn=_cmd_opt)

    p = sub.add_parser("ckpt",
                       help="survey a checkpoint directory: steps, "
                            "per-shard manifest status, saved mesh "
                            "topology, last-good pointer; verify "
                            "re-hashes and exit-codes on corruption")
    p.add_argument("action", choices=["inspect", "verify"])
    p.add_argument("dir", help="checkpoint directory "
                               "(CheckpointManager dirname)")
    p.add_argument("--step", type=int, default=None,
                   help="limit to one committed step")
    p.add_argument("--json", action="store_true",
                   help="machine-readable report")
    p.set_defaults(fn=_cmd_ckpt)

    p = sub.add_parser("selfcheck",
                       help="one exit-coded pass over every static "
                            "gate: strict zoo lint (single- AND "
                            "multi-program), the paged-KV export gate, "
                            "the scanner-enforced "
                            "diagnostic/metric/failpoint registries, "
                            "the SLO spec schema, the run-ledger "
                            "schema round-trip, and the bench-"
                            "trajectory schema (bench check --dry)")
    p.add_argument("--json", action="store_true",
                   help="machine-readable section report")
    p.set_defaults(fn=_cmd_selfcheck)

    p = sub.add_parser("profile",
                       help="device-performance profiling family: "
                            "per-op device time (op), per-jit-key XLA "
                            "cost/memory + compile phases (compile), "
                            "HBM census (memory), N-step "
                            "feed/dispatch/device-wait/fetch breakdown "
                            "(step)")
    p.add_argument("action", nargs="?", default="op",
                   choices=["op", "compile", "memory", "step"],
                   help="op = per-IR-op device-time table (default); "
                        "compile = per-jit-key FLOPs/bytes/memory "
                        "breakdown + trace/lower/compile phase times; "
                        "memory = live-buffer HBM census by collection; "
                        "step = per-step phase breakdown (+ --trace-dir "
                        "for the XProf device timeline)")
    p.add_argument("--model", default="transformer",
                   choices=["transformer", "resnet"],
                   help="built-in model for op/step modes")
    p.add_argument("--zoo", default="mnist",
                   help="zoo model for compile/memory modes "
                        "(mnist|resnet|vgg|transformer|seq2seq|"
                        "stacked_lstm|gen_lm)")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=64,
                   help="sequence length (transformer) or image side "
                        "(resnet)")
    p.add_argument("--d-model", type=int, default=128)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--steps", type=int, default=3)
    p.add_argument("--sorted-by", default="total",
                   choices=["total", "calls"])
    p.add_argument("--trace-dir", default=None,
                   help="with step: also capture a jax.profiler trace "
                        "of the measured window here")
    p.add_argument("--json", action="store_true",
                   help="machine-readable report (compile/memory)")
    p.set_defaults(fn=_cmd_profile)

    p = sub.add_parser("launch", help="spawn a local N-process cluster")
    p.add_argument("--nproc", type=int, required=True)
    p.add_argument("--port", type=int, default=8357)
    p.add_argument("script")
    p.add_argument("script_args", nargs="*")
    p.set_defaults(fn=_cmd_launch)

    args = parser.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
