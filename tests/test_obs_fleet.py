"""Fleet observability plane units: metrics federation
(obs.aggregate.FleetScraper), cross-process trace assembly with
clock-skew normalization, the SLO watchdog (obs.slo), and the bench
trajectory recorder/gate (obs.bench_history) + their CLI surfaces.
The end-to-end churn drill (kill a replica mid-scrape under a live
router) lives in tests/test_fleet.py next to the other chaos drills."""

import json
import os
import time
import warnings

import pytest

import paddle_tpu as fluid
import paddle_tpu.layers as layers
from paddle_tpu import cli, profiler
from paddle_tpu.obs import aggregate, bench_history, slo, trace
from paddle_tpu.profiler import RuntimeMetrics
from paddle_tpu.serving import InferenceServer

from fake_gen_predictor import FakeGenPredictor
from tests.test_obs_prom import assert_conformant


@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("obs_fleet") / "model")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[4])
        pred = layers.fc(input=x, size=2)
        exe = fluid.Executor()
        exe.run(startup)
        fluid.io.save_inference_model(d, ["x"], [pred], exe,
                                      main_program=main)
    return d


def _addr(server):
    return f"{server.addr[0]}:{server.addr[1]}"


# ---------------------------------------------------------------------------
# metrics federation
# ---------------------------------------------------------------------------

class TestFederation:
    def test_scrape_federate_and_stale_marking(self, model_dir):
        a = InferenceServer(model_dir, port=0)
        b = InferenceServer(model_dir, port=0)
        a.start_background()
        b.start_background()
        targets = [(_addr(a), "ra"), (_addr(b), "rb")]
        scraper = aggregate.FleetScraper(lambda: targets, timeout=5.0)
        try:
            profiler.runtime_metrics.inc("serving.requests_ok", 3)
            text, scrapes = scraper.federate()
            assert all(s["ok"] for s in scrapes)
            assert_conformant(text)
            # per-replica labels + liveness rows for both replicas
            for addr, rid in targets:
                assert (f'paddle_tpu_fleet_replica_up{{replica="{addr}"'
                        f',id="{rid}",stale="0"}} 1') in text
                assert f'replica="{addr}"' in text
            # first pass: totals but no rates yet
            assert "paddle_tpu_fleet_rps" not in text
            assert "paddle_tpu_fleet_replicas_scraped 2" in text
            assert "paddle_tpu_fleet_replicas_stale 0" in text

            # second pass computes rates from counter deltas
            profiler.runtime_metrics.inc("serving.requests_ok", 5)
            text, _ = scraper.federate()
            assert "paddle_tpu_fleet_rps " in text

            # kill one replica: the rollup must still render, with the
            # corpse marked stale instead of failing the scrape
            b.shutdown()
            errors0 = profiler.runtime_metrics.counter(
                "fleet.scrape.errors")
            text, scrapes = scraper.federate()
            assert_conformant(text)
            by_addr = {s["addr"]: s for s in scrapes}
            assert by_addr[_addr(a)]["ok"]
            assert not by_addr[_addr(b)]["ok"]
            assert by_addr[_addr(b)]["error"]
            assert (f'paddle_tpu_fleet_replica_up{{replica='
                    f'"{_addr(b)}",id="rb",stale="1"}} 0') in text
            assert "paddle_tpu_fleet_replicas_stale 1" in text
            # the live replica's samples still carry its label
            assert f'replica="{_addr(a)}"' in text
            assert f'total{{replica="{_addr(b)}"}}' not in text
            assert profiler.runtime_metrics.counter(
                "fleet.scrape.errors") > errors0
        finally:
            a.shutdown()
            try:
                b.shutdown()
            except Exception:
                pass

    def test_rates_survive_replica_death_between_scrapes(self):
        """Review regression: deltas are per-replica — a replica dying
        (its counters leaving the live sum) must not zero the
        survivors' reported fleet rate."""
        m = RuntimeMetrics()

        def scrape_of(addr, requests):
            return {"addr": addr, "id": addr, "ok": True,
                    "stats": {"counters":
                              {"serving.requests_ok": requests}}}

        scraper = aggregate.FleetScraper(lambda: [], metrics=m)
        rps, _ = scraper._rates([scrape_of("a", 10000),
                                 scrape_of("b", 10000)])
        assert rps is None                      # first pass: no window
        time.sleep(0.02)
        # b died; a served 50 more requests — the fleet rate is a's
        # delta, NOT max(0, 10050 - 20000) == 0
        rps, _ = scraper._rates([scrape_of("a", 10050)])
        assert rps is not None and rps > 0
        time.sleep(0.02)
        # b restarts with reset counters: clamped per-replica, a's
        # delta still counts
        rps, _ = scraper._rates([scrape_of("a", 10100),
                                 scrape_of("b", 3)])
        assert rps is not None and rps > 0

    def test_merged_quantile_is_count_weighted(self):
        def scrape(count, p99):
            return {"ok": True, "stats": {"series": {
                "gen.ttft_seconds": {"count": count, "p99": p99}}}}
        scrapes = [scrape(30, 0.1), scrape(10, 0.5),
                   {"ok": False, "stats": None}]
        got = aggregate.merged_quantile(scrapes, "gen.ttft_seconds",
                                        "p99")
        assert got == pytest.approx((30 * 0.1 + 10 * 0.5) / 40)
        assert aggregate.merged_quantile(scrapes, "nope") is None


# ---------------------------------------------------------------------------
# cross-process trace assembly
# ---------------------------------------------------------------------------

def _payload(pid, proc, spans, epoch_unix, now_unix):
    return {"pid": pid, "process_name": proc, "epoch_unix": epoch_unix,
            "now_unix": now_unix, "spans": spans}


class TestTraceAssembly:
    def _span(self, name, ts, span_id, pid, trace_id="rid-1"):
        return {"name": name, "trace_id": trace_id, "span_id": span_id,
                "parent_id": None, "ts": ts, "dur": 0.01, "tid": 1,
                "pid": pid, "proc": None, "attrs": {}}

    def test_skew_normalization_against_envelope(self):
        """A replica whose wall clock is 100s ahead still lands its
        spans where they belong on the assembler's timeline: the
        send/recv envelope pins the offset."""
        zero = 1000.0
        # assembler's own span at t=+1.0s
        local = _payload(10, "router",
                         [self._span("fleet.request", 1.0, 1, 10)],
                         epoch_unix=zero, now_unix=zero + 2.0)
        # the replica handled the same request ~1.05s in (its clock is
        # +100s skewed); the scrape happened at assembler time 2.0
        SKEW = 100.0
        remote = _payload(20, "replica:r0",
                          [self._span("serving.request", 0.05, 1, 20)],
                          epoch_unix=zero + 1.0 + SKEW,
                          now_unix=zero + 2.0 + SKEW)
        obj = aggregate.assemble_fleet_trace(
            [{"source": "router", "payload": local, "envelope": None},
             {"source": "r0", "payload": remote,
              "envelope": (zero + 1.99, zero + 2.01)}],
            zero_unix=zero)
        evs = [e for e in obj["traceEvents"] if e["ph"] == "X"]
        by_pid = {e["pid"]: e for e in evs}
        assert set(by_pid) == {10, 20}
        # local span at 1.0s; remote at ~1.05s on the SAME clock
        assert by_pid[10]["ts"] == pytest.approx(1.0 * 1e6)
        assert by_pid[20]["ts"] == pytest.approx(1.05 * 1e6, abs=0.1e6)
        offsets = {p["source"]: p["clock_offset_s"]
                   for p in obj["fleetAssembly"]["processes"]}
        assert offsets["r0"] == pytest.approx(SKEW, abs=0.1)
        # one process_name metadata row per pid
        meta = {e["pid"]: e["args"]["name"]
                for e in obj["traceEvents"]
                if e["ph"] == "M" and e["name"] == "process_name"}
        assert meta[20] == "replica:r0" and meta[10] == "router"

    def test_colliding_os_pids_stay_distinct_processes(self):
        """Review regression: containerized replicas all run as pid 1 —
        identity is (pid, process_name), so neither replica's spans are
        dropped and each keeps its own (remapped) timeline row."""
        zero = 0.0
        a = _payload(1, "replica:r0",
                     [self._span("serving.request", 1.0, 1, 1)],
                     zero, zero + 2.0)
        b = _payload(1, "replica:r1",
                     [self._span("serving.request", 1.1, 1, 1)],
                     zero, zero + 2.0)
        obj = aggregate.assemble_fleet_trace(
            [{"source": "r0", "payload": a,
              "envelope": (zero + 1.9, zero + 2.1)},
             {"source": "r1", "payload": b,
              "envelope": (zero + 1.9, zero + 2.1)}],
            zero_unix=zero)
        evs = [e for e in obj["traceEvents"] if e["ph"] == "X"]
        assert len(evs) == 2                    # nothing deduped away
        assert len({e["pid"] for e in evs}) == 2  # two distinct rows
        meta = {e["args"]["name"] for e in obj["traceEvents"]
                if e["ph"] == "M" and e["name"] == "process_name"}
        assert {"replica:r0", "replica:r1"} <= meta
        procs = obj["fleetAssembly"]["processes"]
        assert all(p["os_pid"] == 1 for p in procs)
        assert len({p["pid"] for p in procs}) == 2

    def test_dedupe_and_failures_reported(self):
        zero = 0.0
        spans = [self._span("a", 1.0, 7, 10)]
        p = _payload(10, "proc", spans, zero, zero + 1.5)
        obj = aggregate.assemble_fleet_trace(
            [{"source": "self", "payload": p, "envelope": None},
             # the same ring scraped twice (in-process fleet): deduped
             {"source": "again", "payload": p,
              "envelope": (zero + 1.4, zero + 1.6)},
             {"source": "corpse", "error": "ConnectionError: down"}],
            zero_unix=zero)
        evs = [e for e in obj["traceEvents"] if e["ph"] == "X"]
        assert len(evs) == 1
        assert obj["fleetAssembly"]["failures"] == [
            {"source": "corpse", "error": "ConnectionError: down"}]

    def test_live_servers_spans_endpoint_assembles(self, model_dir):
        """/spans end-to-end: scrape a real server's ring and merge it
        with the local one."""
        server = InferenceServer(model_dir, port=0)
        server.start_background()
        trace.enable(4096)
        try:
            with trace.trace_context("rid-spans-1"), \
                    trace.span("local.mark"):
                pass
            payload, envelope = aggregate.fetch_spans(_addr(server))
            assert payload["pid"] == os.getpid()  # in-process server
            assert envelope[0] <= envelope[1]
            obj = aggregate.assemble_fleet_trace(
                [{"source": "local",
                  "payload": trace.snapshot_payload(),
                  "envelope": None},
                 {"source": _addr(server), "payload": payload,
                  "envelope": envelope}])
            names = {e["name"] for e in obj["traceEvents"]}
            assert "local.mark" in names
        finally:
            server.shutdown()
            trace.disable()
            trace.clear()


# ---------------------------------------------------------------------------
# SLO watchdog
# ---------------------------------------------------------------------------

class TestSLOSpec:
    def test_example_spec_is_valid(self):
        assert slo.validate_spec(slo.EXAMPLE_SPEC) == []

    def test_validator_names_every_problem(self):
        problems = slo.validate_spec({
            "version": 2,
            "sustained_breaches": 0,
            "objectives": [
                {"name": "a", "kind": "quantile", "series": "s",
                 "quantile": "p42", "max": -1},
                {"name": "a", "kind": "error_rate", "ok": [],
                 "errors": ["e"], "max_ratio": 2},
                {"name": "c", "kind": "warp_drive"},
                {"name": "d", "kind": "rate_floor", "counter": "t",
                 "min_rate": 1.0, "surprise": True},
            ]})
        text = "\n".join(problems)
        for needle in ("version", "sustained_breaches", "p42", "max",
                       "duplicate name 'a'", "ok", "max_ratio",
                       "warp_drive", "surprise"):
            assert needle in text, (needle, problems)

    def test_load_spec_raises_with_problem_list(self, tmp_path):
        p = tmp_path / "slo.json"
        p.write_text('{"version": 1, "objectives": "nope"}')
        with pytest.raises(ValueError, match="objectives"):
            slo.load_spec(str(p))
        p.write_text("{not json")
        with pytest.raises(ValueError, match="not JSON"):
            slo.load_spec(str(p))

    def test_watchdog_from_env(self, tmp_path, monkeypatch):
        monkeypatch.delenv(slo.SLO_ENV, raising=False)
        assert slo.watchdog_from_env() is None
        good = tmp_path / "good.json"
        good.write_text(json.dumps(slo.EXAMPLE_SPEC))
        monkeypatch.setenv(slo.SLO_ENV, str(good))
        wd = slo.watchdog_from_env()
        assert wd is not None and len(wd.spec.objectives) == 4
        bad = tmp_path / "bad.json"
        bad.write_text("{}")
        monkeypatch.setenv(slo.SLO_ENV, str(bad))
        with pytest.warns(UserWarning, match="disarmed"):
            assert slo.watchdog_from_env() is None


def _spec(*objectives, sustained=3, interval=0.01):
    return {"version": 1, "interval_seconds": interval,
            "sustained_breaches": sustained,
            "objectives": list(objectives)}


class TestSLOWatchdog:
    def test_quantile_breach_and_recovery(self):
        m = RuntimeMetrics()
        wd = slo.SLOWatchdog(_spec(
            {"name": "lat", "kind": "quantile",
             "series": "serving.request_seconds", "quantile": "p99",
             "max": 0.2}), metrics=m)
        assert wd.evaluate() == []          # no samples: skip, no breach
        for _ in range(10):
            m.observe("serving.request_seconds", 0.5)
        (breach,) = wd.evaluate()
        assert breach["objective"] == "lat"
        assert breach["value"] == pytest.approx(0.5)
        assert breach["threshold"] == 0.2
        assert m.counter("slo.breach") == 1
        assert m.counter("slo.evaluations") == 2
        assert m.gauge("slo.breaching") == 1
        assert wd.breach_log and wd.state()["breaching"] == {"lat": 1}
        # recovery: flood the window with fast samples
        for _ in range(3000):
            m.observe("serving.request_seconds", 0.01)
        assert wd.evaluate() == []
        assert m.gauge("slo.breaching") == 0

    def test_error_rate_uses_counter_deltas(self):
        m = RuntimeMetrics()
        wd = slo.SLOWatchdog(_spec(
            {"name": "err", "kind": "error_rate",
             "ok": ["fleet.requests_ok"], "errors": ["fleet.shed"],
             "max_ratio": 0.1}), metrics=m)
        m.inc("fleet.shed", 100)            # PRE-existing errors
        assert wd.evaluate() == []          # first pass: no window yet
        m.inc("fleet.requests_ok", 99)
        m.inc("fleet.shed", 1)              # 1% this window: fine
        assert wd.evaluate() == []
        m.inc("fleet.requests_ok", 5)
        m.inc("fleet.shed", 5)              # 50% this window: breach
        (breach,) = wd.evaluate()
        assert breach["value"] == pytest.approx(0.5)

    def test_rate_floor_skips_idle_unless_told(self):
        m = RuntimeMetrics()
        wd = slo.SLOWatchdog(_spec(
            {"name": "tok", "kind": "rate_floor",
             "counter": "gen.tokens", "min_rate": 1e9}), metrics=m)
        assert wd.evaluate() == []          # no prev window
        assert wd.evaluate() == []          # idle: skipped by default
        m.inc("gen.tokens", 3)              # active but way under floor
        (breach,) = wd.evaluate()
        assert breach["objective"] == "tok"
        # liveness variant: idle_ok false breaches on silence
        wd2 = slo.SLOWatchdog(_spec(
            {"name": "alive", "kind": "rate_floor",
             "counter": "gen.tokens", "min_rate": 1.0,
             "idle_ok": False}), metrics=m)
        assert wd2.evaluate() == []         # first pass seeds
        time.sleep(0.01)
        (breach,) = wd2.evaluate()
        assert breach["objective"] == "alive"

    def test_sustained_breach_writes_one_postmortem_per_episode(
            self, tmp_path, monkeypatch):
        pm_dir = tmp_path / "pm"
        pm_dir.mkdir()
        monkeypatch.setenv("PADDLE_TPU_POSTMORTEM", str(pm_dir))
        m = RuntimeMetrics()
        wd = slo.SLOWatchdog(_spec(
            {"name": "lat", "kind": "quantile",
             "series": "s", "quantile": "p99", "max": 0.1},
            sustained=2), metrics=m)
        m.observe("s", 1.0)
        wd.evaluate()                       # breach 1: no post-mortem
        assert m.counter("slo.postmortems") == 0
        wd.evaluate()                       # breach 2: SUSTAINED
        assert m.counter("slo.postmortems") == 1
        wd.evaluate()                       # still breaching: no redump
        assert m.counter("slo.postmortems") == 1
        pm_file = pm_dir / f"postmortem-{os.getpid()}.json"
        body = json.loads(pm_file.read_text())
        assert "sustained SLO breach: lat" in body["reason"]
        assert body["extra"]["slo_breach"]["objective"] == "lat"
        assert body["extra"]["spec"]["objectives"]
        # recovery re-arms the episode: a NEW sustained breach redumps
        for _ in range(3000):
            m.observe("s", 0.001)
        assert wd.evaluate() == []
        for _ in range(3000):
            m.observe("s", 1.0)
        wd.evaluate()
        wd.evaluate()
        assert m.counter("slo.postmortems") == 2

    def test_maybe_evaluate_respects_interval(self):
        m = RuntimeMetrics()
        wd = slo.SLOWatchdog(_spec(
            {"name": "lat", "kind": "quantile", "series": "s",
             "quantile": "p99", "max": 1.0}, interval=3600.0),
            metrics=m)
        assert wd.maybe_evaluate() is not None    # first call runs
        assert wd.maybe_evaluate() is None        # not due for an hour
        assert wd.evaluations == 1

    def test_background_thread_evaluates(self):
        m = RuntimeMetrics()
        for _ in range(5):
            m.observe("s", 9.0)
        wd = slo.SLOWatchdog(_spec(
            {"name": "lat", "kind": "quantile", "series": "s",
             "quantile": "p99", "max": 0.1}, interval=0.02),
            metrics=m)
        wd.start(interval=0.02)
        try:
            deadline = time.time() + 5
            while m.counter("slo.breach") < 2 and time.time() < deadline:
                time.sleep(0.02)
            assert m.counter("slo.breach") >= 2
        finally:
            wd.stop()

    def test_gen_scheduler_ticks_armed_watchdog(self, tmp_path,
                                                monkeypatch):
        """The GenScheduler wiring: an armed PADDLE_TPU_SLO is picked
        up at construction and evaluated from the decode loop."""
        from paddle_tpu.gen.scheduler import GenScheduler

        spec = tmp_path / "slo.json"
        spec.write_text(json.dumps(_spec(
            {"name": "lat", "kind": "quantile",
             "series": "gen.ttft_seconds", "quantile": "p99",
             "max": 10.0}, interval=0.001)))
        monkeypatch.setenv(slo.SLO_ENV, str(spec))

        sched = GenScheduler(FakeGenPredictor(), queue_size=2)
        try:
            assert sched.slo_watchdog is not None
            assert sched.slo_watchdog.spec.objectives[0]["name"] == "lat"
        finally:
            sched.close()


# ---------------------------------------------------------------------------
# bench trajectory
# ---------------------------------------------------------------------------

class TestBenchTrajectory:
    def test_record_check_roundtrip_and_degradation(self, tmp_path):
        path = str(tmp_path / "traj.json")
        metrics = {"tokens_per_sec": 200.0, "tokens_per_sec_ratio": 2.5,
                   "ttft_p99_ms": 250.0, "lost_requests": 0}
        bench_history.record("decode", metrics, path=path, baseline=True)
        bench_history.record("decode", dict(metrics), path=path)
        report = bench_history.check(path)
        assert report["ok"], report
        assert report["benches"]["decode"]["comparisons"]
        # a degraded newest run regresses past the band: check fails
        bench_history.record("decode",
                             dict(metrics, tokens_per_sec=50.0),
                             path=path)
        report = bench_history.check(path)
        assert not report["ok"]
        (reg,) = report["benches"]["decode"]["regressions"]
        assert reg["metric"] == "tokens_per_sec"
        # --dry ignores the regression but still gates the schema
        assert bench_history.check(path, dry=True)["ok"]

    def test_baseline_flag_wins_over_first_run(self, tmp_path):
        path = str(tmp_path / "traj.json")
        bench_history.record("decode", {"tokens_per_sec": 500.0},
                             path=path)      # old, unrealistic first run
        bench_history.record("decode", {"tokens_per_sec": 200.0},
                             path=path, baseline=True)
        bench_history.record("decode", {"tokens_per_sec": 190.0},
                             path=path)
        report = bench_history.check(path)
        # vs the FLAGGED baseline (200) this passes; vs the first run
        # (500) it would have failed
        assert report["ok"], report

    def test_schema_gate_catches_malformation(self, tmp_path):
        path = tmp_path / "traj.json"
        path.write_text(json.dumps(
            {"format": 1, "runs": [{"bench": "decode",
                                    "time_unix": "yesterday",
                                    "metrics": {"x": "fast"}}]}))
        report = bench_history.check(str(path))
        assert not report["ok"]
        text = "\n".join(report["problems"])
        assert "time_unix" in text and "'x'" in text
        path.write_text("[1, 2]")
        assert not bench_history.check(str(path), dry=True)["ok"]

    def test_extractions_match_repo_artifacts(self):
        """summary_metrics stays in lockstep with the real bench
        artifacts AND the shipped BENCH_TRAJECTORY.json passes the
        gate — the acceptance criterion's 'exit zero on the real one'."""
        root = os.path.dirname(bench_history.default_path())
        for bench, src in (("serving", "BENCH_SERVING.json"),
                           ("datapipe", "BENCH_DATAPIPE.json"),
                           ("fleet", "BENCH_FLEET.json"),
                           ("decode", "BENCH_DECODE.json")):
            with open(os.path.join(root, src)) as f:
                summary = json.load(f)
            metrics = bench_history.summary_metrics(bench, summary)
            assert metrics and all(
                isinstance(v, (int, float)) for v in metrics.values())
            judged = set(metrics) & set(
                bench_history.BENCH_METRICS[bench])
            assert judged, (bench, metrics)
        report = bench_history.check()       # the shipped trajectory
        assert report["ok"], report

    def test_cli_exit_codes(self, tmp_path, capsys):
        path = str(tmp_path / "traj.json")
        metrics = {"tokens_per_sec": 200.0}
        bench_history.record("decode", metrics, path=path,
                             baseline=True)
        assert cli.main(["bench", "check", "--trajectory", path]) == 0
        bench_history.record("decode", {"tokens_per_sec": 10.0},
                             path=path)
        assert cli.main(["bench", "check", "--trajectory", path]) == 1
        assert cli.main(["bench", "check", "--trajectory", path,
                         "--dry"]) == 0
        out = capsys.readouterr().out
        assert "FAIL" in out
        # record imports an artifact through the shared extractor
        root = os.path.dirname(bench_history.default_path())
        assert cli.main([
            "bench", "record", "--bench", "fleet", "--summary",
            os.path.join(root, "BENCH_FLEET.json"),
            "--trajectory", str(tmp_path / "t2.json"),
            "--baseline"]) == 0
        obj = bench_history.load_trajectory(str(tmp_path / "t2.json"))
        assert obj["runs"][0]["bench"] == "fleet"
        assert obj["runs"][0]["baseline"] is True


class TestFleetStatsCLI:
    def test_fleet_stats_static_replicas(self, model_dir, capsys):
        server = InferenceServer(model_dir, port=0)
        server.start_background()
        try:
            rc = cli.main(["fleet-stats", "--replicas", _addr(server)])
            assert rc == 0
            out = capsys.readouterr().out
            assert_conformant(out)
            assert f'replica="{_addr(server)}"' in out
            rc = cli.main(["fleet-stats", "--replicas", _addr(server),
                           "--json"])
            assert rc == 0
            report = json.loads(capsys.readouterr().out)
            assert report["replicas"][0]["ok"] is True
        finally:
            server.shutdown()

    def test_fleet_stats_needs_a_target(self, capsys):
        assert cli.main(["fleet-stats"]) == 2
