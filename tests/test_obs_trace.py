"""Observability suite: span tracing (nesting, error tagging, ring
bound, context propagation), Chrome trace export, flight-recorder
post-mortems (including the chaos-kill drill), Prometheus exposition,
and the concurrent-writer safety of /stats + /metrics
(docs/observability.md)."""

import json
import os
import re
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as fluid
import paddle_tpu.layers as layers
from paddle_tpu.obs import flight, prom, trace
from paddle_tpu.profiler import RuntimeMetrics, record_latency


@pytest.fixture(autouse=True)
def _fresh_tracer():
    """Each test starts with tracing on and an empty ring, and leaves
    the process with tracing off (the import-time default)."""
    trace.enable(trace.DEFAULT_RING)
    trace.clear()
    yield
    trace.clear()
    trace.disable()


# ---------------------------------------------------------------------------
# span primitives
# ---------------------------------------------------------------------------

class TestSpans:
    def test_nesting_parent_child(self):
        with trace.span("outer"):
            with trace.span("inner"):
                pass
        spans = {s["name"]: s for s in trace.snapshot_spans()}
        assert spans["inner"]["parent_id"] == spans["outer"]["span_id"]
        assert spans["inner"]["trace_id"] == spans["outer"]["trace_id"]
        # child interval nests inside the parent's
        assert spans["inner"]["ts"] >= spans["outer"]["ts"]
        assert (spans["inner"]["ts"] + spans["inner"]["dur"] <=
                spans["outer"]["ts"] + spans["outer"]["dur"] + 1e-9)

    def test_disabled_records_nothing_and_is_noop_object(self):
        trace.disable()
        sp = trace.span("x", a=1)
        assert sp is trace.span("y")      # one shared no-op object
        with sp:
            sp.set(b=2)
        trace.record_span("z", 0.0, 1.0)
        assert trace.snapshot_spans() == []

    def test_error_tagging_does_not_swallow(self):
        with pytest.raises(ValueError, match="boom"):
            with trace.span("failing"):
                raise ValueError("boom")
        (sp,) = trace.snapshot_spans()
        assert sp["attrs"]["error"] is True
        assert sp["attrs"]["error_type"] == "ValueError"
        assert sp["dur"] >= 0

    def test_ring_is_bounded(self):
        trace.enable(ring_size=16)
        for i in range(100):
            with trace.span("s", i=i):
                pass
        spans = trace.snapshot_spans()
        assert len(spans) == 16
        assert spans[-1]["attrs"]["i"] == 99   # newest kept, oldest gone
        trace.enable(trace.DEFAULT_RING)

    def test_trace_context_binds_ambient_id(self):
        with trace.trace_context("req-42"):
            assert trace.current_trace_id() == "req-42"
            with trace.span("inside"):
                pass
        assert trace.current_trace_id() is None
        (sp,) = trace.snapshot_spans()
        assert sp["trace_id"] == "req-42"

    def test_record_span_cross_thread_stitching(self):
        t0 = time.perf_counter()
        trace.record_span("queue_wait", t0, 0.005, trace_id="req-7",
                          rows=3)
        (sp,) = trace.snapshot_spans()
        assert sp["trace_id"] == "req-7" and sp["attrs"]["rows"] == 3
        assert sp["dur"] == pytest.approx(0.005)

    def test_record_span_without_context_has_no_trace_id(self):
        # hot-path contract: no ambient context means NO id is minted
        # (a fresh id per datapipe pull would cost a syscall per sample
        # and correlate nothing)
        trace.record_span("pull", time.perf_counter(), 0.001)
        (sp,) = trace.snapshot_spans()
        assert sp["trace_id"] is None
        (ev,) = [e for e in trace.chrome_trace()["traceEvents"]
                 if e["ph"] == "X"]
        assert "trace_id" not in ev["args"]

    def test_env_grammar(self, monkeypatch):
        assert trace.configure_from_env("0") is False
        assert not trace.enabled()
        assert trace.configure_from_env("1") is True
        assert trace.enabled()
        trace.configure_from_env("128")
        for i in range(200):
            with trace.span("s"):
                pass
        assert len(trace.snapshot_spans()) == 128
        # a malformed knob warns and disables — it must never be able
        # to veto `import paddle_tpu` (this parser runs at import)
        with pytest.warns(UserWarning, match="PADDLE_TPU_TRACE"):
            assert trace.configure_from_env("sideways") is False
        assert not trace.enabled()
        trace.enable(trace.DEFAULT_RING)


class TestChromeExport:
    def test_roundtrips_and_nests(self):
        with trace.span("parent", step=1):
            with trace.span("child"):
                time.sleep(0.002)
        body = trace.dump_chrome_trace()
        obj = json.loads(body)              # valid JSON round-trip
        assert obj["displayTimeUnit"] == "ms"
        evs = {e["name"]: e for e in obj["traceEvents"]
               if e["ph"] == "X"}
        for e in evs.values():
            assert e["ph"] == "X" and e["pid"] == os.getpid()
            assert isinstance(e["ts"], float) and e["dur"] >= 0
        child, parent = evs["child"], evs["parent"]
        assert parent["ts"] <= child["ts"]
        assert child["ts"] + child["dur"] <= \
            parent["ts"] + parent["dur"] + 1e-3
        assert child["args"]["parent_id"] == parent["args"]["span_id"]
        assert parent["args"]["step"] == 1

    def test_dump_to_file_is_loadable(self, tmp_path):
        with trace.span("s"):
            pass
        p = tmp_path / "trace.json"
        assert trace.dump_chrome_trace(str(p)) == str(p)
        with open(p) as f:
            obj = json.load(f)
        assert len([e for e in obj["traceEvents"]
                    if e["ph"] == "X"]) == 1

    def test_per_process_pid_and_process_name_metadata(self):
        """Satellite regression: chrome_trace honors each span's OWN
        pid (not a constant) and emits one process_name metadata event
        per distinct pid — merging two processes' span lists must
        produce two labelled timeline rows, not one interleaved row."""
        with trace.span("local.work"):
            pass
        ours = trace.snapshot_spans()
        assert all(s["pid"] == os.getpid() for s in ours)
        # a second process's snapshot, as its /spans scrape would carry
        theirs = [dict(s, pid=os.getpid() + 1, proc="replica:r9",
                       name="remote.work") for s in ours]
        obj = trace.chrome_trace(ours + theirs)
        complete = [e for e in obj["traceEvents"] if e["ph"] == "X"]
        meta = [e for e in obj["traceEvents"]
                if e["ph"] == "M" and e["name"] == "process_name"]
        assert {e["pid"] for e in complete} == \
            {os.getpid(), os.getpid() + 1}
        by_pid = {e["pid"]: e["args"]["name"] for e in meta}
        assert by_pid[os.getpid() + 1] == "replica:r9"
        assert by_pid[os.getpid()]  # the local row is labelled too
        # the two processes' spans landed on different rows
        local = next(e for e in complete if e["name"] == "local.work")
        remote = next(e for e in complete if e["name"] == "remote.work")
        assert local["pid"] != remote["pid"]

    def test_snapshot_payload_carries_clock_anchors(self):
        with trace.span("s"):
            pass
        payload = trace.snapshot_payload()
        assert payload["pid"] == os.getpid()
        assert payload["spans"]
        # epoch_unix + ts ~= the span's absolute wall time, and now_unix
        # sits at/after it (same process, same clock)
        sp = payload["spans"][-1]
        abs_t = payload["epoch_unix"] + sp["ts"]
        assert abs_t == pytest.approx(time.time(), abs=5.0)
        assert payload["now_unix"] >= abs_t - 1e-3


# ---------------------------------------------------------------------------
# satellite regressions: percentiles() on empty series, record_latency
# error attribution
# ---------------------------------------------------------------------------

class TestMetricsRegressions:
    def test_percentiles_unknown_series_returns_none(self):
        m = RuntimeMetrics()
        assert m.percentiles("never.observed") == \
            {"p50": None, "p95": None, "p99": None}

    def test_percentiles_after_reset_returns_none(self):
        m = RuntimeMetrics()
        m.observe("x", 1.0)
        m.reset()
        assert m.percentiles("x") == \
            {"p50": None, "p95": None, "p99": None}
        # snapshot of an empty registry is fine too
        assert m.snapshot()["series"] == {}

    @pytest.mark.parametrize("last,want", [
        (None, [1.0, 2.0, 3.0]), (2, [2.0, 3.0]), (0, []), (9, [1.0, 2.0, 3.0])])
    def test_samples_are_the_newest_kept_oldest_first(self, last, want):
        m = RuntimeMetrics()
        for v in (1.0, 2.0, 3.0):
            m.observe("x", v)
        assert m.samples("x", last=last) == want
        assert m.samples("never.observed", last=last) == []

    def test_record_latency_exception_path_observed_and_tagged(self):
        m = RuntimeMetrics()
        with pytest.raises(RuntimeError, match="kapow"):
            with record_latency("op.seconds", metrics=m):
                time.sleep(0.002)
                raise RuntimeError("kapow")
        # the failed body's time is NOT swallowed...
        snap = m.snapshot()["series"]["op.seconds"]
        assert snap["count"] == 1 and snap["total"] >= 0.002
        # ...and the failure is attributed to the same series
        assert m.counter("op.seconds.errors") == 1

    def test_record_latency_success_has_no_error_counter(self):
        m = RuntimeMetrics()
        with record_latency("op.seconds", metrics=m):
            pass
        assert m.counter("op.seconds.errors") == 0
        assert m.snapshot()["series"]["op.seconds"]["count"] == 1


# ---------------------------------------------------------------------------
# prometheus exposition
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? (NaN|[+-]?Inf|[-+0-9.eE]+)$")


def assert_valid_exposition(text):
    """Minimal v0.0.4 validator: every line is a comment or a sample;
    every sample's base name was TYPE-declared first."""
    declared = set()
    seen_any = False
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# TYPE "):
            declared.add(line.split()[2])
            continue
        if line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"bad exposition line: {line!r}"
        base = line.split("{")[0].split(" ")[0]
        root = re.sub(r"_(total|sum|count|bucket)$", "", base)
        assert base in declared or root in declared, \
            f"sample {base!r} has no TYPE declaration"
        seen_any = True
    assert text.endswith("\n")
    return seen_any


class TestPrometheus:
    def _registry(self):
        m = RuntimeMetrics()
        m.inc("serving.requests_ok", 5)
        m.observe("serving.request_seconds", 0.25)
        m.observe("serving.request_seconds", 0.75)
        m.bucket("serving.batch_occupancy", 1)
        m.bucket("serving.batch_occupancy", 4)
        m.bucket("serving.batch_occupancy", 4)
        m.set_gauge("datapipe.prefetch.queue_depth", 2)
        return m

    def test_renders_all_kinds_validly(self):
        text = prom.render_prometheus(self._registry().snapshot())
        assert assert_valid_exposition(text)
        assert "paddle_tpu_serving_requests_ok_total 5" in text
        assert 'paddle_tpu_serving_request_seconds{quantile="0.5"}' in text
        assert "paddle_tpu_serving_request_seconds_count 2" in text
        # histogram buckets are cumulative, +Inf closes the family
        assert 'paddle_tpu_serving_batch_occupancy_bucket{le="1"} 1' \
            in text
        assert 'paddle_tpu_serving_batch_occupancy_bucket{le="4"} 3' \
            in text
        assert 'paddle_tpu_serving_batch_occupancy_bucket{le="+Inf"} 3' \
            in text
        assert "paddle_tpu_datapipe_prefetch_queue_depth 2" in text

    def test_empty_registry_renders(self):
        assert prom.render_prometheus(RuntimeMetrics().snapshot()) == "\n"

    def test_name_sanitization(self):
        assert prom.sanitize_name("a.b-c/d") == "paddle_tpu_a_b_c_d"


class TestConcurrentSnapshots:
    """Satellite: /stats + /metrics under concurrent writers — hammer
    the registry from threads while snapshotting; every snapshot must
    be valid JSON and valid exposition."""

    def test_hammered_registry_snapshots_stay_valid(self):
        m = RuntimeMetrics()
        stop = threading.Event()
        errors = []

        def writer(i):
            n = 0
            try:
                while not stop.is_set():
                    m.inc(f"c.{i % 3}")
                    m.observe(f"s.{i % 3}", n * 0.001)
                    m.bucket("h.occupancy", n % 8)
                    m.set_gauge(f"g.{i % 2}", n)
                    n += 1
            except Exception as e:  # pragma: no cover - the assertion
                errors.append(e)

        threads = [threading.Thread(target=writer, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        try:
            deadline = time.monotonic() + 1.0
            snaps = 0
            while time.monotonic() < deadline:
                snap = m.snapshot()
                json.loads(json.dumps(snap))          # valid JSON
                assert_valid_exposition(
                    prom.render_prometheus(snap))     # valid exposition
                for q, v in m.percentiles("s.0").items():
                    assert v is None or v >= 0
                snaps += 1
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=5)
        assert not errors
        assert snaps > 5


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

class TestFlightRecorder:
    def test_unarmed_is_noop(self, monkeypatch):
        monkeypatch.delenv(flight.POSTMORTEM_ENV, raising=False)
        assert flight.write_postmortem(reason="x") is None

    def test_write_and_read_roundtrip(self, tmp_path):
        with trace.span("final.step", step=7):
            pass
        target = tmp_path / "pm.json"
        got = flight.write_postmortem(path=str(target), reason="test")
        assert got == str(target)
        body = flight.read_postmortem(got)
        assert body["reason"] == "test" and body["pid"] == os.getpid()
        assert body["spans"][-1]["name"] == "final.step"
        assert "counters" in body["metrics"]
        # atomic: no tmp leftovers
        assert [p.name for p in tmp_path.iterdir()] == ["pm.json"]

    def test_concurrent_dumps_never_tear(self, tmp_path):
        """Regression: a graceful shutdown dumps twice concurrently
        (async handler thread + __exit__ backstop); two writers sharing
        one tmp inode used to interleave into torn JSON ("Extra data").
        Whatever interleaving happens, the file must parse whole."""
        target = tmp_path / "pm.json"
        barrier = threading.Barrier(4)

        def dump():
            barrier.wait()
            for _ in range(10):
                flight.write_postmortem(path=str(target),
                                        reason="concurrent")

        threads = [threading.Thread(target=dump) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        body = flight.read_postmortem(str(target))
        assert body["reason"] == "concurrent"
        # every writer renamed its own tmp: no leftovers, no torn file
        assert [p.name for p in tmp_path.iterdir()] == ["pm.json"]

    def test_env_dir_maps_to_pid_file(self, tmp_path, monkeypatch):
        monkeypatch.setenv(flight.POSTMORTEM_ENV, str(tmp_path))
        got = flight.write_postmortem(reason="dir")
        assert got == str(tmp_path / f"postmortem-{os.getpid()}.json")

    def test_graceful_shutdown_dumps_postmortem(self, tmp_path,
                                                monkeypatch):
        from paddle_tpu.fault import GracefulShutdown
        target = tmp_path / "shutdown.json"
        monkeypatch.setenv(flight.POSTMORTEM_ENV, str(target))
        # the in-handler dump is ASYNC (a signal handler must not take
        # the metrics lock the interrupted frame may hold); __exit__ is
        # the deterministic backstop
        with GracefulShutdown() as stop:
            stop.request(15)
        body = flight.read_postmortem(str(target))
        assert "graceful shutdown" in body["reason"]

    def test_shutdown_request_does_not_block_on_metrics_lock(
            self, tmp_path, monkeypatch):
        """Regression for the handler-deadlock hazard: request() must
        return promptly even while another frame holds the registry
        lock (the situation a mid-observe SIGTERM creates)."""
        from paddle_tpu.fault import GracefulShutdown
        from paddle_tpu.profiler import runtime_metrics
        monkeypatch.setenv(flight.POSTMORTEM_ENV,
                           str(tmp_path / "pm.json"))
        stop = GracefulShutdown()
        with runtime_metrics._lock:       # simulate interrupted observe()
            t0 = time.monotonic()
            stop.request(15)              # must not dump synchronously
            assert time.monotonic() - t0 < 1.0
        # lock released: the async dump completes
        deadline = time.monotonic() + 5.0
        while not (tmp_path / "pm.json").exists():
            assert time.monotonic() < deadline, "async dump never landed"
            time.sleep(0.01)


# ---------------------------------------------------------------------------
# executor + pipeline span integration
# ---------------------------------------------------------------------------

class TestExecutorSpans:
    def test_run_phases_nest_under_run(self):
        x = layers.data(name="x", shape=[4, 8], append_batch_size=False)
        pred = layers.fc(input=x, size=2)
        exe = fluid.Executor()
        exe.run(fluid.default_startup_program())
        trace.clear()
        exe.run(fluid.default_main_program(),
                feed={"x": np.zeros((4, 8), "float32")},
                fetch_list=[pred])
        spans = {s["name"]: s for s in trace.snapshot_spans()}
        run = spans["executor.run"]
        for phase in ("executor.feed", "executor.dispatch",
                      "executor.fetch"):
            assert spans[phase]["parent_id"] == run["span_id"]
            assert spans[phase]["trace_id"] == run["trace_id"]

    def test_run_pipeline_step_timeline(self):
        import paddle_tpu.datapipe as dp
        x = layers.data(name="x", shape=[4, 6], append_batch_size=False)
        pred = layers.fc(input=x, size=1)
        exe = fluid.Executor()
        exe.run(fluid.default_startup_program())
        samples = [{"x": np.full((6,), i, "float32")} for i in range(8)]
        pipe = dp.InMemorySource(samples).batch(4)
        trace.clear()
        outs = exe.run_pipeline(fluid.default_main_program(),
                                pipeline=pipe, fetch_list=[pred])
        assert len(outs) == 2
        spans = trace.snapshot_spans()
        steps = [s for s in spans if s["name"] == "train.step"]
        assert [s["attrs"]["step"] for s in steps] == [0, 1]
        # each step's executor phases join the step's trace
        for s in steps:
            children = [c for c in spans
                        if c["trace_id"] == s["trace_id"]
                        and c["name"].startswith("executor.")]
            assert {"executor.run", "executor.feed", "executor.dispatch",
                    "executor.fetch"} <= {c["name"] for c in children}
        assert any(s["name"] == "datapipe.next" for s in spans)
        assert any(s["name"] == "datapipe.batch.pull" for s in spans)


# ---------------------------------------------------------------------------
# serving endpoints: /trace, /metrics, X-Request-Id
# ---------------------------------------------------------------------------

@pytest.fixture()
def model_dir(tmp_path):
    x = layers.data(name="x", shape=[8, 4], append_batch_size=False)
    pred = layers.fc(input=x, size=1)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    d = str(tmp_path / "model")
    fluid.io.save_inference_model(d, ["x"], [pred], exe)
    return d


class TestServingObservability:
    def _post(self, host, port, path, payload, headers=None):
        req = urllib.request.Request(
            f"http://{host}:{port}{path}",
            data=json.dumps(payload).encode(),
            headers=dict({"Content-Type": "application/json"},
                         **(headers or {})))
        return urllib.request.urlopen(req, timeout=30)

    def test_request_id_trace_and_metrics(self, model_dir):
        from paddle_tpu.serving import InferenceServer
        server = InferenceServer(model_dir, port=0, batching=True)
        server.start_background()
        try:
            host, port = server.addr
            feed = {"feeds": {"x": np.zeros((8, 4)).tolist()}}
            # caller-supplied request id is echoed
            r = self._post(host, port, "/predict", feed,
                           {"X-Request-Id": "rid-echo-1"})
            assert r.headers.get("X-Request-Id") == "rid-echo-1"
            # absent request id: one is generated and echoed
            r = self._post(host, port, "/predict", feed)
            generated = r.headers.get("X-Request-Id")
            assert generated

            # /trace: Perfetto-loadable, request lifecycle stitched to
            # the request ids across handler + batcher threads
            with urllib.request.urlopen(
                    f"http://{host}:{port}/trace", timeout=30) as resp:
                obj = json.loads(resp.read())
            evs = obj["traceEvents"]
            by_trace = {}
            for e in evs:
                by_trace.setdefault(e["args"].get("trace_id"),
                                    set()).add(e["name"])
            for rid in ("rid-echo-1", generated):
                assert {"serving.request", "serving.queue_wait",
                        "serving.dispatch", "serving.scatter",
                        "executor.run"} <= by_trace[rid], rid
            # spans nest: executor.run sits inside serving.dispatch
            for rid in ("rid-echo-1",):
                tr = [e for e in evs if e["args"].get("trace_id") == rid]
                disp = next(e for e in tr
                            if e["name"] == "serving.dispatch")
                erun = next(e for e in tr if e["name"] == "executor.run")
                assert disp["ts"] <= erun["ts"] and \
                    erun["ts"] + erun["dur"] <= \
                    disp["ts"] + disp["dur"] + 1e3

            # /metrics: valid exposition with serving counters
            with urllib.request.urlopen(
                    f"http://{host}:{port}/metrics", timeout=30) as resp:
                assert resp.headers["Content-Type"].startswith(
                    "text/plain")
                text = resp.read().decode()
            assert assert_valid_exposition(text)
            assert "paddle_tpu_serving_requests_ok_total" in text
        finally:
            server.shutdown()


# ---------------------------------------------------------------------------
# master RPC trace propagation
# ---------------------------------------------------------------------------

class TestMasterTracePropagation:
    def test_rpc_carries_callers_trace_id(self):
        from paddle_tpu.parallel.master import (MasterClient, MasterServer,
                                                MasterService,
                                                partition_files)
        svc = MasterService(partition_files(["a"]), timeout=60)
        server = MasterServer(svc, port=0)
        server.start_background()
        try:
            client = MasterClient(f"{server.addr[0]}:{server.addr[1]}")
            with trace.trace_context("trainer-trace-1"):
                assert client.get_task() is not None
            client.close()
        finally:
            server.shutdown()
        spans = trace.snapshot_spans()
        rpc = [s for s in spans if s["name"] == "master.rpc"]
        serve = [s for s in spans if s["name"] == "master.serve"]
        assert rpc and serve
        assert rpc[-1]["trace_id"] == "trainer-trace-1"
        assert serve[-1]["trace_id"] == "trainer-trace-1"
        assert serve[-1]["attrs"]["method"] == "get_task"


# ---------------------------------------------------------------------------
# CLI smoke: `paddle_tpu trace dump`, `paddle_tpu stats --prom`
# ---------------------------------------------------------------------------

class TestCLI:
    def test_trace_dump_local(self, capsys, tmp_path):
        from paddle_tpu import cli
        with trace.span("cli.smoke"):
            pass
        assert cli.main(["trace", "dump", "--local"]) == 0
        obj = json.loads(capsys.readouterr().out)
        assert any(e["name"] == "cli.smoke" for e in obj["traceEvents"])
        out = tmp_path / "t.json"
        assert cli.main(["trace", "dump", "--output", str(out)]) == 0
        with open(out) as f:
            json.load(f)

    def test_stats_prom_local(self, capsys):
        from paddle_tpu import cli
        from paddle_tpu.profiler import runtime_metrics
        runtime_metrics.inc("jit_cache.hits", 0)  # ensure non-empty
        assert cli.main(["stats", "--prom", "--local"]) == 0
        text = capsys.readouterr().out
        assert_valid_exposition(text)


# ---------------------------------------------------------------------------
# chaos-kill post-mortem drill (acceptance criterion)
# ---------------------------------------------------------------------------

KILLED_TRAINER = r'''
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import paddle_tpu as fluid
from paddle_tpu import layers
import paddle_tpu.datapipe as dp

main, startup = fluid.Program(), fluid.Program()
with fluid.program_guard(main, startup):
    x = layers.data("x", shape=[6], dtype="float32")
    y = layers.data("y", shape=[1], dtype="float32")
    pred = layers.fc(x, 1)
    loss = layers.reduce_mean(layers.square_error_cost(pred, y))
    fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)

exe = fluid.Executor()
exe.run(startup)
rng = np.random.RandomState(0)
samples = [{"x": rng.rand(6).astype("float32"),
            "y": rng.rand(1).astype("float32")} for _ in range(64)]
pipe = dp.InMemorySource(samples).batch(4)
exe.run_pipeline(main, pipeline=pipe, fetch_list=[loss.name])
print("survived")  # must not be reached: chaos kills at step 3
'''


@pytest.mark.chaos
class TestChaosKillPostmortem:
    def test_killed_run_leaves_phase_timeline(self, tmp_path):
        from paddle_tpu.fault import chaos
        repo_root = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        script = tmp_path / "trainer.py"
        script.write_text(KILLED_TRAINER)
        pm = tmp_path / "postmortem.json"
        env = dict(os.environ)
        env["PYTHONPATH"] = repo_root + os.pathsep + env.get(
            "PYTHONPATH", "")
        env["JAX_PLATFORMS"] = "cpu"
        env["PADDLE_TPU_TRACE"] = "1"
        env["PADDLE_TPU_POSTMORTEM"] = str(pm)
        env["PADDLE_TPU_CHAOS"] = "train.step=kill@3"
        r = subprocess.run([sys.executable, str(script)], cwd=repo_root,
                           env=env, capture_output=True, text=True,
                           timeout=300)
        assert r.returncode == chaos.KILL_EXIT_CODE, r.stderr[-2000:]
        assert "survived" not in r.stdout

        body = flight.read_postmortem(str(pm))
        assert "chaos kill" in body["reason"]
        assert body["extra"]["failpoint"] == "train.step"
        spans = body["spans"]
        # the final COMPLETED step (index 2: fires 1..3, killed on the
        # 4th) left its full phase timeline in the ring
        steps = [s for s in spans if s["name"] == "train.step"]
        assert [s["attrs"]["step"] for s in steps] == [0, 1, 2]
        last = steps[-1]
        phases = {s["name"] for s in spans
                  if s["trace_id"] == last["trace_id"]}
        assert {"executor.run", "executor.feed", "executor.dispatch",
                "executor.fetch"} <= phases
        assert any(s["name"] == "datapipe.batch.pull" for s in spans)
        # metrics snapshot rode along
        assert body["metrics"]["series"]["executor.step_seconds"][
            "count"] >= 3
