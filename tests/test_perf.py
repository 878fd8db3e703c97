"""Device-performance observability (paddle_tpu.obs.perf): compile
cost/memory capture, the live MFU gauge, the HBM census, the headroom
check, warmup reports, the `paddle_tpu profile` CLI family, and the
bench-trajectory mfu_basis / measured-MFU guard rows."""

import json

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.obs import perf
from paddle_tpu.profiler import runtime_metrics


def _build_fc_train(size=8, act=None):
    """Tiny fc+Adam train program in fresh Program objects."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        y = fluid.layers.fc(input=x, size=size, act=act)
        loss = fluid.layers.mean(y)
        fluid.optimizer.Adam(learning_rate=0.1).minimize(loss)
    return main, startup, loss.name


def _run_fresh(main, startup, fetch, feed=None, runs=1):
    """Run startup + `runs` steps in a fresh scope/executor; returns
    the records captured DURING the call."""
    before = {r["key"] for r in perf.records()}
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        feed = feed or {"x": np.ones((2, 4), np.float32)}
        for _ in range(runs):
            exe.run(main, feed=feed, fetch_list=[fetch], scope=scope)
    return scope, [r for r in perf.records() if r["key"] not in before]


class TestCompileCapture:
    def test_record_fields_and_live_mfu_gauge(self):
        main, startup, loss = _build_fc_train()
        _scope, recs = _run_fresh(main, startup, loss, runs=2)
        # startup + train step both compiled; the train step has feeds
        step = [r for r in recs if "x:2x4" in r["label"]]
        assert step, [r["label"] for r in recs]
        r = step[-1]
        assert r["flops"] and r["flops"] > 0
        assert r["bytes_accessed"] and r["bytes_accessed"] > 0
        for k in perf.MEMORY_KEYS:
            assert isinstance(r["memory"][k], int)
        for k in perf.PHASE_KEYS:
            assert r["phases"][k] >= 0
        # two runs noted against the record; the gauge carries the last
        assert r["steps"] == 2
        assert r["mfu"] is not None and r["mfu"] > 0
        assert runtime_metrics.gauge("train.mfu") == pytest.approx(
            r["mfu"])
        assert runtime_metrics.counter("compile.captures") >= 2

    def test_decode_programs_update_their_own_gauge(self):
        """A program tagged _mfu_gauge (the GenPredictor decode program)
        lands its MFU in gen.decode_mfu, not train.mfu."""
        main, startup, loss = _build_fc_train(size=16)
        main._mfu_gauge = "gen.decode_mfu"
        before = runtime_metrics.gauge("gen.decode_mfu")
        _run_fresh(main, startup, loss)
        after = runtime_metrics.gauge("gen.decode_mfu")
        assert after is not None and after != before

    def test_untagged_inference_programs_derive_no_gauge(self):
        """A serving Predictor / prefill dispatch must not overwrite
        train.mfu (or mask gen.decode_mfu) — only tagged programs and
        training programs feed the fleet-rollup gauges."""
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            y = fluid.layers.fc(input=x, size=8)
        main._is_inference = True
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup)
            runtime_metrics.set_gauge("train.mfu", -3.0)
            exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
                    fetch_list=[y.name], scope=scope)
        assert runtime_metrics.gauge("train.mfu") == -3.0

    def test_async_paths_derive_no_gauge(self):
        """return_numpy=False hands back async device arrays — submit
        time would overstate MFU by the async-dispatch factor, so
        neither run() nor run_steps derives a gauge from it."""
        main, startup, loss = _build_fc_train(size=12)
        scope = fluid.Scope()
        feed = {"x": np.ones((2, 4), np.float32)}
        with fluid.scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup)
            runtime_metrics.set_gauge("train.mfu", -1.0)
            exe.run(main, feed=feed, fetch_list=[loss], scope=scope,
                    return_numpy=False)
            exe.run_steps(main, feed=feed, fetch_list=[loss], steps=2,
                          scope=scope, return_numpy=False)
            assert runtime_metrics.gauge("train.mfu") == -1.0
            exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
            assert runtime_metrics.gauge("train.mfu") > 0

    def test_note_step_scales_scan_flops(self):
        """run_steps: XLA counts the scan body once, so the MFU of an
        N-step window scales the recorded FLOPs by N."""
        rec = {"flops": 1e9, "steps": 0, "last_step_seconds": None,
               "mfu": None}
        m1 = perf.note_step(dict(rec), 1.0)
        m4 = perf.note_step(dict(rec), 1.0, flops_scale=4)
        assert m4 == pytest.approx(4 * m1)

    def test_report_schema(self):
        report = perf.compile_report()
        assert perf.validate_report(report) == []
        assert report["records"]  # earlier tests compiled something
        # and the validator actually rejects drift
        bad = dict(report, mfu_basis="gpu-peak")
        assert perf.validate_report(bad)
        bad2 = json.loads(json.dumps(report))
        del bad2["records"][0]["phases"]["trace_seconds"]
        assert perf.validate_report(bad2)

    def test_capture_disabled_env(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_PERF", "0")
        main, startup, loss = _build_fc_train(size=32)
        _scope, recs = _run_fresh(main, startup, loss)
        assert recs == []  # plain jit path, still correct, no records


class TestAnalyticalFlopsCrossCheck:
    """Satellite: bench.py's analytical FLOPs accounting vs the XLA
    cost_analysis FLOPs of the same compiled program, within DECLARED
    bands — silent drift in the hand accounting (the basis of every
    recorded MFU) fails here.

    Two levels, one band: the forward-only program and the full train
    step both map onto the 2N / 6N-matmul + attention accounting
    (measured 1.13 for the full step on the CPU backend; XLA also counts
    the softmax/layernorm/Adam elementwise work the hand formula
    leaves out).  Until PR 21 the full-step band was [0.35, 0.80]
    "around the measured anchor 0.55": append_backward dropped every
    gradient at the first ``dropout`` op, so the step skipped most of
    its backward pass and the band had been fitted to the bug."""

    FWD_BAND = (0.85, 1.30)
    FULL_BAND = (0.85, 1.30)

    @pytest.fixture(scope="class")
    def hp(self):
        from paddle_tpu.models import transformer as T
        hp = T.ModelHyperParams()
        hp.d_model, hp.d_inner_hid, hp.n_layer = 64, 128, 2
        hp.n_head, hp.d_key, hp.d_value = 4, 16, 16
        hp.src_vocab_size = hp.trg_vocab_size = 1000
        return hp

    def _measured_flops(self, hp, backward):
        from paddle_tpu.models import transformer as T
        batch, seq = 4, 32
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            cost, _ = T.transformer(batch, seq, seq, hp)
            if backward:
                fluid.optimizer.Adam(learning_rate=1e-4).minimize(cost)
        before = {r["key"] for r in perf.records()}
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup)
            feed = T.fake_batch(batch, seq, seq, hp, seed=0)
            exe.run(main, feed=feed, fetch_list=[cost.name], scope=scope)
        recs = [r for r in perf.records()
                if r["key"] not in before and r["flops"]]
        assert recs, "no cost record captured for the transformer step"
        return max(r["flops"] for r in recs)

    def test_forward_accounting_agrees_tightly(self, hp):
        from paddle_tpu.models import transformer as T
        tokens = 4 * 32
        # fwd = 2N of the 6N total; attention fwd = 4 of the 12 S*d
        analytical_fwd = T.train_flops_per_token(hp, 32) * tokens / 3
        measured = self._measured_flops(hp, backward=False)
        ratio = measured / analytical_fwd
        lo, hi = self.FWD_BAND
        assert lo <= ratio <= hi, (
            f"forward-only XLA/analytical FLOPs ratio {ratio:.3f} left "
            f"the declared band [{lo}, {hi}] — the hand accounting "
            f"bench.py derives MFU from has drifted")

    def test_train_step_accounting_within_declared_band(self, hp):
        from paddle_tpu.models import transformer as T
        tokens = 4 * 32
        analytical = T.train_flops_per_token(hp, 32) * tokens
        measured = self._measured_flops(hp, backward=True)
        ratio = measured / analytical
        lo, hi = self.FULL_BAND
        assert lo <= ratio <= hi, (
            f"train-step XLA/analytical FLOPs ratio {ratio:.3f} left "
            f"the declared band [{lo}, {hi}]")


class TestStaticCostModelCrossCheck:
    """ISSUE-15: the static per-op cost model (`analysis/cost`) pinned
    against XLA `cost_analysis()` zoo-wide, so all THREE accountings —
    the bench formula (tested above), the cost rules, and XLA — stay
    mutually anchored.  Measured static/XLA ratios on this backend:
    mnist 1.01, resnet 1.46, vgg 1.25, transformer 0.74, gen_lm 0.88
    (XLA undercounts fused backward convs; the static model undercounts
    unknown-shape LoD chains) — the declared band catches ~2x drift of
    either accounting on any model.  seq2seq/stacked_lstm run in
    op-by-op interpret mode (no compiled executable, no XLA record) and
    are covered by the estimate-level assertions in test_cost.py."""

    BAND = (0.5, 1.75)

    @pytest.mark.parametrize("name", [
        "mnist", "transformer", "gen_lm",
        pytest.param("resnet", marks=pytest.mark.slow),
        pytest.param("vgg", marks=pytest.mark.slow),
    ])
    def test_static_flops_within_declared_band_of_xla(self, name):
        from paddle_tpu.analysis import cost
        from paddle_tpu.models import build_train_program, synth_feed

        main, startup, feeds, fetches = build_train_program(name)
        static = cost.estimate(main).total_flops
        assert static > 0
        before = {r["key"] for r in perf.records()}
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup)
            exe.run(main, feed=synth_feed(main, feeds),
                    fetch_list=fetches, scope=scope)
        recs = [r for r in perf.records()
                if r["key"] not in before and r["flops"]]
        assert recs, f"{name}: no XLA cost record captured"
        xla = max(r["flops"] for r in recs)
        ratio = static / xla
        lo, hi = self.BAND
        assert lo <= ratio <= hi, (
            f"{name}: static-cost/XLA FLOPs ratio {ratio:.3f} left the "
            f"declared band [{lo}, {hi}] — a cost rule (or XLA's "
            f"accounting) drifted")


class TestHbmCensus:
    def test_scope_attribution_and_watermark(self):
        main, startup, loss = _build_fc_train(size=24)
        scope, _ = _run_fresh(main, startup, loss)
        census = perf.hbm_census(scope)
        # Adam state (moments + pow accumulators) vs params split by
        # the accumulator naming convention
        assert census["params"] > 0
        assert census["optimizer"] > 0
        assert census["total"] >= census["params"] + census["optimizer"]
        assert census["high_watermark"] >= census["total"]
        for g in ("hbm.params_bytes", "hbm.optimizer_bytes",
                  "hbm.total_bytes", "hbm.high_watermark_bytes"):
            assert runtime_metrics.gauge(g) is not None

    def test_provider_collection(self):
        import jax.numpy as jnp
        pool = jnp.zeros((4, 16))
        token = perf.register_hbm_provider("kv_pages", lambda: [pool])
        try:
            census = perf.hbm_census(fluid.Scope())
            assert census["kv_pages"] >= pool.nbytes
        finally:
            perf.unregister_hbm_provider(token)
        census = perf.hbm_census(fluid.Scope())
        assert census["kv_pages"] == 0

    def test_census_tick_cadence(self):
        before = runtime_metrics.counter("hbm.census_runs")
        perf.arm_census(3600.0)
        try:
            perf.census_tick(fluid.Scope())   # due immediately (fresh arm)
            perf.census_tick(fluid.Scope())   # armed-not-due: no census
            assert runtime_metrics.counter("hbm.census_runs") \
                == before + 1
        finally:
            perf.arm_census(None)
        perf.census_tick(fluid.Scope())       # unarmed: no census
        assert runtime_metrics.counter("hbm.census_runs") == before + 1

    def test_headroom_warning_fires_before_first_run(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_HBM_LIMIT_BYTES", "1")
        before = runtime_metrics.counter("hbm.headroom_warnings")
        main, startup, loss = _build_fc_train(size=40)
        _run_fresh(main, startup, loss)
        assert runtime_metrics.counter("hbm.headroom_warnings") > before
        assert runtime_metrics.gauge("hbm.limit_bytes") == 1


class TestWarmupReport:
    def _inference_program(self):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            y = fluid.layers.fc(input=x, size=8)
        main._is_inference = True
        return main, startup, y.name

    def test_cold_then_warm_buckets(self):
        inf, startup, fetch = self._inference_program()
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup)
            rep = exe.warmup(inf, [{"x": (1, 4)}, {"x": (2, 4)}],
                             fetch_list=[fetch], scope=scope)
            assert int(rep) == 2          # int contract preserved
            assert [b["cache"] for b in rep.buckets] == ["cold", "cold"]
            assert all(b["seconds"] > 0 and b["compiles"] == 1
                       for b in rep.buckets)
            assert rep.buckets[0]["signature"] == {"x": [1, 4]}
            again = exe.warmup(inf, [{"x": (1, 4)}], fetch_list=[fetch],
                               scope=scope)
            assert int(again) == 0
            assert [b["cache"] for b in again.buckets] == ["warm"]

    def test_merge_tags_programs(self):
        a = perf.WarmupReport(1, [{"signature": {}, "compiles": 1,
                                   "seconds": 0.1, "cache": "cold"}])
        b = perf.WarmupReport(0, [{"signature": {}, "compiles": 0,
                                   "seconds": 0.0, "cache": "warm"}])
        merged = perf.WarmupReport.merge(a, b,
                                         labels=("prefill", "decode"))
        assert int(merged) == 1
        assert [x["program"] for x in merged.buckets] == \
            ["prefill", "decode"]


class TestServingWarmupStats:
    def test_stats_expose_per_bucket_report(self, tmp_path):
        import urllib.request
        from paddle_tpu.serving import InferenceServer

        main = fluid.Program()
        startup = fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            pred = fluid.layers.fc(input=x, size=2)
        exe = fluid.Executor()
        exe.run(startup)
        d = str(tmp_path / "model")
        with fluid.program_guard(main, startup):
            fluid.io.save_inference_model(d, ["x"], [pred], exe)
        server = InferenceServer(d, port=0, warmup=True)
        server.start_background()
        try:
            host, port = server.addr
            snap = json.loads(urllib.request.urlopen(
                f"http://{host}:{port}/stats", timeout=30).read())
            rep = snap["server"]["warmup"]
            assert rep and all(
                b["cache"] in ("cold", "persistent-hit", "warm")
                for b in rep)
            assert all("signature" in b and b["seconds"] >= 0
                       for b in rep)
        finally:
            server.shutdown()


class TestProfileCli:
    def test_profile_compile_json_schema(self, capsys):
        from paddle_tpu import cli
        rc = cli.main(["profile", "compile", "--zoo", "mnist", "--json"])
        assert rc == 0
        report = json.loads(capsys.readouterr().out)
        assert perf.validate_report(report) == []
        assert any(r["flops"] for r in report["records"])

    def test_profile_memory_json(self, capsys):
        from paddle_tpu import cli
        rc = cli.main(["profile", "memory", "--zoo", "mnist", "--json"])
        assert rc == 0
        census = json.loads(capsys.readouterr().out)
        for k in ("params", "optimizer", "kv_pages", "prefetch",
                  "other", "total", "high_watermark"):
            assert k in census
        assert census["params"] > 0


class TestBenchHistoryPerf:
    def test_refuses_cross_basis_comparison(self, tmp_path):
        from paddle_tpu.obs import bench_history as bh
        path = str(tmp_path / "traj.json")
        bh.record("train_transformer",
                  {"tokens_per_sec_per_chip": 5e5, "mfu": 0.9},
                  path=path, baseline=True, mfu_basis="tpu-peak")
        bh.record("train_transformer",
                  {"tokens_per_sec_per_chip": 2e4, "mfu": 0.03},
                  path=path, mfu_basis="cpu-fallback")
        report = bh.check(path=path)
        assert not report["ok"]
        assert any("mfu_basis" in p for p in report["problems"])
        b = report["benches"]["train_transformer"]
        assert b["comparisons"] == []   # never judged across bases
        assert b["basis_mismatch"] == {"baseline": "tpu-peak",
                                       "newest": "cpu-fallback"}

    def test_same_basis_guards_measured_mfu_and_compile_time(
            self, tmp_path):
        from paddle_tpu.obs import bench_history as bh
        path = str(tmp_path / "traj.json")
        good = {"tokens_per_sec_per_chip": 5e5, "mfu": 0.9,
                "measured_mfu": 0.85, "compile_seconds": 10.0}
        bh.record("train_transformer", good, path=path, baseline=True,
                  mfu_basis="tpu-peak")
        bh.record("train_transformer",
                  dict(good, measured_mfu=0.4, compile_seconds=30.0),
                  path=path, mfu_basis="tpu-peak")
        report = bh.check(path=path)
        assert not report["ok"]
        bad = {r["metric"] for r in
               report["benches"]["train_transformer"]["regressions"]}
        assert bad == {"measured_mfu", "compile_seconds"}

    def test_rejects_unknown_basis(self, tmp_path):
        from paddle_tpu.obs import bench_history as bh
        with pytest.raises(ValueError):
            bh.record("train_transformer", {"mfu": 0.5},
                      path=str(tmp_path / "t.json"), mfu_basis="gpu")


class TestFleetPerfRollup:
    def _scrape(self, addr, gauges, ok=True):
        return {"addr": addr, "id": addr, "ok": ok, "error": None,
                "rtt_s": 0.01,
                "stats": {"counters": {}, "series": {},
                          "histograms": {}, "gauges": gauges}}

    def test_replica_perf_and_rollups(self):
        from paddle_tpu.obs import aggregate
        scrapes = [
            self._scrape("a:1", {"train.mfu": 0.8,
                                 "hbm.headroom_bytes": 100.0}),
            self._scrape("b:2", {"gen.decode_mfu": 0.4,
                                 "hbm.headroom_bytes": 50.0}),
            self._scrape("c:3", {}, ok=False),
        ]
        perf_map = aggregate.replica_perf(scrapes)
        assert set(perf_map) == {"a:1", "b:2"}
        assert perf_map["a:1"]["train.mfu"] == 0.8
        text = aggregate.render_federated(scrapes)
        assert "paddle_tpu_fleet_mfu_mean 0.6" in text
        assert "paddle_tpu_fleet_hbm_headroom_min_bytes 50" in text
        # per-replica gauges ride the labelled registries
        assert 'paddle_tpu_train_mfu{replica="a:1"} 0.8' in text
        assert 'paddle_tpu_hbm_headroom_bytes{replica="b:2"} 50' in text

    def test_scraper_caches_last_perf_for_router_stats(self, monkeypatch):
        """The router's /stats `fleet_perf` body: the scraper snapshots
        per-replica perf on every federation pass; /stats reads the
        cache without blocking on a pull."""
        from paddle_tpu.obs import aggregate
        from paddle_tpu.profiler import RuntimeMetrics

        snap = {"counters": {}, "series": {}, "histograms": {},
                "gauges": {"train.mfu": 0.7, "hbm.headroom_bytes": 9.0}}
        monkeypatch.setattr(aggregate, "fetch_stats",
                            lambda addr, timeout=5.0: snap)
        scraper = aggregate.FleetScraper(lambda: [("r:1", "rid")],
                                         metrics=RuntimeMetrics())
        assert scraper.last_perf() == {}   # nothing before a pass
        scraper.scrape()
        got = scraper.last_perf()
        assert got["r:1"]["train.mfu"] == 0.7
        assert got["r:1"]["hbm.headroom_bytes"] == 9.0
        assert got["r:1"]["id"] == "rid"
