"""CI guard: disabled tracing must cost <5% of the step loop.

The bench environment has 2 noisy vCPUs, so the guard does NOT race two
sleep loops against each other (sleep scheduling jitter under load is
tens of microseconds per step — the same order as the bound being
checked).  Instead the step is sleep-MODELED: a production step is
taken as 1 ms of device dispatch, the per-step cost of the disabled
instrumentation shell (the spans + latency series Executor.run /
run_pipeline wrap every step in) is measured directly over many
iterations, and the guard asserts shell < 5% of the modeled step.
That is the same contract — "instrumented loop <= 1.05x plain loop" —
with the noise term removed instead of averaged over."""

import time

from paddle_tpu.obs import numerics, perf, slo, trace
from paddle_tpu.obs.ledger import RunLedger
from paddle_tpu.profiler import RuntimeMetrics, record_latency

# the modeled production step: 1 ms of compiled dispatch (the serving
# fixture's tiny model dispatches in this order of magnitude; real
# training steps are larger, making the bound only easier)
STEP_SECONDS = 0.001
MAX_OVERHEAD_FRACTION = 0.05


def _shell_once(metrics, i, watchdog=None, perf_record=None,
                ledger=None, health=None):
    """The per-step instrumentation shell of Executor.run_pipeline +
    run AND the fleet-plane hooks the hot loops now carry: one step
    span, three phase spans and the four children of the dispatch, one
    latency series, the SLO tick the
    GenScheduler loop makes (a None check unarmed; one clock read
    armed-but-not-due), and the device-perf hooks every Executor.run
    now pays — the MFU note (a None check without a compile record; a
    division + one gauge write with one) and the HBM census tick (a
    None check unarmed; one clock read armed-but-not-due).  Federation
    adds NO per-step hook — it is pull-based, so with no scrape active
    its steady-state cost is exactly zero — which this shell
    demonstrates by containing nothing for it.  The training-health
    plane adds the run-ledger note (a None check unarmed; one buffered
    row append + gauge snapshot armed) and the sentinel's health-gauge
    writes (a None check unarmed; three gauge writes armed — the norms
    themselves ride the sentinel's already-paid device sync)."""
    with trace.span("train.step", step=i):
        with record_latency("obs_overhead.step_seconds",
                            metrics=metrics):
            with trace.span("executor.feed"):
                pass
            with trace.span("executor.dispatch") as dsp:
                # the four stretches of a dispatch (place: mesh path)
                with trace.span("executor.lookup"):
                    pass
                with trace.span("executor.state") as gathered:
                    gathered.set(arrays=2)
                with trace.span("executor.place") as placed:
                    placed.set(arrays=3, moved=1, bytes=8)
                with trace.span("executor.launch"):
                    pass
                dsp.set(fetches=1)
            with trace.span("executor.fetch"):
                pass
    slo.tick(watchdog)
    perf.note_step(perf_record, STEP_SECONDS, metrics=metrics)
    perf.census_tick()
    if ledger is not None:
        ledger.note_step(fetch_names=_FETCH_NAMES, fetches=_FETCHES)
    if health is not None:
        numerics.set_health_gauges(metrics, health)


_FETCH_NAMES = ("mean_0.tmp_0",)
_FETCHES = ([0.125],)


def _per_step_shell_seconds(metrics, iters=2000, watchdog=None,
                            perf_record=None, ledger=None, health=None):
    t0 = time.perf_counter()
    for i in range(iters):
        _shell_once(metrics, i, watchdog, perf_record, ledger, health)
    return (time.perf_counter() - t0) / iters


class TestDisabledTracingOverhead:
    def test_disabled_span_is_shared_noop(self):
        trace.disable()
        assert trace.span("a", x=1) is trace.span("b")

    def test_step_loop_overhead_under_5_percent(self):
        trace.disable()
        m = RuntimeMetrics()
        # best-of-5: a contended 2-vCPU runner inflates some rounds;
        # the minimum is the shell's true cost
        shell = min(_per_step_shell_seconds(m) for _ in range(5))
        budget = STEP_SECONDS * MAX_OVERHEAD_FRACTION
        assert shell <= budget, (
            f"disabled instrumentation shell costs {shell * 1e6:.1f}us "
            f"per step — over {MAX_OVERHEAD_FRACTION:.0%} of a "
            f"{STEP_SECONDS * 1e3:.0f}ms step ({budget * 1e6:.0f}us)")
        # the latency series keeps recording while spans are disabled
        assert m.snapshot()["series"][
            "obs_overhead.step_seconds"]["count"] == 5 * 2000

    def test_scheduler_turn_overhead_under_5_percent(self):
        """The spans one ``GenScheduler`` loop turn carries with tracing
        off — the turn, one decode iteration with its step (the
        compiled turn's launch with its three phases and ``gen.dispatch``,
        then the collect of the step that was in flight) and its emit
        loop, and
        one admission (queue wait, admit, prefill with its run, first
        token, seed) — against the same modeled 1 ms step; a real decode
        step on the chip is ten times that."""
        trace.disable()
        trace.clear()       # what an earlier test of this worker left

        def run():
            with trace.span("executor.run"):
                with trace.span("executor.feed"):
                    pass
                with trace.span("executor.dispatch"):
                    with trace.span("executor.lookup"):
                        pass
                    with trace.span("executor.state") as gathered:
                        gathered.set(arrays=2)
                    with trace.span("executor.launch"):
                        pass
                with trace.span("executor.fetch"):
                    pass

        def launch():
            # the decode turn's one compiled call
            # (``Executor.compiled_step``): no lookup, no state walk
            with trace.span("executor.run"):
                with trace.span("executor.feed"):
                    pass
                with trace.span("executor.dispatch"):
                    with trace.span("executor.launch"):
                        pass
                with trace.span("executor.fetch"):
                    pass
            trace.record_span("gen.dispatch", 0.0, 0.0,
                              parent_id=trace.current_span_id(),
                              patched=0, pages=1)

        def turn(i):
            with trace.span("gen.sched.turn"):
                trace.record_span("gen.queue_wait", 0.0, 0.0,
                                  trace_id="r", queued_behind=0)
                with trace.trace_context("r"):
                    with trace.span("gen.admit", slot=0):
                        with trace.span("gen.prefill", tokens=8):
                            run()
                        with trace.span("gen.first_token"):
                            pass
                        with trace.span("gen.seed_slot") as seed:
                            seed.set(pages=1)
                            seed.set(compiled_calls=1, eager_ops=0)
                with trace.span("gen.decode_iteration", live=i):
                    with trace.span("gen.decode_step", ahead=1) as step:
                        launch()
                        with trace.span("gen.collect"):
                            pass
                        step.set(live=i, discarded=0)
                    with trace.span("gen.emit"):
                        pass

        def per_turn(iters=2000):
            t0 = time.perf_counter()
            for i in range(iters):
                turn(i)
            return (time.perf_counter() - t0) / iters

        shell = min(per_turn() for _ in range(5))
        budget = STEP_SECONDS * MAX_OVERHEAD_FRACTION
        assert shell <= budget, (
            f"disabled scheduler-turn spans cost {shell * 1e6:.1f}us a "
            f"turn — over {MAX_OVERHEAD_FRACTION:.0%} of a "
            f"{STEP_SECONDS * 1e3:.0f}ms step ({budget * 1e6:.0f}us)")
        assert trace.snapshot_spans() == []

    def test_armed_slo_watchdog_stays_under_5_percent(self):
        """Satellite: the SLO evaluator's hot-loop hook with a REAL
        armed watchdog (interval not yet due — the steady state between
        evaluations) still fits the disabled-shell budget; PADDLE_TPU_
        TRACE=0 and no scrape active, so this is the whole fleet-plane
        cost a decode iteration pays."""
        trace.disable()
        m = RuntimeMetrics()
        wd = slo.SLOWatchdog(
            {"version": 1, "interval_seconds": 3600.0,
             "objectives": [{"name": "lat", "kind": "quantile",
                             "series": "obs_overhead.step_seconds",
                             "quantile": "p99", "max": 10.0}]},
            metrics=m)
        wd.evaluate()   # seed _last_eval: steady state = not-due path
        shell = min(_per_step_shell_seconds(m, watchdog=wd)
                    for _ in range(5))
        budget = STEP_SECONDS * MAX_OVERHEAD_FRACTION
        assert shell <= budget, (
            f"armed-SLO instrumentation shell costs "
            f"{shell * 1e6:.1f}us per step — over "
            f"{MAX_OVERHEAD_FRACTION:.0%} of a "
            f"{STEP_SECONDS * 1e3:.0f}ms step ({budget * 1e6:.0f}us)")
        # the not-due path really did skip evaluation (1 seed pass)
        assert wd.evaluations == 1

    def test_armed_perf_hooks_stay_under_5_percent(self):
        """Satellite: the device-perf hooks in their ARMED steady state
        — a live compile record (so every step derives the MFU gauge:
        one division + one locked gauge write) and an armed-but-not-due
        HBM census cadence (one clock read) — still fit the
        disabled-shell budget."""
        trace.disable()
        m = RuntimeMetrics()
        record = {"flops": 1e12, "steps": 0, "last_step_seconds": None,
                  "mfu": None}
        before = m.counter("hbm.census_runs")
        perf.arm_census(3600.0)
        try:
            perf.census_tick()   # burn the fresh-arm due tick
            shell = min(_per_step_shell_seconds(m, perf_record=record)
                        for _ in range(5))
        finally:
            perf.arm_census(None)
        budget = STEP_SECONDS * MAX_OVERHEAD_FRACTION
        assert shell <= budget, (
            f"armed perf-hook shell costs {shell * 1e6:.1f}us per step "
            f"— over {MAX_OVERHEAD_FRACTION:.0%} of a "
            f"{STEP_SECONDS * 1e3:.0f}ms step ({budget * 1e6:.0f}us)")
        # the MFU note really ran per step, the census never tripped
        assert record["steps"] == 5 * 2000
        assert m.gauge("train.mfu") is not None
        assert m.counter("hbm.census_runs") == before

    def test_armed_ledger_and_health_stay_under_5_percent(self):
        """Satellite: the training-health plane in its ARMED steady
        state — a real RunLedger appending one buffered row per step
        (flush_every amortizes the write; no per-row fsync) plus the
        sentinel's three health-gauge writes — still fits the
        disabled-shell budget.  Disabled, both hooks are a single
        None check, covered by the base shell test."""
        import tempfile

        trace.disable()
        m = RuntimeMetrics()
        with tempfile.TemporaryDirectory() as d:
            led = RunLedger(d + "/ledger", rotate_rows=100_000,
                            flush_every=64, metrics=m, install=False)
            health = {"param_norm": 3.0, "grad_norm": 0.01,
                      "update_ratio": 0.0033}
            try:
                shell = min(
                    _per_step_shell_seconds(m, ledger=led, health=health)
                    for _ in range(5))
            finally:
                led.close()
            budget = STEP_SECONDS * MAX_OVERHEAD_FRACTION
            assert shell <= budget, (
                f"armed ledger+health shell costs {shell * 1e6:.1f}us "
                f"per step — over {MAX_OVERHEAD_FRACTION:.0%} of a "
                f"{STEP_SECONDS * 1e3:.0f}ms step "
                f"({budget * 1e6:.0f}us)")
            # every step really appended a row and wrote the gauges
            assert led.rows_total == 5 * 2000
            assert m.gauge("train.grad_norm") == 0.01

    def test_enabled_tracing_records_bounded_spans(self):
        trace.enable(ring_size=256)
        trace.clear()
        m = RuntimeMetrics()
        for i in range(100):
            _shell_once(m, i)
        spans = trace.snapshot_spans()
        assert len(spans) == 256          # ring bound respected (8/step)
        assert {"train.step", "executor.feed", "executor.dispatch",
                "executor.fetch"} <= {s["name"] for s in spans}
        trace.clear()
        trace.disable()


class TestDispatchChildren:
    """``executor.dispatch`` splits into ``executor.lookup`` / ``state``
    / (mesh path) ``place`` / ``launch``: the same names, in that order,
    inside the parent, in ``run``, ``run_steps`` and
    ``ParallelExecutor.run``; nothing is recorded with the ring off."""

    @staticmethod
    def _program():
        import paddle_tpu as fluid
        from paddle_tpu import layers
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = layers.data("x", shape=[16], dtype="float32")
            y = layers.data("y", shape=[1], dtype="int64")
            out = layers.fc(layers.fc(x, 32, act="relu"), 4, act="softmax")
            loss = layers.reduce_mean(layers.cross_entropy(out, y))
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        return fluid, main, startup, loss

    @staticmethod
    def _feed(rows=8):
        import numpy as np
        rng = np.random.RandomState(0)
        return {"x": rng.rand(rows, 16).astype("f"),
                "y": rng.randint(0, 4, (rows, 1)).astype("int64")}

    def _calls(self):
        """``{how: callable}`` over one program, each compiled already."""
        import numpy as np
        from paddle_tpu.parallel import ParallelExecutor
        from paddle_tpu.parallel.mesh import make_mesh
        import jax
        fluid, main, startup, loss = self._program()
        exe = fluid.Executor(fluid.CPUPlace())
        feed = self._feed()
        stacked = {k: np.stack([v, v]) for k, v in feed.items()}
        mesh = make_mesh((4,), ("data",), devices=jax.devices()[:4])
        pexe = ParallelExecutor(loss_name=loss.name, main_program=main,
                                mesh=mesh)
        # the mesh path leaves its state sharded over the mesh: a scope
        # of its own
        single, sharded = fluid.Scope(), fluid.Scope()
        for scope in (single, sharded):
            exe.run(startup, scope=scope)
        calls = {
            "run": lambda: exe.run(main, feed=feed, fetch_list=[loss],
                                   scope=single),
            "run_steps": lambda: exe.run_steps(
                main, feed=stacked, fetch_list=[loss], steps=2,
                scope=single),
            "mesh": lambda: pexe.run(feed=feed, fetch_list=[loss.name],
                                     scope=sharded)}
        for call in calls.values():
            call()      # compile outside what is looked at
        return calls

    def test_children_in_order_inside_the_parent(self):
        calls = self._calls()
        for how, call in calls.items():
            trace.enable(ring_size=256)
            trace.clear()
            try:
                call()
                spans = trace.snapshot_spans()
            finally:
                trace.disable()
                trace.clear()
            (parent,) = [s for s in spans
                         if s["name"] == "executor.dispatch"]
            kids = sorted((s for s in spans
                           if s["parent_id"] == parent["span_id"]),
                          key=lambda s: s["ts"])
            want = ["executor.lookup", "executor.state", "executor.launch"]
            if how == "mesh":
                want.insert(2, "executor.place")
            assert [k["name"] for k in kids] == want, how
            end = parent["ts"] + parent["dur"]
            for a, b in zip(kids, kids[1:]):
                assert a["ts"] + a["dur"] <= b["ts"] + 1e-9
            assert kids[0]["ts"] >= parent["ts"] - 1e-9
            assert kids[-1]["ts"] + kids[-1]["dur"] <= end + 1e-9
            by_name = {k["name"]: k["attrs"] for k in kids}
            assert by_name["executor.state"]["arrays"] > 0
            # the call dispatches from its record: compiled already, so
            # a hit; ``run`` and ``run_steps`` share a scope here, so
            # each looks up what the other's write-back replaced, and
            # the mesh path, alone on its scope, looks up nothing
            assert by_name["executor.lookup"]["record"] == "hit"
            assert by_name["executor.state"]["resolved"] == \
                (0 if how == "mesh"
                 else by_name["executor.state"]["arrays"])
            if how == "mesh":
                placed = by_name["executor.place"]
                # state stays placed after the first step: the feeds
                # and the key are what is compared and what moves
                assert placed["arrays"] > placed["moved"] >= 3
                assert placed["checked"] == len(self._feed()) + 1
                assert placed["bytes"] > 0
            assert not any(s["name"] == "executor.compile" for s in spans)

    def test_nothing_is_recorded_with_the_ring_off(self):
        calls = self._calls()
        trace.disable()
        trace.clear()
        for call in calls.values():
            call()
        assert trace.snapshot_spans() == []
