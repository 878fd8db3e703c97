"""The one tracing control (``profiler.start_profiler`` /
``stop_profiler``): the device profiler and the span ring started and
stopped together in a running process, tied by a clock mark; and the
spans the scheduler thread and ``Executor.run_steps`` record under it."""

import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import profiler
from paddle_tpu.gen import GenPredictor, GenScheduler
from paddle_tpu.models import gen_lm
from paddle_tpu.obs import trace


@pytest.fixture(autouse=True)
def _tracing_off():
    trace.disable()
    trace.clear()
    yield
    if profiler._session is not None:
        profiler.stop_profiler()
    trace.disable()
    trace.clear()


def _blocking_call():
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((256, 256), jnp.float32)
    f(x).block_until_ready()      # compiled before any trace
    return lambda: f(x).block_until_ready()


def _host_events(xplane, name):
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(xplane).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                out += [(float(ev.start_ns),
                         float(ev.start_ns + ev.duration_ns))
                        for ev in line.events if ev.name.startswith(name)]
    return out


class TestControl:
    def test_start_stop_twice_in_one_process(self, tmp_path):
        call = _blocking_call()
        for k in range(2):
            profiler.start_profiler(profile_path=str(tmp_path / f"t{k}"))
            assert profiler._session is not None and trace.enabled()
            call()
            session = profiler.stop_profiler()
            assert profiler._session is None
            assert session["trace_dir"] == str(tmp_path / f"t{k}")
            assert session["xplane"].endswith(".xplane.pb")
            assert session["span_to_trace_ns"] is not None
            assert session["t_start"] < session["t_stop"]
            # perf_counter against the profiler's clock over a few ms
            assert abs(session["drift_ns"]) < 1e6
        assert profiler.stop_profiler() is None     # nothing running

    def test_second_start_raises_and_leaves_the_first_running(self,
                                                              tmp_path):
        profiler.start_profiler(profile_path=str(tmp_path / "a"))
        with pytest.raises(RuntimeError, match="already running"):
            profiler.start_profiler(profile_path=str(tmp_path / "b"))
        assert profiler._session is not None
        assert profiler.stop_profiler()["trace_dir"] == str(tmp_path / "a")

    @pytest.mark.parametrize("ring_on_before", [False, True])
    def test_ring_is_left_as_it_was_found(self, tmp_path, ring_on_before):
        if ring_on_before:
            trace.enable()
        profiler.start_profiler(profile_path=str(tmp_path / "t"))
        assert trace.enabled()
        profiler.stop_profiler()
        assert trace.enabled() is ring_on_before

    def test_clock_mark_ties_spans_to_the_trace(self, tmp_path):
        """The mark is in the host plane, and a span around a blocking
        jitted call, mapped by ``span_to_trace_ns``, contains the call's
        own host-plane event within 1 ms."""
        call = _blocking_call()
        profiler.start_profiler(profile_path=str(tmp_path / "t"))
        import jax
        with trace.span("test.blocking_call"):
            with jax.profiler.TraceAnnotation("test.inner_call"):
                call()
        time.sleep(0.02)
        session = profiler.stop_profiler()
        marks = _host_events(session["xplane"], profiler.CLOCK_MARK)
        assert len(marks) == 2
        ring = [s for s in trace.snapshot_spans()
                if s["name"] == profiler.CLOCK_MARK]
        assert [s["attrs"]["index"] for s in ring] == [0, 1]
        assert ring[0]["ts"] == pytest.approx(session["t_start"])
        (sp,) = [s for s in trace.snapshot_spans()
                 if s["name"] == "test.blocking_call"]
        a = sp["ts"] * 1e9 + session["span_to_trace_ns"]
        b = a + sp["dur"] * 1e9
        ((e0, e1),) = _host_events(session["xplane"], "test.inner_call")
        assert a - 1e6 <= e0 and e1 <= b + 1e6
        # the two were opened back to back: the clocks agree far better
        assert abs(e0 - a) < 1e6 and abs(b - e1) < 1e6

    def test_trace_context_inside_a_span_overrides_its_trace_id(self):
        """A scheduler turn admits one request: what opens under the
        request's context joins the request's trace and keeps the turn
        as its parent; the turn's next child is the turn's again."""
        trace.enable()
        with trace.span("turn") as turn:
            with trace.trace_context("req-1"):
                assert trace.current_trace_id() == "req-1"
                with trace.span("admit"):
                    with trace.span("prefill"):
                        pass
            assert trace.current_trace_id() == turn.trace_id
            with trace.span("iteration"):
                pass
        by_name = {s["name"]: s for s in trace.snapshot_spans()}
        assert by_name["admit"]["trace_id"] == "req-1"
        assert by_name["prefill"]["trace_id"] == "req-1"
        assert by_name["admit"]["parent_id"] == by_name["turn"]["span_id"]
        assert by_name["iteration"]["trace_id"] == \
            by_name["turn"]["trace_id"] != "req-1"

    def test_ts_of_is_the_span_clock(self):
        trace.enable()
        t = time.perf_counter()
        sp = trace.record_span("test.at", t, 0.0)
        assert sp is not None
        (got,) = [s for s in trace.snapshot_spans()
                  if s["name"] == "test.at"]
        assert got["ts"] == pytest.approx(trace.ts_of(t), abs=1e-9)


# ---------------------------------------------------------------------------
# spans where the work happens
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def gen_predictor(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("genlm_trace") / "bundle")
    gen_lm.export_gen_model(d, gen_lm.GenConfig(), num_slots=4)
    p = GenPredictor(d)
    p.warmup()
    return p


def _inside(child, parent):
    return parent["ts"] <= child["ts"] and \
        child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + 1e-9


class TestSchedulerSpans:
    def test_span_tree_of_a_toy_run(self, gen_predictor):
        trace.enable(1 << 14)
        m = profiler.runtime_metrics
        waits0 = (m.snapshot()["series"].get("gen.queue_wait_seconds")
                  or {}).get("count") or 0
        eager0 = m.counter("gen.seed.eager_ops")
        calls0 = m.counter("gen.seed.compiled_calls")
        sched = GenScheduler(gen_predictor, queue_size=8)
        try:
            streams = []
            for k in range(3):
                with trace.trace_context(f"req-{k}"):
                    streams.append(sched.submit([5 + k, 6, 7],
                                                max_new_tokens=4))
            assert all(len(list(s)) == 4 for s in streams)
        finally:
            sched.close()
        spans = trace.snapshot_spans()
        by_id = {s["span_id"]: s for s in spans}
        named = lambda n: [s for s in spans if s["name"] == n]

        # one queue wait and one admission per request, under its id
        for name in ("gen.queue_wait", "gen.admit"):
            assert sorted(s["trace_id"] for s in named(name)) == \
                ["req-0", "req-1", "req-2"], name
        assert all("queued_behind" in s["attrs"]
                   for s in named("gen.queue_wait"))
        for child in ("gen.prefill", "gen.first_token", "gen.seed_slot"):
            got = named(child)
            assert len(got) == 3, child
            for s in got:
                parent = by_id[s["parent_id"]]
                assert parent["name"] == "gen.admit"
                assert s["trace_id"] == parent["trace_id"]
        # one compiled, pool-donating call per admission; no pool
        # array copied (the pre-PR-24 eager path read n_cache here)
        for s in named("gen.seed_slot"):
            assert s["attrs"]["eager_ops"] == 0
            assert s["attrs"]["compiled_calls"] == 1
            assert s["attrs"]["pages"] >= 1
        assert m.counter("gen.seed.eager_ops") - eager0 == 0
        assert m.counter("gen.seed.compiled_calls") - calls0 == 3
        assert m.snapshot()["series"]["gen.queue_wait_seconds"]["count"] \
            - waits0 == 3
        # ... and the series holds what the spans hold
        assert sorted(m.samples("gen.queue_wait_seconds", last=3)) == \
            pytest.approx(sorted(s["dur"] for s in named("gen.queue_wait")))

        # every decode iteration holds one step and one emit loop
        iterations = named("gen.decode_iteration")
        assert iterations and all(s["attrs"]["live"] >= 1
                                  for s in iterations)
        for child in ("gen.decode_step", "gen.emit"):
            got = named(child)
            assert len(got) == len(iterations), child
            for s in got:
                assert by_id[s["parent_id"]]["name"] == \
                    "gen.decode_iteration"
        # and everything on the scheduler thread lies under a turn
        turns = named("gen.sched.turn")
        assert turns
        for s in named("gen.admit") + iterations:
            assert by_id[s["parent_id"]]["name"] == "gen.sched.turn"
            assert any(_inside(s, t) for t in turns)


class TestExecutorSpans:
    @pytest.fixture()
    def regression(self):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[3], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="float32")
            pred = fluid.layers.fc(input=x, size=1)
            cost = fluid.layers.mean(
                x=fluid.layers.square_error_cost(input=pred, label=y))
            fluid.optimizer.SGD(learning_rate=0.1).minimize(cost)
        return main, startup, cost

    def test_run_steps_has_runs_three_phases(self, regression):
        main, startup, cost = regression
        xs = np.zeros((4, 8, 3), "float32")
        ys = np.zeros((4, 8, 1), "float32")
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            feed = {"x": xs, "y": ys}
            exe.run_steps(main, feed=feed, fetch_list=[cost], steps=4)
            trace.enable()
            trace.clear()
            exe.run_steps(main, feed=feed, fetch_list=[cost], steps=4)
        spans = trace.snapshot_spans()
        # spans land in the ring when they close: the dispatch's three
        # children (no mesh: no executor.place) before the dispatch
        assert [s["name"] for s in spans] == [
            "executor.feed", "executor.lookup", "executor.state",
            "executor.launch", "executor.dispatch", "executor.fetch",
            "executor.run_steps"]
        top = spans[-1]
        assert top["attrs"]["steps"] == 4
        phases = [s for s in spans[:-1] if s["name"] in (
            "executor.feed", "executor.dispatch", "executor.fetch")]
        assert all(s["parent_id"] == top["span_id"] for s in phases)
        dispatch = phases[1]
        assert all(s["parent_id"] == dispatch["span_id"]
                   for s in spans[1:4])

    @pytest.mark.parametrize("how", ["run", "run_steps"])
    def test_a_jit_cache_miss_yields_one_compile_span(self, regression,
                                                      how):
        main, startup, cost = regression

        def call(exe, batch):
            if how == "run":
                return exe.run(main, fetch_list=[cost], feed={
                    "x": np.zeros((batch, 3), "float32"),
                    "y": np.zeros((batch, 1), "float32")})
            return exe.run_steps(main, fetch_list=[cost], steps=2, feed={
                "x": np.zeros((2, batch, 3), "float32"),
                "y": np.zeros((2, batch, 1), "float32")})

        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            trace.enable()
            call(exe, 8)
            trace.clear()
            call(exe, 8)                    # cached: no compile span
            assert not [s for s in trace.snapshot_spans()
                        if s["name"] == "executor.compile"]
            call(exe, 16)                   # a new feed signature
        spans = trace.snapshot_spans()
        by_id = {s["span_id"]: s for s in spans}
        (comp,) = [s for s in spans if s["name"] == "executor.compile"]
        assert by_id[comp["parent_id"]]["name"] == "executor.dispatch"
        assert comp["attrs"]["version"] == main._version
        assert any(shape[-2:] == (16, 3) or shape == (16, 3)
                   for _, _, shape in comp["attrs"]["feeds"])

    def test_parallel_executor_miss_yields_one_compile_span(self,
                                                            regression):
        from paddle_tpu.parallel import ParallelExecutor
        main, startup, cost = regression
        feed = {"x": np.zeros((8, 3), "float32"),
                "y": np.zeros((8, 1), "float32")}
        with fluid.scope_guard(fluid.Scope()):
            fluid.Executor(fluid.CPUPlace()).run(startup)
            pexe = ParallelExecutor(loss_name=cost.name, main_program=main)
            trace.enable()
            pexe.run(feed=feed, fetch_list=[cost.name])
            assert len([s for s in trace.snapshot_spans()
                        if s["name"] == "executor.compile"]) == 1
            trace.clear()
            pexe.run(feed=feed, fetch_list=[cost.name])
            assert not [s for s in trace.snapshot_spans()
                        if s["name"] == "executor.compile"]
