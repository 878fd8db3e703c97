"""The benchmark's own tests: run by hand on the CPU, not part of tier-1.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
    (the dp4 rehearsal wants XLA_FLAGS=--xla_force_host_platform_device_count=4)

The trace fixture ``data/synthetic.xplane.pb`` is a hand-built XSpace (two
device planes and a host plane; on device 0 a ``while`` event encloses
its body and two events overlap, so sum != union); ``chip_excerpt``
fixtures, where present, are cut from a real v5e trace.
"""

import copy
import json
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from lib import closedloop, counts, manifest, models, openloop  # noqa: E402
from lib import peaks, served, serving_rig, stats, xtrace         # noqa: E402


# -- trace reducer -----------------------------------------------------------

@pytest.fixture(scope="module")
def events():
    return xtrace.load_device_events(
        os.path.join(HERE, "data", "synthetic.xplane.pb"))


def test_only_device_op_lines_are_read(events):
    assert sorted(events) == ["/device:TPU:0", "/device:TPU:1"]
    assert len(events["/device:TPU:0"]) == 5     # no module/async events


def test_busy_is_union_not_sum(events):
    ev = events["/device:TPU:0"]
    total = sum(e[1] - e[0] for e in ev) / 1e9
    assert total == pytest.approx(2900e-9)
    # while [0,1000] covers its body; [2000,2600] and [2300,2800] overlap
    assert xtrace.busy_seconds(ev) == pytest.approx(1800e-9)
    assert xtrace.busy_seconds(ev, window=(500, 2500)) == \
        pytest.approx(1000e-9)


def test_leaves_drop_the_enclosing_while(events):
    names = [e[2] for e in xtrace.leaf_events(events["/device:TPU:0"])]
    assert "while.1" not in names and names.count("fusion.7") == 2
    by_name = xtrace.seconds_by_name(events["/device:TPU:0"])
    assert by_name["fusion.7"] == pytest.approx(900e-9)


def test_kernel_found_by_its_text_and_gaps(events):
    ev = events["/device:TPU:0"]
    secs, n = xtrace.seconds_matching(ev, ["flash"])
    assert (n, secs) == (1, pytest.approx(400e-9))
    assert xtrace.seconds_matching(ev, ["all-reduce"])[1] == 1
    gaps = xtrace.idle_gaps(ev)
    assert gaps[0][1] == pytest.approx(1000e-9)


def test_runs_of_the_executable_that_holds_a_kernel():
    s = xtrace.summarize(os.path.join(HERE, "data", "synthetic.xplane.pb"))
    plane = "/device:TPU:0"
    assert s["lines"][plane]["XLA Ops"] == len(s["per_device"][plane])
    mods, ops = s["modules"][plane], s["per_device"][plane]
    assert mods and s["modules"]["/device:TPU:1"] == []
    held = xtrace.module_runs_holding(mods, ops, ["flash"])
    assert 1 <= len(held) <= len(mods)
    assert xtrace.module_runs_holding(mods, ops, ["no_such_kernel"]) == []


def test_summary_means_over_planes():
    s = xtrace.summarize(os.path.join(HERE, "data", "synthetic.xplane.pb"))
    assert s["busy_s"] == pytest.approx((1800e-9 + 500e-9) / 2)
    assert s["device_ops"][0][0] == "fusion.7"
    one = xtrace.summarize(os.path.join(HERE, "data",
                                        "synthetic.xplane.pb"), n_devices=1)
    assert one["busy_s"] == pytest.approx(1800e-9)


# -- spans on the trace's clock ----------------------------------------------

OFFSET_NS = 500.0       # span ts * 1e9 + this = the synthetic trace's ns


def _span(span_id, parent_id, name, start_ns, end_ns, tid=7):
    return {"name": name, "trace_id": "t", "span_id": span_id,
            "parent_id": parent_id, "ts": (start_ns - OFFSET_NS) / 1e9,
            "dur": (end_ns - start_ns) / 1e9, "tid": tid, "attrs": {}}


def _recorded_run(spans, facts=None, session=True):
    """What the harness hands a reader, over the synthetic trace's first
    device (busy [1000, 2000] and [3000, 3800] ns, one run of ``jit_step``
    over [1000, 4000]) and a traced stretch of [500, 5500] ns."""
    import run as harness
    trace = xtrace.summarize(os.path.join(HERE, "data",
                                          "synthetic.xplane.pb"),
                             n_devices=1)
    return harness, {
        "spans": spans, "facts": facts or {}, "counters": {},
        "trace": trace, "trace_window_s": 5e-6, "chips": 1,
        "session": {"span_to_trace_ns": OFFSET_NS, "t_start": 0.0,
                    "t_stop": 5e-6, "mark_width_ns": 0.0,
                    "drift_ns": 0.0} if session else None}


SERVE_SPANS = [
    _span(1, None, "gen.sched.turn", 600, 5000),
    _span(2, 1, "gen.decode_iteration", 800, 4200),
    _span(3, 2, "gen.decode_step", 900, 4100),
    _span(4, 3, "executor.run", 900, 4100),
    _span(5, 4, "executor.feed", 900, 1000),
    _span(6, 4, "executor.dispatch", 1000, 1100),
    _span(7, 4, "executor.fetch", 1100, 4100),
    _span(8, 2, "gen.emit", 4100, 4200),
    # recorded after the fact, on no stack: must name no gap
    _span(9, None, "gen.queue_wait", 100, 5400),
    _span(10, 1, "gen.seed_slot", 4300, 4700),
]


def _read(name, run_dict):
    import run as harness
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"]
                     if m["name"] == name)
    got = harness.read_layer_metrics([entry], run_dict)
    return got[name]["value"] if got else None


def test_new_readers_on_recorded_data():
    _, run_dict = _recorded_run(SERVE_SPANS)
    # iteration 3400 ns less its decode step 3200 ns
    assert _read("sched_self_ms_per_iteration", run_dict) == \
        pytest.approx(200e-6)
    assert _read("seed_slot_p50_ms", run_dict) == pytest.approx(400e-6)
    # feed 100 idle + dispatch 100 busy + fetch 3000 of which 1700 busy
    assert _read("executor_self_ms_per_step.serve", run_dict) == \
        pytest.approx(1400e-6)
    # idle 3200 ns of the stretch; under feed, iteration, fetch, emit and
    # seed_slot 2000 of them, the rest under the bare turn or no span
    assert _read("idle_named_share", run_dict) == pytest.approx(62.5)
    _, waits = _recorded_run([], facts={"slot_wait_s": [.001] * 19 + [.5]})
    assert _read("slot_wait_p95_ms", waits) == pytest.approx(
        stats.percentile([.001] * 19 + [.5], 95) * 1e3)
    _, train = _recorded_run(
        [_span(1, None, "executor.run_steps", 900, 4100),
         _span(2, 1, "executor.dispatch", 900, 1000)],
        facts={"traced_steps": 2})
    assert _read("executor_self_ms_per_step.train", train) == \
        pytest.approx((3200 - 1800) / 2 * 1e-6)


@pytest.mark.parametrize("name", [
    "sched_self_ms_per_iteration", "seed_slot_p50_ms", "idle_named_share",
    "executor_self_ms_per_step.serve", "executor_self_ms_per_step.train",
    "slot_wait_p95_ms"])
def test_new_readers_return_nothing_without_data(name):
    # slot_wait_s is empty from a program without the always-on series
    _, empty = _recorded_run([], facts={"traced_steps": 2,
                                        "slot_wait_s": []})
    assert _read(name, empty) is None
    if name == "slot_wait_p95_ms":
        return
    # spans but no clock offset (a program without the tracing control)
    _, no_clock = _recorded_run(SERVE_SPANS, session=False)
    if name not in ("sched_self_ms_per_iteration", "seed_slot_p50_ms"):
        assert _read(name, no_clock) is None
    no_trace = dict(no_clock, trace=None)
    if name not in ("sched_self_ms_per_iteration", "seed_slot_p50_ms"):
        assert _read(name, no_trace) is None


def test_breakdown_names_a_gap_by_the_covering_span():
    harness, run_dict = _recorded_run(SERVE_SPANS)
    # the one gap between device ops, [2000, 3000], lies in the fetch
    assert harness.breakdown_of(run_dict)["idle_gaps"] == \
        [["host:executor.fetch x1", pytest.approx(1000e-9)]]
    harness, bare = _recorded_run([])
    assert harness.breakdown_of(bare)["idle_gaps"] == \
        [["after:while.1|before:all-reduce.3 x1", pytest.approx(1000e-9)]]


def test_only_spans_between_the_clock_marks_reach_the_readers():
    from lib import spanclock
    session = {"t_start": (900 - OFFSET_NS) / 1e9,
               "t_stop": (4250 - OFFSET_NS) / 1e9}
    kept = spanclock.inside_session(SERVE_SPANS, session)
    # the turn starts before the first mark, the seed ends after the
    # second, the queue wait does both
    assert [s["span_id"] for s in kept] == [3, 4, 5, 6, 7, 8]
    assert spanclock.inside_session(SERVE_SPANS, None) == SERVE_SPANS


def test_clock_agreement_reports_share_and_overhang():
    from lib import spanclock
    proof = {"clock_proof": {"span": "gen.decode_step",
                             "holding": ["flash"]}}
    _, run_dict = _recorded_run(SERVE_SPANS, facts=proof)
    got = spanclock.agreement(run_dict)
    assert got["contained_share"] == 1.0 and got["runs_paired"] == 1
    assert got["largest_overhang_us"] == 0.0
    late = [dict(s) for s in SERVE_SPANS]
    late[2] = _span(3, 2, "gen.decode_step", 1100, 4100)
    _, run_dict = _recorded_run(late, facts=proof)
    got = spanclock.agreement(run_dict)
    assert got["contained_share"] == 0.0
    assert got["largest_overhang_us"] == pytest.approx(0.1)
    _, none = _recorded_run(SERVE_SPANS)
    assert spanclock.agreement(none) is None


# -- generator ---------------------------------------------------------------

PARAMS = {"rate_per_s": 4.0, "sample_seed": 22,
          "prompt": {"median": 128, "sigma": 1.0, "min": 4, "cap": 1024},
          "output": {"median": 64, "sigma": 0.7, "min": 2, "cap": 256}}


def test_schedule_is_the_cells_sample_and_the_seed_draws_the_tokens():
    a = openloop.build_schedule(30, PARAMS)
    assert a == openloop.build_schedule(30, PARAMS)
    assert a != openloop.build_schedule(30, dict(PARAMS, sample_seed=23))
    assert len(a) == 120 and 0 < a[0]["due"] and a[-1]["due"] < 30
    lens = [r["prompt_len"] for r in a]
    assert 90 <= stats.median(lens) <= 180 and max(lens) <= 1024
    # a plain Poisson sample: gaps as uneven as an exponential's (cv ~ 1)
    gaps = [r["due"] - (a[i - 1]["due"] if i else 0.0)
            for i, r in enumerate(a)]
    mean = sum(gaps) / len(gaps)
    cv = (sum((g - mean) ** 2 for g in gaps) / len(gaps)) ** 0.5 / mean
    assert 0.8 < cv < 1.25 and mean == pytest.approx(0.25, rel=0.01)
    # what --seed draws: the prompts' tokens (and the weights)
    cfg = {"vocab_size": 512}
    assert serving_rig._prompt(cfg, 5, 0, 16) == \
        serving_rig._prompt(cfg, 5, 0, 16)
    assert serving_rig._prompt(cfg, 5, 0, 16) != \
        serving_rig._prompt(cfg, 6, 0, 16)


def test_closed_loop_takes_one_sample_in_order_and_keeps_clients_busy():
    lengths = lambda n, params: [
        (r["index"], r["prompt_len"], r["max_new"])
        for sample in [closedloop.Sample(params)]
        for r in (sample.take() for _ in range(n))]
    a = lengths(200, PARAMS)
    assert a == lengths(200, PARAMS) and a[:50] == lengths(50, PARAMS)
    assert a != lengths(200, dict(PARAMS, sample_seed=23))
    assert [i for i, _, _ in a] == list(range(200))
    assert 90 <= stats.median([p for _, p, _ in a]) <= 180
    assert max(m for _, _, m in a) <= 256

    live, most, lock = [0], [0], __import__("threading").Lock()

    def send(request):
        with lock:
            live[0] += 1
            most[0] = max(most[0], live[0])
        time.sleep(0.02)
        with lock:
            live[0] -= 1
        if request["index"] == 3:
            raise RuntimeError("refused")
        return {"sent": request["sent_t"]}

    sample = closedloop.Sample(PARAMS)
    loop = closedloop.ClosedLoop(sample, send, clients=4)
    loop.start()
    time.sleep(0.3)
    records = loop.drain(5)
    # every client had one request open at a time, and sent its next at
    # once: 4 clients x ~15 turns of 20 ms
    assert most[0] == 4 and 40 <= len(records) <= 64
    assert sorted(r["request"]["index"] for r in records) == \
        list(range(len(records))) and sample.taken == len(records)
    bad = [r for r in records if not r["ok"]]
    assert len(bad) == 1 and "refused" in bad[0]["error"]
    # the stretch after the window continues the sample
    more = closedloop.ClosedLoop(sample, send, clients=2)
    more.start()
    assert all(r["request"]["index"] >= len(records)
               for r in more.drain(5))


def test_lateness_is_reported_and_timing_is_from_due():
    import time
    schedule = [{"index": i, "due": 0.02 * i, "prompt_len": 1, "max_new": 1}
                for i in range(5)]

    def send(request):
        time.sleep(0.01)
        return {"first": time.perf_counter() - request["due_t"]}

    loop = openloop.OpenLoop(schedule, send)
    records = loop.run(drain_timeout=5)
    assert len(records) == 5 and all(r["ok"] for r in records)
    late = openloop.OpenLoop.lateness(records)
    assert len(late) == 5 and all(0 <= x < 0.05 for x in late)
    assert all(r["first"] >= 0.01 for r in records)


def test_failed_request_is_a_record_not_a_crash():
    def send(request):
        raise RuntimeError("refused")
    records = openloop.OpenLoop(
        [{"index": 0, "due": 0.0, "prompt_len": 1, "max_new": 1}],
        send).run(drain_timeout=5)
    assert records[0]["ok"] is False and "refused" in records[0]["error"]


# -- manifest ----------------------------------------------------------------

@pytest.fixture()
def good():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_committed_manifest_is_valid(good):
    manifest.validate(good)
    for cell in good["workloads"]:
        _, config, wl = manifest.cell_files(good, cell["name"])
        assert os.path.exists(os.path.join(ROOT, config["file"]))
        with open(os.path.join(ROOT, wl)) as f:
            kind = json.load(f)["kind"]
        assert os.path.exists(os.path.join(BENCH, "traffic", kind + ".py"))
        reported = {m["name"] for m in manifest.metrics_of(
            good, "end_to_end", cell["name"])}
        for m in manifest.metrics_of(good, "per_layer", cell["name"]):
            with open(os.path.join(BENCH, "layer_metrics",
                                   m["name"] + ".json")) as f:
                spec = json.load(f)
            # a cell's per-layer metric moves a metric the cell reports
            assert m["moves"] in reported, (cell["name"], m["name"])
            assert "like" not in spec or os.path.exists(os.path.join(
                BENCH, "layer_metrics", spec["like"] + ".json"))
            assert {k: spec[k] for k in ("name", "layer", "moves", "unit",
                                         "source")} \
                == {k: m[k] for k in ("name", "layer", "moves", "unit",
                                      "source")}
        assert manifest.metrics_of(good, "per_layer", cell["name"])
        assert len(manifest.metrics_of(good, "end_to_end",
                                       cell["name"])) >= 2


@pytest.mark.parametrize("breakage", [
    lambda m: m["per_layer"][0].update(moves="no_such_metric"),
    lambda m: m["end_to_end"][0].update(unit="tokens per second"),
    lambda m: m["end_to_end"][0].update(unit="µs"),
    lambda m: m["workloads"][0].update(name="bad name"),
    lambda m: m["workloads"][0].update(name="bad/name"),
    lambda m: m["end_to_end"][0].update(workloads=["nowhere"]),
    lambda m: m["end_to_end"][0].update(why="a stray key"),
    lambda m: m["end_to_end"][0].update(bound=0.5),
    lambda m: m["workloads"].append(dict(m["workloads"][0])),
    lambda m: m.update(run_seconds=52),
    lambda m: [w.update(chips=4) for w in m["workloads"]],
    lambda m: m.update(trace_in_run=False),
    lambda m: m.update(trace_in_the_run=True),
    lambda m: next(e for e in m["per_layer"]
                   if e["name"] == "decode_step_p50_ms")["workloads"].append(
        "genlm_opt6.7b.decode_saturated"),
], ids=["moves", "unit-spaces", "unit-greek", "cell-space", "cell-slash",
        "metric-cell", "stray-key", "bound", "cell-twice", "run-seconds",
        "too-many-four-chip", "trace-in-run-false", "unknown-top-key",
        "moves-not-reported-by-a-listed-cell"])
def test_manifest_refuses(good, breakage):
    bad = copy.deepcopy(good)
    breakage(bad)
    with pytest.raises(manifest.ManifestError):
        manifest.validate(bad)


def test_unknown_cell_is_refused(good):
    with pytest.raises(manifest.ManifestError):
        manifest.cell_files(good, "transformer_base.nothing")


# -- counts and peaks --------------------------------------------------------

def test_training_flops_agree_with_the_programs_own_count():
    from paddle_tpu.models import transformer as T
    with open(os.path.join(BENCH, "configs", "transformer_base.json")) as f:
        cfg = json.load(f)
    hp = T.ModelHyperParams()
    for seq in (256, 1024):
        assert counts.transformer_train_flops_per_token(cfg, seq) == \
            T.train_flops_per_token(hp, seq=seq)
    assert counts.transformer_matmul_params(cfg) == T.matmul_param_count(hp)
    # B256 x S256: 21.19 TFLOP a step (PERF.md, PR 21)
    step = counts.transformer_train_flops_per_token(cfg, 256) * 65536
    assert step == pytest.approx(21.19e12, rel=1e-3)


def test_decode_bytes_from_shapes():
    with open(os.path.join(BENCH, "configs", "genlm_opt6.7b.json")) as f:
        cfg = json.load(f)
    per_layer = 4 * 4096 ** 2 + 2 * 4096 * 16384
    assert counts.genlm_weight_bytes(cfg) == pytest.approx(
        4 * (cfg["num_hidden_layers"] * per_layer + 4096 * 50272), rel=1e-3)
    # one slot with 100 live rows: K and V, every layer, 4096 floats a row
    assert counts.paged_attention_bytes_per_step(cfg, 100) == \
        2 * cfg["num_hidden_layers"] * 100 * 4096 * 4


def test_unknown_device_kind_raises():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9")


def test_percentile_and_spread():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile(range(101), 95) == 95
    assert stats.percentile([], 95) is None
    vals = [100, 101, 102, 103, 104, 105]
    import statistics
    q = statistics.quantiles(vals, n=4)
    assert stats.iqr_share(vals) == pytest.approx((q[2] - q[0]) / 102.5)


# -- toy rehearsals of each traffic kind (never a device metric) -------------

TOY_TRANSFORMER = {
    "config": dict(d_model=32, d_inner_hid=64, n_head=2, d_key=16,
                   d_value=16, n_layer=1, src_vocab_size=128,
                   trg_vocab_size=128),
    "workload": dict(batch=4, seq=16, steps_per_call=2, staged_batches=2,
                     loss_rtol=0.05, trace_calls=1)}
TOY_GENLM = {
    "config": dict(name="toy_genlm", hidden_size=256, ffn_dim=512,
                   num_attention_heads=2, num_hidden_layers=2,
                   vocab_size=512,
                   serving=dict(num_slots=4, max_len=128, page_len=16,
                                prompt_buckets=[32, 64],
                                page_buckets="default")),
    "workload": dict(rate_per_s=4.0,
                     prompt=dict(median=16, sigma=0.8, min=2, cap=64),
                     output=dict(median=8, sigma=0.5, min=2, cap=24),
                     reference_prompts=[10, 40], trace_seconds=0.5)}


def _rehearse(cell, toy, trace, seconds):
    import run
    return run.run_cell(cell, 2 ** 31 + 5, seconds, trace, rehearsal=toy)


@pytest.fixture()
def window_watch(monkeypatch):
    """What tracing is on as the traffic module's ``window`` starts and
    as it returns: a ``--trace 2`` run must be a ``--trace 0`` run until
    then."""
    import run
    from paddle_tpu import profiler
    from paddle_tpu.obs import trace
    seen, real = [], run.load_module

    def look(ctx):
        seen.append({"traced": ctx["traced"], "ring_on": trace.enabled(),
                     "spans": len(trace.snapshot_spans()),
                     "profiling": profiler._session is not None})

    def load(path, name):
        module = real(path, name)
        inner = getattr(module, "window", None)
        if inner is not None:
            def window(state, ctx, *args, **kwargs):
                look(ctx)
                out = inner(state, ctx, *args, **kwargs)
                look(ctx)
                return out
            module.window = window
        return module

    trace.disable()
    trace.clear()
    monkeypatch.setattr(run, "load_module", load)
    return seen


ALL_OFF = {"traced": False, "ring_on": False, "spans": 0,
           "profiling": False}


@pytest.mark.parametrize("trace", [0, 1, 2])
def test_train_steps_rehearsal(trace, window_watch):
    r = _rehearse("transformer_base.train_b256_s256", TOY_TRANSFORMER,
                  trace, 0.5)
    assert r["rehearsal"] and r["correct"] and r["failed"] == 0
    assert r["device"]["platform"] == "cpu"
    assert "busy_s" not in r["device"]
    if trace != 1:
        assert window_watch == [ALL_OFF, ALL_OFF]
    if trace == 1:  # no device trace on the CPU: no device metric at all
        assert r["metrics"] == {}
    else:
        assert set(r["metrics"]) == {"train_tokens_per_s_per_chip",
                                     "setup_s"}


def test_train_mesh_rehearsal():
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("wants XLA_FLAGS=--xla_force_host_platform_device_"
                    "count=4")
    r = _rehearse("transformer_base.train_dp4_b1024_s256", TOY_TRANSFORMER,
                  0, 0.5)
    assert r["correct"] and r["device"]["count"] >= 4


SERVE_SPAN_METRICS = {
    "prefill_p50_ms", "decode_step_p50_ms", "queue_wait_p50_ms",
    "queue_wait_p95_ms", "executor_call_ms_per_step.serve",
    "sched_self_ms_per_iteration", "seed_slot_p50_ms"}


@pytest.mark.parametrize("trace", [0, 1, 2])
def test_serve_open_loop_rehearsal(trace, window_watch):
    r = _rehearse("genlm_opt6.7b.chat_open", TOY_GENLM, trace, 3.0)
    assert r["rehearsal"] and r["correct"] and r["failed"] == 0
    assert r["attempted"] == 12
    if trace != 1:
        assert window_watch == [ALL_OFF, ALL_OFF]
        assert {"gap_p95_ms", "out_tokens_per_s", "setup_s"} \
            < set(r["metrics"])
        assert any(n.startswith("ttft_") for n in r["metrics"])
    if trace:
        # no device trace on the CPU: nothing that needs one
        for name in ("paged_attn_roofline", "decode_step_device_ms",
                     "idle_named_share", "executor_self_ms_per_step.serve"):
            assert name not in r["metrics"]
        assert SERVE_SPAN_METRICS <= set(r["metrics"])
    else:
        assert not SERVE_SPAN_METRICS & set(r["metrics"])
    if trace == 2:      # both kinds side by side, from one process
        assert {"ttft_p95_ms", "generator_late_p95_ms",
                "slot_occupancy_mean"} <= set(r["metrics"])


TOY_CLOSED = {"config": TOY_GENLM["config"],
              "workload": dict(TOY_GENLM["workload"], clients=4)}
SATURATED_SPAN_METRICS = {
    "prefill_p50_ms.saturated", "decode_step_p50_ms.saturated",
    "executor_call_ms_per_step.saturated", "seed_slot_p50_ms.saturated",
    "sched_self_ms_per_iteration.saturated"}


@pytest.mark.parametrize("trace", [0, 2])
def test_serve_closed_loop_rehearsal(trace, window_watch, capsys):
    r = _rehearse("genlm_opt6.7b.decode_saturated", TOY_CLOSED, trace, 3.0)
    assert r["rehearsal"] and r["correct"] and r["failed"] == 0
    assert r["attempted"] >= 8
    assert window_watch == [ALL_OFF, ALL_OFF]
    assert {"saturated_tokens_per_s", "gap_p99_ms", "setup_s"} \
        <= set(r["metrics"])
    assert r["metrics"]["saturated_tokens_per_s"]["value"] > 0
    assert r["metrics"]["gap_p99_ms"]["value"] > 0
    notes = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    seen = next(n for n in notes if n["note"] == "observed")
    # several clients' streams at once: a loop of ONE client cannot pass 1.
    # (No higher: on the CPU a toy's stream of 2-24 tokens of sub-ms steps
    # lives about as long as its client's next request takes to be sent
    # and admitted, so the mean reads 2.2-2.7 of 4 since PR 41.)
    assert seen["slot_occupancy_mean"] > 1.5
    assert seen["clients"] == 4 and seen["gap_p50_ms"] > 0
    for name in ("paged_attn_roofline.saturated", "idle_named_share.saturated",
                 "decode_step_hbm_roofline.saturated",
                 "decode_step_device_ms.saturated"):
        assert name not in r["metrics"]     # no device trace on the CPU
    if trace:
        assert SATURATED_SPAN_METRICS | {"slot_occupancy_mean.saturated"} \
            <= set(r["metrics"])
        phase = next(n for n in notes if n["note"] == "trace_phase")
        assert phase["traced_requests"] >= 4
    else:
        assert set(r["metrics"]) == {"saturated_tokens_per_s", "gap_p99_ms",
                                     "setup_s"}
    # what the window served was held against the reference
    said = next(n for n in notes if n["note"] == "served")
    assert said["served_ok"] and said["served_streams"] == 8
    assert said["served_tokens"] > 50


def test_closed_loop_has_no_trace_1_run():
    with pytest.raises(RuntimeError, match="--trace 2"):
        _rehearse("genlm_opt6.7b.decode_saturated", TOY_CLOSED, 1, 1.0)


def test_a_broken_decode_path_is_not_correct(monkeypatch):
    """The timed path broken underneath: cached decode steps that read a
    stale row give ``correct`` false (the reference check drives the
    same prefill, page seed and decode executables as the window)."""
    from paddle_tpu.gen.predictor import GenPredictor
    real = GenPredictor.decode_step

    def stale(self, tokens, positions, *args, **kwargs):
        import numpy as np
        return real(self, tokens, np.maximum(np.asarray(positions) - 1, 0),
                    *args, **kwargs)

    monkeypatch.setattr(GenPredictor, "decode_step", stale)
    r = _rehearse("genlm_opt6.7b.decode_saturated", TOY_CLOSED, 0, 1.0)
    assert r["failed"] == 0 and not r["correct"]


def test_the_served_sample_is_the_longest_and_what_was_live_beside_it():
    def rec(i, sent, end, prompt, n):
        return {"rid": f"req-{i}", "tokens": [1] * n, "times": [end],
                "request": {"index": i, "sent_t": sent, "prompt_len": prompt}}
    finished = [rec(0, 0.0, 1.0, 10, 5), rec(1, 0.5, 4.0, 40, 60),
                rec(2, 3.0, 5.0, 10, 9), rec(3, 4.5, 6.0, 10, 9),
                rec(4, 1.0, 2.0, 10, 9), rec(5, 7.0, 8.0, 10, 9)]
    got = served.sample(finished, 4, 7)
    assert got[0]["rid"] == "req-1"                     # the longest
    assert {r["rid"] for r in got[1:]} == {"req-0", "req-2", "req-4"}
    assert [r["rid"] for r in served.sample(finished, 4, 7)] \
        == [r["rid"] for r in got]                      # from the seed
    assert len(served.sample(finished, 6, 7)) == 6      # then the others
    assert served.sample([], 4, 7) == []


def test_a_fault_seen_only_with_several_live_slots_is_not_correct(
        monkeypatch):
    """A slot mix-up that shows only while more than one slot is live:
    the set-up check (one live slot, one step) reads sound, the check of
    what the window served does not."""
    import numpy as np
    from paddle_tpu.gen.predictor import GenPredictor
    # the scheduler's decode turn since PR 41: dispatched, then read one
    # turn later (the blocking ``decode_step`` is the set-up check's)
    dispatch, read_turn = GenPredictor.dispatch_turn, GenPredictor.read_turn
    live_of = {}

    def remember(self, tokens, positions, lens):
        read = dispatch(self, tokens, positions, lens)
        live_of[id(read)] = (read, np.flatnonzero(
            np.asarray(lens).reshape(-1) > 0))
        return read

    def mixed_up(self, read):
        ids, counts = read_turn(self, read)
        _, live = live_of.pop(id(read), (None, ()))
        if len(live) > 1:       # every live slot gets its neighbour's token
            ids = list(ids)
            for slot, token in zip(live, [ids[i] for i in np.roll(live, 1)]):
                ids[slot] = token
        return ids, counts

    monkeypatch.setattr(GenPredictor, "dispatch_turn", remember)
    monkeypatch.setattr(GenPredictor, "read_turn", mixed_up)
    seen = []
    real_check = served.check
    monkeypatch.setattr(served, "check", lambda *a, **k: seen.append(
        real_check(*a, **k)) or seen[-1])
    r = _rehearse("genlm_opt6.7b.decode_saturated", TOY_CLOSED, 0, 1.0)
    assert r["failed"] == 0 and not r["correct"]
    assert not seen[0]["served_ok"] and seen[0]["served_gap_of_range"] > 0.05


def test_the_control_is_not_correct(monkeypatch):
    """The reference in a precision below (its matrices kept in float8,
    bfloat16 activations) in the program's place, at a size a test run can hold: the widest gap
    of a token it puts first passes a limit that the sound program keeps
    (the cell's own limit is set from readings on the chip at the cell's
    size: PERF.md section 2)."""
    toy = {"config": TOY_CLOSED["config"],
           "workload": dict(TOY_CLOSED["workload"],
                            served_check={"streams": 400, "limit": 1e-5})}
    control, real_check = [], served.check

    def both(ctx, weights, finished, prompt_of):
        control.append(real_check(ctx, weights, finished, prompt_of,
                                  control="fp8"))
        return real_check(ctx, weights, finished, prompt_of)

    monkeypatch.setattr(served, "check", both)
    r = _rehearse("genlm_opt6.7b.decode_saturated", toy, 0, 3.0)
    assert r["correct"] and r["failed"] == 0
    assert control[0]["served_tokens"] > 500
    assert not control[0]["served_ok"]
    assert control[0]["served_gap_of_range"] > 1e-4


def test_only_the_adapter_names_the_model():
    for folder in ("traffic", "lib"):
        for name in sorted(os.listdir(os.path.join(BENCH, folder))):
            if not name.endswith(".py") or (folder, name) == \
                    ("lib", "counts.py"):
                continue
            with open(os.path.join(BENCH, folder, name)) as f:
                text = f.read()
            # train_steps.py names its own model (PERF.md section 7)
            assert "gen_lm" not in text and "genlm" not in text, name


def test_adapter_of_refuses_what_is_not_an_adapter(tmp_path):
    with open(os.path.join(BENCH, "configs", "genlm_opt6.7b.json")) as f:
        cfg = json.load(f)
    adapter = models.adapter_of(cfg)
    assert all(callable(getattr(adapter, n)) for n in models.INTERFACE)
    assert len(models.INTERFACE) == 7
    assert adapter.kv_bytes_per_row(cfg) == \
        counts.paged_attention_bytes_per_step(cfg, 1)
    assert adapter.decode_weight_bytes(cfg) == counts.genlm_weight_bytes(cfg)
    with pytest.raises(models.AdapterError, match="adapter"):
        models.adapter_of({k: v for k, v in cfg.items() if k != "adapter"})
    with pytest.raises(models.AdapterError, match="no model adapter"):
        models.adapter_of(dict(cfg, adapter="nothing_here"))
    (tmp_path / "half.py").write_text(
        "def bundle_key(cfg): return []\ndef export(path, cfg): pass\n")
    with pytest.raises(models.AdapterError, match="seeded_weights"):
        models.adapter_of(dict(cfg, adapter="half"), models_dir=str(tmp_path))


def test_hbm_roofline_of_a_decode_step_on_recorded_data():
    # the synthetic trace's one run of jit_step, [1000, 4000] ns, holds
    # the "flash" event: taken for the decode executable here
    with open(os.path.join(BENCH, "configs", "genlm_opt6.7b.json")) as f:
        cfg = json.load(f)
    _, run_dict = _recorded_run([], facts={"traced_live_rows": 3200,
                                           "traced_decode_steps": 2})
    run_dict.update(config=cfg, peaks=peaks.peaks_for("TPU v5 lite"))
    import run as harness
    spec = {"name": "decode_step_hbm_roofline", "unit": "%"}
    with open(os.path.join(BENCH, "layer_metrics",
                           "decode_step_hbm_roofline.json")) as f:
        events = json.load(f)["events"]
    reader = harness.load_module(os.path.join(
        BENCH, "layer_metrics", "decode_step_hbm_roofline.py"), "hbm_reader")
    assert reader.read(run_dict, dict(spec, events=events)) is None
    got = reader.read(run_dict, dict(spec, events=["flash"]))
    least = (counts.genlm_weight_bytes(cfg)
             + counts.paged_attention_bytes_per_step(cfg, 1600)) / 819e9
    assert got == pytest.approx(100 * least / 3000e-9)
    # the same reader under the closed-loop cell's name
    assert _read("decode_step_hbm_roofline.saturated", run_dict) is None


def test_a_failed_traced_stretch_keeps_the_measured_numbers(monkeypatch):
    import run
    real = run.load_module

    def load(path, name):
        module = real(path, name)
        if hasattr(module, "traced"):
            def traced(state, ctx):
                raise RuntimeError("the traced stretch broke")
            module.traced = traced
        return module

    monkeypatch.setattr(run, "load_module", load)
    r = _rehearse("transformer_base.train_b256_s256", TOY_TRANSFORMER,
                  2, 0.5)
    assert r["correct"] and set(r["metrics"]) == {
        "train_tokens_per_s_per_chip", "setup_s"}
    from paddle_tpu import profiler
    assert profiler._session is None


def test_a_replay_that_stalls_is_given_up(monkeypatch, capsys):
    import run
    real = run.load_module

    def load(path, name):
        module = real(path, name)
        if hasattr(module, "REPLAY_DRAIN_S"):
            stream = serving_rig._stream

            def stalling(client_cls, addr, ptrace, rid, *rest):
                if rid.startswith("trace-"):
                    time.sleep(30)
                return stream(client_cls, addr, ptrace, rid, *rest)
            monkeypatch.setattr(serving_rig, "_stream", stalling)
            module.REPLAY_DRAIN_S = 0.5
        return module

    monkeypatch.setattr(run, "load_module", load)
    t0 = time.perf_counter()
    r = _rehearse("genlm_opt6.7b.chat_open", TOY_GENLM, 2, 3.0)
    assert time.perf_counter() - t0 < 25     # did not wait the stall out
    assert r["correct"] and r["failed"] == 0
    assert {"gap_p95_ms", "out_tokens_per_s", "setup_s"} < set(r["metrics"])
    assert not SERVE_SPAN_METRICS & set(r["metrics"])
    assert "replayed requests failed" in capsys.readouterr().out


def test_no_chip_means_no_result_line(capsys):
    import run
    assert run.main(["--workload", "transformer_base.train_b256_s256",
                     "--seed", "1", "--seconds", "1", "--trace", "0"]) != 0
    assert capsys.readouterr().out.strip() == ""
