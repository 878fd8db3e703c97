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

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from lib import counts, manifest, openloop, peaks, stats, xtrace  # noqa: E402


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
    sys.path.insert(0, os.path.join(BENCH, "traffic"))
    import serve_open_loop as kind
    cfg = {"vocab_size": 512}
    assert kind._prompt(cfg, 5, 0, 16) == kind._prompt(cfg, 5, 0, 16)
    assert kind._prompt(cfg, 5, 0, 16) != kind._prompt(cfg, 6, 0, 16)


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
        for m in manifest.metrics_of(good, "per_layer", cell["name"]):
            with open(os.path.join(BENCH, "layer_metrics",
                                   m["name"] + ".json")) as f:
                spec = json.load(f)
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
], ids=["moves", "unit-spaces", "unit-greek", "cell-space", "cell-slash",
        "metric-cell", "stray-key", "bound", "cell-twice", "run-seconds",
        "too-many-four-chip"])
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


@pytest.mark.parametrize("trace", [0, 1])
def test_train_steps_rehearsal(trace):
    r = _rehearse("transformer_base.train_b256_s256", TOY_TRANSFORMER,
                  trace, 0.5)
    assert r["rehearsal"] and r["correct"] and r["failed"] == 0
    assert r["device"]["platform"] == "cpu"
    assert "busy_s" not in r["device"]
    if trace:       # no device trace on the CPU: no device metric at all
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


@pytest.mark.parametrize("trace", [0, 1])
def test_serve_open_loop_rehearsal(trace):
    r = _rehearse("genlm_opt6.7b.chat_open", TOY_GENLM, trace, 3.0)
    assert r["rehearsal"] and r["correct"] and r["failed"] == 0
    assert r["attempted"] == 12
    if trace:
        assert "paged_attn_roofline" not in r["metrics"]
        assert "decode_step_device_ms" not in r["metrics"]
        assert {"prefill_p50_ms", "decode_step_p50_ms", "queue_wait_p50_ms",
                "queue_wait_p95_ms", "executor_call_ms_per_step.serve"} \
            <= set(r["metrics"])
    else:
        assert {"gap_p95_ms", "out_tokens_per_s", "setup_s"} \
            < set(r["metrics"])
        assert any(n.startswith("ttft_") for n in r["metrics"])


def test_no_chip_means_no_result_line(capsys):
    import run
    assert run.main(["--workload", "transformer_base.train_b256_s256",
                     "--seed", "1", "--seconds", "1", "--trace", "0"]) != 0
    assert capsys.readouterr().out.strip() == ""
