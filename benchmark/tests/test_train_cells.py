"""What PR 55 changes in the training cells and in the traced stretch, on
hand-made runs (CPU, no chip, no protobuf):

- ``train_step_mfu``: the whole step's share of the chip's peak, over the
  device time ``train_step_device_ms`` reads;
- ``train_steps.verify``: ``correct`` names no lowering any more
  (``kernel_in_hlo`` is reported, the arithmetic is what is held);
- the traced stretch's drain follows the cell, and a ``trace_failed``
  line names the part that failed;
- the result's line ends with the numbers ``correct`` was decided from.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_train_cells.py -q -p no:cacheprovider
"""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from lib import counts, manifest, peaks, xtrace  # noqa: E402

TRAIN = ["transformer_base.train_b256_s256",
         "transformer_base.train_b32_s1024",
         "transformer_base.train_dp4_b1024_s256"]
V5E = peaks.peaks_for("TPU v5 lite")


@pytest.fixture(scope="module")
def good():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return manifest.validate(json.load(f))


def _entry(good, name):
    return next(m for m in good["per_layer"] if m["name"] == name)


def _read(good, name, run):
    import run as harness
    got = harness.read_layer_metrics([_entry(good, name)], run)
    return got[name]["value"] if got else None


def _run(busy_s=0.64, steps=2, per_token=100e6, tokens=65536, chips=1,
         peaks_row=V5E):
    facts = {"traced_steps": steps, "train_flops_per_token": per_token,
             "tokens_per_step_per_chip": tokens}
    return {"facts": {k: v for k, v in facts.items() if v is not None},
            "chips": chips, "peaks": peaks_row, "spans": [], "counters": {},
            "trace": {"busy_s": busy_s} if busy_s is not None else None}


# -- train_step_mfu ----------------------------------------------------------

def test_train_step_mfu_is_the_hand_count(good):
    # 2 steps of 65,536 tokens at 100 MFLOP a token in 0.64 s of device
    # time: 13.1072 TFLOP / (197 TFLOP/s x 0.64 s) = 10.3959...%
    want = 100 * (2 * 65536 * 100e6) / (197e12 * 0.64)
    assert _read(good, "train_step_mfu", _run()) == pytest.approx(want)
    assert want == pytest.approx(10.3959, rel=1e-4)


def test_train_step_mfu_and_the_step_time_share_one_denominator(good):
    with open(os.path.join(BENCH, "configs", "transformer_base.json")) as f:
        cfg = json.load(f)
    per_token = counts.transformer_train_flops_per_token(cfg, 256)
    run = _run(busy_s=2 * 0.32014, per_token=per_token)
    ms = _read(good, "train_step_device_ms", run)
    mfu = _read(good, "train_step_mfu", run)
    # to the last digit: what a reader of the line can recompute
    assert mfu == 100 * (per_token * 65536) / (197e12 * ms / 1e3)
    assert mfu == pytest.approx(33.6, abs=0.05)     # the ledger's PR 54 step


@pytest.mark.parametrize("gone", ["busy_s", "steps", "per_token", "tokens",
                                  "peaks_row"])
def test_train_step_mfu_reads_nothing_where_a_fact_is_missing(good, gone):
    assert _read(good, "train_step_mfu", _run(**{gone: None})) is None


def test_train_step_mfu_is_per_chip_whatever_the_chips(good):
    # busy_s is the mean over the cell's chips and the tokens are a
    # chip's: four chips read what one reads
    assert _read(good, "train_step_mfu", _run(chips=4)) == \
        _read(good, "train_step_mfu", _run(chips=1))


def test_the_count_is_the_algorithms():
    with open(os.path.join(BENCH, "layer_metrics",
                           "train_step_mfu.json")) as f:
        spec = json.load(f)
    for said in ("NOT discounted", "NOT counted", "per chip",
                 "paddle_tpu.models.transformer.train_flops_per_token"):
        assert said in spec["convention"]
    from paddle_tpu.models import transformer as T
    with open(os.path.join(BENCH, "configs", "transformer_base.json")) as f:
        cfg = json.load(f)
    for seq in (256, 1024):
        assert counts.transformer_train_flops_per_token(cfg, seq) == \
            T.train_flops_per_token(T.ModelHyperParams(), seq=seq)


# -- the manifest ------------------------------------------------------------

def test_train_step_mfu_lists_the_three_training_cells(good):
    entry = _entry(good, "train_step_mfu")
    assert entry["workloads"] == TRAIN
    assert [w["name"] for w in good["workloads"]
            if w["config"] == "transformer_base"] == TRAIN
    assert (entry["unit"], entry["better"], entry["source"]) == \
        ("%", "higher", "device_trace")
    assert entry["layer"] == _entry(good, "train_step_device_ms")["layer"]
    assert entry["moves"] == "train_tokens_per_s_per_chip"
    # the kernels' roofline keeps its one cell
    assert _entry(good, "flash_attn_roofline")["workloads"] == [TRAIN[1]]


@pytest.mark.parametrize("cell", TRAIN)
def test_every_training_cell_has_a_share_that_bounds_a_claim(good, cell):
    shares = [m["name"] for m in manifest.metrics_of(good, "per_layer", cell)
              if m["moves"] == "train_tokens_per_s_per_chip"
              and ("mfu" in m["name"] or "roofline" in m["name"])]
    assert "train_step_mfu" in shares


def test_no_workload_file_pins_a_lowering():
    folder = os.path.join(BENCH, "workloads")
    for name in sorted(os.listdir(folder)):
        with open(os.path.join(folder, name)) as f:
            assert "expect_kernel" not in json.load(f), name
    with open(os.path.join(BENCH, "traffic", "train_steps.py")) as f:
        text = f.read()
    assert "expect_kernel" not in text and "kernel_as_expected" not in text
    for cell in TRAIN:
        with open(os.path.join(folder, cell + ".json")) as f:
            why = json.load(f)["why"]
        for guard in ("reference_ok", "loss_falls", "no_flash_fallback",
                      "no_compile_in_window", "train_step_mfu"):
            assert guard in why, (cell, guard)


# -- verify ------------------------------------------------------------------

def _verify(runner="run_steps", rehearsal=False, **checks):
    import run as harness
    train_steps = harness.load_module(
        os.path.join(BENCH, "traffic", "train_steps.py"), "train_steps_t")
    sound = {"reference_ok": True, "reference_rel_err": 3e-5,
             "params_on_chip": True, "kernel_in_hlo": False,
             "distinct_devices": True, "all_reduce_in_hlo": True,
             "replicated_on": [0, 1, 2, 3]}
    means = checks.pop("means", [9.2, 9.1, 9.0, 8.9])
    counters = {"compile.events": 0, "compile_cache.misses": 0,
                "attention.flash_fallback": 0,
                **checks.pop("counters", {})}
    state = {"checks": {**sound, **checks},
             "warm_losses": np.asarray([means[0]])}
    ctx = {"rehearsal": rehearsal, "chips": 4 if runner == "parallel_run"
           else 1, "workload": {"runner": runner, "loss_rtol": 1e-3}}
    return train_steps.verify(state, ctx, {"window_means": means[1:],
                                           "counters": counters})


@pytest.mark.parametrize("runner", ["run_steps", "parallel_run"])
@pytest.mark.parametrize("kernel", [True, False, None])
def test_correct_names_no_lowering(kernel, runner):
    got = _verify(runner=runner, kernel_in_hlo=kernel)
    assert got["correct"] and "kernel_as_expected" not in got
    # still reported, on the verdict line and in the result's last key
    assert got["kernel_in_hlo"] is kernel
    assert got["compared"]["kernel_in_hlo"] == [kernel, None]


@pytest.mark.parametrize("broken, number", [
    (dict(reference_ok=False, reference_rel_err=0.02), "reference_rel_err"),
    (dict(means=[9.0, 9.1, 9.2, 9.3]), "loss_third_call_over_first"),
    (dict(counters={"attention.flash_fallback": 3}),
     "flash_fallbacks_in_window"),
    (dict(counters={"compile.events": 1}), "compiles_in_window"),
])
@pytest.mark.parametrize("kernel", [True, False])
def test_what_guards_the_arithmetic_still_fails_the_run(broken, number,
                                                        kernel):
    got = _verify(kernel_in_hlo=kernel, **broken)
    assert not got["correct"]
    reading, limit = got["compared"][number]
    assert reading > limit      # the number that failed, beside its limit


def test_a_rehearsal_does_not_ask_the_toy_for_a_falling_loss():
    assert _verify(rehearsal=True, means=[9.0, 9.1, 9.2, 9.3])["correct"]
    assert not _verify(rehearsal=False, means=[9.0, 9.1])["correct"]


# -- the result's line -------------------------------------------------------

def test_the_line_ends_with_what_was_compared(monkeypatch, capsys):
    import run as harness
    compared = {"reference_rel_err": [3e-5, 1e-3], "kernel_in_hlo": [True,
                                                                     None]}
    monkeypatch.setattr(harness, "run_cell", lambda *a, **k: {
        "correct": True, "attempted": 8, "failed": 0, "metrics": {},
        "device": {}, "compared": compared})
    assert harness.main(["--workload", TRAIN[0], "--seed", str(2 ** 31 + 7),
                         "--seconds", "1"]) == 0
    said = capsys.readouterr()
    line = json.loads(said.out.splitlines()[-1])
    assert list(line)[-1] == "compared" and line["compared"] == compared
    assert said.err.splitlines()[-2:] == [
        "compared reference_rel_err: 3e-05 limit 0.001",
        "compared kernel_in_hlo: True limit None"]


def test_a_rehearsed_run_carries_its_compared_numbers():
    import run as harness
    toy = {"config": dict(d_model=32, d_inner_hid=64, n_head=2, d_key=16,
                          d_value=16, n_layer=1, src_vocab_size=128,
                          trg_vocab_size=128),
           "workload": dict(batch=4, seq=16, steps_per_call=2,
                            staged_batches=2, loss_rtol=0.05, trace_calls=1)}
    r = harness.run_cell(TRAIN[0], 2 ** 31 + 9, 0.5, 2, rehearsal=toy)
    assert r["correct"] and list(r)[-1] == "compared"
    reading, limit = r["compared"]["reference_rel_err"]
    assert 0 <= reading <= limit == 0.05
    assert r["compared"]["compiles_in_window"] == [0, 0]
    assert "train_step_mfu" not in r["metrics"]     # no device trace here


# -- the traced stretch ------------------------------------------------------

def _closed_loop():
    import run as harness
    return harness.load_module(
        os.path.join(BENCH, "traffic", "serve_closed_loop.py"), "closed_t")


@pytest.mark.parametrize("cap, gap_ms, limit, want", [
    (1024, 7.0, 120, 40.0),                 # 10.8 s: the floor holds
    (1536, 15.4, 120, 40.0),                # 35.5 s: the floor holds
    (2048, 15.0, 120, 1.5 * 2048 * 0.0150),     # 46.1 s: follows the cell
    (2048, 50.0, 120, 120.0),               # never past the file's bound
    (2048, None, 120, 40.0),                # a window with no gap to read
    (2048, 15.0, 30, 30.0)])
def test_the_traced_drain_follows_the_cell(cap, gap_ms, limit, want):
    wl = {"output": {"cap": cap}, "drain_timeout_s": limit}
    assert _closed_loop().traced_drain_s(wl, gap_ms) == pytest.approx(want)


class _Tracer:
    def __init__(self, warm=None):
        self._warm = warm

    def warm(self):
        if self._warm:
            raise self._warm

    def stop(self):
        pass


class _Traffic:
    def __init__(self, error):
        self._error = error

    def traced(self, state, ctx):
        raise self._error


@pytest.mark.parametrize("tracer, error, part", [
    (_Tracer(), xtrace.TracePartFailed("drain", "1 of 65 requests failed"),
     "drain"),
    (_Tracer(xtrace.TracePartFailed("profiler", "OSError('no space')")), None,
     "profiler"),
    (_Tracer(), xtrace.TracePartFailed("reader", "x_roofline: KeyError()"),
     "reader"),
    (_Tracer(), ValueError("the traffic module's own"), "traffic")])
def test_a_failed_stretch_names_the_part(tracer, error, part, capsys):
    import run as harness
    assert harness.traced_stretch(_Traffic(error), {}, {}, tracer) is None
    said = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    failed = next(n for n in said if n["note"] == "trace_failed")
    assert failed["part"] == part and failed["where"] == "traced stretch"


def test_the_profilers_own_failure_is_the_profilers(monkeypatch, tmp_path):
    import run as harness
    from paddle_tpu import profiler

    def broken(**kwargs):
        raise OSError("the profiler broke")

    monkeypatch.setattr(profiler, "start_profiler", broken)
    tracer = harness.DeviceTracer(True, str(tmp_path / "trace"))
    for call in (tracer.start, tracer.warm):
        with pytest.raises(xtrace.TracePartFailed) as caught:
            call()
        assert caught.value.part == "profiler"


def test_a_reader_that_raises_is_named(good, monkeypatch):
    import run as harness
    real = harness.load_module

    def load(path, name):
        module = real(path, name)
        if path.endswith("train_step_mfu.py"):
            def read(run, spec):
                raise KeyError("busy_s")
            module.read = read
        return module

    monkeypatch.setattr(harness, "load_module", load)
    with pytest.raises(xtrace.TracePartFailed) as caught:
        harness.read_layer_metrics([_entry(good, "train_step_mfu")], _run())
    assert caught.value.part == "reader"
    assert "train_step_mfu" in str(caught.value)
