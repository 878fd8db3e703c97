"""The readers PR 35 adds, on hand-made runs (CPU, no protobuf needed:
``run["_scoped_planes"]`` is filled in as ``lib.decode_ops.scoped_planes``
would fill it).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_train_scope_metrics.py -q -p no:cacheprovider
"""

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

from lib import manifest  # noqa: E402

STEP = "jit(multi)/jit(main)/while/body/pt_step/"
PARTS = ["train_dropout_device_ms", "train_optimizer_device_ms",
         "train_attn_core_device_ms", "train_head_loss_device_ms",
         "train_dense_device_ms", "train_norm_residual_device_ms"]
DEVICE = PARTS + ["train_other_device_ms", "train_remat_device_ms"]
SPANS = ["executor_lookup_ms_per_step.train",
         "executor_state_ms_per_step.train",
         "executor_launch_ms_per_step.train",
         "executor_place_ms_per_step.train"]
MS = 1e6        # ns in a ms


def _leaves(scale=1.0):
    """One chip's leaf events ``(start_ns, end_ns, name, scope)``: a ms
    each times its position, so every group reads a different number."""
    rows = [
        ("fusion.1", STEP + "enc0/self_attn/proj/ptop_mul__q/dot"),        # 1 dense
        ("fusion.2", STEP + "enc0/self_attn/core/ptop_softmax__w/exp"),    # 2 core
        ("fusion.3", STEP + "enc0/self_attn/post/ptop_layer_norm__y/rsqrt"),  # 3 post
        ("fusion.4", STEP + "enc0/self_attn/post/ptop_dropout__d/mul"),    # 4 dropout
        ("fusion.5", STEP + "enc0/ffn/ptop_mul__h/dot"),                   # 5 dense
        ("fusion.6.remat", STEP + "bwd/enc0/ffn/post/ptop_layer_norm_grad__g/mul"),  # 6 post, remat
        ("fusion.7", STEP + "bwd/head/ptop_softmax_with_cross_entropy_grad__l/sub"),  # 7 head
        ("fusion.8", STEP + "opt/ptop_adam__w/sqrt"),                      # 8 optimizer
        ("fusion.9", STEP + "embed/ptop_lookup_table__e/gather"),          # 9 other
        ("copy.10", ""),                                                   # 10 other
        ("fusion.11", STEP + "bwd/enc0/self_attn/core/ptop_dropout_grad__w/mul"),  # 11 dropout
    ]
    out, t = [], 0.0
    for i, (name, scope) in enumerate(rows, start=1):
        out.append((t, t + i * MS * scale, name, scope))
        t += i * MS * scale
    return out


def _run(planes, busy_ms, steps=2, spans=()):
    return {"_scoped_planes": planes, "spans": list(spans), "counters": {},
            "facts": {"traced_steps": steps}, "chips": len(planes or ()) or 1,
            "trace": {"busy_s": busy_ms / 1e3} if planes else None,
            "trace_window_s": 1.0, "session": None}


def _read(name, run):
    import run as harness
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"]
                     if m["name"] == name)
    got = harness.read_layer_metrics([entry], run)
    return got[name]["value"] if got else None


def test_each_group_reads_its_own_ms_per_step():
    # two chips, the second twice as slow: the mean is 1.5 x, a step is
    # half of the two traced
    planes = {"/device:TPU:0": (_leaves(), []),
              "/device:TPU:1": (_leaves(2.0), [])}
    run = _run(planes, busy_ms=66 * 1.5)
    per = 1.5 / 2
    want = {"train_dropout_device_ms": (4 + 11) * per,
            "train_optimizer_device_ms": 8 * per,
            "train_attn_core_device_ms": 2 * per,
            "train_head_loss_device_ms": 7 * per,
            "train_dense_device_ms": (1 + 5) * per,
            "train_norm_residual_device_ms": (3 + 6) * per,
            "train_other_device_ms": (9 + 10) * per,
            "train_remat_device_ms": 6 * per}
    got = {name: _read(name, run) for name in DEVICE}
    assert got == {k: pytest.approx(v) for k, v in want.items()}
    # the six parts and the remainder are the step
    assert sum(got[n] for n in PARTS + ["train_other_device_ms"]) == \
        pytest.approx(66 * per)


def test_except_wins_over_events():
    # dropout inside post, and inside core, is dropout's alone; ffn/post
    # is post's and not the FFN's
    run = _run({"/device:TPU:0": (_leaves(), [])}, busy_ms=66, steps=1)
    assert _read("train_norm_residual_device_ms", run) == \
        pytest.approx(3 + 6)
    assert _read("train_attn_core_device_ms", run) == pytest.approx(2)
    assert _read("train_dense_device_ms", run) == pytest.approx(1 + 5)


def test_an_event_made_from_several_ops_counts_once_by_its_main_path():
    proj = STEP + "bwd/dec2/self_attn/proj/ptop_transpose_grad__r"
    core = STEP + "bwd/dec2/self_attn/core/ptop_matmul_grad__t"
    leaves = [(0.0, 2 * MS, "copy.1", f"{proj};{core};{core}:"),   # core
              (2 * MS, 5 * MS, "copy.2", f"{proj};{core}:"),        # a tie
              (5 * MS, 9 * MS, "copy.3", "")]                      # other
    run = _run({"/device:TPU:0": (leaves, [])}, busy_ms=9, steps=1)
    import run as harness
    reader = harness.load_module(os.path.join(
        BENCH, "layer_metrics", "train_scope_device_ms.py"), "scope_reader")
    assert reader.main_path(f"{proj};{core};{core}:") == core
    assert reader.main_path(f"{proj};{core}:") == proj
    assert reader.main_path("") == ""
    assert reader.group_seconds(
        run, {"events": ["/core/"], "except": []}) == pytest.approx(2e-3)
    assert reader.group_seconds(
        run, {"events": ["/proj/"], "except": ["/core/"]}) == \
        pytest.approx(3e-3)


def test_the_steps_share_of_the_peak_is_over_the_groups_step():
    """``train_step_mfu`` (PR 55) divides by the busy time the six parts
    and the remainder add up to: the step the groups split is the step
    whose share of the peak is claimed."""
    planes = {"/device:TPU:0": (_leaves(), []),
              "/device:TPU:1": (_leaves(2.0), [])}
    run = _run(planes, busy_ms=66 * 1.5)
    run["facts"].update(train_flops_per_token=1e6,
                        tokens_per_step_per_chip=65536)
    run["peaks"] = {"bf16_flops_per_s": 197e12}
    step_ms = sum(_read(n, run) for n in PARTS + ["train_other_device_ms"])
    assert _read("train_step_mfu", run) == pytest.approx(
        100 * 1e6 * 65536 / (197e12 * step_ms / 1e3))
    del run["facts"]["train_flops_per_token"]
    assert _read("train_step_mfu", run) is None


def test_no_recomputed_instruction_reads_zero_not_nothing():
    leaves = [ev for ev in _leaves() if ".remat" not in ev[2]]
    run = _run({"/device:TPU:0": (leaves, [])}, busy_ms=60, steps=1)
    assert _read("train_remat_device_ms", run) == 0.0
    assert _read("train_remat_device_ms", _run(None, busy_ms=0)) is None


def test_the_remainder_is_never_negative():
    # leaf events of one chip can overlap: their sum passes the union
    run = _run({"/device:TPU:0": (_leaves(), [])}, busy_ms=40, steps=1)
    assert _read("train_other_device_ms", run) == 0.0


def test_a_program_without_scopes_reports_nothing_it_lacks():
    """The parent of PR 35 writes ``ptop_`` scopes and no role or name
    scope: the groups that need them are absent, not 0; what a ``ptop_``
    scope or an instruction name alone decides is still read."""
    bare = [(s, e, name, scope.replace("bwd/", "").replace("opt/", "")
             .replace("enc0/self_attn/proj/", "").replace("enc0/ffn/", "")
             .replace("enc0/self_attn/core/", "").replace("post/", "")
             .replace("enc0/self_attn/", "").replace("head/", "")
             .replace("embed/", ""))
            for s, e, name, scope in _leaves()]
    run = _run({"/device:TPU:0": (bare, [])}, busy_ms=66, steps=1)
    assert _read("train_dropout_device_ms", run) == pytest.approx(15)
    assert _read("train_remat_device_ms", run) == pytest.approx(6)
    for name in PARTS[1:] + ["train_other_device_ms"]:
        assert _read(name, run) is None, name


@pytest.mark.parametrize("name", DEVICE + SPANS)
def test_every_reader_returns_none_without_a_device_trace(name):
    assert _read(name, _run(None, busy_ms=0)) is None
    no_steps = _run({"/device:TPU:0": (_leaves(), [])}, busy_ms=66,
                    steps=0)
    assert _read(name, no_steps) is None


def _span(name, ts, dur, parent=1):
    return {"name": name, "ts": ts, "dur": dur, "tid": 7, "span_id": id(name),
            "parent_id": parent, "trace_id": "t", "attrs": {}}


def test_dispatch_stretches_per_step():
    spans = [_span("executor.dispatch", 0.0, 0.030, None),
             _span("executor.lookup", 0.000, 0.012),
             _span("executor.state", 0.012, 0.006),
             _span("executor.place", 0.018, 0.004),
             _span("executor.launch", 0.022, 0.008)] * 2
    run = _run({"/device:TPU:0": (_leaves(), [])}, busy_ms=66, steps=16,
               spans=spans)
    assert _read("executor_lookup_ms_per_step.train", run) == \
        pytest.approx(24 / 16)
    assert _read("executor_state_ms_per_step.train", run) == \
        pytest.approx(12 / 16)
    assert _read("executor_place_ms_per_step.train", run) == \
        pytest.approx(8 / 16)
    assert _read("executor_launch_ms_per_step.train", run) == \
        pytest.approx(16 / 16)


def test_the_new_entries_and_their_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        good = manifest.validate(json.load(f))
    train = [w["name"] for w in good["workloads"]
             if w["config"] == "transformer_base"]
    by_name = {m["name"]: m for m in good["per_layer"]}
    for name in DEVICE + SPANS[:3]:
        assert by_name[name]["workloads"] == train, name
        assert by_name[name]["better"] == "lower"
        assert by_name[name]["unit"] == "ms"
    assert by_name[SPANS[3]]["workloads"] == \
        ["transformer_base.train_dp4_b1024_s256"]
    # in the order they were accepted in (where in the list is a later
    # PR's business)
    names = [m["name"] for m in good["per_layer"]]
    assert [n for n in names if n in DEVICE + SPANS] == DEVICE + SPANS
    with open(os.path.join(BENCH, "layer_metrics",
                           "train_other_device_ms.json")) as f:
        assert json.load(f)["minus"] == PARTS
