"""The cell ``nemotron3_super_ep8.decode_saturated`` and its adapter
``hybrid_moe``, rehearsed on the CPU at toy widths (never a device
metric): the configuration's published widths, the adapter's interface
and counts, the cell's own readers on recorded data, and one closed-loop
run through the serving rig.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
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

from lib import decode_ops, manifest, models, peaks  # noqa: E402

CELL = "nemotron3_super_ep8.decode_saturated"
NEW_METRICS = ["moe_experts_roofline", "ssm_update_roofline",
               "ssm_scan_roofline", "moe_device_share", "ssm_device_share",
               "moe_tokens_per_expert", "decode_step_touched_hbm_roofline"]


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs",
                           "nemotron3_super_ep8.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def good():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_cell_is_in_the_manifest_with_its_metrics(good):
    manifest.validate(good)
    entry, config, workload = manifest.cell_files(good, CELL)
    assert entry["chips"] == 1 and config["name"] == "nemotron3_super_ep8"
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    # (by name, not by count: later cells go behind this one)
    assert sum(w["name"] == CELL for w in good["workloads"]) == 1
    assert sum(w["chips"] == 4 for w in good["workloads"]) == 1
    mine = {m["name"] for m in manifest.metrics_of(good, "per_layer", CELL)}
    assert set(NEW_METRICS) <= mine
    assert "decode_step_hbm_roofline.saturated" not in mine
    assert {m["name"] for m in manifest.metrics_of(good, "end_to_end", CELL)} \
        == {"saturated_tokens_per_s", "gap_p99_ms", "setup_s"}
    with open(os.path.join(ROOT, workload)) as f:
        wl = json.load(f)
    assert wl["kind"] == "serve_closed_loop" and wl["clients"] == 32
    assert wl["prompt"] == {"median": 256, "sigma": 0.7, "min": 32,
                            "cap": 1024}
    assert wl["output"] == {"median": 384, "sigma": 0.5, "min": 48,
                            "cap": 768}
    assert wl["sample_seed"] == 22 and wl["served_check"]["streams"] == 8
    assert wl["reference_prompts"] == [40, 200, 700]


def test_every_published_width_is_unchanged(cfg):
    want = dict(hidden_size=4096, mamba_num_heads=128, mamba_head_dim=64,
                ssm_state_size=128, n_groups=8, conv_kernel=4,
                chunk_size=128, num_attention_heads=32,
                num_key_value_heads=2, head_dim=128, moe_latent_size=1024,
                moe_intermediate_size=2688,
                moe_shared_expert_intermediate_size=5376,
                n_routed_experts=512, num_experts_per_tok=22,
                routed_scaling_factor=5, expand=2)
    assert {k: cfg[k] for k in want} == want
    assert cfg["hybrid_override_pattern"] == "EMEMEMEMEM*"
    assert cfg["published"]["hybrid_override_pattern"][26:37] == \
        cfg["hybrid_override_pattern"]
    assert len(cfg["hybrid_override_pattern"]) == cfg["num_hidden_layers"]
    assert cfg["experts_held"] * 8 == cfg["n_routed_experts"]
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["serving"]["num_slots"] == 32
    for key in ("assumed", "departures", "memory", "deployment",
                "published", "reduced"):
        assert cfg[key]


def test_the_adapter_has_the_interface_and_its_counts(cfg):
    adapter = models.adapter_of(cfg)
    assert all(callable(getattr(adapter, n)) for n in models.INTERFACE)
    # 2 x 1024 x 2688 bfloat16 = 11.0 MB an expert
    assert adapter.expert_bytes(cfg) == 2 * 1024 * 2688 * 2
    # one attention layer, 2 K/V heads of 128, float32 pool
    assert adapter.kv_bytes_per_row(cfg) == 2 * 256 * 4
    # 5 mixers x (128 x 64 x 128 + 3 x 10240) float32 = 21.6 MB a slot
    assert adapter.ssm_state_bytes_per_slot(cfg) == \
        5 * (128 * 64 * 128 + 3 * 10240) * 4
    # 2.75 B parameters in bfloat16, less the embedding (read by row)
    total = adapter.decode_weight_bytes(cfg) + 16384 * 4096 * 2
    assert 5.45e9 < total < 5.55e9
    assert adapter.ssm_scan_flops(cfg, 2) == 2 * adapter.ssm_scan_flops(cfg, 1)
    assert adapter.bundle_key(cfg)[1] == cfg["serving"]


def test_the_reference_imports_no_program():
    with open(os.path.join(BENCH, "reference", "hybrid_moe_ref.py")) as f:
        text = f.read()
    assert "import paddle_tpu" not in text and "from paddle_tpu" not in text


def test_the_harness_still_names_no_model():
    for folder in ("traffic", "lib"):
        for name in sorted(os.listdir(os.path.join(BENCH, folder))):
            if name.endswith(".py"):
                with open(os.path.join(BENCH, folder, name)) as f:
                    text = f.read()
                assert "hybrid_moe" not in text and "nemotron" not in text, \
                    name


# -- the cell's own readers on recorded data ----------------------------------

def _span(name, attrs, ts=0.0):
    return {"name": name, "trace_id": "t", "span_id": 1, "parent_id": None,
            "ts": ts, "dur": 1e-6, "tid": 7, "attrs": attrs}


# (line, name, scope, start_ns, end_ns): a decode run [1000, 4000] holding
# the paged kernel, a prefill run [5000, 7000], and a ``while`` around the
# update's body (only leaves count)
EVENTS = [
    ("XLA Modules", "jit_step", "", 1000, 4000),
    ("XLA Modules", "jit_step", "", 5000, 7000),
    ("XLA Ops", "%fusion.1 = bf16[32,1024] fusion(...)",
     "jit(step)/pt_step/ptop_moe_experts__tmp_3/etf,efl->tl/dot_general:",
     1100, 1600),
    ("XLA Ops", "%while.2 = (f32[8]) while(...)", "", 1700, 2300),
    ("XLA Ops", "%fusion.2 = f32[32,128,64,128] fusion(...)",
     "jit(step)/pt_step/ptop_ssm_update__tmp_9/mul:", 1700, 2000),
    ("XLA Ops", "%fusion.3 = f32[32,3,10240] fusion(...)",
     "jit(step)/pt_step/ptop_ssm_update_conv__tmp_8/add:", 2000, 2300),
    ("XLA Ops", "%ptop_paged_attention__tmp_5.1 = bf16[32,1,4096] "
     "custom-call(...)", "jit(step)/pt_step/ptop_paged_attention__tmp_5:",
     2400, 2500),
    ("XLA Ops", "%fusion.7 = bf16[32,4096] fusion(...)",
     "jit(step)/pt_step/ptop_matmul__tmp_6/dot_general:", 2600, 3800),
    ("XLA Ops", "%fusion.8 = f32[1,128,64,128] fusion(...)",
     "jit(step)/pt_step/ptop_ssm_scan__tmp_2/cumsum:", 5100, 5900),
    ("XLA Ops", "%fusion.1 = bf16[256,1024] fusion(...)",
     "jit(step)/pt_step/ptop_moe_experts__tmp_3/etf,efl->tl/dot_general:",
     6000, 6500),
    ("XLA Ops", "%fusion.9 = f32[1,512] fusion(...)",
     "jit(step)/pt_step/ptop_moe_route__tmp_1/top_k:", 6500, 6600),
]
BUSY_S = (500 + 600 + 100 + 1200 + 800 + 500 + 100) / 1e9


@pytest.fixture(scope="module")
def xplane(tmp_path_factory):
    """A hand-built XSpace whose events carry their op scope as the chip's
    do: in the ``tf_op`` stat of the event's metadata."""
    space = decode_ops._xplane_pb2().XSpace()
    plane = space.planes.add(name="/device:TPU:0")
    plane.stat_metadata[1].name = "tf_op"
    lines, metas = {}, {}
    for line, name, scope, start, end in EVENTS:
        if line not in lines:
            lines[line] = plane.lines.add(name=line, timestamp_ns=0)
        if (name, scope) not in metas:
            metas[name, scope] = len(metas) + 1
            meta = plane.event_metadata[metas[name, scope]]
            meta.id, meta.name = metas[name, scope], name
            if scope:
                meta.stats.add(metadata_id=1, str_value=scope)
        lines[line].events.add(metadata_id=metas[name, scope],
                               offset_ps=start * 1000,
                               duration_ps=(end - start) * 1000)
    path = str(tmp_path_factory.mktemp("xplane") / "toy.xplane.pb")
    with open(path, "wb") as f:
        f.write(space.SerializeToString())
    return path


def _run(cfg, spans, xplane=None):
    return {"spans": spans, "facts": {}, "counters": {}, "chips": 1,
            "trace": {"busy_s": BUSY_S} if xplane else None,
            "trace_window_s": 7e-6, "config": cfg,
            "peaks": peaks.peaks_for("TPU v5 lite"),
            "session": {"xplane": xplane} if xplane else None}


def _reader(name):
    import run as harness
    folder = os.path.join(BENCH, "layer_metrics")
    with open(os.path.join(folder, name + ".json")) as f:
        spec = json.load(f)
    reads_as = spec.get("like", name)
    if "like" in spec:
        with open(os.path.join(folder, reads_as + ".json")) as f:
            spec = {**json.load(f), **spec}
    module = harness.load_module(os.path.join(folder, reads_as + ".py"),
                                 "t_" + name)
    return module, spec


STEPS = [_span("gen.decode_step", {"live": 32, "moe_assignments": 440,
                                   "moe_experts_touched": 240,
                                   "moe_max_load": 5}),
         _span("gen.decode_step", {"live": 30, "moe_assignments": 400,
                                   "moe_experts_touched": 200,
                                   "moe_max_load": 4}),
         _span("gen.prefill", {"tokens": 300})]


def test_the_raw_trace_gives_every_leaf_its_scope(xplane):
    planes = decode_ops.scoped_planes({"session": {"xplane": xplane},
                                       "chips": 1})
    leaves, modules = planes["/device:TPU:0"]
    assert modules == [(1000.0, 4000.0), (5000.0, 7000.0)]
    assert [e[2] for e in leaves if "ssm_update" in e[3]] == \
        ["fusion.2", "fusion.3"]
    assert not [e for e in leaves if e[2].startswith("while")]


def test_a_trace_that_cannot_be_read_raises(xplane, monkeypatch):
    """An installation without the protobuf module must not yield a line
    that silently lacks the metrics: the harness turns the error into a
    ``trace_failed`` note."""
    import importlib.util
    decode_ops._xplane_pb2.cache_clear()
    monkeypatch.setattr(importlib.util, "find_spec", lambda name: None)
    try:
        with pytest.raises(RuntimeError, match="xplane_pb2"):
            decode_ops.scoped_planes({"session": {"xplane": xplane},
                                      "chips": 1})
        # no trace at all (the parent's program, --trace 0): nothing, quietly
        assert decode_ops.scoped_planes({"session": None}) is None
    finally:
        decode_ops._xplane_pb2.cache_clear()


@pytest.mark.parametrize("name,seconds,per_run", [
    # the prefill's experts (6000-6500) lie outside the decode run
    ("moe_experts_roofline", 500e-9, lambda a, c: 220 * a.expert_bytes(c)),
    # update + its conv
    ("ssm_update_roofline", 600e-9,
     lambda a, c: 31 * 2 * a.ssm_state_bytes_per_slot(c))])
def test_decode_rooflines_on_recorded_data(cfg, xplane, name, seconds,
                                           per_run):
    module, spec = _reader(name)
    adapter = models.adapter_of(cfg)
    least = per_run(adapter, cfg) / 819e9            # one decode run
    assert module.read(_run(cfg, STEPS, xplane), spec) == pytest.approx(
        100.0 * least / seconds)
    # an op the trace does not hold, spans without the attribute, no trace
    assert module.read(_run(cfg, STEPS, xplane),
                       dict(spec, events=["nothing_here"])) is None
    assert module.read(_run(cfg, [_span("gen.decode_step", {})], xplane),
                       spec) is None
    assert module.read(_run(cfg, STEPS), spec) is None


def test_scan_roofline_on_recorded_data(cfg, xplane):
    module, spec = _reader("ssm_scan_roofline")
    adapter = models.adapter_of(cfg)
    least = max(adapter.ssm_scan_flops(cfg, 300) / 197e12,
                adapter.ssm_scan_bytes(cfg, 300) / 819e9)
    assert module.read(_run(cfg, STEPS, xplane), spec) == pytest.approx(
        100.0 * least / 800e-9)
    assert module.read(_run(cfg, STEPS[:2], xplane), spec) is None


def test_device_shares_on_recorded_data(cfg, xplane):
    for name, seconds in (("moe_device_share", 500 + 500 + 100),
                          ("ssm_device_share", 600 + 800)):
        module, spec = _reader(name)
        assert module.read(_run(cfg, STEPS, xplane), spec) == \
            pytest.approx(100.0 * seconds / 1e9 / BUSY_S)
        assert module.read(_run(cfg, STEPS), spec) is None


def test_whole_step_roofline_counts_what_the_step_touched(cfg):
    """Least bytes = the matrices outside the routed experts + the touched
    experts + the live slots' state twice + the live K/V rows, over the
    mean run of the decode executable (the synthetic trace's, as
    ``decode_step_hbm_roofline`` reads it)."""
    from lib import xtrace
    module, spec = _reader("decode_step_touched_hbm_roofline")
    adapter = models.adapter_of(cfg)
    synthetic = os.path.join(HERE, "data", "synthetic.xplane.pb")
    run = _run(cfg, STEPS, synthetic)
    run["trace"] = xtrace.summarize(synthetic, 1)
    run["facts"] = {"traced_live_rows": 2 * 31 * 500,
                    "traced_decode_steps": 2}
    # the synthetic trace's one run of jit_step, [1000, 4000] ns, holds a
    # "flash" event: taken for the decode executable here
    assert module.read(run, spec) is None
    spec = dict(spec, events=["flash"])
    held = 5 * 64 * adapter.expert_bytes(cfg)
    least = (adapter.decode_weight_bytes(cfg) - held
             + 220 * adapter.expert_bytes(cfg)
             + 2 * 31 * adapter.ssm_state_bytes_per_slot(cfg)
             + 31 * 500 * adapter.kv_bytes_per_row(cfg)) / 819e9
    assert adapter.decode_step_bytes(cfg, 320, 31, 0) == \
        adapter.decode_weight_bytes(cfg) \
        + 2 * 31 * adapter.ssm_state_bytes_per_slot(cfg)
    assert module.read(run, spec) == pytest.approx(100.0 * least / 3000e-9)
    # spans without the attributes (the parent's), or no trace: nothing
    bare = dict(run, spans=[_span("gen.decode_step", {})])
    assert module.read(bare, spec) is None
    assert module.read(dict(run, trace=None), spec) is None


def test_tokens_per_expert_on_recorded_data(cfg):
    module, spec = _reader("moe_tokens_per_expert")
    assert module.read(_run(cfg, STEPS), spec) == pytest.approx(840 / 440)
    assert module.read(_run(cfg, []), spec) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_readers_read_nothing_from_a_program_without_them(cfg, name):
    """The parent's spans and trace: no such attribute, no such scope."""
    import run as harness
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"]
                     if m["name"] == name)
    synthetic = os.path.join(HERE, "data", "synthetic.xplane.pb")
    parent = _run(cfg, [_span("gen.decode_step", {}),
                        _span("gen.prefill", {"tokens": 9})], synthetic)
    assert harness.read_layer_metrics([entry], parent) == {}
    assert harness.read_layer_metrics([entry], _run(cfg, [])) == {}


# -- one closed-loop run at toy widths ------------------------------------------

TOY = {"config": dict(
    name="toy_hybrid", hidden_size=64, vocab_size=256,
    hybrid_override_pattern="EM*", num_hidden_layers=3,
    mamba_num_heads=4, mamba_head_dim=16, n_groups=2, ssm_state_size=16,
    chunk_size=16, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, n_routed_experts=16, num_experts_per_tok=4,
    moe_latent_size=32, moe_intermediate_size=48,
    moe_shared_expert_intermediate_size=96, experts_held=8, expert_offset=0,
    serving=dict(num_slots=4, max_len=128, page_len=16,
                 prompt_buckets=[32, 64], page_buckets="default")),
    "workload": dict(clients=4,
                     prompt=dict(median=16, sigma=0.8, min=2, cap=64),
                     output=dict(median=8, sigma=0.5, min=2, cap=24),
                     reference_prompts=[10, 40], trace_seconds=0.5,
                     logits_tol=0.08, served_check=dict(streams=8,
                                                        limit=0.2))}


@pytest.mark.parametrize("trace", [0, 2])
def test_the_cell_rehearses_through_the_serving_rig(trace, capsys):
    import run
    r = run.run_cell(CELL, 2 ** 31 + 5, 3.0, trace, rehearsal=TOY)
    notes = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    said = next(n for n in notes if n["note"] == "verdict")
    assert r["rehearsal"] and r["correct"] and r["failed"] == 0, said
    assert {"saturated_tokens_per_s", "gap_p99_ms", "setup_s"} \
        <= set(r["metrics"])
    seen = next(n for n in notes if n["note"] == "observed")
    # several clients' streams at once: a loop of ONE client cannot pass 1.
    # (No higher: on the CPU a toy's stream of 2-24 tokens of sub-ms steps
    # lives about as long as its client's next request takes to be sent
    # and admitted, so the mean reads 2.2-2.7 of 4 since PR 41.)
    assert seen["slot_occupancy_mean"] > 1.5
    assert next(n for n in notes if n["note"] == "served")["served_ok"]
    for name in ("moe_experts_roofline", "ssm_update_roofline",
                 "ssm_scan_roofline", "moe_device_share",
                 "ssm_device_share", "paged_attn_roofline.saturated"):
        assert name not in r["metrics"]     # no device trace on the CPU
    if trace:
        # half the experts are held: about half of 4 x top-4 land
        assert 1.0 <= r["metrics"]["moe_tokens_per_expert"]["value"] < 4.0
        assert {"decode_step_p50_ms.saturated", "prefill_p50_ms.saturated",
                "seed_slot_p50_ms.saturated"} <= set(r["metrics"])
    else:
        assert set(r["metrics"]) == {"saturated_tokens_per_s", "gap_p99_ms",
                                     "setup_s"}
