"""The cell ``mimo_v2_flash.mixed_saturated`` and its adapter
``mimo_v2_flash``, rehearsed on the CPU at toy widths (never a device
metric): the configuration's published widths and the cut's arithmetic,
the adapter's interface and counts, the cell's own readers on recorded
data, and one closed-loop run through the serving rig over both kinds of
cache.

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

from lib import manifest, models  # noqa: E402

CELL = "mimo_v2_flash.mixed_saturated"
NEW_METRICS = ["window_attn_device_share", "window_decode_roofline",
               "window_prefill_roofline", "gqa_prefill_roofline",
               "kv_rows_read_share"]
SHARED_METRICS = [
    "decode_step_p50_ms.saturated", "decode_step_device_ms.saturated",
    "executor_call_ms_per_step.saturated",
    "executor_self_ms_per_step.saturated",
    "sched_self_ms_per_iteration.saturated", "prefill_p50_ms.saturated",
    "seed_slot_p50_ms.saturated", "slot_occupancy_mean.saturated",
    "idle_named_share.saturated", "paged_attn_roofline.saturated",
    "moe_experts_roofline", "moe_device_share", "moe_tokens_per_expert",
    "decode_step_touched_hbm_roofline"]


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs", "mimo_v2_flash.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def good():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_cell_is_in_the_manifest_with_its_metrics(good):
    manifest.validate(good)
    entry, config, workload = manifest.cell_files(good, CELL)
    assert entry["chips"] == 1 and config["name"] == "mimo_v2_flash"
    assert entry["traffic"] == "mixed_saturated"
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert sum(w["name"] == CELL for w in good["workloads"]) == 1
    assert sum(c["name"] == "mimo_v2_flash" for c in good["configs"]) == 1
    mine = {m["name"] for m in manifest.metrics_of(good, "per_layer", CELL)}
    # at least these: a later tracing PR gives the cell more
    assert set(NEW_METRICS + SHARED_METRICS) <= mine
    # latent attention and a learned selection are other models'
    assert not {m for m in mine if m.startswith(("mla_", "dsa_", "ssm_"))}
    assert {m["name"] for m in manifest.metrics_of(good, "end_to_end", CELL)} \
        == {"saturated_tokens_per_s", "gap_p99_ms", "setup_s"}
    for name in NEW_METRICS:
        entry = next(m for m in good["per_layer"] if m["name"] == name)
        # (this cell first: a later model with windows lists itself behind)
        assert entry["workloads"][0] == CELL and entry["unit"] == "%"
        assert entry["moves"] == "saturated_tokens_per_s"
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".json"))
    with open(os.path.join(ROOT, workload)) as f:
        wl = json.load(f)
    assert wl["kind"] == "serve_closed_loop" and wl["clients"] == 32
    assert wl["prompt"] == {"median": 2048, "sigma": 1.1, "min": 256,
                            "cap": 16384}
    assert wl["output"] == {"median": 1024, "sigma": 0.5, "min": 128,
                            "cap": 2048}
    assert wl["sample_seed"] == 40 and wl["trace_seconds"] == 5.0
    assert wl["reference_prompts"] == [300, 1900, 9000]
    for key in ("why", "clients_why", "lengths_why", "logits_tol_why",
                "served_check_why"):
        assert wl[key] and "TO FILL" not in wl[key], key


def test_every_published_key_is_unchanged_but_the_reduced(cfg):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(l) for l in f if l.strip()]
    published = next(r for r in rows if r["name"] == "MiMo-V2-Flash")
    assert cfg["source"] == published["source_url"]
    changed = {"num_hidden_layers": 7, "vocab_size": 19072}
    for key, value in published["config"].items():
        if key in changed:
            assert cfg[key] == changed[key] and key in cfg["reduced"]
            assert cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    assert cfg["experts_held"] * 32 == cfg["n_routed_experts"] == 256
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["layer_offset"] == 0
    assert cfg["hybrid_layer_pattern"][:7] == [0, 1, 1, 1, 1, 0, 1]
    assert cfg["moe_layer_freq"][:7] == [0, 1, 1, 1, 1, 1, 1]
    assert len(cfg["hybrid_layer_pattern"]) == 48       # kept whole
    assert cfg["ring"] >= cfg["sliding_window"] == 128
    assert cfg["key_head_stored"] == 256 > cfg["head_dim"] == 192
    sv = cfg["serving"]
    assert (sv["num_slots"], sv["max_len"], sv["page_len"]) == (32, 18432, 64)
    assert sv["prompt_buckets"] == [512, 1024, 2048, 4096, 8192, 16384]
    assert sv["page_buckets"][-1] * sv["page_len"] == sv["max_len"]
    for key in ("assumed", "departures", "memory", "deployment",
                "published", "reduced", "reduced_how", "serving_why"):
        assert cfg[key] and "TO FILL" not in json.dumps(cfg[key]), key
    for key in ("attention_value_scale", "sink", "window",
                "attention_chunk_size", "rotary", "router", "attention"):
        assert key in cfg["assumed"], key
    listed = " ".join(cfg["departures"])
    for word in ("multi-token-prediction", "262144", "STORED 256", "RING"):
        assert word in listed, word


def test_the_samples_lengths_are_the_issues(cfg):
    from lib import closedloop
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        wl = json.load(f)
    sample = closedloop.Sample(wl)
    prompts = [sample.take()["prompt_len"] for _ in range(4000)]
    share = lambda keep: sum(map(keep, prompts)) / len(prompts)
    assert min(prompts) == 256 > cfg["sliding_window"]
    assert max(prompts) == 16384 == cfg["serving"]["prompt_buckets"][-1]
    assert 0.08 < share(lambda n: n < 512) < 0.12
    assert 0.24 < share(lambda n: n < 1024) < 0.29
    assert 0.24 < share(lambda n: n > 4096) < 0.29
    assert 0.08 < share(lambda n: n > 8192) < 0.12
    assert 0.02 < share(lambda n: n == 16384) < 0.04
    assert 3300 < sum(prompts) / len(prompts) < 3700
    # every reference prompt is past the window, none a multiple of the
    # ring, each in a bucket of its own
    buckets = cfg["serving"]["prompt_buckets"]
    refs = wl["reference_prompts"]
    assert all(n > cfg["sliding_window"] and n % cfg["ring"] for n in refs)
    assert len({min(b for b in buckets if b >= n) for n in refs}) == 3


def test_the_adapter_has_the_interface_and_the_issues_counts(cfg):
    adapter = models.adapter_of(cfg)
    assert all(callable(getattr(adapter, n)) for n in models.INTERFACE)
    # the issue's arithmetic: a full layer's attention 89.13M, a window
    # layer's 94.37M, an expert 25.17M
    assert adapter.attention_params(cfg, 0) == 4096 * 64 * 192 \
        + 4096 * 4 * (192 + 128) + 64 * 128 * 4096
    assert round(adapter.attention_params(cfg, 0) / 1e6, 2) == 89.13
    assert round(adapter.attention_params(cfg, 1) / 1e6, 2) == 94.37
    assert adapter.expert_bytes(cfg) == 3 * 4096 * 2048 * 2
    assert adapter.full_layers(cfg) == [0, 5]
    assert adapter.window_layers(cfg) == [1, 2, 3, 4, 6]
    assert adapter.moe_layers(cfg) == [1, 2, 3, 4, 5, 6]
    # 2.22B parameters = 4.44 GB
    assert round(adapter.param_count(cfg) / 1e9, 2) == 2.22
    assert 4.43e9 < 2 * adapter.param_count(cfg) < 4.45e9
    # as STORED: a key head 256 lanes wide
    assert adapter.kv_bytes_per_row(cfg) == 2 * 4 * (256 + 128) * 2
    assert adapter.window_bytes_per_row(cfg) == 8 * (256 + 128) * 2
    assert adapter.window_flops_per_row(cfg) == 2 * 64 * (192 + 128)
    assert adapter.band_flops_per_pair(cfg) == 5 * 2 * 64 * (192 + 128)
    assert adapter.causal_flops_per_pair(cfg) == 2 * 2 * 64 * (192 + 128)
    held = 6 * 8 * adapter.expert_bytes(cfg)
    base = adapter.decode_weight_bytes(cfg) - held
    assert adapter.decode_step_bytes(cfg, 0, 32, 0) == base
    # 32 slots of 4,100 rows: the full layers read every row, the five
    # window layers 128 a slot
    assert adapter.decode_step_bytes(cfg, 40, 32, 131200) == base \
        + 40 * adapter.expert_bytes(cfg) + 131200 * 6144 \
        + 32 * 128 * 5 * 6144
    # streams short of the window: every live row in both kinds
    assert adapter.decode_step_bytes(cfg, 0, 32, 1000) == base \
        + 1000 * (6144 + 5 * 6144)
    assert adapter.bundle_key(cfg)[1] == cfg["serving"]


def test_the_reference_imports_no_program():
    path = os.path.join(BENCH, "reference", "mimo_v2_flash_ref.py")
    with open(path) as f:
        text = f.read()
    assert "import paddle_tpu" not in text and "from paddle_tpu" not in text
    assert '"highest"' in text and "pallas" not in text
    # the adapter's first import is what the parent lacks
    with open(os.path.join(BENCH, "models", "mimo_v2_flash.py")) as f:
        imports = [l for l in f.read().splitlines()
                   if l.startswith(("import ", "from "))]
    assert imports[1] == "from paddle_tpu.ops import window_ops  # noqa: F401"


def test_the_harness_still_names_no_model():
    for folder in ("traffic", "lib"):
        for name in sorted(os.listdir(os.path.join(BENCH, folder))):
            if name.endswith(".py"):
                with open(os.path.join(BENCH, folder, name)) as f:
                    text = f.read()
                assert "mimo" not in text and "window_moe" not in text, name


# -- the cell's own readers on recorded data ----------------------------------

def _reader(name):
    """A metric's reader and spec as ``run.read_layer_metrics`` finds
    them (``"like"``: another metric's reader, this one's parameters)."""
    import run as harness
    folder = os.path.join(BENCH, "layer_metrics")
    with open(os.path.join(folder, name + ".json")) as f:
        spec = json.load(f)
    reads_as = spec.get("like", name)
    if "like" in spec:
        with open(os.path.join(folder, reads_as + ".json")) as f:
            spec = {**json.load(f), **spec}
    return harness.load_module(os.path.join(folder, reads_as + ".py"),
                               "layer_metric_test_" + name), spec


def test_the_new_readers_on_recorded_data(cfg, monkeypatch):
    from lib import decode_ops, peaks
    # 100 steps of 32 slots of 4,100 rows; 3 admissions of 2,000 rows
    live = 32 * 4100
    steps = [{"name": "gen.decode_step", "attrs": {
        "full_rows": 2 * live, "window_rows": 5 * 32 * 128,
        "all_rows": 7 * live, "ring_bytes": 32 * 128 * 5 * 6144}}] * 100
    n = 2000
    band, causal = 128 * 129 // 2 + (n - 128) * 128, n * (n + 1) // 2
    admissions = [{"name": "gen.prefill", "attrs": {
        "tokens": n, "band_pairs": band, "causal_pairs": causal}}] * 3
    run = {"config": cfg, "peaks": peaks.PEAKS["TPU v5 lite"],
           "spans": steps + admissions}
    # 100 traced runs of the decode executable, 3 of a prefill's
    monkeypatch.setattr(
        decode_ops, "op_seconds_in_runs", lambda run, events, holding:
        (0.025, 100) if "step" in events[0] else (0.004, 3))
    module, spec = _reader("window_decode_roofline")
    # memory-bound: 6144 B a row at 819 GB/s against 41 kFLOP at 197 T
    assert module.read(run, spec) == pytest.approx(
        100.0 * 100 * 5 * 32 * 128 * 6144 / 819e9 / 0.025)
    assert spec["events"] == ["ptop_window_attention_step"]
    module, spec = _reader("window_prefill_roofline")
    assert module.read(run, spec) == pytest.approx(
        100.0 * 3 * band * 5 * 2 * 64 * 320 / 197e12 / 0.004)
    # the prefill's scope, and not the decode step's
    assert spec["events"] == ["ptop_window_attention__"]
    module, spec = _reader("gqa_prefill_roofline")
    assert module.read(run, spec) == pytest.approx(
        100.0 * 3 * causal * 2 * 2 * 64 * 320 / 197e12 / 0.004)
    module, spec = _reader("kv_rows_read_share")
    assert module.read(run, spec) == pytest.approx(
        100.0 * (2 * live + 5 * 32 * 128) / (7 * live))
    assert 30 < module.read(run, spec) < 32
    # no share passes 100 while the device takes its least time or more
    for name in NEW_METRICS[1:]:
        module, spec = _reader(name)
        assert 0 < module.read(run, spec) <= 100.0, name
    # no such scope in the trace, or no such attribute on the spans
    monkeypatch.setattr(decode_ops, "op_seconds_in_runs",
                        lambda run, events, holding: None)
    for name in ("window_decode_roofline", "window_prefill_roofline",
                 "gqa_prefill_roofline"):
        module, spec = _reader(name)
        assert module.read(run, spec) is None
    module, spec = _reader("kv_rows_read_share")
    assert module.read(dict(run, spans=[{"name": "gen.decode_step",
                                         "attrs": {"live": 16}}]),
                       spec) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_readers_read_nothing_from_a_program_without_them(cfg, name):
    """The parent's spans and trace: no such scope, no such attribute."""
    import run as harness
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"]
                     if m["name"] == name)
    synthetic = os.path.join(HERE, "data", "synthetic.xplane.pb")
    parent = {"config": cfg, "facts": {"traced_live_rows": 500,
                                       "traced_decode_steps": 2},
              "spans": [{"name": "gen.decode_step", "attrs": {"live": 16}},
                        {"name": "gen.prefill", "attrs": {"tokens": 300}}],
              "session": {"xplane": synthetic},
              "peaks": {"hbm_bytes_per_s": 819e9,
                        "bf16_flops_per_s": 197e12},
              "trace": {"busy_s": 1e-6}, "chips": 1}
    assert harness.read_layer_metrics([entry], parent) == {}
    assert harness.read_layer_metrics(
        [entry], dict(parent, session=None, trace=None)) == {}


# -- one closed-loop run at toy widths ------------------------------------------

TOY = {"config": dict(
    name="toy_window", hidden_size=64, vocab_size=256, num_hidden_layers=7,
    layer_offset=0, num_attention_heads=4, num_key_value_heads=1,
    head_dim=24, v_head_dim=16, swa_num_attention_heads=4,
    swa_num_key_value_heads=2, swa_head_dim=24, swa_v_head_dim=16,
    sliding_window=8, ring=8, key_head_stored=32, intermediate_size=96,
    moe_intermediate_size=32, n_routed_experts=16, num_experts_per_tok=2,
    experts_held=8, expert_offset=0,
    serving=dict(num_slots=4, max_len=128, page_len=8,
                 prompt_buckets=[8, 32, 64], page_buckets=[1, 4, 8, 16])),
    "workload": dict(clients=4,
                     prompt=dict(median=24, sigma=0.5, min=10, cap=64),
                     output=dict(median=8, sigma=0.5, min=2, cap=24),
                     # at these widths bfloat16 moves a toy's logits by a
                     # tenth of their range: the rehearsal holds the
                     # machinery, tests/test_window_moe.py the numbers,
                     # in float32
                     reference_prompts=[6, 20, 50], trace_seconds=0.5,
                     logits_tol=0.5, served_check=dict(streams=4,
                                                       limit=0.95))}


@pytest.mark.parametrize("trace", [0, 2])
def test_the_cell_rehearses_through_the_serving_rig(trace, capsys):
    import run
    r = run.run_cell(CELL, 2 ** 31 + 40, 3.0, trace, rehearsal=TOY)
    notes = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    said = next(n for n in notes if n["note"] == "verdict")
    assert r["rehearsal"] and r["correct"] and r["failed"] == 0, said
    assert {"saturated_tokens_per_s", "gap_p99_ms", "setup_s"} \
        <= set(r["metrics"])
    assert next(n for n in notes if n["note"] == "served")["served_ok"]
    for name in ("window_attn_device_share", "window_decode_roofline",
                 "window_prefill_roofline", "gqa_prefill_roofline",
                 "paged_attn_roofline.saturated", "moe_experts_roofline"):
        assert name not in r["metrics"]     # no device trace on the CPU
    if trace:
        # every stream is past the window of 8 from its first step: a
        # slot of ~35 rows reads 8 of them in 5 of its 7 layers
        share = r["metrics"]["kv_rows_read_share"]["value"]
        assert 2 / 7 * 100 < share < 70.0
        assert {"decode_step_p50_ms.saturated", "prefill_p50_ms.saturated",
                "seed_slot_p50_ms.saturated", "moe_tokens_per_expert"} \
            <= set(r["metrics"])
    else:
        assert set(r["metrics"]) == {"saturated_tokens_per_s", "gap_p99_ms",
                                     "setup_s"}


@pytest.mark.parametrize("kind", ["window_off", "sink_off"])
def test_the_controls_read_far_from_the_reference(kind):
    """What the cell's ``logits_tol`` has to fail at the published widths,
    at the toy's: the float32 reference with the window or the sink
    switched off reads far from the reference."""
    import jax.numpy as jnp
    import numpy as np
    from lib import serving_rig as rig
    cfg = {**json.load(open(os.path.join(BENCH, "configs",
                                         "mimo_v2_flash.json"))),
           **TOY["config"]}
    adapter = models.adapter_of(cfg)
    weights = dict(adapter.seeded_weights(cfg, 5))
    for i in adapter.window_layers(cfg):    # a toy window's sum is small
        weights[f"win{i}_sink"] = weights[f"win{i}_sink"] - 2.0
    prompt = jnp.asarray(rig._prompt(cfg, 5, 0, 50), jnp.int32)
    at = jnp.asarray([49])
    want = np.asarray(adapter.reference_logits(weights, cfg, prompt, at))
    other = np.asarray(adapter.control_logits(weights, cfg, prompt, at, kind))
    spread = want.max() - want.min()
    assert np.abs(other - want).max() / spread > 0.05
