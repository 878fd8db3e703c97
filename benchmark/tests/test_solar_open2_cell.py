"""The cell ``solar_open2_250b.longgen_saturated`` and its adapter
``solar_open2``, rehearsed on the CPU at toy widths (never a device
metric): the configuration's published widths and the cut's arithmetic,
the adapter's interface and counts, the cell's own readers on recorded
data, and one closed-loop run through the serving rig over the page pool
and the per-slot matrix state.

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

CELL = "solar_open2_250b.longgen_saturated"
NEW_METRICS = ["kda_update_roofline", "kda_scan_roofline",
               "kda_device_share"]
SHARED_METRICS = [
    "decode_step_p50_ms.saturated", "decode_step_device_ms.saturated",
    "decode_dispatch_p50_ms.saturated",
    "executor_call_ms_per_step.saturated",
    "executor_self_ms_per_step.saturated",
    "sched_self_ms_per_iteration.saturated", "prefill_p50_ms.saturated",
    "seed_slot_p50_ms.saturated", "slot_occupancy_mean.saturated",
    "idle_named_share.saturated", "paged_attn_roofline.saturated",
    "moe_experts_roofline", "moe_device_share", "moe_tokens_per_expert",
    "decode_step_touched_hbm_roofline"]


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs", "solar_open2_250b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def good():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_cell_is_in_the_manifest_with_its_metrics(good):
    manifest.validate(good)
    entry, config, workload = manifest.cell_files(good, CELL)
    assert entry["chips"] == 1 and config["name"] == "solar_open2_250b"
    assert entry["traffic"] == "longgen_saturated"
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert sum(w["name"] == CELL for w in good["workloads"]) == 1
    assert sum(c["name"] == "solar_open2_250b" for c in good["configs"]) == 1
    mine = {m["name"] for m in manifest.metrics_of(good, "per_layer", CELL)}
    # at least these: a later tracing PR gives the cell more
    assert set(NEW_METRICS + SHARED_METRICS) <= mine
    # other models' mechanisms
    assert not {m for m in mine if m.startswith(
        ("mla_", "dsa_", "ssm_", "window_", "spec_", "mtp_"))}
    assert {m["name"] for m in manifest.metrics_of(good, "end_to_end", CELL)} \
        == {"saturated_tokens_per_s", "gap_p99_ms", "setup_s"}
    # the three new entries, in their order, list this cell alone
    assert [m["name"] for m in good["per_layer"]
            if m["name"] in NEW_METRICS] == NEW_METRICS
    for name, moves in zip(NEW_METRICS, ("saturated_tokens_per_s",
                                         "gap_p99_ms",
                                         "saturated_tokens_per_s")):
        entry = next(m for m in good["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL] and entry["unit"] == "%"
        assert entry["moves"] == moves and entry["source"] == "device_trace"
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".json"))
        # data only: each reads through a reader the benchmark had
        assert not os.path.exists(os.path.join(BENCH, "layer_metrics",
                                               name + ".py"))
    # no metric without a list, and no new metric that lists an old cell
    assert all("workloads" in m for m in good["per_layer"])
    with open(os.path.join(ROOT, workload)) as f:
        wl = json.load(f)
    assert wl["kind"] == "serve_closed_loop" and wl["clients"] == 64
    assert wl["prompt"] == {"median": 1024, "sigma": 0.8, "min": 128,
                            "cap": 4096}
    assert wl["output"] == {"median": 1024, "sigma": 0.5, "min": 128,
                            "cap": 2048}
    assert wl["sample_seed"] == 49 and wl["trace_seconds"] == 5.0
    assert wl["served_check"]["streams"] == 8
    for key in ("why", "clients_why", "lengths_why", "logits_tol_why",
                "served_check_why"):
        assert wl[key] and "TO FILL" not in wl[key], key


def test_every_published_key_is_unchanged_but_the_reduced(cfg):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(l) for l in f if l.strip()]
    published = next(r for r in rows if r["name"] == "Solar-Open2-250B")
    assert cfg["source"] == published["source_url"]
    changed = {"num_hidden_layers": 8, "vocab_size": 24576}
    for key, value in published["config"].items():
        if key in changed:
            assert cfg[key] == changed[key] and key in cfg["reduced"]
            assert cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    assert cfg["experts_held"] * 32 == cfg["n_routed_experts"] == 320
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["layer_offset"] == 0 and cfg["expert_offset"] == 0
    assert len(cfg["gqa_layers"]) == 12                 # kept whole
    assert cfg["pool_dtype"] == "bfloat16"
    sv = cfg["serving"]
    assert (sv["num_slots"], sv["max_len"], sv["page_len"]) == (64, 6144, 64)
    assert sv["prompt_buckets"][-1] == 4096
    assert sv["page_buckets"][-1] * sv["page_len"] == sv["max_len"]
    # the pool is provisioned for the traffic: over the replay's most,
    # under every slot's max_len
    assert 3152 < sv["num_pages"] < 64 * 96
    for key in ("assumed", "departures", "memory", "deployment",
                "published", "reduced", "reduced_how", "serving_why"):
        assert cfg[key] and "TO FILL" not in json.dumps(cfg[key]) \
            and "TO MEASURE" not in json.dumps(cfg[key]), key
    for key in ("kda", "gate_granularity", "no_positional_embedding",
                "sigmoid_router", "e_score_correction_bias", "hidden_act",
                "state", "decay", "router", "attention"):
        assert key in cfg["assumed"], key
    # the program reads the pattern off the published keys
    from paddle_tpu.models import hybrid_moe
    hp = hybrid_moe.HybridConfig.from_dict(cfg)
    assert hp.pattern == "GSKSKSKS" * 2 and hp.chunked
    assert (hp.kda_num_heads, hp.kda_head_dim, hp.kda_conv_kernel) \
        == (64, 128, 4)
    assert hp.kda_beta_scale == 2.0 and hp.held == 10
    assert hp.pool_dtype == "bfloat16" and hp.eps == 1e-5


def test_the_samples_lengths_are_the_issues(cfg):
    from lib import closedloop
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        wl = json.load(f)
    sample = closedloop.Sample(wl)
    taken = [sample.take() for _ in range(4000)]
    prompts = [r["prompt_len"] for r in taken]
    outputs = [r["max_new"] for r in taken]
    share = lambda xs, keep: sum(map(keep, xs)) / len(xs)
    assert min(prompts) == 128 and max(prompts) == 4096
    assert 0.17 < share(prompts, lambda n: n < 512) < 0.22
    assert 0.17 < share(prompts, lambda n: n > 2048) < 0.22
    assert 1250 < sum(prompts) / len(prompts) < 1400
    assert min(outputs) >= 128 and max(outputs) == 2048
    assert 1050 < sum(outputs) / len(outputs) < 1200
    assert max(p + o for p, o in zip(prompts, outputs)) \
        <= cfg["serving"]["max_len"]
    # each reference prompt in a bucket of its own, the longest past three
    # chunks of 1024
    buckets = cfg["serving"]["prompt_buckets"]
    refs = wl["reference_prompts"]
    assert len({min(b for b in buckets if b >= n) for n in refs}) == 3
    assert max(refs) > 3 * 1024


def test_the_adapter_has_the_interface_and_the_issues_counts(cfg):
    adapter = models.adapter_of(cfg)
    assert all(callable(getattr(adapter, n)) for n in models.INTERFACE)
    assert adapter.gqa_layers(cfg) == [0, 8]
    assert adapter.kda_layers(cfg) == [2, 4, 6, 10, 12, 14]
    assert adapter.moe_layers(cfg) == [1, 3, 5, 7, 9, 11, 13, 15]
    # the issue's arithmetic: an expert 15.73M, a softmax mixer 109.1M, a
    # KDA mixer 137.7M
    assert adapter.expert_bytes(cfg) == 3 * 4096 * 1280 * 2
    assert adapter.gqa_params(cfg) == 4096 * (3 * 8192 + 2 * 1024)
    assert round(adapter.gqa_params(cfg) / 1e6, 1) == 109.1
    assert adapter.kda_params(cfg) == 4 * 4096 * 8192 \
        + 2 * (4096 * 128 + 128 * 8192) + 4096 * 64
    assert round(adapter.kda_params(cfg) / 1e6, 1) == 137.6
    # 2,640M parameters = 5.28 GB
    assert round(adapter.param_count(cfg) / 1e6) == 2640
    assert 5.27e9 < 2 * adapter.param_count(cfg) < 5.29e9
    # a slot's state: 4.19 MB a layer, 25.2 MB over the six
    assert adapter.kda_state_bytes_per_slot(cfg) == 6 * 64 * 128 * 128 * 4
    assert round(adapter.kda_state_bytes_per_slot(cfg) / 1e6, 1) == 25.2
    assert adapter.kda_conv_bytes_per_slot(cfg) == 6 * 3 * 24576 * 4
    # 8 KB a live row: 2 layers x (K + V) x 8 heads x 128 x 2 B
    assert adapter.kv_bytes_per_row(cfg) == 2 * 2 * 8 * 128 * 2 == 8192
    # the update's least bytes a live slot: the state twice and the rows
    rows = 3 * 8192 * 2 + (8192 + 64) * 4 + 8192 * 2
    assert adapter.kda_update_bytes_per_slot(cfg) \
        == 2 * 6 * 64 * 128 * 128 * 4 + 6 * rows
    # the chunk form a row, blocks of 64: 64 heads x (8 x 64 x 128 + 6 x
    # 128 x 128)
    assert adapter.kda_scan_flops_per_row(cfg) \
        == 6 * 64 * (8 * 64 * 128 + 6 * 128 * 128)
    held = 8 * 10 * adapter.expert_bytes(cfg)
    base = adapter.decode_weight_bytes(cfg) - held
    assert adapter.decode_step_bytes(cfg, 0, 0, 0) == base
    # 64 slots of 2,000 rows, 66 experts touched
    state = adapter.kda_state_bytes_per_slot(cfg) \
        + adapter.kda_conv_bytes_per_slot(cfg)
    assert adapter.decode_step_bytes(cfg, 66, 64, 128000) == base \
        + 66 * adapter.expert_bytes(cfg) + 2 * 64 * state + 128000 * 8192
    assert adapter.bundle_key(cfg)[1] == cfg["serving"]


def test_the_reference_imports_no_program():
    path = os.path.join(BENCH, "reference", "solar_open2_ref.py")
    with open(path) as f:
        text = f.read()
    assert "import paddle_tpu" not in text and "from paddle_tpu" not in text
    assert '"highest"' in text and "pallas" not in text
    assert "lax.scan" in text       # the recurrence, a token at a time
    # the adapter's first import is what the parent lacks
    with open(os.path.join(BENCH, "models", "solar_open2.py")) as f:
        imports = [l for l in f.read().splitlines()
                   if l.startswith(("import ", "from "))]
    assert imports[1] == "from paddle_tpu.ops import kda_ops  # noqa: F401"


def test_the_harness_still_names_no_model():
    for folder in ("traffic", "lib"):
        for name in sorted(os.listdir(os.path.join(BENCH, folder))):
            if name.endswith(".py"):
                with open(os.path.join(BENCH, folder, name)) as f:
                    text = f.read()
                assert "solar" not in text and "kda" not in text, name


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
    # 100 steps of 60 live slots; 3 chunks of 900 real rows
    steps = [{"name": "gen.decode_step", "attrs": {"live": 60}}] * 100
    chunks = [{"name": "gen.prefill", "attrs": {"tokens": 900,
                                                "rows": 1024}}] * 3
    run = {"config": cfg, "peaks": peaks.PEAKS["TPU v5 lite"],
           "spans": steps + chunks, "trace": {"busy_s": 2.0}}
    # 100 traced runs of the decode executable, 3 of a chunk's
    monkeypatch.setattr(
        decode_ops, "op_seconds_in_runs", lambda run, events, holding:
        (0.6, 100) if "update" in events[0] else (0.03, 3))
    monkeypatch.setattr(decode_ops, "op_seconds",
                        lambda run, events: 0.63)
    module, spec = _reader("kda_update_roofline")
    per_slot = 2 * 6 * 64 * 128 * 128 * 4 \
        + 6 * (3 * 8192 * 2 + (8192 + 64) * 4 + 8192 * 2)
    assert module.read(run, spec) == pytest.approx(
        100.0 * 100 * 60 * per_slot / 819e9 / 0.6)
    assert spec["events"] == ["ptop_kda_update"]
    assert spec["holding"] == ["ptop_paged_attention"]
    module, spec = _reader("kda_scan_roofline")
    assert module.read(run, spec) == pytest.approx(
        100.0 * 3 * 900 * 6 * 64 * 163840 / 197e12 / 0.03)
    assert spec["events"] == ["ptop_kda_scan"]
    module, spec = _reader("kda_device_share")
    assert module.read(run, spec) == pytest.approx(100.0 * 0.63 / 2.0)
    assert spec["events"] == ["ptop_kda_scan", "ptop_kda_update"]
    # no share passes 100 while the device takes its least time or more
    for name in NEW_METRICS:
        module, spec = _reader(name)
        assert 0 < module.read(run, spec) <= 100.0, name
    # no such scope in the trace
    monkeypatch.setattr(decode_ops, "op_seconds_in_runs",
                        lambda run, events, holding: None)
    monkeypatch.setattr(decode_ops, "op_seconds", lambda run, events: None)
    for name in NEW_METRICS:
        module, spec = _reader(name)
        assert module.read(run, spec) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_readers_read_nothing_from_a_program_without_them(cfg, name):
    """The parent's spans and trace: no such scope."""
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
    name="toy_solar", hidden_size=64, vocab_size=256, num_hidden_layers=8,
    layer_offset=0, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16,
    linear_attn_config=dict(short_conv_kernel_size=4, head_dim=16,
                            num_heads=4, num_kv_heads=None),
    moe_intermediate_size=32, n_routed_experts=16, num_experts_per_tok=2,
    experts_held=8, expert_offset=0,
    serving=dict(num_slots=4, max_len=128, page_len=8, num_pages=48,
                 prompt_buckets=[16, 32, 64], page_buckets=[2, 8, 16])),
    "workload": dict(clients=4,
                     prompt=dict(median=24, sigma=0.5, min=10, cap=64),
                     output=dict(median=8, sigma=0.5, min=2, cap=24),
                     # at these widths bfloat16 moves a toy's logits by a
                     # tenth of their range: the rehearsal holds the
                     # machinery, tests/test_solar_open2.py the numbers,
                     # in float32
                     reference_prompts=[6, 20, 50], trace_seconds=0.5,
                     logits_tol=0.5, served_check=dict(streams=4,
                                                       limit=0.95))}


@pytest.mark.parametrize("trace", [0, 2])
def test_the_cell_rehearses_through_the_serving_rig(trace, capsys):
    import run
    r = run.run_cell(CELL, 2 ** 31 + 49, 3.0, trace, rehearsal=TOY)
    notes = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    said = next(n for n in notes if n["note"] == "verdict")
    assert r["rehearsal"] and r["correct"] and r["failed"] == 0, said
    assert {"saturated_tokens_per_s", "gap_p99_ms", "setup_s"} \
        <= set(r["metrics"])
    assert next(n for n in notes if n["note"] == "served")["served_ok"]
    for name in NEW_METRICS + ["paged_attn_roofline.saturated",
                               "moe_experts_roofline"]:
        assert name not in r["metrics"]     # no device trace on the CPU
    if trace:
        assert {"decode_step_p50_ms.saturated", "prefill_p50_ms.saturated",
                "seed_slot_p50_ms.saturated", "moe_tokens_per_expert"} \
            <= set(r["metrics"])
    else:
        assert set(r["metrics"]) == {"saturated_tokens_per_s", "gap_p99_ms",
                                     "setup_s"}


def test_the_decay_control_reads_far_from_the_reference():
    """What the cell's limits have to fail at the published widths, at the
    toy's: the float32 reference with the decay dropped (alpha = 1)."""
    import jax.numpy as jnp
    import numpy as np
    from lib import serving_rig as rig
    cfg = {**json.load(open(os.path.join(BENCH, "configs",
                                         "solar_open2_250b.json"))),
           **TOY["config"]}
    adapter = models.adapter_of(cfg)
    weights = adapter.seeded_weights(cfg, 5)
    prompt = jnp.asarray(rig._prompt(cfg, 5, 0, 50), jnp.int32)
    at = jnp.asarray([49])
    want = np.asarray(adapter.reference_logits(weights, cfg, prompt, at))
    other = np.asarray(adapter.control_logits(weights, cfg, prompt, at,
                                              "decay_off"))
    assert np.abs(other - want).max() / (want.max() - want.min()) > 0.02
