"""The cell ``dots3_note_prev.longdoc_saturated`` and its adapter
``dots3_note``, rehearsed on the CPU at toy widths (never a device
metric): the configuration's published widths and the cut's arithmetic,
the adapter's interface and counts against hand counts, the sample's
lengths, the cell's new readers on recorded data, and one closed-loop run
through the serving rig with pools AND rings.

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

CELL = "dots3_note_prev.longdoc_saturated"
NEW_METRICS = ["latent_window_decode_roofline",
               "latent_window_prefill_roofline",
               "latent_window_device_share"]
SHARED_METRICS = [
    "decode_step_p50_ms.saturated", "decode_step_device_ms.saturated",
    "executor_call_ms_per_step.saturated",
    "executor_self_ms_per_step.saturated",
    "sched_self_ms_per_iteration.saturated", "prefill_p50_ms.saturated",
    "seed_slot_p50_ms.saturated", "slot_occupancy_mean.saturated",
    "idle_named_share.saturated", "decode_dispatch_p50_ms.saturated",
    "admission_device_share", "admission_run_device_ms",
    "decode_attn_device_ms", "decode_experts_device_ms",
    "decode_experts_glue_device_ms", "decode_dense_device_ms",
    "decode_head_device_ms", "decode_other_device_ms",
    "moe_experts_roofline", "moe_device_share", "moe_tokens_per_expert",
    "decode_step_touched_hbm_roofline", "mla_device_share",
    "dsa_index_roofline", "dsa_attn_roofline", "dsa_device_share",
    "dsa_selected_share", "kv_rows_read_share"]


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs", "dots3_note_prev.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def good():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_cell_and_its_configuration_are_in_the_manifest(good):
    """PRESENT, not last and not so many: a later PR appends its own."""
    manifest.validate(good)
    entry, config, workload = manifest.cell_files(good, CELL)
    assert entry["chips"] == 1 and config["name"] == "dots3_note_prev"
    assert entry["traffic"] == "longdoc_saturated"
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert sum(w["name"] == CELL for w in good["workloads"]) == 1
    assert sum(w["chips"] == 4 for w in good["workloads"]) == 1
    mine = {m["name"] for m in manifest.metrics_of(good, "per_layer", CELL)}
    assert set(NEW_METRICS + SHARED_METRICS) <= mine
    # a sparse read would pass what these two count as the least time,
    # and the grouped-query window metrics read other op scopes
    assert not {"mla_decode_roofline", "paged_attn_roofline.saturated",
                "window_decode_roofline", "window_prefill_roofline",
                "window_attn_device_share"} & mine
    assert {m["name"] for m in manifest.metrics_of(good, "end_to_end", CELL)} \
        == {"saturated_tokens_per_s", "gap_p99_ms", "setup_s"}
    for name in NEW_METRICS:
        entry = next(m for m in good["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL] and entry["unit"] == "%"
        assert entry["moves"] == "saturated_tokens_per_s"
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".json"))
        # data only: each reads through a reader the benchmark had
        assert not os.path.exists(os.path.join(BENCH, "layer_metrics",
                                               name + ".py"))
    with open(os.path.join(ROOT, workload)) as f:
        wl = json.load(f)
    assert wl["kind"] == "serve_closed_loop" and wl["clients"] == 16
    assert wl["prompt"] == {"median": 8192, "sigma": 0.5, "min": 2304,
                            "cap": 16384}
    assert wl["output"] == {"median": 768, "sigma": 0.5, "min": 128,
                            "cap": 1536}
    assert wl["sample_seed"] == 57 and wl["trace_seconds"] == 5.0
    assert wl["drain_timeout_s"] == 120 and wl["think_time_s"] == 0
    assert wl["reference_prompts"] == [400, 3000, 15000]
    assert wl["served_check"]["streams"] == 8
    for key in ("why", "clients_why", "lengths_why", "logits_tol_why",
                "served_check_why"):
        assert wl[key] and "TO BE" not in wl[key], key


def test_every_published_key_is_unchanged_but_the_reduced(cfg):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(l) for l in f if l.strip()]
    published = next(r for r in rows if r["name"] == "dots3-note-prev")
    assert cfg["source"] == published["source_url"]
    changed = {"num_hidden_layers": 9, "vocab_size": 19008}
    for key, value in published["config"].items():
        if key in changed:
            assert cfg[key] == changed[key] and key in cfg["reduced"]
            assert cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    assert cfg["experts_held"] * 32 == cfg["n_routed_experts"] == 256
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["layer_types"][:9] == ["full_attention"] * 2 + (
        ["sliding_attention"] * 3 + ["full_attention"]
        + ["sliding_attention"] * 3)
    assert len(cfg["layer_types"]) == 46
    sv = cfg["serving"]
    assert (sv["num_slots"], sv["max_len"], sv["page_len"]) == (16, 18432, 64)
    assert sv["prompt_buckets"][-1] == 16384
    assert sv["page_buckets"][-1] * sv["page_len"] == sv["max_len"]
    for key in ("assumed", "departures", "memory", "deployment",
                "published", "reduced", "reduced_how"):
        assert cfg[key] and "TO BE" not in json.dumps(cfg[key]), key
    for key in ("rescale", "gate", "window", "indexer",
                "rotary_pair_layout", "router", "weights"):
        assert cfg["assumed"][key], key
    listed = " ".join(cfg["departures"])
    for word in ("vision", "MTP", "524288", "EXACT", "32-chip", "greedy"):
        assert word in listed, word


def test_the_samples_lengths_are_the_issues(cfg):
    from lib import closedloop
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        wl = json.load(f)
    sample = closedloop.Sample(wl)
    taken = [sample.take() for _ in range(4000)]
    prompts = [r["prompt_len"] for r in taken]
    outputs = [r["max_new"] for r in taken]
    # every stream is past index_topk AND past the window at its first step
    assert min(prompts) == 2304 > cfg["index_topk"] \
        > cfg["sliding_window_size"]
    assert max(prompts) == 16384 == cfg["serving"]["prompt_buckets"][-1]
    assert 8800 < sum(prompts) / len(prompts) < 9800
    assert min(outputs) >= 128 and max(outputs) == 1536
    assert 780 < sum(outputs) / len(outputs) < 900
    assert max(p + o for p, o in zip(prompts, outputs)) \
        <= cfg["serving"]["max_len"]
    # the reference prompts: inside the window with the ring not wrapped
    # and the selection the identity; past both; the largest bucket
    a, b, c = wl["reference_prompts"]
    assert a < cfg["sliding_window_size"] < cfg["index_topk"] < b < c
    assert c > cfg["serving"]["prompt_buckets"][-2]


def test_the_adapter_has_the_interface_and_the_hand_counts(cfg):
    adapter = models.adapter_of(cfg)
    assert all(callable(getattr(adapter, n)) for n in models.INTERFACE)
    assert adapter.full_layers(cfg) == [0, 1, 5]
    assert adapter.window_layers(cfg) == [2, 3, 4, 6, 7, 8]
    assert adapter.sparse_layers(cfg) == list(range(1, 9))
    # the issue's arithmetic
    assert adapter.attention_params(cfg, 0) == 5120 * 1024 \
        + 1024 * 128 * 192 + 5120 * 576 + 512 * 128 * 256 + 16384 * 5120 \
        + 5120 * 128
    assert adapter.attention_params(cfg, 2) == 5120 * 1024 \
        + 1024 * 64 * 256 + 5120 * 1088 + 1024 * 64 * 320 + 8192 * 5120 \
        + 5120 * 64
    assert round(adapter.attention_params(cfg, 0) / 1e6, 1) == 134.7
    assert round(adapter.attention_params(cfg, 2) / 1e6, 1) == 90.8
    assert adapter.indexer_params(cfg) == 1024 * 64 * 128 + 5120 * 128 \
        + 5120 * 64
    assert adapter.expert_bytes(cfg) == 3 * 5120 * 1536 * 2
    # 356.4M + 2 x 357.7M + 6 x 304.4M + 194.6M = 3.09B = 6.19 GB
    assert round(adapter.param_count(cfg) / 1e9, 2) == 3.09
    assert 6.17e9 < 2 * adapter.param_count(cfg) < 6.20e9
    assert adapter.kv_bytes_per_row(cfg) == 3 * 576 * 2
    assert adapter.index_bytes_per_row(cfg) == 3 * 128 * 2
    assert adapter.index_flops_per_row(cfg) == 3 * 64 * 128 * 2
    assert adapter.mla_decode_flops_per_row(cfg) == 3 * 128 * (576 + 512) * 2
    assert adapter.latent_window_bytes_per_row(cfg) == 1088 * 2
    assert adapter.latent_window_flops_per_row(cfg) == 64 * (1088 + 1024) * 2
    assert adapter.latent_window_flops_per_pair(cfg) \
        == 6 * 64 * (192 + 64 + 128) * 2
    held = 8 * 8 * adapter.expert_bytes(cfg)
    base = adapter.decode_weight_bytes(cfg) - held
    assert adapter.decode_step_bytes(cfg, 0, 16, 0) == base
    # 16 slots of 9,000 rows: every key is scored, 2048 rows a slot
    # attended in the full layers, 513 a slot in each of six rings
    assert adapter.decode_step_bytes(cfg, 10, 16, 144000) == base \
        + 10 * adapter.expert_bytes(cfg) + 144000 * 768 \
        + 16 * 2048 * 3456 + 16 * 513 * 6 * 2176
    # below the window a slot: every live row, everywhere
    assert adapter.decode_step_bytes(cfg, 0, 16, 1000) == base \
        + 1000 * (768 + 3456 + 6 * 2176)
    assert adapter.bundle_key(cfg)[1] == cfg["serving"]
    # the pools and the rings, as the issue reckons them
    sv = cfg["serving"]
    rows = sv["num_slots"] * sv["max_len"]
    assert round(rows * 3 * 1280 / 1e9, 2) == 1.13
    assert round(rows * 3 * 256 / 1e9, 2) == 0.23
    assert round(6 * sv["num_slots"] * 640 * 2304 / 1e9, 2) == 0.14


def test_the_reference_imports_no_program():
    with open(os.path.join(BENCH, "reference", "dots3_note_ref.py")) as f:
        text = f.read()
    assert "import paddle_tpu" not in text and "from paddle_tpu" not in text
    assert '"highest"' in text and "jax.lax.top_k" in text
    assert "pallas" not in text
    # what the adapter's export imports first is what the parent lacks
    with open(os.path.join(BENCH, "models", "dots3_note.py")) as f:
        text = f.read()
    assert "from paddle_tpu.ops.mla_ops import latent_ring_step" in text


def test_the_harness_still_names_no_model():
    for folder in ("traffic", "lib"):
        for name in sorted(os.listdir(os.path.join(BENCH, folder))):
            if name.endswith(".py"):
                with open(os.path.join(BENCH, folder, name)) as f:
                    text = f.read()
                assert "dots3" not in text and "latent_moe" not in text, name


# -- the cell's new readers on recorded data -----------------------------------

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


def test_the_rooflines_on_recorded_data(cfg, monkeypatch):
    from lib import decode_ops, peaks
    run = {"config": cfg, "peaks": peaks.PEAKS["TPU v5 lite"],
           "spans": [{"name": "gen.decode_step",
                      "attrs": {"window_rows": 6 * 16 * 513}}] * 50
           + [{"name": "gen.prefill", "attrs": {"band_pairs": 1024 * 513}}]
           * 7}
    seen = []

    def found(run, events, holding):
        seen.append((tuple(events), tuple(holding)))
        return (0.020, 100)

    monkeypatch.setattr(decode_ops, "op_seconds_in_runs", found)
    module, spec = _reader("latent_window_decode_roofline")
    # memory-bound: 2176 B a row at 819 GB/s against 270 kFLOP at 197 T
    assert module.read(run, spec) == pytest.approx(
        100.0 * 100 * 6 * 16 * 513 * 2176 / 819e9 / 0.020)
    module, spec = _reader("latent_window_prefill_roofline")
    assert module.read(run, spec) == pytest.approx(
        100.0 * 100 * 1024 * 513 * 6 * 64 * 384 * 2 / 197e12 / 0.020)
    # each op's own scope, the decode step's inside the decode executable
    assert seen == [(("ptop_latent_window_step",), ("ptop_paged_attention",)),
                    (("ptop_latent_window_attention",),
                     ("ptop_latent_window_attention",))]
    monkeypatch.setattr(decode_ops, "op_seconds_in_runs",
                        lambda run, events, holding: None)
    for name in NEW_METRICS[:2]:
        module, spec = _reader(name)
        assert module.read(run, spec) is None


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
              "spans": [{"name": "gen.decode_step", "attrs": {"live": 16}}],
              "session": {"xplane": synthetic},
              "peaks": {"hbm_bytes_per_s": 819e9,
                        "bf16_flops_per_s": 197e12},
              "trace": {"busy_s": 1e-6}, "chips": 1}
    assert harness.read_layer_metrics([entry], parent) == {}
    assert harness.read_layer_metrics(
        [entry], dict(parent, session=None, trace=None)) == {}


# -- one closed-loop run at toy widths ------------------------------------------

TOY = {"config": dict(
    name="toy_latent_window", hidden_size=64, vocab_size=256,
    num_hidden_layers=4, first_k_dense_replace=1,
    layer_types=["full_attention", "sliding_attention", "sliding_attention",
                 "full_attention"],
    num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    sliding_window_size=9, ring=12, swa_num_attention_heads=2,
    swa_q_lora_rank=40, swa_kv_lora_rank=48, swa_qk_nope_head_dim=24,
    swa_qk_rope_head_dim=8, swa_v_head_dim=16, index_topk=8,
    index_n_heads=4, index_head_dim=16, intermediate_size=96,
    moe_intermediate_size=32, n_routed_experts=16, num_experts_per_tok=2,
    experts_held=8, expert_offset=0,
    serving=dict(num_slots=4, max_len=128, page_len=8,
                 prompt_buckets=[8, 32, 64], page_buckets=[1, 4, 8, 16])),
    "workload": dict(clients=4,
                     prompt=dict(median=24, sigma=0.5, min=10, cap=64),
                     output=dict(median=8, sigma=0.5, min=2, cap=24),
                     # at these widths ONE of a row's 8 selected rows
                     # that flips under bfloat16 moves the logits by half
                     # their range: the rehearsal holds the machinery,
                     # tests/test_dots3_note.py the numbers, in float32
                     reference_prompts=[6, 20, 50], trace_seconds=0.5,
                     logits_tol=0.95, served_check=dict(streams=4,
                                                        limit=0.95))}


@pytest.mark.parametrize("trace", [0, 2])
def test_the_cell_rehearses_through_the_serving_rig(trace, capsys):
    import run
    r = run.run_cell(CELL, 2 ** 31 + 57, 3.0, trace, rehearsal=TOY)
    notes = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    said = next(n for n in notes if n["note"] == "verdict")
    assert r["rehearsal"] and r["correct"] and r["failed"] == 0, said
    assert {"saturated_tokens_per_s", "gap_p99_ms", "setup_s"} \
        <= set(r["metrics"])
    assert next(n for n in notes if n["note"] == "served")["served_ok"]
    for name in NEW_METRICS + ["dsa_index_roofline", "moe_experts_roofline"]:
        assert name not in r["metrics"]     # no device trace on the CPU
    if trace:
        # every stream is past index_topk 8 and the window 9 from its
        # first step: two of four layers read 9 rows a slot of ~35
        assert 10.0 < r["metrics"]["dsa_selected_share"]["value"] < 60.0
        assert 50.0 < r["metrics"]["kv_rows_read_share"]["value"] < 90.0
        assert {"decode_step_p50_ms.saturated", "prefill_p50_ms.saturated",
                "moe_tokens_per_expert"} <= set(r["metrics"])
    else:
        assert set(r["metrics"]) == {"saturated_tokens_per_s", "gap_p99_ms",
                                     "setup_s"}


@pytest.mark.parametrize("kind", ["select_off", "window_off", "gate_off"])
def test_each_control_reads_far_from_the_reference(kind):
    """What the cell's ``logits_tol`` has to fail at the published widths,
    at the toy's: the float32 reference with one mechanism switched off
    reads a twentieth of the logits' range and more from the reference."""
    import jax.numpy as jnp
    import numpy as np
    from lib import serving_rig as rig
    with open(os.path.join(BENCH, "configs", "dots3_note_prev.json")) as f:
        cfg = {**json.load(f), **TOY["config"]}
    adapter = models.adapter_of(cfg)
    weights = adapter.seeded_weights(cfg, 5)
    prompt = jnp.asarray(rig._prompt(cfg, 5, 0, 50), jnp.int32)
    at = jnp.asarray([49])
    want = np.asarray(adapter.reference_logits(weights, cfg, prompt, at))
    off = np.asarray(adapter.control_logits(weights, cfg, prompt, at, kind))
    assert np.abs(off - want).max() / (want.max() - want.min()) > 0.05
