"""The cell ``kimi_k2.6_text.decode_saturated`` and its adapter ``kimi_k2``,
rehearsed on the CPU at toy widths (never a device metric): the
configuration's published widths and the cut's arithmetic, the adapter's
interface and counts, the cell's own readers on recorded data, and one
closed-loop run through the serving rig.

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

CELL = "kimi_k2.6_text.decode_saturated"
NEW_METRICS = ["mla_decode_roofline", "mla_device_share"]
SHARED_METRICS = ["moe_experts_roofline", "moe_device_share",
                  "moe_tokens_per_expert", "decode_step_touched_hbm_roofline",
                  "paged_attn_roofline.saturated"]


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs", "kimi_k2.6_text.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def good():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_cell_is_in_the_manifest_with_its_metrics(good):
    manifest.validate(good)
    entry, config, workload = manifest.cell_files(good, CELL)
    assert entry["chips"] == 1 and config["name"] == "kimi_k2.6_text"
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    # (by name, not by place: later cells go behind this one)
    assert sum(w["name"] == CELL for w in good["workloads"]) == 1
    assert sum(c["name"] == "kimi_k2.6_text" for c in good["configs"]) == 1
    assert sum(w["chips"] == 4 for w in good["workloads"]) == 1
    mine = {m["name"] for m in manifest.metrics_of(good, "per_layer", CELL)}
    assert set(NEW_METRICS + SHARED_METRICS) <= mine
    assert not {"decode_step_hbm_roofline.saturated", "ssm_update_roofline",
                "ssm_scan_roofline", "ssm_device_share"} & mine
    assert {m["name"] for m in manifest.metrics_of(good, "end_to_end", CELL)} \
        == {"saturated_tokens_per_s", "gap_p99_ms", "setup_s"}
    for name in NEW_METRICS:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".json"))
    with open(os.path.join(ROOT, workload)) as f:
        wl = json.load(f)
    assert wl["kind"] == "serve_closed_loop" and wl["clients"] == 32
    assert wl["prompt"] == {"median": 768, "sigma": 0.6, "min": 64,
                            "cap": 2048}
    assert wl["output"] == {"median": 1024, "sigma": 0.5, "min": 128,
                            "cap": 2048}
    assert wl["sample_seed"] == 22 and wl["served_check"]["streams"] == 8
    assert wl["reference_prompts"] == [100, 700, 1900]
    for key in ("why", "lengths_why", "logits_tol_why", "served_check_why"):
        assert wl[key]


def test_every_published_key_is_unchanged_but_the_reduced(cfg):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(l) for l in f if l.strip()]
    published = next(r for r in rows if r["name"] == "Kimi-K2.6")
    assert cfg["source"] == published["source_url"]
    for key, value in published["config"].items():
        if key in ("num_hidden_layers", "vocab_size"):
            assert cfg[key] != value and key in cfg["reduced"]
            assert cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    assert cfg["num_hidden_layers"] == 5 and cfg["first_k_dense_replace"] == 1
    assert cfg["experts_held"] * 32 == cfg["n_routed_experts"] == 384
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    sv = cfg["serving"]
    assert (sv["num_slots"], sv["max_len"], sv["page_len"]) == (32, 4096, 16)
    assert sv["prompt_buckets"] == [128, 256, 512, 1024, 2048]
    for key in ("assumed", "departures", "memory", "deployment",
                "published", "reduced", "reduced_how"):
        assert cfg[key]


def test_the_adapter_has_the_interface_and_the_tables_counts(cfg):
    adapter = models.adapter_of(cfg)
    assert all(callable(getattr(adapter, n)) for n in models.INTERFACE)
    # the issue's table: an MLA block 101.1M, an expert 44.04M = 88.1 MB
    assert adapter.mla_params(cfg) == 7168 * 1536 + 1536 * 64 * 192 \
        + 7168 * 576 + 512 * 64 * 256 + 8192 * 7168
    assert round(adapter.mla_params(cfg) / 1e6, 1) == 101.1
    assert adapter.expert_bytes(cfg) == 3 * 7168 * 2048 * 2
    # dense layer 497.5M + 4 x (147.9M + 12 x 44.04M) + 293.6M = 3.50B
    assert round(adapter.param_count(cfg) / 1e9, 2) == 3.50
    assert 6.98e9 < 2 * adapter.param_count(cfg) < 7.00e9
    # the latent row a token: 5 layers x 576 bfloat16 values
    assert adapter.kv_bytes_per_row(cfg) == 5 * 576 * 2
    assert adapter.mla_decode_flops_per_row(cfg) == 5 * 64 * (576 + 512) * 2
    # no expert touched: everything but the held experts and the embedding
    held = 4 * 12 * adapter.expert_bytes(cfg)
    assert adapter.decode_step_bytes(cfg, 0, 32, 0) == \
        adapter.decode_weight_bytes(cfg) - held
    assert adapter.decode_step_bytes(cfg, 24, 32, 1000) == \
        adapter.decode_weight_bytes(cfg) - held \
        + 24 * adapter.expert_bytes(cfg) + 1000 * 5760
    assert adapter.bundle_key(cfg)[1] == cfg["serving"]


def test_the_reference_imports_no_program():
    with open(os.path.join(BENCH, "reference", "kimi_k2_ref.py")) as f:
        text = f.read()
    assert "import paddle_tpu" not in text and "from paddle_tpu" not in text
    assert '"highest"' in text


def test_the_harness_still_names_no_model():
    for folder in ("traffic", "lib"):
        for name in sorted(os.listdir(os.path.join(BENCH, folder))):
            if name.endswith(".py"):
                with open(os.path.join(BENCH, folder, name)) as f:
                    text = f.read()
                assert "kimi" not in text and "latent_moe" not in text, name


# -- the cell's own readers on recorded data ----------------------------------

def _reader(name):
    import run as harness
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        spec = json.load(f)
    return harness.load_module(
        os.path.join(BENCH, "layer_metrics", name + ".py"),
        "layer_metric_test_" + name), spec


def test_mla_decode_roofline_on_recorded_data(cfg, monkeypatch):
    from lib import decode_ops, peaks
    module, spec = _reader("mla_decode_roofline")
    run = {"facts": {"traced_live_rows": 1_000_000}, "config": cfg,
           "peaks": peaks.PEAKS["TPU v5 lite"]}
    monkeypatch.setattr(decode_ops, "op_seconds_in_runs",
                        lambda run, events, holding: (0.010, 500))
    # memory-bound: 5760 B a row at 819 GB/s against 696 kFLOP at 197 T
    assert module.read(run, spec) == pytest.approx(
        100.0 * 1e6 * 5760 / 819e9 / 0.010)
    monkeypatch.setattr(decode_ops, "op_seconds_in_runs",
                        lambda run, events, holding: None)
    assert module.read(run, spec) is None        # the parent: no such kernel


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
              "spans": [], "session": {"xplane": synthetic},
              "peaks": {"hbm_bytes_per_s": 819e9,
                        "bf16_flops_per_s": 197e12},
              "trace": {"busy_s": 1e-6}, "chips": 1}
    assert harness.read_layer_metrics([entry], parent) == {}
    assert harness.read_layer_metrics(
        [entry], dict(parent, session=None, trace=None)) == {}


# -- one closed-loop run at toy widths ------------------------------------------

TOY = {"config": dict(
    name="toy_latent", hidden_size=64, vocab_size=256, num_hidden_layers=3,
    first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=48,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=96, moe_intermediate_size=32, n_routed_experts=16,
    num_experts_per_tok=2, experts_held=8, expert_offset=0,
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
    for name in NEW_METRICS + ["moe_experts_roofline", "moe_device_share",
                               "paged_attn_roofline.saturated"]:
        assert name not in r["metrics"]     # no device trace on the CPU
    if trace:
        # half the experts are held: about half of 4 x top-2 land
        assert 1.0 <= r["metrics"]["moe_tokens_per_expert"]["value"] < 4.0
        assert {"decode_step_p50_ms.saturated", "prefill_p50_ms.saturated",
                "seed_slot_p50_ms.saturated"} <= set(r["metrics"])
    else:
        assert set(r["metrics"]) == {"saturated_tokens_per_s", "gap_p99_ms",
                                     "setup_s"}
