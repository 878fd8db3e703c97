"""The cell ``sdar_30b_a3b_chat.decode_saturated`` and its adapter ``sdar``,
rehearsed on the CPU at toy widths (never a device metric): the
configuration's published widths and the cut's arithmetic, the adapter's
interface and counts, the cell's own reader on recorded spans, and one
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

CELL = "sdar_30b_a3b_chat.decode_saturated"
NEW_METRIC = "block_forwards_per_token"
SHARED_METRICS = ["moe_experts_roofline", "moe_device_share",
                  "moe_tokens_per_expert", "decode_step_touched_hbm_roofline",
                  "paged_attn_roofline.saturated",
                  "decode_step_p50_ms.saturated",
                  "decode_step_device_ms.saturated",
                  "executor_call_ms_per_step.saturated",
                  "executor_self_ms_per_step.saturated",
                  "sched_self_ms_per_iteration.saturated",
                  "prefill_p50_ms.saturated", "seed_slot_p50_ms.saturated",
                  "slot_occupancy_mean.saturated",
                  "idle_named_share.saturated"]


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs", "sdar_30b_a3b_chat.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def good():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_cell_is_in_the_manifest_with_its_metrics(good):
    manifest.validate(good)
    entry, config, workload = manifest.cell_files(good, CELL)
    assert entry["chips"] == 1 and config["name"] == "sdar_30b_a3b_chat"
    assert config["reduced"] == ["num_hidden_layers"]
    # (by name, not by place: a later cell goes behind this one)
    assert len(good["workloads"]) >= 8
    assert sum(w["chips"] == 4 for w in good["workloads"]) == 1
    mine = {m["name"] for m in manifest.metrics_of(good, "per_layer", CELL)}
    # at least these: a later tracing PR gives the cell more
    assert set(SHARED_METRICS + [NEW_METRIC]) <= mine
    assert next(m for m in good["per_layer"]
                if m["name"] == NEW_METRIC)["workloads"] == [CELL]
    assert {m["name"] for m in manifest.metrics_of(good, "end_to_end", CELL)} \
        == {"saturated_tokens_per_s", "gap_p99_ms", "setup_s"}
    for ext in (".json", ".py"):
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           NEW_METRIC + ext))
    with open(os.path.join(ROOT, workload)) as f:
        wl = json.load(f)
    assert wl["kind"] == "serve_closed_loop" and wl["clients"] == 32
    assert wl["prompt"] == {"median": 384, "sigma": 0.6, "min": 64,
                            "cap": 2048}
    assert wl["output"] == {"median": 768, "sigma": 0.4, "min": 128,
                            "cap": 1536}
    assert wl["sample_seed"] == 33 and wl["served_check"]["streams"] == 8
    assert wl["trace_seconds"] == 5 and wl["drain_timeout_s"] == 120
    assert wl["think_time_s"] == 0
    # a tail of 0, 1 and 2 tokens opening the first block; three buckets
    assert wl["reference_prompts"] == [100, 701, 1898]
    assert [n % 4 for n in wl["reference_prompts"]] == [0, 1, 2]
    for key in ("why", "lengths_why", "logits_tol_why", "served_check_why",
                "clients_why"):
        assert wl[key] and wl[key] != "TBD", key
    assert os.path.exists(os.path.join(BENCH, "sweeps", CELL + ".json"))


def test_every_published_key_is_unchanged_but_the_depth(cfg):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(l) for l in f if l.strip()]
    published = next(r for r in rows if r["name"] == "SDAR-30B-A3B-Chat")
    assert cfg["source"] == published["source_url"]
    for key, value in published["config"].items():
        if key == "num_hidden_layers":
            assert cfg[key] == 4 and cfg["published"][key] == value == 48
        else:
            assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"]) == (2048, 32, 4, 128)
    assert (cfg["num_experts"], cfg["num_experts_per_tok"],
            cfg["moe_intermediate_size"], cfg["vocab_size"]) \
        == (128, 8, 768, 151936)
    assert (cfg["block_length"], cfg["denoising_steps"],
            cfg["remasking_strategy"], cfg["mask_token_id"]) \
        == (4, 4, "sequential", 151669)
    sv = cfg["serving"]
    assert (sv["num_slots"], sv["max_len"], sv["page_len"]) == (32, 4096, 16)
    assert sv["page_len"] % cfg["block_length"] == 0
    assert sv["prompt_buckets"] == [128, 256, 512, 1024, 2048]
    for key in ("assumed", "departures", "memory", "deployment",
                "published", "reduced", "reduced_how"):
        assert cfg[key]
    for key in ("block_length", "denoising_steps", "remasking_strategy",
                "mask_token_id", "no_shift", "qk_norm",
                "rotary_pair_layout", "weights", "router"):
        assert cfg["assumed"][key], key


def test_the_adapter_has_the_interface_and_the_issues_counts(cfg):
    adapter = models.adapter_of(cfg)
    assert all(callable(getattr(adapter, n)) for n in models.INTERFACE)
    # the issue's arithmetic: attention 18.87M, an expert 4.719M = 9.44 MB,
    # a layer 623.1M, embedding + head 622.3M, 4 layers + vocabulary 3.115B
    assert adapter.attention_params(cfg) == 2048 * 4096 + 2 * 2048 * 512 \
        + 4096 * 2048
    assert adapter.expert_bytes(cfg) == 3 * 2048 * 768 * 2
    assert round(adapter.param_count(cfg) / 1e9, 3) == 3.115
    assert 6.22e9 < 2 * adapter.param_count(cfg) < 6.24e9
    # a K and a V row of 4 heads x 128 a layer: 4 x 2048 B
    assert adapter.kv_bytes_per_row(cfg) == 4 * 2048
    held = 4 * 128 * adapter.expert_bytes(cfg)
    assert adapter.decode_step_bytes(cfg, 0, 32, 0) == \
        adapter.decode_weight_bytes(cfg) - held
    assert adapter.decode_step_bytes(cfg, 500, 32, 1000) == \
        adapter.decode_weight_bytes(cfg) - held \
        + 500 * adapter.expert_bytes(cfg) + 1000 * 8192
    # every expert touched: the whole of the weights but the embedding
    assert adapter.decode_step_bytes(cfg, 512, 32, 0) == \
        adapter.decode_weight_bytes(cfg) == \
        2 * (adapter.param_count(cfg) - 151936 * 2048)
    assert adapter.bundle_key(cfg)[1] == cfg["serving"]
    assert adapter.bundle_key(cfg)[0]["block_length"] == 4


def test_the_reference_imports_no_program():
    with open(os.path.join(BENCH, "reference", "sdar_ref.py")) as f:
        text = f.read()
    assert "import paddle_tpu" not in text and "from paddle_tpu" not in text
    assert '"highest"' in text and "def generate" in text


def test_the_harness_still_names_no_model():
    for folder in ("traffic", "lib"):
        for name in sorted(os.listdir(os.path.join(BENCH, folder))):
            if name.endswith(".py"):
                with open(os.path.join(BENCH, folder, name)) as f:
                    text = f.read()
                assert "sdar" not in text and "block_moe" not in text, name


# -- the cell's own reader on recorded spans ----------------------------------

def _entry():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return next(m for m in json.load(f)["per_layer"]
                    if m["name"] == NEW_METRIC)


def test_block_forwards_per_token_on_recorded_spans(cfg):
    import run as harness
    step = lambda live, yielded: {"name": "gen.decode_step", "attrs": {
        "live": live, "yielded": yielded, "stored": live - yielded}}
    # 32 slots: four turns of every five yield a token
    spans = [step(32, 26), step(32, 25), step(32, 26), step(32, 25),
             step(32, 26), {"name": "gen.decode_step", "attrs": {"ahead": 0}},
             {"name": "gen.emit", "attrs": {}}]
    got = harness.read_layer_metrics([_entry()], {"config": cfg,
                                                  "spans": spans})
    assert got[NEW_METRIC]["value"] == pytest.approx(160 / 128)
    assert got[NEW_METRIC]["unit"] == "forwards/token"


def test_the_new_reader_reads_nothing_from_a_program_without_it(cfg):
    """The parent's spans: ``live`` and no ``yielded``."""
    import run as harness
    spans = [{"name": "gen.decode_step", "attrs": {"live": 32,
                                                   "discarded": 0}}]
    for run in ({"config": cfg, "spans": spans}, {"config": cfg,
                                                  "spans": []},
                {"config": cfg}):
        assert harness.read_layer_metrics([_entry()], run) == {}


# -- one closed-loop run at toy widths ------------------------------------------

TOY = {"config": dict(
    name="toy_block", hidden_size=64, vocab_size=256, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
    mask_token_id=255,
    serving=dict(num_slots=4, max_len=128, page_len=16,
                 prompt_buckets=[32, 64], page_buckets="default")),
    "workload": dict(clients=4,
                     prompt=dict(median=16, sigma=0.8, min=2, cap=64),
                     output=dict(median=8, sigma=0.5, min=2, cap=24),
                     reference_prompts=[8, 41, 22], trace_seconds=0.5,
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
    assert seen["slot_occupancy_mean"] > 2.0
    assert next(n for n in notes if n["note"] == "served")["served_ok"]
    for name in ("moe_experts_roofline", "moe_device_share",
                 "paged_attn_roofline.saturated"):
        assert name not in r["metrics"]     # no device trace on the CPU
    if trace:
        # 4 forwards a block of 4 tokens since the store pass rides the
        # next block's first pass (PR 34; the published loop's 5 read
        # 1.25), a little off where a prompt's tail opened a block or a
        # stream ended inside one
        assert 0.9 <= r["metrics"][NEW_METRIC]["value"] <= 1.05
        # every expert is held: 4 slots x 4 rows x top-2 over 8 experts
        assert 1.0 <= r["metrics"]["moe_tokens_per_expert"]["value"] <= 8.0
        assert {"decode_step_p50_ms.saturated", "prefill_p50_ms.saturated",
                "seed_slot_p50_ms.saturated"} <= set(r["metrics"])
    else:
        assert set(r["metrics"]) == {"saturated_tokens_per_s", "gap_p99_ms",
                                     "setup_s"}
