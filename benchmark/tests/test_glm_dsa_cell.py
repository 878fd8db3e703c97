"""The cell ``glm_5.2.longctx_saturated`` and its adapter ``glm_dsa``,
rehearsed on the CPU at toy widths (never a device metric): the
configuration's published widths and the cut's arithmetic, the adapter's
interface and counts, the cell's own readers on recorded data, and one
closed-loop run through the serving rig with a selection that bites.

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

CELL = "glm_5.2.longctx_saturated"
NEW_METRICS = ["dsa_index_roofline", "dsa_attn_roofline", "dsa_device_share",
               "dsa_selected_share"]
SHARED_METRICS = [
    "decode_step_p50_ms.saturated", "decode_step_device_ms.saturated",
    "executor_call_ms_per_step.saturated",
    "executor_self_ms_per_step.saturated",
    "sched_self_ms_per_iteration.saturated", "prefill_p50_ms.saturated",
    "seed_slot_p50_ms.saturated", "slot_occupancy_mean.saturated",
    "idle_named_share.saturated", "moe_experts_roofline", "moe_device_share",
    "moe_tokens_per_expert", "mla_device_share",
    "decode_step_touched_hbm_roofline"]


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs", "glm_5.2.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def good():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_cell_is_in_the_manifest_with_its_metrics(good):
    manifest.validate(good)
    entry, config, workload = manifest.cell_files(good, CELL)
    assert entry["chips"] == 1 and config["name"] == "glm_5.2"
    assert entry["traffic"] == "longctx_saturated"
    assert config["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                                 "n_routed_experts", "vocab_size"]
    assert sum(w["name"] == CELL for w in good["workloads"]) == 1
    mine = {m["name"] for m in manifest.metrics_of(good, "per_layer", CELL)}
    # at least these: a later tracing PR gives the cell more
    assert set(NEW_METRICS + SHARED_METRICS) <= mine
    # a sparse read would pass what these two count as the least time
    assert not {"mla_decode_roofline", "paged_attn_roofline.saturated"} & mine
    assert {m["name"] for m in manifest.metrics_of(good, "end_to_end", CELL)} \
        == {"saturated_tokens_per_s", "gap_p99_ms", "setup_s"}
    for name in NEW_METRICS:
        entry = next(m for m in good["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL] and entry["unit"] == "%"
        assert entry["moves"] == "saturated_tokens_per_s"
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".json"))
    with open(os.path.join(ROOT, workload)) as f:
        wl = json.load(f)
    assert wl["kind"] == "serve_closed_loop" and wl["clients"] == 16
    assert wl["prompt"] == {"median": 6144, "sigma": 0.5, "min": 2304,
                            "cap": 16384}
    assert wl["output"] == {"median": 1024, "sigma": 0.5, "min": 128,
                            "cap": 2048}
    assert wl["sample_seed"] == 38 and wl["trace_seconds"] == 5.0
    assert wl["reference_prompts"] == [1500, 5000, 15000]
    for key in ("why", "lengths_why", "logits_tol_why", "served_check_why"):
        assert wl[key]


def test_every_published_key_is_unchanged_but_the_reduced(cfg):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(l) for l in f if l.strip()]
    published = next(r for r in rows if r["name"] == "GLM-5.2")
    assert cfg["source"] == published["source_url"]
    changed = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
               "vocab_size": 19360}
    for key, value in published["config"].items():
        if key in changed:
            assert cfg[key] == changed[key] and key in cfg["reduced"]
            assert cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    assert cfg["experts_held"] * 32 == cfg["n_routed_experts"] == 256
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    at = cfg["layer_offset"]
    assert cfg["indexer_types"][at:at + 5] == [
        "full", "shared", "shared", "shared", "full"]
    assert cfg["mlp_layer_types"][at:at + 5] == ["dense"] + ["sparse"] * 4
    sv = cfg["serving"]
    assert (sv["num_slots"], sv["max_len"], sv["page_len"]) == (16, 18432, 64)
    assert sv["prompt_buckets"][0] == 2048 and \
        sv["prompt_buckets"][-1] == 16384
    assert sv["page_buckets"][-1] * sv["page_len"] == sv["max_len"]
    for key in ("assumed", "departures", "memory", "deployment",
                "published", "reduced", "reduced_how"):
        assert cfg[key]
    assert "shared_indexer" in cfg["assumed"]
    listed = " ".join(cfg["departures"])
    for word in ("FP8", "multi-token-prediction", "1048576", "EXACT"):
        assert word in listed, word


def test_padded_rows_stay_under_a_quarter_of_the_samples_prefill(cfg):
    from lib import closedloop
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        wl = json.load(f)
    sample = closedloop.Sample(wl)
    prompts = [sample.take()["prompt_len"] for _ in range(400)]
    buckets = cfg["serving"]["prompt_buckets"]
    padded = [min(b for b in buckets if b >= n) for n in prompts]
    assert min(prompts) == 2304 > cfg["index_topk"]
    assert 1 - sum(prompts) / sum(padded) < 0.25


def test_the_adapter_has_the_interface_and_the_issues_counts(cfg):
    adapter = models.adapter_of(cfg)
    assert all(callable(getattr(adapter, n)) for n in models.INTERFACE)
    # the issue's arithmetic: MLA 165.0M, an indexer 9.4M, an expert 37.75M
    assert adapter.mla_params(cfg) == 6144 * 2048 + 2048 * 64 * 256 \
        + 6144 * 576 + 512 * 64 * 448 + 16384 * 6144
    assert round(adapter.mla_params(cfg) / 1e6, 1) == 165.0
    assert adapter.indexer_params(cfg) == 2048 * 32 * 128 + 6144 * 128 \
        + 6144 * 32
    assert adapter.expert_bytes(cfg) == 3 * 6144 * 2048 * 2
    assert adapter.full_layers(cfg) == [0, 4]
    assert adapter.sparse_layers(cfg) == [1, 2, 3, 4]
    # 2.67B parameters = 5.35 GB
    assert round(adapter.param_count(cfg) / 1e9, 2) == 2.67
    assert 5.33e9 < 2 * adapter.param_count(cfg) < 5.37e9
    assert adapter.kv_bytes_per_row(cfg) == 5 * 576 * 2
    assert adapter.index_bytes_per_row(cfg) == 2 * 128 * 2
    assert adapter.index_flops_per_row(cfg) == 2 * 32 * 128 * 2
    assert adapter.mla_decode_flops_per_row(cfg) == 5 * 64 * (576 + 512) * 2
    held = 4 * 8 * adapter.expert_bytes(cfg)
    base = adapter.decode_weight_bytes(cfg) - held
    assert adapter.decode_step_bytes(cfg, 0, 16, 0) == base
    # 16 slots of 7,500 rows: every key is scored, 2048 rows a slot attended
    assert adapter.decode_step_bytes(cfg, 12, 16, 120000) == base \
        + 12 * adapter.expert_bytes(cfg) + 120000 * 512 + 16 * 2048 * 5760
    # below index_topk a slot: every live row
    assert adapter.decode_step_bytes(cfg, 0, 16, 1000) == base \
        + 1000 * (512 + 5760)
    assert adapter.bundle_key(cfg)[1] == cfg["serving"]


def test_the_reference_imports_no_program():
    with open(os.path.join(BENCH, "reference", "glm_dsa_ref.py")) as f:
        text = f.read()
    assert "import paddle_tpu" not in text and "from paddle_tpu" not in text
    assert '"highest"' in text and "jax.lax.top_k" in text


def test_the_harness_still_names_no_model():
    for folder in ("traffic", "lib"):
        for name in sorted(os.listdir(os.path.join(BENCH, folder))):
            if name.endswith(".py"):
                with open(os.path.join(BENCH, folder, name)) as f:
                    text = f.read()
                assert "glm" not in text and "latent_moe" not in text, name


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


def _steps(n, scored, selected):
    return [{"name": "gen.decode_step", "attrs": {
        "dsa_rows_scored": scored, "dsa_rows_selected": selected}}] * n


def test_the_rooflines_on_recorded_data(cfg, monkeypatch):
    from lib import decode_ops, peaks
    # 100 steps, 16 slots of 7,500 rows, two indexers
    run = {"config": cfg, "peaks": peaks.PEAKS["TPU v5 lite"],
           "spans": _steps(100, 2 * 120000, 2 * 16 * 2048)}
    monkeypatch.setattr(decode_ops, "op_seconds_in_runs",
                        lambda run, events, holding: (0.050, 100))
    module, spec = _reader("dsa_index_roofline")
    # memory-bound: 512 B a row at 819 GB/s against 16 kFLOP at 197 T
    assert module.read(run, spec) == pytest.approx(
        100.0 * 100 * 120000 * 512 / 819e9 / 0.050)
    module, spec = _reader("dsa_attn_roofline")
    assert module.read(run, spec) == pytest.approx(
        100.0 * 100 * 16 * 2048 * 5760 / 819e9 / 0.050)
    module, spec = _reader("dsa_selected_share")
    assert module.read(run, spec) == pytest.approx(100 * 32768 / 120000)
    # no such scope in the trace, or no such attribute on the spans
    monkeypatch.setattr(decode_ops, "op_seconds_in_runs",
                        lambda run, events, holding: None)
    for name in ("dsa_index_roofline", "dsa_attn_roofline"):
        module, spec = _reader(name)
        assert module.read(run, spec) is None
    module, spec = _reader("dsa_selected_share")
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
    name="toy_sparse", hidden_size=64, vocab_size=256, num_hidden_layers=5,
    first_k_dense_replace=1, layer_offset=2, num_attention_heads=4,
    q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, index_topk=8, index_n_heads=4,
    index_head_dim=16, intermediate_size=96, moe_intermediate_size=32,
    n_routed_experts=16, num_experts_per_tok=2, experts_held=8,
    expert_offset=0,
    serving=dict(num_slots=4, max_len=128, page_len=8,
                 prompt_buckets=[8, 32, 64], page_buckets=[1, 4, 8, 16])),
    "workload": dict(clients=4,
                     prompt=dict(median=24, sigma=0.5, min=10, cap=64),
                     output=dict(median=8, sigma=0.5, min=2, cap=24),
                     # at these widths ONE of a row's 8 selected rows
                     # that flips under bfloat16 moves the logits by half
                     # their range (the reference run in bfloat16 reads
                     # 0.01-0.58 against the float32 one): the rehearsal
                     # holds the machinery, tests/test_glm_dsa.py the
                     # numbers, in float32
                     reference_prompts=[6, 20, 50], trace_seconds=0.5,
                     logits_tol=0.95, served_check=dict(streams=4,
                                                        limit=0.95))}


@pytest.mark.parametrize("trace", [0, 2])
def test_the_cell_rehearses_through_the_serving_rig(trace, capsys):
    import run
    r = run.run_cell(CELL, 2 ** 31 + 38, 3.0, trace, rehearsal=TOY)
    notes = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    said = next(n for n in notes if n["note"] == "verdict")
    assert r["rehearsal"] and r["correct"] and r["failed"] == 0, said
    assert {"saturated_tokens_per_s", "gap_p99_ms", "setup_s"} \
        <= set(r["metrics"])
    assert next(n for n in notes if n["note"] == "served")["served_ok"]
    for name in ("dsa_index_roofline", "dsa_attn_roofline",
                 "dsa_device_share", "moe_experts_roofline"):
        assert name not in r["metrics"]     # no device trace on the CPU
    if trace:
        # every stream is past index_topk 8 from its first step: a slot
        # of ~35 rows keeps 8 of them
        share = r["metrics"]["dsa_selected_share"]["value"]
        assert 10.0 < share < 60.0
        assert {"decode_step_p50_ms.saturated", "prefill_p50_ms.saturated",
                "seed_slot_p50_ms.saturated", "moe_tokens_per_expert"} \
            <= set(r["metrics"])
    else:
        assert set(r["metrics"]) == {"saturated_tokens_per_s", "gap_p99_ms",
                                     "setup_s"}


def test_the_selection_off_control_reads_far_from_the_reference():
    """What the cell's ``logits_tol`` has to fail at the published widths,
    at the toy's: the float32 reference with the selection switched off
    reads a quarter of the logits' range and more from the reference."""
    import jax.numpy as jnp
    import numpy as np
    from lib import serving_rig as rig
    cfg = {**json.load(open(os.path.join(BENCH, "configs", "glm_5.2.json"))),
           **TOY["config"]}
    adapter = models.adapter_of(cfg)
    weights = adapter.seeded_weights(cfg, 5)
    prompt = jnp.asarray(rig._prompt(cfg, 5, 0, 50), jnp.int32)
    at = jnp.asarray([49])
    want = np.asarray(adapter.reference_logits(weights, cfg, prompt, at))
    dense = np.asarray(adapter.control_logits(weights, cfg, prompt, at,
                                              "dense"))
    spread = want.max() - want.min()
    assert np.abs(dense - want).max() / spread > 0.25
