"""The cell ``xing4.0_29b_a4b.longprompt_saturated`` and its adapter
``xing4``, rehearsed on the CPU at toy widths (never a device metric): the
configuration's published widths and the cut's arithmetic, the adapter's
interface and counts (two query rows a slot a turn), the draw-time
calibration (a router's offset under four streams, the levelling, the
head's cosine), the cell's own readers on recorded data, and one
closed-loop run through the serving rig with four streams and the MTP
module drafting.

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

CELL = "xing4.0_29b_a4b.longprompt_saturated"
NEW_METRICS = ["mhc_device_share", "decode_mhc_device_ms",
               "mhc_chunk_roofline"]
SHARED_METRICS = [
    "decode_step_p50_ms.saturated", "decode_step_device_ms.saturated",
    "executor_call_ms_per_step.saturated",
    "executor_self_ms_per_step.saturated",
    "sched_self_ms_per_iteration.saturated", "prefill_p50_ms.saturated",
    "seed_slot_p50_ms.saturated", "slot_occupancy_mean.saturated",
    "idle_named_share.saturated", "decode_dispatch_p50_ms.saturated",
    "admission_device_share", "admission_run_device_ms",
    "decode_attn_device_ms", "decode_experts_device_ms",
    "decode_dense_device_ms", "decode_head_device_ms",
    "decode_other_device_ms", "decode_experts_glue_device_ms",
    "moe_experts_roofline", "moe_device_share", "moe_tokens_per_expert",
    "decode_step_touched_hbm_roofline", "mla_decode_roofline",
    "mla_device_share", "mtp_device_share", "spec_accept_rate",
    "spec_tokens_per_slot_turn"]


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs", "xing4.0_29b_a4b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def good():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_cell_is_in_the_manifest_with_its_metrics(good):
    """PRESENT, not last and not a count: a later PR appends."""
    manifest.validate(good)
    entry, config, workload = manifest.cell_files(good, CELL)
    assert entry["chips"] == 1 and config["name"] == "xing4.0_29b_a4b"
    assert entry["traffic"] == "longprompt_saturated"
    assert config["reduced"] == ["num_hidden_layers",
                                 "first_k_dense_replace", "n_routed_experts",
                                 "vocab_size"]
    mine = {m["name"] for m in manifest.metrics_of(good, "per_layer", CELL)}
    assert mine >= set(NEW_METRICS + SHARED_METRICS)
    assert {m["name"] for m in manifest.metrics_of(good, "end_to_end", CELL)} \
        == {"saturated_tokens_per_s", "gap_p99_ms", "setup_s"}
    moves = {"mhc_device_share": "saturated_tokens_per_s",
             "decode_mhc_device_ms": "saturated_tokens_per_s",
             "mhc_chunk_roofline": "gap_p99_ms"}
    for name in NEW_METRICS:
        entry = next(m for m in good["per_layer"] if m["name"] == name)
        assert CELL in entry["workloads"] and entry["moves"] == moves[name]
        with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
            spec = json.load(f)
        assert spec["events"] == ["/mhc/"] and spec["like"]
    with open(os.path.join(ROOT, workload)) as f:
        wl = json.load(f)
    assert wl["kind"] == "serve_closed_loop" and wl["clients"] == 32
    assert wl["prompt"] == {"median": 8192, "sigma": 0.6, "min": 2048,
                            "cap": 16384}
    assert wl["output"] == {"median": 768, "sigma": 0.5, "min": 128,
                            "cap": 1536}
    assert wl["sample_seed"] == 61 and wl["trace_seconds"] == 5.0
    assert wl["reference_prompts"] == [3000, 9000, 16000]
    assert wl["served_check"]["streams"] == 8
    for key in ("why", "clients_why", "lengths_why", "logits_tol_why",
                "served_check_why"):
        assert wl[key] and "TO BE" not in wl[key], key


def test_every_published_key_is_unchanged_but_the_reduced(cfg):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(l) for l in f if l.strip()]
    published = next(r for r in rows if r["name"] == "Xing4.0-29B-A4B")
    assert cfg["source"] == published["source_url"]
    changed = {"num_hidden_layers": 9, "first_k_dense_replace": 1,
               "vocab_size": 16384}
    for key, value in published["config"].items():
        if key in changed:
            assert cfg[key] == changed[key] and key in cfg["reduced"]
            assert cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    # no width, rank, head count, stream count, round, top-4 or depth moved
    assert (cfg["hc_mult"], cfg["hc_sinkhorn_iters"],
            cfg["num_experts_per_tok"], cfg["num_nextn_predict_layers"]) \
        == (4, 20, 4, 1)
    assert cfg["experts_held"] * 8 == cfg["n_routed_experts"] == 64
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["layer_offset"] == 1
    sv = cfg["serving"]
    assert (sv["max_len"], sv["page_len"]) == (18432, 64)
    assert sv["num_slots"] in (32, 24)      # 24: the probe's fallback
    assert sv["prompt_buckets"][-1] == 16384
    assert sv["page_buckets"][-1] * sv["page_len"] == sv["max_len"]
    for key in ("assumed", "departures", "memory", "deployment",
                "published", "reduced", "reduced_how", "serving_why"):
        assert cfg[key] and "TO BE" not in json.dumps(cfg[key]), key
    for key in ("hyper_connections", "sinkhorn", "streams_in_and_out", "mtp",
                "yarn", "rotary_pair_layout", "router", "acceptance",
                "weights"):
        assert key in cfg["assumed"], key
    assert "arXiv:2512.24880" in cfg["assumed"]["hyper_connections"]
    assert "ROWS sum to one" in cfg["assumed"]["router"]
    listed = " ".join(cfg["departures"])
    for word in ("262144", "8-chip", "GREEDY", "depth 1", "random weights"):
        assert word in listed, word


def test_the_samples_lengths_are_the_issues(cfg):
    from lib import closedloop
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        wl = json.load(f)
    sample = closedloop.Sample(wl)
    taken = [sample.take() for _ in range(4000)]
    prompts = [r["prompt_len"] for r in taken]
    outputs = [r["max_new"] for r in taken]
    assert min(prompts) == 2048 and max(prompts) == 16384 \
        == cfg["serving"]["prompt_buckets"][-1]
    # the draft's row behind the last committed one fits the pool
    assert max(prompts) + max(outputs) + 1 <= cfg["serving"]["max_len"]
    assert 8500 < sum(prompts) / len(prompts) < 9600
    assert 800 < sum(outputs) / len(outputs) < 900
    # under and past YaRN's original 4096, the largest bucket
    original = cfg["rope_scaling"]["original_max_position_embeddings"]
    refs = wl["reference_prompts"]
    assert refs[0] < original < refs[1] < refs[2] <= 16384


def test_the_adapter_has_the_interface_and_the_issues_counts(cfg):
    adapter = models.adapter_of(cfg)
    assert all(callable(getattr(adapter, n)) for n in models.INTERFACE)
    # the issue's arithmetic: attention 28.41M a block, an expert 11.01M,
    # a wrapper 0.344M float32; the held cut with its MTP module 1.43B
    # parameters = 2.85 GB
    assert round(adapter.mla_params(cfg) / 1e6, 2) == 28.41
    assert adapter.expert_bytes(cfg) == 3 * 3584 * 1024 * 2
    assert adapter.mhc_params(cfg) == 2 * (14336 * 24 + 3 + 24)
    assert round(adapter.param_count(cfg) / 1e9, 2) == 1.42
    assert round((2 * adapter.param_count(cfg)
                  + 4 * 10 * adapter.mhc_params(cfg)) / 1e9, 2) == 2.87
    assert adapter.blocks(cfg) == list(range(9)) + [adapter.MTP]
    assert adapter.moe_layers(cfg) == list(range(1, 9)) + [adapter.MTP]
    # a live row: 576 values in each of ten pools; both query rows of a
    # turn read them once, and score them twice
    assert adapter.kv_bytes_per_row(cfg) == 10 * 576 * 2
    assert adapter.mla_decode_flops_per_row(cfg) \
        == 2 * 10 * 32 * (576 + 512) * 2
    # a wrapper's least form: (2 x 4 + 2) x 3584 x 2 B a row
    assert adapter.mhc_bytes_per_row(cfg) == 71680
    assert adapter.mhc_wrappers(cfg) == 20
    d, v = 3584, 16384
    assert adapter.decode_weight_bytes(cfg) \
        == adapter.param_count(cfg) * 2 + 10 * adapter.mhc_params(cfg) * 4
    held = 9 * 8 * adapter.expert_bytes(cfg)
    base = adapter.decode_weight_bytes(cfg) - held
    assert adapter.decode_step_bytes(cfg, 0, 32, 0) == base
    assert adapter.decode_step_bytes(cfg, 40, 32, 300000) == base \
        + 40 * adapter.expert_bytes(cfg) + 300000 * 11520
    assert adapter.bundle_key(cfg)[1] == cfg["serving"]
    assert all(k in adapter.control_logits.__doc__ for k in adapter.CONTROLS)
    assert {"fp8", "mhc_static", "sinkhorn_1", "streams_mean", "draft"} \
        <= set(adapter.CONTROLS)


@pytest.mark.parametrize("seed", [2147487001, 11])
def test_the_calibration_lowers_every_logit_and_has_no_favourites(cfg, seed):
    """Under four streams the constant channel reaches a router through
    H_pre: the offset row made at draw time lowers the experts' logits by
    ROUTER_OFFSET on average on rows the calibration did not see, the
    held experts take about their eighth in every sparse block, and the
    head's cosine is the last summed residual's with its token's
    embedding."""
    import jax.numpy as jnp
    import numpy as np
    from models import xing4 as adapter
    ref = adapter.ref
    toy = dict(cfg, hidden_size=128, num_attention_heads=4, q_lora_rank=48,
               kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
               v_head_dim=16, intermediate_size=384,
               moe_intermediate_size=64, vocab_size=1024,
               num_hidden_layers=4)
    w = adapter.seeded_weights(toy, seed)
    ids = jnp.asarray(np.random.RandomState(seed % 2 ** 32).randint(
        0, toy["vocab_size"], 512), jnp.int32)
    value = ref._values(w, jnp.float32, None)
    eps = toy["rms_norm_eps"]
    x = ref._copy_in(ref._embed(w, ids, jnp.float32, None), toy)
    for i in range(toy["num_hidden_layers"]):
        p = lambda name, cast=True: value(f"lat{i}_{name}", cast)
        seen = {}

        def feed_forward(u):
            h = ref._rms(u, p("norm2.scale"), eps)
            if ref.is_moe(toy, i):
                seen["offset"] = h[:, :1] * p("gate.w")[:1].astype(
                    jnp.float32)
                seen["idx"], _ = ref.route(h, p, toy)
            return ref.ffn(h, p, toy, i, jnp.float32)

        x = ref.wrapped(x, lambda u: ref.attention(
            ref._rms(u, p("norm1.scale"), eps), p, toy, jnp.float32), p,
            "hc1", toy)
        x = ref.wrapped(x, feed_forward, p, "hc2", toy)
        # every stream still holds the constant channel
        np.testing.assert_allclose(np.asarray(x[:, :, 0]),
                                   toy["hidden_size"] ** 0.5 / 2, rtol=2e-2)
        if ref.is_moe(toy, i):
            assert -31 < float(jnp.mean(seen["offset"])) < -25
            assert float(jnp.max(seen["offset"])) < -5
            share = float(np.mean(np.asarray(seen["idx"])
                                  < toy["experts_held"]))
            assert 0.5 / 8 < share < 1.7 / 8, (i, share * 8)


def test_the_calibration_compiles_for_a_v5e_at_the_published_widths(cfg):
    """What the rig runs at draw time beside two copies of the weights:
    compiled here for a described chip, its scratch under a few hundred
    MB."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from models import xing4 as adapter
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    chip = SingleDeviceSharding(topo.devices[0])
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    shapes = jax.eval_shape(lambda: adapter.seeded_weights(
        dict(cfg, vocab_size=256), 5))
    w = {k: jax.ShapeDtypeStruct(
        (v, d) if k == "lat_emb" else s.shape, s.dtype, sharding=chip)
        for k, s in shapes.items() if k != "lat_head.w"}
    ids = jax.ShapeDtypeStruct((adapter.LEVEL_ROWS,), jnp.int32,
                               sharding=chip)
    compiled = jax.jit(functools.partial(adapter._calibrated, cfg)) \
        .lower(w, ids).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 512 * 2 ** 20
    made, cosine = jax.eval_shape(
        functools.partial(adapter._calibrated, cfg), w, ids)
    assert sorted(made) == sorted(
        f"lat{i}_gate.w" for i in adapter.moe_layers(cfg))
    assert cosine.shape == ()


def test_the_reference_imports_no_program():
    path = os.path.join(BENCH, "reference", "xing4_ref.py")
    with open(path) as f:
        text = f.read()
    assert "import paddle_tpu" not in text and "from paddle_tpu" not in text
    assert '"highest"' in text and "pallas" not in text
    assert "def draft_logits" in text and "def forward_logits" in text
    assert "def sinkhorn" in text and "for _ in range" in text
    # the adapter's first import is what the parent lacks
    with open(os.path.join(BENCH, "models", "xing4.py")) as f:
        imports = [l for l in f.read().splitlines()
                   if l.startswith("import ")
                   or l.startswith("from ") and " import " in l]
    assert imports[1] == "from paddle_tpu.ops import mhc_ops  # noqa: F401"


def test_the_new_readers_on_recorded_data(cfg):
    """The three readers are the accepted ones' (``like``): on a run with
    no device trace they read nothing and raise nothing (the parent's
    side of a traced run), and the drafting counters read a latent
    bundle's spans as they read K-EXAONE's."""
    import run as harness
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entries = [m for m in json.load(f)["per_layer"]
                   if m["name"] in NEW_METRICS + ["spec_accept_rate",
                                                  "spec_tokens_per_slot_turn"]]
    turns = [{"name": "gen.decode_step", "attrs": {
        "slot_turns": 32, "drafted": 32, "accepted": 24, "emitted": 56,
        "rows": 64}}] * 10
    chunks = [{"name": "gen.prefill", "attrs": {
        "tokens": 1024, "rows": 1024, "mhc_rows": 20480}}] * 4
    run = {"config": cfg, "spans": turns + chunks, "facts": {},
           "trace": None}
    read = harness.read_layer_metrics(entries, run)
    assert read["spec_accept_rate"]["value"] == pytest.approx(75.0)
    assert read["spec_tokens_per_slot_turn"]["value"] == pytest.approx(1.75)
    assert not set(NEW_METRICS) & set(read)         # no device trace


# -- one closed-loop run at toy widths ------------------------------------------

TOY = {"config": dict(
    name="toy_xing4", hidden_size=64, vocab_size=256, num_hidden_layers=3,
    layer_offset=1, first_k_dense_replace=1, num_attention_heads=4,
    q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
    moe_intermediate_size=32, n_routed_experts=16, num_experts_per_tok=2,
    experts_held=8, expert_offset=0,
    rope_scaling=dict(beta_fast=32, beta_slow=1, factor=4, mscale=1,
                      mscale_all_dim=1, original_max_position_embeddings=16,
                      type="yarn"),
    serving=dict(num_slots=4, max_len=128, page_len=8,
                 prompt_buckets=[8, 32, 64], page_buckets=[1, 4, 8, 16])),
    "workload": dict(clients=4,
                     prompt=dict(median=24, sigma=0.5, min=10, cap=64),
                     output=dict(median=8, sigma=0.5, min=2, cap=24),
                     # at these widths bfloat16 moves a toy's logits by a
                     # hundredth of their range and more: the rehearsal
                     # holds the machinery, tests/test_xing4.py the
                     # numbers, in float32
                     reference_prompts=[6, 20, 50], trace_seconds=0.5,
                     logits_tol=0.5, served_check=dict(streams=4,
                                                       limit=0.95))}


@pytest.mark.parametrize("trace", [0, 2])
def test_the_cell_rehearses_through_the_serving_rig(trace, capsys):
    import run
    r = run.run_cell(CELL, 2 ** 31 + 61, 3.0, trace, rehearsal=TOY)
    notes = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    said = next(n for n in notes if n["note"] == "verdict")
    assert r["rehearsal"] and r["correct"] and r["failed"] == 0, said
    assert {"saturated_tokens_per_s", "gap_p99_ms", "setup_s"} \
        <= set(r["metrics"])
    assert next(n for n in notes if n["note"] == "served")["served_ok"]
    if trace:
        # the program's counts, not the device's: a CPU has no trace
        assert 0 < r["metrics"]["spec_accept_rate"]["value"] < 100
        assert 1 < r["metrics"]["spec_tokens_per_slot_turn"]["value"] < 2
        for name in NEW_METRICS + ["mtp_device_share",
                                   "mla_decode_roofline"]:
            assert name not in r["metrics"]
