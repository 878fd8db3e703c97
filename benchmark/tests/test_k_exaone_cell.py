"""The cell ``k_exaone_236b_a23b.selfdraft_saturated`` and its adapter
``k_exaone``, rehearsed on the CPU at toy widths (never a device metric):
the configuration's published widths and the cut's arithmetic, the
adapter's interface and counts (two query rows a slot a turn), the cell's
own readers on recorded data, and one closed-loop run through the serving
rig with the MTP module drafting.

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

CELL = "k_exaone_236b_a23b.selfdraft_saturated"
NEW_METRICS = ["spec_accept_rate", "spec_tokens_per_slot_turn",
               "mtp_device_share"]
SHARED_METRICS = [
    "decode_step_p50_ms.saturated", "decode_step_device_ms.saturated",
    "executor_call_ms_per_step.saturated",
    "executor_self_ms_per_step.saturated",
    "sched_self_ms_per_iteration.saturated", "prefill_p50_ms.saturated",
    "seed_slot_p50_ms.saturated", "slot_occupancy_mean.saturated",
    "idle_named_share.saturated", "paged_attn_roofline.saturated",
    "moe_experts_roofline", "moe_device_share", "moe_tokens_per_expert",
    "decode_step_touched_hbm_roofline", "window_attn_device_share",
    "window_decode_roofline", "kv_rows_read_share",
    "decode_dispatch_p50_ms.saturated", "window_prefill_roofline",
    "gqa_prefill_roofline"]


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs",
                           "k_exaone_236b_a23b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def good():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_cell_is_in_the_manifest_with_its_metrics(good):
    manifest.validate(good)
    entry, config, workload = manifest.cell_files(good, CELL)
    assert entry["chips"] == 1 and config["name"] == "k_exaone_236b_a23b"
    assert entry["traffic"] == "selfdraft_saturated"
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    mine = {m["name"] for m in manifest.metrics_of(good, "per_layer", CELL)}
    # at least these: a later PR may put the cell on further lists
    assert mine >= set(NEW_METRICS + SHARED_METRICS)
    assert {m["name"] for m in manifest.metrics_of(good, "end_to_end", CELL)} \
        == {"saturated_tokens_per_s", "gap_p99_ms", "setup_s"}
    for name in NEW_METRICS:
        entry = next(m for m in good["per_layer"] if m["name"] == name)
        assert CELL in entry["workloads"]
        assert entry["moves"] == "saturated_tokens_per_s"
    with open(os.path.join(ROOT, workload)) as f:
        wl = json.load(f)
    assert wl["kind"] == "serve_closed_loop" and wl["clients"] == 32
    assert wl["prompt"] == {"median": 512, "sigma": 0.8, "min": 160,
                            "cap": 4096}
    assert wl["output"] == {"median": 1024, "sigma": 0.5, "min": 128,
                            "cap": 2048}
    assert wl["sample_seed"] == 45 and wl["trace_seconds"] == 5.0
    assert wl["reference_prompts"] == [300, 1900, 4000]
    assert wl["served_check"]["streams"] == 8
    for key in ("why", "clients_why", "lengths_why", "logits_tol_why",
                "served_check_why"):
        assert wl[key] and "PENDING" not in wl[key], key


def test_every_published_key_is_unchanged_but_the_reduced(cfg):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(l) for l in f if l.strip()]
    published = next(r for r in rows if r["name"] == "K-EXAONE-236B-A23B")
    assert cfg["source"] == published["source_url"]
    changed = {"num_hidden_layers": 5, "vocab_size": 19200}
    for key, value in published["config"].items():
        if key in changed:
            assert cfg[key] == changed[key] and key in cfg["reduced"]
            assert cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    assert cfg["experts_held"] * 16 == cfg["num_experts"] == 128
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["layer_types"][:5] == ["sliding_attention"] * 3 \
        + ["full_attention", "sliding_attention"]
    assert cfg["mlp_layer_types"][:5] == ["dense"] + ["sparse"] * 4
    assert len(cfg["layer_types"]) == 48                # kept whole
    assert cfg["num_nextn_predict_layers"] == 1         # and loaded
    # the window's rows and the draft's, in what tiles
    assert cfg["ring"] == 256 > cfg["sliding_window"] + 1 == 129
    sv = cfg["serving"]
    assert (sv["num_slots"], sv["max_len"], sv["page_len"]) == (32, 6144, 64)
    assert sv["prompt_buckets"] == [512, 1024, 2048, 4096]
    assert sv["page_buckets"][-1] * sv["page_len"] == sv["max_len"]
    for key in ("assumed", "departures", "memory", "deployment",
                "published", "reduced", "reduced_how", "serving_why"):
        assert cfg[key] and "PENDING" not in json.dumps(cfg[key]), key
    for key in ("norm_placement", "qk_norm", "rotary", "mtp", "acceptance",
                "router", "attention"):
        assert key in cfg["assumed"], key
    listed = " ".join(cfg["departures"])
    for word in ("262144", "16-chip", "GREEDY", "RING", "no draft"):
        assert word in listed, word


def test_the_samples_lengths_are_the_issues(cfg):
    from lib import closedloop
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        wl = json.load(f)
    sample = closedloop.Sample(wl)
    taken = [sample.take() for _ in range(4000)]
    prompts = [r["prompt_len"] for r in taken]
    outputs = [r["max_new"] for r in taken]
    # every stream is past the window at its first turn and fits max_len
    assert min(prompts) == 160 > cfg["sliding_window"]
    assert max(prompts) == 4096 == cfg["serving"]["prompt_buckets"][-1]
    assert max(prompts) + max(outputs) == cfg["serving"]["max_len"]
    assert 680 < sum(prompts) / len(prompts) < 740
    assert 1080 < sum(outputs) / len(outputs) < 1150
    refs, buckets = wl["reference_prompts"], cfg["serving"]["prompt_buckets"]
    assert all(n > cfg["sliding_window"] and n % cfg["ring"] for n in refs)
    assert len({min(b for b in buckets if b >= n) for n in refs}) == 3


def test_the_adapter_has_the_interface_and_the_issues_counts(cfg):
    adapter = models.adapter_of(cfg)
    assert all(callable(getattr(adapter, n)) for n in models.INTERFACE)
    # the issue's arithmetic: attention 113.25M a layer, an expert 37.75M,
    # the held cut with its MTP module 3.03B parameters = 6.07 GB
    assert round(adapter.attention_params(cfg) / 1e6, 2) == 113.25
    assert adapter.expert_bytes(cfg) == 3 * 6144 * 2048 * 2
    assert round(adapter.param_count(cfg) / 1e9, 2) == 3.03
    assert round(2 * adapter.param_count(cfg) / 1e9, 2) == 6.07
    assert adapter.blocks(cfg) == [0, 1, 2, 3, 4, adapter.MTP]
    assert adapter.window_layers(cfg) == [0, 1, 2, 4]
    assert adapter.full_layers(cfg) == [3, adapter.MTP]
    assert adapter.moe_layers(cfg) == [1, 2, 3, 4, adapter.MTP]
    # a live row: K and V of 8 heads of 128 in the full layer AND the MTP
    # block; both query rows of a turn read them once
    assert adapter.window_bytes_per_row(cfg) == 2 * 8 * 128 * 2
    assert adapter.kv_bytes_per_row(cfg) == 2 * 4096
    # two query rows a slot: a ring row read meets both
    assert adapter.window_flops_per_row(cfg) == 2 * 4 * 64 * 128
    # an admission's chunk: 4 window layers' band, 2 full blocks' triangle
    assert adapter.band_flops_per_pair(cfg) == 4 * 2 * 64 * (128 + 128)
    assert adapter.causal_flops_per_pair(cfg) == 2 * 2 * 64 * (128 + 128)
    # the head is read twice a turn, the embedding by row
    d, v = 6144, 19200
    assert adapter.decode_weight_bytes(cfg) \
        == (adapter.param_count(cfg) - d * v + d * v) * 2
    held = 5 * 8 * adapter.expert_bytes(cfg)
    base = adapter.decode_weight_bytes(cfg) - held
    assert adapter.decode_step_bytes(cfg, 0, 32, 0) == base
    assert adapter.decode_step_bytes(cfg, 40, 32, 48000) == base \
        + 40 * adapter.expert_bytes(cfg) + 48000 * 8192 \
        + 32 * 129 * 4 * 4096
    assert adapter.bundle_key(cfg)[1] == cfg["serving"]
    kinds = {"float8", "window_off", "draft"}
    assert all(k in adapter.control_logits.__doc__ for k in kinds)


@pytest.mark.parametrize("seed", [2147487001, 2147487038, 11])
def test_the_levelled_routers_have_no_favourites(cfg, seed):
    """Every expert is as likely as the next on rows the routers were not
    levelled on: the 8 held of 128 take about their sixteenth in every
    sparse layer, whatever the seed (as drawn, before ``level_routers``,
    a layer's held experts took 0.3 to 2 times that by seed)."""
    import jax.numpy as jnp
    import numpy as np
    from models import k_exaone as adapter
    ref = adapter.ref
    toy = dict(cfg, hidden_size=128, num_attention_heads=4,
               num_key_value_heads=2, head_dim=32, intermediate_size=384,
               moe_intermediate_size=64, vocab_size=1024, sliding_window=16,
               ring=32)
    w = adapter.seeded_weights(toy, seed)
    rows = 512
    ids = jnp.asarray(np.random.RandomState(seed % 2 ** 32).randint(
        0, toy["vocab_size"], rows), jnp.int32)
    value = ref._values(w, jnp.float32, None)
    x = ref._embed(w, ids, jnp.float32, None)
    for i in range(toy["num_hidden_layers"]):
        p = lambda name, cast=True: value(f"win{i}_{name}", cast)
        if ref.is_moe(toy, i):
            seen = x + ref.attention(
                ref._rms(x, p("norm1.scale"), toy["rms_norm_eps"]), p, toy,
                i, jnp.float32)
            idx, _ = ref.route(
                ref._rms(seen, p("norm2.scale"), toy["rms_norm_eps"]), p,
                toy)
            share = float(np.mean(np.asarray(idx) < toy["experts_held"]))
            assert 0.6 / 16 < share < 1.6 / 16, (i, share * 16)
        x = ref._block(x, value, toy, i, jnp.float32)


def test_the_levelling_compiles_for_a_v5e_at_the_published_widths(cfg):
    """What the rig runs at draw time beside two copies of the weights:
    compiled here for a described chip, its scratch a few tens of MB."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from models import k_exaone as adapter
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    chip = SingleDeviceSharding(topo.devices[0])
    shapes = jax.eval_shape(lambda: adapter.seeded_weights(cfg, 5))
    w = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=chip)
         for k, v in shapes.items() if k != "win_head.w"}
    ids = jax.ShapeDtypeStruct((adapter.LEVEL_ROWS,), jnp.int32,
                               sharding=chip)
    compiled = jax.jit(functools.partial(adapter._levelled, cfg)) \
        .lower(w, ids).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 256 * 2 ** 20
    assert sorted(jax.eval_shape(
        functools.partial(adapter._levelled, cfg), w, ids)) == sorted(
        f"win{i}_gate.w" for i in adapter.moe_layers(cfg))


def test_the_reference_imports_no_program():
    path = os.path.join(BENCH, "reference", "k_exaone_ref.py")
    with open(path) as f:
        text = f.read()
    assert "import paddle_tpu" not in text and "from paddle_tpu" not in text
    assert '"highest"' in text and "pallas" not in text
    assert "def draft_logits" in text and "def forward_logits" in text
    # the adapter's first import is what the parent lacks
    with open(os.path.join(BENCH, "models", "k_exaone.py")) as f:
        imports = [l for l in f.read().splitlines()
                   if l.startswith(("import ", "from "))]
    assert imports[1] == "from paddle_tpu.ops import spec_ops  # noqa: F401"


def test_the_new_readers_on_recorded_data(cfg):
    import run as harness
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entries = [m for m in json.load(f)["per_layer"]
                   if m["name"] in NEW_METRICS]
    turns = [{"name": "gen.decode_step", "attrs": {
        "slot_turns": 32, "drafted": 32, "accepted": 22, "emitted": 54,
        "rows": 64}}] * 10
    run = {"config": cfg, "spans": turns, "facts": {}, "trace": None}
    read = harness.read_layer_metrics(entries, run)
    assert read["spec_accept_rate"]["value"] == pytest.approx(68.75)
    assert read["spec_tokens_per_slot_turn"]["value"] == pytest.approx(
        54 / 32)
    assert "mtp_device_share" not in read       # no device trace
    # a program that does not draft: neither attribute, nothing read
    parent = dict(run, spans=[{"name": "gen.decode_step",
                               "attrs": {"live": 16, "yielded": 16}}])
    assert harness.read_layer_metrics(entries, parent) == {}


# -- one closed-loop run at toy widths ------------------------------------------

TOY = {"config": dict(
    name="toy_exaone", hidden_size=64, vocab_size=256, num_hidden_layers=5,
    layer_offset=0, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, sliding_window=8, ring=16, intermediate_size=96,
    moe_intermediate_size=32, num_experts=16, num_experts_per_tok=2,
    experts_held=8, expert_offset=0,
    serving=dict(num_slots=4, max_len=128, page_len=8,
                 prompt_buckets=[8, 32, 64], page_buckets=[1, 4, 8, 16])),
    "workload": dict(clients=4,
                     prompt=dict(median=24, sigma=0.5, min=10, cap=64),
                     output=dict(median=8, sigma=0.5, min=2, cap=24),
                     # at these widths bfloat16 moves a toy's logits by a
                     # hundredth of their range and more: the rehearsal
                     # holds the machinery, tests/test_window_moe_draft.py
                     # the numbers, in float32
                     reference_prompts=[6, 20, 50], trace_seconds=0.5,
                     logits_tol=0.5, served_check=dict(streams=4,
                                                       limit=0.95))}


@pytest.mark.parametrize("trace", [0, 2])
def test_the_cell_rehearses_through_the_serving_rig(trace, capsys):
    import run
    r = run.run_cell(CELL, 2 ** 31 + 45, 3.0, trace, rehearsal=TOY)
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
        for name in ("mtp_device_share", "moe_experts_roofline",
                     "paged_attn_roofline.saturated"):
            assert name not in r["metrics"]
