"""The readers PR 51 adds, on hand-made runs (CPU, no protobuf needed:
``run["_scoped_planes"]`` is filled in as ``lib.decode_ops.scoped_planes``
would fill it): the decode executable's partition by sublayer group, the
admission executables' run length and their share of the busy time.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_decode_scope_metrics.py -q -p no:cacheprovider
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

from lib import manifest, scoperuns  # noqa: E402

TURN = "jit(turn)/pt_step/gen_decode/"
CHUNK = "jit(step)/pt_step/gen_chunk/"
PREFILL = "jit(step)/pt_step/gen_prefill/"
GROUPS = ["decode_attn_device_ms", "decode_mixer_device_ms",
          "decode_experts_device_ms", "decode_dense_device_ms",
          "decode_head_device_ms"]
PARTITION = GROUPS + ["decode_other_device_ms"]
NINE = ["admission_device_share", "admission_run_device_ms"] + PARTITION \
    + ["decode_experts_glue_device_ms"]
MS = 1e6        # ns in a ms


def _decode_rows(mixer=True):
    """A decode run's leaf events ``(name, scope, ms)``."""
    rows = [
        ("fusion.1", TURN + "embed/ptop_lookup_table__e/gather", 1),  # other
        ("fusion.2", TURN + "attn/ptop_rms_norm__n/rsqrt", 2),  # attn
        ("paged_attention.3", TURN + "attn/ptop_paged_attention__c/pallas_call", 3),  # attn
        ("fusion.4", TURN + "mixer/ptop_kda_update__s/mul", 4),  # mixer
        ("fusion.5", TURN + "experts/ptop_moe_route__r/top_k", 5),  # experts
        ("fusion.6", TURN + "experts/ptop_moe_experts_gated__o/sort", 6),  # experts, glue
        ("gmm.7", TURN + "experts/ptop_moe_experts_gated__o/jit(gmm)/pallas_call", 7),  # experts
        ("fusion.8", TURN + "dense/ptop_swiglu__a/mul", 8),  # dense
        ("fusion.9", TURN + "head/ptop_matmul__l/dot_general", 9),  # head
        ("fusion.10", "jit(turn)/gen_turn/argmax", 10),  # other
        ("copy-done.11", "", 11),  # other
        ("fusion.12", TURN + "mtp/attn/ptop_paged_attention__m/pallas_call", 12),  # attn
        ("fusion.13", TURN + "mtp/head/ptop_matmul__d/dot_general", 13),  # head
        ("fusion.14", TURN + "mtp/embed/ptop_matmul__p/dot_general", 14),  # other
    ]
    return [r for r in rows if mixer or "/mixer/" not in r[1]]


def _plane(scale=1.0, mixer=True, scoped=True):
    """One chip: a decode run, a chunk run (7 ms), a decode run, a seeding
    run (2 ms), a second chunk run (9 ms), with a ms of idle between the
    runs.  ``scoped`` False: the same events as the parent of PR 51 names
    them (``ptop_`` scopes alone)."""
    leaves, modules, t = [], [], 0.0

    def a_run(rows):
        nonlocal t
        start = t
        for name, scope, ms in rows:
            leaves.append((t, t + ms * MS * scale, name, scope))
            t += ms * MS * scale
        modules.append((start, t))
        t += MS

    decode = _decode_rows(mixer)
    a_run(decode)
    a_run([("fusion.20", CHUNK + "attn/ptop_mla_attention_chunk__c/dot", 4),
           ("fusion.21", CHUNK + "experts/ptop_moe_experts_gated__o/sort",
            3)])
    a_run(decode)
    a_run([("fusion.30", "jit(_seed_pool)/gen_seed/while/body/"
            "dynamic_update_slice", 2)])
    a_run([("fusion.22", PREFILL + "attn/ptop_gqa_attention__c/dot", 9)])
    if not scoped:
        for role in ("gen_decode/", "gen_chunk/", "gen_prefill/", "gen_turn/",
                     "gen_seed/", "mtp/", "embed/", "attn/", "mixer/",
                     "experts/", "dense/", "head/"):
            leaves = [(s, e, n, scope.replace(role, ""))
                      for s, e, n, scope in leaves]
    return leaves, modules


def _run(planes, busy_ms=None):
    return {"_scoped_planes": planes, "spans": [], "counters": {},
            "facts": {}, "chips": len(planes or ()) or 1,
            "trace": {"busy_s": busy_ms / 1e3} if planes else None,
            "trace_window_s": 1.0, "session": None}


def _read(name, run):
    import run as harness
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"]
                     if m["name"] == name)
    got = harness.read_layer_metrics([entry], run)
    return got[name]["value"] if got else None


DECODE_MS = sum(range(1, 15))      # one decode run's events, 105 ms


def test_the_groups_are_disjoint_and_sum_with_other_to_the_busy_time():
    # two chips, the second twice as slow: per run, over both
    planes = {"/device:TPU:0": _plane(), "/device:TPU:1": _plane(2.0)}
    run = _run(planes, busy_ms=(2 * DECODE_MS + 18) * 1.5)
    per = 1.5
    want = {"decode_attn_device_ms": (2 + 3 + 12) * per,
            "decode_mixer_device_ms": 4 * per,
            "decode_experts_device_ms": (5 + 6 + 7) * per,
            "decode_dense_device_ms": 8 * per,
            "decode_head_device_ms": (9 + 13) * per,
            "decode_other_device_ms": (1 + 10 + 11 + 14) * per,
            "decode_experts_glue_device_ms": 6 * per}
    got = {name: _read(name, run) for name in want}
    assert got == {k: pytest.approx(v) for k, v in want.items()}
    assert sum(got[n] for n in PARTITION) == pytest.approx(DECODE_MS * per)
    # disjoint: every event of a decode run lies in at most one group
    import run as harness
    reader = harness.load_module(os.path.join(
        BENCH, "layer_metrics", "decode_scope_device_ms.py"), "dsd")
    _, _, rows = reader._rows(run, ["/gen_decode/"])
    for row in rows:
        hits = [g for g in GROUPS
                if reader.group_seconds([row], reader._spec_of(g))]
        assert len(hits) <= 1, (row, hits)


def test_the_glue_is_the_routed_product_without_its_grouped_products():
    run = _run({"/device:TPU:0": _plane()}, busy_ms=2 * DECODE_MS + 18)
    # the sort under ptop_moe_experts*, not the gmm kernel (whose PATH
    # holds jit(gmm) as the glue inside that function would), not the
    # router, and not the chunk's sort
    assert _read("decode_experts_glue_device_ms", run) == pytest.approx(6)


def test_admission_runs_and_share():
    run = _run({"/device:TPU:0": _plane()}, busy_ms=2 * DECODE_MS + 18)
    # runs found by role: the chunk (7 ms) and the prefill (9 ms); the
    # seeding call is no prefill run, but it is admission time
    assert _read("admission_run_device_ms", run) == pytest.approx(8.0)
    assert _read("admission_device_share", run) == pytest.approx(
        100.0 * 18 / (2 * DECODE_MS + 18))


def test_a_cell_without_a_mixer_reads_none_for_it_and_zero_for_dense():
    leaves, modules = _plane(mixer=False)
    leaves = [ev for ev in leaves if "/dense/" not in ev[3]]
    run = _run({"/device:TPU:0": (leaves, modules)}, busy_ms=200)
    assert _read("decode_mixer_device_ms", run) is None
    assert _read("decode_dense_device_ms", run) == 0.0
    assert _read("decode_attn_device_ms", run) == pytest.approx(2 + 3 + 12)
    # the remainder still closes the partition
    assert _read("decode_other_device_ms", run) + sum(
        _read(n, run) or 0.0 for n in GROUPS) == pytest.approx(
        DECODE_MS - 4 - 8)


@pytest.mark.parametrize("name", NINE)
def test_a_trace_without_the_roles_reads_none(name):
    """The parent of PR 51 writes ``ptop_`` scopes and no role or group:
    every one of the nine is absent, not 0, and nothing raises."""
    run = _run({"/device:TPU:0": _plane(scoped=False)}, busy_ms=300)
    assert _read(name, run) is None


@pytest.mark.parametrize("name", NINE)
def test_no_device_trace_reads_none(name):
    assert _read(name, _run(None)) is None
    assert _read(name, _run({})) is None


def test_the_remainder_is_never_negative():
    # leaf events of one chip can overlap: their sum passes the union
    leaves, modules = _plane()
    start, end = modules[0]
    leaves = leaves + [(start, end, "fusion.99",
                        TURN + "attn/ptop_matmul__q/dot_general")]
    run = _run({"/device:TPU:0": (sorted(leaves), modules)}, busy_ms=300)
    assert _read("decode_other_device_ms", run) >= 0.0


def test_an_event_belongs_to_the_run_it_starts_in():
    leaves = [(0.0, 2 * MS, "fusion.1", TURN + "attn/ptop_matmul__q/dot"),
              (5 * MS, 6 * MS, "fusion.2", TURN + "head/ptop_matmul__l/dot"),
              (9 * MS, 9.5 * MS, "fusion.3", "")]        # in no run
    modules = [(0.0, 3 * MS), (5 * MS, 7 * MS)]
    run = _run({"/device:TPU:0": (leaves, modules)}, busy_ms=3.5)
    held = scoperuns.runs(run)[0]
    assert [len(events) for _, _, events in held] == [1, 1]
    assert _read("decode_attn_device_ms", run) == pytest.approx(2 / 2)
    assert scoperuns.main_path("a/ptop_x:;b/ptop_y:;b/ptop_y:") == "b/ptop_y"


def test_the_new_entries_and_their_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        good = manifest.validate(json.load(f))
    closed = [w["name"] for w in good["workloads"]
              if w["traffic"].endswith("_saturated")]
    # membership, not count: the eight closed-loop cells PR 51 gave the
    # nine to; a later cell joins them behind
    eight = closed[:8]
    assert eight[0] == "genlm_opt6.7b.decode_saturated" \
        and eight[-1] == "solar_open2_250b.longgen_saturated"
    by_name = {m["name"]: m for m in good["per_layer"]}
    sparse = [c for c in eight if not c.startswith("genlm_")]
    mixers = ["nemotron3_super_ep8.decode_saturated",
              "solar_open2_250b.longgen_saturated"]
    cells = {"decode_mixer_device_ms": mixers,
             "decode_experts_device_ms": sparse,
             "decode_experts_glue_device_ms": sparse}
    for name in NINE:
        entry = by_name[name]
        listed = [c for c in entry["workloads"] if c in eight]
        assert listed == cells.get(name, eight), name
        assert set(entry["workloads"]) <= set(closed), name
        assert entry["better"] == "lower"
        assert entry["source"] == "device_trace"
        assert entry["layer"] == "Lowerings + kernels"
        assert entry["unit"] == ("%" if name.endswith("_share") else "ms")
        assert entry["moves"] == ("gap_p99_ms" if name
                                  == "admission_run_device_ms"
                                  else "saturated_tokens_per_s")
    # the nine are there, in the order they were accepted in (where in
    # the list is a later PR's business)
    assert [m["name"] for m in good["per_layer"] if m["name"] in NINE] == [
        "admission_device_share", "admission_run_device_ms"] + GROUPS[:4] \
        + ["decode_head_device_ms", "decode_other_device_ms",
           "decode_experts_glue_device_ms"]
    with open(os.path.join(BENCH, "layer_metrics",
                           "decode_other_device_ms.json")) as f:
        assert json.load(f)["minus"] == GROUPS
