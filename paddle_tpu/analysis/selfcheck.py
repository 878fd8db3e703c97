"""``paddle_tpu selfcheck`` — every static gate in one exit-coded pass.

CI and humans need ONE command that answers "is the static story
green?": the model zoo lints clean (single-program AND as the
transpiled families the distributed verifier covers), every
scanner-enforced registry — diagnostic codes, metric names, chaos
failpoints — agrees with its documentation table, the SLO spec schema
validates (example + any armed ``PADDLE_TPU_SLO`` file), the autoscaler
policy schema validates (example + any armed ``PADDLE_TPU_AUTOSCALE``
file), and the bench trajectory's schema is intact
(``bench check --dry``).  The pytest suite
enforces the same invariants test-by-test; this module re-runs them as
a deployable command (no pytest, no tests/ checkout needed) so drift
fails a release gate, not a 3am dashboard hunt.

Each section returns ``{"name", "ok", "detail", "failures": [...]}``;
the report is ``{"ok": all-green, "sections": [...]}``.
"""

from __future__ import annotations

import os
import re
import shutil
import tempfile

import paddle_tpu

__all__ = ["run_selfcheck"]

SRC_ROOT = os.path.dirname(os.path.abspath(paddle_tpu.__file__))
DOCS_DIR = os.path.join(os.path.dirname(SRC_ROOT), "docs")

# the same scanner regexes the registry tests use (kept in lockstep by
# tests/test_selfcheck.py's agreement checks)
_CODE = re.compile(r"\bPTA\d{3}\b")
_DOC_CODE = re.compile(r"^\|\s*`(PTA\d{3})`\s*\|", re.M)
_METRIC_LITERAL = re.compile(
    r"\.(?:inc|observe|bucket|set_gauge)\(\s*[\"']([a-zA-Z0-9_.]+)[\"']")
_METRIC_LATENCY = re.compile(r"record_latency\(\s*[\"']([a-zA-Z0-9_.]+)[\"']")
_METRIC_STAGE = re.compile(
    r"\.(?:inc|observe|bucket|set_gauge)\(\s*\n?\s*self\._metrics\s*\+"
    r"\s*[\"']\.([a-zA-Z0-9_]+)[\"']")
_METRIC_MIRROR = re.compile(
    r"[\"']((?:compile|compile_cache)\.[a-zA-Z0-9_.]+)[\"']")
_DOC_METRIC = re.compile(r"^\|\s*`([a-zA-Z0-9_.<>]+)`\s*\|", re.M)
_FIRE = re.compile(
    r"\b_?chaos\.fire\(\s*\n?\s*[\"']"
    r"([a-z0-9_]+(?:\.[a-z0-9_]+)+)[\"']")
_DOC_FAILPOINT = re.compile(r"^\|\s*`([a-z0-9_.]+)`\s*\|", re.M)


def _iter_sources():
    for dirpath, _, names in os.walk(SRC_ROOT):
        for n in sorted(names):
            if n.endswith(".py"):
                with open(os.path.join(dirpath, n)) as f:
                    yield os.path.join(dirpath, n), f.read()


def _read_doc(name):
    with open(os.path.join(DOCS_DIR, name)) as f:
        return f.read()


def _section(name, detail, failures):
    return {"name": name, "ok": not failures, "detail": detail,
            "failures": list(failures)}


# ---------------------------------------------------------------------------
# zoo gates
# ---------------------------------------------------------------------------

def _check_zoo_lint():
    """Strict single-program lint: zero errors AND zero warnings across
    every zoo model's forward+backward and startup programs."""
    from paddle_tpu import analysis
    from paddle_tpu.models import ZOO_MODELS, build_train_program

    failures = []
    for name in ZOO_MODELS:
        main, startup, feeds, fetches = build_train_program(name)
        for label, prog, fd, ft in ((name, main, feeds, fetches),
                                    (f"{name}/startup", startup, None,
                                     None)):
            r = analysis.lint_program(prog, feed_names=fd, fetch_names=ft)
            for d in r.diagnostics:
                failures.append(f"[{label}] {d.severity}[{d.code}]: "
                                f"{d.message}")
    return _section("zoo-lint",
                    f"{len(ZOO_MODELS)} models, strict (warnings fail)",
                    failures)


def _check_zoo_distribute():
    """Every zoo model's DistributeTranspiler plan (sharded params over
    2 shards) verifies clean."""
    from paddle_tpu import analysis
    from paddle_tpu.analysis import ProgramVerificationError
    from paddle_tpu.models import ZOO_MODELS, build_train_program
    from paddle_tpu.parallel.distribute_transpiler import \
        DistributeTranspiler

    failures = []
    for name in ZOO_MODELS:
        main, startup, _feeds, _fetches = build_train_program(name)
        t = DistributeTranspiler()
        try:
            t.transpile(program=main, startup_program=startup,
                        pservers="a:1,b:2", shard_params=True)
        except ProgramVerificationError as e:
            failures.append(f"[{name}] {e.args[0].splitlines()[0]}")
            continue
        diags = analysis.check_distributed_spec(main, t.spec)
        for d in diags:
            failures.append(f"[{name}] {d.severity}[{d.code}]: "
                            f"{d.message}")
    return _section("zoo-distribute",
                    "DistributeTranspiler plan verification, 2 shards",
                    failures)


def _check_zoo_pipeline():
    """Every splittable zoo model's 2-stage pipeline split verifies
    clean (models whose split is rejected outright — a tensor_array
    crossing a cut — are skipped, as the transpiler itself refuses
    them with a recipe)."""
    from paddle_tpu import analysis
    from paddle_tpu.models import ZOO_MODELS, build_train_program

    failures = []
    skipped = []
    for name in ZOO_MODELS:
        main, _startup, feeds, fetches = build_train_program(name)
        if feeds is None:
            feeds = [v.name
                     for v in main.global_block().vars.values()
                     if getattr(v, "is_data", False)]
        try:
            r = analysis.lint_pipeline(main, 2, feeds, fetches)
        except ValueError:
            skipped.append(name)
            continue
        for d in r.diagnostics:
            failures.append(f"[{name}] {d.severity}[{d.code}]: "
                            f"{d.message}")
    detail = "2-stage split verification"
    if skipped:
        detail += f" (unsplittable, skipped: {', '.join(skipped)})"
    return _section("zoo-pipeline", detail, failures)


def _check_gen_bundle():
    """A freshly exported generation bundle (prefill/decode/meta) lints
    clean in multi-program mode."""
    from paddle_tpu import analysis
    from paddle_tpu.analysis import ProgramVerificationError
    from paddle_tpu.models import gen_lm

    failures = []
    hp = gen_lm.GenConfig()
    hp.vocab_size, hp.d_model, hp.d_ffn = 32, 16, 32
    hp.n_head = hp.n_layer = 2
    hp.d_head, hp.max_len = 8, 16
    tmp = tempfile.mkdtemp(prefix="paddle_tpu_selfcheck_gen_")
    try:
        try:
            gen_lm.export_gen_model(tmp, hp, num_slots=2)
        except ProgramVerificationError as e:
            failures.append(e.args[0].splitlines()[0])
        else:
            for label, r in analysis.lint_gen_bundle(tmp):
                for d in r.diagnostics:
                    failures.append(f"[{label}] {d.severity}[{d.code}]: "
                                    f"{d.message}")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return _section("gen-bundle",
                    "export + multi-program lint of prefill/decode",
                    failures)


def _check_paged_kv():
    """Paged-KV gate: a fresh gen export carries complete page-bucket
    meta, the decode program lints clean, and the static cost model
    prices the decode step proportionally to the fed page count — the
    occupancy-proportional read contract."""
    import json

    from paddle_tpu import analysis
    from paddle_tpu.analysis import cost
    from paddle_tpu.analysis.distributed import load_saved_program
    from paddle_tpu.models import gen_lm

    failures = []
    hp = gen_lm.GenConfig()
    hp.vocab_size, hp.d_model, hp.d_ffn = 32, 16, 32
    hp.n_head, hp.n_layer = 2, 1   # one layer proves the page contract
    hp.d_head, hp.max_len = 8, 32
    tmp = tempfile.mkdtemp(prefix="paddle_tpu_selfcheck_paged_")
    try:
        gen_lm.export_gen_model(tmp, hp, num_slots=2)
        with open(os.path.join(tmp, "gen_meta.json")) as f:
            meta = json.load(f)
        for key in ("page_len", "num_pages", "page_buckets",
                    "page_table_feed"):
            if key not in meta:
                failures.append(f"gen_meta.json missing {key!r}")
        if not failures:
            page_len = int(meta["page_len"])
            pps = -(-int(meta["max_len"]) // page_len)
            pbuckets = [int(p) for p in meta["page_buckets"]]
            if pbuckets != sorted(set(pbuckets)):
                failures.append("page_buckets not strictly increasing: "
                                f"{pbuckets}")
            if pbuckets and pbuckets[-1] != pps:
                failures.append(f"largest page bucket {pbuckets[-1]} != "
                                f"pages/slot {pps} (bucket escape)")
            for label, r in analysis.lint_gen_bundle(tmp):
                for d in r.diagnostics:
                    failures.append(f"[{label}] {d.severity}[{d.code}]: "
                                    f"{d.message}")
            decode = load_saved_program(os.path.join(tmp, "decode"))
            fn = cost.row_cost_fn(decode[0],
                                  batch_var=meta["page_table_feed"],
                                  dim=1, probe_rows=(1, max(pps, 2)))
            if not fn(pps) > fn(1):
                failures.append(
                    "cost model does not price pages: decode flops at "
                    f"{pps} pages ({fn(pps):.0f}) <= at 1 page "
                    f"({fn(1):.0f})")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return _section("paged-kv",
                    "page meta + paged decode lint + page-proportional "
                    "cost", failures)


def _check_embedding():
    """Sharded-embedding gate: a fresh wide_and_deep build's row-
    sharding plan (tables + sparse optimizer moments) verifies clean
    under PTA016/PTA017, the cost model prices the table gather's
    bytes, and the HBM census attributes exactly the tables' bytes to
    the ``embedding`` collection."""
    from paddle_tpu.analysis import cost
    from paddle_tpu.embedding import plan_sharded_tables
    from paddle_tpu.models import build_train_program, compile_zoo_step

    failures = []
    main, _startup, _feeds, _fetches = build_train_program(
        "wide_and_deep")
    plan = plan_sharded_tables(main, mesh_axes={"model": 2},
                               raise_on_error=False)
    if not plan.tables:
        failures.append("no is_distributed lookup tables found in "
                        "wide_and_deep")
    if not plan.states:
        failures.append("no sparse optimizer accumulators joined the "
                        "sharding plan (the moments must live with "
                        "their rows)")
    for d in plan.diagnostics:
        failures.append(f"[plan] {d.severity}[{d.code}]: {d.message}")

    report = cost.estimate(main)
    gather_bytes = sum(row["bytes"] for row in report.per_op
                       if row["op_type"] == "lookup_table")
    if "lookup_table" in report.uncovered or gather_bytes <= 0:
        failures.append("cost model does not price the table gather's "
                        f"bytes (got {gather_bytes})")
    for t in ("lookup_table_grad", "merge_selected_rows",
              "get_tensor_from_selected_rows"):
        if t not in cost.covered_op_types():
            failures.append(f"sparse op {t!r} has no cost rule")

    scope = compile_zoo_step("wide_and_deep", batch=4)
    from paddle_tpu.obs.perf import hbm_census
    census = hbm_census(scope)
    expected = 0
    block = main.global_block()
    for name in plan.tables:
        v = block.var(name)
        expected += 4 * int(v.shape[0]) * int(v.shape[1])
    if census.get("embedding") != expected:
        failures.append(
            f"census attributes {census.get('embedding')} embedding "
            f"bytes; the plan's tables hold {expected}")
    return _section("embedding",
                    "sharded-table plan verification + gather cost + "
                    "census attribution", failures)


# ---------------------------------------------------------------------------
# registry scanners (the doc/code lockstep gates)
# ---------------------------------------------------------------------------

def _check_diagnostic_registry():
    from paddle_tpu.analysis.diagnostics import DIAGNOSTIC_CODES

    emitted = set()
    for path, text in _iter_sources():
        rel = os.path.relpath(path, SRC_ROOT)
        if os.path.dirname(rel) != "analysis" or \
                os.path.basename(rel) == "diagnostics.py":
            continue
        emitted.update(_CODE.findall(text))
    documented = set(_DOC_CODE.findall(_read_doc("static_analysis.md")))
    failures = []
    for code in sorted(emitted - set(DIAGNOSTIC_CODES)):
        failures.append(f"emitted but undeclared: {code}")
    for code in sorted(set(DIAGNOSTIC_CODES) - emitted):
        failures.append(f"declared but no pass emits it: {code}")
    for code in sorted(set(DIAGNOSTIC_CODES) - documented):
        failures.append(f"undocumented in static_analysis.md: {code}")
    for code in sorted(documented - set(DIAGNOSTIC_CODES)):
        failures.append(f"documented but unknown: {code}")
    return _section("diagnostic-registry",
                    f"{len(DIAGNOSTIC_CODES)} codes declared/emitted/"
                    f"documented in lockstep", failures)


def _emitted_metric_names():
    names = set()
    latency = set()
    for path, text in _iter_sources():
        names.update(_METRIC_LITERAL.findall(text))
        found = _METRIC_LATENCY.findall(text)
        latency.update(found)
        names.update(found)
        for suffix in _METRIC_STAGE.findall(text):
            names.add(f"datapipe.<stage>.{suffix}")
        if path.endswith("profiler.py"):
            names.update(_METRIC_MIRROR.findall(text))
    names.update(f"{n}.errors" for n in latency)
    return names


def _check_metric_registry():
    documented = set(_DOC_METRIC.findall(_read_doc("observability.md")))
    failures = []
    for name in sorted(_emitted_metric_names()):
        if name in documented:
            continue
        if name.endswith(".errors") and "<series>.errors" in documented:
            continue
        m = re.match(r"datapipe\.[a-zA-Z0-9_]+\.([a-zA-Z0-9_]+)$", name)
        if m and f"datapipe.<stage>.{m.group(1)}" in documented:
            continue
        failures.append(f"emitted but undocumented: {name}")
    return _section("metric-registry",
                    f"{len(documented)} documented metric rows",
                    failures)


def _check_failpoint_registry():
    fired = set()
    for path, text in _iter_sources():
        if os.path.relpath(path, SRC_ROOT) == os.path.join("fault",
                                                           "chaos.py"):
            continue
        fired.update(_FIRE.findall(text))
    documented = set(_DOC_FAILPOINT.findall(
        _read_doc("fault_tolerance.md")))
    failures = [f"fired but undocumented: {n}"
                for n in sorted(fired - documented)]
    return _section("failpoint-registry",
                    f"{len(fired)} fire sites scanned", failures)


# ---------------------------------------------------------------------------
# observability-plane gates: SLO spec schema + bench trajectory schema
# ---------------------------------------------------------------------------

def _check_slo_spec():
    """The SLO spec schema validator runs against the documented
    example spec (so the validator itself is exercised on every
    selfcheck) AND against the operator's armed ``PADDLE_TPU_SLO`` file
    when set — a malformed spec fails HERE, not as a runtime warning
    three breaches too late."""
    from paddle_tpu.obs import slo

    failures = [f"EXAMPLE_SPEC: {p}"
                for p in slo.validate_spec(slo.EXAMPLE_SPEC)]
    path = os.environ.get(slo.SLO_ENV, "").strip()
    detail = "example spec"
    if path:
        detail += f" + {slo.SLO_ENV}={path}"
        try:
            slo.load_spec(path)
        except (OSError, ValueError) as e:
            failures.extend(str(e).splitlines())
    return _section("slo-spec", detail, failures)


def _check_controller_policy():
    """The autoscaler policy schema validator runs against the
    documented example policy AND against the operator's armed
    ``PADDLE_TPU_AUTOSCALE`` file when set — a malformed policy fails
    HERE, not as a disarmed controller discovered mid-incident."""
    from paddle_tpu.fleet import controller

    failures = [f"EXAMPLE_POLICY: {p}"
                for p in controller.validate_policy(
                    controller.EXAMPLE_POLICY)]
    path = os.environ.get(controller.POLICY_ENV, "").strip()
    detail = "example policy"
    if path:
        detail += f" + {controller.POLICY_ENV}={path}"
        try:
            controller.load_policy(path)
        except (OSError, ValueError) as e:
            failures.extend(str(e).splitlines())
    return _section("controller-policy", detail, failures)


def _check_ckpt_manifest():
    """Checkpoint-manifest schema gate: write a fresh SHARD-format
    checkpoint (synthetic state, no executor, no program) through the
    real ``fault.shard_ckpt`` writer + atomic commit, and prove the
    manifest's topology record is present and self-consistent —
    ``verify_checkpoint`` passes (per-shard hashes AND topology
    cross-checks), and a deliberately tampered topology fails.  The
    elastic-resume contract breaks silently if the schema drifts; this
    fails the static gate instead."""
    import json

    import numpy as np

    from paddle_tpu.fault import shard_ckpt
    from paddle_tpu.fault.checkpoint import (CorruptCheckpoint,
                                             MANIFEST_NAME,
                                             commit_checkpoint,
                                             verify_checkpoint)
    from paddle_tpu.parallel.mesh import make_mesh

    failures = []
    tmp = tempfile.mkdtemp(prefix="paddle_tpu_selfcheck_ckpt_")
    try:
        mesh = make_mesh()
        dp = int(mesh.devices.shape[0])
        state = {"w": np.arange(8 * dp * 3, dtype="float32").reshape(
                     8 * dp, 3),
                 "moment.w": np.ones((8 * dp, 3), "float32"),
                 "lr": np.asarray([0.1], "float32")}
        topo = shard_ckpt.build_topology(
            mesh, state, {"moment.w": ("data", None)})
        tmp_dir = os.path.join(tmp, ".tmp-ckpt-1")
        final = os.path.join(tmp, "ckpt-1")
        os.makedirs(tmp_dir)
        shard_ckpt.write_state(tmp_dir, state, topo, step=1)
        commit_checkpoint(tmp_dir, final, step=1,
                          extra={"topology": topo})
        manifest = shard_ckpt.read_manifest(final)
        if manifest is None or "topology" not in manifest:
            failures.append("committed manifest lacks a topology record")
        else:
            failures.extend(shard_ckpt.validate_topology(manifest))
            try:
                verify_checkpoint(final)
            except CorruptCheckpoint as e:
                failures.append(f"fresh shard checkpoint fails "
                                f"verification: {e}")
            rec = manifest["topology"]["shards"]["moment.w"]
            if dp > 1 and rec["num_shards"] != dp:
                failures.append(
                    f"moment.w should shard {dp}-way over `data`, "
                    f"topology records {rec['num_shards']}")
            # the negative direction: a tampered record must FAIL
            manifest["topology"]["shards"]["moment.w"]["num_shards"] = \
                rec["num_shards"] + 1
            with open(os.path.join(final, MANIFEST_NAME), "w") as f:
                json.dump(manifest, f)
            try:
                verify_checkpoint(final)
                failures.append("tampered topology record passed "
                                "verification")
            except CorruptCheckpoint:
                pass
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return _section("ckpt-manifest",
                    "shard-checkpoint topology record write/verify "
                    "round-trip", failures)


def _check_perf():
    """Device-performance gate: a fresh compile of a zoo model must
    yield a well-formed cost/memory record (flops, bytes, memory
    breakdown, phase times all present), and the ``profile compile
    --json`` schema must validate — so the MFU gauge, the profile CLI,
    and the bench trajectory's measured_mfu row can't silently lose
    their data source to a jax API drift."""
    from paddle_tpu.models import compile_zoo_step
    from paddle_tpu.obs import perf

    failures = []
    before = {r["key"] for r in perf.records()}
    scope = compile_zoo_step("mnist")
    fresh = [r for r in perf.records() if r["key"] not in before]
    with_cost = [r for r in fresh if r["flops"]]
    if not with_cost:
        failures.append("fresh zoo compile captured no cost record "
                        "(capture disabled or cost_analysis "
                        "unavailable?)")
    for r in with_cost:
        if r["memory"] is None:
            failures.append(f"{r['key']}: no memory_analysis breakdown")
        if any(r["phases"].get(k) is None for k in perf.PHASE_KEYS):
            failures.append(f"{r['key']}: incomplete compile phases")
    if with_cost and not any(r["mfu"] for r in with_cost):
        failures.append("no record derived a live MFU after the step")
    failures.extend(perf.validate_report(perf.compile_report()))
    census = perf.hbm_census(scope)
    if not census.get("params") or not census.get("optimizer"):
        failures.append(
            f"hbm census failed to attribute params/optimizer state: "
            f"{ {k: census.get(k) for k in ('params', 'optimizer')} }")
    return _section("perf",
                    "fresh zoo compile -> cost/memory record, "
                    "profile-compile schema, hbm census attribution",
                    failures)


def _check_opt():
    """Optimization-pipeline gate: the full pipeline runs over every
    zoo model (main AND startup), no pass is sandwich-aborted, every
    OPTIMIZED program still lints clean (the passes must not trade
    correctness findings for speed), the static cost report keeps its
    schema, and a one-step executor equivalence spot-check proves the
    optimized program computes the same fetches."""
    import numpy as np

    from paddle_tpu import analysis
    from paddle_tpu.analysis import cost
    from paddle_tpu.analysis.opt import optimize_program
    from paddle_tpu.models import ZOO_MODELS, build_train_program

    failures = []
    for name in ZOO_MODELS:
        main, startup, feeds, fetches = build_train_program(name)
        for label, prog, fd, ft in ((name, main, feeds, fetches),
                                    (f"{name}/startup", startup, None,
                                     None)):
            optimized, report = optimize_program(prog, feed_names=fd,
                                                 fetch_names=ft)
            for p in report.aborted_passes:
                failures.append(f"[{label}] pass {p!r} was "
                                f"sandwich-aborted")
            r = analysis.lint_program(optimized, feed_names=fd,
                                      fetch_names=ft)
            for d in r.diagnostics:
                failures.append(f"[{label}] optimized program: "
                                f"{d.severity}[{d.code}]: {d.message}")
        failures.extend(
            f"[{name}] cost report: {p}"
            for p in cost.validate_cost_report(
                cost.estimate(main).to_dict()))

    # equivalence spot-check (one cheap model; the zoo-wide harness is
    # tests/test_opt_equivalence.py): same startup init, one step,
    # fetches must agree
    import paddle_tpu as fluid
    main, startup, feeds, fetches = build_train_program("mnist")
    main.random_seed = startup.random_seed = 3
    optimized, _ = optimize_program(main, feed_names=feeds,
                                    fetch_names=fetches)
    from paddle_tpu.models import synth_feed
    outs = []
    for prog in (main, optimized):
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup)
            outs.append(exe.run(prog,
                                feed=synth_feed(main, feeds),
                                fetch_list=fetches, scope=scope))
    for ft, a, b in zip(fetches, outs[0], outs[1]):
        if not np.allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                           atol=1e-6):
            failures.append(f"equivalence spot-check: fetch {ft!r} "
                            f"diverged under optimization")
    return _section("opt",
                    "zoo-wide pipeline run, optimized-program lint, "
                    "cost schema, equivalence spot-check", failures)


def _check_ledger():
    """Run-ledger gate: a fresh ledger round-trips rows through its
    schema validators and atomic segment rotation, the resume cursor
    rewinds exactly, the documented example drift spec validates (and a
    broken one fails), and a malformed row is refused — the persistence
    layer every divergence hunt reads must not drift silently."""
    from paddle_tpu.obs import ledger

    failures = []
    failures.extend(f"EXAMPLE_DRIFT_SPEC: {p}"
                    for p in ledger.validate_spec(
                        ledger.EXAMPLE_DRIFT_SPEC))
    if not ledger.validate_spec({"version": 1, "rules": []}):
        failures.append("validate_spec accepted an empty rules list")
    if not ledger.validate_row({"step": -1, "time_unix": 0.0}):
        failures.append("validate_row accepted a negative step")
    if not ledger.validate_row({"step": 0, "time_unix": 1.0,
                                "bogus": 2}):
        failures.append("validate_row accepted an unknown field")
    tmp = tempfile.mkdtemp(prefix="paddle_tpu_selfcheck_ledger_")
    try:
        led = ledger.RunLedger(os.path.join(tmp, "run"), rotate_rows=4,
                               flush_every=1, install=False)
        for _ in range(10):
            led.note_step(fetch_names=("loss",), fetches=([0.5],))
        cursor = led.state_dict()
        for _ in range(3):
            led.note_step(fetch_names=("loss",), fetches=([0.5],))
        led.load_state_dict(cursor)
        led.close()
        rows = ledger.read_rows(os.path.join(tmp, "run"))
        if len(rows) != 10:
            failures.append(f"rotation/rewind round-trip kept "
                            f"{len(rows)} rows, want 10")
        if [r["step"] for r in rows] != list(range(10)):
            failures.append("rewound ledger lost step monotonicity: "
                            f"{[r['step'] for r in rows]}")
        segs = [n for n in os.listdir(os.path.join(tmp, "run"))
                if n.startswith("seg-")]
        if len(segs) < 2:
            failures.append(f"rotate_rows=4 over 10 rows produced "
                            f"{len(segs)} segment(s), want >= 2")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return _section("ledger",
                    "row/spec schema validators, rotation + resume-"
                    "cursor round-trip", failures)


def _check_sessions():
    """Resumable-session gate: the resume-event schema round-trips every
    documented wire shape (token events, clean/legacy/new terminal
    tails, migrate hand-backs) through JSON and the validators, a
    malformed event fails, drain checkpoints validate, and the bounded
    session table keeps its eviction invariants (capacity ceiling,
    orphan accounting, eviction-on-done) — protocol drift fails a
    release gate, not a production failover."""
    import json as _json

    from paddle_tpu.fleet import sessions

    failures = []
    good_events = [
        {"token": 7, "index": 0},
        {"token": 3, "index": 41},
        {"done": True, "finish_reason": "eos", "tokens": 5,
         "token_index": 5},
        # legacy error tail: no token_index / retryable — must parse
        {"error": {"type": "upstream_died", "message": "x"},
         "done": True},
        # new error tail: token_index high-water mark + retryable flag
        {"error": {"type": "batcher_crashed", "message": "x"},
         "done": True, "token_index": 9, "retryable": True},
        {"migrate": {"resume_from": 4, "remaining_tokens": 12},
         "done": True, "token_index": 4, "retryable": True},
    ]
    for ev in good_events:
        round_tripped = _json.loads(_json.dumps(ev))
        problems = sessions.validate_stream_event(round_tripped)
        if problems:
            failures.append(f"valid event {ev} rejected: {problems}")
    bad_events = [
        {"token": 7},                                   # no index
        {"token": 7, "index": -1},
        {"token": 7, "index": 0, "done": True},         # token+terminal
        {"done": True},                                 # no kind
        {"done": True, "finish_reason": "eos",
         "error": {"type": "x"}},                       # two kinds
        {"migrate": {"resume_from": 4}, "done": True},  # not retryable
        {"error": "boom", "done": True},                # error not dict
    ]
    for ev in bad_events:
        if not sessions.validate_stream_event(ev):
            failures.append(f"invalid event {ev} accepted")
    ckpt = {"prompt": [1, 2, 3], "tokens": [4, 5],
            "remaining_tokens": 7, "eos_id": None, "reason": "draining"}
    problems = sessions.validate_checkpoint(
        _json.loads(_json.dumps(ckpt)))
    if problems:
        failures.append(f"valid checkpoint rejected: {problems}")
    if not sessions.validate_checkpoint({"prompt": [],
                                         "tokens": [],
                                         "remaining_tokens": -1,
                                         "reason": ""}):
        failures.append("invalid checkpoint accepted")
    # table invariants: bounded, LRU eviction counts unfinished
    # sessions as orphaned, finish() evicts
    table = sessions.SessionTable(capacity=4)
    for i in range(7):
        table.begin(f"s{i}", "127.0.0.1:1", [1, 2], 8)
    if len(table) > 4:
        failures.append(f"capacity 4 table holds {len(table)}")
    if table.orphaned != 3:
        failures.append(f"7 begins over capacity 4 orphaned "
                        f"{table.orphaned}, want 3")
    if table.owner("s6") != "127.0.0.1:1":
        failures.append("youngest session evicted before the LRU one")
    table.finish("s6")
    if table.owner("s6") is not None or len(table) != 3:
        failures.append("finish() did not evict the session")
    if table.finish("s6") is not None:
        failures.append("finish() of an unknown session returned "
                        "an entry")
    snap = table.snapshot()
    if snap["count"] != 3 or snap["orphaned"] != 3 or \
            len(snap["sessions"]) != 3:
        failures.append(f"snapshot out of step with the table: {snap}")
    return _section("sessions",
                    "resume-event/checkpoint schema round-trip, "
                    "session-table eviction invariants", failures)


def _check_bench_trajectory():
    """``bench check --dry`` against the repo's BENCH_TRAJECTORY.json:
    a drifted or malformed trajectory schema fails the static gate (the
    regression COMPARISON stays in `paddle_tpu bench check` proper —
    perf verdicts don't belong in a schema gate)."""
    from paddle_tpu.obs import bench_history

    path = bench_history.default_path()
    report = bench_history.check(path=path, dry=True)
    failures = list(report["problems"])
    detail = f"schema of {os.path.basename(path)}"
    return _section("bench-trajectory", detail, failures)


def run_selfcheck():
    """Run every section; returns the report dict."""
    sections = [
        _check_zoo_lint(),
        _check_zoo_distribute(),
        _check_zoo_pipeline(),
        _check_gen_bundle(),
        _check_paged_kv(),
        _check_embedding(),
        _check_diagnostic_registry(),
        _check_metric_registry(),
        _check_failpoint_registry(),
        _check_slo_spec(),
        _check_controller_policy(),
        _check_opt(),
        _check_ledger(),
        _check_sessions(),
        _check_bench_trajectory(),
        _check_ckpt_manifest(),
        _check_perf(),
    ]
    return {"ok": all(s["ok"] for s in sections), "sections": sections}
