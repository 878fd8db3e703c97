"""Distributed-program verifier (paddle_tpu.analysis.distributed):
negative cases for every cross-program diagnostic code, the acceptance
drills (a deliberately reordered-collective pipeline pair caught as a
static deadlock; a Send-without-Recv transpiled pair), the
multi-program zoo gate (every model's distribute-transpiled and
pipeline-split families verify clean), and the multi-program CLI modes.

``NEGATIVE_CASES`` is the machine-readable registry half the scanner
test (test_analysis_registry.py) enforces: every cross-program
``PTA***`` code must appear here with a builder that constructs a
deliberately inconsistent program FAMILY triggering it (single-program
codes live in tests/test_analysis.py::NEGATIVE_CASES).
"""

import json
import os

import pytest

import paddle_tpu as fluid
from paddle_tpu import analysis
from paddle_tpu.analysis import distributed as D
from paddle_tpu.framework import Program


def _prog():
    p = Program()
    return p, p.global_block()


def _collective_prog(order, axis="data", shape=(4, 4)):
    """A replica program emitting collectives in ``order`` (list of
    c_* op types) over a feed of ``shape``."""
    p, b = _prog()
    b.create_var(name="x", shape=shape, dtype="float32", is_data=True)
    cur = "x"
    for i, op_type in enumerate(order):
        out = f"t{i}"
        b.append_op(type=op_type, inputs={"X": [cur]},
                    outputs={"Out": [out]},
                    attrs={"axis": axis, "root": 0})
        cur = out
    return p


# ---------------------------------------------------------------------------
# negative-case registry: code -> builder returning an AnalysisResult
# over a deliberately broken program family
# ---------------------------------------------------------------------------

def _case_pta011_reordered_collectives():
    a = _collective_prog(["c_allreduce_sum", "c_broadcast"])
    b = _collective_prog(["c_broadcast", "c_allreduce_sum"])
    return analysis.AnalysisResult(
        D.check_collective_match([("replica0", a), ("replica1", b)]))


def _case_pta012_collective_attr_mismatch():
    a = _collective_prog(["c_allreduce_sum"], axis="data")
    b = _collective_prog(["c_allreduce_sum"], axis="model", shape=(4, 8))
    return analysis.AnalysisResult(
        D.check_collective_match([("replica0", a), ("replica1", b)]))


def _trainer_pserver_pair(recv_side=False, block_rows=(3, 3)):
    trainer, tb = _prog()
    tb.create_var(name="w", shape=(8, 4), dtype="float32",
                  persistable=True)
    tb.create_var(name="w@GRAD", shape=(8, 4), dtype="float32")
    tb.append_op(type="send", inputs={"X": ["w@GRAD"]}, outputs={})
    pserver, pb = _prog()
    if recv_side:
        pb.append_op(type="recv", inputs={},
                     outputs={"Out": ["w@GRAD"]})
        pb.create_var(name="w@GRAD", shape=(8, 4), dtype="float32")
    for k, rows in enumerate(block_rows):
        pb.create_var(name=f"w.block{k}", shape=(rows, 4),
                      dtype="float32", persistable=True)
    return trainer, pserver


def _case_pta013_send_without_recv():
    trainer, pserver = _trainer_pserver_pair(recv_side=False,
                                             block_rows=(4, 4))
    return D.lint_pair(("trainer", trainer), [("pserver", pserver)])


def _case_pta014_split_does_not_reassemble():
    # 3 + 3 rows of pserver blocks vs an 8-row original parameter
    trainer, pserver = _trainer_pserver_pair(recv_side=True,
                                             block_rows=(3, 3))
    return D.lint_pair(("trainer", trainer), [("pserver", pserver)])


def _stage_pair(consumer_shape=(2, 4), reorder=False):
    """Two hand-built pipeline stage programs sharing carrier ``h``
    (+ ``m``): the consumer declares ``consumer_shape`` for ``h``."""
    s0, b0 = _prog()
    b0.create_var(name="x", shape=(2, 4), dtype="float32", is_data=True)
    b0.append_op(type="relu", inputs={"X": ["x"]}, outputs={"Out": ["h"]})
    b0.append_op(type="tanh", inputs={"X": ["x"]}, outputs={"Out": ["m"]})
    b0.var("h").shape = (2, 4)
    b0.var("m").shape = (2, 4)
    s1, b1 = _prog()
    b1.create_var(name="h", shape=consumer_shape, dtype="float32",
                  is_data=True)
    b1.create_var(name="m", shape=(2, 4), dtype="float32", is_data=True)
    b1.append_op(type="elementwise_add",
                 inputs={"X": ["h"], "Y": ["m"]}, outputs={"Out": ["y"]})
    out0 = ["m", "h"] if reorder else ["h", "m"]
    return [("stage0", s0, ["x"], out0), ("stage1", s1, ["h", "m"], ["y"])]


def _case_pta015_boundary_carrier_mismatch():
    return analysis.AnalysisResult(
        D.check_pipeline_stages(_stage_pair(consumer_shape=(2, 8))))


def _case_pta016_invalid_sharding_spec():
    p, b = _prog()
    b.create_parameter(shape=(9, 4), dtype="float32", name="w")
    return analysis.AnalysisResult(D.check_sharding(
        p, {"w": ("model",)}, mesh_axes={"model": 2}))


def _case_pta017_implicit_full_reshard():
    p, b = _prog()
    b.create_var(name="a", shape=(4, 4), dtype="float32", is_data=True)
    b.create_var(name="b", shape=(4, 4), dtype="float32", is_data=True)
    b.append_op(type="elementwise_add",
                inputs={"X": ["a"], "Y": ["b"]}, outputs={"Out": ["c"]})
    return analysis.AnalysisResult(D.check_sharding(
        p, {"a": ("data", None), "b": (None, "model")},
        mesh_axes={"data": 2, "model": 2}))


def _paged_family(num_slots=2, max_len=16, page_len=4, num_pages=8,
                  page_buckets=(1, 2, 4), feed_pt=True, pt_rows=None,
                  cache_shape=None, prompt_buckets=(8,)):
    """Hand-built prefill/decode pair + meta (no executor needed):
    pools are ``[num_pages, page_len, hd]`` and decode feeds a
    dynamic-width page table (the one sanctioned dynamic decode dim)."""
    pre, pb = _prog()
    pb.create_var(name="ids", shape=(1, -1), dtype="int32", is_data=True)
    pb.create_var(name="logits", shape=(1, 16), dtype="float32")
    pb.create_var(name="k0", shape=(1, -1, 4), dtype="float32")
    pb.create_var(name="v0", shape=(1, -1, 4), dtype="float32")
    dec, db = _prog()
    db.create_var(name="tok", shape=(num_slots, 1), dtype="int32",
                  is_data=True)
    feeds = ["tok"]
    if feed_pt:
        db.create_var(name="gen_page_table",
                      shape=(pt_rows or num_slots, -1),
                      dtype="int32", is_data=True)
        feeds.append("gen_page_table")
    for name in ("cache_k_0", "cache_v_0"):
        c = db.create_var(name=name,
                          shape=cache_shape or (num_pages, page_len, 4),
                          dtype="float32")
        c.persistable = True
    db.create_var(name="logits", shape=(num_slots, 16), dtype="float32")
    meta = {"num_slots": num_slots, "max_len": max_len,
            "cache_vars": ["cache_k_0", "cache_v_0"],
            "prompt_buckets": list(prompt_buckets),
            "page_len": page_len, "num_pages": num_pages,
            "page_buckets": list(page_buckets),
            "page_table_feed": "gen_page_table"}
    return ((pre, ["ids"], ["logits", "k0", "v0"]),
            (dec, feeds, ["logits"]), meta)


def _case_pta018_bucket_escape():
    # the largest declared prompt bucket exceeds the cache length: it
    # is declared but never warmed -> compiles at request time
    prefill, decode, meta = _paged_family(prompt_buckets=(8, 128))
    return analysis.AnalysisResult(
        D.check_gen_bundle(prefill, decode, meta))


def _case_pta019_signature_drift():
    # meta claims 8 pages, the decode pool holds 4
    prefill, decode, meta = _paged_family(num_pages=8,
                                          cache_shape=(4, 4, 4))
    return analysis.AnalysisResult(
        D.check_gen_bundle(prefill, decode, meta))


#: the cross-program half of the negative-case registry, enforced
#: complete (together with test_analysis.NEGATIVE_CASES) by
#: tests/test_analysis_registry.py
NEGATIVE_CASES = {
    "PTA011": _case_pta011_reordered_collectives,
    "PTA012": _case_pta012_collective_attr_mismatch,
    "PTA013": _case_pta013_send_without_recv,
    "PTA014": _case_pta014_split_does_not_reassemble,
    "PTA015": _case_pta015_boundary_carrier_mismatch,
    "PTA016": _case_pta016_invalid_sharding_spec,
    "PTA017": _case_pta017_implicit_full_reshard,
    "PTA018": _case_pta018_bucket_escape,
    "PTA019": _case_pta019_signature_drift,
}


@pytest.mark.parametrize("code", sorted(NEGATIVE_CASES))
def test_negative_case_triggers_code(code):
    result = NEGATIVE_CASES[code]()
    assert code in result.codes(), (
        f"deliberately inconsistent family did not trigger {code}; "
        f"got {result.codes()}:\n{result.format()}")
    hit = next(d for d in result.diagnostics if d.code == code)
    # actionable: the diagnostic names a concrete var/op/member
    assert hit.var or hit.op_type or hit.program, hit.format()


class TestPagedBundleDiagnostics:
    """The page-bucket family of the gen-bundle verifier: PTA018
    recompile hazards and PTA019 drift for the paged layout."""

    def _result(self, **kw):
        return analysis.AnalysisResult(
            D.check_gen_bundle(*_paged_family(**kw)))

    def test_clean_paged_family_is_silent(self):
        r = self._result()
        assert "PTA018" not in r.codes() and "PTA019" not in r.codes(), \
            r.format()

    def test_missing_page_buckets_is_pta018(self):
        assert "PTA018" in self._result(page_buckets=()).codes()

    def test_page_bucket_escape_is_pta018(self):
        # largest bucket covers 2 pages of the 4 a full slot needs:
        # long prefixes escape the declared ladder and compile fresh
        assert "PTA018" in self._result(page_buckets=(1, 2)).codes()

    def test_unreachable_page_bucket_is_pta018(self):
        assert "PTA018" in self._result(
            page_buckets=(1, 2, 4, 8)).codes()

    def test_missing_page_table_feed_is_pta019(self):
        assert "PTA019" in self._result(feed_pt=False).codes()

    def test_page_table_leading_dim_drift_is_pta019(self):
        assert "PTA019" in self._result(pt_rows=3).codes()

    def test_pool_smaller_than_one_slot_is_pta019(self):
        assert "PTA019" in self._result(num_pages=2).codes()

    def test_pool_geometry_drift_is_pta019(self):
        assert "PTA019" in self._result(
            cache_shape=(8, 2, 4)).codes()


# ---------------------------------------------------------------------------
# acceptance drills
# ---------------------------------------------------------------------------

class TestStaticDeadlockDrills:
    def test_reordered_collective_pipeline_pair_is_static_deadlock(self):
        """The ISSUE's headline drill: a pipeline stage whose
        collectives are reordered relative to its peer is flagged as a
        static deadlock (PTA011) — not a runtime hang."""
        stages = _stage_pair()
        # graft disagreeing collective sequences onto the two stages
        s0 = stages[0][1].global_block()
        s1 = stages[1][1].global_block()
        s0.append_op(type="c_allreduce_sum", inputs={"X": ["h"]},
                     outputs={"Out": ["h_r"]}, attrs={"axis": "pipe"})
        s0.append_op(type="c_broadcast", inputs={"X": ["h_r"]},
                     outputs={"Out": ["h_b"]},
                     attrs={"axis": "pipe", "root": 0})
        s1.append_op(type="c_broadcast", inputs={"X": ["y"]},
                     outputs={"Out": ["y_b"]},
                     attrs={"axis": "pipe", "root": 0})
        s1.append_op(type="c_allreduce_sum", inputs={"X": ["y_b"]},
                     outputs={"Out": ["y_r"]}, attrs={"axis": "pipe"})
        diags = D.check_pipeline_stages(stages)
        codes = {d.code for d in diags}
        assert "PTA011" in codes, [d.format() for d in diags]
        hit = next(d for d in diags if d.code == "PTA011")
        assert "deadlock" in hit.message

    def test_matching_collectives_across_stages_are_clean(self):
        stages = _stage_pair()
        for _, prog, _i, _o in stages:
            prog.global_block().append_op(
                type="c_allreduce_sum",
                inputs={"X": [prog.global_block().ops[0]
                              .output_arg_names[0]]},
                outputs={"Out": ["r"]}, attrs={"axis": "pipe"})
        diags = D.check_pipeline_stages(stages)
        assert not diags, [d.format() for d in diags]

    def test_send_without_recv_pair_drill(self):
        """The second named drill: a transpiled pair where the trainer
        sends a gradient no pserver receives."""
        result = _case_pta013_send_without_recv()
        assert "PTA013" in result.codes()
        hit = next(d for d in result.diagnostics if d.code == "PTA013")
        assert hit.var == "w@GRAD" and "blocks forever" in hit.message

    def test_paired_send_recv_is_clean(self):
        trainer, tb = _prog()
        tb.create_var(name="g", shape=(4, 2), dtype="float32")
        tb.append_op(type="send", inputs={"X": ["g"]}, outputs={})
        pserver, pb = _prog()
        pb.create_var(name="g", shape=(4, 2), dtype="float32")
        pb.append_op(type="recv", inputs={}, outputs={"Out": ["g"]})
        result = D.lint_pair(("trainer", trainer),
                             [("pserver", pserver)])
        assert not result.diagnostics, result.format()

    def test_shape_drifted_send_recv_pair(self):
        trainer, tb = _prog()
        tb.create_var(name="g", shape=(4, 2), dtype="float32")
        tb.append_op(type="send", inputs={"X": ["g"]}, outputs={})
        pserver, pb = _prog()
        pb.create_var(name="g", shape=(2, 2), dtype="float32")
        pb.append_op(type="recv", inputs={}, outputs={"Out": ["g"]})
        result = D.lint_pair(("trainer", trainer),
                             [("pserver", pserver)])
        assert "PTA013" in result.codes()

    def test_reordered_carrier_is_pta015(self):
        """Positional carrier layout: the same names in a different
        order desync producer and consumer."""
        diags = D.check_pipeline_stages(_stage_pair(reorder=True))
        assert "PTA015" in {d.code for d in diags}

    def test_tampered_boundary_is_pta015(self):
        """check_stage_set (the PipelinedProgram wiring): dropping a
        consumed carrier from a boundary is caught statically."""
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            h = fluid.layers.fc(input=x, size=8, act="relu")
            y = fluid.layers.fc(input=h, size=2)
        from paddle_tpu.parallel.pipeline_transpiler import split_program
        block, stage_ops, _params, boundaries = split_program(
            main, 2, ["x"], [y.name])
        tampered = [list(names) for names in boundaries]
        tampered[1] = []  # stage 1 consumes the carrier; drop it all
        diags = D.check_stage_set(block, stage_ops, tampered,
                                  feed_names=["x"])
        assert "PTA015" in {d.code for d in diags}
        # untampered boundaries are clean
        assert not D.check_stage_set(block, stage_ops, boundaries,
                                     feed_names=["x"])


# ---------------------------------------------------------------------------
# sharding-spec propagation
# ---------------------------------------------------------------------------

class TestShardingPropagation:
    def test_spec_for_unknown_var_is_pta016(self):
        p, _ = _prog()
        diags = D.check_sharding(p, {"ghost": ("model",)})
        assert [d.code for d in diags] == ["PTA016"]

    def test_axis_not_in_mesh_is_pta016(self):
        p, b = _prog()
        b.create_parameter(shape=(8, 4), dtype="float32", name="w")
        diags = D.check_sharding(p, {"w": ("nope",)},
                                 mesh_axes={"model": 2})
        assert [d.code for d in diags] == ["PTA016"]

    def test_param_grad_spec_disagreement_is_pta016(self):
        p, b = _prog()
        b.create_parameter(shape=(8, 4), dtype="float32", name="w")
        from paddle_tpu.parallel.distribute_transpiler import \
            DistributedSpec
        spec = DistributedSpec()
        spec.param_specs["w"] = ("model",)
        spec.grad_specs["w"] = ("data",)
        diags = D.check_distributed_spec(p, spec)
        assert "PTA016" in {d.code for d in diags}

    def test_optimizer_sees_through_declared_placements(self):
        p, b = _prog()
        b.create_parameter(shape=(8, 4), dtype="float32", name="w")
        b.create_var(name="g", shape=(8, 4), dtype="float32",
                     is_data=True)
        b.create_var(name="lr", shape=(1,), dtype="float32",
                     is_data=True)
        b.append_op(type="sgd",
                    inputs={"Param": ["w"], "Grad": ["g"],
                            "LearningRate": ["lr"]},
                    outputs={"ParamOut": ["w"]})
        diags = D.check_sharding(
            p, {"w": ("model", None), "g": ("data", None)},
            mesh_axes={"model": 2, "data": 2})
        assert "PTA016" in {d.code for d in diags}

    def test_inconsistent_optimizer_state_is_pta016(self):
        """ZeRO discipline: moment1 sharded + moment2 replicated on one
        adam update is a provably broken state plan."""
        p, b = _prog()
        b.create_parameter(shape=(8, 4), dtype="float32", name="w")
        for name in ("g", "m1", "m2"):
            b.create_var(name=name, shape=(8, 4), dtype="float32",
                         is_data=True)
        for name in ("lr", "b1p", "b2p"):
            b.create_var(name=name, shape=(1,), dtype="float32",
                         is_data=True)
        b.append_op(type="adam",
                    inputs={"Param": ["w"], "Grad": ["g"],
                            "LearningRate": ["lr"],
                            "Moment1": ["m1"], "Moment2": ["m2"],
                            "Beta1Pow": ["b1p"], "Beta2Pow": ["b2p"]},
                    outputs={"ParamOut": ["w"], "Moment1Out": ["m1"],
                             "Moment2Out": ["m2"], "Beta1PowOut": ["b1p"],
                             "Beta2PowOut": ["b2p"]})
        diags = D.check_sharding(
            p, {"m1": ("data", None), "m2": ()},
            mesh_axes={"data": 2})
        assert any(d.code == "PTA016" and "inconsistently" in d.message
                   for d in diags), [d.format() for d in diags]

    def test_zero_shape_state_plan_is_silent(self):
        """The INTENDED ZeRO shape — params/grads replicated, every
        state slot sharded the same way — must verify clean (zero
        false positives)."""
        p, b = _prog()
        b.create_parameter(shape=(8, 4), dtype="float32", name="w")
        for name in ("g", "m1", "m2"):
            b.create_var(name=name, shape=(8, 4), dtype="float32",
                         is_data=True)
        for name in ("lr", "b1p", "b2p"):
            b.create_var(name=name, shape=(1,), dtype="float32",
                         is_data=True)
        b.append_op(type="adam",
                    inputs={"Param": ["w"], "Grad": ["g"],
                            "LearningRate": ["lr"],
                            "Moment1": ["m1"], "Moment2": ["m2"],
                            "Beta1Pow": ["b1p"], "Beta2Pow": ["b2p"]},
                    outputs={"ParamOut": ["w"], "Moment1Out": ["m1"],
                             "Moment2Out": ["m2"], "Beta1PowOut": ["b1p"],
                             "Beta2PowOut": ["b2p"]})
        diags = D.check_sharding(
            p, {"w": (), "g": (), "m1": ("data", None),
                "m2": ("data", None)},
            mesh_axes={"data": 2})
        assert not diags, [d.format() for d in diags]

    def test_replicated_everything_is_silent(self):
        p, b = _prog()
        b.create_parameter(shape=(8, 4), dtype="float32", name="w")
        b.create_var(name="a", shape=(2, 8), dtype="float32",
                     is_data=True)
        b.append_op(type="mul", inputs={"X": ["a"], "Y": ["w"]},
                    outputs={"Out": ["h"]})
        diags = D.check_sharding(p, {"w": ()},
                                 mesh_axes={"model": 2})
        assert not diags, [d.format() for d in diags]

    def test_one_sided_contraction_shard_is_pta017(self):
        p, b = _prog()
        b.create_var(name="a", shape=(2, 8), dtype="float32",
                     is_data=True)
        b.create_parameter(shape=(8, 4), dtype="float32", name="w")
        b.append_op(type="matmul", inputs={"X": ["a"], "Y": ["w"]},
                    outputs={"Out": ["h"]})
        diags = D.check_sharding(
            p, {"a": (None, "model"), "w": (None, None)},
            mesh_axes={"model": 2})
        assert [d.code for d in diags] == ["PTA017"]

    def test_registering_a_sharding_rule(self):
        """The docs/static_analysis.md how-to, as a regression test."""
        calls = []

        @D.sharding_rule("my_test_only_op")
        def _rule(op, senv):
            calls.append(op.type)
            senv.set_output(op, "Out", senv.input_spec(op, "X"))

        try:
            p, b = _prog()
            b.create_var(name="a", shape=(4,), dtype="float32",
                         is_data=True)
            b.append_op(type="my_test_only_op", inputs={"X": ["a"]},
                        outputs={"Out": ["o"]})
            diags = D.check_sharding(p, {"a": ("data",)},
                                     mesh_axes={"data": 2})
            assert calls == ["my_test_only_op"]
            assert not diags
        finally:
            D._SHARDING_RULES.pop("my_test_only_op", None)


# ---------------------------------------------------------------------------
# multi-program zoo gate: the transpiled families of every zoo model
# verify clean (zero false positives is part of the contract)
# ---------------------------------------------------------------------------

def _zoo():
    from paddle_tpu.models import ZOO_MODELS
    return ZOO_MODELS


@pytest.mark.parametrize("name", _zoo())
def test_zoo_distribute_transpile_verifies_clean(name):
    from paddle_tpu.models import build_train_program
    from paddle_tpu.parallel.distribute_transpiler import \
        DistributeTranspiler
    main, startup, _feeds, _fetches = build_train_program(name)
    t = DistributeTranspiler()
    # transpile() itself raises on a plan that fails verification
    t.transpile(program=main, startup_program=startup,
                pservers="a:1,b:2", shard_params=True)
    diags = analysis.check_distributed_spec(main, t.spec)
    assert not diags, [d.format() for d in diags]


@pytest.mark.parametrize("name", _zoo())
def test_zoo_pipeline_split_verifies_clean(name):
    from paddle_tpu.models import build_train_program
    main, _startup, feeds, fetches = build_train_program(name)
    if feeds is None:
        feeds = [v.name for v in main.global_block().vars.values()
                 if getattr(v, "is_data", False)]
    try:
        result = analysis.lint_pipeline(main, 2, feeds, fetches)
    except ValueError as e:
        pytest.skip(f"unsplittable program: {e}")
    assert not result.diagnostics, result.format()


# ---------------------------------------------------------------------------
# multi-program CLI modes
# ---------------------------------------------------------------------------

def _bundle_with_edited_meta(tmp_path, edit):
    """A toy ``gen_lm`` bundle exported clean, then its ``gen_meta.json``
    rewritten by ``edit(meta)`` in place: ``(bundle dir, edited meta)``."""
    from paddle_tpu.models import gen_lm
    hp = gen_lm.GenConfig()
    hp.vocab_size, hp.d_model, hp.d_ffn = 32, 16, 32
    hp.n_head = hp.n_layer = 2
    hp.d_head, hp.max_len = 8, 16
    bundle = str(tmp_path / "bundle")
    gen_lm.export_gen_model(bundle, hp, num_slots=2)
    meta_path = os.path.join(bundle, "gen_meta.json")
    with open(meta_path) as f:
        meta = json.load(f)
    edit(meta)
    with open(meta_path, "w") as f:
        json.dump(meta, f)
    return bundle, meta


class TestMultiProgramCli:
    def _write_model(self, path, program, feeds, fetches):
        os.makedirs(path, exist_ok=True)
        with open(os.path.join(path, "__model__"), "w") as f:
            json.dump({"program": program.to_dict(),
                       "feed_var_names": feeds or [],
                       "fetch_var_names": fetches or []}, f)
        return path

    def test_lint_pair_mode_catches_unpaired_send(self, tmp_path,
                                                  capsys):
        from paddle_tpu.cli import main
        trainer, pserver = _trainer_pserver_pair(recv_side=False)
        t = self._write_model(str(tmp_path / "trainer"), trainer,
                              [], [])
        p = self._write_model(str(tmp_path / "pserver"), pserver,
                              [], [])
        assert main(["lint", "--pair", t, p]) == 1
        assert "PTA013" in capsys.readouterr().out

    def test_lint_pipeline_mode_zoo_clean(self, capsys):
        from paddle_tpu.cli import main
        assert main(["lint", "--zoo", "mnist", "--pipeline", "2"]) == 0
        assert "0 error(s)" in capsys.readouterr().out

    def test_lint_gen_bundle_mode_catches_drift(self, tmp_path, capsys):
        """A tampered gen_meta.json fails the bundle lint with the
        stable drift code (the clean-bundle path joins the zoo gate in
        test_analysis_zoo.py)."""
        from paddle_tpu.cli import main
        bundle, _ = _bundle_with_edited_meta(
            tmp_path, lambda meta: meta.update(num_slots=5))
        assert main(["lint", bundle]) == 1
        assert "PTA019" in capsys.readouterr().out


@pytest.mark.parametrize("how", ["check", "load", "lint"])
def test_a_bundle_without_pages_is_refused(how, tmp_path, capsys):
    """A ``gen_meta.json`` without ``page_len`` is a bundle of the dense
    KV layout, which was removed: PTA019 from the check, at
    ``GenPredictor(...)`` and under ``paddle_tpu lint``, saying to
    re-export, before anything seeds a pool."""
    def strip_pages(meta):
        for key in ("page_len", "num_pages", "page_buckets",
                    "page_table_feed"):
            del meta[key]

    bundle, meta = _bundle_with_edited_meta(tmp_path, strip_pages)
    if how == "check":
        prefill = D.load_saved_program(os.path.join(bundle, "prefill"))
        decode = D.load_saved_program(os.path.join(bundle, "decode"))
        (diag,) = D.check_gen_bundle(prefill, decode, meta)
        assert diag.code == "PTA019" and diag.severity == "error"
        assert "re-export" in diag.message
    elif how == "load":
        from paddle_tpu.gen import GenPredictor
        with pytest.raises(analysis.ProgramVerificationError) as ei:
            GenPredictor(bundle)
        assert "PTA019" in str(ei.value) and "page_len" in str(ei.value)
    else:
        from paddle_tpu.cli import main
        assert main(["lint", bundle]) == 1
        assert "PTA019" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# export-time self-check wiring
# ---------------------------------------------------------------------------

def test_gen_export_self_check_rejects_drifted_bundle(tmp_path):
    """export_gen_model verifies its own output: a meta writer that
    drifts from the decode program fails AT EXPORT, naming the pass."""
    # build the real bundle first, then re-verify with a drifted meta
    bundle, _ = _bundle_with_edited_meta(
        tmp_path, lambda meta: meta["cache_vars"].append("genlm_cache_ghost"))
    with pytest.raises(analysis.ProgramVerificationError) as ei:
        analysis.verify_gen_bundle(bundle,
                                   where="gen_lm.export_gen_model")
    assert "PTA019" in str(ei.value)
    assert ei.value.where == "gen_lm.export_gen_model"
