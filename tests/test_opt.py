"""Program-IR optimization passes (paddle_tpu/analysis/opt): per-pass
unit tests, verify-sandwich negatives (a deliberately broken pass must
be rejected), RNG-slot exactness, executor PADDLE_TPU_OPT wiring, and
the donation planner's PTA009 proof obligation."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.analysis import lints, opmeta
from paddle_tpu.analysis.opt import (OptReport, PassPipeline,
                                     optimize_program)
from paddle_tpu.analysis.opt.passes import (FUSED_OP_TYPE,
                                            RNG_SLOTS_ATTR,
                                            PassContext,
                                            constant_fold_pass,
                                            cse_pass, dce_pass,
                                            fuse_elementwise_pass)
from paddle_tpu.memory_optimization_transpiler import plan_donation


def _run(program, feed=None, fetches=(), scope=None, seed=0):
    program.random_seed = seed
    scope = scope or fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        return exe.run(program, feed=feed or {},
                       fetch_list=list(fetches), scope=scope)


def _op_types(program):
    return [op.type for op in program.global_block().ops]


# ---------------------------------------------------------------------------
# constant folding
# ---------------------------------------------------------------------------

class TestConstantFold:
    def _chain_program(self):
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            b = main.global_block()
            b.append_op("fill_constant", outputs={"Out": ["c0"]},
                        attrs={"shape": [2, 2], "dtype": "float32",
                               "value": 3.0})
            b.append_op("scale", inputs={"X": ["c0"]},
                        outputs={"Out": ["c1"]},
                        attrs={"scale": 2.0, "bias": 1.0})
            b.append_op("elementwise_add", inputs={"X": ["c1"],
                                                   "Y": ["c0"]},
                        outputs={"Out": ["c2"]}, attrs={})
        return main

    def test_folds_chain_to_constant(self):
        main = self._chain_program()
        ctx = PassContext(fetch_names=("c2",))
        stats = constant_fold_pass(main, ctx)
        assert stats["folded"] == 2  # scale + elementwise_add
        assert stats["swept"] == 2   # orphaned fill + intermediate
        assert _op_types(main) == ["assign_value"]  # just the fetch
        (out,) = _run(main, fetches=["c2"])
        np.testing.assert_allclose(out, np.full((2, 2), 10.0))

    def test_fold_then_dce_leaves_one_constant(self):
        main = self._chain_program()
        optimized, report = optimize_program(main, fetch_names=("c2",))
        # the whole chain collapses to the single fetched constant
        assert _op_types(optimized) == ["assign_value"]
        (out,) = _run(optimized, fetches=["c2"])
        np.testing.assert_allclose(out, np.full((2, 2), 10.0))

    def test_redefined_constant_not_stale_folded(self):
        # c0 is re-written between consumers: the second consumer must
        # not fold the first literal
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            b = main.global_block()
            b.append_op("fill_constant", outputs={"Out": ["c0"]},
                        attrs={"shape": [2], "dtype": "float32",
                               "value": 1.0})
            b.append_op("scale", inputs={"X": ["c0"]},
                        outputs={"Out": ["a"]}, attrs={"scale": 2.0})
            # non-const writer of c0 (reads a feed)
            x = b.create_var(name="x", shape=(2,), dtype="float32",
                             is_data=True)
            b.append_op("scale", inputs={"X": ["x"]},
                        outputs={"Out": ["c0"]}, attrs={"scale": 1.0})
            b.append_op("scale", inputs={"X": ["c0"]},
                        outputs={"Out": ["out"]}, attrs={"scale": 3.0})
        constant_fold_pass(main, PassContext(feed_names=("x",),
                                             fetch_names=("a", "out")))
        a, out = _run(main, feed={"x": np.array([5.0, 5.0], "float32")},
                      fetches=["a", "out"])
        np.testing.assert_allclose(a, [2.0, 2.0])
        np.testing.assert_allclose(out, [15.0, 15.0])


# ---------------------------------------------------------------------------
# CSE
# ---------------------------------------------------------------------------

class TestCSE:
    def test_duplicate_pure_ops_dedupe(self):
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            b = main.global_block()
            b.create_var(name="x", shape=(4,), dtype="float32",
                         is_data=True)
            b.append_op("scale", inputs={"X": ["x"]},
                        outputs={"Out": ["s1"]}, attrs={"scale": 2.0})
            b.append_op("scale", inputs={"X": ["x"]},
                        outputs={"Out": ["s2"]}, attrs={"scale": 2.0})
            b.append_op("elementwise_add", inputs={"X": ["s1"],
                                                   "Y": ["s2"]},
                        outputs={"Out": ["out"]}, attrs={})
        stats = cse_pass(main, PassContext(feed_names=("x",),
                                           fetch_names=("out",)))
        assert stats["deduped"] == 1
        assert _op_types(main).count("scale") == 1
        # the consumer now reads the canonical output twice
        add = main.global_block().ops[-1]
        assert add.input("X") == add.input("Y") == ["s1"]
        (out,) = _run(main, feed={"x": np.ones(4, "float32")},
                      fetches=["out"])
        np.testing.assert_allclose(out, np.full(4, 4.0))

    def test_fetched_duplicate_is_kept(self):
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            b = main.global_block()
            b.create_var(name="x", shape=(4,), dtype="float32",
                         is_data=True)
            b.append_op("scale", inputs={"X": ["x"]},
                        outputs={"Out": ["s1"]}, attrs={"scale": 2.0})
            b.append_op("scale", inputs={"X": ["x"]},
                        outputs={"Out": ["s2"]}, attrs={"scale": 2.0})
        stats = cse_pass(main, PassContext(feed_names=("x",),
                                           fetch_names=("s1", "s2")))
        assert stats["deduped"] == 0
        assert _op_types(main).count("scale") == 2

    def test_attr_difference_blocks_dedupe(self):
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            b = main.global_block()
            b.create_var(name="x", shape=(4,), dtype="float32",
                         is_data=True)
            b.append_op("scale", inputs={"X": ["x"]},
                        outputs={"Out": ["s1"]}, attrs={"scale": 2.0})
            b.append_op("scale", inputs={"X": ["x"]},
                        outputs={"Out": ["s2"]}, attrs={"scale": 3.0})
            b.append_op("elementwise_add", inputs={"X": ["s1"],
                                                   "Y": ["s2"]},
                        outputs={"Out": ["out"]}, attrs={})
        stats = cse_pass(main, PassContext(feed_names=("x",),
                                           fetch_names=("out",)))
        assert stats["deduped"] == 0


# ---------------------------------------------------------------------------
# DCE
# ---------------------------------------------------------------------------

class TestDCE:
    def test_removes_dead_and_unfetched_grad_chains(self):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = layers.data("x", shape=[4], dtype="float32")
            h = layers.fc(x, 8, act="relu")
            cost = fluid.layers.mean(h)
            fluid.backward.append_backward(cost)
        n_before = len(main.global_block().ops)
        stats = dce_pass(main, PassContext(feed_names=("x",),
                                           fetch_names=(cost.name,)))
        # nothing fetches the grads and no optimizer consumes them:
        # the whole autodiff chain is dead (XLA would DCE it after
        # paying trace+lower for it)
        assert stats["removed"] > 0
        types = _op_types(main)
        assert not any(t.endswith("_grad") for t in types)
        assert len(types) < n_before

    def test_keeps_effectful_and_persistable_writes(self):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = layers.data("x", shape=[4], dtype="float32")
            h = layers.fc(x, 8)
            cost = fluid.layers.mean(h)
            fluid.optimizer.SGD(learning_rate=0.1).minimize(cost)
        stats = dce_pass(main, PassContext(feed_names=("x",),
                                           fetch_names=(cost.name,)))
        types = _op_types(main)
        assert "sgd" in types  # persistable write = live


# ---------------------------------------------------------------------------
# fusion
# ---------------------------------------------------------------------------

class TestFusion:
    def _chain(self):
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            b = main.global_block()
            b.create_var(name="x", shape=(4,), dtype="float32",
                         is_data=True)
            b.append_op("scale", inputs={"X": ["x"]},
                        outputs={"Out": ["t0"]}, attrs={"scale": 2.0})
            b.append_op("relu", inputs={"X": ["t0"]},
                        outputs={"Out": ["t1"]}, attrs={})
            b.append_op("scale", inputs={"X": ["t1"]},
                        outputs={"Out": ["out"]},
                        attrs={"scale": 3.0, "bias": 1.0})
        return main

    def test_chain_collapses_and_computes_identically(self):
        main = self._chain()
        x = np.array([-1.0, 0.0, 1.0, 2.0], "float32")
        (ref,) = _run(main, feed={"x": x}, fetches=["out"])
        stats = fuse_elementwise_pass(
            main, PassContext(feed_names=("x",), fetch_names=("out",)))
        assert stats == {"chains": 1, "members": 3}
        assert _op_types(main) == [FUSED_OP_TYPE]
        fused = main.global_block().ops[0]
        assert fused.attr(RNG_SLOTS_ATTR) == 3  # keeps key positions
        (out,) = _run(main, feed={"x": x}, fetches=["out"])
        np.testing.assert_array_equal(out, ref)

    def test_externally_consumed_intermediate_splits_chain(self):
        main = self._chain()
        # t1 is now also fetched -> it may not vanish inside a fusion
        stats = fuse_elementwise_pass(
            main, PassContext(feed_names=("x",),
                              fetch_names=("out", "t1")))
        types = _op_types(main)
        assert types[0] == FUSED_OP_TYPE  # scale+relu still fuse
        assert types[-1] == "scale"       # the tail stays separate
        out, t1 = _run(main,
                       feed={"x": np.ones(4, "float32")},
                       fetches=["out", "t1"])
        np.testing.assert_allclose(t1, np.full(4, 2.0))
        np.testing.assert_allclose(out, np.full(4, 7.0))


# ---------------------------------------------------------------------------
# the verify-sandwich: a broken pass must be rejected
# ---------------------------------------------------------------------------

class TestVerifySandwich:
    def _program(self):
        main, _startup, feeds, fetches = self._program_with_startup()
        return main, feeds, fetches

    def _program_with_startup(self):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = layers.data("x", shape=[4], dtype="float32")
            h = layers.fc(x, 8, act="relu")
            cost = fluid.layers.mean(h)
        return main, startup, ("x",), (cost.name,)

    def test_pass_deleting_a_needed_op_is_aborted(self):
        main, startup, feeds, fetches = self._program_with_startup()

        def evil_delete(program, ctx):
            # drop the op producing the fetch target
            program.global_block().ops.pop()
            return {"mangled": 1}

        pipe = PassPipeline([evil_delete])
        optimized, report = pipe.run(main, feed_names=feeds,
                                     fetch_names=fetches)
        assert report.passes[0]["status"] == "aborted"
        assert report.passes[0]["new_diagnostics"]
        # the program reverted: still runs and fetches
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup)
            (out,) = exe.run(optimized,
                             feed={"x": np.ones((1, 4), "float32")},
                             fetch_list=list(fetches), scope=scope)
        assert np.isfinite(out).all()

    def test_pass_rewiring_to_undefined_name_is_aborted(self):
        main, feeds, fetches = self._program()

        def evil_rewire(program, ctx):
            op = program.global_block().ops[-1]
            op.inputs = {k: ["__no_such_var__"] for k in op.inputs}
            return {"mangled": 1}

        pipe = PassPipeline([evil_rewire])
        optimized, report = pipe.run(main, feed_names=feeds,
                                     fetch_names=fetches)
        assert report.passes[0]["status"] == "aborted"
        codes = {d["code"] for d in
                 report.passes[0]["new_diagnostics"]}
        assert "PTA001" in codes

    def test_raising_pass_is_aborted_not_fatal(self):
        main, feeds, fetches = self._program()

        def evil_raise(program, ctx):
            raise RuntimeError("boom")

        optimized, report = PassPipeline([evil_raise]).run(
            main, feed_names=feeds, fetch_names=fetches)
        assert report.passes[0]["status"] == "aborted"
        assert report.passes[0]["stats"] == {"raised": 1}

    def test_input_program_never_mutated(self):
        main, feeds, fetches = self._program()
        before = main.to_dict()
        optimize_program(main, feed_names=feeds, fetch_names=fetches)
        assert main.to_dict() == before

    def test_unknown_pass_name_rejected(self):
        with pytest.raises(ValueError, match="unknown optimization"):
            PassPipeline(["not_a_pass"])


# ---------------------------------------------------------------------------
# RNG-slot exactness: removing ops must not shift dropout keys
# ---------------------------------------------------------------------------

class TestRngSlots:
    def test_dce_preserves_dropout_masks(self):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = layers.data("x", shape=[16], dtype="float32")
            # a dead pure op BEFORE the dropout: removing it shifts the
            # op positions, and without slot bookkeeping the mask key
            dead = layers.fc(x, 4)
            h = layers.fc(x, 16)
            d = fluid.layers.dropout(h, dropout_prob=0.5)
            out = fluid.layers.mean(d)
        feed = {"x": np.random.RandomState(0)
                .randn(4, 16).astype("float32")}
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup)
            main.random_seed = 9
            (ref,) = exe.run(main, feed=feed, fetch_list=[out.name],
                             scope=scope)
        optimized, report = optimize_program(
            main, feed_names=("x",), fetch_names=(out.name,))
        assert report.ops_removed() > 0  # the dead fc went away
        # surviving ops carry the removed ops' rng slots
        slots = [op.attr(RNG_SLOTS_ATTR, 1)
                 for op in optimized.global_block().ops]
        assert sum(slots) == len(main.global_block().ops)
        scope2 = fluid.Scope()
        with fluid.scope_guard(scope2):
            exe2 = fluid.Executor()
            exe2.run(startup)
            (opt_out,) = exe2.run(optimized, feed=feed,
                                  fetch_list=[out.name], scope=scope2)
        # EXACT: the dropout folded the same key
        np.testing.assert_array_equal(ref, opt_out)


# ---------------------------------------------------------------------------
# executor wiring (PADDLE_TPU_OPT)
# ---------------------------------------------------------------------------

class TestExecutorWiring:
    def _train(self):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = layers.data("x", shape=[8], dtype="float32")
            y = layers.data("y", shape=[1], dtype="float32")
            h = layers.fc(x, 8, act="relu")
            pred = layers.fc(h, 1)
            cost = fluid.layers.mean(
                fluid.layers.square(pred - y))
            fluid.optimizer.SGD(learning_rate=0.01).minimize(cost)
        return main, startup, cost

    def test_env_gated_and_memoized(self, monkeypatch):
        main, startup, cost = self._train()
        main.random_seed = startup.random_seed = 4
        rng = np.random.RandomState(1)
        feed = {"x": rng.randn(4, 8).astype("float32"),
                "y": rng.randn(4, 1).astype("float32")}

        scope = fluid.Scope()
        monkeypatch.delenv("PADDLE_TPU_OPT", raising=False)
        with fluid.scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup)
            (ref,) = exe.run(main, feed=feed, fetch_list=[cost.name],
                             scope=scope)
            assert exe._opt_cache == {}  # off by default

        monkeypatch.setenv("PADDLE_TPU_OPT", "1")
        scope2 = fluid.Scope()
        with fluid.scope_guard(scope2):
            exe2 = fluid.Executor()
            exe2.run(startup)
            (opt1,) = exe2.run(main, feed=feed, fetch_list=[cost.name],
                               scope=scope2)
            assert len(exe2._opt_cache) >= 1
            memo = dict(exe2._opt_cache)
            (_,) = exe2.run(main, feed=feed, fetch_list=[cost.name],
                            scope=scope2)
            # second run re-used the optimized clone (same objects)
            for k, v in memo.items():
                assert exe2._opt_cache[k] is v
        np.testing.assert_allclose(ref, opt1, rtol=1e-5, atol=1e-6)

    def test_program_mutation_reoptimizes(self, monkeypatch):
        main, startup, cost = self._train()
        monkeypatch.setenv("PADDLE_TPU_OPT", "1")
        scope = fluid.Scope()
        feed = {"x": np.zeros((2, 8), "float32"),
                "y": np.zeros((2, 1), "float32")}
        with fluid.scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup)
            exe.run(main, feed=feed, fetch_list=[cost.name],
                    scope=scope)
            n = len(exe._opt_cache)
            main.bump_version()
            exe.run(main, feed=feed, fetch_list=[cost.name],
                    scope=scope)
            assert len(exe._opt_cache) == n + 1

    def test_a_clone_is_its_own_programs_alone(self, monkeypatch):
        """The memo is keyed by ``id(program)``, which the next
        short-lived program can take over (a generation bundle's two load
        programs, same feeds and fetches: the decode model then loaded
        the prefill's parameter list and its pools were never read).  An
        entry is a hit for the program it was made from and no other."""
        monkeypatch.setenv("PADDLE_TPU_OPT", "1")
        first, _, cost = self._train()
        second, _, _ = self._train()
        exe = fluid.Executor()
        clone = exe._maybe_optimize(first, {}, (cost.name,))
        assert exe._maybe_optimize(first, {}, (cost.name,)) is clone
        (key, entry), = exe._opt_cache.items()
        # ``second`` at ``first``'s address, as after a collection
        stale = (id(second),) + key[1:]
        exe._opt_cache[stale] = entry
        other = exe._maybe_optimize(second, {}, (cost.name,))
        assert other is not clone
        assert exe._maybe_optimize(second, {}, (cost.name,)) is other

    def test_amortize_gate_interprets_startup(self, monkeypatch):
        from paddle_tpu.analysis.opt.passes import AMORTIZE_MIN_OPS
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = layers.data("x", shape=[8], dtype="float32")
            h = x
            for _ in range(1 + AMORTIZE_MIN_OPS // 2):
                h = layers.fc(h, 8)
            cost = fluid.layers.mean(h)
        assert len(startup.global_block().ops) >= AMORTIZE_MIN_OPS
        optimized, _ = optimize_program(startup)
        assert getattr(optimized, "_opt_interpret", False)
        # ...but never for a program with fetch targets
        opt_main, _ = optimize_program(main, feed_names=("x",),
                                       fetch_names=(cost.name,))
        assert not getattr(opt_main, "_opt_interpret", False)
        # and the interpreted startup still initializes the scope
        monkeypatch.setenv("PADDLE_TPU_OPT", "1")
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup)
            (out,) = exe.run(main,
                             feed={"x": np.ones((2, 8), "float32")},
                             fetch_list=[cost.name], scope=scope)
        assert np.isfinite(out).all()


# ---------------------------------------------------------------------------
# donation planner (memory_optimization_transpiler rewrite)
# ---------------------------------------------------------------------------

class TestDonationPlan:
    def test_plan_facts_and_feed_donation(self):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = layers.data("x", shape=[4], dtype="float32")
            h = layers.fc(x, 4)
            cost = fluid.layers.mean(h)
            fluid.optimizer.SGD(learning_rate=0.1).minimize(cost)
        plan = plan_donation(main, feed_names=("x",),
                             fetch_names=(cost.name,))
        assert main._donation_plan is plan
        assert "x" in plan.donatable_feeds  # dies inside the step
        assert plan.inplace_updates         # sgd ParamOut facts
        assert all(t == "sgd" for _, t, _ in
                   plan.inplace_updates.values())
        assert plan.dropped == []
        assert "donation plan" in plan.report()

    def test_hazardous_update_is_dropped_not_planned(self):
        # a read AFTER the in-place update: PTA009 — the planner must
        # refuse the aliasing fact for that var
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = layers.data("x", shape=[4], dtype="float32")
            h = layers.fc(x, 4)
            cost = fluid.layers.mean(h)
            fluid.optimizer.SGD(learning_rate=0.1).minimize(cost)
        b = main.global_block()
        sgd = next(op for op in b.ops if op.type == "sgd")
        param = sgd.output("ParamOut")[0]
        b.append_op("scale", inputs={"X": [param]},
                    outputs={"Out": ["late_read"]}, attrs={"scale": 1.0})
        hazards = [d for d in lints.check_graph(main)
                   if d.code == "PTA009"]
        assert hazards  # the lint sees it...
        plan = plan_donation(main, feed_names=("x",),
                             fetch_names=(cost.name, "late_read"))
        dropped_vars = {v for v, _ in plan.dropped}
        assert param in dropped_vars          # ...so the plan drops it
        assert param not in plan.inplace_updates


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

class TestOptCli:
    def test_zoo_target(self, capsys):
        from paddle_tpu.cli import main
        assert main(["opt", "--zoo", "mnist"]) == 0
        out = capsys.readouterr().out
        assert "optimization report" in out
        assert "donation plan" in out

    def test_bad_target_exits_2(self, tmp_path, capsys):
        from paddle_tpu.cli import main
        assert main(["opt", str(tmp_path / "nope")]) == 2
        assert main(["opt"]) == 2

    def test_json_report(self, capsys):
        import json
        from paddle_tpu.cli import main
        assert main(["opt", "--zoo", "mnist", "--json"]) == 0
        body = json.loads(capsys.readouterr().out)
        assert body["targets"]
        t = body["targets"][0]
        assert {"passes", "ops_before", "ops_after", "target",
                "donation_plan", "interpret"} <= set(t)
