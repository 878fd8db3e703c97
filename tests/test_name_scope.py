"""``fluid.name_scope`` and ``op_role``: stamped into the Program by the
layers DSL / ``append_backward`` / ``Optimizer.minimize``, kept by clone,
serialisation and the opt passes, and carried by the executor into the
lowered HLO's ``op_name`` metadata beside the ``ptop_`` scope
(docs/observability.md: ``pt_step/<role>/<scope...>/ptop_<type>__<out>``).
Metadata only: the computation is what it was without them."""

import contextlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import framework, layers, profiler
from paddle_tpu.executor import lower_block
from paddle_tpu.models import transformer as T

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NS, ROLE = framework.OP_NAMESCOPE_ATTR, framework.OP_ROLE_ATTR


def _small(scoped=True, optimizer=True):
    """x -> fc(relu) -> fc -> softmax cross entropy, under ``body`` /
    ``loss`` scopes when ``scoped``."""
    scope = fluid.name_scope if scoped else \
        (lambda name: contextlib.nullcontext())
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[16], dtype="float32")
        y = layers.data("y", shape=[1], dtype="int64")
        with scope("body"):
            with scope("hidden"):
                h = layers.fc(x, 32, act="relu")
            out = layers.fc(h, 4, act="softmax")
        with scope("loss"):
            loss = layers.reduce_mean(layers.cross_entropy(out, y))
        test = main.clone(for_test=True)
        if optimizer:
            fluid.optimizer.Adam(learning_rate=0.1).minimize(loss)
    return main, startup, test, loss


def _ops(program):
    return program.global_block().ops


def _lowered(program, fetch, feed, debug_info):
    """HLO text of the program's whole step as the executor lowers it."""
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.global_scope()
    block = program.global_block()
    feeds = {n: jnp.asarray(v) for n, v in feed.items()}
    parts = exe._prepare(program, block, feeds, (fetch,), scope)
    ro = {n: jnp.asarray(scope.find_var(n)) for n in parts["ro_names"]}
    inout = {n: jnp.asarray(scope.find_var(n))
             for n in parts["inout_names"]}
    lowered = jax.jit(parts["step"]).lower(feeds, ro, inout,
                                           jax.random.PRNGKey(0))
    return lowered.as_text(debug_info=debug_info)


def _feed(rows=4):
    rng = np.random.RandomState(0)
    return {"x": rng.rand(rows, 16).astype("f"),
            "y": rng.randint(0, 4, (rows, 1)).astype("int64")}


class TestNameScopeInTheProgram:
    def test_nests_and_stamps_every_op(self):
        main, _, _, _ = _small(optimizer=False)
        scopes = [op.attr(NS) for op in _ops(main)]
        assert scopes[0] == "body/hidden"
        assert "body" in scopes and "loss" in scopes
        assert all(scopes), "an op built under a scope lacks op_namescope"
        assert not any(op.has_attr(ROLE) for op in _ops(main)), \
            "a forward-only program carries no op_role"
        assert _ops(main)[0].attr(ROLE, "forward") == "forward"

    def test_adds_no_op_no_var_and_leaves_unique_name_alone(self):
        def build(scoped):
            with framework.unique_name_scope("t_"):
                main, startup, _, _ = _small(scoped)
            strip = lambda d: {k: v for k, v in d.items() if k != "attrs"}
            return ([strip(op.to_dict()) | {"attrs": {
                k: v for k, v in op.attrs.items() if k not in (NS, ROLE)}}
                for op in _ops(main)],
                list(main.global_block().vars),
                [op.type for op in _ops(startup)])
        plain, scoped = build(False), build(True)
        for a, b in zip(plain[0], scoped[0]):
            a.pop("creation_site", None), b.pop("creation_site", None)
        assert plain == scoped

    def test_empty_name_is_refused_and_the_stack_unwinds(self):
        with pytest.raises(ValueError):
            with fluid.name_scope(""):
                pass
        with pytest.raises(RuntimeError):
            with fluid.name_scope("a"):
                raise RuntimeError
        main = fluid.Program()
        with fluid.program_guard(main):
            layers.fill_constant(shape=[1], dtype="float32", value=1.0)
        assert not _ops(main)[0].has_attr(NS)

    def test_roles_and_inherited_scopes(self):
        main, _, _, loss = _small()
        ops = _ops(main)
        roles = [op.attr(ROLE, "forward") for op in ops]
        first_bwd = roles.index("backward")
        first_opt = roles.index("optimize")
        assert roles[:first_bwd] == ["forward"] * first_bwd
        assert set(roles[first_bwd:first_opt]) == {"backward"}
        assert set(roles[first_opt:]) == {"optimize"}
        seed = ops[first_bwd]
        assert seed.type == "fill_constant" and seed.attr(NS) == "loss"
        by_type = {op.type: op for op in ops}
        assert by_type["mul_grad"].attr(NS) in ("body", "body/hidden")
        assert by_type["relu_grad"].attr(NS) == "body/hidden"
        assert by_type["cross_entropy_grad"].attr(NS) == "loss"
        adam = [op for op in ops if op.type == "adam"]
        assert adam and all(op.attr(ROLE) == "optimize" and
                            not op.has_attr(NS) for op in adam)

    def test_optimizer_does_not_mark_the_startup_program(self):
        _, startup, _, _ = _small()
        assert not any(op.has_attr(ROLE) for op in _ops(startup))

    @pytest.mark.parametrize("how", ["clone", "clone_for_test",
                                     "round_trip", "prune", "opt_pipeline"])
    def test_attributes_survive(self, how):
        main, _, _, loss = _small()
        if how == "clone":
            other = main.clone()
        elif how == "clone_for_test":
            other = main.clone(for_test=True)
        elif how == "round_trip":
            other = fluid.Program.from_dict(
                json.loads(json.dumps(main.to_dict())))
        elif how == "prune":
            other = main.prune([loss])
        else:
            from paddle_tpu.analysis.opt import optimize_program
            other, _ = optimize_program(main, feed_names=("x", "y"),
                                        fetch_names=(loss.name,))
        want = {op.output_arg_names[0]: (op.attr(NS), op.attr(ROLE))
                for op in _ops(main)}
        got = {op.output_arg_names[0]: (op.attr(NS), op.attr(ROLE))
               for op in _ops(other)}
        assert got and all(want[k] == v for k, v in got.items())
        if how != "prune":
            assert {r for _, r in got.values()} == \
                {None, "backward", "optimize"}

    def test_fusing_pass_keeps_the_first_ops_annotations(self):
        from paddle_tpu.analysis.opt import passes
        main = fluid.Program()
        with fluid.program_guard(main):
            x = layers.data("x", shape=[8], dtype="float32")
            with fluid.name_scope("first"):
                a = layers.scale(x, scale=2.0)
            with fluid.name_scope("second"):
                b = layers.relu(a)
        ctx = passes.PassContext(feed_names=("x",), fetch_names=(b.name,))
        stats = passes.fuse_elementwise_pass(main, ctx)
        fused = [op for op in _ops(main)
                 if op.type == passes.FUSED_OP_TYPE]
        assert stats["chains"] == 1 and fused[0].attr(NS) == "first"


class TestScopesInTheLoweredHLO:
    def test_role_then_scope_then_ptop(self):
        main, startup, test, loss = _small()
        fluid.Executor(fluid.CPUPlace()).run(startup)
        hlo = _lowered(main, loss.name, _feed(), debug_info=True)
        assert "pt_step/body/hidden/ptop_mul__" in hlo
        assert "pt_step/bwd/body/hidden/ptop_mul_grad__" in hlo
        assert "pt_step/bwd/loss/ptop_fill_constant__" in hlo
        assert "pt_step/opt/ptop_adam__" in hlo
        inference = _lowered(test, loss.name, _feed(), debug_info=True)
        assert "pt_step/body/hidden/ptop_mul__" in inference
        assert "pt_step/bwd/" not in inference
        assert "pt_step/opt/" not in inference

    def test_unannotated_program_lowers_as_before(self):
        main, startup, _, loss = _small(scoped=False, optimizer=False)
        fluid.Executor(fluid.CPUPlace()).run(startup)
        hlo = _lowered(main, loss.name, _feed(), debug_info=True)
        assert "pt_step/ptop_mul__" in hlo
        assert "pt_step/bwd/" not in hlo and "pt_step/opt/" not in hlo

    def test_components_are_sanitised(self):
        op = framework.Operator(fluid.Program().global_block(), "scale",
                                attrs={NS: "a.b/c", ROLE: "backward"})
        assert profiler.op_scope_path(op) == ["bwd", "a_b", "c",
                                              "ptop_scale__"]
        plain = framework.Operator(fluid.Program().global_block(), "scale")
        assert profiler.op_scope_path(plain) == ["ptop_scale__"]

    def test_scope_path_grammar(self):
        parse = profiler.parse_scope_path
        assert parse("jit(multi)/jit(main)/while/body/pt_step/bwd/enc0/"
                     "self_attn/core/ptop_matmul_grad__x/dot_general") == \
            ("bwd", ("enc0", "self_attn", "core"), "matmul_grad")
        assert parse("jit(step)/pt_step/enc0/ffn/ptop_mul__y/dot") == \
            ("fwd", ("enc0", "ffn"), "mul")
        assert parse("jit(step)/pt_step/opt/ptop_adam__w") == \
            ("opt", (), "adam")
        assert parse("jit(step)/pt_step/ptop_mul__y") == ("fwd", (), "mul")
        assert parse("jit(step)/ptop_mul__y") == ("fwd", (), "mul")
        assert parse("jit(step)/pt_step/copy") is None


def _toy_transformer(strip):
    hp = T.ModelHyperParams()
    hp.n_layer, hp.d_model, hp.d_inner_hid = 2, 32, 64
    hp.n_head, hp.d_key, hp.d_value = 2, 16, 16
    hp.src_vocab_size = hp.trg_vocab_size = 128
    hp.max_length = 16
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    with framework.unique_name_scope("toy_"):
        with fluid.program_guard(main, startup):
            avg_cost, _ = T.transformer(4, 16, 16, hp)
            fluid.optimizer.Adam(learning_rate=1e-3).minimize(avg_cost)
    if strip:
        for program in (main, startup):
            for op in _ops(program):
                op.attrs.pop(NS, None)
                op.attrs.pop(ROLE, None)
    return main, startup, avg_cost, T.fake_batch(4, 16, 16, hp, seed=3)


def _partition_rules():
    """The six partition rules as the benchmark's JSONs state them."""
    folder = os.path.join(ROOT, "benchmark", "layer_metrics")
    with open(os.path.join(folder, "train_other_device_ms.json")) as f:
        parts = json.load(f)["minus"]
    rules = {}
    for name in parts:
        with open(os.path.join(folder, name + ".json")) as f:
            spec = json.load(f)
        rules[name] = (spec["events"], spec.get("except", []))
    return rules


class TestTransformerScopes:
    def test_metadata_only_same_hlo_and_same_loss(self):
        texts, losses = [], []
        for strip in (False, True):
            main, startup, avg_cost, batch = _toy_transformer(strip)
            scope = fluid.Scope()
            with fluid.scope_guard(scope):
                exe = fluid.Executor(fluid.CPUPlace())
                exe.run(startup)
                texts.append(_lowered(main, avg_cost.name, batch,
                                      debug_info=False))
                losses.append([float(exe.run(
                    main, feed=batch, fetch_list=[avg_cost.name])[0])
                    for _ in range(2)])
        assert texts[0] == texts[1]
        assert losses[0] == losses[1]

    def test_every_op_lies_under_one_leaf_scope(self):
        main, _, _, _ = _toy_transformer(strip=False)
        leaves = ("embed", "head", "proj", "core", "post", "ffn")
        for op in _ops(main):
            if op.attr(ROLE) == "optimize":
                assert not op.has_attr(NS)
                continue
            scope = op.attr(NS)
            assert scope and scope.split("/")[-1] in leaves, (op, scope)
            assert scope.split("/")[0] in (
                "embed", "head", "enc0", "enc1", "dec0", "dec1")

    def test_every_op_matches_exactly_one_partition_rule(self):
        """An op's scope path, as the executor writes it, is claimed by
        exactly one of the benchmark's six device-time groups; the
        embeddings alone are claimed by none (``train_other_device_ms``
        holds them)."""
        main, _, _, _ = _toy_transformer(strip=False)
        rules = _partition_rules()
        assert len(rules) == 6
        seen = set()
        for op in _ops(main):
            path = "/".join(["pt_step"] + profiler.op_scope_path(op)
                            + ["x"]).lower()
            hits = [name for name, (events, excepts) in rules.items()
                    if any(e.lower() in path for e in events)
                    and not any(e.lower() in path for e in excepts)]
            if "/embed/" in path and "ptop_dropout" not in path:
                assert hits == [], (path, hits)
                continue
            assert len(hits) == 1, (path, hits)
            seen.add(hits[0])
        assert seen == set(rules)


def test_serving_bundle_carries_its_role_and_groups(tmp_path):
    """A generative bundle's programs are annotated since PR 51: every op
    of the exported prefill / decode model names its program's role and a
    sublayer (``tests/test_serving_scopes.py`` has the vocabulary), no op
    carries an ``op_role``, and the executor lowers each under that path
    before its ``ptop_`` scope."""
    from paddle_tpu.models import gen_lm
    hp = gen_lm.GenConfig()
    gen_lm.export_gen_model(str(tmp_path), hp, num_slots=2)
    for part, role in (("prefill", "gen_prefill"), ("decode", "gen_decode")):
        with open(tmp_path / part / "__model__") as f:
            ops = json.load(f)["program"]["blocks"][0]["ops"]
        assert ops and all(
            op["attrs"][NS].split("/")[0] == role and ROLE not in op["attrs"]
            for op in ops), part
    decode = fluid.Program()
    with fluid.program_guard(decode, fluid.Program()):
        gen_lm.build_paged_decode_program(hp, 2, 8, 8)
    assert _ops(decode) and all(
        profiler.op_scope_path(op)
        == op.attr(NS).split("/") + [profiler.op_scope_name(op)]
        for blk in decode.blocks for op in blk.ops)


def test_a_scoped_program_keeps_its_own_compile_cache_entry(tmp_path,
                                                            monkeypatch):
    """jax's persistent-cache key strips metadata by default, so the
    scoped twin of a cached program would load the twin's executable with
    ITS ``op_name``s (seen on the chip: the parent of PR 35 reported PR
    35's scope names under a shared cache).  Every program keys its entry
    with its metadata (``executor.py``, at import), so the twin misses
    and then finds its own entry, and so does the program without
    annotations."""
    from paddle_tpu.executor import disable_compile_cache
    monkeypatch.setenv("PADDLE_TPU_COMPILE_CACHE", str(tmp_path / "xla"))
    counter = profiler.runtime_metrics.counter
    flag = "jax_compilation_cache_include_metadata_in_key"

    def run_fresh(scoped):
        jax.clear_caches()
        # a restart: the same program under the same generated names (a
        # ``ptop_`` scope holds an output's name, and is in the key now)
        with framework.unique_name_scope("cc_"):
            main, startup, _, loss = _small(scoped=scoped, optimizer=False)
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        exe.run(startup, scope=scope)
        hits, misses = counter("compile_cache.hits"), \
            counter("compile_cache.misses")
        exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
        assert getattr(jax.config, flag) is True      # never flipped
        return counter("compile_cache.hits") - hits, \
            counter("compile_cache.misses") - misses

    try:
        # (the key holds the op names and no call stack: any call site)
        (_, cold), plain, twin, again = [
            run_fresh(scoped) for scoped in (False, False, True, True)]
        assert cold > 0                               # fills the cache
        assert plain[0] > 0 and plain[1] == 0         # its own entry: a hit
        assert twin[1] > 0, "the scoped twin took the unscoped executable"
        assert again[0] > 0 and again[1] == 0         # its own entry
    finally:
        disable_compile_cache()
