"""Multi-device tests on the 8-device virtual CPU mesh (reference
strategy: simulate clusters on one host, SURVEY.md §4.5;
test_parallel_executor.py analog)."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import paddle_tpu as fluid
import paddle_tpu.layers as layers
from paddle_tpu.parallel.mesh import make_mesh
from paddle_tpu.parallel import ParallelExecutor


def _mnist_like_program(batch):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = layers.data(name="img", shape=[batch, 32],
                          append_batch_size=False)
        label = layers.data(name="label", shape=[batch, 1], dtype="int64",
                            append_batch_size=False)
        hidden = layers.fc(input=img, size=64, act="relu")
        pred = layers.fc(input=hidden, size=10, act="softmax")
        loss = layers.mean(layers.cross_entropy(input=pred, label=label))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


class TestDataParallel:
    def test_dp_matches_single_device(self):
        batch = 16
        rng = np.random.RandomState(0)
        img = rng.rand(batch, 32).astype("float32")
        lab = rng.randint(0, 10, size=(batch, 1)).astype("int64")

        # single-device run
        main, startup, loss = _mnist_like_program(batch)
        s1 = fluid.Scope()
        with fluid.scope_guard(s1):
            exe = fluid.Executor()
            exe.run(startup)
            init_params = {p.name: np.asarray(s1.find_var(p.name)).copy()
                           for p in main.global_block().all_parameters()}
            ref_losses = [float(np.asarray(
                exe.run(main, feed={"img": img, "label": lab},
                        fetch_list=[loss])[0]).reshape(()))
                for _ in range(3)]

        # data-parallel run over 8 virtual devices, same init (seeded)
        main2, startup2, loss2 = _mnist_like_program(batch)
        mesh = make_mesh((8,), ("data",))
        s2 = fluid.Scope()
        with fluid.scope_guard(s2):
            exe = fluid.Executor()
            exe.run(startup2)
            # copy INITIAL params from the single-device run for equality
            for name, val in init_params.items():
                if s2.find_var(name) is not None:
                    s2.set_var(name, val)
            pexe = ParallelExecutor(loss_name=loss2.name,
                                    main_program=main2, mesh=mesh)
            dp_losses = [float(np.asarray(
                pexe.run(feed={"img": img, "label": lab},
                         fetch_list=[loss2])[0]).reshape(()))
                for _ in range(3)]

        np.testing.assert_allclose(dp_losses, ref_losses, rtol=2e-5,
                                   atol=1e-6)


class TestDropoutOnADataMesh:
    @pytest.mark.parametrize("mesh_shape,axes", [
        ((4,), ("data",)), ((2, 2), ("data", "model"))])
    def test_every_shard_draws_its_own_mask_of_its_own_shape(self, mesh_shape,
                                                             axes):
        """GSPMD does not partition ``rng_bit_generator`` (it draws the
        GLOBAL shape on every device and slices it), so the ``dropout``
        lowering draws per shard under a ``shard_map`` over ``data``;
        beside a ``model`` axis (a tensor-parallel ``fc`` feeds the
        dropout and a step is trained through it) the draw is split over
        ``data`` alone and the same along ``model``."""
        batch, cols, p, shards = 64, 256, 0.5, mesh_shape[0]
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 7
        with fluid.program_guard(main, startup):
            x = layers.data(name="x", shape=[batch, cols],
                            append_batch_size=False)
            hidden = layers.fc(input=x, size=cols, param_attr="tp_w",
                               bias_attr=False)
            dropped = layers.dropout(hidden, dropout_prob=p)
            loss = layers.mean(layers.fc(input=dropped, size=1))
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        mask_name = [op for op in main.global_block().ops
                     if op.type == "dropout"][0].output("Mask")[0]
        mesh = make_mesh(mesh_shape, axes,
                         devices=jax.devices()[:int(np.prod(mesh_shape))])
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            fluid.Executor().run(startup)
            pexe = ParallelExecutor(
                loss_name=loss.name, main_program=main, mesh=mesh,
                param_shardings=[(r"tp_w", P(None, "model"))]
                if "model" in axes else None)
            xv = np.random.RandomState(0).rand(batch, cols).astype("float32")
            mask, loss_value = pexe.run(feed={"x": xv},
                                        fetch_list=[mask_name, loss.name],
                                        return_numpy=False)
        assert np.isfinite(np.asarray(loss_value)).all()
        assert {s.data.shape[0] for s in mask.addressable_shards} == \
            {batch // shards}
        mask = np.asarray(mask)
        assert abs(mask.mean() - (1 - p)) < 4 * (p * (1 - p) / mask.size) ** .5
        blocks = mask.reshape(shards, batch // shards, cols)
        for i in range(shards):
            for j in range(i + 1, shards):
                differ = (blocks[i] != blocks[j]).mean()
                assert abs(differ - 2 * p * (1 - p)) < 0.03, (i, j, differ)
        # the compiled per-device program: nothing the generator makes
        # is as large as the global mask (the CPU backend expands the
        # instruction into Philox rounds under the same op_name)
        texts = [e.perf["exec"].as_text() for e in pexe._cache.values()
                 if getattr(e, "perf", None) and e.perf.get("exec")]
        assert texts
        sizes = [int(np.prod([int(d) for d in dims.split(",")]))
                 for text in texts for line in text.splitlines()
                 if "ptop_dropout__" in line
                 for dims in re.findall(r"= u(?:16|32|64)\[([0-9,]+)\]",
                                        line)]
        assert sizes and max(sizes) >= batch // shards * cols // 4
        assert max(sizes) <= batch // shards * cols < batch * cols


class TestRunPipelineParallel:
    def test_run_pipeline_drives_parallel_executor(self):
        """Regression: run_pipeline passed program POSITIONALLY into
        self.run, but ParallelExecutor.run's first positional is
        fetch_list — guarded parallel training (the sentinel's loop)
        died with a TypeError on the first batch."""
        import paddle_tpu.datapipe as dp
        batch = 8
        main, startup, loss = _mnist_like_program(batch)
        mesh = make_mesh((8,), ("data",))
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup)
            pexe = ParallelExecutor(loss_name=loss.name,
                                    main_program=main, mesh=mesh)
            rng = np.random.RandomState(0)
            rows = [{"img": rng.rand(32).astype("float32"),
                     "label": rng.randint(0, 10, (1,)).astype("int64")}
                    for _ in range(batch * 2)]
            pipe = dp.InMemorySource(rows).batch(batch, drop_last=True)
            outs = pexe.run_pipeline(main, pipe, fetch_list=[loss.name])
        assert len(outs) == 2
        for o in outs:
            assert np.isfinite(np.asarray(o[0])).all()


class TestTensorParallel:
    def test_tp_transformer_matches_replicated(self):
        from paddle_tpu.models import transformer as T
        hp = T.ModelHyperParams()
        hp.d_model, hp.d_inner_hid, hp.n_layer = 32, 64, 2
        hp.n_head, hp.d_key, hp.d_value = 4, 8, 8
        hp.src_vocab_size = hp.trg_vocab_size = 64
        hp.max_length = 16
        hp.dropout = 0.0
        batch, slen = 8, 8

        def build():
            main, startup = fluid.Program(), fluid.Program()
            with fluid.program_guard(main, startup):
                cost, _ = T.transformer(batch, slen, slen, hp)
                fluid.optimizer.Adam(learning_rate=1e-3).minimize(cost)
            return main, startup, cost

        feed = T.fake_batch(batch, slen, slen, hp)

        main, startup, cost = build()
        s1 = fluid.Scope()
        with fluid.scope_guard(s1):
            exe = fluid.Executor()
            exe.run(startup)
            init_params = {p.name: np.asarray(s1.find_var(p.name)).copy()
                           for p in main.global_block().all_parameters()}
            ref = [float(np.asarray(
                exe.run(main, feed=feed, fetch_list=[cost])[0])
                .reshape(())) for _ in range(2)]

        main2, startup2, cost2 = build()
        mesh = make_mesh((2, 4), ("data", "model"))
        s2 = fluid.Scope()
        with fluid.scope_guard(s2):
            exe = fluid.Executor()
            exe.run(startup2)
            for name, val in init_params.items():
                if s2.find_var(name) is not None:
                    s2.set_var(name, val)
            pexe = ParallelExecutor(loss_name=cost2.name,
                                    main_program=main2, mesh=mesh,
                                    param_shardings=T.tp_shardings())
            tp = [float(np.asarray(
                pexe.run(feed=feed, fetch_list=[cost2])[0]).reshape(()))
                for _ in range(2)]

        np.testing.assert_allclose(tp, ref, rtol=5e-4, atol=1e-5)


class TestZeroShardedOptimizer:
    """ZeRO optimizer-state sharding (parallel/zero.py): training with
    dp-sharded accumulators must match the unsharded trajectory, the
    state must actually live sharded on device, and an inconsistent
    plan must fail the PTA016 pass statically."""

    def _run_steps(self, opt_factory, mesh=None, zero=False, steps=3,
                   init_params=None):
        batch = 16
        rng = np.random.RandomState(0)
        img = rng.rand(batch, 32).astype("float32")
        lab = rng.randint(0, 10, size=(batch, 1)).astype("int64")
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = layers.data(name="img", shape=[batch, 32],
                            append_batch_size=False)
            y = layers.data(name="label", shape=[batch, 1], dtype="int64",
                            append_batch_size=False)
            hidden = layers.fc(input=x, size=64, act="relu")
            pred = layers.fc(input=hidden, size=8, act="softmax")
            loss = layers.mean(layers.cross_entropy(input=pred, label=y))
            opt_factory().minimize(loss)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup)
            if init_params is not None:
                for name, val in init_params.items():
                    if scope.find_var(name) is not None:
                        scope.set_var(name, val)
            params = {p.name: np.asarray(scope.find_var(p.name)).copy()
                      for p in main.global_block().all_parameters()}
            if mesh is None:
                runner = exe
                run = lambda: exe.run(main, feed={"img": img, "label": lab},
                                      fetch_list=[loss])
            else:
                runner = ParallelExecutor(loss_name=loss.name,
                                          main_program=main, mesh=mesh,
                                          zero=zero)
                run = lambda: runner.run(feed={"img": img, "label": lab},
                                         fetch_list=[loss])
            losses = [float(np.asarray(run()[0]).reshape(()))
                      for _ in range(steps)]
            state = {n: scope.find_var(n)
                     for n in scope.local_var_names()}
        return losses, params, state, runner

    @pytest.mark.parametrize("opt", ["adam", "momentum"])
    def test_zero_matches_unsharded(self, opt):
        factories = {
            "adam": lambda: fluid.optimizer.Adam(learning_rate=0.01),
            "momentum": lambda: fluid.optimizer.Momentum(
                learning_rate=0.05, momentum=0.9),
        }
        ref, init, _, _ = self._run_steps(factories[opt])
        mesh = make_mesh((4,), ("data",), devices=jax.devices()[:4])
        got, _, state, pexe = self._run_steps(
            factories[opt], mesh=mesh, zero=True, init_params=init)
        np.testing.assert_allclose(got, ref, rtol=2e-5, atol=1e-6)
        # the plan actually sharded something, and the live state is
        # REALLY partitioned on device (1/N per dp rank, not replicated)
        plan = pexe.zero_plan
        assert plan and plan.placements
        for name, spec in plan.placements.items():
            arr = state[name]
            assert tuple(arr.sharding.spec) == spec, name
            shard = arr.addressable_shards[0]
            assert shard.data.shape[0] * 4 == arr.shape[0], name

    def test_zero_on_zoo_model(self):
        """The satellite's zoo-model parity: mnist (conv + fc, Adam)
        trains loss-identical with ZeRO-sharded state on dp4."""
        from paddle_tpu.models import build_train_program
        rng = np.random.RandomState(3)
        feed = {"pixel": rng.rand(8, 1, 28, 28).astype("float32"),
                "label": rng.randint(0, 10, (8, 1)).astype("int64")}

        def one(mesh=None, zero=False, init=None):
            main, startup, feeds, fetches = build_train_program("mnist")
            scope = fluid.Scope()
            with fluid.scope_guard(scope):
                exe = fluid.Executor()
                exe.run(startup)
                if init is not None:
                    for name, val in init.items():
                        if scope.find_var(name) is not None:
                            scope.set_var(name, val)
                params = {p.name:
                          np.asarray(scope.find_var(p.name)).copy()
                          for p in main.global_block().all_parameters()}
                if mesh is None:
                    losses = [float(np.asarray(exe.run(
                        main, feed=feed, fetch_list=[fetches[0]])[0])
                        .reshape(())) for _ in range(2)]
                    return losses, params, None
                pexe = ParallelExecutor(main_program=main, mesh=mesh,
                                        zero=True)
                losses = [float(np.asarray(pexe.run(
                    feed=feed, fetch_list=[fetches[0]])[0]).reshape(()))
                    for _ in range(2)]
                return losses, params, pexe

        ref, init, _ = one()
        mesh = make_mesh((4,), ("data",), devices=jax.devices()[:4])
        got, _, pexe = one(mesh=mesh, zero=True, init=init)
        assert pexe.zero_plan.placements   # conv/fc moments sharded
        np.testing.assert_allclose(got, ref, rtol=5e-5, atol=1e-6)

    def test_inconsistent_state_plan_is_pta016(self):
        """A deliberately inconsistent optimizer-state sharding plan
        (moment1 sharded, moment2 replicated) is a static PTA016 error
        — the verifier refuses it before anything compiles."""
        from paddle_tpu.analysis.distributed import check_sharding
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = layers.data(name="img", shape=[16, 32],
                            append_batch_size=False)
            y = layers.data(name="label", shape=[16, 1], dtype="int64",
                            append_batch_size=False)
            pred = layers.fc(input=x, size=8, act="softmax")
            loss = layers.mean(layers.cross_entropy(input=pred, label=y))
            fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
        block = main.global_block()
        m1 = next(n for n in block.vars if n.startswith("moment1.")
                  and ".w_" in n)
        m2 = "moment2." + m1[len("moment1."):]
        diags = check_sharding(main, {m1: ("data", None), m2: ()},
                               mesh_axes={"data": 4})
        assert any(d.code == "PTA016" and
                   "inconsistently sharded" in d.message
                   for d in diags), [d.format() for d in diags]
        # and the ParallelExecutor path refuses the bad plan end to end
        from paddle_tpu.analysis import ProgramVerificationError
        from paddle_tpu.parallel.zero import zero_plan
        mesh = make_mesh((4,), ("data",), devices=jax.devices()[:4])
        plan = zero_plan(main, mesh)
        plan.placements[m2] = ()         # corrupt the plan by hand
        with pytest.raises(ProgramVerificationError):
            plan.verify()

    def test_zero_collective_helpers_roundtrip(self):
        """The explicit shard_map form of the ZeRO step (built on
        parallel/collective.py): reduce-scatter hands each rank its
        owned 1/N gradient slice, all-gather re-materializes the full
        tensor — together they equal a plain psum."""
        from jax.experimental.shard_map import shard_map
        from paddle_tpu.parallel.zero import (allgather_params,
                                              reduce_scatter_grads)
        mesh = make_mesh((4,), ("data",), devices=jax.devices()[:4])
        rng = np.random.RandomState(0)
        grads = jnp.asarray(rng.rand(4, 8, 3).astype("float32"))

        def step(g):
            owned = reduce_scatter_grads(g[0], "data")   # [2, 3] slice
            assert owned.shape == (2, 3)
            return allgather_params(owned, "data")       # [8, 3] full

        out = shard_map(step, mesh=mesh,
                        in_specs=(P("data", None, None),),
                        out_specs=P(), check_rep=False)(grads)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(grads).sum(0),
                                   rtol=1e-6, atol=1e-6)

    def test_zero_skips_user_ruled_state(self):
        """User param_shardings rules keep precedence: accumulators a
        TP rule matches stay OUT of the ZeRO plan (no double-shard)."""
        from jax.sharding import PartitionSpec as P
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = layers.data(name="img", shape=[16, 32],
                            append_batch_size=False)
            y = layers.data(name="label", shape=[16, 1], dtype="int64",
                            append_batch_size=False)
            pred = layers.fc(input=x, size=8, act="softmax",
                             param_attr="tp_w")
            loss = layers.mean(layers.cross_entropy(input=pred, label=y))
            fluid.optimizer.Momentum(learning_rate=0.05,
                                     momentum=0.9).minimize(loss)
        mesh = make_mesh((2, 2), ("data", "model"),
                         devices=jax.devices()[:4])
        pexe = ParallelExecutor(
            main_program=main, mesh=mesh, zero=True,
            param_shardings=[(r"tp_w", P(None, "model"))])
        assert all("tp_w" not in n
                   for n in pexe.zero_plan.placements), \
            pexe.zero_plan.placements
        assert any("tp_w" in n for n in pexe.zero_plan.skipped)


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, causal):
        from paddle_tpu.parallel.ring_attention import ring_attention
        from paddle_tpu.ops.attention_ops import _reference_attention
        mesh = make_mesh((8,), ("seq",))
        B, H, S, D = 2, 2, 64, 8
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(B, H, S, D).astype("float32") * 0.5)
        k = jnp.asarray(rng.randn(B, H, S, D).astype("float32") * 0.5)
        v = jnp.asarray(rng.randn(B, H, S, D).astype("float32") * 0.5)

        out = ring_attention(q, k, v, mesh, axis="seq", causal=causal)
        ref = _reference_attention(q, k, v, None, causal, D ** -0.5)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    def test_grads_flow(self):
        from paddle_tpu.parallel.ring_attention import ring_attention
        from paddle_tpu.ops.attention_ops import _reference_attention
        mesh = make_mesh((4,), ("seq",), devices=jax.devices()[:4])
        B, H, S, D = 1, 2, 32, 8
        rng = np.random.RandomState(1)
        q = jnp.asarray(rng.randn(B, H, S, D).astype("float32") * 0.5)
        k = jnp.asarray(rng.randn(B, H, S, D).astype("float32") * 0.5)
        v = jnp.asarray(rng.randn(B, H, S, D).astype("float32") * 0.5)

        g_ring = jax.grad(lambda q_: ring_attention(
            q_, k, v, mesh, axis="seq", causal=True).sum())(q)
        g_ref = jax.grad(lambda q_: _reference_attention(
            q_, k, v, None, True, D ** -0.5).sum())(q)
        np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_ref),
                                   rtol=2e-4, atol=2e-5)


class TestRingAttentionOp:
    """Sequence-parallel ring attention (SURVEY.md §2.8 superseding
    design): numerics match single-device attention, and gradients flow
    through the ppermute ring."""

    def _inputs(self, B=2, H=2, S=16, D=4, seed=0):
        rng = np.random.RandomState(seed)
        return (rng.rand(B, H, S, D).astype("float32"),
                rng.rand(B, H, S, D).astype("float32"),
                rng.rand(B, H, S, D).astype("float32"))

    def _reference(self, q, k, v, causal):
        s = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
        if causal:
            S = q.shape[2]
            mask = np.triu(np.ones((S, S), bool), k=1)
            s = np.where(mask[None, None], -1e30, s)
        p = np.exp(s - s.max(-1, keepdims=True))
        p = p / p.sum(-1, keepdims=True)
        return np.einsum("bhqk,bhkd->bhqd", p, v)

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference_on_seq_mesh(self, causal):
        qn, kn, vn = self._inputs()
        q = layers.data(name="q", shape=[2, 2, 16, 4],
                        append_batch_size=False)
        k = layers.data(name="k", shape=[2, 2, 16, 4],
                        append_batch_size=False)
        v = layers.data(name="v", shape=[2, 2, 16, 4],
                        append_batch_size=False)
        out = layers.ring_attention(q, k, v, causal=causal)
        mesh = make_mesh((2, 4), ("data", "seq"))
        pexe = ParallelExecutor(mesh=mesh)
        (got,) = pexe.run(feed={"q": qn, "k": kn, "v": vn},
                          fetch_list=[out])
        np.testing.assert_allclose(np.asarray(got),
                                   self._reference(qn, kn, vn, causal),
                                   rtol=2e-4, atol=2e-5)

    def test_gradients_flow_through_ring(self):
        qn, kn, vn = self._inputs(seed=3)
        q = layers.data(name="q", shape=[2, 2, 16, 4],
                        append_batch_size=False)
        k = layers.data(name="k", shape=[2, 2, 16, 4],
                        append_batch_size=False)
        v = layers.data(name="v", shape=[2, 2, 16, 4],
                        append_batch_size=False)
        for var in (q, k, v):
            var.stop_gradient = False
        out = layers.ring_attention(q, k, v, causal=True)
        loss = layers.reduce_mean(out)
        fluid.append_backward(loss, parameter_list=[])
        mesh = make_mesh((1, 8), ("data", "seq"))
        pexe = ParallelExecutor(mesh=mesh)
        gq, gk, gv = pexe.run(
            feed={"q": qn, "k": kn, "v": vn},
            fetch_list=["q@GRAD", "k@GRAD", "v@GRAD"])
        for g in (gq, gk, gv):
            g = np.asarray(g)
            assert g.shape == (2, 2, 16, 4)
            assert np.isfinite(g).all() and np.abs(g).sum() > 0

        # numeric check of dV against the softmax-weighted cotangent
        ref = self._reference(qn, kn, vn, True)
        eps = 1e-3
        vn2 = vn.copy()
        vn2[0, 0, 5, 2] += eps
        ref2 = self._reference(qn, kn, vn2, True)
        got = float(np.asarray(gv)[0, 0, 5, 2])
        np.testing.assert_allclose(got, (ref2 - ref).mean() / eps,
                                   rtol=5e-2, atol=1e-6)


class TestRingAttentionScaling:
    """Ring attention perf/memory story (VERDICT r2 item 9): at S=4096 the
    4-way-sharded ring compiles and runs where the unsharded composed
    path's [B,H,S,S] scores dominate; XLA's own memory analysis bounds
    the win."""

    def test_s4096_sharded_4way_memory_and_numerics(self):
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from paddle_tpu.parallel.ring_attention import ring_attention
        from paddle_tpu.ops.attention_ops import _reference_attention

        from paddle_tpu.parallel.mesh import make_mesh
        B, H, S, D = 1, 2, 4096, 64
        mesh = make_mesh((4,), ("seq",), devices=jax.devices()[:4])
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.rand(B, H, S, D).astype("float32") * 0.1)
        sh = NamedSharding(mesh, P(None, None, "seq", None))
        qs = jax.device_put(q, sh)

        ring = jax.jit(lambda a, b, c: ring_attention(
            a, b, c, mesh, axis="seq", causal=True))
        ref = jax.jit(lambda a, b, c: _reference_attention(
            a, b, c, None, True, D ** -0.5))
        c_ring = ring.lower(qs, qs, qs).compile()
        c_ref = ref.lower(q, q, q).compile()
        ring_tmp = c_ring.memory_analysis().temp_size_in_bytes
        ref_tmp = c_ref.memory_analysis().temp_size_in_bytes
        # measured on the 8-device CPU mesh: 18.6MB vs 272.6MB (14.6x);
        # assert a conservative bound so compiler drift doesn't flake
        assert ring_tmp * 4 < ref_tmp, (ring_tmp, ref_tmp)

        # reuse the compiled executables (lower().compile() does not
        # populate jit's cache; calling ring()/ref() would recompile)
        def _one(res):
            return res[0] if isinstance(res, (list, tuple)) else res

        out = np.asarray(_one(c_ring(qs, qs, qs)))
        want = np.asarray(_one(c_ref(q, q, q)))
        np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-5)
