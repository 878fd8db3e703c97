"""Fused (Pallas) attention vs composed-op reference, forward and grads."""

import json
import os

import numpy as np
import pytest

import paddle_tpu as fluid
import paddle_tpu.layers as layers

from paddle_tpu.ops.attention_ops import (
    fused_attention, _reference_attention)

import jax
import jax.numpy as jnp


B, H, S, D = 2, 4, 32, 16
_GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "golden")


def _qkv(seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, H, S, D).astype("float32") * 0.3)
    mask = np.ones((B, S), "float32")
    mask[0, -5:] = 0.0
    return mk(), mk(), mk(), jnp.asarray(mask)


class TestFusedAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_pallas_matches_reference(self, causal):
        q, k, v, mask = _qkv()
        ref = _reference_attention(q, k, v, mask, causal, D ** -0.5)
        out = fused_attention(q, k, v, mask, causal, D ** -0.5, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    def test_grads_flow(self):
        q, k, v, mask = _qkv(1)

        def loss_fn(q_, k_, v_):
            return fused_attention(q_, k_, v_, mask, True, D ** -0.5,
                                   True).sum()

        def ref_fn(q_, k_, v_):
            return _reference_attention(q_, k_, v_, mask, True,
                                        D ** -0.5).sum()

        g = jax.grad(loss_fn, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(ref_fn, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)


class TestFlashBackwardKernel:
    """The dedicated flash backward kernels (dq; dk+dv) vs reference vjp."""

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_pallas_bwd_matches_reference(self, causal, dtype):
        from paddle_tpu.ops.attention_ops import (
            _pallas_attention, _pallas_attention_bwd)
        dt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
        q, k, v, mask = (x.astype(dt) if x.ndim == 4 else x
                         for x in _qkv(7))
        scale = D ** -0.5
        out, lse = _pallas_attention(q, k, v, mask, causal, scale,
                                     interpret=True)
        g = jnp.ones_like(out)
        dq, dk, dv = _pallas_attention_bwd(q, k, v, mask, out, lse, g,
                                           causal, scale, interpret=True)
        _, vjp = jax.vjp(
            lambda q_, k_, v_: _reference_attention(q_, k_, v_, mask,
                                                    causal, scale), q, k, v)
        rq, rk, rv = vjp(g)
        tol = dict(rtol=2e-2, atol=3e-2) if dtype == "bfloat16" else \
            dict(rtol=2e-3, atol=2e-4)
        for a, b in ((dq, rq), (dk, rk), (dv, rv)):
            np.testing.assert_allclose(np.asarray(a, "float32"),
                                       np.asarray(b, "float32"), **tol)

    def test_uneven_blocks_and_cross_attention(self):
        from paddle_tpu.ops.attention_ops import fused_attention
        rng = np.random.RandomState(11)
        q = jnp.asarray(rng.randn(1, 2, 96, 16).astype("float32") * 0.3)
        k = jnp.asarray(rng.randn(1, 2, 48, 16).astype("float32") * 0.3)
        v = jnp.asarray(rng.randn(1, 2, 48, 16).astype("float32") * 0.3)
        mask = jnp.ones((1, 48), "float32")

        def f(q_, k_, v_):
            return fused_attention(q_, k_, v_, mask, False, 0.25, True).sum()

        def r(q_, k_, v_):
            return _reference_attention(q_, k_, v_, mask, False, 0.25).sum()

        gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(r, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-4)


class TestAttentionOp:
    def test_layer_and_grad(self):
        rng = np.random.RandomState(3)
        qv = rng.randn(B, H, S, D).astype("float32") * 0.2
        q = layers.data(name="q", shape=[B, H, S, D],
                        append_batch_size=False)
        q.stop_gradient = False
        out = layers.fused_attention(q, q, q, causal=True, scale=D ** -0.5)
        loss = layers.reduce_mean(out)
        fluid.append_backward(loss)
        exe = fluid.Executor()
        ov, gv = exe.run(fluid.default_main_program(), feed={"q": qv},
                         fetch_list=[out, "q@GRAD"])
        assert ov.shape == (B, H, S, D)
        assert np.isfinite(ov).all() and np.isfinite(gv).all()
        assert np.abs(gv).sum() > 0


class TestTransformerWithFlash:
    def test_transformer_trains_with_flash(self):
        from paddle_tpu.models import transformer as T
        hp = T.ModelHyperParams()
        hp.d_model, hp.d_inner_hid, hp.n_layer = 32, 64, 1
        hp.n_head, hp.d_key, hp.d_value = 2, 16, 16
        hp.src_vocab_size = hp.trg_vocab_size = 64
        hp.max_length = 16
        hp.dropout = 0.0
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            cost, _ = T.transformer(4, 8, 8, hp)
            fluid.optimizer.Adam(learning_rate=5e-3).minimize(cost)
        exe = fluid.Executor()
        exe.run(startup)
        feed = T.fake_batch(4, 8, 8, hp)
        losses = []
        for _ in range(8):
            (lv,) = exe.run(main, feed=feed, fetch_list=[cost])
            losses.append(float(np.asarray(lv).reshape(())))
        assert losses[-1] < losses[0], losses


class TestStreamingKernelsAtS128:
    """The streaming kernels on ``[B, H, S, D]`` at one lane tile of keys
    (S % 128 == 0, S_q == S_k): held to the reference at the shapes and
    tolerances the single-pass pair removed in PR 59 was held to."""

    def _qkv128(self, seed=7):
        rng = np.random.RandomState(seed)
        shape = (2, 4, 128, 16)
        mk = lambda: jnp.asarray(rng.randn(*shape).astype("float32") * 0.3)
        mask = np.ones((2, 128), "float32")
        mask[0, -9:] = 0.0
        return mk(), mk(), mk(), jnp.asarray(mask)

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, causal):
        q, k, v, mask = self._qkv128()
        ref = _reference_attention(q, k, v, mask, causal, 0.25)
        out = fused_attention(q, k, v, mask, causal, 0.25, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_reference(self, causal):
        q, k, v, mask = self._qkv128(8)
        w = jnp.asarray(np.random.RandomState(9).randn(16).astype("f"))

        def flash_loss(q_, k_, v_):
            return jnp.sum(fused_attention(q_, k_, v_, mask, causal,
                                           0.25, True) * w)

        def ref_loss(q_, k_, v_):
            return jnp.sum(_reference_attention(q_, k_, v_, mask, causal,
                                                0.25) * w)

        gf = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-4)

    def test_fully_masked_row_grads(self):
        # regression: k_mask masking position 0 + causal makes row 0
        # fully masked; the old lse = m + log(l) residual lost log(l)
        # next to |m| ~ 1e9 in f32 and bwd probs came out n times too big
        q, k, v, mask = self._qkv128(10)
        mask = mask.at[:, 0].set(0.0)

        def flash_loss(q_, k_, v_):
            return jnp.sum(fused_attention(q_, k_, v_, mask, True,
                                           0.25, True))

        def ref_loss(q_, k_, v_):
            return jnp.sum(_reference_attention(q_, k_, v_, mask, True,
                                                0.25))

        gf = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-4)


def _unpack(x, n_head):
    b, s, hd = x.shape
    return x.reshape(b, s, n_head, hd // n_head).transpose(0, 2, 1, 3)


def _pack(x):
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def _packed_reference(q, k, v, mask, causal, scale, n_head):
    """``_reference_attention`` on the unpacked operands, packed back."""
    return _pack(_reference_attention(
        _unpack(q, n_head), _unpack(k, n_head), _unpack(v, n_head), mask,
        causal, scale))


def _packed_counter():
    from paddle_tpu.profiler import runtime_metrics
    return runtime_metrics.counter("attention.packed_kernel")


class TestPackedAttention:
    """The packed ``[B, S, H*D]`` single-pass kernels
    (``ops/attention_packed.py``), interpret mode: the path the long
    training cell's 18 attention modules take."""

    HEADS, DIM, SCALE = 2, 64, 0.125

    def _qkv(self, S, padded, seed=11, s_k=None):
        rng = np.random.RandomState(seed + S)
        mk = lambda s: jnp.asarray(
            rng.randn(1, s, self.HEADS * self.DIM).astype("float32") * 0.3)
        s_k = s_k or S
        mask = np.ones((1, s_k), "float32")
        if padded:
            mask[0, s_k - s_k // 3:] = 0.0
        return mk(S), mk(s_k), mk(s_k), mk(S), jnp.asarray(mask)

    def _both(self, q, k, v, g, mask, causal):
        """(out, dq, dk, dv) of the fused op on packed operands and of the
        reference on the unpacked ones."""
        def fused(q_, k_, v_):
            return fused_attention(q_, k_, v_, mask, causal, self.SCALE,
                                   True, self.HEADS)

        def ref(q_, k_, v_):
            return _packed_reference(q_, k_, v_, mask, causal, self.SCALE,
                                     self.HEADS)

        got, vjp = jax.vjp(fused, q, k, v)
        want, ref_vjp = jax.vjp(ref, q, k, v)
        return (got,) + vjp(g), (want,) + ref_vjp(g)

    @pytest.mark.parametrize("padded", [False, True],
                             ids=["dense", "padded-keys"])
    @pytest.mark.parametrize("S", [128, 256, 1024])
    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_and_grads_match_reference(self, causal, S, padded):
        q, k, v, g, mask = self._qkv(S, padded)
        n0 = _packed_counter()
        got, want = self._both(q, k, v, g, mask, causal)
        assert _packed_counter() == n0 + 2      # forward + backward
        for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=2e-5, err_msg=name)

    @pytest.mark.parametrize("rows", [128, 256])
    def test_fully_masked_row_grads(self, rows, monkeypatch):
        # keys 0-2 padded + causal: rows 0-2 have no live key and softmax
        # over everything that carries one mask, the keys ABOVE the
        # diagonal among them.  With 128-row blocks the programs would
        # skip those keys: such a batch row takes the whole square.
        from paddle_tpu.ops import attention_packed as P
        monkeypatch.setattr(P, "CAUSAL_ROWS", rows)
        q, k, v, g, mask = self._qkv(256, True)
        mask = mask.at[:, :3].set(0.0)
        got, want = self._both(q, k, v, g, mask, True)
        for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-4, err_msg=name)

    @pytest.mark.parametrize("rows,lanes", [(128, 128), (256, 128),
                                            (128, 256)])
    def test_every_blocking_gives_the_same_numbers(self, rows, lanes,
                                                   monkeypatch):
        from paddle_tpu.ops import attention_packed as P
        rng = np.random.RandomState(5)
        mk = lambda: jnp.asarray(rng.randn(2, 256, 256).astype("float32"))
        q, k, v, g = mk(), mk(), mk(), mk()
        mask = jnp.ones((2, 256), "float32").at[1, 200:].set(0.0)
        for causal in (False, True):
            assert P.plan(q.shape, k.shape, v.shape, 4, causal) == (256, 256)
            assert P.plan((2, 1024, 512), (2, 1024, 512), (2, 1024, 512),
                          8, causal) == ((256, 256) if causal else (512, 512))
            out, res = P.attention(q, k, v, mask, causal, 0.125, 4,
                                   (rows, lanes), interpret=True)
            grads = P.attention_bwd(q, k, v, mask, out, res, g, causal,
                                    0.125, 4, (rows, lanes), interpret=True)
            want, vjp = jax.vjp(
                lambda q_, k_, v_: _packed_reference(q_, k_, v_, mask,
                                                     causal, 0.125, 4),
                q, k, v)
            for a, b in zip((out,) + tuple(grads), (want,) + vjp(g)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-4, atol=2e-5)

    @pytest.mark.parametrize("dim", [32, 128])
    def test_other_head_widths(self, dim):
        # 4 heads a 128-lane group at D 32, one at D 128
        rng = np.random.RandomState(dim)
        heads = 256 // dim
        mk = lambda: jnp.asarray(rng.randn(1, 128, 256).astype("float32"))
        q, k, v, g = mk(), mk(), mk(), mk()
        mask = jnp.ones((1, 128), "float32").at[0, 100:].set(0.0)
        got, vjp = jax.vjp(lambda *a: fused_attention(
            *a, mask, True, dim ** -0.5, True, heads), q, k, v)
        want, ref_vjp = jax.vjp(lambda *a: _packed_reference(
            *a, mask, True, dim ** -0.5, heads), q, k, v)
        for a, b in zip((got,) + vjp(g), (want,) + ref_vjp(g)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=2e-5)

    @pytest.mark.parametrize("s_q,s_k", [(192, 192), (128, 256)],
                             ids=["S192", "Sq128-Sk256"])
    def test_refused_shapes_take_the_old_path(self, s_q, s_k):
        from paddle_tpu.ops import attention_packed as P
        q, k, v, g, mask = self._qkv(s_q, True, s_k=s_k)
        assert P.plan(q.shape, k.shape, v.shape, self.HEADS) is None
        n0 = _packed_counter()
        got, want = self._both(q, k, v, g, mask, False)
        assert _packed_counter() == n0
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=2e-5)
        # ... and the same numbers as the [B, H, S, D] op gives unpacked
        old = fused_attention(_unpack(q, self.HEADS), _unpack(k, self.HEADS),
                              _unpack(v, self.HEADS), mask, False,
                              self.SCALE, True)
        np.testing.assert_array_equal(np.asarray(got[0]),
                                      np.asarray(_pack(old)))

    @pytest.mark.parametrize("S,kernel", [(128, True), (192, False)],
                             ids=["S128-packed", "S192-unpacked"])
    def test_op_and_grad_op(self, S, kernel):
        """The IR op on packed operands: one count a lowered op (forward
        and grad), the residual in its lane-dense shape, the numbers of
        the reference."""
        rng = np.random.RandomState(S)
        hd = self.HEADS * self.DIM
        qv, kv, vv, wv = (rng.randn(2, S, hd).astype("float32") * 0.3
                          for _ in range(4))
        mv = np.ones((2, S), "float32")
        mv[1, S - 7:] = 0.0
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            q, k, v, w = (layers.data(name=n, shape=[2, S, hd],
                                      append_batch_size=False)
                          for n in "qkvw")
            m = layers.data(name="m", shape=[2, S], append_batch_size=False)
            for x in (q, k, v):
                x.stop_gradient = False
            out = layers.fused_attention(q, k, v, k_mask=m, causal=True,
                                         scale=self.SCALE,
                                         n_head=self.HEADS)
            loss = layers.reduce_sum(out * w)
            fluid.append_backward(loss)
        op = next(o for o in main.global_block().ops
                  if o.type == "scaled_dot_product_attention")
        lse = main.global_block().var(op.output("Lse")[0])
        assert tuple(out.shape) == (2, S, hd)
        assert tuple(lse.shape) == ((2, hd // 128, 8, S) if kernel
                                    else (2, self.HEADS, S, 2))
        n0 = _packed_counter()
        got = fluid.Executor().run(
            main, feed={"q": qv, "k": kv, "v": vv, "m": mv, "w": wv},
            fetch_list=[out, "q@GRAD", "k@GRAD", "v@GRAD", lse])
        assert _packed_counter() == n0 + (2 if kernel else 0)
        assert got[4].shape == tuple(lse.shape)

        want, vjp = jax.vjp(
            lambda *a: _packed_reference(*a, jnp.asarray(mv), True,
                                         self.SCALE, self.HEADS),
            jnp.asarray(qv), jnp.asarray(kv), jnp.asarray(vv))
        for a, b in zip(got[:4], (want,) + vjp(jnp.asarray(wv))):
            np.testing.assert_allclose(a, np.asarray(b), rtol=1e-4,
                                       atol=2e-5)


# id: Q [B, S_q, H*D] (rank 4: [B, H, S_q, D]), S_k, heads, causal,
# use_flash, devices of a ``data`` mesh (0: none), the lowering
_DECISION_CASES = [
    pytest.param((2, 128, 128), 128, 2, False, True, 0, "packed",
                 id="S128"),
    pytest.param((2, 256, 128), 256, 2, True, True, 0, "packed",
                 id="S256-causal"),
    pytest.param((2, 1024, 128), 1024, 2, False, True, 0, "packed",
                 id="S1024"),
    pytest.param((2, 1024, 256), 1024, 2, True, True, 0, "packed",
                 id="S1024-D128-causal"),
    pytest.param((8, 128, 128), 128, 2, True, True, 4, "packed",
                 id="S128-data4"),
    # refused and short
    pytest.param((2, 192, 128), 192, 2, False, True, 0, "streaming",
                 id="S192"),
    pytest.param((2, 128, 128), 256, 2, True, True, 0, "streaming",
                 id="Sq128-Sk256-causal"),
    # refused and long: the first took the single-pass [B, H, S, D] pair
    # until PR 59
    pytest.param((1, 1024, 384), 1024, 4, True, True, 0, "streaming",
                 id="S1024-D96-causal"),
    pytest.param((1, 512, 128), 1024, 2, False, True, 0, "streaming",
                 id="Sq512-Sk1024"),
    pytest.param((1, 2048, 128), 2048, 2, True, True, 0, "streaming",
                 id="S2048-causal"),
    pytest.param((2, 2, 128, 64), 128, None, True, True, 0, "streaming",
                 id="BHSD-S128-causal"),
    pytest.param((8, 2, 128, 64), 128, None, False, True, 4, "streaming",
                 id="BHSD-S128-data4"),
    # a kernel asked for, the reference given: counted
    pytest.param((6, 128, 128), 128, 2, False, True, 4, "reference",
                 id="S128-batch-not-divided"),
    pytest.param((2, 100, 128), 100, 2, False, True, 0, "streaming",
                 id="S100-interpreted"),
    # not asked for: not counted
    pytest.param((2, 128, 128), 128, 2, True, False, 0, "reference",
                 id="S128-use_flash-off"),
    pytest.param((2, 2, 128, 64), 128, None, False, False, 0, "reference",
                 id="BHSD-use_flash-off"),
]


class TestTheOneDecision:
    """``attention_ops.attention_lowering`` is the one place that says
    which of the three lowerings an op gets.  Held here: the shape
    inference's ``Lse``, the kernels (and their blocks) the forward
    lowering traces and the kernels the grad lowering traces are the
    decision's, over shapes on every side of every rule."""

    def _step_jaxpr(self, q_shape, s_k, heads, causal, use_flash, mesh):
        """(declared Lse shape, jaxpr) of the lowered forward + grad op."""
        k_shape = q_shape[:-2] + (s_k, q_shape[-1]) if heads is None \
            else (q_shape[0], s_k, q_shape[2])
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            q = layers.data(name="q", shape=list(q_shape),
                            append_batch_size=False)
            k, v = (layers.data(name=n, shape=list(k_shape),
                                append_batch_size=False) for n in "kv")
            m = layers.data(name="m", shape=[q_shape[0], s_k],
                            append_batch_size=False)
            for x in (q, k, v):
                x.stop_gradient = False
            out = layers.fused_attention(
                q, k, v, k_mask=m, causal=causal, scale=0.125,
                use_flash=use_flash, n_head=heads)
            loss = layers.reduce_sum(out)
            fluid.append_backward(loss)
        block = main.global_block()
        op = next(o for o in block.ops
                  if o.type == "scaled_dot_product_attention")
        lse = tuple(block.var(op.output("Lse")[0]).shape)
        if mesh is None:
            exe = fluid.Executor()
        else:
            from paddle_tpu.parallel import ParallelExecutor
            exe = ParallelExecutor(loss_name=loss.name, main_program=main,
                                   mesh=mesh)
        feeds = {"q": jnp.zeros(q_shape), "k": jnp.zeros(k_shape),
                 "v": jnp.zeros(k_shape), "m": jnp.ones((q_shape[0], s_k))}
        scope = fluid.Scope()
        parts = exe._prepare(main, block, feeds,
                             (loss.name, "q@GRAD", "k@GRAD", "v@GRAD"),
                             scope)
        assert not parts["ro_names"] and not parts["inout_names"]
        return lse, k_shape, jax.make_jaxpr(parts["step"])(
            feeds, {}, {}, jax.random.PRNGKey(0)).jaxpr

    @pytest.mark.parametrize(
        "q_shape,s_k,heads,causal,use_flash,devices,kind", _DECISION_CASES)
    def test_shape_inference_forward_and_grad_take_the_decision(
            self, q_shape, s_k, heads, causal, use_flash, devices, kind):
        from paddle_tpu.ops import attention_ops as A
        from paddle_tpu.ops import attention_packed as P
        from paddle_tpu.parallel.mesh import make_mesh
        from paddle_tpu.profiler import runtime_metrics
        from test_attention_mesh import _pallas_calls
        mesh = make_mesh((devices,), ("data",),
                         devices=jax.devices()[:devices]) if devices else None
        p0 = _packed_counter()
        f0 = runtime_metrics.counter("attention.flash_fallback")
        lse, k_shape, jaxpr = self._step_jaxpr(q_shape, s_k, heads, causal,
                                               use_flash, mesh)
        low = A.attention_lowering(q_shape, k_shape, k_shape, heads, causal,
                                   use_flash, mesh, 0, interpret=True)
        assert low.kind == kind
        B, S_q = q_shape[0], q_shape[-2]
        H = heads or q_shape[1]
        local = B // devices if devices and kind != "reference" else B

        # what the program declares for Lse is what the decision's kernel
        # saves (asked without a mesh: a program is built before its mesh)
        built = A.attention_lowering(q_shape, k_shape, k_shape, heads,
                                     causal)
        want_lse = P.res_shape(*q_shape) if built.kind == "packed" \
            else (B, H, S_q, 2)
        assert lse == want_lse

        calls = [call for _, call in _pallas_calls(jaxpr)]
        grids = [tuple(c.params["grid_mapping"].grid) for c in calls]
        counted = (_packed_counter() - p0,
                   runtime_metrics.counter("attention.flash_fallback") - f0)
        if kind == "reference":
            assert low.blocks is None and grids == []
            assert counted == (0, 1 if use_flash else 0)
            return
        assert calls[0].outvars[1].aval.shape == (local,) + want_lse[1:]
        if kind == "packed":
            rows, lanes = low.blocks
            assert low.blocks == P.plan(q_shape, k_shape, k_shape, heads,
                                        causal)
            # one forward, one backward, on the operands as they are
            assert grids == [(local, q_shape[2] // lanes, S_q // rows)] * 2
            assert counted == (2, 0)
        else:
            bq, bk = low.blocks
            assert low.blocks == A._flash_blocks(S_q, s_k, True)
            # forward, dq, dk + dv, on [B, H, S, D]
            assert grids == [(local, H, S_q // bq, s_k // bk)] * 2 + \
                [(local, H, s_k // bk, S_q // bq)]
            assert counted == (0, 0)

    def test_the_chips_tiling_rule_is_stricter_than_the_interpreters(self):
        """Off the chip any block tiles; on it a key block is a multiple
        of 128 or the whole length, so the same op is the reference's."""
        from paddle_tpu.ops import attention_ops as A
        shape = (2, 2, 520, 64)
        assert A.attention_lowering(shape, shape, shape,
                                    interpret=True).kind == "streaming"
        low = A.attention_lowering(shape, shape, shape, interpret=False)
        assert (low.kind, low.blocks, low.beats_composed) == \
            ("reference", None, True)


def _transformer_ops(seq):
    """The op list of a small Transformer + Adam built at ``seq`` (the
    attention branch depends on nothing but the key length): type,
    attributes, input and output names (``_op_rows``)."""
    from paddle_tpu.framework import unique_name_scope
    from paddle_tpu.models import transformer as T
    hp = T.ModelHyperParams()
    hp.d_model, hp.d_inner_hid, hp.n_layer = 128, 256, 1
    hp.n_head, hp.d_key, hp.d_value = 2, 64, 64
    hp.src_vocab_size = hp.trg_vocab_size = 64
    hp.max_length = seq
    hp.dropout = hp.attention_dropout = 0.0
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    with fluid.program_guard(main, startup), unique_name_scope(""):
        avg_cost, _ = T.transformer(2, seq, seq, hp)
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(avg_cost)

    return _op_rows(main.global_block())


def _op_rows(block):
    """A block's ops as comparable rows: type, attributes, input and
    output names; a long attribute (the causal constant) by its digest."""
    import hashlib

    def short(v):
        r = repr(v)
        return r if len(r) <= 200 else \
            "sha256:" + hashlib.sha256(r.encode()).hexdigest()

    return [[op.type,
             sorted([k, short(v)] for k, v in op.attrs.items()),
             sorted([k, list(v)] for k, v in op.inputs.items()),
             sorted([k, list(v)] for k, v in op.outputs.items())]
            for op in block.ops]


def _attention_module_ops(s_q, s_k, d_head, dropout, causal=False):
    """The ops ONE ``multi_head_attention`` of ``128 // d_head`` heads
    and its backward build over ``[2, s_q, 128]`` queries and
    ``[2, s_k, 128]`` keys."""
    from paddle_tpu.framework import unique_name_scope
    from paddle_tpu.models import transformer as T
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    with fluid.program_guard(main, startup), unique_name_scope(""):
        x = layers.data(name="x", shape=[2, s_q, 128],
                        append_batch_size=False)
        x.stop_gradient = False
        mem = None if s_k == s_q else layers.data(
            name="mem", shape=[2, s_k, 128], append_batch_size=False)
        mask = None if causal else layers.data(
            name="mask", shape=[2, s_k], append_batch_size=False)
        out = T.multi_head_attention(x, mem, mem, d_head, d_head, 128,
                                     n_head=128 // d_head,
                                     dropout_rate=dropout,
                                     k_mask=mask, causal=causal)
        fluid.append_backward(layers.mean(out))
    return _op_rows(main.global_block())


# S_q, S_k, head width, attention dropout, causal, the fused op?
_GATE_CASES = [
    pytest.param(256, 256, 64, 0.0, False, True, id="S256-D64"),
    pytest.param(1024, 1024, 64, 0.0, False, True, id="S1024-D64"),
    pytest.param(128, 128, 64, 0.0, True, True, id="S128-D64-causal"),
    pytest.param(256, 256, 32, 0.0, False, True, id="S256-D32"),
    # keys of 512 and more keep the older rule: the op unpacks what the
    # packed kernels refuse and takes the [B, H, S, D] ones
    pytest.param(128, 512, 64, 0.0, False, True, id="Sq128-Sk512"),
    pytest.param(64, 64, 64, 0.0, False, False, id="S64-D64"),
    pytest.param(192, 192, 64, 0.0, False, False, id="S192-D64"),
    pytest.param(256, 256, 16, 0.0, False, False, id="S256-D16"),
    pytest.param(128, 256, 64, 0.0, False, False, id="Sq128-Sk256"),
    pytest.param(256, 256, 64, 0.1, False, False, id="S256-D64-dropout"),
    pytest.param(64, 64, 16, 0.0, True, False, id="S64-D16-causal"),
]


class TestTransformerAttentionBranches:
    """Which ops ``multi_head_attention`` builds: read off the operands'
    shapes (``attention_lowering(...).beats_composed``: the packed
    kernels' shapes, or keys of 512 and more) and the attention-weight
    dropout, with no name in the environment."""

    @pytest.mark.parametrize("s_q,s_k,d_head,dropout,causal,fused",
                             _GATE_CASES)
    def test_the_gate_reads_the_shapes(self, s_q, s_k, d_head, dropout,
                                       causal, fused, request):
        n0 = _packed_counter()
        got = json.loads(json.dumps(
            _attention_module_ops(s_q, s_k, d_head, dropout, causal)))
        assert _packed_counter() == n0      # building lowers nothing
        types = [op[0] for op in got]
        if fused:
            i = types.index("scaled_dot_product_attention")
            assert types.count("scaled_dot_product_attention") == 1
            assert types.count("scaled_dot_product_attention_grad") == 1
            assert dict(map(tuple, got[i][1]))["n_head"] == \
                str(128 // d_head)
            # packed: straight from the projections to the output's
            assert types[i - 1] == "mul" and types[i + 1] == "mul"
            for t in ("transpose", "transpose_grad", "softmax", "matmul",
                      "reshape"):
                assert t not in types, t
            return
        # the composed ops, op for op what the commit before this rule
        # built (tests/golden/attention_composed_ops.json, written from
        # PR 55's tree by this very function)
        with open(os.path.join(_GOLDEN, "attention_composed_ops.json")) as f:
            want = json.load(f)[request.node.callspec.id]
        assert "scaled_dot_product_attention" not in types
        assert types.count("softmax") == 1
        assert len(got) == len(want)
        for i, (a, b) in enumerate(zip(got, want)):
            assert a == b, f"op {i}: {a} != {b}"

    @pytest.mark.parametrize("seq", [256, 1024])
    def test_the_model_hands_the_op_the_projections_layout(self, seq):
        n0 = _packed_counter()
        ops = _transformer_ops(seq)
        assert _packed_counter() == n0      # building lowers nothing
        types = [op[0] for op in ops]
        attn = [i for i, t in enumerate(types)
                if t == "scaled_dot_product_attention"]
        assert len(attn) == 3               # enc self, dec self, cross
        for i in attn:
            attrs = dict(map(tuple, ops[i][1]))
            assert attrs["n_head"] == "2"
            # straight from the three projection muls, straight to the
            # output projection's: nothing reshapes or transposes between
            before = types[:i]
            last_mul = max(j for j, t in enumerate(before) if t == "mul")
            assert "transpose" not in types[last_mul:i]
            assert "reshape" not in types[last_mul:i]
            assert types[i + 1] == "mul"
        assert "transpose" not in types and "softmax" not in types
        # ... and their mirrors are gone from the backward too
        assert "transpose_grad" not in types
        assert types.count("scaled_dot_product_attention_grad") == 3

    @pytest.mark.parametrize("amp", [False, True], ids=["f32", "bf16-amp"])
    def test_long_transformer_trains_on_the_packed_kernels(self, amp):
        from paddle_tpu.models import transformer as T
        from paddle_tpu.profiler import runtime_metrics
        hp = T.ModelHyperParams()
        hp.d_model, hp.d_inner_hid, hp.n_layer = 128, 256, 1
        hp.n_head, hp.d_key, hp.d_value = 2, 64, 64
        hp.src_vocab_size = hp.trg_vocab_size = 64
        hp.max_length = 512
        hp.dropout = hp.attention_dropout = 0.0
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 5
        with fluid.program_guard(main, startup):
            cost, _ = T.transformer(2, 512, 512, hp)
            fluid.optimizer.Adam(learning_rate=5e-3).minimize(cost)
        main.amp = amp
        exe = fluid.Executor()
        exe.run(startup)
        feed = T.fake_batch(2, 512, 512, hp)
        n0 = _packed_counter()
        f0 = runtime_metrics.counter("attention.flash_fallback")
        losses = []
        for _ in range(4):
            (lv,) = exe.run(main, feed=feed, fetch_list=[cost])
            losses.append(float(np.asarray(lv).reshape(())))
        # one compiled signature: 3 forward + 3 backward ops lowered once
        assert _packed_counter() == n0 + 6
        assert runtime_metrics.counter("attention.flash_fallback") == f0
        assert losses[-1] < losses[0], losses

    def test_every_packed_call_keeps_its_own_op_scope(self):
        """The kernels of one signature are traced once and their jaxpr
        evaluated at each call site (``attention_packed._program``): each
        site's ``pallas_call`` must still lie under ITS op's scope, which
        is how the device trace (``flash_attn_roofline``, the
        ``train_*_device_ms`` groups) tells the 18 + 18 calls apart."""
        import re
        from paddle_tpu.models import transformer as T
        hp = T.ModelHyperParams()
        hp.d_model, hp.d_inner_hid, hp.n_layer = 128, 256, 2
        hp.n_head, hp.d_key, hp.d_value = 2, 64, 64
        hp.src_vocab_size = hp.trg_vocab_size = 64
        hp.max_length = 512
        hp.dropout = hp.attention_dropout = 0.0
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            cost, _ = T.transformer(1, 512, 512, hp)
            fluid.optimizer.Adam(learning_rate=1e-3).minimize(cost)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup)
            feeds = {k: jnp.asarray(v)
                     for k, v in T.fake_batch(1, 512, 512, hp).items()}
            block = main.global_block()
            parts = exe._prepare(main, block, feeds, (cost.name,), scope)
            state = lambda names: {n: jnp.asarray(scope.find_var(n))
                                   for n in names}
            text = jax.jit(parts["step"]).lower(
                feeds, state(parts["ro_names"]), state(parts["inout_names"]),
                jax.random.PRNGKey(0)).as_text(debug_info=True)
        sites = set(re.findall(
            r'(ptop_scaled_dot_product_attention(?:_grad)?__[\w.@]+)'
            r'/pallas_call"', text))
        fwd = {s for s in sites if "_grad__" not in s}
        assert len(fwd) == 6 and len(sites - fwd) == 6, sorted(sites)


class TestComposedPathMaskWiring:
    """Regression (r5): ``layers.softmax`` was shadowed by the auto-
    generated unary wrapper in layers/ops.py, which swallowed the fused
    ``bias`` kwarg into dead attrs — padding and causal masks silently
    dropped on the composed path.  Assert the wiring AND the numerics."""

    def _tiny_hp(self):
        from paddle_tpu.models import transformer as T
        hp = T.ModelHyperParams()
        hp.d_model, hp.d_inner_hid, hp.n_layer = 16, 32, 1
        hp.n_head, hp.d_key, hp.d_value = 2, 8, 8
        hp.src_vocab_size = hp.trg_vocab_size = 40
        hp.max_length = 16
        hp.dropout = hp.attention_dropout = 0.0
        hp.use_flash = False                   # force the composed path
        return hp

    def _run(self, feed, seed=9):
        from paddle_tpu.models import transformer as T
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = seed
        with fluid.program_guard(main, startup):
            avg_cost, _ = T.transformer(2, 8, 8, self._tiny_hp())
        n_bias = sum(1 for op in main.global_block().ops
                     if op.type == "softmax" and op.input("Bias"))
        n_sm = sum(1 for op in main.global_block().ops
                   if op.type == "softmax")
        assert n_sm == 3 and n_bias == 3, (n_sm, n_bias)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup)
            (lv,) = exe.run(main, feed=feed, fetch_list=[avg_cost.name])
        return float(np.asarray(lv).reshape(()))

    def _feed(self, trg_tail=7, mask_on=True):
        rng = np.random.RandomState(3)
        f = {
            "src_word": rng.randint(1, 40, (2, 8)).astype("int32"),
            "trg_word": rng.randint(1, 40, (2, 8)).astype("int32"),
            "lbl_word": rng.randint(1, 40, (2, 8)).astype("int32"),
            "src_mask": np.ones((2, 8), "float32"),
            "lbl_weight": np.ones((2, 8), "float32"),
        }
        f["trg_word"][:, -1] = trg_tail
        if not mask_on:
            f["src_mask"][:, 4:] = 0.0
        return f

    def test_padding_mask_changes_encoder_attention(self):
        full = self._run(self._feed(mask_on=True))
        padded = self._run(self._feed(mask_on=False))
        assert abs(full - padded) > 1e-6, (full, padded)

    def test_decoder_self_attention_is_causal(self):
        # two batches differing ONLY in the final target token, with the
        # final label position weighted out: a causal decoder must
        # produce identical loss; a mask-less one leaks the future
        fa = self._feed(trg_tail=7)
        fb = self._feed(trg_tail=23)
        fa["lbl_weight"][:, -1] = 0.0
        fb["lbl_weight"][:, -1] = 0.0
        la = self._run(fa)
        lb = self._run(fb)
        np.testing.assert_allclose(la, lb, rtol=1e-6, atol=1e-7)


class TestPagedAttention:
    """Paged decode attention: the Pallas kernel (interpret mode) and
    the XLA gather fallback share one lowering contract — same inputs,
    same masked-softmax semantics over table-listed pages — so they
    must agree with each other AND with a slot-by-slot dense reference
    to float32 round-off (mirrors TestFusedSoftmaxGradPrecision's
    kernel-vs-fallback discipline)."""

    S, H, D, PL, P, NP = 4, 2, 8, 8, 3, 16

    def _case(self, seed=11):
        rng = np.random.RandomState(seed)
        S, H, D, PL, P, NP = (self.S, self.H, self.D, self.PL,
                              self.P, self.NP)
        q = jnp.asarray(rng.randn(S, H * D).astype("float32") * 0.4)
        kc = jnp.asarray(rng.randn(NP, PL, H * D).astype("float32") * 0.4)
        vc = jnp.asarray(rng.randn(NP, PL, H * D).astype("float32") * 0.4)
        pt = jnp.asarray(
            rng.permutation(NP)[:S * P].reshape(S, P).astype("int32"))
        # live prefixes spanning page boundaries, one-row, and a DEAD
        # slot (lens 0) — the kernel's zero-denominator guard
        lens = jnp.asarray(np.array([[20], [8], [1], [0]], "int32"))
        return q, kc, vc, pt, lens

    def _reference(self, q, kc, vc, pt, lens):
        S, H, D = self.S, self.H, self.D
        scale = float(D) ** -0.5
        out = np.zeros((S, H * D), "float32")
        for s in range(S):
            n = int(lens[s, 0])
            if n == 0:
                continue
            rows_k = np.asarray(kc)[np.asarray(pt)[s]].reshape(-1, H, D)
            rows_v = np.asarray(vc)[np.asarray(pt)[s]].reshape(-1, H, D)
            qs = np.asarray(q)[s].reshape(H, D)
            for h in range(H):
                sc = rows_k[:n, h] @ qs[h] * scale
                p = np.exp(sc - sc.max())
                p /= p.sum()
                out[s, h * D:(h + 1) * D] = p @ rows_v[:n, h]
        return out

    def test_fallback_matches_dense_reference(self):
        from paddle_tpu.ops import attention_ops as A
        q, kc, vc, pt, lens = self._case()
        got = np.asarray(A._xla_paged_attention(
            q, kc, vc, pt, lens, self.H, float(self.D) ** -0.5))
        want = self._reference(q, kc, vc, pt, lens)
        live = np.asarray(lens)[:, 0] > 0
        np.testing.assert_allclose(got[live], want[live],
                                   rtol=1e-5, atol=1e-5)
        # a dead slot (lens 0, fully masked) is never read back — it
        # only has to stay finite so it cannot poison the batch
        assert np.all(np.isfinite(got))

    def test_kernel_matches_fallback(self):
        from paddle_tpu.ops import attention_ops as A
        q, kc, vc, pt, lens = self._case(seed=12)
        scale = float(self.D) ** -0.5
        kernel = A._pallas_paged_attention(q, kc, vc, pt, lens, self.H,
                                           scale, interpret=True)
        assert kernel is not None, "interpret kernel unexpectedly gated"
        fallback = np.asarray(A._xla_paged_attention(
            q, kc, vc, pt, lens, self.H, scale))
        live = np.asarray(lens)[:, 0] > 0
        np.testing.assert_allclose(np.asarray(kernel)[live], fallback[live],
                                   rtol=1e-5, atol=1e-6)
        # the free slot makes no trip: nothing of it is read or summed
        assert np.all(np.isfinite(np.asarray(kernel)))


class TestPagedKernelWalksLiveRows:
    """The decode kernel's trips follow ``lens`` in blocks of several
    pages: whatever the block, it agrees with the gather lowering on
    every kind of length, and it reads no page that holds no live row."""

    S, D, PL, P, NP = 8, 8, 8, 5, 48
    HEADS = {"as_many_kv_heads": (2, 2), "grouped": (4, 2)}

    def _case(self, heads, block_pages, seed=3):
        H, Hkv = self.HEADS[heads]
        S, D, PL, P, NP = self.S, self.D, self.PL, self.P, self.NP
        B = block_pages * PL
        rng = np.random.RandomState(seed)
        q = jnp.asarray(rng.randn(S, 1, H * D).astype("float32") * 0.5)
        kc = jnp.asarray(rng.randn(NP, PL, Hkv * D).astype("float32"))
        vc = jnp.asarray(rng.randn(NP, PL, Hkv * D).astype("float32"))
        # a free slot, one row, a page's edge and one row past it, a
        # block's edge and one row past it, the whole bucket, and ragged
        lens = np.array([0, 1, PL, PL + 1, B, B + 1, P * PL, 19], "int32")
        need = -(-lens // PL)
        # pages up to a horizon past the length are the slot's own
        # (allocated, not written yet); the table's tail repeats page 0
        horizon = np.minimum(need + np.array([0, 2, 1, 0, 2, 1, 0, 2]), P)
        pages = rng.permutation(np.arange(1, NP))
        pt = np.zeros((S, P), "int32")
        at = 0
        for s in range(S):
            pt[s, :horizon[s]] = pages[at:at + horizon[s]]
            at += horizon[s]
        needed = np.concatenate([pt[s, :need[s]] for s in range(S)])
        return (q, kc, vc, jnp.asarray(pt), jnp.asarray(lens[:, None]),
                H, needed)

    @pytest.mark.parametrize("block_pages", [1, 2, 4])
    @pytest.mark.parametrize("heads", list(HEADS))
    def test_kernel_equals_the_gather_at_every_length(self, heads,
                                                      block_pages):
        from paddle_tpu.ops import attention_ops as A
        q, kc, vc, pt, lens, H, _ = self._case(heads, block_pages)
        scale = float(self.D) ** -0.5
        got = np.asarray(A._pallas_paged_attention(
            q, kc, vc, pt, lens, H, scale, interpret=True,
            block_pages=block_pages))
        want = np.asarray(A._xla_paged_attention(q, kc, vc, pt, lens, H,
                                                 scale))
        live = np.asarray(lens)[:, 0] > 0
        np.testing.assert_allclose(got[live], want[live], rtol=1e-5,
                                   atol=1e-6)
        assert np.all(np.isfinite(got))

    @pytest.mark.parametrize("block_pages", [1, 2, 4])
    @pytest.mark.parametrize("heads", list(HEADS))
    def test_dead_pages_are_never_read(self, heads, block_pages):
        """Every pool page no live row needs is NaN: the pages a slot
        holds past its length, page 0 that the table's tail and the free
        slot's row name, and the pages nobody holds.  The parent's
        kernel walked the bucket and weighed them with 0: NaN."""
        from paddle_tpu.ops import attention_ops as A
        q, kc, vc, pt, lens, H, needed = self._case(heads, block_pages)
        assert 0 not in needed
        dead = np.setdiff1d(np.arange(self.NP), needed)
        poison = lambda c: c.at[dead].set(np.nan)
        scale = float(self.D) ** -0.5
        run = lambda k, v: np.asarray(A._pallas_paged_attention(
            q, k, v, pt, lens, H, scale, interpret=True,
            block_pages=block_pages))
        got = run(poison(kc), poison(vc))
        assert np.all(np.isfinite(got))
        assert np.array_equal(got, run(kc, vc))

    @pytest.mark.parametrize("shape,want", [
        # (page bucket, page_len, Hkv*D, bytes an element, grouped)
        ((64, 16, 4096, 4, False), (4, 64)),     # genlm_opt6.7b
        ((128, 16, 256, 4, True), (64, 512)),    # nemotron3_super_ep8
        ((2, 16, 4096, 4, False), (2, 32)),      # the smallest bucket
        ((8, 16, 1024, 2, False), (8, 64)),      # chip_smoke's, bfloat16
    ])
    def test_block_and_chunk_come_from_the_shapes(self, shape, want):
        from paddle_tpu.ops import attention_ops as A
        assert A._paged_blocking(*shape) == want
