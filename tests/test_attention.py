"""Fused (Pallas) attention vs composed-op reference, forward and grads."""

import numpy as np
import pytest

import paddle_tpu as fluid
import paddle_tpu.layers as layers

from paddle_tpu.ops.attention_ops import (
    fused_attention, _reference_attention)

import jax
import jax.numpy as jnp


B, H, S, D = 2, 4, 32, 16


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


class TestSmallSSinglePass:
    """The single-pass small-S kernels (S % 128 == 0, S_q == S_k) — the
    path the transformer-base flagship shapes take."""

    def _qkv128(self, seed=7):
        rng = np.random.RandomState(seed)
        shape = (2, 4, 128, 16)
        mk = lambda: jnp.asarray(rng.randn(*shape).astype("float32") * 0.3)
        mask = np.ones((2, 128), "float32")
        mask[0, -9:] = 0.0
        return mk(), mk(), mk(), jnp.asarray(mask)

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, causal):
        from paddle_tpu.ops import attention_ops as A
        assert A._smalls_group(2 * 4, 128) is not None
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


class TestFusedSoftmaxFallbackSignal:
    """ADVICE r5 / ROADMAP item 4: the decoder's combined
    padding+causal [B,1,S,S] bias is now a PER-BATCH tri_bias the
    Pallas kernel consumes directly (no fallback), and a bias the
    kernel genuinely cannot decompose takes the XLA path with BOTH a
    debug-log signal and the scanner-registered
    ``attention.fused_softmax_fallback`` counter — partial kernel
    coverage is measurable, not just loggable."""

    def _softmax_program(self, bias_shape):
        main = fluid.Program()
        block = main.global_block()
        block.create_var(name="x", shape=(B, H, S, S), dtype="float32",
                         is_data=True)
        block.create_var(name="bias", shape=bias_shape, dtype="float32",
                         is_data=True)
        block.append_op(type="softmax",
                        inputs={"X": ["x"], "Bias": ["bias"]},
                        outputs={"Out": ["out"]})
        return main

    def _run(self, bias_shape, monkeypatch, caplog):
        import logging

        monkeypatch.setenv("PADDLE_TPU_FUSED_SOFTMAX", "1")
        rng = np.random.RandomState(0)
        feed = {"x": rng.randn(B, H, S, S).astype("float32"),
                "bias": rng.randn(*bias_shape).astype("float32")}
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(fluid.Scope()):
            with caplog.at_level(logging.DEBUG,
                                 logger="paddle_tpu.ops.nn_ops"):
                out, = exe.run(self._softmax_program(bias_shape),
                               feed=feed, fetch_list=["out"])
        want = jax.nn.softmax(feed["x"] + feed["bias"], axis=-1)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-6)
        return [r for r in caplog.records
                if "fell back" in r.getMessage()]

    @staticmethod
    def _fallback_count():
        from paddle_tpu.profiler import runtime_metrics
        return runtime_metrics.counter("attention.fused_softmax_fallback")

    def test_combined_bias_takes_kernel_path(self, monkeypatch, caplog):
        # the decoder's combined padding+causal bias [B,1,S,S] rides
        # the per-batch tri_bias form now: kernel path, no signal
        # (numerics vs the XLA reference asserted inside _run)
        before = self._fallback_count()
        records = self._run((B, 1, S, S), monkeypatch, caplog)
        assert not records, [r.getMessage() for r in records]
        assert self._fallback_count() == before

    def test_undecomposable_bias_falls_back_with_counter(
            self, monkeypatch, caplog):
        # a full per-head bias [B,H,S,S] has no row/tri decomposition:
        # XLA path + debug signal + the fallback counter moves
        before = self._fallback_count()
        records = self._run((B, H, S, S), monkeypatch, caplog)
        assert records, "fallback emitted no debug-log signal"
        msg = records[0].getMessage()
        assert "PADDLE_TPU_FUSED_SOFTMAX" in msg
        assert str((B, H, S, S)) in msg  # the reason names the shape
        assert self._fallback_count() == before + 1

    def test_untileable_shape_moves_counter_too(self, monkeypatch,
                                                caplog):
        # a decomposable bias whose SCORES fail the kernel's tiling
        # gate (Sq=30: no block size divides it) silently takes the
        # XLA path inside fused_softmax — the counter must cover that
        # fallback as well, or counter==0 lies about kernel coverage
        import logging

        monkeypatch.setenv("PADDLE_TPU_FUSED_SOFTMAX", "1")
        before = self._fallback_count()
        S_odd = 30
        rng = np.random.RandomState(1)
        main = fluid.Program()
        block = main.global_block()
        block.create_var(name="x", shape=(B, H, S_odd, S_odd),
                         dtype="float32", is_data=True)
        block.create_var(name="bias", shape=(1, 1, S_odd, S_odd),
                         dtype="float32", is_data=True)
        block.append_op(type="softmax",
                        inputs={"X": ["x"], "Bias": ["bias"]},
                        outputs={"Out": ["out"]})
        feed = {"x": rng.randn(B, H, S_odd, S_odd).astype("float32"),
                "bias": rng.randn(1, 1, S_odd, S_odd).astype("float32")}
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(fluid.Scope()):
            with caplog.at_level(logging.DEBUG,
                                 logger="paddle_tpu.ops.nn_ops"):
                out, = exe.run(main, feed=feed, fetch_list=["out"])
        want = jax.nn.softmax(feed["x"] + feed["bias"], axis=-1)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-6)
        assert self._fallback_count() == before + 1

    def test_supported_bias_does_not_log_fallback(self, monkeypatch,
                                                  caplog):
        # shared causal [1,1,S,S] IS decomposable: no fallback signal
        before = self._fallback_count()
        records = self._run((1, 1, S, S), monkeypatch, caplog)
        assert not records, [r.getMessage() for r in records]
        assert self._fallback_count() == before

    def test_per_batch_tri_bias_matches_xla(self):
        # the kernel itself (interpret mode), per-batch planes vs the
        # XLA fallback — bit-level agreement within f32 rounding
        from paddle_tpu.ops import attention_ops as A
        rng = np.random.RandomState(7)
        x = jnp.asarray(rng.randn(B, H, S, S).astype("float32"))
        tri = jnp.asarray(
            rng.randn(B, S, S).astype("float32"))  # B distinct planes
        out = A._pallas_softmax_fwd(x, None, tri, interpret=True)
        assert out is not None, "per-batch tri_bias failed the gate"
        want = A._xla_softmax(x, None, tri)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-6)
        # and the planes actually differ per batch row: swapping them
        # changes the answer (guards against a broadcast-of-plane-0 bug)
        out_swapped = A._pallas_softmax_fwd(
            x, None, tri[::-1], interpret=True)
        assert np.max(np.abs(np.asarray(out_swapped)
                             - np.asarray(out))) > 1e-3


class TestFusedSoftmaxGradPrecision:
    """ADVICE r5 regression: the Pallas fused-softmax backward must
    consume the incoming cotangent at ITS dtype (f32 under AMP), not
    pre-cast it to the bf16 activation dtype.  The constant component
    of g cancels in dx = (g - sum(g*y))*y, so dx is made of exactly the
    small per-element differences a bf16 cast of g destroys — the old
    pre-cast gave the kernel LOWER gradient precision than its own XLA
    fallback."""

    def _case(self, seed=3):
        rng = np.random.RandomState(seed)
        x = jnp.asarray(rng.randn(1, 2, 32, 128).astype("float32"))
        y = jax.nn.softmax(x, axis=-1).astype(jnp.bfloat16)
        # cotangent = O(1) constant + O(1e-3) signal: bf16 resolution
        # around 1.0 is ~8e-3, so casting g to bf16 mangles the signal
        delta = rng.randn(1, 2, 32, 128).astype("float32") * 1e-3
        g = jnp.asarray(1.0 + delta, dtype=jnp.float32)
        yf = y.astype(jnp.float32)
        dx_true = (g - jnp.sum(g * yf, axis=-1, keepdims=True)) * yf
        return y, g, np.asarray(dx_true)

    def test_bwd_kernel_consumes_f32_cotangent(self):
        from paddle_tpu.ops import attention_ops as A
        y, g, dx_true = self._case()
        dx = A._pallas_softmax_bwd(y, g, interpret=True)
        assert dx is not None, "shape unexpectedly failed the bwd gate"
        assert dx.dtype == y.dtype  # dx cast on the way OUT only
        err = np.max(np.abs(np.asarray(dx, np.float32) - dx_true))
        # the old behavior (g pre-cast to bf16) for comparison: its
        # error must dwarf the fixed path's bf16 output quantization
        dx_cast = A._pallas_softmax_bwd(y, g.astype(jnp.bfloat16),
                                        interpret=True)
        err_cast = np.max(np.abs(np.asarray(dx_cast, np.float32)
                                 - dx_true))
        assert err_cast > 10 * err, (err_cast, err)

    def test_bwd_kernel_matches_xla_fallback(self):
        """The custom-vjp entry: kernel and fallback agree to within
        bf16 output quantization on a mixed-precision cotangent."""
        from paddle_tpu.ops import attention_ops as A
        y, g, dx_true = self._case(seed=4)
        dx_kernel = np.asarray(A._fused_softmax_bwd(True, y, g)[0],
                               np.float32)
        yf = y.astype(jnp.float32)
        gf = g.astype(jnp.float32)
        dx_fallback = np.asarray(
            ((gf - jnp.sum(gf * yf, axis=-1, keepdims=True)) * yf)
            .astype(y.dtype), np.float32)
        np.testing.assert_allclose(dx_kernel, dx_fallback,
                                   rtol=1e-2, atol=2e-6)
        # and both sit at the true-f32 answer within quantization
        assert np.max(np.abs(dx_kernel - dx_true)) < 2e-5


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
