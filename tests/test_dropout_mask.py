"""How a training ``dropout`` draws its mask (``ops/nn_ops.py``): XLA's bit
generator seeded from the op's threefry key, 16 bits an element, kept where
``bits >= round(p * 65536)``.  A mask is a pure function of
``(program.random_seed, run counter, the op's rng slot)``."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.executor import _step_key
from paddle_tpu.models import transformer as T
from paddle_tpu.ops import nn_ops
from paddle_tpu.profiler import runtime_metrics

ROWS, COLS = 1024, 1024          # 1 M elements a mask


def _build(p, sites=1, seed=None, lead=0, cols=COLS, is_test=False):
    """``x -> dropout`` ``sites`` times side by side (after ``lead`` ops
    that only move the rng slots); returns the program and, per site,
    its ``(Out, Mask)`` names."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[cols], dtype="float32")
        for _ in range(lead):
            layers.scale(x, scale=2.0)
        for _ in range(sites):
            layers.dropout(x, dropout_prob=p, seed=seed, is_test=is_test)
    names = [(op.output("Out")[0], op.output("Mask")[0])
             for op in main.global_block().ops if op.type == "dropout"]
    return main, names


def _x(rows=ROWS, cols=COLS):
    return np.random.RandomState(0).randn(rows, cols).astype("float32")


def _run(main, names, x, steps=1, random_seed=11):
    """The fetched ``[Out, Mask, Out, Mask, ...]`` of each of ``steps``
    runs of one executor."""
    main.random_seed = random_seed
    exe = fluid.Executor(fluid.CPUPlace())
    flat = [n for pair in names for n in pair]
    return [[np.asarray(v) for v in
             exe.run(main, feed={"x": x}, fetch_list=flat)]
            for _ in range(steps)]


def _lowered_text(main, fetch, x):
    exe = fluid.Executor(fluid.CPUPlace())
    feeds = {"x": jnp.asarray(x)}
    parts = exe._prepare(main, main.global_block(), feeds, tuple(fetch),
                         fluid.global_scope())
    return jax.jit(parts["step"]).lower(
        feeds, {}, {}, jax.random.PRNGKey(0)).as_text()


# -- the threshold and the rate ---------------------------------------------

@pytest.mark.parametrize("p", [0.1, 0.3, 0.5])
def test_threshold_is_p_in_sixteen_bits(p):
    dtype, width, threshold = nn_ops._mask_threshold(p)
    assert (dtype, width) == (jnp.uint16, 16)
    assert threshold == int(round(p * 65536))
    assert abs(threshold / 65536 - p) <= 1e-4 * p


def test_threshold_of_the_benchmarks_dropout():
    # 0.1 -> 6554 / 65536 = 0.100006: the configured 0.1 to 6e-5
    assert nn_ops._mask_threshold(0.1)[2] == 6554
    assert abs(6554 / 65536 - 0.1) < 7e-6


@pytest.mark.parametrize("p", [0.01, 0.001, 1e-5])
def test_a_p_sixteen_bits_miss_draws_thirty_two(p):
    dtype, width, threshold = nn_ops._mask_threshold(p)
    assert (dtype, width) == (jnp.uint32, 32)
    assert abs(threshold / 2 ** 32 - p) <= 1e-4 * p


@pytest.mark.parametrize("p", [0.1, 0.3, 0.5])
def test_keep_rate_within_four_sigma(p):
    main, names = _build(p)
    (out, mask), = _run(main, names, _x())
    n = mask.size
    assert n >= 1 << 20
    drop = int(round(p * 65536)) / 65536
    sigma = (drop * (1 - drop) / n) ** 0.5
    assert abs(mask.mean() - (1 - drop)) < 4 * sigma
    assert set(np.unique(mask)) == {0.0, 1.0}


@pytest.mark.parametrize("p", [0.1, 0.3, 0.5, 0.01])
def test_mask_is_the_generators_bits_against_the_threshold(p):
    """Not only the rate: the mask IS ``bits(key) >= threshold`` for the
    key the executor folds for the op's slot."""
    main, names = _build(p, lead=2)
    (out, mask), = _run(main, names, _x(64), random_seed=5)
    dtype, width, threshold = nn_ops._mask_threshold(p)
    slot = [op.type for op in main.global_block().ops].index("dropout") + 1
    key = jax.random.fold_in(_step_key(5 * 1000003 + 1), slot)
    bits = np.asarray(nn_ops._draw_bits(key, mask.shape, dtype))
    np.testing.assert_array_equal(mask, (bits >= threshold).astype("f"))


@pytest.mark.parametrize("p,kept", [(0.0, 1.0), (1.0, 0.0)])
def test_the_ends_keep_all_and_nothing(p, kept):
    main, names = _build(p, cols=64)
    (out, mask), = _run(main, names, _x(8, 64))
    assert np.all(mask == kept)


# -- what the op computes ----------------------------------------------------

@pytest.mark.parametrize("impl", ["downgrade_in_infer", "upscale_in_train"])
def test_out_and_grad_are_x_and_grad_times_mask(impl):
    p = 0.3
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[64], dtype="float32")
        x.stop_gradient = False
        w = layers.data("w", shape=[64], dtype="float32")
        d = layers.dropout(x, dropout_prob=p)
        loss = layers.reduce_sum(layers.elementwise_mul(d, w))
        op = next(o for o in main.global_block().ops if o.type == "dropout")
        op.attrs["dropout_implementation"] = impl
        fluid.backward.append_backward(loss)
    rng = np.random.RandomState(1)
    xv, wv = rng.randn(32, 64).astype("f"), rng.randn(32, 64).astype("f")
    out, mask, gx, gout = [np.asarray(v) for v in fluid.Executor(
        fluid.CPUPlace()).run(
            main, feed={"x": xv, "w": wv},
            fetch_list=[d.name, op.output("Mask")[0], "x@GRAD",
                        d.name + "@GRAD"])]
    scale = 1.0 / (1.0 - p) if impl == "upscale_in_train" else 1.0
    kept = mask != 0
    assert 0.5 < kept.mean() < 0.9
    np.testing.assert_allclose(mask[kept], scale, rtol=1e-6)
    np.testing.assert_array_equal(out, xv * mask)
    np.testing.assert_array_equal(gout, wv)
    np.testing.assert_array_equal(gx, gout * mask)


# -- a mask is a function of (seed, step, slot) ------------------------------

@pytest.mark.parametrize("what", ["same", "next_step", "next_slot",
                                  "next_seed"])
def test_mask_is_a_function_of_seed_step_and_slot(what):
    p = 0.3
    x = _x(256)
    main, names = _build(p)
    first, second = _run(main, names, x, steps=2)
    if what == "same":       # another executor's first step: same triple
        again, = _run(main, names, x)
        np.testing.assert_array_equal(first[1], again[1])
        return
    if what == "next_step":
        other = second[1]
    elif what == "next_slot":
        shifted, shifted_names = _build(p, lead=1)
        other = _run(shifted, shifted_names, x)[0][1]
    else:
        other = _run(main, names, x, random_seed=12)[0][1]
    # two independent masks differ where exactly one of them dropped
    differ = (first[1] != other).mean()
    assert abs(differ - 2 * p * (1 - p)) < 0.01


def test_fix_seed_repeats_every_step():
    main, names = _build(0.5, seed=123)
    runs = _run(main, names, _x(64), steps=3)
    np.testing.assert_array_equal(runs[0][1], runs[1][1])
    np.testing.assert_array_equal(runs[0][1], runs[2][1])
    assert 0.45 < runs[0][1].mean() < 0.55


def test_two_dropouts_of_one_program_never_share_a_mask():
    p = 0.5
    main, names = _build(p, sites=3)
    (o1, m1, o2, m2, o3, m3), = _run(main, names, _x(256))
    for a, b in ((m1, m2), (m1, m3), (m2, m3)):
        assert abs((a != b).mean() - 2 * p * (1 - p)) < 0.01


# -- what the lowering emits -------------------------------------------------

def test_training_hlo_holds_a_sixteen_bit_generator_and_no_float_uniform():
    main, names = _build(0.1, cols=96)
    text = _lowered_text(main, names[0], _x(40, 96))
    draws = re.findall(r"stablehlo\.rng_bit_generator.*", text)
    # the generator sits in a function of its own (inlined by the
    # compiler: it is what leaves the instruction its op scope on the
    # TPU), called once for Out and, behind a barrier, again for Mask
    assert len(draws) == 1 and "optimization_barrier" in text
    assert draws[0].rstrip().endswith("tensor<40x96xui16>)")
    assert len(re.findall(r"call @_draw_bits", text)) == 2
    # threefry made the op's key (fold_in: a block of two words) and no
    # word of the mask; no uniform in [0, 1) is built from bits
    assert "40x96xui32" not in text
    assert not re.search(r"bitcast_convert.*-> tensor<40x96xf32>", text), \
        "a float made from mantissa bits: a uniform of the mask's shape"


def test_a_test_mode_program_lowers_no_generator():
    sites0 = runtime_metrics.counter("dropout.mask_sites")
    main, names = _build(0.1, cols=96, is_test=True)
    text = _lowered_text(main, names[0], _x(40, 96))
    assert "rng_bit_generator" not in text
    inference = _build(0.1, cols=96)[0].clone(for_test=True)
    fetch = [op.output("Out")[0] for op in inference.global_block().ops
             if op.type == "dropout"]
    assert "rng_bit_generator" not in _lowered_text(inference, fetch,
                                                    _x(40, 96))
    assert runtime_metrics.counter("dropout.mask_sites") == sites0


def test_counters_read_the_toy_transformers_sites_and_bits():
    hp = T.ModelHyperParams()
    for key, value in dict(d_model=32, d_inner_hid=64, n_head=2, d_key=16,
                           d_value=16, n_layer=6, src_vocab_size=64,
                           trg_vocab_size=64, max_length=16,
                           dropout=0.1).items():
        setattr(hp, key, value)
    batch, seq = 4, 16
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    with fluid.program_guard(main, startup):
        avg_cost, _ = T.transformer(batch, seq, seq, hp)
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(avg_cost)
    training = [op for op in main.global_block().ops if op.type == "dropout"]
    assert len(training) == 32      # 30 posts in `dan` order + 2 embeds
    rng = np.random.RandomState(0)
    word = lambda: rng.randint(1, 64, size=(batch, seq)).astype("int32")
    ones = np.ones((batch, seq), "float32")
    feed = {"src_word": word(), "trg_word": word(), "src_mask": ones,
            "lbl_word": word(), "lbl_weight": ones}
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    sites0 = runtime_metrics.counter("dropout.mask_sites")
    bits0 = runtime_metrics.counter("dropout.mask_bits")
    losses = [float(np.asarray(exe.run(main, feed=feed,
                                       fetch_list=[avg_cost.name])[0]))
              for _ in range(2)]
    assert np.all(np.isfinite(losses))
    # counted where the step is lowered: the second run adds nothing
    assert runtime_metrics.counter("dropout.mask_sites") - sites0 == 32
    assert runtime_metrics.counter("dropout.mask_bits") - bits0 == \
        32 * batch * seq * hp.d_model * 16
