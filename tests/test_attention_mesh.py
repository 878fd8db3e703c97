"""The fused attention op on a mesh: every Pallas call runs per shard of
the batch over the ``data`` axis (``attention_ops._per_shard``).

The partitioner has no rule for a ``tpu_custom_call`` and would hand each
chip the gathered GLOBAL batch.  What the CPU can show of "no kernel sees
the global batch" is the jaxpr of the step: interpret mode makes no custom
call, but the ``pallas_call`` equations sit where the chip's would.  The
compile for a described v5e:2x2 is ``tests/test_tpu_compile.py``'s."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.framework import unique_name_scope
from paddle_tpu.models import transformer as T
from paddle_tpu.parallel import ParallelExecutor
from paddle_tpu.parallel.mesh import make_mesh
from paddle_tpu.profiler import runtime_metrics

BATCH, SEQ = 8, 128      # the packed kernels' floor (attention_packed.MIN_S)


def _hp():
    hp = T.ModelHyperParams()
    hp.d_model, hp.d_inner_hid, hp.n_layer = 128, 256, 1
    hp.n_head, hp.d_key, hp.d_value = 2, 64, 64
    hp.src_vocab_size = hp.trg_vocab_size = 64
    hp.max_length = SEQ
    hp.dropout = hp.attention_dropout = 0.0
    return hp


def _build(batch=BATCH, amp=False, seed=11):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    # the same names in every build: parameters are compared by name
    with fluid.program_guard(main, startup), unique_name_scope(""):
        cost, _ = T.transformer(batch, SEQ, SEQ, _hp())
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(cost)
    main.amp = amp
    return main, startup, cost


def _mesh(shape=(4,), names=("data",)):
    n = int(np.prod(shape))
    return make_mesh(shape, names, devices=jax.devices()[:n])


def _feed(batch=BATCH):
    feed = T.fake_batch(batch, SEQ, SEQ, _hp(), seed=3)
    feed["src_mask"][1, SEQ - 9:] = 0.0     # a padded row on one shard
    return feed


def _train(mesh, amp, batch=BATCH, steps=2):
    """Losses of ``steps`` steps and every parameter after them."""
    main, startup, cost = _build(batch, amp)
    scope = fluid.Scope()
    fluid.Executor().run(startup, scope=scope)
    exe = fluid.Executor() if mesh is None else ParallelExecutor(
        loss_name=cost.name, main_program=main, mesh=mesh)
    losses = [float(np.asarray(exe.run(
        program=main, feed=_feed(batch), fetch_list=[cost.name],
        scope=scope)[0]).reshape(())) for _ in range(steps)]
    params = {p.name: np.asarray(scope.find_var(p.name))
              for p in main.global_block().all_parameters()}
    return losses, params


def _step_jaxpr(mesh, batch=BATCH, batch_axis=0):
    """The jaxpr of the classified training step, as the executor with
    this mesh (None: the plain one) lowers it."""
    main, startup, cost = _build(batch)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor().run(startup)
        exe = fluid.Executor() if mesh is None else ParallelExecutor(
            loss_name=cost.name, main_program=main, mesh=mesh,
            batch_axis=batch_axis)
        feeds = {k: jnp.asarray(v) for k, v in _feed(batch).items()}
        parts = exe._prepare(main, main.global_block(), feeds,
                             (cost.name,), scope)
        state = lambda names: {n: jnp.asarray(scope.find_var(n))
                               for n in names}
        return jax.make_jaxpr(parts["step"])(
            feeds, state(parts["ro_names"]), state(parts["inout_names"]),
            jax.random.PRNGKey(0)).jaxpr


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for x in (v if isinstance(v, (list, tuple)) else (v,)):
            inner = getattr(x, "jaxpr", x)      # ClosedJaxpr or Jaxpr
            if hasattr(inner, "eqns"):
                yield inner


def _pallas_calls(jaxpr, inside=None):
    """``(enclosing shard_map eqn or None, pallas_call eqn)`` pairs."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield inside, eqn
            continue
        here = eqn if eqn.primitive.name == "shard_map" else inside
        for sub in _sub_jaxprs(eqn):
            yield from _pallas_calls(sub, here)


def _counters():
    return (runtime_metrics.counter("attention.packed_kernel"),
            runtime_metrics.counter("attention.flash_fallback"))


@pytest.mark.parametrize("amp", [False, True], ids=["f32", "bf16-amp"])
def test_a_data_mesh_trains_as_one_device_does(amp):
    p0, f0 = _counters()
    want_losses, want = _train(None, amp)
    assert _counters() == (p0 + 6, f0)
    got_losses, got = _train(_mesh(), amp)
    assert _counters() == (p0 + 12, f0)     # 3 + 3 ops, the kernels again
    # the rows' arithmetic is the same; what differs is the order of the
    # gradients' sums over the batch (four partial sums, all-reduced)
    np.testing.assert_allclose(got_losses, want_losses,
                               rtol=2e-3 if amp else 1e-5)
    assert got_losses[1] < got_losses[0]
    for name in want:
        np.testing.assert_allclose(got[name], want[name],
                                   rtol=0, atol=2e-3 if amp else 2e-5,
                                   err_msg=name)


@pytest.mark.parametrize("shape,names", [
    ((4,), ("data",)), ((4, 1), ("data", "model"))],
    ids=["data4", "data4-model1"])
def test_every_kernel_sits_in_a_shard_map_over_its_shard(shape, names):
    calls = list(_pallas_calls(_step_jaxpr(_mesh(shape, names))))
    assert len(calls) == 6                  # 3 forward, 3 backward
    for shard_map, call in calls:
        assert shard_map is not None, "a kernel outside every shard_map"
        # manual over the WHOLE mesh: Mosaic refuses a kernel under an
        # axis left to the partitioner
        assert set(shard_map.params["manual_axes"]) == set(names)
        # no kernel sees the global batch: every operand and result of
        # every call leads with the shard's rows
        for v in (*call.invars, *call.outvars):
            assert v.aval.shape[0] == BATCH // 4, v.aval
        for spec in (*shard_map.params["in_specs"],
                     *shard_map.params["out_specs"]):
            assert tuple(spec) == ("data",), spec


def test_no_mesh_no_shard_map():
    calls = list(_pallas_calls(_step_jaxpr(None)))
    assert len(calls) == 6
    for shard_map, call in calls:
        assert shard_map is None
        assert call.invars[-1].aval.shape[0] == BATCH


def test_a_mesh_of_one_device_is_the_plain_call():
    calls = list(_pallas_calls(_step_jaxpr(_mesh((1,)))))
    assert len(calls) == 6 and all(sm is None for sm, _ in calls)


@pytest.mark.parametrize("batch,batch_axis,shape,names", [
    (6, 0, (4,), ("data",)),
    (8, 1, (4,), ("data",)),
    (8, 0, (2, 2), ("data", "model")),
    (8, 0, (1, 2), ("data", "model")),
], ids=["batch-not-divided", "another-batch-axis", "model-populated",
        "model-alone"])
def test_a_mesh_the_batch_does_not_fit_takes_the_reference(batch, batch_axis,
                                                           shape, names):
    """A kernel would need the global batch (or the other axis's features)
    on every chip, and Mosaic refuses one the partitioner would have to
    split: the op lowers as ``_reference_attention`` (which it can
    split) and says so in ``attention.flash_fallback``."""
    p0, f0 = _counters()
    jaxpr = _step_jaxpr(_mesh(shape, names), batch, batch_axis)
    assert not list(_pallas_calls(jaxpr))
    assert _counters() == (p0, f0 + 3)      # the three forward ops


def test_the_undivided_batch_still_trains_to_the_same_numbers():
    want_losses, want = _train(None, False, batch=6)
    got_losses, got = _train(_mesh(), False, batch=6)
    np.testing.assert_allclose(got_losses, want_losses, rtol=1e-4)
    for name in want:
        np.testing.assert_allclose(got[name], want[name], rtol=0,
                                   atol=5e-5, err_msg=name)


def test_the_function_entry_runs_per_shard_too():
    """``fused_attention(..., mesh=)``, the custom-vjp entry the grad op
    takes when the forward saved nothing: both directions per shard, the
    numbers of the plain call."""
    from paddle_tpu.ops.attention_ops import fused_attention
    rng = np.random.RandomState(0)
    q, k, v, g = (jnp.asarray(rng.randn(BATCH, SEQ, 128).astype("float32"))
                  for _ in range(4))
    mask = jnp.ones((BATCH, SEQ), "float32").at[5, 100:].set(0.0)

    def run(mesh):
        f = lambda *a: fused_attention(*a, mask, True, 0.125, True, 2,
                                       mesh=mesh)
        out, vjp = jax.vjp(f, q, k, v)
        return (out, *vjp(g)), jax.make_jaxpr(
            lambda *a: jax.vjp(f, *a)[1](g))(q, k, v).jaxpr

    want, plain = run(None)
    got, sharded = run(_mesh())
    assert all(sm is None for sm, _ in _pallas_calls(plain))
    calls = list(_pallas_calls(sharded))
    assert len(calls) == 2 and all(sm is not None for sm, _ in calls)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)
