"""The dispatch record (``executor._DispatchRecord``): ``Executor.run``,
``Executor.run_steps`` and ``ParallelExecutor.run`` classify a program
once a signature, resolve its state from the scope once and adopt the
step's outputs as the next call's state, and let go of the arrays the
moment anyone else writes the scope.  Every case runs the three ways to
call (the mesh over four of the host devices ``conftest.py`` forces)."""

import gc
import threading
import weakref

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import framework, layers, profiler
from paddle_tpu.fault import NumericalFault, Sentinel, chaos
from paddle_tpu.obs import trace
from paddle_tpu.parallel import ParallelExecutor
from paddle_tpu.parallel.mesh import make_mesh

HOWS = ("run", "run_steps", "mesh")
STEPS = 2      # a run_steps call
COUNTERS = ("executor.record.hits", "executor.record.misses",
            "executor.record.forgets")


def _model(seed=5):
    """A classifier with dropout (the step key matters) under Adam
    (moments and beta powers: in-out state besides the parameters)."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    # the same names whenever it is built: two rigs are compared by name
    with fluid.program_guard(main, startup), \
            framework.unique_name_scope("dr_"):
        x = layers.data("x", shape=[16], dtype="float32")
        y = layers.data("y", shape=[1], dtype="int64")
        hidden = layers.dropout(layers.fc(x, 32, act="relu"), 0.25)
        out = layers.fc(hidden, 4, act="softmax")
        loss = layers.reduce_mean(layers.cross_entropy(out, y))
        test = main.clone(for_test=True)
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    return main, startup, test, loss


def _feed(i, rows=8):
    rng = np.random.RandomState(100 + i)
    return {"x": rng.rand(rows, 16).astype("f"),
            "y": rng.randint(0, 4, (rows, 1)).astype("int64")}


def _stacked(i, rows=8):
    feeds = [_feed(STEPS * i + s, rows) for s in range(STEPS)]
    return {k: np.stack([f[k] for f in feeds]) for k in feeds[0]}


class Rig:
    """One program, one scope, one executor, called ``how``."""

    def __init__(self, how, seed=5):
        self.how = how
        self.main, self.startup, self.test, self.loss = _model(seed)
        self.scope = fluid.Scope()
        self.exe = self._executor()
        fluid.Executor(fluid.CPUPlace()).run(self.startup, scope=self.scope)

    def _executor(self):
        if self.how != "mesh":
            return fluid.Executor(fluid.CPUPlace())
        mesh = make_mesh((4,), ("data",), devices=jax.devices()[:4])
        return ParallelExecutor(loss_name=self.loss.name,
                                main_program=self.main, mesh=mesh)

    def call(self, i, exe=None, scope=None, program=None, fetch=None,
             rows=8, **kw):
        exe = exe or self.exe
        scope = scope or self.scope
        program = program or self.main
        fetch = fetch or [self.loss.name]
        if self.how == "run_steps":
            out = exe.run_steps(program, feed=_stacked(i, rows),
                                fetch_list=fetch, steps=STEPS, scope=scope)
        elif self.how == "mesh":
            out = exe.run(program=program, feed=_feed(i, rows),
                          fetch_list=fetch, scope=scope, **kw)
        else:
            out = exe.run(program, feed=_feed(i, rows), fetch_list=fetch,
                          scope=scope, **kw)
        return np.asarray(out[0])

    def state(self, scope=None):
        """Every persistable of the training program, by name."""
        scope = scope or self.scope
        return {v.name: np.asarray(scope.find_var(v.name))
                for v in self.main.global_block().vars.values()
                if v.persistable and scope.find_var(v.name) is not None
                and hasattr(scope.find_var(v.name), "shape")}

    def records(self, exe=None):
        return list((exe or self.exe)._cache.values())

    def a_parameter(self):
        return self.main.global_block().all_parameters()[0].name


def _counts():
    return {k: profiler.runtime_metrics.counter(k) for k in COUNTERS}


def _gained(before):
    return {k.rsplit(".", 1)[1]: profiler.runtime_metrics.counter(k) - v
            for k, v in before.items()}


def _same(a, b):
    assert sorted(a) == sorted(b)
    for k in a:
        assert np.array_equal(a[k], b[k]), k


@pytest.fixture(autouse=True)
def _clean():
    chaos.clear()
    yield
    chaos.clear()
    trace.disable()
    trace.clear()


@pytest.mark.parametrize("how", HOWS)
def test_k_calls_are_the_calls_of_a_fresh_executor_each(how):
    """(a) k calls through one record give the losses and the final state,
    bit for bit, of k calls that each resolve everything from the scope
    again (a fresh executor a call, its step counter carried on: the
    parent's behaviour)."""
    kept, fresh = Rig(how), Rig(how)
    want = []
    for i in range(4):
        exe = fresh._executor()
        exe._run_counter = i
        want.append(fresh.call(i, exe=exe))
    got = [kept.call(i) for i in range(4)]
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert not np.array_equal(got[0], got[1])
    _same(kept.state(), fresh.state())


@pytest.mark.parametrize("how", HOWS)
def test_prepare_runs_once_and_the_counters_say_so(how, monkeypatch):
    """(b) the program is classified by the first of k calls alone:
    hits k-1, misses 1, forgets 0; steady state resolves nothing."""
    rig, k, prepared = Rig(how), 5, []
    orig = type(rig.exe)._prepare

    def spy(self, *a, **kw):
        prepared.append(1)
        return orig(self, *a, **kw)

    monkeypatch.setattr(type(rig.exe), "_prepare", spy)
    before = _counts()
    trace.enable(ring_size=512)
    trace.clear()
    for i in range(k):
        rig.call(i)
    spans = trace.snapshot_spans()
    assert len(prepared) == 1
    assert _gained(before) == {"hits": k - 1, "misses": 1, "forgets": 0}
    looked = [s["attrs"]["record"] for s in spans
              if s["name"] == "executor.lookup"]
    assert looked == ["miss"] + ["hit"] * (k - 1)
    state = [s["attrs"] for s in spans if s["name"] == "executor.state"]
    assert state[0]["resolved"] == state[0]["arrays"] > 0
    assert [s["resolved"] for s in state[1:]] == [0] * (k - 1)
    assert len({s["arrays"] for s in state}) == 1


def _write_a_parameter(rig):
    name = rig.a_parameter()
    rig.scope.set_var(name, np.asarray(rig.scope.find_var(name)) * 0 + 0.5)


def _erase_and_restore(rig):
    name = rig.a_parameter()
    value = np.asarray(rig.scope.find_var(name))
    rig.scope.erase([name])
    with pytest.raises(RuntimeError, match="not initialized"):
        rig.call(9)
    rig.scope.set_var(name, value * 0 + 0.5)


def _load_a_checkpoint(rig, tmp_path):
    exe = fluid.Executor(fluid.CPUPlace())
    other = Rig("run", seed=77)     # the same names, other values
    with fluid.scope_guard(other.scope):
        fluid.io.save_persistables(exe, str(tmp_path), other.main)
    with fluid.scope_guard(rig.scope):
        fluid.io.load_persistables(exe, str(tmp_path), rig.main)


@pytest.mark.parametrize("write", ["set_var", "erase", "checkpoint"])
@pytest.mark.parametrize("how", HOWS)
def test_a_write_by_someone_else_reaches_the_next_call(how, write,
                                                       tmp_path):
    """(c) the scope stays the truth: a parameter set, erased or loaded
    between two calls is what the next call computes with (as a fresh
    executor would), the record lets go of its arrays AT the write and
    counts one forget."""
    rig, twin = Rig(how), Rig(how)
    for r in (rig, twin):
        r.call(0)
        r.call(1)
    (record,) = [r for r in rig.records() if r.fn is not None]
    assert record._state is not None
    before = _counts()
    for r in (rig, twin):
        if write == "set_var":
            _write_a_parameter(r)
        elif write == "erase":
            _erase_and_restore(r)
        else:
            _load_a_checkpoint(r, tmp_path / str(r is rig))
        if r is rig:
            assert record._state is None        # at the write
            assert _gained(before)["forgets"] == 1
            # told once: a record that holds nothing costs a write nothing
            assert record not in [w().__self__ for w in rig.scope._watchers
                                  if w() is not None]
    before = _counts()
    fresh = twin._executor()
    fresh._run_counter = rig.exe._run_counter
    assert np.array_equal(rig.call(2), twin.call(2, exe=fresh))
    _same(rig.state(), twin.state())
    assert record._state is not None
    assert _gained(before)["forgets"] == 0


@pytest.mark.parametrize("change", ["program", "feed_shape", "fetch_list"])
@pytest.mark.parametrize("how", HOWS)
def test_another_signature_is_prepared_again(how, change):
    """(d) a mutated program, a new feed shape and a new fetch list are
    other signatures: each is classified (one miss) and keeps a record of
    its own."""
    rig = Rig(how)
    rig.call(0)
    rig.call(1)
    before = _counts()
    if change == "program":
        rig.main.bump_version()
        rig.call(2)
    elif change == "feed_shape":
        rig.call(2, rows=12)
    else:
        acc = rig.a_parameter()
        rig.call(2, fetch=[rig.loss.name, acc])
    # (the record of the signature before let go at the new one's
    # write-back)
    assert _gained(before) == {"hits": 0, "misses": 1, "forgets": 1}
    before = _counts()
    rig.call(3)     # the first signature again, but for a new program
    assert _gained(before) == {"hits": 1, "misses": 0,
                               "forgets": int(change != "program")}


@pytest.mark.parametrize("how", HOWS)
def test_a_second_scope_gets_a_record_of_its_own(how):
    """(e) one program and one executor over two scopes: each scope has
    its own record, arrays and (mesh) shardings, and trains as if alone."""
    rig, alone = Rig(how), Rig(how)
    second = fluid.Scope()
    fluid.Executor(fluid.CPUPlace()).run(rig.startup, scope=second)
    start = rig.state(second)
    _same(start, rig.state())
    a = [rig.call(i) for i in range(2)]
    rig.exe._run_counter = 0
    b = [rig.call(i, scope=second) for i in range(2)]
    assert np.array_equal(np.asarray(a), np.asarray(b))
    mine = [r for r in rig.records() if r.fn is not None]
    assert len(mine) == 2
    assert {r._scope() for r in mine} == {rig.scope, second}
    for r in mine:
        held = r._state[1][0]
        name = r.carry_names[0]
        assert held is r._scope().find_var(name)
        if how == "mesh":
            assert held.sharding == r.shardings[1][name]
            assert len(held.sharding.device_set) == 4
    assert [alone.call(i) for i in range(2)] is not None
    _same(rig.state(second), alone.state())
    _same(rig.state(), alone.state())


@pytest.mark.parametrize("how", ["run", "mesh"])
def test_a_tripped_sentinel_leaves_scope_and_record_on_the_pre_step_state(
        how):
    """(f) a guarded step adopts its outputs only after the sentinel let
    them through: a trip leaves the scope and the record where they were
    and the next step runs from there."""
    rig, twin = Rig(how), Rig(how)
    guard = Sentinel(cadence=1, strikes=99, spike_factor=None)
    for r in (rig, twin):
        r.call(0, sentinel=Sentinel(cadence=1, strikes=99,
                                    spike_factor=None))
    (record,) = [r for r in rig.records()
                 if r.fn is not None and not r.donated]
    held, before = record._state, rig.state()
    chaos.inject("sentinel.nan", times=1)
    with pytest.raises(NumericalFault):
        rig.call(1, sentinel=guard)
    chaos.clear("sentinel.nan")
    assert record._state is held
    assert all(a is b for a, b in zip(record._state[1], held[1]))
    _same(rig.state(), before)
    twin.exe._run_counter += 1      # the tripped step drew a key too
    got = rig.call(2, sentinel=guard)
    want = twin.call(2, sentinel=Sentinel(cadence=1, strikes=99,
                                          spike_factor=None))
    assert np.array_equal(got, want)
    _same(rig.state(), twin.state())


@pytest.mark.parametrize("how", HOWS)
def test_eviction_drops_the_record_and_its_watcher(how):
    """(g) the record lives and dies with its jit-cache entry: evicted
    from the LRU it is gone, with the arrays it held, and the scope tells
    nobody of its next write."""
    rig = Rig(how)
    rig.exe._cache_capacity = 1
    rig.call(0)
    (record,) = rig.records()
    ref = weakref.ref(record)
    del record
    assert [w() for w in rig.scope._watchers if w() is not None]
    rig.call(1, rows=12)        # another signature takes the one place
    gc.collect()
    assert ref() is None
    assert len(rig.records()) == 1
    _write_a_parameter(rig)     # tells the living record, once
    assert rig.scope._watchers == []
    rig.call(2, rows=12)
    assert [w().__self__ for w in rig.scope._watchers] == rig.records()


def test_steady_state_on_the_mesh_places_the_feeds_and_the_key():
    """(h) on the mesh the step's own outputs are where the executable's
    shardings want them: a steady step compares the feeds' shardings and
    the key's, moves them, and looks up nothing."""
    rig = Rig("mesh")
    trace.enable(ring_size=512)
    trace.clear()
    for i in range(3):
        rig.call(i)
    spans = trace.snapshot_spans()
    place = [s["attrs"] for s in spans if s["name"] == "executor.place"]
    state = [s["attrs"] for s in spans if s["name"] == "executor.state"]
    feeds = len(_feed(0))
    assert place[0]["checked"] == place[0]["arrays"] \
        == state[0]["arrays"] + feeds + 1
    for placed, gathered in zip(place[1:], state[1:]):
        assert placed["checked"] == feeds + 1
        assert placed["arrays"] == place[0]["arrays"]
        assert placed["moved"] >= 3 and placed["bytes"] > 0
        assert gathered["resolved"] == 0
        assert gathered["arrays"] == state[0]["arrays"]


def test_a_host_feed_goes_under_its_sharding_from_the_host():
    """The mesh path leaves host memory on the host, in the dtype the
    device holds, for the one ``device_put`` of ``executor.place``."""
    rig = Rig("mesh")
    fed = rig.exe._feed_array(np.arange(6, dtype="int64").reshape(3, 2),
                              "int64")
    assert isinstance(fed, np.ndarray)
    assert fed.dtype == jax.dtypes.canonicalize_dtype(np.int64)
    assert rig.exe._feed_array(1.5, "float32").dtype == np.float32
    on_device = jax.numpy.ones((2, 2))
    assert rig.exe._feed_array(on_device, "float32") is on_device


@pytest.mark.parametrize("how", ["run", "mesh"])
def test_an_inference_record_sees_a_seeded_pool(how):
    """What the gen runtime does to a prefill: a program that only READS
    a persistable keeps it resolved, and a ``set_var`` of it (a seeded
    slot, a loaded weight) is what the next call reads."""
    rig = Rig(how)
    first = rig.call(0, program=rig.test)
    assert np.array_equal(first, rig.call(0, program=rig.test))
    (record,) = [r for r in rig.records() if r.fn is not None]
    assert record._state is not None and not record.carry_names
    name = rig.a_parameter()
    old = np.array(rig.scope.find_var(name))    # a copy: holds nothing
    held = weakref.ref(record._state[0][record.ro_names.index(name)])
    rig.scope.set_var(name, old * 0)
    gc.collect()
    assert record._state is None and held() is None
    assert not np.array_equal(first, rig.call(0, program=rig.test))
    rig.scope.set_var(name, old)
    assert np.array_equal(first, rig.call(0, program=rig.test))


def test_an_interpreted_program_keeps_no_record_state():
    """A program that runs op by op (here: under op profiling) reads and
    writes the scope itself, as it did: its record holds no arrays,
    watches nothing and takes no lock."""
    rig = Rig("run")
    profiler.enable_op_profiling()
    try:
        first = rig.call(0, program=rig.test)
        (record,) = [r for r in rig.records() if r.interpret]
        assert record._state is None
        assert not isinstance(record._mutex, type(threading.RLock()))
        name = rig.a_parameter()
        rig.scope.set_var(name, np.array(rig.scope.find_var(name)) * 0)
        assert not np.array_equal(first, rig.call(0, program=rig.test))
        assert record._state is None
        watching = [w().__self__ for w in rig.scope._watchers
                    if w() is not None]
        assert record not in watching
    finally:
        profiler.disable_op_profiling()


@pytest.mark.parametrize("how", HOWS)
def test_calls_from_two_threads_are_ordered(how):
    """Two threads calling one record: the record's mutex orders them, so
    no call launches on state another has donated."""
    rig, errors = Rig(how), []
    rig.call(0)

    def work():
        try:
            for i in range(4):
                assert np.all(np.isfinite(rig.call(i)))
        except BaseException as e:     # noqa: BLE001 - reported below
            errors.append(e)

    threads = [threading.Thread(target=work) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert not errors, errors
    assert rig.exe._run_counter == 9
    assert all(np.all(np.isfinite(v)) for v in rig.state().values())


@pytest.mark.parametrize("how", HOWS)
def test_two_executors_over_one_scope_hand_the_state_to_each_other(how):
    """Another executor's write-back is a write by someone else: two
    executors alternating over one scope each compute with what the other
    left, as one executor would."""
    rig, alone = Rig(how), Rig(how)
    other = rig._executor()
    got = []
    for i in range(4):
        exe = (rig.exe, other)[i % 2]
        exe._run_counter = i
        got.append(rig.call(i, exe=exe))
    want = [alone.call(i) for i in range(4)]
    assert np.array_equal(np.asarray(got), np.asarray(want))
    _same(rig.state(), alone.state())
