"""Compilation-reuse runtime: the persistent XLA compilation cache
(PADDLE_TPU_COMPILE_CACHE) survives "restarts" (a second Executor
re-tracing an identical program loads executables instead of invoking
the backend compiler), the executor jit LRU is capacity-configurable
(PADDLE_TPU_JIT_CACHE_SIZE) with a visible eviction counter, and the
feeder raises a NAMED shape error at the boundary."""

import os

import numpy as np
import pytest

import paddle_tpu as fluid
import paddle_tpu.layers as layers
from paddle_tpu import profiler


def _fc_program():
    """A fresh (main, startup, feed name, fetch) quad — param names fixed
    and generated names started anew, as a restarted process starts them,
    so two independently-built copies lower to identical computations
    under identical op scopes (the persistent cache keys an entry with
    its ``op_name``s: ``ptop_<type>__<output>`` is part of the key)."""
    from paddle_tpu.framework import unique_name_scope
    main, startup = fluid.Program(), fluid.Program()
    with unique_name_scope("cc_"), fluid.program_guard(main, startup):
        x = layers.data(name="xcc", shape=[4])
        pred = layers.fc(input=x, size=3,
                         param_attr=fluid.ParamAttr(name="wcc"),
                         bias_attr=fluid.ParamAttr(name="bcc"))
    return main, startup, pred


class TestPersistentCompileCache:
    def test_warm_restart_reports_cache_hits_no_fresh_compiles(
            self, tmp_path, monkeypatch):
        """With PADDLE_TPU_COMPILE_CACHE set, a second Executor running
        an IDENTICAL program must hit the persistent cache for every
        lowering — zero new backend compiles."""
        import jax

        from paddle_tpu.executor import disable_compile_cache

        cache_dir = tmp_path / "xla-cache"
        monkeypatch.setenv("PADDLE_TPU_COMPILE_CACHE", str(cache_dir))
        feed = {"xcc": np.ones((8, 4), "float32")}
        try:
            exe1 = fluid.Executor()  # reads the env, enables the cache
            # drop in-memory executables EARLIER TESTS may have left for
            # identical jaxprs — the cold run below must actually compile
            # (and thus miss + populate the persistent cache)
            jax.clear_caches()
            main1, startup1, pred1 = _fc_program()
            exe1.run(startup1)
            (out1,) = exe1.run(main1, feed=feed, fetch_list=[pred1])

            misses0 = profiler.runtime_metrics.counter(
                "compile_cache.misses")
            hits0 = profiler.runtime_metrics.counter("compile_cache.hits")
            assert misses0 > 0          # the cold path populated the cache
            assert len(os.listdir(cache_dir)) > 0

            # "restart": drop every in-memory jit cache, build the same
            # program again on a fresh Executor
            jax.clear_caches()
            exe2 = fluid.Executor()
            main2, startup2, pred2 = _fc_program()
            exe2.run(startup2)
            (out2,) = exe2.run(main2, feed=feed, fetch_list=[pred2])

            assert profiler.runtime_metrics.counter(
                "compile_cache.hits") > hits0
            assert profiler.runtime_metrics.counter(
                "compile_cache.misses") == misses0
            assert out1.shape == out2.shape
        finally:
            disable_compile_cache()

    def test_enable_disable_idempotent(self, tmp_path):
        from paddle_tpu.executor import (disable_compile_cache,
                                         enable_compile_cache)
        try:
            assert enable_compile_cache(str(tmp_path / "c"))
            assert enable_compile_cache(str(tmp_path / "c"))  # no-op
        finally:
            disable_compile_cache()
            disable_compile_cache()  # double-disable is safe


class TestCompileCachePlacement:
    """ONE resolver decides where the persistent cache lives
    (executor.resolve_compile_cache_dir): JAX_COMPILATION_CACHE_DIR,
    when set, wins and the program sets no dir in code; else the
    explicit dir / PADDLE_TPU_COMPILE_CACHE; else — entry points only —
    the fixed <checkout>/.jax_cache.  Never a temp dir."""

    def test_resolver_order(self, monkeypatch, tmp_path):
        from paddle_tpu.executor import resolve_compile_cache_dir as r
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.delenv("PADDLE_TPU_COMPILE_CACHE", raising=False)
        assert r() == ""                       # bare library: no cache
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert r(entry_point=True) == os.path.join(repo, ".jax_cache")
        assert r(entry_point=True) == r(entry_point=True)   # fixed path
        monkeypatch.setenv("PADDLE_TPU_COMPILE_CACHE", "/env/dir")
        assert r(entry_point=True) == "/env/dir"
        assert r("/flag/dir", entry_point=True) == "/flag/dir"
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/outside")
        assert r("/flag/dir", entry_point=True) == "/outside"

    def test_external_dir_is_never_set_in_code(self, monkeypatch,
                                               tmp_path):
        import jax

        from paddle_tpu import executor
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / "outside"))
        monkeypatch.setattr(executor, "_compile_cache_dir", None)
        seen = []
        real = jax.config.update
        monkeypatch.setattr(
            jax.config, "update",
            lambda k, v: (seen.append(k), real(k, v))[1])
        floors = {k: getattr(jax.config, k) for k in (
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")}
        try:
            assert executor.enable_compile_cache("/flag/dir")
            assert executor.enable_compile_cache(entry_point=True)
            fluid.Executor()
            executor.disable_compile_cache()
        finally:
            for k, v in floors.items():
                real(k, v)
            executor._reset_jax_cache_memo()
        assert "jax_compilation_cache_dir" not in seen
        # it still relaxed the admission floors
        assert "jax_persistent_cache_min_compile_time_secs" in seen

    def test_only_the_resolver_names_the_jax_option(self):
        """No second place in the tree points jax at a cache dir."""
        import glob
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        files = glob.glob(os.path.join(repo, "*.py")) + glob.glob(
            os.path.join(repo, "paddle_tpu", "**", "*.py"), recursive=True)
        hits = [os.path.relpath(f, repo) for f in files
                if "jax_compilation_cache_dir" in open(f).read()]
        assert hits == [os.path.join("paddle_tpu", "executor.py")], hits


class TestJitCacheCapacity:
    def _scale_program(self):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = layers.data(name="xjc", shape=[4])
            out = layers.scale(x, scale=2.0)
        return main, out

    def test_capacity_env_and_eviction_counter(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_JIT_CACHE_SIZE", "2")
        exe = fluid.Executor()
        assert exe._cache_capacity == 2
        main, out = self._scale_program()
        ev0 = profiler.runtime_metrics.counter("jit_cache.evictions")
        for rows in (1, 2, 3, 4):  # 4 distinct signatures, capacity 2
            exe.run(main, feed={"xjc": np.ones((rows, 4), "float32")},
                    fetch_list=[out])
        assert len(exe._cache) <= 2
        assert profiler.runtime_metrics.counter(
            "jit_cache.evictions") >= ev0 + 2

    def test_default_and_bad_values(self, monkeypatch):
        from paddle_tpu.executor import jit_cache_capacity
        monkeypatch.delenv("PADDLE_TPU_JIT_CACHE_SIZE", raising=False)
        assert jit_cache_capacity() == 64
        monkeypatch.setenv("PADDLE_TPU_JIT_CACHE_SIZE", "not-a-number")
        assert jit_cache_capacity() == 64
        monkeypatch.setenv("PADDLE_TPU_JIT_CACHE_SIZE", "0")
        assert jit_cache_capacity() == 1  # clamped

    def test_hit_miss_counters_move(self, monkeypatch):
        monkeypatch.delenv("PADDLE_TPU_JIT_CACHE_SIZE", raising=False)
        exe = fluid.Executor()
        main, out = self._scale_program()
        feed = {"xjc": np.ones((2, 4), "float32")}
        m0 = profiler.runtime_metrics.counter("jit_cache.misses")
        h0 = profiler.runtime_metrics.counter("jit_cache.hits")
        exe.run(main, feed=feed, fetch_list=[out])
        assert profiler.runtime_metrics.counter(
            "jit_cache.misses") == m0 + 1
        exe.run(main, feed=feed, fetch_list=[out])
        assert profiler.runtime_metrics.counter("jit_cache.hits") == h0 + 1


class TestExecutorWarmup:
    def test_warmup_compiles_declared_shapes_once(self):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = layers.data(name="xwu", shape=[4])
            pred = layers.fc(input=x, size=2)
        exe = fluid.Executor()
        exe.run(startup)
        n = exe.warmup(main, [{"xwu": (8, 4)}, {"xwu": (16, 4)}],
                       fetch_list=[pred])
        assert n == 2
        assert exe.warmup(main, [{"xwu": (8, 4)}],
                          fetch_list=[pred]) == 0
        m0 = profiler.runtime_metrics.counter("jit_cache.misses")
        exe.run(main, feed={"xwu": np.ones((16, 4), "float32")},
                fetch_list=[pred])
        assert profiler.runtime_metrics.counter("jit_cache.misses") == m0

    def test_warmup_refuses_state_mutating_programs(self):
        """Warmup executes the program; a TRAINING step would apply a
        zero-feed optimizer update — refused unless opted into."""
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = layers.data(name="xwt", shape=[4])
            y = layers.data(name="ywt", shape=[1])
            pred = layers.fc(input=x, size=1)
            loss = layers.mean(layers.square_error_cost(input=pred,
                                                        label=y))
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        exe = fluid.Executor()
        exe.run(startup)
        with pytest.raises(ValueError, match="persistable state"):
            exe.warmup(main, [{"xwt": (8, 4), "ywt": (8, 1)}],
                       fetch_list=[loss])
        assert exe.warmup(main, [{"xwt": (8, 4), "ywt": (8, 1)}],
                          fetch_list=[loss],
                          allow_state_updates=True) == 1

    def test_warmup_count_survives_lru_eviction(self, monkeypatch):
        """A full LRU evicting during warmup must still report the true
        fresh-compile count (inserts, not cache-size delta)."""
        monkeypatch.setenv("PADDLE_TPU_JIT_CACHE_SIZE", "1")
        exe = fluid.Executor()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = layers.data(name="xwe", shape=[4])
            pred = layers.fc(input=x, size=2)
        exe.run(startup)  # fills the capacity-1 cache
        n = exe.warmup(main, [{"xwe": (8, 4)}, {"xwe": (16, 4)}],
                       fetch_list=[pred])
        assert n == 2  # size delta would have said 0

    def test_warmup_rejects_dynamic_dims(self):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = layers.data(name="xwd", shape=[4])
            pred = layers.fc(input=x, size=2)
        exe = fluid.Executor()
        exe.run(startup)
        with pytest.raises(ValueError, match="concrete"):
            exe.warmup(main, [{"xwd": (-1, 4)}], fetch_list=[pred])


class TestRowBuckets:
    def test_row_bucket_ladder_and_custom_edges(self):
        from paddle_tpu.lod import bucket_edges, row_bucket
        assert row_bucket(1) == 8
        assert row_bucket(8) == 8
        assert row_bucket(9) == 16
        assert row_bucket(5, edges=[4, 6]) == 6
        assert row_bucket(7, edges=[4, 6]) == 8    # past edges: pow-2
        assert bucket_edges(1, 20) == [8, 16, 32]


class TestFeedShapeError:
    def test_feeder_raises_named_error_instead_of_silent_pass(self):
        from paddle_tpu.data_feeder import FeedShapeError
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = layers.data(name="xfs", shape=[4])
            feeder = fluid.DataFeeder(feed_list=[x],
                                      place=fluid.CPUPlace(),
                                      program=main)
        with pytest.raises(FeedShapeError, match="xfs"):
            feeder.feed([([1.0, 2.0, 3.0],)])  # 3 values vs declared [4]
        # FeedShapeError is a ValueError: existing callers' except
        # clauses (serving's 400 mapping) keep working
        assert issubclass(FeedShapeError, ValueError)

    def test_dynamic_inner_dims_still_pass_unchecked(self):
        """Declared shapes with dynamic NON-batch dims (e.g. [-1, -1, 4])
        cannot be strictly reshaped; consistent samples must come back
        stacked, not raise."""
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = layers.data(name="xdy", shape=[-1, 4])  # -> [-1, -1, 4]
            feeder = fluid.DataFeeder(feed_list=[x],
                                      place=fluid.CPUPlace(),
                                      program=main)
        sample = np.ones((3, 4), "float32")
        out = feeder.feed([(sample,), (sample,)])
        assert out["xdy"].shape == (2, 3, 4)

    def test_well_shaped_feeds_still_pass(self):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = layers.data(name="xok", shape=[4])
            feeder = fluid.DataFeeder(feed_list=[x],
                                      place=fluid.CPUPlace(),
                                      program=main)
        out = feeder.feed([([1.0, 2.0, 3.0, 4.0],),
                           ([5.0, 6.0, 7.0, 8.0],)])
        assert out["xok"].shape == (2, 4)

    def test_float_into_int_slot_rejected_not_truncated(self):
        """Float samples fed to a declared integer slot (labels/features
        swapped) used to silently truncate through np.array(dtype=)."""
        from paddle_tpu.data_feeder import FeedShapeError
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            lb = layers.data(name="lbl", shape=[1], dtype="int64")
            feeder = fluid.DataFeeder(feed_list=[lb],
                                      place=fluid.CPUPlace(),
                                      program=main)
        with pytest.raises(FeedShapeError, match="lbl"):
            feeder.feed([(np.array([0.7], "float32"),)])
        # one float sample hidden in an otherwise-int batch is caught
        # too (the stacked batch promotes to float)
        with pytest.raises(FeedShapeError, match="lbl"):
            feeder.feed([(np.array([3], "int64"),),
                         (np.array([0.7], "float32"),)])
        # integer samples into the integer slot still pass
        out = feeder.feed([(np.array([3], "int64"),),
                           (np.array([5], "int64"),)])
        assert out["lbl"].dtype == np.int64

    def test_converters_cached_across_feed_calls(self):
        """One converter set per feeder, reset per batch — not rebuilt
        per feed() call — and batches stay independent."""
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = layers.data(name="xc", shape=[2])
            feeder = fluid.DataFeeder(feed_list=[x],
                                      place=fluid.CPUPlace(),
                                      program=main)
        out1 = feeder.feed([([1.0, 2.0],)])
        convs = feeder._converters
        out2 = feeder.feed([([3.0, 4.0],), ([5.0, 6.0],)])
        assert feeder._converters is convs          # reused, not rebuilt
        assert out1["xc"].shape == (1, 2)           # no cross-batch bleed
        assert out2["xc"].shape == (2, 2)
        np.testing.assert_allclose(out2["xc"][0], [3.0, 4.0])
