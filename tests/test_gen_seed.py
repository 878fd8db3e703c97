"""Admission's page seed (``gen/predictor.py:_seed_pool``): ONE compiled,
pool-donating call per ``write_slot`` / ``clear_slot``.  It must write
exactly what the eager ``cache.at[idx].set(buf)`` it replaced wrote, bit
for bit; its signature must depend on the prompt bucket
alone (never on the page count); the pools must be donated; and the
prefill's K/V must stay device arrays from ``prefill`` to ``write_slot``."""

import jax
import numpy as np
import pytest

from paddle_tpu import profiler
from paddle_tpu.gen import GenPredictor, GenScheduler
from paddle_tpu.gen import predictor as predictor_mod
from paddle_tpu.models import gen_lm

SENTINEL = 7.25
BUCKETS = (8, 16, 32, 64, 128)
PAGE_LEN = 16
PAGES_PER_SLOT = 8


class _Config(gen_lm.GenConfig):
    max_len = 128


def _export(tmp_path_factory, name, **kw):
    d = str(tmp_path_factory.mktemp(name) / "bundle")
    gen_lm.export_gen_model(d, _Config(), num_slots=4, **kw)
    return GenPredictor(d)


@pytest.fixture(scope="module")
def paged(tmp_path_factory):
    p = _export(tmp_path_factory, "seed_paged")
    assert tuple(p.prompt_buckets) == BUCKETS
    assert (p.page_len, p.pages_per_slot) == (PAGE_LEN, PAGES_PER_SLOT)
    return p


def _fill_pools(p, value=SENTINEL):
    """Every cache array <- ``value`` (so stale rows and writes outside
    the slot's entries both show); returns the host copies."""
    before = []
    for name in p.cache_vars:
        shape = np.asarray(p._scope.find_var(name)).shape
        host = np.full(shape, value, np.float32)
        p._scope.set_var(name, jax.device_put(host))
        before.append(host)
    return before


def _pools(p):
    return [np.asarray(p._scope.find_var(n)) for n in p.cache_vars]


def _kv(p, bucket, prompt_len, seed):
    """K/V as a prefill of ``bucket`` hands them over: random rows up to
    the prompt's length, zeros on the pad rows."""
    rng = np.random.RandomState(seed)
    width = int(p._pre_fetch[1].shape[-1])
    out = []
    for _ in p.cache_vars:
        a = rng.standard_normal((1, bucket, width)).astype(np.float32)
        a[0, prompt_len:] = 0.0
        out.append(a)
    return out


def _eager_paged(before, kv, pages, page_len, max_len):
    """What ``write_slot`` did before it was compiled (PR 23's code, on
    the host): per array, the prompt rows laid over zeros for the rest of
    the allocated pages, set at the page indices."""
    want = []
    for pool, arr in zip(before, kv):
        rows = min(arr.shape[1], max_len, len(pages) * page_len)
        buf = np.zeros((len(pages), page_len, arr.shape[2]), arr.dtype)
        buf.reshape(-1, arr.shape[2])[:rows] = arr[0, :rows]
        pool = pool.copy()
        pool[np.asarray(pages)] = buf
        want.append(pool)
    return want


def _page_counts(bucket):
    return sorted({max(1, min(n, PAGES_PER_SLOT))
                   for n in (1, bucket // PAGE_LEN, bucket // PAGE_LEN + 3,
                             PAGES_PER_SLOT)})


def _hold_shuffled_pages(p, slot, n, seed):
    """Give ``slot`` ``n`` pages that are neither contiguous nor in
    order (the allocator hands out a prefix of its free list)."""
    p.free_all_pages()
    rng = np.random.RandomState(seed)
    with p._lock:
        p._free_list = [int(i) for i in rng.permutation(p.num_pages)]
    pages = p.alloc_slot_pages(slot, n)
    assert len(pages) == n
    if n > 2:
        assert sorted(pages) != pages
        assert np.any(np.diff(sorted(pages)) > 1)
    return pages


PAGED_CASES = [(b, n) for b in BUCKETS for n in _page_counts(b)]


class TestSeedEqualsEagerWrite:
    @pytest.mark.parametrize("bucket,n_pages", PAGED_CASES)
    def test_paged_write_slot(self, paged, bucket, n_pages):
        p = paged
        pages = _hold_shuffled_pages(p, 2, n_pages, seed=bucket + n_pages)
        prompt_len = max(1, bucket - 3)
        kv = _kv(p, bucket, prompt_len, seed=bucket * 31 + n_pages)
        before = _fill_pools(p)
        try:
            assert p.write_slot(2, [jax.device_put(a) for a in kv],
                                prompt_len) == 0
            got = _pools(p)
        finally:
            p.free_all_pages()
        want = _eager_paged(before, kv, pages, p.page_len, p.max_len)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
        # the sentinel survives everywhere but in the slot's pages
        others = np.setdiff1d(np.arange(p.num_pages), pages)
        assert all(np.all(g[others] == SENTINEL) for g in got)

    @pytest.mark.parametrize("n_pages", [1, 3, PAGES_PER_SLOT])
    def test_paged_clear_slot(self, paged, n_pages):
        p = paged
        pages = _hold_shuffled_pages(p, 1, n_pages, seed=100 + n_pages)
        before = _fill_pools(p)
        try:
            p.clear_slot(1)
            got = _pools(p)
        finally:
            p.free_all_pages()
        for g, b in zip(got, before):
            want = b.copy()
            want[np.asarray(pages)] = 0.0
            np.testing.assert_array_equal(g, want)
        p.clear_slot(3)           # holds no pages: nothing to clear

    def test_write_before_alloc_raises(self, paged):
        paged.free_all_pages()
        with pytest.raises(RuntimeError, match="before alloc_slot_pages"):
            paged.write_slot(0, _kv(paged, 8, 4, seed=0), 4)


class TestSeedSignatures:
    def test_twelve_page_counts_compile_nothing_after_warmup(
            self, tmp_path_factory):
        """The seed's signature is the prompt bucket's: admitting
        requests that hold 12 different numbers of pages through a
        warmed scheduler compiles nothing, and the seeding executables
        number at most the prompt buckets."""
        seeds0 = predictor_mod._seed_pool._cache_size()
        d = str(tmp_path_factory.mktemp("seed_sig") / "bundle")
        # pages of 4 rows: 16 pages a slot, so 12 counts fit in max_len
        gen_lm.export_gen_model(d, gen_lm.GenConfig(), num_slots=2,
                                page_len=4)
        p = GenPredictor(d)
        report = p.warmup()
        seeded = [b for b in report.buckets if b["program"] == "seed"]
        assert len(seeded) == len(p.prompt_buckets)
        assert p.warmup() == 0, "second warmup compiled something"
        m = profiler.runtime_metrics
        compiles0 = m.counter("compile.events")
        misses0 = m.counter("jit_cache.misses")
        sched = GenScheduler(p, queue_size=16)
        counts = set()
        try:
            for n_pages in range(2, 14):
                prompt_len = 4 * n_pages - 5      # spans every bucket
                counts.add(p.pages_needed(prompt_len, 3))
                got = list(sched.submit([3 + n_pages] * prompt_len,
                                        max_new_tokens=3))
                assert len(got) == 3
        finally:
            sched.close()
        assert len(counts) == 12
        assert m.counter("compile.events") == compiles0
        assert m.counter("jit_cache.misses") == misses0
        assert predictor_mod._seed_pool._cache_size() - seeds0 \
            <= len(p.prompt_buckets)

    def test_pools_are_donated(self, paged):
        """The array the scope held before ``write_slot`` is deleted by
        it (its buffer became the new pool's), and the compiled seed
        aliases every pool input to an output."""
        p = paged
        _fill_pools(p)
        p.free_all_pages()
        p.alloc_slot_pages(0, 2)
        held = [p._scope.find_var(n) for n in p.cache_vars]
        kv = [jax.device_put(a) for a in _kv(p, 16, 9, seed=5)]
        try:
            assert p.write_slot(0, kv, 9) == 0
        finally:
            p.free_all_pages()
        assert all(a.is_deleted() for a in held)
        assert not any(a.is_deleted() for a in kv)
        now = tuple(p._scope.find_var(n) for n in p.cache_vars)
        idx = np.zeros(p.pages_per_slot, np.int32)
        hlo = predictor_mod._seed_pool.lower(
            now, tuple(kv), idx, np.int32(1),
            max_rows=p.max_len).compile().as_text()
        header = hlo.split("\n", 1)[0]
        assert "input_output_alias" in header
        assert header.count("may-alias") + header.count("must-alias") \
            == len(p.cache_vars), header


class TestKVStaysOnTheDevice:
    def test_prefill_returns_device_kv_and_host_logits(self, paged):
        logits, kv = paged.prefill([5, 9, 3, 17, 2])
        assert isinstance(logits, np.ndarray)
        assert logits.shape == (paged.vocab_size,)
        assert len(kv) == len(paged.cache_vars)
        assert all(isinstance(a, jax.Array) for a in kv)
        assert all(a.shape == (1, 8, kv[0].shape[-1]) for a in kv)
        # pad rows are zero: what the seed lays over the pages
        assert all(not np.asarray(a)[0, 5:].any() for a in kv)

    def test_three_admissions_three_compiled_calls_no_copy(self, paged):
        paged.free_all_pages()
        paged.warmup()
        m = profiler.runtime_metrics
        eager0 = m.counter("gen.seed.eager_ops")
        calls0 = m.counter("gen.seed.compiled_calls")
        sched = GenScheduler(paged, queue_size=8)
        try:
            streams = [sched.submit([4 + k] * (6 + 20 * k),
                                    max_new_tokens=3) for k in range(3)]
            assert all(len(list(s)) == 3 for s in streams)
        finally:
            sched.close()
        assert m.counter("gen.seed.eager_ops") == eager0
        assert m.counter("gen.seed.compiled_calls") - calls0 == 3
