"""Continuous-batching generation runtime: prefill/decode KV-cache
equivalence, iteration-level admission into a running batch, streamed
chunked /generate over keep-alive HTTP (directly and through the
FleetRouter), warm-replica zero-compile first /generate, the
MicroBatcher-contract deadline/queue semantics at token granularity,
and the client-disconnect slot-reclamation drill."""

import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from paddle_tpu import profiler
from paddle_tpu.fault import chaos
from paddle_tpu.fleet import FleetRouter
from paddle_tpu.gen import GenPredictor, GenScheduler, is_gen_bundle
from paddle_tpu.models import gen_lm
from paddle_tpu.serving import InferenceServer, ServingClient

import gen_lookahead


@pytest.fixture(scope="module")
def bundle_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("genlm") / "bundle")
    gen_lm.export_gen_model(d, gen_lm.GenConfig(), num_slots=4)
    return d


@pytest.fixture(scope="module")
def predictor(bundle_dir):
    p = GenPredictor(bundle_dir)
    p.warmup()
    return p


@pytest.fixture()
def scheduler(predictor):
    s = GenScheduler(predictor, queue_size=8)
    yield s
    s.close()


def _server(bundle_dir, **kw):
    kw.setdefault("warmup", True)
    kw.setdefault("request_timeout", 30.0)
    server = InferenceServer(bundle_dir, port=0, **kw)
    server.start_background()
    assert server.wait_until_ready(180)
    return server


def _ref_greedy(predictor, prompt, n):
    """Reference decode: re-run the (cache-free) prefill over the
    growing sequence — what the KV-cached path must reproduce."""
    seq = list(prompt)
    out = []
    for _ in range(n):
        logits, _ = predictor.prefill(seq)
        t = int(np.argmax(logits))
        out.append(t)
        seq.append(t)
    return out


class TestBundle:
    def test_bundle_detection(self, bundle_dir, tmp_path):
        assert is_gen_bundle(bundle_dir)
        assert not is_gen_bundle(str(tmp_path))

    def test_warmup_idempotent(self, predictor):
        # module fixture already warmed: everything must be cached
        assert predictor.warmup() == 0

    def test_export_refuses_the_dense_layout(self, tmp_path):
        """``paged`` outlives the option it chose only as a keyword an
        older caller may still pass: the dense layout was removed."""
        with pytest.raises(ValueError, match="dense KV layout was removed"):
            gen_lm.export_gen_model(str(tmp_path / "b"), gen_lm.GenConfig(),
                                    num_slots=4, paged=False)
        assert not (tmp_path / "b").exists()


class TestKVCacheEquivalence:
    def test_cached_decode_matches_reference(self, predictor, scheduler):
        """Greedy decode through the slot cache must produce EXACTLY the
        tokens the cache-free reference (full re-prefill per step)
        produces — the KV cache is an optimization, not a model."""
        prompt = [5, 9, 3, 17]
        stream = scheduler.submit(prompt, max_new_tokens=7)
        got = list(stream)
        assert stream.finish_reason == "length"
        assert got == _ref_greedy(predictor, prompt, 7)

    def test_interleaved_requests_do_not_corrupt_each_other(
            self, predictor, scheduler):
        """Two concurrent generations share the decode batch but not
        state: each must still match its own isolated reference."""
        pa, pb = [2, 11, 29], [40, 7]
        sa = scheduler.submit(pa, max_new_tokens=6)
        sb = scheduler.submit(pb, max_new_tokens=6)
        got_a, got_b = list(sa), list(sb)
        assert got_a == _ref_greedy(predictor, pa, 6)
        assert got_b == _ref_greedy(predictor, pb, 6)

    def test_slot_reuse_after_eviction_is_clean(self, predictor,
                                                scheduler):
        """A slot freed by a finished request must serve the next
        request without stale-cache bleed-through."""
        want = _ref_greedy(predictor, [8, 8, 8], 5)
        for _ in range(3):   # cycles through (and re-uses) slots
            s = scheduler.submit([8, 8, 8], max_new_tokens=5)
            assert list(s) == want

    def test_eos_override_stops_early_and_frees_slot(self, predictor,
                                                     scheduler):
        ref = _ref_greedy(predictor, [5, 9, 3], 6)
        evb = profiler.runtime_metrics.counter("gen.evictions")
        s = scheduler.submit([5, 9, 3], max_new_tokens=6, eos_id=ref[1])
        assert list(s) == ref[:2]
        assert s.finish_reason == "eos"
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and scheduler.active_slots:
            time.sleep(0.02)
        assert scheduler.active_slots == 0
        assert profiler.runtime_metrics.counter("gen.evictions") > evb


class TestIterationLevelScheduling:
    def test_admission_into_running_batch(self, scheduler):
        """The headline capability: a short request submitted while a
        long generation is mid-flight gets its first token IMMEDIATELY
        (admitted between decode steps), not after the long one ends."""
        chaos.inject("gen.decode.stall", delay=0.05)
        try:
            long_s = scheduler.submit([7, 8], max_new_tokens=40)
            assert long_s.next_event(timeout=30)[0] == "token"
            short_s = scheduler.submit([2, 4], max_new_tokens=2)
            ev = short_s.next_event(timeout=30)
            assert ev is not None and ev[0] == "token"
            # the long request is still decoding — we did not queue
            # behind it
            assert long_s.finish_reason is None
            list(short_s)
            assert short_s.finish_reason is not None
            assert long_s.finish_reason is None
        finally:
            chaos.clear()
            long_s.cancel()
            list(long_s)

    def test_batch_admission_queues_behind_running_batch(self,
                                                         predictor):
        """admission='batch' is the PR 2 request-level baseline: a new
        request waits for the WHOLE running batch to finish."""
        sched = GenScheduler(predictor, queue_size=8, admission="batch")
        chaos.inject("gen.decode.stall", delay=0.03)
        try:
            first = sched.submit([3, 3], max_new_tokens=10)
            assert first.next_event(timeout=30)[0] == "token"
            # a SECOND token means decode iterations began — the batch
            # assembly window is over, so the late arrival cannot ride
            # this batch
            assert first.next_event(timeout=30)[0] == "token"
            late = sched.submit([4, 4], max_new_tokens=2)
            ev = late.next_event(timeout=30)
            # by the time the late request produced its first token the
            # batch it had to wait for has fully finished
            assert ev is not None and ev[0] == "token"
            assert first.finish_reason is not None
            list(late)
        finally:
            chaos.clear()
            sched.close()

    def test_queue_full_sheds_503_class(self, predictor):
        from paddle_tpu.serving import QueueFull
        sched = GenScheduler(predictor, queue_size=1)
        chaos.inject("gen.decode.stall", delay=0.05)
        busy = []
        try:
            # 4 slots busy + 1 queued: the next submit must shed.
            # queue_size=1 admits one request per decode iteration, so
            # wait for each admission before submitting the next
            for i in range(4):
                busy.append(sched.submit([1 + i], max_new_tokens=50))
                deadline = time.monotonic() + 10
                while time.monotonic() < deadline and \
                        sched.active_slots < i + 1:
                    time.sleep(0.02)
            assert sched.active_slots == 4
            busy.append(sched.submit([5], max_new_tokens=50))
            rej = profiler.runtime_metrics.counter(
                "gen.queue_rejections")
            with pytest.raises(QueueFull):
                sched.submit([9], max_new_tokens=2)
            assert profiler.runtime_metrics.counter(
                "gen.queue_rejections") == rej + 1
        finally:
            chaos.clear()
            for b in busy:
                b.cancel()
            sched.close()

    def test_expired_deadline_while_queued_gets_immediate_504(
            self, predictor):
        """The MicroBatcher deadline contract at admission granularity
        (mirroring Predictor.run_many's batched-dispatch timeout): a
        request whose X-Deadline-Ms budget expires while still QUEUED
        fails with DeadlineExceeded — it never takes a KV slot — and
        gen.expired counts it."""
        from paddle_tpu.serving import DeadlineExceeded
        sched = GenScheduler(predictor, queue_size=8)
        chaos.inject("gen.decode.stall", delay=0.05)
        try:
            blockers = [sched.submit([1 + i], max_new_tokens=50)
                        for i in range(4)]
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and \
                    sched.active_slots < 4:
                time.sleep(0.02)
            expired = profiler.runtime_metrics.counter("gen.expired")
            adm = profiler.runtime_metrics.counter("gen.admissions")
            q = sched.submit([9], max_new_tokens=5, deadline=0.05)
            ev = q.next_event(timeout=10)
            assert ev[0] == "error" and \
                isinstance(ev[1], DeadlineExceeded)
            assert profiler.runtime_metrics.counter(
                "gen.expired") == expired + 1
            # not admitted: no slot was ever taken for it
            assert profiler.runtime_metrics.counter(
                "gen.admissions") == adm
        finally:
            chaos.clear()
            for b in blockers:
                b.cancel()
            sched.close()


def _read_stream(host, port, payload, headers=None, timeout=60):
    """Stream /generate with http.client, returning the parsed events
    AND each event's arrival time (the incrementality evidence)."""
    import http.client
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    hdrs = {"Content-Type": "application/json"}
    hdrs.update(headers or {})
    conn.request("POST", "/generate", json.dumps(payload).encode(), hdrs)
    resp = conn.getresponse()
    if resp.status != 200:
        body = json.loads(resp.read())
        conn.close()
        return resp.status, body, []
    events, stamps = [], []
    while True:
        line = resp.readline()
        if not line:
            break
        events.append(json.loads(line))
        stamps.append(time.monotonic())
        if events[-1].get("done"):
            break
    conn.close()
    return 200, events, stamps


class TestServingGenerate:
    @pytest.fixture(scope="class")
    def server(self, bundle_dir):
        server = _server(bundle_dir)
        yield server
        server.shutdown()

    def test_warm_replica_first_generate_compiles_nothing(
            self, bundle_dir):
        """Acceptance: warmup declared BOTH signature families (every
        prefill bucket + the decode step) before /readyz — the first
        real /generate triggers no fresh lowering/compile."""
        server = _server(bundle_dir)
        try:
            host, port = server.addr
            misses = profiler.runtime_metrics.counter("jit_cache.misses")
            status, events, _ = _read_stream(
                host, port, {"prompt": [3, 5, 7], "max_new_tokens": 5})
            assert status == 200
            assert sum(1 for e in events if "token" in e) == 5
            assert profiler.runtime_metrics.counter(
                "jit_cache.misses") == misses, \
                "first /generate paid a cold compile on a warm replica"
        finally:
            server.shutdown()

    def test_stream_chunks_arrive_incrementally(self, server):
        """First chunk must land while the server is still decoding —
        chunked transfer, not a buffered body."""
        host, port = server.addr
        chaos.inject("gen.decode.stall", delay=0.06)
        try:
            t0 = time.monotonic()
            status, events, stamps = _read_stream(
                host, port, {"prompt": [2, 9], "max_new_tokens": 10})
        finally:
            chaos.clear()
        assert status == 200
        assert events[-1]["done"] and \
            events[-1]["finish_reason"] == "length"
        t_first, t_last = stamps[0] - t0, stamps[-1] - t0
        assert t_first < t_last / 2, (t_first, t_last)

    def test_generate_matches_scheduler_output(self, server, predictor):
        host, port = server.addr
        status, events, _ = _read_stream(
            host, port, {"prompt": [5, 9, 3, 17], "max_new_tokens": 6})
        assert status == 200
        toks = [e["token"] for e in events if "token" in e]
        assert toks == _ref_greedy(predictor, [5, 9, 3, 17], 6)

    def test_buffered_mode(self, server, predictor):
        host, port = server.addr
        status, events, _ = _read_stream(
            host, port, {"prompt": [5, 9, 3], "max_new_tokens": 4,
                         "stream": False})
        assert status == 200
        assert events[-1]["tokens"] == _ref_greedy(predictor,
                                                   [5, 9, 3], 4)

    def test_client_disconnect_reclaims_slot(self, server):
        """Satellite drill: a streaming client dropping mid-generation
        (gen.client.disconnect failpoint) frees its KV slot, stops its
        decode work, and must not crash the decode loop — the next
        request is served normally."""
        host, port = server.addr
        dis = profiler.runtime_metrics.counter("gen.disconnects")
        chaos.inject("gen.client.disconnect", error=True, after=1,
                     times=1)
        chaos.inject("gen.decode.stall", delay=0.02)
        try:
            status, events, _ = _read_stream(
                host, port, {"prompt": [4, 4], "max_new_tokens": 40})
        except Exception:
            pass   # a torn chunked body is a legal client-side outcome
        finally:
            chaos.clear()
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline and \
                server._gen.active_slots > 0:
            time.sleep(0.05)
        assert server._gen.active_slots == 0, "KV slot leaked"
        # paged pool: eviction must also return the slot's pages to
        # the free list, or disconnects slowly strand the pool
        gp = server.gen_predictor
        assert gp.free_pages == gp.num_pages, "KV pages leaked"
        assert profiler.runtime_metrics.counter(
            "gen.disconnects") == dis + 1
        # decode loop survived the closed socket
        status, events, _ = _read_stream(
            host, port, {"prompt": [3, 5, 7], "max_new_tokens": 3})
        assert status == 200
        assert sum(1 for e in events if "token" in e) == 3

    def test_expired_deadline_on_arrival_504(self, server):
        host, port = server.addr
        expired = profiler.runtime_metrics.counter("gen.expired")
        status, body, _ = _read_stream(
            host, port, {"prompt": [1], "max_new_tokens": 2},
            headers={"X-Deadline-Ms": "0"})
        assert status == 504
        assert body["error"]["type"] == "deadline_exceeded"
        assert body["retryable"] is True
        assert profiler.runtime_metrics.counter(
            "gen.expired") == expired + 1

    def test_deadline_expires_while_queued_504_over_http(self, server):
        """X-Deadline-Ms end to end: slots pinned by long generations,
        a tiny-budget request 504s without ever being admitted."""
        host, port = server.addr
        chaos.inject("gen.decode.stall", delay=0.05)
        # pin every slot deterministically via the scheduler itself
        holds = [server._gen.submit([1 + i], max_new_tokens=80)
                 for i in range(4)]
        try:
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and \
                    server._gen.active_slots < 4:
                time.sleep(0.02)
            assert server._gen.active_slots == 4
            expired = profiler.runtime_metrics.counter("gen.expired")
            status, body, _ = _read_stream(
                host, port, {"prompt": [9], "max_new_tokens": 5},
                headers={"X-Deadline-Ms": "60"})
            assert status == 504, body
            assert profiler.runtime_metrics.counter(
                "gen.expired") == expired + 1
        finally:
            chaos.clear()
            for h in holds:
                h.cancel()
            for h in holds:
                list(h)

    def test_predict_on_gen_bundle_404(self, server):
        host, port = server.addr
        req = urllib.request.Request(
            f"http://{host}:{port}/predict",
            data=json.dumps({"feeds": {"x": [[1.0]]}}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(req, timeout=10)
        assert e.value.code == 404

    def test_bad_request_400(self, server):
        host, port = server.addr
        status, body, _ = _read_stream(
            host, port, {"prompt": [], "max_new_tokens": 2})
        assert status == 400
        status, body, _ = _read_stream(
            host, port, {"prompt": [10 ** 6], "max_new_tokens": 2})
        assert status == 400

    def test_stats_and_meta_report_gen_state(self, server):
        host, port = server.addr
        with urllib.request.urlopen(
                f"http://{host}:{port}/stats", timeout=10) as r:
            snap = json.loads(r.read())
        assert snap["server"]["gen"]["num_slots"] == 4
        assert snap["server"]["gen"]["admission"] == "continuous"
        with urllib.request.urlopen(
                f"http://{host}:{port}/meta", timeout=10) as r:
            meta = json.loads(r.read())
        assert meta["generate"] is True
        assert meta["max_len"] == 64


class TestFleetStreaming:
    def test_chunks_flow_incrementally_through_router(self, bundle_dir,
                                                      predictor):
        """Acceptance: the router forwards /generate chunks AS the
        replica produces them — the first chunk reaches the client
        before the generation completes, so TTFT survives the hop."""
        server = _server(bundle_dir)
        router = FleetRouter(
            replicas=[f"{server.addr[0]}:{server.addr[1]}"])
        router.start_background()
        chaos.inject("gen.decode.stall", delay=0.06)
        try:
            host, port = router.addr
            t0 = time.monotonic()
            status, events, stamps = _read_stream(
                host, port, {"prompt": [2, 9], "max_new_tokens": 10})
            assert status == 200
            toks = [e["token"] for e in events if "token" in e]
            assert toks == _ref_greedy(predictor, [2, 9], 10)
            t_first, t_last = stamps[0] - t0, stamps[-1] - t0
            assert t_first < t_last / 2, \
                f"router buffered the stream (ttft {t_first:.3f}s of " \
                f"{t_last:.3f}s total)"
        finally:
            chaos.clear()
            router.shutdown()
            server.shutdown()

    def test_serving_client_generate_through_router(self, bundle_dir,
                                                    predictor):
        server = _server(bundle_dir)
        router = FleetRouter(
            replicas=[f"{server.addr[0]}:{server.addr[1]}"])
        router.start_background()
        try:
            client = ServingClient(router.addr)
            events = list(client.generate([5, 9, 3], max_new_tokens=4))
            toks = [e["token"] for e in events if "token" in e]
            assert toks == _ref_greedy(predictor, [5, 9, 3], 4)
            assert events[-1]["done"]
        finally:
            router.shutdown()
            server.shutdown()

    def test_router_sheds_when_replica_queue_full(self, bundle_dir):
        """A replica 503 (generation queue full) surfaces through the
        router as a retryable shed, not a hang."""
        server = _server(bundle_dir, gen_queue_size=1)
        router = FleetRouter(
            replicas=[f"{server.addr[0]}:{server.addr[1]}"],
            retry=None, default_deadline=1.0)
        router.start_background()
        chaos.inject("gen.decode.stall", delay=0.08)
        holds = []
        try:
            # pin every slot AND the (size-1) admission queue; with
            # queue_size=1 each hold must be admitted before the next
            # submit fits the queue
            for i in range(4):
                holds.append(server._gen.submit([1 + i],
                                                max_new_tokens=80))
                deadline = time.monotonic() + 10
                while time.monotonic() < deadline and \
                        server._gen.active_slots < i + 1:
                    time.sleep(0.02)
            assert server._gen.active_slots == 4
            holds.append(server._gen.submit([5], max_new_tokens=80))
            assert server._gen.queue_depth == 1
            status, body, _ = _read_stream(
                router.addr[0], router.addr[1],
                {"prompt": [9], "max_new_tokens": 2})
            assert status in (503, 504), body
            assert body["retryable"] is True
        finally:
            chaos.clear()
            for h in holds:
                h.cancel()
            for h in holds:
                list(h)
            router.shutdown()
            server.shutdown()


class TestCLI:
    def test_generate_command_streams_tokens(self, bundle_dir,
                                             predictor, capsys):
        from paddle_tpu.cli import main as cli_main
        server = _server(bundle_dir)
        try:
            host, port = server.addr
            rc = cli_main(["generate", "--addr", f"{host}:{port}",
                           "--prompt", "5 9 3", "--max-new", "4"])
            assert rc == 0
            out = capsys.readouterr().out.strip().splitlines()
            want = _ref_greedy(predictor, [5, 9, 3], 4)
            assert [int(x) for x in out[:-1]] == want
            assert out[-1].startswith("# done")
        finally:
            server.shutdown()


class TestPagedKV:
    """Paged KV pool: equivalence with the re-prefill reference under
    PADDLE_TPU_OPT=1, page-allocator lifecycle, page reuse without stale
    reads, bucketed zero-recompile decode, and occupancy-proportional
    decode bytes."""

    def test_default_export_is_paged(self, predictor):
        assert predictor.meta["page_len"] == 16
        assert predictor.page_buckets[-1] == predictor.pages_per_slot

    def test_paged_equivalence_under_opt(self, bundle_dir, predictor,
                                         monkeypatch):
        """The optimization pipeline must not reorder the paged op's
        stateful cache writes: greedy tokens stay identical under
        PADDLE_TPU_OPT=1."""
        monkeypatch.setenv("PADDLE_TPU_OPT", "1")
        p = GenPredictor(bundle_dir)
        s = GenScheduler(p, queue_size=8)
        try:
            for prompt in ([5, 9, 3, 17], [6] * 21):
                got = list(s.submit(prompt, max_new_tokens=6))
                assert got == _ref_greedy(predictor, prompt, 6)
        finally:
            s.close()

    def test_paged_equivalence_through_the_interpreted_kernel(
            self, bundle_dir, predictor, monkeypatch):
        """The op's kernel lowering (interpret mode off the TPU) inside
        the decode program: its trips follow ``lens``, so streams of
        unlike lengths beside free slots decode to the reference's
        tokens, and no bucket falls back to the gather."""
        monkeypatch.setenv("PADDLE_TPU_PAGED_INTERPRET", "1")
        fell_back = profiler.runtime_metrics.counter("gen.paged.fallback")
        p = GenPredictor(bundle_dir)
        s = GenScheduler(p, queue_size=8)
        try:
            prompts = ([5, 9, 3, 17], [6] * 21, [11, 2] * 17)
            streams = [s.submit(pr, max_new_tokens=6) for pr in prompts]
            for pr, st in zip(prompts, streams):
                assert list(st) == _ref_greedy(predictor, pr, 6)
        finally:
            s.close()
        assert profiler.runtime_metrics.counter("gen.paged.fallback") \
            == fell_back

    def test_pages_in_bucket_is_what_the_table_fed_holds(self, bundle_dir):
        """``gen.paged.pages_in_bucket`` beside ``pages_touched``: slots x
        the step's page bucket against the pages that hold live rows;
        their ratio is the share of the table the kernel skips."""
        p = GenPredictor(bundle_dir)
        m = profiler.runtime_metrics
        lens = np.zeros(p.num_slots, "int32")
        lens[1] = 2 * p.page_len + 1              # 3 pages: bucket of 4
        bucket = p._page_bucket(lens)
        assert bucket == 4
        assert m.samples("gen.paged.pages_in_bucket", last=1) \
            == [float(p.num_slots * bucket)]
        assert m.samples("gen.paged.pages_touched", last=1) == [3.0]

    def test_page_allocator_lifecycle(self, bundle_dir):
        p = GenPredictor(bundle_dir)
        total = p.num_pages
        n = p.pages_needed(20, 5)          # ceil(25 / 16) = 2 pages
        assert n == 2
        p.alloc_slot_pages(0, n)
        assert p.free_pages == total - n
        with pytest.raises(ValueError):    # double-alloc is a bug
            p.alloc_slot_pages(0, 1)
        assert p.free_slot_pages(0) == n
        assert p.free_pages == total
        assert p.free_slot_pages(0) == 0   # idempotent (evict paths)

    def test_page_pool_exhaustion_raises_then_recovers(self, tmp_path):
        d = str(tmp_path / "b")
        gen_lm.export_gen_model(d, gen_lm.GenConfig(), num_slots=4,
                                num_pages=8)
        p = GenPredictor(d)
        p.alloc_slot_pages(0, 4)
        p.alloc_slot_pages(1, 4)
        with pytest.raises(RuntimeError):
            p.alloc_slot_pages(2, 1)
        p.free_slot_pages(0)
        p.alloc_slot_pages(2, 4)           # freed pages are reusable

    def test_a_failed_seed_returns_its_pages(self, predictor):
        """An admission whose ``write_slot`` raises (a device fault while
        seeding) leaves nothing behind: the pages it was given are back
        in the pool, its stream ends with the error after the prefill's
        token instead of waiting for ever, and the restarted scheduler
        seats the next request."""
        boom = RuntimeError("seed failed")
        fail_once = [boom]

        class FailingSeed:
            def __getattr__(self, name):
                return getattr(predictor, name)

            def write_slot(self, slot, kv, prompt_len):
                if fail_once:
                    raise fail_once.pop()
                return predictor.write_slot(slot, kv, prompt_len)

        prompt = [5, 9, 3, 17]
        want = _ref_greedy(predictor, prompt, 5)
        restarts = profiler.runtime_metrics.counter("gen.scheduler_restarts")
        sched = GenScheduler(FailingSeed(), queue_size=8)
        try:
            stream = sched.submit(prompt, max_new_tokens=5)
            assert stream.next_event(timeout=30) == ("token", want[0])
            assert stream.next_event(timeout=30) == ("error", boom)
            assert predictor.free_pages == predictor.num_pages
            # the fault is the scheduler thread's crash: what is queued
            # before its restart is failed retryable, so wait for it
            deadline = time.monotonic() + 30
            while profiler.runtime_metrics.counter(
                    "gen.scheduler_restarts") == restarts:
                assert time.monotonic() < deadline, "no restart"
                time.sleep(0.01)
            assert list(sched.submit(prompt, max_new_tokens=5)) == want
            gen_lookahead.settle(sched)
        finally:
            sched.close()
        assert predictor.free_pages == predictor.num_pages

    def test_evicted_pages_are_reused_clean(self, predictor, scheduler):
        """admit -> decode -> evict -> re-admit cycles the SAME pages
        through different requests; a stale read would break the
        re-prefill reference on later iterations."""
        total = predictor.num_pages
        long, short = [9] * 40, [8, 8, 8]
        want_long = _ref_greedy(predictor, long, 5)
        want_short = _ref_greedy(predictor, short, 5)
        for _ in range(3):
            assert list(scheduler.submit(long, max_new_tokens=5)) \
                == want_long
            assert list(scheduler.submit(short, max_new_tokens=5)) \
                == want_short
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and \
                predictor.free_pages < total:
            time.sleep(0.02)
        assert predictor.free_pages == total, "pages leaked"

    def test_mixed_page_buckets_no_fresh_compiles(self, predictor,
                                                  scheduler):
        """A warmed replica serving lengths that span EVERY declared
        page bucket must never compile: each live page count maps onto
        a warmed bucket signature."""
        prompts = [[7] * 5, [9] * 20, [3] * 40, [11] * 50]
        refs = [_ref_greedy(predictor, p, 4) for p in prompts]
        misses = profiler.runtime_metrics.counter("jit_cache.misses")
        for prompt, ref in zip(prompts, refs):
            assert list(scheduler.submit(prompt, max_new_tokens=4)) \
                == ref
        assert profiler.runtime_metrics.counter("jit_cache.misses") \
            == misses, "paged decode compiled outside warmup"

    def test_decode_bytes_scale_with_page_bucket(self, bundle_dir):
        """Occupancy-proportional reads, held on the page ladder itself:
        every declared bucket has a warmed decode executable, and from
        one bucket to the next its XLA cost-analysis bytes grow by the
        K/V bytes of the pages added (every slot, K and V, every layer)
        and not by a pool's worth.  The CPU's gather lowering reads those
        rows, writes the gathered copy and reads it again for the scores
        and the context: 2.25x and 2.39x the K/V bytes measured here
        (PR 29), so the factor is held between 1 and 3.  The records are
        those of a predictor of the test's own: the bytes count the whole
        pool once, so two pools' records must not be mixed."""
        import re as _re
        from paddle_tpu.obs import perf
        before = {r["key"] for r in perf.records()}
        p = GenPredictor(bundle_dir)
        p.warmup()
        by_bucket = {}
        for r in perf.records():
            m = _re.search(r"gen_page_table:4x(\d+)", r["label"])
            if m and r["bytes_accessed"] and r["key"] not in before:
                by_bucket[int(m.group(1))] = r["bytes_accessed"]
        if not by_bucket:
            pytest.skip("backend reported no cost analysis")
        assert set(p.page_buckets) == set(by_bucket)
        block = p._dec_prog.global_block()
        row_bytes = sum(4 * int(block.var(n).shape[-1])
                        for n in p.cache_vars)      # 2 * n_layer * H*D * 4
        assert len(p.page_buckets) > 1
        for lo, hi in zip(p.page_buckets, p.page_buckets[1:]):
            added = p.num_slots * (hi - lo) * p.page_len * row_bytes
            grew = by_bucket[hi] - by_bucket[lo]
            assert added <= grew <= 3 * added, (lo, hi, grew, added)


class TestLookahead:
    """Decode runs one step ahead of the host: the next step is
    dispatched from the device's own pick before the last step's tokens
    are read.  The drills (``gen_lookahead.py``; a bundle with
    ``state_vars`` runs them in ``test_hybrid_moe.py``) hold every stream
    to the cache-free reference, on the roomy default pool (16 pages:
    the free list hands out pages nobody has held) and, the drills that
    seat a request where another has just left, on a TIGHT pool: the
    same bundle exported with ``num_pages`` cut to the drill's own peak
    demand, so that the request lands on the very pages a finished,
    cancelled or killed slot returned while a step was in flight."""

    # pages a stream holds: ceil(min(max_len 64, prompt + max_new) / 16)
    TIGHT = [
        # three streams of one page and the victim's three; the late
        # request is seated on the victim's
        (gen_lookahead.cancel_then_readmit, 6),
        # three streams of four pages each, twice over; then one page of
        # a killed stream's
        (gen_lookahead.drain_and_abort_in_flight, 12),
        # one slot's worth: the long prompt waits (admission
        # backpressure) for all three short streams' pages
        (gen_lookahead.length_endings_cost_no_row, 4),
    ]

    @pytest.fixture()
    def ref(self, predictor):
        return lambda prompt, n: _ref_greedy(predictor, prompt, n)

    @pytest.mark.parametrize(
        "drill, num_pages",
        [pytest.param(d, None, id=f"roomy-{d.__name__}")
         for d in gen_lookahead.DRILLS] +
        [pytest.param(d, n, id=f"tight-{d.__name__}") for d, n in TIGHT])
    def test_drill(self, drill, num_pages, predictor, ref, tmp_path):
        if num_pages is None:
            drill(predictor, ref)
            return
        d = str(tmp_path / "bundle")
        gen_lm.export_gen_model(d, gen_lm.GenConfig(), num_slots=4,
                                num_pages=num_pages)
        tight = GenPredictor(d)
        tight.warmup()
        least_free = [tight.free_pages]

        class Watched:
            def __getattr__(self, name):
                return getattr(tight, name)

            def alloc_slot_pages(self, slot, n):
                pages = tight.alloc_slot_pages(slot, n)
                least_free.append(tight.free_pages)
                return pages

        drill(Watched(), ref)
        assert min(least_free) == 0, "the pool was never exhausted"

    def test_next_step_is_dispatched_before_the_last_is_read(
            self, predictor):
        """The order of one stream's turns, and what crosses to the host:
        step k+1 is dispatched, then step k's ``[S]`` ids are read; the
        ``[S, V]`` logits never are, and but for the first step's token
        nothing is sent."""
        import jax
        log = []

        class Recording:
            def __getattr__(self, name):
                return getattr(predictor, name)

            def dispatch_turn(self, tokens, positions, lens):
                log.append(("dispatch", int((np.asarray(tokens) >= 0).sum())))
                read = predictor.dispatch_turn(tokens, positions, lens)
                assert isinstance(read, jax.Array)
                return read

            def read_turn(self, read):
                log.append(("read ids", tuple(read.shape)))
                return predictor.read_turn(read)

        prompt = [5, 9, 3, 17]
        sched = GenScheduler(Recording(), queue_size=8)
        try:
            got = list(sched.submit(prompt, max_new_tokens=6))
            gen_lookahead.settle(sched)
        finally:
            sched.close()
        assert got == _ref_greedy(predictor, prompt, 6)
        ids = ("read ids", (predictor.num_slots,))
        # the pipeline fills (two dispatches: the first is fed the
        # prefill's token, every later one the device's own pick), then
        # every turn reads the step before the one it dispatched; the
        # last has none to dispatch
        assert log == [("dispatch", 1)] + [("dispatch", 0), ids] * 4 + [ids]

    def test_warmup_compiles_the_turn(self, bundle_dir):
        """``warmup()`` compiles the decode turn of every page bucket,
        step and pick and state advance in one executable: a warmed
        scheduler decodes with no compile event."""
        profiler.install_jax_compile_listeners()
        p = GenPredictor(bundle_dir)
        turns = [b for b in p.warmup().buckets if b["program"] == "decode"]
        assert [b["compiles"] for b in turns] == [1] * len(p.page_buckets)
        assert [b["signature"]["gen_page_table"][1] for b in turns] \
            == p.page_buckets
        events = profiler.runtime_metrics.counter("compile.events")
        sched = GenScheduler(p, queue_size=8)
        try:
            got = [list(sched.submit(prompt, max_new_tokens=5))
                   for prompt in ([3, 5, 7], [9] * 20)]
        finally:
            sched.close()
        assert [len(g) for g in got] == [5, 5]
        assert profiler.runtime_metrics.counter("compile.events") == events
