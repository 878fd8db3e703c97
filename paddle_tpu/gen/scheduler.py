"""Iteration-level (continuous-batching) generation scheduler.

The vLLM/Orca scheduling idea composed from pieces the tree already
has: between decode steps the scheduler admits queued requests into free
KV-cache slots (prefill interleaved with decode), evicts finished
sequences (EOS / length cap / client disconnect), and streams each
request's tokens out as they are produced.  The
:class:`~paddle_tpu.serving.MicroBatcher` degradation contract is
reused at token granularity: a full admission queue raises
:class:`~paddle_tpu.serving.QueueFull` (503 load shedding), a request
whose ``X-Deadline-Ms`` budget expires while still queued for admission
fails with :class:`~paddle_tpu.serving.DeadlineExceeded` (504,
``gen.expired``) WITHOUT ever taking a slot, and an unexpected
scheduler-thread crash fails every live stream fast (retryable 503) and
restarts the thread within a bounded consecutive-crash budget.

``admission="batch"`` degrades the scheduler to PR 2's request-level
semantics — new requests are admitted only when the pool is EMPTY, so a
batch runs start-to-finish as a unit while later arrivals queue behind
it.  That mode exists as the benchmark baseline (``bench_decode.py``):
the measured gap between the two admission policies IS the
continuous-batching win.

Decode runs ONE STEP AHEAD of the host.  Nothing in a step needs the host
before the next can start: decoding is greedy, positions advance by one,
a live slot's pages do not change, and an ending by length is known
before the token is.  All of that state lives on the device and the step
advances it (``GenPredictor.dispatch_turn``): a turn in which no slot was
admitted, evicted or ended is ONE compiled call that takes nothing from
the host, and ONE read.  So a turn with step *k* in flight dispatches
step *k+1* first, then reads step *k*'s ``[S]`` token ids and emits them
while the device runs *k+1*.  An ending the host cannot foresee (EOS, a
cancel) costs one computed row that is thrown away; at every whole-token
boundary (migrate, abort, close, crash) the step in flight is first
collected and emitted, or dropped.  With nothing in flight (the first
step after idle) a turn only dispatches.

An ADMISSION is a run of chunks between decode steps where the bundle's
prefill can continue a slot's rows in place (``predictor.prefill_chunks``:
``models/window_moe.py``).  A slot then has a third state beside free and
decoding: ADMITTING, with a cursor into its prompt.  ``_admit`` allocates
the request's pages (the whole horizon at once, as ever) and queues its
chunks; every turn launches the decode step for the live slots FIRST and
then at most one chunk (more only under an explicit ``prefill_budget``),
so a live stream waits through a step and one chunk between two of its
tokens, never through a whole prompt; with no stream live the chunks run
back to back, since nobody waits.  Requests are admitted first come
first served, one request's chunks before the next one's.  The admitting
slot is not in the decode turn: its row of the device's decode state is
patched once, when it is seated, after its last chunk, whose logits give
the first token.  A bundle without a chunk program is admitted by one
whole-prompt prefill on this thread (``_admit_one``), as before.

A step carries more rows than tokens where a bundle decodes BLOCKS of
``predictor.block_length`` rows (``models/block_moe.py``): a slot's turn
forwards the block it is generating and, where the token it feeds
completes that block, stores it and opens the next one in the same
forward (the predictor and the program see to it: the feeds are a
token's position and ``pos + 1`` rows, as every bundle's).  Every turn
still yields every slot it carries one token; what the scheduler knows
of blocks is where a stream ends by length (``_horizon``: the token at
``max_len - 1`` would open a block past the pool's end) and, for the
``gen.decode_step`` span, how many of a step's slots completed a block
(``fused``).

A turn yields a slot ONE OR TWO tokens where a bundle drafts
(``predictor.speculative``: ``models/window_moe.py`` with its MTP module
loaded): the program verifies its own draft and the device advances the
slot by what it yielded, so the host learns a slot's position and count
ONE TURN LATE, at the read.  A slot's ``pos`` and ``steps`` are then what
the host has READ; the step ahead is dispatched for every slot that the
step in flight cannot have ended for certain (a slot one token short of
its cap ends with it whatever it yields), and a slot that the read shows
ended is taken out then: the row computed for it meanwhile is thrown
away, as after an EOS, and what it wrote lies inside the pages the
request holds (``pages_needed`` counts that row).  A run is emitted in
order and cut at ``max_new_tokens``.
"""

from __future__ import annotations

import logging
import queue
import threading
import time

import numpy as np

from paddle_tpu.obs import trace as _trace
from paddle_tpu.obs.slo import tick as _slo_tick
from paddle_tpu.obs.trace import span as _span

logger = logging.getLogger(__name__)

__all__ = ["GenScheduler", "GenStream", "SchedulerDraining",
           "StreamMigrated"]


class SchedulerDraining(RuntimeError):
    """The scheduler stopped admitting new sessions (rolling-restart
    drain): retryable by contract — a sibling replica will take the
    request."""


class StreamMigrated(RuntimeError):
    """A locally-iterated stream was checkpoint-migrated at a token
    boundary (drain-time hand-back); ``.checkpoint`` holds everything a
    survivor needs to continue token-identically."""

    def __init__(self, checkpoint):
        super().__init__("stream checkpoint-migrated at token boundary")
        self.checkpoint = checkpoint


class GenStream:
    """One request's token stream, produced by the scheduler thread and
    consumed by an HTTP handler (or any iterator)."""

    def __init__(self, prompt, max_new_tokens, eos_id, deadline_at,
                 trace_id=None):
        self.prompt = list(prompt)
        self.max_new_tokens = int(max_new_tokens)
        self.eos_id = eos_id
        self.deadline_at = deadline_at      # monotonic, None = unbounded
        self.trace_id = trace_id or _trace.current_trace_id()
        self.created_t = time.perf_counter()
        self.cancelled = False              # set by the consumer side
        self.finish_reason = None
        self.error = None
        self.tokens = []
        self._events = queue.SimpleQueue()
        # a consumer that serves many streams from one thread (the
        # server's token relay) sets this: called with None after every
        # event queued, on the scheduler thread
        self.on_event = None

    # -- producer side (scheduler thread) ---------------------------------
    def _push(self, event):
        self._events.put(event)
        hook = self.on_event
        if hook is not None:
            hook(None)

    def emit(self, token):
        token = int(token)
        self.tokens.append(token)
        self._push(("token", token))

    def finish(self, reason):
        self.finish_reason = reason
        self._push(("done", reason))

    def fail(self, exc):
        self.error = exc
        self._push(("error", exc))

    # -- consumer side -----------------------------------------------------
    def cancel(self):
        """Mark the consumer gone (client disconnect): the scheduler
        reclaims the slot and stops decoding for this stream on its next
        iteration."""
        self.cancelled = True

    def next_event(self, timeout=None):
        """Block for the next ``("token", id)`` / ``("done", reason)`` /
        ``("error", exc)`` event; returns None on timeout."""
        try:
            if timeout is not None and timeout <= 0:
                return self._events.get_nowait()
            return self._events.get(timeout=timeout)
        except queue.Empty:
            return None

    def __iter__(self):
        """Yield token ids until the stream finishes; raises the
        stream's error if it failed."""
        while True:
            kind, value = self.next_event()
            if kind == "token":
                yield value
            elif kind == "done":
                return
            elif kind == "migrate":
                raise StreamMigrated(value)
            else:
                raise value


class _Slot:
    __slots__ = ("stream", "pos", "steps", "last_token", "last_emit_t")

    def __init__(self, stream, prompt_len, first_token):
        self.stream = stream
        # the next decode step DISPATCHED for this slot feeds the token at
        # ``pos`` and writes its K/V there; ``steps`` counts those
        # dispatched so far, a token each.  The slot's first step
        # consumes first_token; the later ones are fed the device's own
        # pick, and last_token is what the host has seen
        self.pos = prompt_len
        self.steps = 0
        self.last_token = first_token
        self.last_emit_t = time.perf_counter()


class _Admission:
    """A request whose prompt is being prefilled chunk by chunk into the
    slot it will decode in."""
    __slots__ = ("stream", "slot_idx", "spans", "cursor", "logits", "t0")

    def __init__(self, stream, slot_idx, spans):
        self.stream, self.slot_idx = stream, slot_idx
        self.spans = spans      # [(start, stop), ...] of its prompt
        self.cursor = 0         # chunks dispatched so far
        self.logits = None      # the last dispatched chunk's, unread
        self.t0 = time.perf_counter()


class _Step:
    """A dispatched decode step whose tokens nobody has read yet."""
    __slots__ = ("rows", "read", "fused")

    def __init__(self, rows, read, fused):
        # rows: (slot index, _Slot, whether the stream reaches its length
        # cap with this step) for each slot the step carries
        self.rows = rows
        self.read = read        # as dispatch_turn gave it: ids and stats
        # slots whose token completed a block: the step stored it and
        # opened the next (0 unless the bundle decodes blocks)
        self.fused = fused


class GenScheduler:
    """Continuous-batching decode loop over a :class:`GenPredictor`."""

    def __init__(self, predictor, queue_size=64, admission="continuous",
                 max_restarts=5, slo_watchdog=None,
                 prefill_budget=None):
        if admission not in ("continuous", "batch"):
            raise ValueError(
                f"admission must be 'continuous' or 'batch', "
                f"got {admission!r}")
        # admission weighting (analysis/cost): cap the static prefill
        # FLOPs admitted between two decode iterations at
        # ``prefill_budget`` (None = unbounded, the pre-ISSUE-15
        # behavior).  Prefills interleave with decode on ONE device, so
        # an unbounded admission burst stalls every live stream's next
        # token; the budget bounds that stall by compute actually
        # admitted (weighted by GenPredictor.prefill_cost — the real
        # program's cost at the prompt's padded bucket, not a guess).
        # At least one request is always admitted per pass, so the
        # queue drains even when one prefill exceeds the budget.
        # CONTINUOUS admission only: batch mode refills the pool as one
        # unit by definition (the request-level baseline) — a budget
        # cut mid-refill would strand the unfilled slots for the whole
        # batch generation, not one decode iteration.
        self.prefill_budget = None if prefill_budget is None \
            or admission != "continuous" else float(prefill_budget)
        if self.prefill_budget is not None:
            # warm the cost model's affine fit HERE (it walks the
            # prefill program twice) so no _admit pass pays it while
            # holding the scheduler lock
            predictor.prefill_cost(1)
        # SLO watchdog (obs.slo): evaluated from the scheduler loop so
        # TTFT/tokens-per-sec objectives are judged by the thread that
        # produces them.  Default arms from PADDLE_TPU_SLO; unarmed the
        # per-iteration cost is one None check (tick()).
        if slo_watchdog is None:
            from paddle_tpu.obs import slo as _slo
            slo_watchdog = _slo.watchdog_from_env()
        self.slo_watchdog = slo_watchdog
        self.predictor = predictor
        # rows a slot a decode step, and the position at which a stream
        # ends by length: ``max_len``, and one under it for a block
        # bundle (the token at ``max_len`` would open a block past the
        # pool's end)
        self._block = getattr(predictor, "block_length", 1)
        self._horizon = predictor.max_len - (self._block > 1)
        # a turn yields a slot a run of 1 .. spec_rows tokens
        self._drafts = bool(getattr(predictor, "speculative", None))
        self.queue_size = max(1, int(queue_size))
        self.admission = admission
        self.max_restarts = max(0, int(max_restarts))
        self._queue = []
        self._slots = {}          # slot index -> _Slot
        self._free = list(range(predictor.num_slots))
        self._in_flight = None    # the uncollected _Step (scheduler thread)
        self._cv = threading.Condition()
        self._closed = False
        self._restarts = 0
        self._failed = None
        # drain-time migration (rolling restarts): _draining rejects
        # new admissions; _migrate_req asks the scheduler thread to
        # checkpoint every remaining stream at the next token boundary
        # (between decode iterations — the only place a stream is
        # guaranteed whole-token); _abort_exc is the in-process
        # hard-kill analog (fail everything retryable, no checkpoint)
        self._draining = False
        self._migrate_req = False
        self._migrate_done = None
        self._abort_exc = None
        # streams popped from _queue but not yet seated in _slots
        # (prefill in flight): drain()'s all-idle check must count
        # these or it can declare the scheduler empty mid-admission
        self._admitting = 0
        # a chunk-capable bundle's admissions: the one whose chunks are
        # being dispatched, and those whose last chunk is dispatched and
        # whose first token nobody has read yet (scheduler thread)
        self._chunked = bool(getattr(predictor, "prefill_chunks", ()))
        self._admission = None
        self._awaiting = []
        self.migrated = []        # checkpoints handed back by drain()
        self._thread = self._spawn_thread()

    # -- public surface ----------------------------------------------------
    @property
    def queue_depth(self):
        with self._cv:
            return len(self._queue)

    @property
    def active_slots(self):
        with self._cv:
            return len(self._slots)

    @property
    def failed(self):
        """Terminal crash once the consecutive-restart budget is spent
        (None while alive) — the /readyz pull-the-replica signal."""
        with self._cv:
            return self._failed

    def submit(self, prompt, max_new_tokens=16, deadline=None,
               eos_id=None, timeout=None):
        """Enqueue one generation request; returns a :class:`GenStream`.

        ``deadline``: seconds of end-to-end admission budget (the
        ``X-Deadline-Ms`` contract) — expiry while queued fails the
        stream with DeadlineExceeded without taking a slot.  ``eos_id``
        overrides the bundle's EOS token for this request."""
        from paddle_tpu import profiler as _profiler
        from paddle_tpu.serving import BatcherCrashed, QueueFull

        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        if any(t < 0 or t >= self.predictor.vocab_size for t in prompt):
            raise ValueError("prompt token out of vocabulary range")
        if len(prompt) > self.predictor.max_prompt_len:
            raise ValueError(
                f"prompt of {len(prompt)} tokens exceeds the bundle's "
                f"max prompt length {self.predictor.max_prompt_len}")
        if int(max_new_tokens) < 1:
            raise ValueError("max_new_tokens must be >= 1")
        eos = self.predictor.eos_id if eos_id is None else int(eos_id)
        deadline_at = None
        if deadline is not None:
            deadline_at = time.monotonic() + float(deadline)
        elif timeout is not None:
            deadline_at = time.monotonic() + float(timeout)
        stream = GenStream(prompt, max_new_tokens, eos, deadline_at)
        with self._cv:
            if self._closed:
                raise RuntimeError("generation scheduler is shut down")
            if self._draining:
                raise SchedulerDraining(
                    "replica is draining: not admitting new sessions")
            if self._failed is not None:
                raise BatcherCrashed(
                    f"generation scheduler is down after "
                    f"{self._restarts} restarts: {self._failed}")
            if len(self._queue) >= self.queue_size:
                _profiler.runtime_metrics.inc("gen.queue_rejections")
                raise QueueFull(
                    f"generation queue full ({self.queue_size} pending)")
            self._queue.append(stream)
            self._cv.notify_all()
        return stream

    def close(self):
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        self._thread.join(timeout=10)

    def drain(self, deadline_s=None):
        """Stop admitting new sessions, await the live ones to natural
        completion for up to ``deadline_s`` seconds (None = unbounded),
        then checkpoint-migrate whatever remains at the next token
        boundary.  Returns the list of checkpoints handed back (empty
        when every stream finished inside the deadline) — each one is
        ``{"prompt", "tokens", "remaining_tokens", "eos_id",
        "reason"}``, everything a survivor replica needs to continue
        the stream token-identically via deterministic re-prefill.

        A length-cap decode used to be able to hold a rolling restart
        open for minutes; with a deadline it costs at most
        ``deadline_s`` plus one decode iteration."""
        with self._cv:
            self._draining = True
            self._cv.notify_all()
        deadline_at = None if deadline_s is None \
            else time.monotonic() + float(deadline_s)
        while True:
            with self._cv:
                if (not self._queue and not self._slots and
                        not self._admitting) or \
                        self._closed or self._failed is not None:
                    return list(self.migrated)
            if deadline_at is not None and \
                    time.monotonic() >= deadline_at:
                break
            time.sleep(0.005)
        done = threading.Event()
        with self._cv:
            self._migrate_done = done
            self._migrate_req = True
            self._cv.notify_all()
        done.wait(timeout=30.0)
        return list(self.migrated)

    def abort_streams(self, exc=None):
        """In-process hard-kill support: ask the scheduler thread to
        fail every queued and active stream with a RETRYABLE error at
        the next token boundary — what a real ``kill -9`` looks like to
        a resume-capable client, minus the socket corpse.  Returns
        immediately (the kill is asynchronous, like a crash)."""
        if exc is None:
            from paddle_tpu.serving import BatcherCrashed
            exc = BatcherCrashed(
                "replica hard-killed mid-decode; stream aborted — "
                "resume on a survivor")
        with self._cv:
            self._abort_exc = exc
            self._cv.notify_all()

    # -- scheduler thread --------------------------------------------------
    def _spawn_thread(self):
        t = threading.Thread(target=self._run, daemon=True,
                             name="paddle-tpu-gen-scheduler")
        t.start()
        return t

    def _run(self):
        try:
            self._loop()
        except BaseException as e:
            self._crash(e)

    def _crash(self, exc):
        from paddle_tpu import profiler as _profiler
        from paddle_tpu.serving import BatcherCrashed
        logger.exception("generation scheduler thread crashed")
        self._in_flight = None
        admitting = self._take_admissions()
        with self._cv:
            queued, self._queue = admitting + self._queue, []
            active, self._slots = list(self._slots.values()), {}
            self._free = list(range(self.predictor.num_slots))
            restart = not self._closed and \
                self._restarts < self.max_restarts
            if restart:
                self._restarts += 1
            elif not self._closed:
                self._failed = exc
        # the wholesale slot reset above must also reset the page
        # pool, or a crash strands every live allocation and the
        # restarted loop livelocks on page-aware admission
        self.predictor.free_all_pages()
        if restart:
            _profiler.runtime_metrics.inc("gen.scheduler_restarts")
            self._thread = self._spawn_thread()
        err = BatcherCrashed(
            f"generation scheduler crashed ({type(exc).__name__}: {exc});"
            f" request aborted — retry")
        err.__cause__ = exc
        for slot in active:
            slot.stream.fail(err)
        for stream in queued:
            stream.fail(err)

    def _loop(self):
        from paddle_tpu import profiler as _profiler
        while True:
            with self._cv:
                while not self._queue and not self._slots and \
                        self._in_flight is None and \
                        self._admission is None and not self._awaiting and \
                        not self._closed and self._abort_exc is None \
                        and not self._migrate_req:
                    self._cv.wait(0.05)
                if self._closed:
                    queued = self._take_admissions() + self._queue
                    self._queue = []
                    active, self._slots = list(self._slots.items()), {}
                    self._in_flight = None
                    break
            # one turn that has work: parent of every span below on
            # this thread; its self time is the sweep, the gauge, the
            # SLO tick and the lock waits
            with _span("gen.sched.turn"):
                # kill/migrate run HERE — between decode iterations;
                # with the step in flight collected or dropped, every
                # live stream is at a whole-token boundary
                if self._abort_exc is not None:
                    self._do_abort()
                if self._migrate_req:
                    self._do_migrate()
                self._sweep_queue()
                self._admit()
                if self._slots or self._in_flight is not None:
                    self._decode_iteration()
                    # a completed iteration is forward progress: the
                    # restart budget bounds CONSECUTIVE crashes, not
                    # lifetime ones
                    with self._cv:
                        self._restarts = 0
                elif self._admission is not None or self._awaiting:
                    # the live streams ended under an admission: nobody
                    # waits for a token between its chunks any more
                    self._admit_alone()
                _profiler.runtime_metrics.set_gauge("gen.slots_active",
                                                    len(self._slots))
                _slo_tick(self.slo_watchdog)
        # shutdown discards the slots wholesale; return their pages so
        # a later scheduler over the SAME predictor starts with a full
        # pool (the test suite reuses warmed predictors this way)
        self.predictor.free_all_pages()
        err = RuntimeError("generation scheduler shut down")
        for _, slot in active:
            slot.stream.fail(err)
        for stream in queued:
            stream.fail(err)

    def _do_abort(self):
        """Scheduler-thread half of :meth:`abort_streams`: wholesale
        reset (slots, free list, page pool), every stream failed with
        the retryable kill error.  The step in flight is dropped, as a
        kill would: the device runs what was queued in order, so a later
        admission's seed cannot be overtaken by its writes."""
        self._in_flight = None
        admitting = self._take_admissions()
        with self._cv:
            exc, self._abort_exc = self._abort_exc, None
            queued, self._queue = admitting + self._queue, []
            active, self._slots = list(self._slots.values()), {}
            self._free = list(range(self.predictor.num_slots))
        self.predictor.free_all_pages()
        for slot in active:
            slot.stream.fail(exc)
        for stream in queued:
            stream.fail(exc)

    def _do_migrate(self):
        """Scheduler-thread half of :meth:`drain`'s expiry path:
        checkpoint every remaining stream at its current token boundary
        and hand it back as a ``("migrate", checkpoint)`` event, then
        release the slot/pages.  Queued (never-admitted) streams
        migrate with zero emitted tokens.  The step in flight is
        collected and emitted first: a checkpoint's ``tokens`` are what
        the client received, and nothing is queued on the device for a
        slot whose pages go back."""
        if self._in_flight is not None:
            self._decode_iteration(dispatch=False)
        # an admitting stream has no token yet: it goes as a queued one
        # does, and its slot and pages back
        admitting = self._admissions()
        for adm in admitting:
            self._end_admission(adm)
        with self._cv:
            queued = [a.stream for a in admitting] + self._queue
            self._queue = []
            active = sorted(self._slots.items())
        for idx, slot in active:
            if not slot.stream.cancelled:
                self._checkpoint_out(slot.stream)
            else:
                slot.stream.finish("disconnect")
            self.predictor.free_slot_pages(idx)
            with self._cv:
                self._slots.pop(idx, None)
                self._free.append(idx)
        for stream in queued:
            if not stream.cancelled:
                self._checkpoint_out(stream)
            else:
                stream.finish("disconnect")
        with self._cv:
            self._migrate_req = False
            done, self._migrate_done = self._migrate_done, None
        if done is not None:
            done.set()

    def _checkpoint_out(self, stream):
        from paddle_tpu import profiler as _profiler
        ckpt = {"prompt": list(stream.prompt),
                "tokens": list(stream.tokens),
                "remaining_tokens": max(
                    0, stream.max_new_tokens - len(stream.tokens)),
                "eos_id": stream.eos_id,
                "reason": "draining"}
        self.migrated.append(ckpt)
        _profiler.runtime_metrics.inc("gen.session.migrations")
        stream.finish_reason = "migrated"
        stream._push(("migrate", ckpt))

    def _sweep_queue(self):
        """Fail expired/abandoned QUEUED requests immediately — an
        expired deadline gets its 504 now, not when a slot frees up."""
        from paddle_tpu import profiler as _profiler
        from paddle_tpu.serving import DeadlineExceeded
        now = time.monotonic()
        with self._cv:
            keep = []
            for stream in self._queue:
                if stream.cancelled:
                    stream.finish("disconnect")
                    continue
                if stream.deadline_at is not None and \
                        now > stream.deadline_at:
                    _profiler.runtime_metrics.inc("gen.expired")
                    stream.fail(DeadlineExceeded(
                        "deadline expired while queued for admission"))
                    continue
                keep.append(stream)
            self._queue = keep

    def _admit(self):
        """Move queued requests into free slots (continuous mode), or —
        batch mode — refill the pool only once it is completely empty,
        and then fill it WHOLE (the refill decision is made once per
        call, so one batch admission loads every free slot rather than
        degrading to serial batch-of-1)."""
        from paddle_tpu import profiler as _profiler
        refill = None
        spent = 0.0
        admitted_n = 0
        while True:
            with self._cv:
                if not self._queue or not self._free:
                    return
                if self._admission is not None:
                    return      # one request's chunks before the next's
                if self.admission == "batch":
                    if refill is None:
                        refill = not self._slots
                    if not refill:
                        return
                # page-aware admission: a request is only admitted when
                # the pool can cover its WHOLE length horizon (allocation
                # happens once, at admission), so decode growth never
                # fails mid-request; otherwise the head-of-line request
                # waits for an eviction to return pages — backpressure,
                # like the FLOPs budget below, not an error
                head = self._queue[0]
                need = self.predictor.pages_needed(
                    len(head.prompt), head.max_new_tokens)
                if need > self.predictor.free_pages:
                    return
                if self.prefill_budget is not None and admitted_n \
                        and not self._chunked:
                    # cost-weighted admission: stop once this pass has
                    # admitted its budget of static prefill FLOPs (the
                    # first admission is always free so the queue
                    # drains); the rest of the queue waits one decode
                    # iteration instead of stalling every live stream
                    cost = self.predictor.prefill_cost(
                        len(self._queue[0].prompt))
                    if spent + cost > self.prefill_budget:
                        return
                stream = self._queue.pop(0)
                slot_idx = self._free.pop(0)
                self._admitting += 1
                queued_behind = len(self._queue)
            now = time.perf_counter()
            waited = now - stream.created_t
            _profiler.runtime_metrics.observe("gen.queue_wait_seconds",
                                              waited)
            _trace.record_span("gen.queue_wait", stream.created_t, waited,
                               trace_id=stream.trace_id,
                               queued_behind=queued_behind)
            if self.prefill_budget is not None:
                cost = self.predictor.prefill_cost(len(stream.prompt))
                spent += cost
                _profiler.runtime_metrics.observe("gen.admission_cost",
                                                  cost)
            admitted_n += 1
            if self._chunked:
                self._admission = self._begin_admission(slot_idx, stream)
                if self._admission is None:
                    continue        # the pool could not cover it: failed
                # with no stream live (or a batch refill, which nothing
                # decodes beside) the chunks run back to back, here; else
                # the decode turns take them, one a turn
                if not refill and (self._slots
                                   or self._in_flight is not None):
                    return
                self._admit_alone()
                continue
            admitted = False
            try:
                admitted = self._prefill_into(slot_idx, stream)
            finally:
                with self._cv:
                    self._admitting -= 1
                    if not admitted:
                        self._free.append(slot_idx)

    def _prefill_into(self, slot_idx, stream):
        """Prefill one request and seed its slot; returns True when the
        slot stays occupied (request still generating)."""
        with _trace.trace_context(stream.trace_id):
            with _span("gen.admit", slot=slot_idx):
                return self._admit_one(slot_idx, stream)

    def _admit_one(self, slot_idx, stream):
        from paddle_tpu import profiler as _profiler
        t0 = time.perf_counter()
        try:
            logits, kv = self.predictor.prefill(stream.prompt)
        except BaseException as e:
            stream.fail(e)
            return False
        # counted only when prefill actually ran for an admitted
        # request — a failed prefill above never takes the slot
        _profiler.runtime_metrics.inc("gen.admissions")
        _profiler.runtime_metrics.observe("gen.prefill_seconds",
                                          time.perf_counter() - t0)
        with _span("gen.first_token"):
            first = int(np.argmax(logits))
            now = time.perf_counter()
            _profiler.runtime_metrics.observe("gen.ttft_seconds",
                                              now - stream.created_t)
            _profiler.runtime_metrics.inc("gen.tokens")
            stream.emit(first)
        prompt_len = len(stream.prompt)
        if first == stream.eos_id:
            return self._finish(stream, "eos")
        if stream.max_new_tokens <= 1 or prompt_len >= self._horizon:
            return self._finish(stream, "length")
        with _span("gen.seed_slot") as seed:
            try:
                pages = self.predictor.alloc_slot_pages(
                    slot_idx, self.predictor.pages_needed(
                        prompt_len, stream.max_new_tokens))
            except BaseException as e:
                stream.fail(e)
                return False
            seed.set(pages=len(pages),
                     row_bytes=self.predictor.cache_row_bytes)
            win = getattr(self.predictor, "window_attention", None)
            if win:
                # a window layer is seeded with its ring, not pages: the
                # prompt's last rows
                seed.set(ring_rows=len(win["layers"])
                         * min(prompt_len, int(win["ring"])))
            try:
                written = self.predictor.write_slot(slot_idx, kv,
                                                    prompt_len)
            except BaseException as e:
                # a fault in the seed is the scheduler's crash (_run); the
                # stream, out of the queue and in no slot yet, is nobody
                # else's to fail
                self.predictor.free_slot_pages(slot_idx)
                stream.fail(e)
                raise
            seed.set(compiled_calls=1, eager_ops=written,
                     state_arrays=len(self.predictor.state_vars))
        self._count_state("seeded")
        with self._cv:
            self._slots[slot_idx] = _Slot(stream, prompt_len, first)
        return True

    def _count_state(self, what):
        """A slot's rows of the per-slot state arrays were taken for a
        stream (``seeded``: written by the seed, or by the admission's
        first chunk from zeros) or given up (``freed``): always-on
        ``gen.state.bytes_seeded`` / ``gen.state.bytes_freed``; nothing
        for a bundle without ``state_vars``."""
        from paddle_tpu import profiler as _profiler
        held = getattr(self.predictor, "state_bytes_per_slot", 0)
        if held:
            _profiler.runtime_metrics.inc(f"gen.state.bytes_{what}", held)

    # -- an admission as a run of chunks (``predictor.prefill_chunks``) ----
    def _begin_admission(self, slot_idx, stream):
        """Allocate the request's pages (its whole horizon, as ever) and
        lay out its chunks.  Returns the :class:`_Admission`, which owns
        the slot until :meth:`_seat` or :meth:`_end_admission`; None
        where the pool could not cover it (the stream is failed, the
        slot free again)."""
        p, prompt_len = self.predictor, len(stream.prompt)
        with _trace.trace_context(stream.trace_id), \
                _span("gen.seed_slot") as seed:
            try:
                pages = p.alloc_slot_pages(slot_idx, p.pages_needed(
                    prompt_len, stream.max_new_tokens))
            except BaseException as e:
                stream.fail(e)
                with self._cv:
                    self._admitting -= 1
                    self._free.append(slot_idx)
                return None
            # nothing is seeded: the chunks write pages and state
            seed.set(pages=len(pages), row_bytes=p.cache_row_bytes,
                     compiled_calls=0, eager_ops=0,
                     state_arrays=len(p.state_vars),
                     state_bytes=getattr(p, "state_bytes_per_slot", 0))
            win = getattr(p, "window_attention", None)
            if win:
                seed.set(ring_rows=len(win["layers"])
                         * min(prompt_len, int(win["ring"])))
        self._count_state("seeded")
        return _Admission(stream, slot_idx, p.chunk_spans(prompt_len))

    def _end_admission(self, adm, seated=False):
        """``adm`` is over: its slot decodes (``seated``), or goes back
        with its pages."""
        if not seated:
            self.predictor.free_slot_pages(adm.slot_idx)
            self._count_state("freed")
        if self._admission is adm:
            self._admission = None
        elif adm in self._awaiting:
            self._awaiting.remove(adm)
        with self._cv:
            self._admitting -= 1
            if not seated:
                self._free.append(adm.slot_idx)

    def _admissions(self):
        """Those that wait for their first token, then the one whose
        chunks are going out."""
        return [a for a in self._awaiting + [self._admission] if a]

    def _take_admissions(self):
        """Every admitting stream, for a wholesale reset (crash, abort,
        shutdown), which takes slots and pages back itself."""
        taken = self._admissions()
        self._admission, self._awaiting = None, []
        with self._cv:
            self._admitting -= len(taken)
        return [a.stream for a in taken]

    def _run_chunk(self, adm):
        """Dispatch ``adm``'s next chunk, not waited for.  False where
        the admission ended instead: its reader gone, or the chunk
        failed (the stream's failure, as a failed prefill is)."""
        from paddle_tpu import profiler as _profiler
        stream = adm.stream
        if stream.cancelled:
            _profiler.runtime_metrics.inc("gen.disconnects")
            self.predictor.clear_slot(adm.slot_idx)
            stream.finish("disconnect")
            self._end_admission(adm)
            return False
        a, b = adm.spans[adm.cursor]
        try:
            # a bundle that drafts takes the prompt's token behind the
            # chunk too (its MTP rows embed the token that follows)
            more = {"after": stream.prompt[b] if b < len(stream.prompt)
                    else None} if self._drafts else {}
            with _trace.trace_context(stream.trace_id):
                adm.logits = self.predictor.prefill_chunk(
                    adm.slot_idx, stream.prompt[a:b], a, **more)
        except BaseException as e:
            stream.fail(e)
            self._end_admission(adm)
            return False
        adm.cursor += 1
        return True

    def _dispatch_chunks(self, beside_step):
        """This turn's share of the admission in progress, launched
        behind the turn's decode step: ONE chunk, more only while an
        explicit ``prefill_budget`` covers the next one's static cost.
        An admission whose last chunk went out waits for its first token
        (:meth:`_seat`, a turn later)."""
        from paddle_tpu import profiler as _profiler
        adm = self._admission
        if adm is None:
            return
        spent, ran = 0.0, 0
        while adm.cursor < len(adm.spans):
            if ran:
                if self.prefill_budget is None:
                    break
                a, b = adm.spans[adm.cursor]
                spent += self.predictor.chunk_cost(a, b - a)
                if spent > self.prefill_budget:
                    break
            if not self._run_chunk(adm):
                break
            ran += 1
        if ran:
            _profiler.runtime_metrics.inc(
                "gen.prefill.turns_interleaved" if beside_step
                else "gen.prefill.turns_alone", 1 if beside_step else ran)
        if self._admission is adm and adm.cursor == len(adm.spans):
            self._admission = None
            self._awaiting.append(adm)

    def _admit_alone(self):
        """No stream is live: the chunks of every admission under way
        run back to back and it is seated, since nobody waits for a
        token between them."""
        from paddle_tpu import profiler as _profiler
        for adm in self._admissions():
            while adm.cursor < len(adm.spans):
                if not self._run_chunk(adm):
                    break
                _profiler.runtime_metrics.inc("gen.prefill.turns_alone")
            else:
                self._seat(adm)

    def _seat(self, adm):
        """``adm``'s last chunk is out: read its logits (the one wait of
        an admission), emit the first token, and seat the stream in its
        slot, whose row of the device's decode state the next turn's
        patch sets."""
        from paddle_tpu import profiler as _profiler
        metrics = _profiler.runtime_metrics
        stream, prompt_len = adm.stream, len(adm.stream.prompt)
        try:
            logits = np.asarray(adm.logits)[0]
        except BaseException as e:
            stream.fail(e)
            self._end_admission(adm)
            return
        metrics.inc("gen.admissions")
        metrics.inc("gen.prefill.admissions_chunked")
        with _trace.trace_context(stream.trace_id):
            with _span("gen.first_token"):
                first = int(np.argmax(logits))
                now = time.perf_counter()
                metrics.observe("gen.prefill_seconds", now - adm.t0)
                metrics.observe("gen.ttft_seconds", now - stream.created_t)
                metrics.inc("gen.tokens")
                stream.emit(first)
        # the whole admission, first chunk to first token
        _trace.record_span("gen.admit", adm.t0, now - adm.t0,
                           trace_id=stream.trace_id, slot=adm.slot_idx,
                           chunks=len(adm.spans))
        if first == stream.eos_id:
            self._finish(stream, "eos")
        elif stream.max_new_tokens <= 1 or prompt_len >= self._horizon:
            self._finish(stream, "length")
        else:
            with self._cv:
                self._slots[adm.slot_idx] = _Slot(stream, prompt_len, first)
            return self._end_admission(adm, seated=True)
        self._end_admission(adm)

    def _finish(self, stream, reason):
        from paddle_tpu import profiler as _profiler
        stream.finish(reason)
        _profiler.runtime_metrics.inc("gen.requests_ok")
        return False

    def _evict(self, slot_idx, reason=None):
        # Eviction runs only on the scheduler thread, so the slot
        # cannot be re-admitted while this is in flight.  The slot is
        # removed from `_slots`/returned to `_free` LAST: once
        # `active_slots` reads 0, the slot's pages are already back in
        # the pool — observers (and page-aware admission) never see a
        # half-evicted slot.
        from paddle_tpu import profiler as _profiler
        with self._cv:
            slot = self._slots.get(slot_idx)
            if slot is None:
                return
        if reason == "disconnect":
            _profiler.runtime_metrics.inc("gen.disconnects")
            self.predictor.clear_slot(slot_idx)
            # terminal event even though the usual consumer is gone: a
            # LOCAL consumer that cancelled must not block forever on a
            # stream nobody will ever finish
            slot.stream.finish("disconnect")
        # EVERY eviction (eos / length / disconnect) returns the slot's
        # pages to the pool — the admission backpressure above turns a
        # leak here into a livelock; for disconnects this runs AFTER
        # clear_slot, which addresses pages through the still-live
        # allocation
        self.predictor.free_slot_pages(slot_idx)
        self._count_state("freed")
        with self._cv:
            self._slots.pop(slot_idx, None)
            self._free.append(slot_idx)
        _profiler.runtime_metrics.inc("gen.evictions")

    def _decode_iteration(self, dispatch=True):
        """One scheduler turn of decoding: sweep disconnects, dispatch
        the next step for every live slot that goes on (none with
        ``dispatch`` false: a whole-token boundary), then collect and
        emit the step that was in flight."""
        from paddle_tpu import profiler as _profiler
        # reclaim disconnected streams BEFORE paying a step for them
        with self._cv:
            live = list(self._slots.items())
        for idx, slot in live:
            if slot.stream.cancelled:
                self._evict(idx, reason="disconnect")
        with self._cv:
            live = sorted(self._slots.items()) if dispatch else []
        if not live and self._in_flight is None:
            return
        # what is left of this span beside its two children is the
        # bookkeeping below
        with _span("gen.decode_iteration", live=len(live)):
            self._step_and_emit(live, _profiler.runtime_metrics,
                                chunks=dispatch)

    @staticmethod
    def _count_runs(kept, runs, metrics):
        """A collected turn of a bundle that drafts, counted always-on
        (``gen.spec.*``) and returned for the ``gen.decode_step`` span:
        ``slot_turns`` (slots that yielded), ``rows`` (query rows they
        forwarded), ``drafted`` (drafts verified: one a slot turn; the
        few turns whose draft row was off, at ``max_len``'s edge, count
        as drafted and not accepted), ``accepted`` (drafts kept) and
        ``emitted`` (tokens yielded, before a stream's cap cuts its last
        run); ``gen.spec.run``: the histogram of a slot turn's run."""
        lengths = [len(runs[idx]) for idx, _, _ in kept if runs[idx]]
        out = {"slot_turns": len(lengths), "rows": 2 * len(lengths),
               "drafted": len(lengths),
               "accepted": sum(n > 1 for n in lengths),
               "emitted": sum(lengths)}
        metrics.inc("gen.spec.slot_turns", out["slot_turns"])
        metrics.inc("gen.spec.drafted", out["drafted"])
        metrics.inc("gen.spec.accepted", out["accepted"])
        metrics.inc("gen.spec.emitted", out["emitted"])
        for n in lengths:
            metrics.bucket("gen.spec.run", n)
        return out

    def _step_and_emit(self, live, metrics, chunks=True):
        S, horizon, block = self.predictor.num_slots, self._horizon, \
            self._block
        prev = self._in_flight
        carried = {idx: slot for idx, slot, _ in prev.rows} if prev else {}
        # -1: the slot's token is the device's own pick from ``prev``
        tokens = np.full(S, -1, np.int32)
        positions = np.zeros(S, np.int32)
        lens = np.zeros(S, np.int32)
        rows, fused = [], 0
        for idx, slot in live:
            cap = slot.stream.max_new_tokens
            if self._drafts:
                # ``steps`` and ``pos`` are what the host has READ; the
                # step in flight yields this slot one token at least
                ahead = int(carried.get(idx) is slot)
                if 1 + slot.steps + ahead >= cap \
                        or slot.pos + ahead >= horizon:
                    continue    # ended, or ends for certain in flight
                if not ahead:
                    tokens[idx] = slot.last_token
                # the most rows the slot can hold when this turn runs:
                # the page bucket's bound (the device's own state is
                # what the turn runs at)
                positions[idx] = slot.pos + 2 * ahead
                lens[idx] = positions[idx] + 1
                rows.append((idx, slot, False))
                continue
            if 1 + slot.steps >= cap or slot.pos >= horizon:
                continue    # ends by length with the step in flight
            if carried.get(idx) is not slot:
                tokens[idx] = slot.last_token
            positions[idx] = slot.pos
            lens[idx] = slot.pos + 1
            slot.steps += 1
            slot.pos += 1
            # the token fed completes a block: the step stores it and
            # opens the next
            fused += block > 1 and slot.pos % block == 0
            rows.append((idx, slot,
                         1 + slot.steps >= cap or slot.pos >= horizon))
        t0 = time.perf_counter()
        kept = ()
        # the scheduler thread's time in the predictor this turn: the
        # next step's dispatch, then the wait for the one in flight
        with _span("gen.decode_step", ahead=int(bool(rows and prev))) as step:
            self._in_flight = None
            if rows:
                metrics.bucket("gen.slot_occupancy", len(rows))
                metrics.inc("gen.decode.steps")
                if prev:
                    metrics.inc("gen.decode.steps_ahead")
                read = self.predictor.dispatch_turn(tokens, positions, lens)
                # the selections of the step just dispatched (learned
                # sparse attention) and the rows its full and its window
                # layers read; {} without
                step.set(**self.predictor.last_step_counts)
                self._in_flight = _Step(rows, read, fused)
            # the admissions whose last chunk went out a turn ago, and
            # this turn's chunk, behind the step just launched
            seats = list(self._awaiting)
            if chunks:
                self._dispatch_chunks(beside_step=bool(rows))
            if prev:
                with _span("gen.collect"):
                    ids, attrs = self.predictor.read_turn(prev.read)
                # a row whose slot was vacated since (EOS at the last
                # collect, a cancel) was computed for nothing
                kept = [row for row in prev.rows
                        if self._slots.get(row[0]) is row[1]]
                discarded = len(prev.rows) - len(kept)
                metrics.inc("gen.decode.rows_discarded", discarded)
                # ``stored``: slots whose turn yielded nothing; there
                # has been none since a block is stored by the forward
                # that opens the next (``fused``)
                step.set(live=len(prev.rows), discarded=discarded,
                         yielded=len(kept), stored=0, fused=prev.fused,
                         block_rows=(len(prev.rows) + prev.fused) * block,
                         **attrs)
                if self._drafts:
                    step.set(**self._count_runs(kept, ids, metrics))
        now = time.perf_counter()
        metrics.observe("gen.decode_step_seconds", now - t0)
        with _span("gen.emit"):
            for idx, slot, ends in kept:
                stream = slot.stream
                run = ids[idx] if self._drafts else [ids[idx]]
                if self._drafts:
                    # the device advanced the slot by its run; the run is
                    # cut where the stream reaches its cap
                    slot.pos += len(run)
                    run = run[:stream.max_new_tokens - 1 - slot.steps]
                    slot.steps += len(run)
                    ends = 1 + slot.steps >= stream.max_new_tokens \
                        or slot.pos >= horizon
                for token in run:
                    slot.last_token = token
                    metrics.inc("gen.tokens")
                    metrics.observe("gen.intertoken_seconds",
                                    now - slot.last_emit_t)
                    slot.last_emit_t = now
                    stream.emit(token)
                    if token == stream.eos_id:
                        break
                if run and slot.last_token == stream.eos_id:
                    self._finish(stream, "eos")
                    self._evict(idx)
                elif ends:
                    self._finish(stream, "length")
                    self._evict(idx)
        for adm in seats:
            self._seat(adm)
