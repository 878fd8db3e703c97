"""Executor: lowers a Program block to ONE compiled XLA computation.

This replaces the reference's per-op interpreter hot loop
(``paddle/fluid/framework/executor.cc:334-352`` — CreateOp / InferShape /
kernel dispatch per op per step) with trace-once/compile-once semantics:

  1. Partition block variables into feeds, read-only state, in-out state
     (persistables written by ops, e.g. parameters under SGD), and scratch.
  2. Trace every op's registered lowering into a single jaxpr.
  3. ``jax.jit`` the whole step with in-out state donated, cache by
     (program version, feed shapes/dtypes, fetch names).

Each subsequent ``run`` with the same signature is one XLA executable
launch — no Python per-op work at all.
"""

from __future__ import annotations

import contextlib
import logging
import threading
import time
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu import framework
from paddle_tpu.analysis import opmeta as _opmeta
from paddle_tpu.framework import Program, default_main_program
from paddle_tpu.obs.trace import span as _span, record_span as _record_span
from paddle_tpu.place import CPUPlace, TPUPlace
from paddle_tpu.scope import Scope, global_scope
from paddle_tpu.ops import registry

__all__ = ["Executor", "fetch_var", "enable_compile_cache",
           "resolve_compile_cache_dir",
           "disable_compile_cache", "jit_cache_capacity"]

logger = logging.getLogger(__name__)
_NO_SPAN = contextlib.nullcontext()

# op types that exist for API parity but are no-ops inside a lowered block
from paddle_tpu.ops.reader_ops import (READER_CREATE_OPS, READER_OPS,
                                       EOFException, build_reader)

# feed/fetch are rewritten by the executor; reader ops run in the host-side
# pre-pass (_run_reader_ops) so the compiled step never sees them
_SKIP_OPS = frozenset({"feed", "fetch"}) | READER_OPS


def _run_reader_ops(block, scope, feed_arrays, device, steps=None):
    """Host-side reader pre-pass: construct reader objects (idempotent) and
    pop one batch per ``read`` op into ``feed_arrays`` (or ``steps`` stacked
    batches for the device-side loop).  Runs BEFORE compile/dispatch each
    step — the TPU placement of the reference's per-op reader dispatch
    (``operators/reader/reader_op_registry.h``)."""
    for op in block.ops:
        if op.type in READER_CREATE_OPS:
            out = op.output("Out")[0]
            if scope.find_var(out) is None:
                reader = build_reader(op, scope, device=device)
                scope.set_var(out, reader)
                # back-pointer for Variable.reset() so the user-facing
                # handle works with explicit (non-global) scopes too
                try:
                    block.var(out)._reader_runtime = reader
                except KeyError:
                    pass
        elif op.type == "read":
            reader = scope.find_var(op.input("Reader")[0])
            if reader is None:
                raise RuntimeError(
                    f"reader {op.input('Reader')[0]!r} is not created — "
                    f"run the startup program first")
            try:
                if steps is None:
                    batch = reader.next()
                else:
                    pulled = []
                    try:
                        for _ in range(steps):
                            pulled.append(reader.next())
                    except StopIteration:
                        # mid-pull EOF: return the consumed batches so a
                        # later pull serves them (in order) instead of
                        # dropping them
                        for p in reversed(pulled):
                            reader.unget(p)
                        raise
                    # keep the stack on-device when the reader (double
                    # buffer) already staged the batches there
                    stack = jnp.stack if hasattr(pulled[0][0], "devices") \
                        else np.stack
                    batch = tuple(stack([p[i] for p in pulled])
                                  for i in range(len(pulled[0])))
            except StopIteration:
                raise EOFException(
                    "reader exhausted — call reader.reset() to rewind")
            for name, arr in zip(op.output("Out"), batch):
                feed_arrays[name] = _as_device_array(arr, None, device) \
                    if not hasattr(arr, "devices") else arr


def _host_value(value, dtype=None):
    """A fed value with what the host does to it: a Python scalar made an
    array, a numpy array cast to the variable's ``dtype``."""
    if isinstance(value, (int, float, bool)):
        value = np.asarray(value, dtype=dtype or None)
    if isinstance(value, np.ndarray) and dtype is not None:
        want = jnp.dtype(dtype) if dtype != "bfloat16" else jnp.bfloat16
        if value.dtype != want and dtype not in (None,):
            value = value.astype(want)
    return value


def _as_device_array(value, dtype=None, device=None):
    value = _host_value(value, dtype)
    if device is not None and isinstance(value, (np.ndarray, jax.Array)):
        # one placement, committed, in the canonical dtype: what
        # jnp.asarray and then device_put gave in two dispatches
        return jax.device_put(value, device)
    arr = jnp.asarray(value)
    if device is not None:
        arr = jax.device_put(arr, device)
    return arr


def _step_key(seed):
    """``jax.random.PRNGKey(seed)`` for one step.  Under the default
    threefry implementation the key is the seed's two 32-bit words, made
    here on the host and handed over with the call; ``PRNGKey`` makes the
    same two words in three dispatches of their own, each of which gives
    the calling thread's GIL away (a serving decode turn makes one key a
    step, and in a turn that changed no slot it is all the call takes
    from the host: ``CompiledStep.call``)."""
    if jax.config.jax_default_prng_impl != "threefry2x32" or \
            jax.config.jax_enable_custom_prng:
        return jax.random.PRNGKey(seed)
    high = (seed >> 32) & 0xFFFFFFFF if jax.config.jax_enable_x64 else 0
    return np.array([high, seed & 0xFFFFFFFF], np.uint32)


# ---------------------------------------------------------------------------
# persistent XLA compilation cache: a restart no longer recompiles every
# program from scratch — XLA executables are stored under the cache dir
# keyed by the lowered module, and a second process (or a second Executor
# re-tracing an identical program) loads them instead of invoking the
# backend compiler.  Hit/miss counters land in profiler.runtime_metrics
# (compile_cache.hits / .misses).
#
# WHERE the cache lives is decided in one place, resolve_compile_cache_dir:
#   1. JAX_COMPILATION_CACHE_DIR set  -> the cache was placed from outside;
#      jax reads it itself and this program never sets a dir in code;
#   2. an explicit dir (--compile-cache / PADDLE_TPU_COMPILE_CACHE);
#   3. entry points only (chip_smoke.py, `paddle_tpu train|serve|
#      controller`, bench.py, bench_autoscale.py): the fixed
#      <checkout>/.jax_cache — the path is part of the cache key, so it is
#      never a temp dir, a pid or a time.
# A bare library Executor with none of these set keeps no persistent cache.
#
# WHAT an entry is keyed by is decided here too, once, at import: the
# lowered module WITH its metadata (jax strips it by default).  Every
# instruction's ``op_name`` holds the scope path device traces are read by
# (``pt_step/<role>/<name scope...>/ptop_<type>__<output>``, and the
# predictor's ``gen_turn`` / ``gen_seed``), so two builds that lower the
# same computation under other scope names must not share an executable:
# the one loaded would carry the OTHER build's ``op_name``s and the trace
# would attribute nothing, or the wrong thing.  The option covers every
# program this process compiles (``Executor.run`` / ``run_steps`` /
# ``compiled_step``, the predictor's own ``jax.jit``s, ``ParallelExecutor``)
# and a cache placed from outside alike, and nothing flips it afterwards.
#
# The metadata in the key has to be the NAMES alone.  jax also records the
# Python call stack of every equation in its location, and a function it
# traces once a process (``jax.random.uniform``'s inner jit, any shared
# helper) keeps the stack of whoever traced it FIRST: a run that exports a
# bundle and a warm start of the same tree then lower the same program
# with different locations, and the warm start would miss (seen on the
# chip, PR 51: 14 of 54 executables of a warm serving start).  So no frame
# goes into a location: a key is the computation and its ``op_name``s, the
# same from any call site, process history and checkout path.
# ---------------------------------------------------------------------------

jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
jax.config.update("jax_traceback_in_locations_limit", 0)

_compile_cache_dir = None


def _external_compile_cache_dir():
    import os
    return os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()


def resolve_compile_cache_dir(explicit=None, entry_point=False):
    """The dir the persistent cache should use, or ``""`` for none (see
    the block comment above for the order)."""
    import os
    return (_external_compile_cache_dir() or str(explicit or "")
            or os.environ.get("PADDLE_TPU_COMPILE_CACHE", "").strip()
            or (os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), ".jax_cache")
                if entry_point else ""))


def enable_compile_cache(cache_dir=None, entry_point=False):
    """Turn on jax's persistent compilation cache at the resolved dir and
    relax its size/compile-time admission floors so every executable is
    cached (the floors exist to keep trivial kernels out of shared caches;
    a serving replica wants ALL of its programs warm).  Idempotent."""
    global _compile_cache_dir
    cache_dir = resolve_compile_cache_dir(cache_dir, entry_point)
    if not cache_dir or _compile_cache_dir == cache_dir:
        return _compile_cache_dir is not None
    if not _external_compile_cache_dir():
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _reset_jax_cache_memo()  # see below — without this, enabling after
    # the process has already compiled something is silently a no-op
    _compile_cache_dir = cache_dir
    from paddle_tpu import profiler as _profiler
    _profiler.install_jax_compile_listeners()
    return True


def disable_compile_cache():
    """Turn the persistent cache back off (tests; config symmetry).  A
    cache placed from outside is not this program's to turn off."""
    global _compile_cache_dir
    if _compile_cache_dir is None:
        return
    if not _external_compile_cache_dir():
        jax.config.update("jax_compilation_cache_dir", None)
        _reset_jax_cache_memo()
    _compile_cache_dir = None


def _reset_jax_cache_memo():
    """jax memoizes cache-enabled/disabled at the FIRST compile of the
    process (compilation_cache._cache_checked); reset it so a dir set
    mid-process (serving replica enabling the cache at load time) takes
    effect."""
    try:
        from jax._src import compilation_cache as _cc
        _cc.reset_cache()
    except Exception:  # pragma: no cover - internal API moved
        logger.warning("could not reset jax compilation-cache state; "
                       "a cache dir set after the first compile may be "
                       "ignored", exc_info=True)


def jit_cache_capacity():
    """Executor-level jit LRU capacity: PADDLE_TPU_JIT_CACHE_SIZE
    (default 64; values < 1 clamp to 1)."""
    import os
    raw = os.environ.get("PADDLE_TPU_JIT_CACHE_SIZE", "").strip()
    try:
        return max(1, int(raw)) if raw else 64
    except ValueError:
        logger.warning("bad PADDLE_TPU_JIT_CACHE_SIZE=%r; using 64", raw)
        return 64


def _first_call(program, feed_arrays):
    """The ``executor.compile`` span around the first call of a fresh
    executable."""
    return _span("executor.compile", program=id(program),
                 version=program._version,
                 feeds=sorted((n, str(a.dtype), tuple(a.shape))
                              for n, a in feed_arrays.items()))


def _captured(fn, feed_arrays, fetch_names, tag=""):
    """``fn`` (a fresh ``jax.jit``) wrapped so that its first call
    AOT-compiles and captures the cost / memory record of this jit key
    (``paddle_tpu profile compile``, the live MFU gauge, the headroom
    check); ``fn`` itself with the capture off."""
    from paddle_tpu.obs import perf as _perf
    if not _perf.capture_enabled():
        return fn
    return _perf.instrument_jit(
        fn, label=_perf.jit_label(feed_arrays, fetch_names, tag=tag))


class _DispatchRecord:
    """What a call of one (signature, scope) would derive again although
    it cannot have changed since the last one; :meth:`Executor.run`,
    :meth:`Executor.run_steps`, ``ParallelExecutor.run`` and
    :class:`CompiledStep` dispatch from it.

    It holds the classification (``Executor._prepare``'s parts) and the
    executable ``fn`` (``None`` for a caller that jits the step itself),
    built once by the jit-cache miss that made the record, and the state:
    ``ro`` and the carry (the in-out state; under ``run_steps`` the
    scan's carry) as two flat tuples in name order, looked up in the
    scope once (``resolve``).  After a launch the step's own outputs are
    the next call's carry (``adopt``): the donated inputs are dead and
    the outputs lie where the executable put them, so nothing is looked
    up, compared or placed again.  The scope stays the truth: ``adopt``
    writes every output back, and a write or erasure by anyone else
    drops the arrays, not the classification, AT the write
    (``Scope.watch``), so a replaced array is held by nothing here and
    the next call resolves again.

    ``shardings`` (a mesh alone): ``(feeds by name, state by name, key)``,
    what ``place`` puts the arguments under.  An interpreted program
    (a host op, op profiling, numerics probing) keeps no arrays and
    takes no lock: its ops read and write the scope themselves.

    The record belongs to one scope, held weakly, and lives and dies
    with its jit-cache entry."""

    def __init__(self, exe, scope, parts, fn=None, donated=True,
                 shardings=None, scan_sample=None):
        self._exe, self._scope = exe, weakref.ref(scope)
        self.fn, self.fresh = fn, True  # fresh: its first call compiles
        self.perf = getattr(fn, "perf", None)
        self.step = parts["step"]
        self.ro_names = parts["ro_names"]
        self.inout_names = parts["inout_names"]
        # what a launch hands back, in order: the in-out state, then
        # persistables the step writes without reading
        self.written = self.inout_names + parts["create_state"]
        self.param_names = parts["param_names"]
        self.interpret = parts["interpret"]
        self.donated = donated and not self.interpret
        self.shardings = shardings
        # run_steps: the feeds of one step (shapes do), for the shapes of
        # write-only persistables that ride the scan's carry
        self._scan_sample = None if self.interpret else scan_sample
        self.carry_names = self.inout_names
        # (ro, carry) as resolved, None once the scope was written by
        # someone else; ``_dirty``: written since the last ``resolve``
        self._state, self._dirty, self._placed = None, False, False
        # orders calls from two threads (``calling``; re-entrant: a
        # caller's feed() may call back); ``_forget`` never waits for it,
        # so two records over one scope cannot lock each other out
        self._mutex = _NO_SPAN if self.interpret else threading.RLock()

    def _forget(self):
        """Someone else wrote the scope: hold none of its arrays."""
        self._dirty, state, self._state = True, self._state, None
        if state is not None and (state[0] or state[1]):
            from paddle_tpu import profiler as _profiler
            _profiler.runtime_metrics.inc("executor.record.forgets")

    @contextlib.contextmanager
    def calling(self):
        """One call, from ``resolve`` to ``adopt``, calls from other
        threads kept out.  A call that fails in between leaves a donating
        record holding dead arrays, so the next one looks at the scope
        again.  (A guarded step donates nothing: a tripped sentinel leaves
        the record on the pre-step state.)"""
        with self._mutex:
            try:
                yield
            except BaseException:
                if self.donated:
                    self._state = None
                raise

    def _lookup(self, scope, name, device):
        """``name``'s array in the scope.  For a caller that jits the step
        itself (no ``fn`` here) it is committed to the executor's device:
        a loaded array is not, the step's outputs are, and the two would
        be two signatures of that caller's executable.  (``run``'s own
        executable was compiled for what the scope held at its first
        call; on a mesh ``place`` puts state under its sharding.)"""
        v = self._exe._state_value(scope, name, device, by=self)
        if self.fn is None and device is not None \
                and isinstance(v, jax.Array) and not v.committed:
            v = jax.device_put(v, device)
            scope.set_var(name, v, by=self)
        return v

    def resolve(self, span=None):
        """``(ro, carry)``: the record's own where it holds them, else
        looked up in the scope (``span``: ``arrays`` handed to the step,
        ``resolved`` how many of them this call looked up)."""
        state, resolved = self._state, 0
        if state is None:
            self._dirty = False
            scope, device = self._scope(), self._exe._feed_device()
            if not self.interpret:
                # told once of the next write by someone else: before the
                # first lookup, so that none of them is missed
                scope.watch(self._forget)
            ro = tuple(self._lookup(scope, n, device)
                       for n in self.ro_names)
            carry = tuple(self._lookup(scope, n, device)
                          for n in self.inout_names)
            if self._scan_sample is not None:
                carry = self._scan_carry(scope, device, ro, carry)
            state, resolved = (ro, carry), len(ro) + len(carry)
            self._placed = False
            if not (self.interpret or self._dirty):
                self._state = state
        if span is not None:
            span.set(arrays=len(state[0]) + len(state[1]),
                     resolved=resolved)
        return state

    def _scan_carry(self, scope, device, ro, inout):
        """The scan's carry over ``inout``: write-only persistables ride
        it too, so their final value lands back in the scope as ``run``'s
        does; one the scope does not hold yet is seeded with zeros of its
        traced shape.  Sets ``carry_names``."""
        create = self.written[len(self.inout_names):]
        names = self.inout_names + tuple(
            n for n in create if scope.find_var(n) is not None)
        carry = inout + tuple(self._lookup(scope, n, device)
                              for n in names[len(inout):])
        still = [n for n in create if n not in names]
        if still:
            _, shapes = jax.eval_shape(
                self.step, self._scan_sample,
                dict(zip(self.ro_names, ro)),
                dict(zip(self.inout_names, inout)), jax.random.PRNGKey(0))
            still = [n for n in still if n in shapes]
            names += tuple(still)
            carry += tuple(jnp.zeros(shapes[n].shape, shapes[n].dtype)
                           for n in still)
        self.carry_names = names
        return carry

    def adopt(self, names, values):
        """The step wrote ``values`` under ``names``, the carry first and
        in ``carry_names``' order: they go back to the scope and are the
        next call's carry, unless someone wrote the scope meanwhile."""
        scope, by = self._scope(), None if self.interpret else self
        for n, v in zip(names, values):
            scope.set_var(n, v, by=by)
        state = self._state
        if state is not None:
            self._state = state[0], tuple(values[:len(self.carry_names)])
            if self._dirty:     # written on another thread just now
                self._state = None

    def place(self, span, feeds, ro, carry, key):
        """The step's arguments under the executable's shardings
        (``executor.place``: ``arrays`` handed to the step, ``checked``
        those whose sharding was compared, ``moved`` put by the one
        ``device_put``, ``bytes`` those held).  State the record placed
        or the executable returned is where it belongs: per step the
        feeds and the key are what is looked at."""
        feed_at, state_at, key_at = self.shardings
        names = tuple(feeds)
        args = [feeds[n] for n in names] + [key]
        want = [feed_at[n] for n in names] + [key_at]
        if not self._placed:
            args += ro + carry
            want += [state_at[n] for n in self.ro_names + self.carry_names]
        move = [i for i, (a, s) in enumerate(zip(args, want))
                if getattr(a, "sharding", None) != s]
        span.set(arrays=len(names) + 1 + len(ro) + len(carry),
                 checked=len(args), moved=len(move),
                 bytes=sum(int(getattr(args[i], "nbytes", 0))
                           for i in move))
        if move:
            put = jax.device_put([args[i] for i in move],
                                 [want[i] for i in move])
            for i, a in zip(move, put):
                args[i] = a
        if not self._placed:
            at = len(names) + 1
            ro, carry = tuple(args[at:at + len(ro)]), \
                tuple(args[at + len(ro):])
            if self._state is not None:
                self._state = ro, carry
            self._placed = True
        return dict(zip(names, args)), ro, carry, args[len(names)]

    def call(self, feeds, ro, carry, key, *more):
        """Place (a mesh alone) and launch: ``executor.place`` and
        ``executor.launch``, the last two stretches of a dispatch.  The
        executable takes the state as dicts by name (``more``: ``run_steps``'
        per-step feeds, which go first)."""
        if self.shardings is not None:
            with _span("executor.place") as placed:
                feeds, ro, carry, key = self.place(placed, feeds, ro,
                                                   carry, key)
        with _span("executor.launch"):
            return self.fn(feeds, *more, dict(zip(self.ro_names, ro)),
                           dict(zip(self.carry_names, carry)), key)


class CompiledStep:
    """A program's step taken out of :meth:`Executor.run`, for a caller
    that launches it every few milliseconds inside a ``jax.jit`` of its
    own (``GenPredictor``'s decode turn): :meth:`Executor.compiled_step`
    makes one per (program, feed names, fetch list, scope).

    It dispatches from a :class:`_DispatchRecord` as ``run`` does, one
    that holds no executable: the program is classified once and its
    read-only and in-out state resolved from the scope into two flat
    tuples, let go of the moment anyone else writes the scope (a seeded
    slot, a weight load).  ``ro_names`` / ``inout_names`` / ``written``
    name the tuples' entries.

    :meth:`flat` is the TRACEABLE step, to be inlined in the caller's
    jitted function (a nested ``jax.jit`` would rename the op scopes
    ``pt_step/ptop_<op>`` that device traces are read by);
    :meth:`call` launches that function under the spans of an
    ``Executor.run`` and writes the new state back."""

    def __init__(self, record, program):
        self._record, self._program = record, program
        self.ro_names = record.ro_names
        self.inout_names = record.inout_names
        self.written = record.written

    @property
    def _state(self):
        return self._record._state

    def flat(self, feeds, ro, inout, key):
        """``(fetches, written)``: the program's step over ``feeds`` (a
        dict), ``ro`` / ``inout`` (tuples as :meth:`call` passes them)
        and an RNG ``key``; ``written`` is a tuple in the order of
        :attr:`written`.  Traceable, never jitted here."""
        fetches, new_state = self._record.step(
            feeds, dict(zip(self.ro_names, ro)),
            dict(zip(self.inout_names, inout)), key)
        return fetches, tuple(new_state[n] for n in self.written)

    def call(self, fn, feed):
        """One launch: ``fn(*feed(), ro, inout, key) -> (out, written)``,
        ``fn`` a jitted function that inlines :meth:`flat` (and donates
        ``inout``).  Opens ``executor.run`` and its phases as
        ``Executor.run`` does: ``executor.feed`` is ``feed()`` (the
        caller building its arguments: nothing is converted here),
        ``executor.dispatch`` the state looked up where the scope
        changed and ``executor.launch``, ``executor.fetch`` the
        write-back; ``executor.step_seconds`` and the HBM census's tick
        as there.  Returns ``out``, unread."""
        record = self._record
        exe = record._exe
        with _span("executor.run"), record.calling():
            with _span("executor.feed"):
                args = feed()
            with _span("executor.dispatch"):
                ro, inout = record.resolve()
                exe._run_counter += 1
                key = _step_key((self._program.random_seed or 0) * 1000003
                                + exe._run_counter)
                t0 = time.perf_counter()
                with _span("executor.launch"):
                    out, written = fn(*args, ro, inout, key)
            from paddle_tpu import profiler as _profiler
            from paddle_tpu.obs import perf as _perf
            _profiler.runtime_metrics.observe("executor.step_seconds",
                                              time.perf_counter() - t0)
            _perf.census_tick(record._scope())
            with _span("executor.fetch"):
                record.adopt(self.written, written)
        return out


class ScopeEnv(dict):
    """Interpret-mode env with write-through/read-through of PERSISTABLE
    vars to the scope — the reference's semantics, where every thread's op
    reads and writes one shared Scope (scope.h).  Needed so CSP go-routine
    threads and the main block observe each other's persistable writes."""

    def __init__(self, scope, persistable_names, init=None):
        super().__init__()
        self.scope = scope
        self.persistable_names = persistable_names
        if init:
            dict.update(self, init)

    def __getitem__(self, k):
        if k in self.persistable_names:
            v = self.scope.find_var(k)
            if v is not None:
                return v
        return dict.__getitem__(self, k)

    def get(self, k, default=None):
        try:
            return self[k]
        except KeyError:
            return default

    def __setitem__(self, k, v):
        dict.__setitem__(self, k, v)
        if k in self.persistable_names:
            self.scope.set_var(k, v)

    def update(self, other=(), **kw):
        items = other.items() if hasattr(other, "items") else other
        for k, v in items:
            self[k] = v
        for k, v in kw.items():
            self[k] = v

    def clone_for_thread(self):
        return ScopeEnv(self.scope, self.persistable_names, init=self)


def _persistable_names(program):
    names = set()
    for blk in program.blocks:
        for v in blk.vars.values():
            if getattr(v, "persistable", False):
                names.add(v.name)
    return names


def lower_block(block, env, rng_key, training, aux):
    """Trace all ops of ``block`` into ``env`` (used for the main block and,
    recursively, by control-flow op lowerings for sub-blocks)."""
    from paddle_tpu import profiler as _profiler
    from paddle_tpu.obs import numerics as _numerics
    profiling = _profiler.op_profiling_enabled() and aux.get("interpret")
    probing = _numerics.probing_enabled() and aux.get("interpret")
    release = aux.get("release", {}).get(block.idx)
    rng_plan = aux.get("rng_plan")
    for i, op in enumerate(block.ops):
        if op.type in _SKIP_OPS:
            continue
        opdef = registry.resolve_lowering(op.type)
        key = None
        if rng_key is not None:
            # one counter slot per op (optimization passes leave
            # __rng_slots__ behind for ops they removed/fused, so
            # surviving RNG consumers keep their exact key positions)
            aux["rng_counter"] += op.attrs.get("__rng_slots__", 1)
            if rng_plan is None or _opmeta.needs_rng_key(op, registry):
                # under an opt-pipeline rng plan, ops statically proven
                # key-free skip the fold_in — a traced threefry
                # computation per op that XLA must carry through
                # trace/lower/DCE for nothing
                key = jax.random.fold_in(rng_key, aux["rng_counter"])
        ctx = registry.LowerContext(op, env, block, rng_key=key,
                                    training=training, aux=aux)
        if profiling:
            with _profiler.record_op(op.type, ctx):
                opdef.lower(ctx)
        else:
            # named_scope is trace-time-only: XLA carries it into every
            # emitted HLO op's metadata, so XProf traces of the COMPILED
            # step attribute device time back to IR ops (reference
            # platform/profiler.h RecordEvent — here the attribution
            # survives jit; see profiler.compiled_op_table), behind the
            # op's role and name scopes (framework.name_scope) where it
            # carries them: pt_step/bwd/enc0/self_attn/core/ptop_...
            with contextlib.ExitStack() as scopes:
                for part in _profiler.op_scope_path(op):
                    scopes.enter_context(jax.named_scope(part))
                opdef.lower(ctx)
        env.update(ctx.outputs)
        if probing:
            # per-op numerics probes (obs/numerics.py): stats of every
            # output right after the op ran, first-non-finite capture
            _numerics.record_op(op, ctx.outputs, env)
        _share_lod(op, ctx, env, aux)
        if release is not None:
            # early release (memory_optimization_transpiler.release_memory):
            # in interpret mode every intermediate otherwise lives for the
            # whole step; drop vars past their last use, like the
            # reference's delete_var ops
            stats = release.get("stats")
            for n in release["dead_after"].get(i, ()):
                v = env.pop(n, None)
                if v is not None and hasattr(v, "nbytes") \
                        and stats is not None:
                    stats["bytes"] += int(v.nbytes)
                    stats["vars"] += 1
    return env


def _share_lod(op, ctx, env, aux):
    """Default LoD propagation (reference: OpKernels call ShareLoD(X, Out)
    unless they change the row structure): outputs whose leading dim equals
    a LoD-carrying input's row count inherit that input's lod, unless the
    lowering set an explicit output lod."""
    lod_map = aux.get("lod")
    if not lod_map or not ctx.outputs:
        return
    src = None
    rows = None
    for n in op.input_arg_names:
        if n in lod_map and n in env and hasattr(env[n], "shape") \
                and env[n].ndim > 0:
            src, rows = lod_map[n], env[n].shape[0]
            break
    if src is None:
        return
    for n, v in ctx.outputs.items():
        if n not in lod_map and hasattr(v, "shape") and \
                getattr(v, "ndim", 0) > 0 and v.shape[0] == rows:
            lod_map[n] = src


class Executor:
    """Reference: ``python/paddle/fluid/executor.py:181`` +
    ``paddle/fluid/framework/executor.cc:133``."""

    def __init__(self, place=None):
        self.place = place if place is not None else (
            TPUPlace(0) if any(d.platform != "cpu" for d in jax.devices())
            else CPUPlace())
        self._cache = {}
        self._cache_capacity = jit_cache_capacity()
        self._cache_inserts = 0  # lifetime insert count (eviction-proof)
        self._run_counter = 0
        self._verified = set()  # (id(program), version) PADDLE_TPU_VERIFY memo
        self._opt_cache = {}    # (id, version, feeds, fetches) -> program
        enable_compile_cache()
        from paddle_tpu import profiler as _profiler
        _profiler.install_jax_compile_listeners()
        from paddle_tpu.obs import perf as _perf
        _perf.arm_census_from_env()

    # ------------------------------------------------------------------
    def _cache_insert(self, sig, value):
        """LRU insert bounded by PADDLE_TPU_JIT_CACHE_SIZE; evictions are
        counted (jit_cache.evictions) — a serving process churning through
        more signatures than the cache holds is recompiling, and the
        counter is how you see it."""
        from paddle_tpu import profiler as _profiler
        while len(self._cache) >= self._cache_capacity:
            self._cache.pop(next(iter(self._cache)))
            _profiler.runtime_metrics.inc("jit_cache.evictions")
        self._cache[sig] = value
        self._cache_inserts += 1

    @staticmethod
    def _compile_span(fresh, program, feed_arrays):
        """``executor.compile`` around the first call of an executable a
        jit-cache miss has just built: ``jax.jit`` traces, lowers and
        compiles inside that call, so the span says WHICH call
        recompiled and for how long.  Nothing on a cache hit."""
        if not fresh:
            return _NO_SPAN
        return _first_call(program, feed_arrays)

    # ------------------------------------------------------------------
    def run(self, program=None, feed=None, fetch_list=None, scope=None,
            return_numpy=True, use_program_cache=True, sentinel=None):
        """``sentinel``: an optional :class:`paddle_tpu.fault.Sentinel`
        guarding this step — its device-side finite/spike checks run
        before the state write-back, and a trip discards the update and
        raises :class:`~paddle_tpu.fault.NumericalFault` (buffer
        donation is disabled for guarded programs so the pre-step scope
        state survives the discard).  ``sentinel=None`` is the donating
        fast path with zero added synchronization."""
        program = program if program is not None else default_main_program()
        if not isinstance(program, Program):
            raise TypeError("executor requires a Program")
        feed = feed or {}
        fetch_list = fetch_list or []
        scope = scope if scope is not None else global_scope()

        fetch_names = [f.name if isinstance(f, framework.Variable) else f
                       for f in fetch_list]

        if _env_flag("PADDLE_TPU_VERIFY"):
            self._maybe_verify(program, feed, fetch_names)
        program = self._maybe_optimize(program, feed, fetch_names)
        block = program.global_block()

        with _span("executor.run"):
            return self._run_traced(program, block, feed, fetch_names,
                                    scope, return_numpy, sentinel=sentinel)

    # ------------------------------------------------------------------
    def _maybe_optimize(self, program, feed, fetch_names):
        """``PADDLE_TPU_OPT=1``: run the analysis/opt pass pipeline
        over the program ONCE per ``(program, version, feeds,
        fetches)`` before first compile — the executor then traces and
        compiles the optimized clone.  Memoized exactly like the jit
        cache: a cached step pays one dict lookup; mutating the program
        (``bump_version``) re-optimizes.  The input program is never
        mutated, and every pass is verify-sandwiched (a pass that
        introduces any diagnostic reverts — see analysis/opt)."""
        if not _env_flag("PADDLE_TPU_OPT"):
            return program
        if getattr(program, "_opt_report", None) is not None:
            return program  # already an optimized clone (direct call)
        key = (id(program), program._version, tuple(sorted(feed or ())),
               tuple(fetch_names))
        # a clone remembers WHOSE it is: ``id()`` is reused once a program
        # is collected, and two short-lived programs with the same feeds
        # and fetches (the two load programs of a generation bundle) can
        # follow each other at one address
        cached = self._opt_cache.get(key)
        if cached is not None and cached._opt_source() is program:
            return cached
        from paddle_tpu.analysis.opt import optimize_program
        optimized, report = optimize_program(
            program, feed_names=tuple(feed or ()),
            fetch_names=tuple(fetch_names))
        logger.debug("PADDLE_TPU_OPT: %r", report)
        if getattr(program, "_release_memory", False):
            # the interpret-mode early-release plan keys op indices —
            # rebuild it against the optimized op list
            from paddle_tpu.memory_optimization_transpiler import \
                release_memory
            release_memory(optimized)
        if len(self._opt_cache) > 256:  # id()-reuse bound, not a cache
            self._opt_cache.clear()
        optimized._opt_source = weakref.ref(program)
        self._opt_cache[key] = optimized
        return optimized

    # ------------------------------------------------------------------
    def _maybe_verify(self, program, feed, fetch_names):
        """PADDLE_TPU_VERIFY=1: run the structural verifier
        (paddle_tpu.analysis) BEFORE first compile, so an ill-formed
        program fails with named vars/ops instead of a deep trace
        error.  Memoized per (program, version): a cached step pays one
        set lookup (<5% guard in tests/test_analysis.py), and mutating
        the program (bump_version) re-verifies."""
        key = (id(program), program._version)
        if key in self._verified:
            return
        from paddle_tpu import analysis
        analysis.verify_program(program, feed_names=tuple(feed),
                                fetch_names=tuple(fetch_names),
                                where="executor.run")
        if len(self._verified) > 4096:  # id() reuse bound, not a cache
            self._verified.clear()
        self._verified.add(key)

    def _run_traced(self, program, block, feed, fetch_names, scope,
                    return_numpy, sentinel=None):
        """Body of :meth:`run`, phase-annotated: ``executor.feed``
        (host->device conversion + reader pre-pass), ``executor.dispatch``
        (``executor.lookup`` the signature and the call's dispatch record,
        ``executor.state`` the state the record holds, looked up in the
        scope where it holds none, on a mesh ``executor.place``,
        ``executor.launch`` the XLA launch), ``executor.fetch`` (the new
        state adopted by the record and written back + host conversion) —
        the spans that answer "where did step N spend its time"."""
        from paddle_tpu.obs import perf as _perf
        phases = _perf.step_phases_enabled()
        feed_arrays = {}
        device = self._feed_device()
        t_feed = time.perf_counter()
        with _span("executor.feed"):
            for name, value in feed.items():
                var = block.var(name) if block.has_var(name) else None
                lod = None
                if isinstance(value, tuple) and len(value) == 2 and \
                        isinstance(value[1], (list, tuple)):
                    value, lod = value
                dtype = var.dtype if var is not None else None
                _enforce_feed(name, value, var)
                if lod is not None and len(lod) == 1 and \
                        _lod_buckets_enabled(program):
                    # bucketed ragged mode (lod.py): pad rows to a bucket
                    # and feed the row-splits as data, so the jit key is
                    # the bucket, not the exact lod
                    from paddle_tpu.lod import (bucket_ragged_feed,
                                                SPLITS_SUFFIX)
                    value, splits, meta = bucket_ragged_feed(
                        name, np.asarray(value), lod)
                    feed_arrays[name] = self._feed_array(value, dtype)
                    feed_arrays[name + SPLITS_SUFFIX] = self._feed_array(
                        splits, "int32")
                    scope.set_lod(name, meta)
                    continue
                feed_arrays[name] = self._feed_array(value, dtype)
                # a dense feed must also CLEAR any stale lod from a
                # previous ragged feed of the same variable
                scope.set_lod(name, lod)

            _run_reader_ops(block, scope, feed_arrays, device)
        feed_dt = time.perf_counter() - t_feed

        with contextlib.ExitStack() as held:
            with _span("executor.dispatch") as dsp:
                with _span("executor.lookup") as looked:
                    record = self._get_compiled(program, block, feed_arrays,
                                                tuple(fetch_names), scope,
                                                donate=sentinel is None)
                    looked.set(record="miss" if record.fresh else "hit")
                held.enter_context(record.calling())
                with _span("executor.state") as gathered:
                    ro, inout = record.resolve(gathered)

                self._run_counter += 1
                key = _step_key((program.random_seed or 0) * 1000003
                                + self._run_counter)

                t0 = time.perf_counter()
                fresh, record.fresh = record.fresh, False
                with self._compile_span(fresh, program, feed_arrays):
                    fetches, new_state = record.call(feed_arrays, ro, inout,
                                                     key)
                if record.donated:
                    # the donated arrays die where ``adopt`` replaces them,
                    # under the device's step, and not with this frame,
                    # after the host conversion has waited for the device
                    # (918 arrays of 4 shards: 4 ms a dp4 step with the
                    # chips dark)
                    inout = None
                dsp.set(fetches=len(fetch_names))
            dt = time.perf_counter() - t0
            from paddle_tpu import profiler as _profiler
            _profiler.runtime_metrics.observe("executor.step_seconds", dt)
            perf_record = record.perf["record"] if record.perf else None
            _perf.census_tick(scope)
            with _span("executor.fetch"):
                if sentinel is not None:
                    # the guard runs BEFORE write-back: a NumericalFault here
                    # leaves the scope and the record holding the (undonated)
                    # pre-step state — the skip-step rung of the escalation
                    # ladder
                    ro_state = dict(zip(record.ro_names, ro))
                    inout_state = dict(zip(record.inout_names, inout))
                    fetches, new_state = sentinel.after_step(
                        fetch_names, fetches, new_state,
                        repro=lambda: self._repro_payload(
                            program, feed_arrays, ro_state, inout_state,
                            fetch_names),
                        # for the fused health norms: the pre-step state
                        # (valid: guarded steps never donate) and which of
                        # its names are Parameters
                        prev_state=inout_state,
                        param_names=record.param_names)
                if _check_nan_inf_enabled(program):
                    _check_nan_inf(fetch_names, fetches, new_state)
                if phases:
                    # profile-step mode only: one explicit sync separates
                    # "device still computing" from host-side conversion
                    tw = time.perf_counter()
                    for v in list(fetches) + list(new_state.values()):
                        if hasattr(v, "block_until_ready"):
                            try:
                                v.block_until_ready()
                            except Exception:
                                pass
                    t_fetch = time.perf_counter()
                    _profiler.runtime_metrics.observe(
                        "perf.step.device_wait_seconds", t_fetch - tw)
                names = [n for n in record.written if n in new_state]
                record.adopt(names, [new_state[n] for n in names])
                held.close()    # the host conversion waits for the device
                result = [np.asarray(v) for v in fetches] if return_numpy \
                    else list(fetches)
                gauge = _mfu_gauge_for(program)
                if return_numpy and perf_record is not None and gauge:
                    # live MFU over the WHOLE step (feed staging -> fetch
                    # materialization): the numpy conversion above BLOCKED
                    # on the device, so this is an honest bench-style wall
                    # time (host feed/fetch overhead included, same as the
                    # analytical MFU bench.py reports).  The
                    # return_numpy=False path hands back async arrays — its
                    # submit time would overstate MFU by the async-dispatch
                    # factor, so no gauge from it.
                    _perf.note_step(perf_record, time.perf_counter() - t_feed,
                                    gauge=gauge,
                                    devices=getattr(self, "device_count", 1))
                if phases:
                    _profiler.runtime_metrics.observe(
                        "perf.step.feed_seconds", feed_dt)
                    _profiler.runtime_metrics.observe(
                        "perf.step.dispatch_seconds", dt)
                    _profiler.runtime_metrics.observe(
                        "perf.step.fetch_seconds",
                        time.perf_counter() - t_fetch)
                return result

    # ------------------------------------------------------------------
    def _repro_payload(self, program, feed_arrays, ro_state, inout_state,
                       fetch_names):
        """Self-contained replay payload for a sentinel quarantine
        bundle: the program, PRE-step state, the batch, and the RNG
        coordinates needed to re-execute this exact step offline
        (``paddle_tpu replay``).  Built lazily — only on a trip."""
        state = {}
        for src in (ro_state, inout_state):
            for n, v in src.items():
                state[n] = np.asarray(v)
        return {"program": program.to_dict(),
                "random_seed": program.random_seed,
                "run_counter": self._run_counter,
                "feed": {n: np.asarray(v)
                         for n, v in feed_arrays.items()},
                "state": state,
                "fetch_names": list(fetch_names)}

    # ------------------------------------------------------------------
    def warmup(self, program=None, feed_shapes=None, fetch_list=None,
               scope=None, allow_state_updates=False):
        """AOT warmup: trace + lower + compile ``program`` for each
        declared feed signature BEFORE real traffic arrives, so the first
        real request pays zero compile time.

        ``feed_shapes``: a dict ``name -> concrete shape`` (one
        signature), or a list of such dicts (one per serving bucket).
        Every listed dim must be concrete — warmup exists to pin exact
        signatures.  Dtypes come from the program's variables.  Each
        signature is executed once on zero-filled feeds, which lands the
        executable in this executor's jit cache and — when
        PADDLE_TPU_COMPILE_CACHE is set — in the persistent XLA cache,
        where a restarted process finds it again.

        Warmup EXECUTES the program, so a program that writes persistable
        state (a training step: parameters, optimizer moments) would be
        mutated by zero-filled feeds — that is refused unless
        ``allow_state_updates`` opts in: ``True`` allows every state
        write, or an iterable of variable names allows exactly those
        (the generation decode step declares its KV-cache tensors this
        way — cache writes are intended, parameter writes still refuse).

        Returns a :class:`paddle_tpu.obs.perf.WarmupReport` — an ``int``
        equal to the number of signatures that were freshly compiled
        (0 = everything was already warm; existing callers keep
        working), whose ``buckets`` list carries one entry per declared
        signature: wall seconds, fresh-compile count, and whether the
        executable came ``"warm"`` (already in the jit LRU),
        ``"persistent-hit"`` (loaded from the PADDLE_TPU_COMPILE_CACHE
        dir), or ``"cold"`` (backend-compiled).  A rolling restart's
        "warm via compile cache" claim is checkable per bucket from a
        replica's ``/stats`` instead of inferred from global counters."""
        program = program if program is not None else default_main_program()
        specs = feed_shapes if isinstance(feed_shapes, (list, tuple)) \
            else [feed_shapes or {}]
        block = program.global_block()
        if allow_state_updates is not True:
            allowed = set(allow_state_updates or ())
            written = [n for op in block.ops if op.type not in _SKIP_OPS
                       for n in op.output_arg_names
                       if n not in allowed and block.has_var(n) and
                       block.var(n).persistable]
            if written:
                raise ValueError(
                    f"warmup would EXECUTE this program, mutating "
                    f"persistable state ({sorted(set(written))[:3]}...) "
                    f"with zero-filled feeds — warm an inference program "
                    f"instead, or pass allow_state_updates=True if the "
                    f"state writes are intended")
        # count INSERTS, not the cache-size delta: a full LRU evicting
        # during warmup would otherwise report 0 (or negative) compiles
        before = self._cache_inserts
        from paddle_tpu import profiler as _profiler
        from paddle_tpu.obs.perf import WarmupReport
        buckets = []
        with _profiler.record_latency("executor.warmup_seconds"):
            for spec in specs:
                feed = {}
                for name, shape in spec.items():
                    if shape is None or any(
                            d is None or int(d) < 0 for d in shape):
                        raise ValueError(
                            f"warmup feed {name!r} needs a concrete "
                            f"shape, got {shape}")
                    var = block.var(name) if block.has_var(name) else None
                    dtype = (var.dtype if var is not None
                             and var.dtype is not None else "float32")
                    from paddle_tpu.io import synth_feed_value
                    feed[name] = synth_feed_value(shape, dtype)
                ins0 = self._cache_inserts
                hits0 = _profiler.runtime_metrics.counter(
                    "compile_cache.hits")
                t0 = time.perf_counter()
                self.run(program=program, feed=feed, fetch_list=fetch_list,
                         scope=scope)
                fresh = self._cache_inserts - ins0
                hit = _profiler.runtime_metrics.counter(
                    "compile_cache.hits") - hits0
                buckets.append({
                    "signature": {n: list(map(int, s))
                                  for n, s in spec.items()},
                    "compiles": fresh,
                    "seconds": time.perf_counter() - t0,
                    # per-bucket provenance of the executable: how a
                    # rolling restart proves "warm via compile cache"
                    "cache": ("warm" if fresh == 0 else
                              "persistent-hit" if hit > 0 else "cold"),
                })
        compiled = self._cache_inserts - before
        _profiler.runtime_metrics.inc("warmup.signatures", len(specs))
        _profiler.runtime_metrics.inc("warmup.compiles", compiled)
        return WarmupReport(compiled, buckets)

    # ------------------------------------------------------------------
    def run_steps(self, program=None, feed=None, fetch_list=None, steps=1,
                  scope=None, return_numpy=True):
        """Run ``steps`` iterations of ``program`` in ONE device dispatch.

        The training loop runs ON the device (``lax.scan`` over the step
        function with the state donated as the carry), so host<->device
        latency is paid once per call instead of once per step — the TPU
        analog of the reference's double-buffered reader pipeline
        (``operators/reader/create_double_buffer_reader_op.cc``) which
        exists to hide exactly this latency on GPU.

        ``feed`` values may be either one batch (reused every step) or
        stacked ``[steps, ...]`` arrays (leading axis = step axis, sliced
        per step in-graph).  Fetches come back stacked ``[steps, ...]``.

        A call computes its signature first (feed shapes and dtypes, the
        fetch list, ``steps``, the program's version) and dispatches from
        the record the jit cache holds under it: the program is
        classified (``_prepare``) by the call that misses, and the carry
        is the previous call's own output until someone else writes the
        scope.
        """
        program = program if program is not None else default_main_program()
        if not isinstance(program, Program):
            raise TypeError("executor requires a Program")
        feed = feed or {}
        fetch_list = fetch_list or []
        scope = scope if scope is not None else global_scope()
        steps = int(steps)

        fetch_names = [f.name if isinstance(f, framework.Variable) else f
                       for f in fetch_list]

        if _env_flag("PADDLE_TPU_VERIFY"):
            self._maybe_verify(program, feed, fetch_names)
        program = self._maybe_optimize(program, feed, fetch_names)
        block = program.global_block()

        with _span("executor.run_steps", steps=steps):
            return self._run_steps_traced(program, block, feed,
                                          fetch_names, steps, scope,
                                          return_numpy)

    def _run_steps_traced(self, program, block, feed, fetch_names, steps,
                          scope, return_numpy):
        """Body of :meth:`run_steps` in the three phases :meth:`run` has,
        under the same span names: ``executor.feed`` (staging the window's
        batches), ``executor.dispatch`` (``executor.lookup``: the
        signature, from shapes alone, FIRST, then the jit cache's dispatch
        record, classified on a miss; ``executor.state``: the carry the
        record holds, looked up in the scope where it holds none;
        ``executor.launch``: the one call) and ``executor.fetch`` (the
        final carry adopted and written back, and the host conversion,
        which blocks until the device is done)."""
        with _span("executor.feed"):
            device = self._feed_device()
            per_step_feed = {}
            const_feed = {}

            def is_lod_pair(v):
                return isinstance(v, tuple) and len(v) == 2 and \
                    isinstance(v[1], (list, tuple))

            for name, value in feed.items():
                if isinstance(value, list) and value and \
                        all(is_lod_pair(v) for v in value):
                    # per-step ragged batches: bucketed mode pads the whole
                    # window to ONE bucket signature and threads the
                    # row-splits through the device-side loop as data — the
                    # streaming-LoD counterpart of the stacked dense feed
                    if not _lod_buckets_enabled(program):
                        raise ValueError(
                            f"run_steps got per-step LoD feeds for {name!r}; "
                            f"enable bucketed mode (program.lod_buckets = "
                            f"True) so the window shares one executable")
                    if len(value) != steps:
                        raise ValueError(
                            f"run_steps: {name!r} has {len(value)} ragged "
                            f"batches for {steps} steps")
                    from paddle_tpu.lod import (bucket_ragged_feed,
                                                next_bucket, SPLITS_SUFFIX)
                    var = block.var(name) if block.has_var(name) else None
                    dtype = var.dtype if var is not None else None
                    rows = [np.asarray(v[0]).shape[0] for v in value]
                    mls = []
                    n_seqs = set()
                    for _, lod in value:
                        sp = np.asarray(lod[-1], np.int64)
                        lens = sp[1:] - sp[:-1]
                        mls.append(int(lens.max()) if len(lens) else 0)
                        n_seqs.add(len(sp) - 1)
                    if len(n_seqs) != 1:
                        raise ValueError(
                            f"run_steps: {name!r} batches disagree on "
                            f"sequence count {sorted(n_seqs)}")
                    nb = next_bucket(max(max(rows), 1))
                    tb = next_bucket(max(max(mls), 1))
                    padded_steps, splits_steps = [], []
                    meta = None
                    for v, lod in value:
                        padded, splits, meta = bucket_ragged_feed(
                            name, np.asarray(v), lod, n_bucket=nb,
                            t_bucket=tb)
                        padded_steps.append(padded)
                        splits_steps.append(splits)
                    per_step_feed[name] = _as_device_array(
                        np.stack(padded_steps), dtype, device)
                    per_step_feed[name + SPLITS_SUFFIX] = _as_device_array(
                        np.stack(splits_steps), "int32", device)
                    scope.set_lod(name, meta)
                    continue
                if is_lod_pair(value):
                    raise ValueError(
                        f"run_steps does not support a single LoD feed (got "
                        f"one for {name!r}); pass a LIST of per-step "
                        f"(value, lod) batches under program.lod_buckets, "
                        f"or bucket/pad ragged batches and use run()")
                var = block.var(name) if block.has_var(name) else None
                dtype = var.dtype if var is not None else None
                arr = _as_device_array(value, dtype, device)
                want_shape = tuple(var.shape) \
                    if var is not None and var.shape is not None else None
                # an array with exactly one extra leading dim of length `steps`
                # is treated as stacked per-step batches (documented behavior;
                # reshape away any coincidental match)
                if want_shape is not None and arr.ndim == len(want_shape) + 1 \
                        and arr.shape[0] == steps:
                    per_step_feed[name] = arr        # stacked [steps, ...]
                else:
                    const_feed[name] = arr           # one batch, reused
                scope.set_lod(name, None)

            # reader ops: pull `steps` batches and ride the per-step axis of
            # the device-side loop (double-buffer + scan = the full pipeline)
            reader_feed = {}
            _run_reader_ops(block, scope, reader_feed, device, steps=steps)
            per_step_feed.update(reader_feed)

        with contextlib.ExitStack() as held:
            with _span("executor.dispatch"):
                with _span("executor.lookup") as looked:
                    # one step's feeds, as shapes: the signature, the
                    # classification and the carry's shapes read no more
                    sample = dict(const_feed)
                    sample.update(
                        {n: jax.ShapeDtypeStruct(a.shape[1:], a.dtype)
                         for n, a in per_step_feed.items()})
                    record = self._scan_record(
                        program, block, sample, tuple(fetch_names), scope,
                        steps, per_step_feed or const_feed,
                        tuple(sorted(per_step_feed)))
                    looked.set(record="miss" if record.fresh else "hit")
                held.enter_context(record.calling())
                with _span("executor.state") as gathered:
                    ro, carry = record.resolve(gathered)

                self._run_counter += 1
                base_key = jax.random.PRNGKey(
                    (program.random_seed or 0) * 1000003 + self._run_counter)

                if record.interpret:
                    # host ops: plain Python loop (still correct, just not
                    # fused)
                    return self._interpret_steps(
                        record, const_feed, per_step_feed, ro, carry,
                        base_key, steps, len(fetch_names), return_numpy)

                t0 = time.perf_counter()
                fresh, record.fresh = record.fresh, False
                with self._compile_span(fresh, program, sample):
                    ys, final = record.call(const_feed, ro, carry, base_key,
                                            per_step_feed)
                carry = None    # donated: dies in ``adopt``, as in ``run``
            with _span("executor.fetch"):
                record.adopt(record.carry_names,
                             [final[n] for n in record.carry_names])
                held.close()    # the host conversion waits for the device
                result = [np.asarray(v) for v in ys] if return_numpy \
                    else list(ys)
        from paddle_tpu.obs import perf as _perf
        gauge = _mfu_gauge_for(program)
        if return_numpy and gauge:
            # MFU over the whole on-device window: XLA's cost analysis
            # counts the scan BODY once regardless of trip count, so
            # the captured FLOPs scale by `steps`; ONLY the numpy
            # conversion above blocks on the device, so only this path
            # yields an honest window wall time (async submit time
            # would overstate MFU by orders of magnitude)
            _perf.note_step(record.perf["record"] if record.perf else None,
                            time.perf_counter() - t0,
                            gauge=gauge,
                            devices=getattr(self, "device_count", 1),
                            flops_scale=steps)
        _perf.census_tick(scope)
        return result

    @staticmethod
    def _interpret_steps(record, const_feed, per_step_feed, ro, inout,
                         base_key, steps, n_fetches, return_numpy):
        """``run_steps`` over a program that has to be interpreted: the
        steps one by one, the state written back after the last."""
        keys = jax.random.split(base_key, steps)
        ro_state = dict(zip(record.ro_names, ro))
        inout_state = dict(zip(record.inout_names, inout))
        outs = []
        for i in range(steps):
            feeds_i = dict(const_feed)
            feeds_i.update({n: a[i] for n, a in per_step_feed.items()})
            fetches, new_state = record.step(feeds_i, ro_state, inout_state,
                                             keys[i])
            inout_state = dict(inout_state)
            inout_state.update(new_state)
            outs.append(fetches)
        record.adopt(tuple(inout_state), tuple(inout_state.values()))
        stacked = [jnp.stack([o[i] for o in outs]) for i in range(n_fetches)]
        return [np.asarray(v) for v in stacked] if return_numpy else stacked

    # ------------------------------------------------------------------
    def _scan_record(self, program, block, sample, fetch_names, scope, steps,
                     label_feeds, per_step_names):
        """The dispatch record of ``steps`` steps of ``program`` in one
        scan: the signature first (cheap: shapes, dtypes and names),
        ``_prepare`` on a jit-cache miss alone."""
        def jit(parts):
            return _captured(self._scan_fn(parts["step"], steps), label_feeds,
                             fetch_names, tag=f"scan{steps}"), None

        sig = self._signature(program, block, sample, fetch_names, scope) \
            + ("run_steps", steps, per_step_names)
        return self._record(sig, program, block, sample, fetch_names, scope,
                            jit, scan_sample=sample)

    @staticmethod
    def _scan_fn(step, steps):
        """The jitted ``steps``-step scan over ``step``, the carry
        donated.  (The executable's instructions carry this function's
        name, ``..._scan_fn_..._multi``: a traced cell reads them.)"""
        def multi(const_feeds, per_feeds, ro_state, carry, base_key):
            keys = jax.random.split(base_key, steps)

            def body(carry, xs):
                key, step_feeds = xs
                feeds = dict(const_feeds)
                feeds.update(step_feeds)
                fetches, new_state = step(feeds, ro_state, carry, key)
                new_carry = {n: new_state.get(n, carry[n])
                             for n in carry}
                return new_carry, tuple(fetches)

            carry, ys = jax.lax.scan(body, carry, (keys, per_feeds))
            return ys, carry

        return jax.jit(multi, donate_argnums=(3,))

    # ------------------------------------------------------------------
    def run_pipeline(self, program=None, pipeline=None, fetch_list=None,
                     scope=None, max_steps=None, return_numpy=True,
                     on_step=None, sentinel=None, ledger=None):
        """Drive one epoch (or ``max_steps`` batches) of a
        ``datapipe`` pipeline through :meth:`run`.

        Each batch must be a feed dict (``name -> array``) — the shape a
        ``Batch`` stage with dict samples (or a custom collate) emits;
        batches already placed by a ``DevicePrefetch`` stage skip the
        host->device copy inside :meth:`run`.  Fires the ``train.step``
        failpoint per batch (so ``PADDLE_TPU_CHAOS`` kill drills target
        this loop) and records ``datapipe.step_seconds``.  Stopping at
        ``max_steps`` closes the iterator cleanly: threaded stages
        quiesce with their position intact, so a following
        ``pipeline.state_dict()`` checkpoints mid-epoch.

        ``on_step(step_index, fetches)`` runs after each batch (metrics,
        checkpointing).  Returns the list of per-batch fetch lists.

        ``sentinel``: a :class:`paddle_tpu.fault.Sentinel` turns this
        loop into the automatic recovery loop — a tripped check skips
        the poisoned update, quarantines the batch as a repro bundle,
        and after K strikes rolls back to the sentinel's last
        known-good checkpoint (which also rewinds the pipeline's
        iterator position) and resumes.  Skipped steps never appear in
        the returned fetch lists, and a rollback also drops the entries
        it rewound (their batches re-run and re-append), so each
        applied batch appears exactly once.

        ``ledger``: a :class:`paddle_tpu.obs.ledger.RunLedger` appends
        one step row per APPLIED batch (skipped/poisoned steps write no
        row), BEFORE ``on_step`` runs — so a checkpoint committed by
        ``on_step`` carries a sidecar whose ``rows_total`` includes its
        own step, the exactly-once resume invariant.  When omitted, the
        sentinel's checkpoint manager's ``ledger`` attribute (if any)
        is used, so wiring the ledger into the manager arms the whole
        loop.  Disabled path is a single ``None`` check per step."""
        from paddle_tpu import profiler as _profiler
        from paddle_tpu.fault import chaos as _chaos
        from paddle_tpu.fault.sentinel import NumericalFault
        if pipeline is None:
            raise ValueError("run_pipeline requires a datapipe pipeline")
        outs = []
        # checkpoint step -> len(outs) when the manager committed it,
        # keyed by the step number the checkpoint was SAVED under (which
        # need not match this loop's 0-based index — a resumed trainer
        # may number globally); observed via the manager's in-process
        # last_committed_step after each on_step so the rollback branch
        # can truncate exactly.  NOT latest_step(): that lists the
        # directory (per-step I/O), and a restarted trainer renumbering
        # from 0 under a directory still holding a prior run's higher
        # ckpt-N would never see its own commits through it
        marks = {}
        mgr = sentinel.manager if sentinel is not None else None
        last_ckpt = getattr(mgr, "last_committed_step", None) \
            if mgr is not None else None
        if ledger is None and mgr is not None:
            ledger = getattr(mgr, "ledger", None)
        fetch_name_list = [v.name if hasattr(v, "name") else str(v)
                           for v in (fetch_list or [])]
        it = iter(pipeline)
        try:
            step = 0
            # check the budget BEFORE pulling: a batch pulled past the
            # limit would be dropped (lost from the resume sequence)
            while max_steps is None or step < max_steps:
                t0 = time.perf_counter()
                try:
                    batch = next(it)
                except StopIteration:
                    break
                stall = time.perf_counter() - t0
                # recorded only on success: a normal epoch-end
                # StopIteration is not an error-tagged span
                _record_span("datapipe.next", t0, stall, step=step)
                _chaos.fire("train.step", step=step)
                try:
                    with _span("train.step", step=step):
                        with _profiler.record_latency(
                                "datapipe.step_seconds"):
                            # program by KEYWORD: ParallelExecutor.run's
                            # first positional is fetch_list, not program
                            fetches = self.run(program=program, feed=batch,
                                               fetch_list=fetch_list,
                                               scope=scope,
                                               return_numpy=return_numpy,
                                               sentinel=sentinel)
                        if ledger is not None:
                            ledger.note_step(fetch_names=fetch_name_list,
                                             fetches=fetches,
                                             stall_seconds=stall)
                        if on_step is not None:
                            on_step(step, fetches)
                except NumericalFault as fault:
                    if sentinel is None:
                        raise
                    restored = sentinel.handle_fault(fault, step=step)
                    if restored is not None:
                        mgr = sentinel.manager
                        if getattr(mgr, "last_restore_rewound", False) \
                                and hasattr(pipeline, "load_state_dict"):
                            # the rollback rewound the pipeline's
                            # position; the open iterator still points
                            # at the pre-rollback stream — reopen from
                            # the restored state
                            close = getattr(it, "close", None)
                            if close is not None:
                                close()
                            it = iter(pipeline)
                            # drop the entries the rollback undid:
                            # their batches re-run from the rewound
                            # stream, keeping the returned list
                            # exactly-once.  The mark maps the restored
                            # checkpoint number back to this loop's own
                            # outs length; a checkpoint this loop never
                            # committed (restart resuming a prior run's
                            # ckpt) rewinds past everything we returned
                            del outs[marks.get(restored, 0):]
                        else:
                            # params-only rollback: no datapipe on the
                            # manager, or the restored checkpoint
                            # carried no iterator state — the stream
                            # cannot be rewound.  Keep consuming the
                            # current iterator (reopening would restart
                            # the epoch) and say what was lost
                            logger.warning(
                                "sentinel rollback restored step %s "
                                "params-only (no datapipe state to "
                                "rewind): batches since that step "
                                "cannot be replayed — attach datapipe= "
                                "to CheckpointManager for exact-once "
                                "semantics", restored)
                    step += 1
                    continue
                outs.append(fetches)
                if mgr is not None and on_step is not None:
                    # did on_step commit a checkpoint this step?  Its
                    # saved position is AFTER this batch, so the mark
                    # includes the entry just appended
                    ckpt = getattr(mgr, "last_committed_step", None)
                    if ckpt is not None and ckpt != last_ckpt:
                        marks[ckpt] = len(outs)
                        last_ckpt = ckpt
                step += 1
        finally:
            close = getattr(it, "close", None)  # plain iterables lack it
            if close is not None:
                close()
        return outs

    # ------------------------------------------------------------------
    def compiled_step(self, program, feed_names, fetch_list, scope):
        """The :class:`CompiledStep` of ``program`` fed ``feed_names`` and
        fetching ``fetch_list`` over ``scope``: the step a caller launches
        itself, every few milliseconds, without ``run``'s per-call lookup
        and state walk.  ``PADDLE_TPU_VERIFY`` / ``PADDLE_TPU_OPT`` apply
        once, here.  A program that has to be interpreted (a host op, op
        profiling) has no such step and raises."""
        fetch_names = tuple(f.name if isinstance(f, framework.Variable)
                            else f for f in fetch_list)
        feed = dict.fromkeys(feed_names)
        if _env_flag("PADDLE_TPU_VERIFY"):
            self._maybe_verify(program, feed, fetch_names)
        program = self._maybe_optimize(program, feed, fetch_names)
        parts = self._classify(program, program.global_block(), feed,
                               fetch_names, scope)
        if parts["interpret"]:
            raise NotImplementedError(
                "compiled_step: the program has to be interpreted op by "
                "op (a host op, or op profiling is on); run it through "
                "Executor.run")
        return CompiledStep(_DispatchRecord(self, scope, parts), program)

    # ------------------------------------------------------------------
    def _feed_device(self):
        """Target placement for feed arrays; ParallelExecutor overrides to
        None so sharded placement happens against the mesh instead."""
        return self.place.jax_device()

    def _feed_array(self, value, dtype):
        """One of ``run``'s feeds as the step takes it: on the executor's
        device.  (``ParallelExecutor`` leaves host memory on the host for
        ``executor.place``.)"""
        return _as_device_array(value, dtype, self._feed_device())

    # ------------------------------------------------------------------
    def _state_value(self, scope, name, device, by=None):
        """``name``'s value in the scope, a host array put on ``device``
        and written back (``by``: the watcher that asks, ``Scope.watch``)."""
        v = scope.find_var(name)
        if v is None:
            raise RuntimeError(
                f"variable {name!r} is not initialized in the scope — "
                f"run the startup program first")
        if isinstance(v, np.ndarray):
            # commit to the target device: mixed committed/uncommitted
            # arguments would give the same computation two jit signatures
            # (one extra compile on the second call)
            v = jax.device_put(jnp.asarray(v), device) if device is not None \
                else jnp.asarray(v)
            scope.set_var(name, v, by=by)
        return v

    # ------------------------------------------------------------------
    def _signature(self, program, block, feed_arrays, fetch_names, scope):
        """Cheap cache key — no per-op work, safe to compute every step.

        LoD (ragged row-splits) is static trace-time metadata on TPU: a
        distinct lod means a distinct compiled executable (bucket batches
        upstream to bound recompiles; reference carries LoD on the tensor,
        lod_tensor.h:110).
        """
        feed_lods = tuple(sorted(
            (n, _freeze_lod(scope.find_lod(n))) for n in feed_arrays
            if scope.find_lod(n) is not None))
        from paddle_tpu import profiler as _profiler
        from paddle_tpu.obs import numerics as _numerics
        return (id(program), program._version, block.idx, _amp_enabled(program),
                id(scope),  # interpret-mode steps bind the scope (ScopeEnv)
                _profiler.op_profiling_enabled(),  # forces interpret mode
                _numerics.probing_enabled(),  # forces interpret mode
                bool(getattr(program, "_release_memory", False)),
                tuple(sorted((n, str(a.dtype), a.shape)
                             for n, a in feed_arrays.items())),
                feed_lods,
                tuple(fetch_names))

    # ------------------------------------------------------------------
    def _interprets(self, program, block):
        """Whether ``block`` has to run op by op, eagerly: a host op, op
        profiling, numerics probing, or a run-once initializer."""
        from paddle_tpu import profiler as _profiler
        from paddle_tpu.obs import numerics as _numerics
        interpret = _has_host_ops(
            block, dyn=_lod_buckets_enabled(program))
        if interpret and not getattr(program, "expect_host_ops", False):
            _warn_host_op_cliff(program, block)
        # the opt pipeline's compile-amortization gate: a run-once
        # initializer whose static cost proves the XLA compile can
        # never pay for itself executes op-by-op eagerly instead
        # (34-51% of the zoo's measured cold start; JAX PRNG is
        # deterministic across eager and compiled, so init values are
        # unchanged)
        return bool(interpret or _profiler.op_profiling_enabled()
                    or _numerics.probing_enabled()
                    or getattr(program, "_opt_interpret", False))

    def _step_aux(self):
        """What this executor adds to the ``aux`` every lowering sees
        (``ParallelExecutor``: its mesh and batch axis)."""
        return {}

    def _prepare(self, program, block, feed_arrays, fetch_names, scope):
        """Classify block variables and build the traceable step function:
        THE classification of a program, for every executor and every way
        to call one.

        Returns a dict with the (untraced) ``step`` callable, the
        state-name partitions, and the interpret flag.  Of ``feed_arrays``
        only the names (and their lods in the scope) are read: shapes do.
        O(#ops): ``run``, ``run_steps`` and the mesh path compute the
        cheap signature first and come here on a jit-cache miss alone
        (``_classify``); what it returns lives in the miss's dispatch
        record.
        """
        feed_names = tuple(sorted(feed_arrays))

        # classify non-feed external inputs (state) and written persistables
        produced = set(feed_names)
        reads = []
        writes = []
        for op in block.ops:
            if op.type in _SKIP_OPS:
                continue
            for n in op.input_arg_names:
                if n and n not in produced:
                    reads.append(n)
            for n in op.output_arg_names:
                if n:
                    produced.add(n)
                    writes.append(n)
        # also: sub-block reads of outer vars.  Conservatively include any
        # var referenced by sub-blocks of ops in this block.
        for op in block.ops:
            for a in op.attrs.values():
                if isinstance(a, framework.Block):
                    for n in _external_reads(a, produced):
                        reads.append(n)

        state_names = []
        seen = set()
        for n in reads:
            if n not in seen and n not in feed_names:
                seen.add(n)
                state_names.append(n)

        written_state = []
        for n in writes:
            try:
                var = block.var(n)
            except KeyError:
                continue
            if var.persistable and n not in written_state:
                written_state.append(n)
        # fetched non-persistable vars that are never produced in this block
        # (e.g. fetching a param) are state reads handled below.
        for n in fetch_names:
            if n not in produced and n not in state_names and \
                    n not in feed_names:
                state_names.append(n)

        inout_names = tuple(n for n in state_names if n in written_state)
        ro_names = tuple(n for n in state_names if n not in written_state)
        # persistables written but never read still need write-back
        create_state = tuple(n for n in written_state if n not in inout_names)

        training = not program._is_inference
        interpret = self._interprets(program, block)

        from paddle_tpu.lod import DynLoD, SPLITS_SUFFIX
        lod_map = {}
        for n in feed_arrays:
            lod = scope.find_lod(n)
            if lod is None:
                continue
            if isinstance(lod, tuple) and lod and lod[0] == "dyn":
                lod_map[n] = DynLoD(n + SPLITS_SUFFIX, lod[1], lod[2])
            else:
                lod_map[n] = [list(level) for level in lod]

        amp = _amp_enabled(program)

        persist_names = _persistable_names(program) if interpret else None

        # interpret-mode early release per the memory plan (the compiled
        # path needs none of this: XLA buffer assignment frees dead values)
        release_map = None
        if interpret and getattr(program, "_release_memory", False):
            plan = getattr(program, "_memory_plan", None)
            if plan is not None and block.idx in plan.last_use:
                protect = set(fetch_names) | set(inout_names) | \
                    set(create_state) | set(persist_names or ())
                dead_after = {}
                for name, idx in plan.last_use[block.idx].items():
                    if name not in protect:
                        dead_after.setdefault(idx, []).append(name)
                stats = {"bytes": 0, "vars": 0}
                program._release_stats = stats  # measured drop, per run
                release_map = {block.idx: {"dead_after": dead_after,
                                           "stats": stats}}

        def step(feeds, ro_state, inout_state, rng_key):
            if interpret:
                # shared-scope semantics for persistables (CSP threads)
                env = ScopeEnv(scope, persist_names)
            else:
                env = {}
            env.update(feeds)
            env.update(ro_state)
            env.update(inout_state)
            aux = {"rng_counter": 0, "scope": scope,
                   "lower_block": lower_block, "lod": dict(lod_map),
                   "amp": amp, "interpret": interpret, "block": block,
                   # set only by the opt pipeline: ops statically
                   # proven key-free skip their per-op fold_in
                   "rng_plan": True
                   if getattr(program, "_opt_rng_plan", False)
                   else None, **self._step_aux()}
            if release_map is not None:
                stats = release_map[block.idx]["stats"]
                stats["bytes"] = stats["vars"] = 0  # per-run measurement
                aux["release"] = release_map
            # whole-step scope: every emitted HLO op (including scan/
            # slicing glue outside the per-op ptop_ scopes) carries it,
            # so scope-attributed WHOLE-STEP device time is one
            # scope_device_seconds("pt_step") read
            with jax.named_scope("pt_step"):
                lower_block(block, env, rng_key, training, aux)
                fetches = [env[n] for n in
                           self.fetch_missing_check(fetch_names, env)]
                new_state = {n: env[n]
                             for n in inout_names + create_state
                             if n in env}
            return fetches, new_state

        # which inout state names are Parameters — the sentinel's fused
        # health norms (train.param_norm / train.grad_norm) reduce over
        # exactly these
        param_names = tuple(
            n for n in inout_names + create_state
            if isinstance(_safe_var(block, n), framework.Parameter))

        return {"step": step, "feed_names": feed_names,
                "ro_names": ro_names, "inout_names": inout_names,
                "create_state": create_state, "interpret": interpret,
                "param_names": param_names}

    # ------------------------------------------------------------------
    def _cached(self, sig):
        """The dispatch record the jit cache (an LRU) holds under ``sig``,
        or None; counted (``jit_cache.*``, ``executor.record.hits``)."""
        from paddle_tpu import profiler as _profiler
        record = self._cache.pop(sig, None)
        if record is None:
            _profiler.runtime_metrics.inc("jit_cache.misses")
            return None
        self._cache[sig] = record   # LRU bump
        _profiler.runtime_metrics.inc("jit_cache.hits")
        _profiler.runtime_metrics.inc("executor.record.hits")
        return record

    def _classify(self, program, block, feed_arrays, fetch_names, scope):
        """:meth:`_prepare`, for the record a jit-cache miss builds."""
        from paddle_tpu import profiler as _profiler
        _profiler.runtime_metrics.inc("executor.record.misses")
        with _profiler.record_latency("executor.prepare_seconds"):
            return self._prepare(program, block, feed_arrays, fetch_names,
                                 scope)

    def _record(self, sig, program, block, feed_arrays, fetch_names, scope,
                jit, **kept):
        """The dispatch record under ``sig``, from the jit cache or built:
        classified, jitted by ``jit(parts) -> (fn, shardings)`` (compiled
        by its first call) and kept under ``sig``."""
        record = self._cached(sig)
        if record is None:
            parts = self._classify(program, block, feed_arrays, fetch_names,
                                   scope)
            # interpreted: op-by-op eager execution — needed when a host
            # op (data-dependent shapes, numpy DP) is in the block; the
            # reference's analogous path is its per-op CPU-kernel
            # interpreter
            fn, shardings = (parts["step"], None) if parts["interpret"] \
                else jit(parts)
            record = _DispatchRecord(self, scope, parts, fn,
                                     shardings=shardings, **kept)
            self._cache_insert(sig, record)
        return record

    def _get_compiled(self, program, block, feed_arrays, fetch_names, scope,
                      donate=True):
        """The dispatch record of one step of ``program``."""
        # donation is part of the executable's identity: a sentinel-
        # guarded step (donate=False) must be able to discard its update,
        # so the pre-step state buffers have to stay valid
        sig = self._signature(program, block, feed_arrays, fetch_names,
                              scope) + (("donate", donate),)
        return self._record(
            sig, program, block, feed_arrays, fetch_names, scope,
            lambda parts: self._jit_step(parts, feed_arrays, fetch_names,
                                         scope, donate), donated=donate)

    def _jit_step(self, parts, feed_arrays, fetch_names, scope, donate):
        """``(fn, shardings)``: the classified step jitted (``shardings``:
        what a mesh's record places its arguments under; None here)."""
        fn = jax.jit(parts["step"], donate_argnums=(2,) if donate else ())
        return _captured(fn, feed_arrays, fetch_names), None

    @staticmethod
    def fetch_missing_check(fetch_names, env):
        for n in fetch_names:
            if n not in env:
                raise KeyError(f"fetch target {n!r} was not produced by the "
                               f"program and is not in the scope")
        return fetch_names

    def close(self):
        self._cache.clear()


def _safe_var(block, name):
    try:
        return block.var(name)
    except Exception:
        return None


def _mfu_gauge_for(program):
    """Which MFU gauge a program's dispatches feed: an explicit
    ``_mfu_gauge`` tag wins (GenPredictor tags its decode program
    ``gen.decode_mfu``); untagged TRAINING programs land in
    ``train.mfu``; untagged inference programs (a serving Predictor, a
    prefill) derive none — a one-shot prefill must not overwrite the
    training/decode gauges the fleet rollups read."""
    tagged = getattr(program, "_mfu_gauge", None)
    if tagged:
        return tagged
    return None if program._is_inference else "train.mfu"


def _enforce_feed(name, value, var):
    """PADDLE_ENFORCE-style feed validation (reference ``enforce.h`` +
    runtime InferShape): catch shape/rank mismatches at the feed boundary
    with a named message instead of a deep XLA trace error."""
    if var is None or var.shape is None:
        return
    shape = np.shape(value)
    want = tuple(var.shape)
    if len(shape) != len(want):
        raise ValueError(
            f"feed variable {name!r}: expected rank {len(want)} "
            f"(shape {want}), got rank {len(shape)} (shape {shape})")
    ragged = getattr(var, "lod_level", 0) or 0
    for i, (got_d, want_d) in enumerate(zip(shape, want)):
        if i == 0 and ragged:
            continue  # LoD feeds have data-dependent row counts
        if want_d is not None and want_d >= 0 and got_d != want_d:
            raise ValueError(
                f"feed variable {name!r}: expected shape {want} "
                f"(-1 = any), got {shape}")


def _env_flag(name, default="0"):
    """Shared env-var truthiness parsing for the gflags-style config
    layer (SURVEY.md §5.6)."""
    import os
    return os.environ.get(name, default).strip().lower() \
        not in ("0", "", "false", "off", "no")


def _lod_buckets_enabled(program):
    """Bucketed dynamic-LoD mode (lod.py): per-program ``lod_buckets``
    attr or the PADDLE_TPU_LOD_BUCKETS env var."""
    if getattr(program, "lod_buckets", None) is not None:
        return bool(program.lod_buckets)
    return _env_flag("PADDLE_TPU_LOD_BUCKETS")


def _check_nan_inf_enabled(program):
    """check_nan_inf executor mode (reference FLAGS_check_nan_inf,
    ``executor.cc:28,352`` CheckTensorNANOrInf): per-program flag or the
    PADDLE_TPU_CHECK_NAN_INF env var."""
    if getattr(program, "check_nan_inf", None) is not None:
        return bool(program.check_nan_inf)
    return _env_flag("PADDLE_TPU_CHECK_NAN_INF")


def _check_nan_inf(fetch_names, fetches, new_state):
    """Raise naming the first non-finite fetched value or state var —
    the named-tensor diagnostic CheckTensorNANOrInf gives on the
    reference (a device-side jax debug_nans check would lose the name)."""
    def bad(v):
        try:
            a = np.asarray(v)
        except TypeError:
            return False
        return np.issubdtype(a.dtype, np.floating) and \
            not np.isfinite(a).all()

    for name, v in zip(fetch_names, fetches):
        if bad(v):
            raise RuntimeError(
                f"Operator output {name!r} contains NaN/Inf "
                f"(check_nan_inf mode)")
    for name, v in new_state.items():
        if bad(v):
            raise RuntimeError(
                f"Variable {name!r} contains NaN/Inf after the step "
                f"(check_nan_inf mode)")


def _amp_enabled(program):
    """Mixed precision: per-program ``Program.amp`` wins; env default
    PADDLE_TPU_AMP=1 covers existing scripts (gflags-style config,
    SURVEY.md §5.6)."""
    if getattr(program, "amp", None) is not None:
        return bool(program.amp)
    return _env_flag("PADDLE_TPU_AMP")


_WARNED_HOST_OP_BLOCKS = set()


def _warn_host_op_cliff(program, block):
    """One host op anywhere switches the WHOLE block to op-by-op eager
    execution — warn once per (program, block) naming the culprits so a
    user adding e.g. edit_distance to a training graph learns why the
    step got slow (VERDICT r1 'host-op cliff')."""
    key = (id(program), block.idx)
    if key in _WARNED_HOST_OP_BLOCKS:
        return
    _WARNED_HOST_OP_BLOCKS.add(key)
    culprits = []

    def scan(blk):
        for op in blk.ops:
            opdef = registry.lookup(op.type)
            if opdef is not None and opdef.host:
                culprits.append(op.type)
            for a in op.attrs.values():
                if isinstance(a, framework.Block):
                    scan(a)

    scan(block)
    import warnings
    warnings.warn(
        f"block {block.idx} contains host op(s) "
        f"{sorted(set(culprits))} — the whole block runs op-by-op eager "
        f"instead of one compiled XLA computation; keep host ops "
        f"(metrics/decoding) in a separate program to keep training "
        f"compiled", stacklevel=4)


def _has_host_ops(block, dyn=False):
    """``dyn=True`` (bucketed dynamic-LoD mode): ops whose bucketed
    branch is fully traced (``host_dyn_ok``) do not force interpret."""
    for op in block.ops:
        opdef = registry.lookup(op.type)
        if opdef is not None and opdef.host and \
                not (dyn and opdef.host_dyn_ok):
            return True
        for a in op.attrs.values():
            if isinstance(a, framework.Block) and _has_host_ops(a, dyn):
                return True
    return False


def _freeze_lod(lod):
    """Nested row-splits list -> hashable tuple (jit cache key component).
    Bucketed-mode metas ("dyn", B, T_bucket) are already hashable — that
    IS the point: the exact splits stay out of the key."""
    if lod is None:
        return None
    if isinstance(lod, tuple) and lod and lod[0] == "dyn":
        return lod
    return tuple(tuple(int(x) for x in level) for level in lod)


def _external_reads(block, produced_outer):
    """Names read inside ``block`` (recursively) that neither the block nor
    the outer trace produces — they must come from scope state."""
    produced = set(produced_outer)
    ext = []
    for op in block.ops:
        for n in op.input_arg_names:
            if n and n not in produced and not block.has_var_local(n):
                ext.append(n)
        for n in op.output_arg_names:
            produced.add(n)
        for a in op.attrs.values():
            if isinstance(a, framework.Block):
                ext.extend(_external_reads(a, produced))
    return ext


def fetch_var(name, scope=None, return_numpy=True):
    scope = scope or global_scope()
    v = scope.find_var(name)
    if v is None:
        raise KeyError(f"variable {name!r} not found in scope")
    return np.asarray(v) if return_numpy else v
