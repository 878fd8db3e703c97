"""Profiler bridge (reference ``python/paddle/fluid/profiler.py`` over the
C++ host/device tracer ``paddle/fluid/platform/profiler.cc`` + CUPTI
``device_tracer.h:32``).

TPU-native realization: ``jax.profiler`` traces (viewable in
TensorBoard/XProf) carry both host and device timelines — the role CUPTI
plays on GPU.  Op-level annotation uses ``jax.named_scope`` markers inserted
by the executor; ``profiler(state, sorted_key)`` context mirrors the
reference API.
"""

from __future__ import annotations

import collections
import contextlib
import glob
import os
import threading
import time

import jax

__all__ = ["cuda_profiler", "reset_profiler", "profiler",
           "start_profiler", "stop_profiler", "enable_op_profiling",
           "disable_op_profiling", "op_profile_table", "op_profiler",
           "RuntimeMetrics", "runtime_metrics", "record_latency",
           "install_jax_compile_listeners"]

# ---------------------------------------------------------------------------
# The ONE control that starts and stops tracing in the process that holds
# the chip: the device profiler (``jax.profiler``) and the span ring
# (``obs.trace``) together, tied by a clock mark so that a span's ``ts``
# maps onto the device trace's nanoseconds.
# ---------------------------------------------------------------------------

CLOCK_MARK = "profiler.clock_mark"   # ring span + host-plane annotation

_session = None     # the running device trace: one per process
_session_lock = threading.Lock()


def _clock_mark(index):
    """One ``TraceAnnotation`` in the trace's host plane with the
    ``perf_counter`` reading taken inside it, and the same as a ring
    span: the pair that ties the two clocks."""
    from paddle_tpu.obs import trace as _trace
    with jax.profiler.TraceAnnotation(f"{CLOCK_MARK}#{index}"):
        t = time.perf_counter()
    _trace.record_span(CLOCK_MARK, t, 0.0, index=index)
    return t


def start_profiler(state="All", profile_path="/tmp/paddle_tpu_profile",
                   ring_size=None):
    """Start tracing in this process: the span ring is turned on if it is
    off (``stop_profiler`` turns it off again), ``jax.profiler`` starts
    writing under ``profile_path`` (device ops and the host runtime; no
    Python tracer) and a clock mark is written.  Raises ``RuntimeError``
    when a trace started here is already running, and whatever
    ``jax.profiler.start_trace`` raises.  ``state`` is the reference
    API's ('CPU'/'GPU'/'All') and is not read."""
    global _session
    from paddle_tpu.obs import trace as _trace
    with _session_lock:
        if _session is not None:
            raise RuntimeError(
                f"a device trace is already running (into "
                f"{_session['trace_dir']}): stop_profiler() first")
        ring_was_on = _trace.enabled()
        _trace.enable(ring_size)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # device ops and host runtime only
        opts.host_tracer_level = 1
        try:
            jax.profiler.start_trace(str(profile_path),
                                     profiler_options=opts)
        except BaseException:
            if not ring_was_on:
                _trace.disable()
            raise
        _session = {"trace_dir": str(profile_path),
                    "ring_was_on": ring_was_on,
                    "marks": [_clock_mark(0)]}


def stop_profiler(sorted_key=None, profile_path=None):
    """Stop the trace :func:`start_profiler` started and return what a
    reader needs to put spans beside it::

        {"trace_dir", "xplane",        # the ``.xplane.pb`` written
         "span_to_trace_ns",           # trace ns = ts * 1e9 + this
         "drift_ns",                   # second mark's offset - first's
         "mark_width_ns",              # the first mark's own length
         "t_start", "t_stop"}          # span-clock ts of the two marks

    ``span_to_trace_ns`` is None when the marks are not in the trace.
    Returns None when no trace was running.  The arguments are the
    reference API's and are not read."""
    global _session
    from paddle_tpu.obs import trace as _trace
    with _session_lock:
        session, _session = _session, None
        if session is None:
            return None
        try:
            session["marks"].append(_clock_mark(1))
        finally:
            try:
                jax.profiler.stop_trace()
            finally:
                if not session["ring_was_on"]:
                    _trace.disable()
    t0, t1 = (_trace.ts_of(t) for t in session["marks"])
    out = {"trace_dir": session["trace_dir"], "xplane": None,
           "span_to_trace_ns": None, "drift_ns": None,
           "mark_width_ns": None, "t_start": t0, "t_stop": t1}
    files = sorted(glob.glob(os.path.join(session["trace_dir"], "**",
                                          "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if files:
        out["xplane"] = files[-1]
        out.update(_clock_offsets(files[-1], t0, t1))
    return out


def _clock_offsets(xplane, t0, t1):
    """Find the two clock marks in the trace's host plane; a mark's
    ``perf_counter`` reading was taken inside its annotation, so it is
    put at the annotation's middle (half its width is the error)."""
    from jax.profiler import ProfileData
    found = {}
    for plane in ProfileData.from_file(xplane).planes:
        if plane.name.startswith("/device:"):
            continue        # the marks are host events
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(CLOCK_MARK + "#"):
                    found[ev.name] = (float(ev.start_ns),
                                      float(ev.duration_ns))
    first, second = (found.get(f"{CLOCK_MARK}#{i}") for i in (0, 1))
    if first is None:
        return {}
    offset = first[0] + first[1] / 2 - t0 * 1e9
    out = {"span_to_trace_ns": offset, "mark_width_ns": first[1]}
    if second is not None:
        out["drift_ns"] = second[0] + second[1] / 2 - t1 * 1e9 - offset
    return out


@contextlib.contextmanager
def cuda_profiler(output_file, output_mode=None, config=None):
    """Name kept for API parity; on TPU this is an XLA/XProf trace."""
    with profiler("All", profile_path=output_file):
        yield


def reset_profiler():
    """Clear collected op-level events (reference ``profiler.py``
    reset_profiler)."""
    global _op_events
    _op_events = {}


@contextlib.contextmanager
def profiler(state="All", sorted_key=None,
             profile_path="/tmp/paddle_tpu_profile"):
    """reference ``profiler.py:76``."""
    start_profiler(state, profile_path)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


# ---------------------------------------------------------------------------
# op-level aggregation table (reference EnableProfiler/DisableProfiler,
# ``platform/profiler.h:110-115``: sorted per-op-type event tables).
#
# On TPU the compiled path fuses ops away, so op-level timing runs the
# block in the executor's op-by-op interpret mode with a device sync per op
# — the same overhead FLAGS_benchmark adds on the reference.
# ---------------------------------------------------------------------------

_op_profiling = False
_op_events = {}


def op_profiling_enabled():
    return _op_profiling


def enable_op_profiling():
    """Start collecting per-op timings; forces interpret-mode execution."""
    global _op_profiling, _op_events
    _op_profiling = True
    _op_events = {}


def disable_op_profiling():
    global _op_profiling
    _op_profiling = False


@contextlib.contextmanager
def record_op(op_type, ctx=None):
    t0 = time.perf_counter()
    with jax.named_scope(op_type):
        yield
    # sync so the interval covers device work (reference implicit Wait)
    if ctx is not None:
        for v in ctx.outputs.values():
            if hasattr(v, "block_until_ready"):
                try:
                    v.block_until_ready()
                except Exception:
                    pass
    dt = time.perf_counter() - t0
    ev = _op_events.setdefault(op_type, [0, 0.0, 0.0])
    ev[0] += 1
    ev[1] += dt
    ev[2] = max(ev[2], dt)


def op_profile_table(sorted_key="total"):
    """Sorted per-op aggregation table as a string (reference
    ``profiler.h`` PrintProfiler: Event/Calls/Total/Min/Max/Ave)."""
    keys = {"total": 1, "calls": 0, "max": 2,
            "ave": lambda item: item[1][1] / max(item[1][0], 1)}
    k = keys.get(sorted_key or "total", 1)
    rows = sorted(_op_events.items(),
                  key=(k if callable(k) else (lambda item, i=k: item[1][i])),
                  reverse=True)
    lines = [f"{'Event':<28}{'Calls':>8}{'Total(ms)':>12}"
             f"{'Ave(ms)':>12}{'Max(ms)':>12}"]
    for op_type, (calls, total, mx) in rows:
        lines.append(f"{op_type:<28}{calls:>8}{total * 1e3:>12.3f}"
                     f"{total / max(calls, 1) * 1e3:>12.3f}{mx * 1e3:>12.3f}")
    return "\n".join(lines)


@contextlib.contextmanager
def op_profiler(sorted_key="total"):
    """Context manager: profile per-op and print the table on exit."""
    enable_op_profiling()
    try:
        yield
    finally:
        disable_op_profiling()
        print(op_profile_table(sorted_key))


# ---------------------------------------------------------------------------
# compiled-path per-op attribution (round 3; reference platform/profiler.h
# RecordEvent:110 attributes real run time to ops — here the executor
# wraps every op lowering in jax.named_scope, XLA carries the scope into
# each HLO instruction's op_name metadata, and a trace of the COMPILED
# step is aggregated back to IR op names)
# ---------------------------------------------------------------------------

_SCOPE_PREFIX = "ptop_"


def op_scope_name(op):
    """named_scope label for an IR op: ptop_<type>__<primary output>.
    Dots/slashes are scope separators in XLA metadata, so sanitize."""
    outs = op.output_arg_names
    tag = outs[0] if outs else ""
    return _SCOPE_PREFIX + f"{op.type}__{tag}".replace(".", "_") \
        .replace("/", "_")


_ROLE_SCOPES = {"backward": "bwd", "optimize": "opt"}


def op_scope_path(op):
    """The named scopes the executor lowers an op under, outermost first:
    ``bwd`` / ``opt`` for a backward / optimize op, one a component of
    its ``op_namescope``, then its ``ptop_`` scope.  An op with neither
    attribute has the ``ptop_`` scope alone, as it always had."""
    parts = []
    role = _ROLE_SCOPES.get(op.attrs.get("op_role"))
    if role:
        parts.append(role)
    scope = op.attrs.get("op_namescope")
    if scope:
        parts += [p.replace(".", "_") for p in str(scope).split("/") if p]
    parts.append(op_scope_name(op))
    return parts


def parse_scope_path(hlo_op_name):
    """``(role, name scopes, op type)`` of an HLO ``op_name`` path
    ``…/pt_step/<role>/<scope…>/ptop_<type>__<output>/…``: the role is
    ``fwd`` where the path names none, the name scopes are the
    components between the role (or ``pt_step``) and the first ``ptop_``
    scope, the type is the deepest ``ptop_`` scope's (an op of a
    sub-block lies inside its control-flow op's scope).  None for a path
    without a ``ptop_`` scope.  An instruction the compiler made from
    several (a copy between two ops) carries their paths joined by
    ``;``: the path named most often counts, the first on a tie."""
    # the trace's ``tf_op`` stat is ``<op_name>:<op type>``
    paths = [p.split(":", 1)[0] for p in str(hlo_op_name).split(";")
             if _SCOPE_PREFIX in p]
    if not paths:
        return None
    hlo_op_name = collections.Counter(paths).most_common(1)[0][0]
    parts = hlo_op_name.split("/")
    at = next((i for i, p in enumerate(parts)
               if p.startswith(_SCOPE_PREFIX)), None)
    if at is None:
        return None
    op_type = parse_op_scope(hlo_op_name)[0]
    roles = set(_ROLE_SCOPES.values())
    start = next((i for i in range(at - 1, -1, -1)
                  if parts[i] in roles or parts[i] == "pt_step"), None)
    if start is None:
        return "fwd", (), op_type
    role = parts[start] if parts[start] in roles else "fwd"
    return role, tuple(parts[start + 1:at]), op_type


def parse_op_scope(hlo_op_name):
    """Deepest ptop_ scope component of an HLO op_name path, as
    (op_type, output_tag), or None."""
    hit = None
    for part in str(hlo_op_name).split("/"):
        if part.startswith(_SCOPE_PREFIX):
            hit = part[len(_SCOPE_PREFIX):]
    if hit is None:
        return None
    op_type, _, tag = hit.partition("__")
    return op_type, tag


def iter_trace_events(trace_dir, device_only=False, exclude_async=False):
    """Yield ``(name_candidates, duration_ps)`` for every event in a
    jax.profiler trace (xplane protos under ``trace_dir``).  The scope
    label appears either in the event name or in the tf_op/long_name stat
    depending on the backend — callers match against ALL candidates.
    ``device_only`` restricts to accelerator planes (``/device:...``) so
    host Python-tracer events cannot pollute device-time sums;
    ``exclude_async`` drops 'Async XLA Ops' lines, whose overlapping DMA
    durations multi-count wall time.  A SUM over every event of every
    line (parents and the children they enclose alike): right for
    :func:`scope_device_seconds` over a micro-benchmark's one scope, wrong
    for a table of a whole step, which :func:`compiled_op_groups` builds
    from leaf events instead."""
    for plane in _iter_xplanes(trace_dir):
        if device_only and not plane.name.startswith("/device:"):
            continue
        statmeta = plane.stat_metadata
        evmeta = plane.event_metadata
        for line in plane.lines:
            if exclude_async and "async" in line.name.lower():
                continue
            for ev in line.events:
                m = evmeta[ev.metadata_id]
                cands = [m.name, getattr(m, "display_name", "")]
                for st in list(ev.stats) + list(m.stats):
                    sname = statmeta[st.metadata_id].name
                    if sname in ("tf_op", "long_name", "name"):
                        if st.str_value:
                            cands.append(st.str_value)
                        elif st.ref_value:
                            cands.append(
                                statmeta[st.ref_value].name)
                yield cands, ev.duration_ps


def measure_device_seconds(fn, scope=None):
    """Run ``fn()`` under a jax.profiler trace and return its DEVICE
    seconds — total busy time, or only events matching the ``scope``
    substring when given.  Owns the trace-dir lifecycle and the
    pure-python protobuf env the xplane parser needs; wall clocks on
    this backend carry dispatch/sync latencies, so this is the shared
    measurement harness for the bench scripts (``bench_attention.py``,
    ``bench_lstm.py``, ``bench_resnet.py``)."""
    import os
    import shutil
    import tempfile

    import jax

    os.environ.setdefault("PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION",
                          "python")
    td = tempfile.mkdtemp(prefix="pttrace_")
    start_profiler(profile_path=td)
    try:
        fn()
    finally:
        stop_profiler()
    try:
        if scope is not None:
            return scope_device_seconds(td, scope)
        return device_busy_seconds(td)
    finally:
        shutil.rmtree(td, ignore_errors=True)


_xplane_module = None


def _xplane_pb2():
    """The ``XSpace`` protobuf module that ships inside the installed
    TensorFlow, loaded by its path: importing TensorFlow itself costs
    seconds and, in the process that holds the chip, loads a second
    accelerator runtime."""
    global _xplane_module
    if _xplane_module is None:
        import importlib.util
        try:
            spec = importlib.util.find_spec("tensorflow")
            path = os.path.join(os.path.dirname(spec.origin), "tsl",
                                "profiler", "protobuf", "xplane_pb2.py")
            inner = importlib.util.spec_from_file_location(
                "_paddle_tpu_xplane_pb2", path)
            module = importlib.util.module_from_spec(inner)
            inner.loader.exec_module(module)
        except Exception:   # another layout: the package import finds it
            from tensorflow.tsl.profiler.protobuf import xplane_pb2 as module
        _xplane_module = module
    return _xplane_module


def _iter_xplanes(trace_dir):
    """Yield every plane of every xplane proto under ``trace_dir``."""
    import glob as _glob

    xplane_pb2 = _xplane_pb2()
    for path in _glob.glob(str(trace_dir) + "/**/*.xplane.pb",
                           recursive=True):
        xs = xplane_pb2.XSpace()
        with open(path, "rb") as f:
            xs.ParseFromString(f.read())
        yield from xs.planes


def device_busy_seconds(trace_dir):
    """Busy device seconds of a trace: per accelerator plane, the op
    timeline is the line named 'XLA Ops' (span lines like 'Steps' /
    'XLA Modules' include on-device idle gaps, and 'Async XLA Ops' holds
    OVERLAPPING DMA copies whose durations multi-count wall time).  Falls
    back to the max non-async line sum when no 'XLA Ops' line exists.

    The device tracer records EVERY program the process ran on the chip
    during the window, so this total can exceed the time of the one
    computation you care about.  When that matters, wrap it in
    ``jax.named_scope`` and use :func:`scope_device_seconds` /
    :func:`measure_device_seconds` with ``scope=``, which other events
    cannot match."""
    busy = 0.0
    for plane in _iter_xplanes(trace_dir):
        if not plane.name.startswith("/device:"):
            continue
        sums = {}
        for line in plane.lines:
            if "async" in line.name.lower():
                continue
            sums[line.name] = sums.get(line.name, 0) + sum(
                ev.duration_ps for ev in line.events)
        if "XLA Ops" in sums:
            busy += sums["XLA Ops"] / 1e12
        elif sums:
            busy += max(sums.values()) / 1e12
    return busy


def scope_device_seconds(trace_dir, substring):
    """Total device seconds of events whose any name candidate contains
    ``substring`` — the micro-benchmark counterpart of
    :func:`compiled_op_table` (wall clocks on this backend are poisoned
    by dispatch/sync latency; device time is the ground truth)."""
    total_ps = 0
    for cands, dur in iter_trace_events(trace_dir, device_only=True,
                                        exclude_async=True):
        if any(substring in c for c in cands):
            total_ps += dur
    return total_ps / 1e12


def _leaf_op_events(plane):
    """``(seconds, instruction name, scope path)`` of the events of a
    device plane's ``XLA Ops`` line that enclose no other (a ``while`` or
    a ``call`` encloses its body: counting both counts the body twice),
    and the union of all of them in seconds."""
    scope_stats = {k for k, m in plane.stat_metadata.items()
                   if m.name == "tf_op"}
    events = []
    for line in plane.lines:
        if line.name != "XLA Ops":
            continue
        for ev in line.events:
            start = line.timestamp_ns * 1000 + ev.offset_ps
            events.append((start, start + ev.duration_ps, ev.metadata_id))
    events.sort(key=lambda e: (e[0], -e[1]))
    leaves, busy, reach = [], 0, None
    for i, (start, end, meta_id) in enumerate(events):
        if reach is None or start > reach:
            busy += end - start
            reach = end
        elif end > reach:
            busy += end - reach
            reach = end
        nxt = events[i + 1] if i + 1 < len(events) else None
        if nxt is not None and nxt[0] < end and nxt[1] <= end and \
                (nxt[0] > start or nxt[1] < end):
            continue
        meta = plane.event_metadata[meta_id]
        scope = next((st.str_value or plane.stat_metadata[st.ref_value].name
                      for st in meta.stats
                      if st.metadata_id in scope_stats), "")
        name = meta.name.split(" = ", 1)[0].lstrip("%")
        leaves.append(((end - start) / 1e12, name, scope))
    return leaves, busy / 1e12


UNSCOPED = "(unscoped)"


def compiled_op_groups(trace_dir, by=("type",), depth=2):
    """Device time of a trace of COMPILED steps by group.  Per device
    plane only the LEAF events of the ``XLA Ops`` line are counted, and
    the result is the MEAN over the device planes (one plane a chip).

    ``by`` picks the key: any of ``"role"`` (``fwd`` / ``bwd`` / ``opt``),
    ``"scope"`` (the ``framework.name_scope`` path, cut to ``depth``
    components, ``-`` where the op has none) and ``"type"`` (the IR op
    type), read from the scope path the executor wrote
    (:func:`parse_scope_path`).  Events under no ``ptop_`` scope (copies,
    loop glue) are gathered under :data:`UNSCOPED`, so the rows sum to the
    busy time but for genuine overlap.  Returns ``{"rows": [(*key, calls,
    seconds)], "remat_seconds": events whose instruction name holds
    ``.remat`` (an overlay: they are in the rows too), "busy_seconds": the
    union, "planes": n}``."""
    unknown = set(by) - {"role", "scope", "type"}
    if unknown or not by:
        raise ValueError(f"compiled_op_groups: by={by!r}")
    seconds, calls = collections.Counter(), collections.Counter()
    remat = busy = 0.0
    planes = 0
    for plane in _iter_xplanes(trace_dir):
        if not plane.name.startswith("/device:"):
            continue
        leaves, plane_busy = _leaf_op_events(plane)
        if not leaves:
            continue
        planes += 1
        busy += plane_busy
        for secs, name, scope in leaves:
            parsed = parse_scope_path(scope) or parse_scope_path(name)
            if parsed is None:
                key = (UNSCOPED,) + ("",) * (len(by) - 1)
            else:
                fields = {"role": parsed[0],
                          "scope": "/".join(parsed[1][:depth]) or "-",
                          "type": parsed[2]}
                key = tuple(fields[k] for k in by)
            seconds[key] += secs
            calls[key] += 1
            if ".remat" in name:
                remat += secs
    n = max(planes, 1)
    rows = [(*key, max(1, round(calls[key] / n)), secs / n)
            for key, secs in seconds.items()]
    return {"rows": rows, "remat_seconds": remat / n,
            "busy_seconds": busy / n, "planes": planes}


def compiled_op_table(trace_dir, sorted_key="total", by=("type",), depth=2):
    """Aggregate a jax.profiler trace (xplane protos under ``trace_dir``)
    into per-group device time (:func:`compiled_op_groups`: leaf events
    only, mean over the chips).  Returns ``(table_string, rows)`` where
    rows = ``[(*key, calls, total_seconds)]`` sorted descending; with the
    default ``by`` that is ``[(op_type, calls, total_seconds)]``.  The
    table ends with the ``.remat`` overlay and the busy union."""
    groups = compiled_op_groups(trace_dir, by, depth)
    rows = sorted(groups["rows"],
                  key=lambda r: r[-2 if sorted_key == "calls" else -1],
                  reverse=True)
    width = 28 if len(by) == 1 else 44
    lines = [f"{'Event':<{width}}{'Calls':>8}{'Total(ms)':>12}"
             f"{'Ave(ms)':>12}"]
    for *key, n, total in rows:
        label = " ".join(k for k in key if k)
        lines.append(f"{label:<{width}}{n:>8}{total * 1e3:>12.3f}"
                     f"{total / max(n, 1) * 1e3:>12.3f}")
    if groups["planes"]:
        lines.append(f"{'.remat (also counted above)':<{width}}{'':>8}"
                     f"{groups['remat_seconds'] * 1e3:>12.3f}")
        lines.append(f"{'busy (union, mean of ' + str(groups['planes']) + ' chips)':<{width}}"
                     f"{'':>8}{groups['busy_seconds'] * 1e3:>12.3f}")
    return "\n".join(lines), rows


# ---------------------------------------------------------------------------
# runtime metrics surface (serving/compile hot path): counters, latency
# percentiles, and small-value histograms, exported via the inference
# server's /stats endpoint and `paddle_tpu stats`.  The reference exposes
# analogous counters through its pserver/master Prometheus handlers
# (go/pserver/service.go); here one process-wide registry serves the
# executor (jit-cache hits/evictions, compile seconds), the persistent
# XLA compilation cache (hits/misses via jax monitoring events), and the
# serving batcher (request latency, batch occupancy).
# ---------------------------------------------------------------------------

_LATENCY_WINDOW = 2048  # samples kept per series for percentile estimates


def _nearest_rank(sorted_xs, q):
    """Nearest-rank percentile over an ascending-sorted list (shared by
    percentiles() and snapshot() so the two can never drift)."""
    if not sorted_xs:
        return None
    i = min(len(sorted_xs) - 1,
            max(0, int(round(q / 100.0 * len(sorted_xs))) - 1))
    return sorted_xs[i]


class RuntimeMetrics:
    """Thread-safe process-wide counters + bounded latency reservoirs."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters = collections.Counter()
        self._series = {}       # name -> deque[float] (bounded window)
        self._series_agg = {}   # name -> [count, total]  (unwindowed)
        self._hist = {}         # name -> Counter (small integer values)
        self._gauges = {}       # name -> float (last-write-wins level)

    # -- writers -------------------------------------------------------
    def inc(self, name, n=1):
        with self._lock:
            self._counters[name] += n

    def observe(self, name, value):
        """Record one sample (seconds, rows, ...) into a bounded window."""
        with self._lock:
            d = self._series.get(name)
            if d is None:
                d = self._series[name] = collections.deque(
                    maxlen=_LATENCY_WINDOW)
                self._series_agg[name] = [0, 0.0]
            d.append(float(value))
            agg = self._series_agg[name]
            agg[0] += 1
            agg[1] += float(value)

    def bucket(self, name, key):
        """Histogram over small discrete values (batch occupancy)."""
        with self._lock:
            self._hist.setdefault(name, collections.Counter())[int(key)] += 1

    def set_gauge(self, name, value):
        """Instantaneous level (queue depth, pool size): last write wins,
        unlike observe()'s sample series."""
        with self._lock:
            self._gauges[name] = float(value)

    # -- readers -------------------------------------------------------
    def counter(self, name):
        with self._lock:
            return self._counters.get(name, 0)

    def gauge(self, name):
        with self._lock:
            return self._gauges.get(name)

    def samples(self, name, last=None):
        """The newest ``last`` samples kept of series ``name`` (all that
        are kept when None), oldest first: with the difference of two
        ``count`` readings for ``last``, the samples between them."""
        with self._lock:
            xs = list(self._series.get(name) or ())
        return xs if last is None else xs[max(0, len(xs) - int(last)):]

    def percentiles(self, name, qs=(50, 95, 99)):
        """Window percentiles of ``name``; an unknown or empty series
        yields None per quantile (never raises — dashboards poll series
        that may not have emitted yet)."""
        with self._lock:
            d = self._series.get(name)
            xs = sorted(d) if d else []
        return {f"p{q}": _nearest_rank(xs, q) for q in qs}

    def snapshot(self):
        """One JSON-serializable dict of everything (the /stats body)."""
        with self._lock:
            counters = dict(self._counters)
            gauges = dict(self._gauges)
            hist = {n: {str(k): v for k, v in sorted(c.items())}
                    for n, c in self._hist.items()}
            series = {n: (list(d), list(self._series_agg[n]))
                      for n, d in self._series.items()}
        latency = {}
        for name, (window, (count, total)) in series.items():
            xs = sorted(window)
            entry = {"count": count, "total": total,
                     "mean": (total / count) if count else None}
            for q in (50, 95, 99):
                entry[f"p{q}"] = _nearest_rank(xs, q)
            # 1/mean — a true rate ONLY for serially-recorded series
            # (executor.step_seconds = steps/sec); for concurrent
            # series (request latencies) it is NOT throughput — divide
            # a request counter by wall time instead
            entry["per_sec_serial"] = (count / total) if total else None
            latency[name] = entry
        return {"counters": counters, "series": latency,
                "histograms": hist, "gauges": gauges}

    def reset(self):
        with self._lock:
            self._counters.clear()
            self._series.clear()
            self._series_agg.clear()
            self._hist.clear()
            self._gauges.clear()


runtime_metrics = RuntimeMetrics()


@contextlib.contextmanager
def record_latency(name, metrics=None):
    """Time the body and observe it as one sample of ``name``.

    A raising body still has its elapsed time observed (failures are
    often the SLOW samples — dropping them would flatter the
    percentiles) and additionally bumps the ``<name>.errors`` counter,
    so error-rate and latency stay attributable to the same series."""
    m = metrics or runtime_metrics
    t0 = time.perf_counter()
    try:
        yield
    except BaseException:
        m.observe(name, time.perf_counter() - t0)
        m.inc(name + ".errors")
        raise
    else:
        m.observe(name, time.perf_counter() - t0)


_jax_listeners_installed = False


def install_jax_compile_listeners():
    """Mirror jax's compile/compilation-cache monitoring events into the
    runtime metrics registry (idempotent):

    - ``compile_cache.hits`` / ``compile_cache.misses``: persistent XLA
      compilation-cache outcomes (PADDLE_TPU_COMPILE_CACHE) — a warm
      restart shows hits where a cold one shows misses;
    - ``compile.backend_seconds`` / ``compile.trace_seconds`` /
      ``compile.lower_seconds``: where compile time goes (XLA backend vs
      jaxpr trace vs MLIR lowering).
    """
    global _jax_listeners_installed
    if _jax_listeners_installed:
        return True
    try:
        from jax._src import monitoring
    except ImportError:  # pragma: no cover - monitoring moved/absent
        return False

    _EVENT_COUNTERS = {
        "/jax/compilation_cache/cache_hits": "compile_cache.hits",
        "/jax/compilation_cache/cache_misses": "compile_cache.misses",
    }
    _DURATION_SERIES = {
        "/jax/core/compile/backend_compile_duration":
            "compile.backend_seconds",
        "/jax/core/compile/jaxpr_trace_duration": "compile.trace_seconds",
        "/jax/core/compile/jaxpr_to_mlir_module_duration":
            "compile.lower_seconds",
    }

    def _on_event(event, **kw):
        name = _EVENT_COUNTERS.get(event)
        if name is not None:
            runtime_metrics.inc(name)

    def _on_duration(event, duration, **kw):
        name = _DURATION_SERIES.get(event)
        if name is not None:
            runtime_metrics.observe(name, duration)
            runtime_metrics.inc("compile.events")

    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)
    _jax_listeners_installed = True
    return True


@contextlib.contextmanager
def compiled_profiler(trace_dir=None, sorted_key="total",
                      by=("role", "scope", "type"), depth=2):
    """Trace compiled execution inside the block and print the device-time
    table on exit, grouped by role / name scope / op type
    (:func:`compiled_op_table`; the compiled-path counterpart of
    ``op_profiler``, which times interpret mode).  A temp trace dir is
    created — and removed afterwards — unless ``trace_dir`` is given
    (pass one to keep the raw xplane protos)."""
    import shutil
    import tempfile
    own = trace_dir is None
    d = trace_dir or tempfile.mkdtemp(prefix="ptprof_")
    start_profiler(profile_path=d)
    try:
        yield d
    finally:
        stop_profiler()
        try:
            table, _ = compiled_op_table(d, sorted_key, by, depth)
            print(table)
        finally:
            if own:
                shutil.rmtree(d, ignore_errors=True)
