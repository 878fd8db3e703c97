"""Device-performance observability: XLA cost/memory attribution, HBM
accounting, live MFU.

The host-side plane (spans, federated metrics, SLOs) sees everything the
PROCESS does; this module lights up the DEVICE:

- **Compile capture** — on every jit-cache miss the executor routes the
  fresh ``jax.jit`` through :func:`instrument_jit`: the first call runs
  the AOT pipeline (``trace -> lower -> compile``) with the real
  arguments, records per-phase wall time, the compiled executable's XLA
  ``cost_analysis()`` (FLOPs, bytes accessed) and ``memory_analysis()``
  (argument/output/temp/generated-code bytes) keyed by jit key, and
  keeps serving the AOT executable (same donation semantics as the jit
  call path; a signature mismatch falls back to the original jit
  function).  Records surface through :func:`records` /
  :func:`compile_report` and the ``paddle_tpu profile compile`` CLI.

- **Live MFU** — :func:`note_step` divides a record's cost-analysis
  FLOPs by the measured step seconds and the chip's peak
  (:func:`peak_flops_per_chip`, moved here from ``bench.py`` so the
  library and the bench share one table) into the ``train.mfu`` gauge
  (or ``gen.decode_mfu`` for a decode program).  The measured step
  time covers the whole step — feed staging to the host
  materialization of the fetches, the point that BLOCKS on the
  device — so it is an honest
  (slightly conservative: host conversion included) wall time; paths
  that hand back async device arrays (``return_numpy=False``) derive
  no gauge, because their submit time would overstate MFU by the
  async-dispatch factor.

- **HBM census** — :func:`hbm_census` walks ``jax.live_arrays()`` and
  attributes bytes to collections: scope params vs optimizer state
  (accumulator-name conventions from ``optimizer.py``), KV-cache slots
  (``GenPredictor`` registers a provider), datapipe prefetch buffers
  (``DevicePrefetch`` registers one), everything else ``other`` — as
  ``hbm.*`` gauges with a process-lifetime high watermark.  Armed on a
  cadence via ``PADDLE_TPU_HBM_CENSUS=<seconds>`` the executor's
  per-step :func:`census_tick` costs a None check unarmed and one clock
  read armed-but-not-due (guarded in ``tests/test_obs_overhead.py``).

- **Headroom check** — when a compile's ``memory_analysis`` lands, the
  projected footprint (temp + output + generated code) is compared
  against the device limit minus the live set; a program that will not
  fit warns (``hbm.headroom_warnings``) BEFORE it runs, and the
  ``hbm.limit_bytes`` / ``hbm.headroom_bytes`` gauges track the margin.

See ``docs/performance.md`` ("Device performance") for the CLI family
and the MFU derivation, and ``docs/observability.md`` for the metric
registry rows.
"""

from __future__ import annotations

import collections
import logging
import os
import threading
import time

__all__ = ["peak_flops_per_chip", "peak_flops_info", "MFU_BASES",
           "instrument_jit",
           "capture_enabled", "note_step", "records", "compile_report",
           "validate_report", "reset_records", "hbm_census",
           "register_hbm_provider", "unregister_hbm_provider",
           "hbm_limit_bytes", "census_tick", "arm_census",
           "enable_step_phases", "disable_step_phases",
           "step_phases_enabled", "WarmupReport"]

logger = logging.getLogger(__name__)

# ---------------------------------------------------------------------------
# peak FLOPs (moved from bench.py — the library and the bench must never
# disagree on the denominator MFU claims rest on)
# ---------------------------------------------------------------------------

#: peak dense bf16 FLOP/s per chip, keyed by a lower-case substring of
#: the ``device_kind`` the runtime reports.  Sources: the Google Cloud
#: TPU documentation's per-generation system-architecture pages ("TPU
#: v5e": 197 TFLOP/s bf16 per chip; "TPU v5p": 459; "TPU v4": 275;
#: "TPU v3": 123; "TPU v2": 45; "TPU v6e": 918).  The v5e runtime
#: (libtpu 0.0.34) reports ``device_kind == "TPU v5 lite"``; "v5e" and
#: "v5litepod" are the names the cloud API and docs use for the same chip.
PEAK_FLOPS_TABLE = {
    "tpu v5 lite": 197e12, "v5e": 197e12, "v5litepod": 197e12,
    "v5p": 459e12, "v4": 275e12, "v3": 123e12, "v2": 45e12,
    "v6e": 918e12,
}

#: the finite-but-meaningless CPU fallback (tagged, never compared
#: against tpu-peak records — see bench_history's mfu_basis refusal)
CPU_FALLBACK_PEAK = 1e12

#: every legal MFU basis tag — the ONE definition ``peak_flops_info``
#: emits from, ``validate_report`` checks against, and
#: ``bench_history`` re-exports for trajectory validation
MFU_BASES = ("tpu-peak", "cpu-fallback")

_peak_cache = None  # (value, basis)


def peak_flops_info():
    """``(peak_flops, basis)`` for the local accelerator; ``basis`` is
    ``"tpu-peak"`` when the device kind is in ``PEAK_FLOPS_TABLE`` and
    ``"cpu-fallback"`` on the CPU backend — every MFU number carries its
    basis so a CPU smoke run can never be compared against a real-chip
    trajectory.  An accelerator whose kind is not in the table raises:
    a guessed denominator is worse than none."""
    global _peak_cache
    if _peak_cache is not None:
        return _peak_cache
    import jax
    d = jax.devices()[0]
    kind = getattr(d, "device_kind", "cpu").lower()
    for k, v in PEAK_FLOPS_TABLE.items():
        if k in kind:
            _peak_cache = (v, "tpu-peak")
            return _peak_cache
    if d.platform != "cpu":
        raise ValueError(
            f"device_kind {d.device_kind!r} (platform {d.platform!r}) is "
            f"not in obs.perf.PEAK_FLOPS_TABLE — add its documented peak "
            f"with the source instead of guessing one")
    _peak_cache = (CPU_FALLBACK_PEAK, "cpu-fallback")
    return _peak_cache


def peak_flops_per_chip():
    """Best-effort peak (bf16) FLOP/s for the local accelerator (the
    ``bench.py`` function, now library API)."""
    return peak_flops_info()[0]


# ---------------------------------------------------------------------------
# compile capture
# ---------------------------------------------------------------------------

_records_lock = threading.Lock()
_records = collections.OrderedDict()   # key -> record dict
_RECORDS_MAX = 256
_key_counter = [0]

REPORT_FORMAT = 1

#: keys every compile record carries (``validate_report`` and the
#: selfcheck ``perf`` section hold the ``profile compile --json`` schema
#: to this)
RECORD_KEYS = ("key", "label", "created_unix", "flops", "bytes_accessed",
               "memory", "phases", "steps", "last_step_seconds", "mfu")
MEMORY_KEYS = ("argument_bytes", "output_bytes", "temp_bytes",
               "alias_bytes", "generated_code_bytes")
PHASE_KEYS = ("trace_seconds", "lower_seconds", "backend_seconds")


def capture_enabled():
    """Compile capture is on by default; ``PADDLE_TPU_PERF=0`` disables
    it (the executor then jits exactly as before this module existed)."""
    return os.environ.get("PADDLE_TPU_PERF", "1").strip().lower() \
        not in ("0", "false", "off", "no")


def _metrics():
    from paddle_tpu.profiler import runtime_metrics
    return runtime_metrics


def _cost_summary(compiled):
    """(flops, bytes_accessed) from ``cost_analysis()`` — a list of
    per-computation dicts on this jax, a dict on others, possibly
    unavailable on exotic backends."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None, None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return None, None

    def _clean(v):
        # XLA reports -1 for costs it cannot model (some convolutions,
        # custom calls) — that is "unknown", not a number to divide by
        if v is None or float(v) < 0:
            return None
        return float(v)

    return _clean(ca.get("flops")), _clean(ca.get("bytes accessed"))


def _memory_summary(compiled):
    """The device-memory breakdown of ``memory_analysis()`` as a plain
    dict (None when the backend does not report one)."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    out = {}
    for key, attr in (("argument_bytes", "argument_size_in_bytes"),
                      ("output_bytes", "output_size_in_bytes"),
                      ("temp_bytes", "temp_size_in_bytes"),
                      ("alias_bytes", "alias_size_in_bytes"),
                      ("generated_code_bytes",
                       "generated_code_size_in_bytes")):
        v = getattr(ma, attr, None)
        if v is None:
            return None
        out[key] = int(v)
    return out


def jit_label(feed_arrays, fetch_names, tag=""):
    """Human-readable jit-key label for the profile tables: the sorted
    feed name:shape pairs (truncated) — recognizable without leaking a
    whole signature tuple into a table column."""
    parts = []
    for n in sorted(feed_arrays):
        a = feed_arrays[n]
        shape = "x".join(str(d) for d in getattr(a, "shape", ())) or "()"
        parts.append(f"{n}:{shape}")
    label = (f"{tag}:" if tag else "") + ",".join(parts)
    if len(label) > 96:
        label = label[:93] + "..."
    return label or "(no feeds)"


def _insert_record(record):
    with _records_lock:
        while len(_records) >= _RECORDS_MAX:
            _records.popitem(last=False)
        _records[record["key"]] = record


def instrument_jit(jitted, label="", metrics=None):
    """Wrap a fresh ``jax.jit`` callable so its FIRST call compiles via
    the AOT pipeline and captures a compile record; later calls run the
    AOT executable directly.

    Degradation contract: any capture failure (backend without AOT,
    analysis unavailable, tracing quirk) falls back to calling
    ``jitted`` unchanged and bumps ``compile.capture_failures``; a
    post-capture signature mismatch (``TypeError`` from the AOT
    executable's argument check — raised before execution, so donation
    never half-happens) re-dispatches through ``jitted`` and bumps
    ``compile.aot_fallbacks``.  The wrapper exposes ``.perf`` (the
    holder dict whose ``"record"`` the executor reads for MFU)."""
    m = metrics or _metrics()
    holder = {"exec": None, "record": None, "failed": False,
              "label": label}

    def call(*args):
        if holder["exec"] is None and not holder["failed"]:
            try:
                _capture(jitted, args, holder, m)
            except Exception:
                holder["failed"] = True
                m.inc("compile.capture_failures")
                logger.debug("compile capture failed for %r; running "
                             "the plain jit path", label, exc_info=True)
        if holder["exec"] is not None:
            try:
                return holder["exec"](*args)
            except TypeError:
                # argument signature drifted from the captured one
                # (checked before execution — donation is safe); the
                # plain jit path recompiles and keeps serving
                m.inc("compile.aot_fallbacks")
                return jitted(*args)
        return jitted(*args)

    call.perf = holder
    return call


def _capture(jitted, args, holder, m):
    t0 = time.perf_counter()
    traced = jitted.trace(*args)
    t1 = time.perf_counter()
    lowered = traced.lower()
    t2 = time.perf_counter()
    compiled = lowered.compile()
    t3 = time.perf_counter()

    _key_counter[0] += 1
    key = f"jit-{_key_counter[0]:04d}"
    flops, bytes_accessed = _cost_summary(compiled)
    memory = _memory_summary(compiled)
    record = {
        "key": key,
        "label": holder["label"] or key,
        "created_unix": time.time(),
        "flops": flops,
        "bytes_accessed": bytes_accessed,
        "memory": memory,
        "phases": {"trace_seconds": t1 - t0,
                   "lower_seconds": t2 - t1,
                   "backend_seconds": t3 - t2},
        "steps": 0,
        "last_step_seconds": None,
        "mfu": None,
    }
    _insert_record(record)
    holder["exec"] = compiled
    holder["record"] = record

    m.inc("compile.captures")
    m.observe("compile.phase_trace_seconds", t1 - t0)
    m.observe("compile.phase_lower_seconds", t2 - t1)
    m.observe("compile.phase_backend_seconds", t3 - t2)
    if flops is not None:
        m.observe("compile.cost_flops", flops)
    if bytes_accessed is not None:
        m.observe("compile.cost_bytes", bytes_accessed)
    if memory is not None:
        m.observe("compile.memory_temp_bytes", memory["temp_bytes"])
        _headroom_check(record, m)
    return record


def records():
    """Snapshot of the captured compile records, oldest first."""
    with _records_lock:
        return [dict(r, phases=dict(r["phases"]),
                     memory=(dict(r["memory"]) if r["memory"] else None))
                for r in _records.values()]


def reset_records():
    """Drop captured records (tests)."""
    with _records_lock:
        _records.clear()


def total_compile_seconds():
    """Summed trace+lower+backend wall time across captured records —
    the compile cost a cold process paid (what ``bench check`` guards
    via the ``compile_seconds`` trajectory row)."""
    total = 0.0
    for r in records():
        total += sum(r["phases"].values())
    return total


def compile_report():
    """The ``profile compile --json`` body (schema held stable by
    :func:`validate_report` and the selfcheck ``perf`` section)."""
    import jax
    try:
        backend = jax.default_backend()
    except Exception:
        backend = "unavailable"
    peak, basis = peak_flops_info()
    return {"format": REPORT_FORMAT, "backend": backend,
            "peak_flops_per_chip": peak, "mfu_basis": basis,
            "records": records()}


def validate_report(obj):
    """Schema problems of a :func:`compile_report` body as a list of
    strings (empty = valid)."""
    problems = []
    if not isinstance(obj, dict):
        return [f"report must be an object, got {type(obj).__name__}"]
    if obj.get("format") != REPORT_FORMAT:
        problems.append(f"format must be {REPORT_FORMAT}, "
                        f"got {obj.get('format')!r}")
    if obj.get("mfu_basis") not in MFU_BASES:
        problems.append(f"mfu_basis must be one of {MFU_BASES}, "
                        f"got {obj.get('mfu_basis')!r}")
    recs = obj.get("records")
    if not isinstance(recs, list):
        return problems + ["records must be a list"]
    for i, r in enumerate(recs):
        where = f"records[{i}]"
        if not isinstance(r, dict):
            problems.append(f"{where}: must be an object")
            continue
        for k in RECORD_KEYS:
            if k not in r:
                problems.append(f"{where}: missing key {k!r}")
        phases = r.get("phases")
        if not isinstance(phases, dict) or \
                any(k not in phases for k in PHASE_KEYS):
            problems.append(f"{where}: phases needs {PHASE_KEYS}")
        mem = r.get("memory")
        if mem is not None and (not isinstance(mem, dict) or
                                any(k not in mem for k in MEMORY_KEYS)):
            problems.append(f"{where}: memory needs {MEMORY_KEYS}")
        for k in ("flops", "bytes_accessed"):
            v = r.get(k)
            if v is not None and (not isinstance(v, (int, float))
                                  or isinstance(v, bool) or v < 0):
                problems.append(f"{where}: {k} must be a non-negative "
                                f"number or null")
    return problems


# ---------------------------------------------------------------------------
# live MFU
# ---------------------------------------------------------------------------

def note_step(record, seconds, gauge="train.mfu", devices=1,
              flops_scale=1, metrics=None):
    """Per-step MFU hook (called by the executor after every dispatch):
    with a captured record carrying cost-analysis FLOPs, derive
    ``flops * flops_scale / seconds / (peak * devices)`` into
    ``gauge``.  Without one (capture disabled/failed, interpret mode)
    this is a None check — the hot path stays inside the <5% overhead
    guard.  ``flops_scale`` exists for the ``run_steps`` scan path:
    XLA's cost analysis counts a loop body ONCE regardless of trip
    count, so the executor passes ``steps`` there."""
    if record is None or not seconds or seconds <= 0:
        return None
    flops = record.get("flops")
    if not flops:
        return None
    peak, _basis = peak_flops_info()
    mfu = flops * flops_scale / seconds / (peak * max(int(devices), 1))
    record["steps"] += 1
    record["last_step_seconds"] = seconds
    record["mfu"] = mfu
    (metrics or _metrics()).set_gauge(gauge, mfu)
    return mfu


# ---------------------------------------------------------------------------
# HBM census
# ---------------------------------------------------------------------------

#: scope-variable name prefixes that mark optimizer accumulator state
#: (``optimizer.py`` names accumulators ``<slot>.<param>_N`` via
#: ``unique_name(".".join([name, param.name]))``)
OPTIMIZER_STATE_PREFIXES = (
    "moment", "velocity", "beta1_pow", "beta2_pow", "inf_norm",
    "avg_squared", "mean_square", "squared_accumulator",
    "linear_accumulator",
)

#: census collections, in attribution priority order; provider-backed
#: collections claim their buffers before the scope walk (``kv_pages``:
#: a paged gen bundle's page pool + its host-side page tables;
#: ``gen_state``: a gen bundle's per-slot state that is not pages, e.g.
#: the recurrent state and conv window of a state-space layer)
HBM_COLLECTIONS = ("kv_pages", "gen_state", "prefetch", "embedding",
                   "optimizer", "params")

_hbm_lock = threading.Lock()
_hbm_providers = {}     # collection -> {token: callable}
_hbm_token = [0]
_hbm_high_watermark = [0.0]


def register_hbm_provider(collection, fn):
    """Register ``fn`` (no args -> iterable of device arrays) as a
    source of buffers for ``collection`` (``kv_pages`` / ``prefetch`` /
    custom).  Returns a token for :func:`unregister_hbm_provider`.
    Providers that raise are skipped, never fatal — the census is a
    diagnostic, not a dependency."""
    with _hbm_lock:
        _hbm_token[0] += 1
        token = _hbm_token[0]
        _hbm_providers.setdefault(collection, {})[token] = fn
    return token


def unregister_hbm_provider(token):
    with _hbm_lock:
        for fns in _hbm_providers.values():
            fns.pop(token, None)


def _provider_arrays(collection):
    with _hbm_lock:
        fns = list(_hbm_providers.get(collection, {}).values())
    out = []
    for fn in fns:
        try:
            out.extend(fn() or ())
        except Exception:
            logger.debug("hbm provider for %r raised; skipped",
                         collection, exc_info=True)
    return out


def _is_optimizer_state(name):
    base = name.rsplit("/", 1)[-1]
    return any(base.startswith(p) for p in OPTIMIZER_STATE_PREFIXES)


_limit_cache = [False, None]   # [resolved, value]


def hbm_limit_bytes():
    """Device memory limit for headroom accounting:
    ``PADDLE_TPU_HBM_LIMIT_BYTES`` wins (operators and tests), else the
    backend's ``memory_stats()['bytes_limit']`` (TPU/GPU report it, CPU
    does not), else None — the headroom check then stands down."""
    raw = os.environ.get("PADDLE_TPU_HBM_LIMIT_BYTES", "").strip()
    if raw:
        try:
            return int(float(raw))
        except ValueError:
            logger.warning("bad PADDLE_TPU_HBM_LIMIT_BYTES=%r; ignored",
                           raw)
    if _limit_cache[0]:
        return _limit_cache[1]
    limit = None
    try:
        import jax
        d = jax.devices()[0]
        stats = d.memory_stats() if hasattr(d, "memory_stats") else None
        if stats:
            limit = int(stats.get("bytes_limit") or 0) or None
    except Exception:
        limit = None
    _limit_cache[0], _limit_cache[1] = True, limit
    return limit


def live_device_bytes():
    """Total bytes of every live jax array in the process (the census
    denominator; best-effort — aliased views may double-count)."""
    import jax
    total = 0
    for a in jax.live_arrays():
        total += int(getattr(a, "nbytes", 0) or 0)
    return total


def hbm_census(scope=None, metrics=None):
    """One live-buffer walk attributed to collections, exported as the
    ``hbm.*`` gauges.  ``scope`` defaults to the ambient global scope;
    its device arrays split into ``params`` vs ``optimizer`` by the
    accumulator naming convention, provider-backed collections
    (``kv_pages``, ``prefetch``) claim their buffers first, and
    everything unattributed lands in ``other``.  Returns the census
    dict.  Cost is O(live arrays) — run it on the
    ``PADDLE_TPU_HBM_CENSUS`` cadence or from ``profile memory``, not
    per step."""
    import jax
    m = metrics or _metrics()
    counted = set()
    census = {c: 0 for c in HBM_COLLECTIONS}

    def claim(collection, arrays):
        for a in arrays:
            nbytes = getattr(a, "nbytes", None)
            if nbytes is None or not hasattr(a, "dtype"):
                continue
            i = id(a)
            if i in counted:
                continue
            counted.add(i)
            census[collection] += int(nbytes)

    claim("kv_pages", _provider_arrays("kv_pages"))
    claim("gen_state", _provider_arrays("gen_state"))
    claim("prefetch", _provider_arrays("prefetch"))
    claim("embedding", _provider_arrays("embedding"))

    if scope is None:
        from paddle_tpu.scope import global_scope
        scope = global_scope()
    # embedding tables are params by structure but their own memory
    # story (the axis the CTR workload scales along) — attribute them
    # by the table registry, ahead of the params split
    from paddle_tpu.embedding import is_table as _is_table
    emb_arrays, opt_arrays, param_arrays = [], [], []
    s = scope
    while s is not None:
        for name, v in s.items():
            if not hasattr(v, "nbytes") or not hasattr(v, "dtype"):
                continue  # readers, lod metadata, host objects
            if _is_table(name):
                emb_arrays.append(v)
            elif _is_optimizer_state(name):
                opt_arrays.append(v)
            else:
                param_arrays.append(v)
        s = s.parent
    claim("embedding", emb_arrays)
    claim("optimizer", opt_arrays)
    claim("params", param_arrays)

    total = 0
    attributed = 0
    for a in jax.live_arrays():
        nbytes = int(getattr(a, "nbytes", 0) or 0)
        total += nbytes
        if id(a) in counted:
            attributed += nbytes
    census["other"] = max(0, total - attributed)
    census["total"] = total
    if total > _hbm_high_watermark[0]:
        _hbm_high_watermark[0] = float(total)
    census["high_watermark"] = _hbm_high_watermark[0]

    m.inc("hbm.census_runs")
    m.set_gauge("hbm.params_bytes", census["params"])
    m.set_gauge("hbm.optimizer_bytes", census["optimizer"])
    m.set_gauge("hbm.kv_pages_bytes", census["kv_pages"])
    m.set_gauge("hbm.gen_state_bytes", census["gen_state"])
    m.set_gauge("hbm.prefetch_bytes", census["prefetch"])
    m.set_gauge("hbm.embedding_bytes", census["embedding"])
    m.set_gauge("hbm.other_bytes", census["other"])
    m.set_gauge("hbm.total_bytes", census["total"])
    m.set_gauge("hbm.high_watermark_bytes", census["high_watermark"])
    limit = hbm_limit_bytes()
    if limit is not None:
        census["limit"] = limit
        census["headroom"] = limit - total
        m.set_gauge("hbm.limit_bytes", limit)
        m.set_gauge("hbm.headroom_bytes", limit - total)
    return census


def _headroom_check(record, m):
    """Projected-footprint check for a freshly compiled program: its
    temp + output + generated-code bytes must fit beside the CURRENT
    live set (arguments are already live).  Warns — counter plus a log
    line naming the program — before the program ever runs."""
    limit = hbm_limit_bytes()
    mem = record.get("memory")
    if limit is None or mem is None:
        return
    live = live_device_bytes()
    projected = (mem["temp_bytes"] + mem["output_bytes"]
                 + mem["generated_code_bytes"])
    headroom = limit - live
    m.set_gauge("hbm.limit_bytes", limit)
    m.set_gauge("hbm.headroom_bytes", headroom)
    if projected > headroom:
        m.inc("hbm.headroom_warnings")
        logger.warning(
            "projected footprint of %s (%s) is %.1f MB but only %.1f MB "
            "of device memory remains beside the %.1f MB live set — the "
            "next dispatch may OOM",
            record["key"], record["label"], projected / 1e6,
            headroom / 1e6, live / 1e6)


# ---------------------------------------------------------------------------
# census cadence (the executor's per-step hook)
# ---------------------------------------------------------------------------

_census_interval = None
_census_due = 0.0


def arm_census(interval_seconds):
    """Arm (or, with None/0, disarm) the per-step census cadence.
    Re-arming at the SAME interval keeps the current due time — every
    ``Executor.__init__`` re-reads the env, and each construction must
    not force an immediate off-cadence census."""
    global _census_interval, _census_due
    if not interval_seconds:
        _census_interval = None
        return
    interval = float(interval_seconds)
    if _census_interval == interval:
        return
    _census_interval = interval
    _census_due = 0.0


def arm_census_from_env():
    """``PADDLE_TPU_HBM_CENSUS=<seconds>`` arms the cadence (called by
    ``Executor.__init__`` — idempotent, env wins over a previous
    programmatic arm only when set)."""
    raw = os.environ.get("PADDLE_TPU_HBM_CENSUS", "").strip()
    if not raw:
        return
    try:
        arm_census(float(raw))
    except ValueError:
        logger.warning("bad PADDLE_TPU_HBM_CENSUS=%r; census not armed",
                       raw)


def census_tick(scope=None):
    """The executor's per-step hook: a None check unarmed, one clock
    read armed-but-not-due, a full census when the interval elapsed."""
    global _census_due
    if _census_interval is None:
        return
    now = time.monotonic()
    if now < _census_due:
        return
    _census_due = now + _census_interval
    try:
        hbm_census(scope)
    except Exception:
        logger.warning("hbm census failed", exc_info=True)


# ---------------------------------------------------------------------------
# step-phase breakdown (paddle_tpu profile step)
# ---------------------------------------------------------------------------

_step_phases = False


def enable_step_phases():
    """Arm the executor's per-step feed/dispatch/device-wait/fetch
    series (``perf.step.*``) — adds one device sync per step, so this
    is a profiling mode (``paddle_tpu profile step``), not a
    steady-state default."""
    global _step_phases
    _step_phases = True


def disable_step_phases():
    global _step_phases
    _step_phases = False


def step_phases_enabled():
    return _step_phases


# ---------------------------------------------------------------------------
# warmup report
# ---------------------------------------------------------------------------

class WarmupReport(int):
    """``Executor.warmup``'s return value: still the fresh-compile count
    (int subclass — every existing caller keeps working), plus a
    per-bucket ``buckets`` list: ``{"signature": {name: shape},
    "compiles": n, "seconds": s, "cache": "cold" | "persistent-hit" |
    "warm"}`` — the observable form of a rolling restart's "warm via
    compile cache" claim, surfaced per bucket in serving ``/stats``."""

    def __new__(cls, compiles, buckets=()):
        obj = super().__new__(cls, int(compiles))
        obj.buckets = list(buckets)
        return obj

    @staticmethod
    def merge(*reports, **tags):
        """Concatenate reports; keyword tags are stamped onto every
        bucket of the matching positional report by index name
        (``merge(pre, dec, prefill=0, decode=1)`` is NOT the API —
        pass ``labels=("prefill", "decode")`` instead)."""
        labels = tags.pop("labels", None)
        buckets = []
        for i, rep in enumerate(reports):
            for b in getattr(rep, "buckets", ()):
                b = dict(b)
                if labels is not None:
                    b["program"] = labels[i]
                buckets.append(b)
        return WarmupReport(sum(int(r) for r in reports), buckets)
