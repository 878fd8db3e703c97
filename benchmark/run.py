#!/usr/bin/env python3
"""Run ONE cell of the benchmark once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1|2>

``--trace 0`` measures the end-to-end metrics with all tracing off,
``--trace 1`` is a traced run of its own that reads the per-layer ones,
and ``--trace 2`` is a ``--trace 0`` run that, once its measured window
has closed and been judged, traces a few seconds of the same traffic in
the same process and prints both kinds of metric on the one last line.

Everything about a cell is data, found by name: ``BENCHMARK.json`` (cells,
configurations, metrics, bounds), ``benchmark/configs/<configuration>.json``,
``benchmark/workloads/<cell>.json`` (traffic kind + parameters),
``benchmark/traffic/<kind>.py`` (the one generator of that kind) and
``benchmark/layer_metrics/<metric>.{json,py}`` (one reader per per-layer
metric).  There is no ``if workload == ...`` here.

The LAST stdout line is the contract's JSON object and nothing else; what
else is worth seeing goes on earlier lines (one JSON object each) and into
``benchmark/out/``.  The numbers ``correct`` was decided from come last in
that object (``compared``: each reading beside its limit) and are the last
lines on standard error.  Without a TPU (or with fewer chips than the cell
asks for) the run exits non-zero and prints no result line.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import time

_IMPORT_UNIX = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from lib import manifest as _manifest  # noqa: E402
from lib import peaks as _peaks        # noqa: E402
from lib import spanclock as _spanclock  # noqa: E402
from lib import xtrace as _xtrace      # noqa: E402


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def process_start_unix():
    """When this process started (``/proc``), else when this file was
    imported: ``setup_s`` counts from here."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - int(fields[19]) / os.sysconf("SC_CLK_TCK")
        started = time.time() - age
        if 0 <= _IMPORT_UNIX - started < 60:
            return started
    except (OSError, ValueError, IndexError):
        pass
    return _IMPORT_UNIX


def say(kind, **facts):
    """One observation line (never the last line of a run)."""
    print(json.dumps({"note": kind, **facts}, default=str), flush=True)


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def mixed_seed(seed, bits=31):
    """Fold any whole number (the driver's seeds pass 2**31) into
    ``bits`` bits for generators that take a signed 32-bit seed."""
    seed = int(seed)
    return (seed ^ (seed >> bits) ^ (seed >> (2 * bits))) & ((1 << bits) - 1)


def require_chips(chips):
    import jax
    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    if info["platform"] != "tpu":
        raise NoChip(f"jax found no TPU: {info}")
    if info["count"] < chips:
        raise NoChip(f"the cell needs {chips} chip(s), jax sees "
                     f"{info['count']}")
    return devices, info


class DeviceTracer:
    """Hands the traffic module ``start()`` / ``stop()`` around the part
    of its traffic that is traced; off (both no-ops) in a ``--trace 0``
    run.  One session per run, started and stopped through the program's
    own tracing control (``paddle_tpu.profiler``), whose returned dict
    (``session``: the xplane written, ``span_to_trace_ns``) every reader
    is handed."""

    def __init__(self, enabled, trace_dir):
        self.enabled = bool(enabled)
        self.trace_dir = trace_dir
        self.t_start = self.t_stop = self.session = None
        self._on = False
        self.costs = {}     # seconds the control's own calls took

    def _control(self, call, *args, **kwargs):
        """One call of the program's tracing control; its failure is the
        profiler's, whoever made the call."""
        try:
            return call(*args, **kwargs)
        except Exception as e:
            raise _xtrace.TracePartFailed("profiler", repr(e)) from e

    def start(self):
        if not self.enabled or self.t_start is not None:
            return
        from paddle_tpu import profiler
        t0 = time.perf_counter()
        self._control(profiler.start_profiler, profile_path=self.trace_dir)
        self._on = True
        self.t_start = time.perf_counter()
        self.costs["start_s"] = self.t_start - t0

    def stop(self):
        if not self._on:
            return
        from paddle_tpu import profiler
        self.t_stop = time.perf_counter()
        self._on = False
        self.session = self._control(profiler.stop_profiler)
        self.costs["stop_s"] = time.perf_counter() - self.t_stop

    def warm(self):
        """Start and stop the profiler once and throw that trace away,
        so that the cost of the first start falls into no number."""
        from paddle_tpu import profiler
        t0 = time.perf_counter()
        self._control(profiler.start_profiler,
                      profile_path=self.trace_dir + ".warm")
        t1 = time.perf_counter()
        self._control(profiler.stop_profiler)
        self.costs.update(first_start_s=t1 - t0,
                          first_stop_s=time.perf_counter() - t1)
        shutil.rmtree(self.trace_dir + ".warm", ignore_errors=True)

    @property
    def window_s(self):
        if self.t_start is None or self.t_stop is None:
            return None
        return self.t_stop - self.t_start


def memory_peak_bytes(devices, program_peak=None):
    """What the result's ``memory_peak_bytes`` carries: the most the
    measured window holds on the fullest chip, as far as it can be known.
    That is the larger of the bytes in use when the window has ended
    (weights, pools and whatever else stays on the device) and, where
    the traffic module can ask the program's executables for it, the
    compiler's account of the largest one (arguments + outputs +
    temporaries - aliased): the TPU runtime's own counters leave a
    running program's temporaries out (PR 22), so for a training step
    the compiler's account is the only figure there is.  The
    allocator's all-time ``peak_bytes_in_use`` also covers set-up (a
    server's load holds its weights twice for a moment): it goes on the
    note line, not into the result."""
    held = []
    for d in devices:
        stats = d.memory_stats() or {}
        say("memory_stats", device=str(d), **{k: v for k, v in stats.items()
                                              if isinstance(v, (int, float))})
        if "bytes_in_use" in stats:
            held.append(int(stats["bytes_in_use"]))
    say("memory", in_use_at_window_end=max(held) if held else None,
        compiler_account_of_largest_executable=program_peak,
        result_carries="the larger of the two")
    if program_peak:
        held.append(int(program_peak))
    return max(held) if held else None


def read_layer_metrics(entries, run):
    """Each per-layer metric's own reader; one that finds nothing to read
    returns None and the metric is left out of the line.  A reader that
    raises is named in what is raised (part ``reader``)."""
    from lib import readers
    out = {}
    for m in entries:
        folder = os.path.join(HERE, "layer_metrics")
        with open(os.path.join(folder, m["name"] + ".json")) as f:
            spec = json.load(f)
        # ``"like": <metric>`` = read as that metric is read (its reader
        # and its parameters), under this one's name: one quantity, cells
        # that move different end-to-end metrics
        reads_as = spec.get("like", m["name"])
        if "like" in spec:
            with open(os.path.join(folder, reads_as + ".json")) as f:
                spec = {**json.load(f), **spec}
        try:
            if os.path.exists(os.path.join(folder, reads_as + ".py")):
                reader = load_module(
                    os.path.join(folder, reads_as + ".py"),
                    "layer_metric_" + m["name"].replace(".", "_"))
                value = reader.read(run, spec)
            else:
                value = readers.generic(run, spec)
        except Exception as e:
            raise _xtrace.TracePartFailed(
                "reader", f"{m['name']}: {e!r}") from e
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def breakdown_of(run, top=10):
    """Top device operations, and the idle gaps of the first chip, each
    named by the innermost program span that covers most of it on the
    dispatching thread (``host:<span>``; the spans are put on the
    trace's clock by the tracing control's ``span_to_trace_ns``).  A gap
    that no span covers keeps the name of the device operations on
    either side (``after:<op>|before:<op>``)."""
    summary = run["trace"]
    events = summary["per_device"][min(summary["per_device"])]
    merged = _xtrace.union_intervals([(ev[0], ev[1]) for ev in events])
    gaps = [(a1, b0) for (_, a1), (b0, _) in zip(merged, merged[1:])]
    timeline = _spanclock.host_timeline(run)
    under = _spanclock.name_gaps(gaps, timeline or [])
    ends, starts = {}, {}
    for ev in events:
        ends[ev[1]] = ev[2]
        starts.setdefault(ev[0], ev[2])
    by_name = {}
    for (a1, b0), covered in zip(gaps, under):
        span = max(covered, key=covered.get) if covered else None
        if span is not None:
            name = f"host:{span}"
        else:
            name = (f"after:{ends.get(a1, '?')[:40]}|before:"
                    f"{starts.get(b0, '?')[:40]}")
        n, total = by_name.get(name, (0, 0.0))
        by_name[name] = (n + 1, total + (b0 - a1) / 1e9)
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1][1])[:top]
    return {"device_ops": summary["device_ops"][:top],
            "idle_gaps": [[f"{name} x{n}", secs]
                          for name, (n, secs) in ranked]}


CACHE_ROOT = os.path.join(HERE, "cache")


def enable_cache():
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (``JAX_COMPILATION_CACHE_DIR`` wins where it is set)."""
    from paddle_tpu.executor import enable_compile_cache
    enable_compile_cache(os.path.join(CACHE_ROOT, "jax"))


def make_context(cell, seed, seconds, config, workload, devices, tracer,
                 traced=False, rehearsal=False):
    """What a traffic module's ``setup/window/verify`` are handed."""
    return {"cell": cell, "seed": int(seed), "seed31": mixed_seed(seed),
            "seconds": float(seconds), "traced": traced, "config": config,
            "workload": workload, "chips": len(devices), "devices": devices,
            "cache_root": CACHE_ROOT, "say": say, "rehearsal": rehearsal,
            "tracer": tracer}


def run_cell(cell, seed, seconds, trace, rehearsal=None):
    """Run one cell; returns the result object.  ``rehearsal`` (tests
    only, never the command line) is a dict of toy-size overrides
    ``{"config": {...}, "workload": {...}}``: the chip is then not asked
    for, and the result is marked so that it is never printed as one."""
    t_proc = process_start_unix()
    manifest, read = _manifest.load(ROOT)
    entry, config_entry, workload_file = _manifest.cell_files(manifest, cell)
    config = read(config_entry["file"])
    workload = read(workload_file)
    if rehearsal:
        config = {**config, **rehearsal.get("config", {})}
        workload = {**workload, **rehearsal.get("workload", {})}
    traffic = load_module(os.path.join(HERE, "traffic",
                                       workload["kind"] + ".py"),
                          "traffic_" + workload["kind"])

    import jax
    if rehearsal:
        devices = jax.devices()
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices)}
    else:
        devices, device = require_chips(entry["chips"])
    used = devices[:entry["chips"]]

    out_dir = os.path.join(HERE, "out", f"{cell}-{seed}-t{int(trace)}")
    os.makedirs(out_dir, exist_ok=True)
    enable_cache()

    # a --trace 2 run is a --trace 0 run until its window is closed and
    # judged: the traffic module sees ``traced`` false and no tracing is
    # on; the tracer it is handed is used by its ``traced`` entry point
    tracer = DeviceTracer(trace == 2 or (trace == 1 and not rehearsal),
                          os.path.join(out_dir, "trace"))
    ctx = make_context(cell, seed, seconds, config, workload, used, tracer,
                       traced=trace == 1, rehearsal=bool(rehearsal))

    stretch = None
    state = traffic.setup(ctx)
    try:
        t_window = time.time()
        raw = traffic.window(state, ctx)
        tracer.stop()
        verdict = traffic.verify(state, ctx, raw)
        if trace == 2:
            t_phase = time.perf_counter()
            stretch = traced_stretch(traffic, state, ctx, tracer)
            t_stretch = time.perf_counter()
    finally:
        traffic.close(state)
    setup_s = t_window - t_proc

    metrics = {}
    if trace != 1:
        values = dict(raw["end_to_end"], setup_s=setup_s)
        for m in _manifest.metrics_of(manifest, "end_to_end", cell):
            if values.get(m["name"]) is None:
                raise RuntimeError(f"cell {cell} did not measure "
                                   f"{m['name']}")
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
    device_out = dict(device)
    device_out["memory_peak_bytes"] = memory_peak_bytes(
        used, raw.get("program_peak_bytes"))
    result = {"correct": bool(verdict["correct"]),
              "attempted": int(raw["attempted"]),
              "failed": int(raw["failed"])}
    if trace == 1 or stretch is not None:
        # what every reader is handed: spans and the device trace of the
        # traced stretch, counters and client-side facts of the measured
        # window (in a --trace 1 run the stretch lies inside the window)
        stretch = stretch or {}
        spans = stretch.get("spans", raw.get("spans", []))
        if trace == 2:
            # only what lies between the profiler's two clock marks, so
            # that span and device-trace metrics cover the same seconds
            spans = _spanclock.inside_session(spans, tracer.session)
        run = {"cell": cell, "config": config, "workload": workload,
               "chips": entry["chips"], "raw": raw, "spans": spans,
               "counters": raw.get("counters", {}),
               "facts": {**raw.get("facts", {}),
                         **stretch.get("facts", {})},
               "trace": None, "peaks": None, "session": tracer.session}
        try:
            result.update(reduce_trace(run, tracer, device, device_out))
            metrics.update(read_layer_metrics(
                _manifest.metrics_of(manifest, "per_layer", cell), run))
        except Exception as e:      # the measured numbers outlive it
            if trace == 1:
                raise
            say("trace_failed", part=getattr(e, "part", "reader"),
                where="reduce", error=repr(e))
    if trace == 2:
        shutil.rmtree(tracer.trace_dir, ignore_errors=True)
        say("trace_phase", trace_phase_s=time.perf_counter() - t_phase,
            traced_stretch_s=t_stretch - t_phase, **tracer.costs,
            **(stretch or {}).get("observed", {}))
    result["metrics"] = metrics
    result["device"] = device_out
    if rehearsal:
        result["rehearsal"] = True
    say("verdict", **verdict)
    say("observed", setup_s=setup_s, **raw.get("observed", {}))
    # last of the line: every number ``correct`` was decided from, as
    # ``name: [reading, limit]`` (a limit of null: reported, not held)
    result["compared"] = verdict.get("compared", {})
    return result


def traced_stretch(traffic, state, ctx, tracer):
    """The traced part of a ``--trace 2`` run, after the measured window:
    the profiler is started and stopped once for nothing, then the
    traffic module's ``traced(state, ctx)`` sends a few seconds of the
    same traffic with the program's spans and the profiler on (it calls
    ``ctx["tracer"].start()`` / ``stop()`` as in a ``--trace 1`` window)
    and returns ``spans`` and ``facts`` of that stretch.  It stays on
    this, the main thread: ``jax.profiler.stop_trace`` takes three times
    as long from any other (PERF.md), so a traffic module bounds every
    wait of its ``traced`` itself.  A failure in here is reported with
    the part that failed (``lib.xtrace.TracePartFailed``) and loses only
    the per-layer metrics."""
    try:
        tracer.warm()
        return traffic.traced(state, ctx)
    except Exception as e:
        say("trace_failed", part=getattr(e, "part", "traffic"),
            where="traced stretch", error=repr(e))
        return None
    finally:
        tracer.stop()


def reduce_trace(run, tracer, device, device_out):
    """Reduce the device trace (if one was taken) into ``run`` and
    ``device_out``; returns what the result gains (``breakdown``)."""
    if tracer.window_s is None:
        return {}
    xplane = (tracer.session or {}).get("xplane") \
        or _xtrace.find_xplane(tracer.trace_dir)
    summary = _xtrace.summarize(xplane, n_devices=run["chips"], top=40)
    run["trace"] = summary
    run["trace_window_s"] = tracer.window_s
    say("trace", file=xplane, lines=summary.get("lines"),
        busy_s=summary["busy_s"], window_s=tracer.window_s,
        top_ops=summary["device_ops"],
        session={k: v for k, v in (tracer.session or {}).items()
                 if k != "xplane"})
    if not summary["per_device"]:
        return {}       # no device plane (a rehearsal on the CPU)
    run["peaks"] = _peaks.peaks_for(device["kind"])
    device_out["busy_s"] = summary["busy_s"]
    device_out["window_s"] = tracer.window_s
    say("clock_agreement", **(_spanclock.agreement(run) or {}))
    idle = _spanclock.idle_by_span(run)
    if idle:
        say("idle_by_span", seconds={str(k): v for k, v in sorted(
            idle.items(), key=lambda kv: -kv[1])})
    return {"breakdown": breakdown_of(run)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1, 2), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds, args.trace)
    except NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 1
    sys.stdout.flush()
    for name, (reading, limit) in result["compared"].items():
        print(f"compared {name}: {reading!r} limit {limit!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
