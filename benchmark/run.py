#!/usr/bin/env python3
"""Run ONE cell of the benchmark once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data, found by name: ``BENCHMARK.json`` (cells,
configurations, metrics, bounds), ``benchmark/configs/<configuration>.json``,
``benchmark/workloads/<cell>.json`` (traffic kind + parameters),
``benchmark/traffic/<kind>.py`` (the one generator of that kind) and
``benchmark/layer_metrics/<metric>.{json,py}`` (one reader per per-layer
metric).  There is no ``if workload == ...`` here.

The LAST stdout line is the contract's JSON object and nothing else; what
else is worth seeing goes on earlier lines (one JSON object each) and into
``benchmark/out/``.  Without a TPU (or with fewer chips than the cell asks
for) the run exits non-zero and prints no result line.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
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
    of its window that is traced; off (both no-ops) in a ``--trace 0``
    run.  One session per run."""

    def __init__(self, enabled, trace_dir):
        self.enabled = bool(enabled)
        self.trace_dir = trace_dir
        self.t_start = self.t_stop = None
        self._on = False

    def start(self):
        if not self.enabled or self.t_start is not None:
            return
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # device ops and host runtime only
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._on = True
        self.t_start = time.perf_counter()

    def stop(self):
        if not self._on:
            return
        import jax
        self.t_stop = time.perf_counter()
        self._on = False
        jax.profiler.stop_trace()

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
    returns None and the metric is left out of the line."""
    from lib import readers
    out = {}
    for m in entries:
        base = os.path.join(HERE, "layer_metrics", m["name"])
        with open(base + ".json") as f:
            spec = json.load(f)
        if os.path.exists(base + ".py"):
            reader = load_module(base + ".py",
                                 "layer_metric_" + m["name"].replace(".", "_"))
            value = reader.read(run, spec)
        else:
            value = readers.generic(run, spec)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def breakdown_of(summary, top=10):
    """Top device operations, and the longest idle gaps named by the
    operations on either side (host spans are not on the device's clock
    yet, so a gap cannot be named by what the host did)."""
    events = summary["per_device"][min(summary["per_device"])]
    merged = _xtrace.union_intervals([(ev[0], ev[1]) for ev in events])
    ends, starts = {}, {}
    for ev in events:
        ends[ev[1]] = ev[2]
        starts.setdefault(ev[0], ev[2])
    by_name = {}
    for (_, a1), (b0, _) in zip(merged, merged[1:]):
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

    tracer = DeviceTracer(bool(trace) and not rehearsal,
                          os.path.join(out_dir, "trace"))
    ctx = make_context(cell, seed, seconds, config, workload, used, tracer,
                       traced=bool(trace), rehearsal=bool(rehearsal))

    state = traffic.setup(ctx)
    try:
        t_window = time.time()
        raw = traffic.window(state, ctx)
        tracer.stop()
        verdict = traffic.verify(state, ctx, raw)
    finally:
        traffic.close(state)
    setup_s = t_window - t_proc

    metrics = {}
    if not trace:
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
    if trace:
        run = {"cell": cell, "config": config, "workload": workload,
               "chips": entry["chips"], "raw": raw,
               "spans": raw.get("spans", []),
               "counters": raw.get("counters", {}),
               "facts": raw.get("facts", {}), "trace": None, "peaks": None}
        if tracer.window_s is not None:
            xplane = _xtrace.find_xplane(tracer.trace_dir)
            summary = _xtrace.summarize(xplane, n_devices=entry["chips"],
                                        top=40)
            run["trace"] = summary
            run["trace_window_s"] = tracer.window_s
            run["peaks"] = _peaks.peaks_for(device["kind"])
            device_out["busy_s"] = summary["busy_s"]
            device_out["window_s"] = tracer.window_s
            if summary["per_device"]:
                result["breakdown"] = breakdown_of(summary)
            say("trace", file=xplane, lines=summary.get("lines"),
                busy_s=summary["busy_s"], window_s=tracer.window_s,
                top_ops=summary["device_ops"])
        metrics = read_layer_metrics(
            _manifest.metrics_of(manifest, "per_layer", cell), run)
    result["metrics"] = metrics
    result["device"] = device_out
    if rehearsal:
        result["rehearsal"] = True
    say("verdict", **verdict)
    say("observed", setup_s=setup_s, **raw.get("observed", {}))
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds, args.trace)
    except NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 1
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
