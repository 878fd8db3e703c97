"""Load ``BENCHMARK.json`` and the data files it names, and refuse what
the benchmark's contract refuses: unknown names, units or characters."""

from __future__ import annotations

import json
import os
import re

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


class ManifestError(ValueError):
    pass


def _name(value, what):
    if not isinstance(value, str) or not NAME_RE.match(value):
        raise ManifestError(f"{what} {value!r}: a name is 1-64 letters, "
                            f"digits, '_', '.', '-' and starts with a "
                            f"letter, a digit or '_'")
    return value


def _line(value, what):
    if not isinstance(value, str) or not 1 <= len(value) <= 200 \
            or "\n" in value or "\t" in value:
        raise ManifestError(f"{what}: 1-200 characters on one line, "
                            f"got {value!r}")
    return value


def _metric(entry, kind, e2e_names, cell_names):
    allowed = {"name", "unit", "better", "source", "workloads"}
    allowed |= {"bound"} if kind == "end_to_end" else {"layer", "moves"}
    extra = set(entry) - allowed
    if extra:
        raise ManifestError(f"metric {entry.get('name')!r}: keys {extra}")
    _name(entry.get("name"), "metric")
    if not isinstance(entry.get("unit"), str) or \
            not UNIT_RE.match(entry["unit"]):
        raise ManifestError(f"metric {entry['name']}: unit "
                            f"{entry.get('unit')!r}")
    if entry.get("better") not in ("lower", "higher"):
        raise ManifestError(f"metric {entry['name']}: better")
    if entry.get("source") not in SOURCES:
        raise ManifestError(f"metric {entry['name']}: source "
                            f"{entry.get('source')!r}")
    for cell in entry.get("workloads", []):
        if cell not in cell_names:
            raise ManifestError(f"metric {entry['name']}: unknown cell "
                                f"{cell!r}")
    if kind == "end_to_end":
        if entry["source"] not in ("host_clock", "device_trace"):
            raise ManifestError(f"end-to-end metric {entry['name']}: "
                                f"source {entry['source']!r}")
        bound = entry.get("bound")
        if not isinstance(bound, (int, float)) or not 0 < bound <= 0.1:
            raise ManifestError(f"metric {entry['name']}: bound {bound!r}")
    else:
        _line(entry.get("layer"), f"metric {entry['name']} layer")
        if entry.get("moves") not in e2e_names:
            raise ManifestError(f"metric {entry['name']}: moves "
                                f"{entry.get('moves')!r} is no "
                                f"end-to-end metric")


def validate(manifest):
    if set(manifest) - {"trace_in_run"} != TOP_KEYS:
        raise ManifestError(f"BENCHMARK.json keys {sorted(manifest)} != "
                            f"{sorted(TOP_KEYS)} (+ trace_in_run)")
    if manifest.get("trace_in_run", True) is not True:
        raise ManifestError("trace_in_run is true or absent")
    if not isinstance(manifest["run_seconds"], int) or \
            not 1 <= manifest["run_seconds"] <= 51:
        raise ManifestError("run_seconds")
    paths = manifest["paths"]
    configs, seen = {}, set()
    for c in manifest["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            raise ManifestError(f"config keys {sorted(c)}")
        _name(c["name"], "config")
        _line(c["source"], "config source")
        _line(c["why"], "config why")
        for key in c["reduced"]:
            _name(key, "reduced key")
        if not any(c["file"].startswith(p.rstrip("/") + "/")
                   for p in paths):
            raise ManifestError(f"config file {c['file']!r} outside paths")
        if c["name"] in configs or c["file"] in seen:
            raise ManifestError(f"config {c['name']!r} twice")
        configs[c["name"]] = c
        seen.add(c["file"])
    cells, pairs = {}, set()
    for w in manifest["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            raise ManifestError(f"workload keys {sorted(w)}")
        _name(w["name"], "cell")
        _name(w["traffic"], "traffic")
        _line(w["why"], "cell why")
        if w["config"] not in configs:
            raise ManifestError(f"cell {w['name']}: unknown config "
                                f"{w['config']!r}")
        if w["chips"] not in (1, 4):
            raise ManifestError(f"cell {w['name']}: chips {w['chips']!r}")
        if w["name"] in cells or (w["config"], w["traffic"]) in pairs:
            raise ManifestError(f"cell {w['name']!r} twice")
        cells[w["name"]] = w
        pairs.add((w["config"], w["traffic"]))
    four = sum(1 for w in cells.values() if w["chips"] == 4)
    if four > max(1, len(cells) // 4):
        raise ManifestError(f"{four} four-chip cells of {len(cells)}")
    e2e = {m.get("name") for m in manifest["end_to_end"]}
    if "setup_s" not in e2e:
        raise ManifestError("end_to_end lacks setup_s")
    names = set()
    for kind in ("end_to_end", "per_layer"):
        for m in manifest[kind]:
            _metric(m, kind, e2e, cells)
            if m["name"] in names:
                raise ManifestError(f"metric {m['name']!r} twice")
            names.add(m["name"])
    return manifest


def metrics_of(manifest, kind, cell):
    """The cell's metrics of one kind: those without a ``workloads`` key
    and those that list the cell."""
    return [m for m in manifest[kind]
            if "workloads" not in m or cell in m["workloads"]]


def load(root):
    """``(manifest, read)`` where ``read(relative path)`` loads a JSON
    file of the checkout."""
    def read(rel):
        with open(os.path.join(root, rel)) as f:
            return json.load(f)
    return validate(read("BENCHMARK.json")), read


def cell_files(manifest, cell, paths_root="benchmark"):
    """The data files of one cell, found by name alone."""
    try:
        entry = next(w for w in manifest["workloads"] if w["name"] == cell)
    except StopIteration:
        raise ManifestError(f"unknown cell {cell!r}; BENCHMARK.json has "
                            f"{[w['name'] for w in manifest['workloads']]}"
                            ) from None
    config = next(c for c in manifest["configs"]
                  if c["name"] == entry["config"])
    return entry, config, f"{paths_root}/workloads/{cell}.json"
