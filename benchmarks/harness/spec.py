"""Reading the benchmark's data files: manifest, configurations, traffic
mixes and per-layer metrics, each found by the name ``BENCHMARK.json``
gives it, and the two modules a configuration file names: its
architecture's adapter (``"adapter"``, a dotted module path) and its plain
reference (``"reference"``, a file).  Adding a cell, a configuration, a mix,
a metric or an architecture is adding files and an entry; nothing here, in
the rest of ``harness/``, in ``readers/`` or in ``run.py`` names one, or
reads a key that only one architecture's configuration has."""

from __future__ import annotations

import importlib
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks"


def _read(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def manifest() -> dict:
    return _read(ROOT / "BENCHMARK.json")


def cell(man: dict, name: str) -> dict:
    for w in man["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                   f"{[w['name'] for w in man['workloads']]}")


def config(man: dict, name: str, *, rehearse: bool = False) -> dict:
    entry = next(c for c in man["configs"] if c["name"] == name)
    cfg = _read(ROOT / entry["file"])
    if rehearse:
        # tiny widths for the CPU rehearsal; never a measurement
        cfg = _merge(cfg, cfg["rehearse"])
    cfg["name"] = name
    return cfg


def adapter(config: dict):
    """The adapter of the configuration's architecture: the one module that
    knows the architecture's keys.  What it has to offer is listed in
    ``benchmarks/arch/__init__.py``."""
    return importlib.import_module(config["adapter"])


def reference(config: dict):
    """The configuration's plain reference, by the file it names."""
    return importlib.import_module(
        ".".join(Path(config["reference"]).with_suffix("").parts))


def traffic(name: str, *, rehearse: bool = False) -> dict:
    tr = _read(BENCH / "traffic" / f"{name}.json")
    if rehearse:
        tr = _merge(tr, tr.get("rehearse", {}))
    tr["name"] = name
    return tr


def metrics_of(entries, cell_name: str) -> list:
    """The entries of ``end_to_end`` or ``per_layer`` a cell reports."""
    return [m for m in entries
            if "workloads" not in m or cell_name in m["workloads"]]


def layer_metric_path(name: str) -> Path:
    """The file that says how a per-layer metric is read:
    ``benchmarks/layer_metrics/<name>.json``.  A name with suffixes
    (``device_idle_share.serve``) that has no file of its own is read as
    its stem is (``device_idle_share.json``), so the same quantity under
    another end-to-end metric needs an entry and no file."""
    parts = name.split(".")
    for n in range(len(parts), 0, -1):
        path = BENCH / "layer_metrics" / (".".join(parts[:n]) + ".json")
        if path.is_file():
            return path
    raise FileNotFoundError(
        f"no file for per-layer metric {name!r} under "
        f"{BENCH / 'layer_metrics'}")


def layer_metric_file(name: str) -> dict:
    """How a per-layer metric is read: ``{"reader": ..., "params": {...}}``
    from its file.  ``BENCHMARK.json`` alone says what the metric is (layer,
    unit, moves, cells): one entry a quantity, its ``workloads`` the cells
    that report it."""
    return _read(layer_metric_path(name))
