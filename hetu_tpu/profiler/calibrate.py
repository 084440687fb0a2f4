"""Fit the cost model to MEASUREMENTS — the reference always measures.

Reference: python/hetu/profiler.py:390-608 — HetuProfiler times real ops
and NCCLProfiler times real collectives; every searcher consumes measured
costs, never an analytic prior.  hetu_tpu's Simulator defaults to the
roofline prior (cost_model.py); this module closes the loop:

  * `calibrate_simulator(mesh)` — one real matmul fits the MXU utilization,
    two real allreduce sizes per mesh axis fit the effective interconnect
    bandwidth (slope of bytes->time); returns a Simulator running on the
    FITTED ChipSpec plus the fit report, and persists both through the
    shared JSON cost cache so later runs skip the measurement.
  * `layer_spec_from_measurement` — Galvatron-style per-layer profiling:
    time a layer's forward and back out the FLOPs-equivalent the fitted
    simulator will reproduce, so searched plans rank layers by how they
    actually run, not how big their matmuls look on paper.

On one chip only the matmul calibration is meaningful (ICI needs
multiple real devices); on the CPU test mesh the whole loop runs and keeps
the plumbing honest.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import numpy as np

from hetu_tpu.profiler.cost_model import (
    ChipSpec, allreduce_time, detect_chip,
)
from hetu_tpu.profiler.profiler import CollectiveProfiler, OpProfiler
from hetu_tpu.profiler.simulator import LayerSpec, ShardOption, Simulator


def fit_mxu_util(profiler: OpProfiler, chip: ChipSpec, *,
                 m: int = 2048, k: int = 2048, n: int = 2048) -> float:
    """Measured bf16 matmul -> achieved fraction of the chip's peak."""
    t = profiler.time_matmul(m, k, n)
    achieved = 2.0 * m * k * n / t / chip.bf16_flops
    return float(np.clip(achieved, 1e-4, 1.0))


def fit_ici_bandwidth(cprof: CollectiveProfiler, axis: str, n_devices: int,
                      *, sizes: Tuple[int, int] = (1 << 20, 8 << 20),
                      ) -> Tuple[float, float]:
    """Two allreduce sizes -> (effective bytes/s, latency seconds).

    Ring allreduce moves 2*(n-1)/n * S bytes over the bottleneck link, so
    bw_eff = wire_bytes_delta / time_delta; the intercept is latency."""
    if n_devices < 2:
        raise ValueError(
            f"fit_ici_bandwidth needs a multi-device axis; axis {axis!r} has "
            f"{n_devices} device(s) (no wire traffic to fit)")
    s1, s2 = sizes
    t1 = cprof.allreduce_time(s1, axis)
    t2 = cprof.allreduce_time(s2, axis)
    wire = 2.0 * (n_devices - 1) / n_devices
    slope = max((t2 - t1) / (wire * (s2 - s1)), 1e-15)  # s per wire-byte
    bw = 1.0 / slope
    lat = max(t1 - wire * s1 / bw, 0.0)
    return float(bw), float(lat)


def calibrate_simulator(mesh=None, *, chip: Optional[ChipSpec] = None,
                        profiler: Optional[OpProfiler] = None,
                        axes: Optional[Sequence[str]] = None):
    """Measure, fit, and return (Simulator-on-fitted-chip, report dict).

    The fitted ChipSpec replaces `mxu_util` with the measured matmul
    efficiency and, when a multi-device mesh axis is given, `ici_bw` with
    the fitted allreduce bandwidth (ici_util folds to 1.0 — the fit IS the
    effective rate).  Measurements go through the profilers' JSON cost
    cache, so a committed cache file replays without touching devices."""
    chip = chip or detect_chip()
    profiler = profiler or OpProfiler()
    report = {"chip": chip.name}

    mxu = fit_mxu_util(profiler, chip)
    report["mxu_util_fit"] = mxu
    fitted = dataclasses.replace(chip, mxu_util=mxu)

    axis_rates = {}
    if mesh is not None:
        axes = list(axes) if axes is not None else \
            [a for a in mesh.axis_names if mesh.shape[a] > 1]
        cprof = CollectiveProfiler(mesh, cache=profiler.cache)
        bws = {}
        for ax in axes:
            bw, lat = fit_ici_bandwidth(cprof, ax, mesh.shape[ax])
            bws[ax] = {"bw_bytes_per_s": bw, "latency_s": lat}
            axis_rates[ax] = (bw, lat)
        report["ici_fit"] = bws
        if bws:
            # chip-level fallback rate for roles without a fitted axis:
            # the slowest fitted axis (conservative for plan feasibility);
            # fitted axes themselves keep their OWN rate via axis_rates —
            # multi-tier pricing, not worst-axis folding
            worst = min(b["bw_bytes_per_s"] for b in bws.values())
            fitted = dataclasses.replace(fitted, ici_bw=worst, ici_util=1.0)
    return Simulator(fitted, axis_rates=axis_rates), report


def simulator_from_calibration(report, *, axis_of=None) -> Simulator:
    """Rebuild a Simulator from a persisted calibration report.

    ``report``: the dict `calibrate_simulator` returns (also the content
    of CALIBRATION.json written by tools/calibrate_chip.py), or a path to
    such a JSON file.  The fitted mxu_util and per-axis ici rates are
    re-applied, so searchers price plans from the last real measurement
    without touching devices — the reference's cached-cost contract
    (python/hetu/profiler.py:609-1266 replays its pickled op times the
    same way).  ``axis_of`` maps parallelism roles to fitted mesh axes
    (see Simulator).
    """
    import json
    import pathlib

    if isinstance(report, (str, pathlib.Path)):
        report = json.loads(pathlib.Path(report).read_text())
    chip = detect_chip()
    if report.get("chip") and report["chip"] != chip.name:
        import warnings

        # a foreign-chip fit still applies RELATIVELY (axis-rate ratios
        # order collectives correctly) but absolute times will be off
        warnings.warn(
            f"calibration was fitted on {report['chip']!r} but this "
            f"backend detects {chip.name!r}; applying it anyway — "
            "rankings stay meaningful, absolute times may not",
            stacklevel=2)
    fitted = dataclasses.replace(
        chip, mxu_util=float(report.get("mxu_util_fit", chip.mxu_util)))
    axis_rates = {}
    for ax, fit in (report.get("ici_fit") or {}).items():
        axis_rates[ax] = (float(fit["bw_bytes_per_s"]),
                          float(fit["latency_s"]))
    if axis_rates:
        worst = min(bw for bw, _ in axis_rates.values())
        fitted = dataclasses.replace(fitted, ici_bw=worst, ici_util=1.0)
    return Simulator(fitted, axis_rates=axis_rates, axis_of=axis_of)


def layer_spec_from_measurement(name: str, fwd_fn, args, *,
                                param_bytes: float, act_bytes: float,
                                options: Optional[Sequence[ShardOption]]
                                = None,
                                profiler: Optional[OpProfiler] = None,
                                chip: Optional[ChipSpec] = None,
                                sim: Optional[Simulator] = None,
                                ) -> LayerSpec:
    """Build a LayerSpec whose cost comes from TIMING fwd_fn(*args).

    The measured forward time is converted to the FLOPs-equivalent that
    `Simulator.layer_time` maps back to the same duration (under the
    simulator's chip), so analytic and measured LayerSpecs mix freely in
    one search — the Galvatron profile-then-plan workflow
    (tools/Galvatron profiling configs -> search)."""
    profiler = profiler or OpProfiler()
    if sim is not None:
        chip = sim.chip
        cal = sim.cal
    else:
        chip = chip or detect_chip()
        cal = 1.0
    t = profiler.time_fn(fwd_fn, *args, key=f"layer:{name}")
    flops_equiv = t * chip.bf16_flops * chip.mxu_util / cal
    return LayerSpec(
        name=name, flops=float(flops_equiv),
        param_bytes=float(param_bytes), act_bytes=float(act_bytes),
        options=list(options) if options is not None
        else [ShardOption("dp")])
