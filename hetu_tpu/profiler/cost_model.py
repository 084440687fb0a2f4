"""Analytic chip/interconnect cost model.

Reference: the measured-cost side lives in profiler.py; this is the roofline
prior the simulator falls back to when no measurement exists (the reference
always measures — on TPU the published chip specs make a good prior, and
the public scaling-book methodology is exactly this arithmetic).

Numbers are per-chip peak specs from public documentation; effective
utilization factors default conservatively and are calibratable from one
OpProfiler.time_matmul measurement.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax


@dataclass
class ChipSpec:
    name: str
    bf16_flops: float        # peak bf16 FLOP/s (MXU)
    hbm_bw: float            # bytes/s
    hbm_bytes: float         # capacity
    ici_bw: float            # bytes/s per direction, all links combined
    dcn_bw: float            # bytes/s per host
    mxu_util: float = 0.55   # achievable fraction of peak on big matmuls
    ici_util: float = 0.7


# Per-chip peaks, keyed by the name :func:`detect_chip` maps a
# ``device_kind`` to.  Sources: Google Cloud TPU documentation, "TPU v5e"
# (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s), "TPU v5p", "TPU v4".
CHIPS = {
    "v5e": ChipSpec("v5e", bf16_flops=197e12, hbm_bw=819e9, hbm_bytes=16e9,
                    ici_bw=4 * 112.5e9 / 2, dcn_bw=25e9),
    "v5p": ChipSpec("v5p", bf16_flops=459e12, hbm_bw=2765e9, hbm_bytes=95e9,
                    ici_bw=6 * 200e9 / 2, dcn_bw=25e9),
    "v4": ChipSpec("v4", bf16_flops=275e12, hbm_bw=1228e9, hbm_bytes=32e9,
                   ici_bw=6 * 100e9 / 2, dcn_bw=25e9),
    # NOT a measurement of anything: a stand-in so the offline planner and
    # its tests can rank plans on a host with no TPU.  No utilization is
    # ever computed from it (benchmarks/run.py refuses a non-TPU device).
    "cpu": ChipSpec("cpu", bf16_flops=2e11, hbm_bw=5e10, hbm_bytes=64e9,
                    ici_bw=1e10, dcn_bw=1e10),
}

# device_kind (as ``jax.devices()[0].device_kind`` reports it) -> CHIPS key
_TPU_KINDS = {
    "TPU v5 lite": "v5e",
    "TPU v5": "v5p",  # what a v5p reports; matched exactly, not by prefix
    "TPU v4": "v4",
}


def chip_for_device(device) -> ChipSpec:
    """The peaks-table entry for a JAX device.  A TPU whose kind is not in
    the table is an error naming the kind — never a default; the CPU
    platform maps to the planner's stand-in entry."""
    if device.platform == "cpu":
        return CHIPS["cpu"]
    key = _TPU_KINDS.get(device.device_kind)
    if key is None:
        raise ValueError(
            f"device_kind {device.device_kind!r} (platform "
            f"{device.platform!r}) is not in the peaks table "
            f"{sorted(_TPU_KINDS)}; add its published peaks to "
            f"profiler/cost_model.py before computing anything from them")
    return CHIPS[key]


def detect_chip() -> ChipSpec:
    """The :class:`ChipSpec` of the default backend's first device."""
    return chip_for_device(jax.devices()[0])


def matmul_time(spec: ChipSpec, m: int, k: int, n: int,
                bytes_per_el: int = 2) -> float:
    """Roofline matmul time: max(compute, memory)."""
    flops = 2.0 * m * k * n
    bytes_moved = bytes_per_el * (m * k + k * n + m * n)
    return max(flops / (spec.bf16_flops * spec.mxu_util),
               bytes_moved / spec.hbm_bw)


def allreduce_time(spec: ChipSpec, nbytes: float, n_devices: int,
                   *, over_dcn: bool = False) -> float:
    """Ring allreduce: 2*(n-1)/n * bytes over the slowest link."""
    if n_devices <= 1:
        return 0.0
    bw = (spec.dcn_bw if over_dcn else spec.ici_bw) * spec.ici_util
    return 2.0 * (n_devices - 1) / n_devices * nbytes / bw + 5e-6


def allgather_time(spec: ChipSpec, nbytes: float, n_devices: int,
                   *, over_dcn: bool = False) -> float:
    if n_devices <= 1:
        return 0.0
    bw = (spec.dcn_bw if over_dcn else spec.ici_bw) * spec.ici_util
    return (n_devices - 1) / n_devices * nbytes / bw + 5e-6


def alltoall_time(spec: ChipSpec, nbytes: float, n_devices: int,
                  *, over_dcn: bool = False) -> float:
    if n_devices <= 1:
        return 0.0
    bw = (spec.dcn_bw if over_dcn else spec.ici_bw) * spec.ici_util
    return (n_devices - 1) / n_devices * nbytes / bw + 5e-6


def p2p_time(spec: ChipSpec, nbytes: float, *, over_dcn: bool = False) -> float:
    bw = (spec.dcn_bw if over_dcn else spec.ici_bw) * spec.ici_util
    return nbytes / bw + 5e-6
