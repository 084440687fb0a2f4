"""The device a run is on: peaks table, stamp, refusal of anything else."""

from __future__ import annotations

import os
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

# Published peaks of one chip.  Source: Google Cloud documentation,
# "TPU v5e" (cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 16 GB of
# HBM at 819 GB/s.  No assumed efficiency: a share of these is a share of
# the published number.  A device that is not here is an error.
_V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
PEAKS = {"TPU v5 lite": _V5E,   # what jax.devices()[0].device_kind says
         "TPU v5e": _V5E}


class NoChip(RuntimeError):
    pass


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise NoChip(f"device kind {device_kind!r} is not in the peaks "
                     f"table {sorted(PEAKS)}")
    return PEAKS[device_kind]


def enable_compile_cache() -> str:
    """JAX's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` if set, else
    at ``<checkout>/.jax_cache``: a fixed path, since the path is part of
    the key.  Every program is cached, however short its compile, so that a
    second run of a cell finds all of them."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def require_chips(chips: int) -> dict:
    """The stamp of the accelerator this process holds, or NoChip when JAX
    found no TPU, an unknown one, or fewer chips than the cell asks for."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found platform={d.platform}")
    peaks(d.device_kind)
    if len(devs) < chips:
        raise NoChip(f"cell needs {chips} chips; JAX found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


class MemoryProbe:
    """What the cell's traffic holds on the fullest chip, in two parts that
    are reported apart.  The TPU allocator keeps arrays on a heap
    (``bytes_in_use``) and lends running programs a stack for their
    temporaries (``peak_bytes_reserved``; gigabytes for a train step or a
    decode round, and in neither heap figure).

    The allocator's peaks are process-lifetime and cannot be reset, so the
    loops order their work round that: the comparison with the reference,
    which holds a second copy of the weights and the reference's own
    temporaries, runs AFTER ``close()``; warm-up may run only programs the
    cell's traffic reaches (a saturated backlog reaches every slot and
    page bucket; an open-loop cell well under its knee does not, and has
    to be served with the slots it fills, or its stack figure is that of
    a program no request ever ran).  ``sample()`` is called when the window
    opens and ``close()`` when it ends: the heap is the larger of the two
    live readings (weights, optimizer state or page pool: it is level
    while the loop runs), the stack is the largest any program was lent up
    to the window's end.  Their sum on the fullest chip is
    ``memory_peak_bytes``: a level and a peak, which do fall together."""

    def __init__(self):
        self.heap = {}       # device id -> largest live heap reading
        self.stack = {}      # device id -> peak stack, read at close()
        self.heap_lifetime = {}

    @staticmethod
    def _stats():
        import jax
        return {d.id: d.memory_stats() or {} for d in jax.local_devices()}

    def sample(self) -> None:
        for i, st in self._stats().items():
            self.heap[i] = max(self.heap.get(i, 0),
                               int(st.get("bytes_in_use", 0)))

    def close(self) -> None:
        self.sample()
        for i, st in self._stats().items():
            self.stack[i] = int(st.get("peak_bytes_reserved", 0))
            self.heap_lifetime[i] = int(st.get("peak_bytes_in_use", 0))

    def fullest(self) -> dict:
        """{heap_bytes, stack_bytes, total_bytes, heap_lifetime_peak_bytes}
        of the chip whose heap + stack is largest (zeros where the backend
        reports nothing, as on the CPU)."""
        if not self.stack:
            self.close()
        i = max(self.heap, key=lambda k: self.heap[k] + self.stack[k],
                default=None)
        if i is None:
            return {"heap_bytes": 0, "stack_bytes": 0, "total_bytes": 0,
                    "heap_lifetime_peak_bytes": 0}
        return {"heap_bytes": self.heap[i], "stack_bytes": self.stack[i],
                "total_bytes": self.heap[i] + self.stack[i],
                "heap_lifetime_peak_bytes": self.heap_lifetime[i]}
