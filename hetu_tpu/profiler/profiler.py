"""Measurement profilers.

Reference: python/hetu/profiler.py — `HetuProfiler` (:55) replays single ops
with CUDA-event timing; `NCCLProfiler` (:390) micro-benchmarks allreduce and
sendrecv over device subsets; results cached to /tmp/hetu_cached_exetime.bin
and consumed by the searchers.

TPU translation: ops are jitted callables timed after compile+warmup
(block_until_ready); collectives are timed per mesh axis.  The cost cache is
a JSON file keyed by op/shape/mesh so searchers run offline without
re-benchmarking (the /tmp cache-file role, but human-readable).
"""

from __future__ import annotations

import json
import time
from pathlib import Path
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_CACHE = Path("/tmp/hetu_tpu_cost_cache.json")


class _CostCache:
    def __init__(self, path=DEFAULT_CACHE):
        self.path = Path(path)
        try:
            self.data = json.loads(self.path.read_text())
        except Exception:
            self.data = {}

    def get(self, key: str):
        return self.data.get(key)

    def put(self, key: str, value: float):
        self.data[key] = value
        try:
            self.path.write_text(json.dumps(self.data, indent=0))
        except OSError:  # pragma: no cover
            pass


def _sync(x):
    """Wait for the device to finish computing ``x``."""
    jax.block_until_ready(x)


class OpProfiler:
    """Time jitted callables (reference HetuProfiler.profile).

    Two modes:
      * time_fn: dispatch + sync per call.  Includes the host's dispatch
        latency.
      * time_chained: runs k dependent iterations on device and syncs
        once, for two values of k; the slope (T_k2-T_k1)/(k2-k1) cancels
        the dispatch latency.  Use for per-op costs feeding the
        simulator.
    """

    def __init__(self, *, warmup: int = 3, iters: int = 3, cache=None):
        self.warmup = warmup
        self.iters = iters
        self.cache = cache if cache is not None else _CostCache()

    def time_fn(self, fn: Callable, *args, key: Optional[str] = None) -> float:
        """Median wall time (s) of fn(*args), including round-trip."""
        if key is not None:
            hit = self.cache.get(key)
            if hit is not None:
                return hit
        jfn = jax.jit(fn)
        _sync(jfn(*args))
        for _ in range(self.warmup - 1):
            _sync(jfn(*args))
        times = []
        for _ in range(self.iters):
            t0 = time.perf_counter()
            _sync(jfn(*args))
            times.append(time.perf_counter() - t0)
        t = float(np.median(times))
        if key is not None:
            self.cache.put(key, t)
        return t

    def time_chained(self, step: Callable, x0, *, k1: int = 4, k2: int = 12,
                     key: Optional[str] = None) -> float:
        """Per-iteration time of x = step(x): two chained runs, slope."""
        if key is not None:
            hit = self.cache.get(key)
            if hit is not None:
                return hit

        def run(k):
            @jax.jit
            def f(x):
                return jax.lax.fori_loop(0, k, lambda i, c: step(c), x)
            _sync(f(x0))
            ts = []
            for _ in range(self.iters):
                t0 = time.perf_counter()
                _sync(f(x0))
                ts.append(time.perf_counter() - t0)
            return float(np.median(ts))

        t = max((run(k2) - run(k1)) / (k2 - k1), 1e-9)
        if key is not None:
            self.cache.put(key, t)
        return t

    def time_matmul(self, m: int, k: int, n: int, dtype=jnp.bfloat16) -> float:
        kk = jax.random.split(jax.random.PRNGKey(0))
        a = (jax.random.normal(kk[0], (m, k)) / np.sqrt(k)).astype(dtype)
        b = (jax.random.normal(kk[1], (k, n)) / np.sqrt(k)).astype(dtype)

        def step(c):
            out = jnp.matmul(c, b, preferred_element_type=jnp.float32)
            return out.astype(dtype)

        if m != n:  # chain needs square carry; fall back to fetch timing
            return self.time_fn(
                lambda a, b: jnp.matmul(a, b,
                                        preferred_element_type=jnp.float32),
                a, b, key=f"matmul:{m}x{k}x{n}:{jnp.dtype(dtype).name}:"
                          f"{jax.devices()[0].platform}")
        return self.time_chained(
            step, a, key=f"matmul:{m}x{k}x{n}:{jnp.dtype(dtype).name}:"
                         f"{jax.devices()[0].platform}")


class CollectiveProfiler:
    """Micro-benchmark collectives per mesh axis (reference NCCLProfiler)."""

    def __init__(self, mesh, *, warmup: int = 2, iters: int = 5, cache=None):
        self.mesh = mesh
        self.warmup = warmup
        self.iters = iters
        self.cache = cache if cache is not None else _CostCache()

    def _run(self, build, nbytes: int, tag: str, axis: str) -> float:
        key = (f"coll:{tag}:{axis}:{self.mesh.shape[axis]}:{nbytes}:"
               f"{jax.devices()[0].platform}")
        hit = self.cache.get(key)
        if hit is not None:
            return hit
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        n = nbytes // 4
        x = jnp.ones((max(n, self.mesh.shape[axis]),), jnp.float32)
        body = build(axis)

        # chain k collectives on-device (output feeds input) and sync once:
        # slope timing cancels dispatch latency (see OpProfiler)
        def chained(k):
            def f(v):
                return jax.lax.fori_loop(
                    0, k, lambda i, c: body(c) * 0.5 + c * 0.5, v)
            fn = shard_map(f, mesh=self.mesh, in_specs=P(axis),
                           out_specs=P(axis), check_vma=False)
            jfn = jax.jit(fn)
            _sync(jfn(x))
            for _ in range(self.warmup):
                _sync(jfn(x))
            ts = []
            for _ in range(self.iters):
                t0 = time.perf_counter()
                _sync(jfn(x))
                ts.append(time.perf_counter() - t0)
            return float(np.median(ts))

        t = max((chained(9) - chained(3)) / 6.0, 1e-9)
        self.cache.put(key, t)
        return t

    def allreduce_time(self, nbytes: int, axis: str) -> float:
        from jax import lax
        return self._run(lambda ax: (lambda v: lax.psum(v, ax)), nbytes,
                         "allreduce", axis)

    def ppermute_time(self, nbytes: int, axis: str) -> float:
        from jax import lax

        def build(ax):
            n = self.mesh.shape[ax]
            perm = [(i, (i + 1) % n) for i in range(n)]
            return lambda v: lax.ppermute(v, ax, perm)

        return self._run(build, nbytes, "ppermute", axis)

    def alltoall_time(self, nbytes: int, axis: str) -> float:
        from jax import lax

        def build(ax):
            return lambda v: lax.all_to_all(
                v.reshape(self.mesh.shape[ax], -1), ax, split_axis=0,
                concat_axis=0, tiled=True).reshape(-1)

        return self._run(build, nbytes, "alltoall", axis)
