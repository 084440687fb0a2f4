"""Serving metrics: TTFT, tokens/sec, queue depth, occupancy, recompiles,
and the round log: the engine's last calls, phase by phase.

Host-side counters shared by the engine (compile counts), scheduler
(admission/eviction, queue depth, occupancy) and server (request
outcomes).  Thread-safe — listener threads and the engine loop update
concurrently.  ``report()`` flushes a snapshot through the repo's
``utils/logger.MetricLogger`` so serving runs log/means/wandb exactly like
training runs do.

Backed by a :class:`~hetu_tpu.telemetry.registry.MetricsRegistry`:
counters/gauges are typed metrics, and TTFT is BOTH an exact bounded ring
(``collections.deque(maxlen=window)`` — O(1) per observation; the old
list-slice trim was O(window)) and a fixed-bucket
:class:`~hetu_tpu.telemetry.registry.Histogram`.  ``snapshot()`` reports
avg/max AND p50/p90/p99 from the ring — all WINDOWED and mutually
consistent, the numbers a live SLO check wants — while the cumulative
histogram feeds ``prometheus_text()`` (lifetime ``_bucket`` counts, the
Prometheus convention).

**The round log** (PR 54).  :class:`RoundLog`, a second bounded ring in the
TTFT ring's idiom (a ``deque(maxlen=ROUND_LOG_ROWS)`` of exact values,
percentiles taken when asked), keeps one row for each of the engine's last
calls: a decode round or a prefill chunk, written by the engine when the
call's outer span has closed.  A row is the ten integers of
:data:`ROUND_FIELDS`: ``seq`` (the launch's number, the one its ``hetu:``
launch and fetch spans carry), ``kind`` (:data:`DECODE` | :data:`CHUNK`),
the five seam times ``t_prep``, ``t_launch``, ``t_fetch``, ``t_post``,
``t_close`` in ns of ``time.monotonic_ns()`` (the clock any caller's
``time.monotonic()`` reads: ``prep`` is ``t_launch - t_prep``, ``launch``
``t_fetch - t_launch``, ``fetch`` ``t_post - t_fetch``, ``post``
``t_close - t_post``, and the time from one row's ``t_close`` to the next
row's ``t_prep`` is whoever drives the engine), ``batch`` (the slot bucket;
1 for a chunk), ``pages`` (a round's page bucket, a chunk's chunk bucket)
and ``tokens`` (rows the call produced or prefilled).  The log has no switch
and no size to set: it is always on, as the spans are always the same
Python, and costs five clock reads and one append a call.  ``snapshot()``
reads ``tokens_per_sec`` and the phase percentiles from it and
:meth:`ServeMetrics.rounds` hands the rows out.  ``RoundLog.recent`` keeps
the last few LOGS made in the process (the rows, not the ``ServeMetrics``
and not its engine: it pins neither), which is how a tool that holds no
handle on an engine, or that runs after the engine was dropped, reads what
the engine did.  ``observe_decode``, with its own clock read a round, went
with PR 54: the log's stamps are the one per-call clock path.
"""

from __future__ import annotations

import threading
from collections import deque
from typing import Optional

import numpy as np

from hetu_tpu.telemetry.registry import (
    DEFAULT_LATENCY_BUCKETS, MetricsRegistry,
)

# a row of the round log, in order; ``kind`` is DECODE or CHUNK
ROUND_FIELDS = ("seq", "kind", "t_prep", "t_launch", "t_fetch", "t_post",
                "t_close", "batch", "pages", "tokens")
DECODE, CHUNK = 0, 1
PHASES = ("prep", "launch", "fetch", "post")
# the fastest benchmark cell makes about 7,500 calls in 40 s; 16,384 rows of
# ten integers are about 1.5 MB of host memory
ROUND_LOG_ROWS = 16384


def _p50_p95_ms(ns, prefix: str) -> dict:
    """Nearest-rank median and 95th percentile, as the ``ttft_*`` keys take
    theirs, of durations in ns; in milliseconds."""
    ns = np.sort(ns)
    n = len(ns)
    return {prefix + "_p50_ms": float(ns[min(n // 2, n - 1)]) / 1e6,
            prefix + "_p95_ms": float(ns[min(int(0.95 * n), n - 1)]) / 1e6}


class RoundLog:
    """The engine's last calls, one row each (:data:`ROUND_FIELDS`)."""

    # the logs made last in this process, newest last: rows of integers
    # only, so a dropped engine, its parameters and its ``ServeMetrics`` are
    # freed as before, and at most 8 x 1.5 MB stay behind
    recent = deque(maxlen=8)

    def __init__(self):
        # the one uncontended lock a call: a deque may not be copied while
        # another thread appends to it
        self._lock = threading.Lock()
        self._rows = deque(maxlen=ROUND_LOG_ROWS)
        RoundLog.recent.append(self)

    def append(self, row: tuple) -> None:
        with self._lock:
            self._rows.append(row)

    def rows(self) -> np.ndarray:
        """``int64 [rows, 10]``, oldest first: a copy."""
        with self._lock:
            rows = list(self._rows)
        return np.asarray(rows, np.int64).reshape(len(rows),
                                                  len(ROUND_FIELDS))


class ServeMetrics:
    def __init__(self, *, window: int = 512,
                 registry: Optional[MetricsRegistry] = None):
        self._lock = threading.Lock()
        self.registry = registry if registry is not None else MetricsRegistry()
        self._ttft = deque(maxlen=int(window))  # seconds, bounded ring
        self._ttft_hist = self.registry.histogram(
            "ttft_s", DEFAULT_LATENCY_BUCKETS,
            help="request admission to first generated token")
        self._window = int(window)
        self.round_log = RoundLog()

    # ---- counters / gauges ----
    def inc(self, name: str, n: int = 1) -> None:
        self.registry.counter(name).inc(n)

    def set_gauge(self, name: str, value) -> None:
        self.registry.gauge(name).set(value)

    def count(self, name: str) -> int:
        return self.registry.counter(name).value

    # ---- per-tenant accounting (SLO-class groundwork) ----
    @staticmethod
    def _tenant_slug(tenant) -> str:
        """Tenant tags are FREE-FORM caller input but become metric
        name segments: anything outside [A-Za-z0-9_.-] (a space, a
        brace, a newline) would produce an invalid Prometheus
        exposition line — a hostile tag could even inject extra metric
        lines — so non-name characters collapse to '_' and the slug is
        length-capped.  (Cardinality bounding — a cap on DISTINCT
        tenants — belongs to the SLO-class admission layer, not here.)"""
        s = "".join(c if (c.isalnum() or c in "_.-") else "_"
                    for c in str(tenant))
        return s[:64] or "_"

    def note_tenant(self, tenant, event: str, n: int = 1) -> None:
        """Per-tenant counter (``tenant.<t>.<event>``): requests, sheds,
        status outcomes — the accounting surface per-tenant SLO classes
        will be enforced against.  No-op for untagged traffic."""
        if tenant:
            self.registry.counter(
                f"tenant.{self._tenant_slug(tenant)}.{event}").inc(n)

    # ---- latency / throughput ----
    def observe_ttft(self, seconds: float, *, tenant=None) -> None:
        """Time-to-first-token: request admission → prefill's first token.
        A ``tenant`` tag ALSO records into that tenant's own histogram
        (``tenant.<t>.ttft_s``) so per-tenant TTFT rides the same fleet
        scrape as the counters."""
        s = float(seconds)
        with self._lock:
            self._ttft.append(s)
        # outside the ring lock: the histogram has its own lock and its
        # only reader is the prometheus exposition — snapshot() derives
        # every ttft_* key from the ring alone
        self._ttft_hist.observe(s)
        if tenant:
            self.registry.histogram(
                f"tenant.{self._tenant_slug(tenant)}.ttft_s",
                DEFAULT_LATENCY_BUCKETS,
                help="per-tenant TTFT").observe(s)

    def observe_round(self, seq, kind, t_prep, t_launch, t_fetch, t_post,
                      t_close, batch, pages, tokens) -> None:
        """One engine call's row of the round log (:data:`ROUND_FIELDS`)."""
        self.round_log.append((seq, kind, t_prep, t_launch, t_fetch, t_post,
                               t_close, batch, pages, tokens))

    def rounds(self) -> np.ndarray:
        """The round log as one ``int64`` array ``[rows, 10]``, oldest row
        first, columns as :data:`ROUND_FIELDS`: a copy."""
        return self.round_log.rows()

    # ---- reporting ----
    def snapshot(self) -> dict:
        from hetu_tpu.telemetry.registry import Counter, Gauge
        out = {}
        for name, m in self.registry.metrics().items():
            if isinstance(m, (Counter, Gauge)):
                out[name] = m.value
        with self._lock:
            ring = list(self._ttft)
        if ring:
            # snapshot stats are all WINDOWED (the last `window`
            # observations, like the pre-histogram implementation): avg,
            # max AND the percentiles come from the same ring, so the
            # numbers in one snapshot are mutually consistent and track
            # current latency.  The cumulative histogram feeds the
            # Prometheus exposition (where lifetime _bucket counts are
            # the convention), not these keys.
            ts = sorted(ring)
            n = len(ts)
            out["ttft_avg_s"] = sum(ts) / n
            out["ttft_p50_s"] = ts[min(n // 2, n - 1)]
            out["ttft_p90_s"] = ts[min(int(0.90 * n), n - 1)]
            out["ttft_p99_s"] = ts[min(int(0.99 * n), n - 1)]
            out["ttft_max_s"] = ts[-1]
        out.update(self._round_stats(self.rounds()))
        # paged-engine derived rate: what fraction of prompt tokens were
        # served from the prefix cache instead of prefilled (the dedup
        # telemetry the paged A/B bench and dashboards read)
        hit = out.get("prefix_hit_tokens", 0)
        miss = out.get("prefix_miss_tokens", 0)
        if hit or miss:
            out["prefix_hit_rate"] = hit / (hit + miss)
        return out

    @staticmethod
    def _round_stats(rows: np.ndarray) -> dict:
        """The snapshot's keys that come from the round log, all WINDOWED to
        the rows the ring keeps and so consistent with each other:
        ``tokens_per_sec`` (tokens the kept decode rounds generated over the
        time from the first of them opening to the last closing; the
        process's lifetime mean before PR 54), p50 / p95 of each phase of a
        decode round and of a prefill chunk, and of the gap from one call's
        close to the next call's opening."""
        if not len(rows):
            return {}
        col = {name: rows[:, i] for i, name in enumerate(ROUND_FIELDS)}
        out = {"rounds_kept": len(rows)}
        seams = [col["t_" + p] for p in PHASES] + [col["t_close"]]
        for prefix, kind in (("decode", DECODE), ("chunk", CHUNK)):
            mine = col["kind"] == kind
            if not mine.any():
                continue
            for phase, a, b in zip(PHASES, seams, seams[1:]):
                out.update(_p50_p95_ms((b - a)[mine], f"{prefix}_{phase}"))
            if kind == DECODE:
                dt = col["t_close"][mine][-1] - col["t_prep"][mine][0]
                out["tokens_per_sec"] = \
                    float(col["tokens"][mine].sum()) * 1e9 / max(dt, 1)
        if len(rows) > 1:
            out.update(_p50_p95_ms(col["t_prep"][1:] - col["t_close"][:-1],
                                   "engine_gap"))
        return out

    def report(self, logger, step=None) -> dict:
        """Log the snapshot through utils/logger.MetricLogger."""
        snap = self.snapshot()
        logger.log(snap, step=step)
        return snap

    def prometheus_text(self) -> str:
        return self.registry.prometheus_text()
