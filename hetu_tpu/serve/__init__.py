"""TPU-native inference serving: KV-cache decode + continuous batching.

The serving layer the ROADMAP's "heavy traffic" north star needs on top
of the training-only models:

  * :mod:`kv_cache` — the GQA-aware paged K/V cache
    (:class:`PagedKVCache`) with refcounted prefix sharing +
    copy-on-write, so finished sequences release memory to queued
    requests and identical system prompts dedup to one physical copy;
  * :mod:`engine` — :class:`PagedServeEngine`: bucketed jit-compiled
    page-aligned chunked prefill + single-token decode with page-table
    gather/scatter steps (bounded executable count) over the models'
    cache entry points, optionally tp-sharded over a mesh;
  * :mod:`scheduler` — continuous batching: admit into free slots every
    decode step, evict on EOS/max_tokens/deadline, page-budget
    backpressure, chunked-prefill interleave;
  * :mod:`server` — blob-channel front-end over the van transport with
    per-request timeouts, idempotent resubmission dedup, and graceful
    shutdown;
  * :mod:`metrics` — TTFT / tokens-per-sec / queue depth / occupancy /
    recompile counters, reportable through ``utils/logger.MetricLogger``;
  * :mod:`migrate` — live KV-cache slot migration: chunked CRC-checked
    slot transfer over the van, scheduler hand-off with zero re-prefill;
  * :mod:`pool` — :class:`ServingPool`: health-routed routing over N
    members, planned drain (migrate-then-exit) and unplanned failover;
  * :mod:`crosshost` — :class:`CrossProcessServingPool`: the pool
    across REAL process boundaries — member processes, membership
    leases over the van, two-phase cross-process KV drain;
  * :mod:`recsys` — the SECOND serving workload: online CTR inference
    (WideDeep/DeepFM/DCN) behind the same van front-end and pool
    machinery, with a staleness-bounded hot-embedding serving cache
    over the PS (HET) and a micro-batching scheduler.

See examples/gpt_serve.py, examples/gpt_serve_pool.py and
examples/ctr_serve.py for the end-to-end paths.
"""

from hetu_tpu.serve.crosshost import CrossProcessServingPool
from hetu_tpu.serve.engine import PagedServeEngine
from hetu_tpu.serve.kv_cache import (
    KVCacheSpec, KVSlotSnapshot, PagedKVCache,
)
from hetu_tpu.serve.metrics import ServeMetrics
from hetu_tpu.serve.migrate import MigrationError
from hetu_tpu.serve.pool import ServingPool
from hetu_tpu.serve.scheduler import ContinuousBatchingScheduler, Request
from hetu_tpu.serve.recsys import (
    RecsysBatcher, RecsysClient, RecsysEngine, RecsysPool, RecsysRequest,
    RecsysServer, ServingEmbeddingCache,
)
from hetu_tpu.serve.server import (
    InferenceClient, InferenceServer, request_channel, response_channel,
)

__all__ = [
    "PagedServeEngine", "PagedKVCache", "KVCacheSpec", "KVSlotSnapshot",
    "ServeMetrics", "MigrationError", "ServingPool",
    "CrossProcessServingPool",
    "ContinuousBatchingScheduler", "Request",
    "InferenceClient", "InferenceServer",
    "request_channel", "response_channel",
    "ServingEmbeddingCache", "RecsysEngine", "RecsysBatcher",
    "RecsysRequest", "RecsysServer", "RecsysClient", "RecsysPool",
]
