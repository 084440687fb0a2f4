"""Traffic plane: trace-driven load generation + measured-load
autoscaling over the serving pools.

Three cooperating parts (ISSUE 16 / the ROADMAP's "million-user traffic
plane"):

* :mod:`loadgen`   — seeded open-loop workload synthesis (diurnal rate
  curves, bursty multi-tenant arrivals, Zipfian prompt/key popularity,
  per-tenant deadlines) with a byte-stable JSON trace format, and an
  open-loop replay that paces them against any ``(event) -> handle``
  submit callable;
* :mod:`autoscale` — a control loop on the controller that reads
  MEASURED load from ``fleet_metrics()`` (queue depth, shed rate,
  windowed per-tenant TTFT p99 vs SLO) and scales the member fleet:
  scale-up revives a parked slot through the spawn harness, scale-down
  hands the victim's live KV to a peer via the zero-re-prefill
  ``drain_member`` — with hysteresis, cooldowns, and min/max bounds;
* per-tenant SLO classes live in ``serve/scheduler.py`` (priority
  admission + weighted fair queueing) and ride the submit wire through
  ``serve/crosshost.py`` — the traffic plane only names them.

``pytest tests/ -m traffic`` asserts the plane: the trace's bytes, the
replay's pacing, tiered admission and the autoscaler's decisions.
"""

from hetu_tpu.traffic.autoscale import Autoscaler, AutoscalePolicy
from hetu_tpu.traffic.loadgen import (TenantSpec, TraceSpec,
                                      diurnal_multiplier, dumps_trace,
                                      load_trace, replay, save_trace,
                                      synthesize)

__all__ = [
    "Autoscaler", "AutoscalePolicy", "TenantSpec", "TraceSpec",
    "diurnal_multiplier", "dumps_trace", "load_trace", "replay",
    "save_trace", "synthesize",
]
