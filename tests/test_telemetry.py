"""Telemetry tier: tracer, typed metrics, chaos timeline, ports, reporter.

Fast lane (tier-1): the no-op disabled path, Chrome-trace schema the way
Perfetto requires it, the JSONL stream, registry percentiles + Prometheus
text, the MetricLogger/ServeMetrics ports (API-compatible + the satellite
fixes), fault-instant determinism across seeded runs, timeline pairing,
and the trace_report CLI.  The multi-process PS/elastic chaos trace lives
in tests/test_telemetry_chaos.py (slow + chaos).
"""

import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu import layers, optim, telemetry
from hetu_tpu.resilience import FaultInjector, FaultSchedule, Supervisor
from hetu_tpu.telemetry import timeline, trace
from hetu_tpu.telemetry.registry import (
    Counter, Gauge, Histogram, MetricsRegistry,
)
from hetu_tpu.train.executor import Executor

pytestmark = pytest.mark.telemetry

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(autouse=True)
def _tracing_off():
    """Every test starts and ends with tracing disabled — a test that
    enables it must not leak a live tracer into the next."""
    telemetry.disable()
    yield
    telemetry.disable()


# ---------------------------------------------------------------------------
# tracer: disabled path
# ---------------------------------------------------------------------------

def test_disabled_span_is_the_noop_singleton(monkeypatch):
    # the contract of a process that has not loaded jax; with jax loaded
    # the off path is an inert profiler annotation
    # (tests/test_profiler_spans.py)
    monkeypatch.setattr(trace, "_annotation", None)
    assert not telemetry.enabled()
    s1 = telemetry.span("anything")
    s2 = telemetry.span("else")
    assert s1 is trace.NULL_SPAN and s2 is trace.NULL_SPAN
    with s1 as s:
        s.set("k", "v")  # swallowed, no error
    telemetry.instant("nothing")          # returns None, records nothing
    telemetry.complete("nothing", 0.0)
    assert telemetry.now_us() == 0.0


def test_enable_disable_roundtrip(tmp_path):
    t = telemetry.enable()
    assert telemetry.enabled() and telemetry.get_tracer() is t
    with telemetry.span("a"):
        pass
    got = telemetry.disable()
    assert got is t and not telemetry.enabled()
    assert any(e["name"] == "a" for e in t.events)


# ---------------------------------------------------------------------------
# tracer: chrome-trace schema (the shape Perfetto requires)
# ---------------------------------------------------------------------------

def _sample_tracer():
    t = telemetry.enable()
    with telemetry.span("outer") as sp:
        sp.set("k", 1)
        with telemetry.span("inner"):
            pass
        telemetry.instant("mark", {"x": 2})
    with telemetry.span("second"):
        pass
    telemetry.disable()
    return t


def test_chrome_trace_schema_and_track_monotonicity():
    t = _sample_tracer()
    doc = t.chrome_trace()
    assert "traceEvents" in doc and doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    names = {e["name"] for e in evs}
    assert {"outer", "inner", "mark", "second"} <= names
    for e in evs:
        assert e["ph"] in ("X", "i", "M")
        assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        if e["ph"] == "X":
            assert e["dur"] >= 0
        if e["ph"] == "i":
            assert e["s"] in ("t", "p", "g")  # instant scope
    # ts monotone within each (pid, tid) track; same-ts parents first
    by_track = {}
    for e in evs:
        by_track.setdefault((e["pid"], e["tid"]), []).append(e["ts"])
    for ts in by_track.values():
        assert ts == sorted(ts)
    # nesting: inner is contained in outer on the same track
    outer = next(e for e in evs if e["name"] == "outer")
    inner = next(e for e in evs if e["name"] == "inner")
    assert outer["tid"] == inner["tid"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    assert outer["args"] == {"k": 1}
    # the whole document is valid JSON (what Perfetto actually loads)
    json.loads(json.dumps(doc))


def test_span_records_exception_attr():
    t = telemetry.enable()
    with pytest.raises(ValueError):
        with telemetry.span("boom"):
            raise ValueError("x")
    telemetry.disable()
    ev = next(e for e in t.events if e["name"] == "boom")
    assert ev["args"]["error"] == "ValueError"


def test_jsonl_stream_appends_and_reloads(tmp_path):
    p = tmp_path / "sub" / "run.trace.jsonl"  # parent dir auto-created
    t = telemetry.enable(jsonl_path=p)
    with telemetry.span("a"):
        telemetry.instant("b")
    telemetry.disable()
    evs = telemetry.load_jsonl(p)
    assert [e["name"] for e in evs] == [e["name"] for e in t.events]
    # append-only: a second session extends the stream
    telemetry.enable(jsonl_path=p)
    with telemetry.span("c"):
        pass
    telemetry.disable()
    assert len(telemetry.load_jsonl(p)) > len(evs)
    # a torn final line (crash mid-write) is skipped, not fatal
    with open(p, "a") as f:
        f.write('{"name": "torn...')
    assert [e["name"] for e in telemetry.load_jsonl(p)][-1] == "c"


def test_write_chrome_loads_back(tmp_path):
    t = _sample_tracer()
    path = t.write_chrome(tmp_path / "t.json")
    doc = json.loads(Path(path).read_text())
    assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_counter_gauge():
    r = MetricsRegistry()
    c = r.counter("x.calls")
    assert c.inc() == 1 and c.inc(4) == 5
    assert r.counter("x.calls") is c  # get-or-create
    r.gauge("x.depth").set(3)
    assert r.gauge("x.depth").value == 3.0
    snap = r.snapshot()
    assert snap == {"x.calls": 5, "x.depth": 3.0}


def test_registry_type_conflict_raises():
    r = MetricsRegistry()
    r.counter("n")
    with pytest.raises(TypeError):
        r.gauge("n")
    with pytest.raises(TypeError):
        r.histogram("n")


def test_histogram_percentiles():
    h = Histogram("lat", buckets=(1.0, 2.0, 4.0, 8.0))
    assert h.percentile(0.5) is None  # empty
    for v in np.linspace(0.1, 7.9, 100):
        h.observe(float(v))
    p50, p90, p99 = (h.percentile(q) for q in (0.5, 0.9, 0.99))
    assert p50 <= p90 <= p99
    # interpolated estimates stay within one bucket of the exact values
    assert 2.0 <= p50 <= 4.0 + 1e-9      # exact ~4.0
    assert 4.0 <= p90 <= 8.0             # exact ~7.1
    snap = h.snapshot()
    assert snap["count"] == 100 and snap["min"] == pytest.approx(0.1)
    assert snap["max"] == pytest.approx(7.9)
    # single observation: percentile == the value, not a bucket edge
    h1 = Histogram("one", buckets=(1.0, 10.0))
    h1.observe(3.0)
    assert h1.percentile(0.5) == 3.0 and h1.percentile(0.99) == 3.0
    with pytest.raises(ValueError):
        h1.percentile(1.5)
    with pytest.raises(ValueError):
        Histogram("bad", buckets=(2.0, 1.0))


def test_prometheus_text_exposition(tmp_path):
    r = MetricsRegistry()
    r.counter("van.pull.calls", help="pull count").inc(7)
    r.gauge("queue-depth").set(2)
    h = r.histogram("lat_s", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = r.prometheus_text()
    assert "# TYPE van_pull_calls counter" in text
    assert "van_pull_calls 7" in text
    assert "# HELP van_pull_calls pull count" in text
    assert "# TYPE queue_depth gauge" in text
    assert "# TYPE lat_s histogram" in text
    assert 'lat_s_bucket{le="0.1"} 1' in text
    assert 'lat_s_bucket{le="1.0"} 2' in text
    assert 'lat_s_bucket{le="+Inf"} 3' in text
    assert "lat_s_count 3" in text
    # file-based scrape helper writes the same text
    p = r.write_prometheus(tmp_path / "metrics" / "hetu.prom")
    assert Path(p).read_text() == text


# ---------------------------------------------------------------------------
# MetricLogger port (satellites: parent dirs, reset flag)
# ---------------------------------------------------------------------------

def test_metric_logger_creates_parent_dirs(tmp_path):
    p = tmp_path / "not" / "yet" / "there" / "log.jsonl"
    lg = ht.utils.logger.MetricLogger(str(p))
    lg.log({"loss": 1.5}, step=3)
    lg.close()
    rec = json.loads(p.read_text().strip())
    assert rec["step"] == 3 and rec["loss"] == 1.5


def test_metric_logger_reset_flag():
    lg = ht.utils.logger.MetricLogger()
    lg.log({"loss": 2.0})
    assert lg.inc("faults", 2) == 2
    lg.reset()  # default: means clear, monotonic counters SURVIVE
    assert lg.means() == {}
    assert lg.counters_snapshot() == {"faults": 2}
    lg.reset(counters=True)  # explicit: chaos tests zero deliberately
    assert lg.counters_snapshot() == {"faults": 0}


def test_metric_logger_means_and_prometheus():
    lg = ht.utils.logger.MetricLogger()
    lg.log({"loss": 2.0})
    lg.log({"loss": 4.0})
    lg.inc("retries")
    assert lg.means() == {"loss": 3.0}
    assert lg.counters == {"retries": 1}  # historical attribute shape
    text = lg.prometheus_text()
    # counters render with the _total suffix (separate namespace from the
    # log() gauges, so an inc()+log() shared name can't collide)
    assert "retries_total 1" in text and "loss 4.0" in text


def test_metric_logger_shared_registry_prometheus():
    """A logger sharing a registry that other instrumentation populated
    (histograms, gauges) must render those with their real types, not
    crash assuming everything is a counter."""
    reg = MetricsRegistry()
    reg.histogram("van.op.latency_s").observe(0.01)
    reg.gauge("width").set(4)
    lg = ht.utils.logger.MetricLogger(registry=reg)
    lg.inc("retries", 2)
    text = lg.prometheus_text()
    assert "retries_total 2" in text
    assert "# TYPE van_op_latency_s histogram" in text
    assert "# TYPE width gauge" in text and "width 4.0" in text


# ---------------------------------------------------------------------------
# ServeMetrics port (satellites: deque ring, p90/p99)
# ---------------------------------------------------------------------------

def test_serve_metrics_ttft_ring_is_bounded_deque():
    from collections import deque

    from hetu_tpu.serve.metrics import ServeMetrics
    m = ServeMetrics(window=8)
    assert isinstance(m._ttft, deque) and m._ttft.maxlen == 8
    for i in range(100):
        m.observe_ttft(0.001 * (i + 1))
    assert len(m._ttft) == 8
    snap = m.snapshot()
    # avg/max AND percentiles all over the WINDOW (last 8 observations):
    # mutually consistent, tracking current latency — slow-start history
    # outside the window must not dominate p50 forever
    assert snap["ttft_max_s"] == pytest.approx(0.1)
    assert snap["ttft_avg_s"] == pytest.approx(np.mean(
        [0.001 * (i + 1) for i in range(92, 100)]))
    assert 0.093 - 1e-9 <= snap["ttft_p50_s"] <= snap["ttft_p90_s"] \
        <= snap["ttft_p99_s"] <= 0.1 + 1e-9
    # the cumulative histogram (prometheus exposition) still saw all 100
    assert m._ttft_hist.count == 100
    assert "ttft_s_bucket" in m.prometheus_text()


def test_serve_metrics_report_through_logger():
    from hetu_tpu.serve.metrics import ServeMetrics
    m = ServeMetrics()
    m.inc("requests_ok", 2)
    m.set_gauge("queue_depth", 1)
    m.observe_ttft(0.02)
    # one decode round of the round log (seq, kind, five seams in ns, batch,
    # pages, tokens): 8 tokens in 4 ms
    m.observe_round(1, 0, 1_000_000, 2_000_000, 2_500_000, 4_500_000,
                    5_000_000, 8, 4, 8)
    lg = ht.utils.logger.MetricLogger()
    snap = m.report(lg, step=1)
    for key in ("requests_ok", "queue_depth", "ttft_avg_s", "ttft_p50_s",
                "ttft_p90_s", "ttft_p99_s", "ttft_max_s", "tokens_per_sec",
                "rounds_kept", "decode_fetch_p50_ms", "decode_prep_p95_ms"):
        assert key in snap
    assert snap["tokens_per_sec"] == pytest.approx(2000.0)
    assert snap["decode_fetch_p50_ms"] == pytest.approx(2.0)
    assert lg.means()["requests_ok"] == 2


# ---------------------------------------------------------------------------
# instrumentation + determinism
# ---------------------------------------------------------------------------

def _tiny_supervised(seed, schedule, steps=10):
    model = layers.Sequential(layers.Linear(8, 16), layers.Relu(),
                              layers.Linear(16, 2))

    def loss_fn(params, model_state, batch, rng, train):
        out, new_state = model.apply(
            {"params": params, "state": model_state}, batch["x"],
            train=train, rng=rng)
        loss = jnp.mean(ht.ops.softmax_cross_entropy_sparse(
            out, batch["y"]))
        return loss, ({}, new_state)

    ex = Executor(loss_fn, optim.AdamOptimizer(0.01), seed=seed)
    state = ex.init_state(model.init(jax.random.PRNGKey(seed)))
    g = np.random.default_rng(0)
    X = g.standard_normal((32, 8)).astype(np.float32)
    Y = (X.sum(1) > 0).astype(np.int32)
    t = telemetry.enable()
    sup = Supervisor(ex, injector=FaultInjector(schedule),
                     backoff_base_s=0.001)
    rep = sup.run(state, lambda i: {"x": X, "y": Y}, steps)
    telemetry.disable()
    return t, rep


def test_executor_and_supervisor_phase_spans():
    sched = FaultSchedule([])
    t, rep = _tiny_supervised(0, sched, steps=4)
    names = [e["name"] for e in t.events]
    assert "train.compile" in names
    assert names.count("train.data_wait") == 4
    assert names.count("train.host_to_device") == 4
    assert names.count("train.step.train_guarded") == 4


def test_fault_instants_are_seed_deterministic():
    """Two chaos runs with the same fault seed emit the IDENTICAL ordered
    sequence of injection instant-events (names + args, schedule id
    included) — the replay contract the timeline tooling depends on."""
    sched = FaultSchedule.generate(steps=10, seed=11, data_errors=2,
                                   nan_steps=1, van_delays=1)
    t1, _ = _tiny_supervised(0, sched)
    t2, _ = _tiny_supervised(0, sched)
    f1 = [(e["name"], e["args"]) for e in t1.events
          if e["name"].startswith("fault.")]
    f2 = [(e["name"], e["args"]) for e in t2.events
          if e["name"].startswith("fault.")]
    assert f1 == f2 and len(f1) == len(sched)
    assert all(a["schedule"] == sched.schedule_id for _, a in f1)
    # byte-identical: serialize the ordered sequence
    assert json.dumps(f1) == json.dumps(f2)


def test_chaos_faults_pair_with_recoveries():
    sched = FaultSchedule.generate(steps=12, seed=3, data_errors=2,
                                   nan_steps=1)
    t, rep = _tiny_supervised(0, sched, steps=12)
    pairs = timeline.correlate(t.events)
    assert len(pairs) == 3
    assert all(p.paired for p in pairs)
    for p in pairs:
        assert p.recover_s >= p.detect_s >= 0
    rep_d = timeline.report(pairs)
    assert rep_d["data_error"]["injected"] == 2
    assert rep_d["data_error"]["paired"] == 2
    assert "p99" in rep_d["data_error"]["recover_s"]


def test_timeline_synthetic_pairing_rules():
    evs = [
        {"ph": "i", "name": "fault.kill_shard", "ts": 100.0, "seq": 0,
         "args": {"kind": "kill_shard", "step": 1}},
        {"ph": "i", "name": "fault.van_delay", "ts": 110.0, "seq": 1,
         "args": {"kind": "van_delay", "step": 2}},
        # ends before the fault: must not pair
        {"ph": "X", "name": "recovery.shard_repair", "ts": 10.0,
         "dur": 20.0, "seq": 2, "args": {}},
        {"ph": "X", "name": "recovery.shard_repair", "ts": 400.0,
         "dur": 50.0, "seq": 3, "args": {}},
        # loss+join sharing one reshard
        {"ph": "i", "name": "fault.worker_loss", "ts": 500.0, "seq": 4,
         "args": {"kind": "worker_loss", "step": 5}},
        {"ph": "i", "name": "fault.worker_join", "ts": 500.5, "seq": 5,
         "args": {"kind": "worker_join", "step": 5}},
        {"ph": "X", "name": "elastic.reshard", "ts": 600.0, "dur": 80.0,
         "seq": 6, "args": {}},
    ]
    pairs = timeline.correlate(evs)
    by_kind = {p.kind: p for p in pairs}
    ks = by_kind["kill_shard"]
    assert ks.paired and ks.recovery_start_us == 400.0
    assert ks.detect_s == pytest.approx(300e-6)
    assert ks.recover_s == pytest.approx(350e-6)
    assert not by_kind["van_delay"].paired  # needs no recovery
    # one reshard answers both membership faults
    assert by_kind["worker_loss"].recovery_name == "elastic.reshard"
    assert by_kind["worker_join"].recovery_name == "elastic.reshard"
    reg = timeline.recovery_histograms(pairs)
    assert reg.metrics()["recovery.kill_shard.detect_s"].count == 1
    assert reg.metrics()["recovery.van_delay.unpaired"].value == 1


def test_timeline_suspend_takes_its_own_retry_not_a_later_repair():
    """Multi-name kinds pair time-first: a suspend_shard answered by a
    quick retry must NOT claim an unrelated later kill_shard's
    shard_repair (which would skew both kinds' SLO histograms)."""
    evs = [
        {"ph": "i", "name": "fault.suspend_shard", "ts": 100.0, "seq": 0,
         "args": {"kind": "suspend_shard", "step": 1}},
        {"ph": "X", "name": "recovery.retry", "ts": 110.0, "dur": 10.0,
         "seq": 1, "args": {}},
        {"ph": "i", "name": "fault.kill_shard", "ts": 300.0, "seq": 2,
         "args": {"kind": "kill_shard", "step": 3}},
        {"ph": "X", "name": "recovery.shard_repair", "ts": 350.0,
         "dur": 50.0, "seq": 3, "args": {}},
    ]
    by_kind = {p.kind: p for p in timeline.correlate(evs)}
    assert by_kind["suspend_shard"].recovery_name == "recovery.retry"
    assert by_kind["suspend_shard"].recovery_end_us == 120.0
    assert by_kind["kill_shard"].recovery_name == "recovery.shard_repair"
    assert by_kind["kill_shard"].recovery_end_us == 400.0


def test_timeline_serve_preempt_prefers_migrate_over_earlier_failover():
    """serve_preempt is PREFERENCE_ORDERED: its migrate drain wins even
    when an unrelated failover (here answering an engine kill) ended
    first — and the kill still gets that failover."""
    evs = [
        {"ph": "i", "name": "fault.serve_preempt", "ts": 100.0, "seq": 0,
         "args": {"kind": "serve_preempt", "step": 1}},
        {"ph": "i", "name": "fault.serve_engine_kill", "ts": 105.0,
         "seq": 1, "args": {"kind": "serve_engine_kill", "step": 1}},
        {"ph": "X", "name": "serve.failover", "ts": 110.0, "dur": 10.0,
         "seq": 2, "args": {}},
        {"ph": "X", "name": "serve.migrate", "ts": 150.0, "dur": 30.0,
         "seq": 3, "args": {}},
    ]
    by_kind = {p.kind: p for p in timeline.correlate(evs)}
    assert by_kind["serve_preempt"].recovery_name == "serve.migrate"
    assert by_kind["serve_engine_kill"].recovery_name == "serve.failover"


def test_timeline_failed_recovery_span_is_never_claimed():
    """A serve.migrate span whose drain FAILED (tracer tags args.error)
    repaired nothing: the preemption must pair with the real failover
    that followed, not the rolled-back migrate."""
    evs = [
        {"ph": "i", "name": "fault.serve_preempt", "ts": 100.0, "seq": 0,
         "args": {"kind": "serve_preempt", "step": 1}},
        {"ph": "X", "name": "serve.migrate", "ts": 110.0, "dur": 10.0,
         "seq": 1, "args": {"error": "RuntimeError"}},
        {"ph": "X", "name": "serve.failover", "ts": 200.0, "dur": 20.0,
         "seq": 2, "args": {}},
    ]
    (p,) = timeline.correlate(evs)
    assert p.recovery_name == "serve.failover"
    assert p.recovery_start_us == 200.0


def test_timeline_preempt_claims_the_preempt_checkpoint():
    """A cadence checkpoint landing between the SIGTERM and the preempt
    checkpoint must NOT be claimed as the preempt's recovery — the
    matcher filters by the span's recorded reason."""
    evs = [
        {"ph": "i", "name": "fault.preempt", "ts": 100.0, "seq": 0,
         "args": {"kind": "preempt", "step": 4}},
        {"ph": "X", "name": "supervisor.checkpoint", "ts": 150.0,
         "dur": 10.0, "seq": 1, "args": {"reason": "cadence", "step": 4}},
        {"ph": "X", "name": "supervisor.checkpoint", "ts": 200.0,
         "dur": 10.0, "seq": 2, "args": {"reason": "preempt", "step": 4}},
    ]
    (p,) = timeline.correlate(evs)
    assert p.paired and p.recovery_start_us == 200.0


# ---------------------------------------------------------------------------
# trace_report CLI
# ---------------------------------------------------------------------------

def _load_trace_report():
    spec = importlib.util.spec_from_file_location(
        "trace_report", REPO / "tools" / "trace_report.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_trace_report_renders_phases_and_fault_table(tmp_path, capsys):
    sched = FaultSchedule.generate(steps=10, seed=3, data_errors=1,
                                   nan_steps=1)
    t, _ = _tiny_supervised(0, sched)
    jsonl = tmp_path / "run.trace.jsonl"
    with open(jsonl, "w") as f:
        for e in t.events:
            f.write(json.dumps(e) + "\n")
    chrome = t.write_chrome(tmp_path / "run.trace.json")

    tr = _load_trace_report()
    assert tr.main([str(jsonl)]) == 0
    out = capsys.readouterr().out
    assert "per-phase breakdown" in out
    assert "train.step.train_guarded" in out
    assert "fault -> recovery" in out
    assert "data_error" in out and "nan_grad" in out
    assert "UNPAIRED" not in out

    # the chrome export parses to the same phase totals
    assert tr.main([str(chrome), "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert any(p["name"] == "train.data_wait" for p in doc["phases"])
    assert doc["faults"]["data_error"]["paired"] == 1


def test_trace_report_empty_trace(tmp_path, capsys):
    p = tmp_path / "empty.jsonl"
    p.write_text("")
    tr = _load_trace_report()
    assert tr.main([str(p)]) == 0
    out = capsys.readouterr().out
    assert "no spans" in out and "no injected faults" in out


# ---------------------------------------------------------------------------
# graphboard satellite
# ---------------------------------------------------------------------------

def test_graphboard_escapes_script_breaking_labels(tmp_path):
    """A node label containing </script> must not terminate the embedded
    <script> block (HTML injection / broken page)."""
    from hetu_tpu.graphboard import render_html
    g = {"nodes": [{"id": "a",
                    "label": "</script><script>alert(1)</script>",
                    "kind": "op"}],
         "edges": []}
    path = render_html(g, tmp_path / "g.html")
    text = Path(path).read_text()
    # only the template's own closer remains; the payload is escaped
    assert text.count("</script>") == 1
    assert "\\u003c/script>" in text
    # the embedded JSON still parses to the original label
    start = text.index("const graph = ") + len("const graph = ")
    end = text.index(";\nconst svg")
    parsed = json.loads(text[start:end])
    assert parsed["nodes"][0]["label"] == g["nodes"][0]["label"]


def test_graphboard_export_still_works(tmp_path):
    from hetu_tpu.graphboard import export_html

    def fn(x):
        return jnp.tanh(x) * 2.0

    path = export_html(fn, jnp.ones((2, 2)), path=tmp_path / "jx.html")
    text = Path(path).read_text()
    assert "hetu_tpu graphboard" in text and "tanh" in text
