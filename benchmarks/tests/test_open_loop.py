"""The ``open_loop_schedule`` loop, end to end on the CPU at rehearsal
widths.  No cell of ``BENCHMARK.json`` runs this kind today (PERF.md says
why), so this test is what keeps the loop, the ``chat-small`` mix and the
four readers a latency cell will use in working order."""

import pytest

from benchmarks import run as bench
from benchmarks.harness import loops, spec
from benchmarks.harness.spans import Recorder

SECONDS = 2.0


@pytest.fixture(scope="module")
def replay():
    man = spec.manifest()
    config = spec.config(man, "gpt2-small", rehearse=True)
    traffic = spec.traffic("chat-small", rehearse=True)
    ctx = bench.Ctx(cell={"name": "rehearsal", "chips": 1}, config=config,
                    traffic=traffic, seed=3000000019, seconds=SECONDS,
                    trace=False, chips=1, rec=Recorder(),
                    compiles=loops.CompileCounter())
    return ctx, loops.open_loop(ctx)


def test_every_counted_request_is_sent_answered_and_checked(replay):
    ctx, run = replay
    assert run.attempted == round(ctx.traffic["rate_rps"] * SECONDS)
    assert run.failed == 0 and run.check["ok"]
    assert run.compiles_in_window == 0
    v = run.values
    assert len(v["ttft_ms"]) == run.attempted
    # the first token is stamped by the benchmark when its step returns,
    # after the request was due; later tokens follow it
    assert min(v["ttft_ms"]) > 0 and min(v["itl_ms"]) >= 0
    assert run.end_to_end["ttft_mean_ms"] > 0


def test_what_runs_muted_leaves_nothing_in_the_recorder():
    """The comparison with the reference runs after the window through the
    same wrapped engine; its steps must not reach the window's means."""
    rec = Recorder()
    with rec.span("engine.decode"):
        rec.counters["prefill_tokens"] += 3
    with rec.muted():
        with rec.span("engine.decode"):
            rec.series["decode_active"].append(4)
            rec.counters["prefill_tokens"] += 5
    assert len(rec.spans["engine.decode"]) == 1
    assert rec.counters["prefill_tokens"] == 3 and not rec.series


@pytest.mark.parametrize("name", ["ttft_p95_ms", "queue_wait_p50_ms",
                                  "sched_self_ms", "loadgen_lag_p99_ms"])
def test_latency_readers_read_the_replay(replay, name):
    ctx, run = replay
    rctx = bench.ReadCtx(run=run, rec=ctx.rec, trace=None,
                         config=ctx.config, traffic=ctx.traffic,
                         cell=ctx.cell, peaks=None, memory=run.memory)
    got = bench.read_layer_metrics(rctx, [{"name": name, "unit": "ms"}])
    assert got[name]["value"] >= 0


def test_a_slow_process_and_a_stall_read_apart_in_the_windows_counts():
    """``Serving.stalls`` from the lists the window already keeps: a
    process in which every call is slower moves the MEDIAN of its engine
    spans; one held step moves the longest and the mean and leaves the
    median where it was."""
    def counts(decode_ms):
        sv = loops.Serving.__new__(loops.Serving)
        sv.rec, sv.host_probe, t = Recorder(), [], 0.0
        sv.steps = []
        for ms in decode_ms:
            sv.rec.spans["engine.decode"].append((t, t + ms / 1e3))
            sv.steps.append((t, t + ms / 1e3 + 1e-4))
            t += ms / 1e3 + 2e-4
        sv.rec.spans["engine.prefill_step"] += [(0.0, 0.040), (1.0, 1.050)]
        return sv.stalls(0.0)

    fast = counts([5.0] * 99 + [6.0])
    assert fast["decode_span_median_ms"] == pytest.approx(5.0)
    assert fast["decode_span_p95_ms"] == pytest.approx(5.0)
    assert fast["decode_span_mean_ms"] == pytest.approx(5.01)
    assert fast["chunk_span_median_ms"] == pytest.approx(45.0)
    assert fast["chunk_span_p95_ms"] == pytest.approx(49.5)
    assert fast["steps_over_1p5x_median"] == 0
    slow = counts([7.2] * 99 + [8.2])        # every call 2.2 ms up
    assert slow["decode_span_median_ms"] == pytest.approx(7.2)
    assert slow["steps_over_1p5x_median"] == 0
    held = counts([5.0] * 97 + [9.0, 9.0, 2500.0])   # one launch held
    assert held["decode_span_median_ms"] == pytest.approx(5.0)
    assert held["decode_span_mean_ms"] > 6 * held["decode_span_median_ms"]
    assert held["steps_over_1p5x_median"] == 3
    assert held["longest_step_ms"] == pytest.approx(2500.1)


def test_the_traced_stretch_runs_without_the_python_tracer(
        monkeypatch, tmp_path):
    """``traced`` hands the profiler ``python_tracer_level`` 0 and leaves
    the host tracer, which writes the spans and the runtime's events, as
    it is."""
    import jax

    calls = []
    monkeypatch.setattr(loops, "TRACE_DIR", tmp_path / "trace")
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda log_dir, **kw: calls.append((log_dir, kw)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    rec = Recorder()
    rec.series["decode_active"].append(3)       # from before the stretch
    with loops.traced(rec):
        assert rec.annotate and not rec.series
    (log_dir, kw), stop = calls
    assert log_dir == str(tmp_path / "trace") and stop == "stop"
    options = kw["profiler_options"]
    assert options.python_tracer_level == 0
    assert options.host_tracer_level == \
        jax.profiler.ProfileOptions().host_tracer_level
    assert not rec.annotate
