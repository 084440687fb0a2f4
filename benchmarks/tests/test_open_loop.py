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
