"""The engine's round log (ISSUE 54): ``serve/metrics.py`` ``RoundLog``.

Every engine call (a decode round, a prefill chunk) leaves one row: its
launch's ``seq``, its kind, the five seam times on ``time.monotonic_ns()``
and its buckets.  No profiler and no tracer is needed to read it; here a
``Tracer`` is installed only to hear what the launch spans' ids said, which
is what a row has to agree with.  ``tests/test_profiler_spans.py`` lays the
rows over the profiler's spans.
"""

import gc
import time
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.models.gpt import GPTConfig, GPTModel
from hetu_tpu.serve import PagedServeEngine
from hetu_tpu.serve.metrics import (
    CHUNK, DECODE, ROUND_FIELDS, ROUND_LOG_ROWS, RoundLog, ServeMetrics,
)
from hetu_tpu.telemetry import trace

pytestmark = pytest.mark.telemetry

ROUNDS = 3
CALLS = ("chunk_mid", "chunk_mid", "chunk_last") + ("round",) * ROUNDS


def _one_group():
    m = GPTModel(GPTConfig(
        vocab_size=97, hidden_size=64, num_layers=2, num_heads=4,
        ffn_size=128, max_position=64, dropout_rate=0.0))
    return m, m.init(jax.random.PRNGKey(0))


def _grouped_with_state():
    from hetu_tpu.models.lfm2_moe import Lfm2MoeConfig, Lfm2MoeModel

    m = Lfm2MoeModel(Lfm2MoeConfig(
        vocab_size=97, hidden_size=32, num_layers=5, num_heads=4,
        num_kv_heads=2, head_dim=8, ffn_size=64, expert_ffn_size=16,
        first_dense=1, n_routed_experts=8, moe_topk=2,
        layer_types=("conv", "full_attention", "conv", "conv",
                     "full_attention"),
        max_position=64, dtype=jnp.float32, param_dtype=jnp.float32,
        init_std=0.2, router_init_std=0.5, expert_block_rows=4))
    return m, jax.jit(m.init)(jax.random.PRNGKey(3))


@pytest.fixture(scope="module", params=["one_group", "grouped_with_state"])
def driven(request):
    """An engine of each kind of cache after a 20-token prompt in chunks of
    8 (two chunks that return None, the last the first token) and three
    decode rounds: (what each call returned, the launch spans' ids in order,
    the rows, the engine)."""
    model, variables = {"one_group": _one_group,
                        "grouped_with_state": _grouped_with_state}[
                            request.param]()
    tracer = trace.enable()
    try:
        eng = PagedServeEngine(model, variables, num_slots=4, max_len=64,
                               page_size=4, prefill_chunk=8, min_bucket=4)
        assert bool(eng._more or eng._states) == (
            request.param == "grouped_with_state")
        slot = eng.alloc_slot()
        eng.begin_prefill(slot, list(range(1, 21)))
        returned = [eng.prefill_step(slot) for _ in range(3)]
        returned += [eng.decode() for _ in range(ROUNDS)]
    finally:
        trace.disable()
    launches = [e for e in tracer.chrome_trace()["traceEvents"]
                if e["name"].endswith(".launch")]
    return returned, launches, eng.metrics.rounds(), eng


def test_every_call_leaves_exactly_one_row(driven):
    returned, launches, rows, eng = driven
    assert returned[:2] == [None, None] and isinstance(returned[2], int)
    assert all(len(out) == 1 for out in returned[3:])
    assert len(rows) == len(CALLS) == len(launches)
    assert rows[:, 0].tolist() == list(range(1, len(CALLS) + 1))
    assert eng.decode() and len(eng.metrics.rounds()) == len(CALLS) + 1
    eng.active[:] = False    # nothing to decode: no call, no row
    assert eng.decode() == {}
    assert len(eng.metrics.rounds()) == len(CALLS) + 1


@pytest.mark.parametrize("at", range(len(CALLS)),
                         ids=[f"{i}-{c}" for i, c in enumerate(CALLS)])
def test_a_row_says_what_its_launch_spans_ids_say(driven, at):
    """A chunk's row, the one of a chunk that returned early too: ``batch``
    1, ``pages`` the chunk bucket, ``tokens`` the rows prefilled; a round's:
    the slot bucket, the page bucket, a token an active slot."""
    _, launches, rows, _ = driven
    row = dict(zip(ROUND_FIELDS, rows[at].tolist()))
    ids = launches[at]["args"]
    assert row["seq"] == ids["seq"] == at + 1
    if CALLS[at] == "round":
        assert launches[at]["name"] == "serve.decode.launch"
        assert (row["kind"], row["batch"], row["pages"], row["tokens"]) \
            == (DECODE, ids["batch"], ids["pages"], 1)
    else:
        assert launches[at]["name"] == "serve.prefill_chunk.launch"
        assert (row["kind"], row["batch"], row["pages"], row["tokens"]) \
            == (CHUNK, 1, ids["bucket"], ids["tokens"])
        assert row["tokens"] == (4 if CALLS[at] == "chunk_last" else 8)
    seams = [row["t_" + s] for s in ("prep", "launch", "fetch", "post",
                                     "close")]
    assert seams == sorted(seams) and seams[0] < seams[-1]
    if at:
        assert seams[0] >= rows[at - 1][ROUND_FIELDS.index("t_close")]


def test_the_stamps_are_on_the_clock_time_monotonic_reads():
    before = time.monotonic()
    m, v = _one_group()
    eng = PagedServeEngine(m, v, num_slots=2, max_len=32, page_size=4,
                           prefill_chunk=8, min_bucket=4)
    eng.prefill(eng.alloc_slot(), [1, 2, 3])
    eng.decode()
    after = time.monotonic()
    seams = eng.metrics.rounds()[:, 2:7]
    assert before * 1e9 <= seams.min() and seams.max() <= after * 1e9


def _row(seq, kind, t_prep, parts_us, batch=8, pages=4, tokens=8):
    """A hand-made row: the four phases in microseconds from ``t_prep``."""
    seams = np.cumsum([t_prep] + [1000 * p for p in parts_us])
    return (seq, kind, *seams.tolist(), batch, pages, tokens)


def test_the_ring_is_bounded_and_rounds_is_a_copy():
    m = ServeMetrics()
    for i in range(ROUND_LOG_ROWS + 10):
        m.observe_round(*_row(i, DECODE, 1000 * i, (1, 1, 1, 1)))
    rows = m.rounds()
    assert rows.shape == (ROUND_LOG_ROWS, len(ROUND_FIELDS))
    assert rows[0, 0] == 10 and rows[-1, 0] == ROUND_LOG_ROWS + 9
    rows[:] = -1
    assert m.rounds()[0, 0] == 10


def test_snapshot_reads_the_phases_the_gap_and_the_rate_from_the_log():
    """Five rounds whose fetch takes 1, 2, 3, 4 and 5 ms and two chunks, 0.3
    ms apart: nearest-rank percentiles as the ``ttft_*`` keys take theirs
    (of five values p50 is the third, p95 the fifth), each phase under its
    own key, the gap over every pair of neighbours, and ``tokens_per_sec``
    the rounds' tokens over the time from the first round's opening to the
    last one's close."""
    m = ServeMetrics()
    assert "tokens_per_sec" not in m.snapshot()
    assert "rounds_kept" not in m.snapshot()
    t, rows = 5_000_000_000, []
    for i in range(5):
        rows.append(_row(i + 1, DECODE, t, (100 + 10 * i, 500,
                                            1000 * (i + 1), 200)))
        t = rows[-1][6] + 300_000
    for i in range(2):
        rows.append(_row(6 + i, CHUNK, t, (50, 700, 9000 + 1000 * i, 80),
                         batch=1, pages=64, tokens=40))
        t = rows[-1][6] + 300_000
    for r in rows:
        m.observe_round(*r)
    snap = m.snapshot()
    assert snap["rounds_kept"] == 7
    want = {"decode_prep": (0.12, 0.14), "decode_launch": (0.5, 0.5),
            "decode_fetch": (3.0, 5.0), "decode_post": (0.2, 0.2),
            "chunk_prep": (0.05, 0.05), "chunk_launch": (0.7, 0.7),
            "chunk_fetch": (10.0, 10.0), "chunk_post": (0.08, 0.08),
            "engine_gap": (0.3, 0.3)}
    for key, (p50, p95) in want.items():
        assert snap[key + "_p50_ms"] == pytest.approx(p50), key
        assert snap[key + "_p95_ms"] == pytest.approx(p95), key
    # 5 rounds x 8 tokens from the first's t_prep to the fifth's t_close
    span_s = (rows[4][6] - rows[0][2]) / 1e9
    assert snap["tokens_per_sec"] == pytest.approx(40 / span_s)
    assert span_s == pytest.approx(
        (15_000 + 5 * 700 + 100 + 110 + 120 + 130 + 140 + 4 * 300) / 1e6)


def test_a_dropped_engines_log_pins_neither_the_engine_nor_its_metrics():
    """What finds a log without a handle on its engine, ``RoundLog.recent``,
    holds rows of integers: the engine, its parameters and its
    ``ServeMetrics`` go when they are dropped, and the log itself when eight
    newer ones have been made."""
    m, v = _one_group()
    eng = PagedServeEngine(m, v, num_slots=2, max_len=32, page_size=4,
                           prefill_chunk=8, min_bucket=4)
    eng.prefill(eng.alloc_slot(), [1, 2, 3])
    log = eng.metrics.round_log
    gone = [weakref.ref(eng), weakref.ref(eng.metrics),
            weakref.ref(eng.cache)]
    assert RoundLog.recent[-1] is log
    del eng
    gc.collect()
    assert [r() for r in gone] == [None, None, None]
    assert RoundLog.recent[-1] is log and len(log.rows()) == 1
    later = [ServeMetrics() for _ in range(RoundLog.recent.maxlen)]
    assert all(x is not log for x in RoundLog.recent)
    assert [x.round_log for x in later] == list(RoundLog.recent)
