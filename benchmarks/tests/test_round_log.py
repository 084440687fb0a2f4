"""``readers/round_log.py`` and ``tools/round_report.py`` against hand-made
round logs: the six ``window_*`` values worked out by hand, the cases in
which the reader declines (no log in the program, two logs, a ring that
wrapped), the window's edges, and the join of a log with a trace recorded on
the chip (``launch_serial_v5e.xplane.pb``) under a known clock offset."""

import importlib
import json
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks.harness import spec
from benchmarks.readers import hetu_launches, round_log
from benchmarks.tests.test_hetu_launches import SERIAL
from benchmarks.tools import round_report
from hetu_tpu.serve import metrics as program

NAMES = ("window_decode_fetch_ms", "window_decode_launch_ms",
         "window_decode_host_ms", "window_chunk_fetch_ms",
         "window_outside_ms", "window_stall_share")
SERVING = next(m for m in spec.manifest()["end_to_end"]
               if m["name"] == "serve_tokens_per_s")["workloads"]
OPENED_S, WINDOW_S = 100.0, 10.0
MS = 1_000_000


def ctx():
    return SimpleNamespace(run=SimpleNamespace(
        setup_done=OPENED_S, values={"window_s": WINDOW_S}))


def log_of(calls, *, at_ms: float, seq: int = 1) -> np.ndarray:
    """A log whose first call opens ``at_ms`` after the window does (before
    it: negative).  ``calls``: (kind, gap before, prep, launch, fetch,
    post) in ms, then batch, pages."""
    t = int(round((OPENED_S * 1000 + at_ms) * MS))
    rows = []
    for i, (kind, gap, prep, launch, fetch, post, batch, pages) in \
            enumerate(calls):
        if i:
            t += int(round(gap * MS))
        seams = np.cumsum([t] + [int(round(p * MS))
                                 for p in (prep, launch, fetch, post)])
        rows.append((seq + i, kind, *seams.tolist(), batch, pages, batch))
        t = int(seams[-1])
    return np.asarray(rows, np.int64)


D, C = program.DECODE, program.CHUNK


def worked_calls():
    """One round just before the window; in it nine rounds whose fetch takes
    4.0 ... 4.7 ms and once 40 ms, launch 0.50 ... 0.58, prep 0.3, post
    0.10 ... 0.18, the gap before them 0.20 ... 0.28 ms, and three chunks
    (fetch 20, 22 and 90 ms, gap 0.5)."""
    fetches = [4.0, 4.1, 4.2, 4.3, 4.4, 4.5, 4.6, 4.7, 40.0]
    calls = [(D, 0, .3, .6, 4.0, .1, 8, 4)]
    for i, f in enumerate(fetches):
        calls.append((D, .2 + .01 * i, .3, .5 + .01 * i, f, .1 + .01 * i,
                      8, 4))
    for f in (20.0, 22.0, 90.0):
        calls.append((C, .5, .2, .7, f, .1, 1, 64))
    return calls


@pytest.fixture
def worked(monkeypatch):
    calls = worked_calls()
    inside = log_of(calls, at_ms=-5.2)      # the first opens before
    late = log_of([(D, 0, .3, .6, 1000.0, .1, 8, 4)],
                  at_ms=WINDOW_S * 1000 + 500, seq=100)
    rows = np.concatenate([inside, late])
    monkeypatch.setattr(round_log, "program_log",
                        lambda: (program, [rows]))
    return rows


def read(name: str):
    f = spec.layer_metric_file(name)
    reader = importlib.import_module(f"benchmarks.readers.{f['reader']}")
    return reader.read(ctx(), **f.get("params", {}))


@pytest.mark.parametrize("name,want", [
    ("window_decode_fetch_ms", 4.4),        # the fifth of nine
    ("window_decode_launch_ms", 0.54),
    ("window_decode_host_ms", 0.3 + 0.14),  # prep + post, fifth of nine
    ("window_chunk_fetch_ms", 22.0),
    # twelve gaps: .20 ... .28 and three of .5: between the sixth and seventh
    ("window_outside_ms", 0.255),
    # the rounds' fetch alone runs over three medians: 40 - 3 x 4.4 ms of
    # 10 s; the chunks are a group of three, under eight, and not counted
    ("window_stall_share", 100 * (40 - 3 * 4.4) / (WINDOW_S * 1000)),
])
def test_each_value_by_hand(worked, name, want):
    assert read(name) == pytest.approx(want, rel=1e-9)


def test_the_first_row_opens_before_the_window_and_is_left_out(worked):
    col, inside, _ = round_log.window_columns(ctx().run)
    assert inside.sum() == 12 and not inside[0] and not inside[-1]
    # its close to the window's first call is that call's gap all the same
    assert col["gap"][1] == pytest.approx(0.2 * MS)


def test_equal_calls_stall_nothing(monkeypatch):
    rows = log_of([(D, .2, .3, .6, 4.0, .1, 8, 4)] * 40, at_ms=-3.0)
    monkeypatch.setattr(round_log, "program_log",
                        lambda: (program, [rows]))
    assert read("window_stall_share") == 0.0
    assert read("window_chunk_fetch_ms") is None    # no chunk in the window


def test_a_program_without_the_log_gives_none(monkeypatch):
    monkeypatch.delattr(program, "RoundLog")
    assert round_log.program_log() is None
    assert [read(n) for n in NAMES] == [None] * 6


def test_two_logs_in_the_window_give_none_and_an_idle_one_is_ignored(
        monkeypatch):
    rows = log_of(worked_calls(), at_ms=-5.2)
    empty = np.zeros((0, len(program.ROUND_FIELDS)), np.int64)
    before = log_of([(D, 0, .3, .6, 4.0, .1, 8, 4)] * 3, at_ms=-500.0)
    monkeypatch.setattr(round_log, "program_log",
                        lambda: (program, [empty, rows, before]))
    assert read("window_decode_fetch_ms") == pytest.approx(4.4)
    monkeypatch.setattr(round_log, "program_log",
                        lambda: (program, [rows, rows.copy()]))
    assert [read(n) for n in NAMES] == [None] * 6
    monkeypatch.setattr(round_log, "program_log",
                        lambda: (program, [empty, before]))
    assert [read(n) for n in NAMES] == [None] * 6


def test_a_wrapped_ring_gives_none(monkeypatch):
    """The oldest row the ring kept opened inside the window: what came
    before it is lost, and the rest must not read as the whole window."""
    rows = log_of(worked_calls(), at_ms=-5.2)[1:]
    monkeypatch.setattr(round_log, "program_log",
                        lambda: (program, [rows]))
    assert [read(n) for n in NAMES] == [None] * 6


def test_the_reader_finds_the_programs_own_log(monkeypatch):
    """Through ``RoundLog.recent``, with no handle on what made it."""
    monkeypatch.setattr(program.RoundLog, "recent",
                        type(program.RoundLog.recent)(maxlen=8))
    m = program.ServeMetrics()
    for row in log_of(worked_calls(), at_ms=-5.2):
        m.observe_round(*row.tolist())
    del m
    assert read("window_decode_fetch_ms") == pytest.approx(4.4)


@pytest.mark.parametrize("name", NAMES)
def test_each_entry_resolves_to_its_own_file_and_the_one_reader(name):
    entry = next(m for m in spec.manifest()["per_layer"]
                 if m["name"] == name)
    assert spec.layer_metric_path(name).name == name + ".json"
    assert spec.layer_metric_file(name)["reader"] == "round_log"
    assert entry["workloads"] == SERVING
    assert (entry["source"], entry["better"], entry["moves"]) == (
        "program_span", "lower", "serve_tokens_per_s")
    assert entry["unit"] == ("%" if name == "window_stall_share" else "ms")
    assert entry["layer"] == ("scheduler" if name == "window_outside_ms"
                              else "serving engine")


def test_the_manifest_has_71_entries_and_the_six_are_its_last():
    names = [m["name"] for m in spec.manifest()["per_layer"]]
    assert len(names) == 71 and tuple(names[-6:]) == NAMES


def test_a_traced_rehearsal_prints_the_six_names(monkeypatch, capsys):
    import os

    from benchmarks import run as bench

    for var in ("JAX_PLATFORMS", "XLA_FLAGS"):   # --rehearse sets them
        monkeypatch.setenv(var, os.environ.get(var, ""))
    rc = bench.main(["--workload", "gpt2-large.batch", "--seconds", "1",
                     "--seed", "3000000029", "--rehearse", "--trace", "1"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"]
    assert set(NAMES) <= set(line["metric_names"])


# --------------------------------------------------- tools/round_report.py

def test_the_identity_reads_100_when_the_log_covers_the_window(worked):
    """Parts and gaps clipped to the window: the long wait from the last
    chunk to the round that opens after the far edge is a gap like any
    other, so everything adds up; the shares are each part's."""
    col, inside, _ = round_log.window_columns(ctx().run)
    t0, t1 = round_log.window_of(ctx().run)
    lines = round_report.window_lines(col, inside, t0, t1)
    window = lines[0]["window"]
    assert window["calls"] == 12
    assert window["identity_pct"] == pytest.approx(100.0, abs=1e-9)
    fetch_ms = sum([4.0, 4.1, 4.2, 4.3, 4.4, 4.5, 4.6, 4.7, 40.0]) + 132.0
    assert window["share_pct"]["fetch"] == pytest.approx(
        100 * fetch_ms / (WINDOW_S * 1000))
    buckets = [x["bucket"] for x in lines if "bucket" in x]
    assert [(b["kind"], b["batch"], b["pages"], b["calls"])
            for b in buckets] == [("serve.decode", 8, 4, 9),
                                  ("serve.prefill_chunk", 1, 64, 3)]
    assert buckets[0]["fetch"]["p50"] == pytest.approx(4.4)
    assert buckets[0]["fetch"]["max"] == pytest.approx(40.0)
    longest = [x["long"] for x in lines if "long" in x]
    assert len(longest) == 10
    assert (longest[0]["kind"], longest[0]["carried_by"]) == (
        "serve.prefill_chunk", "fetch")     # 90 ms where 22 is usual
    assert longest[0]["excess_ms"] == pytest.approx(68.0)
    assert (longest[1]["seq"], longest[1]["carried_by"]) == (10, "fetch")


def test_the_identity_falls_short_where_the_log_does(monkeypatch):
    rows = log_of(worked_calls(), at_ms=2000.0)     # 2 s of 10 unseen
    col = round_log.columns(program.ROUND_FIELDS, rows)
    t0, t1 = round_log.window_of(ctx().run)
    inside = (col["t_prep"] >= t0) & (col["t_prep"] < t1)
    window = round_report.window_lines(col, inside, t0, t1)[0]["window"]
    assert window["identity_pct"] < 3.0     # 0.2 s of calls in 10 s


def test_a_log_joins_a_recorded_trace_by_seq_under_one_offset():
    """Rows made from the recorded launches' own spans, on a clock 7 s
    behind the profiler's and read 2 us inside each span: the join gives the
    offset back to the read, the log's ``launch`` the span's less nothing,
    and ``fetch`` less the module's own run on the device a bucket."""
    records, _ = hetu_launches.pair_scan(hetu_launches.scan_file(SERIAL))
    offset, inset = 7_000_000_000, 2_000
    rows = []
    for rec in records:
        chunk = rec.kind == "serve.prefill_chunk"
        t_launch = int(rec.l0) - offset + inset
        t_fetch = int(rec.fetch[0]) - offset + inset
        t_post = int(rec.fetch[1]) - offset + inset
        rows.append((rec.ids["seq"], C if chunk else D, t_launch - 300_000,
                     t_launch, t_fetch, t_post, t_post + 100_000,
                     1 if chunk else rec.ids["batch"],
                     rec.ids["bucket"] if chunk else rec.ids["pages"], 1))
    col = round_log.columns(program.ROUND_FIELDS, np.asarray(rows, np.int64))
    lines = round_report.stretch_lines(col, np.zeros(len(rows), bool),
                                       SERIAL)
    clock = lines[0]["clock"]
    assert clock["joined"] == len(records) == 8
    assert clock["offset_ns"] == offset - inset
    assert clock["offset_range_us"] == 0.0
    assert clock["fetch_less_span_us"] == 0.0
    by_kind = {x["stretch"]["kind"]: x["stretch"] for x in lines[1:]}
    rounds = [r for r in records if r.kind == "serve.decode"]
    want = np.median([r.fetch[1] - r.fetch[0] - r.program_ns
                      for r in rounds]) / 1e6
    assert by_kind["serve.decode"]["launches"] == len(rounds)
    assert by_kind["serve.decode"]["fetch_less_program_p50_ms"] == \
        pytest.approx(want)
    assert by_kind["serve.decode"]["window_fetch_p50_ms"] is None
