"""The readers of the program's own spans (``hetu:<name>`` in the xplane),
on a trace recorded on a TPU v5e (``data/hetu_v5e.xplane.pb``, by
``tools/record_hetu_trace.py``: four scheduler steps of a small scanned
program with known host sleeps, 2 ms in ``serve.decode.prep`` and 0.5 ms in
its ``post``, 1 ms and 0.2 ms in the chunk's, and a train step's two spans
after each).  The expected values were worked out from the file's raw
events with plain loops, outside this package (the PR's scratch script;
device times shifted by the 1.30017 ms the twelve launches give)."""

from pathlib import Path
from types import SimpleNamespace

import importlib

import pytest

from benchmarks.harness import reduce, spec
from benchmarks.harness.reduce import Event, Line, Plane
from benchmarks.readers import hetu_spans

DATA = Path(__file__).parent / "data"
RECORDED = str(DATA / "hetu_v5e.xplane.pb")
NO_HETU = str(DATA / "tiny_v5e.xplane.pb")     # bench: spans only

# metric -> value by hand on the recorded trace
BY_HAND = {
    "decode_host_ms": 3.14411475,      # (prep + post) / 4 rounds
    "prefill_host_ms": 1.92946725,
    "sched_host_ms": 4.749618,         # step less decode and chunk
    "host_gap_share": 66.70955309329374,
    "train_dispatch_ms": 1.40179,      # host_to_device + step.train
}
NEEDS_DEVICE = {"host_gap_share"}


def ctx_of(path: str, *, summary: bool = True):
    return SimpleNamespace(
        trace=reduce.summarize(reduce.load(path)) if summary else None,
        run=SimpleNamespace(trace_path=path))


def read(name: str, ctx):
    f = spec.layer_metric_file(name)
    reader = importlib.import_module(f"benchmarks.readers.{f['reader']}")
    return reader.read(ctx, **f.get("params", {}))


@pytest.fixture(scope="module")
def recorded():
    return ctx_of(RECORDED)


@pytest.fixture(scope="module")
def no_hetu():
    return ctx_of(NO_HETU)


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_reader_gives_the_value_worked_out_by_hand(recorded, name):
    assert read(name, recorded) == pytest.approx(BY_HAND[name], rel=1e-9)


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_trace_without_program_spans_gives_none_not_zero(no_hetu, name):
    assert read(name, no_hetu) is None


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_new_entry_resolves_to_a_file_and_a_reader(name):
    entry = next(m for m in spec.manifest()["per_layer"]
                 if m["name"] == name)
    assert entry["source"] == ("device_trace" if name in NEEDS_DEVICE
                               else "program_span")
    stem = name.split(".")[0]
    assert (spec.BENCH / "layer_metrics" / f"{stem}.json").is_file()
    f = spec.layer_metric_file(name)
    assert set(f) == {"reader", "params"}
    assert f["reader"].startswith("hetu_")
    assert (spec.BENCH / "readers" / f"{f['reader']}.py").is_file()


def test_known_sleeps_show_in_the_host_metrics(recorded):
    """2 + 0.5 ms slept in a decode round's host phases, 1 + 0.2 in a
    chunk's, a sleep overshooting by up to half a millisecond; the device
    ran 0.12 ms a call, all of it under launch and fetch (by hand: the
    reader that laid busy time under those two spans went in PR 53, a
    program's device time is its own module run's, ``hetu_launches``)."""
    assert 2.5 <= read("decode_host_ms", recorded) <= 3.6
    assert 1.2 <= read("prefill_host_ms", recorded) <= 2.3
    sp = hetu_spans.spans(recorded)
    assert {len(iv) for iv in sp.values()} == {4}
    busy = recorded.trace.first_chip().busy
    for name in ("serve.decode.prep", "serve.decode.post", "serve.admit"):
        assert reduce.intersect(reduce.union(sp[name]), busy) == []
    whole = reduce.measure(reduce.intersect(
        reduce.union(sp["serve.decode"]), busy)) / 4e6
    under = reduce.measure(reduce.intersect(hetu_spans.intervals(
        sp, ["serve.decode.launch", "serve.decode.fetch"]), busy)) / 4e6
    assert under == pytest.approx(0.12340975, rel=1e-9)
    assert under == pytest.approx(whole, rel=0.05)


def test_host_gap_share_is_a_part_of_the_idle_share(recorded):
    idle = 100.0 * recorded.trace.idle_share
    assert idle == pytest.approx(97.3997386034324, rel=1e-9)
    assert 0 < read("host_gap_share", recorded) < idle


def test_without_a_device_in_the_trace_the_marked_window_is_used():
    """A rehearsal on the CPU has no trace summary: span metrics read the
    window the benchmark marked in the file, device metrics nothing."""
    ctx = ctx_of(RECORDED, summary=False)
    for name, want in BY_HAND.items():
        got = read(name, ctx)
        if name in NEEDS_DEVICE:
            assert got is None
        else:
            assert got == pytest.approx(want, rel=1e-9)
    assert hetu_spans.spans(SimpleNamespace(
        trace=None, run=SimpleNamespace(trace_path=None))) is None


def test_ids_after_a_hash_are_cut_and_spans_clipped_to_the_window(
        monkeypatch):
    planes = [
        Plane("/host:CPU", [Line("python", [
            Event("bench:trace_window", 100.0, 1000.0),
            Event("hetu:serve.decode#active=3,pages=4#", 200.0, 300.0),
            Event("hetu:serve.decode", 400.0, 450.0),
            Event("hetu:serve.decode", 50.0, 150.0),      # cut by the edge
            Event("hetu:serve.decode", 900.0, 1100.0),    # cut by the edge
            Event("other", 500.0, 600.0)])]),
        Plane("/device:TPU:0", [Line("XLA Ops", [
            Event("hetu:not.a.host.event", 200.0, 300.0)])])]
    monkeypatch.setattr(reduce, "load", lambda path: planes)
    hetu_spans._load.cache_clear()
    try:
        ctx = SimpleNamespace(trace=None,
                              run=SimpleNamespace(trace_path="by-hand"))
        assert hetu_spans.spans(ctx) == {
            "serve.decode": [(200.0, 300.0), (400.0, 450.0)]}
    finally:
        hetu_spans._load.cache_clear()
