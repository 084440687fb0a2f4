"""``readers/hetu_launches.py`` against three traces recorded on the chip
(``benchmarks/tools/record_launch_trace.py``, TPU v5e): the engine's span
layout round two small programs jitted under the engine's names, once SERIAL
(every call launches and fetches its own program) and once with ONE LAUNCH OF
RUN-AHEAD (round n + 1 is launched, then round n fetched), and the
executor's round a train step dispatched one step ahead.  Each part of a
launch is worked out by hand from the file's events; a trace that cannot be
paired gives None."""

import copy
import importlib
from types import SimpleNamespace

import pytest

from benchmarks.harness import reduce, spec
from benchmarks.readers import hetu_launches, hetu_spans
from benchmarks.tests.test_hetu_readers import DATA, RECORDED

SERIAL = str(DATA / "launch_serial_v5e.xplane.pb")
RUN_AHEAD = str(DATA / "launch_runahead_v5e.xplane.pb")
TRAIN = str(DATA / "launch_train_v5e.xplane.pb")
NEW = {
    "decode_program_ms": ("serve.decode", "program", "device_trace"),
    "prefill_program_ms": ("serve.prefill_chunk", "program", "device_trace"),
    "decode_issue_ms": ("serve.decode", "issue", "program_span"),
    "decode_runtime_ms": ("serve.decode", "runtime", "device_trace"),
    "decode_readback_ms": ("serve.decode", "readback", "program_span"),
}
# what the benchmark's wrapper noted of the six rounds of a stretch: slots
# decoded, tokens live in them
ROUNDS = {"decode_active": [8, 8, 7, 7, 6, 6],
          "decode_cached_tokens": [1000, 1008, 900, 907, 800, 806]}
V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def ctx_of(path: str, series=None, peaks=V5E):
    """A reader's context over a recorded trace; ``decode_roofline`` also
    reads the device's peaks, a configuration (gpt2-small's, whose counts
    ``test_reduce.py`` works by hand) and the stretch's series."""
    man = spec.manifest()
    return SimpleNamespace(
        trace=reduce.summarize(reduce.load(path)), peaks=peaks,
        config=spec.config(man, "gpt2-small"),
        run=SimpleNamespace(trace_path=path, values={
            "traced_series": ROUNDS if series is None else series}))


def read(name: str, ctx):
    f = spec.layer_metric_file(name)
    reader = importlib.import_module(f"benchmarks.readers.{f['reader']}")
    return reader.read(ctx, **f.get("params", {}))


@pytest.fixture(scope="module")
def serial():
    return ctx_of(SERIAL)


@pytest.fixture(scope="module")
def run_ahead():
    return ctx_of(RUN_AHEAD)


def _raw(path: str) -> dict:
    """{event name: [(start, end, stats)]} of the file, host and device."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            if line.name == reduce.OPS_LINE:
                continue
            for e in line.events:
                out.setdefault(e.name.split("#")[0].split("(")[0], []).append(
                    (float(e.start_ns),
                     float(e.start_ns) + float(e.duration_ns),
                     dict(e.stats)))
    return {k: sorted(v, key=lambda x: x[0]) for k, v in out.items()}


# ----------------------------------------------- each part, worked by hand

def test_every_part_of_the_first_round_by_hand(serial):
    """The serial stretch's first decode round, event by event: the launch
    span, the Execute inside it, the module run with the completion's
    run_id, the =>Done, the fetch span of the same seq and its two
    transfers (tokens, then the counts)."""
    raw = _raw(SERIAL)
    first = hetu_launches.launches(serial, "serve.decode")[0]
    l0, l1, ids = next(e for e in raw["hetu:serve.decode.launch"])
    assert (first.l0, first.l1, first.ids) == (l0, l1, ids)
    (x,) = [a for a, _, _ in raw["tpu::System::Execute"] if l0 <= a <= l1]
    assert first.x == x
    run = next(m for m in raw["jit_hetu_serve_decode"])
    assert first.program_ns == pytest.approx(run[1] - run[0])
    done = next(c for c in raw["CompleteCallbacks"]
                if c[2]["run_id"] == run[2]["run_id"])
    (d,) = [a for a, _, _ in raw["tpu::System::Execute=>Done"]
            if done[0] <= a <= done[1]]
    assert first.done == d
    f0, f1, fids = next(e for e in raw["hetu:serve.decode.fetch"])
    assert fids == {"seq": ids["seq"]} and first.fetch == (f0, f1)
    before = max(a for a, _, _ in raw["tpu::System::Execute=>Done"] if a < d)
    assert first.done_prev == before < x      # serial: nothing queued
    assert first.issue_ns == x - l0
    assert first.runtime_ns == pytest.approx(d - x - (run[1] - run[0]))
    assert first.readback_ns == f1 - d
    moved = [s for _, b, s in raw[hetu_launches.HOST_TRANSFER_DONE]
             if f0 <= b <= f1]
    assert first.transfers == len(moved) == 2
    assert first.transfer_bytes == sum(s["size"] for s in moved)


@pytest.mark.parametrize("stem", sorted(NEW))
def test_metric_is_the_mean_of_its_part(serial, stem):
    kind, part, _ = NEW[stem]
    recs = hetu_launches.launches(serial, kind)
    values = [r.part_ns(part) for r in recs]
    assert len(values) == (2 if kind == "serve.prefill_chunk" else 6)
    assert read(stem, serial) == pytest.approx(
        sum(values) / len(values) / 1e6, rel=1e-12)
    assert all(v > 0 for v in values)


def test_the_parts_tile_the_call(serial):
    """prep + issue + program + runtime + readback + post is the call's own
    span but for the seams between the spans."""
    sp = hetu_spans.spans(serial)
    call = sum(b - a for a, b in sp["serve.decode"]) / 6e6
    host = sum(b - a for s in ("prep", "post")
               for a, b in sp[f"serve.decode.{s}"]) / 6e6
    parts = sum(read(f"decode_{p}_ms", serial)
                for p in ("issue", "program", "runtime", "readback"))
    assert host + parts == pytest.approx(call, rel=0.02)
    assert host + parts <= call


def _busy_under_the_calls_spans(ctx) -> float:
    """What the retired ``decode_device_ms`` read: milliseconds a round the
    chip was busy under the call's ``launch`` + ``fetch`` spans."""
    sp = hetu_spans.spans(ctx)
    under = hetu_spans.intervals(
        sp, ["serve.decode.launch", "serve.decode.fetch"])
    return reduce.measure(reduce.intersect(
        under, ctx.trace.first_chip().busy)) / 1e6 / len(sp["serve.decode"])


def test_serial_program_time_is_the_busy_time_under_the_calls_spans(serial):
    """On the serial engine the program's own run and the device's busy time
    under launch + fetch are the same thing, read two ways: which is why
    the second reader could go (PR 53)."""
    assert read("decode_program_ms", serial) == pytest.approx(
        _busy_under_the_calls_spans(serial), rel=0.02)


# ------------------------------------------------------------- run-ahead

def test_program_time_is_the_same_under_run_ahead(serial, run_ahead):
    a = read("decode_program_ms", serial)
    b = read("decode_program_ms", run_ahead)
    assert b == pytest.approx(a, rel=0.02)


def test_busy_time_under_a_calls_spans_is_another_programs_under_run_ahead(
        run_ahead):
    """With round n + 1 launched before round n is read, the device's busy
    time under a call's ``launch`` + ``fetch`` is part of two programs,
    neither its own: no reader lays device time under host spans."""
    own = read("decode_program_ms", run_ahead)
    assert abs(_busy_under_the_calls_spans(run_ahead) - own) > 0.1 * own
    recs = hetu_launches.launches(run_ahead, "serve.decode")
    assert [r.fetch is not None for r in recs] == [True] * 6
    # the fetch that waits for a launch opens after the NEXT launch closed
    for r, nxt in zip(recs, recs[1:]):
        assert r.fetch[0] >= nxt.l1


# ---------------------------------------------------- the roofline's share

def _least_s(active: int, cached: int) -> float:
    """gpt2-small's decode round by hand (``test_reduce.py``): every matmul
    weight and the live cache read once in bfloat16, or its operations."""
    bytes_ = 2 * (123_651_840 + 2 * 12 * 768 * cached)
    flops = 2 * 123_651_840 * active + 4 * 12 * 768 * cached
    return max(bytes_ / 819e9, flops / 197e12)


def test_decode_roofline_divides_by_the_rounds_own_module_runs(serial):
    runs = _raw(SERIAL)["jit_hetu_serve_decode"]
    assert len(runs) == 6
    first = hetu_launches.launches(serial, "serve.decode")[0]
    assert first.program_ns == runs[0][1] - runs[0][0]
    assert _least_s(8, 1000) == pytest.approx(284_167_680 / 819e9)
    least = sum(_least_s(a, c) for a, c in zip(*ROUNDS.values()))
    on_device = sum(b - a for a, b, _ in runs) / 1e9
    assert read("decode_roofline", serial) == pytest.approx(
        100.0 * least / on_device, rel=1e-9)
    # one round alone: its least time over its own run
    one = ctx_of(SERIAL, {k: v[:1] for k, v in ROUNDS.items()})
    one.trace.window = (first.l0 - 1.0, first.fetch[1] + 1.0)
    assert read("decode_roofline", one) == pytest.approx(
        100.0 * _least_s(8, 1000) / (first.program_ns / 1e9), rel=1e-9)


def test_decode_roofline_reads_the_same_under_run_ahead(serial, run_ahead):
    """The host's spans lie elsewhere, the programs' runs are the same."""
    a = read("decode_roofline", serial)
    b = read("decode_roofline", run_ahead)
    assert b == pytest.approx(a, rel=0.02)


def test_decode_roofline_gives_none_where_it_cannot_pair():
    short = {k: v[:5] for k, v in ROUNDS.items()}
    assert read("decode_roofline", ctx_of(SERIAL, short)) is None
    assert read("decode_roofline", ctx_of(SERIAL, {})) is None
    assert read("decode_roofline", ctx_of(RECORDED)) is None     # no seq
    assert read("decode_roofline", ctx_of(SERIAL, peaks=None)) is None
    assert spec.layer_metric_file("decode_roofline") == {
        "reader": "decode_roofline"}


def test_runtime_part_leaves_out_the_queued_time(serial, run_ahead):
    """From the second round on a program is handed over while its
    predecessor runs: Execute to =>Done then holds the predecessor's rest,
    which the runtime part does not count."""
    recs = hetu_launches.launches(run_ahead, "serve.decode")
    queued = [r for r in recs if r.done_prev is not None
              and r.done_prev > r.x]
    assert len(queued) >= 4
    naive = sum(r.done - r.x - r.program_ns for r in queued) / len(queued)
    counted = sum(r.runtime_ns for r in queued) / len(queued)
    waited = sum(r.done_prev - r.x for r in queued) / len(queued)
    assert waited > 1e6                        # over a millisecond queued
    assert naive == pytest.approx(counted + waited)
    # a queued program starts when its predecessor ends (the module runs lie
    # a microsecond apart), so what is left is the jitter of two completion
    # reports: nothing like the 0.8 ms a program pays when launched alone
    serial_runtime = read("decode_runtime_ms", serial) * 1e6
    assert serial_runtime > 0.5e6
    assert abs(counted) < 0.2 * serial_runtime < 0.2 * waited
    assert all(abs(r.runtime_ns) < 0.3e6 for r in queued)
    alone = next(r for r in recs if r not in queued)
    assert alone.runtime_ns > 0.5e6


# ------------------------------------------------------------ train steps

def test_train_program_ms_is_the_module_runs_mean_whatever_the_host_did():
    """Step n + 1 is dispatched before the host waits for step n: every
    step but the first is handed over while its predecessor runs, and its
    device time is still its own run's."""
    ctx = ctx_of(TRAIN)
    recs = hetu_launches.launches(ctx, "train.step.train")
    assert [r.ids["step"] for r in recs] == [1, 2, 3, 4, 5, 6]
    runs = _raw(TRAIN)["jit__train_step"]
    assert len(runs) == 6
    by_hand = sum(b - a for a, b, _ in runs) / 6e6
    assert read("train_program_ms", ctx) == pytest.approx(by_hand, rel=1e-9)
    assert all(r.fetch is None and r.readback_ns is None for r in recs)
    queued = [r for r in recs if r.done_prev is not None
              and r.done_prev > r.x]
    assert len(queued) >= 4
    for r in queued:
        assert abs(r.runtime_ns) < 0.3e6 < r.done - r.x - r.program_ns
    # the device never waited for the host: the runs follow each other
    starts = [a for a, _, _ in runs]
    ends = [b for _, b, _ in runs]
    assert max(a - b for a, b in zip(starts[1:], ends)) < 0.1 * by_hand * 1e6


# ------------------------------------------------- what cannot be paired

@pytest.mark.parametrize("stem", sorted(NEW))
def test_a_trace_without_launch_ids_gives_none(stem):
    """The trace PR 25 recorded: the same spans, no ``seq``."""
    assert read(stem, ctx_of(RECORDED)) is None
    assert hetu_launches.pair(RECORDED) is None
    assert "seq" in hetu_launches.pair_scan(
        hetu_launches.scan_file(RECORDED))[1]


@pytest.fixture(scope="module")
def scanned():
    return hetu_launches.scan_file(SERIAL)


def test_the_recorded_trace_pairs(scanned):
    records, why = hetu_launches.pair_scan(scanned)
    assert why == "" and len(records) == 8
    assert [r.ids["seq"] for r in records] == list(
        range(records[0].ids["seq"], records[0].ids["seq"] + 8))


@pytest.mark.parametrize("which", [0, 3, -1])
def test_one_done_removed_gives_none(scanned, which):
    scan = copy.deepcopy(scanned)
    del scan["dones"][which]
    records, why = hetu_launches.pair_scan(scan)
    assert records is None and "counts disagree" in why


def test_shuffled_run_ids_give_none(scanned):
    scan = copy.deepcopy(scanned)
    runs = scan["modules"][0]
    runs[2], runs[3] = (runs[2][:3] + (runs[3][3],),
                        runs[3][:3] + (runs[2][3],))
    records, why = hetu_launches.pair_scan(scan)
    assert records is None and "run_id" in why


def test_another_programs_name_gives_none(scanned):
    scan = copy.deepcopy(scanned)
    scan["modules"][0] = [(a, d, name.replace("hetu_serve_decode", "fn"), r)
                          for a, d, name, r in scan["modules"][0]]
    records, why = hetu_launches.pair_scan(scan)
    assert records is None and "jit_fn" in why


def test_a_renamed_runtime_event_gives_none(scanned):
    scan = copy.deepcopy(scanned)
    scan["executes"] = []           # libtpu calls it something else now
    assert hetu_launches.pair_scan(scan)[0] is None
    scan = copy.deepcopy(scanned)
    scan["launches"] = [(a, b, k, {i: v for i, v in ids.items()
                                   if i != "seq"})
                        for a, b, k, ids in scan["launches"]]
    assert hetu_launches.pair_scan(scan)[0] is None


def test_an_execute_outside_its_launch_span_gives_none(scanned):
    scan = copy.deepcopy(scanned)
    a, b, kind, ids = scan["launches"][4]
    scan["launches"][4] = (a, scan["executes"][4] - 1.0, kind, ids)
    records, why = hetu_launches.pair_scan(scan)
    assert records is None and "no Execute inside" in why


def _four_chips(steps: int = 3) -> dict:
    """A scan as a four-chip host gives it (the shape of
    ``gpt2-large.train-dp2tp2``'s trace, PR 36): one launch span a step with
    four Executes inside it, a module run a chip with run_ids of the chip's
    own, four completion threads reporting at once."""
    scan = {"launches": [], "fetches": [], "executes": [], "dones": [],
            "transfers": [], "modules": {c: [] for c in range(4)}}
    for i in range(steps):
        t = i * 470e6
        scan["launches"].append((t, t + 1.9e6, "train.step.train",
                                 {"step": i + 3}))
        scan["executes"] += [t + 0.76e6 + c * 15e3 for c in range(4)]
        for c in range(4):
            run_id = (95 if c == 0 else 20) + i
            scan["modules"][c].append(
                (t - 1e6 + c * 3e3, 467.8e6 + c * 1e3,
                 "jit__train_step(6040)", run_id))
            # the chips report in any order, their callbacks overlapping
            scan["dones"].append((t + 470.4e6 + ((c + i) % 4) * 40e3,
                                  run_id, c, f"/host:CPU/{9 + c}/{i}"))
    scan["dones"].sort()
    return scan


def test_four_chips_pair_by_the_first_chips_own_runs():
    records, why = hetu_launches.pair_scan(_four_chips())
    assert why == ""
    assert [r.ids["step"] for r in records] == [3, 4, 5]
    assert [r.program_ns for r in records] == [467.8e6] * 3
    assert [r.x for r in records] == [i * 470e6 + 0.76e6 for i in range(3)]
    mine = [d for d, _, c, _ in _four_chips()["dones"] if c == 0]
    assert [r.done for r in records] == mine


def test_four_chips_and_a_program_of_one_chip_give_none():
    scan = _four_chips()
    scan["executes"].insert(4, 300e6)      # a program the others did not run
    records, why = hetu_launches.pair_scan(scan)
    assert records is None and "counts disagree" in why
    scan = _four_chips()
    scan["dones"] = [(d, r, None, k) for d, r, _, k in scan["dones"]]
    records, why = hetu_launches.pair_scan(scan)
    assert records is None and "device_ordinal" in why


def test_no_trace_gives_none():
    ctx = SimpleNamespace(trace=None, run=SimpleNamespace(trace_path=None))
    assert hetu_launches.launches(ctx, "serve.decode") is None


# ------------------------------------------------------------ the entries

@pytest.mark.parametrize("stem", sorted(NEW))
def test_new_entries_resolve_to_the_one_reader(stem):
    """One entry a quantity (PR 53): the stem, listing the serving cells."""
    kind, part, source = NEW[stem]
    man = spec.manifest()
    (entry,) = [m for m in man["per_layer"]
                if m["name"].split(".")[0] == stem]
    assert entry["name"] == stem
    assert entry["source"] == source and entry["better"] == "lower"
    assert entry["moves"] == "serve_tokens_per_s"
    assert set(entry["workloads"]) == {
        m["name"] for m in man["workloads"]
        if "serve_tokens_per_s" in {
            e["name"] for e in spec.metrics_of(man["end_to_end"],
                                               m["name"])}}
    assert spec.layer_metric_file(stem) == {
        "reader": "hetu_launch_ms",
        "params": {"kind": kind, "part": part}}


def test_train_program_ms_is_reported_by_the_train_cells():
    man = spec.manifest()
    entry = next(m for m in man["per_layer"]
                 if m["name"] == "train_program_ms")
    assert entry["moves"] == "train_tokens_per_s"
    assert set(entry["workloads"]) == {
        m["name"] for m in man["workloads"]
        if "train_tokens_per_s" in {
            e["name"] for e in spec.metrics_of(man["end_to_end"],
                                               m["name"])}}
    assert spec.layer_metric_file("train_program_ms")["params"] == {
        "kind": "train.step.train", "part": "program"}


def test_the_clock_shift_is_not_used():
    import inspect

    src = inspect.getsource(hetu_launches)
    assert "clock_shift_ns" not in src
    assert "device_clock_shift(" not in src
