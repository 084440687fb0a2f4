"""The reduction from a trace to numbers, on a trace recorded on a TPU v5e
(``data/tiny_v5e.xplane.pb``, by ``tools/record_tiny_trace.py``: four calls
of a three-layer scanned program, each inside ``bench:inner.call``, each
followed by 2 ms of host sleep inside ``bench:inner.host``) and on traces
made by hand; and the operation and byte functions (the kernels' in
``harness/flops.py``, GPT-2's in its adapter) against hand-worked values for
both configurations."""

from pathlib import Path

import pytest

from benchmarks.arch import gpt2
from benchmarks.harness import flops, reduce, spec
from benchmarks.harness.reduce import Event, Line, Plane

DATA = Path(__file__).parent / "data" / "tiny_v5e.xplane.pb"


@pytest.fixture(scope="module")
def tiny():
    return reduce.summarize(reduce.load(str(DATA)))


# ------------------------------------------------------ the recorded trace

def test_recorded_trace_window_and_busy(tiny):
    assert sorted(tiny.chips) == [0]
    assert 0.010 < tiny.window_s < 0.020          # the marked window
    assert 10e-6 < tiny.busy_s < 20e-6            # four programs of ~3.5 us
    assert tiny.idle_share > 0.99
    # busy is a union: the while contains its body, a plain sum counts twice
    plain = sum(e.end - e.start for e, _, _ in tiny.first_chip().ops) / 1e9
    assert plain > 1.5 * tiny.busy_s


def test_recorded_trace_clock_shift(tiny):
    """Device times ran 1.6 ms behind the host's in this trace; shifted,
    each program lies inside the span that launched it."""
    assert 1.5e6 < tiny.clock_shift_ns < 1.75e6
    busy = tiny.first_chip().busy
    calls = [reduce.measure(reduce.intersect([iv], busy)) / 1e9
             for iv in sorted(tiny.spans["inner.call"])]
    assert len(calls) == 4 and all(3e-6 < s < 4.5e-6 for s in calls)
    assert reduce.intersect(reduce.union(tiny.spans["inner.host"]),
                            busy) == []
    assert abs(sum(calls) - tiny.busy_s) < 1e-9


def test_recorded_trace_operations(tiny):
    ops = tiny.op_seconds()
    assert "fusion.13 bf16[256,256]" in ops          # the layer's matmul
    assert max(ops, key=ops.get) == "fusion.13 bf16[256,256]"
    assert len(tiny.kernel_events(r"^%fusion\.13 ")) == 12   # 3 layers x 4
    whiles = [(own, leaf) for e, own, leaf in tiny.first_chip().ops
              if e.name.startswith("%while")]
    assert len(whiles) == 4 and not any(leaf for _, leaf in whiles)
    assert all(own < 0.3e3 for own, _ in whiles)     # own time: loop glue
    assert abs(sum(ops.values()) - tiny.busy_s) < 1e-7
    assert tiny.exposed_collective_s() == 0.0


def test_recorded_trace_gap_attribution(tiny):
    gaps = tiny.idle_gaps()
    assert 0.008 < gaps["inner.host"] < 0.010        # 4 sleeps of ~2.2 ms
    assert 0.002 < gaps["inner.call"] < 0.005        # launch + wait, x4
    assert abs(sum(gaps.values()) - (tiny.window_s - tiny.busy_s)) < 1e-9
    b = tiny.breakdown()
    assert b["device_ops"][0][0].startswith("fusion.13")
    assert b["idle_gaps"][0][0] == "inner.host"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


# --------------------------------------------------------- traces by hand

def test_interval_arithmetic():
    u = reduce.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)])
    assert u == [(0, 3), (5, 8)]
    assert reduce.measure(u) == 6
    assert reduce.subtract(u, [(1, 2), (6, 10)]) == [(0, 1), (2, 3), (5, 6)]
    assert reduce.intersect(u, [(2, 6)]) == [(2, 3), (5, 6)]
    assert reduce.complement(u, -1, 10) == [(-1, 0), (3, 5), (8, 10)]
    assert reduce.clip(u, 1, 6) == [(1, 3), (5, 6)]


def test_self_times_nest():
    ev = [Event("%while = x", 0, 100), Event("%a = x", 10, 30),
          Event("%b = x", 30, 90), Event("%c = x", 40, 50),
          Event("%d = x", 120, 130)]
    got = {e.name: (own, leaf) for e, own, leaf in reduce.self_times(ev)}
    assert got == {"%while = x": (20, False), "%a = x": (20, True),
                   "%b = x": (50, False), "%c = x": (10, True),
                   "%d = x": (10, True)}


def _planes(ops, host=(), modules=()):
    return [Plane("/device:TPU:0", [Line("XLA Modules", list(modules)),
                                    Line("XLA Ops", list(ops))]),
            Plane("/host:CPU", [Line("python3", list(host))])]


def test_exposed_collective_time():
    """An async pair costs its start and its wait; compute between them
    hides the rest.  A blocking all-reduce is exposed whole.  The scan's
    while is a container, not compute."""
    ops = [Event("%while.1 = f32[] while(...)", 0, 30),
           Event("%all-reduce-start.1 = f32[8] all-reduce-start(...)", 8, 9),
           Event("%fusion.2 = f32[8] fusion(...)", 9, 12),
           Event("%all-reduce-done.1 = f32[8] all-reduce-done(...)", 12, 15),
           Event("%all-reduce.5 = f32[8] all-reduce(...)", 20, 25),
           Event("%all-gather.7 = f32[8] all-gather(...)", 40, 42)]
    s = reduce.summarize(_planes(ops))
    assert s.exposed_collective_s() == pytest.approx(11e-9)
    assert s.busy_s == pytest.approx(32e-9)        # [0,30] and [40,42]
    assert s.window == (0, 42)


def test_clock_shift_pairs_launches_from_the_end():
    launch = [Event(reduce.HOST_LAUNCH, t, t + 5) for t in (150, 260)]
    mods = [Event("jit_f(1)", t, t + 20) for t in (10, 100, 200)]
    ops = [Event("%a = f32[] add()", t, t + 20) for t in (10, 100, 200)]
    planes = _planes(ops, host=launch, modules=mods)
    assert reduce.device_clock_shift(planes) == 60
    s = reduce.summarize(planes)
    assert s.clock_shift_ns == 60
    assert s.first_chip().busy == [(70, 90), (160, 180), (260, 280)]
    assert reduce.device_clock_shift(_planes(ops)) == 0.0


def test_gaps_split_across_spans_and_pool_short_ones():
    spans = {"outer": [(0, 100_000)], "in": [(10_000, 40_000)],
             reduce.WINDOW_SPAN: [(0, 200_000)]}
    gaps = [(5_000, 50_000), (60_000, 60_004), (150_000, 170_000)]
    got = reduce.attribute_gaps(gaps, spans)
    assert got == {"outer": pytest.approx(15_000e-9),
                   "in": pytest.approx(30_000e-9),
                   reduce.SHORT_GAPS: pytest.approx(4e-9),
                   reduce.NO_SPAN: pytest.approx(20_000e-9)}


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError, match="nothing ran on a device"):
        reduce.summarize([Plane("/host:CPU", [Line("python3", [])])])


def test_short_name():
    assert reduce.short_name(
        "%copy.41 = bf16[36,385,16,20,64]{4,3,2,1,0:T(8,128)(2,1)} "
        "copy(bf16[36,385,16,20,64]{...} %p)") \
        == "copy.41 bf16[36,385,16,20,64]"
    assert reduce.short_name("%t = (bf16[2,3]{1,0}, f32[2]{0}) custom-call(") \
        == "t bf16[2,3]"
    assert reduce.short_name("jit_step(123)") == "jit_step(123)"


# ----------------------------------------------- operations and bytes

@pytest.fixture(scope="module")
def configs():
    man = spec.manifest()
    out = {n: spec.config(man, n) for n in ("gpt2-small", "gpt2-large")}
    assert all(spec.adapter(c) is gpt2 for c in out.values())
    return out


def test_parameter_counts(configs):
    """Worked by hand from the published widths; the totals are the models'
    well-known sizes."""
    small, large = configs["gpt2-small"], configs["gpt2-large"]
    assert gpt2.block_params(small) == 7_087_872
    assert gpt2.total_params(small) == 124_439_808
    assert gpt2.matmul_params(small) == 85_054_464 + 50_257 * 768
    assert gpt2.block_params(large) == 19_677_440
    assert gpt2.total_params(large) == 774_030_080
    assert gpt2.matmul_params(large) == 708_387_840 + 50_257 * 1280


def test_train_flops_per_token(configs):
    assert gpt2.train_flops_per_token(configs["gpt2-small"], 1024) \
        == 6 * 123_651_840 + 6 * 12 * 768 * 1024 == 798_534_144
    assert gpt2.train_flops_per_token(configs["gpt2-large"], 1024) \
        == 6 * 772_716_800 + 6 * 36 * 1280 * 1024 == 4_919_416_320


def test_flash_and_decode_counts(configs):
    f = flops.flash_call_flops(64, 12, 1024, 64)
    assert f == {"fwd": 103_079_215_104.0, "bwd": 257_698_037_760.0}
    b = flops.flash_call_bytes(64, 12, 1024, 64)
    assert b == {"fwd": 402_653_184.0, "bwd": 805_306_368.0}
    assert gpt2.decode_step_bytes(configs["gpt2-small"], 1000) \
        == 2 * (123_651_840 + 2 * 12 * 768 * 1000) == 284_167_680
    assert gpt2.decode_step_flops(configs["gpt2-large"], 8, 2000) \
        == 2 * 772_716_800 * 8 + 4 * 36 * 1280 * 2000


def test_attention_call_shape_divides_batch_and_heads_over_the_mesh(configs):
    values = {"batch": 16, "seq": 1024, "mesh": {"dp": 2, "tp": 2}}
    assert gpt2.attention_call_shape(configs["gpt2-large"], values) \
        == (8, 10, 1024, 64)
    assert gpt2.attention_call_shape(
        configs["gpt2-small"], {"batch": 64, "seq": 1024, "mesh": {}}) \
        == (64, 12, 1024, 64)
