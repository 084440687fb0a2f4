"""From a profiler trace (``.xplane.pb``) to numbers.

Everything here works on a plain structure, ``[Plane(name, [Line(name,
[Event(name, start_ns, end_ns)])])]``, which ``load`` makes from the file
with ``jax.profiler.ProfileData`` and tests can make by hand.

What the trace looks like on a TPU v5e (looked at by hand, PR 23): one plane
``/device:TPU:<n>`` per chip, whose line ``XLA Ops`` holds one event per
executed HLO operation, in order, on the chip's one core; a ``while`` (every
scan over layers) is itself an event that CONTAINS its body's events, so
durations nest and a plain sum counts the body twice.  Busy time is
therefore the measure of the union of the events, and an operation's own
time is its duration less its children's.  Host threads are lines of the
plane ``/host:CPU``; the benchmark's spans are the events there whose names
start with ``bench:``, on the same clock.
"""

from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import NamedTuple

from benchmarks.harness.spans import PREFIX

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_LAUNCH = "tpu::System::Execute"
MAX_SHIFT_NS = 20e6
HLO_TEXT = re.compile(r"^%(\S+) = \(?([a-z0-9]+\[[0-9,]*\])?")
WINDOW_SPAN = "trace_window"
SHORT_GAP_NS = 5_000
SHORT_GAPS = "between_operations__each_under_5_us_"
NO_SPAN = "no_benchmark_span"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast")


class Event(NamedTuple):
    name: str
    start: float   # ns
    end: float     # ns


@dataclass
class Line:
    name: str
    events: list


@dataclass
class Plane:
    name: str
    lines: list


def load(path: str) -> list:
    from jax.profiler import ProfileData

    planes = []
    for p in ProfileData.from_file(path).planes:
        lines = []
        for ln in p.lines:
            lines.append(Line(ln.name, [
                Event(e.name, float(e.start_ns),
                      float(e.start_ns) + float(e.duration_ns))
                for e in ln.events]))
        planes.append(Plane(p.name, lines))
    return planes


# ------------------------------------------------------------- intervals

def union(intervals) -> list:
    """Disjoint, sorted cover of ``intervals`` [(start, end)]."""
    out = []
    for a, b in sorted((a, b) for a, b in intervals if b > a):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def measure(disjoint) -> float:
    return sum(b - a for a, b in disjoint)


def clip(disjoint, lo: float, hi: float) -> list:
    return [(max(a, lo), min(b, hi)) for a, b in disjoint
            if min(b, hi) > max(a, lo)]


def subtract(disjoint, holes) -> list:
    """Parts of ``disjoint`` not covered by ``holes`` (both disjoint and
    sorted)."""
    out, j = [], 0
    for a, b in disjoint:
        cur = a
        while j < len(holes) and holes[j][1] <= cur:
            j += 1
        k = j
        while k < len(holes) and holes[k][0] < b:
            if holes[k][0] > cur:
                out.append((cur, holes[k][0]))
            cur = max(cur, holes[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def intersect(disjoint, other) -> list:
    return subtract(disjoint, subtract(disjoint, other))


def complement(disjoint, lo: float, hi: float) -> list:
    return subtract([(lo, hi)], clip(disjoint, lo, hi))


# ---------------------------------------------------------------- events

def self_times(events) -> list:
    """[(event, own_ns, is_leaf)] for the events of one in-order line:
    an event's own time is its duration less that of the events nested
    directly inside it."""
    order = sorted(events, key=lambda e: (e.start, -e.end))
    own = [e.end - e.start for e in order]
    leaf = [True] * len(order)
    stack = []
    for i, e in enumerate(order):
        while stack and order[stack[-1]].end <= e.start:
            stack.pop()
        if stack:
            own[stack[-1]] -= e.end - e.start
            leaf[stack[-1]] = False
        stack.append(i)
    return [(e, max(o, 0.0), lf) for e, o, lf in zip(order, own, leaf)]


def device_ops(planes) -> dict:
    """{chip index: events of its ``XLA Ops`` line}."""
    out = {}
    for p in planes:
        m = DEVICE_PLANE.match(p.name)
        if not m:
            continue
        for ln in p.lines:
            if ln.name == OPS_LINE:
                out[int(m.group(1))] = list(ln.events)
    return out


def host_spans(planes) -> dict:
    """{span name: [(start, end)]} of the benchmark's annotations."""
    out = defaultdict(list)
    for p in planes:
        if DEVICE_PLANE.match(p.name):
            continue
        for ln in p.lines:
            for e in ln.events:
                if e.name.startswith(PREFIX):
                    out[e.name[len(PREFIX):]].append((e.start, e.end))
    return dict(out)


def innermost_segments(spans: dict) -> list:
    """[(start, end, name)], disjoint and sorted: every instant covered by
    a benchmark span, labelled with the innermost one (the spans of one
    thread nest)."""
    flat = sorted(((a, b, name) for name, iv in spans.items()
                   if name != WINDOW_SPAN for a, b in iv),
                  key=lambda x: (x[0], -x[1]))
    out, stack = [], []          # stack of [start_of_own_part, end, name]

    def close(until):
        while stack and stack[-1][1] <= until:
            own, end, name = stack.pop()
            if end > own:
                out.append((own, end, name))
            if stack:
                stack[-1][0] = max(stack[-1][0], end)

    for a, b, name in flat:
        close(a)
        if stack and a > stack[-1][0]:
            out.append((stack[-1][0], a, stack[-1][2]))
        stack.append([a, b, name])
    close(float("inf"))
    return sorted(out)


def attribute_gaps(gaps, spans: dict) -> dict:
    """Seconds of idle time by the innermost benchmark span covering them
    (a gap is split where it crosses from one span into another); gaps
    under 5 us (the device's own turn-round between operations) are
    pooled."""
    segments = innermost_segments(spans)
    out = defaultdict(float)
    j = 0
    for a, b in sorted(gaps):
        if b - a < SHORT_GAP_NS:
            out[SHORT_GAPS] += (b - a) / 1e9
            continue
        while j < len(segments) and segments[j][1] <= a:
            j += 1
        covered, k = 0.0, j
        while k < len(segments) and segments[k][0] < b:
            part = min(b, segments[k][1]) - max(a, segments[k][0])
            if part > 0:
                out[segments[k][2]] += part / 1e9
                covered += part
            k += 1
        if b - a - covered > 0:
            out[NO_SPAN] += (b - a - covered) / 1e9
    return dict(out)


# --------------------------------------------------------------- summary

@dataclass
class ChipTrace:
    busy: list            # disjoint busy intervals inside the window
    ops: list             # [(Event, own_ns, is_leaf)] inside the window


@dataclass
class TraceSummary:
    window: tuple                      # (start_ns, end_ns)
    chips: dict                        # chip index -> ChipTrace
    spans: dict = field(default_factory=dict)
    clock_shift_ns: float = 0.0        # added to every device time

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds an operation ran, averaged over the chips traced."""
        if not self.chips:
            return 0.0
        return sum(measure(c.busy) for c in self.chips.values()) \
            / len(self.chips) / 1e9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def first_chip(self) -> ChipTrace:
        return self.chips[min(self.chips)]

    def op_seconds(self, chip: ChipTrace = None) -> dict:
        """Own time by operation name on one chip (the first)."""
        out = defaultdict(float)
        for e, own, _ in (chip or self.first_chip()).ops:
            out[short_name(e.name)] += own / 1e9
        return dict(out)

    def kernel_events(self, pattern: str, chip: ChipTrace = None) -> list:
        rx = re.compile(pattern)
        return [(e, own) for e, own, _ in (chip or self.first_chip()).ops
                if rx.search(e.name)]

    def exposed_collective_s(self, chip: ChipTrace = None) -> float:
        """Seconds in which a collective operation ran on the chip and no
        other operation did."""
        chip = chip or self.first_chip()
        coll = union((e.start, e.end) for e, _, lf in chip.ops
                     if lf and COLLECTIVE.search(e.name))
        comp = union((e.start, e.end) for e, _, lf in chip.ops
                     if lf and not COLLECTIVE.search(e.name))
        return measure(subtract(coll, comp)) / 1e9

    def idle_gaps(self) -> dict:
        chip = self.first_chip()
        return attribute_gaps(complement(chip.busy, *self.window),
                              self.spans)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])
        gaps = sorted(self.idle_gaps().items(), key=lambda kv: -kv[1])
        return {"device_ops": [[_safe(n), s] for n, s in ops[:top]],
                "idle_gaps": [[_safe(n), s] for n, s in gaps[:top]]}


def short_name(hlo: str) -> str:
    """``copy.41 bf16[36,385,16,20,64]`` from the event's name, which on a
    TPU is the whole HLO instruction (``%copy.41 = bf16[36,385,...]{...}
    copy(...)``): the instruction's name and its (first) result shape."""
    m = HLO_TEXT.match(hlo)
    if not m:
        return hlo
    return m.group(1) + (" " + m.group(2) if m.group(2) else "")


def device_clock_shift(planes) -> float:
    """Nanoseconds to add to device times so that they sit on the host's
    clock.  The profiler puts both in one file but not on one clock: on the
    v5e every program appeared to START 1-1.6 ms BEFORE the host call that
    launched it (PR 23's traces; constant within a trace).  A program cannot
    start before its launch, so the shift is the largest lead of a device
    program (``XLA Modules``) over its launch (``tpu::System::Execute`` on
    the host).  The two lists are paired from their ends: when the trace
    stops everything launched has run, while at its start programs launched
    earlier may still be running.  The host launches once per chip, so on
    several chips the programs of all of them are paired; where the counts
    fit one chip better (one launch for all), the first chip's are.  0 where
    the trace has no such events, or where the pairing gives more than
    MAX_SHIFT_NS, which no clock skew seen explains and a mispairing
    does."""
    launches, modules = [], {}
    for p in planes:
        m = DEVICE_PLANE.match(p.name)
        for ln in p.lines:
            if m and ln.name == MODULES_LINE:
                modules[int(m.group(1))] = sorted(e.start for e in ln.events)
            elif not m:
                launches += [e.start for e in ln.events
                             if e.name == HOST_LAUNCH]
    if not launches or not modules:
        return 0.0
    launches.sort()
    every = sorted(t for starts in modules.values() for t in starts)
    first = modules[min(modules)]
    starts = min((every, first),
                 key=lambda xs: abs(len(xs) - len(launches)))
    n = min(len(launches), len(starts))
    shift = max(h - d for h, d in zip(launches[-n:], starts[-n:]))
    return shift if 0.0 <= shift <= MAX_SHIFT_NS else 0.0


def _safe(name: str) -> str:
    return re.sub(r"[^A-Za-z0-9_.\-]", "_", name)[:120]


def summarize(planes) -> TraceSummary:
    """Reduce a trace to the window the benchmark marked
    (``bench:trace_window``; the extent of the device events if there is no
    mark)."""
    spans = host_spans(planes)
    shift = device_clock_shift(planes)
    per_chip = {idx: [Event(e.name, e.start + shift, e.end + shift)
                      for e in events]
                for idx, events in device_ops(planes).items()}
    if not per_chip:
        raise ValueError("the trace holds no /device:TPU:<n> plane with an "
                         f"{OPS_LINE!r} line: nothing ran on a device")
    marks = spans.get(WINDOW_SPAN)
    if marks:
        lo, hi = min(a for a, _ in marks), max(b for _, b in marks)
    else:
        every = [e for ev in per_chip.values() for e in ev]
        lo, hi = min(e.start for e in every), max(e.end for e in every)
    chips = {}
    for idx, events in per_chip.items():
        inside = [e for e in events if e.end > lo and e.start < hi]
        chips[idx] = ChipTrace(
            busy=clip(union((e.start, e.end) for e in inside), lo, hi),
            ops=self_times(inside))
    return TraceSummary(window=(lo, hi), chips=chips, spans=spans,
                        clock_shift_ns=shift)
