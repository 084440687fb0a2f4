"""Every program launch of a traced stretch, followed from the host to the
device and back: one record a launch, built from the program's own spans
(``hetu:serve.decode.launch`` / ``.fetch``, ``hetu:serve.prefill_chunk.*``,
``hetu:train.step.<name>``) and their ids (``seq``, ``step``), the runtime's
own host events and the device's ``XLA Modules`` line.

What is read, on a TPU v5e (looked at by hand in
``benchmarks/tests/data/hetu_v5e.xplane.pb``, PR 36): the jitted call reaches
``tpu::System::Execute`` on the calling thread, inside the launch span; the
device runs the program as one event of its ``XLA Modules`` line, named after
the jitted function (``jit_hetu_serve_decode(<fingerprint>)``) and carrying a
``run_id``; the runtime's completion thread then opens ``CompleteCallbacks``
with the SAME ``run_id`` and the chip's ``device_ordinal``, and inside it
``tpu::System::Execute=>Done``; the transfers a fetch asks for end in
``tpu::System::TransferFromDevice=>IssueEvent=>Done`` events with their
``size``.  From these a launch's time splits into four parts, none of which
needs the two clocks laid over each other (``reduce.device_clock_shift`` is
not used): three are differences on the host's clock and one is a duration
on the device's.

    issue     X - L0                    launch span opens -> Execute
    program   the module event's duration (the device's clock)
    runtime   (D - max(X, D_prev)) - program
    readback  F1 - D                    =>Done -> the fetch span closes

``D_prev`` is the ``=>Done`` of the program that ran before this one on the
chip: a program queued behind another cannot start before that one ends, so
time spent queued under run-ahead is not counted as the runtime's.

Pairing is by order, per chip, counted from the trace's END (when the trace
stops everything launched has run; at its start a program launched earlier
may still be running), and CHECKED: the ``run_id`` of a completion against
its module's, the module's name against the span's program, one ``Execute``
a chip inside every launch span, a fetch span for the launch's ``seq``.  A
trace that cannot be paired gives None, never a number: no ``seq`` / ``step``
ids (a program from before them), counts that disagree, a ``run_id`` or a
name that does not match, a runtime that renamed an event.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache
from typing import Optional

from benchmarks.harness import reduce
from benchmarks.readers import hetu_spans

# the runtime's events this file rests on (libtpu's names)
HOST_EXECUTE = reduce.HOST_LAUNCH                    # on the calling thread
HOST_DONE = "tpu::System::Execute=>Done"             # completion thread
HOST_COMPLETE = "CompleteCallbacks"                  # encloses HOST_DONE
HOST_TRANSFER_DONE = "tpu::System::TransferFromDevice=>IssueEvent=>Done"

# the span round an engine call (its ``.launch`` and ``.fetch`` carry
# ``seq``) or round a train step's dispatch (``step``; nothing fetches it)
# -> the name of the function that call jits
SERVE = {
    "serve.decode": "hetu_serve_decode",
    "serve.prefill_chunk": "hetu_serve_prefill_chunk",
}
TRAIN = {"train.step.train": "_train_step"}


@dataclass(frozen=True)
class Launch:
    kind: str                 # "serve.decode", "train.step.train", ...
    ids: dict                 # the launch span's ids
    l0: float                 # launch span, host ns
    l1: float
    x: float                  # tpu::System::Execute opens (first chip's)
    program_ns: float         # the module run's duration, device clock
    module: str               # its name
    done: float               # =>Done opens, host ns
    done_prev: Optional[float]
    fetch: Optional[tuple]    # (F0, F1) of the fetch span of the same seq
    transfers: int            # device-to-host transfers ended in the fetch
    transfer_bytes: int

    @property
    def issue_ns(self) -> float:
        return self.x - self.l0

    @property
    def runtime_ns(self) -> float:
        start = self.x if self.done_prev is None \
            else max(self.x, self.done_prev)
        return self.done - start - self.program_ns

    @property
    def readback_ns(self) -> Optional[float]:
        return None if self.fetch is None else self.fetch[1] - self.done

    def part_ns(self, part: str) -> Optional[float]:
        return getattr(self, part + "_ns")


PARTS = ("issue", "program", "runtime", "readback")


def scan_file(path: str) -> dict:
    """One pass over the file: the program's launch and fetch spans with
    their ids, the runtime's host events, and each chip's module runs.
    A ``=>Done`` is kept with the ``CompleteCallbacks`` that encloses it ON
    ITS OWN THREAD (several chips complete on threads of their own, at
    once): ``(opens, run_id, device_ordinal, callback)``, the last a key
    that Dones of one callback share, None where no callback encloses it."""
    from jax.profiler import ProfileData

    launch_names = {hetu_spans.PREFIX + k + ".launch": k for k in SERVE}
    launch_names.update({hetu_spans.PREFIX + k: k for k in TRAIN})
    fetch_names = {hetu_spans.PREFIX + k + ".fetch" for k in SERVE}
    out = {"launches": [], "fetches": [], "executes": [], "dones": [],
           "transfers": [], "modules": {}}
    for plane in ProfileData.from_file(path).planes:
        chip = reduce.DEVICE_PLANE.match(plane.name)
        for at, line in enumerate(plane.lines):
            if chip:
                if line.name == reduce.MODULES_LINE:
                    out["modules"][int(chip.group(1))] = sorted(
                        (float(e.start_ns), float(e.duration_ns), e.name,
                         dict(e.stats).get("run_id")) for e in line.events)
                continue
            dones, completes = [], []
            for e in line.events:
                name = e.name.split("#")[0]
                a = float(e.start_ns)
                if name == HOST_EXECUTE:
                    out["executes"].append(a)
                elif name == HOST_DONE:
                    dones.append(a)
                elif name == HOST_COMPLETE:
                    st = dict(e.stats)
                    completes.append(
                        (a, a + float(e.duration_ns), st.get("run_id"),
                         st.get("device_ordinal")))
                elif name == HOST_TRANSFER_DONE:
                    out["transfers"].append(
                        (a + float(e.duration_ns),
                         int(dict(e.stats).get("size", 0))))
                elif name in launch_names:
                    out["launches"].append(
                        (a, a + float(e.duration_ns), launch_names[name],
                         dict(e.stats)))
                elif name in fetch_names:
                    out["fetches"].append(
                        (a, a + float(e.duration_ns), dict(e.stats).get("seq")))
            completes.sort()
            j = 0
            for d in sorted(dones):
                while j < len(completes) and completes[j][1] < d:
                    j += 1
                if j < len(completes) and completes[j][0] <= d:
                    out["dones"].append(
                        (d, *completes[j][2:], f"{plane.name}/{at}/{j}"))
                else:
                    out["dones"].append((d, None, None, None))
    for key in ("launches", "fetches", "executes", "dones", "transfers"):
        out[key].sort(key=lambda v: v[0] if isinstance(v, tuple) else v)
    return out


def _completions(scan: dict, chip: int, chips: int) -> Optional[list]:
    """[(=>Done opens, run_id or None)] of ``chip``, in order.  One
    ``CompleteCallbacks`` may report several programs that ended close
    together; it carries the ``run_id`` of the LAST of them (seen in the
    fastest serving cell's traces, PR 36), so the others have none to check.
    Without the callbacks' ``device_ordinal`` only a trace of one chip can
    be read."""
    out, last = [], None
    for d, run_id, ordinal, callback in scan["dones"]:
        if ordinal is None:
            if chips > 1:
                return None
            ordinal = chip
        if ordinal != chip:
            continue
        if callback is not None and callback == last:
            out[-1] = (out[-1][0], None)        # not the callback's last
        out.append((d, run_id))
        last = callback
    return out


IN_FLIGHT_AT_START = 2    # programs a trace may open on, at most


def pair_scan(scan: dict) -> tuple:
    """(records or None, why not): every launch the program's spans name,
    in order, as :class:`Launch` records."""
    launches, modules = scan["launches"], scan["modules"]
    if not launches:
        return None, "no launch span of the program in the trace"
    if not modules or not any(modules.values()):
        return None, "no module run on a device in the trace"
    if any(("step" if kind in TRAIN else "seq") not in ids
           for _, _, kind, ids in launches):
        return None, "launch spans without seq / step ids"
    chips, chip = len(modules), min(modules)
    runs = modules[chip]
    done = _completions(scan, chip, chips)
    if done is None:
        return None, "several chips and no device_ordinal on completions"
    executes = scan["executes"]
    # from the end: program i of the chip is handed over by `chips` Execute
    # events (one a chip), run as one module event, reported by one =>Done;
    # a program the trace opened on has lost its Execute, perhaps its run
    n, rest = divmod(len(executes), chips)
    if rest or not n or not (
            n <= len(runs) <= len(done) <= n + IN_FLIGHT_AT_START):
        return None, (f"counts disagree: {len(executes)} Execute on "
                      f"{chips} chip(s), {len(runs)} module runs, "
                      f"{len(done)} =>Done")
    runs, done = runs[-n:], done[-n:]
    for (_, _, _, run_id), (_, completed) in zip(runs, done):
        if run_id is not None and completed is not None \
                and run_id != completed:
            return None, (f"run_id {completed} completed where run_id "
                          f"{run_id} ran")
    fetch_of = {}
    for a, b, seq in scan["fetches"]:
        if seq is None or seq in fetch_of:
            return None, f"fetch spans without a seq of their own ({seq})"
        fetch_of[seq] = (a, b)
    out, i = [], 0
    for at, (l0, l1, kind, ids) in enumerate(launches):
        # one chip: the Execute lies inside the launch span; several: the
        # runtime may hand the chips their program from its own threads
        until = l1 if chips == 1 else (
            launches[at + 1][0] if at + 1 < len(launches) else float("inf"))
        while i < n and executes[i * chips] < l0:
            i += 1
        if i == n or executes[(i + 1) * chips - 1] > until:
            return None, f"no Execute inside the launch span at {l0:.0f}"
        _, duration, module, _ = runs[i]
        program = TRAIN.get(kind) or SERVE[kind]
        if not module.startswith("jit_" + program + "("):
            return None, f"{kind} launched {program}, {module} ran"
        fetch, moved = None, []
        if kind in SERVE:
            fetch = fetch_of.get(ids["seq"])
        if fetch is not None:
            if fetch[1] < done[i][0]:
                return None, f"fetch of seq {ids['seq']} closed too early"
            moved = [size for end, size in scan["transfers"]
                     if fetch[0] <= end <= fetch[1]]
        out.append(Launch(
            kind=kind, ids=ids, l0=l0, l1=l1, x=executes[i * chips],
            program_ns=duration, module=module, done=done[i][0],
            done_prev=done[i - 1][0] if i else None, fetch=fetch,
            transfers=len(moved), transfer_bytes=sum(moved)))
        i += 1
    return tuple(out), ""


@lru_cache(maxsize=2)
def pair(path: str) -> Optional[tuple]:
    """:func:`pair_scan` of the file; None where it cannot be paired."""
    return pair_scan(scan_file(path))[0]


def launches(ctx, kind: str) -> Optional[list]:
    """The launches of ``kind`` whose launch span lies inside the traced
    window (the trace summary's, or the benchmark's mark in the file); None
    where there is no trace, no pairing or no such launch."""
    path = getattr(ctx.run, "trace_path", None)
    if not path:
        return None
    window = ctx.trace.window if ctx.trace is not None \
        else hetu_spans._load(path)[1]
    every = pair(path)
    if every is None or window is None:
        return None
    return inside(every, kind, window) or None


def inside(records, kind: str, window: tuple) -> list:
    """Those of ``records`` of ``kind`` whose launch span lies in
    ``window``."""
    return [rec for rec in records if rec.kind == kind
            and rec.l0 >= window[0] and rec.l1 <= window[1]]


def mean_ms(records, part: str) -> Optional[float]:
    """Mean of one part over the records that have it, in milliseconds."""
    values = [v for v in (rec.part_ns(part) for rec in records)
              if v is not None]
    return sum(values) / len(values) / 1e6 if values else None
