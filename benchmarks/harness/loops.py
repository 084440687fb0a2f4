"""The three loops a traffic file can name by ``kind``: ``train_steps``,
``backlog`` and ``open_loop_schedule``.  Each sets its cell up (weights, warm-up of the
shapes the traffic can reach), optionally traces a short stretch under the
cell's load, measures for ``--seconds`` with the profiler off, reads what
the chip holds, and only then compares the system with the reference (the
comparison's second copy of the weights and its own temporaries would
otherwise stand in the allocator's peaks as if the traffic held them).
It returns what the metrics are made from.  One process, one thread: the load comes from the loop that steps the
system (``scheduler.step`` holds the scheduler's lock for the whole step,
so a second thread could submit only between steps anyway)."""

from __future__ import annotations

import itertools
import shutil
import time
from contextlib import contextmanager
from dataclasses import dataclass, field

import numpy as np

from benchmarks.harness import build, check, device, reduce, schedule, spec
from benchmarks.harness.spans import Recorder

TRACE_DIR = spec.ROOT / ".bench_out" / "trace"
SETTLE_S = 1.0     # after the profiler stops, before the window opens
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclass
class Run:
    """What a loop hands back."""
    end_to_end: dict                     # metric name -> value
    values: dict                         # raw material for the readers
    attempted: int
    failed: int
    check: dict
    compiles_in_window: int
    setup_done: float                    # monotonic time the window opened
    memory: dict = field(default_factory=dict)   # MemoryProbe.fullest()
    trace_path: str = None
    extra: dict = field(default_factory=dict)


class CompileCounter:
    """Counts every program JAX compiles or loads in this process (``n``),
    and how many of them the persistent cache held or lacked."""

    def __init__(self):
        import jax
        self.n = self.cache_hits = self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_cache)

    def _on(self, name, *_args, **_kw):
        if name == COMPILE_EVENT:
            self.n += 1

    def _on_cache(self, name, *_args, **_kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


@contextmanager
def traced(rec: Recorder):
    """Profile the body, marked as ``bench:trace_window``.  The profiler's
    Python tracer is off: it slows the host's Python (a tenth of the
    fastest serving cell's rate inside the stretch, PR 36), and the stretch
    the host metrics are read in should run as the window runs.  The
    ``bench:`` and ``hetu:`` annotations are TraceMe events either way."""
    import jax

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    TRACE_DIR.mkdir(parents=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=options)
    rec.clear()    # what the readers lay over the trace starts here
    rec.annotate = True
    try:
        with rec.span(reduce.WINDOW_SPAN):
            yield
    finally:
        rec.annotate = False
        jax.profiler.stop_trace()


def trace_file() -> str:
    found = sorted(TRACE_DIR.glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"the profiler wrote no xplane under "
                                f"{TRACE_DIR}")
    return str(found[-1])


def setup_phases(rec: Recorder) -> dict:
    """Seconds spent in each ``setup.*`` span so far."""
    return {name: sum(b - a for a, b in iv)
            for name, iv in rec.spans.items() if name.startswith("setup.")}


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


# ------------------------------------------------------------ train_steps

def train_steps(ctx) -> Run:
    import jax

    cfg, tr, rec = ctx.config, ctx.traffic, ctx.rec
    batch, seq = int(tr["batch"]), int(tr["seq"])
    arch = spec.adapter(cfg)
    model = arch.make_model(cfg, "train")
    mesh, strategy = build.mesh_and_strategy(cfg, ctx.chips)
    variables = build.init_variables(model, ctx.seed, mesh=mesh,
                                     strategy=strategy)
    rng = np.random.default_rng(int(ctx.seed))
    low, high = arch.id_range(cfg)
    batches = [rng.integers(low, high, (batch, seq)).astype(np.int32)
               for _ in range(int(tr["distinct_batches"]))]

    ex = build.make_executor(model, cfg, mesh=mesh, strategy=strategy)
    state = ex.init_state(variables, rng_key=build.key_for(ctx.seed, 1))
    del variables

    feed = itertools.cycle(batches)

    def step(state):
        with rec.span("executor.run"):
            return ex.run("train", state, (next(feed),))

    def run_for(state, seconds: float):
        """Issue steps for ``seconds`` with a bounded run-ahead (the loss of
        step k-lookahead is fetched before step k+1 is issued, as a training
        loop that logs its loss does), then wait for the last.  Returns
        (state, steps, t0, t1, last loss)."""
        look = int(tr["lookahead_steps"])
        pending, steps = [], 0
        t0 = time.monotonic()
        while True:
            state, metrics = step(state)
            steps += 1
            pending.append(metrics["loss"])
            if len(pending) > look:
                with rec.span("wait_step"):
                    jax.block_until_ready(pending.pop(0))
            if time.monotonic() - t0 >= seconds:
                break
        with rec.span("wait_step"):
            jax.block_until_ready(state)
        return state, steps, t0, time.monotonic(), float(metrics["loss"])

    with rec.span("setup.warmup"):
        for _ in range(2):   # the first compiles, the second is steady
            state, metrics = step(state)
            loss0 = float(metrics["loss"])
    trace_path = None
    if ctx.trace:
        with traced(rec):
            state, *_ = run_for(state, float(tr["trace_s"]))
        trace_path = trace_file()
        state, *_ = run_for(state, SETTLE_S)

    phases = setup_phases(rec)
    rec.clear()
    probe = device.MemoryProbe()
    probe.sample()
    compiles0 = ctx.compiles.n
    state, steps, t0, t1, loss1 = run_for(state, ctx.seconds)
    compiles = ctx.compiles.n - compiles0
    probe.close()
    tokens = steps * batch * seq
    finite = bool(np.isfinite(loss0) and np.isfinite(loss1))

    # the comparison with the reference, on the run's own weights as the
    # seed made them: the trained state goes first, so that chip 0 holds
    # what it held when this was measured before the window
    del state
    t_check = time.monotonic()
    variables = build.init_variables(model, ctx.seed, mesh=mesh,
                                     strategy=strategy)
    ref_params = variables["params"]
    if mesh is not None:  # weights gathered to one device for it
        ref_params = jax.device_put(ref_params, jax.devices()[0])
    fresh = ex.init_state(variables, rng_key=build.key_for(ctx.seed, 1))
    verdict = check.training(
        model, ref_params, fresh.params, cfg,
        batches[0][:int(tr["check_sequences"])], mesh=mesh)
    phases["after.check"] = time.monotonic() - t_check
    return Run(
        end_to_end={"train_tokens_per_s": tokens / (t1 - t0)},
        values={"steps": steps, "tokens": tokens, "window_s": t1 - t0,
                "batch": batch, "seq": seq,
                "mesh": dict(mesh.shape) if mesh is not None else {}},
        attempted=steps, failed=0 if finite else steps,
        check={**verdict, "first_loss": loss0, "last_loss": loss1},
        extra={"setup": phases}, memory=probe.fullest(),
        compiles_in_window=compiles,
        setup_done=t0, trace_path=trace_path)


# ---------------------------------------------------------------- serving

class Serving:
    """The serving stack of a cell with the benchmark's spans round the
    calls into each layer, and per-token times kept by the benchmark: every
    token, the first included, is stamped when the ``scheduler.step`` that
    produced it returns, which is when a caller of the scheduler can have
    it (``Request.first_token_at`` is the program's own stamp, which a
    later PR could move)."""

    def __init__(self, ctx):
        from hetu_tpu.serve import Request

        self.Request = Request
        self.ctx, self.rec = ctx, ctx.rec
        cfg = ctx.config
        t0 = time.monotonic()
        self.arch = spec.adapter(cfg)
        self.model = self.arch.make_model(cfg, "serve")
        self.variables = build.init_variables(self.model, ctx.seed)
        self.engine, self.scheduler = build.make_serving(
            self.model, self.variables, cfg)
        import jax
        jax.block_until_ready(self.engine.params)
        build_s = time.monotonic() - t0
        self._wrap()
        self.inflight = []          # [record] submitted and not finished
        self.finished = []
        self.setup = {"setup.build": build_s}   # seconds per phase
        self.made = 0               # tokens generated, all requests
        self.steps = []             # (start, end) of each step since the
        #                             window opened: where a stall shows
        self.host_probe = []        # seconds a fixed piece of Python took

    def _wrap(self):
        eng, rec = self.engine, self.rec

        def before_chunk(slot):
            return int(eng.cache.lengths[slot])

        def after_chunk(before, _result, slot):
            rec.counters["prefill_tokens"] += \
                int(eng.cache.lengths[slot]) - before

        def before_decode():
            act = np.nonzero(eng.active)[0]
            return len(act), int(eng.cache.lengths[act].sum())

        def after_decode(before, result):
            if result:
                rec.series["decode_active"].append(before[0])
                rec.series["decode_cached_tokens"].append(before[1])

        rec.wrap(eng, "prefill_step", "engine.prefill_step",
                 before_chunk, after_chunk)
        rec.wrap(eng, "decode", "engine.decode", before_decode, after_decode)
        rec.wrap(self.scheduler, "step", "scheduler.step")

    # -- set-up ----------------------------------------------------------
    def check(self) -> dict:
        """The comparison with the reference, once the window is over and
        the chip's memory has been read: whatever is still in flight is
        ended first, so the check's four requests have the engine alone."""
        self.scheduler.drain("benchmark_over")
        self.inflight = []
        t0 = time.monotonic()
        with self.rec.muted():   # its steps are not the window's
            verdict = check.serving(self.model, self.variables, self.engine,
                                    self.scheduler, self.ctx.config,
                                    self.ctx.seed)
        self.setup["after.check"] = time.monotonic() - t0
        return verdict

    def warm(self, reach: dict) -> None:
        """Run every program the traffic can reach, through the engine's
        public calls: each prefill chunk bucket up to the longest prompt,
        and each (active-slot bucket x page-count bucket) of decode up to
        the longest prompt + answer."""
        eng = self.engine
        cache = eng.cache
        ps, slots = cache.page_size, cache.num_slots
        rng = np.random.default_rng(0)
        low, high = self.arch.id_range(self.ctx.config)

        def prompt(n):   # unshared, or the prefix index would skip chunks
            return rng.integers(low, high, n).astype(np.int32).tolist()

        t0 = time.monotonic()
        for b in eng.chunk_buckets:
            if b <= max(reach["max_prompt"], eng.chunk_buckets[0]):
                s = eng.alloc_slot()
                eng.prefill(s, prompt(min(b, cache.max_len - 2)))
                eng.release(s)
        top = cache.pages_for_tokens(
            min(reach["max_total"] + 1, cache.max_len))
        page_buckets, p = [], 1
        while p < 2 * top and p // 2 < cache.pages_per_slot:
            page_buckets.append(p)
            p *= 2
        counts = [slots] + [c for c in (1 << i for i in range(
            slots.bit_length() - 1, -1, -1)) if c < slots]
        for p in page_buckets:
            long = (p // 2) * ps + ps // 2
            if long + len(counts) + 1 >= cache.max_len:
                continue
            held = [eng.alloc_slot() for _ in range(slots)]
            eng.prefill(held[0], prompt(long))
            for s in held[1:]:
                eng.prefill(s, prompt(4))
            for c in counts:
                while len(held) > c:
                    eng.release(held.pop())
                eng.decode()
            for s in held:
                eng.release(s)
        self.setup["setup.warmup"] = time.monotonic() - t0

    # -- load ------------------------------------------------------------
    def submit(self, prompt, output_len: int, due: float, counted: bool):
        req = self.Request(prompt=prompt, max_tokens=int(output_len))
        self.scheduler.submit(req)
        self.inflight.append({"req": req, "due": due, "counted": counted,
                              "want": int(output_len), "seen": 0,
                              "times": []})

    def step(self) -> None:
        """One scheduler step; stamps the tokens it produced."""
        before = time.monotonic()
        self.scheduler.step()
        now = time.monotonic()
        self.steps.append((before, now))
        if len(self.steps) % HOST_PROBE_EVERY == 0:
            self.host_probe.append(_host_probe())
        still = []
        for r in self.inflight:
            req = r["req"]
            new = len(req.tokens) - r["seen"]
            if new > 0:
                r["times"].extend([now] * new)
                self.made += new
                r["seen"] = len(req.tokens)
            (self.finished if req.done.is_set() else still).append(r)
        self.inflight = still

    def stalls(self, opened: float) -> dict:
        """Where a run that reads far off lost its time: the longest step
        and the longest pause between steps (the host's own code, a
        collection, a descheduled process) with when they fell, the time
        steps took beyond three times the median step, the steps longer
        than 1.5 times the median, mean, median and 95th percentile of each
        engine call, and how fast the host ran a fixed piece of Python
        during the window (a host shared with other tenants slows all of a
        synchronous serving loop's steps alike)."""
        if not self.steps:
            return {}
        took = [b - a for a, b in self.steps]
        gaps = [(b[0] - a[1], b[0]) for a, b in zip(self.steps,
                                                    self.steps[1:])]
        worst = max(range(len(took)), key=took.__getitem__)
        gap, gap_at = max(gaps, default=(0.0, opened))
        median = float(np.median(took))

        def span_ms(prefix, span):
            """Mean, median and 95th percentile of a span's calls in the
            window: a slow-mode process moves the median of every call, a
            stall only the longest."""
            ms = [1e3 * (b - a)
                  for a, b in self.rec.spans.get(span, ())] or [0.0]
            return {prefix + "_span_mean_ms": sum(ms) / len(ms),
                    prefix + "_span_median_ms": float(np.median(ms)),
                    prefix + "_span_p95_ms": percentile(ms, 95)}

        return {"steps_in_window": len(took),
                **span_ms("decode", "engine.decode"),
                **span_ms("chunk", "engine.prefill_step"),
                "host_probe_median_us": 1e6 * float(
                    np.median(self.host_probe)) if self.host_probe else 0.0,
                "median_step_ms": median * 1e3,
                "steps_over_1p5x_median": sum(t > 1.5 * median
                                              for t in took),
                "longest_step_ms": took[worst] * 1e3,
                "longest_step_at_s": self.steps[worst][0] - opened,
                "longest_pause_ms": gap * 1e3,
                "longest_pause_at_s": gap_at - opened,
                "over_3x_median_ms": sum(max(0.0, t - 3 * median)
                                         for t in took) * 1e3}


HOST_PROBE_EVERY = 8    # steps; the probe is some 50 us, a step 15-70 ms


def _host_probe() -> float:
    t0 = time.perf_counter()
    x = 0
    for i in range(1000):
        x += i
    return time.perf_counter() - t0


def _ok(r) -> bool:
    return r["req"].status == "ok" and len(r["req"].tokens) == r["want"]


def backlog(ctx) -> Run:
    tr, rec = ctx.traffic, ctx.rec
    sv = Serving(ctx)
    sv.warm(schedule.reach(tr))
    lengths = schedule.backlog_lengths(tr)
    prompts = schedule.token_ids(ctx.seed, [p for p, _ in lengths],
                                 sv.arch.id_range(ctx.config))
    depth = int(tr["queue_depth_slots"]) * sv.engine.cache.num_slots
    nxt = {"i": 0}

    def top_up():
        while len(sv.inflight) < depth + sv.engine.cache.num_slots:
            i = nxt["i"] % len(lengths)
            sv.submit(prompts[i], lengths[i][1], time.monotonic(), True)
            nxt["i"] += 1

    def run_for(seconds: float):
        t0 = time.monotonic()
        while time.monotonic() - t0 < seconds:
            top_up()
            sv.step()
        return t0, time.monotonic()

    # warm-up by COUNT, not by time: the window then opens at the same
    # point of the fixed request sequence in every run, so two runs differ
    # by the step at the window's far edge and nothing else
    top_up()
    with rec.span("setup.fill"):
        while len(sv.finished) < int(tr["warmup_finished_requests"]):
            top_up()
            sv.step()
    trace_path, traced_series = None, {}
    if ctx.trace:
        with traced(rec):
            run_for(float(tr["trace_s"]))
        trace_path = trace_file()
        # the stretch's own rounds, one a decode launch in its trace
        traced_series = {k: list(v) for k, v in rec.series.items()}
        run_for(SETTLE_S)

    phases = {**sv.setup, **setup_phases(rec)}
    rec.clear()
    sv.finished.clear()
    sv.steps.clear()
    sv.host_probe.clear()
    probe = device.MemoryProbe()
    probe.sample()
    compiles0 = ctx.compiles.n
    engine0 = sv.engine.compiled_executables()
    # the window is exactly --seconds long and opens on a step boundary;
    # the step that straddles its far edge counts for the part of it
    # inside, so the rate does not move by whole steps (a prefill chunk is
    # 64 tokens, a decode round 8) when the edge falls a step earlier
    t0 = time.monotonic()
    t_end = t0 + ctx.seconds
    tokens = 0.0
    while True:
        top_up()
        made0, pre0 = sv.made, rec.counters["prefill_tokens"]
        a = time.monotonic()
        sv.step()
        b = time.monotonic()
        n = (sv.made - made0) \
            + (rec.counters["prefill_tokens"] - pre0)
        if b >= t_end:
            tokens += n * (t_end - a) / (b - a)
            break
        tokens += n
    compiles = ctx.compiles.n - compiles0
    engine_new = sv.engine.compiled_executables() - engine0
    probe.close()
    done = list(sv.finished)
    failed = sum(not _ok(r) for r in done)
    verdict = sv.check()
    return Run(
        end_to_end={"serve_tokens_per_s": tokens / ctx.seconds},
        values={"window_s": ctx.seconds, "window_tokens": tokens,
                "requests_finished": len(done),
                **sv.stalls(t0),
                "engine_new_executables": engine_new,
                "traced_series": traced_series},
        attempted=len(done), failed=failed, check=verdict,
        compiles_in_window=compiles, memory=probe.fullest(),
        setup_done=t0, trace_path=trace_path,
        extra={"setup": {**phases, **sv.setup}})


def open_loop(ctx, *, rate_rps: float = None, serving: Serving = None,
              checked: dict = None) -> Run:
    """Replay the cell's fixed schedule: requests due during the warm-up
    stretch (and, in a traced run, the traced stretch before the window)
    are sent and not counted; the run ends when every counted request has
    finished, or ``drain_timeout_s`` after the window, what is left then
    counting as failed."""
    tr, rec = ctx.traffic, ctx.rec
    sv = serving or Serving(ctx)
    if serving is None:
        sv.warm(schedule.reach(tr))
    lead = float(tr["warmup_s"])
    if ctx.trace:
        lead += float(tr["trace_s"]) + SETTLE_S
    plan = schedule.open_loop_schedule(
        {**tr, "warmup_s": lead}, ctx.seconds, rate_rps=rate_rps)
    prompts = schedule.token_ids(ctx.seed, [a.prompt_len for a in plan],
                                 sv.arch.id_range(ctx.config))
    win0, win1 = lead, lead + ctx.seconds
    deadline = win1 + float(tr["drain_timeout_s"])
    trace_at = float(tr["warmup_s"]) if ctx.trace else None
    trace_cm, trace_path = None, None
    opened_at = compiles0 = engine0 = None
    probe = device.MemoryProbe()
    backlog_mid = backlog_end = None
    traced_series = {}

    def waiting() -> int:
        """Requests sent and not yet given a slot: the backlog."""
        return sum(r["req"].admitted_at is None for r in sv.inflight)

    start = time.monotonic()
    i = 0
    while True:
        now = time.monotonic() - start
        # starting and stopping the profiler blocks this thread for
        # seconds; the replay's clock is stopped meanwhile, or every
        # arrival due in the stall would be sent at once when it ends
        if trace_at is not None and trace_cm is None and now >= trace_at:
            stall = time.monotonic()
            trace_cm = traced(rec)
            trace_cm.__enter__()
            start += time.monotonic() - stall
            continue
        if trace_cm is not None and trace_path is None \
                and now >= trace_at + float(tr["trace_s"]):
            stall = time.monotonic()
            trace_cm.__exit__(None, None, None)
            trace_path = trace_file()
            traced_series = {k: list(v) for k, v in rec.series.items()}
            start += time.monotonic() - stall
            continue
        if opened_at is None and now >= win0:
            opened_at = start + win0
            compiles0 = ctx.compiles.n
            engine0 = sv.engine.compiled_executables()
            rec.clear()
            sv.steps.clear()
            sv.host_probe.clear()
            probe.sample()
        if backlog_mid is None and now >= (win0 + win1) / 2:
            backlog_mid = waiting()
        while i < len(plan) and plan[i].due_s <= now:
            a = plan[i]
            sv.submit(prompts[i], a.output_len, start + a.due_s, a.counted)
            i += 1
        counted_left = any(r["counted"] for r in sv.inflight)
        if backlog_end is None and now >= win1:
            backlog_end = waiting()
        if (now >= win1 and not counted_left) or now >= deadline:
            break
        if sv.scheduler.has_work():
            sv.step()
        elif i < len(plan):
            time.sleep(max(0.0, min(plan[i].due_s - now, 0.002)))
        else:
            break
    compiles = ctx.compiles.n - compiles0
    engine_new = sv.engine.compiled_executables() - engine0
    # let what is left finish outside every count, so the engine is clean
    idle_until = time.monotonic() + 30.0
    while sv.scheduler.has_work() and time.monotonic() < idle_until:
        sv.step()
    probe.close()
    counted = [r for r in sv.finished + sv.inflight if r["counted"]]
    verdict = checked if checked is not None else sv.check()
    good = [r for r in counted if r["req"].done.is_set() and _ok(r)]
    failed = len(counted) - len(good)
    ttft = [(r["times"][0] - r["due"]) * 1e3 for r in good]
    gaps = [(b - a) * 1e3 for r in good
            for a, b in zip(r["times"], r["times"][1:])]
    waits = [(r["req"].admitted_at - r["req"].submitted_at) * 1e3
             for r in good]
    lags = [(r["req"].submitted_at - r["due"]) * 1e3 for r in counted]
    e2e = {}
    if ttft and gaps:
        e2e = {"ttft_mean_ms": float(np.mean(ttft)),
               "itl_p95_ms": percentile(gaps, 95)}
    return Run(
        end_to_end=e2e,
        values={"window_s": ctx.seconds, "ttft_ms": ttft, "itl_ms": gaps,
                "queue_wait_ms": waits, "loadgen_lag_ms": lags,
                "backlog_mid": backlog_mid,
                "backlog_end": backlog_end,
                "requests_counted": len(counted),
                "generated_tokens": sum(r["seen"] for r in counted),
                "engine_new_executables": engine_new,
                **sv.stalls(opened_at),
                "longest_loadgen_lag_ms": max(lags) if lags else 0.0,
                "rate_rps": float(tr["rate_rps"] if rate_rps is None
                                  else rate_rps),
                "traced_series": traced_series},
        attempted=len(counted), failed=failed, check=verdict,
        compiles_in_window=compiles, memory=probe.fullest(),
        setup_done=opened_at,
        trace_path=trace_path, extra={"setup": dict(sv.setup)})


KINDS = {"train_steps": train_steps, "backlog": backlog,
         "open_loop_schedule": open_loop}
