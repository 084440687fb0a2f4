"""ISSUE 10 network-plane chaos acceptance.

(a) a seeded asymmetric ONE-WAY partition of a serving-member process
    (its writes black-hole, its reads work) degrades it to suspect and
    CLEARS on heal — suspected=1, cleared=1, lost=0, rejoins=0, all
    traffic ok, the fault paired with ``serve.member_suspect``;
(b) an injected 10x-slow link on a training worker is detected as a
    ``train.straggler`` within the deadline, and BOTH policies (wait,
    evict-to-reshard) preserve byte-identical global batches
    (``check_complete_cover``);
(c) a traffic spike + lossy link on a 3-member pool degrades to
    bounded-latency partial service: accepted requests finish inside
    their deadlines, overflow is shed ('shed' status, instant reject),
    zero timeout-collapse.

The deterministic admission-control mechanics (projection model, shed
instants) are covered fast-lane with a controllable fake engine; the
three scenario runs spawn real processes (slow+chaos).
"""

import threading
import time

import numpy as np
import pytest

from hetu_tpu.ps import available
from hetu_tpu.serve.metrics import ServeMetrics
from hetu_tpu.serve.scheduler import ContinuousBatchingScheduler, Request
from hetu_tpu.telemetry import timeline, trace

pytestmark = pytest.mark.netchaos


# ---------------------------------------------------------------------------
# fast lane: deadline-projection shedding, deterministic
# ---------------------------------------------------------------------------

class _Cache:
    num_pages = 1 << 20  # never what holds an admission back

    def __init__(self, num_slots, max_len=64):
        self.num_slots, self.max_len = num_slots, max_len
        self.lengths = np.zeros(num_slots, np.int32)
        self.free = list(range(num_slots))

    @property
    def num_free(self):
        return len(self.free)

    @property
    def occupancy(self):
        return 1.0 - len(self.free) / self.num_slots


class SlowEngine:
    """Engine whose per-step latency is a knob — the deterministic
    stand-in for 'the device is saturated'."""

    def __init__(self, step_s=0.02, num_slots=2):
        self.cache = _Cache(num_slots)
        self.step_s = step_s
        self.metrics = ServeMetrics()
        self._prompts = {}  # slot -> prompt length, begun and not prefilled

    def alloc_slot(self):
        return self.cache.free.pop()

    def release(self, slot):
        self.cache.lengths[slot] = 0
        self._prompts.pop(slot, None)
        if slot not in self.cache.free:
            self.cache.free.append(slot)

    def admission_pages(self, prompt_len, max_tokens):
        return 1

    def admission_ok(self, prompt, max_tokens):
        return True

    def begin_prefill(self, slot, prompt, *, max_tokens=0):
        self._prompts[slot] = len(prompt)

    def prefill_step(self, slot):
        self.cache.lengths[slot] = self._prompts.pop(slot) + 1
        time.sleep(self.step_s)
        return 1

    def decode(self):
        time.sleep(self.step_s)
        out = {}
        for s in range(self.cache.num_slots):
            if s not in self.cache.free and self.cache.lengths[s] > 0:
                self.cache.lengths[s] += 1
                out[s] = 1
        return out


def _drain_all(sched, max_steps=10_000):
    for _ in range(max_steps):
        if not sched.has_work():
            return
        sched.step()
    raise AssertionError("scheduler never drained")


def test_shed_rejects_doomed_submits_instantly():
    eng = SlowEngine(step_s=0.02, num_slots=2)
    sched = ContinuousBatchingScheduler(eng, shed=True)
    # no service-time evidence yet: nothing sheds (projection is 0)
    assert sched.projected_wait_s() == 0.0
    seed = Request(prompt=[1, 2], max_tokens=4, timeout_s=30.0)
    sched.submit(seed)
    _drain_all(sched)
    assert seed.status == "ok"
    ewma = sched._ewma_service_s
    assert ewma is not None and ewma > 0.01
    # a feasible deadline is accepted...
    ok = Request(prompt=[1], max_tokens=2, timeout_s=30.0)
    tracer = trace.Tracer()
    trace.enable(tracer=tracer)
    try:
        sched.submit(ok)
        assert not ok.done.is_set()
        # ...an infeasible one is shed INSTANTLY, waiter resolved, no
        # queue entry, counter charged, instant in the trace
        doomed = Request(prompt=[1], max_tokens=2, timeout_s=ewma / 10)
        t0 = time.perf_counter()
        sched.submit(doomed)
        assert time.perf_counter() - t0 < 0.01
        assert doomed.done.is_set() and doomed.status == "shed"
        assert sched.metrics.count("requests_shed") == 1
        assert not sched.owns(doomed)
    finally:
        trace.disable()
    names = [e.get("name") for e in tracer.events]
    assert "serve.shed" in names
    _drain_all(sched)
    assert ok.status == "ok"


def test_shed_projection_scales_with_queue_depth():
    """The projection is load-aware: the SAME deadline passes an idle
    scheduler and sheds a deep queue — that is what keeps accepted
    requests meeting their deadlines under a spike."""
    eng = SlowEngine(step_s=0.02, num_slots=1)
    sched = ContinuousBatchingScheduler(eng, shed=True)
    seed = Request(prompt=[1], max_tokens=3, timeout_s=30.0)
    sched.submit(seed)
    _drain_all(sched)
    ewma = sched._ewma_service_s
    deadline = 3.0 * ewma
    # idle: projection = 1 service time < deadline -> accepted
    r1 = Request(prompt=[1], max_tokens=3, timeout_s=deadline)
    sched.submit(r1)
    assert not r1.done.is_set()
    # pile up a queue; the same deadline now projects past itself
    backlog = [Request(prompt=[1], max_tokens=3, timeout_s=60.0)
               for _ in range(8)]
    for r in backlog:
        sched.submit(r)
    r2 = Request(prompt=[1], max_tokens=3, timeout_s=deadline)
    sched.submit(r2)
    assert r2.done.is_set() and r2.status == "shed"
    _drain_all(sched)
    assert r1.status == "ok" and all(r.status == "ok" for r in backlog)


def test_no_deadline_never_sheds():
    eng = SlowEngine(step_s=0.01, num_slots=1)
    sched = ContinuousBatchingScheduler(eng, shed=True)
    seed = Request(prompt=[1], max_tokens=2, timeout_s=10.0)
    sched.submit(seed)
    _drain_all(sched)
    for _ in range(6):
        sched.submit(Request(prompt=[1], max_tokens=2))  # no deadline
    assert sched.metrics.count("requests_shed") == 0
    _drain_all(sched)


# ---------------------------------------------------------------------------
# the three scenario acceptance runs (real processes)
# ---------------------------------------------------------------------------

def _gen_threads(pool, prompts, results, *, max_tokens, timeout_s):
    ts = []
    for i, p in enumerate(prompts):
        def worker(i=i, p=p):
            results[i] = pool.generate(p, max_tokens=max_tokens,
                                       timeout_s=timeout_s)
        t = threading.Thread(target=worker)
        t.start()
        ts.append(t)
    return ts


@pytest.mark.slow
@pytest.mark.chaos
def test_asymmetric_partition_suspects_clears_never_grieves(tmp_path):
    """Acceptance (a): seeded one-way egress partition of a member
    process — the controller stops hearing its beats (and its
    completions queue member-side) while the member still hears
    everything.  Within the window: suspected=1; at heal: cleared=1;
    never lost, never failed over, never rejoined; every accepted
    request 'ok'; the fault pairs with the retroactive
    ``serve.member_suspect`` span."""
    if not available():
        pytest.skip("native PS lib unavailable")
    from hetu_tpu.resilience.faults import (
        FaultEvent, FaultInjector, FaultSchedule,
    )
    from hetu_tpu.serve.crosshost import CrossProcessServingPool
    PART_S = 1.0
    schedule = FaultSchedule([FaultEvent(1, "netem_partition", 0.0,
                                         PART_S)])
    inj = FaultInjector(schedule)
    tracer = trace.Tracer()
    trace.enable(tracer=tracer)
    try:
        pool = CrossProcessServingPool(
            2, workdir=tmp_path,
            model={"hidden_size": 64, "num_layers": 2, "num_slots": 6,
                   "max_len": 48},
            hb_ms=60, lease_s=0.4, suspect_grace_s=2.5,
            request_timeout_s=60.0,
            member_env={"JAX_PLATFORMS": "cpu"})
        try:
            prompts = [[(5 * i) % 90 + 1, (3 * i) % 90 + 1, 7]
                       for i in range(8)]
            results = {}
            ts = _gen_threads(pool, prompts, results, max_tokens=24,
                              timeout_s=60.0)
            time.sleep(0.15)  # let routing spread before the cut
            inj.on_step(1)
            pool.run_net_events(inj.pop_net_events())
            for t in ts:
                t.join(120)
            assert len(results) == len(prompts), sorted(results)
            assert all(r["status"] == "ok" for r in results.values()), \
                {i: r["status"] for i, r in results.items()}
            # wait out the heal + clear
            deadline = time.monotonic() + 15.0
            while pool.metrics.count("members_suspect_cleared") < 1 and \
                    time.monotonic() < deadline:
                time.sleep(0.05)
            assert pool.metrics.count("members_suspected") == 1
            assert pool.metrics.count("members_suspect_cleared") == 1
            assert pool.metrics.count("pool_failovers") == 0
            assert pool.metrics.count("members_rejoined") == 0
            # both member processes still alive: nobody was grieved
            assert all(p.poll() is None for p in pool.procs)
        finally:
            pool.close()
    finally:
        trace.disable()
    pairs = timeline.correlate(tracer.events)
    parts = [p for p in pairs if p.kind == "netem_partition"]
    assert len(parts) == 1 and parts[0].paired, parts
    assert parts[0].recovery_name == "serve.member_suspect"
    # detection = the suspect window opening: bounded by lease + poll
    assert parts[0].recover_s < 10.0
    rep = timeline.report(pairs)
    assert rep["netem_partition"]["paired"] == 1


def _run_straggler_fleet(tmp_path, *, policy, duration_s, steps=40,
                         evict_after=2, **kw):
    from hetu_tpu.resilience.faults import (
        FaultEvent, FaultInjector, FaultSchedule,
    )
    from hetu_tpu.resilience.multicontroller import (
        MultiControllerElasticSupervisor,
    )
    schedule = FaultSchedule([FaultEvent(5, "straggler", 1.0,
                                         duration_s)])
    sup = MultiControllerElasticSupervisor(
        3, workdir=tmp_path, steps=steps, global_batch=24,
        lease_s=1.5, suspect_grace_s=1.0, step_sleep_s=0.01,
        straggler_policy=policy, straggler_factor=4.0,
        straggler_evict_after=evict_after, straggler_slow_ms=120,
        injector=FaultInjector(schedule), **kw)
    return sup


@pytest.mark.slow
@pytest.mark.chaos
def test_straggler_wait_policy_detects_and_tolerates(tmp_path):
    """Acceptance (b), wait policy: the injected slow link makes worker
    1 ~10x slow; it is detected (``train.straggler``), tolerated, and
    recovers when the link heals — and the consumed global batches are
    byte-identical to a never-resized run."""
    if not available():
        pytest.skip("native PS lib unavailable")
    tracer = trace.Tracer()
    trace.enable(tracer=tracer)
    try:
        sup = _run_straggler_fleet(tmp_path, policy="wait",
                                   duration_s=1.5)
        try:
            rep = sup.run(deadline_s=240.0)
            sup.verify_consumed(rep["consumed"])
            assert sup.straggle_records, "straggler never detected"
            rec = sup.straggle_records[0]
            assert rec["worker"] == 1 and rec["policy"] == "wait"
            assert rec["ratio"] >= 4.0
            # wait policy: nobody evicted, no reshard ever published
            assert not sup._evicted and not sup.resizes
        finally:
            sup.close()
    finally:
        trace.disable()
    pairs = timeline.correlate(tracer.events)
    stragglers = [p for p in pairs if p.kind == "straggler"]
    assert len(stragglers) == 1 and stragglers[0].paired
    assert stragglers[0].recovery_name == "train.straggler"
    assert stragglers[0].detect_s < 20.0


@pytest.mark.slow
@pytest.mark.chaos
def test_straggler_evict_policy_reshards_around(tmp_path):
    """Acceptance (b), evict policy: the slow link outlasts patience,
    the fleet reshards AROUND the straggler (shrink epoch, worker
    alive-but-excluded), survivors finish, and the consumed batches
    are still byte-identical (complete cover at the new width)."""
    if not available():
        pytest.skip("native PS lib unavailable")
    tracer = trace.Tracer()
    trace.enable(tracer=tracer)
    try:
        sup = _run_straggler_fleet(tmp_path, policy="evict",
                                   duration_s=60.0, evict_after=2)
        try:
            rep = sup.run(deadline_s=240.0)
            sup.verify_consumed(rep["consumed"])
            assert 1 in sup._evicted
            rec = next(r for r in sup.straggle_records
                       if r["resolution"] == "evicted")
            assert rec["worker"] == 1
            shrinks = [r for r in rep["resizes"] if r["kind"] == "shrink"]
            assert shrinks and shrinks[0]["width"] == 2
            # the evicted worker was never DEAD: still a live process,
            # never lost by the lease machine
            assert sup.procs[1].poll() is None
            assert sup.svc.state_of(1).state in ("alive", "suspect")
        finally:
            sup.close()
    finally:
        trace.disable()
    pairs = timeline.correlate(tracer.events)
    stragglers = [p for p in pairs if p.kind == "straggler"]
    assert len(stragglers) == 1 and stragglers[0].paired
    assert stragglers[0].recovery_name == "train.straggler"


@pytest.mark.slow
@pytest.mark.chaos
def test_straggler_probation_auto_readmits_after_heal(tmp_path):
    """ISSUE 11 satellite (closes the PR 10 'no auto re-admission'
    residual): the evicted-but-alive straggler keeps probing its van
    link while excluded; once the injected slow link heals, N
    consecutive healthy probed beats trip the probation loop, the
    controller lifts the eviction (a grow epoch), the worker rejoins
    the mesh, and the run finishes at full width with byte-identical
    consumed batches."""
    if not available():
        pytest.skip("native PS lib unavailable")
    sup = _run_straggler_fleet(tmp_path, policy="evict",
                               duration_s=2.5, evict_after=2,
                               steps=220, straggler_readmit_after=3)
    try:
        rep = sup.run(deadline_s=240.0)
        sup.verify_consumed(rep["consumed"])
        # it WAS evicted...
        assert any(r["resolution"] == "evicted"
                   for r in sup.straggle_records)
        shrinks = [r for r in rep["resizes"] if r["kind"] == "shrink"]
        assert shrinks and shrinks[0]["slot"] == 1
        # ...and the probation loop readmitted it without an operator
        assert 1 not in sup._evicted
        grows = [r for r in rep["resizes"] if r["kind"] == "grow"]
        assert grows and grows[-1]["width"] == 3
        assert grows[-1]["epoch"] > shrinks[0]["epoch"]
        # the readmitted worker trained to the end at full width
        assert sup.svc.state_of(1).committed >= sup.steps - 1
    finally:
        sup.close()


@pytest.mark.slow
@pytest.mark.chaos
def test_spike_plus_lossy_link_sheds_instead_of_collapsing(tmp_path):
    """Acceptance (c): 3-member pool, one member behind a seeded lossy
    link, a spike of deadline-carrying traffic.  The pool degrades to
    bounded-latency PARTIAL service: every accepted request finishes
    'ok' within its deadline, infeasible overflow is shed instantly,
    and nothing collapses to timeout — plus the degraded link opens
    and closes a ``serve.link_degraded`` window that pairs with the
    injected ``fault.netem_degrade``."""
    if not available():
        pytest.skip("native PS lib unavailable")
    from hetu_tpu.resilience.faults import (
        FaultEvent, FaultInjector, FaultSchedule,
    )
    from hetu_tpu.serve.crosshost import CrossProcessServingPool
    schedule = FaultSchedule([FaultEvent(1, "netem_degrade", 0.0, 2.5)])
    inj = FaultInjector(schedule)
    tracer = trace.Tracer()
    trace.enable(tracer=tracer)
    try:
        pool = CrossProcessServingPool(
            3, workdir=tmp_path,
            model={"hidden_size": 64, "num_layers": 2, "num_slots": 4,
                   "max_len": 48},
            hb_ms=60, lease_s=1.0, suspect_grace_s=1.0,
            request_timeout_s=60.0, shed=True,
            member_env={"JAX_PLATFORMS": "cpu"})
        try:
            # wave 1: seed every member's service-time model
            warm = {}
            for t in _gen_threads(pool, [[3, 1, 4], [1, 5, 9],
                                         [2, 6, 5], [3, 5, 8],
                                         [9, 7, 9], [3, 2, 3]],
                                  warm, max_tokens=16, timeout_s=60.0):
                t.join(120)
            assert all(r["status"] == "ok" for r in warm.values())
            # the lossy link lands on member 0
            inj.on_step(1)
            pool.run_net_events(inj.pop_net_events())
            # wave 2 (the spike): deadlines generous enough to be
            # servable after shedding, tight enough to mean something
            spike = {}
            prompts = [[(7 * i) % 90 + 1, (5 * i) % 90 + 1, 11]
                       for i in range(24)]
            t0 = time.monotonic()
            ts = _gen_threads(pool, prompts, spike, max_tokens=16,
                              timeout_s=30.0)
            for t in ts:
                t.join(120)
            wall = time.monotonic() - t0
            assert len(spike) == len(prompts)
            statuses = {r["status"] for r in spike.values()}
            # bounded partial service, never timeout-collapse
            assert statuses <= {"ok", "shed"}, \
                {i: r["status"] for i, r in spike.items()}
            oks = [r for r in spike.values() if r["status"] == "ok"]
            assert oks, "the pool served nobody"
            assert wall < 30.0  # everyone resolved inside the deadline
            # wave 3: infeasible deadlines -> shed, instantly, all
            doomed = {}
            t0 = time.monotonic()
            for t in _gen_threads(pool, [[1, 2, 3]] * 6, doomed,
                                  max_tokens=16, timeout_s=0.002):
                t.join(60)
            assert all(r["status"] == "shed" for r in doomed.values()), \
                {i: r["status"] for i, r in doomed.items()}
            assert time.monotonic() - t0 < 10.0
            assert pool.metrics.count("requests_shed") >= 6
            assert pool.metrics.count("requests_timeout") == 0
            assert pool.metrics.count("requests_error") == 0
            # the degraded link was noticed and recovered
            deadline = time.monotonic() + 20.0
            while pool.metrics.count("links_recovered") < 1 and \
                    time.monotonic() < deadline:
                time.sleep(0.05)
            assert pool.metrics.count("links_degraded") >= 1
            assert pool.metrics.count("links_recovered") >= 1
        finally:
            pool.close()
    finally:
        trace.disable()
    pairs = timeline.correlate(tracer.events)
    degrades = [p for p in pairs if p.kind == "netem_degrade"]
    assert len(degrades) == 1 and degrades[0].paired, degrades
    assert degrades[0].recovery_name == "serve.link_degraded"
    rep = timeline.report(pairs)
    assert rep["netem_degrade"]["paired"] == 1
