"""ServingPool HA: health-routed routing, planned drain with live KV
migration (zero re-prefill on the survivor), unplanned engine-kill
failover (re-prefill on a peer), and the seeded chaos run whose every
``fault.serve_*`` instant pairs with a ``serve.migrate`` /
``serve.failover`` recovery span (ISSUE 5 acceptance).
"""

import threading
import time

import jax
import pytest

from hetu_tpu.ps import available

if not available():  # pragma: no cover
    pytest.skip("native PS lib unavailable", allow_module_level=True)

from hetu_tpu.models.gpt import GPTConfig, GPTModel
from hetu_tpu.resilience.faults import FaultInjector, FaultSchedule
from hetu_tpu.serve import PagedServeEngine, ServingPool
from hetu_tpu.telemetry import timeline, trace
from paged_programs import ref_greedy as _ref_greedy
from paged_programs import submit_and_hold_mid_decode

pytestmark = pytest.mark.migrate


@pytest.fixture(scope="module")
def gpt():
    m = GPTModel(GPTConfig(
        vocab_size=97, hidden_size=64, num_layers=2, num_heads=4,
        ffn_size=128, max_position=64, dropout_rate=0.0))
    return m, m.init(jax.random.PRNGKey(0))


def _factory(model, variables):
    def make():
        return PagedServeEngine(model, variables, num_slots=4, max_len=48,
                                page_size=8, min_bucket=8)
    return make


def _serve_all(pool, prompts, *, max_tokens, mid=None, mid_after_s=0.25):
    """Generate every prompt through the pool on worker threads; ``mid``
    (if given) runs once after decoding has started.  Returns {i: resp}."""
    results = {}

    def worker(i):
        results[i] = pool.generate(prompts[i], max_tokens=max_tokens,
                                   timeout_s=90.0)

    ts = [threading.Thread(target=worker, args=(i,))
          for i in range(len(prompts))]
    for t in ts:
        t.start()
    if mid is not None:
        time.sleep(mid_after_s)
        mid()
    for t in ts:
        t.join(180)
    assert len(results) == len(prompts)
    return results


def test_pool_routes_and_serves_parity(gpt):
    model, variables = gpt
    f = _factory(model, variables)
    pool = ServingPool({"a": f, "b": f}, start_poll=False)
    prompts = [[1, 2, 3], [9, 8, 7, 6], [42, 5], [3, 14, 15, 9]]
    try:
        results = _serve_all(pool, prompts, max_tokens=6)
        for i, resp in results.items():
            assert resp["status"] == "ok", (i, resp)
            assert resp["tokens"] == _ref_greedy(model, variables,
                                                 prompts[i], 6)
        assert pool.metrics.count("pool_requests") == len(prompts)
    finally:
        pool.close()


def test_no_member_available_fails_fast(gpt):
    model, variables = gpt
    f = _factory(model, variables)
    pool = ServingPool({"a": f}, start_poll=False)
    try:
        pool.kill_member("a")
        # the engine loop needs strikes to notice; fail-fast routing only
        # needs `available` to drop, which tracks server.healthy — force
        # the point by marking the member dead directly
        pool.members["a"].dead = True
        t0 = time.monotonic()
        resp = pool.generate([1, 2], max_tokens=4)
        assert resp["status"] == "error"
        assert time.monotonic() - t0 < 1.0
        assert pool.metrics.count("requests_rejected_no_member") == 1
    finally:
        pool.close()


def test_planned_drain_migrates_zero_prefill(gpt):
    """Drain a member mid-decode: its requests finish on the peer with
    token parity and the PEER never prefills the migrated slots (the
    ``prefill_tokens`` metric stays flat)."""
    model, variables = gpt
    f = _factory(model, variables)
    pool = ServingPool({"a": f, "b": f}, start_poll=False)
    prompts = [[1, 2, 3], [9, 8, 7, 6]]
    try:
        a, b = pool.members["a"], pool.members["b"]
        from hetu_tpu.serve import Request
        # route straight to 'a' so the drain has work
        reqs = [Request(prompt=p, max_tokens=12, timeout_s=90.0)
                for p in prompts]
        submit_and_hold_mid_decode(a, reqs)
        slot_map = pool.drain_member("a")
        assert len(slot_map) == len(reqs)
        assert a.server._stop.is_set()  # migrate-then-exit
        for r in reqs:
            assert r.done.wait(60)
            assert r.status == "ok"
        for r, p in zip(reqs, prompts):
            assert r.tokens == _ref_greedy(model, variables, p, 12)
        assert b.engine.metrics.count("prefill_tokens") == 0
        assert pool.metrics.count("slots_migrated") == len(slot_map)
        # the drained member is out of the rotation; the pool still serves
        resp = pool.generate([5, 5], max_tokens=4)
        assert resp["status"] == "ok"
    finally:
        pool.close()


def test_drain_codec_override_per_drain(gpt):
    """ISSUE 9 satellite (PR 7 residual): ``drain_member(codec=)``
    overrides the pool-level ``migrate_codec`` for ONE drain — the
    preemption-deadline case picks a compressed wire while the pool
    default stays lossless — and the compressed body really moves fewer
    wire bytes (``serve.migrate.bytes_*`` telemetry delta)."""
    from hetu_tpu.serve import Request
    from hetu_tpu.telemetry import default_registry as reg

    def counter(name):
        m = reg.metrics().get(name)
        return m.value if m is not None else 0

    model, variables = gpt
    f = _factory(model, variables)
    pool = ServingPool({"a": f, "b": f}, start_poll=False)
    try:
        with pytest.raises(ValueError, match="codec"):
            pool.drain_member("a", codec="zstd")
        reqs = [Request(prompt=p, max_tokens=12, timeout_s=90.0)
                for p in ([1, 2, 3], [9, 8, 7, 6])]
        submit_and_hold_mid_decode(pool.members["a"], reqs)
        logical0 = counter("serve.migrate.bytes_logical")
        wire0 = counter("serve.migrate.bytes_wire")
        slot_map = pool.drain_member("a", codec="bf16")
        assert len(slot_map) == len(reqs)
        # the pool-level default is untouched by the per-drain override
        assert pool.migrate_codec == "none"
        logical = counter("serve.migrate.bytes_logical") - logical0
        wire = counter("serve.migrate.bytes_wire") - wire0
        assert logical > 0
        assert wire * 2 == logical  # bf16 body: exactly half the bytes
        for r in reqs:
            assert r.done.wait(60)
            assert r.status == "ok"
    finally:
        pool.close()


def test_unplanned_kill_fails_over_with_parity(gpt):
    model, variables = gpt
    f = _factory(model, variables)
    pool = ServingPool({"a": f, "b": f}, health_poll_s=0.05,
                       max_loop_errors=2)
    prompts = [[1, 2, 3], [9, 8, 7, 6], [42, 5], [7, 7], [2, 4, 6]]
    try:
        def kill_loaded():
            loaded = max(pool.members.values(),
                         key=lambda m: m.scheduler.load)
            pool.kill_member(loaded.name)

        results = _serve_all(pool, prompts, max_tokens=12, mid=kill_loaded)
        for i, resp in results.items():
            assert resp["status"] == "ok", (i, resp)
            assert resp["tokens"] == _ref_greedy(model, variables,
                                                 prompts[i], 12)
        assert pool.metrics.count("pool_failovers") == 1
    finally:
        pool.close()


def test_revive_after_kill_rejoins_routing(gpt):
    model, variables = gpt
    f = _factory(model, variables)
    pool = ServingPool({"a": f, "b": f}, health_poll_s=0.05,
                       max_loop_errors=2)
    try:
        pool.kill_member("a")
        # a kill is only NOTICED under load (the engine loop must strike
        # out): route a request straight at the dead member
        from hetu_tpu.serve import Request
        victim = Request(prompt=[1, 2], max_tokens=6, timeout_s=60.0)
        pool.members["a"].scheduler.submit(victim)
        deadline = time.monotonic() + 30
        while not pool.members["a"].dead:
            assert time.monotonic() < deadline, "failover never happened"
            time.sleep(0.02)
        assert victim.done.wait(60)  # failed over, served by 'b'
        assert victim.status == "ok"
        pool.revive_member("a")
        assert pool.members["a"].available
        # drive traffic until the revived member serves some of it
        for _ in range(4):
            assert pool.generate([3, 1], max_tokens=3)["status"] == "ok"
        assert pool.metrics.count("members_revived") == 1
    finally:
        pool.close()


def test_request_compares_by_identity():
    """Queue-membership scans mean "this object": field-wise __eq__
    would deep-compare full prompt/token lists against every queued
    request on the serving path (owns(), adoption rollback)."""
    from hetu_tpu.serve import Request
    a = Request(prompt=[1, 2], max_tokens=4)
    b = Request(prompt=[1, 2], max_tokens=4)
    b.rid = a.rid  # field-identical, still a different request
    assert a == a and a != b
    import collections
    assert b not in collections.deque([a])


def test_failover_closes_intake_and_rejects_without_phantom_counters(gpt):
    """A submit that raced the pick-vs-failover window must be REJECTED
    (so pool.submit re-routes it), never admitted into the dead queue —
    and the reject must not charge the member's requests_<status>
    terminal counters (one request would otherwise count N-1 times
    'error' plus once 'ok' across the pool)."""
    model, variables = gpt
    f = _factory(model, variables)
    pool = ServingPool({"a": f, "b": f}, start_poll=False)
    try:
        a = pool.members["a"]
        pool.failover("a")
        from hetu_tpu.serve import Request
        req = Request(prompt=[1, 2], max_tokens=4, timeout_s=30.0)
        a.scheduler.submit(req)  # the racing submit, post-failover
        assert req.done.is_set() and not req.tokens
        assert req.status == "error"
        assert a.scheduler.metrics.count("requests_rejected") == 1
        assert a.scheduler.metrics.count("requests_error") == 0
        # the pool itself routes new work away from the dead member
        assert pool.generate([1, 2], max_tokens=4)["status"] == "ok"
    finally:
        pool.close()


def test_cancel_does_not_block_on_an_unrelated_wedged_member(gpt):
    """The backstop cancel goes straight to the request's stamped owner:
    scanning members would take each scheduler's lock in turn, so one
    wedged member (engine stuck mid-step, loop alive) would block
    cancelling a request served by a healthy peer — forever."""
    model, variables = gpt
    f = _factory(model, variables)
    pool = ServingPool({"a": f, "b": f}, start_poll=False)
    from hetu_tpu.serve import Request
    try:
        req = Request(prompt=[1, 2], max_tokens=4, timeout_s=30.0)
        pool.members["b"].scheduler.submit(req)
        assert req.owner is pool.members["b"].scheduler
        # member 'a' wedges mid-decode: its scheduler lock is held and
        # never released while we cancel a request owned by 'b'
        assert pool.members["a"].scheduler._lock.acquire(timeout=5)
        try:
            t0 = time.monotonic()
            pool._cancel(req, "timeout")
            assert time.monotonic() - t0 < 2.0
            assert req.done.is_set() and req.status == "timeout"
        finally:
            pool.members["a"].scheduler._lock.release()
    finally:
        pool.close()


def test_cancel_does_not_block_on_the_wedged_owner_itself(gpt):
    """The OWNER may be the wedged member: its scheduler lock is held
    across the stuck engine step, so the backstop must resolve the
    waiter without that lock (cancel_detached) and detach the
    dequeue/slot cleanup — a plain owner.cancel would hang forever on
    exactly the wedge the backstop exists to escape."""
    model, variables = gpt
    f = _factory(model, variables)
    pool = ServingPool({"a": f, "b": f}, start_poll=False)
    from hetu_tpu.serve import Request
    try:
        # enough decode steps that the engine loop cannot finish the
        # request in the instant before the wedge lands
        req = Request(prompt=[1, 2], max_tokens=40, timeout_s=30.0)
        owner = pool.members["b"].scheduler
        owner.submit(req)
        assert req.owner is owner
        # 'b' — the owner — wedges mid-decode: its own lock never frees
        assert owner._lock.acquire(timeout=5)
        try:
            t0 = time.monotonic()
            pool._cancel(req, "timeout")
            assert time.monotonic() - t0 < 2.0
            assert req.done.is_set() and req.status == "timeout"
        finally:
            owner._lock.release()
        # once the wedge clears, the detached cleanup dequeues the
        # request (and frees its slot if it had one)
        deadline = time.monotonic() + 10
        while owner.owns(req):
            assert time.monotonic() < deadline, "detached cleanup never ran"
            time.sleep(0.01)
    finally:
        pool.close()


class _RecordingVan:
    """Pass-through to the real van module that records every
    BlobChannel id opened through it."""

    def __init__(self, van, ids):
        self._van = van
        self._ids = ids

    def BlobChannel(self, host, port, ch_id, *a, **kw):
        self._ids.append(ch_id)
        return self._van.BlobChannel(host, port, ch_id, *a, **kw)

    def __getattr__(self, name):
        return getattr(self._van, name)


def test_two_pools_sharing_one_van_draw_distinct_migration_channels(gpt):
    """Migration channel ids are drawn PROCESS-globally: two pools
    attached to one van (``own_van=False`` is supported) must never hand
    two transfers the same channel id — each receiver would consume the
    other's individually-CRC-valid chunks and adopt a peer pool's KV
    rows."""
    model, variables = gpt
    f = _factory(model, variables)
    from hetu_tpu.serve import Request
    pool_a = ServingPool({"a": f, "b": f}, start_poll=False)
    pool_b = ServingPool({"a": f, "b": f}, start_poll=False,
                         own_van=False, port=pool_a.port)
    ids_a, ids_b = [], []
    pool_a._van = _RecordingVan(pool_a._van, ids_a)
    pool_b._van = _RecordingVan(pool_b._van, ids_b)
    try:
        reqs = []
        for pool in (pool_a, pool_b):
            r = Request(prompt=[1, 2, 3], max_tokens=30, timeout_s=90.0)
            submit_and_hold_mid_decode(pool.members["a"], [r])
            reqs.append(r)
        # drain CONCURRENTLY — the interleaving where same-id transfers
        # would cross-consume each other's chunks
        maps = {}
        ts = [threading.Thread(
            target=lambda p=p, k=k: maps.setdefault(k, p.drain_member("a")))
            for k, p in (("a", pool_a), ("b", pool_b))]
        for t in ts:
            t.start()
        for t in ts:
            t.join(90)
        assert maps.get("a") and maps.get("b"), maps
        for r in reqs:
            assert r.done.wait(60)
            assert r.status == "ok"
            assert r.tokens == _ref_greedy(model, variables, [1, 2, 3], 30)
        assert ids_a and ids_b
        assert not set(ids_a) & set(ids_b), (ids_a, ids_b)
    finally:
        pool_b.close()
        pool_a.close()


def test_soft_reject_leaves_parked_waiter_unresolved(gpt):
    """The pool's routing retry uses resolve_on_reject=False: a member's
    intake reject flags the request without touching done/status, so a
    thread already parked on request.done sleeps through the re-route
    instead of waking into a half-routed request and reading it as an
    empty success."""
    model, variables = gpt
    f = _factory(model, variables)
    pool = ServingPool({"a": f, "b": f}, start_poll=False)
    from hetu_tpu.serve import Request
    a = pool.members["a"]
    real = a.scheduler.submit

    def drain_then_submit(req, **kw):
        # the member drains INSIDE the pick-vs-submit window — the race
        # the re-route exists to resolve
        a.draining = True
        a.scheduler.stop_intake("shutdown")
        return real(req, **kw)

    try:
        a.scheduler.submit = drain_then_submit
        # pool-level: a waiter parked BEFORE submit sees only the final
        # completion on the re-routed member, never the transit reject
        req = Request(prompt=[1, 2], max_tokens=3, timeout_s=60.0)
        seen = {}

        def park():
            seen["woke"] = req.done.wait(90)
            seen["status"] = req.status
            seen["tokens"] = list(req.tokens)

        t = threading.Thread(target=park)
        t.start()
        pool.submit(req)  # 'a' soft-rejects mid-window, 'b' serves
        t.join(120)
        assert seen["woke"] and seen["status"] == "ok"
        assert seen["tokens"] == _ref_greedy(model, variables, [1, 2], 3)
        # scheduler-level contract: the soft reject resolved NOTHING on
        # the request it bounced
        probe = Request(prompt=[5], max_tokens=2)
        a.scheduler.submit(probe, resolve_on_reject=False)
        assert probe.rejected
        assert not probe.done.is_set() and probe.status == ""
    finally:
        a.scheduler.submit = real
        pool.close()


def test_finish_request_single_winner():
    """Racing finishers (backstop cancel vs the owning engine loop)
    resolve a request exactly once: the loser is a no-op, the settled
    status survives, and terminal counters never double-charge."""
    from hetu_tpu.serve import Request
    from hetu_tpu.serve.metrics import ServeMetrics
    from hetu_tpu.serve.scheduler import finish_request
    m = ServeMetrics()
    req = Request(prompt=[1], max_tokens=1)
    assert finish_request(req, "ok", m) is True
    assert finish_request(req, "timeout", m) is False
    assert req.status == "ok"
    assert m.count("requests_ok") == 1
    assert m.count("requests_timeout") == 0


def test_pool_submit_does_not_reroute_accepted_then_failed(gpt):
    """Only the scheduler's EXPLICIT intake reject re-routes: a request
    that was genuinely accepted and then failed with zero tokens inside
    the submit window must stay failed — resubmitting it to every peer
    would double-finish it and double-count terminal metrics."""
    model, variables = gpt
    f = _factory(model, variables)
    pool = ServingPool({"a": f, "b": f}, start_poll=False)
    from hetu_tpu.serve import Request
    from hetu_tpu.serve.scheduler import finish_request
    a = pool.members["a"]
    real = a.scheduler.submit

    def accept_then_fail(req, **kw):
        real(req, **kw)
        # the engine loop wins the race inside the submit window:
        # admitted, then terminally failed with zero tokens
        with a.scheduler._lock:
            a.scheduler._queue.remove(req)
        finish_request(req, "error", a.scheduler.metrics)
        return req

    try:
        a.scheduler.submit = accept_then_fail
        req = Request(prompt=[1, 2], max_tokens=4, timeout_s=30.0)
        pool.submit(req)  # routes to 'a' (insertion-order tie-break)
        assert req.done.is_set() and req.status == "error"
        assert not req.tokens
        assert a.scheduler.metrics.count("requests_error") == 1
        b = pool.members["b"]
        assert b.scheduler.metrics.count("requests_submitted") == 0
        assert pool.metrics.count("requests_rejected_no_member") == 0
    finally:
        a.scheduler.submit = real
        pool.close()


def test_failover_skips_member_mid_drain(gpt):
    """The health poll's failover must leave a draining member to its
    drain: closing the source's intake mid-migration would make the
    drain's failure rollback (adopt-back onto the source) impossible,
    terminally 'error'-ing accepted requests a peer could still serve."""
    model, variables = gpt
    f = _factory(model, variables)
    pool = ServingPool({"a": f, "b": f}, start_poll=False)
    try:
        a = pool.members["a"]
        a.draining = True  # drain_member holds the member here mid-flight
        assert pool.failover("a") == 0
        assert not a.dead
        assert a.scheduler._accepting  # intake untouched — rollback works
        a.draining = False  # drain failed: next sweep may now claim it
        pool.failover("a")
        assert a.dead
    finally:
        pool.close()


def test_drain_close_sweeps_submit_admitted_during_migration(gpt):
    """A request admitted to the source AFTER its export (the
    pick-vs-drain race) must be swept onto a peer before the drained
    member closes — close() must never terminally 'shutdown' an
    accepted request."""
    model, variables = gpt
    f = _factory(model, variables)
    pool = ServingPool({"a": f, "b": f}, start_poll=False)
    from hetu_tpu.serve import Request, migrate as mg
    straggler = Request(prompt=[4, 2], max_tokens=6, timeout_s=60.0)
    real = mg.migrate_inflight
    injected = []

    def migrate_then_lose_the_race(src, dst, **kw):
        out = real(src, dst, **kw)
        # a submit whose pick happened before m.draining was set lands
        # here — after the export, before the close
        pool.members["a"].scheduler.submit(straggler)
        injected.append(not straggler.done.is_set())
        return out

    try:
        mg.migrate_inflight = migrate_then_lose_the_race
        pool.drain_member("a")
    finally:
        mg.migrate_inflight = real
    assert injected == [True]  # it really was ADMITTED, not rejected
    try:
        assert straggler.done.wait(60)
        assert straggler.status == "ok"
        assert straggler.tokens == _ref_greedy(model, variables, [4, 2], 6)
        assert pool.members["a"].scheduler.metrics.count(
            "requests_shutdown") == 0
    finally:
        pool.close()


@pytest.mark.slow
@pytest.mark.chaos
def test_pool_chaos_seeded_preempt_plus_kill_all_ok(gpt):
    """ISSUE 5 acceptance chaos run: a seeded schedule preempts one pool
    member (planned → live migration) and kills another (unplanned →
    re-prefill failover) while requests are in flight.  Every accepted
    request completes 'ok' with exact greedy parity, and
    ``timeline.report`` pairs every ``fault.serve_*`` instant with a
    ``serve.migrate`` or ``serve.failover`` recovery span."""
    model, variables = gpt
    f = _factory(model, variables)

    def victims(sched):
        return {e.kind: int(e.arg) for e in sched.events}

    # deterministically pick the first seed whose two victims differ (a
    # preempt aimed at an already-killed member has no recovery to pair)
    seed, sched = next(
        (s, sc) for s, sc in
        ((s, FaultSchedule.generate(steps=6, seed=s, serve_preempts=1,
                                    serve_engine_kills=1, n_members=3))
         for s in range(64))
        if len(sc) == 2 and
        victims(sc)["serve_preempt"] != victims(sc)["serve_engine_kill"])
    # replay contract: same seed+kwargs → byte-identical schedule
    assert sched.to_json() == FaultSchedule.generate(
        steps=6, seed=seed, serve_preempts=1, serve_engine_kills=1,
        n_members=3).to_json()

    inj = FaultInjector(sched)
    tracer = trace.enable()
    pool = ServingPool({"m0": f, "m1": f, "m2": f}, health_poll_s=0.05,
                       max_loop_errors=2)
    prompts = [[1, 2, 3], [9, 8, 7, 6], [42, 5], [3, 14], [7, 7, 7],
               [2, 4, 6, 8]]
    served: list = []
    stop = threading.Event()

    def traffic(wid: int):
        # CONTINUOUS traffic: the faults must land while requests are in
        # flight (a killed member is only DETECTED when routed work makes
        # its engine loop strike out), so workers keep generating until
        # the fault schedule has fully played out
        k = 0
        while not stop.is_set():
            p = prompts[(wid + 3 * k) % len(prompts)]
            served.append((p, pool.generate(p, max_tokens=24,
                                            timeout_s=90.0)))
            k += 1

    workers = [threading.Thread(target=traffic, args=(w,))
               for w in range(3)]
    try:
        for w in workers:
            w.start()
        deadline = time.monotonic() + 60
        while pool.metrics.count("pool_requests") < 6:  # pool is warm
            assert time.monotonic() < deadline, "traffic never started"
            time.sleep(0.02)
        for step in range(1, 6):
            inj.on_step(step)
            pool.run_fault_events(inj.pop_serve_events())
            time.sleep(0.15)
        # let the health poll detect the killed member under load
        while pool.metrics.count("pool_failovers") < 1:
            assert time.monotonic() < deadline, "failover never happened"
            time.sleep(0.05)
        stop.set()
        for w in workers:
            w.join(120)
        assert served
        refs: dict = {}
        for p, resp in served:
            assert resp["status"] == "ok", resp
            key = tuple(p)
            if key not in refs:
                refs[key] = _ref_greedy(model, variables, p, 24)
            assert resp["tokens"] == refs[key]
    finally:
        stop.set()
        pool.close()
        trace.disable()

    pairs = timeline.correlate(tracer.events)
    serve_pairs = [p for p in pairs if p.kind.startswith("serve_")]
    assert len(serve_pairs) == 2
    for p in serve_pairs:
        assert p.paired, f"fault.{p.kind} has no recovery span"
        assert p.recovery_name in ("serve.migrate", "serve.failover")
        assert p.recover_s >= 0.0
    rep = timeline.report(pairs)
    assert rep["serve_preempt"]["paired"] == 1
    assert rep["serve_engine_kill"]["paired"] == 1
    assert "recover_s" in rep["serve_preempt"]
