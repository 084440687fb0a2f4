"""Traffic plane (ISSUE 16): seeded open-loop trace synthesis + replay,
per-tenant SLO classes (priority admission + weighted fair queueing),
decode-time preemption for unreserved adopted slots, adopted-payload
prefix re-dedup, and the measured-load autoscaler.

All fast lane: the loadgen is pure numpy, the replay tests drive a fake
clock, the scheduler tests pump a tiny in-process GPT, and the
autoscaler tests run against a fake pool with canned ``fleet_metrics``
dumps.  The real cross-process arms are the slow revive-survival test in
tests/test_fleet_obs.py and the journaled scale-up of tests/test_soak.py.
"""

import jax
import numpy as np
import pytest

from hetu_tpu.models.gpt import GPTConfig, GPTModel
from hetu_tpu.serve import (
    ContinuousBatchingScheduler, PagedServeEngine, Request,
)
from hetu_tpu.traffic import (
    AutoscalePolicy, Autoscaler, TenantSpec, TraceSpec, diurnal_multiplier,
    dumps_trace, load_trace, replay, save_trace, synthesize,
)
from paged_programs import ref_greedy

pytestmark = pytest.mark.traffic


# ---------------------------------------------------------------------------
# loadgen: determinism, rates, skew, replay pacing
# ---------------------------------------------------------------------------

def _spec(**kw):
    base = dict(
        seed=7, duration_s=20.0, base_qps=6.0,
        tenants=[
            TenantSpec(name="gold", share=0.25, slo="gold",
                       deadline_lo_s=3.0, deadline_hi_s=5.0),
            TenantSpec(name="bronze", share=0.75, slo="bronze",
                       burst_x=3.0, burst_on_s=2.0, burst_off_s=4.0),
            TenantSpec(name="ctr", share=0.5, kind="ctr"),
        ])
    base.update(kw)
    return TraceSpec(**base)


def test_trace_bytes_stable_and_roundtrip(tmp_path):
    """Same spec, same BYTES — twice in-process and through disk."""
    a, b = synthesize(_spec()), synthesize(_spec())
    assert dumps_trace(a) == dumps_trace(b)
    p = tmp_path / "trace.json"
    save_trace(a, p)
    assert dumps_trace(load_trace(p)) == dumps_trace(a)
    # a different seed is a different trace, not a permutation
    assert dumps_trace(synthesize(_spec(seed=8))) != dumps_trace(a)
    # versioned: a future format must fail loudly, not misparse
    p2 = tmp_path / "bad.json"
    p2.write_text(dumps_trace({**a, "version": 999}))
    with pytest.raises(ValueError, match="version"):
        load_trace(p2)


def test_per_tenant_rates_and_diurnal_integral():
    """Event counts track each tenant's rate integral: share * base_qps
    * duration, scaled by the diurnal curve's mean multiplier
    ((1 + peak)/2 for the raised cosine) — Poisson, so assert within
    generous sigma bands, seeded so there is no flake."""
    flat = synthesize(_spec(diurnal_peak_x=1.0))
    by = {}
    for ev in flat["events"]:
        by.setdefault(ev["tenant"], []).append(ev)
    # gold: 0.25 * 6 qps * 20 s = 30 expected (no bursts)
    assert 15 <= len(by["gold"]) <= 50
    # bronze bursts multiply only its own windows, never gold's stream
    # (per-tenant rng streams are salted independently)
    assert len(by["bronze"]) > len(by["gold"])
    spiky = synthesize(_spec(diurnal_peak_x=10.0))
    # mean multiplier 5.5 vs 1.0: the spike is unmissable in the count
    assert len(spiky["events"]) > 2.5 * len(flat["events"])
    # and the spike is WHERE the curve says: mid-trace rate dominates
    mid = [e for e in spiky["events"] if 7.5 <= e["t"] < 12.5]
    edge = [e for e in spiky["events"] if e["t"] < 2.5 or e["t"] >= 17.5]
    assert len(mid) > 2 * len(edge)
    assert diurnal_multiplier(10.0, peak_x=10.0, period_s=20.0) == \
        pytest.approx(10.0)
    assert diurnal_multiplier(0.0, peak_x=10.0, period_s=20.0) == \
        pytest.approx(1.0)
    # every event carries its admission-control contract
    for ev in flat["events"]:
        if ev["tenant"] == "gold":
            assert 3.0 <= ev["deadline_s"] <= 5.0
            assert ev["slo"] == "gold"
    # CTR events carry the recsys payload, LLM events the prompt
    assert all("sparse" in e and "dense" in e for e in by["ctr"])
    assert all("prompt" in e for e in by["gold"])


def test_zipf_popularity_is_skewed():
    """Hot prompts repeat — the skew the prefix cache and the PS
    embedding cache are built for.  Rank-0 must beat the median rank by
    a wide margin at s=1.1 over a few hundred draws."""
    t = synthesize(_spec(duration_s=60.0, base_qps=8.0, zipf_s=1.1))
    prompts = [tuple(e["prompt"]) for e in t["events"]
               if e["kind"] == "llm"]
    assert len(prompts) > 200
    counts = sorted((prompts.count(p) for p in set(prompts)),
                    reverse=True)
    assert counts[0] >= 5 * counts[len(counts) // 2]
    # CTR sparse keys share the same skew
    keys = [k for e in t["events"] if e["kind"] == "ctr"
            for k in e["sparse"]]
    kc = sorted((keys.count(k) for k in set(keys)), reverse=True)
    assert kc[0] >= 3 * kc[len(kc) // 2]


def test_replay_is_open_loop_on_a_fake_clock():
    """Every event issues at its RECORDED arrival time — a slow pool
    cannot push the schedule (open loop), and a submit that raises is
    recorded without silencing the rest of the trace."""
    trace = synthesize(_spec(duration_s=5.0))
    now = [100.0]
    issued = []

    def clock():
        return now[0]

    def sleep(dt):
        assert dt > 0
        now[0] += dt

    calls = [0]

    def submit(ev):
        calls[0] += 1
        if calls[0] == 3:
            raise RuntimeError("pool said no")
        issued.append((now[0] - 100.0, ev["t"]))
        return {"ok": ev["t"]}

    out = replay(trace, submit, clock=clock, sleep=sleep)
    assert len(out) == len(trace["events"])  # the raise didn't truncate
    assert sum(1 for _, h in out if isinstance(h, Exception)) == 1
    for issue_t, arrival_t in issued:
        assert issue_t == pytest.approx(arrival_t, abs=1e-6)
    # speed=2 compresses the schedule 2x
    now[0], issued[:], calls[0] = 100.0, [], -10**9
    replay(trace, submit, speed=2.0, clock=clock, sleep=sleep)
    for issue_t, arrival_t in issued:
        assert issue_t == pytest.approx(arrival_t / 2.0, abs=1e-6)
    with pytest.raises(ValueError):
        replay(trace, submit, speed=0.0, clock=clock, sleep=sleep)


# ---------------------------------------------------------------------------
# SLO classes: priority admission + WFQ (in-process scheduler)
# ---------------------------------------------------------------------------

def _gpt():
    m = GPTModel(GPTConfig(
        vocab_size=97, hidden_size=64, num_layers=2, num_heads=4,
        ffn_size=128, max_position=64, dropout_rate=0.0))
    return m, m.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def gpt():
    return _gpt()


def _pump(sch, max_steps=400):
    for _ in range(max_steps):
        if not sch.has_work():
            return
        sch.step()
    raise AssertionError("scheduler did not drain")


def _admission_order(reqs):
    """Requests prefill at admission, so first_token_at IS the
    admission order — observed black-box, no scheduler internals."""
    assert all(r.first_token_at is not None for r in reqs)
    return [r.tenant for r in
            sorted(reqs, key=lambda r: r.first_token_at)]


def test_priority_admission_strict_tiering(gpt):
    """One slot, FIFO submission of bronze-then-gold: every gold admits
    before any bronze — and with NO classes configured the same
    submission order stays pure FIFO (zero behavior change)."""
    model, variables = gpt
    g = np.random.default_rng(31)
    prompts = [[int(t) for t in g.integers(1, 97, 5)] for _ in range(6)]

    def run(slo_classes):
        engine = PagedServeEngine(model, variables, num_slots=1,
                                  max_len=64, page_size=8)
        sch = ContinuousBatchingScheduler(engine,
                                          slo_classes=slo_classes)
        reqs = []
        for i, p in enumerate(prompts):
            slo = "bronze" if i < 3 else "gold"
            reqs.append(Request(prompt=list(p), max_tokens=2,
                                tenant=f"{slo}{i}", slo=slo))
        for r in reqs:
            sch.submit(r)
        _pump(sch)
        assert all(r.status == "ok" for r in reqs)
        return _admission_order(reqs)

    order = run({"gold": {"priority": 2, "weight": 1.0},
                 "bronze": {"priority": 0, "weight": 1.0}})
    assert [t[:4] for t in order] == ["gold"] * 3 + ["bron"] * 3
    assert [t[:4] for t in run(None)] == ["bron"] * 3 + ["gold"] * 3


def test_wfq_interleaves_flows_within_a_tier(gpt):
    """Same priority, equal weights, tenant A's whole burst submitted
    BEFORE tenant B's: fair queueing interleaves A,B,A,B,... instead of
    letting A's head start starve B (which is exactly what FIFO
    does)."""
    model, variables = gpt
    g = np.random.default_rng(33)
    engine = PagedServeEngine(model, variables, num_slots=1, max_len=64,
                              page_size=8)
    sch = ContinuousBatchingScheduler(
        engine, slo_classes={"std": {"priority": 0, "weight": 1.0}})
    reqs = []
    for tenant in ("a", "a", "a", "a", "b", "b", "b", "b"):
        reqs.append(Request(
            prompt=[int(t) for t in g.integers(1, 97, 5)],
            max_tokens=2, tenant=tenant, slo="std"))
    for r in reqs:
        sch.submit(r)
    _pump(sch)
    assert _admission_order(reqs) == \
        ["a", "b", "a", "b", "a", "b", "a", "b"]


def test_wfq_weights_split_admissions_proportionally(gpt):
    """weight 2 vs weight 1 within one tier: over the first six
    admissions the heavy flow gets twice the light flow's share
    (virtual-finish tags advance at 1/weight)."""
    model, variables = gpt
    g = np.random.default_rng(34)
    engine = PagedServeEngine(model, variables, num_slots=1, max_len=64,
                              page_size=8)
    sch = ContinuousBatchingScheduler(
        engine, slo_classes={"hi": {"priority": 0, "weight": 2.0},
                             "lo": {"priority": 0, "weight": 1.0}})
    reqs = []
    for slo in ("lo",) * 4 + ("hi",) * 4:
        reqs.append(Request(
            prompt=[int(t) for t in g.integers(1, 97, 5)],
            max_tokens=2, tenant=slo, slo=slo))
    for r in reqs:
        sch.submit(r)
    _pump(sch)
    first6 = _admission_order(reqs)[:6]
    assert first6.count("hi") == 4 and first6.count("lo") == 2


def test_shed_projection_counts_only_same_or_higher_tier(gpt):
    """A bursting low-SLO tenant's backlog must shed ITS OWN traffic,
    not the high-priority tenant queued behind it: the projected wait
    for a gold submit ignores the bronze queue."""
    model, variables = gpt
    engine = PagedServeEngine(model, variables, num_slots=1, max_len=64,
                              page_size=8)
    sch = ContinuousBatchingScheduler(
        engine, shed=True,
        slo_classes={"gold": {"priority": 2, "weight": 1.0},
                     "bronze": {"priority": 0, "weight": 1.0}})
    sch._ewma_service_s = 1.0  # seed the queue-delay model
    g = np.random.default_rng(35)

    def mk(slo):
        return Request(prompt=[int(t) for t in g.integers(1, 97, 5)],
                       max_tokens=2, tenant=slo, slo=slo, timeout_s=4.0)

    accepted_bronze = shed_bronze = 0
    for _ in range(10):
        r = sch.submit(mk("bronze"))
        if r.status == "shed":
            shed_bronze += 1
        else:
            accepted_bronze += 1
    assert shed_bronze >= 1  # the burst overran its own deadline math
    gold = sch.submit(mk("gold"))
    # projected wait for gold = 1 generation (no gold ahead), well
    # inside its 4 s deadline — admitted despite the bronze wall
    assert gold.status != "shed" and gold.state == "queued"
    # sanity: one more bronze still sheds (the wall is still there)
    assert sch.submit(mk("bronze")).status == "shed"
    sch.drain()


# ---------------------------------------------------------------------------
# decode-time preemption for unreserved adopted slots
# ---------------------------------------------------------------------------

def _oracle(model, variables, prompts, n):
    return [ref_greedy(model, variables, p, n) for p in prompts]


@pytest.mark.migrate
@pytest.mark.paged
def test_adopted_overcommit_preempts_and_requeues_not_raises(gpt):
    """Migration adopts slots WITHOUT page-budget reservations; decode
    then grows them past a tight receiver's pool.  The scheduler must
    preempt a victim (release pages, fold tokens, requeue at head) and
    finish EVERY request token-exact — never surface
    PagePoolExhausted."""
    from hetu_tpu.serve import migrate as mg
    model, variables = gpt
    g = np.random.default_rng(41)
    prompts = [[int(t) for t in g.integers(1, 97, 10)] for _ in range(3)]
    want = _oracle(model, variables, prompts, 24)
    src = ContinuousBatchingScheduler(PagedServeEngine(
        model, variables, num_slots=3, max_len=64, page_size=8))
    reqs = [Request(prompt=list(p), max_tokens=24) for p in prompts]
    for r in reqs:
        src.submit(r)
    for _ in range(3):
        src.step()  # mid-decode: ~12 tokens per slot (2 pages each)
    # receiver: 9 pages hold the 6 adopted pages, but three requests
    # decoding to 34 tokens each need 15 — guaranteed exhaustion
    dst = ContinuousBatchingScheduler(PagedServeEngine(
        model, variables, num_slots=3, max_len=64, page_size=8,
        num_pages=9, prefix_sharing=False))
    mg.migrate_inflight(src, dst)
    _pump(dst)
    assert [r.tokens for r in reqs] == want
    assert all(r.status == "ok" for r in reqs)
    assert dst.metrics.count("requests_preempted") >= 1


# ---------------------------------------------------------------------------
# adopted payloads re-dedup into the receiver's prefix index
# ---------------------------------------------------------------------------

@pytest.mark.migrate
@pytest.mark.paged
def test_adopt_reindexes_prefix_for_future_sharing(gpt):
    """A migrated-in slot's pages must be findable by the receiver's
    prefix index: a NEW same-prefix request after the adopt dedups
    against the adopted KV instead of re-prefilling it."""
    from hetu_tpu.serve import migrate as mg
    model, variables = gpt
    g = np.random.default_rng(43)
    prefix = [int(t) for t in g.integers(1, 97, 16)]  # two full pages
    src = ContinuousBatchingScheduler(PagedServeEngine(
        model, variables, num_slots=2, max_len=64, page_size=8))
    moved = Request(prompt=prefix + [3, 5], max_tokens=12)
    src.submit(moved)
    for _ in range(3):
        src.step()
    dst = ContinuousBatchingScheduler(PagedServeEngine(
        model, variables, num_slots=2, max_len=64, page_size=8))
    mg.migrate_inflight(src, dst)
    # the adopter re-registered the slot's page-aligned prefix
    assert dst.metrics.count("prefix_reindexed") >= 2
    follower = Request(prompt=prefix + [7, 9], max_tokens=6)
    dst.submit(follower)
    _pump(dst)
    assert moved.status == "ok" and follower.status == "ok"
    # the follower's prefill HIT the adopted prefix: 2 pages, 16 tokens
    assert dst.engine.cache.prefix_hit_tokens >= 16
    # parity: sharing the adopted pages changed no tokens
    assert moved.tokens == _oracle(model, variables,
                                   [prefix + [3, 5]], 12)[0]
    assert follower.tokens == _oracle(model, variables,
                                      [prefix + [7, 9]], 6)[0]


# ---------------------------------------------------------------------------
# autoscaler: fake pool, canned dumps, fake clock
# ---------------------------------------------------------------------------

class FakePool:
    def __init__(self, n_members=4):
        self.n_members = n_members
        self.dump = {}
        self.revived, self.drained = [], []
        self.fail_next = None

    def fleet_metrics(self, *, scrape=True):
        outer = self

        class _Reg:
            def dump(self):
                return dict(outer.dump)
        return _Reg()

    def revive_member(self, slot):
        if self.fail_next == "up":
            self.fail_next = None
            raise RuntimeError("spawn failed")
        self.revived.append(slot)

    def drain_member(self, slot, close=False):
        if self.fail_next == "down":
            self.fail_next = None
            raise RuntimeError("drain failed")
        self.drained.append((slot, close))


def _gauge(v):
    return {"type": "gauge", "value": float(v)}


def _counter(v):
    return {"type": "counter", "value": int(v)}


def _mk(policy=None, **kw):
    pool = FakePool()
    now = [0.0]
    pol = policy or AutoscalePolicy(
        min_members=1, max_members=3, queue_high=4.0, queue_low=0.5,
        shed_high=0.02, shed_low=0.001, up_ticks=2, down_ticks=3,
        up_cooldown_s=5.0, down_cooldown_s=10.0)
    sc = Autoscaler(pool, pol, clock=lambda: now[0],
                    active={0}, **kw)
    return pool, sc, now


def test_autoscaler_up_needs_streak_then_cooldown():
    pool, sc, now = _mk()
    pool.dump = {"m0.queue_depth": _gauge(9.0)}
    assert sc.tick()["action"] == "hold"  # 1 tick < up_ticks: hysteresis
    now[0] += 1
    assert sc.tick()["action"] == "up"
    assert pool.revived == [1] and sc.active == {0, 1}
    now[0] += 1  # still overloaded, but inside up_cooldown_s
    sc.tick()
    now[0] += 1
    assert pool.revived == [1]
    now[0] += 10  # cooldown over; streak rebuilt across those ticks
    assert sc.tick()["action"] == "up"
    assert pool.revived == [1, 2] and sc.active == {0, 1, 2}
    # max_members is a hard wall no streak can climb
    for _ in range(10):
        now[0] += 10
        sc.tick()
    assert len(sc.active) == 3 and pool.revived == [1, 2]
    assert sc.scale_ups == 2


def test_autoscaler_down_is_slow_bounded_and_picks_idle_victim():
    pool, sc, now = _mk()
    sc.active = {0, 1, 2}
    pool.dump = {"m0.queue_depth": _gauge(0.5),
                 "m1.queue_depth": _gauge(0.0),
                 "m2.queue_depth": _gauge(0.1)}
    for _ in range(2):  # calm, but short of down_ticks
        now[0] += 1
        assert sc.tick()["action"] == "hold"
    now[0] += 1
    rec = sc.tick()
    # victim is the SHALLOWEST queue (cheapest drain), not round-robin
    assert rec["action"] == "down" and rec["slot"] == 1
    assert pool.drained == [(1, True)] and sc.active == {0, 2}
    for _ in range(3):  # down_cooldown_s gates the next shrink
        now[0] += 1
        sc.tick()
    assert len(pool.drained) == 1
    now[0] += 20
    for _ in range(4):
        now[0] += 1
        sc.tick()
    assert sc.active == {0}  # min_members floor
    for _ in range(6):
        now[0] += 10
        sc.tick()
    assert len(sc.active) == 1 and sc.scale_downs == 2


def test_autoscaler_shed_rate_is_windowed_counter_deltas():
    pool, sc, now = _mk()
    pool.dump = {"requests_submitted": _counter(100),
                 "requests_shed": _counter(0),
                 "m0.queue_depth": _gauge(0.0)}
    sc.tick()  # baseline window
    pool.dump = {"requests_submitted": _counter(200),
                 "requests_shed": _counter(50),
                 "m0.queue_depth": _gauge(0.0)}
    now[0] += 1
    rec = sc.tick()  # delta: 50/100 shed — overloaded
    assert rec["shed_rate"] == pytest.approx(0.5)
    now[0] += 10
    rec = sc.tick()  # counters UNCHANGED: the old burst must not
    assert rec["shed_rate"] == 0.0  # keep voting (windowed, not level)


def test_autoscaler_slo_breach_scales_up_with_named_reason():
    pool, sc, now = _mk(ttft_slos={"gold": 0.5})
    hist = {"type": "histogram", "buckets": [0.1, 1.0, 5.0],
            "counts": [0, 0, 20], "sum": 40.0, "count": 20}
    pool.dump = {"tenant.gold.ttft_s": dict(hist),
                 "m0.queue_depth": _gauge(0.0)}
    rec = sc.tick()
    assert rec["slo_breaches"].get("gold") == pytest.approx(5.0)
    now[0] += 1
    rec = sc.tick()  # same counts: zero delta, breach clears...
    assert rec["slo_breaches"] == {}
    pool.dump["tenant.gold.ttft_s"] = {**hist, "counts": [0, 0, 45],
                                       "count": 45}
    now[0] += 1
    rec = sc.tick()  # ...fresh slow observations re-vote
    now[0] += 1
    pool.dump["tenant.gold.ttft_s"] = {**hist, "counts": [0, 0, 70],
                                       "count": 70}
    rec = sc.tick()
    assert rec["action"] == "up" and rec["reason"] == "slo_breach:gold"
    assert pool.revived == [1]


def test_autoscaler_actuator_failure_keeps_bookkeeping_honest():
    pool, sc, now = _mk()
    pool.dump = {"m0.queue_depth": _gauge(9.0)}
    pool.fail_next = "up"
    sc.tick()
    now[0] += 1
    rec = sc.tick()
    assert rec["action"] == "up_failed" and "spawn failed" in rec["error"]
    assert sc.active == {0}  # the slot it failed to start is NOT active
    now[0] += 10
    assert sc.tick()["action"] == "up"  # retried once the streak rebuilt


def test_autoscaler_bounds_validated_against_pool_geometry():
    pool = FakePool(n_members=2)
    with pytest.raises(ValueError, match="exceeds"):
        Autoscaler(pool, AutoscalePolicy(min_members=1, max_members=3))
    with pytest.raises(ValueError, match="min_members"):
        Autoscaler(pool, AutoscalePolicy(min_members=0, max_members=2))
    with pytest.raises(ValueError, match="max_members"):
        Autoscaler(pool, AutoscalePolicy(min_members=2, max_members=1))
