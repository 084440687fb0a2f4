"""Network-plane emulation (ISSUE 10): the ps/netem link-policy layer
(seeded replay, direction classification, asymmetric partitions, the
schedule), the hardened membership suspicion (probe-failed vs
beats-stopped), the bounded-and-named control_rpc timeout under 100%
drop, and the auto drain-codec crossover model.  Everything here is
fast-lane except the real-van partition runs (slow)."""

import time

import numpy as np
import pytest

from hetu_tpu.ps import available
from hetu_tpu.ps import membership as mb
from hetu_tpu.ps import netem as ne

pytestmark = pytest.mark.netchaos


# ---------------------------------------------------------------------------
# LinkPolicy / NetEm mechanics (no van)
# ---------------------------------------------------------------------------

def test_op_direction_classification():
    assert ne.op_directions("van_sparse_push") == (ne.EGRESS,)
    assert ne.op_directions("van_sparse_set") == (ne.EGRESS,)
    assert ne.op_directions("blob_put") == (ne.EGRESS,)
    assert ne.op_directions("van_dense_pull") == (ne.INGRESS,)
    assert ne.op_directions("blob_get") == (ne.INGRESS,)
    # control ops need both directions up
    assert set(ne.op_directions("van_ping")) == {ne.EGRESS, ne.INGRESS}


def test_drop_decisions_replay_byte_for_byte():
    def run(seed):
        em = ne.NetEm(local="a", peer="van", seed=seed)
        em.set_link(ne.LinkPolicy(drop_p=0.4), direction="egress")
        out = []
        for _ in range(50):
            try:
                em.hook("van_sparse_set", 64)
                out.append(0)
            except ne.NetemDrop:
                out.append(1)
        return out

    a, b = run(7), run(7)
    assert a == b and 0 < sum(a) < 50
    assert run(8) != a  # a different seed is a different run


def test_asymmetric_partition_is_one_way():
    em = ne.NetEm(local="m0", peer="van", seed=0)
    em.set_link(ne.LinkPolicy(partition=True), direction="egress")
    # m0's writes black-hole...
    with pytest.raises(ne.NetemDrop) as ei:
        em.hook("van_sparse_set", 32)
    assert "m0->van" in str(ei.value)
    # ...while its reads still work (the controller-ward half is up)
    em.hook("van_sparse_pull", 32)
    em.clear_link(direction="egress")
    em.hook("van_sparse_set", 32)  # healed


def test_partition_auto_expires():
    em = ne.NetEm(seed=0)
    em.set_link(ne.LinkPolicy(partition=True, duration_s=0.15),
                direction="egress")
    with pytest.raises(ne.NetemDrop):
        em.hook("blob_put", 8)
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        try:
            em.hook("blob_put", 8)
            break
        except ne.NetemDrop:
            time.sleep(0.02)
    else:
        pytest.fail("partition did not self-heal")


def test_latency_and_bandwidth_delay():
    em = ne.NetEm(seed=0)
    em.set_link(ne.LinkPolicy(latency_s=0.05, rate_mbps=8.0),
                direction="egress")
    t0 = time.perf_counter()
    em.hook("van_dense_push", 100_000)  # 100 KB @ 1 MB/s = 0.1 s
    dt = time.perf_counter() - t0
    assert dt >= 0.14  # latency + serialization
    # ingress ops see neither (policy is egress-only)
    t0 = time.perf_counter()
    em.hook("van_dense_pull", 100_000)
    assert time.perf_counter() - t0 < 0.05


def test_policy_and_schedule_json_roundtrip():
    pol = ne.LinkPolicy(latency_s=0.01, jitter_s=0.2, drop_p=0.01,
                        rate_mbps=50.0, duration_s=1.5)
    assert ne.LinkPolicy.from_dict(pol.to_dict()) == pol
    sched = ne.NetemSchedule(
        [ne.NetemEvent(0.5, ne.EGRESS, pol.to_dict()),
         ne.NetemEvent(2.0, ne.EGRESS, None)], t0_unix=123.0)
    back = ne.NetemSchedule.from_json(sched.to_json())
    assert back.t0_unix == 123.0
    assert [(e.t_s, e.direction, e.policy) for e in back.events] == \
        [(e.t_s, e.direction, e.policy) for e in sched.events]


def test_schedule_applies_and_clears_policies():
    em = ne.NetEm(seed=0)
    ne.NetemSchedule(
        [ne.NetemEvent(0.05, ne.EGRESS,
                       ne.LinkPolicy(partition=True).to_dict()),
         ne.NetemEvent(0.25, ne.EGRESS, None)]).start(em)
    deadline = time.monotonic() + 5.0
    dropped = False
    while time.monotonic() < deadline:
        try:
            em.hook("blob_put", 8)
            if dropped:
                return  # partitioned then healed, in order
        except ne.NetemDrop:
            dropped = True
        time.sleep(0.02)
    pytest.fail("schedule never applied+cleared the partition")


# ---------------------------------------------------------------------------
# membership: probe-failed vs beats-stopped suspicion (fake blackboard)
# ---------------------------------------------------------------------------

class FlakyTable:
    """Blackboard stand-in whose PULLS can be made to fail — the
    controller-side half of an asymmetric partition."""

    def __init__(self, n_slots):
        # n member rows + control row + controller row
        self.rows = np.zeros((n_slots + 2, mb.MEMBER_DIM), np.float32)
        self.down = False

    def sparse_set(self, idx, vals):
        self.rows[np.asarray(idx, int)] = np.asarray(vals, np.float32)

    def sparse_pull(self, idx):
        if self.down:
            raise ConnectionError("injected: controller link down")
        return self.rows[np.asarray(idx, int)].copy()


def _beat(table, slot, inc, beat):
    row = np.zeros((1, mb.MEMBER_DIM), np.float32)
    row[0, mb.F_INCARNATION] = inc
    row[0, mb.F_BEAT] = beat
    row[0, mb.F_FLAG] = 1.0
    table.sparse_set([slot], row)


def test_probe_failure_suspects_but_never_grieves():
    """The controller's OWN pull failing is 'my probe failed', not
    'their beats stopped': members degrade to suspect(probe_failed),
    the silence clocks freeze, and however long the blindness lasts
    nothing is ever lost on that evidence — a beating member clears
    the moment visibility returns (lost=0, rejoins=0)."""
    t = FlakyTable(2)
    svc = mb.MembershipService(t, 2, lease_s=0.05, suspect_grace_s=0.05,
                               rpc_deadline_s=0.1)
    _beat(t, 0, 7, 1)
    _beat(t, 1, 9, 1)
    assert sorted(svc.poll()) == [("join", 0), ("join", 1)]
    _beat(t, 0, 7, 2)
    _beat(t, 1, 9, 2)
    svc.poll()
    t.down = True
    evs = svc.poll()
    assert sorted(evs) == [("suspect", 0), ("suspect", 1)]
    assert svc.state_of(0).suspect_reason == "probe_failed"
    assert svc.alive_slots() == []          # blind: stop routing
    assert sorted(svc.present_slots()) == [0, 1]  # but nobody kicked
    time.sleep(0.3)  # would be far past lease+grace if it counted
    assert svc.poll() == []  # still blind, still silent, still no loss
    t.down = False
    _beat(t, 0, 7, 3)  # slot 0 was beating all along
    evs = svc.poll()
    assert ("clear", 0) in evs
    assert ("lost", 1) not in evs  # slot 1 judged on OBSERVED silence
    assert svc.state_of(0).state == "alive"
    assert svc.probe_failures == 2
    assert svc.probe_blind_s > 0.2
    # slot 1 really is silent now: observed silence escalates normally
    assert svc.state_of(1).suspect_reason == "beats_stopped"
    events = []
    deadline = time.monotonic() + 3.0
    while ("lost", 1) not in events and time.monotonic() < deadline:
        time.sleep(0.04)
        events += svc.poll()
    assert ("lost", 1) in events


def test_beats_stopped_still_escalates_to_lost():
    """The hardening must not soften the real-death path: observed
    silence past lease+grace is still a loss."""
    t = FlakyTable(1)
    svc = mb.MembershipService(t, 1, lease_s=0.04, suspect_grace_s=0.04)
    _beat(t, 0, 5, 1)
    svc.poll()
    time.sleep(0.1)
    assert svc.poll() == [("suspect", 0)]
    assert svc.state_of(0).suspect_reason == "beats_stopped"
    time.sleep(0.1)
    assert svc.poll() == [("lost", 0)]


# ---------------------------------------------------------------------------
# deaf-member detection: the INGRESS-cut direction (ISSUE 11 satellite —
# netem can already black-hole a member's reads; now membership sees it)
# ---------------------------------------------------------------------------

def _beat_ack(table, slot, inc, beat, epoch_ack):
    row = np.zeros((1, mb.MEMBER_DIM), np.float32)
    row[0, mb.F_INCARNATION] = inc
    row[0, mb.F_BEAT] = beat
    row[0, mb.F_FLAG] = 1.0
    row[0, mb.F_EPOCH_ACK] = epoch_ack
    table.sparse_set([slot], row)


def test_deaf_member_suspected_then_cleared_on_ack():
    """A member whose beats ARRIVE but who never acks the published
    control epoch inside the bound is suspect(reason=deaf) — alive (no
    escalation to lost while beating), unroutable — and CLEARS the
    moment its epoch_ack catches up."""
    t = FlakyTable(2)
    svc = mb.MembershipService(t, 2, lease_s=10.0, suspect_grace_s=10.0,
                               deaf_ack_s=0.05)
    _beat_ack(t, 0, 7, 1, 0)
    _beat_ack(t, 1, 9, 1, 0)
    assert sorted(svc.poll()) == [("join", 0), ("join", 1)]
    svc.publish_control(epoch=3, width=2, alive_mask=3)
    # inside the bound: behind on acks is not yet deafness
    _beat_ack(t, 0, 7, 2, 3)   # slot 0 hears and acks
    _beat_ack(t, 1, 9, 2, 0)   # slot 1's ingress is cut: beats only
    assert svc.poll() == []
    time.sleep(0.1)            # past deaf_ack_s
    _beat_ack(t, 0, 7, 3, 3)
    _beat_ack(t, 1, 9, 3, 0)
    assert svc.poll() == [("suspect", 1)]
    assert svc.state_of(1).suspect_reason == "deaf"
    assert svc.alive_slots() == [0]          # unroutable
    assert sorted(svc.present_slots()) == [0, 1]  # but never kicked
    # beats keep flowing: deafness must NOT clear, NOR escalate to lost
    for b in (4, 5, 6):
        _beat_ack(t, 1, 9, b, 0)
        assert svc.poll() == []
        assert svc.state_of(1).state == "suspect"
    # the ingress heals: the next beat carries the ack → clear
    _beat_ack(t, 1, 9, 7, 3)
    assert svc.poll() == [("clear", 1)]
    assert svc.state_of(1).state == "alive"
    assert svc.state_of(1).suspect_reason is None


def test_deaf_member_never_lost_while_beating_even_past_grace():
    """The invariant under tight polling: a poll landing BETWEEN two
    heartbeats of a deaf member must never read as silence — deafness
    alone never escalates to lost, however long it lasts relative to
    the suspect grace."""
    t = FlakyTable(2)
    svc = mb.MembershipService(t, 2, lease_s=0.3, suspect_grace_s=0.02,
                               deaf_ack_s=0.03)
    _beat_ack(t, 0, 7, 1, 0)
    _beat_ack(t, 1, 9, 1, 0)
    svc.poll()
    svc.publish_control(epoch=2, width=2, alive_mask=3)
    time.sleep(0.06)
    _beat_ack(t, 0, 7, 2, 2)
    _beat_ack(t, 1, 9, 2, 0)
    assert svc.poll() == [("suspect", 1)]
    deadline = time.monotonic() + 0.4
    beat = 3
    while time.monotonic() < deadline:
        # beats keep flowing; MANY polls land between them (the
        # grace, 20ms, elapses many times over)
        for _ in range(4):
            assert svc.poll() == []
            time.sleep(0.02)
        _beat_ack(t, 0, 7, beat, 2)
        _beat_ack(t, 1, 9, beat, 0)
        beat += 1
    assert svc.state_of(1).state == "suspect"
    assert svc.state_of(1).suspect_reason == "deaf"
    assert svc.poll() == []  # absorb the loop's final beat write
    # and when its beats REALLY stop, silence escalates normally
    time.sleep(0.35)  # past lease_s: reclassified to beats_stopped
    _beat_ack(t, 0, 7, 99, 2)  # slot 0 stays healthy throughout
    assert svc.poll() == []
    assert svc.state_of(1).suspect_reason == "beats_stopped"
    time.sleep(0.05)  # past the (restarted) grace
    _beat_ack(t, 0, 7, 100, 2)
    assert svc.poll() == [("lost", 1)]


def test_fresh_joiner_is_not_instantly_deaf():
    """The deaf bound measures time the MEMBER had to ack: a
    replacement joining long after the epoch was published gets its own
    deaf_ack_s window before suspicion, instead of being suspected on
    its first beat advance."""
    t = FlakyTable(2)
    svc = mb.MembershipService(t, 2, lease_s=10.0, suspect_grace_s=10.0,
                               deaf_ack_s=0.05)
    _beat_ack(t, 0, 7, 1, 0)
    svc.poll()
    svc.publish_control(epoch=2, width=2, alive_mask=3)
    _beat_ack(t, 0, 7, 2, 2)
    svc.poll()
    time.sleep(0.08)           # well past deaf_ack_s since publication
    _beat_ack(t, 1, 9, 1, 0)   # the replacement joins only NOW
    assert svc.poll() == [("join", 1)]
    _beat_ack(t, 1, 9, 2, 0)   # first beat advance, ack still pending
    assert svc.poll() == []    # inside ITS OWN window: not deaf yet
    assert svc.state_of(1).state == "alive"
    time.sleep(0.08)           # its window elapses without an ack
    _beat_ack(t, 1, 9, 3, 0)
    assert svc.poll() == [("suspect", 1)]
    assert svc.state_of(1).suspect_reason == "deaf"


def test_deaf_detection_disabled_by_default():
    """Membership planes whose members never ack epochs (the serving
    pool's blackboard) must not all read as deaf: deaf_ack_s=None is
    the default and disables the bound entirely."""
    t = FlakyTable(1)
    svc = mb.MembershipService(t, 1, lease_s=10.0, suspect_grace_s=10.0)
    _beat_ack(t, 0, 5, 1, 0)
    assert svc.poll() == [("join", 0)]
    svc.publish_control(epoch=4, width=1, alive_mask=1)
    time.sleep(0.1)
    _beat_ack(t, 0, 5, 2, 0)   # never acks; still fine
    assert svc.poll() == []
    assert svc.state_of(0).state == "alive"


def test_deaf_clock_starts_at_epoch_publication():
    """The deaf clock measures time since the EPOCH was first
    published, not since the member joined — re-publishes of the same
    epoch (phase flips, set_slow) must not restart it."""
    t = FlakyTable(1)
    svc = mb.MembershipService(t, 1, lease_s=10.0, suspect_grace_s=10.0,
                               deaf_ack_s=0.06)
    _beat_ack(t, 0, 5, 1, 0)
    svc.poll()
    svc.publish_control(epoch=2, width=1, alive_mask=1)
    time.sleep(0.08)
    # same epoch re-published (a set_slow-style rewrite): no clock reset
    svc.publish_control(epoch=2, width=1, alive_mask=1, phase=1)
    _beat_ack(t, 0, 5, 2, 0)
    assert svc.poll() == [("suspect", 0)]
    assert svc.state_of(0).suspect_reason == "deaf"


# ---------------------------------------------------------------------------
# control_rpc under 100% drop: bounded, link-named (ISSUE 10 satellite)
# ---------------------------------------------------------------------------

def test_control_rpc_names_op_and_link_on_exhaustion():
    def always():
        raise ConnectionError("wire down")

    with pytest.raises(mb.MembershipWireError) as ei:
        mb.control_rpc(always, attempts=3, base_s=0.001,
                       op="heartbeat", link="member0->van")
    msg = str(ei.value)
    assert "heartbeat" in msg and "member0->van" in msg
    assert "3 attempts" in msg
    assert isinstance(ei.value.__cause__, ConnectionError)


def test_control_rpc_wall_clock_cap():
    """deadline_s bounds TOTAL wall-clock (attempts + backoff), so a
    fully partitioned link costs a bounded, predictable period per
    rpc — not a full exponential ladder."""
    def always():
        raise ConnectionError("drop")

    t0 = time.monotonic()
    with pytest.raises(mb.MembershipWireError):
        mb.control_rpc(always, attempts=50, base_s=0.2, max_s=5.0,
                       deadline_s=0.3, link="member1->van")
    assert time.monotonic() - t0 < 1.5


@pytest.mark.slow
def test_heartbeat_under_total_drop_surfaces_named_timeout():
    """The regression the satellite asks for, end-to-end on a REAL van:
    a member behind a 100%-drop egress link gets a clear, link-named
    MembershipWireError from heartbeat() within a bounded wall-clock —
    not an unbounded hang, not a bare ConnectionError."""
    if not available():
        pytest.skip("native PS lib unavailable")
    from hetu_tpu.ps import van
    port = van.serve(0)
    em = None
    try:
        table_id = mb.fresh_table_id()
        bb = mb.create_blackboard("127.0.0.1", port, table_id=table_id,
                                  n_slots=1)
        client = mb.MembershipClient("127.0.0.1", port,
                                     table_id=table_id, slot=0,
                                     n_slots=1, rpc_deadline_s=1.0)
        client.join()
        em = ne.NetEm(local="member0", peer="van", seed=3).install()
        em.set_link(ne.LinkPolicy(drop_p=1.0), direction="egress")
        t0 = time.monotonic()
        with pytest.raises(mb.MembershipWireError) as ei:
            client.heartbeat()
        assert time.monotonic() - t0 < 5.0
        assert "member0->van" in str(ei.value)
        em.clear()
        client.heartbeat()  # healed link: back to normal
        client.close()
        bb.close()
    finally:
        if em is not None:
            em.uninstall()
        van.stop()


# ---------------------------------------------------------------------------
# auto drain codec: the crossover model + measured link rate
# ---------------------------------------------------------------------------

def test_pick_codec_crossover_model():
    from hetu_tpu.serve.migrate import pick_codec
    MB = 1_000_000
    # no rate evidence, or loopback-fast: compression only burns CPU
    assert pick_codec(None, 8 * MB, "float32") == "none"
    assert pick_codec(10_000.0, 1 * MB, "float32") == "none"
    # f32 cache over a slow link: int8's 4x is the measured winner
    assert pick_codec(100.0, 8 * MB, "float32") == "int8"
    # bf16 cache: bf16 is bit-lossless at 2x once transfer costs time
    assert pick_codec(400.0, 8 * MB, "bfloat16") == "bf16"
    # ...and escalates to int8 in the preemption-deadline regime
    assert pick_codec(20.0, 8 * MB, "bfloat16") == "int8"


def test_measured_link_mbps_from_bulk_transfers():
    """The rate signal comes ONLY from completed bulk payload sends
    (send_payload records migrate.wire.mbps_last); with no bulk
    evidence there is no number — tiny ack-paced control frames must
    never masquerade as a link measurement."""
    from hetu_tpu.serve.migrate import measured_link_mbps
    from hetu_tpu.telemetry.registry import MetricsRegistry
    reg = MetricsRegistry()
    assert measured_link_mbps(reg) is None  # no evidence, no number
    reg.gauge("migrate.wire.mbps_last").set(80.0)
    assert measured_link_mbps(reg) == pytest.approx(80.0)


def test_send_payload_records_bulk_rate():
    """A real >=64KB chunked send over a van blob channel leaves the
    rate sample the auto codec consults."""
    if not available():
        pytest.skip("native PS lib unavailable")
    import threading

    from hetu_tpu.ps import van
    from hetu_tpu.serve.migrate import (
        measured_link_mbps, recv_payload, send_payload,
    )
    from hetu_tpu.telemetry import default_registry
    default_registry.gauge("migrate.wire.mbps_last").set(0.0)
    port = van.serve(0)
    try:
        tx = van.BlobChannel("127.0.0.1", port, 0x52415445)
        rx = van.BlobChannel("127.0.0.1", port, 0x52415445)
        payload = bytes(bytearray(200_000))
        t = threading.Thread(target=send_payload, args=(tx, payload),
                             kwargs={"chunk_bytes": 64_000}, daemon=True)
        t.start()
        got = recv_payload(rx)
        t.join(30)
        assert got == payload
        rate = measured_link_mbps()
        assert rate is not None and rate > 0
        tx.close()
        rx.close()
    finally:
        van.stop()


@pytest.mark.slow
def test_pool_drain_codec_auto_end_to_end():
    """`drain_member(codec="auto")` — the PR 7/PR 8 ROADMAP residual:
    the pool accepts the auto policy at construction AND per drain,
    resolves it from the link rate at drain time, and the drain's
    migrated requests stay token-identical."""
    if not available():
        pytest.skip("native PS lib unavailable")
    import jax

    from hetu_tpu.models.gpt import GPTConfig, GPTModel
    from hetu_tpu.serve import PagedServeEngine, ServingPool
    from hetu_tpu.serve.scheduler import Request
    from paged_programs import submit_and_hold_mid_decode
    model = GPTModel(GPTConfig(
        vocab_size=97, hidden_size=64, num_layers=2, num_heads=4,
        ffn_size=128, max_position=64, dropout_rate=0.0))
    variables = model.init(jax.random.PRNGKey(0))

    def factory():
        return PagedServeEngine(model, variables, num_slots=4, max_len=48,
                                page_size=8, min_bucket=8)

    pool = ServingPool({"a": factory, "b": factory},
                       migrate_codec="auto", start_poll=False)
    em = ne.NetEm(seed=0).install()
    try:
        reqs = [Request(prompt=[3, 1, 4, 1, 5], max_tokens=12,
                        timeout_s=60.0),
                Request(prompt=[2, 7, 1, 8], max_tokens=12,
                        timeout_s=60.0)]
        submit_and_hold_mid_decode(pool.members["a"], reqs)
        # an emulated slow link: auto must pick the compressed codec,
        # and the drain still completes token-exact on the peer
        em.set_link(ne.LinkPolicy(rate_mbps=0.001), direction="ingress")
        pool.drain_member("a")
        for r in reqs:
            assert r.done.wait(60) and r.status == "ok"
    finally:
        em.uninstall()
        pool.close()


def test_resolve_codec_prefers_netem_visible_rate():
    """With a netem bandwidth cap installed, resolve_codec uses the
    emulator's known rate — no op-span traffic needed."""
    if not available():
        pytest.skip("native PS lib unavailable")
    import jax

    from hetu_tpu.models.gpt import GPTConfig, GPTModel
    from hetu_tpu.serve.engine import PagedServeEngine
    from hetu_tpu.serve.migrate import resolve_codec
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                    num_heads=2, ffn_size=64, max_position=32,
                    dropout_rate=0.0)
    model = GPTModel(cfg)
    engine = PagedServeEngine(model, model.init(jax.random.PRNGKey(0)),
                              num_slots=2, max_len=32)
    slot = engine.alloc_slot()
    engine.prefill(slot, [1, 2, 3, 4, 5, 6, 7, 8])
    em = ne.NetEm(seed=0).install()
    try:
        em.set_link(ne.LinkPolicy(rate_mbps=0.001), direction="egress")
        # an absurdly slow emulated link: even this small payload takes
        # seconds — auto must pick the compressed codec
        assert resolve_codec("auto", engine) == "int8"
        em.clear()
        # no cap, no measured traffic: auto stays uncompressed
        assert resolve_codec("auto", engine) == "none"
        assert resolve_codec("bf16", engine) == "bf16"  # passthrough
        with pytest.raises(ValueError):
            resolve_codec("gzip", engine)
    finally:
        em.uninstall()
        engine.release(slot)
