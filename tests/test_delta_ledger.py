"""Append-only delta ledger (ISSUE 15).

The serving accept path used to journal a FULL controller snapshot per
accept — O(inflight) bytes behind one lock with a hard refuse-accepts
cliff at the table's capacity.  ``DeltaLedger`` replaces it: accept/
resolve/route records append in one atomic frame each (O(record)
bytes), a full delta region triggers COMPACTION (the current state
becomes the new base, one amortized atomic frame), and a reader at any
instant — including a takeover racing a compaction — sees either the
old base + old deltas or the new base, never a torn mix.

All fast lane: the ledger runs over an in-memory fake table (the codec
and the atomic-frame geometry are the unit under test; the van's
per-table mutex supplies the frame atomicity these tests assume, as
pinned by the real-van runs in test_vanchaos.py).
"""

import threading

import numpy as np
import pytest

from hetu_tpu.ps import membership as mb

pytestmark = pytest.mark.vanchaos


class FakeLedgerTable:
    """In-memory stand-in; a lock makes each sparse_set/pull atomic like
    the van server's per-table mutex."""

    def __init__(self, rows, dim):
        self.rows = np.zeros((rows, dim), np.float32)
        self._mu = threading.Lock()

    def sparse_set(self, idx, vals):
        with self._mu:
            self.rows[np.asarray(idx, int)] = np.asarray(vals,
                                                         np.float32)

    def sparse_pull(self, idx):
        with self._mu:
            return self.rows[np.asarray(idx, int)].copy()


def _ledger(rows=64, dim=16, **kw):
    return mb.DeltaLedger(table=FakeLedgerTable(rows, dim), rows=rows,
                          dim=dim, **kw)


def _fresh_reader(led):
    """A takeover-style second handle on the SAME table."""
    out = mb.DeltaLedger(table=led._table, rows=led.rows, dim=led.dim,
                         base_rows=led.base_rows, create=False)
    return out


def _replay(got):
    """The test-side replay: base requests + accept/resolve deltas →
    the final request set (mirrors the pool's ``_replay_ledger``)."""
    reqs = dict((got["state"].get("requests") or {}))
    resolved = dict((got["state"].get("resolved") or {}))
    for d in got["deltas"]:
        if "a" in d:
            reqs[str(int(d["a"][0]))] = {"msg": d["a"][1]}
        elif "r" in d:
            reqs.pop(str(int(d["r"][0])), None)
            resolved[str(int(d["r"][0]))] = d["r"][1]
    return reqs, resolved


def test_append_read_roundtrip_and_fresh_reader():
    led = _ledger()
    led.append({"a": [1, {"prompt": [1, 2, 3], "s": "π∂η"}]},
               ctrl_inc=1)
    led.append([{"o": [1, 0, 0]}, {"r": [1, "ok"]}], ctrl_inc=1)
    got = led.read()
    assert got["state"] == {}
    assert len(got["deltas"]) == 3
    assert got["deltas"][0]["a"][1]["s"] == "π∂η"
    # a fresh handle (the takeover path) reads the identical log
    assert _fresh_reader(led).read()["deltas"] == got["deltas"]


def test_uninitialized_table_reads_none():
    led = mb.DeltaLedger(table=FakeLedgerTable(64, 16), rows=64, dim=16,
                         create=False)
    assert led.read() is None


def test_append_is_o_delta_not_o_inflight():
    """The acceptance counter-assertion: with a LARGE inflight state,
    one accept's ledger write is proportional to the record, not to
    everything in flight."""
    from hetu_tpu.telemetry import default_registry
    led = _ledger(rows=4096, dim=32)
    # a fat base: 300 inflight requests (~ the old per-accept cost)
    state = {"requests": {str(i): {"msg": {"prompt": list(range(8))}}
                          for i in range(300)}}
    led.compact(state, ctrl_inc=1)
    c = default_registry.counter("ledger.delta_bytes")
    before = c.value
    led.append({"a": [1000, {"prompt": [1, 2, 3]}]}, ctrl_inc=1)
    per_accept = c.value - before
    import json
    state_bytes = len(json.dumps(state).encode())
    # header row + a couple of record rows << the inflight state
    assert per_accept <= 4 * led.dim * 4, per_accept
    assert per_accept * 10 < state_bytes, (per_accept, state_bytes)


def test_sustained_accepts_past_snapshot_cliff_zero_refusals():
    """Sustained accept/resolve traffic whose CUMULATIVE journal volume
    is far past the old ~64KB snapshot capacity: zero refusals — a full
    delta region compacts (caller-triggered, as the pool does) and the
    log continues."""
    led = _ledger(rows=128, dim=16)
    inflight, resolved, compactions, journaled = {}, {}, 0, 0
    for i in range(1, 1200):
        rec = {"a": [i, {"prompt": list(range(10))}]}
        inflight[str(i)] = {"msg": rec["a"][1]}
        recs = [rec]
        if len(inflight) > 6:
            rid = min(inflight, key=int)
            del inflight[rid]
            resolved[rid] = "ok"
            while len(resolved) > 16:
                resolved.pop(min(resolved, key=int))
            recs.append({"r": [int(rid), "ok"]})
        state = {"requests": dict(inflight), "resolved": dict(resolved)}
        try:
            led.append(recs, ctrl_inc=1)
        except mb.LedgerCompactionNeeded:
            led.compact(state, ctrl_inc=1)
            led.append(recs, ctrl_inc=1)
            compactions += 1
        journaled += sum(len(str(r)) for r in recs)
    assert journaled > 64 * 1024  # well past the old cliff
    assert compactions >= 3
    reqs, res = _replay(led.read())
    assert set(reqs) == set(inflight)


def test_takeover_mid_compaction_restores_exact_request_set():
    """A reader (the takeover) interleaved at EVERY point around a
    compaction sees the exact same request set: before (old base +
    deltas), after (new base), and — thanks to the one-frame write —
    never a torn mix."""
    led = _ledger(rows=64, dim=16)
    inflight = {}
    for i in range(1, 9):
        inflight[str(i)] = {"msg": {"prompt": [i]}}
        led.append({"a": [i, {"prompt": [i]}]}, ctrl_inc=1)
    led.append({"r": [3, "ok"]}, ctrl_inc=1)
    del inflight["3"]
    want = set(inflight)
    before, _ = _replay(_fresh_reader(led).read())
    assert set(before) == want
    led.compact({"requests": dict(inflight)}, ctrl_inc=1)
    after, _ = _replay(_fresh_reader(led).read())
    assert set(after) == want
    # and post-compaction deltas replay on the new base
    led.append({"a": [9, {"prompt": [9]}]}, ctrl_inc=1)
    got, _ = _replay(_fresh_reader(led).read())
    assert set(got) == want | {"9"}


class HookedPulls:
    """The reader's view of the writer's table: ``before_pull(n)`` runs
    ahead of the reader's n-th ``sparse_pull`` (0 is the header probe),
    which is where a concurrent writer's frame would land."""

    def __init__(self, inner, before_pull):
        self._inner, self._before_pull, self.pulls = inner, before_pull, 0

    def sparse_pull(self, idx):
        self._before_pull(self.pulls)
        self.pulls += 1
        return self._inner.sparse_pull(idx)


@pytest.mark.parametrize("lands_before, compacts, pulls, gives_up", [
    pytest.param(lambda n: n == 1, False, 3, False, id="append_after_probe"),
    pytest.param(lambda n: n == 1, True, 2, False, id="compact_after_probe"),
    pytest.param(lambda n: n >= 1, False, 9, True,
                 id="append_before_every_pull"),
])
def test_concurrent_reader_never_sees_torn_state(lands_before, compacts,
                                                 pulls, gives_up):
    """The seqlock, stepped by hand: the writer's frame lands between the
    reader's header probe and its big pull, in each way ``read``'s
    two-pull protocol can meet.  A head that grew costs one retry, a head
    that fell back to a new base none, and the read yields the request set
    the writer had at that instant; behind a writer that never pauses the
    8 attempts run out, which is the designed refusal."""
    led = _ledger(rows=64, dim=16)
    inflight = {}

    def accept(i):
        inflight[str(i)] = {"msg": {"p": [i]}}
        led.append({"a": [i, {"p": [i]}]}, ctrl_inc=1)

    for i in range(1, 6):
        accept(i)
    ids = iter(range(6, 64))

    def before_pull(n):
        if not lands_before(n):
            return
        if compacts:
            led.append({"r": [1, "ok"]}, ctrl_inc=1)
            del inflight["1"]
            led.compact({"requests": dict(inflight)}, ctrl_inc=1)
        else:
            accept(next(ids))

    r = _fresh_reader(led)
    r._table = hooked = HookedPulls(led._table, before_pull)
    if gives_up:
        with pytest.raises(RuntimeError, match="quiescent header"):
            r.read()
        r._table = led._table  # the writer pauses: the next read lands
    got = r.read()
    assert hooked.pulls == pulls  # the probe, then each attempt
    assert got["compactions"] == int(compacts)
    # the new base comes with zero deltas, never old deltas on a new base:
    # the old region's resolve record is not replayed
    assert len(got["deltas"]) == (0 if compacts else len(inflight))
    reqs, resolved = _replay(got)
    assert set(reqs) == set(inflight)
    assert resolved == {}


def test_append_is_fenced_and_successor_geometry_adopted():
    led = _ledger()
    led.append({"a": [1, {}]}, ctrl_inc=5)
    zombie = _fresh_reader(led)
    zombie.read()
    led.append({"a": [2, {}]}, ctrl_inc=7)  # the successor writes
    with pytest.raises(mb.ControllerFenced):
        zombie.append({"a": [3, {}]}, ctrl_inc=5)
    # the successor's own handle keeps appending freely
    led.append({"a": [4, {}]}, ctrl_inc=7)
    assert len(led.read()["deltas"]) == 3


def test_compact_rejects_oversize_base():
    led = _ledger(rows=32, dim=8)
    with pytest.raises(ValueError, match="base capacity"):
        led.compact({"blob": "x" * 4096}, ctrl_inc=1)


def test_needs_compaction_margin():
    led = _ledger(rows=64, dim=16)
    assert not led.needs_compaction()
    while True:
        try:
            led.append({"a": [1, {"p": list(range(12))}]}, ctrl_inc=1)
        except mb.LedgerCompactionNeeded:
            break
    assert led.needs_compaction(margin_rows=1)


@pytest.mark.slow
@pytest.mark.chaos
def test_compaction_racing_van_failover_restores_exact_request_set(
        tmp_path):
    """A compaction issued while the primary van is ALREADY DEAD rides
    the replica's promotion dance (the append-path retry ladder drives
    the CAS) and lands — atomically — on the promoted backup.  Every
    takeover-style reader along the way replays the exact request set:
    before the kill (old base + sync-replicated deltas on the backup),
    after the raced compaction (new base, zero deltas), and after
    post-compaction appends (new base + fresh deltas)."""
    from hetu_tpu.ps import available
    if not available():
        pytest.skip("native hetu_ps lib not built")
    from hetu_tpu.ps.replica import ReplicaSpec, VanReplica
    from hetu_tpu.resilience.shardproc import (free_port,
                                               spawn_shard_server)

    p1, p2 = free_port(), free_port()
    v1 = spawn_shard_server(tmp_path, p1, tag="prim")
    v2 = spawn_shard_server(tmp_path, p2, tag="back")
    rep = None
    try:
        spec = {"endpoints": [["127.0.0.1", p1], ["127.0.0.1", p2]],
                "epoch_table": mb.fresh_table_id(),
                "promote_after_s": 0.1, "rcv_timeout_s": 1.5,
                "revalidate_s": 0.05}
        rep = VanReplica.from_spec(spec, bootstrap=True)
        tid = mb.fresh_table_id()
        led = mb.DeltaLedger(replica=rep, table_id=tid, rows=64,
                             dim=16)
        inflight = {}
        for i in range(1, 9):
            inflight[str(i)] = {"msg": {"prompt": [i]}}
            led.append({"a": [i, {"prompt": [i]}]}, ctrl_inc=1)
        led.append({"r": [3, "ok"]}, ctrl_inc=1)
        del inflight["3"]
        want = set(inflight)

        v1.kill()
        v1.wait()

        # the raced compaction: its fence read + one-frame write hit
        # the corpse, the retry ladder promotes, the frame lands on
        # the survivor
        led.compact({"requests": dict(inflight)}, ctrl_inc=1)
        assert rep.incarnation == 2 and rep.primary[1] == p2

        def takeover_read():
            # DIRECT construction: from_spec caches per-process, and a
            # takeover must start from a fresh (pre-failover) view and
            # discover the promotion itself
            r2 = VanReplica(ReplicaSpec.from_dict(spec))
            r2.refresh()
            l2 = mb.DeltaLedger(replica=r2, table_id=tid, rows=64,
                                dim=16, create=False)
            try:
                return l2.read()
            finally:
                l2.close()
        got = takeover_read()
        assert got["compactions"] == 1 and got["deltas"] == []
        after, _ = _replay(got)
        assert set(after) == want

        # post-compaction deltas replay over the new base on the
        # promoted van
        led.append({"a": [9, {"prompt": [9]}]}, ctrl_inc=1)
        led.append({"r": [5, "ok"]}, ctrl_inc=1)
        want = (want - {"5"}) | {"9"}
        final, resolved = _replay(takeover_read())
        assert set(final) == want and "5" in resolved
        led.close()
    finally:
        if rep is not None:
            try:
                rep.close()
            except Exception:
                pass
        for v in (v1, v2):
            if v.poll() is None:
                v.kill()
                v.wait()
