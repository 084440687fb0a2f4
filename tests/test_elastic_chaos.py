"""Durable-slot chaos: SIGKILL a real PS shard server mid-training and
prove the resurrected shard resumes with BITWISE-identical server-side
optimizer accumulators (not fresh zeros).  Marked slow + chaos + elastic
(multi-process, wall-clock); the in-process elastic tests live in tests/test_elastic.py.
"""

import time

import numpy as np
import pytest

pytestmark = [pytest.mark.slow, pytest.mark.chaos, pytest.mark.elastic]

from hetu_tpu.ps import available

if not available():  # pragma: no cover
    pytest.skip("native PS lib unavailable", allow_module_level=True)

from hetu_tpu.ps import van
from hetu_tpu.resilience import PSShardGuard
from hetu_tpu.resilience.shardproc import free_port, spawn_shard_server

ROWS, DIM = 16, 4


@pytest.fixture
def two_servers(tmp_path):
    ports = [free_port(), free_port()]
    procs = [spawn_shard_server(tmp_path, p, f"s{i}")
             for i, p in enumerate(ports)]
    yield ports, procs
    for p in procs:
        p.kill()
        p.wait()


def _adam_table(ports, table_id):
    return van.PartitionedPSTable(
        [("127.0.0.1", p) for p in ports], rows=ROWS, dim=DIM,
        init="zeros", optimizer="adam", lr=0.01, table_id=table_id,
        heartbeat_ms=100)


def test_killed_shard_resumes_with_bitwise_identical_slots(two_servers,
                                                           tmp_path):
    """Same pushes into a guarded table and a control table; SIGKILL the
    guarded table's shard 1 after the snapshot; after repair, weights AND
    Adam m/v/step on the resurrected shard equal the control's BITWISE.
    Without the slot replay the accumulators would restart at zero (the
    pre-PR behavior this test exists to rule out)."""
    ports, procs = two_servers
    t = _adam_table(ports, table_id=951)
    control = _adam_table(ports, table_id=952)

    idx = np.arange(ROWS, dtype=np.int64)
    g = np.random.default_rng(3).standard_normal((ROWS, DIM)) \
        .astype(np.float32)
    for k in range(5):  # build up real momentum/variance state
        t.sparse_push(idx, g * (k + 1))
        control.sparse_push(idx, g * (k + 1))

    guard = PSShardGuard(t, snapshot_path=tmp_path / "snap.npz")
    assert guard.slots  # the table exposes the slot plane
    guard.snapshot()

    shard1 = np.arange(8, 16, dtype=np.int64)
    want_w = control.sparse_pull(shard1)
    want_s1, want_s2, want_step = control.slots_get(shard1)
    assert (want_step == 5).all()
    assert np.abs(want_s1).sum() > 0 and np.abs(want_s2).sum() > 0
    # the control's 6th step happens BEFORE the kill (the same server
    # hosts both tables' shard 1, so the control dies too): this is the
    # ground-truth "never-killed" trajectory the repaired table must
    # rejoin bitwise
    control.sparse_push(shard1, g[8:])
    want_w6 = control.sparse_pull(shard1)
    want_s1_6, want_s2_6, want_step_6 = control.slots_get(shard1)

    procs[1].kill()
    procs[1].wait()
    # wait until the heartbeat notices the death, then resurrect
    deadline = time.monotonic() + 30
    while all(t.alive) and time.monotonic() < deadline:
        time.sleep(0.05)
    procs[1] = spawn_shard_server(tmp_path, ports[1], "r1")
    while guard.repairs == 0:
        assert time.monotonic() < deadline, "shard never repaired"
        guard.poll()
        time.sleep(0.05)

    np.testing.assert_array_equal(t.sparse_pull(shard1), want_w)
    got_s1, got_s2, got_step = t.slots_get(shard1)
    np.testing.assert_array_equal(got_s1, want_s1)   # bitwise m
    np.testing.assert_array_equal(got_s2, want_s2)   # bitwise v
    np.testing.assert_array_equal(got_step, want_step)

    # and training RESUMES from those accumulators identically: the same
    # 6th push lands the repaired table exactly on the never-killed
    # trajectory — weights AND accumulators bitwise
    t.sparse_push(shard1, g[8:])
    np.testing.assert_array_equal(t.sparse_pull(shard1), want_w6)
    got6 = t.slots_get(shard1)
    np.testing.assert_array_equal(got6[0], want_s1_6)
    np.testing.assert_array_equal(got6[1], want_s2_6)
    np.testing.assert_array_equal(got6[2], want_step_6)
    t.close()
    control.close()


def test_slot_snapshot_persists_and_reloads(two_servers, tmp_path):
    """A guard rebuilt from its persisted snapshot file (the
    preempted-and-resumed worker path) still repairs slots."""
    ports, procs = two_servers
    t = _adam_table(ports, table_id=953)
    idx = np.arange(ROWS, dtype=np.int64)
    g = np.random.default_rng(5).standard_normal((ROWS, DIM)) \
        .astype(np.float32)
    t.sparse_push(idx, g)
    guard = PSShardGuard(t, snapshot_path=tmp_path / "snap.npz")
    guard.snapshot()
    s1, s2, st = t.slots_get(idx)

    # a NEW guard (fresh process) loads the persisted slot snapshot
    guard2 = PSShardGuard(t, snapshot_path=tmp_path / "snap.npz")
    assert guard2._have_slots == {0, 1}
    np.testing.assert_array_equal(guard2._snap_s1, s1)
    np.testing.assert_array_equal(guard2._snap_s2, s2)
    np.testing.assert_array_equal(guard2._snap_step, st)
    t.close()
