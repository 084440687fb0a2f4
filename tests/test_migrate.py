"""Live KV-cache slot migration: token-for-token parity with ZERO
re-prefill on the receiving engine (GPT and GQA-Llama), loud geometry
rejection with nothing partially adopted, CRC-checked wire framing, and
source-side rollback on a failed transfer.

The contract under test (ISSUE 5 acceptance): a request migrated
mid-decode produces argmax tokens identical to the same request never
migrated, and the receiving engine performs zero prefill steps for
migrated slots (the ``prefill_tokens`` metric stays flat).
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.models.gpt import GPTConfig, GPTModel
from hetu_tpu.models.llama import LlamaConfig, LlamaModel
from hetu_tpu.serve import (
    ContinuousBatchingScheduler, MigrationError, PagedServeEngine, Request,
)
from hetu_tpu.serve import migrate as mg
from paged_programs import ref_greedy as _ref_greedy

pytestmark = pytest.mark.migrate


@pytest.fixture(scope="module")
def gpt():
    m = GPTModel(GPTConfig(
        vocab_size=97, hidden_size=64, num_layers=2, num_heads=4,
        ffn_size=128, max_position=64, dropout_rate=0.0))
    return m, m.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def llama():
    m = LlamaModel(LlamaConfig(
        vocab_size=97, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, ffn_size=96, max_position=64))
    return m, m.init(jax.random.PRNGKey(1))


def _engine(model, variables, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_len", 48)
    kw.setdefault("min_bucket", 8)
    kw.setdefault("page_size", 8)
    return PagedServeEngine(model, variables, **kw)


def _migrate_mid_decode(model, variables, prompt, n_total, n_before,
                        *, via_wire: bool = False):
    """Decode ``n_before`` tokens on a source engine, migrate the live
    slot to a fresh peer, decode the rest there; returns (tokens,
    dst_engine)."""
    src = _engine(model, variables)
    dst = _engine(model, variables)
    slot = src.alloc_slot()
    toks = [src.prefill(slot, prompt)]
    for _ in range(n_before - 1):
        toks.append(src.decode()[slot])
    snaps = src.export_slots([slot])
    if via_wire:
        payload = mg.pack(src.cache.spec, snaps)
        spec_d, snaps, _ = mg.unpack(payload)
        mg.check_spec(dst.cache.spec, spec_d)
    slot_map = dst.adopt_slots(snaps)
    src.release(slot)
    new = slot_map[slot]
    for _ in range(n_total - n_before):
        toks.append(dst.decode()[new])
    return toks, dst


# ---- migration parity (the tentpole contract) ----

@pytest.mark.parametrize("n_before", [1, 4])
def test_gpt_migrated_decode_parity_zero_prefill(gpt, n_before):
    model, variables = gpt
    prompt = [3, 14, 15, 9, 2, 6]
    toks, dst = _migrate_mid_decode(model, variables, prompt, 10, n_before)
    assert toks == _ref_greedy(model, variables, prompt, 10)
    # the receiving engine NEVER prefilled: its prefill metrics stay flat
    assert dst.metrics.count("prefill_tokens") == 0
    assert dst.metrics.count("prefill_compiles") == 0


def test_llama_gqa_migrated_decode_parity(llama):
    model, variables = llama
    assert model.c.num_kv_heads < model.c.num_heads  # really GQA
    prompt = [7, 3, 1, 88]
    toks, dst = _migrate_mid_decode(model, variables, prompt, 9, 3)
    assert toks == _ref_greedy(model, variables, prompt, 9)
    assert dst.metrics.count("prefill_tokens") == 0


def test_parity_through_packed_wire_payload(gpt):
    """Same contract with the K/V rows serialized through the full
    pack → unpack → check_spec wire path (array round-trip included)."""
    model, variables = gpt
    prompt = [5, 6, 7]
    toks, dst = _migrate_mid_decode(model, variables, prompt, 8, 2,
                                    via_wire=True)
    assert toks == _ref_greedy(model, variables, prompt, 8)
    assert dst.metrics.count("prefill_tokens") == 0


# ---- geometry/dtype gating: loud errors, nothing partially adopted ----

def test_geometry_mismatch_errors_loudly_adopts_nothing(gpt, llama):
    gm, gv = gpt
    lm, lv = llama
    src = _engine(gm, gv)
    dst = _engine(lm, lv)  # 2 kv heads vs GPT's 4: incompatible
    slot = src.alloc_slot()
    src.prefill(slot, [1, 2, 3])
    snaps = src.export_slots([slot])
    free_before = dst.cache.num_free
    with pytest.raises(ValueError, match="mismatch"):
        dst.adopt_slots(snaps)
    assert dst.cache.num_free == free_before  # no partial adoption
    # the wire-level gate rejects the same pairing before any array work
    payload = mg.pack(src.cache.spec, snaps)
    spec_d, _, _ = mg.unpack(payload)
    with pytest.raises(MigrationError, match="geometry mismatch"):
        mg.check_spec(dst.cache.spec, spec_d)


def test_snapshot_longer_than_peer_max_len_rejected(gpt):
    model, variables = gpt
    src = _engine(model, variables, max_len=48)
    dst = _engine(model, variables, max_len=8)
    slot = src.alloc_slot()
    src.prefill(slot, list(range(1, 11)))  # 10 cached tokens
    snaps = src.export_slots([slot])
    with pytest.raises(ValueError, match="room to decode"):
        dst.adopt_slots(snaps)
    assert dst.cache.num_free == dst.cache.num_slots


def test_export_validates_slot_state(gpt):
    model, variables = gpt
    eng = _engine(model, variables)
    with pytest.raises(ValueError):  # free slot: nothing to export
        eng.cache.export_slots([0])
    slot = eng.alloc_slot()
    with pytest.raises(ValueError):  # allocated but never prefilled
        eng.export_slots([slot])


def test_exported_slots_suspend_until_released_or_resumed(gpt):
    """The wire transfer runs outside any lock: a decode step landing in
    that window (straggler admission on the draining source) must NOT
    advance exported slots — those tokens are in no request's record and
    a rollback could never recover them.  Export = suspend;
    ``resume_slots`` = the rollback half."""
    model, variables = gpt
    eng = _engine(model, variables)
    a = eng.alloc_slot()
    eng.prefill(a, [3, 1, 4])
    b = eng.alloc_slot()
    eng.prefill(b, [2, 7])
    len_a = int(eng.cache.lengths[a])
    eng.export_slots([a])
    out = eng.decode()  # the in-window decode step
    assert b in out and a not in out
    assert int(eng.cache.lengths[a]) == len_a  # untouched
    eng.resume_slots([a])
    out2 = eng.decode()  # rollback: resumes exactly where it stopped
    assert a in out2
    assert int(eng.cache.lengths[a]) == len_a + 1


# ---- wire format ----

def test_pack_unpack_roundtrip_with_records(gpt):
    model, variables = gpt
    eng = _engine(model, variables)
    slot = eng.alloc_slot()
    first = eng.prefill(slot, [4, 5, 6])
    req = Request(prompt=[4, 5, 6], max_tokens=9, eos_id=7, timeout_s=30.0)
    req.tokens = [first]
    req.submitted_at = __import__("time").monotonic() - 1.5
    snaps = eng.export_slots([slot])
    payload = mg.pack(eng.cache.spec, snaps,
                      records=[mg.request_record(req)])
    spec_d, snaps2, recs = mg.unpack(payload)
    assert spec_d["dtype"] == "float32"
    (s,) = snaps2
    np.testing.assert_array_equal(s.k, snaps[0].k)
    np.testing.assert_array_equal(s.v, snaps[0].v)
    assert s.meta["last_token"] == first
    (rec,) = recs
    got = mg.request_from_record(rec)
    assert got.prompt == [4, 5, 6] and got.tokens == [first]
    assert got.max_tokens == 9 and got.eos_id == 7
    assert 1.0 < __import__("time").monotonic() - got.submitted_at < 3.0


def test_corrupt_body_fails_clean(gpt):
    model, variables = gpt
    eng = _engine(model, variables)
    slot = eng.alloc_slot()
    eng.prefill(slot, [1, 2, 3, 4])
    payload = bytearray(mg.pack(eng.cache.spec, eng.export_slots([slot])))
    payload[-3] ^= 0xFF  # flip a K/V byte: body CRC must catch it
    with pytest.raises(MigrationError, match="CRC"):
        mg.unpack(bytes(payload))
    with pytest.raises(MigrationError, match="magic"):
        mg.unpack(b"JUNK" + bytes(payload[4:]))
    with pytest.raises(MigrationError):
        mg.unpack(bytes(payload[:10]))  # truncated header


class _ListChannel:
    """In-memory stand-in for a van BlobChannel (seq-keyed slots)."""

    def __init__(self, store):
        self.store = store

    def put(self, data, seq, *, timeout_s=None):
        self.store[seq] = bytes(data)

    def get(self, seq, *, timeout_s=None):
        return self.store[seq]


def test_chunked_frames_roundtrip_and_crc_detection():
    payload = np.random.default_rng(0).bytes(10_000)
    store: dict = {}
    ch = _ListChannel(store)
    nxt = mg.send_payload(ch, payload, chunk_bytes=1024)
    assert nxt - 1 == len(store) == 10  # ceil(10000/1024)
    assert mg.recv_payload(_ListChannel(store)) == payload
    # corrupt one chunk's payload: the per-chunk CRC catches it
    bad = dict(store)
    frame = bytearray(bad[4])
    frame[-1] ^= 0x01
    bad[4] = bytes(frame)
    with pytest.raises(MigrationError, match="CRC"):
        mg.recv_payload(_ListChannel(bad))
    # corrupt the framing header: caught before the CRC
    bad2 = dict(store)
    bad2[1] = b"\x00" * 30
    with pytest.raises(MigrationError, match="magic|header"):
        mg.recv_payload(_ListChannel(bad2))


# ---- wire compression (ISSUE 8: quantized KV migration codec) ----

@pytest.fixture(scope="module")
def gpt_bf16():
    m = GPTModel(GPTConfig(
        vocab_size=97, hidden_size=64, num_layers=2, num_heads=4,
        ffn_size=128, max_position=64, dropout_rate=0.0,
        dtype=jnp.bfloat16))
    return m, m.init(jax.random.PRNGKey(0))


def test_bf16_model_pack_unpack_roundtrip(gpt_bf16):
    """The `_np_dtype` ml_dtypes fallback (migrate.py:80): a bf16 cache's
    dtype name round-trips through the JSON header and back to a numpy
    dtype np.dtype() alone cannot resolve."""
    model, variables = gpt_bf16
    eng = _engine(model, variables)
    slot = eng.alloc_slot()
    eng.prefill(slot, [4, 5, 6, 7])
    snaps = eng.export_slots([slot])
    assert np.dtype(snaps[0].k.dtype) == mg._np_dtype("bfloat16")
    payload = mg.pack(eng.cache.spec, snaps)
    spec_d, snaps2, _ = mg.unpack(payload)
    assert spec_d["dtype"] == "bfloat16"
    (s,) = snaps2
    np.testing.assert_array_equal(np.asarray(s.k), np.asarray(snaps[0].k))
    np.testing.assert_array_equal(np.asarray(s.v), np.asarray(snaps[0].v))


def test_int8_codec_shrinks_and_bounds_error(gpt):
    model, variables = gpt
    eng = _engine(model, variables)
    slot = eng.alloc_slot()
    eng.prefill(slot, list(range(1, 25)))
    snaps = eng.export_slots([slot])
    raw = mg.pack(eng.cache.spec, snaps)
    packed = mg.pack(eng.cache.spec, snaps, codec="int8")
    assert len(raw) >= 3 * len(packed)  # ~4x on an f32 cache
    spec_d, snaps2, _ = mg.unpack(packed)
    assert spec_d["dtype"] == "float32"
    (s,) = snaps2
    assert s.k.dtype == np.float32 and s.length == snaps[0].length
    for a, b in ((snaps[0].k, s.k), (snaps[0].v, s.v)):
        # per-(layer, head) block scale: |err| <= blockmax/254 per element
        bound = np.max(np.abs(a), axis=(1, 3), keepdims=True) / 254 + 1e-7
        assert np.all(np.abs(np.asarray(a) - np.asarray(b)) <= bound)
    # the decoded snapshots adopt cleanly (dtype/geometry gates pass)
    dst = _engine(model, variables)
    slot_map = dst.adopt_slots(snaps2)
    assert snaps[0].slot in slot_map


def test_bf16_codec_token_parity_on_bf16_model(gpt_bf16):
    """bf16 codec over a bf16 cache is bit-lossless: a request migrated
    through the COMPRESSED payload decodes token-for-token identically
    to one never migrated."""
    model, variables = gpt_bf16
    prompt = [3, 1, 4, 1, 5]
    n_total, n_before = 10, 4
    ref = _ref_greedy(model, variables, prompt, n_total)
    src = _engine(model, variables)
    dst = _engine(model, variables)
    slot = src.alloc_slot()
    toks = [src.prefill(slot, prompt)]
    for _ in range(n_before - 1):
        toks.append(src.decode()[slot])
    payload = mg.pack(src.cache.spec, src.export_slots([slot]),
                      codec="bf16")
    spec_d, snaps, _ = mg.unpack(payload)
    mg.check_spec(dst.cache.spec, spec_d)
    slot_map = dst.adopt_slots(snaps)
    new = slot_map[slot]
    while len(toks) < n_total:
        toks.append(dst.decode()[new])
    assert toks == ref


def test_corrupt_compressed_body_names_chunk(gpt):
    """A compressed payload crossing the chunked wire with a flipped byte
    fails with a MigrationError NAMING the offending chunk — and nothing
    decodes (the whole-body CRC also refuses the direct-unpack path)."""
    model, variables = gpt
    eng = _engine(model, variables)
    slot = eng.alloc_slot()
    eng.prefill(slot, list(range(1, 30)))
    payload = mg.pack(eng.cache.spec, eng.export_slots([slot]),
                      codec="int8")
    store: dict = {}
    mg.send_payload(_ListChannel(store), payload, chunk_bytes=2048)
    assert len(store) >= 3
    bad = dict(store)
    frame = bytearray(bad[3])
    frame[-1] ^= 0x40
    bad[3] = bytes(frame)
    with pytest.raises(MigrationError, match="chunk 2 CRC mismatch"):
        mg.recv_payload(_ListChannel(bad))
    # same corruption surviving to unpack (e.g. a bad disk copy): the
    # body CRC still refuses it before any snapshot is built
    corrupt = bytearray(payload)
    corrupt[-1] ^= 0x40
    with pytest.raises(MigrationError, match="CRC"):
        mg.unpack(bytes(corrupt))


def test_unknown_codec_rejected_both_ways(gpt):
    model, variables = gpt
    eng = _engine(model, variables)
    slot = eng.alloc_slot()
    eng.prefill(slot, [1, 2, 3])
    snaps = eng.export_slots([slot])
    with pytest.raises(ValueError, match="codec"):
        mg.pack(eng.cache.spec, snaps, codec="zstd")
    # a payload CLAIMING a codec this build does not speak errors loudly
    # (self-describing header, validate-first)
    payload = mg.pack(eng.cache.spec, snaps)
    import json as _json
    magic, ver, hlen = mg._PAYLOAD_HDR.unpack_from(payload)
    off = mg._PAYLOAD_HDR.size
    hdr = _json.loads(payload[off:off + hlen])
    hdr["codec"] = "zstd"
    hb = _json.dumps(hdr, separators=(",", ":")).encode()
    tampered = mg._PAYLOAD_HDR.pack(magic, ver, len(hb)) + hb + \
        payload[off + hlen:]
    with pytest.raises(MigrationError, match="unknown KV codec"):
        mg.unpack(tampered)


# ---- scheduler hand-off ----

def test_scheduler_migration_mid_decode_parity(gpt):
    """Two mid-decode requests move scheduler→scheduler with their live
    slots; the peer finishes them token-for-token with zero prefill."""
    model, variables = gpt
    s1 = ContinuousBatchingScheduler(_engine(model, variables))
    s2 = ContinuousBatchingScheduler(_engine(model, variables))
    r1 = Request(prompt=[1, 2, 3], max_tokens=10)
    r2 = Request(prompt=[9, 8, 7, 6], max_tokens=12)
    s1.submit(r1)
    s1.submit(r2)
    for _ in range(4):
        s1.step()
    assert r1.tokens and r2.tokens  # really mid-decode
    slot_map = mg.migrate_inflight(s1, s2)
    assert len(slot_map) == 2
    assert not s1.has_work()
    assert s1.engine.cache.num_free == s1.engine.cache.num_slots
    s2.run([])
    assert r1.status == "ok" and r2.status == "ok"
    assert r1.tokens == _ref_greedy(model, variables, [1, 2, 3], 10)
    assert r2.tokens == _ref_greedy(model, variables, [9, 8, 7, 6], 12)
    assert s2.engine.metrics.count("prefill_tokens") == 0


def test_scheduler_migration_carries_queued_requests(gpt):
    """Queued (never-admitted) requests ride the same hand-off and
    prefill on the peer; running ones still skip prefill."""
    model, variables = gpt
    s1 = ContinuousBatchingScheduler(
        _engine(model, variables, num_slots=1))
    s2 = ContinuousBatchingScheduler(_engine(model, variables))
    running = Request(prompt=[1, 2], max_tokens=8)
    queued = Request(prompt=[5, 6, 7], max_tokens=6)
    s1.submit(running)
    s1.submit(queued)  # one slot: stays queued
    s1.step()
    assert running.state == "running" and queued.state == "queued"
    mg.migrate_inflight(s1, s2)
    s2.run([])
    assert running.tokens == _ref_greedy(model, variables, [1, 2], 8)
    assert queued.tokens == _ref_greedy(model, variables, [5, 6, 7], 6)
    # exactly ONE prefill on the peer: the queued request's
    assert s2.engine.metrics.count("prefill_tokens") == 3


def test_export_fold_charges_requeue_and_frees_slots(gpt):
    model, variables = gpt
    s1 = ContinuousBatchingScheduler(_engine(model, variables))
    req = Request(prompt=[1, 2, 3], max_tokens=10)
    s1.submit(req)
    for _ in range(3):
        s1.step()
    emitted = list(req.tokens)
    pairs = s1.export_inflight(fold=True)
    assert pairs == [(req, None)]
    assert req.requeues == 1
    assert req.prompt == [1, 2, 3] + emitted  # folded for re-prefill
    assert s1.engine.cache.num_free == s1.engine.cache.num_slots


class _NeverAckedWire:
    """A channel whose single ack slot never frees: every put times out
    — the shape of a receiver that died mid-stream."""

    def put(self, data, seq, *, timeout_s=None):
        time.sleep(min(timeout_s or 0.05, 0.05))
        raise TimeoutError("ack of the previous message not observed")


def test_send_payload_stop_aborts_wedged_sender():
    """A failed receive must not leave the rollback waiting out the
    sender's whole ack window: `stop` aborts the sender between short
    put slices, well inside the 60s it would otherwise wedge for."""
    stop = threading.Event()
    exc = []

    def run():
        try:
            mg.send_payload(_NeverAckedWire(), b"x" * 100, chunk_bytes=10,
                            timeout_s=60.0, stop=stop)
        except Exception as e:
            exc.append(e)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    time.sleep(0.2)  # let it wedge inside the first chunk's ack wait
    stop.set()
    t.join(5.0)
    assert not t.is_alive()
    assert exc and isinstance(exc[0], mg.MigrationError)


class _BoomWire:
    def put(self, data, seq, *, timeout_s=None):
        raise ConnectionError("wire died mid-transfer")

    def get(self, seq, *, timeout_s=None):
        raise ConnectionError("wire died mid-transfer")


def test_rollback_onto_dead_engine_attaches_nothing(gpt):
    """A serve_engine_kill landing between a failed target adoption and
    the source rollback: the local re-adopt must raise with NOTHING
    attached (all-or-nothing), so the caller's double-failure handler
    resolves requests that are in neither _running nor the queue —
    never half-attached bookkeeping a later failover would re-export."""
    from hetu_tpu.serve.pool import EngineKilled, _GuardedEngine
    model, variables = gpt
    eng = _GuardedEngine(_engine(model, variables))
    sched = ContinuousBatchingScheduler(eng)
    req = Request(prompt=[3, 1, 4], max_tokens=9)
    sched.submit(req)
    for _ in range(2):
        sched.step()
    pairs, _snaps = sched.export_inflight_with_slots()
    eng.kill()  # the chaos fault lands mid-rollback
    with pytest.raises(EngineKilled):
        sched.adopt_inflight(pairs)
    assert not sched._running and not sched._queue
    assert not req.done.is_set()  # the CALLER resolves it (migrate_inflight)


def test_export_rollback_does_not_count_requests_exported(gpt):
    """requests_exported must only count hand-offs that actually
    happened: an export the engine dies under rolls back WHOLE,
    counter included — repeated failed drains under chaos must not make
    it sum past real hand-offs."""
    from hetu_tpu.serve.pool import EngineKilled, _GuardedEngine
    model, variables = gpt
    eng = _GuardedEngine(_engine(model, variables))
    sched = ContinuousBatchingScheduler(eng)
    req = Request(prompt=[3, 1, 4], max_tokens=9)
    sched.submit(req)
    for _ in range(2):
        sched.step()
    eng.kill()  # engine.export_slots will raise mid-export
    with pytest.raises(EngineKilled):
        sched.export_inflight_with_slots()
    assert sched.metrics.count("requests_exported") == 0
    assert sched._running  # request re-attached where it was


def test_export_rollback_releases_done_in_transit_slot(gpt):
    """A request resolved DURING a failed export (a backstop cancel
    holds only the request's terminal lock, which the scheduler lock
    does not exclude) is skipped by the rollback — its slot must be
    RELEASED, not silently dropped: an ownerless active slot keeps
    decoding until max_len and wedges the whole engine."""
    from hetu_tpu.serve.pool import EngineKilled, _GuardedEngine
    from hetu_tpu.serve.scheduler import finish_request
    model, variables = gpt
    eng = _GuardedEngine(_engine(model, variables))  # num_slots=2
    sched = ContinuousBatchingScheduler(eng)
    live = Request(prompt=[3, 1, 4], max_tokens=9)
    doomed = Request(prompt=[2, 7], max_tokens=9)
    sched.submit(live)
    sched.submit(doomed)
    for _ in range(2):
        sched.step()
    assert len(sched._running) == 2 and eng.cache.num_free == 0
    finish_request(doomed, "timeout")  # the backstop cancel, mid-export
    eng.kill()  # engine.export_slots raises → rollback path
    with pytest.raises(EngineKilled):
        sched.export_inflight_with_slots()
    assert eng.cache.num_free == 1  # doomed's slot freed, not leaked
    assert list(sched._running.values()) == [live]  # live re-attached


@pytest.mark.parametrize("verb,args", [
    ("admission_pages", (3, 4)), ("admission_ok", ([1, 2, 3], 4)),
    ("begin_prefill", (0, [1, 2, 3])), ("prefill_step", (0,)),
    ("reindex_prefix", (0, [1, 2, 3]))])
def test_killed_guard_raises_from_the_scheduling_verbs(gpt, verb, args):
    """The guard forwards every verb the scheduler calls, so a pool member
    is scheduled as a bare engine is (page-budget admission, chunked
    prefill, re-dedup after an adoption) — and each of them dies with the
    engine."""
    from hetu_tpu.serve.pool import EngineKilled, _GuardedEngine
    model, variables = gpt
    eng = _GuardedEngine(_engine(model, variables))
    assert eng.alloc_slot() == 0
    if verb == "prefill_step":
        eng.begin_prefill(0, [1, 2, 3])
    getattr(eng, verb)(*args)  # alive: reaches the engine
    eng.kill()
    with pytest.raises(EngineKilled):
        getattr(eng, verb)(*args)


def test_guarded_members_interleave_chunks_and_reindex_adoptions(gpt):
    """Two pool-style members (the scheduler over the pool's guard): a long
    prompt prefills a chunk a step, not whole inside the admission, and a
    slot adopted from the peer is re-deduped into the adopter's prefix
    index — what a bare engine gets, through the guard."""
    from hetu_tpu.serve.pool import _GuardedEngine
    model, variables = gpt
    s1, s2 = (ContinuousBatchingScheduler(_GuardedEngine(
        _engine(model, variables, prefill_chunk=8))) for _ in range(2))
    prompt = list(range(1, 21))  # three chunks of 8
    req = Request(prompt=prompt, max_tokens=8)
    s1.submit(req)
    s1.step()
    assert s1._prefilling and not req.tokens  # one chunk in, two to go
    for _ in range(4):
        s1.step()
    assert 0 < len(req.tokens) < 8  # mid-decode
    assert len(mg.migrate_inflight(s1, s2)) == 1
    assert s2.engine.metrics.count("prefix_reindexed") >= 2  # two full pages
    s2.run([])
    assert req.status == "ok"
    assert req.tokens == _ref_greedy(model, variables, prompt, 8)
    assert s2.engine.metrics.count("prefill_tokens") == 0


def test_dead_wire_mid_migration_rolls_back_to_the_source(gpt):
    """A dead wire mid-migration re-adopts requests AND slots at the
    source — migration either completes or the source keeps serving."""
    model, variables = gpt
    s1 = ContinuousBatchingScheduler(_engine(model, variables))
    s2 = ContinuousBatchingScheduler(_engine(model, variables))
    req = Request(prompt=[3, 1, 4], max_tokens=9)
    s1.submit(req)
    for _ in range(2):
        s1.step()
    with pytest.raises(ConnectionError):
        mg.migrate_inflight(s1, s2, wire=(_BoomWire(), _BoomWire()))
    assert s1.has_work()  # rolled back, still mid-decode on the source
    s1.run([])
    assert req.status == "ok"
    assert req.tokens == _ref_greedy(model, variables, [3, 1, 4], 9)
    assert s2.engine.cache.num_free == s2.engine.cache.num_slots
