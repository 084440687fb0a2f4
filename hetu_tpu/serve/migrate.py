"""Live KV-cache slot migration between serving engines.

PR 3 taught one `InferenceServer` to survive engine death by requeueing
in-flight requests and RE-PREFILLING them — correct, but the recovery
cost grows with context length (a 10k-token conversation re-forwards 10k
tokens).  This module is the other half the ROADMAP left open: hand the
LIVE KV slots to a peer engine so decoding continues token-for-token
with zero prefill — the difference between "recovers eventually" and
"users never notice" on preemptible capacity.  The same pattern Hetu's
PS tier already proves (state handed between processes over the van with
deterministic replay) applied at the serve tier.

Three layers, separable on purpose:

* **slot payloads** — :func:`pack` / :func:`unpack` serialize
  :class:`~hetu_tpu.serve.kv_cache.KVSlotSnapshot` lists (plus optional
  request records) into one self-describing byte string: magic + JSON
  header (cache geometry, per-slot metadata, body CRC) + raw K/V bytes.
  ``unpack`` re-validates everything — magic, version, geometry, body
  CRC — before any array is materialized, so a corrupt transfer fails
  clean with nothing adopted;
* **chunked wire** — :func:`send_payload` / :func:`recv_payload` move a
  payload over an existing van :class:`~hetu_tpu.ps.van.BlobChannel` as
  CRC-framed chunks at consecutive seqs.  Every frame is a single-slot
  acked blob put, idempotent under same-seq resend, so a transport drop
  mid-transfer reconnects and resumes at the unacked chunk instead of
  restarting the payload (tests/test_van_blob.py kills the connection
  between chunks);
* **orchestration** — :func:`migrate_inflight` moves every in-flight
  request from one scheduler to another: mid-decode requests carry
  their live slots, queued ones re-queue, and ANY failure re-adopts
  everything at the source and re-raises — migration either completes
  or leaves the source serving.

Request records (:func:`request_record` / :func:`request_from_record`)
are the wire form of a mid-decode ``Request``: prompt, emitted tokens,
fold watermark, deadline (as elapsed time — monotonic clocks do not
compare across processes), requeue count.  Decoding is greedy argmax
today, so there is no sampler/RNG state to carry; a sampling engine
extends the record here.

The wire holds no page ids: an export (serve/kv_cache.py:PagedKVCache)
assembles each slot's LIVE pages into one contiguous truncated-rows
snapshot (page ids are process-local and meaningless on the wire — the
adopter rebuilds page tables as it imports), so payload size scales with
live tokens and every codec applies.  The
adopter also RE-DEDUPS each imported slot back into its prefix index
(scheduler ``adopt_inflight`` → engine ``reindex_prefix``: page-boundary
hashes of the request's token stream registered against the imported
pages), so post-drain traffic sharing the migrated requests' prompts
keeps its prefix hit rate instead of re-prefilling until the pages age
out.
"""

from __future__ import annotations

import json
import struct
import threading
import time
import zlib

import numpy as np

from hetu_tpu.serve.kv_cache import KVSlotSnapshot

MAGIC = b"HTMG"
VERSION = 1
DEFAULT_CHUNK_BYTES = 1 << 20

# per-chunk frame header: magic, version, chunk index, total chunks,
# crc32 of this chunk's payload
_CHUNK_HDR = struct.Struct("<4sIIII")
# payload prefix: magic, version, JSON header length
_PAYLOAD_HDR = struct.Struct("<4sII")


class MigrationError(RuntimeError):
    """A slot transfer failed validation (geometry, CRC, framing).  The
    receiving side adopts NOTHING when this raises — partial adoption is
    the one outcome the wire format must make impossible."""


class MigrationTargetError(MigrationError):
    """The DESTINATION refused or failed the adoption (drained, killed,
    incompatible geometry).  A pool catches this specifically to retry
    the migration against a different peer — source-side and wire-layer
    failures raise plain exceptions, where retrying with another target
    would be futile."""


def _np_dtype(name: str) -> np.dtype:
    try:
        return np.dtype(name)
    except TypeError:
        # bf16 etc. live in ml_dtypes, registered via jax
        import jax.numpy as jnp
        return np.dtype(jnp.dtype(name))


# ---------------------------------------------------------------------------
# request records
# ---------------------------------------------------------------------------

def request_record(req, *, now: float | None = None) -> dict:
    """The wire form of a mid-decode ``Request`` — everything a peer
    scheduler needs to continue it.  Deadlines travel as elapsed seconds
    since submission (``time.monotonic`` values are process-local)."""
    now = time.monotonic() if now is None else now
    return {
        "rid": int(req.rid),
        "prompt": [int(t) for t in req.prompt],
        "tokens": [int(t) for t in req.tokens],
        "folded": int(req.folded),
        "max_tokens": int(req.max_tokens),
        "eos_id": None if req.eos_id is None else int(req.eos_id),
        "timeout_s": req.timeout_s,
        "elapsed_s": 0.0 if req.submitted_at is None
        else max(now - req.submitted_at, 0.0),
        "requeues": int(req.requeues),
        "had_first_token": req.first_token_at is not None,
        # per-tenant attribution must survive the hand-off: the
        # adopter's serve.request span and accounting carry it forward
        "tenant": getattr(req, "tenant", None),
        # SLO class rides too — a migrated high-priority request must
        # keep its admission tier on the adopter's scheduler
        "slo": getattr(req, "slo", None),
    }


def request_from_record(rec: dict, *, now: float | None = None):
    """Rebuild a ``Request`` from :func:`request_record` output on the
    adopting side (cross-process migration; the in-process pool hands
    the live objects over instead so waiters keep their events)."""
    from hetu_tpu.serve.scheduler import Request
    now = time.monotonic() if now is None else now
    req = Request(
        prompt=list(rec["prompt"]), max_tokens=int(rec["max_tokens"]),
        eos_id=rec.get("eos_id"), timeout_s=rec.get("timeout_s"))
    req.rid = int(rec["rid"])
    req.tenant = rec.get("tenant")
    req.slo = rec.get("slo")
    req.tokens = list(rec["tokens"])
    req.folded = int(rec.get("folded", 0))
    req.requeues = int(rec.get("requeues", 0))
    req.submitted_at = now - float(rec.get("elapsed_s", 0.0))
    if rec.get("had_first_token"):
        # the migrated request already observed TTFT at the source; the
        # adopter must not re-observe it (exact value is source-local)
        req.first_token_at = req.submitted_at
    return req


# ---------------------------------------------------------------------------
# payload pack/unpack
# ---------------------------------------------------------------------------

CODECS = ("none", "bf16", "int8")


def check_codec(codec: str, *, allow_auto: bool = True) -> str:
    """Validate a KV wire codec name up front (pool construction, the
    per-drain override) so a typo fails where it was written, not at
    the first drain under a preemption deadline.  ONE home for the
    check — both pool flavors and both override points use it.
    ``"auto"`` (policy, not a wire format — :func:`pick_codec` resolves
    it per drain from the measured link rate) is accepted everywhere
    except the pack/unpack layer itself (``allow_auto=False``)."""
    if codec == "auto" and allow_auto:
        return codec
    if codec not in CODECS:
        raise ValueError(f"unknown migrate codec {codec!r}; expected "
                         f"one of {CODECS}" +
                         (" or 'auto'" if allow_auto else ""))
    return codec


# a link-rate sample must come from a transfer big enough that the
# payload, not per-frame ack pacing, dominated the wall time
MIN_RATE_SAMPLE_BYTES = 1 << 16


def measured_link_mbps(registry=None) -> float | None:
    """Observed bulk-transfer rate in Mbit/s — the op-span-derived half
    of the "netem-visible or op-span-derived" link-rate signal the auto
    drain codec uses.  The ONLY samples consulted are completed
    migration payload sends of at least ``MIN_RATE_SAMPLE_BYTES``
    (``migrate.wire.mbps``, recorded by :func:`send_payload`): the
    generic ``van.blob_put`` aggregate is dominated by tiny ack-paced
    control frames whose bytes/latency ratio reads orders of magnitude
    below the real wire — a "measurement" that would always escalate
    the codec on loopback.  Returns None until a real bulk transfer has
    been observed (no evidence = no compression)."""
    if registry is None:
        from hetu_tpu.telemetry import default_registry as registry
    g = registry.metrics().get("migrate.wire.mbps_last")
    if g is None:
        return None
    rate = float(g.value)
    return rate if rate > 0 else None


def known_link_mbps() -> float | None:
    """The best link-rate signal available in THIS process: an
    installed netem bandwidth cap (the emulated truth) wins, else the
    last observed bulk-transfer rate, else None."""
    from hetu_tpu.ps import van as _van
    em = getattr(getattr(_van, "_netem_hook", None), "__self__", None)
    if em is not None and hasattr(em, "current_rate_mbps"):
        rate = em.current_rate_mbps()
        if rate is not None:
            return rate
    return measured_link_mbps()


def estimate_payload_bytes(engine) -> int:
    """Uncompressed (codec="none") drain payload size for the engine's
    LIVE slots: what :func:`pack` would ship, from the cache's lengths
    and geometry — no export needed to decide a codec."""
    cache = engine.cache
    spec = cache.spec
    itemsize = _np_dtype(str(np.dtype(spec.dtype))).itemsize
    per_tok = spec.num_kv_heads * (spec.head_dim + spec.v_dim) * itemsize
    live_tokens = int(np.sum(cache.lengths))
    return live_tokens * spec.num_layers * per_tok


def pick_codec(rate_mbps: float | None, payload_bytes: int,
               cache_dtype: str, *,
               fast_s: float = 0.05, slow_s: float = 0.5) -> str:
    """Resolve ``codec="auto"`` to a concrete wire codec: compression
    only wins when the LINK, not the CPU, is the bottleneck — loopback
    moves bytes for free and the codec would just burn encode time.

    * rate unknown or projected transfer under ``fast_s`` → ``none``
      (nothing to save);
    * bf16 cache → ``bf16`` (bit-lossless, 2x) once transfer costs
      real time; escalate to ``int8`` (4x vs f32, 2x vs bf16,
      near-lossless block scales) when even the bf16 payload would
      exceed ``slow_s`` — the preemption-deadline regime, where the
      wire is all of the drain's time;
    * f32 cache → ``int8`` directly (bf16 would be lossy anyway at
      only 2x; int8's block scales give 4x).
    """
    if rate_mbps is None or rate_mbps <= 0 or payload_bytes <= 0:
        return "none"
    transfer_s = payload_bytes / (rate_mbps * 125_000.0)
    if transfer_s <= fast_s:
        return "none"
    if "bfloat16" in str(cache_dtype) or "bf16" in str(cache_dtype):
        return "int8" if transfer_s / 2.0 > slow_s else "bf16"
    return "int8"


def resolve_codec(codec: str, engine, *,
                  rate_mbps: float | None = None) -> str:
    """The per-drain "auto" resolution both pool flavors share: prefer
    an explicitly known link rate (a netem cap, a configured DCN
    share), fall back to the op-span-derived measurement, and feed the
    engine's live payload estimate through :func:`pick_codec`.
    Concrete codecs pass through untouched."""
    if codec != "auto":
        return check_codec(codec, allow_auto=False)
    if rate_mbps is None:
        rate_mbps = known_link_mbps()
    return pick_codec(rate_mbps, estimate_payload_bytes(engine),
                      str(np.dtype(engine.cache.spec.dtype)))


def _encode_kv(arr: np.ndarray, codec: str, dt: np.dtype) -> bytes:
    """One K or V array ``[layers, tokens, heads, head_dim]`` → body bytes.

    ``bf16``: elementwise round (lossless when the model already runs
    bf16 — the token-parity tier); ``int8``: block-scaled with one f32
    scale per (layer, head) — scales prefix the codes, ~4x smaller than
    f32 K/V at negligible per-token cost."""
    if codec == "none":
        return arr.tobytes()
    if codec == "bf16":
        return np.ascontiguousarray(
            arr.astype(_np_dtype("bfloat16"))).tobytes()
    if codec == "int8":
        from hetu_tpu.quantwire import q8_encode_axes
        q, scales = q8_encode_axes(arr, (1, 3))  # block = (layer, head)
        return (np.ascontiguousarray(scales, np.float32).tobytes()
                + np.ascontiguousarray(q).tobytes())
    raise ValueError(f"unknown KV codec {codec!r}; expected one of {CODECS}")


def _decode_kv(buf: memoryview, codec: str, dt: np.dtype,
               shape_tail: tuple, slot: int, name: str) -> np.ndarray:
    """Inverse of :func:`_encode_kv` back to the spec dtype; raises
    :class:`MigrationError` naming the slot on any size mismatch."""
    L, _, H, D = shape_tail
    if codec == "none":
        return np.frombuffer(buf, dt).reshape(shape_tail)
    if codec == "bf16":
        bf = _np_dtype("bfloat16")
        return np.frombuffer(buf, bf).reshape(shape_tail).astype(dt)
    if codec == "int8":
        from hetu_tpu.quantwire import q8_decode_axes
        scale_bytes = L * H * 4
        if len(buf) < scale_bytes:
            raise MigrationError(
                f"slot {slot}: {name} compressed body shorter than its "
                f"{L}x{H} block-scale table")
        scales = np.frombuffer(buf[:scale_bytes],
                               np.float32).reshape(L, 1, H, 1)
        q = np.frombuffer(buf[scale_bytes:], np.int8).reshape(shape_tail)
        return q8_decode_axes(q, scales).astype(dt)
    raise MigrationError(f"payload names unknown KV codec {codec!r}; "
                         f"this build speaks {CODECS}")


def _encoded_tokens(nbytes: int, codec: str, dt: np.dtype, L: int, H: int,
                    D: int) -> int:
    """Token count implied by an encoded K/V byte length (-1: not a whole
    number of tokens — corrupt meta)."""
    if codec == "bf16":
        per_tok = L * H * D * 2
    elif codec == "int8":
        nbytes -= L * H * 4  # block-scale prefix
        per_tok = L * H * D
    else:
        per_tok = L * H * D * dt.itemsize
    if nbytes < 0 or per_tok <= 0 or nbytes % per_tok:
        return -1
    return nbytes // per_tok


def pack(spec, snapshots, records=(), *, codec: str = "none") -> bytes:
    """Serialize slot snapshots (+ optional request records) into one
    migration payload.  ``spec`` is the source cache's ``KVCacheSpec`` —
    the receiver validates it against its own before touching a slot.

    ``codec`` compresses the K/V body ("bf16": 2 B/elt, lossless for
    bf16-model caches; "int8": ~1 B/elt, block-scaled per (layer, head)).
    The payload is self-describing — the header names the codec and the
    body CRC covers the COMPRESSED bytes — so ``unpack`` needs no side
    channel and an old payload (no codec field) still decodes as raw.
    Logical-vs-wire bytes land on the shared ``serve.migrate.bytes_*``
    telemetry counters."""
    if codec not in CODECS:
        raise ValueError(f"unknown KV codec {codec!r}; expected one of "
                         f"{CODECS}")
    dt = np.dtype(spec.dtype)
    slots_meta = []
    blobs = []
    logical = 0
    for s in snapshots:
        k = np.ascontiguousarray(s.k)
        v = np.ascontiguousarray(s.v)
        logical += k.nbytes + v.nbytes
        kb = _encode_kv(k, codec, dt)
        vb = _encode_kv(v, codec, dt)
        slots_meta.append({"slot": int(s.slot), "length": int(s.length),
                           "meta": dict(s.meta),
                           "k_bytes": len(kb), "v_bytes": len(vb)})
        blobs.append(kb)
        blobs.append(vb)
    body = b"".join(blobs)
    header = {
        "version": VERSION,
        "codec": codec,
        "spec": {"num_layers": int(spec.num_layers),
                 "num_kv_heads": int(spec.num_kv_heads),
                 "head_dim": int(spec.head_dim),
                 "v_head_dim": int(spec.v_dim),
                 "dtype": dt.name},
        "slots": slots_meta,
        "records": list(records),
        "body_bytes": len(body),
        "body_crc": zlib.crc32(body),
    }
    from hetu_tpu.quantwire import record_wire_bytes
    record_wire_bytes("serve.migrate", logical, len(body))
    hb = json.dumps(header, separators=(",", ":")).encode()
    return _PAYLOAD_HDR.pack(MAGIC, VERSION, len(hb)) + hb + body


def unpack(payload: bytes):
    """Parse a :func:`pack` payload back into ``(spec_dict, snapshots,
    records)``.  Raises :class:`MigrationError` on any framing/CRC
    problem — before any snapshot is built."""
    if len(payload) < _PAYLOAD_HDR.size:
        raise MigrationError("migration payload shorter than its header")
    magic, ver, hlen = _PAYLOAD_HDR.unpack_from(payload)
    if magic != MAGIC:
        raise MigrationError(f"bad migration magic {magic!r}")
    if ver != VERSION:
        raise MigrationError(f"migration payload version {ver}; this "
                             f"build speaks {VERSION}")
    off = _PAYLOAD_HDR.size
    if len(payload) < off + hlen:
        raise MigrationError("truncated migration header")
    try:
        header = json.loads(payload[off:off + hlen])
    except json.JSONDecodeError as e:
        raise MigrationError(f"corrupt migration header: {e}") from None
    body = payload[off + hlen:]
    if len(body) != int(header["body_bytes"]):
        raise MigrationError(
            f"migration body is {len(body)} bytes; header promised "
            f"{header['body_bytes']}")
    if zlib.crc32(body) != int(header["body_crc"]):
        raise MigrationError("migration body CRC mismatch — refusing to "
                             "adopt any slot from a corrupt transfer")
    spec_d = header["spec"]
    codec = header.get("codec", "none")  # pre-codec payloads: raw body
    if codec not in CODECS:
        raise MigrationError(f"payload names unknown KV codec {codec!r}; "
                             f"this build speaks {CODECS}")
    dt = _np_dtype(spec_d["dtype"])
    L = int(spec_d["num_layers"])
    H = int(spec_d["num_kv_heads"])
    D = int(spec_d["head_dim"])
    Dv = int(spec_d.get("v_head_dim", D))  # older payloads: one width
    snaps = []
    pos = 0
    bodyv = memoryview(body)
    for m in header["slots"]:
        kb, vb = int(m["k_bytes"]), int(m["v_bytes"])
        if pos + kb + vb > len(body):
            raise MigrationError("slot byte ranges overrun the body")
        # token counts are derived from the ENCODED byte lengths before
        # any frombuffer touches the body — a corrupt meta fails loudly,
        # never reshapes garbage
        nk = _encoded_tokens(kb, codec, dt, L, H, D)
        nv = _encoded_tokens(vb, codec, dt, L, H, Dv)
        if nk < 0 or nv < 0:
            raise MigrationError(
                f"slot {m['slot']}: K/V bytes do not factor into the "
                f"declared geometry under codec {codec!r}")
        try:
            k = _decode_kv(bodyv[pos:pos + kb], codec, dt, (L, nk, H, D),
                           int(m["slot"]), "k")
            v = _decode_kv(bodyv[pos + kb:pos + kb + vb], codec, dt,
                           (L, nv, H, Dv), int(m["slot"]), "v")
        except ValueError as e:
            raise MigrationError(
                f"slot {m['slot']}: K/V bytes do not factor into the "
                f"declared geometry ({e})") from None
        pos += kb + vb
        if nk != int(m["length"]) or nv != int(m["length"]):
            raise MigrationError(
                f"slot {m['slot']}: {nk} rows of K/V for a "
                f"declared length of {m['length']}")
        snaps.append(KVSlotSnapshot(slot=int(m["slot"]),
                                    length=int(m["length"]),
                                    k=k, v=v, meta=dict(m.get("meta", {}))))
    return spec_d, snaps, list(header.get("records", []))


def check_spec(spec, spec_dict: dict) -> None:
    """Receiver-side geometry gate: the adopting cache's spec must match
    the payload's exactly (layers/kv-heads/head-dim/dtype) — erroring
    loudly beats adopting garbage rows."""
    mine = {"num_layers": int(spec.num_layers),
            "num_kv_heads": int(spec.num_kv_heads),
            "head_dim": int(spec.head_dim),
            "v_head_dim": int(spec.v_dim),
            "dtype": np.dtype(spec.dtype).name}
    theirs = {k: spec_dict.get(k) for k in mine}
    theirs["v_head_dim"] = spec_dict.get("v_head_dim", theirs["head_dim"])
    if mine != theirs:
        raise MigrationError(
            f"KV cache geometry mismatch: payload {theirs} vs local "
            f"{mine} — slots can only migrate between engines serving "
            f"the same model geometry")


# ---------------------------------------------------------------------------
# whole-scheduler payloads (the cross-process drain)
# ---------------------------------------------------------------------------

def export_payload(scheduler, *, codec: str = "none"):
    """Export EVERY in-flight request from ``scheduler`` into one
    self-describing migration payload: mid-decode requests ride with
    their live KV snapshots (zero re-prefill on the adopter), queued
    ones as bare records.  The scheduler half of a PROCESS-BOUNDARY
    drain (serve/crosshost.py): unlike :func:`migrate_inflight`, source
    and destination here share no objects — everything a peer process
    needs crosses inside the payload.

    Returns ``(payload, pairs)``; ``pairs`` is the live export the
    caller must hold for rollback (``scheduler.adopt_inflight(pairs)``)
    until the peer confirms adoption, then release via
    :func:`release_exported`.  Each request record carries its SOURCE
    slot id (``rec["slot"]``, None for queued) so :func:`adopt_payload`
    can rebind it to the imported snapshot."""
    pairs, snaps = scheduler.export_inflight_with_slots()
    try:
        records = []
        now = time.monotonic()
        for req, slot in pairs:
            rec = request_record(req, now=now)
            rec["slot"] = None if slot is None else int(slot)
            records.append(rec)
        payload = pack(scheduler.engine.cache.spec, snaps, records,
                       codec=codec)
    except Exception:
        # the export succeeded but the payload build did not: the
        # requests are off the scheduler and the CALLER never received
        # `pairs` to roll back — re-adopt here or they strand forever
        scheduler.adopt_inflight(pairs)
        raise
    return payload, pairs


def adopt_payload(scheduler, payload: bytes):
    """Adopt an :func:`export_payload` payload into ``scheduler`` —
    geometry-gated, all-or-nothing (KV import + request attachment under
    the adopter's scheduler lock).  Requests are REBUILT from their wire
    records (:func:`request_from_record`): the adopting process owns
    fresh ``Request`` objects whose completion the caller must report
    back over its own control plane.  Returns ``(requests,
    slot_map)`` in the payload's admission order."""
    spec_d, snaps, records = unpack(payload)
    check_spec(scheduler.engine.cache.spec, spec_d)
    now = time.monotonic()
    by_slot = {int(s.slot): s for s in snaps}
    pairs = []
    for rec in records:
        req = request_from_record(rec, now=now)
        slot = rec.get("slot")
        if slot is not None and int(slot) not in by_slot:
            raise MigrationError(
                f"record {rec.get('rid')} names source slot {slot} but "
                f"the payload carries no snapshot for it")
        pairs.append((req, None if slot is None else int(slot)))
    carried = {s for _, s in pairs if s is not None}
    orphans = sorted(set(by_slot) - carried)
    if orphans:
        raise MigrationError(
            f"payload carries snapshots for slots {orphans} that no "
            f"request record references — refusing a partial adoption")
    try:
        slot_map = scheduler.adopt_inflight(pairs,
                                            snapshots=snaps or None)
    except Exception as e:
        raise MigrationTargetError(
            f"destination failed the adoption: {e}") from e
    return [req for req, _ in pairs], slot_map


def release_exported(scheduler, pairs) -> None:
    """Commit half of a cross-process drain: the peer confirmed
    adoption, so the source's exported slots are dead weight — release
    them (best-effort; the source may be about to exit anyway) and
    charge ``requests_exported`` with the committed hand-off."""
    from hetu_tpu.serve.scheduler import release_slot_best_effort
    for _req, slot in pairs:
        if slot is not None:
            release_slot_best_effort(scheduler.engine, slot)
    scheduler.metrics.inc("requests_exported", len(pairs))


# ---------------------------------------------------------------------------
# chunked wire over a van blob channel
# ---------------------------------------------------------------------------

def send_payload(channel, payload: bytes, *, seq0: int = 1,
                 chunk_bytes: int = DEFAULT_CHUNK_BYTES,
                 timeout_s: float = 60.0, stop=None) -> int:
    """Send ``payload`` over a van blob channel as CRC-framed chunks at
    seqs ``[seq0, seq0+n)``; returns the next free seq.  Each frame is a
    single-slot acked put, idempotent under same-seq resend — a
    connection drop mid-transfer reconnects and resends the in-flight
    chunk, never restarting the payload.

    ``stop`` (a ``threading.Event``): cooperative abort, checked between
    SHORT put slices instead of one ``timeout_s``-long ack wait.  A
    receiver that died mid-stream never acks, and the caller cannot
    safely close the channel under a blocked native put — without the
    slicing, an aborted transfer wedges the sender (and whoever joins
    it) for the whole ack window.  Raises :class:`MigrationError` when
    set."""
    chunk_bytes = max(int(chunk_bytes), 1)
    n = max((len(payload) + chunk_bytes - 1) // chunk_bytes, 1)
    slice_s = 0.5 if stop is not None else timeout_s
    t0 = time.perf_counter()
    for i in range(n):
        part = payload[i * chunk_bytes:(i + 1) * chunk_bytes]
        frame = _CHUNK_HDR.pack(MAGIC, VERSION, i, n,
                                zlib.crc32(part)) + part
        deadline = time.monotonic() + timeout_s
        while True:
            if stop is not None and stop.is_set():
                raise MigrationError(
                    f"send aborted at chunk {i}/{n}: receiver gone")
            remaining = deadline - time.monotonic()
            try:
                channel.put(frame, seq0 + i,
                            timeout_s=max(min(slice_s, remaining), 0.001))
                break
            except TimeoutError:
                # ack window still blocked: same-seq resend is idempotent
                if time.monotonic() >= deadline:
                    raise
    dt = time.perf_counter() - t0
    if len(payload) >= MIN_RATE_SAMPLE_BYTES and dt > 0:
        # a completed BULK transfer is the one honest link-rate sample
        # this process gets (control frames are tiny and ack-paced —
        # their byte/latency aggregate reads orders of magnitude slow):
        # feed the auto-codec model (measured_link_mbps)
        from hetu_tpu.telemetry import default_registry as _reg
        _reg.gauge("migrate.wire.mbps_last").set(
            len(payload) * 8.0 / (dt * 1e6))
        _reg.counter("migrate.wire.rate_samples").inc()
    return seq0 + n


def recv_payload(channel, *, seq0: int = 1,
                 timeout_s: float = 60.0) -> bytes:
    """Receive a :func:`send_payload` stream.  Validates each chunk's
    framing and CRC as it lands and raises :class:`MigrationError` on
    the first mismatch — the caller adopts nothing from a bad stream."""
    parts = []
    i, n = 0, 1
    while i < n:
        frame = channel.get(seq0 + i, timeout_s=timeout_s)
        if len(frame) < _CHUNK_HDR.size:
            raise MigrationError(f"chunk {i}: frame shorter than header")
        magic, ver, idx, total, crc = _CHUNK_HDR.unpack_from(frame)
        if magic != MAGIC or ver != VERSION:
            raise MigrationError(f"chunk {i}: bad magic/version")
        if idx != i or total < 1 or (i > 0 and total != n):
            raise MigrationError(
                f"chunk sequence corrupt: got idx {idx}/{total} at "
                f"position {i}/{n}")
        part = frame[_CHUNK_HDR.size:]
        if zlib.crc32(part) != crc:
            raise MigrationError(f"chunk {i} CRC mismatch")
        n = total
        parts.append(part)
        i += 1
    return b"".join(parts)


# ---------------------------------------------------------------------------
# orchestration
# ---------------------------------------------------------------------------

def migrate_inflight(src, dst, *, wire=None, codec: str = "none",
                     chunk_bytes: int = DEFAULT_CHUNK_BYTES,
                     timeout_s: float = 60.0) -> dict:
    """Move EVERY in-flight request from scheduler ``src`` to scheduler
    ``dst``: mid-decode requests carry their live KV slots (the peer
    continues with zero prefill); queued ones re-queue on the peer with
    their deadlines intact.  Returns ``{source_slot: dest_slot}``.

    ``wire``: a ``(tx, rx)`` pair of van blob channels the K/V payload
    crosses as CRC-checked chunks (the sender runs in a helper thread —
    blob puts block on the single-slot ack window); ``None`` hands the
    host arrays over directly (same-process fast path, identical
    validation via the engines).

    ``codec`` ("bf16"/"int8", wire transfers only): compress the K/V
    body — see :func:`pack`.  "bf16" keeps token parity for bf16-model
    caches at half the bytes; "int8" is ~4x smaller (2x for bf16 caches)
    with per-(layer, head) block scales, a near-lossless approximation
    whose drain payloads move the migrate-vs-re-prefill crossover to
    shorter contexts.

    Failure atomicity: any error re-adopts the requests AND their slots
    at the source (the slots were never released) and re-raises —
    migration either completes or leaves the source serving.  On the
    destination, KV import and request attachment happen atomically
    under its scheduler lock (``adopt_inflight``), so a live peer keeps
    serving its own traffic safely throughout.
    """
    # export + KV snapshot atomically under the source scheduler lock: a
    # decode step sneaking in between would advance the exported slots
    # past the requests' recorded tokens (a silently dropped token on
    # the adopter)
    pairs, snaps = src.export_inflight_with_slots()
    slots = [slot for _, slot in pairs if slot is not None]
    try:
        if wire is not None and snaps:
            spec = src.engine.cache.spec
            payload = pack(spec, snaps, codec=codec)
            tx, rx = wire
            send_exc: list = []
            send_stop = threading.Event()

            def _send():
                try:
                    send_payload(tx, payload, chunk_bytes=chunk_bytes,
                                 timeout_s=timeout_s, stop=send_stop)
                except Exception as e:  # surfaced after the join
                    send_exc.append(e)

            t = threading.Thread(target=_send, daemon=True)
            t.start()
            try:
                got = recv_payload(rx, timeout_s=timeout_s)
            except BaseException:
                # the receive failed mid-stream (corrupt chunk/timeout):
                # the sender would sit out its WHOLE ack window against
                # a peer that will never ack — signal it down instead.
                # The rollback below must run promptly: the exported
                # requests are off both schedulers, burning their
                # serving deadlines while we wait
                send_stop.set()
                t.join(timeout_s)
                raise
            t.join(timeout_s)
            if send_exc:
                raise send_exc[0]
            spec_d, snaps, _ = unpack(got)
            check_spec(dst.engine.cache.spec, spec_d)
        try:
            slot_map, n_adopted = dst.adopt_inflight(
                pairs, snapshots=snaps or None, return_count=True)
        except Exception as e:
            raise MigrationTargetError(
                f"destination failed the adoption: {e}") from e
    except Exception:
        try:
            src.adopt_inflight(pairs)  # source resumes serving, slots
        except Exception:              # intact
            # the source is gone too (closed/drained mid-transfer): the
            # requests must still RESOLVE — nothing will ever serve them,
            # and a waiter blocked on done would sit out its whole
            # backstop timeout undiagnosed
            from hetu_tpu.serve.scheduler import (
                finish_request, release_slot_best_effort,
            )
            for req, _ in pairs:
                if not req.done.is_set():
                    finish_request(req, req.status or "error",
                                   getattr(src, "metrics", None))
            for slot in slots:
                release_slot_best_effort(src.engine, slot)
        raise  # the ORIGINAL failure, not the rollback's
    # the migration has COMMITTED: the hand-off is now real, so charge
    # the source's requests_exported (deferred from the export — a
    # rolled-back export must not count) with what the destination
    # ACTUALLY attached (requests that finished in transit were skipped
    # there and never handed off).  Releasing the source's now-dead
    # slots is best-effort (a source engine dying right here must not
    # turn a successful hand-off into a raised error)
    src.metrics.inc("requests_exported", n_adopted)
    from hetu_tpu.serve.scheduler import release_slot_best_effort
    for slot in slots:
        release_slot_best_effort(src.engine, slot)
    return slot_map
