"""Inference server over the van blob-channel transport: end-to-end
generate, concurrent clients, per-request timeout, graceful shutdown —
plus the OP_STATS since-server-start regression (counters must reset
across serve() incarnations in one process).
"""

import json
import threading
import time

import jax
import numpy as np
import pytest

from hetu_tpu.ps import available

if not available():  # pragma: no cover
    pytest.skip("native PS lib unavailable", allow_module_level=True)

from hetu_tpu.models.gpt import GPTConfig, GPTModel
from hetu_tpu.ps import van
from hetu_tpu.serve import (
    ContinuousBatchingScheduler, InferenceClient, InferenceServer,
    PagedServeEngine, Request, request_channel, response_channel,
)
from paged_programs import ref_greedy as _ref_greedy


@pytest.fixture(scope="module")
def gpt():
    m = GPTModel(GPTConfig(
        vocab_size=97, hidden_size=64, num_layers=2, num_heads=4,
        ffn_size=128, max_position=64, dropout_rate=0.0))
    return m, m.init(jax.random.PRNGKey(0))


@pytest.fixture
def server(gpt):
    model, variables = gpt
    engine = _engine(model, variables, num_slots=4)
    sched = ContinuousBatchingScheduler(engine)
    srv = InferenceServer(sched, max_clients=3, request_timeout_s=60.0,
                          poll_s=0.1)
    yield srv, model, variables
    srv.close()


def _engine(model, variables, *, num_slots=2, max_len=48):
    return PagedServeEngine(model, variables, num_slots=num_slots,
                            max_len=max_len, page_size=8, min_bucket=8)


def test_generate_end_to_end_matches_reference(server):
    srv, model, variables = server
    prompt = [3, 14, 15, 9, 2, 6]
    client = InferenceClient("127.0.0.1", srv.port, 0)
    try:
        resp = client.generate(prompt, max_tokens=8)
    finally:
        client.close()
    assert resp["status"] == "ok"
    assert resp["tokens"] == _ref_greedy(model, variables, prompt, 8)
    assert resp["ttft_s"] > 0


def test_concurrent_clients_each_get_their_own_answer(server):
    srv, model, variables = server
    prompts = {0: [1, 2, 3], 1: [9, 8, 7, 6], 2: [42]}
    results = {}
    errors = []

    def worker(cid):
        c = InferenceClient("127.0.0.1", srv.port, cid)
        try:
            for j in range(2):  # two sequential requests per client
                results[(cid, j)] = c.generate(prompts[cid], max_tokens=5)
        except Exception as e:  # pragma: no cover - failure reporting
            errors.append((cid, repr(e)))
        finally:
            c.close()

    ts = [threading.Thread(target=worker, args=(cid,)) for cid in prompts]
    for t in ts:
        t.start()
    for t in ts:
        t.join(120)
    assert not errors, errors
    assert len(results) == 6
    for (cid, _), resp in results.items():
        assert resp["status"] == "ok"
        assert resp["tokens"] == _ref_greedy(model, variables,
                                             prompts[cid], 5)


def test_per_request_timeout_returns_timeout_status(server):
    """A request whose deadline is already past when admission runs must
    come back status=timeout with no tokens — the wire analog of the
    scheduler's queue-expiry eviction."""
    srv, _, _ = server
    client = InferenceClient("127.0.0.1", srv.port, 1)
    try:
        resp = client.generate([1, 2, 3], max_tokens=8, deadline_s=0.0)
    finally:
        client.close()
    assert resp["status"] in ("timeout", "cancelled")
    assert resp["tokens"] == []


def test_graceful_shutdown_drains_and_stops_van(gpt):
    model, variables = gpt
    engine = _engine(model, variables, max_len=32)
    sched = ContinuousBatchingScheduler(engine)
    srv = InferenceServer(sched, max_clients=1, poll_s=0.05)
    client = InferenceClient("127.0.0.1", srv.port, 0)
    try:
        assert client.generate([5, 6], max_tokens=3)["status"] == "ok"
    finally:
        client.close()
    srv.close()
    assert not srv._loop.is_alive()
    assert not any(t.is_alive() for t in srv._listeners)
    # the van really stopped: a fresh serve() binds again in this process
    port = van.serve(0)
    assert port > 0
    van.stop()


def test_client_restart_with_same_id_is_served(server):
    """A client process that dies and reconnects under the same id starts
    its seqs over at 1; the listener must resync instead of waiting
    forever at the old seq."""
    srv, model, variables = server
    first = InferenceClient("127.0.0.1", srv.port, 0)
    try:
        for _ in range(2):  # advance the server listener's seq past 1
            assert first.generate([1, 2], max_tokens=3)["status"] == "ok"
    finally:
        first.close()
    reborn = InferenceClient("127.0.0.1", srv.port, 0)  # seq restarts at 1
    try:
        resp = reborn.generate([9, 8, 7], max_tokens=4, timeout_s=30.0)
    finally:
        reborn.close()
    assert resp["status"] == "ok"
    assert resp["tokens"] == _ref_greedy(model, variables, [9, 8, 7], 4)


def test_malformed_request_gets_error_response(server):
    srv, _, _ = server
    ch_req = van.BlobChannel("127.0.0.1", srv.port, request_channel(2))
    ch_resp = van.BlobChannel("127.0.0.1", srv.port, response_channel(2))
    try:
        ch_req.put(json.dumps({"max_tokens": 4}).encode(), 1)  # no prompt
        resp = json.loads(ch_resp.get(1, timeout_s=30))
        assert resp["status"] == "bad_request" and resp["tokens"] == []
        ch_req.put(json.dumps({"prompt": []}).encode(), 2)  # empty prompt
        resp = json.loads(ch_resp.get(2, timeout_s=30))
        assert resp["status"] == "bad_request" and resp["tokens"] == []
    finally:
        ch_req.close()
        ch_resp.close()


class _BoomEngine:
    """Engine double that admits and then always blows up — the
    'unexpected engine-loop exception' case the server must survive
    visibly."""

    class _Cache:
        num_slots = 2
        max_len = 16
        num_free = 2
        num_pages = 5
        occupancy = 0.0
        lengths = [0, 0]

    def __init__(self):
        from hetu_tpu.serve.metrics import ServeMetrics
        self.cache = self._Cache()
        self.metrics = ServeMetrics()

    def alloc_slot(self):
        return 0

    def release(self, slot):
        pass

    def admission_pages(self, prompt_len, max_tokens):
        return 1

    def admission_ok(self, prompt, max_tokens):
        return True

    def begin_prefill(self, slot, prompt, *, max_tokens=0):
        raise RuntimeError("boom: engine exploded mid-step")

    def prefill_step(self, slot):
        raise RuntimeError("boom: engine exploded mid-step")

    def decode(self):
        raise RuntimeError("boom: engine exploded mid-step")


def test_dead_engine_fails_requests_and_reports_unhealthy():
    """An engine whose step raises must NOT leave clients timing out with
    no diagnosis: with no failover grace (restart_engine will never come),
    in-flight requests get an 'error' response once the loop gives up
    after max_loop_errors consecutive failures, `healthy` flips False,
    and later requests fail fast instead of parking listeners."""
    sched = ContinuousBatchingScheduler(_BoomEngine())
    srv = InferenceServer(sched, max_clients=1, poll_s=0.05,
                          request_timeout_s=10.0, max_loop_errors=3,
                          failover_grace_s=0.0)
    client = InferenceClient("127.0.0.1", srv.port, 0)
    try:
        assert srv.healthy
        # every request fails with 'error' (never a hang, never a timeout);
        # a request can ride a PREVIOUS error's drain without triggering
        # its own step, so loop until the errors accumulate to death —
        # nothing ever resets the consecutive count (no step succeeds)
        deadline = time.monotonic() + 30
        while srv.healthy and time.monotonic() < deadline:
            resp = client.generate([1, 2, 3], max_tokens=4, timeout_s=20.0)
            assert resp["status"] == "error"
            assert resp["tokens"] == []
            time.sleep(0.05)
        assert not srv.healthy
        assert "boom" in srv.last_loop_error
        assert srv.metrics.count("engine_loop_errors") == 3
        assert srv.metrics.count("engine_loop_dead") == 1
        # dead engine: requests now fail fast (scheduler rejects with the
        # drain's 'error' status; nothing waits out a timeout)
        t0 = time.monotonic()
        resp = client.generate([4, 5], max_tokens=4, timeout_s=20.0)
        assert resp["status"] == "error"
        assert time.monotonic() - t0 < 5.0
    finally:
        client.close()
        srv.close()


class _FlakyEngine:
    """Proxy over a real engine that starts raising on command — the
    'engine crashed mid-decode' case the failover path must survive."""

    def __init__(self, inner):
        self.inner = inner
        self.dead = False
        self.decode_rounds = 0

    @property
    def cache(self):
        return self.inner.cache

    @property
    def metrics(self):
        return self.inner.metrics

    def _check(self):
        if self.dead:
            raise RuntimeError("flaky: engine crashed")

    def __getattr__(self, verb):
        """Every other verb the scheduler calls (admission, the two
        prefill verbs, slot alloc and release): dead once ``dead``."""
        self._check()
        return getattr(self.inner, verb)

    def decode(self):
        self._check()
        out = self.inner.decode()
        self.decode_rounds += 1
        return out


def test_engine_crash_restart_loses_zero_requests(gpt):
    """Kill the engine mid-generation, restart_engine a fresh one inside
    the grace window: every accepted request completes 'ok' with the
    token-for-token greedy answer (re-prefill from prompt + tokens
    emitted so far), and `healthy` recovers."""
    model, variables = gpt
    flaky = _FlakyEngine(_engine(model, variables))
    sched = ContinuousBatchingScheduler(flaky)
    srv = InferenceServer(sched, max_clients=3, poll_s=0.05,
                          request_timeout_s=120.0, max_loop_errors=2,
                          failover_grace_s=60.0)
    prompts = {0: [1, 2, 3], 1: [9, 8, 7, 6], 2: [42, 5]}
    results = {}
    errors = []

    def worker(cid):
        c = InferenceClient("127.0.0.1", srv.port, cid)
        try:
            results[cid] = c.generate(prompts[cid], max_tokens=12,
                                      timeout_s=120.0)
        except Exception as e:  # pragma: no cover - failure reporting
            errors.append((cid, repr(e)))
        finally:
            c.close()

    ts = [threading.Thread(target=worker, args=(cid,)) for cid in prompts]
    try:
        for t in ts:
            t.start()
        # let real decoding start, then crash the engine mid-flight
        deadline = time.monotonic() + 60
        while flaky.decode_rounds < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert flaky.decode_rounds >= 2, "engine never started decoding"
        flaky.dead = True
        while srv.healthy and time.monotonic() < deadline:
            time.sleep(0.02)
        assert not srv.healthy
        # restart inside the grace window: a FRESH engine adopts the queue
        srv.restart_engine(_engine(model, variables))
        assert srv.healthy
        for t in ts:
            t.join(120)
        assert not errors, errors
        # ZERO loss: every accepted request completed, token-for-token
        assert len(results) == 3
        for cid, resp in results.items():
            assert resp["status"] == "ok", (cid, resp)
            assert resp["tokens"] == _ref_greedy(model, variables,
                                                 prompts[cid], 12)
        assert sched.metrics.count("requests_requeued") >= 1
        assert sched.metrics.count("engine_restarts") == 1
    finally:
        srv.close()


class _SelectivePoisonEngine:
    """Proxy over a real engine whose ``verb`` (``begin_prefill``, at
    admission, or ``prefill_step``, at the prompt's first chunk) raises for
    ONE magic prompt — the 'poisoned request' that must fail alone, not
    kill the server."""

    def __init__(self, inner, verb):
        self.inner = inner
        self.verb = verb
        self.poisoned = set()  # slots holding the magic prompt

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def begin_prefill(self, slot, prompt, *, max_tokens=0):
        self.poisoned.discard(slot)
        if int(np.asarray(prompt).reshape(-1)[0]) == 66:
            if self.verb == "begin_prefill":
                raise RuntimeError("poisoned prompt")
            self.poisoned.add(slot)
        self.inner.begin_prefill(slot, prompt, max_tokens=max_tokens)

    def prefill_step(self, slot):
        if slot in self.poisoned:
            raise RuntimeError("poisoned prompt")
        return self.inner.prefill_step(slot)


def test_poisoned_request_fails_alone_server_stays_healthy(gpt):
    """A request whose admission deterministically raises is charged to the
    REQUEST (status 'error' after its requeue cap) while the engine keeps
    serving everyone else: no engine-loop strikes, `healthy` stays True."""
    model, variables = gpt
    eng = _SelectivePoisonEngine(_engine(model, variables), "begin_prefill")
    sched = ContinuousBatchingScheduler(eng)
    srv = InferenceServer(sched, max_clients=2, poll_s=0.05,
                          request_timeout_s=60.0, max_loop_errors=3)
    good = InferenceClient("127.0.0.1", srv.port, 0)
    bad = InferenceClient("127.0.0.1", srv.port, 1)
    try:
        r_bad = bad.generate([66, 2, 3], max_tokens=6, timeout_s=60.0)
        assert r_bad["status"] == "error"
        r_good = good.generate([5, 6, 7], max_tokens=6, timeout_s=60.0)
        assert r_good["status"] == "ok"
        assert r_good["tokens"] == _ref_greedy(model, variables,
                                               [5, 6, 7], 6)
        assert srv.healthy
        assert srv.metrics.count("engine_loop_dead") == 0
    finally:
        good.close()
        bad.close()
        srv.close()


def test_poisoned_chunk_is_charged_to_the_request_while_others_decode(gpt):
    """A request whose prefill CHUNK deterministically raises, one attempt
    a step: while another request decodes, every step makes progress, so
    none of the attempts raises out of ``step()`` (no engine-loop strike),
    the poisoned request ends 'error' at its requeue cap and the other one
    is served token for token."""
    model, variables = gpt
    eng = _SelectivePoisonEngine(_engine(model, variables), "prefill_step")
    sched = ContinuousBatchingScheduler(eng, max_requeues=3)
    good = sched.submit(Request(prompt=[5, 6, 7], max_tokens=20))
    while not good.tokens:
        sched.step()
    bad = sched.submit(Request(prompt=[66, 2, 3], max_tokens=6))
    for _ in range(8):
        sched.step()                     # raises on an engine-loop strike
    assert bad.status == "error" and bad.requeues == 4  # past the cap
    assert not good.done.is_set()        # it was decoding all the while
    sched.run([])
    assert good.status == "ok"
    assert good.tokens == _ref_greedy(model, variables, [5, 6, 7], 20)
    assert eng.cache.num_free == 2


def test_close_mid_grace_cannot_flip_state_after_shutdown():
    """Regression (ISSUE 5 satellite): close() while the failover-grace
    timer is armed must CANCEL it — a drained/closed server must never
    have the grace thread fire later and 'error'-drain (flipping the
    reject status) on the dead scheduler."""
    sched = ContinuousBatchingScheduler(_BoomEngine())
    srv = InferenceServer(sched, max_clients=0, poll_s=0.05,
                          max_loop_errors=1, failover_grace_s=0.6)
    try:
        sched.submit(Request(prompt=[1, 2], max_tokens=4, timeout_s=30.0))
        deadline = time.monotonic() + 30
        while srv.healthy and time.monotonic() < deadline:
            time.sleep(0.02)
        assert not srv.healthy  # engine dead, grace timer armed
    finally:
        srv.close()         # mid-grace
    time.sleep(1.0)         # past the grace expiry
    assert sched._reject_status == "shutdown"  # not flipped to 'error'
    assert srv.metrics.count("failover_expired") == 0
    late = sched.submit(Request(prompt=[3], max_tokens=2))
    assert late.status == "shutdown"


def test_cancel_grace_tolerates_armed_but_unstarted_thread():
    """Regression: _arm_failover_grace assigns the grace thread BEFORE
    start(), and a pool failover can call cancel_failover_grace inside
    that window — join() on a not-yet-started thread raises
    RuntimeError, which used to abort the whole failover with the dead
    member's queue stranded.  The disarm (the event set) must still
    happen and the cancel must not raise."""
    import threading
    sched = ContinuousBatchingScheduler(_BoomEngine())
    srv = InferenceServer(sched, max_clients=0, poll_s=0.05,
                          max_loop_errors=1, failover_grace_s=30.0)
    try:
        evt = srv._restart_evt
        srv._grace_thread = threading.Thread(target=lambda: None,
                                             daemon=True)
        srv.cancel_failover_grace()  # must not raise
        assert evt.is_set()          # the disarm still happened
    finally:
        srv.close()


def test_close_before_loop_death_sync_expiry_guarded():
    """The grace_s<=0 SYNC expiry path: a loop dying after close() began
    must not 'error'-drain over the shutdown drain."""
    sched = ContinuousBatchingScheduler(_BoomEngine())
    srv = InferenceServer(sched, max_clients=0, poll_s=0.05,
                          max_loop_errors=1, failover_grace_s=0.0)
    srv._stop.set()  # close() has begun; the loop may still be striking
    srv._arm_failover_grace()
    assert srv.metrics.count("failover_expired") == 0
    srv.close()


def test_duplicate_submit_same_id_dedups(server):
    """Idempotent resubmission (ISSUE 5 satellite): a client retrying a
    timed-out submit with the same request id must NOT double-generate —
    the server attaches the retry to the original request."""
    srv, model, variables = server
    ch_req = van.BlobChannel("127.0.0.1", srv.port, request_channel(2))
    ch_resp = van.BlobChannel("127.0.0.1", srv.port, response_channel(2))
    before = srv.metrics.count("requests_submitted")
    try:
        msg = json.dumps({"id": 7, "cn": "abc", "prompt": [1, 2, 3],
                          "max_tokens": 5}).encode()
        ch_req.put(msg, 1)
        ch_req.put(msg, 2)  # the retry: same id+nonce, next seq
        r1 = json.loads(ch_resp.get(1, timeout_s=60))
        r2 = json.loads(ch_resp.get(2, timeout_s=60))
        ref = _ref_greedy(model, variables, [1, 2, 3], 5)
        assert r1["status"] == "ok" and r1["tokens"] == ref
        assert r2["status"] == "ok" and r2["tokens"] == ref
        # ONE generation, ONE page reservation
        assert srv.metrics.count("requests_submitted") - before == 1
        assert srv.metrics.count("requests_deduped") == 1
        # a DIFFERENT id (or a restarted client's new nonce) is fresh
        ch_req.put(json.dumps({"id": 7, "cn": "xyz", "prompt": [4, 5],
                               "max_tokens": 3}).encode(), 3)
        r3 = json.loads(ch_resp.get(3, timeout_s=60))
        assert r3["tokens"] == _ref_greedy(model, variables, [4, 5], 3)
        assert srv.metrics.count("requests_submitted") - before == 2
    finally:
        ch_req.close()
        ch_resp.close()


def test_client_retries_timed_out_response_without_regenerating(server):
    """The client half: a response-wait timeout retries the SAME id at
    the next seq; the server dedups and the client still gets exactly
    the original answer."""
    srv, model, variables = server
    client = InferenceClient("127.0.0.1", srv.port, 1)
    try:
        calls = [0]
        orig_get = client._resp.get

        def flaky_get(seq, *, timeout_s=60.0):
            calls[0] += 1
            if calls[0] == 1:  # first wait "times out" on the wire
                raise TimeoutError("injected response timeout")
            return orig_get(seq, timeout_s=timeout_s)

        client._resp.get = flaky_get
        before = srv.metrics.count("requests_submitted")
        resp = client.generate([6, 5, 4], max_tokens=4, timeout_s=30.0,
                               wire_retries=2)
        assert resp["status"] == "ok"
        assert resp["tokens"] == _ref_greedy(model, variables,
                                             [6, 5, 4], 4)
        # exactly one generation, however the retry resolved (the grace
        # drain may catch the late answer before a resubmit is needed)
        assert srv.metrics.count("requests_submitted") - before == 1
    finally:
        client.close()


def test_van_stats_reset_across_serve_incarnations():
    """csrc satellite: g_frames_handled/g_bytes_rx/g_bytes_tx zero at
    serve() start, so OP_STATS really reads "since server start"."""
    port = van.serve(0)
    try:
        t = van.RemotePSTable("127.0.0.1", port, 8, 4, table_id=701,
                              init="zeros")
        t.sparse_pull(np.arange(8))
        t.close()
        s1 = van.stats("127.0.0.1", port)
        assert s1["frames"] > 2 and s1["bytes_rx"] > 0
    finally:
        van.stop()
    port = van.serve(0)
    try:
        s2 = van.stats("127.0.0.1", port)
        # only the probe's own frame has been counted in this incarnation
        assert s2["frames"] <= 2, s2
        assert s2["bytes_rx"] < s1["bytes_rx"], (s1, s2)
    finally:
        van.stop()
