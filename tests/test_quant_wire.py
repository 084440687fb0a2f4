"""Quantized wire everywhere (ISSUE 8): the shared int8/bf16 codecs and
the three bandwidth-bound paths that ride them.

* direct csrc q8 codec roundtrip through the python binding — error
  <= scale/2 per element, zero rows exactly zero, NaN/Inf clamp;
* RemotePSTable's negotiated gradient wire: parity, error-feedback
  convergence (int8 push-pull tracks the f32 wire at loss parity on a
  tiny CTR model over a REAL van server), telemetry byte counters, and
  the rc=-100 fallback to f32 against an old server;
* quantized_psum / quantized_pmean: exact f32 fallback, bounded int8
  error, and the Executor's grad_sync path converging at parity.
"""

import ctypes

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.ps import van
from hetu_tpu.ps.client import ErrorFeedback, q8_decode, q8_encode
from hetu_tpu import quantwire

pytestmark = pytest.mark.quant


# ---------------------------------------------------------------------------
# direct q8 codec (csrc, via the binding)
# ---------------------------------------------------------------------------

class TestQ8Codec:
    def test_roundtrip_error_within_half_scale(self):
        rng = np.random.default_rng(0)
        v = rng.normal(0, 3.0, (32, 24)).astype(np.float32)
        q, s = q8_encode(v)
        out = q8_decode(q, s)
        # symmetric per-row scheme: |err| <= scale/2 per element (round-
        # to-nearest of v/scale), scale = max|row|/127
        assert np.all(np.abs(out - v) <= s[:, None] / 2 + 1e-7)
        assert np.allclose(s, np.max(np.abs(v), axis=1) / 127.0)

    def test_zero_rows_stay_exactly_zero(self):
        v = np.zeros((3, 16), np.float32)
        q, s = q8_encode(v)
        assert np.all(q == 0) and np.all(s == 0)
        assert np.all(q8_decode(q, s) == 0.0)

    def test_nan_inf_clamp(self):
        v = np.array([[np.nan, np.inf, -np.inf, 2.0, -1.0]], np.float32)
        q, s = q8_encode(v)
        # scale from FINITE magnitudes only (2.0), NaN -> 0, Inf -> +/-127
        assert s[0] == pytest.approx(2.0 / 127.0)
        assert q[0, 0] == 0
        assert q[0, 1] == 127 and q[0, 2] == -127
        out = q8_decode(q, s)
        assert np.all(np.isfinite(out))
        assert out[0, 1] == pytest.approx(2.0) and \
            out[0, 2] == pytest.approx(-2.0)

    def test_all_nonfinite_row_decodes_to_zeros(self):
        v = np.full((1, 8), np.nan, np.float32)
        q, s = q8_encode(v)
        assert s[0] == 0.0
        assert np.all(q8_decode(q, s) == 0.0)

    def test_binding_rejects_bad_shape(self):
        from hetu_tpu.ps.binding import lib
        buf = np.zeros(4, np.float32)
        q = np.zeros(4, np.int8)
        rc = lib.ps_q8_encode(
            buf.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), 1, 0,
            q.ctypes.data_as(ctypes.POINTER(ctypes.c_int8)),
            buf.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
        assert rc == -3


class TestBlockCodec:
    def test_axes_roundtrip_error_bound(self):
        rng = np.random.default_rng(1)
        a = rng.normal(0, 2.0, (3, 7, 4, 8)).astype(np.float32)
        q, s = quantwire.q8_encode_axes(a, (1, 3))
        assert q.shape == a.shape and s.shape == (3, 1, 4, 1)
        out = quantwire.q8_decode_axes(q, s)
        assert np.all(np.abs(out - a) <= s / 2 + 1e-7)

    def test_axes_nonfinite(self):
        a = np.array([[1.0, np.nan], [np.inf, -2.0]], np.float32)
        q, s = quantwire.q8_encode_axes(a, (1,))
        out = quantwire.q8_decode_axes(q, s)
        assert np.all(np.isfinite(out))
        assert out[0, 1] == 0.0          # NaN -> 0
        assert out[1, 0] == pytest.approx(2.0)  # +Inf -> block max

    def test_wire_byte_formulas(self):
        assert quantwire.row_wire_bytes("f32", 10, 16) == 640
        assert quantwire.row_wire_bytes("bf16", 10, 16) == 320
        assert quantwire.row_wire_bytes("int8", 10, 16) == 200
        assert quantwire.block_wire_bytes(1024, "int8", 256) == 1024 + 16
        with pytest.raises(ValueError):
            quantwire.check_wire("fp4")


# ---------------------------------------------------------------------------
# error feedback
# ---------------------------------------------------------------------------

class TestErrorFeedback:
    def test_dense_residual_sums_to_truth(self):
        ef = ErrorFeedback(dim=8)
        rng = np.random.default_rng(2)
        g = rng.normal(0, 1, (4, 8)).astype(np.float32)
        applied = np.zeros_like(g)
        for _ in range(50):
            send = ef.fold_dense(g)
            q, s = q8_encode(send)
            rt = q8_decode(q, s)
            ef.absorb_dense(send, rt)
            applied += rt
        # total applied after N steps ~= N * g: the residual re-injects
        # the rounding error instead of losing it
        assert np.allclose(applied / 50, g, atol=np.max(np.abs(g)) / 200)

    def test_sparse_duplicate_ids_fold_once(self):
        ef = ErrorFeedback(dim=4)
        ef._sparse[7] = np.full(4, 0.5, np.float32)
        idx = np.array([7, 7, 3])
        g = np.zeros((3, 4), np.float32)
        out = ef.fold_sparse(idx, g)
        assert np.all(out[0] == 0.5) and np.all(out[1] == 0.0)

    def test_sparse_bound(self):
        ef = ErrorFeedback(dim=2, max_rows=3)
        for i in range(5):
            ef.absorb_sparse(np.array([i]),
                             np.ones((1, 2), np.float32),
                             np.zeros((1, 2), np.float32))
        assert len(ef._sparse) == 3
        assert set(ef._sparse) == {2, 3, 4}  # oldest dropped


# ---------------------------------------------------------------------------
# negotiated PS wire over a real van
# ---------------------------------------------------------------------------

@pytest.fixture
def van_port():
    port = van.serve(0)
    yield port
    van.stop()


class TestQuantizedPSWire:
    def test_int8_push_pull_tracks_f32(self, van_port):
        kw = dict(init="zeros", optimizer="sgd", lr=0.5)
        tf = van.RemotePSTable("127.0.0.1", van_port, 8, 16, seed=1, **kw)
        tq = van.RemotePSTable("127.0.0.1", van_port, 8, 16, seed=1,
                               wire="int8", **kw)
        g = np.random.default_rng(0).normal(0, 1, (8, 16)).astype(np.float32)
        for _ in range(30):
            tf.dense_push(g)
            tq.dense_push(g)
        wf, wq = tf.dense_pull(), tq.dense_pull()
        # error feedback: the cumulative update is within ~one quantum of
        # the f32 wire's (a no-feedback int8 wire drifts with sqrt(N))
        assert np.max(np.abs(wf - wq)) <= np.max(np.abs(wf)) * 0.02
        tf.close(); tq.close()

    def test_bf16_wire_dense_roundtrip(self, van_port):
        t = van.RemotePSTable("127.0.0.1", van_port, 4, 8, seed=3,
                              init="zeros", optimizer="sgd", lr=1.0,
                              wire="bf16")
        g = np.random.default_rng(1).normal(0, 1, (4, 8)).astype(np.float32)
        t.dense_push(g)
        got = t.dense_pull()
        # sgd lr=1: w = -g through two bf16 roundings (push + pull)
        assert np.allclose(got, -g, atol=np.max(np.abs(g)) / 64)
        t.close()

    def test_sparse_push_int8_applies(self, van_port):
        t = van.RemotePSTable("127.0.0.1", van_port, 16, 8, seed=5,
                              init="zeros", optimizer="sgd", lr=1.0,
                              wire="int8")
        idx = np.array([2, 9])
        g = np.array([[1.0] * 8, [-2.0] * 8], np.float32)
        t.sparse_push(idx, g)
        rows = t.sparse_pull(idx)
        assert np.allclose(rows, -g, atol=0.02)
        # untouched rows stay zero
        assert np.all(t.sparse_pull([0]) == 0.0)
        t.close()

    def test_wire_byte_counters(self, van_port):
        from hetu_tpu.telemetry import default_registry as reg
        t = van.RemotePSTable("127.0.0.1", van_port, 4, 32, seed=6,
                              init="zeros", optimizer="sgd", lr=0.1,
                              wire="int8")
        before = {n: m.value for n, m in reg.metrics().items()
                  if n.startswith("van.van_dense_push.bytes")}
        t.dense_push(np.ones((4, 32), np.float32))
        after = {n: m.value for n, m in reg.metrics().items()
                 if n.startswith("van.van_dense_push.bytes")}
        d = {n: after.get(n, 0) - before.get(n, 0) for n in after}
        assert d["van.van_dense_push.bytes_logical"] == 4 * 32 * 4
        assert d["van.van_dense_push.bytes_wire"] == 4 * (32 + 4)
        assert d["van.van_dense_push.bytes_saved"] == \
            4 * 32 * 4 - 4 * (32 + 4)
        assert d["van.van_dense_push.bytes"] == 4 * (32 + 4)
        # >= 3x reduction at dim 32: the acceptance number
        assert d["van.van_dense_push.bytes_logical"] >= \
            3 * d["van.van_dense_push.bytes_wire"]
        t.close()

    def test_old_server_negotiates_down_to_f32(self, van_port, monkeypatch):
        from hetu_tpu.ps import binding
        from hetu_tpu.telemetry import default_registry as reg
        t = van.RemotePSTable("127.0.0.1", van_port, 4, 8, seed=7,
                              init="zeros", optimizer="sgd", lr=1.0,
                              wire="int8")
        monkeypatch.setattr(binding.lib, "ps_van_dense_push_w",
                            lambda *a: -100, raising=False)
        g = np.full((4, 8), 0.125, np.float32)
        t.dense_push(g)  # falls back to the legacy f32 op, applied once
        assert t.wire is None and t._ef is None
        assert np.allclose(t.dense_pull(), -g)
        assert reg.counter("van.wire_negotiation.fallbacks").value >= 1
        # later pushes go straight to the legacy path (no repeated probe)
        t.dense_push(g)
        assert np.allclose(t.dense_pull(), -2 * g)
        t.close()

    def test_rejects_unknown_wire(self, van_port):
        with pytest.raises(ValueError, match="wire"):
            van.RemotePSTable("127.0.0.1", van_port, 4, 8, wire="fp4")


@pytest.mark.slow
class TestCTRLossParity:
    def test_int8_wire_loss_parity(self, van_port):
        """The tentpole's convergence claim: a tiny CTR model (logistic
        regression over sum-pooled embeddings) trained over the int8
        gradient wire (push AND dense pull quantized, error feedback on)
        lands within 2% of the f32-wire final loss on identical data."""
        V, D, F, B, STEPS = 500, 16, 4, 64, 120
        teacher = np.random.default_rng(42).normal(0, 1, V).astype(
            np.float32)

        def train(wire, port):
            emb = van.RemotePSTable("127.0.0.1", port, V, D, seed=7,
                                    init="normal", init_b=0.01,
                                    optimizer="adagrad", lr=0.1, wire=wire)
            wt = van.RemotePSTable("127.0.0.1", port, 1, D + 1, seed=8,
                                   init="zeros", optimizer="adagrad",
                                   lr=0.1, wire=wire)
            rng = np.random.default_rng(3)
            tail = []
            for step in range(STEPS):
                ids = rng.integers(0, V, (B, F))
                y = (teacher[ids].sum(1) > 0).astype(np.float32)
                x = emb.sparse_pull(ids.ravel()).reshape(B, F, D).sum(1)
                wb = wt.dense_pull()[0]
                p = 1.0 / (1.0 + np.exp(-(x @ wb[:D] + wb[D])))
                dlog = (p - y) / B
                wt.dense_push(np.concatenate(
                    [x.T @ dlog, [dlog.sum()]])[None, :])
                emb.sparse_push(
                    ids.ravel(),
                    (dlog[:, None] * wb[None, :D])[:, None, :].repeat(
                        F, axis=1).reshape(B * F, D))
                if step >= STEPS - 20:
                    eps = 1e-7
                    tail.append(float(np.mean(
                        -y * np.log(p + eps)
                        - (1 - y) * np.log(1 - p + eps))))
            emb.close(); wt.close()
            return float(np.mean(tail))

        loss_f32 = train(None, van_port)
        loss_int8 = train("int8", van_port)
        assert loss_int8 < 0.6  # it actually learned (chance ~0.693)
        assert abs(loss_int8 - loss_f32) <= 0.02 * abs(loss_f32)


# ---------------------------------------------------------------------------
# quantized collectives + executor grad sync
# ---------------------------------------------------------------------------

def _dp_mesh():
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()), ("dp",))


class TestQuantizedPsum:
    def _run(self, x, **kw):
        from functools import partial

        from hetu_tpu.parallel import collectives as coll
        from jax.sharding import PartitionSpec as P
        mesh = _dp_mesh()

        @partial(coll.shard_map, mesh=mesh, in_specs=P("dp"),
                 out_specs=P(), check_vma=False)
        def f(x):
            return coll.quantized_psum(x, "dp", **kw)

        return np.asarray(jax.jit(f)(x))

    def test_f32_fallback_is_exact(self):
        rng = np.random.default_rng(0)
        x = rng.normal(0, 1, (8, 64)).astype(np.float32)
        got = self._run(x, wire="f32")
        assert np.allclose(got, x.sum(0), atol=1e-5)

    def test_int8_error_bounded(self):
        rng = np.random.default_rng(1)
        n = len(jax.devices())
        x = rng.normal(0, 0.05, (n, 1000)).astype(np.float32)
        exact = x.sum(0)
        got = self._run(x, wire="int8", block=128)
        # each replica contributes <= half a quantum of error per element:
        # quantum = blockmax/127, so |err| <= n * max|x| / 254
        bound = n * np.max(np.abs(x)) / 254 + 1e-6
        assert np.max(np.abs(got - exact)) <= bound

    def test_bf16_error_small(self):
        rng = np.random.default_rng(2)
        n = len(jax.devices())
        x = rng.normal(0, 1, (n, 257)).astype(np.float32)  # odd size
        exact = x.sum(0)
        got = self._run(x, wire="bf16")
        assert np.max(np.abs(got - exact)) <= n * np.max(np.abs(x)) / 128

    def test_pmean_and_bad_wire(self):
        from functools import partial

        from hetu_tpu.parallel import collectives as coll
        from jax.sharding import PartitionSpec as P
        mesh = _dp_mesh()
        x = np.ones((len(jax.devices()), 8), np.float32)

        @partial(coll.shard_map, mesh=mesh, in_specs=P("dp"),
                 out_specs=P(), check_vma=False)
        def f(x):
            return coll.quantized_pmean(x, "dp", wire="int8")

        assert np.allclose(np.asarray(jax.jit(f)(x)), 1.0, atol=0.01)
        with pytest.raises(ValueError, match="wire"):
            self._run(x, wire="fp4")


@pytest.mark.slow
class TestExecutorGradSync:
    def _setup(self):
        rng = np.random.default_rng(0)
        W = rng.normal(size=(16, 1)).astype(np.float32)
        x = rng.normal(size=(64, 16)).astype(np.float32)
        batch = {"x": x, "y": x @ W + 0.01 * rng.normal(
            size=(64, 1)).astype(np.float32)}
        variables = {"params": {"w": jnp.zeros((16, 1)),
                                "b": jnp.zeros((1,))}}

        def loss_fn(params, state, b, rng_, train):
            pred = b["x"] @ params["w"] + params["b"]
            loss = jnp.mean((pred - b["y"]) ** 2)
            return loss, ({"mse": loss}, state)

        return loss_fn, variables, batch

    def _train(self, grad_sync, steps=50):
        from hetu_tpu.optim.optimizer import SGDOptimizer
        from hetu_tpu.train.executor import Executor
        loss_fn, variables, batch = self._setup()
        ex = Executor(loss_fn, SGDOptimizer(0.1), mesh=_dp_mesh(),
                      dp_axis="dp", grad_sync=grad_sync)
        st = ex.init_state(variables)
        m = None
        for _ in range(steps):
            st, m = ex.run("train", st, batch)
        return float(m["loss"])

    def test_int8_grad_sync_loss_parity(self):
        exact = self._train("exact")
        quant = self._train("int8")
        assert quant <= max(2 * exact, exact + 1e-4)

    def test_per_param_callable_and_counters(self):
        from hetu_tpu.telemetry import default_registry as reg
        c0 = reg.counter("train.grad_sync.bytes_wire").value
        loss = self._train(lambda p: "int8" if "w" in p else "f32",
                           steps=5)
        assert np.isfinite(loss)
        d = reg.counter("train.grad_sync.bytes_wire").value - c0
        # 5 steps x (w: 16 int8 + 1 scale, b: 1 f32 elt)
        assert d == 5 * ((16 + 4) + 4)

    def test_quant_sync_requires_mesh(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from hetu_tpu.optim.optimizer import SGDOptimizer
        from hetu_tpu.train.executor import Executor
        loss_fn, _, _ = self._setup()
        with pytest.raises(ValueError, match="mesh"):
            Executor(loss_fn, SGDOptimizer(0.1), grad_sync="int8")
        with pytest.raises(ValueError, match="grad_sync"):
            Executor(loss_fn, SGDOptimizer(0.1), mesh=_dp_mesh(),
                     grad_sync="fp4")
        # quantized sync declares params replicated in its shard_map —
        # sharded-parameter setups must be refused, not silently gathered
        mesh = _dp_mesh()
        with pytest.raises(ValueError, match="replicated"):
            Executor(loss_fn, SGDOptimizer(0.1), mesh=mesh,
                     grad_sync="int8",
                     param_sharding=NamedSharding(mesh, P("dp")))
