"""The Lightning rule (``S <- lambda_h S + k v^T``, ``o = S^T q``: linear
attention with a constant decay a head) as ``models/minicpm_sala.py`` runs it
through ``ops/ssm.py``'s recurrence (``dt`` = 1, ``A`` = the negated decay
rate, ``x`` = v, ``B`` = k, ``C`` = q, ``D`` = 0, a group a head): the chunk
scan = the step = the naive recurrence, across chunk edges, from a carried
state and with ``last``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.ops import ssm

B, H, D = 2, 4, 8


def rows(s: int, seed: int = 0):
    q, k, v = (jax.random.normal(key, (B, s, H, D), jnp.float32)
               for key in jax.random.split(jax.random.PRNGKey(seed), 3))
    rates = 2.0 ** (-8.0 * jnp.arange(1, H + 1) / H) * 0.7
    return q * D ** -0.5, k, v, rates


def naive(q, k, v, rates, state=None):
    """Row by row: S [b, h, d_k, d_v] float32."""
    lam = np.exp(-np.asarray(rates, np.float64))[None, :, None, None]
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    S = np.zeros((B, H, D, D)) if state is None else np.asarray(state)
    out = []
    for t in range(q.shape[1]):
        S = lam * S + k[:, t, :, :, None] * v[:, t, :, None, :]
        out.append(np.einsum("bhkv,bhk->bhv", S, q[:, t]))
    return np.stack(out, 1), S


def scan(q, k, v, rates, state=None, **kw):
    """The rule through ``ssd_chunk_scan``; its state is [b, h, d_v, d_k]."""
    b, s = q.shape[:2]
    return ssm.ssd_chunk_scan(
        v, jnp.ones((b, s, H), jnp.float32), -rates, k, q,
        jnp.zeros((H,), jnp.float32), state, **kw)


@pytest.mark.parametrize("s,chunk", [(37, 8), (16, 8), (5, 8), (40, 16)])
def test_the_chunk_scan_is_the_naive_recurrence(s, chunk):
    q, k, v, rates = rows(s)
    want, S = naive(q, k, v, rates)
    got, state = scan(q, k, v, rates, chunk=chunk)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(jnp.swapaxes(state, -1, -2), S, rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("cut,last", [(20, None), (13, 9), (8, 0)])
def test_a_carried_state_and_last_continue_the_sequence(cut, last):
    """Two calls, the first padded past its ``last`` real row: what the
    second starts from is the state after that row, and its rows are the
    whole sequence's."""
    real = cut if last is None else last + 1
    q, k, v, rates = rows(real + 17, seed=1)
    want, S = naive(q, k, v, rates)
    pad = lambda a: jnp.concatenate(                       # noqa: E731
        [a[:, :real], 9.0 * jnp.ones((B, cut - real, H, D))], 1)
    first, state = scan(pad(q), pad(k), pad(v), rates, chunk=8, last=last)
    rest, state = scan(q[:, real:], k[:, real:], v[:, real:], rates, state,
                       chunk=8)
    np.testing.assert_allclose(first[:, :real], want[:, :real], rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(rest, want[:, real:], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(jnp.swapaxes(state, -1, -2), S, rtol=2e-5,
                               atol=2e-5)


def test_the_step_is_the_naive_recurrence_and_leaves_idle_rows_alone():
    q, k, v, rates = rows(11, seed=2)
    want, S = naive(q, k, v, rates)
    state = jnp.zeros((B, H, D, D), jnp.float32)
    for t in range(11):
        # the second sequence sits out round 5: dt = 0 neither decays nor
        # feeds its matrix
        dt = jnp.ones((B, H)).at[1].set(0.0 if t == 5 else 1.0)
        before = state
        o, state = ssm.ssm_step(v[:, t], dt, -rates, k[:, t], q[:, t],
                                jnp.zeros((H,)), state)
        if t == 5:
            np.testing.assert_array_equal(state[1], before[1])
            _, state = ssm.ssm_step(
                v[:, t], jnp.ones((B, H)), -rates, k[:, t], q[:, t],
                jnp.zeros((H,)), before)
        else:
            np.testing.assert_allclose(o, want[:, t], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(jnp.swapaxes(state, -1, -2), S, rtol=2e-5,
                               atol=2e-5)


def test_scan_then_steps_is_one_sequence():
    q, k, v, rates = rows(30, seed=3)
    want, _ = naive(q, k, v, rates)
    _, state = scan(q[:, :19], k[:, :19], v[:, :19], rates, chunk=8)
    for t in range(19, 30):
        o, state = ssm.ssm_step(v[:, t], jnp.ones((B, H)), -rates, k[:, t],
                                q[:, t], jnp.zeros((H,)), state)
        np.testing.assert_allclose(o, want[:, t], rtol=3e-5, atol=3e-5)
