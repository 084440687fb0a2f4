"""``ops/delta_rule.py`` against the recurrence written row by row in numpy
(float64): (a) the chunked form for lengths that the chunk does not divide,
from a state that is not zero, with ``last`` inside a padded chunk; (b) the
step as one row of (a); the inverse by its finite product; rows that change
nothing; bfloat16 rows over a float32 state."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.ops import delta_rule

TOL = 1e-5
HK, HV, DK, DV = 2, 4, 8, 6


def draw(seed: int, b: int, s: int, state: bool = True):
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(b, s, HK, DK))
    k = rng.normal(size=(b, s, HK, DK))
    v = rng.normal(size=(b, s, HV, DV))
    # decays from 0.999 a row down to exp(-3): both ends of a trained head
    g = -np.exp(rng.uniform(np.log(1e-3), np.log(3.0), (b, s, HV)))
    beta = rng.uniform(0.0, 1.0, (b, s, HV))
    st = rng.normal(size=(b, HV, DK, DV)) if state else None
    return q, k, v, g, beta, st


def by_rows(q, k, v, g, beta, state, upto=None):
    """The recurrence a row at a time, float64: (o [b, s, h_v, d_v], the
    state after row ``upto``)."""
    b, s = q.shape[:2]
    rep = HV // HK
    S = np.zeros((b, HV, DK, DV)) if state is None else state.copy()
    out = np.zeros((b, s, HV, DV))
    kept = None
    unit = lambda x: x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)
    for t in range(s):
        qt = np.repeat(unit(q[:, t]) / np.sqrt(DK), rep, axis=1)
        kt = np.repeat(unit(k[:, t]), rep, axis=1)
        S = S * np.exp(g[:, t])[..., None, None]
        d = beta[:, t][..., None] * (v[:, t] - np.einsum("bhkd,bhk->bhd", S,
                                                         kt))
        S = S + kt[..., None] * d[..., None, :]
        out[:, t] = np.einsum("bhkd,bhk->bhd", S, qt)
        if t == (s - 1 if upto is None else upto):
            kept = S.copy()
    return out, kept


def f32(*arrays):
    return tuple(None if a is None else jnp.asarray(a, jnp.float32)
                 for a in arrays)


@pytest.mark.parametrize("s,chunk,state", [
    (16, 8, True),      # two whole chunks
    (21, 8, True),      # the chunk does not divide: the third is padded
    (5, 8, True),       # one short chunk
    (64, 64, False),    # the published chunk, from zeros
    (150, 64, True),    # ... and over it, not a multiple
    (1, 8, True),       # one row
])
def test_the_chunked_rule_is_the_recurrence(s, chunk, state):
    args = draw(s, 2, s, state)
    want, kept = by_rows(*args)
    o, st = jax.jit(lambda *a: delta_rule.gated_delta_chunk_scan(
        *a, chunk=chunk))(*f32(*args))
    assert o.dtype == jnp.float32 and st.dtype == jnp.float32
    np.testing.assert_allclose(o, want, atol=TOL, rtol=TOL)
    np.testing.assert_allclose(st, kept, atol=TOL, rtol=TOL)


@pytest.mark.parametrize("s,chunk,last", [
    (16, 8, 10),    # the last real row inside the second chunk
    (16, 8, 3),     # ... inside the first: the second is all padding
    (24, 8, 23),    # the call's last row, said
    (13, 8, 0),     # one real row
])
def test_rows_past_last_leave_the_state_after_the_last_real_row(s, chunk,
                                                                last):
    args = draw(7 * s + last, 2, s)
    want, kept = by_rows(*args, upto=last)
    o, st = jax.jit(lambda *a: delta_rule.gated_delta_chunk_scan(
        *a[:6], chunk=chunk, last=a[6]))(*f32(*args), jnp.int32(last))
    np.testing.assert_allclose(o[:, :last + 1], want[:, :last + 1],
                               atol=TOL, rtol=TOL)
    np.testing.assert_allclose(st, kept, atol=TOL, rtol=TOL)


def test_chunks_hand_the_state_on_as_one_call_does():
    """A sequence in three calls of unequal length, each from the state the
    one before left, is the sequence in one call."""
    args = draw(11, 1, 37)
    q, k, v, g, beta, st = f32(*args)
    whole, end = delta_rule.gated_delta_chunk_scan(q, k, v, g, beta, st,
                                                   chunk=8)
    outs = []
    for lo, hi in ((0, 8), (8, 29), (29, 37)):
        o, st = delta_rule.gated_delta_chunk_scan(
            q[:, lo:hi], k[:, lo:hi], v[:, lo:hi], g[:, lo:hi],
            beta[:, lo:hi], st, chunk=8)
        outs.append(o)
    np.testing.assert_allclose(jnp.concatenate(outs, 1), whole, atol=TOL,
                               rtol=TOL)
    np.testing.assert_allclose(st, end, atol=TOL, rtol=TOL)


def test_the_step_is_one_row_of_the_recurrence():
    args = draw(5, 3, 4)
    want, _ = by_rows(*args)
    q, k, v, g, beta, st = f32(*args)
    step = jax.jit(delta_rule.gated_delta_step)
    for t in range(4):
        o, st = step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], st)
        np.testing.assert_allclose(o, want[:, t], atol=TOL, rtol=TOL)
    # and of the chunked form: one call of four rows ends where four steps do
    _, end = delta_rule.gated_delta_chunk_scan(*f32(*args), chunk=64)
    np.testing.assert_allclose(st, end, atol=TOL, rtol=TOL)


def test_a_row_that_neither_decays_nor_writes_leaves_its_state_bit_for_bit():
    q, k, v, g, beta, st = f32(*draw(9, 5, 1))
    live = jnp.asarray([True, False, True, False, False])
    g = jnp.where(live[:, None], g[:, 0], 0.0)
    beta = jnp.where(live[:, None], beta[:, 0], 0.0)
    # an idle slot's inputs are zeros (SlotStates.spread)
    q, k, v = (jnp.where(live[:, None, None], a[:, 0], 0.0) for a in (q, k, v))
    _, new = jax.jit(delta_rule.gated_delta_step)(q, k, v, g, beta, st)
    idle = np.asarray(~live)
    np.testing.assert_array_equal(np.asarray(new)[idle], np.asarray(st)[idle])
    assert not np.array_equal(np.asarray(new)[~idle], np.asarray(st)[~idle])


def test_the_inverse_is_the_finite_product():
    rng = np.random.default_rng(2)
    for c in (1, 2, 5, 8, 64):
        low = np.tril(rng.normal(size=(3, c, c)) * 0.3, -1)
        inv = delta_rule.unit_lower_inverse(jnp.asarray(low, jnp.float32))
        np.testing.assert_allclose(
            inv, np.linalg.inv(np.eye(c) + low), atol=2e-4, rtol=2e-4)
    assert delta_rule.SOLVE == "product"


def test_bfloat16_rows_keep_a_float32_state_and_stay_near():
    args = draw(4, 2, 40)
    want, kept = by_rows(*args)
    q, k, v, g, beta, st = f32(*args)
    low = tuple(a.astype(jnp.bfloat16) for a in (q, k, v))
    o, end = delta_rule.gated_delta_chunk_scan(*low, g, beta, st, chunk=8)
    assert o.dtype == jnp.bfloat16 and end.dtype == jnp.float32
    err = np.abs(np.asarray(o, np.float32) - want).max() / np.abs(want).max()
    assert 1e-4 < err < 0.05
    assert np.abs(np.asarray(end) - kept).max() / np.abs(kept).max() < 0.05
