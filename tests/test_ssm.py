"""``ops/ssm.py`` against the recurrence written out a row at a time: the
chunked scan, the one-row step folded over the rows and the reference agree
from a state that is not zero, over groups of heads, for ``last`` anywhere
and for row counts under, at and over the scan's chunk; the causal
convolution over ``[state | rows]``; the gated norm over groups."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.ops import ssm

H, P, N, G = 4, 8, 16, 2


def case(b: int, s: int, seed: int = 0, state: bool = True):
    r = np.random.default_rng(seed)
    f = lambda *sh: jnp.asarray(r.standard_normal(sh), jnp.float32)
    return dict(
        x=f(b, s, H, P), dt=jnp.asarray(r.uniform(0.01, 0.6, (b, s, H)),
                                        jnp.float32),
        A=-jnp.asarray(r.uniform(0.5, 4.0, (H,)), jnp.float32),
        B=f(b, s, G, N), C=f(b, s, G, N), D=f(H),
        state=f(b, H, P, N) if state else None)


def by_rows(c, last=None):
    """The recurrence a row at a time in numpy float64: (y, the state after
    row ``last``)."""
    x, dt, A, B, C, D = (np.asarray(c[k], np.float64)
                         for k in ("x", "dt", "A", "B", "C", "D"))
    b, s = x.shape[:2]
    S = np.zeros((b, H, P, N)) if c["state"] is None \
        else np.asarray(c["state"], np.float64)
    rep = H // G
    y = np.zeros((b, s, H, P))
    kept = S.copy()
    for t in range(s):
        Bt, Ct = np.repeat(B[:, t], rep, 1), np.repeat(C[:, t], rep, 1)
        S = np.exp(dt[:, t] * A)[..., None, None] * S \
            + (dt[:, t, :, None] * x[:, t])[..., None] * Bt[:, :, None, :]
        y[:, t] = (S * Ct[:, :, None, :]).sum(-1) + D[:, None] * x[:, t]
        if t == (s - 1 if last is None else last):
            kept = S.copy()
    return y, kept


@pytest.mark.parametrize("s,chunk", [(5, 8), (8, 8), (24, 8), (21, 8),
                                     (16, 128), (33, 4)])
def test_the_chunked_scan_is_the_recurrence(s, chunk):
    """Under the chunk (one short chunk), at it, a multiple of it, and a row
    count the chunk does not divide (padded inside)."""
    c = case(2, s, seed=s)
    y, state = jax.jit(lambda c: ssm.ssd_chunk_scan(
        c["x"], c["dt"], c["A"], c["B"], c["C"], c["D"], c["state"],
        chunk=chunk))(c)
    want_y, want_state = by_rows(c)
    np.testing.assert_allclose(y, want_y, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(state, want_state, rtol=2e-4, atol=2e-4)
    assert state.dtype == jnp.float32 and y.dtype == c["x"].dtype


@pytest.mark.parametrize("last", [0, 3, 7, 8, 12, 23])
def test_rows_past_last_neither_decay_nor_feed_the_state(last):
    """A chunk padded to its bucket: the state left is the one after the
    last REAL row, wherever in a chunk that is, and the real rows' results
    are the recurrence's."""
    c = case(1, 24, seed=last)
    y, state = jax.jit(lambda c, n: ssm.ssd_chunk_scan(
        c["x"], c["dt"], c["A"], c["B"], c["C"], c["D"], c["state"],
        chunk=8, last=n))(c, jnp.int32(last))
    want_y, want_state = by_rows(c, last)
    np.testing.assert_allclose(state, want_state, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(y[:, :last + 1], want_y[:, :last + 1],
                               rtol=2e-4, atol=2e-4)


def test_from_no_state_the_scan_starts_at_zeros():
    c = case(2, 12, seed=5, state=False)
    y, state = ssm.ssd_chunk_scan(c["x"], c["dt"], c["A"], c["B"], c["C"],
                                  c["D"], None, chunk=4)
    want_y, want_state = by_rows(c)
    np.testing.assert_allclose(y, want_y, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(state, want_state, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("s", [1, 9])
def test_the_step_folded_over_the_rows_is_the_scan(s):
    c = case(3, s, seed=40 + s)
    state, ys = c["state"], []
    for t in range(s):
        y, state = ssm.ssm_step(c["x"][:, t], c["dt"][:, t], c["A"],
                                c["B"][:, t], c["C"][:, t], c["D"], state)
        ys.append(y)
    y2, state2 = ssm.ssd_chunk_scan(c["x"], c["dt"], c["A"], c["B"], c["C"],
                                    c["D"], c["state"], chunk=4)
    np.testing.assert_allclose(jnp.stack(ys, 1), y2, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(state, state2, rtol=2e-4, atol=2e-4)
    want_y, want_state = by_rows(c)
    np.testing.assert_allclose(state, want_state, rtol=2e-4, atol=2e-4)


def test_a_step_with_no_time_leaves_the_state_as_it_is():
    """``dt`` = 0 is how a decode round passes over a slot of no sequence:
    bit for bit."""
    c = case(2, 1, seed=9)
    _, state = ssm.ssm_step(c["x"][:, 0], jnp.zeros((2, H)), c["A"],
                            c["B"][:, 0], c["C"][:, 0], c["D"], c["state"])
    np.testing.assert_array_equal(state, c["state"])


def test_heads_read_their_own_groups_b_and_c():
    """Changing group 1's B and C moves the heads of group 1 alone."""
    c = case(1, 6, seed=2)
    other = dict(c, B=c["B"].at[:, :, 1].add(1.0),
                 C=c["C"].at[:, :, 1].add(1.0))
    run = lambda c: ssm.ssd_chunk_scan(c["x"], c["dt"], c["A"], c["B"],
                                       c["C"], c["D"], c["state"], chunk=4)[0]
    a, b = np.asarray(run(c)), np.asarray(run(other))
    np.testing.assert_array_equal(a[:, :, :H // G], b[:, :, :H // G])
    assert np.abs(a[:, :, H // G:] - b[:, :, H // G:]).max() > 0.1


def test_bfloat16_rows_keep_a_float32_state():
    c = case(2, 16, seed=3)
    low = {k: (v.astype(jnp.bfloat16) if k in ("x", "B", "C") else v)
           for k, v in c.items()}
    y, state = ssm.ssd_chunk_scan(low["x"], low["dt"], low["A"], low["B"],
                                  low["C"], low["D"], low["state"], chunk=8)
    assert y.dtype == jnp.bfloat16 and state.dtype == jnp.float32
    want_y, want_state = by_rows(c)
    assert np.abs(np.asarray(state) - want_state).max() \
        < 0.05 * np.abs(want_state).max()
    assert np.abs(np.asarray(y, np.float32) - want_y).max() \
        < 0.05 * np.abs(want_y).max()


# ---- the causal convolution ----

def conv_by_rows(rows, taps, bias, before):
    k = taps.shape[0]
    both = np.concatenate([before, rows], 1)
    return np.stack([sum(taps[j] * both[:, t + j] for j in range(k)) + bias
                     for t in range(rows.shape[1])], 1)


@pytest.mark.parametrize("k", [2, 3, 4])
def test_the_convolution_is_taps_shifted_products_over_state_and_rows(k):
    r = np.random.default_rng(k)
    rows, taps, bias, before = (r.standard_normal(s).astype(np.float32)
                                for s in ((2, 9, 6), (k, 6), (6,),
                                          (2, k - 1, 6)))
    out, keep = ssm.causal_conv(jnp.asarray(rows), jnp.asarray(taps),
                                jnp.asarray(bias), jnp.asarray(before))
    np.testing.assert_allclose(out, conv_by_rows(rows, taps, bias, before),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(keep, rows[:, 9 - (k - 1):])
    # from nothing: zeros before the sequence, no bias
    out0, _ = ssm.causal_conv(jnp.asarray(rows), jnp.asarray(taps))
    np.testing.assert_allclose(
        out0, conv_by_rows(rows, taps, 0.0, np.zeros_like(before)),
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("last", [0, 1, 4, 8])
def test_the_convolution_keeps_the_rows_ending_at_last(last):
    """``last`` under ``taps - 1`` reaches back into the state handed in."""
    r = np.random.default_rng(last)
    rows, taps, before = (r.standard_normal(s).astype(np.float32)
                          for s in ((1, 9, 5), (4, 5), (1, 3, 5)))
    _, keep = jax.jit(ssm.causal_conv)(
        jnp.asarray(rows), jnp.asarray(taps), None, jnp.asarray(before),
        jnp.int32(last))
    both = np.concatenate([before, rows], 1)
    np.testing.assert_array_equal(keep, both[:, last + 1:last + 4])


def test_two_calls_of_the_convolution_are_one():
    r = np.random.default_rng(7)
    rows, taps, bias = (jnp.asarray(r.standard_normal(s), jnp.float32)
                        for s in ((2, 12, 5), (4, 5), (5,)))
    whole, _ = ssm.causal_conv(rows, taps, bias)
    first, keep = ssm.causal_conv(rows[:, :7], taps, bias)
    second, _ = ssm.causal_conv(rows[:, 7:], taps, bias, keep)
    np.testing.assert_allclose(jnp.concatenate([first, second], 1), whole,
                               rtol=1e-6, atol=1e-6)


# ---- the gated norm ----

@pytest.mark.parametrize("groups", [1, 2, 4])
def test_the_gated_norm_gates_first_and_normalises_a_group(groups):
    r = np.random.default_rng(groups)
    y, z, w = (r.standard_normal(s).astype(np.float32)
               for s in ((2, 3, 16), (2, 3, 16), (16,)))
    got = ssm.gated_group_rms_norm(jnp.asarray(y), jnp.asarray(z),
                                   jnp.asarray(w), groups=groups, eps=1e-5)
    v = (y * z / (1 + np.exp(-z))).reshape(2, 3, groups, -1)
    v = v / np.sqrt((v * v).mean(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(got, v.reshape(2, 3, 16) * w, rtol=1e-5,
                               atol=1e-5)
