"""The gated delta rule (Gated DeltaNet) as a server runs it.

One value head keeps a MATRIX of a sequence, ``S`` [d_k, d_v], whatever the
sequence's length; a row decays it, reads it, and writes the part of its
value that the state did not already hold under its key::

    S   <- exp(g_t) S                                   g_t <= 0
    d_t  = beta_t (v_t - S^T k_t)                       0 <= beta_t <= 1
    S   <- S + k_t d_t^T
    o_t  = S^T q_t

``q_t`` and ``k_t`` [d_k] are the head's query and key, each brought to unit
length here (``x / sqrt(sum x^2 + 1e-6)``) and the query then scaled by ``1 /
sqrt(d_k)``, so that a caller hands over what its projections give; ``v_t``
[d_v]; a key head serves ``h_v / h_k`` value heads.  Unlike ``ops/ssm.py``'s
recurrence (a decay and a rank-one add: a row never reads what an earlier row
of its chunk wrote) the update is NOT diagonal: ``d_t`` depends on the state
that the rows before ``t`` left, so inside a chunk the rows' ``d`` solve a
unit lower triangular system.  With ``gamma_i`` the sum of ``g`` over the
chunk's rows up to ``i`` and ``S`` the state the chunk is handed::

    L_ij = beta_i (k_i . k_j) exp(gamma_i - gamma_j)   j < i;  0 elsewhere
    T    = (I + L)^-1
    U    = T diag(beta) V          W = T diag(beta exp(gamma)) K
    D    = U - W S                                      # the rows' d
    O    = (Q * exp(gamma)) S + (Q K^T * exp(gamma_i - gamma_j))_(j <= i) D
    S   <- exp(gamma_C) S + (K * exp(gamma_C - gamma))^T D

(Yang, Kautz, Hatamizadeh, "Gated Delta Networks", arXiv:2412.06464; the WY /
UT form.)  ``L`` is strictly lower triangular, so ``L^C = 0`` and ``T`` is
the finite product ``(I - L)(I + L^2)(I + L^4) ...`` of ``log2 C`` factors:
:data:`SOLVE` names it, ten 64 x 64 matmuls a chunk a head at ``C`` = 64 and
no row-by-row substitution.  Every exponent taken is of a difference that is
<= 0.

Both serving forms, from a state handed in and handing the new one back in
float32:

* :func:`gated_delta_chunk_scan`: many rows of one sequence; ``T``, ``U``,
  ``W`` and the masked ``Q K^T`` of every chunk at once, then a ``lax.scan``
  over the chunks that carries the state (three matmuls against it a chunk).
  Rows past ``last`` get ``g`` = 0 and ``beta`` = 0: they neither decay the
  state nor write it, so the state left is the one after row ``last``, which
  is what a prefill chunk padded to its bucket needs (``ssd_chunk_scan``'s
  rule).
* :func:`gated_delta_step`: one row a sequence, a decode round: the state is
  read (``S^T k``) BEFORE it is written.  A row of ``g`` = 0, ``beta`` = 0
  leaves its state bit for bit.

**A decay a key channel** (Kimi Delta Attention, arXiv:2510.26692:
:func:`kda_chunk_scan`, :func:`kda_step`): ``S <- Diag(exp(g_t)) S`` with
``g_t`` [d_k], every row of the matrix forgetting at its own rate.  The
chunk's ``L_ij = beta_i sum_c k_ic k_jc exp(Gamma_ic - Gamma_jc)`` no longer
factors into ``(K K^T) * exp(gamma_i - gamma_j)``: the decays are folded into
the operands, ``k_i * exp(Gamma_i - Gamma_ref)`` against ``k_j *
exp(Gamma_ref - Gamma_j)``, and the second factor GROWS with ``j`` past the
reference row.  So a chunk's rows are taken in SUB-BLOCKS of ``sub`` rows,
each with its own reference, its first row: against the keys of earlier
sub-blocks both exponents are <= 0, inside the sub-block the second is at
most ``(sub - 1) |g|_max`` (a caller bounds ``g`` below: 15 x 5 = 75 at
GLM-5.3's ``gate_lower_bound``, e^75 = 3.7e32, inside float32), and a pair
the mask drops (a later sub-block's key) is never exponentiated.  The
sub-blocks' ``L`` are ONE [C, C] system a chunk, solved and chained through
the carried state as above; every other exponent taken is of a number <= 0.

Plain ``jax.numpy`` / ``lax``.  The decays, their sums, ``L``, ``T``, ``U``,
``W`` and the state are float32 whatever the rows' dtype (the float32
matmuls at the highest precision: on a TPU a float32 product is otherwise
rounded to bfloat16 passes); the products over the rows and against the
state take their operands in the rows' dtype and sum in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
# how T = (I + L)^-1 is made, as the ``gdn.plan`` instant states it
SOLVE = "product"


def unit_rows(x, eps: float = 1e-6):
    """``x / sqrt(sum x^2 + eps)`` over the last axis, float32."""
    xf = x.astype(F32)
    return xf * jax.lax.rsqrt(jnp.sum(xf * xf, -1, keepdims=True) + eps)


def _f32_matmul(spec: str, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=F32)


def unit_lower_inverse(low):
    """``(I + low)^-1`` for ``low`` [..., C, C] strictly lower triangular,
    float32: ``(I - low)(I + low^2)(I + low^4) ...`` until the power passes
    ``C`` (``low`` is nilpotent)."""
    c = low.shape[-1]
    eye = jnp.eye(c, dtype=F32)
    inv, power, reach = eye - low, low, 2
    while reach < c:
        power = _f32_matmul("...ij,...jk->...ik", power, power)
        inv = _f32_matmul("...ij,...jk->...ik", inv, eye + power)
        reach *= 2
    return inv


def gated_delta_chunk_scan(q, k, v, g, beta, state, *, chunk: int = 64,
                           last=None):
    """``q``, ``k`` [b, s, h_k, d_k] as projected (the unit length and the
    query's ``1 / sqrt(d_k)`` are taken here); ``v`` [b, s, h_v, d_v]; ``g``
    (<= 0) and ``beta`` (in [0, 1]) [b, s, h_v]; ``state`` [b, h_v, d_k, d_v]
    or None (zeros); ``last`` the index of the last real row (None: ``s -
    1``).  Returns (o [b, s, h_v, d_v] in ``v``'s dtype, the state after row
    ``last`` [b, h_v, d_k, d_v] float32).  A call of fewer rows than
    ``chunk`` is one short chunk; a row count that ``chunk`` does not divide
    is padded with rows that, like those past ``last``, change nothing."""
    b, s, hk, dk = q.shape
    hv, dv = v.shape[2:]
    rep, dt = hv // hk, v.dtype
    g, beta = g.astype(F32), beta.astype(F32)
    if last is not None:
        real = jnp.arange(s)[None, :, None] <= last
        g, beta = jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0)
    c = min(int(chunk), s)
    pad = -s % c
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    n = (s + pad) // c
    # [b, chunks, rows of a chunk, heads, ...]; a key head's rows once for
    # the ``rep`` value heads it serves: [b, n, c, h_k, (rep,) ...]
    qc = (unit_rows(q) * dk ** -0.5).astype(dt).reshape(b, n, c, hk, dk)
    kc = unit_rows(k).astype(dt).reshape(b, n, c, hk, dk)
    vc = v.reshape(b, n, c, hk, rep, dv)
    gc, bc = (a.reshape(b, n, c, hk, rep) for a in (g, beta))
    # gamma, the running sum of g down a chunk's rows, as a product with a
    # triangle of ones: a cumulative sum over 64 rows is a windowed
    # reduction to the TPU's compiler, 230 us a layer for 65 K numbers
    # (PERF.md section 6, PR 51)
    cum = _f32_matmul("ij,bnjhr->bnihr", jnp.tril(jnp.ones((c, c), F32)), gc)
    if state is None:
        state = jnp.zeros((b, hv, dk, dv), F32)

    # row i against row j of its chunk: [b, n, h_k, rep, i, j]
    gap = jnp.moveaxis(cum, 2, -1)[..., :, None] \
        - jnp.moveaxis(cum, 2, -1)[..., None, :]
    rows, cols = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    kk = jnp.einsum("bnihd,bnjhd->bnhij", kc, kc,
                    preferred_element_type=F32)[:, :, :, None]
    qk = jnp.einsum("bnihd,bnjhd->bnhij", qc, kc,
                    preferred_element_type=F32)[:, :, :, None]
    by_row = jnp.moveaxis(bc, 2, -1)[..., None]            # beta_i
    low = kk * jnp.exp(jnp.where(rows > cols, gap, -jnp.inf)) * by_row
    reads = (qk * jnp.exp(jnp.where(rows >= cols, gap, -jnp.inf))).astype(dt)
    inv = unit_lower_inverse(low)
    # from here on the heads lead and a chunk's rows are the matrices' own:
    # [b, n, h_k, rep, rows, width]
    by_head = lambda a: jnp.moveaxis(a, 2, 4)
    decay = by_head(jnp.exp(cum)[..., None])               # exp(gamma_i)
    keys = by_head(kc.astype(F32)[:, :, :, :, None])
    u = _f32_matmul("bnhrij,bnhrjd->bnhrid", inv,
                    by_head(vc.astype(F32) * bc[..., None]))
    w = _f32_matmul("bnhrij,bnhrjd->bnhrid", inv,
                    keys * by_head(bc[..., None]) * decay).astype(dt)
    end = cum[:, :, -1]                                    # b n h_k rep
    q_in = (by_head(qc.astype(F32)[:, :, :, :, None]) * decay).astype(dt)
    k_out = (keys * by_head(jnp.exp(end[:, :, None] - cum)[..., None])) \
        .astype(dt)

    def one_chunk(st, xs):
        u_c, w_c, q_c, k_c, reads_c, left = xs
        low_st = st.astype(dt)
        d = (u_c - jnp.einsum("bhrik,bhrkd->bhrid", w_c, low_st,
                              preferred_element_type=F32)).astype(dt)
        o = jnp.einsum("bhrik,bhrkd->bhrid", q_c, low_st,
                       preferred_element_type=F32) \
            + jnp.einsum("bhrij,bhrjd->bhrid", reads_c, d,
                         preferred_element_type=F32)
        st = st * left[..., None, None] \
            + jnp.einsum("bhrik,bhrid->bhrkd", k_c, d,
                         preferred_element_type=F32)
        return st, o.astype(dt)

    state, o = jax.lax.scan(
        one_chunk, state.astype(F32).reshape(b, hk, rep, dk, dv),
        tuple(jnp.moveaxis(a, 1, 0)
              for a in (u, w, q_in, k_out, reads, jnp.exp(end))))
    # [n, b, h_k, rep, c, d_v] -> [b, s, h_v, d_v]
    o = jnp.transpose(o, (1, 0, 4, 2, 3, 5)).reshape(b, n * c, hv, dv)[:, :s]
    return o, state.reshape(b, hv, dk, dv)


def gated_delta_step(q, k, v, g, beta, state):
    """One row a sequence: ``q``, ``k`` [n, h_k, d_k] as projected, ``v`` [n,
    h_v, d_v], ``g``, ``beta`` [n, h_v], ``state`` [n, h_v, d_k, d_v].
    Returns (o [n, h_v, d_v] in ``v``'s dtype, the new state float32)."""
    hk, dk = q.shape[1:]
    rep = v.shape[1] // hk
    qf = jnp.repeat(unit_rows(q) * dk ** -0.5, rep, axis=1)
    kf = jnp.repeat(unit_rows(k), rep, axis=1)
    state = state.astype(F32) * jnp.exp(g.astype(F32))[..., None, None]
    held = jnp.sum(state * kf[..., None], axis=-2)         # S^T k
    d = beta.astype(F32)[..., None] * (v.astype(F32) - held)
    state = state + kf[..., None] * d[..., None, :]
    return jnp.sum(state * qf[..., None], axis=-2).astype(v.dtype), state


# ---------------------------------------------------------------------------
# a decay a key channel (Kimi Delta Attention)
# ---------------------------------------------------------------------------

def kda_chunk_scan(q, k, v, g, beta, state, *, chunk: int = 64,
                   sub: int = 16, last=None):
    """The rule with a decay a key CHANNEL: ``q``, ``k`` [b, s, h, d_k] as
    projected (the unit length and the query's ``1 / sqrt(d_k)`` are taken
    here), ``v`` [b, s, h, d_v], ``g`` [b, s, h, d_k] (<= 0, bounded below by
    the caller: ``(sub - 1) * max |g|`` is exponentiated), ``beta`` [b, s,
    h]; ``state`` [b, h, d_k, d_v] or None (zeros); ``last`` the index of the
    last real row (None: ``s - 1``).  ``chunk`` rows are solved together in
    sub-blocks of ``sub`` (which divides it).  Returns (o [b, s, h, d_v] in
    ``v``'s dtype, the state after row ``last`` float32).  Rows past
    ``last`` and the padding of a ragged row count get ``g`` = 0 and
    ``beta`` = 0 and leave the state bit for bit."""
    b, s, h, dk = q.shape
    dv, dt = v.shape[-1], v.dtype
    if chunk % sub:
        raise ValueError(f"sub-blocks of {sub} rows do not divide a chunk "
                         f"of {chunk}")
    g, beta = g.astype(F32), beta.astype(F32)
    if last is not None:
        real = jnp.arange(s)[None, :, None] <= last
        g, beta = jnp.where(real[..., None], g, 0.0), \
            jnp.where(real, beta, 0.0)
    c = min(int(chunk), -(-s // sub) * sub)
    pad = -s % c
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    n, a = (s + pad) // c, c // sub
    # heads lead, a chunk's rows are the matrices' own: [b, n, h, c, width]
    by_head = lambda x: jnp.moveaxis(x.reshape((b, n, c) + x.shape[2:]), 3, 2)
    qc = by_head(unit_rows(q) * dk ** -0.5)
    kc, vc = by_head(unit_rows(k)), by_head(v.astype(F32))
    bc = by_head(beta)[..., None]                          # b n h c 1
    # Gamma: the running sum of g down a chunk's rows (a product with a
    # triangle of ones, as the scalar rule's: a windowed reduction otherwise)
    cum = _f32_matmul("ij,bnhjd->bnhid", jnp.tril(jnp.ones((c, c), F32)),
                      by_head(g))
    # a sub-block's reference: Gamma at its first row
    ref = cum.reshape(b, n, h, a, sub, dk)[:, :, :, :, 0]  # b n h a d
    down = jnp.exp(cum.reshape(b, n, h, a, sub, dk) - ref[:, :, :, :, None])
    # a key against sub-block ``a``'s reference: earlier rows decay further
    # (<= 0), its own rows grow (at most (sub - 1) |g|), later rows are none
    # of its business (masked BEFORE the exponent is taken)
    upto = jnp.arange(c)[None, :] < (jnp.arange(a)[:, None] + 1) * sub
    kx = kc[:, :, :, None] * jnp.exp(jnp.where(
        upto[..., None], ref[:, :, :, :, None] - cum[:, :, :, None],
        -jnp.inf))                                         # b n h a c d
    pairs = lambda x: _f32_matmul(
        "bnhaid,bnhajd->bnhaij", x.reshape(b, n, h, a, sub, dk) * down,
        kx).reshape(b, n, h, c, c)
    rows, cols = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    low = jnp.where(rows > cols, pairs(kc), 0.0) * bc
    reads = jnp.where(rows >= cols, pairs(qc), 0.0).astype(dt)
    inv = unit_lower_inverse(low)
    decay = jnp.exp(cum)                                   # exp(Gamma_i)
    u = _f32_matmul("bnhij,bnhjd->bnhid", inv, vc * bc)
    w = _f32_matmul("bnhij,bnhjd->bnhid", inv, kc * bc * decay).astype(dt)
    end = cum[:, :, :, -1]                                 # b n h d_k
    q_in = (qc * decay).astype(dt)
    k_out = (kc * jnp.exp(end[:, :, :, None] - cum)).astype(dt)
    if state is None:
        state = jnp.zeros((b, h, dk, dv), F32)

    def one_chunk(st, xs):
        u_c, w_c, q_c, k_c, reads_c, left = xs
        low_st = st.astype(dt)
        d = (u_c - jnp.einsum("bhik,bhkd->bhid", w_c, low_st,
                              preferred_element_type=F32)).astype(dt)
        o = jnp.einsum("bhik,bhkd->bhid", q_c, low_st,
                       preferred_element_type=F32) \
            + jnp.einsum("bhij,bhjd->bhid", reads_c, d,
                         preferred_element_type=F32)
        st = st * left[..., None] \
            + jnp.einsum("bhik,bhid->bhkd", k_c, d,
                         preferred_element_type=F32)
        return st, o.astype(dt)

    state, o = jax.lax.scan(
        one_chunk, state.astype(F32),
        tuple(jnp.moveaxis(x, 1, 0)
              for x in (u, w, q_in, k_out, reads, jnp.exp(end))))
    # [n, b, h, c, d_v] -> [b, s, h, d_v]
    o = jnp.transpose(o, (1, 0, 3, 2, 4)).reshape(b, n * c, h, dv)[:, :s]
    return o, state


def kda_step(q, k, v, g, beta, state):
    """One row a sequence: ``q``, ``k`` [n, h, d_k] as projected, ``v`` [n,
    h, d_v], ``g`` [n, h, d_k], ``beta`` [n, h], ``state`` [n, h, d_k, d_v].
    Returns (o [n, h, d_v] in ``v``'s dtype, the new state float32).  A row
    of ``g`` = 0, ``beta`` = 0 leaves its state bit for bit."""
    dk = q.shape[-1]
    qf, kf = unit_rows(q) * dk ** -0.5, unit_rows(k)
    state = state.astype(F32) * jnp.exp(g.astype(F32))[..., None]
    held = jnp.sum(state * kf[..., None], axis=-2)         # S^T k
    d = beta.astype(F32)[..., None] * (v.astype(F32) - held)
    state = state + kf[..., None] * d[..., None, :]
    return jnp.sum(state * qf[..., None], axis=-2).astype(v.dtype), state
