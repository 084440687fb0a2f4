"""Flash attention forward + fused backward kernels (Pallas/TPU).

The reference has no fused attention (its MHA composes batch_matmul +
softmax ops, layers/attention.py); on TPU the fusion matters because the
[S, S] score matrix otherwise round-trips HBM.  The forward streams K/V
blocks through VMEM — grid = (batch*heads, q_blocks, k_blocks) with the k
dimension innermost, online-softmax state held in VMEM scratch across the
k iterations — so VMEM usage is O(block_q * D + block_k * D) regardless of
sequence length.  It also emits the log-sum-exp rows (LSE), which the
backward uses to recompute probabilities tile-by-tile.

Backward is the standard FlashAttention-2 two-kernel scheme:

  * delta = rowsum(dO * O)                       (one cheap XLA reduction)
  * dK/dV kernel: grid (bh, k_blocks, q_blocks), accumulating
        p   = exp(q k^T * scale - lse)
        dv += p^T dO
        ds  = p * (dO v^T - delta) * scale
        dk += ds^T q
    in VMEM f32 scratch across the q iterations;
  * dQ kernel: grid (bh, q_blocks, k_blocks), accumulating dq += ds k.

No O(S^2) tensor ever touches HBM in either direction — this beats the
reference's training memory profile (its attention materializes scores for
the backward), and it is what makes S >= 8k practical on one chip.

Causal masking is BOTTOM-RIGHT aligned (query i attends to keys
<= i + (S_k - S_q)), matching ops.causal_attention, so cross-length
(prefix/KV-cache) calls agree with the oracle in both directions — except
query rows whose mask hides EVERY key (only possible when s_q > s_k):
there the kernel returns 0 output and 0 gradients, whereas the XLA
composition softmaxes the uniform -1e30 scores into garbage averages.
Zero is the deliberate semantics for an all-masked row.
Interpret mode runs the same kernels on CPU for correctness tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import AxisType, PartitionSpec as P

from hetu_tpu.parallel.mesh import AXIS_DP, AXIS_TP
from hetu_tpu.utils.platform import auto_interpret

NEG_INF = -1e30


# ---------------------------------------------------------------- forward

def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
                      l_ref, *, block_q: int, block_k: int, scale: float,
                      causal: bool, causal_offset: int):
    """Program (bh, qi, ki): one [block_q, block_k] tile of the attention.

    q_ref [block_q, D]; k_ref/v_ref [block_k, D]; o_ref [block_q, D];
    lse_ref [block_q]; acc/m/l: VMEM scratch carrying online-softmax state
    across ki.
    """
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q_last = (qi + 1) * block_q - 1 + causal_offset  # last visible k pos
    k_first = ki * block_k
    live = (not causal) or (k_first <= q_last)

    @pl.when(live)
    def _():
        # dots stay in the input dtype (bf16 hits the fast MXU path) with
        # f32 accumulation; scale is applied to the f32 scores
        scores = lax.dot_general(q_ref[:], k_ref[:], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * block_q + causal_offset + \
                lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + \
                lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            scores = jnp.where(q_pos >= k_pos, scores, NEG_INF)
        m_prev = m_ref[:]
        l_prev = l_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1))
        p = jnp.exp(scores - m_new[:, None])
        if causal:
            p = jnp.where(scores <= NEG_INF / 2, 0.0, p)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:] = l_prev * corr + jnp.sum(p, axis=-1)
        m_ref[:] = m_new
        acc_ref[:] = acc_ref[:] * corr[:, None] + lax.dot_general(
            p.astype(v_ref.dtype), v_ref[:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _():
        l = jnp.maximum(l_ref[:], 1e-20)
        o_ref[:] = (acc_ref[:] / l[:, None]).astype(o_ref.dtype)
        lse_ref[:] = (m_ref[:] + jnp.log(l))[:, None]


def _fit_block(s: int, want: int) -> int:
    """Largest block <= want dividing s: s itself when s <= want, else the
    first halving of want that divides s (>=8 for TPU tiles)."""
    b = min(want, s)
    while b > 8 and s % b:
        b //= 2
    if s % b:
        raise ValueError(
            f"sequence length {s} is not divisible by any block size <= "
            f"{want}; pad the sequence (flash blocks must tile it exactly)")
    return b


def _flash_fwd(q, k, v, *, scale, causal, block_q, block_k, interpret):
    b, h, s_q, d = q.shape
    s_k = k.shape[2]
    bq = _fit_block(s_q, block_q)
    bk = _fit_block(s_k, block_k)

    qf = q.reshape(b * h, s_q, d)
    kf = k.reshape(b * h, s_k, d)
    vf = v.reshape(b * h, s_k, d)

    kernel = functools.partial(
        _flash_fwd_kernel, block_q=bq, block_k=bk, scale=scale,
        causal=causal, causal_offset=s_k - s_q)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, s_q // bq, s_k // bk),
        in_specs=[
            pl.BlockSpec((None, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((None, bk, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((None, bk, d), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            # TPU blocks need the trailing dims (8,128)-aligned or full; a
            # trailing singleton keeps the row vector legal: block (bq, 1)
            pl.BlockSpec((None, bq, 1), lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s_q, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, s_q, 1), jnp.float32),
        ],
        scratch_shapes=_scratch(bq, d),
        compiler_params=_params(),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, s_q, d), lse


def _scratch(bq, d):
    return [pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32)]


def _params():
    """bh and the outer block axis are parallel; the innermost axis carries
    the VMEM accumulator and must run in order."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


# ---------------------------------------------------------------- backward

def _recompute_p(q_ref, k_ref, lse_ref, qi, ki, *, block_q, block_k, scale,
                 causal, causal_offset):
    """Recompute one probability tile p = exp(q k^T * scale - lse)."""
    scores = lax.dot_general(q_ref[:], k_ref[:], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = qi * block_q + causal_offset + \
            lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = ki * block_k + \
            lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        scores = jnp.where(q_pos >= k_pos, scores, NEG_INF)
    p = jnp.exp(scores - lse_ref[:])  # lse block is [bq, 1]
    if causal:
        # guard fully-masked rows: lse there is ~NEG_INF and the subtraction
        # above would overflow exp
        p = jnp.where(scores <= NEG_INF / 2, 0.0, p)
    return p, scores


def _flash_bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                           dk_ref, dv_ref, dk_acc, dv_acc, *, block_q: int,
                           block_k: int, scale: float, causal: bool,
                           causal_offset: int):
    """Program (bh, ki, qi): accumulate dk/dv for one k block over q blocks."""
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    n_q = pl.num_programs(2)

    @pl.when(qi == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_last = (qi + 1) * block_q - 1 + causal_offset
    k_first = ki * block_k
    live = (not causal) or (k_first <= q_last)

    @pl.when(live)
    def _():
        p, _ = _recompute_p(q_ref, k_ref, lse_ref, qi, ki, block_q=block_q,
                            block_k=block_k, scale=scale, causal=causal,
                            causal_offset=causal_offset)
        pc = p.astype(do_ref.dtype)
        # dv += p^T dO
        dv_acc[:] += lax.dot_general(pc, do_ref[:], (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        # dp = dO v^T ; ds = p * (dp - delta) * scale
        dp = lax.dot_general(do_ref[:], v_ref[:], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[:]) * scale).astype(q_ref.dtype)
        # dk += ds^T q
        dk_acc[:] += lax.dot_general(ds, q_ref[:], (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)

    @pl.when(qi == n_q - 1)
    def _():
        dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dq_acc, *, block_q: int, block_k: int,
                         scale: float, causal: bool, causal_offset: int):
    """Program (bh, qi, ki): accumulate dq for one q block over k blocks."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q_last = (qi + 1) * block_q - 1 + causal_offset
    k_first = ki * block_k
    live = (not causal) or (k_first <= q_last)

    @pl.when(live)
    def _():
        p, _ = _recompute_p(q_ref, k_ref, lse_ref, qi, ki, block_q=block_q,
                            block_k=block_k, scale=scale, causal=causal,
                            causal_offset=causal_offset)
        dp = lax.dot_general(do_ref[:], v_ref[:], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[:]) * scale).astype(k_ref.dtype)
        # dq += ds k
        dq_acc[:] += lax.dot_general(ds, k_ref[:], (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _():
        dq_ref[:] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd(q, k, v, out, lse, g, *, scale, causal, block_q, block_k,
               interpret):
    b, h, s_q, d = q.shape
    s_k = k.shape[2]
    bq = _fit_block(s_q, block_q)
    bk = _fit_block(s_k, block_k)

    qf = q.reshape(b * h, s_q, d)
    kf = k.reshape(b * h, s_k, d)
    vf = v.reshape(b * h, s_k, d)
    dof = g.reshape(b * h, s_q, d)
    # delta = rowsum(dO * O): one fused elementwise+reduce, O(S*D) traffic
    delta = jnp.sum(dof.astype(jnp.float32)
                    * out.reshape(b * h, s_q, d).astype(jnp.float32),
                    axis=-1, keepdims=True)

    common = dict(block_q=bq, block_k=bk, scale=scale, causal=causal,
                  causal_offset=s_k - s_q)

    # dK/dV kernel: grid (bh, ki, qi) — q blocks innermost
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkdv_kernel, **common),
        grid=(b * h, s_k // bk, s_q // bq),
        in_specs=[
            pl.BlockSpec((None, bq, d), lambda bh, ki, qi: (bh, qi, 0)),  # q
            pl.BlockSpec((None, bk, d), lambda bh, ki, qi: (bh, ki, 0)),  # k
            pl.BlockSpec((None, bk, d), lambda bh, ki, qi: (bh, ki, 0)),  # v
            pl.BlockSpec((None, bq, d), lambda bh, ki, qi: (bh, qi, 0)),  # dO
            pl.BlockSpec((None, bq, 1), lambda bh, ki, qi: (bh, qi, 0)),  # lse
            pl.BlockSpec((None, bq, 1), lambda bh, ki, qi: (bh, qi, 0)),  # delta
        ],
        out_specs=[
            pl.BlockSpec((None, bk, d), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((None, bk, d), lambda bh, ki, qi: (bh, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s_k, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, s_k, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
    )(qf, kf, vf, dof, lse, delta)

    # dQ kernel: grid (bh, qi, ki) — k blocks innermost
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **common),
        grid=(b * h, s_q // bq, s_k // bk),
        in_specs=[
            pl.BlockSpec((None, bq, d), lambda bh, qi, ki: (bh, qi, 0)),  # q
            pl.BlockSpec((None, bk, d), lambda bh, qi, ki: (bh, ki, 0)),  # k
            pl.BlockSpec((None, bk, d), lambda bh, qi, ki: (bh, ki, 0)),  # v
            pl.BlockSpec((None, bq, d), lambda bh, qi, ki: (bh, qi, 0)),  # dO
            pl.BlockSpec((None, bq, 1), lambda bh, qi, ki: (bh, qi, 0)),  # lse
            pl.BlockSpec((None, bq, 1), lambda bh, qi, ki: (bh, qi, 0)),  # delta
        ],
        out_specs=pl.BlockSpec((None, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
    )(qf, kf, vf, dof, lse, delta)

    return (dq.reshape(b, h, s_q, d), dk.reshape(b, h, s_k, d),
            dv.reshape(b, h, s_k, d))


# ---------------------------------------------------------------- public op

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, causal, block_q, block_k, interpret):
    out, _ = _flash_fwd(q, k, v, scale=scale, causal=causal, block_q=block_q,
                        block_k=block_k, interpret=interpret)
    return out


def _flash_vjp_fwd(q, k, v, scale, causal, block_q, block_k, interpret):
    out, lse = _flash_fwd(q, k, v, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          interpret=interpret)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(scale, causal, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    return _flash_bwd(q, k, v, out, lse, g, scale=scale, causal=causal,
                      block_q=block_q, block_k=block_k, interpret=interpret)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _mesh_partition(batch: int, heads: int):
    """How the mesh in context (``jax.set_mesh``; Executor and the serving
    engines enter theirs) splits a [B, H, S, D] attention operand: batch
    over 'dp' and heads over 'tp' — the two dims every kernel program is
    independent along — where the axis divides the dim, replicated over
    the rest.  Returns (axes to take manual, spec); no axes when no mesh
    is set or an enclosing shard_map already holds them all."""
    mesh = jax.sharding.get_abstract_mesh()
    auto = frozenset(a for a, t in zip(mesh.axis_names, mesh.axis_types)
                     if t == AxisType.Auto)

    def axis(name, n):
        return name if name in auto and n % mesh.shape[name] == 0 else None

    return auto, P(axis(AXIS_DP, batch), axis(AXIS_TP, heads), None, None)


def flash_attention(q, k, v, *, causal: bool = False, scale=None,
                    block_q: int = 256, block_k: int = 256,
                    interpret=None):
    """Fused attention: q,k,v [B, H, S, D] → [B, H, S_q, D].

    Fully fused in both directions: forward streams K/V blocks with online
    softmax; backward recomputes probability tiles from the saved LSE
    (FlashAttention-2) — no O(S^2) tensor in HBM either way.

    interpret=None auto-selects: compiled kernel on TPU, interpret mode on
    CPU.  Block sizes auto-fit down to the sequence length (any S
    divisible by a power-of-two >= 8 works; only truly odd lengths need
    upstream padding).  Causal masking is bottom-right aligned for
    S_q != S_k.

    The SPMD partitioner cannot split a compiled ``pallas_call`` (JAX
    refuses to lower one outside a fully manual region), so under a mesh
    the call is wrapped in a ``shard_map`` over the batch and head axes
    (:func:`_mesh_partition`): each device runs the kernel on its own shard.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    static = (float(scale), bool(causal), int(block_q), int(block_k),
              auto_interpret(interpret))
    axes, spec = _mesh_partition(q.shape[0], q.shape[1])
    if not axes:
        return _flash(q, k, v, *static)
    return shard_map(lambda q, k, v: _flash(q, k, v, *static),
                     in_specs=(spec, spec, spec), out_specs=spec,
                     axis_names=axes, check_vma=False)(q, k, v)
