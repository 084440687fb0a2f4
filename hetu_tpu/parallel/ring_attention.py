"""Ring attention over the 'sp' mesh axis — long-context capability.

Reference: absent in Hetu core (SURVEY.md §2.3/§5: only Megatron
sequence-parallel in vendored Galvatron code); this is the planned new
capability: blockwise attention with online-softmax accumulation while K/V
chunks rotate around the ICI ring via ppermute, so sequence length scales
with the number of chips at O(S/n) memory per chip and compute overlaps
communication (Liu et al. ring attention; the standard TPU formulation).

Layout: q,k,v are [B, H, S, D] sharded on S over `axis`.  Inside shard_map
each device sees [B, H, S/n, D] and performs n blockwise steps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _block_attn(q, k, v, bias_mask, scale, o, m, l):
    """One blockwise online-softmax accumulation step.

    q [B,H,Sq,D]; k,v [B,H,Sk,D]; bias_mask [Sq,Sk] bool (True=keep).
    o,m,l are the running output / max / normalizer (f32).
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(bias_mask[None, None], s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # guard fully-masked rows (m_new stays NEG_INF): exp(NEG_INF - NEG_INF)=1
    # would pollute l; clamp the correction instead.
    p = jnp.exp(s - m_new[..., None])
    p = jnp.where(bias_mask[None, None], p, 0.0)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1)
    o_new = o * corr[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v.astype(jnp.float32),
        preferred_element_type=jnp.float32)
    return o_new, m_new, l_new


def _ring_attention_local(q, k, v, *, axis: str, causal: bool, scale):
    n = lax.axis_size(axis)
    my = lax.axis_index(axis)
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    qf = q.astype(jnp.float32)

    o = jnp.zeros((B, H, Sq, D), jnp.float32)
    m = jnp.full((B, H, Sq), NEG_INF, jnp.float32)
    l = jnp.zeros((B, H, Sq), jnp.float32)

    q_pos = my * Sq + jnp.arange(Sq)

    def body(i, carry):
        o, m, l, k_cur, v_cur = carry
        src = (my - i) % n  # rank whose chunk we currently hold
        if causal:
            k_pos = src * Sk + jnp.arange(Sk)
            mask = q_pos[:, None] >= k_pos[None, :]
        else:
            mask = jnp.ones((Sq, Sk), bool)
        o, m, l = _block_attn(qf, k_cur, v_cur, mask, scale, o, m, l)
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_nxt = lax.ppermute(k_cur, axis, perm)
        v_nxt = lax.ppermute(v_cur, axis, perm)
        return o, m, l, k_nxt, v_nxt

    o, m, l, _, _ = lax.fori_loop(0, n, body, (o, m, l, k, v))
    l = jnp.maximum(l, 1e-20)  # fully-masked rows → 0 output
    return (o / l[..., None]).astype(q.dtype)


def ring_attention(q, k, v, mesh: Mesh, *, axis: str = "sp",
                   causal: bool = False, scale=None):
    """q,k,v: [B, H, S, D] with S sharded over `axis` on `mesh`."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    fn = functools.partial(_ring_attention_local, axis=axis, causal=causal,
                           scale=scale)
    spec = P(None, None, axis, None)
    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)
