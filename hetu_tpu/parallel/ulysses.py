"""Ulysses (DeepSpeed-style) sequence parallelism: head <-> sequence A2A.

Reference: absent in Hetu core; the MoE AllToAll machinery
(gpu_ops/AllToAll.py, src/communication _ncclAllToAll) is the building block
(SURVEY.md §2.3 'Sequence parallelism' row).  Attention inputs arrive
sequence-sharded [B, H, S/n, D]; an all_to_all re-shards to head-sharded
[B, H/n, S, D], local full attention runs per device, and a reverse a2a
restores sequence sharding.  Requires num_heads %% n == 0.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from hetu_tpu.ops.attention import attention, causal_attention


def _ulysses_local(q, k, v, *, axis: str, causal: bool, scale):
    # [B, H, S/n, D] --a2a--> [B, H/n, S, D]
    def to_heads(x):
        return lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                              tiled=True)

    def to_seq(x):
        return lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                              tiled=True)

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)
    if causal:
        oh = causal_attention(qh, kh, vh, scale=scale)
    else:
        oh = attention(qh, kh, vh, scale=scale)
    return to_seq(oh)


def ulysses_attention(q, k, v, mesh: Mesh, *, axis: str = "sp",
                      causal: bool = False, scale=None):
    """q,k,v: [B, H, S, D] with S sharded over `axis`; heads must divide the
    axis size."""
    n = mesh.shape[axis]
    if q.shape[1] % n != 0:
        raise ValueError(f"num_heads {q.shape[1]} not divisible by "
                         f"{axis}={n}")
    if scale is None:
        scale = q.shape[-1] ** -0.5
    fn = functools.partial(_ulysses_local, axis=axis, causal=causal,
                           scale=scale)
    spec = P(None, None, axis, None)
    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)
