"""Distributed GCN aggregation (1.5-D partitioning).

Reference: python/hetu/gpu_ops/DistGCN_15d.py (156 LoC): adjacency is
partitioned over workers in a 1.5-D scheme — nodes row-sharded, features
replicated within row groups — and each layer's aggregation exchanges
partial products.

TPU form: nodes sharded over the 'dp' axis inside shard_map; each shard
owns its destination-node rows and the edges POINTING AT them (dst-sharded
COO, the standard pull model).  Per layer: all-gather the source features
over dp (the 1.5-D row exchange), run the local segment-sum on owned
destinations.  For very large graphs the all_gather becomes a ring of
ppermute steps consuming one source shard at a time — same wire bytes,
O(N/p) peak memory; both paths below.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from hetu_tpu.ops.graph_ops import coo_spmm


def dist_gcn_aggregate(h, edge_src, edge_dst, edge_weight, mesh: Mesh, *,
                       axis: str = "dp", ring: bool = False):
    """A_norm @ H with nodes sharded over `axis`.

    h: [N, F] node features, row-sharded.  edge_src/dst/weight: [E] COO,
    DST-sharded (each shard's slice holds only edges whose dst it owns;
    dst indices are GLOBAL, src indices are GLOBAL).  Returns [N, F]
    row-sharded aggregation.
    """
    n_total = h.shape[0]
    p = mesh.shape[axis]
    assert n_total % p == 0
    n_loc = n_total // p

    def local_gather(h_loc, src, dst, w):
        i = lax.axis_index(axis)
        h_all = lax.all_gather(h_loc, axis, axis=0, tiled=True)  # [N, F]
        local_dst = dst.astype(jnp.int32) - i * n_loc
        return coo_spmm(src, local_dst, w, h_all, n_loc)

    def local_ring(h_loc, src, dst, w):
        i = lax.axis_index(axis)
        local_dst = dst.astype(jnp.int32) - i * n_loc
        out = jnp.zeros_like(h_loc)
        perm = [(j, (j + 1) % p) for j in range(p)]

        def body(k, carry):
            out, h_cur = carry
            # h_cur currently holds shard (i - k) mod p's rows
            owner = (i - k) % p
            rel = src.astype(jnp.int32) - owner * n_loc
            in_shard = (rel >= 0) & (rel < n_loc)
            safe = jnp.clip(rel, 0, n_loc - 1)
            msgs = h_cur[safe]
            if w is not None:
                msgs = msgs * w[:, None]
            msgs = jnp.where(in_shard[:, None], msgs, 0.0)
            out = out + jax.ops.segment_sum(msgs, local_dst,
                                            num_segments=n_loc)
            return out, lax.ppermute(h_cur, axis, perm)

        out, _ = lax.fori_loop(0, p, body, (out, h_loc))
        return out

    fn = local_ring if ring else local_gather
    return shard_map(
        fn, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis),
                  P(axis) if edge_weight is not None else P()),
        out_specs=P(axis), check_vma=False)(h, edge_src, edge_dst,
                                            edge_weight)


def shard_edges_by_dst(edge_src, edge_dst, edge_weight, n_nodes: int,
                       n_shards: int):
    """Host-side edge partitioner: sort edges by owning dst shard and pad
    each shard to equal length (static shapes).  Returns (src, dst, w)
    arrays of shape [n_shards * max_per_shard] laid out shard-major, ready
    to device_put with P('dp') sharding."""
    import numpy as np
    assert n_nodes % n_shards == 0, (
        f"{n_nodes} nodes not divisible by {n_shards} shards: edges owned "
        "by the remainder would be silently dropped")
    src = np.asarray(edge_src)
    dst = np.asarray(edge_dst)
    w = np.asarray(edge_weight) if edge_weight is not None else None
    n_loc = n_nodes // n_shards
    owner = dst // n_loc
    buckets = [np.where(owner == s)[0] for s in range(n_shards)]
    cap = max(len(b) for b in buckets)
    S, D, W = [], [], []
    for s, b in enumerate(buckets):
        pad = cap - len(b)
        S.append(np.concatenate([src[b], np.zeros(pad, src.dtype)]))
        # padding edges point at the shard's first node with weight 0
        D.append(np.concatenate([dst[b],
                                 np.full(pad, s * n_loc, dst.dtype)]))
        if w is not None:
            W.append(np.concatenate([w[b], np.zeros(pad, w.dtype)]))
        else:
            W.append(np.concatenate([np.ones(len(b), np.float32),
                                     np.zeros(pad, np.float32)]))
    return (np.concatenate(S), np.concatenate(D),
            np.concatenate(W).astype(np.float32))
