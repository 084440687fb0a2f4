"""Explicit collective helpers over mesh axes.

Reference: python/hetu/communicator/mpi_nccl_comm.py (NCCL_Communicator
:164 — global/group/rank-tuple communicators, collectives :295-336) and
src/communication/mpi_nccl_communication.cu (custom grouped-send/recv
AllToAll :245-278 and hierarchical AllToAll :152-213).

TPU translation: communicators ARE mesh axes — a "device group" is an axis
(or axis tuple) of the Mesh, and arbitrary subgroup communicators correspond
to sub-axes obtained by reshaping the mesh, not runtime unique-id exchange.
These wrappers run inside shard_map; under plain pjit XLA usually inserts
the same collectives from sharding constraints, so these exist for (a) the
explicit-planner path (parallel/planner.py), (b) pipeline/ring primitives
that SPMD cannot infer, (c) parity with the reference's API surface.

Hierarchical A2A: the reference gathers intra-node, exchanges across node
leaders, then scatters (HAllToAll).  On TPU the same two-level structure is
expressed by factoring 'ep' into ('ep_outer','ep_inner') — inner axis on
ICI, outer on DCN — and running a2a per level; XLA routes each over the
right fabric because axis order encodes locality (mesh.py).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def psum(x, axis):
    """AllReduce(sum) over a mesh axis (dlarrayNcclAllReduce analog)."""
    return lax.psum(x, axis)


def pmean(x, axis):
    return lax.pmean(x, axis)


def all_gather(x, axis, *, tiled_dim: int = 0):
    """AllGather along a mesh axis, concatenating on tiled_dim."""
    return lax.all_gather(x, axis, axis=tiled_dim, tiled=True)


def reduce_scatter(x, axis, *, scatter_dim: int = 0):
    """ReduceScatter(sum) along a mesh axis."""
    return lax.psum_scatter(x, axis, scatter_dimension=scatter_dim,
                            tiled=True)


def all_to_all(x, axis, *, split_dim: int = 0, concat_dim: int = 0):
    """AllToAll: split `split_dim` across the axis, concat received chunks on
    `concat_dim` (the reference's _ncclAllToAll, grouped send/recv)."""
    return lax.all_to_all(x, axis, split_axis=split_dim,
                          concat_axis=concat_dim, tiled=True)


def hierarchical_all_to_all(x, outer_axis: str, inner_axis: str,
                            *, split_dim: int = 0, concat_dim: int = 0):
    """Two-level A2A (reference _ncclHAllToAll): exchange within the inner
    (ICI) axis, then across the outer (DCN) axis.

    Destination rank order matches a FLAT all_to_all over the composite
    ('outer', 'inner') axis: send chunks (outer-major destination order) are
    pre-permuted to inner-major so the two-stage exchange delivers them in
    flat order — verified chunk-for-chunk against the composite-axis a2a in
    tests/test_moe.py.
    """
    n_o = lax.axis_size(outer_axis)
    n_i = lax.axis_size(inner_axis)
    L = x.shape[split_dim]
    assert L % (n_o * n_i) == 0
    rest = L // (n_o * n_i)
    # view split_dim as [n_o, n_i, rest] and swap to [n_i, n_o, rest]
    pre = x.shape[:split_dim]
    post = x.shape[split_dim + 1:]
    xr = x.reshape(*pre, n_o, n_i, rest, *post)
    xr = jnp.swapaxes(xr, split_dim, split_dim + 1)
    x = xr.reshape(*pre, L, *post)
    y = lax.all_to_all(x, inner_axis, split_axis=split_dim,
                       concat_axis=concat_dim, tiled=True)
    return lax.all_to_all(y, outer_axis, split_axis=split_dim,
                          concat_axis=concat_dim, tiled=True)


def quantized_psum(x, axis, *, wire: str = "int8", block: int = 256):
    """Block-scaled quantized AllReduce(sum) over a mesh axis — the
    EQuARX scheme (PAPERS.md, arXiv 2506.17615) built from jax
    primitives so it stays INSIDE jit and XLA fuses quantize →
    collective → dequantize:

      flatten → blocks of ``block`` elts → symmetric int8 with one f32
      scale per block → ``all_gather`` of codes+scales in low precision
      → dequantize + sum in f32 → reshape back.

    Wire bytes per element: 1 + 4/block (int8) or 2 (bf16) vs 4 for the
    exact f32 path — ``wire="f32"`` IS the exact path (plain
    ``lax.psum``), so call sites can select precision per op with no
    structural change.  Per-replica quantization error is bounded by
    half a quantum: |err| <= max|block| / 254 per element per replica
    (asserted in tests/test_quant_wire.py); gradient call sites that
    need the bias removed over time pair this with error feedback the
    same way the PS wire does.

    Only valid where ``lax.psum`` is (inside ``shard_map``/``pmap`` over
    ``axis``).  Byte accounting happens at the call site (the executor's
    gradient-sync path records ``train.grad_sync.bytes_*``) — a traced
    function cannot touch host counters.
    """
    if wire in (None, "f32", "exact"):
        return lax.psum(x, axis)
    if wire == "bf16":
        g = lax.all_gather(x.astype(jnp.bfloat16), axis)
        return jnp.sum(g.astype(jnp.float32), axis=0).astype(x.dtype)
    if wire != "int8":
        raise ValueError(f"unknown wire dtype {wire!r}; expected "
                         f"'f32'/'bf16'/'int8'")
    from hetu_tpu.quantwire import jnp_block_encode
    q, scale = jnp_block_encode(x, block)
    qg = lax.all_gather(q, axis)          # [n_dev, nblk, block] int8
    sg = lax.all_gather(scale, axis)      # [n_dev, nblk, 1] f32
    out = jnp.sum(qg.astype(jnp.float32) * sg, axis=0)
    return (out.reshape(-1)[:x.size].reshape(x.shape)).astype(x.dtype)


def quantized_pmean(x, axis, *, wire: str = "int8", block: int = 256):
    """AllReduce(mean) counterpart of :func:`quantized_psum` (the
    gradient-sync shape: data-parallel gradients average over dp)."""
    if wire in (None, "f32", "exact"):
        return lax.pmean(x, axis)
    return quantized_psum(x, axis, wire=wire, block=block) / \
        lax.psum(1, axis)


def ppermute_shift(x, axis, shift: int = 1):
    """Ring shift over a mesh axis (PipelineSend/Receive analog and the ring-
    attention building block)."""
    n = lax.axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis, perm)


def grouped_allreduce(mesh: Mesh, axis, fn=None):
    """Build a jitted allreduce over one mesh axis for replicated-elsewhere
    arrays — the reference's per-param grouped communicators
    (context.py:1827 get_allreduce_devices).  Returns f(x) -> psum over axis.
    """
    in_spec = P(axis)

    @partial(shard_map, mesh=mesh, in_specs=in_spec, out_specs=P())
    def _ar(x):
        return lax.psum(x, axis)

    return _ar
