"""Partial-reduce DP training: straggler-tolerant dynamic-group averaging.

Reference: python/hetu/preduce.py (:8 PartialReduce) + ps-lite
preduce_handler — a worker asks the scheduler for this round's ready group,
then allreduces ONLY within that group (ncclAvg over a lazily-created
communicator for the member tuple).

TPU translation: one SPMD program cannot drop devices mid-step, but the
same semantics are a MASKED group mean inside shard_map over the dp axis:
every device computes its shard's gradient, members contribute to the
psum'd mean, non-members contribute zero (and receive the group mean, so
parameter state stays replicated-consistent — the reference's stragglers
simply skip pushing their stale grads).  The matchmaking is the host-side
PS service (hetu_tpu/ps/client.py PartialReduce); its member list becomes
this step's 0/1 mask.  Useful on multi-slice dp axes (DCN) where slice
speeds diverge.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


def preduce_step_fn(loss_fn, optimizer, mesh: Mesh, *, axis: str = "dp"):
    """Build a DP train step whose gradient reduction averages only over the
    matched group (member_mask[i] == 1), the preduce/HetPipe DP mode.

    loss_fn(params, batch_shard) -> scalar loss for ONE dp shard.
    Returns step(params, opt_state, batch, member_mask) ->
    (params, opt_state, group_loss); batch dim 0 is sharded over `axis`,
    params replicated, member_mask [axis_size] of 0/1.
    """
    n = mesh.shape[axis]

    def local(params, batch, mask):
        i = lax.axis_index(axis)
        m = mask[i]
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        denom = jnp.maximum(lax.psum(m, axis), 1.0)
        grads = jax.tree_util.tree_map(
            lambda g: lax.psum(g * m, axis) / denom, grads)
        loss = lax.psum(loss * m, axis) / denom
        return loss, grads

    shmapped = shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(axis), P()),
        out_specs=(P(), P()),
        check_vma=False)

    def step(params, opt_state, batch, member_mask):
        mask = jnp.asarray(member_mask, jnp.float32)
        loss, grads = shmapped(params, batch, mask)
        # empty group = nobody pushed = NO update: stateful optimizers
        # (momentum decay, adam step) must not advance either
        new_params, new_opt = optimizer.update(grads, opt_state, params)
        has_members = jnp.sum(mask) > 0
        pick = lambda new, old: jax.tree_util.tree_map(
            lambda a, b: jnp.where(has_members, a, b), new, old)
        return pick(new_params, params), pick(new_opt, opt_state), loss

    return jax.jit(step, donate_argnums=(0, 1)), n
