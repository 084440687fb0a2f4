"""Pipeline parallelism over the 'pp' mesh axis.

Reference: python/hetu/gpu_ops/pipeline_subexecutor.py (stage partitioning
:29-85, round-robin for unequal stage DP :87-128), gpipe_subexecutor.py
(all-forward-then-all-backward :33-89), pipedream_subexecutor.py (1F1B
generator :25-48, weight stashing :93-120), PipelineSend/ReceiveOp with
NCCL group calls (executor.py:1196-1205).

TPU design (SPMD collective pipelining): stages hold equal-structure block
stacks, stacked on a leading dim sharded over 'pp'.  A fori_loop runs
M + n_stages - 1 ticks; every tick each device applies its stage and
ppermutes activations to the next stage — the PipelineSend/Recv pair is one
ICI hop.  The schedule emerges from XLA autodiff: differentiating the loop
replays it in reverse, which IS all-forward-then-all-backward (GPipe).
Per-stage rematerialization (jax.checkpoint) gives the activation-memory
profile the reference gets from micro-batch array maps.  The 1F1B
(PipeDream) interleaving is provided as an explicit schedule object
(`pipedream_schedule`, same contract as the reference's generator) — used by
the simulator/planner; on-TPU execution uses the SPMD loop, where XLA
already overlaps the fwd/bwd halves it can.

Heterogeneous per-stage DP (reference round-robin skip schedules) maps to a
dp axis alongside pp in the same mesh: every stage runs the same dp degree
in SPMD, which subsumes the reference's unequal-DP machinery for the common
case; truly unequal degrees would need MPMD (multi-controller), out of scope
for a single jit program.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


def stack_stage_params(per_layer_params, n_stages: int):
    """[L, ...] stacked layer params → [n_stages, L/n_stages, ...] (shared
    by the GPipe and 1F1B executors)."""
    def reshape(leaf):
        L = leaf.shape[0]
        assert L % n_stages == 0, (
            f"{L} layers not divisible by {n_stages} stages")
        return leaf.reshape(n_stages, L // n_stages, *leaf.shape[1:])
    return jax.tree_util.tree_map(reshape, per_layer_params)


def pipedream_schedule(n_stages: int, n_microbatches: int):
    """1F1B order per stage (reference pipedream_subexecutor.py:25-48).

    Yields per-stage lists of ("fwd"|"bwd", microbatch_id): warmup of
    (n_stages - stage - 1) forwards, then alternating 1F1B, then drain.
    """
    out = []
    for s in range(n_stages):
        warmup = min(n_stages - s - 1, n_microbatches)
        order = []
        f = b = 0
        for _ in range(warmup):
            order.append(("fwd", f)); f += 1
        while f < n_microbatches:
            order.append(("fwd", f)); f += 1
            order.append(("bwd", b)); b += 1
        while b < n_microbatches:
            order.append(("bwd", b)); b += 1
        out.append(order)
    return out


class GPipe:
    """SPMD GPipe executor for a homogeneous block stack.

    block_fn(block_params, h) -> h — one transformer-block-like unit.
    Stage s applies its slice of the stacked blocks via lax.scan.

    stacked params layout: each leaf [n_stages, layers_per_stage, ...],
    sharded P('pp') on dim 0.  Input/output h: [B, S, ...] (batch dim 0 is
    split into n_microbatches).

    Usage:
        pipe = GPipe(block_fn, mesh, n_microbatches=8)
        out = pipe(stacked_params, h)         # differentiable
    """

    def __init__(self, block_fn: Callable, mesh: Mesh, *, axis: str = "pp",
                 n_microbatches: int = 4, remat: bool = True):
        self.block_fn = block_fn
        self.mesh = mesh
        self.axis = axis
        self.n_stages = mesh.shape[axis]
        self.n_microbatches = n_microbatches
        self.remat = remat

    def stack_params(self, per_layer_params):
        """[L, ...] stacked layer params → [n_stages, L/n_stages, ...]."""
        return stack_stage_params(per_layer_params, self.n_stages)

    def stack_params_unequal(self, per_layer_params, stage_bounds):
        """Pack UNEQUAL stages (a searcher's Plan.stage_bounds) by padding
        every stage to the longest one; returns (stacked, layer_mask) where
        layer_mask [n_stages, L_max] marks real (non-padding) layer slots.

        per_layer_params: leaves stacked on a leading layer dim [L, ...]
        (same layout stack_params takes).  stage_bounds: ascending layer
        end-indices, one per stage (GPipeSearching output).
        """
        bounds = list(stage_bounds)
        assert len(bounds) == self.n_stages, (bounds, self.n_stages)
        starts = [0] + bounds[:-1]
        sizes = [e - s for s, e in zip(starts, bounds)]
        l_max = max(sizes)
        mask = jnp.asarray([[1.0] * n + [0.0] * (l_max - n) for n in sizes])

        def pack(leaf):
            segs = []
            for s, n in zip(starts, sizes):
                seg = leaf[s:s + n]
                if n < l_max:
                    pad = jnp.zeros((l_max - n, *leaf.shape[1:]), leaf.dtype)
                    seg = jnp.concatenate([seg, pad], axis=0)
                segs.append(seg)
            return jnp.stack(segs)

        return jax.tree_util.tree_map(pack, per_layer_params), mask

    def __call__(self, stacked_params, h, *, layer_mask=None):
        """layer_mask [n_stages, L_max]: 1 = real layer, 0 = padding slot
        (identity) — produced by stack_params_unequal for searched plans."""
        M = self.n_microbatches
        B = h.shape[0]
        assert B % M == 0, f"batch {B} not divisible by {M} microbatches"
        mb = B // M
        xs = h.reshape(M, mb, *h.shape[1:])
        if layer_mask is None:
            n_per = jax.tree_util.tree_leaves(stacked_params)[0].shape[1]
            layer_mask = jnp.ones((self.n_stages, n_per))

        block = self.block_fn
        if self.remat:
            block = jax.checkpoint(block)
        axis = self.axis
        n = self.n_stages

        def local(params, xs, mask):
            # params leaves arrive [1, Lps, ...] (this stage's slice)
            params = jax.tree_util.tree_map(lambda a: a[0], params)
            mask = mask[0]
            s = lax.axis_index(axis)
            T = M + n - 1
            buf = jnp.zeros_like(xs[0])
            outs = jnp.zeros_like(xs)

            def stage_apply(h):
                def body(carry, xs_l):
                    p_l, valid = xs_l
                    out = block(p_l, carry)
                    # padding slots pass activations through unchanged
                    return jnp.where(valid > 0, out, carry), None
                out, _ = lax.scan(body, h, (params, mask))
                return out

            def tick(carry, t):
                buf, outs = carry
                inject = xs[jnp.clip(t, 0, M - 1)]
                h_in = jnp.where(s == 0, inject, buf)
                h_out = stage_apply(h_in)
                perm = [(j, (j + 1) % n) for j in range(n)]
                buf_next = lax.ppermute(h_out, axis, perm)
                done = t - (n - 1)
                valid = (done >= 0) & (s == n - 1)
                idx = jnp.clip(done, 0, M - 1)
                outs = outs.at[idx].set(
                    jnp.where(valid, h_out, outs[idx]))
                return (buf_next, outs), None

            # scan (not fori_loop): the tick loop must be reverse-mode
            # differentiable — its reversal IS the backward pipeline
            (buf, outs), _ = lax.scan(tick, (buf, outs), jnp.arange(T))
            # broadcast last stage's outputs to all stages (zero elsewhere,
            # psum over the pp axis)
            outs = jnp.where(s == n - 1, outs, jnp.zeros_like(outs))
            return lax.psum(outs, axis)

        in_param_spec = jax.tree_util.tree_map(
            lambda _: P(self.axis), stacked_params)
        out = shard_map(local, mesh=self.mesh,
                        in_specs=(in_param_spec, P(), P(self.axis)),
                        out_specs=P(),
                        check_vma=False)(stacked_params, xs, layer_mask)
        return out.reshape(B, *h.shape[1:])
