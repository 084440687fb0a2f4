"""PipeDream-style 1F1B pipeline runtime.

Reference: python/hetu/gpu_ops/pipedream_subexecutor.py — the 1F1B
generator schedule (:25-48) with per-micro-batch weight stashing (:93-120).

TPU runtime: unlike GPipe (parallel/pipeline.py), whose autodiff reversal
stores EVERY microbatch's stage activations, this executor interleaves
forward and backward ticks explicitly so a stage holds at most
``2*n_stages`` stashed microbatch INPUTS (activation checkpointing at stage
granularity — backward recomputes the stage forward from the stashed input
via jax.vjp).  Memory: O(n_stages) stashes vs GPipe's O(n_microbatches).

Weight stashing note: the reference stashes WEIGHTS per in-flight
microbatch so delayed backwards use the weights their forward saw.  Here
parameters are functionally frozen for the whole step (grads apply once at
the end — the PipeDream-Flush / 1F1B-with-flush variant Galvatron uses),
so forward/backward always agree by construction and the stash holds only
activations.

Schedule (flush variant): tick t runs, per stage s,
  forward  of microbatch f whenever the warmup/steady pattern admits one,
  backward of microbatch b once the next stage has returned its cotangent,
interleaved exactly as pipedream_schedule(n_stages, M) prescribes; the
implementation runs BOTH phases each tick (masked) which realizes that
order with the same bubble structure.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


class PipeDream1F1B:
    """1F1B (flush) pipeline over a homogeneous block stack.

    block_fn(stage_params, h) -> h; stage s applies its [L/S] slice via
    scan.  Usage:

        pipe = PipeDream1F1B(block_fn, mesh, n_microbatches=8)
        stacked = pipe.stack_params(per_layer_params)   # [S, L/S, ...]
        out, grads = pipe.forward_and_grad(stacked, h, cotangent)
    or, with a scalar loss on the last stage's outputs, use
    `value_and_grad(stacked, h, loss_fn)`.
    """

    def __init__(self, block_fn: Callable, mesh: Mesh, *, axis: str = "pp",
                 n_microbatches: int = 4):
        self.block_fn = block_fn
        self.mesh = mesh
        self.axis = axis
        self.n_stages = mesh.shape[axis]
        self.n_microbatches = n_microbatches

    def stack_params(self, per_layer_params):
        from hetu_tpu.parallel.pipeline import stack_stage_params
        return stack_stage_params(per_layer_params, self.n_stages)

    # ---- core: forward outputs + parameter grads in ONE pipelined pass ----
    def _run(self, stacked_params, xs, gout, *, fwd_only: bool = False):
        """xs [M, mb, ...] stage-0 inputs; gout [M, mb, ...] cotangents of
        the last stage's outputs.  Returns (outs [M, mb, ...], grads like
        stacked_params local slice).  fwd_only skips the whole backward
        phase (used by value_and_grad's output pass)."""
        M = self.n_microbatches
        n = self.n_stages
        axis = self.axis
        block = self.block_fn

        def stage_fwd(params, h):
            def body(carry, p_l):
                return block(p_l, carry), None
            out, _ = lax.scan(body, h, params)
            return out

        def local(params, xs, gout):
            params = jax.tree_util.tree_map(lambda a: a[0], params)
            mask_shape = xs.shape[1:]
            s = lax.axis_index(axis)

            # a forward for microbatch f runs on this stage at tick f + s;
            # its backward returns here at tick 2*n - 2 + 2*(f - ... ) —
            # with the flush schedule below, bwd of f runs at stage s at
            # tick T_b(f, s) = (n - 1) + f + (n - 1 - s) = 2n - 2 + f - s.
            T = (n - 1 + M) if fwd_only else (2 * n - 2 + M)

            # stash depth 2n: with fwd pacing f+s and bwd at 2n-2-s+f, a
            # stage holds at most 2n-2-2s in-flight inputs; 2n slots make
            # slot reuse (f and f+2n) always land after the consume tick
            dt = xs.dtype  # keep activations in the input precision
            stash = jnp.zeros((2 * n, *mask_shape), dt)  # in-flight inputs
            fwd_buf = jnp.zeros(mask_shape, dt)   # activation hop fwd
            bwd_buf = jnp.zeros(mask_shape, dt)   # cotangent hop bwd
            outs = jnp.zeros_like(xs)
            grads0 = jax.tree_util.tree_map(jnp.zeros_like, params)
            perm_f = [(j, (j + 1) % n) for j in range(n)]
            perm_b = [(j, (j - 1) % n) for j in range(n)]

            def tick(carry, t):
                stash, fwd_buf, bwd_buf, outs, grads = carry

                # ---- forward phase of this tick ----
                f_id = t - s                       # microbatch this stage fwds
                fwd_live = (f_id >= 0) & (f_id < M)
                h_in = jnp.where(s == 0, xs[jnp.clip(f_id, 0, M - 1)],
                                 fwd_buf)
                h_out = stage_fwd(params, h_in)
                # stash the INPUT for this microbatch's backward
                slot = jnp.clip(f_id, 0, M - 1) % (2 * n)
                stash = stash.at[slot].set(
                    jnp.where(fwd_live, h_in, stash[slot]))
                # last stage records its outputs
                o_idx = jnp.clip(f_id, 0, M - 1)
                outs = outs.at[o_idx].set(
                    jnp.where(fwd_live & (s == n - 1), h_out, outs[o_idx]))

                # ---- backward phase of this tick ----
                if not fwd_only:
                    b_id = t - (2 * n - 2 - s)     # microbatch this stage bwds
                    bwd_live = (b_id >= 0) & (b_id < M)
                    g_in = jnp.where(s == n - 1,
                                     gout[jnp.clip(b_id, 0, M - 1)], bwd_buf)
                    x_saved = stash[jnp.clip(b_id, 0, M - 1) % (2 * n)]
                    _, vjp = jax.vjp(stage_fwd, params, x_saved)
                    gp, gx = vjp(g_in)
                    grads = jax.tree_util.tree_map(
                        lambda acc, g: acc + jnp.where(bwd_live, g, 0.0),
                        grads, gp)
                    bwd_buf_next = lax.ppermute(
                        jnp.where(bwd_live, gx, 0.0), axis, perm_b)
                else:
                    bwd_buf_next = bwd_buf

                # ---- hops ----
                fwd_buf = lax.ppermute(
                    jnp.where(fwd_live, h_out, jnp.zeros_like(h_out)),
                    axis, perm_f)
                return (stash, fwd_buf, bwd_buf_next, outs, grads), None

            (stash, fwd_buf, bwd_buf, outs, grads), _ = lax.scan(
                tick, (stash, fwd_buf, bwd_buf, outs, grads0),
                jnp.arange(T))
            # broadcast last stage's outputs everywhere (zero elsewhere)
            outs = jnp.where(s == n - 1, outs, jnp.zeros_like(outs))
            outs = lax.psum(outs, axis)
            return outs, jax.tree_util.tree_map(lambda g: g[None], grads)

        in_param_spec = jax.tree_util.tree_map(
            lambda _: P(self.axis), stacked_params)
        outs, grads = shard_map(
            local, mesh=self.mesh,
            in_specs=(in_param_spec, P(), P()),
            out_specs=(P(), in_param_spec),
            check_vma=False)(stacked_params, xs, gout)
        return outs, grads

    # ---- public API ----
    def forward_and_grad(self, stacked_params, h, cotangent):
        """h [B, ...] stage-0 inputs; cotangent [B, ...] = dL/d(outputs).
        Returns (outputs [B, ...], param grads like stacked_params)."""
        M = self.n_microbatches
        B = h.shape[0]
        assert B % M == 0
        mb = B // M
        xs = h.reshape(M, mb, *h.shape[1:])
        gs = cotangent.reshape(M, mb, *h.shape[1:])
        outs, grads = self._run(stacked_params, xs, gs)
        return outs.reshape(B, *h.shape[1:]), grads

    def value_and_grad(self, stacked_params, h, loss_fn):
        """loss_fn(outputs [B, ...]) -> scalar, computed (replicated) on the
        last stage's outputs; returns (loss, param grads).

        Two pipelined passes: one to get outputs (for the loss cotangent),
        one interleaved fwd/bwd pass for the grads — still O(n_stages)
        activation stash per stage.
        """
        M = self.n_microbatches
        B = h.shape[0]
        assert B % M == 0, f"batch {B} not divisible by {M} microbatches"
        mb = B // M
        xs = h.reshape(M, mb, *h.shape[1:])
        zero_g = jnp.zeros_like(xs)
        outs, _ = self._run(stacked_params, xs, zero_g, fwd_only=True)
        outs_flat = outs.reshape(B, *h.shape[1:])
        loss, back = jax.vjp(loss_fn, outs_flat)
        (cot,) = back(jnp.ones_like(loss))
        _, grads = self.forward_and_grad(stacked_params, h, cot)
        return loss, grads
