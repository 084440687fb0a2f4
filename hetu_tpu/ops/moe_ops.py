"""MoE dispatch/combine primitives.

Reference: python/hetu/gpu_ops/{Dispatch,LayoutTransform,ReverseLayoutTransform,
TopKIdx,GroupTopKIdx,BalanceAssignment,MinDist,Sample}.py and the CUDA layout
kernels; assembled by layers/moe_layer.py in the reference.

TPU design (GShard-style): instead of the reference's scatter/gather layout
kernels we build one-hot *dispatch* and *combine* tensors so the whole
token->expert permutation is two einsums — dense MXU work that XLA overlaps
with the expert all_to_all.  Capacity is static (required by XLA); overflow
tokens are dropped exactly like the reference's capacity_factor path.

Two routings live here, and they are not interchangeable.  Everything down to
:func:`balance_assignment` is the CAPACITY routing that ``layers/moe.py``'s
``MoELayer`` trains with: each expert gets a static number of slots, is
padded to it, and drops what overflows.  :func:`route_biased_top_k` and
:func:`held_expert_ffn` at the end are the HELD-EXPERT routing of a model
served or trained as one chip's share (``models/longcat_flash.py``,
``models/exaone_moe.py``, ``models/deepseek_v3.py``): a router as wide as
published over experts of which this chip holds a contiguous share, no
capacity, no drops, and work that follows the rows routed here, forward and
backward.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax


def top_k_idx_gate(logits, k: int):
    """Top-k expert selection (gpu_ops/TopKIdx.py).

    Returns (gate_weights [tokens,k] softmaxed over the chosen k, idx [tokens,k]).
    """
    vals, idx = lax.top_k(logits, k)
    gates = jax.nn.softmax(vals, axis=-1)
    return gates, idx


def _capacity_positions(expert_idx, num_experts: int, capacity: int):
    """Shared in-order capacity assignment: position of each (token,
    choice) within its chosen expert's queue — earlier tokens and lower
    choice index first, matching the reference's LayoutTransform.cu index
    computation.  Both routing builders (dense-mask and index-based) call
    this so their routing decisions agree bit-for-bit.

    Returns (one_hot [T,k,E] int32, pos [T,k], within_capacity [T,k] bool).
    """
    T, k = expert_idx.shape
    oh = jax.nn.one_hot(expert_idx, num_experts, dtype=jnp.int32)
    flat = oh.reshape(T * k, num_experts)
    pos_in_expert = jnp.cumsum(flat, axis=0) - flat          # [T*k, E]
    pos = jnp.sum(pos_in_expert.reshape(T, k, num_experts) * oh, axis=-1)
    return oh, pos, pos < capacity


def make_dispatch_combine(gates, expert_idx, num_experts: int, capacity: int):
    """Build dispatch/combine tensors from top-k gate decisions.

    gates: [T, k] combine weights; expert_idx: [T, k] chosen experts.
    Returns:
      dispatch [T, E, C] bool — token t goes to slot c of expert e
      combine  [T, E, C] float — dispatch weighted by gate prob
    Equivalent of the reference's layout_transform_op index computation
    (src/ops/LayoutTransform.cu) but as dense masks for the MXU.
    """
    T, k = gates.shape
    oh, pos, within_cap = _capacity_positions(expert_idx, num_experts,
                                              capacity)
    slot_oh = jax.nn.one_hot(jnp.where(within_cap, pos, capacity),
                             capacity + 1, dtype=gates.dtype)[..., :capacity]
    disp = jnp.einsum("tke,tkc->tec", oh.astype(gates.dtype), slot_oh)
    comb = jnp.einsum("tk,tke,tkc->tec", gates, oh.astype(gates.dtype), slot_oh)
    return disp, comb


def make_slot_routing(gates, expert_idx, num_experts: int, capacity: int):
    """Index-based routing tables (the O(T·k) alternative to the dense
    [T, E, C] masks of :func:`make_dispatch_combine`, whose einsum
    dispatch costs O(T²·D) at MoE scale).

    Same in-order capacity assignment as the reference's
    LayoutTransform.cu index computation, but kept as indices:
      slot_token [E*C] — which token fills each expert slot (-1 = empty)
      token_slot [T,k] — which flat slot each (token, choice) landed in
                         (-1 = dropped by capacity)
      n_dropped  []    — how many (token, choice) routes overflowed
    """
    T, k = gates.shape
    _, pos, within = _capacity_positions(expert_idx, num_experts, capacity)
    token_slot = jnp.where(within, expert_idx * capacity + pos, -1)
    tok_ids = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[:, None],
                               (T, k))
    slot_token = jnp.full((num_experts * capacity,), -1, jnp.int32).at[
        jnp.where(within, token_slot, num_experts * capacity)
    ].set(tok_ids, mode="drop")
    n_dropped = T * k - jnp.sum(within.astype(jnp.int32))
    return slot_token, token_slot, n_dropped


def gather_dispatch(tokens, slot_token, num_experts: int, capacity: int,
                    *, kernel=None):
    """tokens [T, D] → expert-major [E, C, D] by row gather (empty slots
    zero).  Pallas routed_gather on TPU (``kernel``: see routed_gather);
    replaces the einsum dispatch's O(T·E·C·D) flops with O(E·C·D) bytes."""
    from hetu_tpu.ops.pallas_kernels import routed_gather
    rows = routed_gather(tokens, slot_token, kernel=kernel)
    return rows.reshape(num_experts, capacity, tokens.shape[-1])


def gather_combine(expert_out, token_slot, gates, *, kernel=None):
    """[E, C, D] expert outputs → [T, D] token outputs, gate-weighted;
    dropped routes contribute zero (capacity-overflow semantics of the
    reference's ReverseLayoutTransform)."""
    from hetu_tpu.ops.pallas_kernels import routed_gather
    E, C, D = expert_out.shape
    T, k = token_slot.shape
    flat = expert_out.reshape(E * C, D)
    picked = routed_gather(flat, token_slot.reshape(-1),
                           kernel=kernel)                # [T*k, D]
    picked = picked.reshape(T, k, D)
    return jnp.sum(gates[..., None].astype(picked.dtype) * picked, axis=1)


def layout_transform(tokens, dispatch):
    """Pack tokens into [E, C, D] expert-major layout (gpu_ops/LayoutTransform.py)."""
    return jnp.einsum("td,tec->ecd", tokens, dispatch)


def reverse_layout_transform(expert_out, combine):
    """Un-pack expert outputs back to token order, gate-weighted
    (gpu_ops/ReverseLayoutTransform.py)."""
    return jnp.einsum("ecd,tec->td", expert_out, combine)


def balance_assignment(scores, *, iters: int = 20):
    """Balanced token->expert assignment via Sinkhorn iteration.

    Reference: gpu_ops/BalanceAssignment.py implements the BASE layer's
    auction algorithm (Lewis et al.).  Auctions are sequential and hostile to
    XLA; Sinkhorn normalization achieves the same balanced doubly-stochastic
    assignment with fixed iteration count (the standard TPU reformulation).
    scores: [T, E] affinities. Returns expert index per token [T].
    """
    T, E = scores.shape
    logp = scores - jnp.max(scores, axis=-1, keepdims=True)

    def body(_, lp):
        lp = lp - jax.nn.logsumexp(lp, axis=0, keepdims=True)
        lp = lp - jax.nn.logsumexp(lp, axis=1, keepdims=True)
        return lp

    lp = lax.fori_loop(0, iters, body, logp)
    return jnp.argmax(lp, axis=-1)


# ---------------------------------------------------------------------------
# held-expert routing: no capacity, no drops (the served path)
# ---------------------------------------------------------------------------

def route_biased_top_k(scores, bias, k: int):
    """Choose each token's ``k`` experts by ``scores + bias`` and weigh them
    by ``scores`` alone (the correction bias steers the choice and never the
    weight).  scores [T, E] float32, bias [E] -> (weights [T, k] float32,
    idx [T, k] int32).  No renormalisation over the chosen ``k``."""
    _, idx = lax.top_k(scores + bias, k)
    return jnp.take_along_axis(scores, idx, axis=-1), idx.astype(jnp.int32)


class _WalkPlan(NamedTuple):
    """The walk over the (token, choice) pairs that land on the ``E`` held
    experts, in blocks of ``R`` rows.  The forward and the backward walk
    both read their trip count from it: ``trips``, the blocks that hold a
    pair."""
    order: jax.Array       # pair indices sorted by held expert, absent last,
    #                        R zeros behind them: a block's slice never clamps
    counts: jax.Array      # [E] pairs per held expert
    blocks: jax.Array      # [E] blocks per held expert
    block_end: jax.Array   # [E] their running end
    row_start: jax.Array   # [E] each expert's first row in the sorted order

    @property
    def trips(self):
        return self.block_end[-1]


def _walk_plan(idx, first: int, E: int, R: int) -> _WalkPlan:
    """The plan for pairs ``idx`` [T, k] over the ``E`` experts held from
    ``first`` on."""
    local = idx.reshape(-1) - first
    held = (local >= 0) & (local < E)
    key = jnp.where(held, local, E)                       # absent: sorted last
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    counts = jnp.zeros((E + 1,), jnp.int32).at[key].add(1)[:E]
    blocks = -(-counts // R)                              # per expert
    block_end = jnp.cumsum(blocks)                        # [E]
    row_start = jnp.cumsum(counts) - counts               # [E]
    order = jnp.concatenate([order, jnp.zeros((R,), jnp.int32)])
    return _WalkPlan(order, counts, blocks, block_end, row_start)


def _walk_block(plan, b, R: int):
    """Block ``b`` of the walk: (its held expert, the ``R`` pair indices it
    covers, which of them are its expert's own)."""
    order, counts, blocks, block_end, row_start = plan
    E = counts.shape[0]
    e = jnp.minimum(jnp.searchsorted(block_end, b, side="right"), E - 1)
    within = b - (block_end[e] - blocks[e])               # block of expert e
    start = row_start[e] + within * R
    pairs = lax.dynamic_slice_in_dim(order, start, R)
    live = (start + jnp.arange(R)) < row_start[e] + counts[e]
    return e, pairs, live


def _expert_of(w, e, layer):
    return w[e] if layer is None else w[layer, e]


def _held_forward(x, weights, idx, w_gate, w_up, w_down, layer, first, R):
    k = idx.shape[1]
    E = w_gate.shape[0 if layer is None else 1]
    plan = _walk_plan(idx, first, E, R)
    pair_w = weights.reshape(-1)
    dt = x.dtype

    def body(b, out):
        e, pairs, live = _walk_block(plan, b, R)
        rows = x[pairs // k]                              # [R, H]
        g = jnp.dot(rows, _expert_of(w_gate, e, layer).astype(dt))
        u = jnp.dot(rows, _expert_of(w_up, e, layer).astype(dt))
        y = jnp.dot(jax.nn.silu(g) * u,
                    _expert_of(w_down, e, layer).astype(dt),
                    preferred_element_type=jnp.float32)
        y = jnp.where(live[:, None], y * pair_w[pairs][:, None], 0.0)
        return out.at[pairs // k].add(y)

    out = lax.fori_loop(0, plan.trips, body,
                        jnp.zeros(x.shape, jnp.float32))
    return (out, plan.counts), plan


def _held_backward(x, weights, idx, w_gate, w_up, w_down, layer, plan, d_out,
                   R):
    """The walk again, block by block over the blocks that hold a pair: a
    block's gate and up are recomputed from its rows, ``dW`` is added into
    its expert's gradient, ``dx`` into its rows' and the pair weights'
    gradient into its pairs'.  An expert nobody chose keeps a zero gradient
    and its weights are not read."""
    k = idx.shape[1]
    pair_w = weights.reshape(-1)
    dt = x.dtype
    f32 = jnp.float32

    def body(b, carry):
        dx, dpw, dwg, dwu, dwd = carry
        e, pairs, live = _walk_block(plan, b, R)
        tok = pairs // k
        rows = x[tok]
        wg = _expert_of(w_gate, e, layer).astype(dt)
        wu = _expert_of(w_up, e, layer).astype(dt)
        wd = _expert_of(w_down, e, layer).astype(dt)
        g, u = jnp.dot(rows, wg), jnp.dot(rows, wu)       # as the forward's
        a = jax.nn.silu(g) * u                            # [R, F]
        dy = jnp.where(live[:, None], d_out[tok], 0.0)    # [R, H] float32
        y = jnp.dot(a, wd, preferred_element_type=f32)
        dpw = dpw.at[pairs].add(jnp.sum(y * dy, -1))
        dy = (dy * pair_w[pairs][:, None]).astype(dt)
        da = lax.dot_general(dy, wd, (((1,), (1,)), ((), ())),
                             preferred_element_type=f32)  # [R, F]
        gf, uf = g.astype(f32), u.astype(f32)
        sig = jax.nn.sigmoid(gf)
        dg = (da * uf * sig * (1.0 + gf * (1.0 - sig))).astype(dt)
        du = (da * gf * sig).astype(dt)
        tn = (((0,), (0,)), ((), ()))                     # a^T b
        dwd = dwd.at[e].add(lax.dot_general(
            a, dy, tn, preferred_element_type=f32))
        dwg = dwg.at[e].add(lax.dot_general(
            rows, dg, tn, preferred_element_type=f32))
        dwu = dwu.at[e].add(lax.dot_general(
            rows, du, tn, preferred_element_type=f32))
        nt = (((1,), (1,)), ((), ()))                     # a b^T
        drows = lax.dot_general(dg, wg, nt, preferred_element_type=f32) \
            + lax.dot_general(du, wu, nt, preferred_element_type=f32)
        return dx.at[tok].add(drows), dpw, dwg, dwu, dwd

    def zeros(w):
        return jnp.zeros(w.shape[-3:], f32)

    dx, dpw, dwg, dwu, dwd = lax.fori_loop(
        0, plan.trips, body,
        (jnp.zeros(x.shape, f32), jnp.zeros(pair_w.shape, f32),
         zeros(w_gate), zeros(w_up), zeros(w_down)))

    def back(w, dw):
        dw = dw.astype(w.dtype)
        return dw if layer is None else jnp.zeros_like(w).at[layer].set(dw)

    return (dx.astype(x.dtype), dpw.reshape(weights.shape).astype(
        weights.dtype), None, back(w_gate, dwg), back(w_up, dwu),
        back(w_down, dwd), None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _held(x, weights, idx, w_gate, w_up, w_down, layer, first, R):
    return _held_forward(x, weights, idx, w_gate, w_up, w_down, layer,
                         first, R)[0]


def _held_vjp_fwd(x, weights, idx, w_gate, w_up, w_down, layer, first, R):
    out, plan = _held_forward(x, weights, idx, w_gate, w_up, w_down, layer,
                              first, R)
    return out, (x, weights, idx, w_gate, w_up, w_down, layer, plan)


def _held_vjp_bwd(first, R, res, g):
    return _held_backward(*res, g[0], R)


_held.defvjp(_held_vjp_fwd, _held_vjp_bwd)


def held_expert_ffn(x, weights, idx, w_gate, w_up, w_down, *, first: int,
                    block_rows: int = 128, layer=None):
    """SwiGLU experts over the (token, choice) pairs that land on the
    experts held here; what absent experts would add is left out.

    x [T, H]; weights/idx [T, k] from the router over ALL experts;
    w_gate/w_up [E, H, F], w_down [E, F, H]: the ``E`` held experts, global
    indices ``first .. first + E - 1``; with ``layer`` given they are
    stacked over layers, [L, E, ...], and this is layer ``layer`` (inside a
    scan over layers a block then reads its expert straight from the stacked
    leaf; sliced a layer at a time first, the slice is copied every step).
    Returns (out [T, H] float32, counts [E] int32 pairs per held expert).

    No capacity and no drops: the pairs are sorted by held expert and walked
    in blocks of ``block_rows`` rows, ``ceil(count_e / block_rows)`` blocks
    for expert ``e``, so every pair is computed at any imbalance and the
    work follows the rows routed here, not ``T * k``.  An expert nobody chose costs
    nothing: its weights are not read.  Nothing is bounded by a buffer of
    rows: a block gathers its rows from ``x`` and adds its result into
    ``out``; only the sorted pair indices are held, ``T * k + block_rows``
    integers.  The trip count is read from the counts.

    Reverse mode walks the same blocks (a ``custom_vjp``: a loop whose trip
    count is read from data has no transpose of its own): the backward's
    cost follows the load as the forward's does, and no block's
    intermediate is kept between the two.  Gradients go to ``x``, to the
    pair ``weights`` (and through them to the router) and to the three
    weight leaves, accumulated in float32 and rounded once to the leaf's
    type; given ``layer``, a leaf's gradient is zero outside that layer."""
    return _held(x, weights, idx, w_gate, w_up, w_down, layer, int(first),
                 int(block_rows))
