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
``models/exaone_moe.py``, ``models/deepseek_v3.py``, ``models/mellum.py``):
a router as wide as published over experts of which this chip holds a
contiguous share, no capacity, no drops, and work that follows the rows
routed here, forward and backward.  It has two paths, chosen from static
shapes by :func:`held_expert_path`: sorted rows through grouped matmuls
where an expert's weight fits the kernels' VMEM whole (the trained cells'
steps, LFM2's and Qwen3-Next's decode rounds and prefill chunks); and where
it does not (K-EXAONE's and LongCat's 6144 x 2048 experts) the same sorted
rows through ONE fused grouped call cut along the expert's intermediate
width wherever the walk is evaluated, and a loop over row blocks wherever it
is differentiated.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from hetu_tpu.ops.pallas_kernels import grouped_matmul as gm


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


def _clamped(g, u, limit):
    """The model's clamp inside a SwiGLU: ``(min(g, limit), clip(u, -limit,
    limit))``; ``limit`` None: as they are."""
    if limit is None:
        return g, u
    return jnp.minimum(g, limit), jnp.clip(u, -limit, limit)


def _held_forward(x, weights, idx, w_gate, w_up, w_down, layer, first, R,
                  limit=None):
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
        g, u = _clamped(g, u, limit)
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _held(x, weights, idx, w_gate, w_up, w_down, layer, first, R, B,
          limit=None):
    # evaluated, not differentiated (JAX runs the two rules below only under
    # autodiff): the grouped path's forward in trips of ``B`` rows, an
    # expert a fused call cut along F, and the loop reverse mode's alone;
    # without ``B`` (no tile of F fits) the loop's own forward
    if B is None:
        return _held_forward(x, weights, idx, w_gate, w_up, w_down, layer,
                             first, R, limit)[0]
    return _grouped_forward(x, weights, idx, w_gate, w_up, w_down, layer,
                            first, B, True, limit)[0]


def _served_alone(limit):
    if limit is not None:
        raise NotImplementedError(
            "a SwiGLU clamped by the model's swiglu_limit is evaluated (a "
            "served round or chunk, the dense forward); its reverse mode "
            "is not written")


def _held_vjp_fwd(x, weights, idx, w_gate, w_up, w_down, layer, first, R, B,
                  limit):
    _served_alone(limit)
    out, plan = _held_forward(x, weights, idx, w_gate, w_up, w_down, layer,
                              first, R)
    return out, (x, weights, idx, w_gate, w_up, w_down, layer, plan)


def _held_vjp_bwd(first, R, B, limit, res, g):
    return _held_backward(*res, g[0], R)


_held.defvjp(_held_vjp_fwd, _held_vjp_bwd)


# ---------------------------------------------------------------------------
# the grouped path: sort once, grouped matmuls, combine once
#
# Its forward and backward are module-level JITTED functions of arrays, the
# Python values (``first``, the row budget) static: every walk of a program
# at one shape is then one traced function, lowered once, its Pallas calls
# with it (why that matters, and what it asks of ``ops.remat``:
# :func:`held_expert_ffn`).  XLA inlines the calls.  The forward has two
# forms by the static row budget: trips of three grouped calls and a
# scatter-add where a chip holds a share of the experts (a training step),
# and ONE call and a gather where one trip holds every pair (a served model
# that holds every expert).
# ---------------------------------------------------------------------------

# the most sorted rows a trip of the grouped path holds at once: its buffers
# are [rows, H] and [rows, F], whatever T * k is
GROUPED_ROW_BUDGET = 20480
# the grouped path is taken, forward and backward, where an expert's [H, F]
# weight is at most this many elements: the grouped matmuls keep one whole in
# VMEM, two of them double-buffered in the call that makes dx, three in the
# call that is a whole expert.  Past it the constant separates what is
# EVALUATED (a served round or chunk: the fused call of a whole expert, cut
# along F, ``gm.ffn_tiles``) from what is DIFFERENTIATED (the loop: the calls
# that make dx and dW hold two or three whole weights and are not cut)
GROUPED_MAX_WEIGHT = 4 * 1024 * 1024


def held_expert_path(T: int, k: int, E: int, H: int, F: int,
                     itemsize: int = 2) -> str:
    """``"grouped"``, ``"cut"`` or ``"loop"``: which walk
    :func:`held_expert_ffn` takes for ``T`` tokens of ``k`` choices over
    ``E`` held experts of ``[H, F]`` (``itemsize`` bytes an element).
    Static shapes alone decide, and of them the expert's size alone: where
    the kernels can keep a weight whole the grouped path is ahead at every
    row count measured, one token included; where they cannot, a walk that
    is EVALUATED runs the grouped path's forward with an expert a fused call
    cut along F, and one that is differentiated runs the loop's rules
    (``"cut"``); the loop evaluates too only where no whole-lane tile of F
    fits (``"loop"``: no published width).  The rule and the measurements
    behind it are stated in :func:`held_expert_ffn`."""
    if H * F <= GROUPED_MAX_WEIGHT:
        return "grouped"
    return "cut" if gm.ffn_tiles(H, F, itemsize) else "loop"


def grouped_row_budget(T: int, k: int, E: int, routed=None) -> int:
    """Sorted rows a trip of the grouped path holds.  The pairs an even
    router over ``routed`` experts sends to ``E`` of them, and an eighth
    more (every pair, where the router's width is not given), are cut into
    the fewest equal trips of at most :data:`GROUPED_ROW_BUDGET` rows, in
    whole tiles.  Static; a load past it costs trips, never rows.  Where the
    chip holds every expert the budget is every pair, ``T * k`` rounded up
    to a tile: one trip, whatever the router does."""
    pairs = T * k if routed is None else -(-T * k * E * 9 // (routed * 8))
    rows = min(T * k, pairs)
    rows = -(-rows // -(-rows // GROUPED_ROW_BUDGET))     # a trip's
    return -(-rows // gm.TILE_ROWS) * gm.TILE_ROWS


def _layer_leaves(leaves, layer, dt):
    """(leaves, layer) as the grouped matmuls read them: leaves of ``dt``
    already stay in place, stacked or not; otherwise this layer's slices,
    cast."""
    if all(w.dtype == dt for w in leaves):
        return leaves, layer
    return [(w if layer is None else w[layer]).astype(dt)
            for w in leaves], None


def _leaf_grad(w, dw, layer):
    """``dw`` (one layer's, float32) as the cotangent of leaf ``w``."""
    dw = dw.astype(w.dtype)
    return dw if layer is None else jnp.zeros_like(w).at[layer].set(dw)


class _GroupedPlan(NamedTuple):
    """The pairs that land on the ``E`` held experts, sorted once."""
    order: jax.Array       # pair indices sorted by held expert, absent last,
    #                        a trip's rows of zeros behind them
    counts: jax.Array      # [E] pairs per held expert
    row_start: jax.Array   # [E] each expert's first row in the sorted order

    @property
    def held(self):
        return self.row_start[-1] + self.counts[-1]


def _grouped_plan(idx, first: int, E: int, B: int) -> _GroupedPlan:
    """:func:`_walk_plan`'s sort and counts for the grouped path, the counts
    by comparison (a scalar scatter-add of ``T * k`` ones costs a
    millisecond a walk on the chip, PERF.md section 6, PR 41)."""
    local = idx.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < E), local, E)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    counts = jnp.sum(key[None] == jnp.arange(E)[:, None], axis=1,
                     dtype=jnp.int32)
    order = jnp.concatenate([order, jnp.zeros((B,), jnp.int32)])
    return _GroupedPlan(order, counts, jnp.cumsum(counts) - counts)


class _Trip(NamedTuple):
    """One trip of the grouped path: ``B`` rows of the sorted order."""
    pairs: jax.Array       # [B] pair indices
    tok: jax.Array         # [B] their tokens
    add_pair: jax.Array    # [B] the same where a row is a held pair, past
    add_tok: jax.Array     # the last index where not: a dropped update
    visits: gm.Visits      # the experts' rows inside the trip, as every
    #                        grouped call of the trip reads them


def _trip(plan: _GroupedPlan, s, B: int, T: int, k: int,
          tile_rows=None) -> _Trip:
    lo = s * B
    pairs = lax.dynamic_slice_in_dim(plan.order, lo, B)
    live = lo + jnp.arange(B) < plan.held
    end = plan.row_start + plan.counts
    return _Trip(pairs, pairs // k, jnp.where(live, pairs, T * k),
                 jnp.where(live, pairs // k, T),
                 gm.group_visits(jnp.clip(plan.row_start - lo, 0, B),
                                 jnp.clip(end - lo, 0, B), B, tile_rows))


@functools.partial(jax.jit, static_argnames=("first", "B", "fused", "limit"))
def _grouped_forward(x, weights, idx, w_gate, w_up, w_down, layer, first,
                     B, fused=False, limit=None):
    """``fused``: a trip's experts in ONE call (``gmm_ffn``) where the rule
    says three; the row tile is then that call's (``gm.ffn_tiles``).
    ``limit``: the model's clamp inside the SwiGLU (:func:`_clamped`)."""
    (T, _), k, dt = x.shape, idx.shape[1], x.dtype
    plan = _grouped_plan(idx, first, w_gate.shape[-3], B)
    pair_w = weights.reshape(-1)
    (wg, wu, wd), layer = _layer_leaves((w_gate, w_up, w_down), layer, dt)
    tile_rows = gm.ffn_tiles(*wg.shape[-2:], dt.itemsize)[0] if fused \
        else None

    if B >= T * k:
        # one trip holds every pair: a whole expert a visit in one call, and
        # each token gathers its k rows back (a pair's row is where the
        # sort put it: the inverse permutation)
        t = _trip(plan, 0, B, T, k, tile_rows)
        y = gm.gmm_ffn(x[t.tok], wg, wu, wd, pair_w[t.pairs], t.visits,
                       layer=layer, limit=limit)
        at = jnp.argsort(plan.order[:T * k])
        y = jnp.where((at < plan.held)[:, None], y[at], 0.0)
        return (y.reshape(T, k, -1).sum(1), plan.counts), plan

    def body(s, out):
        t = _trip(plan, s, B, T, k, tile_rows)
        rows = x[t.tok]                                   # [B, H], once
        if fused:
            y = gm.gmm_ffn(rows, wg, wu, wd, pair_w[t.pairs], t.visits,
                           layer=layer, limit=limit)
        else:
            g = gm.gmm(rows, wg, t.visits, layer=layer, out_dtype=dt)
            u = gm.gmm(rows, wu, t.visits, layer=layer, out_dtype=dt)
            g, u = _clamped(g, u, limit)
            y = gm.gmm(jax.nn.silu(g) * u, wd, t.visits, layer=layer,
                       row_scale=pair_w[t.pairs])
        # rows past the held pairs were never written: dropped, not added
        return out.at[t.add_tok].add(y, mode="drop")

    out = lax.fori_loop(0, -(-plan.held // B), body,
                        jnp.zeros(x.shape, jnp.float32))
    return (out, plan.counts), plan


@functools.partial(jax.jit, static_argnames=("B",))
def _grouped_backward(x, weights, idx, w_gate, w_up, w_down, layer, plan,
                      d_out, B):
    """The trips again: a trip's gate and up are recomputed from its rows;
    an expert's ``dW`` is summed on chip over its row tiles and written once
    a trip, ``dx`` and the pair weights' gradient are made in sorted order
    and added once a trip."""
    (T, _), k, dt, f32 = x.shape, idx.shape[1], x.dtype, jnp.float32
    pair_w = weights.reshape(-1)
    (wg, wu, wd), at = _layer_leaves((w_gate, w_up, w_down), layer, dt)

    def body(s, carry):
        dx, dpw, dwg, dwu, dwd = carry
        t = _trip(plan, s, B, T, k)
        rows = x[t.tok]
        g = gm.gmm(rows, wg, t.visits, layer=at, out_dtype=dt)   # as forward
        u = gm.gmm(rows, wu, t.visits, layer=at, out_dtype=dt)
        a = jax.nn.silu(g) * u                            # [B, F]
        # y = a @ wd stays on chip: its product with dy is all that is read
        dpw_rows, dy, da = gm.gmm_down_back(
            a, wd, d_out[t.tok], pair_w[t.pairs], t.visits, layer=at)
        dpw = dpw.at[t.add_pair].add(dpw_rows, mode="drop")
        gf, uf = g.astype(f32), u.astype(f32)
        sig = jax.nn.sigmoid(gf)
        dg = (da * uf * sig * (1.0 + gf * (1.0 - sig))).astype(dt)
        du = (da * gf * sig).astype(dt)
        dwd = gm.tgmm(a, dy, t.visits, dwd)
        dwg = gm.tgmm(rows, dg, t.visits, dwg)
        dwu = gm.tgmm(rows, du, t.visits, dwu)
        drows = gm.gmm(dg, wg, t.visits, layer=at, transpose_rhs=True,
                       also=(du, wu))
        dx = dx.at[t.add_tok].add(drows, mode="drop")
        return dx, dpw, dwg, dwu, dwd

    def zeros(w):
        return jnp.zeros(w.shape[-3:], f32)

    dx, dpw, dwg, dwu, dwd = lax.fori_loop(
        0, -(-plan.held // B), body,
        (jnp.zeros(x.shape, f32), jnp.zeros(pair_w.shape, f32),
         zeros(w_gate), zeros(w_up), zeros(w_down)))
    return (dx.astype(x.dtype), dpw.reshape(weights.shape).astype(
        weights.dtype), None, _leaf_grad(w_gate, dwg, layer),
        _leaf_grad(w_up, dwu, layer), _leaf_grad(w_down, dwd, layer), None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _grouped(x, weights, idx, w_gate, w_up, w_down, layer, first, B,
             limit=None):
    return _grouped_forward(x, weights, idx, w_gate, w_up, w_down, layer,
                            first, B, False, limit)[0]


def _grouped_vjp_fwd(x, weights, idx, w_gate, w_up, w_down, layer, first, B,
                     limit):
    _served_alone(limit)
    out, plan = _grouped_forward(x, weights, idx, w_gate, w_up, w_down,
                                 layer, first, B)
    return out, (x, weights, idx, w_gate, w_up, w_down, layer, plan)


def _grouped_vjp_bwd(first, B, limit, res, g):
    return _grouped_backward(*res, g[0], B)


_grouped.defvjp(_grouped_vjp_fwd, _grouped_vjp_bwd)


def held_expert_ffn(x, weights, idx, w_gate, w_up, w_down, *, first: int,
                    block_rows: int = 128, layer=None, routed=None,
                    limit=None):
    """SwiGLU experts over the (token, choice) pairs that land on the
    experts held here; what absent experts would add is left out.

    x [T, H]; weights/idx [T, k] from the router over ALL experts (``routed``
    of them, where the caller says); w_gate/w_up [E, H, F], w_down [E, F, H]:
    the ``E`` held experts, global indices ``first .. first + E - 1``; with
    ``layer`` given they are stacked over layers, [L, E, ...], and this is
    layer ``layer`` (inside a scan over layers an expert's weight is then
    read straight from the stacked leaf; sliced a layer at a time first, the
    slice is copied every step).  Returns (out [T, H] float32, counts [E]
    int32 pairs per held expert).

    No capacity and no drops, on either path: the pairs are sorted by
    held expert once, every pair is computed at any imbalance, the work
    follows the rows routed here and not ``T * k``, and an expert nobody
    chose costs nothing: its weights are not read.

    **The grouped path** brings the rows into expert order once a trip
    (``x[order // k]``) and multiplies each expert's contiguous rows by that
    expert's weights in Pallas calls (``ops/pallas_kernels/grouped_matmul.py``,
    ``hetu.moe.gmm`` in a trace: an expert's weight read in place and kept
    whole in VMEM across its row tiles, fetched ONCE a call with the next
    expert's fetch behind the current one's matmul, a tile two experts share
    masked, not padded).  A trip holds :func:`grouped_row_budget`
    sorted rows, a static number: memory is bounded by that row budget,
    never by a capacity and never by ``T * k``; a load past it costs trips,
    never rows.  The forward has two forms, by that static budget:

    - *one trip holds every pair* (``budget >= T * k``: the chip holds every
      expert the router chooses from, as a served LFM2 does; any decode
      round, any prefill chunk): ONE call a walk (``gmm_ffn``: gate, up,
      SwiGLU, down and the pair weight a visit, the ``[rows, F]``
      intermediate never leaving VMEM), and each token gathers its ``k``
      result rows back by the inverse of the sort and sums them: no
      scatter-add (one of 8,192 rows costs 2.4 ms on a v5e, the gather 0.4);
    - *a share of the experts* (a training step's expert-parallel share):
      gate, up and down as three grouped matmuls a trip and ONE scatter-add
      a trip into ``out``.

    In reverse (either form: the backward reads only the sort) an expert's
    ``dW`` is summed in VMEM over its row tiles and written once, the down
    projection's result never leaves the chip (its product with the
    cotangent is all the pair weights' gradient needs), and ``dx`` is one
    more scatter-add.  The path's forward and backward are jitted functions,
    so that every walk of a program at one shape is the same traced function
    and each grouped kernel is lowered once a program, not once a layer: ten
    Mosaic modules in a step whose scan body holds four expert layers, not
    forty; one in an LFM2 decode or chunk program of twelve unrolled expert
    layers, not twelve (a program's build cost, about 0.13 s of tracing and
    0.03 s of lowering a ``pallas_call``, which a warm compile cache does not
    remove; nothing the device runs changes).  ``layer`` reaches them as an
    array index or None, never as a static value, which would make a
    function a layer.  Under per-layer ``jax.checkpoint`` the sharing holds
    where the layers share ONE policy object, as ``ops.remat`` hands out:
    JAX splits a jitted call into kept and recomputed parts once a (jaxpr,
    policy object) pair.

    **Experts too wide to keep whole** (``H * F > 4 Mi``: K-EXAONE's and
    LongCat's 6144 x 2048, three weights of 25 MB) take the grouped path's
    forward wherever the walk is EVALUATED (a decode round, a prefill chunk,
    a dense forward that nothing differentiates), in either of its two forms
    by the same static budget, with ONE call where the other experts have
    one or three: ``gmm_ffn`` cut along F.  A visit walks the expert's
    intermediate width in tiles of ``f_t`` columns on a second, innermost
    grid axis: a step holds ``w_gate[:, f]``, ``w_up[:, f]`` and
    ``w_down[f, :]``, computes ``silu(x w_gate_f) * (x w_up_f)`` whole (the
    contraction over H is whole inside a tile, rounded exactly as the
    whole-weight call rounds it) and adds its down product into a float32
    ``[rows, H]`` sum in VMEM that the last step scales by the pair weight
    and stores; neither the ``[rows, F]`` intermediate nor a partial sum
    reaches HBM, and the next tile's fetch runs behind the current one's
    matmuls.  ``f_t`` and the row tile are constants read from the shapes
    (``gm.ffn_tiles``: 1,024 columns and 128 rows at 6144 x 2048, with the
    chip's readings).  A served round of K-EXAONE (16 slots x 8 choices of
    128 experts, 16 held: ``budget`` 256 >= 128 pairs) is the one-trip form;
    its 512-token chunk (``budget`` 768 < 4,096) the trips form, the call
    standing where the three calls stand and the trip's combine ONE
    scatter-add (0.87 ms for 768 rows timed alone, 1.04 for the gather of
    4,096 that the one-trip form would need there: it stays).  Where the
    walk is DIFFERENTIATED the loop below runs, forward and backward: JAX
    runs a ``custom_vjp``'s rules only under autodiff, so static shapes and
    JAX's own split decide, no option and no model's name.  No cell of the
    benchmark trains such experts (``tests/test_moe.py`` does).

    **The loop path** walks the sorted pairs in blocks of ``block_rows``
    rows, ``ceil(count_e / block_rows)`` blocks for expert ``e``, the trip
    count read from the counts.  A block gathers its rows from ``x``,
    multiplies them by its expert (three XLA dots, each waiting for its own
    weight: a block costs 55-62 us on a v5e whatever it holds) and adds its
    result into ``out``; in reverse it also adds into its expert's whole
    float32 ``dW``.  Nothing is bounded by a buffer of rows: only the sorted
    pair indices are held.  It is reverse mode's where an expert's weight
    does not fit the grouped kernels' VMEM (``gmm(..., also=)``,
    ``gmm_down_back`` and ``tgmm`` hold two or three whole weights and are
    not cut), and evaluates too only where no whole-lane tile of F fits.
    ``block_rows`` is this path's alone.

    **The rule** (:func:`held_expert_path`; static shapes only, no option):
    grouped where ``H * F <= 4 Mi``, at ANY row count; past that the fused
    call cut along F evaluates and the loop differentiates.
    Set on the chip (v5e).  *Forward alone* at LFM2's 2048 x 1792, 32 of 32
    held, ``k`` = 4, twelve walks in a chain as its programs hold them
    (PERF.md section 6, PR 44, call 6; ms, loop at 128-row blocks | grouped
    at the kernels' one row tile of 256):

        T      1     2      4      8     16     32     64    128    256
        loop  4.14  5.58  11.82  16.03  19.37  22.12  22.32  22.41  22.50
        grpd  2.68  3.73   6.67   9.58  11.36  12.68  12.73  13.24  14.25

        T     512   1024   2048
        loop 23.00  33.46  58.53
        grpd 16.32  22.16  30.72

    (a decode round of 64 slots reads its 384 hit experts at 81% of the
    chip's bandwidth; a 2,048-token chunk's 256-row groups straddle two
    256-row tiles and the kernel multiplies about twice the live rows, at
    the MXU's rate: tiles of 64 and 128 read 30.0 and 30.2, of 512 46.1; a
    tile of 32 reads 2.20 at one token and 12.59 at 64, a fifth and a
    hundredth less, and was not kept: one tile for every shape).
    *Forward alone* at 6144 x 2048, 16 held, twelve walks in a chain over
    four stacked layers (PERF.md section 6, PR 55, call 1; ms, loop at
    128-row blocks | the fused call cut along F at 128 rows and ``f_t``
    1,024; K-EXAONE ``k`` = 8 of 128 experts, LongCat ``k`` = 12 of 768):

        T (K-EXAONE)   1      4      16     512     T (LongCat)  16     512
        loop          3.29   7.16  19.44  32.98                 7.89  33.04
        cut           3.02   5.88  14.61  28.59                 6.65  23.26

    (T = 1 and 4 from call 3.  A round of 16 slots reads its 129 hit
    experts' 75.5 MB each at 667 GB/s, 81% of the chip's 819, where the
    loop reads 501, 61%; a chunk of K-EXAONE's re-reads the expert whose
    rows cross one of its three 128-row tile boundaries, a cut visit
    keeping no slab across visits, and reads 507 GB/s of the hit experts'
    own bytes; LongCat's 128 held pairs a chunk are one tile, 623.  Row
    tiles of 64 | 128 | 256 at ``f_t`` 512 read 15.36 | 15.47 | 15.81 for
    that round and 34.94 | 30.17 | 28.42 for that chunk, 256 rows do not fit
    VMEM beside ``f_t`` 1,024, and 64 there read 14.48 and 33.07: 128 rows
    are kept, one tile for every shape; ``gm.ffn_tiles`` has the table.)
    *Forward and backward* at 2304 x 896, 16 held of 64, ``k`` = 8, one
    walk: 4.8 | 6.1 | 10.0 | 16.2 | 30.0 ms on the loop against 4.3 | 5.1 |
    6.6 | 10.0 | 16.4 grouped at ``T`` = 512 ... 8192 (PR 41), and under
    the edge PR 41 drew at 1,024 pairs an expert 5.94 | 5.80 | 5.90 | 5.74 |
    6.84 against 4.86 | 4.87 | 5.00 | 5.22 | 6.07 at ``T`` = 64 ... 1024 (8
    to 128 pairs an expert; PR 44): no row count measured has the loop
    ahead, so the rule has no lower edge.  At 6144 x 2048 the THREE-call
    form and the loop are within a tenth of each other up to ``T`` = 4096
    (PR 44), which is why the cure at that width is the one fused call.

    Reverse mode follows the path taken (a ``custom_vjp``: a loop whose trip
    count is read from data has no transpose of its own): the backward's
    cost follows the load as the forward's does, and no intermediate is kept
    between the two.  Gradients go to ``x``, to the pair ``weights`` (and
    through them to the router) and to the three weight leaves, accumulated
    in float32 and rounded once to the leaf's type; given ``layer``, a
    leaf's gradient is zero outside that layer.

    ``limit`` (None: none): the model's clamp inside the SwiGLU,
    ``silu(min(gate, limit)) * clip(up, -limit, limit)``, on whichever path
    the shapes take, the kernels' fused activation included; such a layer is
    evaluated only (its reverse mode raises)."""
    T, k = idx.shape
    limit = None if limit is None else float(limit)
    E, H, F = w_gate.shape[-3:]
    if layer is not None:               # an array: a static layer would
        layer = jnp.asarray(layer, jnp.int32)       # make a walk a layer
    path = held_expert_path(T, k, E, H, F, x.dtype.itemsize)
    B = grouped_row_budget(T, k, E, routed)
    # a model without a clamp calls the walks as it always did
    clamp = () if limit is None else (limit,)
    if path == "grouped":
        return _grouped(x, weights, idx, w_gate, w_up, w_down, layer,
                        int(first), B, *clamp)
    return _held(x, weights, idx, w_gate, w_up, w_down, layer, int(first),
                 int(block_rows), B if path == "cut" else None, *clamp)
