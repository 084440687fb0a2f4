"""Mixture-of-Experts layer and gates.

Reference: python/hetu/layers/moe_layer.py (`Expert` :6, `MoELayer` :45 —
gate → layout_transform → AllToAll → local experts → reverse AllToAll →
reverse layout) and the gate zoo: `TopKGate` (TopGate.py), `HashGate`,
`KTop1Gate` (ktop1_layer.py), `BalanceAssignmentGate` (BASE layer, auction),
`SAMGate` (sam_layer.py).

TPU design: index-based gather dispatch/combine by default (Pallas
routed_gather on TPU — O(T·k·D), the LayoutTransform.cu analog), with the
GShard-style dense dispatch/combine einsums kept as `dispatch_impl=
'einsum'` (simple, but O(T²·D) — only for small T / cross-checking);
expert weights are stacked [E, ...] and sharded over the 'ep' mesh axis,
dispatched tokens constrained to P('ep', ...), and XLA's SPMD partitioner
materializes the all_to_all exactly where the reference called alltoall_op
(gpu_ops/AllToAll.py).  Gates produce (combine_weights [T,k],
expert_idx [T,k], aux_loss).

Which routing is which.  :class:`MoELayer` and its gates are the CAPACITY
routing: every expert has a static number of slots
(``capacity_factor``), is padded to it, and tokens past it are DROPPED; the
gate routes over exactly the experts the layer holds.  It is what
``models/moe_transformer.py`` trains with, and it is not served.
:class:`HeldExpertLayer` at the end of this file is the HELD-EXPERT routing
of the served model (``models/longcat_flash.py``): a router as wide as
published over experts of which this chip holds a share, identity experts
behind them, no capacity, no drops.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from hetu_tpu import init as initializers
from hetu_tpu import ops
from hetu_tpu.layers.base import Module
from hetu_tpu.ops.moe_ops import (
    balance_assignment, gather_combine, gather_dispatch, held_expert_ffn,
    layout_transform, make_dispatch_combine, make_slot_routing,
    reverse_layout_transform, route_biased_top_k, top_k_idx_gate,
)


class TopKGate(Module):
    """Top-k softmax gate with GShard load-balancing aux loss
    (reference layers/TopGate.py)."""

    def __init__(self, hidden_size: int, num_experts: int, k: int = 2,
                 aux_weight: float = 1e-2, impl: str = "auto"):
        if impl not in ("auto", "xla", "pallas"):
            raise ValueError(f"impl {impl!r}: 'auto', 'xla' or 'pallas'")
        self.hidden_size, self.num_experts, self.k = hidden_size, num_experts, k
        self.aux_weight = aux_weight
        self.impl = impl  # 'auto': fused Pallas top-k+softmax on TPU when
        # the token count tiles (single-device hot path); 'xla' is required
        # under SPMD sharding (the partitioner can't split a pallas_call)
        self.w_init = initializers.xavier_uniform()

    def init(self, key):
        return {"params": {"gate_w": self.w_init(
            key, (self.hidden_size, self.num_experts), jnp.float32)},
            "state": {}}

    def apply(self, variables, tokens, *, train: bool = False, rng=None,
              force_xla: bool = False):
        logits = ops.linear(tokens.astype(jnp.float32),
                            variables["params"]["gate_w"])
        probs = jax.nn.softmax(logits, axis=-1)
        T = logits.shape[0]
        bt = next((b for b in (256, 128, 64, 32, 16, 8) if T % b == 0),
                  None)
        use_pallas = not force_xla and bt is not None and self.impl != "xla"
        if self.impl == "pallas" and bt is None and not force_xla:
            # under force_xla the kernel was never going to run, so the
            # divisibility contract doesn't apply — the warning below covers
            raise ValueError(
                f"impl='pallas' needs a token count divisible by a "
                f"power-of-two block >= 8; got T={T}")
        if self.impl == "pallas" and force_xla:
            # SPMD (meshed MoELayer) forces XLA because the partitioner
            # cannot split a pallas_call — an explicit 'pallas' request
            # cannot be honored there, and silence would contradict the
            # shape error above.  Warn rather than raise: the XLA path is
            # numerically identical (same vjp), only the fusion differs.
            import warnings
            warnings.warn(
                "TopKGate(impl='pallas') runs the XLA gate under SPMD "
                "sharding (pallas_call is not partitionable); use "
                "impl='auto' to silence this", stacklevel=2)
        if use_pallas:
            from hetu_tpu.ops.pallas_kernels import topk_gating
            gates, idx = topk_gating(logits, self.k, block_tokens=bt)
        else:
            gates, idx = top_k_idx_gate(logits, self.k)
        # GShard aux: E * sum_e (mean gate prob_e * mean dispatch frac_e)
        me = jnp.mean(probs, axis=0)
        oh = jax.nn.one_hot(idx[:, 0], self.num_experts)
        ce = jnp.mean(oh, axis=0)
        aux = self.aux_weight * self.num_experts * jnp.sum(me * ce)
        return (gates, idx, aux), {}


class HashGate(Module):
    """Deterministic hash routing (reference layers/hash_layer.py): expert =
    token_id %% num_experts; requires integer ids alongside embeddings."""

    def __init__(self, num_experts: int):
        self.num_experts = num_experts

    def apply(self, variables, token_ids, *, train: bool = False, rng=None):
        idx = (token_ids.reshape(-1) % self.num_experts).astype(jnp.int32)
        gates = jnp.ones((idx.shape[0], 1), jnp.float32)
        return (gates, idx[:, None], jnp.asarray(0.0)), {}


class KTop1Gate(Module):
    """k independent groups, each top-1 (reference layers/ktop1_layer.py):
    experts are partitioned into k groups; a token picks its best expert in
    every group, gates softmaxed over the k winners."""

    def __init__(self, hidden_size: int, num_experts: int, k: int = 2):
        assert num_experts % k == 0
        self.hidden_size, self.num_experts, self.k = hidden_size, num_experts, k
        self.w_init = initializers.xavier_uniform()

    def init(self, key):
        return {"params": {"gate_w": self.w_init(
            key, (self.hidden_size, self.num_experts), jnp.float32)},
            "state": {}}

    def apply(self, variables, tokens, *, train: bool = False, rng=None):
        logits = ops.linear(tokens.astype(jnp.float32),
                            variables["params"]["gate_w"])
        T = logits.shape[0]
        per = self.num_experts // self.k
        grouped = logits.reshape(T, self.k, per)
        best = jnp.argmax(grouped, axis=-1)                      # [T,k]
        offset = jnp.arange(self.k, dtype=jnp.int32) * per
        idx = best.astype(jnp.int32) + offset[None, :]
        best_val = jnp.max(grouped, axis=-1)
        gates = jax.nn.softmax(best_val, axis=-1)
        return (gates, idx, jnp.asarray(0.0)), {}


class BalanceAssignmentGate(Module):
    """BASE-layer balanced assignment (reference layers/base via
    gpu_ops/BalanceAssignment.py auction; Sinkhorn reformulation on TPU —
    see ops.balance_assignment)."""

    def __init__(self, hidden_size: int, num_experts: int, iters: int = 20):
        self.hidden_size, self.num_experts, self.iters = (
            hidden_size, num_experts, iters)
        self.w_init = initializers.xavier_uniform()

    def init(self, key):
        return {"params": {"gate_w": self.w_init(
            key, (self.hidden_size, self.num_experts), jnp.float32)},
            "state": {}}

    def apply(self, variables, tokens, *, train: bool = False, rng=None):
        scores = ops.linear(tokens.astype(jnp.float32),
                            variables["params"]["gate_w"])
        idx = balance_assignment(scores, iters=self.iters)
        gates = jnp.take_along_axis(
            jax.nn.sigmoid(scores), idx[:, None], axis=-1)
        return (gates, idx[:, None].astype(jnp.int32), jnp.asarray(0.0)), {}


class SAMGate(Module):
    """Switch-and-mix style grouped gate (reference layers/sam_layer.py using
    SamGroupSum/SamMax kernels): tokens are bucketed by nearest centroid,
    buckets summarized by group-sum, each group routed top-1."""

    def __init__(self, hidden_size: int, num_experts: int):
        self.hidden_size, self.num_experts = hidden_size, num_experts
        self.w_init = initializers.xavier_uniform()

    def init(self, key):
        return {"params": {
            "centroids": self.w_init(key, (self.num_experts,
                                           self.hidden_size), jnp.float32)},
            "state": {}}

    def apply(self, variables, tokens, *, train: bool = False, rng=None):
        c = variables["params"]["centroids"]
        t = tokens.astype(jnp.float32)
        # nearest centroid by dot-product affinity
        aff = t @ c.T                                            # [T,E]
        idx = jnp.argmax(aff, axis=-1).astype(jnp.int32)
        # group-sum summarization (ops.sam_group_sum) re-scores the groups
        gsum = ops.sam_group_sum(t, idx, self.num_experts)       # [E,D]
        gscore = jnp.sum(gsum * c, axis=-1)                      # [E]
        gates = jax.nn.sigmoid(jnp.take(gscore, idx))[:, None]
        return (gates, idx[:, None], jnp.asarray(0.0)), {}


class Expert(Module):
    """Stacked FFN experts: w1 [E,D,F], w2 [E,F,D] (reference layers/
    moe_layer.py:6 Expert as per-device FFN; stacked here for SPMD)."""

    def __init__(self, num_experts: int, hidden_size: int, ffn_size: int,
                 activation=ops.gelu, dtype=jnp.float32):
        self.num_experts, self.hidden_size, self.ffn_size = (
            num_experts, hidden_size, ffn_size)
        self.activation = activation
        self.dtype = dtype
        self.w_init = initializers.he_normal()

    def init(self, key):
        k1, k2 = jax.random.split(key)
        E, D, F = self.num_experts, self.hidden_size, self.ffn_size
        return {"params": {
            "w1": self.w_init(k1, (E, D, F), jnp.float32),
            "b1": jnp.zeros((E, F), jnp.float32),
            "w2": self.w_init(k2, (E, F, D), jnp.float32),
            "b2": jnp.zeros((E, D), jnp.float32)}, "state": {}}

    def apply(self, variables, xe, *, train: bool = False, rng=None):
        """xe: [E, C, D] → [E, C, D]."""
        p = variables["params"]
        dt = self.dtype
        h = jnp.einsum("ecd,edf->ecf", xe.astype(dt), p["w1"].astype(dt),
                       preferred_element_type=jnp.float32) + p["b1"][:, None]
        h = self.activation(h)
        y = jnp.einsum("ecf,efd->ecd", h.astype(dt), p["w2"].astype(dt),
                       preferred_element_type=jnp.float32) + p["b2"][:, None]
        return y, {}


class MoELayer(Module):
    """gate → dispatch → (A2A) → experts → (reverse A2A) → combine.

    capacity_factor bounds tokens per expert: C = cf * T * k / E (static for
    XLA; overflow dropped like the reference's capacity path).  With `mesh`
    given, expert-major tensors are sharding-constrained to the 'ep' axis so
    XLA inserts the all_to_all pair.
    """

    def __init__(self, gate: Module, experts: Expert, *,
                 capacity_factor: float = 1.25, mesh=None, ep_axis: str = "ep",
                 dispatch_impl: str = "gather"):
        if dispatch_impl not in ("gather", "einsum"):
            raise ValueError(f"dispatch_impl {dispatch_impl!r}: "
                             "'gather' or 'einsum'")
        self.gate = gate
        self.experts = experts
        self.capacity_factor = capacity_factor
        self.mesh = mesh
        self.ep_axis = ep_axis
        self.dispatch_impl = dispatch_impl

    def init(self, key):
        kg, ke = jax.random.split(key)
        g = self.gate.init(kg)
        e = self.experts.init(ke)
        return {"params": {"gate": g["params"], "experts": e["params"]},
                "state": {}}

    def _constrain(self, x, *spec):
        if self.mesh is None:
            return x
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(*spec)))

    def apply(self, variables, x, *, gate_input=None, train: bool = False,
              rng=None, return_metrics: bool = False):
        """x: [B, S, D] or [T, D]. gate_input: alternative gate features
        (e.g. token ids for HashGate).

        With ``return_metrics`` the first element becomes
        ``(out, aux, metrics)`` where metrics carries the capacity-overflow
        counter (``dropped_frac``: fraction of (token, choice) routes
        silently dropped — the reference drops them silently too, but on
        TPU the capacity is static so surfacing it is the only way to see
        an undersized capacity_factor).
        """
        p = variables["params"]
        orig_shape = x.shape
        D = x.shape[-1]
        tokens = x.reshape(-1, D)
        T = tokens.shape[0]
        E = self.experts.num_experts
        k_choices = getattr(self.gate, "k", 1)
        capacity = max(1, int(self.capacity_factor * T * k_choices / E))

        gi = gate_input.reshape(-1) if gate_input is not None else tokens
        gate_kw = {}
        if self.mesh is not None and hasattr(self.gate, "impl"):
            gate_kw["force_xla"] = True  # SPMD can't split a pallas_call
        (gates, idx, aux), _ = self.gate.apply(
            {"params": p["gate"], "state": {}}, gi, train=train, rng=rng,
            **gate_kw)

        # with a mesh, the XLA gather IS the sharded path: GSPMD splits a
        # gather over 'ep' and refuses to partition a pallas_call
        kern = {"kernel": False} if self.mesh is not None else {}
        if self.dispatch_impl == "gather":
            slot_token, token_slot, n_dropped = make_slot_routing(
                gates, idx, E, capacity)
            xe = gather_dispatch(tokens, slot_token, E, capacity,
                                 **kern)             # [E, C, D]
        else:
            disp, comb = make_dispatch_combine(gates, idx, E, capacity)
            n_dropped = (jnp.asarray(T * k_choices, jnp.int32)
                         - jnp.sum(disp).astype(jnp.int32))
            xe = layout_transform(tokens, disp)      # [E, C, D]
        xe = self._constrain(xe, self.ep_axis)       # A2A insertion point
        ye, _ = self.experts.apply({"params": p["experts"], "state": {}}, xe,
                                   train=train)
        ye = self._constrain(ye, self.ep_axis)       # reverse A2A
        if self.dispatch_impl == "gather":
            out = gather_combine(ye, token_slot, gates, **kern)
        else:
            out = reverse_layout_transform(ye, comb)  # [T, D]
        out = out.reshape(orig_shape)
        if return_metrics:
            metrics = {"dropped_frac":
                       n_dropped.astype(jnp.float32) / (T * k_choices)}
            return (out, aux, metrics), {}
        return (out, aux), {}


# what HeldExpertLayer counts, in the order of its counts vector: (token,
# choice) pairs routed to held experts, to identity experts, to experts
# other chips hold; held experts chosen at least once
MOE_STATS = ("moe_held", "moe_zero", "moe_absent", "moe_hit")


class HeldExpertLayer:
    """One chip's share of an expert layer, without capacity and without
    drops.  The router is as wide as published, ``n_routed + n_zero``:
    indices below ``n_routed`` are SwiGLU experts, of which this chip holds
    ``held = (first, count)``; the ``n_zero`` behind them are identity
    experts, which every chip computes for its own tokens.  For tokens ``u``::

        s = score(float32(u) @ float32(router))            # all experts
        chosen: the k largest of s + router_bias            # bias: choice only
        w_i = scaling * s_i            (or, renormalised, over the chosen k:
              scaling * s_i / (sum of the chosen s_j + 1e-20))
        i held:      + w_i * W_down_i(silu(W_gate_i u) * W_up_i u)
        i identity:  + w_i * u
        i absent:    nothing (that chip adds it; nothing stands in for it)
        shared:      + W_down(silu(W_gate u) * W_up u)      # every token
                     (times sigmoid(w_sg . u) where the leaves hold w_sg)

    ``scoring`` is the published rule, ``"softmax"`` over all experts or
    ``"sigmoid"`` of each; ``renormalise`` whether the chosen weights are
    divided by their sum (the sum keeps ALL ``k`` chosen scores, the absent
    experts' too: the router is whole on every chip); ``shared`` whether the
    layer has a shared expert, one more dense SwiGLU that every chip
    computes for its own tokens and that is no part of the routing;
    ``swiglu_limit`` a clamp inside every SwiGLU of the layer, the shared
    expert's too (``silu(min(gate, x)) * clip(up, -x, x)``; None: none);
    where the leaves hold ``shared_gate_w`` [H] its result is scaled a token by
    ``sigmoid(w_sg . u)`` (absent: as it is), and where they hold no
    ``router_bias`` the choice is by the scores alone.  All
    three are the model's, set from its configuration, not options of a
    deployment.  The identity experts are one weighted sum of ``u``, never a
    matmul; the held experts' work follows the pairs routed to them
    (``ops.moe_ops.held_expert_ffn``, which takes one of two paths by the
    static shapes it is handed, ``ops.moe_ops.held_expert_path``: sorted rows
    through grouped Pallas matmuls wherever an expert's ``[H, F]`` weight
    fits the kernels' VMEM, at any row count (a training step's walk, three
    grouped calls and one scatter-add a trip; a served model that holds
    every expert, as LFM2's decode rounds and prefill chunks, ONE call a
    walk that reads each hit expert's weights once and a gather back); where
    it does not, as K-EXAONE's and LongCat's 6144 x 2048 experts, a served
    round or chunk still takes the grouped path's forward, an expert ONE
    fused call cut along its intermediate width, and only reverse mode walks
    a loop over blocks of ``block_rows`` sorted rows; measured on a v5e at
    2048 x 1792, 32 of 32 held, twelve walks: a round of 64 slots 22.3 ms on
    the loop and 12.7 grouped, a 2,048-token chunk 58.5 and 30.7, one token
    4.1 and 2.7: the table is in ``held_expert_ffn``.  ``block_rows``
    governs the loop path alone; the grouped path's row tile is its
    kernels' constant and its memory is bounded by a static row budget
    made from ``T``, ``k``, the held count and the router's width, never by
    a capacity; it is one jitted function for every layer of a program that
    calls it at one shape, so its kernels are lowered
    once a program).  Parameters (one layer's): ``router``
    [H, n_routed + n_zero] float32, ``router_bias`` [n_routed + n_zero]
    float32, ``gate``/``up`` [count, H, F], ``down`` [count, F, H]; with a
    shared expert ``shared_gate``/``shared_up`` [H, F_s], ``shared_down``
    [F_s, H] and, where it is gated a token, ``shared_gate_w`` [H].  ``router_bias`` is whatever the model hands over under that
    name: a parameter leaf where the model serves published weights, the
    model's STATE where it trains without an auxiliary loss and moves the
    bias itself (``models/deepseek_v3.py``); it steers the choice only, so
    no gradient reaches it."""

    def __init__(self, *, n_routed: int, n_zero: int, k: int, scaling: float,
                 held: tuple, block_rows: int = 128, dtype=jnp.bfloat16,
                 scoring: str = "softmax", renormalise: bool = False,
                 shared: bool = False, swiglu_limit=None):
        if scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring {scoring!r}: 'softmax' or 'sigmoid'")
        self.n_routed, self.n_zero, self.k = n_routed, n_zero, k
        self.scaling = float(scaling)
        self.first, self.count = held
        self.block_rows = block_rows
        self.dtype = dtype
        self.scoring, self.renormalise, self.shared = \
            scoring, bool(renormalise), bool(shared)
        # the model's clamp inside every SwiGLU, the shared expert's too:
        # silu(min(gate, x)) * clip(up, -x, x); None: none
        self.swiglu_limit = None if swiglu_limit is None \
            else float(swiglu_limit)

    def route(self, p, tokens):
        """tokens [T, H] -> (weights [T, k] float32, scaling included,
        idx [T, k])."""
        with jax.named_scope("hetu.moe.route"):
            logits = jnp.dot(tokens.astype(jnp.float32), p["router"],
                             precision=jax.lax.Precision.HIGHEST)
            scores = jax.nn.softmax(logits, axis=-1) \
                if self.scoring == "softmax" else jax.nn.sigmoid(logits)
            if "router_bias" in p:
                w, idx = route_biased_top_k(scores, p["router_bias"], self.k)
            else:
                w, idx = jax.lax.top_k(scores, self.k)
            if self.renormalise:
                w = w / (w.sum(-1, keepdims=True) + 1e-20)
            return w * self.scaling, idx

    def shared_expert(self, p, tokens, layer=None):
        """The shared expert's part for tokens [T, H], float32 [T, H]."""
        dt = self.dtype

        def of(name):
            w = p[name] if layer is None else p[name][layer]
            return w.astype(dt)

        with jax.named_scope("hetu.moe.shared"):
            x = tokens.astype(dt)
            # gate, its activation, then up: the order the programs of the
            # models without a clamp were lowered in
            limit = self.swiglu_limit
            g = jnp.dot(x, of("shared_gate"))
            a = jax.nn.silu(g if limit is None else jnp.minimum(g, limit))
            u = jnp.dot(x, of("shared_up"))
            h = a * (u if limit is None else jnp.clip(u, -limit, limit))
            out = jnp.dot(h, of("shared_down"),
                          preferred_element_type=jnp.float32)
            if "shared_gate_w" in p:
                out = out * jax.nn.sigmoid(jnp.dot(
                    x, of("shared_gate_w"),
                    preferred_element_type=jnp.float32))[:, None]
            return out

    def combine(self, p, tokens, w, idx, layer=None):
        """What the chosen experts add for tokens [T, H] under the router's
        ``(w, idx)``: (float32 [T, H], counts [4] int32 in ``MOE_STATS``
        order)."""
        zero = idx >= self.n_routed
        if self.n_zero:
            with jax.named_scope("hetu.moe.zero"):
                out = jnp.sum(jnp.where(zero, w, 0.0), -1, keepdims=True) \
                    * tokens.astype(jnp.float32)
        with jax.named_scope("hetu.moe.experts"):
            routed, per_expert = held_expert_ffn(
                tokens.astype(self.dtype), w, idx, p["gate"], p["up"],
                p["down"], first=self.first, block_rows=self.block_rows,
                layer=layer, routed=self.n_routed + self.n_zero,
                limit=self.swiglu_limit)
        n_held = per_expert.sum()
        n_zero = zero.sum().astype(jnp.int32)
        stats = jnp.stack([n_held, n_zero, idx.size - n_held - n_zero,
                           (per_expert > 0).sum().astype(jnp.int32)])
        out = out + routed if self.n_zero else routed
        if self.shared:
            out = out + self.shared_expert(p, tokens, layer)
        return out, stats

    def apply(self, p, u, *, layer=None):
        """u [..., H] -> (m [..., H] in u's dtype, counts [4] int32 in
        ``MOE_STATS`` order).  With ``layer`` given, ``gate``/``up``/``down``
        (and the shared expert's three) are stacked over layers and this is
        layer ``layer`` of them.  :meth:`route` then :meth:`combine`; a
        model that needs the router's choices between the two (to count
        them for the correction bias) calls the pair itself."""
        tokens = u.reshape(-1, u.shape[-1])
        w, idx = self.route(p, tokens)
        out, stats = self.combine(p, tokens, w, idx, layer)
        return out.astype(u.dtype).reshape(u.shape), stats
