"""LFM2's expert model: gated short convolutions between grouped-query
attention layers, a leading dense layer before the expert layers, sigmoid
routing over many small experts.

Source of the shapes: ``huggingface.co/LiquidAI/LFM2-8B-A1B`` ``config.json``
(``model_type`` ``lfm2_moe``).  One layer, ``N`` RMSNorm (each its own
weight), no projection has a bias::

    h = h + Op(N(h));   h = h + FF(N(h))
    conv layer:  [B | C | x] = W_in a;  u = B * x
                 c_t = w_0 u_(t-2) + w_1 u_(t-1) + w_2 u_t   # a channel its
                 Op = W_out (C * c)                          # own three taps
    full layer:  q = N_D(W_q a), k = N_D(W_k a) per head, v = W_v a; q, k
                 rotated (half-rotation layout, all of the head); causal
                 softmax(q k^T / sqrt(D)) v, head h reads KV head
                 h // (heads / kv_heads); W_o
    dense layer (the first ``first_dense``):  FF = W_d(silu(W_g u) * W_u u)
    expert layer: s = sigmoid(float32(u) W_r); chosen = top-k of s + bias;
                 w_i = s_i / (sum of the chosen s_j + 1e-6);
                 FF = sum over the chosen w_i W_d_i(silu(W_g_i u) * W_u_i u)
    logits = N(h) E^T                                   # the head is tied

**What a conv layer remembers** of a sequence is ``(u_(t-1), u_t)``, the last
``conv_L_cache - 1`` rows of the gated product, whatever the sequence's
length: a STATE LAYER of the serving cache
(``serve.kv_cache.KVCacheSpec.state_layers``, ``SlotStates``), not rows a
token.  The three calls are one sum over ``[state | new rows]``
(:meth:`Lfm2MoeModel._short_conv`): the dense forward from zeros, a chunk
from its slot's state, a token from its slot's state; a chunk padded to its
bucket leaves the state after its last REAL token.

**Shared**: the layer, the three calls, both cache entry points, the tied
head and the loss are ``models/block.py``'s (``BlockDecoder``, ``LayerCall``,
``GroupedHeads``); the router is ``ops.route_biased_top_k`` and the experts
``ops.moe_ops.held_expert_ffn`` through ``layers/moe.py``'s
``HeldExpertLayer``, told which experts it holds as everywhere (``held``;
None: all of them), which keeps the renormalising sum from zero by 1e-20
where the family's implementation has 1e-6 (the chosen sigmoid scores sum
to the order of one, so the two differ by under 1e-6 of a weight; the
reference has 1e-6, and no tolerance here can tell them apart).  Here: the configuration, the weights, the tables (attention leaves
and one page group for the attention layers alone, rotation on them, state
layers for the rest) and the convolution.

``jax.named_scope``s: ``hetu.conv.short``, ``hetu.attn.full``,
``hetu.ffn.dense``, ``hetu.moe.route``, ``hetu.moe.experts``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from hetu_tpu import ops
from hetu_tpu.layers.moe import MOE_STATS, HeldExpertLayer
from hetu_tpu.models.block import (
    FULL, BlockDecoder, LayerCall, counts_with_grouped, draw_leaf,
)

CONV = "conv"


@dataclass
class Lfm2MoeConfig:
    vocab_size: int = 65536
    hidden_size: int = 2048
    num_layers: int = 24
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64
    ffn_size: int = 7168                 # the dense layers' SwiGLU
    expert_ffn_size: int = 1792
    first_dense: int = 2                 # leading layers with a dense FFN
    n_routed_experts: int = 32           # as published: the router's width
    moe_topk: int = 4
    routed_scaling_factor: float = 1.0
    held: Optional[tuple] = None         # (first, count); None: all of them
    conv_taps: int = 3                   # conv_L_cache
    layer_types: Optional[tuple] = None  # None: conv but for every fourth
    rope_theta: float = 1e6
    rms_eps: float = 1e-5
    max_position: int = 128000
    dtype: object = jnp.bfloat16         # compute
    param_dtype: object = jnp.bfloat16
    init_std: float = 0.02
    router_init_std: float = 0.02
    router_bias_std: float = 0.01
    expert_block_rows: int = 128

    def __post_init__(self):
        if self.held is None:
            self.held = (0, self.n_routed_experts)
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.n_routed_experts:
            raise ValueError(f"held {self.held} is no share of "
                             f"{self.n_routed_experts} experts")
        self.held = (int(first), int(count))
        if self.layer_types is None:
            self.layer_types = tuple(FULL if l % 4 == 2 else CONV
                                     for l in range(self.num_layers))
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != self.num_layers or \
                set(self.layer_types) - {CONV, FULL} or \
                FULL not in self.layer_types:
            raise ValueError(f"layer_types {self.layer_types} do not name "
                             f"{self.num_layers} conv or full layers, one "
                             f"of them full")
        if self.head_dim % 2 or self.num_heads % self.num_kv_heads:
            raise ValueError("head_dim must be even and kv heads divide "
                             "the query heads")
        if self.conv_taps < 2:
            raise ValueError("a short convolution has two taps or more")


class Lfm2MoeModel(BlockDecoder):
    """``params``: ``tok_emb`` [V, H] (the head too), ``norm_f``,
    ``layers``: ``attn_norm``/``ffn_norm`` [L, H] (the operator's norm under
    the name the block reads it by), ``attn`` (``GroupedHeads``' leaves)
    stacked over the FULL layers, ``conv`` {in [H, 3H], taps [taps, H], out
    [H, H]} over the conv layers, ``ffn`` over the ``first_dense`` leading
    layers, ``moe`` (router, router_bias, gate, up, down) over the rest."""

    # the expert layers' counts; the held experts a call could hit at most
    # (held x expert layers): a constant, for the share that were hit; and
    # the held pairs that the walk's grouped path computed: all of them or
    # none, by ops.moe_ops.held_expert_path's static rule
    step_stats = MOE_STATS + ("moe_experts", "moe_grouped")

    def __init__(self, config: Lfm2MoeConfig):
        c = config
        # layer -> its index among the layers of its own kind: a full
        # layer's attention leaves and cache layer (of the one page group),
        # a conv layer's leaves and state layer
        full = [l for l, kind in enumerate(c.layer_types) if kind == FULL]
        conv = [l for l, kind in enumerate(c.layer_types) if kind == CONV]
        self.conv_leaf = {l: i for i, l in enumerate(conv)}
        super().__init__(
            c, HeldExpertLayer(
                n_routed=c.n_routed_experts, n_zero=0, k=c.moe_topk,
                scaling=c.routed_scaling_factor, held=c.held,
                block_rows=c.expert_block_rows, dtype=c.dtype,
                scoring="sigmoid", renormalise=True),
            attn_leaf={l: i for i, l in enumerate(full)},
            cache_layer={l: (0, i) for i, l in enumerate(full)},
            rotated=full)

    # ---- the cache this model asks of the serving engine ----
    def kv_cache_spec(self):
        from hetu_tpu.serve.kv_cache import KVCacheSpec

        c = self.c
        return KVCacheSpec(
            num_layers=len(self.attn_leaf), num_kv_heads=c.num_kv_heads,
            head_dim=c.head_dim, dtype=c.dtype,
            state_layers=len(self.conv_leaf),
            state_shape=(c.conv_taps - 1, c.hidden_size))

    # ---- weights ----
    def init(self, key):
        """Every leaf in ``param_dtype`` (the router and its correction bias
        in float32), a large leaf drawn a piece at a time."""
        c = self.c
        pd = c.param_dtype
        H, E, F = c.hidden_size, c.held[1], c.expert_ffn_size
        qw, kvw = c.num_heads * c.head_dim, c.num_kv_heads * c.head_dim
        L, D = c.num_layers, c.first_dense
        A, C, S = len(self.attn_leaf), len(self.conv_leaf), L - D
        std = c.init_std
        ks = iter(jax.random.split(key, 16))

        def draw(lead, shape, std=std, dtype=pd):
            return draw_leaf(next(ks), lead, shape, std, dtype)

        def ones(*shape):
            return jnp.ones(shape, pd)

        layers = {
            "attn_norm": ones(L, H), "ffn_norm": ones(L, H),
            # q and k [out, in], as the block's GroupedHeads reads them
            "attn": {"q": draw((A,), (qw, H)), "k": draw((A,), (kvw, H)),
                     "v": draw((A,), (H, kvw)), "o": draw((A,), (qw, H)),
                     "q_norm": ones(A, c.head_dim),
                     "k_norm": ones(A, c.head_dim)},
            # taps of the order of one: three of them sum a channel
            "conv": {"in": draw((C,), (H, 3 * H)),
                     "taps": draw((C,), (c.conv_taps, H),
                                  c.conv_taps ** -0.5),
                     "out": draw((C,), (H, H))},
            "ffn": {"gate": draw((D,), (H, c.ffn_size)),
                    "up": draw((D,), (H, c.ffn_size)),
                    "down": draw((D,), (c.ffn_size, H))},
            "moe": {
                "router": draw((S,), (H, c.n_routed_experts),
                               c.router_init_std, jnp.float32),
                "router_bias": draw((S,), (c.n_routed_experts,),
                                    c.router_bias_std, jnp.float32),
                "gate": draw((S, E), (H, F)), "up": draw((S, E), (H, F)),
                "down": draw((S, E), (F, H))},
        }
        return {"params": {"tok_emb": draw((), (c.vocab_size, H)),
                           "norm_f": ones(H), "layers": layers},
                "state": {}}

    # ---- pieces of a layer ----
    def _operator(self, p, l: int, a, call: LayerCall):
        if self.c.layer_types[l] == CONV:
            return self._short_conv(p["conv"], self.conv_leaf[l], a, call)
        return self._attention(p["attn"], l, a, call)

    def _short_conv(self, p, cl: int, a, call: LayerCall):
        """The gated short convolution of conv layer ``cl`` on ``a`` [B, S,
        H], ``p`` the stacked conv leaves: one sum of ``taps`` shifted
        products over ``[state | the call's rows]`` of the gated input
        ``u``, the state ``taps - 1`` rows: zeros in the dense forward, the
        slot's in a cached call, which then keeps the rows ending at
        ``call.last`` (the last real token of a padded chunk)."""
        dt = self.c.dtype
        taps = self.c.conv_taps
        with jax.named_scope("hetu.conv.short"):
            gate_in, gate_out, x = jnp.split(
                ops.linear(a, p["in"][cl].astype(dt)), 3, axis=-1)
            u = gate_in * x
            b, s, _ = u.shape
            before = jnp.zeros((b, taps - 1, u.shape[-1]), u.dtype) \
                if call.state is None else call.state.read(cl).astype(u.dtype)
            rows = jnp.concatenate([before, u], axis=1)    # [B, taps-1+S, H]
            if call.state is not None:
                last = s - 1 if call.last is None else call.last
                call.state = call.state.write(
                    cl, jax.lax.dynamic_slice_in_dim(rows, last + 1,
                                                     taps - 1, axis=1))
            w = p["taps"][cl].astype(jnp.float32)
            c = sum(w[j] * rows[:, j:j + s].astype(jnp.float32)
                    for j in range(taps)).astype(dt)
            return ops.linear(gate_out * c, p["out"][cl].astype(dt))

    def _counts(self, stats):
        return counts_with_grouped(self.c, stats)
