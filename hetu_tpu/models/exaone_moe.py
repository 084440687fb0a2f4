"""K-EXAONE's language model: window and full attention layers mixed, a
leading dense layer before the expert layers, sigmoid routing with a shared
expert.

Source of the shapes: ``huggingface.co/LGAI-EXAONE/K-EXAONE-236B-A23B``
``config.json`` (``model_type`` ``exaone_moe``; the next-token-prediction
module of ``num_nextn_predict_layers`` is not here: it is no part of the
distribution the model serves).  One layer, ``N`` RMSNorm (each its own
weight), all projections without bias::

    a = N(x);  q = N_128(W_q a) per head;  k = N_128(W_k a) per head;  v = W_v a
    window layer: q, k rotated (half-rotation layout, all of the head);
                  query i sees keys j with 0 <= i - j < window
    full layer:   no rotation; query i sees keys j <= i
    x = x + W_o softmax(q k^T / sqrt(head_dim)) v      # head h reads KV head h // (heads / kv_heads)
    u = N(x)
    dense layer (the first ``first_dense``):  x = x + W_d(silu(W_g u) * W_u u)
    expert layer:  x = x + shared(u) + sum over the chosen held experts

``head_dim`` is its own number (128), not ``hidden / heads`` (96).  The
normalisation comes BEFORE each sub-layer and rope is on the window layers
only: the configuration file's ``assumed`` says why.

**The expert layer** (:class:`HeldExpertLayer`, ``layers/moe.py``) is told
which experts it holds, ``held = (first, count)`` of the published
``n_routed_experts``; it scores all of them with a sigmoid in float32,
chooses ``moe_topk`` by score plus a correction bias, renormalises the
chosen scores over their own sum (all of the chosen, the absent ones'
too), scales by ``routed_scaling_factor``, computes its own experts' part
and the shared expert, and leaves out what absent experts would add.

**Two kinds of cache layer** (:meth:`ExaoneMoeModel.kv_cache_spec`): the full
layers' group keeps every position; the window layers' group keeps the last
``window`` and is read as a ring (``ops.ring_update``, ``window=`` of
``ops.chunk_attention`` / ``ops.decode_attention``).  The serving engine
hands both cache entry points a pair of cache layers in each of the places
of ``k_cache`` / ``v_cache``: ``(full group, window group)``, each with
``read(layer)`` and ``write(layer, rows)`` (``serve.kv_cache.PagedLayers``).

The layer, its three calls (the dense forward, a prefill chunk, a decode
round), both cache entry points and the loss are ``models/block.py``'s
(:class:`~hetu_tpu.models.block.BlockDecoder`); here are the configuration,
the weights, the two cache groups and the tables that say where each layer's
cache layer lies, that the window layers are rotated and what their window
is.  Weights are made in ``param_dtype`` directly, a slice at a time, the
router and its correction bias in float32.  ``jax.named_scope``s in the
jitted programs: ``hetu.attn.window``, ``hetu.attn.full``, ``hetu.ffn.dense``,
``hetu.moe.route``, ``hetu.moe.experts``, ``hetu.moe.shared``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from hetu_tpu.layers.moe import MOE_STATS, HeldExpertLayer
from hetu_tpu.models.block import (
    FULL, WINDOW, BlockDecoder, draw_leaf, with_grouped,
)


@dataclass
class ExaoneMoeConfig:
    vocab_size: int = 153600
    hidden_size: int = 6144
    num_layers: int = 48
    num_heads: int = 64
    num_kv_heads: int = 8
    head_dim: int = 128
    ffn_size: int = 18432                # the dense layers' SwiGLU
    expert_ffn_size: int = 2048          # routed and shared experts alike
    first_dense: int = 1                 # leading layers with a dense FFN
    n_routed_experts: int = 128          # as published: the router's width
    moe_topk: int = 8
    routed_scaling_factor: float = 2.5
    held: Optional[tuple] = None         # (first, count); None: all of them
    window: int = 128
    layer_types: Optional[tuple] = None  # None: every fourth layer full
    rope_theta: float = 1e6
    rms_eps: float = 1e-5
    max_position: int = 262144
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
            self.layer_types = tuple(FULL if l % 4 == 3 else WINDOW
                                     for l in range(self.num_layers))
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != self.num_layers or \
                set(self.layer_types) - {WINDOW, FULL}:
            raise ValueError(f"layer_types {self.layer_types} do not name "
                             f"{self.num_layers} window or full layers")
        if self.head_dim % 2 or self.num_heads % self.num_kv_heads:
            raise ValueError("head_dim must be even and kv heads divide "
                             "the query heads")


class ExaoneMoeModel(BlockDecoder):
    """``params["layers"]``: ``attn_norm``/``ffn_norm`` [L, H], ``attn`` {q
    [heads * D, H], k [kv_heads * D, H], v [H, kv_heads * D], o [heads * D,
    H], q_norm, k_norm} stacked over the L layers, ``ffn`` {gate, up,
    down} over the ``first_dense`` leading layers, ``moe``
    (:class:`HeldExpertLayer`'s parameters) over the L - ``first_dense``
    expert layers."""

    # the expert layers' counts and the held pairs that the walk's grouped
    # calls computed (``models.block.with_grouped``)
    step_stats = MOE_STATS + ("moe_grouped",)

    def __init__(self, config: ExaoneMoeConfig):
        c = config
        # (group, cache layer in the group) of each layer: group 0 the full
        # layers, group 1 the window layers
        seen = [0, 0]
        cache_layer = []
        for kind in c.layer_types:
            g = int(kind == WINDOW)
            cache_layer.append((g, seen[g]))
            seen[g] += 1
        self.group_layers = tuple(seen)
        if not all(seen):
            raise ValueError("the cache is stated as a full group followed "
                             "by a window group: the layers need both kinds")
        windowed = [l for l, kind in enumerate(c.layer_types)
                    if kind == WINDOW]
        super().__init__(
            c, HeldExpertLayer(
                n_routed=c.n_routed_experts, n_zero=0, k=c.moe_topk,
                scaling=c.routed_scaling_factor, held=c.held,
                block_rows=c.expert_block_rows, dtype=c.dtype,
                scoring="sigmoid", renormalise=True, shared=True),
            attn_leaf=tuple(range(c.num_layers)), cache_layer=cache_layer,
            rotated=windowed, window={l: c.window for l in windowed})

    # ---- the cache this model asks of the serving engine ----
    def kv_cache_spec(self):
        from hetu_tpu.serve.kv_cache import KVCacheSpec

        c = self.c
        full, window = self.group_layers
        return KVCacheSpec(
            num_layers=full, num_kv_heads=c.num_kv_heads,
            head_dim=c.head_dim, dtype=c.dtype,
            also=(KVCacheSpec(num_layers=window,
                              num_kv_heads=c.num_kv_heads,
                              head_dim=c.head_dim, dtype=c.dtype,
                              window=c.window),))

    # ---- weights ----
    def init(self, key):
        """Every leaf in ``param_dtype`` (the router and its correction bias
        in float32), a large leaf drawn a piece at a time: the float32 draw
        of a piece is the only wide temporary, never a twin of the leaf."""
        c = self.c
        pd = c.param_dtype
        H, E, F = c.hidden_size, c.held[1], c.expert_ffn_size
        qw, kvw = c.num_heads * c.head_dim, c.num_kv_heads * c.head_dim

        def draw(k, lead: tuple, shape: tuple, std, dtype=pd):
            return draw_leaf(k, lead, shape, std, dtype)

        def ones(*shape):
            return jnp.ones(shape, pd)

        ks = iter(jax.random.split(key, 20))
        std = c.init_std
        L, D = c.num_layers, c.first_dense
        S = L - D
        layers = {
            "attn_norm": ones(L, H), "ffn_norm": ones(L, H),
            # q and k are held [out, in]: both programs compute them a head
            # at a time with the per-head norm fused in, and relaid an
            # [in, out] leaf for it in every call (PERF.md, PR 32)
            "attn": {"q": draw(next(ks), (L,), (qw, H), std),
                     "k": draw(next(ks), (L,), (kvw, H), std),
                     "v": draw(next(ks), (L,), (H, kvw), std),
                     "o": draw(next(ks), (L,), (qw, H), std),
                     "q_norm": ones(L, c.head_dim),
                     "k_norm": ones(L, c.head_dim)},
            "ffn": {"gate": draw(next(ks), (D,), (H, c.ffn_size), std),
                    "up": draw(next(ks), (D,), (H, c.ffn_size), std),
                    "down": draw(next(ks), (D,), (c.ffn_size, H), std)},
            "moe": {
                "router": draw(next(ks), (S,), (H, c.n_routed_experts),
                               c.router_init_std, jnp.float32),
                "router_bias": draw(next(ks), (S,), (c.n_routed_experts,),
                                    c.router_bias_std, jnp.float32),
                "gate": draw(next(ks), (S, E), (H, F), std),
                "up": draw(next(ks), (S, E), (H, F), std),
                "down": draw(next(ks), (S, E), (F, H), std),
                "shared_gate": draw(next(ks), (S,), (H, F), std),
                "shared_up": draw(next(ks), (S,), (H, F), std),
                "shared_down": draw(next(ks), (S,), (F, H), std)},
        }
        return {"params": {
            "tok_emb": draw(next(ks), (), (c.vocab_size, H), std),
            "lm_head": draw(next(ks), (), (c.vocab_size, H), std),
            "norm_f": ones(H),
            "layers": layers,
        }, "state": {}}

    def _counts(self, stats):
        return with_grouped(self.c, stats)
