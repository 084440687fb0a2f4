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

The layers run as a Python loop, not a scan: layers of two kinds with
caches of two shapes do not scan.  Parameter leaves are stacked, attention's
over all layers, the dense FFN's over the leading dense layers, the expert
layer's over the layers that follow, and each is read at its layer's own
(static) index where it is used.  Weights are made in ``param_dtype``
directly, a slice at a time, the router and its correction bias in float32.

``jax.named_scope``s mark the sub-layers in the jitted programs
(``hetu.attn.window``, ``hetu.attn.full``, ``hetu.ffn.dense``,
``hetu.moe.route``, ``hetu.moe.experts``, ``hetu.moe.shared``).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from hetu_tpu import ops
from hetu_tpu.layers.base import Module
from hetu_tpu.layers.moe import MOE_STATS, HeldExpertLayer

WINDOW, FULL = "sliding_attention", "full_attention"


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


class GroupedHeads:
    """Grouped-query attention's projections as K-EXAONE has them, for any
    model whose configuration ``self.c`` gives ``num_heads``,
    ``num_kv_heads``, ``head_dim``, ``rms_eps`` and ``dtype``
    (``models/mellum.py`` shares them): Q and K normalised per head, the
    half-rotation layout over the whole head, the out-projection.  ``p`` is
    the attention leaves stacked over layers, ``l`` the layer read."""

    def _norm(self, x, scale):
        return ops.rms_norm(x, scale, eps=self.c.rms_eps)

    @staticmethod
    def _rotate(x, cos, sin):
        """Half-rotation layout over the whole head: x [B, S, heads, D],
        cos/sin [B, S, D / 2]; float32 inside, result in x's dtype."""
        xf = x.astype(jnp.float32)
        d2 = x.shape[-1] // 2
        x1, x2 = xf[..., :d2], xf[..., d2:]
        cos, sin = cos[:, :, None], sin[:, :, None]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               axis=-1).astype(x.dtype)

    def _qkv(self, p, l: int, a, cos, sin, rotate: bool):
        """a [B, S, H] normed -> (q [B, heads, S, D], k [B, S, kv_heads, D],
        v the same) of layer ``l``: q and k normalised per head, rotated
        where ``rotate``.  k and v are the rows a cache holds."""
        c, dt = self.c, self.c.dtype
        b, s, _ = a.shape
        q = ops.linear(a, p["q"][l].astype(dt), trans_w=True).reshape(
            b, s, c.num_heads, c.head_dim)
        k = ops.linear(a, p["k"][l].astype(dt), trans_w=True).reshape(
            b, s, c.num_kv_heads, c.head_dim)
        v = ops.linear(a, p["v"][l].astype(dt)).reshape(
            b, s, c.num_kv_heads, c.head_dim)
        q = self._norm(q, p["q_norm"][l])
        k = self._norm(k, p["k_norm"][l])
        if rotate:
            q, k = self._rotate(q, cos, sin), self._rotate(k, cos, sin)
        return jnp.moveaxis(q, 1, 2), k, v

    def _out(self, p, l: int, o):
        """o [B, heads, S, D] -> [B, S, H]."""
        b, _, s, _ = o.shape
        o = jnp.moveaxis(o, 1, 2).reshape(b, s, -1)
        return ops.linear(o.astype(self.c.dtype),
                          p["o"][l].astype(self.c.dtype))


class ExaoneMoeModel(GroupedHeads, Module):
    """``params["layers"]``: ``attn_norm``/``ffn_norm`` [L, H], ``attn`` {q
    [heads * D, H], k [kv_heads * D, H], v [H, kv_heads * D], o [heads * D,
    H], q_norm, k_norm} stacked over the L layers, ``ffn`` {gate, up,
    down} over the ``first_dense`` leading layers, ``moe``
    (:class:`HeldExpertLayer`'s parameters) over the L - ``first_dense``
    expert layers."""

    # what the fourth value of the two cache entry points counts, in order
    step_stats = MOE_STATS

    def __init__(self, config: ExaoneMoeConfig):
        c = self.c = config
        self.moe = HeldExpertLayer(
            n_routed=c.n_routed_experts, n_zero=0, k=c.moe_topk,
            scaling=c.routed_scaling_factor, held=c.held,
            block_rows=c.expert_block_rows, dtype=c.dtype,
            scoring="sigmoid", renormalise=True, shared=True)
        self.scale = c.head_dim ** -0.5
        # (group, cache layer in the group) of each layer: group 0 the full
        # layers, group 1 the window layers
        seen = [0, 0]
        self.cache_layer = []
        for kind in c.layer_types:
            g = int(kind == WINDOW)
            self.cache_layer.append((g, seen[g]))
            seen[g] += 1
        self.group_layers = tuple(seen)
        if not all(seen):
            raise ValueError("the cache is stated as a full group followed "
                             "by a window group: the layers need both kinds")

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
            # one float32 draw at a time; a slice of 2**25 numbers or more
            # (a dense FFN leaf, the embedding) in up to eight row blocks
            rows = math.gcd(shape[0], 8) if math.prod(shape) >= 2 ** 25 \
                else 1
            part = (shape[0] // rows,) + tuple(shape[1:])
            out = jax.lax.map(
                lambda kk: (jax.random.normal(kk, part, jnp.float32)
                            * std).astype(dtype),
                jax.random.split(k, math.prod(lead) * rows))
            return out.reshape(lead + shape)

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

    # ---- pieces of a layer ----
    def rope_at(self, pos):
        """cos/sin [..., head_dim / 2] float32 at absolute positions."""
        d = self.c.head_dim
        inv = 1.0 / self.c.rope_theta ** (
            jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        ang = pos.astype(jnp.float32)[..., None] * inv
        return jnp.cos(ang), jnp.sin(ang)

    def _ffn(self, p, l: int, x):
        dt = self.c.dtype
        with jax.named_scope("hetu.ffn.dense"):
            g = ops.linear(x, p["gate"][l].astype(dt))
            u = ops.linear(x, p["up"][l].astype(dt))
            return ops.linear(ops.silu(g) * u, p["down"][l].astype(dt))

    def _layer(self, p, l: int, h, attend):
        """Layer ``l`` over ``h`` [B, S, H], ``p`` the stacked leaves of
        every layer; ``attend(l, window, a)`` is the phase's attention
        block on the normed input ``a``, ``window`` None on a full layer.
        Returns (out, the expert layer's counts [4] int32, zeros on a dense
        layer)."""
        window = self.c.window if self.c.layer_types[l] == WINDOW else None
        h = h + attend(l, window, self._norm(h, p["attn_norm"][l]))
        u = self._norm(h, p["ffn_norm"][l])
        if l < self.c.first_dense:
            return h + self._ffn(p["ffn"], l, u), jnp.zeros((4,), jnp.int32)
        moe, e = p["moe"], l - self.c.first_dense
        m, stats = self.moe.apply(
            dict(moe, router=moe["router"][e],
                 router_bias=moe["router_bias"][e]),
            u, layer=e)
        return h + m, stats

    def _embed(self, p, ids):
        return ops.embedding_lookup(p["tok_emb"], ids).astype(self.c.dtype)

    def _head(self, p, h):
        """h: the stream after the last layer -> logits."""
        return ops.linear(self._norm(h, p["norm_f"]),
                          p["lm_head"].T.astype(self.c.dtype))

    # ---- dense forward ----
    def hidden_states(self, variables, input_ids, *, train: bool = False,
                      rng=None):
        p = variables["params"]
        c = self.c
        b, s = input_ids.shape
        h = self._embed(p, input_ids)
        pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        cos, sin = self.rope_at(pos)
        rep = c.num_heads // c.num_kv_heads

        pa = p["layers"]["attn"]

        def attend(l, window, a):
            q, k, v = self._qkv(pa, l, a, cos, sin, window is not None)
            with jax.named_scope(
                    "hetu.attn.window" if window else "hetu.attn.full"):
                # heads grouped by the KV head they read: [B, kv, rep, S, D]
                # against [B, kv, 1, S, D]
                o = ops.causal_attention(
                    q.reshape(b, c.num_kv_heads, rep, s, c.head_dim),
                    jnp.moveaxis(k, 1, 2)[:, :, None],
                    jnp.moveaxis(v, 1, 2)[:, :, None],
                    scale=self.scale, window=window)
            return self._out(pa, l,
                             o.reshape(b, c.num_heads, s, c.head_dim))

        for l in range(c.num_layers):
            h, _ = self._layer(p["layers"], l, h, attend)
        return h

    def apply(self, variables, input_ids, *, train: bool = False, rng=None):
        h = self.hidden_states(variables, input_ids, train=train, rng=rng)
        return self._head(variables["params"], h), {}

    # ---- serving (hetu_tpu/serve): prefill in chunks / decode ----
    # k_cache and v_cache are each a pair (full group, window group) of cache
    # layers with ``read(layer)`` -> [B, T, kv_heads, D] and ``write(layer,
    # rows)``; the window group's view is a ring.  Both entry points return a
    # fourth value, the expert layers' counts (``step_stats`` names them)
    # summed over the layers.

    def _cached(self, p, input_ids, k_cache, v_cache, pos, attention,
                one_query: bool = False):
        """Both cache entry points: a layer reads its own cache layer of its
        group (of the engine's pools, that layer's pages and no more),
        writes its new rows [B, S, kv_heads, D] into the views from each
        sequence's first position ``pos[:, 0]`` on (a ring wraps), attends
        over them (``attention(q, k_view, v_view, window)``, the phase's
        step) and puts the new rows into the pool.  Views and new rows are
        kept FLAT, [B, T, kv_heads * D], as the pages hold them: split by
        head a view is tiled another way and copied whole.  ``one_query``
        (a decode round): a FULL layer makes no view, its step is
        ``ops.decode_layer_attention`` over its group's cache where it
        lies; a window layer's ring is read as above."""
        h = self._embed(p, input_ids)
        cos, sin = self.rope_at(pos)
        at = pos[:, 0]
        k_cache, v_cache = list(k_cache), list(v_cache)

        pa = p["layers"]["attn"]

        def attend(l, window, a):
            q, k, v = self._qkv(pa, l, a, cos, sin, window is not None)
            g, cl = self.cache_layer[l]
            b, s = k.shape[:2]
            with jax.named_scope(
                    "hetu.attn.window" if window else "hetu.attn.full"):
                if one_query and window is None:
                    o, k_cache[g], v_cache[g] = ops.decode_layer_attention(
                        q, k, v, k_cache[g], v_cache[g], cl, at,
                        scale=self.scale)
                    return self._out(pa, l, o)
                update = ops.ring_update if window else ops.cache_update
                k_view, v_view = k_cache[g].read(cl), v_cache[g].read(cl)
                t = k_view.shape[1]
                k_view, v_view = update(
                    k_view.reshape(b, t, -1), v_view.reshape(b, t, -1),
                    k.reshape(b, s, -1), v.reshape(b, s, -1), at)
                o = attention(q, k_view, v_view, window)
            k_cache[g] = k_cache[g].write(cl, k)
            v_cache[g] = v_cache[g].write(cl, v)
            return self._out(pa, l, o)

        stats = jnp.zeros((4,), jnp.int32)
        for l in range(self.c.num_layers):
            h, n = self._layer(p["layers"], l, h, attend)
            stats = stats + n
        return h, tuple(k_cache), tuple(v_cache), stats

    def prefill_chunk_with_cache(self, variables, input_ids, k_cache,
                                 v_cache, start, *, last_index=None):
        """input_ids [B, S_c] at absolute positions ``start..``; positions
        below ``start`` of the caches are written.  Returns (logits [B, V]
        at chunk-relative ``last_index``, new_k, new_v, counts)."""
        p = variables["params"]
        b, s = input_ids.shape
        starts = jnp.broadcast_to(jnp.asarray(start, jnp.int32), (b,))
        pos = starts[:, None] + jnp.arange(s)[None]

        heads = (self.c.num_kv_heads, self.c.head_dim)

        def attention(q, k_view, v_view, window):
            return ops.chunk_attention(
                q, k_view.reshape(k_view.shape[:2] + heads),
                v_view.reshape(v_view.shape[:2] + heads), starts,
                scale=self.scale, window=window)

        h, k_cache, v_cache, stats = self._cached(
            p, input_ids, k_cache, v_cache, pos, attention)
        idx = s - 1 if last_index is None else last_index
        h = jax.lax.dynamic_index_in_dim(h, idx, axis=1, keepdims=False)
        return self._head(p, h), k_cache, v_cache, stats

    def decode_with_cache(self, variables, input_ids, k_cache, v_cache,
                          lengths):
        """One decode step; input_ids [B], lengths [B] tokens cached.
        Returns (logits [B, V], new_k, new_v, counts)."""
        p = variables["params"]

        def attention(q, k_view, v_view, window):
            return ops.decode_attention(
                q, k_view, v_view, lengths, scale=self.scale, window=window,
                kv_heads=self.c.num_kv_heads)

        h, k_cache, v_cache, stats = self._cached(
            p, input_ids[:, None], k_cache, v_cache, lengths[:, None],
            attention, one_query=True)
        return self._head(p, h[:, 0]), k_cache, v_cache, stats

    # ---- training (test size; no cut of the published model trains on
    # one chip) ----
    def lm_loss_fn(self):
        """Next-token loss; batch = (input_ids,)."""
        def fn(params, model_state, batch, rng, train):
            ids = batch[0] if isinstance(batch, (tuple, list)) else batch
            logits, _ = self.apply({"params": params, "state": {}}, ids,
                                   train=train, rng=rng)
            per = ops.softmax_cross_entropy_sparse(logits[:, :-1], ids[:, 1:])
            return jnp.mean(per), ({}, model_state)
        return fn
