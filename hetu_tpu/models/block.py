"""One decoder block for the models that are served through
``hetu_tpu/serve``'s cache entry points (expert models and a dense hybrid),
and its three calls.

A layer is ``h + operator(N(h))`` then ``h + feed_forward(N(h))``, ``N``
RMSNorm with its own weight (``w``, or ``1 + w`` where the model states so):
the operator grouped-query attention (:class:`GroupedHeads`: q and k
normalised per head where the weights hold such norms, the half-rotation
layout where the layer is rotated, over the whole head or its leading
``rotary_dim`` dims, full or over a window, its result gated by a second
half of the query projection where the model states one) unless the model
states another (:meth:`BlockDecoder._operator`: a convolution or a delta-rule
mixer in its place, or a second branch beside it); the feed-forward
a dense SwiGLU on the ``first_dense`` leading layers and the model's
:class:`~hetu_tpu.layers.moe.HeldExpertLayer` on the rest (a model without
one is dense throughout).  A layer runs in one of three calls, which
:class:`LayerCall` describes: the dense forward, a prefill chunk over the
serving engine's cache layers, a decode round over them.

:class:`BlockDecoder` is the layer, the three calls, both cache entry points
of ``hetu_tpu/serve`` and the loss.  A model (``models/exaone_moe.py``: window
and full attention layers in two cache groups; ``models/lfm2_moe.py``: short
convolutions with state layers between full attention layers in one group;
``models/falcon_h1.py``: a state-space branch beside attention in every
layer, each layer a cache layer AND a state layer; ``models/qwen3_next.py``:
Gated DeltaNet state layers of two parts between gated, partly rotated full
attention layers in one group; ``models/minicpm_sala.py``: block-sparse
layers that CHOOSE the positions a query reads by compressed keys, a third
kind of cached attention layer beside window and full, between
linear-attention state layers) states through the
constructor its expert layer, the constant factors it scales its products by
(``multipliers``) and, by layer, where a layer's attention leaves and cache
layer lie, whether it is rotated and what window it has; and itself holds
its configuration, its weights (``init``) and the cache it asks for
(``kv_cache_spec``).  The head is ``lm_head`` where the weights have one,
else tied to the embedding.

The layers run as a Python loop, not a scan: layers of several kinds with
caches of several shapes do not scan.  Parameter leaves are stacked over the
layers that have them and read at a layer's own (static) index; a server
holds the attention projections a layer an array
(:meth:`BlockDecoder.serving_params`), which the same index reads.

``jax.named_scope``s mark the sub-layers in the jitted programs
(``hetu.attn.window``, ``hetu.attn.full``, ``hetu.ffn.dense``; a sparse
layer's ``hetu.sparse.compress|select|attend``; the expert layer's are
``layers/moe.py``'s).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from hetu_tpu import ops
from hetu_tpu.layers.base import Module, held_by_layer
from hetu_tpu.layers.moe import MOE_STATS
from hetu_tpu.ops.moe_ops import held_expert_path

# the names ``layer_types`` gives the two kinds of attention layer
WINDOW, FULL = "sliding_attention", "full_attention"

# what the cache entry points of a model with SPARSE layers count, summed
# over those layers: the real queries that read their chosen blocks and
# those that read every position (the layer's dense branch), the blocks the
# former chose and could have (chosen / visible: how sparse the call was),
# on a decode round the pages the attention walked for them beside the pages
# their sequences hold, and of the former those whose attention ran in the
# flash forward kernel's sparse chunk call (a chunk's, where
# ``ops.sparse_kernel_why`` is "": all of a chunk's or none)
SPARSE_STATS = ("sparse_queries", "dense_queries", "blocks_chosen",
                "blocks_visible", "sparse_pages_read", "pages_held",
                "sparse_kernel_queries")


@dataclass(frozen=True)
class ChosenBlocks:
    """What a SPARSE attention layer chooses by (InfLLM-V2;
    ``ops.select_blocks``): a compressed key every ``stride`` positions over
    ``kernel`` of them, blocks of ``block`` positions (a serving cache's
    page), the ``topk`` best a query a KV head with the first
    ``init_blocks`` and those of the last ``local`` positions among them;
    dense instead while the sequence is shorter than ``dense_len`` at the
    call that computes the token (a prompt's token by the PROMPT's length, a
    generated one by its own position + 1)."""

    stride: int = 16
    kernel: int = 32
    block: int = 64
    topk: int = 64
    init_blocks: int = 1
    local: int = 2048
    dense_len: int = 8192

    def __post_init__(self):
        if self.kernel % self.stride or self.block % self.stride:
            raise ValueError(f"window {self.kernel} and block {self.block} "
                             f"are multiples of the stride {self.stride}")
        if self.init_blocks + -(-(self.local - 1) // self.block) + 1 \
                > self.topk:
            raise ValueError("the forced blocks alone exceed topk")

    @property
    def how(self) -> dict:
        """``ops.select_blocks``' keywords."""
        return {"stride": self.stride, "kernel": self.kernel,
                "block": self.block, "topk": self.topk,
                "init_blocks": self.init_blocks, "local": self.local}


def draw_leaf(key, lead: tuple, shape: tuple, std, dtype):
    """A normal leaf ``lead + shape`` of ``dtype``, one float32 draw at a
    time: a slice of 2**25 numbers or more (a dense FFN leaf, the embedding)
    in up to eight row blocks, so the float32 draw of a piece is the only
    wide temporary, never a twin of the leaf."""
    rows = math.gcd(shape[0], 8) if math.prod(shape) >= 2 ** 25 else 1
    part = (shape[0] // rows,) + tuple(shape[1:])
    out = jax.lax.map(
        lambda kk: (jax.random.normal(kk, part, jnp.float32)
                    * std).astype(dtype),
        jax.random.split(key, math.prod(lead) * rows))
    return out.reshape(lead + shape)


def _evaluated_grouped(c) -> int:
    """1 where grouped calls compute the held pairs of a cache entry point
    of configuration ``c``, 0 where the loop does.  An entry point is
    evaluated and never differentiated, so 1 unless
    ``ops.moe_ops.held_expert_path``'s static rule says ``"loop"`` (it reads
    an expert's size alone, so one token stands for a call of any row
    count)."""
    return int(held_expert_path(1, c.moe_topk, c.held[1], c.hidden_size,
                                c.expert_ffn_size) != "loop")


def with_grouped(c, stats):
    """``stats`` (the expert layers' sum, ``MOE_STATS`` first) with
    ``moe_grouped`` behind them: the held pairs that grouped calls computed,
    all of them or none (:func:`_evaluated_grouped`)."""
    return jnp.concatenate([stats, (stats[0] * _evaluated_grouped(c))[None]])


def counts_with_grouped(c, stats):
    """The counts a cache entry point of a model whose ``step_stats`` are
    ``MOE_STATS + ("moe_experts", "moe_grouped")`` returns, from the expert
    layers' sum ``stats`` [4]: behind them the held experts a call could hit
    at most (held x expert layers, a constant) and the held pairs that
    grouped calls computed, all of them or none
    (:func:`_evaluated_grouped`)."""
    return jnp.concatenate([stats, jnp.stack([
        jnp.int32(c.held[1] * (c.num_layers - c.first_dense)),
        stats[0] * _evaluated_grouped(c)])])


@dataclass
class LayerCall:
    """The call a layer's operator runs in.  The dense forward: the rotary
    table alone.  A cached call (a prefill chunk, a decode round) besides:
    ``k`` / ``v`` the cache layers a group, lists that a layer replaces as it
    writes; ``at`` [B] each sequence's first new position; ``attention(q,
    k_view, v_view, window)`` the call's attention step over a layer's view;
    ``one_query`` a decode round.  A model with state layers: ``state`` its
    ``serve.kv_cache.SlotStates`` (None in the dense forward, which starts
    from zeros) and ``last`` the chunk-relative index of the last REAL token
    (None: the call's last row)."""

    cos: jax.Array
    sin: jax.Array
    k: Optional[list] = None
    v: Optional[list] = None
    at: Optional[jax.Array] = None
    attention: Optional[Callable] = None
    one_query: bool = False
    state: object = None
    last: object = None
    prompt_len: object = None
    counts: object = None


class GroupedHeads:
    """Grouped-query attention's projections for any model whose
    configuration ``self.c`` gives ``num_heads``, ``num_kv_heads``,
    ``head_dim``, ``rms_eps`` and ``dtype`` (:class:`BlockDecoder`'s models;
    ``models/mellum.py``, which trains): Q and K normalised per head where
    the leaves hold ``q_norm`` / ``k_norm`` (a model without them has no such
    norm), K times ``multipliers["key"]`` where the model states one, the
    half-rotation layout over the head's rotated dims, the out-projection.
    A ``g`` leaf [H, heads * D] is an output gate from a projection of its
    own (the result times ``sigmoid(a W_g)``).  Three things a model may
    STATE, each absent here: ``rotary_dim``, the
    leading dims of a head that are rotated (None: the whole head; the rest
    pass as they are); ``gated_query``, a ``q`` leaf of twice the width,
    ``[query | gate]`` a head, the attention's result times ``sigmoid(gate)``
    before the out-projection; ``unit_offset_norms``, every norm weighs by
    ``1 + w``.  ``p`` is the attention leaves stacked over layers, ``l`` the
    layer read."""

    # constant factors a model scales its products by, by name; none here
    multipliers: dict = {}
    rotary_dim = None
    gated_query = False
    unit_offset_norms = False
    # a clamp inside every SwiGLU a model may state (``silu(min(gate, x)) *
    # clip(up, -x, x)``); None: none, and nothing in the program
    swiglu_limit = None

    def _norm(self, x, scale):
        if self.unit_offset_norms:
            scale = 1.0 + scale.astype(jnp.float32)
        return ops.rms_norm(x, scale, eps=self.c.rms_eps)

    @staticmethod
    def _rotate(x, cos, sin):
        """Half-rotation layout over the leading ``2 r`` dims of the head,
        the whole head where the tables are that wide: x [B, S, heads, D],
        cos/sin [B, S, r]; the other ``D - 2 r`` dims pass as they are;
        float32 inside, result in x's dtype."""
        xf = x.astype(jnp.float32)
        d2 = cos.shape[-1]
        x1, x2 = xf[..., :d2], xf[..., d2:2 * d2]
        cos, sin = cos[:, :, None], sin[:, :, None]
        turned = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
        if 2 * d2 < x.shape[-1]:
            turned.append(xf[..., 2 * d2:])
        return jnp.concatenate(turned, axis=-1).astype(x.dtype)

    def _qkv(self, p, l: int, a, cos, sin, rotate: bool):
        """a [B, S, H] normed -> (q [B, heads, S, D], k [B, S, kv_heads, D],
        v the same) of layer ``l``: q and k normalised per head where the
        leaves hold the norms, rotated where ``rotate``.  k and v are the
        rows a cache holds.  A model with a gated query projection gets a
        fourth value, the gate [B, S, heads * D]."""
        c, dt = self.c, self.c.dtype
        b, s, _ = a.shape
        q = ops.linear(a, p["q"][l].astype(dt), trans_w=True).reshape(
            b, s, c.num_heads, -1)
        gate = ()
        if self.gated_query:
            q, g = jnp.split(q, 2, axis=-1)
            gate = (g.reshape(b, s, -1),)
        elif "g" in p:
            gate = (ops.linear(a, p["g"][l].astype(dt)),)
        k = ops.linear(a, p["k"][l].astype(dt), trans_w=True).reshape(
            b, s, c.num_kv_heads, c.head_dim)
        v = ops.linear(a, p["v"][l].astype(dt)).reshape(
            b, s, c.num_kv_heads, c.head_dim)
        if "q_norm" in p:
            q = self._norm(q, p["q_norm"][l])
            k = self._norm(k, p["k_norm"][l])
        if "key" in self.multipliers:
            k = k * self.multipliers["key"]
        if rotate:
            q, k = self._rotate(q, cos, sin), self._rotate(k, cos, sin)
        return (jnp.moveaxis(q, 1, 2), k, v) + gate

    def _out(self, p, l: int, o, gate=None):
        """o [B, heads, S, D] -> [B, S, H]; times ``sigmoid(gate)`` [B, S,
        heads * D] first where the query projection has one."""
        b, _, s, _ = o.shape
        o = jnp.moveaxis(o, 1, 2).reshape(b, s, -1)
        if gate is not None:
            with jax.named_scope("hetu.attn.full"):
                o = o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(
                    o.dtype)
        return ops.linear(o.astype(self.c.dtype),
                          p["o"][l].astype(self.c.dtype))


class BlockDecoder(GroupedHeads, Module):
    """``config`` gives ``num_layers``, ``first_dense``,
    :class:`GroupedHeads`' widths, ``rope_theta`` and ``dtype``; ``moe`` is
    the model's expert layer (None: every layer is dense, ``first_dense`` =
    ``num_layers``); ``multipliers`` the constant factors the model scales
    by, each applied where it is named and nowhere when absent: ``embed`` the
    embedded rows, ``key`` attention's K, ``gate`` the feed-forward's gate
    product inside its activation, ``down`` its result, ``head`` the logits,
    ``branch`` what either sub-layer adds to the stream.  ``sparse``
    (:class:`ChosenBlocks`) with ``sparse_layers``: the layers whose cached
    attention reads the blocks a query chose (their cache group keeps
    compressed rows, ``serve.kv_cache.KVCacheSpec.comp_stride``, and its
    pages are the blocks); the entry points of such a model return
    ``SPARSE_STATS`` as their counts.
    The tables, each by layer index and holding
    the attention layers alone: ``attn_leaf`` the layer's index in the
    stacked attention leaves, ``cache_layer`` its (group, cache layer in the
    group) of the serving cache, ``rotated`` the layers whose q and k are
    rotated, ``window`` the window of those that have one.  ``rotary_dim``,
    ``gated_query`` and ``unit_offset_norms`` are :class:`GroupedHeads`',
    stated by the models that have them.

    ``params``: ``tok_emb`` [V, H], ``lm_head`` [V, H] unless the head is
    tied, ``norm_f``, ``layers``: ``attn_norm``/``ffn_norm`` [L, H] (the
    operator's norm whatever the operator), ``attn`` (:class:`GroupedHeads`'
    leaves, stacked), ``ffn`` {gate, up, down} over the ``first_dense``
    leading layers, ``moe`` (the expert layer's) over the rest."""

    # what the fourth value of the two cache entry points counts, in order
    step_stats = MOE_STATS

    @property
    def call_stats(self) -> tuple:
        """What the model's attention layers count into ``LayerCall.counts``
        in a cached call (behind the expert layers' counts where the model
        has both): ``SPARSE_STATS`` of a model with sparse layers; a model
        that selects otherwise states its own."""
        return SPARSE_STATS if self.sparse_layers else ()

    def __init__(self, config, moe, *, attn_leaf, cache_layer, rotated,
                 window=None, multipliers=None, rotary_dim=None,
                 gated_query: bool = False, unit_offset_norms: bool = False,
                 sparse: Optional[ChosenBlocks] = None, sparse_layers=()):
        self.c = config
        self.sparse, self.sparse_layers = sparse, frozenset(sparse_layers)
        self.moe = moe
        self.multipliers = dict(multipliers or {})
        self.rotary_dim, self.gated_query = rotary_dim, bool(gated_query)
        self.unit_offset_norms = bool(unit_offset_norms)
        self.scale = config.head_dim ** -0.5
        self.attn_leaf, self.cache_layer = attn_leaf, cache_layer
        self.rotated = frozenset(rotated)
        self.window = dict(window or {})

    # ---- the weights as a server holds them ----
    def serving_params(self, params):
        """The four projection leaves of the stacked attention leaves a
        layer an array: the layers are a Python loop, and a layer cut out
        of a stacked leaf at a static index is written into a buffer of
        its own in every call (``layers/base.py``
        ``Module.serving_params``).  ``p["q"][l]`` reads either form."""
        attn = held_by_layer(params["layers"]["attn"], "q", "k", "v", "o",
                             *(("g",) if "g" in params["layers"]["attn"]
                               else ()))
        return dict(params, layers=dict(params["layers"], attn=attn))

    # ---- pieces of a layer ----
    def rope_at(self, pos):
        """cos/sin [..., rotated dims / 2] float32 at absolute positions."""
        d = self.rotary_dim or self.c.head_dim
        inv = 1.0 / self.c.rope_theta ** (
            jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        ang = pos.astype(jnp.float32)[..., None] * inv
        return jnp.cos(ang), jnp.sin(ang)

    def _ffn(self, p, l: int, x):
        dt, m = self.c.dtype, self.multipliers
        with jax.named_scope("hetu.ffn.dense"):
            g = ops.linear(x, p["gate"][l].astype(dt))
            if "gate" in m:
                g = g * m["gate"]
            u = ops.linear(x, p["up"][l].astype(dt))
            if self.swiglu_limit is not None:
                g = jnp.minimum(g, self.swiglu_limit)
                u = jnp.clip(u, -self.swiglu_limit, self.swiglu_limit)
            out = ops.linear(ops.silu(g) * u, p["down"][l].astype(dt))
            return out * m["down"] if "down" in m else out

    def _operator(self, p, l: int, a, call: LayerCall):
        """Layer ``l``'s operator on its normed input ``a`` [B, S, H]:
        attention, unless the model states another for the layer."""
        return self._attention(p["attn"], l, a, call)

    def _attention(self, pa, l: int, a, call: LayerCall):
        """Grouped-query attention of layer ``l`` in the call ``call``, ``pa``
        the stacked attention leaves.  The dense forward attends over the
        call's own rows.  A cached call: the layer reads its own cache layer
        of its group (of the engine's pools, that layer's pages and no more),
        writes its new rows [B, S, kv_heads, D] into the views from each
        sequence's first position on (a ring wraps), attends over them and
        puts the new rows into the pool.  Views and new rows are kept FLAT,
        [B, T, kv_heads * D], as the pages hold them: split by head a view
        is tiled another way and copied whole.  A decode round's FULL layer
        makes no view, its step is ``ops.decode_layer_attention`` over its
        group's cache where it lies; a window layer's ring is read as
        above."""
        c = self.c
        window = self.window.get(l)
        al = self.attn_leaf[l]
        q, k, v, *gate = self._qkv(pa, al, a, call.cos, call.sin,
                                   l in self.rotated)
        b, s = k.shape[:2]
        scope = "hetu.attn.window" if window else "hetu.attn.full"
        if l in self.sparse_layers and not (
                call.k is None and call.prompt_len is None
                and s < self.sparse.dense_len):
            # a sparse layer, unless the dense forward's own rows are a
            # prompt under the dense length: then causal attention, below
            return self._out(pa, al, self._chosen(q, k, v, l, call), *gate)
        if call.k is None:
            with jax.named_scope(scope):
                # heads grouped by the KV head they read: [B, kv, rep, S, D]
                # against [B, kv, 1, S, D]
                o = ops.causal_attention(
                    q.reshape(b, c.num_kv_heads, c.num_heads // c.num_kv_heads,
                              s, c.head_dim),
                    jnp.moveaxis(k, 1, 2)[:, :, None],
                    jnp.moveaxis(v, 1, 2)[:, :, None],
                    scale=self.scale, window=window)
            return self._out(pa, al, o.reshape(b, c.num_heads, s, c.head_dim),
                             *gate)
        g, cl = self.cache_layer[l]
        with jax.named_scope(scope):
            if call.one_query and window is None:
                o, call.k[g], call.v[g] = ops.decode_layer_attention(
                    q, k, v, call.k[g], call.v[g], cl, call.at,
                    scale=self.scale)
                return self._out(pa, al, o, *gate)
            update = ops.ring_update if window else ops.cache_update
            k_view, v_view = call.k[g].read(cl), call.v[g].read(cl)
            t = k_view.shape[1]
            k_view, v_view = update(
                k_view.reshape(b, t, -1), v_view.reshape(b, t, -1),
                k.reshape(b, s, -1), v.reshape(b, s, -1), call.at)
            o = call.attention(q, k_view, v_view, window)
        call.k[g] = call.k[g].write(cl, k)
        call.v[g] = call.v[g].write(cl, v)
        return self._out(pa, al, o, *gate)

    # ---- a sparse layer: the blocks a query chose ----
    def _count(self, call: LayerCall, real, sparse, n, pos, *,
               walked: bool = False, kernel: bool = False):
        """Add one sparse layer's ``SPARSE_STATS`` to the call's counts:
        ``real`` [B, S] the queries that are tokens, ``sparse`` [B, S] those
        that read their choice, ``n`` [B, S] how many blocks each chose a KV
        head, ``pos`` their positions; ``walked``: a decode round, whose
        attention walked the chosen pages and no others; ``kernel``: a chunk
        whose masked attention is the flash kernel's sparse chunk call."""
        if call.counts is None:
            return
        g, blk = self.c.num_kv_heads, self.sparse.block
        chose = real & sparse
        queries = jnp.sum(chose)
        chosen = jnp.sum(jnp.where(chose, n, 0)) * g
        visible = jnp.sum(jnp.where(chose, pos // blk + 1, 0)) * g
        call.counts = call.counts + jnp.stack([
            queries, jnp.sum(real & ~sparse), chosen, visible,
            chosen * walked, visible * walked,
            queries * kernel]).astype(jnp.int32)

    def _masked(self, q, comp, pos, k_rows, v_rows, every):
        """Choose, then walk the rows under the choice's mask: q [B, heads,
        S, D] at ``pos`` [B, S] over k_rows / v_rows [B, T, kv_heads, D]
        with the compressed keys ``comp``; ``every`` (bool, broadcast
        against [B, S, kv_heads, blocks]): the queries that read every
        block all the same."""
        sp = self.sparse
        with jax.named_scope("hetu.sparse.select"):
            idx, _ = ops.select_blocks(q, comp, pos, scale=self.scale,
                                       **sp.how)
            chosen = ops.chosen_mask(idx, k_rows.shape[1] // sp.block) | every
        with jax.named_scope("hetu.sparse.attend"):
            return ops.masked_block_attention(
                q, k_rows, v_rows, pos, chosen, block=sp.block,
                scale=self.scale)

    def _chosen(self, q, k, v, l: int, call: LayerCall):
        """The attention of sparse layer ``l`` (``self.sparse``), q [B,
        heads, S, D], k / v [B, S, kv_heads, D] the call's new rows.  The
        dense forward compresses and chooses over its own rows.  A cached
        call keeps the layer's compressed keys beside its K rows
        (``PagedLayers.read_comp`` / ``write_comp``): it writes the windows
        its new rows COMPLETE (from the view, or the pool, where the rows
        before them lie), scores the sequence's compressed keys, and a
        chunk then walks its view under its queries' masks
        (``ops.masked_block_attention``) while a decode round walks, a
        (sequence, KV head), the pages that were chosen and no others
        (``ops.chosen_pages_attention``).  Sequences under the dense length
        read every position: a chunk whose prompt is that short runs the
        call's own attention step (one ``lax.cond`` a layer), a round that
        mixes both hands the short ones their whole (short) tables."""
        c, sp = self.c, self.sparse
        g, d = c.num_kv_heads, c.head_dim
        b, s = k.shape[:2]
        if call.k is None:                          # the dense forward
            pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
            own = jnp.full((b, 1), s) if call.prompt_len is None \
                else jnp.asarray(call.prompt_len).reshape(-1, 1)
            sparse = jnp.where(pos < own, own >= sp.dense_len,
                               pos + 1 >= sp.dense_len)
            t = -(-s // sp.block) * sp.block
            pad = ((0, 0), (0, t - s), (0, 0), (0, 0))
            with jax.named_scope("hetu.sparse.compress"):
                comp = ops.compress_keys(
                    jnp.pad(k, ((0, 0), (0, t - s + sp.kernel - sp.stride),
                                (0, 0), (0, 0))),
                    stride=sp.stride, kernel=sp.kernel).astype(k.dtype)
            return self._masked(q, comp, pos, jnp.pad(k, pad),
                                jnp.pad(v, pad), ~sparse[:, :, None, None])
        grp, cl = self.cache_layer[l]
        kc, vc = call.k[grp], call.v[grp]
        if kc.pool.shape[2] != sp.block:
            raise ValueError(f"a sparse layer's blocks of {sp.block} are "
                             f"its cache's pages, not {kc.pool.shape[2]}")
        n_pg = kc.tables.shape[1]
        real = jnp.ones((b, s), bool)
        if call.state is not None and call.one_query:
            real = call.state.real[:, None]
        if call.one_query:
            at = call.at
            short = n_pg * sp.block < sp.dense_len  # every sequence is short
            if short:
                with jax.named_scope("hetu.attn.full"):
                    o, kc, vc = ops.decode_layer_attention(
                        q, k, v, kc, vc, cl, at, scale=self.scale)
                sparse = jnp.zeros((b,), bool)
                n = jnp.zeros((b,), jnp.int32)
            else:
                kc, vc = kc.write(cl, k), vc.write(cl, v)
            with jax.named_scope("hetu.sparse.compress"):
                # the one window the round's row may complete: its rows from
                # the pool where they lie, the new one among them
                rows = at[:, None] - sp.kernel + 1 + jnp.arange(sp.kernel)
                pages = jnp.take_along_axis(
                    kc.tables, jnp.clip(rows // sp.block, 0, n_pg - 1), 1)
                new = ops.compress_keys(
                    kc.pool[cl, pages, rows % sp.block], stride=sp.kernel,
                    kernel=sp.kernel)
                kc = kc.write_comp(
                    cl, new, (rows[:, :1] // sp.stride),
                    (rows[:, :1] >= 0) & (rows[:, :1] % sp.stride == 0))
            if not short:
                with jax.named_scope("hetu.sparse.select"):
                    idx, n = ops.select_blocks(
                        q, kc.read_comp(cl), at[:, None], scale=self.scale,
                        **sp.how)
                    n, sparse = n[:, 0], at + 1 >= sp.dense_len
                with jax.named_scope("hetu.sparse.attend"):
                    o = ops.chosen_pages_attention(
                        q, kc, vc, cl, idx[:, 0], n, at, sparse,
                        dense_blocks=-(-sp.dense_len // sp.block),
                        scale=self.scale)
            self._count(call, real, sparse[:, None], n[:, None],
                        at[:, None], walked=True)
            call.k[grp], call.v[grp] = kc, vc
            return o
        # a chunk: the view with the new rows in it, as a full layer's
        pos = call.at[:, None] + jnp.arange(s)[None]
        if call.last is not None:
            real = jnp.arange(s)[None] <= call.last
        k_view, v_view = kc.read(cl), vc.read(cl)
        t = k_view.shape[1]
        k_view, v_view = ops.cache_update(
            k_view.reshape(b, t, -1), v_view.reshape(b, t, -1),
            k.reshape(b, s, -1), v.reshape(b, s, -1), call.at)
        with jax.named_scope("hetu.sparse.compress"):
            # the windows that END in the chunk's rows: the first of them
            # starts up to ``kernel - 1`` rows before the chunk
            m = sp.kernel // sp.stride
            first = jnp.maximum((call.at - sp.kernel) // sp.stride + 1, 0)
            span = sp.stride * first[:, None] + jnp.arange(
                sp.stride * (s // sp.stride + m))[None]
            new = ops.compress_keys(
                jnp.take_along_axis(
                    k_view, jnp.minimum(span, t - 1)[..., None], 1),
                stride=sp.stride, kernel=sp.kernel)
            index = first[:, None] + jnp.arange(new.shape[1])[None]
            end = call.at + (s - 1 if call.last is None else call.last)
            kc = kc.write_comp(
                cl, new, index,
                sp.stride * index + sp.kernel - 1 <= end[:, None])
            comp = kc.read_comp(cl)
        own = jnp.asarray(call.prompt_len).reshape(-1)
        heads = (g, d)

        def dense(_):
            with jax.named_scope("hetu.attn.full"):
                return call.attention(q, k_view, v_view, None)

        def choose(_):
            return self._masked(
                q, comp, pos, k_view.reshape((b, t) + heads),
                v_view.reshape((b, t) + heads),
                (own < sp.dense_len)[:, None, None, None])

        o = jax.lax.cond(jnp.all(own < sp.dense_len), dense, choose, None)
        self._count(call, real, jnp.broadcast_to(
            (own >= sp.dense_len)[:, None], (b, s)),
            jnp.minimum(sp.topk, pos // sp.block + 1), pos,
            kernel=not ops.sparse_kernel_why(s, t, sp.block))
        call.k[grp], call.v[grp] = kc.write(cl, k), vc.write(cl, v)
        return o

    def _layer(self, p, l: int, h, call: LayerCall):
        """Layer ``l`` over ``h`` [B, S, H] in the call ``call``, ``p`` the
        stacked leaves of every layer.  Returns (out, the expert layer's
        counts [4] int32, zeros on a dense layer)."""
        by = self.multipliers.get("branch")
        u, mix = self._read(p, l, 0, h)
        op = self._operator(p, l, self._norm(u, p["attn_norm"][l]), call)
        h = self._write(h, op if by is None else op * by, mix)
        u, mix = self._read(p, l, 1, h)
        u = self._norm(u, p["ffn_norm"][l])
        if l < self.c.first_dense:
            f = self._ffn(p["ffn"], l, u)
            return self._write(h, f if by is None else f * by, mix), \
                jnp.zeros((4,), jnp.int32)
        moe, e = p["moe"], l - self.c.first_dense
        # the router's leaves are this layer's; a router with no correction
        # bias has no such leaf
        own = {name: moe[name][e] for name in ("router", "router_bias")
               if name in moe}
        m, stats = self.moe.apply(dict(moe, **own), u, layer=e)
        return self._write(h, m, mix), stats

    # ---- the residual seam: what a sublayer reads of the stream, and how
    # its result goes back.  Here the plain add, which puts nothing into a
    # program; a model whose stream is several rows a token states its own
    # pair (``models/glm5_next.py``: hyper-connections) ----
    def _read(self, p, l: int, sub: int, h):
        """What sublayer ``sub`` (0 the operator, 1 the feed-forward) of
        layer ``l`` reads of the stream ``h`` BEFORE its norm, and what
        :meth:`_write` needs of the read: the stream itself, and nothing."""
        return h, None

    def _write(self, h, y, mix):
        """The stream after a sublayer added ``y`` (its result, the model's
        ``branch`` factor already in it); ``mix`` is :meth:`_read`'s."""
        return h + y

    def _embed(self, p, ids):
        h = ops.embedding_lookup(p["tok_emb"], ids).astype(self.c.dtype)
        return h * self.multipliers["embed"] if "embed" in self.multipliers \
            else h

    def _head(self, p, h):
        """h: the stream after the last layer -> logits; the head tied to
        the embedding where the weights have no ``lm_head``."""
        logits = ops.linear(self._norm(h, p["norm_f"]),
                            p.get("lm_head", p["tok_emb"]).T.astype(
                                self.c.dtype))
        return logits * self.multipliers["head"] \
            if "head" in self.multipliers else logits

    # ---- dense forward ----
    def hidden_states(self, variables, input_ids, *, train: bool = False,
                      rng=None, prompt_len=None):
        """``prompt_len`` [B] (a model with sparse layers): the rows'
        first ``prompt_len`` tokens are a prompt, the rest were generated a
        token a call; None: the rows are prompts whole."""
        p = variables["params"]
        c = self.c
        b, s = input_ids.shape
        h = self._embed(p, input_ids)
        pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        call = LayerCall(*self.rope_at(pos), prompt_len=prompt_len)
        for l in range(c.num_layers):
            h, _ = self._layer(p["layers"], l, h, call)
        return h

    def apply(self, variables, input_ids, *, train: bool = False, rng=None,
              **how):
        h = self.hidden_states(variables, input_ids, train=train, rng=rng,
                               **how)
        return self._head(variables["params"], h), {}

    # ---- serving (hetu_tpu/serve): prefill in chunks / decode ----
    # k_cache and v_cache are each the cache layers of the model's one group,
    # or a tuple of them, one a group of its ``kv_cache_spec()`` in order,
    # with ``read(layer)`` -> [B, T, kv_heads, D] and ``write(layer, rows)``;
    # a window group's view is a ring.  Both entry points of a model with
    # an expert layer return a fourth value, the expert layers' counts
    # (``step_stats`` names them) summed over the layers; a model without
    # one states no ``step_stats`` and returns none.

    def _cached(self, p, input_ids, k_cache, v_cache, pos, attention,
                one_query: bool = False, state=None, last=None,
                prompt_len=None):
        """Both cache entry points: every layer in a :class:`LayerCall` over
        the cache layers (a group's pair, or one pair bare where the model's
        cache has one group) from each sequence's first position
        ``pos[:, 0]`` on, ``attention`` the call's step.  Returns (the
        stream, new_k, new_v), the counts of a model with an expert layer
        and, given ``state``, the state last."""
        h = self._embed(p, input_ids)
        bare = not isinstance(k_cache, (tuple, list))
        call = LayerCall(
            *self.rope_at(pos), k=[k_cache] if bare else list(k_cache),
            v=[v_cache] if bare else list(v_cache), at=pos[:, 0],
            attention=attention, one_query=one_query, state=state, last=last,
            prompt_len=prompt_len,
            counts=jnp.zeros((len(self.call_stats),), jnp.int32)
            if self.call_stats else None)
        stats = jnp.zeros((4,), jnp.int32)
        for l in range(self.c.num_layers):
            h, n = self._layer(p["layers"], l, h, call)
            stats = stats + n
        k_cache, v_cache = (call.k[0], call.v[0]) if bare \
            else (tuple(call.k), tuple(call.v))
        out = (h, k_cache, v_cache)
        if self.moe is not None:
            counts = self._counts(stats)
            out += (counts if call.counts is None
                    else jnp.concatenate([counts, call.counts]),)
        elif call.counts is not None:
            out += (call.counts,)
        return out if state is None else out + (call.state,)

    def _counts(self, stats):
        """The counts a cache entry point returns, from the expert layers'
        sum ``stats`` [4]: here as they are (``step_stats`` names them)."""
        return stats

    def prefill_chunk_with_cache(self, variables, input_ids, k_cache,
                                 v_cache, start, *, last_index=None,
                                 state=None, prompt_len=None):
        """input_ids [B, S_c] at absolute positions ``start..``; positions
        below ``start`` of the caches are written.  Returns (logits [B, V]
        at chunk-relative ``last_index``, new_k, new_v, counts where the
        model has an expert layer or sparse layers), and with
        ``state`` (a model with state layers) the state after
        ``last_index`` behind them.  ``prompt_len`` [B] (a model with sparse
        layers): the length of the prompt the chunk is a part of."""
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

        h, *rest = self._cached(p, input_ids, k_cache, v_cache, pos,
                                attention, state=state, last=last_index,
                                prompt_len=prompt_len)
        idx = s - 1 if last_index is None else last_index
        h = jax.lax.dynamic_index_in_dim(h, idx, axis=1, keepdims=False)
        return (self._head(p, h), *rest)

    def decode_with_cache(self, variables, input_ids, k_cache, v_cache,
                          lengths, *, state=None):
        """One decode step; input_ids [B], lengths [B] tokens cached.
        Returns (logits [B, V], new_k, new_v, counts where the model has an
        expert layer), and with ``state`` the new state behind them."""
        p = variables["params"]

        def attention(q, k_view, v_view, window):
            return ops.decode_attention(
                q, k_view, v_view, lengths, scale=self.scale, window=window,
                kv_heads=self.c.num_kv_heads)

        h, *rest = self._cached(
            p, input_ids[:, None], k_cache, v_cache, lengths[:, None],
            attention, one_query=True, state=state)
        return (self._head(p, h[:, 0]), *rest)

    # ---- training (test size) ----
    def lm_loss_fn(self):
        """Next-token loss; batch = (input_ids,)."""
        def fn(params, model_state, batch, rng, train):
            ids = batch[0] if isinstance(batch, (tuple, list)) else batch
            logits, _ = self.apply({"params": params, "state": {}}, ids,
                                   train=train, rng=rng)
            per = ops.softmax_cross_entropy_sparse(logits[:, :-1], ids[:, 1:])
            return jnp.mean(per), ({}, model_state)
        return fn
