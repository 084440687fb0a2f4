"""LongCat-Flash's language model: double layers with a shortcut expert
layer, identity experts behind one wide router, latent attention.

Source of the shapes: ``huggingface.co/meituan-longcat/LongCat-Flash-Omni``
``config.json`` (the language model of it; the audio and vision encoders and
the codec decoder are not here).  One DOUBLE layer, with ``N`` RMSNorm (each
its own weight), ``A0``/``A1`` latent attention, ``F0``/``F1`` dense SwiGLU
and ``M`` the expert layer::

    h1 = h + A0(N(h));  u = N(h1);  m = M(u);  h2 = h1 + F0(u)
    h3 = h2 + A1(N(h2));  out = h3 + F1(N(h3)) + m

The expert layer reads the first half and is added at the end of the second
(the shortcut): its matmuls depend on nothing ``F0``, ``A1`` or ``F1`` make.

**The expert layer** (:class:`HeldExpertLayer`, ``layers/moe.py``) is told
which experts it holds, ``held = (first, count)`` of the published
``n_routed_experts``; it routes over all of them and the identity experts
behind them (a router ``n_routed_experts + zero_expert_num`` wide, in
float32), computes its own experts' part and the identity part, and leaves
out what absent experts would add.  No capacity, no drops.

**Latent attention** caches, per token and per attention block, one
normalised latent ``c`` (``kv_lora_rank`` wide) and one rotated key ``k_r``
(``qk_rope_head_dim`` wide) shared by every head: the K pool holds ``c``, the
V pool ``k_r`` (:meth:`LongcatFlashModel.kv_cache_spec`; two cache layers a
double layer).  Chunked prefill runs the EXPANDED form (keys and values of
all heads rebuilt from the gathered latents, a block of keys at a time under
a running softmax, as far as the chunk's last token can see);
decode the ABSORBED form (``q W_kvb^K`` attends over the latents directly and
the result is re-expanded by ``W_kvb^V``; nothing heads-wide is built from
the cache).  One path a phase, no switch.

Weights are made in ``param_dtype`` directly (:meth:`LongcatFlashModel.init`
fills each stacked leaf one slice at a time, so no float32 twin of a stacked
leaf is ever live) and nothing on the serving path widens a weight leaf: the
casts to the compute type below are no-ops when the two types agree.

``jax.named_scope``s mark the sub-layers in the jitted programs
(``hetu.mla.prefill``, ``hetu.mla.decode``, ``hetu.ffn.dense``,
``hetu.moe.route``, ``hetu.moe.experts``, ``hetu.moe.zero``).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from hetu_tpu import ops
from hetu_tpu.layers.base import (
    HELD_TRANSPOSED, Module, held_transposed, linear_held,
)
from hetu_tpu.layers.moe import MOE_STATS, HeldExpertLayer
from hetu_tpu.models.block import with_grouped
from hetu_tpu.ops.pallas_kernels.flash_attention import (
    flash_chunk_attention, unwritten, write_rows,
)


# A chunk's expanded attention rebuilds K and V of all heads for the flash
# kernel when a key block's float32 scores, heads x queries x keys, are at
# least this many.  The rebuild costs the same a block of 1,024 keys whatever
# the chunk, the walk's softmax passes 1.2 ms at 512 queries of 64 heads, 0.65
# at 256 and 0.38 at 128; the line was drawn when the rebuild still cost 0.9
# ms a block and has not been measured again at its 0.2 (my chip runs, PR 52:
# PERF.md section 6, ROADMAP S9 (b)).
REBUILD_MIN_SCORES = 1 << 25


@dataclass
class LongcatFlashConfig:
    vocab_size: int = 131072
    hidden_size: int = 6144
    num_layers: int = 28                 # DOUBLE layers
    num_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    ffn_size: int = 12288                # the dense SwiGLU
    expert_ffn_size: int = 2048
    n_routed_experts: int = 512          # as published: the router's width
    zero_expert_num: int = 256           # identity experts behind them
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    held: Optional[tuple] = None         # (first, count); None: all of them
    rope_theta: float = 1e7
    rms_eps: float = 1e-5
    max_position: int = 131072
    dtype: object = jnp.bfloat16         # compute
    param_dtype: object = jnp.bfloat16
    init_std: float = 0.02
    router_init_std: float = 0.06
    router_bias_std: float = 2e-4
    expert_block_rows: int = 128
    attn_key_block: int = 1024           # keys walked at a time in prefill

    def __post_init__(self):
        if self.held is None:
            self.held = (0, self.n_routed_experts)
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.n_routed_experts:
            raise ValueError(f"held {self.held} is no share of "
                             f"{self.n_routed_experts} experts")
        self.held = (int(first), int(count))
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


class LatentAttention:
    """One latent-attention block's two forms over one set of weights
    ``p``: ``q_a`` [H, q_rank], ``q_a_norm``, ``q_b`` [q_rank, heads * (nope
    + rope)], ``kv_a`` [H, kv_rank + rope], ``kv_a_norm``, ``kv_b``
    [kv_rank, heads * (nope + v)], ``o`` [heads * v, H]."""

    def __init__(self, c: LongcatFlashConfig):
        self.c = c
        self.q_scale = math.sqrt(c.hidden_size / c.q_lora_rank)
        self.kv_scale = math.sqrt(c.hidden_size / c.kv_lora_rank)
        self.scale = c.qk_head_dim ** -0.5

    def rope_at(self, pos):
        """cos/sin [..., rope/2] float32 at absolute positions ``pos``."""
        d = self.c.qk_rope_head_dim
        inv = 1.0 / self.c.rope_theta ** (
            jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        ang = pos.astype(jnp.float32)[..., None] * inv
        return jnp.cos(ang), jnp.sin(ang)

    def _w(self, a):
        return a.astype(self.c.dtype)

    def project(self, p, x, cos, sin):
        """x [B, S, H]; cos/sin [B, S, rope/2] at each token's position ->
        (q_n [B, S, heads, nope], q_r [B, S, heads, rope] rotated,
        c [B, S, kv_rank] normalised and scaled, k_r [B, S, rope] rotated):
        ``c`` and ``k_r`` are what the cache holds."""
        cfg = self.c
        b, s, _ = x.shape
        q = ops.rms_norm(ops.linear(x, self._w(p["q_a"])), p["q_a_norm"],
                         eps=cfg.rms_eps) * self.q_scale
        q = linear_held(q.astype(cfg.dtype), p, "q_b", cfg.dtype).reshape(
            b, s, cfg.num_heads, cfg.qk_head_dim)
        q_n, q_r = q[..., :cfg.qk_nope_head_dim], q[..., cfg.qk_nope_head_dim:]
        kv = ops.linear(x, self._w(p["kv_a"]))
        c = ops.rms_norm(kv[..., :cfg.kv_lora_rank], p["kv_a_norm"],
                         eps=cfg.rms_eps) * self.kv_scale
        k_r = ops.apply_rope_interleaved(kv[..., cfg.kv_lora_rank:], cos, sin)
        q_r = ops.apply_rope_interleaved(q_r, cos[:, :, None], sin[:, :, None])
        return q_n, q_r, c.astype(cfg.dtype), k_r

    def _kv_b(self, p):
        cfg = self.c
        held = p.get("kv_b" + HELD_TRANSPOSED)
        if held is not None:
            return jnp.moveaxis(held.reshape(
                cfg.num_heads, cfg.qk_nope_head_dim + cfg.v_head_dim,
                cfg.kv_lora_rank), 2, 0)
        return self._w(p["kv_b"]).reshape(
            cfg.kv_lora_rank, cfg.num_heads,
            cfg.qk_nope_head_dim + cfg.v_head_dim)

    def _out(self, p, o):
        b, s = o.shape[:2]
        return ops.linear(o.reshape(b, s, -1).astype(self.c.dtype),
                          self._w(p["o"]))

    def expanded(self, p, q_n, q_r, c_all, r_all, q_pos, *,
                 static_trip: bool = False):
        """Expanded attention of queries at absolute positions ``q_pos``
        [B, S] (a chunk: row ``i`` at ``q_pos[b, 0] + i``) over latents
        ``c_all`` [B, T, kv_rank] and rotated keys ``r_all`` [B, T, rope]:
        key ``t`` is seen by a query at position ``>= t``.  The keys are
        walked ``attn_key_block`` at a time as far as the last key any query
        can see, so a chunk's cost follows its history and not the width of
        the table it was handed.  That trip count is read from ``q_pos``;
        under ``static_trip`` (reverse-mode differentiation needs a static
        one) every block of the table is walked.

        Two forms of one mathematics, chosen on what is observed here
        (``ops.chunk_kernel_why``; a ``chunk_attn.plan`` instant says which
        and why).  On a TPU backend with no mesh in context, not under
        ``static_trip``, and with queries enough that a block's scores are
        the larger cost (``REBUILD_MIN_SCORES``; else ``few_queries``): the
        walk only REBUILDS the keys and values of all heads from the
        latents, block by block into two head-major arrays, and one chunk
        call of the flash forward kernel attends over them at 192 | 128,
        its score tiles in VMEM.  Anywhere else the walk itself attends
        under a running softmax: the heads-wide history is never whole, but
        each block's float32 scores go through HBM.  Returns [B, S, H]."""
        cfg = self.c
        nope = cfg.qk_nope_head_dim
        b, s = q_pos.shape
        t = c_all.shape[1]
        kb = min(cfg.attn_key_block, t)
        blocks = -(-t // kb)
        kv_b = self._kv_b(p)
        heads = cfg.num_heads
        trips = blocks if static_trip else jnp.minimum(
            jnp.max(q_pos) // kb + 1, blocks)
        why = "static_trip" if static_trip else ops.chunk_kernel_why() \
            or ("" if heads * s * kb >= REBUILD_MIN_SCORES else "few_queries")
        ops.chunk_plan(jax.ShapeDtypeStruct(
            (b, heads, s, cfg.qk_head_dim), cfg.dtype), t, heads,
            cfg.v_head_dim, why)

        if not why:
            # a head's rebuilt keys lie [key, width] as the kernel reads
            # them; left to itself the compiler lays the products keys-minor
            # and relays both arrays whole after the walk
            rows_major = Layout(major_to_minor=(0, 1, 2, 3))
            # keys and queries padded with zeros to whole lane tiles (192 ->
            # 256: what a tiled array holds and the MXU multiplies anyway),
            # so that a block's rows can be put in place by a DMA
            lanes = -cfg.qk_head_dim % 128

            def fill(j, kv_all):
                at = jnp.minimum(j * kb, t - kb)
                c_blk = jax.lax.dynamic_slice_in_dim(c_all, at, kb, 1)
                r_blk = jax.lax.dynamic_slice_in_dim(r_all, at, kb, 1)
                k_n, v_blk = (with_layout_constraint(
                    jnp.einsum("btc,chd->bhtd", c_blk, part), rows_major)
                    for part in (kv_b[..., :nope], kv_b[..., nope:]))
                k_blk = jnp.concatenate([k_n, jnp.broadcast_to(
                    r_blk[:, None], (b, heads) + r_blk.shape[1:]),
                    jnp.zeros((b, heads, kb, lanes), cfg.dtype)], -1)
                return tuple(
                    write_rows(whole, blk, at, multiple_of=math.gcd(kb, t))
                    for whole, blk in zip(kv_all, (k_blk, v_blk)))

            # nobody writes the rows past the walk's end and the kernel
            # fetches none of them: its K blocks lie inside the walk's, and
            # a block it does not step over is not fetched.  The table's
            # padding to whole blocks it may fetch (masked: zeros)
            k_all, v_all = (unwritten((b, heads, blocks * kb, d), cfg.dtype)
                            for d in (cfg.qk_head_dim + lanes, cfg.v_head_dim))
            if blocks * kb > t:
                k_all, v_all = (x.at[:, :, t:].set(0) for x in (k_all, v_all))
            k_all, v_all = jax.lax.fori_loop(0, trips, fill, (k_all, v_all))
            out = flash_chunk_attention(
                jnp.moveaxis(jnp.concatenate([q_n, q_r, jnp.zeros(
                    q_n.shape[:3] + (lanes,), cfg.dtype)], -1), 1, 2),
                k_all, v_all, q_pos[:, 0], scale=self.scale,
                block_k=math.gcd(kb, 512), head_major=True)
            return self._out(p, jnp.moveaxis(out, 1, 2))

        def block(j, carry):
            m, l, acc = carry
            # the last block is moved back to end with the table; the keys
            # it then shares with the block before are masked out of it
            at = jnp.minimum(j * kb, t - kb)
            c_blk = jax.lax.dynamic_slice_in_dim(c_all, at, kb, 1)
            r_blk = jax.lax.dynamic_slice_in_dim(r_all, at, kb, 1)
            key = at + jnp.arange(kb)
            allowed = ((key[None, None, None, :] <= q_pos[:, None, :, None])
                       & (key >= j * kb)[None, None, None, :])
            kv = jnp.einsum("btc,chd->bthd", c_blk, kv_b)
            scores = (jnp.einsum("bshd,bthd->bhst", q_n, kv[..., :nope],
                                 preferred_element_type=jnp.float32)
                      + jnp.einsum("bshr,btr->bhst", q_r, r_blk,
                                   preferred_element_type=jnp.float32))
            scores = jnp.where(allowed, scores * self.scale, -1e30)
            m_new = jnp.maximum(m, scores.max(-1))
            alpha = jnp.exp(m - m_new)
            probs = jnp.where(allowed, jnp.exp(scores - m_new[..., None]), 0.0)
            l = l * alpha + probs.sum(-1)
            acc = acc * jnp.moveaxis(alpha, 1, 2)[..., None] + jnp.einsum(
                "bhst,bthd->bshd", probs.astype(cfg.dtype), kv[..., nope:],
                preferred_element_type=jnp.float32)
            return m_new, l, acc

        carry = (jnp.full((b, heads, s), -1e30, jnp.float32),
                 jnp.zeros((b, heads, s), jnp.float32),
                 jnp.zeros((b, s, heads, cfg.v_head_dim), jnp.float32))
        _, l, acc = jax.lax.fori_loop(0, trips, block, carry)
        return self._out(p, acc / jnp.moveaxis(l, 1, 2)[..., None])

    def absorbed(self, p, q_n, q_r, c_all, r_all, lengths):
        """Absorbed attention of ONE query a sequence (q_n/q_r [B, 1, heads,
        .]) at position ``lengths[b]`` over the cached latents [B, T,
        kv_rank] and rotated keys [B, T, rope], its own row included:
        ``q_n W_kvb^K`` attends over the latents and the result is
        re-expanded by ``W_kvb^V``.  Returns [B, 1, H]."""
        cfg = self.c
        nope = cfg.qk_nope_head_dim
        kv_b = self._kv_b(p)
        q_lat = jnp.einsum("bhd,chd->bhc", q_n[:, 0], kv_b[..., :nope])
        scores = (jnp.einsum("bhc,btc->bht", q_lat, c_all,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bhr,btr->bht", q_r[:, 0], r_all,
                               preferred_element_type=jnp.float32))
        allowed = (jnp.arange(c_all.shape[1])[None, None, :]
                   <= lengths[:, None, None])
        probs = jax.nn.softmax(
            jnp.where(allowed, scores * self.scale, -1e30), axis=-1)
        o_lat = jnp.einsum("bht,btc->bhc", probs.astype(cfg.dtype), c_all)
        o = jnp.einsum("bhc,chd->bhd", o_lat, kv_b[..., nope:])
        return self._out(p, o[:, None])


class LongcatFlashModel(Module):
    """Scan-stacked double layers.  Parameter leaves are stacked over the
    layers, and the two attention blocks / dense FFNs of a double layer
    over a second axis of 2."""

    # what the fourth value of the two cache entry points counts, in order:
    # the expert layers' counts and the held pairs that the walk's grouped
    # calls computed (``models.block.with_grouped``)
    step_stats = MOE_STATS + ("moe_grouped",)

    def __init__(self, config: LongcatFlashConfig):
        self.c = config
        self.attn = LatentAttention(config)
        self.moe = HeldExpertLayer(
            n_routed=config.n_routed_experts, n_zero=config.zero_expert_num,
            k=config.moe_topk, scaling=config.routed_scaling_factor,
            held=config.held, block_rows=config.expert_block_rows,
            dtype=config.dtype)

    # ---- the weights as a server holds them ----
    def serving_params(self, params):
        """``q_b`` and ``kv_b`` transposed: the double layers are scanned
        and both products' results are read head by head (192 and 256
        wide), so the compiler contracts over each leaf's minor axis and
        relaid both whole leaves once a call (``layers/base.py``
        ``Module.serving_params``).  ``q_b`` split by head as its result is
        read; ``kv_b`` left whole: split ``[heads, 256, kv_rank]``, of which
        the absorbed form reads the two halves of the 256, the compiler
        relaid it whole again (a compile for a described v5e, PR 45)."""
        c = self.c
        attn = held_transposed(
            params["layers"]["attn"], q_b=(c.num_heads, c.qk_head_dim),
            kv_b=None)
        return dict(params, layers=dict(params["layers"], attn=attn))

    # ---- the cache this model asks of the serving engine ----
    def kv_cache_spec(self):
        from hetu_tpu.serve.kv_cache import KVCacheSpec

        c = self.c
        return KVCacheSpec(num_layers=2 * c.num_layers, num_kv_heads=1,
                           head_dim=c.kv_lora_rank, dtype=c.dtype,
                           v_head_dim=c.qk_rope_head_dim)

    # ---- weights ----
    def init(self, key):
        """Every leaf in ``param_dtype`` (the router in float32), each
        stacked leaf filled one slice at a time: the float32 draw of a
        slice is the only wide temporary, never a twin of the whole leaf."""
        c = self.c
        pd = c.param_dtype
        L, H, E = c.num_layers, c.hidden_size, c.held[1]
        heads = c.num_heads
        router_w = c.n_routed_experts + c.zero_expert_num

        def draw(k, lead: tuple, shape: tuple, std, dtype=pd):
            n = math.prod(lead)
            out = jax.lax.map(
                lambda kk: (jax.random.normal(kk, shape, jnp.float32)
                            * std).astype(dtype),
                jax.random.split(k, n))
            return out.reshape(lead + shape)

        def ones(*shape):
            return jnp.ones(shape, pd)

        ks = iter(jax.random.split(key, 16))
        std = c.init_std
        layers = {
            "attn_norm": ones(L, 2, H),
            "ffn_norm": ones(L, 2, H),
            "attn": {
                "q_a": draw(next(ks), (L, 2), (H, c.q_lora_rank), std),
                "q_a_norm": ones(L, 2, c.q_lora_rank),
                "q_b": draw(next(ks), (L, 2),
                            (c.q_lora_rank, heads * c.qk_head_dim), std),
                "kv_a": draw(next(ks), (L, 2),
                             (H, c.kv_lora_rank + c.qk_rope_head_dim), std),
                "kv_a_norm": ones(L, 2, c.kv_lora_rank),
                "kv_b": draw(next(ks), (L, 2), (
                    c.kv_lora_rank,
                    heads * (c.qk_nope_head_dim + c.v_head_dim)), std),
                "o": draw(next(ks), (L, 2), (heads * c.v_head_dim, H), std),
            },
            "ffn": {
                "gate": draw(next(ks), (L, 2), (H, c.ffn_size), std),
                "up": draw(next(ks), (L, 2), (H, c.ffn_size), std),
                "down": draw(next(ks), (L, 2), (c.ffn_size, H), std),
            },
            "moe": {
                "router": draw(next(ks), (L,), (H, router_w),
                               c.router_init_std, jnp.float32),
                "router_bias": draw(next(ks), (L,), (router_w,),
                                    c.router_bias_std, jnp.float32),
                "gate": draw(next(ks), (L, E), (H, c.expert_ffn_size), std),
                "up": draw(next(ks), (L, E), (H, c.expert_ffn_size), std),
                "down": draw(next(ks), (L, E), (c.expert_ffn_size, H), std),
            },
        }
        return {"params": {
            "tok_emb": draw(next(ks), (), (c.vocab_size, H), std),
            "lm_head": draw(next(ks), (), (c.vocab_size, H), std),
            "norm_f": ones(H),
            "layers": layers,
        }, "state": {}}

    # ---- pieces of a double layer ----
    def _norm(self, x, scale):
        return ops.rms_norm(x, scale, eps=self.c.rms_eps)

    def _ffn(self, p, l, i: int, x):
        dt = self.c.dtype
        with jax.named_scope("hetu.ffn.dense"):
            g = ops.linear(x, p["gate"][l, i].astype(dt))
            u = ops.linear(x, p["up"][l, i].astype(dt))
            return ops.linear(ops.silu(g) * u, p["down"][l, i].astype(dt))

    def _double_layer(self, p, l, h, attend):
        """Double layer ``l`` over ``h`` [B, S, H].  ``p`` are the stacked
        leaves of EVERY layer, each read at ``[l, ...]`` where it is used:
        handed to the scan a layer at a time instead, a layer's slice of a
        leaf that two sub-layers share, or that the loop over row blocks
        reads, is copied whole every step (1.2 GB a layer of each at the
        published widths).  ``attend(i, x)`` is attention block ``i`` (0 or
        1) of this layer on the normed input, in whatever form the phase
        uses.  Returns (out, expert-layer counts [4] int32)."""
        h1 = h + attend(0, self._norm(h, p["attn_norm"][l, 0]))
        u = self._norm(h1, p["ffn_norm"][l, 0])
        moe = p["moe"]
        m, stats = self.moe.apply(
            dict(moe, router=moe["router"][l],
                 router_bias=moe["router_bias"][l]),
            u, layer=l)
        h2 = h1 + self._ffn(p["ffn"], l, 0, u)
        h3 = h2 + attend(1, self._norm(h2, p["attn_norm"][l, 1]))
        out = h3 + self._ffn(p["ffn"], l, 1,
                             self._norm(h3, p["ffn_norm"][l, 1])) + m
        return out, stats

    @staticmethod
    def _block(p_attn, l, i: int):
        return jax.tree_util.tree_map(lambda a: a[l, i], p_attn)

    def _embed(self, p, ids):
        return ops.embedding_lookup(p["tok_emb"], ids).astype(self.c.dtype)

    def _head(self, p, h):
        return ops.linear(h, p["lm_head"].T.astype(self.c.dtype))

    # ---- dense forward ----
    def hidden_states(self, variables, input_ids, *, train: bool = False,
                      rng=None):
        p = variables["params"]
        b, s = input_ids.shape
        h = self._embed(p, input_ids)
        pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        cos, sin = self.attn.rope_at(pos)

        layers = p["layers"]

        def layer(h, l):
            def attend(i, x):
                with jax.named_scope("hetu.mla.prefill"):
                    pa = self._block(layers["attn"], l, i)
                    q_n, q_r, c, k_r = self.attn.project(pa, x, cos, sin)
                    return self.attn.expanded(pa, q_n, q_r, c, k_r, pos,
                                              static_trip=train)
            out, _ = self._double_layer(layers, l, h, attend)
            return out, None

        h, _ = jax.lax.scan(layer, h, jnp.arange(self.c.num_layers))
        return self._norm(h, p["norm_f"])

    def apply(self, variables, input_ids, *, train: bool = False, rng=None):
        h = self.hidden_states(variables, input_ids, train=train, rng=rng)
        return self._head(variables["params"], h), {}

    # ---- serving (hetu_tpu/serve): latent-cache prefill / decode ----
    # k_cache [2L, B, T, 1, kv_rank] holds the latents, v_cache [2L, B, T, 1,
    # rope] the rotated shared keys (or whatever ``ops.read_cache_layer``
    # reads such layers from: the paged engine's pools with their page
    # tables and write map); cache layer 2l + i is attention block i of
    # double layer l.  Both entry points return a fourth value, the
    # expert layers' counts (``step_stats`` names them) summed over the
    # layers.

    def _cached(self, p, input_ids, k_cache, v_cache, pos, attend_over):
        """Both cache entry points: the double layers scanned with the two
        caches CARRIED (as scan inputs and outputs they would be held twice
        and rewritten whole).  An attention block reads its own cache layer
        of each (``ops.read_cache_layer``: of the paged engine's pools, that
        layer's pages and no more), writes its new rows [B, S, w] into those
        views from each sequence's first position ``pos[:, 0]`` on, attends
        over them (``attend_over(pa, q_n, q_r, c_all, r_all)``, the phase's
        form) and puts the new rows back (``ops.write_cache_layer``)."""
        h = self._embed(p, input_ids)
        cos, sin = self.attn.rope_at(pos)
        at, s = pos[:, 0], pos.shape[1]

        layers = p["layers"]

        def layer(carry, l):
            h, k_all, v_all = carry
            caches = [k_all, v_all]

            def attend(i, x):
                pa = self._block(layers["attn"], l, i)
                q_n, q_r, c, k_r = self.attn.project(pa, x, cos, sin)
                cl = 2 * l + i
                # the views without their one-head axis: written to with it,
                # a view's (1, width) minor pair is tiled two rows deep and
                # copied whole before the attention can read it
                c_all, r_all = ops.cache_update(
                    ops.read_cache_layer(caches[0], cl)[:, :, 0],
                    ops.read_cache_layer(caches[1], cl)[:, :, 0],
                    c, k_r.astype(self.c.dtype), at)
                caches[0] = ops.write_cache_layer(caches[0], cl, c_all, at, s)
                caches[1] = ops.write_cache_layer(caches[1], cl, r_all, at, s)
                return attend_over(pa, q_n, q_r, c_all, r_all)

            out, stats = self._double_layer(layers, l, h, attend)
            return (out, caches[0], caches[1]), stats

        (h, k_cache, v_cache), stats = jax.lax.scan(
            layer, (h, k_cache, v_cache), jnp.arange(self.c.num_layers))
        return (self._norm(h, p["norm_f"]), k_cache, v_cache,
                with_grouped(self.c, stats.sum(0)))

    def prefill_chunk_with_cache(self, variables, input_ids, k_cache,
                                 v_cache, start, *, last_index=None):
        """input_ids [B, S_c] at absolute positions ``start..``; positions
        below ``start`` of the caches are written.  Returns (logits [B, V]
        at chunk-relative ``last_index``, new_k, new_v, counts)."""
        p = variables["params"]
        b, s = input_ids.shape
        pos = start + jnp.broadcast_to(jnp.arange(s)[None], (b, s))

        def attend_over(pa, q_n, q_r, c_all, r_all):
            with jax.named_scope("hetu.mla.prefill"):
                return self.attn.expanded(pa, q_n, q_r, c_all, r_all, pos)

        h, k_cache, v_cache, stats = self._cached(
            p, input_ids, k_cache, v_cache, pos, attend_over)
        idx = s - 1 if last_index is None else last_index
        h = jax.lax.dynamic_index_in_dim(h, idx, axis=1, keepdims=False)
        return self._head(p, h), k_cache, v_cache, stats

    def decode_with_cache(self, variables, input_ids, k_cache, v_cache,
                          lengths):
        """One decode step; input_ids [B], lengths [B] tokens cached.
        Returns (logits [B, V], new_k, new_v, counts)."""
        p = variables["params"]

        def attend_over(pa, q_n, q_r, c_all, r_all):
            with jax.named_scope("hetu.mla.decode"):
                return self.attn.absorbed(pa, q_n, q_r, c_all, r_all, lengths)

        h, k_cache, v_cache, stats = self._cached(
            p, input_ids[:, None], k_cache, v_cache, lengths[:, None],
            attend_over)
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

