"""The ``deepseek_v3`` block, trained: latent attention without a query
rank through the flash kernels, one leading dense layer, then expert layers
whose router is steered by a correction bias that is STATE, not a parameter.

Source of the shapes: ``huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601``
``config.json`` (``model_type`` ``deepseek_v3``, ``q_lora_rank`` null,
``topk_method`` ``noaux_tc``, ``n_group`` 1).  One layer, ``N`` RMSNorm (each
its own weight), no projection has a bias::

    h1 = h + MLA(N(h));   h2 = h1 + FFN(N(h1))
    MLA(x):  q = W_q x -> heads x [q_n (nope), q_r (rope)]
             [c (kv_rank), k_r (rope)] = W_kva x
             [k_n (nope), v (v_dim)] per head = W_kvb N(c)
             q_r, k_r rotated pairwise (theta, no scaling); k_r shared by
             every head; k = [k_n, k_r]
             o = W_o concat_heads(softmax(q k^T / sqrt(nope + rope)) v)
    FFN:     SwiGLU ``ffn_size`` in the ``first_dense`` leading layers; after
             them  Shared(u) + sum over the chosen HELD experts w_i E_i(u)
    router:  s = sigmoid(float32(u) W_r) over all ``n_routed_experts``;
             chosen = top-k of s + b;  w_i = scaling * s_i / (sum of the
             chosen s_j + 1e-20)

Q and K are ``nope + rope`` wide (192) and V, O ``v_dim`` (128): the flash
kernels take the two widths as they are (``ops/pallas_kernels/flash_attention``),
no ``[S, S]`` tensor exists and V is not padded to the keys' width.

**The correction bias** ``b`` (float32 ``[n_routed_experts]`` a layer) is
model state, ``state["router_bias"]`` ``[expert layers, n_routed_experts]``,
zero at the start; an absent state reads as zeros.  It takes no gradient and
no weight decay.  Each step counts the choices ``c_e`` of every expert (all
of them, absent ones too: the router is whole on every chip) and returns
``b_e + gamma * sign(mean(c) - c_e)`` as the new state (DeepSeek-V3,
arXiv:2412.19437 section 2.1.2).  In a deployment the counts are summed over
the expert-parallel group first; here they are this chip's tokens'.

**One chip's share**: ``held = (first, count)`` of the routed experts are
here (``layers/moe.py`` ``HeldExpertLayer``, sigmoid, renormalised, shared
expert); what absent experts would add is left out.  The walk over the held
experts' pairs follows the load forward and backward
(``ops.moe_ops.held_expert_ffn``: experts that fit the grouped kernels, so
sorted rows through grouped matmuls, ``hetu.moe.gmm``, at any row count).

**Shared with** ``models/longcat_flash.py``: the rotary table
(``LatentAttention.rope_at``), the pairwise rotation, the ``W_kvb`` view and
the output projection, and with them the expanded and absorbed cache forms a
later serving section would use (inherited, not exercised here).  **Split**:
``project`` (no query rank, and none of LongCat's ``sqrt(hidden / rank)``
factors on ``q`` and ``c``) and the training attention (flash, heads-major).

Layers run under ``lax.scan`` with per-layer remat (``ops.remat``: a layer
keeps its input and the flash kernel's output and LSE rows, and recomputes
the rest, the held-expert walk included); a layer's leaves reach
the scan body as that layer's slice, so that the held-expert walk's
gradients are one layer's and not a stacked leaf's (``layer=`` reads in
place, and its cotangent is as large as the whole leaf).  Master weights are
``param_dtype`` (float32), made a slice at a time; matmuls run in ``dtype``.

``jax.named_scope``s: ``hetu.mla.train``, ``hetu.ffn.dense``,
``hetu.moe.route``, ``hetu.moe.experts``, ``hetu.moe.shared``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from hetu_tpu import ops
from hetu_tpu.layers.base import Module
from hetu_tpu.layers.moe import HeldExpertLayer
from hetu_tpu.models.longcat_flash import LatentAttention
from hetu_tpu.ops.moe_ops import held_expert_path

# the scalar ids of one step's expert layers, summed over the layers; the
# trainer puts the group on its ``train.moe`` instant
MOE_STEP_IDS = ("moe_held", "moe_absent", "moe_hit", "moe_blocks_fwd",
                "moe_blocks_bwd", "router_bias_absmax", "moe_grouped")


@dataclass
class DeepseekV3Config:
    vocab_size: int = 128256
    hidden_size: int = 2048
    num_layers: int = 48
    num_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    ffn_size: int = 6144                 # the leading dense layers' SwiGLU
    expert_ffn_size: int = 768
    n_shared_experts: int = 2            # side by side: one SwiGLU of 2 x 768
    first_dense: int = 1
    n_routed_experts: int = 128          # as published: the router's width
    moe_topk: int = 6
    routed_scaling_factor: float = 2.448
    held: Optional[tuple] = None         # (first, count); None: all of them
    bias_update_rate: float = 0.001      # gamma
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    max_position: int = 32768
    dtype: object = jnp.bfloat16         # compute
    param_dtype: object = jnp.float32    # master weights
    init_std: float = 0.02
    router_init_std: float = 0.02
    embedding_init_std: Optional[float] = None   # None: init_std
    expert_block_rows: int = 128
    attention_impl: str = "flash"        # 'xla': the composed oracle
    fused_ce: bool = True
    remat: bool = True
    ce_row_chunk: int = 2048

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
        if not 0 <= self.first_dense <= self.num_layers:
            raise ValueError(f"first_dense {self.first_dense} of "
                             f"{self.num_layers} layers")
        if self.attention_impl not in ("flash", "xla"):
            raise ValueError(f"attention_impl {self.attention_impl!r}: "
                             "'flash' or 'xla'")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


class DeepseekV3Attention(LatentAttention):
    """Latent attention as ``deepseek_v3`` has it with ``q_lora_rank`` null,
    over one layer's weights ``p``: ``q`` [H, heads * (nope + rope)],
    ``kv_a`` [H, kv_rank + rope], ``kv_a_norm``, ``kv_b`` [kv_rank, heads *
    (nope + v)], ``o`` [heads * v, H]."""

    def __init__(self, c: DeepseekV3Config):
        self.c = c
        self.scale = c.qk_head_dim ** -0.5

    def project(self, p, x, cos, sin):
        """x [B, S, H]; cos/sin [B, S, rope/2] -> (q_n [B, S, heads, nope],
        q_r [B, S, heads, rope] rotated, c [B, S, kv_rank] normalised,
        k_r [B, S, rope] rotated)."""
        cfg = self.c
        b, s, _ = x.shape
        q = ops.linear(x, self._w(p["q"])).reshape(
            b, s, cfg.num_heads, cfg.qk_head_dim)
        q_n, q_r = q[..., :cfg.qk_nope_head_dim], q[..., cfg.qk_nope_head_dim:]
        kv = ops.linear(x, self._w(p["kv_a"]))
        c = ops.rms_norm(kv[..., :cfg.kv_lora_rank], p["kv_a_norm"],
                         eps=cfg.rms_eps)
        k_r = ops.apply_rope_interleaved(kv[..., cfg.kv_lora_rank:], cos, sin)
        q_r = ops.apply_rope_interleaved(q_r, cos[:, :, None], sin[:, :, None])
        return q_n, q_r, c.astype(cfg.dtype), k_r

    def train(self, p, x, cos, sin):
        """Causal self-attention of x [B, S, H] over itself, every key and
        value of every head built once: [B, S, H]."""
        cfg = self.c
        nope = cfg.qk_nope_head_dim
        b, s, _ = x.shape
        q_n, q_r, c, k_r = self.project(p, x, cos, sin)
        kv = jnp.einsum("btc,chd->bhtd", c, self._kv_b(p))
        q = jnp.moveaxis(jnp.concatenate([q_n, q_r], -1), 1, 2)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(
                k_r[:, None], (b, cfg.num_heads, s, cfg.qk_rope_head_dim))],
            -1)
        v = kv[..., nope:]
        if cfg.attention_impl == "flash":
            from hetu_tpu.ops.pallas_kernels import flash_attention
            o = flash_attention(q, k, v, causal=True, scale=self.scale)
        else:
            o = ops.causal_attention(q, k, v, scale=self.scale)
        return self._out(p, jnp.moveaxis(o, 1, 2))


class DeepseekV3Model(Module):
    """``params``: ``tok_emb``, ``lm_head`` [V, H] (untied), ``norm_f``,
    ``dense`` {attn_norm, ffn_norm, attn, ffn {gate, up, down}} stacked over
    the ``first_dense`` leading layers, ``sparse`` {attn_norm, ffn_norm,
    attn, moe {router [H, n_routed] float32, gate/up [E, H, F], down [E, F,
    H], shared_gate/shared_up [H, F_s], shared_down [F_s, H]}} stacked over
    the expert layers.  ``state``: ``router_bias`` [expert layers,
    n_routed] float32."""

    def __init__(self, config: DeepseekV3Config):
        self.c = c = config
        self.attn = DeepseekV3Attention(c)
        self.moe = HeldExpertLayer(
            n_routed=c.n_routed_experts, n_zero=0, k=c.moe_topk,
            scaling=c.routed_scaling_factor, held=c.held,
            block_rows=c.expert_block_rows, dtype=c.dtype,
            scoring="sigmoid", renormalise=True, shared=True)

    @property
    def sparse_layers(self) -> int:
        return self.c.num_layers - self.c.first_dense

    # ---- weights ----
    def init(self, key):
        """Float32 masters, each stacked leaf filled one slice at a time."""
        c = self.c
        pd = c.param_dtype
        H, E, F = c.hidden_size, c.held[1], c.expert_ffn_size
        D, S = c.first_dense, self.sparse_layers
        Fs = c.n_shared_experts * F
        heads = c.num_heads

        def draw(k, lead: tuple, shape: tuple, std, dtype=pd):
            n = math.prod(lead)
            out = jax.lax.map(
                lambda kk: (jax.random.normal(kk, shape, jnp.float32)
                            * std).astype(dtype),
                jax.random.split(k, n))
            return out.reshape(lead + shape)

        def ones(*shape):
            return jnp.ones(shape, pd)

        ks = iter(jax.random.split(key, 32))
        std = c.init_std

        def attention(n):
            return {
                "q": draw(next(ks), (n,), (H, heads * c.qk_head_dim), std),
                "kv_a": draw(next(ks), (n,),
                             (H, c.kv_lora_rank + c.qk_rope_head_dim), std),
                "kv_a_norm": ones(n, c.kv_lora_rank),
                "kv_b": draw(next(ks), (n,), (
                    c.kv_lora_rank,
                    heads * (c.qk_nope_head_dim + c.v_head_dim)), std),
                "o": draw(next(ks), (n,), (heads * c.v_head_dim, H), std),
            }

        dense = {
            "attn_norm": ones(D, H), "ffn_norm": ones(D, H),
            "attn": attention(D),
            "ffn": {"gate": draw(next(ks), (D,), (H, c.ffn_size), std),
                    "up": draw(next(ks), (D,), (H, c.ffn_size), std),
                    "down": draw(next(ks), (D,), (c.ffn_size, H), std)},
        }
        sparse = {
            "attn_norm": ones(S, H), "ffn_norm": ones(S, H),
            "attn": attention(S),
            "moe": {
                "router": draw(next(ks), (S,), (H, c.n_routed_experts),
                               c.router_init_std, jnp.float32),
                "gate": draw(next(ks), (S, E), (H, F), std),
                "up": draw(next(ks), (S, E), (H, F), std),
                "down": draw(next(ks), (S, E), (F, H), std),
                "shared_gate": draw(next(ks), (S,), (H, Fs), std),
                "shared_up": draw(next(ks), (S,), (H, Fs), std),
                "shared_down": draw(next(ks), (S,), (Fs, H), std)},
        }
        return {"params": {
            "tok_emb": draw(next(ks), (), (c.vocab_size, H),
                            c.embedding_init_std or std),
            "lm_head": draw(next(ks), (), (c.vocab_size, H), std),
            "norm_f": ones(H),
            "dense": dense, "sparse": sparse,
        }, "state": {"router_bias": jnp.zeros(
            (S, c.n_routed_experts), jnp.float32)}}

    # ---- pieces of a layer ----
    def _norm(self, x, scale):
        return ops.rms_norm(x, scale, eps=self.c.rms_eps)

    def _attend(self, p, h, cos, sin):
        with jax.named_scope("hetu.mla.train"):
            return self.attn.train(p["attn"], self._norm(h, p["attn_norm"]),
                                   cos, sin)

    def _dense_layer(self, p, h, cos, sin):
        dt = self.c.dtype
        h = h + self._attend(p, h, cos, sin)
        u = self._norm(h, p["ffn_norm"])
        with jax.named_scope("hetu.ffn.dense"):
            f = p["ffn"]
            g = ops.linear(u, f["gate"].astype(dt))
            up = ops.linear(u, f["up"].astype(dt))
            return h + ops.linear(ops.silu(g) * up, f["down"].astype(dt))

    def _sparse_layer(self, p, bias, h, cos, sin):
        """One expert layer over h [B, S, H] with its correction bias
        [n_routed]: (out, choices of every expert [n_routed] int32,
        ``HeldExpertLayer``'s counts [4], blocks its walk takes)."""
        c, dt = self.c, self.c.dtype
        h = h + self._attend(p, h, cos, sin)
        u = self._norm(h, p["ffn_norm"])
        tokens = u.reshape(-1, u.shape[-1])
        # the walk reads an expert's weights once a block: cast them once
        moe = dict(p["moe"], router_bias=bias,
                   **{k: p["moe"][k].astype(dt)
                      for k in ("gate", "up", "down")})
        w, idx = self.moe.route(moe, tokens)
        chosen = jnp.zeros((c.n_routed_experts,), jnp.int32).at[
            idx.reshape(-1)].add(1)
        m, stats = self.moe.combine(moe, tokens, w, idx)
        first, count = c.held
        blocks = jnp.sum(-(-chosen[first:first + count]
                           // c.expert_block_rows))
        return (h + m.astype(dt).reshape(h.shape), chosen, stats, blocks)

    def next_bias(self, bias, chosen):
        """The correction bias after a step whose choices were ``chosen``
        [layers, n_routed]: an expert chosen more often than the mean is
        steered away from, one chosen less often toward."""
        load = chosen.astype(jnp.float32)
        mean = jnp.mean(load, axis=-1, keepdims=True)
        return bias + self.c.bias_update_rate * jnp.sign(mean - load)

    # ---- forward ----
    def hidden_states(self, variables, input_ids, *, train: bool = False,
                      rng=None):
        """(final hidden [B, S, H] before the last norm, per-step counts:
        ``chosen`` [expert layers, n_routed], ``stats`` [expert layers, 4],
        ``blocks`` [expert layers])."""
        c = self.c
        p = variables["params"]
        bias = (variables.get("state") or {}).get("router_bias")
        if bias is None:
            bias = jnp.zeros((self.sparse_layers, c.n_routed_experts),
                             jnp.float32)
        b, s = input_ids.shape
        h = ops.embedding_lookup(p["tok_emb"], input_ids).astype(c.dtype)
        pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        cos, sin = self.attn.rope_at(pos)

        def dense(h, p_l):
            return self._dense_layer(p_l, h, cos, sin), None

        def sparse(h, xs):
            out, *counts = self._sparse_layer(*xs, h, cos, sin)
            return out, tuple(counts)

        if c.remat:
            dense, sparse = ops.remat(dense), ops.remat(sparse)
        h, _ = jax.lax.scan(dense, h, p["dense"])
        h, (chosen, stats, blocks) = jax.lax.scan(
            sparse, h, (p["sparse"], bias))
        return h, {"chosen": chosen, "stats": stats, "blocks": blocks}

    def _head_weight(self, p):
        return p["lm_head"].T.astype(self.c.dtype)

    def apply(self, variables, input_ids, *, train: bool = False, rng=None):
        """Returns (logits [B, S, V], {})."""
        p = variables["params"]
        h, _ = self.hidden_states(variables, input_ids, train=train, rng=rng)
        return ops.linear(self._norm(h, p["norm_f"]),
                          self._head_weight(p)), {}

    # ---- training ----
    def lm_loss_fn(self):
        """Next-token loss; batch = (input_ids,).  The new model state holds
        the moved correction bias; the metrics hold the step's counts: the
        group ``moe`` (scalars named in ``MOE_STEP_IDS``, summed over the
        expert layers) and ``moe_chosen`` [expert layers, n_routed]."""
        def fn(params, model_state, batch, rng, train):
            ids = batch[0] if isinstance(batch, (tuple, list)) else batch
            c = self.c
            variables = {"params": params, "state": model_state}
            h, counts = self.hidden_states(variables, ids, train=train,
                                           rng=rng)
            h = self._norm(h, params["norm_f"])
            if c.fused_ce:
                loss = ops.lm_head_cross_entropy(
                    h[:, :-1], params["lm_head"], ids[:, 1:],
                    row_chunk=c.ce_row_chunk)
            else:
                logits = ops.linear(h, self._head_weight(params))
                loss = jnp.mean(ops.softmax_cross_entropy_sparse(
                    logits[:, :-1], ids[:, 1:]))
            old = (model_state or {}).get("router_bias")
            if old is None:
                old = jnp.zeros(counts["chosen"].shape, jnp.float32)
            bias = self.next_bias(old, counts["chosen"])
            held, _, absent, hit = (counts["stats"].sum(0)[i]
                                    for i in range(4))
            blocks = counts["blocks"].sum()
            # ``blocks``: what the loop path's walks take, forward and
            # backward (both read their trip count from
            # ops.moe_ops._walk_plan: the blocks that hold a pair); at a
            # shape the grouped path takes, what the loop WOULD walk.
            # ``grouped``: the held pairs the grouped path computed, all of
            # them or none by ops.moe_ops.held_expert_path's static rule
            grouped = held * int(held_expert_path(
                ids.size, c.moe_topk, c.held[1], c.hidden_size,
                c.expert_ffn_size) == "grouped")
            group = dict(zip(MOE_STEP_IDS, (
                held, absent, hit, blocks, blocks, jnp.max(jnp.abs(bias)),
                grouped)))
            state = dict(model_state or {}, router_bias=bias)
            return loss, ({"moe": group, "moe_chosen": counts["chosen"]},
                          state)
        return fn
