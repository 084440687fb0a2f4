"""GLM-5.3-Flash (``model_type`` ``glm5_next_text``): a lightning indexer that
chooses groups of cached tokens over a NoPE latent cache on one layer in four
(DeepSeek Sparse Attention over latent attention without a rotated part),
Kimi Delta Attention on the other three, four residual streams mixed by
Sinkhorn-projected hyper-connections, K-EXAONE's expert layer with a clamp
inside every SwiGLU.

Source of the shapes: ``huggingface.co/zai-org/GLM-5.3-Flash`` ``config.json``.
``N`` is RMSNorm with a plain weight; no projection has a bias; ``n`` =
``hc_mult``; ``layer_types`` and ``mlp_layer_types`` are data, not formulas::

    Stream.  X in R^{n x H} a token; X_0 = E[id] in each of the n rows.
    For every sublayer F (the operator, then the feed-forward), ``ops/hyper.py``:
      u = sum_i H_pre[i] X_i;  y = F(N(u));  X_i <- sum_j H_res[i, j] X_j + H_post[i] y
    logits = W_head N(sum_i X_i)                                   # head untied

    KDA ("linear_attention"; heads of d_k = d_v; a = N(u)):
      q = unit(silu(conv(W_q a)));  k = unit(silu(conv(W_k a)));  v = silu(conv(W_v a))
      g = gate_lower_bound * sigmoid(exp(A_log_h) * (W_g2 (W_g1 a) + dt_bias))   # [heads, d_k] in (lower, 0)
      beta = sigmoid(W_beta a)
      S <- Diag(exp(g_t)) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T;  o_t = S^T q_t / sqrt(d_k)
      out = W_o (N_head(o) * sigmoid(W_z2 (W_z1 a)))

    DSA ("deepseek_sparse_attention"; heads of qk | v dims, NOT rotated):
      cq = N(W_qa a);  q_h = W_qb,h cq;  c = N(W_kva a)            # c is what the cache holds
      k_h,s = W_kb,h c_s;  v_h,s = W_vb,h c_s
      o_h = softmax_{s in chosen(t)}(q_h . k_h,s / sqrt(qk)) v_h,s;  out = W_o [o_h]
      indexer:  qI_j = rot(W_qI,j cq);  kI_s = rot(LayerNorm(W_kI a_s));  w = W_w a / sqrt(J d_I)
                kbar_b = mean of kI over positions P b .. P b + P - 1    # P = index_kpool
                I(t, b) = sum_j w_j relu(qI_j . kbar_b)                  # float32
      chosen(t) = the rows of the index_topk / P complete groups b < (t + 1) // P of largest
                  I(t, b), and the open group's rows up to t; everything while there are
                  no more complete groups than that

    FF: SwiGLU(u) = W_down(silu(min(W_gate u, x)) * clip(W_up u, -x, x)),  x = swiglu_limit;
        dense on the ``first_dense`` leading layers, ``layers/moe.py``'s
        ``HeldExpertLayer`` on the rest (sigmoid scores, the choice by score
        + bias, renormalised, times ``routed_scaling_factor``, one shared
        expert)

**What a layer remembers.**  A KDA layer: a STATE LAYER of two parts
(``serve.kv_cache.SlotStates``), the convolution's last ``taps - 1`` rows of
``[q | k | v]`` in the compute type and the rule's matrix a head in float32
(64 x 128 x 128: 4 MB a slot a layer).  A DSA layer THREE things: its latent
``c`` a token, ONE array of the page group (the V pool is of no width:
``KVCacheSpec.v_head_dim`` 0); a POOLED INDEXER KEY every ``index_kpool``
tokens beside it under the same page tables (``comp_stride``, ``comp_dim``);
and the running sum of the open group's keys, a state part of its own that
the DSA layers keep (a group's keys arrive across chunk and round edges).

**The attention is the absorbed form over the CHOSEN rows**: a query's
scores are taken against the latents themselves (``q_h W_kb,h``, 512 wide)
and its values are the same rows, up-projected after the softmax.  A chunk's
queries go ``index_query_block`` at a time, and who fetches a block's rows
is decided on what is observed (``ops.index_kernel_why``): on a TPU with no
mesh in context the chosen groups are fetched INSIDE the call that attends
them (``ops/pallas_kernels/chosen_groups.py``: the slot's view laid by group
once a chunk, a group one 4 KB slab, one DMA a chosen group into VMEM;
nothing gathered and no score goes through HBM); anywhere else, and in the
dense forward (differentiated: the kernel has no backward), XLA gathers the
rows (128 queries x 2,064 rows x 512 x 2 B = 271 MB of gathered latents, 68
MB of float32 scores; a query's 2,052 rows brought up to whole 16-row tiles,
or the gathered block is copied once more before it is read).  A round
gathers out of the page pool through each slot's table.
``ops/attention.py``: ``pool_index_keys``, ``select_groups``,
``chosen_rows``, ``chosen_rows_attention``.

**Shared**: the layer loop, the three calls, both cache entry points and the
loss are ``models/block.py``'s ``BlockDecoder``, whose residual SEAM
(``_read`` / ``_write``) this model states as hyper-connections; the rule is
``ops/delta_rule.py``'s ``kda_chunk_scan`` / ``kda_step``; the convolution
``ops/ssm.py``'s; the experts ``HeldExpertLayer`` (``swiglu_limit``).

``jax.named_scope``s: ``hetu.mhc.mix`` (coefficients, read and write),
``hetu.kda.proj|conv|rule|step|norm``, ``hetu.dsa.proj``,
``hetu.index.proj|pool|select|attend``, ``hetu.ffn.dense``,
``hetu.moe.route|experts|shared``; instants ``kda.plan``, ``index.plan`` and
``mhc.plan`` once a program traced.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from hetu_tpu import ops
from hetu_tpu.layers.moe import MOE_STATS, HeldExpertLayer
from hetu_tpu.models.block import (
    BlockDecoder, LayerCall, counts_with_grouped, draw_leaf,
)
from hetu_tpu.ops import delta_rule, hyper
from hetu_tpu.ops.pallas_kernels.chosen_groups import chosen_groups_attention
from hetu_tpu.ops.ssm import causal_conv
from hetu_tpu.telemetry import trace

KDA, DSA = "linear_attention", "deepseek_sparse_attention"
DENSE, SPARSE = "dense", "sparse"

# what the cache entry points count over the DSA layers, behind the expert
# layers' counts: the real queries with more complete groups than the indexer
# keeps (they read a choice) and those that read everything, the groups the
# queries read and could have (chosen / visible: how sparse the call was),
# the real queries whose groups were fetched inside the call that attended
# them (``ops.index_kernel_why``: all of a chunk's or none)
INDEX_STATS = ("sparse_queries", "dense_queries", "groups_chosen",
               "groups_visible", "index_kernel_queries")

# the parts of the cache's state, in the order it holds them
CONV, DELTA, OPEN = 0, 1, 2


@dataclass
class GLM5NextConfig:
    vocab_size: int = 154880             # or the slice of it held here
    hidden_size: int = 4096
    num_layers: int = 45                 # held here
    layer_types: tuple = ()              # of the layers held; () = published
    mlp_layer_types: tuple = ()          # "dense" | "sparse", the same
    num_heads: int = 64
    head_dim: int = 256                  # qk_head_dim = qk_nope_head_dim
    v_head_dim: int = 256
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048               # in TOKENS: index_topk / kpool groups
    index_kpool: int = 4
    index_rope_dim: int = 64             # assumed: DeepSeek-V3.2-Exp's
    index_query_block: int = 128         # queries a chunk gathers for at once
    kda_heads: int = 64
    kda_head_dim: int = 128
    conv_taps: int = 4
    gate_lower_bound: float = -5.0
    kda_gate_rank: int = 128             # assumed: the head size
    kda_chunk: int = 64                  # rows the rule solves together
    kda_sub: int = 16                    # ... in sub-blocks of
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    ffn_size: int = 12288                # intermediate_size
    expert_ffn_size: int = 2048          # moe_intermediate_size
    n_routed_experts: int = 288          # as published: the router's width
    moe_topk: int = 8
    routed_scaling_factor: float = 2.5
    swiglu_limit: float = 10.0
    held: Optional[tuple] = None         # (first, count); None: all of them
    rope_theta: float = 10000.0          # the indexer's
    rms_eps: float = 1e-5
    max_position: int = 1048576
    dtype: object = jnp.bfloat16         # compute
    param_dtype: object = jnp.bfloat16
    state_dtype: object = jnp.float32    # the rule's matrix
    expert_block_rows: int = 128

    def __post_init__(self):
        if not self.layer_types:
            self.layer_types = tuple(
                DSA if i % 4 == 3 else KDA for i in range(self.num_layers))
        if not self.mlp_layer_types:
            self.mlp_layer_types = tuple(
                DENSE if i < 3 else SPARSE for i in range(self.num_layers))
        self.layer_types = tuple(self.layer_types)
        self.mlp_layer_types = tuple(self.mlp_layer_types)
        if len(self.layer_types) != self.num_layers \
                or set(self.layer_types) - {KDA, DSA} \
                or KDA not in self.layer_types:
            raise ValueError(f"layer_types names {self.num_layers} layers, "
                             f"each {KDA!r} or {DSA!r}, one {KDA!r} at least")
        d = self.first_dense
        if len(self.mlp_layer_types) != self.num_layers \
                or self.mlp_layer_types != (DENSE,) * d \
                + (SPARSE,) * (self.num_layers - d):
            raise ValueError("mlp_layer_types names every layer, the dense "
                             "ones leading")
        if self.held is None:
            self.held = (0, self.n_routed_experts)
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.n_routed_experts:
            raise ValueError(f"held {self.held} is no share of "
                             f"{self.n_routed_experts} experts")
        self.held = (int(first), int(count))
        if self.index_topk % self.index_kpool \
                or self.kda_chunk % self.kda_sub \
                or self.index_rope_dim % 2 \
                or self.index_rope_dim > self.index_head_dim:
            raise ValueError("index_kpool divides index_topk, the rule's "
                             "sub-block its chunk, and the indexer rotates "
                             "an even number of its head's dims")
        if (self.kda_sub - 1) * abs(self.gate_lower_bound) > 80.0:
            raise ValueError("a sub-block's decays leave float32")
        if self.conv_taps < 2:
            raise ValueError("a causal convolution has two taps or more")

    @property
    def first_dense(self) -> int:
        """The leading dense feed-forwards: BlockDecoder reads it."""
        return sum(m == DENSE for m in self.mlp_layer_types)

    # BlockDecoder's GroupedHeads names; no layer here is grouped-query
    num_kv_heads = 1

    @property
    def index_groups(self) -> int:
        """Complete groups a query reads at most."""
        return self.index_topk // self.index_kpool

    @property
    def kda_width(self) -> int:
        return self.kda_heads * self.kda_head_dim

    def unit_stds(self) -> dict:
        """By leaf, the std ``init`` draws it with.  In-projections at ``1 /
        sqrt(fan-in)``: their products are of order one over a unit-rms
        input, so a comparison sees the rule's ``beta`` and decays, the
        indexer's choice, the router's and the hyper-connections' mix.  The
        OUT-projections (``kda.o``, ``dsa.o``, the SwiGLUs' down) at half of
        that or so, reckoning a gated normed read-out and a gated product at
        a half and a softmax's average of 2,052 unit values well under one:
        a branch adds about a half to a stream of one to two, as a trained
        model's branches are smaller than its stream (``qwen3_next.py``).
        ``hc.phi`` at ``1 / sqrt(n H)``: under ``alpha`` = 1 a token's read
        and write gates move by about one in the logit round a bias of 0
        (``H_pre`` a half, ``H_post`` one); the stream mix's ``alpha`` is
        ``hc.alpha_res`` = 0.5 round a bias of ``hc.res_diagonal`` I (each
        row keeps most of itself), because 20 Sinkhorn passes bring logits
        that spread by a half to within 1e-5 of doubly stochastic and
        logits that spread by one only to within 7e-3
        (``tests/test_hyper_connections.py``)."""
        H = self.hidden_size
        over = 1.0 / math.sqrt(H)
        return {
            "tok_emb": 1.0, "lm_head": over, "norm": 0.1,
            "kda.qkv": over, "kda.g1": over, "kda.z1": over,
            "kda.g2": 1.0 / math.sqrt(self.kda_gate_rank),
            "kda.z2": 1.0 / math.sqrt(self.kda_gate_rank),
            "kda.beta": over, "kda.conv_w": self.conv_taps ** -0.5,
            "kda.o": 1.0 / math.sqrt(self.kda_width),
            "dsa.qa": over, "dsa.kva": over,
            "dsa.qb": 1.0 / math.sqrt(self.q_lora_rank),
            "dsa.kb": 1.0 / math.sqrt(self.kv_lora_rank),
            "dsa.vb": 1.0 / math.sqrt(self.kv_lora_rank),
            "dsa.o": 4.0 / math.sqrt(self.num_heads * self.v_head_dim),
            "dsa.iq": 1.0 / math.sqrt(self.q_lora_rank),
            "dsa.ik": over, "dsa.iw": over,
            "ffn.gate": over, "ffn.up": over,
            "ffn.down": 1.0 / math.sqrt(self.ffn_size),
            "moe.router": over, "moe.router_bias": 0.1,
            "moe.gate": over, "moe.up": over,
            "moe.down": 1.0 / math.sqrt(self.expert_ffn_size),
            "moe.shared_gate": over, "moe.shared_up": over,
            "moe.shared_down": 1.0 / math.sqrt(self.expert_ffn_size),
            "hc.phi": 1.0 / math.sqrt(self.hc_mult * H),
            "hc.alpha_res": 0.5, "hc.res_diagonal": 1.0,
        }


def rotate_interleaved(x, cos, sin):
    """The interleaved rotary layout over the leading ``2 r`` dims of the
    last axis (pairs ``(x_0, x_1), (x_2, x_3), ...``), the rest passed: x
    [B, S, ..., D], cos / sin [B, S, r]; float32 inside, ``x``'s dtype
    out."""
    r = cos.shape[-1]
    xf = x.astype(jnp.float32)
    lead = xf[..., :2 * r].reshape(xf.shape[:-1] + (r, 2))
    shape = cos.shape[:2] + (1,) * (x.ndim - 3) + (r,)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    a, b = lead[..., 0], lead[..., 1]
    turned = jnp.stack([a * cos - b * sin, b * cos + a * sin], -1)
    return jnp.concatenate(
        [turned.reshape(xf.shape[:-1] + (2 * r,)), xf[..., 2 * r:]],
        -1).astype(x.dtype)


class GLM5NextModel(BlockDecoder):
    """``params``: ``tok_emb`` [V, H], ``lm_head`` [V, H], ``norm_f``,
    ``layers``: ``attn_norm``/``ffn_norm`` [L, H] (a sublayer's norm of the
    mixed row ``u``), ``hc`` the hyper-connections of the 2 L sublayers
    {norm [2 L, n H], phi [2 L, n H, 2 n + n n] float32, alpha [2 L, 3],
    bias [2 L, 2 n + n n] float32} (sublayer ``2 l + sub``), ``kda`` over
    the KDA layers {qkv [H, 3 w] (``[q | k | v]``), o [w, H], g1 / z1 [H,
    r], g2 / z2 [r, w], beta [H, heads], conv_w [taps, 3 w], A_log [heads]
    and dt_bias [w] float32, norm [d]}, ``dsa`` over the DSA layers {qa [H,
    q_lora], q_norm, qb [q_lora, heads * qk], kva [H, kv_lora], kv_norm, kb
    [heads, kv_lora, qk], vb [heads, kv_lora, v], o [heads * v, H], iq
    [q_lora, J * d_I], ik [H, d_I], ik_w / ik_b [d_I] (a LayerNorm's), iw
    [H, J]}, ``ffn`` {gate, up, down} over the leading dense layers, ``moe``
    (``HeldExpertLayer``'s, with ``router_bias``) over the rest.  A matrix a
    layer reads whole is a TUPLE of the layers' arrays, as ``init`` yields
    them (``layers/base.py`` ``Module.serving_params``); ``leaf[l]`` reads
    either form."""

    step_stats = MOE_STATS + ("moe_experts", "moe_grouped") + INDEX_STATS
    call_stats = INDEX_STATS

    def __init__(self, config: GLM5NextConfig):
        c = config
        dsa = [l for l, t in enumerate(c.layer_types) if t == DSA]
        kda = [l for l, t in enumerate(c.layer_types) if t == KDA]
        self.kda_leaf = {l: i for i, l in enumerate(kda)}
        self.swiglu_limit = c.swiglu_limit
        super().__init__(
            c, HeldExpertLayer(
                n_routed=c.n_routed_experts, n_zero=0, k=c.moe_topk,
                scaling=c.routed_scaling_factor, held=c.held,
                block_rows=c.expert_block_rows, dtype=c.dtype,
                scoring="sigmoid", renormalise=True, shared=True,
                swiglu_limit=c.swiglu_limit)
            if c.first_dense < c.num_layers else None,
            attn_leaf={l: i for i, l in enumerate(dsa)},
            cache_layer={l: (0, i) for i, l in enumerate(dsa)},
            rotated=(), rotary_dim=c.index_rope_dim)

    # ---- the cache this model asks of the serving engine ----
    def kv_cache_spec(self):
        from hetu_tpu.serve.kv_cache import KVCacheSpec

        c = self.c
        return KVCacheSpec(
            num_layers=len(self.attn_leaf), num_kv_heads=1,
            head_dim=c.kv_lora_rank, v_head_dim=0, dtype=c.dtype,
            comp_stride=c.index_kpool if self.attn_leaf else None,
            comp_dim=c.index_head_dim,
            # a round reads index_topk + pool chosen rows a slot and 32 B a
            # cached token of pooled keys: no page bucket is worth a program
            whole_tables=bool(self.attn_leaf),
            state_layers=len(self.kda_leaf),
            state_parts=(
                ("conv", ((c.conv_taps - 1) * 3 * c.kda_width,), c.dtype),
                ("delta", (c.kda_heads, c.kda_head_dim, c.kda_head_dim),
                 c.state_dtype),
                ("open", (c.index_head_dim,), jnp.float32,
                 len(self.attn_leaf))))

    # ---- weights ----
    def init(self, key):
        """Every matrix in ``param_dtype`` at its own std
        (``config.unit_stds()``), a large leaf drawn a piece at a time; the
        router, its bias and the hyper-connections' projections float32; the
        rule's ``A_log`` uniform in log (1, 16] a head and ``dt_bias`` normal
        a channel, so that channels that forget in a row stand beside
        channels that keep a twentieth of a percent a row."""
        c = self.c
        pd, std = c.param_dtype, c.unit_stds()
        H, L, n = c.hidden_size, c.num_layers, c.hc_mult
        D, S = c.first_dense, c.num_layers - c.first_dense
        E, F = c.held[1], c.expert_ffn_size
        A, G = len(self.attn_leaf), len(self.kda_leaf)
        w, r, d = c.kda_width, c.kda_gate_rank, c.kda_head_dim
        nh, qk, dv = c.num_heads, c.head_dim, c.v_head_dim
        J, dI = c.index_n_heads, c.index_head_dim
        ks = iter(jax.random.split(key, 64))

        def draw(name, lead, shape, dtype=pd):
            return draw_leaf(next(ks), lead, shape, std[name], dtype)

        def each(name, count, shape, dtype=pd):
            """A matrix a layer: a tuple of the layers' arrays."""
            return tuple(draw_leaf(k, (), shape, std[name], dtype)
                         for k in jax.random.split(next(ks), count))

        def norm(*shape, about=1.0):
            return (about + draw_leaf(next(ks), (), shape, std["norm"],
                                      jnp.float32)).astype(pd)

        width = 2 * n + n * n
        bias = jnp.concatenate([
            jnp.zeros((2 * n,), jnp.float32),
            std["hc.res_diagonal"] * jnp.eye(n, dtype=jnp.float32).reshape(-1)])
        layers = {
            "attn_norm": norm(L, H), "ffn_norm": norm(L, H),
            "hc": {"norm": norm(2 * L, n * H),
                   "phi": each("hc.phi", 2 * L, (n * H, width), jnp.float32),
                   "alpha": jnp.broadcast_to(jnp.array(
                       [1.0, 1.0, std["hc.alpha_res"]], jnp.float32),
                       (2 * L, 3)),
                   "bias": bias + 0.1 * jax.random.normal(
                       next(ks), (2 * L, width), jnp.float32)},
            "kda": {"qkv": each("kda.qkv", G, (H, 3 * w)),
                    "o": each("kda.o", G, (w, H)),
                    "g1": draw("kda.g1", (G,), (H, r)),
                    "g2": each("kda.g2", G, (r, w)),
                    "z1": draw("kda.z1", (G,), (H, r)),
                    "z2": each("kda.z2", G, (r, w)),
                    "beta": draw("kda.beta", (G,), (H, c.kda_heads)),
                    "conv_w": draw("kda.conv_w", (G,), (c.conv_taps, 3 * w)),
                    "A_log": jnp.log(1.0 + 15.0 * jax.random.uniform(
                        next(ks), (G, c.kda_heads), jnp.float32)),
                    "dt_bias": jax.random.normal(next(ks), (G, w),
                                                 jnp.float32),
                    "norm": norm(G, d)},
            "dsa": {"qa": each("dsa.qa", A, (H, c.q_lora_rank)),
                    "q_norm": norm(A, c.q_lora_rank),
                    "qb": each("dsa.qb", A, (c.q_lora_rank, nh * qk)),
                    "kva": each("dsa.kva", A, (H, c.kv_lora_rank)),
                    "kv_norm": norm(A, c.kv_lora_rank),
                    "kb": each("dsa.kb", A, (nh, c.kv_lora_rank, qk)),
                    "vb": each("dsa.vb", A, (nh, c.kv_lora_rank, dv)),
                    "o": each("dsa.o", A, (nh * dv, H)),
                    "iq": each("dsa.iq", A, (c.q_lora_rank, J * dI)),
                    "ik": draw("dsa.ik", (A,), (H, dI)),
                    "ik_w": norm(A, dI),
                    "ik_b": norm(A, dI, about=0.0),
                    "iw": draw("dsa.iw", (A,), (H, J))},
            "ffn": {"gate": each("ffn.gate", D, (H, c.ffn_size)),
                    "up": each("ffn.up", D, (H, c.ffn_size)),
                    "down": each("ffn.down", D, (c.ffn_size, H))},
        }
        if S:
            layers["moe"] = {
                "router": each("moe.router", S, (H, c.n_routed_experts),
                               jnp.float32),
                "router_bias": draw("moe.router_bias", (S,),
                                    (c.n_routed_experts,), jnp.float32),
                "gate": draw("moe.gate", (S, E), (H, F)),
                "up": draw("moe.up", (S, E), (H, F)),
                "down": draw("moe.down", (S, E), (F, H)),
                "shared_gate": each("moe.shared_gate", S, (H, F)),
                "shared_up": each("moe.shared_up", S, (H, F)),
                "shared_down": each("moe.shared_down", S, (F, H))}

        def rows(name):
            """[V, H] in runs of a few rows: a draw of a few pieces of an
            odd row count compiles for minutes (``minicpm_sala.py``)."""
            few = math.gcd(c.vocab_size, 1024)
            return draw_leaf(next(ks), (c.vocab_size // few,), (few, H),
                             std[name], pd).reshape(c.vocab_size, H)

        return {"params": {"tok_emb": rows("tok_emb"),
                           "lm_head": rows("lm_head"),
                           "norm_f": norm(H), "layers": layers},
                "state": {}}

    def serving_params(self, params):
        """As given: ``init`` yields the matrices a layer an array."""
        return params

    # ---- the stream: embedding, the seam, the head ----
    def _embed(self, p, ids):
        h = super()._embed(p, ids)
        return jnp.broadcast_to(
            h[..., None, :], h.shape[:-1] + (self.c.hc_mult, h.shape[-1]))

    def _read(self, p, l: int, sub: int, h):
        c = self.c
        at = 2 * l + sub
        hc = {name: p["hc"][name][at] for name in ("norm", "phi", "alpha",
                                                   "bias")}
        if at == 0:
            trace.instant("mhc.plan", {
                "streams": c.hc_mult, "sinkhorn_iters": c.hc_sinkhorn_iters,
                "sublayers": 2 * c.num_layers, "rows": int(h.shape[1]),
                "batch": int(h.shape[0]),
                "stream_bytes": int(h.size) * h.dtype.itemsize})
        with jax.named_scope("hetu.mhc.mix"):
            pre, post, res = hyper.coefficients(
                h, hc, iters=c.hc_sinkhorn_iters, eps=c.hc_eps,
                rms_eps=c.rms_eps)
            return hyper.stream_read(h, pre), (res, post)

    def _write(self, h, y, mix):
        with jax.named_scope("hetu.mhc.mix"):
            return hyper.stream_write(h, mix[0], mix[1], y)

    def _head(self, p, h):
        with jax.named_scope("hetu.mhc.mix"):
            h = jnp.sum(h.astype(jnp.float32), -2).astype(h.dtype)
        return super()._head(p, h)

    # ---- pieces of a layer ----
    def _operator(self, p, l: int, a, call: LayerCall):
        if l in self.kda_leaf:
            return self._kda(p["kda"], self.kda_leaf[l], a, call)
        return self._dsa(p["dsa"], l, a, call)

    def _kda(self, p, gl: int, a, call: LayerCall):
        """The Kimi Delta Attention mixer of KDA layer ``gl`` on ``a`` [B,
        S, H].  Its state layer holds the convolution's last rows and the
        rule's matrix: zeros in the dense forward, the slot's in a cached
        call, which keeps both as they stand after ``call.last``.  A decode
        round steps over EVERY slot's matrix, a layer whole
        (``SlotStates.whole``); every other call solves in chunks."""
        c, dt_ = self.c, self.c.dtype
        b, s, _ = a.shape
        nh, d = c.kda_heads, c.kda_head_dim
        st, whole = call.state, call.one_query and call.state is not None
        conv_state = delta = None
        if whole:
            conv_state, delta = st.read(gl, CONV), st.whole(gl, DELTA)
        elif st is not None:
            conv_state, delta = st.read(gl, CONV), st.read(gl, DELTA)
        if st is not None:
            conv_state = conv_state.reshape(b, c.conv_taps - 1, -1)
        if gl == 0:
            trace.instant("kda.plan", {
                "form": "step" if call.one_query else "chunk", "rows": s,
                "batch": b, "chunk": c.kda_chunk, "sub": c.kda_sub,
                "heads": nh, "d": d, "solve": delta_rule.SOLVE,
                "gate_lower_bound": c.gate_lower_bound,
                "state_bytes_per_slot": 4 * nh * d * d})
        with jax.named_scope("hetu.kda.proj"):
            qkv = ops.linear(a, p["qkv"][gl].astype(dt_))
            low = lambda one, two: ops.linear(
                ops.linear(a, p[one][gl].astype(dt_)), p[two][gl].astype(dt_))
            g = c.gate_lower_bound * jax.nn.sigmoid(
                jnp.exp(p["A_log"][gl])[:, None]
                * (low("g1", "g2").astype(jnp.float32)
                   + p["dt_bias"][gl]).reshape(b, s, nh, d))
            z = low("z1", "z2")
            beta = jax.nn.sigmoid(ops.linear(
                a, p["beta"][gl].astype(dt_)).astype(jnp.float32))
        with jax.named_scope("hetu.kda.conv"):
            qkv, conv_state = causal_conv(qkv, p["conv_w"][gl], None,
                                          conv_state, call.last)
            qkv = ops.silu(qkv).astype(dt_)
            if st is not None:
                st = st.write(gl, conv_state.reshape(b, -1), CONV)
        q, k, v = (t.reshape(b, s, nh, d) for t in jnp.split(qkv, 3, -1))
        if call.one_query:
            with jax.named_scope("hetu.kda.step"):
                # by slot where the state is: a slot of no sequence of the
                # round has g = 0 and beta = 0, so its matrix stays as it is
                by_slot = (lambda t: st.spread(t, delta)) if whole \
                    else (lambda t: t)
                if delta is None:
                    delta = jnp.zeros((b, nh, d, d), jnp.float32)
                o, delta = delta_rule.kda_step(
                    *(by_slot(t[:, 0]) for t in (q, k, v, g, beta)), delta)
                o = (st.pick(o) if whole else o)[:, None]
                if whole:
                    st = st.put_whole(gl, DELTA, delta)
                elif st is not None:
                    st = st.write(gl, delta, DELTA)
        else:
            with jax.named_scope("hetu.kda.rule"):
                o, delta = delta_rule.kda_chunk_scan(
                    q, k, v, g, beta, delta, chunk=c.kda_chunk,
                    sub=c.kda_sub, last=call.last)
                if st is not None:
                    st = st.write(gl, delta, DELTA)
        call.state = st
        with jax.named_scope("hetu.kda.norm"):
            y = self._norm(o, p["norm"][gl]).reshape(b, s, -1)
            y = y * jax.nn.sigmoid(z.astype(jnp.float32)).astype(y.dtype)
        with jax.named_scope("hetu.kda.proj"):
            return ops.linear(y.astype(dt_), p["o"][gl].astype(dt_))

    def _count_index(self, call: LayerCall, real, n, pos, kernel: bool):
        """Add one DSA layer's ``INDEX_STATS`` to the call's counts: ``real``
        [B, S] the queries that are tokens, ``n`` [B, S] the complete groups
        each read, ``pos`` their positions; ``kernel``: their groups were
        fetched inside the call that attended them."""
        if call.counts is None:
            return
        complete = (pos + 1) // self.c.index_kpool
        sparse = complete > self.c.index_groups
        call.counts = call.counts + jnp.stack([
            jnp.sum(real & sparse), jnp.sum(real & ~sparse),
            jnp.sum(jnp.where(real, n, 0)),
            jnp.sum(jnp.where(real, complete, 0)),
            jnp.sum(real) * kernel]).astype(jnp.int32)

    def _read_chosen(self, q, qi, w, kbar, view, pos, why: str):
        """A chunk's (or the dense forward's) queries over the view they
        chose from, ``index_query_block`` queries at a time: q [B, S, heads,
        C] absorbed, qi [B, S, J, d_I], w [B, S, J], kbar [B, G, d_I], view
        [B, T, C], pos [B, S] -> (o [B, S, heads, C], n [B, S]).  ``why``
        (``ops.index_kernel_why``) "": a block's chosen groups are fetched
        inside the call that attends them, out of the view laid BY GROUP
        once a call; else XLA gathers their rows, a query's brought up to
        whole row tiles."""
        c = self.c
        b, s = pos.shape
        qb = min(c.index_query_block, s)
        pad = -s % qb
        t, P = view.shape[1], c.index_kpool
        if not why:
            view = jnp.pad(view, ((0, 0), (0, -t % P), (0, 0))).reshape(
                b, -1, P, view.shape[-1])

        def block(xs):
            q_, qi_, w_, pos_ = xs
            with jax.named_scope("hetu.index.select"):
                idx, n = ops.select_groups(
                    qi_, w_, kbar, pos_, topk=c.index_groups, pool=P)
            with jax.named_scope("hetu.index.attend"):
                if not why:
                    return chosen_groups_attention(
                        q_, view, idx, n, pos_, pool=P, scale=self.scale), n
                rows, valid = ops.chosen_rows(idx, n, pos_, pool=P,
                                              tile=ops.INDEX_ROW_TILE)
                latents = jax.vmap(lambda v, r: v[r])(
                    view, jnp.clip(rows, 0, t - 1))
                return ops.chosen_rows_attention(
                    q_, latents, valid, scale=self.scale), n

        cut = lambda x: jnp.moveaxis(jnp.pad(
            x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)).reshape(
                (b, (s + pad) // qb, qb) + x.shape[2:]), 1, 0)
        o, n = jax.lax.map(block, (cut(q), cut(qi), cut(w), cut(pos)))
        join = lambda x: jnp.moveaxis(x, 0, 1).reshape(
            (b, s + pad) + x.shape[3:])[:, :s]
        return join(o), join(n)

    def _dsa(self, p, l: int, a, call: LayerCall):
        """The sparse latent attention of layer ``l`` on ``a`` [B, S, H]:
        the latent rows into the layer's cache layer, the pooled indexer
        keys its new rows COMPLETE beside them (the open group's sum from
        and to the state), the choice, then the absorbed attention over the
        chosen rows: gathered from the slot's view (a chunk; the dense
        forward's own rows) or out of the pool through the tables (a
        round)."""
        c, dt_ = self.c, self.c.dtype
        al = self.attn_leaf[l]
        b, s, _ = a.shape
        nh, J, dI, P = c.num_heads, c.index_n_heads, c.index_head_dim, \
            c.index_kpool
        with jax.named_scope("hetu.dsa.proj"):
            cq = self._norm(ops.linear(a, p["qa"][al].astype(dt_)),
                            p["q_norm"][al])
            q = ops.linear(cq, p["qb"][al].astype(dt_)).reshape(b, s, nh, -1)
            lat = self._norm(ops.linear(a, p["kva"][al].astype(dt_)),
                             p["kv_norm"][al])
            # absorbed: a head's query through its key up-projection
            q = jnp.einsum("bshd,hcd->bshc", q, p["kb"][al].astype(dt_))
        with jax.named_scope("hetu.index.proj"):
            qi = rotate_interleaved(
                ops.linear(cq, p["iq"][al].astype(dt_)).reshape(b, s, J, dI),
                call.cos, call.sin)
            ki = ops.layer_norm(
                ops.linear(a, p["ik"][al].astype(dt_)), p["ik_w"][al],
                p["ik_b"][al], eps=c.rms_eps)
            ki = rotate_interleaved(ki.astype(dt_), call.cos, call.sin)
            w = ops.linear(a, p["iw"][al].astype(dt_)).astype(jnp.float32) \
                * (J * dI) ** -0.5
        st = call.state
        at = jnp.zeros((b,), jnp.int32) if call.at is None else call.at
        pos = at[:, None] + jnp.arange(s)[None]
        with jax.named_scope("hetu.index.pool"):
            open_sum = jnp.zeros((b, dI), jnp.float32) if st is None \
                else st.read(al, OPEN)
            means, done, open_sum = ops.pool_index_keys(
                ki, open_sum, at, pool=P, last=call.last)
            if st is not None:
                call.state = st.write(al, open_sum, OPEN)
        # a round gathers out of the pool through the tables; the dense
        # forward is differentiated (the model's loss), and the kernel has
        # no backward; a chunk goes by what is observed
        why = "round" if call.one_query else "forward" if call.k is None \
            else ops.index_kernel_why(c.kv_lora_rank)
        if al == 0:
            ops.index_plan(
                "gathered" if why else "kernel", s, b, c.index_groups,
                pool=P, why=why, rows=(c.index_groups + 1) * P,
                query_block=1 if call.one_query
                else min(c.index_query_block, s),
                heads=nh, latent=c.kv_lora_rank, index_heads=J)
        real = jnp.ones((b, s), bool)
        if call.k is None:                          # the dense forward
            o, n = self._read_chosen(q, qi, w, means.astype(dt_), lat, pos,
                                     why)
        else:
            grp, cl = self.cache_layer[l]
            kc = call.k[grp]
            ps = kc.pool.shape[2]
            with jax.named_scope("hetu.index.pool"):
                kc = kc.write_comp(
                    cl, means, (at // P)[:, None]
                    + jnp.arange(means.shape[1])[None], done)
                kbar = kc.read_comp(cl, row=(dI,))
            if call.one_query:
                kc = kc.write(cl, lat)
                if call.state is not None:
                    real = call.state.real[:, None]
                with jax.named_scope("hetu.index.select"):
                    idx, n = ops.select_groups(qi, w, kbar, pos,
                                               topk=c.index_groups, pool=P)
                with jax.named_scope("hetu.index.attend"):
                    rows, valid = ops.chosen_rows(idx, n, pos, pool=P)
                    pages = jnp.take_along_axis(
                        kc.tables, jnp.clip(rows[:, 0] // ps, 0,
                                            kc.tables.shape[1] - 1), 1)
                    latents = kc.pool[cl, pages, rows[:, 0] % ps][:, None]
                    o = ops.chosen_rows_attention(q, latents, valid,
                                                  scale=self.scale)
            else:
                if call.last is not None:
                    real = jnp.arange(s)[None] <= call.last
                view = kc.read(cl)
                view = jax.vmap(lambda v, rows_, i: jax.lax.
                                dynamic_update_slice(v, rows_, (i, 0)))(
                    view.reshape(b, view.shape[1], -1), lat, at)
                o, n = self._read_chosen(q, qi, w, kbar, view, pos, why)
                kc = kc.write(cl, lat)
            call.k[grp] = kc
        self._count_index(call, real, n, pos, not why)
        with jax.named_scope("hetu.dsa.proj"):
            o = jnp.einsum("bshc,hcd->bshd", o, p["vb"][al].astype(dt_))
            return ops.linear(o.reshape(b, s, -1), p["o"][al].astype(dt_))

    def _counts(self, stats):
        return counts_with_grouped(self.c, stats)
