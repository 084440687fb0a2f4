"""The ``mellum`` block, trained: window and full attention layers through
the flash kernels with grouped heads, one rotary table a layer TYPE (YaRN on
the full layers, plain on the window layers), every FFN an expert layer with
a softmax router and no shared expert.

Source of the shapes: ``huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct``
``config.json`` (``model_type`` ``mellum``).  One layer, ``N`` RMSNorm (each
its own weight), no projection has a bias::

    h1 = h + Attn(N(h));   h2 = h1 + MoE(N(h1))
    Attn(a): q = N_D(W_q a) per head (heads of D);  k = N_D(W_k a), v = W_v a
             per KV head (kv_heads of D)          # the per-head norms: assumed
             q, k rotated in the half layout by the layer TYPE's table
             key j visible to query i when 0 <= i - j, and on a sliding
             layer i - j < window
             o = W_o concat_heads(softmax(q k^T / sqrt(D)) v)   # head h reads
                                                    # KV head h // (heads / kv)
    MoE(u):  s = softmax(float32(u) W_r) over all ``n_routed_experts``
             chosen = top-k of s;  w_i = s_i / (sum of the chosen s_j + 1e-20)
             sum over the chosen HELD experts w_i W_down_i(silu(W_gate_i u)
             * W_up_i u)

**Two rotary tables** (:meth:`MellumModel.rope_at`), made once a step and
chosen by layer type.  Plain: ``inv_freq_i = theta^(-2i/D)``.  YaRN (the
full layers, ``rope_parameters.full_attention``): with ``c(n) = D ln(L / (2
pi n)) / (2 ln theta)``, ``L`` the original positions, ``low =
floor(c(beta_fast))`` and ``high = ceil(c(beta_slow))`` clipped to ``[0, D -
1]``, ``ramp_i = clip((i - low) / (high - low), 0, 1)``: ``inv_freq_i = (1 -
ramp_i) theta^(-2i/D) + ramp_i theta^(-2i/D) / factor``, and cos and sin are
multiplied by ``attention_factor``.

**One chip's share**: ``held = (first, count)`` of the routed experts are
here (``layers/moe.py`` ``HeldExpertLayer``, softmax, renormalised, no shared
expert, no bias); pairs that land on an absent expert are left out and the
renormalising sum keeps all ``k`` scores.  The walk over the held experts'
pairs follows the load forward and backward
(``ops.moe_ops.held_expert_ffn``: experts that fit the grouped kernels, so
sorted rows through grouped matmuls, ``hetu.moe.gmm``, at any row count).
No auxiliary balance loss (the configuration gives no coefficient) and no
next-token head (it gives no key for one).

**Shared with** ``models/block.py`` (``GroupedHeads``): the grouped
projections with their per-head norms, the half-layout rotation and the
out-projection.  **Split**: rotation on every layer, by two tables; the
training attention (flash with ``window=`` and K, V at ``kv_heads``: no
repeated copy of either exists); no dense layer, no shared expert.

Layers are scanned a PERIOD at a time (the shortest repeating run of
``layer_types``: three window layers and a full one), so both kinds are
compiled once, with per-layer remat (``ops.remat``: a layer keeps its input
and the flash kernel's output and LSE rows).  Parameter leaves are stacked
``[periods, layers a period, ...]``, one a kind of weight; the scan hands a
period its slice and a layer reads its own at a static index.  Master
weights are ``param_dtype`` (float32), made a slice at a time; matmuls run in
``dtype``.

``jax.named_scope``s: ``hetu.attn.window``, ``hetu.attn.full`` (the flash
custom-calls carry them as their names in a device trace),
``hetu.moe.route``, ``hetu.moe.experts``.
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
from hetu_tpu.models.block import FULL, WINDOW, GroupedHeads
from hetu_tpu.ops.moe_ops import held_expert_path

# the scalar ids of one step's expert layers, summed over the layers; the
# trainer puts the group on its ``train.moe`` instant
MOE_STEP_IDS = ("moe_held", "moe_absent", "moe_hit", "moe_blocks_fwd",
                "moe_blocks_bwd", "moe_grouped")


@dataclass
class MellumConfig:
    vocab_size: int = 98304
    hidden_size: int = 2304
    num_layers: int = 28
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    expert_ffn_size: int = 896
    n_routed_experts: int = 64           # as published: the router's width
    moe_topk: int = 8
    held: Optional[tuple] = None         # (first, count); None: all of them
    window: int = 1024
    layer_types: Optional[tuple] = None  # None: every fourth layer full
    rope_theta: float = 5e5
    # the full layers' table: factor, original_max_position_embeddings,
    # beta_fast, beta_slow, attention_factor; None: plain, as the window
    # layers'
    yarn: Optional[dict] = None
    rms_eps: float = 1e-6
    max_position: int = 131072
    dtype: object = jnp.bfloat16         # compute
    param_dtype: object = jnp.float32    # master weights
    init_std: float = 0.02
    router_init_std: float = 0.02
    embedding_init_std: Optional[float] = None   # None: init_std
    # the attention's out-projection; None: init_std.  With random weights
    # attention's output is nearly the mean of its values, one direction all
    # tokens share; a benchmark keeps it small so that routing stays even
    out_init_std: Optional[float] = None
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
        if self.attention_impl not in ("flash", "xla"):
            raise ValueError(f"attention_impl {self.attention_impl!r}: "
                             "'flash' or 'xla'")

    @property
    def period(self) -> tuple:
        """The shortest run of layer types the model repeats."""
        kinds, n = self.layer_types, self.num_layers
        return next(kinds[:p] for p in range(1, n + 1)
                    if n % p == 0 and kinds == kinds[:p] * (n // p))


def yarn_inv_freq(head_dim: int, theta: float, yarn: dict):
    """The YaRN frequencies [head_dim / 2] float32 (module docstring) and
    the block indices ``(low, high)`` its ramp runs between."""
    def turns_at(n):      # the index whose wavelength turns n times in L
        return head_dim * math.log(
            yarn["original_max_position_embeddings"] / (n * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(turns_at(yarn["beta_fast"])), 0)
    high = min(math.ceil(turns_at(yarn["beta_slow"])), head_dim - 1)
    i = jnp.arange(head_dim // 2, dtype=jnp.float32)
    plain = 1.0 / theta ** (2.0 * i / head_dim)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (1.0 - ramp) * plain + ramp * plain / yarn["factor"], (low, high)


class MellumModel(GroupedHeads, Module):
    """``params``: ``tok_emb``, ``lm_head`` [V, H] (untied), ``norm_f``,
    ``layers`` {attn_norm, ffn_norm, attn {q [heads * D, H], k [kv_heads *
    D, H], v [H, kv_heads * D], o [heads * D, H], q_norm, k_norm}, moe
    {router [H, n_routed] float32, gate/up [E, H, F], down [E, F, H]}}, each
    stacked ``[periods, layers a period, ...]``.  No state."""

    def __init__(self, config: MellumConfig):
        self.c = c = config
        self.moe = HeldExpertLayer(
            n_routed=c.n_routed_experts, n_zero=0, k=c.moe_topk, scaling=1.0,
            held=c.held, block_rows=c.expert_block_rows, dtype=c.dtype,
            scoring="softmax", renormalise=True, shared=False)
        self.scale = c.head_dim ** -0.5
        self.periods = c.num_layers // len(c.period)

    # ---- weights ----
    def init(self, key):
        """Float32 masters, each stacked leaf filled one slice at a time."""
        c = self.c
        pd = c.param_dtype
        H, E, F = c.hidden_size, c.held[1], c.expert_ffn_size
        qw, kvw = c.num_heads * c.head_dim, c.num_kv_heads * c.head_dim
        lead = (self.periods, len(c.period))

        def draw(k, lead: tuple, shape: tuple, std, dtype=pd):
            out = jax.lax.map(
                lambda kk: (jax.random.normal(kk, shape, jnp.float32)
                            * std).astype(dtype),
                jax.random.split(k, math.prod(lead)))
            return out.reshape(lead + shape)

        def ones(*shape):
            return jnp.ones(lead + shape, pd)

        ks = iter(jax.random.split(key, 12))
        std = c.init_std
        layers = {
            "attn_norm": ones(H), "ffn_norm": ones(H),
            "attn": {"q": draw(next(ks), lead, (qw, H), std),
                     "k": draw(next(ks), lead, (kvw, H), std),
                     "v": draw(next(ks), lead, (H, kvw), std),
                     "o": draw(next(ks), lead, (qw, H),
                               c.out_init_std or std),
                     "q_norm": ones(c.head_dim), "k_norm": ones(c.head_dim)},
            "moe": {"router": draw(next(ks), lead, (H, c.n_routed_experts),
                                   c.router_init_std, jnp.float32),
                    "gate": draw(next(ks), lead + (E,), (H, F), std),
                    "up": draw(next(ks), lead + (E,), (H, F), std),
                    "down": draw(next(ks), lead + (E,), (F, H), std)},
        }
        return {"params": {
            "tok_emb": draw(next(ks), (), (c.vocab_size, H),
                            c.embedding_init_std or std),
            "lm_head": draw(next(ks), (), (c.vocab_size, H), std),
            "norm_f": jnp.ones((H,), pd),
            "layers": layers,
        }, "state": {}}

    # ---- pieces of a layer ----
    def rope_at(self, pos, kind: str):
        """cos/sin [..., head_dim / 2] float32 at absolute positions, by the
        table of layer type ``kind``."""
        c = self.c
        if kind == FULL and c.yarn:
            inv, _ = yarn_inv_freq(c.head_dim, c.rope_theta, c.yarn)
            factor = c.yarn["attention_factor"]
        else:
            inv = 1.0 / c.rope_theta ** (
                jnp.arange(0, c.head_dim, 2, dtype=jnp.float32) / c.head_dim)
            factor = 1.0
        ang = pos.astype(jnp.float32)[..., None] * inv
        return jnp.cos(ang) * factor, jnp.sin(ang) * factor

    def _attend(self, p, l: int, kind: str, a, cos, sin):
        """Layer ``l`` of the period's attention over the normed ``a`` [B,
        S, H]: [B, S, H]."""
        c = self.c
        b, s, _ = a.shape
        window = c.window if kind == WINDOW else None
        q, k, v = self._qkv(p, l, a, cos, sin, True)
        k, v = jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2)
        with jax.named_scope(
                "hetu.attn.window" if window else "hetu.attn.full"):
            if c.attention_impl == "flash":
                from hetu_tpu.ops.pallas_kernels import flash_attention
                o = flash_attention(q, k, v, causal=True, window=window,
                                    scale=self.scale)
            else:
                rep = c.num_heads // c.num_kv_heads
                o = ops.causal_attention(
                    q.reshape(b, c.num_kv_heads, rep, s, c.head_dim),
                    k[:, :, None], v[:, :, None], scale=self.scale,
                    window=window).reshape(q.shape)
        return self._out(p, l, o)

    def _layer(self, p, l: int, kind: str, h, cos, sin):
        """Layer ``l`` of a period over h [B, S, H], ``p`` the period's
        leaves: (out, ``HeldExpertLayer``'s counts [4], blocks its walk
        takes)."""
        c, dt = self.c, self.c.dtype
        h = h + self._attend(p["attn"], l, kind,
                             self._norm(h, p["attn_norm"][l]), cos, sin)
        u = self._norm(h, p["ffn_norm"][l])
        tokens = u.reshape(-1, u.shape[-1])
        # the walk reads an expert's weights once a block: cast them once
        moe = {"router": p["moe"]["router"][l],
               "router_bias": jnp.zeros((c.n_routed_experts,), jnp.float32),
               **{k: p["moe"][k][l].astype(dt)
                  for k in ("gate", "up", "down")}}
        w, idx = self.moe.route(moe, tokens)
        m, stats = self.moe.combine(moe, tokens, w, idx)
        first, count = c.held
        chosen = jnp.zeros((c.n_routed_experts,), jnp.int32).at[
            idx.reshape(-1)].add(1)
        blocks = jnp.sum(-(-chosen[first:first + count]
                           // c.expert_block_rows))
        return h + m.astype(dt).reshape(h.shape), stats, blocks

    # ---- forward ----
    def hidden_states(self, variables, input_ids, *, train: bool = False,
                      rng=None):
        """(final hidden [B, S, H] before the last norm, per-step counts:
        ``stats`` [layers, 4], ``blocks`` [layers])."""
        c = self.c
        p = variables["params"]
        b, s = input_ids.shape
        h = ops.embedding_lookup(p["tok_emb"], input_ids).astype(c.dtype)
        pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        tables = {kind: self.rope_at(pos, kind) for kind in set(c.period)}

        def layer(l, kind):
            def run(p_period, h):
                return self._layer(p_period, l, kind, h, *tables[kind])
            return ops.remat(run) if c.remat else run

        layers = [layer(l, kind) for l, kind in enumerate(c.period)]

        def period(h, p_period):
            counts = []
            for run in layers:
                h, *n = run(p_period, h)
                counts.append(n)
            stats, blocks = zip(*counts)
            return h, (jnp.stack(stats), jnp.stack(blocks))

        h, (stats, blocks) = jax.lax.scan(period, h, p["layers"])
        return h, {"stats": stats.reshape(-1, 4),
                   "blocks": blocks.reshape(-1)}

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
        """Next-token loss; batch = (input_ids,).  The metrics hold the
        step's counts: the group ``moe`` (scalars named in ``MOE_STEP_IDS``,
        summed over the layers)."""
        def fn(params, model_state, batch, rng, train):
            ids = batch[0] if isinstance(batch, (tuple, list)) else batch
            c = self.c
            h, counts = self.hidden_states(
                {"params": params, "state": model_state}, ids, train=train,
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
            group = dict(zip(MOE_STEP_IDS,
                             (held, absent, hit, blocks, blocks, grouped)))
            return loss, ({"moe": group}, model_state)
        return fn
