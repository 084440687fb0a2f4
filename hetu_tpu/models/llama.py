"""Llama-family decoder LM: RMSNorm + SwiGLU + RoPE (+ GQA).

Reference: tools/Galvatron/galvatron/models/llama_hf — the second model
family the reference's hybrid-parallel trainer ships (gpt/llama/baichuan),
proving the planner is not GPT-shaped by accident.  Same role here:
:class:`HeteroLlama` executes a searched per-layer Plan (per-layer TP
degree, dp_type, remat) through the SAME ``PlanStrategy`` as HeteroGPT —
the strategy matches the Megatron split points by name (qkv/out for
attention, gate/up col + down row for SwiGLU).

TPU notes: pre-norm residual blocks scan-stack in :class:`LlamaModel`
(one compiled layer body); RoPE tables are computed once per forward and
hoisted out of the scan by XLA; GQA repeats kv heads with a reshape
(no gather).  The LM head is UNTIED (Llama convention) and runs through
the fused vocab-chunked CE so logits never materialize.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from hetu_tpu import init as initializers
from hetu_tpu import ops
from hetu_tpu.layers.base import Module, held_as
from hetu_tpu.ops.attention import SAVED_REDUCED


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int | None = None  # < num_heads = GQA; None = MHA
    ffn_size: int = 11008            # SwiGLU intermediate
    max_position: int = 4096
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6
    dtype: object = jnp.float32
    attention_impl: str = "xla"      # 'flash' = Pallas kernel (TPU)
    remat: bool = False
    fused_ce: bool = True
    ce_row_chunk: int = 2048

    def __post_init__(self):
        if self.num_kv_heads is None:
            self.num_kv_heads = self.num_heads
        if self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"num_heads {self.num_heads} must be a multiple of "
                f"num_kv_heads {self.num_kv_heads}")
        if self.hidden_size % self.num_heads:
            raise ValueError(
                f"num_heads {self.num_heads} must divide hidden_size "
                f"{self.hidden_size}")


class LlamaBlock(Module):
    """Pre-RMSNorm residual block: RoPE attention + SwiGLU MLP.

    Megatron-shardable layout (what PlanStrategy keys on): ``qkv_weight``
    [H, (nh+2*nkv)*hd] and ``ffn_gate``/``ffn_up`` [H, F] are col-split
    points; ``out_weight`` [H, H] and ``ffn_down`` [F, H] row-split points.
    No biases anywhere (Llama convention).
    """

    def __init__(self, c: LlamaConfig):
        self.c = c
        self.head_dim = c.hidden_size // c.num_heads
        self.w_init = initializers.xavier_uniform()

    def init(self, key):
        c = self.c
        kq, ko, kg, ku, kd = jax.random.split(key, 5)
        hd, nh, nkv = self.head_dim, c.num_heads, c.num_kv_heads
        return {"params": {
            "attn": {
                "qkv_weight": self.w_init(
                    kq, (c.hidden_size, (nh + 2 * nkv) * hd), jnp.float32),
                "out_weight": self.w_init(
                    ko, (c.hidden_size, c.hidden_size), jnp.float32),
            },
            "rms1_scale": jnp.ones((c.hidden_size,)),
            "rms2_scale": jnp.ones((c.hidden_size,)),
            "ffn_gate": self.w_init(kg, (c.hidden_size, c.ffn_size),
                                    jnp.float32),
            "ffn_up": self.w_init(ku, (c.hidden_size, c.ffn_size),
                                  jnp.float32),
            "ffn_down": self.w_init(kd, (c.ffn_size, c.hidden_size),
                                    jnp.float32),
        }, "state": {}}

    def _attention(self, p, x, cos, sin):
        c = self.c
        b, s, h = x.shape
        nh, nkv = c.num_heads, c.num_kv_heads
        q, k, v = self._qkv(p, x)
        q, k, v = (jnp.moveaxis(t, 1, 2) for t in (q, k, v))  # [B,h,S,D]
        q = ops.apply_rope(q, cos, sin)
        k = ops.apply_rope(k, cos, sin)
        if nkv != nh:  # GQA: each kv head serves num_heads/nkv query heads
            rep = nh // nkv
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)
        if c.attention_impl == "flash":
            from hetu_tpu.ops.pallas_kernels import flash_attention
            out = flash_attention(q, k, v, causal=True)
        else:
            out = ops.causal_attention(q, k, v)
        out = jnp.moveaxis(out, 1, 2).reshape(b, s, h)
        # row-parallel under Megatron: kept by a layer recomputed under a
        # 'tp' mesh (ops.remat); the identity anywhere else
        return checkpoint_name(
            ops.linear(out.astype(c.dtype), p["out_weight"].astype(c.dtype)),
            SAVED_REDUCED)

    def apply(self, variables, x, cos, sin):
        p = variables["params"]
        c = self.c
        a = self._attention(p["attn"],
                            ops.rms_norm(x, p["rms1_scale"], eps=c.rms_eps),
                            cos, sin)
        x = x + a
        return self._mlp(p, x), {}

    def _mlp(self, p, x):
        c = self.c
        hn = ops.rms_norm(x, p["rms2_scale"], eps=c.rms_eps)
        gate = ops.linear(hn, p["ffn_gate"].astype(c.dtype))
        up = ops.linear(hn, p["ffn_up"].astype(c.dtype))
        down = ops.linear(ops.silu(gate) * up,
                          p["ffn_down"].astype(c.dtype))
        return x + down

    def serving_params(self, params):
        # mirrors _qkv, the two steps' out projection and _mlp: every
        # matmul leaf is read as astype(c.dtype) and as nothing else; the
        # two norm scales are read as they are
        matmuls = ("attn", "ffn_gate", "ffn_up", "ffn_down")
        return dict(params, **held_as({k: params[k] for k in matmuls},
                                      self.c.dtype))

    # ---- serving (hetu_tpu/serve): KV-cache prefill / decode ----
    # The cache stores ROTATED k (RoPE applied at write time, the standard
    # serving layout) and the nkv un-repeated GQA heads; the query heads
    # are grouped at read time.

    def _qkv(self, pa, x):
        c = self.c
        b, s, _ = x.shape
        hd, nh, nkv = self.head_dim, c.num_heads, c.num_kv_heads
        qkv = ops.linear(x, pa["qkv_weight"].astype(c.dtype))
        q = qkv[..., :nh * hd].reshape(b, s, nh, hd)
        k = qkv[..., nh * hd:(nh + nkv) * hd].reshape(b, s, nkv, hd)
        v = qkv[..., (nh + nkv) * hd:].reshape(b, s, nkv, hd)
        return q, k, v

    def prefill_chunk_step(self, variables, x, k_cache, v_cache, starts,
                           cos, sin):
        """Chunked prefill; cos/sin are FULL tables [T_max, hd/2] gathered
        at each token's absolute position (``starts[b] + i``).
        x [B,S_c,H]; caches [B,T,nkv,hd] holding everything before the
        chunk.  Returns (out, new_k, new_v)."""
        p = variables["params"]
        c = self.c
        b, s, _ = x.shape
        hn = ops.rms_norm(x, p["rms1_scale"], eps=c.rms_eps)
        q, k, v = self._qkv(p["attn"], hn)
        q = ops.apply_rope_at(jnp.moveaxis(q, 1, 2), cos, sin, starts)
        k = ops.apply_rope_at(jnp.moveaxis(k, 1, 2), cos, sin, starts)
        k_cache, v_cache = ops.cache_update(
            k_cache, v_cache, jnp.moveaxis(k, 1, 2), v, starts)
        out = ops.chunk_attention(q, k_cache, v_cache, starts)
        out = jnp.moveaxis(out, 1, 2).reshape(b, s, c.hidden_size)
        a = ops.linear(out.astype(c.dtype),
                       p["attn"]["out_weight"].astype(c.dtype))
        return self._mlp(p, x + a), k_cache, v_cache

    def decode_step(self, variables, x, k_cache, v_cache, layer, lengths,
                    cos, sin):
        """One-token decode; cos/sin are FULL tables [T_max, hd/2] gathered
        at each sequence's position.  x [B,1,H]; cache layer ``layer`` of
        the two all-layer caches (``ops.decode_layer_attention``); lengths
        [B] = tokens already cached.  Returns (out, new_k, new_v).
        """
        p = variables["params"]
        c = self.c
        b = x.shape[0]
        hn = ops.rms_norm(x, p["rms1_scale"], eps=c.rms_eps)
        q, k, v = self._qkv(p["attn"], hn)
        q = ops.apply_rope_at(jnp.moveaxis(q, 1, 2), cos, sin, lengths)
        k = ops.apply_rope_at(jnp.moveaxis(k, 1, 2), cos, sin, lengths)
        out, k_cache, v_cache = ops.decode_layer_attention(
            q, jnp.moveaxis(k, 1, 2), v, k_cache, v_cache, layer, lengths)
        out = jnp.moveaxis(out, 1, 2).reshape(b, 1, c.hidden_size)
        a = ops.linear(out.astype(c.dtype),
                       p["attn"]["out_weight"].astype(c.dtype))
        return self._mlp(p, x + a), k_cache, v_cache


class LlamaModel(Module):
    """Scan-stacked Llama (homogeneous layers, one compiled body)."""

    def __init__(self, config: LlamaConfig):
        self.c = config
        self.block = LlamaBlock(config)
        self.w_init = initializers.normal(stddev=0.02)

    def init(self, key):
        c = self.c
        ks = jax.random.split(key, 3)
        block_keys = jax.random.split(ks[0], c.num_layers)
        blocks = jax.vmap(lambda k: self.block.init(k)["params"])(block_keys)
        return {"params": {
            "tok_emb": self.w_init(ks[1], (c.vocab_size, c.hidden_size)),
            "lm_head": self.w_init(ks[2], (c.vocab_size, c.hidden_size)),
            "blocks": blocks,
            "rms_f_scale": jnp.ones((c.hidden_size,)),
        }, "state": {}}

    def _tables(self, s):
        c = self.c
        return ops.rope_tables(s, c.hidden_size // c.num_heads,
                               theta=c.rope_theta)

    def hidden_states(self, variables, input_ids, *, train: bool = False,
                      rng=None):
        p = variables["params"]
        c = self.c
        h = ops.embedding_lookup(p["tok_emb"], input_ids).astype(c.dtype)
        cos, sin = self._tables(input_ids.shape[1])

        def layer(carry, p_l):
            out, _ = self.block.apply({"params": p_l, "state": {}}, carry,
                                      cos, sin)
            return out, None

        if c.remat:
            layer = ops.remat(layer)
        h, _ = jax.lax.scan(layer, h, p["blocks"])
        return ops.rms_norm(h, p["rms_f_scale"], eps=c.rms_eps)

    def apply(self, variables, input_ids, *, train: bool = False, rng=None):
        h = self.hidden_states(variables, input_ids, train=train, rng=rng)
        logits = ops.linear(
            h, variables["params"]["lm_head"].T.astype(self.c.dtype))
        return logits, {}

    # ---- serving (hetu_tpu/serve): KV-cache prefill / decode ----

    def serving_params(self, params):
        """The blocks' matmul leaves and the untied head in the compute
        dtype; ``tok_emb`` (looked up, then rounded) and the norm scales as
        given."""
        return dict(params,
                    blocks=self.block.serving_params(params["blocks"]),
                    lm_head=held_as(params["lm_head"], self.c.dtype))

    def prefill_chunk_with_cache(self, variables, input_ids, k_cache,
                                 v_cache, start, *, last_index=None):
        """Chunked prefill (see GPTModel.prefill_chunk_with_cache):
        input_ids [B, S_c] at absolute positions ``start..start+S_c-1``,
        caches [L, B, T, nkv, hd] with positions < start written.
        Returns (logits [B, V] at chunk-relative ``last_index``, new_k,
        new_v)."""
        p = variables["params"]
        c = self.c
        b, s = input_ids.shape
        h = ops.embedding_lookup(p["tok_emb"], input_ids).astype(c.dtype)
        # full tables, gathered per token at its absolute position
        cos, sin = self._tables(c.max_position)
        starts = jnp.full((b,), start, jnp.int32)
        h, k_cache, v_cache = ops.scan_cached_layers(
            lambda p_l, h, k_l, v_l: self.block.prefill_chunk_step(
                {"params": p_l, "state": {}}, h, k_l, v_l, starts, cos, sin),
            p["blocks"], h, k_cache, v_cache, starts, s)
        h = ops.rms_norm(h, p["rms_f_scale"], eps=c.rms_eps)
        idx = s - 1 if last_index is None else last_index
        h = jax.lax.dynamic_index_in_dim(h, idx, axis=1, keepdims=False)
        logits = ops.linear(h, p["lm_head"].T.astype(c.dtype))
        return logits, k_cache, v_cache

    def decode_with_cache(self, variables, input_ids, k_cache, v_cache,
                          lengths):
        """One decode step; input_ids [B], caches [L, B, T, nkv, hd],
        lengths [B].  Returns (logits [B, V], new_k, new_v)."""
        p = variables["params"]
        c = self.c
        h = ops.embedding_lookup(
            p["tok_emb"], input_ids[:, None]).astype(c.dtype)
        # full tables, gathered per sequence at its own position
        cos, sin = self._tables(c.max_position)
        h, k_cache, v_cache = ops.scan_layers_over_caches(
            lambda p_l, h, k, v, l: self.block.decode_step(
                {"params": p_l, "state": {}}, h, k, v, l, lengths, cos, sin),
            p["blocks"], h, k_cache, v_cache)
        h = ops.rms_norm(h, p["rms_f_scale"], eps=c.rms_eps)
        logits = ops.linear(h[:, 0], p["lm_head"].T.astype(c.dtype))
        return logits, k_cache, v_cache

    def lm_loss_fn(self):
        """Next-token loss; batch = (input_ids,).  Fused CE against the
        UNTIED lm_head (ops.lm_head_cross_entropy takes any [V, H])."""
        def fn(params, model_state, batch, rng, train):
            ids = batch[0] if isinstance(batch, (tuple, list)) else batch
            c = self.c
            if c.fused_ce:
                h = self.hidden_states({"params": params, "state": {}}, ids,
                                       train=train, rng=rng)
                loss = ops.lm_head_cross_entropy(
                    h[:, :-1], params["lm_head"], ids[:, 1:],
                    row_chunk=c.ce_row_chunk)
            else:
                logits, _ = self.apply({"params": params, "state": {}}, ids,
                                       train=train, rng=rng)
                per = ops.softmax_cross_entropy_sparse(
                    logits[:, :-1], ids[:, 1:])
                n_valid = jnp.sum(ids[:, 1:] != -1)
                loss = jnp.sum(per) / jnp.maximum(n_valid, 1)
            return loss, ({}, model_state)
        return fn


class HeteroLlama(LlamaModel):
    """Llama with per-layer parameter trees, executing a searched Plan.

    The Galvatron loop for the second family (reference
    tools/Galvatron/galvatron/models/llama_hf):

        layers = llama_layer_specs(...)                # cost IR
        plan = GalvatronSearching(sim, ...).search(layers)
        model = HeteroLlama.from_plan(cfg, plan)       # per-layer remat
        ex = Executor(model.lm_loss_fn(), opt, mesh=mesh,
                      dist_strategy=PlanStrategy(plan))  # per-layer tp/dp
    """

    def __init__(self, config: LlamaConfig, *,
                 layer_remat: "tuple[bool, ...] | None" = None):
        super().__init__(config)
        if layer_remat is not None and len(layer_remat) != config.num_layers:
            raise ValueError(
                f"layer_remat has {len(layer_remat)} flags for "
                f"{config.num_layers} layers")
        self.layer_remat = layer_remat

    @classmethod
    def from_plan(cls, config: LlamaConfig, plan) -> "HeteroLlama":
        from hetu_tpu.models.gpt_hetero import plan_block_remat
        return cls(config,
                   layer_remat=plan_block_remat(plan, config.num_layers))

    def init(self, key):
        c = self.c
        ks = jax.random.split(key, c.num_layers + 3)
        params = {
            "tok_emb": self.w_init(ks[0], (c.vocab_size, c.hidden_size)),
            "lm_head": self.w_init(ks[1], (c.vocab_size, c.hidden_size)),
            "rms_f_scale": jnp.ones((c.hidden_size,)),
        }
        for i in range(c.num_layers):
            params[f"layer{i}"] = self.block.init(ks[2 + i])["params"]
        return {"params": params, "state": {}}

    def hidden_states(self, variables, input_ids, *, train: bool = False,
                      rng=None):
        p = variables["params"]
        c = self.c
        h = ops.embedding_lookup(p["tok_emb"], input_ids).astype(c.dtype)
        cos, sin = self._tables(input_ids.shape[1])
        for i in range(c.num_layers):
            def block_fn(lp, hh):
                return self.block.apply({"params": lp, "state": {}}, hh,
                                        cos, sin)[0]
            if self.layer_remat is not None and self.layer_remat[i]:
                block_fn = ops.remat(block_fn)
            h = block_fn(p[f"layer{i}"], h)
        return ops.rms_norm(h, p["rms_f_scale"], eps=c.rms_eps)


def llama2_7b(**kw) -> LlamaModel:
    return LlamaModel(LlamaConfig(**kw))
