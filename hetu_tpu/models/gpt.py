"""GPT (decoder-only LM).

Reference: examples/nlp GPT-2 examples + tools/Galvatron gpt models
(hybrid-parallel flagship workload).  Pre-LN causal transformer with tied
LM head; scan-over-layers; Megatron-shardable weights.  This is the flagship
model for the multi-chip dry-run (tp/dp/pp/sp shardings).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from hetu_tpu import init as initializers
from hetu_tpu import ops
from hetu_tpu.layers.base import Module, held_as
from hetu_tpu.layers.transformer import TransformerBlock


@dataclass
class GPTConfig:
    vocab_size: int = 50257
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    ffn_size: int = 3072
    max_position: int = 1024
    dropout_rate: float = 0.1
    dtype: object = jnp.float32
    attention_impl: str = "xla"  # 'flash' = Pallas kernel (TPU)
    remat: bool = False  # recompute each layer in backward: O(L*S*H) residuals
    # instead of O(L*S^2) attention scores — the jax.checkpoint analog of the
    # reference's recompute/checkpoint knobs (Galvatron's ckpt flag)
    remat_policy: str = "full"  # 'full' = save the layer's input and, where
    # attention runs the flash kernel, its output and LSE rows (ops.remat:
    # the backward never runs the forward kernel again); 'dots' = also save
    # matmul outputs (recompute elementwise only)
    fused_ce: bool = True  # lm_loss via ops.lm_head_cross_entropy: head
    # matmul fused into a chunked exact-LSE CE so [B*S, V] f32 logits never
    # materialize (the unfused path is the reference's
    # Linear→SoftmaxCrossEntropySparse composition)
    ce_row_chunk: int = 2048


class GPTModel(Module):
    def __init__(self, config: GPTConfig):
        self.c = config
        self.block = TransformerBlock(
            config.hidden_size, config.num_heads, config.ffn_size,
            dropout_rate=config.dropout_rate, causal=True, pre_norm=True,
            dtype=config.dtype, attention_impl=config.attention_impl)
        self.w_init = initializers.normal(stddev=0.02)

    def init(self, key):
        c = self.c
        ks = jax.random.split(key, 4)
        block_keys = jax.random.split(ks[0], c.num_layers)
        blocks = jax.vmap(lambda k: self.block.init(k)["params"])(block_keys)
        params = {
            "tok_emb": self.w_init(ks[1], (c.vocab_size, c.hidden_size)),
            "pos_emb": self.w_init(ks[2], (c.max_position, c.hidden_size)),
            "blocks": blocks,
            "ln_f_scale": jnp.ones((c.hidden_size,)),
            "ln_f_bias": jnp.zeros((c.hidden_size,)),
        }
        return {"params": params, "state": {}}

    def hidden_states(self, variables, input_ids, *, train: bool = False,
                      rng=None):
        """Final pre-head hidden states ``[B, S, H]`` (post final LN)."""
        p = variables["params"]
        c = self.c
        b, s = input_ids.shape
        h = ops.embedding_lookup(p["tok_emb"], input_ids)
        h = h + p["pos_emb"][None, :s]
        if train and c.dropout_rate > 0:
            h = ops.dropout(h, c.dropout_rate, jax.random.fold_in(rng, 999),
                            train=True)
        h = h.astype(c.dtype)

        def layer(carry, xs):
            p_l, k_l = xs
            out, _ = self.block.apply({"params": p_l, "state": {}}, carry,
                                      train=train, rng=k_l)
            return out, None

        if c.remat:
            layer = ops.remat(layer, c.remat_policy)
        keys = (jax.random.split(rng, c.num_layers) if rng is not None
                else jnp.zeros((c.num_layers, 2), jnp.uint32))
        h, _ = jax.lax.scan(layer, h, (p["blocks"], keys))
        return ops.layer_norm(h, p["ln_f_scale"], p["ln_f_bias"])

    def apply(self, variables, input_ids, *, train: bool = False, rng=None):
        """Returns (logits [B,S,V], {})."""
        p = variables["params"]
        c = self.c
        h = self.hidden_states(variables, input_ids, train=train, rng=rng)
        # tied LM head in the compute dtype: an f32 matmul would skip the
        # MXU bf16 path; CE upcasts to f32 for the reduction
        logits = ops.linear(h, self._head_weight(p))
        return logits, {}

    def _head_weight(self, p):
        """The tied head ``[H, V]`` in the compute dtype: from the copy a
        server holds for it (:meth:`serving_params`) when there is one."""
        return p.get("lm_head", p["tok_emb"]).T.astype(self.c.dtype)

    # ---- serving (hetu_tpu/serve): KV-cache prefill / decode ----

    def serving_params(self, params):
        """The blocks' matmul leaves in the compute dtype (each layer's
        own ``serving_params``; the stacked leaves cast whole, the fused
        QKV projection's also held transposed and split by head).  The
        embeddings and norms stay as given: the lookup adds ``tok_emb`` and
        ``pos_emb`` rows in their own dtype and only then rounds.  The tied
        ``tok_emb`` is read a second way, by the head in the compute dtype,
        so a tree whose ``tok_emb`` is not in it gains that copy as
        ``lm_head``."""
        held = dict(params, blocks=self.block.serving_params(params["blocks"]))
        if params["tok_emb"].dtype != self.c.dtype:
            held["lm_head"] = held_as(params["tok_emb"], self.c.dtype)
        return held

    def prefill_chunk_with_cache(self, variables, input_ids, k_cache,
                                 v_cache, start, *, last_index=None):
        """Chunked prefill: forward ONE chunk of the prompt against a
        cache already holding everything before it (earlier chunks, or a
        shared prefix adopted from the prefix cache).

        input_ids: [B, S_c] at absolute positions ``start .. start+S_c-1``
        (right-padded within the chunk bucket; pad positions produce junk
        K/V that decode masks/overwrites).  k_cache/v_cache:
        [L, B, T, nh, hd] with positions ``< start`` already written (or
        whatever ``ops.read_cache_layer`` reads such a layer from: the
        paged engine hands its pools with their page tables and write map),
        carried through the layer scan (``ops.scan_cached_layers``).
        Returns (logits [B, V] at chunk-relative ``last_index``
        (default S_c - 1), new_k, new_v).  With start == 0 and one chunk
        covering the prompt, the tokens match :meth:`apply`'s.
        """
        p = variables["params"]
        c = self.c
        b, s = input_ids.shape
        h = ops.embedding_lookup(p["tok_emb"], input_ids)
        # per-index gather (not dynamic_slice): a final chunk's PAD tail
        # may run past max_position, and slice-start clamping would shift
        # the REAL tokens' positions.  mode="clip" is load-bearing: the
        # default gather fills out-of-range rows with NaN, and a NaN pad
        # K/V row poisons real queries through 0 * NaN in the masked
        # attention product
        pos = jnp.take(p["pos_emb"], start + jnp.arange(s), axis=0,
                       mode="clip")
        h = (h + pos[None]).astype(c.dtype)
        starts = jnp.full((b,), start, jnp.int32)
        h, k_cache, v_cache = ops.scan_cached_layers(
            lambda p_l, h, k_l, v_l: self.block.prefill_chunk_step(
                {"params": p_l, "state": {}}, h, k_l, v_l, starts),
            p["blocks"], h, k_cache, v_cache, starts, s)
        h = ops.layer_norm(h, p["ln_f_scale"], p["ln_f_bias"])
        idx = s - 1 if last_index is None else last_index
        h = jax.lax.dynamic_index_in_dim(h, idx, axis=1, keepdims=False)
        logits = ops.linear(h, self._head_weight(p))
        return logits, k_cache, v_cache

    def decode_with_cache(self, variables, input_ids, k_cache, v_cache,
                          lengths):
        """One decode step for a batch of cached sequences.

        input_ids: [B] int32 newest token per sequence; k_cache/v_cache:
        [L, B, T, nh, hd] (or as :meth:`prefill_chunk_with_cache` takes
        them); lengths: [B] int32 tokens already cached (the new token's
        position).  Returns (logits [B, V], new_k, new_v).
        """
        p = variables["params"]
        c = self.c
        h = ops.embedding_lookup(p["tok_emb"], input_ids[:, None])
        h = (h + p["pos_emb"][lengths][:, None]).astype(c.dtype)
        h, k_cache, v_cache = ops.scan_layers_over_caches(
            lambda p_l, h, k, v, l: self.block.decode_step(
                {"params": p_l, "state": {}}, h, k, v, l, lengths),
            p["blocks"], h, k_cache, v_cache)
        h = ops.layer_norm(h, p["ln_f_scale"], p["ln_f_bias"])
        logits = ops.linear(h[:, 0], self._head_weight(p))
        return logits, k_cache, v_cache

    def lm_loss_fn(self):
        """Next-token LM loss; batch = (input_ids,) or (input_ids, labels).

        With ``config.fused_ce`` the head matmul + CE run through
        ``ops.lm_head_cross_entropy`` (chunked exact-LSE; logits never
        materialize); otherwise the reference-shaped unfused composition.
        """
        def fn(params, model_state, batch, rng, train):
            ids = batch[0] if isinstance(batch, (tuple, list)) else batch
            c = self.c
            if c.fused_ce:
                h = self.hidden_states({"params": params, "state": {}}, ids,
                                       train=train, rng=rng)
                loss = ops.lm_head_cross_entropy(
                    h[:, :-1], params["tok_emb"], ids[:, 1:],
                    row_chunk=c.ce_row_chunk)
            else:
                logits, _ = self.apply({"params": params, "state": {}}, ids,
                                       train=train, rng=rng)
                per = ops.softmax_cross_entropy_sparse(
                    logits[:, :-1], ids[:, 1:])
                # normalize by non-ignored rows, matching the fused path
                # (identical when no label is ignored_index, as here)
                n_valid = jnp.sum(ids[:, 1:] != -1)
                loss = jnp.sum(per) / jnp.maximum(n_valid, 1)
            return loss, ({}, model_state)
        return fn


def gpt2_small(**kw) -> GPTModel:
    return GPTModel(GPTConfig(**kw))
