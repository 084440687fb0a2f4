"""Transformer encoder/decoder block.

Reference: the BERT implementation in examples/nlp/bert/hetu_transformer.py and
Galvatron's vendored Megatron transformer
(tools/Galvatron/galvatron/site_package/megatron + core/tensor_parallel/
transformer.py).  The weight layout is Megatron-shardable: qkv & ffn-in are
column-split points, out-proj & ffn-out row-split points — see
hetu_tpu/parallel/strategies/megatron.py for the spec preset
(reference distributed_strategies/simple.py:174-283).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from hetu_tpu import ops
from hetu_tpu.layers.attention import MultiHeadAttention
from hetu_tpu.layers.base import Module, child_rng
from hetu_tpu.layers.linear import Linear
from hetu_tpu.layers.norm import LayerNorm


class TransformerBlock(Module):
    """Pre- or post-LN block: MHA + 2-layer MLP with residuals."""

    def __init__(self, hidden_size: int, num_heads: int, ffn_size: int = None,
                 *, dropout_rate: float = 0.0, causal: bool = False,
                 pre_norm: bool = False, activation=ops.gelu,
                 dtype=jnp.float32, attention_impl: str = "xla"):
        ffn_size = ffn_size or 4 * hidden_size
        self.attn = MultiHeadAttention(hidden_size, num_heads,
                                       dropout_rate=dropout_rate,
                                       causal=causal, dtype=dtype,
                                       attention_impl=attention_impl)
        self.ln1 = LayerNorm(hidden_size)
        self.ffn_in = Linear(hidden_size, ffn_size, dtype=dtype)
        self.ffn_out = Linear(ffn_size, hidden_size, dtype=dtype)
        self.ln2 = LayerNorm(hidden_size)
        self.dropout_rate = dropout_rate
        self.pre_norm = pre_norm
        self.activation = activation

    def init(self, key):
        ks = jax.random.split(key, 5)
        sub = {"attn": self.attn.init(ks[0]), "ln1": self.ln1.init(ks[1]),
               "ffn_in": self.ffn_in.init(ks[2]),
               "ffn_out": self.ffn_out.init(ks[3]),
               "ln2": self.ln2.init(ks[4])}
        return {"params": {k: v["params"] for k, v in sub.items()},
                "state": {}}

    def apply(self, variables, x, *, mask=None, train: bool = False, rng=None):
        p = variables["params"]
        def mod(m, name, h, **kw):
            out, _ = m.apply({"params": p[name], "state": {}}, h, **kw)
            return out

        r1, r2 = (child_rng(rng, 0), child_rng(rng, 1)) if rng is not None \
            else (None, None)
        if self.pre_norm:
            a = mod(self.attn, "attn", mod(self.ln1, "ln1", x), mask=mask,
                    train=train, rng=r1)
            x = x + a
            h = mod(self.ffn_in, "ffn_in", mod(self.ln2, "ln2", x))
            h = self.activation(h)
            h = mod(self.ffn_out, "ffn_out", h)
            if train and self.dropout_rate > 0:
                h = ops.dropout(h, self.dropout_rate, r2, train=True)
            return x + h, {}
        # post-LN (original BERT)
        a = mod(self.attn, "attn", x, mask=mask, train=train, rng=r1)
        x = mod(self.ln1, "ln1", x + a)
        h = self.activation(mod(self.ffn_in, "ffn_in", x))
        h = mod(self.ffn_out, "ffn_out", h)
        if train and self.dropout_rate > 0:
            h = ops.dropout(h, self.dropout_rate, r2, train=True)
        return mod(self.ln2, "ln2", x + h), {}

    # ---- serving (hetu_tpu/serve): KV-cache prefill / decode ----
    # Pre-LN causal blocks only — the decoder-LM configuration GPT uses;
    # the post-LN (BERT) layout is an encoder and has no decode loop.

    def serving_params(self, params):
        return {name: getattr(self, name).serving_params(p)
                for name, p in params.items()}

    def _mod(self, p, m, name, h, **kw):
        out, _ = m.apply({"params": p[name], "state": {}}, h, **kw)
        return out

    def _mlp(self, p, x):
        h = self._mod(p, self.ffn_in, "ffn_in", self._mod(p, self.ln2,
                                                          "ln2", x))
        return x + self._mod(p, self.ffn_out, "ffn_out", self.activation(h))

    def prefill_chunk_step(self, variables, x, k_cache, v_cache, starts):
        """Chunked prefill: x [B,S_c,H] at absolute positions
        ``starts[b] + i``, caches [B,T,nh,hd] holding everything before
        the chunk → (out, new_k_cache, new_v_cache)."""
        if not self.pre_norm:
            raise NotImplementedError("KV-cache decode needs pre-LN blocks")
        p = variables["params"]
        a, k_cache, v_cache = self.attn.prefill_chunk_step(
            {"params": p["attn"], "state": {}},
            self._mod(p, self.ln1, "ln1", x), k_cache, v_cache, starts)
        return self._mlp(p, x + a), k_cache, v_cache

    def decode_step(self, variables, x, k_cache, v_cache, layer, lengths):
        """x [B,1,H], cache layer ``layer`` of the two all-layer caches →
        (out, new_k_cache, new_v_cache)."""
        if not self.pre_norm:
            raise NotImplementedError("KV-cache decode needs pre-LN blocks")
        p = variables["params"]
        a, k_cache, v_cache = self.attn.decode_step(
            {"params": p["attn"], "state": {}},
            self._mod(p, self.ln1, "ln1", x), k_cache, v_cache, layer,
            lengths)
        return self._mlp(p, x + a), k_cache, v_cache
