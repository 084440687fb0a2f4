"""Multi-head attention layer.

Reference: python/hetu/layers/attention.py (MultiHeadAttention composing
batch_matmul/softmax ops).  TPU-native: one fused QKV projection (a single
MXU matmul), `ops.attention` core (or Pallas flash attention for long
sequences), and Megatron-shardable weight layout — the QKV and output
projections are the col-/row-split points the MegatronLM strategy uses
(reference distributed_strategies/simple.py:174-283).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from hetu_tpu import init as initializers
from hetu_tpu import ops
from hetu_tpu.layers.base import (
    Module, held_as, held_transposed, linear_held,
)
from hetu_tpu.ops.attention import SAVED_REDUCED


class MultiHeadAttention(Module):
    def __init__(self, hidden_size: int, num_heads: int, *,
                 dropout_rate: float = 0.0, causal: bool = False,
                 weight_init=None, dtype=jnp.float32,
                 attention_impl: str = "xla"):
        """attention_impl: 'xla' (compiler-fused composition) or 'flash'
        (Pallas kernel, hetu_tpu/ops/pallas_kernels) — flash requires seq
        divisible by its block size and takes no explicit mask (a masked
        call raises: the kernel covers the causal/unmasked cases only)."""
        assert attention_impl in ("xla", "flash"), attention_impl
        assert hidden_size % num_heads == 0
        self.hidden_size = hidden_size
        self.num_heads = num_heads
        self.head_dim = hidden_size // num_heads
        self.dropout_rate = dropout_rate
        self.causal = causal
        self.weight_init = weight_init or initializers.xavier_uniform()
        self.dtype = dtype
        self.attention_impl = attention_impl

    def init(self, key):
        # f32 master weights; self.dtype is the compute dtype (see Linear)
        kq, ko = jax.random.split(key)
        h = self.hidden_size
        return {"params": {
            "qkv_weight": self.weight_init(kq, (h, 3 * h), jnp.float32),
            "qkv_bias": jnp.zeros((3 * h,), jnp.float32),
            "out_weight": self.weight_init(ko, (h, h), jnp.float32),
            "out_bias": jnp.zeros((h,), jnp.float32),
        }, "state": {}}

    def apply(self, variables, x, *, mask=None, train: bool = False, rng=None):
        """x: [batch, seq, hidden]; mask broadcastable to [B,H,S,S] (1=keep)."""
        p = variables["params"]
        b, s, h = x.shape
        if self.attention_impl == "flash" and mask is not None:
            raise ValueError(
                "attention_impl='flash' takes no explicit mask (the kernel "
                "covers the causal and unmasked cases); build the layer "
                "with attention_impl='xla' for masked attention")
        x = x.astype(self.dtype)
        q, k, v = (jnp.moveaxis(t, 1, 2)
                   for t in self._qkv(p, x))  # [B,nh,S,hd]
        if mask is None and self.causal:
            out = self._causal_core(q, k, v)
        elif self.attention_impl == "flash":
            from hetu_tpu.ops.pallas_kernels import flash_attention
            out = flash_attention(q, k, v, causal=False)
        elif self.causal and mask is not None:
            # honor BOTH the causal structure and the user's mask
            causal = jnp.tril(jnp.ones((s, s), bool))
            out = ops.attention(q, k, v,
                                mask=jnp.logical_and(mask.astype(bool),
                                                     causal))
        else:
            out = ops.attention(q, k, v, mask=mask)
        out = jnp.moveaxis(out, 1, 2).reshape(b, s, h)
        if train and self.dropout_rate > 0.0:
            out = ops.dropout(out, self.dropout_rate, rng, train=True)
        y = ops.linear(out.astype(self.dtype),
                       p["out_weight"].astype(self.dtype),
                       p["out_bias"].astype(self.dtype))
        # row-parallel under Megatron: a layer recomputed under a 'tp' mesh
        # keeps the summed value (ops.remat); the identity anywhere else
        return checkpoint_name(y, SAVED_REDUCED), {}

    def _causal_core(self, q, k, v):
        """The unmasked causal attention core, honoring attention_impl
        (incl. the flash kernel path)."""
        if self.attention_impl == "flash":
            from hetu_tpu.ops.pallas_kernels import flash_attention
            return flash_attention(q, k, v, causal=True)
        return ops.causal_attention(q, k, v)

    # ---- serving (hetu_tpu/serve): KV-cache prefill / decode ----

    def serving_params(self, params):
        # mirrors _qkv and _out: all four leaves are read as
        # astype(self.dtype) and as nothing else; the fused projection's
        # result is read head by head ([nh, 3, hd]), so its leaf is held
        # transposed and split by those axes (Module.serving_params)
        return held_transposed(
            held_as(params, self.dtype),
            qkv_weight=(self.num_heads, 3, self.head_dim))

    def _qkv(self, p, x):
        """Fused projection split into q/k/v in cache layout [B,S,nh,hd].

        The 3H columns are HEAD-major ([nh, 3, hd], Megatron's layout, as
        in models/gpt_sharded.py): a column split over tp then lands whole
        heads on each device and attention needs no gather of the heads.
        """
        b, s, _ = x.shape
        qkv = linear_held(x, p, "qkv_weight", self.dtype, p["qkv_bias"])
        qkv = qkv.reshape(b, s, self.num_heads, 3, self.head_dim)
        return qkv[:, :, :, 0], qkv[:, :, :, 1], qkv[:, :, :, 2]

    def _out(self, p, out, b, s):
        out = jnp.moveaxis(out, 1, 2).reshape(b, s, self.hidden_size)
        return ops.linear(out.astype(self.dtype),
                          p["out_weight"].astype(self.dtype),
                          p["out_bias"].astype(self.dtype))

    def prefill_chunk_step(self, variables, x, k_cache, v_cache, starts):
        """Chunked prefill against a cache (the serving engine's prefill).

        x: [B, S_c, H] — a chunk whose token ``i`` sits at absolute
        position ``starts[b] + i``; k_cache/v_cache: [B, T, nh, hd]
        already holding the tokens before the chunk (a shared prefix,
        earlier chunks).  Writes the chunk's K/V at ``starts`` and
        attends over history + the chunk's causal triangle.  Returns
        (y [B, S_c, H], new_k_cache, new_v_cache).  Inference-only (no
        dropout); with starts == 0 the tokens match
        ``apply(causal=True, train=False)``.
        """
        if not self.causal:
            raise NotImplementedError("KV-cache decode is causal-LM only")
        p = variables["params"]
        b, s, _ = x.shape
        x = x.astype(self.dtype)
        q, k, v = self._qkv(p, x)
        k_cache, v_cache = ops.cache_update(k_cache, v_cache, k, v, starts)
        out = ops.chunk_attention(jnp.moveaxis(q, 1, 2), k_cache, v_cache,
                                  starts)
        return self._out(p, out, b, s), k_cache, v_cache

    def decode_step(self, variables, x, k_cache, v_cache, layer, lengths):
        """One-token decode against cache layer ``layer`` of the two
        ALL-LAYER caches (``ops.decode_layer_attention`` says what they may
        be: a paged cache on a TPU is attended where its pages lie).

        x: [B, 1, H]; lengths: [B] int32 = tokens already cached (the new
        token's K/V is written at that index).  Returns (y [B, 1, H],
        new_k_cache, new_v_cache).
        """
        if not self.causal:
            raise NotImplementedError("KV-cache decode is causal-LM only")
        p = variables["params"]
        b = x.shape[0]
        x = x.astype(self.dtype)
        q, k, v = self._qkv(p, x)
        out, k_cache, v_cache = ops.decode_layer_attention(
            jnp.moveaxis(q, 1, 2), k, v, k_cache, v_cache, layer, lengths)
        return self._out(p, out, b, 1), k_cache, v_cache
