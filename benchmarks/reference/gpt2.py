"""GPT-2 in plain ``jax.numpy``: the benchmark's yardstick for ``correct``.

The architecture as published (Radford et al. 2019, "Language Models are
Unsupervised Multitask Learners"; the shapes are those of
``huggingface.co/openai-community/gpt2``): learned token and position
embeddings, pre-LN blocks of causal multi-head attention and a 4x GELU
(tanh form) MLP, a final LayerNorm and an output head tied to the token
embedding.  Float32 everywhere under
``jax.default_matmul_precision("highest")`` — on a TPU an f32 matmul
otherwise runs in bf16 passes.  No kernels, no cache, no batching tricks;
nothing is imported from the program under test.

Departures from the published model, each because the program's parameter
layout is the input here and not because the maths differs:

* the fused QKV weight's 3H columns are head-major ``[heads, 3, head_dim]``
  (Megatron's layout, what ``layers/attention.py`` stores), where the
  published checkpoint is ``[3, heads, head_dim]``.  A fixed permutation of
  columns; with random weights from a seed it changes nothing.
* the embedding may hold more rows than the 50257 of the vocabulary (padded
  to a multiple of 128).  The extra rows are ordinary logits here, as they
  are in the program: no id ever names them, and both sides see the same.
* layers are stacked on a leading axis and run by ``lax.scan``: 36 unrolled
  layers compile for minutes.  ``loss_and_grad_norm`` recomputes each layer
  in the backward pass (``jax.checkpoint``) so that GPT-2-large fits beside
  the system under test; that changes memory, not one number.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LN_EPS = 1e-5


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def layer_norm(x, scale, bias):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * scale + bias


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def block(p, x, num_heads: int):
    """One pre-LN block.  x [B, S, H]; p the block's parameters."""
    b, s, h = x.shape
    hd = h // num_heads
    a = layer_norm(x, p["ln1"]["scale"], p["ln1"]["bias"])
    qkv = a @ p["attn"]["qkv_weight"] + p["attn"]["qkv_bias"]
    qkv = qkv.reshape(b, s, num_heads, 3, hd)
    q, k, v = (jnp.moveaxis(qkv[:, :, :, i], 1, 2) for i in range(3))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    out = jnp.moveaxis(out, 1, 2).reshape(b, s, h)
    x = x + out @ p["attn"]["out_weight"] + p["attn"]["out_bias"]
    m = layer_norm(x, p["ln2"]["scale"], p["ln2"]["bias"])
    m = gelu_tanh(m @ p["ffn_in"]["weight"] + p["ffn_in"]["bias"])
    return x + m @ p["ffn_out"]["weight"] + p["ffn_out"]["bias"]


def hidden(params, ids, num_heads: int, *, remat: bool = False):
    s = ids.shape[1]
    x = params["tok_emb"][ids] + params["pos_emb"][:s][None]

    def layer(x, p):
        return block(p, x, num_heads), None

    if remat:
        layer = jax.checkpoint(layer)
    x, _ = jax.lax.scan(layer, x, params["blocks"])
    return layer_norm(x, params["ln_f_scale"], params["ln_f_bias"])


def logits(params, ids, num_heads: int):
    """Full forward: ids [B, S] int -> logits [B, S, V] float32."""
    with jax.default_matmul_precision("highest"):
        params = _f32(params)
        return hidden(params, ids, num_heads) @ params["tok_emb"].T


def loss(params, ids, num_heads: int, *, remat: bool = False):
    """Mean next-token cross entropy over ids [B, S]."""
    with jax.default_matmul_precision("highest"):
        params = _f32(params)
        lg = hidden(params, ids[:, :-1], num_heads, remat=remat) \
            @ params["tok_emb"].T
        lse = jax.nn.logsumexp(lg, axis=-1)
        picked = jnp.take_along_axis(lg, ids[:, 1:, None], axis=-1)[..., 0]
        return jnp.mean(lse - picked)


def loss_and_grads(params, ids, num_heads: int, *, remat: bool = False):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: loss(p, ids, num_heads, remat=remat))(_f32(params))


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(a.astype(jnp.float32)))
                        for a in jax.tree_util.tree_leaves(tree)))


def loss_and_grad_norm(params, ids, num_heads: int):
    """(loss, global L2 norm of the gradient), layers recomputed."""
    value, grads = loss_and_grads(params, ids, num_heads, remat=True)
    return value, global_norm(grads)
