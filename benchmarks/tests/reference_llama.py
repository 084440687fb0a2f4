"""A Llama-shaped decoder in plain ``jax.numpy``: the yardstick of the
test-only second architecture (``arch_llama.py``), which proves that the
harness takes an architecture by files alone.  Never a benchmark entry.

The block as published (Touvron et al. 2023, "LLaMA: Open and Efficient
Foundation Language Models"): token embedding, pre-RMSNorm blocks of causal
attention with rotary positions (half-split form: dimension i pairs with
i + D/2) and fewer key/value heads than query heads, a SwiGLU MLP, no
biases, a final RMSNorm and an UNTIED output head.  Float32 everywhere under
``jax.default_matmul_precision("highest")``.  No kernels, no cache; nothing
is imported from the program under test.

The program's parameter layout is the input: the fused QKV weight's columns
are ``[heads x D | kv_heads x D | kv_heads x D]`` and layers are stacked on
a leading axis (run by ``lax.scan``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def rms_norm(x, scale, eps: float):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * scale


def rope(x, theta: float):
    """x [B, heads, S, D] rotated by position, half-split pairs."""
    s, d = x.shape[-2], x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def block(p, x, w: dict):
    b, s, h = x.shape
    nh, nkv = w["heads"], w["kv_heads"]
    hd = h // nh
    a = rms_norm(x, p["rms1_scale"], w["eps"])
    qkv = a @ p["attn"]["qkv_weight"]
    q = qkv[..., :nh * hd].reshape(b, s, nh, hd)
    k = qkv[..., nh * hd:(nh + nkv) * hd].reshape(b, s, nkv, hd)
    v = qkv[..., (nh + nkv) * hd:].reshape(b, s, nkv, hd)
    q, k, v = (jnp.moveaxis(t, 1, 2) for t in (q, k, v))
    q, k = rope(q, w["theta"]), rope(k, w["theta"])
    # query head j reads key/value head j // (heads / kv_heads)
    k, v = (jnp.repeat(t, nh // nkv, axis=1) for t in (k, v))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(jnp.float32(hd))
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v)
    x = x + jnp.moveaxis(out, 1, 2).reshape(b, s, h) @ p["attn"]["out_weight"]
    m = rms_norm(x, p["rms2_scale"], w["eps"])
    m = jax.nn.silu(m @ p["ffn_gate"]) * (m @ p["ffn_up"])
    return x + m @ p["ffn_down"]


def hidden(params, ids, w: dict, *, remat: bool = False):
    def layer(x, p):
        return block(p, x, w), None

    if remat:
        layer = jax.checkpoint(layer)
    x, _ = jax.lax.scan(layer, params["tok_emb"][ids], params["blocks"])
    return rms_norm(x, params["rms_f_scale"], w["eps"])


def logits(params, ids, w: dict):
    """ids [B, S] -> logits [B, S, V] float32.  ``w``: heads, kv_heads,
    theta, eps."""
    with jax.default_matmul_precision("highest"):
        params = _f32(params)
        return hidden(params, ids, w) @ params["lm_head"].T


def loss(params, ids, w: dict, *, remat: bool = False):
    with jax.default_matmul_precision("highest"):
        params = _f32(params)
        lg = hidden(params, ids[:, :-1], w, remat=remat) @ params["lm_head"].T
        picked = jnp.take_along_axis(lg, ids[:, 1:, None], axis=-1)[..., 0]
        return jnp.mean(jax.nn.logsumexp(lg, axis=-1) - picked)


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(a.astype(jnp.float32)))
                        for a in jax.tree_util.tree_leaves(tree)))


def loss_and_grad_norm(params, ids, w: dict):
    with jax.default_matmul_precision("highest"):
        value, grads = jax.value_and_grad(
            lambda p: loss(p, ids, w, remat=True))(_f32(params))
    return value, global_norm(grads)
