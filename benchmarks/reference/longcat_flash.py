"""LongCat-Flash's language model in plain ``jax.numpy``: the benchmark's
yardstick for ``correct`` of the ``longcat-flash-omni`` configuration.

The architecture as its public configuration describes it
(``huggingface.co/meituan-longcat/LongCat-Flash-Omni`` ``config.json``, the
language model of it).  Float32 everywhere under
``jax.default_matmul_precision("highest")``; no kernels, no cache, no
batching tricks, one expert at a time over every token; nothing is imported
from the program under test.

One double layer, ``N`` RMSNorm (eps as given, each its own weight)::

    h1 = h + A0(N(h));  u = N(h1);  m = M(u);  h2 = h1 + F0(u)
    h3 = h2 + A1(N(h2));  out = h3 + F1(N(h3)) + m

``A(x)``, latent attention: ``q = W_qb(N(W_qa x) * sqrt(hidden / q_rank))``
-> heads of ``[q_n | q_r]``; ``[c | k_r] = W_kva x``; ``c = N(c) *
sqrt(hidden / kv_rank)``; ``[k_n | v] = W_kvb c`` per head; interleaved RoPE
(pairs ``(x[2i], x[2i+1])``) on ``q_r`` and on the one ``k_r`` all heads
share; scores ``([q_n, q_r] . [k_n, k_r]) / sqrt(nope + rope)``, causal
softmax; ``W_o`` over heads x v.

``M(u)``, the expert layer: ``s = softmax(u @ W_r)`` over all routed and
identity experts; the ``topk`` largest of ``s + b`` are chosen (``b`` the
correction bias, for the choice only); a chosen ``i`` weighs ``scaling *
s_i``; ``i < n_routed`` adds ``w_i * W_down_i(silu(W_gate_i u) * W_up_i u)``,
``i >= n_routed`` adds ``w_i * u`` (identity).  No shared expert.

``dims["held"] = (first, count)`` is the share of the routed experts whose
weights ``params`` holds: held experts and identity experts add, experts
other chips hold do not (what they would add is left out, as in the program).

Departures and sizes set here, each under ``assumed`` in the configuration
file: no renormalisation of the chosen weights; no bias term in the router's
logits; an output head not tied to the embedding.  The parameter layout is
the program's (leaves stacked over layers, the two attention blocks and
dense FFNs of a double layer over a second axis of 2): a layout, not
mathematics.

The pieces (:func:`attention`, :func:`dense_ffn`, :func:`expert_choice`,
:func:`one_expert`) are public so that the benchmark's adapter can run the
same forward one sub-block at a time, widening one sub-block's bfloat16
weights to float32 at a time (all of them at once are 20.7 GB).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = "highest"


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * scale


def rope_interleaved(x, theta):
    """x [B, S, ..., D] at positions 0..S-1: pairs (x[2i], x[2i+1]) turned
    by ``pos / theta**(2i / D)``."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv  # [S, D/2]
    ang = ang.reshape((1, x.shape[1]) + (1,) * (x.ndim - 3) + (d // 2,))
    pairs = x.reshape(x.shape[:-1] + (d // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                     b * jnp.cos(ang) + a * jnp.sin(ang)], -1)
    return out.reshape(x.shape)


def attention(p, x, dims):
    """One latent-attention block.  x [B, S, H] (already normed); p the
    block's weights."""
    with jax.default_matmul_precision(HIGHEST):
        p = _f32(p)
        b, s, hidden = x.shape
        heads, nope, rope, v_dim = (dims["heads"], dims["nope"],
                                    dims["rope"], dims["v"])
        q_rank, kv_rank, eps = dims["q_rank"], dims["kv_rank"], dims["eps"]
        q = rms_norm(x @ p["q_a"], p["q_a_norm"], eps) \
            * jnp.sqrt(jnp.float32(hidden / q_rank))
        q = (q @ p["q_b"]).reshape(b, s, heads, nope + rope)
        q_n, q_r = q[..., :nope], rope_interleaved(q[..., nope:],
                                                   dims["theta"])
        kv = x @ p["kv_a"]
        c = rms_norm(kv[..., :kv_rank], p["kv_a_norm"], eps) \
            * jnp.sqrt(jnp.float32(hidden / kv_rank))
        k_r = rope_interleaved(kv[..., kv_rank:], dims["theta"])   # [B,S,rope]
        kvb = (c @ p["kv_b"]).reshape(b, s, heads, nope + v_dim)
        k_n, v = kvb[..., :nope], kvb[..., nope:]
        scores = (jnp.einsum("bqhd,bkhd->bhqk", q_n, k_n)
                  + jnp.einsum("bqhr,bkr->bhqk", q_r, k_r)) \
            / jnp.sqrt(jnp.float32(nope + rope))
        causal = jnp.tril(jnp.ones((s, s), bool))
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        return out.reshape(b, s, heads * v_dim) @ p["o"]


def dense_ffn(p, x):
    """SwiGLU.  p: gate [H, F], up [H, F], down [F, H]."""
    with jax.default_matmul_precision(HIGHEST):
        p = _f32(p)
        return (jax.nn.silu(x @ p["gate"]) * (x @ p["up"])) @ p["down"]


def expert_choice(router, router_bias, u, dims):
    """(weights [..., topk] with the scaling factor in, idx [..., topk])."""
    with jax.default_matmul_precision(HIGHEST):
        s = jax.nn.softmax(u @ jnp.asarray(router, jnp.float32), -1)
        _, idx = jax.lax.top_k(s + router_bias, dims["topk"])
        return dims["scaling"] * jnp.take_along_axis(s, idx, -1), idx


def one_expert(p, u, weights, idx, index):
    """What the routed expert with global index ``index`` adds, for every
    token: its SwiGLU of ``u`` times the token's weight for it (0 where the
    token did not choose it).  p: gate [H, F], up [H, F], down [F, H]."""
    w = jnp.sum(jnp.where(idx == index, weights, 0.0), -1, keepdims=True)
    return w * dense_ffn(p, u)


def identity_experts(u, weights, idx, dims):
    w = jnp.sum(jnp.where(idx >= dims["n_routed"], weights, 0.0), -1,
                keepdims=True)
    return w * u


def expert_layer(p, u, dims):
    """M(u) for the share of the experts ``p`` holds.  p: router, router_bias,
    gate/up/down stacked over the held experts."""
    first, count = dims["held"]
    weights, idx = expert_choice(p["router"], p["router_bias"], u, dims)
    out = identity_experts(u, weights, idx, dims)
    for e in range(count):
        out = out + one_expert({k: p[k][e] for k in ("gate", "up", "down")},
                               u, weights, idx, first + e)
    return out


def double_layer(p, h, dims):
    eps = dims["eps"]

    def block(tree, i):
        return jax.tree_util.tree_map(lambda a: a[i], tree)

    p = _f32(p)
    h1 = h + attention(block(p["attn"], 0),
                       rms_norm(h, p["attn_norm"][0], eps), dims)
    u = rms_norm(h1, p["ffn_norm"][0], eps)
    m = expert_layer(p["moe"], u, dims)
    h2 = h1 + dense_ffn(block(p["ffn"], 0), u)
    h3 = h2 + attention(block(p["attn"], 1),
                        rms_norm(h2, p["attn_norm"][1], eps), dims)
    return h3 + dense_ffn(block(p["ffn"], 1),
                          rms_norm(h3, p["ffn_norm"][1], eps)) + m


def hidden(params, ids, dims):
    h = jnp.asarray(params["tok_emb"], jnp.float32)[ids]
    layers = params["layers"]
    n = jax.tree_util.tree_leaves(layers)[0].shape[0]
    for i in range(n):
        h = double_layer(jax.tree_util.tree_map(lambda a: a[i], layers), h,
                         dims)
    return rms_norm(h, jnp.asarray(params["norm_f"], jnp.float32),
                    dims["eps"])


def head(lm_head, h):
    with jax.default_matmul_precision(HIGHEST):
        return h @ jnp.asarray(lm_head, jnp.float32).T


def logits(params, ids, dims):
    """Full forward: ids [B, S] int -> logits [B, S, V] float32."""
    return head(params["lm_head"], hidden(params, ids, dims))


def loss(params, ids, dims):
    """Mean next-token cross entropy over ids [B, S]."""
    lg = logits(params, ids[:, :-1], dims)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, ids[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


def loss_and_grads(params, ids, dims):
    return jax.value_and_grad(lambda p: loss(p, ids, dims))(_f32(params))


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(a.astype(jnp.float32)))
                        for a in jax.tree_util.tree_leaves(tree)))


def loss_and_grad_norm(params, ids, dims):
    value, grads = loss_and_grads(params, ids, dims)
    return value, global_norm(grads)
