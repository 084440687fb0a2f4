"""K-EXAONE's language model in plain ``jax.numpy``: the benchmark's
yardstick for ``correct`` of the ``k-exaone-236b-a23b`` configuration.

The architecture as its public configuration describes it
(``huggingface.co/LGAI-EXAONE/K-EXAONE-236B-A23B`` ``config.json``,
``model_type`` ``exaone_moe``).  Float32 everywhere under
``jax.default_matmul_precision("highest")``; no kernels, no cache, no
batching tricks, one expert at a time over every token; nothing is imported
from the program under test.

Layer ``l``, ``N`` RMSNorm (eps as given, each its own weight), every
projection without bias::

    a = N(x)
    q = N_d(W_q a) per head (heads of d);  k = N_d(W_k a) per KV head;  v = W_v a
    layer_types[l] == "sliding_attention": q and k rotated (theta, all d dims,
        halves [x1 | x2] -> [x1 cos - x2 sin | x2 cos + x1 sin]);
        query i sees keys j with 0 <= i - j < window
    "full_attention": no rotation; query i sees keys j <= i
    x = x + W_o softmax(q k^T / sqrt(d)) v        # head h reads KV head h // (heads / kv_heads)
    u = N(x)
    l < first_dense:  x = x + W_d(silu(W_g u) * W_u u)
    else:  s = sigmoid(u @ R);  chosen = the topk largest of s + b (b: choice only)
           w_i = scaling * s_i / (sum of the chosen s_j + 1e-20)
           x = x + shared(u) + sum over chosen i held here of w_i * E_i(u)
    logits = head(N(x)), head untied from the embedding

``n_group = topk_group = 1`` in the source: group-limited choice is vacuous
and is not written.  ``dims["held"] = (first, count)`` is the share of the
routed experts whose weights ``params`` holds: held experts and the shared
expert add, experts other chips hold do not (what they would add is left
out, as in the program), and the denominator keeps all ``topk`` chosen
scores: the router is whole on every chip.

**Set here because the source is silent, each under ``assumed`` in the
configuration file**: the norms come BEFORE each sub-layer (the
DeepSeek-V3-style keys of this config, ``first_k_dense_replace``,
``n_group``, ``routed_scaling_factor``, go with pre-norm blocks; EXAONE 4.0
normed after the sub-layer, and if K-EXAONE does too this is a departure
from "as published"); rope on the window layers only (EXAONE 4.0's hybrid
layers do so); the half-rotation layout; a float32 correction bias of one
number an expert a layer.  The next-token-prediction module
(``num_nextn_predict_layers``) is absent: it predicts the token after next
and changes no logit of the distribution served.  The parameter layout is
the program's (attention's leaves stacked over all layers, the dense FFN's
over the leading dense layers, the expert layer's over the layers that
follow): a layout, not mathematics.

The pieces (:func:`qkv`, :func:`attend`, :func:`out_projection`, :func:`dense_ffn`,
:func:`expert_choice`, :func:`one_expert`, :func:`shared_expert`) are public
so that the benchmark's adapter can run the same forward one sub-block and
one block of rows at a time, widening one sub-block's bfloat16 weights to
float32 at a time; :func:`qkv` and :func:`attend` take the position of their
first row for that.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = "highest"
WINDOW = "sliding_attention"


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * scale


def rope_halves(x, theta, first=0):
    """x [B, S, heads, D] at positions ``first .. first + S - 1``: halves
    [x1 | x2] turned by ``pos / theta**(2i / D)``."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    pos = (first + jnp.arange(x.shape[1])).astype(jnp.float32)
    ang = pos[:, None] * inv                                      # [S, D/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def qkv(p, x, dims, rotate, first=0):
    """x [B, S, H] (already normed), its first row at position ``first`` ->
    (q [B, S, heads, d], k [B, S, kv_heads, d], v the same): q and k
    normalised per head, and rotated where ``rotate``."""
    with jax.default_matmul_precision(HIGHEST):
        p = _f32(p)
        b, s, _ = x.shape
        d = dims["head_dim"]
        # W_q and W_k are held [out, in] (the program's layout)
        q = rms_norm((x @ p["q"].T).reshape(b, s, -1, d), p["q_norm"],
                     dims["eps"])
        k = rms_norm((x @ p["k"].T).reshape(b, s, -1, d), p["k_norm"],
                     dims["eps"])
        v = (x @ p["v"]).reshape(b, s, -1, d)
        if rotate:
            q = rope_halves(q, dims["theta"], first)
            k = rope_halves(k, dims["theta"], first)
        return q, k, v


def attend(q, k, v, window, first=0, key_first=0):
    """Queries q [B, S, heads, d] at positions ``first + i`` over keys and
    values [B, T, kv_heads, d] at positions ``key_first + j``: query head
    ``h`` reads KV head ``h // (heads / kv_heads)``; a query sees the keys
    at positions ``0 <= p <= its own``, with a ``window`` those of the last
    ``window`` positions only.  Returns [B, S, heads * d]."""
    with jax.default_matmul_precision(HIGHEST):
        b, s, heads, d = q.shape
        k = jnp.repeat(k, heads // k.shape[2], axis=2)
        v = jnp.repeat(v, heads // v.shape[2], axis=2)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(d))
        key = key_first + jnp.arange(k.shape[1])[None, :]
        back = first + jnp.arange(s)[:, None] - key                # i - j
        seen = (back >= 0) & (key >= 0)
        if window is not None:
            seen = seen & (back < window)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(
            b, s, heads * d)


def out_projection(w_o, o):
    """o [B, S, heads * d] -> [B, S, H]."""
    with jax.default_matmul_precision(HIGHEST):
        return o @ jnp.asarray(w_o, jnp.float32)


def attention(p, x, dims, window):
    """One attention block.  x [B, S, H] (already normed); p the block's
    weights; ``window`` None on a full layer."""
    q, k, v = qkv(p, x, dims, window is not None)
    return out_projection(p["o"], attend(q, k, v, window))


def dense_ffn(p, x):
    """SwiGLU.  p: gate [H, F], up [H, F], down [F, H]."""
    with jax.default_matmul_precision(HIGHEST):
        p = _f32(p)
        return (jax.nn.silu(x @ p["gate"]) * (x @ p["up"])) @ p["down"]


def expert_choice(router, router_bias, u, dims):
    """(weights [..., topk] renormalised over the chosen and scaled,
    idx [..., topk])."""
    with jax.default_matmul_precision(HIGHEST):
        s = jax.nn.sigmoid(u @ jnp.asarray(router, jnp.float32))
        _, idx = jax.lax.top_k(s + router_bias, dims["topk"])
        chosen = jnp.take_along_axis(s, idx, -1)
        return dims["scaling"] * chosen / (
            jnp.sum(chosen, -1, keepdims=True) + 1e-20), idx


def one_expert(p, u, weights, idx, index):
    """What the routed expert with global index ``index`` adds, for every
    token: its SwiGLU of ``u`` times the token's weight for it (0 where the
    token did not choose it).  p: gate [H, F], up [H, F], down [F, H]."""
    w = jnp.sum(jnp.where(idx == index, weights, 0.0), -1, keepdims=True)
    return w * dense_ffn(p, u)


def shared_expert(p, u):
    return dense_ffn({"gate": p["shared_gate"], "up": p["shared_up"],
                      "down": p["shared_down"]}, u)


def expert_layer(p, u, dims):
    """shared(u) + the routed part of the share of the experts ``p`` holds.
    p: router, router_bias, gate/up/down stacked over the held experts, the
    shared expert's three."""
    first, count = dims["held"]
    weights, idx = expert_choice(p["router"], p["router_bias"], u, dims)
    out = shared_expert(p, u)
    for e in range(count):
        out = out + one_expert({k: p[k][e] for k in ("gate", "up", "down")},
                               u, weights, idx, first + e)
    return out


def at(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


def layer(layers, l, h, dims):
    """Layer ``l`` of the stacked ``layers``; ``dims["layer_types"][l]`` says
    window or full, ``dims["first_dense"]`` how many leading layers are
    dense."""
    eps, dense = dims["eps"], dims["first_dense"]
    window = dims["window"] if dims["layer_types"][l] == WINDOW else None
    norm = _f32({k: layers[k][l] for k in ("attn_norm", "ffn_norm")})
    h = h + attention(at(layers["attn"], l),
                      rms_norm(h, norm["attn_norm"], eps), dims, window)
    u = rms_norm(h, norm["ffn_norm"], eps)
    if l < dense:
        return h + dense_ffn(at(layers["ffn"], l), u)
    return h + expert_layer(at(layers["moe"], l - dense), u, dims)


def hidden(params, ids, dims):
    h = jnp.asarray(params["tok_emb"], jnp.float32)[ids]
    for l in range(len(dims["layer_types"])):
        h = layer(params["layers"], l, h, dims)
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
