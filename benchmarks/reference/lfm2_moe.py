"""LFM2's expert model in plain ``jax.numpy``: the benchmark's yardstick for
``correct`` of the ``lfm2-8b-a1b`` configuration.

The architecture as its public configuration describes it
(``huggingface.co/LiquidAI/LFM2-8B-A1B`` ``config.json``, ``model_type``
``lfm2_moe``).  Float32 everywhere under
``jax.default_matmul_precision("highest")``; no kernels, no cache, no state
carried between calls, no batching tricks: the convolution is three shifted
products over the whole sequence, and every expert is computed on every
token and weighed by a ``[..., experts]`` matrix that is zero off the chosen
four.  Nothing is imported from the program under test.

Layer ``l``, ``N`` RMSNorm (``norm_eps``, each its own weight), every
projection without bias (``conv_bias`` false)::

    h = h + Op_l(N(h));   h = h + FF_l(N(h))

    layer_types[l] == "conv" (``conv_L_cache`` 3):
        [B | C | x] = a W_in          (H -> 3 H, split in that order)
        u = B * x
        c_t = w_0 * u_(t-2) + w_1 * u_(t-1) + w_2 * u_t    (one weight of 3 a
              channel; u is zero before the sequence starts)
        Op = (C * c) W_out            (no activation, no norm inside)
    "full_attention":
        q = N_d(a W_q) per head (heads of d);  k = N_d(a W_k), v = a W_v per
        KV head; q and k rotated (rope_theta, all d dims, halves [x1 | x2]
        -> [x1 cos - x2 sin | x2 cos + x1 sin]); query i sees keys j <= i;
        Op = softmax(q k^T / sqrt(d)) v W_o, head h reading KV head
        h // (heads / kv_heads)
    l < num_dense_layers:  FF = (silu(a W_1) * a W_3) W_2
    else:  s = sigmoid(a R)  (float32);  chosen = the top-k of s + b
           (``use_expert_bias``: b steers the choice and is no weight)
           w_i = scaling * s_i / (sum of the chosen s_j + 1e-6)
           FF = sum over the chosen i of w_i * E_i(a),  E_i a SwiGLU
    logits = N(h) E^T, the head tied to the embedding E

**Set here because the source is silent, each under ``assumed`` in the
configuration file**: the tied head (the family's default; the card's 8.3B
parameters bear it out); the order of the ``B | C | x`` split; the ``1e-6``
in the renormalising sum; a float32 correction bias of one number an expert
a layer.  ``dims["held"] = (first, count)`` is the share of the routed
experts whose weights ``params`` holds (here all of them); the parameter
layout is the program's (attention's leaves stacked over the attention
layers, the convolution's over the conv layers, the dense FFN's over the
leading dense layers, the experts' over the layers that follow): a layout,
not mathematics.

The pieces are public so that the benchmark's adapter can run the same
forward a layer and an expert at a time, widening one piece's bfloat16
weights to float32 at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = "highest"
CONV = "conv"


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * scale


def short_conv(p, a):
    """The gated short convolution on a [B, S, H] (already normed).  p: in
    [H, 3 H], taps [3, H] (taps[j] weighs u at t - 2 + j), out [H, H]."""
    with jax.default_matmul_precision(HIGHEST):
        p = _f32(p)
        gate_in, gate_out, x = jnp.split(a @ p["in"], 3, axis=-1)
        u = gate_in * x
        n = p["taps"].shape[0]
        s = u.shape[1]
        padded = jnp.pad(u, ((0, 0), (n - 1, 0), (0, 0)))   # zeros before 0
        c = sum(p["taps"][j] * padded[:, j:j + s] for j in range(n))
        return (gate_out * c) @ p["out"]


def rope_halves(x, theta):
    """x [B, S, heads, D] at positions 0 .. S - 1: halves [x1 | x2] turned
    by ``pos / theta**(2i / D)``."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p, a, dims):
    """One attention block on a [B, S, H] (already normed).  p: q [heads * d,
    H] and k [kv_heads * d, H] (held [out, in], the program's layout), v [H,
    kv_heads * d], o [heads * d, H], q_norm, k_norm [d]."""
    with jax.default_matmul_precision(HIGHEST):
        p = _f32(p)
        b, s, _ = a.shape
        d, eps = dims["head_dim"], dims["eps"]
        q = rms_norm((a @ p["q"].T).reshape(b, s, -1, d), p["q_norm"], eps)
        k = rms_norm((a @ p["k"].T).reshape(b, s, -1, d), p["k_norm"], eps)
        v = (a @ p["v"]).reshape(b, s, -1, d)
        q, k = rope_halves(q, dims["theta"]), rope_halves(k, dims["theta"])
        rep = q.shape[2] // k.shape[2]
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(d))
        seen = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        o = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, -1)
        return o @ p["o"]


def dense_ffn(p, x):
    """SwiGLU.  p: gate [H, F], up [H, F], down [F, H]."""
    with jax.default_matmul_precision(HIGHEST):
        p = _f32(p)
        return (jax.nn.silu(x @ p["gate"]) * (x @ p["up"])) @ p["down"]


def expert_weights(router, router_bias, u, dims):
    """[..., experts]: each token's weight for every routed expert, zero off
    the ``topk`` it chose."""
    with jax.default_matmul_precision(HIGHEST):
        s = jax.nn.sigmoid(u @ jnp.asarray(router, jnp.float32))
        _, idx = jax.lax.top_k(s + router_bias, dims["topk"])
        chosen = jnp.take_along_axis(s, idx, -1)
        w = dims["scaling"] * chosen / (
            jnp.sum(chosen, -1, keepdims=True) + 1e-6)
        return jnp.sum(jax.nn.one_hot(idx, s.shape[-1]) * w[..., None], -2)


def one_expert(p, u, weights, index):
    """What the routed expert ``index`` adds: its SwiGLU of EVERY token,
    times the token's weight for it."""
    return weights[..., index, None] * dense_ffn(p, u)


def expert_layer(p, u, dims):
    """p: router [H, experts], router_bias [experts], gate/up/down stacked
    over the held experts."""
    first, count = dims["held"]
    weights = expert_weights(p["router"], p["router_bias"], u, dims)
    return sum(one_expert({k: p[k][e] for k in ("gate", "up", "down")}, u,
                          weights, first + e) for e in range(count))


def at(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


def leaf_index(dims, l):
    """Layer ``l``'s index among the layers of its own kind: where its
    operator's leaves lie in their stack."""
    kinds = dims["layer_types"]
    return sum(k == kinds[l] for k in kinds[:l])


def layer(layers, l, h, dims):
    eps, dense = dims["eps"], dims["first_dense"]
    norm = _f32({k: layers[k][l] for k in ("attn_norm", "ffn_norm")})
    a = rms_norm(h, norm["attn_norm"], eps)
    i = leaf_index(dims, l)
    if dims["layer_types"][l] == CONV:
        h = h + short_conv(at(layers["conv"], i), a)
    else:
        h = h + attention(at(layers["attn"], i), a, dims)
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


def head(embedding, h):
    with jax.default_matmul_precision(HIGHEST):
        return h @ jnp.asarray(embedding, jnp.float32).T


def logits(params, ids, dims):
    """Full forward: ids [B, S] int -> logits [B, S, V] float32."""
    return head(params["tok_emb"], hidden(params, ids, dims))


def loss(params, ids, dims):
    """Mean next-token cross entropy over ids [B, S]."""
    lg = logits(params, ids[:, :-1], dims)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, ids[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(a.astype(jnp.float32)))
                        for a in jax.tree_util.tree_leaves(tree)))


def loss_and_grad_norm(params, ids, dims):
    value, grads = jax.value_and_grad(lambda p: loss(p, ids, dims))(
        _f32(params))
    return value, global_norm(grads)
