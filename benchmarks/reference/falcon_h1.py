"""Falcon-H1 in plain ``jax.numpy``: the benchmark's yardstick for
``correct`` of the ``falcon-h1-34b-instruct`` configuration.

The architecture as its public configuration describes it
(``huggingface.co/tiiuae/Falcon-H1-34B-Instruct`` ``config.json``,
``model_type`` ``falcon_h1``).  Float32 everywhere under
``jax.default_matmul_precision("highest")``; no kernels, no cache, no chunks,
no state carried between calls, no batching tricks: the state-space mixer is
the token-by-token recurrence (a ``lax.scan`` over positions), the
convolution four shifted products over the whole sequence, attention a full
masked softmax.  Nothing is imported from the program under test.

Every layer runs a Mamba-2 mixer and grouped-query attention IN PARALLEL on
the same normed input and adds both to the stream; muP multipliers (``m.*``,
the configuration's nine keys) scale the embedding, both branches, the key,
the five segments of the mixer's in-projection, the feed-forward's gate and
down products and the head.  ``N`` RMSNorm (``rms_norm_eps``, each its own
weight); no projection has a bias, the convolution has one::

    h0 = E[ids] * m.embedding
    a  = N_in(h)
    attention:  q = (a m.attention_in) W_q;  k = ((a m.attention_in) W_k) m.key
                v = (a m.attention_in) W_v;  q, k rotated (rope_theta, all d
                dims, halves [x1 | x2] -> [x1 cos - x2 sin | x2 cos + x1 sin]),
                no norm of q or k;  query i sees keys j <= i, head i reads KV
                head i // (heads / kv_heads)
                A = (softmax(q k^T / sqrt(d)) v W_o) m.attention_out
    mixer:      [z | x | B | C | dt] = ((a m.ssm_in) W_in) * m.ssm   (one
                factor a segment; widths d_ssm | d_ssm | g n | g n | heads)
                [x | B | C] <- silu(conv([x | B | C]) + b)   (depth-wise,
                causal, ``mamba_d_conv`` taps, zeros before the sequence)
                dt = softplus(dt + dt_bias);  a_t = exp(dt_t * A), A = -exp(A_log)
                S_t = a_t S_(t-1) + dt_t x_t B_t^T     (a head of d_head its own
                S [d_head, n]; B, C of the head's group; S_(-1) = 0)
                y_t = S_t C_t + D x_t
                y <- N_groups(y * silu(z))   (the gate FIRST,
                ``mamba_norm_before_gate`` false; RMS over each group's
                d_ssm / g channels, one weight of d_ssm)
                M = (y W_out) m.ssm_out
    h  = h + M + A
    u  = N_ff(h);  h = h + ((silu((u W_g) m.mlp[0]) * (u W_u)) W_d) m.mlp[1]
    logits = (N_f(h) W_head^T) m.lm_head                 (the head is untied)

**Set here because the source is silent, each under ``assumed`` in the
configuration file**: the segment order ``z | x | B | C | dt`` and that
``ssm_multipliers`` follow it; that the ``_in_`` multipliers scale a branch's
input and the ``_out_`` ones its result; the half-rotation layout over the
whole head; the group-wise gated norm; no clamp on ``dt``; a float32 state
(this file is float32 throughout).  The parameter layout is the program's
(a leaf stacked over the layers or a tuple of the layers' arrays; ``q`` and
``k`` held [out, in]): a layout, not mathematics.

The pieces are public so that the benchmark's adapter can run the same
forward a layer, a block of the feed-forward's columns and a block of the
vocabulary at a time, widening one piece's bfloat16 weights to float32 at a
time.
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
    """The attention branch on a [B, S, H] (already normed).  p: q [heads *
    d, H] and k [kv_heads * d, H] (held [out, in], the program's layout), v
    [H, kv_heads * d], o [heads * d, H]."""
    with jax.default_matmul_precision(HIGHEST):
        p, m = _f32(p), dims["mult"]
        b, s, _ = a.shape
        d = dims["head_dim"]
        a = a * m["attention_in"]
        q = (a @ p["q"].T).reshape(b, s, -1, d)
        k = ((a @ p["k"].T) * m["key"]).reshape(b, s, -1, d)
        v = (a @ p["v"]).reshape(b, s, -1, d)
        q, k = rope_halves(q, dims["theta"]), rope_halves(k, dims["theta"])
        rep = q.shape[2] // k.shape[2]
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(d))
        seen = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        o = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, -1)
        return (o @ p["o"]) * m["attention_out"]


def recurrence(x, dt, A, B, C, D):
    """The state-space recurrence, a position at a time from a zero state.
    x [B, S, heads, P]; dt [B, S, heads]; A, D [heads]; B, C [B, S, groups,
    N]; head i reads group i // (heads / groups).  Returns y [B, S, heads,
    P]."""
    heads, groups = x.shape[2], B.shape[2]
    B, C = (jnp.repeat(t, heads // groups, axis=2) for t in (B, C))

    def step(S, row):
        x_t, dt_t, B_t, C_t = row          # [B, heads, P] [B, heads] [B, heads, N]
        S = jnp.exp(dt_t * A)[..., None, None] * S \
            + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, :]
        return S, jnp.sum(S * C_t[:, :, None, :], -1) + D[:, None] * x_t

    S0 = jnp.zeros(x.shape[:1] + x.shape[2:] + B.shape[-1:], jnp.float32)
    _, y = jax.lax.scan(step, S0, tuple(
        jnp.moveaxis(t, 1, 0) for t in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1)


def mixer(p, a, dims):
    """The Mamba-2 branch on a [B, S, H] (already normed).  p: in [H, 2
    d_ssm + 2 g n + heads], conv_w [taps, d_ssm + 2 g n] (conv_w[j] weighs
    the row taps - 1 - j before), conv_b, dt_bias, A_log, D [heads], norm
    [d_ssm], out [d_ssm, H]."""
    with jax.default_matmul_precision(HIGHEST):
        p, m = _f32(p), dims["mult"]
        b, s, _ = a.shape
        heads, P = dims["ssm_heads"], dims["ssm_head_dim"]
        g, n = dims["groups"], dims["d_state"]
        d_ssm, gn = heads * P, g * n
        z, x, B, C, dt = jnp.split(
            (a * m["ssm_in"]) @ p["in"],
            [d_ssm, 2 * d_ssm, 2 * d_ssm + gn, 2 * d_ssm + 2 * gn], axis=-1)
        # one factor a segment, in the segments' order
        z, x, B, C, dt = (t * f for t, f in zip((z, x, B, C, dt), m["ssm"]))
        xbc = jnp.concatenate([x, B, C], -1)
        taps = p["conv_w"].shape[0]
        padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))   # zeros before 0
        xbc = jax.nn.silu(sum(p["conv_w"][j] * padded[:, j:j + s]
                              for j in range(taps)) + p["conv_b"])
        x, B, C = jnp.split(xbc, [d_ssm, d_ssm + gn], axis=-1)
        dt = jax.nn.softplus(dt + p["dt_bias"])      # no clamp (assumed)
        y = recurrence(x.reshape(b, s, heads, P), dt, -jnp.exp(p["A_log"]),
                       B.reshape(b, s, g, n), C.reshape(b, s, g, n), p["D"])
        # the gate first, then the norm over each group's channels
        y = (y.reshape(b, s, d_ssm) * jax.nn.silu(z)).reshape(b, s, g, -1)
        y = y / jnp.sqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                         + dims["eps"])
        return ((y.reshape(b, s, d_ssm) * p["norm"]) @ p["out"]) \
            * m["ssm_out"]


def ffn_inner(p, u, dims):
    """What the columns ``p`` holds of the feed-forward add to its result,
    before the down multiplier: a sum over the intermediate width, so any
    split of gate's and up's columns with down's rows adds up to the whole.
    p: gate [H, F'], up [H, F'], down [F', H]."""
    with jax.default_matmul_precision(HIGHEST):
        p = _f32(p)
        return (jax.nn.silu((u @ p["gate"]) * dims["mult"]["mlp"][0])
                * (u @ p["up"])) @ p["down"]


def dense_ffn(p, u, dims):
    return ffn_inner(p, u, dims) * dims["mult"]["mlp"][1]


def at(leaves, i):
    """Layer ``i`` of a dict of leaves, each stacked over the layers or a
    tuple of the layers' arrays: ``leaf[i]`` reads either."""
    return {name: leaf[i] for name, leaf in leaves.items()}


def layer(layers, l, h, dims):
    eps = dims["eps"]
    norm = _f32({k: layers[k][l] for k in ("attn_norm", "ffn_norm")})
    a = rms_norm(h, norm["attn_norm"], eps)
    h = h + mixer(at(layers["ssm"], l), a, dims) \
        + attention(at(layers["attn"], l), a, dims)
    u = rms_norm(h, norm["ffn_norm"], eps)
    return h + dense_ffn(at(layers["ffn"], l), u, dims)


def embed(embedding, ids, dims):
    return jnp.asarray(embedding[ids], jnp.float32) * dims["mult"]["embedding"]


def hidden(params, ids, dims):
    h = embed(params["tok_emb"], ids, dims)
    for l in range(params["layers"]["attn_norm"].shape[0]):
        h = layer(params["layers"], l, h, dims)
    return rms_norm(h, jnp.asarray(params["norm_f"], jnp.float32),
                    dims["eps"])


def head(weight, h, dims):
    """weight [V', H], any block of the head's rows: the logits of those."""
    with jax.default_matmul_precision(HIGHEST):
        return (h @ jnp.asarray(weight, jnp.float32).T) \
            * dims["mult"]["lm_head"]


def logits(params, ids, dims):
    """Full forward: ids [B, S] int -> logits [B, S, V] float32."""
    return head(params["lm_head"], hidden(params, ids, dims), dims)


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
