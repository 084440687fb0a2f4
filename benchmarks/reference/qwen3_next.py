"""Qwen3-Next in plain ``jax.numpy``: the benchmark's yardstick for ``correct``
of the ``qwen3-next-80b-a3b-instruct`` configuration.

The architecture as its public configuration describes it
(``huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct`` ``config.json``,
``model_type`` ``qwen3_next``).  Float32 everywhere under
``jax.default_matmul_precision("highest")``; no kernels, no cache, no chunks,
no state carried between calls, no batching tricks: the Gated DeltaNet layer
is the recurrence ROW BY ROW (a ``lax.scan`` over positions from a zero
state), the convolution four shifted products over the whole sequence,
attention a full masked softmax, every held expert computed on every token
and weighed by the ``[tokens, experts]`` matrix.  Nothing is imported from
the program under test.

Every norm ``N`` is ``x / sqrt(mean x^2 + eps) * (1 + w)`` with its own ``w``
(``rms_norm_eps``); no projection has a bias.  Layer ``i`` is a full layer
where ``(i + 1) % full_attention_interval == 0``, else Gated DeltaNet; every
layer's feed-forward is the expert layer (``decoder_sparse_step`` 1,
``mlp_only_layers`` [])::

    h0 = E[ids]
    h  = h + Op(N_in(h));   h = h + FF(N_ff(h));   logits = N_f(h) W_head^T
    DeltaNet:   [q | k | v | z] a KEY head = a W_qkvz;  [b | a_] = a W_ba
                (a key head's d_k of query, d_k of key, then the d_v of value
                and the d_v of gate of each of the value heads it serves)
                [q | k | v] <- silu(conv([q | k | v]))   (depth-wise, causal,
                ``linear_conv_kernel_dim`` taps, no bias, zeros before the
                sequence)
                beta = sigmoid(b);  g = -exp(A_log) softplus(a_ + dt_bias)
                q^ = q / sqrt(sum q^2 + 1e-6) / sqrt(d_k);  k^ likewise, unscaled
                a value head's S [d_k, d_v] from zeros, its key head's q^, k^:
                S <- exp(g_t) S;  d = beta_t (v_t - S^T k^_t)
                S <- S + k^_t d^T;  o_t = S^T q^_t
                y = o / sqrt(mean o^2 + eps) * w_n * silu(z)   (over the head's
                d_v: the norm FIRST, then the gate; one w_n for every head,
                a plain weight, not 1 + w)
                Op = y W_o
    full layer: [q | gate] a head = a W_q;  k = a W_k;  v = a W_v
                q = N_q(q), k = N_k(k) per head (1 + w)
                the head's first ``head_dim * partial_rotary_factor`` dims
                rotated (rope_theta, halves [x1 | x2] -> [x1 cos - x2 sin |
                x2 cos + x1 sin]), the rest passed; query i sees keys j <= i,
                head i reads KV head i // (heads / kv_heads)
                Op = (softmax(q k^T / sqrt(d)) v * sigmoid(gate)) W_o
    FF:         p = softmax(u W_r) over ALL experts; the ``num_experts_per_tok``
                largest, divided by their sum (``norm_topk_prob``)
                sum over the chosen experts HELD here of
                p_e (silu(u W_gate_e) * (u W_up_e)) W_down_e
                + sigmoid(u . w_sg) (silu(u W_sg) * (u W_su)) W_sd

**One chip's share**: ``dims["held"] = (first, count)`` says which routed
experts the parameters hold; an expert chosen but held elsewhere adds nothing
(that chip adds it), and its weight stays in the renormalising sum: the router
is whole on every chip.  The vocabulary is the slice the parameters hold.

**Departures from the published description, each under ``assumed`` in the
configuration file**: the order of ``W_qkvz``'s and ``W_ba``'s outputs (the
config gives widths only: grouped a key head, as above); the convolution over
``[q | k | v]`` before the split; a float32 state (this file is float32
throughout); the multi-token-prediction module ``described_as`` names is left
out (``config`` has no key for it).  The parameter layout is the program's (a
leaf stacked over its layers or a tuple of the layers' arrays; ``q`` and
``k`` of attention held [out, in]): a layout, not mathematics.

The pieces are public so that the benchmark's adapter can run the same
forward a layer, an expert and a block of the vocabulary at a time, widening
one piece's bfloat16 weights to float32 at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = "highest"


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def rms_norm(x, w, eps):
    """``x / sqrt(mean x^2 + eps) * (1 + w)``."""
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * (1.0 + w)


def rope_leading(x, theta, rotary_dim):
    """x [B, S, heads, D] at positions 0 .. S - 1: the first ``rotary_dim``
    dims as halves [x1 | x2] turned by ``pos / theta**(2i / rotary_dim)``,
    the other dims as they are."""
    r = rotary_dim
    inv = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def attention(p, a, dims):
    """The gated attention operator on a [B, S, H] (already normed).  p: q
    [heads * 2 d, H] (a head's d of query then its d of gate) and k
    [kv_heads * d, H] (held [out, in], the program's layout), v [H, kv_heads
    * d], o [heads * d, H], q_norm / k_norm [d]."""
    with jax.default_matmul_precision(HIGHEST):
        p = _f32(p)
        b, s, _ = a.shape
        d = dims["head_dim"]
        qg = (a @ p["q"].T).reshape(b, s, -1, 2 * d)
        q, gate = qg[..., :d], qg[..., d:]
        k = (a @ p["k"].T).reshape(b, s, -1, d)
        v = (a @ p["v"]).reshape(b, s, -1, d)
        q = rms_norm(q, p["q_norm"], dims["eps"])
        k = rms_norm(k, p["k_norm"], dims["eps"])
        q = rope_leading(q, dims["theta"], dims["rotary_dim"])
        k = rope_leading(k, dims["theta"], dims["rotary_dim"])
        rep = q.shape[2] // k.shape[2]
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(d))
        seen = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        o = jnp.einsum("bhqk,bkhd->bqhd", probs, v) * jax.nn.sigmoid(gate)
        return o.reshape(b, s, -1) @ p["o"]


def delta_recurrence(q, k, v, g, beta):
    """The gated delta rule, a position at a time from a zero state.  q, k
    [B, S, heads, d_k] (already of unit length, q scaled), v [B, S, heads,
    d_v], g, beta [B, S, heads].  Returns o [B, S, heads, d_v]."""
    def step(S, row):
        q_t, k_t, v_t, g_t, b_t = row
        S = jnp.exp(g_t)[..., None, None] * S
        d = b_t[..., None] * (v_t - jnp.einsum("bhkd,bhk->bhd", S, k_t))
        S = S + k_t[..., :, None] * d[..., None, :]
        return S, jnp.einsum("bhkd,bhk->bhd", S, q_t)

    S0 = jnp.zeros(q.shape[:1] + q.shape[2:] + v.shape[-1:], jnp.float32)
    _, o = jax.lax.scan(step, S0, tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def unit(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)


def mixer(p, a, dims):
    """The Gated DeltaNet operator on a [B, S, H] (already normed).  p: qkvz
    [H, 2 h_k d_k + 2 h_v d_v] and ba [H, 2 h_v], both grouped a key head,
    conv_w [taps, 2 h_k d_k + h_v d_v] (conv_w[j] weighs the row taps - 1 - j
    before), dt_bias, A_log [h_v], norm [d_v], out [h_v d_v, H]."""
    with jax.default_matmul_precision(HIGHEST):
        p = _f32(p)
        b, s, _ = a.shape
        hk, hv = dims["gdn_key_heads"], dims["gdn_value_heads"]
        dk, dv, rep = dims["gdn_key_dim"], dims["gdn_value_dim"], hv // hk
        qkvz = (a @ p["qkvz"]).reshape(b, s, hk, 2 * dk + 2 * rep * dv)
        q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
        v = qkvz[..., 2 * dk:2 * dk + rep * dv].reshape(b, s, hv * dv)
        z = qkvz[..., 2 * dk + rep * dv:].reshape(b, s, hv, dv)
        ba = (a @ p["ba"]).reshape(b, s, hk, 2 * rep)
        beta = jax.nn.sigmoid(ba[..., :rep]).reshape(b, s, hv)
        g = -jnp.exp(p["A_log"]) * jax.nn.softplus(
            ba[..., rep:].reshape(b, s, hv) + p["dt_bias"])
        qkv = jnp.concatenate([q.reshape(b, s, -1), k.reshape(b, s, -1), v],
                              -1)
        taps = p["conv_w"].shape[0]
        padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))   # zeros before 0
        qkv = jax.nn.silu(sum(p["conv_w"][j] * padded[:, j:j + s]
                              for j in range(taps)))
        q = qkv[..., :hk * dk].reshape(b, s, hk, dk)
        k = qkv[..., hk * dk:2 * hk * dk].reshape(b, s, hk, dk)
        v = qkv[..., 2 * hk * dk:].reshape(b, s, hv, dv)
        # value head i reads key head i // rep
        q = jnp.repeat(unit(q) / jnp.sqrt(jnp.float32(dk)), rep, axis=2)
        k = jnp.repeat(unit(k), rep, axis=2)
        o = delta_recurrence(q, k, v, g, beta)
        # the norm first, then the gate; a plain weight
        y = o / jnp.sqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                         + dims["eps"]) * p["norm"] * jax.nn.silu(z)
        return y.reshape(b, s, -1) @ p["out"]


def expert_weights(router, u, dims):
    """[B, S, experts] float32: each token's renormalised probability on the
    experts it chose, zero elsewhere."""
    with jax.default_matmul_precision(HIGHEST):
        probs = jax.nn.softmax(u @ jnp.asarray(router, jnp.float32), -1)
        top, idx = jax.lax.top_k(probs, dims["topk"])
        top = top / jnp.sum(top, -1, keepdims=True)
        return jnp.sum(jax.nn.one_hot(idx, probs.shape[-1]) * top[..., None],
                       -2)


def swiglu(p, u):
    """p: gate [H, F], up [H, F], down [F, H]."""
    with jax.default_matmul_precision(HIGHEST):
        p = _f32(p)
        return (jax.nn.silu(u @ p["gate"]) * (u @ p["up"])) @ p["down"]


def one_expert(p, u, weights, index):
    """What routed expert ``index`` (global) adds: its SwiGLU on every token,
    weighed by the token's weight on it (zero where it was not chosen)."""
    return swiglu(p, u) * jax.lax.dynamic_index_in_dim(
        weights, index, -1, keepdims=True)


def shared_expert(p, u):
    """p: shared_gate, shared_up [H, F_s], shared_down [F_s, H],
    shared_gate_w [H]: the shared SwiGLU times ``sigmoid(u . w_sg)`` a
    token."""
    with jax.default_matmul_precision(HIGHEST):
        w = jnp.asarray(p["shared_gate_w"], jnp.float32)
        return swiglu({"gate": p["shared_gate"], "up": p["shared_up"],
                       "down": p["shared_down"]}, u) \
            * jax.nn.sigmoid(u @ w)[..., None]


def at(leaves, i):
    """Layer ``i`` of a dict of leaves, each stacked over its layers or a
    tuple of the layers' arrays: ``leaf[i]`` reads either."""
    return {name: leaf[i] for name, leaf in leaves.items()}


def is_full(dims, l: int) -> bool:
    return (l + 1) % dims["full_interval"] == 0


def leaf_index(dims, l: int) -> int:
    """Layer ``l``'s index among the layers of its own kind."""
    return sum(is_full(dims, j) == is_full(dims, l) for j in range(l))


def expert_layer(moe, u, dims):
    first, count = dims["held"]
    weights = expert_weights(moe["router"], u, dims)
    out = shared_expert(moe, u)
    for e in range(count):
        out = out + one_expert({k: moe[k][e] for k in ("gate", "up", "down")},
                               u, weights, first + e)
    return out


def layer(layers, l, h, dims):
    eps = dims["eps"]
    norm = _f32({k: layers[k][l] for k in ("attn_norm", "ffn_norm")})
    a = rms_norm(h, norm["attn_norm"], eps)
    i = leaf_index(dims, l)
    h = h + (attention(at(layers["attn"], i), a, dims) if is_full(dims, l)
             else mixer(at(layers["gdn"], i), a, dims))
    u = rms_norm(h, norm["ffn_norm"], eps)
    return h + expert_layer(at(layers["moe"], l), u, dims)


def embed(embedding, ids):
    return jnp.asarray(embedding[ids], jnp.float32)


def hidden(params, ids, dims):
    h = embed(params["tok_emb"], ids)
    for l in range(params["layers"]["attn_norm"].shape[0]):
        h = layer(params["layers"], l, h, dims)
    return rms_norm(h, jnp.asarray(params["norm_f"], jnp.float32),
                    dims["eps"])


def head(weight, h):
    """weight [V', H], any block of the head's rows: the logits of those."""
    with jax.default_matmul_precision(HIGHEST):
        return h @ jnp.asarray(weight, jnp.float32).T


def logits(params, ids, dims):
    """Full forward: ids [B, S] int -> logits [B, S, V] float32."""
    return head(params["lm_head"], hidden(params, ids, dims))


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
