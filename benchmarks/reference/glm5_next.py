"""GLM-5.3-Flash in plain ``jax.numpy``: the benchmark's yardstick for
``correct`` of the ``glm-5.3-flash`` configuration.

The architecture as its public configuration describes it
(``huggingface.co/zai-org/GLM-5.3-Flash`` ``config.json``, ``model_type``
``glm5_next_text``) and, for what that file does not carry, as the public
descriptions of its three mechanisms do: Kimi Delta Attention (Kimi Linear,
arXiv 2510.26692), DeepSeek Sparse Attention (DeepSeek-V3.2-Exp: a lightning
indexer's top-k over latent attention) and manifold-constrained
hyper-connections (mHC, arXiv 2512.24880).  Float32 everywhere under
``jax.default_matmul_precision("highest")``; no kernels, no cache, no chunks,
no state carried between calls: a KDA layer is its recurrence ROW BY ROW (a
``lax.scan`` over positions from a zero state), a DSA layer a full masked
softmax in the EXPANDED form (every head's keys and values made from the
latents, never the absorbed product) under the mask its queries' choices
make, the choice by a stable sort of the scores.  Nothing is imported from
the program under test.

``N`` is ``x / sqrt(mean x^2 + eps) * w``; no projection has a bias; ``n`` =
``hc_mult``::

    Stream.  X in R^{n x H} a token.  X_0 = E[id] in each of the n rows.
    For every sublayer F (a layer has two: the operator, then the feed-forward):
      x~   = N_flat(vec(X))                         # one RMSNorm over n H
      a_pre  = alpha_pre  (x~ Phi_pre)  + b_pre     # [n]
      a_post = alpha_post (x~ Phi_post) + b_post    # [n]
      A_res  = alpha_res  mat(x~ Phi_res) + B_res   # [n, n]
      H_pre = sigmoid(a_pre);  H_post = 2 sigmoid(a_post)
      H_res = SK(exp(A_res)): hc_sinkhorn_iters times { rows / (row sums + hc_eps);
                                                        columns / (column sums + hc_eps) }
      u = sum_i H_pre[i] X_i;   y = F(N(u))
      X_i <- sum_j H_res[i, j] X_j + H_post[i] y
    logits = W_head N(sum_i X_i)

    KDA (heads of d_k = d_v = d; a = N(u)):
      q = unit(silu(conv(W_q a)));  k = unit(silu(conv(W_k a)));  v = silu(conv(W_v a))
          # conv: causal depthwise, ``taps`` taps a channel; unit: x / sqrt(sum x^2 + 1e-6) a head
      g = gate_lower_bound * sigmoid(exp(A_log_h) * (W_g2 (W_g1 a) + dt_bias))   # [heads, d]
      beta = sigmoid(W_beta a)
      S <- Diag(exp(g_t)) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T;  o_t = S^T q_t / sqrt(d)
      out = W_o ( N_head(o) * sigmoid(W_z2 (W_z1 a)) )

    DSA (heads of qk | v dims, no rotation):
      cq = N(W_qa a);  q_h = W_qb,h cq;  c = N(W_kva a)
      k_h,s = W_kb,h c_s;  v_h,s = W_vb,h c_s
      o_h = softmax_{s in chosen(t)}(q_h . k_h,s / sqrt(qk)) v_h,s;  out = W_o [o_h]
      indexer (J heads of d_I):  qI_j = rot(W_qI,j cq);  kI_s = rot(LayerNorm(W_kI a_s));
          w = W_w a / sqrt(J d_I)      # rot: the first ``index_rope_dim`` dims, interleaved pairs
          kbar_b = mean of kI over positions P b .. P b + P - 1
          I(t, b) = sum_j w_j relu(qI_j . kbar_b)
      G(t) = (t + 1) // P complete groups;  tail(t) = positions P G(t) .. t
      chosen(t) = the rows of the index_topk / P groups b < G(t) of largest I(t, b)
                  (ties to the lower b), and tail(t);  everything when G(t) <= index_topk / P

    Feed-forward: SwiGLU(u) = W_down( silu(min(W_gate u, x)) * clip(W_up u, -x, x) ),  x = swiglu_limit;
      dense on the ``first_dense`` leading layers; on the rest sigmoid scores over the
      published router width, the choice by score + bias, ``topk`` of them, renormalised
      over the chosen, times ``scaling``, over the experts HELD here (what absent experts
      would add is left out), + one shared SwiGLU

**Departures from the published description, each under ``assumed`` in the
configuration file**: the pooled key is the MEAN of ``index_kpool`` rotated
keys, ``index_topk`` counts tokens, the tail is the open group; the gate's
bounded-sigmoid form; gates of rank 128; the indexer's rotation (first 64
dims, interleaved, theta 10,000) and the LayerNorm on its key; the clamp's
form; ``X_0`` by copies and the sum before the head; a float32 state (this
file is float32 throughout).  The parameter layout is the program's (a leaf
stacked over its layers or a tuple of the layers' arrays): a layout, not
mathematics.

The pieces are public so that the benchmark's adapter can run the same
forward a layer, a block of rows, a group of heads and a block of the
vocabulary at a time, widening one piece's bfloat16 weights to float32 at a
time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = "highest"
KDA, DSA = "linear_attention", "deepseek_sparse_attention"


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def at(tree, i: int):
    """Layer ``i`` of a dict of leaves, each stacked over the layers or a
    tuple of the layers' arrays."""
    return {k: v[i] for k, v in tree.items()}


def leaf_index(dims, i: int) -> int:
    """Layer ``i``'s index among the layers of its own kind."""
    kind = dims["layer_types"][i]
    return sum(t == kind for t in dims["layer_types"][:i])


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def layer_norm(x, w, b, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w + b


def rope_interleaved(x, pos, theta, width: int):
    """Pairs ``(x_0, x_1), (x_2, x_3), ...`` of the first ``width`` dims of
    the last axis turned by ``pos * theta^(-2 i / width)``; x [B, S, ..., D],
    pos [S]."""
    inv = 1.0 / theta ** (jnp.arange(0, width, 2, dtype=jnp.float32) / width)
    ang = pos.astype(jnp.float32)[:, None] * inv                # [S, w / 2]
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (width // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    a, b = x[..., 0:width:2], x[..., 1:width:2]
    turned = jnp.stack([a * cos - b * sin, b * cos + a * sin], -1)
    return jnp.concatenate(
        [turned.reshape(x.shape[:-1] + (width,)), x[..., width:]], -1)


# ----------------------------------------------------------- the stream

def embed(tok_emb, ids, dims):
    """X_0: the embedded row in each of the n rows, [B, S, n, H]."""
    h = jnp.asarray(tok_emb, jnp.float32)[ids]
    return jnp.broadcast_to(h[:, :, None], h.shape[:2] + (dims["n"],)
                            + h.shape[2:])


def sinkhorn(logits, iters: int, eps: float):
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (jnp.sum(m, -1, keepdims=True) + eps)
        m = m / (jnp.sum(m, -2, keepdims=True) + eps)
    return m


def hc_coefficients(p, x, dims):
    """p: one sublayer's norm [n H], phi [n H, 2 n + n n] (``[pre | post |
    res]``), alpha [3], bias [2 n + n n]; x [B, S, n, H].  Returns (H_pre
    [B, S, n], H_post [B, S, n], H_res [B, S, n, n])."""
    n = dims["n"]
    with jax.default_matmul_precision(HIGHEST):
        p = _f32(p)
        flat = rms_norm(x.reshape(x.shape[:2] + (-1,)), p["norm"],
                        dims["eps"])
        a = flat @ p["phi"]
        pre = p["alpha"][0] * a[..., :n] + p["bias"][:n]
        post = p["alpha"][1] * a[..., n:2 * n] + p["bias"][n:2 * n]
        res = (p["alpha"][2] * a[..., 2 * n:] + p["bias"][2 * n:]).reshape(
            a.shape[:-1] + (n, n))
        return jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post), \
            sinkhorn(res, dims["hc_iters"], dims["hc_eps"])


def hc_read(x, pre):
    return jnp.einsum("bsn,bsnh->bsh", pre, x, precision=HIGHEST)


def hc_write(x, res, post, y):
    return jnp.einsum("bsij,bsjh->bsih", res, x, precision=HIGHEST) \
        + post[..., None] * y[:, :, None]


# ----------------------------------------------------------- feed-forward

def swiglu(p, u, limit):
    """p: gate [H, F], up [H, F], down [F, H]."""
    with jax.default_matmul_precision(HIGHEST):
        p = _f32(p)
        g = jnp.minimum(u @ p["gate"], limit)
        v = jnp.clip(u @ p["up"], -limit, limit)
        return (jax.nn.silu(g) * v) @ p["down"]


def expert_choice(router, router_bias, u, dims):
    """(weights [..., topk] renormalised over the chosen and scaled,
    idx [..., topk])."""
    with jax.default_matmul_precision(HIGHEST):
        s = jax.nn.sigmoid(u @ jnp.asarray(router, jnp.float32))
        _, idx = jax.lax.top_k(s + router_bias, dims["topk"])
        chosen = jnp.take_along_axis(s, idx, -1)
        return dims["scaling"] * chosen / (
            jnp.sum(chosen, -1, keepdims=True) + 1e-20), idx


def one_expert(p, u, weights, idx, index, dims):
    """What the routed expert with global index ``index`` adds, for every
    token."""
    w = jnp.sum(jnp.where(idx == index, weights, 0.0), -1, keepdims=True)
    return w * swiglu(p, u, dims["limit"])


def shared_expert(p, u, dims):
    return swiglu({"gate": p["shared_gate"], "up": p["shared_up"],
                   "down": p["shared_down"]}, u, dims["limit"])


def expert_layer(p, u, dims, held=None):
    """shared(u) + the routed part of the share of the experts ``p`` holds
    (``held`` = (first, count); None: ``dims``')."""
    first, count = dims["held"] if held is None else held
    weights, idx = expert_choice(p["router"], p["router_bias"], u, dims)
    out = shared_expert(p, u, dims)
    for e in range(count):
        out = out + one_expert({k: p[k][e] for k in ("gate", "up", "down")},
                               u, weights, idx, first + e, dims)
    return out


# ----------------------------------------------------------- KDA

def kda(p, a, dims, heads=None):
    """The KDA operator on a [B, S, H], row by row from a zero state;
    ``heads`` = (first, count): those heads' part of the result alone (the
    parts add)."""
    nh, d, taps = dims["kda_heads"], dims["kda_dim"], dims["taps"]
    lo, cnt = (0, nh) if heads is None else heads
    w = nh * d
    with jax.default_matmul_precision(HIGHEST):
        p = _f32(p)
        b, s, _ = a.shape
        cols = jnp.concatenate([part * w + lo * d + jnp.arange(cnt * d)
                                for part in range(3)])
        own = lo * d + jnp.arange(cnt * d)
        qkv = a @ p["qkv"][:, cols]
        past = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
        conv = sum(p["conv_w"][j, cols] * past[:, j:j + s]
                   for j in range(taps))
        q, k, v = (t.reshape(b, s, cnt, d)
                   for t in jnp.split(jax.nn.silu(conv), 3, -1))
        unit = lambda x: x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
        q, k = unit(q), unit(k)
        g = dims["lower"] * jax.nn.sigmoid(
            jnp.exp(p["A_log"][lo:lo + cnt])[:, None]
            * ((a @ p["g1"]) @ p["g2"][:, own]
               + p["dt_bias"][own]).reshape(b, s, cnt, d))
        beta = jax.nn.sigmoid(a @ p["beta"][:, lo:lo + cnt])
        z = (a @ p["z1"]) @ p["z2"][:, own]

        def row(S, xs):
            q_t, k_t, v_t, g_t, b_t = xs
            S = jnp.exp(g_t)[..., None] * S
            dlt = b_t[..., None] * (v_t - jnp.einsum("bhkd,bhk->bhd", S, k_t))
            S = S + k_t[..., None] * dlt[..., None, :]
            return S, jnp.einsum("bhkd,bhk->bhd", S, q_t) * d ** -0.5

        _, o = jax.lax.scan(
            row, jnp.zeros((b, cnt, d, d), jnp.float32),
            tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
        o = jnp.moveaxis(o, 0, 1)                              # B S h d
        y = rms_norm(o, p["norm"], dims["eps"]).reshape(b, s, -1) \
            * jax.nn.sigmoid(z)
        return y @ p["o"][own]


# ----------------------------------------------------------- DSA

def index_keys(p, a, dims):
    """What a DSA layer keeps of a sequence: (c [B, S, kv_lora] the latents,
    kbar [B, S // P, d_I] the pooled indexer keys of the complete groups)."""
    P = dims["pool"]
    with jax.default_matmul_precision(HIGHEST):
        p = _f32(p)
        b, s, _ = a.shape
        c = rms_norm(a @ p["kva"], p["kv_norm"], dims["eps"])
        ki = layer_norm(a @ p["ik"], p["ik_w"], p["ik_b"], dims["eps"])
        ki = rope_interleaved(ki, jnp.arange(s), dims["theta"],
                              dims["index_rope_dim"])
        full = s // P
        return c, ki[:, :full * P].reshape(b, full, P, -1).mean(2)


def chosen_positions(p, cq, a_rows, pos, kbar, dims, s: int):
    """The positions each query reads: mask [B, Q, s] bool.  cq [B, Q,
    q_lora] and a_rows [B, Q, H] the queries' rows at positions ``pos``
    [Q]."""
    J, dI, P, K = dims["index_heads"], dims["index_dim"], dims["pool"], \
        dims["topk_groups"]
    b, nq, _ = cq.shape
    qi = rope_interleaved((cq @ p["iq"]).reshape(b, nq, J, dI), pos,
                          dims["theta"], dims["index_rope_dim"])
    w = (a_rows @ p["iw"]) * (J * dI) ** -0.5
    scores = jnp.einsum(
        "bqj,bqjg->bqg", w,
        jax.nn.relu(jnp.einsum("bqjd,bgd->bqjg", qi, kbar)))
    n_groups = kbar.shape[1]
    complete = (pos + 1) // P                                   # [Q]
    seen = jnp.arange(n_groups)[None] < complete[:, None]       # [Q, G]
    scores = jnp.where(seen[None], scores, -jnp.inf)
    # rank by falling score, ties to the lower group: a stable sort
    order = jnp.argsort(-scores, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    group = (rank < K) & seen[None]                             # [B, Q, G]
    at = jnp.arange(s)
    in_group = jnp.take_along_axis(
        jnp.pad(group, ((0, 0), (0, 0), (0, 1))),
        jnp.broadcast_to(jnp.minimum(at // P, n_groups)[None, None],
                         (b, nq, s)), -1)
    tail = (at[None] >= (complete * P)[:, None]) & (at[None] <= pos[:, None])
    return (in_group | tail[None]) & (at[None] <= pos[:, None])[None]


def dsa_rows(p, a_rows, pos, c, kbar, dims, heads=None,
             with_choice: bool = False):
    """The DSA operator for the queries ``a_rows`` [B, Q, H] at positions
    ``pos`` [Q] over the sequence's latents ``c`` [B, S, kv_lora] and pooled
    keys ``kbar``; ``heads`` = (first, count): those heads' part of the
    result alone (the parts add).  The expanded form: every head's keys and
    values made from the latents."""
    nh, qk, dv = dims["heads"], dims["qk"], dims["v_dim"]
    lo, cnt = (0, nh) if heads is None else heads
    with jax.default_matmul_precision(HIGHEST):
        p = _f32(p)
        b, nq, _ = a_rows.shape
        s = c.shape[1]
        cq = rms_norm(a_rows @ p["qa"], p["q_norm"], dims["eps"])
        mask = chosen_positions(p, cq, a_rows, pos, kbar, dims, s)
        q = (cq @ p["qb"]).reshape(b, nq, nh, qk)[:, :, lo:lo + cnt]
        k = jnp.einsum("bsc,hcd->bshd", c, p["kb"][lo:lo + cnt])
        v = jnp.einsum("bsc,hcd->bshd", c, p["vb"][lo:lo + cnt])
        scores = jnp.einsum("bqhd,bshd->bhqs", q, k) * qk ** -0.5
        scores = jnp.where(mask[:, None], scores, -jnp.inf)
        o = jnp.einsum("bhqs,bshd->bqhd", jax.nn.softmax(scores, -1), v)
        out = o.reshape(b, nq, -1) @ p["o"][lo * dv:(lo + cnt) * dv]
        return (out, mask) if with_choice else out


def dsa(p, a, dims):
    c, kbar = index_keys(p, a, dims)
    return dsa_rows(p, a, jnp.arange(a.shape[1]), c, kbar, dims)


# ----------------------------------------------------------- the model

def head(w, norm_f, x, dims):
    """logits = W_head N(sum_i X_i); x [B, S, n, H]."""
    with jax.default_matmul_precision(HIGHEST):
        h = rms_norm(jnp.sum(x, -2), jnp.asarray(norm_f, jnp.float32),
                     dims["eps"])
        return h @ jnp.asarray(w, jnp.float32).T


def sublayer(layers, i: int, sub: int, x, branch, dims):
    """One residual step: ``branch(a)`` is the sublayer on its normed
    input."""
    pre, post, res = hc_coefficients(at(layers["hc"], 2 * i + sub), x, dims)
    norm = layers["attn_norm" if sub == 0 else "ffn_norm"][i]
    a = rms_norm(hc_read(x, pre), jnp.asarray(norm, jnp.float32),
                 dims["eps"])
    return hc_write(x, res, post, branch(a))


def operator(layers, i: int, a, dims):
    j = leaf_index(dims, i)
    if dims["layer_types"][i] == KDA:
        return kda(at(layers["kda"], j), a, dims)
    return dsa(at(layers["dsa"], j), a, dims)


def feed_forward(layers, i: int, u, dims):
    d = dims["first_dense"]
    if i < d:
        return swiglu(at(layers["ffn"], i), u, dims["limit"])
    return expert_layer(at(layers["moe"], i - d), u, dims)


def logits(params, ids, dims):
    layers = params["layers"]
    x = embed(params["tok_emb"], ids, dims)
    for i in range(len(dims["layer_types"])):
        x = sublayer(layers, i, 0, x,
                     lambda a: operator(layers, i, a, dims), dims)
        x = sublayer(layers, i, 1, x,
                     lambda u: feed_forward(layers, i, u, dims), dims)
    return head(params["lm_head"], params["norm_f"], x, dims)


def loss_and_grad_norm(params, ids, dims):
    """Next-token loss and the gradients' global norm (test size: no cell
    trains this configuration)."""
    def loss(p):
        lg = logits(p, ids, dims)
        logp = jax.nn.log_softmax(lg[:, :-1], -1)
        return -jnp.mean(jnp.take_along_axis(logp, ids[:, 1:, None], -1))

    value, grads = jax.value_and_grad(loss)(_f32(params))
    return value, jnp.sqrt(sum(jnp.sum(g * g)
                               for g in jax.tree_util.tree_leaves(grads)))
