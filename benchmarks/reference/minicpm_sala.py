"""MiniCPM-SALA in plain ``jax.numpy``: the benchmark's yardstick for
``correct`` of the ``minicpm-sala`` configuration.

The architecture as its public configuration describes it
(``huggingface.co/openbmb/MiniCPM-SALA`` ``config.json``, ``model_type``
``minicpm_sala``) and, for what that file does not carry, as the family's
papers do (MiniCPM4, arXiv 2506.07900; InfLLM-V2, arXiv 2509.24663; Lightning
Attention-2 / MiniMax-01 for the decay).  Float32 everywhere under
``jax.default_matmul_precision("highest")``; no kernels, no cache, no chunks,
no state carried between calls, no batching tricks: a Lightning layer is its
recurrence ROW BY ROW (a ``lax.scan`` over positions from a zero state), a
sparse layer a full masked softmax under the mask its queries' choices make,
the choice by explicit ranks over a window-shares-a-position-with-block test
applied to every candidate window round a block.
Nothing is imported from the program under test.

Every norm ``N`` is ``x / sqrt(mean x^2 + eps) * w`` (a plain weight); no
projection has a bias.  ``L`` = ``dims["published_layers"]`` whatever is held;
the layer held at index ``i`` has the published index ``l = first_layer + i``
and the kind ``dims["mixer_types"][i]``::

    h0 = scale_emb * E[ids]
    h  = h + branch * Op(N_in(h));   h = h + branch * FF(N_ff(h))     # branch = scale_depth / sqrt(L)
    logits = N_f(h) W_head^T / (hidden / dim_model_base)
    FF:         (silu(u W_gate) * (u W_up)) W_down
    Lightning:  q = a W_q, k = a W_k, v = a W_v a head; q = N_q(q), k = N_k(k)
                over the head; both rotated over the WHOLE head (rope_theta,
                halves [x1 | x2] -> [x1 cos - x2 sin | x2 cos + x1 sin]);
                q <- q / sqrt(d)
                a head's S [d, d] from zeros:  S <- lambda_h S + k_t v_t^T;  o_t = S^T q_t
                lambda_h = exp(-s_h f_l),  s_h = 2^(-8 (h + 1) / heads),
                f_l = 1 - l / (L - 1) + 1e-5
                Op = (N_o(o) * sigmoid(a W_g)) W_o         # N_o over the head, one weight
    sparse:     q = a W_q, k = a W_k, v = a W_v a head; q = N_q(q), k = N_k(k);
                NOT rotated; head i reads KV head i // (heads / kv_heads)
                compressed keys of a KV head:  c_i = mean(k[stride i : stride i + kernel]),
                seen by the query at t when stride i + kernel <= t + 1
                p_h = softmax_i(q_h . c_i / sqrt(d)) over the windows t sees
                P   = the sum of p_h over the query heads of the KV head
                a block is ``block`` positions; window i OVERLAPS block j
                when they share a position; score_j = max of P over the
                windows that overlap j (among those t sees)
                forced: the first ``init_blocks`` blocks and the blocks that
                hold positions t - local + 1 .. t
                chosen(t, KV head): the ``topk`` best-scoring blocks among
                those with a position <= t, the forced ones counted in and
                first
                o = causal softmax(q k^T / sqrt(d)) v over the positions of
                the chosen blocks; over EVERY position <= t when the
                sequence is shorter than ``dense_len`` at the call that
                computes t: a prompt's token by the PROMPT's length (the
                published prefill is one call), a generated token by t + 1
                Op = (o * sigmoid(a W_g)) W_o

**Departures from the published description, each under ``assumed`` in the
configuration file**: the sparse layers' sizes (window 32 at stride 16,
blocks of 64, top 64, one initial block, 2,048 local positions, dense under
8,192) and the max-pool form of a block's score are the family's public
convention and not keys of the catalog's ``config`` (the family's kernel pools
five windows at stride four: with a window of two strides and a block of four
those are exactly the windows that share a position with the block, the
statement held to here); both gates are full projections under a sigmoid;
``N_o`` is one weight over a head's dims; the state is float32 (this file is
float32 throughout).  The parameter layout is the program's (a leaf stacked
over its layers or a tuple of the layers' arrays; ``q`` and ``k`` of a sparse
layer held [out, in]): a layout, not mathematics.

The pieces are public so that the benchmark's adapter can run the same
forward a layer, a block of queries and a block of the vocabulary at a time,
widening one piece's bfloat16 weights to float32 at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = "highest"
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def at(tree, i: int):
    """Layer ``i`` of leaves stacked over layers or held a layer an array."""
    return jax.tree_util.tree_map(
        lambda a: a[i], tree, is_leaf=lambda a: isinstance(a, tuple))


def is_sparse(dims, i: int) -> bool:
    return dims["mixer_types"][i] == SPARSE


def leaf_index(dims, i: int) -> int:
    """Layer ``i``'s index among the layers of its own kind."""
    return sum(m == dims["mixer_types"][i] for m in dims["mixer_types"][:i])


def rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def rope(x, theta):
    """x [B, S, heads, D] at positions 0 .. S - 1, the whole head as halves."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def embed(tok_emb, ids, dims):
    return dims["scale_emb"] * jnp.asarray(tok_emb, jnp.float32)[ids]


def feed_forward(p, u):
    with jax.default_matmul_precision(HIGHEST):
        p = _f32(p)
        return (jax.nn.silu(u @ p["gate"]) * (u @ p["up"])) @ p["down"]


def head(w, h, dims):
    with jax.default_matmul_precision(HIGHEST):
        return h @ jnp.asarray(w, jnp.float32).T \
            / (h.shape[-1] / dims["dim_model_base"])


# --------------------------------------------------------------- Lightning

def decay(dims, i: int):
    """lambda_h [heads] of the layer held at index ``i``."""
    n = dims["lightning_heads"]
    s = 2.0 ** (-8.0 * jnp.arange(1, n + 1, dtype=jnp.float32) / n)
    f = 1.0 - (dims["first_layer"] + i) / (dims["published_layers"] - 1) \
        + 1e-5
    return jnp.exp(-s * f)


def lightning(p, a, dims, i: int, heads=None):
    """The Lightning operator of the layer held at index ``i`` on a [B, S,
    H]: the recurrence row by row from a zero state.  ``heads`` (first,
    count): those heads' part of the result alone (a head's columns of the
    four in-projections, its rows of W_o; the parts add up to the whole,
    the norm being a head's own), for a caller whose memory cannot hold
    every head's rows at once."""
    with jax.default_matmul_precision(HIGHEST):
        d = dims["head_dim"]
        lo, n = heads or (0, dims["lightning_heads"])
        cols = slice(lo * d, (lo + n) * d)
        p = _f32({**{m: p[m][:, cols] for m in ("q", "k", "v", "g")},
                  "o": p["o"][cols], **{m: p[m] for m in (
                      "q_norm", "k_norm", "norm")}})
        b, s, _ = a.shape
        q, k, v = ((a @ p[m]).reshape(b, s, -1, d) for m in ("q", "k", "v"))
        q = rope(rms_norm(q, p["q_norm"], dims["eps"]), dims["theta"]) \
            / jnp.sqrt(jnp.float32(d))
        k = rope(rms_norm(k, p["k_norm"], dims["eps"]), dims["theta"])
        lam = decay(dims, i)[lo:lo + n][None, :, None, None]

        def row(S, x):
            q_t, k_t, v_t = x                        # [B, heads, d]
            S = lam * S + k_t[..., :, None] * v_t[..., None, :]
            return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

        _, o = jax.lax.scan(
            row, jnp.zeros((b, q.shape[2], d, d), jnp.float32),
            tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v)))
        o = rms_norm(jnp.moveaxis(o, 0, 1), p["norm"], dims["eps"])
        return (o.reshape(b, s, -1) * jax.nn.sigmoid(a @ p["g"])) @ p["o"]


# ------------------------------------------------------------------ sparse

def sparse_tokens(s: int, dims, prompt_len=None):
    """[S] bool: the token at t reads its chosen blocks (else every
    position).  ``prompt_len``: the first that many tokens are a prompt
    (None: all of them), the rest were generated one a call."""
    t = jnp.arange(s)
    own = s if prompt_len is None else prompt_len
    return jnp.where(t < own, own >= dims["dense_len"],
                     t + 1 >= dims["dense_len"])


def sparse_keys(p, a, dims):
    """(k, v [B, S, kv_heads, d], compressed keys [B, W, kv_heads, d]) of a
    sparse layer over the whole sequence: every WHOLE window of ``kernel``
    keys at ``stride`` (W = 0 where the sequence is shorter than one)."""
    with jax.default_matmul_precision(HIGHEST):
        p = _f32(p)
        b, s, _ = a.shape
        d = dims["head_dim"]
        k = rms_norm((a @ p["k"].T).reshape(b, s, -1, d), p["k_norm"],
                     dims["eps"])
        v = (a @ p["v"]).reshape(b, s, -1, d)
        n_w = max((s - dims["kernel"]) // dims["stride"] + 1, 0)
        comp = jnp.stack(
            [k[:, dims["stride"] * i:dims["stride"] * i + dims["kernel"]]
             .mean(1) for i in range(n_w)], 1) if n_w \
            else jnp.zeros((b, 0) + k.shape[2:], jnp.float32)
        return k, v, comp


def chosen_blocks(q, comp, pos, dims, n_blocks: int):
    """[B, kv_heads, Q, n_blocks] bool: the blocks the queries q [B, Q,
    heads, d] at positions ``pos`` [Q] choose, by the module's statement."""
    with jax.default_matmul_precision(HIGHEST):
        b, nq, nh, d = q.shape
        n_w, g = comp.shape[1:3]
        stride, kernel, block = dims["stride"], dims["kernel"], dims["block"]
        j = jnp.arange(n_blocks)
        t = pos[:, None]
        forced = (j[None] < dims["init_blocks"]) | (
            (block * j[None] + block - 1 >= t - dims["local"] + 1)
            & (block * j[None] <= t))                         # [Q, blocks]
        visible = block * j[None] <= t
        score = jnp.zeros((b, g, nq, n_blocks), jnp.float32)
        if n_w:
            w = jnp.arange(n_w)
            seen = stride * w[None] + kernel <= t + 1          # [Q, W]
            s = jnp.einsum("bqgrd,bwgd->bgrqw",
                           q.reshape(b, nq, g, nh // g, d), comp) \
                / jnp.sqrt(jnp.float32(d))
            s = jnp.where(seen, s, -jnp.inf)
            top = jnp.max(s, -1, keepdims=True)
            e = jnp.where(seen, jnp.exp(s - jnp.where(
                jnp.isfinite(top), top, 0.0)), 0.0)
            p = e / jnp.maximum(e.sum(-1, keepdims=True), 1e-30)
            P = p.sum(2)                                      # [B, g, Q, W]
            # the windows that share a position with block j, tested one
            # candidate offset at a time (a [W, blocks] matrix of the test
            # times P would not fit at length): candidates a window's
            # length either side of the block, the test decides
            for o in range(-(kernel // stride) - 1, block // stride + 2):
                wi = (block // stride) * j + o
                share = (wi >= 0) & (wi < n_w) \
                    & (stride * wi + kernel - 1 >= block * j) \
                    & (stride * wi <= block * j + block - 1)
                score = jnp.maximum(score, jnp.where(
                    share, jnp.take(P, jnp.clip(wi, 0, n_w - 1), axis=-1),
                    0.0))
        score = jnp.where(forced, jnp.inf, score)
        score = jnp.where(visible, score, -jnp.inf)
        rank = jnp.argsort(jnp.argsort(-score, axis=-1, stable=True), axis=-1)
        return (rank < dims["topk"]) & visible


def sparse_rows(p, a_rows, pos, sparse, k, v, comp, dims, *,
                with_choice: bool = False):
    """The sparse operator's result for the queries ``a_rows`` [B, Q, H]
    (normed) at positions ``pos`` [Q], ``sparse`` [Q] bool which of them read
    their choice, over the sequence's :func:`sparse_keys`."""
    with jax.default_matmul_precision(HIGHEST):
        p = _f32(p)
        b, nq, _ = a_rows.shape
        d, s = dims["head_dim"], k.shape[1]
        g = k.shape[2]
        q = rms_norm((a_rows @ p["q"].T).reshape(b, nq, -1, d), p["q_norm"],
                     dims["eps"])
        n_blocks = -(-s // dims["block"])
        choice = chosen_blocks(q, comp, pos, dims, n_blocks)
        keys = jnp.arange(s)
        mask = (keys[None] <= pos[:, None])[None, None] & (
            jnp.repeat(choice, dims["block"], axis=-1)[..., :s]
            | ~sparse[None, None, :, None])                   # [B, g, Q, S]
        scores = jnp.einsum("bqgrd,bkgd->bgrqk",
                            q.reshape(b, nq, g, -1, d), k) \
            / jnp.sqrt(jnp.float32(d))
        scores = jnp.where(mask[:, :, None], scores, -jnp.inf)
        o = jnp.einsum("bgrqk,bkgd->bqgrd", jax.nn.softmax(scores, -1), v)
        out = (o.reshape(b, nq, -1) * jax.nn.sigmoid(a_rows @ p["g"])) @ p["o"]
        return (out, choice) if with_choice else out


def sparse_attention(p, a, dims, prompt_len=None):
    """The sparse operator on a [B, S, H] whole."""
    s = a.shape[1]
    k, v, comp = sparse_keys(p, a, dims)
    return sparse_rows(p, a, jnp.arange(s), sparse_tokens(s, dims, prompt_len),
                       k, v, comp, dims)


# --------------------------------------------------------------- the model

def logits(params, ids, dims, prompt_len=None):
    """ids [B, S] -> logits [B, S, V] float32."""
    layers = params["layers"]
    h = embed(params["tok_emb"], ids, dims)
    f32 = lambda w: jnp.asarray(w, jnp.float32)          # noqa: E731
    for i in range(len(dims["mixer_types"])):
        a = rms_norm(h, f32(layers["attn_norm"][i]), dims["eps"])
        j = leaf_index(dims, i)
        op = sparse_attention(at(layers["attn"], j), a, dims, prompt_len) \
            if is_sparse(dims, i) else lightning(at(layers["lin"], j), a,
                                                 dims, i)
        h = h + dims["branch"] * op
        u = rms_norm(h, f32(layers["ffn_norm"][i]), dims["eps"])
        h = h + dims["branch"] * feed_forward(at(layers["ffn"], i), u)
    return head(params["lm_head"],
                rms_norm(h, f32(params["norm_f"]), dims["eps"]), dims)


def loss_and_grad_norm(params, ids, dims):
    """Mean next-token cross entropy and the global gradient norm."""
    def loss(p):
        lg = logits(p, ids, dims)[:, :-1]
        lp = jax.nn.log_softmax(lg, -1)
        return -jnp.mean(jnp.take_along_axis(lp, ids[:, 1:, None], -1))

    value, grads = jax.value_and_grad(loss)(_f32(params))
    return value, jnp.sqrt(sum(jnp.sum(jnp.square(g))
                               for g in jax.tree_util.tree_leaves(grads)))
