"""The ``mellum`` block in plain ``jax.numpy``: the benchmark's yardstick for
``correct`` where the configuration is trained.

The architecture as ``huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct``
``config.json`` states it (``model_type`` ``mellum``).  With ``N`` RMSNorm
(eps from the configuration, each its own weight), ``x`` a token's hidden
row, no projection with a bias::

    h1 = h + Attn_l(N(h));  h2 = h1 + MoE_l(N(h1))
    Attn:  q = W_q x -> heads of D;  k = W_k x, v = W_v x -> kv_heads of D
           q, k normalised per head (N over D, one weight each a layer)
           q, k rotated in the half layout (x cos + rotate_half(x) sin) by
           the table of the layer's TYPE
           scores q k^T / sqrt(D); key j visible to query i when 0 <= i - j
           and, on a sliding layer, i - j < window; softmax in float32
           query head h reads KV head h // (heads / kv_heads)
           o W_o
    MoE:   s = softmax(x W_r) over all n_routed experts;  chosen = top-k of s
           w_e = s_e / (sum of the chosen s + 1e-20)
           y = sum over chosen e HELD here of w_e W_down_e(silu(W_gate_e x)
               * W_up_e x)
    logits = W_head N(h_last)

**Two tables.**  Sliding layers: ``inv_freq_i = theta^(-2i/D)``.  Full
layers, YaRN (``rope_parameters.full_attention``): ``c(n) = D ln(L / (2 pi
n)) / (2 ln theta)`` with ``L`` the original positions; ``low =
floor(c(beta_fast))``, ``high = ceil(c(beta_slow))``, both clipped to ``[0, D
- 1]``; ``ramp_i = clip((i - low) / (high - low), 0, 1)``; ``inv_freq_i = (1
- ramp_i) theta^(-2i/D) + ramp_i theta^(-2i/D) / factor``; cos and sin times
``attention_factor``.

**Departures from the published description, and what the config is silent
on** (the configuration file's ``assumed`` says why each):

* the per-head norms on q and k are a READING: the config has no key for
  them (``qk_norm=False`` here is the other reading, for the test that it is
  another model);
* no auxiliary balance loss: the config gives no coefficient
  (``aux_coef`` here is the other reading, the switch-style ``E sum_e f_e
  P_e`` a layer);
* the next-token head ``described_as`` mentions has no key in the config and
  is absent;
* ``intermediate_size`` (7168) is read by nothing: ``mlp_layer_types`` is
  ``sparse`` for every layer.

**One chip's share**: ``d["held"] = (first, count)`` of the routed experts
are here; a chosen expert held elsewhere adds nothing, exactly as in the
program; the sum over the chosen in ``w_e`` keeps every chosen score.  ``ids``
are drawn from the slice of the vocabulary held here, and logits and loss are
over the slice.

Float32 everywhere under ``jax.default_matmul_precision("highest")``; no
kernels, nothing imported from the program under test.  Its parameter layout
is the input here (``hetu_tpu/models/mellum.py``): ``layers`` leaves stacked
``[periods, layers a period, ...]``, W_q, W_k and W_o held ``[out, in]`` /
``[heads * D, H]`` as the program holds them.

**At the timed sizes** a head's whole scores are 1.07 GB at S = 16,384, so
attention runs a block of query rows at a time over all heads, every block
against every key under the layer's mask (a sliding layer's too: the
reference skips nothing), each block recomputed in the backward
(``jax.checkpoint``), and ``loss_and_grad_norm_by_layer`` takes the gradient
ONE LAYER at a time as ``reference/deepseek_v3.py`` does.  The same
functions, whole, are ``loss_and_grads`` (tests).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

SCORES_BYTES = 256 * 2 ** 20     # one attention block's score matrix, at most
ROWS = 2048                      # rows of the head taken at a time
SLIDING, FULL = "sliding_attention", "full_attention"


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def inv_freq(d, kind):
    """(frequencies [D / 2], the factor on cos and sin) of a layer type."""
    dim, theta, yarn = d["head_dim"], d["theta"], d.get("yarn")
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    plain = theta ** (-2.0 * i / dim)
    if kind != FULL or not yarn:
        return plain, 1.0

    def c(n):
        return dim * math.log(yarn["original_max_position_embeddings"]
                              / (2 * math.pi * n)) / (2 * math.log(theta))

    low = min(max(math.floor(c(yarn["beta_fast"])), 0), dim - 1)
    high = min(max(math.ceil(c(yarn["beta_slow"])), 0), dim - 1)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return ((1.0 - ramp) * plain + ramp * plain / yarn["factor"],
            yarn["attention_factor"])


def rope(x, d, kind):
    """x [B, S, heads, D] at positions 0 .. S - 1, half layout."""
    dim = x.shape[-1]
    inv, factor = inv_freq(d, kind)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv   # [S, D/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None] * factor
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None] * factor
    half = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], -1)
    return x * cos + half * sin


def qkv(p, x, d, kind, qk_norm: bool = True):
    """x [B, S, H] normed -> q [B, S, heads, D], k, v [B, S, kv_heads, D]."""
    b, s, _ = x.shape
    dim = d["head_dim"]
    q = (x @ p["q"].T).reshape(b, s, d["heads"], dim)
    k = (x @ p["k"].T).reshape(b, s, d["kv_heads"], dim)
    v = (x @ p["v"]).reshape(b, s, d["kv_heads"], dim)
    if qk_norm:
        q = rms_norm(q, p["q_norm"], d["eps"])
        k = rms_norm(k, p["k_norm"], d["eps"])
    return rope(q, d, kind), rope(k, d, kind), v


def _block(q, k, v, at, window):
    """Attention of query rows ``at .. at + rows - 1`` (q [B, rows, kv, g,
    D]) over all keys k, v [B, S, kv, D] under the layer's mask."""
    rows, s = q.shape[1], k.shape[1]
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q, k) \
        / jnp.sqrt(jnp.float32(q.shape[-1]))
    back = (at + jnp.arange(rows))[:, None] - jnp.arange(s)[None]   # i - j
    seen = back >= 0
    if window is not None:
        seen &= back < window
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)


def attend(q, k, v, window):
    """softmax_masked(q k^T / sqrt(D)) v with query head h on KV head h //
    g, a block of query rows at a time so that one block's scores stay
    under ``SCORES_BYTES``; whole when that fits."""
    b, s, heads, dim = q.shape
    kv = k.shape[2]
    q = q.reshape(b, s, kv, heads // kv, dim)
    rows = s
    while rows > 16 and 4 * b * heads * rows * s > SCORES_BYTES:
        rows //= 2
    if rows == s:
        return _block(q, k, v, 0, window).reshape(b, s, heads, dim)
    block = jax.checkpoint(functools.partial(_block, window=window))
    n = -(-s // rows)           # the last block's rows past s are dropped
    q = jnp.pad(q, ((0, 0), (0, n * rows - s)) + ((0, 0),) * 3)

    def row_block(i):
        return block(jax.lax.dynamic_slice_in_dim(q, i * rows, rows, 1),
                     k, v, i * rows)

    o = jax.lax.map(row_block, jnp.arange(n))           # [n, B, rows, ...]
    return jnp.moveaxis(o, 0, 1).reshape(b, n * rows, heads, dim)[:, :s]


def attention(p, x, d, kind, qk_norm: bool = True):
    b, s, _ = x.shape
    q, k, v = qkv(p, x, d, kind, qk_norm)
    o = attend(q, k, v, d["window"] if kind == SLIDING else None)
    return o.reshape(b, s, -1) @ p["o"]


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def expert_choice(router, u, d):
    """u [T, H] -> (scores [T, n_routed], weights [T, k], idx [T, k])."""
    s = jax.nn.softmax(u @ router, axis=-1)
    w, idx = jax.lax.top_k(s, d["topk"])
    return s, w / (w.sum(-1, keepdims=True) + 1e-20), idx


def expert_layer(p, u, d):
    """u [T, H] -> (what the layer adds [T, H], the balance term ``E sum_e
    f_e P_e`` of the other reading)."""
    s, w, idx = expert_choice(p["router"], u, d)
    first, count = d["held"]
    t, e_all = u.shape[0], d["n_routed"]
    # [T, n_routed]: a token's weight on each expert (0 where not chosen)
    dense_w = jnp.zeros((t, e_all), jnp.float32).at[
        jnp.arange(t)[:, None], idx].add(w)
    chosen = jnp.zeros((e_all,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    balance = e_all * jnp.sum(chosen / idx.size * jnp.mean(s, 0))

    @jax.checkpoint
    def part(gate, up, down, e):
        return dense_w[:, first + e, None] * swiglu(u, gate, up, down)

    # the sum is outside the recomputed part: a step keeps nothing of it
    y, _ = jax.lax.scan(lambda y, xs: (y + part(*xs), None),
                        jnp.zeros_like(u),
                        (p["gate"], p["up"], p["down"], jnp.arange(count)))
    return y, balance


def layer(p, h, d, kind, qk_norm: bool = True):
    """One layer's leaves ``p`` over h [B, S, H]: (out, balance term)."""
    h = h + attention(p["attn"], rms_norm(h, p["attn_norm"], d["eps"]), d,
                      kind, qk_norm)
    u = rms_norm(h, p["ffn_norm"], d["eps"])
    y, balance = expert_layer(p["moe"], u.reshape(-1, u.shape[-1]), d)
    return h + y.reshape(h.shape), balance


def _layers(params, d):
    """[(period, layer in the period, its type)] in order."""
    periods = params["layers"]["attn_norm"].shape[0]
    return [(i, l, kind) for i in range(periods)
            for l, kind in enumerate(d["period"])]


def _at(tree, i, l):
    return jax.tree_util.tree_map(lambda a: a[i, l], tree)


def hidden(params, ids, d, qk_norm: bool = True):
    """(the stream after the last layer [B, S, H], the layers' balance
    terms summed)."""
    h = params["tok_emb"][ids]
    balance = 0.0
    for i, l, kind in _layers(params, d):
        h, b = layer(_at(params["layers"], i, l), h, d, kind, qk_norm)
        balance = balance + b
    return h, balance


def head_loss(norm_f, lm_head, h, labels, d):
    """Sum over rows of the next-token cross entropy: h [N, H] the stream's
    rows, labels [N]; ``ROWS`` rows at a time."""
    n = h.shape[0]
    rows = min(ROWS, n)
    pad = (-n) % rows
    h = jnp.concatenate([h, jnp.zeros((pad, h.shape[1]), h.dtype)])
    labels = jnp.concatenate([labels, jnp.full((pad,), -1, labels.dtype)])

    @jax.checkpoint
    def block(hb, yb):
        lg = rms_norm(hb, norm_f, d["eps"]) @ lm_head.T
        lse = jax.nn.logsumexp(lg, axis=-1)
        picked = jnp.take_along_axis(lg, jnp.maximum(yb, 0)[:, None],
                                     axis=-1)[:, 0]
        return jnp.sum(jnp.where(yb >= 0, lse - picked, 0.0))

    return jnp.sum(jax.lax.map(
        lambda xs: block(*xs),
        (h.reshape(-1, rows, h.shape[1]), labels.reshape(-1, rows))))


def logits(params, ids, d, qk_norm: bool = True):
    """Full forward: ids [B, S] -> logits [B, S, V] float32."""
    with jax.default_matmul_precision("highest"):
        params = _f32(params)
        h, _ = hidden(params, ids, d, qk_norm)
        return rms_norm(h, params["norm_f"], d["eps"]) @ params["lm_head"].T


def loss(params, ids, d, qk_norm: bool = True, aux_coef: float = 0.0):
    """Mean next-token cross entropy over ids [B, S] (plus, in the other
    reading, ``aux_coef`` times the layers' balance terms)."""
    with jax.default_matmul_precision("highest"):
        params = _f32(params)
        # every position is routed, as in the program; the last one
        # predicts nothing
        h, balance = hidden(params, ids, d, qk_norm)
        total = head_loss(params["norm_f"], params["lm_head"],
                          h[:, :-1].reshape(-1, h.shape[-1]),
                          ids[:, 1:].reshape(-1), d)
        return total / (ids.shape[0] * (ids.shape[1] - 1)) \
            + aux_coef * balance


def loss_and_grads(params, ids, d, **reading):
    """(loss, gradient of every leaf); whole, for small sizes."""
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: loss(p, ids, d, **reading))(_f32(params))


def _sumsq(tree):
    return sum(jnp.sum(jnp.square(a)) for a in jax.tree_util.tree_leaves(tree))


def loss_and_grad_norm_by_layer(params, ids, d):
    """(loss, global L2 norm of the gradient), the gradient taken one layer
    at a time: forward keeping each layer's input, then the head's gradient,
    then each layer's vector-Jacobian product from the last to the first,
    summing the squared norms and dropping that layer's gradient.  Each
    piece is its own jitted program of one layer's float32 weights (one a
    layer type); Python drives them."""
    with jax.default_matmul_precision("highest"):
        x, labels = ids, ids[:, 1:].reshape(-1)
        count = labels.shape[0]
        order = _layers(params, d)

        def run(kind, p, h):
            return layer(_f32(p), h, d, kind)[0]

        fwd = jax.jit(run, static_argnums=0)

        @functools.partial(jax.jit, static_argnums=0, donate_argnums=3)
        def bwd(kind, p, h, dh):
            _, pull = jax.vjp(lambda q, x: run(kind, q, x), _f32(p), h)
            dp, dx = pull(dh)
            return _sumsq(dp), dx

        @jax.jit
        def head(norm_f, lm_head, h):
            def f(nf, w, hh):
                return head_loss(nf, w, hh[:, :-1].reshape(-1, hh.shape[-1]),
                                 labels, d) / count
            value, (dn, dw, dh) = jax.value_and_grad(f, argnums=(0, 1, 2))(
                _f32(norm_f), _f32(lm_head), h)
            return value, _sumsq((dn, dw)), dh

        @jax.jit
        def embed_sumsq(tok_emb, dh):
            g = jnp.zeros(tok_emb.shape, jnp.float32).at[x].add(dh)
            return _sumsq(g)

        h = jax.jit(lambda e: e[x].astype(jnp.float32))(params["tok_emb"])
        inputs = []
        for i, l, kind in order:
            inputs.append(h)
            h = fwd(kind, _at(params["layers"], i, l), h)
        value, total, dh = head(params["norm_f"], params["lm_head"], h)
        del h
        for i, l, kind in reversed(order):
            sq, dh = bwd(kind, _at(params["layers"], i, l), inputs.pop(), dh)
            total = total + sq
        total = total + embed_sumsq(params["tok_emb"], dh)
        return value, jnp.sqrt(total)
