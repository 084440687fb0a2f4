"""The ``deepseek_v3`` block in plain ``jax.numpy``: the benchmark's yardstick
for ``correct`` where the configuration is trained.

The architecture as ``huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601``
``config.json`` states it (``model_type`` ``deepseek_v3``, ``q_lora_rank``
null) and the DeepSeek-V3 report (arXiv:2412.19437) defines it.  With ``N``
RMSNorm (eps from the configuration, each its own weight), ``x`` a token's
hidden row::

    h1 = h + MLA(N(h));  h2 = h1 + FFN(N(h1))
    MLA:  q = W_q x -> heads x [q_n (nope) | q_r (rope)]
          [c (kv_rank) | k_r (rope)] = W_kva x
          [k_n (nope) | v (v_dim)] per head = W_kvb N(c)
          rope on q_r and on k_r (shared by all heads); k = [k_n | k_r]
          o = W_o concat_heads(softmax_causal(q k^T / sqrt(nope + rope)) v)
    FFN:  layer < first_dense:  W_d(silu(W_g x) * W_u x)
          after:  s = sigmoid(float32(x) W_r) over all n_routed experts
                  chosen = top-k of s + b      (b: the correction bias)
                  w_i = scaling * s_i / (sum of the chosen s_j + 1e-20)
                  y = Shared(x) + sum over chosen i HELD here of w_i E_i(x)
    logits = W_head N(h_last)

**Rope, ``rope_interleave`` true**, as ``deepseek_v3`` reads it: the pairs
(2i, 2i+1) of a rope part are brought to the half layout ``[x_0, x_2, ..,
x_1, x_3, ..]`` and rotated there (``x cos + rotate_half(x) sin`` with angle
``i`` on entries ``i`` and ``i + rope/2``).  Queries and keys are permuted
alike, so scores equal those of rotating each pair in place (what the
program does); reading the stored order AS the half layout is another model
(``interleaved=False`` here, for the test that says so).

**The correction bias** is state: ``next_bias`` moves it by ``gamma *
sign(mean(c) - c_e)`` from a step's counts ``c_e`` of choices over all
experts (report section 2.1.2).  An absent bias is zero.

**One chip's share**: ``d["held"] = (first, count)`` of the routed experts
are here; a chosen expert held elsewhere adds nothing, exactly as in the
program; the sum over the chosen in ``w_i`` keeps every chosen score.

Float32 everywhere under ``jax.default_matmul_precision("highest")``; no
kernels, nothing imported from the program under test.  Its parameter layout
is the input here (``hetu_tpu/models/deepseek_v3.py``): ``dense`` and
``sparse`` groups of stacked layers.

**At the timed sizes** one 8192-token sequence's scores are 8.6 GB, so
attention runs a tile of (heads x query rows) at a time, each tile
recomputed in the backward (``jax.checkpoint``), and
``loss_and_grad_norm_by_layer`` takes the gradient ONE LAYER at a time:
forward keeping each layer's input, then the head's gradient, then each
layer's vector-Jacobian product from the last to the first, summing the
squared norms and dropping that layer's gradient; a second whole gradient
never exists.  The same functions, whole, are ``loss_and_grads`` (tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

SCORES_BYTES = 256 * 2 ** 20     # one attention tile's score matrix, at most
ROWS = 2048                      # rows of the head taken at a time


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta, interleaved: bool = True):
    """x [..., S, (heads,) R] at positions pos [S]; the result is in the
    half layout."""
    r = x.shape[-1]
    if interleaved:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    inv = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = pos.astype(jnp.float32)[:, None] * inv            # [S, R/2]
    if x.ndim - ang.ndim == 2:                              # a heads axis
        ang = ang[:, None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)
    half = jnp.concatenate([-x[..., r // 2:], x[..., :r // 2]], -1)
    return x * cos + half * sin


def qkv(p, x, d, interleaved: bool = True):
    """x [B, S, H] normed -> q, k [B, S, heads, nope + rope], v [B, S, heads,
    v_dim]."""
    b, s, _ = x.shape
    heads, nope, rp = d["heads"], d["nope"], d["rope"]
    pos = jnp.arange(s)
    q = (x @ p["q"]).reshape(b, s, heads, nope + rp)
    q = jnp.concatenate(
        [q[..., :nope], rope(q[..., nope:], pos, d["theta"], interleaved)],
        -1)
    kv_a = x @ p["kv_a"]
    c = rms_norm(kv_a[..., :d["kv_rank"]], p["kv_a_norm"], d["eps"])
    k_r = rope(kv_a[..., d["kv_rank"]:], pos, d["theta"], interleaved)
    kv = (c @ p["kv_b"]).reshape(b, s, heads, nope + d["v_dim"])
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_r[:, :, None], (b, s, heads, rp))], -1)
    return q, k, kv[..., nope:]


def _tile(q, k, v, at):
    """Causal attention of query rows ``at .. at + rows - 1`` (q [B, rows,
    g, D]) over all keys k [B, S, g, D], v [B, S, g, Dv]."""
    rows, s = q.shape[1], k.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) \
        / jnp.sqrt(jnp.float32(q.shape[-1]))
    seen = (at + jnp.arange(rows))[:, None] >= jnp.arange(s)[None]
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def attend(q, k, v):
    """softmax_causal(q k^T / sqrt(D)) v, a tile of (head group x query
    rows) at a time so that one tile's scores stay under ``SCORES_BYTES``;
    whole when that fits."""
    b, s, heads, _ = q.shape
    groups, rows = heads, s
    while groups > 1 and 4 * b * groups * rows * s > SCORES_BYTES:
        groups //= 2
    while rows > 16 and 4 * b * groups * rows * s > SCORES_BYTES:
        rows //= 2
    if groups == heads and rows == s:
        return _tile(q, k, v, 0)
    tile = jax.checkpoint(_tile)
    blocks = -(-s // rows)      # the last block's rows past s are dropped
    q = jnp.pad(q, ((0, 0), (0, blocks * rows - s), (0, 0), (0, 0)))

    def head_group(g):
        sl = functools.partial(jax.lax.dynamic_slice_in_dim,
                               start_index=g * groups, slice_size=groups,
                               axis=2)
        kg, vg = sl(k), sl(v)

        def row_block(i):
            qb = jax.lax.dynamic_slice_in_dim(sl(q), i * rows, rows, 1)
            return tile(qb, kg, vg, i * rows)

        o = jax.lax.map(row_block, jnp.arange(blocks))   # [n, B, rows, ..]
        return jnp.moveaxis(o, 0, 1).reshape(b, blocks * rows, groups, -1)

    o = jax.lax.map(head_group, jnp.arange(heads // groups))
    return jnp.moveaxis(o, 0, 2).reshape(b, blocks * rows, heads, -1)[:, :s]


def mla(p, x, d, interleaved: bool = True):
    b, s, _ = x.shape
    q, k, v = qkv(p, x, d, interleaved)
    return attend(q, k, v).reshape(b, s, -1) @ p["o"]


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def expert_choice(router, bias, u, d):
    """u [T, H] -> (weights [T, k] with the scaling, idx [T, k])."""
    s = jax.nn.sigmoid(u @ router)
    _, idx = jax.lax.top_k(s + bias, d["topk"])
    w = jnp.take_along_axis(s, idx, -1)
    return d["scaling"] * w / (w.sum(-1, keepdims=True) + 1e-20), idx


def expert_layer(p, bias, u, d):
    """u [T, H] -> (what the layer adds [T, H], choices of every expert
    [n_routed] int32)."""
    w, idx = expert_choice(p["router"], bias, u, d)
    first, count = d["held"]
    # [T, n_routed]: a token's weight on each expert (0 where not chosen)
    dense_w = jnp.zeros((u.shape[0], d["n_routed"]), jnp.float32).at[
        jnp.arange(u.shape[0])[:, None], idx].add(w)
    chosen = jnp.zeros((d["n_routed"],), jnp.int32).at[
        idx.reshape(-1)].add(1)

    @jax.checkpoint
    def one(y, xs):
        gate, up, down, e = xs
        return y + dense_w[:, first + e, None] * swiglu(u, gate, up, down), \
            None

    y = swiglu(u, p["shared_gate"], p["shared_up"], p["shared_down"])
    y, _ = jax.lax.scan(one, y, (p["gate"], p["up"], p["down"],
                                 jnp.arange(count)))
    return y, chosen


def dense_layer(p, h, d, interleaved: bool = True):
    h = h + mla(p["attn"], rms_norm(h, p["attn_norm"], d["eps"]), d,
                interleaved)
    u = rms_norm(h, p["ffn_norm"], d["eps"])
    f = p["ffn"]
    return h + swiglu(u, f["gate"], f["up"], f["down"])


def sparse_layer(p, bias, h, d, interleaved: bool = True):
    """(out, choices of every expert [n_routed])."""
    h = h + mla(p["attn"], rms_norm(h, p["attn_norm"], d["eps"]), d,
                interleaved)
    u = rms_norm(h, p["ffn_norm"], d["eps"])
    y, chosen = expert_layer(p["moe"], bias, u.reshape(-1, u.shape[-1]), d)
    return h + y.reshape(h.shape), chosen


def _layers(params):
    n_dense = params["dense"]["attn_norm"].shape[0]
    n_sparse = params["sparse"]["attn_norm"].shape[0]
    return n_dense, n_sparse


def _at(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


def zero_bias(params, d):
    return jnp.zeros((_layers(params)[1], d["n_routed"]), jnp.float32)


def hidden(params, ids, d, bias=None, interleaved: bool = True):
    """(the stream after the last layer [B, S, H], choices [expert layers,
    n_routed])."""
    bias = zero_bias(params, d) if bias is None else bias
    n_dense, n_sparse = _layers(params)
    h = params["tok_emb"][ids]
    for l in range(n_dense):
        h = dense_layer(_at(params["dense"], l), h, d, interleaved)
    chosen = []
    for l in range(n_sparse):
        h, c = sparse_layer(_at(params["sparse"], l), bias[l], h, d,
                            interleaved)
        chosen.append(c)
    return h, jnp.stack(chosen) if chosen else jnp.zeros(
        (0, d["n_routed"]), jnp.int32)


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


def logits(params, ids, d, bias=None, interleaved: bool = True):
    """Full forward: ids [B, S] -> logits [B, S, V] float32."""
    with jax.default_matmul_precision("highest"):
        params = _f32(params)
        h, _ = hidden(params, ids, d, bias, interleaved)
        return rms_norm(h, params["norm_f"], d["eps"]) @ params["lm_head"].T


def loss_and_choices(params, ids, d, bias=None, interleaved: bool = True):
    """(mean next-token cross entropy over ids [B, S], the choices)."""
    with jax.default_matmul_precision("highest"):
        params = _f32(params)
        # every position is routed (and counted), as in the program; the
        # last one predicts nothing
        h, chosen = hidden(params, ids, d, bias, interleaved)
        total = head_loss(params["norm_f"], params["lm_head"],
                          h[:, :-1].reshape(-1, h.shape[-1]),
                          ids[:, 1:].reshape(-1), d)
        return total / (ids.shape[0] * (ids.shape[1] - 1)), chosen


def loss(params, ids, d, bias=None, interleaved: bool = True):
    return loss_and_choices(params, ids, d, bias, interleaved)[0]


def loss_and_grads(params, ids, d, bias=None):
    """((loss, choices), gradient of every leaf); whole, for small sizes."""
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: loss_and_choices(p, ids, d, bias), has_aux=True)(
                _f32(params))


def next_bias(bias, chosen, gamma: float):
    """The correction bias after a step whose choices were ``chosen``."""
    load = chosen.astype(jnp.float32)
    return bias + gamma * jnp.sign(
        jnp.mean(load, -1, keepdims=True) - load)


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(a.astype(jnp.float32)))
                        for a in jax.tree_util.tree_leaves(tree)))


def _sumsq(tree):
    return sum(jnp.sum(jnp.square(a)) for a in jax.tree_util.tree_leaves(tree))


def loss_and_grad_norm_by_layer(params, ids, d, bias=None):
    """(loss, global L2 norm of the gradient), the gradient taken one layer
    at a time.  Each piece is its own jitted program of one layer's
    float32 weights; Python drives them."""
    with jax.default_matmul_precision("highest"):
        n_dense, n_sparse = _layers(params)
        bias = zero_bias(params, d) if bias is None else bias
        x, labels = ids, ids[:, 1:].reshape(-1)
        count = labels.shape[0]
        layers = [("dense", l) for l in range(n_dense)] \
            + [("sparse", l) for l in range(n_sparse)]

        def run(kind, p, b, h):
            if kind == "dense":
                return dense_layer(_f32(p), h, d)
            return sparse_layer(_f32(p), b, h, d)[0]

        def bias_of(kind, l):
            return bias[l] if kind == "sparse" else None

        fwd = jax.jit(run, static_argnums=0)

        @functools.partial(jax.jit, static_argnums=0, donate_argnums=4)
        def bwd(kind, p, b, h, dh):
            _, pull = jax.vjp(lambda q, x: run(kind, q, b, x), _f32(p), h)
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
        for kind, l in layers:
            inputs.append(h)
            h = fwd(kind, _at(params[kind], l), bias_of(kind, l), h)
        value, total, dh = head(params["norm_f"], params["lm_head"], h)
        del h
        for kind, l in reversed(layers):
            sq, dh = bwd(kind, _at(params[kind], l), bias_of(kind, l),
                         inputs.pop(), dh)
            total = total + sq
        total = total + embed_sumsq(params["tok_emb"], dh)
        return value, jnp.sqrt(total)
