"""LongCat-Flash's adapter: everything the benchmark knows of the
architecture whose configuration has ``ffn_hidden_size``,
``expert_ffn_hidden_size``, ``kv_lora_rank``, ``q_lora_rank``,
``n_routed_experts``, ``zero_expert_num`` and ``moe_topk``: double layers of
two latent-attention blocks, two dense SwiGLU FFNs and one expert layer
with a shortcut across the halves, identity experts behind one wide router.
The model is the program's ``LongcatFlashModel``, the reference
``benchmarks/reference/longcat_flash.py``.

**One chip's share.**  ``n_routed_experts`` in the configuration file is the
number of routed experts HELD here; ``deployment.n_routed_experts_published``
is the router's published width and ``deployment.expert_parallel_rank`` says
which share.  ``vocab_size`` is the slice of the vocabulary held here: ids,
logits and sampling are over the slice.  Program and reference get the same
share: held experts and identity experts add, absent ones do not.

**The reference runs in blocks** (``reference_logits`` owns the jit): the
weights are bfloat16 on the chip and a float32 copy of all of them is
20.7 GB, so one sub-block (one attention block, one dense FFN, one expert)
is widened at a time, and attention takes a few heads at a time once the
score matrix of all of them would pass ``SCORES_BYTES``.

**Counts** are what the algorithm needs, from shapes alone.  A decode step
reads every dense weight once (attention, dense FFNs, router), the output
head, and the latent cache of every live token; the experts it reads are
those its tokens HIT, which shapes cannot say, so ``decode_step_bytes``
leaves them out and ``decode_roofline`` can only be under-stated by them
(4 hit experts of 16 a layer are 6% of a layer's dense bytes).
``decode_step_flops`` counts the expected pairs on held experts under
uniform routing (``topk * held / router width`` a token).

**Tolerances.**  Weights and compute are bfloat16, the router float32; the
reference is float32 at the highest matmul precision over the same bfloat16
weights.  The readings are in ``TOLERANCES`` and ``PERF.md`` (PR 28), taken
with ``benchmarks/tools/check_seeds.py --control``.  ``logit_err`` reads
three times GPT-2's: 6144-wide sums and a residual stream that grows to an
rms of about 12 over twenty bfloat16 additions; a flipped 12th choice of
the router (272 of 768 indices act here) is inside that, not above it.
"""

from __future__ import annotations

import numpy as np

from benchmarks.harness import spec

SCORES_BYTES = 512 * 2 ** 20    # the reference's score matrix, at most

TOLERANCES = {
    "logit_err": {
        "limit": 0.08,
        "why": "max |system - reference| over the reference's range at the "
               "checked rows. Two readings on the v5e at the published "
               "widths (PR 28, PERF.md): bfloat16 as stated 0.020-0.036 "
               "over 30 seeds, all of it arithmetic (the same program in "
               "float32 reads 3e-6); weights rounded to an 8-bit float's "
               "mantissa 0.24-0.25. The limit is twice the first and a "
               "third of the second"},
    "token_gap": {
        "limit": 0.04,
        "why": "by the reference's logits the engine's token may trail the "
               "best by the two candidates' own errors, at most twice "
               "logit_err; read 0-0.015 over 30 seeds (PR 28), the limit "
               "2.6 times the worst. No 8-bit reading: the engine over "
               "rounded weights and the reference over the true ones do "
               "not fit the chip together; logit_err is the limit the "
               "lower precision fails"},
    "loss_rel": {
        "limit": 2e-3,
        "why": "no cell trains this configuration; stated for a "
               "test-size comparison on the CPU"},
    "grad_norm_rel": {
        "limit": 2e-2,
        "why": "no cell trains this configuration; stated for a "
               "test-size comparison on the CPU"},
}


def tolerances(config: dict) -> dict:
    return TOLERANCES


# ------------------------------------------------------- the configuration

def widths(config: dict) -> dict:
    dep = config["deployment"]
    held = int(config["n_routed_experts"])
    return {
        "hidden": int(config["hidden_size"]),
        "layers": int(config["num_layers"]),
        "heads": int(config["num_attention_heads"]),
        "q_rank": int(config["q_lora_rank"]),
        "kv_rank": int(config["kv_lora_rank"]),
        "nope": int(config["qk_nope_head_dim"]),
        "rope": int(config["qk_rope_head_dim"]),
        "v": int(config["v_head_dim"]),
        "ffn": int(config["ffn_hidden_size"]),
        "expert_ffn": int(config["expert_ffn_hidden_size"]),
        "held": held,
        "first": int(dep["expert_parallel_rank"]) * held,
        "n_routed": int(dep["n_routed_experts_published"]),
        "n_zero": int(config["zero_expert_num"]),
        "topk": int(config["moe_topk"]),
        "scaling": float(config["routed_scaling_factor"]),
        "theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        "vocab": int(config["vocab_size"]),
    }


def dims(config: dict) -> dict:
    """What the reference's functions take."""
    w = widths(config)
    return {**{k: w[k] for k in ("heads", "q_rank", "kv_rank", "nope",
                                 "rope", "v", "n_routed", "n_zero", "topk",
                                 "scaling", "theta", "eps")},
            "held": (w["first"], w["held"])}


def id_range(config: dict) -> tuple:
    """Ids are drawn from the slice of the vocabulary held here."""
    return 0, int(config["vocab_size"])


def positions(config: dict) -> int:
    return int(config["serve"]["max_len"])


def make_model(config: dict, section: str):
    import jax.numpy as jnp

    from hetu_tpu.models.longcat_flash import (
        LongcatFlashConfig, LongcatFlashModel,
    )

    if section != "serve":
        raise ValueError(
            f"configuration {config['name']} has no {section!r} section: "
            f"no cut of it trains on one chip")
    w, a = widths(config), config["assumed"]
    return LongcatFlashModel(LongcatFlashConfig(
        vocab_size=w["vocab"], hidden_size=w["hidden"],
        num_layers=w["layers"], num_heads=w["heads"],
        q_lora_rank=w["q_rank"], kv_lora_rank=w["kv_rank"],
        qk_nope_head_dim=w["nope"], qk_rope_head_dim=w["rope"],
        v_head_dim=w["v"], ffn_size=w["ffn"],
        expert_ffn_size=w["expert_ffn"], n_routed_experts=w["n_routed"],
        zero_expert_num=w["n_zero"], moe_topk=w["topk"],
        routed_scaling_factor=w["scaling"], held=(w["first"], w["held"]),
        rope_theta=w["theta"], rms_eps=w["eps"],
        max_position=positions(config),
        dtype=getattr(jnp, config["compute_dtype"]),
        param_dtype=getattr(jnp, config["param_dtype"]),
        init_std=float(a["init_std"]),
        router_init_std=float(a["router_init_std"]),
        router_bias_std=float(a["router_bias_std"])))


# ------------------------------------------------- reference and system

def reference(config: dict):
    return spec.reference(config)


_JITS: dict = {}     # sizes -> the reference's jitted pieces


def _jitted(config: dict):
    """The reference's pieces, each under its own jit; made once for each
    set of sizes (the jits keep their compiled programs)."""
    d = dims(config)
    key = repr(sorted(d.items()))
    if key not in _JITS:
        import jax
        import jax.numpy as jnp

        ref = reference(config)
        heads = d["heads"]

        def _cols(w, lo, n):
            """Columns of heads ``lo .. lo + n - 1`` of a weight whose
            columns are head-major."""
            per = w.shape[1] // heads
            return jax.lax.dynamic_slice_in_dim(
                w.reshape(w.shape[0], heads, per), lo, n, 1) \
                .reshape(w.shape[0], n * per)

        def some_heads(p, x, lo, n):
            """The part of one attention block's output that heads
            ``lo .. lo + n - 1`` give (``W_o`` is linear over heads)."""
            take = dict(
                p, q_b=_cols(p["q_b"], lo, n), kv_b=_cols(p["kv_b"], lo, n),
                o=jax.lax.dynamic_slice_in_dim(
                    p["o"].reshape(heads, d["v"], -1), lo, n, 0)
                .reshape(n * d["v"], -1))
            return ref.attention(take, x, {**d, "heads": n})

        _JITS[key] = {
            "embed": jax.jit(lambda emb, ids: emb[ids].astype(jnp.float32)),
            "norm": jax.jit(lambda x, scale: ref.rms_norm(
                x, scale.astype(jnp.float32), d["eps"])),
            "heads": jax.jit(some_heads, static_argnums=3),
            "ffn": jax.jit(ref.dense_ffn),
            "choice": jax.jit(lambda r, b, u: ref.expert_choice(r, b, u, d)),
            "identity": jax.jit(lambda u, w, i: ref.identity_experts(
                u, w, i, d)),
            "expert": jax.jit(ref.one_expert),
            "head": jax.jit(ref.head),
        }
    return _JITS[key]


def reference_logits(params, ids, config: dict):
    """The reference's full forward, one sub-block's weights widened to
    float32 at a time; the same functions ``ref.logits`` is made of."""
    import jax

    fn, d = _jitted(config), dims(config)
    layers = params["layers"]
    ids = np.asarray(ids)
    b, s = ids.shape
    group = d["heads"]
    while group > 1 and 4 * b * group * s * s > SCORES_BYTES:
        group //= 2

    def at(tree, *index):
        return jax.tree_util.tree_map(lambda a: a[index], tree)

    def attend(l, i, x):
        p = at(layers["attn"], l, i)
        out = None
        for lo in range(0, d["heads"], group):
            part = fn["heads"](p, x, lo, group)
            out = part if out is None else out + part
        return out

    first, count = d["held"]
    h = fn["embed"](params["tok_emb"], ids)
    for l in range(int(layers["attn_norm"].shape[0])):
        h1 = h + attend(l, 0, fn["norm"](h, layers["attn_norm"][l, 0]))
        u = fn["norm"](h1, layers["ffn_norm"][l, 0])
        moe = at(layers["moe"], l)
        weights, idx = fn["choice"](moe["router"], moe["router_bias"], u)
        m = fn["identity"](u, weights, idx)
        for e in range(count):
            m = m + fn["expert"](
                {k: moe[k][e] for k in ("gate", "up", "down")}, u, weights,
                idx, first + e)
        h2 = h1 + fn["ffn"](at(layers["ffn"], l, 0), u)
        h3 = h2 + attend(l, 1, fn["norm"](h2, layers["attn_norm"][l, 1]))
        h = h3 + fn["ffn"](at(layers["ffn"], l, 1),
                           fn["norm"](h3, layers["ffn_norm"][l, 1])) + m
    h = fn["norm"](h, params["norm_f"])
    return np.asarray(fn["head"](params["lm_head"], h))


def reference_loss_and_grad_norm(params, ids, config: dict) -> tuple:
    """Whole, not in blocks: no cell trains this configuration, and the
    test size fits."""
    import jax

    ref, d = reference(config), dims(config)
    loss, norm = jax.jit(
        lambda p, x: ref.loss_and_grad_norm(p, x, d))(params, ids)
    return float(loss), float(norm)


def system_logits(model, params, ids):
    import jax
    import jax.numpy as jnp

    return np.asarray(jax.jit(
        lambda p, x: model.apply({"params": p, "state": {}}, x)[0])(
            params, jnp.asarray(ids)).astype(jnp.float32))


# ------------------------------------------------- operations and bytes

def attention_params(config: dict) -> int:
    w = widths(config)
    h, heads = w["hidden"], w["heads"]
    return (h * w["q_rank"] + w["q_rank"] * heads * (w["nope"] + w["rope"])
            + h * (w["kv_rank"] + w["rope"])
            + w["kv_rank"] * heads * (w["nope"] + w["v"])
            + heads * w["v"] * h)


def dense_layer_params(config: dict) -> int:
    """Matmul weights of one double layer outside its experts: two
    attention blocks, two dense FFNs, the router."""
    w = widths(config)
    return (2 * attention_params(config) + 2 * 3 * w["hidden"] * w["ffn"]
            + w["hidden"] * (w["n_routed"] + w["n_zero"]))


def expert_params(config: dict) -> int:
    w = widths(config)
    return 3 * w["hidden"] * w["expert_ffn"]


def head_params(config: dict) -> int:
    w = widths(config)
    return w["vocab"] * w["hidden"]


def total_params(config: dict) -> int:
    """Parameters this chip holds: the layers kept, the experts held, the
    slice of the embedding and of the (untied) head, every norm weight and
    the router's correction bias."""
    w = widths(config)
    norms = 4 * w["hidden"] + 2 * (w["q_rank"] + w["kv_rank"])
    per_layer = (dense_layer_params(config)
                 + w["held"] * expert_params(config) + norms
                 + w["n_routed"] + w["n_zero"])
    return w["layers"] * per_layer + 2 * head_params(config) + w["hidden"]


def cache_bytes_per_token(config: dict, itemsize: int = 2) -> int:
    w = widths(config)
    return 2 * w["layers"] * (w["kv_rank"] + w["rope"]) * itemsize


def expected_held_pairs(config: dict) -> float:
    """(token, choice) pairs a token sends to this chip's experts under
    uniform routing."""
    w = widths(config)
    return w["topk"] * w["held"] / (w["n_routed"] + w["n_zero"])


def token_matmul_params(config: dict) -> float:
    """Weights one token is multiplied by: the dense part of every layer,
    the head, and its expected pairs on held experts."""
    w = widths(config)
    return (w["layers"] * (dense_layer_params(config)
                           + expected_held_pairs(config)
                           * expert_params(config))
            + head_params(config))


def decode_step_bytes(config: dict, cached_tokens: int,
                      itemsize: int = 2) -> float:
    """Bytes one decode step has to read: every dense weight and the head
    once, and the latent cache of every live token.  The experts a step's
    tokens hit are left out (shapes do not say which)."""
    w = widths(config)
    return (itemsize * (w["layers"] * dense_layer_params(config)
                        + head_params(config))
            + float(cache_bytes_per_token(config, itemsize)) * cached_tokens)


def decode_step_flops(config: dict, active: int, cached_tokens: int) -> float:
    """Absorbed attention reads a cached token as one ``kv_rank + rope``
    key and one ``kv_rank`` value for every head."""
    w = widths(config)
    per_cached = 2.0 * w["heads"] * (2 * w["kv_rank"] + w["rope"])
    return 2.0 * token_matmul_params(config) * active \
        + 2 * w["layers"] * per_cached * cached_tokens


def train_flops_per_token(config: dict, seq: int) -> float:
    """Forward + backward: 6 per matmul weight a token meets, plus
    expanded causal attention (scores over ``nope + rope``, values over
    ``v``, half of ``seq`` under the mask, times 3)."""
    w = widths(config)
    attn = 3.0 * w["heads"] * (w["nope"] + w["rope"] + w["v"]) * seq
    return 6.0 * token_matmul_params(config) + 2 * w["layers"] * attn


def attention_call_shape(config: dict, run_values: dict) -> tuple:
    w = widths(config)
    return (run_values["batch"], w["heads"], run_values["seq"],
            w["nope"] + w["rope"])
