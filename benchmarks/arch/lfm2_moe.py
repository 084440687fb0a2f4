"""LFM2-MoE's adapter: everything the benchmark knows of the architecture
whose configuration has ``layer_types`` of ``conv`` and ``full_attention``,
``conv_L_cache``, ``num_dense_layers``, ``num_experts``,
``num_experts_per_tok``, ``use_expert_bias`` and ``norm_topk_prob``: gated
short convolutions between grouped-query attention layers with per-head q/k
norms and rope, leading dense layers, then expert layers with a sigmoid
router steered by a bias, renormalised top-k, no shared expert, the head
tied to the embedding.  The model is the program's ``Lfm2MoeModel``, the
reference ``benchmarks/reference/lfm2_moe.py``.

**One chip holds each layer whole.**  ``num_experts`` is the router's width
and the experts held alike (``deployment.chips_sharing_a_layer`` 1); the
vocabulary is whole.  What is cut is depth: ``layer_types`` is kept whole
(24 entries) and the layers run are ``num_hidden_layers`` of them from
``deployment.first_layer_run`` on, the first ``num_dense_layers`` of those
with a dense feed-forward.

**The reference runs a piece at a time** (``reference_logits`` owns the
jits): one operator's, one dense FFN's or ONE expert's bfloat16 weights are
widened to float32 at a time and the head takes ``ROWS`` positions at a
time, so that the float32 reference fits beside 9.2 GB of bfloat16 weights
and the pools once the window is over.  Every expert is computed on every
token and weighed by the ``[tokens, experts]`` matrix, as the reference file
does whole.

**Counts** are what the algorithm needs, from shapes alone.  A decode round
reads every dense weight once (the convolution and attention operators, the
dense FFN, the routers) and the tied embedding as the head; the experts its
tokens HIT, ``experts x (1 - (1 - topk / experts) ** num_slots)`` a layer
under uniform routing (31.99 of 32 at 64 slots: shapes do not say how many
slots are live, and from 32 live slots on the count is within 1% of all);
the cached rows of every live token in the attention layers; and the state
rows of every slot in the conv layers.  ``decode_step_flops`` counts ``topk``
experts a token.

**Tolerances.**  Weights and compute are bfloat16, the router float32, the
convolution's three products summed in float32; the reference is float32 at
the highest matmul precision over the same bfloat16 weights.  The readings
are in ``TOLERANCES`` and ``PERF.md`` (PR 43): the program as stated with
``benchmarks/tools/check_seeds.py`` and the runs' own checks, the lower
precision in the program's place with ``benchmarks/tools/check_control.py``.
"""

from __future__ import annotations

import numpy as np

from benchmarks.harness import spec

ROWS = 512          # positions the reference's head takes at a time

TOLERANCES = {
    "logit_err": {
        "limit": 0.32,
        "why": "max |system - reference| over the reference's range at the "
               "checked rows. Two readings on the v5e at the published "
               "widths (PR 43, PERF.md section 6). The program as stated "
               "(bfloat16): 0.102-0.258 over 38 seeds of harness/check.py's "
               "comparison (check_seeds.py and sixteen 40 s runs' own "
               "checks; median 0.164); the same arithmetic done exactly "
               "(float32 compute, highest precision, over the same "
               "weights) reads 9e-7, so the whole of it is rounding: twelve "
               "expert layers deep, a router choice exchanged at a near tie "
               "(the fourth and fifth of 32 sigmoid scores) moves a quarter "
               "of an expert layer's output and the layers behind it choose "
               "over the moved stream, and the harness takes the MAXIMUM "
               "over 36 rows. The control, "
               "benchmarks/tools/check_control.py: the nearest precision "
               "below put in the PROGRAM's place (every bfloat16 value its "
               "three entry points compute rounded to the three mantissa "
               "bits of an 8-bit float, matmul operands and so the weights "
               "included; the engine over it; the same comparison): "
               "0.356-0.463 over 17 seeds, median 0.392 (the weights alone "
               "rounded, dense forward: 0.269-0.295 over six, beside "
               "0.169-0.207 as stated over the same 512 rows). The limit "
               "lies 1.24 times over the stated largest and 1.11 times "
               "under the control's smallest: the stated readings spread "
               "three times as widely as the control's (0.25 against 0.07 "
               "in logarithms), and 0.32 is 2.8 of its own spreads from "
               "either median. The control is not correct on any seed"},
    "token_gap": {
        "limit": 0.23,
        "why": "by the reference's logits the engine's token may trail the "
               "best by the two candidates' own errors. As stated "
               "0.024-0.153 over the same 38 seeds (median 0.058); the "
               "control (as above, through the engine and its decode "
               "program) 0.228-0.416 over 17, median 0.263. The stated "
               "readings spread over a factor of six and their tail "
               "reaches two thirds of the control's smallest, so no limit "
               "parts the two with room on both sides: this one is 1.5 "
               "times the stated largest (as K-EXAONE's), and logit_err is "
               "the limit the control fails on every seed"},
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
    layers = int(config["num_hidden_layers"])
    first = int(dep["first_layer_run"])
    kinds = tuple(config["layer_types"][first:first + layers])
    if len(kinds) != layers or set(kinds) - {"conv", "full_attention"}:
        raise ValueError(f"layer_types from {first} on do not name {layers} "
                         f"conv or full_attention layers: {kinds}")
    hidden, heads = int(config["hidden_size"]), \
        int(config["num_attention_heads"])
    return {
        "hidden": hidden,
        "layers": layers,
        "layer_types": kinds,
        "conv_layers": kinds.count("conv"),
        "full_layers": kinds.count("full_attention"),
        "first_dense": int(config["num_dense_layers"]),
        "heads": heads,
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": hidden // heads,
        "taps": int(config["conv_L_cache"]),
        "ffn": int(config["intermediate_size"]),
        "expert_ffn": int(config["moe_intermediate_size"]),
        "experts": int(config["num_experts"]),
        "topk": int(config["num_experts_per_tok"]),
        "scaling": float(config["routed_scaling_factor"]),
        "theta": float(config["rope_theta"]),
        "eps": float(config["norm_eps"]),
        "vocab": int(config["vocab_size"]),
        "slots": int(config["serve"]["num_slots"]),
    }


def dims(config: dict) -> dict:
    """What the reference's functions take."""
    w = widths(config)
    return {**{k: w[k] for k in ("heads", "kv_heads", "head_dim",
                                 "layer_types", "first_dense", "topk",
                                 "scaling", "theta", "eps")},
            "held": (0, w["experts"])}


def id_range(config: dict) -> tuple:
    """The whole vocabulary."""
    return 0, int(config["vocab_size"])


def positions(config: dict) -> int:
    return int(config["serve"]["max_len"])


def make_model(config: dict, section: str):
    import jax.numpy as jnp

    from hetu_tpu.models.lfm2_moe import Lfm2MoeConfig, Lfm2MoeModel

    if section != "serve":
        raise ValueError(
            f"configuration {config['name']} has no {section!r} section: "
            f"the cell it was cut for serves")
    if not config["norm_topk_prob"] or not config["use_expert_bias"] \
            or config["conv_bias"] \
            or int(config["deployment"]["chips_sharing_a_layer"]) != 1:
        raise ValueError("the program's Lfm2MoeModel routes by sigmoid "
                         "scores plus a bias, renormalised over the chosen, "
                         "convolves without a bias, and is built here with "
                         "every expert held")
    w, a = widths(config), config["assumed"]
    return Lfm2MoeModel(Lfm2MoeConfig(
        vocab_size=w["vocab"], hidden_size=w["hidden"],
        num_layers=w["layers"], num_heads=w["heads"],
        num_kv_heads=w["kv_heads"], head_dim=w["head_dim"],
        ffn_size=w["ffn"], expert_ffn_size=w["expert_ffn"],
        first_dense=w["first_dense"], n_routed_experts=w["experts"],
        moe_topk=w["topk"], routed_scaling_factor=w["scaling"],
        conv_taps=w["taps"], layer_types=w["layer_types"],
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
        _JITS[key] = {
            "embed": jax.jit(lambda emb, ids: emb[ids].astype(jnp.float32)),
            "norm": jax.jit(lambda x, scale: ref.rms_norm(
                x, scale.astype(jnp.float32), d["eps"])),
            "add": jax.jit(lambda h, more: h + more, donate_argnums=0),
            "conv": jax.jit(ref.short_conv),
            "attention": jax.jit(lambda p, a: ref.attention(p, a, d)),
            "ffn": jax.jit(ref.dense_ffn),
            "weights": jax.jit(lambda r, b, u: ref.expert_weights(r, b, u, d)),
            "expert": jax.jit(ref.one_expert),
            "head": jax.jit(lambda emb, h, lo, n: ref.head(
                emb, jax.lax.dynamic_slice_in_dim(h, lo, n, 1)),
                static_argnums=3),
        }
    return _JITS[key]


def reference_logits(params, ids, config: dict):
    """The reference's full forward, one operator's, one dense FFN's or one
    expert's weights widened to float32 at a time, the head ``ROWS``
    positions at a time; the same functions ``ref.logits`` is made of."""
    import gc

    import jax

    gc.collect()    # a caller that has just dropped an engine: its pools
    fn, d = _jitted(config), dims(config)
    ref = reference(config)
    layers = params["layers"]
    ids = np.asarray(ids)
    s = ids.shape[1]
    first, count = d["held"]
    h = fn["embed"](params["tok_emb"], ids)
    for l, kind in enumerate(d["layer_types"]):
        a = fn["norm"](h, layers["attn_norm"][l])
        i = ref.leaf_index(d, l)
        h = fn["add"](h, fn["conv"](ref.at(layers["conv"], i), a)
                      if kind == "conv"
                      else fn["attention"](ref.at(layers["attn"], i), a))
        u = fn["norm"](h, layers["ffn_norm"][l])
        if l < d["first_dense"]:
            h = fn["add"](h, fn["ffn"](ref.at(layers["ffn"], l), u))
            continue
        moe = ref.at(layers["moe"], l - d["first_dense"])
        weights = fn["weights"](moe["router"], moe["router_bias"], u)
        for e in range(count):
            h = fn["add"](h, fn["expert"](
                {k: moe[k][e] for k in ("gate", "up", "down")}, u, weights,
                first + e))
    h = fn["norm"](h, params["norm_f"])
    return np.concatenate(
        [np.asarray(fn["head"](params["tok_emb"], h, lo, min(ROWS, s - lo)))
         for lo in range(0, s, ROWS)], 1)


def reference_loss_and_grad_norm(params, ids, config: dict) -> tuple:
    """Whole, not in pieces: no cell trains this configuration, and the
    test size fits."""
    import jax

    ref, d = reference(config), dims(config)
    loss, norm = jax.jit(
        lambda p, x: ref.loss_and_grad_norm(p, x, d))(params, ids)
    return float(loss), float(norm)


def system_logits(model, params, ids):
    """The program's dense forward, one sequence at a time: [S, vocabulary]
    logits of one are 100 MB in float32 at the check's width, and the chip
    holds the weights and the pools beside them."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda p, x: model.apply({"params": p, "state": {}}, x)[0]
                .astype(jnp.float32))
    return np.concatenate([np.asarray(f(params, jnp.asarray(row[None])))
                           for row in np.asarray(ids)], 0)


# ------------------------------------------------- operations and bytes

def conv_params(config: dict) -> int:
    """One convolution operator: in-projection, taps, out-projection."""
    w = widths(config)
    return w["hidden"] * 3 * w["hidden"] + w["taps"] * w["hidden"] \
        + w["hidden"] * w["hidden"]


def attention_params(config: dict) -> int:
    w = widths(config)
    q = w["heads"] * w["head_dim"]
    kv = w["kv_heads"] * w["head_dim"]
    return w["hidden"] * (q + 2 * kv) + q * w["hidden"]


def expert_params(config: dict) -> int:
    w = widths(config)
    return 3 * w["hidden"] * w["expert_ffn"]


def dense_params(config: dict) -> int:
    """Matmul weights outside the routed experts and the embedding, all
    layers: the operators, the dense FFN of the leading layers, the routers
    of the others."""
    w = widths(config)
    sparse = w["layers"] - w["first_dense"]
    return (w["conv_layers"] * conv_params(config)
            + w["full_layers"] * attention_params(config)
            + w["first_dense"] * 3 * w["hidden"] * w["ffn"]
            + sparse * w["hidden"] * w["experts"])


def head_params(config: dict) -> int:
    """The embedding, which is the head too."""
    w = widths(config)
    return w["vocab"] * w["hidden"]


def total_params(config: dict) -> int:
    """Parameters the chip holds: the layers kept with every expert, the
    embedding once (the head is tied to it), every norm weight and the
    router's correction bias."""
    w = widths(config)
    sparse = w["layers"] - w["first_dense"]
    norms = (w["layers"] * 2 * w["hidden"]
             + w["full_layers"] * 2 * w["head_dim"] + w["hidden"])
    return (dense_params(config)
            + sparse * w["experts"] * (expert_params(config) + 1)
            + head_params(config) + norms)


def cache_bytes_per_token(config: dict, itemsize: int = 2) -> int:
    """K and V of one token in ONE attention layer."""
    w = widths(config)
    return 2 * w["kv_heads"] * w["head_dim"] * itemsize


def state_bytes_per_slot(config: dict, itemsize: int = 2) -> int:
    """The rows ONE conv layer keeps of a sequence."""
    w = widths(config)
    return (w["taps"] - 1) * w["hidden"] * itemsize


def experts_hit(config: dict) -> float:
    """Experts of one layer that ``num_slots`` tokens hit under uniform
    routing: each is missed by a token with probability ``1 - topk /
    experts``."""
    w = widths(config)
    return w["experts"] * (1.0 - (1.0 - w["topk"] / w["experts"])
                           ** w["slots"])


def token_matmul_params(config: dict) -> float:
    """Weights one token is multiplied by: the dense part of every layer,
    the head, and ``topk`` experts an expert layer."""
    w = widths(config)
    sparse = w["layers"] - w["first_dense"]
    return (dense_params(config) + head_params(config)
            + sparse * w["topk"] * expert_params(config))


def decode_step_bytes(config: dict, cached_tokens: int,
                      itemsize: int = 2) -> float:
    """Bytes one decode round has to read: every dense weight and the head
    once, the experts hit a layer, every cached token's rows in the
    attention layers, every slot's state rows in the conv layers."""
    w = widths(config)
    sparse = w["layers"] - w["first_dense"]
    weights = (dense_params(config) + head_params(config)
               + sparse * experts_hit(config) * expert_params(config))
    return (itemsize * weights
            + float(cache_bytes_per_token(config, itemsize))
            * w["full_layers"] * int(cached_tokens)
            + float(state_bytes_per_slot(config, itemsize))
            * w["conv_layers"] * w["slots"])


def decode_step_flops(config: dict, active: int, cached_tokens: int) -> float:
    """A query reads a cached row as one ``head_dim`` key and one
    ``head_dim`` value for every query head; a conv layer's three taps are
    a multiply and an add a channel each."""
    w = widths(config)
    per_row = 2.0 * w["heads"] * 2 * w["head_dim"]
    conv = 2.0 * w["taps"] * w["hidden"] * w["conv_layers"]
    return ((2.0 * token_matmul_params(config) + conv) * active
            + per_row * w["full_layers"] * int(cached_tokens))


def train_flops_per_token(config: dict, seq: int) -> float:
    """Forward + backward: 6 per matmul weight a token meets, plus causal
    attention in the full layers (scores and values over ``head_dim``, half
    of ``seq`` under the mask, times 3)."""
    w = widths(config)
    per_key = 3.0 * w["heads"] * 2 * w["head_dim"]
    return 6.0 * token_matmul_params(config) \
        + per_key * w["full_layers"] * seq


def attention_call_shape(config: dict, run_values: dict) -> tuple:
    w = widths(config)
    return (run_values["batch"], w["heads"], run_values["seq"],
            w["head_dim"])
