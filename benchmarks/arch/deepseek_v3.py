"""The ``deepseek_v3`` adapter: everything the benchmark knows of the
architecture whose configuration has ``kv_lora_rank``, ``qk_nope_head_dim``,
``qk_rope_head_dim``, ``v_head_dim``, ``q_lora_rank`` (null here),
``first_k_dense_replace``, ``n_routed_experts``, ``n_shared_experts``,
``num_experts_per_tok``, ``scoring_func`` and ``topk_method``: latent
attention, leading dense layers, then expert layers with a sigmoid router
steered by a correction bias, top-k renormalised and scaled, shared experts.
The model is the program's ``DeepseekV3Model``, the reference
``benchmarks/reference/deepseek_v3.py``.  TRAINED; no serving section yet
(the two decode counts below are from shapes, for a later cell).

**One chip's share.**  ``n_routed_experts`` in the configuration file is
the number of routed experts HELD here; ``deployment.n_routed_experts_published``
is the router's published width, ``deployment.expert_parallel_rank`` the
share.  ``vocab_size`` is the slice of the vocabulary held here: ids, logits
and loss are over the slice.  Program and reference get the same share.

**Counts** are what the algorithm needs, from shapes alone: 6 operations a
matmul weight a trained token meets (the dense part of every layer, the
head over the slice, and its EXPECTED pairs on held experts under uniform
routing, ``topk x held / router width``), plus causal attention at two
widths: a key costs a query ``2 x (nope + rope)`` operations for the score
and ``2 x v_dim`` for the value, half the keys under the mask, backward
twice the forward (scores recomputed by the flash kernels and layers
recomputed under remat are the program's choices and count for nothing).

**Tolerances.**  Compute is bfloat16 over float32 master weights, the router
float32; the reference float32 at the highest matmul precision.
``loss_rel`` and ``grad_norm_rel`` are set from the chip's readings of the
program as stated and of the nearest precision below put in its place
(``benchmarks/tools/check_control_train.py``; the numbers are in
``TOLERANCES`` and PERF.md, PR 34).
"""

from __future__ import annotations

import numpy as np

from benchmarks.harness import spec

TOLERANCES = {
    "logit_err": {
        "limit": 0.10,
        "why": "no cell serves this configuration; stated for a test-size "
               "comparison on the CPU (an exchanged router choice moves a "
               "row, as in K-EXAONE's adapter)"},
    "token_gap": {
        "limit": 0.07,
        "why": "no cell serves this configuration; stated for a test-size "
               "comparison on the CPU"},
    "loss_rel": {
        "limit": 1e-4,
        "why": "two readings on the v5e at the published widths, one "
               "8192-token sequence (PR 34, PERF.md). The program as "
               "stated: 0.97e-5 to 3.04e-5 over ten seeds (six 40 s runs' "
               "own checks, a traced run's, three of "
               "benchmarks/tools/check_control_train.py --stated). The "
               "control, the nearest precision below in the loss "
               "function's place (every bfloat16 value and every "
               "cotangent of one rounded to three mantissa bits, matmul "
               "and kernel operands included): 6.2e-4 to 8.4e-4 over "
               "three seeds; matmul and kernel operands alone: 3.6e-4 and "
               "5.5e-4. The limit is the geometric mean of the stated "
               "largest and the control's smallest, 3.3 times over the "
               "one and 3.6 under the other"},
    "grad_norm_rel": {
        "limit": 1e-3,
        "why": "as above: the program as stated 6.2e-5 to 9.0e-5 over ten "
               "seeds; the control 1.29e-2 to 1.30e-2 (all values) and "
               "1.11e-2 (matmul and kernel operands alone). The geometric "
               "mean, 11 times from each: the control is not correct by "
               "either limit on any seed"},
}


def tolerances(config: dict) -> dict:
    return TOLERANCES


# ------------------------------------------------------- the configuration

def widths(config: dict) -> dict:
    dep = config["deployment"]
    held = int(config["n_routed_experts"])
    return {
        "hidden": int(config["hidden_size"]),
        "layers": int(config["num_hidden_layers"]),
        "first_dense": int(config["first_k_dense_replace"]),
        "heads": int(config["num_attention_heads"]),
        "kv_rank": int(config["kv_lora_rank"]),
        "nope": int(config["qk_nope_head_dim"]),
        "rope": int(config["qk_rope_head_dim"]),
        "v_dim": int(config["v_head_dim"]),
        "ffn": int(config["intermediate_size"]),
        "expert_ffn": int(config["moe_intermediate_size"]),
        "shared": int(config["n_shared_experts"]),
        "held": held,
        "first": int(dep["expert_parallel_rank"]) * held,
        "n_routed": int(dep["n_routed_experts_published"]),
        "topk": int(config["num_experts_per_tok"]),
        "scaling": float(config["routed_scaling_factor"]),
        "theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        "vocab": int(config["vocab_size"]),
        "positions": int(config["max_position_embeddings"]),
    }


def dims(config: dict) -> dict:
    """What the reference's functions take."""
    w = widths(config)
    return {**{k: w[k] for k in ("heads", "nope", "rope", "v_dim", "kv_rank",
                                 "theta", "eps", "topk", "scaling",
                                 "n_routed")},
            "held": (w["first"], w["held"])}


def id_range(config: dict) -> tuple:
    """Ids are drawn from the slice of the vocabulary held here."""
    return 0, int(config["vocab_size"])


def positions(config: dict) -> int:
    return int(config["max_position_embeddings"])


def make_model(config: dict, section: str):
    import jax.numpy as jnp

    from hetu_tpu.models.deepseek_v3 import DeepseekV3Config, DeepseekV3Model

    if section != "train":
        raise ValueError(
            f"configuration {config['name']} has no {section!r} section: "
            f"its scope is training")
    if config["q_lora_rank"] is not None or config["rope_scaling"] \
            or config["scoring_func"] != "sigmoid" \
            or config["topk_method"] != "noaux_tc" \
            or not config["norm_topk_prob"] or int(config["n_group"]) != 1 \
            or int(config["topk_group"]) != 1 \
            or int(config["moe_layer_freq"]) != 1 \
            or config["tie_word_embeddings"] or config["attention_bias"]:
        raise ValueError(
            "the program's DeepseekV3Model has no query rank, no rope "
            "scaling, one group, a sigmoid router steered by a correction "
            "bias and renormalised, an expert layer in every layer after "
            "the leading dense ones, an untied head and no attention bias")
    w, a, sec = widths(config), config["assumed"], config["train"]
    return DeepseekV3Model(DeepseekV3Config(
        vocab_size=w["vocab"], hidden_size=w["hidden"],
        num_layers=w["layers"], num_heads=w["heads"],
        kv_lora_rank=w["kv_rank"], qk_nope_head_dim=w["nope"],
        qk_rope_head_dim=w["rope"], v_head_dim=w["v_dim"],
        ffn_size=w["ffn"], expert_ffn_size=w["expert_ffn"],
        n_shared_experts=w["shared"], first_dense=w["first_dense"],
        n_routed_experts=w["n_routed"], moe_topk=w["topk"],
        routed_scaling_factor=w["scaling"], held=(w["first"], w["held"]),
        bias_update_rate=float(a["bias_update_rate"]),
        rope_theta=w["theta"], rms_eps=w["eps"],
        max_position=w["positions"],
        dtype=getattr(jnp, config["compute_dtype"]),
        param_dtype=getattr(jnp, config["param_dtype"]),
        init_std=float(a["init_std"]),
        router_init_std=float(a["router_init_std"]),
        embedding_init_std=float(a["embedding_init_std"]),
        attention_impl=sec["attention_impl"],
        fused_ce=bool(sec["fused_ce"]), remat=bool(sec["remat"])))


# ------------------------------------------------- reference and system

def reference(config: dict):
    return spec.reference(config)


def reference_logits(params, ids, config: dict):
    """Whole: no cell serves this configuration, and the test size fits."""
    import jax

    ref, d = reference(config), dims(config)
    return np.asarray(jax.jit(lambda p, x: ref.logits(p, x, d))(params, ids))


def reference_loss_and_grad_norm(params, ids, config: dict) -> tuple:
    """The gradient a layer at a time, attention a tile at a time: an
    8192-token sequence's scores are 8.6 GB, and after the window the chip
    also holds fresh weights and a fresh optimizer state."""
    import jax.numpy as jnp

    loss, norm = reference(config).loss_and_grad_norm_by_layer(
        params, jnp.asarray(ids), dims(config))
    return float(loss), float(norm)


def system_logits(model, params, ids):
    import jax
    import jax.numpy as jnp

    return np.asarray(jax.jit(
        lambda p, x: model.apply({"params": p, "state": {}}, x)[0])(
            params, jnp.asarray(ids)).astype(jnp.float32))


# ------------------------------------------------- operations and bytes

def attention_params(config: dict) -> int:
    """Matmul weights of one attention block."""
    w = widths(config)
    return (w["hidden"] * w["heads"] * (w["nope"] + w["rope"])
            + w["hidden"] * (w["kv_rank"] + w["rope"])
            + w["kv_rank"] * w["heads"] * (w["nope"] + w["v_dim"])
            + w["heads"] * w["v_dim"] * w["hidden"])


def expert_params(config: dict) -> int:
    """One routed expert; the shared experts are ``n_shared`` of them side
    by side."""
    w = widths(config)
    return 3 * w["hidden"] * w["expert_ffn"]


def dense_params(config: dict) -> int:
    """Matmul weights outside the routed experts and the head, all layers."""
    w = widths(config)
    sparse = w["layers"] - w["first_dense"]
    return (w["layers"] * attention_params(config)
            + w["first_dense"] * 3 * w["hidden"] * w["ffn"]
            + sparse * (w["shared"] * expert_params(config)
                        + w["hidden"] * w["n_routed"]))


def head_params(config: dict) -> int:
    w = widths(config)
    return w["vocab"] * w["hidden"]


def total_params(config: dict) -> int:
    """Parameters this chip holds and trains: the layers kept, the experts
    held, the slice of the embedding and of the untied head, every norm
    weight.  The correction bias is state, not a parameter."""
    w = widths(config)
    sparse = w["layers"] - w["first_dense"]
    norms = w["layers"] * (2 * w["hidden"] + w["kv_rank"]) + w["hidden"]
    return (dense_params(config)
            + sparse * w["held"] * expert_params(config)
            + 2 * head_params(config) + norms)


def expected_held_pairs(config: dict) -> float:
    """(token, choice) pairs a token sends to this chip's experts under
    uniform routing."""
    w = widths(config)
    return w["topk"] * w["held"] / w["n_routed"]


def token_matmul_params(config: dict) -> float:
    """Weights one token is multiplied by."""
    w = widths(config)
    sparse = w["layers"] - w["first_dense"]
    return (dense_params(config) + head_params(config)
            + sparse * expected_held_pairs(config) * expert_params(config))


def train_flops_per_token(config: dict, seq: int) -> float:
    """Forward + backward: 6 per matmul weight a token meets, plus causal
    attention at two widths, ``3 x heads x seq x (qk + v)`` a layer."""
    w = widths(config)
    per_key = 3.0 * w["heads"] * (w["nope"] + w["rope"] + w["v_dim"])
    return 6.0 * token_matmul_params(config) + per_key * w["layers"] * seq


def attention_call_shape(config: dict, run_values: dict) -> tuple:
    """(batch, heads, seq, head_dim of Q and K) of one flash call."""
    w = widths(config)
    return (run_values["batch"], w["heads"], run_values["seq"],
            w["nope"] + w["rope"])


def attention_call_widths(config: dict) -> tuple:
    """(width of Q and K, width of V and O) of one flash call."""
    w = widths(config)
    return w["nope"] + w["rope"], w["v_dim"]


def cache_bytes_per_token(config: dict, itemsize: int = 2) -> int:
    """The latent and the shared rotated key of one token in ONE layer."""
    w = widths(config)
    return (w["kv_rank"] + w["rope"]) * itemsize


def decode_step_bytes(config: dict, cached_tokens: int,
                      itemsize: int = 2) -> float:
    """Bytes one decode step has to read: every dense weight and the head
    once and every cached token's latent row in every layer; the experts a
    step's tokens hit are left out (shapes do not say which)."""
    w = widths(config)
    return (itemsize * (dense_params(config) + head_params(config))
            + float(cache_bytes_per_token(config, itemsize))
            * w["layers"] * int(cached_tokens))


def decode_step_flops(config: dict, active: int, cached_tokens: int) -> float:
    """Absorbed form: a query reads a cached row as ``kv_rank + rope`` for
    the score and ``kv_rank`` for the value, for every head."""
    w = widths(config)
    per_row = 2.0 * w["heads"] * (2 * w["kv_rank"] + w["rope"])
    return (2.0 * token_matmul_params(config) * active
            + per_row * w["layers"] * int(cached_tokens))
