"""The adapter of a second architecture, for tests only: the block of
``hetu_tpu/models/llama.py`` (RMSNorm, rotary positions, SwiGLU, fewer
key/value heads than query heads, an untied head, no learned positions)
under a configuration with the keys a published Llama config has.  It is
what shows that ``benchmarks/harness/``, ``benchmarks/readers/`` and
``benchmarks/run.py`` take an architecture by the files its configuration
names; it is never listed in ``BENCHMARK.json`` (the ``model-configs`` guide
excludes the family from cells), and its widths are 64.

Tolerances: the fixture states float32 on both sides, which differ only by
the order of operations.  Read on the CPU (PR 27, seeds 0-11, the largest
of sound runs): ``logit_err`` 7.1e-7, ``token_gap`` 0, ``loss_rel`` 2.3e-7,
``grad_norm_rel`` 1.2e-7.  The control, bfloat16 where float32 is stated
(seeds 0-2, the smallest): ``logit_err`` 7.5e-3, ``token_gap`` 7.2e-5,
``loss_rel`` 1.4e-5, ``grad_norm_rel`` 1.6e-3.  ``logit_err``, ``loss_rel``
and ``grad_norm_rel`` each fail it; ``token_gap`` may not (a token that
trails by nothing under coarser arithmetic is still the best), and is held
by a wrong cache read instead (``test_arch_seam.py``).
"""

from __future__ import annotations

import numpy as np

from benchmarks.harness import spec

TOLERANCES = {
    "logit_err": {"limit": 1e-4, "why": "sound 7.1e-7 at most, bfloat16 "
                  "7.5e-3 at least"},
    "token_gap": {"limit": 1e-4, "why": "sound 0; a decode that reads a "
                  "page too few reads 0.1 and more"},
    "loss_rel": {"limit": 2e-6, "why": "sound 2.3e-7 at most, bfloat16 "
                 "1.4e-5 at least"},
    "grad_norm_rel": {"limit": 1e-5, "why": "sound 1.2e-7 at most, "
                      "bfloat16 1.6e-3 at least"},
}


def tolerances(config: dict) -> dict:
    return TOLERANCES


def widths(config: dict) -> dict:
    h, nh = int(config["hidden_size"]), int(config["num_attention_heads"])
    return {"hidden": h, "heads": nh, "head_dim": h // nh,
            "kv_heads": int(config["num_key_value_heads"]),
            "layers": int(config["num_hidden_layers"]),
            "ffn": int(config["intermediate_size"]),
            "vocab": int(config["vocab_size"]),
            "theta": float(config["rope_theta"]),
            "eps": float(config["rms_norm_eps"])}


def id_range(config: dict) -> tuple:
    return 0, int(config["vocab_size"])


def positions(config: dict) -> int:
    return int(config["max_position_embeddings"])


def make_model(config: dict, section: str):
    import jax.numpy as jnp

    from hetu_tpu.models.llama import LlamaConfig, LlamaModel

    w, sec = widths(config), config[section]
    kw = {}
    if section == "train":
        kw = {"fused_ce": bool(sec["fused_ce"]), "remat": bool(sec["remat"])}
    return LlamaModel(LlamaConfig(
        vocab_size=int(config["assumed"]["embedding_rows"]),
        hidden_size=w["hidden"], num_layers=w["layers"],
        num_heads=w["heads"], num_kv_heads=w["kv_heads"], ffn_size=w["ffn"],
        max_position=positions(config), rope_theta=w["theta"],
        rms_eps=w["eps"], dtype=getattr(jnp, config["compute_dtype"]),
        attention_impl=sec["attention_impl"], **kw))


def reference(config: dict):
    return spec.reference(config)


def reference_logits(params, ids, config: dict):
    import jax

    ref, w = reference(config), widths(config)
    return np.asarray(jax.jit(lambda p, x: ref.logits(p, x, w))(params, ids))


def reference_loss_and_grad_norm(params, ids, config: dict) -> tuple:
    import jax

    ref, w = reference(config), widths(config)
    loss, norm = jax.jit(
        lambda p, x: ref.loss_and_grad_norm(p, x, w))(params, ids)
    return float(loss), float(norm)


def system_logits(model, params, ids):
    import jax
    import jax.numpy as jnp

    return np.asarray(jax.jit(
        lambda p, x: model.apply({"params": p, "state": {}}, x)[0])(
            params, jnp.asarray(ids)).astype(jnp.float32))


def matmul_params(config: dict) -> int:
    """Per-token matmul parameters: attention (Q and the output over all
    heads, K and V over the key/value heads), three SwiGLU matrices, and
    the untied output head over the real vocabulary."""
    w = widths(config)
    h, kv = w["hidden"], w["kv_heads"] * w["head_dim"]
    return w["layers"] * (2 * h * h + 2 * h * kv + 3 * h * w["ffn"]) \
        + w["vocab"] * h


def total_params(config: dict) -> int:
    w = widths(config)
    return matmul_params(config) + w["vocab"] * w["hidden"] \
        + (2 * w["layers"] + 1) * w["hidden"]


def train_flops_per_token(config: dict, seq: int) -> float:
    w = widths(config)
    return 6.0 * matmul_params(config) + 6.0 * w["layers"] * w["hidden"] * seq


def attention_call_shape(config: dict, run_values: dict) -> tuple:
    w, mesh = widths(config), run_values.get("mesh", {})
    return (run_values["batch"] // mesh.get("dp", 1),
            w["heads"] // mesh.get("tp", 1), run_values["seq"],
            w["head_dim"])


def decode_step_bytes(config: dict, cached_tokens: int,
                      itemsize: int = 2) -> float:
    """Weights once, and K and V of the key/value heads alone."""
    w = widths(config)
    return itemsize * (matmul_params(config) + 2.0 * w["layers"]
                       * w["kv_heads"] * w["head_dim"] * cached_tokens)


def decode_step_flops(config: dict, active: int, cached_tokens: int) -> float:
    w = widths(config)
    return 2.0 * matmul_params(config) * active \
        + 4.0 * w["layers"] * w["hidden"] * cached_tokens
