"""GPT-2's adapter: everything the benchmark knows of the architecture whose
configuration has ``n_embd``, ``n_head``, ``n_layer``, ``n_positions`` and
``vocab_size``.  Dense, pre-LN, a tied output head, learned positions, K and
V of ``hidden`` per layer.  The model is the program's ``GPTModel``, the
reference ``benchmarks/reference/gpt2.py`` (found through the
configuration's ``"reference"`` key).

Operations and bytes are what the algorithm needs, from shapes alone.
"Needs" means the published mathematics: recomputed layers (remat), padded
vocabulary rows, masked-out attention tiles and pool-sized copies are the
program's choices and count for nothing here, so a share of a peak computed
from these can only be UNDER-stated by them.  ``bench.py``'s
``_gpt_flops_per_token`` is the origin of ``train_flops_per_token``; it
counts attention unmasked (12*L*H*S), this one counts the causal half
(6*L*H*S), which is what a causal model requires.

Tolerances.  The configurations compute in bfloat16 (8 bits of mantissa,
relative rounding 2**-8 = 0.0039 per operation) over float32 weights; the
reference is float32 at the highest matmul precision.  Measured on the v5e
at the published widths (builder's chip runs, PR 23, some sixty runs over
four cells): ``logit_err`` 0.0056-0.0060 of the reference's logit range for
gpt2-small and 0.0075-0.0078 for gpt2-large, ``token_gap`` 0-0.002,
``loss_rel`` 3e-6-3e-5, ``grad_norm_rel`` 0.9e-3-1.6e-3.  Each bound below is
three to six times the worst of these.  A program that computed in 8-bit
floats or integers where bfloat16 is stated rounds sixteen times coarser
(2**-4 per operation) and lands an order of magnitude outside every one;
a wrong page, position or mask moves logits by their whole range
(``tests/test_reference.py`` shows both, at tiny widths).

``logit_err`` holds the model's dense forward; the engine, its page tables
and its decode program return tokens only and are held by ``token_gap``:
under the best of 50257 near-Gaussian logits lie on average 0.4 other
candidates within 1% of the range and six to eight within 5%, so the bound
is 1% (five times the worst measured) and not the 5% it first was, under
which a coarser cache or decode program could have passed.
"""

from __future__ import annotations

import numpy as np

from benchmarks.harness import spec

TOLERANCES = {
    "logit_err": {
        "limit": 0.025,
        "why": "max |system - reference| over the reference's range; "
               "measured 0.0056-0.0078 (PR 23), 8-bit arithmetic lands an "
               "order of magnitude outside"},
    "token_gap": {
        "limit": 0.01,
        "why": "by the reference's logits the engine's token may trail the "
               "best by the two candidates' own errors; measured 0-0.002 "
               "(PR 23), five times the worst and not the 5% under which a "
               "coarser cache or decode program could have passed"},
    "loss_rel": {
        "limit": 2e-4,
        "why": "measured 3e-6-3e-5 (PR 23)"},
    "grad_norm_rel": {
        "limit": 6e-3,
        "why": "measured 0.9e-3-1.6e-3 (PR 23)"},
}


def tolerances(config: dict) -> dict:
    return TOLERANCES


# ------------------------------------------------------- the configuration

def widths(config: dict) -> dict:
    h = int(config["n_embd"])
    ffn = config.get("n_inner") or config["assumed"]["n_inner_value"]
    return {"hidden": h, "layers": int(config["n_layer"]),
            "heads": int(config["n_head"]), "ffn": int(ffn),
            "head_dim": h // int(config["n_head"]),
            "vocab": int(config["vocab_size"]),
            "positions": int(config["n_positions"])}


def id_range(config: dict) -> tuple:
    """Ids are drawn from the real vocabulary only, never the padded rows."""
    return 0, int(config["vocab_size"])


def positions(config: dict) -> int:
    return int(config["n_positions"])


def make_model(config: dict, section: str):
    import jax.numpy as jnp

    from hetu_tpu.models.gpt import GPTConfig, GPTModel

    w = widths(config)
    sec = config[section]
    kw = {}
    if section == "train":
        kw = {"fused_ce": bool(sec["fused_ce"]), "remat": bool(sec["remat"])}
    return GPTModel(GPTConfig(
        vocab_size=int(config["assumed"]["embedding_rows"]),
        hidden_size=w["hidden"], num_layers=w["layers"],
        num_heads=w["heads"], ffn_size=w["ffn"],
        max_position=w["positions"], dropout_rate=0.0,
        dtype=getattr(jnp, config["compute_dtype"]),
        attention_impl=sec["attention_impl"], **kw))


# ------------------------------------------------- reference and system

def reference(config: dict):
    return spec.reference(config)


def reference_logits(params, ids, config: dict):
    import jax

    ref, heads = reference(config), int(config["n_head"])
    return np.asarray(jax.jit(
        lambda p, x: ref.logits(p, x, heads))(params, ids))


def reference_loss_and_grad_norm(params, ids, config: dict) -> tuple:
    import jax

    ref, heads = reference(config), int(config["n_head"])
    loss, norm = jax.jit(
        lambda p, x: ref.loss_and_grad_norm(p, x, heads))(params, ids)
    return float(loss), float(norm)


def system_logits(model, params, ids):
    import jax
    import jax.numpy as jnp

    return np.asarray(jax.jit(
        lambda p, x: model.apply({"params": p, "state": {}}, x)[0])(
            params, jnp.asarray(ids)).astype(jnp.float32))


# ------------------------------------------------- operations and bytes

def block_params(config: dict) -> int:
    """Parameters of one transformer block (weights and biases)."""
    w = widths(config)
    h, f = w["hidden"], w["ffn"]
    return (h * 3 * h + 3 * h) + (h * h + h) + (h * f + f) + (f * h + h) \
        + 4 * h


def matmul_params(config: dict) -> int:
    """Parameters every token is multiplied by: the blocks and the (tied)
    output head over the real vocabulary.  Embedding lookups are not
    matmuls."""
    w = widths(config)
    return w["layers"] * block_params(config) + w["vocab"] * w["hidden"]


def total_params(config: dict) -> int:
    w = widths(config)
    return (w["layers"] * block_params(config) + 2 * w["hidden"]
            + (w["vocab"] + w["positions"]) * w["hidden"])


def train_flops_per_token(config: dict, seq: int) -> float:
    """Forward + backward operations one trained token requires: 6 per
    matmul parameter, plus causal attention (QK^T and PV, each 2*S*H per
    token unmasked, half of it under the causal mask, times 3 for forward
    and backward)."""
    w = widths(config)
    return 6.0 * matmul_params(config) + 6.0 * w["layers"] * w["hidden"] * seq


def attention_call_shape(config: dict, run_values: dict) -> tuple:
    """(batch, heads, seq, head_dim) of one flash-attention call on one
    chip: the step's batch over ``dp``, the heads over ``tp``."""
    w = widths(config)
    mesh = run_values.get("mesh", {})
    return (run_values["batch"] // mesh.get("dp", 1),
            w["heads"] // mesh.get("tp", 1), run_values["seq"],
            w["head_dim"])


def decode_step_bytes(config: dict, cached_tokens: int,
                      itemsize: int = 2) -> float:
    """Bytes one decode step has to read: every matmul weight once, in the
    compute type, and the live cache (K and V of every layer for every
    token already cached in an active slot)."""
    w = widths(config)
    return itemsize * (matmul_params(config)
                       + 2.0 * w["layers"] * w["hidden"] * cached_tokens)


def decode_step_flops(config: dict, active: int, cached_tokens: int) -> float:
    w = widths(config)
    return 2.0 * matmul_params(config) * active \
        + 4.0 * w["layers"] * w["hidden"] * cached_tokens
