"""The ``mellum`` adapter: everything the benchmark knows of the architecture
whose configuration has ``layer_types`` (sliding and full attention layers
mixed), ``rope_parameters`` by layer type (YaRN on the full layers),
``head_dim``, ``num_key_value_heads``, ``sliding_window``, ``num_experts``,
``num_experts_per_tok``, ``norm_topk_prob`` and ``mlp_layer_types`` (every
one ``sparse``): grouped-query attention through the flash kernels with a
window on three layers of four, then an expert layer with a softmax router
over all experts, top-k renormalised, no shared expert and no bias.  The
model is the program's ``MellumModel``, the reference
``benchmarks/reference/mellum.py``.  TRAINED; no serving section.

**One chip's share.**  ``num_experts`` in the configuration file is the
number of routed experts HELD here; ``deployment.num_experts_published`` is
the router's published width, ``deployment.expert_parallel_rank`` the share.
``vocab_size`` is the slice of the vocabulary held here: ids, logits and loss
are over the slice.  ``layer_types`` and ``mlp_layer_types`` are kept whole
(28 entries); the first ``num_hidden_layers`` of them are the layers run.
Program and reference get the same share.

**Counts** are what the algorithm needs, from shapes alone: 6 operations a
matmul weight a trained token meets (attention's four projections and the
router in every layer, the head over the slice, and its EXPECTED pairs on
held experts under uniform routing, ``topk x held / router width``), plus
attention at the scores the layer's MASK leaves live: a query at position
``i`` sees ``i + 1`` keys on a full layer and ``min(i + 1, window)`` on a
sliding one, a live score costs ``2 x head_dim`` operations for the score
and ``2 x head_dim`` for the value in every query head, backward twice the
forward (scores recomputed by the flash kernels and layers recomputed under
remat are the program's choices and count for nothing).  So ``mfu_pct`` is of
the work THIS model requires, not of a causal model's.

**Tolerances.**  Compute is bfloat16 over float32 master weights, the router
and the softmax float32; the reference float32 at the highest matmul
precision.  ``loss_rel`` and ``grad_norm_rel`` are set from the chip's
readings of the program as stated and of the nearest precision below put in
its place (``benchmarks/tools/check_control_train.py``; the numbers are in
``TOLERANCES`` and PERF.md, PR 40).
"""

from __future__ import annotations

import numpy as np

from benchmarks.harness import spec
from benchmarks.readers.flash_roofline_masked import live_scores

SLIDING, FULL = "sliding_attention", "full_attention"
# the ``jax.named_scope`` each kind of layer's attention runs under: a flash
# custom-call carries it as its name in a device trace
SCOPES = {SLIDING: "hetu.attn.window", FULL: "hetu.attn.full"}

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
        "why": "two readings on the v5e at the published widths, the "
               "step's one 16,384-token sequence (PR 40, PERF.md). The "
               "program as stated: 2.7e-7 to 1.2e-5 over eight seeds "
               "(the cell's own checks in call 7 and two of "
               "benchmarks/tools/check_control_train.py --stated; 5.4e-7 to "
               "1.0e-5 over ten seeds before assumed.attention_out_init_std, "
               "calls 1-5). The control, the nearest precision below in the "
               "loss function's place (every bfloat16 value and every "
               "cotangent of one rounded to three mantissa bits, matmul and "
               "kernel operands included): 1.7e-3 and 1.8e-3 on two seeds "
               "(7.8e-4 and 8.9e-4 before). The limit is the harness's "
               "accepted train cells' (Kanana's), eight times over the "
               "stated largest and 17 times under the control's smallest"},
    "grad_norm_rel": {
        "limit": 1e-3,
        "why": "as above: the program as stated 2.5e-5 to 3.3e-5 over "
               "eight seeds (1.5e-6 to 7.4e-5 before); the control 1.85e-2 "
               "and 1.87e-2 (8.7e-3 and 9.0e-3 before). 30 times over "
               "the one and 18 times under the other: the control is not "
               "correct by either limit on any seed"},
}


def tolerances(config: dict) -> dict:
    return TOLERANCES


# ------------------------------------------------------- the configuration

def widths(config: dict) -> dict:
    dep = config["deployment"]
    held = int(config["num_experts"])
    layers = int(config["num_hidden_layers"])
    kinds = tuple(config["layer_types"][:layers])
    if set(config["mlp_layer_types"][:layers]) != {"sparse"}:
        raise ValueError("the program's MellumModel has an expert layer in "
                         "every layer")
    rope = config["rope_parameters"]
    thetas = {float(r["rope_theta"]) for r in rope.values()}
    if len(thetas) != 1 or rope[SLIDING]["rope_type"] != "default" \
            or rope[FULL]["rope_type"] != "yarn":
        raise ValueError("the program's MellumModel rotates sliding layers "
                         "by the plain table and full layers by YaRN, at "
                         "one theta")
    return {
        "hidden": int(config["hidden_size"]),
        "layers": layers,
        "layer_types": kinds,
        "full_layers": kinds.count(FULL),
        "window_layers": kinds.count(SLIDING),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "window": int(config["sliding_window"]),
        "expert_ffn": int(config["moe_intermediate_size"]),
        "held": held,
        "first": int(dep["expert_parallel_rank"]) * held,
        "n_routed": int(dep["num_experts_published"]),
        "topk": int(config["num_experts_per_tok"]),
        "theta": thetas.pop(),
        "yarn": {k: rope[FULL][k] for k in (
            "factor", "original_max_position_embeddings", "beta_fast",
            "beta_slow", "attention_factor")},
        "eps": float(config["rms_norm_eps"]),
        "vocab": int(config["vocab_size"]),
        "positions": int(config["max_position_embeddings"]),
    }


def _period(kinds: tuple) -> tuple:
    """The shortest run of layer types the layers repeat."""
    n = len(kinds)
    return next(kinds[:p] for p in range(1, n + 1)
                if n % p == 0 and kinds == kinds[:p] * (n // p))


def dims(config: dict) -> dict:
    """What the reference's functions take."""
    w = widths(config)
    return {**{k: w[k] for k in ("heads", "kv_heads", "head_dim", "window",
                                 "theta", "yarn", "eps", "topk",
                                 "n_routed")},
            "held": (w["first"], w["held"]),
            "period": _period(w["layer_types"])}


def id_range(config: dict) -> tuple:
    """Ids are drawn from the slice of the vocabulary held here."""
    return 0, int(config["vocab_size"])


def positions(config: dict) -> int:
    return int(config["max_position_embeddings"])


def make_model(config: dict, section: str):
    import jax.numpy as jnp

    from hetu_tpu.models.mellum import MellumConfig, MellumModel

    if section != "train":
        raise ValueError(
            f"configuration {config['name']} has no {section!r} section: "
            f"its scope is training")
    if not config["norm_topk_prob"] or config["tie_word_embeddings"] \
            or config["attention_bias"] or not config["use_sliding_window"] \
            or int(config["max_window_layers"]) != 0 \
            or config["hidden_act"] != "silu":
        raise ValueError(
            "the program's MellumModel renormalises its top-k, has an "
            "untied head, no attention bias, SwiGLU experts, and a window "
            "on every layer layer_types calls sliding")
    w, a, sec = widths(config), config["assumed"], config["train"]
    return MellumModel(MellumConfig(
        vocab_size=w["vocab"], hidden_size=w["hidden"],
        num_layers=w["layers"], num_heads=w["heads"],
        num_kv_heads=w["kv_heads"], head_dim=w["head_dim"],
        expert_ffn_size=w["expert_ffn"], n_routed_experts=w["n_routed"],
        moe_topk=w["topk"], held=(w["first"], w["held"]),
        window=w["window"], layer_types=w["layer_types"],
        rope_theta=w["theta"], yarn=w["yarn"], rms_eps=w["eps"],
        max_position=w["positions"],
        dtype=getattr(jnp, config["compute_dtype"]),
        param_dtype=getattr(jnp, config["param_dtype"]),
        init_std=float(a["init_std"]),
        router_init_std=float(a["router_init_std"]),
        embedding_init_std=float(a["embedding_init_std"]),
        out_init_std=float(a["attention_out_init_std"]),
        expert_block_rows=int(sec["expert_block_rows"]),
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
    """The gradient a layer at a time, attention a block of query rows at a
    time: a head's whole scores at 16,384 tokens are 1.07 GB, and after the
    window the chip also holds fresh weights and a fresh optimizer state."""
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
    """Matmul weights of one attention block: W_q, W_k, W_v, W_o."""
    w = widths(config)
    q, kv = w["heads"] * w["head_dim"], w["kv_heads"] * w["head_dim"]
    return w["hidden"] * (2 * q + 2 * kv)


def expert_params(config: dict) -> int:
    """One routed expert."""
    w = widths(config)
    return 3 * w["hidden"] * w["expert_ffn"]


def dense_params(config: dict) -> int:
    """Matmul weights outside the routed experts and the head, all layers."""
    w = widths(config)
    return w["layers"] * (attention_params(config)
                          + w["hidden"] * w["n_routed"])


def head_params(config: dict) -> int:
    w = widths(config)
    return w["vocab"] * w["hidden"]


def total_params(config: dict) -> int:
    """Parameters this chip holds and trains: the layers kept, the experts
    held, the slice of the embedding and of the untied head, every norm
    weight (a layer's two, its q and k norms, the last)."""
    w = widths(config)
    norms = w["layers"] * (2 * w["hidden"] + 2 * w["head_dim"]) + w["hidden"]
    return (dense_params(config)
            + w["layers"] * w["held"] * expert_params(config)
            + 2 * head_params(config) + norms)


def expected_held_pairs(config: dict) -> float:
    """(token, choice) pairs a token sends to this chip's experts under
    uniform routing."""
    w = widths(config)
    return w["topk"] * w["held"] / w["n_routed"]


def token_matmul_params(config: dict) -> float:
    """Weights one token is multiplied by."""
    w = widths(config)
    return (dense_params(config) + head_params(config)
            + w["layers"] * expected_held_pairs(config)
            * expert_params(config))


def train_flops_per_token(config: dict, seq: int) -> float:
    """Forward + backward: 6 per matmul weight a token meets, plus
    attention at the live scores of each layer's mask: ``3 x heads x 4 x
    head_dim`` a live score."""
    w = widths(config)
    per_score = 3.0 * w["heads"] * 4 * w["head_dim"]
    live = (w["full_layers"] * live_scores(seq)
            + w["window_layers"] * live_scores(seq, w["window"]))
    return 6.0 * token_matmul_params(config) + per_score * live / seq


def attention_call_shape(config: dict, run_values: dict) -> tuple:
    """(batch, heads, seq, head_dim of Q and K) of one flash call."""
    w = widths(config)
    return (run_values["batch"], w["heads"], run_values["seq"],
            w["head_dim"])


def attention_calls(config: dict, run_values: dict) -> dict:
    """{scope a kind of layer's flash calls run under: the call} for
    ``readers/flash_roofline_masked.py``: batch, query and KV heads,
    sequence, the two widths, and the window (None on a full layer)."""
    w = widths(config)
    call = {"batch": run_values["batch"], "heads": w["heads"],
            "kv_heads": w["kv_heads"], "seq": run_values["seq"],
            "d_qk": w["head_dim"], "d_v": w["head_dim"]}
    return {SCOPES[SLIDING]: {**call, "window": w["window"]},
            SCOPES[FULL]: {**call, "window": None}}


def cache_bytes_per_token(config: dict, itemsize: int = 2) -> int:
    """K and V of one token in ONE layer."""
    w = widths(config)
    return 2 * w["kv_heads"] * w["head_dim"] * itemsize


def _cached_rows(config: dict, cached_tokens: int) -> int:
    """Rows one decode step reads over all layers: every cached token in a
    full layer, at most the window's in a sliding one (as if one sequence
    held them all: shapes do not say how many are live)."""
    w = widths(config)
    return (w["full_layers"] * int(cached_tokens)
            + w["window_layers"] * min(int(cached_tokens), w["window"]))


def decode_step_bytes(config: dict, cached_tokens: int,
                      itemsize: int = 2) -> float:
    """No cell serves this configuration; from shapes, for a later one:
    every dense weight and the head once and the cached rows a step reads;
    the experts a step's tokens hit are left out (shapes do not say
    which)."""
    return (itemsize * (dense_params(config) + head_params(config))
            + float(cache_bytes_per_token(config, itemsize))
            * _cached_rows(config, cached_tokens))


def decode_step_flops(config: dict, active: int, cached_tokens: int) -> float:
    """As above: a query reads a cached row as ``head_dim`` for the score
    and ``head_dim`` for the value, for every query head."""
    w = widths(config)
    per_row = 2.0 * w["heads"] * 2 * w["head_dim"]
    return (2.0 * token_matmul_params(config) * active
            + per_row * _cached_rows(config, cached_tokens))
