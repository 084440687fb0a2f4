"""Falcon-H1's adapter: everything the benchmark knows of the architecture
whose configuration has ``mamba_d_ssm``, ``mamba_n_heads``, ``mamba_d_head``,
``mamba_d_state``, ``mamba_n_groups``, ``mamba_d_conv``, ``mamba_chunk_size``
and the nine multiplier keys: a Mamba-2 state-space mixer beside
grouped-query attention in every layer, a dense SwiGLU, an untied head.  The
model is the program's ``FalconH1Model``, the reference
``benchmarks/reference/falcon_h1.py``.

**One chip holds each layer whole.**  What is cut is depth
(``num_hidden_layers`` of the published 72; the period is one layer) and the
positions served; the vocabulary, every head and every width are whole
(``deployment.chips_sharing_a_layer`` 1).

**The reference runs a piece at a time** (``reference_logits`` owns the
jits): one branch's weights, ``FFN_COLS`` columns of the feed-forward or
``VOCAB_ROWS`` rows of the head are widened from bfloat16 to float32 at a
time, so that the float32 reference fits beside 10.5 GB of weights, the
pools and 1.65 GB of state once the window is over.  The feed-forward is a
sum over its intermediate width and the logits are rows of the head, so the
blocks add up and line up to what the reference file computes whole.

**Counts** are what the algorithm needs, from shapes alone.  A decode round
reads every matmul weight once (both branches, the feed-forward, the head;
the embedding is a gather of a row a slot), the cached rows of every live
token in every layer's attention, and READS AND WRITES every slot's state,
both parts (the convolution's three rows and the recurrence's float32
matrix): the state is the one thing a round has to write back whole.  Its
operations are two a matmul weight a token, attention's over the cached
rows, and the state update: a multiply-add an element of the matrix to
decay it, one to feed it and one to read it out.  This is the round's
roofline whatever implements the update.

**Tolerances.**  Weights and compute are bfloat16, the recurrence's state,
its decays and the convolution's sum float32; the reference is float32 at
the highest matmul precision over the same bfloat16 weights.  The readings
are in ``TOLERANCES`` and ``PERF.md`` (PR 47).
"""

from __future__ import annotations

import numpy as np

from benchmarks.harness import spec

FFN_COLS = 5376      # columns of the feed-forward the reference takes at a time
VOCAB_ROWS = 16320   # rows of the head the reference takes at a time

TOLERANCES = {
    "logit_err": {
        "limit": 0.025,
        "why": "max |system - reference| over the reference's range at the "
               "checked rows. Two readings on the v5e at the published "
               "widths (my chip runs, PR 47, PERF.md section 6). The "
               "program as stated (bfloat16 weights and compute, float32 "
               "state): 0.0047-0.0059 over 18 seeds of harness/check.py's "
               "comparison (check_seeds.py, call 3; the 40 s runs' own "
               "checks lie inside). The control, check_control.py: the "
               "nearest precision below put in the PROGRAM's place (every "
               "bfloat16 value its three entry points compute rounded to "
               "the three mantissa bits of an 8-bit float, matmul operands "
               "and so the weights included; the engine over it at 32 "
               "slots, because the rounded head is 2.5 GB more than a chip "
               "that is 93% full holds; the same comparison): 0.109-0.117 "
               "over three seeds, beside 0.0047-0.0059 as stated on the "
               "same seeds at 32 slots. No router exchanges a near tie "
               "here, so the stated readings spread by a factor of 1.25 "
               "where LFM2's spread by 2.5. The limit is the geometric "
               "mean of the two: 4.2 times over the stated largest, 4.4 "
               "times under the control's smallest; the control is not "
               "correct on any seed"},
    "token_gap": {
        "limit": 0.016,
        "why": "by the reference's logits the engine's token may trail the "
               "best by the two candidates' own errors. As stated 0-0.0044 "
               "over the same 18 seeds (the engine's token IS the "
               "reference's best on 10 of them); the control, through the "
               "engine and its decode program, 0.060-0.081 over three. The "
               "limit is the geometric mean: 3.6 times over the stated "
               "largest, 3.7 times under the control's smallest. This is "
               "the limit that holds the ENGINE: an earlier form of this "
               "PR's decode program, in which the TPU compiler "
               "rematerialised layer 0's in-place state update (two copies "
               "of it in the compiled text, both over the donated input), "
               "read 0.0027-0.0589 over the same 18 seeds (7 of "
               "them over 0.02) while logit_err, the dense forward's, read "
               "0.0047-0.0056 (call 2; PERF.md section 6). The recurrence's "
               "state held in bfloat16 with everything else as stated does "
               "NOT show in eight decoded tokens (0-0.0008, three seeds, "
               "call 2): tests/test_falcon_h1.py decodes 256 rounds both "
               "ways, and the bfloat16 one fails"},
    "loss_rel": {
        "limit": 2e-3,
        "why": "no cell trains this configuration; stated for a "
               "test-size comparison on the CPU"},
    "grad_norm_rel": {
        "limit": 2e-2,
        "why": "no cell trains this configuration; stated for a "
               "test-size comparison on the CPU"},
}

MULTIPLIERS = ("embedding_multiplier", "attention_in_multiplier",
               "attention_out_multiplier", "key_multiplier",
               "ssm_in_multiplier", "ssm_multipliers", "ssm_out_multiplier",
               "mlp_multipliers", "lm_head_multiplier")


def tolerances(config: dict) -> dict:
    return TOLERANCES


# ------------------------------------------------------- the configuration

def widths(config: dict) -> dict:
    heads, d_head = int(config["mamba_n_heads"]), int(config["mamba_d_head"])
    if heads * d_head != int(config["mamba_d_ssm"]):
        raise ValueError("mamba_d_ssm is not mamba_n_heads x mamba_d_head")
    groups, d_state = int(config["mamba_n_groups"]), \
        int(config["mamba_d_state"])
    channels = heads * d_head + 2 * groups * d_state
    return {
        "hidden": int(config["hidden_size"]),
        "layers": int(config["num_hidden_layers"]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "ffn": int(config["intermediate_size"]),
        "ssm_heads": heads, "ssm_head_dim": d_head, "d_ssm": heads * d_head,
        "d_state": d_state, "groups": groups,
        "taps": int(config["mamba_d_conv"]),
        "chunk": int(config["mamba_chunk_size"]),
        "conv_channels": channels,
        "in_width": heads * d_head + channels + heads,
        "theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        "vocab": int(config["vocab_size"]),
        "slots": int(config["serve"]["num_slots"]),
    }


def dims(config: dict) -> dict:
    """What the reference's functions take."""
    w = widths(config)
    return {**{k: w[k] for k in ("heads", "kv_heads", "head_dim", "theta",
                                 "eps", "ssm_heads", "ssm_head_dim",
                                 "d_state", "groups")},
            "mult": {
                "embedding": float(config["embedding_multiplier"]),
                "attention_in": float(config["attention_in_multiplier"]),
                "attention_out": float(config["attention_out_multiplier"]),
                "key": float(config["key_multiplier"]),
                "ssm_in": float(config["ssm_in_multiplier"]),
                "ssm": tuple(map(float, config["ssm_multipliers"])),
                "ssm_out": float(config["ssm_out_multiplier"]),
                "mlp": tuple(map(float, config["mlp_multipliers"])),
                "lm_head": float(config["lm_head_multiplier"])}}


def id_range(config: dict) -> tuple:
    """The whole vocabulary."""
    return 0, int(config["vocab_size"])


def positions(config: dict) -> int:
    return int(config["serve"]["max_len"])


def make_model(config: dict, section: str):
    import jax.numpy as jnp

    from hetu_tpu.models.falcon_h1 import FalconH1Config, FalconH1Model

    if section != "serve":
        raise ValueError(
            f"configuration {config['name']} has no {section!r} section: "
            f"the cell it was cut for serves")
    if config["attention_bias"] or config["mamba_proj_bias"] \
            or config["mlp_bias"] or config["projectors_bias"] \
            or not config["mamba_conv_bias"] or not config["mamba_rms_norm"] \
            or config["mamba_norm_before_gate"] \
            or config["tie_word_embeddings"] or config["rope_scaling"] \
            or config["hidden_act"] != "silu" \
            or int(config["deployment"]["chips_sharing_a_layer"]) != 1:
        raise ValueError("the program's FalconH1Model has no projection "
                         "bias but the convolution's, gates before its "
                         "group norm, rotates without scaling, has an "
                         "untied head, and is built here a layer whole")
    w = widths(config)
    model = FalconH1Model(FalconH1Config(
        vocab_size=w["vocab"], hidden_size=w["hidden"],
        num_layers=w["layers"], num_heads=w["heads"],
        num_kv_heads=w["kv_heads"], head_dim=w["head_dim"],
        ffn_size=w["ffn"], ssm_heads=w["ssm_heads"],
        ssm_head_dim=w["ssm_head_dim"], ssm_state=w["d_state"],
        ssm_groups=w["groups"], conv_taps=w["taps"], ssm_chunk=w["chunk"],
        rope_theta=w["theta"], rms_eps=w["eps"],
        max_position=positions(config),
        **{k: config[k] for k in MULTIPLIERS},
        dtype=getattr(jnp, config["compute_dtype"]),
        param_dtype=getattr(jnp, config["param_dtype"]),
        state_dtype=getattr(jnp, config["assumed"]["ssm_state_dtype"])))
    # the stds the file states are the ones the program draws with
    stated, drawn = config["assumed"]["init"]["std"], model.c.unit_stds()
    if set(stated) != set(drawn) or any(
            abs(stated[k] - drawn[k]) > 1e-4 * drawn[k] for k in drawn):
        raise ValueError(f"assumed.init.std {stated} is not the program's "
                         f"rule at these widths: {drawn}")
    return model


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
        cut = jax.lax.dynamic_slice_in_dim

        def ffn_block(p, u, lo, n):
            return ref.ffn_inner(
                {"gate": cut(p["gate"], lo, n, 1), "up": cut(p["up"], lo, n, 1),
                 "down": cut(p["down"], lo, n, 0)}, u, d)

        _JITS[key] = {
            "embed": jax.jit(lambda emb, ids: ref.embed(emb, ids, d)),
            "norm": jax.jit(lambda x, scale: ref.rms_norm(
                x, scale.astype(jnp.float32), d["eps"])),
            "add": jax.jit(lambda h, more, by: h + more * by,
                           donate_argnums=0),
            "attention": jax.jit(lambda p, a: ref.attention(p, a, d)),
            "mixer": jax.jit(lambda p, a: ref.mixer(p, a, d)),
            "ffn_block": jax.jit(ffn_block, static_argnums=3),
            "head": jax.jit(lambda w, h, lo, n: ref.head(
                cut(w, lo, n, 0), h, d), static_argnums=3),
        }
    return _JITS[key]


def reference_logits(params, ids, config: dict):
    """The reference's full forward, one branch's weights, ``FFN_COLS``
    columns of a feed-forward or ``VOCAB_ROWS`` rows of the head widened to
    float32 at a time; the same functions ``ref.logits`` is made of."""
    import gc

    gc.collect()    # a caller that has just dropped an engine: its pools
    fn, d = _jitted(config), dims(config)
    ref = reference(config)
    layers = params["layers"]
    ids = np.asarray(ids)
    h = fn["embed"](params["tok_emb"], ids)
    ffn_width = layers["ffn"]["gate"][0].shape[-1]
    for l in range(layers["attn_norm"].shape[0]):
        a = fn["norm"](h, layers["attn_norm"][l])
        h = fn["add"](h, fn["mixer"](ref.at(layers["ssm"], l), a), 1.0)
        h = fn["add"](h, fn["attention"](ref.at(layers["attn"], l), a), 1.0)
        u = fn["norm"](h, layers["ffn_norm"][l])
        ffn = ref.at(layers["ffn"], l)
        for lo in range(0, ffn_width, FFN_COLS):
            h = fn["add"](h, fn["ffn_block"](
                ffn, u, lo, min(FFN_COLS, ffn_width - lo)), d["mult"]["mlp"][1])
    h = fn["norm"](h, params["norm_f"])
    vocab = params["lm_head"].shape[0]
    return np.concatenate(
        [np.asarray(fn["head"](params["lm_head"], h, lo,
                               min(VOCAB_ROWS, vocab - lo)))
         for lo in range(0, vocab, VOCAB_ROWS)], -1)


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
    logits of one are 400 MB in float32 at the check's width, and the chip
    holds the weights, the pools and the state beside them."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda p, x: model.apply({"params": p, "state": {}}, x)[0]
                .astype(jnp.float32))
    return np.concatenate([np.asarray(f(params, jnp.asarray(row[None])))
                           for row in np.asarray(ids)], 0)


# ------------------------------------------------- operations and bytes

def attention_params(config: dict) -> int:
    w = widths(config)
    q = w["heads"] * w["head_dim"]
    kv = w["kv_heads"] * w["head_dim"]
    return w["hidden"] * (q + 2 * kv) + q * w["hidden"]


def mixer_matmul_params(config: dict) -> int:
    """The mixer's two projections."""
    w = widths(config)
    return w["hidden"] * w["in_width"] + w["d_ssm"] * w["hidden"]


def mixer_params(config: dict) -> int:
    """One mixer: the projections, the convolution's taps and bias, the
    gated norm's weight, and ``dt_bias``, ``A_log``, ``D`` a head."""
    w = widths(config)
    return (mixer_matmul_params(config) + (w["taps"] + 1) * w["conv_channels"]
            + w["d_ssm"] + 3 * w["ssm_heads"])


def ffn_params(config: dict) -> int:
    w = widths(config)
    return 3 * w["hidden"] * w["ffn"]


def layer_params(config: dict) -> int:
    """One layer: both branches, the feed-forward, the two norms."""
    w = widths(config)
    return (attention_params(config) + mixer_params(config)
            + ffn_params(config) + 2 * w["hidden"])


def head_params(config: dict) -> int:
    """The untied head; the embedding is as large again."""
    w = widths(config)
    return w["vocab"] * w["hidden"]


def total_params(config: dict) -> int:
    """Parameters the chip holds: the layers kept, the embedding, the head
    and the last norm."""
    w = widths(config)
    return (w["layers"] * layer_params(config) + 2 * head_params(config)
            + w["hidden"])


def token_matmul_params(config: dict) -> int:
    """Weights one token is multiplied by: both branches' projections and
    the feed-forward of every layer, and the head."""
    w = widths(config)
    return (w["layers"] * (attention_params(config)
                           + mixer_matmul_params(config) + ffn_params(config))
            + head_params(config))


def cache_bytes_per_token(config: dict, itemsize: int = 2) -> int:
    """K and V of one token in ONE layer's attention."""
    w = widths(config)
    return 2 * w["kv_heads"] * w["head_dim"] * itemsize


def state_elements(config: dict) -> int:
    """Elements of the recurrence's matrix a slot a layer."""
    w = widths(config)
    return w["ssm_heads"] * w["ssm_head_dim"] * w["d_state"]


def state_bytes_per_slot(config: dict, itemsize: int = 2) -> int:
    """What ONE layer keeps of a sequence: the convolution's rows in the
    compute type and the recurrence's matrix in float32."""
    w = widths(config)
    return ((w["taps"] - 1) * w["conv_channels"] * itemsize
            + state_elements(config) * 4)


def decode_step_bytes(config: dict, cached_tokens: int,
                      itemsize: int = 2) -> float:
    """Bytes one decode round has to move: every matmul weight and the head
    once, every cached token's rows in every layer's attention, and every
    slot's state, both parts, read AND written."""
    w = widths(config)
    return (itemsize * token_matmul_params(config)
            + float(cache_bytes_per_token(config, itemsize))
            * w["layers"] * int(cached_tokens)
            + 2.0 * state_bytes_per_slot(config, itemsize)
            * w["layers"] * w["slots"])


def decode_step_flops(config: dict, active: int, cached_tokens: int) -> float:
    """A query reads a cached row as one ``head_dim`` key and one
    ``head_dim`` value for every query head; the state update is a
    multiply-add an element of the matrix to decay it, one to feed it and
    one to read it out; the convolution's taps a multiply and an add a
    channel each."""
    w = widths(config)
    per_row = 2.0 * w["heads"] * 2 * w["head_dim"]
    update = (6.0 * state_elements(config)
              + 2.0 * w["taps"] * w["conv_channels"]) * w["layers"]
    return ((2.0 * token_matmul_params(config) + update) * active
            + per_row * w["layers"] * int(cached_tokens))


def train_flops_per_token(config: dict, seq: int) -> float:
    """Forward + backward: 6 per matmul weight a token meets, causal
    attention (scores and values over ``head_dim``, half of ``seq`` under
    the mask, times 3) and the recurrence's three multiply-adds an element,
    times 3.  Test size only: no cell trains this configuration."""
    w = widths(config)
    per_key = 3.0 * w["heads"] * 2 * w["head_dim"]
    return (6.0 * token_matmul_params(config)
            + per_key * w["layers"] * seq
            + 18.0 * state_elements(config) * w["layers"])


def attention_call_shape(config: dict, run_values: dict) -> tuple:
    w = widths(config)
    return (run_values["batch"], w["heads"], run_values["seq"],
            w["head_dim"])
