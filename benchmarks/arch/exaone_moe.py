"""K-EXAONE's adapter: everything the benchmark knows of the architecture
whose configuration has ``layer_types`` (window and full attention layers
mixed), ``head_dim``, ``num_key_value_heads``, ``first_k_dense_replace``,
``num_experts``, ``num_experts_per_tok``, ``num_shared_experts`` and
``scoring_func``: grouped-query attention with per-head q/k norms, window
layers with rope and full layers without, one leading dense layer, then
expert layers with a sigmoid router, renormalised top-k and a shared expert.
The model is the program's ``ExaoneMoeModel``, the reference
``benchmarks/reference/exaone_moe.py``.

**One chip's share.**  ``num_experts`` in the configuration file is the
number of routed experts HELD here; ``deployment.num_experts_published`` is
the router's published width and ``deployment.expert_parallel_rank`` says
which share.  ``vocab_size`` is the slice of the vocabulary held here: ids,
logits and sampling are over the slice.  ``layer_types`` and
``mlp_layer_types`` are kept whole (48 entries); the first
``num_hidden_layers`` of them are the layers run.  Program and reference get
the same share: held experts and the shared expert add, absent ones do not.

**The reference runs in blocks** (``reference_logits`` owns the jit): one
sub-block's bfloat16 weights are widened to float32 at a time, every
per-token piece takes ``ROWS`` tokens at a time, and attention takes one
tile of (KV heads x query rows) at a time so that its score matrix stays
under ``SCORES_BYTES``: a 30,000-token comparison
(``benchmarks/tools/compare_long.py``) then fits beside 7.4 GB of weights.
A window layer's tile is handed only the keys its queries can see.

**Counts** are what the algorithm needs, from shapes alone.  A decode step
reads every dense weight once (attention, the dense FFN, the shared experts,
the routers) and the output head, the cache of every live token in the full
layers, and in the window layers the last ``sliding_window`` rows of each
live sequence: ``min(cached_tokens, num_slots x window)`` rows, since shapes
do not say how many slots are live.  The experts its tokens HIT are left
out, as in LongCat's adapter (shapes do not say which), so
``decode_roofline`` can only be under-stated by them (at 16 live tokens x 8
choices x 16 / 128 held, up to 16 experts a layer: at most 2.4 GB beside
2.2 GB of dense bytes; PERF.md gives the measured hit count).
``decode_step_flops`` counts the expected pairs on held experts under
uniform routing (``topk x held / router width`` a token).

**Tolerances.**  Weights and compute are bfloat16, the router float32; the
reference is float32 at the highest matmul precision over the same bfloat16
weights.  The readings are in ``TOLERANCES`` and ``PERF.md`` (PR 32): the
program as stated with ``benchmarks/tools/check_seeds.py``, the lower
precision in the program's place with ``benchmarks/tools/check_control.py``.
"""

from __future__ import annotations

import numpy as np

from benchmarks.harness import spec

SCORES_BYTES = 512 * 2 ** 20    # the reference's score matrix, at most
ROWS = 2048                     # tokens a per-token piece takes at a time

TOLERANCES = {
    "logit_err": {
        "limit": 0.10,
        "why": "max |system - reference| over the reference's range at the "
               "checked rows. Two readings on the v5e at the published "
               "widths, final tree (PR 32, PERF.md). The program as stated "
               "(bfloat16): 0.006-0.078 over 62 seeds of harness/check.py's "
               "comparison and nine 40 s runs' own checks; a row in which "
               "no router choice is exchanged reads 0.0046, an exchange of "
               "the eighth and ninth of 128 sigmoid scores between a held "
               "and an absent expert moves a row by 0.03-0.04 (2.5 / 8 of "
               "an expert's output in a stream five layers deep), and "
               "nearly every check of 36 rows holds at least one. The control, "
               "benchmarks/tools/check_control.py: the nearest precision "
               "below put in the PROGRAM's place (every bfloat16 value its "
               "three entry points compute rounded to the three mantissa "
               "bits of an 8-bit float, matmul operands and so the weights "
               "included; engine and page tables over it; the same "
               "comparison): 0.130-0.168 over 10 seeds (matmul operands "
               "alone rounded: 0.111-0.135 over 6). The limit is the "
               "geometric mean of the stated largest and the control's "
               "smallest, 1.29 times from each: the control is not correct "
               "on any seed. The room is thin because the harness takes the "
               "MAXIMUM over rows, which one exchange sets in the stated "
               "program (PERF.md section 7: 59 of 65 rows read 0.004-0.007 "
               "as stated, so a statistic over rows would part the two "
               "readings widely; harness/check.py is not this PR's)"},
    "token_gap": {
        "limit": 0.07,
        "why": "by the reference's logits the engine's token may trail the "
               "best by the two candidates' own errors: an exchanged router "
               "choice at a near tie. As stated 0-0.017 over 62 seeds, "
               "0.001-0.047 in nine runs' checks, 0-0.024 at 1,500 to "
               "30,000 tokens (compare_long.py); the control (as above, "
               "through the engine) 0.044-0.128 over 10 seeds, median "
               "0.080, over this limit on 8 of 10. The two readings "
               "overlap (0.044 against 0.047), so no limit parts them: "
               "this one is 1.5 times the stated largest, and logit_err is "
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
    held = int(config["num_experts"])
    layers = int(config["num_hidden_layers"])
    kinds = tuple(config["layer_types"][:layers])
    mlp = tuple(config["mlp_layer_types"][:layers])
    dense = int(config["first_k_dense_replace"])
    if mlp != ("dense",) * dense + ("sparse",) * (layers - dense):
        raise ValueError(f"mlp_layer_types {mlp} are not "
                         f"{dense} dense layers then sparse ones")
    windows = {w for w, k in zip(config["sliding_windows"], kinds)
               if k == "sliding_attention"}
    if windows - {int(config["sliding_window"])}:
        raise ValueError(f"window layers of several widths: {windows}")
    return {
        "hidden": int(config["hidden_size"]),
        "layers": layers,
        "layer_types": kinds,
        "full_layers": kinds.count("full_attention"),
        "window_layers": kinds.count("sliding_attention"),
        "first_dense": dense,
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "window": int(config["sliding_window"]),
        "ffn": int(config["intermediate_size"]),
        "expert_ffn": int(config["moe_intermediate_size"]),
        "shared": int(config["num_shared_experts"]),
        "held": held,
        "first": int(dep["expert_parallel_rank"]) * held,
        "n_routed": int(dep["num_experts_published"]),
        "topk": int(config["num_experts_per_tok"]),
        "scaling": float(config["routed_scaling_factor"]),
        "theta": float(config["rope_parameters"]["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        "vocab": int(config["vocab_size"]),
        "slots": int(config["serve"]["num_slots"]),
    }


def dims(config: dict) -> dict:
    """What the reference's functions take."""
    w = widths(config)
    return {**{k: w[k] for k in ("heads", "kv_heads", "head_dim", "window",
                                 "layer_types", "first_dense", "n_routed",
                                 "topk", "scaling", "theta", "eps")},
            "held": (w["first"], w["held"])}


def id_range(config: dict) -> tuple:
    """Ids are drawn from the slice of the vocabulary held here."""
    return 0, int(config["vocab_size"])


def positions(config: dict) -> int:
    return int(config["serve"]["max_len"])


def make_model(config: dict, section: str):
    import jax.numpy as jnp

    from hetu_tpu.models.exaone_moe import ExaoneMoeConfig, ExaoneMoeModel

    if section != "serve":
        raise ValueError(
            f"configuration {config['name']} has no {section!r} section: "
            f"no cut of it trains on one chip")
    if config["scoring_func"] != "sigmoid" or not config["norm_topk_prob"] \
            or int(config["num_shared_experts"]) != 1 \
            or int(config["n_group"]) != 1 or int(config["topk_group"]) != 1:
        raise ValueError("the program's ExaoneMoeModel routes by sigmoid "
                         "scores renormalised over the chosen, one group, "
                         "one shared expert")
    w, a = widths(config), config["assumed"]
    return ExaoneMoeModel(ExaoneMoeConfig(
        vocab_size=w["vocab"], hidden_size=w["hidden"],
        num_layers=w["layers"], num_heads=w["heads"],
        num_kv_heads=w["kv_heads"], head_dim=w["head_dim"],
        ffn_size=w["ffn"], expert_ffn_size=w["expert_ffn"],
        first_dense=w["first_dense"], n_routed_experts=w["n_routed"],
        moe_topk=w["topk"], routed_scaling_factor=w["scaling"],
        held=(w["first"], w["held"]), window=w["window"],
        layer_types=w["layer_types"], rope_theta=w["theta"],
        rms_eps=w["eps"], max_position=positions(config),
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

        def tile(q, k, v, at, g, window, key_first, first, *, rows, groups):
            """Attention of rows ``at .. at + rows - 1`` of the queries ``q``
            (whose first row is at position ``first``) of the KV heads
            ``g .. g + groups - 1`` (and the query heads that read them) over
            the keys it was handed, whose first is at position
            ``key_first``."""
            rep = q.shape[2] // k.shape[2]
            q = jax.lax.dynamic_slice_in_dim(
                jax.lax.dynamic_slice_in_dim(q, at, rows, 1),
                g * rep, groups * rep, 2)
            k = jax.lax.dynamic_slice_in_dim(k, g, groups, 2)
            v = jax.lax.dynamic_slice_in_dim(v, g, groups, 2)
            return ref.attend(q, k, v, window, first + at, key_first)

        _JITS[key] = {
            "embed": jax.jit(lambda emb, ids: emb[ids].astype(jnp.float32)),
            "norm": jax.jit(lambda x, scale: ref.rms_norm(
                x, scale.astype(jnp.float32), d["eps"])),
            "add": jax.jit(lambda h, more: h + more, donate_argnums=0),
            "qkv": jax.jit(lambda p, x, first, rotate: ref.qkv(
                p, x, d, rotate, first), static_argnums=3),
            "tile": jax.jit(tile, static_argnums=5,
                            static_argnames=("rows", "groups")),
            "out": jax.jit(lambda o, w: ref.out_projection(w, o)),
            "ffn": jax.jit(ref.dense_ffn),
            "choice": jax.jit(lambda r, b, u: ref.expert_choice(r, b, u, d)),
            "shared": jax.jit(ref.shared_expert),
            "expert": jax.jit(ref.one_expert),
            "head": jax.jit(ref.head),
        }
    return _JITS[key]


def _rows(x, lo: int, n: int):
    """Rows ``lo .. lo + n - 1`` of ``x`` [B, S, ...] (one program a shape,
    whatever ``lo``)."""
    import jax

    return jax.lax.dynamic_slice_in_dim(x, lo, n, 1)


def _by_rows(f, s: int):
    """``f(lo, n)`` over the row blocks of ``s`` rows, the results joined on
    axis 1 (each a tuple of arrays or one array)."""
    import jax.numpy as jnp

    parts = [f(lo, min(ROWS, s - lo)) for lo in range(0, s, ROWS)]
    if isinstance(parts[0], tuple):
        return tuple(jnp.concatenate(p, 1) for p in zip(*parts))
    return jnp.concatenate(parts, 1)


def _attention(fn, d, p, h, scale, window):
    """One attention block of the reference over the stream ``h`` [B, S, H]
    (normed with ``scale`` a block of rows at a time): the keys and values
    of every row first, then one block of rows at a time its queries, their
    attention a tile of (KV heads x query rows) at a time, and the output
    projection; the normed stream, the queries and the heads-wide output of
    all rows are never whole (0.7 to 1 GB each at 30,000 tokens)."""
    import jax.numpy as jnp

    b, s, _ = h.shape
    rotate = window is not None

    def normed(lo, n):
        return fn["norm"](_rows(h, lo, n), scale)

    k, v = _by_rows(lambda lo, n: fn["qkv"](p, normed(lo, n), lo,
                                            rotate)[1:], s)
    rep = d["heads"] // d["kv_heads"]
    # a window layer's tile is handed the keys its rows can see: the rows'
    # own and the window - 1 before them (zeros at negative positions,
    # which the reference masks)
    rows = min(s, ROWS)
    if window is not None:
        pad = jnp.zeros((b, window - 1) + k.shape[2:], k.dtype)
        k, v = jnp.concatenate([pad, k], 1), jnp.concatenate([pad, v], 1)
    keys = rows + window - 1 if window is not None else s
    groups = d["kv_heads"]
    while groups > 1 and 4 * b * groups * rep * rows * keys > SCORES_BYTES:
        groups //= 2
    while rows > 16 and 4 * b * groups * rep * rows * keys > SCORES_BYTES:
        rows //= 2
        keys = rows + window - 1 if window is not None else s

    def block(lo, n):
        """Rows ``lo .. lo + n - 1``: [B, n, H]."""
        q = fn["qkv"](p, normed(lo, n), lo, rotate)[0]
        t = min(rows, n)
        tiles = []
        for at in range(0, n, t):
            at = min(at, n - t)         # the last tile ends with the block
            if window is not None:
                start = lo + at         # of the padded keys: its position
                seen = t + window - 1   # less window - 1
                k_t, v_t = _rows(k, start, seen), _rows(v, start, seen)
                key_first = start - (window - 1)
            else:
                k_t, v_t, key_first = k, v, 0
            tiles.append((at, jnp.concatenate(
                [fn["tile"](q, k_t, v_t, at, g, window, key_first, lo,
                            rows=t, groups=groups)
                 for g in range(0, d["kv_heads"], groups)], -1)))
        # the tiles in order; a last tile moved back overlaps the one before
        o = jnp.concatenate(
            [part[:, max(end - at, 0):] for (at, part), end in zip(
                tiles, [0] + [at + t for at, _ in tiles[:-1]])], 1)
        return fn["out"](o, p["o"])

    return _by_rows(block, s)


def reference_logits(params, ids, config: dict):
    """The reference's full forward, one sub-block's weights widened to
    float32 at a time and ``ROWS`` tokens at a time; the same functions
    ``ref.logits`` is made of."""
    import gc

    import jax

    # a caller that has just dropped a serving engine (compare_long.py) has
    # its pools back only once the engine's reference cycles are collected
    gc.collect()
    fn, d = _jitted(config), dims(config)
    layers = params["layers"]
    ids = np.asarray(ids)
    s = ids.shape[1]

    def at(tree, i):
        return jax.tree_util.tree_map(lambda a: a[i], tree)

    first, count = d["held"]
    h = fn["embed"](params["tok_emb"], ids)
    for l, kind in enumerate(d["layer_types"]):
        window = d["window"] if kind == "sliding_attention" else None
        h = fn["add"](h, _attention(fn, d, at(layers["attn"], l), h,
                                    layers["attn_norm"][l], window))
        scale = layers["ffn_norm"][l]
        if l < d["first_dense"]:
            p = at(layers["ffn"], l)
            h = fn["add"](h, _by_rows(lambda lo, n: fn["ffn"](
                p, fn["norm"](_rows(h, lo, n), scale)), s))
            continue
        moe = at(layers["moe"], l - d["first_dense"])

        def expert_layer(lo, n):
            rows = fn["norm"](_rows(h, lo, n), scale)
            weights, idx = fn["choice"](moe["router"], moe["router_bias"],
                                        rows)
            m = fn["shared"](moe, rows)
            for e in range(count):
                m = m + fn["expert"](
                    {k: moe[k][e] for k in ("gate", "up", "down")}, rows,
                    weights, idx, first + e)
            return m

        h = fn["add"](h, _by_rows(expert_layer, s))
    return np.concatenate(
        [np.asarray(fn["head"](params["lm_head"], fn["norm"](
            _rows(h, lo, min(ROWS, s - lo)), params["norm_f"])))
         for lo in range(0, s, ROWS)], 1)


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
    q = w["heads"] * w["head_dim"]
    kv = w["kv_heads"] * w["head_dim"]
    return w["hidden"] * (q + 2 * kv) + q * w["hidden"]


def expert_params(config: dict) -> int:
    """One routed expert, and the shared expert alike."""
    w = widths(config)
    return 3 * w["hidden"] * w["expert_ffn"]


def dense_params(config: dict) -> int:
    """Matmul weights outside the routed experts, all layers: attention in
    every layer, the dense FFN of the leading layers, the shared expert and
    the router of the others."""
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
    """Parameters this chip holds: the layers kept, the experts held, the
    slice of the embedding and of the (untied) head, every norm weight and
    the router's correction bias."""
    w = widths(config)
    sparse = w["layers"] - w["first_dense"]
    norms = w["layers"] * (2 * w["hidden"] + 2 * w["head_dim"]) + w["hidden"]
    return (dense_params(config)
            + sparse * (w["held"] * expert_params(config) + w["n_routed"])
            + 2 * head_params(config) + norms)


def cache_bytes_per_token(config: dict, itemsize: int = 2) -> int:
    """K and V of one token in ONE cache layer."""
    w = widths(config)
    return 2 * w["kv_heads"] * w["head_dim"] * itemsize


def expected_held_pairs(config: dict) -> float:
    """(token, choice) pairs a token sends to this chip's experts under
    uniform routing."""
    w = widths(config)
    return w["topk"] * w["held"] / w["n_routed"]


def token_matmul_params(config: dict) -> float:
    """Weights one token is multiplied by: the dense part of every layer,
    the head, and its expected pairs on held experts."""
    w = widths(config)
    sparse = w["layers"] - w["first_dense"]
    return (dense_params(config) + head_params(config)
            + sparse * expected_held_pairs(config) * expert_params(config))


def window_rows(config: dict, cached_tokens: int) -> int:
    """Rows a window layer's attention reads in a decode step: the last
    ``window`` of each live sequence, at most all that is cached."""
    w = widths(config)
    return min(int(cached_tokens), w["slots"] * w["window"])


def decode_step_bytes(config: dict, cached_tokens: int,
                      itemsize: int = 2) -> float:
    """Bytes one decode step has to read: every dense weight and the head
    once, every cached token's rows in the full layers, the last ``window``
    rows a live sequence in the window layers.  The experts a step's tokens
    hit are left out (shapes do not say which)."""
    w = widths(config)
    rows = (w["full_layers"] * int(cached_tokens)
            + w["window_layers"] * window_rows(config, cached_tokens))
    return (itemsize * (dense_params(config) + head_params(config))
            + float(cache_bytes_per_token(config, itemsize)) * rows)


def decode_step_flops(config: dict, active: int, cached_tokens: int) -> float:
    """A query reads a cached row as one ``head_dim`` key and one
    ``head_dim`` value for every query head."""
    w = widths(config)
    per_row = 2.0 * w["heads"] * 2 * w["head_dim"]
    rows = (w["full_layers"] * int(cached_tokens)
            + w["window_layers"] * window_rows(config, cached_tokens))
    return 2.0 * token_matmul_params(config) * active + per_row * rows


def train_flops_per_token(config: dict, seq: int) -> float:
    """Forward + backward: 6 per matmul weight a token meets, plus causal
    attention (scores and values over ``head_dim``, half of ``seq`` under
    the mask in a full layer, ``window`` in a window layer, times 3)."""
    w = widths(config)
    per_key = 3.0 * w["heads"] * 2 * w["head_dim"]
    keys = (w["full_layers"] * seq
            + w["window_layers"] * 2 * min(w["window"], seq))
    return 6.0 * token_matmul_params(config) + per_key * keys


def attention_call_shape(config: dict, run_values: dict) -> tuple:
    w = widths(config)
    return (run_values["batch"], w["heads"], run_values["seq"],
            w["head_dim"])
