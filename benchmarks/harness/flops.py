"""Operations and bytes the algorithm needs, from shapes alone.

Kept with the benchmark so that no PR that claims a gain can change how a
utilization is counted.  "Needs" means the published mathematics: recomputed
layers (remat), padded vocabulary rows, masked-out attention tiles and
pool-sized copies are the program's choices and count for nothing here, so
a share of a peak computed from these can only be UNDER-stated by them.

``bench.py``'s ``_gpt_flops_per_token`` is the origin of ``train_flops_per
_token``; it counts attention unmasked (12*L*H*S), this one counts the
causal half (6*L*H*S), which is what a causal model requires.
"""

from __future__ import annotations


def widths(config: dict) -> dict:
    h = int(config["n_embd"])
    ffn = config.get("n_inner") or config["assumed"]["n_inner_value"]
    return {"hidden": h, "layers": int(config["n_layer"]),
            "heads": int(config["n_head"]), "ffn": int(ffn),
            "head_dim": h // int(config["n_head"]),
            "vocab": int(config["vocab_size"]),
            "positions": int(config["n_positions"])}


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


def flash_call_flops(batch: int, heads: int, seq: int, head_dim: int) -> dict:
    """Operations of one causal flash-attention call over [batch, heads,
    seq, head_dim]: forward QK^T and PV (4*S^2*D unmasked); backward five
    matmuls (S recomputed, dV, dP, dQ, dK: 10*S^2*D); half under the
    mask."""
    unit = batch * heads * seq * seq * head_dim
    return {"fwd": 2.0 * unit, "bwd": 5.0 * unit}


def flash_call_bytes(batch: int, heads: int, seq: int, head_dim: int,
                     itemsize: int = 2) -> dict:
    """Bytes one call has to move: forward reads Q, K, V and writes O;
    backward reads Q, K, V, O, dO and writes dQ, dK, dV."""
    unit = batch * heads * seq * head_dim * itemsize
    return {"fwd": 4.0 * unit, "bwd": 8.0 * unit}


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
