"""Operations and bytes of a kernel call, from its shapes alone: what no
architecture owns.  What depends on an architecture's widths (operations a
trained token requires, bytes a decode step reads, the shape of one
attention call) is its adapter's, under ``benchmarks/arch/``.

Kept with the benchmark so that no PR that claims a gain can change how a
utilization is counted.  "Needs" means the published mathematics:
masked-out attention tiles are the program's choice and count for nothing
here, so a share of a peak computed from these can only be UNDER-stated.
"""

from __future__ import annotations


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
