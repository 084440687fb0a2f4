"""Matmul-family ops — the MXU workhorses.

Reference: python/hetu/gpu_ops/{MatrixMult,Linear,BatchMatrixMult,Addmm,
Baddbmm,MatrixDot}.py dispatching to cuBLAS (src/ops/MatrixMult.cu).

TPU notes: all of these lower to dot_general, which XLA tiles onto the
128x128 MXU.  We default accumulation to float32 (preferred_element_type)
so bfloat16 inputs keep full-precision accumulation — the TPU-native analog
of cuBLAS's default compute type.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def _acc_dtype(a, b):
    # bf16 x bf16 accumulates in f32 on the MXU — the TPU-native analog of
    # cuBLAS's fp32 compute type for fp16/bf16 GEMMs.
    if a.dtype == jnp.bfloat16 or b.dtype == jnp.bfloat16:
        return jnp.float32
    return None


def _as_operands(y, a, b):
    """A product's float32 accumulation back in its operands' promoted
    dtype."""
    out = jnp.promote_types(a.dtype, b.dtype)
    return y.astype(out) if y.dtype != out else y


def _mm(a, b):
    """Matmul with f32 MXU accumulation, result cast back to the inputs'
    promoted dtype — a bf16 network stays bf16 (half the HBM traffic on every
    activation) while each dot still accumulates in full precision."""
    return _as_operands(
        jnp.matmul(a, b, preferred_element_type=_acc_dtype(a, b)), a, b)


def matmul(a, b, trans_a: bool = False, trans_b: bool = False):
    """2-D matmul with transpose flags (gpu_ops/MatrixMult.py matmul_op)."""
    if trans_a:
        a = a.T
    if trans_b:
        b = b.T
    return _mm(a, b)


def linear(x, w, bias=None, trans_w: bool = False):
    """x @ w (+ bias) — gpu_ops/Linear.py."""
    if trans_w:
        w = w.T
    y = _mm(x, w)
    if bias is not None:
        y = y + bias
    return y


def linear_minor(x, w, bias=None):
    """``x`` [..., K] contracted with the MINOR axis of ``w`` [*out, K] (+
    ``bias`` [*out]) -> [..., *out]: :func:`linear` over a weight stored
    with its contracting axis last and its output axis split as the caller
    reads the result (heads, width), in one product with no transpose and no
    reshape of the weight between its storage and the product."""
    y = _as_operands(lax.dot_general(
        x, w, (((x.ndim - 1,), (w.ndim - 1,)), ((), ())),
        preferred_element_type=_acc_dtype(x, w)), x, w)
    if bias is not None:
        y = y + bias
    return y


def batch_matmul(a, b, trans_a: bool = False, trans_b: bool = False):
    """Batched matmul (gpu_ops/BatchMatrixMult.py)."""
    if trans_a:
        a = jnp.swapaxes(a, -1, -2)
    if trans_b:
        b = jnp.swapaxes(b, -1, -2)
    return _mm(a, b)


def addmm(input_, a, b, alpha: float = 1.0, beta: float = 1.0):
    """beta*input + alpha*(a @ b) — gpu_ops/Addmm.py."""
    return beta * input_ + alpha * jnp.matmul(a, b)


def baddbmm(input_, a, b, alpha: float = 1.0, beta: float = 1.0):
    """Batched addmm — gpu_ops/Baddbmm.py."""
    return beta * input_ + alpha * jnp.matmul(a, b)


def matrix_dot(a, b):
    """Elementwise product then row-sum (gpu_ops/MatrixDot.py)."""
    return jnp.sum(a * b, axis=-1)
