"""Functional op library.

The reference implements 143 op classes (python/hetu/gpu_ops/*.py, SURVEY.md §2.1)
each dispatching to a hand-written CUDA kernel (src/ops/*.cu).  On TPU the op zoo
collapses into jnp/lax compositions that XLA fuses and tiles onto the MXU; only
hot fusions (attention, embedding gather/scatter, top-k gating) get Pallas
kernels (hetu_tpu/ops/pallas_kernels/).

Every public name here corresponds to an op class in the reference inventory so
capability parity is line-checkable.
"""

from hetu_tpu.ops.elementwise import (
    abs_, add, add_const, minus, minus_const, const_minus, multiply, mul_const,
    divide, div_const, const_div, opposite, exp, log, pow_, const_pow, power,
    sqrt, rsqrt, sin, cos, floor, ceil, clamp, sign, bool_, where, masked_fill,
    mask,
)
from hetu_tpu.ops.matmul import (
    matmul, linear, linear_minor, batch_matmul, addmm, baddbmm, matrix_dot,
)
from hetu_tpu.ops.conv import (
    conv2d, conv2d_add_bias, max_pool2d, avg_pool2d,
)
from hetu_tpu.ops.norm import (
    batch_norm, layer_norm, instance_norm2d, rms_norm,
)
from hetu_tpu.ops.rope import (
    apply_rope, apply_rope_at, apply_rope_interleaved, rope_tables,
)
from hetu_tpu.ops.activations import (
    relu, leaky_relu, gelu, sigmoid, tanh, softmax, log_softmax, silu,
)
from hetu_tpu.ops.losses import (
    binary_cross_entropy, binary_cross_entropy_with_logits,
    cross_entropy, cross_entropy_sparse,
    softmax_cross_entropy, softmax_cross_entropy_sparse, nll_loss,
    lm_head_cross_entropy,
)
from hetu_tpu.ops.shape import (
    reshape, transpose, concat, concatenate, split, slice_, slice_assign,
    slice_by_matrix, pad, tile, repeat, roll, broadcast_shape, broadcast_to,
    gather, gather_elements, scatter, scatter1d, indexing, one_hot, arange,
    full, full_like, ones_like, zeros_like, cumsum, interpolate, flip,
    tril_lookup, triu, tril,
)
from hetu_tpu.ops.reduce import (
    reduce_sum, reduce_mean, reduce_min, reduce_max, reduce_mul, reduce_norm1,
    reduce_norm2, reduce_sum_axis_zero, norm, max_, min_, argmax, argsort,
    topk_idx, topk_val, topk, unique, sam_group_sum, sam_max,
)
from hetu_tpu.ops.dropout import dropout
from hetu_tpu.ops.embedding import (
    embedding_lookup, sparse_embedding_lookup, IndexedSlices,
    sum_sparse_gradient, assign_with_indexed_slices, take_grad_indexed,
)
from hetu_tpu.ops.quantize import (
    quantize, dequantize, quantize_embedding_lookup, prune_low_magnitude,
    param_clip,
)
from hetu_tpu.ops.moe_ops import (
    top_k_idx_gate, layout_transform, reverse_layout_transform,
    balance_assignment, make_slot_routing, gather_dispatch, gather_combine,
)
from hetu_tpu.ops.attention import (
    INDEX_ROW_TILE,
    attention, cache_update, causal_attention, chosen_mask,
    chosen_pages_attention, chosen_rows, chosen_rows_attention,
    chunk_attention, chunk_kernel_why, chunk_plan, compress_keys,
    decode_attention, decode_layer_attention, index_kernel_why, index_plan,
    masked_block_attention, pool_index_keys, read_cache_layer, remat,
    ring_update, scan_cached_layers, scan_layers_over_caches, select_blocks,
    select_groups, sparse_kernel_why, write_cache_layer,
)
from hetu_tpu.ops.graph_ops import (
    coo_spmm, gcn_norm, gcn_conv,
)
from hetu_tpu.ops.pallas_kernels import (
    flash_attention as pallas_flash_attention,
    embedding_gather as pallas_embedding_gather,
    embedding_scatter_add as pallas_embedding_scatter_add,
    topk_gating as pallas_topk_gating,
    routed_gather as pallas_routed_gather,
)
