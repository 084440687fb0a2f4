from hetu_tpu.ops.pallas_kernels.flash_attention import (
    flash_attention, flash_chunk_attention, flash_sparse_chunk_attention,
)
from hetu_tpu.ops.pallas_kernels.embedding import (
    embedding_gather, embedding_scatter_add, topk_gating, routed_gather,
)
