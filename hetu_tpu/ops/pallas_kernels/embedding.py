"""Pallas TPU kernels for the embedding hot path and MoE gating.

Reference kernels being replaced: src/ops/EmbeddingLookUp.cu (gather with
bounds check), its scatter-add gradient kernel, and gpu_ops/TopKIdx.py's
CUDA top-k (src/ops/TopKIdx.cu).

Why Pallas here: XLA lowers `jnp.take` over a huge vocab table to a gather
that reads whole table tiles; with scalar-prefetched row ids the DMA engine
streams only the 8-row tile groups holding requested rows HBM->VMEM while
the previous group is copied out — the Pallas sparse-gather pattern.  The
scatter-add gradient exploits the TPU grid's sequential execution: sorted
unique ids visit each output group in one run of steps, no atomics (which
TPU lacks).  The top-k gate fuses k argmax passes + softmax into one
VMEM-resident kernel, avoiding XLA's full sort for small k over the
experts axis.

All kernels run in interpret mode on CPU for tests and compiled on a TPU.
Row width D should be a multiple of 128 (lane width) for peak DMA
efficiency — other widths work but pad internally.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hetu_tpu.utils.platform import (
    auto_interpret as _auto_interpret,
    default_backend_is_tpu as _default_backend_is_tpu,
)


# ---------------------------------------------------------------- gather
#
# Mosaic only moves blocks whose trailing dims are whole (sublane, 128) tiles
# (or the whole array), and refuses a one-row DMA out of a tiled 2-D table.
# So both kernels move the aligned GROUP of ``_sublanes(dtype)`` rows that
# holds the wanted row and pick the row inside VMEM with a sublane mask
# (a dynamic one-row load only compiles for 4-byte dtypes; the mask compiles
# for every dtype and costs a few vregs next to the group's DMA).  Reshaping
# the table to [V, 1, D] would make one-row blocks legal too, but XLA then
# re-tiles the WHOLE table on every call.

def _sublanes(dtype) -> int:
    """Rows in one TPU tile of ``dtype``: 8 x the sublane packing."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def _pick_row(block, row):
    """Row ``row`` (traced scalar) of a [G, D] VMEM block as [1, D] f32."""
    rows = jax.lax.broadcasted_iota(jnp.int32, block.shape, 0)
    return jnp.sum(jnp.where(rows == row, block, 0).astype(jnp.float32),
                   axis=0, keepdims=True)


def _gather_kernel(ids_ref, *refs, group: int):
    table_refs, out_ref = refs[:group], refs[group]
    base = pl.program_id(0) * group
    for r in range(group):  # one prefetched table group per output row
        row = _pick_row(table_refs[r][...], ids_ref[base + r] % group)
        out_ref[pl.ds(r, 1), :] = row.astype(out_ref.dtype)


def embedding_gather(table, ids, *, interpret=None):
    """table [V, D], ids [N] int32 -> [N, D]; out-of-range ids give zero
    rows (EmbeddingLookUp.cu bounds-check semantics).

    One grid step per group of G = ``_sublanes(dtype)`` ids; the table is
    passed G times and each copy's index_map reads one scalar-prefetched id,
    so G row-group DMAs are in flight per step and only the groups holding
    requested rows are read.
    """
    interpret = _auto_interpret(interpret)
    V, D = table.shape
    G = _sublanes(table.dtype)
    ids = ids.astype(jnp.int32)
    (N,) = ids.shape
    n_pad = -(-N // G) * G
    # clamp for the DMA (invalid ids fetch row 0; masked AFTER the kernel
    # with the true ids)
    safe = jnp.pad(jnp.clip(ids, 0, V - 1), (0, n_pad - N))

    def table_map(r):
        return lambda i, ids_ref: (ids_ref[i * G + r] // G, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_pad // G,),
        in_specs=[pl.BlockSpec((G, D), table_map(r)) for r in range(G)],
        out_specs=pl.BlockSpec((G, D), lambda i, ids_ref: (i, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_gather_kernel, group=G), grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_pad, D), table.dtype),
        interpret=interpret,
    )(safe, *([table] * G))
    valid = (ids >= 0) & (ids < V)
    return jnp.where(valid[:, None], out[:N], 0)


# ------------------------------------------------------------ scatter-add

def _scatter_kernel(ids_ref, rows_ref, acc_ref, out_ref, *, group: int):
    del acc_ref  # only aliased into the output: untouched groups stay zero
    i = pl.program_id(0)
    rid = ids_ref[i]
    prev = ids_ref[jnp.maximum(i - 1, 0)]

    # ids arrive sorted, so each output group is one run of consecutive
    # steps during which its block stays resident; zero it on entry
    @pl.when((i == 0) | (rid // group != prev // group))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    row = _pick_row(rows_ref[...], i % group).astype(out_ref.dtype)
    rows = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 0)
    out_ref[...] = jnp.where(rows == rid % group, row, out_ref[...])


def embedding_scatter_add(grads, ids, num_rows: int, *, interpret=None):
    """grads [N, D], ids [N] -> dense table-grad [num_rows, D].

    The gradient of embedding_gather.  Duplicates are pre-summed with an
    XLA segment-sum over the SORTED ids (cheap: N log N on tiny int rows),
    so the kernel writes each unique row exactly once, in row order: every
    G-row output group is visited in one run of consecutive grid steps and
    written back once.  The zeros accumulator aliases the output buffer, so
    untouched groups are zero without an extra HBM pass."""
    interpret = _auto_interpret(interpret)
    N, D = grads.shape
    G = _sublanes(grads.dtype)
    ids = ids.astype(jnp.int32)
    order = jnp.argsort(ids)
    sids = ids[order]
    sgrads = grads[order]
    # segment-sum consecutive duplicates: segment j = rank of unique id
    new_seg = jnp.concatenate([jnp.ones((1,), jnp.int32),
                               (sids[1:] != sids[:-1]).astype(jnp.int32)])
    seg = jnp.cumsum(new_seg) - 1                      # [N], 0..U-1
    summed = jax.ops.segment_sum(sgrads, seg, num_segments=N)
    uids = jnp.full((N,), -1, jnp.int32).at[seg].set(sids)

    # invalid slots (duplicate padding, out-of-range ids) route to a
    # SENTINEL group past the last real one, sliced off below — it may be
    # entered twice (negatives sort first, padding last) and re-zeroed, so
    # it must share no group with a real row; out-of-range grads are
    # dropped like the XLA oracle's
    valid = (uids >= 0) & (uids < num_rows)
    sentinel = -(-num_rows // G) * G
    safe = jnp.where(valid, uids, sentinel).astype(jnp.int32)
    acc = jnp.zeros((sentinel + G, D), grads.dtype)
    summed = jnp.pad(summed, ((0, -N % G), (0, 0)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(N,),
        in_specs=[
            pl.BlockSpec((G, D), lambda i, ids_ref: (i // G, 0)),      # rows
            pl.BlockSpec(memory_space=pl.ANY),                         # acc
        ],
        out_specs=pl.BlockSpec((G, D),
                               lambda i, ids_ref: (ids_ref[i] // G, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_scatter_kernel, group=G), grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(acc.shape, grads.dtype),
        input_output_aliases={2: 0},  # acc -> out: zero-init untouched rows
        interpret=interpret,
    )(safe, summed, acc)
    return out[:num_rows]


# ------------------------------------------------------- routed gather op

@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _routed_gather(table, ids, kernel):
    if kernel:
        return embedding_gather(table, ids)
    valid = (ids >= 0) & (ids < table.shape[0])
    rows = jnp.take(table, jnp.clip(ids, 0, table.shape[0] - 1), axis=0)
    return jnp.where(valid[:, None], rows, 0)


def _routed_gather_fwd(table, ids, kernel):
    return _routed_gather(table, ids, kernel), (ids, table.shape[0])


def _routed_gather_bwd(kernel, res, g):
    ids, num_rows = res
    if kernel:
        return embedding_scatter_add(g, ids, num_rows), None
    valid = (ids >= 0) & (ids < num_rows)
    g = jnp.where(valid[:, None], g, 0)
    dt = jnp.zeros((num_rows, g.shape[-1]), g.dtype).at[
        jnp.clip(ids, 0, num_rows - 1)].add(g)
    return dt, None


_routed_gather.defvjp(_routed_gather_fwd, _routed_gather_bwd)


def _auto_kernel(kernel) -> bool:
    """``kernel=None`` picks the Pallas kernels on a TPU backend and the
    equivalent XLA ops elsewhere (interpret-mode Pallas is orders of
    magnitude slower than XLA on CPU)."""
    return _default_backend_is_tpu() if kernel is None else bool(kernel)


def routed_gather(table, ids, *, kernel=None):
    """Differentiable row gather with -1/out-of-range → zero-row semantics.

    The gather/scatter-add kernels above bound into one autodiff op:
    forward pulls ``table[ids]`` (invalid ids give zero rows), backward
    scatter-adds the cotangent rows back (duplicates accumulate, invalid
    ids drop) — the vjp-transpose contract ``test_scatter_is_gather_
    transpose`` pins.  ``kernel=True`` runs both directions through the
    Pallas kernels (compiled on TPU, interpret mode on CPU),
    ``kernel=False`` through the equivalent XLA gather/scatter — the form
    GSPMD can partition — and ``None`` picks by backend.  This is the
    building block the MoE gather-dispatch and device-resident embedding
    layers route through.
    """
    ids = ids.astype(jnp.int32)
    return _routed_gather(table, ids, _auto_kernel(kernel))


# ---------------------------------------------------------------- top-k

def _topk_kernel(logits_ref, vals_ref, idx_ref, *, k: int, experts: int):
    x = logits_ref[...].astype(jnp.float32)        # [bt, E]
    bt = x.shape[0]
    iota = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    for j in range(k):                             # k small, unrolled
        m = jnp.max(x, axis=-1)                    # [bt]
        # first position attaining the max
        hit = x == m[:, None]
        pos = jnp.min(jnp.where(hit, iota, experts), axis=-1)
        vals_ref[:, j] = m
        idx_ref[:, j] = pos
        x = jnp.where(iota == pos[:, None], -jnp.inf, x)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _topk_gating(logits, k, block_tokens, kernel):
    return _topk_gating_impl(logits, k, block_tokens, kernel)


def _topk_gating_fwd(logits, k, block_tokens, kernel):
    gates, idx = _topk_gating_impl(logits, k, block_tokens, kernel)
    return (gates, idx), (gates, idx, logits.shape[1])


def _topk_gating_bwd(k, block_tokens, kernel, res, ct):
    """softmax-over-the-chosen-k vjp, scattered back into [T, E]: the same
    gradient lax.top_k + softmax would produce (idx is non-differentiable,
    selection is piecewise-constant)."""
    gates, idx, E = res
    g_gates = ct[0]
    inner = jnp.sum(g_gates * gates, axis=-1, keepdims=True)
    dvals = (gates * (g_gates - inner)).astype(gates.dtype)
    T = gates.shape[0]
    dlogits = jnp.zeros((T, E), dvals.dtype).at[
        jnp.arange(T)[:, None], idx].add(dvals)
    return (dlogits,)


_topk_gating.defvjp(_topk_gating_fwd, _topk_gating_bwd)


def topk_gating(logits, k: int, *, block_tokens: int = 256, kernel=None):
    """logits [T, E] -> (gates [T, k] softmaxed over the k, idx [T, k]).

    The MoE gate's top-k + softmax fused in VMEM (TopKIdx.cu analog):
    k repeated max/mask passes beat a full sort for the k << E regime.
    Matches ops.top_k_idx_gate (ties resolved to the lowest index,
    lax.top_k's order) — including its gradient, via a custom vjp.

    ``kernel``: True runs the Pallas kernel (compiled on TPU, interpret
    mode on CPU — the tests' path, so the kernel body keeps CPU coverage),
    False the equivalent ``lax.top_k`` + softmax, None picks by backend
    like :func:`routed_gather`.
    """
    return _topk_gating(logits, int(k), int(min(block_tokens,
                                                logits.shape[0])),
                        _auto_kernel(kernel))


def _topk_gating_impl(logits, k, block_tokens, kernel):
    T, E = logits.shape
    if k > E:
        raise ValueError(f"top-{k} of only {E} experts (lax.top_k would "
                         "reject this too)")
    bt = min(block_tokens, T)
    if T % bt:
        # validated on both paths so callers see the same contract whether
        # or not the kernel runs
        raise ValueError(f"tokens {T} not divisible by block {bt}")
    if not kernel:
        vals, idx = jax.lax.top_k(logits, k)
        # f32 softmax like the kernel path (which accumulates f32 vals),
        # so CPU-validated gate values match TPU bit-for-bit policy
        return (jax.nn.softmax(vals.astype(jnp.float32), axis=-1)
                .astype(logits.dtype), idx)
    vals, idx = pl.pallas_call(
        functools.partial(_topk_kernel, k=k, experts=E),
        grid=(T // bt,),
        in_specs=[pl.BlockSpec((bt, E), lambda i: (i, 0))],
        out_specs=(pl.BlockSpec((bt, k), lambda i: (i, 0)),
                   pl.BlockSpec((bt, k), lambda i: (i, 0))),
        out_shape=(jax.ShapeDtypeStruct((T, k), jnp.float32),
                   jax.ShapeDtypeStruct((T, k), jnp.int32)),
        interpret=_auto_interpret(None),
    )(logits)
    gates = jax.nn.softmax(vals, axis=-1).astype(logits.dtype)
    return gates, idx
