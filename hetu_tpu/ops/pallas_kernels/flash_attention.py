"""Flash attention forward + fused backward kernels (Pallas/TPU).

The reference has no fused attention (its MHA composes batch_matmul +
softmax ops, layers/attention.py); on TPU the fusion matters because the
[S, S] score matrix otherwise round-trips HBM.

One algorithm — online softmax over tiles, forward, and the FlashAttention-2
two-kernel backward — and two ways of feeding it, chosen from the operand
shapes (:func:`_resident`), never by a caller (a CHUNK call, further down,
is the streamed forward with a traced diagonal and a traced walk length):

  * **resident** (a row's operands fit VMEM; every shape up to S of a few
    thousand): one program owns a whole ROW of tiles.  Forward and dQ run on
    grid (batch*heads, q_blocks): the program holds its Q block and the whole
    K and V of its (batch, head), and walks the K/V blocks 0 .. last live one
    in a ``lax.fori_loop`` whose bound comes from the block's position, so a
    tile above the causal diagonal is never fetched nor stepped over.  dK/dV
    runs on grid (batch*heads, k_blocks) with Q, dO, LSE and delta resident,
    walking the Q blocks from the first live one.  The softmax state and
    dq^T are loop values; dk and dv accumulate in 2-D VMEM scratch.
  * **streamed** (S of tens of thousands): grid (batch*heads, q_blocks,
    walked steps) with the walked axis innermost, the same tile bodies, the
    state in 2-D VMEM scratch across the steps, and index maps clamped to the
    last (first) live block so that a dead step re-names the block already in
    VMEM and fetches nothing.  Without a window the walked axis is the whole
    row, k_blocks; with one it is as long as the LONGEST live walk of any
    block (``_Walk.steps``) and step j stands at the walk's first block + j
    (``_Walk.at``).  VMEM use is O(block_q * D + block_k * D) whatever S.

A tile is held TRANSPOSED, [block_k, block_q]: keys down the sublanes,
queries along the lanes.  The row statistics of the softmax (m, l, and the
LSE and delta the backward reads) are then lane-dense [1, block_q] rows, the
max and the sum over keys are elementwise across vregs instead of cross-lane
reductions, acc^T [D, block_q] fills whole vregs at D=64, and neither
backward kernel transposes a probability tile (dv += p^T dO and dk += ds^T q
are plain matmuls of the transposed tiles).  The only transposes left are of
the [D, block_q] results, once per Q block.

Either way the walk is split at the diagonal: tiles wholly below it run a
body with no iota, compare or select; only the tiles the diagonal crosses
build a mask.  ``scale`` is folded into the Q block once where that is exact
(a power of two, as 1/8 for D=64) and stays on the f32 scores otherwise.
Dots run in the input dtype with f32 accumulation; softmax statistics are
f32.  The forward also emits the log-sum-exp rows (LSE, [batch*heads,
q_blocks, 1, block_q]), from which the backward recomputes probabilities
tile by tile:

  * delta = rowsum(dO * O)                       (one cheap XLA reduction)
  * dK/dV kernel:  p = exp(q k^T * scale - lse);  dv += p^T dO;
        ds = p * (dO v^T - delta) * scale;  dk += ds^T q
  * dQ kernel:     dq += ds k.

No O(S^2) tensor ever touches HBM in either direction — this beats the
reference's training memory profile (its attention materializes scores for
the backward), and it is what makes S >= 8k practical on one chip.

Q and K share one width ``d_qk`` and V, O and dO another, ``d_v`` (latent
attention trains at 192 | 128; everywhere else the two are equal): a score
tile contracts over ``d_qk``, acc^T and dv are ``d_v`` wide, dq and dk
``d_qk``.  Nothing is padded to the wider of the two.

Causal masking is BOTTOM-RIGHT aligned (query i attends to keys
<= i + (S_k - S_q)), matching ops.causal_attention, so cross-length
(prefix/KV-cache) calls agree with the oracle in both directions — except
query rows whose mask hides EVERY key (only possible when s_q > s_k):
there the kernel returns 0 output and 0 gradients, whereas the XLA
composition softmaxes the uniform -1e30 scores into garbage averages.
Zero is the deliberate semantics for an all-masked row.

**A window** (``window=W``, causal only): a query sees itself and the
``W - 1`` keys before it, aligned as the diagonal is (the rule of
``ops.causal_attention``).  The walk then has a LOWER edge as well: a Q
block's K blocks are up to three spans, the blocks the window's lower edge
crosses (masked), the blocks wholly inside (no iota, compare or select) and
the blocks the diagonal crosses (masked; a block both edges cross is masked
once, with both), and a K block's Q blocks mirror it and END at the last
query block that still sees the K block.  Blocks outside every span are
neither fetched nor stepped over, resident or streamed: a streamed call's
walked grid axis starts at each walk's first live block and is as long as
the longest walk, so the only dead steps left are at the end of a walk
shorter than that (the first rows of blocks, and what ``offset`` cuts), and
there the index maps clamp to the last live block.  At S = 16,384, W = 1,024
and 512 x 512 tiles a Q block walks 3 K blocks where a causal one walks 16.5
on average, and the grid of a kernel is 32 x 32 x 3 steps for 2,976 tiles
(``flash.plan``'s ``steps`` beside ``tiles_live``), not 32 x 32 x 32.

**Grouped heads**: K and V may come with ``heads / g`` heads.  The program of
query head ``h`` reads K and V of head ``h // g`` through its index map, and
the dK/dV program that owns a K block of one KV head accumulates over the
``g`` query heads of its group before it writes (resident: the group's Q,
dO and statistics are one block and the heads a loop; streamed: the walked
axes are heads x Q blocks), so dK and dV leave at ``heads / g`` heads.
Neither a repeated copy of K or V nor a ``[B, heads, S, D]`` dK or dV ever
exists in HBM.  With ``g = 1`` and no window every call traces to the
kernels it traced to before either existed, and every call without a window
to the kernels it traced to before the walked axis followed the spans.

**A chunk call** (:func:`flash_chunk_attention`; serving's chunked prefill,
``ops.chunk_attention`` and ``LatentAttention.expanded``): ``S_c`` queries of
each sequence against a longer history of ``T`` keys, FORWARD only.  The
diagonal is not where the lengths put it but where the sequence stands:
query ``i`` sits at position ``starts[b] + i``, ``starts`` [B] traced, handed
to the kernel and its index maps by scalar prefetch, and ``_Walk``'s span
arithmetic, which already runs on traced block indices, reads it where a
training call reads ``s_k - s_q`` (:meth:`_Walk.from_start`).  It is always
the streamed forward, the same tile bodies: the walked axis of the grid is a
TRACED bound, the K blocks up to the one the call's longest history ends in,
so a chunk's cost follows its history and not the width of the table it was
handed; a sequence of the call with a shorter history ends in dead steps
clamped as above, and keys past its chunk (unwritten rows, a page's stale
occupant, padding) are masked by the diagonal.  No LSE leaves and there is
no backward: what trains has static lengths and calls
:func:`flash_attention`.

**A sparse chunk call** (:func:`flash_sparse_chunk_attention`; a
block-sparse layer's chunk, ``ops.masked_block_attention``): the chunk call
with the queries' BLOCK CHOICE as one more operand, a word of bits a (K tile,
query, KV head) laid as the statistics' rows are.  Same grid, walk and tile
body; every tile is masked, by the choice and on the diagonal by both, and a
masked probability is zeroed (a row may see nothing in a tile, the first
included).  The dense calls do not know it exists: they lower to the text
they lowered to.

Interpret mode runs the same kernels on CPU for correctness tests.
"""

from __future__ import annotations

import copy
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import AxisType, PartitionSpec as P

from hetu_tpu.parallel.mesh import AXIS_DP, AXIS_TP
from hetu_tpu.telemetry import trace
from hetu_tpu.utils.platform import auto_interpret

NEG_INF = -1e30

# The names (``jax.ad_checkpoint.checkpoint_name``) of the forward kernel's
# two results, which are all its backward needs beside q, k and v.  A
# ``jax.checkpoint`` whose policy saves them (``ops.remat``) keeps both, and
# the recomputed layer then holds no forward kernel.
SAVED_OUT = "hetu.flash.out"
SAVED_LSE = "hetu.flash.lse"

# What one row's resident operands may take of VMEM, as the pipeline holds
# them (two buffers each, padded to whole (8, 128) tiles).  With the
# per-step blocks and a few [block_k, block_q] f32 temporaries on top this
# stays well under the 16 MiB a v5e kernel is lent by default.
_RESIDENT_VMEM_BYTES = 6 * 2 ** 20

_NT = (((1,), (1,)), ((), ()))   # a b^T
_NN = (((1,), (0,)), ((), ()))   # a b
_TN = (((0,), (0,)), ((), ()))   # a^T b


def _dot(a, b, dims):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


# ------------------------------------------------------------ the tile walk

def _clip(x, lo, hi):
    """``min(max(x, lo), hi)`` of block indices: traced in a kernel or an
    index map, plain integers where a call's tiles are counted."""
    if all(isinstance(a, int) for a in (x, lo, hi)):
        return max(lo, min(x, hi))
    return jnp.clip(x, lo, hi)


class _Walk:
    """Static facts of one call that every kernel body shares: tile sizes,
    block counts, the causal offset, the window, how many query heads read
    one KV head, and how ``scale`` is applied; of a SPARSE chunk call also
    ``block``, the positions a block of its queries' choice spans."""

    def __init__(self, *, s_q, s_k, block_q, block_k, scale, causal,
                 window=None, group=1, block=None):
        self.bq, self.bk = block_q, block_k
        self.block = block
        self.n_q, self.n_k = s_q // block_q, s_k // block_k
        self.causal = causal
        self.offset = s_k - s_q
        # a window as long as the keys hides nothing the diagonal leaves
        self.window = None if window is None or window >= s_k else window
        self.g = group
        self.scale = scale
        # q * 2**n is exact in any float dtype: fold the scale into the Q
        # block once instead of multiplying every f32 score tile
        self.fold = math.frexp(scale)[0] == 0.5
        # a row that sees no key at all exists only when s_q > s_k (under a
        # window too: a query whose position is a key's sees that key).  A
        # row may still see nothing in the FIRST tiles of its walk, the ones
        # the window's lower edge crosses; what the forward then sums is
        # wiped by the correction of the first tile that holds a real score
        # (exp(-1e30 - m) is 0), and the diagonal tile always does.  Under a
        # CHOICE a row may see nothing in any tile, the first one included
        # (no block is forced here), and nothing wipes what it summed.
        self.guard = (causal and self.offset < 0) or block is not None

    def from_start(self, start):
        """This walk with the diagonal where a CHUNK's puts it: query row 0
        sits at key position ``start`` (traced: a sequence's entry of the
        prefetched ``starts``), not at ``s_k - s_q``.  ``start >= 0``, so
        every row sees key 0 and the diagonal asks for no guard."""
        w = copy.copy(self)
        w.offset = start
        return w

    def q_block(self, q):
        return q * jnp.asarray(self.scale, q.dtype) if self.fold else q

    def scores(self, q, k):
        """The transposed score tile k q^T * scale, [block_k, block_q]."""
        s = _dot(k, q, _NT)
        return s if self.fold else s * self.scale

    def ds(self, p, dp, delta):
        """d(scores) less the folded scale (the caller's accumulator, or its
        pre-scaled Q, carries it then)."""
        ds = p * (dp - delta)
        return ds if self.fold else ds * self.scale

    def mask(self, qi, ki):
        """[block_k, block_q]: key ki*bk + r is visible to query qi*bq + c:
        not after it and, under a window, fewer than ``window`` before."""
        rel = lax.broadcasted_iota(jnp.int32, (self.bk, self.bq), 1) - \
            lax.broadcasted_iota(jnp.int32, (self.bk, self.bq), 0)
        edge = ki * self.bk - qi * self.bq - self.offset
        if self.window is None:
            return rel >= edge
        return (rel >= edge) & (rel < edge + self.window)

    def chose(self, words):
        """[block_k, block_q]: query c chose the block of ``block`` positions
        that key row r of this tile lies in: bit ``r // block`` of its word,
        ``words`` [1, block_q] int32 (:func:`_choice_words`).  A block's rows
        share a bit, so the tile is ``block_k / block`` rows of bits, each
        spread down its block's sublanes: no per-element shift."""
        bits = [jnp.broadcast_to((words >> r) & 1, (self.block, self.bq))
                for r in range(self.bk // self.block)]
        return jnp.concatenate(bits, axis=0) != 0

    # A walk is a few (lo, hi, masked) spans of block indices, in the order
    # they are visited; blocks outside every span are dead (above the
    # diagonal, or wholly behind the window) and are neither fetched nor
    # stepped over.  ``qi`` / ``ki`` traced or a plain integer (``tiles``).

    def k_spans(self, qi):
        """The K blocks Q block qi walks: those the window's lower edge
        crosses (none without a window), those wholly visible, unmasked,
        then the ones the diagonal crosses."""
        if not self.causal:
            return ((0, self.n_k, False),)
        q_first = qi * self.bq + self.offset     # last key row 0 sees
        q_last = q_first + self.bq - 1           # last key any row sees
        n_live = _clip((q_last + self.bk) // self.bk, 0, self.n_k)
        n_full = _clip((q_first + 1) // self.bk, 0, n_live)
        if self.window is None:
            return (0, n_full, False), (n_full, n_live, True)
        lo_first = q_first - self.window + 1     # first key row 0 sees
        lo_last = q_last - self.window + 1       # first key every row sees
        k_start = _clip(lo_first // self.bk, 0, n_live)
        k_in = _clip((lo_last + self.bk - 1) // self.bk, k_start, n_live)
        n_full = _clip(n_full, k_in, n_live)
        return ((k_start, k_in, True), (k_in, n_full, False),
                (n_full, n_live, True))

    def q_spans(self, ki):
        """The Q blocks K block ki walks: from the first live one, those the
        diagonal crosses, then the ones wholly visible, unmasked, and under
        a window the ones its lower edge crosses, the last that see the
        block."""
        if not self.causal:
            return ((0, self.n_q, False),)
        k_first = ki * self.bk - self.offset
        k_last = k_first + self.bk - 1
        q_start = _clip(k_first // self.bq, 0, self.n_q)
        if self.window is None:
            q_full = _clip((k_last + self.bq - 1) // self.bq, q_start,
                           self.n_q)
            return (q_start, q_full, True), (q_full, self.n_q, False)
        q_end = _clip((k_last + self.window - 1) // self.bq + 1, q_start,
                      self.n_q)
        q_full = _clip((k_last + self.bq - 1) // self.bq, q_start, q_end)
        q_in = _clip((k_first + self.window) // self.bq, q_full, q_end)
        return ((q_start, q_full, True), (q_full, q_in, False),
                (q_in, q_end, True))

    def tiles(self) -> int:
        """Tiles one head's walk visits, a kernel call (the forward's count;
        dQ's is the same walk and dK/dV's the same tiles by columns)."""
        return sum(hi - lo for qi in range(self.n_q)
                   for lo, hi, _ in self.k_spans(qi))

    # A streamed walk is an axis of the grid.  Under a window a walk is a
    # few blocks of a long row wherever its owner stands, so the axis is as
    # long as the LONGEST live walk and step j of it is the walk's first
    # block + j; without one it is the whole row and step j block j.

    def steps(self, own_q: bool) -> int:
        """Steps of the walked grid axis of a streamed call: the K blocks
        of a Q block's walk (``own_q``) or the Q blocks of a K block's."""
        if self.window is None:
            return self.n_k if own_q else self.n_q
        walks = map(self.k_spans, range(self.n_q)) if own_q \
            else map(self.q_spans, range(self.n_k))
        return max(spans[-1][1] - spans[0][0] for spans in walks)

    def at(self, spans, j):
        """The block that step ``j`` of a streamed walk over ``spans``
        stands at; past the walk's end under a window, a dead step."""
        return j if self.window is None else spans[0][0] + j


def _walk(spans, tile, carry=None):
    """A resident walk: ``carry = tile(i, masked, carry)`` over every span,
    as loops inside the program."""
    for lo, hi, masked in spans:
        carry = lax.fori_loop(
            lo, hi, lambda i, c, masked=masked: tile(i, masked, c), carry)
    return carry


def _step(spans, i, tile):
    """Grid step i of a streamed walk: ``tile(masked)`` if a span holds i."""
    for lo, hi, masked in spans:
        pl.when((i >= lo) & (i < hi))(functools.partial(tile, masked))


def _rows(ref, i, block, n, head=None):
    """Block i of a resident [S, D] operand (the whole of it when it is one
    block: a static read, which also keeps sub-tile lengths legal); of head
    ``head`` where the operand holds a group's heads, [g, S, D]."""
    if head is not None:
        if n == 1:
            return ref[head]
        return ref[head, pl.ds(pl.multiple_of(i * block, block), block), :]
    if n == 1:
        return ref[:]
    return ref[pl.ds(pl.multiple_of(i * block, block), block), :]


def _fwd_tile(w, q, k, v, m, l, acc, mask):
    s = w.scores(q, k)
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
    p = jnp.exp(s - m_new)
    if mask is not None and w.guard:
        p = jnp.where(mask, p, 0.0)     # a query with m_new still NEG_INF
    corr = jnp.exp(m - m_new)
    l = l * corr + jnp.sum(p, axis=0, keepdims=True)
    acc = acc * corr + _dot(v, p.astype(v.dtype), _TN)
    return m_new, l, acc


def _fwd_init(w, d):
    """m, l [1, block_q]; acc^T [D_v, block_q]."""
    return (jnp.full((1, w.bq), NEG_INF, jnp.float32),
            jnp.zeros((1, w.bq), jnp.float32),
            jnp.zeros((d, w.bq), jnp.float32))


def _fwd_finish(o_ref, lse_ref, m, l, acc):
    l = jnp.maximum(l, 1e-20)
    o_ref[:] = (acc / l).T.astype(o_ref.dtype)
    if lse_ref is not None:         # a chunk call has no backward to read it
        lse_ref[:] = m + jnp.log(l)


def _p_tile(w, q, k, lse, mask):
    """One probability tile p^T = exp(k q^T * scale - lse); a masked entry
    reads 0 whatever its query's lse (NEG_INF for one that saw no key)."""
    p = jnp.exp(w.scores(q, k) - lse)
    return p if mask is None else jnp.where(mask, p, 0.0)


def _dq_tile(w, q, k, v, do, lse, delta, mask):
    """dq^T [D_qk, block_q] of one tile, less the folded scale."""
    p = _p_tile(w, q, k, lse, mask)
    ds = w.ds(p, _dot(v, do, _NT), delta)
    return _dot(k, ds.astype(k.dtype), _TN)


def _dq_finish(w, dq_ref, dq):
    dq_ref[:] = (dq * w.scale if w.fold else dq).T.astype(dq_ref.dtype)


def _dkdv_tile(w, q, k, v, do, lse, delta, mask):
    """(dk [block_k, D_qk], dv [block_k, D_v]) of one tile; q is the
    (pre-scaled) Q block."""
    p = _p_tile(w, q, k, lse, mask)
    dv = _dot(p.astype(do.dtype), do, _NN)
    ds = w.ds(p, _dot(v, do, _NT), delta)
    return _dot(ds.astype(q.dtype), q, _NN), dv


# ------------------------------------------------------- resident kernels

def _fwd_resident_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, w):
    """Program (bh, qi): Q block qi against the whole K/V of its row.
    q_ref [block_q, D_qk], o_ref [block_q, D_v]; k_ref [S_k, D_qk], v_ref
    [S_k, D_v]; lse_ref [1, block_q]."""
    qi = pl.program_id(1)
    q = w.q_block(q_ref[:])

    def tile(ki, masked, state):
        return _fwd_tile(w, q, _rows(k_ref, ki, w.bk, w.n_k),
                         _rows(v_ref, ki, w.bk, w.n_k), *state,
                         w.mask(qi, ki) if masked else None)

    _fwd_finish(o_ref, lse_ref,
                *_walk(w.k_spans(qi), tile, _fwd_init(w, v_ref.shape[-1])))


def _dq_resident_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        dq_ref, *, w):
    """Program (bh, qi): dq of Q block qi over the K/V blocks of its row;
    lse_ref/delta_ref [1, block_q]."""
    qi = pl.program_id(1)
    q, do = w.q_block(q_ref[:]), do_ref[:]
    lse, delta = lse_ref[:], delta_ref[:]

    def tile(ki, masked, dq):
        return dq + _dq_tile(w, q, _rows(k_ref, ki, w.bk, w.n_k),
                             _rows(v_ref, ki, w.bk, w.n_k), do, lse, delta,
                             w.mask(qi, ki) if masked else None)

    _dq_finish(w, dq_ref, _walk(w.k_spans(qi), tile,
                                jnp.zeros(q.shape[::-1], jnp.float32)))


def _dkdv_resident_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, w):
    """Program (b * kv_heads, ki): dk/dv of K block ki over the Q blocks of
    its row, of every query head that reads its KV head.  k_ref/v_ref
    [block_k, D]; q_ref/do_ref [S_q, D]; lse_ref/delta_ref [q_blocks, 1,
    block_q]; with ``g`` query heads a KV head each of the four has the
    group's heads in front ([g, S_q, D], [g, q_blocks, 1, block_q]) and the
    walk is run a head.  The two [block_k, D] accumulators are scratch: as
    loop values they cost 13% of the kernel on the chip."""
    ki = pl.program_id(1)
    k, v = k_ref[:], v_ref[:]
    dk_acc[:] = jnp.zeros_like(dk_acc)
    dv_acc[:] = jnp.zeros_like(dv_acc)

    def head(h):
        def stat(ref, qi):
            return ref[qi] if h is None else ref[h, qi]

        def tile(qi, masked, _):
            dk, dv = _dkdv_tile(
                w, w.q_block(_rows(q_ref, qi, w.bq, w.n_q, h)), k, v,
                _rows(do_ref, qi, w.bq, w.n_q, h), stat(lse_ref, qi),
                stat(delta_ref, qi), w.mask(qi, ki) if masked else None)
            dk_acc[:] += dk
            dv_acc[:] += dv

        _walk(w.q_spans(ki), tile)

    if w.g == 1:
        head(None)
    else:
        lax.fori_loop(0, w.g, lambda h, _: head(h), None)
    dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
    dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


# ------------------------------------------------------- streamed kernels

def _fwd_streamed_step(w, last, q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref,
                       l_ref, acc_ref, words_ref=None):
    """Program (bh, qi, j): one tile, K block ``ki`` of the walk's step j;
    m/l [1, block_q] and acc^T [D, block_q] carry the online-softmax state
    across the walk in scratch, and step ``last`` writes the results.
    ``words_ref`` [1, block_q] (a sparse chunk call): the queries' choice
    among this tile's blocks; EVERY tile is then masked, by the choice and,
    where the diagonal crosses it, by both."""
    qi, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        m_ref[:], l_ref[:], acc_ref[:] = _fwd_init(w, acc_ref.shape[0])

    spans = w.k_spans(qi)
    ki = w.at(spans, j)

    def tile(masked):
        mask = w.mask(qi, ki) if masked else None
        if words_ref is not None:
            mine = w.chose(words_ref[:])
            mask = mine if mask is None else mask & mine
        m_ref[:], l_ref[:], acc_ref[:] = _fwd_tile(
            w, w.q_block(q_ref[:]), k_ref[:], v_ref[:], m_ref[:], l_ref[:],
            acc_ref[:], mask)

    _step(spans, ki, tile)

    @pl.when(j == last)
    def _():
        _fwd_finish(o_ref, lse_ref, m_ref[:], l_ref[:], acc_ref[:])


def _fwd_streamed_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
                         acc_ref, *, w):
    _fwd_streamed_step(w, w.steps(True) - 1, q_ref, k_ref, v_ref, o_ref,
                       lse_ref, m_ref, l_ref, acc_ref)


def _fwd_chunk_kernel(starts_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                      acc_ref, *, w, heads):
    """The streamed forward step of a CHUNK call: the diagonal is where the
    program's sequence starts (``starts_ref`` [B], prefetched), and the
    walked axis is as long as the call's longest live walk, a traced bound
    of the grid."""
    _fwd_streamed_step(
        w.from_start(starts_ref[pl.program_id(0) // heads]),
        pl.num_programs(2) - 1, q_ref, k_ref, v_ref, o_ref, None, m_ref,
        l_ref, acc_ref)


def _fwd_sparse_chunk_kernel(starts_ref, q_ref, k_ref, v_ref, words_ref,
                             o_ref, m_ref, l_ref, acc_ref, *, w, heads):
    """:func:`_fwd_chunk_kernel` under the queries' block choice:
    ``words_ref`` [1, block_q], this tile's bits of this Q block's queries,
    the KV head's (its query heads share them)."""
    _fwd_streamed_step(
        w.from_start(starts_ref[pl.program_id(0) // heads]),
        pl.num_programs(2) - 1, q_ref, k_ref, v_ref, o_ref, None, m_ref,
        l_ref, acc_ref, words_ref)


def _dq_streamed_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        dq_ref, dq_acc, *, w):
    """Program (bh, qi, j): accumulate dq^T of one Q block over the K
    blocks of its walk."""
    qi, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    spans = w.k_spans(qi)
    ki = w.at(spans, j)

    def tile(masked):
        dq_acc[:] += _dq_tile(w, w.q_block(q_ref[:]), k_ref[:], v_ref[:],
                              do_ref[:], lse_ref[:], delta_ref[:],
                              w.mask(qi, ki) if masked else None)

    _step(spans, ki, tile)

    @pl.when(j == w.steps(True) - 1)
    def _():
        _dq_finish(w, dq_ref, dq_acc[:])


def _dkdv_streamed_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, w):
    """Program (bh, ki, j): accumulate dk/dv of one K block over the Q
    blocks of its walk; with ``g`` query heads a KV head, program (b *
    kv_heads, ki, head of the group, j): over the group's heads as well."""
    ki, j = pl.program_id(1), pl.program_id(2 if w.g == 1 else 3)

    def at(head, step):
        """This is step ``step`` of the walk of the group's head ``head``."""
        here = j == step
        return here if w.g == 1 else (pl.program_id(2) == head) & here

    @pl.when(at(0, 0))
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    spans = w.q_spans(ki)
    qi = w.at(spans, j)

    def tile(masked):
        dk, dv = _dkdv_tile(w, w.q_block(q_ref[:]), k_ref[:], v_ref[:],
                            do_ref[:], lse_ref[:], delta_ref[:],
                            w.mask(qi, ki) if masked else None)
        dk_acc[:] += dk
        dv_acc[:] += dv

    _step(spans, qi, tile)

    @pl.when(at(w.g - 1, w.steps(False) - 1))
    def _():
        dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


# ------------------------------------------------------------ pallas_calls

def _fit_block(s: int, want: int) -> int:
    """Largest block <= want dividing s: s itself when s <= want, else the
    first halving of want that divides s (>=8 for TPU tiles)."""
    b = min(want, s)
    while b > 8 and s % b:
        b //= 2
    if s % b:
        raise ValueError(
            f"sequence length {s} is not divisible by any block size <= "
            f"{want}; pad the sequence (flash blocks must tile it exactly)")
    return b


def _vmem_bytes(rows: int, cols: int, dtype) -> int:
    """Bytes a [rows, cols] operand takes in VMEM: whole (8, 128) tiles of
    32-bit words (16 rows a tile for bf16)."""
    item = jnp.dtype(dtype).itemsize
    sub = 8 * 4 // item
    return -(-rows // sub) * sub * -(-cols // 128) * 128 * item


def _resident(*operands) -> bool:
    """Whether one row's walked operands ([rows, cols] of dtype each) stay
    in VMEM for the whole row, double-buffered by the pipeline; beyond the
    budget the walk is streamed block by block."""
    return 2 * sum(_vmem_bytes(*o) for o in operands) <= _RESIDENT_VMEM_BYTES


def _params(grid):
    """bh and the block axis a program owns are parallel.  A streamed walk
    adds the walked axes innermost; they carry the VMEM accumulators and
    must run in order.  (A resident walk is a loop inside the program.)"""
    return pltpu.CompilerParams(dimension_semantics=(
        ("parallel", "parallel") + ("arbitrary",) * (len(grid) - 2)))


def _plan(kernel: str, resident: bool, w: _Walk, q, k, v, window, grid,
          **more):
    """Which feeding a kernel got is fixed when the program is traced: one
    instant per pallas_call built says so in a JSONL trace or the xplane of
    a profiled compile.  ``tiles_live`` is what the call's walk visits (every
    head's), ``tiles_causal`` what it would without the window, ``steps``
    the steps of the call's grid: of a streamed call those that run a tile
    and the dead ones (of a chunk call, both for a chunk that ends the
    view: its walk's traced length is at most that).  ``more``: a kernel's
    own facts."""
    (b, h, s_q, d), s_k = q.shape, k.shape[2]
    plain = _Walk(s_q=s_q, s_k=s_k, block_q=w.bq, block_k=w.bk,
                  scale=w.scale, causal=w.causal)
    trace.instant("flash.plan", {
        "kernel": kernel, "resident": int(resident), "block_q": w.bq,
        "block_k": w.bk, "s_q": s_q, "s_k": s_k, "d": d,
        "d_v": v.shape[3], "window": int(window or 0),
        "kv_heads": k.shape[1], "tiles_live": b * h * w.tiles(),
        "tiles_causal": b * h * plain.tiles(), "steps": math.prod(grid),
        **more})


def _live(i, spans, n, *, ends: str = "both"):
    """The block ``i`` a step of a streamed walk over ``n`` blocks stands
    at (``_Walk.at``) clamped into the walk's live range, so a dead step
    re-names a block already in VMEM.  ``ends``: a walk without a window is
    dead at one end only (a Q block's K blocks start at 0: ``"last"``; a K
    block's Q blocks run to the end: ``"first"``), and is clamped there
    alone, as it always was."""
    first = jnp.minimum(spans[0][0], n - 1)
    if ends == "first":
        return jnp.maximum(i, first)
    if ends == "last":
        return jnp.minimum(i, jnp.maximum(spans[-1][1] - 1, 0))
    return jnp.clip(i, first, jnp.maximum(spans[-1][1] - 1, first))


def _specs(resident, w, s_q, s_k, *, own_q: bool):
    """(Q-side spec of a width, Q-side statistics rows' spec, K-side spec
    of a width): Q and K are ``d_qk`` wide, V, O and dO ``d_v``.

    ``own_q``: the program owns a Q block and walks K blocks (forward, dQ);
    else it owns a K block and walks Q blocks (dK/dV).  Resident: the walked
    side is the whole row.  Streamed: the walked side follows the innermost
    grid axis from the block ``_Walk.at`` puts its step 0 at, clamped into
    the live range so dead steps fetch nothing.  The statistics (lse, delta)
    are [bh, q_blocks, 1, block_q].

    With ``g`` query heads a KV head the leading axis of the Q side counts
    query heads and the K side's KV heads: a program that owns a Q block
    reads the K side at ``bh // g``; one that owns a K block of KV head
    ``j`` holds the group's ``g`` heads of the Q side as one block
    (resident) or walks them on a grid axis of their own (streamed)."""
    g = w.g
    lead = None                # the Q side's leading block dim: one head

    def kv(bh):
        return bh if g == 1 else bh // g

    if resident:
        if own_q:
            def q_at(bh, i):
                return bh, i
            def k_at(bh, i):
                return kv(bh), 0
            q_rows, k_rows, stat_blocks = w.bq, s_k, None
        else:
            def q_at(bh, i):
                return bh, 0
            def k_at(bh, i):
                return bh, i
            q_rows, k_rows, stat_blocks = s_q, w.bk, w.n_q
            lead = None if g == 1 else g
    else:
        windowed = w.window is not None
        if own_q:
            def q_at(bh, qi, j):
                return bh, qi
            def k_at(bh, qi, j):
                head, spans = kv(bh), w.k_spans(qi)
                return head, _live(w.at(spans, j), spans, w.n_k,
                                   ends="both" if windowed else "last")
        else:
            def k_at(bh, ki, *walked):
                return bh, ki
            def q_at(bh, ki, *walked):     # walked: (head of the group,) j
                head = bh if g == 1 else bh * g + walked[0]
                spans = w.q_spans(ki)
                return head, _live(w.at(spans, walked[-1]), spans, w.n_q,
                                   ends="both" if windowed else "first")
        q_rows, k_rows, stat_blocks = w.bq, w.bk, None
    return (lambda d: pl.BlockSpec((lead, q_rows, d),
                                   lambda *i: (*q_at(*i), 0)),
            pl.BlockSpec((lead, stat_blocks, 1, w.bq),
                         lambda *i: (*q_at(*i), 0, 0)),
            lambda d: pl.BlockSpec((None, k_rows, d),
                                   lambda *i: (*k_at(*i), 0)))


def _call_walk(q, k, *, scale, causal, window, block_q, block_k) -> _Walk:
    (_, h, s_q, _), (_, h_kv, s_k, _) = q.shape, k.shape
    return _Walk(s_q=s_q, s_k=s_k, block_q=_fit_block(s_q, block_q),
                 block_k=_fit_block(s_k, block_k), scale=scale,
                 causal=causal, window=window, group=h // h_kv)


def _flash_fwd(q, k, v, *, scale, causal, window, block_q, block_k,
               interpret):
    b, h, s_q, d = q.shape
    h_kv, s_k, d_v = k.shape[1], k.shape[2], v.shape[3]
    w = _call_walk(q, k, scale=scale, causal=causal, window=window,
                   block_q=block_q, block_k=block_k)
    resident = _resident((s_k, d, k.dtype), (s_k, d_v, v.dtype))
    grid = (b * h, w.n_q) if resident else (b * h, w.n_q, w.steps(True))
    _plan("fwd", resident, w, q, k, v, window, grid)
    q_side, lse_spec, k_side = _specs(resident, w, s_q, s_k, own_q=True)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_resident_kernel if resident
                          else _fwd_streamed_kernel, w=w),
        grid=grid,
        in_specs=[q_side(d), k_side(d), k_side(d_v)],
        out_specs=[q_side(d_v), lse_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s_q, d_v), q.dtype),
            jax.ShapeDtypeStruct((b * h, w.n_q, 1, w.bq), jnp.float32),
        ],
        scratch_shapes=[] if resident else [
            pltpu.VMEM((1, w.bq), jnp.float32),
            pltpu.VMEM((1, w.bq), jnp.float32),
            pltpu.VMEM((d_v, w.bq), jnp.float32)],
        compiler_params=_params(grid),
        interpret=interpret,
    )(q.reshape(b * h, s_q, d), k.reshape(b * h_kv, s_k, d),
      v.reshape(b * h_kv, s_k, d_v))
    return out.reshape(b, h, s_q, d_v), lse


def _choice_words(chosen, n_k: int, per: int):
    """A chunk's block choice as the sparse chunk kernel reads it: chosen
    [B, S, g, blocks] bool -> int32 [B * g, n_k, 1, S], bit ``r`` of word
    (K tile, query) saying whether the query chose block ``tile * per + r``
    (``per`` blocks a K tile: 8 bits at 512 | 64); blocks past the view's
    are unchosen.  Lane-dense as the m and l rows are: a tile's words are a
    [1, block_q] row, one a (KV head, query)."""
    b, s, g, n = chosen.shape
    bits = jnp.pad(chosen, ((0, 0),) * 3 + ((0, n_k * per - n),)).reshape(
        b, s, g, n_k, per).astype(jnp.int32)
    words = jnp.sum(bits << jnp.arange(per, dtype=jnp.int32), axis=-1)
    return jnp.moveaxis(words, 1, 3).reshape(b * g, n_k, 1, s)


def _chunk_call(q, k, v, starts, chosen=None, *, scale, block_q, block_k,
                head_major, interpret, block=None):
    """The forward of a chunk call, q [B, H, S_c, D_qk] against the
    TIME-major k [B, T, H / g, D_qk] and v [B, T, H / g, D_v] (``head_major``:
    [B, H / g, T, .]); under ``chosen`` [B, S_c, H / g, T / block] each
    query over the blocks of ``block`` positions it chose (every tile is
    then masked: the other kernel body, the same walk).

    Where both widths of a time-major view are whole lane tiles (128, 256)
    it is fed AS IT LIES, its flat rows [B, T, (H / g) * D] with a KV head a
    column block of the index map: no copy of it is made.  Any other width
    (64; 192 | 128) cannot be a column block, and the view is relaid
    head-major first, two passes over it; operands given head-major are fed
    as they lie."""
    b, h, s_q, d = q.shape
    t, h_kv = (k.shape[2], k.shape[1]) if head_major else k.shape[1:3]
    d_v = v.shape[3]
    # a view that is not a whole number of K blocks is padded to one, as it
    # is fed: the keys there lie past every chunk
    s_k = t + -t % min(block_k, t)
    w = _Walk(s_q=s_q, s_k=s_k, block_q=_fit_block(s_q, block_q),
              block_k=_fit_block(s_k, block_k), scale=scale, causal=True,
              group=h // h_kv, block=block)
    # the K blocks up to the one the last chunk of the call ends in: the
    # walked axis of the grid, a traced bound
    steps = jnp.clip((jnp.max(starts) + s_q + w.bk - 1) // w.bk, 1, w.n_k)
    grid = (b * h, w.n_q, steps)
    as_rows = not head_major and d % 128 == 0 and d_v % 128 == 0
    _plan("fwd_chunk" if chosen is None else "fwd_sparse_chunk", False, w, q,
          jax.ShapeDtypeStruct((b, h_kv, s_k, d), k.dtype),
          jax.ShapeDtypeStruct((b, h_kv, s_k, d_v), v.dtype), None,
          (b * h, w.n_q, w.n_k), as_rows=int(as_rows),
          **({} if chosen is None else {"block": block}))

    def q_at(bh, qi, j, starts):
        return bh, qi, 0

    def k_block(bh, qi, j, starts):
        spans = w.from_start(starts[bh // h]).k_spans(qi)
        return _live(j, spans, w.n_k, ends="last")

    def k_at(bh, qi, j, starts):
        at = k_block(bh, qi, j, starts)
        if as_rows:
            return bh // h, at, bh % h // w.g
        return bh // w.g, at, 0

    def words_at(bh, qi, j, starts):
        return bh // w.g, k_block(bh, qi, j, starts), 0, qi

    def fed(x):
        if as_rows:
            x = x.reshape(b, t, -1)
        else:
            x = (x if head_major else jnp.moveaxis(x, 1, 2)).reshape(
                b * h_kv, t, -1)
        return x if t == s_k else jnp.pad(x, ((0, 0), (0, s_k - t), (0, 0)))

    kernel, more_specs, more = _fwd_chunk_kernel, [], []
    if chosen is not None:
        kernel = _fwd_sparse_chunk_kernel
        more_specs = [pl.BlockSpec((None, None, 1, w.bq), words_at)]
        more = [_choice_words(chosen, w.n_k, w.bk // block)]
    out = pl.pallas_call(
        functools.partial(kernel, w=w, heads=h),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid,
            in_specs=[pl.BlockSpec((None, w.bq, d), q_at),
                      pl.BlockSpec((None, w.bk, d), k_at),
                      pl.BlockSpec((None, w.bk, d_v), k_at), *more_specs],
            out_specs=pl.BlockSpec((None, w.bq, d_v), q_at),
            scratch_shapes=[pltpu.VMEM((1, w.bq), jnp.float32),
                            pltpu.VMEM((1, w.bq), jnp.float32),
                            pltpu.VMEM((d_v, w.bq), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b * h, s_q, d_v), q.dtype),
        compiler_params=_params(grid),
        interpret=interpret,
    )(starts, q.reshape(b * h, s_q, d), fed(k), fed(v), *more)
    return out.reshape(b, h, s_q, d_v)


# Jitted, each under a name of its own (the compiled call's name, which a
# device trace is read by): a program that attends in several places (layers
# outside a scan, the branches of a switch) traces and lowers a kernel once.

@functools.partial(jax.jit, static_argnames=(
    "scale", "block_q", "block_k", "head_major", "interpret"))
def _flash_chunk(q, k, v, starts, *, scale, block_q, block_k, head_major,
                 interpret):
    return _chunk_call(q, k, v, starts, scale=scale, block_q=block_q,
                       block_k=block_k, head_major=head_major,
                       interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "block", "scale", "block_q", "block_k", "interpret"))
def _flash_sparse_chunk(q, k, v, starts, chosen, *, block, scale, block_q,
                        block_k, interpret):
    return _chunk_call(q, k, v, starts, chosen, block=block, scale=scale,
                       block_q=block_q, block_k=block_k, head_major=False,
                       interpret=interpret)


def _flash_bwd(q, k, v, out, lse, g, *, scale, causal, window, block_q,
               block_k, interpret):
    b, h, s_q, d = q.shape
    h_kv, s_k, d_v = k.shape[1], k.shape[2], v.shape[3]
    w = _call_walk(q, k, scale=scale, causal=causal, window=window,
                   block_q=block_q, block_k=block_k)

    qf = q.reshape(b * h, s_q, d)
    kf = k.reshape(b * h_kv, s_k, d)
    vf = v.reshape(b * h_kv, s_k, d_v)
    dof = g.reshape(b * h, s_q, d_v)
    # delta = rowsum(dO * O): one fused elementwise+reduce, O(S*D) traffic
    delta = jnp.sum(dof.astype(jnp.float32)
                    * out.reshape(b * h, s_q, d_v).astype(jnp.float32),
                    axis=-1).reshape(lse.shape)

    # dK/dV: a program owns a K block of one KV head and walks the Q side,
    # of every query head of its group
    resident = _resident((w.g * s_q, d, q.dtype), (w.g * s_q, d_v, g.dtype),
                         (w.g * 8 * w.n_q, w.bq, jnp.float32),
                         (w.g * 8 * w.n_q, w.bq, jnp.float32))
    grid = (b * h_kv, w.n_k) if resident \
        else (b * h_kv, w.n_k, w.steps(False)) if w.g == 1 \
        else (b * h_kv, w.n_k, w.g, w.steps(False))
    _plan("dkdv", resident, w, q, k, v, window, grid)
    q_side, stat_spec, k_side = _specs(resident, w, s_q, s_k, own_q=False)
    dk, dv = pl.pallas_call(
        functools.partial(_dkdv_resident_kernel if resident
                          else _dkdv_streamed_kernel, w=w),
        grid=grid,
        in_specs=[q_side(d), k_side(d), k_side(d_v), q_side(d_v), stat_spec,
                  stat_spec],
        out_specs=[k_side(d), k_side(d_v)],
        out_shape=[
            jax.ShapeDtypeStruct((b * h_kv, s_k, d), k.dtype),
            jax.ShapeDtypeStruct((b * h_kv, s_k, d_v), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((w.bk, d), jnp.float32),
                        pltpu.VMEM((w.bk, d_v), jnp.float32)],
        compiler_params=_params(grid),
        interpret=interpret,
    )(qf, kf, vf, dof, lse, delta)

    # dQ: a program owns a Q block and walks the K side
    resident = _resident((s_k, d, k.dtype), (s_k, d_v, v.dtype))
    grid = (b * h, w.n_q) if resident else (b * h, w.n_q, w.steps(True))
    _plan("dq", resident, w, q, k, v, window, grid)
    q_side, stat_spec, k_side = _specs(resident, w, s_q, s_k, own_q=True)
    dq = pl.pallas_call(
        functools.partial(_dq_resident_kernel if resident
                          else _dq_streamed_kernel, w=w),
        grid=grid,
        in_specs=[q_side(d), k_side(d), k_side(d_v), q_side(d_v), stat_spec,
                  stat_spec],
        out_specs=q_side(d),
        out_shape=jax.ShapeDtypeStruct((b * h, s_q, d), q.dtype),
        scratch_shapes=[] if resident else [
            pltpu.VMEM((d, w.bq), jnp.float32)],
        compiler_params=_params(grid),
        interpret=interpret,
    )(qf, kf, vf, dof, lse, delta)

    return (dq.reshape(b, h, s_q, d), dk.reshape(b, h_kv, s_k, d),
            dv.reshape(b, h_kv, s_k, d_v))


# ---------------------------------------------------------------- public op

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, scale, causal, window, block_q, block_k, interpret):
    out, _ = _flash_fwd(q, k, v, scale=scale, causal=causal, window=window,
                        block_q=block_q, block_k=block_k,
                        interpret=interpret)
    return out


def _flash_vjp_fwd(q, k, v, scale, causal, window, block_q, block_k,
                   interpret):
    out, lse = _flash_fwd(q, k, v, scale=scale, causal=causal,
                          window=window, block_q=block_q, block_k=block_k,
                          interpret=interpret)
    # named before ``out`` leaves both ways, so that a recomputed layer
    # reads the saved value as the primal and as the residual
    out = checkpoint_name(out, SAVED_OUT)
    lse = checkpoint_name(lse, SAVED_LSE)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(scale, causal, window, block_q, block_k, interpret, res,
                   g):
    q, k, v, out, lse = res
    return _flash_bwd(q, k, v, out, lse, g, scale=scale, causal=causal,
                      window=window, block_q=block_q, block_k=block_k,
                      interpret=interpret)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _mesh_partition(batch: int, heads: int):
    """How the mesh in context (``jax.set_mesh``; Executor and the serving
    engines enter theirs) splits a [B, H, S, D] attention operand: batch
    over 'dp' and heads over 'tp' — the two dims every kernel program is
    independent along — where the axis divides the dim, replicated over
    the rest.  Returns (axes to take manual, spec); no axes when no mesh
    is set or an enclosing shard_map already holds them all."""
    mesh = jax.sharding.get_abstract_mesh()
    auto = frozenset(a for a, t in zip(mesh.axis_names, mesh.axis_types)
                     if t == AxisType.Auto)

    def axis(name, n):
        return name if name in auto and n % mesh.shape[name] == 0 else None

    return auto, P(axis(AXIS_DP, batch), axis(AXIS_TP, heads), None, None)


def _check_heads(h: int, h_k: int, h_v: int) -> None:
    if h_k != h_v or h % h_k:
        raise ValueError(
            f"{h} query heads over {h_k} key and {h_v} value heads: K and V "
            "share a head count that divides the queries'")


def flash_attention(q, k, v, *, causal: bool = False, window=None,
                    scale=None, block_q: int = 512, block_k: int = 512,
                    interpret=None):
    """Fused attention: q [B, H, S, D_qk], k [B, H / g, S_k, D_qk], v [B,
    H / g, S_k, D_v] → [B, H, S_q, D_v] (the two widths may differ; ``g``
    any divisor of H: query head ``h`` reads KV head ``h // g``, and dK and
    dV come back at ``H / g`` heads).

    Fully fused in both directions: forward walks K/V blocks with online
    softmax; backward recomputes probability tiles from the saved LSE
    (FlashAttention-2) — no O(S^2) tensor in HBM either way.  Whether a
    row's K/V (Q, dO for dK/dV) stays in VMEM or is streamed block by block
    follows from the shapes (module docstring); ``block_q``/``block_k`` are
    the tile size either way.

    interpret=None auto-selects: compiled kernel on TPU, interpret mode on
    CPU.  Block sizes auto-fit down to the sequence length (any S
    divisible by a power-of-two >= 8 works; only truly odd lengths need
    upstream padding).  Causal masking is bottom-right aligned for
    S_q != S_k.  ``window`` (with ``causal``): a query sees itself and the
    ``window - 1`` keys before it, the rule of ``ops.causal_attention``;
    blocks wholly behind the window are not walked.

    The SPMD partitioner cannot split a compiled ``pallas_call`` (JAX
    refuses to lower one outside a fully manual region), so under a mesh
    the call is wrapped in a ``shard_map`` over the batch and head axes
    (:func:`_mesh_partition`): each device runs the kernel on its own shard.

    Under differentiation the output and the LSE rows carry the names
    ``SAVED_OUT`` and ``SAVED_LSE`` (inside the ``shard_map`` too).  A name
    is the identity wherever no ``jax.checkpoint`` policy asks for it; a
    layer recomputed through ``ops.remat`` keeps the two, so its backward
    runs dK/dV and dQ and not the forward kernel a second time.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    _check_heads(q.shape[1], k.shape[1], v.shape[1])
    if window is not None and (not causal or int(window) < 1):
        raise ValueError(f"window {window!r}: a positive number of keys, "
                         "and only with causal=True")
    static = (float(scale), bool(causal),
              None if window is None else int(window), int(block_q),
              int(block_k), auto_interpret(interpret))
    # the KV heads are the fewer: an axis that divides them divides both
    axes, spec = _mesh_partition(q.shape[0], k.shape[1])
    if not axes:
        return _flash(q, k, v, *static)
    return shard_map(lambda q, k, v: _flash(q, k, v, *static),
                     in_specs=(spec, spec, spec), out_specs=spec,
                     axis_names=axes, check_vma=False)(q, k, v)


def unwritten(shape, dtype, *, interpret=None):
    """An array of ``shape`` that NOBODY WROTE: what a caller fills part of
    (the keys and values a chunk call will walk, rebuilt as far as the
    history goes) without paying a pass of zeros over the whole of it first.
    Every element that is read must have been written; interpret mode hands
    out NaNs where a float was not."""
    return pl.pallas_call(
        lambda out_ref: None, out_shape=jax.ShapeDtypeStruct(shape, dtype),
        out_specs=pl.BlockSpec(memory_space=pl.ANY), name="unwritten",
        interpret=auto_interpret(interpret))()


def write_rows(whole, rows, at, *, multiple_of: int, interpret=None):
    """``whole`` [B, H, T, D] with ``rows`` [B, H, n, D] put at key rows
    ``at .. at + n`` of every head IN PLACE, ``at`` traced and a multiple of
    ``multiple_of``: one DMA from HBM to HBM.  What
    ``lax.dynamic_update_slice`` says, which the TPU's compiler runs as a
    copy of its own at a quarter of the memory's bandwidth (0.30 ms for 25
    MB: ``PERF.md`` section 6, PR 52), and which is what runs where ``at``
    may fall inside a tile of 16 rows or ``D`` is not whole lane tiles: a
    DMA cannot start or end there."""
    if multiple_of % 16 or rows.shape[3] % 128:
        return lax.dynamic_update_slice_in_dim(whole, rows, at, 2)
    n = rows.shape[2]

    def kernel(at_ref, rows_ref, _, out_ref, sem):
        start = pl.multiple_of(at_ref[0], multiple_of)
        copy = pltpu.make_async_copy(
            rows_ref, out_ref.at[:, :, pl.ds(start, n)], sem)
        copy.start()
        copy.wait()

    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct(whole.shape, whole.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())]),
        input_output_aliases={2: 0}, name="write_rows",
        interpret=auto_interpret(interpret),
    )(jnp.reshape(at, (1,)).astype(jnp.int32), rows, whole)


def flash_chunk_attention(q, k, v, starts, *, scale=None, block_q: int = 512,
                          block_k: int = 512, head_major: bool = False,
                          interpret=None):
    """A CHUNK of queries against a longer history, forward only: q [B, H,
    S_c, D_qk] whose row ``i`` sits at absolute position ``starts[b] + i``
    (``starts`` [B] int32, traced); k [B, T, H / g, D_qk] and v [B, T,
    H / g, D_v] TIME-major, as a cache's view lies, with ``T >= starts[b] +
    S_c`` static -> [B, H, S_c, D_v].  ``head_major``: k and v are given
    [B, H / g, T, .], as a caller that builds them for this call lays them.

    Query ``i`` sees key ``t <= starts[b] + i``: the history and the chunk's
    own triangle.  Keys past the chunk's last position (unwritten rows, a
    page's stale occupant, padding) are masked, and the K blocks wholly past
    it are never stepped over (the walked axis of the grid ends with the
    longest history of the call) or, for a sequence shorter than that, are
    dead steps that fetch nothing: the cost follows the history, not ``T``.
    A ``T`` that is not a whole number of K blocks is padded to one (a copy
    of the view); widths that are whole lane tiles are read from the view
    where it lies, others from a head-major copy (:func:`_flash_chunk`).

    There is no backward (``jax.grad`` through it fails): what trains calls
    :func:`flash_attention`, whose lengths are static.  No mesh either: the
    caller keeps a view laid over one away (``ops.chunk_attention``)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    heads = 1 if head_major else 2
    _check_heads(q.shape[1], k.shape[heads], v.shape[heads])
    return _flash_chunk(q, k, v, starts.astype(jnp.int32),
                        scale=float(scale), block_q=int(block_q),
                        block_k=int(block_k), head_major=bool(head_major),
                        interpret=auto_interpret(interpret))


def flash_sparse_chunk_attention(q, k, v, starts, chosen, *, block: int,
                                 scale=None, block_q: int = 512,
                                 block_k: int = 512, interpret=None):
    """:func:`flash_chunk_attention` of a BLOCK-SPARSE layer: query ``i``
    sees key ``t <= starts[b] + i`` only where it chose ``t``'s block of
    ``block`` positions, ``chosen`` [B, S_c, H / g, T / block] bool (a
    choice is a (token, KV head)'s: the ``g`` query heads of a KV head share
    it; a query that reads every block has all of them set).  q [B, H, S_c,
    D_qk]; k [B, T, H / g, D_qk] and v [B, T, H / g, D_v] time-major, ``T``
    whole blocks.  The same walk, tiles and online softmax as the dense
    call, every tile masked: by the choice (``_Walk.chose``, from words of
    bits laid as the statistics' rows are, :func:`_choice_words`) and, where
    the diagonal crosses it, by both.  Nothing is skipped for the choice's
    sake: on a tile of 512 x 512 some query reads some block unless
    neighbouring tokens choose alike.  A query that chose nothing it can see
    reads 0.  ``block`` divides the K tile (``block_k`` rows, or one block
    where a block is longer), at most 32 blocks a tile."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    _check_heads(q.shape[1], k.shape[2], v.shape[2])
    t = k.shape[1]
    block_k = max(int(block_k), int(block))     # a K tile holds whole blocks
    tile = _fit_block(t + -t % min(block_k, t), block_k)
    if t % block or tile % block or tile // block > 32 \
            or chosen.shape != (q.shape[0], q.shape[2], k.shape[2],
                                t // block):
        raise ValueError(
            f"a choice {chosen.shape} among blocks of {block} positions "
            f"over {t} rows walked {tile} at a time: whole blocks, a K tile "
            "whole blocks (32 at most), a flag a (query, KV head, block)")
    return _flash_sparse_chunk(q, k, v, starts.astype(jnp.int32), chosen,
                               block=int(block), scale=float(scale),
                               block_q=int(block_q), block_k=block_k,
                               interpret=auto_interpret(interpret))
