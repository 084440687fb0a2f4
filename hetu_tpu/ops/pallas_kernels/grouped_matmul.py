"""Grouped matmuls over rows sorted by group (the held-expert walk's
experts): :func:`gmm`, ``out[rows of g] = lhs[rows of g] @ rhs[g]``, and its
transpose :func:`tgmm`, ``out[g] += lhs[rows of g]^T @ rhs[rows of g]``.

The shape of ``jax.experimental.pallas.ops.tpu.megablox``, cut to what the
walk needs.  Group sizes are DATA, every shape is static: the rows of group
``g`` are ``starts[g] .. ends[g] - 1`` of the first operand, contiguous and
in group order, anywhere inside it (a window of a longer sorted order has
its sizes clipped to the window by the caller).  The row axis is cut into
tiles of :data:`TILE_ROWS`; the grid's row axis is the list of VISITS, one a
(group, tile) pair that shares a row, read from scalar-prefetched tables
(:func:`group_visits`, made once for all the calls over the same rows), and
its length is read from the sizes too: an empty group is never visited, its
weights never read, and a tile that two groups share is visited once by each
with the other's rows masked (no group is padded to a tile).  Tiles past the
last group's rows are not visited: :func:`gmm` leaves those rows of its
result UNWRITTEN, and the caller masks them.

``gmm`` keeps a group's whole ``[K, N]`` weight in VMEM, read in place from
the stacked ``[G, K, N]`` operand (with ``layer``: ``[L, G, K, N]``) and
fetched once a group, the visits of one group being consecutive (the next
group's fetch runs behind the current one's matmul); :func:`gmm_ffn` is a
whole SwiGLU expert in one such call, gate, up and down, the ``[rows, F]``
intermediate never leaving VMEM, and where an expert's three weights do not
fit VMEM whole the same call CUT ALONG F (:func:`ffn_tiles`): a visit walks
the expert's intermediate width in tiles, a tile's three slabs in VMEM at a
time and its down product summed in a float32 scratch; :func:`gmm_down_back`
is the down projection's backward in one such call,
the forward's product recomputed and never written.  ``tgmm``
keeps a group's ``[K, N]`` sum in a float32 VMEM scratch across that group's
visits and writes it once, added to what the aliased accumulator held
(``acc``: a group without rows is not touched and keeps its value).

bf16 operands, float32 accumulation.  The calls carry the name
``hetu.moe.gmm`` in a device trace.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hetu_tpu.utils.platform import auto_interpret

# rows a visit multiplies.  128 x 128 is the MXU; 256 rows keep a visit's
# matmul near 4 us at the trained widths while a tile shared by two groups
# (one a group boundary, computed twice) stays a small share of the rows
TILE_ROWS = 256
# a tgmm sum block, in elements: [K, N] is cut along K or N to fit
_TGMM_BLOCK = 1152 * 1024
_VMEM_LIMIT = 96 * 1024 * 1024
# what an expert's three weights may hold, double-buffered, to be kept WHOLE
# across its visits by gmm_ffn: half the limit, the rest being a 256-row
# visit's rows, result and [rows, F] intermediates.  An [H, F] expert of 4 Mi
# bf16 elements is the largest kept whole
_FFN_WEIGHT_BYTES = _VMEM_LIMIT // 2
# where they are not, gmm_ffn is cut along F, and a step's three weight tiles
# may hold this much, double-buffered: the limit less 16 MiB, which is what a
# 128-row visit keeps beside them at H = 6144 (rows 1.6 x 2, result 3.1 x 2,
# the float32 sum 3.1, gate, up and their product at f_t = 1024 1.5: 14 MB)
_CUT_WEIGHT_BYTES = _VMEM_LIMIT - (16 << 20)
# rows a visit of the F-cut gmm_ffn multiplies (ffn_tiles says why), and what
# its F tile is a multiple of: a weight block's minor dimension in whole lanes
CUT_TILE_ROWS = 128
_LANES = 128
SCOPE = "hetu.moe.gmm"


class Visits(NamedTuple):
    """The visits of groups ``starts[g] .. ends[g] - 1`` over some rows in
    tiles: what every grouped call over the same sorted rows reads, made
    once (:func:`group_visits`) and handed to each."""
    group: jax.Array       # [V_max] the group of visit v
    tile: jax.Array        # [V_max] its row tile
    count: jax.Array       # how many of the V_max are real
    starts: jax.Array      # [G] each group's first row
    ends: jax.Array        # [G] one past its last
    tile_rows: int         # rows a tile (static)


def group_visits(starts, ends, rows: int, tile_rows=None) -> Visits:
    """The visit tables of groups ``starts[g] .. ends[g] - 1`` over ``rows``
    rows in tiles of ``tile_rows`` (:data:`TILE_ROWS` unless given; of
    ``rows``, if fewer).  ``V_max = rows / tile + G - 1``."""
    tm = _tile_rows(rows, tile_rows)
    G = starts.shape[0]
    starts, ends = starts.astype(jnp.int32), ends.astype(jnp.int32)
    sizes = ends - starts
    first = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    v_end = jnp.cumsum(tiles)
    v_max = rows // tm + G - 1
    v = jnp.arange(v_max, dtype=jnp.int32)
    group = jnp.minimum(jnp.searchsorted(v_end, v, side="right"),
                        G - 1).astype(jnp.int32)
    tile = first[group] + v - (v_end[group] - tiles[group])
    tile = jnp.clip(tile, 0, rows // tm - 1).astype(jnp.int32)
    return Visits(group, tile, v_end[-1].astype(jnp.int32), starts, ends, tm)


def _tile_rows(M, tile_rows=None):
    tm = min(tile_rows or TILE_ROWS, M)
    if M % tm:
        raise ValueError(f"{M} rows are no multiple of the tile's {tm}")
    return tm


def _row_mask(starts, ends, g, t, tm):
    rows = t * tm + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    return (rows >= starts[g]) & (rows < ends[g])


def _store(out, value, mask, fresh):
    """A visit's rows of ``value`` into its tile of ``out``: the first visit
    of a tile zeroes the rows it does not own, a later one keeps them."""
    @pl.when(fresh)
    def _():
        out[...] = jnp.where(mask, value, 0).astype(out.dtype)

    @pl.when(jnp.logical_not(fresh))
    def _():
        out[...] = jnp.where(mask, value.astype(out.dtype), out[...])


def _visit(group, tile, starts, ends, tm):
    """(group, row mask, whether the tile is visited for the first time) of
    this grid step's visit."""
    v = pl.program_id(0)
    g, t = group[v], tile[v]
    fresh = (v == 0) | (tile[jnp.maximum(v - 1, 0)] != t)
    return g, _row_mask(starts, ends, g, t, tm), fresh


_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))


def _gmm_kernel(group, tile, starts, ends, layer, *refs, tm, transpose_rhs,
                pairs, scaled):
    del layer
    out = refs[-1]
    _, mask, fresh = _visit(group, tile, starts, ends, tm)
    dims = _NT if transpose_rhs else _NN
    acc = sum(lax.dot_general(refs[2 * i][...], refs[2 * i + 1][...], dims,
                              preferred_element_type=jnp.float32)
              for i in range(pairs))
    if scaled:
        acc = acc * refs[2 * pairs][...]
    _store(out, acc, mask, fresh)


def _weight_spec(w, layer, cut=None):
    """(block spec, the layer operand) of a stacked weight read in place:
    group ``group[v]``'s whole [K, N] slab, of layer ``layer`` if given.
    ``cut=(axis, f_t)``: the slab's tile of ``f_t`` along ``axis`` (0 | 1)
    that the grid's second axis names."""
    stacked = layer is not None

    def index(v, *rest):
        group, layer = rest[-5], rest[-1]
        at = [0, 0]
        if cut is not None:
            at[cut[0]] = rest[0]
        return ((layer[0],) if stacked else ()) + (group[v], *at)

    slab = list(w.shape[-2:])
    if cut is not None:
        slab[cut[0]] = cut[1]
    block = ((None,) if stacked else ()) + (None,) + tuple(slab)
    layer = jnp.zeros((1,), jnp.int32) if layer is None \
        else jnp.asarray(layer, jnp.int32).reshape(1)
    return pl.BlockSpec(block, index), layer


def _rows_spec(tm, width):
    return pl.BlockSpec((tm, width), lambda v, *rest: (rest[-4][v], 0))


def _over_visits(kernel, visits: Visits, layer_op, operands, specs, outs, *,
                 interpret, inner=(), scratch=()):
    """``kernel`` run once a visit of ``visits`` over the rows of
    ``operands[0]``; ``outs``: the (width, dtype) of each [M, width] result.
    One result comes back bare.  ``inner``: the steps of a second, innermost
    grid axis a visit walks, ``scratch`` the VMEM it keeps across them."""
    M = operands[0].shape[0]
    tm = _tile_rows(M, visits.tile_rows)
    call = pl.pallas_call(
        functools.partial(kernel, tm=tm),
        out_shape=tuple(jax.ShapeDtypeStruct((M, n), d) for n, d in outs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            in_specs=[s if isinstance(s, pl.BlockSpec) else _rows_spec(tm, s)
                      for s in specs],
            out_specs=tuple(_rows_spec(tm, n) for n, _ in outs),
            grid=(visits.count,) + tuple(inner),
            scratch_shapes=list(scratch)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * (1 + len(inner)),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=auto_interpret(interpret))
    with jax.named_scope(SCOPE):
        out = call(visits.group, visits.tile, visits.starts, visits.ends,
                   layer_op, *operands)
    return out[0] if len(outs) == 1 else out


def _column(v):
    return v.astype(jnp.float32).reshape(-1, 1)


def gmm(lhs, rhs, visits: Visits, *, layer=None, transpose_rhs: bool = False,
        out_dtype=jnp.float32, also=None, row_scale=None, interpret=None):
    """``lhs`` [M, K] rows sorted by group (``visits`` over its ``M`` rows:
    :func:`group_visits`), ``rhs`` [G, K, N] (or [G, N, K]
    with ``transpose_rhs``; a leading layer axis with ``layer``) ->
    [M, N] ``out_dtype``: row ``r`` of group ``g`` is ``lhs[r] @ rhs[g]``,
    accumulated in float32.  ``also=(lhs2, rhs2)`` adds a second such
    product before the result is rounded; ``row_scale`` [M] float32
    multiplies row ``r`` by ``row_scale[r]``, in float32.  Rows of a visited
    tile that no group owns are zero; tiles past the last group's rows are
    unwritten."""
    N = rhs.shape[-2] if transpose_rhs else rhs.shape[-1]
    operands, specs = [], []
    for a, w in ((lhs, rhs),) + ((also,) if also is not None else ()):
        spec, layer_op = _weight_spec(w, layer)
        operands += [a, w]
        specs += [a.shape[1], spec]
    pairs = len(operands) // 2
    if row_scale is not None:
        operands.append(_column(row_scale))
        specs.append(1)
    return _over_visits(
        functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs,
                          pairs=pairs, scaled=row_scale is not None),
        visits, layer_op, operands, specs, [(N, out_dtype)],
        interpret=interpret)


def _swiglu_down(x, w_gate, w_up, w_down, limit=None):
    """``(silu(x w_gate) * (x w_up)) w_down`` in float32 over whole weights
    or over one F tile of each (the contraction over H is whole either way):
    gate and up rounded to the rows' type, as the three-call form hands
    them on; SwiGLU in float32, its product rounded once.  ``limit``: the
    model's clamp, ``silu(min(gate, limit)) * clip(up, -limit, limit)``."""
    f32 = jnp.float32
    g, u = (lax.dot_general(x, w[...], _NN, preferred_element_type=f32)
            .astype(x.dtype).astype(f32) for w in (w_gate, w_up))
    if limit is not None:
        g, u = jnp.minimum(g, limit), jnp.clip(u, -limit, limit)
    a = (g * jax.nn.sigmoid(g) * u).astype(x.dtype)
    return lax.dot_general(a, w_down[...], _NN, preferred_element_type=f32)


def _ffn_kernel(group, tile, starts, ends, layer, rows, w_gate, w_up, w_down,
                scale, out, *, tm, limit=None):
    del layer
    _, mask, fresh = _visit(group, tile, starts, ends, tm)
    y = _swiglu_down(rows[...], w_gate, w_up, w_down, limit)
    _store(out, y * scale[...], mask, fresh)


def _ffn_cut_kernel(group, tile, starts, ends, layer, rows, w_gate, w_up,
                    w_down, scale, out, acc, *, tm, limit=None):
    """:func:`_ffn_kernel` with the grid's second axis over F tiles: a
    step's down product is added into ``acc`` (float32 [tm, H], VMEM) and
    the visit's last step stores the sum."""
    del layer
    f, last = pl.program_id(1), pl.num_programs(1) - 1
    _, mask, fresh = _visit(group, tile, starts, ends, tm)
    y = _swiglu_down(rows[...], w_gate, w_up, w_down, limit)

    @pl.when(f == 0)
    def _():
        acc[...] = y

    @pl.when(f > 0)
    def _():
        acc[...] += y

    @pl.when(f == last)
    def _():
        _store(out, acc[...] * scale[...], mask, fresh)


def ffn_tiles(H: int, F: int, itemsize: int = 2):
    """(rows a tile, ``f_t``) of :func:`gmm_ffn` over ``[H, F]`` experts:
    ``(TILE_ROWS, F)``, the whole-weight call, where the three weights fit
    :data:`_FFN_WEIGHT_BYTES` double-buffered; otherwise the F-cut call at
    :data:`CUT_TILE_ROWS` rows and the widest ``f_t`` that divides ``F`` in
    whole lanes and fits :data:`_CUT_WEIGHT_BYTES` (6144 x 2048 bf16: 1024,
    37.7 MB of weights a step, 75.5 MB double-buffered); None where no such
    tile does.  Static, read from the shapes.

    Both constants from the chip (v5e; PERF.md section 6, PR 55, call 1):
    twelve walks in a chain over 6144 x 2048 experts, 16 held, ms (GB/s of
    the hit experts' 75.5 MB each; the loop the walk replaced first):

        rows  f_t   16 x 8 of 128   512 x 8 of 128   16 x 12 of 768  512 x 12
        loop          19.44 (501)     32.98 (440)      7.89 (469)   33.04 (439)
         64   512     15.36 (634)     34.94 (415)      6.67 (555)   25.38 (571)
        128   512     15.47 (630)     30.17 (480)      6.74 (549)   24.54 (591)
        256   512     15.81 (616)     28.42 (510)      7.49 (494)   24.97 (580)
        128   256     15.28 (638)     29.62 (489)      6.66 (556)   24.01 (604)
        128  1024     14.61 (667)     28.59 (507)      6.65 (556)   23.26 (623)
         64  1024     14.48 (673)     33.07 (438)      6.35 (582)   23.89 (607)

    (256 rows at 1024 columns pass VMEM.)  The widest tile that fits is
    ahead at every shape: two steps a visit, each adding into the sum once.
    A tile index moves every step, so a visit re-reads its expert where the
    whole-weight call keeps it: a trip whose experts' rows cross a row
    tile's boundary reads that expert twice, which is what a 512-token
    chunk of K-EXAONE pays at 64 rows (768 sorted rows, up to eleven
    boundaries) and 256 rows win back; 128 rows are within 1% of K-EXAONE's
    best round and best chunk and LongCat's best chunk (its round of 4 held
    pairs reads 4.5% ahead at 64), and one tile serves every shape."""
    def fits(f_t, limit):
        return 3 * H * f_t * itemsize * 2 <= limit

    if fits(F, _FFN_WEIGHT_BYTES):
        return TILE_ROWS, F
    cuts = [f for f in range(_LANES, F, _LANES)
            if F % f == 0 and fits(f, _CUT_WEIGHT_BYTES)]
    return (CUT_TILE_ROWS, max(cuts)) if cuts else None


def gmm_ffn(rows, w_gate, w_up, w_down, row_scale, visits: Visits, *,
            layer=None, interpret=None, limit=None):
    """A SwiGLU expert a group over sorted rows, in ONE call: ``rows``
    [M, H], ``w_gate``/``w_up`` [G, H, F], ``w_down`` [G, F, H] (a leading
    layer axis with ``layer``), ``row_scale`` [M] float32 -> [M, H] float32:
    row ``r`` of group ``g`` is ``row_scale[r] * ((silu(rows[r] @ w_gate[g])
    * (rows[r] @ w_up[g])) @ w_down[g])``.  A group's three whole weights
    sit in VMEM across its row tiles, fetched once a group; the [tile, F]
    intermediate is never written.  Where the three do not fit
    (:func:`ffn_tiles`) a visit walks F in tiles on a second grid axis, a
    tile's three slabs fetched a step and the down products summed in a
    float32 VMEM scratch: the same five matmuls on the same bytes, read
    once a VISIT (``visits`` are then in tiles of ``ffn_tiles``' rows).
    float32 accumulation, gate and up rounded to ``rows``' type before
    SwiGLU, the down product and the scale in float32.  Rows as :func:`gmm`
    leaves them.  ``limit`` (static; None: none): a clamp inside the SwiGLU,
    ``silu(min(gate, limit)) * clip(up, -limit, limit)``, an elementwise
    pass over the same float32 tile."""
    H, F = w_gate.shape[-2:]
    tiles = ffn_tiles(H, F, rows.dtype.itemsize)
    if tiles is None:
        raise ValueError(f"no tile of whole lanes divides F = {F} and fits "
                         f"VMEM beside H = {H}")
    f_t = tiles[1]
    cut = f_t < F
    specs = [_weight_spec(w, layer, (axis, f_t) if cut else None)
             for w, axis in ((w_gate, 1), (w_up, 1), (w_down, 0))]
    more = dict(inner=(F // f_t,), scratch=[pltpu.VMEM(
        (visits.tile_rows, H), jnp.float32)]) if cut else {}
    kernel = _ffn_cut_kernel if cut else _ffn_kernel
    if limit is not None:
        kernel = functools.partial(kernel, limit=float(limit))
    return _over_visits(
        kernel, visits, specs[0][1],
        [rows, w_gate, w_up, w_down, _column(row_scale)],
        [H] + [spec for spec, _ in specs] + [1], [(H, jnp.float32)],
        interpret=interpret, **more)


def _down_back_kernel(group, tile, starts, ends, layer, a, w, dy, scale,
                      dot, dyw, da, *, tm):
    del layer
    _, mask, fresh = _visit(group, tile, starts, ends, tm)
    d = dy[...]
    y = lax.dot_general(a[...], w[...], _NN,
                        preferred_element_type=jnp.float32)
    weighted = (d * scale[...]).astype(dyw.dtype)
    _store(dot, jnp.sum(y * d, axis=1, keepdims=True), mask, fresh)
    _store(dyw, weighted, mask, fresh)
    _store(da, lax.dot_general(weighted, w[...], _NT,
                               preferred_element_type=jnp.float32),
           mask, fresh)


def gmm_down_back(a, w_down, dy, row_scale, visits: Visits, *, layer=None,
                  interpret=None):
    """The down-projection's backward over sorted rows, its result never
    written: with ``y = a @ w_down[g]`` ([M, F] x [G, F, H], float32) and
    ``dy`` [M, H] float32 the cotangent of ``row_scale * y``, returns
    (``sum(y * dy, -1)`` [M] float32: the scale's gradient; ``dyw = dy *
    row_scale`` [M, H] in ``a``'s type; ``dyw @ w_down[g]^T`` [M, F]
    float32: ``a``'s gradient).  Rows as :func:`gmm` leaves them."""
    F, H = a.shape[1], dy.shape[1]
    spec, layer_op = _weight_spec(w_down, layer)
    dot, dyw, da = _over_visits(
        _down_back_kernel, visits, layer_op,
        [a, w_down, dy, _column(row_scale)], [F, spec, H, 1],
        [(1, jnp.float32), (H, a.dtype), (F, jnp.float32)],
        interpret=interpret)
    return dot[:, 0], dyw, da


def _tgmm_kernel(group, tile, starts, ends, lhs, rhs, acc, out, sums, *, tm):
    v = pl.program_id(2)
    last = pl.num_programs(2) - 1
    g, t = group[v], tile[v]

    @pl.when((v == 0) | (group[jnp.maximum(v - 1, 0)] != g))
    def _():
        sums[...] = jnp.zeros_like(sums)

    mask = _row_mask(starts, ends, g, t, tm)
    a = jnp.where(mask, lhs[...], jnp.zeros_like(lhs))
    b = jnp.where(mask, rhs[...], jnp.zeros_like(rhs))
    sums[...] += lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)

    @pl.when((v == last) | (group[jnp.minimum(v + 1, last)] != g))
    def _():
        out[...] = acc[...] + sums[...]


def _cut(K: int, N: int, limit: int):
    """(tk, tn): the largest [tk, tn] block of a [K, N] sum within ``limit``
    elements, each side the whole dimension or a multiple of 128 dividing
    it."""
    def sides(d):
        return [d] + [s for s in range(128, d, 128) if d % s == 0]

    fits = [(tk * tn, tn, tk) for tk in sides(K) for tn in sides(N)
            if tk * tn <= limit]
    if not fits:
        return min(sides(K)), min(sides(N))
    _, tn, tk = max(fits)
    return tk, tn


def tgmm(lhs, rhs, visits: Visits, acc, *, interpret=None):
    """``lhs`` [M, K], ``rhs`` [M, N], rows sorted by group; ``acc``
    [G, K, N] float32 -> ``acc`` with ``lhs[rows of g]^T @ rhs[rows of g]``
    added to group ``g``'s slab, each slab summed in VMEM over its group's
    row tiles and written once.  ``acc`` is donated."""
    M, K = lhs.shape
    N = rhs.shape[1]
    tm = _tile_rows(M, visits.tile_rows)
    tk, tn = _cut(K, N, _TGMM_BLOCK)
    slab = pl.BlockSpec((None, tk, tn), lambda i, j, v, group, *_:
                        (group[v], i, j))
    call = pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct(acc.shape, jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda i, j, v, group, tile, *_:
                             (tile[v], i)),
                pl.BlockSpec((tm, tn), lambda i, j, v, group, tile, *_:
                             (tile[v], j)),
                slab,
            ],
            out_specs=slab,
            grid=(K // tk, N // tn, visits.count),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=auto_interpret(interpret))
    with jax.named_scope(SCOPE):
        return call(visits.group, visits.tile, visits.starts, visits.ends,
                    lhs, rhs, acc)
