"""The paged KV cache for decoder-LM serving.

:class:`PagedKVCache` holds fixed-size PAGES (``page_size`` tokens) in
device-resident pools ``[L, num_pages, page_size, kv_heads * head_dim]``, one
pair for each GROUP of cache layers the model's :class:`KVCacheSpec` states
(most models have one group; a model with window layers beside full ones has
two, and its window group keeps only the pages a future query can still
see), beside them the STATE LAYERS of a model whose spec states any (a
layer that remembers a sequence in a fixed-size array, not in rows a token:
a short convolution's last inputs, a state-space recurrence's matrix;
arrays ``[state layers, slots + 1, *shape]``, or ``[slots + 1, *shape]`` a
layer for each part a layer keeps, rows that a slot owns whole, never paged, never shared, read as zeros by the
chunk that starts a sequence: :class:`SlotStates`), and for a group whose
layers CHOOSE the positions a query reads (block-sparse attention by
compressed keys: ``KVCacheSpec.comp_stride``) a third pool of
COMPRESSED-KEY rows, ``page_size // comp_stride`` rows a page a cache layer
under the same page tables, so that allocation, release and copy-on-write
are the pages' own,
per-request page tables a group, refcounted PREFIX SHARING (hash-of-token-prefix
→ shared read-only pages, so identical system prompts across a pool's
traffic dedup to one physical copy) with copy-on-write on the first
divergent write, and an LRU prefix index whose pages are reclaimed under
pressure.  The vLLM/Gemma-on-TPU serving memory model (PAPERS.md, arXiv
2605.25645), under the jitted-step discipline of engine.py.  A sequence
holds the pages its tokens fill, not ``max_len`` of them.

It hands a slot to each admitted request and reclaims it on eviction —
finished sequences release their pages to queued requests immediately
(continuous batching, scheduler.py) instead of waiting for a static
batch to drain.

GQA-aware: the cache stores the model's ``num_kv_heads`` heads un-repeated
(half or a quarter of the MHA footprint for typical GQA configs);
``ops.decode_attention`` groups the query heads by the KV head they read.  Works for both
``GPTConfig`` (kv_heads == num_heads) and ``LlamaConfig``
(``num_kv_heads <= num_heads``).

The arrays are functionally updated inside the engine's jitted steps and
donated to them.  Donation alone did not make the update in place: through
PR 28 the two paged programs gathered every layer's pages into one view
before the model ran and scattered the new rows over a full leading axis
afterwards, and the TPU compiler turned both into relayouts of the whole
pool, seven copies of it a decode round (PERF.md, ledger of PR 28).  Since
PR 29 the pool travels through the model's layer scan as a carry
(:class:`PagedLayers`): a layer gathers its own pages and scatters its own
new rows, and the compiled programs hold nothing of the pool's size but the
pool (checked in the HLO compiled for a described v5e,
``benchmarks/tools/compile_v5e_serve.py --hlo``, in the jaxpr by
``tests/paged_programs.py``, and on the chip by the ledger's
``breakdown.device_ops``).  Since PR 33 a decode round gathers no view of a
layer that keeps every position either: ONE Pallas kernel walks each
sequence's page table in the pool where it lies, as far as the sequence is
long (:meth:`PagedLayers.attend`, ``ops.decode_layer_attention``); the chunk
programs and a window group's ring still read a layer's view
(:meth:`PagedLayers.read`).  The allocator owns the slot lifecycle and the
per-slot host-side lengths.
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from hetu_tpu.ops.pallas_kernels.paged_attention import paged_decode_attention


@dataclass(frozen=True)
class KVCacheSpec:
    """Per-layer cache geometry, derived from a model config.

    The two pools need not be of one width: ``head_dim`` is the K pool's,
    ``v_head_dim`` the V pool's (None: the same).  A latent-attention model
    keeps its normalised latent in the K pool and its rotated shared key in
    the V pool, one "head" each.  ``num_layers`` counts CACHE layers, which
    a model with two attention blocks a layer has twice as many of.

    **Groups.**  A model whose cache layers are not all of one kind states
    the further kinds under ``also``, each a spec of its own (its cache
    layers, its row shapes, its ``window``): :attr:`groups` is this spec
    followed by them, and the cache holds a pool pair and a page table a
    slot for each.  ``window``: the layers of the group see the last
    ``window`` positions only (a query at ``i`` the keys ``j`` with ``0 <=
    i - j < window``), so the cache keeps the pages a future query can still
    see and drops the rest as a slot advances; None: every position.  The
    first group is the one whose tables grow with the sequence: the
    engine's page buckets and ``pages_per_slot`` are its.

    **State layers.**  A third kind of slot state, beside the pages that
    keep every position and a window group's ring: ``state_layers`` layers
    each remember a sequence in arrays of fixed shape, whatever the
    sequence's length, so they cost :attr:`bytes_per_slot` a slot and
    nothing a token.  A layer that keeps ONE array states ``state_shape``
    (in ``state_dtype``; None: ``dtype``); one that keeps SEVERAL states
    ``state_parts``, each ``(name, shape, dtype)`` (a convolution's last
    rows in the compute type beside a recurrence's float32 matrix), and
    every state layer keeps every part, unless a part states a fourth
    value, HOW MANY layers keep it (the open group of a row-selecting
    layer's pooled keys: a state on the layers that hold paged rows, not on
    the ``state_layers`` that hold none; :attr:`part_layers`).  :attr:`parts`
    reads either form.
    The cache holds them as rows a slot owns whole (:class:`SlotStates`):
    one array ``[state layers, slots + 1, *state_shape]`` where the spec
    states ``state_shape``; where it states ``state_parts`` a tuple, in the
    parts' order, of tuples of the layers' arrays ``[slots + 1, *shape]``;
    only the spec a model hands over states them (a group under ``also``
    has none of its own).

    **Compressed rows.**  ``comp_stride``: the group's layers keep, beside K
    and V rows a token, one COMPRESSED-KEY row every ``comp_stride`` tokens
    a cache layer (the mean of a window of K rows that starts there; what a
    block-sparse layer scores its blocks by), of K's row shape and dtype.
    They live in a third pool of ``page_size // comp_stride`` rows a page
    under the group's own page tables (compressed row ``i`` of a sequence
    is row ``i % rows`` of its page ``i // rows``), so a page's allocation,
    release and copy carry them; ``page_size`` must be a multiple of the
    stride.  None: no such rows.  ``comp_dim``: such a row's width where it
    is not K's (a lightning indexer's pooled keys beside latent rows; the
    rows are then read flat, ``[B, n, comp_dim]``).

    ``v_head_dim`` 0: the layers keep ONE array a token (a latent that is
    key and value both); the V pool is then of no width, and the programs
    carry it empty.

    ``whole_tables``: a decode round takes every slot's WHOLE page table,
    whatever the histories.  For a model whose layers read a fixed number of
    CHOSEN rows a round (and a few bytes a token of what they choose by), a
    shorter table saves no read worth a program: the engine then compiles
    one decode program a slot bucket, not one a (slot, page) bucket pair."""

    num_layers: int
    num_kv_heads: int
    head_dim: int
    dtype: object = jnp.float32
    v_head_dim: Optional[int] = None
    window: Optional[int] = None
    also: tuple = ()
    state_layers: int = 0
    state_shape: tuple = ()
    state_dtype: object = None
    state_parts: tuple = ()
    comp_stride: Optional[int] = None
    comp_dim: Optional[int] = None
    whole_tables: bool = False

    @property
    def comp_width(self) -> int:
        """Width of a compressed row: ``comp_dim``, or K's."""
        return self.num_kv_heads * self.head_dim if self.comp_dim is None \
            else int(self.comp_dim)

    @property
    def part_layers(self) -> tuple:
        """How many layers keep each of :attr:`parts`: ``state_layers``
        unless the part states its own count."""
        if self.state_layers and self.state_parts:
            return tuple(int(p[3]) if len(p) > 3 else self.state_layers
                         for p in self.state_parts)
        return (self.state_layers,) * len(self.parts)

    @property
    def parts(self) -> tuple:
        """((name, shape, dtype), ...) of what ONE state layer keeps of a
        slot: ``state_parts`` as stated, or the one array ``state_shape``
        under the name ``state``; empty without state layers."""
        if not self.state_layers:
            return ()
        if self.state_parts:
            return tuple((str(p[0]), tuple(p[1]), np.dtype(p[2]))
                         for p in self.state_parts)
        return (("state", tuple(self.state_shape),
                 np.dtype(self.state_dtype or self.dtype)),)

    @property
    def part_bytes_per_slot(self) -> dict:
        """By part's name, the bytes one slot's state takes in it over the
        state layers."""
        return {n: layers * int(np.prod(sh, dtype=int)) * dt.itemsize
                for (n, sh, dt), layers in zip(self.parts, self.part_layers)}

    @property
    def groups(self) -> tuple:
        """Every group's own spec, this one's first."""
        return (replace(self, also=()),) + tuple(self.also) \
            if self.also else (self,)

    @property
    def bytes_per_slot(self) -> int:
        """Bytes one slot's state takes over the state layers, every part,
        whatever the sequence's length; 0 for a model with none."""
        return sum(self.part_bytes_per_slot.values())

    @property
    def v_dim(self) -> int:
        """Width of the V pool's rows."""
        return self.head_dim if self.v_head_dim is None else self.v_head_dim

    def row_shapes(self) -> tuple:
        """((heads, K width), (heads, V width)) of one token's rows."""
        return ((self.num_kv_heads, self.head_dim),
                (self.num_kv_heads, self.v_dim))

    @property
    def bytes_per_token(self) -> int:
        """Bytes one cached token takes over this group's cache layers:
        its K and V rows and its share of a compressed row."""
        row = self.num_layers * self.num_kv_heads \
            * np.dtype(self.dtype).itemsize
        comp = self.num_layers * self.comp_width \
            * np.dtype(self.dtype).itemsize // self.comp_stride \
            if self.comp_stride else 0
        return row * (self.head_dim + self.v_dim) + comp

    def ring_pages(self, rows: int, page_size: int) -> Optional[int]:
        """Pages a slot of a window group holds at most while a step writes
        ``rows`` new rows: the window behind the first of them, the rows
        themselves, and one page more because neither end need fall on a
        page's edge.  The fixed width of the group's table in a program;
        None for a group that keeps every position."""
        if self.window is None:
            return None
        return -(-(self.window - 1 + int(rows)) // page_size) + 1

    @staticmethod
    def from_model(model) -> "KVCacheSpec":
        """A model that states its own cache (``kv_cache_spec()``) is
        asked; otherwise the geometry is read off a GPTModel/LlamaModel
        config: models with ``num_kv_heads`` are GQA (cache the un-repeated
        heads); the rest cache all ``num_heads``."""
        own = getattr(model, "kv_cache_spec", None)
        if own is not None:
            return own()
        c = model.c
        nkv = getattr(c, "num_kv_heads", None) or c.num_heads
        return KVCacheSpec(
            num_layers=c.num_layers, num_kv_heads=nkv,
            head_dim=c.hidden_size // c.num_heads, dtype=c.dtype)


@dataclass
class KVSlotSnapshot:
    """One live cache slot lifted onto the host for migration.

    ``k``/``v`` are ``[num_layers, length, kv_heads, head_dim]`` numpy
    arrays truncated to the slot's live ``length`` (never ``max_len`` —
    migration cost must scale with what is actually cached), in the
    source cache's dtype.  ``slot`` is the SOURCE slot id (import
    returns a mapping from it to the adopting cache's slot).  ``meta``
    carries engine-level per-slot state (the last emitted token) and any
    future sampler state — opaque to the cache itself.
    """

    slot: int
    length: int
    k: np.ndarray
    v: np.ndarray
    meta: dict = field(default_factory=dict)

    @property
    def nbytes(self) -> int:
        return self.k.nbytes + self.v.nbytes


# ---------------------------------------------------------------------------
# paged allocation + prefix sharing
# ---------------------------------------------------------------------------

def pow2_ceil(n: int, cap: int) -> int:
    """Smallest power of two >= n, clamped to [1, cap] — the ONE
    bucketing helper the paged engine's executables key on (chunk
    widths, decode batch, page counts, import pads)."""
    b = 1
    while b < n:
        b *= 2
    return max(min(b, cap), 1)


@partial(jax.tree_util.register_dataclass,
         data_fields=("pool", "tables", "wpage", "woff", "comp"),
         meta_fields=("row", "sharded"))
@dataclass(frozen=True)
class PagedLayers:
    """One pool of a paged cache as a jitted step hands it to the model's
    cache entry points, in the place of a dense ``[L, B, T, *row]`` cache:
    the pool itself with the step's page tables and write map, ALL the page
    arithmetic of the two paged programs.

    ``pool`` ``[L, num_pages, page_size, width]`` (rows flat, as
    :class:`PagedKVCache` holds them); ``tables`` ``[B, n_pg]`` int32, each
    sequence's pages in order (scratch-padded); ``wpage`` / ``woff``
    ``[B, S]`` int32, where the host's write map puts each of the step's
    ``S`` new rows a sequence (pad rows: scratch page 0); ``row`` the shape
    ``(heads, head width)`` the model sees a row in (static); ``sharded``
    whether the engine laid the pool over a mesh (static: a traced pool does
    not say; the one-query step then keeps the view, which the partitioner
    splits by head, and does not hand a split pool to :meth:`attend`'s
    kernel, which it cannot split); ``comp`` ``[L, num_pages, page_size //
    stride, width]``, the K pool's COMPRESSED rows where the group keeps
    them (``KVCacheSpec.comp_stride``; None elsewhere, and on the V pool),
    read and written through the same tables (:meth:`read_comp`,
    :meth:`write_comp`).

    The model carries the value through its layer scan
    (``ops.scan_cached_layers``, ``ops.scan_layers_over_caches``); a layer reads
    its own pages and writes its own new rows, so nothing of the pool's
    size, and no view of every layer, is ever made: the pool is a loop carry
    of a donated argument, the row scatter updates it in place, and a decode
    round's attention reads it in place (:meth:`attend`)."""

    pool: jax.Array
    tables: jax.Array
    wpage: jax.Array
    woff: jax.Array
    row: tuple
    sharded: bool = False
    comp: Optional[jax.Array] = None

    @classmethod
    def over(cls, pool, tables, wpage, woff, row, sharded: bool = False):
        """The value over ``pool`` as a program is handed it: the pool's
        array, or the pair (pool, compressed rows) of a group that keeps
        them (:func:`PagedKVCache.pool_args`)."""
        pool, comp = pool if isinstance(pool, (tuple, list)) else (pool, None)
        return cls(pool, tables, wpage, woff, row, sharded, comp)

    def held(self):
        """What a program hands back of this value: :meth:`over`'s
        ``pool``."""
        return self.pool if self.comp is None else (self.pool, self.comp)

    def read(self, layer, tables=None):
        """Cache layer ``layer`` of every sequence, ``[B, n_pg * page_size,
        *row]``: one gather of that layer's pages by the tables (or by
        ``tables`` ``[B', n]`` in their place: the pages a query chose), on
        the pool's two major axes."""
        tables = self.tables if tables is None else tables
        pages = self.pool[layer, tables]           # [B, n_pg, ps, width]
        b, n_pg, ps = pages.shape[:3]
        return pages.reshape((b, n_pg * ps) + tuple(self.row))

    def read_comp(self, layer, row=None):
        """The compressed rows of cache layer ``layer`` of every sequence,
        ``[B, n_pg * rows a page, *row]``, by the same tables; ``row``: the
        shape the caller keeps such a row in where it is not K's
        (``KVCacheSpec.comp_dim``)."""
        pages = self.comp[layer, self.tables]      # [B, n_pg, rpp, width]
        b, n_pg, rpp = pages.shape[:3]
        return pages.reshape((b, n_pg * rpp)
                             + tuple(self.row if row is None else row))

    def write_comp(self, layer, rows, index, valid):
        """Compressed rows ``rows`` ``[B, n, *row]`` (or flat) of cache
        layer ``layer``, row ``j`` of sequence ``b`` the sequence's
        compressed row ``index[b, j]``: into row ``index % rows a page`` of
        the table's page ``index // rows a page``; a row that is not
        ``valid`` ``[B, n]`` (a window no real token has completed yet)
        lands in the scratch page 0."""
        rpp = self.comp.shape[2]
        rows = rows.reshape(rows.shape[:2] + self.comp.shape[3:])
        at = jnp.clip(index // rpp, 0, self.tables.shape[1] - 1)
        page = jnp.where(valid, jnp.take_along_axis(self.tables, at, 1), 0)
        return replace(self, comp=self.comp.at[layer, page, index % rpp].set(
            rows.astype(self.comp.dtype)))

    def write(self, layer, rows):
        """The step's new rows ``[B, S, *row]`` (or flat, ``[B, S,
        width]``) of cache layer ``layer``, scattered through the write
        map."""
        rows = rows.reshape(rows.shape[:2] + self.pool.shape[3:])
        return replace(
            self, pool=self.pool.at[layer, self.wpage, self.woff].set(rows))

    def attend(self, values, layer, q, lengths, *, scale=None, tables=None):
        """The one-query step of a decode round over cache layer ``layer``,
        this pool the keys' and ``values`` the other pool of the pair (the
        same tables): q ``[B, heads, 1, D]`` attends over positions ``<=
        lengths[b]`` of each sequence, whose newest row is already written
        (:meth:`write`).  ONE Pallas kernel walks the tables in the pools
        where they lie, a sequence's live pages and no more
        (``ops.pallas_kernels.paged_attention``): no view is gathered.
        ``tables`` ``[B, n]`` in the tables' place: the pages each query
        chose, in the order it walks them, ``lengths`` then counted along
        THAT walk.  Returns ``[B, heads, 1, Dv]``."""
        return paged_decode_attention(
            q, self.pool, values.pool, layer,
            self.tables if tables is None else tables, lengths,
            kv_heads=self.row[0], scale=scale)


@partial(jax.tree_util.register_dataclass,
         data_fields=("rows", "slots", "fresh"), meta_fields=())
@dataclass(frozen=True)
class SlotStates:
    """The state layers of a cache as a jitted step hands them to the model's
    cache entry points (their ``state=`` argument, handed back as their last
    result): ``rows`` ``[state layers, num_slots + 1, *state_shape]``, the
    cache's array itself (donated, carried through the layers, updated in
    place), or where a state layer keeps several parts
    (``KVCacheSpec.state_parts``) a tuple, one a part, each a tuple of the
    LAYERS' arrays ``[num_slots + 1, *shape]``; ``slots`` ``[B]`` int32, the slot of each of the step's
    sequences, a bucket's padding row naming the SCRATCH slot ``num_slots``,
    which no request owns; ``fresh`` ``[B]`` bool, or None in a decode round:
    the sequence starts in this step (a chunk at position 0), so its state
    READS as zeros whatever the slot's last owner left there, in every
    part.  A state layer of the model reads its own layer's rows and writes
    them back; what it writes is the state after the step's last REAL token
    (a chunk is padded to its bucket: the model's ``last_index`` says where
    that is).

    Two ways to a part's rows.  BY SEQUENCE (:meth:`read`, :meth:`write`):
    the step's sequences' rows gathered by slot and scattered back; one
    sequence's row (a chunk) is cut out and put back where it lies, but the
    rows of MANY are a gather, which the TPU's compiler runs over a copy of
    the whole array, so this is for parts that are small (a convolution's
    last rows).  A LAYER WHOLE (:meth:`whole`, :meth:`put_whole`), for a
    decode round over a part that is large (a recurrence's matrix, 4 MB a
    slot a layer): the model computes every slot's row at once, its small
    per-sequence inputs laid out by slot (:meth:`spread`; a slot of no
    sequence of the step gets zeros, and the model's update must leave such
    a row as it is) and its per-slot results read back by sequence
    (:meth:`pick`); the update is one elementwise pass over the layer's
    array in place, each row read and written once.  That is why the parts
    of a ``state_parts`` spec are held a layer an array: a layer's rows
    rewritten whole inside ONE array over the layers is a chain of in-place
    updates of 1.6 GB values, which the TPU compiler's rematerialisation,
    blind to the aliasing, computed twice (PR 47, ``PERF.md`` section 6)."""

    rows: object
    slots: jax.Array
    fresh: Optional[jax.Array] = None

    @property
    def real(self):
        """[B] bool: the step's rows that are sequences; a bucket's padding
        row names the scratch slot, the arrays' last."""
        one = self.rows
        while isinstance(one, tuple):
            one = one[0]
        slots = one.shape[0 if isinstance(self.rows, tuple) else 1]
        return self.slots < slots - 1

    def _part(self, part):
        return self.rows if part is None else self.rows[part]

    def _with(self, part, new):
        return replace(self, rows=new if part is None else
                       self.rows[:part] + (new,) + self.rows[part + 1:])

    def _read(self, held, layer):
        # one array over the layers, or a layer an array
        rows = held[layer][self.slots] if isinstance(held, tuple) \
            else held[layer, self.slots]
        if self.fresh is None:
            return rows
        fresh = self.fresh.reshape((-1,) + (1,) * (rows.ndim - 1))
        return jnp.where(fresh, 0, rows)

    def _write(self, held, layer, new):
        if isinstance(held, tuple):
            one = held[layer]
            return held[:layer] + (one.at[self.slots].set(
                new.astype(one.dtype)),) + held[layer + 1:]
        return held.at[layer, self.slots].set(new.astype(held.dtype))

    def read(self, layer, part=None):
        """State layer ``layer`` of the step's sequences, ``[B,
        *state_shape]``; a tuple of them, one a part, where ``rows`` is, or
        of these the part of index ``part`` alone."""
        if part is None and isinstance(self.rows, tuple):
            return tuple(self._read(held, layer) for held in self.rows)
        return self._read(self._part(part), layer)

    def write(self, layer, rows, part=None):
        """The step's sequences' new state ``[B, *state_shape]`` of state
        layer ``layer`` (a tuple of them, one a part, where ``rows`` is; the
        part of index ``part`` alone where given), each into its own slot
        (padding rows all into the scratch slot, where the last one written
        stays and nothing reads it)."""
        if part is None and isinstance(self.rows, tuple):
            return replace(self, rows=tuple(
                self._write(held, layer, new)
                for held, new in zip(self.rows, rows)))
        return self._with(part, self._write(self._part(part), layer, rows))

    def whole(self, layer, part):
        """Every slot's row of part ``part`` of state layer ``layer``,
        ``[num_slots + 1, *shape]``, the scratch slot's last."""
        return self.rows[part][layer]

    def put_whole(self, layer, part, rows):
        """:meth:`whole`'s rows, every slot's, in the layer's place: the
        layer's own array, so a program that computed them from
        :meth:`whole`'s writes them where it read them."""
        held = self.rows[part]
        return self._with(part, held[:layer] + (
            rows.astype(held[layer].dtype),) + held[layer + 1:])

    def spread(self, values, like):
        """Per-sequence ``values`` ``[B, ...]`` laid out by slot beside
        :meth:`whole`'s rows ``like``, ``[num_slots + 1, ...]``: zeros at
        every slot of no sequence of the step."""
        return jnp.zeros(like.shape[:1] + values.shape[1:], values.dtype) \
            .at[self.slots].set(values)

    def pick(self, values):
        """Per-slot ``values`` ``[num_slots + 1, ...]`` of the step's
        sequences, ``[B, ...]``."""
        return values[self.slots]


class PagePoolExhausted(RuntimeError):
    """The page pool has no free page and nothing reclaimable.

    Raised from :meth:`PagedKVCache._alloc_page` — reachable at DECODE
    time only by a slot decoding past its reservation, i.e. an adopted
    (migrated-in) slot, whose import allocates its live pages but
    reserves nothing for the decode ahead.  A distinct type so the
    scheduler can catch exactly this and preempt-and-requeue a victim
    (vLLM recompute-mode preemption) instead of killing the engine
    loop."""


class GroupedCacheNotPortable(RuntimeError):
    """A cache of more than one group, or one with state layers, was asked
    to export or import live slots.  The wire form (:class:`KVSlotSnapshot`,
    ``serve/migrate.py``) is one ``[layers, length, heads, width]`` pair a
    slot; a window group's slot holds the rows of its last pages only, under
    another layer count, and a state layer's memory of the sequence is in no
    page at all: no peer could adopt either through that form, and pages
    without their state would continue to wrong tokens.  Such a cache's
    requests move by re-prefill, not by their rows:
    ``ContinuousBatchingScheduler.export_inflight_with_slots`` catches this
    error and hands the requests over folded (``slot=None``, no snapshot),
    so a drain or a planned migration completes at a prefill a request."""


@dataclass
class _PrefixEntry:
    """One cached token-prefix: ``pages`` hold the K/V of the first
    ``n_tokens`` tokens whose sha256 is ``key``: for each group ``(first,
    pages)``, the pages of logical indices ``first, first + 1, ...`` (a
    group that keeps every position: all of them from 0; a window group:
    those a continuation from ``n_tokens - 1`` on can still see).  Entries
    hold an INDEX reference on each page (``ref_index``); pages referenced
    only by the index are reclaimable under pressure (LRU eviction)."""

    key: bytes
    pages: tuple
    n_tokens: int


class _PageGroup:
    """One group of cache layers in a :class:`PagedKVCache`: its pool pair,
    its pages' two refcounts and free list, and each slot's page table and
    reservation.  ``tables[slot]`` are the slot's pages of logical indices
    ``base[slot], base[slot] + 1, ...`` (logical page ``i`` holds positions
    ``[i * page_size, (i + 1) * page_size)``): ``base`` stays 0 in a group
    that keeps every position and advances in a window group as pages fall
    behind the window."""

    def __init__(self, spec: KVCacheSpec, num_slots: int, num_pages: int,
                 page_size: int, sharding):
        if num_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is scratch)")
        self.spec = spec
        self.window = spec.window
        self.num_pages = int(num_pages)
        k_row, v_row = spec.row_shapes()
        lead = (spec.num_layers, self.num_pages, page_size)
        self.k = jnp.zeros(lead + (int(np.prod(k_row)),), spec.dtype)
        self.v = jnp.zeros(lead + (int(np.prod(v_row)),), spec.dtype)
        self.comp = None         # compressed-key rows under the same pages
        if spec.comp_stride:
            if sharding is not None or page_size % spec.comp_stride:
                raise ValueError(
                    f"compressed rows every {spec.comp_stride} tokens need "
                    f"pages of a multiple of that ({page_size}) and no mesh")
            self.comp = jnp.zeros(
                lead[:2] + (page_size // spec.comp_stride, spec.comp_width),
                spec.dtype)
        if sharding is not None:
            self.k = jax.device_put(self.k, sharding)
            self.v = jax.device_put(self.v, sharding)
        self.tables: list = [[] for _ in range(num_slots)]
        self.base = np.zeros(num_slots, np.int64)
        self.ref_table = np.zeros(self.num_pages, np.int32)
        self.ref_index = np.zeros(self.num_pages, np.int32)
        # LIFO: recently-touched pages stay hot.
        # Page 0 excluded — the scratch page is never allocated.
        self.free_pages = list(range(self.num_pages - 1, 0, -1))
        self.reserve = np.zeros(num_slots, np.int32)
        # a window group's claim is of pages held AT ONCE: a page dropped
        # from behind the window gives its claim back, up to this many
        self.reserve_cap = np.zeros(num_slots, np.int32)
        self.released = 0        # pages dropped from behind a window
        self.copy_fn = None      # lazily jitted page copy (COW)

    @property
    def pages_in_use(self) -> int:
        return self.num_pages - 1 - len(self.free_pages)

    @property
    def reclaimable_pages(self) -> int:
        return int(np.sum((self.ref_table == 0) & (self.ref_index > 0)))

    def available_pages(self) -> int:
        return (len(self.free_pages) + self.reclaimable_pages
                - int(self.reserve.sum()))

    def unref_table(self, page: int) -> None:
        self.ref_table[page] -= 1
        if self.ref_table[page] < 0:
            raise AssertionError(f"page {page} table-ref underflow")
        if self.ref_table[page] == 0 and self.ref_index[page] == 0:
            self.free_pages.append(page)

    def unref_index(self, page: int) -> None:
        self.ref_index[page] -= 1
        if self.ref_table[page] == 0 and self.ref_index[page] == 0:
            self.free_pages.append(page)

    def clear_slot(self, slot: int) -> None:
        for page in self.tables[slot]:
            self.unref_table(page)
        self.tables[slot] = []
        self.base[slot] = 0
        self.reserve[slot] = self.reserve_cap[slot] = 0

    def release_behind(self, slot: int, next_position: int,
                       page_size: int) -> None:
        """Drop the slot's table reference to every page that lies wholly
        behind the window of every query from ``next_position`` on (its
        last position ``<= next_position - window``): freed, unless the
        prefix index holds it."""
        keep_from = max(next_position - self.window + 1, 0) // page_size
        table = self.tables[slot]
        n = min(max(keep_from - int(self.base[slot]), 0), len(table))
        for page in table[:n]:
            self.unref_table(page)
        del table[:n]
        self.base[slot] = keep_from if not table else self.base[slot] + n
        self.released += n
        self.reserve[slot] = min(self.reserve[slot] + n,
                                 self.reserve_cap[slot])

    def device_table(self, slot: int, width: int) -> np.ndarray:
        """The slot's table as a program takes it, ``width`` columns
        (scratch-padded).  A group that keeps every position: its first
        ``width`` pages in order.  A window group: a RING, logical page
        ``i`` in column ``i % width``, so that the gathered view's row ``r``
        holds the position ``p = r (mod width * page_size)`` that was
        written last."""
        out = np.zeros(width, np.int32)
        table = self.tables[slot]
        if self.window is None:
            t = table[:width]
            out[:len(t)] = t
            return out
        if len(table) > width:
            raise AssertionError(
                f"slot {slot} holds {len(table)} window pages, the "
                f"program's ring has {width}")
        for i, page in enumerate(table):
            out[(int(self.base[slot]) + i) % width] = page
        return out


class PagedKVCache:
    """Paged K/V pools + per-slot page tables + refcounted prefix sharing,
    for each GROUP of cache layers the spec states (``spec.groups``; most
    models have one).

    A group's ``k``/``v``: ``[L, num_pages, page_size, kv_heads *
    head_dim]`` jax arrays over that group's cache layers, replaced
    wholesale by the engine after each jitted step.  A
    token's row is held FLAT: with a ``[kv_heads, head_dim]`` minor pair of
    (20, 64) the TPU's own layout of the array put the PAGE axis in the
    lanes (least padding), and every gather or scatter by page then cost a
    relayout of the whole pool, before and after the layer loop; 1280 flat
    is ten whole lane tiles, the pool keeps the order it is declared in and
    a page is one contiguous block (compiles for a described v5e, PR 29).
    Page 0 is a reserved SCRATCH page: jitted steps run over every slot
    with fixed shapes, and inactive slots' (masked, garbage) writes need
    a harmless landing zone — page 0 is never allocated to a request.

    ``k``, ``v``, ``tables``, ``num_pages``, ``pages_per_slot``,
    ``ref_table``, ``ref_index`` and ``_reserve`` are the FIRST group's: the
    one whose tables grow with the sequence, and the only one of a model
    with one kind of cache layer, for which this class is what it was
    before groups.  A WINDOW group (``spec.window``) keeps, for each slot,
    the pages that a query at the slot's next position or later can still
    see, and the pages of the step being written; :meth:`prepare_write`
    drops the table's reference to the rest as the slot advances
    (``window_released``).  Its table in a program is a ring of fixed
    width (:meth:`KVCacheSpec.ring_pages`), so the programs' shapes follow
    the first group's page count alone.

    STATE LAYERS (``spec.state_layers``; :attr:`state`, None without any):
    one array ``[state layers, num_slots + 1, *state_shape]``, or for a spec
    of ``state_parts`` a tuple, one a part, of tuples of the layers' arrays
    ``[num_slots + 1, *shape]``, that the
    engine's two programs take donated beside the pools and hand back
    (:class:`SlotStates`, :meth:`update`).  Row ``slot`` is that slot's and
    nobody else's: never paged, never shared, in no prefix entry (so the
    engine takes no prefix match over such a cache, and this class is built
    with no index).  Row ``num_slots`` is SCRATCH, where a decode bucket's
    padding rows write.  :meth:`alloc` does nothing about it: a slot's
    state is DEFINED as zeros at position 0, and the chunk that starts at 0
    reads zeros in the program (``SlotStates.fresh``).  No stale state can
    be read: the only other readers are a chunk at ``start > 0``, which
    follows this request's own earlier chunk in this slot (a preempted
    request prefills again from 0), and a decode round, which runs a slot
    only after its last chunk; a prefix match, the one way a first chunk
    starts past 0, is off.  The bytes count in :attr:`state_bytes`.

    Ownership model: each page carries two refcounts — ``ref_table``
    (how many slot page-tables reference it) and ``ref_index`` (how many
    prefix-index entries do).  A page is WRITABLE by a slot only when it
    is that slot's sole reference (``ref_table == 1 and ref_index ==
    0``); any write into a shared page copies it first (copy-on-write,
    counted in ``cow_copies``), so indexed prefix pages are immutable
    and a forked request can never corrupt its sibling's (or the
    cache's) prefix.  A page returns to the free list when BOTH counts
    reach zero; eviction of LRU index entries under allocation pressure
    is what turns "referenced only by the index" into free pages.  A
    prefix entry holds pages of EVERY group (of a window group those of
    the prefix's last ``window`` positions), and is made only where every
    group still holds them, so a hit always finds what the continuation
    reads.

    Reservations: :meth:`reserve`/``reserved_remaining`` implement the
    scheduler's page-budget backpressure — an admission reserves the
    worst-case pages its request can touch in each group (prompt +
    generation + one COW; of a window group the ring + one COW), and
    :meth:`available_pages` nets a group's free + reclaimable pages
    against outstanding reservations so admissions cannot oversubscribe
    a pool out from under running decodes.
    """

    def __init__(self, spec: KVCacheSpec, num_slots: int, max_len: int, *,
                 page_size: int = 16, num_pages=None, sharding=None,
                 max_prefix_entries: int = 256, step_rows: int = 1):
        if num_slots < 1 or max_len < 2:
            raise ValueError(f"need >=1 slot and max_len >= 2, got "
                             f"{num_slots}/{max_len}")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.spec = spec
        self.num_slots = int(num_slots)
        self.max_len = int(max_len)
        self.page_size = int(page_size)
        self.pages_per_slot = -(-self.max_len // self.page_size)  # ceil
        if num_pages is None:
            # default: every slot can reach max_len at once, plus the
            # scratch page
            num_pages = 1 + self.num_slots * self.pages_per_slot
        # ``step_rows``: the most rows one step writes a slot (the engine's
        # prefill chunk), which sizes a window group's ring and pool: every
        # slot its ring and one copy-on-write, plus the scratch page
        self.groups = []
        for g in spec.groups:
            ring = g.ring_pages(step_rows, self.page_size)
            self.groups.append(_PageGroup(
                g, self.num_slots,
                int(num_pages) if ring is None
                else 1 + self.num_slots * (min(ring, self.pages_per_slot)
                                           + 1),
                self.page_size, sharding))
        self.state = None
        if spec.state_layers:
            if sharding is not None:
                raise ValueError("state layers over a mesh are not laid out")
            # one row more than slots: the scratch slot of padding rows
            rows = self.num_slots + 1
            if spec.state_parts:    # a part a tuple of its layers' arrays
                self.state = tuple(
                    tuple(jnp.zeros((rows,) + shape, dtype)
                          for _ in range(layers))
                    for (_, shape, dtype), layers
                    in zip(spec.parts, spec.part_layers))
            else:
                (_, shape, dtype), = spec.parts
                self.state = jnp.zeros((spec.state_layers, rows) + shape,
                                       dtype)
            max_prefix_entries = 0   # an entry would need the state AT its
            #                          boundary, which nobody keeps
        self.lengths = np.zeros(self.num_slots, np.int32)
        self._free_slots = list(range(self.num_slots - 1, -1, -1))
        self.max_prefix_entries = int(max_prefix_entries)
        from collections import OrderedDict
        self._prefix: "OrderedDict[bytes, _PrefixEntry]" = OrderedDict()
        # host-side counters the engine mirrors into ServeMetrics
        self.cow_copies = 0
        self.prefix_hit_tokens = 0
        self.prefix_evictions = 0
        self._import_fn = None   # lazily jitted page writer (import_slots)

    # ---- the first group's books, under the names they had ----
    k = property(lambda self: self.groups[0].k)
    v = property(lambda self: self.groups[0].v)
    tables = property(lambda self: self.groups[0].tables)
    num_pages = property(lambda self: self.groups[0].num_pages)
    ref_table = property(lambda self: self.groups[0].ref_table)
    ref_index = property(lambda self: self.groups[0].ref_index)
    _reserve = property(lambda self: self.groups[0].reserve)

    # ---- geometry helpers ----
    def pages_for_tokens(self, n: int) -> int:
        return -(-int(n) // self.page_size)

    @property
    def num_free(self) -> int:
        """Free REQUEST slots (admission gate)."""
        return len(self._free_slots)

    @property
    def pages_in_use(self) -> int:
        return sum(g.pages_in_use for g in self.groups)

    @property
    def reclaimable_pages(self) -> int:
        """Pages held only by the prefix index — allocatable after an
        LRU eviction, so admission counts them as available."""
        return sum(g.reclaimable_pages for g in self.groups)

    def available_pages(self, group: int = 0) -> int:
        """Pages of ``group`` an admission may still claim: free +
        reclaimable, net of every running slot's outstanding
        reservation."""
        return self.groups[group].available_pages()

    @property
    def occupancy(self) -> float:
        return self.pages_in_use / max(
            sum(g.num_pages - 1 for g in self.groups), 1)

    @property
    def state_bytes(self) -> int:
        """Bytes the state layers' arrays take on the device (every slot's
        and the scratch row's, every part), 0 without state layers."""
        return sum(int(a.nbytes)
                   for a in jax.tree_util.tree_leaves(self.state))

    @property
    def window_released(self) -> int:
        """Pages dropped from behind a window so far, all window groups."""
        return sum(g.released for g in self.groups)

    def held_layer_pages(self, lengths=None) -> tuple:
        """What the allocated slots' tables hold, in pages of ONE cache
        layer: (in the groups that keep every position, in the window
        groups, what the same slots would hold at ``lengths`` (default:
        their own) were every cache layer of every group to keep every
        position)."""
        if lengths is None:
            lengths = self.lengths
        live = [s for s in range(self.num_slots)
                if s not in self._free_slots]
        full = window = 0
        for g in self.groups:
            n = sum(len(g.tables[s]) for s in live) * g.spec.num_layers
            if g.window is None:
                full += n
            else:
                window += n
        layers = sum(g.spec.num_layers for g in self.groups)
        return full, window, layers * sum(
            self.pages_for_tokens(lengths[s]) for s in live)

    # ---- slot lifecycle ----
    def alloc(self) -> int:
        if not self._free_slots:
            raise RuntimeError("paged KV cache has no free slots")
        slot = self._free_slots.pop()
        self.lengths[slot] = 0
        for g in self.groups:
            g.clear_slot(slot)      # a freed slot's books are empty already
        return slot

    def free(self, slot: int) -> None:
        if not 0 <= slot < self.num_slots:
            raise ValueError(f"slot {slot} out of range")
        if slot in self._free_slots:
            raise ValueError(f"slot {slot} double-freed")
        for g in self.groups:
            g.clear_slot(slot)
        self.lengths[slot] = 0
        self._free_slots.append(slot)

    def reserve(self, slot: int, n_pages) -> None:
        """Record the admission's worst-case page claim for ``slot``, one
        number a group (a bare number: the first group's); every page the
        slot later allocates draws it down."""
        if isinstance(n_pages, (int, np.integer)):
            n_pages = (n_pages,)
        for g, n in zip(self.groups, n_pages):
            g.reserve[slot] = g.reserve_cap[slot] = max(int(n), 0)

    def update(self, k, v, state=None) -> None:
        """Swap in the pool arrays a jitted step returned: one pair, or a
        sequence of each in the groups' order; with state layers, their
        array too.  A group that keeps compressed rows comes back as
        :meth:`pool_args` handed it over: K the pair (pool, rows)."""
        if not isinstance(k, (tuple, list)) or (
                len(self.groups) == 1 and self.groups[0].comp is not None):
            k, v = (k,), (v,)
        for g, k_g, v_g in zip(self.groups, k, v):
            if g.comp is not None:
                k_g, g.comp = k_g
            g.k, g.v = k_g, v_g
        if state is not None:
            self.state = state

    def pool_args(self) -> tuple:
        """(K, V) as the engine's programs take them: a group's pool, the
        pair (pool, compressed rows) for the K of a group that keeps them;
        with several groups a tuple of each, in the groups' order."""
        k = tuple(g.k if g.comp is None else (g.k, g.comp)
                  for g in self.groups)
        v = tuple(g.v for g in self.groups)
        return (k[0], v[0]) if len(self.groups) == 1 else (k, v)

    # ---- page lifecycle (internal) ----
    def _evict_one_entry(self) -> bool:
        """Drop the least-recently-used prefix entry; True if any entry
        was evicted (its index refs released — pages with no table refs
        return to the free list)."""
        if not self._prefix:
            return False
        _, entry = self._prefix.popitem(last=False)
        for g, (_, pages) in zip(self.groups, entry.pages):
            for page in pages:
                g.unref_index(page)
        self.prefix_evictions += 1
        return True

    def _alloc_page(self, slot: int, group: int = 0) -> int:
        """Claim a free page of ``group`` for ``slot`` (evicting LRU prefix
        entries under pressure), charging its reservation."""
        g = self.groups[group]
        while not g.free_pages:
            if not self._evict_one_entry():
                raise PagePoolExhausted(
                    "KV page pool exhausted: no free pages and nothing "
                    "reclaimable — an unreserved (adopted) slot decoded "
                    "past the pool, or the scheduler's page budget "
                    "under-reserved")
        page = g.free_pages.pop()
        g.ref_table[page] = 1
        g.ref_index[page] = 0
        if g.reserve[slot] > 0:
            g.reserve[slot] -= 1
        return page

    def _cow(self, slot: int, idx: int, group: int = 0) -> int:
        """Copy-on-write: replace ``tables[slot][idx]`` (shared) with a
        private copy; the page bytes move on device (donated, in place
        in the pool)."""
        g = self.groups[group]
        src = g.tables[slot][idx]
        dst = self._alloc_page(slot, group)
        if g.copy_fn is None:
            def copy(pools, src, dst):
                # K, V and the page's compressed rows where the group
                # keeps them
                return tuple(jax.lax.dynamic_update_slice_in_dim(
                    pool, jax.lax.dynamic_slice_in_dim(pool, src, 1, axis=1),
                    dst, axis=1) for pool in pools)

            g.copy_fn = jax.jit(copy, donate_argnums=(0,))
        g.k, g.v, *comp = g.copy_fn(
            (g.k, g.v) if g.comp is None else (g.k, g.v, g.comp),
            jnp.int32(src), jnp.int32(dst))
        if comp:
            g.comp, = comp
        g.tables[slot][idx] = dst
        g.unref_table(src)
        self.cow_copies += 1
        return dst

    def prepare_write(self, slot: int, start: int, n: int):
        """Make positions ``[start, start + n)`` of ``slot`` writable in
        every group: append fresh pages as the range grows the table, COW
        any shared page the range touches; a window group first drops the
        pages that fell behind the window of position ``start``.  Returns
        ``(write_pages, write_off)``: for each group an int32 array of
        length ``n`` mapping each position to its physical page, and the
        offsets in the page, which all groups share — the scatter maps the
        jitted steps take."""
        ps = self.page_size
        if start + n > self.max_len:
            raise ValueError(f"write [{start}, {start + n}) overruns "
                             f"max_len {self.max_len}")
        pos = start + np.arange(n)
        offs = (pos % ps).astype(np.int32)
        by_group = []
        for gi, g in enumerate(self.groups):
            if g.window is not None:
                g.release_behind(slot, start, ps)
            pages = np.empty(n, np.int32)
            for pi in range(start // ps, (start + n - 1) // ps + 1):
                pages[max(pi * ps - start, 0):(pi + 1) * ps - start] = \
                    self._writable_page(slot, pi, gi)
            by_group.append(pages)
        return by_group, offs

    def _writable_page(self, slot: int, pi: int, group: int) -> int:
        """The physical page that holds logical page ``pi`` of ``slot`` in
        ``group``, made writable: claimed where the table ends just before
        it, copied first where it is shared."""
        g = self.groups[group]
        table = g.tables[slot]
        idx = pi - int(g.base[slot])
        if idx == len(table):
            table.append(self._alloc_page(slot, group))
        elif not 0 <= idx < len(table):
            raise AssertionError(
                f"write at page {pi} skips pages (table holds "
                f"{int(g.base[slot])}..+{len(table)})")
        page = table[idx]
        if g.ref_table[page] + g.ref_index[page] > 1:
            page = self._cow(slot, idx, group)
        return page

    def prepare_round(self, slots):
        """:meth:`prepare_write` of ONE position, its next, for each of
        ``slots`` in turn (a decode round's): the same pages claimed, copied
        and dropped in the same order, the same errors, and as safe to
        repeat; one pass in plain ints, where sixty-four calls build five
        small arrays each.  Returns ``(write_pages, write_off)``: an int32
        array ``[groups, len(slots)]`` of each slot's physical page a group,
        and the offsets in the page, which all groups share."""
        ps = self.page_size
        starts = self.lengths[slots]
        if len(starts) and int(starts.max()) >= self.max_len:
            start = int(starts.max())
            raise ValueError(f"write [{start}, {start + 1}) overruns "
                             f"max_len {self.max_len}")
        pages = []
        for slot, start in zip(slots.tolist(), starts.tolist()):
            for gi, g in enumerate(self.groups):
                if g.window is not None:
                    g.release_behind(slot, start, ps)
                pages.append(self._writable_page(slot, start // ps, gi))
        pages = np.array(pages, np.int32).reshape(len(starts),
                                                  len(self.groups))
        return pages.T, (starts % ps).astype(np.int32)

    def padded_write_map(self, pages, offs, total: int):
        """Extend a :meth:`prepare_write` map (one group's pages, the
        offsets) to a padded chunk bucket: pad positions scatter into the
        scratch page (0, 0)."""
        n = len(pages)
        wp = np.zeros(total, np.int32)
        wo = np.zeros(total, np.int32)
        wp[:n] = pages
        wo[:n] = offs
        return wp, wo

    # ---- prefix sharing ----
    @staticmethod
    def _digests(tokens, page_size: int):
        """sha256 digests of every page-aligned prefix of ``tokens``
        plus the full (possibly partial-page) prompt, computed
        incrementally: ``{n_tokens: digest}``."""
        import hashlib
        arr = np.ascontiguousarray(np.asarray(tokens, np.int32))
        h = hashlib.sha256()
        out = {}
        n = len(arr)
        for j in range(page_size, n + 1, page_size):
            h.update(arr[j - page_size:j].tobytes())
            out[j] = h.digest()
        if n % page_size:
            h.update(arr[(n // page_size) * page_size:].tobytes())
            out[n] = h.digest()
        return out

    def match_prefix(self, tokens, *, touch: bool = True):
        """Longest cached prefix of ``tokens``: ``(n_shared, pages)``,
        ``pages`` what :meth:`adopt_prefix` takes (the entry's, a group).

        Tries the exact-prompt entry first (full dedup — identical
        prompts share even the partial tail page), then page-aligned
        chains, longest first.  The match is CAPPED at ``len(tokens) -
        1``: at least one token always prefills, because the first
        generated token needs the last prompt position's logits — when
        the cap bites, that one token recomputes into a shared page and
        copy-on-writes it (bitwise-identical K/V, private copy).
        ``(0, [])`` when nothing matches.

        ``touch=False`` (the admission-backpressure probe): report the
        match WITHOUT refreshing the entry's LRU position — a queued
        request re-probing every scheduler step must not pin entries it
        has not actually adopted against eviction."""
        n = len(tokens)
        if n < 2 or not self.max_prefix_entries:
            return 0, []
        digests = self._digests(tokens, self.page_size)
        for cand in sorted(digests, reverse=True):
            entry = self._prefix.get(digests[cand])
            if entry is None or entry.n_tokens != cand:
                continue
            if touch:
                self._prefix.move_to_end(digests[cand])  # LRU refresh
            return min(cand, n - 1), list(entry.pages)
        return 0, []

    def adopt_prefix(self, slot: int, n_shared: int, pages) -> None:
        """Attach a matched prefix to ``slot``: each group's table starts
        as the shared pages (read-only — any write COWs), with
        ``n_shared`` tokens already valid."""
        if any(g.tables[slot] for g in self.groups):
            raise ValueError(f"slot {slot} already has pages")
        for g, (first, shared) in zip(self.groups, pages):
            g.tables[slot] = list(shared)
            g.base[slot] = first
            for page in shared:
                g.ref_table[page] += 1
        self.lengths[slot] = int(n_shared)
        self.prefix_hit_tokens += int(n_shared)

    def _entry_pages(self, slot: int, n_tok: int):
        """The pages an entry for the first ``n_tok`` tokens of ``slot``
        holds, ``(first, pages)`` a group, or None where a window group no
        longer holds what a continuation from ``n_tok - 1`` on would read
        (a query there sees back to ``n_tok - window``)."""
        out = []
        last = self.pages_for_tokens(n_tok)          # pages [0, last)
        for g in self.groups:
            first = 0 if g.window is None \
                else max(n_tok - g.window, 0) // self.page_size
            lo = first - int(g.base[slot])
            if lo < 0:
                return None
            out.append((first, tuple(
                g.tables[slot][lo:last - int(g.base[slot])])))
        return tuple(out)

    def register_prefix(self, slot: int, tokens, *,
                        aligned_only: bool = False) -> None:
        """Index ``slot``'s freshly prefilled prompt so later arrivals
        can share it: one entry per page-aligned prefix plus the partial
        tail.  Registered pages become IMMUTABLE (index refs make them
        COW-on-write) — including for ``slot`` itself, whose first
        decode into a registered partial page copies it, leaving the
        indexed prompt K/V pristine.

        ``aligned_only``: skip the partial-tail entry — the re-index
        path for ADOPTED (migrated-in) slots, whose tail page is still
        being decoded into; indexing it would force a useless COW on
        the very next token and leave a stale never-matching entry."""
        if not self.max_prefix_entries:
            return
        for n_tok, digest in self._digests(tokens, self.page_size).items():
            if aligned_only and n_tok % self.page_size:
                continue
            if digest in self._prefix:
                self._prefix.move_to_end(digest)
                continue
            pages = self._entry_pages(slot, n_tok)
            if pages is None:
                continue
            self._prefix[digest] = _PrefixEntry(
                key=digest, pages=pages, n_tokens=int(n_tok))
            for g, (_, held) in zip(self.groups, pages):
                for page in held:
                    g.ref_index[page] += 1
            while len(self._prefix) > self.max_prefix_entries:
                self._evict_one_entry()

    @property
    def prefix_entries(self) -> int:
        return len(self._prefix)

    # ---- live-slot migration (serve/migrate.py rides on these) ----
    def _one_group(self, verb: str) -> None:
        if len(self.groups) > 1:
            raise GroupedCacheNotPortable(
                f"cannot {verb} slots of a cache of {len(self.groups)} "
                f"groups: a window group holds a slot's last pages only, "
                f"which the one-pair snapshot cannot carry; requeue the "
                f"requests instead")
        if self.groups[0].comp is not None:
            raise GroupedCacheNotPortable(
                f"cannot {verb} slots of a cache with compressed rows: the "
                f"one-pair snapshot carries K and V rows alone; requeue "
                f"the requests instead")
        if self.state is not None:
            raise GroupedCacheNotPortable(
                f"cannot {verb} slots of a cache with "
                f"{self.spec.state_layers} state layers: the one-pair "
                f"snapshot carries pages and no state, and pages without "
                f"their state continue to wrong tokens; requeue the "
                f"requests instead")

    def export_slots(self, slot_ids) -> list:
        """Snapshot occupied slots as CONTIGUOUS truncated K/V rows
        (:class:`KVSlotSnapshot`, the wire form migrate.py's codecs
        pack), assembled by gathering each slot's LIVE
        pages only: sharing means a page can back many slots, but a
        migration payload ships each slot's logical tokens (the adopter
        rebuilds page tables locally; re-dedup on import is the
        adopter's prefix index's job).  A cache of several groups, or with
        state layers, refuses (:class:`GroupedCacheNotPortable`)."""
        self._one_group("export")
        snaps = []
        ps = self.page_size
        for slot in slot_ids:
            slot = int(slot)
            if not 0 <= slot < self.num_slots:
                raise ValueError(f"slot {slot} out of range")
            if slot in self._free_slots:
                raise ValueError(f"slot {slot} is free; nothing to export")
            n = int(self.lengths[slot])
            if n < 1:
                raise ValueError(f"slot {slot} has no cached tokens")
            pages = np.asarray(self.tables[slot][:self.pages_for_tokens(n)],
                               np.int32)
            L = self.spec.num_layers
            k_row, v_row = self.spec.row_shapes()
            k_pg = np.asarray(self.k[:, pages])  # [L, P, ps, H * D]
            v_pg = np.asarray(self.v[:, pages])
            k_rows = k_pg.reshape(L, len(pages) * ps, *k_row)[:, :n]
            v_rows = v_pg.reshape(L, len(pages) * ps, *v_row)[:, :n]
            snaps.append(KVSlotSnapshot(
                slot=slot, length=n, k=np.ascontiguousarray(k_rows),
                v=np.ascontiguousarray(v_rows)))
        return snaps

    def import_slots(self, snapshots) -> dict:
        """Adopt peer-exported snapshots into fresh pages; returns
        ``{source_slot: slot}``.  Validates EVERYTHING (geometry, dtype,
        slot and page headroom) before allocating anything — a
        mismatched migration errors loudly and adopts nothing."""
        self._one_group("import")
        snaps = list(snapshots)
        if len(snaps) > self.num_free:
            raise RuntimeError(
                f"cannot adopt {len(snaps)} slots: only {self.num_free} "
                f"free")
        spec = self.spec
        dt = np.dtype(spec.dtype)
        need_pages = 0
        for s in snaps:
            if s.length < 1 or s.length >= self.max_len:
                raise ValueError(
                    f"slot snapshot of {s.length} tokens does not leave "
                    f"room to decode within max_len {self.max_len}")
            for name, arr, row in (("k", s.k, spec.row_shapes()[0]),
                                   ("v", s.v, spec.row_shapes()[1])):
                want = (spec.num_layers, s.length) + row
                if tuple(arr.shape) != want:
                    raise ValueError(
                        f"{name} geometry mismatch: snapshot "
                        f"{tuple(arr.shape)} vs cache spec {want} "
                        f"(layers/kv_heads/head_dim must match exactly)")
                if np.dtype(arr.dtype) != dt:
                    raise ValueError(
                        f"{name} dtype mismatch: snapshot "
                        f"{np.dtype(arr.dtype).name} vs cache {dt.name}")
            need_pages += self.pages_for_tokens(s.length)
        # net of outstanding reservations (available_pages), not just
        # free+reclaimable: an adoption must not consume the headroom an
        # in-flight chunked prefill's admission was promised
        if need_pages > self.available_pages():
            raise RuntimeError(
                f"cannot adopt {need_pages} pages: only "
                f"{self.available_pages()} available "
                f"(free + reclaimable - reserved)")
        if self._import_fn is None:
            def write(k, v, k_pages, v_pages, pages):
                k = k.at[:, pages].set(k_pages)
                v = v.at[:, pages].set(v_pages)
                return k, v

            self._import_fn = jax.jit(write, donate_argnums=(0, 1))
        ps = self.page_size
        g = self.groups[0]
        slot_map: dict = {}
        allocated: list = []
        try:
            for s in snaps:
                slot = self.alloc()
                allocated.append(slot)
                n_pg = self.pages_for_tokens(s.length)
                # pow2 page-count bucket keeps the import executable
                # count bounded
                pad = pow2_ceil(n_pg, self.pages_per_slot)
                table = [self._alloc_page(slot) for _ in range(n_pg)]
                pages = np.zeros(pad, np.int32)  # surplus -> scratch 0
                pages[:n_pg] = table
                L = spec.num_layers
                k_row, v_row = spec.row_shapes()
                k_pg = np.zeros((L, pad, ps) + k_row, dt)
                v_pg = np.zeros((L, pad, ps) + v_row, dt)
                k_pg.reshape(L, pad * ps, *k_row)[:, :s.length] = s.k
                v_pg.reshape(L, pad * ps, *v_row)[:, :s.length] = s.v
                g.k, g.v = self._import_fn(
                    g.k, g.v,
                    jnp.asarray(k_pg.reshape(L, pad, ps, -1)),
                    jnp.asarray(v_pg.reshape(L, pad, ps, -1)),
                    jnp.asarray(pages))
                g.tables[slot] = table
                self.lengths[slot] = s.length
                slot_map[s.slot] = slot
        except Exception:
            for slot in allocated:
                self.free(slot)
            raise
        return slot_map
