"""The paged KV cache for decoder-LM serving.

:class:`PagedKVCache` holds fixed-size PAGES (``page_size`` tokens) in a
device-resident pool ``[L, num_pages, page_size, kv_heads * head_dim]``,
per-request page tables, refcounted PREFIX SHARING (hash-of-token-prefix
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
``ops.decode_attention`` repeats them at read time.  Works for both
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
``breakdown.device_ops``).  The allocator owns the slot lifecycle and the
per-slot host-side lengths.
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class KVCacheSpec:
    """Per-layer cache geometry, derived from a model config.

    The two pools need not be of one width: ``head_dim`` is the K pool's,
    ``v_head_dim`` the V pool's (None: the same).  A latent-attention model
    keeps its normalised latent in the K pool and its rotated shared key in
    the V pool, one "head" each.  ``num_layers`` counts CACHE layers, which
    a model with two attention blocks a layer has twice as many of."""

    num_layers: int
    num_kv_heads: int
    head_dim: int
    dtype: object = jnp.float32
    v_head_dim: Optional[int] = None

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
        """Bytes one cached token takes over all cache layers."""
        return (self.num_layers * self.num_kv_heads
                * (self.head_dim + self.v_dim)
                * np.dtype(self.dtype).itemsize)

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
         data_fields=("pool", "tables", "wpage", "woff"),
         meta_fields=("row",))
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
    ``(heads, head width)`` the model sees a row in (static).

    The model carries the value through its layer scan
    (``ops.scan_cached_layers``); a layer reads its own pages and writes its
    own new rows, so nothing of the pool's size, and no view of every
    layer, is ever made: the pool is a loop carry of a donated argument and
    the row scatter updates it in place."""

    pool: jax.Array
    tables: jax.Array
    wpage: jax.Array
    woff: jax.Array
    row: tuple

    def read(self, layer):
        """Cache layer ``layer`` of every sequence, ``[B, n_pg * page_size,
        *row]``: one gather of that layer's pages by the tables, on the
        pool's two major axes."""
        pages = self.pool[layer, self.tables]      # [B, n_pg, ps, width]
        b, n_pg, ps = pages.shape[:3]
        return pages.reshape((b, n_pg * ps) + tuple(self.row))

    def write(self, layer, rows):
        """The step's new rows ``[B, S, *row]`` (or flat, ``[B, S,
        width]``) of cache layer ``layer``, scattered through the write
        map."""
        rows = rows.reshape(rows.shape[:2] + self.pool.shape[3:])
        return replace(
            self, pool=self.pool.at[layer, self.wpage, self.woff].set(rows))


class PagePoolExhausted(RuntimeError):
    """The page pool has no free page and nothing reclaimable.

    Raised from :meth:`PagedKVCache._alloc_page` — reachable at DECODE
    time only by a slot decoding past its reservation, i.e. an adopted
    (migrated-in) slot, whose import allocates its live pages but
    reserves nothing for the decode ahead.  A distinct type so the
    scheduler can catch exactly this and preempt-and-requeue a victim
    (vLLM recompute-mode preemption) instead of killing the engine
    loop."""


@dataclass
class _PrefixEntry:
    """One cached token-prefix: ``pages`` hold the K/V of the first
    ``n_tokens`` tokens whose sha256 is ``key``.  Entries hold an INDEX
    reference on each page (``ref_index``); pages referenced only by the
    index are reclaimable under pressure (LRU eviction)."""

    key: bytes
    pages: tuple
    n_tokens: int


class PagedKVCache:
    """Paged K/V pool + per-slot page tables + refcounted prefix sharing.

    ``k``/``v``: ``[L, num_pages, page_size, kv_heads * head_dim]`` jax
    arrays, replaced wholesale by the engine after each jitted step.  A
    token's row is held FLAT: with a ``[kv_heads, head_dim]`` minor pair of
    (20, 64) the TPU's own layout of the array put the PAGE axis in the
    lanes (least padding), and every gather or scatter by page then cost a
    relayout of the whole pool, before and after the layer loop; 1280 flat
    is ten whole lane tiles, the pool keeps the order it is declared in and
    a page is one contiguous block (compiles for a described v5e, PR 29).
    Page 0 is a reserved SCRATCH page: jitted steps run over every slot
    with fixed shapes, and inactive slots' (masked, garbage) writes need
    a harmless landing zone — page 0 is never allocated to a request.

    Ownership model: each page carries two refcounts — ``ref_table``
    (how many slot page-tables reference it) and ``ref_index`` (how many
    prefix-index entries do).  A page is WRITABLE by a slot only when it
    is that slot's sole reference (``ref_table == 1 and ref_index ==
    0``); any write into a shared page copies it first (copy-on-write,
    counted in ``cow_copies``), so indexed prefix pages are immutable
    and a forked request can never corrupt its sibling's (or the
    cache's) prefix.  A page returns to the free list when BOTH counts
    reach zero; eviction of LRU index entries under allocation pressure
    is what turns "referenced only by the index" into free pages.

    Reservations: :meth:`reserve`/``reserved_remaining`` implement the
    scheduler's page-budget backpressure — an admission reserves the
    worst-case pages its request can touch (prompt + generation + one
    COW), and :meth:`available_pages` nets free + reclaimable pages
    against outstanding reservations so admissions cannot oversubscribe
    the pool out from under running decodes.
    """

    def __init__(self, spec: KVCacheSpec, num_slots: int, max_len: int, *,
                 page_size: int = 16, num_pages=None, sharding=None,
                 max_prefix_entries: int = 256):
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
        self.num_pages = int(num_pages)
        if self.num_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is scratch)")
        k_row, v_row = spec.row_shapes()
        lead = (spec.num_layers, self.num_pages, self.page_size)
        self.k = jnp.zeros(lead + (int(np.prod(k_row)),), spec.dtype)
        self.v = jnp.zeros(lead + (int(np.prod(v_row)),), spec.dtype)
        if sharding is not None:
            self.k = jax.device_put(self.k, sharding)
            self.v = jax.device_put(self.v, sharding)
        self.lengths = np.zeros(self.num_slots, np.int32)
        self.tables: list = [[] for _ in range(self.num_slots)]
        self.ref_table = np.zeros(self.num_pages, np.int32)
        self.ref_index = np.zeros(self.num_pages, np.int32)
        self._free_slots = list(range(self.num_slots - 1, -1, -1))
        # LIFO: recently-touched pages stay hot.
        # Page 0 excluded — the scratch page is never allocated.
        self._free_pages = list(range(self.num_pages - 1, 0, -1))
        self._reserve = np.zeros(self.num_slots, np.int32)
        self.max_prefix_entries = int(max_prefix_entries)
        from collections import OrderedDict
        self._prefix: "OrderedDict[bytes, _PrefixEntry]" = OrderedDict()
        # host-side counters the engine mirrors into ServeMetrics
        self.cow_copies = 0
        self.prefix_hit_tokens = 0
        self.prefix_evictions = 0
        self._copy_fn = None     # lazily jitted page copy (COW)
        self._import_fn = None   # lazily jitted page writer (import_slots)

    # ---- geometry helpers ----
    def pages_for_tokens(self, n: int) -> int:
        return -(-int(n) // self.page_size)

    @property
    def num_free(self) -> int:
        """Free REQUEST slots (admission gate)."""
        return len(self._free_slots)

    @property
    def pages_in_use(self) -> int:
        return self.num_pages - 1 - len(self._free_pages)

    @property
    def reclaimable_pages(self) -> int:
        """Pages held only by the prefix index — allocatable after an
        LRU eviction, so admission counts them as available."""
        return int(np.sum((self.ref_table == 0) & (self.ref_index > 0)))

    def available_pages(self) -> int:
        """Pages an admission may still claim: free + reclaimable, net
        of every running slot's outstanding reservation."""
        return (len(self._free_pages) + self.reclaimable_pages
                - int(self._reserve.sum()))

    @property
    def occupancy(self) -> float:
        return self.pages_in_use / max(self.num_pages - 1, 1)

    # ---- slot lifecycle ----
    def alloc(self) -> int:
        if not self._free_slots:
            raise RuntimeError("paged KV cache has no free slots")
        slot = self._free_slots.pop()
        self.lengths[slot] = 0
        self.tables[slot] = []
        self._reserve[slot] = 0
        return slot

    def free(self, slot: int) -> None:
        if not 0 <= slot < self.num_slots:
            raise ValueError(f"slot {slot} out of range")
        if slot in self._free_slots:
            raise ValueError(f"slot {slot} double-freed")
        for page in self.tables[slot]:
            self._unref_table(page)
        self.tables[slot] = []
        self.lengths[slot] = 0
        self._reserve[slot] = 0
        self._free_slots.append(slot)

    def reserve(self, slot: int, n_pages: int) -> None:
        """Record the admission's worst-case page claim for ``slot``;
        every page the slot later allocates draws it down."""
        self._reserve[slot] = max(int(n_pages), 0)

    def update(self, k, v) -> None:
        """Swap in the pool arrays a jitted step returned."""
        self.k, self.v = k, v

    # ---- page lifecycle (internal) ----
    def _unref_table(self, page: int) -> None:
        self.ref_table[page] -= 1
        if self.ref_table[page] < 0:
            raise AssertionError(f"page {page} table-ref underflow")
        if self.ref_table[page] == 0 and self.ref_index[page] == 0:
            self._free_pages.append(page)

    def _evict_one_entry(self) -> bool:
        """Drop the least-recently-used prefix entry; True if any entry
        was evicted (its index refs released — pages with no table refs
        return to the free list)."""
        if not self._prefix:
            return False
        _, entry = self._prefix.popitem(last=False)
        for page in entry.pages:
            self.ref_index[page] -= 1
            if self.ref_table[page] == 0 and self.ref_index[page] == 0:
                self._free_pages.append(page)
        self.prefix_evictions += 1
        return True

    def _alloc_page(self, slot: int) -> int:
        """Claim a free page for ``slot`` (evicting LRU prefix entries
        under pressure), charging its reservation."""
        while not self._free_pages:
            if not self._evict_one_entry():
                raise PagePoolExhausted(
                    "KV page pool exhausted: no free pages and nothing "
                    "reclaimable — an unreserved (adopted) slot decoded "
                    "past the pool, or the scheduler's page budget "
                    "under-reserved")
        page = self._free_pages.pop()
        self.ref_table[page] = 1
        self.ref_index[page] = 0
        if self._reserve[slot] > 0:
            self._reserve[slot] -= 1
        return page

    def _cow(self, slot: int, idx: int) -> int:
        """Copy-on-write: replace ``tables[slot][idx]`` (shared) with a
        private copy; the page bytes move on device (donated, in place
        in the pool)."""
        src = self.tables[slot][idx]
        dst = self._alloc_page(slot)
        if self._copy_fn is None:
            def copy(k, v, src, dst):
                k_page = jax.lax.dynamic_slice_in_dim(k, src, 1, axis=1)
                v_page = jax.lax.dynamic_slice_in_dim(v, src, 1, axis=1)
                k = jax.lax.dynamic_update_slice_in_dim(k, k_page, dst,
                                                        axis=1)
                v = jax.lax.dynamic_update_slice_in_dim(v, v_page, dst,
                                                        axis=1)
                return k, v

            self._copy_fn = jax.jit(copy, donate_argnums=(0, 1))
        self.k, self.v = self._copy_fn(self.k, self.v, jnp.int32(src),
                                       jnp.int32(dst))
        self.tables[slot][idx] = dst
        self._unref_table(src)
        self.cow_copies += 1
        return dst

    def prepare_write(self, slot: int, start: int, n: int):
        """Make positions ``[start, start + n)`` of ``slot`` writable:
        append fresh pages as the range grows the table, COW any shared
        page the range touches.  Returns ``(write_page, write_off)``
        int32 arrays of length ``n`` mapping each position to its
        physical (page, offset) — the scatter map the jitted steps take.
        """
        ps = self.page_size
        if start + n > self.max_len:
            raise ValueError(f"write [{start}, {start + n}) overruns "
                             f"max_len {self.max_len}")
        table = self.tables[slot]
        pages = np.empty(n, np.int32)
        offs = np.empty(n, np.int32)
        for i in range(n):
            pos = start + i
            pi = pos // ps
            if pi == len(table):
                table.append(self._alloc_page(slot))
            elif pi > len(table):
                raise AssertionError(
                    f"write at {pos} skips pages (table has {len(table)})")
            page = table[pi]
            if self.ref_table[page] + self.ref_index[page] > 1:
                page = self._cow(slot, pi)
            pages[i] = page
            offs[i] = pos % ps
        return pages, offs

    def padded_write_map(self, pages, offs, total: int):
        """Extend a :meth:`prepare_write` map to a padded chunk bucket:
        pad positions scatter into the scratch page (0, 0)."""
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
        """Longest cached prefix of ``tokens``: ``(n_shared, pages)``.

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
        """Attach a matched prefix to ``slot``: its table starts as the
        shared pages (read-only — any write COWs), with ``n_shared``
        tokens already valid."""
        if self.tables[slot]:
            raise ValueError(f"slot {slot} already has pages")
        self.tables[slot] = list(pages)
        for page in pages:
            self.ref_table[page] += 1
        self.lengths[slot] = int(n_shared)
        self.prefix_hit_tokens += int(n_shared)

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
        table = self.tables[slot]
        for n_tok, digest in self._digests(tokens, self.page_size).items():
            if aligned_only and n_tok % self.page_size:
                continue
            if digest in self._prefix:
                self._prefix.move_to_end(digest)
                continue
            pages = tuple(table[:self.pages_for_tokens(n_tok)])
            self._prefix[digest] = _PrefixEntry(
                key=digest, pages=pages, n_tokens=int(n_tok))
            for page in pages:
                self.ref_index[page] += 1
            while len(self._prefix) > self.max_prefix_entries:
                self._evict_one_entry()

    @property
    def prefix_entries(self) -> int:
        return len(self._prefix)

    # ---- live-slot migration (serve/migrate.py rides on these) ----
    def export_slots(self, slot_ids) -> list:
        """Snapshot occupied slots as CONTIGUOUS truncated K/V rows
        (:class:`KVSlotSnapshot`, the wire form migrate.py's codecs
        pack), assembled by gathering each slot's LIVE
        pages only: sharing means a page can back many slots, but a
        migration payload ships each slot's logical tokens (the adopter
        rebuilds page tables locally; re-dedup on import is the
        adopter's prefix index's job)."""
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
                self.k, self.v = self._import_fn(
                    self.k, self.v,
                    jnp.asarray(k_pg.reshape(L, pad, ps, -1)),
                    jnp.asarray(v_pg.reshape(L, pad, ps, -1)),
                    jnp.asarray(pages))
                self.tables[slot] = table
                self.lengths[slot] = s.length
                slot_map[s.slot] = slot
        except Exception:
            for slot in allocated:
                self.free(slot)
            raise
        return slot_map
