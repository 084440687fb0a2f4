"""Chunked prefill + single-token decode over the model forwards.

One engine, :class:`PagedServeEngine`, over the paged pools (page tables,
prefix sharing, chunked prefill — see kv_cache.py).

What a served model implements: ``prefill_chunk_with_cache`` and
``decode_with_cache`` (both take the cache layers in the places of their
``k_cache`` / ``v_cache`` arguments and return them beside the logits),
and either ``kv_cache_spec()`` or the config fields
:meth:`KVCacheSpec.from_model` reads.  ``models/gpt.py``,
``models/llama.py``, ``models/longcat_flash.py``,
``models/exaone_moe.py``, ``models/lfm2_moe.py`` and
``models/falcon_h1.py`` do.  A model whose spec
states several GROUPS of cache layers (window layers beside full ones) is
handed a tuple of cache layers in each place, one a group in the spec's
order, and returns the same.  A model whose spec states STATE LAYERS
(``KVCacheSpec.state_layers``: a layer that remembers a sequence in a
fixed-size array or several, ``models/lfm2_moe.py``'s short convolutions,
``models/falcon_h1.py``'s convolution rows beside its recurrence's matrix)
takes them
as ``state=`` (:class:`~hetu_tpu.serve.kv_cache.SlotStates`) in both entry
points and returns them as its LAST result, behind its counts.  Optionally
``serving_params(params)``: the parameters as those two entry points READ
them (a leaf they read only as ``astype(compute dtype)`` in that dtype, a
leaf they read in two dtypes held in both; an attention projection leaf
where its product reads it: transposed where a scanned layer's product
contracts its minor axis, a layer an array where a Python loop reads it at
a static index, ``layers/base.py`` ``Module.serving_params``; the rest as
given, stacked leaves included).  The engine calls it once, at build, and
holds what it returns, so a weight is cast, cut out of its stacked leaf or
relaid once and not in every decode round and every prefill chunk; a model
without it is served from the very arrays it was given, and so is every
leaf its rendering leaves alone.  The caller's ``variables`` are not kept:
a caller that drops them after the build gets their bytes back, those of
a stacked leaf the engine holds in another form too (a caller that keeps
them holds that leaf twice).  ``serve.params_held`` says what the build
did: leaves given, ``retyped``, ``relaid``, bytes on each side.

Compilation discipline is the whole point of this module: serving traffic
has arbitrary prompt lengths, and a naive jit would compile one executable
per distinct length.  Instead a prompt is prefilled in page-aligned chunks
right-padded to power-of-two chunk BUCKETS, and a decode step runs over a
power-of-two bucket of active slots by a power-of-two bucket of pages, so
the engine compiles at most :attr:`PagedServeEngine.max_executables`
programs for the whole life of the server — asserted in
tests/test_paged_kv.py via :meth:`PagedServeEngine.compiled_executables`.

Prefill runs one request at a time (batch 1, bounded compile count);
decode steps every ACTIVE slot at once, so continuous batching admissions
change the decode executable only when they cross a bucket.

Tensor parallelism: pass ``mesh`` and the engine places the parameters
with the Megatron split points (qkv/ffn-in column, out/ffn-down row — the
same ``parallel.strategies.MegatronLM`` preset training uses, minus the
vocab split: serving reads full logits every step) and shards the cache
over the kv-head axis when it divides tp.  XLA SPMD then inserts the
row-parallel all-reduces inside both jitted steps.
"""

from __future__ import annotations

import contextlib
import itertools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from hetu_tpu.layers.base import held_sources
from hetu_tpu.parallel.mesh import AXIS_TP
from hetu_tpu.parallel.strategies.simple import MegatronLM
from hetu_tpu.serve.kv_cache import (
    KVCacheSpec, PagedKVCache, PagedLayers, SlotStates, pow2_ceil,
)
from hetu_tpu.serve.metrics import CHUNK, DECODE, ServeMetrics
from hetu_tpu.telemetry import trace


class _DecodeTP(MegatronLM):
    """MegatronLM splits with the vocab kept replicated: a decode step
    reads the full ``[V]`` logits row per sequence every token, so a
    vocab-parallel embedding would all-gather per step for no win at
    serving batch sizes."""

    VOCAB = ()


def _pow2_buckets(lo: int, hi: int) -> tuple:
    out = []
    b = lo
    while b < hi:
        out.append(b)
        b *= 2
    out.append(hi)
    return tuple(out)


def _place_params_and_cache_spec(model, variables, mesh, spec):
    """The engine's tp placement: Megatron split points on the params,
    kv-head-sharded cache when GQA heads divide tp.  The placed leaves are
    then held as the model's cache entry points read them (the module
    docstring's ``serving_params``; a cast, a layer's array and a
    transposed hold keep their leaf's sharding on its other axes).
    Returns (params, cache sharding, the ids of ``serve.params_held``)."""
    given = params = variables["params"] if "params" in variables \
        else variables
    cache_sharding = None
    if mesh is not None:
        tp = mesh.shape.get(AXIS_TP, 1)
        params = _DecodeTP().place(params, mesh)
        # axis 3 holds the kv heads of the pool's flat rows
        # ``[L, pages, ps, heads * hd]``
        axes = (None, None, None,
                AXIS_TP if spec.num_kv_heads % tp == 0 else None)
        cache_sharding = NamedSharding(mesh, P(*axes))
    as_read = getattr(model, "serving_params", None)
    if as_read is not None:
        params = as_read(params)
    return params, cache_sharding, _params_held(given, params)


def _params_held(given, held) -> dict:
    """What the build did to the parameters, by leaf path: the leaves it
    was given, those it holds in another dtype than they came in (or in a
    second one), those it holds in another shape or structure (a leaf a
    layer, the two minor axes exchanged: ``layers/base.py``), and the bytes
    on each side."""
    given, held = ({jax.tree_util.keystr(path): leaf for path, leaf
                    in jax.tree_util.tree_leaves_with_path(tree)}
                   for tree in (given, held))

    def source(path):
        """The given leaf that the held leaf at ``path`` renders; None for
        a leaf the model added."""
        return next((p for p in held_sources(path) if p in given), None)

    def nbytes(leaves):
        return sum(int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
                   for a in leaves.values())

    sources = {path: source(path) for path in held}
    retyped = {src or path for path, src in sources.items()
               if src is None or given[src].dtype != held[path].dtype}
    return {
        "leaves": len(given), "retyped": len(retyped),
        "relaid": sum(path not in held or held[path].shape != leaf.shape
                      for path, leaf in given.items()),
        "bytes_given": nbytes(given), "bytes_held": nbytes(held)}


def _pools(layers):
    """The pool(s) of what a cache entry point returned in the place of its
    cache layers: one :class:`PagedLayers`' pool, or a tuple of them, one a
    group."""
    if isinstance(layers, PagedLayers):
        return layers.held()
    return tuple(g.held() for g in layers)


class _PrefillCursor:
    """Host-side state of one in-progress chunked prefill."""

    __slots__ = ("prompt", "pos", "n", "max_tokens", "matched")

    def __init__(self, prompt: np.ndarray, max_tokens: int):
        self.prompt = prompt
        self.pos = 0             # next un-prefilled position
        self.n = int(prompt.shape[0])
        self.max_tokens = int(max_tokens)
        self.matched = False     # prefix match ran (first chunk)

    @property
    def done(self) -> bool:
        return self.pos >= self.n


class PagedServeEngine:
    """Owns params + a :class:`PagedKVCache` + the jitted chunk/decode
    executables: paged gather/scatter decode, chunked prefill, prefix
    sharing with copy-on-write.  The params it owns are ``variables``'
    leaves in the dtype, and an attention projection's in the form, the two
    programs read each in (the model's ``serving_params``, once, here): the
    leaves themselves where that is how they came in, a copy where it is
    not.  ``variables`` itself is not kept.

    model: anything with the cache entry points the module docstring names.
    num_slots bounds concurrent sequences; max_len bounds tokens per
    sequence (prompt + generation), defaulting to the model's max_position.

    Both jitted programs hand the model the pools themselves, with the
    call's page tables and write map (:class:`PagedLayers`), in the places
    of its ``k_cache`` / ``v_cache`` arguments: one :class:`PagedLayers`
    each for a cache of one group, a tuple of them (one a group, the
    spec's order) for a model that states several.  A further group's
    table is of a width fixed at build (a window group's ring: what a
    chunk, or one decoded token, needs beside its window), packed behind
    the first group's operands in the same ``aux``, so both programs stay
    keyed by the first group's shapes alone and a model of one group
    compiles to the programs it compiled to before groups.  The model carries them
    through its layer scan: in a chunk each layer gathers its own pages
    into a view ``[b, n_pg * page_size, *row]``, runs its attention step on
    that view and scatters the step's new rows into the carried, donated
    pool; in a decode round a layer scatters its new rows and attends over
    its pages where they lie in the pool (one Pallas kernel on a TPU,
    :meth:`PagedLayers.attend`; a window group's ring, a pool laid over a
    tensor-parallel ``mesh``, and any layer on a CPU backend, gathers its
    view as a chunk does).  No view of every layer is built and the pool is
    never copied (how that was checked: ``kv_cache.py``'s docstring).

    STATE LAYERS (a cache whose spec states any, ``cache.state``): both
    programs take the state array (a tree of arrays, a part and a layer
    each, where a state layer keeps several parts) as a FIFTH argument,
    donated like the pools, and return it; one more int32 of ``aux`` a sequence names its
    slot.  A chunk reads and writes its one slot's state: zeros where it
    starts at position 0, and what it leaves is the state after its last
    REAL token, not after its bucket's padding (the model's ``last_index``).
    A decode round reads and writes the active slots' rows by slot; a slot
    bucket's padding rows write the scratch row ``num_slots``, which nothing
    reads.  Such an engine takes NO prefix match (a match would hand over
    the shared tokens' pages without the state at their boundary, which
    nobody kept): its cache is built without an index, no probe hashes a
    prompt, and each request that would have probed counts in
    ``prefix_state_refusals``.  A preempted request prefills again from
    position 0 and so rebuilds its state.  Live slots are not exported
    (``kv_cache.GroupedCacheNotPortable``, as a grouped cache).  A model
    without state layers pays nothing: its programs take four arguments and
    trace to the jaxpr they traced to before.

    The scheduler/pool/migration stack drives it through
    :meth:`admission_ok`, :meth:`begin_prefill`, :meth:`prefill_step`
    (page-budget admission a group, chunked prefill interleaved with
    decode), :meth:`decode`, the export/resume/adopt/reindex verbs of a
    live-slot migration (a grouped cache refuses them:
    ``kv_cache.GroupedCacheNotPortable``), :meth:`alloc_slot`/:meth:`release`, and the cache's
    ``lengths``/``max_len``/``num_free`` geometry.

    Compilation discipline: chunked prefill compiles one executable per
    power-of-two CHUNK bucket (a layer always gathers the slot's full page
    table, so chunk width is the only specializing shape); decode compiles
    one executable per power-of-two PAGE-COUNT bucket — short sequences
    gather a fraction of ``max_len`` a layer instead of every slot's worst
    case, which is where paged decode's per-step byte traffic win comes
    from (a model whose layers read a fixed number of CHOSEN rows has no such
    win to make and states ``KVCacheSpec.whole_tables``: one page bucket, the
    whole table).  Both are asserted via :meth:`compiled_executables`.
    """

    def __init__(self, model, variables, *, num_slots: int = 8,
                 max_len: Optional[int] = None, mesh=None,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 min_bucket: int = 16, prefix_sharing: bool = True,
                 max_prefix_entries: int = 256,
                 metrics: Optional[ServeMetrics] = None):
        self.model = model
        self.metrics = metrics or ServeMetrics()
        c = model.c
        max_len = int(max_len or c.max_position)
        if max_len > c.max_position:
            raise ValueError(f"max_len {max_len} exceeds the model's "
                             f"max_position {c.max_position}")
        spec = KVCacheSpec.from_model(model)
        self.mesh = mesh
        self.params, cache_sharding, held = _place_params_and_cache_spec(
            model, variables, mesh, spec)
        for name, n in held.items():
            self.metrics.set_gauge(f"params_{name}", n)
        # chunked prefill: chunk ends align to prefill_chunk boundaries
        # (a multiple of page_size), so chunks fill whole pages and the
        # prefix index's page-aligned entries match cleanly
        if prefill_chunk is None:
            prefill_chunk = max(4 * page_size, min_bucket)
        ps = int(page_size)
        self.prefill_chunk = -(-int(prefill_chunk) // ps) * ps
        self.cache = PagedKVCache(
            spec, num_slots, max_len, page_size=page_size,
            num_pages=num_pages, sharding=cache_sharding,
            max_prefix_entries=max_prefix_entries if prefix_sharing else 0,
            step_rows=self.prefill_chunk)
        self.chunk_buckets = _pow2_buckets(
            min(min_bucket, self.prefill_chunk), self.prefill_chunk)

        self.last_tokens = np.zeros(num_slots, np.int32)
        self.active = np.zeros(num_slots, bool)
        self._cursors: dict = {}  # slot -> _PrefillCursor

        self._chunk_fn = None      # hot path, specialized per chunk bucket
        self._chunk_fn_ext = None  # extended-view boundary path
        self._decode_fn = None     # one jit, specialized per page bucket
        self._seen_chunk_buckets = set()
        self._seen_page_buckets = set()
        # names of the counts a model's cache entry points return beside
        # their logits, in order (an expert model: pairs routed to held,
        # identity and absent experts, held experts hit); most have none
        self._stat_names = tuple(getattr(model, "step_stats", ()))
        self._released_seen = 0   # of cache.window_released, by _count
        # programs launched so far, chunks and decode rounds counted
        # together: ``seq`` on a launch span, on the fetch span that waits
        # for that launch, and on the call's row of the round log
        self._seq = 0
        # the running call's seam times (prep, launch, fetch, post) for its
        # row of the round log; a list here and no locals there: decode()
        self._stamps = [0, 0, 0, 0]
        # the further groups of the model's cache (window layers beside
        # full ones): the fixed widths of their tables in the chunk programs
        # and in the decode program (a ring as wide as the step needs)
        self._more = self.cache.groups[1:]
        # state layers (kv_cache.SlotStates): the cache built itself without
        # a prefix index; both programs carry ``cache.state``
        self._states = self.cache.state is not None
        # a group whose layers choose what a query reads by the request's
        # LENGTH (compressed rows, kv_cache.KVCacheSpec.comp_stride): the
        # chunk programs take the prompt's length as one int more
        self._chosen = spec.comp_stride is not None
        # a round's page bucket is the whole table (KVCacheSpec.whole_tables)
        self._whole_tables = spec.whole_tables
        self._ring_chunk = tuple(g.spec.ring_pages(self.prefill_chunk, ps)
                                 for g in self._more)
        self._ring_decode = tuple(g.spec.ring_pages(1, ps)
                                  for g in self._more)
        # bytes ONE cache layer's view of one page holds, K and V together,
        # a group: what a chunk's ``view_bytes`` id multiplies by its pages
        # (a decode round gathers no view on a TPU and states none)
        self._page_view_bytes = tuple(
            ps * (g.bytes_per_token // g.num_layers) for g in spec.groups)
        k_row, v_row = spec.row_shapes()
        ids = {"k_width": int(np.prod(k_row)), "v_width": int(np.prod(v_row)),
               "cache_layers": int(spec.num_layers),
               "bytes_per_token": int(spec.bytes_per_token),
               "pool_bytes": int(self.cache.k.nbytes + self.cache.v.nbytes)}
        for i, g in enumerate(self._more, 1):
            k_row, v_row = g.spec.row_shapes()
            ids.update({
                f"g{i}_k_width": int(np.prod(k_row)),
                f"g{i}_v_width": int(np.prod(v_row)),
                f"g{i}_cache_layers": int(g.spec.num_layers),
                f"g{i}_window": int(g.window or 0),
                f"g{i}_pool_bytes": int(g.k.nbytes + g.v.nbytes)})
        if self._states:
            ids.update({"state_layers": int(spec.state_layers),
                        "bytes_per_slot": int(spec.bytes_per_slot),
                        "state_bytes": self.cache.state_bytes})
            # a state layer of several parts: each part's array besides
            ids.update({f"state_{name}_bytes": sum(int(a.nbytes) for a in held)
                        for (name, *_), held
                        in zip(spec.state_parts, self.cache.state)})
        trace.instant("serve.cache_spec", ids)
        trace.instant("serve.params_held", held)

    def _pool_args(self):
        """The pools as the two programs take them: one pair, or with
        further groups a tuple of each."""
        return self.cache.pool_args()

    def _count(self, stats):
        """The counts a step returned beside its tokens, added to the
        metrics under the model's names for them and returned as ids for
        the step's ``post`` span (a span's ids are fixed when it opens, and
        these arrive with the tokens); None from a model that counts
        nothing."""
        if not stats:
            return None
        ids = dict(zip(self._stat_names, np.asarray(stats[0]).tolist()))
        for name, n in ids.items():
            self.metrics.inc(name, n)
        return ids

    def _held(self, ids, slots, lengths):
        """``ids`` (a step's counts, or None) with what a cache of SEVERAL
        groups holds once ``slots`` stand at ``lengths`` (the step's effect,
        which ``post`` is about to book): ``kv_pages_full`` /
        ``kv_pages_window``, the pages the allocated slots' tables hold in
        the groups that keep every position and in the window groups,
        ``kv_pages_if_one_group``, what they would hold were every cache
        layer to keep every position (all three in pages of one cache
        layer, and gauges of the same names), and ``kv_window_released``,
        the pages dropped from behind a window since the last call (a
        counter).  Over STATE LAYERS, besides: ``state_slots_held``, the
        slots handed out, ``state_bytes``, what their state takes (and
        ``state_<part>_bytes`` what it takes in each part, where a state
        layer keeps several), and ``kv_bytes_held``, the bytes of the pages
        their tables hold, all groups; gauges of the same names.  A cache
        of one group without state: ``ids`` as given."""
        if not self._more and not self._states:
            return ids
        if self._states:
            cache = self.cache
            n = cache.num_slots - cache.num_free
            # a freed slot's tables are empty
            pages = [sum(map(len, g.tables)) for g in cache.groups]
            held = {"state_slots_held": n,
                    "state_bytes": n * cache.spec.bytes_per_slot,
                    "kv_bytes_held": cache.page_size * sum(
                        g.spec.bytes_per_token * held
                        for g, held in zip(cache.groups, pages))}
            if cache.spec.state_parts:
                held.update(
                    (f"state_{name}_bytes", n * each) for name, each
                    in cache.spec.part_bytes_per_slot.items())
            for name, value in held.items():
                self.metrics.set_gauge(name, value)
            ids = {**(ids or {}), **held}
            if not self._more:
                ids["kv_pages_full"] = pages[0] * cache.spec.num_layers
                return ids
        after = self.cache.lengths.copy()
        after[slots] = lengths
        full, window, one = self.cache.held_layer_pages(after)
        released = self.cache.window_released
        held = {"kv_pages_full": full, "kv_pages_window": window,
                "kv_pages_if_one_group": one}
        for name, n in held.items():
            self.metrics.set_gauge(name, n)
        self.metrics.inc("kv_window_released",
                         released - self._released_seen)
        held["kv_window_released"] = released - self._released_seen
        self._released_seen = released
        return {**(ids or {}), **held}

    # ---- compile accounting ----
    def compiled_executables(self) -> int:
        return sum(fn._cache_size()
                   for fn in (self._chunk_fn, self._chunk_fn_ext,
                              self._decode_fn)
                   if fn is not None)

    @property
    def max_executables(self) -> int:
        """One per chunk bucket per view family (hot + extended
        boundary) + one per (pow2 active-batch, pow2 page-count) decode
        bucket pair."""
        n_page_buckets = 1
        b = self.cache.pages_per_slot if self._whole_tables else 1
        while b < self.cache.pages_per_slot:
            b *= 2
            n_page_buckets += 1
        n_batch_buckets = 1
        b = 1
        while b < self.cache.num_slots:
            b *= 2
            n_batch_buckets += 1
        return (2 * len(self.chunk_buckets)
                + n_page_buckets * n_batch_buckets)

    def chunk_bucket_for(self, n: int) -> int:
        for b in self.chunk_buckets:
            if n <= b:
                return b
        raise ValueError(f"chunk of {n} tokens exceeds prefill_chunk "
                         f"{self.prefill_chunk}")

    # ---- jitted step builders ----
    def _build_chunk(self, n_table: int):
        """One chunk executable family over views of ``n_table`` pages.
        Each layer of the model's scan gathers ITS pages of the one slot
        into a view, writes the chunk's rows into that view and attends
        over it, and scatters the same rows into the carried pool through
        the host's write map (:class:`PagedLayers`).  TWO families exist:
        the hot path's views are exactly ``pages_per_slot`` pages wide, and
        a BOUNDARY path (:attr:`_chunk_fn_ext`) extends every layer's view
        by one max-chunk of scratch columns — a padded final chunk near
        max_len writes (and re-extracts) rows at ``start + bucket``, which
        can run past ``pages_per_slot * ps``, and without the extension
        dynamic_update_slice/dynamic_slice would CLAMP the start and
        silently smear pad junk over real history (wrong tokens on
        exactly the near-full-context shared-prompt resubmit).  Keeping
        the extension off the hot path keeps the common chunk's gather
        at its minimum width."""
        model = self.model
        k_row, v_row = self.cache.spec.row_shapes()
        more = tuple(zip((g.spec.row_shapes() for g in self._more),
                         self._ring_chunk))
        mesh = None if self.mesh is None else self.mesh.abstract_mesh
        in_context = contextlib.nullcontext if mesh is None \
            else jax.sharding.use_abstract_mesh
        # a local, as every other value the program closes over: a program
        # that closed over the engine would keep it (and its pools) alive
        # in a cycle until the collector ran
        chosen = self._chosen

        def hetu_serve_prefill_chunk(params, k_pool, v_pool, aux, *state):
            # aux [3*sc + n_table + 2] int32 packs the chunk's host
            # operands (ids | write pages | write offsets | page table |
            # start | last) into one device_put, like the decode step; a
            # further group appends its own (write pages | ring table);
            # over state layers ``state`` is their array and one int more,
            # the last, names the slot; before it, where the model's layers
            # choose by it, the prompt's length
            rings = sum(ring for _, ring in more)
            sc = (aux.shape[0] - n_table - 2 - rings - len(state)
                  - chosen) // (3 + len(more))
            ids = aux[:sc][None]
            # per-token write map: real positions land in their pages,
            # pad positions in scratch 0
            wpage = aux[sc:2 * sc][None]
            woff = aux[2 * sc:3 * sc][None]
            table = aux[3 * sc:3 * sc + n_table][None]
            start = aux[3 * sc + n_table]
            last = aux[3 * sc + n_table + 1]
            # the pools: one pair, or with further groups a tuple of each
            k = PagedLayers.over(k_pool[0] if more else k_pool, table, wpage,
                                 woff, k_row)
            v = PagedLayers.over(v_pool[0] if more else v_pool, table, wpage,
                                 woff, v_row)
            if more:
                k, v, at = [k], [v], 3 * sc + n_table + 2
                for i, ((k_r, v_r), ring) in enumerate(more, 1):
                    wpage = aux[at:at + sc][None]
                    table = aux[at + sc:at + sc + ring][None]
                    k.append(PagedLayers.over(k_pool[i], table, wpage, woff,
                                              k_r))
                    v.append(PagedLayers.over(v_pool[i], table, wpage, woff,
                                              v_r))
                    at += sc + ring
                k, v = tuple(k), tuple(v)
            # the slot's state, read as zeros by the chunk that starts the
            # sequence; the model hands it back last
            held = {"state": SlotStates(state[0], aux[-1:],
                                        (start == 0)[None])} if state else {}
            if chosen:
                held["prompt_len"] = aux[-1 - len(state)][None]
            # a model may return a fourth value, its per-call counts
            # (``model.step_stats`` names them); most return none.  The
            # views it gathers do not say that the pools are laid over a
            # mesh: the mesh in context does (``ops.chunk_kernel_why``)
            with in_context(mesh):
                logits, k, v, *stats = model.prefill_chunk_with_cache(
                    {"params": params, "state": {}}, ids, k, v,
                    start, last_index=last, **held)
            tok = jnp.argmax(logits[0], -1).astype(jnp.int32)
            if state:
                *stats, held = stats
                return _pools(k), _pools(v), tok, tuple(stats), held.rows
            return _pools(k), _pools(v), tok, tuple(stats)

        # the function's name is the program's on the device: the profiler's
        # ``XLA Modules`` events, compile logs and HLO dumps read
        # ``jit_hetu_serve_prefill_chunk``
        return jax.jit(hetu_serve_prefill_chunk,
                       donate_argnums=(1, 2, 4) if self._states else (1, 2))

    def _build_decode(self):
        model = self.model
        k_row, v_row = self.cache.spec.row_shapes()
        more = tuple(zip((g.spec.row_shapes() for g in self._more),
                         self._ring_decode))
        # a pool laid over a mesh says so: its one-query step keeps the view
        sharded = self.mesh is not None

        def hetu_serve_decode(params, k_pool, v_pool, aux, *state):
            # aux [B, n_pg + 4] int32 packs every host-side operand of
            # the step (page table | length | token | write page | write
            # offset) into ONE device_put — five small uploads per step
            # cost more wall time than the decode math at serving batch
            # sizes; a further group appends its own (ring table | write
            # page), of a width fixed at build, so the program stays keyed
            # by the first group's page bucket and the batch bucket; over
            # state layers ``state`` is their array and one column more, the
            # last, names each row's slot (a padding row: the scratch slot)
            n_pg = aux.shape[1] - 4 - sum(ring + 1 for _, ring in more) \
                - len(state)
            tables = aux[:, :n_pg]
            lengths = aux[:, n_pg]
            tokens = aux[:, n_pg + 1]
            wpage = aux[:, n_pg + 2:n_pg + 3]
            woff = aux[:, n_pg + 3:n_pg + 4]
            # a layer scatters B new rows into the pool and attends over
            # its pages of the B sequences where they lie
            # (:meth:`PagedLayers.attend`, on a TPU; a window group's ring,
            # a pool laid over a mesh and a CPU backend gather that layer's
            # view): a decode step moves O(B) rows into the pool, never a
            # view of every layer and never the pool
            k = PagedLayers.over(k_pool[0] if more else k_pool, tables,
                                 wpage, woff, k_row, sharded)
            v = PagedLayers.over(v_pool[0] if more else v_pool, tables,
                                 wpage, woff, v_row, sharded)
            if more:
                k, v, at = [k], [v], n_pg + 4
                for i, ((k_r, v_r), ring) in enumerate(more, 1):
                    tables = aux[:, at:at + ring]
                    wpage = aux[:, at + ring:at + ring + 1]
                    k.append(PagedLayers.over(k_pool[i], tables, wpage, woff,
                                              k_r, sharded))
                    v.append(PagedLayers.over(v_pool[i], tables, wpage, woff,
                                              v_r, sharded))
                    at += ring + 1
                k, v = tuple(k), tuple(v)
            held = {"state": SlotStates(state[0], aux[:, -1])} \
                if state else {}
            logits, k, v, *stats = model.decode_with_cache(
                {"params": params, "state": {}}, tokens, k, v, lengths,
                **held)
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            if state:
                *stats, held = stats
                return _pools(k), _pools(v), nxt, tuple(stats), held.rows
            return _pools(k), _pools(v), nxt, tuple(stats)

        # named as the chunk program is: ``jit_hetu_serve_decode``
        return jax.jit(hetu_serve_decode,
                       donate_argnums=(1, 2, 4) if self._states else (1, 2))

    # ---- admission (the scheduler's page-budget backpressure) ----
    def admission_pages(self, prompt_len: int, max_tokens: int,
                        shared_tokens: int = 0, group: int = 0) -> int:
        """Worst-case pages of ``group`` an admission can touch: prompt +
        generation (capped at max_len) minus already-shared pages, plus one
        page of copy-on-write headroom.  Of a window group: what it holds at
        once, at most its ring, plus the same one page."""
        total = min(int(prompt_len) + int(max_tokens) + 1,
                    self.cache.max_len)
        pages = self.cache.pages_for_tokens(total)
        if group:
            return min(pages, self._ring_chunk[group - 1]) + 1
        return max(pages
                   - self.cache.pages_for_tokens(int(shared_tokens)), 0) + 1

    def _admission_by_group(self, prompt_len: int, max_tokens: int,
                            shared_tokens: int = 0) -> tuple:
        return tuple(self.admission_pages(prompt_len, max_tokens,
                                          shared_tokens, g)
                     for g in range(len(self.cache.groups)))

    def _fits(self, need: tuple) -> bool:
        return all(n <= self.cache.available_pages(g)
                   for g, n in enumerate(need))

    def admission_ok(self, prompt, max_tokens: int) -> bool:
        """True when the page pool can hold this request's worst case
        alongside every outstanding reservation.  Prefix-shared pages
        are credited — the dedup is what lets a pool of identical system
        prompts admit far past one private copy a request.

        The uncredited check runs first: when the worst case fits
        anyway (the common uncontended admission), no prefix probe runs
        at all — a backpressured queue head re-probes every scheduler
        step, and hashing its full prompt each time is wasted work
        unless the shared credit is what decides.  When the probe does
        run it is LRU-neutral (``touch=False``): a request must not pin
        index entries it never adopted."""
        if self._fits(self._admission_by_group(len(prompt), max_tokens)):
            return True
        n_shared, _ = self.cache.match_prefix(prompt, touch=False)
        if not n_shared:
            return False
        return self._fits(self._admission_by_group(len(prompt), max_tokens,
                                                   n_shared))

    # ---- chunked prefill ----
    def begin_prefill(self, slot: int, prompt_ids, *,
                      max_tokens: int = 0) -> None:
        """Start a chunked prefill into ``slot``: reserve the worst-case
        page budget and park a cursor for :meth:`prefill_step` to
        advance.  The prefix match runs on the FIRST chunk, not here —
        so a burst of identical prompts admitted in one scheduler sweep
        still shares whenever an earlier request's prefill COMPLETES
        (register_prefix runs on its final chunk) before a later
        request's first chunk.  Multi-chunk prompts whose first chunks
        all land in the same interleave window can still prefill
        privately — the match is one-shot, and adopting a prefix after
        a chunk has written would mean merging half-built tables (a
        known residual, not attempted).  The slot stays INACTIVE (no
        decode) until the final chunk emits the first token."""
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        n = prompt.shape[0]
        if n < 1:
            raise ValueError("empty prompt")
        if n >= self.cache.max_len:
            raise ValueError(f"prompt of {n} tokens leaves no room to "
                             f"generate within max_len {self.cache.max_len}")
        self.cache.reserve(slot, self._admission_by_group(n, max_tokens))
        self._cursors[slot] = _PrefillCursor(prompt, max_tokens)
        self.active[slot] = False

    def _match_on_first_chunk(self, slot: int, cur: _PrefillCursor) -> None:
        cur.matched = True
        if self._states:
            # no index exists (the class docstring says why): the request
            # prefills every token, and is counted where it would have probed
            self.metrics.inc("prefix_state_refusals")
            self.metrics.inc("prefix_miss_tokens", cur.n)
            return
        n_shared, pages = self.cache.match_prefix(cur.prompt)
        if n_shared and not any(g.tables[slot] for g in self.cache.groups):
            self.cache.adopt_prefix(slot, n_shared, pages)
            cur.pos = n_shared
            # shrink the admission's reservation by the shared credit
            self.cache.reserve(slot, self._admission_by_group(
                cur.n, cur.max_tokens, n_shared))
            self.metrics.inc("prefix_hits")
            self.metrics.inc("prefix_hit_tokens", n_shared)
            trace.instant("serve.prefix_hit",
                          {"slot": int(slot), "tokens": int(n_shared)})
        self.metrics.inc("prefix_miss_tokens", cur.n - cur.pos)

    def prefill_step(self, slot: int) -> Optional[int]:
        """Run the next page-aligned chunk of ``slot``'s prefill.
        Returns the first generated (greedy) token when the final chunk
        completes (the slot then decodes), else None."""
        cur = self._cursors.get(slot)
        if cur is None:
            raise ValueError(f"slot {slot} has no prefill in progress")
        if self._more or self._states or self._chosen:
            return self._prefill_step_grouped(slot, cur)
        # one span for the whole chunk, tiled by its four seams: prep is
        # the host's work before the program can be called (prefix match
        # on the first chunk, pages, operands), launch hands the operands
        # to the device and calls it, fetch is the host blocked on the
        # device, post the books (and the prefix index on the last chunk).
        # Inline, not a helper: see decode()
        with trace.span("serve.prefill_chunk", {"slot": int(slot)}):
            with trace.span("serve.prefill_chunk.prep"):
                self._stamps[0] = time.monotonic_ns()
                if not cur.matched:
                    self._match_on_first_chunk(slot, cur)
                start = cur.pos
                end = min(cur.n, (start // self.prefill_chunk + 1)
                          * self.prefill_chunk)
                size = end - start
                s = self.chunk_bucket_for(size)
                ps = self.cache.page_size
                n_table = self.cache.pages_per_slot
                # boundary path: the PADDED window [start, start+s) runs past
                # the slot's own page view — use the extended-view executable
                # family so nothing clamps (see _build_chunk)
                if start + s > n_table * ps:
                    n_table += -(-self.prefill_chunk // ps)
                    if self._chunk_fn_ext is None:
                        self._chunk_fn_ext = self._build_chunk(n_table)
                    chunk_fn = self._chunk_fn_ext
                else:
                    if self._chunk_fn is None:
                        self._chunk_fn = self._build_chunk(n_table)
                    chunk_fn = self._chunk_fn
                if (s, n_table) not in self._seen_chunk_buckets:
                    self._seen_chunk_buckets.add((s, n_table))
                    self.metrics.inc("prefill_compiles")
                    trace.instant("serve.recompile",
                                  {"kind": "prefill_chunk", "bucket": s})
                cow0 = self.cache.cow_copies
                (wp,), wo = self.cache.prepare_write(slot, start, size)
                wp, wo = self.cache.padded_write_map(wp, wo, s)
                aux = np.zeros(3 * s + n_table + 2, np.int32)
                aux[:size] = cur.prompt[start:end]
                aux[s:2 * s] = wp
                aux[2 * s:3 * s] = wo
                t = self.cache.tables[slot]
                aux[3 * s:3 * s + len(t)] = t
                aux[3 * s + n_table] = start
                aux[3 * s + n_table + 1] = size - 1
                self._seq += 1
            with trace.span("serve.prefill_chunk.launch",
                            {"start": int(start), "tokens": int(size),
                             "bucket": int(s),
                             "view_bytes": n_table * self._page_view_bytes[0],
                             "seq": self._seq}):
                self._stamps[1] = time.monotonic_ns()
                k, v, tok, stats = chunk_fn(
                    self.params, self.cache.k, self.cache.v, jnp.asarray(aux))
            with trace.span("serve.prefill_chunk.fetch", {"seq": self._seq}):
                self._stamps[2] = time.monotonic_ns()
                tok = int(tok)  # the host blocked on the device
                counts = self._count(stats)
            with trace.span("serve.prefill_chunk.post", counts):
                self._stamps[3] = time.monotonic_ns()
                self.cache.update(k, v)
                self.cache.lengths[slot] = end
                cur.pos = end
                self.metrics.inc("prefill_tokens", size)
                self.metrics.inc("prefill_chunks")
                if self.cache.cow_copies > cow0:
                    self.metrics.inc("cow_copies",
                                     self.cache.cow_copies - cow0)
                first = None
                if cur.done:
                    del self._cursors[slot]
                    self.cache.register_prefix(slot, cur.prompt)
                    self.last_tokens[slot] = first = tok
                    self.active[slot] = True
        self.metrics.observe_round(
            self._seq, CHUNK, *self._stamps, time.monotonic_ns(), 1, s, size)
        return first

    def _prefill_step_grouped(self, slot: int, cur) -> Optional[int]:
        """:meth:`prefill_step` over a cache of several groups or with state
        layers: the same four seams, with each further group's write pages
        and ring table packed behind the first group's operands, the pools
        handed over as tuples, the state array and the slot behind them, and
        the groups' page counts and the state's on the ``post`` span."""
        with trace.span("serve.prefill_chunk", {"slot": int(slot)}):
            with trace.span("serve.prefill_chunk.prep"):
                self._stamps[0] = time.monotonic_ns()
                if not cur.matched:
                    self._match_on_first_chunk(slot, cur)
                start = cur.pos
                end = min(cur.n, (start // self.prefill_chunk + 1)
                          * self.prefill_chunk)
                size = end - start
                s = self.chunk_bucket_for(size)
                ps = self.cache.page_size
                n_table = self.cache.pages_per_slot
                # boundary path: the PADDED window [start, start+s) runs past
                # the slot's own page view — use the extended-view executable
                # family so nothing clamps (see _build_chunk)
                if start + s > n_table * ps:
                    n_table += -(-self.prefill_chunk // ps)
                    if self._chunk_fn_ext is None:
                        self._chunk_fn_ext = self._build_chunk(n_table)
                    chunk_fn = self._chunk_fn_ext
                else:
                    if self._chunk_fn is None:
                        self._chunk_fn = self._build_chunk(n_table)
                    chunk_fn = self._chunk_fn
                if (s, n_table) not in self._seen_chunk_buckets:
                    self._seen_chunk_buckets.add((s, n_table))
                    self.metrics.inc("prefill_compiles")
                    trace.instant("serve.recompile",
                                  {"kind": "prefill_chunk", "bucket": s})
                cow0 = self.cache.cow_copies
                pages, wo = self.cache.prepare_write(slot, start, size)
                wp, wo = self.cache.padded_write_map(pages[0], wo, s)
                aux = np.zeros(3 * s + n_table + 2 + sum(
                    s + ring for ring in self._ring_chunk) + self._states
                    + self._chosen, np.int32)
                aux[:size] = cur.prompt[start:end]
                aux[s:2 * s] = wp
                aux[2 * s:3 * s] = wo
                t = self.cache.tables[slot]
                aux[3 * s:3 * s + len(t)] = t
                aux[3 * s + n_table] = start
                aux[3 * s + n_table + 1] = size - 1
                at = 3 * s + n_table + 2
                for g, wp, ring in zip(self._more, pages[1:],
                                       self._ring_chunk):
                    aux[at:at + size] = wp
                    aux[at + s:at + s + ring] = g.device_table(slot, ring)
                    at += s + ring
                if self._chosen:
                    aux[-1 - self._states] = cur.n
                if self._states:
                    aux[-1] = slot
                k_pool, v_pool = self._pool_args()
                state = (self.cache.state,) if self._states else ()
                self._seq += 1
                launch = {"start": int(start), "tokens": int(size),
                          "bucket": int(s),
                          "view_bytes": n_table * self._page_view_bytes[0],
                          "seq": self._seq}
                for i, ring in enumerate(self._ring_chunk, 1):
                    launch[f"g{i}_view_bytes"] = \
                        ring * self._page_view_bytes[i]
            with trace.span("serve.prefill_chunk.launch", launch):
                self._stamps[1] = time.monotonic_ns()
                k, v, tok, stats, *state = chunk_fn(
                    self.params, k_pool, v_pool, jnp.asarray(aux), *state)
            with trace.span("serve.prefill_chunk.fetch", {"seq": self._seq}):
                self._stamps[2] = time.monotonic_ns()
                tok = int(tok)  # the host blocked on the device
                counts = self._held(self._count(stats), slot, end)
            with trace.span("serve.prefill_chunk.post", counts):
                self._stamps[3] = time.monotonic_ns()
                self.cache.update(k, v, *state)
                self.cache.lengths[slot] = end
                cur.pos = end
                self.metrics.inc("prefill_tokens", size)
                self.metrics.inc("prefill_chunks")
                if self.cache.cow_copies > cow0:
                    self.metrics.inc("cow_copies",
                                     self.cache.cow_copies - cow0)
                first = None
                if cur.done:
                    del self._cursors[slot]
                    self.cache.register_prefix(slot, cur.prompt)
                    self.last_tokens[slot] = first = tok
                    self.active[slot] = True
        self.metrics.observe_round(
            self._seq, CHUNK, *self._stamps, time.monotonic_ns(), 1, s, size)
        return first

    def prefill(self, slot: int, prompt_ids) -> int:
        """Whole-prompt prefill, for callers with nothing to interleave:
        begin + advance every chunk in one call."""
        self.begin_prefill(slot, prompt_ids)
        while True:
            tok = self.prefill_step(slot)
            if tok is not None:
                return tok

    # ---- decode ----
    def decode(self) -> dict:
        """One decode step over the ACTIVE slots (paged gather/scatter);
        returns {slot: token} for them.

        The step gathers only a power-of-two BUCKET of active slots:
        per-step work scales with live traffic, not the engine's
        concurrency ceiling.  Pad rows in the bucket duplicate a real
        slot's table (harmless gather) but their write map points at the
        scratch page, so they can never corrupt the pool."""
        act = np.nonzero(self.active)[0]
        if len(act) == 0:
            return {}
        if self._more or self._states or self._chosen:
            return self._decode_grouped(act)
        # a cache of one group takes the round below, kept line for line as
        # it was before groups.  Folded into ONE round with the groups'
        # additions under ``if self._more:`` (the same programs, the same
        # cache keys, every one a cache hit), the first call of every
        # program was slower on the v5e: warm-up 8.84 -> 12.9-13.2 s in
        # gpt2-large's cell, 36.6 -> 53.0-53.5 s in LongCat's.  No one
        # statement carries it: this round with the pools in two locals
        # 10.25 s, with the launch ids made before the span 9.22 s, the
        # fold without those locals 12.7 s (PERF.md, PR 32; ROADMAP S11)
        # one span for the whole round, tiled by its four seams: prep builds
        # the step's operands on the host, launch hands them to the device
        # and calls the program, fetch is the host blocked on the device,
        # post the books.  Inline on purpose: with the round in a helper
        # method the first call of each of the 28 decode programs took
        # 0.16 s longer on the v5e (warm-up 11.6 s -> 16.2 s; PERF.md, PR 25).
        # Each seam also reads ``time.monotonic_ns()`` as its span's first
        # statement, and the reads are the call's row of the round log
        # (serve/metrics.py), written once the outer span has closed: no
        # profiler session needed to read it.  The reads go into a list the
        # engine holds, NOT into locals of this frame: four more locals round
        # the jitted call made the first call of each of 52 programs 0.055 s
        # slower on the v5e (warm-up 9.5 s -> 12.5 s, the same reads into
        # ``self._stamps`` 9.5 s; PERF.md, PR 54), which is also what PR 32's
        # "pools in two locals" paid
        with trace.span("serve.decode", {"active": len(act)}):
            with trace.span("serve.decode.prep"):
                self._stamps[0] = time.monotonic_ns()
                if (self.cache.lengths[act] >= self.cache.max_len).any():
                    raise RuntimeError(
                        "an active slot is at max_len; the scheduler must "
                        "evict before decoding further")
                if self._decode_fn is None:
                    self._decode_fn = self._build_decode()
                cow0 = self.cache.cow_copies
                bb = pow2_ceil(len(act), self.cache.num_slots)
                sl = np.zeros(bb, np.int32)
                sl[:len(act)] = act
                # grow/COW the write target of every active slot BEFORE
                # the step
                wp = np.zeros(bb, np.int32)
                wo = np.zeros(bb, np.int32)
                for i, slot in enumerate(act):
                    p, o = self.cache.prepare_write(
                        int(slot), int(self.cache.lengths[slot]), 1)
                    wp[i], wo[i] = p[0][0], o[0]
                # page bucket over ACTIVE slots only (after prepare_write
                # grew them): an inactive mid-chunked-prefill long prompt
                # must not inflate every interleaved decode's gather to its
                # table width — that would re-create exactly the
                # long-arrival latency spike the chunk interleave exists to
                # remove
                n_pg = pow2_ceil(
                    max(len(self.cache.tables[int(s)]) for s in act),
                    self.cache.pages_per_slot)
                if (bb, n_pg) not in self._seen_page_buckets:
                    self._seen_page_buckets.add((bb, n_pg))
                    self.metrics.inc("decode_compiles")
                    trace.instant("serve.recompile",
                                  {"kind": "decode", "pages": int(n_pg),
                                   "batch": int(bb)})
                aux = np.zeros((bb, n_pg + 4), np.int32)
                for i, slot in enumerate(sl):
                    t = self.cache.tables[slot][:n_pg]
                    aux[i, :len(t)] = t
                aux[:, n_pg] = self.cache.lengths[sl]
                aux[:, n_pg + 1] = self.last_tokens[sl]
                aux[:, n_pg + 2] = wp
                aux[:, n_pg + 3] = wo
                self._seq += 1
            with trace.span("serve.decode.launch",
                            {"pages": int(n_pg), "batch": int(bb),
                             "seq": self._seq}):
                self._stamps[1] = time.monotonic_ns()
                k, v, nxt, stats = self._decode_fn(
                    self.params, self.cache.k, self.cache.v,
                    jnp.asarray(aux))
            with trace.span("serve.decode.fetch", {"seq": self._seq}):
                self._stamps[2] = time.monotonic_ns()
                nxt = np.asarray(nxt)  # the host blocked on the device
                counts = self._count(stats)
            with trace.span("serve.decode.post", counts):
                self._stamps[3] = time.monotonic_ns()
                self.cache.update(k, v)
                out = {}
                for i, slot in enumerate(act):
                    self.cache.lengths[slot] += 1
                    self.last_tokens[slot] = nxt[i]
                    out[int(slot)] = int(nxt[i])
                if self.cache.cow_copies > cow0:
                    self.metrics.inc("cow_copies",
                                     self.cache.cow_copies - cow0)
                self.metrics.inc("decode_steps")
                self.metrics.set_gauge("pages_in_use",
                                       self.cache.pages_in_use)
                self.metrics.set_gauge("prefix_entries",
                                       self.cache.prefix_entries)
        self.metrics.observe_round(
            self._seq, DECODE, *self._stamps, time.monotonic_ns(),
            bb, n_pg, len(out))
        return out

    def _decode_grouped(self, act) -> dict:
        """:meth:`decode` over a cache of several groups or with state
        layers, ``act`` the active slots: the same four seams, with each
        further group's ring table and write page packed behind the first
        group's operands, the pools handed over as tuples, the state array
        and each row's slot behind them (a padding row: the scratch slot),
        and the groups' page counts and the state's on the ``post`` span."""
        with trace.span("serve.decode", {"active": len(act)}):
            with trace.span("serve.decode.prep"):
                self._stamps[0] = time.monotonic_ns()
                if (self.cache.lengths[act] >= self.cache.max_len).any():
                    raise RuntimeError(
                        "an active slot is at max_len; the scheduler must "
                        "evict before decoding further")
                if self._decode_fn is None:
                    self._decode_fn = self._build_decode()
                cow0 = self.cache.cow_copies
                n = len(act)
                bb = pow2_ceil(n, self.cache.num_slots)
                sl = np.zeros(bb, np.int32)
                sl[:n] = act
                # grow/COW the write target of every active slot BEFORE
                # the step
                wp = np.zeros((len(self.cache.groups), bb), np.int32)
                wo = np.zeros(bb, np.int32)
                wp[:, :n], wo[:n] = self.cache.prepare_round(act)
                # page bucket over ACTIVE slots only (after prepare_round
                # grew them): an inactive mid-chunked-prefill long prompt
                # must not inflate every interleaved decode's gather to its
                # table width — that would re-create exactly the
                # long-arrival latency spike the chunk interleave exists to
                # remove
                tables = [self.cache.tables[s] for s in act.tolist()]
                widths = np.fromiter(map(len, tables), np.int64, n)
                n_pg = self.cache.pages_per_slot if self._whole_tables \
                    else pow2_ceil(int(widths.max()),
                                   self.cache.pages_per_slot)
                if (bb, n_pg) not in self._seen_page_buckets:
                    self._seen_page_buckets.add((bb, n_pg))
                    self.metrics.inc("decode_compiles")
                    trace.instant("serve.recompile",
                                  {"kind": "decode", "pages": int(n_pg),
                                   "batch": int(bb)})
                aux = np.zeros((bb, n_pg + 4 + sum(
                    ring + 1 for ring in self._ring_decode) + self._states),
                    np.int32)
                # the active slots' tables in ONE assignment (row by row,
                # 64 rows were a fifth of a millisecond a round)
                aux[:n, :n_pg][np.arange(n_pg) < widths[:, None]] = \
                    np.fromiter(itertools.chain.from_iterable(tables),
                                np.int32, int(widths.sum()))
                for i in range(n, bb):    # pad rows: slot 0's, harmless
                    t = self.cache.tables[0][:n_pg]
                    aux[i, :len(t)] = t
                aux[:, n_pg] = self.cache.lengths[sl]
                aux[:, n_pg + 1] = self.last_tokens[sl]
                aux[:, n_pg + 2] = wp[0]
                aux[:, n_pg + 3] = wo
                at = n_pg + 4
                for g, wp_g, ring in zip(self._more, wp[1:],
                                         self._ring_decode):
                    for i, slot in enumerate(act):   # pad rows: scratch
                        aux[i, at:at + ring] = g.device_table(int(slot), ring)
                    aux[:, at + ring] = wp_g
                    at += ring + 1
                if self._states:
                    aux[:, -1] = self.cache.num_slots      # scratch
                    aux[:len(act), -1] = act
                k_pool, v_pool = self._pool_args()
                state = (self.cache.state,) if self._states else ()
                self._seq += 1
            with trace.span("serve.decode.launch",
                            {"pages": int(n_pg), "batch": int(bb),
                             "seq": self._seq}):
                self._stamps[1] = time.monotonic_ns()
                k, v, nxt, stats, *state = self._decode_fn(
                    self.params, k_pool, v_pool, aux, *state)
                # the copies to the host queued behind the program, not
                # asked for once the host has seen it end
                for result in (nxt, *stats):
                    result.copy_to_host_async()
            with trace.span("serve.decode.fetch", {"seq": self._seq}):
                self._stamps[2] = time.monotonic_ns()
                # the books that need no token, while the device runs
                held = self._held(None, act, self.cache.lengths[act] + 1)
                nxt = np.asarray(nxt)  # the host blocked on the device
                counts = {**(self._count(stats) or {}), **held}
            with trace.span("serve.decode.post", counts):
                self._stamps[3] = time.monotonic_ns()
                self.cache.update(k, v, *state)
                self.cache.lengths[act] += 1
                self.last_tokens[act] = nxt[:n]
                out = dict(zip(act.tolist(), nxt[:n].tolist()))
                if self.cache.cow_copies > cow0:
                    self.metrics.inc("cow_copies",
                                     self.cache.cow_copies - cow0)
                self.metrics.inc("decode_steps")
                self.metrics.set_gauge("pages_in_use",
                                       self.cache.pages_in_use)
                self.metrics.set_gauge("prefix_entries",
                                       self.cache.prefix_entries)
        self.metrics.observe_round(
            self._seq, DECODE, *self._stamps, time.monotonic_ns(),
            bb, n_pg, len(out))
        return out

    # ---- live-slot migration ----
    def export_slots(self, slot_ids) -> list:
        """Snapshot mid-decode slots for hand-off to a peer engine: the
        cache's truncated K/V rows plus this engine's per-slot decode
        state (the last emitted token, which is NOT in the cache yet) in
        ``meta`` — everything a peer needs to continue decoding
        token-for-token with zero prefill.

        Exported slots are SUSPENDED (allocated but excluded from
        :meth:`decode`) until the caller either releases them (the
        migration committed) or :meth:`resume_slots` them (rollback).
        The wire transfer runs outside any lock, and a decode step
        admitted in that window would otherwise silently advance the
        exported slots past their requests' recorded tokens — tokens a
        rollback could never recover."""
        for slot in slot_ids:
            if not self.active[int(slot)]:
                raise ValueError(f"slot {int(slot)} is not mid-decode; "
                                 f"nothing to migrate")
        snaps = self.cache.export_slots(slot_ids)
        for s in snaps:
            s.meta["last_token"] = int(self.last_tokens[s.slot])
        for slot in slot_ids:
            self.active[int(slot)] = False
        return snaps

    def resume_slots(self, slot_ids) -> None:
        """Re-activate slots suspended by :meth:`export_slots` — the
        rollback half of a failed migration: the source engine resumes
        decoding them exactly where they stopped (``last_tokens`` was
        kept through the suspension)."""
        slots = [int(s) for s in slot_ids]
        for slot in slots:
            if self.cache.lengths[slot] < 1:
                raise ValueError(f"slot {slot} has no cached tokens to "
                                 f"resume")
        for slot in slots:
            self.active[slot] = True

    def adopt_slots(self, snapshots) -> dict:
        """Adopt peer-exported slots; returns ``{source_slot: slot}``.
        The next :meth:`decode` continues each adopted sequence exactly
        where the source left off — no prefill step runs (the
        ``serve.prefill_chunk`` span/metric stays flat, the
        zero-re-prefill contract tests assert)."""
        snaps = list(snapshots)
        for s in snaps:
            if "last_token" not in s.meta:
                raise ValueError(
                    f"slot snapshot {s.slot} has no last_token meta — "
                    f"exported from a cache, not an engine?")
        slot_map = self.cache.import_slots(snaps)
        for s in snaps:
            slot = slot_map[s.slot]
            self.last_tokens[slot] = int(s.meta["last_token"])
            self.active[slot] = True
        self.metrics.inc("slots_adopted", len(slot_map))
        return slot_map

    def reindex_prefix(self, slot: int, tokens) -> None:
        """Re-dedup an ADOPTED slot into this engine's prefix index:
        register the page-boundary hashes of ``tokens`` (the slot's
        cached token stream — the scheduler knows it; the cache only
        holds K/V rows) against the freshly imported pages.  Without
        this, post-drain traffic sharing the migrated requests' prompts
        re-prefills the prefix from scratch until the imported pages
        age out — the receiver keeps the source's hit rate only if the
        hashes move with the pages.  Page-aligned entries only: the
        tail page is mid-decode (``register_prefix(aligned_only)``)."""
        n = int(self.cache.lengths[slot])
        toks = list(tokens)[:n]
        if len(toks) < n or n < self.cache.page_size:
            return  # stream shorter than the cached rows (defensive),
            # or no complete page to index
        before = self.cache.prefix_entries
        self.cache.register_prefix(slot, toks, aligned_only=True)
        added = self.cache.prefix_entries - before
        if added > 0:
            self.metrics.inc("prefix_reindexed", added)

    # ---- slot lifecycle ----
    def alloc_slot(self) -> int:
        slot = self.cache.alloc()
        self.active[slot] = False
        if self._states:
            # the slot's state is zeros again (read so by its first chunk)
            self.metrics.inc("state_resets")
            trace.instant("serve.state_reset", {"slot": int(slot)})
        return slot

    def release(self, slot: int) -> None:
        self.active[slot] = False
        self.last_tokens[slot] = 0
        self._cursors.pop(slot, None)
        self.cache.free(slot)
