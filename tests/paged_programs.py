"""What the paged engine's two programs hold and where they write: the
checks ``test_paged_kv.py`` (GPT, Llama) and ``test_longcat_flash.py`` share
(ISSUE 29).  A helper module, no tests of its own."""

import jax
import numpy as np

# the primitives a pool may leave whole: the in-place row scatter, and the
# loop and call boundaries the carried pool passes through
CARRIERS = {"scatter", "scan", "while", "pjit", "jit", "closed_call",
            "core_call"}


def _sub_jaxprs(params):
    for v in params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            x = getattr(x, "jaxpr", x)       # ClosedJaxpr -> Jaxpr
            if hasattr(x, "eqns"):
                yield x


def _results(jaxpr):
    """(primitive, shape) of every value every equation makes, the bodies of
    loops and calls included."""
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            shape = getattr(var.aval, "shape", None)
            if shape is not None:
                yield eqn.primitive.name, tuple(shape)
        for sub in _sub_jaxprs(eqn.params):
            yield from _results(sub)


def program(engine, name: str, *, batch: int, chunk: int):
    """The jaxpr of the ``decode`` program, of the hot ``chunk`` program or
    of the boundary one (``chunk_ext``, its views one max-chunk wider), and
    the pages a sequence's view spans in it."""
    cache = engine.cache
    args = (engine.params, cache.k, cache.v)
    n_pg = cache.pages_per_slot
    if name == "chunk_ext":
        n_pg += -(-engine.prefill_chunk // cache.page_size)
    if name == "decode":
        fn, aux = engine._build_decode(), (batch, n_pg + 4)
    else:
        fn, aux = engine._build_chunk(n_pg), (3 * chunk + n_pg + 2,)
    return jax.make_jaxpr(fn)(
        *args, jax.ShapeDtypeStruct(aux, np.int32)), n_pg


def oversized(engine, name: str, *, batch: int, chunk: int):
    """(floor, values): the values of program ``name`` that are as large as
    the K pool or as a view of every cache layer (``L x b x T x row``),
    other than the carried pool itself, and the size they were held to.
    An empty list is the claim."""
    cache = engine.cache
    pools = {tuple(cache.k.shape), tuple(cache.v.shape)}
    closed, n_pg = program(engine, name, batch=batch, chunk=chunk)
    b = batch if name == "decode" else 1
    floor = min(int(cache.k.size), cache.spec.num_layers * b * n_pg
                * cache.page_size * int(cache.k.shape[-1]))
    seen = list(_results(closed.jaxpr))
    assert any(p == "scatter" and s in pools for p, s in seen), name
    return floor, [(p, s) for p, s in seen if int(np.prod(s)) >= floor
                   and not (s in pools and p in CARRIERS)]


def engine_greedy(engine, prompt, n: int) -> list:
    """``n`` greedy tokens of one request through an engine's own steps."""
    slot = engine.alloc_slot()
    toks = [engine.prefill(slot, prompt)]
    for _ in range(n - 1):
        toks.append(engine.decode()[slot])
    engine.release(slot)
    return toks


def pad_writes(engine, prompts, sentinel: float = 7.0) -> dict:
    """Fill both pools with ``sentinel``, prefill ``prompts`` (each ends in a
    padded chunk) into one slot each and decode ONE round (three slots in a
    bucket of four: one pad row), then sort every (page, offset) row of the
    pools by whether it still holds the sentinel.  Returns the rows written
    that no live position owns, the live rows left unwritten, and whether
    scratch row (0, 0) was written: no stray, none missed and scratch
    written is the claim."""
    cache = engine.cache
    cache.update(cache.k + sentinel, cache.v + sentinel)
    slots = []
    for p in prompts:
        slots.append(engine.alloc_slot())
        engine.prefill(slots[-1], p)
    engine.decode()
    live = {(0, 0)}
    ps = cache.page_size
    for s in slots:
        for pos in range(int(cache.lengths[s])):
            live.add((cache.tables[s][pos // ps], pos % ps))
    stray, missed = set(), set()
    for pool in (np.asarray(cache.k, np.float32),
                 np.asarray(cache.v, np.float32)):
        for layer in pool:
            untouched = np.all(layer == sentinel, axis=-1)   # [pages, ps]
            written = {tuple(map(int, r)) for r in np.argwhere(~untouched)}
            stray |= written - live
            missed |= live - written
    return {"stray": sorted(stray), "missed": sorted(missed - {(0, 0)}),
            "scratch_written": (0, 0) not in missed}
