"""What the paged engine's two programs hold and where they write: the
checks ``test_paged_kv.py`` (GPT, Llama) and ``test_longcat_flash.py`` share
(ISSUE 29), which parameter leaves they convert and the logits they argmax
(ISSUE 31), how they read a weight and the tiny served families (ISSUE
45), and the two token oracles every serving test holds the engine
to, both independent of any engine, and the hold that lets a pool test
catch a member mid-decode, and an engine's chunk programs with the flash
forward kernel beside the XLA key-block walk (ISSUE 52).  A helper module,
no tests of its own."""

import hashlib
import re
import threading
import time

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np

from hetu_tpu.serve import KVCacheSpec

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


def _holds_jaxpr(value) -> bool:
    return any(hasattr(getattr(x, "jaxpr", x), "eqns") for x in
               (value if isinstance(value, (tuple, list)) else (value,)))


def _equations(jaxpr):
    """Every equation, the bodies of loops and calls included, as (its
    primitive, its operands' types, a literal's value, its results' types,
    its parameters but for inner jaxprs and addresses)."""
    for eqn in jaxpr.eqns:
        params = sorted((k, re.sub(r" at 0x[0-9a-f]+", "", repr(v)))
                        for k, v in eqn.params.items() if not _holds_jaxpr(v))
        yield repr((eqn.primitive.name,
                    [str(v.val) if isinstance(v, jax.extend.core.Literal)
                     else str(v.aval) for v in eqn.invars],
                    [str(v.aval) for v in eqn.outvars], params))
        for sub in _sub_jaxprs(eqn.params):
            yield from _equations(sub)


def all_eqns(jaxpr):
    """Every equation of ``jaxpr``, bodies of loops, calls and checkpoints
    included, in program order."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from all_eqns(sub)


def eqn_names(jaxpr) -> set:
    """What ``jaxpr`` holds, by name: each equation's primitive, a jitted
    call by the function's own name."""
    return {eqn.params["name"] if eqn.primitive.name == "jit"
            else eqn.primitive.name for eqn in all_eqns(jaxpr)}


def pallas_grids(jaxpr) -> list:
    """The grid of every ``pallas_call`` in ``jaxpr``, in program order."""
    return [tuple(eqn.params["grid_mapping"].grid) for eqn in all_eqns(jaxpr)
            if eqn.primitive.name == "pallas_call"]


def program_digest(jaxpr) -> str:
    """A traced program as the MULTISET of its equations, hashed: equal for
    two traces of one program, whatever names the tracer gave the values
    and whatever order it closed over constants in (both vary from process
    to process under ``jax.grad``); any equation added, dropped or changed
    in a type or a parameter changes it."""
    return hashlib.sha256(
        "\n".join(sorted(_equations(jaxpr))).encode()).hexdigest()


def program(engine, name: str, *, batch: int, chunk: int, params=None):
    """The jaxpr of the ``decode`` program, of the hot ``chunk`` program or
    of the boundary one (``chunk_ext``, its views one max-chunk wider), and
    the pages a sequence's view spans in it.  Over the engine's own leaves,
    or over ``params``."""
    cache = engine.cache
    args = (engine.params if params is None else params, cache.k, cache.v)
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


# the primitives that hand a leaf on as it is but for its shape, or a
# slice of it: ``tok_emb.T``, a stacked leaf read at ``[l]``
LAYOUT_ONLY = {"transpose", "reshape", "squeeze", "slice", "dynamic_slice"}
# the primitives whose body takes the operands themselves, one for one
BODIES = {"scan", "pjit", "jit", "closed_call", "core_call", "checkpoint",
          "custom_jvp_call", "custom_vjp_call"}


def _inner_operands(eqn, sub, marks: list) -> dict:
    """``sub``'s inputs that stand for marked operands of ``eqn``, with their
    marks (``marks`` lies beside ``eqn.invars``, falsy for an operand of no
    interest): a loop's or a call's body takes the operands themselves, a
    scatter's or a reduction's combiner takes scalars."""
    name = eqn.primitive.name
    if name == "while":
        nc, nb = eqn.params["cond_nconsts"], eqn.params["body_nconsts"]
        carry = marks[nc + nb:]
        marks = (marks[:nc] + carry
                 if sub is eqn.params["cond_jaxpr"].jaxpr
                 else marks[nc:nc + nb] + carry)
    elif name == "cond":
        marks = marks[1:]
    elif name not in BODIES:
        return {}
    assert len(marks) == len(sub.invars), (name, "operands not mapped")
    return {v: m for v, m in zip(sub.invars, marks) if m}


def _inner_leaves(eqn, sub, is_leaf):
    """Which of ``sub``'s inputs are parameter leaves, given which of
    ``eqn``'s operands are."""
    return set(_inner_operands(eqn, sub, is_leaf))


def _leaf_converts(jaxpr, leaves: set):
    for eqn in jaxpr.eqns:
        is_leaf = [isinstance(v, jax.extend.core.Var) and v in leaves
                   for v in eqn.invars]
        name = eqn.primitive.name
        if name == "convert_element_type" and is_leaf[0]:
            aval = eqn.invars[0].aval
            if len(aval.shape) >= 2:
                yield (tuple(aval.shape), str(aval.dtype),
                       str(eqn.params["new_dtype"]))
        if name in LAYOUT_ONLY and is_leaf[0]:
            leaves.update(eqn.outvars)
        for sub in _sub_jaxprs(eqn.params):
            yield from _leaf_converts(sub, _inner_leaves(eqn, sub, is_leaf))


def param_converts(engine, name: str, *, batch: int, chunk: int,
                   params=None) -> list:
    """(shape, from, to) of every ``convert_element_type`` in program
    ``name`` whose operand is a parameter leaf of two or more dimensions,
    whole, transposed or one layer's slice of it, the bodies of loops and
    calls included: the casts a program makes of its weights in every call.
    Over the engine's own leaves, or over ``params``."""
    leaves = engine.params if params is None else params
    closed, _ = program(engine, name, batch=batch, chunk=chunk, params=leaves)
    n = len(jax.tree_util.tree_leaves(leaves))
    return list(_leaf_converts(closed.jaxpr, set(closed.jaxpr.invars[:n])))


def traced(engine, name: str, *, batch: int, chunk: int, params=None):
    """The engine's ``decode`` program at ``batch`` sequences or its
    ``chunk`` program at ``chunk`` tokens, traced (``.jaxpr``, ``.lower``)
    over the engine's own leaves or over ``params``: a cache of several
    groups, with state layers or with compressed rows (a chunk program
    then takes its prompt's length as one int more) too."""
    k_pool, v_pool = engine._pool_args()
    n_pg = engine.cache.pages_per_slot
    state = () if engine.cache.state is None else (engine.cache.state,)
    if name == "decode":
        fn = engine._build_decode()
        aux = (batch, n_pg + 4 + len(state)
               + sum(r + 1 for r in engine._ring_decode))
    else:
        fn = engine._build_chunk(n_pg)
        aux = (3 * chunk + n_pg + 2 + len(state) + engine._chosen
               + sum(chunk + r for r in engine._ring_chunk),)
    return fn.trace(engine.params if params is None else params, k_pool,
                    v_pool, jax.ShapeDtypeStruct(aux, np.int32), *state)


# what may lie between a weight's parameter and the product that reads it
READ_THROUGH = LAYOUT_ONLY | {"convert_element_type", "copy", "gather"}


def _leaf_reads(jaxpr, chains: dict):
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        held = [chains.get(v) if isinstance(v, jax.extend.core.Var) else None
                for v in eqn.invars]
        if not any(held):
            continue
        subs = list(_sub_jaxprs(eqn.params))
        if name == "scan":      # a layer of a scanned leaf is the scan's own
            n = eqn.params["num_consts"] + eqn.params["num_carry"]
            held = held[:n] + [h and (h[0], h[1] + ("scan",))
                               for h in held[n:]]
        if subs:
            for sub in subs:
                yield from _leaf_reads(sub, _inner_operands(eqn, sub, held))
        elif name in READ_THROUGH and held[0]:
            leaf, chain = held[0]
            chains.update((v, (leaf, chain + (name,))) for v in eqn.outvars)
        else:
            yield from ((h[0], h[1] + (name,)) for h in held if h)


def weight_reads(engine, name: str, which, *, batch: int = 4, chunk: int = 8,
                 params=None) -> dict:
    """By leaf path, the set of ways program ``name`` reads each parameter
    leaf whose path ``which`` accepts: every chain of primitives from the
    parameter to the first one that computes with it (``("transpose",
    "dot_general")``: the product contracts the leaf's minor axis;
    ``("slice", "squeeze", "dot_general")``: a layer is cut out of a
    stacked leaf at a static index first), loops' and calls' bodies
    included, ``"scan"`` where the layer is a scan's own slice of its
    operand.  Over the engine's own leaves, or over ``params``."""
    leaves = engine.params if params is None else params
    closed = traced(engine, name, batch=batch, chunk=chunk,
                    params=leaves).jaxpr
    paths = [jax.tree_util.keystr(path) for path, _
             in jax.tree_util.tree_leaves_with_path(leaves)]
    chains = {v: (path, ()) for v, path in zip(closed.jaxpr.invars, paths)
              if which(path)}
    reads = {}
    for path, chain in _leaf_reads(closed.jaxpr, chains):
        reads.setdefault(path, set()).add(chain)
    return reads


def tiny_model(kind: str):
    """A tiny bfloat16 model of each served family."""
    bf16 = jnp.bfloat16
    if kind == "gpt":
        from hetu_tpu.models.gpt import GPTConfig, GPTModel
        model = GPTModel(GPTConfig(
            vocab_size=96, hidden_size=64, num_layers=3, num_heads=4,
            ffn_size=128, max_position=160, dropout_rate=0.0, dtype=bf16))
    elif kind == "exaone":
        from hetu_tpu.models.exaone_moe import (
            ExaoneMoeConfig, ExaoneMoeModel,
        )
        model = ExaoneMoeModel(ExaoneMoeConfig(
            vocab_size=96, hidden_size=128, num_layers=5, num_heads=4,
            num_kv_heads=2, head_dim=32, ffn_size=256, expert_ffn_size=128,
            first_dense=1, n_routed_experts=16, moe_topk=4, held=(4, 4),
            window=8, max_position=256, dtype=bf16, param_dtype=bf16,
            expert_block_rows=8))
    elif kind == "lfm2":
        from hetu_tpu.models.lfm2_moe import Lfm2MoeConfig, Lfm2MoeModel
        model = Lfm2MoeModel(Lfm2MoeConfig(
            vocab_size=96, hidden_size=128, num_layers=7, num_heads=4,
            num_kv_heads=2, head_dim=32, ffn_size=256, expert_ffn_size=128,
            first_dense=2, n_routed_experts=8, moe_topk=4, max_position=256,
            dtype=bf16, param_dtype=bf16, expert_block_rows=24))
    elif kind == "falcon":
        from hetu_tpu.models.falcon_h1 import FalconH1Config, FalconH1Model
        model = FalconH1Model(FalconH1Config(
            vocab_size=96, hidden_size=128, num_layers=3, num_heads=4,
            num_kv_heads=2, head_dim=32, ffn_size=256, ssm_heads=4,
            ssm_head_dim=16, ssm_state=16, ssm_groups=2, ssm_chunk=8,
            max_position=256, dtype=bf16, param_dtype=bf16))
    else:
        from hetu_tpu.models.longcat_flash import (
            LongcatFlashConfig, LongcatFlashModel,
        )
        model = LongcatFlashModel(LongcatFlashConfig(
            vocab_size=96, hidden_size=128, num_layers=2, num_heads=4,
            q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, ffn_size=256,
            expert_ffn_size=128, n_routed_experts=16, zero_expert_num=4,
            moe_topk=4, held=(4, 4), max_position=256, dtype=bf16,
            param_dtype=bf16, expert_block_rows=8))
    return model


def tiny_served(kind: str):
    """(:func:`tiny_model`, its variables, an engine's keywords for it)."""
    model = tiny_model(kind)
    return model, jax.jit(model.init)(jax.random.PRNGKey(0)), dict(
        num_slots=4, max_len=160, page_size=4, prefill_chunk=8, min_bucket=4)


class LogitsOut:
    """A served model that hands each cache entry point's logits on as its
    per-call counts too, so that the engine's own two programs return them
    beside the token they argmax."""

    def __init__(self, model):
        self.model, self.c = model, model.c

    def __getattr__(self, name):
        return getattr(self.model, name)

    # a model's own counts (one that names any returns them fourth) are
    # dropped; its state (a model with state layers returns it last) is
    # handed on
    def _out(self, logits, k, v, *rest):
        return (logits, k, v, logits,
                *rest[bool(getattr(self.model, "step_stats", ())):])

    def prefill_chunk_with_cache(self, *args, **kw):
        return self._out(*self.model.prefill_chunk_with_cache(*args, **kw))

    def decode_with_cache(self, *args, **kw):
        return self._out(*self.model.decode_with_cache(*args, **kw))


def engine_logits(model, variables, prompt, n: int, *, as_given=False,
                  **engine_kw):
    """The logits behind every chunk of ``prompt`` and each of ``n - 1``
    decode rounds after it, out of the engine's own programs
    (:class:`LogitsOut`), and the engine.  ``as_given``: over the leaves the
    engine was given, not over those it holds."""
    from hetu_tpu.serve import PagedServeEngine

    engine = PagedServeEngine(LogitsOut(model), variables, **engine_kw)
    if as_given:
        engine.params = variables["params"]
    logits = []
    engine._count = lambda stats: logits.append(np.asarray(stats[0]))
    engine_greedy(engine, prompt, n)
    return logits, engine


def ref_greedy(model, variables, prompt, n: int) -> list:
    """``n`` greedy tokens by a full re-forward of the training ``apply``
    each step: no cache at all."""
    ids = list(prompt)
    out = []
    for _ in range(n):
        logits, _ = model.apply(variables, jnp.asarray([ids], jnp.int32))
        tok = int(jnp.argmax(logits[0, -1]))
        out.append(tok)
        ids.append(tok)
    return out


def dense_greedy(model, variables, prompt, n: int, max_len: int) -> list:
    """``n`` greedy tokens through the model's two cache entry points over
    DENSE caches ``[L, 1, max_len, *row]``, the whole prompt one chunk: a
    cached run with no pages, tables or write maps in it."""
    spec = KVCacheSpec.from_model(model)
    k_row, v_row = spec.row_shapes()
    k = jnp.zeros((spec.num_layers, 1, max_len) + k_row, spec.dtype)
    v = jnp.zeros((spec.num_layers, 1, max_len) + v_row, spec.dtype)
    # a model may return a fourth value, its per-call counts
    logits, k, v, *_ = jax.jit(model.prefill_chunk_with_cache)(
        variables, jnp.asarray([prompt], jnp.int32), k, v, jnp.int32(0))
    toks = [int(jnp.argmax(logits[0]))]
    step = jax.jit(model.decode_with_cache)
    for i in range(n - 1):
        logits, k, v, *_ = step(
            variables, jnp.asarray(toks[-1:], jnp.int32), k, v,
            jnp.asarray([len(prompt) + i], jnp.int32))
        toks.append(int(jnp.argmax(logits[0])))
    return toks


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


def submit_and_hold_mid_decode(member, requests, steps: int = 3) -> None:
    """Submit ``requests`` to ``member`` and let its engine loop take exactly
    ``steps`` scheduler steps, then hold it BETWEEN steps (outside the
    scheduler's lock) until its server stops.  Three steps admit two
    requests, prefill one prompt chunk each and decode a round or two: every
    request is then mid-decode, whatever a step costs on this machine, and
    stays so while the test drains the member.  (Catching a 12-token request
    mid-decode by polling raced the loop for the scheduler's lock.)"""
    permits = threading.Semaphore(0)
    stop = member.server._stop
    real_step = member.scheduler.step
    taken = []

    def step():
        while not permits.acquire(timeout=0.02):
            if stop.is_set():  # drained and closing: nothing left to hold
                break
        try:
            return real_step()
        finally:
            taken.append(1)

    member.scheduler.step = step
    for r in requests:
        member.scheduler.submit(r)
    for _ in range(steps):
        permits.release()
    deadline = time.monotonic() + 60
    while len(taken) < steps:
        assert time.monotonic() < deadline, "the engine loop never stepped"
        time.sleep(0.005)
    assert all(r.tokens and not r.done.is_set() for r in requests)


# ---- a chunk's attention over a long view: the kernel beside the walk ----

def chunk_plans(monkeypatch) -> list:
    """The ``chunk_attn.plan`` instants of the programs traced from here on
    (``ops.chunk_plan``: one an attention built)."""
    import sys

    seen = []
    monkeypatch.setattr(
        sys.modules["hetu_tpu.ops.attention"].trace, "instant",
        lambda name, attrs=None, cat="hetu": seen.append(attrs)
        if name == "chunk_attn.plan" else None)
    return seen


def on_a_tpu(monkeypatch, tpu: bool = True) -> None:
    """The rules of ``ops.attention`` read the backend: say TPU, and a chunk
    over a long view takes the flash forward kernel and a decode round the
    paged one (both interpreted on this CPU)."""
    import sys

    monkeypatch.setattr(sys.modules["hetu_tpu.ops.attention"],
                        "_default_backend_is_tpu", lambda: tpu)


def chunk_kernel_beside_the_walk(monkeypatch, model, variables, prompt,
                                 n: int, **engine_kw):
    """One request of several chunks through an engine whose chunk programs
    walk the view with the XLA loop (this CPU's rule) and through one whose
    chunk programs take the kernel: the worst distance between their logits
    (every chunk's and ``n - 1`` decode rounds'), as a share of the logits'
    range, with both runs' tokens and each run's plans."""
    plans = chunk_plans(monkeypatch)
    on_a_tpu(monkeypatch, False)
    walk, _ = engine_logits(model, variables, prompt, n, **engine_kw)
    walk_plans = list(plans)
    del plans[:]
    on_a_tpu(monkeypatch)
    kernel, _ = engine_logits(model, variables, prompt, n, **engine_kw)
    assert len(walk) == len(kernel) > n
    worst = max(float(np.max(np.abs(a.astype(np.float32)
                                    - b.astype(np.float32)))
                      / (b.max() - b.min()).astype(np.float32))
                for a, b in zip(kernel, walk))
    tokens = [[int(np.argmax(row[0])) for row in rows[-n:]]
              for rows in (walk, kernel)]
    return worst, tokens, walk_plans, list(plans)


# ---- experts past the grouped kernels' whole-weight limit (ISSUE 55)

def cut_tiny_experts(monkeypatch, hidden: int = 32, ffn: int = 16):
    """The limits of ``ops.moe_ops`` and the grouped kernels scaled down to
    float32 experts of ``hidden x ffn``: past the whole-weight limit
    (``held_expert_path`` says ``"cut"``), an F tile of 4 columns, so a
    visit walks ``ffn / 4``, row tiles of 8 and a row budget in tiles of
    16, so that a round's pairs fit one trip and a chunk's do not, as the
    cells'.  The walk is a jitted function of shapes alone: the caller
    empties JAX's caches on the way in and out."""
    from hetu_tpu.ops import moe_ops
    from hetu_tpu.ops.pallas_kernels import grouped_matmul

    monkeypatch.setattr(moe_ops, "GROUPED_MAX_WEIGHT", hidden * ffn // 3)
    monkeypatch.setattr(grouped_matmul, "_LANES", 4)
    monkeypatch.setattr(grouped_matmul, "CUT_TILE_ROWS", 8)
    monkeypatch.setattr(grouped_matmul, "TILE_ROWS", 16)
    for budget in ("_FFN_WEIGHT_BYTES", "_CUT_WEIGHT_BYTES"):
        monkeypatch.setattr(grouped_matmul, budget, 3 * hidden * 4 * 4 * 2)
    assert moe_ops.held_expert_path(1, 2, 4, hidden, ffn) == "cut"
    assert grouped_matmul.ffn_tiles(hidden, ffn, 4) == (8, 4)


def loop_evaluates(monkeypatch):
    """The cut path's evaluation put back on the loop's forward, which since
    ISSUE 55 only reverse mode reaches."""
    from hetu_tpu.ops import moe_ops

    monkeypatch.setattr(
        moe_ops, "_held", lambda x, w, idx, wg, wu, wd, layer, first, R, B:
        moe_ops._held_forward(x, w, idx, wg, wu, wd, layer, first, R)[0])
