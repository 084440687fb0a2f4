"""The grouped path of the held-expert walk (``ops.moe_ops.held_expert_ffn``:
sort once, grouped matmuls over each expert's contiguous rows, combine once)
against the loop path and against the dense composition, values and
gradients, at routings chosen to sit on and off a row tile's boundary and to
take more than one trip; a serving call's shapes, where one trip holds every
pair and a visit is a whole expert; the fused call CUT ALONG F, which
evaluates the experts too wide to keep whole (the loop is their reverse
mode's); and the static rule that sends a shape down one path or the other.
CPU: the Pallas kernels run in interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.ops import moe_ops
from hetu_tpu.ops.pallas_kernels import grouped_matmul
from paged_programs import _sub_jaxprs, all_eqns, pallas_grids

T, K, ROUTED, FIRST, E, H, F = 32, 2, 8, 2, 3, 16, 8
TILE, BUDGET = 8, 24


@pytest.fixture
def small_tiles(monkeypatch):
    """Tiles of 8 rows and trips of 24, so that 64 pairs cross both.  The
    walk is a jitted function and JAX keeps its trace by shapes and static
    arguments, not by the tile: the caches are emptied on the way in and
    out, so that no trace outlives the tile it was made with."""
    monkeypatch.setattr(grouped_matmul, "TILE_ROWS", TILE)
    monkeypatch.setattr(moe_ops, "GROUPED_ROW_BUDGET", BUDGET)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _routing(name: str):
    """idx [T, K] over ``ROUTED`` experts of which ``FIRST .. FIRST + E - 1``
    are held."""
    rng = np.random.default_rng(7)
    absent = [e for e in range(ROUTED) if not FIRST <= e < FIRST + E]
    if name == "even":
        return rng.integers(0, ROUTED, (T, K))
    if name == "one expert":
        return np.full((T, K), FIRST + 1)
    if name == "none held":
        return rng.choice(absent, (T, K))
    counts = {"on the boundary": (8, 16, 8), "one off": (9, 15, 8)}[name]
    flat = rng.choice(absent, T * K)
    where = rng.permutation(T * K)[:sum(counts)]
    flat[where] = np.repeat(np.arange(FIRST, FIRST + E), counts)
    return flat.reshape(T, K)


def _operands(dtype, layer):
    rng = np.random.default_rng(3)

    def draw(*shape, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape) * scale, dtype)

    lead = () if layer is None else (3,)
    return (draw(T, H), jnp.asarray(rng.uniform(0.1, 1.0, (T, K)),
                                    jnp.float32),
            draw(*lead, E, H, F, scale=0.3), draw(*lead, E, H, F, scale=0.3),
            draw(*lead, E, F, H, scale=0.3))


def _dense(x, w, idx, wg, wu, wd, layer, first=FIRST):
    """Every pair through its expert, no sort and no loop: float32."""
    if layer is not None:
        wg, wu, wd = wg[layer], wu[layer], wd[layer]
    f32 = jnp.float32
    x, wg, wu, wd = (a.astype(f32) for a in (x, wg, wu, wd))
    E = wg.shape[0]
    local = idx - first
    held = (local >= 0) & (local < E)
    e = jnp.clip(local, 0, E - 1)                         # [T, K]
    g = jnp.einsum("th,tkhf->tkf", x, wg[e])
    u = jnp.einsum("th,tkhf->tkf", x, wu[e])
    y = jnp.einsum("tkf,tkfh->tkh", jax.nn.silu(g) * u, wd[e])
    out = jnp.sum(jnp.where(held[..., None], w[..., None] * y, 0.0), 1)
    return out, jnp.sum(held[..., None] & (e[..., None] == jnp.arange(E)),
                        (0, 1))


def _value_and_grads(fn, idx, operands):
    x = operands[0]
    probe = jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape)

    def loss(x, w, wg, wu, wd):
        out, counts = fn(x, w, idx, wg, wu, wd)
        return jnp.sum(out.astype(jnp.float32) * probe), (out, counts)

    (_, (out, counts)), grads = jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(*operands)
    return (out, *grads), counts


def _walk(monkeypatch, path, layer, first=FIRST, routed=ROUTED):
    """``held_expert_ffn`` held to ``path`` by the rule's own limit: these
    experts fit it, and none fits a limit of nothing.  ``"loop"``: the path
    of experts past the limit (``"cut"``) as reverse mode runs it, which is
    how every caller here runs it."""
    monkeypatch.setattr(moe_ops, "GROUPED_MAX_WEIGHT",
                        4 << 20 if path == "grouped" else 0)
    assert moe_ops.held_expert_path(T, K, E, H, F) == {
        "loop": "cut"}.get(path, path)
    return lambda x, w, idx, wg, wu, wd: moe_ops.held_expert_ffn(
        x, w, idx, wg, wu, wd, first=first, block_rows=TILE, layer=layer,
        routed=routed)


CASES = [(r, layer, jnp.float32)
         for r in ("even", "one expert", "none held", "on the boundary",
                   "one off") for layer in (None, 1)] \
    + [("even", None, jnp.bfloat16)]


@pytest.mark.parametrize(
    "routing,layer,dtype", CASES,
    ids=[f"{r}-{'stacked' if l is not None else 'one layer'}-{d.__name__}"
         for r, l, d in CASES])
def test_the_grouped_path_equals_the_loop_and_the_dense_composition(
        routing, layer, dtype, small_tiles, monkeypatch):
    idx = jnp.asarray(_routing(routing), jnp.int32)
    operands = _operands(dtype, layer)
    grouped, counts = _value_and_grads(
        _walk(monkeypatch, "grouped", layer), idx, operands)
    loop, loop_counts = _value_and_grads(
        _walk(monkeypatch, "loop", layer), idx, operands)
    dense, dense_counts = _value_and_grads(
        lambda *a: _dense(*a, layer), idx, operands)
    assert counts.tolist() == loop_counts.tolist() == dense_counts.tolist()
    held = int(counts.sum())
    assert held == {"one expert": T * K, "none held": 0,
                    "on the boundary": 32, "one off": 32}.get(routing, held)
    if routing != "none held":
        # more than one trip, and an expert's rows over a tile's boundary
        assert held > moe_ops.grouped_row_budget(T, K, E, ROUTED) >= TILE
    tol = 2e-5 if dtype == jnp.float32 else 6e-2
    for name, got, want, ref in zip(
            ("out", "dx", "dweights", "dgate", "dup", "ddown"), grouped,
            loop, dense):
        assert got.shape == want.shape and got.dtype == want.dtype, name
        got, want, ref = (np.asarray(a, np.float32) for a in (got, want, ref))
        scale = max(1.0, float(np.abs(ref).max()))
        assert np.abs(got - want).max() <= tol * scale, name
        assert np.abs(got - ref).max() <= tol * scale, name
    if layer is not None:
        # a stacked leaf's gradient is zero outside the layer walked
        for g in grouped[3:]:
            assert not np.asarray(g[0]).any() and not np.asarray(g[2]).any()


# ---- a serving call: every expert held, one trip, a whole expert a visit

S_K, S_E, S_H, S_F = 4, 32, 32, 24
# (tokens, routing, stacked at a traced layer, gradients too)
SERVING = [
    (1, "top", True, False),              # 4 pairs: under one tile
    (4, "top", False, True),
    (64, "top", True, False),             # a decode round at every slot
    (200, "top", True, True),             # a chunk, its bucket no power of 2
    (64, "few", True, False),             # experts 8.. chosen by nobody
    (16, "one expert", False, False),     # one expert takes every pair
]


def _serving_case(t: int, routing: str, stacked: bool):
    rng = np.random.default_rng(11)

    def draw(*shape, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape) * scale, jnp.float32)

    pool = {"top": S_E, "few": 8}.get(routing)
    idx = np.full((t, S_K), 5) if pool is None else np.stack(
        [rng.permutation(pool)[:S_K] for _ in range(t)])
    lead = (3,) if stacked else ()
    return jnp.asarray(idx, jnp.int32), (
        draw(t, S_H), jnp.asarray(rng.uniform(0.1, 1.0, (t, S_K)),
                                  jnp.float32),
        draw(*lead, S_E, S_H, S_F, scale=0.3),
        draw(*lead, S_E, S_H, S_F, scale=0.3),
        draw(*lead, S_E, S_F, S_H, scale=0.3))


@pytest.mark.parametrize(
    "t,routing,stacked,grads", SERVING,
    ids=[f"T{t}-{r}-{'stacked' if s else 'one layer'}" for t, r, s, _ in
         SERVING])
def test_a_serving_call_on_the_grouped_path_equals_the_loop_and_the_dense_composition(
        t, routing, stacked, grads, monkeypatch):
    """All 32 experts held, 4 choices a token: one trip holds every pair
    (``grouped_row_budget`` >= ``T * k``), so the walk is ONE grouped call,
    a whole expert a visit, and each token gathers its ``k`` rows back.
    Stacked leaves are read at a TRACED layer, as a program of several
    expert layers hands it over."""
    idx, operands = _serving_case(t, routing, stacked)
    budget = moe_ops.grouped_row_budget(t, S_K, S_E, S_E)
    assert 0 <= budget - t * S_K < grouped_matmul.TILE_ROWS

    def walk(path):
        # "loop": the loop's forward itself, which since ISSUE 55 only
        # reverse mode reaches (the cases without gradients would otherwise
        # evaluate by the cut path's fused call)
        monkeypatch.setattr(moe_ops, "GROUPED_MAX_WEIGHT",
                            4 << 20 if path == "grouped" else 0)
        assert moe_ops.held_expert_path(t, S_K, S_E, S_H, S_F) == {
            "loop": "cut"}.get(path, path)
        if path == "loop" and not grads:
            return jax.jit(lambda x, w, idx, wg, wu, wd, layer:
                           moe_ops._held_forward(
                               x, w, idx, wg, wu, wd,
                               layer if stacked else None, 0, 8)[0])

        def fn(x, w, idx, wg, wu, wd, layer):
            return moe_ops.held_expert_ffn(
                x, w, idx, wg, wu, wd, first=0, block_rows=8, routed=S_E,
                layer=layer if stacked else None)
        return jax.jit(fn)

    def run(fn):
        call = lambda x, w, idx, wg, wu, wd: fn(x, w, idx, wg, wu, wd,
                                                jnp.int32(1))
        if grads:
            return _value_and_grads(call, idx, operands)
        out, counts = call(operands[0], operands[1], idx, *operands[2:])
        return (out,), counts

    grouped_fn = walk("grouped")
    closed = jax.make_jaxpr(grouped_fn)(operands[0], operands[1], idx,
                                        *operands[2:], jnp.int32(1))
    assert _pallas_calls(closed.jaxpr) == 1
    grouped, counts = run(grouped_fn)
    loop, loop_counts = run(walk("loop"))
    dense, dense_counts = run(
        lambda x, w, idx, wg, wu, wd, layer: _dense(
            x, w, idx, wg, wu, wd, 1 if stacked else None, first=0))
    assert counts.tolist() == loop_counts.tolist() == dense_counts.tolist()
    assert int(counts.sum()) == t * S_K                   # no pair dropped
    assert int((counts > 0).sum()) == {"few": 8, "one expert": 1}.get(
        routing, int((counts > 0).sum()))
    for name, got, want, ref in zip(
            ("out", "dx", "dweights", "dgate", "dup", "ddown"), grouped,
            loop, dense):
        assert got.shape == want.shape and got.dtype == want.dtype, name
        got, want, ref = (np.asarray(a, np.float32) for a in (got, want, ref))
        scale = max(1.0, float(np.abs(ref).max()))
        assert np.abs(got - want).max() <= 2e-5 * scale, name
        assert np.abs(got - ref).max() <= 2e-5 * scale, name


def _period(walk, idx, layers: int = 4):
    """A scan body of ``layers`` expert layers, each under its own
    ``ops.remat`` and each with its own weights (leaves stacked
    [layers, E, ...], sliced by a static index, as ``models/mellum.py``
    hands them over): (loss, each layer's result [layers, T, H])."""
    from hetu_tpu import ops

    probe = jnp.sin(jnp.arange(T * H, dtype=jnp.float32)).reshape(T, H)

    def layer(l):
        def run(h, w, wg, wu, wd):
            out, _ = walk(h, w, idx, wg[l], wu[l], wd[l])
            return h + out.astype(h.dtype), out
        return ops.remat(run)

    runs = [layer(l) for l in range(layers)]

    def loss(x, w, wg, wu, wd):
        h, outs = x, []
        for run in runs:
            h, out = run(h, w, wg, wu, wd)
            outs.append(out)
        return jnp.sum(h.astype(jnp.float32) * probe), jnp.stack(outs)

    return loss


def _walk_jaxprs(jaxpr, name: str):
    """The jaxpr of every jitted call named ``name`` in ``jaxpr``, bodies of
    loops, calls and checkpoints included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "jit" and eqn.params["name"] == name:
            yield eqn.params["jaxpr"]
        for sub in _sub_jaxprs(eqn.params):
            yield from _walk_jaxprs(sub, name)


def _pallas_grids(jaxpr) -> list:
    """The grid rank of every ``pallas_call`` in ``jaxpr``."""
    return [len(grid) for grid in pallas_grids(jaxpr)]


def _pallas_calls(jaxpr) -> int:
    return sum((eqn.primitive.name == "pallas_call")
               + sum(map(_pallas_calls, _sub_jaxprs(eqn.params)))
               for eqn in jaxpr.eqns)


def test_the_layers_of_a_scan_body_share_one_traced_walk_and_keep_their_results(
        small_tiles, monkeypatch):
    """Four expert layers at one shape, each under its own ``ops.remat``:
    the grouped path is ONE traced forward and ONE traced backward that all
    four call (so its kernels are lowered once a program), and every layer
    still computes with its own weights: results and gradients, layer by
    layer, are the loop path's."""
    idx = jnp.asarray(_routing("even"), jnp.int32)
    x, w, *leaves = _operands(jnp.float32, 1)             # [3, E, ...] leaves
    leaves = [jnp.concatenate([a, a[:1] * 0.5]) for a in leaves]   # four

    def run(path):
        loss = _period(_walk(monkeypatch, path, None), idx)
        both = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4),
                                  has_aux=True)
        (_, outs), grads = both(x, w, *leaves)
        return (outs, *grads), jax.make_jaxpr(both)(x, w, *leaves).jaxpr

    grouped, jaxpr = run("grouped")
    for name, pallas in (("_grouped_forward", 3), ("_grouped_backward", 7)):
        walks = list(_walk_jaxprs(jaxpr, name))
        held = [j for j in walks if _pallas_calls(j.jaxpr) == pallas]
        # four calls of one jaxpr hold the kernels (what else a checkpoint
        # splits off the forward, the sort and the counts, holds none)
        assert len(held) == 4 and len({id(j) for j in held}) == 1, name
    loop, jaxpr = run("loop")
    assert not list(_walk_jaxprs(jaxpr, "_grouped_forward"))
    outs = np.asarray(grouped[0])
    for a in range(4):
        for b in range(a):
            assert np.abs(outs[a] - outs[b]).max() > 1e-3     # four results
    for name, got, want in zip(
            ("out", "dx", "dweights", "dgate", "dup", "ddown"), grouped,
            loop):
        got, want = np.asarray(got), np.asarray(want)
        assert got.shape == want.shape, name
        scale = max(1.0, float(np.abs(want).max()))
        assert np.abs(got - want).max() <= 2e-5 * scale, name
        if name in ("out", "dgate", "dup", "ddown"):          # layer by layer
            assert all(np.abs(got[l]).max() > 0 for l in range(4)), name


# (cell and program, T, k, the router's width, held experts, hidden, expert
# FFN): the shapes the benchmark's five expert cells hand to held_expert_ffn
SHAPES = [
    ("kanana train-ep8 step", 16384, 6, 128, 16, 2048, 768, "grouped"),
    ("mellum train-ep4 step", 16384, 8, 64, 16, 2304, 896, "grouped"),
    ("lfm2 batch-docs decode at one slot", 1, 4, 32, 32, 2048, 1792,
     "grouped"),
    ("lfm2 batch-docs decode", 64, 4, 32, 32, 2048, 1792, "grouped"),
    ("lfm2 batch-docs chunk", 2048, 4, 32, 32, 2048, 1792, "grouped"),
    # an expert's weight too large to keep whole in VMEM: evaluated by the
    # fused call cut along F, at any T
    ("k-exaone batch-mixed decode", 16, 8, 128, 16, 6144, 2048, "cut"),
    ("k-exaone batch-mixed chunk", 512, 8, 128, 16, 6144, 2048, "cut"),
    ("longcat batch-long decode", 16, 12, 768, 16, 6144, 2048, "cut"),
    ("longcat batch-long chunk", 512, 12, 768, 16, 6144, 2048, "cut"),
    ("a 6144 x 2048 expert at a step's tokens", 16384, 8, 64, 16, 6144, 2048,
     "cut"),
    # too large whole, and no whole-lane tile divides its F: the loop
    # evaluates too (no published width)
    ("a 6144 x 2000 expert", 16, 8, 128, 16, 6144, 2000, "loop"),
]
# the rows a trip of the two training cells' walks holds, which this rule
# must keep
TRAINED = {"kanana train-ep8 step": 13824, "mellum train-ep4 step": 18432}


@pytest.mark.parametrize("name,t,k,routed,e,h,f,path", SHAPES,
                         ids=[s[0] for s in SHAPES])
def test_the_rule_sends_experts_that_fit_the_kernels_to_the_grouped_path(
        name, t, k, routed, e, h, f, path):
    """Static shapes alone decide, at any row count, and the program says
    which it was: the grouped path's is Pallas calls (three a trip and a
    scatter-add where a chip holds a share of the experts, ONE where a trip
    holds every pair); the cut path's evaluation is ONE call a trip in
    either form, over ``F / f_t`` steps a visit."""
    assert moe_ops.held_expert_path(t, k, e, h, f) == path
    bf16 = jnp.bfloat16
    args = [jax.ShapeDtypeStruct(s, d) for s, d in (
        ((t, h), bf16), ((t, k), jnp.float32), ((t, k), jnp.int32),
        ((e, h, f), bf16), ((e, h, f), bf16), ((e, f, h), bf16))]
    closed = jax.make_jaxpr(lambda *a: moe_ops.held_expert_ffn(
        *a, first=0, block_rows=128, routed=routed))(*args)
    # a trip holds a static number of rows, in whole tiles
    budget = moe_ops.grouped_row_budget(t, k, e, routed)
    tile = grouped_matmul.TILE_ROWS
    assert tile == 256 and budget % tile == 0
    assert budget <= moe_ops.GROUPED_ROW_BUDGET + tile
    whole = budget >= t * k
    assert _pallas_calls(closed.jaxpr) == (
        0 if path == "loop" else 1 if path == "cut" or whole else 3)
    if path == "cut":
        rows, f_t = grouped_matmul.ffn_tiles(h, f)
        assert (rows, f_t) == (grouped_matmul.CUT_TILE_ROWS, 1024) == (128, 1024)
        assert _pallas_grids(closed.jaxpr) == [2]     # (visits, F tiles)
        # K-EXAONE's and LongCat's rounds take the one-trip form, their
        # chunks the trips form (``routed`` is always given)
        assert whole == ("decode" in name)
    elif path == "loop":
        assert grouped_matmul.ffn_tiles(h, f) is None
    else:
        assert grouped_matmul.ffn_tiles(h, f) == (tile, f)
    if name in TRAINED:
        assert budget == TRAINED[name]
    if name.startswith("lfm2"):
        # one trip for every serving shape
        assert whole and budget - t * k < tile
    elif path == "grouped":
        assert t * k * e // routed <= budget * -(
            -t * k * e // routed // budget) < t * k


# ---- the fused call cut along F (ISSUE 55): experts too wide to keep whole

@pytest.fixture
def narrow_vmem(monkeypatch):
    """A weight budget that holds an F tile of 8 columns of the experts
    below and no more, tiles of 8 rows and of 4 lanes: ``F = 4 f_t`` at
    sizes the interpreter walks in a second."""
    monkeypatch.setattr(grouped_matmul, "CUT_TILE_ROWS", 8)
    monkeypatch.setattr(grouped_matmul, "_LANES", 4)
    for budget in ("_FFN_WEIGHT_BYTES", "_CUT_WEIGHT_BYTES"):
        monkeypatch.setattr(grouped_matmul, budget, 3 * C_H * 8 * 4 * 2)
    jax.clear_caches()
    yield
    jax.clear_caches()


C_H, C_F, C_G = 16, 32, 5


def _cut_reference(rows, wg, wu, wd, scale, starts, ends):
    """Every row through the group that owns it, in float32; rows no group
    owns are zero."""
    out = np.zeros((rows.shape[0], wg.shape[-2]), np.float32)
    for g, (s, e) in enumerate(zip(starts, ends)):
        x = np.asarray(rows, np.float32)[s:e]
        gate, up = x @ np.asarray(wg[g]), x @ np.asarray(wu[g])
        a = gate / (1.0 + np.exp(-gate)) * up
        out[s:e] = (a @ np.asarray(wd[g])) * np.asarray(scale)[s:e, None]
    return out


@pytest.mark.parametrize("stacked", [False, True],
                         ids=["one layer", "stacked"])
def test_the_cut_call_equals_a_plain_reference(stacked, narrow_vmem):
    """``gmm_ffn`` over experts whose three weights pass the budget: four F
    tiles a visit (``F = 4 f_t``), a row tile that three groups share, a
    group without rows (never visited, its weights never read) and two
    tiles past the last group's rows, which stay unwritten; the leaves read
    in place from a stacked ``[L, G, ...]`` operand at a traced layer."""
    assert grouped_matmul.ffn_tiles(C_H, C_F, 4) == (8, 8)
    rng = np.random.default_rng(5)
    M = 40
    # rows 0-2 | 3-6 | (none) | 7-19 | 20-22: tile 0 holds three groups,
    # group 3 spans three tiles, tiles 3 and 4 hold no group's row
    starts, ends = [0, 3, 7, 7, 20], [3, 7, 7, 20, 23]
    rows = jnp.asarray(rng.standard_normal((M, C_H)), jnp.float32)
    scale = jnp.asarray(rng.uniform(0.1, 1.0, (M,)), jnp.float32)
    lead = (3,) if stacked else ()
    wg, wu, wd = (jnp.asarray(rng.standard_normal(lead + shape) * 0.3,
                              jnp.float32)
                  for shape in ((C_G, C_H, C_F), (C_G, C_H, C_F),
                                (C_G, C_F, C_H)))
    # the group without rows holds what would poison any row that read it
    poison = (slice(None),) * len(lead) + (2,)
    wg = wg.at[poison].set(jnp.nan)

    def call(rows, wg, wu, wd, scale, layer):
        visits = grouped_matmul.group_visits(
            jnp.asarray(starts), jnp.asarray(ends), M,
            grouped_matmul.CUT_TILE_ROWS)
        return grouped_matmul.gmm_ffn(rows, wg, wu, wd, scale, visits,
                                      layer=layer if stacked else None)

    closed = jax.make_jaxpr(call)(rows, wg, wu, wd, scale, jnp.int32(1))
    assert _pallas_grids(closed.jaxpr) == [2]
    got = np.asarray(jax.jit(call)(rows, wg, wu, wd, scale, jnp.int32(1)))
    one = (lambda w: w[1]) if stacked else (lambda w: w)
    want = _cut_reference(rows, one(wg), one(wu), one(wd), scale, starts,
                          ends)
    written = np.arange(M) < 24                 # the three visited tiles
    assert np.abs(got[written] - want[written]).max() <= 2e-5 * max(
        1.0, np.abs(want).max())
    assert not want[23:24].any() and not got[23:24].any()   # owned by none


# experts past the rule's real limit: 2048 x 2560 = 5 Mi, float32, of which
# an F tile of 1,280 columns fits the kernels' budget: F = 2 f_t.  (name,
# tokens, choices, the router's width, held from, held)
WIDE_H, WIDE_F = 2048, 2560
WIDE = [("a round: one trip", 16, 2, 8, 2, 2),
        ("a chunk: the trips form", 512, 2, 8, 2, 2)]


@pytest.mark.parametrize("name,t,k,routed,first,e", WIDE,
                         ids=[w[0] for w in WIDE])
def test_experts_past_the_limit_are_evaluated_by_the_cut_call_as_the_loop_computes(
        name, t, k, routed, first, e):
    """``held_expert_ffn`` over experts of ``H * F > 4 Mi`` with nothing
    patched: evaluated, the fused call cut along F in either forward form,
    equal to the loop path's output and counts."""
    assert WIDE_H * WIDE_F > moe_ops.GROUPED_MAX_WEIGHT
    assert moe_ops.held_expert_path(t, k, e, WIDE_H, WIDE_F) == "cut"
    assert grouped_matmul.ffn_tiles(WIDE_H, WIDE_F, 4) == (128, 1280)
    budget = moe_ops.grouped_row_budget(t, k, e, routed)
    assert (budget >= t * k) == name.startswith("a round")
    rng = np.random.default_rng(13)
    f32 = jnp.float32
    x = jnp.asarray(rng.standard_normal((t, WIDE_H)), f32)
    w = jnp.asarray(rng.uniform(0.1, 1.0, (t, k)), f32)
    idx = jnp.asarray(np.stack([rng.permutation(routed)[:k]
                                for _ in range(t)]), jnp.int32)
    wg, wu, wd = (jnp.asarray(rng.standard_normal(shape) * 0.02, f32)
                  for shape in ((e, WIDE_H, WIDE_F), (e, WIDE_H, WIDE_F),
                                (e, WIDE_F, WIDE_H)))
    walk = jax.jit(lambda *a: moe_ops.held_expert_ffn(
        *a, first=first, routed=routed))
    closed = jax.make_jaxpr(walk)(x, w, idx, wg, wu, wd)
    assert _pallas_grids(closed.jaxpr) == [2]
    out, counts = walk(x, w, idx, wg, wu, wd)
    (want, want_counts), _ = jax.jit(
        lambda *a: moe_ops._held_forward(*a, None, first, 128))(
            x, w, idx, wg, wu, wd)
    assert counts.tolist() == want_counts.tolist()
    held = int(counts.sum())
    assert 0 < held < t * k
    out, want = np.asarray(out), np.asarray(want)
    assert np.abs(out - want).max() <= 2e-5 * max(1.0, np.abs(want).max())


def test_reverse_mode_past_the_limit_still_runs_the_loops_rules():
    """``jax.grad`` of the same call: no Pallas call, the loop's ``while``
    in both directions (``gmm_down_back`` and ``tgmm`` keep two or three
    whole weights and are not cut).  K-EXAONE's shapes, traced only."""
    t, k, e, h, f = 512, 8, 16, 6144, 2048
    bf16 = jnp.bfloat16
    args = [jax.ShapeDtypeStruct(s, d) for s, d in (
        ((t, h), bf16), ((t, k), jnp.float32), ((t, k), jnp.int32),
        ((e, h, f), bf16), ((e, h, f), bf16), ((e, f, h), bf16))]

    def loss(x, w, idx, wg, wu, wd):
        out, _ = moe_ops.held_expert_ffn(x, w, idx, wg, wu, wd, first=0,
                                         routed=128)
        return out.sum()

    evaluated = jax.make_jaxpr(loss)(*args).jaxpr
    assert _pallas_calls(evaluated) == 1
    reverse = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 3, 4, 5)))(
        *args).jaxpr
    assert _pallas_calls(reverse) == 0
    whiles = [eqn for eqn in all_eqns(reverse)
              if eqn.primitive.name == "while"]
    assert len(whiles) == 2                     # the walk, twice

