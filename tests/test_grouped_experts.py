"""The grouped path of the held-expert walk (``ops.moe_ops.held_expert_ffn``:
sort once, grouped matmuls over each expert's contiguous rows, combine once)
against the loop path and against the dense composition, values and
gradients, at routings chosen to sit on and off a row tile's boundary and to
take more than one trip; a serving call's shapes, where one trip holds every
pair and a visit is a whole expert; and the static rule that sends a shape
down one path or the other.  CPU: the Pallas kernels run in interpret
mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.ops import moe_ops
from hetu_tpu.ops.pallas_kernels import grouped_matmul
from paged_programs import _sub_jaxprs

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
    experts fit it, and none fits a limit of nothing."""
    monkeypatch.setattr(moe_ops, "GROUPED_MAX_WEIGHT",
                        4 << 20 if path == "grouped" else 0)
    assert moe_ops.held_expert_path(T, K, E, H, F) == path
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
        monkeypatch.setattr(moe_ops, "GROUPED_MAX_WEIGHT",
                            4 << 20 if path == "grouped" else 0)
        assert moe_ops.held_expert_path(t, S_K, S_E, S_H, S_F) == path

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
    # an expert's weight too large to keep whole in VMEM: the loop, at any T
    ("k-exaone batch-mixed decode", 16, 8, 128, 16, 6144, 2048, "loop"),
    ("k-exaone batch-mixed chunk", 512, 8, 128, 16, 6144, 2048, "loop"),
    ("longcat batch-long decode", 16, 12, 768, 16, 6144, 2048, "loop"),
    ("longcat batch-long chunk", 512, 12, 768, 16, 6144, 2048, "loop"),
    ("a 6144 x 2048 expert at a step's tokens", 16384, 8, 64, 16, 6144, 2048,
     "loop"),
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
    holds every pair), the loop's holds no Pallas call."""
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
        0 if path == "loop" else 1 if whole else 3)
    if name in TRAINED:
        assert budget == TRAINED[name]
    if name.startswith("lfm2"):
        # one trip for every serving shape
        assert whole and budget - t * k < tile
    elif path == "grouped":
        assert t * k * e // routed <= budget * -(
            -t * k * e // routed // budget) < t * k
