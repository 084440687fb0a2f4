"""The grouped path of the held-expert walk (``ops.moe_ops.held_expert_ffn``:
sort once, grouped matmuls over each expert's contiguous rows, combine once)
against the loop path and against the dense composition, values and
gradients, at routings chosen to sit on and off a row tile's boundary and to
take more than one trip; and the static rule that sends a shape down one
path or the other.  CPU: the Pallas kernels run in interpret mode."""

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


def _dense(x, w, idx, wg, wu, wd, layer):
    """Every pair through its expert, no sort and no loop: float32."""
    if layer is not None:
        wg, wu, wd = wg[layer], wu[layer], wd[layer]
    f32 = jnp.float32
    x, wg, wu, wd = (a.astype(f32) for a in (x, wg, wu, wd))
    local = idx - FIRST
    held = (local >= 0) & (local < E)
    e = jnp.clip(local, 0, E - 1)                         # [T, K]
    g = jnp.einsum("th,tkhf->tkf", x, wg[e])
    u = jnp.einsum("th,tkhf->tkf", x, wu[e])
    y = jnp.einsum("tkf,tkfh->tkh", jax.nn.silu(g) * u, wd[e])
    out = jnp.sum(jnp.where(held[..., None], w[..., None] * y, 0.0), 1)
    return out, jnp.sum(held[..., None] & (e[..., None] == jnp.arange(E)),
                        (0, 1))


def _value_and_grads(fn, idx, operands):
    probe = jnp.cos(jnp.arange(T * H, dtype=jnp.float32)).reshape(T, H)

    def loss(x, w, wg, wu, wd):
        out, counts = fn(x, w, idx, wg, wu, wd)
        return jnp.sum(out.astype(jnp.float32) * probe), (out, counts)

    (_, (out, counts)), grads = jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(*operands)
    return (out, *grads), counts


def _walk(monkeypatch, path, layer):
    """``held_expert_ffn`` held to ``path`` by the rule's own threshold."""
    monkeypatch.setattr(moe_ops, "GROUPED_MIN_PAIRS_AN_EXPERT",
                        1 if path == "grouped" else 10 ** 9)
    assert moe_ops.held_expert_path(T, K, E, H, F) == path
    return lambda x, w, idx, wg, wu, wd: moe_ops.held_expert_ffn(
        x, w, idx, wg, wu, wd, first=FIRST, block_rows=TILE, layer=layer,
        routed=ROUTED)


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


# (cell and program, T, k, held experts, hidden, expert FFN): the shapes the
# benchmark's four expert cells hand to held_expert_ffn
SHAPES = [
    ("kanana train-ep8 step", 16384, 6, 16, 2048, 768, "grouped"),
    ("mellum train-ep4 step", 16384, 8, 16, 2304, 896, "grouped"),
    ("k-exaone batch-mixed decode", 16, 8, 16, 6144, 2048, "loop"),
    ("k-exaone batch-mixed chunk", 512, 8, 16, 6144, 2048, "loop"),
    ("longcat batch-long decode", 16, 12, 16, 6144, 2048, "loop"),
    ("longcat batch-long chunk", 512, 12, 16, 6144, 2048, "loop"),
    # an expert's weight too large to keep whole in VMEM: the loop, at any T
    ("a 6144 x 2048 expert at a step's tokens", 16384, 8, 16, 6144, 2048,
     "loop"),
]


@pytest.mark.parametrize("name,t,k,e,h,f,path", SHAPES,
                         ids=[s[0] for s in SHAPES])
def test_the_rule_sends_training_shapes_to_the_grouped_path_and_serving_to_the_loop(
        name, t, k, e, h, f, path):
    """Static shapes alone decide, and the program says which it was: the
    grouped path's is Pallas calls around one scatter-add a trip, the
    loop's holds no Pallas call."""
    assert moe_ops.held_expert_path(t, k, e, h, f) == path
    bf16 = jnp.bfloat16
    args = [jax.ShapeDtypeStruct(s, d) for s, d in (
        ((t, h), bf16), ((t, k), jnp.float32), ((t, k), jnp.int32),
        ((e, h, f), bf16), ((e, h, f), bf16), ((e, f, h), bf16))]
    closed = jax.make_jaxpr(lambda *a: moe_ops.held_expert_ffn(
        *a, first=0, block_rows=128))(*args)
    assert _pallas_calls(closed.jaxpr) == (3 if path == "grouped" else 0)
    # a trip holds a static number of rows, well under every pair
    budget = moe_ops.grouped_row_budget(t, k, e, 4 * e)
    assert budget % grouped_matmul.TILE_ROWS == 0
    assert budget <= moe_ops.GROUPED_ROW_BUDGET + grouped_matmul.TILE_ROWS
    if path == "grouped":
        assert t * k // 4 <= budget * -(-t * k // 4 // budget) < t * k
