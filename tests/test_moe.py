"""MoE tests: dispatch/combine correctness, gates, EP-sharded layer on the
8-device mesh, and the MoE transformer training step.

Reference analogs: examples/moe scripts, gpu_ops/{Dispatch,LayoutTransform,
AllToAll}.py tests.
"""

import pytest

pytestmark = pytest.mark.slow

import jax
import jax.numpy as jnp
import numpy as np

import hetu_tpu as ht
from hetu_tpu import layers, optim
from hetu_tpu.layers.moe import (
    BalanceAssignmentGate, Expert, HashGate, KTop1Gate, MoELayer, SAMGate,
    TopKGate,
)
from hetu_tpu.ops.moe_ops import (
    balance_assignment, layout_transform, make_dispatch_combine,
    reverse_layout_transform, top_k_idx_gate,
)


def test_dispatch_combine_roundtrip():
    """With ample capacity, dispatch+combine must reproduce gate-weighted
    identity expert output."""
    g = np.random.default_rng(0)
    T, D, E, k = 16, 8, 4, 2
    tokens = g.standard_normal((T, D)).astype(np.float32)
    logits = g.standard_normal((T, E)).astype(np.float32)
    gates, idx = top_k_idx_gate(jnp.asarray(logits), k)
    disp, comb = make_dispatch_combine(gates, idx, E, capacity=T * k)
    xe = layout_transform(jnp.asarray(tokens), disp)
    assert xe.shape == (E, T * k, D)
    out = reverse_layout_transform(xe, comb)  # identity experts
    # each token = sum_k gate_k * token = token (gates sum to 1)
    np.testing.assert_allclose(np.asarray(out), tokens, rtol=1e-4, atol=1e-5)


def test_capacity_drops_overflow():
    T, D, E = 8, 4, 2
    tokens = jnp.ones((T, D))
    # all tokens pick expert 0
    gates = jnp.ones((T, 1))
    idx = jnp.zeros((T, 1), jnp.int32)
    disp, comb = make_dispatch_combine(gates, idx, E, capacity=3)
    out = reverse_layout_transform(layout_transform(tokens, disp), comb)
    kept = np.asarray(jnp.sum(jnp.abs(out), axis=-1) > 0)
    assert kept.sum() == 3  # first 3 in order, rest dropped (reference order)
    assert kept[:3].all()


def test_gates_shapes_and_validity():
    g = np.random.default_rng(1)
    T, D, E = 12, 16, 4
    tokens = jnp.asarray(g.standard_normal((T, D)).astype(np.float32))
    key = jax.random.PRNGKey(0)

    for gate, k_exp, inp in (
            (TopKGate(D, E, 2), 2, tokens),
            (KTop1Gate(D, E, 2), 2, tokens),
            (BalanceAssignmentGate(D, E), 1, tokens),
            (SAMGate(D, E), 1, tokens),
            (HashGate(E), 1, jnp.arange(T, dtype=jnp.int32))):
        v = gate.init(key)
        (gates, idx, aux), _ = gate.apply(v, inp)
        assert gates.shape == (T, k_exp), type(gate).__name__
        assert idx.shape == (T, k_exp)
        assert np.asarray(idx).min() >= 0 and np.asarray(idx).max() < E
        assert np.isfinite(float(jnp.sum(gates)))


def test_balance_assignment_is_balanced():
    g = np.random.default_rng(2)
    scores = jnp.asarray(g.standard_normal((32, 4)).astype(np.float32))
    idx = np.asarray(balance_assignment(scores, iters=50))
    counts = np.bincount(idx, minlength=4)
    assert counts.max() <= 2 * counts.min() + 4, counts  # roughly balanced


def test_moe_layer_ep_sharded_matches_unsharded():
    """MoE layer under an ep=8 mesh must match the unsharded result — the
    A2A-inserted path is numerically identical."""
    mesh = ht.make_mesh(ep=8)
    D, F, E = 16, 32, 8
    gate = TopKGate(D, E, 2)
    experts = Expert(E, D, F)
    layer_plain = MoELayer(gate, experts, capacity_factor=2.0)
    layer_ep = MoELayer(gate, experts, capacity_factor=2.0, mesh=mesh)
    v = layer_plain.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, D))

    (y_plain, aux_p), _ = jax.jit(
        lambda vv, xx: layer_plain.apply(vv, xx))(v, x)

    # place expert weights ep-sharded
    from jax.sharding import NamedSharding, PartitionSpec as P
    v_ep = jax.tree_util.tree_map(lambda a: a, v)
    ep_spec = {"w1": P("ep"), "b1": P("ep"), "w2": P("ep"), "b2": P("ep")}
    v_ep["params"]["experts"] = {
        k: jax.device_put(v["params"]["experts"][k],
                          NamedSharding(mesh, ep_spec[k]))
        for k in v["params"]["experts"]}
    (y_ep, aux_e), _ = jax.jit(lambda vv, xx: layer_ep.apply(vv, xx))(v_ep, x)
    np.testing.assert_allclose(np.asarray(y_plain), np.asarray(y_ep),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(float(aux_p), float(aux_e), rtol=1e-5)


def test_moe_transformer_trains():
    from hetu_tpu.models.moe_transformer import MoEConfig, MoETransformer
    cfg = MoEConfig(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
                    ffn_size=64, num_experts=4, top_k=2, max_position=32)
    model = MoETransformer(cfg)
    v = model.init(jax.random.PRNGKey(0))
    ids = np.random.default_rng(0).integers(0, 64, (4, 16)).astype(np.int32)
    ex = ht.Executor(model.lm_loss_fn(), optim.AdamOptimizer(1e-3), seed=0)
    state = ex.init_state(v)
    l0 = None
    for _ in range(5):
        state, m = ex.run("train", state, (ids,))
        l0 = l0 or float(m["loss"])
    assert float(m["loss"]) < l0
    assert float(m["aux_loss"]) >= 0


def test_collective_helpers():
    """shard_map collective wrappers over the 8-dev mesh."""
    from functools import partial
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from hetu_tpu.parallel import collectives as coll

    mesh = ht.make_mesh(dp=8)
    x = jnp.arange(8.0)

    f = partial(shard_map, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))

    out = f(lambda a: coll.psum(a, "dp"))(x)
    np.testing.assert_allclose(np.asarray(out), np.full(8, 28.0))

    out = f(lambda a: coll.ppermute_shift(a, "dp", 1))(x)
    np.testing.assert_allclose(np.asarray(out), np.roll(np.arange(8.0), 1))

    # a2a redistributes row-sharding to column-sharding; the global array is
    # unchanged (it's a resharding — the Ulysses/MoE building block)
    M = jnp.arange(64.0).reshape(8, 8)
    out = shard_map(lambda a: coll.all_to_all(a, "dp", split_dim=1,
                                              concat_dim=0),
                    mesh=mesh, in_specs=P("dp", None),
                    out_specs=P(None, "dp"))(M)
    np.testing.assert_allclose(np.asarray(out), np.asarray(M))
    assert "dp" in str(out.sharding.spec)

    ar = coll.grouped_allreduce(mesh, "dp")
    res = np.asarray(ar(x))
    np.testing.assert_allclose(res, 28.0)


def test_hierarchical_a2a_matches_flat():
    """Two-level A2A must deliver chunks in the same order as a flat a2a over
    the composite axis (reference _ncclHAllToAll contract)."""
    from jax import lax, shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    from hetu_tpu.parallel import collectives as coll

    mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4), ("o", "i"))
    x = jnp.arange(64.0).reshape(8, 8)

    flat = shard_map(
        lambda a: lax.all_to_all(a, ("o", "i"), split_axis=1, concat_axis=0,
                                 tiled=True),
        mesh=mesh, in_specs=P(("o", "i"), None),
        out_specs=P(None, ("o", "i")))(x)
    hier = shard_map(
        lambda a: coll.hierarchical_all_to_all(a, "o", "i", split_dim=1,
                                               concat_dim=0),
        mesh=mesh, in_specs=P(("o", "i"), None),
        out_specs=P(None, ("o", "i")))(x)
    np.testing.assert_allclose(np.asarray(hier), np.asarray(flat))


def test_gather_dispatch_matches_einsum():
    """dispatch_impl='gather' (index routing, Pallas on TPU) must equal the
    dense-mask einsum path bit-for-bit in routing decisions: same outputs
    and same grads, including under capacity overflow."""
    D, F, E = 16, 32, 4
    gate = TopKGate(D, E, 2, impl="xla")
    experts = Expert(E, D, F)
    cf = 0.5  # force overflow so dropped routes are exercised
    l_g = MoELayer(gate, experts, capacity_factor=cf, dispatch_impl="gather")
    l_e = MoELayer(gate, experts, capacity_factor=cf, dispatch_impl="einsum")
    v = l_g.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (64, D))

    def loss(layer, vv, xx):
        (y, aux), _ = layer.apply(vv, xx)
        return jnp.sum(y * y) + aux

    lg, gg = jax.value_and_grad(lambda vv: loss(l_g, vv, x))(v)
    le, ge = jax.value_and_grad(lambda vv: loss(l_e, vv, x))(v)
    np.testing.assert_allclose(float(lg), float(le), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(gg),
                    jax.tree_util.tree_leaves(ge)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


def test_moe_dropped_frac_metric():
    """return_metrics surfaces the capacity-overflow counter: ample
    capacity → 0 dropped; capacity 1/4 of demand → ~3/4 dropped."""
    D, F, E = 8, 16, 2
    gate = TopKGate(D, E, 1, impl="xla")
    experts = Expert(E, D, F)
    x = jax.random.normal(jax.random.PRNGKey(2), (32, D))

    ample = MoELayer(gate, experts, capacity_factor=4.0)
    v = ample.init(jax.random.PRNGKey(0))
    (_, _, m), _ = ample.apply(v, x, return_metrics=True)
    assert float(m["dropped_frac"]) == 0.0

    tight = MoELayer(gate, experts, capacity_factor=0.25)
    (_, _, m2), _ = tight.apply(v, x, return_metrics=True)
    # capacity = 0.25*32/2 = 4 per expert => at most 8 of 32 routed
    assert float(m2["dropped_frac"]) >= 0.5


def test_topk_gate_pallas_impl_matches_xla():
    D, E = 16, 8
    g_x = TopKGate(D, E, 2, impl="xla")
    g_p = TopKGate(D, E, 2, impl="pallas")
    v = g_x.init(jax.random.PRNGKey(3))
    toks = jax.random.normal(jax.random.PRNGKey(4), (64, D))
    (ga, ia, aa), _ = g_x.apply(v, toks)
    (gb, ib, ab), _ = g_p.apply(v, toks)
    np.testing.assert_array_equal(np.asarray(ia), np.asarray(ib))
    np.testing.assert_allclose(np.asarray(ga), np.asarray(gb), rtol=1e-5)
    np.testing.assert_allclose(float(aa), float(ab), rtol=1e-5)


# ---- the held-expert walk: forward and backward follow the load ----

def _held_case(routing: str, T=40, k=3, H=16, F=12, E_all=12, first=4, E=4,
               seed=0):
    """Tokens, pair weights, choices and the held experts' weights; ``even``
    spreads the choices, ``skewed`` sends every token's first choice to one
    held expert, ``empty`` leaves two held experts unchosen."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(T, H)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.1, 1.0, size=(T, k)), jnp.float32)
    if routing == "even":
        idx = np.stack([rng.permutation(E_all)[:k] for _ in range(T)])
    elif routing == "skewed":
        idx = np.stack([np.concatenate([[first + 1], rng.permutation(
            [e for e in range(E_all) if e != first + 1])[:k - 1]])
            for _ in range(T)])
    else:
        pool = [e for e in range(E_all) if e not in (first, first + 3)]
        idx = np.stack([rng.permutation(pool)[:k] for _ in range(T)])
    ws = [jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)
          for s in ((E, H, F), (E, H, F), (E, F, H))]
    return x, w, jnp.asarray(idx, jnp.int32), ws


def _dense_composition(x, w, idx, wg, wu, wd, first):
    """Every held expert over every token, weighted by the token's weight on
    it (zero where it was not chosen)."""
    out = jnp.zeros(x.shape, jnp.float32)
    for e in range(wg.shape[0]):
        we = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1, keepdims=True)
        out = out + we * ((jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e])
    return out


@pytest.mark.parametrize("routing", ["even", "skewed", "empty"])
def test_held_walk_gradients_equal_the_dense_compositions(routing,
                                                          monkeypatch):
    """The LOOP walk's (experts this small fit the grouped kernels, whose
    gradients ``tests/test_grouped_experts.py`` holds: the rule's limit is
    set to nothing here)."""
    from hetu_tpu.ops import moe_ops
    from hetu_tpu.ops.moe_ops import held_expert_ffn

    monkeypatch.setattr(moe_ops, "GROUPED_MAX_WEIGHT", 0)
    x, w, idx, (wg, wu, wd) = _held_case(routing)
    probe = jnp.asarray(np.random.default_rng(9).normal(size=x.shape),
                        jnp.float32)

    def walk(x, w, wg, wu, wd):
        out, _ = held_expert_ffn(x, w, idx, wg, wu, wd, first=4,
                                 block_rows=8)
        return jnp.sum(out * probe)

    def dense(x, w, wg, wu, wd):
        return jnp.sum(_dense_composition(x, w, idx, wg, wu, wd, 4) * probe)

    got = jax.jit(jax.grad(walk, argnums=(0, 1, 2, 3, 4)))(x, w, wg, wu, wd)
    want = jax.grad(dense, argnums=(0, 1, 2, 3, 4))(x, w, wg, wu, wd)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    if routing == "empty":      # nobody chose them: zero, weights unread
        for a in got[2:]:
            assert not np.any(np.asarray(a[0])) \
                and not np.any(np.asarray(a[3]))
    # stacked leaves read in place: the gradient is the layer's, zero elsewhere
    stacked = [jnp.stack([jnp.zeros_like(a), a]) for a in (wg, wu, wd)]
    got2 = jax.grad(lambda *ws: jnp.sum(held_expert_ffn(
        x, w, idx, *ws, first=4, block_rows=8, layer=1)[0] * probe),
        argnums=(0, 1, 2))(*stacked)
    for a, b in zip(got2, want[2:]):
        np.testing.assert_allclose(a[1], b, rtol=1e-4, atol=1e-5)
        assert not np.any(np.asarray(a[0]))


@pytest.mark.parametrize("routing", ["even", "skewed", "empty"])
def test_held_walk_trips_forward_and_backward_follow_the_counts(
        routing, monkeypatch):
    """Both walks run ``sum(ceil(count_e / R))`` trips: counted by a host
    callback put round the block lookup both loop bodies share (the loop
    path, which experts this small leave to the grouped one unless the
    rule's limit is nothing)."""
    from hetu_tpu.ops import moe_ops

    monkeypatch.setattr(moe_ops, "GROUPED_MAX_WEIGHT", 0)
    x, w, idx, (wg, wu, wd) = _held_case(routing, seed=3)
    trips = []
    block = moe_ops._walk_block

    def counted(plan, b, R):
        jax.debug.callback(lambda: trips.append(1))
        return block(plan, b, R)

    monkeypatch.setattr(moe_ops, "_walk_block", counted)
    (_, counts), pull = jax.vjp(
        lambda x, wg: moe_ops.held_expert_ffn(x, w, idx, wg, wu, wd, first=4,
                                              block_rows=8), x, wg)
    jax.effects_barrier()
    want = int(np.sum(-(-np.asarray(counts) // 8)))
    local = np.asarray(idx) - 4
    assert np.array_equal(counts, np.bincount(
        local[(local >= 0) & (local < 4)], minlength=4))
    forward = len(trips)
    assert forward == want
    pull((jnp.ones(x.shape, jnp.float32), np.zeros(
        counts.shape, jax.dtypes.float0)))
    jax.effects_barrier()
    assert len(trips) - forward == want
    # far fewer than the worst case a static trip count would walk
    assert want < -(-x.shape[0] * 3 // 8) + 4
