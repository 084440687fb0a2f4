"""Flash-attention Pallas kernel vs XLA oracle (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.ops.attention import attention, causal_attention
from hetu_tpu.ops.pallas_kernels import flash_attention


def qkv(B=2, H=4, S=256, D=64, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (B, H, S, D)) for k in ks)


def test_flash_matches_xla_full():
    q, k, v = qkv()
    out = flash_attention(q, k, v, block_q=64, block_k=64)
    ref = attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                               atol=2e-5)


def test_flash_matches_xla_causal():
    q, k, v = qkv(seed=1)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    ref = causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                               atol=2e-5)


def test_flash_uneven_blocks():
    # block sizes larger than S clamp down; S=128 with block 128
    q, k, v = qkv(S=128, seed=2)
    out = flash_attention(q, k, v, causal=True)
    ref = causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                               atol=2e-5)


def test_flash_grads_match():
    q, k, v = qkv(S=128, seed=3)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(causal_attention(q, k, v) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3,
                                   atol=1e-4)


def test_flash_causal_cross_length():
    """s_q != s_k causal: bottom-right alignment must match the oracle in
    BOTH forward and gradient (regression: fwd was top-left, bwd
    bottom-right)."""
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (1, 2, 64, 32))
    k = jax.random.normal(ks[1], (1, 2, 128, 32))
    v = jax.random.normal(ks[2], (1, 2, 128, 32))
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    ref = causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                               atol=2e-5)
    g1 = jax.grad(lambda q: jnp.sum(
        flash_attention(q, k, v, causal=True, block_q=32, block_k=32) ** 2))(q)
    g2 = jax.grad(lambda q: jnp.sum(causal_attention(q, k, v) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-3,
                               atol=1e-4)


def test_flash_bf16():
    q, k, v = (x.astype(jnp.bfloat16) for x in qkv(S=128, seed=4))
    out = flash_attention(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    ref = causal_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                           v.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=5e-2, atol=5e-2)


def test_flash_block_autofit():
    """S not divisible by the default 256 block auto-fits down (S=384 -> 128)."""
    q, k, v = qkv(S=384, seed=5)
    out = flash_attention(q, k, v, causal=True)
    ref = causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                               atol=2e-5)


def test_flash_fully_masked_rows_zero():
    """s_q > s_k bottom-right causal: rows that see no key return 0 output
    and 0 grads (the XLA composition instead softmaxes -inf rows into a
    garbage average — zero is the deliberate kernel semantics)."""
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (1, 2, 96, 32))
    k = jax.random.normal(ks[1], (1, 2, 32, 32))
    v = jax.random.normal(ks[2], (1, 2, 32, 32))
    out, vjp = jax.vjp(
        lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=32,
                                        block_k=32), q, k, v)
    # offset = 32 - 96 = -64: query rows 0..63 see no keys
    np.testing.assert_array_equal(np.asarray(out[:, :, :64]), 0.0)
    ref = causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out[:, :, 64:]),
                               np.asarray(ref[:, :, 64:]), rtol=2e-4,
                               atol=2e-5)
    dq, dk, dv = vjp(jnp.ones_like(out))
    np.testing.assert_array_equal(np.asarray(dq[:, :, :64]), 0.0)
    assert np.all(np.isfinite(np.asarray(dk)))
    assert np.all(np.isfinite(np.asarray(dv)))


# ---- both feedings, every shape class, against the XLA oracle ----

_MOD = "hetu_tpu.ops.pallas_kernels.flash_attention"


@pytest.fixture
def feeding(request, monkeypatch):
    """'resident': a row's operands stay in VMEM (what these small shapes
    get).  'streamed': the budget is taken away, so the same call walks the
    blocks on the grid — steered here, the op has no switch for it."""
    import sys
    if request.param == "streamed":
        monkeypatch.setattr(sys.modules[_MOD], "_RESIDENT_VMEM_BYTES", 0)
    return request.param


def _plans(monkeypatch):
    """The flash.plan instants of the calls built from here on."""
    import sys
    seen = []
    monkeypatch.setattr(
        sys.modules[_MOD].trace, "instant",
        lambda name, attrs=None, cat="hetu": seen.append((name, attrs)))
    return seen


def _oracle_case(s_q, s_k, d, dtype, causal, block, seed):
    """(flash out + grads, oracle out + grads, first visible query row)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, g = (jax.random.normal(kk, (1, 2, s_q, d)).astype(dtype)
            for kk in (ks[0], ks[3]))
    k, v = (jax.random.normal(kk, (1, 2, s_k, d)).astype(dtype)
            for kk in ks[1:3])
    blocks = {} if block is None else {"block_q": block, "block_k": block}
    out, vjp = jax.vjp(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, **blocks), q, k, v)
    # rows that see no key (s_q > s_k, causal) are 0 in the kernel and a
    # garbage average in the oracle: keep them out of the oracle's gradients
    lo = max(0, s_q - s_k) if causal else 0
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    ref, ref_vjp = jax.vjp(causal_attention if causal else attention, *f32)
    want = ref_vjp(g.astype(jnp.float32).at[:, :, :lo].set(0.0))
    return (out, *vjp(g)), (ref, *want), lo


_SHAPES = {"sq_lt_sk": (64, 128), "sq_eq_sk": (128, 128),
           "sq_gt_sk": (128, 64)}


@pytest.mark.parametrize("block", [32, 64, None],
                         ids=["b32", "b64", "bdefault"])
@pytest.mark.parametrize("shape", list(_SHAPES))
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("feeding", ["resident", "streamed"], indirect=True)
def test_flash_matches_oracle_causal(feeding, dtype, d, shape, block,
                                     monkeypatch):
    """Forward and all three gradients, causal, against ops.causal_attention:
    both feedings, D 64 (scale folded into Q) and 128 (scale on the scores),
    f32 and bf16, s_q <, ==, > s_k, tiles of 32 / 64 and the default (one
    tile a row: S == block, the diagonal tile alone)."""
    plans = _plans(monkeypatch)
    s_q, s_k = _SHAPES[shape]
    got, want, lo = _oracle_case(s_q, s_k, d, dtype, True, block,
                                 seed=s_q + d)
    assert [a["kernel"] for _, a in plans] == ["fwd", "dkdv", "dq"]
    assert all(a["resident"] == (feeding == "resident") for _, a in plans)
    tol = dict(rtol=1e-3, atol=2e-4) if dtype == jnp.float32 \
        else dict(rtol=5e-2, atol=5e-2)
    out, dq, dk, dv = (np.asarray(x, np.float32) for x in got)
    ref, rq, rk, rv = (np.asarray(x) for x in want)
    assert got[0].dtype == dtype
    # all-masked rows read zero, in the output and in dq
    np.testing.assert_array_equal(out[:, :, :lo], 0.0)
    np.testing.assert_array_equal(dq[:, :, :lo], 0.0)
    np.testing.assert_allclose(out[:, :, lo:], ref[:, :, lo:], **tol)
    np.testing.assert_allclose(dq[:, :, lo:], rq[:, :, lo:], **tol)
    np.testing.assert_allclose(dk, rk, **tol)
    np.testing.assert_allclose(dv, rv, **tol)


@pytest.mark.parametrize("block", [32, None], ids=["b32", "bdefault"])
@pytest.mark.parametrize("shape", list(_SHAPES))
@pytest.mark.parametrize("feeding", ["resident", "streamed"], indirect=True)
def test_flash_matches_oracle_unmasked(feeding, shape, block):
    """Non-causal (BERT-shaped) calls take the unmasked body for every
    tile, cross-length included."""
    s_q, s_k = _SHAPES[shape]
    got, want, _ = _oracle_case(s_q, s_k, 64, jnp.float32, False, block,
                                seed=5)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3,
                                   atol=2e-4)


@pytest.mark.parametrize("s_q,s_k,streamed", [
    (128, 4096, {"fwd": True, "dkdv": False, "dq": True}),
    (4096, 128, {"fwd": False, "dkdv": True, "dq": False}),
    (128, 128, {"fwd": False, "dkdv": False, "dq": False})],
    ids=["long_kv", "long_q", "short"])
def test_flash_feeding_follows_the_shape(s_q, s_k, streamed, monkeypatch):
    """The residency choice is a function of the operand shapes — a row of
    4096 x 128 f32 is over the VMEM budget, so the kernels that would hold
    it stream it — and is visible as the flash.plan instant; the streamed
    kernels agree with the oracle at such a shape too."""
    plans = _plans(monkeypatch)
    got, want, lo = _oracle_case(s_q, s_k, 128, jnp.float32, True, None,
                                 seed=11)
    assert {a["kernel"]: not a["resident"] for _, a in plans} == streamed
    assert all(name == "flash.plan" and a["block_q"] == min(s_q, 512)
               and a["block_k"] == min(s_k, 512)
               and (a["s_q"], a["s_k"], a["d"]) == (s_q, s_k, 128)
               for name, a in plans)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a)[:, :, lo:],
                                   np.asarray(b)[:, :, lo:], rtol=1e-3,
                                   atol=2e-4)


def test_flash_scale_not_a_power_of_two_stays_on_the_scores():
    """An explicit scale that cannot be folded into Q exactly is applied to
    the f32 scores, forward and backward."""
    q, k, v = qkv(S=64, seed=6)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    flash = loss(lambda q, k, v: flash_attention(
        q, k, v, causal=True, scale=0.3, block_q=32, block_k=32))
    ref = loss(lambda q, k, v: causal_attention(q * 0.3 * 8.0, k, v))
    for a, b in zip(jax.grad(flash, argnums=(0, 1, 2))(q, k, v),
                    jax.grad(ref, argnums=(0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3,
                                   atol=2e-4)


# ---- two widths: Q, K of one, V, O, dO of another (latent attention) ----

# a streamed window call with MANY dead blocks a side of every walk: 64 | 32
# blocks of 32 a row, 4 of them live under a window of 96
_LONG = {"long_sq_eq_sk": (2048, 2048), "long_sq_lt_sk": (1024, 2048),
         "long_sq_gt_sk": (2048, 1024)}
_LONG_WINDOW = 96


def _case_id(v):
    """A readable id for a (s_q, s_k) pair, a window or a group size."""
    if isinstance(v, tuple):
        return "x".join(map(str, v))
    return "causal" if v is None else str(v)


@pytest.mark.parametrize("feeding,shape,window", [
    *((f, s, w) for f in ("resident", "streamed")
      for s in ((128, 128), (64, 192), (192, 64)) for w in (None, 40)),
    *(("streamed", _LONG[s], _LONG_WINDOW)
      for s in ("long_sq_lt_sk", "long_sq_gt_sk"))],
    indirect=["feeding"], ids=_case_id)
def test_flash_two_widths_match_the_oracle(feeding, shape, window,
                                           monkeypatch):
    """Forward, dQ and dK/dV with Q, K 48 wide and V, O, dO 32 wide, against
    ``ops.causal_attention``, resident and streamed, ``S_q != S_k`` either
    way round, with and without a window, and streamed over rows of which a
    window leaves most blocks dead; every plan carries both widths."""
    s_q, s_k = shape
    plans = _plans(monkeypatch)
    ks = jax.random.split(jax.random.PRNGKey(21), 4)
    q = jax.random.normal(ks[0], (1, 2, s_q, 48))
    k = jax.random.normal(ks[1], (1, 2, s_k, 48))
    v = jax.random.normal(ks[2], (1, 2, s_k, 32))
    g = jax.random.normal(ks[3], (1, 2, s_q, 32))
    out, vjp = jax.vjp(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, block_q=32, block_k=32),
        q, k, v)
    lo = max(0, s_q - s_k)            # rows that see no key: 0 in the kernel
    ref, ref_vjp = jax.vjp(
        lambda q, k, v: causal_attention(q, k, v, window=window), q, k, v)
    assert out.shape == (1, 2, s_q, 32)
    for a, b in zip((out, *vjp(g)),
                    (ref, *ref_vjp(g.at[:, :, :lo].set(0.0)))):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a)[:, :, lo:],
                                   np.asarray(b)[:, :, lo:], rtol=1e-3,
                                   atol=2e-4)
    assert {a["kernel"] for _, a in plans} == {"fwd", "dkdv", "dq"}
    assert all((a["d"], a["d_v"]) == (48, 32)
               and a["resident"] == (feeding == "resident")
               for _, a in plans)


def test_flash_plan_carries_d_v_when_the_widths_agree(monkeypatch):
    plans = _plans(monkeypatch)
    q, k, v = qkv(S=64, seed=7)
    flash_attention(q, k, v, causal=True)
    assert plans and all(a["d"] == a["d_v"] == q.shape[-1] for _, a in plans)


def test_flash_residency_counts_each_width(monkeypatch):
    """One row of K at 192 and V at 128 (bfloat16, 8192 keys) is over the
    budget where two operands of 64 at 1024 keys are not: the walk is chosen
    from both widths."""
    import sys
    mod = sys.modules[_MOD]
    assert not mod._resident((8192, 192, jnp.bfloat16),
                             (8192, 128, jnp.bfloat16))
    assert mod._resident((1024, 64, jnp.bfloat16), (1024, 64, jnp.bfloat16))


# ---- a window beside the diagonal, and K, V at fewer heads than Q ----

def _masked_case(s_q, s_k, window, *, heads=2, group=1, d=64, d_v=None,
                 block=32, seed=0):
    """(flash out + grads, oracle out + grads over K, V repeated to the
    query heads, first visible query row)."""
    d_v = d_v or d
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (1, heads, s_q, d))
    k = jax.random.normal(ks[1], (1, heads // group, s_k, d))
    v = jax.random.normal(ks[2], (1, heads // group, s_k, d_v))
    g = jax.random.normal(ks[3], (1, heads, s_q, d_v))
    out, vjp = jax.vjp(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, block_q=block, block_k=block),
        q, k, v)
    lo = max(0, s_q - s_k)
    ref, ref_vjp = jax.vjp(lambda q, k, v: causal_attention(
        q, jnp.repeat(k, group, 1), jnp.repeat(v, group, 1), window=window),
        q, k, v)
    return (out, *vjp(g)), (ref, *ref_vjp(g.at[:, :, :lo].set(0.0))), lo


def _assert_agree(got, want, lo):
    out, dq = (np.asarray(x) for x in got[:2])
    np.testing.assert_array_equal(out[:, :, :lo], 0.0)
    np.testing.assert_array_equal(dq[:, :, :lo], 0.0)
    for i, (a, b) in enumerate(zip(got, want)):
        assert a.shape == b.shape
        rows = slice(lo, None) if i < 2 else slice(None)
        np.testing.assert_allclose(np.asarray(a)[:, :, rows],
                                   np.asarray(b)[:, :, rows], rtol=1e-3,
                                   atol=2e-4)


# in tiles of 32: under a block, not a multiple of one, one block, the
# keys' own length (hides nothing), longer than the keys
_WINDOWS = {"lt_block": lambda s_k: 8, "ragged": lambda s_k: 40,
            "one_block": lambda s_k: 32, "eq_s": lambda s_k: s_k,
            "gt_s": lambda s_k: 2 * s_k + 3}


@pytest.mark.parametrize("feeding,shape,window", [
    *((f, s, w) for f in ("resident", "streamed") for s in _SHAPES
      for w in _WINDOWS),
    *(("streamed", s, "many_dead") for s in _LONG)], indirect=["feeding"])
def test_flash_window_matches_the_oracle(feeding, shape, window,
                                         monkeypatch):
    """Forward and all three gradients against
    ``ops.causal_attention(window=)``: windows smaller than a block, not a
    multiple of it, equal to it, equal to and larger than S, ``s_q`` <, ==,
    > ``s_k``, resident and streamed, and streamed over 64 blocks a row of
    which a walk has 4 live.  The plan says what the walk visits: no more
    than a causal walk, and as much where the window hides nothing."""
    plans = _plans(monkeypatch)
    s_q, s_k = {**_SHAPES, **_LONG}[shape]
    w = {**_WINDOWS, "many_dead": lambda s_k: _LONG_WINDOW}[window](s_k)
    got, want, lo = _masked_case(s_q, s_k, w, seed=s_q + w)
    _assert_agree(got, want, lo)
    assert [a["kernel"] for _, a in plans] == ["fwd", "dkdv", "dq"]
    for _, a in plans:
        assert a["resident"] == (feeding == "resident")
        assert (a["window"], a["kv_heads"]) == (w, 2)
        assert 0 < a["tiles_live"] <= a["tiles_causal"]
        if w >= s_k:
            assert a["tiles_live"] == a["tiles_causal"]
        elif s_q == s_k:      # 4 x 4 tiles: the corner of 3 is behind it
            assert a["tiles_live"] < a["tiles_causal"]
        if shape == "long_sq_eq_sk":    # the grid walks few blocks more
            assert a["tiles_live"] <= a["steps"] < 1.1 * a["tiles_live"]


@pytest.mark.parametrize("feeding,group,window,shape", [
    *((f, g, w, (128, 128)) for f in ("resident", "streamed")
      for g in (1, 4, 8) for w in (None, 40)),
    *(("streamed", 4, _LONG_WINDOW, _LONG[s])
      for s in ("long_sq_lt_sk", "long_sq_gt_sk"))], indirect=["feeding"],
    ids=_case_id)
def test_flash_grouped_heads_match_repeated_kv(feeding, group, window, shape,
                                               monkeypatch):
    """8 query heads over 8, 2 and 1 KV heads: value, dQ, and dK, dV AT THE
    KV HEADS, against the oracle over K and V repeated to 8 heads (whose
    gradient sums each group), with and without a window, and streamed over
    rows of which a window leaves most blocks dead."""
    plans = _plans(monkeypatch)
    s_q, s_k = shape
    got, want, lo = _masked_case(s_q, s_k, window, heads=8, group=group,
                                 d=32, seed=group)
    assert got[2].shape == got[3].shape == (1, 8 // group, s_k, 32)
    _assert_agree(got, want, lo)
    assert all(a["kv_heads"] == 8 // group
               and a["resident"] == (feeding == "resident")
               for _, a in plans)


def test_flash_refuses_heads_that_do_not_group_and_a_window_alone():
    q, k, v = qkv(H=4, S=64)
    with pytest.raises(ValueError, match="divides the queries"):
        flash_attention(q, k[:, :3], v[:, :3], causal=True)
    with pytest.raises(ValueError, match="divides the queries"):
        flash_attention(q, k[:, :2], v[:, :1], causal=True)
    with pytest.raises(ValueError, match="only with causal"):
        flash_attention(q, k, v, window=16)
    with pytest.raises(ValueError, match="positive number"):
        flash_attention(q, k, v, causal=True, window=0)


@pytest.mark.parametrize("case", [
    (128, 128, None, 1), (128, 128, 40, 1), (128, 128, 8, 4),
    (64, 128, 40, 2), (128, 64, 24, 1), (128, 128, 128, 1),
    (1024, 1024, 96, 1), (512, 1024, 96, 2), (1024, 512, 96, 4)],
    ids=["causal", "w40", "w8-g4", "sq_lt_sk-w40-g2", "sq_gt_sk-w24",
         "w_eq_s", "many_dead-w96", "many_dead-sq_lt_sk-w96-g2",
         "many_dead-sq_gt_sk-w96-g4"])
@pytest.mark.parametrize("feeding", ["resident", "streamed"], indirect=True)
def test_each_kernel_visits_exactly_the_live_tiles(feeding, case,
                                                   monkeypatch):
    """The tile bodies the three kernels run, counted by a host callback
    round each: every one runs ``tiles_live`` times a call, the plan's
    count, which is the number of (Q block, K block) pairs that hold a
    visible score, times the query heads; a streamed walk's dead grid steps
    run none.  ``steps`` is the call's grid: resident, a step a block a
    program owns; streamed, heads x owned blocks x the whole row without a
    window, x the LONGEST live walk with one."""
    import sys
    mod = sys.modules[_MOD]
    s_q, s_k, window, group = case
    heads, block = 4, 32
    trips = {"fwd": 0, "dq": 0, "dkdv": 0}

    def counted(name):
        body = getattr(mod, f"_{name}_tile")

        def tile(*args):
            jax.debug.callback(
                lambda: trips.__setitem__(name, trips[name] + 1))
            return body(*args)
        return tile

    for name in trips:
        monkeypatch.setattr(mod, f"_{name}_tile", counted(name))
    plans = _plans(monkeypatch)
    got, want, lo = _masked_case(s_q, s_k, window, heads=heads, group=group,
                                 d=32, block=block, seed=3)
    jax.effects_barrier()
    _assert_agree(got, want, lo)
    back = np.arange(s_q)[:, None] + (s_k - s_q) - np.arange(s_k)[None]
    seen = (back >= 0) & (back < (window or s_k + s_q))
    live = seen.reshape(s_q // block, block, s_k // block, block).any((1, 3))
    assert {a["kernel"]: a["tiles_live"] for _, a in plans} == \
        dict.fromkeys(trips, heads * int(live.sum()))
    assert trips == dict.fromkeys(trips, heads * int(live.sum()))
    assert all(a["tiles_causal"] >= a["tiles_live"] for _, a in plans)
    w = mod._Walk(s_q=s_q, s_k=s_k, block_q=block, block_k=block, scale=1.0,
                  causal=True, window=window, group=group)

    def longest(spans_of, n):
        walks = [spans_of(i) for i in range(n)]    # on plain integers
        return max(spans[-1][1] - spans[0][0] for spans in walks)

    for _, a in plans:
        own_q = a["kernel"] != "dkdv"
        owned, row = (w.n_q, w.n_k) if own_q else (w.n_k, w.n_q)
        if a["resident"]:        # the group's heads are a loop in a program
            want = (heads if own_q else heads // group) * owned
        elif w.window is None:
            want = heads * owned * row
        else:
            want = heads * owned * (longest(w.k_spans, w.n_q) if own_q
                                    else longest(w.q_spans, w.n_k))
        assert a["steps"] == want, a
        if not a["resident"] and window == 96:
            # 4 blocks of a row are live; a row of the longer side that
            # sees nothing of the shorter still takes its walk's steps
            assert a["steps"] < 1.15 * max(s_q, s_k) / min(s_q, s_k) \
                * a["tiles_live"]


# ---- a recomputed layer keeps the forward kernel's output and LSE rows

def _remat_gpt_grads(mesh_axes, policy, named, monkeypatch, run=True):
    """(loss, gradients, jaxpr text of the gradient) of a small flash GPT
    with per-layer remat (``policy`` 'period': of a small ``mellum`` model,
    three window layers and a full one, K and V at half the heads): through
    ``ops.remat`` as it is (``named``), or through the plain
    ``jax.checkpoint`` it stands in for.  Run operation by operation
    (``jax.disable_jit``), so that no compiler's fusion choices stand
    between the two programs: what is compared is their arithmetic."""
    import hetu_tpu as ht
    from hetu_tpu import ops
    from hetu_tpu.models.gpt import GPTConfig, GPTModel
    from hetu_tpu.parallel.mesh import mesh_context
    from hetu_tpu.parallel.strategies.simple import MegatronLM

    if not named:
        dots = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        monkeypatch.setattr(ops, "remat", lambda layer, policy="full":
                            jax.checkpoint(layer, policy=dots
                                           if policy == "dots" else None))
    if policy == "period":
        from hetu_tpu.models.mellum import MellumConfig, MellumModel
        model = MellumModel(MellumConfig(
            vocab_size=64, hidden_size=32, num_layers=4, num_heads=4,
            num_kv_heads=2, head_dim=8, expert_ffn_size=16,
            n_routed_experts=4, moe_topk=2, held=(1, 2), window=12,
            max_position=32, dtype=jnp.float32, expert_block_rows=8,
            ce_row_chunk=32, embedding_init_std=1.0))
    else:
        model = GPTModel(GPTConfig(
            vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
            ffn_size=64, max_position=32, dropout_rate=0.0,
            attention_impl="flash", remat=True, remat_policy=policy,
            ce_row_chunk=32))
    params = model.init(jax.random.PRNGKey(0))["params"]
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
    mesh = ht.make_mesh(**mesh_axes) if mesh_axes else None
    if mesh is not None:
        params = jax.device_put(params, MegatronLM().shardings(params, mesh))
    loss_fn = model.lm_loss_fn()

    def grad(p):
        return jax.value_and_grad(
            lambda p: loss_fn(p, {}, (ids,), None, True)[0])(p)

    with mesh_context(mesh):
        text = str(jax.make_jaxpr(grad)(params))
        if not run:
            return None, None, text
        with jax.disable_jit():
            return (*grad(params), text)


@pytest.mark.parametrize("mesh_axes,policy", [
    (None, "full"), ({"dp": 2, "tp": 2}, "full"), (None, "dots"),
    (None, "period")],
    ids=["one-device-full", "dp2tp2-full", "one-device-dots",
         "window-and-full-layers"])
def test_remat_keeps_the_forward_kernels_results_bit_for_bit(
        mesh_axes, policy, monkeypatch):
    """The saved output and LSE rows equal the recomputed ones, so loss and
    every gradient leaf are the plain ``jax.checkpoint``'s bit for bit; what
    differs is the gradient's program: the scanned layer holds the forward
    kernel once, not twice (through the kernel's ``shard_map`` too), and
    under 'dots' the matmul results are still kept beside the two names."""
    loss, grads, text = _remat_gpt_grads(mesh_axes, policy, True,
                                         monkeypatch)
    want_loss, want, want_text = _remat_gpt_grads(mesh_axes, policy, False,
                                                  monkeypatch)
    assert np.asarray(loss).tobytes() == np.asarray(want_loss).tobytes()
    got = jax.tree_util.tree_leaves_with_path(grads)
    assert len(got) == len(jax.tree_util.tree_leaves(want)) > 10
    for (path, a), b in zip(got, jax.tree_util.tree_leaves(want)):
        assert np.abs(np.asarray(b)).max() > 0, jax.tree_util.keystr(path)
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), \
            jax.tree_util.keystr(path)
    # forward, dK/dV, dQ | and the recomputed forward; a layer of the
    # unrolled period (three window layers, one full)
    calls = 4 if policy == "period" else 1

    def flash_calls(t):
        # the period's expert walks take the grouped matmuls (told by the
        # VMEM limit they state): the same calls in both programs
        from hetu_tpu.ops.pallas_kernels.grouped_matmul import _VMEM_LIMIT
        return t.count("pallas_call[") \
            - t.count(f"vmem_limit_bytes={_VMEM_LIMIT}")

    assert (flash_calls(text), flash_calls(want_text)) \
        == (3 * calls, 4 * calls)
    if policy == "dots":
        monkeypatch.undo()
        full_text = _remat_gpt_grads(mesh_axes, "full", True, monkeypatch,
                                     run=False)[2]
        assert text.count("dot_general") < full_text.count("dot_general")


# ---- a chunk call: queries at traced positions over a longer history ----

def _chunk_case(heads, kv_heads, d, d_v, starts, extra, s_c=32, block=32,
                dtype=jnp.float32, seed=0):
    """q [B, heads, S_c, d], a time-major view [B, T, kv_heads, .] with
    ``T = max(starts) + S_c + extra`` whose rows past each sequence's chunk
    hold garbage, and ``starts``."""
    b, t = len(starts), max(starts) + s_c + extra
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, heads, s_c, d)).astype(dtype)
    k = jax.random.normal(ks[1], (b, t, kv_heads, d)).astype(dtype)
    v = jax.random.normal(ks[2], (b, t, kv_heads, d_v)).astype(dtype)
    starts = jnp.asarray(starts, jnp.int32)
    unseen = (jnp.arange(t)[None] >= (starts + s_c)[:, None])[:, :, None,
                                                             None]
    return q, jnp.where(unseen, 3e3, k), jnp.where(unseen, -7e3, v), starts


def _dense_chunk(q, k, v, starts, scale):
    """A float32 softmax over the whole view, the heads repeated."""
    g = q.shape[1] // k.shape[2]
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    s = jnp.einsum("bhsd,bthd->bhst", q, jnp.repeat(k, g, 2)) * scale
    pos = starts[:, None] + jnp.arange(q.shape[2])
    seen = jnp.arange(k.shape[1])[None, None, :] <= pos[:, :, None]
    p = jax.nn.softmax(jnp.where(seen[:, None], s, -1e30), -1)
    return jnp.einsum("bhst,bthd->bhsd", p, jnp.repeat(v, g, 2))


CHUNK_CASES = {
    # heads | KV heads
    "h4kv4": dict(heads=4, kv_heads=4, d=64, d_v=64, starts=[40], extra=24),
    "h8kv2": dict(heads=8, kv_heads=2, d=64, d_v=64, starts=[40], extra=24),
    "h16kv2": dict(heads=16, kv_heads=2, d=64, d_v=64, starts=[40],
                   extra=24),
    # widths
    "d128": dict(heads=4, kv_heads=2, d=128, d_v=128, starts=[40], extra=24),
    "d256": dict(heads=4, kv_heads=2, d=256, d_v=256, starts=[40], extra=24),
    "d192v128": dict(heads=4, kv_heads=4, d=192, d_v=128, starts=[40],
                     extra=24),
    # where the chunk starts
    "start0": dict(heads=4, kv_heads=2, d=64, d_v=64, starts=[0], extra=32),
    "start_on_a_block": dict(heads=4, kv_heads=2, d=64, d_v=64, starts=[64],
                             extra=0),
    "start_unaligned": dict(heads=4, kv_heads=2, d=64, d_v=64, starts=[37],
                            extra=27),
    "two_sequences": dict(heads=4, kv_heads=2, d=64, d_v=64,
                          starts=[96, 5], extra=0),
    "two_sequences_192": dict(heads=4, kv_heads=4, d=192, d_v=128,
                              starts=[7, 64], extra=32),
    # a view wider than the history: by one row (a last block of one, the
    # view padded to whole blocks), by several whole K blocks (dead steps)
    "view_one_wider": dict(heads=4, kv_heads=2, d=64, d_v=64, starts=[32],
                           extra=1),
    "view_blocks_wider": dict(heads=4, kv_heads=2, d=64, d_v=64,
                              starts=[32], extra=160),
    "view_blocks_wider_two": dict(heads=8, kv_heads=2, d=128, d_v=128,
                                  starts=[0, 70], extra=128),
    # several Q blocks a chunk, the K blocks another size
    "q_blocks": dict(heads=4, kv_heads=2, d=64, d_v=64, starts=[50],
                     extra=14, s_c=64, block=16),
    "bf16": dict(heads=8, kv_heads=2, d=128, d_v=128, starts=[45, 64],
                 extra=64, dtype=jnp.bfloat16),
    "bf16_192": dict(heads=4, kv_heads=4, d=192, d_v=128, starts=[33],
                     extra=31, dtype=jnp.bfloat16),
    # K and V built head-major for the call (LongCat's rebuilt keys, padded
    # to whole lane tiles): fed as they lie
    "head_major": dict(heads=4, kv_heads=4, d=256, d_v=128, starts=[40, 9],
                       extra=24, head_major=True),
    # no history, a view as long as the chunk: plain causal attention
    "causal": dict(heads=4, kv_heads=2, d=64, d_v=64, starts=[0, 0],
                   extra=0, s_c=64),
}


@pytest.mark.parametrize("case", list(CHUNK_CASES))
def test_a_chunk_call_matches_the_key_block_walk_and_a_dense_softmax(
        case, monkeypatch):
    """The forward kernel fed a chunk (interpret mode) against the XLA walk
    it replaces on the chip (``ops.attention._attend_blocks``) and a float32
    softmax over the whole view; its ``flash.plan`` instant says
    ``fwd_chunk`` with the bucket's tiles and steps."""
    from hetu_tpu.ops.attention import _attend_blocks
    from hetu_tpu.ops.pallas_kernels.flash_attention import (
        flash_chunk_attention,
    )

    kw = dict(CHUNK_CASES[case])
    block = kw.get("block", 32)
    head_major = kw.pop("head_major", False)
    q, k, v, starts = _chunk_case(**kw)
    scale = q.shape[-1] ** -0.5
    jax.clear_caches()      # the call is jitted: a shape met before says
    seen = _plans(monkeypatch)                       # nothing a second time
    if head_major:
        out = flash_chunk_attention(
            q, jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2), starts,
            block_q=block, block_k=block, head_major=True)
    else:
        out = flash_chunk_attention(q, k, v, starts, block_q=block,
                                    block_k=block)
    assert out.shape == q.shape[:3] + v.shape[3:] and out.dtype == q.dtype
    pos = starts[:, None] + jnp.arange(q.shape[2])
    walk = _attend_blocks(q, k, v, pos, scale, block)
    dense = _dense_chunk(q, k, v, starts, scale)
    tol = dict(rtol=2e-2, atol=2e-2) if q.dtype == jnp.bfloat16 \
        else dict(rtol=2e-4, atol=2e-5)
    out = np.asarray(out.astype(jnp.float32))
    np.testing.assert_allclose(out, np.asarray(walk.astype(jnp.float32)),
                               **tol)
    np.testing.assert_allclose(out, np.asarray(dense), **tol)
    (name, plan), = seen
    rows = -(-k.shape[1] // block) * block
    assert name == "flash.plan" and plan["kernel"] == "fwd_chunk" \
        and (plan["s_q"], plan["s_k"]) == (q.shape[2], rows) \
        and plan["kv_heads"] == k.shape[2] \
        and plan["as_rows"] == int(not head_major and q.shape[3] % 128
                                   == v.shape[3] % 128 == 0) \
        and plan["steps"] == q.shape[0] * q.shape[1] \
        * (q.shape[2] // block) * (rows // block) >= plan["tiles_live"] > 0
    if case == "causal":
        want = flash_attention(q, jnp.moveaxis(k, 1, 2),
                               jnp.moveaxis(v, 1, 2), causal=True,
                               block_q=block, block_k=block)
        np.testing.assert_array_equal(out, np.asarray(want))


def test_a_chunk_call_has_no_backward():
    from hetu_tpu.ops.pallas_kernels.flash_attention import (
        flash_chunk_attention,
    )

    q, k, v, starts = _chunk_case(4, 2, 64, 64, [8], 24)
    with pytest.raises(Exception):
        jax.grad(lambda q: flash_chunk_attention(
            q, k, v, starts, block_q=32, block_k=32).sum())(q)


@pytest.mark.parametrize("case,width,at,step", [
    ("dma", 128, 32, 16), ("narrow_rows", 24, 32, 16),
    ("inside_a_tile", 128, 20, 4)])
def test_write_rows_is_a_dynamic_update_slice_in_place(case, width, at, step):
    """A block of key rows put into an array nobody wrote, by the DMA where
    its start and width allow one and by ``dynamic_update_slice`` elsewhere:
    the rows written read back, the rest is still unwritten (NaN in
    interpret mode)."""
    from hetu_tpu.ops.pallas_kernels.flash_attention import (
        unwritten, write_rows,
    )

    rows = jax.random.normal(jax.random.PRNGKey(3), (2, 3, 16, width))
    out = jax.jit(lambda r, at: write_rows(
        unwritten((2, 3, 64, width), r.dtype), r, at, multiple_of=step))(
            rows, jnp.int32(at))
    np.testing.assert_array_equal(np.asarray(out[:, :, at:at + 16]),
                                  np.asarray(rows))
    rest = np.delete(np.asarray(out), np.s_[at:at + 16], axis=2)
    assert np.isnan(rest).all()
    text = str(jax.make_jaxpr(lambda r: write_rows(
        jnp.zeros((2, 3, 64, width)), r, jnp.int32(at), multiple_of=step))(
            rows))
    assert ("pallas_call" in text) == (case == "dma")
