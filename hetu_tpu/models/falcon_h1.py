"""Falcon-H1: a Mamba-2 state-space mixer BESIDE grouped-query attention in
every layer, a dense SwiGLU behind them, muP multipliers on every product.

Source of the shapes: ``huggingface.co/tiiuae/Falcon-H1-34B-Instruct``
``config.json`` (``model_type`` ``falcon_h1``).  One layer, ``N`` RMSNorm
(each its own weight), no projection has a bias but the convolution; ``m.*``
the configuration's multipliers::

    h0 = E[ids] * m.embedding
    a  = N(h)
    attention:  q = W_q (a m.attention_in), k = (W_k (a m.attention_in)) m.key,
                v = W_v (a m.attention_in); q, k rotated (half-rotation layout,
                all of the head), no norm of q or k; causal softmax(q k^T /
                sqrt(D)) v, head i reads KV head i // (heads / kv_heads)
                A = W_o(.) m.attention_out
    mixer:      [z | x | B | C | dt] = (W_in (a m.ssm_in)) * m.ssm  (a factor a
                segment)
                [x | B | C] <- silu(conv([x | B | C]) + b)   (causal, depth-wise)
                dt = softplus(dt + dt_bias);  A = -exp(A_log)
                S_t = exp(dt_t A) S_(t-1) + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t
                M = (W_out N_groups(y * silu(z))) m.ssm_out
    h  = h + M + A
    h  = h + (W_d(silu((W_g u) m.mlp[0]) * W_u u)) m.mlp[1],   u = N(h)
    logits = (W_head N(h)) m.lm_head                    # the head is untied

**What a layer remembers** of a sequence: attention its K and V rows, a
cache layer of the one page group; the mixer TWO arrays whatever the
sequence's length, the convolution's last ``taps - 1`` rows of ``[x | B |
C]`` in the compute type and the recurrence's matrix ``S`` [heads, d_head,
d_state] in float32: a STATE LAYER of two parts
(``serve.kv_cache.KVCacheSpec.state_parts``, ``SlotStates``).  The state is
float32 whatever the compute type: a head whose ``exp(dt A)`` is within a
thousandth of one is fed increments of a thousandth part of ``S`` for
thousands of rounds, and bfloat16's eight bits lose them
(``tests/test_falcon_h1.py`` decodes 256 rounds both ways).  The three calls
are one mixer (:meth:`FalconH1Model._mixer`): the dense forward scans from
zeros (``ops.ssm.ssd_chunk_scan``), a chunk scans from its slot's state and
leaves the state after its last REAL token, a decode round steps
(``ops.ssm.ssm_step``).

**Shared**: the layer, the three calls, both cache entry points, the head
and the loss are ``models/block.py``'s (``BlockDecoder``, ``LayerCall``,
``GroupedHeads``), which takes the embedding's, the key's, the
feed-forward's and the head's factors as ``multipliers``; the recurrence, the
convolution and the gated norm are ``ops/ssm.py``'s.  Here: the
configuration, the weights, the tables (every layer an attention leaf, a
cache layer and a state layer) and the operator, the sum of the two
branches.

``jax.named_scope``s: ``hetu.ssm.proj`` (both projections), ``hetu.ssm.conv``,
``hetu.ssm.scan`` (a chunk or the dense forward), ``hetu.ssm.step`` (a decode
round), ``hetu.ssm.norm``, ``hetu.attn.full``, ``hetu.ffn.dense``; an instant
``ssm.plan`` once a program traced says which form the program holds.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from hetu_tpu import ops
from hetu_tpu.models.block import BlockDecoder, LayerCall, draw_leaf
from hetu_tpu.ops import ssm
from hetu_tpu.telemetry import trace


@dataclass
class FalconH1Config:
    vocab_size: int = 261120
    hidden_size: int = 5120
    num_layers: int = 72
    num_heads: int = 20
    num_kv_heads: int = 4
    head_dim: int = 128
    ffn_size: int = 21504
    ssm_heads: int = 32                  # mamba_n_heads
    ssm_head_dim: int = 128              # mamba_d_head
    ssm_state: int = 256                 # mamba_d_state
    ssm_groups: int = 2                  # mamba_n_groups
    conv_taps: int = 4                   # mamba_d_conv
    ssm_chunk: int = 128                 # mamba_chunk_size
    rope_theta: float = 1e11
    rms_eps: float = 1e-5
    max_position: int = 262144
    embedding_multiplier: float = 5.656854249492381
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    ssm_in_multiplier: float = 0.25
    ssm_multipliers: tuple = (0.3535533905932738, 0.25, 0.1767766952966369,
                              0.5, 0.3535533905932738)    # z | x | B | C | dt
    ssm_out_multiplier: float = 0.08838834764831845
    mlp_multipliers: tuple = (0.1767766952966369, 0.011160714285714284)
    lm_head_multiplier: float = 0.0078125
    dtype: object = jnp.bfloat16         # compute
    param_dtype: object = jnp.bfloat16
    state_dtype: object = jnp.float32    # the recurrence's matrix

    def __post_init__(self):
        self.ssm_multipliers = tuple(float(v) for v in self.ssm_multipliers)
        self.mlp_multipliers = tuple(float(v) for v in self.mlp_multipliers)
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError("ssm_multipliers are five (z, x, B, C, dt) and "
                             "mlp_multipliers two (gate, down)")
        if self.head_dim % 2 or self.num_heads % self.num_kv_heads \
                or self.ssm_heads % self.ssm_groups:
            raise ValueError("head_dim must be even, kv heads divide the "
                             "query heads and groups the mixer's heads")
        if self.conv_taps < 2:
            raise ValueError("a causal convolution has two taps or more")

    # every layer's feed-forward is dense: BlockDecoder reads the count
    @property
    def first_dense(self) -> int:
        return self.num_layers

    @property
    def d_ssm(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_channels(self) -> int:
        """``[x | B | C]``: what the convolution runs over."""
        return self.d_ssm + 2 * self.ssm_groups * self.ssm_state

    @property
    def in_width(self) -> int:
        """``[z | x | B | C | dt]``: the in-projection's outputs."""
        return self.d_ssm + self.conv_channels + self.ssm_heads

    def unit_stds(self) -> dict:
        """By leaf, the std ``init`` draws it with: the one that makes the
        leaf's product of order one AFTER its multipliers over a unit-rms
        input: the published multipliers
        presuppose trained muP weights, and one std for every leaf under
        them leaves attention's scores at 0.02 (a uniform softmax: keys and
        rotation invisible) and both branches' results vanishing beside the
        stream.  ``attn.o`` reckons a softmax's average of unit values at a
        quarter, ``ffn.down`` the gated product at a half; ``ssm.in`` is one
        leaf, so its x segment is the unit one and the others stand at
        their factor over x's (z 1.4, B 0.7, C 2, dt 1.4)."""
        H = self.hidden_size
        over = 1.0 / math.sqrt(H)
        attn_in = over / self.attention_in_multiplier
        return {
            "tok_emb": 1.0 / self.embedding_multiplier,
            "lm_head": over / self.lm_head_multiplier,
            "attn.q": attn_in, "attn.v": attn_in,
            "attn.k": attn_in / self.key_multiplier,
            "attn.o": 4.0 / (math.sqrt(self.num_heads * self.head_dim)
                             * self.attention_out_multiplier),
            "ssm.in": over / (self.ssm_in_multiplier
                              * self.ssm_multipliers[1]),
            "ssm.conv_w": self.conv_taps ** -0.5,
            "ssm.conv_b": 0.1,
            "ssm.out": 1.0 / (math.sqrt(self.d_ssm)
                              * self.ssm_out_multiplier),
            "ffn.gate": over / self.mlp_multipliers[0],
            "ffn.up": over,
            "ffn.down": 2.0 / (math.sqrt(self.ffn_size)
                               * self.mlp_multipliers[1]),
        }


# the parts of a state layer, in the order the cache holds them
CONV, SSM = 0, 1


class FalconH1Model(BlockDecoder):
    """``params``: ``tok_emb`` [V, H], ``lm_head`` [V, H], ``norm_f``,
    ``layers``: ``attn_norm`` (the norm both branches read) / ``ffn_norm``
    [L, H], ``attn`` {q [qw, H], k [kvw, H], v [H, kvw], o [qw, H]}, ``ssm``
    {in [H, in_width], conv_w [taps, channels], conv_b [channels], dt_bias,
    A_log, D [heads] (float32), norm [d_ssm], out [d_ssm, H]}, ``ffn``
    {gate, up, down}.  The small leaves are stacked over the layers; the
    nine MATRICES of a layer (``attn``'s four, ``ssm``'s ``in`` and ``out``,
    ``ffn``'s three) are each a TUPLE of the layers' arrays, as ``init``
    yields them: the layers are a Python loop, a layer cut out of a stacked
    leaf at a static index is written into a buffer of its own in every
    call (``layers/base.py`` ``Module.serving_params``; 660 MB a layer
    here), and a server that re-held 7.9 GB of feed-forward a layer an
    array beside the tree it was given would hold them twice.  ``leaf[l]``
    reads either form, so ``serving_params`` finds nothing left to do."""

    # no expert layer: both cache entry points return no counts
    step_stats = ()

    def __init__(self, config: FalconH1Config):
        c = config
        every = range(c.num_layers)
        super().__init__(
            c, None, attn_leaf={l: l for l in every},
            cache_layer={l: (0, l) for l in every}, rotated=every,
            multipliers={"embed": c.embedding_multiplier,
                         "key": c.key_multiplier,
                         "gate": c.mlp_multipliers[0],
                         "down": c.mlp_multipliers[1],
                         "head": c.lm_head_multiplier})

    # ---- the cache this model asks of the serving engine ----
    def kv_cache_spec(self):
        from hetu_tpu.serve.kv_cache import KVCacheSpec

        c = self.c
        return KVCacheSpec(
            num_layers=c.num_layers, num_kv_heads=c.num_kv_heads,
            head_dim=c.head_dim, dtype=c.dtype, state_layers=c.num_layers,
            state_parts=(
                # the rows side by side: a part of three rows a slot is held
                # padded to eight and relaid round every gather
                ("conv", ((c.conv_taps - 1) * c.conv_channels,), c.dtype),
                ("ssm", (c.ssm_heads, c.ssm_head_dim, c.ssm_state),
                 c.state_dtype)))

    # ---- weights ----
    def init(self, key):
        """Every matrix in ``param_dtype`` at its own std
        (``config.unit_stds()``), a large leaf drawn a piece at a time; the
        recurrence's ``A_log``, ``dt_bias`` and ``D`` in float32, as the
        Mamba-2 paper starts them: ``A`` uniform in (1, 16), ``dt``
        log-uniform in (0.001, 0.1) through the inverse softplus, ``D``
        ones."""
        c = self.c
        pd, std = c.param_dtype, c.unit_stds()
        H, L, F = c.hidden_size, c.num_layers, c.ffn_size
        qw, kvw = c.num_heads * c.head_dim, c.num_kv_heads * c.head_dim
        ks = iter(jax.random.split(key, 16))

        def draw(name, shape, lead=(L,)):
            return draw_leaf(next(ks), lead, shape, std[name], pd)

        def each(name, shape):
            """A matrix a layer: a tuple of the layers' arrays."""
            return tuple(draw_leaf(k, (), shape, std[name], pd)
                         for k in jax.random.split(next(ks), L))

        def ones(*shape):
            return jnp.ones(shape, pd)

        heads = (L, c.ssm_heads)
        dt = jnp.exp(jax.random.uniform(
            next(ks), heads, jnp.float32, math.log(1e-3), math.log(1e-1)))
        layers = {
            "attn_norm": ones(L, H), "ffn_norm": ones(L, H),
            # q and k [out, in], as the block's GroupedHeads reads them
            "attn": {"q": each("attn.q", (qw, H)),
                     "k": each("attn.k", (kvw, H)),
                     "v": each("attn.v", (H, kvw)),
                     "o": each("attn.o", (qw, H))},
            "ssm": {"in": each("ssm.in", (H, c.in_width)),
                    "conv_w": draw("ssm.conv_w",
                                   (c.conv_taps, c.conv_channels)),
                    "conv_b": draw("ssm.conv_b", (c.conv_channels,)),
                    "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                    "A_log": jnp.log(jax.random.uniform(
                        next(ks), heads, jnp.float32, 1.0, 16.0)),
                    "D": jnp.ones(heads, jnp.float32),
                    "norm": ones(L, c.d_ssm),
                    "out": each("ssm.out", (c.d_ssm, H))},
            "ffn": {"gate": each("ffn.gate", (H, F)),
                    "up": each("ffn.up", (H, F)),
                    "down": each("ffn.down", (F, H))},
        }
        return {"params": {"tok_emb": draw("tok_emb", (c.vocab_size, H), ()),
                           "lm_head": draw("lm_head", (c.vocab_size, H), ()),
                           "norm_f": ones(H), "layers": layers},
                "state": {}}

    # ---- pieces of a layer ----
    def _operator(self, p, l: int, a, call: LayerCall):
        """Both branches on the same normed input, each scaled going in and
        coming out, summed."""
        c = self.c
        mixed = self._mixer(p["ssm"], l, a * c.ssm_in_multiplier, call)
        if c.attention_in_multiplier != 1.0:
            a = a * c.attention_in_multiplier
        attended = self._attention(p["attn"], l, a, call)
        return mixed * c.ssm_out_multiplier \
            + attended * c.attention_out_multiplier

    def _mixer(self, p, l: int, a, call: LayerCall):
        """The Mamba-2 mixer of layer ``l`` on ``a`` [B, S, H], ``p`` the
        stacked mixer leaves.  Its state layer ``l`` holds two parts, the
        convolution's last rows and the recurrence's matrix: zeros in the
        dense forward, the slot's in a cached call, which then keeps both
        as they stand after ``call.last`` (the last real token of a padded
        chunk).  A decode round steps; every other call scans in chunks."""
        c, dt_ = self.c, self.c.dtype
        b, s, _ = a.shape
        d_ssm, g, n = c.d_ssm, c.ssm_groups, c.ssm_state
        # a decode round takes the matrix of EVERY slot, a layer whole, and
        # updates it in place (SlotStates.whole); a chunk cuts its one
        # slot's out; the convolution's rows are small and read by sequence
        st, whole = call.state, call.one_query and call.state is not None
        conv_state = ssm_state = None
        if whole:
            conv_state, ssm_state = st.read(l, CONV), st.whole(l, SSM)
        elif st is not None:
            conv_state, ssm_state = st.read(l)
        if st is not None:
            conv_state = conv_state.reshape(b, c.conv_taps - 1, -1)
        if l == 0:
            trace.instant("ssm.plan", {
                "form": "step" if call.one_query else "scan", "rows": s,
                "batch": b, "chunk": c.ssm_chunk, "heads": c.ssm_heads,
                "d_head": c.ssm_head_dim, "d_state": n, "groups": g,
                "state_bytes_per_slot":
                    self.kv_cache_spec().bytes_per_slot // c.num_layers})
        with jax.named_scope("hetu.ssm.proj"):
            zxbcdt = ops.linear(a, p["in"][l].astype(dt_))
            # one factor a segment: z | x | B | C | dt
            z, xbc, dt = jnp.split(zxbcdt, [d_ssm, d_ssm + c.conv_channels],
                                   axis=-1)
            mz, mx, mb, mc, mdt = c.ssm_multipliers
            z = z * mz
            xbc = xbc * jnp.concatenate([
                jnp.full((d_ssm,), mx, dt_), jnp.full((g * n,), mb, dt_),
                jnp.full((g * n,), mc, dt_)])
            dt = jax.nn.softplus(dt.astype(jnp.float32) * mdt
                                 + p["dt_bias"][l])
        with jax.named_scope("hetu.ssm.conv"):
            xbc, conv_state = ssm.causal_conv(
                xbc, p["conv_w"][l], p["conv_b"][l], conv_state, call.last)
            xbc = ops.silu(xbc).astype(dt_)
            if st is not None:
                st = st.write(l, conv_state.reshape(b, -1), CONV)
        x, B, C = jnp.split(xbc, [d_ssm, d_ssm + g * n], axis=-1)
        x = x.reshape(b, s, c.ssm_heads, c.ssm_head_dim)
        B, C = B.reshape(b, s, g, n), C.reshape(b, s, g, n)
        A = -jnp.exp(p["A_log"][l])
        if call.one_query:
            with jax.named_scope("hetu.ssm.step"):
                # by slot where the state is: a slot of no sequence of the
                # round has dt = 0, so its matrix stays as it is
                by_slot = (lambda v: st.spread(v, ssm_state)) if whole \
                    else (lambda v: v)
                y, ssm_state = ssm.ssm_step(
                    *(by_slot(v[:, 0]) for v in (x, dt)), A,
                    *(by_slot(v[:, 0]) for v in (B, C)), p["D"][l], ssm_state)
                y = (st.pick(y) if whole else y)[:, None]
                if whole:
                    st = st.put_whole(l, SSM, ssm_state)
        else:
            with jax.named_scope("hetu.ssm.scan"):
                y, ssm_state = ssm.ssd_chunk_scan(
                    x, dt, A, B, C, p["D"][l], ssm_state, chunk=c.ssm_chunk,
                    last=call.last)
                if st is not None:
                    st = st.write(l, ssm_state, SSM)
        call.state = st
        with jax.named_scope("hetu.ssm.norm"):
            y = ssm.gated_group_rms_norm(
                y.reshape(b, s, d_ssm), z, p["norm"][l], groups=g,
                eps=c.rms_eps)
        with jax.named_scope("hetu.ssm.proj"):
            return ops.linear(y, p["out"][l].astype(dt_))
