"""Qwen3-Next: three Gated DeltaNet layers to one gated full-attention layer,
every layer's feed-forward an expert layer of many small experts beside a
gated shared one.

Source of the shapes: ``huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct``
``config.json`` (``model_type`` ``qwen3_next``).  One layer; every norm ``N``
is ``x rsqrt(mean x^2 + eps) (1 + w)`` with its own ``w``; no projection has
a bias; layer ``i`` is a full layer where ``(i + 1) %
full_attention_interval == 0``::

    h = h + Op(N(h));   h = h + FF(N(h));   logits = W_head N(h)   # untied
    DeltaNet:   [q | k | v | z] a key head = W_qkvz a;  [b | a_] = W_ba a
                [q | k | v] <- silu(conv([q | k | v]))    (causal, depth-wise,
                                                           4 taps, no bias)
                beta = sigmoid(b);  g = -exp(A_log) softplus(a_ + dt_bias)
                q, k to unit length, q / sqrt(d_k); a value head's S [d_k, d_v]:
                S <- exp(g) S;  d = beta (v - S^T k);  S <- S + k d^T;  o = S^T q
                Op = W_o (RMS(o) w_n * silu(z))      # the norm FIRST, one w_n
    full layer: [q | gate] a head = W_q a;  k = W_k a;  v = W_v a;  q, k
                normed per head; the head's first ``rotary_dim`` dims rotated
                (half-rotation layout), the rest passed; causal softmax(q k^T
                / sqrt(D)) v, head h reads KV head h // (heads / kv_heads);
                Op = W_o (o * sigmoid(gate))
    FF:         p = softmax(float32(u) W_r); the k largest, renormalised;
                sum p_e W_down_e(silu(W_gate_e u) * W_up_e u) over the experts
                held here + sigmoid(w_sg . u) shared(u)

**What a DeltaNet layer remembers** of a sequence is TWO arrays whatever the
sequence's length, a STATE LAYER of two parts
(``serve.kv_cache.KVCacheSpec.state_parts``, ``SlotStates``): ``conv``, the
convolution's last ``taps - 1`` rows of ``[q | k | v]`` side by side in the
compute type, and ``delta``, the rule's matrix ``S`` [value heads, d_k, d_v]
in float32 (2 MB a slot a layer at the published widths; float32 because a
head whose decay is within a thousandth of one is fed increments that
bfloat16's eight bits lose: ``tests/test_qwen3_next.py`` decodes 256 rounds
both ways).  A full layer remembers K and V rows, a cache layer of the one
page group: fewer cache layers than layers.  The three calls are one mixer
(:meth:`Qwen3NextModel._mixer`): the dense forward scans from zeros
(``ops.delta_rule.gated_delta_chunk_scan``), a chunk scans from its slot's
state and leaves the state after its last REAL token, a decode round steps
(``gated_delta_step``) over every slot's matrix, a layer WHOLE
(``SlotStates.whole`` / ``put_whole``).

**Shared**: the layer, the three calls, both cache entry points, the head
and the loss are ``models/block.py``'s (``BlockDecoder``, ``LayerCall``,
``GroupedHeads``), which is told ``rotary_dim``, the gated query projection
and the ``1 + w`` norms; the rule is ``ops/delta_rule.py``'s, the convolution
``ops/ssm.py``'s ``causal_conv``, the experts ``layers/moe.py``'s
``HeldExpertLayer`` (softmax scores, renormalised, no correction bias, the
shared expert gated a token by ``shared_gate_w``).  Here: the configuration,
the weights, the tables and the mixer.

``jax.named_scope``s: ``hetu.gdn.proj`` (both projections), ``hetu.gdn.conv``,
``hetu.gdn.rule`` (a chunk or the dense forward), ``hetu.gdn.step`` (a decode
round), ``hetu.gdn.norm``, ``hetu.attn.full`` (the gate inside it),
``hetu.moe.route|experts|shared``; an instant ``gdn.plan`` once a program
traced says which form the program holds.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from hetu_tpu import ops
from hetu_tpu.layers.moe import MOE_STATS, HeldExpertLayer
from hetu_tpu.models.block import (
    BlockDecoder, LayerCall, counts_with_grouped, draw_leaf,
)
from hetu_tpu.ops import delta_rule
from hetu_tpu.ops.ssm import causal_conv
from hetu_tpu.telemetry import trace


@dataclass
class Qwen3NextConfig:
    vocab_size: int = 151936             # or the slice of it held here
    hidden_size: int = 2048
    num_layers: int = 48
    full_attention_interval: int = 4
    num_heads: int = 16
    num_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    gdn_key_heads: int = 16              # linear_num_key_heads
    gdn_value_heads: int = 32            # linear_num_value_heads
    gdn_key_dim: int = 128               # linear_key_head_dim
    gdn_value_dim: int = 128             # linear_value_head_dim
    conv_taps: int = 4                   # linear_conv_kernel_dim
    gdn_chunk: int = 64                  # rows the rule solves together
    expert_ffn_size: int = 512           # moe_intermediate_size
    shared_ffn_size: int = 512           # shared_expert_intermediate_size
    n_routed_experts: int = 512          # as published: the router's width
    moe_topk: int = 10
    held: Optional[tuple] = None         # (first, count); None: all of them
    rope_theta: float = 1e7
    rms_eps: float = 1e-6
    max_position: int = 262144
    dtype: object = jnp.bfloat16         # compute
    param_dtype: object = jnp.bfloat16
    state_dtype: object = jnp.float32    # the rule's matrix
    expert_block_rows: int = 128

    def __post_init__(self):
        if self.held is None:
            self.held = (0, self.n_routed_experts)
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.n_routed_experts:
            raise ValueError(f"held {self.held} is no share of "
                             f"{self.n_routed_experts} experts")
        self.held = (int(first), int(count))
        if self.num_heads % self.num_kv_heads \
                or self.gdn_value_heads % self.gdn_key_heads:
            raise ValueError("kv heads divide the query heads and the "
                             "DeltaNet's key heads its value heads")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError(f"partial_rotary_factor "
                             f"{self.partial_rotary_factor} of head_dim "
                             f"{self.head_dim} is no even number of dims")
        if self.conv_taps < 2:
            raise ValueError("a causal convolution has two taps or more")
        if self.num_layers < self.full_attention_interval:
            raise ValueError("fewer layers than a period: no full layer")

    # every layer's feed-forward is the expert layer: BlockDecoder reads it
    first_dense = 0

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    def is_full(self, l: int) -> bool:
        return (l + 1) % self.full_attention_interval == 0

    @property
    def conv_channels(self) -> int:
        """``[q | k | v]``: what the convolution runs over."""
        return 2 * self.gdn_key_heads * self.gdn_key_dim \
            + self.gdn_value_heads * self.gdn_value_dim

    @property
    def qkvz_width(self) -> int:
        """``[q | k | v | z]``: the in-projection's outputs."""
        return self.conv_channels + self.gdn_value_heads * self.gdn_value_dim

    def unit_stds(self) -> dict:
        """By leaf, the std ``init`` draws it with.  The in-projections, the
        router, the embedding and the head: the one that makes the leaf's
        product of order one over a unit-rms input, so that a comparison
        sees the attention's gate and rotation, the rule's ``beta`` and
        decay, the shared expert's gate and the router's choice.  The four
        OUT-projections: HALF of that (``attn.o`` reckons a softmax's
        average of unit values under a sigmoid gate at a quarter, ``gdn.out``
        and the shared expert's down projection the gated product at a half;
        an expert's stands twice over the shared one's, because the chosen
        weights sum to one over ``moe_topk`` experts of which a share is
        held), so that a branch adds about a half to a stream of one to
        two, as a trained model's branches are smaller than its stream.  At
        the full unit every block hands a rounding on a third larger than
        it got it (norms, unit-length keys and a normed read-out divide by
        what the rounding moved): bfloat16 against the float32 reference
        read 0.115 of the logits' range on the chip at eight layers
        (``PERF.md`` section 6, PR 51), and no limit parts that from a lower
        precision."""
        H = self.hidden_size
        over = 1.0 / math.sqrt(H)
        return {
            "tok_emb": 1.0, "lm_head": over, "norm": 0.1,
            "attn.q": over, "attn.k": over, "attn.v": over,
            "attn.o": 2.0 / math.sqrt(self.num_heads * self.head_dim),
            "gdn.qkvz": over, "gdn.ba": over,
            "gdn.conv_w": self.conv_taps ** -0.5,
            "gdn.out": 1.0 / math.sqrt(self.gdn_value_heads
                                       * self.gdn_value_dim),
            "moe.router": over, "moe.gate": over, "moe.up": over,
            "moe.down": 2.0 / math.sqrt(self.expert_ffn_size),
            "moe.shared_gate": over, "moe.shared_up": over,
            "moe.shared_down": 1.0 / math.sqrt(self.shared_ffn_size),
            "moe.shared_gate_w": over,
        }


# the parts of a state layer, in the order the cache holds them
CONV, DELTA = 0, 1


def rms_norm_then_gate(o, gate, weight, eps: float):
    """``RMS(o) * weight * silu(gate)`` over the last axis: the norm FIRST,
    then the gate.  Float32 inside, ``o``'s dtype out."""
    of = o.astype(jnp.float32)
    of = of * jax.lax.rsqrt(jnp.mean(of * of, -1, keepdims=True) + eps)
    return (of * weight.astype(jnp.float32)
            * jax.nn.silu(gate.astype(jnp.float32))).astype(o.dtype)


class Qwen3NextModel(BlockDecoder):
    """``params``: ``tok_emb`` [V, H], ``lm_head`` [V, H], ``norm_f``,
    ``layers``: ``attn_norm``/``ffn_norm`` [L, H] (the operator's norm under
    the name the block reads it by), ``attn`` (``GroupedHeads``' leaves over
    the FULL layers: q [2 qw, H] ``[query | gate]`` a head, k [kvw, H], v [H,
    kvw], o [qw, H], q_norm / k_norm [A, D]), ``gdn`` over the DeltaNet
    layers {qkvz [H, qkvz_width] and ba [H, 2 value heads], both grouped a
    KEY head, conv_w [taps, channels], dt_bias, A_log [value heads]
    (float32), norm [d_v], out [value heads * d_v, H]}, ``moe`` {router [H,
    n_routed] float32, gate / up [L, E, H, F], down [L, E, F, H] (stacked:
    the grouped matmuls read an expert where it lies), shared_gate /
    shared_up [H, F_s], shared_down [F_s, H], shared_gate_w [L, H]}.  The
    norms' ``w`` are drawn round 0 (``1 + w`` weighs), the DeltaNet's
    ``norm`` round 1.  The matrices a layer reads whole (``attn``'s four,
    ``qkvz``, ``out``, the router and the shared expert's three) are each a
    TUPLE of the layers' arrays, as ``init`` yields them: the layers are a
    Python loop, and a layer cut out of a stacked leaf at a static index is
    written into a buffer of its own in every call (``layers/base.py``
    ``Module.serving_params``).  ``leaf[l]`` reads either form."""

    # the expert layers' counts; the held experts a call could hit at most
    # (held x layers): a constant, for the share that were hit; and the held
    # pairs that the walk's grouped path computed: all of them or none, by
    # ops.moe_ops.held_expert_path's static rule
    step_stats = MOE_STATS + ("moe_experts", "moe_grouped")

    def __init__(self, config: Qwen3NextConfig):
        c = config
        full = [l for l in range(c.num_layers) if c.is_full(l)]
        gdn = [l for l in range(c.num_layers) if not c.is_full(l)]
        # layer -> its index among the layers of its own kind: a full
        # layer's attention leaves and cache layer (of the one page group),
        # a DeltaNet layer's leaves and state layer
        self.gdn_leaf = {l: i for i, l in enumerate(gdn)}
        super().__init__(
            c, HeldExpertLayer(
                n_routed=c.n_routed_experts, n_zero=0, k=c.moe_topk,
                scaling=1.0, held=c.held, block_rows=c.expert_block_rows,
                dtype=c.dtype, scoring="softmax", renormalise=True,
                shared=True),
            attn_leaf={l: i for i, l in enumerate(full)},
            cache_layer={l: (0, i) for i, l in enumerate(full)},
            rotated=full, rotary_dim=c.rotary_dim, gated_query=True,
            unit_offset_norms=True)

    # ---- the cache this model asks of the serving engine ----
    def kv_cache_spec(self):
        from hetu_tpu.serve.kv_cache import KVCacheSpec

        c = self.c
        return KVCacheSpec(
            num_layers=len(self.attn_leaf), num_kv_heads=c.num_kv_heads,
            head_dim=c.head_dim, dtype=c.dtype,
            state_layers=len(self.gdn_leaf),
            state_parts=(
                # the rows side by side: a part of three rows a slot is held
                # padded to eight and relaid round every gather
                ("conv", ((c.conv_taps - 1) * c.conv_channels,), c.dtype),
                ("delta", (c.gdn_value_heads, c.gdn_key_dim,
                           c.gdn_value_dim), c.state_dtype)))

    # ---- weights ----
    def init(self, key):
        """Every matrix in ``param_dtype`` at its own std
        (``config.unit_stds()``), a large leaf drawn a piece at a time, the
        router float32; the rule's ``A_log`` and ``dt_bias`` float32 a value
        head: ``A`` uniform in (0, 16] as the family starts it, ``dt``
        log-uniform in (0.001, 0.1) through the inverse softplus, so that
        heads that forget in a dozen rows stand beside heads that remember
        thousands."""
        c = self.c
        pd, std = c.param_dtype, c.unit_stds()
        H, L, F, Fs = c.hidden_size, c.num_layers, c.expert_ffn_size, \
            c.shared_ffn_size
        E = c.held[1]
        A, G = len(self.attn_leaf), len(self.gdn_leaf)
        qw, kvw = c.num_heads * c.head_dim, c.num_kv_heads * c.head_dim
        hv, d_v = c.gdn_value_heads, c.gdn_value_heads * c.gdn_value_dim
        ks = iter(jax.random.split(key, 32))

        def draw(name, lead, shape, dtype=pd):
            return draw_leaf(next(ks), lead, shape, std[name], dtype)

        def each(name, n, shape, dtype=pd):
            """A matrix a layer: a tuple of the layers' arrays."""
            return tuple(draw_leaf(k, (), shape, std[name], dtype)
                         for k in jax.random.split(next(ks), n))

        def norm(*shape, about=0.0):
            return (about + draw_leaf(next(ks), (), shape, std["norm"],
                                      jnp.float32)).astype(pd)

        dt = jnp.exp(jax.random.uniform(
            next(ks), (G, hv), jnp.float32, math.log(1e-3), math.log(1e-1)))
        layers = {
            "attn_norm": norm(L, H), "ffn_norm": norm(L, H),
            # q and k [out, in], as the block's GroupedHeads reads them
            "attn": {"q": each("attn.q", A, (2 * qw, H)),
                     "k": each("attn.k", A, (kvw, H)),
                     "v": each("attn.v", A, (H, kvw)),
                     "o": each("attn.o", A, (qw, H)),
                     "q_norm": norm(A, c.head_dim),
                     "k_norm": norm(A, c.head_dim)},
            "gdn": {"qkvz": each("gdn.qkvz", G, (H, c.qkvz_width)),
                    "ba": draw("gdn.ba", (G,), (H, 2 * hv)),
                    "conv_w": draw("gdn.conv_w", (G,),
                                   (c.conv_taps, c.conv_channels)),
                    "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                    "A_log": jnp.log(16.0 * (1.0 - jax.random.uniform(
                        next(ks), (G, hv), jnp.float32))),
                    "norm": norm(G, c.gdn_value_dim, about=1.0),
                    "out": each("gdn.out", G, (d_v, H))},
            "moe": {
                "router": each("moe.router", L, (H, c.n_routed_experts),
                               jnp.float32),
                "gate": draw("moe.gate", (L, E), (H, F)),
                "up": draw("moe.up", (L, E), (H, F)),
                "down": draw("moe.down", (L, E), (F, H)),
                "shared_gate": each("moe.shared_gate", L, (H, Fs)),
                "shared_up": each("moe.shared_up", L, (H, Fs)),
                "shared_down": each("moe.shared_down", L, (Fs, H)),
                "shared_gate_w": draw("moe.shared_gate_w", (L,), (H,))},
        }
        return {"params": {"tok_emb": draw("tok_emb", (), (c.vocab_size, H)),
                           "lm_head": draw("lm_head", (), (c.vocab_size, H)),
                           "norm_f": norm(H), "layers": layers},
                "state": {}}

    # ---- pieces of a layer ----
    def _operator(self, p, l: int, a, call: LayerCall):
        if self.c.is_full(l):
            return self._attention(p["attn"], l, a, call)
        return self._mixer(p["gdn"], self.gdn_leaf[l], a, call)

    def _mixer(self, p, gl: int, a, call: LayerCall):
        """The Gated DeltaNet mixer of DeltaNet layer ``gl`` on ``a`` [B, S,
        H], ``p`` the stacked mixer leaves.  Its state layer ``gl`` holds
        two parts, the convolution's last rows and the rule's matrix: zeros
        in the dense forward, the slot's in a cached call, which then keeps
        both as they stand after ``call.last`` (the last real token of a
        padded chunk).  A decode round steps; every other call solves in
        chunks."""
        c, dt_ = self.c, self.c.dtype
        b, s, _ = a.shape
        hk, hv = c.gdn_key_heads, c.gdn_value_heads
        dk, dv, rep = c.gdn_key_dim, c.gdn_value_dim, hv // hk
        # a decode round takes the matrix of EVERY slot, a layer whole, and
        # updates it in place (SlotStates.whole); a chunk cuts its one
        # slot's out; the convolution's rows are small and read by sequence
        st, whole = call.state, call.one_query and call.state is not None
        conv_state = delta = None
        if whole:
            conv_state, delta = st.read(gl, CONV), st.whole(gl, DELTA)
        elif st is not None:
            conv_state, delta = st.read(gl)
        if st is not None:
            conv_state = conv_state.reshape(b, c.conv_taps - 1, -1)
        if gl == 0:
            trace.instant("gdn.plan", {
                "form": "step" if call.one_query else "chunk", "rows": s,
                "batch": b, "chunk": c.gdn_chunk, "heads_k": hk,
                "heads_v": hv, "d_k": dk, "d_v": dv,
                "solve": delta_rule.SOLVE,
                "state_bytes_per_slot": self.kv_cache_spec().bytes_per_slot
                // len(self.gdn_leaf)})
        with jax.named_scope("hetu.gdn.proj"):
            # both projections' outputs are grouped a KEY head: its query,
            # its key, then the values and gates of the heads it serves
            q, k, v, z = jnp.split(
                ops.linear(a, p["qkvz"][gl].astype(dt_)).reshape(
                    b, s, hk, -1), [dk, 2 * dk, 2 * dk + rep * dv], axis=-1)
            beta, a_in = jnp.split(
                ops.linear(a, p["ba"][gl].astype(dt_)).astype(
                    jnp.float32).reshape(b, s, hk, 2 * rep), 2, axis=-1)
            beta = jax.nn.sigmoid(beta).reshape(b, s, hv)
            g = -jnp.exp(p["A_log"][gl]) * jax.nn.softplus(
                a_in.reshape(b, s, hv) + p["dt_bias"][gl])
        with jax.named_scope("hetu.gdn.conv"):
            qkv, conv_state = causal_conv(
                jnp.concatenate([t.reshape(b, s, -1) for t in (q, k, v)], -1),
                p["conv_w"][gl], None, conv_state, call.last)
            qkv = ops.silu(qkv).astype(dt_)
            if st is not None:
                st = st.write(gl, conv_state.reshape(b, -1), CONV)
        q, k, v = jnp.split(qkv, [hk * dk, 2 * hk * dk], axis=-1)
        q, k = q.reshape(b, s, hk, dk), k.reshape(b, s, hk, dk)
        v = v.reshape(b, s, hv, dv)
        if call.one_query:
            with jax.named_scope("hetu.gdn.step"):
                # by slot where the state is: a slot of no sequence of the
                # round has g = 0 and beta = 0, so its matrix stays as it is
                by_slot = (lambda t: st.spread(t, delta)) if whole \
                    else (lambda t: t)
                o, delta = delta_rule.gated_delta_step(
                    *(by_slot(t[:, 0]) for t in (q, k, v, g, beta)), delta)
                o = (st.pick(o) if whole else o)[:, None]
                if whole:
                    st = st.put_whole(gl, DELTA, delta)
        else:
            with jax.named_scope("hetu.gdn.rule"):
                o, delta = delta_rule.gated_delta_chunk_scan(
                    q, k, v, g, beta, delta, chunk=c.gdn_chunk,
                    last=call.last)
                if st is not None:
                    st = st.write(gl, delta, DELTA)
        call.state = st
        with jax.named_scope("hetu.gdn.norm"):
            y = rms_norm_then_gate(o, z.reshape(b, s, hv, dv), p["norm"][gl],
                                   c.rms_eps)
        with jax.named_scope("hetu.gdn.proj"):
            return ops.linear(y.reshape(b, s, -1), p["out"][gl].astype(dt_))

    def _counts(self, stats):
        return counts_with_grouped(self.c, stats)
