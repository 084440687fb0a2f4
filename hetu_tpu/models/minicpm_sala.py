"""MiniCPM-SALA: block-sparse attention chosen by compressed keys (InfLLM-V2)
on one layer in four, Lightning linear attention on the other three, a dense
SwiGLU in every layer.

Source of the shapes: ``huggingface.co/openbmb/MiniCPM-SALA`` ``config.json``
(``model_type`` ``minicpm_sala``).  Every norm ``N`` is RMSNorm with a plain
weight; no projection has a bias; ``L`` is the PUBLISHED depth whatever is
held here, ``l`` a layer's PUBLISHED index (``first_layer`` + its index
here); ``mixer_types`` says a layer's kind and is data, not a formula::

    h0 = scale_emb * E[id]
    h  = h + (scale_depth / sqrt(L)) * Op(N(h))
    h  = h + (scale_depth / sqrt(L)) * W_down(silu(W_gate u) * W_up u),  u = N(h)
    logits = W_head N(h) / (hidden_size / dim_model_base)        # untied

    Lightning layer ("lightning-attn"; as many KV heads as heads):
      q, k, v = W_q a, W_k a, W_v a;  q, k normed a head, then rotated (whole
      head, half-rotation layout);  q <- q / sqrt(d)
      a head's S [d, d] float32:  S_t = lambda_h S_(t-1) + k_t v_t^T;  o_t = S_t^T q_t
      lambda_h = exp(-s_h f_l),  s_h = 2^(-8 (h + 1) / heads),  f_l = 1 - l / (L - 1) + 1e-5
      Op = W_o (N_head(o) * sigmoid(W_g a))

    Sparse layer ("minicpm4"; grouped heads, NOT rotated):
      q, k, v = W_q a, W_k a, W_v a;  q, k normed a head
      o = causal softmax(q k^T / sqrt(d)) v over the blocks the query CHOSE
          (``models.block.ChosenBlocks``, ``ops.select_blocks``), every
          position while the sequence is under ``dense_len``
      Op = W_o (o * sigmoid(W_g a))

**What a layer remembers.**  A Lightning layer: one float32 matrix a head
whatever the sequence's length, a STATE LAYER of one part
(``serve.kv_cache.SlotStates``; 2 MB a slot a layer at 32 heads of 128).  It
IS ``ops/ssm.py``'s recurrence (``dt`` = 1, ``A`` = ``-s_h f_l``, ``x`` = v,
``B`` = k, ``C`` = q, ``D`` = 0, a group a head, held ``[heads, d_v, d_k]``),
so the three calls run ``ssd_chunk_scan`` / ``ssm_step`` and nothing is added:
the dense forward scans from zeros, a chunk from its slot's state to the
state after its last REAL token, a decode round steps over every slot's
matrix, a layer whole.  A sparse layer: K and V rows a token AND a compressed
key every ``stride`` tokens, a cache layer of the one page group whose spec
states ``comp_stride``; its pages are the blocks (``page_size`` = ``block``).

**Shared**: the layer, the three calls, both cache entry points, the sparse
layer's attention, the head and the loss are ``models/block.py``'s
(``BlockDecoder``, ``LayerCall``, ``GroupedHeads``, ``ChosenBlocks``), which
takes the three constant factors as ``multipliers`` (``embed``, ``branch``,
``head``).  Here: the configuration, the weights, the tables and the
Lightning mixer.

``jax.named_scope``s: ``hetu.lightning.proj``, ``hetu.lightning.rule`` (a chunk
or the dense forward), ``hetu.lightning.step`` (a decode round),
``hetu.lightning.norm``, ``hetu.sparse.compress|select|attend``,
``hetu.attn.full`` (a sparse layer's dense branch and its gate),
``hetu.ffn.dense``; instants ``lightning.plan`` and ``sparse.plan`` once a
program traced say which form it holds.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from hetu_tpu import ops
from hetu_tpu.models.block import (
    SPARSE_STATS, BlockDecoder, ChosenBlocks, LayerCall, draw_leaf,
)
from hetu_tpu.ops import ssm
from hetu_tpu.telemetry import trace

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"

# the published list (config.json ``mixer_types``): irregular, so data
PUBLISHED_MIXERS = tuple(
    SPARSE if i in (0, 9, 16, 17, 22, 29, 30, 31) else LIGHTNING
    for i in range(32))


@dataclass
class MiniCPMSALAConfig:
    vocab_size: int = 73448
    hidden_size: int = 4096
    num_layers: int = 32                 # held here
    mixer_types: tuple = PUBLISHED_MIXERS    # of the layers held, in order
    num_heads: int = 32                  # a sparse layer's
    num_kv_heads: int = 2
    head_dim: int = 128
    lightning_heads: int = 32            # lightning_nh
    lightning_kv_heads: int = 32         # lightning_nkv
    lightning_head_dim: int = 128
    lightning_chunk: int = 64            # rows the rule solves together
    ffn_size: int = 16384                # intermediate_size
    sparse: ChosenBlocks = field(default_factory=ChosenBlocks)
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    published_layers: int = 32           # L of the factors and the decays
    first_layer: int = 0                 # published index of layer 0 here
    max_position: int = 524288
    dtype: object = jnp.bfloat16         # compute
    param_dtype: object = jnp.bfloat16
    state_dtype: object = jnp.float32    # the rule's matrix

    def __post_init__(self):
        self.mixer_types = tuple(self.mixer_types)
        if len(self.mixer_types) != self.num_layers \
                or set(self.mixer_types) - {SPARSE, LIGHTNING}:
            raise ValueError(f"mixer_types names {self.num_layers} layers, "
                             f"each {SPARSE!r} or {LIGHTNING!r}")
        if self.first_layer + self.num_layers > self.published_layers:
            raise ValueError("the layers held lie inside the published ones")
        if self.num_heads % self.num_kv_heads:
            raise ValueError("kv heads divide the query heads")
        if self.lightning_kv_heads != self.lightning_heads \
                or self.lightning_head_dim != self.head_dim:
            raise ValueError(
                "a Lightning layer has as many KV heads as heads and the "
                "sparse layers' head size (one rotary table serves both)")

    # every layer's feed-forward is dense: BlockDecoder reads it
    @property
    def first_dense(self) -> int:
        return self.num_layers

    def decay_rates(self, l: int):
        """``s_h f_l`` [heads] float32 of the layer held at index ``l``: a
        head's state is multiplied by ``exp(-s_h f_l)`` a row (Lightning
        Attention-2's slopes, the layer factor by the PUBLISHED index)."""
        h = jnp.arange(1, self.lightning_heads + 1, dtype=jnp.float32)
        f = 1.0 - (self.first_layer + l) / (self.published_layers - 1) + 1e-5
        return jnp.exp2(-8.0 * h / self.lightning_heads) * f

    def unit_stds(self) -> dict:
        """By leaf, the std ``init`` draws it with (``*_about``: the mean a
        norm's weight is drawn round).  The embedding at ``1 / scale_emb``
        and the head at ``(hidden / dim_model_base) / sqrt(hidden)``, so
        that the stream starts at unit rms and the logits are of order one
        through the model's own constant factors; the in-projections at
        ``1 / sqrt(fan-in)``.  A sparse layer's q and k norms weigh by
        ``kernel ** 0.25`` each: a compressed key is the mean of ``kernel``
        keys of random directions, ``sqrt(kernel)`` shorter than one of
        them, and at unit weights every q . c score would lie within a fifth
        of the next and every block tie; so a q . c score spreads by about
        one and a q . k score by ``sqrt(kernel)``, peaked as trained
        attention is.  The out-projections: ``attn.o`` reckons a softmax's
        average under a sigmoid gate at a quarter, ``lin.o`` the normed
        read-out under its gate and ``ffn.down`` the gated product at a
        half, so that a branch is of order one BEFORE the model's residual
        factor (``scale_depth / sqrt(L)`` = 0.25) and the stream grows from
        one to about two over the published depth."""
        H = self.hidden_size
        over = 1.0 / math.sqrt(H)
        qw = self.num_heads * self.head_dim
        lw = self.lightning_heads * self.lightning_head_dim
        return {
            "tok_emb": 1.0 / self.scale_emb,
            "lm_head": (H / self.dim_model_base) * over, "norm": 0.1,
            "attn.q": over, "attn.k": over, "attn.v": over, "attn.g": over,
            "attn.o": 4.0 / math.sqrt(qw),
            "attn.qk_norm_about": self.sparse.kernel ** 0.25,
            "lin.q": over, "lin.k": over, "lin.v": over, "lin.g": over,
            "lin.o": 2.0 / math.sqrt(lw),
            "ffn.gate": over, "ffn.up": over,
            "ffn.down": 2.0 / math.sqrt(self.ffn_size),
        }


class MiniCPMSALAModel(BlockDecoder):
    """``params``: ``tok_emb`` [V, H], ``lm_head`` [V, H], ``norm_f``,
    ``layers``: ``attn_norm``/``ffn_norm`` [L, H] (the operator's norm under
    the name the block reads it by), ``attn`` (``GroupedHeads``' leaves over
    the SPARSE layers: q [qw, H], k [kvw, H], v [H, kvw], o [qw, H], g [H,
    qw] the output gate's projection, q_norm / k_norm [A, D]), ``lin`` over
    the Lightning layers {q, k, v, g [H, lw], o [lw, H], q_norm / k_norm /
    norm [G, d]}, ``ffn`` {gate, up [H, F], down [F, H]} every layer.  Each
    matrix is a TUPLE of the layers' arrays, as ``init`` yields them: the
    layers are a Python loop, and a layer cut out of a stacked leaf at a
    static index is written into a buffer of its own in every call
    (``layers/base.py`` ``Module.serving_params``).  ``leaf[l]`` reads
    either form."""

    step_stats = SPARSE_STATS

    def __init__(self, config: MiniCPMSALAConfig):
        c = config
        sparse = [l for l, m in enumerate(c.mixer_types) if m == SPARSE]
        lin = [l for l, m in enumerate(c.mixer_types) if m == LIGHTNING]
        # layer -> its index among the layers of its own kind: a sparse
        # layer's attention leaves and cache layer (of the one page group),
        # a Lightning layer's leaves and state layer
        self.lin_leaf = {l: i for i, l in enumerate(lin)}
        super().__init__(
            c, None,
            attn_leaf={l: i for i, l in enumerate(sparse)},
            cache_layer={l: (0, i) for i, l in enumerate(sparse)},
            rotated=(),                      # attn_use_rope false
            multipliers={
                "embed": c.scale_emb,
                "branch": c.scale_depth / math.sqrt(c.published_layers),
                "head": c.dim_model_base / c.hidden_size},
            sparse=c.sparse, sparse_layers=sparse)

    # ---- the cache this model asks of the serving engine ----
    def kv_cache_spec(self):
        from hetu_tpu.serve.kv_cache import KVCacheSpec

        c = self.c
        return KVCacheSpec(
            num_layers=len(self.attn_leaf), num_kv_heads=c.num_kv_heads,
            head_dim=c.head_dim, dtype=c.dtype,
            comp_stride=c.sparse.stride if self.attn_leaf else None,
            state_layers=len(self.lin_leaf),
            state_parts=(("lightning", (
                c.lightning_heads, c.lightning_head_dim,
                c.lightning_head_dim), c.state_dtype),))

    # ---- weights ----
    def init(self, key):
        """Every matrix in ``param_dtype`` at its own std
        (``config.unit_stds()``), a large leaf drawn a piece at a time, a
        matrix a layer an array of its own."""
        c = self.c
        pd, std = c.param_dtype, c.unit_stds()
        H, L, F = c.hidden_size, c.num_layers, c.ffn_size
        A, G = len(self.attn_leaf), len(self.lin_leaf)
        qw, kvw = c.num_heads * c.head_dim, c.num_kv_heads * c.head_dim
        lw, d = c.lightning_heads * c.lightning_head_dim, c.head_dim
        ks = iter(jax.random.split(key, 32))

        def each(name, n, shape):
            """A matrix a layer: a tuple of the layers' arrays."""
            return tuple(draw_leaf(k, (), shape, std[name], pd)
                         for k in jax.random.split(next(ks), n))

        def norm(*shape, about=1.0):
            return (about + draw_leaf(next(ks), (), shape, std["norm"],
                                      jnp.float32)).astype(pd)

        layers = {
            "attn_norm": norm(L, H), "ffn_norm": norm(L, H),
            # q and k [out, in], as the block's GroupedHeads reads them
            "attn": {"q": each("attn.q", A, (qw, H)),
                     "k": each("attn.k", A, (kvw, H)),
                     "v": each("attn.v", A, (H, kvw)),
                     "g": each("attn.g", A, (H, qw)),
                     "o": each("attn.o", A, (qw, H)),
                     "q_norm": norm(A, d, about=std["attn.qk_norm_about"]),
                     "k_norm": norm(A, d, about=std["attn.qk_norm_about"])},
            "lin": {"q": each("lin.q", G, (H, lw)),
                    "k": each("lin.k", G, (H, lw)),
                    "v": each("lin.v", G, (H, lw)),
                    "g": each("lin.g", G, (H, lw)),
                    "o": each("lin.o", G, (lw, H)),
                    "q_norm": norm(G, d), "k_norm": norm(G, d),
                    "norm": norm(G, d)},
            "ffn": {"gate": each("ffn.gate", L, (H, F)),
                    "up": each("ffn.up", L, (H, F)),
                    "down": each("ffn.down", L, (F, H))},
        }
        def rows(name):
            """[V, H] in runs of a few rows: 73,448 is 8 x 9,181, and a
            draw of eight pieces of 9,181 rows took 140 s to COMPILE for the
            chip (twice: ``PERF.md`` section 6, PR 56); 9,181 pieces of 8
            rows take one."""
            few = math.gcd(c.vocab_size, 1024)
            return draw_leaf(next(ks), (c.vocab_size // few,), (few, H),
                             std[name], pd).reshape(c.vocab_size, H)

        return {"params": {"tok_emb": rows("tok_emb"),
                           "lm_head": rows("lm_head"),
                           "norm_f": norm(H), "layers": layers},
                "state": {}}

    # ---- pieces of a layer ----
    def _operator(self, p, l: int, a, call: LayerCall):
        if l in self.lin_leaf:
            return self._mixer(p["lin"], l, a, call)
        return self._attention(p["attn"], l, a, call)

    def _mixer(self, p, l: int, a, call: LayerCall):
        """The Lightning mixer of layer ``l`` on ``a`` [B, S, H], ``p`` the
        Lightning layers' leaves.  Its state layer holds the rule's matrix a
        head: zeros in the dense forward, the slot's in a cached call, which
        then keeps it as it stands after ``call.last`` (the last real token
        of a padded chunk).  A decode round steps over EVERY slot's matrix,
        a layer whole (``SlotStates.whole``): a slot of no sequence of the
        round gets ``dt`` = 0, which neither decays nor feeds it."""
        c, dt_ = self.c, self.c.dtype
        gl = self.lin_leaf[l]
        b, s, _ = a.shape
        nh, d = c.lightning_heads, c.lightning_head_dim
        st, whole = call.state, call.one_query and call.state is not None
        if gl == 0:
            trace.instant("lightning.plan", {
                "form": "step" if call.one_query else "chunk", "rows": s,
                "batch": b, "chunk": c.lightning_chunk, "heads": nh, "d": d,
                "rule": "ops.ssm", "state_bytes_per_slot":
                    self.kv_cache_spec().bytes_per_slot
                    // len(self.lin_leaf)})
        with jax.named_scope("hetu.lightning.proj"):
            q, k, v = (ops.linear(a, p[n][gl].astype(dt_)).reshape(
                b, s, nh, d) for n in ("q", "k", "v"))
            gate = ops.linear(a, p["g"][gl].astype(dt_))
            q = self._rotate(self._norm(q, p["q_norm"][gl]), call.cos,
                             call.sin) * (d ** -0.5)
            k = self._rotate(self._norm(k, p["k_norm"][gl]), call.cos,
                             call.sin)
        rates, none = -c.decay_rates(l), jnp.zeros((nh,), jnp.float32)
        if call.one_query:
            with jax.named_scope("hetu.lightning.step"):
                held = st.whole(gl, 0) if whole \
                    else None if st is None else st.read(gl, 0)
                by_slot = (lambda t: st.spread(t, held)) if whole \
                    else (lambda t: t)
                if held is None:
                    held = jnp.zeros((b, nh, d, d), jnp.float32)
                o, held = ssm.ssm_step(
                    by_slot(v[:, 0]), by_slot(jnp.ones((b, nh), jnp.float32)),
                    rates, by_slot(k[:, 0]), by_slot(q[:, 0]), none, held)
                o = (st.pick(o) if whole else o)[:, None]
                if whole:
                    st = st.put_whole(gl, 0, held)
                elif st is not None:
                    st = st.write(gl, held, 0)
        else:
            with jax.named_scope("hetu.lightning.rule"):
                o, held = ssm.ssd_chunk_scan(
                    v, jnp.ones((b, s, nh), jnp.float32), rates, k, q, none,
                    None if st is None else st.read(gl, 0),
                    chunk=c.lightning_chunk, last=call.last)
                if st is not None:
                    st = st.write(gl, held, 0)
        call.state = st
        with jax.named_scope("hetu.lightning.norm"):
            y = self._norm(o, p["norm"][gl]).reshape(b, s, -1)
            y = y * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(y.dtype)
        with jax.named_scope("hetu.lightning.proj"):
            return ops.linear(y.astype(dt_), p["o"][gl].astype(dt_))
