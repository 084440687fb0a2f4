"""ShardedGPT: the flagship fully-sharded training program.

One explicit-SPMD (shard_map, all axes manual) GPT-MoE that composes every
parallelism axis in a single jitted train step:

  dp — batch sharding, gradient psum (reference: AllReduce DP plane)
  pp — GPipe collective pipelining over the block stack with ppermute
       activation transfer (reference: pipeline_subexecutor / gpipe)
  sp — ring attention over the sequence axis (new capability; SURVEY §2.3)
  tp — Megatron tensor parallel: col-split QKV/FFN-in, row-split
       out-proj/FFN-out with explicit psum (reference:
       distributed_strategies/simple.py:174-283)
  ep — expert parallel MoE FFN with all_to_all dispatch (reference:
       layers/moe_layer.py + _ncclAllToAll)

Why fully manual: XLA's SPMD partitioner cannot infer a pipeline schedule,
and partial-manual shard_map in current JAX rejects auto-sharded residuals —
so the flagship writes every collective explicitly, Megatron-style.  Each
piece is unit-verified against its SPMD/unsharded oracle in
tests/test_sharded_gpt.py.

Constraints: layers %% pp == 0, heads %% tp == 0, seq %% sp == 0,
batch %% (dp * n_microbatches) == 0, experts %% ep == 0, ffn %% tp == 0.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from hetu_tpu import init as initializers
from hetu_tpu import ops
from hetu_tpu.parallel.ring_attention import _ring_attention_local
from hetu_tpu.ops.moe_ops import (
    layout_transform, make_dispatch_combine, reverse_layout_transform,
    top_k_idx_gate,
)


@dataclass
class ShardedGPTConfig:
    vocab_size: int = 512
    hidden_size: int = 128
    num_layers: int = 4
    num_heads: int = 8
    ffn_size: int = 256
    num_experts: int = 4
    top_k: int = 2
    capacity_factor: float = 2.0
    max_position: int = 128
    n_microbatches: int = 2
    aux_weight: float = 1e-2
    dtype: object = jnp.float32
    vocab_parallel: bool = True   # Megatron vocab-split embedding + CE
    remat: bool = False           # rematerialize blocks (activation memory)


class ShardedGPT:
    def __init__(self, config: ShardedGPTConfig, mesh: Mesh):
        self.c = config
        self.mesh = mesh
        ax = mesh.shape
        self.dp, self.pp, self.sp, self.tp, self.ep = (
            ax.get("dp", 1), ax.get("pp", 1), ax.get("sp", 1),
            ax.get("tp", 1), ax.get("ep", 1))
        c = config
        assert c.num_layers % self.pp == 0
        assert c.num_heads % self.tp == 0
        assert c.ffn_size % self.tp == 0
        assert c.num_experts % self.ep == 0
        self.vocab_parallel = c.vocab_parallel and \
            c.vocab_size % self.tp == 0

    # ---- parameters ----
    def init(self, key):
        c = self.c
        D, F, E, L, V = (c.hidden_size, c.ffn_size, c.num_experts,
                         c.num_layers, c.vocab_size)
        wi = initializers.normal(stddev=0.02)
        hi = initializers.he_normal()
        ks = jax.random.split(key, 8)
        def stack(init_fn, shape, kk):
            return jax.vmap(lambda k: init_fn(k, shape, jnp.float32))(
                jax.random.split(kk, L))
        return {
            "tok_emb": wi(ks[0], (V, D), jnp.float32),
            "pos_emb": wi(ks[1], (c.max_position, D), jnp.float32),
            "blocks": {
                "ln1_scale": jnp.ones((L, D)), "ln1_bias": jnp.zeros((L, D)),
                "qkv_w": stack(wi, (D, 3 * D), ks[2]),
                "qkv_b": jnp.zeros((L, 3 * D)),
                "out_w": stack(wi, (D, D), ks[3]),
                "out_b": jnp.zeros((L, D)),
                "ln2_scale": jnp.ones((L, D)), "ln2_bias": jnp.zeros((L, D)),
                "gate_w": stack(wi, (D, E), ks[4]),
                "w1": stack(hi, (E, D, F), ks[5]),
                "b1": jnp.zeros((L, E, F)),
                "w2": stack(hi, (E, F, D), ks[6]),
                "b2": jnp.zeros((L, E, D)),
            },
            "ln_f_scale": jnp.ones((D,)), "ln_f_bias": jnp.zeros((D,)),
        }

    def param_specs(self):
        pp, tp, ep = "pp", "tp", "ep"
        return {
            # vocab-parallel: embedding rows split over tp (reference
            # MegatronLM vocab-parallel embedding + softmax-CE with partial,
            # distributed_strategies/simple.py:174-283)
            "tok_emb": P("tp") if self.vocab_parallel else P(),
            "pos_emb": P(),
            "blocks": {
                "ln1_scale": P(pp), "ln1_bias": P(pp),
                "qkv_w": P(pp, None, tp), "qkv_b": P(pp, tp),
                "out_w": P(pp, tp, None), "out_b": P(pp),
                "ln2_scale": P(pp), "ln2_bias": P(pp),
                "gate_w": P(pp),
                "w1": P(pp, ep, None, tp), "b1": P(pp, ep, tp),
                "w2": P(pp, ep, tp, None), "b2": P(pp, ep),
            },
            "ln_f_scale": P(), "ln_f_bias": P(),
        }

    def shardings(self):
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s), self.param_specs(),
            is_leaf=lambda x: isinstance(x, P))

    def place(self, params):
        return jax.tree_util.tree_map(jax.device_put, params,
                                      self.shardings())

    # ---- local (per-device) computation ----
    def _attention(self, p_l, h):
        """h: [mb, s_loc, D] replicated over tp. Megatron col/row split +
        ring attention over sp."""
        c = self.c
        mb, s_loc, D = h.shape
        H_loc = c.num_heads // self.tp
        hd = D // c.num_heads
        x = ops.layer_norm(h, p_l["ln1_scale"], p_l["ln1_bias"])
        qkv = x.astype(c.dtype) @ p_l["qkv_w"].astype(c.dtype) + p_l["qkv_b"]
        # fused-QKV layout is HEAD-major (H, 3, hd) so the tp column split
        # hands every rank whole (q,k,v) triples for its heads — the (3,H,hd)
        # layout would split "all of Q + half of K" to rank 0
        qkv = qkv.reshape(mb, s_loc, H_loc, 3, hd)
        q, k, v = (jnp.moveaxis(qkv[:, :, :, i], 1, 2) for i in range(3))
        o = _ring_attention_local(q, k, v, axis="sp", causal=True,
                                  scale=hd ** -0.5)
        o = jnp.moveaxis(o, 1, 2).reshape(mb, s_loc, H_loc * hd)
        y = o.astype(c.dtype) @ p_l["out_w"].astype(c.dtype)
        y = lax.psum(y, "tp") + p_l["out_b"]
        return h + y

    def _moe_ffn(self, p_l, h):
        """MoE FFN: a2a over ep, experts' F dim split over tp."""
        c = self.c
        mb, s_loc, D = h.shape
        E, ep = c.num_experts, self.ep
        E_loc = E // ep
        x = ops.layer_norm(h, p_l["ln2_scale"], p_l["ln2_bias"])
        tokens = x.reshape(-1, D)
        t = tokens.shape[0]
        C = max(1, int(c.capacity_factor * t * c.top_k / E))

        logits = tokens.astype(jnp.float32) @ p_l["gate_w"]
        gates, idx = top_k_idx_gate(logits, c.top_k)
        # load-balancing aux (GShard) — statistics over the GLOBAL batch so
        # the sharded loss is identical to the single-device one
        probs = jax.nn.softmax(logits, axis=-1)
        me = lax.pmean(jnp.mean(probs, axis=0), ("dp", "sp"))
        ce = lax.pmean(jnp.mean(jax.nn.one_hot(idx[:, 0], E), axis=0),
                       ("dp", "sp"))
        aux = c.aux_weight * E * jnp.sum(me * ce)

        disp, comb = make_dispatch_combine(gates, idx, E, C)
        xe = layout_transform(tokens, disp)                    # [E, C, D]
        # dispatch: every ep peer sends each expert its tokens
        xe = lax.all_to_all(xe, "ep", split_axis=0, concat_axis=1,
                            tiled=True)                        # [E_loc, ep*C, D]
        dt = c.dtype
        h1 = jnp.einsum("ecd,edf->ecf", xe.astype(dt),
                        p_l["w1"].astype(dt),
                        preferred_element_type=jnp.float32) + p_l["b1"][:, None]
        h1 = ops.gelu(h1)
        ye = jnp.einsum("ecf,efd->ecd", h1.astype(dt),
                        p_l["w2"].astype(dt),
                        preferred_element_type=jnp.float32)
        ye = lax.psum(ye, "tp") + p_l["b2"][:, None]           # F split → psum
        ye = lax.all_to_all(ye, "ep", split_axis=1, concat_axis=0,
                            tiled=True)                        # [E, C, D]
        out = reverse_layout_transform(ye, comb)
        return h + out.reshape(mb, s_loc, D), aux

    def _block(self, p_l, carry):
        h, aux = carry
        h = self._attention(p_l, h)
        h, a = self._moe_ffn(p_l, h)
        return h, aux + a

    def _local_step(self, params, ids, labels):
        """Local program on every device; all mesh axes manual.

        ids, labels: [b_loc, s_loc] (sharded dp x sp).
        Returns replicated scalar (loss, aux).
        """
        c = self.c
        M = c.n_microbatches
        pp_idx = lax.axis_index("pp")
        sp_idx = lax.axis_index("sp")
        n_pp = self.pp

        b_loc, s_loc = ids.shape
        assert b_loc % M == 0, (b_loc, M)
        mb = b_loc // M

        # embeddings (replicated over pp; each (dp,sp) shard embeds its slice)
        pos = sp_idx * s_loc + jnp.arange(s_loc)
        emb = params["tok_emb"]           # [V/tp, D] when vocab-parallel
        if self.vocab_parallel:
            tp_idx = lax.axis_index("tp")
            v_loc = emb.shape[0]
            rel = ids.astype(jnp.int32) - tp_idx * v_loc
            in_rng = (rel >= 0) & (rel < v_loc)
            h = jnp.take(emb, jnp.clip(rel, 0, v_loc - 1), axis=0)
            h = jnp.where(in_rng[..., None], h, 0.0)
            h = lax.psum(h, "tp")         # assemble full embedding
        else:
            h = ops.embedding_lookup(emb, ids)
        h = h + jnp.take(params["pos_emb"], pos, axis=0)[None]
        xs = h.reshape(M, mb, s_loc, c.hidden_size)

        blocks = params["blocks"]  # leaves [L/pp, ...]

        block = self._block
        if c.remat:
            block = jax.checkpoint(block)

        def stage_apply(h_mb):
            def body(carry, p_l):
                h, aux = block(p_l, carry)
                return (h, aux), None
            (h_out, aux), _ = lax.scan(body, (h_mb, jnp.asarray(0.0)), blocks)
            return h_out, aux

        T = M + n_pp - 1
        buf = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)
        aux_total = jnp.asarray(0.0)

        def tick(carry, tt):
            buf, outs, aux_total = carry
            h_in = jnp.where(pp_idx == 0, xs[jnp.clip(tt, 0, M - 1)], buf)
            h_out, aux = stage_apply(h_in)
            perm = [(j, (j + 1) % n_pp) for j in range(n_pp)]
            buf_next = lax.ppermute(h_out, "pp", perm)
            done = tt - (n_pp - 1)
            valid = (done >= 0) & (pp_idx == n_pp - 1)
            odx = jnp.clip(done, 0, M - 1)
            outs = outs.at[odx].set(jnp.where(valid, h_out, outs[odx]))
            in_flight = (tt >= pp_idx) & (tt - pp_idx < M)
            aux_total = aux_total + jnp.where(in_flight, aux, 0.0)
            return (buf_next, outs, aux_total), None

        (buf, outs, aux_total), _ = lax.scan(
            tick, (buf, outs, aux_total), jnp.arange(T))

        # head + loss on the last stage (tied weights)
        hs = outs.reshape(b_loc, s_loc, c.hidden_size).astype(jnp.float32)
        hs = ops.layer_norm(hs, params["ln_f_scale"], params["ln_f_bias"])
        if self.vocab_parallel:
            # vocab-parallel CE: each tp rank scores its vocab slice; the
            # softmax normalizer and target logit assemble via pmax/psum —
            # the [b, s, V] logits never materialize on one chip
            tp_idx = lax.axis_index("tp")
            v_loc = emb.shape[0]
            logits_loc = hs @ emb.T                      # [b, s, V/tp]
            # global max for stability via all_gather (pmax lacks an AD
            # rule); stop_gradient is exact — the max is stability-only
            m_loc = lax.stop_gradient(jnp.max(logits_loc, axis=-1))
            m = jnp.max(lax.all_gather(m_loc, "tp", axis=0), axis=0)
            se = jnp.sum(jnp.exp(logits_loc - m[..., None]), axis=-1)
            lse = jnp.log(lax.psum(se, "tp")) + m
            rell = labels.astype(jnp.int32) - tp_idx * v_loc
            in_rng = (rell >= 0) & (rell < v_loc)
            tgt_loc = jnp.take_along_axis(
                logits_loc, jnp.clip(rell, 0, v_loc - 1)[..., None],
                axis=-1)[..., 0]
            tgt = lax.psum(jnp.where(in_rng, tgt_loc, 0.0), "tp")
            per_tok = jnp.where(labels == -1, 0.0, lse - tgt)
        else:
            logits = hs @ params["tok_emb"].T
            per_tok = ops.softmax_cross_entropy_sparse(logits, labels,
                                                       ignored_index=-1)
        # global sum / global count (NOT mean-of-shard-ratios): keeps the
        # sharded loss bit-comparable to single-device
        num = lax.psum(jnp.sum(per_tok), ("dp", "sp"))
        den = lax.psum(jnp.sum(labels != -1), ("dp", "sp"))
        local_loss = num / jnp.maximum(den, 1)
        loss = jnp.where(pp_idx == n_pp - 1, local_loss, 0.0)
        loss = lax.psum(loss, "pp")          # broadcast from last stage
        # psum over pp sums DISTINCT layer groups (not replicas): no /pp
        aux_mean = lax.pmean(lax.psum(aux_total, "pp") / M, ("dp", "sp"))
        return loss + aux_mean, aux_mean

    # ---- public API ----
    def loss_fn(self):
        specs = self.param_specs()
        data_spec = P("dp", "sp")
        fn = shard_map(self._local_step, mesh=self.mesh,
                       in_specs=(specs, data_spec, data_spec),
                       out_specs=(P(), P()), check_vma=False)
        return fn

    def make_train_step(self, optimizer):
        loss = self.loss_fn()

        def step(params, opt_state, ids, labels):
            (l, aux), grads = jax.value_and_grad(
                lambda p: loss(p, ids, labels), has_aux=True)(params)
            params, opt_state = optimizer.update(grads, opt_state, params)
            return params, opt_state, {"loss": l, "aux_loss": aux}

        return jax.jit(step, donate_argnums=(0, 1))

    def data_sharding(self):
        return NamedSharding(self.mesh, P("dp", "sp"))
