"""MoE transformer LM: every other FFN replaced by an MoE layer.

Reference: examples/moe (HetuMoE scripts, top-1/top-2 gating over 8-16 GPUs)
— here the experts shard over the 'ep' mesh axis and XLA inserts the A2A pair
(BASELINE.json config #5 workload).

Routing: the CAPACITY path (``layers/moe.py`` ``MoELayer``: static slots per
expert, padded, overflow dropped).  This model trains; it has no cache entry
points and is not served.  The served expert model is
``models/longcat_flash.py``, whose ``HeldExpertLayer`` routes without
capacity and without drops.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from hetu_tpu import init as initializers
from hetu_tpu import ops
from hetu_tpu.layers.base import Module
from hetu_tpu.layers.attention import MultiHeadAttention
from hetu_tpu.layers.linear import Linear
from hetu_tpu.layers.norm import LayerNorm
from hetu_tpu.layers.moe import Expert, MoELayer, TopKGate


@dataclass
class MoEConfig:
    vocab_size: int = 32000
    hidden_size: int = 512
    num_layers: int = 4
    num_heads: int = 8
    ffn_size: int = 2048
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    max_position: int = 512
    dtype: object = jnp.float32


class MoETransformer(Module):
    def __init__(self, config: MoEConfig, *, mesh=None, ep_axis: str = "ep"):
        c = self.c = config
        self.attn = MultiHeadAttention(c.hidden_size, c.num_heads,
                                       causal=True, dtype=c.dtype)
        self.ln1 = LayerNorm(c.hidden_size)
        self.ln2 = LayerNorm(c.hidden_size)
        self.moe = MoELayer(
            TopKGate(c.hidden_size, c.num_experts, c.top_k),
            Expert(c.num_experts, c.hidden_size, c.ffn_size, dtype=c.dtype),
            capacity_factor=c.capacity_factor, mesh=mesh, ep_axis=ep_axis)
        self.w_init = initializers.normal(stddev=0.02)

    def init(self, key):
        c = self.c
        ks = jax.random.split(key, 3 + c.num_layers * 4)
        params = {
            "tok_emb": self.w_init(ks[0], (c.vocab_size, c.hidden_size)),
            "pos_emb": self.w_init(ks[1], (c.max_position, c.hidden_size)),
        }
        for l in range(c.num_layers):
            base = 2 + l * 4
            params[f"layer{l}"] = {
                "attn": self.attn.init(ks[base])["params"],
                "ln1": self.ln1.init(ks[base + 1])["params"],
                "moe": self.moe.init(ks[base + 2])["params"],
                "ln2": self.ln2.init(ks[base + 3])["params"],
            }
        return {"params": params, "state": {}}

    def apply(self, variables, input_ids, *, train: bool = False, rng=None):
        p = variables["params"]
        c = self.c
        b, s = input_ids.shape
        h = ops.embedding_lookup(p["tok_emb"], input_ids)
        h = (h + p["pos_emb"][None, :s]).astype(c.dtype)
        total_aux = 0.0
        for l in range(c.num_layers):
            pl = p[f"layer{l}"]
            a, _ = self.attn.apply({"params": pl["attn"], "state": {}},
                                   ops.layer_norm(h, pl["ln1"]["scale"],
                                                  pl["ln1"]["bias"]),
                                   train=train,
                                   rng=None if rng is None else
                                   jax.random.fold_in(rng, l))
            h = h + a
            moe_in = ops.layer_norm(h, pl["ln2"]["scale"], pl["ln2"]["bias"])
            (m, aux), _ = self.moe.apply({"params": pl["moe"], "state": {}},
                                         moe_in, train=train)
            total_aux = total_aux + aux
            h = h + m.astype(c.dtype)
        logits = ops.linear(h.astype(jnp.float32), p["tok_emb"].T)
        return (logits, total_aux), {}

    def lm_loss_fn(self):
        def fn(params, model_state, batch, rng, train):
            ids = batch[0] if isinstance(batch, (tuple, list)) else batch
            (logits, aux), _ = self.apply({"params": params, "state": {}},
                                          ids, train=train, rng=rng)
            lm = jnp.mean(ops.softmax_cross_entropy_sparse(
                logits[:, :-1], ids[:, 1:]))
            return lm + aux, ({"lm_loss": lm, "aux_loss": aux}, model_state)
        return fn

    def param_specs(self, params):
        """EP sharding: expert-stacked weights split on dim 0 over 'ep'."""
        from jax.sharding import PartitionSpec as P

        def spec(path, leaf):
            if "experts" in path:
                return P("ep", *(None,) * (leaf.ndim - 1))
            return P()

        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        return jax.tree_util.tree_unflatten(
            treedef,
            [spec(jax.tree_util.keystr(pa), le) for pa, le in flat])
