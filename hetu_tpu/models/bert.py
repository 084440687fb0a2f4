"""BERT.

Reference: examples/nlp/bert (hetu BERT-base pretraining, BASELINE.json
config #3).  Encoder-only transformer with token/position/segment embeddings,
post-LN blocks, MLM + NSP heads.

TPU notes: the whole model is one jit region; blocks run under lax.scan over
stacked per-layer params ("scan-over-layers") so compile time stays flat with
depth and XLA pipelines layer collectives.  Weights are Megatron-shardable
(see parallel/strategies/megatron.py).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from hetu_tpu import init as initializers
from hetu_tpu import ops
from hetu_tpu.layers.base import Module
from hetu_tpu.layers.transformer import TransformerBlock


@dataclass
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    ffn_size: int = 3072
    max_position: int = 512
    type_vocab_size: int = 2
    dropout_rate: float = 0.1
    dtype: object = jnp.float32
    attention_impl: str = "xla"  # 'flash' = Pallas kernel (TPU); takes no
    # attention_mask (a masked call raises — use 'xla' for padded batches)


class BertModel(Module):
    def __init__(self, config: BertConfig):
        self.c = config
        self.block = TransformerBlock(
            config.hidden_size, config.num_heads, config.ffn_size,
            dropout_rate=config.dropout_rate, causal=False, pre_norm=False,
            dtype=config.dtype, attention_impl=config.attention_impl)
        self.w_init = initializers.truncated_normal(stddev=0.02)

    def init(self, key):
        c = self.c
        ks = jax.random.split(key, 8)
        block_keys = jax.random.split(ks[0], c.num_layers)
        # stacked per-layer params for scan-over-layers
        blocks = jax.vmap(lambda k: self.block.init(k)["params"])(block_keys)
        params = {
            "tok_emb": self.w_init(ks[1], (c.vocab_size, c.hidden_size)),
            "pos_emb": self.w_init(ks[2], (c.max_position, c.hidden_size)),
            "seg_emb": self.w_init(ks[3], (c.type_vocab_size, c.hidden_size)),
            "emb_ln_scale": jnp.ones((c.hidden_size,)),
            "emb_ln_bias": jnp.zeros((c.hidden_size,)),
            "blocks": blocks,
            "pooler_w": self.w_init(ks[4], (c.hidden_size, c.hidden_size)),
            "pooler_b": jnp.zeros((c.hidden_size,)),
            # MLM head (tied decoder uses tok_emb.T) + NSP head
            "mlm_dense_w": self.w_init(ks[5], (c.hidden_size, c.hidden_size)),
            "mlm_dense_b": jnp.zeros((c.hidden_size,)),
            "mlm_ln_scale": jnp.ones((c.hidden_size,)),
            "mlm_ln_bias": jnp.zeros((c.hidden_size,)),
            "mlm_bias": jnp.zeros((c.vocab_size,)),
            "nsp_w": self.w_init(ks[6], (c.hidden_size, 2)),
            "nsp_b": jnp.zeros((2,)),
        }
        return {"params": params, "state": {}}

    def encode(self, params, input_ids, token_type_ids=None,
               attention_mask=None, *, train=False, rng=None):
        c = self.c
        b, s = input_ids.shape
        h = ops.embedding_lookup(params["tok_emb"], input_ids)
        h = h + params["pos_emb"][None, :s]
        if token_type_ids is not None:
            h = h + ops.embedding_lookup(params["seg_emb"], token_type_ids)
        h = ops.layer_norm(h, params["emb_ln_scale"], params["emb_ln_bias"])
        if train and c.dropout_rate > 0:
            h = ops.dropout(h, c.dropout_rate, jax.random.fold_in(rng, 999),
                            train=True)
        h = h.astype(c.dtype)
        mask = None
        if attention_mask is not None:
            mask = attention_mask[:, None, None, :]  # [B,1,1,S]

        def layer(carry, xs):
            p_l, k_l = xs
            out, _ = self.block.apply({"params": p_l, "state": {}}, carry,
                                      mask=mask, train=train, rng=k_l)
            return out, None

        keys = (jax.random.split(rng, c.num_layers) if rng is not None
                else jnp.zeros((c.num_layers, 2), jnp.uint32))
        h, _ = jax.lax.scan(layer, h, (params["blocks"], keys))
        return h.astype(jnp.float32)

    def apply(self, variables, input_ids, token_type_ids=None,
              attention_mask=None, *, train: bool = False, rng=None):
        """Returns (sequence_output [B,S,H], pooled [B,H])."""
        p = variables["params"]
        seq = self.encode(p, input_ids, token_type_ids, attention_mask,
                          train=train, rng=rng)
        pooled = ops.tanh(ops.linear(seq[:, 0], p["pooler_w"], p["pooler_b"]))
        return (seq, pooled), {}

    def mlm_logits(self, params, seq):
        h = ops.gelu(ops.linear(seq, params["mlm_dense_w"],
                                params["mlm_dense_b"]))
        h = ops.layer_norm(h, params["mlm_ln_scale"], params["mlm_ln_bias"])
        return ops.linear(h, params["tok_emb"].T, params["mlm_bias"])

    def pretrain_loss_fn(self):
        """MLM + NSP loss (reference: examples/nlp/bert pretraining scripts).

        batch = (input_ids, token_type_ids, attention_mask, mlm_labels
                 [-1 = unmasked], nsp_labels)
        """
        def fn(params, model_state, batch, rng, train):
            input_ids, tok_type, attn_mask, mlm_labels, nsp_labels = batch
            seq = self.encode(params, input_ids, tok_type, attn_mask,
                              train=train, rng=rng)
            logits = self.mlm_logits(params, seq)
            per_tok = ops.softmax_cross_entropy_sparse(logits, mlm_labels,
                                                       ignored_index=-1)
            denom = jnp.maximum(jnp.sum(mlm_labels != -1), 1)
            mlm_loss = jnp.sum(per_tok) / denom
            pooled = ops.tanh(ops.linear(seq[:, 0], params["pooler_w"],
                                         params["pooler_b"]))
            nsp_logits = ops.linear(pooled, params["nsp_w"], params["nsp_b"])
            nsp_loss = jnp.mean(
                ops.softmax_cross_entropy_sparse(nsp_logits, nsp_labels))
            loss = mlm_loss + nsp_loss
            return loss, ({"mlm_loss": mlm_loss, "nsp_loss": nsp_loss},
                          model_state)
        return fn


def bert_base(**kw) -> BertModel:
    return BertModel(BertConfig(**kw))


def bert_large(**kw) -> BertModel:
    kw.setdefault("hidden_size", 1024)
    kw.setdefault("num_layers", 24)
    kw.setdefault("num_heads", 16)
    kw.setdefault("ffn_size", 4096)
    return BertModel(BertConfig(**kw))
