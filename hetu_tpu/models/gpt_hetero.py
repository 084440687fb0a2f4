"""HeteroGPT: executes a searched per-layer parallelism Plan.

Reference: tools/Galvatron — the runtime half of the planner: each layer
gets its own TP degree / DP type from the searched JSON config
(core/hybrid_parallel_config.py) and activations are redistributed between
differently-parallelized layers (core/redistribute.py).

TPU form: per-layer (non-stacked) parameters so every layer can carry its
own PartitionSpec from a `strategies.search.Plan`; XLA's SPMD partitioner
inserts the activation resharding between layers (the redistribute.py
split/gather pairs) from the sharding mismatch.  `PlanStrategy` adapts a
Plan to the Executor's dist_strategy hook, so the full loop is:

    layers = transformer_layer_specs(...)          # cost IR
    plan = OptCNNSearching(sim, dp).search(layers) # search
    model = HeteroGPT(cfg)
    ex = Executor(model.lm_loss_fn(), opt, mesh=mesh,
                  dist_strategy=PlanStrategy(plan))

Pipeline plans (stage_bounds / meta['pp'] > 1) are NOT executable here —
PlanStrategy covers the intra-stage SPMD layout; pair it with the GPipe
executor for the pipeline dimension.
"""

from __future__ import annotations

from dataclasses import dataclass
import re

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from hetu_tpu import init as initializers
from hetu_tpu import ops
from hetu_tpu.layers.transformer import TransformerBlock
from hetu_tpu.models.gpt import GPTConfig, GPTModel
from hetu_tpu.parallel.strategies.base import Strategy
from hetu_tpu.parallel.strategies.search import Plan
from hetu_tpu.profiler.simulator import ShardOption


class HeteroGPT(GPTModel):
    """GPT with per-layer parameter trees (plan-shardable).

    Subclasses GPTModel: the loss (lm_loss_fn) is inherited — only the
    parameter layout (per-layer dicts instead of scan-stacked) and the
    layer loop differ.
    """

    def __init__(self, config: GPTConfig, *,
                 layer_remat: "tuple[bool, ...] | None" = None):
        """``layer_remat``: per-transformer-layer activation-checkpoint
        flags, normally taken from a searched Galvatron plan via
        :func:`plan_block_remat` (reference per-layer ckpt flag,
        tools/Galvatron/galvatron/core/hybrid_parallel_config.py:26-110).
        The searcher prices remat per layer; this executes it, so the
        memory the plan certified is the memory the compiled step uses."""
        super().__init__(config)
        if layer_remat is not None and len(layer_remat) != config.num_layers:
            raise ValueError(
                f"layer_remat has {len(layer_remat)} flags for "
                f"{config.num_layers} layers")
        self.layer_remat = layer_remat

    @classmethod
    def from_plan(cls, config: GPTConfig, plan: "Plan") -> "HeteroGPT":
        """The full Galvatron loop in one call: build the model with the
        plan's searched per-layer remat flags applied (pair with
        ``PlanStrategy(plan)`` on the Executor for the sharding half)."""
        return cls(config,
                   layer_remat=plan_block_remat(plan, config.num_layers))

    def init(self, key):
        c = self.c
        ks = jax.random.split(key, c.num_layers + 3)
        params = {
            "tok_emb": self.w_init(ks[0], (c.vocab_size, c.hidden_size)),
            "pos_emb": self.w_init(ks[1], (c.max_position, c.hidden_size)),
            "ln_f_scale": jnp.ones((c.hidden_size,)),
            "ln_f_bias": jnp.zeros((c.hidden_size,)),
        }
        for i in range(c.num_layers):
            params[f"layer{i}"] = self.block.init(ks[2 + i])["params"]
        return {"params": params, "state": {}}

    def hidden_states(self, variables, input_ids, *, train: bool = False,
                      rng=None):
        p = variables["params"]
        c = self.c
        b, s = input_ids.shape
        h = ops.embedding_lookup(p["tok_emb"], input_ids)
        h = h + p["pos_emb"][None, :s]
        if train and c.dropout_rate > 0:  # same regularization as GPTModel
            h = ops.dropout(h, c.dropout_rate, jax.random.fold_in(rng, 999),
                            train=True)
        h = h.astype(c.dtype)
        for i in range(c.num_layers):
            lrng = None if rng is None else jax.random.fold_in(rng, i)

            def block_fn(lp, hh, lr, _train=train):
                return self.block.apply({"params": lp, "state": {}}, hh,
                                        train=_train, rng=lr)[0]

            if self.layer_remat is not None and self.layer_remat[i]:
                # execute the plan's per-layer ckpt flag: activations of
                # this layer are rematerialized in backward instead of held
                block_fn = ops.remat(block_fn)
            h = block_fn(p[f"layer{i}"], h, lrng)
        return ops.layer_norm(h.astype(jnp.float32), p["ln_f_scale"],
                              p["ln_f_bias"])

    def apply(self, variables, input_ids, *, train: bool = False, rng=None):
        h = self.hidden_states(variables, input_ids, train=train, rng=rng)
        return ops.linear(h, variables["params"]["tok_emb"].T), {}


_LAYER_RE = re.compile(r"\['layer(\d+)'\]")


def plan_block_remat(plan: Plan, num_layers: int) -> "tuple[bool, ...]":
    """Fold a searched plan's per-LayerSpec remat flags into per-block
    flags for :class:`HeteroGPT`.

    The transformer_layer_specs chain is [embed, (attn_i, ffn_i)*, head];
    a block checkpoints when the searcher flagged EITHER of its halves
    (jax.checkpoint granularity is the block — the conservative rounding:
    never less remat than the plan's memory certificate assumed).
    Plans without remat metadata (non-Galvatron searchers) mean no remat.
    """
    flags = plan.meta.get("remat")
    if not flags:
        return tuple(False for _ in range(num_layers))
    body = flags[1:-1]
    if len(body) != 2 * num_layers:
        raise ValueError(
            f"plan has {len(body)} body remat flags for {num_layers} "
            "transformer layers (expected attn+ffn per layer)")
    return tuple(bool(body[2 * i] or body[2 * i + 1])
                 for i in range(num_layers))


def _add_dp_axis(spec: P, ndim: int) -> P:
    """Shard the first unsharded dim over 'dp' (FSDP/ZeRO param slicing).

    Combined with tp: e.g. qkv [H,3H] tp_col P(None,'tp') -> P('dp','tp');
    ffn_out [F,H] tp_row P('tp',None) -> P('tp','dp').  Dims that don't
    divide fall back to replication in Strategy._fit.
    """
    dims = list(spec) + [None] * (ndim - len(spec))
    for i, e in enumerate(dims):
        if e is None:
            dims[i] = "dp"
            return P(*dims)
    return spec  # every dim already sharded


class PlanStrategy(Strategy):
    """Adapt a searched Plan to per-layer PartitionSpecs.

    The Plan's layer_options are matched to HeteroGPT's transformer layers
    in order, skipping non-transformer entries (embed/head LayerSpecs).
    Layers whose option has tp > 1 get Megatron col/row splits; 'dp'
    layers stay replicated (grad-allreduce DP via the sharded batch).

    Per-layer dp_type executes Galvatron's DP-flavor axis
    (core/hybrid_parallel_config.py:26,70,76 / comm_groups.py:58-196):
      'sdp'   — params sharded over the dp mesh axis too (FSDP): XLA SPMD
                inserts the param allgathers and gradient reduce_scatters;
      'zero1' — params replicated but optimizer slots sharded over dp
                (slot_spec below): the slot update runs shard-wise and XLA
                allgathers the updated params.
    embed_sdp mirrors the reference's flag: apply sdp to the (untied
    position/token) embedding tables as well.
    """

    # Megatron split points by param name, shared across model families:
    # GPT blocks expose ffn_in/ffn_out, Llama blocks ffn_gate/ffn_up/
    # ffn_down (SwiGLU: both input mats col-split, down row-split)
    COL = ("qkv_weight", "qkv_bias")
    ROW = ("out_weight",)
    FFN_COL = ("ffn_in", "ffn_gate", "ffn_up")
    FFN_ROW = ("ffn_out", "ffn_down")

    def __init__(self, plan: Plan, *, embed_sdp: bool = False):
        if plan.stage_bounds or plan.meta.get("pp", 1) > 1:
            raise ValueError(
                "plan carries pipeline stages; PlanStrategy executes the "
                "intra-stage SPMD layout only — run the pipeline dimension "
                "with parallel.pipeline.GPipe")
        # the transformer_layer_specs chain is [embed, (attn_i, ffn_i)*,
        # head]; keep attn and ffn tp SEPARATE so the executed layout is
        # exactly what the searcher costed
        body = plan.layer_options[1:-1]
        self.block_opt = {}
        for li in range(len(body) // 2):
            self.block_opt[li] = (body[2 * li], body[2 * li + 1])
        # honor the searcher's dp_type choice for the embed/head LayerSpecs
        # too (the memory budget was certified WITH them): tok_emb is tied
        # to the head here, so either edge option requesting sharding wins
        edge = [plan.layer_options[0], plan.layer_options[-1]]
        self.embed_sdp = embed_sdp or any(
            getattr(o, "dp_type", "dp") == "sdp" for o in edge)
        self.embed_zero1 = any(
            getattr(o, "dp_type", "dp") == "zero1" for o in edge)

    def _layer_opt(self, path):
        m = _LAYER_RE.search(path)
        if not m:
            return None
        attn_opt, ffn_opt = self.block_opt.get(
            int(m.group(1)), (ShardOption("dp"), ShardOption("dp")))
        is_attn = "attn" in path or any(k in path for k in
                                        self.COL + self.ROW)
        return attn_opt if is_attn else ffn_opt

    def _tp_spec(self, path, ndim, tp):
        if tp <= 1:
            return P()
        if any(k in path for k in self.COL + self.FFN_COL):
            return P(*((None,) * (ndim - 1)), "tp")
        if "bias" not in path and any(k in path
                                      for k in self.ROW + self.FFN_ROW):
            if ndim >= 2:
                return P(*((None,) * (ndim - 2)), "tp", None)
        return P()

    # edge (non-transformer) params the embed/head dp_type options govern:
    # tied GPT embeddings and Llama's UNTIED lm_head — the searcher's
    # memory certificate assumes the head shards when its edge says so
    EDGE = ("tok_emb", "pos_emb", "lm_head")

    def param_spec(self, path, leaf):
        ndim = leaf.ndim if hasattr(leaf, "ndim") else len(leaf.shape)
        opt = self._layer_opt(path)
        if opt is None:
            if self.embed_sdp and any(k in path for k in self.EDGE):
                return _add_dp_axis(P(), ndim)
            return P()
        spec = self._tp_spec(path, ndim, opt.tp)
        if opt.dp_type == "sdp":
            spec = _add_dp_axis(spec, ndim)
        return spec

    def slot_spec(self, path, leaf):
        ndim = leaf.ndim if hasattr(leaf, "ndim") else len(leaf.shape)
        opt = self._layer_opt(path)
        if opt is None:
            if (self.embed_sdp or self.embed_zero1) and \
                    any(k in path for k in self.EDGE):
                return _add_dp_axis(P(), ndim)
            return self.param_spec(path, leaf)
        spec = self._tp_spec(path, ndim, opt.tp)
        if opt.dp_type in ("sdp", "zero1"):
            spec = _add_dp_axis(spec, ndim)
        return spec
