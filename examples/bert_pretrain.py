"""BERT pretraining (MLM + NSP) — reference: examples/nlp/bert
(BASELINE config #3).

Synthetic corpus by default (no egress); to use real data, provide token-id
numpy arrays via --data. Megatron TP via --tp, DP via --dp.
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from hetu_tpu.utils.platform import bootstrap_example

bootstrap_example(8)  # virtual CPU devices for bare runs + compile cache

import jax
import numpy as np

import hetu_tpu as ht
from hetu_tpu import lr, models, optim
from hetu_tpu.parallel.strategies import MegatronLM
from hetu_tpu.train.executor import TrainState
from hetu_tpu.utils.logger import MetricLogger


def synthetic_batch(g, B, S, vocab):
    """STRUCTURED synthetic pretraining stream (uniform-random tokens
    would pin the MLM loss at its ln(vocab) floor — nothing to learn).

    Sticky-Markov stream: token[t] repeats token[t-1] with probability
    0.9, else redraws from the sequence's own 16-token topic vocabulary.
    A masked position is inferable from its (visible) neighbors, so the
    MLM loss can fall from the ln(vocab) floor toward the ~1.2-nat
    conditional entropy of the chain.  NSP is consistent: positive pairs
    continue the same topic vocabulary across the segment boundary,
    negatives switch to a disjoint one.
    """
    half = S // 2
    topic_a = g.integers(5, vocab, (B, 16))   # per-sequence vocabularies
    topic_b = g.integers(5, vocab, (B, 16))   # for NSP negatives
    nsp = g.integers(0, 2, (B,)).astype(np.int32)
    pick = g.integers(0, 16, (B, S))
    stay = g.random((B, S)) < 0.9
    # vectorized sticky chain (this runs EVERY training step): each
    # position copies the value drawn at the most recent redraw position,
    # so ids[t] = draws[last_redraw<=t] via a running maximum of indices
    redraw = ~stay
    redraw[:, 0] = True
    redraw[nsp == 0, half] = True  # negatives restart at the boundary
    seg_vocab = np.where((np.arange(S)[None, :] < half) | (nsp[:, None]
                                                           == 1),
                         np.take_along_axis(topic_a, pick, 1),
                         np.take_along_axis(topic_b, pick, 1))
    last_redraw = np.maximum.accumulate(
        np.where(redraw, np.arange(S)[None, :], 0), axis=1)
    ids = np.take_along_axis(seg_vocab, last_redraw, 1).astype(np.int32)
    tok_type = (np.arange(S)[None] >= half).astype(np.int32) * np.ones(
        (B, 1), np.int32)
    attn = np.ones((B, S), np.int32)
    mlm = np.where(g.random((B, S)) < 0.15, ids, -1).astype(np.int32)
    masked_ids = np.where(mlm != -1, 4, ids)  # 4 = [MASK]
    return masked_ids, tok_type, attn, mlm, nsp


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--size", choices=["tiny", "base", "large"],
                    default="tiny")
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-4)
    args = ap.parse_args()

    if args.size == "base":
        model = models.bert_base(max_position=args.seq)
    elif args.size == "large":
        model = models.bert_large(max_position=args.seq)
    else:
        model = models.BertModel(models.BertConfig(
            vocab_size=8192, hidden_size=128, num_layers=2, num_heads=4,
            ffn_size=512, max_position=args.seq))

    mesh = (ht.make_mesh(dp=args.dp, tp=args.tp)
            if args.dp * args.tp > 1 else None)
    sched = lr.CosineScheduler(args.lr, t_max=args.steps, warmup=10)
    ex = ht.Executor(model.pretrain_loss_fn(),
                     optim.AdamWOptimizer(sched, weight_decay=0.01),
                     mesh=mesh, seed=0)
    state = ex.init_state(model.init(jax.random.PRNGKey(0)))
    if mesh is not None and args.tp > 1:
        strat = MegatronLM()
        sh = strat.shardings(state.params, mesh)
        state = TrainState(
            params=jax.tree_util.tree_map(jax.device_put, state.params, sh),
            opt_state={"step": state.opt_state["step"],
                       "slots": {k: jax.tree_util.tree_map(
                           jax.device_put, v, sh)
                           for k, v in state.opt_state["slots"].items()}},
            model_state=state.model_state, rng=state.rng, step=state.step)

    g = np.random.default_rng(0)
    logger = MetricLogger()
    t0 = time.perf_counter()
    for it in range(args.steps):
        batch = synthetic_batch(g, args.batch, args.seq,
                                model.c.vocab_size)
        state, m = ex.run("train", state, batch)
        logger.log(m)
        if (it + 1) % 20 == 0:
            means = logger.means(); logger.reset()
            tput = 20 * args.batch / (time.perf_counter() - t0)
            t0 = time.perf_counter()
            print(f"step {it+1}: loss={means['loss']:.4f} "
                  f"mlm={means['mlm_loss']:.4f} nsp={means['nsp_loss']:.4f} "
                  f"({tput:.0f} seq/s)")


if __name__ == "__main__":
    main()
