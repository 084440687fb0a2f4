"""Auto-parallel on a branching model: search the ResNet DAG per-node,
execute the plan through the Executor (reference analog: FlexFlowSearching
over the op graph, distributed_strategies/flexflow.py).

    python examples/auto_parallel_resnet.py --dp 4 --tp 2
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from hetu_tpu.utils.platform import bootstrap_example

bootstrap_example(8)  # virtual CPU devices for bare runs + compile cache

import jax
import jax.numpy as jnp
import numpy as np

import hetu_tpu as ht
from hetu_tpu import models, optim
from hetu_tpu.parallel.strategies import FlexFlowSearching, GraphPlanStrategy
from hetu_tpu.profiler import Simulator, resnet_graph_spec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dp", type=int, default=4)
    ap.add_argument("--tp", type=int, default=2)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--plan-out", default=None,
                    help="save the searched plan JSON here")
    args = ap.parse_args()

    # 1. cost DAG with the real branch structure (skip connections)
    gspec = resnet_graph_spec((1, 1, 1, 1), num_classes=10,
                              batch=args.batch,
                              tp_candidates=(1, args.tp))
    print(f"graph: {len(gspec.layers)} nodes, "
          f"{sum(1 for _ in gspec.edges())} edges")

    # 2. per-node MCMC search + greedy polish
    sim = Simulator()
    plan = FlexFlowSearching(sim, dp=args.dp, iters=800,
                             seed=0).search_graph(gspec)
    picked = {(o.kind, o.tp) for o in plan.layer_options}
    print(f"searched plan: t={plan.predicted_time:.2e}s options={picked}")
    if args.plan_out:
        plan.save(args.plan_out, gspec.layers)

    # 3. execute end-to-end
    mesh = ht.make_mesh(dp=args.dp, tp=args.tp)
    model = models.ResNet(models.BasicBlock, [1, 1, 1, 1], num_classes=10)
    ex = ht.Executor(model.loss_fn(), optim.MomentumOptimizer(0.05, 0.9),
                     mesh=mesh, dist_strategy=GraphPlanStrategy(plan, gspec))
    state = ex.init_state(model.init(jax.random.PRNGKey(0)))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((args.batch, 3, 32, 32)),
                    jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, args.batch), jnp.int32)
    for step in range(args.steps):
        state, m = ex.run("train", state, (x, y))
        print(f"step {step:2d}  loss {float(m['loss']):.4f}  "
              f"acc {float(m['acc']):.3f}")


if __name__ == "__main__":
    main()
