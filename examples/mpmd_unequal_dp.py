"""MPMD pipeline with UNEQUAL per-stage data parallelism, multi-process.

Reference analog: the reference's round-robin pipeline machinery
(gpu_ops/pipeline_subexecutor.py:87-128 + context.py:164-188) lets stage 0
run at dp=2 while stage 1 runs at dp=1 — different programs on different
device groups.  SPMD (one jit, one mesh) cannot express that; this example
launches one PROCESS per (stage, replica) and routes activations/cotangents
through acked mailboxes on a PS van server (parallel/mpmd.py
MPMDStageRunner), with cross-replica gradient reduction on a PS
accumulator.

Run:  python examples/mpmd_unequal_dp.py [--steps 3]
(spawns 4 worker subprocesses: stage dp degrees 2, 1, 1; CPU-safe)
"""

from __future__ import annotations

import argparse
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from hetu_tpu.utils.platform import bootstrap_example

bootstrap_example(8)  # virtual CPU devices for bare runs + compile cache

import numpy as np

WORKER = """
import sys
sys.path.insert(0, {repo!r})
import os
os.environ["JAX_PLATFORMS"] = "cpu"
import jax; jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np
from hetu_tpu.parallel.mpmd import MPMDStageRunner

stage, replica, steps = {stage}, {replica}, {steps}
D, B, M = 16, 16, 4
DPS = {dps}
mb = B // M

def stage_fn(w, x):
    return jnp.tanh(x @ w)

w = jnp.asarray(
    np.random.default_rng(100 + stage).standard_normal((D, D)) * 0.4,
    jnp.float32)
runner = MPMDStageRunner(
    stage_fn, stage=stage, replica=replica, stage_dps=DPS,
    n_microbatches=M, in_shape=(mb, D), out_shape=(mb, D),
    host="127.0.0.1", port={port}, grad_size=D * D)

rng = np.random.default_rng(0)
x = rng.standard_normal((B, D)).astype(np.float32)
data = [x[i * mb:(i + 1) * mb] for i in range(M)] if stage == 0 else None
y = jnp.zeros((mb, D))

for step in range(steps):
    loss_fn = None
    if stage == len(DPS) - 1:
        def loss_fn(out):
            return jnp.mean((out - y) ** 2)
    loss, grads = runner.run_step(w, loss_fn=loss_fn, data=data)
    w = w - 0.2 * jnp.asarray(np.asarray(grads))
    if stage == len(DPS) - 1:
        print(f"step {{step}}: loss {{loss / M:.4f}}", flush=True)
runner.close()
print("DONE", flush=True)
"""


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=3)
    args = ap.parse_args(argv)

    from hetu_tpu.ps import van

    port = van.serve(0)
    dps = [2, 1, 1]
    procs = []
    try:
        for stage, dp in enumerate(dps):
            for rep in range(dp):
                src = WORKER.format(repo=str(REPO), stage=stage,
                                    replica=rep, steps=args.steps,
                                    port=port, dps=dps)
                procs.append(subprocess.Popen(
                    [sys.executable, "-c", src], stdout=subprocess.PIPE,
                    stderr=subprocess.PIPE, text=True))
        ok = True
        for p in procs:
            out, err = p.communicate(timeout=600)
            if p.returncode != 0 or "DONE" not in out:
                ok = False
                print(err[-1500:], file=sys.stderr)
            for line in out.splitlines():
                if line.startswith("step"):
                    print(line)
        print("MPMD 3-stage dp=(2,1,1) x", args.steps, "steps:",
              "OK" if ok else "FAILED")
        return 0 if ok else 1
    finally:
        for p in procs:
            p.kill()
            p.wait()
        van.stop()


if __name__ == "__main__":
    sys.exit(main())
