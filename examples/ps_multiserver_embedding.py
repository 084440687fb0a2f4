"""Multi-server parameter-server training: an embedding table key-range
partitioned over N van server processes, trained by this worker process
(reference analog: ps-lite multi-server deployment, 'trillions of
parameters across 100 nodes' — README.md:19).

    python examples/ps_multiserver_embedding.py --servers 3 --steps 50
"""

from __future__ import annotations

import argparse
import socket
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from hetu_tpu.utils.platform import bootstrap_example

bootstrap_example(8)  # virtual CPU devices for bare runs + compile cache

import numpy as np


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--servers", type=int, default=2)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--rows", type=int, default=10_000)
    ap.add_argument("--dim", type=int, default=16)
    args = ap.parse_args()

    # 1. launch server processes (bin/heturun does this from a cluster
    # yaml in a real deployment)
    ports = [free_port() for _ in range(args.servers)]
    procs = []
    for p in ports:
        code = (f"import sys,time; sys.path.insert(0,{str(REPO)!r}); "
                f"from hetu_tpu.ps import van; van.serve({p}); "
                "print('ready',flush=True); time.sleep(600)")
        pr = subprocess.Popen([sys.executable, "-c", code],
                              stdout=subprocess.PIPE, text=True)
        pr.stdout.readline()
        procs.append(pr)
    print(f"{args.servers} PS servers up on ports {ports}")

    try:
        from hetu_tpu.ps import van

        # 2. one logical table over all servers; keys auto-partitioned
        table = van.PartitionedPSTable(
            [("127.0.0.1", p) for p in ports], args.rows, args.dim,
            init="normal", init_b=0.05, optimizer="adagrad", lr=0.1,
            heartbeat_ms=500)
        print("shard starts:", table.shard_starts, "alive:", table.alive)

        # 3. embedding-style training: pull rows, compute a toy loss grad,
        # push — the server-side adagrad applies it
        rng = np.random.default_rng(0)
        for step in range(args.steps):
            ids = rng.integers(0, args.rows, 256)
            rows = table.sparse_pull(ids)
            grad = rows  # pull toward zero: d/dw ||w||^2/2 = w
            table.sparse_push(ids, grad)
            if step % 10 == 0 or step == args.steps - 1:
                norm = float(np.linalg.norm(
                    table.sparse_pull(ids[:64])) / 8)
                print(f"step {step:3d}  sampled row norm {norm:.4f}")
        table.close()
    finally:
        for pr in procs:
            pr.kill()
            pr.wait()
    print("done")


if __name__ == "__main__":
    main()
