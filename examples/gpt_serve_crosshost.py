"""GPT serving across REAL process boundaries: 2 member processes, one
SIGKILLed under load, zero lost work.

The cross-process promotion of examples/gpt_serve_pool.py: each pool
member is its own OS process (listener-less InferenceServer attached to
the controller's van), membership crosses the wire as heartbeats with a
lease, and the kill is a real ``SIGKILL`` on a real pid — the
controller's lease expires, the member is declared lost, and every
outstanding request re-routes to the surviving process, which
re-prefills from the original prompt and (greedy decode) produces the
EXACT tokens the dead process would have.

    python examples/gpt_serve_crosshost.py --requests 8 --max-tokens 24

The controller (this process) never touches a JAX backend.  With
``JAX_PLATFORMS=cpu`` the members run on CPU; on a TPU host each member is
pinned to its own chip, so two members need two chips — on one chip the
pool refuses before spawning anything.
"""

from __future__ import annotations

import argparse
import sys
import tempfile
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from hetu_tpu.utils.platform import bootstrap_example

bootstrap_example(8)  # virtual CPU devices for bare runs + compile cache

PROMPTS = [
    "two processes, one van",
    "kill -9 the member",
    "the lease expires",
    "survivors re-prefill",
    "tokens come out exact",
    "preemption is routine",
]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-tokens", type=int, default=24)
    ap.add_argument("--workdir", default=None)
    args = ap.parse_args()

    from hetu_tpu.serve.crosshost import CrossProcessServingPool

    workdir = args.workdir or tempfile.mkdtemp(prefix="crosshost_")
    Path(workdir).mkdir(parents=True, exist_ok=True)
    model = {"vocab_size": 256, "hidden_size": 96, "num_layers": 2,
             "num_heads": 4, "ffn_size": 192, "max_position": 96,
             "num_slots": 4, "max_len": 80, "min_bucket": 8, "seed": 0}
    pool = CrossProcessServingPool(
        2, workdir=workdir, model=model, lease_s=0.4,
        suspect_grace_s=0.4, request_timeout_s=180.0)
    print(f"pool up: 2 member PROCESSES "
          f"(pids {[p.pid for p in pool.procs]}), "
          f"{'one TPU chip each' if pool.members_on_chips else 'on CPU'}, "
          f"van on 127.0.0.1:{pool.port}")

    results = {}
    errors = []

    def worker(j: int):
        prompt = list(PROMPTS[j % len(PROMPTS)].encode())
        try:
            results[j] = pool.generate(prompt,
                                       max_tokens=args.max_tokens,
                                       timeout_s=180.0)
        except Exception as e:  # pragma: no cover - demo failure surface
            errors.append(repr(e))

    threads = [threading.Thread(target=worker, args=(j,))
               for j in range(args.requests)]
    for t in threads:
        t.start()
    # kill the member holding the most in-flight work — a real SIGKILL
    # on a real pid, mid-decode
    deadline = time.monotonic() + 30.0
    victim = 0
    while time.monotonic() < deadline:
        victim = max(range(2), key=lambda s: pool._inflight.get(s, 0))
        if pool._inflight.get(victim, 0) > 0:
            break
        time.sleep(0.01)
    print(f"SIGKILL member {victim} (pid {pool.procs[victim].pid}) "
          f"under load")
    pool.procs[victim].kill()
    pool.procs[victim].wait()
    for t in threads:
        t.join(300)
    # detection is lease-driven: give the poll a beat to record the
    # failover even if every request already finished on the survivor
    deadline = time.monotonic() + 10.0
    while pool.metrics.count("pool_failovers") < 1 and \
            time.monotonic() < deadline:
        time.sleep(0.05)

    if errors:
        pool.close()
        raise SystemExit(f"client errors: {errors}")
    for j in sorted(results):
        resp = results[j]
        text = bytes(t % 256 for t in resp["tokens"]).decode(
            "utf-8", errors="replace")
        print(f"  [{j}] {resp['status']:>4}  "
              f"{PROMPTS[j % len(PROMPTS)]!r} -> {text!r}")

    failovers = pool.metrics.count("pool_failovers")
    moved = pool.metrics.count("requests_failed_over")
    pool.close()
    ok = (len(results) == args.requests and
          all(r["status"] == "ok" for r in results.values()) and
          failovers >= 1)
    print(f"served {len(results)}/{args.requests} | "
          f"failovers={failovers} requests_failed_over={moved}")
    print("crosshost serve: OK" if ok else "crosshost serve: FAILED")
    if not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
