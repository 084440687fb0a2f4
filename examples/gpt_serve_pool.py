"""GPT serving-pool HA: 2 engines, one killed under load, zero lost work.

A :class:`~hetu_tpu.serve.pool.ServingPool` routes byte-level prompts to
the least-loaded healthy member.  Mid-run one member's engine is KILLED
(the ``serve_engine_kill`` chaos fault: abrupt, state-losing) — the
pool's health poll fails its queue over to the survivor, which
re-prefills from prompt + tokens-so-far; every request still completes
'ok' with the exact greedy continuation.  A planned preemption would
instead live-migrate the KV slots (``pool.drain_member``;
``pytest tests/ -m migrate`` asserts its token parity).

    python examples/gpt_serve_pool.py --requests 8 --max-tokens 12
"""

from __future__ import annotations

import argparse
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from hetu_tpu.utils.platform import bootstrap_example

bootstrap_example(8)  # virtual CPU devices for bare runs + compile cache

import jax

from hetu_tpu.models.gpt import GPTConfig, GPTModel
from hetu_tpu.serve import PagedServeEngine, ServingPool

PROMPTS = [
    "the tpu mesh hums",
    "heavy traffic incoming",
    "decode one token",
    "slots free up fast",
    "preemption is routine",
    "migrate the cache",
]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-tokens", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument("--layers", type=int, default=2)
    args = ap.parse_args()

    model = GPTModel(GPTConfig(
        vocab_size=256, hidden_size=args.hidden, num_layers=args.layers,
        num_heads=max(4, args.hidden // 32), ffn_size=4 * args.hidden,
        max_position=args.max_len, dropout_rate=0.0))
    variables = model.init(jax.random.PRNGKey(0))

    def factory():
        return PagedServeEngine(model, variables, num_slots=args.slots,
                                max_len=args.max_len)

    pool = ServingPool({"alpha": factory, "beta": factory},
                       health_poll_s=0.05, max_loop_errors=2)
    print(f"pool up: 2 members, van on 127.0.0.1:{pool.port}")

    results = {}
    errors = []

    def worker(j: int):
        prompt = list(PROMPTS[j % len(PROMPTS)].encode())
        try:
            results[j] = pool.generate(prompt, max_tokens=args.max_tokens,
                                       timeout_s=120.0)
        except Exception as e:  # pragma: no cover - demo failure surface
            errors.append(repr(e))

    threads = [threading.Thread(target=worker, args=(j,))
               for j in range(args.requests)]
    for t in threads:
        t.start()
    # a killed engine is only NOTICED under load (the engine loop must
    # strike out on real work), so wait until a member actually holds
    # requests and kill THAT one — killing an idle member would leave an
    # undetectable corpse and nothing to fail over
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        victim = max(pool.members.values(), key=lambda m: m.scheduler.load)
        if victim.scheduler.load > 0:
            break
        time.sleep(0.01)
    print(f"killing member {victim.name!r} under load "
          "(unplanned, state-losing)")
    pool.kill_member(victim.name)
    for t in threads:
        t.join(300)
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
    print("serve pool: OK" if ok else "serve pool: FAILED")
    if not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
