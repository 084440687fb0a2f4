"""GPT serving: KV-cache decode + continuous batching over the van.

The full serving path end to end — byte-level prompts go over the blob
channel to an InferenceServer whose engine decodes through the paged KV
cache, with concurrent clients exercising the continuous-batching
scheduler:

    python examples/gpt_serve.py --requests 8 --max-tokens 16
    python examples/gpt_serve.py --tp 4          # tp-sharded decode
"""

from __future__ import annotations

import argparse
import sys
import threading
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from hetu_tpu.utils.platform import bootstrap_example

bootstrap_example(8)  # virtual CPU devices for bare runs + compile cache

import jax

import hetu_tpu as ht
from hetu_tpu.models.gpt import GPTConfig, GPTModel
from hetu_tpu.serve import (
    ContinuousBatchingScheduler, InferenceClient, InferenceServer,
    PagedServeEngine,
)
from hetu_tpu.utils.logger import MetricLogger

PROMPTS = [
    "the tpu mesh hums",
    "heavy traffic incoming",
    "decode one token",
    "slots free up fast",
]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-tokens", type=int, default=16)
    ap.add_argument("--clients", type=int, default=3)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument("--layers", type=int, default=2)
    args = ap.parse_args()

    # byte-level tokens: any prompt string fits a 256-way vocab
    model = GPTModel(GPTConfig(
        vocab_size=256, hidden_size=args.hidden, num_layers=args.layers,
        num_heads=max(4, args.hidden // 32), ffn_size=4 * args.hidden,
        max_position=args.max_len, dropout_rate=0.0))
    variables = model.init(jax.random.PRNGKey(0))
    mesh = ht.make_mesh(tp=args.tp) if args.tp > 1 else None
    engine = PagedServeEngine(model, variables, num_slots=args.slots,
                              max_len=args.max_len, mesh=mesh)
    server = InferenceServer(ContinuousBatchingScheduler(engine),
                             max_clients=args.clients)
    print(f"serving on 127.0.0.1:{server.port} "
          f"(slots={args.slots}, chunk buckets={engine.chunk_buckets}, "
          f"tp={args.tp})")

    results = {}
    errors = []

    def client_worker(cid: int):
        client = InferenceClient("127.0.0.1", server.port, cid)
        try:
            for j in range(cid, args.requests, args.clients):
                prompt = list(PROMPTS[j % len(PROMPTS)].encode())
                resp = client.generate(prompt, max_tokens=args.max_tokens)
                results[j] = (PROMPTS[j % len(PROMPTS)], resp)
        except Exception as e:  # pragma: no cover - demo failure surface
            errors.append(repr(e))
        finally:
            client.close()

    threads = [threading.Thread(target=client_worker, args=(cid,))
               for cid in range(min(args.clients, args.requests))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(300)
    server.close()
    if errors:
        raise SystemExit(f"client errors: {errors}")

    for j in sorted(results):
        prompt, resp = results[j]
        text = bytes(t % 256 for t in resp["tokens"]).decode(
            "utf-8", errors="replace")
        print(f"  [{j}] {resp['status']:>4}  {prompt!r} -> {text!r}")

    snap = engine.metrics.report(MetricLogger())
    print(f"served {len(results)}/{args.requests} requests | "
          f"ttft_avg={snap.get('ttft_avg_s', 0):.3f}s "
          f"tokens/s={snap.get('tokens_per_sec', 0):.1f} "
          f"executables={engine.compiled_executables()}"
          f"<={engine.max_executables}")
    ok = (len(results) == args.requests and
          all(r["status"] == "ok" for _, r in results.values()) and
          engine.compiled_executables() <= engine.max_executables)
    print("serve: OK" if ok else "serve: FAILED")
    if not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
