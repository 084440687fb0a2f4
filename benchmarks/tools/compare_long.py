"""The serving comparison at LENGTH, once, by the builder: one prompt of
``--prompt`` tokens prefilled in chunks and ``--decoded`` tokens decoded
through the scheduler, the paged engine and its page tables, and the logits
the engine's own programs computed at every decoded position against the
configuration's reference over prompt + answer (``harness/check.py``'s
prompts are 24-333 tokens).  The logits are caught on their way to the
engine's argmax by a host callback round the model's two cache entry
points; nothing else of the path is changed.  On a TPU only.

    python3 benchmarks/tools/compare_long.py <cell> --seed N [--prompt 8000] [--decoded 8]

Prints one JSON object: ``logit_err`` (largest error over the reference's
range at those rows), ``token_gap``, the limits the adapter states.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prompt", type=int, default=8000)
    ap.add_argument("--decoded", type=int, default=8)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from benchmarks.harness import build, device, spec
    from hetu_tpu.serve import Request

    device.enable_compile_cache()
    stamp = device.require_chips(1)
    man = spec.manifest()
    config = spec.config(man, spec.cell(man, args.cell)["config"])
    arch = spec.adapter(config)
    model = arch.make_model(config, "serve")
    rows = {}

    def note(logits, lengths):
        for lg, n in zip(np.asarray(logits), np.asarray(lengths)):
            rows[int(n)] = lg.astype(np.float32)

    chunk, decode = model.prefill_chunk_with_cache, model.decode_with_cache

    def chunk_(variables, ids, k, v, start, *, last_index=None):
        out = chunk(variables, ids, k, v, start, last_index=last_index)
        jax.debug.callback(note, out[0], (start + last_index)[None])
        return out

    def decode_(variables, ids, k, v, lengths):
        out = decode(variables, ids, k, v, lengths)
        jax.debug.callback(note, out[0], lengths)
        return out

    model.prefill_chunk_with_cache = chunk_
    model.decode_with_cache = decode_
    variables = build.init_variables(model, args.seed)
    engine, scheduler = build.make_serving(model, variables, config)
    low, high = arch.id_range(config)
    prompt = np.random.default_rng([args.seed, 11]).integers(
        low, high, args.prompt).astype(np.int32).tolist()
    req = Request(prompt=prompt, max_tokens=args.decoded + 1)
    t0 = time.monotonic()
    scheduler.run([req])
    jax.effects_barrier()
    served_s = time.monotonic() - t0
    assert req.status == "ok" and len(req.tokens) == args.decoded + 1
    del engine, scheduler        # the pools make room for the reference

    ids = np.asarray([prompt + list(req.tokens)], np.int32)
    t0 = time.monotonic()
    ref = arch.reference_logits(variables["params"], ids, config)[0]
    n = len(prompt)
    want = ref[n - 1:n + args.decoded]          # predict tokens[0..decoded]
    got = np.stack([rows[n - 1 + j] for j in range(args.decoded + 1)])
    span = float(want.max() - want.min())
    gaps = [float(want[j].max() - want[j][tok]) / span
            for j, tok in enumerate(req.tokens)]
    tol = arch.tolerances(config)
    print(json.dumps({
        "cell": args.cell, "seed": args.seed, "device": stamp,
        "prompt": n, "decoded": args.decoded + 1,
        "chunks": -(-n // int(config["serve"]["prefill_chunk"])),
        "logit_err": float(np.max(np.abs(got - want))) / span,
        "logit_err_by_row": [float(np.max(np.abs(g - w))) / span
                             for g, w in zip(got, want)],
        "token_gap": max(gaps), "reference_range": span,
        "limits": {k: tol[k]["limit"] for k in ("logit_err", "token_gap")},
        "served_s": served_s, "reference_s": time.monotonic() - t0}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
