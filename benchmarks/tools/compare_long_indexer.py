"""``compare_long_sparse.py`` for a configuration whose sparse layers choose
ROWS by a lightning indexer over a latent cache, beside Kimi Delta Attention
state layers: the serving comparison at LENGTH, by the builder.  For each
prompt length: one prompt prefilled in chunks and ``--decoded`` tokens
decoded through the scheduler, the paged engine, its page tables, its pooled
keys and its three-part state; the logits the engine's own programs computed
at the last prompt position and every decoded one against the
configuration's reference over prompt + answer (in blocks, float32 at the
highest matmul precision); and the groups each of those tokens CHOSE on each
DSA layer against the reference's choice (``harness/check.py``'s prompts are
24-333 tokens, under ``index_topk``, where every row is read).  Logits and
choices are caught by host callbacks round the model's two cache entry
points and round ``ops.select_groups``; nothing else of the path is changed.
On a TPU only.

    python3 benchmarks/tools/compare_long_indexer.py <cell> --seed N
        [--prompts 6000,12288,40000] [--decoded 8]
        [--control first-512|bf16-state] [--rehearse]

``--control``: the same comparison with the program WRONG in one way, which
the tolerances have to fail: ``first-512``, a query reads its FIRST
``index_topk / index_kpool`` groups instead of its best; ``bf16-state``, the
KDA layers' matrix held in bfloat16.

Prints one JSON object a prompt: ``logit_err`` (largest error over the
reference's range at those rows), ``token_gap``, the limits the adapter
states (``tolerances_at_length``), ``ok`` (both inside: a control has to
read false), and of the choices ``choice_same`` (the
share of (token, layer) whose chosen sets are the reference's, over the
tokens that had more complete groups than they read) and ``choice_overlap``
(the mean share of a token's chosen groups that the reference chose too).
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
    ap.add_argument("--prompts", default="6000,12288,40000")
    ap.add_argument("--decoded", type=int, default=8)
    ap.add_argument("--control", choices=("first-512", "bf16-state"))
    ap.add_argument("--rehearse", action="store_true",
                    help="any backend, the configuration's rehearse widths")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import build, device, spec
    from hetu_tpu import ops
    from hetu_tpu.serve import Request

    device.enable_compile_cache()
    stamp = None if args.rehearse else device.require_chips(1)
    man = spec.manifest()
    config = spec.config(man, spec.cell(man, args.cell)["config"],
                         rehearse=args.rehearse)
    if args.control == "bf16-state":
        config = {**config, "assumed": {
            **config["assumed"], "kda_state_dtype": "bfloat16"}}
    arch = spec.adapter(config)
    model = arch.make_model(config, "serve")
    pool, topk = model.c.index_kpool, model.c.index_groups
    rows, chose = {}, {}
    keep_from = [0]

    def note(logits, lengths):
        for lg, n in zip(np.asarray(logits), np.asarray(lengths)):
            rows[int(n)] = lg.astype(np.float32)

    def note_choice(layer, idx, n, pos):
        for i, m, t in zip(np.asarray(idx)[0], np.asarray(n)[0],
                           np.asarray(pos)[0]):
            if t >= keep_from[0]:
                chose[layer, int(t)] = i[:int(m)]

    chunk, decode = model.prefill_chunk_with_cache, model.decode_with_cache

    def chunk_(variables, ids, k, v, start, *, last_index=None, **kw):
        out = chunk(variables, ids, k, v, start, last_index=last_index, **kw)
        jax.debug.callback(note, out[0], (start + last_index)[None])
        return out

    def decode_(variables, ids, k, v, lengths, **kw):
        out = decode(variables, ids, k, v, lengths, **kw)
        jax.debug.callback(note, out[0], lengths)
        return out

    select, calls = ops.select_groups, [0]
    n_dsa = len(model.attn_leaf)

    def select_(qi, w, kbar, pos, **how):
        # the DSA layers call in order, in every program traced
        layer = calls[0] % n_dsa
        calls[0] += 1
        idx, n = select(qi, w, kbar, pos, **how)
        if args.control == "first-512":
            idx = jnp.broadcast_to(jnp.arange(idx.shape[-1]), idx.shape)
        if qi.shape[0] == 1:         # the engine's one request, no padding
            jax.debug.callback(
                lambda i, m, t: note_choice(layer, i, m, t), idx, n, pos)
        return idx, n

    model.prefill_chunk_with_cache = chunk_
    model.decode_with_cache = decode_
    ops.select_groups = select_
    variables = build.init_variables(model, args.seed)
    low, high = arch.id_range(config)
    far = arch.tolerances_at_length(config)
    for n_prompt in (int(x) for x in args.prompts.split(",")):
        rows.clear()
        chose.clear()
        keep_from[0] = n_prompt - 1
        engine, scheduler = build.make_serving(model, variables, config)
        prompt = np.random.default_rng([args.seed, 11, n_prompt]).integers(
            low, high, n_prompt).astype(np.int32).tolist()
        req = Request(prompt=prompt, max_tokens=args.decoded + 1)
        t0 = time.monotonic()
        scheduler.run([req])
        jax.effects_barrier()
        served_s = time.monotonic() - t0
        assert req.status == "ok" and len(req.tokens) == args.decoded + 1
        tokens = list(req.tokens)
        counters = {k: int(v) for k, v in engine.metrics.snapshot().items()
                    if k in model.step_stats}
        # the pools make room for the reference: a request keeps its
        # scheduler, which keeps the engine
        del engine, scheduler, req

        ids = np.asarray([prompt + tokens], np.int32)
        n = len(prompt)
        t0 = time.monotonic()
        ref_choice = {}
        want = arch.reference_logits(
            variables["params"], ids, config,
            rows=slice(n - 1, n + args.decoded), choices=ref_choice)[0]
        got = np.stack([rows[n - 1 + j] for j in range(args.decoded + 1)])
        span = float(want.max() - want.min())
        gaps = [float(want[j].max() - want[j][tok]) / span
                for j, tok in enumerate(tokens)]
        # the choices: the reference's mask at the groups the system chose
        same = total = 0
        overlap = []
        for layer, blocks in ref_choice.items():
            for lo, mask in blocks:              # [1, Q, S] over positions
                for i in range(mask.shape[1]):
                    t = lo + i
                    mine = chose.get((layer, t))
                    complete = (t + 1) // pool
                    # a padded chunk row past the prompt is no token
                    if mine is None or complete <= topk \
                            or t >= n + args.decoded:
                        continue
                    theirs = mask[0, i, :complete * pool:pool]
                    hit = int(theirs[mine].sum())
                    total += 1
                    same += hit == len(mine) == int(theirs.sum())
                    overlap.append(hit / max(len(mine), 1))
        logit_err = float(np.max(np.abs(got - want))) / span
        limits = {k: far[k]["limit"] for k in ("logit_err", "token_gap")}
        print(json.dumps({
            "cell": args.cell, "seed": args.seed, "device": stamp,
            "control": args.control, "prompt": n,
            "decoded": args.decoded + 1,
            "chunks": -(-n // int(config["serve"]["prefill_chunk"])),
            "ok": logit_err <= limits["logit_err"]
            and max(gaps) <= limits["token_gap"],
            "logit_err": logit_err,
            "logit_err_by_row": [float(np.max(np.abs(g - w))) / span
                                 for g, w in zip(got, want)],
            "token_gap": max(gaps), "reference_range": span,
            "choices_compared": total,
            "choice_same": same / total if total else None,
            "choice_overlap": float(np.mean(overlap)) if overlap else None,
            "counters": counters,
            "limits": limits,
            "served_s": served_s, "reference_s": time.monotonic() - t0}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
