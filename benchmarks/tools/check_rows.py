"""``check_seeds.py`` a row at a time, for a seed whose ``token_gap`` or
``logit_err`` has to be explained before a limit is touched: the weights
from the seed, a fresh engine, ``harness/check.py``'s four requests, and for
each checked row the reference's logits beside the dense forward's AND the
engine's own (caught by host callbacks round the model's two cache entry
points, as ``compare_long_sparse.py`` does; nothing else of the path is
changed).  On a TPU only, but for ``--rehearse``.

    python3 benchmarks/tools/check_rows.py <cell> [--layers REQUEST]
        [--rehearse] seed [seed ...]

A seed prints one JSON object: the check's two numbers (they are the
check's own to the last digit: the comparison is a function of the seed),
``requests``, by request the smallest and largest over its nine rows of a
row's largest error and of its root mean square over the vocabulary, for
the dense forward (``sys_*``) and the engine (``eng_*``), all over the
request's range of the reference; and ``gaps``, each row whose token trails
the reference's best: the token, its rank by the reference, what the dense
forward takes there, and the engine's own error at the two tokens.

``--layers R`` (one seed a process: the captured streams stay on the host;
it walks the pieces of MiniCPM-SALA's adapter, ``benchmarks/arch/
minicpm_sala.py``, and so serves that configuration's cells alone):
request R's stream after every layer, the dense forward's against the
reference's, as ``|difference| / |reference|`` a position: at the row with
the largest gap, the median and the largest position; by position after
some layers; and for a model with Lightning layers, position 0's products
``q_0 . k_0 / sqrt(d)`` a head, recomputed in float32 from each side's
stream: the first token's read-out is one term, which the head norm leaves
as ``sign(q_0 . k_0) v_0 / rms(v_0)``, so a product within rounding of zero
is a head whose whole output the two sides may take with opposite signs
(``PERF.md`` section 6, PR 56).
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

BY_POSITION = (0, 1, 3, 6, 8, 15)     # layers whose deviation is listed whole


def _span(a) -> list:
    return [float(min(a)), float(max(a))]


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("seeds", type=int, nargs="+")
    ap.add_argument("--layers", type=int, metavar="REQUEST")
    ap.add_argument("--rehearse", action="store_true",
                    help="any backend, the configuration's rehearse widths")
    args = ap.parse_args(argv)
    if args.layers is not None and len(args.seeds) > 1:
        ap.error("--layers takes one seed a process")

    import jax
    import numpy as np

    from benchmarks.harness import build, check, device, spec
    from hetu_tpu.serve import Request

    device.enable_compile_cache()
    if not args.rehearse:
        device.require_chips(1)
    man = spec.manifest()
    config = spec.config(man, spec.cell(man, args.cell)["config"],
                         rehearse=args.rehearse)
    arch = spec.adapter(config)
    model = arch.make_model(config, "serve")
    caught = {}          # (position, the id fed there) -> the engine's logits

    def note(logits, ids, at):
        for lg, i, n in zip(np.asarray(logits), np.asarray(ids),
                            np.asarray(at)):
            caught[int(n), int(i)] = lg.astype(np.float32)

    chunk, decode = model.prefill_chunk_with_cache, model.decode_with_cache

    def chunk_(variables, ids, k, v, start, *, last_index=None, **kw):
        out = chunk(variables, ids, k, v, start, last_index=last_index, **kw)
        jax.debug.callback(note, out[0], ids[:, last_index],
                           (start + last_index)[None])
        return out

    def decode_(variables, ids, k, v, lengths, **kw):
        out = decode(variables, ids, k, v, lengths, **kw)
        # a padded row of the round shares a position with a real one, not
        # its id as well
        jax.debug.callback(note, out[0], ids, lengths)
        return out

    model.prefill_chunk_with_cache = chunk_
    model.decode_with_cache = decode_
    low, high = arch.id_range(config)
    decoded = check.SERVE_DECODED
    for seed in args.seeds:
        caught.clear()
        variables = build.init_variables(model, seed)
        engine, scheduler = build.make_serving(model, variables, config)
        max_prompt = int(config["serve"]["max_len"]) - decoded - 2
        lens = [min(n, max_prompt) for n in check.SERVE_PROMPT_LENS]
        rng = np.random.default_rng([int(seed), 7])
        prompts = [rng.integers(low, high, n).astype(np.int32).tolist()
                   for n in lens]
        reqs = [Request(prompt=p, max_tokens=decoded + 1) for p in prompts]
        scheduler.run(reqs)
        jax.effects_barrier()
        tokens = [list(r.tokens) for r in reqs]
        del engine, scheduler, reqs
        width = -(-(max(lens) + decoded + 1) // 128) * 128
        ids = np.zeros((len(lens), min(width, arch.positions(config))),
                       np.int32)
        for i, (p, t) in enumerate(zip(prompts, tokens)):
            ids[i, :len(p) + len(t)] = p + t
        params = variables["params"]
        ref = arch.reference_logits(params, ids, config)
        sysl = arch.system_logits(model, params, ids)
        requests, gaps = [], []
        for i, n in enumerate(lens):
            rows = range(n - 1, n + decoded)
            span = check._range(ref[i, n - 1:n + decoded])
            eng = [caught.get((t, int(ids[i, t]))) for t in rows]
            err = {"sys": [sysl[i, t] - ref[i, t] for t in rows],
                   "eng": [e - ref[i, t] for e, t in zip(eng, rows)
                           if e is not None]}
            one = {"prompt": n, "engine_rows_caught": len(err["eng"])}
            for side, es in err.items():
                if es:
                    one[side + "_max"] = _span(
                        [np.abs(e).max() / span for e in es])
                    one[side + "_rms"] = _span(
                        [np.sqrt(np.mean(e * e)) / span for e in es])
            one["token_gap"] = 0.0
            for j, (t, tok) in enumerate(zip(rows, tokens[i])):
                row = ref[i, t]
                gap = float(row.max() - row[tok]) / span
                one["token_gap"] = max(one["token_gap"], gap)
                if gap > 0:
                    best = int(row.argmax())
                    g = {"request": i, "row": j, "gap": gap, "token": tok,
                         "rank_by_reference": int((row > row[tok]).sum()),
                         "reference_best": best,
                         "dense_forward_takes": int(sysl[i, t].argmax())}
                    if eng[j] is not None:
                        g["engine_err_at_token"] = float(
                            eng[j][tok] - row[tok]) / span
                        g["engine_err_at_best"] = float(
                            eng[j][best] - row[best]) / span
                    gaps.append(g)
            requests.append(one)
        print(json.dumps({
            "cell": args.cell, "seed": seed,
            "logit_err": max(r["sys_max"][1] for r in requests),
            "token_gap": max(r["token_gap"] for r in requests),
            "requests": requests, "gaps": gaps}), flush=True)
        if args.layers is not None:
            i = args.layers
            worst = max((g for g in gaps if g["request"] == i),
                        key=lambda g: g["gap"], default={"row": 0})
            _layers(model, arch, config, params, ids[i],
                    lens[i] + decoded, lens[i] - 1 + worst["row"])
        del variables, params
    return 0


def _layers(model, arch, config, params, ids, real: int, row: int):
    """The stream after every layer, the dense forward's beside the
    reference's (the pieces ``reference_logits`` is made of), over the
    sequence's ``real`` positions; ``row`` the position reported alone."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    streams, layer = {}, model._layer

    def layer_(p, l, h, call):
        out = layer(p, l, h, call)
        jax.debug.callback(
            lambda x, l=l: streams.__setitem__(l, np.asarray(x, np.float32)),
            out[0])
        return out

    model._layer = layer_
    try:
        np.asarray(jax.jit(lambda p, x: model.apply(
            {"params": p, "state": {}}, x)[0])(params, jnp.asarray(ids[None])))
        jax.effects_barrier()
    finally:
        model._layer = layer
    fn, d = arch._jitted(config), arch.dims(config)
    ref, held = arch.reference(config), params["layers"]
    eps, hd = d["eps"], d["head_dim"]

    def normed(x, w):
        return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * w

    def first_products(h0, l, j):
        """q_0 . k_0 / sqrt(d) [heads] of Lightning layer ``l`` from the
        stream ``h0`` [H] before it, float32 (no rotation at position 0)."""
        leaf = lambda name: np.asarray(held["lin"][name][j], np.float32)
        a0 = normed(h0, np.asarray(held["attn_norm"][l], np.float32))
        q0 = normed((a0 @ leaf("q")).reshape(-1, hd), leaf("q_norm"))
        k0 = normed((a0 @ leaf("k")).reshape(-1, hd), leaf("k_norm"))
        return np.sum(q0 * k0, -1) / np.sqrt(hd)

    h = fn["embed"](params["tok_emb"], np.asarray(ids[None]))
    for l, kind in enumerate(d["mixer_types"]):
        a = fn["norm"](h, held["attn_norm"][l])
        j = ref.leaf_index(d, l)
        out = {"layer": l, "kind": kind}
        if ref.is_sparse(d, l):
            op = arch._sparse_layer(fn, ref, ref.at(held["attn"], j), a, d,
                                    None, None)
        else:
            before = np.asarray(h)[0, 0]
            z_ref = first_products(before, l, j)
            z_sys = first_products(streams[l - 1][0, 0] if l else before,
                                   l, j)
            out["first_products_nearest_zero"] = sorted(
                (round(float(z), 4) for z in z_ref), key=abs)[:3]
            out["first_products_of_opposite_sign"] = int(
                np.sum(np.sign(z_ref) != np.sign(z_sys)))
            out["first_products_moved_by_up_to"] = float(
                np.abs(z_ref - z_sys).max())
            op = arch._lightning_layer(fn, ref.at(held["lin"], j), a, l,
                                       d["lightning_heads"])
        h = fn["add"](h, op)
        u = fn["norm"](h, held["ffn_norm"][l])
        h = fn["add"](h, fn["ffn"](ref.at(held["ffn"], l), u, 0, u.shape[1]))
        want, got = np.asarray(h)[0, :real], streams[l][0, :real]
        dev = np.linalg.norm(got - want, axis=-1) \
            / np.linalg.norm(want, axis=-1)
        out.update(at_row=float(dev[row]), median=float(np.median(dev)),
                   largest=float(dev.max()), largest_at=int(dev.argmax()))
        if l in BY_POSITION:
            out["by_position"] = [round(float(x), 4) for x in dev[:48]]
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
