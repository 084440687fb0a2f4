"""The control a configuration's serving tolerances have to FAIL: the
nearest precision below the one the configuration states, put in the
PROGRAM's place, through ``harness/check.py``'s own serving comparison.

``check_seeds.py --control`` rounds the weights alone and compares the dense
forward alone.  Here the program itself computes in the lower precision:
every bfloat16 value the model's three entry points (``apply``,
``prefill_chunk_with_cache``, ``decode_with_cache``) make is rounded to the
three mantissa bits of an 8-bit float (e4m3; the exponent is left alone, the
kindest reading of "8-bit"), the operands of every matmul included, so the
weights too.  The engine, its page tables and its two programs are built
over that model, and ``check.serving`` gives ``logit_err`` AND ``token_gap``
against the same float32 reference over the true weights (the weights are
rounded inside the programs, where they are read, so one copy is held).

``--round all`` (the default) rounds every bfloat16 value a primitive
computes, the residual stream and the cached rows with it: the stated
arithmetic with 8-bit floats for bfloat16.  ``--round matmuls`` rounds the
operands of the matmuls only (8-bit matmuls between bfloat16 activations).
What is float32 in the program (the router, softmax sums, norms' insides)
stays float32 in both.  ``--stated`` first gives the program as stated on
the same seed.  On a TPU at the cell's size; any backend at ``--rehearse``.

    python3 benchmarks/tools/check_control.py <cell> [--round all|matmuls]
        [--stated] [--rehearse] seed [seed ...]

One JSON object a seed, then one with the smallest and largest of each.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

# primitives that move values and make none: their results need no rounding
# (a pool of gigabytes passes through them)
MOVES = frozenset((
    "slice", "dynamic_slice", "dynamic_update_slice", "gather", "scatter",
    "reshape", "transpose", "squeeze", "broadcast_in_dim", "concatenate",
    "select_n", "copy", "rev", "pad", "iota", "split"))
# (scatter-add adds, and is rounded)


def three_mantissa_bits(a):
    """bfloat16 rounded to the three mantissa bits of an 8-bit float
    (e4m3), to nearest, by its bits (the TPU compiler folds a convert to
    float8 and back away: ``check_seeds.py``)."""
    import jax
    import jax.numpy as jnp

    bits = jax.lax.bitcast_convert_type(a, jnp.uint16)
    return jax.lax.bitcast_convert_type(
        (bits + jnp.uint16(8)) & jnp.uint16(0xFFF0), jnp.bfloat16)


def _low(a):
    import jax.numpy as jnp

    if getattr(a, "dtype", None) == jnp.bfloat16:
        return three_mantissa_bits(a)
    return a


def evaluate(jaxpr, consts, args, everything: bool):
    """``jaxpr`` over ``args`` with bfloat16 rounded to three mantissa bits:
    the operands of every ``dot_general``, and with ``everything`` the
    results of every primitive that computes.  Loops, branches and calls
    are walked into."""
    import jax
    from jax.extend import core as jex

    def sub(closed):
        if hasattr(closed, "jaxpr"):
            return lambda *a: evaluate(closed.jaxpr, closed.consts, a,
                                       everything)
        return lambda *a: evaluate(closed, (), a, everything)

    env = {}

    def read(v):
        return v.val if isinstance(v, jex.Literal) else env[v]

    for v, c in zip(jaxpr.constvars, consts):
        env[v] = c
    for v, a in zip(jaxpr.invars, args):
        env[v] = a
    for eqn in jaxpr.eqns:
        vals = [read(v) for v in eqn.invars]
        name, p = eqn.primitive.name, eqn.params
        if name == "scan":
            nc, nk = p["num_consts"], p["num_carry"]
            body = sub(p["jaxpr"])
            held = vals[:nc]

            def step(carry, x, body=body, held=held, nk=nk):
                out = body(*held, *carry, *x)
                return tuple(out[:nk]), tuple(out[nk:])

            carry, ys = jax.lax.scan(
                step, tuple(vals[nc:nc + nk]), tuple(vals[nc + nk:]),
                length=p["length"], reverse=p["reverse"],
                unroll=p["unroll"])
            outs = list(carry) + list(ys)
        elif name == "while":
            cn, bn = p["cond_nconsts"], p["body_nconsts"]
            cond, body = sub(p["cond_jaxpr"]), sub(p["body_jaxpr"])
            cc, bc = vals[:cn], vals[cn:cn + bn]
            outs = list(jax.lax.while_loop(
                lambda c: cond(*cc, *c)[0],
                lambda c: tuple(body(*bc, *c)), tuple(vals[cn + bn:])))
        elif name == "cond":
            outs = list(jax.lax.switch(
                vals[0], [lambda *a, f=sub(b): tuple(f(*a))
                          for b in p["branches"]], *vals[1:]))
        elif name != "pallas_call" and ("jaxpr" in p or "call_jaxpr" in p):
            # jit, remat, custom_jvp and custom_vjp calls: walked into (a
            # Pallas kernel is bound as it is: none on this path)
            outs = list(sub(p.get("jaxpr", p.get("call_jaxpr")))(*vals))
        else:
            if name == "dot_general":
                # rounded operands are made before the matmul reads them
                # and its result before it is rounded: with the bit
                # operations fused into a convolution the TPU compiler's
                # cost model recursed until its stack ran out (PR 32)
                vals = list(jax.lax.optimization_barrier(
                    tuple(_low(a) for a in vals)))
            outs = eqn.primitive.bind(*vals, **p)
            if not eqn.primitive.multiple_results:
                outs = [outs]
            if name == "dot_general":
                outs = list(jax.lax.optimization_barrier(tuple(outs)))
            if everything and name not in MOVES:
                outs = [_low(a) for a in outs]
        for v, a in zip(eqn.outvars, outs):
            env[v] = a
    return [read(v) for v in jaxpr.outvars]


def lowered(f, everything: bool):
    """``f`` computing in the lower precision (see :func:`evaluate`)."""
    import jax

    def g(*args, **kw):
        closed, shape = jax.make_jaxpr(lambda: f(*args, **kw),
                                       return_shape=True)()
        out = evaluate(closed.jaxpr, closed.consts, (), everything)
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(shape), out)

    return g


ENTRIES = ("apply", "prefill_chunk_with_cache", "decode_with_cache")


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("seeds", type=int, nargs="+")
    ap.add_argument("--round", choices=("all", "matmuls"), default="all")
    ap.add_argument("--stated", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from benchmarks.harness import build, check, device, spec

    device.enable_compile_cache()
    if not args.rehearse:
        device.require_chips(1)
    man = spec.manifest()
    config = spec.config(man, spec.cell(man, args.cell)["config"],
                         rehearse=args.rehearse)
    if args.rehearse:           # the rehearsal section computes in float32
        config = {**config, "compute_dtype": "bfloat16",
                  "param_dtype": "bfloat16"}
    arch = spec.adapter(config)
    ends = {}

    def reading(model, variables, seed):
        engine, scheduler = build.make_serving(model, variables, config)
        verdict = check.serving(model, variables, engine, scheduler, config,
                                seed)
        return {k: verdict.get(k) for k in ("ok", "logit_err", "token_gap",
                                            "why")}

    for seed in args.seeds:
        t0 = time.monotonic()
        out = {"seed": seed, "round": args.round}
        model = arch.make_model(config, "serve")
        variables = build.init_variables(model, seed)
        if args.stated:
            out["stated"] = reading(model, variables, seed)
        for name in ENTRIES:
            setattr(model, name, lowered(getattr(model, name),
                                         args.round == "all"))
        out["control"] = reading(model, variables, seed)
        del model, variables
        out["seconds"] = time.monotonic() - t0
        print(json.dumps(out), flush=True)
        for side in ("stated", "control"):
            for k in ("logit_err", "token_gap"):
                v = out.get(side, {}).get(k)
                if v is not None:
                    lo, hi = ends.get((side, k), (v, v))
                    ends[side, k] = (min(lo, v), max(hi, v))
    tol = arch.tolerances(config)
    print(json.dumps({
        "smallest_largest": {f"{s}.{k}": v for (s, k), v in ends.items()},
        "seeds": len(args.seeds), "round": args.round,
        "limits": {k: tol[k]["limit"] for k in ("logit_err", "token_gap")}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
