"""The control a TRAINED configuration's tolerances have to fail:
``check_control.py``'s lower precision put in the place of the program's
LOSS FUNCTION, through ``harness/check.py``'s own training comparison (loss
and gradient norm of the first step's first sequences against the float32
reference).

Every bfloat16 value the loss function makes is rounded to the three
mantissa bits of an 8-bit float (``check_control.three_mantissa_bits``), the
operands of every matmul and of every kernel call included, so the weights
too, where they are read; the COTANGENT of each such value is rounded the
same way on the way back (a ``custom_vjp`` identity: the bit operations
alone have no derivative), so the backward pass computes in the lower
precision as the forward does.  ``--round matmuls`` rounds the operands of
the matmuls and kernel calls only.  What is float32 in the program (the
router, the softmax statistics, the norms' insides, the master weights and
the gradient leaves) stays float32.  Calls that carry their own backward
(``custom_vjp``: the flash kernels, the held-expert walk) are bound as they
are, operands and results rounded; remat stays remat (without it an
8192-token step does not fit); loops are walked into.

    python3 benchmarks/tools/check_control_train.py <cell> [--round all|matmuls]
        [--stated] [--rehearse] seed [seed ...]

``evaluate`` is ``check_control.py``'s walk with those three differences
(that file is an accepted benchmark file, which a PR that adds a cell may
not edit; a ``benchmark`` PR can fold the two).

One JSON object a seed (the reference is computed once a seed and serves
both readings), then one with the smallest and largest of each number.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks.tools.check_control import MOVES, three_mantissa_bits  # noqa: E402


def _make_low():
    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def low(a):
        return three_mantissa_bits(a)

    low.defvjp(lambda a: (three_mantissa_bits(a), None),
               lambda _, g: (three_mantissa_bits(g),))

    def maybe(a):
        return low(a) if getattr(a, "dtype", None) == jnp.bfloat16 else a

    return maybe


def evaluate(jaxpr, consts, args, everything: bool, low):
    """``jaxpr`` over ``args`` in the lower precision (module docstring)."""
    import jax
    from jax.extend import core as jex

    def sub(closed):
        if hasattr(closed, "jaxpr"):
            return lambda *a: evaluate(closed.jaxpr, closed.consts, a,
                                       everything, low)
        return lambda *a: evaluate(closed, (), a, everything, low)

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
        elif "prevent_cse" in p:          # remat stays remat
            outs = list(jax.checkpoint(
                lambda *a, f=sub(p["jaxpr"]): tuple(f(*a)),
                prevent_cse=p["prevent_cse"], policy=p["policy"])(*vals))
        elif not name.startswith("custom_vjp_call") \
                and name != "pallas_call" \
                and ("jaxpr" in p or "call_jaxpr" in p):
            # jit and custom_jvp calls: walked into
            outs = list(sub(p.get("jaxpr", p.get("call_jaxpr")))(*vals))
        else:
            kernel = name.startswith("custom_vjp_call")
            if name == "dot_general" or kernel:
                vals = list(jax.lax.optimization_barrier(
                    tuple(low(a) for a in vals)))
            subfuns, bind_params = eqn.primitive.get_bind_params(p)
            outs = eqn.primitive.bind(*subfuns, *vals, **bind_params)
            if not eqn.primitive.multiple_results:
                outs = [outs]
            if name == "dot_general":
                outs = list(jax.lax.optimization_barrier(tuple(outs)))
            if (everything or kernel) and name not in MOVES:
                outs = [low(a) for a in outs]
        for v, a in zip(eqn.outvars, outs):
            env[v] = a
    return [read(v) for v in jaxpr.outvars]


def lowered(f, everything: bool):
    """``f`` computing in the lower precision, differentiable."""
    import jax

    low = _make_low()

    def g(*args):
        closed, shape = jax.make_jaxpr(f, return_shape=True)(*args)
        out = evaluate(closed.jaxpr, closed.consts,
                       jax.tree_util.tree_leaves(args), everything, low)
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(shape), out)

    return g


class _Lowered:
    """The model with its loss function in the lower precision."""

    def __init__(self, model, everything: bool):
        self._fn = model.lm_loss_fn()
        self._everything = everything

    def lm_loss_fn(self):
        def fn(params, model_state, batch, rng, train):
            return lowered(
                lambda p, b: self._fn(p, model_state, b, rng, train),
                self._everything)(params, batch)
        return fn


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("seeds", type=int, nargs="+")
    ap.add_argument("--round", choices=("all", "matmuls"), default="all")
    ap.add_argument("--stated", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import numpy as np

    from benchmarks.harness import build, check, device, spec

    device.enable_compile_cache()
    if not args.rehearse:
        device.require_chips(1)
    man = spec.manifest()
    cell = spec.cell(man, args.cell)
    config = spec.config(man, cell["config"], rehearse=args.rehearse)
    traffic = spec.traffic(cell["traffic"], rehearse=args.rehearse)
    if args.rehearse:           # the rehearsal section computes in float32
        config = {**config, "compute_dtype": "bfloat16"}
    arch = spec.adapter(config)
    whole = arch.reference_loss_and_grad_norm
    keys = ("ok", "loss_rel", "grad_norm_rel", "sys_loss", "sys_grad_norm",
            "ref_loss", "ref_grad_norm")
    ends = {}
    for seed in args.seeds:
        t0 = time.monotonic()
        out = {"seed": seed, "round": args.round}
        model = arch.make_model(config, "train")
        variables = build.init_variables(model, seed)
        low, high = arch.id_range(config)
        ids = np.random.default_rng(int(seed)).integers(
            low, high, (int(traffic["batch"]), int(traffic["seq"]))).astype(
                np.int32)[:int(traffic["check_sequences"])]
        ref = whole(variables["params"], ids, config)
        arch.reference_loss_and_grad_norm = lambda *a, ref=ref: ref
        sides = {"control": _Lowered(model, args.round == "all")}
        if args.stated:
            sides = {"stated": model, **sides}
        for side, m in sides.items():
            verdict = check.training(m, variables["params"],
                                     variables["params"], config, ids)
            out[side] = {k: verdict.get(k) for k in keys}
            for k in ("loss_rel", "grad_norm_rel"):
                lo, hi = ends.get((side, k), (verdict[k], verdict[k]))
                ends[side, k] = (min(lo, verdict[k]), max(hi, verdict[k]))
        arch.reference_loss_and_grad_norm = whole
        del model, variables
        out["seconds"] = time.monotonic() - t0
        print(json.dumps(out), flush=True)
    tol = arch.tolerances(config)
    print(json.dumps({
        "smallest_largest": {f"{s}.{k}": v for (s, k), v in ends.items()},
        "seeds": len(args.seeds), "round": args.round,
        "limits": {k: tol[k]["limit"]
                   for k in ("loss_rel", "grad_norm_rel")}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
