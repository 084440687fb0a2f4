"""The readings a configuration's serving tolerances are set from: for each
seed, the weights from that seed, a fresh engine, and ``harness/check.py``'s
serving comparison alone (no warm-up, no window), so that twenty seeds cost
what two benchmark runs do.  ``--control`` adds, for each seed, the reading
of the nearest precision BELOW the one the configuration states: the
system's forward over weights rounded to the mantissa of an 8-bit float
(e4m3) against the same reference, which has to land outside
``logit_err``'s limit; and the reading of the stated arithmetic done exactly
(float32, highest precision), which has to be near nothing.  On a TPU only.

    python3 benchmarks/tools/check_seeds.py <cell> [--control] seed [seed ...]

One JSON object a seed, then one with the largest of each.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def _three_mantissa_bits(a):
    """bfloat16 rounded to the three mantissa bits of an 8-bit float
    (e4m3), to nearest, by its bits: a convert to float8 and back is folded
    away by the TPU compiler."""
    import jax
    import jax.numpy as jnp

    bits = jax.lax.bitcast_convert_type(a, jnp.uint16)
    return jax.lax.bitcast_convert_type(
        (bits + jnp.uint16(8)) & jnp.uint16(0xFFF0), jnp.bfloat16)


def main(argv) -> int:
    control = "--control" in argv
    argv = [a for a in argv if a != "--control"]
    cell, seeds = argv[0], [int(a) for a in argv[1:]]

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import build, check, device, spec

    device.enable_compile_cache()
    device.require_chips(1)
    man = spec.manifest()
    config = spec.config(man, spec.cell(man, cell)["config"])
    arch = spec.adapter(config)
    model = arch.make_model(config, "serve")
    worst = {}
    for seed in seeds:
        t0 = time.monotonic()
        variables = build.init_variables(model, seed)
        engine, scheduler = build.make_serving(model, variables, config)
        verdict = check.serving(model, variables, engine, scheduler, config,
                                seed)
        out = {"seed": seed, **{k: verdict.get(k) for k in (
            "ok", "logit_err", "token_gap", "why")}}
        del engine, scheduler
        if control:
            low, high = arch.id_range(config)
            ids = np.random.default_rng([seed, 13]).integers(
                low, high, (2, 256)).astype(np.int32)
            params = variables["params"]
            ref = arch.reference_logits(params, ids, config)
            span = float(ref.max() - ref.min())
            out["dense_logit_err"] = float(np.max(np.abs(
                arch.system_logits(model, params, ids) - ref))) / span
            # the stated arithmetic done exactly: float32 at the highest
            # matmul precision over the same weights (what is left is the
            # order of operations; a larger number is a fault)
            exact = arch.make_model({**config, "compute_dtype": "float32"},
                                    "serve")
            with jax.default_matmul_precision("highest"):
                out["float32_compute_logit_err"] = float(np.max(np.abs(
                    arch.system_logits(exact, params, ids) - ref))) / span
            lower = jax.jit(lambda p: jax.tree_util.tree_map(
                lambda a: _three_mantissa_bits(a)
                if a.ndim > 2 and a.dtype == jnp.bfloat16 else a, p),
                donate_argnums=0)(params)
            out["float8_weights_logit_err"] = float(np.max(np.abs(
                arch.system_logits(model, lower, ids) - ref))) / span
            del lower, params
        del variables
        out["seconds"] = time.monotonic() - t0
        print(json.dumps(out), flush=True)
        for k, v in out.items():
            if k.endswith("_err") or k == "token_gap":
                worst[k] = max(worst.get(k, 0.0), v or 0.0)
    tol = arch.tolerances(config)
    print(json.dumps({"largest": worst, "seeds": len(seeds), "limits": {
        k: tol[k]["limit"] for k in ("logit_err", "token_gap")}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
