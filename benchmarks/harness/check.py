"""The comparison that decides ``correct``: the system under test against
the configuration's plain reference at the run's own widths and weights,
once the measured window is over and the chip's memory has been read.  The
architecture's adapter (``spec.adapter(config)``) runs both sides and states
the four tolerances with their reasons; this file only says what is
compared with what.

Logits are compared, never tokens: the weights are random, so the largest
logit changes on rounding (PR 21 found two XLA programs of the same weights
0.007 logits apart at a near tie).

``logit_err`` holds the model's dense forward; the engine, its page tables
and its decode program return tokens only and are held by ``token_gap``.
"""

from __future__ import annotations

import numpy as np

from benchmarks.harness import build, spec

SERVE_PROMPT_LENS = (24, 100, 200, 333)   # one, two, four and six chunks
SERVE_DECODED = 8


def _range(a) -> float:
    return float(np.max(a) - np.min(a))


def global_norm(tree):
    import jax
    import jax.numpy as jnp

    return jnp.sqrt(sum(jnp.sum(jnp.square(a.astype(jnp.float32)))
                        for a in jax.tree_util.tree_leaves(tree)))


def _verdict(arch, config: dict, compared: dict, **rest) -> dict:
    """``ok`` when every number compared is within the limit the adapter
    states for it; the limits go into the verdict, so that a run prints each
    number beside its limit."""
    stated = arch.tolerances(config)
    limits = {k: stated[k]["limit"] for k in compared}
    ok = all(compared[k] <= limits[k] for k in compared)
    return {"ok": bool(ok), **compared, **rest, "limits": limits}


def serving(model, variables, engine, scheduler, config: dict,
            seed: int) -> dict:
    """Prefill, then eight decoded tokens, for four seeded prompts through
    the scheduler, the paged engine and its page tables, all four in flight
    together; against the reference's full forward over prompt + answer.

    Two comparisons, both on logits.  (1) The model's own forward in the
    configured precision against the reference at every position.  (2) Each
    token the engine emitted must be, by the REFERENCE's logits at that
    position, within the ``token_gap`` tolerance (of the logit range) of the
    best token: the engine returns tokens only, and this holds them to the
    reference without asking two roundings of a near tie to agree."""
    from hetu_tpu.serve import Request

    arch = spec.adapter(config)
    low, high = arch.id_range(config)
    max_prompt = int(config["serve"]["max_len"]) - SERVE_DECODED - 2
    lens = [min(n, max_prompt) for n in SERVE_PROMPT_LENS]
    rng = np.random.default_rng([int(seed), 7])
    prompts = [rng.integers(low, high, n).astype(np.int32).tolist()
               for n in lens]
    reqs = [Request(prompt=p, max_tokens=SERVE_DECODED + 1) for p in prompts]
    scheduler.run(reqs)
    bad = [r.status for r in reqs
           if r.status != "ok" or len(r.tokens) != SERVE_DECODED + 1]
    if bad:
        return {"ok": False, "why": f"check requests ended {bad}"}

    width = -(-(max(lens) + SERVE_DECODED + 1) // 128) * 128
    width = min(width, arch.positions(config))
    ids = np.zeros((len(reqs), width), np.int32)
    for i, r in enumerate(reqs):
        seq = list(r.prompt) + list(r.tokens)
        ids[i, :len(seq)] = seq
    params = variables["params"]
    ref = arch.reference_logits(params, ids, config)
    sysl = arch.system_logits(model, params, ids)
    logit_err, token_gap = 0.0, 0.0
    for i, r in enumerate(reqs):
        n = len(r.prompt)
        rows = slice(n - 1, n + SERVE_DECODED)     # predict tokens[0..8]
        span = _range(ref[i, rows])
        logit_err = max(logit_err, float(
            np.max(np.abs(sysl[i, rows] - ref[i, rows]))) / span)
        for j, tok in enumerate(r.tokens):
            row = ref[i, n - 1 + j]
            token_gap = max(token_gap,
                            float(np.max(row) - row[tok]) / span)
    return _verdict(arch, config, {"logit_err": logit_err,
                                   "token_gap": token_gap},
                    prompts=lens, decoded=SERVE_DECODED)


def training(model, ref_params, sys_params, config: dict, ids,
             *, mesh=None) -> dict:
    """Loss and gradient norm of the first step's first sequences: the
    system's own loss function (flash attention, fused cross entropy,
    remat, bfloat16, sharded as it trains) against the reference on one
    device.  ``ref_params`` are the same weights gathered to one device."""
    import jax

    from hetu_tpu.parallel.mesh import mesh_context

    arch = spec.adapter(config)
    loss_fn = model.lm_loss_fn()

    def system(p, x):
        value, grads = jax.value_and_grad(
            lambda q: loss_fn(q, {}, (x,), build.key_for(0, 1), True)[0])(p)
        return value, global_norm(grads)

    ref_loss, ref_norm = arch.reference_loss_and_grad_norm(
        ref_params, np.asarray(ids), config)
    x = ids
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        x = jax.device_put(np.asarray(ids), NamedSharding(mesh, P("dp")))
    with mesh_context(mesh):
        sys_loss, sys_norm = (float(v) for v in jax.jit(system)(
            sys_params, x))
    loss_rel = abs(sys_loss - ref_loss) / abs(ref_loss)
    norm_rel = abs(sys_norm - ref_norm) / abs(ref_norm)
    return _verdict(arch, config, {"loss_rel": loss_rel,
                                   "grad_norm_rel": norm_rel},
                    ref_loss=ref_loss, sys_loss=sys_loss,
                    ref_grad_norm=ref_norm, sys_grad_norm=sys_norm)
