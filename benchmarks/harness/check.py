"""The comparison that decides ``correct``: the system under test against
``benchmarks/reference/gpt2.py`` at the run's own widths and weights, during
once the measured window is over and the chip's memory has been read.

Logits are compared, never tokens: the weights are random, so the largest
logit changes on rounding (PR 21 found two XLA programs of the same weights
0.007 logits apart at a near tie).

Tolerances.  The configurations compute in bfloat16 (8 bits of mantissa,
relative rounding 2**-8 = 0.0039 per operation) over float32 weights; the
reference is float32 at the highest matmul precision.  Measured on the v5e
at the published widths (builder's chip runs, PR 23, some sixty runs over
four cells): ``logit_err`` 0.0056-0.0060 of the reference's logit range for
gpt2-small and 0.0075-0.0078 for gpt2-large, ``token_gap`` 0-0.002,
``loss_rel`` 3e-6-3e-5, ``grad_norm_rel`` 0.9e-3-1.6e-3.  Each bound below is
three to six times the worst of these.  A program that computed in 8-bit
floats or integers where bfloat16 is stated rounds sixteen times coarser
(2**-4 per operation) and lands an order of magnitude outside every one;
a wrong page, position or mask moves logits by their whole range
(``tests/test_reference.py`` shows both, at tiny widths).

``logit_err`` holds the model's dense forward; the engine, its page tables
and its decode program return tokens only and are held by ``token_gap``:
under the best of 50257 near-Gaussian logits lie on average 0.4 other
candidates within 1% of the range and six to eight within 5%, so the bound
is 1% (five times the worst measured) and not the 5% it first was, under
which a coarser cache or decode program could have passed.
"""

from __future__ import annotations

import numpy as np

from benchmarks.harness import build
from benchmarks.reference import gpt2 as reference

LOGIT_TOL = 0.025      # max |system - reference| over the reference's range
TOKEN_GAP_TOL = 0.01   # by the reference's logits the engine's token may
#                        trail the best by the two candidates' own errors
LOSS_REL_TOL = 2e-4
GRAD_NORM_REL_TOL = 6e-3

SERVE_PROMPT_LENS = (24, 100, 200, 333)   # one, two, four and six chunks
SERVE_DECODED = 8


def _range(a) -> float:
    return float(np.max(a) - np.min(a))


def serving(model, variables, engine, scheduler, config: dict,
            seed: int) -> dict:
    """Prefill, then eight decoded tokens, for four seeded prompts through
    the scheduler, the paged engine and its page tables, all four in flight
    together; against the reference's full forward over prompt + answer.

    Two comparisons, both on logits.  (1) The model's own forward in the
    configured precision against the reference at every position.  (2) Each
    token the engine emitted must be, by the REFERENCE's logits at that
    position, within TOKEN_GAP_TOL (of the logit range) of the best token:
    the engine returns tokens only, and this holds them to the reference
    without asking two roundings of a near tie to agree."""
    import jax
    import jax.numpy as jnp

    from hetu_tpu.serve import Request

    heads = int(config["n_head"])
    vocab = int(config["vocab_size"])
    max_prompt = int(config["serve"]["max_len"]) - SERVE_DECODED - 2
    lens = [min(n, max_prompt) for n in SERVE_PROMPT_LENS]
    rng = np.random.default_rng([int(seed), 7])
    prompts = [rng.integers(0, vocab, n).astype(np.int32).tolist()
               for n in lens]
    reqs = [Request(prompt=p, max_tokens=SERVE_DECODED + 1) for p in prompts]
    scheduler.run(reqs)
    bad = [r.status for r in reqs
           if r.status != "ok" or len(r.tokens) != SERVE_DECODED + 1]
    if bad:
        return {"ok": False, "why": f"check requests ended {bad}"}

    width = -(-(max(lens) + SERVE_DECODED + 1) // 128) * 128
    width = min(width, int(config["n_positions"]))
    ids = np.zeros((len(reqs), width), np.int32)
    for i, r in enumerate(reqs):
        seq = list(r.prompt) + list(r.tokens)
        ids[i, :len(seq)] = seq
    params = variables["params"]
    ref = np.asarray(jax.jit(
        lambda p, x: reference.logits(p, x, heads))(params, ids))
    sysl = np.asarray(jax.jit(
        lambda p, x: model.apply({"params": p, "state": {}}, x)[0])(
            params, jnp.asarray(ids)).astype(jnp.float32))
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
    ok = logit_err <= LOGIT_TOL and token_gap <= TOKEN_GAP_TOL
    return {"ok": bool(ok), "logit_err": logit_err, "token_gap": token_gap,
            "prompts": lens, "decoded": SERVE_DECODED}


def training(model, ref_params, sys_params, config: dict, ids,
             *, mesh=None) -> dict:
    """Loss and gradient norm of the first step's first sequences: the
    system's own loss function (flash attention, fused cross entropy,
    remat, bfloat16, sharded as it trains) against the reference on one
    device.  ``ref_params`` are the same weights gathered to one device."""
    import jax

    from hetu_tpu.parallel.mesh import mesh_context

    heads = int(config["n_head"])
    loss_fn = model.lm_loss_fn()

    def system(p, x):
        value, grads = jax.value_and_grad(
            lambda q: loss_fn(q, {}, (x,), build.key_for(0, 1), True)[0])(p)
        return value, reference.global_norm(grads)

    ref_loss, ref_norm = (float(v) for v in jax.jit(
        lambda p, x: reference.loss_and_grad_norm(p, x, heads))(
            ref_params, np.asarray(ids)))
    x = ids
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        x = jax.device_put(np.asarray(ids), NamedSharding(mesh, P("dp")))
    with mesh_context(mesh):
        sys_loss, sys_norm = (float(v) for v in jax.jit(system)(
            sys_params, x))
    loss_rel = abs(sys_loss - ref_loss) / abs(ref_loss)
    norm_rel = abs(sys_norm - ref_norm) / abs(ref_norm)
    ok = loss_rel <= LOSS_REL_TOL and norm_rel <= GRAD_NORM_REL_TOL
    return {"ok": bool(ok), "loss_rel": loss_rel, "grad_norm_rel": norm_rel,
            "ref_loss": ref_loss, "sys_loss": sys_loss,
            "ref_grad_norm": ref_norm, "sys_grad_norm": sys_norm}
