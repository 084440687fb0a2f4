"""One adapter per architecture: the only files that know an architecture's
configuration keys, its model class and its arithmetic.  A configuration
file names its adapter by module path (``"adapter": "benchmarks.arch.gpt2"``)
and ``harness/spec.py`` imports it; the harness, the readers and ``run.py``
ask it the questions below and nothing else of an architecture.

The operation and byte counts live here, with the benchmark, so that no PR
that claims a gain can change how a utilization is counted."""

# name -> what the harness asks by it
OFFERS = {
    "make_model": "(config, section) -> the program's model for the "
                  "'train' or 'serve' section of the configuration",
    "id_range": "(config) -> (low, high): the ids traffic, warm-up and the "
                "check draw from, high exclusive",
    "positions": "(config) -> the most positions a sequence may have",
    "reference": "(config) -> the plain reference's module, the file the "
                 "configuration's 'reference' key names",
    "reference_logits": "(params, ids, config) -> float32 numpy "
                        "[B, S, V]; the adapter owns the jit, so it may "
                        "run in blocks or layer by layer",
    "reference_loss_and_grad_norm": "(params, ids, config) -> (loss, "
                                    "global gradient norm), two floats",
    "system_logits": "(model, params, ids) -> float32 numpy [B, S, V] by "
                     "the program's dense forward",
    "tolerances": "(config) -> {logit_err, token_gap, loss_rel, "
                  "grad_norm_rel: {'limit': number, 'why': words}}",
    "train_flops_per_token": "(config, seq) -> operations one trained "
                             "token requires, forward and backward",
    "decode_step_bytes": "(config, cached_tokens) -> bytes one decode step "
                         "has to read",
    "decode_step_flops": "(config, active, cached_tokens) -> operations of "
                         "one decode step",
    "attention_call_shape": "(config, run_values) -> (batch, heads, seq, "
                            "head_dim) of one attention-kernel call on one "
                            "chip",
    "total_params": "(config) -> parameters of the whole model",
}
