"""Building the system under test from a configuration file: its weights
from ``--seed``, the mesh and strategy, the trainer and the serving stack.
The model itself comes from the configuration's adapter
(``spec.adapter(config).make_model``); nothing here names an architecture.
Only the program's public entry points are used: ``Executor``,
``PagedServeEngine``, ``ContinuousBatchingScheduler``, ``make_mesh`` and the
strategy presets."""

from __future__ import annotations


def key_for(seed: int, stream: int = 0):
    """A PRNG key from any non-negative ``--seed`` (the driver's pass 2**31)."""
    import jax

    return jax.random.fold_in(
        jax.random.PRNGKey(int(seed) % 2147483647), stream)


def mesh_and_strategy(config: dict, chips: int):
    """The mesh preset the configuration names for this many chips."""
    axes = config["train"]["mesh"].get(str(chips))
    if axes is None:
        raise ValueError(f"configuration {config['name']} has no training "
                         f"mesh for {chips} chips")
    if not axes:
        return None, None
    import hetu_tpu as ht
    from hetu_tpu.parallel.strategies import simple

    mesh = ht.make_mesh(**axes)
    strategy = getattr(simple, config["train"]["strategy"][str(chips)])()
    return mesh, strategy


def init_variables(model, seed: int, *, mesh=None, strategy=None):
    """The weights, made on the device(s) in one jitted call from the seed,
    already laid out as the strategy wants them (so that a model that fits
    only sharded never sits whole on one chip)."""
    import jax

    key = key_for(seed)
    if mesh is None:
        return jax.jit(model.init)(key)
    from jax.sharding import NamedSharding, PartitionSpec as P

    shapes = jax.eval_shape(model.init, key)
    rep = NamedSharding(mesh, P())
    out = {"params": strategy.shardings(shapes["params"], mesh),
           "state": jax.tree_util.tree_map(lambda _: rep, shapes["state"])}
    return jax.jit(model.init, out_shardings=out)(key)


def make_executor(model, config: dict, *, mesh=None, strategy=None):
    import hetu_tpu as ht
    from hetu_tpu import optim

    if config["train"]["optimizer"] != "adamw":
        raise ValueError(f"unknown optimizer {config['train']['optimizer']}")
    return ht.Executor(
        model.lm_loss_fn(),
        optim.AdamWOptimizer(float(config["train"]["learning_rate"])),
        mesh=mesh, dist_strategy=strategy)


def make_serving(model, variables, config: dict):
    from hetu_tpu.serve import ContinuousBatchingScheduler, PagedServeEngine

    s = config["serve"]
    engine = PagedServeEngine(
        model, variables, num_slots=int(s["num_slots"]),
        max_len=int(s["max_len"]), page_size=int(s["page_size"]),
        prefill_chunk=int(s["prefill_chunk"]),
        num_pages=s.get("num_pages"))
    return engine, ContinuousBatchingScheduler(engine)
