"""Rehearsal 3: compile each cell's programs at full size for a DESCRIBED
TPU v5e (``v5e:2x2``), with no chip attached, and print what the compiler's
``memory_analysis`` says.  What the TPU compiler refuses here costs no chip
time.  Nothing runs; no number this prints is a measurement of speed.

    JAX_PLATFORMS=cpu python3 benchmarks/tools/compile_v5e.py [cell ...]

A tool for the builder, not part of the measured command: it reaches into
the engine's jitted-step builders (``_build_chunk``, ``_build_decode``),
which the benchmark proper never does.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmarks.harness import build, schedule, spec  # noqa: E402


def _compile(lowered) -> dict:
    """memory_analysis of the compiled program, or the compiler's refusal."""
    try:
        return _mem(lowered.compile())
    except Exception as e:  # the tool reports a refusal, it does not stop
        msg = str(e)
        used = msg[msg.find("Used "):].split(".\n")[0][:120] \
            if "Used " in msg else msg[:200]
        return {"refused": used}


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    return {"argument_bytes": int(m.argument_size_in_bytes),
            "output_bytes": int(m.output_size_in_bytes),
            "alias_bytes": int(m.alias_size_in_bytes),
            "temp_bytes": int(m.temp_size_in_bytes),
            "live_bytes": int(m.argument_size_in_bytes
                              + m.output_size_in_bytes
                              - m.alias_size_in_bytes
                              + m.temp_size_in_bytes)}


def _abstract(tree, sharding):
    """ShapeDtypeStructs of ``tree`` with ``sharding`` (one, or a tree)."""
    if not isinstance(sharding, (NamedSharding, SingleDeviceSharding)):
        return jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            tree, sharding)
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def train_cell(cell, cfg, tr, topo) -> dict:
    import hetu_tpu as ht
    from hetu_tpu.train.executor import TrainState

    chips = int(cell["chips"])
    model = spec.adapter(cfg).make_model(cfg, "train")
    axes = cfg["train"]["mesh"][str(chips)]
    key = jax.random.PRNGKey(0)
    shapes = jax.eval_shape(model.init, key)
    if axes:
        from hetu_tpu.parallel.strategies import simple
        mesh = ht.make_mesh(devices=topo.devices, **axes)
        strategy = getattr(simple, cfg["train"]["strategy"][str(chips)])()
        rep = NamedSharding(mesh, P())
        p_sh = strategy.shardings(shapes["params"], mesh)
        s_sh = strategy.slot_shardings(shapes["params"], mesh)
    else:
        mesh = strategy = None
        rep = p_sh = s_sh = SingleDeviceSharding(topo.devices[0])
    ex = build.make_executor(model, cfg, mesh=mesh, strategy=strategy)
    opt = jax.eval_shape(ex.optimizer.init_state, shapes["params"])
    opt_abs = {k: (_abstract(v, rep) if k != "slots" else
                   {n: _abstract(sl, s_sh) for n, sl in v.items()})
               for k, v in opt.items()}
    state = TrainState(
        params=_abstract(shapes["params"], p_sh), opt_state=opt_abs,
        model_state={}, rng=jax.ShapeDtypeStruct((2,), jnp.uint32,
                                                 sharding=rep),
        step=jax.ShapeDtypeStruct((), jnp.int32, sharding=rep))
    b_sh = NamedSharding(mesh, P("dp")) if mesh is not None else rep
    batch = (jax.ShapeDtypeStruct((int(tr["batch"]), int(tr["seq"])),
                                  jnp.int32, sharding=b_sh),)
    fn = ex._compile("train")
    from hetu_tpu.parallel.mesh import mesh_context
    with mesh_context(mesh):
        compiled = fn.lower(state, batch).compile()
    text = compiled.as_text()
    return {"train_step": {
        **_mem(compiled),
        "mosaic_calls": text.count("tpu_custom_call"),
        "all_reduce": text.count(" all-reduce("),
        "all_gather": text.count(" all-gather("),
        "reduce_scatter": text.count(" reduce-scatter(")}}


def serve_cell(cell, cfg, tr, topo) -> dict:
    from hetu_tpu.serve import PagedServeEngine

    one = SingleDeviceSharding(topo.devices[0])
    model = spec.adapter(cfg).make_model(cfg, "serve")
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    s = cfg["serve"]
    # a one-page pool: the engine is built only for its step builders
    engine = PagedServeEngine(
        model, shapes, num_slots=int(s["num_slots"]),
        max_len=int(s["max_len"]), page_size=int(s["page_size"]),
        prefill_chunk=int(s["prefill_chunk"]), num_pages=2)
    cache = engine.cache
    n_pages = s.get("num_pages") or 1 + cache.num_slots * cache.pages_per_slot
    pool = jax.ShapeDtypeStruct(
        (cache.spec.num_layers, int(n_pages), cache.page_size,
         cache.spec.num_kv_heads, cache.spec.head_dim), cache.spec.dtype,
        sharding=one)
    params = _abstract(shapes["params"], one)
    out = {"pool_bytes_k_plus_v": 2 * int(np.prod(pool.shape)) * 2,
           "param_bytes": int(sum(np.prod(a.shape) * a.dtype.itemsize
                                  for a in jax.tree_util.tree_leaves(
                                      shapes["params"])))}
    n_table = cache.pages_per_slot
    chunk = engine._build_chunk(n_table)
    for b in engine.chunk_buckets:
        aux = jax.ShapeDtypeStruct((3 * b + n_table + 2,), jnp.int32,
                                   sharding=one)
        out[f"prefill_chunk_{b}"] = _compile(
            chunk.lower(params, pool, pool, aux))
    decode = engine._build_decode()
    reach = schedule.reach(tr)
    top = cache.pages_for_tokens(min(reach["max_total"] + 1, cache.max_len))
    pg = 1
    while pg < top:
        pg *= 2
    pg = min(pg, cache.pages_per_slot)   # the engine's own cap
    for bb, n_pg in ((cache.num_slots, pg), (1, 1)):
        aux = jax.ShapeDtypeStruct((bb, n_pg + 4), jnp.int32, sharding=one)
        out[f"decode_b{bb}_p{n_pg}"] = _compile(
            decode.lower(params, pool, pool, aux))
    return out


def main(argv) -> int:
    overrides = [a for a in argv if "=" in a]   # e.g. serve.num_slots=8
    argv = [a for a in argv if "=" not in a]
    # compile the kernels: they pick interpret mode from the DEFAULT
    # backend, which is the CPU here (as tests/test_kernel_lowering.py)
    import hetu_tpu.ops.pallas_kernels  # noqa: F401
    sys.modules["hetu_tpu.ops.pallas_kernels.flash_attention"] \
        .auto_interpret = lambda interpret: False
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    man = spec.manifest()
    names = argv or [w["name"] for w in man["workloads"]]
    for name in names:
        cell = spec.cell(man, name)
        cfg = spec.config(man, cell["config"])
        tr = spec.traffic(cell["traffic"])
        for o in overrides:
            path, value = o.split("=")
            where = {"config": cfg, "traffic": tr}[path.split(".")[0]]
            *keys, last = path.split(".")[1:]
            for k in keys:
                where = where[k]
            where[last] = json.loads(value)
        fn = train_cell if tr["kind"] == "train_steps" else serve_cell
        print(json.dumps({"cell": name, "device": "described v5e:2x2",
                          "memory_analysis": fn(cell, cfg, tr, topo)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
