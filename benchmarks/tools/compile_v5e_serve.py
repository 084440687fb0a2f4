"""``compile_v5e.py``'s rehearsal for a serving cell whose two cache pools
differ in width (``compile_v5e.serve_cell`` gives both pools the K pool's
shape): each pool takes its shape from the engine's own cache, and the
program that makes the weights (``jit(model.init)``) is compiled too, since
for a model that fills the chip its temporaries decide whether it fits.
Compiles for a DESCRIBED v5e; nothing runs, no number is a measurement.

    JAX_PLATFORMS=cpu python3 benchmarks/tools/compile_v5e_serve.py <cell> [--hlo DIR]

``--hlo DIR`` also writes each compiled program's text there: the names the
profiler gives device operations are these instructions, which is what the
``*_time_share`` metrics' patterns are written against.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

# compile_v5e sets the environment (CPU, no TPU log directory) and the path
# to the repo's root before it imports jax, so it comes first
from compile_v5e import _abstract, _mem  # noqa: E402  isort: skip

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmarks.harness import schedule, spec  # noqa: E402


def serve_cell(cfg, tr, topo, hlo_dir=None) -> dict:
    from hetu_tpu.serve import PagedServeEngine

    one = SingleDeviceSharding(topo.devices[0])
    model = spec.adapter(cfg).make_model(cfg, "serve")
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
    out = {}

    def note(name, lowered):
        try:
            compiled = lowered.compile()
        except Exception as e:  # the tool reports a refusal and goes on
            msg = str(e)
            out[name] = {"refused": msg[msg.find("Used "):][:160]
                         if "Used " in msg else msg[:300]}
            return
        out[name] = _mem(compiled)
        if hlo_dir:
            Path(hlo_dir).mkdir(parents=True, exist_ok=True)
            (Path(hlo_dir) / f"{name}.hlo.txt").write_text(compiled.as_text())

    note("init", jax.jit(model.init).lower(key))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    s = cfg["serve"]
    engine = PagedServeEngine(     # a two-page pool: built for its builders
        model, shapes, num_slots=int(s["num_slots"]),
        max_len=int(s["max_len"]), page_size=int(s["page_size"]),
        prefill_chunk=int(s["prefill_chunk"]), num_pages=2)
    cache = engine.cache
    n_pages = s.get("num_pages") or 1 + cache.num_slots * cache.pages_per_slot

    def pool(small):
        return jax.ShapeDtypeStruct(
            (small.shape[0], int(n_pages)) + small.shape[2:], small.dtype,
            sharding=one)

    k_pool, v_pool = pool(cache.k), pool(cache.v)
    params = _abstract(shapes["params"], one)
    out["pool_bytes_k_plus_v"] = int(sum(
        np.prod(p.shape) * p.dtype.itemsize for p in (k_pool, v_pool)))
    out["param_bytes"] = int(sum(
        np.prod(a.shape) * a.dtype.itemsize
        for a in jax.tree_util.tree_leaves(shapes["params"])))
    n_table = cache.pages_per_slot
    chunk = engine._build_chunk(n_table)
    for b in engine.chunk_buckets:
        aux = jax.ShapeDtypeStruct((3 * b + n_table + 2,), jnp.int32,
                                   sharding=one)
        note(f"prefill_chunk_{b}", chunk.lower(params, k_pool, v_pool, aux))
    decode = engine._build_decode()
    reach = schedule.reach(tr)
    top = cache.pages_for_tokens(min(reach["max_total"] + 1, cache.max_len))
    pg = 1
    while pg < top:
        pg *= 2
    pg = min(pg, cache.pages_per_slot)
    for bb, n_pg in ((cache.num_slots, pg), (1, 1)):
        aux = jax.ShapeDtypeStruct((bb, n_pg + 4), jnp.int32, sharding=one)
        note(f"decode_b{bb}_p{n_pg}", decode.lower(params, k_pool, v_pool,
                                                   aux))
    return out


def main(argv) -> int:
    hlo_dir = None
    if "--hlo" in argv:
        i = argv.index("--hlo")
        hlo_dir = argv[i + 1]
        argv = argv[:i] + argv[i + 2:]
    overrides = [a for a in argv if "=" in a]   # e.g. config.serve.num_slots=8
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    man = spec.manifest()
    for name in (a for a in argv if "=" not in a):
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
        print(json.dumps({"cell": name, "device": "described v5e:2x2",
                          "memory_analysis": serve_cell(cfg, tr, topo,
                                                        hlo_dir)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
