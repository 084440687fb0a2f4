"""``compile_v5e_parts.py``'s rehearsal for a serving cell whose cache keeps
COMPRESSED rows beside its pages (``KVCacheSpec.comp_stride``: the K pool
travels as the pair (pool, compressed rows) and a chunk program takes its
prompt's length as one int more), over the tree the ENGINE holds, so the
text written is the chip's program.  Compiles for a DESCRIBED v5e; nothing
runs, no number is a measurement.

    JAX_PLATFORMS=cpu python3 benchmarks/tools/compile_v5e_sparse.py <cell> [--hlo DIR] [program ...]

Programs are named ``init``, ``chunk<bucket>`` and ``decode<slots>x<pages>``;
without any: ``init``, the smallest and the largest chunk bucket and the
decode program of every slot at the top page bucket.  ``--hlo DIR`` also
writes each compiled program's text there (``compile_v5e_parts.py`` says
what it is read for).
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

# compile_v5e sets the environment (CPU, no TPU log directory) and the path
# to the repo's root before it imports jax, so it comes first
from compile_v5e import _abstract, _mem  # noqa: E402  isort: skip
from compile_v5e_state import _as_on_the_chip  # noqa: E402  isort: skip

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmarks.harness import spec  # noqa: E402


def serve_cell(cfg, topo, programs, hlo_dir=None) -> dict:
    from hetu_tpu.serve import PagedServeEngine

    _as_on_the_chip()
    one = SingleDeviceSharding(topo.devices[0])
    model = spec.adapter(cfg).make_model(cfg, "serve")
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    s = cfg["serve"]
    engine = PagedServeEngine(     # a two-page pool: built for its builders
        model, shapes, num_slots=int(s["num_slots"]),
        max_len=int(s["max_len"]), page_size=int(s["page_size"]),
        prefill_chunk=int(s["prefill_chunk"]), num_pages=2)
    cache = engine.cache
    if cache.state is None or len(cache.groups) != 1 \
            or cache.groups[0].comp is None:
        raise ValueError("for a cache of one group with compressed rows "
                         "and state layers")
    pages = s.get("num_pages") or 1 + cache.num_slots * cache.pages_per_slot

    def sized(a, shape=None):
        return jax.ShapeDtypeStruct(shape or a.shape, a.dtype, sharding=one)

    def pool(a):
        return sized(a, (a.shape[0], int(pages)) + a.shape[2:])

    k_pool, v_pool = jax.tree_util.tree_map(pool, cache.pool_args())
    state = jax.tree_util.tree_map(sized, cache.state)
    params = _abstract(engine.params, one)
    n_table = cache.pages_per_slot
    if not programs:
        programs = ["init", f"chunk{engine.chunk_buckets[0]}",
                    f"chunk{engine.chunk_buckets[-1]}",
                    f"decode{cache.num_slots}x{n_table}"]

    def nbytes(tree):
        return int(sum(a.size * a.dtype.itemsize
                       for a in jax.tree_util.tree_leaves(tree)))

    out = {"pool_bytes_k_v_and_compressed": nbytes((k_pool, v_pool)),
           "state_bytes": nbytes(state), "param_bytes": nbytes(params)}

    def note(name, lowered):
        try:
            compiled = lowered.compile()
        except Exception as e:  # the tool reports a refusal and goes on
            out[name] = {"refused": str(e)[:600]}
            return
        out[name] = _mem(compiled)
        if hlo_dir:
            Path(hlo_dir).mkdir(parents=True, exist_ok=True)
            (Path(hlo_dir) / f"{name}.hlo.txt").write_text(compiled.as_text())

    for name in programs:
        if name == "init":
            note(name, jax.jit(model.init).lower(
                jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)))
        elif name.startswith("chunk"):
            b = int(name[5:])
            # ids | write pages | write offsets | table | start | last |
            # the prompt's length | the slot
            aux = jax.ShapeDtypeStruct((3 * b + n_table + 2 + 2,),
                                       jnp.int32, sharding=one)
            note(name, engine._build_chunk(n_table).lower(
                params, k_pool, v_pool, aux, state))
        else:
            slots, n_pg = (int(x) for x in name[6:].split("x"))
            aux = jax.ShapeDtypeStruct((slots, n_pg + 4 + 1), jnp.int32,
                                       sharding=one)
            note(name, engine._build_decode().lower(
                params, k_pool, v_pool, aux, state))
    return out


def main(argv) -> int:
    hlo_dir = None
    if "--hlo" in argv:
        i = argv.index("--hlo")
        hlo_dir = argv[i + 1]
        argv = argv[:i] + argv[i + 2:]
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    man = spec.manifest()
    cell = spec.cell(man, argv[0])
    cfg = spec.config(man, cell["config"])
    print(json.dumps({"cell": argv[0], "device": "described v5e:2x2",
                      "memory_analysis": serve_cell(cfg, topo, argv[1:],
                                                    hlo_dir)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
