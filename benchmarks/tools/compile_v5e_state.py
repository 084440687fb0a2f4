"""``compile_v5e_groups.py``'s rehearsal for a serving cell whose cache has
STATE LAYERS (``KVCacheSpec.state_layers``: a fixed-size array a slot beside
the page pools): both programs then take the state array as a fifth,
donated argument and one more ``aux`` operand a sequence (its slot), which
neither ``compile_v5e_serve.py`` nor ``compile_v5e_groups.py`` hands over.
Here the pools and the state take their shapes from the engine's own cache at
the size the configuration gives, and a decode round's full attention layers
are compiled with the paged Pallas kernel the chip's programs hold (the
kernel is chosen from the default backend, which is the CPU here).  Compiles
for a DESCRIBED v5e; nothing runs, no number is a measurement.

    JAX_PLATFORMS=cpu python3 benchmarks/tools/compile_v5e_state.py <cell> [--hlo DIR] [program ...]

Programs are named ``init``, ``chunk<bucket>`` and ``decode<slots>x<pages>``;
without any: ``init``, the smallest and the largest chunk bucket, the decode
program of every slot at the top page bucket and at a quarter of it.
``--hlo DIR`` also writes each compiled program's text there: the names the
profiler gives device operations are these instructions, and their
``op_name`` metadata carries the model's ``jax.named_scope``s, which is what
the ``*_time_share`` metrics' patterns are written against and held to.
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

from benchmarks.harness import spec  # noqa: E402


def _as_on_the_chip() -> None:
    """The paged decode kernel is chosen, and compiled not interpreted, as
    on a TPU backend."""
    from hetu_tpu.ops.pallas_kernels import paged_attention

    sys.modules["hetu_tpu.ops.attention"]._default_backend_is_tpu = \
        lambda: True
    paged_attention.auto_interpret = lambda interpret: False


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
    if cache.state is None or len(cache.groups) != 1:
        raise ValueError("for a cache of one group with state layers; "
                         "compile_v5e_groups.py takes the others")
    pages = s.get("num_pages") or 1 + cache.num_slots * cache.pages_per_slot

    def sized(a, shape=None):
        return jax.ShapeDtypeStruct(shape or a.shape, a.dtype, sharding=one)

    k_pool = sized(cache.k, (cache.k.shape[0], int(pages)) + cache.k.shape[2:])
    v_pool = sized(cache.v, (cache.v.shape[0], int(pages)) + cache.v.shape[2:])
    state = sized(cache.state)
    params = _abstract(shapes["params"], one)
    n_table = top = cache.pages_per_slot
    if not programs:
        programs = ["init", f"chunk{engine.chunk_buckets[0]}",
                    f"chunk{engine.chunk_buckets[-1]}",
                    f"decode{cache.num_slots}x{top}",
                    f"decode{cache.num_slots}x{max(top // 4, 1)}"]
    out = {"pool_bytes_k_plus_v": int(
        2 * np.prod(k_pool.shape) * k_pool.dtype.itemsize),
        "state_bytes": int(np.prod(state.shape) * state.dtype.itemsize),
        "param_bytes": int(sum(
            np.prod(a.shape) * a.dtype.itemsize
            for a in jax.tree_util.tree_leaves(shapes["params"])))}

    def note(name, lowered):
        try:
            compiled = lowered.compile()
        except Exception as e:  # the tool reports a refusal and goes on
            out[name] = {"refused": str(e)[:300]}
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
            aux = jax.ShapeDtypeStruct((3 * b + n_table + 2 + 1,),
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
