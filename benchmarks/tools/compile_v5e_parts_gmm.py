"""``compile_v5e_parts.py`` for a serving cell whose programs also hold the
GROUPED expert walk (``ops.moe_ops.held_expert_path`` ``grouped``: an expert
of at most 4 Mi elements, as ``qwen3-next-80b-a3b-instruct.batch-mixed``'s 2048
x 512): that tool patches the paged decode kernel's choice and not
``grouped_matmul.auto_interpret``, so the text it writes would hold the
interpreter's composition of the grouped matmuls and not the chip's Mosaic
calls.  Same arguments, same output; compiles for a DESCRIBED v5e, nothing
runs, no number is a measurement.

    JAX_PLATFORMS=cpu python3 benchmarks/tools/compile_v5e_parts_gmm.py <cell> [--hlo DIR] [program ...]
"""

from __future__ import annotations

import sys

import compile_v5e_parts  # sets the environment and the path first

from hetu_tpu.ops.pallas_kernels import grouped_matmul  # noqa: E402

if __name__ == "__main__":
    grouped_matmul.auto_interpret = lambda interpret: False
    sys.exit(compile_v5e_parts.main(sys.argv[1:]))
