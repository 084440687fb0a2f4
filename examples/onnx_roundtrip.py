"""ONNX interop: export a trained model to a real .onnx file (no onnx
package needed) and load it back as an executable function (reference
analog: python/hetu/onnx hetu2onnx/onnx2hetu).

    python examples/onnx_roundtrip.py --model resnet
"""

from __future__ import annotations

import argparse
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from hetu_tpu.utils.platform import bootstrap_example

bootstrap_example(8)  # virtual CPU devices for bare runs + compile cache

import jax
import jax.numpy as jnp
import numpy as np

from hetu_tpu import models
from hetu_tpu.onnx import export_onnx, import_onnx


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=("resnet", "gpt"), default="resnet")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    if args.model == "resnet":
        m = models.ResNet18(num_classes=10)
        v = m.init(jax.random.PRNGKey(0))
        fn = lambda x: m.apply(v, x, train=False)[0]  # noqa: E731
        ex_args = (jax.random.normal(jax.random.PRNGKey(1),
                                     (2, 3, 32, 32)),)
    else:
        cfg = models.GPTConfig(vocab_size=1000, hidden_size=64,
                               num_layers=2, num_heads=4, ffn_size=128,
                               max_position=32, dropout_rate=0.0)
        m = models.HeteroGPT(cfg)  # per-layer params -> flat ONNX graph
        v = m.init(jax.random.PRNGKey(0))
        fn = lambda ids: m.apply(v, ids, train=False)[0]  # noqa: E731
        ex_args = (jnp.zeros((2, 32), jnp.int32),)

    out = args.out or str(Path(tempfile.mkdtemp()) / f"{args.model}.onnx")
    export_onnx(fn, ex_args, out)
    size_mb = Path(out).stat().st_size / 1e6
    print(f"exported {out} ({size_mb:.1f} MB)")

    imported, meta = import_onnx(out)
    got = imported(*ex_args)
    want = fn(*ex_args)
    err = float(jnp.max(jnp.abs(jnp.asarray(got) - jnp.asarray(want))))
    print(f"imported: {meta['n_nodes']} nodes, opset "
          f"{meta['opsets'][0]['version']}, max |Δ| vs original = {err:.2e}")
    assert err < 1e-3
    print("round trip OK")


if __name__ == "__main__":
    main()
