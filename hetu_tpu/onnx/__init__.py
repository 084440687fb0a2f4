"""Model import/export.

Reference: python/hetu/onnx/ (2,337 LoC — hetu2onnx.export, onnx2hetu.
load_onnx, per-op opset handlers, tested against TF round trips).

Two interchange formats, neither needing the `onnx` package (absent here):

    export_graph(fn, args, path)   -> HTIR json (lossless jaxpr dump)
    load_graph(path)               -> dict graph
    import_graph(path)             -> executable fn from HTIR
    export_onnx(fn, args, path)    -> real .onnx, opset 13: the protobuf
                                      wire format is written directly
                                      (proto.py) and the jaxpr lowered per
                                      primitive (_export.py)
    import_onnx(path)              -> (fn, meta) from a real .onnx file,
                                      including ones written by other
                                      producers (_import.py)

The wire codec is cross-validated against the canonical google.protobuf
implementation in tests/test_onnx.py; the op semantics by zoo round trips
(ResNet-18, HeteroGPT) against the traced original.
"""

from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.core import Literal as _Literal

# jax primitive name → ONNX op type (the opset-handler table analog,
# reference onnx/onnx_opset/*)
_PRIM_TO_ONNX = {
    "add": "Add", "sub": "Sub", "mul": "Mul", "div": "Div", "neg": "Neg",
    "exp": "Exp", "log": "Log", "tanh": "Tanh", "sqrt": "Sqrt", "abs": "Abs",
    "sign": "Sign", "floor": "Floor", "ceil": "Ceil", "max": "Max",
    "min": "Min", "pow": "Pow", "dot_general": "MatMul",
    "conv_general_dilated": "Conv", "reshape": "Reshape",
    "transpose": "Transpose", "concatenate": "Concat", "slice": "Slice",
    "pad": "Pad", "broadcast_in_dim": "Expand", "reduce_sum": "ReduceSum",
    "reduce_max": "ReduceMax", "reduce_min": "ReduceMin",
    "logistic": "Sigmoid", "erf": "Erf", "rsqrt": "Reciprocal",
    "gather": "Gather", "dynamic_slice": "Slice", "select_n": "Where",
    "convert_element_type": "Cast", "stop_gradient": "Identity",
    "custom_jvp_call": "Identity", "integer_pow": "Pow", "squeeze": "Squeeze",
    # jax.ad_checkpoint.checkpoint_name: a label for a remat policy
    "name": "Identity",
    "argmax": "ArgMax", "iota": "Range", "clamp": "Clip",
}


def trace_graph(fn, *example_args, max_inline_const=None) -> dict:
    """Serialize the traced dataflow graph to a portable dict.

    Closure-captured arrays (model WEIGHTS) become jaxpr constants and are
    inlined by default — that is the point of exporting a trained model
    (ONNX stores weights the same way).  Pass max_inline_const=N to elide
    constants above N elements (shape/dtype stub only; the file then can't
    be imported as executable).
    """
    closed = jax.make_jaxpr(fn)(*example_args)
    jaxpr = closed.jaxpr
    def enc_const(c):
        a = np.asarray(c)
        if max_inline_const is not None and a.size > max_inline_const:
            return {"elided": True, "shape": list(a.shape),
                    "dtype": str(a.dtype)}
        return {"data": a.tolist(), "dtype": str(a.dtype)}

    consts = [enc_const(c) for c in closed.consts]
    const_names = [str(v) for v in jaxpr.constvars]
    literals = {}

    def vname(v):
        if isinstance(v, _Literal):
            key = f"__lit_{len(literals)}"
            a = np.asarray(v.val)
            literals[key] = {"data": a.tolist(), "dtype": str(a.dtype)}
            return key
        return str(v)

    nodes = []
    for eqn in jaxpr.eqns:
        nodes.append({
            "op": eqn.primitive.name,
            "onnx_op": _PRIM_TO_ONNX.get(eqn.primitive.name),
            "inputs": [vname(v) for v in eqn.invars],
            "outputs": [str(v) for v in eqn.outvars],
            # repr for humans, plus machine-decodable fields for import
            "attrs": {k: repr(v) for k, v in eqn.params.items()},
            "raw_attrs": _encode_params(eqn.params),
        })
    return {
        "format": "hetu_tpu.htir.v1",
        "inputs": [{"name": str(v), "shape": list(v.aval.shape),
                    "dtype": str(v.aval.dtype)} for v in jaxpr.invars],
        "outputs": [vname(v) for v in jaxpr.outvars],
        "constants": consts,
        "const_names": const_names,
        "literals": literals,
        "nodes": nodes,
    }


def _encode_params(params: dict) -> dict:
    """JSON-encode the primitive params the importer understands."""
    out = {}
    for k, v in params.items():
        if v is None:
            continue  # genuinely absent: nothing to consume
        if isinstance(v, (int, float, str, bool)):
            out[k] = v
        elif isinstance(v, (tuple, list)) and all(
                isinstance(x, (int, float, tuple, list)) for x in v):
            out[k] = json.loads(json.dumps(v))  # nested tuples → lists
        elif hasattr(v, "name"):  # dtypes etc.
            out[k] = str(getattr(v, "name", v))
        else:
            out[k] = "__unencodable__"  # import rejects the node
    return out


def export_graph(fn, example_args, path, *, max_inline_const=None) -> str:
    g = trace_graph(fn, *example_args, max_inline_const=max_inline_const)
    Path(path).write_text(json.dumps(g, indent=1))
    return str(path)


def load_graph(path) -> dict:
    g = json.loads(Path(path).read_text())
    if g.get("format") != "hetu_tpu.htir.v1":
        raise ValueError(f"not an HTIR graph: {path}")
    return g


# executable interpreters for the common primitive subset — the onnx2hetu
# per-op handler table analog (reference onnx/onnx_opset/*)
def _mk_dot(attrs):
    dn = attrs.get("dimension_numbers")
    # honor the EXPORTED accumulation dtype: inventing one would change the
    # original model's output dtype/numerics
    pet = attrs.get("preferred_element_type")

    def run(a, b):
        return jax.lax.dot_general(
            a, b, tuple(map(lambda t: tuple(map(tuple, t)), dn))
            if dn else (((a.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=pet)
    return run


# params each handler consumes; anything else present (beyond the harmless
# metadata set) makes import REJECT the node rather than silently drop
# semantics (e.g. lax.reshape's `dimensions` permutation)
_IGNORABLE_PARAMS = {"sharding", "precision", "preferred_element_type",
                     "out_sharding", "weak_type", "accuracy"}
_HANDLER_PARAMS = {
    "dot_general": {"dimension_numbers"},
    "reshape": {"new_sizes"},
    "transpose": {"permutation"},
    "broadcast_in_dim": {"shape", "broadcast_dimensions"},
    "reduce_sum": {"axes"}, "reduce_max": {"axes"}, "reduce_min": {"axes"},
    "convert_element_type": {"new_dtype"},
    "integer_pow": {"y"},
    "squeeze": {"dimensions"},
    "concatenate": {"dimension"},
    "name": {"name"},
}

_IMPORT_HANDLERS = {
    "add": lambda at: jnp.add, "sub": lambda at: jnp.subtract,
    "mul": lambda at: jnp.multiply, "div": lambda at: jnp.divide,
    "neg": lambda at: jnp.negative, "exp": lambda at: jnp.exp,
    "log": lambda at: jnp.log, "tanh": lambda at: jnp.tanh,
    "sqrt": lambda at: jnp.sqrt, "abs": lambda at: jnp.abs,
    "sign": lambda at: jnp.sign, "floor": lambda at: jnp.floor,
    "ceil": lambda at: jnp.ceil, "max": lambda at: jnp.maximum,
    "min": lambda at: jnp.minimum, "pow": lambda at: jnp.power,
    "logistic": lambda at: jax.nn.sigmoid,
    "erf": lambda at: jax.scipy.special.erf,
    "rsqrt": lambda at: jax.lax.rsqrt,
    "dot_general": _mk_dot,
    "reshape": lambda at: (lambda x: jnp.reshape(x, at["new_sizes"])),
    "transpose": lambda at: (lambda x: jnp.transpose(x, at["permutation"])),
    "broadcast_in_dim": lambda at: (lambda x: jax.lax.broadcast_in_dim(
        x, at["shape"], at["broadcast_dimensions"])),
    "reduce_sum": lambda at: (lambda x: jnp.sum(x, axis=tuple(at["axes"]))),
    "reduce_max": lambda at: (lambda x: jnp.max(x, axis=tuple(at["axes"]))),
    "reduce_min": lambda at: (lambda x: jnp.min(x, axis=tuple(at["axes"]))),
    "convert_element_type": lambda at: (
        lambda x: x.astype(at["new_dtype"])),
    "stop_gradient": lambda at: (lambda x: jax.lax.stop_gradient(x)),
    "name": lambda at: (lambda x: x),
    "integer_pow": lambda at: (lambda x: jnp.power(x, at["y"])),
    "squeeze": lambda at: (lambda x: jnp.squeeze(
        x, axis=tuple(at["dimensions"]))),
    "concatenate": lambda at: (lambda *xs: jnp.concatenate(
        xs, axis=at["dimension"])),
    "select_n": lambda at: (lambda c, *xs: jnp.select(
        [c == i for i in range(len(xs))], list(xs)) if len(xs) > 2
        else jnp.where(c.astype(bool), xs[1], xs[0])),
    "clamp": lambda at: (lambda lo, x, hi: jnp.clip(x, lo, hi)),
}


def import_graph(path):
    """Rebuild an executable python function from an HTIR file — the
    onnx2hetu.load_onnx analog.  Raises on primitives outside the handler
    table (same contract as the reference's unsupported-op errors)."""
    g = load_graph(path)
    missing = sorted({n["op"] for n in g["nodes"]
                      if n["op"] not in _IMPORT_HANDLERS})
    if missing:
        raise ValueError(f"HTIR import: unsupported primitives {missing}")
    for n in g["nodes"]:
        accepted = _HANDLER_PARAMS.get(n["op"], set()) | _IGNORABLE_PARAMS
        ra = n.get("raw_attrs", {})
        extra = sorted(k for k, v in ra.items()
                       if k not in accepted or v == "__unencodable__")
        if extra:
            raise ValueError(
                f"HTIR import: node {n['op']} carries params the handler "
                f"does not consume: {extra} — refusing to silently drop "
                "semantics")
    const_names = g.get("const_names", [])
    const_vals = []
    for c in g["constants"]:
        if not isinstance(c, dict):        # legacy files: bare list
            const_vals.append(jnp.asarray(c))
            continue
        if c.get("elided"):
            raise ValueError(
                "HTIR import: constants were elided at export "
                "(max_inline_const was set); re-export with the default "
                "inline-all to get an executable graph")
        const_vals.append(jnp.asarray(c["data"], dtype=c["dtype"]))

    def fn(*args):
        if len(args) != len(g["inputs"]):
            raise TypeError(f"expected {len(g['inputs'])} args")
        env = {}
        for spec, a in zip(g["inputs"], args):
            env[spec["name"]] = jnp.asarray(a)
        for name, v in zip(const_names, const_vals):
            env[name] = v
        for name, v in g.get("literals", {}).items():
            if isinstance(v, dict):
                env[name] = jnp.asarray(v["data"], dtype=v["dtype"])
            else:  # legacy
                env[name] = jnp.asarray(v)

        def lookup(name):
            if name in env:
                return env[name]
            raise KeyError(f"HTIR import: unbound value {name!r}")

        for node in g["nodes"]:
            handler = _IMPORT_HANDLERS[node["op"]](node.get("raw_attrs", {}))
            ins = [lookup(nm) for nm in node["inputs"]]
            outs = handler(*ins)
            if not isinstance(outs, tuple):
                outs = (outs,)
            for nm, val in zip(node["outputs"], outs):
                env[nm] = val
        res = [env[nm] for nm in g["outputs"]]
        return res[0] if len(res) == 1 else tuple(res)

    return fn


def unsupported_ops(graph: dict) -> list:
    """Primitives with no ONNX mapping — what export_onnx would reject."""
    return sorted({n["op"] for n in graph["nodes"] if n["onnx_op"] is None})


def export_onnx(fn, example_args, path) -> str:
    """Emit a real .onnx file (opset 13) — no `onnx` package needed: the
    protobuf wire format is written directly (hetu_tpu.onnx.proto), the
    jaxpr lowered per primitive (hetu_tpu.onnx._export), mirroring the
    reference's hetu2onnx.export (python/hetu/onnx/hetu2onnx.py:27)."""
    from hetu_tpu.onnx._export import jaxpr_to_onnx
    data = jaxpr_to_onnx(fn, *example_args)
    Path(path).write_bytes(data)
    return str(path)


# onnx2hetu.load_onnx analog: .onnx file -> executable jax fn
from hetu_tpu.onnx._import import import_onnx  # noqa: E402
