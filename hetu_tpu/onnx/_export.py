"""jaxpr -> ONNX model bytes (the hetu2onnx.export analog).

Reference: python/hetu/onnx/hetu2onnx.py:27 walks the hetu op graph and
emits ONNX nodes through per-op opset handlers (onnx_opset/*); here the
traced jaxpr is walked and each primitive lowered through `_EMITTERS`,
writing the wire format directly via `hetu_tpu.onnx.proto` (no `onnx`
package in this environment).

Weights (jaxpr consts) become graph initializers, as ONNX stores them.
pjit / custom_jvp / closed_call sub-jaxprs are inlined; `scan` (RNNs,
scan-stacked layers) is UNROLLED — the static trip count is in the jaxpr,
and the unrolled form round-trips through any consumer without Loop/Scan
subgraph support (size-capped; see _unroll_scan).  Target opset 13.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from hetu_tpu.onnx import proto as P


class _Ctx:
    def __init__(self):
        self.nodes: List[bytes] = []
        self.initializers: List[bytes] = []
        self.counter = 0
        self._literal_cache: Dict = {}

    def fresh(self, hint="t"):
        self.counter += 1
        return f"{hint}_{self.counter}"

    def init_tensor(self, arr, hint="const"):
        name = self.fresh(hint)
        self.initializers.append(P.tensor_proto(name, np.asarray(arr)))
        return name

    def init_literal(self, arr):
        """Deduped initializer for jaxpr Literals: the same scalar (an
        epsilon repeated per layer) serializes once."""
        a = np.asarray(arr)
        key = (a.tobytes(), str(a.dtype), a.shape)
        if key not in self._literal_cache:
            self._literal_cache[key] = self.init_tensor(a, "lit")
        return self._literal_cache[key]

    def emit(self, op_type, inputs, outputs, attrs=None):
        self.nodes.append(P.node_proto(op_type, inputs, outputs,
                                       attrs=attrs))


def _std_matmul(dn, lhs_nd, rhs_nd) -> bool:
    """dot_general patterns ONNX MatMul covers: [..., M, K] x [..., K, N]
    — one contraction (lhs LAST dim with rhs first non-batch dim), batch
    dims leading and aligned, and exactly ONE free dim on each side."""
    (lc, rc), (lb, rb) = dn
    if len(lc) != 1 or len(rc) != 1:
        return False
    nb = len(lb)
    if tuple(lb) != tuple(range(nb)) or tuple(rb) != tuple(range(nb)):
        return False
    return (rc[0] == nb and lc[0] == lhs_nd - 1
            and lhs_nd - nb == 2 and rhs_nd - nb == 2)


def _einsum_eq(dn, lhs_ndim, rhs_ndim) -> str:
    (lc, rc), (lb, rb) = dn
    letters = "abcdefghijklmnopqrstuvwxyz"
    it = iter(letters)
    lhs = [None] * lhs_ndim
    rhs = [None] * rhs_ndim
    for i, j in zip(lb, rb):
        c = next(it)
        lhs[i] = c
        rhs[j] = c
    for i, j in zip(lc, rc):
        c = next(it)
        lhs[i] = c
        rhs[j] = c
    for i in range(lhs_ndim):
        if lhs[i] is None:
            lhs[i] = next(it)
    for j in range(rhs_ndim):
        if rhs[j] is None:
            rhs[j] = next(it)
    out = [lhs[i] for i in lb]
    out += [lhs[i] for i in range(lhs_ndim) if i not in lb and i not in lc]
    out += [rhs[j] for j in range(rhs_ndim) if j not in rb and j not in rc]
    return f"{''.join(lhs)},{''.join(rhs)}->{''.join(out)}"


# ---- per-primitive emitters: fn(ctx, eqn, ins, outs) ----

_SIMPLE = {
    "add": "Add", "sub": "Sub", "mul": "Mul", "div": "Div", "neg": "Neg",
    "exp": "Exp", "log": "Log", "tanh": "Tanh", "sqrt": "Sqrt",
    "abs": "Abs", "sign": "Sign", "floor": "Floor", "ceil": "Ceil",
    "max": "Max", "min": "Min", "pow": "Pow", "logistic": "Sigmoid",
    "erf": "Erf", "stop_gradient": "Identity", "copy": "Identity",
    "and": "And", "or": "Or", "not": "Not", "eq": "Equal",
    # jax.ad_checkpoint.checkpoint_name (what ops.remat keeps by name)
    "name": "Identity",
}
_COMPARE = {"lt": ("Less", False), "le": ("LessOrEqual", False),
            "gt": ("Greater", False), "ge": ("GreaterOrEqual", False)}


def _emit_eqn(ctx: _Ctx, eqn, ins, outs):
    prim = eqn.primitive.name
    p = eqn.params
    if prim in _SIMPLE:
        ctx.emit(_SIMPLE[prim], ins, outs)
    elif prim in _COMPARE:
        ctx.emit(_COMPARE[prim][0], ins, outs)
    elif prim == "rsqrt":
        mid = ctx.fresh("sqrt")
        ctx.emit("Sqrt", ins, [mid])
        ctx.emit("Reciprocal", [mid], outs)
    elif prim == "is_finite":
        # finite == Not(Or(IsInf, IsNaN))
        m1, m2, m3 = ctx.fresh("inf"), ctx.fresh("nan"), ctx.fresh("or")
        ctx.emit("IsInf", ins, [m1])
        ctx.emit("IsNaN", ins, [m2])
        ctx.emit("Or", [m1, m2], [m3])
        ctx.emit("Not", [m3], outs)
    elif prim == "square":
        ctx.emit("Mul", [ins[0], ins[0]], outs)
    elif prim == "cube":
        mid = ctx.fresh("sq")
        ctx.emit("Mul", [ins[0], ins[0]], [mid])
        ctx.emit("Mul", [mid, ins[0]], outs)
    elif prim == "integer_pow":
        dt = np.dtype(eqn.invars[0].aval.dtype)
        y = ctx.init_tensor(np.asarray(p["y"], dt), "pow_exp")
        ctx.emit("Pow", [ins[0], y], outs)
    elif prim == "dot_general":
        dn = p["dimension_numbers"]
        lhs_nd = len(eqn.invars[0].aval.shape)
        rhs_nd = len(eqn.invars[1].aval.shape)
        if _std_matmul(dn, lhs_nd, rhs_nd):
            ctx.emit("MatMul", ins, outs)
        else:
            ctx.emit("Einsum", ins, outs,
                     {"equation": _einsum_eq(dn, lhs_nd, rhs_nd)})
    elif prim == "conv_general_dilated":
        dn = p["dimension_numbers"]
        if (dn.lhs_spec[0], dn.lhs_spec[1]) != (0, 1) or \
                (dn.rhs_spec[0], dn.rhs_spec[1]) != (0, 1) or \
                (dn.out_spec[0], dn.out_spec[1]) != (0, 1):
            raise ValueError("ONNX export: conv must be NCHW/OIHW")
        if any(d != 1 for d in p["lhs_dilation"]):
            raise ValueError("ONNX export: transposed conv unsupported")
        pads = [lo for lo, _ in p["padding"]] + \
               [hi for _, hi in p["padding"]]
        ctx.emit("Conv", ins, outs, {
            "strides": list(p["window_strides"]),
            "pads": pads,
            "dilations": list(p["rhs_dilation"]),
            "group": int(p["feature_group_count"]),
        })
    elif prim == "reshape":
        if p.get("dimensions") is not None:
            raise ValueError("ONNX export: reshape with permutation")
        shape = ctx.init_tensor(np.asarray(p["new_sizes"], np.int64),
                                "shape")
        ctx.emit("Reshape", [ins[0], shape], outs)
    elif prim == "transpose":
        ctx.emit("Transpose", ins, outs, {"perm": list(p["permutation"])})
    elif prim == "broadcast_in_dim":
        shape = p["shape"]
        bdims = p["broadcast_dimensions"]
        inter = [1] * len(shape)
        for src, dst in enumerate(bdims):
            inter[dst] = eqn.invars[0].aval.shape[src]
        cur = ins[0]
        if tuple(inter) != tuple(eqn.invars[0].aval.shape):
            rs = ctx.init_tensor(np.asarray(inter, np.int64), "shape")
            mid = ctx.fresh("rshp")
            ctx.emit("Reshape", [cur, rs], [mid])
            cur = mid
        tgt = ctx.init_tensor(np.asarray(shape, np.int64), "shape")
        ctx.emit("Expand", [cur, tgt], outs)
    elif prim == "reduce_sum":
        axes = ctx.init_tensor(np.asarray(p["axes"], np.int64), "axes")
        ctx.emit("ReduceSum", [ins[0], axes], outs, {"keepdims": 0})
    elif prim in ("reduce_max", "reduce_min", "reduce_prod"):
        op = {"reduce_max": "ReduceMax", "reduce_min": "ReduceMin",
              "reduce_prod": "ReduceProd"}[prim]
        ctx.emit(op, ins, outs, {"axes": list(p["axes"]), "keepdims": 0})
    elif prim == "reduce_and":
        # bool all(): Cast -> ReduceMin -> Cast (opset-13 has no ReduceAnd)
        m1, m2 = ctx.fresh("c"), ctx.fresh("r")
        ctx.emit("Cast", ins, [m1], {"to": P.INT32})
        ctx.emit("ReduceMin", [m1], [m2],
                 {"axes": list(p["axes"]), "keepdims": 0})
        ctx.emit("Cast", [m2], outs, {"to": P.BOOL})
    elif prim == "convert_element_type":
        dt = P.NP_TO_ONNX.get(np.dtype(p["new_dtype"]))
        if dt is None:
            raise ValueError(f"ONNX export: no dtype for {p['new_dtype']}")
        ctx.emit("Cast", ins, outs, {"to": dt})
    elif prim == "select_n":
        if len(ins) != 3:
            raise ValueError("ONNX export: select_n with >2 cases")
        # select_n(pred, a, b) -> b where pred else a
        ctx.emit("Where", [ins[0], ins[2], ins[1]], outs)
    elif prim == "squeeze":
        axes = ctx.init_tensor(np.asarray(p["dimensions"], np.int64),
                               "axes")
        ctx.emit("Squeeze", [ins[0], axes], outs)
    elif prim == "concatenate":
        ctx.emit("Concat", ins, outs, {"axis": int(p["dimension"])})
    elif prim == "split":
        st = ctx.init_tensor(np.asarray(p["sizes"], np.int64), "split")
        ctx.emit("Split", [ins[0], st], outs, {"axis": int(p["axis"])})
    elif prim == "slice":
        if p.get("strides") and any(s != 1 for s in p["strides"]):
            steps = list(p["strides"])
        else:
            steps = [1] * len(p["start_indices"])
        starts = ctx.init_tensor(
            np.asarray(p["start_indices"], np.int64), "starts")
        ends = ctx.init_tensor(
            np.asarray(p["limit_indices"], np.int64), "ends")
        axes = ctx.init_tensor(
            np.arange(len(p["start_indices"]), dtype=np.int64), "axes")
        st = ctx.init_tensor(np.asarray(steps, np.int64), "steps")
        ctx.emit("Slice", [ins[0], starts, ends, axes, st], outs)
    elif prim == "pad":
        cfg = p["padding_config"]
        if any(i != 0 for _, _, i in cfg):
            raise ValueError("ONNX export: interior padding unsupported")
        pads = [lo for lo, _, _ in cfg] + [hi for _, hi, _ in cfg]
        pt = ctx.init_tensor(np.asarray(pads, np.int64), "pads")
        ctx.emit("Pad", [ins[0], pt, ins[1]], outs, {"mode": "constant"})
    elif prim == "clamp":
        # Clip needs scalars; Max(Min(x, hi), lo) is universal
        mid = ctx.fresh("clip")
        ctx.emit("Min", [ins[1], ins[2]], [mid])
        ctx.emit("Max", [mid, ins[0]], outs)
    elif prim == "iota":
        dt = np.dtype(p["dtype"])
        dim = p["dimension"]
        shape = p["shape"]
        ar = np.arange(shape[dim], dtype=dt)
        ar = np.broadcast_to(
            ar.reshape([-1 if i == dim else 1
                        for i in range(len(shape))]), shape)
        name = ctx.init_tensor(ar, "iota")
        ctx.emit("Identity", [name], outs)
    elif prim == "gather":
        _emit_gather(ctx, eqn, ins, outs)
    elif prim == "argmax":
        axes = p["axes"]
        if len(axes) != 1:
            raise ValueError("ONNX export: multi-axis argmax")
        mid = ctx.fresh("am")
        ctx.emit("ArgMax", ins, [mid],
                 {"axis": int(axes[0]), "keepdims": 0})
        dt = P.NP_TO_ONNX[np.dtype(p["index_dtype"])]
        ctx.emit("Cast", [mid], outs, {"to": dt})
    else:
        raise ValueError(f"ONNX export: unsupported primitive '{prim}'")


def _emit_gather(ctx, eqn, ins, outs):
    """lax.gather -> ONNX Gather for the embedding/take pattern:
    one collapsed slice dim indexed by the (squeezed) indices, full slices
    elsewhere."""
    p = eqn.params
    dn = p["dimension_numbers"]
    operand = eqn.invars[0].aval
    slice_sizes = tuple(p["slice_sizes"])
    if len(dn.start_index_map) != 1 or \
            dn.collapsed_slice_dims != dn.start_index_map:
        raise ValueError("ONNX export: general lax.gather unsupported")
    axis = dn.start_index_map[0]
    for d, s in enumerate(slice_sizes):
        want = 1 if d == axis else operand.shape[d]
        if s != want:
            raise ValueError("ONNX export: partial-slice gather")
    # indices usually carry a trailing length-1 coordinate dim: squeeze it.
    # Decide by rank arithmetic, not shape[-1]==1 — output rank is
    # batch-dims + offset-dims, so a coordinate dim is present exactly when
    # out.ndim == (idx.ndim - 1) + len(offset_dims); a data dim that merely
    # happens to be size 1 fails this and must NOT be squeezed.
    idx = eqn.invars[1].aval
    out_rank = eqn.outvars[0].aval.ndim
    has_coord_dim = (idx.shape and idx.shape[-1] == 1
                     and out_rank == (idx.ndim - 1) + len(dn.offset_dims))
    idx_in = ins[1]
    if has_coord_dim:
        ax = ctx.init_tensor(np.asarray([idx.ndim - 1], np.int64), "axes")
        mid = ctx.fresh("sq")
        ctx.emit("Squeeze", [idx_in, ax], [mid])
        idx_in = mid
    ctx.emit("Gather", [ins[0], idx_in], outs, {"axis": int(axis)})


_UNROLL_NODE_CAP = 20_000  # unrolled-scan size guard (nodes)

_CALL_PRIMS = ("pjit", "jit", "closed_call", "custom_jvp_call",
               "custom_vjp_call", "custom_vjp_call_jaxpr",
               "remat", "checkpoint")


def _est_nodes(jaxpr) -> int:
    """Recursive node-count estimate for the unroll cap: nested scans
    multiply by their trip count and call sub-jaxprs count at their true
    size, so a scan-of-scans cannot sneak under the guard as one eqn."""
    total = 0
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim == "scan":
            inner = getattr(eqn.params["jaxpr"], "jaxpr",
                            eqn.params["jaxpr"])
            total += int(eqn.params["length"]) * max(1, _est_nodes(inner))
        elif prim in _CALL_PRIMS:
            sub = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr") \
                or eqn.params.get("fun_jaxpr")
            if sub is not None:
                total += max(1, _est_nodes(getattr(sub, "jaxpr", sub)))
            else:
                total += 1
        else:
            total += 1
    return total


def _unroll_scan(ctx, env, eqn):
    """Inline a lax.scan by unrolling its body `length` times (static trip
    count — jax guarantees it).  Reference round-trips RNNs through ONNX
    (tests/onnx); the unrolled form is the most portable encoding (no Loop/
    Scan subgraph support required of the consumer) at the cost of model
    size, hence the node cap.  xs are sliced per step with a scalar Gather
    (drops axis 0), ys re-stacked with Unsqueeze+Concat; `reverse` scans
    iterate back-to-front but ys keep index order (lax semantics).
    """
    p = eqn.params
    closed = p["jaxpr"]
    inner = getattr(closed, "jaxpr", closed)
    nc, nk = p["num_consts"], p["num_carry"]
    length, reverse = int(p["length"]), bool(p["reverse"])
    est = length * max(1, _est_nodes(inner))
    if est > _UNROLL_NODE_CAP:
        raise ValueError(
            f"ONNX export: scan unroll would emit ~{est} nodes "
            f"(cap {_UNROLL_NODE_CAP}); shorten the sequence for export or "
            "export the per-layer variant (e.g. HeteroGPT)")
    if length == 0 and len(inner.outvars) > nk:
        # ys would need a zero-input Concat — invalid ONNX.  A 0-length
        # scan in an exported model is a degenerate trace; reject loudly.
        raise ValueError(
            "ONNX export: cannot unroll a length-0 scan with scan outputs "
            "(the empty ys has no ONNX encoding); trace with a non-empty "
            "sequence")
    const_names = [_name_of(ctx, env, v) for v in eqn.invars[:nc]]
    carries = [_name_of(ctx, env, v) for v in eqn.invars[nc:nc + nk]]
    xs_names = [_name_of(ctx, env, v) for v in eqn.invars[nc + nk:]]
    n_ys = len(inner.outvars) - nk
    ys_names: List[List] = [[] for _ in range(n_ys)]
    ax0 = ctx.init_tensor(np.asarray([0], np.int64), "axes")
    order = range(length - 1, -1, -1) if reverse else range(length)
    for t in order:
        # each iteration gets a FRESH env: the body's internal vars (same
        # jaxpr objects every iteration) must resolve to fresh node names,
        # or all iterations would write the same outputs
        body_env: Dict[int, str] = {}
        for iv, nm in zip(inner.invars[:nc], const_names):
            body_env[id(iv)] = nm
        for iv, cname in zip(inner.invars[nc:nc + nk], carries):
            body_env[id(iv)] = cname
        for iv, xname in zip(inner.invars[nc + nk:], xs_names):
            # 1-D index + Squeeze (not a 0-d index: scalar TensorProtos
            # don't survive every codec; [t] then squeeze is equivalent)
            idx = ctx.init_literal(np.asarray([t], np.int64))
            gat = ctx.fresh("xg")
            ctx.emit("Gather", [xname, idx], [gat], {"axis": 0})
            sl = ctx.fresh("xt")
            ctx.emit("Squeeze", [gat, ax0], [sl])
            body_env[id(iv)] = sl
        for cv, c in zip(inner.constvars, getattr(closed, "consts", [])):
            body_env[id(cv)] = ctx.init_literal(np.asarray(c))
        _emit_jaxpr(inner, ctx, body_env)
        carries = [_name_of(ctx, body_env, ov)
                   for ov in inner.outvars[:nk]]
        for j, ov in enumerate(inner.outvars[nk:]):
            ys_names[j].append((t, _name_of(ctx, body_env, ov)))
    for souter, name in zip(eqn.outvars[:nk], carries):
        env[id(souter)] = name
    if n_ys:
        for j, pairs in enumerate(ys_names):
            pairs.sort()  # ys keep index order even for reverse scans
            stacked = []
            for _, nm in pairs:
                u = ctx.fresh("yt")
                ctx.emit("Unsqueeze", [nm, ax0], [u])
                stacked.append(u)
            out_name = _name_of(ctx, env, eqn.outvars[nk + j])
            if len(stacked) == 1:
                ctx.emit("Identity", stacked, [out_name])
            else:
                ctx.emit("Concat", stacked, [out_name], {"axis": 0})


def _emit_jaxpr(jaxpr, ctx, env):
    """Emit every eqn of `jaxpr`: pjit/custom_jvp/closed_call sub-jaxprs
    are inlined with a FRESH scoped env per call site (jax caches traces,
    so two calls of one jitted helper share the same sub-jaxpr objects — a
    shared env would make the second call overwrite the first call's
    output names and silently miscompute), and scan bodies are unrolled
    the same way (fresh env per iteration)."""
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim == "scan":
            _unroll_scan(ctx, env, eqn)
            continue
        if prim in _CALL_PRIMS:
            sub = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr") \
                or eqn.params.get("fun_jaxpr")
            if sub is None:
                raise ValueError(f"ONNX export: opaque call '{prim}'")
            consts = getattr(sub, "consts", [])
            inner = getattr(sub, "jaxpr", sub)
            sub_env: Dict[int, str] = {}
            for iv, ov in zip(inner.invars, eqn.invars):
                sub_env[id(iv)] = _name_of(ctx, env, ov)
            for cv, c in zip(inner.constvars, consts):
                sub_env[id(cv)] = ctx.init_tensor(np.asarray(c), "w")
            _emit_jaxpr(inner, ctx, sub_env)
            for souter, sinner in zip(eqn.outvars, inner.outvars):
                env[id(souter)] = _name_of(ctx, sub_env, sinner)
            continue
        ins = [_name_of(ctx, env, v) for v in eqn.invars]
        outs = [_name_of(ctx, env, v) for v in eqn.outvars]
        _emit_eqn(ctx, eqn, ins, outs)


def _name_of(ctx, env, var):
    from jax.extend.core import Literal
    if isinstance(var, Literal):
        return ctx.init_literal(np.asarray(var.val))
    key = id(var)
    if key not in env:
        env[key] = ctx.fresh("v")
    return env[key]


def jaxpr_to_onnx(fn, *example_args, graph_name="hetu_tpu") -> bytes:
    """Trace `fn` and lower the jaxpr to ONNX model bytes (opset 13)."""
    import jax

    closed = jax.make_jaxpr(fn)(*example_args)
    jaxpr = closed.jaxpr
    try:
        # make_jaxpr does not DCE: inference traces often carry dead
        # training-only machinery (threaded-but-unused PRNG keys inside
        # scan bodies, etc.) whose primitives have no ONNX lowering.
        # dce_jaxpr prunes them — including inside scan params.
        from jax._src.interpreters.partial_eval import dce_jaxpr

        # instantiate=True keeps ALL invars so the ONNX graph signature
        # still matches example_args even when an arg is unused
        jaxpr, _ = dce_jaxpr(jaxpr, [True] * len(jaxpr.outvars),
                             instantiate=True)
    except Exception:
        pass  # private API moved: export the un-DCE'd jaxpr as before
    ctx = _Ctx()
    env: Dict[int, str] = {}

    graph_inputs = []
    for v in jaxpr.invars:
        name = _name_of(ctx, env, v)
        dt = P.NP_TO_ONNX.get(np.dtype(v.aval.dtype))
        if dt is None:
            raise ValueError(f"ONNX export: input dtype {v.aval.dtype}")
        graph_inputs.append(P.value_info_proto(name, dt,
                                               list(v.aval.shape)))
    for cv, c in zip(jaxpr.constvars, closed.consts):
        env[id(cv)] = ctx.init_tensor(np.asarray(c), "w")

    _emit_jaxpr(jaxpr, ctx, env)

    graph_outputs = []
    for v in jaxpr.outvars:
        name = _name_of(ctx, env, v)
        aval = getattr(v, "aval", None)
        dt = P.NP_TO_ONNX.get(np.dtype(aval.dtype)) if aval is not None \
            else P.FLOAT
        shape = list(aval.shape) if aval is not None else []
        graph_outputs.append(P.value_info_proto(name, dt, shape))

    graph = P.graph_proto(ctx.nodes, graph_name, ctx.initializers,
                          graph_inputs, graph_outputs)
    return P.model_proto(graph)
