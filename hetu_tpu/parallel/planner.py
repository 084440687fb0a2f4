"""Sharding plan analysis: what collectives did SPMD actually insert?

Reference: GraphStatus.assign_context_by_traverse_nodes (context.py:1469)
decides explicitly where AllReduce/AllGather/ReduceScatter/Send/Recv ops go,
and cross_send/cross_receive (context.py:1640-1826) price generic re-splits.

TPU inversion of control: XLA's SPMD partitioner makes those decisions from
the sharding annotations, so the planner's job flips from *inserting* comm
ops to *auditing* them — lower the jitted step under a candidate sharding,
extract the collectives XLA inserted (with byte counts), and price the plan
with the simulator's cost model.  This closes the loop the reference closed
with HetuSimulator.get_general_comm_time: searchers propose shardings,
the audit verifies what they actually cost.
"""

from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import numpy as np

from hetu_tpu.profiler.cost_model import (
    ChipSpec, allgather_time, allreduce_time, alltoall_time, detect_chip,
    p2p_time,
)

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
                "u8": 1, "f64": 8, "s64": 8, "u64": 8, "pred": 1, "s16": 2,
                "u16": 2, "f8e4m3fn": 1, "f8e5m2": 1}


@dataclass
class CollectiveInfo:
    kind: str
    dtype: str
    shape: tuple
    bytes: int
    count: int = 1


@dataclass
class PlanAudit:
    collectives: List[CollectiveInfo] = field(default_factory=list)
    flops: float = 0.0
    bytes_accessed: float = 0.0

    def total_comm_bytes(self) -> int:
        return sum(c.bytes * c.count for c in self.collectives)

    def scaled(self, kind_multipliers: Dict[str, int]) -> "PlanAudit":
        """Scale per-kind counts by known loop trip counts (collectives in
        while/scan bodies appear once in HLO text)."""
        out = PlanAudit(flops=self.flops, bytes_accessed=self.bytes_accessed)
        out.collectives = [
            CollectiveInfo(c.kind, c.dtype, c.shape, c.bytes,
                           c.count * kind_multipliers.get(c.kind, 1))
            for c in self.collectives]
        return out

    def by_kind(self) -> Dict[str, int]:
        out: Dict[str, int] = defaultdict(int)
        for c in self.collectives:
            out[c.kind] += c.bytes * c.count
        return dict(out)

    def estimate_time(self, chip: Optional[ChipSpec] = None,
                      n_devices: int = 8) -> float:
        """Roofline step-time estimate: compute + comm (no overlap)."""
        chip = chip or detect_chip()
        t = self.flops / (chip.bf16_flops * chip.mxu_util)
        t = max(t, self.bytes_accessed / chip.hbm_bw)
        for c in self.collectives:
            nbytes = c.bytes * c.count
            if c.kind == "all-reduce":
                t += allreduce_time(chip, nbytes, n_devices)
            elif c.kind in ("all-gather", "reduce-scatter"):
                t += allgather_time(chip, nbytes, n_devices)
            elif c.kind == "all-to-all":
                t += alltoall_time(chip, nbytes, n_devices)
            else:  # collective-permute
                t += p2p_time(chip, nbytes)
        return t


# op name with optional async suffix; '-done' halves of start/done pairs are
# skipped so async collectives (the TPU default) are not double-counted
_KIND_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(-start|-done)?\(")
_FIRST_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([\d,]*)\]")


def audit(fn, *args, static_argnums=(), donate_argnums=()) -> PlanAudit:
    """Lower fn(*args) (args carry their shardings) and audit the result.

    Caveat: collectives inside while/scan bodies (e.g. the GPipe tick loop)
    are counted once, not per trip — scale those by the known trip count
    when comparing pipelined plans (PlanAudit.scaled()).
    """
    jfn = jax.jit(fn, static_argnums=static_argnums,
                  donate_argnums=donate_argnums)
    lowered = jfn.lower(*args)
    compiled = lowered.compile()
    txt = compiled.as_text()

    result = PlanAudit()
    agg: Dict[tuple, CollectiveInfo] = {}
    for line in txt.splitlines():
        line = line.strip()
        km = _KIND_RE.search(line)
        if not km or km.group(2) == "-done":
            continue
        kind = km.group(1)
        # result shape = first dtype[dims] on the line (for tuple results of
        # async starts this is the first element, which is the payload)
        sm = _FIRST_SHAPE_RE.search(line)
        if not sm:
            continue
        dtype, dims = sm.group(1), sm.group(2)
        shape = tuple(int(d) for d in dims.split(",") if d) if dims else ()
        nbytes = int(np.prod(shape, dtype=np.int64)) * _DTYPE_BYTES.get(
            dtype, 4) if shape else _DTYPE_BYTES.get(dtype, 4)
        key = (kind, dtype, shape)
        if key in agg:
            agg[key].count += 1
        else:
            agg[key] = CollectiveInfo(kind, dtype, shape, nbytes)
    result.collectives = list(agg.values())

    cost = compiled.cost_analysis()
    if cost:
        c = cost[0] if isinstance(cost, (list, tuple)) else cost
        result.flops = float(c.get("flops", 0.0))
        result.bytes_accessed = float(c.get("bytes accessed", 0.0))
    return result


_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%(?P<name>[\w.\-]+)\s*=\s*.*?\s"
    r"(?P<op>[a-z][a-z\-]*)\((?P<args>[^)]*)\)")
_MATMUL_RE = re.compile(r"\s(?:convolution|dot)\(")
_COMPUTATION_RE = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")


def gathers_feeding(hlo_text: str, target: str = "tpu_custom_call") -> list:
    """All-gathers whose result reaches an operand of a ``target`` custom
    call in compiled HLO text, returned as instruction lines.

    The question a sharded kernel call must answer: did the partitioner
    gather the batch or the heads back onto every device in front of it?
    The walk goes up the def-use chain from each call's operands inside its
    computation, through layout ops and matmul-free fusions, and stops at
    the producing matmul, at parameters and at control flow — what lies
    beyond the projection that made q/k/v is not "in front of" the call.
    """
    comps: Dict[str, Dict[str, tuple]] = {}
    cur = None
    for line in hlo_text.splitlines():
        header = _COMPUTATION_RE.match(line)  # unindented; instructions are
        if header:
            cur = comps.setdefault(header.group(1), {})
        elif cur is not None and (m := _INSTR_RE.match(line)):
            cur[m.group("name")] = (
                m.group("op"), re.findall(r"%([\w.\-]+)", m.group("args")),
                line.strip())
    has_matmul = {name: any(_MATMUL_RE.search(i[2]) for i in body.values())
                  for name, body in comps.items()}

    found = []
    for body in comps.values():
        calls = [i for i in body.values()
                 if i[0] == "custom-call" and f'"{target}"' in i[2]]
        todo = [o for _, ops, _ in calls for o in ops]
        seen = set()
        while todo:
            name = todo.pop()
            if name in seen or name not in body:
                continue
            seen.add(name)
            op, ops, line = body[name]
            if op.startswith("all-gather"):
                found.append(line)
            called = re.search(r"calls=%([\w.\-]+)", line)
            if op in ("convolution", "dot", "parameter", "while", "call",
                      "conditional", "custom-call") or (
                    called and has_matmul.get(called.group(1))):
                continue
            todo.extend(ops)
    return found


def verify_spec_transition(mesh, shape, src, dst, dtype=None):
    """Assert XLA realizes a src→dst ShardSpec transition with the collective
    the NodeStatus algebra predicts (spec.predict_collective).

    This is the executable bridge between the reference's pattern checks
    (context.py:769-783) and GSPMD: we build the minimal program whose
    producer has spec `src` (partial specs are produced authentically, by a
    matmul whose contraction dim is sharded over the partial axes) and whose
    consumer demands `dst`, audit the compiled HLO, and compare.

    Returns (predicted_kind, audited_kinds).  Raises AssertionError on
    mismatch — a failing searcher/strategy would mis-price its plan.
    """
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from hetu_tpu.parallel.spec import predict_collective

    dtype = dtype or jnp.float32
    pred = predict_collective(src, dst)
    dst_sh = NamedSharding(mesh, dst.pspec())

    if src.partial:
        # authentic partial producer: y = x @ w with the contraction dim
        # sharded over the partial axes — each device holds a partial sum
        k = 8 * int(np.prod([mesh.shape[a] for a in src.partial]))
        x = jnp.ones((shape[0], k), dtype)
        w = jnp.ones((k,) + tuple(shape[1:]), dtype)
        x = jax.device_put(x, NamedSharding(mesh, P(src.dims[0],
                                                    src.partial)))
        w = jax.device_put(w, NamedSharding(mesh, P(src.partial,
                                                    *src.dims[1:])))

        def prog(x, w):
            return jax.lax.with_sharding_constraint(x @ w, dst_sh)

        a = audit(prog, x, w)
    else:
        x = jax.device_put(jnp.ones(shape, dtype),
                           NamedSharding(mesh, src.pspec()))

        def prog(x):
            return jax.lax.with_sharding_constraint(x * 2, dst_sh)

        a = audit(prog, x)

    audited = sorted({c.kind for c in a.collectives})
    if pred is None:
        assert audited in ([], ["collective-permute"]), (
            f"algebra predicts a local transition but XLA inserted "
            f"{audited}")
        return None, audited
    kind = pred[0]
    # GSPMD may realize a reduce-scatter as all-reduce + local slice (it
    # does on the CPU backend); that is the same pattern priced pessimally,
    # so accept the superset collective for the RS check
    ok = {kind} | ({"all-reduce"} if kind == "reduce-scatter" else set())
    assert ok & set(audited), (
        f"algebra predicts {kind} for {src}→{dst} but XLA inserted "
        f"{audited or 'nothing'}")
    return kind, audited


def report(audit_result: PlanAudit, *, chip: Optional[ChipSpec] = None,
           n_devices: int = 8) -> str:
    lines = [f"flops/step:        {audit_result.flops:.3e}",
             f"hbm bytes/step:    {audit_result.bytes_accessed:.3e}",
             f"comm bytes/step:   {audit_result.total_comm_bytes():.3e}"]
    for kind, nbytes in sorted(audit_result.by_kind().items()):
        lines.append(f"  {kind:<20} {nbytes:.3e} B")
    lines.append(f"est step time:     "
                 f"{audit_result.estimate_time(chip, n_devices) * 1e3:.2f} ms")
    return "\n".join(lines)
