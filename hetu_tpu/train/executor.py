"""Executor — the compiled training engine.

Reference: python/hetu/gpu_ops/executor.py (1,648 LoC): `HetuConfig` decides
the comm mode and builds streams/communicators, `Executor` holds named
subexecutors ('train'/'validate'), `SubExecutor` topo-sorts, infers shapes,
plans memory, and runs the per-op compute loop with event-synced streams
(:1191-1246); `gradients()` (:1265) is reverse-mode autodiff over the graph.

TPU translation: the entire SubExecutor machinery — topo order, shape
inference, memory planning, stream routing, event sync — IS `jax.jit`: the
step function traces once to a jaxpr (the dataflow graph), XLA plans memory
(the BFC-allocator analog), schedules, and overlaps collectives with compute
(the nccl-stream analog).  What remains ours:

  * named subexecutors  → one cached compiled function per name
    ('train'/'validate'), sharing parameter state;
  * comm-mode decision  → a Mesh + shardings instead of PS/AllReduce wiring:
    with batch sharded over 'dp' and params replicated, XLA inserts the
    gradient psum exactly where the reference placed AllReduceCommunicateOps;
  * buffer donation     → state is donated so parameters update in place
    (the memory_pool.py reuse-plan analog).

`gradients()` is kept as an API-parity wrapper over jax.grad.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from hetu_tpu import rng as hrng
from hetu_tpu.optim.optimizer import Optimizer
from hetu_tpu.parallel.mesh import AXIS_DP, mesh_context
from hetu_tpu.telemetry import trace

# span names cached per subexecutor: the disabled-tracing hot path must
# not even pay the f-string allocation
_STEP_SPAN: Dict[str, str] = {}


def gradients(loss_fn: Callable, argnums=0, has_aux: bool = False):
    """API-parity wrapper for the reference's `ht.gradients`
    (executor.py:1265); reverse-mode autodiff of a scalar loss."""
    return jax.grad(loss_fn, argnums=argnums, has_aux=has_aux)


def async_collective_options(mesh: Optional[Mesh]) -> Dict[str, bool]:
    """Compiler options for a train step's executable on ``mesh``: on
    several TPU devices XLA:TPU starts each all-reduce asynchronously and
    fuses the matmuls that do not read its result between the start and the
    done, so a backward input-gradient all-reduce runs under the same
    layer's weight-gradient matmuls.  Empty for anything else.  The options
    belong to that one executable and its compile-cache key; nothing in the
    process reads them."""
    if mesh is None or mesh.size < 2 \
            or mesh.devices.flat[0].platform != "tpu":
        return {}
    return {"xla_enable_async_all_reduce": True,
            "xla_tpu_enable_async_collective_fusion_fuse_all_reduce": True}


@jax.tree_util.register_pytree_node_class
@dataclass
class TrainState:
    """Carried training state: params + optimizer slots + module state + rng.

    The analog of the reference executor's placeholder_to_arr_map (params),
    optimizer internal arrays, and the (seed, seqnum) RNG — all explicit and
    donate-able.
    """

    params: Any
    opt_state: Any
    model_state: Any
    rng: jax.Array
    step: jax.Array

    def tree_flatten(self):
        return ((self.params, self.opt_state, self.model_state, self.rng,
                 self.step), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


class Executor:
    """Named compiled subexecutors over one shared TrainState.

    loss_fn(params, model_state, batch, rng, train) ->
        (loss, (metrics_dict, new_model_state))

    A metric that is itself a dict of scalars is a GROUP of per-step ids
    (an expert model's counts under ``"moe"``): besides being returned with
    the rest, each train step's groups go onto a ``train.<group>`` instant
    (ids ``step`` and the group's own) when a later ``run`` finds them
    finished.  ``run`` never waits for them: a group still being computed
    stays queued.

    Usage:
        ex = Executor(loss_fn, optimizer, mesh=mesh)
        state = ex.init_state(variables)
        state, metrics = ex.run('train', state, batch)
        metrics = ex.run('validate', state, batch)
    """

    def __init__(self, loss_fn: Callable, optimizer: Optional[Optimizer] = None,
                 *, mesh: Optional[Mesh] = None, dp_axis: str = AXIS_DP,
                 param_sharding=None, dist_strategy=None,
                 grad_sync: object = "exact", grad_sync_block: int = 256,
                 seed: Optional[int] = None):
        """dist_strategy: a parallel.strategies.Strategy — init_state places
        params (and mirrored optimizer slots) per its specs, the reference's
        `Executor(..., dist_strategy=...)` ergonomics.

        grad_sync selects how data-parallel gradients synchronize:
        "exact" (default) leaves the psum to XLA/SPMD; "int8"/"bf16" run
        the gradient allreduce through
        ``parallel.collectives.quantized_psum`` (EQuARX-style block-scaled
        wire) under an explicit shard_map over ``dp_axis`` — or pass a
        callable ``path_str -> wire`` to choose PER PARAMETER (e.g. int8
        for the bulky matmul weights, exact f32 for layernorm scales).
        Quantized sync needs a mesh, a batch sharded on dim 0, and a
        loss_fn that is per-shard pure (no cross-dp collectives of its
        own — the executor owns the dp sync).  Wire-vs-logical bytes per
        step land on the ``train.grad_sync.bytes_*`` telemetry counters;
        ``grad_sync_block`` is the int8 block size (one f32 scale per
        block)."""
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.mesh = mesh
        self.dp_axis = dp_axis
        self.param_sharding = param_sharding  # pytree of NamedSharding, optional
        self.dist_strategy = dist_strategy
        if dist_strategy is not None and mesh is None:
            raise ValueError("dist_strategy requires a mesh")
        if isinstance(grad_sync, str) and grad_sync not in (
                "exact", "f32", "bf16", "int8"):
            raise ValueError(f"unknown grad_sync {grad_sync!r}; expected "
                             f"'exact'/'f32'/'bf16'/'int8' or a callable")
        self.grad_sync = grad_sync
        self.grad_sync_block = int(grad_sync_block)
        if self._quant_sync():
            if mesh is None:
                raise ValueError("quantized grad_sync requires a mesh")
            if dist_strategy is not None or param_sharding is not None:
                # _quant_grad_step's shard_map declares params replicated
                # (in_specs=P()); running it over sharded params would
                # all-gather the full parameter set on every device each
                # step and, with check_vma off, silently produce wrong
                # gradients for a loss_fn doing its own model-axis
                # collectives — refuse loudly instead
                raise ValueError(
                    "quantized grad_sync supports replicated parameters "
                    "only (plain data parallelism); it cannot combine "
                    "with dist_strategy/param_sharding")
        self._grad_sync_bytes = None  # (logical, wire) per step, lazy
        if seed is not None:
            hrng.set_random_seed(seed)
        # constant baked into the traced step: an elastic shrink at fixed
        # per-worker batch rescales gradients by nominal/current width so a
        # sum-over-nominal-global-batch loss keeps its scale (set via
        # set_grad_scale, which retraces)
        self.grad_scale = 1.0
        self._compiled: Dict[str, Callable] = {}
        # (step number, {group: {id: scalar array}}) of train steps issued
        # whose groups have not been put on their instants yet
        self._groups: list = []
        self._steps_issued = 0

    # ---- elastic resharding support (resilience/elastic.py) ----
    def set_mesh(self, mesh: Optional[Mesh]) -> None:
        """Point the executor at a (re)formed mesh and drop every compiled
        executable — shardings are baked into the jitted steps at trace
        time, so a mesh change REQUIRES a retrace.  The caller re-places
        the live TrainState itself (jax.device_put under the new mesh's
        shardings) before the next run()."""
        self.mesh = mesh
        self._compiled.clear()

    def set_grad_scale(self, scale: float) -> None:
        """Change the gradient rescale constant (traced in, so this drops
        the compiled steps).  No-op when the scale is unchanged."""
        if float(scale) != self.grad_scale:
            self.grad_scale = float(scale)
            self._compiled.clear()

    # ---- state ----
    def init_state(self, variables: dict, rng_key=None) -> TrainState:
        params = variables["params"]
        model_state = variables.get("state", {})
        opt_state = (self.optimizer.init_state(params)
                     if self.optimizer is not None else {})
        rng_key = rng_key if rng_key is not None else hrng.next_key()
        # copy leaves: the train step donates its input state, which would
        # otherwise invalidate the caller's `variables`/rng buffers
        params, model_state, rng_key = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a).copy(), (params, model_state, rng_key))
        state = TrainState(params=params, opt_state=opt_state,
                           model_state=model_state, rng=rng_key,
                           step=jnp.zeros((), jnp.int32))
        if self.dist_strategy is not None:
            sh = self.dist_strategy.shardings(state.params, self.mesh)
            placed = jax.tree_util.tree_map(jax.device_put, state.params, sh)
            # slots get their own shardings: under ZeRO-1 they shard over dp
            # while the params they mirror stay replicated
            slot_sh = self.dist_strategy.slot_shardings(state.params,
                                                        self.mesh)
            slots = {k: jax.tree_util.tree_map(jax.device_put, v, slot_sh)
                     for k, v in state.opt_state.get("slots", {}).items()} \
                if isinstance(state.opt_state, dict) else {}
            # everything else replicated ON THE MESH, like the step's own
            # outputs: leaves left on the default device would change
            # sharding after the first step and compile the step twice
            rep = NamedSharding(self.mesh, P())
            opt_state2 = state.opt_state
            if isinstance(opt_state2, dict):
                opt_state2 = dict(jax.device_put(
                    {k: v for k, v in opt_state2.items() if k != "slots"},
                    rep), slots=slots)
            model_state, rng, step = jax.device_put(
                (state.model_state, state.rng, state.step), rep)
            state = TrainState(params=placed, opt_state=opt_state2,
                               model_state=model_state, rng=rng, step=step)
        elif self.mesh is not None:
            shard = (self.param_sharding if self.param_sharding is not None
                     else NamedSharding(self.mesh, P()))
            state = jax.device_put(state, shard) if not isinstance(
                shard, dict) else state
        return state

    # ---- quantized gradient sync (parallel/collectives.quantized_psum) --
    def _quant_sync(self) -> bool:
        return callable(self.grad_sync) or self.grad_sync in ("int8",
                                                              "bf16")

    def _wire_for(self, path_str: str) -> str:
        gs = self.grad_sync
        return gs(path_str) if callable(gs) else gs

    def _quant_grad_step(self, state: TrainState, batch, step_rng):
        """Per-shard grads + explicit quantized dp allreduce.

        Under plain pjit the dp gradient psum belongs to XLA and cannot
        be intercepted; shard_map makes the sync OURS: the loss runs on
        each dp shard's local batch, then every gradient leaf crosses
        the wire in its selected dtype (quantized_pmean) while loss and
        float metrics pmean exactly.  check_vma=False: a quantized
        allreduce is device-identical but not PROVABLY replicated to the
        varying-axes checker.

        Reduction semantics vs the exact path (where loss_fn sees the
        GLOBAL batch): float metrics pmean over dp, integer metrics
        psum (count semantics — a per-shard correct-prediction count
        sums to the global one); model_state floats pmean, model_state
        non-floats are NOT reduced (shard 0's value wins) — per-call
        counters there would double-count under a sum, so keep
        non-float state per-shard-invariant when using quantized
        grad_sync."""
        from jax.tree_util import tree_map, tree_map_with_path

        from hetu_tpu.parallel.collectives import (
            quantized_pmean, shard_map,
        )
        dp = self.dp_axis
        block = self.grad_sync_block

        def local(params, model_state, batch, rng):
            def lf(p):
                return self.loss_fn(p, model_state, batch, rng, True)
            (loss, (metrics, nms)), g = jax.value_and_grad(
                lf, has_aux=True)(params)
            g = tree_map_with_path(
                lambda pth, leaf: quantized_pmean(
                    leaf, dp, wire=self._wire_for(jax.tree_util.keystr(pth)),
                    block=block), g)

            def red_metric(v):
                dt = jnp.result_type(v)
                if jnp.issubdtype(dt, jnp.inexact):
                    return jax.lax.pmean(v, dp)
                if jnp.issubdtype(dt, jnp.integer):
                    return jax.lax.psum(v, dp)
                return v
            pm = lambda v: (jax.lax.pmean(v, dp)  # noqa: E731
                            if jnp.issubdtype(jnp.result_type(v),
                                              jnp.inexact) else v)
            return (jax.lax.pmean(loss, dp), tree_map(red_metric, metrics),
                    tree_map(pm, nms), g)

        from jax.sharding import PartitionSpec as _P
        f = shard_map(local, mesh=self.mesh,
                      in_specs=(_P(), _P(), _P(dp), _P()),
                      out_specs=(_P(), _P(), _P(), _P()),
                      check_vma=False)
        return f(state.params, state.model_state, batch, step_rng)

    # ---- step builders ----
    def _train_step(self, state: TrainState, batch):
        step_rng = jax.random.fold_in(state.rng, state.step)
        def lf(params):
            return self.loss_fn(params, state.model_state, batch, step_rng,
                                True)
        if self._quant_sync():
            loss, metrics, new_model_state, grads = self._quant_grad_step(
                state, batch, step_rng)
        else:
            (loss, (metrics, new_model_state)), grads = jax.value_and_grad(
                lf, has_aux=True)(state.params)
        if self.grad_scale != 1.0:
            s = self.grad_scale
            grads = jax.tree_util.tree_map(lambda g: g * s, grads)
        params, opt_state = self.optimizer.update(grads, state.opt_state,
                                                  state.params)
        new_state = TrainState(params=params, opt_state=opt_state,
                               model_state=new_model_state, rng=state.rng,
                               step=state.step + 1)
        metrics = dict(metrics)
        metrics["loss"] = loss
        return new_state, metrics

    def _train_step_guarded(self, state: TrainState, batch):
        """Train step with an in-graph nonfinite guard (resilience tier).

        A poisoned batch or an exploding update yields NaN/Inf loss or
        params; this variant keeps the PRE-step params/opt/model state in
        that case (jnp.where select — a few elementwise reductions, cheap
        next to the step itself) and reports ``metrics['nonfinite']`` so
        the supervisor can count-and-abort.  The step counter and RNG still
        advance on a skipped step, so training moves PAST the poisoned
        batch instead of retrying it forever.
        """
        new_state, metrics = self._train_step(state, batch)
        ok = jnp.isfinite(metrics["loss"])
        for leaf in jax.tree_util.tree_leaves(new_state.params):
            if jnp.issubdtype(leaf.dtype, jnp.floating):
                ok &= jnp.all(jnp.isfinite(leaf))
        keep = lambda n, o: jnp.where(ok, n, o)  # noqa: E731
        guarded = TrainState(
            params=jax.tree_util.tree_map(keep, new_state.params,
                                          state.params),
            opt_state=jax.tree_util.tree_map(keep, new_state.opt_state,
                                             state.opt_state),
            model_state=jax.tree_util.tree_map(keep, new_state.model_state,
                                               state.model_state),
            rng=new_state.rng, step=new_state.step)
        metrics = dict(metrics)
        metrics["nonfinite"] = (~ok).astype(jnp.int32)
        return guarded, metrics

    def _eval_step(self, state: TrainState, batch):
        loss, (metrics, _) = self.loss_fn(state.params, state.model_state,
                                          batch, state.rng, False)
        metrics = dict(metrics)
        metrics["loss"] = loss
        return metrics

    def _compile(self, name: str):
        if name in ("train", "train_guarded"):
            if self.optimizer is None:
                raise ValueError(f"{name} subexecutor needs an optimizer")
            fn = (self._train_step_guarded if name == "train_guarded"
                  else self._train_step)
            donate = (0,)
            options = async_collective_options(self.mesh)
        elif name in ("validate", "eval", "test"):
            fn, donate, options = self._eval_step, (), {}
        else:
            raise KeyError(f"unknown subexecutor {name!r}")
        kwargs = {}
        if self.mesh is not None:
            # batch sharded over dp; everything else left to XLA/SPMD
            kwargs["in_shardings"] = (
                None, NamedSharding(self.mesh, P(self.dp_axis)))
        if options:
            kwargs["compiler_options"] = options
        return jax.jit(fn, donate_argnums=donate, **kwargs)

    def run(self, name: str, state: TrainState, batch):
        """Reference analog: Executor.run('train', feed_dict)
        (executor.py:524)."""
        if name not in self._compiled:
            trace.instant("train.compile", {"subexecutor": name})
            self._compiled[name] = self._compile(name)
        if self._quant_sync() and name in ("train", "train_guarded"):
            self._record_grad_sync_bytes(state)
        if self._groups:
            self._emit_finished_groups()
        with trace.span("train.host_to_device"):
            batch = _device_batch(batch, self.mesh, self.dp_axis)
        sname = _STEP_SPAN.get(name)
        if sname is None:
            sname = _STEP_SPAN.setdefault(name, "train.step." + name)
        # ``step``: the number this launch's train step gets below, the one
        # its ``train.<group>`` instants carry
        with trace.span(sname, {"step": self._steps_issued + 1}), \
                mesh_context(self.mesh):
            out = self._compiled[name](state, batch)
            if trace.enabled():
                # jit dispatch is async: without a sync the span times the
                # ~µs enqueue and the real step cost lands in whatever
                # phase fetches a value next.  Only a TRACED run pays this
                # barrier — tracing off keeps the async pipeline.
                jax.block_until_ready(out)
        if name in ("train", "train_guarded"):
            self._steps_issued += 1
            groups = {k: v for k, v in out[1].items() if isinstance(v, dict)}
            if groups:
                self._groups.append((self._steps_issued, groups))
        return out

    def _emit_finished_groups(self) -> None:
        """Put the queued steps' metric groups on their instants, oldest
        first, as far as they are finished; the first one still being
        computed ends the pass (no wait: ``is_ready`` only asks)."""
        while self._groups:
            step, groups = self._groups[0]
            leaves = jax.tree_util.tree_leaves(groups)
            if not all(a.is_ready() for a in leaves):
                return
            self._groups.pop(0)
            for group, ids in jax.device_get(groups).items():
                trace.instant("train." + group, {
                    "step": step, **{k: v.item() for k, v in ids.items()}})

    def lower(self, name: str, state: TrainState, batch):
        """The named subexecutor lowered for ``(state, batch)`` — a
        ``jax.stages.Lowered`` to inspect or ``.compile()``.  Runs nothing
        and donates nothing."""
        if name not in self._compiled:
            self._compiled[name] = self._compile(name)
        with mesh_context(self.mesh):
            return self._compiled[name].lower(
                state, _device_batch(batch, self.mesh, self.dp_axis))

    def _record_grad_sync_bytes(self, state: TrainState) -> None:
        """Fold one step's gradient-sync traffic into the shared
        ``train.grad_sync.bytes_logical``/``.bytes_wire`` counter pair.
        Sizes are static per model, so they compute once; the per-step
        cost is two counter increments."""
        from hetu_tpu.quantwire import block_wire_bytes, record_wire_bytes
        if self._grad_sync_bytes is None:
            logical = wire = 0
            for pth, leaf in jax.tree_util.tree_leaves_with_path(
                    state.params):
                w = self._wire_for(jax.tree_util.keystr(pth))
                n = int(leaf.size)
                logical += n * 4
                wire += block_wire_bytes(
                    n, "f32" if w == "exact" else w, self.grad_sync_block)
            self._grad_sync_bytes = (logical, wire)
        record_wire_bytes("train.grad_sync", *self._grad_sync_bytes)

    def save(self, path, state: TrainState, *, extra=None) -> None:
        """Reference-parity convenience (executor.py:558): checkpoint the
        full TrainState incl. (seed, seqnum) RNG."""
        from hetu_tpu.train import checkpoint
        checkpoint.save(path, state, extra=extra)

    def load(self, path, state_template: TrainState) -> TrainState:
        """Restore into the template's structure/shardings (executor.py:630
        load_dict(consider_splits=True) analog — re-sharding is device_put)."""
        from hetu_tpu.train import checkpoint
        return checkpoint.load(path, state_template)

    def profile(self, state: TrainState, batch, *, name: str = "train",
                k1: int = 3, k2: int = 9):
        """Per-step timing + compiled cost/collective breakdown.

        Reference analog: TimerSubExecutor (`Executor(timing=...)`,
        timer_subexecutor.py) + HetuProfiler — here one call returns the
        slope-timed step wall time (two chained runs, each ended by a value
        fetch) and XLA's own cost analysis with the collectives the
        partitioner inserted (parallel/planner.py audit).
        Note: does NOT mutate `state` (runs on copies).
        """
        import time as _time

        from hetu_tpu.parallel.planner import audit

        if name != "train":
            raise ValueError("profile supports the train subexecutor")
        if name not in self._compiled:
            self._compiled[name] = self._compile(name)
        batch = _device_batch(batch, self.mesh, self.dp_axis)
        # private copy: the compiled step donates its input state
        s0 = jax.tree_util.tree_map(lambda a: jnp.asarray(a).copy(), state)

        def run_k(s, k):
            m = None
            for _ in range(k):
                s, m = self._compiled[name](s, batch)
            float(m["loss"])  # value fetch = true sync
            return s

        with mesh_context(self.mesh):
            s = run_k(s0, 2)  # warmup
            t0 = _time.perf_counter()
            s = run_k(s, k1)
            t1 = _time.perf_counter()
            s = run_k(s, k2)
            t2 = _time.perf_counter()
            per_step = max(((t2 - t1) - (t1 - t0)) / (k2 - k1), 1e-9)

            # audit only lowers/compiles (no execution, no donation): the
            # caller's state is safe to pass directly
            a = audit(self._train_step, state, batch)
        return {
            "per_step_s": per_step,
            "steps_per_s": 1.0 / per_step,
            "flops": a.flops,
            "hbm_bytes": a.bytes_accessed,
            "comm_bytes_by_kind": a.by_kind(),
        }


def _device_batch(batch, mesh, dp_axis):
    if mesh is None:
        return batch
    dp = mesh.shape[dp_axis]
    sh = NamedSharding(mesh, P(dp_axis))

    def put(a):
        if a.shape[0] % dp != 0:
            raise ValueError(
                f"global batch dim {a.shape[0]} not divisible by dp={dp}; "
                f"pad or drop the remainder (Dataloader(drop_last=True))")
        return jax.device_put(a, sh)

    return jax.tree_util.tree_map(put, batch)
