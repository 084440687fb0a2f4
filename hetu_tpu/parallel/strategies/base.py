"""Strategy base: maps a model's parameter tree to shardings over a mesh.

Reference: python/hetu/distributed_strategies/base.py:13 (`Strategy`): cluster
settings + per-node NodeStatus assignment + JSON save/load of per-layer
{splits, duplicate, partial, order, device} (:158-227).

TPU translation: a Strategy produces a pytree of PartitionSpec matching the
parameter tree (+ the batch spec), which the Executor materializes as
NamedShardings.  JSON round-trip keeps the same role as the reference's
strategy files: a searcher emits one, a run loads it.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _fit(spec: P, leaf, mesh: Mesh) -> NamedSharding:
    """Drop spec entries whose mesh-axis product does not divide the dim."""
    dims = []
    for i, entry in enumerate(spec):
        if i >= leaf.ndim:
            break  # truncate over-long specs (NamedSharding rejects
                   # len(spec) > rank even with trailing Nones)
        if entry is None:
            dims.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        k = 1
        for a in axes:
            k *= mesh.shape[a]
        dims.append(entry if leaf.shape[i] % k == 0 else None)
    # canonical form, as jit writes its outputs' specs: no trailing Nones.
    # P('tp', None) and P('tp') shard alike but key jit's cache apart, so a
    # state placed with the long form compiles its step twice.
    while dims and dims[-1] is None:
        dims.pop()
    return NamedSharding(mesh, P(*dims))


class Strategy:
    """Assign PartitionSpecs to parameters by tree-path pattern."""

    def param_spec(self, path: str, leaf) -> P:
        """Override: spec for one parameter, by its tree path string."""
        return P()

    def slot_spec(self, path: str, leaf) -> P:
        """Spec for one optimizer slot — defaults to the param's spec.
        Override for ZeRO-1 style layouts where slots shard over dp while
        params stay replicated."""
        return self.param_spec(path, leaf)

    def batch_spec(self) -> P:
        return P("dp")

    # ---- tree-level API ----
    def param_specs(self, params) -> Any:
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        specs = [self.param_spec(jax.tree_util.keystr(path), leaf)
                 for path, leaf in flat]
        return jax.tree_util.tree_unflatten(treedef, specs)

    def shardings(self, params, mesh: Mesh) -> Any:
        """Materialize NamedShardings; dims whose size does not divide the
        assigned axis product fall back to replication (the reference
        requires divisible splits — we degrade gracefully instead, e.g. a
        10-class FC head under tp=4)."""
        return jax.tree_util.tree_map(
            lambda spec, leaf: _fit(spec, leaf, mesh),
            self.param_specs(params), params,
            is_leaf=lambda x: isinstance(x, P))

    def slot_shardings(self, params, mesh: Mesh) -> Any:
        """NamedShardings for optimizer slots (one tree, reused per slot)."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        shs = [_fit(self.slot_spec(jax.tree_util.keystr(path), leaf), leaf,
                    mesh) for path, leaf in flat]
        return jax.tree_util.tree_unflatten(treedef, shs)

    def place(self, params, mesh: Mesh):
        """device_put the parameter tree according to this strategy."""
        return jax.tree_util.tree_map(
            lambda leaf, sh: jax.device_put(leaf, sh), params,
            self.shardings(params, mesh))

    # ---- JSON round-trip (reference base.py:158-227) ----
    def save_json(self, params, path):
        flat, _ = jax.tree_util.tree_flatten_with_path(params)
        out = {}
        for p, leaf in flat:
            key = jax.tree_util.keystr(p)
            out[key] = {"spec": list(self.param_spec(key, leaf)),
                        "shape": list(leaf.shape)}
        Path(path).write_text(json.dumps(out, indent=1, default=str))

    @staticmethod
    def load_json(path) -> "Strategy":
        table = {k: tuple(None if s is None else s for s in v["spec"])
                 for k, v in json.loads(Path(path).read_text()).items()}

        class _Loaded(Strategy):
            def param_spec(self, path_str, leaf):
                return P(*table.get(path_str, ()))

        return _Loaded()
