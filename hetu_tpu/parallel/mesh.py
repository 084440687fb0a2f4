"""Device mesh construction — the TPU-native DeviceGroup/DistConfig.

Reference: python/hetu/context.py: `DeviceGroup` (:28) is an ordered worker
list with tuple entries for model-parallel groups; `DistConfig` (:2204) parses
a yaml cluster spec and the heturun launcher spawns MPI ranks.

TPU design: the cluster IS a mesh.  One `jax.sharding.Mesh` with named axes
('dp','tp','pp','ep','sp') replaces DeviceGroup/worker indices; XLA binds
collectives to axes and routes them over ICI (within slice) / DCN (across
slices).  Axis ordering matters for locality: we put 'tp' innermost so
tensor-parallel collectives ride the fastest ICI links, then 'ep'/'sp', with
'dp'/'pp' outermost (cross-slice friendly) — the mesh-layout recipe from the
public scaling playbooks.
"""

from __future__ import annotations

import contextlib
import math
from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS_DP = "dp"  # data parallel
AXIS_TP = "tp"  # tensor/model parallel
AXIS_PP = "pp"  # pipeline stages
AXIS_EP = "ep"  # expert parallel
AXIS_SP = "sp"  # sequence/context parallel

# outermost-to-innermost default ordering (innermost = fastest ICI)
DEFAULT_AXIS_ORDER = (AXIS_PP, AXIS_DP, AXIS_SP, AXIS_EP, AXIS_TP)


@dataclass
class MeshConfig:
    """Named-axis sizes; unspecified axes default to 1.

    The analog of the reference's yaml DistConfig + DeviceGroup nesting: e.g.
    reference `DeviceGroup([(gpu0,gpu1),(gpu2,gpu3)])` (2-way DP of 2-way MP)
    == MeshConfig(dp=2, tp=2).
    """

    dp: int = 1
    tp: int = 1
    pp: int = 1
    ep: int = 1
    sp: int = 1
    axis_order: Sequence[str] = field(default=DEFAULT_AXIS_ORDER)

    def sizes(self):
        return {AXIS_DP: self.dp, AXIS_TP: self.tp, AXIS_PP: self.pp,
                AXIS_EP: self.ep, AXIS_SP: self.sp}

    @property
    def num_devices(self) -> int:
        return self.dp * self.tp * self.pp * self.ep * self.sp


def mesh_context(mesh: Optional[Mesh]):
    """Make ``mesh`` the one in context (``jax.set_mesh``) while a jitted
    step traces and runs, so code deep inside it that must partition itself
    — the flash-attention kernel's shard_map — can find it.  A no-op
    context for ``None``."""
    return jax.set_mesh(mesh) if mesh is not None else contextlib.nullcontext()


def make_mesh(config: Optional[MeshConfig] = None, *, devices=None,
              **axis_sizes) -> Mesh:
    """Build a Mesh from a MeshConfig or axis sizes (make_mesh(dp=2, tp=4)).

    Axes of size 1 are kept in the mesh so shardings can always name every
    axis; XLA drops trivial axes at lowering.
    """
    if config is None:
        config = MeshConfig(**axis_sizes)
    devices = np.asarray(devices if devices is not None else jax.devices())
    n = config.num_devices
    if devices.size < n:
        raise ValueError(
            f"mesh needs {n} devices, have {devices.size}")
    order = [a for a in config.axis_order]
    sizes = config.sizes()
    shape = [sizes[a] for a in order]
    dev = devices.reshape(-1)[:n].reshape(shape)
    return Mesh(dev, tuple(order))


def elastic_mesh(config: MeshConfig, alive: Sequence[int], *,
                 devices=None) -> Mesh:
    """Re-form the mesh with only the ``alive`` data-parallel workers.

    ``config`` describes the NOMINAL layout (dp = fleet width); ``alive``
    lists the surviving dp indices (sorted, each < config.dp).  Each dp
    worker owns one contiguous group of ``tp*pp*ep*sp`` devices in the
    nominal device array; the elastic mesh is built from the survivors'
    groups only, in rank order, so a worker that was never lost keeps its
    exact devices across resizes (its replica of the state never moves —
    only the lost/joined worker's shard placement changes).

    Mesh membership as a runtime input (arxiv 2412.14374): the same
    ``MeshConfig`` reshapes to any width 1..dp without re-describing the
    cluster.  Used by resilience/elastic.ElasticSupervisor.
    """
    alive = sorted(int(i) for i in alive)
    if not alive:
        raise ValueError("elastic mesh needs at least one alive worker")
    if alive[0] < 0 or alive[-1] >= config.dp:
        raise ValueError(
            f"alive indices {alive} out of range for nominal dp={config.dp}")
    if len(set(alive)) != len(alive):
        raise ValueError(f"duplicate alive indices {alive}")
    nominal = make_mesh(config, devices=devices)
    dp_axis = nominal.axis_names.index(AXIS_DP)
    dev = np.take(nominal.devices, alive, axis=dp_axis)
    return Mesh(dev, nominal.axis_names)


def local_mesh(axis: str = AXIS_DP) -> Mesh:
    """All local devices on one axis — the default DP mesh (reference analog:
    heturun's single-host allreduce config)."""
    devs = np.asarray(jax.devices())
    return Mesh(devs, (axis,))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def host_to_device(arr, sharding):
    """``jax.device_put`` with the CPU zero-copy-adoption guard.

    On CPU targets device_put can ADOPT a host numpy buffer zero-copy,
    and a later DONATED step then frees memory numpy still owns —
    observed as NaN state / heap corruption.  Route through a jax-owned
    copy there.  Non-CPU targets always copy host→device, so direct
    placement keeps sharded transfers single-pass (no full-leaf
    materialization on one device).  Shared by train/checkpoint.load and
    resilience/elastic's resharding — keep the workaround in ONE place.
    """
    import jax.numpy as jnp
    if any(d.platform == "cpu" for d in sharding.device_set):
        arr = jnp.array(arr)
    return jax.device_put(arr, sharding)


def batch_sharding(mesh: Mesh, axis: str = AXIS_DP) -> NamedSharding:
    """Shard dim 0 (batch) along the dp axis."""
    return NamedSharding(mesh, P(axis))
