"""Device-mesh model.

The reference models devices as flat world ranks grouped into DeviceGroups with
per-strategy DeviceGroupHierarchy (reference: hetu/core/device.h,
hetu/graph/distributed_states.h:360-573).  On TPU the idiomatic equivalent is a
named `jax.sharding.Mesh` whose axes are the parallelism dimensions; collectives
then ride ICI along mesh axes.  We standardize the axis vocabulary:

    dp  — data parallel (batch dim)
    cp  — context parallel (sequence dim, ring attention)
    tp  — tensor parallel (Megatron-style; also sequence-parallel axis)
    pp  — pipeline parallel (stage axis)
    ep  — expert parallel (MoE)

"dcp" in the reference (trainer.py:208-260: fused dp×cp input dim) corresponds
here to sharding the batch dim over ("dp","cp") jointly.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

# Canonical axis order: pipeline outermost (cross-slice / DCN friendly), then
# data, context, expert, tensor innermost (tp wants the fastest ICI links).
AXIS_ORDER = ("pp", "dp", "cp", "ep", "tp")

DP_AXIS = "dp"
CP_AXIS = "cp"
TP_AXIS = "tp"
PP_AXIS = "pp"
EP_AXIS = "ep"


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Declarative mesh shape; axes of size 1 are still materialized so that
    PartitionSpecs can always name them (XLA treats size-1 axes as free)."""

    dp: int = 1
    cp: int = 1
    tp: int = 1
    pp: int = 1
    ep: int = 1

    @property
    def num_devices(self) -> int:
        return self.dp * self.cp * self.tp * self.pp * self.ep

    def axis_sizes(self) -> Dict[str, int]:
        return {"pp": self.pp, "dp": self.dp, "cp": self.cp, "ep": self.ep, "tp": self.tp}

    def __str__(self):
        return "x".join(f"{k}{v}" for k, v in self.axis_sizes().items() if v > 1) or "single"


def create_mesh(
    config: Optional[MeshConfig] = None,
    *,
    devices: Optional[Sequence[jax.Device]] = None,
    **axis_sizes: int,
) -> Mesh:
    """Build a Mesh from a MeshConfig or axis sizes (dp=, tp=, ...).

    Axes are laid out in AXIS_ORDER so that tp varies fastest over adjacent
    devices (best ICI locality), mirroring how the reference orders DS `order`
    vectors innermost-last (reference: distributed_states.h order semantics).
    """
    if config is None:
        config = MeshConfig(**{k: int(v) for k, v in axis_sizes.items()})
    if devices is None:
        devices = jax.devices()
    n = config.num_devices
    if n > len(devices):
        raise ValueError(
            f"mesh {config} needs {n} devices but only {len(devices)} available"
        )
    sizes = config.axis_sizes()
    shape = tuple(sizes[a] for a in AXIS_ORDER)
    dev_array = np.asarray(devices[:n]).reshape(shape)
    return Mesh(dev_array, AXIS_ORDER)


# ---------------------------------------------------------------------------
# Current-mesh context (the analog of the reference graph context stack,
# reference: python/hetu/context.py:50-115).
# ---------------------------------------------------------------------------

_local = threading.local()


def current_mesh() -> Optional[Mesh]:
    return getattr(_local, "mesh", None)


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    prev = getattr(_local, "mesh", None)
    _local.mesh = mesh
    try:
        with mesh:
            yield mesh
    finally:
        _local.mesh = prev


def mesh_axis_size(mesh: Optional[Mesh], axis: str) -> int:
    if mesh is None:
        return 1
    return int(mesh.shape.get(axis, 1))


def mesh_axis_group(mesh: Mesh, axis: str) -> Tuple[int, ...]:
    """The ranks of `axis`'s group that holds rank 0, as a compiled
    program's `replica_groups` number them: positions in the mesh's
    device order (dp2 x tp2 with tp fastest: dp's group is (0, 2))."""
    ranks = np.arange(mesh.devices.size).reshape(mesh.devices.shape)
    along = np.moveaxis(ranks, mesh.axis_names.index(axis), 0)
    return tuple(int(r) for r in along.reshape(along.shape[0], -1)[:, 0])


def single_device_mesh() -> Mesh:
    return create_mesh(MeshConfig())
