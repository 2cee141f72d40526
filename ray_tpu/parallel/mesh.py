"""Device mesh construction and registry.

The TPU-native replacement for the reference's process-group world
(``train/torch/config.py:63`` ``dist.init_process_group`` and
``util/collective/collective.py:40`` ``GroupManager``): instead of NCCL
communicators keyed by group name, we build `jax.sharding.Mesh`es over the
device torus and register them by name. All parallelism (DP/FSDP/TP/SP/EP/PP)
is expressed as axes of one mesh; XLA inserts the collectives.

Axis convention (outer → inner, slowest → fastest varying):

    pp   — pipeline stages (DCN or ICI, coarse)
    dp   — pure data parallelism (gradient all-reduce; can ride DCN)
    fsdp — sharded data parallelism (param/grad/optimizer sharding, ICI)
    ep   — expert parallelism for MoE (ICI)
    sp   — sequence/context parallelism (ICI, ring collectives)
    tp   — tensor/model parallelism (innermost: highest-bandwidth ICI)

Inner axes get ICI-contiguous device assignment via
``jax.experimental.mesh_utils.create_device_mesh``, which optimizes placement
for the physical torus topology. Cross-slice (DCN) meshes use
``create_hybrid_device_mesh`` with dcn axes outermost.
"""

from __future__ import annotations

import math
import threading
from dataclasses import dataclass, field

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh

# Canonical axis order, outer to inner. Meshes may use any subset.
AXIS_ORDER = ("pp", "dp", "fsdp", "ep", "sp", "tp")


@dataclass(frozen=True)
class MeshSpec:
    """Declarative mesh shape: axis name -> size. Size -1 means "absorb all
    remaining devices" (at most one axis may be -1)."""

    axes: dict[str, int] = field(default_factory=dict)

    def resolved(self, n_devices: int) -> dict[str, int]:
        axes = dict(self.axes)
        wild = [k for k, v in axes.items() if v == -1]
        if len(wild) > 1:
            raise ValueError(f"At most one axis may be -1, got {wild}")
        fixed = math.prod(v for v in axes.values() if v != -1)
        if wild:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes {axes}"
                )
            axes[wild[0]] = n_devices // fixed
        else:
            if fixed != n_devices:
                raise ValueError(
                    f"Mesh axes {axes} require {fixed} devices, have {n_devices}"
                )
        # order axes canonically; unknown axes go last in given order
        known = [a for a in AXIS_ORDER if a in axes]
        extra = [a for a in axes if a not in AXIS_ORDER]
        return {a: axes[a] for a in known + extra}


def create_mesh(
    axes: dict[str, int] | MeshSpec,
    *,
    devices=None,
    allow_split_physical_axes: bool = True,
) -> Mesh:
    """Build a Mesh with ICI-topology-aware device assignment.

    ``axes`` maps axis name -> size; one axis may be -1 (remaining devices).
    On TPU the device order comes from ``mesh_utils.create_device_mesh`` so
    that inner mesh axes map to physically adjacent chips (wrong assignment
    silently halves collective bandwidth — SURVEY.md §7 hard parts).
    """
    if devices is None:
        devices = jax.devices()
    spec = axes if isinstance(axes, MeshSpec) else MeshSpec(dict(axes))
    resolved = spec.resolved(len(devices))
    shape = tuple(resolved.values())
    names = tuple(resolved.keys())
    if devices and devices[0].platform == "tpu":
        device_array = mesh_utils.create_device_mesh(
            shape, devices=devices,
            allow_split_physical_axes=allow_split_physical_axes,
        )
    else:
        # CPU/GPU or virtual devices: logical row-major assignment.
        device_array = np.asarray(devices).reshape(shape)
    return Mesh(device_array, axis_names=names)


def create_hybrid_mesh(
    ici_axes: dict[str, int],
    dcn_axes: dict[str, int],
    *,
    devices=None,
) -> Mesh:
    """Multi-slice mesh: ``dcn_axes`` (outermost, cross-slice — usually
    ``{"dp": n_slices}`` or ``{"pp": n_slices}``) × ``ici_axes`` (within a
    slice). Analog of the reference's multi-node NCCL world, except the
    slow/fast network split is explicit in the mesh so XLA routes gradient
    all-reduce over DCN and param all-gather over ICI.
    """
    if devices is None:
        devices = jax.devices()
    if not devices:
        raise ValueError("create_hybrid_mesh: no devices")
    n = len(devices)
    dcn_shape = tuple(dcn_axes.values())
    n_slices = math.prod(dcn_shape)
    if n % n_slices != 0:
        raise ValueError(
            f"{n} devices not divisible by dcn axes {dcn_axes} "
            f"({n_slices} slices)"
        )
    per_slice = n // n_slices
    ici_resolved = MeshSpec(dict(ici_axes)).resolved(per_slice)
    names = tuple(dcn_axes.keys()) + tuple(ici_resolved.keys())
    if devices[0].platform == "tpu":
        device_array = mesh_utils.create_hybrid_device_mesh(
            mesh_shape=tuple(ici_resolved.values()),
            dcn_mesh_shape=dcn_shape,
            devices=devices,
        )
    else:
        device_array = np.asarray(devices).reshape(
            dcn_shape + tuple(ici_resolved.values())
        )
    return Mesh(device_array, axis_names=names)


class MeshRegistry:
    """Named meshes (analog of the reference's collective ``GroupManager``,
    ``util/collective/collective.py:40``, which keys NCCL groups by name)."""

    def __init__(self):
        self._meshes: dict[str, Mesh] = {}
        self._lock = threading.Lock()

    def register(self, name: str, mesh: Mesh, *, overwrite: bool = False):
        with self._lock:
            return self._register_locked(name, mesh, overwrite)

    def _register_locked(self, name: str, mesh: Mesh, overwrite: bool):
        if name in self._meshes and not overwrite:
            raise ValueError(f"Mesh {name!r} already registered")
        self._meshes[name] = mesh
        return mesh

    def get(self, name: str) -> Mesh:
        with self._lock:
            if name not in self._meshes:
                raise KeyError(
                    f"No mesh named {name!r}; registered: {list(self._meshes)}"
                )
            return self._meshes[name]

    def get_or_create(self, name: str, axes: dict[str, int], **kwargs) -> Mesh:
        # Single critical section: a concurrent creator must get the winner's
        # mesh back, not a ValueError from a lost register race.
        with self._lock:
            if name in self._meshes:
                return self._meshes[name]
            mesh = create_mesh(axes, **kwargs)
            return self._register_locked(name, mesh, overwrite=False)

    def remove(self, name: str):
        with self._lock:
            self._meshes.pop(name, None)

    def names(self) -> list[str]:
        with self._lock:
            return list(self._meshes)


_registry = MeshRegistry()


def mesh_registry() -> MeshRegistry:
    return _registry


def slice_topology() -> dict:
    """Describe the local TPU slice (chip count, platform, coords if TPU).
    Analog of the reference's TPU autodetect (``_private/accelerator.py``)."""
    devices = jax.devices()
    info = {
        "platform": devices[0].platform if devices else "none",
        "num_devices": len(devices),
        "num_hosts": max((d.process_index for d in devices), default=0) + 1,
    }
    if devices and devices[0].platform == "tpu":
        info["coords"] = [d.coords for d in devices]
        info["device_kind"] = devices[0].device_kind
    return info
