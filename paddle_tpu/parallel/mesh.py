"""Device-mesh management — the TPU-native replacement for the reference's
ring-id-keyed NCCL communicator registry (platform/collective_helper.h:63):
instead of bootstrapping per-ring communicators over TCP
(c_gen_nccl_id/c_comm_init, operators/collective/), a single
`jax.sharding.Mesh` names the parallelism axes and XLA inserts/schedules all
collectives over ICI/DCN.

Canonical axis names: "dp" (data), "pp" (pipeline stages), "ep" (experts /
MoE), "tp" (tensor / intra-layer model), "sp" (sequence / context).  A mesh
axis of size 1 simply disables that parallelism dimension.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXES = ("dp", "pp", "ep", "tp", "sp")

_GLOBAL_MESH: Optional[Mesh] = None


def create_mesh(axes: Optional[Dict[str, int]] = None,
                devices: Optional[Sequence] = None, **axis_sizes) -> Mesh:
    """Build a Mesh from {"dp": 2, "tp": 4, ...}; unlisted axes get size 1.

    Axis order is fixed (dp, pp, tp, sp) with dp outermost — tp/sp vary
    fastest so they land on the most tightly coupled (ICI-adjacent) devices,
    the analogue of putting the hierarchical-allreduce inner ring on NVLink
    (distributed_strategy.proto:128).
    """
    from ..core.errors import enforce
    sizes = dict(axes or {})
    sizes.update(axis_sizes)
    for a in sizes:
        enforce(a in AXES, f"unknown mesh axis {a!r}; valid: {AXES}")
    devices = list(devices if devices is not None else jax.devices())
    n = int(np.prod([sizes.get(a, 1) for a in AXES]))
    enforce(n <= len(devices),
            f"mesh wants {n} devices but only {len(devices)} available")
    shape = tuple(sizes.get(a, 1) for a in AXES)
    arr = np.array(devices[:n]).reshape(shape)
    # arm eager dispatch's placement harmonization: once a mesh exists,
    # eager ops may mix mesh-sharded and single-device operands (core.op
    # skips that per-input scan until this is called — the cheap-path gate)
    from ..core import op as _op
    _op.note_multi_device()
    return Mesh(arr, AXES)


def set_mesh(mesh: Optional[Mesh]):
    global _GLOBAL_MESH
    _GLOBAL_MESH = mesh
    if mesh is not None:
        # externally built meshes (jax.sharding.Mesh direct) must also arm
        # eager placement harmonization
        from ..core import op as _op
        _op.note_multi_device()


def get_mesh(create_default: bool = False) -> Optional[Mesh]:
    global _GLOBAL_MESH
    if _GLOBAL_MESH is None and create_default:
        _GLOBAL_MESH = create_mesh({"dp": len(jax.devices())})
    return _GLOBAL_MESH


def axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape.get(axis, 1)


def sharding(mesh: Mesh, *spec) -> NamedSharding:
    """NamedSharding helper: sharding(mesh, 'dp', None) -> rows over dp."""
    return NamedSharding(mesh, P(*spec))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
