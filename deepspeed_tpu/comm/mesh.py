"""Device-mesh management.

The reference builds a zoo of torch.distributed process groups
(deepspeed/utils/groups.py, deepspeed/runtime/pipe/topology.py). The
TPU-native equivalent is ONE ``jax.sharding.Mesh`` with named axes; every
"process group" becomes a mesh axis (or tuple of axes) and XLA lowers the
collectives onto ICI/DCN rings automatically.

Axis vocabulary (sizes default to 1, ``data`` absorbs the remainder):

- ``stage``  : pipeline-parallel stages           (reference: pipe_parallel_size)
- ``data``   : pure data parallel replicas        (reference: data_parallel group)
- ``expert`` : expert-parallel shard of the data group (reference: expert_parallel_size;
               dense params treat ("data","expert") as the full DP group, expert
               params are data-parallel over "data" only — mirrors
               deepspeed/utils/groups.py:107 _create_expert_and_data_parallel)
- ``fsdp``   : ZeRO-3 parameter-sharding axis (reference: ZeRO partitioning over DP ranks)
- ``seq``    : sequence/context parallel (Ulysses / ring attention — new capability)
- ``model``  : tensor parallel (reference: external Megatron mpu protocol)

Axis order is outer→inner = furthest→nearest in the interconnect: ``stage``
over DCN-ish links is fine, ``model`` innermost so TP collectives ride
nearest-neighbor ICI.
"""

import contextlib
from dataclasses import dataclass, field
from typing import Optional, Tuple

import numpy as np

MESH_AXES = ("stage", "data", "expert", "fsdp", "seq", "model")

# Composite "groups" expressed as axis tuples (the analog of the reference's
# process groups). PartitionSpecs may use these directly.
DENSE_DP_AXES = ("data", "expert", "fsdp")  # full data-parallel group for dense params
EXPERT_DP_AXES = ("data",)                  # data-parallel group for expert params


@dataclass(frozen=True)
class MeshSpec:
    """Declarative mesh shape. -1 for ``data`` means absorb remaining devices."""
    stage: int = 1
    data: int = -1
    expert: int = 1
    fsdp: int = 1
    seq: int = 1
    model: int = 1

    def resolve(self, n_devices: int) -> Tuple[int, ...]:
        fixed = [self.stage, self.expert, self.fsdp, self.seq, self.model]
        if any(s <= 0 for s in fixed):
            raise ValueError(f"Only the data axis may be -1, got {self}")
        prod = int(np.prod(fixed))
        data = self.data
        if data == -1:
            if n_devices % prod != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes product {prod} ({self})")
            data = n_devices // prod
        total = prod * data
        if total != n_devices:
            raise ValueError(
                f"Mesh {self} needs {total} devices but {n_devices} are available")
        return (self.stage, data, self.expert, self.fsdp, self.seq, self.model)


_GLOBAL_MESH = None


def build_mesh(spec: Optional[MeshSpec] = None, devices=None, set_global: bool = True):
    """Build a ``jax.sharding.Mesh`` over all (or given) devices."""
    import jax
    from jax.sharding import Mesh

    if spec is None:
        spec = MeshSpec()
    if devices is None:
        devices = jax.devices()
    shape = spec.resolve(len(devices))
    dev_array = np.asarray(devices).reshape(shape)
    mesh = Mesh(dev_array, MESH_AXES)
    if set_global:
        set_global_mesh(mesh)
    return mesh


def set_global_mesh(mesh):
    global _GLOBAL_MESH
    _GLOBAL_MESH = mesh


def get_global_mesh():
    """Current global mesh; builds a trivial all-data mesh lazily if unset."""
    global _GLOBAL_MESH
    if _GLOBAL_MESH is None:
        _GLOBAL_MESH = build_mesh(MeshSpec(), set_global=False)
    return _GLOBAL_MESH


def peek_global_mesh():
    """Current global mesh or None — no lazy construction (for callers
    that must not invent a mesh, e.g. activation constraints)."""
    return _GLOBAL_MESH


@contextlib.contextmanager
def global_mesh_scope(mesh):
    """``mesh`` is the global mesh inside the block, the previous one
    after it. The global mesh is read while a program TRACES (activation
    constraints, the attention dispatch), so an engine that shares its
    process with another runs its own traces under its own mesh."""
    global _GLOBAL_MESH
    previous, _GLOBAL_MESH = _GLOBAL_MESH, mesh
    try:
        yield
    finally:
        _GLOBAL_MESH = previous


def axis_size(axis, mesh=None) -> int:
    """Size of a mesh axis (or product over a tuple of axes).

    Unknown names raise a ValueError naming the declared axes instead of
    a bare KeyError (or a deep lax failure downstream)."""
    mesh = mesh or get_global_mesh()
    if isinstance(axis, (tuple, list)):
        return int(np.prod([axis_size(a, mesh) for a in axis]))
    if axis not in mesh.shape:
        raise ValueError(
            f"unknown mesh axis {axis!r}: declared axes are "
            f"{tuple(mesh.shape.keys())}")
    return mesh.shape[axis]


def dp_world_size(mesh=None) -> int:
    """Full data-parallel degree for dense params: data*expert*fsdp."""
    return axis_size(DENSE_DP_AXES, mesh)


def mp_world_size(mesh=None) -> int:
    return axis_size("model", mesh)


def pp_world_size(mesh=None) -> int:
    return axis_size("stage", mesh)


def sp_world_size(mesh=None) -> int:
    return axis_size("seq", mesh)


def ep_world_size(mesh=None) -> int:
    return axis_size("expert", mesh)
