"""Device mesh construction: the parallelism substrate.

This is the TPU-native replacement for the reference's process-group world
(reference: ray.train torch process groups + util/collective NCCL groups).
Instead of N processes each owning one GPU and gradient sync via NCCL, a
ray_tpu SPMD job holds a single logical `jax.sharding.Mesh` spanning every
chip of the slice (or multi-slice), with named axes:

    dp   — data parallel (batch split; psum of grads)
    fsdp — fully-sharded data parallel (weights sharded along with batch)
    tp   — tensor parallel (weight matrices split; collectives inside layers)
    pp   — pipeline parallel (layer groups; ppermute microbatches)
    sp   — sequence/context parallel (ring attention over sequence shards)
    ep   — expert parallel (MoE expert sharding + all_to_all dispatch)

`MeshSpec` validates that the axis product matches the device count, orders
axes so the fastest-varying axes land on ICI-adjacent devices (tp/sp
innermost — they carry per-layer collectives; dp outermost — it can cross
DCN), and builds the Mesh. The "How to Scale Your Model" recipe: pick a mesh,
annotate shardings, let XLA insert collectives.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

AXIS_ORDER = ("dp", "fsdp", "pp", "ep", "sp", "tp")
# innermost (rightmost) axes get ICI-contiguous devices; tp/sp carry the
# highest-frequency collectives so they sit innermost.


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Logical parallelism layout, independent of physical devices."""

    dp: int = 1
    fsdp: int = 1
    tp: int = 1
    pp: int = 1
    sp: int = 1
    ep: int = 1

    def axis_sizes(self) -> Dict[str, int]:
        return {a: getattr(self, a) for a in AXIS_ORDER}

    @property
    def num_devices(self) -> int:
        return math.prod(self.axis_sizes().values())

    def active_axes(self) -> List[str]:
        return [a for a in AXIS_ORDER if getattr(self, a) > 1]

    @classmethod
    def from_dict(cls, d: Dict[str, int]) -> "MeshSpec":
        unknown = set(d) - set(AXIS_ORDER)
        if unknown:
            raise ValueError(f"unknown mesh axes {unknown}; valid: {AXIS_ORDER}")
        return cls(**d)

    def with_auto_dp(self, num_devices: int) -> "MeshSpec":
        """Fill the dp axis to absorb remaining devices."""
        fixed = self.num_devices // max(self.dp, 1)
        if num_devices % fixed != 0:
            raise ValueError(
                f"{num_devices} devices not divisible by non-dp axes ({fixed})")
        return dataclasses.replace(self, dp=num_devices // fixed)

    def build(self, devices: Optional[Sequence] = None):
        """Build a jax.sharding.Mesh over the given (or all) devices."""
        import jax
        from jax.sharding import Mesh

        if devices is None:
            devices = jax.devices()
        devices = list(devices)
        if self.num_devices != len(devices):
            raise ValueError(
                f"MeshSpec wants {self.num_devices} devices "
                f"({self.axis_sizes()}), got {len(devices)}")
        shape = tuple(self.axis_sizes()[a] for a in AXIS_ORDER)
        arr = _topology_aware_reshape(devices, shape)
        return Mesh(arr, AXIS_ORDER)

    def describe(self) -> str:
        active = {a: getattr(self, a) for a in self.active_axes()}
        return f"MeshSpec({active or 'single-device'})"


def _topology_aware_reshape(devices: List, shape: Tuple[int, ...]) -> np.ndarray:
    """Order devices so innermost mesh axes are ICI-adjacent.

    On TPU jax.experimental.mesh_utils does the topology-aware assignment
    (and raises if it cannot: a mesh in id order on a real slice would be
    silently slow); elsewhere id order (CPU meshes in tests don't care).
    """
    if getattr(devices[0], "platform", "") == "tpu" and len(devices) > 1:
        from jax.experimental import mesh_utils
        return mesh_utils.create_device_mesh(shape, devices=devices)
    ordered = sorted(devices, key=lambda d: (getattr(d, "process_index", 0),
                                             d.id))
    return np.array(ordered).reshape(shape)


def single_axis_mesh(axis: str, devices: Optional[Sequence] = None):
    """Convenience: a 1-axis mesh (e.g. pure data parallel)."""
    import jax
    if devices is None:
        devices = jax.devices()
    return MeshSpec.from_dict({axis: len(devices)}).build(devices)


# ---------------------------------------------------------------------------
# Sharding rules


def param_sharding(mesh, path: Tuple[str, ...], shape: Tuple[int, ...],
                   spec: MeshSpec):
    """Default parameter PartitionSpec under a MeshSpec.

    Policy (the standard megatron/fsdp hybrid):
      - tp axis shards the largest contraction dim of matmul weights
      - fsdp shards the largest remaining dim
      - biases/scales/small params replicate
    Models can override per-layer; this default keeps MXU-friendly layouts
    (shard model dims, never the minor-most 128-lane dim below tile size).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    ndim = len(shape)
    assign: List[Optional[str]] = [None] * ndim
    if ndim >= 2:
        order = sorted(range(ndim), key=lambda i: -shape[i])
        if spec.tp > 1:
            for i in order:
                if shape[i] % spec.tp == 0 and _tp_hint(path, i, ndim):
                    assign[i] = "tp"
                    break
        if spec.fsdp > 1:
            for i in order:
                if assign[i] is None and shape[i] % spec.fsdp == 0:
                    assign[i] = "fsdp"
                    break
    elif ndim == 1 and spec.fsdp > 1 and shape[0] % spec.fsdp == 0 and \
            shape[0] >= 1024:
        assign[0] = "fsdp"
    return NamedSharding(mesh, P(*assign))


def _tp_hint(path: Tuple[str, ...], dim: int, ndim: int) -> bool:
    """Heuristic: attention/mlp 'out' projections shard input dim, others
    shard output dim — this alternates collectives correctly for megatron
    style TP. Path entries are param-tree keys."""
    name = "/".join(str(p) for p in path).lower()
    if any(k in name for k in ("out_proj", "down_proj", "wo", "o_proj", "fc2")):
        return dim == 0
    return dim == ndim - 1


def data_sharding(mesh, batch_ndim: int = 1):
    """Shard the batch dim over (dp, fsdp); replicate the rest."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    axes: list = [("dp", "fsdp")] + [None] * (batch_ndim - 1)
    return NamedSharding(mesh, P(*axes))


def replicated(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(mesh, P())


def shard_params(params, mesh, spec: MeshSpec):
    """Apply param_sharding across a pytree; returns sharded params."""
    import jax
    from jax.tree_util import tree_flatten_with_path, tree_unflatten

    leaves, treedef = tree_flatten_with_path(params)
    out = []
    for path, leaf in leaves:
        keys = tuple(getattr(p, "key", getattr(p, "idx", str(p)))
                     for p in path)
        sh = param_sharding(mesh, keys, leaf.shape, spec)
        out.append(jax.device_put(leaf, sh))
    return tree_unflatten(treedef, out)


def sharding_pytree(params, mesh, spec: MeshSpec):
    """The NamedSharding pytree for params (for jit in/out shardings)."""
    from jax.tree_util import tree_flatten_with_path, tree_unflatten

    leaves, treedef = tree_flatten_with_path(params)
    out = []
    for path, leaf in leaves:
        keys = tuple(getattr(p, "key", getattr(p, "idx", str(p)))
                     for p in path)
        out.append(param_sharding(mesh, keys, leaf.shape, spec))
    return tree_unflatten(treedef, out)
