"""Device-mesh topology — TPU-native replacement of the reference "mpu".

The reference (megatron/core/parallel_state.py:51-205) carves the NCCL world
into data/tensor/pipeline/embedding process subgroups, one process per GPU.
On TPU we run single-program SPMD: one JAX process sees every chip, and
parallelism is a named ``jax.sharding.Mesh`` over axes ``(dp, pp, tp)``.
Collectives that the reference issues explicitly (all-reduce over the TP
group, isend/irecv over the PP group, ...) become either XLA-inserted
collectives (via ``NamedSharding`` constraints) or explicit ``psum`` /
``ppermute`` over mesh axis names inside ``shard_map``.

Axis order is (dp, pp, tp) so that tp is innermost — adjacent devices on the
ICI ring carry the highest-bandwidth collectives (TP all-reduce), matching
the reference's guidance that TP ranks be intra-node (NVLink there, ICI here).
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Canonical axis names.
DP_AXIS = "dp"
PP_AXIS = "pp"
TP_AXIS = "tp"
CP_AXIS = "cp"  # context (sequence/ring-attention) parallelism
EP_AXIS = "ep"  # expert parallelism (MoE)

# Batch axes: expert parallelism is carved out of data parallelism (the
# Megatron-LM convention, ep | dp): the global batch is sharded over BOTH
# axes, and MoE expert weights shard over ep only. For dense models ep=1
# and this degenerates to plain dp.
DATA_AXES = (DP_AXIS, EP_AXIS)

_GLOBAL_MESH: Optional[Mesh] = None


@dataclasses.dataclass(frozen=True)
class MeshLayout:
    """Logical parallel layout; mirrors reference initialize_model_parallel args."""

    tensor_model_parallel_size: int = 1
    pipeline_model_parallel_size: int = 1
    data_parallel_size: Optional[int] = None
    context_parallel_size: int = 1
    expert_parallel_size: int = 1


def build_mesh(
    tensor_model_parallel_size: int = 1,
    pipeline_model_parallel_size: int = 1,
    data_parallel_size: Optional[int] = None,
    context_parallel_size: int = 1,
    expert_parallel_size: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build the (dp, ep, pp, cp, tp) device mesh.

    Analog of ``initialize_model_parallel`` (parallel_state.py:51-205): instead
    of enumerating rank lists per subgroup, the reshaped device array defines
    every "group" implicitly — a TP group is a row of the tp axis, etc.

    ``expert_parallel_size`` (ep) is carved out of data parallelism
    (Megatron-LM's ep | dp convention): ``data_parallel_size`` counts the
    TOTAL data-parallel replicas, of which ep also carry distinct experts.
    The batch shards over (dp, ep) jointly (DATA_AXES); expert weights
    shard over ep; dense weights are replicated across both.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    tp = tensor_model_parallel_size
    pp = pipeline_model_parallel_size
    cp = context_parallel_size
    ep = expert_parallel_size
    if data_parallel_size is None:
        assert n % (tp * pp * cp * ep) == 0, (
            f"{n} devices not divisible by tp*pp*cp*ep = {tp * pp * cp * ep}"
        )
        dp = n // (tp * pp * cp * ep)
        need = n  # auto dp must consume every device
    else:
        # an explicitly requested layout may use a subset of the devices
        assert data_parallel_size % ep == 0, (
            f"data_parallel_size {data_parallel_size} not divisible by "
            f"expert_parallel_size {ep}"
        )
        dp = data_parallel_size // ep
        need = dp * ep * pp * cp * tp
        assert need <= n, f"dp*ep*pp*cp*tp = {need} > device count {n}"
    devices = list(devices)[:need]
    dev_array = np.asarray(devices).reshape(dp, ep, pp, cp, tp)
    names = [DP_AXIS, EP_AXIS, PP_AXIS, CP_AXIS, TP_AXIS]
    order = os.environ.get("MLT_MESH_ORDER")
    if order:
        # Experimental logical-axis reorder (tools/flash_nested_repro.py):
        # a pure transpose — every axis keeps EXACTLY the same device
        # groups, only the Mesh tuple order (and hence GSPMD's device
        # enumeration) changes.
        perm = [n.strip() for n in order.split(",")]
        assert sorted(perm) == sorted(names), (perm, names)
        dev_array = dev_array.transpose([names.index(n) for n in perm])
        names = perm
    return Mesh(dev_array, tuple(names))


def build_mesh_from_config(cfg, devices=None) -> Mesh:
    p = cfg.parallel
    return build_mesh(
        tensor_model_parallel_size=p.tensor_model_parallel_size,
        pipeline_model_parallel_size=p.pipeline_model_parallel_size,
        data_parallel_size=p.data_parallel_size,
        context_parallel_size=p.context_parallel_size,
        expert_parallel_size=getattr(p, "expert_parallel_size", 1),
        devices=devices,
    )


# ---------------------------------------------------------------------------
# Global mesh management (analog of the reference's module-level group
# singletons + get_*_group accessors, parallel_state.py:217-481)
# ---------------------------------------------------------------------------


def set_global_mesh(mesh: Mesh) -> None:
    global _GLOBAL_MESH
    _GLOBAL_MESH = mesh


def get_global_mesh() -> Mesh:
    assert _GLOBAL_MESH is not None, "mesh is not initialized (call set_global_mesh)"
    return _GLOBAL_MESH


def mesh_is_initialized() -> bool:
    return _GLOBAL_MESH is not None


def destroy_global_mesh() -> None:
    """Analog of destroy_model_parallel (parallel_state.py:497-524)."""
    global _GLOBAL_MESH
    _GLOBAL_MESH = None


def target_platform() -> str:
    """Platform the current mesh's devices belong to ('tpu'/'cpu').

    Kernel dispatch must key on the COMPILE TARGET, not the host default
    backend: AOT-lowering a TPU-topology mesh (tools/aot_scale_check.py)
    happens on a CPU host, and the compiled program must still contain the
    Pallas flash path it will run on hardware. Falls back to
    jax.default_backend() when no mesh is set (single-chip eager use)."""
    if _GLOBAL_MESH is not None:
        try:
            return _GLOBAL_MESH.devices.flat[0].platform
        except (AttributeError, IndexError):
            pass  # AbstractMesh has no devices; fall through
    return jax.default_backend()


@contextlib.contextmanager
def global_mesh(mesh: Mesh):
    global _GLOBAL_MESH
    prev = _GLOBAL_MESH
    set_global_mesh(mesh)
    try:
        with mesh:
            yield mesh
    finally:
        _GLOBAL_MESH = prev


def _axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape[axis] if axis in mesh.shape else 1


def get_tensor_model_parallel_world_size(mesh: Optional[Mesh] = None) -> int:
    return _axis_size(mesh or get_global_mesh(), TP_AXIS)


def get_pipeline_model_parallel_world_size(mesh: Optional[Mesh] = None) -> int:
    return _axis_size(mesh or get_global_mesh(), PP_AXIS)


def get_data_parallel_world_size(mesh: Optional[Mesh] = None) -> int:
    """TOTAL data-parallel replicas = dp * ep (ep is carved out of dp)."""
    m = mesh or get_global_mesh()
    return _axis_size(m, DP_AXIS) * _axis_size(m, EP_AXIS)


def get_expert_parallel_world_size(mesh: Optional[Mesh] = None) -> int:
    return _axis_size(mesh or get_global_mesh(), EP_AXIS)


def get_context_parallel_world_size(mesh: Optional[Mesh] = None) -> int:
    return _axis_size(mesh or get_global_mesh(), CP_AXIS)


def named_sharding(*spec, mesh: Optional[Mesh] = None) -> NamedSharding:
    return NamedSharding(mesh or get_global_mesh(), P(*spec))


def placement_report(mesh: Mesh, **trees) -> str:
    """One log line: on how many of the mesh's local devices each named
    pytree has an addressable shard, and every such device's bytes in use
    and peak (where the backend reports them; XLA:CPU does not).

    Raises when a tree leaves a mesh device without a shard, or a device
    reports zero bytes in use: the layout asked for is then not the layout
    on the chips (a mesh built over the first device only, a pool left on
    one chip)."""
    devices = list(mesh.local_devices)
    parts = []
    for name, tree in trees.items():
        held = {s.device for leaf in jax.tree_util.tree_leaves(tree)
                for s in leaf.addressable_shards}
        missing = [d.id for d in devices if d not in held]
        if missing:
            raise RuntimeError(
                f"{name} has no shard on mesh devices {missing}")
        parts.append(f"{name} on {len(devices)}/{len(devices)} devices")
    for d in devices:
        stats = d.memory_stats()
        if stats is None:
            continue
        if not stats["bytes_in_use"]:
            raise RuntimeError(f"device {d.id} reports 0 bytes in use")
        parts.append(
            f"device {d.id} in_use {stats['bytes_in_use'] / 2**30:.2f} GiB "
            f"peak {stats['peak_bytes_in_use'] / 2**30:.2f} GiB")
    return "placement: " + "; ".join(parts)


# Inside shard_map, pipeline stage index is the device's coordinate on the pp
# axis (analog of get_pipeline_model_parallel_rank, parallel_state.py:311-320).

def pipeline_stage_index() -> jax.Array:
    """Current pp-stage index; only valid inside shard_map over PP_AXIS."""
    return jax.lax.axis_index(PP_AXIS)


def is_pipeline_first_stage() -> jax.Array:
    return pipeline_stage_index() == 0


def is_pipeline_last_stage() -> jax.Array:
    return pipeline_stage_index() == jax.lax.axis_size(PP_AXIS) - 1
