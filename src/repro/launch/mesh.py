"""Production meshes and per-(arch, shape) sharding rule assembly.

TPU v5e target: single pod = 16x16 = 256 chips (axes data x model);
multi-pod = 2 pods = 512 chips (pod x data x model).

Functions only — importing this module never touches jax device state.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax

from repro.sharding.rules import MeshRules

__all__ = [
    "make_production_mesh",
    "make_node_mesh",
    "make_rules",
    "mesh_axis_sizes",
    "node_shard_count",
    "FSDP_ARCHS",
    "TRAIN_MICROBATCHES",
]


# Archs whose parameter+optimizer state exceeds per-chip HBM under 16-way TP
# alone: shard the d_model dim of large matrices over the data axis (FSDP /
# ZeRO-3-style; XLA inserts the all-gathers).
FSDP_ARCHS = {
    "deepseek-v2-236b",
    "chameleon-34b",
    "internlm2-20b",
    "mixtral-8x7b",
}

# Gradient-accumulation microbatches for train_4k (global batch 256).
TRAIN_MICROBATCHES = {
    "default": 8,
    "llama3-8b": 4,
    "deepseek-v2-236b": 16,
    "chameleon-34b": 16,
    "internlm2-20b": 8,
}


def train_microbatches(arch_id: str, *, global_batch: int = 256,
                       batch_extent: int = 1) -> int:
    """Per-arch microbatch count, capped so each microbatch still fills the
    batch mesh axes (B/mb >= batch_extent) — otherwise the microbatch loses
    its batch sharding and activations replicate (observed 3.5x FLOPs/dev on
    the 2x16x16 mesh with mb=16: 256/16 = 16 rows < 32 shards)."""
    mb = TRAIN_MICROBATCHES.get(arch_id, TRAIN_MICROBATCHES["default"])
    max_mb = max(global_batch // max(batch_extent, 1), 1)
    return min(mb, max_mb)


def make_production_mesh(*, multi_pod: bool = False):
    """The pod mesh with Auto axes: the sharding rules place arrays with
    bare PartitionSpecs and let XLA propagate the rest, which
    ``jax.make_mesh``'s default Explicit axes would refuse."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def node_shard_count(n_nodes: int, device_count: Optional[int] = None) -> int:
    """Shard count for the RealBackend node axis: the largest divisor of
    ``n_nodes`` that fits the available local devices.

    Divisibility (rather than padding to the device count) is deliberate:
    padded zero-mask node rows would drag the nanmedian inside
    ``guard_weights`` toward zero and flag every real node as an outlier,
    so the node axis is never padded — shards just get n/D nodes each.
    """
    if n_nodes <= 0:
        raise ValueError("n_nodes must be positive")
    if device_count is None:
        device_count = jax.local_device_count()
    d = max(1, min(n_nodes, device_count))
    while n_nodes % d:
        d -= 1
    return d


def make_node_mesh(n_nodes: int, devices=None):
    """1-D ``("nodes",)`` mesh over local devices for the sharded RealBackend.

    Uses the first ``node_shard_count(n_nodes)`` local devices so the padded
    ``(n, b_max)`` node axis splits evenly across shards.
    """
    import numpy as np

    if devices is None:
        devices = jax.local_devices()
    d = node_shard_count(n_nodes, len(devices))
    return jax.sharding.Mesh(np.asarray(devices[:d]), ("nodes",))


def make_rules(
    mesh,
    arch_id: str,
    *,
    kind: str = "train",
    global_batch: Optional[int] = None,
) -> MeshRules:
    """MeshRules for one (mesh, arch, shape-kind) combination.

    Decode KV caches shard their sequence dim over the model axis
    (flash-decode style); when the batch is too small to occupy the data
    axis (long_500k: B=1) the cache sequence also spreads over data.
    """
    sizes = mesh_axis_sizes(mesh)
    multi = "pod" in sizes
    batch_axes: Tuple[str, ...] = ("pod", "data") if multi else ("data",)
    cache_seq: Tuple[str, ...] = ("model",)
    if kind == "decode" and global_batch is not None:
        data_extent = sizes["data"] * (sizes.get("pod", 1))
        if global_batch < data_extent:
            cache_seq = ("pod", "data", "model") if multi else ("data", "model")
    fsdp = "data" if arch_id in FSDP_ARCHS else None
    # Expert parallelism (experts sharded over the model axis) pays off when
    # E >= model-axis extent: deepseek's 160 experts (§Perf: 5.4x less
    # collective traffic than re-sharding capacity over data).
    experts_axis = "model" if arch_id == "deepseek-v2-236b" else None
    return MeshRules(
        mesh_axes=sizes,
        batch_axes=batch_axes,
        model_axis="model",
        fsdp_axis=fsdp,
        cache_seq_axes=cache_seq,
        experts_axis=experts_axis,
    )
