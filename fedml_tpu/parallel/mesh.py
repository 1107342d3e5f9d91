"""Mesh construction + sharding helpers.

Replaces the reference's process/placement machinery: `mpirun -np W+1` +
gpu_mapping.yaml rank→GPU tables (fedml_api/distributed/utils/
gpu_mapping.py:8-39).  Here "placement" is a `jax.sharding.Mesh` and a
`PartitionSpec`; the runtime below (XLA) moves the bytes.

Axis conventions used throughout the framework:

  "clients"  — the federated data-parallel axis (cohort dimension K).
  "silo"     — the cross-silo / DCN tier for hierarchical FL (2-D meshes).
  "batch"    — per-client sample parallelism (each client's per-step batch
               split over devices, grads psum'd per step): the scaling
               axis once chips outnumber the cohort (PERF.md v4-128
               projection break #1/#2).

Multi-host note: on a real pod these helpers take `jax.devices()` spanning
hosts; ICI carries the "clients" psum within a slice and DCN the "silo"
reductions, exactly the two-tier layout of SURVEY.md §2.5.
"""
from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Pytree = Any

CLIENT_AXIS = "clients"
SILO_AXIS = "silo"
BATCH_AXIS = "batch"


def make_mesh(n_devices: Optional[int] = None,
              axis_name: str = CLIENT_AXIS,
              devices: Optional[Sequence] = None) -> Mesh:
    """1-D mesh over `n_devices` (default: all local devices)."""
    devs = list(devices) if devices is not None else jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis_name,))


def make_mesh_2d(n_silos: int, per_silo: Optional[int] = None,
                 devices: Optional[Sequence] = None) -> Mesh:
    """2-D (silo × clients) mesh for hierarchical FL (SURVEY.md §2.5:
    'psum within ICI slice, DCN cross-slice')."""
    devs = list(devices) if devices is not None else jax.devices()
    per_silo = per_silo if per_silo is not None else len(devs) // n_silos
    devs = devs[: n_silos * per_silo]
    grid = np.array(devs).reshape(n_silos, per_silo)
    return Mesh(grid, (SILO_AXIS, CLIENT_AXIS))


def make_mesh_batch(n_client_shards: int, n_batch: int,
                    devices: Optional[Sequence] = None) -> Mesh:
    """2-D (clients × batch) mesh: the cohort splits over the first axis
    and each client's per-step batch over the second.  This is the layout
    for chips > cohort (PERF.md projection break #2): with K clients and
    N = K·b chips, every client trains on b devices at once."""
    devs = list(devices) if devices is not None else jax.devices()
    devs = devs[: n_client_shards * n_batch]
    grid = np.array(devs).reshape(n_client_shards, n_batch)
    return Mesh(grid, (CLIENT_AXIS, BATCH_AXIS))


def pvary_tree(tree: Pytree, axis_names) -> Pytree:
    """Mark a replicated pytree as varying over `axis_names` inside
    shard_map (needed before per-shard scans/vmaps mutate it, else the
    vma type-check rejects the scan carry)."""
    return jax.tree.map(
        lambda a: jax.lax.pcast(a, axis_names, to="varying"), tree)


def client_axes(mesh: Mesh) -> tuple:
    """The mesh axes that shard the CLIENT dimension — every axis except
    "batch" (which shards within-client samples instead)."""
    return tuple(a for a in mesh.axis_names if a != BATCH_AXIS)


def client_shard_count(mesh: Mesh) -> int:
    """How many ways the client axis is partitioned over the mesh."""
    return int(np.prod([mesh.shape[a] for a in client_axes(mesh)]))


def client_sharding(mesh: Mesh) -> NamedSharding:
    """Shard a [K, ...] cohort/stack along its leading (client) axis over
    the client axes — on a silo×clients mesh clients split over both; a
    "batch" axis never shards the client dim (replicated there)."""
    return NamedSharding(mesh, P(client_axes(mesh)))


def _splits_batch(mesh: Mesh, leaf) -> bool:
    """Whether a stack leaf's per-step sample dim (axis 2) splits over the
    "batch" axis.  A non-dividing sample dim falls back to replication
    along "batch" — still numerically correct (each shard then holds the
    full batch and the trainer's S/C_g normalization makes the per-step
    psum a mean over identical contributions), just without the split."""
    return (BATCH_AXIS in mesh.axis_names and np.ndim(leaf) >= 3
            and np.shape(leaf)[2] % mesh.shape[BATCH_AXIS] == 0)


def stack_leaf_sharding(mesh: Mesh, leaf) -> NamedSharding:
    """Per-leaf sharding for a client data stack {x,y,mask}[C,B,bs,...]:
    the client dim over the client axes and — when the mesh has a "batch"
    axis — the per-step sample dim (axis 2) over it.  Weight/[C] leaves
    fall back to client_sharding."""
    ca = client_axes(mesh)
    if _splits_batch(mesh, leaf):
        return NamedSharding(mesh, P(ca, None, BATCH_AXIS))
    return NamedSharding(mesh, P(ca))


def stack_leaf_spec(mesh: Mesh, leaf) -> P:
    """shard_map PartitionSpec matching stack_leaf_sharding."""
    if _splits_batch(mesh, leaf):
        return P(client_axes(mesh), None, BATCH_AXIS)
    return P(client_axes(mesh))


def shard_stack(mesh: Mesh, stack: dict) -> dict:
    """device_put a client data stack with per-leaf stack_leaf_sharding."""
    return {k: jax.device_put(v, stack_leaf_sharding(mesh, v))
            for k, v in stack.items()}


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_cohort(mesh: Mesh, cohort: Pytree) -> Pytree:
    """Place a host-side cohort {x,y,mask}[K,...] (+weights [K]) onto the
    mesh, leading axis split across devices. K must divide evenly — callers
    pad the cohort with zero-weight clients otherwise (pad_cohort)."""
    sh = client_sharding(mesh)
    return jax.tree.map(lambda a: jax.device_put(a, sh), cohort)


def pad_cohort(cohort: dict, weights: np.ndarray, multiple: int):
    """Pad cohort to a multiple of the mesh size with zero-weight dummy
    clients (mask=0 ⇒ their local_train is a no-op and weight 0 drops them
    from the psum numerator and denominator)."""
    K = int(weights.shape[0])
    pad = (-K) % multiple
    if pad == 0:
        return cohort, weights
    def pad_leaf(a):
        z = np.zeros((pad,) + tuple(a.shape[1:]), a.dtype)
        return np.concatenate([np.asarray(a), z], axis=0)
    cohort = {k: pad_leaf(v) for k, v in cohort.items()}
    weights = np.concatenate([np.asarray(weights),
                              np.zeros(pad, np.asarray(weights).dtype)])
    return cohort, weights
