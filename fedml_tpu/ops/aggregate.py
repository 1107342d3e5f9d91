"""Stacked client pytrees as one padded float32 matrix, and back.

`flatten_stacked_tree(stacked)` lays `[C, ...]` leaves side by side as one
f32 `[C, N]` matrix (N padded to `ROW_MULTIPLE` lanes) and returns the spec
that `unflatten_to_tree(vec, spec)` needs to cut one `[N]` row back into the
tree.  The mesh engines' robust defenses (krum, multi-krum, median, trimmed
mean: parallel/engine.py) select and reduce over these rows; no kernel is
involved, XLA fuses the concat with the pad.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

Pytree = Any
ROW_MULTIPLE = 512                     # row padding, 4 x 128 lanes


def flatten_stacked_tree(stacked: Pytree):
    """[C, ...] leaves → float32 [C, N] (N padded to ROW_MULTIPLE) + the
    unflatten spec.

    Donation-safe: builds one fresh [C, N] buffer and never aliases the
    input leaves into the returned spec, so callers may donate `stacked`
    at their jit boundary (the mesh engines' block steps donate their
    whole block inputs — parallel/engine.py); a single-leaf tree skips
    the concatenate (reshape only), letting XLA alias a donated f32
    input straight into the flat buffer."""
    leaves, treedef = jax.tree.flatten(stacked)
    C = leaves[0].shape[0]
    if len(leaves) == 1:
        flat = leaves[0].reshape(C, -1).astype(jnp.float32)
    else:
        flat = jnp.concatenate(
            [l.reshape(C, -1).astype(jnp.float32) for l in leaves], axis=1)
    n = flat.shape[1]
    pad = (-n) % ROW_MULTIPLE
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    shapes = [jax.ShapeDtypeStruct(l.shape, l.dtype) for l in leaves]
    return flat, (treedef, shapes, n)


def unflatten_to_tree(vec: jax.Array, spec) -> Pytree:
    """[N] → pytree with the per-leaf shapes of the stacked input (minus the
    client axis)."""
    treedef, leaves, n = spec
    vec = vec[:n]
    out, off = [], 0
    for l in leaves:
        shape = l.shape[1:]
        size = 1
        for s in shape:
            size *= s
        out.append(vec[off:off + size].reshape(shape).astype(l.dtype))
        off += size
    return jax.tree.unflatten(treedef, out)
