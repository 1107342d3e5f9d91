"""Fused federated-aggregation pallas kernels.

Two ops, both forward-only (server aggregation is never differentiated
through):

* `weighted_mean_pallas(stacked, w)` — sample-weighted mean over the
  client axis: Σᵢ wᵢ·xᵢ / Σᵢ wᵢ.  Replaces the reference's CPU
  dict-of-tensors loop (FedAVGAggregator.py:73-81).  One [1,C]×[C,T]
  MXU dot per tile.
* `robust_weighted_mean_pallas(stacked, w, global_tree, tau)` — the
  Byzantine-robust pipeline (norm-difference clipping,
  robust_aggregation.py:38-49) fused into two passes over the stack:
  pass 1 accumulates per-client ‖xᵢ−g‖², pass 2 applies the clip factor
  inside the weighted reduction:  g + Σᵢ ŵᵢ·min(1, τ/‖dᵢ‖)·(xᵢ−g).
  Without fusion this is 4+ HBM round-trips over [C,N]; fused it is 2.

Layout: client pytrees are flattened to one [C, N] matrix (N padded to
the 128-lane tile), so every leaf rides the same kernel and the tiling is
always aligned.  On non-TPU backends the kernels run in pallas interpret
mode (tests), selected automatically and counted in
`ops_kernel_path_total{op="aggregate", path=...}`.

Size limit (one v5e chip, 16 GB HBM): the fused ops materialize the
whole cohort as ONE f32 [C, N] matrix next to the stacked input —
`flatten_stacked_tree`'s concat (fused with the pad to a TILE multiple)
and a relayout copy of the big leaves.  At the ResNet-18 row
(N = 11,173,962; 44.7 MB per client per copy) C = 8 and C = 10 compile
for the chip; at C = 128 the compiler refuses with RESOURCE_EXHAUSTED
(16.01 G of 15.75 G HBM: 5.37 G of arguments + a 5.33 G [128, N] concat
+ a 5.31 G leaf copy; tests/test_tpu_compile.py pins the refusal) — a
size limit of this layout, not a kernel fault.  Cohorts that large
aggregate through the mesh engines' chunked Σw·v carry
(parallel/engine.py), which never builds [C, N].
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedml_tpu import obs

Pytree = Any
TILE = 512                             # lanes per grid step (4×128)
_VMEM = pltpu.VMEM
_SMEM = pltpu.SMEM


def _interpret_default() -> bool:
    """Compiled (Mosaic) on a TPU backend, pallas interpret mode
    elsewhere — the CPU tests' path.  The choice is counted at trace
    time (`ops_kernel_path_total{op="aggregate"}`), so a run that
    reports "pallas" can show which one it got."""
    interpret = jax.default_backend() != "tpu"
    obs.counter("ops_kernel_path_total", op="aggregate",
                path="interpret" if interpret else "pallas").inc()
    return interpret


# ---------------------------------------------------------------------------
# pytree <-> [C, N] matrix
# ---------------------------------------------------------------------------

def flatten_stacked_tree(stacked: Pytree):
    """[C, ...] leaves → float32 [C, N] (N padded to TILE) + unflatten spec.

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
    pad = (-n) % TILE
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


# ---------------------------------------------------------------------------
# kernel 1: weighted mean
# ---------------------------------------------------------------------------

# The MXU's default f32 matmul is ONE bf16 pass: measured on the v5e it
# cost the weighted mean 5.6e-3 absolute against the f32 reference (PR 21
# chip run) — model weights aggregated at 8 bits of mantissa.  HIGHEST
# keeps the reduction at f32 accuracy; the op is HBM-bound either way.
_F32_DOT = dict(preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)


def _wmean_kernel(w_ref, x_ref, inv_ref, o_ref):
    # [1,C] @ [C,T] on the MXU, scaled by 1/Σw from SMEM
    o_ref[:] = jnp.dot(w_ref[:], x_ref[:], **_F32_DOT) * inv_ref[0, 0]


def _wmean_flat(flat: jax.Array, w: jax.Array, interpret: bool) -> jax.Array:
    C, N = flat.shape
    inv = (1.0 / jnp.maximum(jnp.sum(w), 1e-12)).reshape(1, 1)
    out = pl.pallas_call(
        _wmean_kernel,
        grid=(N // TILE,),
        in_specs=[
            pl.BlockSpec((1, C), lambda i: (0, 0), memory_space=_VMEM),
            pl.BlockSpec((C, TILE), lambda i: (0, i), memory_space=_VMEM),
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=_SMEM),
        ],
        out_specs=pl.BlockSpec((1, TILE), lambda i: (0, i),
                               memory_space=_VMEM),
        out_shape=jax.ShapeDtypeStruct((1, N), jnp.float32),
        interpret=interpret,
    )(w.astype(jnp.float32).reshape(1, C), flat, inv)
    return out[0]


def weighted_mean_pallas(stacked: Pytree, weights: jax.Array,
                         interpret: bool | None = None) -> Pytree:
    """Drop-in for core.pytree.tree_weighted_mean, fused over all leaves."""
    if interpret is None:
        interpret = _interpret_default()
    flat, spec = flatten_stacked_tree(stacked)
    return unflatten_to_tree(_wmean_flat(flat, weights, interpret), spec)


# ---------------------------------------------------------------------------
# kernel 2: fused robust (norm-clip) aggregation
# ---------------------------------------------------------------------------

def _sqnorm_kernel(x_ref, g_ref, o_ref):
    # accumulate per-client Σ (x−g)² across the tile grid (grid on TPU is
    # sequential, so the running += into the same output block is sound)
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        o_ref[:] = jnp.zeros_like(o_ref)
    d = x_ref[:] - g_ref[:]
    o_ref[:] += jnp.sum(d * d, axis=1, keepdims=True)


def _clip_agg_kernel(cf_ref, x_ref, g_ref, o_ref):
    # out = g + Σ_c cf_c·(x_c − g):   cf already folds ŵ_c·min(1, τ/‖d_c‖)
    d = x_ref[:] - g_ref[:]
    o_ref[:] = g_ref[:] + jnp.dot(cf_ref[:], d, **_F32_DOT)


def robust_weighted_mean_pallas(stacked: Pytree, weights: jax.Array,
                                global_tree: Pytree, norm_bound: float,
                                interpret: bool | None = None) -> Pytree:
    """Fused  g + Σᵢ ŵᵢ·clipᵢ·(xᵢ−g),  ŵ = w/Σw,
    clipᵢ = min(1, τ/‖xᵢ−g‖) — exactly norm_diff_clip + weighted mean
    (reference clips each client before averaging,
    FedAvgRobustAggregator.py:176-185)."""
    if interpret is None:
        interpret = _interpret_default()
    flat, spec = flatten_stacked_tree(stacked)
    C, N = flat.shape
    gflat, _ = flatten_stacked_tree(
        jax.tree.map(lambda x: x[None], global_tree))

    sq = pl.pallas_call(
        _sqnorm_kernel,
        grid=(N // TILE,),
        in_specs=[
            pl.BlockSpec((C, TILE), lambda i: (0, i), memory_space=_VMEM),
            pl.BlockSpec((1, TILE), lambda i: (0, i), memory_space=_VMEM),
        ],
        out_specs=pl.BlockSpec((C, 1), lambda i: (0, 0),
                               memory_space=_VMEM),
        out_shape=jax.ShapeDtypeStruct((C, 1), jnp.float32),
        interpret=interpret,
    )(flat, gflat)

    # the clip factor is the ONE shared definition (core/pytree.clip_scale
    # — same 1e-24-floored sqrt), so this fused path, norm_diff_clip and
    # the flat-row admission/DP clip cannot drift (ISSUE-9 dedupe)
    from fedml_tpu.core.pytree import clip_scale
    clip = clip_scale(sq[:, 0], norm_bound)
    w = weights.astype(jnp.float32)
    cf = (w / jnp.maximum(jnp.sum(w), 1e-12)) * clip

    out = pl.pallas_call(
        _clip_agg_kernel,
        grid=(N // TILE,),
        in_specs=[
            pl.BlockSpec((1, C), lambda i: (0, 0), memory_space=_VMEM),
            pl.BlockSpec((C, TILE), lambda i: (0, i), memory_space=_VMEM),
            pl.BlockSpec((1, TILE), lambda i: (0, i), memory_space=_VMEM),
        ],
        out_specs=pl.BlockSpec((1, TILE), lambda i: (0, i),
                               memory_space=_VMEM),
        out_shape=jax.ShapeDtypeStruct((1, N), jnp.float32),
        # the output rides the gflat buffer: same [1, N] f32 shape, gflat
        # is dead after this call (the sq pass above already consumed
        # it), and each grid step reads its g tile into VMEM before the
        # o tile stores back — one less HBM allocation per aggregation
        input_output_aliases={2: 0},
        interpret=interpret,
    )(cf.reshape(1, C), flat, gflat)
    return unflatten_to_tree(out[0], spec)
