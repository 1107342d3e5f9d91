"""Byzantine-robust aggregation primitives.

Parity with reference fedml_core/robustness/robust_aggregation.py: norm
-difference clipping ``w_t + clip(w_local - w_t)`` (:38-49) and weak-DP
Gaussian noise (:51-55).  The reference excludes BatchNorm running stats from
the norm via `is_weight_param` (:28-29); here the caller passes the params
subtree (stats live in a separate collection in flax, so the split is
structural, not name-matching).

All ops are pure pytree functions — they run inside the jitted aggregation
step, not in a host loop.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from fedml_tpu.core.pytree import (clip_scale, tree_add, tree_clip_by_norm,
                                   tree_sub)

Pytree = Any

__all__ = ["norm_diff_clip", "clip_scale", "clip_row", "add_weak_dp_noise",
           "krum_select_flat", "krum_scores_flat", "multi_krum_select_flat",
           "default_multi_krum_m", "krum_select", "multi_krum_select",
           "coordinate_median", "trimmed_mean"]


def norm_diff_clip(local_params: Pytree, global_params: Pytree,
                   norm_bound: float) -> Pytree:
    """Clip the update (w_local - w_global) to `norm_bound` and re-apply:
    returns w_global + clip(w_local - w_global).  The clip factor is the
    ONE shared definition (core/pytree.clip_scale) — the flat-row
    admission/DP clip uses the same one."""
    diff = tree_sub(local_params, global_params)
    return tree_add(global_params, tree_clip_by_norm(diff, norm_bound))


def clip_row(row: jax.Array, norm_bound: float) -> jax.Array:
    """Flat-row norm clip: `row * clip_scale(‖row‖², bound)` — the
    RowLayout-row form of norm_diff_clip's clip (callers pass the DELTA
    row, i.e. uplink − global, and re-add the global themselves).  The
    async admission pipeline and the DP-FedAvg per-client clip
    (async_/defense.py) both resolve here, so the two cannot drift."""
    row = jnp.asarray(row, jnp.float32)
    return row * clip_scale(jnp.sum(row * row), norm_bound)


def add_weak_dp_noise(params: Pytree, rng: jax.Array, stddev: float) -> Pytree:
    """Per-leaf Gaussian noise with std `stddev` (weak differential privacy)."""
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(rng, len(leaves))
    noised = [leaf + stddev * jax.random.normal(k, leaf.shape, leaf.dtype)
              for leaf, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, noised)


def krum_select_flat(flat: jax.Array, n_byzantine: int) -> jax.Array:
    """Krum on a [K, P] client-update matrix: index of the client whose
    update has the smallest sum of squared distances to its n-f-2 nearest
    neighbors.  Gram-matrix form (krum_scores_flat): O(K·P + K²) memory,
    and the K×P matmul runs on the MXU — never materialize the [K,K,P]
    broadcast."""
    return jnp.argmin(krum_scores_flat(flat, n_byzantine))


def krum_scores_flat(flat: jax.Array, n_byzantine: int) -> jax.Array:
    """Per-client krum scores on a [K, P] matrix: Σ of squared distances
    to the n-f-2 nearest neighbors (the quantity krum argmins and
    multi-krum top-m's — one definition for both)."""
    sq = jnp.sum(flat * flat, axis=1)
    d2 = jnp.maximum(sq[:, None] + sq[None, :] - 2.0 * (flat @ flat.T), 0.0)
    n = flat.shape[0]
    k = max(n - n_byzantine - 2, 1)
    d2 = jnp.where(jnp.eye(n, dtype=bool), jnp.inf, d2)
    # NaN/Inf guard: a non-finite row would otherwise poison EVERY
    # pairwise distance it touches (NaN sorts unpredictably and argmin
    # propagates it), letting one garbage uplink break the selection for
    # honest clients too.  Non-finite distances become +inf: the bad row
    # scores inf (never selected) and drops out of everyone else's
    # k-nearest sums — for finite inputs this where() is the identity.
    d2 = jnp.where(jnp.isfinite(d2) | jnp.eye(n, dtype=bool), d2, jnp.inf)
    return jnp.sum(jnp.sort(d2, axis=1)[:, :k], axis=1)


def default_multi_krum_m(K: int, n_byzantine: int,
                         m: "int | None" = None) -> int:
    """Multi-krum selection size: the Blanchard et al. 2017 default
    m = K - f - 2 when unset, clamped to [1, K] either way — THE one
    definition both the single-device and mesh engines share."""
    if m is None:
        m = K - n_byzantine - 2
    return max(1, min(m, K))


def multi_krum_select_flat(flat: jax.Array, n_byzantine: int,
                           m: int) -> jax.Array:
    """Multi-krum on a [K, P] matrix: indices of the m clients with the
    LOWEST krum scores (Blanchard et al. 2017 §4 — m=1 degenerates to
    krum; the aggregate is the plain mean of the selected updates)."""
    scores = krum_scores_flat(flat, n_byzantine)
    m = max(1, min(m, flat.shape[0]))
    return jnp.argsort(scores)[:m]


def _flatten_clients(stacked_params: Pytree) -> jax.Array:
    """[K, ...] stacked pytree -> the [K, P] matrix the krum family
    scores (ONE definition of the flattening convention)."""
    return jnp.concatenate(
        [x.reshape(x.shape[0], -1)
         for x in jax.tree.leaves(stacked_params)], axis=1)


def krum_select(stacked_params: Pytree, n_byzantine: int) -> jax.Array:
    """Krum over a stacked pytree.  (An addition beyond the reference's
    clip+noise, standard in the robust-FL literature.)"""
    return krum_select_flat(_flatten_clients(stacked_params), n_byzantine)


def multi_krum_select(stacked_params: Pytree, n_byzantine: int,
                      m: int) -> jax.Array:
    """Multi-krum over a stacked pytree: indices of the m best-scored
    clients (their plain mean is the aggregate)."""
    return multi_krum_select_flat(_flatten_clients(stacked_params),
                                  n_byzantine, m)


def coordinate_median(stacked_params: Pytree) -> Pytree:
    """Coordinate-wise median over the client axis."""
    return jax.tree.map(lambda x: jnp.median(x, axis=0), stacked_params)


def trimmed_mean(stacked_params: Pytree, trim_k: int) -> Pytree:
    """Coordinate-wise trimmed mean: drop the k largest and smallest
    (k is capped so at least one value survives)."""
    def _tm(x):
        n = x.shape[0]
        k = min(trim_k, (n - 1) // 2)
        s = jnp.sort(x, axis=0)
        return jnp.mean(s[k:n - k], axis=0)
    return jax.tree.map(_tm, stacked_params)
