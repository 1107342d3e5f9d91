"""Pytree arithmetic for federated aggregation.

These are the TPU-native replacement for the reference's server-side
dict-of-tensors loops (FedAVGAggregator.aggregate,
reference fedml_api/distributed/fedavg/FedAVGAggregator.py:59-88): instead of
a Python loop over state_dict keys on CPU, aggregation is a jit-able
tree-map over stacked leaves that XLA fuses into a handful of HBM-bandwidth
bound kernels (and into a single `psum` when the client axis is sharded over
a mesh).
"""
from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp

Pytree = Any


def tree_weighted_mean(trees_stacked: Pytree, weights: jax.Array) -> Pytree:
    """Sample-weighted mean over leading (client) axis of stacked pytrees.

    ``sum_i (n_i / N) * w_i`` — exactly the FedAvg aggregation rule of the
    reference (FedAVGAggregator.py:73-81), including averaging *all* leaves
    (BN/GN statistics included, matching the reference's iteration over every
    state_dict key).

    Args:
      trees_stacked: pytree whose leaves have a leading axis of size C
        (number of clients).
      weights: [C] float array of per-client sample counts (need not be
        normalized).
    """
    w = weights / jnp.sum(weights)

    def _avg(leaf):
        wb = w.reshape((-1,) + (1,) * (leaf.ndim - 1)).astype(leaf.dtype)
        return jnp.sum(leaf * wb, axis=0)

    return jax.tree.map(_avg, trees_stacked)


def tree_stack(trees: Sequence[Pytree]) -> Pytree:
    """Stack a list of identically-structured pytrees along a new axis 0."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *trees)


def tree_unstack(tree: Pytree) -> list[Pytree]:
    """Inverse of tree_stack: split leading axis into a list of pytrees."""
    leaves, treedef = jax.tree.flatten(tree)
    n = leaves[0].shape[0]
    return [jax.tree.unflatten(treedef, [leaf[i] for leaf in leaves]) for i in range(n)]


def tree_zeros_like(tree: Pytree) -> Pytree:
    return jax.tree.map(jnp.zeros_like, tree)


def tree_add(a: Pytree, b: Pytree) -> Pytree:
    return jax.tree.map(jnp.add, a, b)


def tree_sub(a: Pytree, b: Pytree) -> Pytree:
    return jax.tree.map(jnp.subtract, a, b)


def tree_scale(tree: Pytree, s) -> Pytree:
    return jax.tree.map(lambda x: x * jnp.asarray(s, dtype=x.dtype), tree)


def tree_dot(a: Pytree, b: Pytree) -> jax.Array:
    parts = jax.tree.leaves(jax.tree.map(lambda x, y: jnp.vdot(x, y), a, b))
    return jnp.sum(jnp.stack([p.astype(jnp.float32) for p in parts]))


def tree_l2_norm(tree: Pytree) -> jax.Array:
    """Global L2 norm over all leaves (the reference's vectorize_weight +
    torch.norm, robust_aggregation.py:4-9)."""
    sq = jax.tree.leaves(jax.tree.map(lambda x: jnp.sum(jnp.square(x.astype(jnp.float32))), tree))
    return jnp.sqrt(jnp.sum(jnp.stack(sq)))


def clip_scale(sq_norm, max_norm):
    """THE norm-clip factor:  min(1, τ/‖·‖)  from a SQUARED norm, with
    the 1e-24 floor inside the sqrt guarding the zero-update case.

    One definition, two call sites (the ISSUE-9 dedupe): the pytree
    clip below (→ core/robust.norm_diff_clip) and the flat-row
    admission/DP clip (core/robust.clip_row → async_/defense.py).  They
    reduce their squared norms differently (tree-sum vs flat dot), so
    the cross-pin in tests/test_robustness.py holds on the FACTOR given
    equal sq_norm — routing both through here is what keeps the
    DP-FedAvg clip and the admission clip from drifting."""
    norm = jnp.sqrt(jnp.maximum(jnp.asarray(sq_norm, jnp.float32), 1e-24))
    return jnp.minimum(1.0, max_norm / norm)


def tree_sq_norm(tree: Pytree) -> jax.Array:
    """Global squared L2 norm over all leaves (f32 accumulate)."""
    sq = jax.tree.leaves(jax.tree.map(
        lambda x: jnp.sum(jnp.square(x.astype(jnp.float32))), tree))
    return jnp.sum(jnp.stack(sq))


def tree_clip_by_norm(tree: Pytree, max_norm) -> Pytree:
    return tree_scale(tree, clip_scale(tree_sq_norm(tree), max_norm))


def tree_cast(tree: Pytree, dtype) -> Pytree:
    return jax.tree.map(lambda x: x.astype(dtype), tree)


def vectorize_weights(tree: Pytree) -> jax.Array:
    """Flatten a parameter pytree into one 1-D vector (reference
    robust_aggregation.py:4-9). Useful for MPC encoding and norm math."""
    return jnp.concatenate([jnp.ravel(x) for x in jax.tree.leaves(tree)])


def unvectorize_weights(vec: jax.Array, like: Pytree) -> Pytree:
    """Inverse of vectorize_weights given a template pytree."""
    leaves, treedef = jax.tree.flatten(like)
    out, off = [], 0
    for leaf in leaves:
        n = leaf.size
        out.append(vec[off:off + n].reshape(leaf.shape).astype(leaf.dtype))
        off += n
    return jax.tree.unflatten(treedef, out)


def tree_select(pred, new: Pytree, old: Pytree) -> Pytree:
    """Elementwise `jnp.where(pred, new, old)` over two matching pytrees.

    The standard empty-batch guard: an all-padding batch must be a no-op,
    but momentum / weight-decay / prox updates are nonzero even at zero
    data gradient — so freeze params and optimizer state when the batch
    holds no real samples (the reference iterates only real batches)."""
    return jax.tree.map(lambda n, o: jnp.where(pred, n, o), new, old)


def _map_schedule_counts(fn, state: Pytree, *others: Pytree) -> Pytree:
    """`state` with every SCHEDULE step count — the ``count`` field of
    optax ``ScaleByScheduleState`` NamedTuples — replaced by
    `fn(count, *the same count of each of others)`; everything else,
    other counts included, as it was."""
    if hasattr(state, "_fields"):          # optax states are NamedTuples
        schedule = type(state).__name__ == "ScaleByScheduleState"

        def field(f):
            args = (getattr(state, f), *(getattr(o, f) for o in others))
            if f == "count" and schedule:
                return fn(*args)
            return _map_schedule_counts(fn, *args)
        return type(state)(**{f: field(f) for f in state._fields})
    if isinstance(state, (list, tuple)):
        return type(state)(_map_schedule_counts(fn, *group)
                           for group in zip(state, *others))
    if isinstance(state, dict):
        return {k: _map_schedule_counts(fn, v, *(o[k] for o in others))
                for k, v in state.items()}
    return state


def tree_merge_counts(kept: Pytree, advanced: Pytree) -> Pytree:
    """Return `kept` with every SCHEDULE step count taken from `advanced`.

    The empty-batch guard freezes optimizer state via tree_select, which
    also freezes the schedule step count — so padded-lane clients would
    stall on the LR schedule while real steps elapse.  The SCHEDULE
    count measures elapsed local steps, not applied updates: merging the
    advanced count back makes every client in a ragged cohort walk the
    same LR trajectory over the padded E x B loop (the CLI sizes
    total_steps to the padded batch count).  Other counts — notably
    ScaleByAdamState.count, whose bias correction must agree with the
    frozen mu/nu moments — and momentum / moment buffers stay frozen."""
    return _map_schedule_counts(lambda _, count: count, kept, advanced)


def tree_advance_counts(state: Pytree, steps) -> Pytree:
    """Return `state` with every SCHEDULE step count (the counts
    tree_merge_counts merges) advanced by `steps` more elapsed local
    steps: what the padded batches a bounded batch loop never visits
    (ClientTrainer.local_train) would have added one at a time."""
    return _map_schedule_counts(
        lambda count: count + jnp.asarray(steps, count.dtype), state)


def tree_vary_noop(tree: Pytree, shard) -> Pytree:
    """Value-preserving select that makes `tree` carry the shard data's
    shard_map variance type.

    Why: under shard_map, the empty-batch guard's tree_select varies any
    STATEFUL optimizer state after the first step (has_data depends on
    the shard), while a freshly tx.init'd state is replicated-typed — a
    lax.scan carry-type mismatch.  select(pred, x, x) with a pred that is
    data-dependent but always true fixes the type without changing a bit.
    The invariant lives here so every local-training loop uses the same
    trick."""
    pred = jnp.sum(shard["mask"]) >= 0        # always true, shard-typed
    return tree_select(pred, tree, tree)
