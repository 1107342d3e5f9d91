"""Mesh-sharded federated engines — the 128-client north-star path.

One federated round is ONE jit-compiled SPMD program over a device mesh:

    round_fn(variables, server_state, ids, wmask, rng)
      cohort   = take(client_stack, ids)          # HBM-resident, sharded
      shard_map over the client axis:
        vmap(local_train)  over this device's slice of the cohort
        client_transform   per-client hook (robust clipping, ...)
        psum(w_i · v_i), psum(w_i)                # ICI collectives
      server_update(avg)                          # replicated (FedOpt, noise)

This replaces the reference's per-client OS processes + MPI sends + CPU
aggregation loop (FedAvgAPI.py:20-66, mpi/com_manager.py:13-98,
FedAVGAggregator.py:59-88).  The client stack {x,y,mask}[C,B,bs,...] is
uploaded once, sharded over the mesh; per-round traffic is an index vector.
"""
from __future__ import annotations

import logging
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from fedml_tpu import obs
from fedml_tpu.obs import programs as obs_programs
from fedml_tpu.obs import scopes
from fedml_tpu.algorithms.fedavg import FedAvgEngine
from fedml_tpu.algorithms.fedopt import make_server_optimizer
from fedml_tpu.core import robust as robust_ops
from fedml_tpu.core.trainer import ClientTrainer
from fedml_tpu.data.federated import FederatedData
from fedml_tpu.parallel.mesh import (BATCH_AXIS, client_axes,
                                     client_shard_count, client_sharding,
                                     make_mesh, pvary_tree,
                                     replicated_sharding, shard_stack,
                                     stack_leaf_sharding, stack_leaf_spec)
from fedml_tpu.parallel.prefetch import (AsyncValue, InlineFetcher,
                                         Prefetcher)
from fedml_tpu.utils.config import FedConfig
from fedml_tpu.utils.profiling import TransferOverlapStats

log = logging.getLogger(__name__)
Pytree = Any


def cast_local(tree, dtype):
    """Cast the float leaves of a variables tree to the LOCAL training
    dtype (bf16 local masters — see MeshFedAvgEngine docstring); None is
    the identity."""
    if dtype is None:
        return tree
    return jax.tree.map(
        lambda a: a.astype(dtype)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)


# leaves of at least this many elements (16 MiB in f32; the largest conv
# kernel of a ResNet-18 is 2.4 M) accumulate outside the packed Σ w·v
# carry of the chunked cohort loop: chunked_weighted_train says why
BIG_CARRY_LEAF = 1 << 22


def weighted_acc(w):
    """Accumulator step for the chunked loops: acc + Σₖ wₖ·vₖ in f32.
    One definition so every engine's accumulation (FedAvg/Nova/robust/
    GAN/NAS) shares the exact cast-and-einsum policy."""
    return lambda acc, v: acc + jnp.einsum(
        "k,k...->...", w, v.astype(jnp.float32))


def weighted_sum_tree(w, tree):
    """Σₖ wₖ·vₖ over a [k, ...]-stacked pytree, per leaf, in f32 — the
    same cast-and-einsum policy as weighted_acc, without the carry add
    (the chunked loops accumulate the result into their FLAT carry)."""
    return jax.tree.map(
        lambda v: jnp.einsum("k,k...->...", w, v.astype(jnp.float32)), tree)


def flatten_carry_f32(tree):
    """Pack an (unstacked) pytree into ONE [P] f32 vector + unflatten
    spec — THE scan-carry layout for the chunked cohort loops.

    Why: a pytree carry gives XLA one while-loop buffer per leaf, and
    any leaf whose in-loop producer prefers a different layout than the
    carry (e.g. the einsum's transposed output vs the row-major carry)
    gets a relayout `copy` EVERY scan trip — the round-2b trace's
    scan-carry copy category (PERF.md), reproduced structurally on CPU
    by tools/hlo_copy_audit.py (a params-shaped copy per trip in the
    block step).  A single 1-D f32 buffer has exactly one layout, so the
    carry aliases across trips and the per-leaf adds fuse into one
    concatenated update.  Exact: ravel+concat reorder nothing, each
    element sees the same adds in the same order as the per-leaf carry."""
    leaves = jax.tree.leaves(tree)
    if not leaves:
        return jnp.zeros((0,), jnp.float32), tree
    if len(leaves) == 1:
        flat = leaves[0].astype(jnp.float32).reshape(-1)
    else:
        flat = jnp.concatenate(
            [l.astype(jnp.float32).reshape(-1) for l in leaves])
    return flat, tree


def unflatten_carry_f32(flat, spec_tree):
    """Undo flatten_carry_f32: [P] f32 vector back to the pytree of
    `spec_tree`'s leaf shapes (f32 — the chunk-loop accumulators stay
    f32; callers apply their own ref-dtype cast when dividing)."""
    leaves, treedef = jax.tree.flatten(spec_tree)
    if not leaves:
        return spec_tree
    out, off = [], 0
    for l in leaves:
        size = int(np.prod(l.shape)) if l.ndim else 1
        out.append(flat[off:off + size].reshape(l.shape))
        off += size
    return jax.tree.unflatten(treedef, out)


def pad_ids(ids: np.ndarray, n_shards: int):
    """THE cohort-padding policy (host side): pad sampled client ids to a
    mesh-size multiple with zero-weight repeats of client 0 — wmask=0
    drops them from every weighted reduction.  Shared by all mesh
    engines."""
    ids = np.asarray(ids)
    pad = (-len(ids)) % n_shards
    wmask = np.concatenate([np.ones(len(ids), np.float32),
                            np.zeros(pad, np.float32)])
    ids = np.concatenate([ids, np.zeros(pad, ids.dtype)])
    return ids, wmask


def take_cohort(mesh: Mesh, stack: dict, stack_w, ids, wmask):
    """THE resident cohort take (the `fed_take` scope, the benchmark's
    `take_ms`): the cohort {x,y,mask}[K,B,bs,...] and its weights [K]
    out of the device-resident client stack, by the padded ids of
    `pad_ids`.  Two bodies, chosen by whether the client axis is
    partitioned over the mesh:

    one shard — a `dynamic_index_in_dim` per cohort slot (K is static),
      stacked.  The program then reads K clients.  A gather here costs
      a pass over the WHOLE stack on the TPU: its lowering pulls the
      trainer's narrowing convert of the cohort above the gather, onto
      the gather's operand, and relays the stack's layout to clients-
      major first (PERF.md §6 d: 19 of 71 ms a round at 10 of 4,000).
      The slices must stay unrolled here, outside every loop: from a
      scan or a `lax.map` the compiler hoists that convert back out.
    several shards — `jnp.take` along the sharded client axis, which XLA
      lowers to a cross-shard gather with ICI collectives.

    ids are always in range (the sampler's, padded with client 0 at
    weight 0), so both bodies return the same values bit for bit."""
    if client_shard_count(mesh) == 1:
        slots = [ids[i] for i in range(ids.shape[0])]

        def take(v):
            return jnp.stack([
                jax.lax.dynamic_index_in_dim(v, i, 0, keepdims=False)
                for i in slots])
    else:
        def take(v):
            return jnp.take(v, ids, axis=0)
    with jax.named_scope(scopes.FED_TAKE):
        cohort = {k: jax.lax.with_sharding_constraint(
            take(v), stack_leaf_sharding(mesh, v))
            for k, v in stack.items()}
        weights = jnp.take(stack_w, ids) * wmask
    return cohort, weights


def chunk_shape(k_local: int, chunk_cap: int):
    """Balanced chunk sizing shared by every chunked cohort loop: same
    number of scan trips as ceil(k/cap) but lanes spread evenly (k=12,
    cap=8 gives 2x6 not 2x8).  Returns (chunk, pad): lanes a chunk and
    the zero-weight lanes that fill the last one."""
    n_trips = -(-k_local // min(chunk_cap, k_local))
    chunk = -(-k_local // n_trips)
    return chunk, (-k_local) % chunk


def pad_and_chunk(cohort, weights, rngs, chunk_cap: int):
    """Chunk a shard-local cohort by `chunk_shape`; non-multiple cohorts
    are padded in-program with zero-weight lanes (static shapes; the
    empty-batch guard makes them numeric no-ops, and a bounded batch loop
    — batch_trips — never visits their all-padding batches).  Lanes keep
    the order they arrive in: chunked_weighted_train orders a ragged
    cohort before it calls this.  Returns (cohort, weights, rngs)
    reshaped to [n_chunks, chunk, ...]."""
    k_local = weights.shape[0]
    chunk, pad = chunk_shape(k_local, chunk_cap)
    if pad:
        cohort = jax.tree.map(
            lambda a: jnp.concatenate(
                [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)]), cohort)
        weights = jnp.concatenate(
            [weights, jnp.zeros((pad,), weights.dtype)])
        rngs = jnp.concatenate([rngs, rngs[:pad]])   # masked lanes; any key
    n_chunks = (k_local + pad) // chunk
    resh = lambda a: a.reshape((n_chunks, chunk) + a.shape[1:])
    return jax.tree.map(resh, cohort), resh(weights), resh(rngs)


def batch_trips(mask):
    """Batch-loop trips each client needs: 1 + the index of its last
    batch that holds a real sample (0 for a client with none) — NOT the
    number of such batches, real batches need not form a prefix.
    mask [..., B, bs] -> int32 [...]; numpy in, numpy out (the engine's
    host-side count), a jax array or tracer in, jax out (the program)."""
    xp = jnp if isinstance(mask, jax.Array) else np
    nth = xp.arange(1, mask.shape[-2] + 1, dtype=xp.int32)
    return ((mask > 0).any(axis=-1) * nth).max(axis=-1)


def population_trips(data):
    """(trips [C], ragged) of a resident population, read off its masks:
    each client's batch_trips, and whether any client leaves batches of
    the stack empty — what decides, at an engine's construction, between
    the bounded batch loop and the static one (chunked_weighted_train,
    `ragged_batches`).  A fact of the data, not an option."""
    mask = np.asarray(data.client_shards["mask"])
    trips = batch_trips(mask)
    return trips, bool((trips < mask.shape[1]).any())


def order_by_trips(trips, chunk_cap: int):
    """THE ordering and the trip bounds of a ragged shard-local cohort,
    for the program and for the host's count of it alike (numpy or jax,
    as batch_trips): `order` sorts the lanes by `trips` [k], descending
    and stable, and `bounds` [n_chunks] is the longest client of each
    chunk once `pad_and_chunk` cuts the ordered lanes (its zero-weight
    fill counts 0).  Ordered, a round's chunks run Σ bounds batch trips,
    near the fewest any grouping of these clients allows; as sampled, a
    long client drags a short neighbour's lane through its padding."""
    xp = jnp if isinstance(trips, jax.Array) else np
    chunk, pad = chunk_shape(trips.shape[0], chunk_cap)
    order = xp.argsort(-trips, stable=True)
    lanes = xp.concatenate([trips[order], xp.zeros((pad,), trips.dtype)])
    return order, lanes.reshape(-1, chunk).max(axis=1)


def default_chunk(local_dtype) -> int:
    """Measured v5e chunk optima (PERF.md §6 "Before PR 22"): the
    L-curve bottoms at 2 with bf16 local masters (1.851 s/round vs 2.080
    at 4, 1.920 at 1); with f32 masters the F-curve bottoms at 8."""
    return 2 if local_dtype == jnp.bfloat16 else 8


def flatten_stack_x(shards: dict):
    """flat_stack flatten (host-side view): image x [C, B, bs, h, w(, c)]
    -> [C, B, bs, prod]; returns (shards, image_shape) with
    image_shape None when x is not image-shaped.  Rationale in
    MeshFedAvgEngine.__init__ (flat_stack)."""
    x = np.asarray(shards["x"]) if "x" in shards else None
    if x is None or x.ndim < 5:
        return shards, None
    return {**shards, "x": x.reshape(x.shape[:3] + (-1,))}, x.shape[3:]


def restore_chunk_x(image_shape, chunk_shards: dict) -> dict:
    """Undo flatten_stack_x on one in-scan chunk slice: [chunk, B, bs, F]
    -> [chunk, B, bs, *image].  Exact (a reshape), O(chunk) memory."""
    if image_shape is None or "x" not in chunk_shards:
        return chunk_shards
    x = chunk_shards["x"]
    return {**chunk_shards, "x": x.reshape(x.shape[:3] + tuple(image_shape))}


def restore_shard_x(image_shape, shard: dict) -> dict:
    """Undo flatten_stack_x on ONE client's shard: [B, bs, F] ->
    [B, bs, *image] (the per-worker/per-client variant of
    restore_chunk_x — gossip's worker loop and the mesh local-eval hook
    both restore at this granularity)."""
    if image_shape is None or "x" not in shard:
        return shard
    x = shard["x"]
    return {**shard, "x": x.reshape(x.shape[:2] + tuple(image_shape))}


def restore_flat_eval_shard(image_shape, shard: dict) -> dict:
    """evaluate_local's per-client restore guard, shared by EVERY engine
    whose resident stack stores x flat (mesh + gossip — ADVICE r4): the
    vmapped eval reuses that stack, so restore [B, bs, F] ->
    [B, bs, *image] in-program; uploaded unflattened stacks pass
    through on the ndim check."""
    if image_shape is not None and "x" in shard and shard["x"].ndim == 3:
        return restore_shard_x(image_shape, shard)
    return shard


def chunked_weighted_train(trainer, variables, cohort, weights, rngs,
                           epochs, vary_axes, chunk_cap: int = 8,
                           client_transform=None,
                           emit_flat_params: bool = False,
                           restore_x=None, ragged_batches: bool = False):
    """Train a shard-local cohort as a lax.scan over chunks of at most
    `chunk_cap` vmapped clients, accumulating Σ w·v / Σ w / Σ w·loss in the
    carry — the HBM-bounded inner loop shared by the flat and hierarchical
    mesh engines (measured on v5e: see MeshFedAvgEngine docstring).

    `variables` must already carry the vma types of `vary_axes` (pvary'd by
    the caller); the f32 accumulators are pvary'd here to match.  Returns
    (num_tree_f32, den, loss_sum, counters) — the caller applies its own
    psum tier(s).

    Where the trainer's model freezes part of its parameters
    (ClientTrainer.split_frozen) only the trained leaves are per-client
    state: `variables` is closed over by the vmapped client function, so
    the frozen leaves enter a chunk un-mapped and every client of it reads
    the same buffers; each client's working copy, the Σ w·v carry and
    `num_tree_f32` hold the trained leaves alone (the structure of
    `trainer.trained_variables(variables)`).  The last value returned is
    always what the model counted in its forward passes
    (ClientTrainer.counters), summed over the cohort's real steps: {} for
    a model that counts nothing.

    With `emit_flat_params` the scan ALSO emits each client's trained
    params flattened to an f32 row (ops/aggregate tile padding), returned
    as a fourth value [n_chunks, chunk, P] — the order-statistic robust
    defenses consume this; rows are in the cohort's order as it arrived
    (any chunk-pad lanes sit at the flattened tail).

    A cohort whose size is not a chunk multiple is padded IN-PROGRAM with
    zero-weight lanes (pad_and_chunk), so chunk stays at the cap instead
    of degenerating to small divisors for awkward (e.g. prime) cohort
    sizes.

    `ragged_batches` says that the population leaves batches of the
    stack empty (what the caller's engine saw in its resident masks: a
    fact of the data, not a preference).  The cohort is then ordered by
    the batch trips each client needs (batch_trips, from its mask, in-
    program; a zero-weight lane needs none) before it is chunked —
    cohort, weights and rngs permuted together, so a client trains on
    its own batches with its own rng whatever lane it lands in — and
    each chunk's batch loop stops at the chunk's own longest client
    (order_by_trips; ClientTrainer.local_train's `batch_bound`, one
    scalar for the vmapped lanes, the pmax over `batch_axes` where the
    batch is split).  Every step left out was a numeric no-op, so each
    client's trained weights are bitwise the static loop's; Σ w·v folds
    the clients in another order and agrees to float32 rounding.  With
    every client filling all its batches the flag is False and the
    program is the static one: no sort, no bound, the `unroll` kept.
    """
    from fedml_tpu.ops.aggregate import flatten_stacked_tree
    global_params = variables["params"] if trainer.prox_mu > 0 else None

    def one(shard, crng, bound):
        v, loss, _n, counts = trainer.local_train_counted(
            variables, shard, crng, epochs, global_params=global_params,
            batch_bound=bound)
        return trainer.trained_variables(v), loss, counts

    # The Σ w·v carry: leaves under BIG_CARRY_LEAF elements packed into
    # ONE f32 vector (flatten_carry_f32: a pytree carry costs them a
    # relayout copy every trip), each leaf of at least that size an
    # accumulator of its own in the leaf's shape.  Packing a large matrix
    # costs what it saves the small ones: a relayout of the whole leaf
    # to one dimension every trip, then the concatenation as a second
    # f32 copy of the tree — at 0.5 B parameters two 2 GB temporaries
    # and five passes over them a trip, where the in-place multiply-add
    # is one.  A model with no such leaf (every conv kernel, the LSTM)
    # carries the one vector and compiles to the program it had.  Either
    # way each element sees the same adds in the same order.
    leaves, treedef = jax.tree.flatten(trainer.trained_variables(variables))
    big = [int(np.prod(a.shape)) >= BIG_CARRY_LEAF for a in leaves]
    packed_spec = [a for a, b in zip(leaves, big) if not b]

    def split(tree_leaves):
        return ([a for a, b in zip(tree_leaves, big) if not b],
                [a for a, b in zip(tree_leaves, big) if b])

    def chunk_body(carry, xs):
        (num_flat, num_big), den, lsum, csum = carry
        cs, cw, cr, bound = xs
        if restore_x is not None:      # flat_stack: image shape back,
            cs = restore_x(cs)         # O(chunk) per trip
        vs, losses, counts = jax.vmap(one, in_axes=(0, 0, None))(cs, cr, bound)
        csum = jax.tree.map(lambda a, c: a + jnp.sum(c, axis=0), csum, counts)
        with jax.named_scope(scopes.FED_AGGREGATE):
            if client_transform is not None:
                vs = jax.vmap(client_transform, in_axes=(0, 0, None))(
                    vs, cw, trainer.trained_variables(variables))
            packed, own = split(jax.tree.leaves(weighted_sum_tree(cw, vs)))
            num_flat = num_flat + flatten_carry_f32(packed)[0]
            num_big = [acc + v for acc, v in zip(num_big, own)]
            ys = (flatten_stacked_tree(vs["params"])[0]
                  if emit_flat_params else None)
            return ((num_flat, num_big), den + jnp.sum(cw),
                    lsum + jnp.sum(losses * cw), csum), ys

    with jax.named_scope(scopes.FED_AGGREGATE):
        packed0, own0 = split([jnp.zeros(a.shape, jnp.float32)
                               for a in leaves])
        zeros = pvary_tree((flatten_carry_f32(packed0)[0], own0), vary_axes)
        zf = pvary_tree(jnp.float32(0), vary_axes)
    zc = pvary_tree({name: jnp.zeros(shape, jnp.float32)
                     for name, shape in trainer.counters.items()}, vary_axes)
    # fed_local_train spans the chunk scan with its plumbing (chunking,
    # the while itself, the flat_stack restore, the per-client training);
    # the aggregation fold inside the body belongs to its own, inner scope
    with jax.named_scope(scopes.FED_LOCAL_TRAIN):
        k_local, order, bounds = weights.shape[0], None, None
        if ragged_batches:
            trips = batch_trips(cohort["mask"])
            if trainer.batch_axes:     # a batch shard sees bs/n samples
                trips = jax.lax.pmax(trips, trainer.batch_axes)
            order, bounds = order_by_trips(
                jnp.where(weights > 0, trips, 0), chunk_cap)
            cohort, weights, rngs = jax.tree.map(
                lambda a: a[order], (cohort, weights, rngs))
        cohort, weights, rngs = pad_and_chunk(cohort, weights, rngs,
                                              chunk_cap)
        ((num_flat, num_big), den, lsum, csum), flats = jax.lax.scan(
            chunk_body, (zeros, zf, zf, zc), (cohort, weights, rngs, bounds))
    with jax.named_scope(scopes.FED_AGGREGATE):
        packed = iter(unflatten_carry_f32(num_flat, packed_spec))
        own = iter(num_big)
        num = jax.tree.unflatten(
            treedef, [next(own) if b else next(packed) for b in big])
    if emit_flat_params:
        if order is not None:          # rows back where the cohort had them
            rows = flats.reshape(-1, flats.shape[-1])
            flats = rows.at[order].set(rows[:k_local]).reshape(flats.shape)
        return num, den, lsum, flats, csum
    return num, den, lsum, csum


class MeshFedAvgEngine(FedAvgEngine):
    """FedAvg with the cohort sharded over a `jax.sharding.Mesh`.

    `chunk` caps how many client model replicas are live at once on each
    shard: the per-shard cohort is processed as a lax.scan over groups of
    `chunk` vmapped clients, weighted-sums accumulated in the scan carry.
    Measured on a v5e chip (PERF.md §6 "Before PR 22"): 128 concurrent
    ResNet-18 replicas run 3.72 s/round; chunked at 8 the same round is
    2.31 s — the full-width vmap blows the HBM working set.

    `streaming=True` keeps the client stack on HOST and uploads only each
    round's sampled cohort (breaks the HBM-resident wall for cross-device
    scale: 3,400-client femnist, 342,477-client stackoverflow —
    reference benchmark/README.md:54-57 — without holding every shard in
    device memory).

    `local_dtype=jnp.bfloat16` runs the LOCAL training loop on bf16 master
    weights: the round's global f32 variables are cast once per round, so
    the per-step f32→bf16 cast inside the loss becomes a no-op and grads,
    optimizer updates and the 13-step weight chain stay bf16 end-to-end.
    Aggregation is unchanged — each client's final weights enter the Σ w·v
    psum in f32, and the global model stays f32 across rounds (the server
    average's small increments need the f32 grid; the 13 local steps at
    lr≫ulp do not).  Measured on v5e: 2.310 → 2.080 s/round at chunk 4
    (bf16 masters at chunk 4 against f32 masters at chunk 8).

    A population that leaves batches of the client stack empty (clients
    of unequal size: the engine reads it off the resident masks at
    construction) gets its cohort ordered by the batch trips each client
    needs, and each chunk's batch loop ends at the chunk's own longest
    client instead of the stack's cap (chunked_weighted_train,
    `ragged_batches`): the steps left out were numeric no-ops.  An
    equal-sized population compiles to the static loop.  Measured on
    v5e: PERF.md §6 PR 29.

    A mesh with a "batch" axis (make_mesh_batch) additionally splits each
    client's per-step batch over that axis — per-client SAMPLE parallelism
    for when chips outnumber the cohort.  The trainer completes each
    step's gradient with one psum over the batch axis (ClientTrainer
    batch_axes; set here automatically), so per-client weights stay
    replicated along it and the round's result equals the unsplit run.
    The cohort pads/shards over the CLIENT axes only.  Models whose
    normalization is per-sample (GroupNorm/LayerNorm — incl. the flagship
    ResNet-18-GN) are oracle-equal to the unsplit run; plain BatchNorm
    would normalize by shard-local statistics, so engines reject a
    batch_stats collection under a batch axis unless
    `allow_batch_stats=True` asserts the model's BN is the cross-replica
    variant bound to the "batch" axis (models/norms.py::sync_batch_norm
    with axis_name="batch")."""

    def __init__(self, trainer: ClientTrainer, data: FederatedData,
                 cfg: FedConfig, mesh: Optional[Mesh] = None,
                 donate: bool = True, chunk: Optional[int] = None,
                 streaming: bool = False, local_dtype=None,
                 stack_dtype=None, flat_stack: bool = True,
                 stream_block: Optional[int] = None,
                 allow_batch_stats: bool = False,
                 prefetch: bool = True):
        self.allow_batch_stats = allow_batch_stats
        # prefetch: background-thread host→device upload pipeline on the
        # streaming/block-stream paths (parallel/prefetch.py): the host
        # gather+cast+device_put of block/cohort k+1 runs while the
        # device trains on k — double-buffered, so device data memory
        # keeps the synchronous path's O(2·block) bound.  False is the
        # --no_prefetch escape hatch: strictly synchronous
        # upload→compute, bitwise-identical results (same jitted
        # programs, same inputs — pinned by tests/test_prefetch.py).
        self.prefetch = prefetch
        # upload/compute overlap accounting, always on (two perf_counter
        # calls per event): overlap_fraction and the H2D byte counts
        # (tests/test_prefetch.py, tests/test_parallel_stream.py)
        self.transfer_stats = TransferOverlapStats()
        # flat_stack stores image cohorts as [C, B, bs, h*w*c] on device
        # and restores [h, w, c] per chunk INSIDE the scan: XLA assigns
        # the big input a tiled layout padded on small minor dims —
        # measured on v5e at the 2048-client bf16 cohort: a 4x-padded
        # relayout copy (bf16[2048,13,32,32,32,3] -> 20.9 GB vs 5.2 GB
        # unpadded) that OOMs 15.75 GB HBM in compile.  The flat layout
        # tiles cleanly (minor dim h*w*c = 3072 = 24*128); only the
        # O(chunk) slice materializes in image layout per scan trip.
        self.flat_stack = flat_stack
        self._x_image_shape = None
        # stack_dtype stores the client stack's INPUT leaf ("x") in this
        # dtype on device — bf16 halves the cohort's HBM footprint and
        # upload bytes, which is what prices in past ~512 bench-shaped
        # clients per chip (measured: the 1024-client knee flattens from
        # 1.32x to 1.06x per client — PERF.md/SCALING.md).  Only "x" is
        # cast: y is integral, and mask must stay f32 (bf16 0/1 sums
        # lose exactness past 256 — sample counts feed the aggregation
        # weights).  Opt-in: inputs at bf16 precision is an accuracy
        # tradeoff the user chooses (tests pin closeness to f32).
        #
        # stack_dtype=uint8 is the transfer-compression tier below bf16
        # (PERF.md "Transfer compression"): the input leaf is stored as
        # uint8 + an affine DequantSpec (data/quant.py) — 4x fewer H2D
        # bytes than f32, 2x fewer than bf16 — and the dequantize
        # (u*scale + offset, f32) is FUSED into the jitted round program
        # as the first op of the block/chunk scan (_dequant_chunk_x via
        # the restore_x hook), so local training still runs the
        # committed float compute recipe.  A loader-quantized stack
        # (load_data store_uint8 / data.x_dequant) passes through as-is;
        # a float stack is quantized ONCE here with a min/max spec.
        self.stack_dtype = stack_dtype
        self._stack_dtype_noop_warned = False
        self._x_dequant = None          # DequantSpec when the stack is u8
        self._u8_host_shards = None     # quantized host view (data stays
        #                                 untouched — it may be shared)
        self._stack_u8 = (stack_dtype is not None
                          and np.dtype(stack_dtype) == np.dtype(np.uint8))
        self.mesh = mesh if mesh is not None else make_mesh()
        # a "batch" mesh axis splits each client's per-step batch over
        # devices (per-client sample parallelism: mesh.py BATCH_AXIS, the
        # chips>cohort scaling axis).  The cohort pads to the CLIENT axes
        # only; the trainer gains a per-step grad psum over the batch axes.
        self.client_axes = client_axes(self.mesh)
        self.batch_axes = tuple(a for a in self.mesh.axis_names
                                if a == BATCH_AXIS)
        self.n_shards = client_shard_count(self.mesh)
        if self.batch_axes:
            nb = self.mesh.shape[BATCH_AXIS]
            bs = int(np.shape(data.client_shards["mask"])[2])
            if bs % nb:
                raise ValueError(
                    f"batch mesh axis ({nb}) must divide the per-step "
                    f"batch size ({bs})")
            if getattr(trainer, "batch_axes", ()) != self.batch_axes:
                import copy
                trainer = copy.copy(trainer)
                trainer.batch_axes = self.batch_axes
        if chunk is not None and chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        self.chunk = chunk if chunk is not None else default_chunk(local_dtype)
        # stream_block: block-streamed rounds — the cohort is uploaded in
        # blocks of `stream_block` clients WITHIN the round (double-
        # buffered), the linear sums accumulating on device across block
        # steps.  Device data memory becomes O(stream_block) instead of
        # O(cohort): the cohort axis is bounded by host RAM and upload
        # bandwidth only, not HBM (SCALING.md).  Implies streaming.
        if stream_block is not None:
            streaming = True
        self.stream_block = stream_block
        self.streaming = streaming
        self.local_dtype = local_dtype
        # a loader-quantized stack (store_uint8) arrives uint8 with its
        # spec on the data object: honor it even without the knob — the
        # dequant is a correctness requirement, not a preference
        if (not self._stack_u8 and getattr(data, "x_dequant", None)
                is not None and "x" in data.client_shards
                and np.asarray(data.client_shards["x"]).dtype == np.uint8):
            self._stack_u8 = True
        if self._stack_u8:
            self._prepare_uint8_stack(data)
        super().__init__(trainer, data, cfg, donate=donate)
        # a client whose last real batch comes before the stack's last
        # leaves trips that train nothing: where any client does, the
        # round program orders its cohort and bounds each chunk's batch
        # loop; where none does (equal-sized clients) it is the static
        # program (population_trips)
        self._client_trips, ragged = population_trips(data)
        self._ragged_batches = self._bounds_batch_loop and ragged
        self._stack = None           # sharded client stack, uploaded lazily
        self._stack_weights = None
        # stack/stack_w are explicit (pre-sharded) args, not closed-over
        # constants, so the jit never embeds the dataset in the program.
        # ISSUE 12: every engine names its jit-program FAMILY — the
        # hlo_copy_audit taxonomy (fedavg_resident/fedavg_streaming/
        # fedavg_blockstream, subclass stems override) — and its round
        # programs dispatch through the obs/programs.py profile
        # registry: per-family dispatch counts + host-wall histograms +
        # compile attribution, values untouched (obs-off results stay
        # bitwise, the standing pins)
        self.program_family = self._program_family_name(streaming,
                                                        stream_block)
        # what the model asks of the compiler of this mesh's platform
        # (`round_compiler_options`): nothing for a model that names none
        options = self.round_compiler_options() or None
        self.round_fn = obs_programs.instrument(
            self.program_family,
            jax.jit(self._mesh_round,
                    donate_argnums=(0, 1) if donate else (),
                    compiler_options=options),
            on_result=self._keep_counters)
        # streaming variant: the gather happened on host; cohort arrives
        # pre-sharded [K, ...] with K = padded cohort size.  This public
        # entry donates variables/server_state ONLY — chip_smoke.py and
        # the convergence tools upload one cohort and replay it for every
        # round, so the cohort args must survive the call.
        self.round_fn_streaming = obs_programs.instrument(
            self.program_family,
            jax.jit(self._mesh_round_streaming,
                    donate_argnums=(0, 1) if donate else (),
                    compiler_options=options))
        # ...but the run() loop gathers a FRESH cohort every round
        # (_round_args), each consumed exactly once — donate it too, so
        # a retired cohort's HBM is recycled into the round instead of
        # sitting next to the prefetched next one (same rationale as the
        # block-step input donation; results are bitwise donate-on/off,
        # pinned in tests/test_parallel_stream.py)
        self._round_fn_streaming_consume = obs_programs.instrument(
            self.program_family,
            jax.jit(self._mesh_round_streaming,
                    donate_argnums=(0, 1, 2, 3) if donate else (),
                    compiler_options=options),
            on_result=self._keep_counters)
        if streaming:
            self.round_fn = self._round_fn_streaming_consume
        if self.stream_block is not None:
            if self.stream_block < 1 or self.stream_block % self.n_shards:
                raise ValueError(
                    f"stream_block ({self.stream_block}) must be a "
                    f"positive multiple of the mesh's client-shard count "
                    f"({self.n_shards})")
            # block accumulation step + round finalize: two small jitted
            # programs the host loop drives per round.  The accumulators
            # (argnum 1) are donated so the sums carry through without
            # copies; the block inputs (2-4) are donated too — each is
            # consumed exactly once, and without donation a retired
            # block would stay resident in HBM next to the prefetched
            # one, breaking the O(2·block) device-data bound
            self._block_step = obs_programs.instrument(
                self.program_family,
                jax.jit(self._block_step_impl,
                        donate_argnums=(1, 2, 3, 4)))
            # sums (argnum 2) is engine-internal and dead after finalize
            # — always donated; variables/server_state follow the
            # user-visible donate flag
            self._block_finalize = obs_programs.instrument(
                self.program_family,
                jax.jit(self._block_finalize_impl,
                        donate_argnums=(0, 1, 2) if donate else (2,)))
            self.round_fn = self._round_blockstream


    def round_compiler_options(self) -> dict:
        """{option: value} for the compiler of the round programs: what the
        model names for the platform of this mesh's devices
        (``compiler_options`` = {platform: {option: value}} beside
        ``trainable``; a compiler refuses another platform's options).
        Empty for a model that names none: its programs and their cache
        keys are what they were."""
        named = getattr(self.trainer.model, "compiler_options", None) or {}
        return dict(named.get(self.mesh.devices.flat[0].platform, {}))

    # jit-program family stem (ISSUE 12): subclasses override so their
    # profile rows and compile attribution name the right family in the
    # hlo_copy_audit taxonomy
    _family_stem = "fedavg"
    # an engine whose chunk body is its own (FedNova's) runs the static
    # batch loop whatever the population
    _bounds_batch_loop = True

    def _program_family_name(self, streaming: bool,
                             stream_block) -> str:
        if stream_block is not None:
            return f"{self._family_stem}_blockstream"
        if streaming:
            return f"{self._family_stem}_streaming"
        return f"{self._family_stem}_resident"

    def _keep_counters(self, result) -> None:
        """What the round's model counted (ClientTrainer.counters; the
        round program returns the sums in its metrics) goes to
        `transfer_stats`, as device arrays: read when somebody asks."""
        names = self.trainer.counters
        if names:
            self.transfer_stats.add_program_counters(
                {name: result[2][name] for name in names
                 if name in result[2]})

    # -- hooks ---------------------------------------------------------------
    def client_transform(self, client_variables: Pytree, weight: jax.Array,
                         global_variables: Pytree) -> Pytree:
        """Per-client post-training hook (vmapped inside the shard). Robust
        engines clip here; FedAvg is identity."""
        return client_variables

    def server_update(self, avg_variables: Pytree, global_variables: Pytree,
                      server_state: Pytree, rng: jax.Array):
        """Replicated server-side update applied to the psum'd average.
        FedAvg installs the average directly (FedAVGAggregator.py:59-88)."""
        return avg_variables, server_state

    # -- device data ----------------------------------------------------------
    def _prepare_uint8_stack(self, data) -> None:
        """uint8 cohort storage (stack_dtype=uint8): resolve the dequant
        spec and the uint8 HOST view of the client stack, ONCE at
        construction.  A loader-quantized stack (data.x_dequant) passes
        through; a float stack is quantized here with a min/max spec —
        into a separate view, never mutating `data` (test oracles and
        sibling engines share the data object).  Eager so the spec is
        set on the construction thread before any jit trace or prefetch
        worker reads it."""
        from fedml_tpu.data.quant import quantize_uint8, spec_from_minmax
        shards = data.client_shards
        x = np.asarray(shards["x"]) if "x" in shards else None
        if x is None or (x.dtype != np.uint8
                         and not np.issubdtype(x.dtype, np.floating)):
            self._stack_u8 = False
            if x is not None and not self._stack_dtype_noop_warned:
                self._stack_dtype_noop_warned = True
                log.warning(
                    "stack_dtype=uint8 ignored: the input leaf is %s "
                    "(integer token-id datasets must not be quantized)",
                    x.dtype)
            return
        if x.dtype == np.uint8:
            spec = getattr(data, "x_dequant", None)
            if spec is None:
                raise ValueError(
                    "client stack x is uint8 but data.x_dequant is unset: "
                    "a uint8 stack needs its DequantSpec (load_data "
                    "store_uint8=True sets it)")
            self._u8_host_shards = shards
        else:
            spec = spec_from_minmax(x)
            self._u8_host_shards = {**shards, "x": quantize_uint8(x, spec)}
        self._x_dequant = spec

    def _host_shards(self) -> dict:
        """The host-side client stack every upload path gathers from:
        the uint8-quantized view when stack_dtype=uint8, else the data's
        own shards."""
        return (self._u8_host_shards if self._u8_host_shards is not None
                else self.data.client_shards)

    def _cast_stack_x(self, shards: dict) -> dict:
        """Apply stack_dtype to the input leaf (see __init__); identity
        when unset — and for INTEGER inputs (token ids on the text
        datasets): bf16 represents integers exactly only up to 256, so
        casting ids would silently remap most of a 10k vocabulary.
        The uint8 tier never casts here: `_host_shards` is already
        quantized (once, at construction)."""
        if (self.stack_dtype is not None and not self._stack_u8
                and "x" in shards):
            if np.issubdtype(np.asarray(shards["x"]).dtype, np.floating):
                shards = dict(shards)
                shards["x"] = np.asarray(shards["x"],
                                         jnp.dtype(self.stack_dtype))
            elif not self._stack_dtype_noop_warned:
                self._stack_dtype_noop_warned = True
                log.warning(
                    "stack_dtype=%s ignored: the input leaf is %s (token-id "
                    "datasets keep integer inputs — casting would remap the "
                    "vocabulary)", self.stack_dtype,
                    np.asarray(shards["x"]).dtype)
        if self.flat_stack:
            shards, image_shape = flatten_stack_x(shards)
            if image_shape is not None:
                self._x_image_shape = image_shape
        return shards

    def _dequant_chunk_x(self, shards: dict) -> dict:
        """In-program dequantize of a uint8 input slice — the FIRST op
        of the block/chunk scan body (after the flat_stack restore, so a
        per-channel spec broadcasts over [..., h, w, c]).  Identity when
        the stack is not quantized, and for float leaves (the local-eval
        fallback stacks stay f32)."""
        if self._x_dequant is None or "x" not in shards:
            return shards
        x = shards["x"]
        if not jnp.issubdtype(x.dtype, jnp.integer):
            return shards
        scale = jnp.asarray(self._x_dequant.scale, jnp.float32)
        offset = jnp.asarray(self._x_dequant.offset, jnp.float32)
        return {**shards, "x": x.astype(jnp.float32) * scale + offset}

    def _restore_chunk_x(self, chunk_shards: dict) -> dict:
        """Undo flat_stack on one in-scan chunk slice (restore_chunk_x),
        then dequantize a uint8 slice — O(chunk) memory either way."""
        return self._dequant_chunk_x(
            restore_chunk_x(self._x_image_shape, chunk_shards))

    def _local_eval_transform(self, shard: dict) -> dict:
        """Per-client shard hook inside evaluate_local's vmap (shared
        flat_stack restore guard — restore_flat_eval_shard — plus the
        uint8 dequant when the resident stack is quantized)."""
        return self._dequant_chunk_x(
            restore_flat_eval_shard(self._x_image_shape, shard))

    def _device_stack(self):
        """Upload the [C,...] client stack ONCE, leading axis sharded over the
        mesh (C padded to a mesh-size multiple with zero-weight clients)."""
        if self._stack is None:
            from fedml_tpu.parallel.mesh import pad_cohort
            shards, weights = self._host_shards(), self.data.client_num_samples
            shards, weights = pad_cohort(
                self._cast_stack_x(dict(shards)),
                np.asarray(weights, np.float32), self.n_shards)
            self.transfer_stats.add_h2d_bytes(
                sum(np.asarray(v).nbytes for v in shards.values())
                + weights.nbytes)
            self._stack = shard_stack(self.mesh, shards)
            self._stack_weights = jax.device_put(
                weights.astype(np.float32), client_sharding(self.mesh))
        return self._stack, self._stack_weights

    def _upload_eval_stack(self, shards):
        """Per-client eval stacks ride the mesh too: pad the client axis
        to a mesh multiple (mask-0 lanes add nothing to the eval sums)
        and shard it — the train stack needed sharding to fit, so the
        test stack gets the same treatment (ADVICE r2)."""
        from fedml_tpu.parallel.mesh import pad_cohort
        C = jax.tree.leaves(shards)[0].shape[0]
        shards, _ = pad_cohort(dict(shards),
                               np.zeros(C, np.float32), self.n_shards)
        return shard_stack(self.mesh, shards)

    # -- the round program ----------------------------------------------------
    def _shard_sums(self, variables, cohort, weights, client_rngs):
        """Per-shard cohort training (chunked_weighted_train) + one psum
        tier over the mesh: returns the REPLICATED (Σ w·v, Σ w, Σ w·loss)
        — the linear core shared by the whole-cohort round (_shard_body)
        and the block-streamed round (_round_blockstream), which
        accumulates these sums across blocks before dividing.  Σ w·v
        covers the leaves the round trains (all of them, unless the
        model freezes some); the fourth value is what the model counted
        (ClientTrainer.counters; {} for most)."""
        axes = self.mesh.axis_names
        # the global model arrives replicated; per-client training makes
        # it shard-varying, so cast up-front for the vma type system
        with jax.named_scope(scopes.FED_LOCAL_TRAIN):
            variables = pvary_tree(variables, axes)
            # frozen leaves stay in the dtype they are stored in
            local_vars = self.trainer.with_frozen(
                cast_local(self.trainer.trained_variables(variables),
                           self.local_dtype), variables)
        sums = chunked_weighted_train(
            self.trainer, local_vars, cohort, weights, client_rngs,
            self.cfg.epochs, vary_axes=axes, chunk_cap=self.chunk,
            client_transform=self.client_transform,
            restore_x=self._restore_chunk_x,
            ragged_batches=self._ragged_batches)
        with jax.named_scope(scopes.FED_AGGREGATE):
            return tuple(jax.lax.psum(s, axes) for s in sums)

    def _zero_sums(self, variables):
        """Zero accumulators matching _shard_sums' output structure (the
        block-streamed round's carry; engines with extra linear sums —
        FedNova's tau — override the triple together)."""
        return (jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32),
                             self.trainer.trained_variables(variables)),
                jnp.float32(0), jnp.float32(0),
                {name: jnp.zeros(shape, jnp.float32)
                 for name, shape in self.trainer.counters.items()})

    def _finalize_from_sums(self, variables, sums):
        """(aggregated model, mean loss, counters) from the accumulated
        linear sums — pure math, shared verbatim by the whole-cohort shard
        body and the block-streamed finalize.  The mean covers the leaves
        the round trains; the caller puts it back beside the frozen ones
        (`_install`)."""
        num, den, lsum, counters = sums
        avg = jax.tree.map(
            lambda s, ref: (s / den).astype(ref.dtype), num,
            self.trainer.trained_variables(variables))
        return avg, lsum / den, counters

    def _install(self, avg, variables, server_state, agg_rng):
        """The round's tail, at the top level of its jitted program: the
        aggregate beside the frozen leaves of `variables` — the program's
        own donated arguments, which the output then aliases: they come
        back in the buffers they came in — then the server update."""
        with jax.named_scope(scopes.FED_SERVER_UPDATE):
            return self.server_update(
                self.trainer.with_frozen(avg, variables), variables,
                server_state, agg_rng)

    def _shard_body(self, variables, cohort, weights, client_rngs):
        """Whole-cohort round body: the two-collective FedAvg aggregation
        (SURVEY.md §5) — sums then the weighted mean."""
        sums = self._shard_sums(variables, cohort, weights, client_rngs)
        with jax.named_scope(scopes.FED_AGGREGATE):
            return self._finalize_from_sums(variables, sums)

    def _train_and_update(self, variables, server_state, cohort, weights,
                          rng):
        """Common round tail for the resident and streaming entry points:
        shard_map the chunked cohort training, then the (replicated) server
        update — so subclass overrides of _shard_body/server_update apply to
        BOTH paths identically."""
        mesh = self.mesh
        csh = P(self.client_axes)
        cohort_specs = {k: stack_leaf_spec(mesh, v)
                        for k, v in cohort.items()}
        rng, agg_rng = jax.random.split(rng)
        client_rngs = jax.random.split(rng, weights.shape[0])
        avg, train_loss, counters = jax.shard_map(
            self._shard_body, mesh=mesh,
            in_specs=(P(), cohort_specs, csh, csh), out_specs=P())(
                variables, cohort, weights, client_rngs)
        new_variables, server_state = self._install(
            avg, variables, server_state, agg_rng)
        return new_variables, server_state, {"train_loss": train_loss,
                                             **counters}

    def _mesh_round(self, variables, server_state, stack, stack_w, ids,
                    wmask, rng):
        # device-side cohort take: per-slot slices of the resident stack on
        # one shard, a cross-shard gather (ICI collectives) on several
        cohort, weights = take_cohort(self.mesh, stack, stack_w, ids, wmask)
        return self._train_and_update(variables, server_state, cohort,
                                      weights, rng)

    def _mesh_round_streaming(self, variables, server_state, cohort, weights,
                              rng):
        """Streaming round: the cohort was gathered on HOST (only the
        sampled clients' shards were uploaded, sharded over the mesh) — the
        device never holds the full client stack."""
        return self._train_and_update(variables, server_state, cohort,
                                      weights, rng)

    # -- two-level (multi-host) aggregation programs (ISSUE 13) --------------
    # The multihost runner (parallel/multihost.py) decomposes a round
    # into per-block PARTIALS (this engine's linear sums, psum'd over
    # the LOCAL mesh only — the ICI tier) and one replicated COMMIT
    # after the host-level inter-process fold of the P-sized flat
    # carries (the DCN tier).  The partial returns the carry FLAT
    # (flatten_carry_f32 over the engine's sums pytree) because the
    # flat f32 vector is exactly what crosses hosts; the commit
    # unflattens, divides, and applies the server update — so subclass
    # overrides of _shard_sums/_zero_sums/_finalize_from_sums/
    # server_update (FedNova's tau sums, FedOpt's optimizer, robust
    # norm_clip's noise) ride the two-level path unchanged.
    def _ensure_twolevel(self) -> None:
        """Build the two-level programs lazily (most engines never run
        multihost; the extra jits must not tax single-host
        construction)."""
        if getattr(self, "_twolevel_ready", False):
            return
        if getattr(self, "defense", "norm_clip") != "norm_clip":
            raise ValueError(
                f"two-level aggregation is linear: order-statistic "
                f"defense {self.defense!r} cannot fold per-host "
                f"partials (it needs the full [K, P] cohort matrix)")
        fam = f"{self._family_stem}_twolevel"
        # block cohorts are gathered fresh per round and consumed
        # exactly once — donated like the streaming-consume round
        self._twolevel_partial = obs_programs.instrument(
            fam, jax.jit(self._twolevel_partial_impl,
                         donate_argnums=(1, 2, 3) if self.donate
                         else ()))
        self._twolevel_partial_resident = obs_programs.instrument(
            fam, jax.jit(self._twolevel_partial_resident_impl))
        # flat_sums (argnum 2) is NOT donated: a 1-D [S] carry can never
        # alias the variables-shaped outputs, so donating it only buys
        # an unusable-donation warning per compile — unlike the
        # block-finalize sums, whose variables-shaped num tree aliases
        # the averaged output
        self._twolevel_commit = obs_programs.instrument(
            "twolevel_commit",
            jax.jit(self._twolevel_commit_impl,
                    donate_argnums=(0, 1) if self.donate else ()))
        self._twolevel_ready = True

    def _twolevel_partial_body(self, variables, cohort, weights, rngs):
        specs = {k: stack_leaf_spec(self.mesh, v)
                 for k, v in cohort.items()}
        csh = P(self.client_axes)
        sums = jax.shard_map(
            self._shard_sums, mesh=self.mesh,
            in_specs=(P(), specs, csh, csh), out_specs=P())(
                variables, cohort, weights, rngs)
        return flatten_carry_f32(sums)[0]

    def _twolevel_partial_impl(self, variables, cohort, weights, rngs):
        """One block's partial from a host-gathered cohort (streaming
        residency): intra-host psum'd linear sums, returned as ONE flat
        f32 carry — the vector the inter-host allreduce folds."""
        return self._twolevel_partial_body(variables, cohort, weights,
                                           rngs)

    def _twolevel_partial_resident_impl(self, variables, stack, stack_w,
                                        ids, wmask, rngs):
        """Resident variant: the process's id-range stack lives on
        device; the block cohort is a device-side take by LOCAL index.
        Gather values are bitwise the host-gather's, so both residency
        modes feed the identical partial math."""
        cohort, weights = take_cohort(self.mesh, stack, stack_w, ids, wmask)
        return self._twolevel_partial_body(variables, cohort, weights,
                                           rngs)

    def _twolevel_commit_impl(self, variables, server_state, flat_sums,
                              agg_rng):
        """Replicated commit from the globally-folded flat carry:
        unflatten into the engine's sums structure, divide, apply the
        server update — run identically on every host (audited as the
        `twolevel_commit` hlo family: 0 copy ops, donation
        complete)."""
        with jax.named_scope(scopes.FED_AGGREGATE):
            sums = unflatten_carry_f32(flat_sums,
                                       self._zero_sums(variables))
            avg, loss, counters = self._finalize_from_sums(variables, sums)
        new_variables, server_state = self._install(
            avg, variables, server_state, agg_rng)
        return new_variables, server_state, {"train_loss": loss,
                                             **counters}

    @staticmethod
    def _round_attr(round_idx) -> dict:
        """The `round` identifier of an upload's spans (none where the
        caller gathers outside a round: chip_smoke.py, the tools)."""
        return {} if round_idx is None else {"round": int(round_idx)}

    def _host_gather_upload(self, ids, round_idx=None) -> dict:
        """THE host-gather upload pipeline (shared by stream_cohort and
        _upload_block so the two streaming granularities can never
        diverge): slice the host arrays (the uint8 view when the stack
        is quantized — compressed bytes are what cross H2D), apply
        stack_dtype/flat_stack (_cast_stack_x), async device_put with
        per-leaf sharding.  Every byte handed to device_put lands in
        the engine_h2d_bytes_total accounting.  The h2d.put span is the
        host's enqueue and staging, not the transfer."""
        span_attrs = self._round_attr(round_idx)
        with obs.span(scopes.SPAN_GATHER, **span_attrs):
            host = self._cast_stack_x(
                {k: np.take(np.asarray(v), ids, axis=0)
                 for k, v in self._host_shards().items()})
        self.transfer_stats.add_h2d_bytes(
            sum(v.nbytes for v in host.values()))
        with obs.span(scopes.SPAN_PUT, **span_attrs):
            return {k: jax.device_put(v, stack_leaf_sharding(self.mesh, v))
                    for k, v in host.items()}

    def stream_cohort(self, round_idx: int):
        """Host-side cohort gather for the streaming path: the same padded
        sampling as the resident path, but slicing the HOST arrays and
        uploading only the cohort (chunk-multiple padding happens inside
        chunked_weighted_train)."""
        return self._stream_gather(*self._sample_padded_np(round_idx),
                                   round_idx)

    def _stream_gather(self, ids, wmask, round_idx=None):
        """The upload half of stream_cohort, split from the sampling:
        this part is what runs on the prefetch thread (_round_args);
        the sampler's draw stays on the caller thread (milliseconds at
        most, and it decides what the thread is asked to gather).  The
        wall lands in transfer_stats from whichever thread runs it;
        `round_idx` (the round the cohort is FOR) rides its spans."""
        with obs.span("h2d.upload_cohort", clients=len(ids),
                      **self._round_attr(round_idx)), \
                self.transfer_stats.uploading():
            cohort = self._host_gather_upload(ids, round_idx)
            w = self._lane_weights(ids, wmask)
            self.transfer_stats.add_h2d_bytes(w.nbytes)
            weights = jax.device_put(w, client_sharding(self.mesh))
        self._count_batch_trips(ids, w)
        return cohort, weights

    # -- block-streamed round (stream_block) ---------------------------------
    def _block_step_impl(self, variables, sums, block, weights, rngs):
        """One block's contribution: shard_map the engine's linear sums
        (whatever pytree _shard_sums returns) and fold them into the
        round accumulators (donated)."""
        specs = {k: stack_leaf_spec(self.mesh, v) for k, v in block.items()}
        csh = P(self.client_axes)
        bsums = jax.shard_map(
            self._shard_sums, mesh=self.mesh,
            in_specs=(P(), specs, csh, csh), out_specs=P())(
                variables, block, weights, rngs)
        with jax.named_scope(scopes.FED_AGGREGATE):
            return jax.tree.map(lambda a, b: a + b, sums, bsums)

    def _block_finalize_impl(self, variables, server_state, sums, agg_rng):
        with jax.named_scope(scopes.FED_AGGREGATE):
            avg, loss, counters = self._finalize_from_sums(variables, sums)
        new_variables, server_state = self._install(
            avg, variables, server_state, agg_rng)
        return new_variables, server_state, {"train_loss": loss,
                                             **counters}

    def _upload_block(self, ids_blk, w_blk, rngs_blk, round_idx=None):
        """Host-gather + async device_put of one client block (the
        double-buffer unit), via the shared _host_gather_upload pipeline.
        Runs on the prefetch thread when the pipeline is on; the wall
        lands in transfer_stats either way.  The span is produced from
        whichever thread uploads, so on the pipelined path it lands on
        the worker's trace row, interleaved with the round loop's
        block_step spans — the overlap is visible directly."""
        with obs.span("h2d.upload_block", clients=len(ids_blk),
                      **self._round_attr(round_idx)), \
                self.transfer_stats.uploading():
            block = self._host_gather_upload(ids_blk, round_idx)
            self.transfer_stats.add_h2d_bytes(
                np.asarray(w_blk).nbytes + np.asarray(rngs_blk).nbytes)
            weights = jax.device_put(w_blk, client_sharding(self.mesh))
            rngs = jax.device_put(rngs_blk, client_sharding(self.mesh))
        self._count_batch_trips(ids_blk, w_blk)
        return block, weights, rngs

    def _pad_to_block(self, ids, wmask):
        """Pad the shard-padded cohort to a stream_block multiple with
        zero-weight repeated-id lanes, and return the per-round block
        spans [(start, stop), ...]."""
        B = self.stream_block
        pad = (-len(ids)) % B
        if pad:       # pad to a block multiple with zero-weight lanes
            ids = np.concatenate([ids, np.repeat(ids[:1], pad)])
            wmask = np.concatenate([wmask, np.zeros(pad, np.float32)])
        spans = [(s, s + B) for s in range(0, len(ids), B)]
        return ids, wmask, spans

    def _block_fetcher(self, ids, w_all, crngs, spans, round_idx=None):
        """Block iterator for the streamed rounds: the background
        double-buffered upload pipeline (prefetch.py), or the strictly
        synchronous inline path under prefetch=False (--no_prefetch).
        Both deliver blocks in span order via get(); use as a context
        manager so an aborted round joins the worker and drops
        undelivered buffers."""
        def produce(span):
            s, e = span
            return self._upload_block(ids[s:e], w_all[s:e], crngs[s:e],
                                      round_idx)

        cls = Prefetcher if self.prefetch else InlineFetcher
        return cls(produce, spans, stats=self.transfer_stats,
                   wait_attrs=self._round_attr(round_idx))

    def _round_blockstream(self, variables, server_state, round_idx, rng):
        """Block-streamed round: `stream_block`-client blocks cross
        host→device while the jitted block step accumulates
        Σ w·v / Σ w / Σ w·loss on device; one finalize divides and
        applies the server update.  Uploads are double-buffered on a
        background thread (_block_fetcher): the host gather + cast +
        device_put of block k+1 runs while the device trains on block k,
        so round wall approaches max(upload, compute) instead of their
        sum — transfer_stats records the per-round upload/compute walls
        and overlap_fraction.  Aggregation is linear, so the result
        equals the whole-cohort streaming round up to float summation
        order (oracle-pinned in tests/test_parallel.py) and is BITWISE
        prefetch-knob-independent (tests/test_prefetch.py); the
        per-client rngs are the SAME (jax.random.split prefixes are
        stable, and zero-weight pad lanes contribute exactly 0).

        Device data memory is O(2 · stream_block · shard bytes) — the
        cohort axis is unbounded by HBM (block inputs are donated to the
        block step, so retired blocks never stack).  The cost: the
        cohort's bytes cross host→device EVERY round (the resident/
        streaming paths upload once), so this path pays off when the
        cohort does not fit HBM at all, and its round time is bounded
        below by upload bandwidth."""
        ids, wmask = self._sample_padded_np(round_idx)
        ids, wmask, spans = self._pad_to_block(ids, wmask)
        w_all = self._lane_weights(ids, wmask)
        rng, agg_rng = jax.random.split(rng)
        crngs = np.asarray(jax.random.split(rng, len(ids)))
        self.transfer_stats.round_start()
        try:
            with obs.span("round.blockstream", round=int(round_idx),
                          clients=len(ids), blocks=len(spans)):
                sums = jax.device_put(self._zero_sums(variables),
                                      replicated_sharding(self.mesh))
                with self._block_fetcher(ids, w_all, crngs, spans,
                                         round_idx) as fetch:
                    for i, _ in enumerate(spans):
                        args = fetch.get()
                        # dispatch wall only (the jit call is async);
                        # the device wall shows up as the NEXT get()'s
                        # wait when uploads outpace compute
                        with obs.span("round.block_step", block=i):
                            sums = self._block_step(variables, sums, *args)
                with obs.span("round.block_finalize"):
                    return self._block_finalize(variables, server_state,
                                                sums, agg_rng)
        finally:
            self.transfer_stats.round_end()

    # NOTE: a fully on-device multi-round path (`run_scanned`: whole blocks
    # of rounds as one lax.scan program, in-program fold-in sampling) was
    # built and CUT after chip measurement: at ms-scale rounds (LR/MNIST,
    # 1000 clients, 10/round — the regime where amortizing per-round
    # dispatch should pay if it ever does) the jitted per-round loop ran
    # 2.56 ms/round vs 23.8 ms/round scanned (v5e, 2026-07-31;
    # PERF.md §6 "Before PR 22").  The in-scan cohort gather +
    # shard_map compile far worse than the host-dispatched round program,
    # and per-round dispatch is not a bottleneck at any measured scale.
    # -- driver loop ----------------------------------------------------------
    def _sample_padded_np(self, round_idx: int):
        """Sample the round's cohort and pad to a mesh-size multiple
        (pad_ids — the one padding policy shared by the resident,
        streaming, and GAN mesh paths)."""
        with obs.span(scopes.SPAN_SAMPLE, round=int(round_idx)):
            return pad_ids(self.sampler.sample(round_idx), self.n_shards)

    def _lane_weights(self, ids, wmask) -> np.ndarray:
        """The aggregation weights of a round's lanes, as the resident
        take computes them in-program: sample counts, 0 on pad lanes."""
        return np.take(np.asarray(self.data.client_num_samples,
                                  np.float32), ids) * wmask

    def _count_batch_trips(self, ids, live) -> None:
        """Host-side count of the batch trips ONE round program runs on
        these lanes (mesh-padded ids; `live` > 0 where a lane holds a
        client — its wmask or its weight, a client of no samples needs no
        trip either way), beside what the static loop would run:
        `order_by_trips` on the host-known trips of the sampled ids, a
        client shard at a time as the program chunks them — the same
        helper on the same numbers as the program's, so no device sync
        (microseconds; tests pin it to the in-program bounds)."""
        n_batches = np.shape(self.data.client_shards["mask"])[1]
        k_local = len(ids) // self.n_shards
        chunk, pad = chunk_shape(k_local, self.chunk)
        static = self.n_shards * ((k_local + pad) // chunk) * n_batches
        ran = static
        if self._ragged_batches:
            trips = np.where(np.asarray(live) > 0,
                             self._client_trips[np.asarray(ids)], 0)
            ran = sum(int(order_by_trips(t, self.chunk)[1].sum())
                      for t in trips.reshape(self.n_shards, k_local))
        self.transfer_stats.add_batch_trips(self.cfg.epochs * ran,
                                            self.cfg.epochs * static)

    def sample_padded(self, round_idx: int):
        ids, wmask = self._sample_padded_np(round_idx)
        self._count_batch_trips(ids, wmask)
        # the resident round's only per-round host→device put
        with obs.span(scopes.SPAN_ARGS_PUT, round=int(round_idx)):
            return jnp.asarray(ids), jnp.asarray(wmask)

    def _prepare_server_state(self, server_state):
        # via host: a checkpoint-restored state arrives COMMITTED to one
        # local device, and a committed->global device_put would need
        # cross-host transfers (unsupported on the gloo CPU backend);
        # every process holds the full replicated value, so the numpy
        # round-trip makes the placement purely process-local
        sh = replicated_sharding(self.mesh)
        return jax.tree.map(
            lambda a: jax.device_put(np.asarray(a), sh), server_state)

    # the base FedAvgEngine.run drives the loop through these two hooks
    def _prepare_variables(self, variables: Pytree) -> Pytree:
        if self.batch_axes and not self.allow_batch_stats and any(
                k != "params" for k in variables):
            raise ValueError(
                "model carries a stats collection "
                f"({[k for k in variables if k != 'params']}) and the mesh "
                "has a 'batch' axis: plain BatchNorm would normalize by "
                "shard-local statistics.  Use per-sample normalization "
                "(GroupNorm/LayerNorm), or sync_batch_norm(axis_name="
                "'batch') (models/norms.py) and pass "
                "allow_batch_stats=True")
        return jax.device_put(variables, replicated_sharding(self.mesh))

    def _round_args(self, round_idx: int) -> tuple:
        if self.stream_block is not None:
            # block-streamed rounds gather their own blocks on the fly
            return (round_idx,)
        if self.streaming:
            # double-buffered round uploads: round r+1's host gather +
            # cast + device_put (_stream_gather) runs on a background
            # thread (AsyncValue) while round r computes — the HOST side
            # of the upload no longer serializes with the round loop.
            # SAMPLING stays on THIS thread either way (the knob must
            # not change cohorts).
            # With prefetch=False the gather runs inline here, the old
            # synchronous path, recorded as consumer wait (unhidden).
            # Two cohorts live on device, bounded.  The base run()
            # exposes its round budget via _rounds_limit — no gather
            # past the final round, and the last buffer is released.
            # No per-round stats windows here (the round body runs in
            # the caller's loop, out of this hook's sight; a window
            # opened here would span into the NEXT round) — the
            # streaming path reports cumulative walls only; per-round
            # records are a block-stream feature.
            pre = getattr(self, "_prefetched", None)
            if pre is not None and pre[0] != round_idx:
                # stale prefetch (an aborted run retried, or rounds
                # replayed out of order): JOIN the in-flight upload
                # before gathering anew — letting it run unobserved
                # would put a third cohort on device (the documented
                # bound is two).  Its error is logged and dropped
                # (superseded — a fresh gather follows); Exception
                # only, so a Ctrl-C during the join still aborts.
                if isinstance(pre[1], AsyncValue):
                    try:
                        pre[1].result()
                    except Exception:
                        log.warning("discarding failed stale prefetch "
                                    "for round %d", pre[0], exc_info=True)
                pre = None
                self._prefetched = None
            if pre is not None:
                if isinstance(pre[1], AsyncValue):
                    try:
                        args = pre[1].result()
                    except BaseException:
                        # never cache a failed gather: a resumed run
                        # hitting this round again must re-gather
                        # fresh, not re-raise the stale exception
                        self._prefetched = None
                        raise
                else:
                    args = pre[1]
            else:
                with self.transfer_stats.waiting(round=int(round_idx)):
                    args = self.stream_cohort(round_idx)  # unhidden gather
            limit = getattr(self, "_rounds_limit", None)
            if limit is None or round_idx + 1 < limit:
                nxt = round_idx + 1
                if self.prefetch:
                    nxt_ids, nxt_wmask = self._sample_padded_np(nxt)
                    self._prefetched = (
                        nxt, AsyncValue(
                            self._stream_gather, nxt_ids, nxt_wmask, nxt,
                            stats=self.transfer_stats,
                            wait_attrs={"round": nxt}))
                else:
                    with self.transfer_stats.waiting(round=nxt):
                        self._prefetched = (nxt, self.stream_cohort(nxt))
            else:
                self._prefetched = None
            return args
        stack, stack_w = self._device_stack()
        ids, wmask = self.sample_padded(round_idx)
        return (stack, stack_w, ids, wmask)


class MeshFedProxEngine(MeshFedAvgEngine):
    """FedProx on the mesh: the proximal term lives in the trainer's loss
    (reference keeps the same aggregator, fedprox/ mirrors fedavg/)."""

    _family_stem = "fedprox"

    def __init__(self, trainer, data, cfg, **kw):
        if trainer.prox_mu <= 0:
            # don't mutate the caller's (possibly shared) trainer — other
            # engines built on it would silently gain the proximal term
            import copy
            trainer = copy.copy(trainer)
            trainer.prox_mu = cfg.prox_mu
        super().__init__(trainer, data, cfg, **kw)


class MeshFedOptEngine(MeshFedAvgEngine):
    """Server-optimizer FL: pseudo-gradient w_global − w_avg fed to an optax
    server optimizer (FedOptAggregator.py:94-123, optrepo.py:11-39).  The
    optimizer state persists across rounds in server_state."""

    _family_stem = "fedopt"

    def __init__(self, trainer, data, cfg, **kw):
        self.server_tx = make_server_optimizer(
            cfg.server_optimizer, cfg.server_lr, cfg.server_momentum)
        super().__init__(trainer, data, cfg, **kw)

    def server_init(self, variables):
        return self.server_tx.init(variables["params"])

    def server_update(self, avg_variables, global_variables, server_state, rng):
        pseudo_grad = jax.tree.map(lambda g, a: g - a,
                                   global_variables["params"],
                                   avg_variables["params"])
        updates, server_state = self.server_tx.update(
            pseudo_grad, server_state, global_variables["params"])
        new_params = jax.tree.map(lambda p, u: p + u,
                                  global_variables["params"], updates)
        new_vars = dict(avg_variables)   # stats collections take the average
        new_vars["params"] = new_params
        return new_vars, server_state


class MeshFedNovaEngine(MeshFedAvgEngine):
    """FedNova on the mesh — normalized averaging (algorithms/fednova.py,
    reference fednova.py:50-200): d = Σᵢ pᵢ(g−wᵢ)/τᵢ, w_new = g − τ_eff·d
    with τ_eff = Σᵢ pᵢτᵢ.  All three reductions are linear, so the whole
    aggregation stays two psum tiers like FedAvg; the only extra device
    state is one weighted τ accumulator in the chunk-scan carry."""

    _family_stem = "fednova"
    _bounds_batch_loop = False     # _shard_sums below: the static loop

    @staticmethod
    def _split(v):
        return v["params"], {k: x for k, x in v.items() if k != "params"}

    def _shard_sums(self, variables, cohort, weights, client_rngs):
        """FedNova's linear sums: (Σ w·(g−v)/τ, Σ w·stats, Σ w, Σ w·τ,
        Σ w·loss) — same structure contract as the FedAvg triple, so the
        whole-cohort shard body AND the block-streamed round drive it
        through the shared _finalize_from_sums."""
        axes = self.mesh.axis_names
        with jax.named_scope(scopes.FED_LOCAL_TRAIN):
            variables = pvary_tree(variables, axes)
            local_vars = cast_local(variables, self.local_dtype)
        epochs = self.cfg.epochs
        trainer = self.trainer

        from fedml_tpu.algorithms.fednova import fednova_tau

        def one(shard, crng):
            v, loss, _n = trainer.local_train(local_vars, shard, crng,
                                              epochs)
            return v, loss, fednova_tau(shard, epochs, self.batch_axes)

        g_params, _ = self._split(local_vars)

        def chunk_body(carry, xs):
            dflat, rflat, den, tsum, lsum = carry
            cs, cw, cr = xs
            cs = self._restore_chunk_x(cs)      # flat_stack (engine.py)
            vs, losses, taus = jax.vmap(one)(cs, cr)
            with jax.named_scope(scopes.FED_AGGREGATE):
                v_params, v_rest = self._split(vs)
                # params: Σ w·(g − v)/τ  (zero-weight pad lanes contribute
                # 0) — folded into flat f32 carries like
                # chunked_weighted_train (flatten_carry_f32: one 1-D
                # buffer per carry, no per-leaf relayout copies across
                # scan trips)
                coef = cw / jnp.maximum(taus, 1.0)
                d_chunk = jax.tree.map(
                    lambda g, v: jnp.einsum(
                        "k,k...->...", coef,
                        g[None].astype(jnp.float32)
                        - v.astype(jnp.float32)),
                    g_params, v_params)
                dflat = dflat + flatten_carry_f32(d_chunk)[0]
                # stats collections: plain weighted mean, like FedAvg
                rflat = rflat + flatten_carry_f32(
                    weighted_sum_tree(cw, v_rest))[0]
                return (dflat, rflat, den + jnp.sum(cw),
                        tsum + jnp.sum(cw * taus),
                        lsum + jnp.sum(losses * cw)), None

        with jax.named_scope(scopes.FED_AGGREGATE):
            zp, zr = self._split(jax.tree.map(
                lambda a: jnp.zeros(a.shape, jnp.float32), variables))
            zpf, d_spec = flatten_carry_f32(zp)
            zrf, r_spec = flatten_carry_f32(zr)
            zpf, zrf = pvary_tree(zpf, axes), pvary_tree(zrf, axes)
            zf = pvary_tree(jnp.float32(0), axes)
        # scopes as in chunked_weighted_train: fed_local_train spans the
        # chunk scan, the fold inside the body is fed_aggregate's
        with jax.named_scope(scopes.FED_LOCAL_TRAIN):
            ch_cohort, ch_w, ch_r = pad_and_chunk(
                cohort, weights, client_rngs, self.chunk)
            (dflat, rflat, den, tsum, lsum), _ = jax.lax.scan(
                chunk_body, (zpf, zrf, zf, zf, zf), (ch_cohort, ch_w, ch_r))
        with jax.named_scope(scopes.FED_AGGREGATE):
            dsum = unflatten_carry_f32(dflat, d_spec)
            rest_num = unflatten_carry_f32(rflat, r_spec)
            return (jax.lax.psum(dsum, axes), jax.lax.psum(rest_num, axes),
                    jax.lax.psum(den, axes), jax.lax.psum(tsum, axes),
                    jax.lax.psum(lsum, axes))

    def _zero_sums(self, variables):
        zp, zr = self._split(jax.tree.map(
            lambda a: jnp.zeros(a.shape, jnp.float32), variables))
        return (zp, zr, jnp.float32(0), jnp.float32(0), jnp.float32(0))

    def _finalize_from_sums(self, variables, sums):
        dsum, rest_num, den, tsum, lsum = sums
        tau_eff = tsum / den
        gp, grest = self._split(variables)
        new_params = jax.tree.map(
            lambda g, d: (g.astype(jnp.float32)
                          - tau_eff * d / den).astype(g.dtype), gp, dsum)
        new = {"params": new_params,
               **jax.tree.map(lambda s, ref: (s / den).astype(ref.dtype),
                              rest_num, grest)}
        return new, lsum / den, {}


class MeshRobustEngine(MeshFedAvgEngine):
    """Byzantine-robust FedAvg on the mesh.

    defense="norm_clip" (the reference's clip+weak-DP,
    robust_aggregation.py:38-55, FedAvgRobustAggregator.py:176-206) stays
    collective-only: per-client clipping inside the shard, then the psum.

    defense in {"krum", "multi_krum", "median", "trimmed_mean"} needs
    ORDER STATISTICS over the whole cohort's parameter vectors, which a
    weighted psum cannot express: each shard flattens its clients' trained params to a
    [k_local, P] f32 matrix (P padded to the ops/aggregate tile),
    all_gathers it over ICI into the replicated [K, P] cohort matrix, and
    applies the defense there (krum = one MXU gram matrix, median/trimmed
    = a sort along the client axis).  Memory bound: K·P·4 bytes per
    device — fine for the LR/CNN models these defenses are used with;
    past that, `stream_block` switches to the two-phase beyond-HBM path
    (_round_blockstream_orderstat below).  Cohort size must divide
    evenly over the mesh (zero-weight pad lanes have no principled place
    in a median), enforced at construction."""

    def _program_family_name(self, streaming: bool, stream_block) -> str:
        # the audit taxonomy's names: the resident order-stat round is
        # "robust_orderstat", the two-phase beyond-HBM path
        # "robust_blockstream" (norm_clip shares the resident program
        # shape and books under the same family)
        return ("robust_blockstream" if stream_block is not None
                else "robust_orderstat")

    def __init__(self, trainer, data, cfg, defense: str = "norm_clip",
                 n_byzantine: int = 0, multi_krum_m: Optional[int] = None,
                 param_block_bytes: int = 128 << 20, **kw):
        if defense not in ("norm_clip", "krum", "multi_krum", "median",
                           "trimmed_mean"):
            raise ValueError(f"unknown defense {defense!r}")
        self.defense = defense
        self.n_byzantine = n_byzantine
        self.multi_krum_m = robust_ops.default_multi_krum_m(
            min(cfg.client_num_per_round, data.client_num), n_byzantine,
            multi_krum_m)
        self.param_block_bytes = param_block_bytes
        super().__init__(trainer, data, cfg, **kw)
        if defense != "norm_clip" and self.batch_axes:
            # the order-stat scatter offsets index CLIENT rows per shard;
            # a batch axis would duplicate rows at distinct offsets
            raise ValueError(f"defense {defense!r} does not support a "
                             f"'batch' mesh axis (norm_clip does)")
        if defense != "norm_clip":
            K = min(cfg.client_num_per_round, data.client_num)
            if K % self.n_shards:
                raise ValueError(
                    f"defense {defense!r} needs the cohort ({K}) to divide "
                    f"evenly over the mesh ({self.n_shards} shards): order "
                    "statistics cannot ignore padded lanes")
            if self.stream_block is not None:
                if K % self.stream_block:
                    raise ValueError(
                        f"defense {defense!r} with stream_block needs the "
                        f"cohort ({K}) to be a block multiple "
                        f"({self.stream_block}): order statistics cannot "
                        "ignore padded lanes")
                if jax.process_count() > 1:
                    # phase 1 offloads each block's client-sharded flats
                    # with np.asarray — non-addressable across processes.
                    # Fail at construction like the other unsupported
                    # combinations, not mid-round after training work.
                    raise ValueError(
                        f"defense {defense!r} with stream_block is "
                        "single-process only: the host [K, P] matrix "
                        "offload needs every client shard addressable")
                # two-phase beyond-HBM path (VERDICT r4 #3): phase 1
                # trains client blocks and lands each block's flattened
                # params on HOST; phase 2 re-streams the [K, P] matrix
                # PARAMETER-major through the mesh for exact order stats
                # accumulators AND block inputs donated, same rationale
                # as the linear _block_step (O(2·block) device bound)
                self._block_step_flats = obs_programs.instrument(
                    self.program_family,
                    jax.jit(self._block_step_flats_impl,
                            donate_argnums=(1, 2, 3, 4)))
                # phase-2 [K, Pb] slices are uploaded fresh per call and
                # consumed exactly once — donate them, so a retired
                # slice's device memory recycles instead of stacking
                # next to the in-flight one (the O(K·Pb) bound).  Gated
                # on the donate flag (unlike the pre-existing always-
                # donated sums) so donate=False stays a complete
                # escape hatch and the bitwise donate-A/B pin really
                # compiles these programs both ways
                self._colstat = obs_programs.instrument(
                    self.program_family,
                    jax.jit(self._colstat_impl,
                            donate_argnums=(0,) if self.donate else ()))
                self._gram = obs_programs.instrument(
                    self.program_family,
                    jax.jit(self._gram_impl,
                            donate_argnums=(0,) if self.donate else ()))
                # new_flat (argnum 3) is engine-internal and dead after
                # the finalize — donated with the flag too
                self._orderstat_finalize = obs_programs.instrument(
                    self.program_family,
                    jax.jit(self._orderstat_finalize_impl,
                            donate_argnums=(0, 1, 2, 3)
                            if self.donate else (2,)))
                self.round_fn = self._round_blockstream_orderstat

    def client_transform(self, client_variables, weight, global_variables):
        if self.defense != "norm_clip":
            return client_variables
        out = dict(client_variables)
        out["params"] = robust_ops.norm_diff_clip(
            client_variables["params"], global_variables["params"],
            self.cfg.norm_bound)
        return out

    def server_update(self, avg_variables, global_variables, server_state, rng):
        if self.defense == "norm_clip" and self.cfg.stddev > 0:
            out = dict(avg_variables)
            out["params"] = robust_ops.add_weak_dp_noise(
                avg_variables["params"], rng, self.cfg.stddev)
            return out, server_state
        return avg_variables, server_state

    def _shard_body(self, variables, cohort, weights, client_rngs):
        if self.defense == "norm_clip":
            return super()._shard_body(variables, cohort, weights,
                                       client_rngs)
        from fedml_tpu.ops.aggregate import (flatten_stacked_tree,
                                             unflatten_to_tree)
        axes = self.mesh.axis_names
        rep_vars = variables
        variables = pvary_tree(variables, axes)
        local_vars = cast_local(variables, self.local_dtype)
        k_local = weights.shape[0]
        # the shared chunked loop, additionally emitting each client's
        # flattened trained params (prox term etc. included — one code
        # path with the norm_clip/FedAvg engines)
        num, den, lsum, flats, _counters = chunked_weighted_train(
            self.trainer, local_vars, cohort, weights, client_rngs,
            self.cfg.epochs, vary_axes=axes, chunk_cap=self.chunk,
            emit_flat_params=True, restore_x=self._restore_chunk_x,
            ragged_batches=self._ragged_batches)
        rest_num = {k: v for k, v in num.items() if k != "params"}
        # [n_chunks, chunk, P] -> this shard's clients; drop the in-chunk
        # pad lanes (they sit at the STATIC tail of the local stack)
        flats = flats.reshape(-1, flats.shape[-1])[:k_local]
        # replicated [K, P] cohort matrix: scatter this shard's rows into
        # zeros and psum — one collective, and unlike all_gather the
        # result is TYPED replicated (which the out_specs check needs)
        off = jnp.int32(0)
        for ax in axes:
            off = off * self.mesh.shape[ax] + jax.lax.axis_index(ax)
        full = jnp.zeros((k_local * self.n_shards, flats.shape[-1]),
                         flats.dtype)
        full = jax.lax.dynamic_update_slice(
            full, flats, (off * k_local, jnp.int32(0)))
        flats = jax.lax.psum(full, axes)
        if self.defense == "krum":
            i = robust_ops.krum_select_flat(flats, self.n_byzantine)
            new_flat = flats[i]
        elif self.defense == "multi_krum":
            idx = robust_ops.multi_krum_select_flat(
                flats, self.n_byzantine, self.multi_krum_m)
            new_flat = jnp.mean(flats[idx], axis=0)
        elif self.defense == "median":
            new_flat = jnp.median(flats, axis=0)
        else:                                 # trimmed_mean
            n = flats.shape[0]
            k = min(max(self.n_byzantine, 1), (n - 1) // 2)
            s = jnp.sort(flats, axis=0)
            new_flat = jnp.mean(s[k:n - k], axis=0)
        _, spec = flatten_stacked_tree(
            jax.tree.map(lambda a: a[None], rep_vars["params"]))
        new_params = unflatten_to_tree(new_flat, spec)
        rest_num = jax.lax.psum(rest_num, axes)
        den = jax.lax.psum(den, axes)
        grest = {k: v for k, v in rep_vars.items() if k != "params"}
        new = {"params": new_params,
               **jax.tree.map(lambda s, ref: (s / den).astype(ref.dtype),
                              rest_num, grest)}
        loss = jax.lax.psum(lsum, axes) / den
        return new, loss, {}

    # -- block-streamed order statistics (VERDICT r4 #3) ---------------------
    # The linear engines stream CLIENT-major: blocks of clients cross
    # H2D and fold into O(P) sums.  Order statistics cannot fold, but
    # they CAN transpose: phase 1 streams client blocks through local
    # training and lands each block's flattened params on host — the
    # [K, P] cohort matrix lives in HOST RAM, never HBM; phase 2 streams
    # that matrix back PARAMETER-major in [K, Pb] slices, each sharded
    # over the mesh's param columns, where the defense is exact:
    #   median/trimmed_mean — per-column sort (no cross-column, and the
    #     column values are bitwise the resident path's, so the result
    #     is bitwise-equal to the in-HBM defense);
    #   krum — the Gram matrix G = Σ_b X_b X_bᵀ accumulates over param
    #     slices (one MXU matmul per slice + a psum), pairwise distances
    #     and the argmin score need only G [K, K].
    # Device memory: O(stream_block·P) in phase 1, O(K·Pb) in phase 2 —
    # both knobs, neither grows with K·P.  The reference's robust path
    # (robust_aggregation.py:32-55) is norm-clip only; this bounds the
    # framework's own beyond-reference defenses at reference-beating
    # cohort scale (SCALING.md "Order statistics beyond HBM").

    def _block_step_flats_impl(self, variables, sums, block, weights, rngs):
        """Phase-1 block step: train one client block, psum its linear
        stats sums into the (donated) accumulators, and emit the block's
        flattened trained params [B, P] client-sharded for host offload."""
        specs = {k: stack_leaf_spec(self.mesh, v) for k, v in block.items()}
        csh = P(self.client_axes)
        axes = self.mesh.axis_names

        def body(variables, cohort, w, r):
            v = pvary_tree(variables, axes)
            local_vars = cast_local(v, self.local_dtype)
            num, den, lsum, flats, _counters = chunked_weighted_train(
                self.trainer, local_vars, cohort, w, r, self.cfg.epochs,
                vary_axes=axes, chunk_cap=self.chunk,
                emit_flat_params=True, restore_x=self._restore_chunk_x,
                ragged_batches=self._ragged_batches)
            flats = flats.reshape(-1, flats.shape[-1])[:w.shape[0]]
            rest = {k: x for k, x in num.items() if k != "params"}
            return (jax.lax.psum(rest, axes), jax.lax.psum(den, axes),
                    jax.lax.psum(lsum, axes)), flats

        bsums, flats = jax.shard_map(
            body, mesh=self.mesh, in_specs=(P(), specs, csh, csh),
            out_specs=((P(), P(), P()), csh))(variables, block, weights,
                                              rngs)
        return jax.tree.map(lambda a, b: a + b, sums, bsums), flats

    def _zero_rest_sums(self, variables):
        rest = {k: v for k, v in variables.items() if k != "params"}
        return (jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32),
                             rest), jnp.float32(0), jnp.float32(0))

    def _param_sharding(self):
        from jax.sharding import NamedSharding
        return NamedSharding(self.mesh, P(None, self.client_axes))

    def _colstat_impl(self, xb):
        """Per-column defense on one [K, Pb] param slice (columns sharded
        over the mesh; a sort is column-local, so no collectives)."""
        def body(x):
            if self.defense == "median":
                return jnp.median(x, axis=0)
            n = x.shape[0]
            k = min(max(self.n_byzantine, 1), (n - 1) // 2)
            s = jnp.sort(x, axis=0)
            return jnp.mean(s[k:n - k], axis=0)

        return jax.shard_map(
            body, mesh=self.mesh, in_specs=(P(None, self.client_axes),),
            out_specs=P(self.client_axes))(xb)

    def _gram_impl(self, xb):
        """One param slice's Gram contribution X_b X_bᵀ: the [K, Pb]
        slice is column-sharded, each shard's matmul runs on the MXU,
        one psum replicates the [K, K] partial."""
        def body(x):
            return jax.lax.psum(
                jnp.dot(x, x.T, preferred_element_type=jnp.float32),
                self.client_axes)

        return jax.shard_map(
            body, mesh=self.mesh, in_specs=(P(None, self.client_axes),),
            out_specs=P())(xb)

    def _orderstat_finalize_impl(self, variables, server_state, sums,
                                 new_flat, agg_rng):
        from fedml_tpu.ops.aggregate import (flatten_stacked_tree,
                                             unflatten_to_tree)
        rest_num, den, lsum = sums
        _, spec = flatten_stacked_tree(
            jax.tree.map(lambda a: a[None], variables["params"]))
        grest = {k: v for k, v in variables.items() if k != "params"}
        new = {"params": unflatten_to_tree(new_flat, spec),
               **jax.tree.map(lambda s, ref: (s / den).astype(ref.dtype),
                              rest_num, grest)}
        new, server_state = self.server_update(new, variables,
                                               server_state, agg_rng)
        return new, server_state, {"train_loss": lsum / den}

    def _krum_scores_from_gram(self, G: np.ndarray) -> np.ndarray:
        """core/robust.py::krum_scores_flat, from the Gram matrix
        (numpy: G is [K, K] — host-trivial next to the matmuls)."""
        sq = np.diag(G)
        d2 = np.maximum(sq[:, None] + sq[None, :] - 2.0 * G, 0.0)
        n = G.shape[0]
        k = max(n - self.n_byzantine - 2, 1)
        np.fill_diagonal(d2, np.inf)
        return np.sort(d2, axis=1)[:, :k].sum(axis=1)

    def _round_blockstream_orderstat(self, variables, server_state,
                                     round_idx, rng):
        """Two-phase block-streamed robust round (see class comment
        above).  Bitwise-equal to the resident defense for median/
        trimmed_mean (same values, same per-column ops); krum matches up
        to Gram summation order in the distance ties."""
        if self.defense == "norm_clip":      # linear — base path streams it
            return super()._round_blockstream(variables, server_state,
                                              round_idx, rng)
        ids, wmask = self._sample_padded_np(round_idx)
        assert wmask.all(), "order statistics cannot ignore padded lanes"
        K = len(ids)
        w_all = self._lane_weights(ids, wmask)
        rng, agg_rng = jax.random.split(rng)
        crngs = np.asarray(jax.random.split(rng, K))
        self.transfer_stats.round_start()
        try:
            with obs.span("round.blockstream_orderstat",
                          round=int(round_idx), clients=K,
                          defense=self.defense):
                return self._blockstream_orderstat_body(
                    variables, server_state, ids, w_all, crngs, agg_rng)
        finally:
            self.transfer_stats.round_end()

    def _blockstream_orderstat_body(self, variables, server_state, ids,
                                    w_all, crngs, agg_rng):
        B, K = self.stream_block, len(ids)
        sums = jax.device_put(self._zero_rest_sums(variables),
                              replicated_sharding(self.mesh))
        # phase 1: client-major blocks through the prefetch pipeline
        # (double-buffered background uploads — the np.asarray pull of
        # block k's flats overlaps block k+1's gather+upload), each
        # block's flats landing in the host matrix as compute proceeds
        X = None
        spans = [(s, s + B) for s in range(0, K, B)]
        with self._block_fetcher(ids, w_all, crngs, spans) as fetch:
            for start, stop in spans:
                sums, flats = self._block_step_flats(variables, sums,
                                                     *fetch.get())
                if X is None:
                    X = np.empty((K, flats.shape[1]), np.float32)
                X[start:stop] = np.asarray(flats)
                # np.asarray forced completion; drop the device buffer
                # NOW — holding it across the next block step would
                # stack [B, P] generations and break the O(block)
                # device bound
                flats.delete()
        # phase 2: parameter-major slices, Pb sized to param_block_bytes
        # of device footprint and mesh-divisible.  Only the FINAL short
        # slice is zero-padded (into its own [K, pb] buffer at upload
        # time — never np.pad the whole host matrix, which would
        # transiently double the very footprint this path exists to
        # bound); pad columns are sliced off the result.
        P_flat = X.shape[1]
        unit = self.n_shards
        pb = max(1, self.param_block_bytes // (K * 4) // unit) * unit
        pb = min(pb, -(-P_flat // unit) * unit)
        n_slices = -(-P_flat // pb)

        def slice_padded(s):
            # phase-2 H2D is upload wall too (the [K, P] matrix crosses
            # back slice by slice), and it runs INLINE on the round
            # loop, so it is simultaneously consumer wait — recording
            # both keeps overlap_fraction honest: this traversal is
            # unhidden transfer, not compute (the OSB256 metric)
            with self.transfer_stats.uploading(), \
                    self.transfer_stats.waiting():
                xb = X[:, s * pb:(s + 1) * pb]
                if xb.shape[1] < pb:
                    buf = np.zeros((K, pb), np.float32)
                    buf[:, :xb.shape[1]] = xb
                    xb = buf
                self.transfer_stats.add_h2d_bytes(K * pb * 4)
                return jax.device_put(xb, self._param_sharding())

        if self.defense in ("krum", "multi_krum"):
            G = np.zeros((K, K), np.float32)
            for s in range(n_slices):
                G += np.asarray(self._gram(slice_padded(s)))
            scores = self._krum_scores_from_gram(G)
            if self.defense == "krum":
                new_flat = jnp.asarray(X[int(np.argmin(scores))])
            else:
                idx = np.argsort(scores)[:self.multi_krum_m]
                new_flat = jnp.asarray(
                    np.mean(X[idx], axis=0, dtype=np.float32))
        else:
            out = np.empty(n_slices * pb, np.float32)
            for s in range(n_slices):
                out[s * pb:(s + 1) * pb] = np.asarray(
                    self._colstat(slice_padded(s)))
            new_flat = jnp.asarray(out[:P_flat])
        return self._orderstat_finalize(variables, server_state, sums,
                                        new_flat, agg_rng)
