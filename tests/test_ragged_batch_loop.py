"""The batch loop of a ragged cohort (ISSUE 29).

Where a population leaves batches of the client stack empty, the round
program orders its shard-local cohort by the batch trips each client
needs and ends each chunk's batch loop at the chunk's own longest client
(`parallel/engine.py::chunked_weighted_train`, `ragged_batches`;
`core/trainer.py::ClientTrainer.local_train`, `batch_bound`).  The
invariant: only steps that were numeric no-ops are left out — every
client's trained weights are BITWISE those of the static loop over all
the stack's batches, and the sums agree to float32 rounding (they fold
the clients in another order).  And the engine's host-side count of the
trips equals the bounds the program computes.
"""
import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from fedml_tpu.core.trainer import ClientTrainer, make_lr_schedule
from fedml_tpu.models import create_model
from fedml_tpu.parallel import MeshFedAvgEngine, MeshRobustEngine
from fedml_tpu.parallel.engine import (batch_trips, chunked_weighted_train,
                                       order_by_trips)
from fedml_tpu.parallel.mesh import (client_axes, make_mesh, make_mesh_batch,
                                     pvary_tree, stack_leaf_spec)
from fedml_tpu.utils.config import FedConfig
from parallel_case import jaxpr_eqns

B, BS, DIM, CLASSES = 4, 4, 12, 5          # the stack: 4 batches of 4


class DropoutMLP(nn.Module):
    @nn.compact
    def __call__(self, x, train: bool = False):
        x = nn.relu(nn.Dense(16)(x))
        x = nn.Dropout(0.5, deterministic=not train)(x)
        return nn.Dense(CLASSES)(x)


def _cohort(masks, seed=0):
    """{x, y, mask}[K, B, BS, ...] with the given per-client masks, each
    [B, BS] (or a sample count: that many leading slots)."""
    g = np.random.default_rng(seed)
    k = len(masks)
    mask = np.zeros((k, B, BS), np.float32)
    for i, m in enumerate(masks):
        if np.ndim(m) == 0:
            mask[i].reshape(-1)[:int(m)] = 1.0
        else:
            mask[i] = np.asarray(m, np.float32)
    return {"x": g.standard_normal((k, B, BS, DIM)).astype(np.float32),
            "y": g.integers(0, CLASSES, (k, B, BS)).astype(np.int32),
            "mask": mask}


def _sums(trainer, variables, cohort, weights, rngs, *, mesh, ragged,
          epochs=1, chunk=2, emit=True):
    """`chunked_weighted_train` under shard_map as the engines call it:
    (Σ w·v, Σ w, Σ w·loss) psum'd, and each client's trained parameters
    as a row, in the cohort's order."""
    axes = mesh.axis_names
    trainer.batch_axes = tuple(a for a in axes if a == "batch")

    def body(v, c, w, r):
        out = chunked_weighted_train(
            trainer, pvary_tree(v, axes), c, w, r, epochs, vary_axes=axes,
            chunk_cap=chunk, emit_flat_params=emit, ragged_batches=ragged)
        sums = jax.lax.psum(out[:3], axes)
        if not emit:
            return sums
        return sums, out[3].reshape(-1, out[3].shape[-1])[:w.shape[0]]

    csh = P(client_axes(mesh))
    specs = {k: stack_leaf_spec(mesh, v) for k, v in cohort.items()}
    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(), specs, csh, csh),
        out_specs=((P(), P(), P()), csh) if emit else (P(), P(), P())))(
            variables, cohort, weights, rngs)


FULL = B * BS
NOT_A_PREFIX = [[1, 1, 0, 0], [0, 0, 0, 0], [1, 0, 0, 0], [0, 0, 0, 0]]
CASES = {
    # sizes (or masks) of the cohort; None weight = the sample count
    "one_sample_and_the_cap": dict(masks=[1, FULL, 7, 3]),
    "chunk_pad_lane_and_odd_cohort": dict(masks=[9, 2, FULL, 5, 6]),
    "zero_weight_lane": dict(masks=[6, 11, 3, 9], weights=[6, 11, 0, 9]),
    "real_batches_not_a_prefix": dict(
        masks=[NOT_A_PREFIX, 5, 13, 2], momentum=0.9),
    "two_epochs": dict(masks=[3, 10, FULL, 6], epochs=2, momentum=0.9),
    "two_epochs_lr_schedule": dict(masks=[3, 10, 13, 6], epochs=2,
                                   schedule=True),
    "dropout_two_epochs": dict(masks=[2, 9, 14, 5, 7], epochs=2,
                               dropout=True),
    "chunk_of_three": dict(masks=[4, 1, 12, 8, 5, 9, 2], chunk=3),
    "two_devices": dict(masks=[1, FULL, 7, 3, 9, 2, 12, 5], devices=2),
    "eight_devices": dict(masks=[1, FULL, 7, 3, 9, 2, 12, 5, 6, 6, 4, 13,
                                 8, 1, 10, 3], devices=8),
    "batch_axis_mesh": dict(masks=[1, FULL, 7, 3, 9, 2, 12, 5],
                            batch_mesh=(2, 2), momentum=0.9),
}


@pytest.mark.parametrize("name", CASES)
def test_bounded_loop_trains_what_the_static_loop_trains(name):
    case = CASES[name]
    epochs, chunk = case.get("epochs", 1), case.get("chunk", 2)
    cohort = _cohort(case["masks"])
    k = len(case["masks"])
    weights = np.asarray(case.get("weights", cohort["mask"].sum((1, 2))),
                         np.float32)
    lr = (make_lr_schedule("poly", 0.3, total_steps=epochs * B)
          if case.get("schedule") else 0.3)
    model = DropoutMLP() if case.get("dropout") else create_model(
        "lr", CLASSES)
    trainer = ClientTrainer(model, lr=lr, momentum=case.get("momentum", 0.0))
    variables = trainer.init(jax.random.PRNGKey(0), cohort["x"][0, 0])
    rngs = jax.random.split(jax.random.PRNGKey(7), k)
    if "batch_mesh" in case:
        mesh = make_mesh_batch(*case["batch_mesh"])
    else:
        mesh = make_mesh(case.get("devices", 1))
    # rows leave a batch-split mesh typed as varying along "batch": the
    # sums alone are compared there
    emit = "batch_mesh" not in case
    run = lambda ragged: _sums(trainer, variables, cohort, weights, rngs,
                               mesh=mesh, ragged=ragged, epochs=epochs,
                               chunk=chunk, emit=emit)
    static, bounded = run(False), run(True)
    if emit:
        (static, rows_s), (bounded, rows_b) = static, bounded
        # every client, its own batches, its own rng, whatever lane the
        # ordering gave it: the same bits, in the cohort's own row order
        np.testing.assert_array_equal(np.asarray(rows_s), np.asarray(rows_b))
        assert np.abs(np.asarray(rows_b)
                      - np.asarray(rows_b)[0]).max() > 1e-3   # they trained
    for a, b in zip(jax.tree.leaves(static), jax.tree.leaves(bounded)):
        scale = max(float(np.abs(np.asarray(a)).max()), 1e-30)
        assert float(np.abs(np.asarray(a) - np.asarray(b)).max()) \
            <= 1e-6 * scale


def test_trip_count_is_the_last_real_batch_not_the_number_of_them():
    mask = _cohort([NOT_A_PREFIX, 0, 1, FULL, 5])["mask"]
    want = [3, 0, 1, 4, 2]
    np.testing.assert_array_equal(batch_trips(mask), want)
    got = jax.jit(batch_trips)(jnp.asarray(mask))
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), want)


def test_order_is_descending_and_stable_and_bounds_are_each_chunks_longest():
    trips = np.asarray([2, 4, 0, 4, 1, 3, 2], np.int32)
    order, bounds = order_by_trips(trips, 3)       # 7 lanes -> 3 chunks of 3
    np.testing.assert_array_equal(order, [1, 3, 5, 0, 6, 4, 2])
    np.testing.assert_array_equal(bounds, [4, 2, 0])
    d_order, d_bounds = jax.jit(order_by_trips, static_argnums=1)(
        jnp.asarray(trips), 3)
    np.testing.assert_array_equal(np.asarray(d_order), order)
    np.testing.assert_array_equal(np.asarray(d_bounds), bounds)


# -- the engine: who takes which path, and its count ------------------------

def _data(masks, seed=0):
    from fedml_tpu.data.federated import FederatedData
    shards = _cohort(masks, seed)
    sizes = shards["mask"].sum((1, 2))
    ev = {k: v[0, :1] for k, v in shards.items()}
    return FederatedData(
        train_data_num=int(sizes.sum()), test_data_num=BS, train_global=ev,
        test_global=ev, client_shards=shards,
        client_num_samples=sizes.astype(np.float32), test_client_shards=None,
        class_num=CLASSES, synthetic=True)


def _engine(masks, cls=MeshFedAvgEngine, cohort=5, devices=1, epochs=1,
            **kw):
    data = _data(masks)
    cfg = FedConfig(model="lr", client_num_in_total=len(masks),
                    client_num_per_round=cohort, comm_round=1, epochs=epochs,
                    batch_size=BS, lr=0.3, frequency_of_the_test=100)
    return cls(ClientTrainer(create_model("lr", CLASSES), lr=0.3), data, cfg,
               mesh=make_mesh(devices), chunk=2, donate=False, **kw)


def _round_jaxpr(engine):
    variables = engine._prepare_variables(engine.init_variables())
    return jax.make_jaxpr(engine._mesh_round)(
        variables, engine.server_init(variables), *engine._round_args(0),
        jax.random.PRNGKey(0)).jaxpr


RAGGED = [1, FULL, 7, 3, 9, 2, 12, 5, 6, 6, 4, 13]
# the last batch part-filled, as the silo cells' 390 = 12 x 32 + 6
EQUAL = [FULL - 2] * 12


@pytest.mark.parametrize("masks, bounded", [(EQUAL, False), (RAGGED, True)],
                         ids=["equal_population", "ragged_population"])
def test_the_population_decides_the_batch_loop(masks, bounded):
    """No option: an engine whose every client fills all the stack's
    batches builds the static scan (no sort, no loop with a traced
    bound), one with a client that leaves a batch empty builds the
    bounded loop — ONE `while` in the chunk scan, its predicate a scalar
    shared by the vmapped lanes, and no select of a lane's parameters in
    its body (what a per-lane bound under vmap turns into)."""
    engine = _engine(masks)
    assert engine._ragged_batches is bounded
    eqns = list(jaxpr_eqns(_round_jaxpr(engine)))
    whiles = [e for e in eqns if e.primitive.name == "while"]
    assert len(whiles) == (1 if bounded else 0)
    assert any(e.primitive.name == "sort" for e in eqns) is bounded
    if bounded:
        (loop,) = whiles
        (pred,) = loop.params["cond_jaxpr"].jaxpr.outvars
        assert pred.aval.shape == ()
        lanes = {(engine.chunk,) + p.shape for p in jax.tree.leaves(
            engine.init_variables())}
        selects = [e for e in jaxpr_eqns(loop.params["body_jaxpr"].jaxpr)
                   if e.primitive.name == "select_n"
                   and e.outvars[0].aval.shape in lanes]
        assert not selects, selects


@pytest.mark.parametrize("devices", [1, 2])
def test_host_count_equals_the_programs_bounds(devices):
    """`transfer_stats.batch_trips` (the engine's host-side count, no
    device sync) against the bounds the round program computes from the
    cohort it took, over a few sampled rounds."""
    engine = _engine(RAGGED, cohort=7, devices=devices, epochs=2)
    stack, stack_w = engine._device_stack()
    axes = engine.mesh.axis_names

    def in_program(stack, stack_w, ids, wmask):
        from fedml_tpu.parallel.engine import take_cohort
        cohort, weights = take_cohort(engine.mesh, stack, stack_w, ids, wmask)

        def shard(mask, w):
            _, bounds = order_by_trips(
                jnp.where(w > 0, batch_trips(mask), 0), engine.chunk)
            return jax.lax.psum(jnp.sum(bounds), axes)
        csh = P(client_axes(engine.mesh))
        return jax.shard_map(shard, mesh=engine.mesh, in_specs=(csh, csh),
                             out_specs=P())(cohort["mask"], weights)

    n_chunks = -(-(8 // devices) // 2) * devices    # 7 padded to 8 lanes
    for r in range(4):
        engine.transfer_stats.reset()
        _, _, ids, wmask = engine._round_args(r)
        ran = int(jax.jit(in_program)(stack, stack_w, ids, wmask))
        assert engine.transfer_stats.batch_trips == 2 * ran      # epochs
        assert engine.transfer_stats.batch_trips_static == 2 * n_chunks * B
        assert 0 < ran < n_chunks * B


def test_equal_population_counts_every_trip():
    engine = _engine(EQUAL, cohort=6)
    engine._round_args(0)
    stats = engine.transfer_stats
    assert stats.batch_trips == stats.batch_trips_static == 3 * B
    stats.reset()
    assert stats.batch_trips == stats.batch_trips_static == 0


@pytest.mark.parametrize("defense", ["median", "krum"])
def test_order_statistic_defense_sees_rows_in_cohort_order(defense):
    """The order-statistic defences index the [K, P] matrix by cohort
    position (krum returns one client's row): on a ragged population the
    round equals the one computed with the cohort left as sampled."""
    ragged = _engine(RAGGED, MeshRobustEngine, cohort=6, devices=2,
                     defense=defense, n_byzantine=1)
    assert ragged._ragged_batches
    static = _engine(RAGGED, MeshRobustEngine, cohort=6, devices=2,
                     defense=defense, n_byzantine=1)
    static._ragged_batches = False
    v0 = ragged.init_variables()
    out = [e.run(variables=jax.tree.map(jnp.copy, v0), rounds=2)
           for e in (static, ragged)]
    for a, b in zip(*map(jax.tree.leaves, out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_hierarchical_engine_inherits_the_bounded_loop():
    """`parallel/hierarchical.py` trains its silo-local cohorts through
    `chunked_weighted_train` too: on a ragged population its round equals
    the one computed with the static loop, to float32 rounding."""
    from fedml_tpu.parallel.hierarchical import MeshHierarchicalEngine
    from fedml_tpu.parallel.mesh import make_mesh_2d

    def run(ragged):
        cfg = FedConfig(model="lr", client_num_in_total=len(RAGGED),
                        client_num_per_round=4, comm_round=1, epochs=2,
                        batch_size=BS, lr=0.3, frequency_of_the_test=100)
        engine = MeshHierarchicalEngine(
            ClientTrainer(create_model("lr", CLASSES), lr=0.3), _data(RAGGED),
            cfg, n_silos=2, group_comm_round=2, mesh=make_mesh_2d(2, 2),
            chunk=2, donate=False)
        assert engine._ragged_batches
        engine._ragged_batches = ragged
        return engine.run(rounds=2)

    for a, b in zip(*(jax.tree.leaves(run(r)) for r in (False, True))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)
