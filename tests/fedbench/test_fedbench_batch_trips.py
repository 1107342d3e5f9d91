"""``batch_trips_pct``: the engine's own count of the batch-loop trips its
round programs ran, over the trips a loop over every batch of the stack would
have run.  A count, so a CPU run prints it too; 100 where the population fills
its batches (the static program), the helper's value on the sampled sizes
where it does not."""
import json
import types

import jax
import numpy as np
import pytest

from fedbench.harness import build
from fedbench.layer_metrics import batch_trips_pct
from fedbench_tiny import REPO, load, run_cell, tiny_checkout, tiny_doc
from parallel_case import jaxpr_eqns


def _engine(config, traffic, seed=3):
    mix = tiny_doc("traffic", traffic)
    data = build.make_data(mix, seed)
    return build.make_engine(tiny_doc("configs", config), mix, data, seed), data


def test_the_entry_repeats_the_reader_and_lists_cells_that_report_what_it_moves():
    manifest = load(REPO + "/BENCHMARK.json")
    entry = manifest["per_layer"][-1]
    assert entry["name"] == "batch_trips_pct"
    assert (entry["layer"], entry["unit"], entry["source"], entry["moves"]) == (
        batch_trips_pct.LAYER, batch_trips_pct.UNIT, batch_trips_pct.SOURCE,
        batch_trips_pct.MOVES) == ("local training", "%", "program_counter",
                                   "rounds_per_s")
    assert set(entry["workloads"]) <= {w["name"] for w in manifest["workloads"]}
    assert "solstm.xdev50of342k" in entry["workloads"]


def test_equal_population_reads_100():
    engine, _ = _engine("resnet18gn_cifar", "silo128of1024")
    assert not engine._ragged_batches
    for r in range(3):
        engine._round_args(r)
    assert batch_trips_pct.read({"engine": engine}) == 100.0


CELLS = [("resnet18gn_cifar", "xdev10of4000"),
         ("resnet18gn_cifar", "silo128of1024"),
         ("resnet18gn_cifar", "silo128of4096x4"),
         ("ouro_2p6b", "silo4of256t1024"),
         ("so_nwp_lstm", "xdev50of342k")]


@pytest.mark.parametrize("config, traffic", CELLS)
def test_only_a_ragged_population_orders_its_cohort_and_bounds_its_batch_loop(
        config, traffic):
    """The tiny cut of every cell, by structure: a population whose clients
    all fill their batches builds a round with no sort and no loop to a
    traced bound (every loop of it is a `scan`); the ragged one has the
    sort and ONE such `while`.  That the four equal cells' programs ARE the
    parent's was checked once, text against text, and is recorded in
    PERF.md §6 PR 29 — not frozen here as hashes a later change of any
    cell's program, or of jax, would have to edit."""
    engine, _ = _engine(config, traffic)
    ragged = config == "so_nwp_lstm"
    assert engine._ragged_batches is ragged
    variables = engine._prepare_variables(build.init_variables(engine))
    eqns = [e.primitive.name for e in jaxpr_eqns(jax.make_jaxpr(
        engine._mesh_round)(variables, engine.server_init(variables),
                            *engine._round_args(0),
                            jax.random.PRNGKey(0)).jaxpr)]
    assert "scan" in eqns
    assert eqns.count("while") == (1 if ragged else 0)
    assert ("sort" in eqns) is ragged


def test_ragged_population_reads_the_helpers_value_on_the_sampled_sizes():
    from fedml_tpu.parallel.engine import order_by_trips
    engine, data = _engine("so_nwp_lstm", "xdev50of342k")
    assert engine._ragged_batches
    n_batches, bs = data.client_shards["mask"].shape[1:3]
    ran = static = 0
    for r in range(2, 6):
        sizes = data.client_num_samples[engine.sampler.sample(r)]
        _, bounds = order_by_trips(
            np.ceil(sizes / bs).astype(np.int32), engine.chunk)
        ran, static = ran + int(bounds.sum()), static + len(bounds) * n_batches
    for r in range(2):                       # warm-up, then the window's reset
        engine._round_args(r)
    engine.transfer_stats.reset()
    for r in range(2, 6):
        engine._round_args(r)
    got = batch_trips_pct.read({"engine": engine})
    assert got == 100.0 * ran / static and 0 < got < 100


def test_a_program_that_keeps_no_count_reads_as_nothing():
    """The parent commit's engine under this PR's benchmark files: its
    ``transfer_stats`` has no trip totals, and one with none dispatched has
    nothing to divide by."""
    no_count = types.SimpleNamespace(transfer_stats=types.SimpleNamespace())
    assert batch_trips_pct.read({"engine": no_count}) is None
    assert batch_trips_pct.read({"engine": object()}) is None
    engine, _ = _engine("resnet18gn_cifar", "silo128of1024")
    assert batch_trips_pct.read({"engine": engine}) is None


@pytest.mark.parametrize("cell, equal", [("solstm.xdev50of342k", False),
                                         ("resnet18gn.xdev10of4000", True)])
def test_a_traced_cpu_run_prints_the_count(cell, equal, tmp_path):
    r = run_cell(tiny_checkout(tmp_path), cell, trace=1)
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"real_slot_pct", "batch_trips_pct"}
    got = line["metrics"]["batch_trips_pct"]
    assert got["unit"] == "%"
    assert got["value"] == 100.0 if equal else 0 < got["value"] < 100
