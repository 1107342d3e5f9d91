"""The check and the counts take a configuration whose local training
updates a subset of its weights (a frozen base under adapters): the reference
trains the subset the configuration names, reads the rest in place, and a
leaf the configuration calls frozen must come back as it went in."""
import json
import textwrap
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedbench import reference
from fedbench.harness import build, correctness, flops
from fedbench_tiny import run_cell, tiny_checkout, tiny_doc

MODEL = '''
    import flax.linen as nn
    import jax
    import jax.numpy as jnp


    class TwoLayer(nn.Module):
        classes: int
        freeze: bool = True

        @nn.compact
        def __call__(self, x, train=False):
            h = nn.Dense(8)(x.reshape(len(x), -1))
            if self.freeze:
                h = jax.lax.stop_gradient(h)
            return nn.Dense(self.classes)(jnp.tanh(h))


    def create(name, classes, **kwargs):
        return TwoLayer(classes, **kwargs)
    '''
REFERENCE = '''
    import jax.numpy as jnp


    def forward(params, x):
        d0, d1 = params["Dense_0"], params["Dense_1"]
        h = jnp.dot(x.reshape(len(x), -1), d0["kernel"], precision="highest") + d0["bias"]
        return jnp.dot(jnp.tanh(h), d1["kernel"], precision="highest") + d1["bias"]


    def forward_flops(params, x_shape):
        return 2.0 * (params["Dense_0"]["kernel"].size + params["Dense_1"]["kernel"].size)
    '''


def _frozen_cell(root, *, model_freezes: bool, trainable):
    """A cell made only of new files in a scratch copy of the benchmark: a
    two-layer model whose first layer the MODEL freezes (``stop_gradient``),
    a configuration that says which leaves local training updates."""
    bench = root / "fedbench"
    (root / "twolayer_model.py").write_text(textwrap.dedent(MODEL))
    (bench / "reference" / "twolayer.py").write_text(textwrap.dedent(REFERENCE))
    check = {"param_tol": 1e-4, "why": "two dense layers in float32"}
    if trainable is not None:
        check["trainable"] = trainable
    (bench / "configs" / "twolayer.json").write_text(json.dumps({
        "name": "twolayer", "source": "test", "reduced": [], "assumed": [],
        "model": {"factory": "twolayer_model.create", "name": "twolayer",
                  "kwargs": {"freeze": model_freezes}},
        "trainer": {"loss": "ce", "optimizer": "sgd", "train_dtype": "float32"},
        "engine": {"local_dtype": None, "chunk": 2}, "reference": "twolayer",
        "check": check}))
    (bench / "traffic" / "res3of6.json").write_text(json.dumps({
        "dataset": {"generator": "class_template_images",
                    "args": {"hw": [4, 4], "channels": 1, "classes": 3}},
        "population": 6, "cohort": 3,
        "client_sizes": {"law": "equal", "samples": 8},
        "batch_size": 4, "epochs": 1, "lr": 0.1, "mesh_devices": 1,
        "engine": {"class": "fedml_tpu.parallel.MeshFedAvgEngine",
                   "args": {"streaming": False}}}))
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append({"name": "twolayer", "source": "test", "reduced": [],
                                "file": "fedbench/configs/twolayer.json", "why": "t"})
    manifest["workloads"].append({"name": "twolayer.res3of6", "config": "twolayer",
                                  "traffic": "res3of6", "chips": 1, "why": "t"})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    r = run_cell(root, "twolayer.res3of6", seed=3)
    assert r.returncode == 0, r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    detail = json.loads(lines[-2][len("fedbench detail "):])
    return json.loads(lines[-1]), detail


@pytest.mark.parametrize("model_freezes,trainable,passes", [
    (True, ["Dense_1"], True),      # the program and the reference agree on the subset
    (True, None, False),            # the reference trains what the program froze
    (False, ["Dense_1"], False),    # the program trains what the configuration calls frozen
], ids=["subset", "reference-trains-frozen", "program-trains-frozen"])
def test_a_frozen_subset_is_checked_end_to_end(model_freezes, trainable, passes,
                                               tmp_path):
    tiny_checkout(tmp_path)
    line, detail = _frozen_cell(tmp_path, model_freezes=model_freezes,
                                trainable=trainable)
    check, split = detail["check"], list(detail["setup_split"])
    assert line["correct"] is passes and check["ok"] is passes
    assert check["max_abs_update"] > 0
    if passes:
        assert check["max_abs_delta"] <= 1e-4 * check["max_abs_update"]
    else:       # a whole layer's update apart, not a rounding
        assert check["max_abs_delta"] > 0.01 * check["max_abs_update"]
    # the check runs before the cell's own engine and stack are placed
    assert split.index("data_s") < split.index("check_s") < split.index("build_s")


def _two_layer(rs):
    return {"params": {
        "Dense_0": {"kernel": jnp.asarray(rs.randn(5, 4), jnp.float32),
                    "bias": jnp.zeros(4, jnp.float32)},
        "Dense_1": {"kernel": jnp.asarray(rs.randn(4, 3), jnp.float32),
                    "bias": jnp.zeros(3, jnp.float32)}}}


def _cohort(rs, k=2, b=2, bs=3):
    return {"x": rs.randn(k, b, bs, 5).astype(np.float32),
            "y": rs.randint(0, 3, (k, b, bs)).astype(np.int32),
            "mask": np.ones((k, b, bs), np.float32)}


def test_the_reference_reads_frozen_leaves_in_place_and_differentiates_the_rest():
    """A frozen leaf reaches ``forward`` as the very array that was handed
    in (no per-client copy, no cast) and is returned as that object; only the
    trainable leaves carry a gradient and come back changed."""
    rs = np.random.RandomState(0)
    variables, seen = _two_layer(rs), []

    def forward(params, x):
        seen.append(jax.tree.map(lambda a: a, params))
        h = jnp.tanh(jnp.dot(x, params["Dense_0"]["kernel"]) + params["Dense_0"]["bias"])
        return jnp.dot(h, params["Dense_1"]["kernel"]) + params["Dense_1"]["bias"]

    ref = types.SimpleNamespace(forward=forward)
    with jax.disable_jit():         # forward then sees the arrays, not jit's tracers
        new, loss = reference.fedavg_round(ref, variables, _cohort(rs), 0.1,
                                           trainable=["Dense_1"])
    before = variables["params"]
    assert len(seen) == 4 and np.isfinite(loss)
    for params in seen:
        for leaf in ("kernel", "bias"):
            assert params["Dense_0"][leaf] is before["Dense_0"][leaf]
            assert isinstance(params["Dense_1"][leaf], jax.core.Tracer)
    for leaf in ("kernel", "bias"):
        assert new["Dense_0"][leaf] is before["Dense_0"][leaf]
        assert np.abs(new["Dense_1"][leaf] - before["Dense_1"][leaf]).max() > 0
    # without the list every leaf is trained: today's meaning
    whole, _ = reference.fedavg_round(ref, variables, _cohort(rs), 0.1)
    assert np.abs(whole["Dense_0"]["kernel"] - before["Dense_0"]["kernel"]).max() > 0


def test_trainable_prefixes_match_whole_path_components():
    rs = np.random.RandomState(1)
    variables = _two_layer(rs)
    variables["params"]["Dense_10"] = {"kernel": jnp.asarray(rs.randn(3, 3), jnp.float32)}

    def forward(params, x):
        h = jnp.tanh(jnp.dot(x, params["Dense_0"]["kernel"]))
        return jnp.dot(jnp.dot(h, params["Dense_1"]["kernel"]), params["Dense_10"]["kernel"])

    ref = types.SimpleNamespace(forward=forward)
    new, _ = reference.fedavg_round(ref, variables, _cohort(rs), 0.1,
                                    trainable=["Dense_1/kernel"])
    before = variables["params"]
    assert new["Dense_10"]["kernel"] is before["Dense_10"]["kernel"]
    assert new["Dense_1"]["bias"] is before["Dense_1"]["bias"]
    assert np.abs(new["Dense_1"]["kernel"] - before["Dense_1"]["kernel"]).max() > 0
    with pytest.raises(ValueError, match="names no leaf"):
        reference.fedavg_round(ref, variables, _cohort(rs), 0.1, trainable=["Dense_7"])


@pytest.mark.parametrize("hooks,flops_per_sample,bytes_per_step", [
    ({}, 3 * 1000.0, 4.0 * 35 * 2),
    ({"train_flops": lambda params, x_shape: 2 * 1000.0 + 7.0,
      "step_bytes": lambda params, itemsize: 100.0 * itemsize}, 2007.0, 200.0),
], ids=["default", "the-configurations-own"])
def test_round_needs_asks_the_configuration_for_its_own_work(
        monkeypatch, hooks, flops_per_sample, bytes_per_step):
    """3 x forward and 4 x params x itemsize are the work of a model that is
    trained whole; a reference module that defines ``train_flops`` /
    ``step_bytes`` states its own (a frozen base has no weight gradient)."""
    ref = types.SimpleNamespace(forward_flops=lambda params, x_shape: 1000.0, **hooks)
    monkeypatch.setattr(reference, "resolve", lambda name: ref)
    sizes = np.array([8, 8, 4, 4], np.float32)
    ctx = {"cell": types.SimpleNamespace(
               config={"reference": "any"},
               traffic={"cohort": 2, "batch_size": 4, "epochs": 1}),
           "data": types.SimpleNamespace(
               client_shards={"x": np.zeros((4, 2, 4, 5), np.float32)},
               client_num_samples=sizes),
           "engine": types.SimpleNamespace(local_dtype=jnp.bfloat16),
           "params": {"w": np.zeros((5, 6)), "b": np.zeros(5)}}
    need = flops.round_needs(ctx)
    assert need["params"] == 35 and need["steps"] == 2 * 1.5
    assert need["flops"] == 2 * 6.0 * flops_per_sample
    assert need["bytes"] == need["steps"] * bytes_per_step


# what `check_round` printed for these tiny cells at the parent commit
# (654adf3, this CPU box): the repair leaves the arithmetic of a
# configuration that is trained whole as it was
RECORDED = {
    ("resnet18gn_cifar", "xdev10of4000", 4): (
        1.1920928955078125e-07, 0.18967287242412567, 3.1106274127960205, 3.11062753200531),
    ("resnet18gn_cifar", "silo128of1024", 7): (
        1.1920928955078125e-07, 0.1398603469133377, 2.9503989219665527, 2.9503991074032254),
    ("so_nwp_lstm", "xdev50of342k", 4): (
        5.960464477539063e-08, 0.0765678659081459, 4.109698295593262, 4.109697892115666),
    ("so_nwp_lstm", "xdev50of342k", 9): (
        5.960464477539063e-08, 0.05137397348880768, 4.090183734893799, 4.09018377157358),
}


@pytest.mark.parametrize("config,traffic,seed", list(RECORDED))
def test_the_check_of_a_model_trained_whole_prints_the_numbers_it_printed(
        config, traffic, seed):
    """Equal to rounding: the update and the two losses to 1e-6 of their
    value (float32 carries 6e-8), the difference - itself one or two float32
    roundings of a weight of order 1 - to within four of them."""
    cfg, mix = tiny_doc("configs", config), tiny_doc("traffic", traffic)
    got = correctness.check_round(cfg, mix, build.make_data(mix, seed), seed,
                                  {"clients": 3, "batches": 2})
    delta, update, engine_loss, reference_loss = RECORDED[config, traffic, seed]
    assert got["ok"]
    assert got["max_abs_update"] == pytest.approx(update, rel=1e-6)
    assert got["engine_loss"] == pytest.approx(engine_loss, rel=1e-6)
    assert got["reference_loss"] == pytest.approx(reference_loss, rel=1e-6)
    assert got["max_abs_delta"] <= 4 * delta


def test_real_slot_pct_is_real_samples_over_the_slots_the_rounds_trained():
    from fedbench.layer_metrics import real_slot_pct
    ctx = {"window": {"attempted": 3}, "samples": 3 * 2 * 390.0,
           "cell": types.SimpleNamespace(traffic={"cohort": 2}),
           "data": types.SimpleNamespace(
               client_shards={"mask": np.zeros((5, 13, 32), np.float32)})}
    assert real_slot_pct.read(ctx) == 93.75
    assert real_slot_pct.read(dict(ctx, window={"attempted": 0})) is None
    assert (real_slot_pct.LAYER, real_slot_pct.MOVES, real_slot_pct.SOURCE) == (
        "local training", "samples_per_s", "program_counter")
