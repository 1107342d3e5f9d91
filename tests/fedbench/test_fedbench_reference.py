"""Each plain reference against the program's own model on the CPU at a tiny
size, and the peaks / FLOPs arithmetic kept with the benchmark.

Tolerance: both sides are float32 on the CPU and differ only by summation
order and by the variance formula of GroupNorm (E[x^2] - E[x]^2 in flax, the
two-pass mean of squares here): 2e-5 absolute on logits of order 1.  A bf16
pass would miss by about 1e-2."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedbench import reference
from fedbench.harness import peaks
from fedbench_tiny import tiny_doc
from fedml_tpu.models import create_model


@pytest.mark.parametrize("ref_name,model_name,kwargs,x", [
    ("resnet18gn_cifar", "resnet18_gn",
     {"num_filters": 8, "stage_sizes": (1, 1, 1, 1)},
     np.random.RandomState(0).randn(3, 16, 16, 3).astype(np.float32)),
    ("resnet18gn_cifar", "resnet18_gn", {"num_filters": 4},
     np.random.RandomState(1).randn(2, 8, 8, 3).astype(np.float32)),
    ("so_nwp_lstm", "rnn_stackoverflow",
     {"embedding_dim": 8, "hidden_size": 16},
     np.random.RandomState(2).randint(0, 50, (3, 7)).astype(np.int32)),
])
def test_reference_forward_and_gradients_match_the_model(ref_name, model_name,
                                                         kwargs, x):
    classes = 50 if x.dtype == np.int32 else 10
    model = create_model(model_name, classes, **kwargs)
    variables = model.init(jax.random.PRNGKey(0), jnp.asarray(x), train=False)
    # move every parameter off its initial value (zero biases, unit scales)
    leaves, tree = jax.tree.flatten(variables["params"])
    rs = np.random.RandomState(5)
    params = jax.tree.unflatten(tree, [
        a + 0.1 * rs.randn(*a.shape).astype(np.float32) for a in leaves])
    ref = reference.resolve(ref_name)
    y = rs.randint(0, classes, x.shape[:1] if x.dtype != np.int32 else x.shape)
    mask = np.array([1.0] * (len(x) - 1) + [0.0], np.float32)

    def loss_model(p):
        return reference.masked_ce(model.apply({"params": p}, jnp.asarray(x),
                                               train=True), y, mask)

    def loss_ref(p):
        return reference.masked_ce(ref.forward(p, jnp.asarray(x)), y, mask)

    np.testing.assert_allclose(
        ref.forward(params, jnp.asarray(x)),
        model.apply({"params": params}, jnp.asarray(x), train=False), atol=2e-5)
    g_model, g_ref = jax.grad(loss_model)(params), jax.grad(loss_ref)(params)
    for a, b in zip(jax.tree.leaves(g_model), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_resnet_flops_from_shapes_match_the_record_and_the_compiler():
    """PERF.md's 1.7e14 FLOP per headline round (128 clients x 390 samples)
    is the dense count; XLA's own count of the reference's forward pass
    leaves out the products with the SAME padding, and so does ours."""
    ref = reference.resolve("resnet18gn_cifar")
    model = create_model("resnet18_gn", 10)
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)), train=False))["params"]
    dense = ref.forward_flops(params, (32, 32, 3), dense=True)
    assert 3 * dense * 128 * 390 == pytest.approx(1.7e14, rel=0.03)
    fwd = ref.forward_flops(params, (32, 32, 3))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    assert n == 11_173_962
    cost = jax.jit(ref.forward).lower(
        params, jax.ShapeDtypeStruct((1, 32, 32, 3), jnp.float32)
    ).compile().cost_analysis()
    assert fwd == pytest.approx(cost["flops"], rel=0.02)


def test_lstm_flops_from_shapes_match_the_compiler():
    ref = reference.resolve("so_nwp_lstm")
    model = create_model("rnn_stackoverflow", 10004)
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 20), jnp.int32), train=False))["params"]
    fwd = ref.forward_flops(params, (20,))
    cost = jax.jit(ref.forward).lower(
        params, jax.ShapeDtypeStruct((1, 20), jnp.int32)).compile().cost_analysis()
    assert fwd == pytest.approx(cost["flops"], rel=0.05)


def test_peaks_know_the_v5e_and_refuse_an_unknown_kind():
    assert peaks.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    assert peaks.peaks("TPU v5 lite")["bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("cpu")


def test_client_size_laws_are_fixed_by_the_file_and_held_to_their_cap():
    from fedbench import client_sizes
    law = {"law": "lognormal_capped", "mu": 4.0, "sigma": 1.5, "cap": 128, "seed": 7}
    mod = client_sizes.resolve(law["law"])
    a, b = mod.sizes(law, 5000), mod.sizes(law, 5000)
    np.testing.assert_array_equal(a, b)
    assert a.min() >= 1 and a.max() == mod.cap(law) == 128
    assert 0.3 < a.mean() / 128 < 0.7          # padded slots exist and are not all
    eq = client_sizes.resolve("equal")
    assert (eq.sizes({"samples": 390}, 4) == 390).all() and eq.cap({"samples": 390}) == 390
    with pytest.raises(ModuleNotFoundError):
        client_sizes.resolve("no_such_law")


def test_memory_peak_is_what_the_program_holds_not_the_compilers_pool(monkeypatch):
    """``memory_peak_bytes`` is ``peak_bytes_in_use`` of the fullest chip; the
    runtime's reserved pool for program temporaries is not added."""
    from fedbench.harness import device

    class Dev:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    devs = [Dev({"peak_bytes_in_use": 3, "peak_bytes_reserved": 100}),
            Dev({"peak_bytes_in_use": 5, "peak_bytes_reserved": 1}), Dev(None),
            Dev({"peak_bytes_in_use": 9})]
    monkeypatch.setattr(jax, "devices", lambda *a: devs)
    assert device.memory_peak_bytes(3) == 5
    assert device.memory_peak_bytes(4) == 9


def test_generators_depend_on_the_seed_only():
    from fedbench.harness import build
    for name in ("xdev10of4000", "xdev50of342k"):
        traffic = tiny_doc("traffic", name)
        a, b, c = (build.make_data(traffic, s) for s in (1, 1, 2))
        for k in a.client_shards:
            np.testing.assert_array_equal(a.client_shards[k], b.client_shards[k])
        assert not np.array_equal(a.client_shards["x"], c.client_shards["x"])
        m = a.client_shards["mask"]
        np.testing.assert_array_equal(m.reshape(len(m), -1).sum(1),
                                      a.client_num_samples)
        assert (a.client_shards["x"][m == 0] == 0).all()


@pytest.mark.parametrize("config_name,traffic_name", [
    ("resnet18gn_cifar", "xdev10of4000"), ("so_nwp_lstm", "xdev50of342k")])
def test_reference_check_passes_in_float32_and_catches_a_bfloat16_pass(
        monkeypatch, config_name, traffic_name):
    """The tolerance in the configuration's file is tight enough that
    computing in a lower precision than float32 fails it."""
    from fedbench.harness import build, correctness
    config = tiny_doc("configs", config_name)
    traffic = tiny_doc("traffic", traffic_name)
    data = build.make_data(traffic, 4)
    sample = {"clients": 3, "batches": 2}
    good = correctness.check_round(config, traffic, data, 4, sample)
    assert good["ok"] and good["max_abs_delta"] <= 1e-4 * good["max_abs_update"]
    real = build.make_engine
    monkeypatch.setattr(build, "make_engine", lambda *a, **k: real(
        *a, **{**k, "train_dtype": "bfloat16"}))
    bad = correctness.check_round(config, traffic, data, 4, sample)
    assert not bad["ok"]
    assert bad["max_abs_delta"] > 2 * config["check"]["param_tol"] * bad["max_abs_update"]
