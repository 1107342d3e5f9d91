"""The looped decoder LM (``fedml_tpu/models/looped_lm.py``) against the plain
reference of ``ouro_2p6b`` on the CPU at a small size (2 layers x 3 passes,
hidden 64, 4 heads of 16, vocabulary 128, 16 tokens), the reference check's
control for this configuration, and the counts kept with the benchmark.

Tolerance: both sides are float32 on the CPU and differ by summation order
through 6 layer applications of four norms each: 1e-5 absolute on logits of
order 1-3 and on gradients of order 1e-2.  A bfloat16 pass misses by 1e-2."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedbench import reference
from fedbench_tiny import REPO, load, tiny_doc
from fedml_tpu.models import create_model

SMALL = dict(d_model=64, n_heads=4, head_dim=16, d_ff=96, n_layers=2,
             n_passes=3, rope_theta=1e6)
REF = dict(n_heads=4, n_passes=3)


@pytest.fixture(scope="module")
def case():
    """(model, params off their initial values - norm weights away from 1,
    a gate with a bias -, tokens)."""
    model = create_model("looped_lm", 128, **SMALL)
    rs = np.random.RandomState(0)
    x = rs.randint(0, 128, (3, 16)).astype(np.int32)
    variables = model.init(jax.random.PRNGKey(0), jnp.asarray(x), train=False)
    leaves, tree = jax.tree.flatten(variables["params"])
    params = jax.tree.unflatten(tree, [
        a + 0.1 * rs.randn(*a.shape).astype(np.float32) for a in leaves])
    return model, params, x


def test_every_exit_and_the_exit_distribution_match_the_reference(case):
    model, params, x = case
    ref = reference.resolve("ouro_2p6b")
    logits, p = model.apply({"params": params}, x, method=model.all_exits)
    want_logits, want_p = ref.forward_all(params, x, **REF)
    assert logits.shape == (3, 3, 16, 128) and p.shape == (3, 3, 16)
    np.testing.assert_allclose(logits, want_logits, atol=1e-5)
    np.testing.assert_allclose(p, want_p, atol=1e-5)
    np.testing.assert_allclose(np.sum(p, axis=0), 1.0, atol=1e-6)
    assert np.all(p > 0) and np.std(p[0]) > 1e-3       # a gate that gates
    # the trainer's contract: __call__ is the last exit, in float32
    last = model.apply({"params": params}, x, train=True)
    assert last.dtype == jnp.float32
    np.testing.assert_allclose(last, ref.forward(params, x, **REF), atol=1e-5)
    np.testing.assert_array_equal(last, logits[-1])
    # exits differ: a loop that did nothing would repeat the first
    assert np.abs(logits[0] - logits[-1]).max() > 1e-2


def test_gradient_of_the_cells_loss_matches_the_reference(case):
    model, params, x = case
    ref = reference.resolve("ouro_2p6b")
    rs = np.random.RandomState(1)
    y = rs.randint(0, 128, x.shape)
    mask = np.array([1.0, 1.0, 0.0], np.float32)
    g_model = jax.grad(lambda q: reference.masked_ce(
        model.apply({"params": q}, x, train=True), y, mask))(params)
    g_ref = jax.grad(lambda q: reference.masked_ce(
        ref.forward(q, x, **REF), y, mask))(params)
    for name in g_ref:
        np.testing.assert_allclose(g_model[name], g_ref[name], atol=1e-5,
                                   err_msg=name)
    # every stored layer is used in every pass: no weight without a gradient,
    # but the gate, which the last exit's loss does not reach
    for name, g in g_model.items():
        assert (np.abs(g).max() == 0) == name.startswith("exit_gate"), name


def test_the_scans_and_the_checkpoint_change_nothing(case):
    model, params, x = case
    plain = create_model("looped_lm", 128, unrolled=True, **SMALL)
    for method in ("__call__", "all_exits"):
        a = model.apply({"params": params}, x, method=method)
        b = plain.apply({"params": params}, x, method=method)
        for u, v in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_allclose(u, v, atol=1e-5)
    loss = lambda m: lambda q: jnp.mean(jnp.square(m.apply({"params": q}, x)))
    g_a, g_b = jax.grad(loss(model))(params), jax.grad(loss(plain))(params)
    for name in g_a:
        np.testing.assert_allclose(g_a[name], g_b[name], atol=1e-6, err_msg=name)
    # one layer body in the program, whatever the depth
    hlo = jax.jit(lambda q: model.apply({"params": q}, x)).lower(params).as_text()
    assert hlo.count("stablehlo.while") == 2


def test_matrix_products_run_in_the_parameters_dtype_and_logits_stay_float32(case):
    model, params, x = case
    half = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    out = model.apply({"params": half}, x, train=True)
    assert out.dtype == jnp.float32
    full = model.apply({"params": params}, x, train=True)
    assert 1e-4 < np.abs(out - full).max() < 0.3


def test_counts_at_the_published_widths():
    """ISSUE 26's arithmetic: 509,661,185 parameters at 6 layers, 2.83 TFLOP
    forward per 1,024-token sequence, 136 TFLOP a round of the cell."""
    config = load(REPO + "/fedbench/configs/ouro_2p6b.json")
    ref = reference.resolve(config["reference"])
    model = create_model(config["model"]["name"], config["vocab_size"],
                         **config["model"]["kwargs"])
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    assert n == config["widths"]["parameters_at_6_layers"] == 509_661_185
    assert n == 6 * 51_388_416 + 201_330_689
    fwd = ref.forward_flops(params, (1024,))
    assert fwd == 2 * 1024 * (24 * 51_380_224 + 100_663_296) + 24 * 2 ** 32
    assert fwd == pytest.approx(2.83e12, rel=2e-3)
    assert 16 * ref.train_flops(params, (1024,)) == pytest.approx(136e12, rel=1e-3)
    # the file states the published widths and cuts depth only
    kw, w = config["model"]["kwargs"], config["widths"]
    assert (kw["d_model"], kw["n_heads"], kw["head_dim"], kw["d_ff"], kw["n_passes"]) \
        == (config["hidden_size"], config["num_attention_heads"], config["head_dim"],
            config["intermediate_size"], config["total_ut_steps"]) == (2048, 16, 128, 5632, 4)
    assert (kw["rope_theta"], kw["norm_eps"]) == (config["rope_theta"], config["rms_norm_eps"])
    assert config["reduced"] == ["num_hidden_layers"] and kw["n_layers"] == 6
    assert w["vocab_size"] == 49152 and config["tie_word_embeddings"] is False


def test_the_file_holds_every_number_of_the_catalog_row():
    import json
    import os
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog in this image")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    config = load(REPO + "/fedbench/configs/ouro_2p6b.json")
    assert config["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if config.get(k, "absent") != v]
    assert differs == config["reduced"] == ["num_hidden_layers"]


@pytest.mark.parametrize("train_dtype,passes", [("float32", True),
                                                ("bfloat16", False)])
def test_reference_check_passes_in_float32_and_catches_a_bfloat16_pass(
        monkeypatch, train_dtype, passes):
    """``test_fedbench_reference.py``'s control for this configuration: the
    file's tolerance holds the float32 round and refuses the bfloat16 one; the
    gate's leaves, which the loss does not reach, come back as they went in."""
    from fedbench.harness import build, correctness
    config = tiny_doc("configs", "ouro_2p6b")
    traffic = tiny_doc("traffic", "silo4of256t1024")
    data = build.make_data(traffic, 4)
    seen = {}
    real_engine, real_round = build.make_engine, reference.fedavg_round

    def engine(*a, **k):
        return real_engine(*a, **{**k, "train_dtype": train_dtype})

    def fedavg_round(ref, variables, *a, **k):
        seen["before"] = variables["params"]
        seen["after"], loss = real_round(ref, variables, *a, **k)
        return seen["after"], loss

    monkeypatch.setattr(build, "make_engine", engine)
    monkeypatch.setattr(reference, "fedavg_round", fedavg_round)
    got = correctness.check_round(config, traffic, data, 4,
                                  {"clients": 4, "batches": 2})
    tol = config["check"]["param_tol"]
    assert got["ok"] is passes
    if passes:
        assert got["max_abs_delta"] <= 0.1 * tol * got["max_abs_update"]
    else:
        assert got["max_abs_delta"] > 2 * tol * got["max_abs_update"]
    for name in ("exit_gate_kernel", "exit_gate_bias"):
        np.testing.assert_array_equal(seen["after"][name], seen["before"][name])
    assert not np.array_equal(seen["after"]["layers_wq"], seen["before"]["layers_wq"])
