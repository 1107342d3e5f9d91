"""The LFM2-MoE model (``fedml_tpu/models/lfm2_moe.py``) and the
trainable/frozen split it brings to ``ClientTrainer`` and the mesh engine,
on the CPU at a tiny size with seeded weights.

Tolerance: model and plain reference are both float32 on the CPU and differ
by summation order through a handful of layers: 1e-5 absolute on logits of
order 1-3 and on adapter gradients of order 1e-1."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedbench import reference
from fedml_tpu.core.trainer import ClientTrainer
from fedml_tpu.models import create_model
from fedml_tpu.models import lfm2_moe
from fedml_tpu.obs import scopes

# both layer kinds, a dense layer (0) and expert layers (2: attention, 3: conv)
SMALL = dict(d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=96,
             d_expert=32, n_experts=8, experts_per_token=2, layers=[0, 2, 3],
             num_dense_layers=1, lora_rank=4, lora_alpha=8.0)
REF = dict(n_heads=4, n_kv_heads=2, top_k=2, alpha=8.0)


@pytest.fixture(scope="module")
def case():
    """(model, float32 params off their initial values - norms away from 1,
    adapters' B and the selection bias away from 0 -, tokens)."""
    model = create_model("lfm2_moe", 128, **SMALL)
    rs = np.random.RandomState(0)
    x = rs.randint(0, 128, (3, 16)).astype(np.int32)
    variables = model.init(jax.random.PRNGKey(0), jnp.asarray(x), train=False)
    assert set(variables) == {"params"}          # no counter at rest
    leaves, tree = jax.tree.flatten(variables["params"])
    params = jax.tree.unflatten(tree, [
        jnp.asarray(a, jnp.float32) + 0.1 * rs.randn(*a.shape).astype(np.float32)
        for a in leaves])
    return model, params, x


def test_logits_match_the_reference(case):
    model, params, x = case
    ref = reference.resolve("lfm2_24b_a2b")
    got = model.apply({"params": params}, x, train=True)
    assert got.dtype == jnp.float32 and got.shape == (3, 16, 128)
    np.testing.assert_allclose(got, ref.forward(params, x, **REF), atol=1e-5)


def test_loss_and_adapter_gradients_match_the_reference(case):
    model, params, x = case
    ref = reference.resolve("lfm2_24b_a2b")
    rs = np.random.RandomState(1)
    y = rs.randint(0, 128, x.shape)
    mask = np.array([1.0, 1.0, 0.0], np.float32)
    with_lora = lambda lora: {**params, "lora": lora}
    l_model, g_model = jax.value_and_grad(lambda q: reference.masked_ce(
        model.apply({"params": with_lora(q)}, x, train=True), y, mask))(params["lora"])
    l_ref, g_ref = jax.value_and_grad(lambda q: reference.masked_ce(
        ref.forward(with_lora(q), x, **REF), y, mask))(params["lora"])
    assert abs(float(l_model) - float(l_ref)) < 1e-5
    flat_ref = dict(jax.tree_util.tree_flatten_with_path(g_ref)[0])
    for path, g in jax.tree_util.tree_flatten_with_path(g_model)[0]:
        np.testing.assert_allclose(g, flat_ref[path], atol=1e-5,
                                   err_msg=jax.tree_util.keystr(path))
        # every adapter of every layer is reached, through the expert layers'
        # hand-written backward pass too (layer 0 lies under both)
        assert np.abs(g).max() > 1e-4, jax.tree_util.keystr(path)


def _expert_layer(rs, n_experts=8, d=16, width=8):
    mk = lambda *s: jnp.asarray(rs.randn(*s), jnp.float32)
    return {"router": mk(d, n_experts), "expert_bias": 0.1 * mk(n_experts),
            "w1": 0.3 * mk(n_experts, d, width), "w3": 0.3 * mk(n_experts, d, width),
            "w2": 0.3 * mk(n_experts, width, d)}


def _dense_loop(f, sel, gate, lp):
    """Every selected expert applied with plain products, one at a time."""
    m = np.zeros(f.shape, np.float64)
    for t in range(f.shape[0]):
        for e, g in zip(np.asarray(sel[t]), np.asarray(gate[t])):
            h = jax.nn.silu(f[t] @ lp["w1"][e]) * (f[t] @ lp["w3"][e])
            m[t] += g * np.asarray(h @ lp["w2"][e], np.float64)
    return m


@pytest.mark.parametrize("routing", ["even", "all_on_one", "one_gets_none"])
def test_grouped_product_equals_a_dense_loop_over_experts(routing):
    rs = np.random.RandomState(2)
    lp = _expert_layer(rs)
    n, k, E = 24, 2, 8
    f = jnp.asarray(rs.randn(n, 16), jnp.float32)
    if routing == "even":
        sel = np.stack([(np.arange(n) * k + j) % E for j in range(k)], axis=1)
    elif routing == "all_on_one":
        sel = np.tile(np.array([[5, 5]]), (n, 1))[:, :k]
        sel[:, 1] = 5                     # both slots of every token: expert 5
    else:
        sel = np.stack([rs.permutation(E - 1)[:k] for _ in range(n)])  # never 7
        assert not (sel == 7).any()
    sel = jnp.asarray(sel, jnp.int32)
    gate = jnp.asarray(rs.rand(n, k), jnp.float32)
    product = lfm2_moe.expert_product(0, E, E)
    got = product(f, sel, gate, lp["w1"], lp["w3"], lp["w2"])
    np.testing.assert_allclose(got, _dense_loop(f, sel, gate, lp), atol=1e-5)
    # and its hand-written backward pass is the dense loop's gradient
    dense = lambda f, gate: jnp.sum(jnp.stack([
        gate[:, j, None] * jnp.einsum(
            "tw,twd->td", jax.nn.silu(jnp.einsum("td,tdw->tw", f, lp["w1"][sel[:, j]]))
            * jnp.einsum("td,tdw->tw", f, lp["w3"][sel[:, j]]), lp["w2"][sel[:, j]])
        for j in range(k)]), axis=0)
    probe = jnp.asarray(rs.randn(n, 16), jnp.float32)
    g_got = jax.grad(lambda f, g: jnp.sum(probe * product(
        f, sel, g, lp["w1"], lp["w3"], lp["w2"])), (0, 1))(f, gate)
    g_want = jax.grad(lambda f, g: jnp.sum(probe * dense(f, g)), (0, 1))(f, gate)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_a_chunk_of_clients_is_one_merged_product_with_the_same_values():
    """Under the engine's vmap over clients the product merges their tokens
    and runs once on weights that are not mapped; every client's result and
    gradient are those of its own, unmapped call."""
    rs = np.random.RandomState(3)
    lp = _expert_layer(rs)
    c, n, k = 3, 10, 2
    f = jnp.asarray(rs.randn(c, n, 16), jnp.float32)
    sel = jnp.asarray(np.stack([np.stack([rs.permutation(8)[:k] for _ in range(n)])
                                for _ in range(c)]), jnp.int32)
    gate = jnp.asarray(rs.rand(c, n, k), jnp.float32)
    product = lfm2_moe.expert_product(0, 8, 8)
    loss = lambda f, s, g: jnp.sum(jax.checkpoint(product)(
        f, s, g, lp["w1"], lp["w3"], lp["w2"]) ** 2)
    mapped = jax.jit(jax.vmap(jax.value_and_grad(loss, (0, 2))))
    got_l, got_g = mapped(f, sel, gate)
    for i in range(c):
        want_l, want_g = jax.value_and_grad(loss, (0, 2))(f[i], sel[i], gate[i])
        np.testing.assert_allclose(got_l[i], want_l, rtol=1e-5)
        for a, b in zip(got_g, want_g):
            np.testing.assert_allclose(a[i], b, atol=1e-5)
    # one grouped product of c x n x k rows, not c of them
    text = str(jax.make_jaxpr(mapped)(f, sel, gate))
    rows = [int(m) for m in re.findall(r"f32\[(\d+),\d+\] = ragged_dot_general", text)]
    assert len(rows) >= 6 and set(rows) == {c * n * k}, rows


def test_the_shares_of_eight_ranges_of_experts_add_up_to_the_whole_layer():
    """The share test of the model-configs guide, section 4: with ``held`` =
    8 ranges of 8 experts each, routing over all 64, the eight partial
    results add up to what the uncut layer - and the uncut reference -
    gives, and the routed-token count does not depend on the share."""
    rs = np.random.RandomState(4)
    lp = _expert_layer(rs, n_experts=64)
    f = jnp.asarray(rs.randn(2, 12, 16), jnp.float32)
    def layer(lp, held=None):
        m, c = lfm2_moe.moe_layer(f, lp, 4, 1.0, held=held)
        return m, c[scopes.MOE_EXPERT_TOKENS]
    whole, counts = layer(lp)
    parts = []
    for first in range(0, 64, 8):
        share = dict(lp, **{w: lp[w][first:first + 8] for w in ("w1", "w3", "w2")})
        m, c = layer(share, (first, first + 8))
        np.testing.assert_array_equal(c, counts)
        parts.append(m)
        ref = reference.resolve("lfm2_24b_a2b")
        np.testing.assert_allclose(
            m, ref.experts(f, share, top_k=4, first_held=first), atol=1e-5)
    assert max(float(jnp.abs(p).max()) for p in parts) > 1e-3
    np.testing.assert_allclose(sum(parts), whole, atol=1e-5)
    np.testing.assert_allclose(
        whole, reference.resolve("lfm2_24b_a2b").experts(f, lp, top_k=4), atol=1e-5)
    assert float(counts.sum()) == 4 * 2 * 12          # dropless: every slot


def test_routed_token_counter_sums_to_k_tokens_layers(case):
    model, params, x = case
    _, aux = model.apply({"params": params}, x, train=True,
                         mutable=[scopes.COUNTERS])
    tokens = aux[scopes.COUNTERS][scopes.MOE_EXPERT_TOKENS]
    assert tokens.shape == (2, 8) == model.counters[scopes.MOE_EXPERT_TOKENS]
    assert float(tokens.sum()) == 2 * x.size * 2      # k x tokens x expert layers
    np.testing.assert_array_equal(tokens.sum(axis=1), 2.0 * x.size)


def test_base_is_stored_in_bfloat16_and_the_compute_dtype_is_the_adapters(case):
    model, _, x = case
    v = model.init(jax.random.PRNGKey(0), jnp.asarray(x), train=False)["params"]
    trained, frozen = ClientTrainer(model, has_time_axis=True).split_frozen(v)
    assert set(trained) == {"lora"} and "lora" not in frozen
    assert {a.dtype for a in jax.tree.leaves(frozen)} == {jnp.dtype(jnp.bfloat16)}
    assert {a.dtype for a in jax.tree.leaves(trained)} == {jnp.dtype(jnp.float32)}
    full = model.apply({"params": v}, x, train=True)
    half = model.apply({"params": {**v, "lora": jax.tree.map(
        lambda a: a.astype(jnp.bfloat16), v["lora"])}}, x, train=True)
    assert full.dtype == half.dtype == jnp.float32
    assert 1e-5 < np.abs(full - half).max() < 0.3
    # B = 0: the adapted model starts as the base
    assert all(float(jnp.abs(b).max()) == 0 for name, b in
               jax.tree_util.tree_leaves_with_path(v["lora"])
               if jax.tree_util.keystr(name).endswith("_b']"))


# -- the trainable/frozen split in the trainer and the mesh engine ----------

def _engine(chunk=2, **model_kw):
    from fedbench.harness import build
    config = {"model": {"factory": "fedml_tpu.models.create_model",
                        "name": "lfm2_moe", "kwargs": {**SMALL, **model_kw}},
              "trainer": {"loss": "ce", "optimizer": "sgd",
                          "train_dtype": "bfloat16", "has_time_axis": True},
              "engine": {"local_dtype": None, "chunk": chunk}}
    traffic = {"dataset": {"generator": "classed_markov_tokens",
                           "args": {"seq_len": 16, "vocab": 128, "classes": 4}},
               "population": 6, "cohort": 4,
               "client_sizes": {"law": "equal", "samples": 2},
               "batch_size": 1, "epochs": 1, "lr": 0.3, "mesh_devices": 1,
               "engine": {"class": "fedml_tpu.parallel.MeshFedAvgEngine",
                          "args": {"streaming": False}}}
    data = build.make_data(traffic, 3)
    return build.make_engine(config, traffic, data, 3), build


def test_frozen_leaves_come_back_bitwise_and_the_counter_is_exact():
    from fedbench.harness import loop
    engine, build = _engine()
    state = loop.State(engine, build.init_variables(engine), 3)
    before = jax.tree.map(np.asarray, state.variables["params"])
    engine.transfer_stats.reset()
    win = loop.run_rounds(state, 2, rounds=3)
    assert win["failed"] == 0 and win["losses"][-1] < win["losses"][0]
    after = jax.tree.map(np.asarray, state.variables["params"])
    for name in before:
        same = jax.tree.map(np.array_equal, before[name], after[name])
        assert all(jax.tree.leaves(same)) == (name != "lora"), name
    # 3 rounds x 4 clients x 2 steps x 16 tokens x 2 a token x 2 expert layers
    tokens = engine.transfer_stats.program_counters()[scopes.MOE_EXPERT_TOKENS]
    assert tokens.shape == (2, 8) and tokens.sum() == 3 * 4 * 2 * 16 * 2 * 2
    engine.transfer_stats.reset()
    assert engine.transfer_stats.program_counters() == {}


def test_round_carries_folds_and_copies_the_adapters_alone():
    """The Σ w·v carry is as long as the adapters, no client holds a copy of a
    frozen leaf (nothing in the program has a frozen leaf's shape with a
    client axis in front), and the compiled round holds no float32 buffer of
    a frozen matrix's shape: the base is read as it is stored."""
    from fedml_tpu.parallel.engine import flatten_carry_f32
    engine, build = _engine()
    variables = jax.eval_shape(engine.init_variables)
    trained = engine.trainer.trained_variables(variables)
    n_adapters = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(trained))
    n_all = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(variables))
    assert n_adapters == sum(
        int(np.prod(a.shape)) for a in jax.tree.leaves(variables["params"]["lora"]))
    zeros = engine._zero_sums(variables)
    assert flatten_carry_f32(zeros[0])[0].shape == (n_adapters,)
    assert n_adapters < n_all / 20
    stack, stack_w = engine._device_stack()
    ids, wmask = engine.sample_padded(0)
    lowered = engine.round_fn.inner.lower(
        engine._prepare_variables(engine.init_variables()), (), stack, stack_w,
        ids, wmask, jax.random.PRNGKey(0))
    frozen = engine.trainer.split_frozen(variables["params"])[1]
    # the expert stacks and the embedding: shapes no activation shares
    shapes = {a.shape for a in jax.tree.leaves(frozen) if len(a.shape) == 3}
    shapes.add(frozen["embed"].shape)
    assert len(shapes) == 3
    # the traced program: a frozen leaf appears as it is stored and never
    # behind a client axis (that the chip's compiled round holds no float32
    # buffer of such a shape either is tests/test_tpu_compile.py's, at the
    # published widths: a CPU's compiler widens bfloat16 products itself)
    text = lowered.as_text()
    for shape in shapes:
        dims = "x".join(map(str, shape))
        assert f"tensor<{dims}xbf16>" in text, shape
        assert not re.search(rf"tensor<\d+x{dims}x(bf16|f32)>", text), shape


def test_naming_every_leaf_trainable_adds_no_operation_to_local_train():
    """One path: a model that names every leaf trainable traces to the same
    jaxpr as the same model naming nothing - the split adds no operation.
    (That a model naming nothing compiles to the PARENT's round program is
    shown on the five cells' real shapes, text against text: PERF.md §6
    PR 34.)"""
    model = create_model("looped_lm", 64, d_model=32, n_heads=2, head_dim=16,
                         d_ff=48, n_layers=1, n_passes=1)
    x = np.zeros((2, 2, 8), np.int32)
    shard = {"x": x, "y": x, "mask": np.ones((2, 2), np.float32)}
    variables = model.init(jax.random.PRNGKey(0), x[0], train=False)

    def jaxpr(m):
        tr = ClientTrainer(m, has_time_axis=True, train_dtype=jnp.bfloat16)
        return str(jax.make_jaxpr(lambda v, s, r: tr.local_train(v, s, r, 1))(
            variables, shard, jax.random.PRNGKey(1)))

    plain = jaxpr(model)
    object.__setattr__(model, "trainable", tuple(variables["params"]))
    try:
        assert jaxpr(model) == plain
    finally:
        object.__delattr__(model, "trainable")


@pytest.mark.parametrize("mesh", [True, False])
def test_cli_runs_two_rounds(tmp_path, mesh):
    import subprocess
    import sys
    import os
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    cmd = [sys.executable, "-m", "fedml_tpu.cli", "--algorithm", "fedavg",
           "--dataset", "fed_shakespeare", "--model", "lfm2_moe",
           "--synthetic_scale", "0.01", "--client_num_in_total", "4",
           "--client_num_per_round", "2", "--comm_round", "2",
           "--batch_size", "2", "--lr", "0.1", "--frequency_of_the_test", "1",
           "--run_dir", str(tmp_path)] + (["--mesh"] if mesh else [])
    r = subprocess.run(cmd, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
