"""What `models/deepseek_v2.py`'s per-layer `jax.checkpoint` keeps (ISSUE 40).

Where the residual stream is 16 bits wide a layer keeps its input and the
values `latent_attention` and `ops/attention.py` name — `W_o`'s adapted
output and the attention rule's `(o, lse)` — and the backward pass re-runs
the latent side, the router, the experts and the MLPs: not the attention
forward and not the output projection.  A float32 stream (the twin the
benchmark's reference check runs) keeps a layer's input alone, as every
stream did before.  The layers are a Python loop, so a kept value is a
residual of its own, not a slice of a scan's stack.  Tiny widths on the CPU;
T = 128 and head parts of 64 are a shape the fused attention takes, so the
rule with the two names is the one that is traced (the platform switch
inside it runs the plain body here).
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals

from fedml_tpu import obs
from fedml_tpu.models import create_model, deepseek_v2
from fedml_tpu.obs import scopes
from test_looped_lm_remat import _rematted

B, T, VOCAB = 1, 128, 64
# a dense layer (0) and two expert layers holding ONE group (experts 2, 3);
# no two matrices of a layer share a shape, so a product is known by its
# weight's
WIDTHS = dict(d_model=48, n_heads=4, q_rank=24, kv_rank=16, nope_dim=64,
              rope_dim=64, v_dim=64, d_ff=96, d_expert=16, n_experts=16,
              experts_per_token=6, n_group=8, topk_group=3, n_shared=2,
              n_layers=6, first_dense=1, layers=[0, 1, 2], held=[2, 2],
              rope_original=64, lora_rank=4, lora_alpha=8.0)
D, H, V_DIM, RANK = (WIDTHS[k] for k in ("d_model", "n_heads", "v_dim", "lora_rank"))
LAYERS = len(WIDTHS["layers"])
DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _case(dtype):
    """(model, loss(lora) with the counter as aux, adapters in `dtype` off
    their initial values, the rest of the parameters, tokens)."""
    model = create_model("deepseek_v2", VOCAB, **WIDTHS)
    x = jnp.asarray(np.random.RandomState(0).randint(0, VOCAB, (B, T)))
    params = model.init(jax.random.PRNGKey(0), x, train=False)["params"]
    rs = np.random.RandomState(1)               # norms away from 1, B from 0
    params = jax.tree.map(lambda a: (a.astype(jnp.float32) + 0.05 * rs.randn(
        *a.shape)).astype(dtype), params)
    lora = params.pop("lora")

    def loss(lora):
        logits, aux = model.apply({"params": {**params, "lora": lora}}, x,
                                  train=True, mutable=[scopes.COUNTERS])
        return (jnp.mean(jnp.square(logits)),
                aux[scopes.COUNTERS][scopes.MOE_EXPERT_TOKENS])

    return model, loss, lora


def _value(loss):
    return lambda lora: loss(lora)[0]


def _kept(loss, lora):
    """{(shape, dtype name): how many} of the gradient's residuals that the
    layers make: not an argument, a weight or the rotary tables, and not the
    head's (the output norm and the loss)."""
    found = {}
    for aval, why in saved_residuals(_value(loss), lora):
        made = ("deepseek_v2.py" in why or "attention.py" in why) and not (
            "<genexpr>" in why or "from the argument" in why
            or "from a constant" in why)
        if made:
            key = (aval.shape, aval.dtype.name)
            found[key] = found.get(key, 0) + 1
    return found


def _products(loss, lora):
    """(primitive names the backward pass runs again, shapes of the weights
    of the matrix products among them)."""
    weights = []
    again, _ = _rematted(
        _value(loss), lora, visit=lambda e: e.primitive.name == "dot_general"
        and weights.append(e.invars[1].aval.shape))
    return again, weights


W_O, W_O_A, W_O_B = (H * V_DIM, D), (H * V_DIM, RANK), (RANK, D)


def test_a_16_bit_stream_keeps_the_layer_inputs_and_the_named_values():
    """(a) One residual a layer for its input, `W_o`'s output, `o` and the
    log-sum-exp, each in the dtype the backward pass reads it in, and nothing
    else of the layers'; the re-run holds no platform switch and no kernel of
    the attention rule's, and
    neither `W_o` nor its adapter's product back to the stream - the rank-wide
    `o A` is made again (the gradient of B reads it, `lfm2_moe._adapted`
    names nothing) - and still the latent side's (the backward kernel reads
    q, k, v again)."""
    _, loss, lora = _case(jnp.bfloat16)
    assert _kept(loss, lora) == {
        ((B, T, D), "bfloat16"): 2 * LAYERS,               # h, W_o's output
        ((B, T, H, V_DIM), "bfloat16"): LAYERS,            # o
        ((B, H, T), "float32"): LAYERS}                    # lse
    again, weights = _products(loss, lora)
    # the one platform switch a layer is the rotary's (`ops/rotary.py`, whose
    # residuals are the tables: the latent side turns q_rope again), with its
    # kernel in the TPU branch; the attention rule's is not there
    kernels = []
    _rematted(_value(loss), lora, visit=lambda e: e.primitive.name == "pallas_call"
              and kernels.append(e.params["name"]))
    assert kernels == ["rotate_half"] * LAYERS, kernels
    assert again.count("platform_index") == again.count("cond") == LAYERS, again
    assert "custom_vjp_call" not in again, again
    assert not {W_O, W_O_B} & set(weights), weights
    assert weights.count(W_O_A) == LAYERS
    assert weights.count((D, WIDTHS["q_rank"])) == LAYERS          # W_qa
    assert weights.count((WIDTHS["kv_rank"], H * 2 * V_DIM)) == LAYERS  # W_kvb
    assert {"rsqrt", "mul", "add", "logistic", "top_k"} <= set(again)
    # a layer that holds a share of its experts re-runs the router's scores
    # and selection, and nothing of the product: the backward blocks sort the
    # slots and re-make the pre-activations of the rows they gather (PR 43)
    assert not {"sort", "while", "ragged_dot_general"} & set(again), again


def test_a_float32_stream_keeps_the_layer_inputs_alone():
    """(c) The program of before - the one the reference check's twin
    compiles to: a layer's input is its only residual (and the last layer's
    output, the output norm's input), and the whole layer, `W_o` and the
    attention rule included, is under the re-run."""
    _, loss, lora = _case(jnp.float32)
    assert _kept(loss, lora) == {((B, T, D), "float32"): LAYERS + 1}
    again, weights = _products(loss, lora)
    assert "pallas_call" in again
    assert all(weights.count(shape) == LAYERS
               for shape in (W_O, W_O_A, W_O_B)), weights


def _bare(monkeypatch):
    monkeypatch.setattr(deepseek_v2, "_KEEP", None)


def _no_checkpoint(monkeypatch):
    monkeypatch.setattr(jax, "checkpoint", lambda f, **kw: f)


@pytest.mark.parametrize("other", [_bare, _no_checkpoint])
@pytest.mark.parametrize("dtype", DTYPES)
def test_gradients_are_the_other_programs_to_the_bit(monkeypatch, dtype, other):
    """(b) What is kept changes nothing that is computed: loss and every
    adapter's gradient equal those of a checkpoint that keeps no name (the
    checkpoint of before) and of the plain loop (no checkpoint at all) to the
    bit - run op by op (`jax.disable_jit`), so that each primitive is its own
    program (under `jit` XLA:CPU fuses the programs differently)."""
    # `rotate_half`'s platform switch is ONE compiled program where a
    # checkpoint evaluates its jaxpr and a Python branch where none does; its
    # plain body, which a CPU program lowers to (tests/test_deepseek_v2.py
    # holds the two equal to the bit under `jit`), is primitives either way
    from fedml_tpu.ops.rotary import apply_rotary
    monkeypatch.setattr(deepseek_v2, "rotate_half", apply_rotary)
    _, loss, lora = _case(DTYPES[dtype])
    with jax.disable_jit():
        got = jax.value_and_grad(_value(loss))(lora)
        other(monkeypatch)
        want = jax.value_and_grad(_value(loss))(lora)
    assert float(got[0]) > 0
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32),
                                      err_msg=jax.tree_util.keystr(path))
        assert np.any(np.asarray(a) != 0), jax.tree_util.keystr(path)


@pytest.mark.parametrize("dtype, saved", [("bfloat16", "attention"),
                                          ("float32", "input_only")])
def test_the_choice_and_the_bytes_kept_are_counted(dtype, saved):
    """(e) `remat_policy_total{model, saved}` ticks once a trace, and
    `remat_saved_bytes` is what a local step's layers keep: the sizes
    `saved_residuals` lists, summed (less the last layer's output, which the
    float32 head keeps, not a layer)."""
    _, loss, lora = _case(DTYPES[dtype])
    obs.reset()
    count = {s: obs.counter("remat_policy_total", model="deepseek_v2", saved=s)
             for s in ("attention", "input_only")}
    jax.make_jaxpr(loss)(lora)
    assert {s: c.value for s, c in count.items() if c.value} == {saved: 1}
    held = sum(n * int(np.prod(shape)) * jnp.dtype(dt).itemsize
               for (shape, dt), n in _kept(loss, lora).items())
    held -= (dtype == "float32") * B * T * D * 4
    assert obs.gauge("remat_saved_bytes", model="deepseek_v2").value == held


def test_bytes_kept_at_the_published_widths():
    """`deepseekv2.lora4of256t4096`'s local step (one sequence of 4,096
    tokens, 5 layers, 128 heads with 128-wide values, hidden 5,120: the
    configuration's file) keeps 178,257,920 B of named values and
    41,943,040 B of input a layer in bfloat16: the gauge reads
    1,101,004,800 B from a trace of the model at those widths; the float32
    twin of the reference check keeps the inputs alone."""
    with open(os.path.join(REPO, "fedbench", "configs", "deepseek_v2.json")) as f:
        config = json.load(f)
    model = create_model("deepseek_v2", config["widths"]["vocab_rows_held"],
                         **config["model"]["kwargs"])
    x = jax.ShapeDtypeStruct((1, 4096), jnp.int32)
    params = jax.eval_shape(lambda x: model.init(
        jax.random.PRNGKey(0), x, train=False), x)["params"]
    h = jax.ShapeDtypeStruct((1, 4096, model.d_model), jnp.bfloat16)
    assert deepseek_v2.kept_bytes(h, model.n_heads, model.v_dim) == 178_257_920
    for dtype, saved, held in (("bfloat16", "attention", 1_101_004_800),
                               ("float32", "input_only", 5 * 2 * 41_943_040)):
        lora = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, DTYPES[dtype]), params["lora"])
        obs.reset()
        jax.eval_shape(lambda base, lora, x: model.apply(
            {"params": {**base, "lora": lora}}, x, train=True), params, lora, x)
        assert obs.counter("remat_policy_total", model="deepseek_v2",
                           saved=saved).value == 1
        assert obs.gauge("remat_saved_bytes", model="deepseek_v2").value == held


@pytest.mark.parametrize("dtype", DTYPES)
def test_the_routed_token_counter_is_exact_under_the_policy(dtype):
    """The counter is sown in a layer's first run and is no residual: under
    either rule the gradient's program returns 6 x tokens for each of the two
    expert layers, once - the re-run adds nothing to it."""
    model, loss, lora = _case(DTYPES[dtype])
    (_, tokens), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(lora)
    assert tokens.shape == model.counters[scopes.MOE_EXPERT_TOKENS] == (2, 16)
    np.testing.assert_array_equal(tokens.sum(axis=1), 6.0 * B * T)
    assert 0 < tokens[:, 2:4].sum() < tokens.sum() / 2          # the held two
    assert all(np.isfinite(np.asarray(g, np.float32)).all()
               for g in jax.tree.leaves(grads))


@pytest.mark.parametrize("dtype", DTYPES)
def test_one_round_trains_what_the_bare_checkpoint_trains(monkeypatch, dtype):
    """One FedAvg round through the mesh engine (four clients in two chunks
    of two under the `vmap`, one closed-over base), computing in `dtype` on
    float32 masters: the committed adapters are the bare checkpoint's to the
    compute dtype's rounding, on either side of the rule, and the round's
    routed-token counter is the same array - the programs are jitted, so
    XLA:CPU fuses them differently (the bitwise statement is the op-by-op
    test's)."""
    from fedbench.harness import build, loop

    def committed():
        config = {"model": {"factory": "fedml_tpu.models.create_model",
                            "name": "deepseek_v2", "kwargs": WIDTHS},
                  "trainer": {"loss": "ce", "optimizer": "sgd",
                              "train_dtype": dtype, "has_time_axis": True},
                  "engine": {"local_dtype": None, "chunk": 2}}
        traffic = {"dataset": {"generator": "classed_markov_tokens",
                               "args": {"seq_len": T, "vocab": VOCAB, "classes": 4}},
                   "population": 4, "cohort": 4,
                   "client_sizes": {"law": "equal", "samples": 2},
                   "batch_size": 1, "epochs": 1, "lr": 0.3, "mesh_devices": 1,
                   "engine": {"class": "fedml_tpu.parallel.MeshFedAvgEngine",
                              "args": {"streaming": False}}}
        data = build.make_data(traffic, 3)
        engine = build.make_engine(config, traffic, data, 3)
        state = loop.State(engine, build.init_variables(engine), 3)
        start = jax.tree.map(np.asarray, state.variables["params"]["lora"])
        engine.transfer_stats.reset()
        assert loop.run_rounds(state, 1, rounds=1)["failed"] == 0
        tokens = engine.transfer_stats.program_counters()[scopes.MOE_EXPERT_TOKENS]
        return start, jax.tree.map(np.asarray, state.variables["params"]["lora"]), tokens

    start, got, tokens = committed()
    _bare(monkeypatch)
    _, want, tokens_bare = committed()
    assert tokens.sum() == 4 * 2 * T * 6 * 2
    # float32: summation order only; bfloat16: a few ulps (2^-8) of the
    # largest update, and a token near a router tie may visit another expert
    tol = {"float32": 1e-4, "bfloat16": 5e-2}[dtype]
    if dtype == "float32":
        np.testing.assert_array_equal(tokens, tokens_bare)
    moved = 0
    for s, a, b in zip(*map(jax.tree.leaves, (start, got, want))):
        update = float(np.max(np.abs(b - s)))
        assert float(np.max(np.abs(a - b))) <= tol * max(update, 1e-6)
        moved += update > 0
    assert moved == LAYERS * 5 * 2                 # every adapter, A and B
