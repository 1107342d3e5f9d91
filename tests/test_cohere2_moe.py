"""The Cohere2-MoE model (``fedml_tpu/models/cohere2_moe.py``: parallel blocks,
sliding-window and full attention layers mixed on one attention op, a sigmoid
router with renormalised gates, averaged shared experts beside the held routed
ones, adapters over a frozen base) against its plain reference
(``fedbench/reference/command_a_plus.py``), on the CPU at a tiny size that
keeps every ratio — three sliding layers to one full, 16 query heads over
each key-value head, 8 experts a token, 4 shared, a sequence over two windows
— with seeded weights; and through ``MeshFedAvgEngine``'s normal round.

Tolerance: model and reference are both float32 on the CPU and differ by
summation order through a handful of layers: 1e-5 absolute on logits of
order 1 and on adapter gradients of order 1e-1."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedbench import reference
from fedml_tpu.core.trainer import ClientTrainer
from fedml_tpu.models import cohere2_moe, create_model, lfm2_moe
from fedml_tpu.models.looped_lm import rotary_tables
from fedml_tpu.obs import scopes

# one whole period (sliding, sliding, sliding, full) holding experts 8..15 of 32
SMALL = dict(d_model=64, n_heads=32, n_kv_heads=2, head_dim=8, d_expert=32,
             n_experts=32, experts_per_token=8, n_shared=4, sliding_window=6,
             layer_types=["sliding_attention"] * 3 + ["full_attention"],
             layers=[0, 1, 2, 3], held=[8, 16], lora_rank=4, lora_alpha=8.0)
REF = dict(n_heads=32, n_kv=2, period=4, window=6, query_block=5, top_k=8,
           n_shared=4, first_held=8, alpha=8.0, theta=5e4)
REF_NAME = "command_a_plus"
T = 15                           # over two windows of 6, three query blocks


@pytest.fixture(scope="module")
def case():
    """(model, float32 params off their initial values - norms away from 1,
    the adapters' B away from 0 -, tokens)."""
    model = create_model("cohere2_moe", 128, **SMALL)
    rs = np.random.RandomState(0)
    x = rs.randint(0, 128, (3, T)).astype(np.int32)
    variables = model.init(jax.random.PRNGKey(0), jnp.asarray(x), train=False)
    assert set(variables) == {"params"}          # no counter at rest
    leaves, tree = jax.tree.flatten(variables["params"])
    params = jax.tree.unflatten(tree, [
        jnp.asarray(a, jnp.float32) + 0.1 * rs.randn(*a.shape).astype(np.float32)
        for a in leaves])
    return model, params, x


def test_logits_match_the_reference(case):
    model, params, x = case
    ref = reference.resolve(REF_NAME)
    got = model.apply({"params": params}, x, train=True)
    assert got.dtype == jnp.float32 and got.shape == (3, T, 128)
    want = ref.forward(params, x, **REF)
    assert float(jnp.abs(want).max()) > 0.5
    np.testing.assert_allclose(got, want, atol=1e-5)
    # attention in blocks of queries is attention: one block of all 15
    np.testing.assert_allclose(
        want, ref.forward(params, x, **{**REF, "query_block": T}), atol=1e-5)
    # the window does something at this length, in model and reference alike
    wide = ref.forward(params, x, **{**REF, "window": T})
    assert float(jnp.abs(wide - want).max()) > 1e-3
    np.testing.assert_allclose(
        create_model("cohere2_moe", 128, **{**SMALL, "sliding_window": T}).apply(
            {"params": params}, x), wide, atol=1e-5)


def test_loss_and_adapter_gradients_match_the_reference(case):
    model, params, x = case
    ref = reference.resolve(REF_NAME)
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
    assert len(flat_ref) == 4 * 4 * 2             # layers x matrices x (A, B)
    for path, g in jax.tree_util.tree_flatten_with_path(g_model)[0]:
        np.testing.assert_allclose(g, flat_ref[path], atol=1e-5,
                                   err_msg=jax.tree_util.keystr(path))
        # every adapter of every layer is reached, through the band, the
        # parallel block's one norm and the expert layers' written-out backward
        assert np.abs(g).max() > 1e-4, jax.tree_util.keystr(path)


def test_a_full_layer_has_no_positional_term_and_a_sliding_one_does(case):
    """With other rotary tables a full layer's output is the same to the bit;
    a sliding layer's is not."""
    model, params, x = case
    h = jnp.asarray(np.random.RandomState(2).randn(2, T, 64), jnp.float32)
    tables = [rotary_tables(T, 8, theta) for theta in (5e4, 1e2)]

    def out(kind, cos, sin):
        return cohere2_moe.block(
            h, params["layer_3"], params["lora"]["layer_3"], cos, sin, kind=kind,
            window=6, n_heads=32, n_kv_heads=2, experts_per_token=8, n_shared=4,
            held=(8, 16), adapter_scale=2.0, eps=1e-5)[0]

    np.testing.assert_array_equal(out(cohere2_moe.FULL, *tables[0]),
                                  out(cohere2_moe.FULL, *tables[1]))
    assert float(jnp.abs(out(cohere2_moe.SLIDING, *tables[0])
                         - out(cohere2_moe.SLIDING, *tables[1])).max()) > 1e-3


def test_layer_norm_is_mean_centred_without_a_bias():
    rs = np.random.RandomState(3)
    x = jnp.asarray(5.0 + 3.0 * rs.randn(4, 64), jnp.float32)
    w = jnp.asarray(rs.randn(64), jnp.float32)
    got = cohere2_moe.layer_norm(x, w, 1e-5)
    want = (x - x.mean(-1, keepdims=True)) / np.sqrt(x.var(-1, keepdims=True) + 1e-5) * w
    np.testing.assert_allclose(got, want, atol=1e-5)
    # a shift of the input changes nothing: an RMSNorm would
    np.testing.assert_allclose(cohere2_moe.layer_norm(x + 7.0, w, 1e-5), got, atol=1e-4)
    assert cohere2_moe.layer_norm(x.astype(jnp.bfloat16), w, 1e-5).dtype == jnp.bfloat16


def _expert_layer(rs, n_experts=32, d=16, width=8, n_shared=4):
    mk = lambda *s: jnp.asarray(rs.randn(*s), jnp.float32)
    return {"router": mk(d, n_experts),
            "w1": 0.3 * mk(n_experts, d, width), "w3": 0.3 * mk(n_experts, d, width),
            "w2": 0.3 * mk(n_experts, width, d),
            "s1": 0.3 * mk(d, n_shared * width), "s3": 0.3 * mk(d, n_shared * width),
            "s2": 0.3 * mk(n_shared * width, d)}


def test_the_sixteen_shares_and_the_shared_experts_once_are_the_uncut_layer():
    """The share test of the model-configs guide, section 4: with ``held`` =
    each sixteenth of the 32 experts in turn, routing over all of them, the
    sixteen partial results - the averaged shared experts, which every chip
    computes alike, counted once - add up to what the uncut layer and the
    uncut reference give, and the routed-token count does not depend on the
    share."""
    rs = np.random.RandomState(4)
    lp = _expert_layer(rs)
    ref = reference.resolve(REF_NAME)
    f = jnp.asarray(rs.randn(2, 12, 16), jnp.float32)
    def layer(lp, held):
        m, c = cohere2_moe.moe_layer(f, lp, 8, 4, held)
        return m, c[scopes.MOE_EXPERT_TOKENS]
    whole, counts = layer(lp, (0, 32))
    shared = lfm2_moe.gated_mlp(f, lp["s1"], lp["s3"], lp["s2"]) / 4
    np.testing.assert_allclose(
        shared, ref.experts(f, dict(lp, **{w: lp[w][:0] for w in ("w1", "w3", "w2")}),
                            8, 4, first_held=0), atol=1e-5)       # four MLPs, averaged
    routed = []
    for first in range(0, 32, 2):
        share = dict(lp, **{w: lp[w][first:first + 2] for w in ("w1", "w3", "w2")})
        m, c = layer(share, (first, first + 2))
        np.testing.assert_array_equal(c, counts)
        np.testing.assert_allclose(m, ref.experts(f, share, 8, 4, first_held=first),
                                   atol=1e-5)
        routed.append(m - shared)
    assert sum(float(jnp.abs(r).max()) > 1e-3 for r in routed) == 16
    np.testing.assert_allclose(sum(routed) + shared, whole, atol=2e-5)
    np.testing.assert_allclose(whole, ref.experts(f, lp, 8, 4, first_held=0), atol=2e-5)
    assert float(counts.sum()) == 8 * 2 * 12          # dropless: every slot


def test_sigmoid_top_8_matches_an_enumeration_ties_to_the_lower_index():
    """Scores on a coarse grid, so that experts tie in most tokens: the
    router's choice is the enumeration's, in order, and so is the reference's
    set; the gates are the chosen scores over their sum."""
    rs = np.random.RandomState(5)
    logits = rs.randint(-2, 2, (40, 32)).astype(np.float32)
    logits[0] = 0.0                                    # every score ties
    sel, gate = cohere2_moe.route_sigmoid(
        jnp.asarray(logits), jnp.eye(32, dtype=jnp.float32), 8)
    r = np.asarray(jax.nn.sigmoid(jnp.asarray(logits)))
    ties = 0
    for t in range(40):
        want = sorted(range(32), key=lambda e: (-r[t][e], e))[:8]
        assert list(np.asarray(sel[t])) == want, (t, sel[t], want)
        ties += len(set(r[t][want])) < 8
    assert ties > 30 and list(np.asarray(sel[0])) == list(range(8))
    chosen = np.take_along_axis(r, np.asarray(sel), 1)
    np.testing.assert_allclose(gate, chosen / chosen.sum(1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gate).sum(1), 1.0, rtol=1e-6)
    weights = reference.resolve(REF_NAME).gate_weights(
        jnp.asarray(logits), jnp.eye(32, dtype=jnp.float32), 8)
    for t in range(40):
        assert sorted(np.flatnonzero(np.asarray(weights[t]))) == sorted(
            np.asarray(sel[t])), t
        np.testing.assert_allclose(np.asarray(weights[t])[np.asarray(sel[t])],
                                   gate[t], rtol=1e-6)


def test_counter_total_is_eight_slots_a_token_and_layer(case):
    """The model counts the tokens routed to every expert of a layer, held or
    not: the total is 8 x tokens x layers exactly, and the held experts' part
    is the number of rows the grouped product runs."""
    model, params, x = case
    _, aux = model.apply({"params": params}, x, train=True,
                         mutable=[scopes.COUNTERS])
    tokens = np.asarray(aux[scopes.COUNTERS][scopes.MOE_EXPERT_TOKENS])
    assert tokens.shape == (4, 32) == model.counters[scopes.MOE_EXPERT_TOKENS]
    assert model.held_layers == (0, 1, 2, 3) and model.held_experts == (8, 16)
    assert tokens.sum() == 8 * x.size * 4
    np.testing.assert_array_equal(tokens.sum(axis=1), 8.0 * x.size)
    held = tokens[:, 8:16].sum()
    assert 0 < held < tokens.sum() - held
    sel = np.repeat(np.arange(32), tokens[0].astype(int)).reshape(-1, 8)
    _, sizes, valid = lfm2_moe._slots(jnp.asarray(sel), 8, 8)
    assert int(sizes.sum()) == int(valid.sum()) == int(tokens[0, 8:16].sum())


def test_base_is_stored_in_bfloat16_and_only_the_adapters_train(case):
    model, _, x = case
    v = model.init(jax.random.PRNGKey(0), jnp.asarray(x), train=False)["params"]
    trained, frozen = ClientTrainer(model, has_time_axis=True).split_frozen(v)
    assert set(trained) == {"lora"} and "lora" not in frozen
    assert {a.dtype for a in jax.tree.leaves(frozen)} == {jnp.dtype(jnp.bfloat16)}
    assert {a.dtype for a in jax.tree.leaves(trained)} == {jnp.dtype(jnp.float32)}
    assert set(trained["lora"]["layer_1"]) == {
        m + s for m in ("wq", "wk", "wv", "wo") for s in ("_a", "_b")}
    assert v["layer_1"]["w1"].shape == (8, 64, 32)        # the experts held
    assert v["layer_1"]["router"].shape == (64, 32)       # scores all experts
    assert v["layer_1"]["s1"].shape == (64, 4 * 32)       # four shared, side by side
    assert v["layer_1"]["wq"].shape == (64, 32 * 8) and v["layer_1"]["wk"].shape == (64, 2 * 8)
    assert set(v) == {"embed", "out_norm", "lora"} | {f"layer_{i}" for i in range(4)}
    assert v["embed"].shape == (128, 64)                  # tied: no head of its own


@pytest.mark.parametrize("dtype, saved", [(jnp.bfloat16, "attention"),
                                          (jnp.float32, "input_only")])
def test_the_checkpoint_policy_follows_the_streams_width_and_is_counted(case, dtype, saved):
    """A 16-bit stream keeps the kernel's (o, lse) and W_o's output beside a
    layer's input, a float32 one the input alone; the choice and the bytes
    are counted at trace time."""
    from fedml_tpu import obs
    model, params, x = case
    count = lambda: obs.counter("remat_policy_total", model="cohere2_moe",
                                saved=saved).value
    cast = jax.tree.map(lambda a: a.astype(dtype), params)
    before = count()
    jaxpr = jax.make_jaxpr(lambda p: model.apply({"params": p}, x))(cast)
    assert count() == before + 1
    policies = {str(e.params.get("policy")) for e in jaxpr.eqns
                if e.primitive.name == "checkpoint" or "remat" in e.primitive.name}
    assert len(policies) == 1
    assert ("None" in policies) == (saved == "input_only")
    tokens, item = x.size, jnp.dtype(dtype).itemsize
    kept = tokens * ((32 * 8 + 64) * item + 4 * 32) if saved == "attention" else 0
    assert obs.gauge("remat_saved_bytes", model="cohere2_moe").value \
        == 4 * (tokens * 64 * item + kept)


# -- through the mesh engine's normal round ---------------------------------

def _engine(chunk=2):
    from fedbench.harness import build
    config = {"model": {"factory": "fedml_tpu.models.create_model",
                        "name": "cohere2_moe", "kwargs": SMALL},
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
    """Two chunks of two clients scanned over one closed-over base: the
    frozen leaves come back bit for bit, every adapter moves, the loss falls
    and the round's counter is 8 x tokens x layers."""
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
    moved = jax.tree.map(lambda a, b: not np.array_equal(a, b),
                         before["lora"], after["lora"])
    assert all(jax.tree.leaves(moved))
    # 3 rounds x 4 clients x 2 steps x 16 tokens x 8 a token x 4 layers
    tokens = engine.transfer_stats.program_counters()[scopes.MOE_EXPERT_TOKENS]
    assert tokens.shape == (4, 32) and tokens.sum() == 3 * 4 * 2 * 16 * 8 * 4
    assert 0 < tokens[:, 8:16].sum() < tokens.sum() / 2


def test_round_has_no_branch_on_the_model_and_folds_the_adapters_alone():
    """`create_model("cohere2_moe")` goes through the split the trainer reads
    off the model (`trainable`), like `lfm2_moe` and `deepseek_v2`: the carry
    is as long as the adapters, and neither `core/` nor `parallel/` names the
    family."""
    from fedml_tpu.parallel.engine import flatten_carry_f32
    engine, _ = _engine()
    variables = jax.eval_shape(engine.init_variables)
    trained = engine.trainer.trained_variables(variables)
    n_adapters = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(trained))
    assert n_adapters == sum(int(np.prod(a.shape))
                             for a in jax.tree.leaves(variables["params"]["lora"]))
    assert flatten_carry_f32(engine._zero_sums(variables)[0])[0].shape == (n_adapters,)
    import fedml_tpu.core.trainer as trainer_mod
    import fedml_tpu.parallel.engine as engine_mod
    for mod in (trainer_mod, engine_mod):
        with open(mod.__file__) as f:
            text = f.read().lower()
        assert "cohere" not in text and "command_a" not in text


def _lane_wide():
    """(model, its parameters' shapes, tokens) at heads as wide as the lanes
    and a sequence of two windows: shapes the TPU kernels take."""
    model = create_model("cohere2_moe", 128, **{
        **SMALL, "head_dim": 128, "n_heads": 4, "sliding_window": 128})
    x = jnp.zeros((1, 256), jnp.int32)
    return model, jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), x))["params"], x


def test_the_round_programs_scopes_tell_the_two_kinds_of_layer_apart():
    """Lowered for a TPU, the model's forward and backward kernels carry the
    scope of their kind of layer - the backward rule's own ``fed_attention``
    yields to it - and `label_of` reads both labels."""
    model, params, x = _lane_wide()
    params = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.bfloat16), params)

    def loss(lora):
        with jax.named_scope(scopes.FED_FORWARD):
            return jnp.sum(model.apply({"params": {**params, "lora": lora}}, x))

    text = jax.jit(jax.grad(loss)).trace(params["lora"]).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    import re
    names = [m.group(1) for m in re.finditer(r'loc\("([^"]*pallas_call[^"]*)"', text)]
    rotary = [n for n in names if "rotate_half" in n]
    labels = [scopes.label_of(n) for n in names if n not in rotary]
    # a location is printed once, whatever the number of layers that share it:
    # a forward and a backward kernel of each kind
    assert sorted(labels) == ["full_attention"] * 2 + ["window_attention"] * 2
    assert any("fed_attention" in n for n in names)       # the rule's own scope is there
    # the rotary's passes are the sliding layers' alone, and one of each pass:
    # the layer's checkpoint keeps the attention kernel's output and re-runs
    # the rotary in front of it
    assert {scopes.label_of(n) for n in rotary} == {"window_attention"}
    assert {scopes.phase_of(n) for n in rotary} == {
        scopes.FORWARD, scopes.RECOMPUTE, scopes.BACKWARD}


def test_a_sliding_layer_holds_the_rotary_op_and_a_full_one_does_not():
    """`attention` at heads as wide as the lanes: under a window q and k go
    through `ops.rotary.rotate_half` (two calls, counted, the kernel in the
    jaxpr's TPU branch), under none the jaxpr does not know the op."""
    from fedml_tpu import obs
    _, params, _ = _lane_wide()
    a = jax.ShapeDtypeStruct((1, 256, 64), jnp.bfloat16)
    tables = rotary_tables(256, 128, 5e4)
    fused = obs.counter("ops_kernel_path_total", op="rotate_half", path="pallas")

    def jaxpr(window):
        return str(jax.make_jaxpr(lambda a, lp, ad: cohere2_moe.attention(
            a, lp, ad, 2.0, *tables, 4, 2, window))(
                a, params["layer_0"], params["lora"]["layer_0"]))

    before = fused.value
    assert jaxpr(128).count("name=rotate_half") == 2
    assert fused.value == before + 2
    assert "rotate_half" not in jaxpr(None)
    assert fused.value == before + 2


def _parent_rotary(x, cos, sin):
    """`looped_lm.apply_rotary` before ISSUE 42, written out."""
    x32 = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x32[..., half:], x32[..., :half]], axis=-1)
    return (x32 * cos[:, None, :] + rot * sin[:, None, :]).astype(x.dtype)


@pytest.mark.parametrize("name, kwargs, call", [
    ("looped_lm", {}, "apply_rotary"), ("lfm2_moe", {"lora_rank": 2}, "apply_rotary"),
    ("deepseek_v2", {}, "rotate_half")])
def test_the_other_models_trace_the_jaxpr_they_traced_before(monkeypatch, name,
                                                             kwargs, call):
    """Two older language models call `apply_rotary`, which moved to
    `ops/rotary.py` as the op's plain body, and `deepseek_v2` calls
    `rotate_half` since PR 46, which takes that body at this size (a rotary
    part of 8): the jaxpr of their loss and gradients is what it is with the
    parent's function in its place, and holds no rotary kernel."""
    import importlib
    module = importlib.import_module("fedml_tpu.models." + name)
    model = create_model(name, output_dim=50, **kwargs)
    x = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, 50)
    params = model.init(jax.random.PRNGKey(0), x)["params"]

    def trace():
        def loss(params):
            return jnp.mean(jnp.square(model.apply({"params": params}, x)))
        return str(jax.make_jaxpr(jax.value_and_grad(loss))(params))

    got = trace()
    monkeypatch.setattr(module, call, _parent_rotary)
    assert got == trace()
    assert "rotate_half" not in got and "concatenate" in got
