"""The DeepSeek-V2 model (``fedml_tpu/models/deepseek_v2.py``: multi-head
latent attention, a group-limited router, shared experts beside the held
routed ones, adapters over a frozen base) against its plain reference
(``fedbench/reference/deepseek_v2.py``), on the CPU at a tiny size that keeps
every ratio — three different head sizes (nope, rope, value), 8 groups of
consecutive experts, the 3 best groups, 6 experts a token, 2 shared — with
seeded weights; and through ``MeshFedAvgEngine``'s normal round.

Tolerance: model and reference are both float32 on the CPU and differ by
summation order through a handful of layers: 1e-5 absolute on logits of
order 1 and on adapter gradients of order 1e-1."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedbench import reference
from fedml_tpu.core.trainer import ClientTrainer
from fedml_tpu.models import create_model, deepseek_v2, lfm2_moe
from fedml_tpu.obs import scopes

# a dense layer (0) and two expert layers holding ONE group (experts 4..7)
SMALL = dict(d_model=64, n_heads=4, q_rank=24, kv_rank=16, nope_dim=16,
             rope_dim=8, v_dim=12, d_ff=96, d_expert=32, n_experts=32,
             experts_per_token=6, n_group=8, topk_group=3, n_shared=2,
             n_layers=6, first_dense=1, layers=[0, 1, 2], held=[4, 4],
             rope_original=16, lora_rank=4, lora_alpha=8.0)
ROPE = dict(theta=1e4, factor=40.0, beta_fast=32.0, beta_slow=1.0,
            original=16, mscale=0.707, mscale_all_dim=0.707)
REF = dict(n_heads=4, head_block=2, top_k=6, n_group=8, topk_group=3,
           first_held=4, scaling=16.0, alpha=8.0, rope=ROPE)
REF_NAME = "deepseek_v2"


@pytest.fixture(scope="module")
def case():
    """(model, float32 params off their initial values - norms away from 1,
    the adapters' B away from 0 -, tokens)."""
    model = create_model("deepseek_v2", 128, **SMALL)
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
    ref = reference.resolve(REF_NAME)
    assert ref.head_sizes(params["layer_1"], 4) == (16, 8, 12)
    got = model.apply({"params": params}, x, train=True)
    assert got.dtype == jnp.float32 and got.shape == (3, 16, 128)
    want = ref.forward(params, x, **REF)
    assert float(jnp.abs(want).max()) > 0.5
    np.testing.assert_allclose(got, want, atol=1e-5)
    # attention in blocks of heads is attention: a block of all four heads
    np.testing.assert_allclose(
        want, ref.forward(params, x, **{**REF, "head_block": 4}), atol=1e-5)


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
    assert len(flat_ref) == 3 * 5 * 2             # layers x matrices x (A, B)
    for path, g in jax.tree_util.tree_flatten_with_path(g_model)[0]:
        np.testing.assert_allclose(g, flat_ref[path], atol=1e-5,
                                   err_msg=jax.tree_util.keystr(path))
        # every adapter of every layer is reached, through the rotary key, both
        # latents and the expert layers' hand-written backward pass
        assert np.abs(g).max() > 1e-4, jax.tree_util.keystr(path)


def test_rotary_tables_are_yarns_and_the_scale_carries_mscale_squared():
    """Above ``beta_fast`` turns a frequency is the plain rotary's, below
    ``beta_slow`` turns it is divided by the factor, between them blended;
    the reference computes the same angles with its own code."""
    cos, sin = deepseek_v2.yarn_tables(8, 64, 1e4, 40.0, 32.0, 1.0, 4096)
    plain = 1e4 ** (-np.arange(0, 64, 2) / 64)
    ang = np.arctan2(np.asarray(sin[1]), np.asarray(cos[1]))[:32]
    np.testing.assert_allclose(ang[:10], plain[:10], rtol=1e-5)
    np.testing.assert_allclose(ang[-8:], plain[-8:] / 40.0, rtol=1e-4)
    assert np.all(ang[10:24] <= plain[10:24] * (1 + 1e-6))
    assert np.all(ang[10:24] >= plain[10:24] / 40.0 * (1 - 1e-6))
    want = reference.resolve(REF_NAME).yarn_angles(
        8, 64, **{**ROPE, "original": 4096})
    np.testing.assert_allclose(cos, np.cos(want), atol=1e-6)
    model = create_model("deepseek_v2", 16, nope_dim=128, rope_dim=64)
    m = 0.1 * 0.707 * np.log(40.0) + 1
    assert abs(model.softmax_scale - 192 ** -0.5 * m * m) < 1e-9
    assert abs(m - 1.2608) < 1e-4


def _expert_layer(rs, n_experts=32, d=16, width=8, shared=16):
    mk = lambda *s: jnp.asarray(rs.randn(*s), jnp.float32)
    return {"router": mk(d, n_experts),
            "w1": 0.3 * mk(n_experts, d, width), "w3": 0.3 * mk(n_experts, d, width),
            "w2": 0.3 * mk(n_experts, width, d),
            "s1": 0.3 * mk(d, shared), "s3": 0.3 * mk(d, shared),
            "s2": 0.3 * mk(shared, d)}


def test_the_shares_of_the_eight_groups_add_up_to_the_whole_layer():
    """The share test of the model-configs guide, section 4: with ``held`` =
    each of the 8 routing groups in turn, routing over all 32 experts, the
    eight partial results - the shared experts, which every chip computes
    alike, counted once - add up to what the uncut layer and the uncut
    reference give, and the routed-token count does not depend on the share."""
    rs = np.random.RandomState(4)
    lp = _expert_layer(rs)
    ref = reference.resolve(REF_NAME)
    f = jnp.asarray(rs.randn(2, 12, 16), jnp.float32)
    def layer(lp, held):
        m, c = deepseek_v2.moe_layer(f, lp, 6, 8, 3, 16.0, held)
        return m, c[scopes.MOE_EXPERT_TOKENS]
    whole, counts = layer(lp, (0, 32))
    shared = lfm2_moe.gated_mlp(f, lp["s1"], lp["s3"], lp["s2"])
    routed = []
    for first in range(0, 32, 4):
        share = dict(lp, **{w: lp[w][first:first + 4] for w in ("w1", "w3", "w2")})
        m, c = layer(share, (first, 4))
        np.testing.assert_array_equal(c, counts)
        np.testing.assert_allclose(
            m, ref.experts(f, share, 6, 8, 3, first_held=first, scaling=16.0),
            atol=1e-5)
        routed.append(m - shared)
        assert float(jnp.abs(routed[-1]).max()) > 1e-3      # every group is used
    np.testing.assert_allclose(sum(routed) + shared, whole, atol=2e-5)
    np.testing.assert_allclose(
        whole, ref.experts(f, lp, 6, 8, 3, first_held=0, scaling=16.0), atol=2e-5)
    assert float(counts.sum()) == 6 * 2 * 12          # dropless: every slot
    # no token leaves its three groups
    per_group = np.asarray(counts).reshape(8, 4).sum(axis=1)
    assert per_group.sum() == 6 * 24 and (per_group > 0).all()


def _brute_force(p, k, n_group, topk_group):
    """The selection by enumeration: every set of ``topk_group`` groups, the
    one whose sorted (score, -index) keys are lexicographically largest; then
    every expert inside it ranked by (score, -index)."""
    per = len(p) // n_group
    best = [max(p[g * per:(g + 1) * per]) for g in range(n_group)]
    key = lambda score, i: (score, -i)
    groups = max(itertools.combinations(range(n_group), topk_group),
                 key=lambda gs: sorted((key(best[g], g) for g in gs), reverse=True))
    inside = [e for g in groups for e in range(g * per, (g + 1) * per)]
    return sorted(inside, key=lambda e: key(p[e], e), reverse=True)[:k]


def test_group_limited_selection_matches_an_enumeration_ties_to_the_lower_index():
    """Scores on a coarse grid, so that groups tie with groups and experts
    with experts in most tokens: the router's choice is the enumeration's, in
    order, and so is the reference's set; gates are 16 x the chosen scores."""
    rs = np.random.RandomState(5)
    logits = rs.randint(0, 3, (40, 32)).astype(np.float32)
    logits[0] = 0.0                                    # every score ties
    sel, gate = deepseek_v2.route_grouped(
        jnp.asarray(logits), jnp.eye(32, dtype=jnp.float32), 6, 8, 3, 16.0)
    p = np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1))
    ties = 0
    for t in range(40):
        want = _brute_force(list(p[t]), 6, 8, 3)
        assert list(np.asarray(sel[t])) == want, (t, sel[t], want)
        ties += len(set(p[t][want])) < 6
    assert ties > 30 and list(np.asarray(sel[0])) == [0, 1, 2, 3, 4, 5]
    np.testing.assert_allclose(gate, 16.0 * np.take_along_axis(p, np.asarray(sel), 1),
                               rtol=1e-6)
    weights = reference.resolve(REF_NAME).gate_weights(
        jnp.asarray(logits), jnp.eye(32, dtype=jnp.float32), 6, 8, 3, 16.0)
    for t in range(40):
        assert sorted(np.flatnonzero(np.asarray(weights[t]))) == sorted(
            np.asarray(sel[t])), t


def test_counter_total_is_held_plus_absent_slots(case):
    """The model counts the tokens routed to every expert of a layer, held or
    not: the total is 6 x tokens x expert layers exactly, and the held
    experts' part is the number of rows the grouped product runs."""
    model, params, x = case
    _, aux = model.apply({"params": params}, x, train=True,
                         mutable=[scopes.COUNTERS])
    tokens = np.asarray(aux[scopes.COUNTERS][scopes.MOE_EXPERT_TOKENS])
    assert tokens.shape == (2, 32) == model.counters[scopes.MOE_EXPERT_TOKENS]
    assert model.expert_layers == (1, 2) and model.held_experts == (4, 4)
    held, absent = tokens[:, 4:8].sum(), tokens.sum() - tokens[:, 4:8].sum()
    assert held + absent == 6 * x.size * 2
    np.testing.assert_array_equal(tokens.sum(axis=1), 6.0 * x.size)
    assert 0 < held < absent
    # the rows of the grouped product of layer 1 are its held slots
    sel = np.repeat(np.arange(32), tokens[0].astype(int)).reshape(-1, 6)
    _, sizes, valid = lfm2_moe._slots(jnp.asarray(sel), 4, 4)
    assert int(sizes.sum()) == int(valid.sum()) == int(tokens[0, 4:8].sum())


def test_base_is_stored_in_bfloat16_and_only_the_adapters_train(case):
    model, _, x = case
    v = model.init(jax.random.PRNGKey(0), jnp.asarray(x), train=False)["params"]
    trained, frozen = ClientTrainer(model, has_time_axis=True).split_frozen(v)
    assert set(trained) == {"lora"} and "lora" not in frozen
    assert {a.dtype for a in jax.tree.leaves(frozen)} == {jnp.dtype(jnp.bfloat16)}
    assert {a.dtype for a in jax.tree.leaves(trained)} == {jnp.dtype(jnp.float32)}
    assert set(trained["lora"]["layer_1"]) == {
        m + s for m in ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo") for s in ("_a", "_b")}
    assert v["layer_1"]["w1"].shape == (4, 64, 32)        # the held group
    assert v["layer_1"]["router"].shape == (64, 32)       # scores all experts
    assert v["layer_1"]["s1"].shape == (64, 2 * 32) and "s1" not in v["layer_0"]
    assert v["embed"].shape == (128, 64) and v["head"].shape == (64, 128)


# -- through the mesh engine's normal round ---------------------------------

def _engine(chunk=2):
    from fedbench.harness import build
    config = {"model": {"factory": "fedml_tpu.models.create_model",
                        "name": "deepseek_v2", "kwargs": SMALL},
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
    and the round's counter is 6 x tokens x expert layers."""
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
    # 3 rounds x 4 clients x 2 steps x 16 tokens x 6 a token x 2 expert layers
    tokens = engine.transfer_stats.program_counters()[scopes.MOE_EXPERT_TOKENS]
    assert tokens.shape == (2, 32) and tokens.sum() == 3 * 4 * 2 * 16 * 6 * 2
    assert 0 < tokens[:, 4:8].sum() < tokens.sum() / 2


def test_round_has_no_branch_on_the_model_and_folds_the_adapters_alone():
    """`create_model("deepseek_v2")` goes through the split the trainer reads
    off the model (`trainable`), like `lfm2_moe`: the carry is as long as the
    adapters."""
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
            assert "deepseek" not in f.read().lower()


# -- the rotary of the queries' 64-wide part (ops/rotary.py, PR 46) ----------

# SMALL at the published rotary width: two heads of 64 fill a row of lanes
LANE_ROPE = dict(SMALL, n_heads=2, rope_dim=64, rope_original=128)


IN_ALL_THREE_PASSES = {("mla_latent", phase) for phase in (
    scopes.FORWARD, scopes.RECOMPUTE, scopes.BACKWARD)}


def _lane_rope(name="deepseek_v2", kwargs=LANE_ROPE, T=128):
    """(model, params as initialised, tokens [1, T]): T a multiple of 128, so
    `rotate_half` takes the queries' rotary part."""
    model = create_model(name, 128, **kwargs)
    x = jax.random.randint(jax.random.PRNGKey(1), (1, T), 0, 128)
    return model, model.init(jax.random.PRNGKey(0), x)["params"], x


def rotary_kernels_by_label_and_phase(model, params, x):
    """{(label, phase)} of the `rotate_half` kernels in the gradient of a
    bfloat16 model lowered for a TPU (`tests/test_xing4.py` shares it)."""
    import re
    params = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.bfloat16), params)

    def loss(lora):
        with jax.named_scope(scopes.FED_FORWARD):
            return jnp.sum(model.apply({"params": {**params, "lora": lora}}, x))

    text = jax.jit(jax.grad(loss)).trace(params["lora"]).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    names = [m.group(1) for m in re.finditer(r'loc\("([^"]*pallas_call[^"]*)"', text)]
    return {(scopes.label_of(n), scopes.phase_of(n)) for n in names
            if "rotate_half" in n}


def rotary_paths_of_one_trace(model, params, x):
    """(pallas, reference) calls of `rotate_half` counted by one forward trace."""
    from fedml_tpu import obs
    paths = lambda: [obs.counter("ops_kernel_path_total", op="rotate_half",
                                 path=path).value for path in ("pallas", "reference")]
    before = paths()
    jax.make_jaxpr(lambda p: model.apply({"params": p}, x))(params)
    return tuple(b - a for a, b in zip(before, paths()))


def test_the_rotary_of_q_rope_is_the_kernel_in_all_three_passes():
    """Lowered for a TPU, `latent_attention` at a rotary part of 64 holds the
    `rotate_half` kernel under the latent side's scope - forward, in the
    checkpoint's re-run and backward (its residuals are the tables: nothing
    new is kept) - and a trace counts one `pallas` (q_rope) and one
    `reference` (the one shared key head, under a row of lanes) a layer."""
    model, params, x = _lane_rope()
    assert rotary_kernels_by_label_and_phase(model, params, x) == IN_ALL_THREE_PASSES
    assert rotary_paths_of_one_trace(model, params, x) == (3, 3)
    assert deepseek_v2.KEPT_NAMES == (
        "attn_out", "causal_attention_o", "causal_attention_lse")


def assert_the_cpu_model_is_the_parents_to_the_bit(monkeypatch, model, params, x):
    """Logits and adapter gradients (parameters moved off their initial
    values) with `rotate_half` in `latent_attention`, and with the parent's
    `apply_rotary` in its place (`tests/test_xing4.py` shares it)."""
    from fedml_tpu.ops.rotary import apply_rotary
    rs = np.random.RandomState(0)
    params = jax.tree.map(
        lambda a: a + 0.1 * rs.randn(*a.shape).astype(a.dtype), params)

    def loss(lora):
        logits = model.apply({"params": {**params, "lora": lora}}, x)
        return jnp.mean(jnp.square(logits)), logits

    # a new function each time: the second trace sees the parent's call
    both = lambda: jax.jit(jax.value_and_grad(loss, has_aux=True))(params["lora"])
    got = both()
    monkeypatch.setattr(deepseek_v2, "rotate_half", apply_rotary)
    want = both()
    assert float(jnp.abs(want[0][1]).max()) > 0.1
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b)


def test_on_the_cpu_the_model_is_the_parents_to_the_bit(monkeypatch):
    """A CPU program lowers `rotate_half` to its plain body: logits and
    adapter gradients are what the parent's call of `apply_rotary` gives, bit
    for bit, at the width the kernel takes on a chip."""
    assert_the_cpu_model_is_the_parents_to_the_bit(monkeypatch, *_lane_rope())


@pytest.mark.parametrize("name, kwargs, T", [
    ("cohere2_moe", dict(d_model=64, n_heads=4, n_kv_heads=2, head_dim=128,
                         d_expert=32, n_experts=8, experts_per_token=2,
                         n_shared=1, sliding_window=128, lora_rank=2), 256),
    ("lfm2_moe", dict(lora_rank=2), 128),
    ("looped_lm", {}, 128)])
def test_the_other_models_lower_to_the_text_they_lowered_to(monkeypatch, name,
                                                            kwargs, T):
    """The narrow form is not on the path of `cmdaplus` (heads of 128: the wide
    form, whose jaxpr `tests/test_rotary_op.py` pins), `lfm2moe24b` or
    `ouro2p6b` (both call `apply_rotary`): their loss and gradients lowered
    for a TPU are the same text under the parent's shape rule, with the narrow
    form taken away."""
    from fedml_tpu.ops import rotary
    model = create_model(name, output_dim=50, **kwargs)
    x = jax.random.randint(jax.random.PRNGKey(1), (1, T), 0, 50)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16), jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), x))["params"])

    def lowered():
        def loss(params):
            return jnp.mean(jnp.square(model.apply({"params": params}, x)))
        return jax.jit(jax.value_and_grad(loss)).trace(params).lower(
            lowering_platforms=("tpu",)).as_text()

    texts = []
    for parents_rule in (False, True):     # one call site: a kernel's body
        if parents_rule:                   # holds the lines it was traced from
            monkeypatch.setattr(rotary, "_lanes_fit", lambda H, hd: hd % 128 == 0)
            monkeypatch.delattr(rotary, "_narrow_kernel")
        texts.append(lowered())
    assert ("rotate_half" in texts[0]) == (name == "cohere2_moe")
    assert texts[0] == texts[1]
