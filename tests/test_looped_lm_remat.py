"""What `models/looped_lm.py`'s per-layer `jax.checkpoint` keeps (ISSUE 38).

Where the residual stream is 16 bits wide a layer application keeps its
input and the values `decoder_layer` and `ops/attention.py` name — the two
projections back to the stream and the attention rule's `(o, lse)` — and
the backward pass re-runs the norms, the q, k, v, gate and up products,
the rotary, `silu * up` and the residual adds: not the other two products
and not the attention forward.  A float32 stream (the twin the benchmark's
reference check runs) keeps a layer's input alone, as every stream did
before.  Tiny widths on the CPU; T = 128 and heads of 64 are a shape the
fused attention takes, so the rule with the two names is the one that is
traced (the platform switch inside it runs the plain body here).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals

from fedml_tpu import obs
from fedml_tpu.models import create_model, looped_lm
from fedml_tpu.ops import attention

B, T, VOCAB = 2, 128, 64
WIDTHS = dict(d_model=32, n_heads=2, head_dim=64, d_ff=48, n_layers=3,
              n_passes=2)
D, H, HD, F, LAYERS, PASSES = WIDTHS.values()
DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def _case(dtype, **kw):
    """(loss(params), params in `dtype`) of the tiny model."""
    model = create_model("looped_lm", output_dim=VOCAB, **{**WIDTHS, **kw})
    x = jnp.asarray(np.random.RandomState(0).randint(0, VOCAB, (B, T)))
    params = model.init(jax.random.PRNGKey(0), x)["params"]
    rs = np.random.RandomState(1)               # norms away from 1
    params = jax.tree.map(lambda a: (a + 0.05 * rs.randn(*a.shape)).astype(
        dtype), params)

    def loss(p):
        return jnp.mean(jnp.square(model.apply({"params": p}, x)))

    return loss, params


def _stacks(loss, params, lead):
    """{(per-application shape, dtype name): how many} of the residuals
    that a scan stacked behind the leading dimensions `lead`."""
    found = {}
    for aval, why in saved_residuals(loss, params):
        if aval.shape[:len(lead)] == lead and "from the argument" not in why:
            key = (aval.shape[len(lead):], aval.dtype.name)
            found[key] = found.get(key, 0) + 1
    return found


def _rematted(loss, *args, visit=None):
    """Primitive names of the gradient's equations that `jax.checkpoint`
    runs again inside the backward pass, with everything beneath them (a
    sub-jaxpr's name stacks are relative to its equation's), and the names
    `checkpoint_name` gave values there; `visit` sees each such equation."""
    again, names = [], set()

    def walk(jaxpr, inside):
        for e in jaxpr.eqns:
            rerun = inside or "rematted_computation" in str(
                e.source_info.name_stack)
            if rerun:
                again.append(e.primitive.name)
                if visit is not None:
                    visit(e)
                names.update({e.params["name"]} if e.primitive.name == "name"
                             else ())
            for v in e.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub, rerun)

    walk(jax.make_jaxpr(jax.grad(loss))(*args).jaxpr, False)
    return again, names


def test_a_16_bit_stream_keeps_the_layer_inputs_and_the_named_values():
    """(a) The scans stack, one slice an application, the layer inputs and
    the named set, each in the dtype the backward pass reads it in today,
    and nothing else of layer size; of the seven matrix products the re-run
    holds five (q, k, v, gate, up), and no platform switch and no kernel."""
    loss, params = _case(jnp.bfloat16)
    assert _stacks(loss, params, (PASSES, LAYERS)) == {
        ((B, T, H, HD), "bfloat16"): 1,                    # o
        ((B, H, T), "float32"): 1,                         # lse
        ((B, T, D), "bfloat16"): 3}                        # h, o W_o, g W_down
    again, _ = _rematted(loss, params)
    assert again.count("dot_general") == 5, again
    assert not {"cond", "platform_index", "pallas_call",
                "custom_vjp_call"} & set(again), again
    assert {"rsqrt", "mul", "add", "logistic"} <= set(again)   # norms, silu


def test_a_float32_stream_keeps_the_layer_inputs_alone():
    """(c) The program of before — the one the reference check's twin
    compiles to: the two scans stack one residual, the stream, and the
    whole layer, products and attention rule, is under the re-run."""
    loss, params = _case(jnp.float32)
    assert _stacks(loss, params, (PASSES, LAYERS)) == {((B, T, D), "float32"): 1}
    again, _ = _rematted(loss, params)
    assert again.count("dot_general") >= 7 and "pallas_call" in again
    # one layer body in the program, whatever the depth
    assert jax.jit(loss).lower(params).as_text().count("stablehlo.while") == 2


@pytest.mark.parametrize("other", ["input_only", "unrolled"])
@pytest.mark.parametrize("dtype", DTYPES)
def test_gradients_are_the_other_programs_to_the_bit(monkeypatch, dtype, other):
    """(b) What is kept changes nothing that is computed: same ops, same
    dtypes, same order within a layer.  Loss and every parameter's gradient
    equal those of a checkpoint that keeps no name (the save-nothing
    checkpoint of before) and of the plain loops (`unrolled=True`, no
    checkpoint at all) to the bit — run op by op (`jax.disable_jit`), so
    that each primitive is its own program: under `jit` XLA:CPU fuses the
    three programs differently, float32 sums reassociate (the float32
    losses differ by one ulp) and in bfloat16 the flips cascade through
    the applications."""
    loss, params = _case(DTYPES[dtype])
    with jax.disable_jit():
        got = jax.value_and_grad(loss)(params)
        if other == "input_only":
            monkeypatch.setattr(looped_lm, "_KEEP", None)
        else:
            loss, _ = _case(DTYPES[dtype], unrolled=True)
        want = jax.value_and_grad(loss)(params)
    assert float(got[0]) > 0
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_leaves_with_path(got)]
    for path, a, b in zip(paths, jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32), err_msg=path)
    moved = [p for p, g in zip(paths, jax.tree.leaves(got)) if np.any(g != 0)]
    assert len(moved) == len(paths) - 2, moved      # all but the exit gate's two


@pytest.mark.parametrize("dtype, saved", [("bfloat16", "products"),
                                          ("float32", "input_only")])
def test_the_choice_and_the_bytes_kept_are_counted(dtype, saved):
    """(e) `remat_policy_total{model, saved}` ticks once a trace, and
    `remat_saved_bytes` is what a step's stacked residuals hold: the sizes
    `saved_residuals` lists, summed."""
    loss, params = _case(DTYPES[dtype])
    obs.reset()
    count = {s: obs.counter("remat_policy_total", model="looped_lm", saved=s)
             for s in ("products", "input_only")}
    jax.make_jaxpr(loss)(params)
    assert {s: c.value for s, c in count.items() if c.value} == {saved: 1}
    lead = (PASSES, LAYERS)
    held = sum(n * int(np.prod(lead + shape)) * jnp.dtype(dt).itemsize
               for (shape, dt), n in _stacks(loss, params, lead).items())
    assert obs.gauge("remat_saved_bytes", model="looped_lm").value == held


def test_bytes_kept_at_the_published_widths():
    """`ouro2p6b.silo4of256t1024`'s local step (2 x 1,024 tokens, 16 heads
    of 128, 6 layers x 4 passes) keeps 0.61 GB of named values in bfloat16;
    the float32 twin of the reference check would keep 1.21 GB."""
    stack = lambda dt: 24 * looped_lm.kept_bytes(
        jax.ShapeDtypeStruct((2, 1024, 2048), dt), 16, 128)
    assert stack(jnp.bfloat16) == 607_125_504
    assert stack(jnp.float32) == 1_211_105_280


def test_the_two_names_are_inert_in_lfm2_moe():
    """(d) `_attention_fwd` is `lfm2_moe.gqa_attention`'s rule too, and
    that model's checkpoints pass no policy: its gradient keeps neither
    `o` nor the log-sum-exp by name, and the rule — names inside — still
    runs again in the backward pass."""
    model = create_model("lfm2_moe", VOCAB, d_model=64, n_heads=2,
                         n_kv_heads=1, head_dim=64, d_ff=96, d_expert=32,
                         n_experts=4, experts_per_token=2, layers=[0, 2],
                         num_dense_layers=1, lora_rank=2, lora_alpha=4.0)
    x = jnp.asarray(np.random.RandomState(0).randint(0, VOCAB, (B, T)))
    params = model.init(jax.random.PRNGKey(0), x, train=False)["params"]
    params = {**params, "lora": jax.tree.map(lambda a: a + 0.01, params["lora"])}

    def loss(lora):
        return jnp.mean(jnp.square(model.apply(
            {"params": {**params, "lora": lora}}, x, train=True)))

    why = [w for _, w in saved_residuals(loss, params["lora"])]
    assert why and not [w for w in why if "causal_attention" in w], why
    again, names = _rematted(loss, params["lora"])
    assert set(attention.SAVED_NAMES) <= names, names
    assert "pallas_call" in again


@pytest.mark.parametrize("dtype", DTYPES)
def test_one_round_trains_what_the_plain_loops_train(dtype):
    """One FedAvg round through the mesh engine (four clients on two
    shards, a chunk of two under the `vmap`), computing in `dtype` on
    float32 masters: the committed parameters are the plain loops'
    (`unrolled=True`) to the compute dtype's rounding, on either side of
    the rule — the programs are jitted, so XLA:CPU fuses them differently
    (the bitwise statement is the op-by-op test's)."""
    from fedml_tpu.core import ClientTrainer
    from fedml_tpu.parallel import MeshFedAvgEngine, make_mesh
    from parallel_case import _token_setup
    widths = dict(d_model=64, n_heads=16, head_dim=4, d_ff=96, n_layers=2,
                  n_passes=2)                      # tests/fedbench/tiny

    def committed(**kw):
        trainer, data, cfg = _token_setup("stackoverflow_nwp", "looped_lm",
                                          {**widths, **kw}, True)
        trainer = ClientTrainer(trainer.model, lr=0.1, has_time_axis=True,
                                train_dtype=DTYPES[dtype])
        eng = MeshFedAvgEngine(trainer, data, cfg, mesh=make_mesh(2), chunk=2)
        start = eng.init_variables()
        return start, eng.run(variables=jax.tree.map(jnp.copy, start), rounds=1)

    start, got = committed()
    _, want = committed(unrolled=True)
    moved = 0
    # float32: `ouro_2p6b`'s `check.param_tol` (a norm weight at 1.0 moves
    # by 7e-4 here and one float32 ulp of it is 1.2e-7); bfloat16: a few
    # ulps (2^-8) of the largest update
    tol = {"float32": 1e-3, "bfloat16": 5e-2}[dtype]
    for s, a, b in zip(*map(jax.tree.leaves, (start, got, want))):
        update = float(np.max(np.abs(b - s)))
        assert float(np.max(np.abs(a - b))) <= tol * update
        moved += update > 0
    assert moved == 14                    # every leaf but the exit gate's two
