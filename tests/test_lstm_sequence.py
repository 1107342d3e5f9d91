"""`models/rnn.py::lstm_sequence` — the LSTM layer with a hand-written VJP
that takes every product with a loop-invariant kernel once per sequence
batch, outside the time loop — against `nn.RNN(nn.OptimizedLSTMCell)`,
which lives on here as the oracle (it was the layer until PR 27):

* float32: outputs and every gradient (the twelve leaves, dx, a non-zero
  initial carry's) to 1e-5 of the oracle's largest entry, at both models'
  shapes (widths cut for the CPU), T = 1, `last_only`, under `vmap` over a
  client axis and through the mesh engine's chunk scan;
* bf16 parameters: gradients no further from the float32 oracle than the
  oracle's own bf16 gradients are;
* the parameter tree: same paths, shapes, dtypes and VALUES for a key;
* the mechanism: the reverse scan of `jax.grad` carries state only, and
  the kernel-shaped products sit outside it.
"""
import collections
import contextlib
from unittest import mock

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.models import create_model, rnn
from fedml_tpu.parallel import MeshFedAvgEngine
from fedml_tpu.parallel.mesh import make_mesh

from parallel_case import _token_setup

# model, its widths cut for the CPU, sequence length, batch
SHAPES = {
    "stackoverflow": ("rnn_stackoverflow",
                      dict(vocab_size=53, embedding_dim=24, hidden_size=67),
                      20, 16),
    "shakespeare": ("rnn", dict(vocab_size=31, embedding_dim=8,
                                hidden_size=48), 80, 4),
    "shakespeare_last": ("rnn", dict(vocab_size=31, embedding_dim=8,
                                     hidden_size=48, last_only=True), 80, 4),
}


def _oracle_lstm(hidden_size, h):
    """`models/rnn.py::_lstm` as it was: flax's scan over flax's cell."""
    cell = nn.OptimizedLSTMCell(hidden_size)
    carry = cell.initialize_carry(jax.random.PRNGKey(0),
                                  h.shape[:-2] + h.shape[-1:])
    bump = jnp.sum(h * 0)
    carry = jax.tree.map(lambda a: a + bump.astype(a.dtype), carry)
    return nn.RNN(cell)(h, initial_carry=carry)


def _oracle():
    """Inside: the repo's two LSTM models are the parent's."""
    return mock.patch.object(rnn, "_lstm", _oracle_lstm)


class OracleLayer(nn.Module):
    hidden_size: int

    @nn.compact
    def __call__(self, x, carry):
        return nn.RNN(nn.OptimizedLSTMCell(self.hidden_size))(
            x, initial_carry=carry)


def _close(got, want, rel=1e-5):
    """Every leaf within `rel` of the largest entry of the oracle's."""
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        assert np.abs(a - b).max() <= rel * np.abs(b).max() + 1e-30, (
            np.abs(a - b).max(), np.abs(b).max())


def _model(case):
    name, kw, T, B = SHAPES[case]
    kw = dict(kw)
    model = create_model(name, kw.pop("vocab_size"), **kw)
    x = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0,
                           model.vocab_size)
    return model, x


def _loss(model, x):
    y = jax.random.randint(jax.random.PRNGKey(2), x.shape, 0,
                           model.vocab_size)

    def loss(params, x=x, y=y):
        logits = model.apply({"params": params}, x).astype(jnp.float32)
        y_ = y[..., -1] if logits.ndim == 2 else y
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, y_[..., None], -1))
    return loss


# -- the layer itself ---------------------------------------------------------

@pytest.mark.parametrize("T", [1, 20])
@pytest.mark.parametrize("E,H", [(24, 67), (8, 48)])
def test_sequence_is_flax_scan_over_the_cell(E, H, T):
    """Outputs and all fifteen gradients (twelve leaves, dx, dc0, dh0),
    from a carry that is not zero, float32."""
    B = 5
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (B, T, E))
    carry = (jax.random.normal(ks[1], (B, H)),
             jnp.tanh(jax.random.normal(ks[2], (B, H))))
    oracle = OracleLayer(H)
    params = oracle.init(ks[3], x, carry)["params"]
    # biases start at zero: move them, or db is tested against no value
    params = jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(ks[4], a.shape), params)
    weight = jnp.cos(jnp.arange(B * T * H, dtype=jnp.float32)
                     ).reshape(B, T, H)

    def want(params, x, carry):
        return jnp.sum(weight * oracle.apply({"params": params}, x, carry))

    def got(params, x, carry):
        cell = params["OptimizedLSTMCell_0"]
        return jnp.sum(weight * rnn.lstm_sequence(
            *rnn.fused_kernels(cell), x, carry))

    cell = params["OptimizedLSTMCell_0"]
    hs = rnn.lstm_sequence(*rnn.fused_kernels(cell), x, carry)
    _close(hs, oracle.apply({"params": params}, x, carry), rel=1e-6)
    grads = jax.grad(got, argnums=(0, 1, 2))(params, x, carry)
    assert len(jax.tree.leaves(grads)) == 15
    _close(grads, jax.grad(want, argnums=(0, 1, 2))(params, x, carry))


# -- the two models -----------------------------------------------------------

@pytest.mark.parametrize("name,T", [("rnn_stackoverflow", 20), ("rnn", 80)])
def test_init_returns_the_parents_tree_and_values(name, T):
    """Published widths through the factory: paths, shapes, dtypes and
    values of `init` for a key are the parent's modules'."""
    model = create_model(name, 10004 if name == "rnn_stackoverflow" else 90)
    x = jnp.zeros((2, T), jnp.int32)
    got = model.init(jax.random.PRNGKey(7), x, train=False)
    with _oracle():
        want = model.init(jax.random.PRNGKey(7), x, train=False)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    cells = [k for k in got["params"] if k.startswith("OptimizedLSTMCell_")]
    assert len(cells) == (1 if name == "rnn_stackoverflow" else 2)
    for k in cells:
        assert sorted(got["params"][k]) == [
            "hf", "hg", "hi", "ho", "if", "ig", "ii", "io"]
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("case", list(SHAPES))
def test_model_float32_matches_the_oracle(case):
    model, x = _model(case)
    params = model.init(jax.random.PRNGKey(0), x)["params"]
    loss = _loss(model, x)
    logits = model.apply({"params": params}, x)
    value, grads = jax.value_and_grad(loss)(params)
    with _oracle():
        _close(logits, model.apply({"params": params}, x), rel=1e-6)
        want_value, want = jax.value_and_grad(loss)(params)
    np.testing.assert_allclose(value, want_value, rtol=1e-6)
    _close(grads, want)


@pytest.mark.parametrize("case", ["stackoverflow", "shakespeare"])
def test_bf16_gradients_are_no_further_from_float32_than_the_parents(case):
    """bf16 parameters (the benchmark's local masters): the parent rounds
    each step's kernel gradient to bf16 and adds the steps in bf16; the
    sequence function sums T*B rows in float32 and rounds once.  Distance
    to the float32 oracle, over the LSTM's leaves: new <= parent x 1.05."""
    model, x = _model(case)
    params = model.init(jax.random.PRNGKey(0), x)["params"]
    half = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    loss = _loss(model, x)
    got = jax.grad(loss)(half)
    with _oracle():
        parent = jax.grad(loss)(half)
        exact = jax.grad(loss)(jax.tree.map(
            lambda a: a.astype(jnp.float32), half))
    for a in jax.tree.leaves(got):
        assert a.dtype == jnp.bfloat16

    def distance(grads):
        num = den = 0.0
        for k in grads:
            if k.startswith("OptimizedLSTMCell_"):
                for g, e in zip(jax.tree.leaves(grads[k]),
                                jax.tree.leaves(exact[k])):
                    num += float(jnp.sum((g.astype(jnp.float32) - e) ** 2))
                    den += float(jnp.sum(e ** 2))
        return (num / den) ** 0.5

    new, old = distance(got), distance(parent)
    assert new <= 1.05 * old, (new, old)


def test_under_vmap_over_a_client_axis():
    """Per-client parameters and tokens, as the chunk's vmap hands them."""
    model, x = _model("stackoverflow")
    C = 3
    xs = jax.random.randint(jax.random.PRNGKey(3), (C,) + x.shape, 0,
                            model.vocab_size)
    params = jax.vmap(lambda k: model.init(k, x)["params"])(
        jax.random.split(jax.random.PRNGKey(4), C))
    loss = _loss(model, x)

    def per_client(params, xs):
        return jax.vmap(jax.value_and_grad(
            lambda p, x_: loss(p, x=x_)))(params, xs)
    value, grads = per_client(params, xs)
    with _oracle():
        want_value, want = per_client(params, xs)
    np.testing.assert_allclose(value, want_value, rtol=1e-6)
    _close(grads, want)


@pytest.mark.parametrize("dataset,name,kw,time_axis", [
    ("stackoverflow_nwp", "rnn_stackoverflow",
     dict(embedding_dim=12, hidden_size=24), True),
    ("shakespeare", "rnn", dict(hidden_size=24, last_only=True), False),
])
def test_through_the_mesh_engines_chunk_scan(dataset, name, kw, time_axis):
    """One resident round on two shards, chunk 1 (two trips of the chunk
    scan, the vmap over a chunk's clients, shard_map): the committed
    parameters are the oracle's to 1e-5 of the largest."""
    trainer, data, cfg = _token_setup(dataset, name, kw, time_axis)

    def committed(patch):
        with patch:                     # the round is traced inside
            eng = MeshFedAvgEngine(trainer, data, cfg, mesh=make_mesh(2),
                                   chunk=1, donate=False)
            return jax.block_until_ready(eng.run(rounds=1))
    got, want = committed(contextlib.nullcontext()), committed(_oracle())
    _close(got, want)


# -- the mechanism ------------------------------------------------------------

def _eqns(jaxpr, in_scan=False):
    """(equation, whether a scan encloses it) over a jaxpr and all it
    calls."""
    for eqn in jaxpr.eqns:
        yield eqn, in_scan
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(
                        sub, in_scan or eqn.primitive.name == "scan")


def _reverse_scan_carries_and_products(case, oracle: bool):
    model, x = _model(case)
    params = model.init(jax.random.PRNGKey(0), x)["params"]
    with (_oracle() if oracle else contextlib.nullcontext()):
        jaxpr = jax.make_jaxpr(jax.grad(_loss(model, x)))(params).jaxpr
    carries, products = [], []
    for eqn, in_scan in _eqns(jaxpr):
        if eqn.primitive.name == "scan" and eqn.params["reverse"]:
            n_consts, n_carry = (eqn.params["num_consts"],
                                 eqn.params["num_carry"])
            carries += [v.aval.shape
                        for v in eqn.invars[n_consts:n_consts + n_carry]]
        if eqn.primitive.name == "dot_general":
            products.append((eqn.outvars[0].aval.shape, in_scan))
    return carries, products


@pytest.mark.parametrize("case", ["stackoverflow", "shakespeare"])
def test_reverse_scan_carries_state_only(case):
    """In `jax.grad` of the model's loss the backward time loop carries
    (dc, dh) — no array of a kernel's or a bias's shape — and the `[E, 4H]`
    / `[H, 4H]` weight-gradient products appear once a layer, outside every
    scan.  The oracle, the parent's layer, fails both: that is what moved."""
    _, kw, _, B = SHAPES[case]
    H = kw["hidden_size"]
    layers = [kw["embedding_dim"]] + ([H] if case == "shakespeare" else [])
    carries, products = _reverse_scan_carries_and_products(case, False)
    assert len(carries) == 2 * len(layers)
    assert all(shape == (B, H) for shape in carries), carries
    expected = collections.Counter(
        [(E, 4 * H) for E in layers] + [(H, 4 * H) for _ in layers])
    for shape, n in expected.items():
        assert products.count((shape, False)) == n, (shape, products)
    kernel_shapes = set()               # either way round
    for E in layers + [H]:
        for n in (1, 4):
            kernel_shapes |= {(E, n * H), (n * H, E)}
    assert not [p for p in products if p[1] and p[0] in kernel_shapes]

    carries, products = _reverse_scan_carries_and_products(case, True)
    assert [shape for shape in carries if shape[-2:] == (H, H)]
    assert [p for p in products if p[1] and p[0] in kernel_shapes]
