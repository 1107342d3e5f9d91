"""`ops/rotary.py::rotate_half` — the fused pass (Pallas interpret mode,
small aligned shapes) against the plain body `apply_rotary`, its hand-written
transpose against autodiff of the plain body, under the transformations the
engine applies to it, and the rule that picks a path.

What only a chip's compiler can show — Mosaic accepting the blocks and the
lane roll at Command A+'s shapes and at latent attention's 64-wide query
parts — is `tests/test_tpu_compile.py::test_rotate_half_compiles`."""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from fedml_tpu import obs
from fedml_tpu.models.looped_lm import rotary_tables
from fedml_tpu.ops import rotary
from fedml_tpu.ops.rotary import apply_rotary, rotate_half

THETA = 5e4
# (B, T, H, hd): several heads a block, every head of a wide operand in one
# block, and a head of two lane tiles (the roll crosses a tile); then the
# narrow form (two heads of 64 to a row of 128 lanes): two rows of lanes a
# position, sixteen (a wide operand), and one
SHAPES = [(1, 256, 4, 128), (2, 128, 16, 128), (1, 256, 1, 256),
          (1, 256, 4, 64), (2, 128, 32, 64), (1, 256, 2, 64)]
DTYPES = [pytest.param(jnp.bfloat16, id="bf16"), pytest.param(jnp.float32, id="f32")]


def _fused(x, cos, sin):
    return rotary._rotate(x, cos, sin, True)


def _operands(shape, dtype, seed=0):
    """x ~ N(0, 1) in ``dtype``, float32 weights of a loss, and the tables."""
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, shape, jnp.float32).astype(dtype)
    return x, jax.random.normal(kw, shape, jnp.float32), *rotary_tables(
        shape[1], shape[-1], THETA)


def _assert_same(got, want):
    """float32: 1e-6 absolute on values of order 1 (the two paths may contract
    ``a * b + c`` differently).  bfloat16: one rounding of such a float32
    value on both paths - at most one unit of the last place (2^-7 of the
    value's power of two) apart, or 1e-6 where the two terms cancel."""
    assert got.dtype == want.dtype and got.shape == want.shape
    a, b = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if want.dtype == jnp.bfloat16:
        ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(b), 1e-30))) - 7)
        assert np.all(np.abs(a - b) <= np.maximum(ulp, 1e-6))
    else:
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=0)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_fused_matches_plain(shape, dtype):
    x, _, cos, sin = _operands(shape, dtype)
    _assert_same(jax.jit(_fused)(x, cos, sin), apply_rotary(x, cos, sin))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_the_transpose_rule_matches_autodiff_of_the_plain_body(shape, dtype):
    """The `custom_vjp`'s gradient (the same pass with the signed sine rolled
    by half a head) against what jax derives from `apply_rotary`."""
    x, w, cos, sin = _operands(shape, dtype)
    grad = lambda fn: jax.jit(jax.grad(lambda x: jnp.sum(
        fn(x, cos, sin).astype(jnp.float32) * w)))(x)
    _assert_same(grad(_fused), grad(apply_rotary))


@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_the_rotation_then_its_transpose_returns_the_input(shape):
    """A rotation is orthogonal: turning by theta and then applying the
    backward rule (the turn by -theta) gives x back, to float32 rounding."""
    x, _, cos, sin = _operands(shape, jnp.float32)
    y, transpose = jax.vjp(lambda x: _fused(x, cos, sin), x)
    assert float(jnp.max(jnp.abs(y - x))) > 0.1
    np.testing.assert_allclose(transpose(y)[0], x, atol=2e-6, rtol=0)


@pytest.mark.parametrize("shape", [SHAPES[0], SHAPES[3]], ids=str)
def test_no_activation_is_kept_for_the_backward_pass(shape):
    """The residuals are the two tables alone."""
    x, _, cos, sin = _operands(shape, jnp.bfloat16)
    _, transpose = jax.vjp(lambda x: _fused(x, cos, sin), x)
    kept = [a.shape for a in jax.tree.leaves(transpose)
            if hasattr(a, "shape") and a.ndim]
    assert kept and all(s == cos.shape for s in kept), kept


@pytest.fixture(scope="module", params=[128, 64], ids=["hd128", "hd64"])
def clients(request):
    """Two clients' operands [C, B, T, H, hd], the tables they share, and
    the plain body's output and gradient; heads as wide as the lanes, and
    the narrow form's."""
    hd = request.param
    x, w = _operands((2, 1, 256, 4, hd), jnp.float32)[:2]
    cos, sin = rotary_tables(256, hd, THETA)

    def both(fn):
        def one(x, w):
            loss = lambda x: jnp.sum(jnp.sin(fn(x, cos, sin)) * w)
            return fn(x, cos, sin), jax.grad(loss)(x)
        return one

    return (x, w), both, jax.jit(jax.vmap(both(apply_rotary)))(x, w)


def _close(got, want):
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-6, rtol=0)


@pytest.mark.parametrize("wrap", [lambda f: f, jax.checkpoint],
                         ids=["bare", "checkpoint"])
def test_under_vmap_over_clients(clients, wrap):
    """A chunk's `vmap` over clients, the tables un-mapped, with and without
    the layer's `jax.checkpoint`."""
    args, both, want = clients
    _close(jax.jit(jax.vmap(both(wrap(_fused))))(*args), want)


def test_under_shard_map_with_check_vma(clients):
    """The engine's `shard_map(check_vma=True)` over a vmap over clients on a
    two-device mesh: the kernel's result says over which axes it varies, and
    the public function lowers for a TPU with both passes in it."""
    args, both, want = clients
    mesh = Mesh(np.array(jax.devices()[:2]), ("clients",))
    spec = (P("clients"),) * 2

    def sharded(fn):
        return jax.jit(jax.shard_map(jax.vmap(both(fn)), mesh=mesh,
                                     in_specs=spec, out_specs=spec))

    interpreted = lambda x, cos, sin: rotary._rotate(
        x, cos, sin, pltpu.InterpretParams())
    _close(sharded(interpreted)(*args), want)
    engine_like = sharded(jax.checkpoint(rotate_half))
    for a, b in zip(engine_like(*args), want):          # CPU: the plain path
        np.testing.assert_array_equal(a, b)
    lowered = engine_like.trace(*args).lower(lowering_platforms=("tpu",))
    assert lowered.as_text().count("tpu_custom_call") >= 2


def _paths():
    return {path: obs.counter("ops_kernel_path_total", op="rotate_half",
                              path=path).value
            for path in ("pallas", "reference")}


@pytest.mark.parametrize("shape, dtype", [
    ((1, 256, 1, 64), jnp.bfloat16),              # latent attention's shared key:
                                                  # H * hd under a row of lanes
    ((1, 256, 4, 96), jnp.bfloat16),              # a head that does not divide 128
    ((1, 200, 4, 128), jnp.bfloat16),             # T not a multiple of 128
    ((1, 256, 4, 128), jnp.float16),              # a dtype of neither kind
], ids=["H1hd64", "hd96", "T200", "f16"])
def test_a_shape_that_does_not_fit_takes_the_plain_body(shape, dtype):
    x, _, cos, sin = _operands(shape, dtype)
    before = _paths()
    jaxpr = jax.make_jaxpr(rotate_half)(x, cos, sin)
    after = _paths()
    assert after["reference"] == before["reference"] + 1
    assert after["pallas"] == before["pallas"]
    assert str(jaxpr) == str(jax.make_jaxpr(apply_rotary)(x, cos, sin))


def test_a_shape_that_fits_is_counted_and_its_cpu_lowering_is_the_plain_body():
    x, w, cos, sin = _operands(SHAPES[0], jnp.bfloat16)
    before = _paths()
    lowered = jax.jit(rotate_half).lower(x, cos, sin)
    after = _paths()
    assert after["pallas"] == before["pallas"] + 1
    assert after["reference"] == before["reference"]
    assert "custom_call" not in lowered.as_text()
    out_and_grad = lambda fn: jax.jit(jax.value_and_grad(lambda x: jnp.sum(
        fn(x, cos, sin).astype(jnp.float32) * w)))(x)
    for a, b in zip(out_and_grad(rotate_half), out_and_grad(apply_rotary)):
        np.testing.assert_array_equal(a, b)
    # the same trace lowered for a TPU holds the kernel
    tpu = jax.jit(rotate_half).trace(x, cos, sin).lower(
        lowering_platforms=("tpu",))
    assert "tpu_custom_call" in tpu.as_text()


def test_the_wide_form_traces_to_the_program_it_was():
    """Heads as wide as the lanes keep the program they had before the narrow
    form came (PR 46): `jax.make_jaxpr(rotate_half)` on bfloat16
    (1, 256, 4, 128) prints what it printed at the parent `dfda071`, equation
    for equation, the kernel's body included (`cmdaplus.lora4of256long` runs
    it) - `tests/data/rotate_half_wide.jaxpr.txt`, addresses and source
    lines taken out."""
    x, _, cos, sin = _operands(SHAPES[0], jnp.bfloat16)
    text = re.sub(r" at 0x[0-9a-f]+", "", str(jax.make_jaxpr(rotate_half)(x, cos, sin)))
    text = re.sub(r"[\w/.\-]+\.py:\d+", "", text)
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "rotate_half_wide.jaxpr.txt")) as f:
        assert text == f.read()
