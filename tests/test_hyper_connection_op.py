"""`ops/hyper_connection.py` — the four fused passes (Pallas interpret mode,
small aligned shapes) against the plain bodies `read_plain` / `write_plain`
and their derivatives by jax, under the transformations the engine and the
model apply to them, and the rule that picks a path.

What only a chip's compiler can show — Mosaic accepting whole rows of four
3,584-wide streams and the 24-wide projection — is
`tests/test_tpu_compile.py::test_hyper_connection_kernels_compile`; the
values on the chip are `chip_smoke.py` phase (c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from fedml_tpu import obs
from fedml_tpu.models import create_model, xing4
from fedml_tpu.ops import hyper_connection as hc

EPS = 1e-6
# (n, C, tokens): four streams and two, one tile of lanes a stream and two
SHAPES = [(4, 128, 64), (4, 256, 128), (2, 128, 64), (2, 256, 64)]
DTYPES = [pytest.param(jnp.bfloat16, id="bf16"), pytest.param(jnp.float32, id="f32")]


def _operands(n, C, tokens, dtype, seed=0, lead=(1,)):
    """Streams ~ N(0, 1), a projection that moves every map, gates and biases
    off their initial values, the maps of a write, and cotangents."""
    rs = np.random.RandomState(seed)
    k = n * (n + 2)
    f32 = lambda *s: jnp.asarray(rs.randn(*s), jnp.float32)
    shape = lead + (tokens,)
    return dict(
        X=f32(*shape, n * C).astype(dtype), y=f32(*shape, C).astype(dtype),
        phi=(0.05 * f32(n * C, k)).astype(dtype),
        gate=0.5 + 0.1 * jnp.abs(f32(k)), b=0.3 * f32(k),
        post=2 * jax.nn.sigmoid(f32(*shape, n)),
        res=jax.nn.softmax(f32(*shape, n, n)).reshape(shape + (n * n,)),
        du=f32(*shape, C).astype(dtype), dht=0.1 * f32(*shape, k),
        dX=f32(*shape, n * C).astype(dtype))


def _read(how):
    if how == "plain":
        return lambda X, phi, gate, b, n: hc.read_plain(X, phi, gate, b, n, EPS)
    return lambda X, phi, gate, b, n: hc._read(X, phi, gate, b, n, EPS, how)


def _write(how):
    if how == "plain":
        return hc.write_plain
    return lambda X, y, post, res: hc._write(
        X, y, jnp.concatenate([post, res], axis=-1), post.shape[-1], how)


def _assert_same(got, want):
    """float32: 2e-6 of the value's scale (the two paths sum in another
    order).  bfloat16: one rounding of such a float32 value on both paths -
    one unit of the last place (2^-7 of the value's power of two) apart, or
    1e-5 where terms cancel."""
    assert got.dtype == want.dtype and got.shape == want.shape
    a, b = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if want.dtype == jnp.bfloat16:
        ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(b), 1e-30))) - 7)
        assert np.all(np.abs(a - b) <= np.maximum(ulp, 1e-5))
    else:
        np.testing.assert_allclose(a, b, atol=2e-6 * max(1.0, np.abs(b).max()),
                                   rtol=0)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_the_read_matches_plain_output_and_gradients(shape, dtype):
    """u, ht and X itself; then dX, and the frozen base's three gradients,
    from cotangents of all three results: ``du``, ``dht`` (what the Sinkhorn
    loop and the sigmoids send back) and the write's share."""
    n = shape[0]
    o = _operands(*shape, dtype)
    args = (o["X"], o["phi"], o["gate"], o["b"])
    cts = (o["du"], o["dht"], o["dX"])

    def both(how):
        out, back = jax.vjp(lambda *a: _read(how)(*a, n), *args)
        return out, back(cts)

    (u, ht, same), grads = jax.jit(lambda: both(True))()
    (u_, ht_, _), grads_ = jax.jit(lambda: both("plain"))()
    np.testing.assert_array_equal(same, o["X"])
    _assert_same(u, u_)
    np.testing.assert_allclose(ht, ht_, atol=4e-6 * float(jnp.abs(ht_).max()))
    if dtype == jnp.float32:
        _assert_same(grads[0], grads_[0])
    else:
        # jax adds the plain path's shares of dX after rounding each, the
        # kernel rounds their float32 sum: no farther from the float32
        # derivative than the plain path is
        up = lambda a: a.astype(jnp.float32)
        exact = jax.vjp(lambda X: _read("plain")(X, up(o["phi"]), *args[2:], n),
                        up(o["X"]))[1]((up(o["du"]), o["dht"], up(o["dX"])))[0]
        off = lambda g: float(jnp.linalg.norm(up(g) - exact))
        assert off(grads[0]) <= 1.05 * off(grads_[0])
        assert off(grads[0]) <= 6e-3 * float(jnp.linalg.norm(exact))
    for g, g_ in zip(grads[1:], grads_[1:]):
        scale = float(jnp.abs(g_.astype(jnp.float32)).max())
        np.testing.assert_allclose(
            g.astype(jnp.float32), g_.astype(jnp.float32),
            atol=(2e-2 if dtype == jnp.bfloat16 else 2e-5) * scale)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_the_write_matches_plain_output_and_gradients(shape, dtype):
    """X'; then the streams' share, dF(u) and the n + n n lane reductions."""
    o = _operands(*shape, dtype)
    args = (o["X"], o["y"], o["post"], o["res"])

    def both(how):
        out, back = jax.vjp(_write(how), *args)
        return out, back(o["dX"])

    out, grads = jax.jit(lambda: both(True))()
    out_, grads_ = jax.jit(lambda: both("plain"))()
    _assert_same(out, out_)
    _assert_same(grads[0], grads_[0])
    _assert_same(grads[1], grads_[1])
    for g, g_ in zip(grads[2:], grads_[2:]):
        np.testing.assert_allclose(g, g_, atol=1e-5 * float(jnp.abs(g_).max()))


def _connection(read, write, n):
    """One hyper-connection around ``F(u) = tanh(u)`` with the model's own
    maps: sum(X' w) and its gradient with respect to the streams."""
    def loss(X, w, phi, gate, b):
        u, ht, X = read(X, phi, gate, b, n)
        post, res, _ = xing4.hc_maps(jnp.moveaxis(ht, -1, 0), n, 20, 1e-6,
                                     (-30.0, 30.0))
        out = write(X, jnp.tanh(u), post, res)
        return jnp.sum(out.astype(jnp.float32) * w)
    return jax.value_and_grad(loss)


@pytest.fixture(scope="module")
def clients():
    """Two clients' float32 streams of 64 tokens, four streams of 128."""
    o = _operands(4, 128, 64, jnp.float32, seed=3, lead=(2, 1))
    w = jnp.asarray(np.random.RandomState(4).randn(*o["X"].shape), jnp.float32)
    args = (o["X"], w)
    rest = (o["phi"], o["gate"], o["b"])
    per_client = lambda how, wrap=lambda f: f: lambda X, w: wrap(
        _connection(_read(how), _write(how), 4))(X, w, *rest)
    want = jax.jit(jax.vmap(per_client("plain")))(*args)
    return args, per_client, want, rest


def _close(got, want):
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=3e-6 * float(jnp.abs(b).max()))


@pytest.mark.parametrize("wrap", [lambda f: f, jax.checkpoint],
                         ids=["bare", "checkpoint"])
def test_under_vmap_over_clients(clients, wrap):
    """A chunk's `vmap` over clients, the maps' leaves un-mapped, with and
    without a `jax.checkpoint`."""
    args, per_client, want, _ = clients
    _close(jax.jit(jax.vmap(per_client(True, wrap)))(*args), want)


def test_under_shard_map_with_check_vma(clients):
    """The engine's `shard_map(check_vma=True)` over a vmap over clients on a
    one-device mesh: every kernel's results say over which axes they vary,
    and the public functions lower for a TPU with all four passes."""
    args, per_client, want, rest = clients
    mesh = Mesh(np.array(jax.devices()[:1]), ("clients",))
    spec = (P("clients"),) * 2

    def sharded(fn):
        return jax.jit(jax.shard_map(jax.vmap(fn), mesh=mesh, in_specs=spec,
                                     out_specs=spec))

    _close(sharded(per_client(pltpu.InterpretParams()))(*args), want)
    public = lambda X, w: jax.checkpoint(_connection(
        lambda X, phi, gate, b, n: hc.hc_read(X, phi, gate, b, n=n, eps=EPS),
        hc.hc_write, 4))(X, w, *rest)
    _close(sharded(public)(*args), want)                # CPU: the plain rules
    lowered = sharded(public).trace(*args).lower(lowering_platforms=("tpu",))
    assert lowered.as_text().count("tpu_custom_call") >= 4


class _Interpreted:
    """`ops.hyper_connection` as `models/xing4.py` sees it, every kernel in
    interpret mode (the public functions lower the plain rules on a CPU)."""
    @staticmethod
    def hc_read(X, phi, gate, b, *, n, eps):
        assert hc._fits(X, n)
        return hc._read(X, phi, gate, b, n, eps, True)

    @staticmethod
    def hc_write(X, y, post, res):
        assert hc._fits(X, post.shape[-1])
        return _write(True)(X, y, post, res)


class _Plain:
    hc_read = staticmethod(lambda X, phi, gate, b, *, n, eps: hc.read_plain(
        X, phi, gate, b, n, eps))
    hc_write = staticmethod(hc.write_plain)


WIDE = dict(d_model=128, n_heads=2, q_rank=24, kv_rank=16, nope_dim=16,
            rope_dim=8, v_dim=16, d_ff=96, d_expert=32, n_experts=8,
            experts_per_token=2, n_shared=1, n_layers=4, first_dense=1,
            layers=[0, 1], held=[0, 4], rope_original=16, lora_rank=4,
            lora_alpha=8.0)


@pytest.mark.parametrize("dtype, saved, tol", [
    (jnp.float32, "input_only", 2e-5), (jnp.bfloat16, "attention", 4e-2)],
    ids=["f32", "bf16_KEEP"])
def test_a_two_layer_model_under_its_checkpoint(monkeypatch, dtype, saved, tol):
    """Loss and adapter gradients of a two-layer `Xing4LM` (a dense layer and
    an expert layer, each a `jax.checkpoint`: on a bfloat16 stream under
    `xing4._KEEP`) with the kernels in the functions' place equal the plain
    body's - to float32 rounding on a float32 stream, to bfloat16's where
    the stream is that."""
    model = create_model("xing4", 128, **WIDE)
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randint(0, 128, (2, 32)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), x, train=False)["params"]
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32) + 0.05 * jnp.asarray(
        rs.randn(*a.shape), jnp.float32), params)
    w = jnp.asarray(rs.randn(2, 32, 128), jnp.float32)
    policy = obs.counter("remat_policy_total", model="xing4", saved=saved)

    def run(ops):
        monkeypatch.setattr(xing4, "hyper_connection", ops)
        before = policy.value
        loss = lambda lora: jnp.sum(w * model.apply({"params": {
            **params, "lora": jax.tree.map(lambda a: a.astype(dtype), lora)}},
            x, train=True))
        out = jax.jit(jax.value_and_grad(loss))(params["lora"])
        assert policy.value == before + 1
        return out

    (l, g), (l_, g_) = run(_Interpreted), run(_Plain)
    assert abs(float(l) - float(l_)) <= tol * abs(float(l_))
    flat = jnp.concatenate([a.ravel() for a in jax.tree.leaves(g)])
    flat_ = jnp.concatenate([a.ravel() for a in jax.tree.leaves(g_)])
    assert float(jnp.abs(flat_).max()) > 1e-3
    assert float(jnp.linalg.norm(flat - flat_)) <= tol * float(jnp.linalg.norm(flat_))


def test_two_blocks_under_the_kept_names_in_float32():
    """`xing4._KEEP` itself - the policy of a 16-bit stream - around two
    stacked `xing4.block`s on a float32 stream, where the two bodies can be
    held to float32 rounding: the names it keeps (the attention kernel's,
    ``W_o``'s output, ``mlp_out``) and what it re-makes (the read's ``u``,
    ``ht`` and residuals, the maps, the first write) give the plain body's
    value and gradient."""
    model = create_model("xing4", 128, **WIDE)
    rs = np.random.RandomState(5)
    x = jnp.asarray(rs.randint(0, 128, (1, 32)), jnp.int32)
    params = model.init(jax.random.PRNGKey(1), x, train=False)["params"]
    lp = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params["layer_1"])
    ad = jax.tree.map(lambda a: a + 0.05 * jnp.asarray(
        rs.randn(*a.shape), jnp.float32), params["lora"]["layer_1"])
    X = jnp.asarray(rs.randn(1, 32, 512), jnp.float32)
    w = jnp.asarray(rs.randn(1, 32, 512), jnp.float32)
    cos, sin = xing4.yarn_tables(32, 8, 1e4, 64.0, 32.0, 1.0, 16)

    def run(ops):
        saved, xing4.hyper_connection = xing4.hyper_connection, ops
        try:
            layer = jax.checkpoint(
                lambda X, ad: model._layer(X, lp, ad, cos, sin)[0],
                policy=xing4._KEEP)
            return jax.jit(jax.value_and_grad(lambda X, ad: jnp.sum(
                layer(layer(X, ad), ad) * w), (0, 1)))(X, ad)
        finally:
            xing4.hyper_connection = saved

    (l, (dX, dad)), (l_, (dX_, dad_)) = run(_Interpreted), run(_Plain)
    assert abs(float(l) - float(l_)) <= 1e-5 * abs(float(l_))
    np.testing.assert_allclose(dX, dX_, atol=1e-5 * float(jnp.abs(dX_).max()))
    for a, b in zip(jax.tree.leaves(dad), jax.tree.leaves(dad_)):
        np.testing.assert_allclose(a, b, atol=1e-5 * float(jnp.abs(b).max()) + 1e-9)


def _paths(op):
    return {path: obs.counter("ops_kernel_path_total", op=op, path=path).value
            for path in ("pallas", "reference")}


@pytest.mark.parametrize("n, C, tokens, dtype", [
    (4, 64, 64, jnp.bfloat16),                 # the tiny tests' width: half a tile of lanes
    (4, 128, 37, jnp.bfloat16),                # an odd number of tokens
    (4, 128, 64, jnp.float16),                 # a dtype of neither kind
], ids=["C64", "T37", "f16"])
def test_a_shape_that_does_not_fit_takes_the_plain_body(n, C, tokens, dtype):
    o = _operands(n, C, tokens, dtype)
    read = lambda X: hc.hc_read(X, o["phi"], o["gate"], o["b"], n=n, eps=EPS)
    write = lambda X: hc.hc_write(X, o["y"], o["post"], o["res"])
    before = _paths("hc_read"), _paths("hc_write")
    got = str(jax.make_jaxpr(read)(o["X"])), str(jax.make_jaxpr(write)(o["X"]))
    after = _paths("hc_read"), _paths("hc_write")
    for b, a in zip(before, after):
        assert a["reference"] == b["reference"] + 1 and a["pallas"] == b["pallas"]
    assert got[0] == str(jax.make_jaxpr(lambda X: hc.read_plain(
        X, o["phi"], o["gate"], o["b"], n, EPS))(o["X"]))
    assert got[1] == str(jax.make_jaxpr(lambda X: hc.write_plain(
        X, o["y"], o["post"], o["res"]))(o["X"]))
    assert "pallas_call" not in got[0] + got[1]


def test_a_shape_that_fits_is_counted_and_its_cpu_lowering_is_the_plain_body():
    o = _operands(4, 128, 64, jnp.bfloat16)
    connection = lambda X: _connection(
        lambda X, phi, gate, b, n: hc.hc_read(X, phi, gate, b, n=n, eps=EPS),
        hc.hc_write, 4)(X, o["dX"].astype(jnp.float32), o["phi"], o["gate"], o["b"])
    before = _paths("hc_read"), _paths("hc_write")
    lowered = jax.jit(connection).lower(o["X"])
    after = _paths("hc_read"), _paths("hc_write")
    for b, a in zip(before, after):
        assert a["pallas"] == b["pallas"] + 1 and a["reference"] == b["reference"]
    assert "custom_call" not in lowered.as_text()
    # the same trace lowered for a TPU holds the four kernels, by name
    tpu = jax.jit(connection).trace(o["X"]).lower(lowering_platforms=("tpu",))
    text = tpu.as_text()
    assert text.count("tpu_custom_call") == 4
    for name in ("hc_read", "hc_write", "hc_write_bwd", "hc_read_bwd"):
        assert f'kernel_name = "{name}"' in text or f"{name}" in text


def test_a_float32_block_holds_half_the_tokens():
    """Blocks of whole rows under one byte budget: 64 tokens of the cell's
    four 3,584-wide bfloat16 streams, 32 of its float32 twin's."""
    assert hc._rows(8192, 4 * 3584, jnp.bfloat16) == 64
    assert hc._rows(8192, 4 * 3584, jnp.float32) == 32
    assert hc._rows(8200, 4 * 3584, jnp.bfloat16) is None     # 8 tokens left over
    assert hc._chunk(3584) == 512 and hc._chunk(128) == 128 and hc._chunk(768) == 256
