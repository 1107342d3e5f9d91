"""`ops/attention.py::causal_attention` — the fused kernels (Pallas
interpret mode, small aligned shapes) against the plain path, under the
transformations the engine applies to them, and the rule that picks a path.

What only a chip can show — Mosaic-compiled kernels at the cells' shapes —
is `tests/test_tpu_compile.py::test_causal_attention_compiles` (compiles)
and `chip_smoke.py` phase (c) (values)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from fedml_tpu import obs
from fedml_tpu.models import create_model, lfm2_moe, looped_lm
from fedml_tpu.ops import attention
from fedml_tpu.ops.attention import causal_attention

T = 384          # three tiles of 128: an unmasked loop and a diagonal


def _fused(q, k, v, *rope, scale=None):
    return attention._attention(q, k, v, True, rope, scale)


def _operands(shape_q, n_kv, dtype, seed=0, rope=None):
    """q, k, v and the weights of the loss; ``rope`` = (r, H_r) adds the
    rotary parts q_rope [.., H, r] and k_rope [.., H_r, r] of latent
    attention before the weights."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    kv = shape_q[:-2] + (n_kv, shape_q[-1])
    shapes = [shape_q, kv, kv]
    if rope:
        shapes += [shape_q[:-1] + (rope[0],), shape_q[:-2] + (rope[1], rope[0])]
    ops = [jax.random.normal(key, s, jnp.float32).astype(dtype)
           for key, s in zip(keys, shapes)]
    return (*ops, jax.random.normal(keys[5], shape_q, jnp.float32))


def _out_and_grads(fn, *operands, **kw):
    """[o, dq, dk, dv(, dq_rope, dk_rope)] in float32 of loss = sum(o * w)."""
    *ops, w = operands

    def loss(*ops):
        o = fn(*ops, **kw)
        return jnp.sum(o.astype(jnp.float32) * w), o
    grads, o = jax.jit(jax.grad(loss, tuple(range(len(ops))), has_aux=True))(*ops)
    return [np.asarray(a, np.float32) for a in (o,) + grads]


def _rel(got, want):
    return [float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
            for a, b in zip(got, want)]


def _distance(got, want):
    return [float(np.linalg.norm(a - b) / np.linalg.norm(b))
            for a, b in zip(got, want)]


# (query heads, key/value heads), head size, (rotary size, rotary key heads),
# scale: the two models' shapes, and latent attention's - keys 128 + 64 wide
# against values of 128, ONE rotary key head for all, a scale that is given
MLA_SCALE = 192 ** -0.5 * 1.2608 ** 2
SHAPES = [pytest.param(heads, hd, None, None, id=f"{name}-{hd}")
          for hd in (64, 128) for name, heads in (("mha", (2, 2)), ("gqa4", (4, 1)))]
SHAPES.append(pytest.param((4, 4), 128, (64, 1), MLA_SCALE, id="mla-128+64"))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("heads, hd, rope, scale", SHAPES)
def test_fused_matches_plain(heads, hd, rope, scale, dtype):
    """Output and the three gradients (five with a rotary part).  float32:
    to 1e-5 of the largest value.  bfloat16: no farther (relative l2
    distance: the largest single error is one rounding of the result on
    either path) from a float32 oracle than 1.5 x the plain bfloat16 path
    is — the softmax stays float32 and every product accumulates in float32
    on both, they differ in where they round (on the CPU the plain path's
    autodiff keeps ds float32 as an operand, which a TPU's one-pass product
    does not)."""
    H, n_kv = heads
    operands = _operands((2, T, H, hd), n_kv, dtype, rope=rope)
    got = _out_and_grads(_fused, *operands, scale=scale)
    plain = _out_and_grads(attention._plain, *operands, scale=scale)
    assert len(got) == (6 if rope else 4)
    if dtype == jnp.float32:
        assert max(_rel(got, plain)) <= 1e-5, _rel(got, plain)
        return
    oracle = _out_and_grads(attention._plain, *(
        a.astype(jnp.float32) for a in operands), scale=scale)
    for f, p in zip(_distance(got, oracle), _distance(plain, oracle)):
        assert f <= 1.5 * p, (_distance(got, oracle), _distance(plain, oracle))


# a sliding window against T = 384 in tiles of 128: narrower than a tile (the
# diagonal tile holds the band's far edge too), exactly a tile, not a multiple
# of the tile (the edge crosses two tiles of a row), spanning several tiles,
# and as long as the sequence (no key is out of reach)
WINDOWS = [5, 128, 200, 300, T]
BAND_SHAPES = [pytest.param((4, 1), 64, None, None, id="gqa4-64"),
               pytest.param((16, 1), 128, None, None, id="gqa16-128"),
               pytest.param((4, 4), 128, (64, 1), MLA_SCALE, id="mla-128+64")]


def _fused_w(window):
    return lambda q, k, v, *rope, scale=None: attention._attention(
        q, k, v, True, rope, scale, window)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("heads, hd, rope, scale", BAND_SHAPES)
def test_fused_band_matches_plain(heads, hd, rope, scale, window, dtype):
    """`test_fused_matches_plain` under a sliding window: output and every
    gradient, the same tolerances - the plain path's mask (``0 <= i - j <
    window``) is the spec, at a group of 4, a group of 16 and with a rotary
    part."""
    H, n_kv = heads
    operands = _operands((1, T, H, hd), n_kv, dtype, rope=rope)
    got = _out_and_grads(_fused_w(window), *operands, scale=scale)
    plain = _out_and_grads(attention._plain, *operands, scale=scale, window=window)
    assert len(got) == (6 if rope else 4)
    if dtype == jnp.float32:
        assert max(_rel(got, plain)) <= 1e-5, _rel(got, plain)
        return
    oracle = _out_and_grads(attention._plain, *(
        a.astype(jnp.float32) for a in operands), scale=scale, window=window)
    for f, p in zip(_distance(got, oracle), _distance(plain, oracle)):
        assert f <= 1.5 * p, (_distance(got, oracle), _distance(plain, oracle))


def test_the_plain_window_is_the_last_window_positions():
    """The numerical spec of the mask: key j is visible to query i iff
    0 <= i - j < window - the key at distance ``window`` is out."""
    q, k, v, _ = _operands((1, 24, 2, 16), 2, jnp.float32)
    got = causal_attention(q, k, v, window=7)
    i, j = np.arange(24)[:, None], np.arange(24)[None, :]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 16 ** -0.5
    s = jnp.where((i - j >= 0) & (i - j < 7), s, -jnp.inf)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    np.testing.assert_allclose(got, want, atol=1e-5)
    # position 7 does not see key 0; with window 8 it does
    bumped = causal_attention(q, k.at[:, 0].add(1.0), v, window=7)
    np.testing.assert_array_equal(got[:, 7:], bumped[:, 7:])
    assert not np.array_equal(got[:, 6], bumped[:, 6])
    assert not np.array_equal(causal_attention(q, k, v, window=8)[:, 7],
                              causal_attention(q, k.at[:, 0].add(1.0), v, window=8)[:, 7])


@pytest.mark.parametrize("T_, window, visited, causal", [
    (8192, 4096, 108, 136),        # cmdaplus.lora4of256long: tiles of 512
    (384, 5, 5, 6), (384, 128, 5, 6), (384, 129, 5, 6), (384, 130, 6, 6),
    (384, 300, 6, 6), (1024, 512, 3, 3), (2048, 512, 7, 10), (2048, 513, 7, 10),
    (2048, 514, 9, 10), (384, None, 6, 6)])
def test_band_blocks_counts_the_tiles_inside_the_band(T_, window, visited, causal):
    assert attention.band_blocks(T_, window) == (visited, causal)
    if window is None:
        return
    tile = attention._tile(T_)
    i, j = np.arange(T_)[:, None], np.arange(T_)[None, :]
    mask = ((i - j >= 0) & (i - j < window)).reshape(
        T_ // tile, tile, T_ // tile, tile).any(axis=(1, 3))
    assert int(mask.sum()) == visited          # exactly the tiles that hold a pair


def test_blocks_outside_the_band_are_not_visited():
    """Keys and values of a block that lies wholly outside a query block's
    band are never read: made NaN, they reach no output of the rows whose
    band excludes them (a kernel that computed and masked them would turn
    ``0 x NaN`` into NaN, as the plain path does), and the windowed call
    counts the tiles it visits."""
    q, k, v, w = _operands((1, T, 2, 128), 1, jnp.float32)
    window = 100                                  # reach: one tile behind
    poison = lambda a: a.at[:, :128].set(jnp.nan)  # tile 0
    got = _out_and_grads(_fused_w(window), q, poison(k), poison(v), w)
    clean = _out_and_grads(_fused_w(window), q, k, v, w)
    rows = slice(256, T)                          # query tile 2 sees tiles 1, 2
    np.testing.assert_array_equal(got[0][:, rows], clean[0][:, rows])
    np.testing.assert_array_equal(got[1][:, rows], clean[1][:, rows])      # dq
    assert np.isnan(got[0][:, :128]).all()        # tile 0's own rows do read it
    plain = np.asarray(attention._plain(q, poison(k), poison(v), window=window))
    assert np.isnan(plain[:, rows]).all()
    count = lambda blocks: obs.counter("attention_band_blocks_total",
                                       blocks=blocks).value
    before = count("visited"), count("causal"), _paths()["pallas"]
    jax.make_jaxpr(lambda *a: causal_attention(*a, window=window))(q, k, v)
    assert count("visited") - before[0] == 5 * 2      # tiles x batch x heads
    assert count("causal") - before[1] == 6 * 2
    assert _paths()["pallas"] == before[2] + 1        # the path counts this shape too


def test_a_window_that_reaches_the_whole_sequence_is_no_window():
    """``window >= T`` traces the program ``window=None`` traces, kernels
    and all, and counts no band."""
    q, k, v, _ = _operands((1, 256, 4, 64), 2, jnp.bfloat16)
    count = lambda: obs.counter("attention_band_blocks_total", blocks="causal").value
    before = count()
    text = lambda **kw: str(jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(
        causal_attention(*a, **kw).astype(jnp.float32)), (0, 1, 2)))(q, k, v))
    assert text(window=256) == text(window=4096) == text()
    assert text(window=255) != text()
    assert count() == before + 1 * 4 * 1          # the one windowed call, T = one tile


def test_band_under_vmap_and_checkpoint(clients):
    """The windowed kernels as the engine runs them: a vmap over a chunk's
    clients of a checkpointed layer."""
    (q, k, v, w), _, _ = clients                  # T = 256: one tile, window < tile
    loss = lambda fn: lambda q, k, v, w: jnp.sum(jnp.sin(fn(q, k, v)) * w)
    grad = lambda fn: jax.jit(jax.vmap(jax.grad(loss(fn), (0, 1, 2))))
    plain = lambda q, k, v: attention._plain(q, k, v, window=77)
    _close(grad(jax.checkpoint(_fused_w(77)))(q, k, v, w), grad(plain)(q, k, v, w))


def test_band_over_several_tiles_under_vmap_and_checkpoint():
    q, k, v, w = _operands((2, 1, T, 2, 128), 1, jnp.float32)     # three tiles of 128
    loss = lambda fn: lambda q, k, v, w: jnp.sum(jnp.sin(fn(q, k, v)) * w)
    grad = lambda fn: jax.jit(jax.vmap(jax.grad(loss(fn), (0, 1, 2))))
    plain = lambda q, k, v: attention._plain(q, k, v, window=200)
    _close(grad(jax.checkpoint(_fused_w(200)))(q, k, v, w), grad(plain)(q, k, v, w))


def test_the_two_part_plain_path_is_attention_over_concatenated_keys():
    """The numerical spec of the two-operand form: the scores of q | q_rope
    against k | k_rope (the rotary key repeated for every head), any three
    head sizes, the scale as given."""
    q, k, v, q_rope, k_rope, _ = _operands((2, 24, 4, 16), 4, jnp.float32,
                                           rope=(8, 1))
    v = v[..., :12]                                # values narrower than keys
    got = causal_attention(q, k, v, rope=(q_rope, k_rope), scale=0.37)
    wide_q = jnp.concatenate([q, q_rope], axis=-1)
    wide_k = jnp.concatenate([k, jnp.repeat(k_rope, 4, axis=2)], axis=-1)
    s = jnp.einsum("bqhd,bkhd->bhqk", wide_q, wide_k) * 0.37
    s = jnp.where(jnp.tril(jnp.ones((24, 24), bool)), s, -jnp.inf)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    assert got.shape == (2, 24, 4, 12)
    np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.fixture(scope="module", params=[64, 128], ids=["hd64", "hd128"])
def clients(request):
    """Two clients' operands [C, B, T, H, hd] and the plain gradients, for
    heads brought heads-first (64) and heads read in place (128)."""
    q, k, v, w = _operands((2, 1, 256, 4, request.param), 2, jnp.float32)
    loss = lambda fn: lambda q, k, v, w: jnp.sum(jnp.sin(fn(q, k, v)) * w)
    grad = lambda fn: jax.grad(loss(fn), (0, 1, 2))
    want = jax.jit(jax.vmap(grad(attention._plain)))(q, k, v, w)
    return (q, k, v, w), grad, want


def _close(got, want):
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-5 * float(jnp.max(jnp.abs(b))))


def test_under_vmap_over_clients(clients):
    args, grad, want = clients
    _close(jax.jit(jax.vmap(grad(_fused)))(*args), want)


def test_under_checkpoint(clients):
    args, grad, want = clients
    _close(jax.jit(jax.vmap(grad(jax.checkpoint(_fused))))(*args), want)


def test_latent_attention_under_vmap_and_checkpoint():
    """The two-part form as the engine runs it: a vmap over a chunk's clients
    of a checkpointed layer, all five gradients."""
    *ops, w = _operands((2, 1, 256, 4, 128), 4, jnp.float32, rope=(64, 1))

    def grad(fn):
        loss = lambda *a: jnp.sum(jnp.sin(fn(*a[:5])) * a[5])
        return jax.jit(jax.vmap(jax.grad(loss, (0, 1, 2, 3, 4))))

    fused = lambda q, k, v, *rope: attention._attention(q, k, v, True, rope, 0.11)
    plain = lambda *a: attention._plain(*a, scale=0.11)
    _close(grad(jax.checkpoint(fused))(*ops, w), grad(plain)(*ops, w))


def test_under_shard_map_with_check_vma(clients):
    """The engine's `shard_map(check_vma=True)` (what refused megablox in
    PR 34: a `pallas_call` result without a `vma`), over a vmap over
    clients, on a two-device mesh: the kernels run (the TPU interpreter:
    the plain one re-binds the kernel's untyped equations under the mesh),
    and the public function lowers for a TPU with both kernels in it."""
    args, grad, want = clients
    mesh = Mesh(np.array(jax.devices()[:2]), ("clients",))
    spec = (P("clients"),) * 4

    def sharded(fn):
        return jax.jit(jax.shard_map(jax.vmap(grad(fn)), mesh=mesh,
                                     in_specs=spec, out_specs=spec[:3]))

    interpreted = lambda q, k, v: attention._attention(
        q, k, v, pltpu.InterpretParams())
    _close(sharded(interpreted)(*args), want)
    engine_like = sharded(jax.checkpoint(causal_attention))
    for a, b in zip(engine_like(*args), want):          # CPU: the plain path
        np.testing.assert_array_equal(a, b)
    lowered = engine_like.trace(*args).lower(lowering_platforms=("tpu",))
    assert lowered.as_text().count("tpu_custom_call") >= 2


def test_causality_is_bitwise():
    """Changing token t leaves every output before t as it was, to the bit."""
    q, k, v, _ = _operands((1, T, 2, 64), 1, jnp.float32)
    t = 200                                       # inside the second tile
    bump = lambda a: a.at[:, t].add(1.0)
    before = jax.jit(_fused)(q, k, v)
    after = jax.jit(_fused)(bump(q), bump(k), bump(v))
    np.testing.assert_array_equal(before[:, :t], after[:, :t])
    assert not np.array_equal(before[:, t:], after[:, t:])


def _paths():
    return {path: obs.counter("ops_kernel_path_total", op="causal_attention",
                              path=path).value
            for path in ("pallas", "reference")}


@pytest.mark.parametrize("shape, n_kv, dtype", [
    ((1, 200, 2, 64), 2, jnp.float32),            # T not a multiple of 128
    ((1, 256, 2, 32), 2, jnp.float32),            # a head size of 32
    ((1, 256, 2, 64), 2, jnp.float16),            # a dtype of neither kind
], ids=["T200", "hd32", "f16"])
def test_a_shape_that_does_not_fit_takes_the_plain_path(shape, n_kv, dtype):
    q, k, v, _ = _operands(shape, n_kv, dtype)
    before = _paths()
    lowered = jax.jit(causal_attention).lower(q, k, v)
    after = _paths()
    assert after["reference"] == before["reference"] + 1
    assert after["pallas"] == before["pallas"]
    assert "platform_index" not in str(jax.make_jaxpr(causal_attention)(q, k, v))
    np.testing.assert_array_equal(lowered.compile()(q, k, v),
                                  jax.jit(attention._plain)(q, k, v))


def test_a_shape_that_fits_is_counted_and_its_cpu_lowering_is_the_plain_path():
    q, k, v, w = _operands((1, 256, 4, 64), 2, jnp.bfloat16)
    before = _paths()
    lowered = jax.jit(causal_attention).lower(q, k, v)
    after = _paths()
    assert after["pallas"] == before["pallas"] + 1
    assert after["reference"] == before["reference"]
    assert "custom_call" not in lowered.as_text()
    for a, b in zip(_out_and_grads(causal_attention, q, k, v, w),
                    _out_and_grads(attention._plain, q, k, v, w)):
        np.testing.assert_array_equal(a, b)
    # the same trace lowered for a TPU holds the kernel
    tpu = jax.jit(causal_attention).trace(q, k, v).lower(
        lowering_platforms=("tpu",))
    assert "tpu_custom_call" in tpu.as_text()


# -- the refactor changed nothing: the parent's bodies, written out ----------

def _parent_looped(q, k, v):
    """`decoder_layer`'s core before ISSUE 35."""
    T, dt = q.shape[1], q.dtype
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32)
    s = s * (q.shape[-1] ** -0.5)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None, None], s, jnp.finfo(jnp.float32).min)
    w = jax.nn.softmax(s, axis=-1).astype(dt)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v,
                      preferred_element_type=jnp.float32).astype(dt)


def _parent_gqa(q, k, v):
    """`gqa_attention`'s core before ISSUE 35."""
    B, T, n_heads, _ = q.shape
    n_kv_heads, dt = k.shape[2], q.dtype
    q = q.reshape(B, T, n_kv_heads, n_heads // n_kv_heads, -1)
    s = jnp.einsum("btgrd,bsgd->bgrts", q, k,
                   preferred_element_type=jnp.float32) * (q.shape[-1] ** -0.5)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, jnp.finfo(jnp.float32).min)
    w = jax.nn.softmax(s, axis=-1).astype(dt)
    o = jnp.einsum("bgrts,bsgd->btgrd", w, v,
                   preferred_element_type=jnp.float32).astype(dt)
    return o.reshape(B, T, n_heads, -1)


@pytest.mark.parametrize("name, module, parent, kwargs", [
    ("looped_lm", looped_lm, _parent_looped, {}),
    ("lfm2_moe", lfm2_moe, _parent_gqa, {"lora_rank": 2}),
])
def test_models_are_bitwise_what_they_were(monkeypatch, name, module, parent,
                                           kwargs):
    """Logits and parameter gradients of both models at a tiny size, with
    `causal_attention` against the parent's einsum-mask-softmax-einsum in
    its place."""
    model = create_model(name, output_dim=50, **kwargs)
    x = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, 50)
    variables = model.init(jax.random.PRNGKey(0), x)
    if name == "lfm2_moe":       # B = 0 would hide the adapters' gradients
        variables = jax.tree.map(
            lambda a: a + 0.01 if a.dtype == jnp.float32 else a, variables)

    def run():
        def loss(params):
            logits = model.apply({"params": params}, x)
            return jnp.mean(jnp.square(logits)), logits
        return jax.jit(jax.value_and_grad(loss, has_aux=True))(
            variables["params"])

    (_, logits), grads = run()
    monkeypatch.setattr(module, "causal_attention", parent)
    (_, want_logits), want_grads = run()
    np.testing.assert_array_equal(logits, want_logits)
    assert float(jnp.max(jnp.abs(logits))) > 0
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_array_equal(a, b)
