"""SyncBatchNorm parity (reference cv/batchnorm_utils.py): batch statistics
psum over the mesh axis, identical param tree with/without sync.

And the norm the three ResNet cells run — flax's `nn.GroupNorm` as
`models/resnet_gn.py` builds it, 2 groups, on bfloat16 activations with
bfloat16 scale and bias (the cells' `train_dtype`) at the four stage shapes —
against a float64 GroupNorm written out here."""
import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from fedml_tpu.models.norms import sync_batch_norm
from fedml_tpu.models.resnet_gn import ResNet18GN
from fedml_tpu.parallel.mesh import make_mesh


class Net(nn.Module):
    axis: str = "clients"
    sync: bool = True

    @nn.compact
    def __call__(self, x, train=True):
        return sync_batch_norm(use_running_average=not train,
                               sync=self.sync, axis_name=self.axis)(x)


def test_sync_bn_uses_global_stats():
    mesh = make_mesh(8)
    axis = mesh.axis_names[0]
    net = Net(axis=axis)
    x = np.random.RandomState(0).rand(32, 6).astype(np.float32)
    v = net.init(jax.random.PRNGKey(0), x[:4], train=False)

    def body(v, xb):
        out, _ = net.apply(v, xb, train=True, mutable=["batch_stats"])
        return out

    f = jax.jit(jax.shard_map(body, mesh=mesh,
                              in_specs=(P(), P(axis)), out_specs=P(axis)))
    out = np.asarray(f(v, x))
    # normalized with GLOBAL batch stats → global mean 0 / std 1, which
    # per-device BN (different per-shard distributions) cannot produce
    assert np.abs(out.mean(0)).max() < 1e-4
    assert np.abs(out.std(0) - 1).max() < 1e-2


def test_sync_and_plain_share_param_tree():
    x = jnp.zeros((4, 6))
    v_sync = Net(sync=True).init(jax.random.PRNGKey(0), x, train=False)
    v_plain = Net(sync=False).init(jax.random.PRNGKey(0), x, train=False)
    assert jax.tree.structure(v_sync) == jax.tree.structure(v_plain)


# -- the ResNet cells' GroupNorm ----------------------------------------------

# [N, H, W, C] after each of ResNet-18's four stages on 32 x 32 inputs
STAGES = [(4, 32, 32, 64), (4, 16, 16, 128), (4, 8, 8, 256), (4, 4, 4, 512)]
GROUPS, EPS = ResNet18GN().groups, 1e-6          # flax's default epsilon


def _grouped(a):
    n, h, w, c = a.shape
    return a.reshape(n, h * w, GROUPS, c // GROUPS)


def _group_norm64(x, gamma, beta, dy):
    """GroupNorm and its input gradient in float64 numpy: statistics over a
    sample's positions and a group's channels, the biased variance.
    ``dx = rstd * (g - mean(g) - xhat * mean(g * xhat))`` with
    ``g = dy * gamma``, the means over the same set."""
    over = dict(axis=(1, 3), keepdims=True)
    xg = _grouped(x)
    mean = xg.mean(**over)
    rstd = 1.0 / np.sqrt(((xg - mean) ** 2).mean(**over) + EPS)
    xhat = (xg - mean) * rstd
    g = _grouped(dy * gamma)
    dx = rstd * (g - g.mean(**over) - xhat * (g * xhat).mean(**over))
    return xhat.reshape(x.shape) * gamma + beta, dx.reshape(x.shape)


@pytest.fixture(scope="module", params=STAGES, ids=str)
def stage(request):
    """bfloat16 operands of one stage, what flax makes of them, and the
    float64 result on the same (exactly representable) operands."""
    shape = request.param
    rs = np.random.RandomState(shape[-1])
    bf16 = lambda a: jnp.asarray(a, jnp.bfloat16)
    x = bf16(1.5 * rs.randn(*shape) + 0.7)
    gamma = bf16(1 + 0.3 * rs.randn(shape[-1]))
    beta = bf16(0.3 * rs.randn(shape[-1]))
    dy = bf16(rs.randn(*shape))
    norm = lambda x: nn.GroupNorm(num_groups=GROUPS).apply(
        {"params": {"scale": gamma, "bias": beta}}, x)
    y, transpose = jax.vjp(jax.jit(norm), x)
    f64 = lambda a: np.asarray(a.astype(jnp.float32), np.float64)
    return (y, transpose(dy)[0]), _group_norm64(*map(f64, (x, gamma, beta, dy)))


def test_group_norm_forward_matches_float64(stage):
    """Statistics and the normalisation in float32, one rounding to
    bfloat16: within one bfloat16 place (2^-7) of the float64 value."""
    (y, _), (want, _) = stage
    assert y.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(y, np.float64), want,
                               rtol=2.0 ** -7, atol=1e-4)


def test_group_norm_input_gradient_matches_float64(stage):
    """The cotangents of x's uses are rounded to bfloat16 before they are
    summed, so a small sum carries the rounding of its large terms: one
    bfloat16 place of the value or of the largest gradient."""
    (_, dx), (_, want) = stage
    assert dx.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(dx, np.float64), want,
                               rtol=2.0 ** -7,
                               atol=2.0 ** -7 * np.abs(want).max())
