"""Fused GroupNorm: value and gradient parity with flax nn.GroupNorm (the
spec), on the reference path (the test platform is CPU, where _use_pallas
is False).  The pallas TPU path shares the custom-VJP plumbing; its
kernels are compiled for the chip (without one) at the ResNet-18 stage
shapes by tests/test_tpu_compile.py, and run against the reference on
the chip by `python chip_smoke.py` phase (c)."""
import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.models import create_model
from fedml_tpu.ops.groupnorm import FusedGroupNorm, group_norm


def _ref_gn(x, gamma, beta, G, eps=1e-5):
    mod = nn.GroupNorm(num_groups=G, epsilon=eps)
    return mod.apply({"params": {"scale": gamma, "bias": beta}}, x)


def test_forward_matches_flax():
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.rand(8, 4, 4, 16).astype(np.float32))
    gamma = jnp.asarray(rs.rand(16).astype(np.float32))
    beta = jnp.asarray(rs.rand(16).astype(np.float32))
    got = group_norm(x, gamma, beta, 8)
    want = _ref_gn(x, gamma, beta, 8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_gradients_match_flax_autodiff():
    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.rand(4, 2, 2, 8).astype(np.float32))
    gamma = jnp.asarray(rs.rand(8).astype(np.float32))
    beta = jnp.asarray(rs.rand(8).astype(np.float32))

    def loss_fused(x, g, b):
        return jnp.sum(jnp.sin(group_norm(x, g, b, 4)))

    def loss_ref(x, g, b):
        return jnp.sum(jnp.sin(_ref_gn(x, g, b, 4)))

    got = jax.grad(loss_fused, argnums=(0, 1, 2))(x, gamma, beta)
    want = jax.grad(loss_ref, argnums=(0, 1, 2))(x, gamma, beta)
    for a, b_ in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-3, atol=1e-5)


def test_module_param_tree_matches_nn_groupnorm():
    x = jnp.zeros((2, 4, 4, 16))
    v_fused = FusedGroupNorm(num_groups=8).init(jax.random.PRNGKey(0), x)
    v_plain = nn.GroupNorm(num_groups=8).init(jax.random.PRNGKey(0), x)
    assert jax.tree.structure(v_fused) == jax.tree.structure(v_plain)


def test_resnet18gn_still_trains():
    """Flagship-model training smoke test.  Note: ResNet18GN deliberately
    uses plain nn.GroupNorm — XLA's fused GN beat the hand kernel on
    hardware (see ops/groupnorm.py MEASURED OUTCOME); FusedGroupNorm is
    covered by the op-level tests above."""
    model = create_model("resnet18_gn", 10)
    x = jnp.asarray(np.random.RandomState(0).rand(4, 16, 16, 3),
                    jnp.float32)
    y = jnp.asarray([0, 1, 2, 3])
    v = model.init(jax.random.PRNGKey(0), x, train=False)

    import optax
    def loss(p):
        logits = model.apply(p, x, train=True)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()
    l0, g = jax.value_and_grad(loss)(v)
    assert np.isfinite(float(l0))
    gn = sum(float(jnp.sum(jnp.abs(t))) for t in jax.tree.leaves(g))
    assert gn > 0
