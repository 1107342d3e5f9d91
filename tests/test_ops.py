"""Pallas aggregation kernels vs their pure-XLA references (interpret mode
on the CPU test platform)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.core.pytree import tree_weighted_mean
from fedml_tpu.core.robust import norm_diff_clip
from fedml_tpu.ops import (flatten_stacked_tree, robust_weighted_mean_pallas,
                           unflatten_to_tree, weighted_mean_pallas)


def random_stack(rng, C=5):
    k1, k2, k3 = jax.random.split(rng, 3)
    return {
        "dense": {"kernel": jax.random.normal(k1, (C, 7, 13)),
                  "bias": jax.random.normal(k2, (C, 13))},
        "out": {"kernel": jax.random.normal(k3, (C, 13, 3))},
    }


def test_flatten_roundtrip():
    stack = random_stack(jax.random.PRNGKey(0))
    flat, spec = flatten_stacked_tree(stack)
    assert flat.shape[0] == 5 and flat.shape[1] % 512 == 0
    one = jax.tree.map(lambda x: x[2], stack)
    back = unflatten_to_tree(flat[2], spec)
    for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(back)):
        np.testing.assert_allclose(a, b, rtol=1e-6)


def test_weighted_mean_matches_tree_mean():
    stack = random_stack(jax.random.PRNGKey(1))
    w = jnp.asarray([1.0, 2.0, 0.0, 4.0, 3.0])
    got = weighted_mean_pallas(stack, w, interpret=True)
    want = tree_weighted_mean(stack, w)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_weighted_mean_under_jit():
    stack = random_stack(jax.random.PRNGKey(2))
    w = jnp.asarray([1.0, 1.0, 1.0, 1.0, 1.0])
    f = jax.jit(lambda s, w: weighted_mean_pallas(s, w, interpret=True))
    got = f(stack, w)
    want = tree_weighted_mean(stack, w)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("tau", [0.5, 100.0])
def test_robust_matches_clip_then_mean(tau):
    """Fused kernel == vmap(norm_diff_clip) + weighted mean, for both a
    binding clip (tau small) and a no-op clip (tau large)."""
    stack = random_stack(jax.random.PRNGKey(3))
    g = jax.tree.map(lambda x: x[0] * 0.5, stack)
    w = jnp.asarray([1.0, 2.0, 3.0, 4.0, 5.0])
    got = robust_weighted_mean_pallas(stack, w, g, tau, interpret=True)
    clipped = jax.vmap(lambda p: norm_diff_clip(p, g, tau))(stack)
    want = tree_weighted_mean(clipped, w)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_engine_pallas_agg_matches_default():
    """FedAvgEngine(pallas_agg=True) produces the same round output."""
    from fedml_tpu.algorithms import FedAvgEngine
    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.models import create_model
    from fedml_tpu.utils.config import FedConfig
    from tests.test_fednas import tiny_data

    data = tiny_data(n_clients=3, bs=4, hw=8)
    cfg = FedConfig(client_num_in_total=3, client_num_per_round=3,
                    comm_round=1, epochs=1, batch_size=4, lr=0.1,
                    frequency_of_the_test=1)
    trainer = ClientTrainer(create_model("lr", 10), lr=0.1)
    e1 = FedAvgEngine(trainer, data, cfg, donate=False)
    e2 = FedAvgEngine(trainer, data, cfg, donate=False, pallas_agg=True)
    v0 = e1.init_variables()
    ids = e1.sampler.sample(0)
    cohort, _ = data.cohort(ids)
    r = jax.random.PRNGKey(7)
    va, _, _ = e1.round_fn(v0, e1.server_init(v0), cohort, r)
    vb, _, _ = e2.round_fn(v0, e2.server_init(v0), cohort, r)
    for a, b in zip(jax.tree.leaves(va), jax.tree.leaves(vb)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_kernel_path_choice_is_counted():
    """The implicit interpret/reference choice off-TPU is observable:
    ops_kernel_path_total{op, path} ticks at trace time."""
    from fedml_tpu import obs
    from fedml_tpu.ops.groupnorm import group_norm
    agg = obs.counter("ops_kernel_path_total", op="aggregate",
                      path="interpret")
    gn = obs.counter("ops_kernel_path_total", op="group_norm",
                     path="reference")
    a0, g0 = agg.value, gn.value
    weighted_mean_pallas(random_stack(jax.random.PRNGKey(4)),
                         jnp.ones(5))              # interpret=None: default
    group_norm(jnp.ones((8, 4, 4, 16)), jnp.ones(16), jnp.zeros(16), 8)
    assert agg.value == a0 + 1 and gn.value == g0 + 1
