"""What `fedml_tpu.ops` exports as a package: the stacked-tree <-> padded
float32 matrix pair, and the rule every kernel op shares — the path it took
is counted once, when the program is traced.  Each kernel's values are its
own file's (tests/test_causal_attention.py, tests/test_rotary_op.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu import obs, ops
from fedml_tpu.models.looped_lm import rotary_tables


def random_stack(rng, C=5):
    k1, k2, k3 = jax.random.split(rng, 3)
    return {
        "dense": {"kernel": jax.random.normal(k1, (C, 7, 13)),
                  "bias": jax.random.normal(k2, (C, 13))},
        "out": {"kernel": jax.random.normal(k3, (C, 13, 3))},
    }


def test_flatten_roundtrip():
    stack = random_stack(jax.random.PRNGKey(0))
    flat, spec = ops.flatten_stacked_tree(stack)
    assert flat.shape[0] == 5 and flat.shape[1] % 512 == 0
    one = jax.tree.map(lambda x: x[2], stack)
    back = ops.unflatten_to_tree(flat[2], spec)
    for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(back)):
        np.testing.assert_allclose(a, b, rtol=1e-6)


def _attention_call():
    q = jnp.ones((1, 256, 4, 64), jnp.bfloat16)
    kv = jnp.ones((1, 256, 2, 64), jnp.bfloat16)
    return ops.causal_attention, (q, kv, kv)


def _rotary_call():
    return ops.rotate_half, (jnp.ones((1, 256, 4, 128), jnp.bfloat16),
                             *rotary_tables(256, 128, 5e4))


@pytest.mark.parametrize("op, call", [("causal_attention", _attention_call),
                                      ("rotate_half", _rotary_call)])
def test_kernel_path_choice_is_counted(op, call):
    """`ops_kernel_path_total{op, path}` ticks when a program is TRACED, not
    when it runs: a jitted step that fits the kernel counts "pallas" once
    however often it is called, and the registry's exposition carries the
    series under that name and those labels."""
    fn, args = call()
    counted = obs.counter("ops_kernel_path_total", op=op, path="pallas")
    plain = obs.counter("ops_kernel_path_total", op=op, path="reference")
    before = counted.value, plain.value
    step = jax.jit(lambda *a: fn(*a))       # a new function: traced here
    jax.block_until_ready([step(*args), step(*args)])
    assert (counted.value, plain.value) == (before[0] + 1, before[1])
    assert (f'ops_kernel_path_total{{op="{op}",path="pallas"}}'
            in obs.registry().to_prometheus())
