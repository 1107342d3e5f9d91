"""Character/word LSTMs (reference fedml_api/model/nlp/rnn.py).

RNNOriginalFedAvg (rnn.py:4-36): embed(vocab 90 -> 8) + 2xLSTM(256) + dense,
used for shakespeare / fed_shakespeare next-char prediction.
RNNStackOverflow (rnn.py:39-70): embed(10004 -> 96) + LSTM(670) + dense(96)
+ dense(vocab), used for stackoverflow next-word prediction.

Both return per-position logits [B, T, vocab]; the loss masks padding.

The LSTM layer is `lstm_sequence`: one function over the whole sequence
with a hand-written VJP.  The kernels are constant over the T steps, so
every product with one is taken ONCE per sequence batch, outside the time
loop: forward, the input projection of all T steps; backward, the two
weight gradients, the bias gradient and dx, each one product over the
T*B rows of the stacked gate cotangents.  The loops keep what the
recurrence needs: `h @ W_h` forward, `dgates @ W_h.T` backward.  Autodiff
of a scan over a cell (`nn.RNN`) instead carries every kernel's cotangent
through the backward loop - a rank-B product and a read-modify-write of
the whole kernel gradient at every step, half of a StackOverflow round on
the TPU (PERF.md section 6, PR 27).
"""
from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

GATES = "ifgo"         # the order of the gates along the fused 4H axis


def _dot(a, w):
    """`a @ w` in the promoted dtype of the two, as flax's layers multiply:
    a float32 state against bf16 kernels is a float32 product."""
    dtype = jnp.promote_types(a.dtype, w.dtype)
    return jnp.dot(a.astype(dtype), w.astype(dtype))


@jax.custom_vjp
def lstm_sequence(w_i, w_h, b, x, carry):
    """The hidden states [..., T, H] of an LSTM over `x` [..., T, E] from
    `carry` = (c, h), each [..., H].  `w_i` [E, 4H], `w_h` [H, 4H] and `b`
    [4H] hold the gates i, f, g, o side by side:

        i, f, g, o = split(x_t @ w_i + (h @ w_h + b))
        c = sigmoid(f) * c + sigmoid(i) * tanh(g);  h = sigmoid(o) * tanh(c)

    flax's `OptimizedLSTMCell` step for step, dtypes included: the state
    and the gates are as wide as the carry (float32 from `_lstm`) whatever
    the kernels' dtype."""
    return _lstm_forward(w_i, w_h, b, x, carry)[0]


def _lstm_forward(w_i, w_h, b, x, carry):
    x_t = jnp.moveaxis(x, -2, 0)                    # time-major: [T, ..., E]
    xi = _dot(x_t, w_i)                             # all T steps: [T, ..., 4H]

    def step(carry, xi_t):
        c, h = carry
        i, f, g, o = jnp.split(xi_t + (_dot(h, w_h) + b), 4, axis=-1)
        i, f, g, o = (jax.nn.sigmoid(i), jax.nn.sigmoid(f), jnp.tanh(g),
                      jax.nn.sigmoid(o))
        new_c = f * c + i * g
        tanh_c = jnp.tanh(new_c)
        new_h = o * tanh_c
        return (new_c, new_h), (new_h, (c, h, i, f, g, o, tanh_c))

    _, (hs, (c_prev, h_prev, *gates)) = lax.scan(step, carry, xi)
    return jnp.moveaxis(hs, 0, -2), (w_i, w_h, b, x_t, c_prev, h_prev, gates)


def _lstm_backward(saved, d_hs):
    w_i, w_h, b, x_t, c_prev, h_prev, gates = saved
    w_h_t = w_h.T

    def step(carry, at_t):
        """Carries (dc, dh) only; the step's gate cotangents are stacked,
        not contracted with anything loop-invariant."""
        dc, dh = carry
        d_out, c, i, f, g, o, tanh_c = at_t
        dh = dh + d_out
        dc = dc + dh * o * (1 - tanh_c * tanh_c)
        dgates = jnp.concatenate(
            [dc * g * i * (1 - i), dc * c * f * (1 - f),
             dc * i * (1 - g * g), dh * tanh_c * o * (1 - o)], axis=-1)
        return (dc * f, _dot(dgates, w_h_t)), dgates

    # zeros_like: of c's shard_map variance too, as the scan's carry must be
    zero = jnp.zeros_like(c_prev[0])
    d_carry, dgates = lax.scan(
        step, (zero, zero),
        (jnp.moveaxis(d_hs, -2, 0), c_prev, *gates), reverse=True)

    # the loop-invariant products, once each over the T*B rows; the sums
    # run in the product's float32 accumulator and are rounded once
    rows = tuple(range(dgates.ndim - 1))
    in_dtype = jnp.promote_types(x_t.dtype, w_i.dtype)
    d_xi = dgates.astype(in_dtype)                  # cotangent of x @ w_i
    d_w_i = jnp.tensordot(
        x_t.astype(in_dtype), d_xi, (rows, rows),
        preferred_element_type=jnp.promote_types(in_dtype, jnp.float32))
    d_w_h = jnp.tensordot(h_prev, dgates, (rows, rows))
    d_x = jnp.moveaxis(_dot(d_xi, w_i.T), 0, -2)
    return (d_w_i.astype(w_i.dtype), d_w_h.astype(w_h.dtype),
            dgates.sum(rows).astype(b.dtype), d_x.astype(x_t.dtype), d_carry)


lstm_sequence.defvjp(_lstm_forward, _lstm_backward)


def fused_kernels(cell_params):
    """(w_i, w_h, b) of `lstm_sequence` from an `OptimizedLSTMCell`'s
    twelve leaves."""
    def side_by_side(prefix, leaf):
        return jnp.concatenate(
            [cell_params[prefix + k][leaf] for k in GATES], axis=-1)
    return (side_by_side("i", "kernel"), side_by_side("h", "kernel"),
            side_by_side("h", "bias"))


def _lstm(hidden_size: int, h):
    """One LSTM layer over `h` [..., T, E] from a zero carry.

    flax's `OptimizedLSTMCell` declares the parameters (the tree
    `OptimizedLSTMCell_k/{ii,if,ig,io}/kernel`, `{hi,hf,hg,ho}/{kernel,
    bias}` that checkpoints, wire formats and the benchmark's reference
    read, from its initialisers on its RNG paths); `lstm_sequence` runs
    them.

    The carry is the cell's float32 zeros plus `0 * sum(0 * h)`: fresh
    zeros are replicated-typed under shard_map, while the scan body's
    carry output varies with the (client-sharded) inputs - a lax.scan
    carry-type mismatch.  The bump promotes the zeros to h's variance
    without changing a bit (same invariant as
    core/pytree.tree_vary_noop)."""
    cell = nn.OptimizedLSTMCell(hidden_size)
    carry = cell.initialize_carry(jax.random.PRNGKey(0),
                                  h.shape[:-2] + h.shape[-1:])
    bump = jnp.sum(h * 0)                       # 0.0, but input-varying
    carry = jax.tree.map(lambda a: a + bump.astype(a.dtype), carry)
    if cell.is_initializing():
        cell(carry, h[..., 0, :])               # declares the twelve leaves
    return lstm_sequence(*fused_kernels(cell.variables["params"]), h, carry)


class RNNOriginalFedAvg(nn.Module):
    """`last_only=True` is the LEAF-shakespeare mode: one next-char logit
    from the final hidden state (reference rnn.py:30-33); False is the
    fed_shakespeare per-position mode (rnn.py:34-36)."""
    vocab_size: int = 90
    embedding_dim: int = 8
    hidden_size: int = 256
    last_only: bool = False

    @nn.compact
    def __call__(self, x, train: bool = False):
        h = nn.Embed(self.vocab_size, self.embedding_dim)(x.astype(jnp.int32))
        h = _lstm(self.hidden_size, h)
        h = _lstm(self.hidden_size, h)
        if self.last_only:
            h = h[:, -1]
        return nn.Dense(self.vocab_size)(h)


class RNNStackOverflow(nn.Module):
    vocab_size: int = 10004        # 10000 words + pad/bos/eos/oov
    embedding_dim: int = 96
    hidden_size: int = 670

    @nn.compact
    def __call__(self, x, train: bool = False):
        h = nn.Embed(self.vocab_size, self.embedding_dim)(x.astype(jnp.int32))
        h = _lstm(self.hidden_size, h)
        h = nn.Dense(self.embedding_dim)(h)
        return nn.Dense(self.vocab_size)(h)
