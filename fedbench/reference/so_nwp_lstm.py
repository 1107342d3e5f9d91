"""The StackOverflow next-word model of Reddi et al. 2021, in plain jax.numpy:
embedding (vocab -> 96), one LSTM layer (670 units), a dense projection to 96
and a dense layer to the vocabulary; logits at every position.

The LSTM cell is Hochreiter & Schmidhuber's with a forget gate:
    i, f, o = sigmoid(W_i* x + W_h* h + b*),  g = tanh(W_ig x + W_hg h + b_g)
    c' = f * c + i * g,   h' = o * tanh(c')
zero initial state, one bias per gate (on the recurrent half, as the
program's flax OptimizedLSTMCell keeps it).  Departure: none known.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def forward(params, x):
    cell = params["OptimizedLSTMCell_0"]
    emb = params["Embed_0"]["embedding"][x.astype(jnp.int32)]      # [N, T, E]
    n, t, _ = emb.shape
    hidden = cell["hi"]["kernel"].shape[0]
    h = c = jnp.zeros((n, hidden), jnp.float32)

    def gate(name, xt, h):
        return (jnp.dot(xt, cell["i" + name]["kernel"], precision=HI)
                + jnp.dot(h, cell["h" + name]["kernel"], precision=HI)
                + cell["h" + name]["bias"])

    outs = []
    for step in range(t):
        xt = emb[:, step]
        i, f, o = (jax.nn.sigmoid(gate(k, xt, h)) for k in "ifo")
        g = jnp.tanh(gate("g", xt, h))
        c = f * c + i * g
        h = o * jnp.tanh(c)
        outs.append(h)
    hs = jnp.stack(outs, axis=1)                                    # [N, T, H]
    d0, d1 = params["Dense_0"], params["Dense_1"]
    z = jnp.dot(hs, d0["kernel"], precision=HI) + d0["bias"]
    return jnp.dot(z, d1["kernel"], precision=HI) + d1["bias"]


def forward_flops(params, x_shape) -> float:
    """Multiply-adds x 2 of the matrix products for ONE sequence of
    ``x_shape`` = (T,) tokens; the embedding look-up, the gates' elementwise
    work and the softmax are not counted."""
    (t,) = x_shape
    cell = params["OptimizedLSTMCell_0"]
    e, h = cell["ii"]["kernel"].shape
    per_step = 2.0 * 4 * (e * h + h * h)
    for name in ("Dense_0", "Dense_1"):
        d_in, d_out = params[name]["kernel"].shape
        per_step += 2.0 * d_in * d_out
    return t * per_step
