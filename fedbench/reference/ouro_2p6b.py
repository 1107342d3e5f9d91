"""Ouro-2.6B (ByteDance, a looped decoder LM), in plain float32 jax.numpy.

From the model's public ``config.json`` (``model_type`` "ouro": hidden 2048,
16 query = 16 key/value heads of 128, SwiGLU width 5632, vocabulary 49,152
untied, RMSNorm eps 1e-6, rotary theta 1e6, ``total_ut_steps`` 4) and, from
memory of the published modelling code and paper (no network here), sandwich
normalisation and the final norm inside the loop:

    RMSNorm_w(x) = x * rsqrt(mean(x^2) + eps) * w
    layer:  a = RMSNorm_1(h);  q, k, v = a W_q, a W_k, a W_v   (no bias)
            rotary embedding (rotate-half) on q and k
            o = softmax_causal(q k^T / sqrt(head_dim)) v W_o
            h = h + RMSNorm_2(o)
            f = RMSNorm_3(h);  m = (silu(f W_gate) * f W_up) W_down
            h = h + RMSNorm_4(m)
    model:  h = E[x]
            for t = 1 .. passes: { for l = 1 .. L: h = layer_l(h);
                                   h = RMSNorm_final(h);  s_t = h }
            logits_t = s_t W_head;  lambda_t = sigmoid(s_t w_g + b_g)
            p_1 = lambda_1;  p_t = lambda_t prod_{j<t} (1 - lambda_j);
            p_last = prod_{j<last} (1 - lambda_j)

the same L layers and the same final norm in every pass; the normed s_t is
exit t's hidden state and the next pass's input.  ``forward`` returns the last
pass's logits, which is what the published ``OuroForCausalLM`` returns as
``logits`` with ``early_exit_threshold`` 1 and what the cell trains on.

Departure, on purpose: the cell fine-tunes with next-token cross-entropy on the
last pass's logits, not with the pre-training objective (expected exit loss
with an entropy term); the exit gate then receives no gradient.

Python loops over passes and layers; each layer application is a
``jax.checkpoint`` so that the gradient of 24 applications at the published
widths fits one chip ("computed in blocks"): it changes no value.  The
parameter tree is the program's, read by name (``layers_*`` leaves are stacked
``[L, ...]``); head count and pass count are not shapes of the tree and are
stated below.

Counting convention (``forward_flops``): matrix products x 2; the causal half
of the two attention products; no recomputation, no elementwise work (norms,
rotary, softmax, SwiGLU's gate, the loss).  Per 1,024-token sequence at 6
layers x 4 passes: 2 x 1,024 x (24 x 51,380,224 + 100,663,296) = 2.73 TFLOP +
24 x 4.30 GFLOP of attention = 2.83 TFLOP forward, x 3 to train.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
N_HEADS = 16            # of 128: the head size is the projections' width / heads
N_PASSES = 4            # total_ut_steps
ROPE_THETA = 1e6
EPS = 1e-6
_MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def _norm(x, w):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * w


def _rotate(x, cos, sin):
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + turned * sin


def _layer(h, lp, cos, sin, n_heads):
    n, t, _ = h.shape
    mm = lambda a, b: jnp.matmul(a, b, precision=HI)
    a = _norm(h, lp["attn_norm"])
    split = lambda z: z.reshape(n, t, n_heads, -1).transpose(0, 2, 1, 3)
    q, k, v = (split(mm(a, lp[w])) for w in ("wq", "wk", "wv"))    # [N, H, T, hd]
    q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
    scores = mm(q, k.transpose(0, 1, 3, 2)) / jnp.sqrt(jnp.float32(q.shape[-1]))
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    o = mm(jax.nn.softmax(scores, axis=-1), v)
    o = mm(o.transpose(0, 2, 1, 3).reshape(n, t, -1), lp["wo"])
    h = h + _norm(o, lp["attn_post_norm"])
    f = _norm(h, lp["mlp_norm"])
    m = mm(jax.nn.silu(mm(f, lp["w_gate"])) * mm(f, lp["w_up"]), lp["w_down"])
    return h + _norm(m, lp["mlp_post_norm"])


def _exit_states(params, x, n_heads, n_passes):
    layers = {k[len("layers_"):]: v for k, v in params.items()
              if k.startswith("layers_")}
    n_layers, _, width = layers["wq"].shape
    head_dim = width // n_heads
    t = x.shape[-1]
    inv = 1.0 / ROPE_THETA ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                               / head_dim)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    layer = jax.checkpoint(_layer, static_argnums=(4,))
    h = params["embed"][x.astype(jnp.int32)]
    states = []
    for _ in range(n_passes):
        for l in range(n_layers):
            h = layer(h, {k: v[l] for k, v in layers.items()}, cos, sin, n_heads)
        h = _norm(h, params["final_norm"])
        states.append(h)
    return states


def forward(params, x, n_heads=N_HEADS, n_passes=N_PASSES):
    """Last pass's logits [N, T, V] for tokens x [N, T]."""
    s = _exit_states(params, x, n_heads, n_passes)[-1]
    return jnp.matmul(s, params["lm_head"], precision=HI)


def forward_all(params, x, n_heads=N_HEADS, n_passes=N_PASSES):
    """(every pass's logits [passes, N, T, V], exit distribution p
    [passes, N, T])."""
    states = _exit_states(params, x, n_heads, n_passes)
    logits = [jnp.matmul(s, params["lm_head"], precision=HI) for s in states]
    lam = [jax.nn.sigmoid(jnp.matmul(s, params["exit_gate_kernel"], precision=HI)
                          + params["exit_gate_bias"][0]) for s in states]
    p, stay = [], jnp.ones_like(lam[0])
    for lam_t in lam[:-1]:
        p.append(lam_t * stay)
        stay = stay * (1.0 - lam_t)
    p.append(stay)
    return jnp.stack(logits), jnp.stack(p)


def forward_flops(params, x_shape, n_passes=N_PASSES) -> float:
    """FLOPs of one forward pass over ONE sequence of ``x_shape`` = (T,)
    tokens, by the convention of the module's docstring."""
    (t,) = x_shape
    n_layers = params["layers_wq"].shape[0]
    per_layer = sum(int(params["layers_" + w].shape[1] * params["layers_" + w].shape[2])
                    for w in _MATRICES)
    head = int(params["lm_head"].shape[0] * params["lm_head"].shape[1])
    attn_width = params["layers_wq"].shape[2]                # heads x head size
    # q k^T and (softmax) v: 2 products x 2 x T^2 x width, the causal half
    attention = 2 * 2.0 * t * t * attn_width / 2
    return (2.0 * t * (n_passes * n_layers * per_layer + head)
            + n_passes * n_layers * attention)


def train_flops(params, x_shape) -> float:
    """Forward, the gradient with respect to activations and the gradient with
    respect to weights: 3 x forward; the recomputed forward of each layer
    application is not counted."""
    return 3.0 * forward_flops(params, x_shape)


def step_bytes(params, local_itemsize: int) -> float:
    """Least bytes one local step moves: the stored layers are read once per
    pass forward and once per pass backward, the embedding's rows and the
    head once each way, and the update reads and writes every parameter."""
    layers = sum(int(jnp.size(v)) for k, v in params.items()
                 if k.startswith("layers_"))
    rest = sum(int(jnp.size(v)) for k, v in params.items()
               if not k.startswith("layers_"))
    return float(local_itemsize) * ((2 * N_PASSES + 2) * layers + 4 * rest)
