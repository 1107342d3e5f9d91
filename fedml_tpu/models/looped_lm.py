"""Looped decoder LM — one stack of decoder layers run several times.

The family of Ouro (ByteDance, "Scaling Latent Reasoning via Looped
Language Models"): ``n_layers`` stored layers are applied ``n_passes``
times, the SAME weights in every pass, so depth of computation is
``n_layers x n_passes`` while the parameters are ``n_layers``' worth.
Every width, the layer count and the pass count are constructor
arguments; a benchmark configuration carries a published model's.

    RMSNorm_w(x) = x * rsqrt(mean(x^2) + eps) * w
    layer:  a = RMSNorm_1(h);  q, k, v = a W_q, a W_k, a W_v  (no bias)
            rotary embedding (rotate-half) on q, k
            o = softmax_causal(q k^T / sqrt(head_dim)) v W_o
            h = h + RMSNorm_2(o)                  (sandwich norms: one
            f = RMSNorm_3(h)                       before AND one after
            m = (silu(f W_gate) * f W_up) W_down   each sub-block)
            h = h + RMSNorm_4(m)
    model:  h = E[x]
            for t in 1..n_passes:
                for l in 1..n_layers: h = layer_l(h)
                h = RMSNorm_final(h);  s_t = h
            logits_t = s_t W_head                  (untied head)
            lambda_t = sigmoid(s_t w_g + b_g)      (exit gate)
            p_1 = lambda_1, p_t = lambda_t prod_{j<t}(1 - lambda_j),
            p_last = prod_{j<last}(1 - lambda_j)

``__call__`` returns ``logits`` of the last pass, [B, T, vocab] in
float32 — the trainer's contract, and what the published model returns
with ``early_exit_threshold`` 1.  ``all_exits`` returns every pass's
logits and the exit distribution.

How it is built for a chip:

* the layers' parameters are ONE stacked tree (``[n_layers, ...]``
  leaves) that an inner ``lax.scan`` walks; the passes are an outer
  ``lax.scan`` over the same tree.  The program holds one layer body,
  whatever the depth (192 unrolled blocks at 48 x 4 would be minutes
  of compilation), and the weight gradients of the passes accumulate in
  the outer scan's backward carry.
* every layer application is a ``jax.checkpoint`` that saves nothing
  but its input: backward keeps ``n_passes x n_layers`` residual
  streams ([B, T, d] in the compute dtype) and recomputes the layer's
  forward — its four norms, seven matrix products and the attention
  core — once.  That is a third more matrix work than the count that
  omits recomputation, for activations that stay at tens of megabytes
  per application (the two [B, T, d_ff] gate products would be the
  largest).
* the attention core, ``softmax_causal(q k^T / sqrt(head_dim)) v``, is
  ``ops/attention.py::causal_attention``: in a program lowered for a TPU,
  with T a multiple of 128 and heads of 64 or 128, fused kernels that
  hold the [B, H, T, T] scores one tile at a time in fast memory — they
  never reach HBM, forward, recomputed or backward, and the tiles above
  the diagonal are not computed; the backward pass keeps the output and
  the rows' log-sum-exp ([B, H, T] float32) in place of the scores.
  Anywhere else (a CPU, a small test shape) it is the einsum, mask,
  softmax, einsum that used to stand here.
* matrix products run in the parameters' dtype (the trainer casts them
  to its ``train_dtype``) with float32 accumulation; norms, rotary
  angles, softmax, the gate and the returned logits are float32.
"""
from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from fedml_tpu.obs import scopes
from fedml_tpu.ops.attention import causal_attention

_LAYER_NORMS = ("attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm")


def rms_norm(x, w, eps):
    """float32 RMSNorm; returns ``x``'s dtype."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def rotary_tables(seq_len: int, head_dim: int, theta: float):
    """cos, sin [T, head_dim] in float32 (rotate-half layout: the
    frequencies repeated over both halves)."""
    inv = 1.0 / theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                          / head_dim)
    ang = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rotary(x, cos, sin):
    """x [B, T, H, hd] -> rotated, same dtype; the rotation in float32."""
    x32 = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x32[..., half:], x32[..., :half]], axis=-1)
    return (x32 * cos[:, None, :] + rot * sin[:, None, :]).astype(x.dtype)


def _dot(x, w):
    """x [..., a] @ w [a, b] in their dtype, accumulated in float32."""
    return jnp.einsum("...a,ab->...b", x, w,
                      preferred_element_type=jnp.float32)


def decoder_layer(h, lp, cos, sin, n_heads: int, eps: float):
    """One layer of the equations above on h [B, T, d]; ``lp`` holds one
    layer's leaves of the stacked tree."""
    B, T, d = h.shape
    dt = h.dtype
    with jax.named_scope(scopes.FED_ATTENTION):
        a = rms_norm(h, lp["attn_norm"], eps)
        heads = lambda w: _dot(a, w).astype(dt).reshape(B, T, n_heads, -1)
        q, k, v = heads(lp["wq"]), heads(lp["wk"]), heads(lp["wv"])
        q, k = apply_rotary(q, cos, sin), apply_rotary(k, cos, sin)
        o = causal_attention(q, k, v)
        o = _dot(o.reshape(B, T, -1), lp["wo"]).astype(dt)
        h = h + rms_norm(o, lp["attn_post_norm"], eps)
    with jax.named_scope(scopes.FED_MLP):
        f = rms_norm(h, lp["mlp_norm"], eps)
        g = jax.nn.silu(_dot(f, lp["w_gate"])) * _dot(f, lp["w_up"])
        m = _dot(g.astype(dt), lp["w_down"]).astype(dt)
        h = h + rms_norm(m, lp["mlp_post_norm"], eps)
    return h


def exit_distribution(lam):
    """lam [n_passes, ...] gate values -> p [n_passes, ...], which sums
    to 1 over the passes: the last pass takes what is left."""
    stay = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]], axis=0)
    return jnp.concatenate([(lam * before)[:-1], before[-1:]], axis=0)


class LoopedDecoderLM(nn.Module):
    """tokens [B, T] int -> float32 logits [B, T, vocab] of the last pass."""
    vocab_size: int
    d_model: int = 64
    n_heads: int = 4
    head_dim: int = 16
    d_ff: int = 128
    n_layers: int = 2
    n_passes: int = 2
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    init_std: float = 0.02
    # the layers and passes as Python loops with no rematerialisation:
    # the same function, for the test that the scans change nothing
    unrolled: bool = False

    # the trainer opens this scope around its loss: the vocabulary
    # head's softmax belongs to the head (obs/scopes.py)
    loss_scope = scopes.FED_LM_HEAD

    def setup(self):
        normal = nn.initializers.normal(self.init_std)
        L, d, a, f = (self.n_layers, self.d_model,
                      self.n_heads * self.head_dim, self.d_ff)
        shapes = {"wq": (L, d, a), "wk": (L, d, a), "wv": (L, d, a),
                  "wo": (L, a, d), "w_gate": (L, d, f), "w_up": (L, d, f),
                  "w_down": (L, f, d)}
        self.embed = self.param("embed", normal, (self.vocab_size, d))
        layers = {name: self.param(f"layers_{name}", normal, shape)
                  for name, shape in shapes.items()}
        layers.update({name: self.param(f"layers_{name}", nn.initializers.ones,
                                        (L, d)) for name in _LAYER_NORMS})
        self.layers = layers
        self.final_norm = self.param("final_norm", nn.initializers.ones, (d,))
        self.lm_head = self.param("lm_head", normal, (d, self.vocab_size))
        self.exit_gate_kernel = self.param("exit_gate_kernel", normal, (d,))
        self.exit_gate_bias = self.param("exit_gate_bias",
                                         nn.initializers.zeros, (1,))

    def _exit_states(self, x):
        """(s_last [B, T, d], every s_t [n_passes, B, T, d])."""
        h = self.embed[x.astype(jnp.int32)]
        cos, sin = rotary_tables(x.shape[-1], self.head_dim, self.rope_theta)

        def layer(h, lp):
            return decoder_layer(h, lp, cos, sin, self.n_heads, self.norm_eps)

        if self.unrolled:
            states = []
            for _ in range(self.n_passes):
                for l in range(self.n_layers):
                    h = layer(h, jax.tree.map(lambda a: a[l], self.layers))
                h = rms_norm(h, self.final_norm, self.norm_eps)
                states.append(h)
            return h, jnp.stack(states)
        remat = jax.checkpoint(layer)

        def one_pass(h, _):
            h, _ = jax.lax.scan(lambda h, lp: (remat(h, lp), None),
                                h, self.layers)
            h = rms_norm(h, self.final_norm, self.norm_eps)
            return h, h

        return jax.lax.scan(one_pass, h, None, length=self.n_passes)

    def _head(self, s):
        with jax.named_scope(scopes.FED_LM_HEAD):
            return _dot(s, self.lm_head)

    def __call__(self, x, train: bool = False):
        return self._head(self._exit_states(x)[0])

    def all_exits(self, x):
        """(logits [n_passes, B, T, vocab], p [n_passes, B, T]): every
        pass's logits and the probability of leaving after it."""
        states = self._exit_states(x)[1]
        lam = jax.nn.sigmoid(
            jnp.einsum("pbtd,d->pbt", states.astype(jnp.float32),
                       self.exit_gate_kernel.astype(jnp.float32))
            + self.exit_gate_bias.astype(jnp.float32))
        return self._head(states), exit_distribution(lam)
