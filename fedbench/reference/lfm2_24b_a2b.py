"""LFM2-24B-A2B (LiquidAI; gated short convolutions, grouped-query attention,
sparse experts) under low-rank adapters, in plain float32 jax.numpy.

From the model's public ``config.json`` (``model_type`` "lfm2_moe": hidden
2048, 40 layers of which 30 ``conv`` and 10 ``full_attention`` - attention at
2, 6, ..., 38 -, 32 query and 8 key/value heads of 64, 2 leading dense layers
of width 11,776, then 64 routed experts of width 1,536 with 4 a token, no
shared expert, ``use_expert_bias``, ``norm_topk_prob``,
``routed_scaling_factor`` 1, ``conv_L_cache`` 3, ``conv_bias`` false,
``norm_eps`` 1e-5, rotary theta 1e6, vocabulary 65,536) and, from memory of the
published modelling code (no network here; each item is under ``assumed`` in
the configuration's file): the gating of the convolution, the per-head q/k
norms, the sigmoid router whose bias selects only, the tied head.

    RMSNorm_w(x) = x * rsqrt(mean(x^2) + 1e-5) * w
    layer l:  a = RMSNorm_op(h)
      conv:   [B, C, X] = split3(a W_in);  u = B * X
              v_t = sum_{j=0..2} k_j * u_{t-2+j}     depthwise, causal, u_{<0} = 0
              o = (C * v) W_out
      attn:   q = a W_q [32 x 64], k = a W_k [8 x 64], v = a W_v [8 x 64]
              q, k <- RMSNorm over each head's 64; rotary (rotate-half) on q, k
              o = softmax_causal(q k^T / 8) v W_o     kv head g serves query heads 4g .. 4g+3
      h = h + o;  f = RMSNorm_ffn(h)
      dense:  m = (silu(f W1) * f W3) W2
      experts: s = sigmoid(f W_r);  sel = the 4 largest of s + b   (ties: the lower index)
               g = s[sel] / (sum s[sel] + 1e-6) * routed_scaling_factor
               m = sum_{e in sel, e held} g_e (silu(f W1_e) * f W3_e) W2_e
      h = h + m
    model:    h = E[x];  layers;  logits = RMSNorm_out(h) E^T
    adapter:  y = x W + (alpha / r) (x A) B  on W_in, W_out, W_q, W_k, W_v, W_o

The parameter tree is the program's, read by name: ``layer_<i>`` holds layer
i's leaves - a layer with ``in_proj`` is a convolution, one with ``wq``
attention; one with ``router`` carries experts (``w1``/``w3``/``w2`` stacked
over the experts HELD, the router over all), any other the dense MLP - and
``lora/layer_<i>/<matrix>_a|_b`` its adapters.  Head counts, the experts a
token visits, the first expert held and the adapters' alpha are not shapes of
the tree and are stated below.

A Python loop over the layers, each a ``jax.checkpoint``; in an expert layer a
scan over ALL held experts, every expert applied to every token and the
unselected weighted 0, so nothing here sorts, gathers or groups; the
selection is a count of the experts that beat each one, not a top-k.  Base
leaves may arrive in bfloat16 (the program stores them so): each is cast to
float32 where it is used, an expert at a time, so the float32 copy of an
expert layer (2.4 GB at the published widths) never exists ("computed in
blocks"): casting changes no value.

Counting convention (``forward_flops``): matrix products x 2, the causal half
of the two attention products, a token's ``TOP_K`` experts (times the held
share of the experts); no elementwise work (norms, gates, the depthwise
convolution's 3 taps, rotary, softmax, the router's top-k, the loss), no
recomputation.  Training over a frozen base (``train_flops``): forward and
the gradient with respect to activations for every frozen matrix (2 x
forward), three for the adapters and for the attention products.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
N_HEADS = 32
N_KV_HEADS = 8
TOP_K = 4                  # num_experts_per_tok
FIRST_HELD = 0             # id of the first expert of the stacked w1 / w3 / w2
SCALING = 1.0              # routed_scaling_factor
LORA_ALPHA = 32.0
ROPE_THETA = 1e6
EPS = 1e-5
ADAPTERS = "lora"          # check.trainable names it


def _mm(a, b):
    return jnp.matmul(a, b.astype(jnp.float32), precision=HI)


def _norm(x, w):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * w.astype(jnp.float32)


def _adapted(x, lp, ad, name, alpha):
    a, b = ad[name + "_a"], ad[name + "_b"]
    return _mm(x, lp[name]) + (alpha / a.shape[1]) * _mm(_mm(x, a), b)


def _rotate(x, cos, sin):
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + turned * sin


def _conv(a, lp, ad, alpha):
    t = a.shape[1]
    b, c, x = jnp.split(_adapted(a, lp, ad, "in_proj", alpha), 3, axis=-1)
    u = b * x
    kernel = lp["conv_kernel"].astype(jnp.float32)
    taps = kernel.shape[0]
    v = jnp.zeros_like(u)
    for j in range(taps):
        back = taps - 1 - j                     # u_{t - back}
        shifted = jnp.concatenate(
            [jnp.zeros_like(u[:, :back]), u[:, :t - back]], axis=1)
        v = v + kernel[j] * shifted
    return _adapted(c * v, lp, ad, "out_proj", alpha)


def _attention(a, lp, ad, alpha, cos, sin, n_heads, n_kv_heads):
    n, t, _ = a.shape
    split = lambda z, h: z.reshape(n, t, h, -1).transpose(0, 2, 1, 3)   # [N, H, T, hd]
    q = split(_adapted(a, lp, ad, "wq", alpha), n_heads)
    k = split(_adapted(a, lp, ad, "wk", alpha), n_kv_heads)
    v = split(_adapted(a, lp, ad, "wv", alpha), n_kv_heads)
    q = _rotate(_norm(q, lp["q_norm"]), cos, sin)
    k = _rotate(_norm(k, lp["k_norm"]), cos, sin)
    k, v = (jnp.repeat(z, n_heads // n_kv_heads, axis=1) for z in (k, v))
    scores = jnp.matmul(q, k.transpose(0, 1, 3, 2), precision=HI) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    o = jnp.matmul(jax.nn.softmax(scores, axis=-1), v, precision=HI)
    return _adapted(o.transpose(0, 2, 1, 3).reshape(n, t, -1), lp, ad, "wo", alpha)


def gate_weights(f, router, bias, top_k, scaling):
    """[tokens, experts] combine weights: the normalised score of each of a
    token's ``top_k`` experts, 0 for every other."""
    s = jax.nn.sigmoid(_mm(f, router))
    biased = s + bias.astype(jnp.float32)
    e = jnp.arange(s.shape[-1])
    # expert j beats expert i: a larger biased score, or an equal one and j < i
    beats = (biased[:, None, :] > biased[:, :, None]) | (
        (biased[:, None, :] == biased[:, :, None]) & (e[None, None, :] < e[None, :, None]))
    chosen = jnp.sum(beats, axis=-1) < top_k
    g = jnp.where(chosen, s, 0.0)
    return g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-6) * scaling


def experts(f, lp, top_k=TOP_K, first_held=FIRST_HELD, scaling=SCALING):
    """The held experts' share of an expert layer's output for f [..., d]."""
    rows = f.reshape(-1, f.shape[-1])
    g = gate_weights(rows, lp["router"], lp["expert_bias"], top_k, scaling)
    held = lp["w1"].shape[0]
    g = g[:, first_held:first_held + held]

    @jax.checkpoint
    def one(total, expert):
        w1, w3, w2, ge = expert
        m = _mm(jax.nn.silu(_mm(rows, w1)) * _mm(rows, w3), w2)
        return total + ge[:, None] * m, None

    total, _ = jax.lax.scan(one, jnp.zeros_like(rows),
                            (lp["w1"], lp["w3"], lp["w2"], g.T))
    return total.reshape(f.shape)


def _layer(h, lp, ad, cos, sin, n_heads, n_kv_heads, top_k, first_held, scaling,
           alpha):
    a = _norm(h, lp["op_norm"])
    if "in_proj" in lp:
        h = h + _conv(a, lp, ad, alpha)
    else:
        h = h + _attention(a, lp, ad, alpha, cos, sin, n_heads, n_kv_heads)
    f = _norm(h, lp["ffn_norm"])
    if "router" in lp:
        return h + experts(f, lp, top_k, first_held, scaling)
    return h + _mm(jax.nn.silu(_mm(f, lp["w1"])) * _mm(f, lp["w3"]), lp["w2"])


def layer_names(params):
    return sorted((k for k in params if k.startswith("layer_")),
                  key=lambda k: int(k[len("layer_"):]))


def forward(params, x, n_heads=N_HEADS, n_kv_heads=N_KV_HEADS, top_k=TOP_K,
            first_held=FIRST_HELD, scaling=SCALING, alpha=LORA_ALPHA):
    """Logits [N, T, V] for tokens x [N, T]."""
    names = layer_names(params)
    head_dim = next(params[n]["wq"].shape[1] for n in names
                    if "wq" in params[n]) // n_heads
    t = x.shape[-1]
    inv = 1.0 / ROPE_THETA ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    layer = jax.checkpoint(_layer, static_argnums=tuple(range(5, 11)))
    h = params["embed"][x.astype(jnp.int32)].astype(jnp.float32)
    for name in names:
        h = layer(h, params[name], params[ADAPTERS][name], cos, sin, n_heads,
                  n_kv_heads, top_k, first_held, scaling, alpha)
    return jnp.matmul(_norm(h, params["out_norm"]),
                      params["embed"].astype(jnp.float32).T, precision=HI)


def _size(a) -> int:
    n = 1
    for d in a.shape:
        n *= int(d)
    return n


def _matrix_work(params, top_k=TOP_K):
    """(frozen, adapters): matrix parameters a token meets in one forward
    pass; (attention width summed over the attention layers)."""
    frozen = adapters = attention = 0
    for name in layer_names(params):
        lp, ad = params[name], params[ADAPTERS][name]
        frozen += sum(_size(lp[w]) for w in ("in_proj", "out_proj", "wq", "wk",
                                             "wv", "wo") if w in lp)
        adapters += sum(_size(a) for a in ad.values())
        if "wq" in lp:
            attention += lp["wq"].shape[1]
        if "router" in lp:
            held, total = lp["w1"].shape[0], lp["router"].shape[1]
            one = sum(_size(lp[w]) for w in ("w1", "w3", "w2")) // held
            frozen += _size(lp["router"]) + top_k * one * held / total
        else:
            frozen += sum(_size(lp[w]) for w in ("w1", "w3", "w2"))
    return frozen + _size(params["embed"]), adapters, attention


def forward_flops(params, x_shape, top_k=TOP_K) -> float:
    """FLOPs of one forward pass over ONE sequence of ``x_shape`` = (T,)
    tokens, by the convention of the module's docstring."""
    (t,) = x_shape
    frozen, adapters, attention = _matrix_work(params, top_k)
    # q k^T and (softmax) v: 2 products x 2 x T^2 x width, the causal half
    return 2.0 * t * (frozen + adapters) + 2 * 2.0 * t * t * attention / 2


def train_flops(params, x_shape, top_k=TOP_K) -> float:
    """Forward and the gradient with respect to activations for the frozen
    matrices (2 x forward); the adapters and the attention products, which
    have two operands to differentiate, 3 x."""
    (t,) = x_shape
    frozen, adapters, attention = _matrix_work(params, top_k)
    return (2 * 2.0 * t * frozen + 3 * 2.0 * t * adapters
            + 3 * 2 * 2.0 * t * t * attention / 2)


def step_bytes(params, local_itemsize: int) -> float:
    """Least bytes one local step of ONE client moves: every frozen leaf read
    once forward and once backward in the dtype it is stored in, and the
    adapters read forward and backward and read + written by the update."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    frozen = sum(_size(a) * jnp.dtype(a.dtype).itemsize for path, a in flat
                 if getattr(path[0], "key", None) != ADAPTERS)
    adapters = sum(_size(a) for a in jax.tree.leaves(params[ADAPTERS]))
    return 2.0 * frozen + 4.0 * adapters * local_itemsize


def expert_flops(params, tokens: float, top_k=TOP_K) -> float:
    """FLOPs of the grouped products of every expert layer for ``tokens``
    trained tokens: three products for each of a token's experts, forward and
    with respect to activations."""
    total = 0.0
    for name in layer_names(params):
        lp = params[name]
        if "router" in lp:
            held, n = lp["w1"].shape[0], lp["router"].shape[1]
            one = sum(_size(lp[w]) for w in ("w1", "w3", "w2")) / held
            total += 2 * 2.0 * tokens * top_k * one * held / n
    return total


def expert_bytes(params, reads: float) -> float:
    """Least bytes of the same products: every held expert's three matrices
    read once forward and once backward, ``reads`` times (once per local step
    of each group of clients that the program trains side by side)."""
    held = sum(_size(params[name][w]) * jnp.dtype(params[name][w].dtype).itemsize
               for name in layer_names(params) if "router" in params[name]
               for w in ("w1", "w3", "w2"))
    return 2.0 * held * reads
