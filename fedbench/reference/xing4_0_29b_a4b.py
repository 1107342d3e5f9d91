"""Xing4.0-29B-A4B (XingChen-AGI; four residual streams under manifold-constrained
hyper-connections around multi-head latent attention and bias-selected sigmoid-routed
experts beside a shared one) under low-rank adapters, in plain float32 jax.numpy.

From the model's public ``config.json`` (``model_type`` "xing4_0": hidden 3584, 40 layers,
32 heads, ``q_lora_rank`` 768, ``kv_lora_rank`` 512, ``qk_nope_head_dim`` 128,
``qk_rope_head_dim`` 64, ``v_head_dim`` 128, two leading dense layers of width 9,216, then
64 routed experts of width 1,024 - 4 a token, ``scoring_func`` sigmoid, ``topk_method``
noaux_tc with ``n_group`` = ``topk_group`` = 1, ``norm_topk_prob`` true,
``routed_scaling_factor`` 2 - beside 1 shared expert, ``hc_mult`` 4,
``hc_sinkhorn_iters`` 20, ``hc_eps`` 1e-6, ``mhc_h_res_clamp_min`` / ``_max`` -30 / 30,
``rms_norm_eps`` 1e-6, rotary theta 1e4 under YaRN (factor 64, ``beta_fast`` 32,
``beta_slow`` 1, original 4,096, ``mscale`` = ``mscale_all_dim`` = 1), vocabulary 131,072,
untied head) and, from memory of the papers (mHC, arXiv:2512.24880; hyper-connections,
arXiv:2409.19606; DeepSeek-V2 / -V3 for the attention and the router; no network here; each
item is under ``assumed`` in the configuration's file): the streams start as copies of the
embedding and end as their sum, the RMS over a token's n C values has no weight, ``hc_eps``
enters both denominators, columns are normalised before rows.

    RMSNorm_w(x) = x * rsqrt(mean(x^2) + 1e-6) * w
    streams:  X_0 = (E[x], E[x], E[x], E[x])                       X in R^{4 x C} a token
    hyper-connection around a sublayer F (attention, then MLP / experts; own maps each):
      z       = vec(X) * rsqrt(mean(vec(X)^2) + 1e-6)              over all 4 C, no weight
      Ht      = a[.] * (z Phi) + b        Phi [4 C, 24] = [pre 4 | post 4 | res 16 (row-major)]
                                          a = (a_pre, a_post, a_res), b [24] laid out alike
      H_pre   = sigmoid(Ht_pre);  H_post = 2 sigmoid(Ht_post)
      H_res   = M after:  M = exp(clip(Ht_res, -30, 30));  20 times:
                M = M / (colsum(M) + 1e-6);  M = M / (rowsum(M) + 1e-6)
      u       = sum_i H_pre[i] X[i]
      X'[i]   = sum_j H_res[i, j] X[j] + H_post[i] F(u)
    F_attn(u): a = RMSNorm_in(u)
      c_q = RMSNorm_q(a W_qa);  q = c_q W_qb -> 32 heads x (128 nope | 64 rope)
      [c_kv | k_r] = a W_kva -> (512 | 64);  c_kv = RMSNorm_kv(c_kv);  k_r ONE head for all 32
      [k_nope | v] = c_kv W_kvb -> 32 heads x (128 | 128)
      rotary (rotate-half) on q_rope and k_r with YaRN's inverse frequencies
      s = (q_nope . k_nope + q_rope . k_r) * 192^-0.5 * m^2,  m = 0.1 * 1 * ln 64 + 1
          (YaRN's mscale at mscale_all_dim 1, as the family's published code applies it)
      o = softmax_causal(s) v W_o
    F_mlp(u)  (a layer without a router) = (silu(f W1) * f W3) W2,  f = RMSNorm_post(u)
    F_moe(u):  f = RMSNorm_post(u);  s = sigmoid(f W_r) over 64;  sel = the 4 largest of
               s + b_e (ties: the lower index; b_e selects only)
               g = s[sel] / (sum s[sel] + 1e-6) * 2
               sum_{e in sel, e held} g_e (silu(f W1_e) * f W3_e) W2_e + (silu(f S1) * f S3) S2
    model:    logits = RMSNorm_out(sum_i X_L[i]) W_head
    adapter:  y = x W + (alpha / r) (x A) B  on W_qa, W_qb, W_kva, W_kvb, W_o

Departure: the multi-token-prediction module (``num_nextn_predict_layers`` 1) is not part of
this reference: `fedbench.reference.fedavg_round` takes one loss from one logits tensor.

The parameter tree is the program's, read by name: ``layer_<i>`` holds layer i's leaves - one
with ``router`` carries experts (``w1``/``w3``/``w2`` stacked over the experts HELD, the router
and ``expert_bias`` over all, ``s1``/``s3``/``s2`` the shared one), any other the dense MLP;
``hc_attn_phi|b|a`` and ``hc_mlp_phi|b|a`` the two hyper-connections' maps - and
``lora/layer_<i>/<matrix>_a|_b`` its adapters.  The head count, the routing's numbers, the first
expert held, the Sinkhorn constants, the rotary's constants and the adapters' alpha are not
shapes of the tree and are stated below; the number of streams and the head sizes follow from
them and the shapes.

Here the streams are ``[N, 4, T, C]`` (a stream is a whole array; the program lays them side
by side in the last dimension) and every map ``[., N, T]``.  A Python loop over the layers, each
a ``jax.checkpoint``.  Attention runs in blocks of ``HEAD_BLOCK`` heads, a scan over the blocks
with each a checkpoint of its own, so that the scores alive at a time are [block, T, T].  In an
expert layer a scan over ALL held experts, every expert applied to every token and the
unselected weighted 0, so nothing here orders, gathers or groups; the selection is a count of
what beats what.  Base leaves may arrive in bfloat16 (the program stores them so): each is cast
to float32 where it is used; casting changes no value.

Counting convention (``forward_flops``): matrix products x 2 - the hyper-connections' 24-wide
projections among them -, the attention products over the pairs key <= query (scores 192 deep,
values 128 deep), a token's ``TOP_K`` experts times the held share of the experts, the shared
expert; no elementwise work (the mixing, the Sinkhorn loop), no recomputation.  Training over a
frozen base (``train_flops``): forward and the gradient with respect to activations for every
frozen matrix (2 x forward), three for the adapters and for the attention products.
``core_flops`` / ``core_bytes`` are the fused attention core's alone (7 products; the
checkpoint's re-run is not counted), ``expert_flops`` / ``expert_bytes`` the held experts'
grouped products', ``hc_bytes`` the hyper-connections' mixing.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
N_HEADS = 32
HEAD_BLOCK = 2             # heads whose scores are alive together
TOP_K = 4                  # num_experts_per_tok
FIRST_HELD = 0             # id of the first expert of the stacked w1 / w3 / w2
SCALING = 2.0              # routed_scaling_factor
SINKHORN_ITERS = 20        # hc_sinkhorn_iters
HC_EPS = 1e-6
CLAMP = (-30.0, 30.0)      # mhc_h_res_clamp_min / _max
LORA_ALPHA = 32.0
ROPE = dict(theta=1e4, factor=64.0, beta_fast=32.0, beta_slow=1.0,
            original=4096, mscale=1.0, mscale_all_dim=1.0)
EPS = 1e-6
ADAPTERS = "lora"          # check.trainable names it
MATRICES = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")
SUBLAYERS = ("attn", "mlp")


def _mm(a, b):
    return jnp.matmul(a, b.astype(jnp.float32), precision=HI)


def _norm(x, w):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * w.astype(jnp.float32)


def _adapted(x, lp, ad, name, alpha):
    a, b = ad[name + "_a"], ad[name + "_b"]
    return _mm(x, lp[name]) + (alpha / a.shape[1]) * _mm(_mm(x, a), b)


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_angles(t, dim, theta, factor, beta_fast, beta_slow, original, **_):
    """[t, dim] rotation angles: position x inverse frequency, the frequencies repeated over
    both halves (rotate-half)."""
    i = np.arange(0, dim, 2, dtype=np.float64)
    extrapolated = theta ** (-i / dim)
    interpolated = extrapolated / factor
    # the dimension at which a frequency makes n turns over the original context
    where = lambda n: dim * math.log(original / (n * 2 * math.pi)) / (2 * math.log(theta))
    low, high = max(math.floor(where(beta_fast)), 0), min(math.ceil(where(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    inv = interpolated * ramp + extrapolated * (1.0 - ramp)
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    return np.concatenate([ang, ang], axis=-1)


def _rotate(x, cos, sin):
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + turned * sin


def _attention(a, lp, ad, alpha, cos, sin, scale, n_heads, head_block):
    n, t, _ = a.shape
    rank = lp["kv_norm"].shape[0]
    rope = lp["wkv_a"].shape[1] - rank
    nope = lp["wq_b"].shape[1] // n_heads - rope
    heads = lambda z: z.reshape(n, t, n_heads, -1).transpose(0, 2, 1, 3)   # [N, H, T, .]
    q = heads(_adapted(_norm(_adapted(a, lp, ad, "wq_a", alpha), lp["q_norm"]),
                       lp, ad, "wq_b", alpha))
    latent = _adapted(a, lp, ad, "wkv_a", alpha)
    c_kv, k_r = latent[..., :rank], latent[..., rank:]
    kv = heads(_adapted(_norm(c_kv, lp["kv_norm"]), lp, ad, "wkv_b", alpha))
    q = jnp.concatenate([q[..., :nope], _rotate(q[..., nope:], cos, sin)], axis=-1)
    k_r = _rotate(k_r, cos, sin)[:, None]                                # [N, 1, T, rope]
    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def block(q, kv):
        k = jnp.concatenate([kv[..., :nope],
                             jnp.broadcast_to(k_r, kv.shape[:3] + (rope,))], axis=-1)
        scores = jnp.matmul(q, k.transpose(0, 1, 3, 2), precision=HI) * scale
        scores = jnp.where(causal, scores, -jnp.inf)
        return jnp.matmul(jax.nn.softmax(scores, axis=-1), kv[..., nope:], precision=HI)

    # one block of heads after the other (a scan: the compiler may not run two at a time),
    # ``head_block`` dividing the head count
    blocks = lambda z: z.reshape(n, -1, head_block, t, z.shape[-1]).transpose(1, 0, 2, 3, 4)
    _, o = jax.lax.scan(lambda _, qkv: (None, block(*qkv)), None, (blocks(q), blocks(kv)))
    o = o.transpose(1, 0, 2, 3, 4).reshape(n, n_heads, t, -1)
    return _adapted(o.transpose(0, 2, 1, 3).reshape(n, t, -1), lp, ad, "wo", alpha)


def gate_weights(f, router, bias, top_k, scaling):
    """[tokens, experts] combine weights: ``scaling`` x the normalised sigmoid score of each
    of a token's ``top_k`` experts - chosen by score + bias -, 0 for every other."""
    s = jax.nn.sigmoid(_mm(f, router))
    biased = s + bias.astype(jnp.float32)
    e = jnp.arange(s.shape[-1])
    # expert j beats expert i: a larger biased score, or an equal one and j < i
    beats = (biased[:, None, :] > biased[:, :, None]) | (
        (biased[:, None, :] == biased[:, :, None]) & (e[None, None, :] < e[None, :, None]))
    g = jnp.where(jnp.sum(beats, axis=-1) < top_k, s, 0.0)
    return g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-6) * scaling


def experts(f, lp, top_k=TOP_K, first_held=FIRST_HELD, scaling=SCALING, shared=True):
    """The held experts' share of an expert layer's output for f [..., d], plus (``shared``)
    the shared expert's."""
    rows = f.reshape(-1, f.shape[-1])
    held = lp["w1"].shape[0]
    g = gate_weights(rows, lp["router"], lp["expert_bias"], top_k, scaling)
    g = g[:, first_held:first_held + held]
    mlp = lambda w1, w3, w2: _mm(jax.nn.silu(_mm(rows, w1)) * _mm(rows, w3), w2)

    @jax.checkpoint
    def one(expert):
        w1, w3, w2, ge = expert
        return ge[:, None] * mlp(w1, w3, w2)

    # the sum is carried outside the checkpoint, so the backward pass keeps no copy of it
    total, _ = jax.lax.scan(lambda total, expert: (total + one(expert), None),
                            jnp.zeros_like(rows), (lp["w1"], lp["w3"], lp["w2"], g.T))
    if shared:
        total = total + mlp(lp["s1"], lp["s3"], lp["s2"])
    return total.reshape(f.shape)


def sinkhorn(ht_res, iters=SINKHORN_ITERS, hc_eps=HC_EPS, clamp=CLAMP):
    """Ht_res [row, column, ...] -> M: exp of the clamped entries, then ``iters`` times every
    column divided by its sum + ``hc_eps``, then every row."""
    m = jnp.exp(jnp.clip(ht_res, clamp[0], clamp[1]))
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=0, keepdims=True) + hc_eps)
        m = m / (jnp.sum(m, axis=1, keepdims=True) + hc_eps)
    return m


def maps(X, phi, b, a, iters=SINKHORN_ITERS, hc_eps=HC_EPS, clamp=CLAMP):
    """(H_pre [n, N, T], H_post [n, N, T], H_res [n, n, N, T]) for the streams X
    [N, n, T, C]."""
    n, C = X.shape[1], X.shape[3]
    z = X * jax.lax.rsqrt(jnp.mean(X * X, axis=(1, 3), keepdims=True) + EPS)
    raw = sum(_mm(z[:, i], phi[i * C:(i + 1) * C]) for i in range(n))       # [N, T, n (n + 2)]
    a = a.astype(jnp.float32)
    gate = jnp.concatenate([jnp.broadcast_to(a[i], (width,))
                            for i, width in enumerate((n, n, n * n))])
    ht = jnp.transpose(gate * raw + b.astype(jnp.float32), (2, 0, 1))
    res = sinkhorn(ht[2 * n:].reshape((n, n) + ht.shape[1:]), iters, hc_eps, clamp)
    return jax.nn.sigmoid(ht[:n]), 2.0 * jax.nn.sigmoid(ht[n:2 * n]), res


def hyper_connected(X, phi, b, a, F, iters=SINKHORN_ITERS, hc_eps=HC_EPS, clamp=CLAMP):
    """X' [N, n, T, C] for the sublayer F under the hyper-connection (phi, b, a)."""
    n = X.shape[1]
    pre, post, res = maps(X, phi, b, a, iters, hc_eps, clamp)
    y = F(sum(pre[i][..., None] * X[:, i] for i in range(n)))
    return jnp.stack([post[i][..., None] * y
                      + sum(res[i, j][..., None] * X[:, j] for j in range(n))
                      for i in range(n)], axis=1)


def _layer(X, lp, ad, cos, sin, scale, n_heads, head_block, top_k, first_held, scaling, alpha,
           iters, hc_eps, clamp):
    hc = lambda s: (lp[f"hc_{s}_phi"], lp[f"hc_{s}_b"], lp[f"hc_{s}_a"])
    attention = lambda u: _attention(_norm(u, lp["in_norm"]), lp, ad, alpha, cos, sin, scale,
                                     n_heads, head_block)

    def mlp(u):
        f = _norm(u, lp["post_norm"])
        if "router" in lp:
            return experts(f, lp, top_k, first_held, scaling)
        return _mm(jax.nn.silu(_mm(f, lp["w1"])) * _mm(f, lp["w3"]), lp["w2"])

    X = hyper_connected(X, *hc("attn"), attention, iters, hc_eps, clamp)
    return hyper_connected(X, *hc("mlp"), mlp, iters, hc_eps, clamp)


def layer_names(params):
    return sorted((k for k in params if k.startswith("layer_")),
                  key=lambda k: int(k[len("layer_"):]))


def head_sizes(lp, n_heads=N_HEADS):
    """(nope, rope, value) head sizes of a layer's leaves."""
    rope = lp["wkv_a"].shape[1] - lp["kv_norm"].shape[0]
    nope = lp["wq_b"].shape[1] // n_heads - rope
    return nope, rope, lp["wkv_b"].shape[1] // n_heads - nope


def n_streams(params) -> int:
    return params[layer_names(params)[0]]["hc_attn_phi"].shape[0] // params["embed"].shape[1]


def forward(params, x, n_heads=N_HEADS, head_block=HEAD_BLOCK, top_k=TOP_K,
            first_held=FIRST_HELD, scaling=SCALING, alpha=LORA_ALPHA, rope=ROPE,
            iters=SINKHORN_ITERS, hc_eps=HC_EPS, clamp=CLAMP):
    """Logits [N, T, V] for tokens x [N, T]."""
    names = layer_names(params)
    nope, rope_dim, _ = head_sizes(params[names[0]], n_heads)
    ang = yarn_angles(x.shape[-1], rope_dim, **rope)
    ratio = _mscale(rope["factor"], rope["mscale"]) / _mscale(
        rope["factor"], rope["mscale_all_dim"])
    cos, sin = (jnp.asarray(ratio * f(ang), jnp.float32) for f in (np.cos, np.sin))
    scale = (nope + rope_dim) ** -0.5 * _mscale(rope["factor"], rope["mscale_all_dim"]) ** 2
    layer = jax.checkpoint(_layer, static_argnums=tuple(range(5, 15)))
    h = params["embed"][x.astype(jnp.int32)].astype(jnp.float32)
    X = jnp.stack([h] * n_streams(params), axis=1)
    for name in names:
        X = layer(X, params[name], params[ADAPTERS][name], cos, sin, scale, n_heads,
                  head_block, top_k, first_held, scaling, alpha, iters, hc_eps, clamp)
    return _mm(_norm(jnp.sum(X, axis=1), params["out_norm"]), params["head"])


def _size(a) -> int:
    n = 1
    for d in a.shape:
        n *= int(d)
    return n


def pairs(t: int) -> int:
    """(query, key) pairs of one head over t positions: key <= query."""
    return t * (t + 1) // 2


def _matrix_work(params, top_k=TOP_K, n_heads=N_HEADS):
    """(frozen, adapters, attention): matrix parameters a token meets in one forward pass,
    and the attention products' depth (scores + values) summed over heads and layers."""
    frozen = adapters = attention = 0
    for name in layer_names(params):
        lp, ad = params[name], params[ADAPTERS][name]
        frozen += sum(_size(lp[w]) for w in MATRICES)
        frozen += sum(_size(lp[f"hc_{s}_phi"]) for s in SUBLAYERS)
        adapters += sum(_size(a) for a in ad.values())
        nope, rope, value = head_sizes(lp, n_heads)
        attention += n_heads * (nope + rope + value)
        if "router" in lp:
            held, total = lp["w1"].shape[0], lp["router"].shape[1]
            one = sum(_size(lp[w]) for w in ("w1", "w3", "w2")) // held
            frozen += (_size(lp["router"]) + top_k * one * held / total
                       + sum(_size(lp[w]) for w in ("s1", "s3", "s2")))
        else:
            frozen += sum(_size(lp[w]) for w in ("w1", "w3", "w2"))
    return frozen + _size(params["head"]), adapters, attention


def forward_flops(params, x_shape, top_k=TOP_K, n_heads=N_HEADS) -> float:
    """FLOPs of one forward pass over ONE sequence of ``x_shape`` = (T,) tokens, by the
    convention of the module's docstring."""
    (t,) = x_shape
    frozen, adapters, attention = _matrix_work(params, top_k, n_heads)
    # q k^T and (softmax) v: 2 FLOPs a pair and a unit of depth
    return 2.0 * t * (frozen + adapters) + 2.0 * pairs(t) * attention


def train_flops(params, x_shape, top_k=TOP_K, n_heads=N_HEADS) -> float:
    """Forward and the gradient with respect to activations for the frozen matrices
    (2 x forward); the adapters and the attention products, which have two operands to
    differentiate, 3 x."""
    (t,) = x_shape
    frozen, adapters, attention = _matrix_work(params, top_k, n_heads)
    return (2 * 2.0 * t * frozen + 3 * 2.0 * t * adapters
            + 3 * 2.0 * pairs(t) * attention)


def step_bytes(params, local_itemsize: int) -> float:
    """Least bytes one local step of ONE client moves: every frozen leaf read once forward
    and once backward in the dtype it is stored in, and the adapters read forward and
    backward and read + written by the update."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    frozen = sum(_size(a) * jnp.dtype(a.dtype).itemsize for path, a in flat
                 if getattr(path[0], "key", None) != ADAPTERS)
    adapters = sum(_size(a) for a in jax.tree.leaves(params[ADAPTERS]))
    return 2.0 * frozen + 4.0 * adapters * local_itemsize


def core_flops(params, tokens: float, t: int, n_heads=N_HEADS) -> float:
    """FLOPs of the attention core (scores, softmax x values; no projection) to train on
    ``tokens`` tokens in sequences of ``t``: forward q k^T and p v, backward p again, dv, dp,
    dk and dq - four products as deep as the keys and three as deep as the values - over the
    pairs key <= query of every [t, t] square."""
    total = 0.0
    for name in layer_names(params):
        nope, rope, value = head_sizes(params[name], n_heads)
        total += n_heads * (4 * (nope + rope) + 3 * value)
    return 2.0 * tokens / t * pairs(t) * total


def core_bytes(params, tokens: float, itemsize: int, n_heads=N_HEADS) -> float:
    """Least bytes of the same: q, k, v and their rotary parts read and the output written
    forward (the one rotary key a token once); backward the same operands and the output's
    gradient read, five gradients written."""
    total = 0.0
    for name in layer_names(params):
        nope, rope, value = head_sizes(params[name], n_heads)
        operands = n_heads * (2 * nope + rope + value) + rope
        total += 3 * operands + 3 * n_heads * value
    return tokens * itemsize * total


def expert_flops(params, tokens: float, top_k=TOP_K) -> float:
    """FLOPs of the grouped products of every expert layer for ``tokens`` trained tokens:
    three products for each of a token's experts that is held here (the held share of
    ``top_k``), forward and with respect to activations."""
    total = 0.0
    for name in layer_names(params):
        lp = params[name]
        if "router" in lp:
            held, n = lp["w1"].shape[0], lp["router"].shape[1]
            one = sum(_size(lp[w]) for w in ("w1", "w3", "w2")) / held
            total += 2 * 2.0 * tokens * top_k * one * held / n
    return total


def expert_bytes(params, reads: float) -> float:
    """Least bytes of the same products: every held expert's three matrices read once
    forward and once backward, ``reads`` times (once per local step of each group of clients
    that the program trains side by side)."""
    held = sum(_size(params[name][w]) * jnp.dtype(params[name][w].dtype).itemsize
               for name in layer_names(params) if "router" in params[name]
               for w in ("w1", "w3", "w2"))
    return 2.0 * held * reads


def hc_bytes(params, tokens: float, itemsize: int) -> float:
    """Least bytes the hyper-connections' mixing of the held layers must move to train on
    ``tokens`` tokens with streams ``itemsize`` bytes wide (n streams of C; the maps
    themselves, n (n + 2) float32 values a token, are not counted).  A token of one sublayer:

      forward   read X (n C), write u (C), read F(u) (C), write X' (n C)         (2 n + 2) C
      backward  read dX' (n C) and write dF(u) = sum_i H_post[i] dX'[i] (C);
                read F(u) (C) for dH_post, X (n C) for dH_res and dH_pre;
                read du (C), which F's backward pass wrote;
                write dX = H_res^T dX' + H_pre du + the maps' part (n C)         (3 n + 3) C

    every operand once: the checkpoint's re-run of the forward pass, and the second and later
    readings of X, are in the time and not in the count.  The first sublayer of a stage that
    starts at the model's first layer writes no dX (the embedding is frozen, nothing upstream
    trains): n C less."""
    names = layer_names(params)
    n, C = n_streams(params), params["embed"].shape[1]
    per_token = len(names) * len(SUBLAYERS) * (5 * n + 5) * C
    if "layer_0" in names:
        per_token -= n * C
    return float(tokens) * itemsize * per_token
