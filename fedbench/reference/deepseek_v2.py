"""DeepSeek-V2 (deepseek-ai; multi-head latent attention, group-limited sparse
experts beside shared ones) under low-rank adapters, in plain float32 jax.numpy.

From the model's public ``config.json`` (``model_type`` "deepseek_v2": hidden
5120, 60 layers, 128 heads, ``q_lora_rank`` 1536, ``kv_lora_rank`` 512,
``qk_nope_head_dim`` 128, ``qk_rope_head_dim`` 64, ``v_head_dim`` 128, one
leading dense layer of width 12,288, then 160 routed experts of width 1,536 in
8 groups - 6 a token out of its 3 best groups, softmax scores,
``norm_topk_prob`` false, ``routed_scaling_factor`` 16 - beside 2 shared
experts, ``rms_norm_eps`` 1e-6, rotary theta 1e4 under YaRN (factor 40,
``beta_fast`` 32, ``beta_slow`` 1, original 4,096, ``mscale`` =
``mscale_all_dim`` = 0.707), vocabulary 102,400, untied head) and, from memory
of the published modelling code (no network here; each item is under
``assumed`` in the configuration's file): pre-norm residuals, the norms on both
latents, the linear-ramp blend of YaRN's frequencies, the softmax scale's
``mscale^2``, groups of consecutive experts scored by their best expert, the
shared experts as one gated MLP of twice the expert width.

    RMSNorm_w(x) = x * rsqrt(mean(x^2) + 1e-6) * w
    layer l:  a = RMSNorm_in(h)
      c_q = RMSNorm_q(a W_qa);  q = c_q W_qb -> 128 heads x (128 nope | 64 rope)
      [c_kv | k_r] = a W_kva -> (512 | 64);  c_kv = RMSNorm_kv(c_kv);  k_r is
                     ONE head, read by all 128
      [k_nope | v] = c_kv W_kvb -> 128 heads x (128 | 128)
      rotary (rotate-half) on q_rope and k_r with YaRN's inverse frequencies
      s = (q_nope . k_nope + q_rope . k_r) * 192^-0.5 * m^2,
          m = 0.1 * 0.707 * ln 40 + 1
      o = softmax_causal(s) v W_o;  h = h + o;  f = RMSNorm_post(h)
      dense (a layer without a router):  m = (silu(f W1) * f W3) W2
      experts: p = softmax(f W_r) over 160
               a group's score = the largest p of its 20 consecutive experts
               the 3 best groups are kept (ties: the lower index), p = 0 elsewhere
               sel = the 6 largest of what is left (ties: the lower index)
               m = sum_{e in sel, e held} 16 p_e (silu(f W1_e) * f W3_e) W2_e
                   + (silu(f S1) * f S3) S2
      h = h + m
    model:    h = E[x];  layers;  logits = RMSNorm_out(h) W_head
    adapter:  y = x W + (alpha / r) (x A) B  on W_qa, W_qb, W_kva, W_kvb, W_o

The parameter tree is the program's, read by name: ``layer_<i>`` holds layer
i's leaves - one with ``router`` carries experts (``w1``/``w3``/``w2`` stacked
over the experts HELD, the router over all, ``s1``/``s3``/``s2`` the shared
ones), any other the dense MLP - and ``lora/layer_<i>/<matrix>_a|_b`` its
adapters.  The head count, the routing's numbers, the first expert held, the
rotary's constants and the adapters' alpha are not shapes of the tree and are
stated below; the three head sizes follow from them and the shapes.

A Python loop over the layers, each a ``jax.checkpoint``.  Attention runs in
blocks of ``HEAD_BLOCK`` heads, a scan over the blocks with each a checkpoint of
its own, so that the scores alive at a time are [block, T, T] and not
[128, T, T] (8.6 GB at T = 4,096; a Python loop over the blocks let the compiler
keep several blocks' scores at once, and the step did not fit the chip); the
rotary key is repeated for a block's heads.  In an
expert layer a scan over ALL held experts, every expert applied to every token
and the unselected weighted 0, so nothing here sorts, gathers or groups; the
selection - of groups and of experts - is a count of what beats what, not a
top-k.  Base leaves may arrive in bfloat16 (the program stores them so): each
is cast to float32 where it is used ("computed in blocks"): casting changes no
value.

Counting convention (``forward_flops``): matrix products x 2, the causal half
of the two attention products (scores 192 deep, values 128 deep), a token's
``TOP_K`` experts times the held share of the experts, the shared experts; no
elementwise work, no recomputation.  Training over a frozen base
(``train_flops``): forward and the gradient with respect to activations for
every frozen matrix (2 x forward), three for the adapters and for the
attention products.  ``core_flops`` / ``core_bytes`` are the fused attention
core's alone, forward and backward as the mathematics has them (7 products; the
checkpoint's re-run of the forward is not counted); ``expert_flops`` /
``expert_bytes`` the held experts' grouped products'.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
N_HEADS = 128
HEAD_BLOCK = 8             # heads whose scores are alive together
TOP_K = 6                  # num_experts_per_tok
N_GROUP = 8
TOPK_GROUP = 3
FIRST_HELD = 0             # id of the first expert of the stacked w1 / w3 / w2
SCALING = 16.0             # routed_scaling_factor
LORA_ALPHA = 32.0
ROPE = dict(theta=1e4, factor=40.0, beta_fast=32.0, beta_slow=1.0,
            original=4096, mscale=0.707, mscale_all_dim=0.707)
EPS = 1e-6
ADAPTERS = "lora"          # check.trainable names it
MATRICES = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")


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
    """[t, dim] rotation angles: position x inverse frequency, the
    frequencies repeated over both halves (rotate-half)."""
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

    # one block of heads after the other (a scan: the compiler may not run
    # two at a time), ``head_block`` dividing the head count
    blocks = lambda z: z.reshape(n, -1, head_block, t, z.shape[-1]).transpose(1, 0, 2, 3, 4)
    _, o = jax.lax.scan(lambda _, qkv: (None, block(*qkv)), None, (blocks(q), blocks(kv)))
    o = o.transpose(1, 0, 2, 3, 4).reshape(n, n_heads, t, -1)
    return _adapted(o.transpose(0, 2, 1, 3).reshape(n, t, -1), lp, ad, "wo", alpha)


def gate_weights(f, router, top_k, n_group, topk_group, scaling):
    """[tokens, experts] combine weights: ``scaling`` x the softmax score of
    each of a token's ``top_k`` experts, chosen inside its ``topk_group`` best
    groups of consecutive experts; 0 for every other."""
    p = jax.nn.softmax(_mm(f, router), axis=-1)
    tokens, experts = p.shape

    def chosen(score, k):
        i = jnp.arange(score.shape[-1])
        # j beats i: a larger score, or an equal one and j < i
        beats = (score[:, None, :] > score[:, :, None]) | (
            (score[:, None, :] == score[:, :, None]) & (i[None, None, :] < i[None, :, None]))
        return jnp.sum(beats, axis=-1) < k

    per = experts // n_group
    best = jnp.max(p.reshape(tokens, n_group, per), axis=-1)
    kept = jnp.repeat(chosen(best, topk_group), per, axis=-1)
    left = jnp.where(kept, p, 0.0)
    return jnp.where(chosen(left, top_k), p, 0.0) * scaling


def experts(f, lp, top_k=TOP_K, n_group=N_GROUP, topk_group=TOPK_GROUP,
            first_held=FIRST_HELD, scaling=SCALING, shared=True):
    """The held experts' share of an expert layer's output for f [..., d],
    plus (``shared``) the shared experts'."""
    rows = f.reshape(-1, f.shape[-1])
    g = gate_weights(rows, lp["router"], top_k, n_group, topk_group, scaling)
    held = lp["w1"].shape[0]
    g = g[:, first_held:first_held + held]
    mlp = lambda w1, w3, w2: _mm(jax.nn.silu(_mm(rows, w1)) * _mm(rows, w3), w2)

    @jax.checkpoint
    def one(total, expert):
        w1, w3, w2, ge = expert
        return total + ge[:, None] * mlp(w1, w3, w2), None

    total, _ = jax.lax.scan(one, jnp.zeros_like(rows),
                            (lp["w1"], lp["w3"], lp["w2"], g.T))
    if shared:
        total = total + mlp(lp["s1"], lp["s3"], lp["s2"])
    return total.reshape(f.shape)


def _layer(h, lp, ad, cos, sin, scale, n_heads, head_block, top_k, n_group,
           topk_group, first_held, scaling, alpha):
    h = h + _attention(_norm(h, lp["in_norm"]), lp, ad, alpha, cos, sin, scale,
                       n_heads, head_block)
    f = _norm(h, lp["post_norm"])
    if "router" in lp:
        return h + experts(f, lp, top_k, n_group, topk_group, first_held, scaling)
    return h + _mm(jax.nn.silu(_mm(f, lp["w1"])) * _mm(f, lp["w3"]), lp["w2"])


def layer_names(params):
    return sorted((k for k in params if k.startswith("layer_")),
                  key=lambda k: int(k[len("layer_"):]))


def head_sizes(lp, n_heads=N_HEADS):
    """(nope, rope, value) head sizes of a layer's leaves."""
    rope = lp["wkv_a"].shape[1] - lp["kv_norm"].shape[0]
    nope = lp["wq_b"].shape[1] // n_heads - rope
    return nope, rope, lp["wkv_b"].shape[1] // n_heads - nope


def forward(params, x, n_heads=N_HEADS, head_block=HEAD_BLOCK, top_k=TOP_K,
            n_group=N_GROUP, topk_group=TOPK_GROUP, first_held=FIRST_HELD,
            scaling=SCALING, alpha=LORA_ALPHA, rope=ROPE):
    """Logits [N, T, V] for tokens x [N, T]."""
    names = layer_names(params)
    nope, rope_dim, _ = head_sizes(params[names[0]], n_heads)
    ang = yarn_angles(x.shape[-1], rope_dim, **rope)
    ratio = _mscale(rope["factor"], rope["mscale"]) / _mscale(
        rope["factor"], rope["mscale_all_dim"])
    cos, sin = (jnp.asarray(ratio * f(ang), jnp.float32) for f in (np.cos, np.sin))
    scale = (nope + rope_dim) ** -0.5 * _mscale(rope["factor"], rope["mscale_all_dim"]) ** 2
    layer = jax.checkpoint(_layer, static_argnums=tuple(range(5, 14)))
    h = params["embed"][x.astype(jnp.int32)].astype(jnp.float32)
    for name in names:
        h = layer(h, params[name], params[ADAPTERS][name], cos, sin, scale,
                  n_heads, head_block, top_k, n_group, topk_group, first_held,
                  scaling, alpha)
    return _mm(_norm(h, params["out_norm"]), params["head"])


def _size(a) -> int:
    n = 1
    for d in a.shape:
        n *= int(d)
    return n


def _matrix_work(params, top_k=TOP_K, n_heads=N_HEADS):
    """(frozen, adapters, attention): matrix parameters a token meets in one
    forward pass, and the attention products' depth (scores + values) summed
    over heads and layers."""
    frozen = adapters = attention = 0
    for name in layer_names(params):
        lp, ad = params[name], params[ADAPTERS][name]
        frozen += sum(_size(lp[w]) for w in MATRICES)
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
    """FLOPs of one forward pass over ONE sequence of ``x_shape`` = (T,)
    tokens, by the convention of the module's docstring."""
    (t,) = x_shape
    frozen, adapters, attention = _matrix_work(params, top_k, n_heads)
    # q k^T and (softmax) v: 2 x T^2 x depth, the causal half
    return 2.0 * t * (frozen + adapters) + 2.0 * t * t * attention / 2


def train_flops(params, x_shape, top_k=TOP_K, n_heads=N_HEADS) -> float:
    """Forward and the gradient with respect to activations for the frozen
    matrices (2 x forward); the adapters and the attention products, which
    have two operands to differentiate, 3 x."""
    (t,) = x_shape
    frozen, adapters, attention = _matrix_work(params, top_k, n_heads)
    return (2 * 2.0 * t * frozen + 3 * 2.0 * t * adapters
            + 3 * 2.0 * t * t * attention / 2)


def step_bytes(params, local_itemsize: int) -> float:
    """Least bytes one local step of ONE client moves: every frozen leaf read
    once forward and once backward in the dtype it is stored in, and the
    adapters read forward and backward and read + written by the update."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    frozen = sum(_size(a) * jnp.dtype(a.dtype).itemsize for path, a in flat
                 if getattr(path[0], "key", None) != ADAPTERS)
    adapters = sum(_size(a) for a in jax.tree.leaves(params[ADAPTERS]))
    return 2.0 * frozen + 4.0 * adapters * local_itemsize


def core_flops(params, tokens: float, t: int, n_heads=N_HEADS) -> float:
    """FLOPs of the attention core (scores, softmax x values; no projection)
    to train on ``tokens`` tokens in sequences of ``t``: forward q k^T and
    p v, backward p again, dv, dp, dk and dq - three products as deep as the
    keys forward and back again twice, two as deep as the values and back
    once - over the causal half of every [t, t] square."""
    total = 0.0
    for name in layer_names(params):
        nope, rope, value = head_sizes(params[name], n_heads)
        total += n_heads * (4 * (nope + rope) + 3 * value)
    return 2.0 * tokens * t / 2 * total


def core_bytes(params, tokens: float, itemsize: int, n_heads=N_HEADS) -> float:
    """Least bytes of the same: q, k, v and their rotary parts read and the
    output written forward (the one rotary key a token once); backward the
    same operands and the output's gradient read, five gradients written."""
    total = 0.0
    for name in layer_names(params):
        nope, rope, value = head_sizes(params[name], n_heads)
        operands = n_heads * (2 * nope + rope + value) + rope
        total += 3 * operands + 3 * n_heads * value
    return tokens * itemsize * total


def expert_flops(params, tokens: float, top_k=TOP_K) -> float:
    """FLOPs of the grouped products of every expert layer for ``tokens``
    trained tokens: three products for each of a token's experts that is held
    here (the held share of ``top_k``), forward and with respect to
    activations."""
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
