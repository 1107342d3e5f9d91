"""Command A+ (CohereLabs; ``model_type`` "cohere2_moe": parallel blocks, sliding-window
and full attention layers mixed, sigmoid-routed experts beside averaged shared ones)
under low-rank adapters, in plain float32 jax.numpy.

From the model's public ``config.json`` as the catalog beside the ``model-configs``
guide holds it (hidden 4,096, 32 layers, 128 query / 8 key-value heads of 128,
``layer_types`` three ``sliding_attention`` to one ``full_attention``,
``sliding_window`` 4,096, ``position_embedding_type`` rope_gptj with theta 50,000 and
``rotary_pct`` 1, ``use_parallel_block``, ``layer_norm_eps`` 1e-5, no q/k norm, no
bias; 128 experts of width 4,096, 8 a token by ``expert_selection_fn`` sigmoid with
``norm_topk_prob``, 4 shared experts combined by "average", ``first_k_dense_replace``
0; tied embedding of 262,144 rows, ``logit_scale`` 1) and its ``described_as``; what is
not among the row's keys is under ``assumed`` in the configuration's file.

    LN_w(x) = (x - mean(x)) * rsqrt(var(x) + 1e-5) * w          no bias
    layer l:  a = LN_l(h)                                       ONE norm a layer
      q = a W_q -> 128 heads x 128;  k = a W_k, v = a W_v -> 8 heads x 128
      sliding (l % 4 != 3): rotary (rotate-half, theta 50,000) on all of q, k;
                            key j visible to query i  iff  0 <= i - j < 4096
      full    (l % 4 == 3): NO positional term;  key j visible iff j <= i
      o = softmax(q k^T / sqrt(128)) v W_o          query head h reads kv head h // 16
      r = sigmoid(a W_r) over 128;  sel = the 8 largest (ties: the lower index)
      g = r[sel] / sum(r[sel])
      m = sum_{e in sel, e held} g_e (silu(a W1_e) * a W3_e) W2_e
          + (1/4) sum_{s<4} (silu(a S1_s) * a S3_s) S2_s
      h = h + o + m
    model:    h = E[x];  layers;  logits = LN_out(h) E^T * 1     tied, held rows
    adapter:  y = x W + (alpha / r) (x A) B  on W_q, W_k, W_v, W_o

The parameter tree is the program's, read by name: ``layer_<i>`` holds layer i's
leaves (``w1``/``w3``/``w2`` stacked over the experts HELD, the router over all;
``s1``/``s3`` [d, 4 x width] and ``s2`` [4 x width, d] hold the shared experts side by
side, expert s in columns / rows ``s x width ...``) and ``lora/layer_<i>/<matrix>_a|_b``
its adapters.  The head counts, the period of the layer pattern, the window, the
routing's numbers, the first expert held, theta and the adapters' alpha are not shapes
of the tree and are stated below; the head size and the experts' width follow from
them and the shapes.

A Python loop over the layers, each a ``jax.checkpoint``.  Attention runs one
key-value head (its 16 query heads) and ``QUERY_BLOCK`` queries at a time, two nested
scans with every step a checkpoint of its own, against ALL the keys of the head: the
scores alive are [16, block, T] (0.5 GB at T = 8,192; [128, T, T] would be 34 GB), and
the band is a mask on them - nothing here skips a block.  In an expert layer a scan
over ALL held experts, every expert applied to every token and the unselected weighted
0, so nothing here sorts, gathers or groups; the selection is a count of what beats
what, not a top-k; the shared experts are four MLPs, summed and divided by four.  Base
leaves may arrive in bfloat16 (the program stores them so): each is cast to float32
where it is used; casting changes no value.

Counting convention (``forward_flops``): matrix products x 2; of the two attention
products the pairs the mathematics has - ``pairs(t, window)`` inside the band for a
sliding layer, the causal t (t + 1) / 2 for a full one, the same count whatever
implements it; a token's ``TOP_K`` experts times the held share of the experts; the
shared experts; the head over the held rows; no elementwise work, no recomputation.
Training over a frozen base (``train_flops``): forward and the gradient with respect to
activations for every frozen matrix (2 x forward), three for the adapters and for the
attention products.  ``core_flops`` / ``core_bytes`` are the attention core's alone,
forward and backward as the mathematics has them (7 products; the checkpoint's re-run
is not counted), of the layers whose kind is asked for; ``expert_flops`` /
``expert_bytes`` the held experts' grouped products'.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
N_HEADS = 128
N_KV_HEADS = 8
SLIDING, FULL = "sliding_attention", "full_attention"
PERIOD = 4                 # layer_switch: layers l with l % 4 == 3 are full, the rest sliding
WINDOW = 4096              # sliding_window
QUERY_BLOCK = 1024         # queries whose scores are alive together
TOP_K = 8                  # num_experts_per_tok
N_SHARED = 4               # num_shared_experts, averaged
FIRST_HELD = 0             # id of the first expert of the stacked w1 / w3 / w2
LORA_ALPHA = 32.0
THETA = 5e4
EPS = 1e-5
LOGIT_SCALE = 1.0
ADAPTERS = "lora"          # check.trainable names it
MATRICES = ("wq", "wk", "wv", "wo")


def _mm(a, b):
    return jnp.matmul(a, b.astype(jnp.float32), precision=HI)


def _norm(x, w, eps=EPS):
    c = x - jnp.mean(x, axis=-1, keepdims=True)
    return c * jax.lax.rsqrt(jnp.mean(c * c, axis=-1, keepdims=True) + eps) * w.astype(jnp.float32)


def _adapted(x, lp, ad, name, alpha):
    a, b = ad[name + "_a"], ad[name + "_b"]
    return _mm(x, lp[name]) + (alpha / a.shape[1]) * _mm(_mm(x, a), b)


def kind_of(name: str, period=PERIOD) -> str:
    """The kind of layer ``layer_<i>``: the last of every ``period`` is full."""
    return FULL if int(name[len("layer_"):]) % period == period - 1 else SLIDING


def rotary_angles(t, dim, theta=THETA):
    """[t, dim] rotation angles, the frequencies repeated over both halves (rotate-half)."""
    inv = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    return np.concatenate([ang, ang], axis=-1)


def _rotate(x, cos, sin):
    """x [N, T, heads, dim], cos / sin [T, dim]."""
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[:, None, :] + turned * sin[:, None, :]


def _attention(a, lp, ad, alpha, cos, sin, n_heads, n_kv, window, query_block):
    """``window`` None: a full layer, no positional term."""
    n, t, _ = a.shape
    heads = lambda name, count: _adapted(a, lp, ad, name, alpha).reshape(n, t, count, -1)
    q, k, v = heads("wq", n_heads), heads("wk", n_kv), heads("wv", n_kv)
    if window is not None:
        q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
    hd, group = q.shape[-1], n_heads // n_kv
    block = query_block if t % query_block == 0 else t
    # [kv head, query block, N, its query heads, block, hd] and [kv head, N, T, hd]
    qs = q.reshape(n, t // block, block, n_kv, group, hd).transpose(3, 1, 0, 4, 2, 5)
    ks, vs = (z.transpose(2, 0, 1, 3) for z in (k, v))
    starts = jnp.arange(t // block) * block

    @jax.checkpoint
    def some_queries(qb, kh, vh, start):
        scores = jnp.einsum("nrqd,nkd->nrqk", qb, kh, precision=HI) * hd ** -0.5
        ahead = (start + jnp.arange(block))[:, None] - jnp.arange(t)[None, :]
        visible = ahead >= 0 if window is None else (ahead >= 0) & (ahead < window)
        scores = jnp.where(visible, scores, -jnp.inf)
        return jnp.einsum("nrqk,nkd->nrqd", jax.nn.softmax(scores, axis=-1), vh, precision=HI)

    def one_head(_, qkv):
        qh, kh, vh = qkv
        _, o = jax.lax.scan(lambda _, qs_: (None, some_queries(qs_[0], kh, vh, qs_[1])),
                            None, (qh, starts))
        return None, o

    _, o = jax.lax.scan(one_head, None, (qs, ks, vs))      # [kv, blocks, N, group, block, hd]
    o = o.transpose(2, 1, 4, 0, 3, 5).reshape(n, t, n_heads * hd)
    return _adapted(o, lp, ad, "wo", alpha)


def gate_weights(f, router, top_k):
    """[tokens, experts] combine weights: the sigmoid score of each of a token's
    ``top_k`` experts over the sum of the ``top_k`` scores; 0 for every other."""
    r = jax.nn.sigmoid(_mm(f, router))
    i = jnp.arange(r.shape[-1])
    # j beats i: a larger score, or an equal one and j < i
    beats = (r[:, None, :] > r[:, :, None]) | (
        (r[:, None, :] == r[:, :, None]) & (i[None, None, :] < i[None, :, None]))
    chosen = jnp.where(jnp.sum(beats, axis=-1) < top_k, r, 0.0)
    return chosen / jnp.sum(chosen, axis=-1, keepdims=True)


def experts(f, lp, top_k=TOP_K, n_shared=N_SHARED, first_held=FIRST_HELD, shared=True):
    """The held experts' share of an expert layer's output for f [..., d], plus
    (``shared``) the mean of the shared experts'."""
    rows = f.reshape(-1, f.shape[-1])
    held = lp["w1"].shape[0]
    g = gate_weights(rows, lp["router"], top_k)[:, first_held:first_held + held]
    mlp = lambda w1, w3, w2: _mm(jax.nn.silu(_mm(rows, w1)) * _mm(rows, w3), w2)

    @jax.checkpoint
    def one(total, expert):
        w1, w3, w2, ge = expert
        return total + ge[:, None] * mlp(w1, w3, w2), None

    total, _ = jax.lax.scan(one, jnp.zeros_like(rows), (lp["w1"], lp["w3"], lp["w2"], g.T))
    if shared:
        width = lp["s1"].shape[1] // n_shared
        for s in range(n_shared):
            cols = slice(s * width, (s + 1) * width)
            total = total + mlp(lp["s1"][:, cols], lp["s3"][:, cols], lp["s2"][cols]) / n_shared
    return total.reshape(f.shape)


def _layer(h, lp, ad, cos, sin, n_heads, n_kv, window, query_block, top_k, n_shared,
           first_held, alpha):
    a = _norm(h, lp["norm"])
    return (h + _attention(a, lp, ad, alpha, cos, sin, n_heads, n_kv, window, query_block)
            + experts(a, lp, top_k, n_shared, first_held))


def layer_names(params):
    return sorted((k for k in params if k.startswith("layer_")),
                  key=lambda k: int(k[len("layer_"):]))


def forward(params, x, n_heads=N_HEADS, n_kv=N_KV_HEADS, period=PERIOD, window=WINDOW,
            query_block=QUERY_BLOCK, top_k=TOP_K, n_shared=N_SHARED,
            first_held=FIRST_HELD, alpha=LORA_ALPHA, theta=THETA):
    """Logits [N, T, V] for tokens x [N, T]."""
    names = layer_names(params)
    hd = params[names[0]]["wq"].shape[1] // n_heads
    ang = rotary_angles(x.shape[-1], hd, theta)
    cos, sin = (jnp.asarray(f(ang), jnp.float32) for f in (np.cos, np.sin))
    layer = jax.checkpoint(_layer, static_argnums=tuple(range(5, 13)))
    h = params["embed"][x.astype(jnp.int32)].astype(jnp.float32)
    for name in names:
        w = window if kind_of(name, period) == SLIDING else None
        h = layer(h, params[name], params[ADAPTERS][name], cos, sin, n_heads, n_kv, w,
                  query_block, top_k, n_shared, first_held, alpha)
    return _mm(_norm(h, params["out_norm"]), params["embed"].T) * LOGIT_SCALE


def _size(a) -> int:
    n = 1
    for d in a.shape:
        n *= int(d)
    return n


def pairs(t: int, window=None) -> int:
    """(query, key) pairs of one head over t positions: key <= query and, under a
    window, fewer than ``window`` positions behind it."""
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def layer_pairs(name: str, t: int, period=PERIOD, window=WINDOW) -> int:
    return pairs(t, window if kind_of(name, period) == SLIDING else None)


def _matrix_work(params, top_k=TOP_K):
    """(frozen, adapters): matrix parameters a token meets in one forward pass."""
    frozen = adapters = 0
    for name in layer_names(params):
        lp = params[name]
        frozen += sum(_size(lp[w]) for w in MATRICES + ("router", "s1", "s3", "s2"))
        held, total = lp["w1"].shape[0], lp["router"].shape[1]
        one = sum(_size(lp[w]) for w in ("w1", "w3", "w2")) // held
        frozen += top_k * one * held / total
        adapters += sum(_size(a) for a in params[ADAPTERS][name].values())
    return frozen + _size(params["embed"]), adapters


def _core_depth(params, t, n_heads, period, window, kinds=(SLIDING, FULL)):
    """Sum over the layers of ``kinds`` and their heads of pairs x head size."""
    return sum(n_heads * layer_pairs(name, t, period, window)
               * (params[name]["wq"].shape[1] // n_heads)
               for name in layer_names(params) if kind_of(name, period) in kinds)


def forward_flops(params, x_shape, top_k=TOP_K, n_heads=N_HEADS, period=PERIOD,
                  window=WINDOW) -> float:
    """FLOPs of one forward pass over ONE sequence of ``x_shape`` = (T,) tokens, by the
    convention of the module's docstring."""
    (t,) = x_shape
    frozen, adapters = _matrix_work(params, top_k)
    # q k^T and (softmax) v: 2 FLOPs a pair and a unit of depth, two products
    return 2.0 * t * (frozen + adapters) + 2 * 2.0 * _core_depth(params, t, n_heads, period, window)


def train_flops(params, x_shape, top_k=TOP_K, n_heads=N_HEADS, period=PERIOD,
                window=WINDOW) -> float:
    """Forward and the gradient with respect to activations for the frozen matrices
    (2 x forward); the adapters and the attention products, which have two operands to
    differentiate, 3 x."""
    (t,) = x_shape
    frozen, adapters = _matrix_work(params, top_k)
    return (2 * 2.0 * t * frozen + 3 * 2.0 * t * adapters
            + 3 * 2 * 2.0 * _core_depth(params, t, n_heads, period, window))


def step_bytes(params, local_itemsize: int) -> float:
    """Least bytes one local step of ONE client moves: every frozen leaf read once
    forward and once backward in the dtype it is stored in, and the adapters read
    forward and backward and read + written by the update."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    frozen = sum(_size(a) * jnp.dtype(a.dtype).itemsize for path, a in flat
                 if getattr(path[0], "key", None) != ADAPTERS)
    adapters = sum(_size(a) for a in jax.tree.leaves(params[ADAPTERS]))
    return 2.0 * frozen + 4.0 * adapters * local_itemsize


def core_flops(params, tokens: float, t: int, n_heads=N_HEADS, period=PERIOD,
               window=WINDOW, kinds=(SLIDING, FULL)) -> float:
    """FLOPs of the attention core (scores, softmax x values; no projection) of the
    layers of ``kinds`` to train on ``tokens`` tokens in sequences of ``t``: forward
    q k^T and p v, backward p again, dv, dp, dk and dq - seven products as deep as a
    head, over the pairs the layer's mask leaves."""
    return 7 * 2.0 * tokens / t * _core_depth(params, t, n_heads, period, window, kinds)


def core_bytes(params, tokens: float, itemsize: int, n_heads=N_HEADS, n_kv=N_KV_HEADS,
               period=PERIOD, kinds=(SLIDING, FULL)) -> float:
    """Least bytes of the same: q, k, v read and the output written forward; backward
    the same operands, the output and its gradient read, three gradients written - the
    band leaves every position of every operand in use, so it takes none away."""
    total = 0.0
    for name in layer_names(params):
        if kind_of(name, period) in kinds:
            hd = params[name]["wq"].shape[1] // n_heads
            total += 3 * (n_heads + 2 * n_kv) * hd + 3 * n_heads * hd
    return tokens * itemsize * total


def expert_flops(params, tokens: float, top_k=TOP_K) -> float:
    """FLOPs of the grouped products of every expert layer for ``tokens`` trained
    tokens: three products for each of a token's experts that is held here (the held
    share of ``top_k``), forward and with respect to activations."""
    total = 0.0
    for name in layer_names(params):
        lp = params[name]
        held, n = lp["w1"].shape[0], lp["router"].shape[1]
        one = sum(_size(lp[w]) for w in ("w1", "w3", "w2")) / held
        total += 2 * 2.0 * tokens * top_k * one * held / n
    return total


def expert_bytes(params, reads: float) -> float:
    """Least bytes of the same products: every held expert's three matrices read once
    forward and once backward, ``reads`` times (once per local step of each group of
    clients that the program trains side by side)."""
    held = sum(_size(params[name][w]) * jnp.dtype(params[name][w].dtype).itemsize
               for name in layer_names(params) for w in ("w1", "w3", "w2"))
    return 2.0 * held * reads
