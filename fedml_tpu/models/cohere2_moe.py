"""Cohere2-MoE — a decoder LM of parallel blocks that mix sliding-window and
full attention layers, with sparse experts beside averaged shared ones and
low-rank adapters over a frozen base.

The family of Cohere's Command A+ (``model_type`` "cohere2_moe"): ONE
mean-centred LayerNorm a layer feeds both the attention and the expert layer,
and both are added to the stream (a parallel block); ``layer_types`` says
which layers see only the last ``sliding_window`` positions, with a rotary
embedding, and which see everything before them with no positional term at
all; grouped-query attention; a sigmoid router over ``n_experts`` gated MLPs
of which a token visits ``experts_per_token`` with renormalised gates, beside
``n_shared`` experts every token visits, whose outputs are averaged.  Every
width, the layer pattern, the window, the layers and the experts held here
and the adapter rank are constructor arguments; a benchmark configuration
carries a published model's.

    LN_w(x) = (x - mean(x)) * rsqrt(var(x) + eps) * w        float32, no bias
    layer l:  a = LN_l(h)                                     one norm a layer
      q = a W_q -> H heads x hd;  k, v = a W_k, a W_v -> H_kv heads x hd
      sliding (layer_types[l] = "sliding_attention"):
          rotary (rotate-half) on all of q, k
          key j visible to query i  iff  0 <= i - j < sliding_window
      full:   no positional term;  key j visible iff j <= i
      o = softmax(q k^T / sqrt(hd)) v W_o        kv head g serves heads g R .. g R + R - 1
      r = sigmoid(a W_r)  float32;  sel = top_k(r)  (ties: the lower index)
      g = r[sel] / sum(r[sel])
      m = sum_{e in sel, e held} g_e (silu(a W1_e) * a W3_e) W2_e
          + (1 / n_shared) sum_s (silu(a S1_s) * a S3_s) S2_s
      h = h + o + m
    model:    h = E[x];  layers;  logits = LN_out(h) E^T * logit_scale  (tied)
    adapter:  y = x W + (alpha / r) (x A) B   on W_q, W_k, W_v, W_o of every
              held layer;  A ~ N(0, 1 / d_in), B = 0

``__call__`` returns float32 logits [B, T, vocab] — the trainer's contract.

How it is built for a chip (what it shares with models/lfm2_moe.py and
models/looped_lm.py is imported from there, not copied):

* the base is frozen and stored in ``base_dtype``; only the adapters train
  (``trainable``), and a base leaf is cast where it is used.
* **both kinds of layer run ``ops/attention.py::causal_attention``**, grouped
  (query head h reads key/value head ``h // (H / H_kv)``): a sliding layer
  hands it ``window``, and in a program lowered for a TPU the fused kernels
  then visit only the key blocks inside the band; a full layer hands it
  nothing and runs the kernels every other model runs.
* **a sliding layer's rotary is ``ops/rotary.py::rotate_half``**: with heads
  as wide as the lanes it is, in a program lowered for a TPU, one elementwise
  kernel pass over q and over k where they lie (`apply_rotary`, which the
  other models call, cost 11-18 ms a pass at [8192, 128, 128] where the
  kernel takes 0.8: PERF.md section 5, PR 42); the result and the rounding
  are `apply_rotary`'s.
* **the expert layer is lfm2_moe's dropless grouped product**, told which
  experts it holds (``held`` = (first, past-last): the router scores all
  ``n_experts``, slots of absent experts sort behind the held ones' and are
  not touched — the product gathers, multiplies and combines the held
  experts' rows alone, in row blocks up to the last held slot).  The shared
  experts are stored side by side — ``s1``, ``s3``
  [d, n_shared x width], ``s2`` [n_shared x width, d] — so their sum is ONE
  gated MLP, and the average is that times ``1 / n_shared``.
* every layer is a ``jax.checkpoint``; the layers are unrolled.  **Where the
  stream is 16 bits wide** a layer keeps, beside its input, what
  ``KEPT_NAMES`` lists — the attention kernel's output and log-sum-exp and
  ``W_o``'s adapted output: outputs, each in the dtype the backward pass reads
  it in (`models/deepseek_v2.py`'s set, measured sound there) — and the
  backward pass re-runs the norm, the q, k, v products, the rotary, the
  router, the experts and the shared experts.  **A float32 stream** (the twin
  the benchmark's reference check runs) keeps a layer's input alone.  The
  stream's width is the whole rule, no option; the choice is counted at trace
  time in ``remat_policy_total{model="cohere2_moe", saved=...}`` beside the
  bytes a local step keeps (``remat_saved_bytes``).
* scopes (obs/scopes.py): ``fed_window_attention`` / ``fed_full_attention``
  hold a sliding / a full layer's norm (THE block's one LayerNorm: its
  backward pass sums what attention and the expert layer send back),
  projections, rotary, core and output projection; ``fed_moe_router``,
  ``fed_moe_experts``, ``fed_shared_expert`` and ``fed_lm_head`` the rest.
* the router's decisions are counted as in lfm2_moe: tokens routed to every
  (layer, expert) of a step, held or not (``counters/moe_expert_tokens``),
  and the rows the grouped products ran over beside the slots routed
  (``counters/moe_slot_rows``).
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from fedml_tpu import obs
from fedml_tpu.models.lfm2_moe import (_adapted, _Groups, _Leaves,
                                       counter_shapes, float_counters,
                                       gated_mlp, held_share, sow_counters)
from fedml_tpu.models.looped_lm import _dot, rotary_tables
from fedml_tpu.obs import scopes
from fedml_tpu.ops.attention import SAVED_NAMES, causal_attention
from fedml_tpu.ops.rotary import rotate_half

SLIDING, FULL = "sliding_attention", "full_attention"
_SCOPE_OF = {SLIDING: scopes.FED_WINDOW_ATTENTION,
             FULL: scopes.FED_FULL_ATTENTION}

# what a layer's checkpoint keeps beside its input where the stream is 16
# bits wide (module docstring)
KEPT_NAMES = ("attn_out",) + SAVED_NAMES
# made once: a jaxpr prints its checkpoint's policy by identity
_KEEP = jax.checkpoint_policies.save_only_these_names(*KEPT_NAMES)


def layer_norm(x, w, eps):
    """float32 mean-centred LayerNorm without a bias; returns ``x``'s dtype."""
    x32 = x.astype(jnp.float32)
    c = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    y = c * jax.lax.rsqrt(jnp.mean(c * c, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def attention(a, lp, ad, scale, cos, sin, n_heads: int, n_kv_heads: int,
              window: Optional[int]):
    """Grouped-query attention on the normed stream a [B, T, d]: a sliding
    layer (``window`` given) turns q and k by the rotary tables and sees the
    band, a full one (None) has no positional term.  The output projection's
    result is named for the layer's checkpoint (``KEPT_NAMES``)."""
    B, T, _ = a.shape
    heads = lambda name, n: _adapted(a, lp, ad, name, scale).reshape(B, T, n, -1)
    q, k, v = heads("wq", n_heads), heads("wk", n_kv_heads), heads("wv", n_kv_heads)
    if window is not None:
        q, k = rotate_half(q, cos, sin), rotate_half(k, cos, sin)
    o = causal_attention(q, k, v, window=window)
    return checkpoint_name(_adapted(o.reshape(B, T, -1), lp, ad, "wo", scale),
                           "attn_out")


def kept_bytes(h, n_heads: int, head_dim: int) -> int:
    """Bytes of ``KEPT_NAMES``' values for ONE layer on the stream h
    [B, T, d]: the attention output and ``W_o``'s in h's dtype, the rows'
    log-sum-exp in float32."""
    tokens, d = h.size // h.shape[-1], h.shape[-1]
    return tokens * ((n_heads * head_dim + d) * h.dtype.itemsize + 4 * n_heads)


def route_sigmoid(f, router, k: int):
    """(sel [N, k] expert ids, gate [N, k] float32) for tokens f [N, d]:
    sigmoid scores in float32, the ``k`` largest selected (ties to the lower
    index) and normalised to sum to 1."""
    r = jax.nn.sigmoid(_dot(f, router.astype(f.dtype)))
    g, sel = jax.lax.top_k(r, k)
    return sel, g / jnp.sum(g, axis=-1, keepdims=True)


def moe_layer(a, lp, k: int, n_shared: int, held):
    """(m, the layer's counters: `lfm2_moe.held_share`) of one expert
    layer for a [..., d]; ``lp``: router, the experts HELD (``held`` =
    (first, past-last)) and the shared experts side by side, averaged."""
    first, last = held
    rows = a.reshape((-1, a.shape[-1]))
    with jax.named_scope(scopes.FED_MOE_ROUTER):
        sel, gate = route_sigmoid(rows, lp["router"], k)
    m, counts = held_share(rows, sel, gate, lp, first, last - first)
    with jax.named_scope(scopes.FED_SHARED_EXPERT):
        m = m + gated_mlp(rows, lp["s1"], lp["s3"], lp["s2"]) / n_shared
    return m.reshape(a.shape), float_counters(counts)


def block(h, lp, ad, cos, sin, *, kind: str, window: int, n_heads: int,
          n_kv_heads: int, experts_per_token: int, n_shared: int, held,
          adapter_scale: float, eps: float):
    """One parallel block on h [B, T, d] -> (h + o + m, routed-token counts)."""
    with jax.named_scope(_SCOPE_OF[kind]):
        a = layer_norm(h, lp["norm"], eps)
        o = attention(a, lp, ad, adapter_scale, cos, sin, n_heads, n_kv_heads,
                      window if kind == SLIDING else None)
    m, counts = moe_layer(a, lp, experts_per_token, n_shared, held)
    return h + o + m, counts


class Cohere2MoeLM(nn.Module):
    """tokens [B, T] int -> float32 logits [B, T, vocab]."""
    vocab_size: int
    d_model: int = 64
    n_heads: int = 8
    n_kv_heads: int = 2
    head_dim: int = 16
    d_expert: int = 32
    n_experts: int = 16
    experts_per_token: int = 4
    n_shared: int = 2
    layer_types: tuple = (SLIDING, SLIDING, SLIDING, FULL)
    sliding_window: int = 8
    layers: Optional[tuple] = None        # ids of the layers held; None = all
    held: Optional[tuple] = None          # (first, past-last) expert; None = all
    rope_theta: float = 5e4
    norm_eps: float = 1e-5
    logit_scale: float = 1.0
    lora_rank: int = 4
    lora_alpha: float = 8.0
    init_std: float = 0.02
    base_dtype: Any = jnp.bfloat16

    # what local training updates, as path prefixes under ``params``;
    # every other leaf is frozen (core/trainer.py reads both names)
    trainable = ("lora",)
    loss_scope = scopes.FED_LM_HEAD

    @property
    def held_layers(self) -> tuple:
        return (tuple(range(len(self.layer_types))) if self.layers is None
                else tuple(self.layers))

    @property
    def held_experts(self) -> tuple:
        return (0, self.n_experts) if self.held is None else tuple(self.held)

    @property
    def counters(self) -> dict:
        return counter_shapes(len(self.held_layers), self.n_experts)

    def _specs(self):
        """(base, adapter) leaf specs of a layer: every layer has the same."""
        d, hd, bt = self.d_model, self.head_dim, self.base_dtype
        normal, ones = nn.initializers.normal(self.init_std), nn.initializers.ones
        mats = {"wq": (d, self.n_heads * hd), "wk": (d, self.n_kv_heads * hd),
                "wv": (d, self.n_kv_heads * hd), "wo": (self.n_heads * hd, d)}
        first, last = self.held_experts
        e, w, s = last - first, self.d_expert, self.n_shared * self.d_expert
        widths = {"router": (d, self.n_experts),
                  "w1": (e, d, w), "w3": (e, d, w), "w2": (e, w, d),
                  "s1": (d, s), "s3": (d, s), "s2": (s, d)}
        base = [(n, sh, normal, bt) for n, sh in {**mats, **widths}.items()]
        base.append(("norm", (d,), ones, bt))
        r = self.lora_rank
        adapters = []
        for n, (d_in, d_out) in mats.items():
            adapters += [
                (n + "_a", (d_in, r), nn.initializers.normal(d_in ** -0.5), jnp.float32),
                (n + "_b", (r, d_out), nn.initializers.zeros, jnp.float32)]
        return tuple(base), tuple(adapters)

    def _layer(self, i: int, h, lp, ad, cos, sin):
        return block(h, lp, ad, cos, sin, kind=self.layer_types[i],
                     window=self.sliding_window, n_heads=self.n_heads,
                     n_kv_heads=self.n_kv_heads,
                     experts_per_token=self.experts_per_token,
                     n_shared=self.n_shared, held=self.held_experts,
                     adapter_scale=self.lora_alpha / self.lora_rank,
                     eps=self.norm_eps)

    @nn.compact
    def __call__(self, x, train: bool = False):
        normal = nn.initializers.normal(self.init_std)
        embed = self.param("embed", normal, (self.vocab_size, self.d_model),
                           self.base_dtype)
        out_norm = self.param("out_norm", nn.initializers.ones,
                              (self.d_model,), self.base_dtype)
        base_specs, adapter_specs = self._specs()
        base = {i: _Leaves(base_specs, name=f"layer_{i}")()
                for i in self.held_layers}
        lora = _Groups(tuple((f"layer_{i}", adapter_specs)
                             for i in self.held_layers), name="lora")()
        dt = jax.tree.leaves(lora)[0].dtype          # the adapters': compute
        cos, sin = rotary_tables(x.shape[-1], self.head_dim, self.rope_theta)
        h = embed[x.astype(jnp.int32)].astype(dt)
        attention_kept = h.dtype.itemsize <= 2
        obs.counter("remat_policy_total", model="cohere2_moe",
                    saved="attention" if attention_kept else "input_only").inc()
        kept = kept_bytes(h, self.n_heads, self.head_dim) if attention_kept else 0
        obs.gauge("remat_saved_bytes", model="cohere2_moe").set(
            len(self.held_layers) * (h.size * h.dtype.itemsize + kept))
        counts = []
        for i in self.held_layers:
            layer = jax.checkpoint(functools.partial(self._layer, i),
                                   policy=_KEEP if attention_kept else None)
            h, c = layer(h, base[i], lora[f"layer_{i}"], cos, sin)
            counts.append(c)
        sow_counters(self, counts)
        with jax.named_scope(scopes.FED_LM_HEAD):
            s = layer_norm(h, out_norm, self.norm_eps)
            logits = jnp.einsum("...d,vd->...v", s, embed.astype(dt),
                                preferred_element_type=jnp.float32)
            return logits * self.logit_scale if self.logit_scale != 1 else logits
