"""DeepSeek-V2 — a decoder LM of multi-head latent attention and
group-limited sparse experts beside shared ones, with low-rank adapters
over a frozen base.

The family of DeepSeek-V2 (``model_type`` "deepseek_v2", arXiv:2405.04434):
queries and keys/values pass through low-rank compressions, a head's key
is a content part expanded from the key/value latent plus ONE rotary part a
token that every head shares, and values are narrower than keys; the first
``first_dense`` layers carry a dense gated MLP, every later one a router
over ``n_experts`` gated MLPs in ``n_group`` groups of consecutive experts —
a token visits ``experts_per_token`` of them, all inside its ``topk_group``
best groups — plus ``n_shared`` experts every token visits.  Every width,
the layers and the experts held here and the adapter rank are constructor
arguments; a benchmark configuration carries a published model's.

    RMSNorm_w(x) = x * rsqrt(mean(x^2) + eps) * w
    layer l:  a = RMSNorm_in(h)
      c_q = RMSNorm_q(a W_qa);  q = c_q W_qb -> H heads x (nope | rope)
      [c_kv | k_r] = a W_kva;   c_kv = RMSNorm_kv(c_kv);  k_r: one head
      [k_nope | v] = c_kv W_kvb -> H heads x (nope | v_dim)
      rotary (rotate-half, YaRN inverse frequencies) on q_rope and k_r
      s = (q_nope . k_nope + q_rope . k_r) * (nope + rope)^-0.5 * m^2
      o = softmax_causal(s) v W_o;  h = h + o;  f = RMSNorm_post(h)
      dense (l < first_dense):  m = (silu(f W1) * f W3) W2
      experts: p = softmax(f W_r)                       float32, over all
               group score = max of p over each group's experts
               keep the ``topk_group`` best groups, p = 0 elsewhere
               sel = top_k(p);  g = p[sel] * routed_scaling_factor
               m = sum_{e in sel, e held} g_e (silu(f W1_e) * f W3_e) W2_e
                   + (silu(f S1) * f S3) S2             the shared experts
      h = h + m
    model:    h = E[x];  layers;  logits = RMSNorm_out(h) W_head  (untied)
    adapter:  y = x W + (alpha / r) (x A) B   on W_qa, W_qb, W_kva, W_kvb,
              W_o of every held layer;  A ~ N(0, 1 / d_in), B = 0

``__call__`` returns float32 logits [B, T, vocab] — the trainer's contract.

How it is built for a chip (what it shares with models/lfm2_moe.py is
imported from there, not copied):

* the base is frozen and stored in ``base_dtype``; only the adapters train
  (``trainable``), and a base leaf is cast where it is used.
* **the attention core is ``ops/attention.py::causal_attention`` in its
  two-part form**: the content product and the rotary product are summed
  tile by tile inside the kernel, so the rotary key stays ``[B, T, 1, rope]``
  — it is never repeated for the heads — and neither the ``nope + rope``
  wide queries and keys nor the [B, H, T, T] scores are ever assembled.
  The softmax scale (YaRN's ``mscale^2`` included) is handed to it.
* **the rotary of the queries' 64-wide part is
  ``ops/rotary.py::rotate_half``**: lowered for a TPU one elementwise kernel
  pass over ``q_rope`` viewed ``[B, T, H * 64]``, two heads to a row of
  lanes, in the forward pass, the checkpoint's re-run and, transposed, the
  backward pass; its residuals are the two tables, so ``KEPT_NAMES`` is
  untouched.  `apply_rotary`, which it replaces and falls back to (the CPU;
  the one shared key head, 0.5 MB), turned float32 halves of 32 lanes that
  the (8, 128) tiling pads 4 x, behind a float32 relayout of the whole
  queries: a layer-step of this function and the core takes 67.3 ms where
  it took 77.1 (PERF.md section 5, PR 46).
* **the expert layer is lfm2_moe's dropless grouped product**, told which
  experts it holds (``held`` = (first, how many): ONE routing group under
  the published expert parallelism): the router scores all ``n_experts``,
  slots of absent experts sort behind the held ones' and are not touched —
  the product gathers, multiplies and combines the held experts' rows
  alone, in row blocks up to the last held slot; the shared experts are a
  dense gated MLP on every token.
* every layer is a ``jax.checkpoint``; the layers are unrolled (each has its
  own leaves), so a kept value is ONE buffer that the forward pass writes
  anyway and the backward pass reads in place — no scan's stack (what made
  kept products cost what they save in `models/looped_lm.py`).  **Where the
  stream is 16 bits wide** a layer keeps, beside its input, what
  ``KEPT_NAMES`` lists: the attention kernel's output and log-sum-exp (named
  in ``ops/attention.py``) and ``W_o``'s adapted output (``attn_out``, named
  in ``latent_attention``), each in the dtype the backward pass reads it in,
  so no value changes.  The backward pass then re-runs the latent side, the
  router, the experts and the MLPs — the backward kernel needs q, k, v again
  — and neither the forward kernel nor the output projection with its
  adapter and the heads-first transposes in front of the kernel.  At
  DeepSeek-V2's widths on 4,096 bfloat16 tokens (``kept_bytes``) that is
  134.2 MB of ``o``, 2.1 MB of log-sum-exp and 41.9 MB of ``attn_out`` a
  layer, 178,257,920 B, beside 41.9 MB of input: 1.10 GB for the five layers
  of a local step.  What bounds the set is the chip's memory: the kernel's
  three 134 MB operands a layer would not fit, and the MXU redoes their
  products in ≈ 1.5 ms.  **A float32 stream** (the twin the benchmark's
  reference check runs, which has under 1 GB to spare on a chip) keeps a
  layer's input alone, as every stream did before.  The stream's width is
  the whole rule, no option — `looped_lm`'s rule, for the same reason; the
  choice is counted at trace time in
  ``remat_policy_total{model="deepseek_v2", saved=...}`` beside the bytes a
  local step keeps (``remat_saved_bytes``).
* the router's decisions are counted as in lfm2_moe: tokens routed to every
  (expert layer, expert) of a step, held or not
  (``counters/moe_expert_tokens``), and the rows the grouped products ran
  over beside the slots routed (``counters/moe_slot_rows``).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from fedml_tpu import obs
from fedml_tpu.models.lfm2_moe import (_adapted, _Groups, _Leaves,
                                       counter_shapes, float_counters,
                                       gated_mlp, held_share, sow_counters)
from fedml_tpu.models.looped_lm import _dot, rms_norm
from fedml_tpu.obs import scopes
from fedml_tpu.ops.attention import SAVED_NAMES, causal_attention
from fedml_tpu.ops.rotary import rotate_half

# what a layer's checkpoint keeps beside its input where the stream is 16
# bits wide (module docstring): ``W_o``'s adapted output, as
# `latent_attention` names it, and the attention kernel's output and
# log-sum-exp
KEPT_NAMES = ("attn_out",) + SAVED_NAMES
# made once: a jaxpr prints its checkpoint's policy by identity
_KEEP = jax.checkpoint_policies.save_only_these_names(*KEPT_NAMES)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_tables(seq_len: int, dim: int, theta: float, factor: float,
                beta_fast: float, beta_slow: float, original: int):
    """cos, sin [T, dim] in float32 (rotate-half layout) for YaRN's inverse
    frequencies: interpolated (1 / factor) below ``beta_slow`` turns over
    the original context, extrapolated above ``beta_fast``, a linear ramp
    of the two between."""
    pos = np.arange(0, dim, 2, dtype=np.float64) / dim
    extrapolated = 1.0 / theta ** pos
    turns = lambda n: dim * math.log(original / (n * 2 * math.pi)) / (
        2 * math.log(theta))
    low = max(math.floor(turns(beta_fast)), 0)
    high = min(math.ceil(turns(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv = extrapolated / factor * ramp + extrapolated * (1 - ramp)
    ang = np.arange(seq_len, dtype=np.float64)[:, None] * inv[None, :]
    ang = np.concatenate([ang, ang], axis=-1)
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def latent_attention(h, lp, ad, scale, eps, cos, sin, n_heads: int,
                     nope: int, v_dim: int, softmax_scale: float):
    """Multi-head latent attention on h [B, T, d]; the scopes: the latent
    side here, the core and the output projection ``fed_attention``.  The
    projection's output is named for the layer's checkpoint (``KEPT_NAMES``).
    `rotate_half` reads off the shapes which body turns what: the kernel the
    queries' rotary part, the plain body the one key head all heads share."""
    B, T, _ = h.shape
    with jax.named_scope(scopes.FED_MLA_LATENT):
        a = rms_norm(h, lp["in_norm"], eps)
        adapted = lambda x, name: _adapted(x, lp, ad, name, scale)
        c_q = rms_norm(adapted(a, "wq_a"), lp["q_norm"], eps)
        q = adapted(c_q, "wq_b").reshape(B, T, n_heads, -1)
        kv_rank = lp["kv_norm"].shape[0]
        c_kv, k_rope = jnp.split(adapted(a, "wkv_a"), [kv_rank], axis=-1)
        kv = adapted(rms_norm(c_kv, lp["kv_norm"], eps), "wkv_b")
        k_nope, v = jnp.split(kv.reshape(B, T, n_heads, nope + v_dim),
                              [nope], axis=-1)
        q_nope, q_rope = jnp.split(q, [nope], axis=-1)
        q_rope = rotate_half(q_rope, cos, sin)
        k_rope = rotate_half(k_rope[:, :, None, :], cos, sin)
    with jax.named_scope(scopes.FED_ATTENTION):
        o = causal_attention(q_nope, k_nope, v, rope=(q_rope, k_rope),
                             scale=softmax_scale)
        return checkpoint_name(adapted(o.reshape(B, T, -1), "wo"), "attn_out")


def kept_bytes(h, n_heads: int, v_dim: int) -> int:
    """Bytes of ``KEPT_NAMES``' values for ONE layer on the stream h
    [B, T, d]: the attention output and ``W_o``'s in h's dtype, the rows'
    log-sum-exp in float32."""
    tokens, d = h.size // h.shape[-1], h.shape[-1]
    return tokens * ((n_heads * v_dim + d) * h.dtype.itemsize + 4 * n_heads)


def route_grouped(f, router, k: int, n_group: int, topk_group: int,
                  scaling: float):
    """(sel [N, k] expert ids, gate [N, k] float32) for tokens f [N, d]:
    softmax scores over all experts, the ``topk_group`` groups with the
    largest best score kept, the ``k`` largest scores inside them selected
    and weighted by score x ``scaling`` (no renormalisation).  Ties go to
    the lower index, groups and experts alike."""
    p = jax.nn.softmax(_dot(f, router.astype(f.dtype)), axis=-1)
    N, n_experts = p.shape
    best = jnp.max(p.reshape(N, n_group, -1), axis=-1)
    _, groups = jax.lax.top_k(best, topk_group)
    kept = jnp.sum(jax.nn.one_hot(groups, n_group, dtype=p.dtype), axis=1)
    inside = jnp.repeat(kept, n_experts // n_group, axis=-1) > 0
    _, sel = jax.lax.top_k(jnp.where(inside, p, 0.0), k)
    return sel, jnp.take_along_axis(p, sel, axis=-1) * scaling


def moe_layer(f, lp, k: int, n_group: int, topk_group: int, scaling: float,
              held):
    """(m, the layer's counters: `lfm2_moe.held_share`) of one expert
    layer for f [..., d]; ``lp``: router, the experts HELD (``held`` =
    (first, how many)) and the shared experts as one gated MLP."""
    rows = f.reshape((-1, f.shape[-1]))
    with jax.named_scope(scopes.FED_MOE_ROUTER):
        sel, gate = route_grouped(rows, lp["router"], k, n_group, topk_group,
                                  scaling)
    m, counts = held_share(rows, sel, gate, lp, *held)
    with jax.named_scope(scopes.FED_SHARED_EXPERT):
        m = m + gated_mlp(rows, lp["s1"], lp["s3"], lp["s2"])
    return m.reshape(f.shape), float_counters(counts)


class DeepSeekV2LM(nn.Module):
    """tokens [B, T] int -> float32 logits [B, T, vocab]."""
    vocab_size: int
    d_model: int = 64
    n_heads: int = 4
    q_rank: int = 24                      # q_lora_rank
    kv_rank: int = 16                     # kv_lora_rank
    nope_dim: int = 16                    # qk_nope_head_dim
    rope_dim: int = 8                     # qk_rope_head_dim
    v_dim: int = 12                       # v_head_dim
    d_ff: int = 96                        # the dense layers' MLP width
    d_expert: int = 32
    n_experts: int = 16
    experts_per_token: int = 6
    n_group: int = 8
    topk_group: int = 3
    n_shared: int = 2
    n_layers: int = 3
    first_dense: int = 1                  # first_k_dense_replace
    layers: Optional[tuple] = None        # ids of the layers held; None = all
    held: Optional[tuple] = None          # (first expert, how many); None = all
    rope_theta: float = 1e4
    rope_factor: float = 40.0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_original: int = 4096
    rope_mscale: float = 0.707
    rope_mscale_all_dim: float = 0.707
    norm_eps: float = 1e-6
    routed_scaling_factor: float = 16.0
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
        return (tuple(range(self.n_layers)) if self.layers is None
                else tuple(self.layers))

    @property
    def expert_layers(self) -> tuple:
        return tuple(i for i in self.held_layers if i >= self.first_dense)

    @property
    def held_experts(self) -> tuple:
        return (0, self.n_experts) if self.held is None else tuple(self.held)

    @property
    def counters(self) -> dict:
        return counter_shapes(len(self.expert_layers), self.n_experts)

    @property
    def softmax_scale(self) -> float:
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return (self.nope_dim + self.rope_dim) ** -0.5 * m * m

    def _specs(self, i: int):
        """(base, adapter) leaf specs of layer i."""
        d, H, bt = self.d_model, self.n_heads, self.base_dtype
        normal, ones = nn.initializers.normal(self.init_std), nn.initializers.ones
        mats = {"wq_a": (d, self.q_rank),
                "wq_b": (self.q_rank, H * (self.nope_dim + self.rope_dim)),
                "wkv_a": (d, self.kv_rank + self.rope_dim),
                "wkv_b": (self.kv_rank, H * (self.nope_dim + self.v_dim)),
                "wo": (H * self.v_dim, d)}
        base = [(n, s, normal, bt) for n, s in mats.items()]
        base += [(n, (w,), ones, bt) for n, w in (
            ("in_norm", d), ("q_norm", self.q_rank),
            ("kv_norm", self.kv_rank), ("post_norm", d))]
        if i < self.first_dense:
            widths = {"w1": (d, self.d_ff), "w3": (d, self.d_ff),
                      "w2": (self.d_ff, d)}
        else:
            e, w = self.held_experts[1], self.d_expert
            s = self.n_shared * w
            widths = {"router": (d, self.n_experts),
                      "w1": (e, d, w), "w3": (e, d, w), "w2": (e, w, d),
                      "s1": (d, s), "s3": (d, s), "s2": (s, d)}
        base += [(n, s, normal, bt) for n, s in widths.items()]
        r = self.lora_rank
        adapters = []
        for n, (d_in, d_out) in mats.items():
            adapters += [
                (n + "_a", (d_in, r), nn.initializers.normal(d_in ** -0.5), jnp.float32),
                (n + "_b", (r, d_out), nn.initializers.zeros, jnp.float32)]
        return tuple(base), tuple(adapters)

    def _layer(self, i: int, h, lp, ad, cos, sin):
        eps = self.norm_eps
        h = h + latent_attention(
            h, lp, ad, self.lora_alpha / self.lora_rank, eps, cos, sin,
            self.n_heads, self.nope_dim, self.v_dim, self.softmax_scale)
        if i < self.first_dense:
            with jax.named_scope(scopes.FED_MLP):
                f = rms_norm(h, lp["post_norm"], eps)
                return h + gated_mlp(f, lp["w1"], lp["w3"], lp["w2"]), None
        with jax.named_scope(scopes.FED_MOE_ROUTER):
            f = rms_norm(h, lp["post_norm"], eps)
        m, counts = moe_layer(f, lp, self.experts_per_token, self.n_group,
                              self.topk_group, self.routed_scaling_factor,
                              self.held_experts)
        return h + m, counts

    @nn.compact
    def __call__(self, x, train: bool = False):
        normal = nn.initializers.normal(self.init_std)
        shape = (self.vocab_size, self.d_model)
        embed = self.param("embed", normal, shape, self.base_dtype)
        head = self.param("head", normal, shape[::-1], self.base_dtype)
        out_norm = self.param("out_norm", nn.initializers.ones,
                              (self.d_model,), self.base_dtype)
        specs = {i: self._specs(i) for i in self.held_layers}
        base = {i: _Leaves(specs[i][0], name=f"layer_{i}")()
                for i in self.held_layers}
        lora = _Groups(tuple((f"layer_{i}", specs[i][1])
                             for i in self.held_layers), name="lora")()
        dt = jax.tree.leaves(lora)[0].dtype          # the adapters': compute
        # cos and sin carry YaRN's mscale / mscale_all_dim (1 as published)
        ratio = (yarn_mscale(self.rope_factor, self.rope_mscale)
                 / yarn_mscale(self.rope_factor, self.rope_mscale_all_dim))
        cos, sin = (ratio * t for t in yarn_tables(
            x.shape[-1], self.rope_dim, self.rope_theta, self.rope_factor,
            self.rope_beta_fast, self.rope_beta_slow, self.rope_original))
        h = embed[x.astype(jnp.int32)].astype(dt)
        counts = []
        attention = h.dtype.itemsize <= 2
        obs.counter("remat_policy_total", model="deepseek_v2",
                    saved="attention" if attention else "input_only").inc()
        kept = kept_bytes(h, self.n_heads, self.v_dim) if attention else 0
        obs.gauge("remat_saved_bytes", model="deepseek_v2").set(
            len(self.held_layers) * (h.size * h.dtype.itemsize + kept))
        for i in self.held_layers:
            layer = jax.checkpoint(functools.partial(self._layer, i),
                                   policy=_KEEP if attention else None)
            h, c = layer(h, base[i], lora[f"layer_{i}"], cos, sin)
            if c is not None:
                counts.append(c)
        sow_counters(self, counts)
        with jax.named_scope(scopes.FED_LM_HEAD):
            s = rms_norm(h, out_norm, self.norm_eps)
            return _dot(s, head.astype(dt))
