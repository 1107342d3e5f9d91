"""LFM2-MoE — a decoder LM of gated short convolutions, grouped-query
attention and sparse experts, with low-rank adapters over a frozen base.

The family of LiquidAI's LFM2-24B-A2B (``model_type`` "lfm2_moe"): most
layers mix tokens with a gated depthwise convolution three tokens wide,
one layer in four with grouped-query attention; the first
``num_dense_layers`` layers carry a dense gated MLP, every later one a
router over ``n_experts`` gated MLPs of which a token visits
``experts_per_token``.  Every width, the layer pattern, the dense-layer
count, the layers and the experts held here and the adapter rank are
constructor arguments; a benchmark configuration carries a published
model's.

    RMSNorm_w(x) = x * rsqrt(mean(x^2) + eps) * w
    layer l:  a = RMSNorm_op(h)
      conv:   [B, C, X] = split3(a W_in)                  (no bias)
              u = B * X;  v_t = sum_j k_j * u_{t-(K-1)+j}  depthwise, causal,
              o = (C * v) W_out                            u_{<0} = 0
      attn:   q = a W_q [H x hd];  k, v = a W_k, a W_v [H_kv x hd]
              q, k <- RMSNorm over each head (q_norm, k_norm); rotary
              (rotate-half) on q, k
              o = softmax_causal(q k^T / sqrt(hd)) v W_o   kv head g serves
                                                           heads g R .. g R + R - 1
      h = h + o;  f = RMSNorm_ffn(h)
      dense (l < num_dense_layers):  m = (silu(f W1) * f W3) W2
      experts: s = sigmoid(f W_r);  sel = top_k(s + b)     b selects only
               g = s[sel] / (sum s[sel] + 1e-6) * routed_scaling_factor
               m = sum_{e in sel, e held} g_e (silu(f W1_e) * f W3_e) W2_e
      h = h + m
    model:    h = E[x];  layers;  logits = RMSNorm_out(h) E^T   (tied head)
    adapter:  y = x W + (alpha / r) (x A) B   on W_in, W_out, W_q, W_k, W_v,
              W_o of every held layer;  A ~ N(0, 1 / d_in), B = 0

``__call__`` returns float32 logits [B, T, vocab] — the trainer's contract.

How it is built for a chip:

* **The base is frozen and stored in ``base_dtype`` (bfloat16)**; only the
  adapters train (``trainable``: the trainer reads it as it reads
  ``loss_scope`` — core/trainer.py).  The compute dtype is the adapters'
  (the trainer casts what it trains to its ``train_dtype``); a base leaf is
  cast where it is used, one layer at a time, so nothing ever holds the
  base in float32.
* **The expert layer is dropless and grouped**: the ``k x tokens`` slots are
  sorted by expert, the three products run as ``jax.lax.ragged_dot`` over
  the experts held (XLA:TPU's grouped-product kernel), and the result is
  un-sorted and combined.  No capacity, no dropped slot, no loop over
  masks.  ``held_experts`` is a range of expert ids: routing is over all
  ``n_experts``, and the part of ``m`` the held experts give is what goes
  on.  **A layer that holds a share of its experts touches only their
  slots**: the sort puts them first, and gather, products and combine run
  in row blocks up to the last held slot — a loop whose bound is the load,
  so no slot is dropped and none of an absent expert is read
  (`_held_share_rules`); a layer that holds them all has nothing to skip
  and runs over every slot at once.
* **A chunk of clients shares one read of the experts**: under the engine's
  ``vmap`` over clients the product merges the clients' tokens before it
  sorts them (``custom_vmap``), instead of running once per client; its
  backward pass is written out (``custom_vjp``) so that it does the same.
* every layer is a ``jax.checkpoint`` that saves its input only, as in
  models/looped_lm.py; the layers are unrolled (each has its own leaves).
* the attention core of a ``full_attention`` layer is
  ``ops/attention.py::causal_attention``, grouped (query head h reads
  key/value head ``h // (H / H_kv)``): fused kernels in a program lowered
  for a TPU, where the [B, H, T, T] float32 scores never reach HBM; the
  einsum, mask, softmax, einsum that used to stand here anywhere else.
  The per-head norms, rotary and the adapted projections stay here.
* the router's decisions are counted: tokens routed to every (expert layer,
  expert) of a step, sown as ``counters/moe_expert_tokens`` where the
  caller asks for that collection (obs/scopes.py), and beside them the rows
  the grouped products ran over and the slots routed
  (``counters/moe_slot_rows``: equal where every expert is held).
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.custom_batching import custom_vmap

from fedml_tpu.models.looped_lm import (_dot, apply_rotary, rms_norm,
                                        rotary_tables)
from fedml_tpu.obs import scopes
from fedml_tpu.ops.attention import causal_attention

def _adapted(x, lp, ad, name, scale):
    """x W + scale (x A) B in x's dtype; W cast where it is used."""
    dt = x.dtype
    y = _dot(x, lp[name].astype(dt))
    z = _dot(_dot(x, ad[name + "_a"]).astype(dt), ad[name + "_b"])
    return (y + scale * z).astype(dt)


def short_conv(h, lp, ad, scale, eps):
    """The gated short convolution on h [B, T, d]."""
    T = h.shape[1]
    a = rms_norm(h, lp["op_norm"], eps)
    b, c, x = jnp.split(_adapted(a, lp, ad, "in_proj", scale), 3, axis=-1)
    kernel = lp["conv_kernel"].astype(jnp.float32)              # [K, d]
    K = kernel.shape[0]
    u = jnp.pad((b * x).astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
    v = sum(kernel[j] * u[:, j:j + T] for j in range(K)).astype(h.dtype)
    return _adapted(c * v, lp, ad, "out_proj", scale)


def gqa_attention(h, lp, ad, scale, eps, cos, sin, n_heads, n_kv_heads):
    """Grouped-query attention with per-head q/k norms on h [B, T, d]."""
    B, T, _ = h.shape
    a = rms_norm(h, lp["op_norm"], eps)
    heads = lambda name, n: _adapted(a, lp, ad, name, scale).reshape(B, T, n, -1)
    q, k, v = heads("wq", n_heads), heads("wk", n_kv_heads), heads("wv", n_kv_heads)
    q = apply_rotary(rms_norm(q, lp["q_norm"], eps), cos, sin)
    k = apply_rotary(rms_norm(k, lp["k_norm"], eps), cos, sin)
    o = causal_attention(q, k, v)
    return _adapted(o.reshape(B, T, -1), lp, ad, "wo", scale)


def gated_mlp(f, w1, w3, w2):
    dt = f.dtype
    g = jax.nn.silu(_dot(f, w1.astype(dt))) * _dot(f, w3.astype(dt))
    return _dot(g.astype(dt), w2.astype(dt)).astype(dt)


def route(f, router, bias, k: int, scaling: float):
    """(sel [N, k] expert ids, gate [N, k] float32) for tokens f [N, d]:
    sigmoid scores, the bias in the selection only, the selected scores
    normalised to sum to ``scaling``."""
    s = jax.nn.sigmoid(_dot(f, router.astype(f.dtype)))
    _, sel = jax.lax.top_k(s + bias.astype(jnp.float32), k)
    g = jnp.take_along_axis(s, sel, axis=-1)
    return sel, g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-6) * scaling


def _keys(sel, first: int, n_held: int):
    """Every slot's held expert, counted from ``first``; ``n_held`` for a
    slot of an expert that is not held: [S] integers."""
    key = sel.reshape(-1) - first
    return jnp.where((key >= 0) & (key < n_held), key, n_held)


def _slots(sel, first: int, n_held: int):
    """The k x N token slots in the order the grouped product wants them:
    (order [S] — slot ids, those of held experts first, by expert;
    sizes [n_held] — slots of each held expert; valid [S] — sorted slot
    belongs to a held expert)."""
    key = _keys(sel, first, n_held)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.bincount(key, length=n_held + 1)[:n_held].astype(jnp.int32)
    return order, sizes, key[order] < n_held


def _unsort(rows, order, k: int):
    """Sorted slot rows [S, ...] back to [N, k, ...]."""
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))
    return rows[inverse].reshape((-1, k) + rows.shape[1:])


def _grouped(x, w, sizes):
    """x [S, a] . w_e [a, b] for the rows of each expert e: float32 [S, b]."""
    return jax.lax.ragged_dot(x, w.astype(x.dtype), sizes,
                              preferred_element_type=jnp.float32)


def _grouped_t(x, w, sizes):
    """x [S, b] . w_e^T for w [G, a, b]: float32 [S, a] — the backward
    product.  XLA:TPU's grouped product wants the contracted dimension
    second, so the compiler copies the experts to that layout; the weights
    are invariant in the round's loops, so it makes the copy once a round,
    outside them, and a second copy of every expert layer lives through
    the round (PERF.md §6 PR 34: what bounds the layers one chip holds)."""
    return _grouped(x, jnp.swapaxes(w, 1, 2), sizes)


def _merged(fn, n_mapped: int):
    """``fn`` whose first ``n_mapped`` arguments (and every result) lead
    with rows, as a ``custom_vmap``: mapped over clients, the clients' rows
    are merged and ``fn`` runs once, on weights that are not mapped."""
    fn = custom_vmap(fn)

    @fn.def_vmap
    def rule(axis_size, in_batched, *args):
        assert not any(in_batched[n_mapped:]), "expert weights are not mapped"
        rows = [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, b in zip(args[:n_mapped], in_batched)]
        flat = [a.reshape((-1,) + a.shape[2:]) for a in rows]
        outs = fn(*flat, *args[n_mapped:])
        return (tuple(o.reshape((axis_size, -1) + o.shape[1:]) for o in outs),
                (True,) * len(outs))

    return fn


# a block's rows over what a uniform router sends the held experts
# (`block_rows`; PERF.md section 6, PR 43 has the chip readings behind it)
_BLOCK_SHARE = 1.5


def block_rows(n_slots: int, n_held: int, n_experts: int) -> int:
    """R, the rows of one block of the held-share product: the multiple of
    512 nearest ``_BLOCK_SHARE`` x the uniform router's expectation
    ``n_slots x n_held / n_experts``, at least 512 — a function of shapes."""
    expected = n_slots * n_held / n_experts
    return 512 * max(1, int(_BLOCK_SHARE * expected / 512 + 0.5))


def _blocks(sel, first: int, n_held: int, n_experts: int):
    """What the held-share product's block loop runs over: (order — `_slots`'
    order padded with slot 0 to whole blocks of R; starts, ends [n_held] —
    each held expert's interval of sorted positions; n_valid — the held
    experts' slots, which are the first n_valid sorted ones; R)."""
    order, sizes, _ = _slots(sel, first, n_held)
    R = block_rows(order.size, n_held, n_experts)
    ends = jnp.cumsum(sizes)
    return jnp.pad(order, (0, -order.size % R)), ends - sizes, ends, ends[-1], R


def _block(b, order, starts, ends, n_valid, R: int, k: int):
    """Block b of the sorted slots: (slot ids [R]; their tokens [R]; live
    [R] — the row is a held expert's slot; the held experts' group sizes
    inside the block [n_held])."""
    lo = b * R
    slot = jax.lax.dynamic_slice(order, (lo,), (R,))
    live = lo + jnp.arange(R, dtype=n_valid.dtype) < n_valid
    sizes = jnp.clip(ends, lo, lo + R) - jnp.clip(starts, lo, lo + R)
    return slot, slot // k, live, sizes


def _add_rows(m, token, rows):
    """m [N, d] with rows [R, d] added at their tokens (float32).  The
    scatter-add runs with d read as d / w rows of w, w the largest power of
    two that divides d, at most 4,096: XLA:TPU's row scatter keeps its rate
    on such rows (1.5 ms for 6,144 rows of 4,096) and loses it on others
    (8.0 ms for 4,608 rows of 5,120, 1.6 as 5 x 1,024: PERF.md section 6,
    PR 43)."""
    N, d = m.shape
    w = min(d & -d, 4096)
    at = (token[:, None] * (d // w) + jnp.arange(d // w, dtype=token.dtype))
    return m.reshape((-1, w)).at[at.reshape(-1)].add(
        rows.reshape((-1, w))).reshape((N, d))


def _held_share_rules(first: int, n_held: int, n_experts: int):
    """`expert_product`'s forward and backward rules for a layer that holds
    fewer experts than it routes over: both touch the sorted slots up to
    the last held one only, R = `block_rows` rows at a time — the block's
    tokens gathered, the grouped products on [R, ...], the block's rows
    added into the result at their tokens (float32, cast once).  The loop
    runs ``ceil(n_valid / R)`` blocks, a traced bound: if every token
    chose held experts that is all S slots.  No slot is dropped, there is
    no second branch, no float array leads with S, and nothing crosses
    from forward to backward: the backward block re-makes both
    pre-activations from the rows it gathers anyway."""

    def forward(f, sel, gate, w1, w3, w2):
        k = sel.shape[-1]
        with jax.named_scope(scopes.FED_MOE_ROUTER):
            order, starts, ends, n_valid, R = _blocks(sel, first, n_held,
                                                      n_experts)

        def block(b, m):
            with jax.named_scope(scopes.FED_MOE_ROUTER):
                slot, token, live, sizes = _block(b, order, starts, ends,
                                                  n_valid, R, k)
                xs = f[token]
            with jax.named_scope(scopes.FED_MOE_EXPERTS):
                a1, a3 = _grouped(xs, w1, sizes), _grouped(xs, w3, sizes)
                y = _grouped((jax.nn.silu(a1) * a3).astype(f.dtype), w2, sizes)
            with jax.named_scope(scopes.FED_MOE_ROUTER):
                gs = gate.reshape(-1)[slot][:, None]
                return _add_rows(m, token, jnp.where(live[:, None], y * gs, 0.0))

        m = jax.lax.fori_loop(0, -(-n_valid // R), block,
                              jnp.zeros_like(f, jnp.float32))
        return (m.astype(f.dtype),)

    def backward(f, sel, gate, dm, w1, w3, w2):
        k, dt = sel.shape[-1], f.dtype
        with jax.named_scope(scopes.FED_MOE_ROUTER):
            order, starts, ends, n_valid, R = _blocks(sel, first, n_held,
                                                      n_experts)

        def block(b, carry):
            df, dgate = carry
            with jax.named_scope(scopes.FED_MOE_ROUTER):
                slot, token, live, sizes = _block(b, order, starts, ends,
                                                  n_valid, R, k)
                xs, dms = f[token], dm[token].astype(dt)
                gs = gate.reshape(-1)[slot][:, None]
            with jax.named_scope(scopes.FED_MOE_EXPERTS):
                a1, a3 = _grouped(xs, w1, sizes), _grouped(xs, w3, sizes)
                back = lambda d, w: jnp.where(
                    live[:, None], _grouped_t(d.astype(dt), w, sizes), 0.0)
                u = back(dms, w2)
                sig = jax.nn.sigmoid(a1)
                act = a1 * sig
                dg = jnp.sum(jnp.where(
                    live[:, None],
                    (act * a3).astype(dt).astype(jnp.float32) * u, 0.0), axis=-1)
                dh = gs * u
                dxs = (back(dh * a3 * sig * (1.0 + a1 * (1.0 - sig)), w1)
                       + back(dh * act, w3))
            with jax.named_scope(scopes.FED_MOE_ROUTER):
                return _add_rows(df, token, dxs), dgate.at[slot].add(dg)

        df, dgate = jax.lax.fori_loop(
            0, -(-n_valid // R), block,
            (jnp.zeros_like(f, jnp.float32),
             jnp.zeros_like(gate, jnp.float32).reshape(-1)))
        return df.astype(dt), dgate.reshape(sel.shape)

    return forward, backward


@functools.lru_cache(maxsize=None)
def expert_product(first: int, n_held: int, n_experts: int):
    """``m = product(f, sel, gate, w1, w3, w2)``: the held experts' share
    of an expert layer's output for tokens f [N, d] routed to ``sel`` with
    weights ``gate`` ([N, k]) over ``n_experts``; w1, w3 [n_held, d, width],
    w2 [n_held, width, d].  Differentiable in f and gate; the weights are
    read, not trained.  **Where every expert is held** the product runs
    over all S = k x N sorted slots at once, and sorted-slot residuals (both
    pre-activations, the order) cross from the forward to the backward
    pass in the merged layout of `_merged`; **where a share is held** it
    runs over the held experts' slots alone (`_held_share_rules`).  What
    tells them apart is what the layer observes — every slot is valid, so
    there is nothing to skip — and no setting."""

    def forward(f, sel, gate, w1, w3, w2):
        k = sel.shape[-1]
        with jax.named_scope(scopes.FED_MOE_ROUTER):
            order, sizes, valid = _slots(sel, first, n_held)
            xs = f[order // k]
        with jax.named_scope(scopes.FED_MOE_EXPERTS):
            a1, a3 = _grouped(xs, w1, sizes), _grouped(xs, w3, sizes)
            y = _grouped((jax.nn.silu(a1) * a3).astype(f.dtype), w2, sizes)
        with jax.named_scope(scopes.FED_MOE_ROUTER):
            y = jnp.where(valid[:, None], y, 0.0)
            m = jnp.sum(_unsort(y, order, k) * gate[..., None], axis=1)
        # residual rows: one per token, so that a merge splits them evenly
        res = lambda a: a.reshape((f.shape[0], -1))
        return m.astype(f.dtype), res(a1), res(a3), res(order)

    def backward(f, sel, gate, a1, a3, order, dm, w1, w3, w2):
        k, dt = sel.shape[-1], f.dtype
        a1, a3 = (a.reshape((order.size, -1)) for a in (a1, a3))
        order = order.reshape(-1)
        with jax.named_scope(scopes.FED_MOE_ROUTER):
            _, sizes, valid = _slots(sel, first, n_held)
            dms = dm[order // k].astype(dt)
            gs = gate.reshape(-1)[order][:, None]
        with jax.named_scope(scopes.FED_MOE_EXPERTS):
            back = lambda d, w: jnp.where(
                valid[:, None], _grouped_t(d.astype(dt), w, sizes), 0.0)
            u = back(dms, w2)
            sig = jax.nn.sigmoid(a1)
            act = a1 * sig
            dgate = jnp.sum((act * a3).astype(dt).astype(jnp.float32) * u, axis=-1)
            dh = gs * u
            dxs = (back(dh * a3 * sig * (1.0 + a1 * (1.0 - sig)), w1)
                   + back(dh * act, w3))
        with jax.named_scope(scopes.FED_MOE_ROUTER):
            df = jnp.sum(_unsort(dxs, order, k), axis=1).astype(dt)
            return df, _unsort(dgate, order, k)

    n_kept = 3               # a1, a3, order: what `forward` returns beside m
    if n_held < n_experts:
        forward, backward = _held_share_rules(first, n_held, n_experts)
        n_kept = 0
    # mapped: f, sel, gate (and, backward, what was kept and dm)
    forward_m, backward_m = _merged(forward, 3), _merged(backward, 4 + n_kept)

    @jax.custom_vjp
    def product(f, sel, gate, w1, w3, w2):
        return forward_m(f, sel, gate, w1, w3, w2)[0]

    def fwd(f, sel, gate, w1, w3, w2):
        m, *kept = forward_m(f, sel, gate, w1, w3, w2)
        return m, (f, sel, gate, *kept, w1, w3, w2)

    def bwd(res, dm):
        *rows, w1, w3, w2 = res
        df, dgate = backward_m(*rows, dm, w1, w3, w2)
        return df, None, dgate.astype(rows[2].dtype), None, None, None

    product.defvjp(fwd, bwd)
    return product


@functools.lru_cache(maxsize=None)
def _rows_run(first: int, n_held: int, n_experts: int):
    """``(share,) = rows(sel)``: the rows the held-share product's blocks
    run for the tokens routed to ``sel`` [N, k], as whole numbers spread
    over the N tokens (float32 [N]; their sum is the rows) — merged over a
    chunk's clients as the product is, so the sum over clients is what
    the one merged product ran."""

    def rows(sel):
        n = sel.shape[0]
        n_valid = jnp.sum(_keys(sel, first, n_held) < n_held)
        R = block_rows(sel.size, n_held, n_experts)
        ran = -(-n_valid // R) * R
        return ((ran // n + (jnp.arange(n) < ran % n)).astype(jnp.float32),)

    return _merged(rows, 1)


def held_share(rows, sel, gate, lp, first: int, n_held: int):
    """(m, the layer's counters) for tokens ``rows`` [N, d] routed to
    ``sel`` with weights ``gate``: the share of the layer's output that the
    held experts ``lp["w1"], lp["w3"], lp["w2"]`` give (`expert_product`);
    tokens routed to every expert [n_experts], held or not, and (rows the
    grouped products ran over, slots routed) — equal where every expert is
    held."""
    n_experts = lp["router"].shape[-1]
    with jax.named_scope(scopes.FED_MOE_ROUTER):
        tokens = jnp.bincount(sel.reshape(-1), length=n_experts)
    m = expert_product(first, n_held, n_experts)(
        rows, sel, gate, lp["w1"], lp["w3"], lp["w2"])
    if n_held == n_experts:
        slot_rows = np.full((2,), sel.size, np.float32)
    else:
        with jax.named_scope(scopes.FED_MOE_ROUTER):
            ran = jnp.sum(_rows_run(first, n_held, n_experts)(sel)[0])
            slot_rows = jnp.stack([ran, jnp.float32(sel.size)])
    return m, {scopes.MOE_EXPERT_TOKENS: tokens, scopes.MOE_SLOT_ROWS: slot_rows}


def moe_layer(f, lp, k: int, scaling: float, held=None):
    """(m, the layer's counters: `held_share`) of one expert layer for f
    [..., d]; ``lp``: router, expert_bias and the experts HELD (``held`` =
    (first, past-last) expert id; None = all)."""
    first, last = held if held is not None else (0, lp["router"].shape[-1])
    rows = f.reshape((-1, f.shape[-1]))
    with jax.named_scope(scopes.FED_MOE_ROUTER):
        sel, gate = route(rows, lp["router"], lp["expert_bias"], k, scaling)
    m, counts = held_share(rows, sel, gate, lp, first, last - first)
    return m.reshape(f.shape), float_counters(counts)


def float_counters(counts: dict) -> dict:
    """A layer's counters as the trainer sums them: float32."""
    return {name: c.astype(jnp.float32) for name, c in counts.items()}


def counter_shapes(n_expert_layers: int, n_experts: int) -> dict:
    """A model's ``counters`` for `sow_counters`: {name: shape}."""
    return {scopes.MOE_EXPERT_TOKENS: (n_expert_layers, n_experts),
            scopes.MOE_SLOT_ROWS: (n_expert_layers, 2)}


def sow_counters(module, counts: list) -> None:
    """Sow the expert layers' counters (a `moe_layer`'s second result, one
    a layer), stacked by layer, where the caller asks for the collection."""
    if (counts and not module.is_initializing()
            and module.is_mutable_collection(scopes.COUNTERS)):
        for name in counts[0]:
            module.sow(scopes.COUNTERS, name,
                       jnp.stack([c[name] for c in counts]),
                       init_fn=lambda: 0.0, reduce_fn=lambda a, b: a + b)


class _Leaves(nn.Module):
    """A named group of parameters: ((name, shape, init, dtype), ...)."""
    specs: tuple

    @nn.compact
    def __call__(self):
        return {name: self.param(name, init, shape, dtype)
                for name, shape, init, dtype in self.specs}


class _Groups(nn.Module):
    """Named groups of parameters under one name of their own (the
    adapters: ``lora/layer_<i>/<matrix>_a``)."""
    specs: tuple

    @nn.compact
    def __call__(self):
        return {name: _Leaves(s, name=name)() for name, s in self.specs}


class Lfm2MoeLM(nn.Module):
    """tokens [B, T] int -> float32 logits [B, T, vocab]."""
    vocab_size: int
    d_model: int = 64
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    d_ff: int = 96                       # the dense layers' MLP width
    d_expert: int = 32
    n_experts: int = 8
    experts_per_token: int = 2
    layer_types: tuple = ("conv", "conv", "full_attention", "conv")
    num_dense_layers: int = 1
    layers: Optional[tuple] = None       # ids of the layers held; None = all
    held_experts: Optional[tuple] = None  # (first, past-last); None = all
    conv_kernel: int = 3
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    routed_scaling_factor: float = 1.0
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
    def expert_layers(self) -> tuple:
        return tuple(i for i in self.held_layers if i >= self.num_dense_layers)

    @property
    def counters(self) -> dict:
        return counter_shapes(len(self.expert_layers), self.n_experts)

    def _specs(self, i: int):
        """(base, adapter) leaf specs of layer i."""
        d, hd, bt = self.d_model, self.head_dim, self.base_dtype
        normal, ones = nn.initializers.normal(self.init_std), nn.initializers.ones
        mats = ({"in_proj": (d, 3 * d), "out_proj": (d, d)}
                if self.layer_types[i] == "conv" else
                {"wq": (d, self.n_heads * hd), "wk": (d, self.n_kv_heads * hd),
                 "wv": (d, self.n_kv_heads * hd), "wo": (self.n_heads * hd, d)})
        base = [(n, s, normal, bt) for n, s in mats.items()]
        base += [("op_norm", (d,), ones, bt), ("ffn_norm", (d,), ones, bt)]
        if self.layer_types[i] == "conv":
            base.append(("conv_kernel", (self.conv_kernel, d), normal, bt))
        else:
            base += [("q_norm", (hd,), ones, bt), ("k_norm", (hd,), ones, bt)]
        if i < self.num_dense_layers:
            base += [("w1", (d, self.d_ff), normal, bt),
                     ("w3", (d, self.d_ff), normal, bt),
                     ("w2", (self.d_ff, d), normal, bt)]
        else:
            first, last = self.held_experts or (0, self.n_experts)
            e, w = last - first, self.d_expert
            base += [("router", (d, self.n_experts), normal, bt),
                     ("expert_bias", (self.n_experts,), nn.initializers.zeros, bt),
                     ("w1", (e, d, w), normal, bt), ("w3", (e, d, w), normal, bt),
                     ("w2", (e, w, d), normal, bt)]
        r = self.lora_rank
        adapters = []
        for n, (d_in, d_out) in mats.items():
            adapters += [
                (n + "_a", (d_in, r), nn.initializers.normal(d_in ** -0.5), jnp.float32),
                (n + "_b", (r, d_out), nn.initializers.zeros, jnp.float32)]
        return tuple(base), tuple(adapters)

    def _layer(self, i: int, h, lp, ad, cos, sin):
        eps, scale = self.norm_eps, self.lora_alpha / self.lora_rank
        if self.layer_types[i] == "conv":
            with jax.named_scope(scopes.FED_SHORT_CONV):
                h = h + short_conv(h, lp, ad, scale, eps)
        else:
            with jax.named_scope(scopes.FED_ATTENTION):
                h = h + gqa_attention(h, lp, ad, scale, eps, cos, sin,
                                      self.n_heads, self.n_kv_heads)
        if i < self.num_dense_layers:
            with jax.named_scope(scopes.FED_MLP):
                f = rms_norm(h, lp["ffn_norm"], eps)
                return h + gated_mlp(f, lp["w1"], lp["w3"], lp["w2"]), None
        with jax.named_scope(scopes.FED_MOE_ROUTER):
            f = rms_norm(h, lp["ffn_norm"], eps)
        m, counts = moe_layer(f, lp, self.experts_per_token,
                              self.routed_scaling_factor, self.held_experts)
        return h + m, counts

    @nn.compact
    def __call__(self, x, train: bool = False):
        normal = nn.initializers.normal(self.init_std)
        embed = self.param("embed", normal, (self.vocab_size, self.d_model),
                           self.base_dtype)
        out_norm = self.param("out_norm", nn.initializers.ones,
                              (self.d_model,), self.base_dtype)
        specs = {i: self._specs(i) for i in self.held_layers}
        base = {i: _Leaves(specs[i][0], name=f"layer_{i}")()
                for i in self.held_layers}
        lora = _Groups(tuple((f"layer_{i}", specs[i][1])
                             for i in self.held_layers), name="lora")()
        dt = jax.tree.leaves(lora)[0].dtype          # the adapters': compute
        cos, sin = rotary_tables(x.shape[-1], self.head_dim, self.rope_theta)
        h = embed[x.astype(jnp.int32)].astype(dt)
        counts = []
        for i in self.held_layers:
            layer = jax.checkpoint(functools.partial(self._layer, i))
            h, c = layer(h, base[i], lora[f"layer_{i}"], cos, sin)
            if c is not None:
                counts.append(c)
        sow_counters(self, counts)
        with jax.named_scope(scopes.FED_LM_HEAD):
            s = rms_norm(h, out_norm, self.norm_eps)
            return jnp.einsum("...d,vd->...v", s, embed.astype(dt),
                              preferred_element_type=jnp.float32)
