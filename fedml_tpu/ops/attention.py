"""Causal softmax attention — the one place the repo spells
``softmax_causal(q k^T / sqrt(hd)) v``.

``causal_attention(q, k, v)`` is the core of the language models'
attention (`models/looped_lm.py::decoder_layer`: 16 heads of 128 at
T = 1,024; `models/lfm2_moe.py::gqa_attention`: 32 query / 8 key-value
heads of 64 at T = 2,048; `models/deepseek_v2.py::latent_attention`: 128
heads at T = 4,096 in the TWO-PART form, ``rope=(q_rope, k_rope)`` and a
given ``scale`` - scores ``(q k^T + q_rope k_rope^T) * scale`` with 128-wide
content parts, 64-wide rotary parts and ONE rotary key head that every
query head reads; `models/cohere2_moe.py::attention`: 128 query / 8
key-value heads of 128 at T = 8,192, its sliding layers under ``window`` =
4,096 - key j visible to query i iff ``0 <= i - j < window`` - and its full
layers under none); projections, norms and rotary stay with the models.
Two bodies, one result:

* **the plain path** — einsum, mask, ``jax.nn.softmax``, einsum: the
  numerical spec, and what a program lowered for anything but a TPU (or a
  shape the kernels do not take) runs.  It writes the float32
  ``[B, H, T, T]`` scores to memory, reads them back several times, and
  computes the full ``T x T`` products.
* **the fused path** — a Pallas TPU forward and backward
  (``jax.custom_vjp``) in which the scores exist one ``tile x tile`` block
  at a time in fast memory and never reach HBM.  Forward: for each query
  block, an online softmax over the key blocks up to the diagonal (the
  blocks above it are not visited, the diagonal one is masked), returning
  ``o`` and the rows' log-sum-exp.  Backward: for each key block, the
  query blocks from the diagonal down; ``p`` is recomputed from ``q``,
  ``k`` and the log-sum-exp, then ``dv += p^T do``, ``dp = do v^T``,
  ``ds = p (dp - delta)`` with ``delta = rowsum(o do)``, ``dk += ds^T q``,
  ``dq += ds k``.  Grouped-query attention reads key/value head
  ``h // group`` through the block index map; ``dk`` / ``dv`` come out per
  query head and are summed over the group in float32.  **Under a window**
  the same kernels visit only the blocks inside the band (`_band`): forward
  from the first key block a query block reaches, backward to the last
  query block that reaches the key block; the blocks the band's far edge
  crosses are masked on that side as the diagonal one is on its own, the
  blocks between are whole, and the blocks outside are not read - loop
  bounds, as above the diagonal.  The blocks a windowed call visits and the
  causal blocks in all are counted at trace time in
  ``attention_band_blocks_total{blocks="visited" | "causal"}``
  (`band_blocks`).  Without a window the kernels are what they were before
  they took one: the same jaxpr, equation for equation.

**Precision is the plain path's**: operands in the compute dtype, every
product accumulated in float32; scores, max, exp, sum and the running
accumulator in float32; ``p`` (and ``ds``) cast to the compute dtype only
as operands of ``p v``, ``p^T do``, ``ds^T q`` and ``ds k``.  No bfloat16
exp, no approximated reciprocal, no dropped row.  (``dp`` stays float32
here where the plain path's autodiff rounds it to the compute dtype.)

**Which body runs is read off the program, not configured**: the fused
path where the program is LOWERED for a TPU (``jax.lax.platform_dependent``:
a compile for a described chip from a CPU process sees the kernels), ``T``
is a multiple of 128, the head size (and a rotary part's) is 64 or 128 and
the operands are bfloat16 or float32; the plain path otherwise.  The tile is the largest
of 512, 256, 128 that divides ``T``.  Heads as wide as the lanes (128) are
read where they lie in ``[B, T, H, hd]``; narrower ones are brought
heads-first around the kernels.  The decision is counted at trace time in
``ops_kernel_path_total{op="causal_attention", path=...}`` (both bodies of
an eligible shape are traced, so ``path="pallas"`` says what a TPU
lowering takes), and a shape that does not qualify is named in a warning.

**Under a `jax.checkpoint`** whose policy lists ``SAVED_NAMES`` (the
forward rule's ``o`` and log-sum-exp carry them) the two are kept and the
forward kernel runs once (`models/looped_lm.py`; ``q``, ``k``, ``v`` are the
caller's to keep or to recompute); any other checkpoint ignores the names
and runs the rule again (`models/lfm2_moe.py`).
"""
from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedml_tpu import obs
from fedml_tpu.obs import scopes

log = logging.getLogger(__name__)

# what the forward rule hands its backward rule besides the operands, by the
# names a `jax.checkpoint` policy can list: the output and the rows'
# log-sum-exp
SAVED_NAMES = ("causal_attention_o", "causal_attention_lse")

_MASK = float(jnp.finfo(jnp.float32).min)
_NT = (((1,), (1,)), ((), ()))       # a [m, d] . b [n, d] -> [m, n]
_NN = (((1,), (0,)), ((), ()))       # a [m, d] . b [d, n] -> [m, n]
_TN = (((0,), (0,)), ((), ()))       # a [d, m] . b [d, n] -> [m, n]


# -- the plain path -----------------------------------------------------------

def _visible(T: int, window=None):
    """[T (queries), T (keys)] bool: key j <= query i and, under a sliding
    window, ``i - j < window``."""
    causal = jnp.tril(jnp.ones((T, T), bool))
    return causal if window is None else jnp.triu(causal, 1 - window)


def _plain(q, k, v, *rope, scale=None, window=None):
    """einsum, mask, softmax, einsum, as both models spelled it before
    they shared it: query heads grouped over their key/value head, and the
    ungrouped products where every head has its own (the same mathematics;
    XLA:CPU rounds the two spellings' gradients differently, and each
    model's CPU results stay what they were to the bit)."""
    B, T, H, hd = q.shape
    dt, n_kv = q.dtype, k.shape[2]
    scale = hd ** -0.5 if scale is None else scale
    if rope:
        return _plain_two_part(q, k, v, *rope, scale, window)
    grouped = n_kv != H
    if grouped:
        q = q.reshape(B, T, n_kv, H // n_kv, hd)
    s = jnp.einsum("btgrd,bsgd->bgrts" if grouped else "bqhd,bkhd->bhqk",
                   q, k, preferred_element_type=jnp.float32) * scale
    s = jnp.where(_visible(T, window), s, _MASK)
    w = jax.nn.softmax(s, axis=-1).astype(dt)
    o = jnp.einsum("bgrts,bsgd->btgrd" if grouped else "bhqk,bkhd->bqhd",
                   w, v, preferred_element_type=jnp.float32).astype(dt)
    return o.reshape(B, T, H, hd)


def _plain_two_part(q, k, v, q_rope, k_rope, scale, window=None):
    """Scores that are the sum of two products (latent attention:
    ``q . k + q_rope . k_rope``, the rotary key of one head read by many
    query heads), any head sizes: every key head is repeated for the query
    heads it serves, which only a small shape can afford."""
    H, dt = q.shape[2], q.dtype
    wide = lambda a: jnp.repeat(a, H // a.shape[2], axis=2)
    scores = lambda a, b: jnp.einsum("bqhd,bkhd->bhqk", a, wide(b),
                                     preferred_element_type=jnp.float32)
    s = (scores(q, k) + scores(q_rope, k_rope)) * scale
    s = jnp.where(_visible(s.shape[-1], window), s, _MASK)
    w = jax.nn.softmax(s, axis=-1).astype(dt)
    return jnp.einsum("bhqk,bkhd->bqhd", w, wide(v),
                      preferred_element_type=jnp.float32).astype(dt)


# -- the fused path -----------------------------------------------------------

def _tile(T: int):
    """The rows a kernel instance owns (queries forward, keys backward),
    and the columns of the other axis it takes at a time — the scores in
    flight are ``[tile, tile]``: the largest of 512, 256, 128 that divides
    T, or None where none does.  (On the chip, of nine pairings of tile and
    columns at T = 2,048 with heads of 64, 512 x 512 was the fastest, and
    the smaller the scores in flight the slower: PERF.md section 6, PR 35.)"""
    return next((b for b in (512, 256, 128) if T % b == 0), None)


def _fits(q, k, v, *rope) -> bool:
    """The kernels' requirement on shapes and dtype (module docstring)."""
    heads = lambda a, b: (a.ndim == b.ndim == 4 and a.shape[-1] == b.shape[-1]
                          and a.shape[:2] == b.shape[:2] == q.shape[:2]
                          and a.shape[-1] in (64, 128)
                          and q.shape[2] == a.shape[2]
                          and a.shape[2] % b.shape[2] == 0
                          and a.dtype == b.dtype == q.dtype)
    return (heads(q, k) and k.shape == v.shape and v.dtype == q.dtype
            and q.dtype in (jnp.bfloat16, jnp.float32)
            and _tile(q.shape[1]) is not None
            and (not rope or heads(*rope)))


def _dot(a, b, dims):
    """A product on the MXU, accumulated in float32.  The precision is
    spelled out so that an ambient ``jax.default_matmul_precision`` cannot
    change the kernel: bfloat16 products are exact in one pass, float32
    operands take every pass."""
    precision = (jax.lax.Precision.HIGHEST if a.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=jnp.float32)


def _causal(s, queries_axis: int):
    """The tile of scores that the diagonal crosses (queries and keys from
    the same position on): key position <= query position, or the plain
    path's mask value."""
    qpos = jax.lax.broadcasted_iota(jnp.int32, s.shape, queries_axis)
    kpos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - queries_axis)
    return jnp.where(kpos <= qpos, s, _MASK)


def _inside(s, queries_axis: int, ahead, window: int):
    """A tile of scores that the far edge of the band may cross, its
    queries ``ahead`` positions after its keys: query position - key
    position < ``window``, or the plain path's mask value."""
    qpos = jax.lax.broadcasted_iota(jnp.int32, s.shape, queries_axis)
    kpos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - queries_axis)
    return jnp.where(qpos - kpos + ahead < window, s, _MASK)


def _band(tile: int, window: int):
    """(reach, whole): a block of queries sees the key blocks at block
    distances 0 .. ``reach`` behind it; those at distances 1 .. ``whole`` - 1
    lie wholly inside the band, those from ``whole`` on (and the diagonal one
    itself where the window is narrower than a tile, ``whole`` = 0) hold a
    pair ``window`` or more apart and are masked."""
    return (window + tile - 2) // tile, window // tile


def band_blocks(T: int, window=None):
    """(visited, causal): the ``tile x tile`` blocks of scores the kernels
    compute for one head of T positions under ``window``, and the blocks on
    and below the diagonal."""
    tile = _tile(T)
    n = T // tile
    reach = n if window is None else _band(tile, window)[0]
    return sum(min(i, reach) + 1 for i in range(n)), n * (n + 1) // 2


def _fwd_kernel(*refs, scale, two_part=False, window=None):
    """One tile of queries [tile, hd] against the keys [T, hd] of its head,
    a tile of keys at a time up to the diagonal - from the start, or under a
    ``window`` from the first block inside the band (`_band`); scores are
    [tile (queries), tile (keys)].  ``two_part``: a second pair of operands,
    the queries' [tile, r] and the keys' [T, r] rotary parts, whose
    product is added to the scores."""
    q_ref, k_ref, v_ref = refs[:3]
    o_ref, lse_ref = refs[-2:]
    i = pl.program_id(2)
    q = q_ref[...]
    tile, hd = q.shape
    if two_part:
        qr_ref, kr_ref = refs[3:5]
        qr = qr_ref[...]

    def step(j, carry, diagonal=False, edge=False):
        m, l, acc = carry
        rows = pl.ds(pl.multiple_of(j * tile, tile), tile)
        k, v = k_ref[rows, :], v_ref[rows, :]
        s = _dot(q, k, _NT)
        if two_part:
            s = s + _dot(qr, kr_ref[rows, :], _NT)
        s = s * scale
        if diagonal:
            s = _causal(s, queries_axis=0)
        if edge:
            # a row may lose every key of this block: its statistics then
            # hold the mask value until a later block, which every row has
            # (its own position, at the latest), rescales them to nothing
            s = _inside(s, 0, (i - j) * tile, window)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        acc = alpha * acc + _dot(p.astype(v.dtype), v, _NN)
        return m_new, l, acc

    carry = (jnp.full((tile, 1), -jnp.inf, jnp.float32),
             jnp.zeros((tile, 1), jnp.float32),
             jnp.zeros((tile, hd), jnp.float32))
    if window is None:
        carry = jax.lax.fori_loop(0, i, step, carry)
        m, l, acc = step(i, carry, diagonal=True)
    else:
        reach, whole = _band(tile, window)
        first = jnp.maximum(i - reach, 0)
        masked = jnp.clip(i - whole + 1, first, i)
        carry = jax.lax.fori_loop(
            first, masked, functools.partial(step, edge=True), carry)
        carry = jax.lax.fori_loop(masked, i, step, carry)
        m, l, acc = step(i, carry, diagonal=True, edge=whole == 0)
    o_ref[...] = (acc / l).astype(o_ref.dtype)
    # the rows' statistics leave as one lane-major row [1, tile]
    lse_ref[...] = jnp.broadcast_to(m + jnp.log(l), (tile, 128)).T[:1]


def _bwd_kernel(*refs, scale, two_part=False, window=None):
    """One tile of keys [tile, hd] against the queries [T, hd] of one head,
    a tile of queries at a time from the diagonal down - to the end, or under
    a ``window`` to the last block inside the band; scores are
    transposed, [tile (keys), tile (queries)], so the rows' statistics
    broadcast along sublanes.  ``dq`` [T, hd] stays in fast memory across
    the head's key tiles.  ``two_part``: the rotary parts of the queries
    [T, r] and of the key tile [tile, r] follow the six operands, their
    gradients the three results."""
    q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref = refs[:6]
    dq_ref, dk_ref, dv_ref = refs[-5:-2] if two_part else refs[-3:]
    j = pl.program_id(2)
    k, v = k_ref[...], v_ref[...]
    tile, dt = k.shape[0], k.dtype
    if two_part:
        qr_ref, kr_ref = refs[6:8]
        dqr_ref, dkr_ref = refs[-2:]
        kr = kr_ref[...]

    @pl.when(j == 0)
    def _():
        dq_ref[...] = jnp.zeros_like(dq_ref)
        if two_part:
            dqr_ref[...] = jnp.zeros_like(dqr_ref)

    def step(i, carry, diagonal=False, edge=False):
        dk, dv = carry[:2]
        rows = pl.ds(pl.multiple_of(i * tile, tile), tile)
        q, do = q_ref[rows, :], do_ref[rows, :]
        s = _dot(k, q, _NT)
        if two_part:
            qr = qr_ref[rows, :]
            s = s + _dot(kr, qr, _NT)
        s = s * scale
        if diagonal:
            s = _causal(s, queries_axis=1)
        if edge:
            s = _inside(s, 1, (i - j) * tile, window)
        p = jnp.exp(s - lse_ref[i])
        dv = dv + _dot(p.astype(dt), do, _NN)
        dp = _dot(v, do, _NT)
        ds = (p * (dp - delta_ref[i]) * scale).astype(dt)
        dk = dk + _dot(ds, q, _NN)
        dq_ref[rows, :] += _dot(ds, k, _TN)
        if not two_part:
            return dk, dv
        dqr_ref[rows, :] += _dot(ds, kr, _TN)
        return dk, dv, carry[2] + _dot(ds, qr, _NN)

    carry = (jnp.zeros(k.shape, jnp.float32),) * 2
    if two_part:
        carry += (jnp.zeros(kr.shape, jnp.float32),)
    n = q_ref.shape[0] // tile
    if window is None:
        carry = step(j, carry, diagonal=True)
        carry = jax.lax.fori_loop(j + 1, n, step, carry)
    else:
        reach, whole = _band(tile, window)
        end = jnp.minimum(j + reach + 1, n)
        masked = jnp.clip(j + whole, j + 1, end)
        carry = step(j, carry, diagonal=True, edge=whole == 0)
        carry = jax.lax.fori_loop(j + 1, masked, step, carry)
        carry = jax.lax.fori_loop(
            masked, end, functools.partial(step, edge=True), carry)
    dk_ref[...] = carry[0]
    dv_ref[...] = carry[1]
    if two_part:
        dkr_ref[...] = carry[2]


# How the kernels reach one head of a [B, T, H, hd] operand.  A head as wide
# as the lanes (hd a multiple of 128) is read IN PLACE: columns h of
# [B, T, H * hd], a free reshape, cut out by the block itself.  A narrower
# head cannot be cut out of the lanes by a block, so those operands are
# brought heads-first, [B, H, T, hd]: transposes, which XLA folds into their
# neighbours where it can.  The kernels see [rows, hd] either way.  (Measured
# in `ouro2p6b`, heads of 128, against heads-first for every head size: 66 ms
# of copies a round less, most of it back inside the matrix products that
# had absorbed the relayouts, + 0.15 % rounds a second; PERF.md section 6,
# PR 35.)

def _kernel_layout(a):
    B, T, H, hd = a.shape
    return a.reshape(B, T, H * hd) if hd % 128 == 0 else jnp.swapaxes(a, 1, 2)


def _model_layout(a, H: int):
    """`_kernel_layout`'s inverse, for a result of H heads."""
    if a.ndim == 3:
        return a.reshape(*a.shape[:2], H, -1)
    return jnp.swapaxes(a, 1, 2)


def _block(rows: int, hd: int, row, head=lambda h: h):
    """``rows`` positions of one head in the kernels' layout: block
    ``row(i)`` of the positions, head ``head(h)``, at grid point (b, h, i)."""
    if hd % 128 == 0:
        return pl.BlockSpec((None, rows, hd),
                            lambda b, h, i: (b, row(i), head(h)))
    return pl.BlockSpec((None, None, rows, hd),
                        lambda b, h, i: (b, head(h), row(i), 0))


def _struct(like, shape, dtype):
    """A result's shape with the operands' varying mesh axes: under
    ``shard_map(check_vma=True)`` jax refuses a ``pallas_call`` whose
    results do not say over which axes they vary."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


# a kernel's blocks, double-buffered, and its tiles of scores may take this
# much fast memory before the call asks for more than Mosaic's default
# (16 MiB on a v5e; every shape of before latent attention stays under it)
_VMEM_DEFAULT = 12 * 2 ** 20


def _vmem(need: int) -> dict:
    """Nothing where a grid point's ``need`` bytes of fast memory stay under
    `_VMEM_DEFAULT`; the call's stated limit where they pass it."""
    return {} if need <= _VMEM_DEFAULT else {
        "vmem_limit_bytes": min(2 * need, 96 * 2 ** 20)}


def _params(interpret, blocks=(), tile=0):
    """How the call is run: Mosaic with the grid's semantics (batch and
    heads independent, the blocks of a head in order), or an interpreter
    (the CPU tests'; it takes no compiler parameters).  ``blocks`` are the
    (rows, columns, dtype) a grid point holds: where they and six float32
    ``tile x tile`` intermediates pass `_VMEM_DEFAULT` (whole-sequence
    blocks at T = 4,096 with a rotary part), the call states its need."""
    if interpret:
        return dict(interpret=interpret)
    need = (2 * sum(r * c * jnp.dtype(d).itemsize for r, c, d in blocks)
            + 6 * 4 * tile * tile)
    return dict(compiler_params=pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        **_vmem(need)))


def _fused_output_lse(q, k, v, *rope, scale, window, interpret):
    """o [B, T, H, hd] and the rows' log-sum-exp [B, H, T] in float32."""
    B, T, H, hd = q.shape
    tile = _tile(T)
    own = lambda a: _block(tile, a.shape[-1], row=lambda i: i)
    whole_kv = lambda a: _block(T, a.shape[-1], row=lambda i: 0,
                                head=_shared(H, a))
    stats = pl.BlockSpec((None, None, None, 1, tile),
                         lambda b, h, i: (b, h, i, 0, 0))
    specs = [own(q), whole_kv(k), whole_kv(v)]
    blocks = [(tile, hd, q.dtype)] * 2 + [(T, hd, q.dtype)] * 2
    if rope:
        specs += [own(rope[0]), whole_kv(rope[1])]
        blocks += [(tile + T, rope[0].shape[-1], q.dtype)]
    q, k, v, *rope = map(_kernel_layout, (q, k, v) + rope)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, two_part=bool(rope),
                          window=window),
        grid=(B, H, T // tile), in_specs=specs,
        out_specs=[specs[0], stats],
        out_shape=[_struct(q, q.shape, q.dtype),
                   _struct(q, (B, H, T // tile, 1, tile), jnp.float32)],
        **_params(interpret, blocks, tile))(q, k, v, *rope)
    return _model_layout(o, H), lse.reshape(B, H, T)


def _shared(H: int, kv):
    """The key/value head of ``kv`` [B, T, H_kv, hd] that query head h
    of H reads."""
    group = H // kv.shape[2]
    return lambda h: h // group


def _fused_grads(q, k, v, o, lse, do, *rope, scale, window, interpret):
    """(dq, dk, dv[, dq_rope, dk_rope]) in the operands' shapes and dtype."""
    B, T, H, hd = q.shape
    tile = _tile(T)
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    # one lane-major row [1, tile] of statistics for each block of queries
    by_block = lambda a: a.reshape(B, H, T // tile, 1, tile)
    stats = pl.BlockSpec((None, None, T // tile, 1, tile),
                         lambda b, h, j: (b, h, 0, 0, 0))
    whole = lambda a: _block(T, a.shape[-1], row=lambda j: 0)
    own = lambda a: _block(tile, a.shape[-1], row=lambda j: j)
    own_kv = lambda a: _block(tile, a.shape[-1], row=lambda j: j,
                              head=_shared(H, a))
    in_specs = [whole(q), whole(q), stats, stats, own_kv(k), own_kv(v)]
    out_specs = [whole(q), own(q), own(q)]
    blocks = ([(T, hd, q.dtype)] * 2 + [(T, hd, jnp.float32)]
              + [(tile, hd, q.dtype)] * 2 + [(tile, hd, jnp.float32)] * 2)
    if rope:
        in_specs += [whole(rope[0]), own_kv(rope[1])]
        out_specs += [whole(rope[0]), own(rope[0])]
        r = rope[0].shape[-1]
        blocks += [(T + tile, r, q.dtype), (T + tile, r, jnp.float32)]
    operands = (q, k, v) + rope
    q, k, v, do, *ropes = map(_kernel_layout, (q, k, v, do) + rope)
    per_head = [_struct(q, q.shape, jnp.float32)] * 3 + [
        _struct(q, a.shape, jnp.float32) for a in ropes[:1] * 2]
    grads = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, two_part=bool(rope),
                          window=window),
        grid=(B, H, T // tile), in_specs=in_specs,
        out_specs=out_specs, out_shape=per_head,
        **_params(interpret, blocks, tile))(
            q, do, by_block(lse), by_block(jnp.swapaxes(delta, 1, 2)), k, v,
            *ropes)
    dq, dk, dv, *dropes = (_model_layout(d, H) for d in grads)
    # the query heads of a group share their key/value head
    over_group = lambda d, a: jnp.sum(
        d.reshape(B, T, a.shape[2], H // a.shape[2], a.shape[-1]),
        axis=3).astype(a.dtype)
    grads = (dq.astype(q.dtype), over_group(dk, operands[1]),
             over_group(dv, operands[2]))
    if rope:
        grads += (dropes[0].astype(q.dtype), over_group(dropes[1], rope[1]))
    return grads


def _plain_output_lse(q, k, v, *rope, scale, window):
    """The plain path keeps no statistics: its backward pass is its own
    transposition.  The zeros stand in for them, made from ``q`` so that
    they vary over the mesh axes the kernel's would."""
    return (_plain(q, k, v, *rope, scale=scale, window=window),
            0.0 * jnp.swapaxes(q[..., 0], 1, 2).astype(jnp.float32))


def _plain_grads(q, k, v, o, lse, do, *rope, scale, window):
    return jax.vjp(functools.partial(_plain, scale=scale, window=window),
                   q, k, v, *rope)[1](do)


def _lowered(interpret, fused, plain, *args):
    """``fused`` where the program is lowered for a TPU, ``plain`` for any
    other platform; ``interpret`` (the CPU tests') runs the kernels in
    Pallas interpret mode whatever the platform."""
    if interpret:
        return fused(*args, interpret=interpret)
    return jax.lax.platform_dependent(
        *args, tpu=lambda *a: fused(*a, interpret=False), default=plain)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 5, 6))
def _attention(q, k, v, interpret=False, rope=(), scale=None, window=None):
    """A shape the kernels take, on [B, T, H, hd] operands (``rope``: the
    rotary (queries, keys) of latent attention, or nothing; ``window``: a
    sliding window narrower than T, or None).  The platform
    is chosen inside each of the three rules, so no transformation ever
    differentiates through the choice (a differentiated switch would carry
    the plain branch's [B, H, T, T] residuals in both)."""
    return _attention_fwd(q, k, v, interpret, rope, scale, window)[0]


def _attention_fwd(q, k, v, interpret, rope, scale, window):
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    how = dict(scale=scale, window=window)
    o, lse = _lowered(interpret,
                      functools.partial(_fused_output_lse, **how),
                      functools.partial(_plain_output_lse, **how),
                      q, k, v, *rope)
    # named outside the platform switch: a checkpoint policy that lists
    # SAVED_NAMES keeps them, and this rule is not run again (module docstring)
    o, lse = map(checkpoint_name, (o, lse), SAVED_NAMES)
    return o, (q, k, v, o, lse, *rope)


def _attention_bwd(interpret, scale, window, res, do):
    scale = res[0].shape[-1] ** -0.5 if scale is None else scale
    how = dict(scale=scale, window=window)
    # the backward kernel is attention's too: the benchmark's labels read
    # the scope, forward and backward alike (obs/scopes.py)
    with jax.named_scope(scopes.FED_ATTENTION):
        grads = _lowered(interpret,
                         functools.partial(_fused_grads, **how),
                         functools.partial(_plain_grads, **how),
                         *res[:5], do, *res[5:])
    return (*grads[:3], tuple(grads[3:]))


_attention.defvjp(_attention_fwd, _attention_bwd)


# -- the choice ---------------------------------------------------------------

def causal_attention(q, k, v, *, window=None, rope=(), scale=None):
    """``softmax_causal(q k^T * scale) v``: q [B, T, H, hd], k and v
    [B, T, H_kv, hd] with ``H % H_kv == 0`` (query head h reads key/value
    head ``h // (H / H_kv)``) -> [B, T, H, hd] in q's dtype; ``scale`` is
    ``hd ** -0.5`` unless given.

    ``window`` (a model's ``sliding_window``, an int >= 1) narrows what a
    query sees to the last ``window`` positions, its own included: key j is
    visible to query i iff ``0 <= i - j < window``.  A window that reaches
    the whole sequence (``window >= T``) is no window.

    ``rope`` = (q_rope [B, T, H, r], k_rope [B, T, H_r, r]) adds a second
    product to the scores, ``(q k^T + q_rope k_rope^T) * scale`` — latent
    attention's decoupled rotary key, one head (``H_r`` = 1) read by every
    query head and never repeated in memory.

    Operands in their dtype, every product accumulated in float32, the
    softmax (scores, max, exp, sum) in float32 on both paths.  The fused
    kernels run where the program is lowered for a TPU and the shape fits
    (module docstring); no option selects a path."""
    rope = tuple(rope)
    if window is not None and window >= q.shape[1]:
        window = None
    fused = _fits(q, k, v, *rope)
    obs.counter("ops_kernel_path_total", op="causal_attention",
                path="pallas" if fused else "reference").inc()
    if fused:
        if window is not None:
            # what share of the causal half the band's calls run, by blocks
            # and heads, at trace time as the path above
            for blocks, n in zip(("visited", "causal"),
                                 band_blocks(q.shape[1], window)):
                obs.counter("attention_band_blocks_total", blocks=blocks).inc(
                    n * q.shape[0] * q.shape[2])
        return _attention(q, k, v, False, rope, scale, window)
    if jax.default_backend() == "tpu":      # the log line only, as group_norm
        log.warning("causal_attention: q %s %s, k %s%s does not fit the fused "
                    "kernels; using the plain path", tuple(q.shape),
                    q.dtype.name, tuple(k.shape),
                    "".join(f", rope {tuple(a.shape)}" for a in rope))
    return _plain(q, k, v, *rope, scale=scale, window=window)
