"""The passes a hyper-connection makes over a token's ``n`` streams — the one
place the repo spells the read ``u = sum_i H_pre[i] X[i]`` and the write
``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] F(u)`` of `models/xing4.py`.

The streams of a token lie side by side in the lanes: X is ``[..., T, n C]``,
stream i the columns ``i C .. (i + 1) C``.  Two ops, each a function with its
own derivative:

``hc_read(X, phi, gate, b, n=, eps=)`` -> ``(u, ht, X)``:

    r   = rsqrt(mean(vec(X)^2) + eps)               float32, a scalar a token
    ht  = gate * ((X phi) * r) + b                  [..., T, n (n + 2)] float32
    u   = sum_i sigmoid(ht[..., i]) X[i]            [..., T, C] in X's dtype

(the three maps' pre-activations at once: the model makes ``H_post`` and the
Sinkhorn-normalised ``H_res`` of ``ht[..., n:]`` in plain jax.numpy; ``H_pre``
is needed here, for ``u``).  The third result is X itself, for `hc_write`:
what the write sends back to the streams then arrives at the read's backward
rule, which folds it into the one ``dX`` it writes - two readers of X would
have jax add two ``[T, n C]`` gradients in a pass of its own.

``hc_write(X, y, post, res)`` -> ``X'`` ``[..., T, n C]`` in X's dtype, from
``y = F(u)`` ``[..., T, C]``, ``post`` ``[..., T, n]`` and ``res``
``[..., T, n n]`` (entry ``i n + j`` is row i, column j), both float32.

Two bodies, one result:

* **the plain path** — `read_plain`, `write_plain`: jax.numpy on lane slices
  of X, differentiated by jax.  The numerical spec and the fallback.  On
  ``[8192, 4 x 3584]`` bfloat16 inside a layer XLA:TPU reads the 235 MB
  streams once for the RMS, again for the projection, again for ``u``, again
  for ``X'``; backward it makes the sixteen ``dX'[i] . X[j]`` in three to
  four multi-output reduction fusions and writes two float32
  ``[8192, 14336]`` intermediates a sublayer: 10.2 ms a sublayer-step where
  the memory allows 1.8 (PERF.md section 5, PR 45).
* **the fused path** — four Pallas TPU kernels over blocks of whole rows (64
  bfloat16 tokens by all ``n C`` lanes, so every reduction over a token's
  lanes ends inside one kernel instance), the lanes walked by a loop in
  chunks of 512 so that no float32 intermediate is wider than a chunk.  On
  the v5e at Xing4.0-29B-A4B's streams, inside the round (PERF.md section
  5, PR 47; ms a call, beside the bytes' time at 819 GB/s):

  - *read, forward* (`_read_kernel`): X once for ``sum x^2``, for the
    ``n (n + 2)``-wide projection (MXU, float32 accumulation) and for ``u``;
    writes ``u``, ``ht`` and, for the backward rule, ``(X phi) r`` and ``r``
    (``[T, n (n + 2)]`` and ``[T, 1]`` float32: under a checkpoint that
    keeps none of them the re-run makes them again with ``u``, which the
    sublayer's own re-run needs anyway).  0.39 ms forward, 0.43 in the
    re-run (0.36).
  - *write, forward* (`_write_kernel`): X and ``F(u)`` once, ``X'`` once.
    0.76 ms forward, 0.72 in the re-run (0.65).
  - *write, backward* (`_write_bwd_kernel`): ``dX'``, X and ``F(u)`` once;
    writes ``dF(u) = sum_i H_post[i] dX'[i]``, the streams' share
    ``H_res^T dX'`` - over ``dX'``'s own buffer, which nothing reads again -
    and the ``n + n n`` lane reductions ``dX'[i] . F(u)``,
    ``dX'[i] . X[j]``, accumulated in float32 lane-wide in fast memory and
    folded once a token.  1.21 ms (1.00).
  - *read, backward* (`_read_bwd_kernel`): X, the write's share, ``du`` and
    ``dht`` once; ``dH_pre[i] = du . X[i]`` through the sigmoid into
    ``dht[:n]``, then ``dX = share + H_pre du + (gate dht r) phi^T -
    (r^2 / n C) (sum gate dht (X phi) r) X`` in one write - no float32
    ``[T, n C]`` ever reaches HBM.  1.08 ms (0.93).

  5.1 ms a sublayer-step with the maps' loop where the plain bodies take
  10.2.  The block's height, how a coefficient's column is read, the chunk's
  width and where the lane reductions end (XLU or MXU) were each timed and
  move nothing (0.5 %): the passes run at 83-91 % of the HBM's rate.

**Precision is the plain path's**: operands in the streams' dtype, every
sum, product and reduction in float32, the projection's operands in their
dtype with float32 accumulation (float32 operands take every pass of the MXU,
as in `ops/attention.py`), one rounding of ``u``, ``X'``, ``dF(u)``, the
write's share and ``dX`` to the streams' dtype.  The gradients of ``phi``,
``gate`` and ``b`` are plain products outside the kernels (of ``dht`` as the
kernel completes it): a frozen base never asks for them and XLA drops them.

**Which body runs is read off the program, not configured** (as
`ops/rotary.py`): the fused path where the program is LOWERED for a TPU
(``jax.lax.platform_dependent``), C is a multiple of 128 lanes, the tokens
are a multiple of a block's rows and the streams are bfloat16 or float32 (a
float32 block holds half the tokens); the plain path otherwise.  Counted at
trace time in ``ops_kernel_path_total{op="hc_read" | "hc_write", path=...}``.
The backward rules open ``fed_hc_maps`` (the read's) and ``fed_hc_mix`` (the
write's) themselves, as `ops/attention.py`'s does ``fed_attention``: the
benchmark's labels read the scope, forward and backward alike.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedml_tpu import obs
from fedml_tpu.obs import scopes
from fedml_tpu.ops.attention import _dot, _lowered, _struct, _vmem

_LANES = 128
# a block's rows of the streams, [rows, n C], may take this many bytes (64
# tokens of four 3,584-wide bfloat16 streams, 32 of float32 ones; 32 to 256
# run alike on a v5e); the kernels hold up to three such blocks and an
# output, double-buffered
_BLOCK_BYTES = 2 ** 21
_NT = (((1,), (1,)), ((), ()))          # [m, k] x [n, k] -> [m, n]
_NN = (((1,), (0,)), ((), ()))          # [m, k] x [k, n] -> [m, n]
_F32 = jnp.float32


# -- the plain path -----------------------------------------------------------

def streams(X, n: int):
    """The n streams of X [..., n C], each [..., C]."""
    C = X.shape[-1] // n
    return [X[..., i * C:(i + 1) * C] for i in range(n)]


def _read_plain4(X, phi, gate, b, *, n: int, eps: float):
    """(u, ht, q = (X phi) r, r) in jax.numpy."""
    x32 = X.astype(_F32)
    r = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    q = jnp.einsum("...a,ab->...b", X, phi, preferred_element_type=_F32) * r
    ht = gate * q + b
    pre = jax.nn.sigmoid(ht[..., :n])
    u = sum(pre[..., i:i + 1] * x.astype(_F32)
            for i, x in enumerate(streams(X, n)))
    return u.astype(X.dtype), ht, q, r


def read_plain(X, phi, gate, b, n: int, eps: float):
    """(u, ht, X) of the module docstring in jax.numpy."""
    return _read_plain4(X, phi, gate, b, n=n, eps=eps)[:2] + (X,)


def write_plain(X, y, post, res):
    """X' of the module docstring in jax.numpy."""
    n = post.shape[-1]
    xs = [x.astype(_F32) for x in streams(X, n)]
    y32 = y.astype(_F32)
    rows = [post[..., i:i + 1] * y32
            + sum(res[..., i * n + j:i * n + j + 1] * xs[j] for j in range(n))
            for i in range(n)]
    return jnp.concatenate(rows, axis=-1).astype(X.dtype)


# -- the fused path: shapes ---------------------------------------------------

def _rows(tokens: int, width: int, dtype):
    """The tokens a kernel instance owns: the largest power of two of whole
    sublane tiles (16 bfloat16 rows, 8 float32) whose [rows, width] block
    stays under `_BLOCK_BYTES` and that divides ``tokens``; None where none
    does."""
    size = jnp.dtype(dtype).itemsize
    least, rows = 32 // size, 1
    while 2 * rows * width * size <= _BLOCK_BYTES:
        rows *= 2
    while rows >= least:
        if tokens % rows == 0:
            return rows
        rows //= 2
    return None


def _chunk(C: int) -> int:
    """The lanes of a stream a kernel walks at a time: up to 512, whole
    128-lane tiles, dividing C."""
    tiles = C // _LANES
    return _LANES * max(g for g in (4, 2, 1) if tiles % g == 0)


def _fits(X, n: int) -> bool:
    """The kernels' requirement on the streams (module docstring)."""
    W = X.shape[-1]
    return (X.ndim >= 2 and W % n == 0 and (W // n) % _LANES == 0
            and X.dtype in (jnp.bfloat16, jnp.float32)
            and _rows(X.size // W, W, X.dtype) is not None)


def _call(kernel, name, rows, tokens, ins, outs, like, interpret, sums=0,
          reuse=None):
    """One pass over blocks of ``rows`` tokens: ``ins`` / ``outs`` are
    (array or shape, dtype, per-token?) - a per-token operand [tokens, w] is
    walked in blocks of [rows, w], any other is held whole; ``sums``
    lane-wide float32 accumulators [rows, 128] in fast memory; ``reuse`` =
    (operand, result): the result is written block for block over an operand
    of its shape that nothing reads again (a gradient on its way back)."""
    def spec(shape, per_token):
        if per_token:
            return pl.BlockSpec((rows, shape[-1]), lambda i: (i, 0))
        return pl.BlockSpec(shape, lambda i: (0,) * len(shape))

    held = [(a.shape, a.dtype, p) for a, p in ins] + list(outs)
    need = 2 * sum((rows if per_token else shape[0]) * max(shape[-1], _LANES)
                   * jnp.dtype(dtype).itemsize
                   for shape, dtype, per_token in held)
    if interpret:
        how = dict(interpret=interpret)
    else:
        how = dict(compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), **_vmem(need)))
    return pl.pallas_call(
        kernel, name=name, grid=(tokens // rows,),
        in_specs=[spec(a.shape, p) for a, p in ins],
        out_specs=[spec(shape, p) for shape, _, p in outs],
        out_shape=[_struct(like, shape, dtype) for shape, dtype, _ in outs],
        scratch_shapes=[pltpu.VMEM((sums, rows, _LANES), _F32)] * bool(sums),
        input_output_aliases=dict([reuse] if reuse else []),
        **how)(*(a for a, _ in ins))


# -- the fused path: kernels --------------------------------------------------

def _fold(a):
    """[rows, k * 128] -> [rows, 128]: the 128-lane tiles added up (whole
    registers, no work across lanes)."""
    return sum(a[:, t:t + _LANES] for t in range(0, a.shape[-1], _LANES))


def _across(acc):
    """[rows, 128] -> [rows, 1]: the one reduction across lanes a token."""
    return jnp.sum(acc, axis=-1, keepdims=True)


def _column(a, k: int):
    """Column k of a small [rows, w] float32 value as [rows, 1]: one value a
    token, which a product then spreads across the lanes."""
    return a[:, k:k + 1]


def _spread(columns, width: int):
    """[rows, 1] values -> [rows, width] float32 with value k in column k and
    zeros behind them."""
    rows = columns[0].shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
    out = jnp.zeros((rows, width), _F32)
    for k, c in enumerate(columns):
        out = jnp.where(lane == k, c, out)
    return out


def _walk(width: int, L: int, body, init=None):
    """``body(s, carry)`` for the first lane s of every chunk of L lanes of
    ``width``, as one loop in the kernel (its text holds the body once)."""
    return jax.lax.fori_loop(
        0, width // L, lambda c, carry: body(pl.multiple_of(c * L, L), carry),
        init)


def _read_kernel(x_ref, phit_ref, gate_ref, b_ref, u_ref, ht_ref, q_ref,
                 r_ref, *, n: int, eps: float):
    rows, W = x_ref.shape
    C = W // n
    L = _chunk(C)

    def squares(s, acc):
        x = x_ref[:, pl.ds(s, L)].astype(_F32)
        return acc + _fold(x * x)

    acc = _walk(W, L, squares, jnp.zeros((rows, _LANES), _F32))
    r = jax.lax.rsqrt(_across(acc) / W + eps)
    q = _dot(x_ref[...], phit_ref[...], _NT) * r
    ht = gate_ref[...] * q + b_ref[...]
    ht_ref[...], q_ref[...], r_ref[...] = ht, q, r
    gates = jax.nn.sigmoid(ht)
    pre = [_column(gates, i) for i in range(n)]

    def mix(s, _):
        u = sum(pre[i] * x_ref[:, pl.ds(i * C + s, L)].astype(_F32)
                for i in range(n))
        u_ref[:, pl.ds(s, L)] = u.astype(u_ref.dtype)

    _walk(C, L, mix)


def _maps(co_ref, n: int):
    """(post [n], res [n][n]) as [rows, 1] columns of the [rows, n + n n]
    block."""
    co = co_ref[...]
    post = [_column(co, i) for i in range(n)]
    res = [[_column(co, n + i * n + j) for j in range(n)] for i in range(n)]
    return post, res


def _write_kernel(x_ref, y_ref, co_ref, o_ref, *, n: int):
    C = y_ref.shape[-1]
    L = _chunk(C)
    post, res = _maps(co_ref, n)

    def mix(s, _):
        at = lambda i: pl.ds(i * C + s, L)
        xs = [x_ref[:, at(j)].astype(_F32) for j in range(n)]
        y = y_ref[:, pl.ds(s, L)].astype(_F32)
        for i in range(n):
            o_ref[:, at(i)] = (post[i] * y + sum(
                res[i][j] * xs[j] for j in range(n))).astype(o_ref.dtype)

    _walk(C, L, mix)


def _write_bwd_kernel(g_ref, x_ref, y_ref, co_ref, dy_ref, dx_ref, dco_ref,
                      acc_ref, *, n: int):
    C = y_ref.shape[-1]
    L = _chunk(C)
    post, res = _maps(co_ref, n)
    acc_ref[...] = jnp.zeros(acc_ref.shape, _F32)

    def mix(s, _):
        at = lambda i: pl.ds(i * C + s, L)
        gs = [g_ref[:, at(i)].astype(_F32) for i in range(n)]
        xs = [x_ref[:, at(j)].astype(_F32) for j in range(n)]
        y = y_ref[:, pl.ds(s, L)].astype(_F32)
        dy_ref[:, pl.ds(s, L)] = sum(
            post[i] * gs[i] for i in range(n)).astype(dy_ref.dtype)
        for j in range(n):
            dx_ref[:, at(j)] = sum(
                res[i][j] * gs[i] for i in range(n)).astype(dx_ref.dtype)
        for i in range(n):
            acc_ref[i] += _fold(gs[i] * y)
            for j in range(n):
                acc_ref[n + i * n + j] += _fold(gs[i] * xs[j])

    _walk(C, L, mix)
    dco_ref[...] = _spread([_across(acc_ref[k]) for k in range(n + n * n)],
                           n + n * n)


def _read_bwd_kernel(x_ref, dxc_ref, du_ref, dht_ref, ht_ref, q_ref, r_ref,
                     phit_ref, gate_ref, dx_ref, dhtf_ref, acc_ref, *, n: int):
    rows, W = x_ref.shape
    C = W // n
    L = _chunk(C)
    K = ht_ref.shape[-1]
    acc_ref[...] = jnp.zeros(acc_ref.shape, _F32)

    def dots(s, _):
        du = du_ref[:, pl.ds(s, L)].astype(_F32)
        for i in range(n):
            acc_ref[i] += _fold(du * x_ref[:, pl.ds(i * C + s, L)].astype(_F32))

    _walk(C, L, dots)
    gates = jax.nn.sigmoid(ht_ref[...])
    pre = [_column(gates, i) for i in range(n)]
    # dH_pre through the sigmoid into the first n of dht; the others as given
    dht = dht_ref[...] + _spread([_across(acc_ref[i]) for i in range(n)], K) * (
        gates * (1.0 - gates))
    dhtf_ref[...] = dht
    r = r_ref[...]
    g = gate_ref[...] * dht
    dp = (g * r).astype(phit_ref.dtype)
    scale = r * r * (1.0 / W) * jnp.sum(g * q_ref[...], axis=-1, keepdims=True)

    def mix(s, _):
        du = du_ref[:, pl.ds(s, L)].astype(_F32)
        for i in range(n):
            at = pl.ds(i * C + s, L)
            dx = (dxc_ref[:, at].astype(_F32) + pre[i] * du
                  + _dot(dp, phit_ref[:, at], _NN)
                  - scale * x_ref[:, at].astype(_F32))
            dx_ref[:, at] = dx.astype(dx_ref.dtype)

    _walk(C, L, mix)


# -- the fused path: the four passes ------------------------------------------

def _flat(a):
    return a.reshape((-1, a.shape[-1]))


def _row(v):
    return v.astype(_F32).reshape((1, -1))


def _read_fused(X, phi, gate, b, *, n, eps, interpret):
    """(u, ht, (X phi) r, r), the last two for the backward rule."""
    x = _flat(X)
    N, W = x.shape
    K = phi.shape[-1]
    rows = _rows(N, W, X.dtype)
    u, ht, q, r = _call(
        functools.partial(_read_kernel, n=n, eps=eps), "hc_read", rows, N,
        [(x, True), (phi.T, False), (_row(gate), False), (_row(b), False)],
        [((N, W // n), X.dtype, True), ((N, K), _F32, True),
         ((N, K), _F32, True), ((N, 1), _F32, True)], x, interpret)
    lead = X.shape[:-1]
    return (u.reshape(lead + (W // n,)), ht.reshape(lead + (K,)),
            q.reshape(lead + (K,)), r.reshape(lead + (1,)))


def _read_bwd_fused(X, phi, gate, b, ht, q, r, dxc, du, dht, *, n, interpret):
    """(dX, dht with dH_pre's share in its first n); ``b`` is the plain
    rule's."""
    x = _flat(X)
    N, W = x.shape
    K = phi.shape[-1]
    rows = _rows(N, W, X.dtype)
    dx, dhtf = _call(
        functools.partial(_read_bwd_kernel, n=n), "hc_read_bwd", rows, N,
        [(x, True), (_flat(dxc), True), (_flat(du), True), (_flat(dht), True),
         (_flat(ht), True), (_flat(q), True), (_flat(r), True),
         (phi.T, False), (_row(gate), False)],
        [((N, W), X.dtype, True), ((N, K), _F32, True)], x, interpret, sums=n)
    return dx.reshape(X.shape), dhtf.reshape(ht.shape)


def _write_fused(X, y, co, *, n, interpret):
    x = _flat(X)
    N, W = x.shape
    rows = _rows(N, W, X.dtype)
    out, = _call(
        functools.partial(_write_kernel, n=n), "hc_write", rows, N,
        [(x, True), (_flat(y), True), (_flat(co), True)],
        [((N, W), X.dtype, True)], x, interpret)
    return out.reshape(X.shape)


def _write_bwd_fused(X, y, co, g, *, n, interpret):
    """(dy, the streams' share H_res^T g, d(post | res))."""
    x = _flat(X)
    N, W = x.shape
    rows = _rows(N, W, X.dtype)
    dy, dx, dco = _call(
        functools.partial(_write_bwd_kernel, n=n), "hc_write_bwd", rows, N,
        [(_flat(g), True), (x, True), (_flat(y), True), (_flat(co), True)],
        [((N, W // n), y.dtype, True), ((N, W), X.dtype, True),
         ((N, co.shape[-1]), _F32, True)], x, interpret, sums=co.shape[-1],
        reuse=(0, 1))
    return dy.reshape(y.shape), dx.reshape(X.shape), dco.reshape(co.shape)


# -- the rules ----------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _read(X, phi, gate, b, n, eps, interpret=False):
    """A shape the kernels take.  The platform is chosen inside each rule,
    as `ops/attention.py::_attention` does."""
    return _read_fwd(X, phi, gate, b, n, eps, interpret)[0]


def _read_fwd(X, phi, gate, b, n, eps, interpret):
    u, ht, q, r = _lowered(
        interpret, functools.partial(_read_fused, n=n, eps=eps),
        functools.partial(_read_plain4, n=n, eps=eps), X, phi, gate, b)
    return (u, ht, X), (X, phi, gate, b, ht, q, r)


def _read_plain_bwd(X, phi, gate, b, ht, q, r, dxc, du, dht, *, n, eps):
    """`read_plain`'s own derivative with respect to X, and ``dht`` with
    dH_pre's share, as the kernel completes it."""
    _, back = jax.vjp(lambda X: read_plain(X, phi, gate, b, n, eps), X)
    gates = jax.nn.sigmoid(ht[..., :n])
    du32 = du.astype(_F32)
    dpre = jnp.stack([jnp.sum(du32 * x.astype(_F32), axis=-1)
                      for x in streams(X, n)], axis=-1)
    return (back((du, dht, dxc))[0],
            dht.at[..., :n].add(dpre * gates * (1.0 - gates)))


def _read_bwd(n, eps, interpret, res, cts):
    X, phi, gate, b, ht, q, r = res
    du, dht, dxc = cts
    with jax.named_scope(scopes.FED_HC_MAPS):
        dX, dhtf = _lowered(
            interpret, functools.partial(_read_bwd_fused, n=n),
            functools.partial(_read_plain_bwd, n=n, eps=eps),
            X, phi, gate, b, ht, q, r, dxc, du, dht)
        # the frozen base's: plain products XLA drops where nobody asks
        dq = gate * dhtf
        dphi = jnp.einsum("...a,...b->ab", X, (dq * r).astype(X.dtype),
                          preferred_element_type=_F32).astype(phi.dtype)
        lead = tuple(range(dhtf.ndim - 1))
        dgate = jnp.sum(dhtf * q, axis=lead).astype(gate.dtype)
        db = jnp.sum(dhtf, axis=lead).astype(gate.dtype)
    return dX, _whole(dphi, phi), _whole(dgate, gate), _whole(db, b)


def _whole(grad, operand):
    """The gradient of an operand that a mesh does not split, summed over
    the mesh axes the streams vary over (jax's own rule for such an operand
    under ``shard_map``)."""
    axes = tuple(sorted(jax.typeof(grad).vma - jax.typeof(operand).vma))
    return jax.lax.psum(grad, axes) if axes else grad


_read.defvjp(_read_fwd, _read_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _write(X, y, co, n, interpret=False):
    return _write_fwd(X, y, co, n, interpret)[0]


def _write_plain3(X, y, co, *, n):
    return write_plain(X, y, co[..., :n], co[..., n:])


def _write_fwd(X, y, co, n, interpret):
    out = _lowered(interpret, functools.partial(_write_fused, n=n),
                   functools.partial(_write_plain3, n=n), X, y, co)
    return out, (X, y, co)


def _write_plain_bwd(X, y, co, g, *, n):
    dX, dy, dco = jax.vjp(functools.partial(_write_plain3, n=n), X, y, co)[1](g)
    return dy, dX, dco


def _write_bwd(n, interpret, res, g):
    with jax.named_scope(scopes.FED_HC_MIX):
        dy, dX, dco = _lowered(
            interpret, functools.partial(_write_bwd_fused, n=n),
            functools.partial(_write_plain_bwd, n=n), *res, g)
    return dX, dy, dco


_write.defvjp(_write_fwd, _write_bwd)


# -- the choice ---------------------------------------------------------------

def _count(op: str, fused: bool):
    obs.counter("ops_kernel_path_total", op=op,
                path="pallas" if fused else "reference").inc()


def hc_read(X, phi, gate, b, *, n: int, eps: float):
    """`read_plain`'s (u, ht, X) for the streams X [..., T, n C], phi
    [n C, k] in X's dtype and gate, b [k] float32; in a program lowered for
    a TPU one kernel pass each way where the shape fits (module docstring);
    no option selects a path."""
    fused = _fits(X, n) and phi.dtype == X.dtype
    _count("hc_read", fused)
    if not fused:
        return read_plain(X, phi, gate, b, n, eps)
    return _read(X, phi, gate.astype(_F32), b.astype(_F32), n, float(eps),
                 False)


def hc_write(X, y, post, res):
    """`write_plain`'s X' for the streams X [..., T, n C], y [..., T, C] in
    X's dtype, post [..., T, n] and res [..., T, n n] float32; chosen as
    `hc_read` is."""
    n = post.shape[-1]
    fused = (_fits(X, n) and y.dtype == X.dtype
             and post.dtype == res.dtype == _F32)
    _count("hc_write", fused)
    if not fused:
        return write_plain(X, y, post, res)
    return _write(X, y, jnp.concatenate([post, res], axis=-1), n, False)
