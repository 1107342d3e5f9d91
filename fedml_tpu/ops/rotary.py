"""Rotate-half rotary embedding — the one place the repo spells
``x * cos + rotate_half(x) * sin``.

``apply_rotary(x, cos, sin)`` turns the heads of x [B, T, H, hd] by the
tables cos, sin [T, hd] (`models/looped_lm.py::rotary_tables`: float32, the
frequencies repeated over both halves of a head): float32 arithmetic on
operands in their dtype, one rounding back to it.  It is what two language
models call (`looped_lm`, `lfm2_moe`: heads of 128 and 64), the numerical
spec, and the fallback of

``rotate_half(x, cos, sin)`` — the same result as one elementwise kernel pass:
for heads as wide as the lanes (`models/cohere2_moe.py::attention`: 128 query
and 8 key heads of 128 at T = 8,192) and for heads that divide them
(`models/deepseek_v2.py::latent_attention`, which `models/xing4.py` calls too:
the queries' rotary part, 128 heads of 64 at T = 4,096 and 32 at T = 8,192).
Two bodies, one result:

* **the plain path** — `apply_rotary`: cast, ``concatenate([-x2, x1])``, two
  multiplies and an add, cast.  On [8192, 128, 128] inside a layer XLA:TPU
  makes of it float32 intermediates of the whole operand, a slice at lane 64
  of 128-lane rows and reshapes that are copies under the (8, 128) tiling:
  11-18 ms a pass where the memory allows 0.7 (PERF.md section 5, PR 42); on
  latent attention's [4096, 128, 64] it turns float32 halves of 32 lanes that
  the tiling pads 4 x, behind a float32 relayout of the whole queries:
  a layer-step of DeepSeek-V2's latent attention takes 77.1 ms with it (PERF.md section 5, PR 46).
* **the fused path** — one elementwise Pallas TPU pass over x viewed as
  [B, T, H * hd]: blocks of ``rows`` positions by a few hundred lanes, beside
  blocks of cos and of the SIGNED sine (``-sin`` on a head's first half,
  ``+sin`` on its second), ``y = x32 * cos + rot(x32) * sin_signed``, the
  operand read once and the result written once, in their dtype.  The head
  size picks one of two kernels at trace time:

  - *a head is whole rows of 128 lanes* (``hd % 128 == 0``): a block is a
    few whole heads, the tables are [rows, hd], and rotate-half is a roll of
    a head's lanes by half a head, which has no direction to get wrong:
    0.80 ms for Command A+'s q on a v5e, 82 % of the HBM rate (PERF.md
    section 5, PR 42).
  - *a row of 128 lanes is whole heads* (``128 % hd == 0``, H * hd a
    multiple of 128): a block is a few whole rows, the tables are a head's
    repeated across a row, [rows, 128], and half a head further INSIDE a
    head is the row rolled by ``hd / 2`` on a head's second half and by
    ``128 - hd / 2`` on its first (a select between two rolls).  Around such
    a call XLA:TPU keeps latent attention's queries T-minor and puts four
    small bfloat16 copies - and the float32 relayout of the whole queries
    that the plain body sat behind goes: 67.3 ms a layer-step, the kernel
    0.12-0.20 ms a pass of it.  The same arithmetic on x heads first,
    [B, H, T, hd] - the layout `ops/attention.py` reads such heads in, with
    no copy between the two kernels - was timed beside it and lost: it
    keeps that relayout (69.4 ms; same place).

**Precision is the plain path's**: operands in their dtype, the rotation in
float32, one rounding.  **The gradient** is the transposed rotation, the same
pass over the cotangent with the signed sine rolled by half a head (for
tables whose halves repeat, the sine negated: the rotation by -theta); the
residuals are the two tables, no activation is kept.

**Which body runs is read off the program, not configured** (as
`ops/attention.py`): the fused path where the program is LOWERED for a TPU
(``jax.lax.platform_dependent``), the head size is a multiple of 128 or
divides it with H * hd a multiple of 128 (so latent attention's one shared
key head, [B, T, 1, 64] and 0.5 MB, keeps the plain body), T is a multiple of
128 and the operand is bfloat16 or float32; the plain path otherwise.
Counted at trace time in ``ops_kernel_path_total{op="rotate_half", path=...}``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedml_tpu import obs
from fedml_tpu.ops.attention import _lowered, _struct

# a block of the operand may take this many bytes: with its result, both
# double-buffered, 4 MiB of the 16 MiB of fast memory Mosaic hands a kernel
_BLOCK_BYTES = 2 ** 20
_LANES = 128


# -- the plain path -----------------------------------------------------------

def apply_rotary(x, cos, sin):
    """x [B, T, H, hd] -> rotated, same dtype; the rotation in float32."""
    x32 = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x32[..., half:], x32[..., :half]], axis=-1)
    return (x32 * cos[:, None, :] + rot * sin[:, None, :]).astype(x.dtype)


def _plain_transpose(dy, cos, sin):
    """`apply_rotary`'s own transposition (it is linear in x)."""
    return jax.vjp(lambda x: apply_rotary(x, cos, sin), dy)[1](dy)[0]


# -- the fused path -----------------------------------------------------------

def _rows(T: int):
    """The positions a kernel instance owns: the largest of 512, 256, 128
    that divides T, or None where none does."""
    return next((r for r in (512, 256, 128) if T % r == 0), None)


def _lanes_fit(H: int, hd: int) -> bool:
    """A head of whole 128-lane rows, or whole rows of whole heads (of an
    even size)."""
    return hd % _LANES == 0 or (
        hd >= 2 and _LANES % hd == 0 and H * hd % _LANES == 0)


def _fits(x, cos, sin) -> bool:
    """The kernel's requirement on shapes and dtype (module docstring)."""
    return (x.ndim == 4 and _lanes_fit(*x.shape[-2:])
            and x.dtype in (jnp.bfloat16, jnp.float32)
            and _rows(x.shape[1]) is not None
            and cos.shape == sin.shape == (x.shape[1], x.shape[-1])
            and cos.dtype == sin.dtype == jnp.float32)


def _signed(sin):
    """``-sin`` on a head's first half, ``+sin`` on its second: the factor
    of ``roll(x, hd / 2)`` in rotate-half."""
    half = sin.shape[-1] // 2
    return jnp.concatenate([-sin[:, :half], sin[:, half:]], axis=-1)


def _kernel(x_ref, cos_ref, sin_ref, y_ref):
    """[rows, n * hd] of the operand, n whole heads side by side, against
    the [rows, hd] tables every head shares."""
    cos, sin = cos_ref[...], sin_ref[...]
    hd = cos.shape[-1]
    for h in range(x_ref.shape[-1] // hd):
        head = slice(h * hd, (h + 1) * hd)
        x = x_ref[:, head].astype(jnp.float32)
        y_ref[:, head] = (x * cos + pltpu.roll(x, hd // 2, 1) * sin).astype(
            y_ref.dtype)


def _narrow_kernel(x_ref, cos_ref, sin_ref, y_ref, *, hd: int):
    """[rows, n * 128] of the operand, ``128 / hd`` whole heads in every row
    of 128 lanes, against [rows, 128] tables - a head's, repeated across the
    row.  Half a head further inside its own head is the row rolled one way
    on a head's first half and the other way on its second."""
    cos, sin = cos_ref[...], sin_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, cos.shape, 1)
    first = (lane & (hd - 1)) < hd // 2
    for t in range(x_ref.shape[-1] // _LANES):
        tile = slice(t * _LANES, (t + 1) * _LANES)
        x = x_ref[:, tile].astype(jnp.float32)
        rot = jnp.where(first, pltpu.roll(x, _LANES - hd // 2, 1),
                        pltpu.roll(x, hd // 2, 1))
        y_ref[:, tile] = (x * cos + rot * sin).astype(y_ref.dtype)


def _turn(x, cos, sin_signed, interpret):
    """``x * cos + roll(x, hd / 2) * sin_signed`` head by head, x
    [B, T, H, hd], over x viewed [B, T, H * hd]: in blocks of a few whole
    heads where a head is whole rows of 128 lanes, of a few whole rows where
    a row holds several heads."""
    B, T, H, hd = x.shape
    rows, width = _rows(T), H * hd
    if hd % _LANES == 0:
        kernel, unit = _kernel, hd
    else:
        kernel, unit = functools.partial(_narrow_kernel, hd=hd), _LANES
        cos, sin_signed = (jnp.tile(t, (1, _LANES // hd))
                           for t in (cos, sin_signed))
    units = width // unit
    n = max(n for n in range(1, units + 1) if units % n == 0 and (
        n == 1 or rows * n * unit * x.dtype.itemsize <= _BLOCK_BYTES))
    wide = pl.BlockSpec((None, rows, n * unit), lambda b, i, j: (b, i, j))
    # the heads innermost: a table's block index then stays put from one
    # grid point to the next, and it is fetched once a block of positions
    table = pl.BlockSpec((rows, unit), lambda b, i, j: (i, 0))
    flat = x.reshape(B, T, width)
    how = dict(interpret=interpret) if interpret else dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")))
    y = pl.pallas_call(
        kernel, name="rotate_half", grid=(B, T // rows, units // n),
        in_specs=[wide, table, table], out_specs=wide,
        out_shape=_struct(flat, flat.shape, x.dtype), **how)(
            flat, cos, sin_signed)
    return y.reshape(x.shape)


def _fused(x, cos, sin, interpret):
    return _turn(x, cos, _signed(sin), interpret)


def _fused_transpose(dy, cos, sin, interpret):
    """The transposed rotation: ``roll`` by half a head is its own inverse,
    so the signed sine moves with the cotangent it multiplies."""
    return _turn(dy, cos, jnp.roll(_signed(sin), sin.shape[-1] // 2, axis=-1),
                 interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rotate(x, cos, sin, interpret=False):
    """A shape the kernel takes.  The platform is chosen inside each rule,
    as `ops/attention.py::_attention` does."""
    return _rotate_fwd(x, cos, sin, interpret)[0]


def _rotate_fwd(x, cos, sin, interpret):
    return _lowered(interpret, _fused, apply_rotary, x, cos, sin), (cos, sin)


def _rotate_bwd(interpret, tables, dy):
    dx = _lowered(interpret, _fused_transpose, _plain_transpose, dy, *tables)
    return (dx,) + tuple(map(jnp.zeros_like, tables))


_rotate.defvjp(_rotate_fwd, _rotate_bwd)


# -- the choice ---------------------------------------------------------------

def rotate_half(x, cos, sin):
    """`apply_rotary`'s result for x [B, T, H, hd] and cos, sin [T, hd]; in
    a program lowered for a TPU one elementwise kernel pass where the shape
    fits (module docstring); no option selects a path."""
    fused = _fits(x, cos, sin)
    obs.counter("ops_kernel_path_total", op="rotate_half",
                path="pallas" if fused else "reference").inc()
    return _rotate(x, cos, sin, False) if fused else apply_rotary(x, cos, sin)
