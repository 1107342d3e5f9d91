"""Rotate-half rotary embedding — the one place the repo spells
``x * cos + rotate_half(x) * sin``.

``apply_rotary(x, cos, sin)`` turns the heads of x [B, T, H, hd] by the
tables cos, sin [T, hd] (`models/looped_lm.py::rotary_tables`: float32, the
frequencies repeated over both halves of a head): float32 arithmetic on
operands in their dtype, one rounding back to it.  It is what the language
models call (`looped_lm`, `lfm2_moe`, `deepseek_v2`: heads, or rotary parts, of
128 and 64), the numerical spec, and the fallback of

``rotate_half(x, cos, sin)`` — the same result for heads as wide as the lanes
(`models/cohere2_moe.py::attention`: 128 query and 8 key heads of 128 at
T = 8,192).  Two bodies, one result:

* **the plain path** — `apply_rotary`: cast, ``concatenate([-x2, x1])``, two
  multiplies and an add, cast.  On [8192, 128, 128] inside a layer XLA:TPU
  makes of it float32 intermediates of the whole operand, a slice at lane 64
  of 128-lane rows and reshapes that are copies under the (8, 128) tiling:
  11-18 ms a pass where the memory allows 0.7 (PERF.md section 5, PR 42).
* **the fused path** — one elementwise Pallas TPU pass over x viewed as
  [B, T, H * hd] (a free reshape): blocks of ``rows`` positions by a few whole
  heads, beside the ``[rows, hd]`` blocks of cos and of the SIGNED sine
  (``-sin`` on a head's first half, ``+sin`` on its second), and for each head
  ``y = x32 * cos + roll(x32, hd / 2) * sin_signed`` — rotate-half is a roll
  of the lanes by half a head, which has no direction to get wrong.  The
  operand is read once and the result written once, in their dtype: 0.80 ms
  for that q on a v5e, 82 % of the HBM rate (same place).

**Precision is the plain path's**: operands in their dtype, the rotation in
float32, one rounding.  **The gradient** is the transposed rotation, the same
pass over the cotangent with the signed sine rolled by half a head (for
tables whose halves repeat, the sine negated: the rotation by -theta); the
residuals are the two tables, no activation is kept.

**Which body runs is read off the program, not configured** (as
`ops/attention.py`): the fused path where the program is LOWERED for a TPU
(``jax.lax.platform_dependent``), the head size is a multiple of 128, T is a
multiple of 128 and the operand is bfloat16 or float32; the plain path
otherwise.  Counted at trace time in
``ops_kernel_path_total{op="rotate_half", path=...}``.
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


def _fits(x, cos, sin) -> bool:
    """The kernel's requirement on shapes and dtype (module docstring)."""
    return (x.ndim == 4 and x.shape[-1] % 128 == 0
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


def _turn(x, cos, sin_signed, interpret):
    """``x * cos + roll(x, hd / 2) * sin_signed`` head by head, x
    [B, T, H, hd]."""
    B, T, H, hd = x.shape
    rows = _rows(T)
    heads = max(n for n in range(1, H + 1) if H % n == 0 and (
        n == 1 or rows * n * hd * x.dtype.itemsize <= _BLOCK_BYTES))
    wide = pl.BlockSpec((None, rows, heads * hd), lambda b, i, j: (b, i, j))
    # the heads innermost: a table's block index then stays put from one
    # grid point to the next, and it is fetched once a block of positions
    table = pl.BlockSpec((rows, hd), lambda b, i, j: (i, 0))
    flat = x.reshape(B, T, H * hd)
    how = dict(interpret=interpret) if interpret else dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")))
    y = pl.pallas_call(
        _kernel, name="rotate_half", grid=(B, T // rows, H // heads),
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
