"""Fused GroupNorm — pallas forward + custom-VJP backward.

GroupNorm is the normalization of the flagship ResNet-18-GN (the
reference's fed_cifar100 model, cv/resnet_gn.py + group_normalization.py)
and ~40% of the bench step's fwd+bwd wall-clock.  The fused layout:

  forward : ONE pass over x → (y, mean, rstd)      [stats in f32]
  backward: ONE pass over (x, dy) → dx; the small dγ/dβ channel
            reductions run as one fused XLA reduction outside the kernel.

Mosaic cannot split the minor (lane) dimension in-kernel, so instead of
reshaping [B, S·C] → [B, S, G, C/G] the kernels select each group with an
iota mask over the flattened feature axis (G unrolled VPU passes over
VMEM-resident data — no extra HBM traffic), and γ/β arrive pre-tiled to
the feature axis from XLA.  Layout requirement: trailing-channel arrays
with (H·W·C) a multiple of 128, C divisible by `num_groups`, and batch a
multiple of BLOCK_N; anything else — and any non-TPU backend — takes the
pure-jnp reference path, which is the numerical spec the tests compare
against.

MEASURED OUTCOME (v5e-1, bs 4096 ResNet-18-GN train step): the hand
kernel loses to XLA — 262 ms/step fused vs 177 ms/step with plain
nn.GroupNorm.  XLA already fuses GN's elementwise tail into the
surrounding relu/conv producers/consumers, and the group-select masks
cost G extra VPU passes over the block.  The models therefore keep
nn.GroupNorm by default; this op remains available (and tested for
value/grad parity) as the building block for cases XLA fuses poorly —
e.g. GN followed by host-visible stats, or very large C where the
mask passes amortize.
"""
from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedml_tpu import obs

log = logging.getLogger(__name__)

BLOCK_N = 8      # sublane granularity: blocks must be multiples of 8
FTILE = 8192     # in-kernel chunk (VMEM temporaries stay ~1 MB)
_VMEM = pltpu.VMEM


def _kernel_supports(shape, num_groups) -> bool:
    """The fused kernels' layout requirement (module docstring)."""
    if len(shape) < 2:
        return False
    feat = 1
    for s in shape[1:]:
        feat *= s
    C = shape[-1]
    if C % num_groups or shape[0] % BLOCK_N or feat % 128:
        return False
    if feat <= FTILE:
        return True
    # chunked path needs C-aligned full tiles
    return feat % FTILE == 0 and FTILE % C == 0


def _use_pallas(shape, num_groups) -> bool:
    """Fused kernels on a TPU backend for shapes they support; the jnp
    reference otherwise.  Decided (and counted, in
    `ops_kernel_path_total{op="group_norm"}`) at trace time; on a TPU
    backend a rejected shape is also logged by name (once per trace), so
    a run that asked for the fused op cannot silently become the
    reference."""
    on_tpu = jax.default_backend() == "tpu"
    fused = on_tpu and _kernel_supports(shape, num_groups)
    obs.counter("ops_kernel_path_total", op="group_norm",
                path="pallas" if fused else "reference").inc()
    if on_tpu and not fused:
        log.warning("group_norm: shape %s (groups=%d) does not fit the "
                    "fused kernels' layout; using the jnp reference",
                    tuple(shape), num_groups)
    return fused


# ---------------------------------------------------------------------------
# reference (spec) path — plain jnp, used off-TPU / unaligned shapes
# ---------------------------------------------------------------------------

def _gn_reference(x, gamma, beta, num_groups, eps):
    N, C = x.shape[0], x.shape[-1]
    xf = x.astype(jnp.float32).reshape(N, -1, num_groups, C // num_groups)
    mean = xf.mean(axis=(1, 3), keepdims=True)
    var = ((xf - mean) ** 2).mean(axis=(1, 3), keepdims=True)
    xhat = ((xf - mean) * jax.lax.rsqrt(var + eps)).reshape(x.shape)
    return (xhat * gamma.astype(jnp.float32)
            + beta.astype(jnp.float32)).astype(x.dtype)


def _stats_reference(x, num_groups, eps):
    N, C = x.shape[0], x.shape[-1]
    xf = x.astype(jnp.float32).reshape(N, -1, num_groups, C // num_groups)
    mean = xf.mean(axis=(1, 3))
    var = ((xf - mean[:, None, :, None]) ** 2).mean(axis=(1, 3))
    return mean, jax.lax.rsqrt(var + eps)


# ---------------------------------------------------------------------------
# pallas kernels (x flattened to [B, F], F = spatial·C)
# ---------------------------------------------------------------------------

def _chunk_layout(F, C):
    ftile = min(F, FTILE)
    return ftile, F // ftile


def _group_onehots(ftile, C, G):
    """[G, ftile] one-hot masks (as f32) selecting each group's lanes —
    identical for every chunk because ftile % C == 0."""
    f_idx = jax.lax.broadcasted_iota(jnp.int32, (1, ftile), 1)
    grp = (f_idx % C) // (C // G)
    return [(grp == g).astype(jnp.float32) for g in range(G)]


def _fwd_kernel(x_ref, gt_ref, bt_ref, y_ref, mean_ref, rstd_ref,
                *, G, C, eps):
    B, F = x_ref.shape
    ftile, n_chunks = _chunk_layout(F, C)
    onehots = _group_onehots(ftile, C, G)
    m = jnp.float32(F // G)
    # pass 1 over VMEM-resident chunks: per-group Σx → mean
    s = [jnp.zeros((B, 1), jnp.float32) for _ in range(G)]
    for t in range(n_chunks):
        xc = x_ref[:, pl.ds(t * ftile, ftile)].astype(jnp.float32)
        for g, oh in enumerate(onehots):
            s[g] = s[g] + jnp.sum(xc * oh, axis=1, keepdims=True)
    mean = jnp.concatenate(s, axis=1) / m
    # pass 2: Σ(x−μ)² — two-pass variance, matching the reference spec
    # (the one-pass E[x²]−μ² form cancels catastrophically for
    # large-mean inputs); chunks are VMEM reads, so the extra pass is
    # compute-only, not HBM traffic
    v = [jnp.zeros((B, 1), jnp.float32) for _ in range(G)]
    for t in range(n_chunks):
        xc = x_ref[:, pl.ds(t * ftile, ftile)].astype(jnp.float32)
        for g, oh in enumerate(onehots):
            d = (xc - mean[:, g][:, None]) * oh
            v[g] = v[g] + jnp.sum(d * d, axis=1, keepdims=True)
    var = jnp.concatenate(v, axis=1) / m
    rstd = jax.lax.rsqrt(var + eps)
    mean_ref[:] = mean
    rstd_ref[:] = rstd
    # pass 3: normalize chunk-by-chunk
    for t in range(n_chunks):
        xc = x_ref[:, pl.ds(t * ftile, ftile)].astype(jnp.float32)
        mean_f = jnp.zeros((B, ftile), jnp.float32)
        rstd_f = jnp.zeros((B, ftile), jnp.float32)
        for g, oh in enumerate(onehots):
            mean_f += mean[:, g][:, None] * oh
            rstd_f += rstd[:, g][:, None] * oh
        yc = (xc - mean_f) * rstd_f * gt_ref[:, pl.ds(t * ftile, ftile)] \
            + bt_ref[:, pl.ds(t * ftile, ftile)]
        y_ref[:, pl.ds(t * ftile, ftile)] = yc.astype(y_ref.dtype)


def _bwd_kernel(x_ref, dy_ref, gt_ref, mean_ref, rstd_ref, dx_ref,
                *, G, C, eps):
    B, F = x_ref.shape
    ftile, n_chunks = _chunk_layout(F, C)
    onehots = _group_onehots(ftile, C, G)
    m = jnp.float32(F // G)
    mean = mean_ref[:]
    rstd = rstd_ref[:]
    # pass 1: per-group Σdx̂, Σdx̂·x̂
    s1l = [jnp.zeros((B, 1), jnp.float32) for _ in range(G)]
    s2l = [jnp.zeros((B, 1), jnp.float32) for _ in range(G)]
    for t in range(n_chunks):
        sl = pl.ds(t * ftile, ftile)
        xc = x_ref[:, sl].astype(jnp.float32)
        dxh = dy_ref[:, sl].astype(jnp.float32) * gt_ref[:, sl]
        for g, oh in enumerate(onehots):
            xh = (xc - mean[:, g][:, None]) * rstd[:, g][:, None]
            s1l[g] = s1l[g] + jnp.sum(dxh * oh, axis=1, keepdims=True)
            s2l[g] = s2l[g] + jnp.sum(dxh * xh * oh, axis=1, keepdims=True)
    s1 = jnp.concatenate(s1l, axis=1)
    s2 = jnp.concatenate(s2l, axis=1)
    # pass 2: dx
    for t in range(n_chunks):
        sl = pl.ds(t * ftile, ftile)
        xc = x_ref[:, sl].astype(jnp.float32)
        dxh = dy_ref[:, sl].astype(jnp.float32) * gt_ref[:, sl]
        mean_f = jnp.zeros((B, ftile), jnp.float32)
        rstd_f = jnp.zeros((B, ftile), jnp.float32)
        s1_f = jnp.zeros((B, ftile), jnp.float32)
        s2_f = jnp.zeros((B, ftile), jnp.float32)
        for g, oh in enumerate(onehots):
            mean_f += mean[:, g][:, None] * oh
            rstd_f += rstd[:, g][:, None] * oh
            s1_f += s1[:, g][:, None] * oh
            s2_f += s2[:, g][:, None] * oh
        xh = (xc - mean_f) * rstd_f
        dxc = (dxh - (s1_f + xh * s2_f) / m) * rstd_f
        dx_ref[:, sl] = dxc.astype(dx_ref.dtype)


def _flat(x):
    N = x.shape[0]
    F = 1
    for s in x.shape[1:]:
        F *= s
    return x.reshape(N, F), N, F


def _tile_feat(v, F):
    """[C] → [1, F] channel-tiled, computed in XLA (cheap, fused)."""
    C = v.shape[0]
    return jnp.broadcast_to(v.astype(jnp.float32)[None, :],
                            (F // C, C)).reshape(1, F)


def _pallas_fwd(x, gamma, beta, num_groups, eps):
    xf, N, F = _flat(x)
    C = x.shape[-1]
    BN = BLOCK_N
    kern = functools.partial(_fwd_kernel, G=num_groups, C=C, eps=eps)
    blk = lambda i: (i, 0)
    row = lambda i: (0, 0)
    y, mean, rstd = pl.pallas_call(
        kern,
        grid=(N // BN,),
        in_specs=[
            pl.BlockSpec((BN, F), blk, memory_space=_VMEM),
            pl.BlockSpec((1, F), row, memory_space=_VMEM),
            pl.BlockSpec((1, F), row, memory_space=_VMEM),
        ],
        out_specs=[
            pl.BlockSpec((BN, F), blk, memory_space=_VMEM),
            pl.BlockSpec((BN, num_groups), blk, memory_space=_VMEM),
            pl.BlockSpec((BN, num_groups), blk, memory_space=_VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, F), x.dtype),
            jax.ShapeDtypeStruct((N, num_groups), jnp.float32),
            jax.ShapeDtypeStruct((N, num_groups), jnp.float32),
        ],
    )(xf, _tile_feat(gamma, F), _tile_feat(beta, F))
    return y.reshape(x.shape), mean, rstd


def _pallas_dx(x, dy, gamma, mean, rstd, num_groups, eps):
    xf, N, F = _flat(x)
    dyf, _, _ = _flat(dy)
    C = x.shape[-1]
    BN = BLOCK_N
    kern = functools.partial(_bwd_kernel, G=num_groups, C=C, eps=eps)
    blk = lambda i: (i, 0)
    dx = pl.pallas_call(
        kern,
        grid=(N // BN,),
        in_specs=[
            pl.BlockSpec((BN, F), blk, memory_space=_VMEM),
            pl.BlockSpec((BN, F), blk, memory_space=_VMEM),
            pl.BlockSpec((1, F), lambda i: (0, 0), memory_space=_VMEM),
            pl.BlockSpec((BN, num_groups), blk, memory_space=_VMEM),
            pl.BlockSpec((BN, num_groups), blk, memory_space=_VMEM),
        ],
        out_specs=pl.BlockSpec((BN, F), blk, memory_space=_VMEM),
        out_shape=jax.ShapeDtypeStruct((N, F), x.dtype),
    )(xf, dyf, _tile_feat(gamma, F), mean, rstd)
    return dx.reshape(x.shape)


# ---------------------------------------------------------------------------
# public op with custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def group_norm(x, gamma, beta, num_groups: int = 8, eps: float = 1e-5):
    """y = GN(x)·γ + β over trailing-channel layout (groups split C)."""
    if _use_pallas(x.shape, num_groups):
        y, _, _ = _pallas_fwd(x, gamma, beta, num_groups, eps)
        return y
    return _gn_reference(x, gamma, beta, num_groups, eps)


def _gn_fwd(x, gamma, beta, num_groups, eps):
    if _use_pallas(x.shape, num_groups):
        y, mean, rstd = _pallas_fwd(x, gamma, beta, num_groups, eps)
    else:
        y = _gn_reference(x, gamma, beta, num_groups, eps)
        mean, rstd = _stats_reference(x, num_groups, eps)
    return y, (x, gamma, mean, rstd)


def _channel_grads(x, dy, mean, rstd, num_groups):
    """dγ/dβ: one fused XLA reduction over (x, dy) — cheap relative to the
    activation-sized dx pass, and XLA fuses the two sums."""
    N, C = x.shape[0], x.shape[-1]
    G, Cg = num_groups, C // num_groups
    xg = x.astype(jnp.float32).reshape(N, -1, G, Cg)
    xhat = (xg - mean[:, None, :, None]) * rstd[:, None, :, None]
    dyg = dy.astype(jnp.float32).reshape(N, -1, G, Cg)
    dg = jnp.sum(dyg * xhat, axis=(0, 1)).reshape(C)
    db = jnp.sum(dyg, axis=(0, 1)).reshape(C)
    return dg, db


def _gn_bwd(num_groups, eps, res, dy):
    x, gamma, mean, rstd = res
    if _use_pallas(x.shape, num_groups):
        dx = _pallas_dx(x, dy, gamma, mean, rstd, num_groups, eps)
    else:
        # reference dx (same math as _bwd_kernel)
        shape = x.shape
        N, C = shape[0], shape[-1]
        G, Cg = num_groups, C // num_groups
        m = 1
        for s in shape[1:-1]:
            m *= s
        m *= Cg
        xg = x.astype(jnp.float32).reshape(N, -1, G, Cg)
        xhat = (xg - mean[:, None, :, None]) * rstd[:, None, :, None]
        dyg = dy.astype(jnp.float32).reshape(N, -1, G, Cg)
        dxhat = dyg * gamma.astype(jnp.float32).reshape(1, 1, G, Cg)
        s1 = jnp.sum(dxhat, axis=(1, 3))
        s2 = jnp.sum(dxhat * xhat, axis=(1, 3))
        dx = ((dxhat - (s1[:, None, :, None] + xhat * s2[:, None, :, None])
               / m) * rstd[:, None, :, None]).reshape(shape).astype(x.dtype)
    dg, db = _channel_grads(x, dy, mean, rstd, num_groups)
    return dx, dg.astype(gamma.dtype), db.astype(gamma.dtype)


group_norm.defvjp(_gn_fwd, _gn_bwd)


_fused_gn_cls = None


def _get_fused_gn_cls():
    """Build the flax module class ONCE (flax import deferred; a fresh
    class per construction would defeat jit caches keyed on module type)."""
    global _fused_gn_cls
    if _fused_gn_cls is None:
        import flax.linen as nn

        class _FusedGN(nn.Module):
            num_groups: int = 8
            epsilon: float = 1e-5

            @nn.compact
            def __call__(self, x):
                C = x.shape[-1]
                scale = self.param("scale", nn.initializers.ones, (C,))
                bias = self.param("bias", nn.initializers.zeros, (C,))
                return group_norm(x, scale, bias, self.num_groups,
                                  self.epsilon)

        _fused_gn_cls = _FusedGN
    return _fused_gn_cls


def FusedGroupNorm(num_groups: int = 8, epsilon: float = 1e-5, name=None):
    """flax-compatible GroupNorm module backed by the fused kernels.
    Parameter names/shapes match nn.GroupNorm ("scale", "bias" of [C]), so
    checkpoints are interchangeable with the plain-XLA module."""
    return _get_fused_gn_cls()(num_groups=num_groups, epsilon=epsilon,
                               name=name)
