"""ResNet-18 with GroupNorm at CIFAR shapes, in plain jax.numpy.

He et al.'s basic-block ResNet-18 (stages of 2 blocks at 64-128-256-512
channels, 3x3 stem without max-pool for 32x32 inputs, stride-2 first block
from stage 2 on with a 1x1 projection shortcut, global mean pool, linear
head), every BatchNorm replaced by GroupNorm (Wu & He 2018) with 2 groups, as
Hsieh et al. 2020 and Reddi et al. 2021 use it for federated CIFAR.
Departure: none known; epsilon 1e-6 and biased variance as the program's
flax GroupNorm has them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

GROUPS, EPS = 2, 1e-6


def conv(x, kernel, stride):
    return jax.lax.conv_general_dilated(
        x, kernel, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)


def group_norm(x, p):
    n, h, w, c = x.shape
    g = x.reshape(n, h, w, GROUPS, c // GROUPS)
    mean = jnp.mean(g, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(g - mean), axis=(1, 2, 4), keepdims=True)
    g = (g - mean) / jnp.sqrt(var + EPS)
    return g.reshape(x.shape) * p["scale"] + p["bias"]


def block(x, p, stride):
    y = jax.nn.relu(group_norm(conv(x, p["Conv_0"]["kernel"], stride),
                               p["GroupNorm_0"]))
    y = group_norm(conv(y, p["Conv_1"]["kernel"], 1), p["GroupNorm_1"])
    if "Conv_2" in p:
        x = group_norm(conv(x, p["Conv_2"]["kernel"], stride), p["GroupNorm_2"])
    return jax.nn.relu(y + x)


def _blocks(params):
    names = sorted((k for k in params if k.startswith("BasicBlockGN_")),
                   key=lambda k: int(k.rsplit("_", 1)[1]))
    return [(params[k], 2 if "Conv_2" in params[k] else 1) for k in names]


def forward(params, x):
    x = x.astype(jnp.float32)
    x = jax.nn.relu(group_norm(conv(x, params["Conv_0"]["kernel"], 1),
                               params["GroupNorm_0"]))
    for p, stride in _blocks(params):
        x = block(x, p, stride)
    x = jnp.mean(x, axis=(1, 2))
    return jnp.dot(x, params["Dense_0"]["kernel"],
                   precision=jax.lax.Precision.HIGHEST) + params["Dense_0"]["bias"]


def _valid_taps(size: int, k: int, stride: int) -> tuple[int, int]:
    """(taps that touch real input, output size) along one dimension of a
    SAME-padded convolution: products with the zero padding are not work the
    algorithm needs, and XLA's cost analysis does not count them either."""
    out = -(-size // stride)
    lo = max((out - 1) * stride + k - size, 0) // 2
    return sum(0 <= o * stride + j - lo < size
               for o in range(out) for j in range(k)), out


def forward_flops(params, x_shape, dense: bool = False) -> float:
    """Multiply-adds x 2 of the convolutions and the head for ONE sample of
    shape ``x_shape`` = (h, w, c); normalisation and activations are not
    counted (under 1 % here).  ``dense=True`` counts the products with the
    padding too, as the repo's older records did (1.7e14 a headline round)."""
    h, w, _ = x_shape

    def conv_flops(kernel, stride, h, w):
        kh, kw, cin, cout = kernel.shape
        vh, oh = _valid_taps(h, kh, stride)
        vw, ow = _valid_taps(w, kw, stride)
        if dense:
            vh, vw = kh * oh, kw * ow
        return 2.0 * cin * cout * vh * vw, oh, ow

    total, h, w = conv_flops(params["Conv_0"]["kernel"], 1, h, w)
    for p, stride in _blocks(params):
        if "Conv_2" in p:
            total += conv_flops(p["Conv_2"]["kernel"], stride, h, w)[0]
        f, h, w = conv_flops(p["Conv_0"]["kernel"], stride, h, w)
        total += f
        total += conv_flops(p["Conv_1"]["kernel"], 1, h, w)[0]
    d_in, d_out = params["Dense_0"]["kernel"].shape
    return total + 2.0 * d_in * d_out
