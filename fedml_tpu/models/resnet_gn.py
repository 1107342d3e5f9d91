"""ResNet-18 with GroupNorm (reference fedml_api/model/cv/resnet_gn.py +
group_normalization.py), the fed_CIFAR100 model of 'Adaptive Federated
Optimization'.

GroupNorm (not BatchNorm) is the federated-friendly choice: no running stats
to average, and every client step is batch-size independent — which also
means the whole variables pytree is pure params, the cheapest case for
vmap/shard_map over the client axis.
"""
from __future__ import annotations

from functools import partial
from typing import Sequence

import flax.linen as nn
import jax.numpy as jnp


class BasicBlockGN(nn.Module):
    filters: int
    strides: int = 1
    groups: int = 2

    @nn.compact
    def __call__(self, x, train: bool = False):
        norm = partial(nn.GroupNorm, num_groups=self.groups)
        residual = x
        y = nn.Conv(self.filters, (3, 3), strides=(self.strides, self.strides),
                    padding="SAME", use_bias=False)(x)
        y = norm()(y)
        y = nn.relu(y)
        y = nn.Conv(self.filters, (3, 3), padding="SAME", use_bias=False)(y)
        y = norm()(y)
        if residual.shape != y.shape:
            residual = nn.Conv(self.filters, (1, 1),
                               strides=(self.strides, self.strides),
                               use_bias=False)(x)
            residual = norm()(residual)
        return nn.relu(y + residual)


class ResNet18GN(nn.Module):
    num_classes: int = 100
    stage_sizes: Sequence[int] = (2, 2, 2, 2)
    num_filters: int = 64
    groups: int = 2

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = nn.Conv(self.num_filters, (3, 3), padding="SAME", use_bias=False)(x)
        x = nn.GroupNorm(num_groups=self.groups)(x)
        x = nn.relu(x)
        for i, n_blocks in enumerate(self.stage_sizes):
            for j in range(n_blocks):
                strides = 2 if i > 0 and j == 0 else 1
                x = BasicBlockGN(self.num_filters * (2 ** i), strides,
                                 self.groups)(x, train)
        x = jnp.mean(x, axis=(1, 2))
        return nn.Dense(self.num_classes)(x)
