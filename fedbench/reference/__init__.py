"""Plain references, one module per configuration, found by the name in the
configuration file's ``"reference"`` key.

A module provides, in straightforward float32 ``jax.numpy`` with matmul
precision "highest" and neither flax nor kernels:

    forward(params, x)                  -> logits
    forward_flops(params, x_shape)      -> FLOPs of one forward pass

``params`` is the program's parameter tree, read by name.  The loss, the
local SGD and the FedAvg round are the same for every configuration and live
here.
"""
from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np


def resolve(name: str):
    return importlib.import_module(f"fedbench.reference.{name}")


def masked_ce(logits, y, mask):
    """Mean softmax cross-entropy over real (mask = 1) labels; a per-sample
    mask covers every position of a sequence label."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ce = -jnp.take_along_axis(logp, y[..., None].astype(jnp.int32), axis=-1)[..., 0]
    m = jnp.broadcast_to(mask.reshape(mask.shape + (1,) * (ce.ndim - mask.ndim)),
                         ce.shape).astype(jnp.float32)
    return jnp.sum(ce * m) / jnp.maximum(jnp.sum(m), 1.0)


def fedavg_round(ref, params, cohort: dict, lr: float, epochs: int = 1):
    """One FedAvg round: per-client SGD in a Python loop (batches with no
    real sample are skipped), then the sample-weighted mean.  Returns the new
    parameters and the sample-weighted mean of the clients' epoch losses."""
    with jax.default_matmul_precision("highest"):
        grad = jax.jit(jax.value_and_grad(
            lambda p, x, y, m: masked_ce(ref.forward(p, x), y, m)))
        total = jax.tree.map(lambda a: np.zeros(a.shape, np.float64), params)
        n_sum = loss_sum = 0.0
        K, B = cohort["mask"].shape[:2]
        for c in range(K):
            p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
            epoch_losses = []
            for _ in range(epochs):
                losses, counts = [], []
                for b in range(B):
                    m = cohort["mask"][c, b]
                    if float(np.sum(m)) == 0:
                        continue
                    loss, g = grad(p, jnp.asarray(cohort["x"][c, b]),
                                   jnp.asarray(cohort["y"][c, b]), jnp.asarray(m))
                    p = jax.tree.map(lambda a, d: a - lr * d, p, g)
                    losses.append(float(loss))
                    counts.append(float(np.sum(m)))
                epoch_losses.append(np.dot(losses, counts) / max(sum(counts), 1.0))
            n = float(np.sum(cohort["mask"][c]))
            total = jax.tree.map(lambda t, a: t + n * np.asarray(a, np.float64),
                                 total, p)
            n_sum += n
            loss_sum += n * float(np.mean(epoch_losses))
        return (jax.tree.map(lambda t: (t / n_sum).astype(np.float32), total),
                loss_sum / n_sum)
