"""Plain references, one module per configuration, found by the name in the
configuration file's ``"reference"`` key.

A module provides, in straightforward float32 ``jax.numpy`` with matmul
precision "highest" and neither flax nor kernels:

    forward(params, x)                  -> logits
    forward_flops(params, x_shape)      -> FLOPs of one forward pass

``params`` is the program's parameter tree, read by name; a collection the
program keeps beside ``params`` reaches ``forward`` as a keyword argument of
its name.  The loss, the local SGD and the FedAvg round are the same for
every configuration and live here.

A configuration whose local training updates a subset of its weights (a
frozen base under adapters) names the subset in its file, ``check.trainable``
(path prefixes under ``params``, ``"Dense_1"`` or ``"block_3/attn"``), and its
module may state its own work for the roofline (fedbench/harness/flops.py):

    train_flops(params, x_shape)        -> FLOPs to train on one sample
    step_bytes(params, local_itemsize)  -> least bytes moved per local step
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


def fedavg_round(ref, variables, cohort: dict, lr: float, epochs: int = 1,
                 trainable=None):
    """One FedAvg round: per-client SGD in a Python loop (batches with no
    real sample are skipped), then the sample-weighted mean.  Returns the new
    parameters and the sample-weighted mean of the clients' epoch losses.

    ``variables`` is everything the program holds.  ``trainable`` lists path
    prefixes under ``params`` (None = every leaf).  Only those leaves are
    copied per client, differentiated and accumulated in float64; every other
    leaf is placed once, read in place by every client, and returned as the
    object that was handed in: what must fit the chip beside a frozen base is
    the trainable subset three times over, not the model."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(variables["params"])
    names = ["/".join(str(getattr(k, "key", k)) for k in path) for path, _ in paths]
    trains = [trainable is None
              or any(n == p or n.startswith(p + "/") for p in trainable)
              for n in names]
    if not any(trains):
        raise ValueError(f"check.trainable {trainable} names no leaf of {names}")
    start = [a for (_, a), t in zip(paths, trains) if t]
    handed = [a for (_, a), t in zip(paths, trains) if not t]
    frozen = [jnp.asarray(a) for a in handed]
    others = {k: jax.tree.map(jnp.asarray, v)
              for k, v in variables.items() if k != "params"}

    def whole(train, held):
        train, held = iter(train), iter(held)
        return jax.tree.unflatten(
            treedef, [next(train) if t else next(held) for t in trains])

    with jax.default_matmul_precision("highest"):
        grad = jax.jit(jax.value_and_grad(
            lambda p, held, rest, x, y, m: masked_ce(
                ref.forward(whole(p, held), x, **rest), y, m)))
        total = [np.zeros(a.shape, np.float64) for a in start]
        n_sum = loss_sum = 0.0
        K, B = cohort["mask"].shape[:2]
        for c in range(K):
            p = [jnp.asarray(a, jnp.float32) for a in start]
            epoch_losses = []
            for _ in range(epochs):
                losses, counts = [], []
                for b in range(B):
                    m = cohort["mask"][c, b]
                    if float(np.sum(m)) == 0:
                        continue
                    loss, g = grad(p, frozen, others,
                                   jnp.asarray(cohort["x"][c, b]),
                                   jnp.asarray(cohort["y"][c, b]), jnp.asarray(m))
                    p = [a - lr * d for a, d in zip(p, g)]
                    losses.append(float(loss))
                    counts.append(float(np.sum(m)))
                epoch_losses.append(np.dot(losses, counts) / max(sum(counts), 1.0))
            n = float(np.sum(cohort["mask"][c]))
            total = [t + n * np.asarray(a, np.float64) for t, a in zip(total, p)]
            n_sum += n
            loss_sum += n * float(np.mean(epoch_losses))
        mean = [(t / n_sum).astype(np.float32) for t in total]
        return (whole(mean, handed),
                loss_sum / n_sum)
