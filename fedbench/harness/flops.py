"""Operations and bytes one round needs, from shapes.

Training costs three forward passes' worth of matrix work per sample (forward,
the gradient with respect to activations, the gradient with respect to
weights); the forward count of one sample comes from the configuration's plain
reference (``forward_flops``).  Padded slots and recomputation do not count.
"""
from __future__ import annotations

import jax
import numpy as np

from fedbench import reference


def train_flops_per_sample(config: dict, params, x_shape) -> float:
    return 3.0 * reference.resolve(config["reference"]).forward_flops(params, x_shape)


def round_needs(ctx: dict) -> dict:
    """FLOPs and the floor of bytes for the mean round of this cell."""
    cell, data, engine = ctx["cell"], ctx["data"], ctx["engine"]
    x = data.client_shards["x"]
    x_shape = x.shape[3:] if np.issubdtype(x.dtype, np.floating) else x.shape[3:4]
    cohort = int(cell.traffic["cohort"])
    sizes = data.client_num_samples
    bs = int(cell.traffic["batch_size"])
    epochs = int(cell.traffic["epochs"])
    steps = cohort * epochs * float(np.mean(np.ceil(sizes / bs)))
    n_params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(ctx["params"]))
    local = np.dtype(engine.local_dtype or np.float32).itemsize
    return {"flops": epochs * cohort * float(sizes.mean())
            * train_flops_per_sample(cell.config, ctx["params"], x_shape),
            "bytes": steps * 4.0 * n_params * local,
            "steps": steps, "params": n_params}
