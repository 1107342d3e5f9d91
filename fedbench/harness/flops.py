"""Operations and bytes one round needs, from shapes.

Training costs three forward passes' worth of matrix work per sample (forward,
the gradient with respect to activations, the gradient with respect to
weights); the forward count of one sample comes from the configuration's plain
reference (``forward_flops``), and each local step reads the client's weights
forward and backward and reads + writes them in the update.  A configuration
for which that is not the work - a frozen base has no weight gradient and no
update - states its own in its reference module (``train_flops``,
``step_bytes``).  Padded slots and recomputation do not count.
"""
from __future__ import annotations

import jax
import numpy as np

from fedbench import reference


def round_needs(ctx: dict) -> dict:
    """FLOPs and the floor of bytes for the mean round of this cell."""
    cell, data, engine = ctx["cell"], ctx["data"], ctx["engine"]
    ref, params = reference.resolve(cell.config["reference"]), ctx["params"]
    x = data.client_shards["x"]
    x_shape = x.shape[3:] if np.issubdtype(x.dtype, np.floating) else x.shape[3:4]
    cohort = int(cell.traffic["cohort"])
    sizes = data.client_num_samples
    bs = int(cell.traffic["batch_size"])
    epochs = int(cell.traffic["epochs"])
    steps = cohort * epochs * float(np.mean(np.ceil(sizes / bs)))
    n_params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    local = np.dtype(engine.local_dtype or np.float32).itemsize
    per_sample = (ref.train_flops(params, x_shape) if hasattr(ref, "train_flops")
                  else 3.0 * ref.forward_flops(params, x_shape))
    per_step = (ref.step_bytes(params, local) if hasattr(ref, "step_bytes")
                else 4.0 * n_params * local)
    return {"flops": epochs * cohort * float(sizes.mean()) * per_sample,
            "bytes": steps * per_step, "steps": steps, "params": n_params}
