"""The reference check, outside the window.

One float32 round of the cell's engine class on a seeded sample at the
published widths (the first few clients and batches of the cell's own data,
full participation) must match one FedAvg round of the configuration's plain
reference, parameter for parameter.  The reference is handed everything the
program holds (``variables``) and, where the configuration's local training
updates a subset of the weights, the subset's path prefixes
(``check.trainable``; absent = every leaf of ``params``): it trains those and
reads the rest in place, and every leaf of ``params`` is compared, so a leaf
the configuration calls frozen must come back as it went in.

The check runs before the cell's own engine and resident stack exist, and its
engine and the round's outputs are dropped before the reference starts: what
must fit the chip is the larger of the two, not their sum beside the cell.

Tolerance: max |delta| <= ``check.param_tol`` x max |update|, written in the
configuration's file with its reason, because the float32 noise floor is the
model's: two float32 implementations of one round differ by summation order,
and a second local step at a loss of 8-10 amplifies the first's difference
through twenty normalised layers (ResNet-18-GN: up to 1.35e-3 of the update,
on the v5e and on a CPU alike) while one LSTM layer keeps it under 1e-5.  The
tolerance sits between that floor and what one bfloat16 pass misses by at the
published widths (measured, PR 22: 1.8e-2 / 3.4e-2 for the ResNet cells,
1.8e-3 for the LSTM).  The mean train loss must agree to 1e-4 relative.
"""
from __future__ import annotations

import gc

import jax
import numpy as np

from fedbench import reference
from fedbench.harness import build, loop

LOSS_TOL = 1e-4
# the cell's first bf16 round runs on other samples and more local steps than
# the check sample, so its train loss is held to a band, not to a value
LOSS_BAND = (0.25, 4.0)


def check_round(config: dict, traffic: dict, data, seed: int, sample: dict) -> dict:
    from fedml_tpu.data.federated import FederatedData
    k, b = int(sample["clients"]), int(sample["batches"])
    shards = {key: np.ascontiguousarray(v[:k, :b])
              for key, v in data.client_shards.items()}
    sizes = shards["mask"].reshape(k, -1).sum(axis=1)
    small = FederatedData(
        train_data_num=int(sizes.sum()), test_data_num=1,
        train_global=data.train_global, test_global=data.test_global,
        client_shards=shards, client_num_samples=sizes.astype(np.float32),
        test_client_shards=None, class_num=data.class_num, synthetic=True)
    full = dict(traffic, cohort=k)
    with jax.default_matmul_precision("highest"):
        engine = build.make_engine(
            config, full, small, seed, train_dtype="float32", local_dtype=None)
        variables = engine._prepare_variables(build.init_variables(engine))
        held = jax.tree.map(np.asarray, variables)      # the round donates them
        new_vars, _, m = engine.round_fn(
            variables, engine.server_init(variables), *engine._round_args(0),
            jax.random.fold_in(jax.random.PRNGKey(seed + 1), 0))
        got = jax.tree.map(np.asarray, new_vars["params"])
        got_loss = float(m["train_loss"])
    loop.join_prefetch(engine)
    lr, epochs = engine.cfg.lr, engine.cfg.epochs
    del engine, variables, new_vars, m
    gc.collect()                    # the engine's device buffers, before the reference's
    want, want_loss = reference.fedavg_round(
        reference.resolve(config["reference"]), held, shards, lr, epochs,
        config["check"].get("trainable"))
    leaves = lambda t: jax.tree.leaves(t)
    delta = max(float(np.max(np.abs(g - w)))
                for g, w in zip(leaves(got), leaves(want)))
    update = max(float(np.max(np.abs(w - a)))
                 for w, a in zip(leaves(want), leaves(held["params"])))
    loss_err = abs(got_loss - want_loss) / max(abs(want_loss), 1e-12)
    tol = float(config["check"]["param_tol"])
    return {"ok": bool(delta <= tol * update and loss_err <= LOSS_TOL
                       and update > 0),
            "max_abs_delta": delta, "max_abs_update": update,
            "engine_loss": got_loss, "reference_loss": want_loss}


def loss_in_band(first_loss: float, reference_loss: float) -> bool:
    lo, hi = LOSS_BAND
    return bool(lo * reference_loss <= first_loss <= hi * reference_loss)
