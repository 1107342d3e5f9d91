"""Byzantine-robust FedAvg.

Reference (fedml_api/distributed/fedavg_robust/FedAvgRobustAggregator.py:
176-206 + fedml_core/robustness/robust_aggregation.py): per-client norm
-difference clipping before the weighted average, plus optional weak-DP
Gaussian noise on the aggregate.  Additional aggregation rules beyond the
reference (krum, multi-krum, coordinate-median, trimmed-mean) are provided since they
are pure pytree ops on the stacked client axis.

Attack simulation parity: the reference schedules Byzantine clients every
`attack_freq` rounds with poisoned data (FedAvgRobustAggregator.py:221-229);
here `attack_fn` lets tests inject arbitrary update corruption on selected
cohort slots inside the jitted round.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from fedml_tpu.algorithms.fedavg import FedAvgEngine
from fedml_tpu.core.pytree import tree_weighted_mean
from fedml_tpu.core.robust import (add_weak_dp_noise, coordinate_median,
                                   default_multi_krum_m, krum_select,
                                   multi_krum_select, norm_diff_clip,
                                   trimmed_mean)


class FedAvgRobustEngine(FedAvgEngine):
    """defense: "norm_clip" (reference), "krum", "multi_krum", "median",
    "trimmed_mean"."""

    def __init__(self, trainer, data, cfg, defense: str = "norm_clip",
                 n_byzantine: int = 0, multi_krum_m: Optional[int] = None,
                 attack_fn: Optional[Callable] = None, **kw):
        self.defense = defense
        self.n_byzantine = n_byzantine
        self.multi_krum_m = default_multi_krum_m(
            min(cfg.client_num_per_round, data.client_num), n_byzantine,
            multi_krum_m)
        self.attack_fn = attack_fn
        super().__init__(trainer, data, cfg, **kw)

    def aggregate(self, stacked_variables, weights, global_variables,
                  server_state, rng):
        if self.attack_fn is not None:
            stacked_variables = self.attack_fn(stacked_variables)
        params = stacked_variables["params"]
        g = global_variables["params"]
        if self.defense == "norm_clip":
            clipped = jax.vmap(
                lambda p: norm_diff_clip(p, g, self.cfg.norm_bound))(params)
            new_params = tree_weighted_mean(clipped, weights)
            if self.cfg.stddev > 0:
                new_params = add_weak_dp_noise(new_params, rng, self.cfg.stddev)
        elif self.defense == "krum":
            i = krum_select(params, self.n_byzantine)
            new_params = jax.tree.map(lambda x: x[i], params)
        elif self.defense == "multi_krum":
            idx = multi_krum_select(params, self.n_byzantine,
                                    self.multi_krum_m)
            new_params = jax.tree.map(
                lambda x: jnp.mean(x[idx].astype(jnp.float32),
                                   axis=0).astype(x.dtype), params)
        elif self.defense == "median":
            new_params = coordinate_median(params)
        elif self.defense == "trimmed_mean":
            new_params = trimmed_mean(params, max(self.n_byzantine, 1))
        else:
            raise ValueError(self.defense)
        new_vars = {k: tree_weighted_mean(v, weights)
                    for k, v in stacked_variables.items() if k != "params"}
        new_vars["params"] = new_params
        return new_vars, server_state

    def evaluate_backdoor(self, variables, poison_shard) -> dict:
        """Backdoor success rate on a triggered test set (the reference's
        poisoned-testset eval, FedAvgRobustAggregator.test :14-111)."""
        shard = jax.tree.map(jnp.asarray, poison_shard)
        sums = self.eval_fn(variables, shard)
        n = max(float(sums["count"]), 1.0)
        return {"backdoor_acc": float(sums["correct"]) / n,
                "backdoor_loss": float(sums["loss_sum"]) / n}
