"""FedAvg — the canonical algorithm, TPU-native.

Reference call stack (SURVEY.md §3.1/§3.2): one OS process per client, MPI
message per model exchange, server aggregates state dicts in a Python loop.
Here the whole round is ONE jit-compiled XLA program:

    round_fn(variables, cohort_shards, rng)
      = vmap(local_train) over the cohort axis       (clients in parallel)
      → sample-weighted tree mean                    (aggregation)

The cohort axis can further be sharded over a `Mesh` (parallel/engine.py) so
aggregation lowers to a `psum` over ICI.  The Python layer is only: sample
client ids (reference-identical numpy semantics), gather the cohort with
`jnp.take`, log metrics.

Parity targets: fedml_api/standalone/fedavg/fedavg_api.py:40-115 (loop,
_aggregate), fedml_api/distributed/fedavg/FedAVGAggregator.py:59-98
(weighted average + sampling), FedAVGTrainer/MyModelTrainer (local SGD).
"""
from __future__ import annotations

import logging
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu import obs
from fedml_tpu.core.pytree import tree_weighted_mean
from fedml_tpu.core.sampling import ClientSampler
from fedml_tpu.core.trainer import ClientTrainer
from fedml_tpu.data.federated import FederatedData
from fedml_tpu.utils.config import FedConfig

log = logging.getLogger(__name__)
Pytree = Any


class FedAvgEngine:
    """Standalone-simulation FedAvg (single device or vmap cohort)."""

    def __init__(self, trainer: ClientTrainer, data: FederatedData,
                 cfg: FedConfig, donate: bool = True):
        self.trainer = trainer
        self.data = data
        self.cfg = cfg
        self.donate = donate
        self.sampler = ClientSampler.for_data(data, cfg)
        # donate BOTH the variables and the server state (FedOpt's adam
        # moments are 2x params — donating avoids an HBM copy per round)
        self.round_fn = jax.jit(
            self._round, donate_argnums=(0, 1) if donate else ())
        self.eval_fn = jax.jit(self.trainer.evaluate)
        # upload eval shards once; evaluate() then runs fully device-side
        self._eval_shards = {
            "train": jax.tree.map(jnp.asarray, data.train_global),
            "test": jax.tree.map(jnp.asarray, data.test_global),
        }
        self._local_eval_fn = None    # built lazily by evaluate_local
        self._local_eval_shards = {}
        self.metrics_history: list[dict] = []

    # ---- server state (FedOpt's persistent optimizer etc.) ----------------
    def server_init(self, variables: Pytree) -> Pytree:
        return ()

    # ---- aggregation customization point (FedOpt/robust override) --------
    def aggregate(self, stacked_variables: Pytree, weights: jax.Array,
                  global_variables: Pytree, server_state: Pytree,
                  rng: jax.Array) -> tuple[Pytree, Pytree]:
        """Sample-weighted mean over ALL variable collections (params and
        batch_stats alike), matching the reference's iteration over every
        state_dict key (FedAVGAggregator.py:74-81)."""
        return tree_weighted_mean(stacked_variables, weights), server_state

    # ---- one federated round, fully jitted -------------------------------
    def _round(self, variables: Pytree, server_state: Pytree, cohort: dict,
               rng: jax.Array):
        K = cohort["mask"].shape[0]
        rng, agg_rng = jax.random.split(rng)
        client_rngs = jax.random.split(rng, K)
        global_params = variables["params"] if self.trainer.prox_mu > 0 else None

        # a model that freezes part of its parameters (ClientTrainer.
        # split_frozen) trains, stacks and averages the rest; the frozen
        # leaves are read un-mapped and come back as they went in
        trained_of = self.trainer.trained_variables

        def one_client(shard, crng):
            v, loss, n = self.trainer.local_train(
                variables, shard, crng, self.cfg.epochs,
                global_params=global_params)
            return trained_of(v), loss, n

        stacked_vars, losses, ns = jax.vmap(one_client)(cohort, client_rngs)
        new_variables, server_state = self.aggregate(
            stacked_vars, ns, trained_of(variables), server_state, agg_rng)
        new_variables = self.trainer.with_frozen(new_variables, variables)
        train_loss = jnp.sum(losses * ns) / jnp.sum(ns)
        return new_variables, server_state, {"train_loss": train_loss}

    # ---- driver loop ------------------------------------------------------
    def init_variables(self, rng: Optional[jax.Array] = None) -> Pytree:
        rng = rng if rng is not None else jax.random.PRNGKey(self.cfg.seed)
        sample = jnp.asarray(self.data.client_shards["x"][0, 0])
        return self.trainer.init(rng, sample)

    # ---- driver-loop hooks (mesh engines override) ------------------------
    def _prepare_variables(self, variables: Pytree) -> Pytree:
        """Post-init/post-restore placement hook (mesh: replicate)."""
        return variables

    def _round_args(self, round_idx: int) -> tuple:
        """Per-round positional args for round_fn between server_state and
        the rng (mesh: the resident device stack + padded cohort ids)."""
        client_ids = self.sampler.sample(round_idx)
        cohort, _ = self.data.cohort(client_ids)
        return (cohort,)

    def run(self, variables: Optional[Pytree] = None,
            rounds: Optional[int] = None, logger=None, ckpt=None,
            ckpt_every: int = 0, resume: bool = False) -> Pytree:
        """The reference's train() loop (fedavg_api.py:40-81), plus the
        round-level checkpoint/resume the reference lacks (SURVEY.md §5):
        `ckpt` is a utils.checkpoint.FedCheckpointManager; with `resume`
        the run continues bitwise-identically (per-round rngs are
        fold_in(round_idx), the sampler reseeds per round).  This one loop
        drives the vmap-simulation and all mesh engines via the
        _prepare_variables/_round_args hooks."""
        cfg = self.cfg
        variables = variables if variables is not None else self.init_variables()
        variables = self._prepare_variables(variables)
        server_state = self.server_init(variables)
        rng_base = jax.random.PRNGKey(cfg.seed + 1)
        rounds = rounds if rounds is not None else cfg.comm_round
        self._rounds_limit = rounds       # lets _round_args bound prefetch
        start = 0
        if ckpt is not None and resume and ckpt.latest_round() is not None:
            start, variables, server_state = ckpt.restore(
                variables, server_state)
            start += 1
            variables = self._prepare_variables(variables)
            # restored state arrives committed to one local device; mesh
            # engines re-replicate it (a multi-process mesh jit rejects
            # the mixed placement outright)
            server_state = self._prepare_server_state(server_state)
            log.info("resumed from round %d", start - 1)
        # observability (fedml_tpu/obs; all no-ops unless --obs_dir):
        # each round gets a span + an optional deadline watchdog (a
        # flight-recorder dump fires if the round overruns
        # cfg.round_deadline_s); an unhandled error dumps the ring
        # before re-raising
        deadline_s = getattr(cfg, "round_deadline_s", None)
        engine_name = type(self).__name__
        try:
            for round_idx in range(start, rounds):
                t0 = time.time()
                round_rng = jax.random.fold_in(rng_base, round_idx)
                with obs.deadline(f"round{round_idx}", deadline_s), \
                        obs.span("round", round=round_idx,
                                 engine=engine_name):
                    variables, server_state, m = self.round_fn(
                        variables, server_state,
                        *self._round_args(round_idx), round_rng)
                if (round_idx % cfg.frequency_of_the_test == 0
                        or round_idx == rounds - 1):
                    with obs.span("eval", round=round_idx):
                        stats = self.evaluate(variables)
                    stats.update(round=round_idx,
                                 train_loss=float(m["train_loss"]),
                                 round_time=time.time() - t0)
                    self.metrics_history.append(stats)
                    if logger is not None:
                        logger.log(stats, step=round_idx)
                    log.info("round %d: %s", round_idx, stats)
                    if obs.enabled():       # live/peak HBM per eval round
                        obs.sample_device_memory()
                if ckpt is not None and ckpt_every and \
                        (round_idx + 1) % ckpt_every == 0:
                    with obs.span("checkpoint", round=round_idx):
                        ckpt.save(round_idx, variables, server_state)
        except Exception as e:
            obs.dump_flight(f"engine_error:{engine_name}: {e!r}")
            raise
        return variables

    def evaluate(self, variables: Pytree) -> dict:
        """Server-side eval on global train/test shards
        (FedAVGAggregator.test_on_server_for_all_clients, :110-164)."""
        out = {}
        for split, shard in self._eval_shards.items():
            sums = self.eval_fn(variables, shard)
            cnt = float(sums["count"])
            out[f"{split}_acc"] = float(sums["correct"]) / max(cnt, 1.0)
            out[f"{split}_loss"] = float(sums["loss_sum"]) / max(cnt, 1.0)
        if (self.cfg.local_test_eval
                and self.data.test_client_shards is not None
                and not getattr(self, "streaming", False)):
            # streaming exists because the per-client stack does NOT fit
            # in HBM — never auto-materialize it for eval there.
            # --no_local_test_eval opts out of the cost entirely; mesh
            # engines shard the uploaded test stack (_upload_eval_stack)
            out.update(self.evaluate_local(variables))
        return out

    def _local_eval_transform(self, shard: dict) -> dict:
        """Per-client shard hook inside evaluate_local's vmap (mesh
        engines restore flat_stack x here; identity for this engine)."""
        return shard

    def _prepare_server_state(self, server_state):
        """Device placement for a checkpoint-restored server_state (mesh
        engines replicate over the mesh; identity here)."""
        return server_state

    def _upload_eval_stack(self, shards):
        """Device placement for the [C,...] per-client eval stack (mesh
        engines override to shard the client axis — evaluate_local must
        not concentrate a stack on one device that training had to
        shard to fit)."""
        return jax.tree.map(jnp.asarray, shards)

    def evaluate_local(self, variables: Pytree, split: str = "test") -> dict:
        """Eval on every client's OWN shard — the reference's
        _local_test_on_all_clients (fedavg_api.py:117-213): per-client
        correct/total sums aggregated into one weighted accuracy, for the
        clients' test shards (split="test", needs the dataset's natural
        per-client test split) or train shards (split="train", always
        available — the reference's local Train/Acc).  With cfg.ci the
        eval truncates to the first client (the reference's --ci 1 CPU-CI
        mode, fedavg_api.py:157-162)."""
        if split not in ("train", "test"):
            raise ValueError(f"split must be 'train' or 'test', got "
                             f"{split!r}")
        if split == "test" and self.data.test_client_shards is None:
            raise ValueError("this dataset has no per-client test shards")
        if getattr(self, "streaming", False):
            raise ValueError("streaming engines keep the client stack on "
                             "host; evaluate_local would materialize it "
                             "in HBM")
        if self._local_eval_fn is None:
            # _local_eval_transform: mesh engines restore flat_stack x
            # in-program before the per-client eval (identity here)
            self._local_eval_fn = jax.jit(jax.vmap(
                lambda v, s: self.trainer.evaluate(
                    v, self._local_eval_transform(s)),
                in_axes=(None, 0)))
        if split not in self._local_eval_shards:
            if split == "train" and not self.cfg.ci:
                # a train stack is already device-resident for cohorts —
                # reuse it rather than holding a second HBM copy: the mesh
                # engine's padded sharded stack (zero-weight pad lanes
                # have mask 0, so they add nothing to the sums), else the
                # plain engine's device_shards cache.  Only a [C, ...]
                # stack qualifies (the hierarchical engine keeps a
                # silo-major [S, C/S, ...] layout — fall through to a
                # fresh upload there).
                resident = getattr(self, "_stack", None)
                if (resident is not None
                        and resident["mask"].ndim
                        != np.asarray(self.data.client_shards["mask"]).ndim):
                    resident = None
                self._local_eval_shards[split] = (
                    resident if resident is not None
                    else self.data.device_shards()[0])
            else:
                # upload once (ci-truncated if set), like _eval_shards
                shards = (self.data.test_client_shards if split == "test"
                          else self.data.client_shards)
                if self.cfg.ci:
                    shards = jax.tree.map(lambda a: a[:1], shards)
                self._local_eval_shards[split] = \
                    self._upload_eval_stack(shards)
        sums = self._local_eval_fn(variables,
                                   self._local_eval_shards[split])
        cnt = float(jnp.sum(sums["count"]))
        return {
            f"local_{split}_acc":
                float(jnp.sum(sums["correct"])) / max(cnt, 1.0),
            f"local_{split}_loss":
                float(jnp.sum(sums["loss_sum"])) / max(cnt, 1.0),
        }
