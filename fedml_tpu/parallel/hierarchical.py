"""Hierarchical FL on a 2-D (silo × clients) mesh.

Reference: fedml_api/standalone/hierarchical_fl/{trainer,group,client}.py —
clients → groups run `group_comm_round` inner FedAvg rounds, groups → global
average every `global_comm_round` (trainer.py:44-69, group.py:24-46).

TPU-native, the two aggregation tiers map onto the two mesh axes:

    inner round:  psum over the "clients" axis only   → per-silo model (ICI)
    outer round:  psum over the "silo" axis           → global model   (DCN)

so a full global round — G inner rounds on every silo plus the cross-silo
reduction — is ONE SPMD program; per-silo models never leave HBM.

Invariant kept from the reference CI (CI-script-fedavg.sh:51-59): with full
batch, E=1, full participation and one inner round, the result equals plain
FedAvg (and hence centralized) regardless of the client→silo grouping,
because Σ_g (W_g/W)·(Σ_i w_i v_i / W_g) = Σ_i (w_i/W) v_i.
"""
from __future__ import annotations

import logging
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fedml_tpu.algorithms.fedavg import FedAvgEngine
from fedml_tpu.core.trainer import ClientTrainer
from fedml_tpu.data.federated import FederatedData
from fedml_tpu.parallel.engine import (cast_local, chunked_weighted_train,
                                       flatten_stack_x, population_trips,
                                       restore_chunk_x, default_chunk)
from fedml_tpu.parallel.mesh import (CLIENT_AXIS, SILO_AXIS, make_mesh_2d,
                                     pvary_tree)
from fedml_tpu.utils.config import FedConfig

log = logging.getLogger(__name__)
Pytree = Any


class MeshHierarchicalEngine(FedAvgEngine):
    """Two-tier FedAvg over a (silo, clients) mesh.

    Clients are assigned to silos contiguously: silo g owns client ids
    [g*C/S, (g+1)*C/S).  Each global round runs `group_comm_round` inner
    rounds; inner cohorts are sampled per silo with the reference's seeded
    numpy semantics (round-deterministic)."""

    def __init__(self, trainer: ClientTrainer, data: FederatedData,
                 cfg: FedConfig, n_silos: int = 2,
                 group_comm_round: int = 1,
                 mesh: Optional[Mesh] = None, donate: bool = True,
                 chunk: Optional[int] = None, local_dtype=None,
                 flat_stack: bool = True):
        if chunk is not None and chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        self.chunk = (chunk if chunk is not None
                      else default_chunk(local_dtype))
        self.local_dtype = local_dtype   # bf16 local masters (engine.py)
        # flat image-cohort storage + per-chunk restore, same rationale
        # and helpers as MeshFedAvgEngine (engine.py flat_stack)
        self.flat_stack = flat_stack
        self._x_image_shape = None
        self.mesh = mesh if mesh is not None else make_mesh_2d(n_silos)
        self.n_silos = self.mesh.shape[SILO_AXIS]
        self.per_silo_shards = self.mesh.shape[CLIENT_AXIS]
        self.group_comm_round = group_comm_round
        super().__init__(trainer, data, cfg, donate=donate)
        C = data.client_num
        assert C % self.n_silos == 0, (
            f"{C} clients cannot split into {self.n_silos} silos")
        self.clients_per_silo = C // self.n_silos
        # a population that leaves batches of the stack empty gets the
        # ordered cohort and the bounded batch loop (engine.py)
        self._ragged_batches = population_trips(data)[1]
        self._stack = None
        self._stack_w = None
        from fedml_tpu.obs import programs as obs_programs
        self.program_family = "hierarchical"
        self.round_fn = obs_programs.instrument(
            self.program_family,
            jax.jit(self._global_round,
                    donate_argnums=(0, 1) if donate else ()))

    # -- data layout: [S, C/S, B, bs, ...] sharded (silo, clients) ----------
    def _device_stack(self):
        if self._stack is None:
            S, Cs = self.n_silos, self.clients_per_silo
            sh = NamedSharding(self.mesh, P(SILO_AXIS, CLIENT_AXIS))
            # pad the per-silo client dim to a multiple of the client-axis size
            pad = (-Cs) % self.per_silo_shards
            def up(a):
                a = np.asarray(a)
                a = a.reshape((S, Cs) + a.shape[1:])
                if pad:
                    z = np.zeros((S, pad) + a.shape[2:], a.dtype)
                    a = np.concatenate([a, z], axis=1)
                return jax.device_put(a, sh)
            shards = dict(self.data.client_shards)
            if self.flat_stack:
                shards, image_shape = flatten_stack_x(shards)
                if image_shape is not None:
                    self._x_image_shape = image_shape
            self._stack = {k: up(v) for k, v in shards.items()}
            w = np.asarray(self.data.client_num_samples, np.float32)
            self._stack_w = up(w)
            self._cs_padded = Cs + pad
        return self._stack, self._stack_w

    # -- sampling: per-silo cohort ids for every inner round ----------------
    def sample_inner_rounds(self, global_round: int):
        """ids[g_round, silo, K_pad] (silo-local indices) + wmask like it.
        Reference seed discipline (group.py / fedavg_api.py:83-91): the
        reference's draw per sampling call, from a private generator
        seeded with the call's round."""
        K = min(self.cfg.client_num_per_round, self.clients_per_silo)
        Kp = K + ((-K) % self.per_silo_shards)
        G = self.group_comm_round
        ids = np.zeros((G, self.n_silos, Kp), np.int32)
        wmask = np.zeros((G, self.n_silos, Kp), np.float32)
        for g in range(G):
            rs = np.random.RandomState(global_round * self.group_comm_round + g)
            for s in range(self.n_silos):
                if K == self.clients_per_silo:
                    pick = np.arange(K)
                else:
                    pick = rs.choice(self.clients_per_silo, K, replace=False)
                ids[g, s, :K] = pick
                wmask[g, s, :K] = 1.0
        return jnp.asarray(ids), jnp.asarray(wmask)

    # -- the global round program -------------------------------------------
    def _global_round(self, variables, server_state, stack, stack_w, ids,
                      wmask, rng):
        mesh = self.mesh
        trainer, epochs = self.trainer, self.cfg.epochs
        G = self.group_comm_round
        sc = P(SILO_AXIS, CLIENT_AXIS)

        def shard_body(variables, stack, stack_w, ids, wmask, rngs):
            # local shapes: stack [1, c_loc, B, bs, ...], ids [G, 1, k_loc]
            # silo-local gather, hoisted OUT of the inner-round scan (XLA
            # does not hoist collectives from scan bodies): all_gather this
            # silo's client shards along the client axis once; data volume
            # per silo is small (C/S clients) and the gather rides ICI.
            full = jax.tree.map(
                lambda a: jax.lax.all_gather(a[0], CLIENT_AXIS, tiled=True),
                stack)
            w_full = jax.lax.all_gather(stack_w[0], CLIENT_AXIS, tiled=True)

            def inner_round(vars_g, inp):
                ids_g, wm_g, rng_g = inp          # [1,k_loc], [1,k_loc], [2]
                idx = ids_g[0]
                cohort = jax.tree.map(lambda a: jnp.take(a, idx, axis=0), full)
                weights = jnp.take(w_full, idx) * wm_g[0]
                crngs = jax.random.split(rng_g, idx.shape[0])
                # per-client training varies over the client axis too
                vars_g = pvary_tree(vars_g, CLIENT_AXIS)
                # bf16 local masters: silo/global masters stay f32, only
                # the per-client step chain runs reduced (engine.py)
                local_vars = cast_local(vars_g, self.local_dtype)
                # chunked inner loop (same HBM-bounding scan as the flat
                # engine, parallel/engine.py::chunked_weighted_train)
                num, den, lsum, _counters = chunked_weighted_train(
                    trainer, local_vars, cohort, weights, crngs, epochs,
                    vary_axes=(SILO_AXIS, CLIENT_AXIS),
                    chunk_cap=self.chunk,
                    restore_x=lambda cs: restore_chunk_x(
                        self._x_image_shape, cs),
                    ragged_batches=self._ragged_batches)
                num = jax.lax.psum(num, CLIENT_AXIS)        # ICI tier
                den = jax.lax.psum(den, CLIENT_AXIS)
                silo_vars = jax.tree.map(
                    lambda s, ref: (s / den).astype(ref.dtype), num, vars_g)
                loss = jax.lax.psum(lsum, CLIENT_AXIS) / den
                return silo_vars, (loss, den)

            inner_rngs = jax.random.split(rngs, G)
            # the scan carries the *per-silo* model (replicated within a
            # silo, distinct across silos); mark the initial carry as
            # silo-varying so the carry type is stable across iterations
            vars0 = pvary_tree(variables, SILO_AXIS)
            silo_vars, (losses, dens) = jax.lax.scan(
                inner_round, vars0, (ids, wmask, inner_rngs))
            # outer tier: sample-weighted cross-silo average (DCN psum)
            W_g = dens[-1]
            num = jax.tree.map(
                lambda v: jax.lax.psum(v.astype(jnp.float32) * W_g,
                                       SILO_AXIS), silo_vars)
            W = jax.lax.psum(W_g, SILO_AXIS)
            new_vars = jax.tree.map(
                lambda s, ref: (s / W).astype(ref.dtype), num, variables)
            loss = jax.lax.psum(losses[-1] * W_g, SILO_AXIS) / W
            return new_vars, loss

        new_variables, train_loss = jax.shard_map(
            shard_body, mesh=mesh,
            in_specs=(P(), sc, sc, P(None, SILO_AXIS, CLIENT_AXIS),
                      P(None, SILO_AXIS, CLIENT_AXIS), P()),
            out_specs=(P(), P()))(
                variables, stack, stack_w, ids, wmask, rng)
        return new_variables, server_state, {"train_loss": train_loss}

    # the base FedAvgEngine.run drives the loop through these hooks
    def _prepare_variables(self, variables: Pytree) -> Pytree:
        from fedml_tpu.parallel.mesh import replicated_sharding
        return jax.device_put(variables, replicated_sharding(self.mesh))

    def _round_args(self, round_idx: int) -> tuple:
        stack, stack_w = self._device_stack()
        ids, wmask = self.sample_inner_rounds(round_idx)
        return (stack, stack_w, ids, wmask)
