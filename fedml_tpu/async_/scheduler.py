"""Event-driven async round scheduler — the virtual-time simulation path.

`AsyncFedAvgEngine` simulates a buffered-asynchronous federation
(FedBuff-style: commit on K buffered results or a round deadline;
FedAsync is the K=1 degenerate config) over a SIMULATED clock: client
latencies, crashes, and rejoins come from the seeded lifecycle model
(fedml_tpu/async_/lifecycle.py), dispatch order is a deterministic
event heap, and no thread ever sleeps — a 10,000-commit churn study
runs at compute speed and is bit-reproducible per `--async_seed`
(pinned in tests/test_async.py).  The real-thread/real-socket
counterpart over the comm backends is lifecycle.run_async_messaging.

TPU-native structure: client training happens in DISPATCH WAVES — all
clients handed work at the same moment share one jitted
vmap(local_train) program (the same one_client body the synchronous
FedAvgEngine vmaps), so the simulator keeps the cohort-batched XLA
shape of the rest of the repo instead of decaying into per-client
dispatches.  Results are flattened to f32 buffer rows on device
(flat-carry layout, staleness.flatten_stacked_rows) and surface to the
host once per wave.

The degenerate config — zero latency, zero dropout, buffer_k == cohort,
constant staleness weight, mix 1.0 — reproduces the synchronous FedAvg
engine BITWISE: wave w dispatches exactly sampler.sample(w) with the
sync path's per-round rng derivation, the wave trains at the sync vmap
width, and the mixing-form commit reduces to the same
tree_weighted_mean (see staleness.py).  That pin is what anchors the
async numerics to the rest of the repo.
"""
from __future__ import annotations

import dataclasses
import heapq
import logging
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu import obs
from fedml_tpu.algorithms.fedavg import FedAvgEngine
from fedml_tpu.async_.adversary import (AdversarySim, AttackConfig,
                                        apply_data_attack)
from fedml_tpu.async_.defense import (DefenseConfig, UpdateAdmission,
                                      make_flatten_fn)
from fedml_tpu.async_.lifecycle import ClientLifecycle, LifecycleConfig
from fedml_tpu.async_.staleness import (AsyncBuffer, STALENESS_MODES,
                                        flat_dim, flatten_stacked_rows,
                                        make_bucket_commit_fn,
                                        make_commit_fn)
from fedml_tpu.scale import registry as _reg
from fedml_tpu.scale.arrivals import (ArrivalConfig, ArrivalProcess,
                                      make_arrivals)
from fedml_tpu.scale.registry import ClientRegistry

log = logging.getLogger(__name__)
Pytree = Any


class AsyncSchedulerDeadlock(RuntimeError):
    """No event can ever arrive and the buffer can never fill — the
    federation is dead (every client crashed with no rejoin and no
    deadline configured).  A flight dump is written at raise time, so
    the generic engine-error handler must not dump a second copy."""

# event kinds, in tie-break priority at equal virtual time: arrivals
# before rejoins (a rejoin at the same instant joins the NEXT wave)
_ARRIVE, _REJOIN, _DEADLINE = 0, 1, 2


class AsyncFedAvgEngine(FedAvgEngine):
    """Buffered staleness-aware async FedAvg over a simulated clock.

    One `run()` drives `rounds` COMMITS (the async analogue of rounds).
    Client results are staleness-discounted at commit time
    (staleness.make_commit_fn); `mix` is the FedAsync server mixing rate
    α (1.0 installs the discounted buffer average directly).

    `concurrency` clients are in flight at once; freed/rejoined clients
    are redispatched in waves (one wave per commit in steady state),
    each wave sampling its ids through the engine's deterministic
    ClientSampler.  The event trace (`self.trace`) records every
    dispatch/arrival/crash/rejoin/commit with virtual timestamps — the
    seeded-determinism contract is that two engines with equal seeds
    produce equal traces."""

    def __init__(self, trainer, data, cfg, *, buffer_k: Optional[int] = None,
                 concurrency: Optional[int] = None,
                 staleness: str = "constant", staleness_a: float = 0.5,
                 staleness_b: float = 4.0, mix: float = 1.0,
                 round_deadline_s: Optional[float] = None,
                 lifecycle_cfg: Optional[LifecycleConfig] = None,
                 async_seed: Optional[int] = None, donate: bool = True,
                 attack: Optional[AttackConfig] = None,
                 defense: Optional[DefenseConfig] = None,
                 shardstore=None,
                 arrivals: Optional[object] = None):
        if staleness not in STALENESS_MODES:
            raise ValueError(f"unknown staleness mode {staleness!r} "
                             f"(choose one of {STALENESS_MODES})")
        # ISSUE 9: the seeded byzantine cohort (attack) and the update
        # admission + bucketed robust commit (defense).  Data-level
        # attacks poison the byzantine clients' shards BEFORE the engine
        # snapshots the data — the attackers then run the honest
        # protocol on hostile data, exactly the reference's backdoor
        # benchmarking shape.
        self.attack = attack
        self.defense = defense
        self._adversary = None
        if attack is not None and attack.mode != "none":
            self._adversary = AdversarySim(attack, cfg.client_num_in_total)
            data = apply_data_attack(data, attack, self._adversary)
        super().__init__(trainer, data, cfg, donate=donate)
        self.buffer_k = (buffer_k if buffer_k is not None
                         else cfg.client_num_per_round)
        if self.buffer_k < 1:
            raise ValueError(f"buffer_k must be >= 1, got {self.buffer_k}")
        self.concurrency = (concurrency if concurrency is not None
                            else max(self.buffer_k,
                                     cfg.client_num_per_round))
        if self.concurrency < self.buffer_k:
            raise ValueError(
                f"concurrency ({self.concurrency}) must be >= buffer_k "
                f"({self.buffer_k}): a full buffer needs that many "
                f"results in flight")
        self.staleness_mode = staleness
        self.staleness_a = staleness_a
        self.staleness_b = staleness_b
        self.mix = float(mix)
        self.round_deadline_s = round_deadline_s
        self.lifecycle_cfg = (lifecycle_cfg if lifecycle_cfg is not None
                              else LifecycleConfig(
                                  seed=async_seed if async_seed is not None
                                  else cfg.seed))
        if async_seed is not None:
            self.lifecycle_cfg = dataclasses.replace(self.lifecycle_cfg,
                                                     seed=async_seed)
        # wave trainer: the SAME one_client body the sync engine vmaps —
        # variables broadcast (in_axes None), one compile per distinct
        # wave width (waves are buffer_k-sized in steady state)
        self._train_wave = jax.jit(jax.vmap(
            self._one_client, in_axes=(None, 0, 0)))
        # ISSUE 10: the sharded client registry replaces the per-client
        # Python containers (free/dead sets, in_flight dict, the
        # staleness/contribution numpy arrays) — O(cohort) touches per
        # wave, O(1) aggregate reads, checkpointable shards.  An
        # optional ShardStore supplies cohorts on demand (no all-client
        # stack), and an arrival process modulates dispatch turnaround
        # with the load curve (scale/arrivals.py).
        self.registry = ClientRegistry(self.sampler.client_num_in_total)
        self._shardstore = shardstore
        if isinstance(arrivals, ArrivalConfig):
            arrivals = make_arrivals(arrivals)
        self._arrivals: Optional[ArrivalProcess] = arrivals
        self._rows_fn = jax.jit(flatten_stacked_rows)
        self._flat_fn = make_flatten_fn()
        self._commit_fn = None        # built per variables template
        self._admission: Optional[UpdateAdmission] = None
        self._p = None
        self.version = 0
        self.commits_deadline = 0
        self.trace: list[tuple] = []
        self.staleness_committed: list[float] = []
        self.occupancy_at_commit: list[int] = []
        self._m_occupancy = obs.gauge("async_buffer_occupancy")
        self._m_staleness = obs.histogram(
            "async_staleness", buckets=obs.metrics.STALENESS_BUCKETS)
        self._m_commits = obs.counter("async_commits_total")
        self._m_updates = obs.counter("async_updates_committed_total")
        self._m_dispatches = obs.counter("async_dispatches_total")

    def _one_client(self, variables, shard, crng):
        global_params = (variables["params"] if self.trainer.prox_mu > 0
                         else None)
        return self.trainer.local_train(variables, shard, crng,
                                        self.cfg.epochs,
                                        global_params=global_params)

    # -- async server state (checkpoint payload) ------------------------------
    def async_state(self) -> dict:
        """Checkpointable async server state: buffer contents + version +
        the sharded client registry (participation/staleness/quarantine
        counters — utils/checkpoint.py extra_state).  The event
        clock/heap is NOT part of it — a resumed run restarts the
        lifecycle clock but keeps every buffered result and staleness
        statistic.  Defended runs additionally carry the bucket
        accumulators (inside the buffer state) and the admission
        pipeline's running reference, so a resumed screen stays armed."""
        self._ensure_buffer()
        out = {
            "buffer": self._buffer.state(),
            "version": np.asarray(self.version, np.int64),
            "registry": self.registry.state(),
        }
        if self._admission is not None:
            out["defense"] = self._admission.state()
        return out

    def load_async_state(self, state: dict) -> None:
        self._ensure_buffer()
        self._buffer.load_state(state["buffer"])
        self.version = int(state["version"])
        if "registry" in state:
            self.registry.load_state(
                jax.tree.map(np.asarray, state["registry"]))
        elif "client_contribs" in state:
            # pre-PR-10 checkpoint: migrate the two flat per-client
            # arrays into registry counters (last_seen is not
            # reconstructible — defaults to -1)
            contribs = np.asarray(state["client_contribs"], np.int64)
            stale = np.asarray(state["client_last_staleness"], np.float32)
            for cid in np.flatnonzero(contribs):
                s, loc = divmod(int(cid), self.registry.shard_size)
                sh = self.registry._alloc(s)
                sh["participation"][loc] = contribs[cid]
                sh["last_staleness"][loc] = stale[cid]
        else:
            raise ValueError(
                "async checkpoint carries neither 'registry' (PR 10) "
                "nor the legacy per-client arrays — not an async "
                "server state")
        if self._admission is not None and "defense" in state:
            self._admission.load_state(state["defense"])

    def _ensure_buffer(self) -> None:
        if getattr(self, "_buffer", None) is None:
            if self.defense is not None:
                # defended path: streaming bucketed buffer — the robust
                # commit needs B accumulators, and the staleness
                # discount moves into the arrival fold (same λ math;
                # the weights ride the fold instead of the drained
                # commit)
                self._buffer = AsyncBuffer(
                    self.buffer_k, self._flat_dim(), streaming=True,
                    staleness_mode=self.staleness_mode,
                    staleness_a=self.staleness_a,
                    staleness_b=self.staleness_b,
                    buckets=self.defense.buckets,
                    bucket_seed=self.defense.seed)
                self._admission = UpdateAdmission(self.defense,
                                                  self._flat_dim())
                self._admission.bind_fold(self.staleness_mode,
                                          self.staleness_a,
                                          self.staleness_b)
            else:
                self._buffer = AsyncBuffer(self.buffer_k, self._flat_dim())

    def _flat_dim(self) -> int:
        if self._p is None:
            self._p = flat_dim(self.init_variables())
        return self._p

    # -- the event-driven loop ------------------------------------------------
    def run(self, variables: Optional[Pytree] = None,
            rounds: Optional[int] = None, logger=None, ckpt=None,
            ckpt_every: int = 0, resume: bool = False) -> Pytree:
        """Drive `rounds` commits of the async federation.  Mirrors the
        base run() contract (eval cadence, metrics_history, logger,
        checkpoint every N commits); `resume` restores variables AND the
        async server state saved by a previous run's checkpoints."""
        cfg = self.cfg
        variables = (variables if variables is not None
                     else self.init_variables())
        self._p = flat_dim(variables)
        self._ensure_buffer()
        total = rounds if rounds is not None else cfg.comm_round
        start_version = 0
        if ckpt is not None and resume and ckpt.latest_round() is not None:
            step, variables, _ss, extra = ckpt.restore(
                variables, (), extra_template=self.async_state())
            self.load_async_state(extra)
            start_version = self.version
            log.info("async resume: version %d, buffer %d/%d", self.version,
                     self._buffer.count, self.buffer_k)
        if self._commit_fn is None:
            if self.defense is not None:
                d = self.defense
                self._commit_fn = make_bucket_commit_fn(
                    variables, combine=d.combine, trim_k=d.trim_k,
                    dp_noise=d.dp_noise, dp_clip=d.dp_clip or 1.0,
                    donate=self.donate)
            else:
                self._commit_fn = make_commit_fn(
                    variables, mode=self.staleness_mode, a=self.staleness_a,
                    b=self.staleness_b, donate=self.donate)
        variables = jax.tree.map(jnp.asarray, variables)
        # the admission screen and the adversary both compare uplinks
        # against the model the clients trained FROM — one flat device
        # row per version, refreshed at every commit
        g_dev = (self._flat_fn(variables)
                 if (self._admission is not None
                     or self._adversary is not None) else None)
        if self._admission is not None:
            self._admission.note_global(self.version, g_dev)
        dp_rng = (jax.random.PRNGKey(cfg.seed + 17)
                  if self.defense is not None and self.defense.dp_noise > 0
                  else None)
        lifecycle = ClientLifecycle(self.lifecycle_cfg,
                                    self.sampler.client_num_in_total)

        rng_base = jax.random.PRNGKey(cfg.seed + 1)
        heap: list[tuple] = []      # (t, kind, seq, payload)
        seq = 0
        now = 0.0
        wave_idx = self.version     # == start_version on resume; also
        #                             covers a manual load_async_state
        # ISSUE 10: client scheduling state lives in the sharded
        # registry — FREE/IN_FLIGHT/CRASHED/DEAD statuses + the
        # dispatched version per client, no per-client Python objects.
        # A (re)started run re-pools everything transient; counters
        # (participation/staleness/quarantine) survive a resume.
        reg = self.registry
        reg.reset_transient()
        last_commit_t = 0.0
        deadline_armed_version = -1
        t_wall0 = time.perf_counter()

        def push(t, kind, payload):
            nonlocal seq
            heapq.heappush(heap, (t, kind, seq, payload))
            seq += 1

        def dispatch_wave():
            """Hand work to (a sampler draw of) free clients at the
            current version: ONE vmapped train program per wave, results
            flattened to buffer rows on device and scheduled as arrival
            events at their lifecycle latencies."""
            nonlocal wave_idx
            slots = self.concurrency - reg.count_in_flight
            if slots <= 0 or reg.count_free == 0:
                return
            draw = self.sampler.sample(wave_idx)
            ids = draw[reg.status_of(draw) == _reg.FREE][:slots]
            if ids.size == 0:   # the draw missed every free client:
                ids = reg.free_ids(slots)     # take the pool directly
            ids = [int(i) for i in ids]
            w_rng, _ = jax.random.split(
                jax.random.fold_in(rng_base, wave_idx))
            crngs = jax.random.split(w_rng, len(ids))
            store = (self._shardstore if self._shardstore is not None
                     else self.data)
            cohort, _ = store.cohort(np.asarray(ids, np.int64))
            with obs.span("async.wave", wave=wave_idx, clients=len(ids),
                          version=self.version):
                stacked, _losses, ns = self._train_wave(
                    variables, cohort, crngs)
                rows = np.asarray(self._rows_fn(stacked))
                ns = np.asarray(ns)
            g_np = (np.asarray(g_dev) if self._adversary is not None
                    and self._adversary.attacks_model() else None)
            self._m_dispatches.inc(len(ids))
            for lane, cid in enumerate(ids):
                if lifecycle.draw_crash(cid):
                    self.trace.append(("crash", round(now, 9), cid,
                                       self.version))
                    obs.counter("async_dropouts_total").inc()
                    delay = lifecycle.draw_rejoin_delay(cid)
                    reg.note_crash(cid, rejoins=delay is not None)
                    if delay is not None:
                        push(now + delay, _REJOIN, cid)
                    continue
                row = rows[lane]
                if g_np is not None and self._adversary.is_byzantine(cid):
                    # byzantine lanes swap their honest result for the
                    # crafted row — AFTER the crash draw, so a crashed
                    # byzantine dispatch (its uplink never arrives)
                    # neither pays the corruption nor counts as an
                    # injected attack in the trace/counters
                    row = self._adversary.corrupt_row(
                        cid, row, g_np, self.version)
                    self.trace.append(("attack", round(now, 9), cid,
                                       self.version))
                reg.note_dispatch_one(cid, self.version)
                lat = lifecycle.draw_latency(cid)
                if self._arrivals is not None:
                    # ISSUE 10: the arrival process shapes turnaround —
                    # at the trough of the load curve the fleet answers
                    # slower (pure function of virtual time, so seeded
                    # determinism survives)
                    lat *= self._arrivals.slowdown(now)
                if self._adversary is not None:
                    # stale-attack: byzantine uplinks deliberately land
                    # several commits late, where the staleness
                    # discount was supposed to defang them
                    lat += self._adversary.stale_extra_latency(cid)
                self.trace.append(("dispatch", round(now, 9), cid,
                                   self.version))
                push(now + lat, _ARRIVE, (cid, row, float(ns[lane])))
            wave_idx += 1

        def commit(deadline_fired: bool):
            nonlocal variables, last_commit_t, deadline_armed_version, \
                g_dev, dp_rng
            if self.defense is not None:
                accs, wsums, _w, _s, n_real, _raw = \
                    self._buffer.take_stream_buckets()
                self.occupancy_at_commit.append(n_real)
                self._m_occupancy.set(0)
                with obs.span("async.commit", version=self.version,
                              n_results=n_real, deadline=deadline_fired,
                              defended=True):
                    if dp_rng is not None:
                        dp_rng, k = jax.random.split(dp_rng)
                        variables, _stats = self._commit_fn(
                            variables, accs, wsums, jnp.float32(self.mix),
                            jnp.float32(n_real), k)
                    else:
                        variables, _stats = self._commit_fn(
                            variables, accs, wsums, jnp.float32(self.mix))
            else:
                rows, w, s, n_real = self._buffer.drain()
                self.occupancy_at_commit.append(n_real)
                self._m_occupancy.set(0)
                with obs.span("async.commit", version=self.version,
                              n_results=n_real, deadline=deadline_fired):
                    variables, _stats = self._commit_fn(
                        variables, jnp.asarray(rows), jnp.asarray(w),
                        jnp.asarray(s), jnp.float32(self.mix))
            if g_dev is not None:
                g_dev = self._flat_fn(variables)
            self.version += 1
            if self._admission is not None:
                self._admission.note_global(self.version, g_dev)
            last_commit_t = now
            deadline_armed_version = -1
            self._m_commits.inc()
            # ISSUE 12: the SLO pack's committed-updates floor
            self._m_updates.inc(n_real)
            if deadline_fired:
                self.commits_deadline += 1
                obs.counter("async_deadline_commits_total").inc()
            self.trace.append(("commit", round(now, 9), n_real,
                               self.version))
            c = self.version - 1
            if (c % cfg.frequency_of_the_test == 0 or
                    self.version >= total):
                with obs.span("async.eval", version=self.version):
                    stats = self.evaluate(variables)
                stats.update(round=c, commit=c,
                             staleness_mean=float(np.mean(
                                 self.staleness_committed[-n_real:]
                                 or [0.0])),
                             buffer_fill=n_real / self.buffer_k,
                             wall_time=time.perf_counter() - t_wall0)
                self.metrics_history.append(stats)
                if logger is not None:
                    logger.log(stats, step=c)
                log.info("commit %d: %s", c, stats)
            if ckpt is not None and ckpt_every and \
                    self.version % ckpt_every == 0:
                ckpt.save(c, jax.tree.map(np.asarray, variables), (),
                          extra_state=self.async_state())
            if self.version < total:     # no wave past the final commit
                dispatch_wave()

        try:
            with obs.span("async.run", commits=total):
                if self.version < total:   # a resume at/past the budget
                    dispatch_wave()        # must not train a dead wave
                while self.version < total:
                    if not heap:
                        if reg.count_free > 0 and reg.count_in_flight == 0:
                            # crash-starved: every in-flight dispatch
                            # died, but clients rejoined — start a wave
                            dispatch_wave()
                            if heap:
                                continue
                        # nothing can ever arrive: scheduler deadlock
                        obs.dump_flight("async_scheduler_deadlock")
                        raise AsyncSchedulerDeadlock(
                            f"async scheduler deadlock at version "
                            f"{self.version}/{total}: buffer "
                            f"{self._buffer.count}/{self.buffer_k}, "
                            f"{reg.count_dead} clients dead with no "
                            f"rejoin, {reg.count_free} free but "
                            f"undispatchable")
                    t, kind, _s, payload = heapq.heappop(heap)
                    now = max(now, t)
                    if kind == _REJOIN:
                        cid = payload
                        reg.note_rejoin(cid)
                        self.trace.append(("rejoin", round(now, 9), cid,
                                           self.version))
                        obs.counter("async_rejoins_total").inc()
                        if reg.count_in_flight == 0:
                            dispatch_wave()
                        continue
                    if kind == _DEADLINE:
                        armed_version = payload
                        if (self.version == armed_version
                                and self._buffer.count > 0):
                            commit(deadline_fired=True)
                        continue
                    cid, row, n = payload
                    dispatched_v = reg.note_return(cid)
                    staleness = float(self.version - dispatched_v)
                    self.trace.append(("arrive", round(now, 9), cid,
                                       self.version, staleness))
                    if self._admission is not None:
                        # the ISSUE-9 admission gate, fused with the
                        # streaming fold (one jitted dispatch); a
                        # quarantined row never reaches the accumulator
                        # (the client is free again and redispatches
                        # with the next wave)
                        full = False
                        ok, why, full = self._buffer.add_screened(
                            row, n, staleness, self._admission,
                            sender=cid, version=int(dispatched_v))
                        if not ok:
                            reg.note_quarantine(cid)
                            self.trace.append(
                                ("quarantine", round(now, 9), cid, why))
                            continue
                    else:
                        full = self._buffer.add(row, n, staleness)
                    self.staleness_committed.append(staleness)
                    reg.note_contribution(cid, staleness, self.version)
                    self._m_staleness.observe(staleness)
                    self._m_occupancy.set(self._buffer.count)
                    if full:
                        commit(deadline_fired=False)
                    elif (self.round_deadline_s is not None
                          and deadline_armed_version != self.version):
                        deadline_armed_version = self.version
                        push(last_commit_t + self.round_deadline_s,
                             _DEADLINE, self.version)
        except AsyncSchedulerDeadlock:
            raise               # already dumped, with the sharper reason
        except Exception as e:
            obs.dump_flight(f"engine_error:AsyncFedAvgEngine: {e!r}")
            raise
        return variables

    # -- observability rollup -------------------------------------------------
    def timeline_report(self) -> Optional[dict]:
        """Round critical-path attribution over the live tracer's spans
        (fedml_tpu/obs/timeline.py): commit-to-commit windows, per-stage
        seconds (train/commit/eval + wait), p95 straggler attribution.
        None when tracing is disabled (no --obs_dir) — metrics alone
        cannot place spans on a timeline."""
        t = obs.tracer()
        if t is None:
            return None
        from fedml_tpu.obs import timeline
        return timeline.critical_path(t.events())

    def staleness_percentiles(self, qs=(50, 95)) -> dict:
        s = np.asarray(self.staleness_committed or [0.0])
        return {f"p{q}": float(np.percentile(s, q)) for q in qs}

    def async_report(self) -> dict:
        """Headline async numbers of a run (tests/test_async.py)."""
        occ = np.asarray(self.occupancy_at_commit or [0])
        out = {
            "committed_updates": int(self.version),
            "deadline_commits": int(self.commits_deadline),
            "staleness_p50": self.staleness_percentiles()["p50"],
            "staleness_p95": self.staleness_percentiles()["p95"],
            "staleness_mean": float(np.mean(
                self.staleness_committed or [0.0])),
            "buffer_occupancy_mean": float(occ.mean()),
        }
        if self._admission is not None:
            out.update(self._admission.report())
        if self._adversary is not None:
            out["byzantine_clients"] = len(self._adversary.byzantine)
            # the unbounded counter, not len(events) — the trace list
            # caps at 50k while long runs keep injecting
            out["attacks_injected"] = self._adversary.injected
        return out

    def quarantine_attribution(self) -> dict:
        """{"byzantine": n, "honest": n} quarantine split — the
        false-positive gate's raw numbers (honest must be 0 in the
        clean arm).  Needs both an adversary (who is byzantine) and an
        admission pipeline (who was quarantined)."""
        byz = self._adversary.byzantine if self._adversary else frozenset()
        out = {"byzantine": 0, "honest": 0}
        if self._admission is not None:
            for cid, _why in self._admission.quarantine_log:
                out["byzantine" if cid in byz else "honest"] += 1
        return out
