"""Fused serving cluster (ISSUE 18) — live sockets feeding
registry-sharded lanes across the elastic multi-host tier.

Every scale axis existed separately before this module: the reactor
(PR 11) sustains 10k live connections but single-process, the
`_ServeLane` loop (PRs 13–14) runs cluster-wide but in virtual time
over synthetic arrivals, and the 1M-client registry (PR 10) had never
been fed by a socket.  Here they fuse:

    reactor      one ReactorGroup per host fronts that host's
                 registry-shard range — the uplink path rides the
                 EXISTING `_deliver_frame` chokepoint (chaos filter,
                 FMLR reliability envelope, decode pool), not a fork
    lanes        decoded rows land in per-range ClusterLanes: the
                 streaming AsyncBuffer fold per lane, per-lane FIFO
                 backlog for rows arriving past a full window (socket
                 arrival ORDER never crosses a window boundary)
    fold         at each commit barrier the host takes every hosted
                 lane's partial IN ITEM ORDER and folds cross-host
                 through ElasticChannel exactly as run_serve_sim does —
                 pack_partial/fold_partials are THE shared functions,
                 so the commit-barrier fold order stays a pure function
                 of the block/lane partition
    shed gate    registry/lane pressure feeds the reactor's
                 set_overload_gate: a host whose lanes are saturated
                 (window full AND backlog at cap) rejects new
                 connections at the door instead of accepting uplinks
                 it would drop

Two invariants, both pinned by tests/test_cluster_serve.py:

  * world==1 with the synthetic-arrival serve sim and a reactor-fed
    lane given the SAME row sequence commit byte-identical digests —
    the fusion adds transport, not math;
  * cross-rank digest equality holds with live ingest, because every
    rank folds the identical exchanged payload bytes in item order.

`python -m fedml_tpu.cli --cluster_serve` is the entry point; a
multi-target connswarm fleet (comm/connswarm.py) striped across the
host endpoints is the load.
"""
from __future__ import annotations

import logging
import socket
import struct
import threading
import time
from collections import deque
from typing import Optional

import numpy as np

from fedml_tpu import obs
from fedml_tpu.obs import propagate
from fedml_tpu.obs import slo as obs_slo
from fedml_tpu.obs.metrics import quantile_from_cumulative
from fedml_tpu.async_.lifecycle import AsyncMessage, AsyncServerManager
from fedml_tpu.comm.message import Message, MessageCodec
from fedml_tpu.scale.registry import ClientRegistry
from fedml_tpu.scale.serve import (fold_partials, pack_partial, rss_bytes,
                                   zero_partial)

log = logging.getLogger(__name__)

_LEN = struct.Struct("<Q")


class ClusterLane:
    """One registry-shard range's live-socket serving state: sharded
    registry over [lo, hi), a streaming AsyncBuffer sized to the
    commit window, and a bounded FIFO backlog for uplinks that arrive
    while the current window is already full.  `item` is the range's
    index in the ORIGINAL world-sized partition — the cross-host fold
    is always in item order, so the global mix is independent of which
    host (or socket) delivered which row when."""

    def __init__(self, item: int, lo: int, hi: int, *, buffer_k: int,
                 row_dim: int, backlog_cap: int,
                 start_version: int = 0):
        from fedml_tpu.async_.staleness import AsyncBuffer
        self.item = int(item)
        self.lo, self.hi = int(lo), int(hi)
        self.local_population = max(1, self.hi - self.lo)
        self.buffer_k = int(buffer_k)
        self.registry = ClientRegistry(self.local_population)
        self.buffer = AsyncBuffer(buffer_k, row_dim, streaming=True)
        self.backlog: deque = deque()
        self.backlog_cap = int(backlog_cap)
        self.version = int(start_version)
        self.admitted = 0
        self.overflow_dropped = 0
        # set the first time ANY uplink routes here (admitted, parked,
        # or dropped): an untouched lane — typically a re-adopted dead
        # host's range with no sockets pointed at it — must not gate
        # the window barrier at full deadline every commit
        self.touched = False

    def full(self) -> bool:
        return self.buffer.count >= self.buffer_k

    def saturated(self) -> bool:
        """Window full AND backlog at cap: this lane cannot absorb
        another uplink without dropping — the shed-gate signal."""
        return (self.buffer.count >= self.buffer_k
                and len(self.backlog) >= self.backlog_cap)


class ClusterServeManager(AsyncServerManager):
    """One host of the fused serving cluster: the PR-11 reactor
    transport + PR-6 decode pool of AsyncServerManager, with the ONE
    insert path (`_ingest_row`) rerouted into per-range ClusterLanes
    instead of the single async buffer.  Commits are NOT triggered
    here — the cross-host driver (run_cluster_serve) closes windows at
    the commit barrier, so a socket burst can never race a partial
    into the wrong window: rows past a full window park in the lane's
    FIFO backlog and drain, in arrival order, into the NEXT window."""

    def __init__(self, row_dim: int, *, population: int,
                 cluster_rank: int = 0, world: int = 1,
                 buffer_k: int = 16, port: int = 54300,
                 n_connections: int = 256, ingest_pool: int = 2,
                 backlog_cap: Optional[int] = None,
                 sparse_uplink: bool = False,
                 reactor_config=None):
        import os as _os
        from fedml_tpu.comm.reactor import ReactorConfig
        if reactor_config is None:
            reactor_config = ReactorConfig(
                reactors=max(2, (_os.cpu_count() or 2)),
                max_connections=max(n_connections + 64, 256),
                stall_timeout_s=30.0,
                shed_on_pressure=True, shed_after_s=2.0)
        self.row_dim = int(row_dim)
        self.population = int(population)
        self.cluster_rank = int(cluster_rank)
        self.world = int(world)
        self._backlog_cap = (int(backlog_cap) if backlog_cap is not None
                             else 4 * int(buffer_k))
        self._lanes: dict[int, ClusterLane] = {}
        self._retired_lanes: list[ClusterLane] = []
        self._hosted: tuple = ()
        self._rr = 0
        self.misrouted = 0
        # this host's own lane exists BEFORE the transport opens: the
        # base constructor listens, and installs the frame sink, long
        # before it returns, and a peer that dials the moment the port
        # answers has its first uplinks decoded while this constructor
        # is still running — with no hosted lane they were counted
        # `misrouted` and dropped (seen under load: a loaded host lost
        # the first 1-16 rows of a run, tests/test_cluster_serve.py)
        self.buffer_k = int(buffer_k)
        self._window_cv: Optional[threading.Condition] = None
        self._adopt_locked(self.cluster_rank, 0)
        template = {"w": np.zeros((row_dim,), np.float32)}
        super().__init__(
            template, 1 << 62, buffer_k, 0, n_connections + 1, "TCP",
            staleness_mode="constant", mix=1.0, streaming=True,
            ingest_pool=ingest_pool, decode_into=True,
            sparse_uplink=sparse_uplink, redispatch=False,
            ip_config={0: "127.0.0.1"}, base_port=port,
            force_python_tcp=True, reactor=True,
            reactor_config=reactor_config)
        # window barrier: _ingest_row notifies when a lane fills; the
        # driver waits on it holding the SAME manager lock the insert
        # path times into async_lock_wait_seconds
        self._window_cv = threading.Condition(self._lock)
        # satellite (ISSUE 18): registry/lane pressure reaches the
        # reactor's door — before this only decode-pool depth and RSS
        # fed the gate, so a lane-bound host kept accepting uplinks it
        # would drop at the backlog cap
        rg = getattr(self.com_manager, "_rg", None)
        if rg is not None:
            rg.set_overload_gate(self.lane_pressure)

    # -- lane partition ------------------------------------------------------
    def _range_of(self, item: int) -> tuple:
        return (item * self.population // self.world,
                (item + 1) * self.population // self.world)

    def _adopt_locked(self, item: int, start_version: int) -> ClusterLane:
        lo, hi = self._range_of(item)
        lane = ClusterLane(item, lo, hi, buffer_k=self.buffer_k,
                           row_dim=self.row_dim,
                           backlog_cap=self._backlog_cap,
                           start_version=start_version)
        self._lanes[item] = lane
        self._hosted = tuple(sorted(self._lanes))
        return lane

    def adopt(self, item: int, start_version: int) -> None:
        with self._lock:
            if item not in self._lanes:
                self._adopt_locked(item, start_version)
                obs.instant("cluster.readopt", item=item,
                            rank=self.cluster_rank,
                            version=start_version)

    def retire(self, item: int) -> None:
        with self._lock:
            lane = self._lanes.pop(item, None)
            if lane is not None:
                self._retired_lanes.append(lane)
                self._hosted = tuple(sorted(self._lanes))

    def hosted_items(self) -> tuple:
        return self._hosted

    def all_lanes(self) -> list:
        return list(self._lanes.values()) + self._retired_lanes

    # -- shed gate -----------------------------------------------------------
    def lane_pressure(self) -> bool:
        """True while ANY hosted lane is saturated (window full +
        backlog at cap) — installed as the reactor's overload gate, so
        the door sheds instead of the backlog dropping.  Runs on the
        reactor loop thread: reads the hosted snapshot tuple, never
        iterates the mutable dict."""
        lanes = self._lanes
        for item in self._hosted:
            lane = lanes.get(item)
            if lane is not None and lane.saturated():
                return True
        return False

    # -- THE insert path (decode pool + FSM route both land here) ------------
    def _ingest_row(self, sender: int, row: np.ndarray, weight: float,
                    dispatched: int, *, sparse=None) -> None:
        t0 = time.perf_counter()
        self._lock.acquire()
        self._m_lock_wait.inc(time.perf_counter() - t0)
        try:
            if self.done.is_set():
                return                  # late straggler after shutdown
            hosted = self._hosted
            if not hosted:
                self.misrouted += 1
                return                  # view moved every range away
            # a sender inside a hosted range lands in ITS range's lane
            # (registry attribution); anything else — a test fleet's
            # baked sender id, a client whose range another host owns —
            # round-robins across the hosted lanes
            item = (sender % self.population) * self.world \
                // self.population
            lane = self._lanes.get(item)
            if lane is None:
                lane = self._lanes[hosted[self._rr % len(hosted)]]
                self._rr += 1
            lane.touched = True
            staleness = float(lane.version - dispatched)
            if lane.full() or lane.backlog:
                # window closed (or rows already queued behind it):
                # park IN ARRIVAL ORDER for the next window — socket
                # timing must not decide which window a row folds into
                # beyond this FIFO
                if len(lane.backlog) >= lane.backlog_cap:
                    lane.overflow_dropped += 1
                    return
                # row is a borrowed scratch buffer (recycled by the
                # decode pool once we return) — parking needs a copy;
                # the direct fold below does not, AsyncBuffer.add
                # blocks until the fold consumed it.  Sparse pairs are
                # fresh arrays (decode_sparse concatenates), so they
                # park as-is under the same 4-tuple shape.
                lane.backlog.append((sparse if sparse is not None
                                     else row.copy(), float(weight),
                                     staleness, int(sender)))
            else:
                self._admit_locked(lane, sparse if sparse is not None
                                   else row, weight, staleness, sender)
            if lane.full() and self._window_cv is not None:
                # (None only while the constructor runs: no driver can
                # be waiting on the barrier yet)
                self._window_cv.notify_all()
        finally:
            self._lock.release()

    def _admit_locked(self, lane: ClusterLane, row, weight: float,
                      staleness: float, sender: int) -> None:
        with obs.span("ingest.fold", sender=sender):
            if isinstance(row, tuple):
                # (idx, vals) pairs from a sparse_topk frame (ISSUE
                # 19): the jitted scatter fold, never a dense row
                lane.buffer.add_sparse(row[0], row[1], weight, staleness)
            else:
                lane.buffer.add(row, weight, staleness)
        lane.admitted += 1
        self.staleness_seen.append(staleness)
        self._m_staleness.observe(staleness)
        self._m_occupancy.set(lane.buffer.count)
        lane.registry.note_push(sender % lane.local_population,
                                staleness, lane.version)

    # -- window barrier ------------------------------------------------------
    def wait_window(self, deadline_s: float) -> bool:
        """Block until EVERY hosted lane's window is full, or the
        deadline passes (an adopted lane with no socket traffic must
        not wedge the cluster barrier — it contributes whatever it
        has, possibly zero, which is deterministic on every rank).
        Returns False on a deadline close."""
        deadline = time.perf_counter() + float(deadline_s)
        with self._window_cv:
            while True:
                # only lanes that have EVER seen traffic gate the
                # barrier: a freshly adopted dead-host range with no
                # sockets pointed at it folds zero without pacing
                # every cluster commit at the full deadline
                active = [self._lanes[i] for i in self._hosted
                          if self._lanes[i].touched]
                if active and all(ln.full() for ln in active):
                    return True
                left = deadline - time.perf_counter()
                if left <= 0.0:
                    return False
                self._window_cv.wait(min(left, 0.05))

    def take_partials(self) -> dict:
        """Close the window: per hosted lane IN ITEM ORDER, take the
        streaming partial and drain the backlog into the fresh window
        (FIFO — the order the sockets delivered).  Returns
        {item: (acc, wsum, n)} for the driver's cross-host fold."""
        out = {}
        with self._lock:
            for item in self._hosted:
                lane = self._lanes[item]
                acc, wsum, _w, _s, n, _raw = lane.buffer.take_stream()
                out[item] = (acc, wsum, int(n))
                lane.version += 1
                while lane.backlog and not lane.full():
                    row, w, s, sender = lane.backlog.popleft()
                    self._admit_locked(lane, row, w, s, sender)
                if lane.full():
                    self._window_cv.notify_all()
        return out


# ---------------------------------------------------------------------------
# uplink frame helpers — the swarm's payload and the tests' senders
# ---------------------------------------------------------------------------

def make_uplink_frame(row: np.ndarray, *, sender: int = 1,
                      weight: float = 1.0, version: int = 0,
                      transport: Optional[str] = None) -> bytes:
    """One pre-encoded C2S result frame carrying a flat f32 row under
    the cluster template {"w": row}.  weight rides NUM_SAMPLES; the
    cluster runs constant staleness weights, so the version echo is
    weight-neutral.  `transport` opts the row into a lossy v2 wire
    dtype ("bf16" | "int8" | "sparse_topk" — ISSUE 19); None keeps the
    exact v1 frame."""
    msg = Message(AsyncMessage.MSG_TYPE_C2S_ASYNC_RESULT, sender, 0)
    msg.add_params(AsyncMessage.MSG_ARG_KEY_MODEL_PARAMS,
                   {"w": np.asarray(row, np.float32)})
    msg.add_params(AsyncMessage.MSG_ARG_KEY_NUM_SAMPLES, float(weight))
    msg.add_params(AsyncMessage.MSG_ARG_KEY_VERSION, int(version))
    if transport is not None:
        msg.set_wire_transport(AsyncMessage.MSG_ARG_KEY_MODEL_PARAMS,
                               transport)
    propagate.stamp(msg, sender)
    return MessageCodec.encode(msg)


def send_uplinks(host: str, port: int, frames, *,
                 hold_open: Optional[threading.Event] = None,
                 timeout_s: float = 30.0) -> None:
    """Test helper: one blocking socket, frames length-prefixed in
    order (the transport preserves it; with ingest_pool=1 the decode
    pool does too — the world==1 byte-identity pin's premise).  Keeps
    the connection open until `hold_open` is set so the server never
    sees a mid-run hangup."""
    s = socket.create_connection((host, port), timeout=timeout_s)
    s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    try:
        for f in frames:
            s.sendall(_LEN.pack(len(f)) + f)
        if hold_open is not None:
            hold_open.wait(timeout=timeout_s)
    finally:
        try:
            s.close()
        except OSError:
            pass


# ---------------------------------------------------------------------------
# the per-host driver — commit barrier + cross-host fold
# ---------------------------------------------------------------------------

def run_cluster_serve(population: int, *, commits: int,
                      warmup_commits: int = 2, buffer_k: int = 16,
                      row_dim: int = 256, port: int = 54300,
                      partition: tuple = (0, 1), channel=None,
                      elastic: bool = False, n_connections: int = 64,
                      ingest_pool: int = 2,
                      window_deadline_s: float = 20.0,
                      timeout_s: float = 600.0,
                      backlog_cap: Optional[int] = None,
                      sparse_uplink: bool = False,
                      reactor_config=None, chaos: Optional[dict] = None,
                      chaos_seed: int = 0,
                      crash_at_commit: Optional[int] = None,
                      slo_window: bool = False) -> dict:
    """Serve `commits` commit windows of live-socket uplinks on this
    host's registry-shard range, folding lane partials cross-host at
    each commit barrier exactly as run_serve_sim does (same
    pack/fold/zero functions, same ElasticChannel contract, same
    re-adoption semantics).  Returns the host report — committed
    digest, local + cluster-wide committed-updates/sec, admission
    percentiles, and every shed/eviction/drop counter.

    `crash_at_commit` is the chaos arm's fault hook: this host
    abruptly closes its channel after that many commits and returns a
    partial report (the worker process then exits nonzero, and the
    survivors' next exchange evicts it — re-adoption exactly as in the
    virtual-time serve path, except an adopted lane here has no
    sockets pointed at it, so its windows close at the deadline with
    whatever arrived: deterministic zeros on every survivor)."""
    import jax.numpy as jnp
    from fedml_tpu.async_.staleness import make_stream_commit_fn
    from fedml_tpu.comm.chaos import ChaosConfig, ChaosPolicy
    from fedml_tpu.parallel.multihost import variables_digest

    if commits <= warmup_commits:
        raise ValueError(f"commits ({commits}) must exceed "
                         f"warmup_commits ({warmup_commits})")
    rank, world = int(partition[0]), int(partition[1])
    if not 0 <= rank < world:
        raise ValueError(f"partition rank {rank} outside world {world}")
    if world > 1 and channel is None:
        raise ValueError("world > 1 needs a channel to fold the "
                         "partial aggregates upward")
    if elastic and world > 1 and not hasattr(channel, "exchange"):
        raise ValueError("elastic=True needs an ElasticChannel "
                         "(n_items=world)")

    mgr = ClusterServeManager(
        row_dim, population=population, cluster_rank=rank, world=world,
        buffer_k=buffer_k, port=port, n_connections=n_connections,
        ingest_pool=ingest_pool, backlog_cap=backlog_cap,
        sparse_uplink=sparse_uplink, reactor_config=reactor_config)
    if chaos:
        mgr.com_manager.install_chaos(
            ChaosPolicy(ChaosConfig(seed=chaos_seed, **chaos)))
    mgr.run_async()

    slo_eng = None
    if slo_window:
        slo_eng = obs_slo.SloEngine(obs_slo.default_slo_pack(),
                                    dump_min_interval_s=30.0)
        slo_eng.prime()
    hist_adm = obs.histogram("comm_admission_seconds")
    evict = {r: obs.counter("comm_connections_evicted_total",
                            backend="tcp", reason=r)
             for r in ("stall", "rate", "shed", "idle", "protocol",
                       "error")}
    shed = obs.counter("comm_uplinks_shed_total", backend="tcp")
    drained = obs.counter("comm_connections_drained_total", backend="tcp")
    deaths = obs.counter("comm_recv_thread_deaths_total")
    dups = obs.counter("comm_reliable_dups_suppressed_total")
    quar = obs.counter("comm_frames_quarantined_total")
    base = {"evict": {r: c.value for r, c in evict.items()},
            "shed": shed.value, "drained": drained.value,
            "deaths": deaths.value, "dups": dups.value,
            "quar": quar.value, "adm": hist_adm.cumulative()}

    zero_payload = zero_partial(row_dim)
    template = {"w": jnp.zeros((row_dim,), jnp.float32)}
    commit_fn = make_stream_commit_fn(template, donate=False)
    variables = template
    version = 0
    deadline_windows = 0
    empty_commits = 0
    global_wsum = 0.0
    commit_walls: list = []     # per-commit wall time (barrier to barrier)
    commit_wsums: list = []     # per-commit folded GLOBAL weight
    adopted_items: list[int] = []
    crashed_out = False
    t_wall0 = time.perf_counter()
    t_commit_prev = t_wall0
    hard_deadline = t_wall0 + float(timeout_s)
    t_timed = None
    admitted_at_warmup = 0
    global_at_warmup = 0.0
    adm0 = base["adm"]

    def lanes_admitted() -> int:
        return sum(ln.admitted for ln in mgr.all_lanes())

    try:
        with obs.span("cluster.run", population=population,
                      commits=commits, rank=rank, world=world,
                      elastic=elastic):
            while version < commits:
                if time.perf_counter() > hard_deadline:
                    obs.dump_flight("cluster_serve_stall")
                    raise TimeoutError(
                        f"cluster serve stalled: {version}/{commits} "
                        f"commits in {timeout_s}s (rank {rank}/"
                        f"{world}, {lanes_admitted()} admitted)")
                if (crash_at_commit is not None
                        and version == crash_at_commit):
                    # fault injection: this host vanishes mid-run — the
                    # survivors' next exchange evicts it and re-adopts
                    # its range at their next commit barrier
                    if channel is not None:
                        channel.close()
                    crashed_out = True
                    break
                if not mgr.wait_window(window_deadline_s):
                    deadline_windows += 1
                partials = mgr.take_partials()
                with obs.span("cluster.commit", version=version,
                              rank=rank):
                    n_committed = sum(p[2] for p in partials.values())
                    if world > 1 and elastic:
                        payloads = {item: pack_partial(acc, wsum)
                                    for item, (acc, wsum, _n)
                                    in partials.items()}
                        allp, view = channel.exchange(
                            version, payloads,
                            lambda items: {i: zero_payload
                                           for i in items})
                        acc, wsum = fold_partials(
                            (allp[item] for item in range(world)),
                            row_dim)
                    elif world > 1:
                        acc, wsum, _n = partials[rank]
                        docs = channel.allgather(pack_partial(acc, wsum))
                        acc, wsum = fold_partials(docs, row_dim)
                    else:
                        # world==1 folds its single partial DIRECTLY —
                        # no pack/unpack round trip, byte-identical to
                        # the pre-fusion serve path
                        acc, wsum, _n = partials[rank]
                    # an all-empty window (every lane deadline-closed
                    # with zero arrivals, cluster-wide) must not fold
                    # acc/0 NaNs into the model — the folded wsum is
                    # identical on every rank, so the skip is too
                    if float(wsum) > 0.0:
                        variables, _stats = commit_fn(
                            variables, acc, wsum, jnp.float32(1.0))
                    else:
                        empty_commits += 1
                global_wsum += float(wsum)
                t_now = time.perf_counter()
                commit_walls.append(t_now - t_commit_prev)
                t_commit_prev = t_now
                commit_wsums.append(float(wsum))
                obs.counter("async_updates_committed_total").inc(
                    n_committed)
                version += 1
                if world > 1 and elastic:
                    # the commit barrier re-partitions lanes onto the
                    # view — exactly ONE host per range, as in
                    # run_serve_sim
                    for item in list(mgr.hosted_items()):
                        if view.owner_of(item) != rank:
                            mgr.retire(item)
                    for item in view.assigned(rank):
                        if item not in mgr.hosted_items():
                            mgr.adopt(item, version)
                            adopted_items.append(item)
                if version == warmup_commits:
                    t_timed = time.perf_counter()
                    admitted_at_warmup = lanes_admitted()
                    global_at_warmup = global_wsum
                    adm0 = hist_adm.cumulative()
    finally:
        mgr.finish()

    wall = time.perf_counter() - (t_timed if t_timed is not None
                                  else t_wall0)
    timed_updates = lanes_admitted() - (admitted_at_warmup
                                        if t_timed is not None else 0)
    timed_global = global_wsum - (global_at_warmup
                                  if t_timed is not None else 0.0)
    adm1 = hist_adm.cumulative()
    if adm1[-1][1] - adm0[-1][1] <= 0:
        adm0 = base["adm"]          # run outpaced the warmup snapshot
    rg = getattr(mgr.com_manager, "_rg", None)
    report = {
        "population": int(population),
        "partition": [rank, world],
        "port": int(port),
        "committed_digest": variables_digest(variables),
        "commits": int(version),
        "committed_updates": int(lanes_admitted()),
        "committed_updates_per_sec": (timed_updates / wall
                                      if wall > 0 else 0.0),
        "cluster_updates_per_sec": (timed_global / wall
                                    if wall > 0 else 0.0),
        "commit_walls_s": [round(w, 6) for w in commit_walls],
        "commit_wsums": [round(w, 2) for w in commit_wsums],
        "admission_p50_s": quantile_from_cumulative(adm0, adm1, 0.50),
        "admission_p95_s": quantile_from_cumulative(adm0, adm1, 0.95),
        "buffer_k": int(buffer_k),
        "row_dim": int(row_dim),
        "ingest_pool": int(ingest_pool),
        "n_connections": int(n_connections),
        "window_deadline_s": float(window_deadline_s),
        "deadline_windows": int(deadline_windows),
        "empty_commits": int(empty_commits),
        "lane_overflow_dropped": int(sum(ln.overflow_dropped
                                         for ln in mgr.all_lanes())),
        "misrouted": int(mgr.misrouted),
        "open_connections_peak": (int(rg.peak_connections)
                                  if rg is not None else 0),
        "shed_reasons": (dict(rg.shed_reasons) if rg is not None
                         else {}),
        "evicted": {r: c.value - base["evict"][r]
                    for r, c in evict.items()},
        "uplinks_shed": shed.value - base["shed"],
        "connections_drained": drained.value - base["drained"],
        "recv_thread_deaths": deaths.value - base["deaths"],
        "dups_suppressed": dups.value - base["dups"],
        "quarantined": quar.value - base["quar"],
        "registry_bytes": int(sum(ln.registry.nbytes
                                  for ln in mgr.all_lanes())),
        "rss_bytes": rss_bytes(),
        "wall_s": float(wall),
        "chaos_injected": bool(chaos),
        "sparse_uplink": bool(sparse_uplink),
    }
    if elastic:
        report["elastic"] = {
            "lanes": sorted(mgr.hosted_items()),
            "adopted_items": adopted_items,
            "retired_items": [ln.item for ln in mgr._retired_lanes],
            "crashed_at_commit": (crash_at_commit if crashed_out
                                  else None),
            "epoch": (channel.view.epoch
                      if channel is not None
                      and hasattr(channel, "view") else 0),
            "view_changes": (len(channel.view_events)
                             if channel is not None
                             and hasattr(channel, "view_events")
                             else 0),
        }
    if slo_eng is not None:
        slo_eng.evaluate()
        report["slo_arm"] = slo_eng.arm_summary()
    return report
