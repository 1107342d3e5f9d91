"""Virtual-time serve simulation — the million-client heavy-traffic
harness (`run_serve_sim`; tests/test_scale.py).

What it measures: the SERVER's cross-device round hot path at
production populations — cohort sampling over the sharded registry,
per-uplink registry bookkeeping, the streaming fold, and the O(P)
commit — under a trace-driven arrival process in virtual time.  Client
compute is out of scope by design (updates are a rotating pool of
pre-generated rows): the north-star question here is whether the
serving spine sustains committed-updates/sec while server memory stays
sub-linear in population (ISSUE 10 acceptance: registry <= ~100
bytes/client at 1M, no per-client Python objects on the hot path).

The loop (one process, no threads — the virtual clock comes from the
arrival process):

    arrivals  λ(t) from scale/arrivals.py yields uplink landing times
    dispatch  when in-flight drops below `concurrency`, the streaming
              cohort sampler draws a batch over the registry's
              eligibility mask and `note_dispatch` marks it (vectorized)
    ingest    each arrival pops the oldest in-flight client (a numpy
              ring, no deque of Python tuples), `note_return` yields its
              dispatched version -> staleness, the row folds into the
              streaming AsyncBuffer (the PR-6 jitted fold), and
              `note_contribution` updates the client's counters
    commit    buffer full -> the O(P) stream commit, version += 1
    faults    a seeded dropout stream crashes dispatches (no fold);
              crashed clients rejoin at the next commit — eligibility
              masks breathe, like the lifecycle model

Determinism: sampler draws, the row pool, dropout and arrival times are
all `default_rng([seed, ...])` streams — one seed, one trace.
"""
from __future__ import annotations

import os
import time
from typing import Optional

import numpy as np

from fedml_tpu import obs
from fedml_tpu.scale.arrivals import (ArrivalConfig, ArrivalProcess,
                                      make_arrivals)
from fedml_tpu.scale.registry import ClientRegistry
from fedml_tpu.scale.sampler import StreamingCohortSampler


def pack_partial(acc, wsum) -> bytes:
    """One lane/rank partial on the wire: <f4 wsum then the f32 acc
    row.  THE one payload layout — run_serve_sim's fold, the fused
    cluster's fold (scale/cluster.py) and the elastic zero-fill all
    speak it, so the cross-rank digest pins compare the same bytes."""
    return (np.float32(wsum).tobytes()
            + np.asarray(acc, np.float32).tobytes())


def zero_partial(row_dim: int) -> bytes:
    """The deterministic zero payload a not-yet-adopted range folds."""
    return (np.float32(0.0).tobytes()
            + np.zeros(row_dim, np.float32).tobytes())


def fold_partials(docs, row_dim: int):
    """Rank/item-ordered sum of (wsum, acc) payloads — THE one
    cross-rank fold, shared by both transports and by the fused
    serving cluster.  Caller supplies docs already in item order; the
    fold itself adds nothing order-dependent."""
    import jax.numpy as jnp
    t_wsum = np.float32(0.0)
    t_acc = np.zeros(row_dim, np.float32)
    for d in docs:
        t_wsum = np.float32(
            t_wsum + np.frombuffer(d, "<f4", count=1)[0])
        t_acc += np.frombuffer(d, "<f4", offset=4)
    return jnp.asarray(t_acc), jnp.float32(t_wsum)


def rss_bytes() -> int:
    """Resident set size of this process (0 where /proc is absent)."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    return 0


class _ServeLane:
    """One client-id range's serving state — registry shards, sampler,
    streaming buffer, in-flight ring, and all the seeded streams — run
    as a generator that yields its partial (acc, wsum, n) at every
    commit boundary.  `item` is the range's index in the ORIGINAL
    world-sized partition: the fold is always in item order, so the
    global mix is independent of which process hosts which lane (the
    elastic re-adoption contract — a survivor adopting a dead rank's
    range creates a fresh lane with the dead rank's item index and the
    dead rank's seed streams, restarted from their beginning).

    The single-lane world==1 path walks EXACTLY the pre-lane per-
    arrival op order (dispatch → arrival → pop → crash|fold →
    commit-yield → rejoins → refill), so every existing seeded
    trace/pin survives the refactor."""

    def __init__(self, item: int, lo: int, hi: int, *, world: int,
                 seed: int, buffer_k: int, concurrency: int,
                 row_dim: int, sampler_mode: str,
                 arrival: ArrivalConfig, dropout_prob: float,
                 banned_frac: float, start_version: int = 0):
        import jax.numpy as jnp  # noqa: F401  (jax warmed by caller)
        from fedml_tpu.async_.staleness import AsyncBuffer
        self.item = int(item)
        self.lo, self.hi = int(lo), int(hi)
        self.local_population = self.hi - self.lo
        self.world = int(world)
        self.buffer_k = int(buffer_k)
        self.concurrency = int(concurrency)
        self.dropout_prob = float(dropout_prob)
        self.registry = ClientRegistry(self.local_population)
        # per-range streams when sharded (each range's bans/dropouts/
        # rows are its own); the world==1 streams stay EXACTLY the
        # pre-partition ones so every seeded trace/pin is unchanged
        key = [seed, 2] if world == 1 else [seed, 2, item]
        self.rng = np.random.default_rng(key)
        if banned_frac > 0.0:
            n_ban = max(1, int(banned_frac * self.local_population))
            self.registry.ban(np.unique(self.rng.integers(
                0, self.local_population, size=2 * n_ban))[:n_ban])
        self.sampler = StreamingCohortSampler(self.registry, buffer_k,
                                              seed=seed,
                                              mode=sampler_mode)
        self.buffer = AsyncBuffer(buffer_k, row_dim, streaming=True)
        self.pool = self.rng.standard_normal(
            (64, row_dim)).astype(np.float32)
        self.drop_rng = np.random.default_rng(
            [seed, 3] if world == 1 else [seed, 3, item])
        proc: Optional[ArrivalProcess] = make_arrivals(arrival)
        self.arr_iter = (proc.arrivals(0.0, np.random.default_rng(
            [arrival.seed, seed, 1] if world == 1
            else [arrival.seed, seed, 1, item]))
            if proc is not None else None)
        cap = 2 * self.concurrency + self.buffer_k
        self.cap = cap
        self.ring = np.zeros(cap, np.int64)
        self.head = self.tail = 0
        self.version = int(start_version)
        self.admitted = 0
        self.crashed = 0
        self.draws = 0   # MONOTONE per draw (the PR-10 uniform lesson)
        self.now = 0.0
        self._rejoin_at_commit: list[np.ndarray] = []

    def _dispatch(self, need: int) -> int:
        ids = self.sampler.sample(self.draws, k=need)
        self.draws += 1
        if ids.size == 0:
            return 0
        self.registry.note_dispatch(ids, self.version)
        for c in ids:
            self.ring[self.tail % self.cap] = c
            self.tail += 1
        return int(ids.size)

    def gen(self):
        """Yield (acc, wsum, n_commit) at each commit boundary; the
        driver folds across lanes/ranks and applies the ONE global
        commit."""
        self._dispatch(self.concurrency)
        while True:
            if (self.head == self.tail
                    and self._dispatch(self.buffer_k) == 0):
                raise RuntimeError(
                    f"serve sim starved at version {self.version} "
                    f"(lane {self.item}): no eligible clients "
                    f"({self.registry.count_free} free)")
            if self.arr_iter is not None:
                try:
                    self.now = next(self.arr_iter)
                except StopIteration:
                    # only TraceArrivals terminates — name the fix
                    raise ValueError(
                        f"arrival trace exhausted after "
                        f"{self.admitted + self.crashed} arrivals at "
                        f"commit {self.version}: the trace needs "
                        f"~commits*buffer_k (+dropout) "
                        f"timestamps") from None
            cid = int(self.ring[self.head % self.cap])
            self.head += 1
            if (self.dropout_prob > 0.0
                    and self.drop_rng.random() < self.dropout_prob):
                self.registry.note_crash(cid, rejoins=True)
                self.crashed += 1
                self._rejoin_at_commit.append(
                    np.asarray([cid], np.int64))
            else:
                v = self.registry.note_return(cid)
                staleness = float(self.version - v)
                full = self.buffer.add(self.pool[self.admitted % 64],
                                       1.0, staleness)
                self.registry.note_contribution(cid, staleness,
                                                self.version)
                self.admitted += 1
                if full:
                    acc, wsum, _w, _s, n_commit, _raw = \
                        self.buffer.take_stream()
                    yield acc, wsum, n_commit
                    self.version += 1
                    for ids in self._rejoin_at_commit:
                        for c in ids:
                            self.registry.note_rejoin(int(c))
                    self._rejoin_at_commit.clear()
            if (self.tail - self.head) <= (self.concurrency
                                           - self.buffer_k):
                with obs.span("serve.dispatch", version=self.version):
                    self._dispatch(self.concurrency
                                   - (self.tail - self.head))


def run_serve_sim(population: int, *, commits: int = 30,
                  warmup_commits: int = 2, buffer_k: int = 32,
                  concurrency: Optional[int] = None, row_dim: int = 1024,
                  sampler_mode: str = "stratified",
                  arrival: Optional[ArrivalConfig] = None,
                  dropout_prob: float = 0.0, banned_frac: float = 0.0,
                  seed: int = 0, partition: tuple = (0, 1),
                  channel=None, elastic: bool = False,
                  crash_at_commit: Optional[int] = None) -> dict:
    """Drive `commits` streaming commits at `population` simulated
    clients; returns the serve report (committed-updates/sec, registry
    memory, RSS, virtual-time stats).

    Host-sharded mode (ISSUE 13): `partition=(rank, world)` makes this
    process own ONLY its client-id range of the population — its
    registry shards, sampler and in-flight ring cover population/world
    clients (the PR-10 id-range partition, executed across processes).
    Each commit folds the partial streaming aggregates upward: the
    local (acc, wsum) allgathers over `channel`
    (parallel/multihost.py HostChannel), every rank sums the P-sized
    partials in RANGE (item) ORDER (deterministic — the two-level fold
    contract), and the identical commit applies everywhere — the
    report's `committed_digest` must agree across ranks.  Commit
    cadence is the synchronization point: every rank performs exactly
    `commits` commits, so the allgathers pair up; a dead rank raises
    the channel's DeadRankError naming it.

    Elastic mode (ISSUE 14): pass an `ElasticChannel` (n_items=world)
    and `elastic=True` — a rank dying mid-run no longer kills the
    survivors.  The window where the death lands folds ZERO for the
    dead range (deterministic on every survivor, so the cross-rank
    digest pin holds through the death), and at the NEXT commit
    barrier the view's new owner re-adopts the dead rank's
    registry-shard range as a fresh `_ServeLane` (the dead rank's item
    index and seed streams, restarted — its in-flight uplinks and
    participation counters died with it, which is the honest
    semantics).  `crash_at_commit` is the fault-injection hook: this
    rank abruptly closes its channel after that many commits and
    returns a partial report."""
    import jax.numpy as jnp
    from fedml_tpu.async_.staleness import make_stream_commit_fn

    if commits <= warmup_commits:
        raise ValueError(f"commits ({commits}) must exceed "
                         f"warmup_commits ({warmup_commits})")
    rank, world = int(partition[0]), int(partition[1])
    if not 0 <= rank < world:
        raise ValueError(f"partition rank {rank} outside world {world}")
    if world > 1 and channel is None:
        raise ValueError("world > 1 needs a HostChannel to fold the "
                         "partial aggregates upward")
    if elastic and world > 1 and not hasattr(channel, "exchange"):
        raise ValueError("elastic=True needs an ElasticChannel "
                         "(n_items=world); HostChannel is the "
                         "fail-fast transport")
    concurrency = (concurrency if concurrency is not None
                   else 4 * buffer_k)
    arrival = arrival if arrival is not None else ArrivalConfig(
        mode="constant", rate=1000.0, seed=seed)

    def make_lane(item: int, start_version: int = 0) -> _ServeLane:
        return _ServeLane(
            item, item * population // world,
            (item + 1) * population // world, world=world, seed=seed,
            buffer_k=buffer_k, concurrency=concurrency,
            row_dim=row_dim, sampler_mode=sampler_mode,
            arrival=arrival, dropout_prob=dropout_prob,
            banned_frac=banned_frac, start_version=start_version)

    primary = make_lane(rank)
    lanes: dict[int, _ServeLane] = {rank: primary}
    gens: dict[int, object] = {}
    retired: list[_ServeLane] = []      # lanes the view moved elsewhere
    adopted_items: list[int] = []
    zero_payload = zero_partial(row_dim)

    # the commit math: a tiny flat-row "model" through the REAL PR-6
    # streaming buffer + O(P) commit program
    template = {"w": jnp.zeros((row_dim,), jnp.float32)}
    commit_fn = make_stream_commit_fn(template, donate=False)
    variables = template
    version = 0
    t_wall0 = time.perf_counter()
    t_timed = None
    admitted_at_warmup = 0
    crashed_out = False

    def _pack(acc, wsum) -> bytes:
        return pack_partial(acc, wsum)

    def _fold(docs):
        return fold_partials(docs, row_dim)

    def all_lanes() -> list:
        return list(lanes.values()) + retired

    def registry_lanes() -> list:
        """Lanes for REGISTRY-state aggregation: at most one per item,
        the live lane winning over a retired one — re-adopting an item
        this rank previously retired must not double-count the range's
        registry bytes/bans/contributors.  Work counters (admitted/
        crashed) still sum over all_lanes(): a retired lane's folded
        updates really happened."""
        by_item = {ln.item: ln for ln in retired}
        by_item.update(lanes)
        return list(by_item.values())

    def lanes_admitted() -> int:
        return sum(ln.admitted for ln in all_lanes())

    def clock_lane() -> _ServeLane:
        """The lane whose virtual clock represents this rank NOW: the
        primary while hosted, else any still-hosted lane — a view
        change can retire even the rank's OWN range (the owner map is
        global), and a retired lane's clock freezes."""
        if rank in lanes:
            return lanes[rank]
        return next(iter(lanes.values())) if lanes else primary

    with obs.span("serve.run", population=population, commits=commits,
                  sampler=sampler_mode, arrival=arrival.mode,
                  elastic=elastic):
        gens[rank] = primary.gen()
        while version < commits:
            if crash_at_commit is not None and version == crash_at_commit:
                # fault injection: this rank vanishes mid-run — the
                # survivors' next exchange evicts it and re-adopts its
                # range at their next commit barrier
                if channel is not None:
                    channel.close()
                crashed_out = True
                break
            partials = {}
            for item in sorted(gens):
                acc, wsum, n_commit = next(gens[item])
                partials[item] = (acc, wsum, n_commit)
            with obs.span("serve.commit", version=version,
                          t_virtual=round(clock_lane().now, 3),
                          rank=rank):
                n_committed = sum(p[2] for p in partials.values())
                if world > 1 and elastic:
                    payloads = {item: _pack(acc, wsum)
                                for item, (acc, wsum, _n)
                                in partials.items()}
                    # a re-assigned range we don't host yet folds ZERO
                    # this window (identical bytes on every survivor);
                    # the lane starts at the next barrier below
                    allp, view = channel.exchange(
                        version, payloads,
                        lambda items: {i: zero_payload for i in items})
                    acc, wsum = _fold(allp[item]
                                      for item in range(world))
                elif world > 1:
                    # fail-fast fold, byte-compatible with ISSUE 13:
                    # one (wsum, acc) payload per rank, summed in rank
                    # order
                    acc, wsum, _n = partials[rank]
                    docs = channel.allgather(_pack(acc, wsum))
                    acc, wsum = _fold(docs)
                else:
                    acc, wsum, _n = partials[rank]
                variables, _stats = commit_fn(
                    variables, acc, wsum, jnp.float32(1.0))
            # ISSUE 12: the SLO pack's committed-updates floor
            obs.counter("async_updates_committed_total").inc(
                n_committed)
            version += 1
            if world > 1 and elastic:
                # the commit barrier re-partitions lanes onto the view:
                # exactly ONE host per range — drop lanes the owner map
                # moved elsewhere (double-hosting would race two
                # different partials for one item), adopt ranges it
                # moved here
                for item in list(gens):
                    if view.owner_of(item) != rank:
                        gens.pop(item).close()
                        retired.append(lanes.pop(item))
                for item in view.assigned(rank):
                    if item not in lanes:
                        lanes[item] = make_lane(item,
                                                start_version=version)
                        gens[item] = lanes[item].gen()
                        adopted_items.append(item)
                        obs.instant("serve.readopt", item=item,
                                    rank=rank, version=version)
            if version == warmup_commits:
                t_timed = time.perf_counter()
                admitted_at_warmup = lanes_admitted()
    wall = time.perf_counter() - (t_timed if t_timed is not None
                                  else t_wall0)
    timed_updates = lanes_admitted() - (admitted_at_warmup
                                        if t_timed is not None else 0)
    # contributor spread (from allocated shards only — O(touched)):
    # a healthy sampler scatters updates across the population; a
    # biased one concentrates them on few clients.  registry_lanes()
    # keeps at most one lane per range, so the sums stay exact even
    # when a retired range is later re-adopted.
    distinct = max_part = 0
    for ln in registry_lanes():
        for sh in ln.registry._shards.values():
            part = sh["participation"]
            distinct += int(np.count_nonzero(part))
            max_part = max(max_part,
                           int(part.max()) if part.size else 0)
    from fedml_tpu.parallel.multihost import variables_digest
    report = {
        "population": int(population),
        "local_population": int(primary.local_population),
        "partition": [rank, world],
        # the cross-rank agreement pin: host-sharded serve commits the
        # same global mix on every rank (THE one bitwise digest,
        # shared with the multihost pins)
        "committed_digest": variables_digest(variables),
        "carry_allreduce_bytes": int(getattr(channel, "bytes_received",
                                             0) if channel is not None
                                     else 0),
        "commits": int(version),
        "committed_updates": int(lanes_admitted()),
        "distinct_contributors": distinct,
        "max_client_participation": max_part,
        "committed_updates_per_sec": (timed_updates / wall
                                      if wall > 0 else 0.0),
        "buffer_k": int(buffer_k),
        "concurrency": int(concurrency),
        "row_dim": int(row_dim),
        "sampler_mode": sampler_mode,
        "sampler_peak_scratch_bytes": int(
            max(ln.sampler.peak_scratch_bytes for ln in all_lanes())),
        "arrival_mode": arrival.mode,
        "virtual_time_s": float(clock_lane().now),
        "mean_arrival_rate": (
            (clock_lane().admitted + clock_lane().crashed)
            / clock_lane().now if clock_lane().now > 0 else 0.0),
        "registry_bytes": int(sum(ln.registry.nbytes
                                  for ln in registry_lanes())),
        "registry_bytes_per_client": float(
            primary.registry.bytes_per_client),
        "registry_shards_allocated": sum(len(ln.registry._shards)
                                         for ln in registry_lanes()),
        "crashed": int(sum(ln.crashed for ln in all_lanes())),
        "banned": int(sum(ln.registry.count_banned
                          for ln in registry_lanes())),
        "rss_bytes": rss_bytes(),
        "wall_s": float(wall),
        "seed": int(seed),
    }
    if elastic:
        report["elastic"] = {
            "lanes": sorted(lanes),
            "adopted_items": adopted_items,
            "retired_items": [ln.item for ln in retired],
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
    return report
