"""Multi-host (DCN) runtime — bootstrap, host channel, and the
two-level round loop (ISSUE 13).

The reference scales across machines with `mpirun -np N -hostfile ...`
(run_fedavg_distributed_pytorch.sh:16-35) — one OS process per client rank
over MPI.  TPU-native, multi-host is one SPMD program: every host runs the
same code, `jax.distributed.initialize` wires the hosts into a single
runtime, and `jax.devices()` becomes the global chip list.  The engines in
parallel/ are already global-view (shard_map over a Mesh, device_put with
NamedShardings), so they run unchanged on a multi-host mesh — XLA routes
in-slice collectives over ICI and cross-slice traffic over DCN.

ISSUE 13 adds the runnable-today runtime on top of that seam, following
the MLPerf pod recipe (arXiv:1909.09756 — per-host input pipelines,
hierarchical gradient reduction) mapped onto FedML's hierarchical
aggregation (arXiv:2007.13518):

* `MultihostContext` / `spawn_cluster` / `tools/launch_multihost.py` —
  a multi-process launcher: N OS processes wired by env
  (`FEDML_MH_RANK/WORLD/COORD`), optionally joined into one jax runtime
  via `init_multihost` (`FEDML_MH_JAX_COORD`; on TPU pods this is what
  makes the local chips visible).
* `HostChannel` — the DCN tier executed for real: a tiny TCP
  coordinator (rank 0) carrying the P-sized flat f32 carry between
  hosts.  On the CPU dev box this stands in for gloo/DCN; it needs NO
  backend collective support.  Every wait is BOUNDED: a dead or hung
  rank raises `DeadRankError` NAMING the rank instead of hanging the
  cluster.
* `MultihostRunner` — the two-level round loop: intra-host psum over
  the flat f32 carry on the LOCAL mesh (the engine's new
  `{family}_twolevel` partial program, ICI tier), then an inter-host
  allreduce of the P-sized per-block partials over the HostChannel
  (DCN tier), then a replicated commit (`twolevel_commit` program) on
  every host.

Bitwise anchor (the pin that anchors this subsystem, like the reactor
and async ones): the reduction tree is a function of the BLOCK
PARTITION, not the process count.  The cohort is sampled per block
from fixed population ranges (`BlockCohortSampler`, rng streams keyed
[seed, round, block]), each block's partial is one compiled program on
a same-shaped local mesh, and every host folds ALL block partials in
global block order.  Any process count that tiles the same blocks
therefore commits bitwise-identically — `n_blocks=2` at 1 process and
at 2 processes produce the same bits (tests/test_multihost_spmd.py).
This is STRONGER than an in-program psum can promise (a topology
change reorders XLA's reduction ring).

Mesh layout guidance (the scaling-book recipe): put the axis with the
heaviest collective traffic (the client/cohort axis — its psum moves the
whole model) INSIDE a slice so it rides ICI; put the hierarchical silo
axis across slices so only the second-tier reduction crosses DCN —
`make_hierarchical_host_mesh` encodes exactly that on top of
mesh.make_mesh_2d.

IMPORTANT: init_multihost() must run before ANY jax call that initializes
the XLA backend (so: first thing in main) — jax.distributed.initialize
refuses to run afterwards.

Streaming/prefetch note (parallel/prefetch.py): the streaming and
block-stream paths' background upload thread is PER PROCESS, and every
process runs the same round loop, so the prefetchers issue their
`jax.device_put(..., NamedSharding)` calls in the same order on every
host — each process materializes only its addressable shards, and the
upload/compute overlap composes across hosts (each host hides its own
gather+DMA behind its chips' compute).  The block-streamed
order-statistic defenses remain single-process (enforced at engine
construction): their host [K, P] offload needs every client shard
addressable.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import logging
import os
import pickle
import select
import signal
import socket
import struct
import subprocess
import sys
import threading
import time
from typing import Callable, Optional

import jax
import numpy as np
from jax.sharding import Mesh

from fedml_tpu import obs
from fedml_tpu.obs import cluster as _cluster
from fedml_tpu.parallel.mesh import CLIENT_AXIS, make_mesh, make_mesh_2d

log = logging.getLogger(__name__)

ENV_RANK = "FEDML_MH_RANK"
ENV_WORLD = "FEDML_MH_WORLD"
ENV_COORD = "FEDML_MH_COORD"           # host:port of the HostChannel
ENV_JAX_COORD = "FEDML_MH_JAX_COORD"   # host:port for jax.distributed


def init_multihost(coordinator_address: Optional[str] = None,
                   num_processes: Optional[int] = None,
                   process_id: Optional[int] = None,
                   required: bool = False) -> None:
    """Join this host into the global runtime (idempotent).

    With no arguments, relies on the cluster's auto-detection (TPU pods
    expose the coordinator via metadata) and degrades gracefully to
    single-process mode on a dev box.  With EXPLICIT arguments — or
    required=True (the CLI's --multihost sets it) — a failure raises:
    silently training independent single-host replicas would corrupt the
    run.  Replaces the reference's mpirun/hostfile bootstrap."""
    if jax.distributed.is_initialized():
        return
    explicit = (required or coordinator_address is not None
                or num_processes is not None or process_id is not None)
    try:
        # CPU cross-process collectives need a transport; without one the
        # global mesh forms but the first psum fails.  jaxlib defaults
        # the option to "gloo" (test_multihost_spmd runs over it); only
        # an unset/disabled value is repaired here, so an operator's
        # explicit transport choice (env
        # JAX_CPU_COLLECTIVES_IMPLEMENTATION=mpi or a prior
        # config.update) wins.  It must happen BEFORE initialize, and
        # without probing the platform — that would initialize the
        # backend, which jax.distributed.initialize forbids (see module
        # docstring); the option only affects the cpu backend (TPU pods
        # use ICI/DCN natively).
        if jax.config.jax_cpu_collectives_implementation in (
                None, "", "none"):
            jax.config.update("jax_cpu_collectives_implementation",
                              "gloo")
        jax.distributed.initialize(coordinator_address=coordinator_address,
                                   num_processes=num_processes,
                                   process_id=process_id)
        log.info("multihost: process %d/%d, %d global devices",
                 jax.process_index(), jax.process_count(),
                 len(jax.devices()))
    except Exception as e:
        if explicit:
            raise RuntimeError(
                f"multi-host initialization failed for coordinator "
                f"{coordinator_address!r}: {e}") from e
        log.info("multihost init skipped (%s); single-process mode", e)


def make_global_mesh(axis_name: str = CLIENT_AXIS) -> Mesh:
    """1-D mesh over ALL chips of ALL hosts — the cohort axis spans the
    pod; psum rides ICI within a slice and DCN across."""
    return make_mesh(axis_name=axis_name)


def make_local_mesh(axis_name: str = CLIENT_AXIS) -> Mesh:
    """1-D mesh over THIS process's chips only — the intra-host tier of
    the two-level aggregation (MultihostRunner requires a local-only
    mesh: its cross-host traffic is the HostChannel carry exchange, not
    in-program collectives)."""
    return make_mesh(axis_name=axis_name, devices=jax.local_devices())


def make_hierarchical_host_mesh(silos: Optional[int] = None) -> Mesh:
    """2-D (silo × clients) mesh with one silo per host by default: the
    inner FedAvg psum stays on each host's ICI, only the per-silo means
    cross DCN — the two-tier reduction of hierarchical FL mapped onto the
    physical network (SURVEY.md §2.5 'hierarchical aggregation').

    VIRTUAL-SILO semantics (single process, silos>1): with only one
    process there is no host boundary to place the silo tier on — the
    requested silo rows are carved out of THIS host's devices, so the
    "DCN tier" is simulated on local links.  That is the intended
    dev/test topology (the virtual-CPU oracles in
    tests/multihost_case.py rely on it), but it measures NOTHING about
    cross-host cost — a loud warning says so, because on a real pod the
    same call with one process per host is the genuine two-tier layout
    and silently accepting the single-process shape has masked
    misconfigured launches (ISSUE 13 satellite)."""
    devs = jax.devices()
    procs = max(jax.process_count(), 1)
    silos = silos or procs
    if len(devs) % silos != 0:
        raise ValueError(f"{len(devs)} devices not divisible into "
                         f"{silos} silos")
    if procs == 1 and silos > 1:
        log.warning(
            "make_hierarchical_host_mesh: building %d VIRTUAL silos on a "
            "single process — every silo row shares this host's devices, "
            "so the cross-silo tier rides local links, not DCN.  This is "
            "the dev/test topology (virtual-CPU oracles); on a pod, "
            "launch one process per host so the silo tier really crosses "
            "hosts.", silos)
    # global device order is NOT guaranteed host-contiguous; sort by
    # process so each silo row really sits on one host's ICI
    devs = sorted(devs, key=lambda d: (d.process_index, d.id))
    return make_mesh_2d(n_silos=silos, devices=devs)


# ---------------------------------------------------------------------------
# process context + cluster spawning
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MultihostContext:
    """One process's place in the launched cluster (env-carried so any
    entry point — cli, bench worker, test worker — resolves the same
    way)."""
    rank: int
    world: int
    coordinator: str                    # "host:port" of the HostChannel
    jax_coordinator: Optional[str] = None   # jax.distributed, when wired

    @classmethod
    def from_env(cls) -> Optional["MultihostContext"]:
        if ENV_RANK not in os.environ or ENV_WORLD not in os.environ:
            return None
        world = int(os.environ[ENV_WORLD])
        rank = int(os.environ[ENV_RANK])
        if not 0 <= rank < world:
            raise ValueError(f"{ENV_RANK}={rank} outside world "
                             f"{world}")
        return cls(rank=rank, world=world,
                   coordinator=os.environ.get(ENV_COORD,
                                              "localhost:0"),
                   jax_coordinator=os.environ.get(ENV_JAX_COORD))

    @classmethod
    def single(cls) -> "MultihostContext":
        return cls(rank=0, world=1, coordinator="localhost:0")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


class MultihostLaunchError(RuntimeError):
    """A launched rank failed/hung; the message names it."""


def _rank_outcome(rc: Optional[int], policy_killed: bool = False) -> str:
    """One rank's exit, human-named: clean/nonzero exit codes and the
    SIGNAL name for signal deaths — SIGKILL (the chaos injection / OOM
    shape) reads differently from SIGSEGV (a real crash) and from a
    plain nonzero exit (a named Python error)."""
    if rc is None:
        return "still running"
    if rc == 0:
        return "ok"
    if rc < 0:
        try:
            name = signal.Signals(-rc).name
        except ValueError:
            name = f"signal {-rc}"
        suffix = (" by launcher cleanup after the first failure"
                  if policy_killed else "")
        return f"killed by {name}{suffix}"
    return f"exit rc={rc}"


def spawn_cluster(cmd: list[str], procs: int, *,
                  env: Optional[dict] = None,
                  timeout_s: float = 600.0,
                  jax_distributed: bool = False,
                  echo: bool = False,
                  coordinator_host: str = "localhost",
                  elastic: bool = False,
                  respawn: bool = False,
                  kill_grace_s: float = 5.0) -> list[str]:
    """Fork `procs` copies of `cmd` wired as one multihost cluster (env
    FEDML_MH_RANK/WORLD/COORD [+ FEDML_MH_JAX_COORD with
    jax_distributed]); returns each rank's stdout, rank-ordered.

    Failure policy (fail-fast, the default): the first rank to exit
    nonzero kills the rest and raises MultihostLaunchError NAMING that
    rank (with its stderr tail) plus a per-rank outcome summary — exit
    code or signal name for EVERY rank, so a chaos-killed rank
    (SIGKILL) is distinguishable from the collateral channel-EOF deaths
    it causes.  A deadline overrun kills everything and names the ranks
    still running.

    Elastic policy (`elastic=True`, ISSUE 14): a dead rank does NOT
    take the survivors down — the cluster runs to completion and only a
    rank-0 (coordinator) failure or the deadline raises.  With
    `respawn=True` a dead nonzero rank > 0 is relaunched ONCE with
    FEDML_MH_REJOIN=1 in its env, so the worker re-enters the cluster
    through the elastic rejoin handshake (ElasticChannel) — the
    process-level chaos/recovery loop, launcher-driven.

    `echo` streams child stderr line-prefixed (`[rank i]`)."""
    outs, _report = spawn_cluster_report(
        cmd, procs, env=env, timeout_s=timeout_s,
        jax_distributed=jax_distributed, echo=echo,
        coordinator_host=coordinator_host, elastic=elastic,
        respawn=respawn, kill_grace_s=kill_grace_s)
    return outs


def spawn_cluster_report(cmd: list[str], procs: int, *,
                         env: Optional[dict] = None,
                         timeout_s: float = 600.0,
                         jax_distributed: bool = False,
                         echo: bool = False,
                         coordinator_host: str = "localhost",
                         elastic: bool = False,
                         respawn: bool = False,
                         kill_grace_s: float = 5.0
                         ) -> tuple[list[str], dict]:
    """spawn_cluster plus a per-rank outcome report: ({rank stdouts},
    {"ranks": {r: {"rc", "outcome", "respawned", "incarnations"}},
    "first_failed": r|None}) — the bench's chaos arm reads survivor
    deaths and the respawn count from here instead of re-parsing
    stderr."""
    if procs < 1:
        raise ValueError(f"procs must be >= 1, got {procs}")
    if not cmd:
        raise ValueError("empty worker command")
    if respawn and not elastic:
        raise ValueError("respawn=True needs elastic=True (a fail-fast "
                         "cluster kills the survivors the rejoiner "
                         "would rejoin)")
    coord = f"{coordinator_host}:{free_port()}"
    # N ranks on ONE host can never share its chip(s) — and the parent
    # may hold them — so every rank is CPU unless the caller's `env`
    # says otherwise: explicit here, not left to a child's setdefault
    base_env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env or {}),
                ENV_WORLD: str(procs), ENV_COORD: coord}
    base_env.pop("FEDML_MH_REJOIN", None)
    if jax_distributed:
        base_env[ENV_JAX_COORD] = f"{coordinator_host}:{free_port()}"

    # per-rank incarnation tables (respawn appends a second incarnation)
    incarnations: list[list[subprocess.Popen]] = [[] for _ in range(procs)]
    bufs: dict[tuple[int, int], tuple[list, list]] = {}
    drains: list[threading.Thread] = []
    policy_killed: set[int] = set()

    def _drain(rank: int, gen: int, p: subprocess.Popen):
        buf_out: list = []
        buf_err: list = []
        bufs[(rank, gen)] = (buf_out, buf_err)

        def _pump(stream, buf, is_err):
            for line in stream:
                buf.append(line)
                if echo and is_err:
                    # stderr streams live (progress/tracebacks); stdout
                    # is returned buffered so machine-readable lines
                    # stay contiguous per rank
                    print(f"[rank {rank}] {line}", end="",
                          file=sys.stderr, flush=True)
        t_err = threading.Thread(target=_pump,
                                 args=(p.stderr, buf_err, True))
        t_err.start()
        _pump(p.stdout, buf_out, False)
        t_err.join()

    def _launch(rank: int, rejoin: bool = False):
        e = dict(base_env)
        e[ENV_RANK] = str(rank)
        if rejoin:
            e["FEDML_MH_REJOIN"] = "1"
        p = subprocess.Popen(cmd, env=e, text=True,
                             stdout=subprocess.PIPE,
                             stderr=subprocess.PIPE)
        gen = len(incarnations[rank])
        incarnations[rank].append(p)
        t = threading.Thread(target=_drain, args=(rank, gen, p))
        t.start()
        drains.append(t)
        return p

    for r in range(procs):
        _launch(r)

    def _cur(rank: int) -> subprocess.Popen:
        return incarnations[rank][-1]

    def _summary() -> str:
        rows = []
        for r in range(procs):
            tags = [_rank_outcome(p.poll(), r in policy_killed)
                    for p in incarnations[r]]
            rows.append(f"rank {r}: " + " -> respawned: ".join(tags))
        return "; ".join(rows)

    def _err_tail(rank: int) -> str:
        chunks = [("".join(bufs.get((rank, g), ([], []))[1]))
                  for g in range(len(incarnations[rank]))]
        return "".join(chunks)[-3000:]

    deadline = time.monotonic() + timeout_s
    first_failed: Optional[int] = None
    handled_deaths: set[tuple[int, int]] = set()
    respawned: set[int] = set()
    try:
        while True:
            live = [r for r in range(procs) if _cur(r).poll() is None]
            for r in range(procs):
                for g, p in enumerate(incarnations[r]):
                    if (p.poll() is not None and p.returncode != 0
                            and (r, g) not in handled_deaths):
                        handled_deaths.add((r, g))
                        if first_failed is None:
                            first_failed = r
                        if (elastic and respawn and r != 0
                                and r not in respawned):
                            respawned.add(r)
                            log.warning(
                                "elastic launch: rank %d died (%s); "
                                "respawning once with FEDML_MH_REJOIN=1",
                                r, _rank_outcome(p.returncode))
                            _launch(r, rejoin=True)
            if elastic:
                # survivors outlive a dead peer; only the coordinator's
                # death (or the deadline) is cluster-fatal
                if (_cur(0).poll() is not None
                        and _cur(0).returncode != 0):
                    break
                if all(_cur(r).poll() is not None
                       for r in range(procs)):
                    break
            else:
                failed = [r for r in range(procs)
                          if _cur(r).poll() is not None
                          and _cur(r).returncode != 0]
                if failed or not live:
                    break
            if time.monotonic() > deadline:
                for r in live:
                    policy_killed.add(r)
                    _cur(r).kill()
                for r in live:   # reap: the summary must show the
                    try:         # kill outcome, not "still running"
                        _cur(r).wait(timeout=10)
                    except Exception:
                        pass
                raise MultihostLaunchError(
                    f"multihost launch timed out after {timeout_s:.0f}s: "
                    f"rank(s) {live} still running (of {procs})\n"
                    f"per-rank: {_summary()}")
            time.sleep(0.05)
        if any(p.returncode not in (0, None)
               for ps in incarnations for p in ps):
            # give survivors a short grace (a dead peer's channel EOF
            # usually fails them promptly with their OWN named error;
            # elastic survivors already ran to completion), then kill
            grace = time.monotonic() + kill_grace_s
            while (time.monotonic() < grace
                   and any(_cur(r).poll() is None
                           for r in range(procs))):
                time.sleep(0.05)
            killed_now = []
            for r in range(procs):
                if _cur(r).poll() is None:
                    policy_killed.add(r)
                    _cur(r).kill()
                    killed_now.append(r)
            for r in killed_now:
                # reap before the report/summary reads returncode —
                # an unreaped kill would show rc=None "still running"
                try:
                    _cur(r).wait(timeout=10)
                except Exception:
                    pass
    finally:
        for t in drains:
            t.join()
    report = {
        "first_failed": first_failed,
        "ranks": {
            r: {"rc": _cur(r).returncode,
                "outcome": _rank_outcome(_cur(r).returncode,
                                         r in policy_killed),
                "respawned": r in respawned,
                "incarnations": len(incarnations[r]),
                "all_rcs": [p.returncode for p in incarnations[r]]}
            for r in range(procs)},
    }
    bad = [r for r in range(procs) if _cur(r).returncode != 0]
    fatal = bad and (not elastic or 0 in bad)
    if fatal:
        # blame the FIRST rank observed failing (the injected/original
        # fault), not a survivor that died of the resulting channel
        # EOF; the per-rank summary names EVERY rank's exit/signal
        i = first_failed if first_failed in bad else bad[0]
        raise MultihostLaunchError(
            f"multihost rank {i}/{procs} failed first "
            f"(rc={_cur(i).returncode}; {len(bad)}/{procs} ranks "
            f"failed):\nper-rank: {_summary()}\n{_err_tail(i)}")
    outs = ["".join("".join(bufs.get((r, g), ([], []))[0])
                    for g in range(len(incarnations[r])))
            for r in range(procs)]
    return outs, report


# ---------------------------------------------------------------------------
# HostChannel — the DCN tier, executed for real
# ---------------------------------------------------------------------------

class DeadRankError(RuntimeError):
    """A peer rank died or stalled past the bounded channel timeout; the
    message names it (the crash-of-one-process acceptance case)."""


def _send_frame(sock: socket.socket, payload: bytes) -> None:
    sock.sendall(struct.pack("<Q", len(payload)) + payload)


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("peer closed")
        buf.extend(chunk)
    return bytes(buf)


def _recv_frame(sock: socket.socket) -> bytes:
    (n,) = struct.unpack("<Q", _recv_exact(sock, 8))
    return _recv_exact(sock, n)


def _dial_with_backoff(host: str, port: int, deadline: float, what: str,
                       *, initial_s: float = 0.05,
                       cap_s: float = 1.0) -> socket.socket:
    """Deadline-bounded TCP dial with exponential backoff — THE connect
    path for every transient dial in this module (worker->coordinator
    data/heartbeat/rejoin links).  A coordinator mid-accept-setup, or
    restarting in elastic mode, refuses connects transiently; retrying
    with growing sleeps (initial_s doubling to cap_s) inside the
    caller's deadline turns that window into latency instead of a
    launch failure.  Final failure raises DeadRankError NAMING `what`
    and the last OS error."""
    delay = initial_s
    last: Optional[Exception] = None
    while True:
        budget = deadline - time.monotonic()
        if budget <= 0:
            raise DeadRankError(
                f"{what}: could not connect to {host}:{port} before its "
                f"deadline (last error: "
                f"{type(last).__name__ if last is not None else 'none'}:"
                f" {last})") from last
        try:
            return socket.create_connection(
                (host, port), timeout=min(5.0, max(0.1, budget)))
        except OSError as e:
            last = e
            time.sleep(min(delay, max(0.0, deadline - time.monotonic())))
            delay = min(delay * 2.0, cap_s)


def _export_channel_byte_counters(rank: int, bytes_sent: int,
                                  bytes_received: int) -> None:
    """Publish a channel's cumulative byte counters as obs metrics
    (called at round boundaries — the counters themselves stay cheap
    plain ints on the hot path).  Shared by HostChannel and
    ElasticChannel so the delta-inc accounting can never diverge."""
    r = str(rank)
    sent = obs.counter("multihost_bytes_sent_total", rank=r)
    recv = obs.counter("multihost_bytes_received_total", rank=r)
    sent.inc(max(0.0, bytes_sent - sent.value))
    recv.inc(max(0.0, bytes_received - recv.value))


def _account_carry(raw: int, wire: int) -> None:
    """Carry-codec compression accounting (ISSUE 16), mirroring the
    comm layer's MessageCodec._account: raw = the f32 bytes of the
    carry partials this rank encoded, wire = the encoded payload it
    shipped; the gauge is the cumulative raw/wire quotient."""
    c_raw = obs.counter("multihost_carry_raw_bytes_total")
    c_wire = obs.counter("multihost_carry_compressed_bytes_total")
    c_raw.inc(raw)
    c_wire.inc(wire)
    if c_wire.value > 0:
        obs.gauge("multihost_carry_compression_ratio").set(
            c_raw.value / c_wire.value)


class _GatherHandle:
    """In-flight state of ONE pipelined carry gather (ISSUE 16): rank 0
    carries the background frame collector, workers the chained
    frame-push tail; gather_finish() consumes it.  One handle per
    collective — never reused."""

    __slots__ = ("n_frames", "deadline", "seq", "own", "pending",
                 "collector", "pushed", "aborted")

    def __init__(self, n_frames: int, deadline: float, seq: int):
        self.n_frames = int(n_frames)
        self.deadline = float(deadline)
        self.seq = int(seq)
        self.own: list[bytes] = []
        self.pending = None
        self.collector = None
        self.pushed = 0
        self.aborted = False


class _ContribHandle:
    """In-flight early contributions of one elastic exchange (ISSUE
    16): workers chain per-block contrib sends (the coordinator's
    multi-contrib protocol already accepts them), rank 0 stashes its
    own blocks locally; ElasticChannel.exchange(pending=...) drains the
    handle.  Stale handles are harmless — the coordinator drops
    contribs whose round header does not match the round in flight."""

    __slots__ = ("round_idx", "blocks", "stash", "pending")

    def __init__(self, round_idx: int):
        self.round_idx = int(round_idx)
        self.blocks: list[int] = []
        self.stash: dict[int, bytes] = {}
        self.pending = None


class HostChannel:
    """Small-payload allgather/barrier between the cluster's processes —
    the inter-host (DCN) tier of the two-level aggregation, carrying the
    P-sized flat f32 carry partials.

    Star topology: rank 0 coordinates (gathers every rank's payload,
    broadcasts the rank-ordered list).  Deliberately NOT a ring: the
    payloads are O(P) model-carry vectors, tiny next to the cohort data
    that never crosses processes, and a star gives every failure a
    single observer that can NAME the dead rank.  All waits are bounded
    (`timeout_s`): a dead peer raises DeadRankError naming it instead
    of hanging the round loop (the PR-8 crash lesson, applied to the
    cluster tier).  Byte/time accounting lands in
    multihost_bytes_sent/received_total and multihost_allgather_seconds
    (the bench's carry-allreduce bytes read)."""

    def __init__(self, ctx: MultihostContext, *,
                 timeout_s: float = 120.0,
                 connect_timeout_s: float = 60.0):
        self.ctx = ctx
        self.timeout_s = float(timeout_s)
        self.bytes_sent = 0
        self.bytes_received = 0
        self._mark = (0, 0)
        self._seq = 0
        # the runner stamps the round in flight here so the barrier
        # ledger (ISSUE 17) can attribute gather waits to a round
        self.round_hint: Optional[int] = None
        self._peers: dict[int, socket.socket] = {}
        self._sock: Optional[socket.socket] = None
        self._listener: Optional[socket.socket] = None
        _cluster.set_role(ctx.rank, ctx.world)
        if ctx.world <= 1:
            return
        host, port = ctx.coordinator.rsplit(":", 1)
        port = int(port)
        if ctx.rank == 0:
            self._listener = socket.create_server((host, port))
            self._listener.settimeout(connect_timeout_s)
            deadline = time.monotonic() + connect_timeout_s

            def _setup_dead(reason: str):
                missing = sorted(set(range(1, ctx.world))
                                 - set(self._peers))
                for s in self._peers.values():
                    s.close()
                self._listener.close()
                raise DeadRankError(
                    f"multihost channel setup: rank(s) {missing} "
                    f"{reason} within {connect_timeout_s:.0f}s")

            while len(self._peers) < ctx.world - 1:
                try:
                    conn, _ = self._listener.accept()
                except socket.timeout:
                    conn = None
                if conn is None or time.monotonic() > deadline:
                    _setup_dead("never connected")
                conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                # accepted sockets are BLOCKING regardless of the
                # listener's timeout — bound the rank handshake too, or
                # a connected-but-stalled peer hangs setup unboundedly
                conn.settimeout(max(0.001, deadline - time.monotonic()))
                try:
                    (r,) = struct.unpack("<I", _recv_exact(conn, 4))
                except (socket.timeout, ConnectionError, OSError):
                    conn.close()
                    _setup_dead("connected but never sent a rank "
                                "handshake")
                self._peers[r] = conn
        else:
            # deadline-bounded exponential-backoff dial: the accept
            # window on rank 0 opens asynchronously with this process's
            # start, so first-connect refusals are expected, not fatal
            self._sock = _dial_with_backoff(
                host, port, time.monotonic() + connect_timeout_s,
                f"multihost channel setup: rank {ctx.rank} dialing the "
                f"rank-0 coordinator at {ctx.coordinator}")
            self._sock.setsockopt(socket.IPPROTO_TCP,
                                  socket.TCP_NODELAY, 1)
            self._sock.sendall(struct.pack("<I", ctx.rank))

    # -- collective ops ------------------------------------------------------
    def allgather(self, payload: bytes,
                  timeout_s: Optional[float] = None) -> list[bytes]:
        """Every rank contributes `payload`; every rank receives the
        rank-ordered list.  Bounded: a silent rank raises DeadRankError
        naming it."""
        t0 = time.perf_counter()
        timeout = self.timeout_s if timeout_s is None else float(timeout_s)
        self._seq += 1
        ctx = self.ctx
        if ctx.world <= 1:
            return [payload]
        deadline = time.monotonic() + timeout
        try:
            if ctx.rank == 0:
                # barrier ledger (ISSUE 17): rank 0 is the star's single
                # observer — its own arrival is the loop open, each
                # peer's is its frame landing.  Piggybacked metric
                # sidecars are stripped BEFORE the broadcast, so every
                # rank folds the identical payload bytes.
                arrivals = {0: time.monotonic()}
                parts: list[Optional[bytes]] = [None] * ctx.world
                parts[0] = payload
                for r in sorted(self._peers):
                    sock = self._peers[r]
                    sock.settimeout(max(0.001,
                                        deadline - time.monotonic()))
                    try:
                        parts[r] = _recv_frame(sock)
                    except (socket.timeout, ConnectionError, OSError) as e:
                        missing = sorted(r2 for r2 in range(1, ctx.world)
                                         if parts[r2] is None)
                        raise DeadRankError(
                            f"multihost allgather #{self._seq}: no "
                            f"payload from rank(s) {missing} within "
                            f"{timeout:.0f}s ({type(e).__name__}: "
                            f"process dead or hung)") from e
                    arrivals[r] = time.monotonic()
                    self.bytes_received += len(parts[r])
                    parts[r], side = _cluster.split_sidecar(parts[r])
                    if side is not None:
                        _cluster.fold_remote(r, side)
                _cluster.note_barrier("allgather", self._seq,
                                      self.round_hint, arrivals)
                blob = struct.pack("<I", ctx.world) + b"".join(
                    struct.pack("<Q", len(p)) + p for p in parts)
                for r in sorted(self._peers):
                    try:
                        _send_frame(self._peers[r], blob)
                    except (socket.timeout, ConnectionError, OSError) as e:
                        raise DeadRankError(
                            f"multihost allgather #{self._seq}: "
                            f"broadcast to rank {r} failed "
                            f"({type(e).__name__}: rank died after "
                            f"contributing)") from e
                    self.bytes_sent += len(blob) + 8
                return list(parts)          # type: ignore[arg-type]
            # non-root: ship ours, await the broadcast.  Reset the
            # send-side timeout first — settimeout() PERSISTS on the
            # socket, so without this the send runs under whatever
            # near-expired recv deadline the previous allgather left
            self._sock.settimeout(max(0.001,
                                      deadline - time.monotonic()))
            # live telemetry plane (ISSUE 17): ship a bounded metrics
            # delta as a self-describing payload trailer — rank 0
            # strips it before the broadcast.  Attached ONLY when an
            # obs dir is configured: the obs-off wire stays
            # byte-identical.
            out = payload
            if _cluster.telemetry_enabled():
                out = _cluster.attach_sidecar(payload, _piggyback_delta())
            try:
                _send_frame(self._sock, out)
            except (socket.timeout, ConnectionError, OSError) as e:
                raise DeadRankError(
                    f"multihost allgather #{self._seq}: rank {ctx.rank} "
                    f"could not ship its payload to the rank-0 "
                    f"coordinator ({type(e).__name__}: coordinator dead "
                    f"or backpressured past {timeout:.0f}s)") from e
            self.bytes_sent += len(out) + 8
            self._sock.settimeout(max(0.001, deadline - time.monotonic()))
            try:
                blob = _recv_frame(self._sock)
            except (socket.timeout, ConnectionError, OSError) as e:
                raise DeadRankError(
                    f"multihost allgather #{self._seq}: rank {ctx.rank} "
                    f"got no broadcast from the rank-0 coordinator "
                    f"within {timeout:.0f}s ({type(e).__name__}: "
                    f"coordinator dead, or a peer stalled it)") from e
            self.bytes_received += len(blob)
            (world,) = struct.unpack_from("<I", blob, 0)
            off, parts = 4, []
            for _ in range(world):
                (n,) = struct.unpack_from("<Q", blob, off)
                off += 8
                parts.append(blob[off:off + n])
                off += n
            return parts
        finally:
            obs.histogram("multihost_allgather_seconds").observe(
                time.perf_counter() - t0)

    def barrier(self, timeout_s: Optional[float] = None) -> None:
        self.allgather(b"", timeout_s=timeout_s)

    # -- per-round wire accounting -------------------------------------------
    def mark_round(self) -> None:
        """Open a per-round wire window (ISSUE 16 satellite): the
        compressed arm's bytes-per-round is what the CHANNEL moved
        between mark_round() and round_wire_delta(), not a host-side
        re-derivation of what it should have moved."""
        self._mark = (self.bytes_sent, self.bytes_received)

    def round_wire_delta(self) -> dict[str, int]:
        s0, r0 = self._mark
        return {"sent": self.bytes_sent - s0,
                "received": self.bytes_received - r0}

    # -- pipelined gather (compute/DCN overlap, ISSUE 16) --------------------
    def gather_begin(self, n_frames: int,
                     timeout_s: Optional[float] = None) -> _GatherHandle:
        """Open a pipelined allgather of `n_frames` frames per rank:
        each rank pushes frames as they materialize (gather_push) and
        the collective completes in gather_finish() — frame j's wire
        transfer overlaps frame j+1's block compute instead of
        serializing behind the whole payload.  Equivalent by
        construction to allgather(b"".join(frames)): the per-rank
        frames concatenate in push order (the deterministic owned-block
        order), and the broadcast blob is identical — which is why the
        f32 escape hatch stays bitwise under overlap."""
        timeout = self.timeout_s if timeout_s is None else float(timeout_s)
        self._seq += 1
        h = _GatherHandle(n_frames, time.monotonic() + timeout, self._seq)
        if self.ctx.world > 1 and self.ctx.rank == 0 and h.n_frames:
            from fedml_tpu.parallel.prefetch import AsyncValue
            h.collector = AsyncValue(self._collect_frames, h,
                                     name=f"gather#{h.seq}")
        return h

    def _collect_frames(self, h: _GatherHandle):
        """Rank 0's background collector: drain every peer's frames in
        per-peer FIFO order while rank 0's own blocks compute.  Runs on
        the gather handle's AsyncValue thread; joined in
        gather_finish() (errors re-raise there).  Returns (frames,
        arrivals): a peer "arrives" at the barrier when its LAST frame
        lands — the ledger stamp the straggler attribution keys on."""
        remaining = {r: h.n_frames for r in self._peers}
        frames: dict[int, list[bytes]] = {r: [] for r in self._peers}
        arrivals: dict[int, float] = {}
        by_sock = {s: r for r, s in self._peers.items()}
        while any(remaining.values()) and not h.aborted:
            budget = h.deadline - time.monotonic()
            if budget <= 0:
                owing = sorted(r for r, n in remaining.items() if n)
                raise DeadRankError(
                    f"multihost gather #{h.seq}: rank(s) {owing} still "
                    f"owe carry frames at the deadline (process dead, "
                    f"hung, or its block compute overran the window)")
            socks = [self._peers[r] for r, n in remaining.items() if n]
            try:
                rl, _, _ = select.select(socks, [], [], min(0.2, budget))
            except (OSError, ValueError):
                rl = []          # a sock closed under us: deadline names it
            for s in rl:
                r = by_sock[s]
                s.settimeout(max(0.001, h.deadline - time.monotonic()))
                try:
                    f = _recv_frame(s)
                except (socket.timeout, ConnectionError, OSError) as e:
                    raise DeadRankError(
                        f"multihost gather #{h.seq}: rank {r} died "
                        f"mid-frame ({type(e).__name__})") from e
                self.bytes_received += len(f)
                frames[r].append(f)
                remaining[r] -= 1
                if remaining[r] == 0:
                    arrivals[r] = time.monotonic()
        return frames, arrivals

    def gather_push(self, h: _GatherHandle, frame: bytes) -> None:
        """Ship one frame into an open gather.  Rank 0 stashes locally
        (its frames never cross the wire); workers chain the send onto
        the previous push's AsyncValue so socket writes serialize while
        the caller returns to computing the next block."""
        h.pushed += 1
        if self.ctx.world <= 1 or self.ctx.rank == 0:
            h.own.append(bytes(frame))
            return
        from fedml_tpu.parallel.prefetch import AsyncValue

        prev = h.pending

        def _ship(prev=prev, frame=frame):
            if prev is not None:
                prev.result()
            self._sock.settimeout(max(0.001,
                                      h.deadline - time.monotonic()))
            try:
                _send_frame(self._sock, frame)
            except (socket.timeout, ConnectionError, OSError) as e:
                raise DeadRankError(
                    f"multihost gather #{h.seq}: rank {self.ctx.rank} "
                    f"could not ship a carry frame to the rank-0 "
                    f"coordinator ({type(e).__name__})") from e
            self.bytes_sent += len(frame) + 8

        h.pending = AsyncValue(_ship, name=f"gather_push#{h.seq}")

    def gather_finish(self, h: _GatherHandle) -> list[bytes]:
        """Complete the collective: returns the rank-ordered list of
        per-rank payloads (each rank's frames concatenated in push
        order) — the same shape allgather returns."""
        ctx = self.ctx
        if ctx.world <= 1:
            return [b"".join(h.own)]
        if h.pushed != h.n_frames:
            raise ValueError(
                f"multihost gather #{h.seq}: {h.pushed} frames pushed "
                f"but {h.n_frames} promised — the collective would "
                f"hang every peer")
        if ctx.rank == 0:
            # rank 0 "arrives" when its own frames are all pushed and
            # it enters the finish — the collector stamps each peer
            t_own = time.monotonic()
            parts: list[bytes] = [b""] * ctx.world
            parts[0] = b"".join(h.own)
            frames, arrivals = (h.collector.result()
                                if h.collector is not None
                                else ({r: [] for r in self._peers}, {}))
            arrivals[0] = t_own
            _cluster.note_barrier("gather", h.seq, self.round_hint,
                                  arrivals)
            for r, fl in frames.items():
                parts[r] = b"".join(fl)
            blob = struct.pack("<I", ctx.world) + b"".join(
                struct.pack("<Q", len(p)) + p for p in parts)
            for r in sorted(self._peers):
                try:
                    self._peers[r].settimeout(
                        max(0.001, h.deadline - time.monotonic()))
                    _send_frame(self._peers[r], blob)
                except (socket.timeout, ConnectionError, OSError) as e:
                    raise DeadRankError(
                        f"multihost gather #{h.seq}: broadcast to rank "
                        f"{r} failed ({type(e).__name__}: rank died "
                        f"after contributing)") from e
                self.bytes_sent += len(blob) + 8
            return parts
        if h.pending is not None:
            h.pending.result()           # drain the push tail first
        self._sock.settimeout(max(0.001, h.deadline - time.monotonic()))
        try:
            blob = _recv_frame(self._sock)
        except (socket.timeout, ConnectionError, OSError) as e:
            raise DeadRankError(
                f"multihost gather #{h.seq}: rank {ctx.rank} got no "
                f"broadcast from the rank-0 coordinator "
                f"({type(e).__name__}: coordinator dead, or a peer "
                f"stalled it)") from e
        self.bytes_received += len(blob)
        (world,) = struct.unpack_from("<I", blob, 0)
        off, parts = 4, []
        for _ in range(world):
            (n,) = struct.unpack_from("<Q", blob, off)
            off += 8
            parts.append(blob[off:off + n])
            off += n
        return parts

    def gather_abort(self, h: _GatherHandle) -> None:
        """Invalidate an in-flight gather on the error path: the
        collector exits at its next poll instead of camping on the
        deadline, and the push tail is drained best-effort."""
        h.aborted = True
        for av in (h.pending, h.collector):
            if av is not None:
                try:
                    av.result()
                except Exception:
                    pass

    def export_byte_counters(self) -> None:
        _export_channel_byte_counters(self.ctx.rank, self.bytes_sent,
                                      self.bytes_received)

    def close(self) -> None:
        for s in self._peers.values():
            try:
                s.close()
            except OSError:
                pass
        self._peers.clear()
        for s in (self._sock, self._listener):
            if s is not None:
                try:
                    s.close()
                except OSError:
                    pass
        self._sock = self._listener = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# ---------------------------------------------------------------------------
# topology-independent block sampling
# ---------------------------------------------------------------------------

class BlockCohortSampler:
    """Per-block cohort sampling over fixed population ranges — the
    sampling half of the bitwise anchor.

    The population [0, C) splits into `n_blocks` contiguous ranges (the
    PR-10 registry/shardstore id-range partition, applied to the
    cohort); block b draws `k_per_block` clients without replacement
    from ITS range on a private `default_rng([seed, round, block])`
    stream.  Every quantity is a pure function of (seed, round, block)
    — NOT of which process computes it — so any topology tiling the
    same blocks samples the same cohort (and the draw is
    background-thread-safe: no global-RNG reseed, the PR-10 lesson)."""

    def __init__(self, population: int, n_blocks: int, k_per_block: int,
                 seed: int):
        if n_blocks < 1:
            raise ValueError(f"n_blocks must be >= 1, got {n_blocks}")
        if population % n_blocks:
            raise ValueError(
                f"population ({population}) must divide evenly into "
                f"{n_blocks} blocks (the id-range partition must be "
                f"topology-independent)")
        self.population = int(population)
        self.n_blocks = int(n_blocks)
        self.range_size = population // n_blocks
        if not 1 <= k_per_block <= self.range_size:
            raise ValueError(
                f"k_per_block ({k_per_block}) must be in [1, "
                f"{self.range_size}] (each block samples within its "
                f"{self.range_size}-client range)")
        self.k_per_block = int(k_per_block)
        self.seed = int(seed)

    def sample_block(self, round_idx: int, block: int) -> np.ndarray:
        """Global client ids of block `block`'s round-`round_idx`
        cohort, sorted ascending (a canonical order so every topology
        builds the identical cohort stack)."""
        if not 0 <= block < self.n_blocks:
            raise ValueError(f"block {block} outside [0, "
                             f"{self.n_blocks})")
        lo = block * self.range_size
        if self.k_per_block == self.range_size:
            return np.arange(lo, lo + self.range_size, dtype=np.int64)
        rng = np.random.default_rng(
            [self.seed, int(round_idx), int(block)])
        ids = rng.choice(self.range_size, size=self.k_per_block,
                         replace=False)
        return np.sort(ids).astype(np.int64) + lo


def fold_block_partials(parts: dict[int, np.ndarray],
                        n_blocks: int) -> np.ndarray:
    """THE deterministic inter-host reduction: left-fold the per-block
    f32 partials in GLOBAL BLOCK ORDER.  Identical on every host and
    for every topology that produced the same blocks — float addition
    is not associative, so the fold order is the contract (never
    tree-reduce here without changing the bitwise anchor)."""
    missing = [b for b in range(n_blocks) if b not in parts]
    if missing:
        raise DeadRankError(
            f"two-level fold: block partial(s) {missing} missing from "
            f"the allgather (owning rank dead mid-round?)")
    total = np.array(parts[0], dtype=np.float32, copy=True)
    for b in range(1, n_blocks):
        total += np.asarray(parts[b], dtype=np.float32)
    return total


def fold_sparse_partials(pairs: dict[int, tuple], n_blocks: int,
                         dim: int) -> np.ndarray:
    """Sparse twin of ``fold_block_partials`` (ISSUE 19): scatter-add
    each block's (idx, vals) pairs into the flat f32 carry IN GLOBAL
    BLOCK ORDER, never densifying a per-block vector.  Per element the
    additions arrive in exactly the block order the dense left-fold
    uses, so replica agreement holds for the same reason: every host
    folds identical wire bytes with identical ops."""
    missing = [b for b in range(n_blocks) if b not in pairs]
    if missing:
        raise DeadRankError(
            f"two-level fold: block partial(s) {missing} missing from "
            f"the allgather (owning rank dead mid-round?)")
    total = np.zeros(int(dim), dtype=np.float32)
    for b in range(n_blocks):
        idx, vals = pairs[b]
        # top-k indices are unique within a block, so fancy-index +=
        # is a well-defined scatter-add
        total[idx] += np.asarray(vals, dtype=np.float32)
    return total


# ---------------------------------------------------------------------------
# elastic membership (ISSUE 14) — epoch-numbered views, heartbeats,
# deterministic block re-adoption, rejoin
# ---------------------------------------------------------------------------

def _send_msg(sock: socket.socket, mtype: str, header: dict,
              payload: bytes = b"") -> int:
    """One elastic-protocol message: length-framed [u32 hdr-len][JSON
    header incl. "t" type][payload].  Returns bytes on the wire."""
    hdr = json.dumps({"t": mtype, **header}, sort_keys=True).encode()
    frame = struct.pack("<I", len(hdr)) + hdr + payload
    _send_frame(sock, frame)
    return len(frame) + 8


def _recv_msg(sock: socket.socket) -> tuple[str, dict, bytes, int]:
    frame = _recv_frame(sock)
    (n,) = struct.unpack_from("<I", frame, 0)
    hdr = json.loads(frame[4:4 + n].decode())
    return hdr.pop("t"), hdr, frame[4 + n:], len(frame) + 8


@dataclasses.dataclass(frozen=True)
class ClusterView:
    """One epoch of elastic membership: the sorted live ranks and THE
    deterministic item→owner map.  `n_items` is the fixed block space
    (the reduction tree's shape — NEVER repartitioned); only ownership
    moves.  owner_of is a pure function of (members, n_items), so every
    rank that knows the member list derives the identical partition —
    no assignment table crosses the wire beyond the member list.  With
    the full initial membership it reduces to the PR-13 contiguous
    tiling (rank r owns blocks [r·B/W, (r+1)·B/W))."""
    epoch: int
    members: tuple
    n_items: int

    def owner_of(self, item: int) -> int:
        if not 0 <= item < self.n_items:
            raise ValueError(f"item {item} outside [0, {self.n_items})")
        return self.members[item * len(self.members) // self.n_items]

    def assigned(self, rank: int) -> tuple:
        return tuple(i for i in range(self.n_items)
                     if self.owner_of(i) == rank)


class ElasticChannel:
    """Epoch-numbered elastic cluster membership over the HostChannel's
    star topology (ISSUE 14).  Rank 0 coordinates: it owns the member
    list, detects death (data-link EOF, bounded waits, AND heartbeats —
    a SIGSTOP'd rank stops heartbeating and is suspected within
    `hb_timeout_s`, between allgathers, not only inside one), drives
    view changes, and admits rejoiners at commit barriers.

    The collective is `exchange(round, parts, compute)`: a block-keyed
    allgather.  Every item (block) is a pure function of (seed, round,
    block) — NOT of who computes it — so when a rank dies mid-round the
    coordinator re-asks the survivors for exactly the missing items
    (`need` lists in VIEW messages, ownership from ClusterView.owner_of
    over the shrunk membership) and the round completes with the SAME
    folded bytes as a clean run: bitwise survival by construction.

    Wire roles (every connection's first frame is a typed hello):
    "data" (CONTRIB/VIEW/RESULT), "hb" (periodic heartbeats), "rejoin"
    (config-digest-checked admission: REJECTed by name on mismatch,
    SNAPSHOT {epoch, resume_round, members} + model blob at the next
    commit barrier otherwise).  Rank-0 death stays fatal by design —
    the coordinator is the single failure observer, exactly the
    HostChannel contract; workers name it in DeadRankError.

    Fail-fast (`HostChannel`) remains the default transport; this class
    is opt-in via `--elastic` / MultihostRunner's elastic twin."""

    def __init__(self, ctx: MultihostContext, *, n_items: int,
                 config_digest: str = "",
                 timeout_s: float = 120.0,
                 connect_timeout_s: float = 60.0,
                 hb_interval_s: float = 0.25,
                 hb_timeout_s: float = 2.0,
                 rejoin: bool = False):
        if n_items < 1:
            raise ValueError(f"n_items must be >= 1, got {n_items}")
        self.ctx = ctx
        self.n_items = int(n_items)
        self.config_digest = str(config_digest)
        self.timeout_s = float(timeout_s)
        self.connect_timeout_s = float(connect_timeout_s)
        self.hb_interval_s = float(hb_interval_s)
        self.hb_timeout_s = float(hb_timeout_s)
        self.bytes_sent = 0
        self.bytes_received = 0
        self._mark = (0, 0)
        self.view = ClusterView(0, tuple(range(ctx.world)), self.n_items)
        self.view_events: list[dict] = []
        self.hb_paused = False          # fault-injection hook: a paused
        #                                 sender emulates a hung (SIGSTOP)
        #                                 rank without stopping the process
        self._item_nbytes: Optional[int] = None
        self._lock = threading.Lock()
        # byte counters are bumped from the exchange thread AND the
        # accept/heartbeat handler threads — a bare += would lose
        # updates; a dedicated lock (never held across I/O waits)
        # keeps the accounting exact without deadlock exposure
        self._io_lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._closed = False
        self._sock: Optional[socket.socket] = None        # worker data
        self._hb_sock: Optional[socket.socket] = None     # worker hb
        self._listener: Optional[socket.socket] = None
        self._data: dict[int, socket.socket] = {}         # coord tables
        self._hb: dict[int, socket.socket] = {}
        self._hb_last: dict[int, float] = {}
        self._suspect: dict[int, str] = {}
        self._pending_rejoin: list[tuple[int, socket.socket]] = []
        host, port = ctx.coordinator.rsplit(":", 1)
        self._host, self._port = host, int(port)
        _cluster.set_role(ctx.rank, ctx.world, elastic=True)
        if ctx.world <= 1:
            return
        if ctx.rank == 0:
            # coordinated incident dumps (ISSUE 17): the observatory's
            # throttled chokepoint fans out through this channel's
            # typed DUMP frames (telemetry-gated — obs-off wire clean)
            _cluster.set_dump_broadcaster(self._broadcast_dump_frames)
            grace = time.monotonic() + self.connect_timeout_s
            for m in self.view.members:
                if m != 0:
                    self._hb_last[m] = grace   # future-dated connect grace
            self._listener = socket.create_server((host, self._port))
            self._listener.settimeout(0.25)
            threading.Thread(target=self._accept_loop, daemon=True,
                             name="elastic-accept").start()
        elif not rejoin:
            self._connect_worker()
        # rejoin=True defers ALL dialing to rejoin_handshake()

    # -- byte-counted message wrappers ---------------------------------------
    def _send(self, sock, mtype, header, payload=b"") -> None:
        n = _send_msg(sock, mtype, header, payload)
        with self._io_lock:
            self.bytes_sent += n

    def _recv(self, sock):
        mtype, hdr, payload, n = _recv_msg(sock)
        with self._io_lock:
            self.bytes_received += n
        return mtype, hdr, payload

    # -- per-round wire accounting -------------------------------------------
    def mark_round(self) -> None:
        """Open a per-round wire window (ISSUE 16 satellite) — same
        contract as HostChannel.mark_round, under the io lock because
        the heartbeat/accept threads bump the counters concurrently."""
        with self._io_lock:
            self._mark = (self.bytes_sent, self.bytes_received)

    def round_wire_delta(self) -> dict[str, int]:
        with self._io_lock:
            s0, r0 = self._mark
            return {"sent": self.bytes_sent - s0,
                    "received": self.bytes_received - r0}

    # -- worker side ---------------------------------------------------------
    def _connect_worker(self) -> None:
        ctx = self.ctx
        deadline = time.monotonic() + self.connect_timeout_s
        self._sock = _dial_with_backoff(
            self._host, self._port, deadline,
            f"elastic channel: rank {ctx.rank} data link to the "
            f"coordinator at {ctx.coordinator}")
        self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._send(self._sock, "hello",
                   {"rank": ctx.rank, "role": "data",
                    "digest": self.config_digest})
        self._sock.settimeout(max(0.1, deadline - time.monotonic()))
        try:
            mtype, hdr, _ = self._recv(self._sock)
        except (socket.timeout, ConnectionError, OSError) as e:
            raise DeadRankError(
                f"elastic channel: rank {ctx.rank} got no hello reply "
                f"from the coordinator within "
                f"{self.connect_timeout_s:.0f}s "
                f"({type(e).__name__})") from e
        if mtype == "reject":
            raise DeadRankError(hdr.get("error", "rejected"))
        self._install_view(hdr)
        self._hb_sock = _dial_with_backoff(
            self._host, self._port, deadline,
            f"elastic channel: rank {ctx.rank} heartbeat link to the "
            f"coordinator at {ctx.coordinator}")
        self._send(self._hb_sock, "hello",
                   {"rank": ctx.rank, "role": "hb"})
        threading.Thread(target=self._hb_loop, daemon=True,
                         name=f"elastic-hb-{ctx.rank}").start()

    def _hb_loop(self) -> None:
        while not self._closed:
            if not self.hb_paused:
                # live telemetry plane (ISSUE 17): piggyback a bounded
                # metrics delta on the heartbeat header.  With
                # telemetry off the header stays exactly {} — the
                # obs-off heartbeat bytes are byte-identical.
                hdr = {}
                if _cluster.telemetry_enabled():
                    d = _piggyback_delta()
                    if d is not None:
                        hdr["delta"] = d
                try:
                    self._send(self._hb_sock, "hb", hdr)
                except OSError:
                    return      # coordinator gone: the data path names it
            time.sleep(self.hb_interval_s)

    def _install_view(self, hdr: dict) -> None:
        v = ClusterView(int(hdr["epoch"]),
                        tuple(int(m) for m in hdr["members"]),
                        self.n_items)
        if v.epoch < self.view.epoch:
            return                       # stale (reordered) view
        if v.epoch > self.view.epoch:
            self.view_events.append({"epoch": v.epoch,
                                     "members": list(v.members)})
            obs.counter("multihost_view_changes_total").inc()
        self.view = v
        obs.gauge("multihost_epoch", rank=str(self.ctx.rank)).set(
            float(v.epoch))

    # -- coordinator side ----------------------------------------------------
    def _accept_loop(self) -> None:
        while not self._closed:
            try:
                conn, _ = self._listener.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            threading.Thread(target=self._handle_hello, args=(conn,),
                             daemon=True).start()

    def _handle_hello(self, conn: socket.socket) -> None:
        try:
            conn.settimeout(10.0)
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            mtype, hdr, _ = self._recv(conn)
        except (socket.timeout, ConnectionError, OSError, ValueError):
            conn.close()
            return
        rank = int(hdr.get("rank", -1))
        role = hdr.get("role", mtype)
        if mtype == "rejoin" or role == "rejoin":
            self._handle_rejoin_hello(rank, hdr, conn)
            return
        if mtype != "hello" or rank < 0:
            conn.close()
            return
        if role == "data":
            if hdr.get("digest", "") != self.config_digest:
                try:
                    self._send(conn, "reject", {"error": (
                        f"elastic channel: rank {rank} config digest "
                        f"{hdr.get('digest', '')!r} does not match the "
                        f"cluster's {self.config_digest!r} — the "
                        f"two-level reduction would not be bitwise")})
                except OSError:
                    pass
                conn.close()
                return
            with self._cond:
                old = self._data.pop(rank, None)
                self._data[rank] = conn
                self._hb_last[rank] = max(
                    self._hb_last.get(rank, 0.0), time.monotonic())
                view = self.view
                self._cond.notify_all()
            if old is not None:
                try:
                    old.close()
                except OSError:
                    pass
            try:
                self._send(conn, "hello_ok",
                           {"epoch": view.epoch,
                            "members": list(view.members),
                            "n_items": self.n_items})
            except OSError:
                pass
        elif role == "hb":
            with self._lock:
                old = self._hb.pop(rank, None)
                self._hb[rank] = conn
                self._hb_last[rank] = max(
                    self._hb_last.get(rank, 0.0), time.monotonic())
            if old is not None:
                try:
                    old.close()
                except OSError:
                    pass
            self._hb_reader(rank, conn)
        else:
            conn.close()

    def _handle_rejoin_hello(self, rank: int, hdr: dict,
                             conn: socket.socket) -> None:
        """Digest-check NOW (a stale build must be named immediately),
        queue for admission at the next commit barrier otherwise."""
        digest = hdr.get("digest", "")
        if digest != self.config_digest:
            try:
                self._send(conn, "reject", {"error": (
                    f"elastic rejoin: rank {rank} config digest "
                    f"{digest!r} does not match the cluster's "
                    f"{self.config_digest!r} — stale config/code; "
                    f"admission refused")})
            except OSError:
                pass
            conn.close()
            obs.counter("multihost_rejoins_rejected_total").inc()
            return
        with self._lock:
            self._pending_rejoin.append((rank, conn))
        obs.instant("multihost.rejoin_request", rank=rank)
        log.info("elastic: rank %d requested rejoin (pending admission "
                 "at the next commit barrier)", rank)

    def _hb_reader(self, rank: int, conn: socket.socket) -> None:
        conn.settimeout(self.hb_timeout_s)
        while not self._closed:
            try:
                mtype, hdr, _ = self._recv(conn)   # byte-counted
                with self._lock:
                    self._hb_last[rank] = time.monotonic()
                    self._suspect.pop(rank, None)
                _cluster.note_heartbeat(rank)
                delta = (hdr.get("delta") if mtype == "hb" else None)
                if delta:
                    _cluster.fold_remote(rank, delta)
            except socket.timeout:
                with self._lock:
                    fresh = (rank in self.view.members
                             and rank not in self._suspect)
                    if fresh:
                        self._suspect[rank] = (
                            f"no heartbeat for {self.hb_timeout_s:.1f}s "
                            f"(process hung or stopped)")
                if fresh:
                    obs.instant("multihost.rank_suspect", rank=rank)
                    obs.counter("multihost_rank_suspects_total",
                                rank=str(rank)).inc()
                    log.warning("elastic: rank %d heartbeat silent — "
                                "suspected hung", rank)
            except (ConnectionError, OSError, ValueError):
                with self._lock:
                    if rank in self.view.members:
                        self._suspect.setdefault(
                            rank, "heartbeat link closed")
                return

    def wait_members(self) -> None:
        """Rank 0, setup barrier: wait for every initial member's data
        link within connect_timeout_s; ranks that never connect are
        EVICTED (epoch bump, loudly) instead of failing the launch —
        the elastic contract from the very first round."""
        if self.ctx.rank != 0 or self.ctx.world <= 1:
            return
        deadline = time.monotonic() + self.connect_timeout_s
        with self._cond:
            while time.monotonic() < deadline:
                missing = [m for m in self.view.members
                           if m != 0 and m not in self._data]
                if not missing:
                    return
                self._cond.wait(0.1)
            missing = [m for m in self.view.members
                       if m != 0 and m not in self._data]
        if missing:
            log.warning("elastic setup: rank(s) %s never connected "
                        "within %.0fs — evicting and starting without "
                        "them", missing, self.connect_timeout_s)
            self._coord_view_change(missing, -1, None, None,
                                    reason="never connected at setup")

    def _coord_view_change(self, dead: list, round_idx: int,
                           have: Optional[dict], compute,
                           reason: str = "dead or hung") -> None:
        """THE view change: evict `dead`, bump the epoch, notify every
        surviving member (VIEW message carrying the member list + the
        missing items that member now owns), then adopt rank 0's own
        newly-owned missing items.  Latency is measured to the point
        every survivor has been re-tasked — the recompute itself is
        goodput, not membership latency."""
        t0 = time.perf_counter()
        dead = sorted(set(int(r) for r in dead))
        with obs.span("multihost.view_change",
                      epoch=self.view.epoch + 1, round=round_idx):
            with self._lock:
                for r in dead:
                    self._suspect.pop(r, None)
                    for tbl in (self._data, self._hb):
                        s = tbl.pop(r, None)
                        if s is not None:
                            try:
                                s.close()
                            except OSError:
                                pass
                members = tuple(m for m in self.view.members
                                if m not in dead)
                self.view = ClusterView(self.view.epoch + 1, members,
                                        self.n_items)
                view = self.view
                socks = dict(self._data)
            for r in dead:
                obs.counter("multihost_rank_deaths_total",
                            rank=str(r)).inc()
            obs.counter("multihost_view_changes_total").inc()
            obs.gauge("multihost_epoch", rank="0").set(float(view.epoch))
            missing = ([] if have is None else
                       [b for b in range(self.n_items) if b not in have])
            for m in view.members:
                if m == 0 or m not in socks:
                    continue
                need = [b for b in missing if view.owner_of(b) == m]
                try:
                    socks[m].settimeout(self.timeout_s)
                    self._send(socks[m], "view",
                               {"epoch": view.epoch, "round": round_idx,
                                "members": list(view.members),
                                "need": need})
                except (socket.timeout, OSError):
                    with self._lock:
                        self._suspect.setdefault(
                            m, "view notification failed")
        latency = time.perf_counter() - t0
        obs.histogram("multihost_view_change_seconds").observe(latency)
        self.view_events.append({
            "epoch": view.epoch, "round": round_idx, "dead": dead,
            "members": list(view.members), "latency_s": latency,
            "reason": reason})
        log.warning("elastic view change: epoch %d, rank(s) %s evicted "
                    "(%s), members now %s (%.1f ms)", view.epoch, dead,
                    reason, list(view.members), latency * 1e3)
        # coordinated incident dump (ISSUE 17): every survivor snapshots
        # the same incident window (throttled; no-op with telemetry off)
        _cluster.maybe_coordinated_dump(
            f"view_change:epoch{view.epoch}:dead{dead}")
        # rank 0's own re-adoption (outside the latency window: this is
        # recompute goodput, the survivors are already re-tasked)
        if have is not None and compute is not None:
            mine = [b for b in missing if view.owner_of(b) == 0]
            if mine:
                have.update({int(b): bytes(v)
                             for b, v in compute(mine).items()})

    # -- the elastic collective ----------------------------------------------
    def _note_items(self, values) -> None:
        for v in values:
            n = len(v)
            if self._item_nbytes is None:
                self._item_nbytes = n
            elif n != self._item_nbytes:
                raise ValueError(
                    f"elastic exchange: item payload of {n} bytes, "
                    f"expected {self._item_nbytes} (config skew or a "
                    f"truncated frame)")

    def contrib_begin(self, round_idx: int) -> _ContribHandle:
        """Open an early-contribution window for `round_idx` (the
        overlap path): blocks pushed through contrib_push ship while
        the remaining blocks still compute, and exchange(pending=h)
        closes the window."""
        return _ContribHandle(round_idx)

    def contrib_push(self, h: _ContribHandle, block: int,
                     data: bytes) -> None:
        """Ship one block's payload into an open window.  Rank 0
        stashes (its blocks never cross the wire); workers chain a
        single-block contrib send onto the previous push so socket
        writes serialize while the caller computes the next block.  A
        death mid-window surfaces at the exchange() join — the round's
        re-adoption then runs against the frozen carry via `compute`,
        never against this stale buffer."""
        data = bytes(data)
        self._note_items([data])
        h.blocks.append(int(block))
        if self.ctx.world <= 1 or self.ctx.rank == 0:
            h.stash[int(block)] = data
            return
        from fedml_tpu.parallel.prefetch import AsyncValue

        prev = h.pending

        def _ship(prev=prev, block=int(block), data=data):
            if prev is not None:
                prev.result()
            self._send_contrib(h.round_idx, {block: data})

        h.pending = AsyncValue(_ship,
                               name=f"contrib_push#{h.round_idx}")

    def exchange(self, round_idx: int, parts: dict,
                 compute: Optional[Callable] = None,
                 pending: Optional[_ContribHandle] = None
                 ) -> tuple[dict, ClusterView]:
        """The block-keyed elastic allgather: contribute `parts`
        ({item: f32 bytes/ndarray}), receive ALL n_items item payloads
        plus the view that completed the round.  `compute(items)` is
        the re-adoption callback — invoked when a view change
        re-assigns a dead rank's missing items to this rank mid-round.
        `pending` closes an overlap window opened by contrib_begin:
        its pushes are drained (worker) or merged into `parts` (rank
        0) before the collective proper.  Every rank receives the
        identical payload set, so any deterministic fold over it
        (fold_block_partials) commits the same bits on every
        survivor."""
        t0 = time.perf_counter()
        parts = {int(b): (v.tobytes() if hasattr(v, "tobytes")
                          else bytes(v))
                 for b, v in parts.items()}
        self._note_items(parts.values())
        pre_sent: tuple = ()
        if pending is not None:
            if pending.round_idx != round_idx:
                raise ValueError(
                    f"elastic exchange round {round_idx}: pending "
                    f"contributions belong to round "
                    f"{pending.round_idx}")
            if self.ctx.rank == 0 or self.ctx.world <= 1:
                parts = {**pending.stash, **parts}
            else:
                if pending.pending is not None:
                    pending.pending.result()   # DeadRankError re-raises
                pre_sent = tuple(pending.blocks)
        try:
            if self.ctx.rank == 0:
                return self._exchange_coord(round_idx, parts, compute)
            return self._exchange_worker(round_idx, parts, compute,
                                         pre_sent)
        finally:
            obs.histogram("multihost_allgather_seconds").observe(
                time.perf_counter() - t0)

    def _exchange_coord(self, round_idx, parts, compute):
        have: dict[int, bytes] = dict(parts)
        # barrier ledger (ISSUE 17): rank 0 arrives with its own parts
        # in hand; each member arrives at its first accepted contrib
        # for THIS round.  Dead ranks never arrive and stay absent.
        arrivals: dict[int, float] = {self.ctx.rank: time.monotonic()}
        deadline = time.monotonic() + self.timeout_s
        while True:
            missing = [b for b in range(self.n_items) if b not in have]
            if not missing:
                break
            # rank 0's own outstanding items first (covers world==1 and
            # re-adoption immediately after a view change)
            mine = [b for b in missing if self.view.owner_of(b) == 0]
            if mine:
                if compute is None:
                    raise DeadRankError(
                        f"elastic exchange #{round_idx}: items {mine} "
                        f"fell to rank 0 but no compute callback was "
                        f"given")
                got = {int(b): bytes(v)
                       for b, v in compute(mine).items()}
                self._note_items(got.values())
                have.update(got)
                continue
            now = time.monotonic()
            with self._lock:
                dead = set(self._suspect)
                hb_stale = [m for m in self.view.members
                            if m != 0
                            and now - self._hb_last.get(m, now)
                            > self.hb_timeout_s]
                socks = dict(self._data)
            dead |= set(hb_stale)
            dead &= set(self.view.members) - {0}
            if now > deadline:
                # whoever still owes an item at the deadline is hung
                dead |= {self.view.owner_of(b) for b in missing} - {0}
            if dead:
                self._coord_view_change(sorted(dead), round_idx, have,
                                        compute)
                # the re-tasked survivors legitimately need fresh time
                # to recompute the dead rank's blocks — without this, a
                # view change late in the window would cascade into
                # false evictions of healthy, still-computing ranks
                deadline = max(deadline,
                               time.monotonic() + self.timeout_s)
                continue
            rl: list = []
            waitable = [s for m, s in socks.items()
                        if m in self.view.members]
            if waitable:
                try:
                    rl, _, _ = select.select(waitable, [], [], 0.1)
                except (OSError, ValueError):
                    rl = []     # a sock closed under us: re-snapshot
            else:
                time.sleep(0.05)
            for s in rl:
                m = next((r for r, c in socks.items() if c is s), None)
                if m is None:
                    continue
                try:
                    s.settimeout(max(0.05, min(5.0,
                                               deadline - now)))
                    mtype, hdr, payload = self._recv(s)
                except (socket.timeout, ConnectionError, OSError,
                        ValueError):
                    with self._lock:
                        self._suspect.setdefault(m, "data link failed")
                    continue
                if mtype != "contrib":
                    continue
                if int(hdr.get("round", -1)) != round_idx:
                    log.warning("elastic: dropping stale contrib for "
                                "round %s from rank %d (at round %d)",
                                hdr.get("round"), m, round_idx)
                    continue
                arrivals.setdefault(m, time.monotonic())
                blocks = [int(b) for b in hdr.get("blocks", [])]
                if self._item_nbytes is None and blocks:
                    self._item_nbytes = len(payload) // len(blocks)
                sz = self._item_nbytes or 0
                if sz * len(blocks) != len(payload):
                    with self._lock:
                        self._suspect.setdefault(
                            m, f"contrib size mismatch "
                               f"({len(payload)} bytes for "
                               f"{len(blocks)} items of {sz})")
                    continue
                for j, b in enumerate(blocks):
                    if 0 <= b < self.n_items and b not in have:
                        have[b] = payload[j * sz:(j + 1) * sz]
        _cluster.note_barrier("exchange", round_idx, round_idx,
                              arrivals)
        # broadcast the complete, identically-ordered payload set
        blob = b"".join(have[b] for b in range(self.n_items))
        view = self.view
        with self._lock:
            socks = dict(self._data)
        for m in view.members:
            if m == 0 or m not in socks:
                continue
            try:
                socks[m].settimeout(self.timeout_s)
                self._send(socks[m], "result",
                           {"epoch": view.epoch, "round": round_idx,
                            "members": list(view.members)},
                           blob)
            except (socket.timeout, OSError):
                with self._lock:
                    self._suspect.setdefault(m, "result send failed")
        return have, view

    def _exchange_worker(self, round_idx, parts, compute,
                         pre_sent: tuple = ()):
        sent = set(parts) | set(pre_sent)
        if parts or not pre_sent:
            # an all-early overlap round has nothing left to contribute
            # inline; everything else keeps the eager single contrib
            self._send_contrib(round_idx, parts)
        deadline = time.monotonic() + self.timeout_s
        while True:
            self._sock.settimeout(
                max(0.05, deadline - time.monotonic()))
            try:
                mtype, hdr, payload = self._recv(self._sock)
            except (socket.timeout, ConnectionError, OSError,
                    ValueError) as e:
                raise DeadRankError(
                    f"elastic exchange round {round_idx}: rank "
                    f"{self.ctx.rank} lost the rank-0 coordinator "
                    f"({type(e).__name__}: coordinator dead, or this "
                    f"rank was evicted from the view)") from e
            if mtype == "view":
                self._install_view(hdr)
                # a view change re-tasks the survivors: the round
                # legitimately runs longer than one clean window
                deadline = max(deadline,
                               time.monotonic() + self.timeout_s)
                need = [int(b) for b in hdr.get("need", [])
                        if int(b) not in sent]
                if need and compute is not None:
                    out = {int(b): bytes(v)
                           for b, v in compute(need).items()}
                    self._send_contrib(round_idx, out)
                    sent |= set(out)
            elif mtype == "result":
                if int(hdr.get("round", -1)) != round_idx:
                    continue             # stale (already-consumed) round
                self._install_view(hdr)
                sz = len(payload) // self.n_items
                if sz * self.n_items != len(payload):
                    raise DeadRankError(
                        f"elastic exchange round {round_idx}: result "
                        f"payload of {len(payload)} bytes does not "
                        f"tile {self.n_items} items")
                return ({b: payload[b * sz:(b + 1) * sz]
                         for b in range(self.n_items)}, self.view)
            elif mtype == "dump":
                # coordinated incident dump (ISSUE 17): the coordinator
                # saw a view change / death / SLO breach — snapshot the
                # same window into THIS rank's obs dir (no-op when obs
                # is off)
                obs.dump_flight(
                    "coordinated:" + str(hdr.get("reason", "")))
            # other message types: ignore

    def _send_contrib(self, round_idx: int,
                      parts: dict[int, bytes]) -> None:
        blocks = sorted(parts)
        try:
            self._sock.settimeout(self.timeout_s)
            self._send(self._sock, "contrib",
                       {"epoch": self.view.epoch, "round": round_idx,
                        "blocks": blocks},
                       b"".join(parts[b] for b in blocks))
        except (socket.timeout, ConnectionError, OSError) as e:
            raise DeadRankError(
                f"elastic exchange round {round_idx}: rank "
                f"{self.ctx.rank} could not ship its contribution to "
                f"the coordinator ({type(e).__name__})") from e

    # -- rejoin --------------------------------------------------------------
    def rejoin_handshake(self) -> tuple[bytes, int, str]:
        """Restarted-worker entry: dial the coordinator's rejoin role,
        present the config digest, await admission (granted at the next
        commit barrier) — returns (snapshot payload, resume_round,
        run_tag) and leaves the channel fully connected (data +
        heartbeat links) under the new membership.  `run_tag` names
        WHICH run the snapshot belongs to (a worker driving several
        sequential runs over one channel — mh_worker's residency modes
        — must resume the run the coordinator is actually in, not
        whichever it would have started first)."""
        ctx = self.ctx
        deadline = time.monotonic() + self.connect_timeout_s
        sock = _dial_with_backoff(
            self._host, self._port, deadline,
            f"elastic rejoin: rank {ctx.rank} dialing the coordinator "
            f"at {ctx.coordinator}")
        try:
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            self._send(sock, "rejoin",
                       {"rank": ctx.rank,
                        "digest": self.config_digest})
            # admission lands at a commit barrier: budget a full round
            # on top of the connect window
            sock.settimeout(self.timeout_s + self.connect_timeout_s)
            try:
                mtype, hdr, payload = self._recv(sock)
            except (socket.timeout, ConnectionError, OSError) as e:
                raise DeadRankError(
                    f"elastic rejoin: rank {ctx.rank} got no admission "
                    f"from the coordinator within "
                    f"{self.timeout_s + self.connect_timeout_s:.0f}s "
                    f"({type(e).__name__}: run finished or coordinator "
                    f"dead)") from e
            if mtype == "reject":
                raise DeadRankError(hdr.get("error", "rejoin rejected"))
            if mtype != "snapshot":
                raise DeadRankError(
                    f"elastic rejoin: unexpected {mtype!r} reply")
        finally:
            sock.close()
        self._install_view(hdr)
        self._connect_worker()
        log.info("elastic: rank %d readmitted at epoch %d, resuming "
                 "run %r at round %d", ctx.rank, self.view.epoch,
                 hdr.get("tag", ""), int(hdr["resume_round"]))
        return payload, int(hdr["resume_round"]), hdr.get("tag", "")

    def admit_rejoins(self, resume_round: int,
                      snapshot_fn: Callable[[], bytes],
                      tag: str = "") -> list:
        """Rank 0, at a commit barrier: admit every pending rejoiner —
        epoch bump, SNAPSHOT reply (view + resume round + the model
        blob snapshot_fn builds), VIEW notification to the incumbents.
        Returns the admitted ranks."""
        if self.ctx.rank != 0:
            return []
        with self._lock:
            pending, self._pending_rejoin = self._pending_rejoin, []
        if not pending:
            return []
        blob = snapshot_fn()
        admitted = []
        for rank, conn in pending:
            if rank in self.view.members:
                try:
                    self._send(conn, "reject", {"error": (
                        f"elastic rejoin: rank {rank} is still a live "
                        f"member of epoch {self.view.epoch} — a rank id "
                        f"cannot be claimed twice")})
                except OSError:
                    pass
                conn.close()
                continue
            members = tuple(sorted(set(self.view.members) | {rank}))
            view = ClusterView(self.view.epoch + 1, members,
                               self.n_items)
            try:
                conn.settimeout(self.timeout_s)
                self._send(conn, "snapshot",
                           {"epoch": view.epoch,
                            "resume_round": int(resume_round),
                            "members": list(members),
                            "n_items": self.n_items,
                            "tag": tag},
                           blob)
            except (socket.timeout, OSError):
                conn.close()
                log.warning("elastic: rejoiner rank %d vanished before "
                            "its snapshot was delivered", rank)
                continue
            conn.close()
            with self._lock:
                self.view = view
                # connect grace for the fresh data/hb links
                self._hb_last[rank] = (time.monotonic()
                                       + self.connect_timeout_s)
                self._suspect.pop(rank, None)
            admitted.append(rank)
            obs.counter("multihost_rejoins_admitted_total").inc()
            obs.gauge("multihost_epoch", rank="0").set(float(view.epoch))
            obs.counter("multihost_view_changes_total").inc()
            self.view_events.append({
                "epoch": view.epoch, "round": int(resume_round),
                "rejoined": [rank], "members": list(members),
                "latency_s": 0.0, "reason": "rejoin admitted"})
            log.warning("elastic: rank %d readmitted at epoch %d "
                        "(resume round %d)", rank, view.epoch,
                        resume_round)
        if admitted:
            with self._lock:
                socks = dict(self._data)
            for m in self.view.members:
                if m == 0 or m in admitted or m not in socks:
                    continue
                try:
                    socks[m].settimeout(self.timeout_s)
                    self._send(socks[m], "view",
                               {"epoch": self.view.epoch,
                                "round": int(resume_round),
                                "members": list(self.view.members),
                                "need": []})
                except (socket.timeout, OSError):
                    with self._lock:
                        self._suspect.setdefault(
                            m, "view notification failed")
        return admitted

    def _broadcast_dump_frames(self, reason: str) -> None:
        """Fan a coordinated-dump order out to every surviving member's
        data link (registered with the observatory as the DUMP
        broadcaster at construction).  Best-effort: a member that died
        between the snapshot and the send is already being handled by
        the failure detector."""
        with self._lock:
            socks = {m: s for m, s in self._data.items()
                     if m in self.view.members}
        for m, s in socks.items():
            try:
                self._send(s, "dump", {"reason": str(reason)})
            except OSError:
                pass

    # -- plumbing shared with HostChannel ------------------------------------
    def export_byte_counters(self) -> None:
        _export_channel_byte_counters(self.ctx.rank, self.bytes_sent,
                                      self.bytes_received)

    def close(self) -> None:
        self._closed = True
        if self.ctx.rank == 0:
            _cluster.set_dump_broadcaster(None)
        with self._lock:
            socks = (list(self._data.values()) + list(self._hb.values())
                     + [c for _, c in self._pending_rejoin])
            self._data.clear()
            self._hb.clear()
            self._pending_rejoin.clear()
        for s in socks + [self._sock, self._hb_sock, self._listener]:
            if s is not None:
                try:
                    s.close()
                except OSError:
                    pass
        self._sock = self._hb_sock = self._listener = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# ---------------------------------------------------------------------------
# the two-level round loop
# ---------------------------------------------------------------------------

# per-PROCESS metrics-rollup baseline: (registry identity, prev state).
# Keyed on the registry object so obs.reset() (tests) naturally resets
# the baseline with it.  The heartbeat thread's live piggyback (ISSUE
# 17) and the end-of-run rollup advance the SAME baseline — their
# shipped windows are disjoint, so rank 0 never double-counts — which
# is why a lock guards the read-modify-write.
_rollup_state: Optional[tuple] = None
_rollup_lock = threading.Lock()


def _delta_since_last_rollup() -> dict:
    global _rollup_state
    with _rollup_lock:
        reg = obs.registry()
        prev = (_rollup_state[1]
                if _rollup_state is not None and _rollup_state[0] is reg
                else None)
        delta, state = reg.delta_snapshot(prev)
        _rollup_state = (reg, state)
        return delta


def _piggyback_delta(
        cap_bytes: int = _cluster.SIDECAR_CAP_BYTES) -> Optional[dict]:
    """Bounded per-beat metrics delta for the live telemetry plane.
    Advances the rollup baseline ONLY when something ships: an empty
    delta returns None, and a delta over the frame budget returns None
    WITHOUT advancing — it rides a later beat or the final rollup
    instead of bloating a control frame."""
    global _rollup_state
    with _rollup_lock:
        reg = obs.registry()
        prev = (_rollup_state[1]
                if _rollup_state is not None and _rollup_state[0] is reg
                else None)
        delta, state = reg.delta_snapshot(prev)
        if not delta.get("metrics"):
            return None
        if len(json.dumps(delta, sort_keys=True).encode()) > cap_bytes:
            return None
        _rollup_state = (reg, state)
        return delta


class MultihostRunner:
    """Two-level multihost round loop over a FedAvg-family mesh engine.

    Per round, on every process:

      1. sample: `BlockCohortSampler` draws each block's cohort from its
         population range — pure function of (seed, round, block);
      2. partial (ICI tier): for each OWNED block (contiguous tiling:
         rank r owns blocks [r·B/W, (r+1)·B/W)), gather+upload the
         block cohort (host-sharded data: only this process's blocks
         cross H2D; double-buffered per-host prefetch on the streaming
         path) and run the engine's `{family}_twolevel` partial program
         — chunk-scanned local training + intra-host psum on the LOCAL
         mesh, returning the flat f32 carry;
      3. allreduce (DCN tier): `HostChannel.allgather` of the owned
         partials, then EVERY process folds all B partials in global
         block order (`fold_block_partials`);
      4. commit: the replicated `twolevel_commit` program divides and
         applies the server update identically on every process.

    Bitwise anchor: with a fixed `n_blocks`, same-seed runs at ANY
    process count that tiles the blocks commit identical bits (the
    2-vs-1-process pin in tests/test_multihost_spmd.py).  Resident
    mode uploads only this process's population range to device;
    streaming mode uploads only its blocks' cohorts per round —
    nothing population-sized crosses process boundaries either way."""

    def __init__(self, engine, ctx: Optional[MultihostContext] = None,
                 *, n_blocks: Optional[int] = None,
                 channel: Optional[HostChannel] = None,
                 timeout_s: float = 120.0,
                 carry_codec: str = "f32",
                 carry_chunk: Optional[int] = None,
                 overlap_exchange: bool = False,
                 on_round_end: Optional[Callable[[int], None]] = None):
        from fedml_tpu.parallel.engine import MeshFedAvgEngine
        from fedml_tpu.parallel.hierarchical import MeshHierarchicalEngine
        if (not isinstance(engine, MeshFedAvgEngine)
                or isinstance(engine, MeshHierarchicalEngine)):
            # hierarchical subclasses the FedAvg engine but its rounds
            # are group_comm_round-structured — folding its sums flat
            # here would SILENTLY compute plain FedAvg instead (its
            # multihost story is the silo-per-host mesh above)
            raise ValueError(
                f"MultihostRunner drives the flat FedAvg-family mesh "
                f"engines, not {type(engine).__name__}")
        if engine.stream_block is not None:
            raise ValueError(
                "MultihostRunner does not drive block-streamed rounds "
                "yet: stream WITHIN a host via smaller blocks, or use "
                "streaming mode (per-block cohorts already bound device "
                "memory by O(block))")
        if getattr(engine, "defense", "norm_clip") not in ("norm_clip",):
            raise ValueError(
                f"two-level aggregation is linear: order-statistic "
                f"defense {engine.defense!r} cannot fold across hosts "
                f"(its [K, P] matrix needs every client row)")
        # the engine's mesh must be process-local: the cross-host tier
        # is the HostChannel, never an in-program collective
        for d in engine.mesh.devices.flat:
            if d.process_index != jax.process_index():
                raise ValueError(
                    "MultihostRunner needs a LOCAL mesh (build the "
                    "engine with make_local_mesh()): device "
                    f"{d} belongs to process {d.process_index}")
        self.engine = engine
        self.ctx = ctx if ctx is not None else (
            MultihostContext.from_env() or MultihostContext.single())
        self.timeout_s = float(timeout_s)
        self.on_round_end = on_round_end
        world = self.ctx.world
        self.n_blocks = int(n_blocks) if n_blocks else world
        if self.n_blocks % world:
            raise ValueError(
                f"n_blocks ({self.n_blocks}) must be a multiple of the "
                f"process count ({world}) — contiguous tiling is the "
                f"bitwise contract")
        cfg = engine.cfg
        if cfg.client_num_per_round % self.n_blocks:
            raise ValueError(
                f"client_num_per_round ({cfg.client_num_per_round}) "
                f"must divide evenly into {self.n_blocks} blocks")
        self.sampler = BlockCohortSampler(
            engine.data.client_num, self.n_blocks,
            cfg.client_num_per_round // self.n_blocks, cfg.seed)
        bpp = self.n_blocks // world
        self.owned_blocks = tuple(range(self.ctx.rank * bpp,
                                        (self.ctx.rank + 1) * bpp))
        # this process's population id range (contiguous because its
        # blocks are) — the resident device stack holds ONLY this slice
        self.range_lo = self.owned_blocks[0] * self.sampler.range_size
        self.range_hi = ((self.owned_blocks[-1] + 1)
                         * self.sampler.range_size)
        self._channel = channel
        self._owns_channel = channel is None
        self._range_stack = None
        self._range_stack_w = None
        self._prefetched = None
        from fedml_tpu.parallel.carry_codec import (DEFAULT_CHUNK,
                                                    make_carry_codec)
        self.codec = make_carry_codec(
            carry_codec,
            chunk=DEFAULT_CHUNK if carry_chunk is None else carry_chunk)
        self.overlap_exchange = bool(overlap_exchange)
        self.round_walls: list[float] = []
        self.carry_bytes: list[int] = []
        self.carry_wire_sent: list[int] = []
        self.carry_raw: list[int] = []       # f32 bytes before encoding
        self.carry_payload: list[int] = []   # encoded payload bytes
        self.overlap_waits: list[float] = []
        self.exchange_walls: list[float] = []
        engine._ensure_twolevel()

    # -- setup ---------------------------------------------------------------
    @property
    def channel(self) -> HostChannel:
        if self._channel is None:
            self._channel = HostChannel(self.ctx,
                                        timeout_s=self.timeout_s)
        return self._channel

    def _config_doc(self) -> bytes:
        """The canonical cross-rank config document: the quantities the
        bitwise contract depends on.  Fail-fast mode allgathers it
        (_handshake); elastic mode hellos/rejoins carry its md5 as the
        cluster config digest."""
        eng = self.engine
        return json.dumps({
            "n_blocks": self.n_blocks,
            "k_per_block": self.sampler.k_per_block,
            "population": self.sampler.population,
            "n_shards": eng.n_shards,
            "chunk": eng.chunk,
            "seed": eng.cfg.seed,
            "family": eng.program_family,
            "streaming": bool(eng.streaming),
            # the carry codec shapes every wire payload — a mixed-codec
            # cluster must be NAMED at handshake, not discovered as a
            # size mismatch mid-round
            "carry_codec": self.codec.name,
            "carry_chunk": self.codec.chunk,
        }, sort_keys=True).encode()

    def _handshake(self) -> None:
        """Cross-rank config agreement: the bitwise contract only holds
        when every process runs the identical partition and programs —
        a mismatch names the ranks instead of silently diverging."""
        doc = self._config_doc()
        docs = self.channel.allgather(doc, timeout_s=self.timeout_s)
        for r, d in enumerate(docs):
            if d != docs[0]:
                raise RuntimeError(
                    f"multihost config mismatch: rank {r} runs "
                    f"{d.decode()!r} vs rank 0's {docs[0].decode()!r} — "
                    f"the two-level reduction would not be bitwise")

    # -- per-round pieces ----------------------------------------------------
    def _block_inputs(self, round_idx: int, block: int, train_rng):
        """(global ids, wmask, crngs) for one block — all pure functions
        of (seed, round, block)."""
        from fedml_tpu.parallel.engine import pad_ids
        ids, wmask = pad_ids(self.sampler.sample_block(round_idx, block),
                             self.engine.n_shards)
        block_rng = jax.random.fold_in(train_rng, block)
        crngs = np.asarray(jax.random.split(block_rng, len(ids)))
        return ids, wmask, crngs

    def _upload_id_range(self, lo: int, hi: int) -> tuple:
        """Slice the host client stack to [lo, hi), cast/pad, and
        upload it sharded over the local mesh — THE one resident
        upload body (the contiguous whole-range stack and the elastic
        per-block stacks both go through here, so cast/pad/byte
        accounting can never diverge)."""
        from fedml_tpu.parallel.mesh import (client_sharding, pad_cohort,
                                             shard_stack)
        eng = self.engine
        shards = {k: np.asarray(v)[lo:hi]
                  for k, v in eng._host_shards().items()}
        weights = np.asarray(eng.data.client_num_samples,
                             np.float32)[lo:hi]
        shards, weights = pad_cohort(eng._cast_stack_x(shards), weights,
                                     eng.n_shards)
        eng.transfer_stats.add_h2d_bytes(
            sum(np.asarray(v).nbytes for v in shards.values())
            + weights.nbytes)
        stack = shard_stack(eng.mesh, shards)
        stack_w = jax.device_put(weights.astype(np.float32),
                                 client_sharding(eng.mesh))
        return stack, stack_w

    def _upload_range_stack(self):
        """Resident mode: upload THIS process's population id range
        once, sharded over the local mesh (device residency is
        id-range-partitioned across hosts — the registry/shardstore
        partition, applied to HBM)."""
        if self._range_stack is not None:
            return self._range_stack, self._range_stack_w
        self._range_stack, self._range_stack_w = self._upload_id_range(
            self.range_lo, self.range_hi)
        return self._range_stack, self._range_stack_w

    def _gather_streaming(self, round_idx: int, train_rng):
        """Host-gather + upload every OWNED block's cohort (the per-host
        input pipeline; runs on the prefetch thread when pipelined)."""
        out = []
        for b in self.owned_blocks:
            ids, wmask, crngs = self._block_inputs(round_idx, b,
                                                   train_rng)
            cohort, weights = self.engine._stream_gather(ids, wmask)
            out.append((b, cohort, weights, crngs))
        return out

    def _partials_resident(self, variables, round_idx: int, train_rng):
        eng = self.engine
        stack, stack_w = self._upload_range_stack()
        parts = {}
        for b in self.owned_blocks:
            ids, wmask, crngs = self._block_inputs(round_idx, b,
                                                   train_rng)
            local_ids = ids - self.range_lo
            flat = eng._twolevel_partial_resident(
                variables, stack, stack_w, jax.numpy.asarray(local_ids),
                jax.numpy.asarray(wmask), jax.numpy.asarray(crngs))
            parts[b] = np.asarray(flat, dtype=np.float32)
        return parts

    def _streaming_blocks(self, round_idx: int, train_rng, rng_base,
                          rounds: int) -> list:
        """The streaming input head with the per-host double-buffered
        prefetch: consume round r's gathered blocks (from the prefetch
        thread when pipelined), schedule round r+1's gather+upload
        (parallel/prefetch.py AsyncValue — the engines' own pipeline,
        reused per host)."""
        from fedml_tpu.parallel.prefetch import AsyncValue
        eng = self.engine
        pre = self._prefetched
        if pre is not None and pre[0] == round_idx:
            blocks = pre[1].result()
        else:
            if pre is not None:
                try:
                    pre[1].result()
                except Exception:
                    log.warning("discarding failed stale multihost "
                                "prefetch for round %d", pre[0],
                                exc_info=True)
            blocks = self._gather_streaming(round_idx, train_rng)
        self._prefetched = None
        if eng.prefetch and round_idx + 1 < rounds:
            nxt_rng = jax.random.split(
                jax.random.fold_in(rng_base, round_idx + 1))[0]
            self._prefetched = (
                round_idx + 1,
                AsyncValue(self._gather_streaming, round_idx + 1,
                           nxt_rng, stats=eng.transfer_stats))
        return blocks

    def _partials_streaming(self, variables, round_idx: int, train_rng,
                            rng_base, rounds: int):
        eng = self.engine
        parts = {}
        for b, cohort, weights, crngs in self._streaming_blocks(
                round_idx, train_rng, rng_base, rounds):
            flat = eng._twolevel_partial(variables, cohort, weights,
                                         jax.numpy.asarray(crngs))
            parts[b] = np.asarray(flat, dtype=np.float32)
        return parts

    def _iter_partials(self, variables, round_idx: int, train_rng,
                       rng_base, rounds: int):
        """Per-block partial stream for the overlapped exchange: yields
        (block, f32 vector) in owned-block order, so each block's carry
        can ship while the next one computes."""
        eng = self.engine
        if eng.streaming:
            for b, cohort, weights, crngs in self._streaming_blocks(
                    round_idx, train_rng, rng_base, rounds):
                flat = eng._twolevel_partial(variables, cohort, weights,
                                             jax.numpy.asarray(crngs))
                yield b, np.asarray(flat, dtype=np.float32)
            return
        stack, stack_w = self._upload_range_stack()
        for b in self.owned_blocks:
            ids, wmask, crngs = self._block_inputs(round_idx, b,
                                                   train_rng)
            local_ids = ids - self.range_lo
            flat = eng._twolevel_partial_resident(
                variables, stack, stack_w, jax.numpy.asarray(local_ids),
                jax.numpy.asarray(wmask), jax.numpy.asarray(crngs))
            yield b, np.asarray(flat, dtype=np.float32)

    # -- codec plumbing ------------------------------------------------------
    def _encode_block(self, block: int, vec: np.ndarray) -> bytes:
        with obs.span("multihost.encode_carry", codec=self.codec.name,
                      block=block):
            data = self.codec.encode(block, vec)
        self._round_raw += vec.size * 4
        self._round_payload += len(data)
        return data

    def _finish_round_bytes(self) -> None:
        """Close this round's byte accounting: payload-level raw/wire
        into the codec counters + the channel-measured wire deltas (the
        ISSUE-16 satellite: the ratio the bench judges is what the
        channel moved)."""
        _account_carry(self._round_raw, self._round_payload)
        self.carry_raw.append(self._round_raw)
        self.carry_payload.append(self._round_payload)
        d = self.channel.round_wire_delta()
        self.carry_bytes.append(d["received"])
        self.carry_wire_sent.append(d["sent"])

    def _fold_docs(self, docs: list, dim: int) -> np.ndarray:
        """Decode every rank's payload through the codec and fold in
        global block order — decode is deterministic f64 math, so all
        ranks fold identical f32 partials from identical wire bytes."""
        world = self.ctx.world
        bpp = self.n_blocks // world
        enb = self.codec.encoded_nbytes(dim)
        all_parts: dict[int, np.ndarray] = {}
        for r, doc in enumerate(docs):
            if len(doc) != bpp * enb:
                raise DeadRankError(
                    f"two-level allreduce: rank {r} shipped "
                    f"{len(doc)} bytes, expected {bpp * enb} "
                    f"({bpp} blocks x {enb} B {self.codec.name} "
                    f"carry) — config skew or a truncated frame")
            for j in range(bpp):
                all_parts[r * bpp + j] = doc[j * enb:(j + 1) * enb]
        return self._decode_fold(all_parts)

    def _decode_fold(self, bufs: dict) -> np.ndarray:
        """Decode per-block wire payloads and fold: dense codecs decode
        then left-fold; sparse codecs scatter-add (idx, vals) pairs in
        the SAME global block order (ISSUE 19) without densifying a
        per-block vector.  The f32 path is untouched — the bitwise
        anchors ride fold_block_partials exactly as before."""
        if getattr(self.codec, "sparse", False):
            if hasattr(self.codec, "integrate"):
                # stateful sparse (topk_ef): every rank advances every
                # block's reconstruction mirror on the same wire bytes
                # — the delta frames integrate into dense per-block
                # reconstructions, then the dense left-fold keeps the
                # block-order contract
                return fold_block_partials(
                    {int(b): self.codec.integrate(int(b), bytes(v))
                     for b, v in bufs.items()}, self.n_blocks)
            pairs, dim = {}, 0
            for b, v in bufs.items():
                dim, idx, vals = self.codec.decode_pairs(bytes(v))
                pairs[int(b)] = (idx, vals)
            return fold_sparse_partials(pairs, self.n_blocks, dim)
        return fold_block_partials(
            {int(b): self.codec.decode(bytes(v))
             for b, v in bufs.items()}, self.n_blocks)

    def carry_state(self) -> dict:
        """The codec's residual state (error-feedback accumulators):
        ship it as FedCheckpointManager extra_state so crash-resume
        continues the same compression-error trajectory."""
        return self.codec.state_dict()

    def load_carry_state(self, state: Optional[dict]) -> None:
        self.codec.load_state_dict(state or {})

    def _round_exchange(self, variables, round_idx: int, train_rng,
                        rng_base, rounds: int) -> np.ndarray:
        """One round's partials + inter-host carry allreduce, returning
        the folded carry.  Serial path: compute everything, then one
        blocking allgather of the encoded payload.  Overlapped path
        (--overlap_exchange): open a pipelined gather and push each
        block's encoded carry as it materializes, so the DCN transfer
        rides under the remaining blocks' compute; only the final
        gather_finish is visible wait (the multihost.overlap_wait
        span).  Both paths move identical bytes in identical order —
        the f32 escape hatch stays bitwise under overlap."""
        ch = self.channel
        ch.mark_round()
        self._round_raw = self._round_payload = 0
        w0 = time.perf_counter()
        if self.overlap_exchange and self.ctx.world > 1:
            h = ch.gather_begin(len(self.owned_blocks),
                                timeout_s=self.timeout_s)
            dim = 0
            try:
                for b, vec in self._iter_partials(
                        variables, round_idx, train_rng, rng_base,
                        rounds):
                    dim = vec.size
                    ch.gather_push(h, self._encode_block(b, vec))
                with obs.span("multihost.overlap_wait",
                              round=round_idx):
                    t0 = time.perf_counter()
                    docs = ch.gather_finish(h)
                    wait = time.perf_counter() - t0
            except Exception:
                ch.gather_abort(h)
                raise
            self.overlap_waits.append(wait)
            self.exchange_walls.append(time.perf_counter() - w0)
        else:
            if self.engine.streaming:
                parts = self._partials_streaming(
                    variables, round_idx, train_rng, rng_base, rounds)
            else:
                parts = self._partials_resident(variables, round_idx,
                                                train_rng)
            dim = next(iter(parts.values())).size
            payload = b"".join(self._encode_block(b, parts[b])
                               for b in sorted(parts))
            with obs.span("multihost.allreduce", round=round_idx):
                t0 = time.perf_counter()
                docs = ch.allgather(payload, timeout_s=self.timeout_s)
                wait = time.perf_counter() - t0
            # the whole exchange is visible wait on the serial path, so
            # overlap_fraction reports an honest ~0 (InlineFetcher's
            # convention)
            self.overlap_waits.append(wait)
            self.exchange_walls.append(wait)
        self._finish_round_bytes()
        return self._fold_docs(docs, dim)

    # -- the loop ------------------------------------------------------------
    def run(self, variables=None, rounds: Optional[int] = None,
            logger=None):
        """Drive `rounds` two-level rounds; returns the trained
        variables (identical bits on every process).  Only rank 0
        appends metrics_history/logs — peers compute the same values
        anyway."""
        eng = self.engine
        cfg = eng.cfg
        rounds = rounds if rounds is not None else cfg.comm_round
        if variables is None:
            variables = eng.init_variables()
        variables = eng._prepare_variables(variables)
        server_state = eng._prepare_server_state(
            eng.server_init(variables))
        rng_base = jax.random.PRNGKey(cfg.seed + 1)
        self._handshake()
        try:
            for round_idx in range(rounds):
                t0 = time.perf_counter()
                round_rng = jax.random.fold_in(rng_base, round_idx)
                train_rng, agg_rng = jax.random.split(round_rng)
                with obs.span("round.twolevel", round=round_idx,
                              rank=self.ctx.rank,
                              blocks=len(self.owned_blocks)):
                    self.channel.round_hint = round_idx
                    total = self._round_exchange(variables, round_idx,
                                                 train_rng, rng_base,
                                                 rounds)
                    variables, server_state, m = eng._twolevel_commit(
                        variables, server_state,
                        jax.numpy.asarray(total), agg_rng)
                jax.block_until_ready(variables)
                obs.counter("multihost_rounds_committed_total",
                            rank=str(self.ctx.rank)).inc()
                self.round_walls.append(time.perf_counter() - t0)
                self.channel.export_byte_counters()
                if self.ctx.rank == 0 and (
                        round_idx % cfg.frequency_of_the_test == 0
                        or round_idx == rounds - 1):
                    stats = eng.evaluate(variables)
                    stats.update(round=round_idx,
                                 train_loss=float(m["train_loss"]),
                                 round_time=self.round_walls[-1])
                    eng.metrics_history.append(stats)
                    if logger is not None:
                        logger.log(stats, step=round_idx)
                    log.info("round %d: %s", round_idx, stats)
                if self.on_round_end is not None:
                    self.on_round_end(round_idx)
        except Exception as e:
            obs.dump_flight(f"multihost_error:rank{self.ctx.rank}: "
                            f"{e!r}")
            raise
        finally:
            pre, self._prefetched = self._prefetched, None
            if pre is not None:
                try:
                    pre[1].result()
                except Exception:
                    pass
        self._rollup_metrics()
        return variables

    def _rollup_metrics(self) -> None:
        """Ship every rank's metric deltas to rank 0 and fold them under
        origin="host<i>" (the PR-7 remote-fold shape): an N-process run
        keeps per-process series instead of last-writer-wins gauges,
        and programs.report() gains its per-process breakdown rows from
        exactly these merged series.  The shipped delta is SINCE THE
        LAST ROLLUP in this process (baseline threaded like the PR-7
        uplink piggyback), so back-to-back runners — mh_worker's
        streaming-then-resident pair — don't re-ship and double-count
        the earlier run's counters."""
        if self.ctx.world <= 1:
            return
        try:
            self.channel.round_hint = None   # ledger: not a round barrier
            delta = _delta_since_last_rollup()
            docs = self.channel.allgather(
                json.dumps(delta).encode(), timeout_s=self.timeout_s)
            if self.ctx.rank == 0:
                for r, doc in enumerate(docs):
                    if r == 0 or not doc:
                        continue
                    obs.registry().merge_delta(json.loads(doc.decode()),
                                               origin=f"host{r}")
        except DeadRankError:
            raise
        except Exception:
            log.warning("multihost metrics rollup failed", exc_info=True)

    def report(self, warmup_rounds: int = 0) -> dict:
        """Timing/byte rollup over the rounds run so far (warmup rounds
        excluded from the rate)."""
        walls = self.round_walls[warmup_rounds:]
        carry = self.carry_bytes[warmup_rounds:] or [0]
        sent = self.carry_wire_sent[warmup_rounds:] or [0]
        raw = self.carry_raw[warmup_rounds:]
        payload = self.carry_payload[warmup_rounds:]
        waits = self.overlap_waits[warmup_rounds:]
        ewalls = self.exchange_walls[warmup_rounds:]
        return {
            "rank": self.ctx.rank,
            "world": self.ctx.world,
            "n_blocks": self.n_blocks,
            "rounds": len(self.round_walls),
            "rounds_per_sec": (len(walls) / sum(walls)
                               if walls and sum(walls) > 0 else 0.0),
            "round_wall_p50_s": (float(np.median(walls))
                                 if walls else 0.0),
            "carry_allreduce_bytes_per_round": float(np.mean(carry)),
            # sum of the per-round deltas, NOT channel.bytes_received:
            # the channel also carries handshake/rollup frames and (in
            # mh_worker) a sibling runner's traffic
            "carry_allreduce_bytes_total": int(sum(self.carry_bytes)),
            # -- compressed tier (ISSUE 16) --
            "carry_codec": self.codec.name,
            "carry_raw_bytes_per_round": (float(np.mean(raw))
                                          if raw else 0.0),
            "carry_payload_bytes_per_round": (float(np.mean(payload))
                                              if payload else 0.0),
            # payload-level ratio: deterministic per (codec, dim); the
            # channel-measured per-round deltas above price the framing
            "carry_compression_ratio": (sum(raw) / sum(payload)
                                        if sum(payload) else 1.0),
            "carry_wire_sent_bytes_per_round": float(np.mean(sent)),
            # fraction of the exchange window (first partial shipped →
            # folded carry ready) NOT spent blocking the round loop:
            # ~0 on the serial path, > 0 when --overlap_exchange hides
            # the DCN transfer behind block compute
            "overlap_fraction": (max(0.0, 1.0 - sum(waits)
                                     / sum(ewalls))
                                 if ewalls and sum(ewalls) > 0
                                 else 0.0),
        }

    def close(self) -> None:
        if self._channel is not None and self._owns_channel:
            self._channel.close()
            self._channel = None


class ElasticRunner(MultihostRunner):
    """Elastic twin of the two-level round loop (ISSUE 14): the same
    sample→partial→allreduce→commit structure, but the inter-host tier
    rides an ElasticChannel — a dead or hung rank triggers a view
    change, its blocks are re-adopted by the survivors mid-round, and a
    restarted process re-enters through the rejoin handshake (config
    digest + a rank-0 model snapshot at the commit barrier).

    Bitwise anchor under death, by construction: `BlockCohortSampler`
    draws on [seed, round, block] streams and every partial is a pure
    function of (variables, seed, round, block), so a re-adopted
    block's partial is byte-identical to the one the dead rank would
    have shipped; `fold_block_partials` folds ALL blocks in global
    block order regardless of who computed them — a run that loses a
    rank commits the same bits as the clean same-partition run
    (tests/test_multihost_spmd.py's elastic kill pin).

    Differences from the fail-fast runner, deliberate: resident mode
    caches PER-BLOCK device stacks (ownership is dynamic, so the
    contiguous whole-range stack no longer exists — every block's
    stack/gather compiles one shape, identical on every survivor set);
    the streaming path gathers synchronously (cross-round prefetch
    assumes static ownership); and the end-of-run metrics rollup is
    skipped (membership may change under it).  Fail-fast stays the
    default — this runner is opt-in via cli --elastic."""

    def __init__(self, engine, ctx: Optional[MultihostContext] = None,
                 *, n_blocks: Optional[int] = None,
                 channel: Optional[ElasticChannel] = None,
                 timeout_s: float = 120.0,
                 connect_timeout_s: float = 60.0,
                 hb_interval_s: float = 0.25,
                 hb_timeout_s: float = 2.0,
                 run_tag: str = "run",
                 carry_codec: str = "f32",
                 carry_chunk: Optional[int] = None,
                 overlap_exchange: bool = False,
                 on_round_end: Optional[Callable[[int], None]] = None):
        if channel is not None and not isinstance(channel,
                                                  ElasticChannel):
            raise ValueError(
                f"ElasticRunner needs an ElasticChannel (got "
                f"{type(channel).__name__}); use MultihostRunner for "
                f"the fail-fast HostChannel")
        super().__init__(engine, ctx, n_blocks=n_blocks,
                         channel=channel, timeout_s=timeout_s,
                         carry_codec=carry_codec,
                         carry_chunk=carry_chunk,
                         overlap_exchange=overlap_exchange,
                         on_round_end=on_round_end)
        self.connect_timeout_s = float(connect_timeout_s)
        self.hb_interval_s = float(hb_interval_s)
        self.hb_timeout_s = float(hb_timeout_s)
        self.run_tag = str(run_tag)
        if channel is not None and channel.n_items != self.n_blocks:
            raise ValueError(
                f"channel n_items ({channel.n_items}) != n_blocks "
                f"({self.n_blocks}) — the block space is the reduction "
                f"tree and must agree")
        self._block_stacks: dict[int, tuple] = {}
        self._round_ctx: Optional[tuple] = None

    @property
    def channel(self) -> ElasticChannel:
        if self._channel is None:
            self._channel = ElasticChannel(
                self.ctx, n_items=self.n_blocks,
                config_digest=self.config_digest(),
                timeout_s=self.timeout_s,
                connect_timeout_s=self.connect_timeout_s,
                hb_interval_s=self.hb_interval_s,
                hb_timeout_s=self.hb_timeout_s,
                rejoin=os.environ.get("FEDML_MH_REJOIN") == "1")
        return self._channel

    def config_digest(self) -> str:
        return hashlib.md5(self._config_doc()).hexdigest()

    # -- per-block partials (ownership-agnostic) -----------------------------
    def _block_stack(self, b: int) -> tuple:
        """Resident mode, one block's device stack (cached): uniform
        [range_size→pad(n_shards)] shape for EVERY block, so any
        survivor adopting any block dispatches the same compiled
        program — and re-adoption costs one H2D upload, not a
        recompile."""
        hit = self._block_stacks.get(b)
        if hit is not None:
            return hit
        rs = self.sampler.range_size
        self._block_stacks[b] = self._upload_id_range(b * rs,
                                                      (b + 1) * rs)
        return self._block_stacks[b]

    def _compute_partials(self, variables, round_idx: int, train_rng,
                          blocks) -> dict[int, np.ndarray]:
        eng = self.engine
        parts: dict[int, np.ndarray] = {}
        for b in blocks:
            ids, wmask, crngs = self._block_inputs(round_idx, b,
                                                   train_rng)
            if eng.streaming:
                cohort, weights = eng._stream_gather(ids, wmask)
                flat = eng._twolevel_partial(variables, cohort, weights,
                                             jax.numpy.asarray(crngs))
            else:
                stack, stack_w = self._block_stack(b)
                local_ids = ids - b * self.sampler.range_size
                flat = eng._twolevel_partial_resident(
                    variables, stack, stack_w,
                    jax.numpy.asarray(local_ids),
                    jax.numpy.asarray(wmask), jax.numpy.asarray(crngs))
            parts[int(b)] = np.asarray(flat, dtype=np.float32)
        return parts

    def _readopt_compute(self, blocks) -> dict[int, bytes]:
        """The mid-round re-adoption callback the channel invokes on a
        view change: recompute the named blocks against THIS round's
        frozen (variables, train_rng) — pure functions, so the bytes
        match what the dead rank would have shipped."""
        if self._round_ctx is None:
            raise RuntimeError("re-adoption requested outside a round")
        variables, train_rng, round_idx = self._round_ctx
        with obs.span("multihost.readopt", round=round_idx,
                      blocks=len(tuple(blocks))):
            parts = self._compute_partials(variables, round_idx,
                                           train_rng, blocks)
        # re-adopted blocks ship through the SAME codec as owned ones
        # (the channel's uniform-item contract); an int8_ef residual
        # for a freshly adopted block starts at zero — compression
        # error trajectory only, never replica agreement
        return {b: self.codec.encode(int(b), v)
                for b, v in parts.items()}

    def _snapshot_blob(self, resume_round: int, variables,
                       server_state) -> bytes:
        """The rejoin catch-up snapshot: the committed model + server
        state as host numpy trees (byte-exact — the rejoiner must
        re-enter the bitwise contract, not an approximation of it).
        Cluster-internal trust boundary: this rides the same
        coordinator sockets as every carry frame."""
        tree = jax.tree.map(np.asarray, (variables, server_state))
        # stateful-codec state rides the snapshot (ISSUE 19): topk_ef's
        # reconstruction mirror is replicated decode state — a rejoiner
        # folding future rounds from a zero mirror would disagree with
        # every survivor.  (int8_ef residuals are encoder-local; the
        # rejoiner's retain_blocks() drops the coordinator's copies, so
        # shipping them preserves the restart-at-zero convention.)
        return pickle.dumps({"round": int(resume_round), "state": tree,
                             "carry": self.carry_state()},
                            protocol=4)

    # -- the elastic loop ----------------------------------------------------
    def run(self, variables=None, rounds: Optional[int] = None,
            logger=None, rejoin: Optional[bool] = None,
            rejoin_state: Optional[tuple] = None):
        """Drive the elastic two-level loop.  `rejoin=True` (defaulted
        from FEDML_MH_REJOIN — the launcher's respawn sets it) makes
        this process re-enter a running cluster: config-digest
        handshake, model snapshot install, resume at the coordinator's
        commit barrier.  `rejoin_state=(snapshot_blob, resume_round)`
        injects a handshake the caller already performed (mh_worker
        does its own so the SNAPSHOT's run tag can pick which runner to
        resume)."""
        eng = self.engine
        cfg = eng.cfg
        rounds = rounds if rounds is not None else cfg.comm_round
        if rejoin is None:
            rejoin = (os.environ.get("FEDML_MH_REJOIN") == "1"
                      and self.ctx.rank != 0)
        ch = self.channel
        if self.ctx.rank == 0:
            ch.wait_members()
        if rejoin or rejoin_state is not None:
            if rejoin_state is not None:
                blob, resume_round = rejoin_state
            else:
                blob, resume_round, tag = ch.rejoin_handshake()
                if tag and tag != self.run_tag:
                    log.warning(
                        "elastic rejoin: admitted into run %r but this "
                        "runner drives %r — resuming anyway (the "
                        "caller should route on the tag, see "
                        "mh_worker)", tag, self.run_tag)
            payload = pickle.loads(blob)
            variables, server_state = payload["state"]
            variables = eng._prepare_variables(variables)
            server_state = eng._prepare_server_state(server_state)
            # install the coordinator's codec state BEFORE the first
            # fold: a stateful sparse codec's reconstruction mirror
            # must match the survivors' bit-for-bit (ISSUE 19)
            self.load_carry_state(payload.get("carry"))
            start_round = int(payload["round"])
        else:
            if variables is None:
                variables = eng.init_variables()
            variables = eng._prepare_variables(variables)
            server_state = eng._prepare_server_state(
                eng.server_init(variables))
            start_round = 0
        rng_base = jax.random.PRNGKey(cfg.seed + 1)
        try:
            for round_idx in range(start_round, rounds):
                t0 = time.perf_counter()
                round_rng = jax.random.fold_in(rng_base, round_idx)
                train_rng, agg_rng = jax.random.split(round_rng)
                self._round_ctx = (variables, train_rng, round_idx)
                with obs.span("round.twolevel", round=round_idx,
                              rank=self.ctx.rank,
                              epoch=ch.view.epoch, elastic=True):
                    mine = ch.view.assigned(self.ctx.rank)
                    # drop resident stacks for blocks the view no
                    # longer assigns here (e.g. a rejoin returned them
                    # to their original owner) — without eviction,
                    # repeated death/rejoin cycles would converge on
                    # every host holding the WHOLE population in HBM,
                    # defeating the id-range partition
                    for b in list(self._block_stacks):
                        if b not in mine:
                            del self._block_stacks[b]
                    # error-feedback residuals follow ownership too
                    self.codec.retain_blocks(mine)
                    ch.mark_round()
                    self._round_raw = self._round_payload = 0
                    w0 = time.perf_counter()
                    if self.overlap_exchange and self.ctx.world > 1:
                        hnd = ch.contrib_begin(round_idx)
                        for b in mine:
                            part = self._compute_partials(
                                variables, round_idx, train_rng, [b])
                            ch.contrib_push(
                                hnd, b,
                                self._encode_block(b, part[int(b)]))
                        with obs.span("multihost.overlap_wait",
                                      round=round_idx), \
                             obs.span("multihost.allreduce",
                                      round=round_idx):
                            t0 = time.perf_counter()
                            all_parts, _view = ch.exchange(
                                round_idx, {}, self._readopt_compute,
                                pending=hnd)
                            wait = time.perf_counter() - t0
                        self.overlap_waits.append(wait)
                        self.exchange_walls.append(
                            time.perf_counter() - w0)
                    else:
                        parts = self._compute_partials(
                            variables, round_idx, train_rng, mine)
                        enc = {b: self._encode_block(b, v)
                               for b, v in parts.items()}
                        with obs.span("multihost.allreduce",
                                      round=round_idx):
                            t0 = time.perf_counter()
                            all_parts, _view = ch.exchange(
                                round_idx, enc, self._readopt_compute)
                            wait = time.perf_counter() - t0
                        self.overlap_waits.append(wait)
                        self.exchange_walls.append(wait)
                    self._finish_round_bytes()
                    total = self._decode_fold(all_parts)
                    variables, server_state, m = eng._twolevel_commit(
                        variables, server_state,
                        jax.numpy.asarray(total), agg_rng)
                jax.block_until_ready(variables)
                obs.counter("multihost_rounds_committed_total",
                            rank=str(self.ctx.rank)).inc()
                self._round_ctx = None
                self.round_walls.append(time.perf_counter() - t0)
                ch.export_byte_counters()
                if self.ctx.rank == 0:
                    # the commit barrier IS the admission point: the
                    # snapshot ships the just-committed bits
                    ch.admit_rejoins(
                        round_idx + 1,
                        lambda: self._snapshot_blob(
                            round_idx + 1, variables, server_state),
                        tag=self.run_tag)
                if self.ctx.rank == 0 and (
                        round_idx % cfg.frequency_of_the_test == 0
                        or round_idx == rounds - 1):
                    stats = eng.evaluate(variables)
                    stats.update(round=round_idx,
                                 train_loss=float(m["train_loss"]),
                                 round_time=self.round_walls[-1])
                    eng.metrics_history.append(stats)
                    if logger is not None:
                        logger.log(stats, step=round_idx)
                    log.info("round %d: %s", round_idx, stats)
                if self.on_round_end is not None:
                    self.on_round_end(round_idx)
        except Exception as e:
            obs.dump_flight(f"multihost_elastic_error:"
                            f"rank{self.ctx.rank}: {e!r}")
            raise
        finally:
            self._round_ctx = None
        return variables

    def report(self, warmup_rounds: int = 0) -> dict:
        rep = super().report(warmup_rounds)
        ch = self._channel
        events = list(ch.view_events) if ch is not None else []
        lat = [e["latency_s"] for e in events if e.get("latency_s")]
        rep.update({
            "elastic": True,
            "epoch": ch.view.epoch if ch is not None else 0,
            "members": list(ch.view.members) if ch is not None else [],
            "view_changes": len(events),
            "view_change_latency_s": (float(np.mean(lat)) if lat
                                      else 0.0),
            "view_events": events,
        })
        return rep


def variables_digest(variables) -> str:
    """md5 over the raw bytes of every leaf (deterministic leaf order)
    — THE bitwise-equality digest of the multihost pins."""
    h = hashlib.md5()
    for leaf in jax.tree.leaves(variables):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()
