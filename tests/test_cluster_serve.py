"""Fused serving cluster tests (ISSUE 18, fedml_tpu/scale/cluster.py).

The fusion's two invariants, pinned over REAL sockets:

  * world==1 with matched traffic is byte-identical to the pre-fusion
    synthetic path — run_serve_sim's _ServeLane and the reactor-fed
    ClusterServeManager commit the SAME digest when fed the same rows
    in the same per-lane order (the fold never sees socket arrival
    order: uplinks buffer per lane, lanes fold in item order);
  * world==2 with live ingest commits the SAME digest on both ranks —
    the commit-barrier fold is a pure function of the block/lane
    partition, exchanged through ElasticChannel exactly like the
    elastic multihost tier.

Plus the satellite pins: the reactor's overload gate reads lane
saturation (registry pressure reaches the door), and the connswarm
fleet stripes across a multi-target endpoint list with per-target
stats and the burst-cap pacing knob.

Budget: everything here is in-process over loopback sockets except the
single spawned 2-rank smoke at the bottom (the ISSUE-18 tier-1 budget
allows at most ONE spawned-cluster arm).
"""
import json
import sys
import threading
import time

import numpy as np
import pytest

from fedml_tpu.parallel.multihost import (ElasticChannel, MultihostContext,
                                          free_port)
from fedml_tpu.scale.arrivals import ArrivalConfig
from fedml_tpu.scale.cluster import (ClusterServeManager, make_uplink_frame,
                                     run_cluster_serve, send_uplinks)
from fedml_tpu.scale.serve import run_serve_sim


def _feed(port, frames, hold, attempts=200):
    """Retry-dial a reactor endpoint that may not be listening yet and
    stream `frames` down one connection, holding it open on `hold` so
    the server never sees a mid-window disconnect."""
    for _ in range(attempts):
        try:
            send_uplinks("127.0.0.1", port, frames, hold_open=hold)
            return
        except OSError:
            time.sleep(0.05)


def test_world1_socket_path_matches_synthetic_digest():
    """The pre-fusion trace pin: run_serve_sim's synthetic lane and the
    reactor-fed cluster path, given the SAME rows in the same order,
    commit byte-identical variables.  The cluster run gets the rows
    over a real TCP connection — so this also pins that the wire
    (codec + decode pool + admission) is value-preserving end-to-end."""
    COMMITS, K, DIM, SEED, POP = 4, 4, 32, 7, 64
    sim = run_serve_sim(POP, commits=COMMITS, warmup_commits=1,
                        buffer_k=K, row_dim=DIM, seed=SEED,
                        arrival=ArrivalConfig(mode="constant",
                                              rate=1000.0, seed=SEED))
    # the exact row stream _ServeLane generates at banned_frac=0: the
    # 64-row pool is the FIRST draw from rng([seed, 2]), admitted
    # round-robin at weight 1.0 (see scale/serve.py)
    pool = np.random.default_rng([SEED, 2]).standard_normal(
        (64, DIM)).astype(np.float32)
    frames = [make_uplink_frame(pool[i % 64], sender=1, weight=1.0)
              for i in range(COMMITS * K)]
    port = free_port()
    hold = threading.Event()
    th = threading.Thread(target=_feed, args=(port, frames, hold),
                          daemon=True)
    th.start()
    try:
        rep = run_cluster_serve(POP, commits=COMMITS, warmup_commits=1,
                                buffer_k=K, row_dim=DIM, port=port,
                                n_connections=4, ingest_pool=1,
                                window_deadline_s=30.0, timeout_s=60.0,
                                backlog_cap=COMMITS * K)
    finally:
        hold.set()
    th.join(timeout=5)
    assert rep["committed_digest"] == sim["committed_digest"], (
        "world==1 reactor-fed path diverged from the synthetic "
        "pre-fusion trace — the fold saw socket arrival order or the "
        "wire mutated a row")
    assert rep["commits"] == COMMITS
    assert rep["committed_updates"] == COMMITS * K
    assert rep["misrouted"] == 0
    assert rep["lane_overflow_dropped"] == 0


def test_two_rank_live_ingest_digests_agree():
    """Invariant (a) executed: two in-process ranks, each fed DIFFERENT
    rows over its own socket, fold lane partials through a real
    ElasticChannel at every commit barrier and must commit the same
    global bits — the fold order is the block/lane partition, not
    arrival order."""
    COMMITS, K, DIM, SEED, POP, WORLD = 3, 4, 32, 5, 64, 2
    coord = free_port()
    ports = [free_port() for _ in range(WORLD)]
    reports = [None] * WORLD
    errors = []
    hold = threading.Event()
    pool = np.random.default_rng([SEED, 9]).standard_normal(
        (64, DIM)).astype(np.float32)

    def worker(r):
        ctx = MultihostContext(rank=r, world=WORLD,
                               coordinator=f"localhost:{coord}")
        ch = ElasticChannel(ctx, n_items=WORLD, config_digest="t2",
                            timeout_s=60.0, connect_timeout_s=30.0,
                            hb_interval_s=0.1, hb_timeout_s=2.0)
        try:
            reports[r] = run_cluster_serve(
                POP, commits=COMMITS, warmup_commits=1, buffer_k=K,
                row_dim=DIM, port=ports[r], partition=(r, WORLD),
                channel=ch, elastic=True, n_connections=4,
                ingest_pool=1, window_deadline_s=30.0, timeout_s=90.0,
                backlog_cap=COMMITS * K)
        except Exception as e:            # surfaced via the assert below
            errors.append((r, repr(e)))
        finally:
            ch.close()

    def feeder(r):
        frames = [make_uplink_frame(pool[(r * 16 + i) % 64], sender=1)
                  for i in range(COMMITS * K)]
        _feed(ports[r], frames, hold)

    ths = [threading.Thread(target=worker, args=(r,))
           for r in range(WORLD)]
    fds = [threading.Thread(target=feeder, args=(r,), daemon=True)
           for r in range(WORLD)]
    for t in ths + fds:
        t.start()
    for t in ths:
        t.join(timeout=120)
    hold.set()
    assert not errors, errors
    assert all(rep is not None for rep in reports)
    d = [rep["committed_digest"] for rep in reports]
    assert d[0] == d[1], (
        f"cross-rank digest mismatch with live ingest: {d} — the "
        "commit-barrier fold is no longer a pure function of the "
        "partition")
    assert all(rep["commits"] == COMMITS for rep in reports)


def test_overload_gate_reads_lane_saturation():
    """Satellite: registry/lane pressure reaches the reactor's door.
    A lane whose window is full AND whose backlog is at cap flips
    lane_pressure() -> the installed overload gate sheds new
    connections with reason "gate" instead of the backlog dropping."""
    mgr = ClusterServeManager(8, population=16, buffer_k=2, port=free_port(),
                              n_connections=4, ingest_pool=1,
                              backlog_cap=2)
    try:
        rg = getattr(mgr.com_manager, "_rg", None)
        assert rg is not None and rg._overload_gate is not None, (
            "ClusterServeManager must install lane_pressure as the "
            "reactor overload gate")
        assert mgr.lane_pressure() is False
        row = np.ones((8,), np.float32)
        # fill the window (buffer_k=2) then the backlog (cap=2)
        for i in range(4):
            mgr._ingest_row(i, row, 1.0, 0.0)
        lane = mgr._lanes[0]
        assert lane.full() and len(lane.backlog) == 2
        assert lane.saturated() and mgr.lane_pressure() is True
        assert rg._overload_reason(time.monotonic()) == "gate"
        # one more uplink beyond saturation drops at the cap
        mgr._ingest_row(4, row, 1.0, 0.0)
        assert lane.overflow_dropped == 1
        # draining the window (commit) releases the pressure: the
        # backlog refills the fresh window and the cap has room again
        parts = mgr.take_partials()
        assert 0 in parts and parts[0][2] == 2     # folded n == buffer_k
        assert mgr.lane_pressure() is False
    finally:
        mgr.finish()


def test_uplink_during_construction_is_admitted(monkeypatch):
    """The host's own lane exists before its transport can deliver.  The
    base constructor listens and installs the frame sink long before
    ClusterServeManager.__init__ returns, so a peer that dials the
    moment the port answers has rows on the insert path while the
    constructor still runs.  Here the first row arrives AT the moment
    the sink goes in: it is folded into the host's lane, not counted
    `misrouted` and dropped (what made
    test_world1_socket_path_matches_synthetic_digest lose its first rows
    on a loaded machine)."""
    from fedml_tpu.comm.base import BaseCommManager
    install = BaseCommManager.set_frame_sink
    arrived = []

    def install_then_deliver(self, sink):
        install(self, sink)
        mgr = sink.__self__
        assert mgr.hosted_items() == (0,), (
            "the transport delivers before the host has a lane")
        mgr._ingest_row(3, np.ones((8,), np.float32), 1.0, 0)
        arrived.append(mgr)

    monkeypatch.setattr(BaseCommManager, "set_frame_sink",
                        install_then_deliver)
    mgr = ClusterServeManager(8, population=16, buffer_k=2, port=free_port(),
                              n_connections=4, ingest_pool=1)
    try:
        assert arrived == [mgr]
        assert mgr.misrouted == 0
        assert mgr._lanes[0].admitted == 1
        # and the row is in the window the first commit closes
        mgr._ingest_row(4, np.ones((8,), np.float32), 1.0, 0)
        assert mgr.wait_window(5.0) is True
        assert mgr.take_partials()[0][2] == 2
    finally:
        mgr.finish()


def test_connswarm_multi_target_striping():
    """Satellite: the subprocess fleet config grows a multi-target
    list — sender i dials targets[(i-1) % N], stats carry a per_target
    block, and the token-bucket burst cap defaults to the historical
    1 s (the cluster bench tightens it)."""
    from fedml_tpu.comm.connswarm import ConnectionSwarm, SwarmConfig
    cfg = SwarmConfig.from_json(json.dumps({
        "host": "127.0.0.1", "port": 1, "n_connections": 4,
        "offered_rate": 10.0, "duration_s": 0.0,
        "targets": [["127.0.0.1", 1111], ["127.0.0.2", 2222]],
        "arrival": {"mode": "diurnal", "rate": 10.0, "period_s": 60.0},
    }))
    assert cfg.burst_cap_s == 1.0          # historical default
    assert cfg.arrival["mode"] == "diurnal"
    sw = ConnectionSwarm(cfg, frame=b"x")
    assert sw._target_of(1) == ("127.0.0.1", 1111)
    assert sw._target_of(2) == ("127.0.0.2", 2222)
    assert sw._target_of(3) == ("127.0.0.1", 1111)   # stripes, wraps
    pt = sw.stats["per_target"]
    assert set(pt) == {"127.0.0.1:1111", "127.0.0.2:2222"}
    for blk in pt.values():
        assert {"connects", "refused", "frames_sent"} <= set(blk)
    # single-target configs keep the legacy (host, port) shape
    solo = ConnectionSwarm(SwarmConfig(host="127.0.0.1", port=7, n_connections=1,
                             offered_rate=1.0), frame=b"x")
    assert solo._target_of(1) == ("127.0.0.1", 7)


def test_spawned_two_rank_cluster_smoke():
    """THE one spawned-cluster arm in tier-1 (budget: everything else
    in this file is in-process): two mh_worker processes take the
    serve_cluster route, adopt their shard ranges, ingest real frames
    from this process, fold through the elastic channel, and report
    equal digests over stdout JSON."""
    from fedml_tpu.parallel.multihost import spawn_cluster_report
    import tempfile
    WORLD, COMMITS, K, DIM = 2, 3, 4, 32
    ports = [free_port() for _ in range(WORLD)]
    cfg = {"serve_cluster": {
        "population": 256, "commits": COMMITS, "warmup_commits": 1,
        "buffer_k": K, "row_dim": DIM, "connections": 8,
        "ingest_pool": 1, "window_deadline_s": 20.0,
        "timeout_s": 120.0, "ports": ports,
    }, "channel_timeout_s": 120.0, "hb_timeout_s": 2.0,
       "hb_interval_s": 0.25}
    with tempfile.NamedTemporaryFile("w", suffix=".json",
                                     delete=False) as f:
        json.dump(cfg, f)
        path = f.name
    pool = np.random.default_rng(3).standard_normal(
        (64, DIM)).astype(np.float32)
    hold = threading.Event()

    def feeder(r):
        frames = [make_uplink_frame(pool[i % 64], sender=1)
                  for i in range(40)]
        _feed(ports[r], frames, hold, attempts=600)

    fds = [threading.Thread(target=feeder, args=(r,), daemon=True)
           for r in range(WORLD)]
    for t in fds:
        t.start()
    try:
        outs, rep = spawn_cluster_report(
            [sys.executable, "-m", "fedml_tpu.parallel.mh_worker", path],
            WORLD, timeout_s=180.0, elastic=True)
    finally:
        hold.set()
    assert all(r["rc"] == 0 for r in rep["ranks"].values()), rep["ranks"]
    docs = {}
    for r, out in enumerate(outs):
        for line in out.splitlines():
            if line.startswith("{"):
                docs[r] = json.loads(line)
    assert set(docs) == set(range(WORLD))
    d = [docs[r]["serve_cluster"]["committed_digest"]
         for r in range(WORLD)]
    assert d[0] == d[1], f"spawned-cluster digest mismatch: {d}"
    for r in range(WORLD):
        sc = docs[r]["serve_cluster"]
        assert sc["commits"] == COMMITS
        assert sc["recv_thread_deaths"] == 0
