"""Multi-host SPMD execution tests (the DCN scaling story, executed):

N OS processes each own `ndev` virtual CPU devices; jax.distributed
wires them into one (N*ndev)-device global mesh, and ALL run the
unmodified mesh-engine round programs — the aggregation psums cross the
process boundaries over gloo (the CPU stand-in for ICI/DCN
collectives).  The trained results must match the single-process
8-device runs of the identical cases (tests/multihost_case.py), proving
the engines are genuinely global-view: scaling to multiple hosts
changes the runtime bootstrap (parallel/multihost.py), not the training
code.  Topologies (VERDICT r3 weak-#6), each running flat + N-silo
hierarchical + streaming FedOpt + block-streamed rounds:

  2 processes x 4 devices   (plus orbax checkpoint/resume across
  4 processes x 2 devices    cluster death — see the ckpt test below)

The reference's equivalent capability is mpirun over a hostfile with
one process per client rank (run_fedavg_distributed_pytorch.sh:16-35);
here the processes are SPMD replicas of one program instead.
"""
import functools
import json
import os
import re
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "multihost_worker.py")


def _free_port():
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _parse(out: str):
    m = re.search(r"DIGEST ([\d.e+-]+) ACC ([\d.]+)", out)
    h = re.search(r"HDIGEST ([\d.e+-]+) HACC ([\d.]+)", out)
    s = re.search(r"SDIGEST ([\d.e+-]+) SACC ([\d.]+)", out)
    b = re.search(r"BDIGEST ([\d.e+-]+) BACC ([\d.]+)", out)
    assert m and h and s and b, f"worker produced no digest:\n{out[-2000:]}"
    return {"d": float(m.group(1)), "a": float(m.group(2)),
            "hd": float(h.group(1)), "ha": float(h.group(2)),
            "sd": float(s.group(1)), "sa": float(s.group(2)),
            "bd": float(b.group(1)), "ba": float(b.group(2))}


def _run_cluster_raw(nprocs: int, ndev: int, worker: str = WORKER,
                     extra_args: tuple = ()):
    """Launch nprocs worker processes with ndev virtual devices each;
    return the per-worker stdout strings."""
    port = _free_port()
    env = {**os.environ,
           "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", "")}
    procs = [subprocess.Popen(
        [sys.executable, worker, str(i), str(port), str(nprocs), str(ndev),
         *extra_args],
        env=env, text=True, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        cwd=REPO) for i in range(nprocs)]
    # drain all workers CONCURRENTLY: if one crashes at init, its peers
    # block in the collective — sequential communicate() would stall the
    # full timeout and lose the crashed worker's traceback
    results = [None] * nprocs

    def _drain(i):
        try:
            results[i] = procs[i].communicate(timeout=300)
        except subprocess.TimeoutExpired:
            procs[i].kill()
            results[i] = procs[i].communicate()
        except Exception as e:          # decode errors etc: kill ALL so
            for p in procs:             # peers don't hang in psum, and
                if p.poll() is None:    # surface what happened
                    p.kill()
            results[i] = ("", f"drain failed: {e!r}")
    threads = [threading.Thread(target=_drain, args=(i,))
               for i in range(nprocs)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for i, p in enumerate(procs):
        out, err = results[i]
        assert p.returncode == 0, \
            f"worker {i}/{nprocs} failed (rc={p.returncode}):\n{err[-3000:]}"
    return [results[i][0] for i in range(nprocs)]


def _run_cluster(nprocs: int, ndev: int):
    """Launch the standard oracle worker; return parsed digest dicts."""
    return [_parse(out) for out in _run_cluster_raw(nprocs, ndev)]


@functools.cache
def _flat_oracle():
    from tests.multihost_case import build_case, digest
    eng = build_case()
    v = eng.run()
    return digest(v), eng.evaluate(v)["test_acc"]


@functools.cache
def _hier_oracle(silos: int):
    from tests.multihost_case import build_hier_case, digest
    h = build_hier_case(multihost=False, silos=silos)
    hv = h.run()
    return digest(hv), h.evaluate(hv)["test_acc"]


@functools.cache
def _fedopt_streaming_oracle():
    from tests.multihost_case import build_fedopt_streaming_case, digest
    s = build_fedopt_streaming_case()
    sv = s.run()
    return digest(sv), s.evaluate(sv)["test_acc"]


@functools.cache
def _blockstream_oracle():
    from tests.multihost_case import build_blockstream_case, digest
    b = build_blockstream_case()
    bv = b.run()
    return digest(bv), b.evaluate(bv)["test_acc"]


def _check_against_oracle(workers, silos: int):
    # all SPMD replicas hold the identical replicated result
    w0 = workers[0]
    for w in workers[1:]:
        for k in ("d", "hd", "sd", "bd"):
            assert w0[k] == pytest.approx(w[k], rel=1e-7)
        for k in ("a", "ha", "sa", "ba"):
            assert w0[k] == w[k]

    # single-process oracles on the same 8 (virtual) devices, cached —
    # only the hierarchical one depends on the cluster shape.  gloo's
    # cross-process allreduce may order reductions differently than the
    # single-process ring — equality up to float tolerance.
    d, a = _flat_oracle()
    assert w0["d"] == pytest.approx(d, rel=1e-5)
    assert w0["a"] == pytest.approx(a, abs=1e-6)

    # hierarchical: one silo per process (inner psum host-local, silo
    # tier crosses the boundary) == the single-process silos×(8/silos)
    # silo mesh
    hd, ha = _hier_oracle(silos)
    assert w0["hd"] == pytest.approx(hd, rel=1e-5)
    assert w0["ha"] == pytest.approx(ha, abs=1e-6)

    # streaming cohort + FedOpt adam server state
    sd, sa = _fedopt_streaming_oracle()
    assert w0["sd"] == pytest.approx(sd, rel=1e-5)
    assert w0["sa"] == pytest.approx(sa, abs=1e-6)

    # block-streamed round (stream_block) across the process boundary
    bd, ba = _blockstream_oracle()
    assert w0["bd"] == pytest.approx(bd, rel=1e-5)
    assert w0["ba"] == pytest.approx(ba, abs=1e-6)


def test_two_process_mesh_matches_single_process():
    _check_against_oracle(_run_cluster(nprocs=2, ndev=4), silos=2)


def test_multihost_checkpoint_resume(tmp_path):
    """save → kill → resume across a 2-process cluster (VERDICT r4 #5):
    cluster A runs rounds 0-1 of 4 with per-round orbax checkpointing
    and exits; a FRESH cluster B restores (variables + FedOpt adam
    server state) and continues rounds 2-3.  B also runs the
    uninterrupted 4-round oracle in the same topology — the resumed
    continuation must be bitwise-identical (per-round rngs are
    fold_in(round_idx), the sampler reseeds per round, and same-topology
    gloo reductions are deterministic)."""
    ckpt_dir = str(tmp_path / "ckpt")
    worker = os.path.join(REPO, "tests", "multihost_ckpt_worker.py")
    outs = _run_cluster_raw(2, 4, worker=worker,
                            extra_args=("interrupt", ckpt_dir))
    assert all(re.search(r"SAVED 1\b", o) for o in outs), outs
    outs = _run_cluster_raw(2, 4, worker=worker,
                            extra_args=("resume", ckpt_dir))
    for out in outs:
        full = re.search(r"CKFULL ([\d.e+-]+)", out)
        res = re.search(r"CKRES ([\d.e+-]+)", out)
        assert full and res, f"missing digests:\n{out[-2000:]}"
        assert float(res.group(1)) == float(full.group(1))


def test_four_process_mesh_matches_single_process():
    _check_against_oracle(_run_cluster(nprocs=4, ndev=2), silos=4)


# ---------------------------------------------------------------------------
# ISSUE 13: the two-level multihost runtime (launcher + HostChannel +
# MultihostRunner).  These run on EVERY jaxlib: the cross-process tier
# is the HostChannel carry allreduce, not an in-program collective.
# ---------------------------------------------------------------------------

LAUNCHER = os.path.join(REPO, "tools", "launch_multihost.py")
MH_ENV = {**os.environ,
          "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH",
                                                           "")}

MH_CASE = {
    # tiny LR case; local_devices=2 so the INTRA-host psum tier is real
    # (2-wide local mesh) on top of the inter-host fold
    "clients": 16, "spc": 24, "dim": 16, "classes": 10,
    "k_per_round": 8, "n_blocks": 2, "rounds": 2, "warmup": 0,
    "seed": 0, "modes": ["streaming", "resident"], "local_devices": 2,
}


def _run_launcher(procs: int, cfg: dict, tmp_path, timeout: int = 300,
                  flags: tuple = ()):
    """Launch `procs` mh_worker ranks through the REAL launcher tool;
    returns ({rank: worker JSON doc}, completed_process)."""
    path = tmp_path / f"mh_{procs}p_{abs(hash(flags))}.json"
    path.write_text(json.dumps(cfg))
    r = subprocess.run(
        [sys.executable, LAUNCHER, "--procs", str(procs), *flags, "--",
         sys.executable, "-m", "fedml_tpu.parallel.mh_worker",
         str(path)],
        env=MH_ENV, cwd=REPO, text=True, capture_output=True,
        timeout=timeout)
    docs = {}
    for line in r.stdout.splitlines():
        m = re.match(r"\[rank (\d+)\] (\{.*)", line)
        if m:
            d = json.loads(m.group(2))
            docs[d["rank"]] = d
    return docs, r


def test_twolevel_two_process_bitwise_pin(tmp_path):
    """THE ISSUE-13 anchor: a 2-process launcher run commits bitwise
    equal to the single-process run on the same seed — FedAvg resident
    AND streaming — because the reduction tree is a function of the
    BLOCK partition (n_blocks=2 in both arms), not the topology.  Also
    pins that the carry really crossed processes (allreduce bytes > 0)
    and that both ranks hold identical replicated results."""
    one, r1 = _run_launcher(1, MH_CASE, tmp_path)
    assert r1.returncode == 0, r1.stderr[-3000:]
    two, r2 = _run_launcher(2, MH_CASE, tmp_path)
    assert r2.returncode == 0, r2.stderr[-3000:]
    assert set(one) == {0} and set(two) == {0, 1}, (one, two,
                                                    r2.stdout[-500:])
    for mode in ("streaming", "resident"):
        d1 = one[0]["digests"][mode]
        assert two[0]["digests"][mode] == d1, (
            f"{mode}: 2-process commit diverged from single-process "
            f"(the block-partition reduction tree broke)")
        assert two[1]["digests"][mode] == d1, (
            f"{mode}: rank 1 diverged from rank 0 (commit not "
            f"replicated)")
    # the carry genuinely crossed processes in the 2-proc arm
    assert two[0]["carry_allreduce_bytes_per_round"] > 0
    assert one[0]["carry_allreduce_bytes_per_round"] == 0
    # ISSUE 17 rider on the SAME spawned run (no new cluster): rank
    # 0's always-on barrier ledger attributed every allgather — each
    # entry names its gating rank — and the cluster SLO pack is green
    # on a clean run
    sl = two[0].get("straggler")
    assert sl and sl["barriers"] > 0, (
        "rank 0's barrier ledger is empty on a 2-process run — the "
        "allgather arrival stamps (obs/cluster.py note_barrier) broke")
    assert all(e["round_gating_rank"] in (0, 1)
               for e in sl["recent"]), sl["recent"]
    cslo = two[0].get("cluster_slo")
    assert cslo and cslo["healthy"] is True, (
        f"clean 2-process run breached the cluster SLO pack: {cslo}")
    # ISSUE 16: the f32 escape hatch stays bitwise UNDER OVERLAP — the
    # ONE extra spawned arm this PR adds (the other compression/
    # overlap pins are in-process): same case, f32 codec + overlapped
    # exchange, digests byte-identical to the serial arms above, and
    # the exchange measurably hid behind compute
    ov, r3 = _run_launcher(2, {**MH_CASE, "carry_codec": "f32",
                               "overlap_exchange": True}, tmp_path)
    assert r3.returncode == 0, r3.stderr[-3000:]
    for mode in ("streaming", "resident"):
        d1 = one[0]["digests"][mode]
        for r in (0, 1):
            assert ov[r]["digests"][mode] == d1, (
                f"{mode}: rank {r} diverged under --overlap_exchange "
                f"— the overlapped gather broke the f32 escape hatch")
    assert ov[0]["carry_codec"] == "f32"
    assert ov[0]["overlap_fraction"] > 0, (
        "overlapped arm reported zero overlap — the exchange never "
        "rode under block compute")


def test_twolevel_crash_names_dead_rank(tmp_path):
    """A rank dying mid-round must NAME itself instead of hanging the
    cluster: the survivor's bounded HostChannel wait raises
    DeadRankError naming rank 1, and the launcher's failure report
    blames the first-failing rank."""
    cfg = {**MH_CASE, "modes": ["streaming"], "rounds": 3,
           "die_rank": 1, "die_at_round": 0, "channel_timeout_s": 10,
           "local_devices": 1}
    docs, r = _run_launcher(2, cfg, tmp_path, timeout=180)
    assert r.returncode != 0
    # rank 0's own named error (streamed through the launcher's
    # [rank 0] stderr prefix) — the bounded-wait contract
    assert "DeadRankError" in r.stderr, r.stderr[-3000:]
    assert re.search(r"rank\(s\) \[1\]", r.stderr), r.stderr[-3000:]
    # the launcher blames the injected fault's rank, not the survivor
    assert re.search(r"rank 1/2 failed first", r.stderr), \
        r.stderr[-3000:]


def test_channel_bounded_timeout_names_stalled_rank():
    """The timeout half of the bounded-barrier contract (the crash test
    covers the EOF half): a rank that connects, handshakes, then goes
    silent is named within timeout_s instead of hanging the
    allgather."""
    import socket
    import struct
    import threading

    from fedml_tpu.parallel.multihost import (DeadRankError, HostChannel,
                                              MultihostContext, free_port)
    port = free_port()
    ctx0 = MultihostContext(rank=0, world=2,
                            coordinator=f"localhost:{port}")
    errs = []

    def rank0():
        try:
            ch = HostChannel(ctx0, timeout_s=1.5, connect_timeout_s=10)
            try:
                ch.allgather(b"payload")
            finally:
                ch.close()
        except Exception as e:
            errs.append(e)

    t = threading.Thread(target=rank0)
    t.start()
    # a "rank 1" that handshakes then stalls forever
    deadline = time.monotonic() + 10
    while True:
        try:
            s = socket.create_connection(("localhost", port),
                                         timeout=1.0)
            break
        except OSError:
            assert time.monotonic() < deadline
            time.sleep(0.05)
    s.sendall(struct.pack("<I", 1))
    t.join(timeout=15)
    s.close()
    assert not t.is_alive(), "allgather hung past its bounded timeout"
    assert len(errs) == 1 and isinstance(errs[0], DeadRankError), errs
    assert "rank(s) [1]" in str(errs[0])


def test_launcher_validates_args():
    """Launcher arg validation fails fast (before any jax import):
    nonpositive --procs and a missing worker command are usage
    errors."""
    r = subprocess.run(
        [sys.executable, LAUNCHER, "--procs", "0", "--", "true"],
        env=MH_ENV, cwd=REPO, text=True, capture_output=True,
        timeout=60)
    assert r.returncode == 2
    assert "--procs must be >= 1" in r.stderr
    r = subprocess.run(
        [sys.executable, LAUNCHER, "--procs", "2"],
        env=MH_ENV, cwd=REPO, text=True, capture_output=True,
        timeout=60)
    assert r.returncode == 2
    assert "missing worker command" in r.stderr


def test_block_sampler_topology_independent():
    """BlockCohortSampler: pure function of (seed, round, block), ids
    confined to the block's population range, distinct blocks/rounds
    differ, and the partition validations name their numbers."""
    from fedml_tpu.parallel.multihost import BlockCohortSampler
    s = BlockCohortSampler(population=64, n_blocks=4, k_per_block=6,
                           seed=3)
    a = s.sample_block(5, 2)
    b = BlockCohortSampler(64, 4, 6, seed=3).sample_block(5, 2)
    assert (a == b).all(), "not a pure function of (seed, round, block)"
    assert len(set(a.tolist())) == 6
    assert a.min() >= 32 and a.max() < 48, "ids escaped block 2's range"
    assert not (s.sample_block(6, 2) == a).all()
    # full-participation block
    f = BlockCohortSampler(64, 4, 16, seed=0).sample_block(0, 1)
    assert (f == np.arange(16, 32)).all()
    with pytest.raises(ValueError, match="divide evenly"):
        BlockCohortSampler(65, 4, 6, seed=0)
    with pytest.raises(ValueError, match="k_per_block"):
        BlockCohortSampler(64, 4, 17, seed=0)


def test_fold_block_partials_is_ordered_left_fold():
    """The inter-host reduction contract: left fold in global block
    order (float addition is not associative — the fold order IS the
    bitwise anchor), and a missing block names itself."""
    from fedml_tpu.parallel.multihost import (DeadRankError,
                                              fold_block_partials)
    rs = np.random.RandomState(0)
    parts = {b: rs.randn(33).astype(np.float32) for b in range(4)}
    got = fold_block_partials(parts, 4)
    want = parts[0].copy()
    for b in (1, 2, 3):
        want = want + parts[b]
    assert got.tobytes() == want.tobytes()
    with pytest.raises(DeadRankError, match=r"\[2\]"):
        fold_block_partials({0: parts[0], 1: parts[1], 3: parts[3]}, 4)


def test_fold_sparse_partials_matches_dense_fold_bitwise():
    """ISSUE 19: the sparse scatter-fold over (index, value) pairs is
    BITWISE the dense left fold over the densified blocks — adding the
    pairs in global block order is the same float program as adding
    dense vectors whose non-selected entries are +0.0 (x + 0.0 == x
    bitwise for every x the fold can produce).  So the sparse tier
    changes wire bytes, never replica agreement, and a missing block
    still names itself."""
    from fedml_tpu.parallel.carry_codec import TopKCarryCodec
    from fedml_tpu.parallel.multihost import (DeadRankError,
                                              fold_block_partials,
                                              fold_sparse_partials)
    c = TopKCarryCodec(topk_ratio=16)
    rs = np.random.RandomState(1)
    dim, n_blocks = 96, 4
    bufs = {b: c.encode(b, rs.randn(dim).astype(np.float32))
            for b in range(n_blocks)}
    pairs = {}
    dense = {}
    for b, buf in bufs.items():
        _, idx, vals = c.decode_pairs(buf)
        pairs[b] = (idx, vals)
        dense[b] = c.decode(buf)
    got = fold_sparse_partials(pairs, n_blocks, dim)
    want = fold_block_partials(dense, n_blocks)
    assert got.tobytes() == want.tobytes()
    with pytest.raises(DeadRankError, match=r"\[1\]"):
        fold_sparse_partials({0: pairs[0], 2: pairs[2], 3: pairs[3]},
                             n_blocks, dim)


def test_hierarchical_host_mesh_virtual_silo_warns(caplog):
    """ISSUE-13 satellite: single-process make_hierarchical_host_mesh
    with silos>1 builds VIRTUAL silo rows sharing this host — still the
    intended dev/test topology (the oracle cases rely on it), but it
    must say so loudly instead of silently looking like a DCN
    layout."""
    import logging
    from fedml_tpu.parallel.multihost import make_hierarchical_host_mesh
    with caplog.at_level(logging.WARNING,
                         logger="fedml_tpu.parallel.multihost"):
        mesh = make_hierarchical_host_mesh(silos=2)
    assert mesh.shape["silo"] == 2
    assert any("VIRTUAL silos" in rec.message for rec in caplog.records)
    # the explicit one-silo case stays quiet
    caplog.clear()
    with caplog.at_level(logging.WARNING,
                         logger="fedml_tpu.parallel.multihost"):
        make_hierarchical_host_mesh(silos=1)
    assert not any("VIRTUAL silos" in rec.message
                   for rec in caplog.records)


# ---------------------------------------------------------------------------
# ISSUE 14: elastic membership — epoch-numbered views, heartbeats,
# deterministic block re-adoption, rejoin.  The channel-level tests run
# fake byte-payload workers in threads (no jax compute): membership is
# a socket protocol, and these pin its edges fast.  The launcher test
# at the bottom is THE acceptance pin — a real 3-process elastic
# cluster, a seeded kill, a respawned rejoiner, byte-identical commits.
# ---------------------------------------------------------------------------

def _evec(item: int, rnd: int) -> bytes:
    return np.full(3, 100 * item + rnd, np.float32).tobytes()


def _elastic_channel(rank, world, port, *, n_items, digest="cfg",
                     timeout_s=30.0, connect_timeout_s=10.0,
                     hb_timeout_s=1.0, rejoin=False):
    from fedml_tpu.parallel.multihost import (ElasticChannel,
                                              MultihostContext)
    ctx = MultihostContext(rank=rank, world=world,
                           coordinator=f"localhost:{port}")
    return ElasticChannel(ctx, n_items=n_items, config_digest=digest,
                          timeout_s=timeout_s,
                          connect_timeout_s=connect_timeout_s,
                          hb_interval_s=0.1, hb_timeout_s=hb_timeout_s,
                          rejoin=rejoin)


def test_cluster_view_deterministic_repartition():
    """The item→owner map is a pure function of (members, n_items):
    full membership reduces to the PR-13 contiguous tiling, any
    survivor subset still covers every item exactly once, and every
    rank derives the identical partition from the member list alone."""
    from fedml_tpu.parallel.multihost import ClusterView
    v = ClusterView(0, (0, 1, 2, 3), 8)
    assert [v.assigned(r) for r in range(4)] == [
        (0, 1), (2, 3), (4, 5), (6, 7)]       # the PR-13 tiling
    for members in [(0,), (0, 2), (1, 3), (0, 1, 3), (2,)]:
        vw = ClusterView(1, members, 8)
        owners = [vw.owner_of(i) for i in range(8)]
        assert set(owners) <= set(members)
        covered = [i for m in members for i in vw.assigned(m)]
        assert sorted(covered) == list(range(8)), (members, covered)
        # pure function: a second view with the same members agrees
        assert owners == [ClusterView(9, members, 8).owner_of(i)
                          for i in range(8)]
    with pytest.raises(ValueError, match="outside"):
        ClusterView(0, (0,), 4).owner_of(4)


def test_elastic_death_and_double_death_epochs_monotone():
    """One rank dying mid-round triggers a view change and the
    survivors re-adopt its items (the round still completes with ALL
    items, byte-identical); BOTH peers dying in one round leaves the
    coordinator to adopt everything.  Epochs only ever increase, the
    obs epoch gauge/view-change counter move, and every completed
    round's payload set is the full deterministic one."""
    from fedml_tpu import obs
    from fedml_tpu.parallel.multihost import free_port
    port = free_port()
    n_items, world, rounds = 6, 3, 4
    vc0 = obs.counter("multihost_view_changes_total").value
    results, errs = {}, []

    def run_rank(r, die_after=None):
        try:
            ch = _elastic_channel(r, world, port, n_items=n_items)
            if r == 0:
                ch.wait_members()
            try:
                for rnd in range(rounds):
                    if die_after is not None and rnd == die_after:
                        ch.close()
                        return
                    parts = {b: _evec(b, rnd)
                             for b in ch.view.assigned(r)}
                    allp, view = ch.exchange(
                        rnd, parts,
                        lambda need, rnd=rnd: {b: _evec(b, rnd)
                                               for b in need})
                    assert set(allp) == set(range(n_items))
                    assert all(allp[b] == _evec(b, rnd)
                               for b in range(n_items))
                    results.setdefault(r, []).append(
                        (view.epoch, view.members))
            finally:
                if r == 0:
                    results["events"] = list(ch.view_events)
                ch.close()
        except Exception as e:
            errs.append((r, e))

    ts = [threading.Thread(target=run_rank, args=(r,),
                           kwargs={"die_after": {1: 2, 2: 3}.get(r)})
          for r in range(world)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(60)
    assert not errs, errs
    assert len(results[0]) == rounds     # the coordinator survives all
    # round 2 lost rank 1 (epoch 1), round 3 lost rank 2 too (epoch 2,
    # coordinator adopts every item)
    assert results[0][-1] == (2, (0,))
    epochs = [e["epoch"] for e in results["events"]]
    assert epochs == sorted(epochs) and len(set(epochs)) == len(epochs), (
        f"epochs must be strictly monotone: {epochs}")
    assert obs.counter("multihost_view_changes_total").value >= vc0 + 2
    assert obs.gauge("multihost_epoch", rank="0").value == 2.0


def test_elastic_death_during_view_change():
    """A survivor dying WHILE a view change re-tasks it: rank 1 dies
    mid-round, the VIEW re-asks rank 2, and rank 2 dies instead of
    re-contributing — the coordinator must chain a second view change
    and finish alone (every item still present)."""
    from fedml_tpu.parallel.multihost import (_recv_msg, _send_msg,
                                              free_port)
    port = free_port()
    n_items = 4
    out, errs = {}, []

    def coord():
        try:
            ch = _elastic_channel(0, 3, port, n_items=n_items,
                                  timeout_s=15)
            ch.wait_members()
            try:
                for rnd in range(2):
                    parts = {b: _evec(b, rnd)
                             for b in ch.view.assigned(0)}
                    allp, view = ch.exchange(
                        rnd, parts,
                        lambda need, rnd=rnd: {b: _evec(b, rnd)
                                               for b in need})
                    assert set(allp) == set(range(n_items))
                    out[rnd] = (view.epoch, view.members)
                out["events"] = list(ch.view_events)
            finally:
                ch.close()
        except Exception as e:
            errs.append(("coord", e))

    def rank1():
        ch = _elastic_channel(1, 3, port, n_items=n_items)
        allp, _ = ch.exchange(0, {b: _evec(b, 0)
                                  for b in ch.view.assigned(1)}, None)
        ch.close()                      # dead before round 1

    def rank2_raw():
        # hand-rolled worker: behaves normally until a VIEW arrives,
        # then dies instead of computing its re-adopted items
        import socket as sk
        try:
            deadline = time.monotonic() + 10
            while True:
                try:
                    data = sk.create_connection(("localhost", port),
                                                timeout=1.0)
                    break
                except OSError:
                    assert time.monotonic() < deadline
                    time.sleep(0.05)
            _send_msg(data, "hello", {"rank": 2, "role": "data",
                                      "digest": "cfg"})
            mtype, hdr, _, _ = _recv_msg(data)
            assert mtype == "hello_ok", (mtype, hdr)
            hb = sk.create_connection(("localhost", port), timeout=5.0)
            _send_msg(hb, "hello", {"rank": 2, "role": "hb"})
            stop = threading.Event()

            def beat():
                while not stop.is_set():
                    try:
                        _send_msg(hb, "hb", {})
                    except OSError:
                        return
                    time.sleep(0.1)
            threading.Thread(target=beat, daemon=True).start()
            for rnd in range(2):
                mine = [b for b in range(n_items)
                        if b * 3 // n_items == 2]
                _send_msg(data, "contrib",
                          {"epoch": 0, "round": rnd,
                           "blocks": mine},
                          b"".join(_evec(b, rnd) for b in mine))
                while True:
                    mtype, hdr, payload, _ = _recv_msg(data)
                    if mtype == "view":
                        # the death-during-view-change moment
                        stop.set()
                        data.close()
                        hb.close()
                        return
                    if mtype == "result":
                        break
        except Exception as e:
            errs.append(("rank2", e))

    ts = [threading.Thread(target=f) for f in (coord, rank1, rank2_raw)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(40)
    assert not errs, errs
    assert out[1][1] == (0,), f"coordinator did not finish alone: {out}"
    epochs = [e["epoch"] for e in out["events"]]
    assert epochs == [1, 2], epochs


def test_elastic_heartbeat_detects_hung_rank_within_timeout():
    """The SIGSTOP shape: a rank that connects, then goes silent
    (paused heartbeats, no contribution) must be evicted within the
    heartbeat timeout — NOT the full round timeout — and the suspicion
    reason must say so.  Detection rides the heartbeat monitor, so a
    hang is caught between allgathers, not only inside one."""
    from fedml_tpu.parallel.multihost import free_port
    port = free_port()
    out, errs = {}, []
    TIMEOUT_S = 30.0                     # the round budget a hung rank
    #                                      must NOT consume

    def coord():
        try:
            ch = _elastic_channel(0, 2, port, n_items=2,
                                  timeout_s=TIMEOUT_S, hb_timeout_s=1.0)
            ch.wait_members()
            t0 = time.monotonic()
            allp, view = ch.exchange(
                0, {0: _evec(0, 0)},
                lambda need: {b: _evec(b, 0) for b in need})
            out["elapsed"] = time.monotonic() - t0
            out["view"] = (view.epoch, view.members)
            out["events"] = list(ch.view_events)
            ch.close()
        except Exception as e:
            errs.append(e)

    def hung_worker():
        ch = _elastic_channel(1, 2, port, n_items=2)
        ch.hb_paused = True              # the process "stops"
        time.sleep(3.0)                  # hung, not dead: socket open
        ch.close()

    tw = threading.Thread(target=hung_worker, daemon=True)
    tc = threading.Thread(target=coord)
    tw.start()
    tc.start()
    tc.join(25)
    assert not errs, errs
    assert out["view"] == (1, (0,))
    assert out["elapsed"] < TIMEOUT_S / 2, (
        f"hung rank took {out['elapsed']:.1f}s to evict — the "
        f"heartbeat detector should fire in ~1s, not the round "
        f"timeout")
    assert any("heartbeat" in e.get("reason", "")
               or "hung" in e.get("reason", "")
               for e in out["events"]), out["events"]
    tw.join(15)


def test_elastic_rejoin_snapshot_and_stale_digest_rejected():
    """The rejoin handshake: a restarted rank presents the config
    digest — a STALE digest is rejected BY NAME (both digests in the
    error), a matching one is admitted at the next commit barrier with
    the coordinator's snapshot + resume round + run tag, and the
    rejoined rank finishes the remaining rounds as a member."""
    from fedml_tpu.parallel.multihost import DeadRankError, free_port
    port = free_port()
    n_items, rounds = 2, 6
    out, errs = {}, []

    def coord():
        try:
            ch = _elastic_channel(0, 2, port, n_items=n_items,
                                  timeout_s=20)
            ch.wait_members()
            for rnd in range(rounds):
                parts = {b: _evec(b, rnd)
                         for b in ch.view.assigned(0)}
                allp, view = ch.exchange(
                    rnd, parts,
                    lambda need, rnd=rnd: {b: _evec(b, rnd)
                                           for b in need})
                admitted = ch.admit_rejoins(
                    rnd + 1, lambda: b"snapshot@%d" % (rnd + 1),
                    tag="streaming")
                if admitted:
                    out["admitted_at"] = rnd + 1
                time.sleep(0.3)
            out["events"] = list(ch.view_events)
            ch.close()
        except Exception as e:
            errs.append(("coord", e))

    def mortal():
        ch = _elastic_channel(1, 2, port, n_items=n_items)
        ch.exchange(0, {b: _evec(b, 0)
                        for b in ch.view.assigned(1)}, None)
        ch.close()

    def stale_rejoiner():
        time.sleep(0.6)
        ch = _elastic_channel(1, 2, port, n_items=n_items,
                              digest="STALE-DIGEST", rejoin=True)
        with pytest.raises(DeadRankError) as ei:
            ch.rejoin_handshake()
        ch.close()
        msg = str(ei.value)
        assert "STALE-DIGEST" in msg and "cfg" in msg and "rank 1" in msg, (
            f"stale rejoin must be rejected naming both digests: {msg}")
        out["stale_named"] = True

    def rejoiner():
        try:
            time.sleep(1.0)
            ch = _elastic_channel(1, 2, port, n_items=n_items,
                                  rejoin=True)
            blob, resume, tag = ch.rejoin_handshake()
            out["snapshot"] = blob
            out["resume"] = resume
            out["tag"] = tag
            for rnd in range(resume, rounds):
                allp, view = ch.exchange(
                    rnd, {b: _evec(b, rnd)
                          for b in ch.view.assigned(1)},
                    lambda need, rnd=rnd: {b: _evec(b, rnd)
                                           for b in need})
                assert all(allp[b] == _evec(b, rnd)
                           for b in range(n_items))
            out["rejoined_rounds"] = rounds - resume
            ch.close()
        except Exception as e:
            errs.append(("rejoiner", e))

    ts = [threading.Thread(target=f)
          for f in (coord, mortal, stale_rejoiner, rejoiner)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(60)
    assert not errs, errs
    assert out.get("stale_named")
    assert out["snapshot"] == b"snapshot@%d" % out["resume"]
    assert out["tag"] == "streaming"
    assert out["rejoined_rounds"] >= 1
    # the admission is its own epoch bump, after the death's
    epochs = [e["epoch"] for e in out["events"]]
    assert epochs == sorted(epochs) and len(epochs) >= 2
    assert any("rejoined" in e for e in out["events"])


def test_rejoin_snapshot_carries_topk_ef_mirror():
    """ISSUE 19 elastic seam: the rejoin catch-up snapshot ships the
    codec's carry state, and the install path rebuilds a codec whose
    reconstruction mirror is byte-identical to the coordinator's — a
    rejoiner folding future topk_ef rounds from a zero mirror would
    disagree with every survivor."""
    import pickle
    from fedml_tpu.parallel.multihost import ElasticRunner
    from fedml_tpu.parallel.carry_codec import TopKEFCarryCodec

    coord = object.__new__(ElasticRunner)
    coord.codec = TopKEFCarryCodec()
    rng = np.random.default_rng(7)
    vec = (3.0 * rng.standard_normal(96)).astype(np.float32)
    for r in range(5):
        vec = (vec + 0.05 * rng.standard_normal(96)).astype(np.float32)
        for b in (0, 1):
            coord.codec.integrate(b, coord.codec.encode(b, vec))
    blob = ElasticRunner._snapshot_blob(
        coord, 5, {"w": np.zeros(2, np.float32)}, ())
    payload = pickle.loads(blob)
    assert "carry" in payload, (
        "the rejoin snapshot must carry the stateful codec's mirror")
    rejoiner = object.__new__(ElasticRunner)
    rejoiner.codec = TopKEFCarryCodec()
    rejoiner.load_carry_state(payload["carry"])
    nxt = (vec + 0.05 * rng.standard_normal(96)).astype(np.float32)
    for b in (0, 1):
        buf = coord.codec.encode(b, nxt)
        assert rejoiner.codec.encode(b, nxt) == buf
        np.testing.assert_array_equal(
            rejoiner.codec.integrate(b, buf).view(np.uint32),
            coord.codec.integrate(b, buf).view(np.uint32))


def test_dial_backoff_late_listener_and_named_failure():
    """ISSUE-14 satellite: every transient connect path retries with
    bounded exponential backoff inside its deadline — a listener that
    appears late is reached, and a dead endpoint fails with a
    DeadRankError NAMING the dial."""
    import socket as sk

    from fedml_tpu.parallel.multihost import (DeadRankError,
                                              _dial_with_backoff,
                                              free_port)
    port = free_port()

    def late_listener():
        time.sleep(0.4)                 # refuse first, accept later
        srv = sk.create_server(("localhost", port))
        conn, _ = srv.accept()
        conn.close()
        srv.close()
    t = threading.Thread(target=late_listener)
    t.start()
    s = _dial_with_backoff("localhost", port,
                           time.monotonic() + 10.0, "late-dial test")
    s.close()
    t.join(10)
    dead_port = free_port()
    t0 = time.monotonic()
    with pytest.raises(DeadRankError) as ei:
        _dial_with_backoff("localhost", dead_port,
                           time.monotonic() + 0.6,
                           "worker 7 dialing the coordinator")
    assert time.monotonic() - t0 < 5.0
    assert "worker 7 dialing the coordinator" in str(ei.value)
    assert "ConnectionRefusedError" in str(ei.value)


def test_spawn_cluster_blame_names_every_rank():
    """ISSUE-14 satellite: MultihostLaunchError carries a per-rank
    outcome summary — exit codes for plain failures and SIGNAL NAMES
    for signal deaths — so the chaos-killed rank reads differently
    from the launcher-cleanup kills it causes."""
    from fedml_tpu.parallel.multihost import (MultihostLaunchError,
                                              spawn_cluster)
    prog = ("import os, sys, time\n"
            "r = int(os.environ['FEDML_MH_RANK'])\n"
            "if r == 1:\n"
            "    time.sleep(0.3); sys.exit(7)\n"
            "time.sleep(30)\n")
    with pytest.raises(MultihostLaunchError) as ei:
        spawn_cluster([sys.executable, "-c", prog], 3, timeout_s=25,
                      kill_grace_s=0.3)
    msg = str(ei.value)
    assert "rank 1/3 failed first" in msg
    assert "rc=7" in msg
    assert "per-rank:" in msg
    assert "exit rc=7" in msg
    assert "SIGKILL" in msg, (
        f"launcher-cleanup kills must be signal-named: {msg}")
    # respawn without elastic is a config error, named
    with pytest.raises(ValueError, match="elastic"):
        spawn_cluster([sys.executable, "-c", "pass"], 1, respawn=True)


MH_ELASTIC_CLEAN = {
    # tiny LR case, 3 blocks; local_devices=1 — the elastic pin is
    # about MEMBERSHIP, the intra-host psum tier is pinned above
    "clients": 12, "spc": 24, "dim": 8, "classes": 4, "k_per_round": 6,
    "n_blocks": 3, "rounds": 5, "warmup": 0, "seed": 0,
    "modes": ["streaming", "resident"], "local_devices": 1,
    "elastic": True,
}


def test_elastic_kill_respawn_bitwise_pin(tmp_path):
    """THE ISSUE-14 acceptance pin, launcher-spawned: a 3-process
    ELASTIC run with a seeded kill of rank 1 mid-run (a) completes on
    the survivors, (b) readmits the respawned rank 1 through the
    rejoin handshake, and (c) commits models BYTE-IDENTICAL
    (md5-over-leaf-bytes) to the clean same-partition run — FedAvg
    resident AND streaming, on every rank including the rejoiner.
    round_sleep_s paces the run so the respawn (a fresh jax boot)
    rejoins deterministically inside the first (streaming) run: the
    four rounds after the kill hold it open for 12 s, and a rank boots
    in 4-5 s on an idle box (at 0.9 s a round the window was 5 s, and
    the rejoin landed in the resident run every other time)."""
    cfg = {**MH_ELASTIC_CLEAN, "die_rank": 1,
           "die_at_round": 0, "round_sleep_s": 3.0,
           "round_sleep_mode": "streaming",
           "hb_timeout_s": 1.5, "channel_timeout_s": 60}
    cleanb, r0b = _run_launcher(1, MH_ELASTIC_CLEAN, tmp_path)
    assert r0b.returncode == 0, r0b.stderr[-3000:]
    killed, r1 = _run_launcher(3, cfg, tmp_path, timeout=280,
                               flags=("--elastic", "--respawn"))
    assert r1.returncode == 0, (r1.stdout[-2000:], r1.stderr[-3000:])
    assert set(killed) == {0, 1, 2}, (set(killed), r1.stderr[-3000:])
    assert killed[1]["rejoined"] is True
    # survivors: byte-identical to the clean same-partition run, BOTH
    # residency modes
    for mode in ("streaming", "resident"):
        want = cleanb[0]["digests"][mode]
        for r in (0, 2):
            assert killed[r]["digests"][mode] == want, (
                f"{mode}: rank {r} diverged after the kill — the "
                f"elastic re-adoption broke the bitwise anchor")
    # the rejoiner: resumes whichever run the coordinator was in when
    # it booted (run-tag routed) — every mode it DID run must match,
    # and it must have run at least one
    assert killed[1]["digests"], "rejoiner reported no digests"
    for mode, digest in killed[1]["digests"].items():
        assert digest == cleanb[0]["digests"][mode], (
            f"{mode}: the REJOINED rank diverged — the snapshot "
            f"catch-up broke the bitwise anchor")
    # the death AND the readmission each bumped the epoch
    rep = killed[0]["per_mode"]["streaming"]
    assert rep["view_changes"] >= 2, rep
    assert rep["epoch"] >= 2, rep
    assert "respawning once" in r1.stderr, r1.stderr[-2000:]
    # ISSUE 17 rider on the SAME spawned chaos run: the cluster SLO
    # pack must BREACH the zero-deaths objective and NAME the killed
    # rank in the attribution, and the barrier ledger observed the
    # exchange barriers (round_hint-free exchange entries included)
    cslo = killed[0].get("cluster_slo")
    assert cslo and cslo["healthy"] is False, (
        f"killed-arm cluster SLO stayed green: {cslo}")
    assert "cluster_no_rank_deaths" in cslo["breached"], cslo
    assert "1" in (cslo["attribution"]["dead_ranks"] or []), (
        f"attribution failed to name the killed rank: "
        f"{cslo['attribution']}")
    sl = killed[0].get("straggler")
    assert sl and sl["barriers"] > 0, (
        "rank 0's barrier ledger is empty on the elastic chaos run — "
        "the exchange arrival stamps (obs/cluster.py) broke")


# ---------------------------------------------------------------------------
# ISSUE 16: compressed + overlapped carry exchange — fast in-process
# pins over REAL sockets (threads, not spawned clusters).  The one
# spawned overlap arm rides test_twolevel_two_process_bitwise_pin.
# ---------------------------------------------------------------------------


def test_gather_primitive_bitwise_equals_allgather():
    """The overlap substrate: the two-phase gather (gather_begin /
    per-frame gather_push / gather_finish) must return EXACTLY what
    `allgather(b"".join(frames))` returns — frames concatenate in push
    order, rank 0 broadcasts the standard allgather blob — which is
    the whole argument for the f32 escape hatch staying bitwise under
    --overlap_exchange.  Also pins the per-round wire delta (ISSUE-16
    satellite: bytes measured ON the channel, not inferred)."""
    from fedml_tpu.parallel.multihost import (HostChannel,
                                              MultihostContext,
                                              free_port)
    port = free_port()
    frames = {r: [bytes([65 + r]) * 7 + bytes([i]) for i in range(3)]
              for r in range(2)}
    out, errs = {}, []

    def run(r):
        try:
            ctx = MultihostContext(rank=r, world=2,
                                   coordinator=f"localhost:{port}")
            ch = HostChannel(ctx, timeout_s=20.0,
                             connect_timeout_s=10.0)
            try:
                ch.mark_round()
                h = ch.gather_begin(3, timeout_s=20.0)
                for f in frames[r]:
                    ch.gather_push(h, f)
                docs_g = ch.gather_finish(h)
                d_gather = ch.round_wire_delta()
                ch.mark_round()
                docs_a = ch.allgather(b"".join(frames[r]))
                d_all = ch.round_wire_delta()
                out[r] = (docs_g, docs_a, d_gather, d_all)
            finally:
                ch.close()
        except Exception as e:
            errs.append((r, e))

    ts = [threading.Thread(target=run, args=(r,)) for r in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(30)
    assert not errs, errs
    want = [b"".join(frames[0]), b"".join(frames[1])]
    for r in (0, 1):
        docs_g, docs_a, d_gather, d_all = out[r]
        assert docs_g == docs_a == want, (
            f"rank {r}: pipelined gather diverged from allgather")
        # the wire delta window: both rounds moved bytes both ways
        for d in (d_gather, d_all):
            assert d["sent"] > 0 and d["received"] > 0, (r, d)


def test_gather_abort_and_push_count_validation():
    """gather_finish validates the push count (a short round is a
    named bug, not a hang) and gather_abort tears down a half-open
    gather so the next collective starts clean."""
    from fedml_tpu.parallel.multihost import (HostChannel,
                                              MultihostContext,
                                              free_port)
    port = free_port()
    out, errs = {}, []

    def run(r):
        try:
            ctx = MultihostContext(rank=r, world=2,
                                   coordinator=f"localhost:{port}")
            ch = HostChannel(ctx, timeout_s=20.0,
                             connect_timeout_s=10.0)
            try:
                h = ch.gather_begin(2, timeout_s=20.0)
                ch.gather_push(h, b"only-one")
                if r == 0:
                    with pytest.raises(ValueError,
                                       match="1 frames pushed"):
                        ch.gather_finish(h)
                ch.gather_abort(h)
                out[r] = True
            finally:
                ch.close()
        except Exception as e:
            errs.append((r, e))

    ts = [threading.Thread(target=run, args=(r,)) for r in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(30)
    assert not errs, errs
    assert out == {0: True, 1: True}


def test_elastic_early_contrib_matches_inline_exchange():
    """ElasticChannel's overlap shape: per-item early sends
    (contrib_begin/contrib_push) + exchange(pending=...) must commit
    the identical full item set as the inline PR-14 exchange — the
    coordinator's multi-contrib protocol and the round-stamped drop of
    stale frames make early frames safe across the same round."""
    from fedml_tpu.parallel.multihost import free_port
    port = free_port()
    n_items = 4
    results, errs = {}, []

    def run_rank(r):
        try:
            ch = _elastic_channel(r, 2, port, n_items=n_items)
            if r == 0:
                ch.wait_members()
            try:
                ch.mark_round()
                h = ch.contrib_begin(0)
                for b in ch.view.assigned(r):
                    ch.contrib_push(h, b, _evec(b, 0))
                allp0, _ = ch.exchange(
                    0, {}, lambda need: {b: _evec(b, 0) for b in need},
                    pending=h)
                delta = ch.round_wire_delta()
                allp1, _ = ch.exchange(
                    1, {b: _evec(b, 1) for b in ch.view.assigned(r)},
                    lambda need: {b: _evec(b, 1) for b in need})
                results[r] = (allp0, allp1, delta)
            finally:
                ch.close()
        except Exception as e:
            errs.append((r, e))

    ts = [threading.Thread(target=run_rank, args=(r,))
          for r in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(60)
    assert not errs, errs
    for r in (0, 1):
        allp0, allp1, delta = results[r]
        assert set(allp0) == set(range(n_items))
        assert all(allp0[b] == _evec(b, 0) for b in range(n_items)), (
            f"rank {r}: early-contrib round lost or corrupted items")
        assert all(allp1[b] == _evec(b, 1) for b in range(n_items))
        assert delta["sent"] > 0 and delta["received"] > 0, (r, delta)


def test_int8_carry_over_channel_fold_agreement_and_wire_cut():
    """The compressed tier end-to-end over a real socket pair, without
    an engine: each rank int8-encodes its block's f32 carry, the
    payloads cross the HostChannel, and BOTH ranks fold bitwise-equal
    totals (decode is deterministic f64 math on shared wire bytes).
    The measured per-round wire bytes must be < 1/3 of the raw f32
    bytes — the ISSUE-16 acceptance ratio, on the wire."""
    from fedml_tpu.parallel.carry_codec import Int8CarryCodec
    from fedml_tpu.parallel.multihost import (HostChannel,
                                              MultihostContext,
                                              fold_block_partials,
                                              free_port)
    dim = 4096
    rng = np.random.default_rng(7)
    vecs = {r: (3.0 * rng.standard_normal(dim)).astype(np.float32)
            for r in range(2)}
    port = free_port()
    out, errs = {}, []

    def run(r):
        try:
            codec = Int8CarryCodec()
            ctx = MultihostContext(rank=r, world=2,
                                   coordinator=f"localhost:{port}")
            ch = HostChannel(ctx, timeout_s=20.0,
                             connect_timeout_s=10.0)
            try:
                ch.mark_round()
                docs = ch.allgather(codec.encode(r, vecs[r]))
                total = fold_block_partials(
                    {b: codec.decode(docs[b]) for b in range(2)}, 2)
                out[r] = (total.tobytes(), ch.round_wire_delta())
            finally:
                ch.close()
        except Exception as e:
            errs.append((r, e))

    ts = [threading.Thread(target=run, args=(r,)) for r in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(30)
    assert not errs, errs
    assert out[0][0] == out[1][0], (
        "ranks folded different totals from identical wire bytes — "
        "int8 decode is not deterministic")
    raw_bytes = 2 * dim * 4             # what the f32 tier would ship
    for r in (0, 1):
        d = out[r][1]
        assert max(d["sent"], d["received"]) < raw_bytes / 3, (
            f"rank {r}: wire bytes {d} not under 1/3 of raw "
            f"{raw_bytes} — the compressed tier is not compressing")


def test_multihost_context_env_roundtrip(monkeypatch):
    from fedml_tpu.parallel.multihost import MultihostContext
    monkeypatch.delenv("FEDML_MH_RANK", raising=False)
    monkeypatch.delenv("FEDML_MH_WORLD", raising=False)
    assert MultihostContext.from_env() is None
    monkeypatch.setenv("FEDML_MH_RANK", "1")
    monkeypatch.setenv("FEDML_MH_WORLD", "3")
    monkeypatch.setenv("FEDML_MH_COORD", "localhost:123")
    ctx = MultihostContext.from_env()
    assert (ctx.rank, ctx.world, ctx.coordinator) == (1, 3,
                                                      "localhost:123")
    monkeypatch.setenv("FEDML_MH_RANK", "3")
    with pytest.raises(ValueError, match="outside world"):
        MultihostContext.from_env()
