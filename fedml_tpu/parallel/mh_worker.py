"""Multihost worker entry — one rank of a launched cluster.

    python -m fedml_tpu.parallel.mh_worker CONFIG.json

Reads its rank/world from the FEDML_MH_* env (set by
tools/launch_multihost.py / spawn_cluster), builds a synthetic
LR workload, drives MultihostRunner for the configured residency
mode(s), and prints ONE JSON line per rank:

    {"rank", "world", "n_blocks", "digests": {mode: md5},
     "rounds_per_sec", "carry_allreduce_bytes_per_round", ...}

Used by tests/test_multihost_spmd.py (the 2-vs-1-process bitwise pin,
the crash-of-one-rank naming case).  Not a test file itself.

Config keys (all optional; defaults in DEFAULTS):
    clients, spc, dim, classes, k_per_round, n_blocks, rounds, warmup,
    seed, modes ["streaming","resident"], local_devices, lr,
    channel_timeout_s, die_rank/die_at_round (crash injection: that
    rank hard-exits rc=3 at the end of that round), jax_distributed,
    eval (bool: report final test_acc from rank 0)

ISSUE 14 (elastic) keys:
    elastic (bool: ElasticRunner/ElasticChannel — rank death triggers a
    view change + block re-adoption instead of cluster teardown; a
    respawned rank with FEDML_MH_REJOIN=1 in its env rejoins the run),
    hang_rank/hang_at_round/hang_s (hang injection: that rank pauses
    its heartbeats and sleeps hang_s at the end of that round — the
    SIGSTOP shape; the coordinator must evict it via heartbeat timeout
    and the evicted rank exits rc=4 when it wakes into a closed
    channel), hb_timeout_s/hb_interval_s (elastic failure detector).

ISSUE 16 (compressed carry) keys:
    carry_codec ("f32" default escape hatch | "int8" | "int8_ef"),
    carry_chunk (f32 elements per quantization scale), and
    overlap_exchange (bool: pipeline each block's encoded carry under
    the remaining blocks' compute).
"""
import json
import os
import sys
import time

DEFAULTS = {
    "clients": 16, "spc": 24, "dim": 16, "classes": 10,
    "k_per_round": 8, "n_blocks": None, "rounds": 3, "warmup": 1,
    "seed": 0, "modes": ["streaming", "resident"], "local_devices": 1,
    "lr": 0.1, "channel_timeout_s": 60.0, "die_rank": None,
    "die_at_round": None, "jax_distributed": False, "eval": False,
    "elastic": False, "hang_rank": None, "hang_at_round": None,
    "hang_s": 20.0, "hb_timeout_s": 2.0, "hb_interval_s": 0.25,
    "round_sleep_s": 0.0, "round_sleep_mode": None,
    # ISSUE 16: compressed + overlapped carry exchange.  carry_codec
    # f32|int8|int8_ef (f32 = the bitwise escape hatch), carry_chunk =
    # f32 elements per quantization scale, overlap_exchange pipelines
    # each block's encoded carry under the remaining blocks' compute
    "carry_codec": "f32", "carry_chunk": None,
    "overlap_exchange": False,
    # ISSUE 18: serve_cluster (dict | None) routes the worker into the
    # fused serving cluster instead of the training engines — this
    # rank binds a reactor on its endpoint port and serves live-socket
    # uplinks into its registry-shard lanes, folding partials
    # cross-host at each commit barrier.  Keys (all optional):
    # population, commits, warmup_commits, buffer_k, row_dim,
    # connections, ingest_pool, window_deadline_s, timeout_s,
    # ports [per-rank endpoint list] | base_port (port = base + rank),
    # chaos {wire-fault dict}, chaos_seed, die_rank/die_at_commit
    # (crash injection: that rank hard-exits rc=3 after that many
    # commits — the survivors' next exchange evicts it), slo (bool),
    # sparse_uplink (bool — ISSUE 19: accept sparse_topk frames via
    # the decode_sparse -> jitted scatter-fold path).
    "serve_cluster": None,
}


def _setup_jax(cfg: dict) -> None:
    """Platform/device-count config — BEFORE any jax backend init (the
    init_multihost contract).  No persistent compile cache in a rank: one
    rank loading an entry while its peers compile skews them, and a skewed
    round can hang (ROADMAP D10).  The multi-process tier
    is host-level and CPU-only today: spawn_cluster_report hands every
    rank JAX_PLATFORMS=cpu explicitly (a parent may hold the chip); the
    setdefault only covers a worker started by hand."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count="
              f"{cfg['local_devices']}")


def build_case(cfg: dict):
    """Synthetic separable-LR federated case — same shape as
    tests/multihost_case.py but parameterized and package-local (the
    bench worker must not import tests/)."""
    import numpy as np
    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.data.federated import (FederatedData,
                                          build_client_shards,
                                          build_eval_shard)
    from fedml_tpu.models import create_model
    from fedml_tpu.parallel.engine import MeshFedAvgEngine
    from fedml_tpu.parallel.multihost import make_local_mesh
    from fedml_tpu.utils.config import FedConfig

    C, spc, dim, classes = (cfg["clients"], cfg["spc"], cfg["dim"],
                            cfg["classes"])
    bs = min(8, spc)
    rs = np.random.RandomState(7)
    n = C * spc
    w = rs.randn(dim, classes)
    x = rs.randn(n, dim).astype(np.float32)
    y = np.argmax(x @ w + 0.2 * rs.randn(n, classes),
                  axis=1).astype(np.int64)
    idx = {i: np.arange(i * spc, (i + 1) * spc) for i in range(C)}
    data = FederatedData(
        train_data_num=n, test_data_num=n,
        train_global=build_eval_shard(x, y, n),
        test_global=build_eval_shard(x, y, n),
        client_shards=build_client_shards(x, y, idx, bs),
        client_num_samples=np.full(C, spc, np.float32),
        test_client_shards=None, class_num=classes)
    fedcfg = FedConfig(client_num_in_total=C,
                       client_num_per_round=cfg["k_per_round"],
                       comm_round=cfg["rounds"], epochs=1,
                       batch_size=bs, lr=cfg["lr"], seed=cfg["seed"],
                       frequency_of_the_test=10_000)
    model = create_model("lr", output_dim=classes)

    def make_engine(streaming: bool):
        return MeshFedAvgEngine(ClientTrainer(model, lr=fedcfg.lr),
                                data, fedcfg, mesh=make_local_mesh(),
                                streaming=streaming)

    return make_engine


def _serve_cluster_main(ctx, cfg: dict) -> int:
    """ISSUE 18: one host of the fused serving cluster.  Builds the
    elastic channel (world > 1), runs run_cluster_serve on this rank's
    endpoint port, and prints ONE JSON line — the same contract the
    training route honors, so spawn_cluster_report parses both.  A
    rank with crash injection armed exits rc=3 WITHOUT a JSON line
    (the launcher's blame report names it; the survivors' reports are
    the evidence)."""
    import hashlib

    from fedml_tpu.parallel.multihost import ElasticChannel
    from fedml_tpu.scale.cluster import run_cluster_serve

    sc = dict(cfg["serve_cluster"])
    channel = None
    crashed = False
    if ctx.world > 1:
        # config digest covers the WHOLE worker config — a skewed rank
        # is rejected by name at hello, exactly as the training route
        digest = hashlib.md5(json.dumps(
            cfg, sort_keys=True).encode()).hexdigest()
        channel = ElasticChannel(
            ctx, n_items=ctx.world, config_digest=digest,
            timeout_s=cfg["channel_timeout_s"],
            hb_interval_s=cfg["hb_interval_s"],
            hb_timeout_s=cfg["hb_timeout_s"])
    ports = sc.get("ports")
    port = (int(ports[ctx.rank]) if ports
            else int(sc.get("base_port", 54300)) + ctx.rank)
    crash_at = (sc.get("die_at_commit")
                if sc.get("die_rank") == ctx.rank else None)
    try:
        report = run_cluster_serve(
            int(sc.get("population", 4096)),
            commits=int(sc.get("commits", 8)),
            warmup_commits=int(sc.get("warmup_commits", 2)),
            buffer_k=int(sc.get("buffer_k", 16)),
            row_dim=int(sc.get("row_dim", 256)),
            port=port, partition=(ctx.rank, ctx.world),
            channel=channel, elastic=ctx.world > 1,
            n_connections=int(sc.get("connections", 64)),
            ingest_pool=int(sc.get("ingest_pool", 2)),
            sparse_uplink=bool(sc.get("sparse_uplink", False)),
            window_deadline_s=float(sc.get("window_deadline_s", 10.0)),
            timeout_s=float(sc.get("timeout_s", 600.0)),
            chaos=sc.get("chaos"),
            chaos_seed=int(sc.get("chaos_seed", 0)),
            crash_at_commit=crash_at,
            slo_window=bool(sc.get("slo", ctx.rank == 0)))
        crashed = bool(crash_at is not None
                       and report.get("elastic", {})
                                .get("crashed_at_commit") is not None)
    finally:
        if channel is not None and not crashed:
            channel.close()
    if crashed:
        print(f"rank {ctx.rank}: injected crash at commit {crash_at}",
              file=sys.stderr, flush=True)
        os._exit(3)
    print(json.dumps({"rank": ctx.rank, "world": ctx.world,
                      "serve_cluster": report}), flush=True)
    return 0


def main(argv=None) -> int:
    argv = argv if argv is not None else sys.argv[1:]
    if len(argv) != 1:
        print("usage: python -m fedml_tpu.parallel.mh_worker CONFIG.json",
              file=sys.stderr)
        return 2
    with open(argv[0]) as f:
        cfg = {**DEFAULTS, **json.load(f)}
    _setup_jax(cfg)
    import hashlib

    import jax

    from fedml_tpu.parallel.multihost import (DeadRankError,
                                              ElasticChannel,
                                              ElasticRunner,
                                              HostChannel,
                                              MultihostContext,
                                              MultihostRunner,
                                              init_multihost,
                                              variables_digest)
    ctx = MultihostContext.from_env() or MultihostContext.single()
    if cfg["jax_distributed"] and ctx.jax_coordinator:
        init_multihost(coordinator_address=ctx.jax_coordinator,
                       num_processes=ctx.world, process_id=ctx.rank,
                       required=True)
    make_engine = build_case(cfg)
    n_blocks = cfg["n_blocks"] or ctx.world
    rejoining = (os.environ.get("FEDML_MH_REJOIN") == "1"
                 and ctx.rank != 0)
    obs_root = os.environ.get("FEDML_OBS_DIR")
    if obs_root:
        # per-RANK obs namespace, same scheme as the cli (ISSUE 17):
        # co-spawned workers handed one dir would race each other's
        # exports, and a rejoining incarnation reuses its rank id —
        # namespace it by pid so both incarnations' traces survive.
        # Enabling obs here also arms the telemetry piggybacks and the
        # coordinated-dump fan-out; with the env unset the wire stays
        # byte-identical to the pre-observatory channel.
        from fedml_tpu import obs
        sub = f"rank{ctx.rank}"
        if os.environ.get("FEDML_MH_REJOIN") == "1":
            sub = f"rank{ctx.rank}-pid{os.getpid()}"
        obs.configure(os.path.join(obs_root, sub))

    if cfg["serve_cluster"]:
        # ISSUE 18: the fused serving cluster — no training engines,
        # no residency modes; the rank serves live sockets instead
        return _serve_cluster_main(ctx, cfg)

    current_mode = {"mode": None}

    def on_round_end(round_idx: int) -> None:
        if cfg["round_sleep_s"] > 0 and (
                cfg["round_sleep_mode"] is None
                or cfg["round_sleep_mode"] == current_mode["mode"]):
            # pacing for the rejoin pins: synthetic rounds finish in
            # milliseconds, far faster than a respawned process can
            # boot jax — a per-round sleep holds the run open so the
            # rejoin handshake lands mid-run, deterministically
            # (round_sleep_mode scopes it to the run being rejoined)
            time.sleep(float(cfg["round_sleep_s"]))
        if (cfg["die_rank"] == ctx.rank
                and cfg["die_at_round"] == round_idx
                and not rejoining):
            print(f"rank {ctx.rank}: injected crash at round "
                  f"{round_idx}", file=sys.stderr, flush=True)
            os._exit(3)
        if (cfg["hang_rank"] == ctx.rank
                and cfg["hang_at_round"] == round_idx
                and not rejoining):
            # the SIGSTOP shape without stopping the OS process (a
            # truly stopped child never exits, which would wedge the
            # launcher): heartbeats pause, the rank goes silent for
            # hang_s, and the coordinator must evict it via heartbeat
            # timeout — waking into the closed channel exits rc=4
            print(f"rank {ctx.rank}: injected hang at round "
                  f"{round_idx} for {cfg['hang_s']:.0f}s",
                  file=sys.stderr, flush=True)
            channel.hb_paused = True
            time.sleep(float(cfg["hang_s"]))
            channel.hb_paused = False

    # ONE channel for the whole worker (both residency modes ride it;
    # re-binding the coordinator port between modes would race peers).
    # The elastic config digest covers the WHOLE worker config — any
    # skewed rank (or stale rejoiner) is rejected by name at hello.
    if cfg["elastic"]:
        digest = hashlib.md5(json.dumps(
            cfg, sort_keys=True).encode()).hexdigest()
        channel = ElasticChannel(
            ctx, n_items=n_blocks, config_digest=digest,
            timeout_s=cfg["channel_timeout_s"],
            hb_interval_s=cfg["hb_interval_s"],
            hb_timeout_s=cfg["hb_timeout_s"],
            rejoin=rejoining)
    else:
        channel = HostChannel(ctx, timeout_s=cfg["channel_timeout_s"])
    out = {"rank": ctx.rank, "world": ctx.world, "n_blocks": n_blocks,
           "elastic": bool(cfg["elastic"]),
           "rejoined": bool(rejoining),
           "digests": {}, "per_mode": {}}
    modes = list(cfg["modes"])
    for mode in modes:
        if mode not in ("streaming", "resident"):
            raise SystemExit(f"unknown residency mode {mode!r}")
    rejoin_state = None
    if cfg["elastic"] and rejoining:
        # handshake BEFORE building any engine: the SNAPSHOT's run tag
        # names which residency-mode run the coordinator is in — a
        # respawned process must resume THAT run, not replay the mode
        # list from the top (the sequential runs share one channel, so
        # rejoining the wrong one would cross-wire the exchanges)
        blob, resume_round, tag = channel.rejoin_handshake()
        if tag in modes:
            skipped, modes = modes[:modes.index(tag)], \
                modes[modes.index(tag):]
            if skipped:
                print(f"rank {ctx.rank}: rejoined into {tag!r}; "
                      f"skipping completed mode(s) {skipped}",
                      file=sys.stderr, flush=True)
        rejoin_state = (blob, resume_round)
    try:
        for mi, mode in enumerate(modes):
            current_mode["mode"] = mode
            engine = make_engine(streaming=(mode == "streaming"))
            codec_kw = {"carry_codec": cfg["carry_codec"],
                        "carry_chunk": cfg["carry_chunk"],
                        "overlap_exchange": cfg["overlap_exchange"]}
            if cfg["elastic"]:
                runner = ElasticRunner(
                    engine, ctx, n_blocks=n_blocks, channel=channel,
                    timeout_s=cfg["channel_timeout_s"],
                    hb_interval_s=cfg["hb_interval_s"],
                    hb_timeout_s=cfg["hb_timeout_s"],
                    run_tag=mode,
                    on_round_end=on_round_end, **codec_kw)
            else:
                runner = MultihostRunner(
                    engine, ctx, n_blocks=n_blocks, channel=channel,
                    timeout_s=cfg["channel_timeout_s"],
                    on_round_end=on_round_end, **codec_kw)
            t0 = time.perf_counter()
            try:
                if cfg["elastic"]:
                    # only the FIRST runner of a respawned process
                    # resumes mid-run; later modes start as a member
                    variables = runner.run(
                        rounds=cfg["rounds"], rejoin=False,
                        rejoin_state=(rejoin_state if mi == 0
                                      else None))
                else:
                    variables = runner.run(rounds=cfg["rounds"])
            except DeadRankError as e:
                if (cfg["hang_rank"] == ctx.rank
                        and not rejoining):
                    # the injected hang got this rank evicted — the
                    # intended outcome; exit distinctly so the
                    # launcher's blame report shows rc=4, not a crash
                    print(f"rank {ctx.rank}: evicted after injected "
                          f"hang: {e}", file=sys.stderr, flush=True)
                    return 4
                raise
            wall = time.perf_counter() - t0
            rep = runner.report(warmup_rounds=cfg["warmup"])
            rep["total_wall_s"] = wall
            out["digests"][mode] = variables_digest(variables)
            out["per_mode"][mode] = rep
            if cfg["eval"] and ctx.rank == 0:
                out.setdefault("eval", {})[mode] = \
                    engine.evaluate(variables)["test_acc"]
        # headline timing: the streaming mode when run, else the first
        head = ("streaming" if "streaming" in out["per_mode"]
                else next(iter(out["per_mode"])))
        out["rounds_per_sec"] = out["per_mode"][head]["rounds_per_sec"]
        out["carry_allreduce_bytes_per_round"] = \
            out["per_mode"][head]["carry_allreduce_bytes_per_round"]
        for k in ("carry_codec", "carry_compression_ratio",
                  "carry_wire_sent_bytes_per_round",
                  "carry_payload_bytes_per_round",
                  "carry_raw_bytes_per_round", "overlap_fraction"):
            out[k] = out["per_mode"][head][k]
        if ctx.rank == 0:
            # cluster observatory (ISSUE 17): the coordinator's barrier
            # ledger + cluster SLO verdict ride the worker doc — both
            # are always-on local bookkeeping, so the bench straggler
            # block and the spawned test pins read them without
            # enabling obs
            from fedml_tpu.obs import cluster as cluster_mod
            out["straggler"] = cluster_mod.straggler_summary()
            out["cluster_slo"] = cluster_mod.cluster_slo_report()
        out["jax"] = jax.__version__
        print(json.dumps(out), flush=True)
    finally:
        channel.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
