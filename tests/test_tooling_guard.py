"""CI/tooling guards: pyproject's pytest addopts must stay xdist-free,
and bench.py's JSON line must keep its schema contract.

An unconditional `-n auto` in addopts once killed EVERY pytest run in
this image — pytest-xdist is not installed here, so pytest dies with
"unrecognized arguments: -n" before collecting a single test, including
the driver's tier-1 command (which even passes `-p no:xdist`).  PR 1
removed it; this test keeps it removed.  Parallelism stays an explicit
opt-in on boxes that have xdist: `pytest -n auto --maxprocesses 8`.
"""
import os
import re

PYPROJECT = os.path.join(os.path.dirname(__file__), "..", "pyproject.toml")
BENCH = os.path.join(os.path.dirname(__file__), "..", "bench.py")


def _addopts() -> str:
    text = open(PYPROJECT).read()
    try:
        import tomllib
        opts = (tomllib.loads(text).get("tool", {}).get("pytest", {})
                .get("ini_options", {}).get("addopts", ""))
    except ModuleNotFoundError:               # python 3.10: regex fallback
        m = re.search(r'^addopts\s*=\s*"(.*)"\s*$', text, re.M)
        opts = m.group(1) if m else ""
    if isinstance(opts, list):
        opts = " ".join(opts)
    return opts


def test_addopts_never_hardcodes_xdist():
    opts = _addopts()
    tokens = opts.split()
    assert "-n" not in tokens and "--numprocesses" not in tokens, (
        f"pyproject addopts={opts!r} reintroduces pytest-xdist flags: "
        "xdist is absent in the CI image and this kills every pytest "
        "run with 'unrecognized arguments: -n' (see PR-1 history)")
    assert "--dist" not in tokens and "--maxprocesses" not in tokens, (
        f"addopts={opts!r} carries xdist-only companions that fail "
        "without the plugin")


def test_bench_json_schema_carries_byte_accounting():
    """BENCH_*.json trajectory consumers key on schema_version; the
    transfer-compression fields (h2d_bytes_per_round in the JSON line,
    h2d_bytes in the per-round records via TransferOverlapStats) landed
    in v3 — a refactor that drops them or forgets the version bump
    would silently fork the trajectory format.  Static source check:
    running the bench needs a chip."""
    src = open(BENCH).read()
    m = re.search(r"^SCHEMA_VERSION\s*=\s*(\d+)", src, re.M)
    assert m, "bench.py lost its SCHEMA_VERSION constant"
    assert int(m.group(1)) >= 3, (
        "bench schema must stay >= v3 (byte accounting)")
    assert '"h2d_bytes_per_round"' in src, (
        "bench.py JSON line lost the h2d_bytes_per_round field "
        "(schema v3 byte accounting)")
    # the per-round records inherit h2d_bytes from the profiler
    prof = open(os.path.join(os.path.dirname(__file__), "..",
                             "fedml_tpu", "utils", "profiling.py")).read()
    assert '"h2d_bytes"' in prof, (
        "TransferOverlapStats round records lost the h2d_bytes field")


def test_bench_json_schema_v4_carries_async_block():
    """ISSUE 5: schema v4 adds the async-mode fields — the "mode" key on
    every line (v3 readers that ignore unknown keys keep working) and
    the "async" block with committed updates, staleness percentiles and
    buffer occupancy from `python bench.py --mode async`.  Static source
    check like the v3 guard."""
    src = open(BENCH).read()
    m = re.search(r"^SCHEMA_VERSION\s*=\s*(\d+)", src, re.M)
    assert int(m.group(1)) >= 4, (
        "bench schema must stay >= v4 (async federation block)")
    for field in ('"mode"', '"async"', "staleness_p50", "staleness_p95",
                  "buffer_occupancy_mean", "committed_updates"):
        assert field in src, (
            f"bench.py lost the v4 async field {field} "
            "(see fedml_tpu/async_ and _bench_async)")
    # the async block's numbers come from the engine's rollup — the
    # field names above must stay in sync with it
    sched = open(os.path.join(os.path.dirname(__file__), "..",
                              "fedml_tpu", "async_", "scheduler.py")).read()
    for field in ("committed_updates", "staleness_p50", "staleness_p95",
                  "buffer_occupancy_mean"):
        assert field in sched, (
            f"AsyncFedAvgEngine.async_report lost {field!r} — bench.py's "
            "v4 async block reads it")


def test_copy_audit_ceilings_artifact_exists():
    """ISSUE 4: the copy-regression gate needs its pinned artifacts —
    the per-family ceilings (with a machine-readable calibration env)
    and the committed pre-PR baseline the FedAvg reduction is asserted
    against.  Losing either silently disarms the gate."""
    import json
    bench_dir = os.path.join(os.path.dirname(__file__), "..", "benchmarks")
    ceil = json.load(open(os.path.join(bench_dir,
                                       "hlo_copy_ceilings.json")))
    assert ceil["families"], "ceilings artifact carries no families"
    for fam, pins in ceil["families"].items():
        assert pins["copy_bytes_ceiling"] >= 0, fam
    for key in ("jax", "jaxlib", "date"):
        assert key in ceil["calibration"], (
            f"ceilings calibration env lost {key!r} (the recalibrate "
            "protocol needs it to name version skew)")
    base = json.load(open(os.path.join(bench_dir,
                                       "hlo_copy_baseline.json")))
    assert "fedavg_resident" in base["families"]


def test_chip_queue_carries_donate_ab():
    """ISSUE 4: the next chip window must price the donate/carry A/B —
    scripts/run_chip_queue.sh carries the DN128 experiment (and stays
    shell-valid: the round-1 unclosed-paren regression)."""
    import subprocess
    queue = os.path.join(os.path.dirname(__file__), "..", "scripts",
                         "run_chip_queue.sh")
    src = open(queue).read()
    assert "DN128" in src, (
        "run_chip_queue.sh lost the DN128 donate on/off A/B "
        "(ISSUE 4 queues it for the next chip window)")
    assert "exp_DN128" in open(os.path.join(
        os.path.dirname(__file__), "..", "tools",
        "profile_bench.py")).read(), (
        "profile_bench.py lost the exp_DN128 experiment the queue runs")
    r = subprocess.run(["bash", "-n", queue], capture_output=True,
                       text=True)
    assert r.returncode == 0, r.stderr


def test_chip_queue_carries_async_ab():
    """ISSUE 5: the next chip window must price the async federation —
    scripts/run_chip_queue.sh carries the ASYNC A/B step and
    profile_bench.py defines the exp_ASYNC experiment it runs."""
    queue = os.path.join(os.path.dirname(__file__), "..", "scripts",
                         "run_chip_queue.sh")
    assert "profile_bench.py ASYNC" in open(queue).read(), (
        "run_chip_queue.sh lost the ASYNC buffered-aggregation A/B "
        "(ISSUE 5 queues it for the next chip window)")
    assert "exp_ASYNC" in open(os.path.join(
        os.path.dirname(__file__), "..", "tools",
        "profile_bench.py")).read(), (
        "profile_bench.py lost the exp_ASYNC experiment the queue runs")


def test_bench_json_schema_v5_carries_ingest_block():
    """ISSUE 6: schema v5 adds the ingest-mode fields — the "ingest"
    block from `python bench.py --mode ingest` with the legacy arm, the
    decode-into+streaming pool arms, decode percentiles, lock-wait and
    the speedup_vs_legacy headline the >=2x acceptance gate reads.
    Static source check like the v3/v4 guards."""
    src = open(BENCH).read()
    m = re.search(r"^SCHEMA_VERSION\s*=\s*(\d+)", src, re.M)
    assert int(m.group(1)) >= 5, (
        "bench schema must stay >= v5 (uplink-ingestion block)")
    for field in ('"ingest"', '"legacy"', '"legacy_bounded_inbox"',
                  '"arms"', "speedup_vs_legacy", "decode_p50_s",
                  "decode_p95_s", "lock_wait_seconds",
                  "committed_updates_per_sec"):
        assert field in src, (
            f"bench.py lost the v5 ingest field {field} "
            "(see fedml_tpu/async_/torture.py and _bench_ingest)")
    # the block's numbers come from the torture harness — names must
    # stay in sync with its report dict
    tort = open(os.path.join(os.path.dirname(__file__), "..",
                             "fedml_tpu", "async_", "torture.py")).read()
    for field in ("committed_updates_per_sec", "decode_p50_s",
                  "decode_p95_s", "lock_wait_seconds"):
        assert field in tort, (
            f"run_ingest_torture's report lost {field!r} — bench.py's "
            "v5 ingest block reads it")


def test_chip_queue_carries_ingest_ab():
    """ISSUE 6: the next chip window must price the ingestion A/B —
    scripts/run_chip_queue.sh carries the INGEST step and
    profile_bench.py defines the exp_INGEST experiment it runs."""
    queue = os.path.join(os.path.dirname(__file__), "..", "scripts",
                         "run_chip_queue.sh")
    assert "profile_bench.py INGEST" in open(queue).read(), (
        "run_chip_queue.sh lost the INGEST uplink-ingestion A/B "
        "(ISSUE 6 queues it for the next chip window)")
    assert "exp_INGEST" in open(os.path.join(
        os.path.dirname(__file__), "..", "tools",
        "profile_bench.py")).read(), (
        "profile_bench.py lost the exp_INGEST experiment the queue runs")
    import subprocess
    r = subprocess.run(["bash", "-n", queue], capture_output=True,
                       text=True)
    assert r.returncode == 0, r.stderr


def test_bench_json_schema_v6_carries_critical_path():
    """ISSUE 7: schema v6 adds the "critical_path" block — per-round
    stage attribution from the span timeline (stage_totals_s,
    stage_share, round_wall_p50/p95_s, p95_attribution) on every bench
    mode, null when the run is untraced.  Static source check like the
    v3/v4/v5 guards."""
    src = open(BENCH).read()
    m = re.search(r"^SCHEMA_VERSION\s*=\s*(\d+)", src, re.M)
    assert int(m.group(1)) >= 6, (
        "bench schema must stay >= v6 (critical_path block)")
    for field in ('"critical_path"', "_critical_path_doc"):
        assert field in src, (
            f"bench.py lost the v6 critical-path field {field} "
            "(see fedml_tpu/obs/timeline.py)")
    # the block's fields come from the analyzer — names must stay in
    # sync with timeline.critical_path's report dict
    tl = open(os.path.join(os.path.dirname(__file__), "..",
                           "fedml_tpu", "obs", "timeline.py")).read()
    for field in ("stage_totals_s", "stage_share", "round_wall_p95_s",
                  "p95_attribution"):
        assert field in tl, (
            f"timeline.critical_path lost {field!r} — bench.py's v6 "
            "critical_path block reads it")
    # and the CLI tool that renders it must exist
    assert os.path.exists(os.path.join(
        os.path.dirname(__file__), "..", "tools", "trace_timeline.py")), (
        "tools/trace_timeline.py (the merge/report CLI) is gone")


def test_chip_queue_carries_trace_ab():
    """ISSUE 7: the next chip window must price the tracing overhead —
    scripts/run_chip_queue.sh carries the TRACE step (traced vs
    untraced ingest torture, < 5% gate) and profile_bench.py defines
    the exp_TRACE experiment it runs."""
    queue = os.path.join(os.path.dirname(__file__), "..", "scripts",
                         "run_chip_queue.sh")
    assert "profile_bench.py TRACE" in open(queue).read(), (
        "run_chip_queue.sh lost the TRACE traced-vs-untraced overhead "
        "A/B (ISSUE 7 queues it for the next chip window)")
    assert "exp_TRACE" in open(os.path.join(
        os.path.dirname(__file__), "..", "tools",
        "profile_bench.py")).read(), (
        "profile_bench.py lost the exp_TRACE experiment the queue runs")
    import subprocess
    r = subprocess.run(["bash", "-n", queue], capture_output=True,
                       text=True)
    assert r.returncode == 0, r.stderr


def test_bench_json_schema_v7_carries_chaos_block():
    """ISSUE 8: schema v7 adds the chaos-mode fields — the "chaos"
    block from `python bench.py --mode chaos` with the clean reliable
    arm, the goodput-vs-fault-rate curve, the mixed acceptance arm and
    its goodput_vs_clean headline, plus the retry/dedup/quarantine/
    recv-death counters every row carries.  Static source check like
    the v3-v6 guards."""
    src = open(BENCH).read()
    m = re.search(r"^SCHEMA_VERSION\s*=\s*(\d+)", src, re.M)
    assert int(m.group(1)) >= 7, (
        "bench schema must stay >= v7 (chaos block)")
    for field in ('"chaos"', '"clean"', '"curve"', '"mixed"',
                  "goodput_ratio", "goodput_vs_clean", "retries",
                  "dups_suppressed", "quarantined",
                  "recv_thread_deaths", "_bench_chaos"):
        assert field in src, (
            f"bench.py lost the v7 chaos field {field} "
            "(see fedml_tpu/comm/chaos.py and _bench_chaos)")
    # the block's numbers come from the torture harness's chaos report
    tort = open(os.path.join(os.path.dirname(__file__), "..",
                             "fedml_tpu", "async_", "torture.py")).read()
    for field in ("chaos_injected", "dups_suppressed", "quarantined",
                  "recv_thread_deaths", "abandoned"):
        assert field in tort, (
            f"run_ingest_torture's report lost {field!r} — bench.py's "
            "v7 chaos block reads it")
    # and the layer itself must exist
    for mod in ("chaos.py", "reliability.py"):
        assert os.path.exists(os.path.join(
            os.path.dirname(__file__), "..", "fedml_tpu", "comm", mod)), (
            f"fedml_tpu/comm/{mod} (the ISSUE-8 robustness layer) is gone")


def test_bench_json_schema_v8_carries_attack_block():
    """ISSUE 9: schema v8 adds the attack-mode fields — the "attack"
    block from `python bench.py --mode attack` with the attack x
    defense accuracy "matrix", the mixed acceptance trio (clean_acc /
    undefended_acc / defended_acc), the false-positive count, and the
    admission-overhead pair whose throughput_ratio is the >=0.9x gate.
    Static source check like the v3-v7 guards."""
    src = open(BENCH).read()
    m = re.search(r"^SCHEMA_VERSION\s*=\s*(\d+)", src, re.M)
    assert int(m.group(1)) >= 8, (
        "bench schema must stay >= v8 (adversarial-robustness block)")
    for field in ('"attack"', '"matrix"', '"overhead"', "_bench_attack",
                  "clean_acc", "defended_acc", "undefended_acc",
                  "false_positive_quarantines", "throughput_ratio",
                  "quarantined_byzantine", "quarantined_honest"):
        assert field in src, (
            f"bench.py lost the v8 attack field {field} "
            "(see fedml_tpu/async_/adversary.py + defense.py and "
            "_bench_attack)")
    # the block's accuracy rows come from the async engine's rollup and
    # the torture report's admission block — names must stay in sync
    sched = open(os.path.join(os.path.dirname(__file__), "..",
                              "fedml_tpu", "async_", "scheduler.py")).read()
    assert "quarantine_attribution" in sched, (
        "AsyncFedAvgEngine lost quarantine_attribution — bench.py's v8 "
        "attack block reads it")
    defn = open(os.path.join(os.path.dirname(__file__), "..",
                             "fedml_tpu", "async_", "defense.py")).read()
    assert "quarantined_total" in defn, (
        "UpdateAdmission.report lost quarantined_total — bench.py's v8 "
        "attack block reads it through async_report")
    tort = open(os.path.join(os.path.dirname(__file__), "..",
                             "fedml_tpu", "async_", "torture.py")).read()
    assert '"admission"' in tort, (
        "run_ingest_torture's report lost the admission block — the v8 "
        "overhead pair reads it")
    # and the layer itself must exist
    for mod in ("adversary.py", "defense.py"):
        assert os.path.exists(os.path.join(
            os.path.dirname(__file__), "..", "fedml_tpu", "async_", mod)), (
            f"fedml_tpu/async_/{mod} (the ISSUE-9 robustness layer) is "
            "gone")


def test_chip_queue_carries_attack_ab():
    """ISSUE 9: the next chip window must price the attack x defense
    matrix — scripts/run_chip_queue.sh carries the ATTACK step (11/11)
    and profile_bench.py defines the exp_ATTACK experiment it runs."""
    queue = os.path.join(os.path.dirname(__file__), "..", "scripts",
                         "run_chip_queue.sh")
    assert "profile_bench.py ATTACK" in open(queue).read(), (
        "run_chip_queue.sh lost the ATTACK adversarial-robustness A/B "
        "(ISSUE 9 queues it for the next chip window)")
    assert "exp_ATTACK" in open(os.path.join(
        os.path.dirname(__file__), "..", "tools",
        "profile_bench.py")).read(), (
        "profile_bench.py lost the exp_ATTACK experiment the queue runs")
    import subprocess
    r = subprocess.run(["bash", "-n", queue], capture_output=True,
                       text=True)
    assert r.returncode == 0, r.stderr


def test_bench_json_schema_v9_carries_serve_block():
    """ISSUE 10: schema v9 adds the serve-mode fields — the "serve"
    block from `python bench.py --mode serve` with one row per
    simulated population carrying committed_updates_per_sec,
    registry_bytes / registry_bytes_per_client (the <= ~100 B/client
    sub-linear-memory gate in "sublinear_ok"), sampler scratch, RSS and
    the sustain ratio.  Static source check like the v3-v8 guards."""
    src = open(BENCH).read()
    m = re.search(r"^SCHEMA_VERSION\s*=\s*(\d+)", src, re.M)
    assert int(m.group(1)) >= 9, (
        "bench schema must stay >= v9 (serving-spine block)")
    for field in ('"serve"', '"populations"', "_bench_serve",
                  "registry_bytes_per_client", "sublinear_ok",
                  "sustain_ratio_vs_smallest",
                  "sampler_peak_scratch_bytes", "rss_bytes"):
        assert field in src, (
            f"bench.py lost the v9 serve field {field} "
            "(see fedml_tpu/scale/serve.py and _bench_serve)")
    # the block's numbers come from the serve sim's report — names must
    # stay in sync with run_serve_sim's dict
    srv = open(os.path.join(os.path.dirname(__file__), "..",
                            "fedml_tpu", "scale", "serve.py")).read()
    for field in ("committed_updates_per_sec", "registry_bytes_per_client",
                  "sampler_peak_scratch_bytes", "rss_bytes",
                  "virtual_time_s"):
        assert field in srv, (
            f"run_serve_sim's report lost {field!r} — bench.py's v9 "
            "serve block reads it")
    # and the subsystem itself must exist
    for mod in ("registry.py", "sampler.py", "shardstore.py",
                "arrivals.py", "serve.py"):
        assert os.path.exists(os.path.join(
            os.path.dirname(__file__), "..", "fedml_tpu", "scale", mod)), (
            f"fedml_tpu/scale/{mod} (the ISSUE-10 serving spine) is gone")


def test_chip_queue_carries_serve_step():
    """ISSUE 10: the next chip window must price the serving spine —
    scripts/run_chip_queue.sh carries the SERVE step (12/12) and
    profile_bench.py defines the exp_SERVE experiment it runs."""
    queue = os.path.join(os.path.dirname(__file__), "..", "scripts",
                         "run_chip_queue.sh")
    assert "profile_bench.py SERVE" in open(queue).read(), (
        "run_chip_queue.sh lost the SERVE million-client serving-spine "
        "step (ISSUE 10 queues it for the next chip window)")
    assert "exp_SERVE" in open(os.path.join(
        os.path.dirname(__file__), "..", "tools",
        "profile_bench.py")).read(), (
        "profile_bench.py lost the exp_SERVE experiment the queue runs")
    import subprocess
    r = subprocess.run(["bash", "-n", queue], capture_output=True,
                       text=True)
    assert r.returncode == 0, r.stderr


def test_bench_json_schema_v10_carries_connections_block():
    """ISSUE 11: schema v10 adds the connections-mode fields — the
    "connections" block from `python bench.py --mode connections` with
    one row per live-connection count, each carrying a clean / chaos /
    storm arm (committed_updates_per_sec, admission p50/p95, peak open
    connections, the evicted{stall|rate|shed} + uplinks_shed +
    recv_thread_deaths + fd_leaked counters, loop-lag p95) and the
    storm_goodput_ratio headline.  Static source check like the v3-v9
    guards."""
    src = open(BENCH).read()
    m = re.search(r"^SCHEMA_VERSION\s*=\s*(\d+)", src, re.M)
    assert int(m.group(1)) >= 10, (
        "bench schema must stay >= v10 (live-connection block)")
    for field in ('"connections"', "_bench_connections",
                  "admission_p50_s", "admission_p95_s",
                  "storm_goodput_ratio", "open_connections_peak",
                  "uplinks_shed", "fd_leaked", "loop_lag_p95_s"):
        assert field in src, (
            f"bench.py lost the v10 connections field {field} "
            "(see fedml_tpu/comm/reactor.py and _bench_connections)")
    # the block's numbers come from the connection torture's report —
    # names must stay in sync
    tort = open(os.path.join(os.path.dirname(__file__), "..",
                             "fedml_tpu", "async_", "torture.py")).read()
    for field in ("run_connection_torture", "admission_p95_s",
                  "open_connections_peak", "fd_leaked", "uplinks_shed",
                  "loop_lag_p95_s"):
        assert field in tort, (
            f"run_connection_torture's report lost {field!r} — "
            "bench.py's v10 connections block reads it")
    # and the transport layer itself must exist
    for mod in ("reactor.py", "connswarm.py"):
        assert os.path.exists(os.path.join(
            os.path.dirname(__file__), "..", "fedml_tpu", "comm", mod)), (
            f"fedml_tpu/comm/{mod} (the ISSUE-11 reactor transport) is "
            "gone")


def test_chip_queue_carries_conn_step():
    """ISSUE 11: the next chip window must price the live-connection
    reactor — scripts/run_chip_queue.sh carries the CONN step (13/13)
    and profile_bench.py defines the exp_CONN experiment it runs."""
    queue = os.path.join(os.path.dirname(__file__), "..", "scripts",
                         "run_chip_queue.sh")
    src = open(queue).read()
    assert "profile_bench.py CONN" in src, (
        "run_chip_queue.sh lost the CONN live-connection reactor step "
        "(ISSUE 11 queues it for the next chip window)")
    assert "13/21" in src, (
        "run_chip_queue.sh lost the CONN step numbering (13/21 since "
        "ISSUEs 12-17 appended bench_diff, exp_POD, exp_ELASTIC, the "
        "compressed-carry arm and the straggler observatory arm)")
    assert "exp_CONN" in open(os.path.join(
        os.path.dirname(__file__), "..", "tools",
        "profile_bench.py")).read(), (
        "profile_bench.py lost the exp_CONN experiment the queue runs")
    import subprocess
    r = subprocess.run(["bash", "-n", queue], capture_output=True,
                       text=True)
    assert r.returncode == 0, r.stderr


def test_bench_json_schema_v11_carries_slo_and_programs_blocks():
    """ISSUE 12: schema v11 adds the judgment layer's fields on every
    mode — the "slo" block (the default serving-spine pack's per-arm
    breach verdicts from fedml_tpu/obs/slo.py) and the "programs" block
    (the per-jit-program-family dispatch/MFU profile from
    fedml_tpu/obs/programs.py).  Static source check like the v3-v10
    guards."""
    src = open(BENCH).read()
    m = re.search(r"^SCHEMA_VERSION\s*=\s*(\d+)", src, re.M)
    assert int(m.group(1)) >= 11, (
        "bench schema must stay >= v11 (slo + programs blocks)")
    for field in ('"slo"', '"programs"', "_slo_doc", "_programs_doc",
                  "_slo_window"):
        assert field in src, (
            f"bench.py lost the v11 observability field {field} "
            "(see fedml_tpu/obs/slo.py + programs.py)")
    # the torture harness feeds the per-arm verdicts
    tort = open(os.path.join(os.path.dirname(__file__), "..",
                             "fedml_tpu", "async_", "torture.py")).read()
    for field in ('"slo_arm"', "default_slo_pack"):
        assert field in tort, (
            f"torture.py lost {field!r} — bench.py's v11 slo block "
            "reads the per-arm summaries from the torture reports")
    # the layer itself must exist
    for mod in ("slo.py", "programs.py"):
        assert os.path.exists(os.path.join(
            os.path.dirname(__file__), "..", "fedml_tpu", "obs", mod)), (
            f"fedml_tpu/obs/{mod} (the ISSUE-12 observatory) is gone")
    # and the profile registry must keep its report fields in sync
    prog = open(os.path.join(os.path.dirname(__file__), "..",
                             "fedml_tpu", "obs", "programs.py")).read()
    for field in ("dispatch_wall_s", "dispatch_p95_s",
                  "flops_per_dispatch"):
        assert field in prog, (
            f"programs.report lost {field!r} — bench.py's v11 programs "
            "block reads it")


def test_bench_json_schema_v12_carries_multihost_block():
    """ISSUE 13: schema v12 adds the multihost weak-scaling block — the
    two-level-aggregation sweep fields (rows per process count with
    rounds/sec + carry-allreduce bytes, weak_efficiency_2p and the
    bitwise_2proc_ok pin) — and the machinery it runs on (the
    spawn_cluster launcher, the mh_worker entry, the HostChannel).
    Static source check like the v3-v11 guards."""
    src = open(BENCH).read()
    m = re.search(r"^SCHEMA_VERSION\s*=\s*(\d+)", src, re.M)
    assert int(m.group(1)) >= 12, (
        "bench schema must stay >= v12 (multihost weak-scaling block)")
    for field in ('"multihost"', "_bench_multihost",
                  "weak_efficiency_2p", "bitwise_2proc_ok",
                  "carry_allreduce_bytes_per_round", "spawn_cluster"):
        assert field in src, (
            f"bench.py lost the v12 multihost field {field} "
            "(see fedml_tpu/parallel/multihost.py)")
    base = os.path.join(os.path.dirname(__file__), "..")
    # the runtime pieces the mode drives must exist
    for path in (os.path.join("fedml_tpu", "parallel", "mh_worker.py"),
                 os.path.join("tools", "launch_multihost.py")):
        assert os.path.exists(os.path.join(base, path)), (
            f"{path} (the ISSUE-13 multihost runtime) is gone")
    mh = open(os.path.join(base, "fedml_tpu", "parallel",
                           "multihost.py")).read()
    for sym in ("class HostChannel", "class MultihostRunner",
                "class DeadRankError", "def fold_block_partials",
                "def spawn_cluster"):
        assert sym in mh, (
            f"fedml_tpu/parallel/multihost.py lost {sym!r} — the "
            "two-level runtime the v12 bench mode drives")
    # bench_diff must judge the new block
    bd = open(os.path.join(base, "tools", "bench_diff.py")).read()
    for field in ("weak_efficiency_2p", '"multihost"'):
        assert field in bd, (
            f"tools/bench_diff.py lost the multihost rule field "
            f"{field} (the v12 acceptance gate)")


def test_bench_json_schema_v13_carries_elastic_chaos_arm():
    """ISSUE 14: schema v13 adds the elastic chaos arm to the
    multihost block — survivor_goodput_ratio (>= 0.5x gate),
    view-change latency/count, survivor_deaths and the
    bitwise_after_death_ok pin — plus the elastic runtime it drives
    (ElasticChannel membership/heartbeats/rejoin, ElasticRunner block
    re-adoption, the spawn_cluster elastic/respawn launch policy) and
    the chip-queue ELASTIC step.  Static source check like the v3-v12
    guards."""
    src = open(BENCH).read()
    m = re.search(r"^SCHEMA_VERSION\s*=\s*(\d+)", src, re.M)
    assert int(m.group(1)) >= 13, (
        "bench schema must stay >= v13 (elastic chaos arm)")
    for field in ("survivor_goodput_ratio", "bitwise_after_death_ok",
                  "view_change_latency_s", "survivor_deaths",
                  "mh_chaos_procs", "mh_arms"):
        assert field in src, (
            f"bench.py lost the v13 elastic-chaos field {field} "
            "(see fedml_tpu/parallel/multihost.py ISSUE 14)")
    base = os.path.join(os.path.dirname(__file__), "..")
    mh = open(os.path.join(base, "fedml_tpu", "parallel",
                           "multihost.py")).read()
    for sym in ("class ElasticChannel", "class ElasticRunner",
                "class ClusterView", "def spawn_cluster_report",
                "def rejoin_handshake", "def admit_rejoins",
                "def _dial_with_backoff"):
        assert sym in mh, (
            f"fedml_tpu/parallel/multihost.py lost {sym!r} — the "
            "ISSUE-14 elastic runtime the v13 chaos arm drives")
    # fail-fast must stay the DEFAULT launch policy
    assert re.search(r"elastic:\s*bool\s*=\s*False", mh), (
        "spawn_cluster's elastic policy must default OFF (fail-fast "
        "kill-the-rest is the documented default)")
    # bench_diff must judge the new fields
    bd = open(os.path.join(base, "tools", "bench_diff.py")).read()
    for field in ("survivor_goodput_ratio", "bitwise_after_death_ok",
                  "survivor_deaths"):
        assert field in bd, (
            f"tools/bench_diff.py lost the elastic-chaos rule field "
            f"{field} (the v13 acceptance gate)")
    # serve-loop re-adoption + cli wiring
    serve = open(os.path.join(base, "fedml_tpu", "scale",
                              "serve.py")).read()
    assert "_ServeLane" in serve and "elastic" in serve, (
        "fedml_tpu/scale/serve.py lost the elastic lane re-adoption "
        "(ISSUE 14 satellite)")
    cli = open(os.path.join(base, "fedml_tpu", "cli.py")).read()
    assert "--elastic" in cli and "ElasticRunner" in cli, (
        "fedml_tpu/cli.py lost the --elastic wiring (fail-fast "
        "default, elastic opt-in)")
    # chip queue: the ELASTIC step + its experiment
    queue = open(os.path.join(base, "scripts",
                              "run_chip_queue.sh")).read()
    assert "profile_bench.py ELASTIC" in queue and "17/21" in queue, (
        "run_chip_queue.sh lost the ELASTIC chaos step (ISSUE 14 "
        "queues it for the next chip window; ISSUE 16 renumbered it "
        "17 when the compressed-carry arm landed as 16, ISSUE 17 "
        "appended the straggler observatory arm as 18)")
    assert "exp_ELASTIC" in open(os.path.join(
        base, "tools", "profile_bench.py")).read(), (
        "profile_bench.py lost the exp_ELASTIC experiment the queue "
        "runs")


def test_chip_queue_carries_pod_step():
    """ISSUE 13: the next chip window must price the multi-host
    weak-scaling sweep on a real pod slice —
    scripts/run_chip_queue.sh carries the POD step (15/21 since
    ISSUEs 14-17 appended the ELASTIC arm, the compressed-carry arm
    and the straggler observatory arm) and profile_bench.py defines
    the exp_POD experiment it runs."""
    queue = os.path.join(os.path.dirname(__file__), "..", "scripts",
                         "run_chip_queue.sh")
    src = open(queue).read()
    assert "profile_bench.py POD" in src, (
        "run_chip_queue.sh lost the POD multi-host weak-scaling sweep "
        "(ISSUE 13 queues it for the next chip window)")
    assert "15/21" in src, (
        "run_chip_queue.sh lost the 15/21 step numbering (exp_POD is "
        "queue step 15; ISSUE 16's compressed arm is 16, ISSUE 14's "
        "exp_ELASTIC is 17, ISSUE 17's straggler arm is 18)")
    assert "exp_POD" in open(os.path.join(
        os.path.dirname(__file__), "..", "tools",
        "profile_bench.py")).read(), (
        "profile_bench.py lost the exp_POD experiment the queue runs")
    import subprocess
    r = subprocess.run(["bash", "-n", queue], capture_output=True,
                       text=True)
    assert r.returncode == 0, r.stderr


def test_bench_json_schema_v14_carries_compressed_carry_arm():
    """ISSUE 16: schema v14 adds the compressed-carry arm to the
    multihost block — bytes-on-wire measured ON the channel,
    compression ratio, efficiency-at-constant-bytes, overlap fraction
    and the f32-escape-hatch bitwise pin — plus the runtime it drives
    (the carry codec registry, the two-phase overlapped gather on
    HostChannel, early contributions on ElasticChannel, the cli
    wiring) and the renumbered chip-queue step.  Static source check
    like the v3-v13 guards."""
    src = open(BENCH).read()
    m = re.search(r"^SCHEMA_VERSION\s*=\s*(\d+)", src, re.M)
    assert int(m.group(1)) >= 14, (
        "bench schema must stay >= v14 (compressed-carry arm)")
    for field in ('"compress"', "carry_wire_bytes_per_round",
                  "carry_compression_ratio", "wire_reduction_vs_f32",
                  "efficiency_at_constant_bytes", "overlap_fraction",
                  "bitwise_f32_escape_ok", "acc_delta_vs_f32"):
        assert field in src, (
            f"bench.py lost the v14 compressed-carry field {field} "
            "(see fedml_tpu/parallel/carry_codec.py ISSUE 16)")
    base = os.path.join(os.path.dirname(__file__), "..")
    # the codec module: registry + the three wire tiers
    codec = open(os.path.join(base, "fedml_tpu", "parallel",
                              "carry_codec.py")).read()
    for sym in ("CARRY_CODECS", "class CarryCodec",
                "class Int8CarryCodec", "class Int8EFCarryCodec",
                "def make_carry_codec"):
        assert sym in codec, (
            f"fedml_tpu/parallel/carry_codec.py lost {sym!r} — the "
            "ISSUE-16 wire tier the v14 compress arm drives")
    # f32 must stay the registry DEFAULT (the bitwise escape hatch)
    assert re.search(r'CARRY_CODECS\s*=\s*\(\s*"f32"', codec), (
        "the carry codec registry must keep f32 first/default — the "
        "PR-13/14 bitwise anchors ride it")
    # the overlap substrate on both channels
    mh = open(os.path.join(base, "fedml_tpu", "parallel",
                           "multihost.py")).read()
    for sym in ("def gather_begin", "def gather_push",
                "def gather_finish", "def gather_abort",
                "def contrib_begin", "def contrib_push",
                "def mark_round", "def round_wire_delta"):
        assert sym in mh, (
            f"fedml_tpu/parallel/multihost.py lost {sym!r} — the "
            "ISSUE-16 overlapped exchange / wire-delta substrate")
    # bench_diff must judge the new fields
    bd = open(os.path.join(base, "tools", "bench_diff.py")).read()
    for field in ("wire_reduction_vs_f32", "efficiency_at_constant_bytes",
                  "acc_delta_vs_f32", "bitwise_f32_escape_ok"):
        assert field in bd, (
            f"tools/bench_diff.py lost the compressed-carry rule field "
            f"{field} (the v14 acceptance gate)")
    # cli wiring: codec choice + overlap opt-in, f32/serial defaults
    cli = open(os.path.join(base, "fedml_tpu", "cli.py")).read()
    assert "--carry_codec" in cli and "--overlap_exchange" in cli, (
        "fedml_tpu/cli.py lost the ISSUE-16 wire-tier flags")
    assert re.search(r'default="f32"', cli), (
        "--carry_codec must default to f32 (the bitwise escape hatch)")
    # chip queue: the compressed arm rides exp_POD, renumbered 16/21
    queue = open(os.path.join(base, "scripts",
                              "run_chip_queue.sh")).read()
    assert "FEDML_POD_ARMS=compress" in queue and "16/21" in queue, (
        "run_chip_queue.sh lost the 16/21 compressed-carry step "
        "(ISSUE 16 prices the bytes column on real DCN frames)")
    assert "FEDML_POD_ARMS" in open(os.path.join(
        base, "tools", "profile_bench.py")).read(), (
        "profile_bench.py exp_POD lost the FEDML_POD_ARMS override "
        "the queue's compressed step uses")


def test_bench_json_schema_v15_carries_straggler_observatory():
    """ISSUE 17: schema v15 adds the straggler block to the multihost
    chaos arm — barrier-ledger gating counts, per-rank wait
    percentiles, the cluster SLO verdicts (clean arm green, killed arm
    breaching with the dead rank named: straggler_attribution_ok) —
    plus the cluster observatory runtime it reads (obs/cluster.py
    telemetry fold + barrier ledger + coordinated dumps, the httpd
    /cluster endpoint, the DUMP control frame on the elastic channel)
    and the appended chip-queue step.  Static source check like the
    v3-v14 guards."""
    src = open(BENCH).read()
    m = re.search(r"^SCHEMA_VERSION\s*=\s*(\d+)", src, re.M)
    assert int(m.group(1)) >= 15, (
        "bench schema must stay >= v15 (straggler observatory block)")
    for field in ('"straggler"', "straggler_attribution_ok",
                  "cluster_clean_breaches", "top_gating_rank",
                  "cluster_killed_breached"):
        assert field in src, (
            f"bench.py lost the v15 straggler field {field} "
            "(see fedml_tpu/obs/cluster.py ISSUE 17)")
    base = os.path.join(os.path.dirname(__file__), "..")
    # the observatory module: telemetry plane + ledger + SLO pack +
    # coordinated dumps
    cl = open(os.path.join(base, "fedml_tpu", "obs", "cluster.py")).read()
    for sym in ("def attach_sidecar", "def split_sidecar",
                "def fold_remote", "def note_barrier",
                "def straggler_summary", "def cluster_slo_pack",
                "def cluster_report", "def maybe_coordinated_dump",
                "round_gating_rank"):
        assert sym in cl, (
            f"fedml_tpu/obs/cluster.py lost {sym!r} — the ISSUE-17 "
            "cluster observatory the v15 straggler block reads")
    # the channel hooks: hb piggyback, arrival stamps, the DUMP frame
    mh = open(os.path.join(base, "fedml_tpu", "parallel",
                           "multihost.py")).read()
    for sym in ("_piggyback_delta", "note_barrier",
                "_broadcast_dump_frames", '"dump"'):
        assert sym in mh, (
            f"fedml_tpu/parallel/multihost.py lost {sym!r} — the "
            "ISSUE-17 telemetry/ledger/dump hooks")
    # the /cluster endpoint + scoped /slo
    httpd = open(os.path.join(base, "fedml_tpu", "obs",
                              "httpd.py")).read()
    assert "/cluster" in httpd and "scope" in httpd, (
        "fedml_tpu/obs/httpd.py lost the /cluster endpoint or the "
        "scope field on /slo (ISSUE 17)")
    # the timeline tool must auto-discover rank dirs + render barriers
    tt = open(os.path.join(base, "tools", "trace_timeline.py")).read()
    assert "_expand_sources" in tt and "barrier_ledger" in tt, (
        "tools/trace_timeline.py lost the per-rank auto-discovery or "
        "the barrier-ledger lanes (ISSUE 17)")
    # bench_diff must judge the new fields
    bd = open(os.path.join(base, "tools", "bench_diff.py")).read()
    for field in ("straggler_attribution_ok", "cluster_clean_breaches"):
        assert field in bd, (
            f"tools/bench_diff.py lost the straggler rule field "
            f"{field} (the v15 acceptance gate)")
    # chip queue: the straggler observatory arm rides as 18/21
    queue = open(os.path.join(base, "scripts",
                              "run_chip_queue.sh")).read()
    assert "18/21" in queue and "trace_timeline.py" in queue, (
        "run_chip_queue.sh lost the 18/21 straggler observatory step "
        "(ISSUE 17 banks per-rank obs dirs + the merged timeline)")
    import subprocess
    r = subprocess.run(["bash", "-n", os.path.join(
        base, "scripts", "run_chip_queue.sh")],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stderr


def test_bench_json_schema_v16_carries_cluster_block():
    """ISSUE 18: schema v16 adds the cluster mode — the fused serving
    path (reactor sockets -> registry-sharded lanes -> cross-host fold
    through ElasticChannel) benched at 1/2/4 hosts with a striped
    connswarm fleet, plus the chaos-everything arm (connection storm +
    wire faults + rank kill in ONE arm).  Static source check like the
    v3-v15 guards: bench fields, the fused-cluster runtime, bench_diff
    v16 rules (goodput >= 0.5 floor, zero recv-thread deaths, boolean
    bitwise pin, clean-arm SLO riding the existing rule), the
    renumbered chip queue staying shell-valid."""
    src = open(BENCH).read()
    m = re.search(r"^SCHEMA_VERSION\s*=\s*(\d+)", src, re.M)
    assert int(m.group(1)) >= 16, (
        "bench schema must stay >= v16 (fused serving cluster block)")
    for field in ('"cluster"', "chaos_everything",
                  "survivor_goodput_ratio", "bitwise_after_death_ok",
                  "steady_updates_per_sec", "admission_p95_s",
                  "ranks_agree", "burst_cap_s"):
        assert field in src, (
            f"bench.py lost the v16 cluster field {field} "
            "(see fedml_tpu/scale/cluster.py ISSUE 18)")
    base = os.path.join(os.path.dirname(__file__), "..")
    # the fused-cluster runtime: lanes, window barrier, ordered fold,
    # the overload gate wired to registry pressure
    cl = open(os.path.join(base, "fedml_tpu", "scale",
                           "cluster.py")).read()
    for sym in ("class ClusterLane", "class ClusterServeManager",
                "def run_cluster_serve", "def wait_window",
                "def take_partials", "def lane_pressure",
                "set_overload_gate", "def make_uplink_frame",
                "def send_uplinks"):
        assert sym in cl, (
            f"fedml_tpu/scale/cluster.py lost {sym!r} — the ISSUE-18 "
            "fused serving path the v16 cluster block benches")
    # the swarm must stripe across a multi-target fleet and cap its
    # token-bucket burst (the bench's pacing knob)
    sw = open(os.path.join(base, "fedml_tpu", "comm",
                           "connswarm.py")).read()
    for sym in ("targets", "per_target", "burst_cap_s", "arrival"):
        assert sym in sw, (
            f"fedml_tpu/comm/connswarm.py lost {sym!r} — the ISSUE-18 "
            "striped-fleet / pacing knobs the cluster bench drives")
    # bench_diff must judge the new fields
    bd = open(os.path.join(base, "tools", "bench_diff.py")).read()
    for field in ("survivor_goodput_ratio", "bitwise_after_death_ok",
                  "recv_thread_deaths", "ranks_agree",
                  "steady_updates_per_sec["):
        assert ('"cluster"' in bd) and field in bd, (
            f"tools/bench_diff.py lost the cluster rule field "
            f"{field} (the v16 acceptance gate)")
    # chip queue: the fused-cluster arm appended as 19/21
    queue = open(os.path.join(base, "scripts",
                              "run_chip_queue.sh")).read()
    assert "19/21" in queue and "profile_bench.py CLUSTER" in queue, (
        "run_chip_queue.sh lost the 19/21 fused-cluster step "
        "(ISSUE 18 appends it as the queue's final arm)")
    assert "exp_CLUSTER" in open(os.path.join(
        base, "tools", "profile_bench.py")).read(), (
        "profile_bench.py lost the exp_CLUSTER experiment the queue "
        "runs")
    import subprocess
    r = subprocess.run(["bash", "-n", os.path.join(
        base, "scripts", "run_chip_queue.sh")],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stderr


def test_bench_json_schema_v17_carries_sparse_exchange():
    """ISSUE 19: schema v17 adds the sparse exchange — the top-k +
    error-feedback carry codecs on the multihost wire (>= 6x reduction
    at k=P/16 vs int8's ~4x, f32 escape hatch still bitwise) and the
    sparse_topk uplink transport on the cluster wire (bytes/update
    reduction at >= 0.9x dense committed-updates/sec).  Static source
    check like the v3-v16 guards: bench fields, the codec + wire
    runtime, bench_diff v17 rules, the appended chip-queue step."""
    src = open(BENCH).read()
    m = re.search(r"^SCHEMA_VERSION\s*=\s*(\d+)", src, re.M)
    assert int(m.group(1)) >= 17, (
        "bench schema must stay >= v17 (sparse exchange arms)")
    for field in ('"sparse"', "wire_reduction_vs_f32",
                  "uplink_reduction_vs_dense",
                  "throughput_ratio_vs_dense",
                  "uplink_bytes_per_update", "digests_equal",
                  "bitwise_f32_escape_ok"):
        assert field in src, (
            f"bench.py lost the v17 sparse-exchange field {field} "
            "(see fedml_tpu/parallel/carry_codec.py ISSUE 19)")
    base = os.path.join(os.path.dirname(__file__), "..")
    # the carry tier: top-k codecs in the registry, sparse fold on the
    # exchange, f32 still the registry default
    codec = open(os.path.join(base, "fedml_tpu", "parallel",
                              "carry_codec.py")).read()
    for sym in ("class TopKCarryCodec", "class TopKEFCarryCodec",
                "decode_pairs", "DEFAULT_TOPK_RATIO"):
        assert sym in codec, (
            f"fedml_tpu/parallel/carry_codec.py lost {sym!r} — the "
            "ISSUE-19 sparse carry tier the v17 arm drives")
    assert re.search(r'CARRY_CODECS\s*=\s*\(\s*"f32"', codec), (
        "the carry codec registry must keep f32 first/default — the "
        "bitwise anchors ride it")
    mh = open(os.path.join(base, "fedml_tpu", "parallel",
                           "multihost.py")).read()
    assert "fold_sparse_partials" in mh, (
        "fedml_tpu/parallel/multihost.py lost fold_sparse_partials — "
        "the ISSUE-19 scatter-fold the sparse carry arm rides")
    # the uplink tier: sparse_topk transport + scatter decode + the
    # version-skew rejection, the jitted sparse fold twin, the server
    # opt-in
    msg = open(os.path.join(base, "fedml_tpu", "comm",
                            "message.py")).read()
    for sym in ("sparse_topk", "def decode_sparse", "WIRE_TRANSPORTS",
                "version skew"):
        assert sym in msg, (
            f"fedml_tpu/comm/message.py lost {sym!r} — the ISSUE-19 "
            "sparse uplink wire (unknown transports must quarantine "
            "as NAMED version skew, not kill the decode pool)")
    st = open(os.path.join(base, "fedml_tpu", "async_",
                           "staleness.py")).read()
    for sym in ("def make_sparse_fold_fn", "def add_sparse"):
        assert sym in st, (
            f"fedml_tpu/async_/staleness.py lost {sym!r} — the "
            "ISSUE-19 streaming sparse fold (bitwise twin of the "
            "dense fold for <=k-sparse rows)")
    assert "sparse_uplink" in open(os.path.join(
        base, "fedml_tpu", "async_", "lifecycle.py")).read(), (
        "fedml_tpu/async_/lifecycle.py lost the sparse_uplink opt-in")
    # bench_diff must judge the new fields
    bd = open(os.path.join(base, "tools", "bench_diff.py")).read()
    for field in ("sparse_wire_reduction_vs_f32",
                  "uplink_reduction_vs_dense",
                  "throughput_ratio_vs_dense", "digests_equal",
                  "sparse_bitwise_f32_escape_ok"):
        assert field in bd, (
            f"tools/bench_diff.py lost the sparse rule field "
            f"{field} (the v17 acceptance gate)")
    # chip queue: the sparse arms appended as 20/21 on both wires
    queue = open(os.path.join(base, "scripts",
                              "run_chip_queue.sh")).read()
    assert ("20/21" in queue and "FEDML_POD_ARMS=sparse" in queue
            and "FEDML_CLUSTER_ARMS=clean,sparse" in queue), (
        "run_chip_queue.sh lost the 20/21 sparse-exchange step "
        "(ISSUE 19 prices both wires on real DCN frames + sockets)")
    assert "FEDML_CLUSTER_ARMS" in open(os.path.join(
        base, "tools", "profile_bench.py")).read(), (
        "profile_bench.py exp_CLUSTER lost the FEDML_CLUSTER_ARMS "
        "override the queue's sparse step uses")
    import subprocess
    r = subprocess.run(["bash", "-n", os.path.join(
        base, "scripts", "run_chip_queue.sh")],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stderr


def test_bench_json_schema_v18_carries_secure_aggregation():
    """ISSUE 20: schema v18 adds the secure block — the pairwise-mask
    data plane (fedml_tpu/secure/secagg.py) priced on the live async
    FSM: privacy-tax ratio with the >= 0.5 floor, the masks-cancel
    bitwise pin, zero below-threshold commits on clean arms, and the
    masked-byzantine pair.  Static source check like the v3-v17
    guards: bench fields, the secure runtime, the wire transport,
    bench_diff v18 rules, the appended chip-queue step."""
    src = open(BENCH).read()
    m = re.search(r"^SCHEMA_VERSION\s*=\s*(\d+)", src, re.M)
    assert int(m.group(1)) >= 18, (
        "bench schema must stay >= v18 (secure aggregation block)")
    for field in ('"secure"', "privacy_tax_ratio",
                  "masks_cancel_bitwise_ok",
                  "below_threshold_commits_clean", "rejected_uplinks",
                  "recovered_rounds"):
        assert field in src, (
            f"bench.py lost the v18 secure-aggregation field {field} "
            "(see fedml_tpu/secure/secagg.py ISSUE 20)")
    base = os.path.join(os.path.dirname(__file__), "..")
    # the data plane: masks, escrowed shares, the named
    # below-threshold refusal, the DP stage
    sa = open(os.path.join(base, "fedml_tpu", "secure",
                           "secagg.py")).read()
    for sym in ("class SecureAggregator", "class SecAggKeyring",
                "class SecAggBelowThreshold", "def pairwise_mask",
                "def client_row", "def reconstruct_sk", "dp_clip"):
        assert sym in sa, (
            f"fedml_tpu/secure/secagg.py lost {sym!r} — the ISSUE-20 "
            "pairwise-mask data plane the v18 arm drives")
    # the wire: the secagg transport is opaque-by-design (masked field
    # words), decode_into must refuse it BY NAME, the codec must have
    # the dedicated masked-frame decode
    msg = open(os.path.join(base, "fedml_tpu", "comm",
                            "message.py")).read()
    for sym in ('"secagg"', "def decode_secagg"):
        assert sym in msg, (
            f"fedml_tpu/comm/message.py lost {sym!r} — the ISSUE-20 "
            "masked uplink wire (secagg frames route through "
            "decode_secagg; decode_into refuses them by name)")
    # the engines: both FSMs carry the secure seam + the marker-skew
    # quarantine; the jitted u32 field fold twin lives in staleness
    assert "MSG_ARG_KEY_SECAGG" in open(os.path.join(
        base, "fedml_tpu", "async_", "lifecycle.py")).read(), (
        "fedml_tpu/async_/lifecycle.py lost the secagg marker — "
        "plain<->secure config skew must quarantine by name")
    assert "MSG_ARG_KEY_SECAGG" in open(os.path.join(
        base, "fedml_tpu", "comm", "fedavg_messaging.py")).read(), (
        "fedml_tpu/comm/fedavg_messaging.py lost the secagg marker")
    assert "def make_field_fold_fn" in open(os.path.join(
        base, "fedml_tpu", "async_", "staleness.py")).read(), (
        "fedml_tpu/async_/staleness.py lost make_field_fold_fn — the "
        "jitted (acc + row) mod p fold the masked ingest rides")
    # bench_diff must judge the new fields
    bd = open(os.path.join(base, "tools", "bench_diff.py")).read()
    for field in ("privacy_tax_ratio", "masks_cancel_bitwise_ok",
                  "below_threshold_commits_clean"):
        assert field in bd, (
            f"tools/bench_diff.py lost the secure rule field "
            f"{field} (the v18 acceptance gate)")
    # chip queue: the secure arm appended as 21/21
    queue = open(os.path.join(base, "scripts",
                              "run_chip_queue.sh")).read()
    assert "21/21" in queue and "profile_bench.py SECAGG" in queue, (
        "run_chip_queue.sh lost the 21/21 secure-aggregation step "
        "(ISSUE 20 prices the privacy tax on the chip-attached fold)")
    assert "def exp_SECAGG" in open(os.path.join(
        base, "tools", "profile_bench.py")).read(), (
        "profile_bench.py lost exp_SECAGG — the queue's 21/21 step "
        "calls it")
    import subprocess
    r = subprocess.run(["bash", "-n", os.path.join(
        base, "scripts", "run_chip_queue.sh")],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stderr


def test_bench_diff_exists_and_flags_synthetic_regression(tmp_path):
    """ISSUE 12: tools/bench_diff.py must exist, exit 0 on a
    self-compare of the committed baseline, and exit nonzero NAMING the
    metric when a headline field is synthetically degraded — the
    regression gate's own regression gate."""
    import json as _json
    import subprocess
    import sys
    diff = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "bench_diff.py")
    base = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "bench_baseline_2core.json")
    assert os.path.exists(diff), "tools/bench_diff.py is gone"
    assert os.path.exists(base), (
        "benchmarks/bench_baseline_2core.json (the bench_diff "
        "regression anchor) is gone")
    doc = _json.load(open(base))
    assert doc["kind"] == "bench_baseline" and doc["modes"], base
    assert "recalibration_protocol" in doc["calibration"], (
        "the baseline lost its recalibration note (the "
        "quality_bands.json-mirrored protocol)")
    r = subprocess.run([sys.executable, diff, base, base],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    doc["modes"]["attack"]["defended_acc"] = round(
        doc["modes"]["attack"]["defended_acc"] * 0.8, 4)
    degraded = tmp_path / "degraded.json"
    degraded.write_text(_json.dumps(doc))
    r = subprocess.run([sys.executable, diff, base, str(degraded)],
                       capture_output=True, text=True)
    assert r.returncode == 1, (
        "bench_diff must exit nonzero on a synthetically injected "
        "regression")
    assert "defended_acc" in r.stdout and "regressed" in r.stdout


def test_chip_queue_carries_bench_diff_step():
    """ISSUE 12: the chip queue's judgment pass diffs the fresh bench
    record against the committed trajectory (step 14/21 since ISSUEs
    13-18 appended exp_POD, exp_ELASTIC, the compressed-carry arm, the
    straggler observatory arm and the fused-cluster arm), and the
    script stays shell-valid."""
    import subprocess
    queue = os.path.join(os.path.dirname(__file__), "..", "scripts",
                         "run_chip_queue.sh")
    src = open(queue).read()
    assert "bench_diff.py" in src, (
        "run_chip_queue.sh lost the bench_diff regression step "
        "(ISSUE 12 appends it as the queue's judgment pass)")
    assert "14/21" in src, (
        "run_chip_queue.sh lost the 14/21 bench_diff step numbering "
        "(the judgment pass rides right after the bench artifacts; "
        "exp_POD is 15, the compressed arm 16, exp_ELASTIC 17, the "
        "straggler observatory arm 18, the fused-cluster arm 19)")
    r = subprocess.run(["bash", "-n", queue], capture_output=True,
                       text=True)
    assert r.returncode == 0, r.stderr


def test_chip_queue_carries_chaos_ab():
    """ISSUE 8: the next chip window must price the chaos goodput —
    scripts/run_chip_queue.sh carries the CHAOS step (10/10) and
    profile_bench.py defines the exp_CHAOS experiment it runs."""
    queue = os.path.join(os.path.dirname(__file__), "..", "scripts",
                         "run_chip_queue.sh")
    assert "profile_bench.py CHAOS" in open(queue).read(), (
        "run_chip_queue.sh lost the CHAOS goodput A/B "
        "(ISSUE 8 queues it for the next chip window)")
    assert "exp_CHAOS" in open(os.path.join(
        os.path.dirname(__file__), "..", "tools",
        "profile_bench.py")).read(), (
        "profile_bench.py lost the exp_CHAOS experiment the queue runs")
    import subprocess
    r = subprocess.run(["bash", "-n", queue], capture_output=True,
                       text=True)
    assert r.returncode == 0, r.stderr
