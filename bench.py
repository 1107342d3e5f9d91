"""North-star benchmark: FedAvg rounds/sec, CIFAR10 + ResNet-18-GN,
128 clients (BASELINE.json).

One full federated round = 128 clients × 1 local epoch over their CIFAR
shard (50k samples total, bs=32) + sample-weighted aggregation — all as one
jit-compiled program (vmap over the cohort; on a multi-device mesh the
aggregation is an ICI psum).  The reference equivalent is 129 MPI processes
exchanging pickled state dicts with a CPU aggregation loop
(fedml_api/distributed/fedavg/*, SURVEY.md §3.1).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

`vs_baseline` compares against an ESTIMATE of the reference's 8×V100
throughput on the same workload, since the reference publishes no
rounds/sec (BASELINE.md): 50k samples/round × ~3.5 GFLOP fwd+bwd per
sample (ResNet-18 @32×32 ≈ 0.58 GFLOP fwd) ≈ 1.7e14 FLOP/round; 8×V100
at 125 TFLOP/s peak fp16 and a generous 35% utilization ≈ 350 TFLOP/s
⇒ ~0.5 s/round ⇒ ~2.0 rounds/s. We use 2.0 — conservative (favors the
reference: real FedML additionally pays MPI serialization + CPU
aggregation per round).  Sensitivity of vs_baseline to the utilization
assumption ({25%, 35%, 50%} ⇒ denominator 1.47/2.06/2.94) is tabulated
in PERF.md §"Baseline sensitivity".
"""
from __future__ import annotations

import json
import os
import sys
import time
from typing import Optional

import numpy as np

ESTIMATED_REFERENCE_ROUNDS_PER_SEC = 2.0

# bump when the JSON line's keys change meaning; BENCH_*.json trajectory
# consumers key on this instead of guessing from key presence.
# v2: + schema_version, git_sha, rounds (per-round transfer records),
#     obs (observability rollup, present only under FEDML_OBS_DIR)
# v3: + h2d_bytes_per_round (transfer-compression byte accounting: mean
#     host->device payload bytes per timed round — 0 on this
#     resident-cohort bench, filled by streaming/block-stream variants);
#     per-round records in "rounds" additionally carry "h2d_bytes"
# v4: + "mode" ("sync" | "async") and "async" block (committed_updates,
#     staleness_p50/p95, buffer_occupancy_mean, deadline_commits —
#     `python bench.py --mode async`, fedml_tpu/async_); null in sync
#     mode, so v3 readers that ignore unknown keys keep working
# v5: + "ingest" block (`python bench.py --mode ingest`, the
#     concurrent-uplink ingestion torture, fedml_tpu/async_/torture.py):
#     a "legacy" arm (the PR-5 path faithfully: inline decode on recv
#     threads + unbounded inbox + drained O(K·P) commit), a
#     "legacy_bounded_inbox" arm (same path + this PR's inbox
#     backpressure — isolates the queue-discipline win), and
#     decode-into+streaming "arms" per pool size, each carrying
#     committed_updates_per_sec, decode_p50_s/decode_p95_s and
#     lock_wait_seconds, plus the headline "speedup_vs_legacy"
#     (best arm / legacy — the ISSUE-6 >=2x acceptance gate); null in
#     sync/async modes
# v6: + "critical_path" block (ISSUE 7, fedml_tpu/obs/timeline.py):
#     per-round stage attribution (stages {train/commit/decode/fold/
#     wait...: seconds}, stage_totals_s/stage_share, round_wall_p50/
#     p95_s, and p95_attribution naming the stage that explains p95
#     round wall).  Computed from the live span tracer, so it is null
#     unless the run is traced (FEDML_OBS_DIR); v5 readers that ignore
#     unknown keys keep working
# v7: + "chaos" block (`python bench.py --mode chaos`, ISSUE 8 —
#     fedml_tpu/comm/chaos.py + reliability.py over the ingest torture):
#     a "clean" reliable arm, a goodput-vs-fault-rate "curve" (loss/
#     dup/corrupt sweeps, each row carrying the rates,
#     committed_updates_per_sec, goodput_ratio vs clean, and the
#     retries/dups_suppressed/quarantined/recv_thread_deaths counters),
#     and a "mixed" arm (5% loss + 1% dup + 0.5% corrupt — the
#     acceptance shape) with its goodput_vs_clean headline; null in
#     other modes, so v6 readers keep working
# v9: + "serve" block (`python bench.py --mode serve`, ISSUE 10 —
#     fedml_tpu/scale/): the million-client serving-spine bench — one
#     row per simulated population (default 10k/100k/1M) from the
#     virtual-time serve loop (scale/serve.py: sharded registry +
#     streaming cohort sampler + trace-driven arrivals driving the
#     PR-6 streaming buffer), each carrying committed_updates_per_sec,
#     registry_bytes / registry_bytes_per_client (the <= ~100 B/client
#     sub-linear-memory gate, recorded in "sublinear_ok"), sampler
#     scratch bytes, rss_bytes and the virtual-time arrival stats;
#     null in other modes, so v8 readers keep working
# v10: + "connections" block (`python bench.py --mode connections`,
#     ISSUE 11 — fedml_tpu/comm/reactor.py + connswarm.py over the
#     live-connection torture): one row per live-connection count
#     (default 256/1k/10k), each with a clean, a mixed-chaos (5% loss +
#     1% dup + 0.5% corrupt) and a storm (chaos + connection storm +
#     reconnect churn) arm carrying committed_updates_per_sec,
#     admission_p50_s/admission_p95_s, open_connections_peak, the
#     evicted{stall|rate|shed} / uplinks_shed / recv_thread_deaths /
#     fd_leaked counters and loop_lag_p95_s, plus per-row
#     storm_goodput_ratio (the >= 0.5x-of-clean acceptance gate) —
#     null in other modes, so v9 readers keep working
# v11: + "slo" block (ISSUE 12, fedml_tpu/obs/slo.py) on EVERY mode —
#     the default serving-spine SLO pack evaluated per bench arm
#     ({"pack", "arms": {arm: {breaches, breached, healthy}}}); clean
#     arms must stay breach-free (tools/bench_diff.py's
#     slo_clean_breaches verdict) while chaos/storm arms breach BY
#     DESIGN with named attribution — and + "programs" block
#     (fedml_tpu/obs/programs.py): the per-jit-program-family profile
#     ({"window_s", "families": [{family, stage,
#     dispatches, dispatch_wall_s, dispatch_p50/p95_s, flops/bytes per
#     dispatch}], "total"}; PR 23 took "mfu" and "peak_flops" out: census
#     FLOPs over HOST dispatch wall), the PERF.md stage table as a standing
#     artifact; v10 readers that ignore unknown keys keep working
# v12: + "multihost" block (`python bench.py --mode multihost`,
#     ISSUE 13 — fedml_tpu/parallel/multihost.py): the weak-scaling
#     two-level-aggregation sweep — N worker processes (spawn_cluster,
#     one block of clients per process, constant per-process work)
#     each train their cohort blocks on a LOCAL mesh and allreduce the
#     P-sized flat f32 carry over the HostChannel, one row per process
#     count (default 1/2/4) carrying rounds_per_sec,
#     carry_allreduce_bytes_per_round, ranks_agree and process_deaths,
#     plus weak_efficiency_2p/4p (rounds/sec vs the 1-process arm; the
#     >= 0.5x-at-2 gate is the documented GIL/gloo floor on the 2-core
#     box — exp_POD prices it on a real pod slice) and
#     bitwise_2proc_ok (the 1-vs-2-process same-block-partition digest
#     pin); null in other modes, so v11 readers keep working
# v13: the "multihost" block gains the elastic "chaos" arm (ISSUE 14 —
#     ElasticChannel/ElasticRunner in fedml_tpu/parallel/multihost.py):
#     a 3-process ELASTIC cluster with a seeded kill of rank 1 mid-run
#     vs the clean elastic same-partition run — survivor_goodput_ratio
#     (killed/clean rounds/sec, >= 0.5x gate), view_changes +
#     view_change_latency_s (death detection -> survivors re-tasked),
#     survivor_deaths (must be 0 — only the killed rank dies),
#     epoch_final, and bitwise_after_death_ok (the killed run's commit
#     digests byte-identical to the clean run's, FedAvg resident AND
#     streaming — the re-adopted blocks are pure functions of [seed,
#     round, block], so the fold is topology-independent); plus
#     elastic_fail_fast_default_ok (fail-fast stays the default policy:
#     the weak-scaling arms above still run non-elastic).  --mh_arms
#     selects weak/bitwise/chaos subsets; v12 readers that ignore
#     unknown keys keep working
# v14: the "multihost" block gains the "compress" arm (ISSUE 16 —
#     fedml_tpu/parallel/carry_codec.py + the overlapped exchange in
#     multihost.py): paired 2-process clusters at the SAME block
#     partition price the compressed inter-host carry tier — an f32
#     serial baseline, the f32+overlap escape-hatch run (digests must
#     be byte-identical to serial: bitwise_f32_escape_ok), and one row
#     per compressed codec (int8, int8_ef; overlap on, eval on)
#     carrying carry_wire_bytes_per_round (measured ON the wire via
#     the channel's per-round delta, not inferred host-side),
#     carry_compression_ratio (raw f32 bytes / encoded payload),
#     wire_reduction_vs_f32 (>= 3x gate rides bench_diff),
#     overlap_fraction (> 0 when the DCN exchange hides behind block
#     compute), eval_acc + acc_delta_vs_f32 (abs; the quality band),
#     and efficiency_at_constant_bytes ((rps_codec/rps_f32) x
#     wire_reduction — rounds per byte-budget).  --mh_arms grows
#     "compress"; v13 readers that ignore unknown keys keep working
# v15: the "multihost" block gains the "straggler" block (ISSUE 17 —
#     fedml_tpu/obs/cluster.py, the cluster observatory): rank 0 keeps
#     an always-on barrier ledger (per-rank arrival timestamps at every
#     gather/allgather/exchange), so the chaos arm now also reports WHO
#     gated each round — barriers observed on the clean and killed
#     elastic runs, per-rank gating_counts, top_gating_rank,
#     worst_gate_margin_s, per_rank_wait_s percentiles, the last few
#     ledger entries (each naming its round_gating_rank), plus the
#     cluster SLO verdicts: cluster_clean_breaches (must be 0 — the
#     clean arm's cluster pack is green) and cluster_killed_breached
#     (the killed arm MUST breach cluster_no_rank_deaths with rank 1
#     named in the attribution — straggler_attribution_ok pins that);
#     v14 readers that ignore unknown keys keep working
# v8: + "attack" block (`python bench.py --mode attack`, ISSUE 9 —
#     fedml_tpu/async_/adversary.py + defense.py): a "matrix" of
#     attack x defense arms on the async MNIST-LR workload (each row:
#     attack mode, defended flag, test_acc, quarantine counts with
#     honest/byzantine attribution), the "mixed" acceptance trio
#     (20% byzantine boost+labelflip — defended_acc vs undefended_acc
#     vs clean_acc, false_positive_quarantines), and an "overhead"
#     ingest-torture pair (admission screen on vs off) whose
#     throughput_ratio prices the fused screen (the >=0.9x target is
#     the chip-side gate — on the 2-core CI box the serial fold is the
#     bottleneck and the paired median is ~0.73x, PERF.md); null in
#     other modes, so v7 readers keep working
# v16: + "cluster" block (`python bench.py --mode cluster`, ISSUE 18 —
#     fedml_tpu/scale/cluster.py, the fused serving cluster): live
#     connswarm sockets feed registry-sharded lanes on each host of an
#     elastic multi-host tier, lane partials folding cross-host at
#     every commit barrier.  Rows sweep host counts (1/2/4 by default,
#     one multi-target swarm striped across the endpoints):
#     cluster_updates_per_sec, admission p95 (max over ranks),
#     ranks_agree (the cross-rank digest pin, live ingest).  The
#     chaos_everything arm composes ALL the fault layers at once —
#     connection storm + seeded wire faults + a rank killed mid-run —
#     and reports survivor_goodput_ratio (>= 0.5 floor),
#     bitwise_after_death_ok (survivor digests agree), and the full
#     evictions/sheds/drops ledger; v15 readers that ignore unknown
#     keys keep working
# v17: the "multihost" block gains the "sparse" arm and the "cluster"
#     block a "sparse" sub-block (ISSUE 19 — topk/topk_ef carry codecs
#     in fedml_tpu/parallel/carry_codec.py + the sparse_topk uplink
#     transport in comm/message.py).  multihost sparse: same paired
#     2-process protocol as the compress arm, one row per sparse codec
#     (topk, topk_ef; overlap on, eval on) with the SAME columns —
#     carry_wire_bytes_per_round (channel-measured),
#     carry_compression_ratio, wire_reduction_vs_f32 (the ISSUE-19
#     >= 6x gate rides bench_diff), overlap_fraction, eval_acc +
#     acc_delta_vs_f32 (quality band; topk is LOSSY where int8 was
#     near-lossless, so this column carries the judgment), ranks_agree,
#     and efficiency_at_constant_bytes; plus bitwise_f32_escape_ok
#     re-pinned on the f32 baseline pair.  cluster sparse: a paired
#     dense-vs-sparse_topk uplink run at the same host count —
#     uplink_bytes_per_update (frame bytes on the wire),
#     uplink_reduction_vs_dense, sparse committed-updates/sec and
#     throughput_ratio_vs_dense (>= 0.9x on 2-core rides bench_diff),
#     digests_equal on a <= k-sparse replay (sparse_topk round-trips
#     <= k-nonzero rows exactly, so dense and sparse ingest commit
#     identical bits); v16 readers that ignore unknown keys keep
#     working
# v18: + "secure" block (`python bench.py --mode secure`, ISSUE 20 —
#     fedml_tpu/secure/secagg.py, the pairwise-mask data plane): the
#     privacy-tax table on the live async messaging FSM (MNIST-LR,
#     full-cohort barrier) — plain vs masked committed-updates/sec
#     (privacy_tax_ratio), plain/secure/dp accuracy (the end-to-end
#     private mode's quality cost), the masks_cancel_bitwise_ok
#     protocol pin (full-cohort masked field sum == plain fixed-point
#     sum, exact integers), measured encoded-frame uplink bytes
#     (plain f32 pytree frame vs masked u32 words at the same model —
#     uplink_bytes_ratio; masked words are incompressible by design,
#     so codec-v2 compression buys nothing), below_threshold_commits
#     (MUST be 0 on the
#     clean arms — masks only fail to cancel when survivors dip under
#     the reconstruction threshold), and the two masked-byzantine
#     arms: in-field boost (fits the quantizer range -> sails through,
#     because the admission screen reads plaintext rows and is BLINDED
#     under masks) vs overflow boost (the client-side quantizer range
#     refusal — the ONE norm-bound enforcement masking cannot blind —
#     drops the uplink and dropout recovery carries the round); v17
#     readers that ignore unknown keys keep working
SCHEMA_VERSION = 18


# the programs block's window opens when main() configures obs (set
# there; None until then so helper calls stay harmless)
_PROGRAMS_T0 = None


def _programs_doc():
    """Schema-v11 programs block: the per-jit-program-family profile
    over this bench invocation's window (dispatch counts + host walls
    always; flops/bytes/MFU when the census ran — see main())."""
    from fedml_tpu.obs import programs
    return programs.report(_PROGRAMS_T0)


def _slo_doc(arms: dict) -> dict:
    """Schema-v11 slo block: the default-pack verdicts per bench arm.
    `arms` maps arm name -> an SloEngine.arm_summary() (or a torture
    report's "slo_arm").  Arm names matter: tools/bench_diff.py treats
    arms whose name contains chaos/storm/mixed/curve as
    breach-by-design and judges only the clean ones."""
    from fedml_tpu.obs import slo
    return {"pack": slo.DEFAULT_PACK_NAME,
            "arms": {k: v for k, v in arms.items() if v is not None}}


def _slo_window():
    """A primed default-pack engine for modes that are one arm (sync/
    async/serve population loops): prime now, summarize at arm end."""
    from fedml_tpu.obs import slo
    eng = slo.SloEngine(slo.default_slo_pack())
    eng.prime()
    return eng


def _slo_close(eng) -> dict:
    eng.evaluate()
    return eng.arm_summary()


def _critical_path_doc():
    """Schema-v6 critical_path block from the live tracer (None when
    the run is untraced — spans are the input, metrics alone cannot
    place stages on a timeline)."""
    from fedml_tpu import obs
    t = obs.tracer()
    if t is None:
        return None
    from fedml_tpu.obs import timeline
    report = timeline.critical_path(t.events())
    report.pop("rounds", None)       # per-round detail stays in obs_dir
    return report


def _git_sha() -> str:
    """Short sha of the bench's code state, best-effort ("unknown" when
    git is absent — the chip tool's copy is not a repository) — bench
    rows stay attributable across PRs."""
    import subprocess
    try:
        r = subprocess.run(
            ["git", "-C", os.path.dirname(os.path.abspath(__file__)),
             "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10)
        if r.returncode == 0:
            return r.stdout.strip()
    except Exception:
        pass
    return "unknown"


def device_doc() -> dict:
    """The device every printed number ran on, as JAX reports it."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def require_device() -> dict:
    """The no-fallback contract: a bench that finds no TPU exits
    non-zero and prints no number.  `JAX_PLATFORMS=cpu` is the only —
    explicit — way to run it off-chip (the host-level modes and dev
    runs); a CPU the backend merely fell back to is refused."""
    doc = device_doc()
    if doc["platform"] != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit(
            f"bench: no TPU attached (jax reports platform "
            f"{doc['platform']!r}); set JAX_PLATFORMS=cpu to run "
            f"off-chip on purpose")
    return doc


def _stamp(doc: dict) -> dict:
    doc["schema_version"] = SCHEMA_VERSION
    doc["git_sha"] = _git_sha()
    doc.update(device_doc())
    return doc

N_CLIENTS = 128
BATCH_SIZE = 32
SAMPLES_PER_CLIENT = 50_000 // N_CLIENTS      # ≈ CIFAR10 over 128 clients
WARMUP_ROUNDS = 2
TIMED_ROUNDS = 8     # measured run-to-run spread at 5 was 0.544-0.549
                     # rounds/sec; 8 tightens the single-run estimate
                     # for ~5 s extra driver time


def build_headline(x, y, n_clients: int = N_CLIENTS,
                   model_name: str = "resnet18_gn",
                   batch_size: int = BATCH_SIZE):
    """The headline cell's (cfg, data, trainer) over pre-made samples
    x [n, h, w, 3] / y [n], split evenly over `n_clients` with full
    participation and one local epoch.  chip_smoke.py builds through
    this same function, so the smoke proves the program the bench
    times; only the samples differ (random labels here — shapes and
    FLOPs are what the timing needs; a learnable stand-in there)."""
    import jax.numpy as jnp

    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.data.federated import (FederatedData, build_client_shards,
                                          build_eval_shard)
    from fedml_tpu.models import create_model
    from fedml_tpu.utils.config import FedConfig

    n = len(y)
    spc = n // n_clients
    cfg = FedConfig(model=model_name, dataset="cifar10",
                    client_num_in_total=n_clients,
                    client_num_per_round=n_clients,
                    epochs=1, batch_size=batch_size, lr=0.1,
                    frequency_of_the_test=10_000)
    idx = {i: np.arange(i * spc, (i + 1) * spc) for i in range(n_clients)}
    ev = build_eval_shard(x[:batch_size], y[:batch_size], batch_size)
    data = FederatedData(
        train_data_num=n, test_data_num=n, train_global=ev, test_global=ev,
        client_shards=build_client_shards(x, y, idx, batch_size),
        client_num_samples=np.full(n_clients, spc, np.float32),
        test_client_shards=None, class_num=10, synthetic=True)
    model = create_model(model_name, output_dim=10)
    # bf16 compute / f32 masters: the MXU fast path (core/trainer.py);
    # batch_unroll=8 unrolls the 13-step batch scan (measured −2.5%:
    # L2U8 1.806 vs L2 1.851, PERF.md round-3 table)
    trainer = ClientTrainer(model, lr=cfg.lr, train_dtype=jnp.bfloat16,
                            batch_unroll=8)
    return cfg, data, trainer


def headline_engine(cfg, data, trainer, mesh=None):
    """MeshFedAvgEngine at the committed recipe — chunk=2 + bf16 local
    masters: the measured v5e optimum (tools/profile_bench.py L2 rows;
    PERF.md round-3 table).  `mesh` defaults to all visible devices."""
    import jax.numpy as jnp

    from fedml_tpu.parallel import MeshFedAvgEngine
    from fedml_tpu.parallel.mesh import make_mesh
    return MeshFedAvgEngine(trainer, data, cfg,
                            mesh=mesh if mesh is not None else make_mesh(),
                            chunk=2, local_dtype=jnp.bfloat16)


class HeadlineRun:
    """Full participation: the cohort IS the whole client stack — upload
    it once (`cohort`, `weights`) and drive the streaming round over it
    (no per-round device-side gather).  Each step() runs one round and
    returns (variables, metrics)."""

    def __init__(self, engine, seed: int = 0):
        import jax
        self.engine = engine
        # placed like every later round's inputs (replicated over the
        # mesh, what run() does): fresh single-device variables would
        # make the FIRST call a program of its own — a second ~2-minute
        # compile and a second 80 MB cache entry of the same round
        # (PR 21 chip runs)
        self.variables = engine._prepare_variables(engine.init_variables())
        self.server_state = engine.server_init(self.variables)
        self.rng = jax.random.PRNGKey(seed)
        self.cohort, self.weights = engine.stream_cohort(0)

    def step(self):
        import jax
        self.rng, r = jax.random.split(self.rng)
        self.variables, self.server_state, m = (
            self.engine.round_fn_streaming(
                self.variables, self.server_state, self.cohort,
                self.weights, r))
        return self.variables, m


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser("bench")
    ap.add_argument("--mode",
                    choices=("sync", "async", "ingest", "chaos", "attack",
                             "serve", "connections", "multihost",
                             "cluster", "secure"),
                    default="sync",
                    help="sync: the north-star resident-cohort rounds/sec "
                         "bench; async: the buffered staleness-aware "
                         "scheduler (fedml_tpu/async_) — committed "
                         "updates/sec + staleness percentiles under the "
                         "seeded lognormal-latency lifecycle; ingest: the "
                         "concurrent-uplink ingestion torture "
                         "(fedml_tpu/async_/torture.py) — sustained "
                         "committed-updates/sec of the server's "
                         "decode+aggregate path under N saturating "
                         "clients, legacy vs decode-into+streaming A/B; "
                         "chaos: the same torture under seeded wire "
                         "faults (fedml_tpu/comm/chaos.py) with the "
                         "reliability envelope on — goodput-vs-fault-"
                         "rate curves for loss/dup/corrupt; attack: "
                         "the adversarial-robustness matrix (ISSUE 9, "
                         "fedml_tpu/async_/adversary.py + defense.py) "
                         "— attack x defense accuracy on the async "
                         "MNIST-LR workload plus the admission-screen "
                         "ingest-overhead pair; serve: the "
                         "million-client serving-spine bench (ISSUE 10, "
                         "fedml_tpu/scale/) — sustained committed-"
                         "updates/sec and server registry memory vs "
                         "simulated population (10k/100k/1M) under a "
                         "trace-driven arrival process in virtual time; "
                         "connections: the live-connection reactor bench "
                         "(ISSUE 11, fedml_tpu/comm/reactor.py) — "
                         "sustained committed-updates/sec + p95 admission "
                         "latency vs live socket count (256/1k/10k), "
                         "clean vs mixed-chaos vs storm arms; multihost: "
                         "the weak-scaling two-level-aggregation sweep "
                         "(ISSUE 13, fedml_tpu/parallel/multihost.py) — "
                         "N spawned processes train one client block "
                         "each on local meshes and allreduce the flat "
                         "f32 carry over the HostChannel; rounds/sec + "
                         "carry bytes vs process count (1/2/4) plus the "
                         "1-vs-2-process bitwise pin; cluster: the "
                         "fused serving cluster (ISSUE 18, "
                         "fedml_tpu/scale/cluster.py) — live connswarm "
                         "sockets feed registry-sharded lanes on each "
                         "host of an elastic multi-host tier; "
                         "committed-updates/sec + p95 admission vs "
                         "(hosts x connections) at 1/2/4 hosts, plus "
                         "the chaos-everything arm (storm + wire "
                         "faults + rank kill at once); secure: the "
                         "pairwise-mask privacy-tax bench (ISSUE 20, "
                         "fedml_tpu/secure/) — plain vs masked "
                         "committed-updates/sec on the live async FSM, "
                         "plain/secure/dp accuracy, the masks-cancel "
                         "bitwise pin, and the masked-byzantine pair "
                         "(blinded screen vs quantizer range refusal)")
    ap.add_argument("--ingest_clients", type=int, default=32,
                    help="ingest mode: concurrent uplink clients")
    ap.add_argument("--ingest_backend", default="TCP",
                    choices=("TCP", "GRPC", "INPROC"),
                    help="ingest mode: transport under torture")
    ap.add_argument("--ingest_pools", default="1,4,8",
                    help="ingest mode: comma-separated decode-pool sizes "
                         "for the decode-into+streaming arms")
    ap.add_argument("--ingest_commits", type=int, default=30,
                    help="ingest mode: timed commits per arm")
    ap.add_argument("--chaos_clients", type=int, default=32,
                    help="chaos mode: concurrent reliable uplink clients")
    ap.add_argument("--chaos_backend", default="TCP",
                    choices=("TCP", "GRPC", "INPROC"),
                    help="chaos mode: transport under fault injection")
    ap.add_argument("--chaos_commits", type=int, default=12,
                    help="chaos mode: timed commits per arm (the curve "
                         "runs ~8 arms; keep this moderate)")
    ap.add_argument("--chaos_seed", type=int, default=0,
                    help="chaos mode: fault-injection seed (same seed = "
                         "same per-stream injected-event trace)")
    ap.add_argument("--attack_commits", type=int, default=16,
                    help="attack mode: async commits per accuracy arm "
                         "(the quality-band workload runs 16)")
    ap.add_argument("--attack_ingest_clients", type=int, default=32,
                    help="attack mode: clients in the screen-overhead "
                         "ingest pair")
    ap.add_argument("--attack_backend", default="TCP",
                    choices=("TCP", "GRPC", "INPROC"),
                    help="attack mode: transport of the overhead pair")
    ap.add_argument("--attack_seed", type=int, default=0,
                    help="attack mode: adversary seed (same seed = same "
                         "byzantine set + corruption streams)")
    ap.add_argument("--serve_populations", default="10000,100000,1000000",
                    help="serve mode: comma-separated simulated client "
                         "populations (one bench row each)")
    ap.add_argument("--serve_commits", type=int, default=40,
                    help="serve mode: streaming commits per population "
                         "arm (K updates each)")
    ap.add_argument("--serve_buffer_k", type=int, default=32,
                    help="serve mode: streaming buffer capacity K")
    ap.add_argument("--serve_row_dim", type=int, default=4096,
                    help="serve mode: flat update-row width P the fold "
                         "and commit run at")
    ap.add_argument("--serve_sampler", default="stratified",
                    choices=("uniform", "reservoir", "stratified"),
                    help="serve mode: cohort sampler over the registry "
                         "(stratified = O(k)-per-draw, the spine "
                         "default; reservoir = exact-uniform one-pass)")
    ap.add_argument("--serve_arrivals", default="diurnal",
                    choices=("constant", "diurnal", "flash"),
                    help="serve mode: arrival-process family driving "
                         "the virtual clock")
    ap.add_argument("--serve_seed", type=int, default=0,
                    help="serve mode: one seed drives sampler, arrivals "
                         "and fault draws (same seed = same trace)")
    ap.add_argument("--conn_counts", default="256,1000,10000",
                    help="connections mode: comma-separated live-"
                         "connection counts (one bench row each; counts "
                         "past ~4k run the client swarm in a subprocess "
                         "so both halves fit under ulimit -n)")
    ap.add_argument("--conn_commits", type=int, default=24,
                    help="connections mode: timed commits per arm")
    ap.add_argument("--conn_buffer_k", type=int, default=32,
                    help="connections mode: streaming buffer capacity K")
    ap.add_argument("--conn_pool", type=int, default=4,
                    help="connections mode: decode-pool size")
    ap.add_argument("--conn_rate", type=float, default=2000.0,
                    help="connections mode: aggregate offered uplink "
                         "frames/sec across the swarm")
    ap.add_argument("--conn_seed", type=int, default=0,
                    help="connections mode: one seed drives the swarm "
                         "schedule and the chaos injector")
    ap.add_argument("--mh_procs", default="1,2,4",
                    help="multihost mode: comma-separated process "
                         "counts (one weak-scaling row each; per-"
                         "process work is constant — one client block "
                         "per process)")
    ap.add_argument("--mh_rounds", type=int, default=10,
                    help="multihost mode: rounds per arm (first "
                         "--mh_warmup excluded from the rate)")
    ap.add_argument("--mh_warmup", type=int, default=2,
                    help="multihost mode: warmup rounds per arm")
    ap.add_argument("--mh_clients_per_block", type=int, default=64,
                    help="multihost mode: population per block (the "
                         "id-range each process owns)")
    ap.add_argument("--mh_k_per_block", type=int, default=8,
                    help="multihost mode: sampled cohort per block per "
                         "round")
    ap.add_argument("--mh_dim", type=int, default=256,
                    help="multihost mode: LR input dim (sets the flat "
                         "carry size P that crosses hosts)")
    ap.add_argument("--mh_local_devices", type=int, default=1,
                    help="multihost mode: virtual devices per process "
                         "(the intra-host psum tier width on CPU)")
    ap.add_argument("--mh_seed", type=int, default=0,
                    help="multihost mode: workload seed (same seed = "
                         "same cohorts = the bitwise pin's premise)")
    ap.add_argument("--mh_arms", default="weak,bitwise,chaos,compress",
                    help="multihost mode: comma-subset of "
                         "{weak,bitwise,chaos,compress} — weak = the "
                         "v12 weak-scaling sweep, bitwise = the "
                         "1p-vs-2p digest pin, chaos = the v13 elastic "
                         "kill-a-rank arm (survivor goodput + "
                         "bitwise_after_death_ok), compress = the v14 "
                         "compressed+overlapped carry tier (bytes on "
                         "the wire, quality band, f32 escape-hatch "
                         "bitwise pin)")
    ap.add_argument("--mh_chaos_procs", type=int, default=3,
                    help="multihost chaos arm: elastic cluster size "
                         "(rank 1 is killed mid-run; the survivors "
                         "must finish)")
    ap.add_argument("--cluster_hosts", default="1,2,4",
                    help="cluster mode: comma-separated host counts "
                         "(one row each; a multi-target swarm stripes "
                         "its fleet across the H endpoints)")
    ap.add_argument("--cluster_connections", type=int, default=32,
                    help="cluster mode: swarm connections per host")
    ap.add_argument("--cluster_commits", type=int, default=8,
                    help="cluster mode: commit windows per arm (first "
                         "2 are warmup)")
    ap.add_argument("--cluster_buffer_k", type=int, default=32,
                    help="cluster mode: uplinks per lane per commit "
                         "window")
    ap.add_argument("--cluster_row_dim", type=int, default=256,
                    help="cluster mode: flat model row dimension")
    ap.add_argument("--cluster_rate", type=float, default=2000.0,
                    help="cluster mode: peak offered frames/sec PER "
                         "HOST — the fleet's aggregate offer scales "
                         "with the host count (weak scaling); the "
                         "diurnal profile modulates the instantaneous "
                         "rate")
    ap.add_argument("--cluster_population", type=int, default=4096,
                    help="cluster mode: client-id space, range-"
                         "partitioned across hosts")
    ap.add_argument("--cluster_ingest_pool", type=int, default=2,
                    help="cluster mode: decode-pool workers per host")
    ap.add_argument("--cluster_seed", type=int, default=0,
                    help="cluster mode: one seed drives the swarm "
                         "schedule, the arrival profile, and the chaos "
                         "injector")
    ap.add_argument("--secure_commits", type=int, default=12,
                    help="secure mode: commits per clean arm (the "
                         "byzantine arms run half — the overflow arm "
                         "pays a real deadline wait per commit)")
    ap.add_argument("--secure_cohort", type=int, default=8,
                    help="secure mode: round cohort (= buffer_k; masks "
                         "cancel over the FULL cohort)")
    ap.add_argument("--secure_seed", type=int, default=0,
                    help="secure mode: one seed drives the keyring, "
                         "the DP noise, and the byzantine set")
    ap.add_argument("--cluster_arms", default="clean",
                    help="cluster mode extra arms: add 'sparse' for "
                         "the paired dense-vs-sparse_topk uplink arm "
                         "(v17, ISSUE 19) — the fleet ships k=dim/16 "
                         "(index, value) frames and the servers opt "
                         "into the scatter-fold ingest path")
    args = ap.parse_args()
    import jax

    from fedml_tpu import obs
    from fedml_tpu.utils import compile_cache
    compile_cache.configure()
    require_device()
    # FEDML_OBS_DIR enables the span tracer/flight recorder for this
    # bench run (Chrome trace + Prometheus snapshot land there); the
    # default-off path adds nothing to the timed loop
    obs.configure_from_env()
    # v11 programs block: open the profile window, and run the one-time
    # HLO flop/byte census for the torture/serve modes (their programs
    # are small — one extra AOT compile per family, amortized by the
    # compile cache).  The sync/async modes compile CHIP-sized round
    # programs, where a doubled cold compile costs real minutes — they
    # publish dispatch walls always and MFU only under an explicit
    # FEDML_OBS_CENSUS=1 opt-in.
    from fedml_tpu.obs import programs as obs_programs
    global _PROGRAMS_T0
    if args.mode in ("ingest", "chaos", "serve", "connections",
                     "cluster"):
        obs_programs.enable_census(True)
    _PROGRAMS_T0 = obs_programs.snapshot()
    if args.mode == "ingest":
        _bench_ingest(args)
        return
    if args.mode == "chaos":
        _bench_chaos(args)
        return
    if args.mode == "attack":
        _bench_attack(args)
        return
    if args.mode == "serve":
        _bench_serve(args)
        return
    if args.mode == "connections":
        _bench_connections(args)
        return
    if args.mode == "multihost":
        _bench_multihost(args)
        return
    if args.mode == "cluster":
        _bench_cluster(args)
        return
    if args.mode == "secure":
        _bench_secure(args)
        return
    print(f"devices: {jax.devices()}", file=sys.stderr)

    # synthetic CIFAR10-shaped data (real files aren't in the image; shapes
    # and FLOPs match the real workload exactly)
    rs = np.random.RandomState(0)
    n = N_CLIENTS * SAMPLES_PER_CLIENT
    x = rs.rand(n, 32, 32, 3).astype(np.float32)
    y = rs.randint(0, 10, n).astype(np.int64)
    cfg, data, trainer = build_headline(x, y)

    if args.mode == "async":
        _bench_async(cfg, data, trainer)
        return
    engine = headline_engine(cfg, data, trainer)
    one_round = HeadlineRun(engine).step

    def force_completion(variables, m):
        """block_until_ready, then a device→host scalar fetch of the
        loss.  On the v5e block_until_ready alone is a complete barrier
        (chip_smoke.py prints the s/round with and without the fetch;
        PERF.md "Bring-up" has the pair) — the fetch is kept because the
        bench prints the loss anyway."""
        jax.block_until_ready(variables)
        return float(m["train_loss"])

    for _ in range(WARMUP_ROUNDS):
        variables, m = one_round()
    force_completion(variables, m)
    # overlap accounting covers the TIMED window only (the one-time
    # cohort upload above is setup): on this resident-cohort bench the
    # timed rounds do no uploads, so overlap_fraction is 1.0 by
    # definition — the field exists so streaming/block-stream bench
    # variants land in the same BENCH_*.json schema (PERF.md §"Prefetch
    # pipeline")
    engine.transfer_stats.reset()

    import contextlib
    from fedml_tpu.utils.profiling import trace
    trace_dir = os.environ.get("BENCH_TRACE_DIR")
    trace_cm = trace(trace_dir) if trace_dir else contextlib.nullcontext()
    slo_eng = _slo_window()          # v11: judge the timed window
    with trace_cm:
        t0 = time.perf_counter()
        for _ in range(TIMED_ROUNDS):
            variables, m = one_round()
        last_loss = force_completion(variables, m)
        dt = time.perf_counter() - t0

    rps = TIMED_ROUNDS / dt
    print(f"train_loss={last_loss:.4f} "
          f"{dt / TIMED_ROUNDS:.3f}s/round", file=sys.stderr)
    doc = _stamp({
        "metric": "fedavg_cifar10_resnet18gn_128clients_rounds_per_sec",
        "value": round(rps, 4),
        "unit": "rounds/sec",
        "vs_baseline": round(rps / ESTIMATED_REFERENCE_ROUNDS_PER_SEC, 4),
        "mode": "sync",
        "async": None,
        "ingest": None,
        "chaos": None,
        "attack": None,
        "serve": None,
        "connections": None,
        "multihost": None,
        "cluster": None,
        "overlap_fraction": round(
            engine.transfer_stats.overlap_fraction(), 4),
        # byte accounting (transfer-compression layer): mean H2D payload
        # bytes per timed round, from the engine's per-instance counter
        # (reset() above zeroed it after the one-time cohort upload) —
        # 0 on this resident path; the stack-dtype A/B lives in
        # tools/profile_bench.py exp_SD512
        "h2d_bytes_per_round": round(
            engine.transfer_stats.h2d_bytes / TIMED_ROUNDS, 1),
        # per-round transfer records (upload/wait/compute walls +
        # overlap, one dict per bracketed round): empty on this
        # resident-cohort path by design — streaming/block-stream bench
        # variants fill it, and the key keeps one schema across them
        "rounds": [
            {k: round(v, 4) for k, v in r.items()}
            for r in engine.transfer_stats.rounds],
        # v6 stage attribution (per-"round" spans on this sync path);
        # null unless the run is traced
        "critical_path": _critical_path_doc(),
        # v11: the default SLO pack over the timed window (the sync
        # bench drives no async server, so most specs read no_data and
        # the block asserts "nothing judged this run unhealthy") + the
        # per-program-family profile
        "slo": _slo_doc({"timed": _slo_close(slo_eng)}),
        "programs": _programs_doc(),
    })
    if obs.enabled():
        obs.export()                   # trace + metrics into FEDML_OBS_DIR
        doc["obs"] = obs.rollup()
    print(json.dumps(doc))


# async-mode shape: concurrency 32 / buffer 8 keeps the dispatch-wave
# vmap at a quarter of the sync bench's 128-wide cohort (the async
# engine runs unchunked vmap waves, not the mesh scan) while the
# 4x concurrency/K ratio plus lognormal latencies produces genuine
# staleness — the regime the discount weights exist for.
ASYNC_CONCURRENCY = 32
ASYNC_BUFFER_K = 8
ASYNC_WARMUP_COMMITS = 2
ASYNC_TIMED_COMMITS = 12


def _bench_async(cfg, data, trainer) -> None:
    """committed-updates/sec of the buffered async scheduler on the
    bench workload, under the seeded lognormal-latency lifecycle.
    Latencies are SIMULATED (no sleeps): the wall measures compute —
    dispatch-wave training + staleness-discounted commits."""
    import jax

    from fedml_tpu import obs
    from fedml_tpu.async_ import AsyncFedAvgEngine, LifecycleConfig

    cfg.frequency_of_the_test = 1        # wall_time per commit
    lc = LifecycleConfig(latency="lognormal", latency_scale=1.0,
                         latency_sigma=0.5, heterogeneity=0.5, seed=0)
    engine = AsyncFedAvgEngine(trainer, data, cfg,
                               buffer_k=ASYNC_BUFFER_K,
                               concurrency=ASYNC_CONCURRENCY,
                               staleness="polynomial", staleness_a=0.5,
                               lifecycle_cfg=lc)
    slo_eng = _slo_window()          # v11: one arm = the whole run
    total = ASYNC_WARMUP_COMMITS + ASYNC_TIMED_COMMITS
    variables = engine.run(rounds=total)
    jax.block_until_ready(variables)
    walls = [m["wall_time"] for m in engine.metrics_history]
    dt = walls[total - 1] - walls[ASYNC_WARMUP_COMMITS - 1]
    ups = ASYNC_TIMED_COMMITS / dt
    rep = engine.async_report()
    print(f"{dt / ASYNC_TIMED_COMMITS:.3f}s/commit  "
          f"staleness p50/p95 {rep['staleness_p50']:.0f}/"
          f"{rep['staleness_p95']:.0f}", file=sys.stderr)
    doc = _stamp({
        "metric": ("fedavg_cifar10_resnet18gn_128clients_async_"
                   "committed_updates_per_sec"),
        "value": round(ups, 4),
        "unit": "commits/sec",
        # the sync baseline estimate is a per-ROUND number; an async
        # commit aggregates buffer_k of 128 clients, so cross-mode
        # ratios are not meaningful — recorded as null by design
        "vs_baseline": None,
        "mode": "async",
        "overlap_fraction": None,
        "h2d_bytes_per_round": None,
        "rounds": [],
        "async": {k: (round(v, 4) if isinstance(v, float) else v)
                  for k, v in rep.items()},
        "ingest": None,
        "chaos": None,
        "attack": None,
        "serve": None,
        "connections": None,
        "multihost": None,
        "cluster": None,
        # v6: commit-to-commit stage attribution from the scheduler's
        # spans (train waves / commits / eval + wait); null untraced
        "critical_path": _critical_path_doc(),
        "slo": _slo_doc({"run": _slo_close(slo_eng)}),
        "programs": _programs_doc(),
    })
    if obs.enabled():
        obs.export()
        doc["obs"] = obs.rollup()
    print(json.dumps(doc))


# ingest-mode shape: 8-deep buffer under 32 saturating clients is the
# same 4x oversubscription the async bench runs, and 30 timed commits
# (240 committed updates) keep even the slow legacy arm's wall around a
# minute on a small box.
INGEST_BUFFER_K = 8
INGEST_WARMUP_COMMITS = 5


def _bench_ingest(args) -> None:
    """Concurrent-uplink ingestion torture (ISSUE 6): N in-process
    clients saturate one transport with pre-encoded result frames while
    the server ingests and commits.  Arms: the PR-5 legacy path
    faithfully (inline decode on the recv threads, unbounded inbox,
    drained O(K·P) commit), the same path with ONLY this PR's inbox
    backpressure (queue-discipline isolation), and decode-into +
    streaming aggregation-on-arrival at each --ingest_pools size.  The
    headline is speedup_vs_legacy = best arm / legacy sustained
    committed-updates/sec — the >=2x acceptance gate."""
    from fedml_tpu import obs
    from fedml_tpu.async_.torture import run_ingest_torture

    pools = [int(p) for p in str(args.ingest_pools).split(",") if p.strip()]
    if not pools or any(p < 1 for p in pools):
        # fail BEFORE the two slow legacy arms burn their minutes; pool=0
        # is the inline FSM route, which would mislabel the A/B table
        raise SystemExit(
            f"--ingest_pools must be a comma-separated list of decode-pool "
            f"sizes >= 1, got {args.ingest_pools!r}")
    port = int(os.environ.get("BENCH_INGEST_PORT", "53300"))

    arm_no = [0]

    def run(tag, **kw):
        # fresh port per arm: the previous arm's listener may linger in
        # TIME_WAIT, and a straggler client thread could still be
        # connected to it
        arm_no[0] += 1
        rep = run_ingest_torture(
            n_clients=args.ingest_clients, backend=args.ingest_backend,
            buffer_k=INGEST_BUFFER_K, commits=args.ingest_commits,
            warmup_commits=INGEST_WARMUP_COMMITS,
            base_port=port + arm_no[0], **kw)
        print(f"{tag}: {rep['committed_updates_per_sec']:.1f} updates/s  "
              f"decode p50/p95 {rep['decode_p50_s'] * 1e3:.2f}/"
              f"{rep['decode_p95_s'] * 1e3:.2f} ms  "
              f"lock wait {rep['lock_wait_seconds']:.2f}s", file=sys.stderr)
        return rep

    legacy = run("legacy pool=0", ingest_pool=0, decode_into=False,
                 streaming=False)
    # queue-discipline isolation: the SAME decode+drain path with only
    # this PR's inbox backpressure applied, so the table separates the
    # "stop letting the heap absorb the uplinks" win from the
    # decode-into/streaming win
    bounded = run("legacy bounded-inbox", ingest_pool=0, decode_into=False,
                  streaming=False, inbox_bound=2 * args.ingest_clients)
    arms = [run(f"decode-into pool={p}", ingest_pool=p, decode_into=True,
                streaming=True) for p in pools]
    best = max(arms, key=lambda r: r["committed_updates_per_sec"])
    legacy_ups = legacy["committed_updates_per_sec"]
    doc = _stamp({
        "metric": (f"async_ingest_{args.ingest_backend.lower()}_"
                   f"{args.ingest_clients}clients_"
                   "committed_updates_per_sec"),
        "value": round(best["committed_updates_per_sec"], 4),
        "unit": "updates/sec",
        # the sync baseline estimate prices training FLOPs; the torture
        # path trains nothing — the in-schema comparison is the legacy
        # arm, so vs_baseline stays null by design
        "vs_baseline": None,
        "mode": "ingest",
        "overlap_fraction": None,
        "h2d_bytes_per_round": None,
        "rounds": [],
        "async": None,
        "attack": None,
        "serve": None,
        "connections": None,
        "multihost": None,
        "cluster": None,
        "ingest": {
            "backend": legacy["backend"],
            "n_clients": legacy["n_clients"],
            "buffer_k": legacy["buffer_k"],
            "p": legacy["p"],
            "frame_bytes": legacy["frame_bytes"],
            "commits": legacy["commits"],
            "legacy": {k: round(legacy[k], 6) for k in (
                "committed_updates_per_sec", "decode_p50_s",
                "decode_p95_s", "lock_wait_seconds")},
            "legacy_bounded_inbox": {k: round(bounded[k], 6) for k in (
                "committed_updates_per_sec", "decode_p50_s",
                "decode_p95_s", "lock_wait_seconds")},
            "arms": [{
                "ingest_pool": a["ingest_pool"],
                "committed_updates_per_sec": round(
                    a["committed_updates_per_sec"], 4),
                "decode_p50_s": round(a["decode_p50_s"], 6),
                "decode_p95_s": round(a["decode_p95_s"], 6),
                "lock_wait_seconds": round(a["lock_wait_seconds"], 4),
            } for a in arms],
            "speedup_vs_legacy": round(
                best["committed_updates_per_sec"] / legacy_ups, 2)
                if legacy_ups > 0 else None,
        },
        # v11: per-arm SLO verdicts (every ingest arm is clean traffic
        # — breaches here regress) + the program profile
        "slo": _slo_doc({
            "legacy": legacy.get("slo_arm"),
            "legacy_bounded_inbox": bounded.get("slo_arm"),
            **{f"pool_{a['ingest_pool']}": a.get("slo_arm")
               for a in arms},
        }),
        "programs": _programs_doc(),
        # v6: the BEST arm's decode/fold/commit attribution (each
        # torture run computes its own window-scoped report); null
        # untraced
        "critical_path": (
            {k: v for k, v in best["critical_path"].items()
             if k != "rounds"}
            if best.get("critical_path") else None),
    })
    if obs.enabled():
        obs.export()
        doc["obs"] = obs.rollup()
    print(json.dumps(doc))


# chaos-mode shape: every arm runs the reliable ingest torture (window-
# limited FMLR uplink pushers, decode-into + streaming, pool 4) so the
# curve isolates the FAULTS, not a transport change; 12 commits/arm
# keeps the ~8-arm sweep around a few minutes on a small box.
CHAOS_INGEST_POOL = 4
CHAOS_WARMUP_COMMITS = 2
CHAOS_CURVE_RATES = (0.05, 0.10, 0.20)
CHAOS_MIXED = {"drop": 0.05, "dup": 0.01, "corrupt": 0.005}


def _bench_chaos(args) -> None:
    """Goodput-vs-fault-rate curves (ISSUE 8): the concurrent-uplink
    ingest torture with the reliability envelope ON, under seeded
    wire-level fault injection (fedml_tpu/comm/chaos.py) at the
    server's receive chokepoint.  Arms: a clean reliable baseline, a
    sweep of loss (drop), duplicate and corrupt rates at 5/10/20%, and
    the acceptance-shaped "mixed" arm (5% loss + 1% dup + 0.5%
    corrupt).  Every row reports committed-updates/sec, the goodput
    ratio vs the clean arm, and the retry/dedup/quarantine/recv-death
    counters — the ≥0.5x-of-clean, zero-recv-deaths gate's raw
    numbers."""
    from fedml_tpu import obs
    from fedml_tpu.async_.torture import run_ingest_torture

    port = int(os.environ.get("BENCH_CHAOS_PORT", "53400"))
    arm_no = [0]

    def run(tag, chaos=None):
        arm_no[0] += 1
        rep = run_ingest_torture(
            n_clients=args.chaos_clients, backend=args.chaos_backend,
            buffer_k=INGEST_BUFFER_K, commits=args.chaos_commits,
            warmup_commits=CHAOS_WARMUP_COMMITS,
            ingest_pool=CHAOS_INGEST_POOL, decode_into=True,
            streaming=True, base_port=port + arm_no[0], timeout_s=600,
            reliable=True, chaos=chaos, chaos_seed=args.chaos_seed)
        print(f"{tag}: {rep['committed_updates_per_sec']:.1f} updates/s  "
              f"retries {rep['retries']:.0f}  dups suppressed "
              f"{rep['dups_suppressed']:.0f}  quarantined "
              f"{rep['quarantined']:.0f}  recv deaths "
              f"{rep['recv_thread_deaths']:.0f}", file=sys.stderr)
        return rep

    def row(rep, clean_ups, **rates):
        return {
            "drop": rates.get("drop", 0.0),
            "dup": rates.get("dup", 0.0),
            "corrupt": rates.get("corrupt", 0.0),
            "committed_updates_per_sec": round(
                rep["committed_updates_per_sec"], 4),
            "goodput_ratio": round(
                rep["committed_updates_per_sec"] / clean_ups, 4)
                if clean_ups > 0 else None,
            "retries": rep["retries"],
            "dups_suppressed": rep["dups_suppressed"],
            "quarantined": rep["quarantined"],
            "abandoned": rep["abandoned"],
            "recv_thread_deaths": rep["recv_thread_deaths"],
            "chaos_injected": rep["chaos_injected"],
        }

    slo_arms: dict = {}
    clean = run("clean reliable")
    slo_arms["clean"] = clean.get("slo_arm")
    clean_ups = clean["committed_updates_per_sec"]
    curve = []
    for key in ("drop", "dup", "corrupt"):
        for rate in CHAOS_CURVE_RATES:
            rep = run(f"{key}_{int(rate * 100)}", {key: rate})
            # "curve_" prefix: bench_diff treats these as
            # breach-by-design fault arms, never clean ones
            slo_arms[f"curve_{key}_{int(rate * 100)}"] = \
                rep.get("slo_arm")
            curve.append(row(rep, clean_ups, **{key: rate}))
    mixed = run("mixed (5% loss + 1% dup + 0.5% corrupt)",
                dict(CHAOS_MIXED))
    slo_arms["mixed"] = mixed.get("slo_arm")
    doc = _stamp({
        "metric": (f"async_chaos_{args.chaos_backend.lower()}_"
                   f"{args.chaos_clients}clients_"
                   "committed_updates_per_sec"),
        "value": round(mixed["committed_updates_per_sec"], 4),
        "unit": "updates/sec",
        # the in-schema comparison is the clean reliable arm
        "vs_baseline": None,
        "mode": "chaos",
        "overlap_fraction": None,
        "h2d_bytes_per_round": None,
        "rounds": [],
        "async": None,
        "ingest": None,
        "attack": None,
        "serve": None,
        "connections": None,
        "multihost": None,
        "cluster": None,
        "chaos": {
            "backend": clean["backend"],
            "n_clients": clean["n_clients"],
            "buffer_k": clean["buffer_k"],
            "p": clean["p"],
            "frame_bytes": clean["frame_bytes"],
            "commits": clean["commits"],
            "seed": args.chaos_seed,
            "clean": row(clean, clean_ups),
            "curve": curve,
            "mixed": row(mixed, clean_ups, **CHAOS_MIXED),
            "goodput_vs_clean": round(
                mixed["committed_updates_per_sec"] / clean_ups, 4)
                if clean_ups > 0 else None,
        },
        "critical_path": (
            {k: v for k, v in mixed["critical_path"].items()
             if k != "rounds"}
            if mixed.get("critical_path") else None),
        "slo": _slo_doc(slo_arms),
        "programs": _programs_doc(),
    })
    if obs.enabled():
        obs.export()
        doc["obs"] = obs.rollup()
    print(json.dumps(doc))


# attack-mode shape (ISSUE 9): the accuracy matrix runs the SAME
# synthetic MNIST-LR async workload the quality bands calibrate
# (1000 clients, buffer K=8, concurrency 16, polynomial staleness,
# lognormal latency), so matrix rows are directly band-comparable;
# the defense arm is the band's defense config.  The overhead pair
# reruns the ingest torture with the admission screen on vs off —
# honest traffic only, so quarantines there are false positives by
# definition and the throughput ratio isolates the screen's cost.
ATTACK_FRAC = 0.2
ATTACK_BOOST = 20.0
# the MIXED arm runs the quality-band calibration shape EXACTLY
# (benchmarks/quality_bands.json async_mnist_lr_attacked_*: boost β=8,
# poison_frac 1.0) so its defended/undefended accuracies are directly
# band-comparable; the other matrix rows explore at ATTACK_BOOST
ATTACK_BAND_BOOST = 8.0
ATTACK_BAND_POISON = 1.0
ATTACK_MATRIX_MODES = ("signflip", "boost", "gaussian", "labelflip",
                       "mixed")
ATTACK_DEFENSE = dict(norm_bound=2.0, screen=True, z_max=8.0,
                      cos_min=-1.0, screen_warmup=10, buckets=4, trim_k=0)
ATTACK_OVERHEAD_COMMITS = 20


def _bench_attack(args) -> None:
    """Attack x defense accuracy/goodput matrix (ISSUE 9): every
    adversary family from fedml_tpu/async_/adversary.py against the
    admission pipeline + bucketed robust commit, on the async MNIST-LR
    quality-band workload, plus the admission-overhead ingest pair.
    Gates: the mixed defended arm stays within the clean band while
    undefended degrades, zero honest quarantines in the clean arm;
    the overhead pair's throughput_ratio prices the fused screen
    (>= 0.9x on chip, ~0.73x paired-median on the fold-bottlenecked
    2-core CI box — PERF.md "Adversarial robustness")."""
    import jax

    from fedml_tpu import obs
    from fedml_tpu.async_ import (AsyncFedAvgEngine, AttackConfig,
                                  DefenseConfig, LifecycleConfig)
    from fedml_tpu.async_.torture import run_ingest_torture
    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.data.loaders import load_data
    from fedml_tpu.models import create_model
    from fedml_tpu.utils.config import FedConfig

    data = load_data("mnist", client_num_in_total=1000, batch_size=10,
                     synthetic_scale=0.2, seed=0)
    cfg = FedConfig(client_num_in_total=1000, client_num_per_round=16,
                    comm_round=args.attack_commits, epochs=1,
                    batch_size=10, lr=0.03, frequency_of_the_test=10_000)
    lc = LifecycleConfig(latency="lognormal", latency_scale=1.0,
                         latency_sigma=0.8, heterogeneity=0.5, seed=0)

    def arm(tag, attack_mode, defended):
        trainer = ClientTrainer(create_model("lr", output_dim=10),
                                lr=cfg.lr)
        attack = None
        if attack_mode == "mixed":
            attack = AttackConfig(mode="mixed", frac=ATTACK_FRAC,
                                  boost=ATTACK_BAND_BOOST,
                                  poison_frac=ATTACK_BAND_POISON,
                                  seed=args.attack_seed)
        elif attack_mode != "none":
            attack = AttackConfig(mode=attack_mode, frac=ATTACK_FRAC,
                                  boost=ATTACK_BOOST,
                                  seed=args.attack_seed)
        defense = (DefenseConfig(**ATTACK_DEFENSE) if defended else None)
        eng = AsyncFedAvgEngine(trainer, data, cfg, buffer_k=8,
                                concurrency=16, staleness="polynomial",
                                staleness_a=0.5, lifecycle_cfg=lc,
                                attack=attack, defense=defense)
        v = eng.run(rounds=args.attack_commits)
        acc = float(eng.evaluate(v)["test_acc"])
        rep = eng.async_report()
        attrib = eng.quarantine_attribution()
        print(f"{tag}: acc {acc:.3f}  quarantined "
              f"{rep.get('quarantined_total', 0)} "
              f"(byz {attrib['byzantine']} / honest {attrib['honest']})",
              file=sys.stderr)
        return {"attack": attack_mode, "defended": defended,
                "test_acc": round(acc, 4),
                "quarantined": rep.get("quarantined", {}),
                "quarantined_total": rep.get("quarantined_total", 0),
                "quarantined_byzantine": attrib["byzantine"],
                "quarantined_honest": attrib["honest"],
                "byzantine_clients": rep.get("byzantine_clients", 0)}

    clean = arm("clean undefended", "none", False)
    clean_def = arm("clean defended", "none", True)
    matrix = []
    for mode in ATTACK_MATRIX_MODES:
        matrix.append(arm(f"{mode} undefended", mode, False))
        matrix.append(arm(f"{mode} defended", mode, True))
    mixed_und = next(r for r in matrix
                     if r["attack"] == "mixed" and not r["defended"])
    mixed_def = next(r for r in matrix
                     if r["attack"] == "mixed" and r["defended"])

    # admission-overhead pair: honest ingest torture, screen off vs on
    port = int(os.environ.get("BENCH_ATTACK_PORT", "53500"))
    off = run_ingest_torture(
        n_clients=args.attack_ingest_clients, backend=args.attack_backend,
        buffer_k=INGEST_BUFFER_K, commits=ATTACK_OVERHEAD_COMMITS,
        warmup_commits=3, ingest_pool=4, decode_into=True, streaming=True,
        base_port=port + 1)
    on = run_ingest_torture(
        n_clients=args.attack_ingest_clients, backend=args.attack_backend,
        buffer_k=INGEST_BUFFER_K, commits=ATTACK_OVERHEAD_COMMITS,
        warmup_commits=3, ingest_pool=4, decode_into=True, streaming=True,
        base_port=port + 2,
        defense=DefenseConfig(screen=True, z_max=8.0, screen_warmup=8))
    ratio = (on["committed_updates_per_sec"]
             / off["committed_updates_per_sec"]
             if off["committed_updates_per_sec"] > 0 else None)
    print(f"overhead: screen-off {off['committed_updates_per_sec']:.1f} "
          f"-> screen-on {on['committed_updates_per_sec']:.1f} updates/s "
          f"(ratio {f'{ratio:.2f}' if ratio is not None else 'n/a'}; "
          f"chip gate >= 0.9)  false-positive "
          f"quarantines {on['admission']['quarantined_total']}",
          file=sys.stderr)

    doc = _stamp({
        "metric": "async_attack_mnist_lr_defended_acc",
        "value": mixed_def["test_acc"],
        "unit": "accuracy",
        # the in-schema comparisons are the clean and undefended arms
        "vs_baseline": None,
        "mode": "attack",
        "overlap_fraction": None,
        "h2d_bytes_per_round": None,
        "rounds": [],
        "async": None,
        "ingest": None,
        "chaos": None,
        "serve": None,
        "connections": None,
        "multihost": None,
        "cluster": None,
        "attack": {
            "workload": "async_mnist_lr (quality-band shape, K=8, "
                        "conc 16, poly a=0.5)",
            "frac": ATTACK_FRAC,
            "boost": ATTACK_BOOST,
            "seed": args.attack_seed,
            "defense": dict(ATTACK_DEFENSE),
            "clean_acc": clean["test_acc"],
            "clean_defended_acc": clean_def["test_acc"],
            "defended_acc": mixed_def["test_acc"],
            "undefended_acc": mixed_und["test_acc"],
            "false_positive_quarantines":
                clean_def["quarantined_honest"],
            "matrix": [clean, clean_def] + matrix,
            "overhead": {
                "backend": off["backend"],
                "n_clients": off["n_clients"],
                "screen_off_updates_per_sec": round(
                    off["committed_updates_per_sec"], 4),
                "screen_on_updates_per_sec": round(
                    on["committed_updates_per_sec"], 4),
                "throughput_ratio": (round(ratio, 4)
                                     if ratio is not None else None),
                "screen_on_quarantined":
                    on["admission"]["quarantined_total"],
            },
        },
        "critical_path": _critical_path_doc(),
        # v11: the overhead pair is honest traffic — its SLO arms are
        # clean; the accuracy matrix runs in-process (no comm metrics)
        "slo": _slo_doc({"overhead_screen_off": off.get("slo_arm"),
                         "overhead_screen_on": on.get("slo_arm")}),
        "programs": _programs_doc(),
    })
    if obs.enabled():
        obs.export()
        doc["obs"] = obs.rollup()
    print(json.dumps(doc))


# secure-mode shape (ISSUE 20): the clean arms share one workload
# (async MNIST-LR, full-cohort barrier, INPROC, no lifecycle latency)
# so the plain/secure pair isolates the DATA PLANE — quantize + mask +
# field fold + unmask vs flatten + f32 fold.  Byzantine arms run the
# same workload with a boost adversary at two magnitudes: one inside
# the ENFORCED quantizer bound — since the REVIEW fix that is the
# per-client cohort-headroom slice (p−1)//(2K·scale), |w·x| < 2048 at
# cohort 8 / scale 2^16, NOT the field half-range — and one past it
# (the range refusal that survives masking).  The in-field boost must
# clear that slice with margin or the arm's attackers are refused at
# quantize, never upload, and the no-deadline barrier stalls: boost 8
# keeps this workload's rows at ~55% of the bound (boost 50 is now
# correctly refused — the headroom guard catching sum-aliasing rows
# the old per-word bound let through).
SECURE_BYZ_FRAC = 0.25
SECURE_BYZ_BOOST_INFIELD = 8.0
SECURE_BYZ_BOOST_OVERFLOW = 1e9
SECURE_OVERFLOW_DEADLINE_S = 0.5


def _bench_secure(args) -> None:
    """Privacy-tax bench for the pairwise-mask data plane (ISSUE 20,
    fedml_tpu/secure/): plain vs masked committed-updates/sec on the
    live async messaging FSM plus the end-to-end private mode's
    accuracy cost, the masks-cancel bitwise protocol pin, and the
    masked-byzantine pair.  Gates (tools/bench_diff.py v18): the tax
    ratio stays above the floor, zero below-threshold commits on the
    clean arms, and the bitwise pin holds."""
    import jax
    import jax.numpy as jnp

    from fedml_tpu import obs
    from fedml_tpu.async_ import AttackConfig
    from fedml_tpu.async_.lifecycle import run_async_messaging
    from fedml_tpu.core import mpc
    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.data.loaders import load_data
    from fedml_tpu.models import create_model
    from fedml_tpu.secure import SecAggConfig, SecureAggregator
    from fedml_tpu.utils.config import FedConfig

    cohort = args.secure_cohort
    data = load_data("mnist", client_num_in_total=cohort, batch_size=10,
                     synthetic_scale=0.2, seed=0)

    def arm(tag, commits, secure=None, attack=None, deadline=None):
        cfg = FedConfig(client_num_in_total=cohort,
                        client_num_per_round=cohort, comm_round=commits,
                        epochs=1, batch_size=10, lr=0.03,
                        frequency_of_the_test=10_000)
        trainer = ClientTrainer(create_model("lr", output_dim=10),
                                lr=cfg.lr)
        slo_eng = _slo_window()
        t0 = time.perf_counter()
        variables, server = run_async_messaging(
            trainer, data, cfg, buffer_k=cohort, worker_num=cohort,
            total_commits=commits, secure=secure, attack=attack,
            deadline_s=deadline)
        wall = time.perf_counter() - t0
        sums = jax.jit(trainer.evaluate)(
            variables, jax.tree.map(jnp.asarray, data.test_global))
        cnt = max(float(sums["count"]), 1.0)
        row = {"arm": tag,
               "commits": server.version,
               "updates_per_sec": round(server.updates_committed / wall,
                                        4),
               "test_acc": round(float(sums["correct"]) / cnt, 4),
               "slo_arm": _slo_close(slo_eng)}
        if secure is not None:
            rep = server._secure.report()
            row.update(
                below_threshold_commits=server.secure_below_threshold,
                recovered_rounds=rep["recovered_rounds"],
                rejected_uplinks=int(
                    obs.counter("secagg_rejected_uplinks_total").value))
        print(f"{tag}: {row['updates_per_sec']:.1f} updates/s  "
              f"acc {row['test_acc']:.3f}", file=sys.stderr)
        return row

    def _sec_cfg(**kw):
        return SecAggConfig(seed=args.secure_seed, **kw)

    commits = args.secure_commits
    plain = arm("plain", commits)
    sec = arm("secure", commits, secure=_sec_cfg())
    dp = arm("secure_dp", commits,
             secure=_sec_cfg(dp_clip=3.0, dp_noise=1e-3))
    byz_kw = dict(frac=SECURE_BYZ_FRAC, seed=args.secure_seed)
    rej0 = int(obs.counter("secagg_rejected_uplinks_total").value)
    infield = arm(
        "byz_infield", max(commits // 2, 2),
        secure=_sec_cfg(),
        attack=AttackConfig(mode="boost",
                            boost=SECURE_BYZ_BOOST_INFIELD, **byz_kw))
    overflow = arm(
        "byz_overflow", max(commits // 2, 2),
        secure=_sec_cfg(),
        attack=AttackConfig(mode="boost",
                            boost=SECURE_BYZ_BOOST_OVERFLOW, **byz_kw),
        deadline=SECURE_OVERFLOW_DEADLINE_S)
    # the counter is process-global: attribute the deltas per arm
    overflow["rejected_uplinks"] -= infield["rejected_uplinks"]
    infield["rejected_uplinks"] -= rej0

    tax = (sec["updates_per_sec"] / plain["updates_per_sec"]
           if plain["updates_per_sec"] > 0 else None)
    print(f"privacy tax: plain {plain['updates_per_sec']:.1f} -> "
          f"masked {sec['updates_per_sec']:.1f} updates/s "
          f"(ratio {f'{tax:.2f}' if tax is not None else 'n/a'})",
          file=sys.stderr)

    # masks-cancel protocol pin, pure integers outside the FSM: a
    # full-cohort masked field sum must equal the plain fixed-point
    # sum BITWISE — masks cancel exactly or not at all
    pin_cfg = _sec_cfg()
    pin_dim, pin_ids = 64, list(range(1, 6))
    pin = SecureAggregator(pin_cfg, pin_ids, pin_dim)
    rs = np.random.RandomState(args.secure_seed + 5)
    p = pin_cfg.prime
    expected = np.zeros(pin_dim + 1, np.int64)
    for c in pin_ids:
        pin.escrow(c)
        flat = rs.randn(pin_dim) * 0.1
        w = float(rs.randint(1, 50))
        q = np.empty(pin_dim + 1, np.int64)
        q[:pin_dim] = mpc.quantize(flat * w, pin_cfg.scale, p)
        q[pin_dim] = mpc.quantize(np.array([w]), pin_cfg.scale, p)[0]
        expected = (expected + q) % p
        pin.fold(c, pin.client_row(c, 0, flat, w))
    words, _included = pin.field_sum(0, pin.arrived)
    masks_cancel = bool(np.array_equal(np.asarray(words) % p, expected))
    print(f"masks cancel bitwise: {masks_cancel}", file=sys.stderr)

    # uplink bytes, measured on REAL encoded frames (the INPROC runs
    # above never serialize): one plain-path uplink (f32 pytree +
    # plaintext sample count) vs one masked uplink (u32 field words,
    # dim+1 — the weight rides as the masked trailing word) through
    # MessageCodec.encode, framed exactly as lifecycle.py ships them
    from fedml_tpu.async_.staleness import flat_dim
    from fedml_tpu.comm.message import Message, MessageCodec
    bytes_vars = ClientTrainer(create_model("lr", output_dim=10),
                               lr=0.03).init(
        jax.random.PRNGKey(0), jnp.asarray(data.client_shards["x"][0, 0]))
    dim = flat_dim(bytes_vars)
    m_plain = Message(4, 1, 0)
    m_plain.add_params("model_params",
                       jax.tree.map(np.asarray, bytes_vars))
    m_plain.add_params("num_samples", 50.0)
    m_plain.add_params("version", 0)
    plain_bytes = len(MessageCodec.encode(m_plain))
    m_sec = Message(4, 1, 0)
    m_sec.add_params("model_params",
                     rs.randint(0, p, dim + 1).astype(np.uint32))
    m_sec.add_params("num_samples", 1.0)
    m_sec.add_params("secagg", {"round": 0})
    m_sec.add_params("version", 0)
    m_sec.set_wire_transport("model_params", "secagg",
                             scale=pin_cfg.scale, p=p)
    sec_bytes = len(MessageCodec.encode(m_sec))
    print(f"uplink frame: plain {plain_bytes} B -> masked {sec_bytes} B "
          f"(dim {dim}; masked words are incompressible by design)",
          file=sys.stderr)

    doc = _stamp({
        "metric": "secure_agg_mnist_lr_privacy_tax_ratio",
        "value": round(tax, 4) if tax is not None else None,
        "unit": "ratio",
        "vs_baseline": None,
        "mode": "secure",
        "overlap_fraction": None,
        "h2d_bytes_per_round": None,
        "rounds": [],
        "async": None,
        "ingest": None,
        "chaos": None,
        "attack": None,
        "serve": None,
        "connections": None,
        "multihost": None,
        "cluster": None,
        "secure": {
            "workload": f"async_mnist_lr (INPROC, cohort {cohort}, "
                        "full-cohort barrier, no lifecycle latency)",
            "cohort": cohort,
            "threshold": pin_cfg.resolve_threshold(cohort),
            "scale": pin_cfg.scale,
            "seed": args.secure_seed,
            "privacy_tax_ratio": (round(tax, 4)
                                  if tax is not None else None),
            "plain_updates_per_sec": plain["updates_per_sec"],
            "secure_updates_per_sec": sec["updates_per_sec"],
            "plain_uplink_bytes": plain_bytes,
            "secure_uplink_bytes": sec_bytes,
            "uplink_bytes_ratio": round(sec_bytes / plain_bytes, 4),
            "flat_dim": dim,
            "plain_acc": plain["test_acc"],
            "secure_acc": sec["test_acc"],
            "dp_acc": dp["test_acc"],
            "acc_delta_secure_vs_plain": round(
                sec["test_acc"] - plain["test_acc"], 4),
            "masks_cancel_bitwise_ok": masks_cancel,
            "below_threshold_commits_clean": (
                sec["below_threshold_commits"]
                + dp["below_threshold_commits"]),
            "byzantine": {
                "frac": SECURE_BYZ_FRAC,
                # admission screening reads plaintext rows and is
                # BLINDED under masks: the in-field boost commits
                # unimpeded (its damage shows in test_acc); the only
                # surviving enforcement is the client-side quantizer
                # range refusal, which the overflow boost trips
                "infield": infield,
                "overflow": overflow,
            },
            "arms": [plain, sec, dp, infield, overflow],
        },
        "critical_path": _critical_path_doc(),
        "slo": _slo_doc({r["arm"]: r.pop("slo_arm")
                         for r in (plain, sec, dp, infield, overflow)}),
        "programs": _programs_doc(),
    })
    if obs.enabled():
        obs.export()
        doc["obs"] = obs.rollup()
    print(json.dumps(doc))


# serve-mode shape (ISSUE 10): one virtual-time serve-loop arm per
# simulated population, same buffer/arrival/sampler config across arms,
# so the table isolates POPULATION — the north star's heavy-traffic
# axis.  The sub-linear gate is the registry's allocated bytes per
# client (<= ~100 B; 29 B at the current field set), asserted per arm.
SERVE_WARMUP_COMMITS = 4
SERVE_BYTES_PER_CLIENT_GATE = 100.0


def _bench_serve(args) -> None:
    """Million-client serving-spine bench (ISSUE 10, fedml_tpu/scale/):
    sustained committed-updates/sec and server memory versus simulated
    client population.  Each arm drives the REAL PR-6 streaming
    buffer/commit through the sharded registry + streaming cohort
    sampler under a seeded arrival process in virtual time; client
    compute is out of scope (pre-generated update rows), so the wall
    prices the SERVER round hot path.  Gates: registry bytes/client
    <= ~100 at every population (sub-linear memory), updates/sec
    sustained (the 1M arm within 2x of the 10k arm on a healthy
    box)."""
    from fedml_tpu import obs
    from fedml_tpu.scale import ArrivalConfig, run_serve_sim

    pops = sorted(int(p) for p in str(args.serve_populations).split(",")
                  if p.strip())
    if not pops or pops[0] < 1:
        raise SystemExit(
            f"--serve_populations must be a comma-separated list of "
            f"positive client counts, got {args.serve_populations!r}")
    # sorted above: the headline row and sustain_ratio_vs_smallest
    # assume rows[-1] is the LARGEST population
    arrival = ArrivalConfig(mode=args.serve_arrivals, rate=2000.0,
                            period_s=600.0, amplitude=0.8,
                            flash_at_s=5.0, flash_duration_s=10.0,
                            flash_boost=5.0, seed=args.serve_seed)
    rows = []
    slo_arms: dict = {}
    for pop in pops:
        slo_eng = _slo_window()      # v11: one arm per population
        rep = run_serve_sim(
            pop, commits=args.serve_commits,
            warmup_commits=SERVE_WARMUP_COMMITS,
            buffer_k=args.serve_buffer_k, row_dim=args.serve_row_dim,
            sampler_mode=args.serve_sampler, arrival=arrival,
            dropout_prob=0.02, banned_frac=0.01, seed=args.serve_seed)
        slo_arms[f"pop_{pop}"] = _slo_close(slo_eng)
        rep["sublinear_ok"] = bool(
            rep["registry_bytes_per_client"] <= SERVE_BYTES_PER_CLIENT_GATE)
        print(f"serve pop={pop}: "
              f"{rep['committed_updates_per_sec']:.0f} updates/s  "
              f"registry {rep['registry_bytes'] / 1e6:.1f} MB "
              f"({rep['registry_bytes_per_client']:.1f} B/client)  "
              f"rss {rep['rss_bytes'] / 1e6:.0f} MB  virtual "
              f"{rep['virtual_time_s']:.1f}s", file=sys.stderr)
        rows.append(rep)
    head = rows[-1]            # the largest population is the headline
    doc = _stamp({
        "metric": (f"serve_spine_{head['population']}clients_"
                   "committed_updates_per_sec"),
        "value": round(head["committed_updates_per_sec"], 4),
        "unit": "updates/sec",
        # the in-schema comparison is across the population arms
        "vs_baseline": None,
        "mode": "serve",
        "overlap_fraction": None,
        "h2d_bytes_per_round": None,
        "rounds": [],
        "async": None,
        "ingest": None,
        "chaos": None,
        "attack": None,
        "connections": None,
        "multihost": None,
        "cluster": None,
        "serve": {
            "buffer_k": args.serve_buffer_k,
            "row_dim": args.serve_row_dim,
            "sampler_mode": args.serve_sampler,
            "arrival_mode": args.serve_arrivals,
            "commits": args.serve_commits,
            "seed": args.serve_seed,
            "bytes_per_client_gate": SERVE_BYTES_PER_CLIENT_GATE,
            "populations": [{
                "population": r["population"],
                "committed_updates_per_sec": round(
                    r["committed_updates_per_sec"], 4),
                "registry_bytes": r["registry_bytes"],
                "registry_bytes_per_client": round(
                    r["registry_bytes_per_client"], 2),
                "registry_shards_allocated":
                    r["registry_shards_allocated"],
                "sampler_peak_scratch_bytes":
                    r["sampler_peak_scratch_bytes"],
                "rss_bytes": r["rss_bytes"],
                "virtual_time_s": round(r["virtual_time_s"], 3),
                "mean_arrival_rate": round(r["mean_arrival_rate"], 2),
                "crashed": r["crashed"],
                "banned": r["banned"],
                "sublinear_ok": r["sublinear_ok"],
            } for r in rows],
            "sublinear_ok": all(r["sublinear_ok"] for r in rows),
            "sustain_ratio_vs_smallest": round(
                head["committed_updates_per_sec"]
                / rows[0]["committed_updates_per_sec"], 4)
                if rows[0]["committed_updates_per_sec"] > 0 else None,
        },
        "critical_path": _critical_path_doc(),
        "slo": _slo_doc(slo_arms),
        "programs": _programs_doc(),
    })
    if obs.enabled():
        obs.export()
        doc["obs"] = obs.rollup()
    print(json.dumps(doc))


# connections-mode shape (ISSUE 11): every arm runs the SAME reactor
# config, buffer, pool and offered rate, so the table isolates the
# live-connection count and the overload scenario.  The mixed-chaos
# rates mirror the PR-8 acceptance shape; the storm arm adds the
# connection storm (every SYN at once) + reconnect churn on top of the
# same chaos — the acceptance arm of the >= 0.5x-of-clean gate.
CONN_WARMUP_COMMITS = 3
CONN_CHAOS = {"drop": 0.05, "dup": 0.01, "corrupt": 0.005}
CONN_CHURN_LIFETIME_S = 5.0


def _bench_connections(args) -> None:
    """Live-connection reactor bench (ISSUE 11, fedml_tpu/comm/
    reactor.py + connswarm.py): N live sockets against the selector
    reactor transport — a swarm keeps every connection open with paced
    FMLR-enveloped uplinks while the server reassembles, dedups, acks
    and commits.  Arms per count: clean, mixed-chaos (5% loss + 1% dup
    + 0.5% corrupt at the receive chokepoint) and storm (the same
    chaos + a connection storm + seeded reconnect churn).  Gates:
    storm sustains >= 0.5x clean committed-updates/sec, zero recv-
    thread deaths, zero leaked FDs, every shed/evicted uplink
    accounted."""
    from fedml_tpu import obs
    from fedml_tpu.async_.torture import run_connection_torture

    counts = sorted(int(c) for c in str(args.conn_counts).split(",")
                    if c.strip())
    if not counts or counts[0] < 1:
        raise SystemExit(
            f"--conn_counts must be a comma-separated list of positive "
            f"connection counts, got {args.conn_counts!r}")
    port = int(os.environ.get("BENCH_CONN_PORT", "53700"))
    arm_no = [0]

    def run(tag, n, **kw):
        arm_no[0] += 1
        rep = run_connection_torture(
            n_connections=n, buffer_k=args.conn_buffer_k,
            commits=args.conn_commits, warmup_commits=CONN_WARMUP_COMMITS,
            ingest_pool=args.conn_pool, offered_rate=args.conn_rate,
            base_port=port + arm_no[0], timeout_s=900,
            seed=args.conn_seed, chaos_seed=args.conn_seed, **kw)
        ev = rep["evicted"]
        print(f"{tag}: {rep['committed_updates_per_sec']:.1f} updates/s  "
              f"admission p95 {rep['admission_p95_s'] * 1e3:.1f} ms  "
              f"peak {rep['open_connections_peak']} conns  evicted "
              f"stall/rate/shed {ev['stall']:.0f}/{ev['rate']:.0f}/"
              f"{ev['shed']:.0f}  shed {rep['uplinks_shed']:.0f}  "
              f"fd leak {rep['fd_leaked']}  recv deaths "
              f"{rep['recv_thread_deaths']:.0f}", file=sys.stderr)
        return rep

    def arm_doc(rep):
        return {
            "committed_updates_per_sec": round(
                rep["committed_updates_per_sec"], 4),
            "admission_p50_s": round(rep["admission_p50_s"], 6),
            "admission_p95_s": round(rep["admission_p95_s"], 6),
            "loop_lag_p95_s": round(rep["loop_lag_p95_s"], 6),
            "open_connections_peak": rep["open_connections_peak"],
            "evicted": rep["evicted"],
            "uplinks_shed": rep["uplinks_shed"],
            "connections_drained": rep["connections_drained"],
            "recv_thread_deaths": rep["recv_thread_deaths"],
            "dups_suppressed": rep["dups_suppressed"],
            "quarantined": rep["quarantined"],
            "fd_leaked": rep["fd_leaked"],
            "chaos_injected": rep["chaos_injected"],
            "swarm": rep["swarm"],
        }

    rows = []
    slo_arms: dict = {}
    for n in counts:
        clean = run(f"n={n} clean", n)
        chaosr = run(f"n={n} chaos", n, chaos=dict(CONN_CHAOS))
        storm = run(f"n={n} storm", n, chaos=dict(CONN_CHAOS),
                    storm=True, churn_lifetime_s=CONN_CHURN_LIFETIME_S)
        slo_arms[f"n{n}_clean"] = clean.get("slo_arm")
        slo_arms[f"n{n}_chaos"] = chaosr.get("slo_arm")
        slo_arms[f"n{n}_storm"] = storm.get("slo_arm")
        clean_ups = clean["committed_updates_per_sec"]
        rows.append({
            "n_connections": n,
            "clean": arm_doc(clean),
            "chaos": arm_doc(chaosr),
            "storm": arm_doc(storm),
            "storm_goodput_ratio": round(
                storm["committed_updates_per_sec"] / clean_ups, 4)
                if clean_ups > 0 else None,
        })
    head = rows[-1]
    doc = _stamp({
        "metric": (f"reactor_{head['n_connections']}conns_storm_"
                   "committed_updates_per_sec"),
        "value": head["storm"]["committed_updates_per_sec"],
        "unit": "updates/sec",
        # the in-schema comparison is the same count's clean arm
        "vs_baseline": None,
        "mode": "connections",
        "overlap_fraction": None,
        "h2d_bytes_per_round": None,
        "rounds": [],
        "async": None,
        "ingest": None,
        "chaos": None,
        "attack": None,
        "serve": None,
        "connections": {
            "buffer_k": args.conn_buffer_k,
            "ingest_pool": args.conn_pool,
            "offered_rate": args.conn_rate,
            "commits": args.conn_commits,
            "seed": args.conn_seed,
            "chaos_rates": dict(CONN_CHAOS),
            "churn_lifetime_s": CONN_CHURN_LIFETIME_S,
            "rows": rows,
            "storm_goodput_ratio": head["storm_goodput_ratio"],
        },
        "cluster": None,
        "secure": None,
        "critical_path": _critical_path_doc(),
        "slo": _slo_doc(slo_arms),
        "programs": _programs_doc(),
    })
    if obs.enabled():
        obs.export()
        doc["obs"] = obs.rollup()
    print(json.dumps(doc))


# multihost-mode shape (ISSUE 13): weak scaling — per-process work is
# CONSTANT (one client block per process: mh_clients_per_block
# population, mh_k_per_block sampled per round), so the ideal curve is
# flat rounds/sec while total clients/round grows with the process
# count.  On the 2-core box 2+ processes oversubscribe the cores and
# the carry rides loopback TCP, so >= 0.5x at 2 processes is the
# documented GIL/gloo floor; the chip gate rides exp_POD (chip queue
# step 15) where each process owns real chips and the carry rides DCN.
MH_BITWISE_ROUNDS = 3


def _bench_multihost(args) -> None:
    """Weak-scaling sweep of the two-level multihost runtime: one
    spawned cluster per process count, each rank reporting rounds/sec
    and carry-allreduce bytes (fedml_tpu/parallel/mh_worker.py), plus
    the 1-vs-2-process same-block-partition bitwise commit pin."""
    import tempfile

    from fedml_tpu import obs
    from fedml_tpu.parallel.multihost import (MultihostLaunchError,
                                              spawn_cluster_report)

    procs_list = sorted({int(p) for p in str(args.mh_procs).split(",")
                         if p.strip()})
    if not procs_list or procs_list[0] < 1:
        raise SystemExit(f"--mh_procs must be positive process counts, "
                         f"got {args.mh_procs!r}")
    if args.mh_rounds <= args.mh_warmup:
        raise SystemExit(f"--mh_rounds ({args.mh_rounds}) must exceed "
                         f"--mh_warmup ({args.mh_warmup})")
    arms = {a.strip() for a in str(args.mh_arms).split(",") if a.strip()}
    bad_arms = arms - {"weak", "bitwise", "chaos", "compress", "sparse"}
    if bad_arms or not arms:
        raise SystemExit(f"--mh_arms must be a non-empty subset of "
                         f"weak,bitwise,chaos,compress,sparse; got "
                         f"{args.mh_arms!r}")
    if args.mh_chaos_procs < 2:
        raise SystemExit(f"--mh_chaos_procs must be >= 2 (someone has "
                         f"to die AND someone has to survive), got "
                         f"{args.mh_chaos_procs}")

    def run_arm(procs: int, n_blocks: int, rounds: int, modes: list,
                extra_cfg: Optional[dict] = None, elastic: bool = False,
                expect_ranks: Optional[set] = None) -> tuple:
        """Spawn one cluster; returns ({rank: worker JSON doc},
        per-rank outcome report from spawn_cluster_report)."""
        cfg = {
            "clients": args.mh_clients_per_block * n_blocks,
            "spc": 24, "dim": args.mh_dim, "classes": 10,
            "k_per_round": args.mh_k_per_block * n_blocks,
            "n_blocks": n_blocks, "rounds": rounds,
            "warmup": args.mh_warmup, "seed": args.mh_seed,
            "modes": modes, "local_devices": args.mh_local_devices,
            **(extra_cfg or {}),
        }
        with tempfile.NamedTemporaryFile("w", suffix=".json",
                                         delete=False) as f:
            json.dump(cfg, f)
            path = f.name
        try:
            outs, report = spawn_cluster_report(
                [sys.executable, "-m", "fedml_tpu.parallel.mh_worker",
                 path], procs, timeout_s=900.0, elastic=elastic)
        finally:
            os.unlink(path)
        docs = {}
        for out in outs:
            for line in out.splitlines():
                if line.startswith("{"):
                    d = json.loads(line)
                    docs[d["rank"]] = d
        expect = (set(range(procs)) if expect_ranks is None
                  else expect_ranks)
        if not expect <= set(docs):
            raise MultihostLaunchError(
                f"rank(s) {sorted(expect - set(docs))} never reported "
                f"({len(docs)}/{procs} docs); per-rank: "
                f"{report['ranks']}")
        return docs, report

    slo_eng = _slo_window()
    rows = []
    deaths_total = 0
    for n in (procs_list if "weak" in arms else []):
        try:
            docs, _rep = run_arm(n, n, args.mh_rounds, ["streaming"])
        except MultihostLaunchError as e:
            print(f"multihost arm procs={n} FAILED: {e}",
                  file=sys.stderr)
            deaths_total += 1
            rows.append({"procs": n, "n_blocks": n, "error": str(e),
                         "process_deaths": 1})
            continue
        d0 = docs[0]
        agree = all(docs[r]["digests"] == d0["digests"]
                    for r in docs)
        row = {
            "procs": n,
            "n_blocks": n,
            "clients_per_round": args.mh_k_per_block * n,
            "population": args.mh_clients_per_block * n,
            "rounds_per_sec": round(d0["rounds_per_sec"], 4),
            "round_wall_p50_s": round(
                d0["per_mode"]["streaming"]["round_wall_p50_s"], 5),
            "carry_allreduce_bytes_per_round": round(
                max(docs[r]["carry_allreduce_bytes_per_round"]
                    for r in docs), 1),
            "ranks_agree": bool(agree),
            "process_deaths": 0,
        }
        print(f"multihost procs={n}: "
              f"{row['rounds_per_sec']:.3f} rounds/s  carry "
              f"{row['carry_allreduce_bytes_per_round']:.0f} B/round  "
              f"agree={agree}", file=sys.stderr)
        rows.append(row)

    ok_rows = {r["procs"]: r for r in rows if "error" not in r}
    base = ok_rows.get(procs_list[0])

    def _eff(n: int):
        r = ok_rows.get(n)
        if (base is None or r is None
                or base["rounds_per_sec"] <= 0):
            return None
        return round(r["rounds_per_sec"] / base["rounds_per_sec"], 4)

    # the bitwise pin arm: SAME block partition (n_blocks=2) at 1 and
    # 2 processes, both residency modes — the commit digests must be
    # byte-identical (the anchor that lets the weak-scaling numbers be
    # trusted as the same computation)
    bitwise_ok = None
    if "bitwise" in arms:
        try:
            one, _ = run_arm(1, 2, MH_BITWISE_ROUNDS,
                             ["streaming", "resident"])
            two, _ = run_arm(2, 2, MH_BITWISE_ROUNDS,
                             ["streaming", "resident"])
            bitwise_ok = bool(
                one[0]["digests"] == two[0]["digests"]
                == two[1]["digests"])
            print(f"multihost bitwise 1p-vs-2p pin: "
                  f"{'OK' if bitwise_ok else 'MISMATCH'} "
                  f"({one[0]['digests']})", file=sys.stderr)
        except MultihostLaunchError as e:
            print(f"multihost bitwise arm FAILED: {e}", file=sys.stderr)
            deaths_total += 1
            bitwise_ok = False

    # v13 elastic chaos arm (ISSUE 14): a clean ELASTIC N-process run
    # vs the same run with rank 1 seeded-killed mid-run.  The killed
    # run must (a) COMPLETE on the survivors (elastic launch policy +
    # view change + block re-adoption), (b) commit byte-identical
    # models to the clean elastic run (the [seed, round, block] purity
    # argument, measured not assumed), (c) keep survivor goodput
    # >= 0.5x clean, with zero survivor deaths.  Fail-fast stays the
    # default everywhere else in this mode — the weak/bitwise arms
    # above run the non-elastic runtime unchanged.
    chaos = None
    straggler = None
    if "chaos" in arms:
        cp = args.mh_chaos_procs
        # the killed arm pays ONE detection stall (~hb_timeout) at the
        # view change — a real deployment amortizes it over hours, so
        # the arm runs 2x the weak-scaling rounds (>= 20) to price the
        # steady survivor state, not the transient; the transient
        # itself is reported separately as view_change_latency_s
        chaos_rounds = max(20, 2 * args.mh_rounds)
        base_cfg = {"elastic": True, "hb_timeout_s": 1.0,
                    "channel_timeout_s": 120.0}
        try:
            clean_docs, _ = run_arm(
                cp, cp, chaos_rounds, ["streaming", "resident"],
                extra_cfg=base_cfg, elastic=True)
            survivors = set(range(cp)) - {1}
            killed_docs, killed_rep = run_arm(
                cp, cp, chaos_rounds, ["streaming", "resident"],
                extra_cfg={**base_cfg, "die_rank": 1,
                           "die_at_round": 1},
                elastic=True, expect_ranks=survivors)
            d0 = killed_docs[0]
            srep = d0["per_mode"]["streaming"]
            clean_rps = clean_docs[0]["rounds_per_sec"]
            killed_rps = d0["rounds_per_sec"]
            survivor_deaths = sum(
                1 for r, info in killed_rep["ranks"].items()
                if int(r) != 1 and info["rc"] != 0)
            bitwise_after_death = all(
                killed_docs[r]["digests"]
                == clean_docs[0]["digests"]
                for r in survivors)
            chaos = {
                "procs": cp,
                "rounds": chaos_rounds,
                "clean_rounds_per_sec": round(clean_rps, 4),
                "killed_rounds_per_sec": round(killed_rps, 4),
                "survivor_goodput_ratio": (
                    round(killed_rps / clean_rps, 4)
                    if clean_rps > 0 else None),
                "view_changes": srep.get("view_changes", 0),
                "view_change_latency_s": round(
                    srep.get("view_change_latency_s", 0.0), 5),
                "epoch_final": srep.get("epoch", 0),
                "survivor_deaths": survivor_deaths,
                "killed_rank_outcome":
                    killed_rep["ranks"][1]["outcome"],
                "bitwise_after_death_ok": bool(bitwise_after_death),
                # asserted only when a non-elastic arm actually ran
                # this invocation (the weak/bitwise arms use the
                # fail-fast launch policy); --mh_arms chaos alone
                # exercises nothing about the default -> null
                "elastic_fail_fast_default_ok": (
                    True if arms & {"weak", "bitwise"} else None),
            }
            print(f"multihost elastic chaos: clean "
                  f"{clean_rps:.3f} -> killed {killed_rps:.3f} "
                  f"rounds/s (ratio "
                  f"{chaos['survivor_goodput_ratio']}), "
                  f"{chaos['view_changes']} view change(s) @ "
                  f"{chaos['view_change_latency_s']*1e3:.1f} ms, "
                  f"bitwise_after_death_ok="
                  f"{chaos['bitwise_after_death_ok']}",
                  file=sys.stderr)
            # v15 straggler block (ISSUE 17): rank 0's always-on
            # barrier ledger + cluster SLO verdicts, from the SAME
            # chaos clusters — no extra spawns.  The killed arm must
            # breach cluster_no_rank_deaths AND name rank 1 dead in
            # the attribution; the clean arm's cluster pack must be
            # green (loopback barrier waits are µs-ms, far under the
            # 2.5 s p95 ceiling).
            c_sl = clean_docs[0].get("straggler") or {}
            k_sl = killed_docs[0].get("straggler") or {}
            c_slo = clean_docs[0].get("cluster_slo") or {}
            k_slo = killed_docs[0].get("cluster_slo") or {}
            k_attr = k_slo.get("attribution") or {}
            straggler = {
                "clean_barriers": c_sl.get("barriers", 0),
                "killed_barriers": k_sl.get("barriers", 0),
                "clean_gating_counts": c_sl.get(
                    "gating_counts", {}),
                "killed_gating_counts": k_sl.get(
                    "gating_counts", {}),
                "top_gating_rank": k_sl.get("top_gating_rank"),
                "worst_gate_margin_s": k_sl.get(
                    "worst_gate_margin_s"),
                "per_rank_wait_s": k_sl.get("per_rank_wait_s", {}),
                # tail of the ledger — each entry names its
                # round_gating_rank and per-rank waits_s
                "recent": (k_sl.get("recent") or [])[-4:],
                "cluster_clean_breaches": len(
                    c_slo.get("breached") or []),
                "cluster_killed_breached": sorted(
                    k_slo.get("breached") or []),
                "straggler_attribution_ok": bool(
                    k_sl.get("barriers", 0) > 0
                    and "1" in (k_attr.get("dead_ranks") or [])
                    and "cluster_no_rank_deaths"
                    in (k_slo.get("breached") or [])),
            }
            print(f"multihost straggler ledger: clean "
                  f"{straggler['clean_barriers']} / killed "
                  f"{straggler['killed_barriers']} barriers, "
                  f"top_gating_rank="
                  f"{straggler['top_gating_rank']}, "
                  f"clean breaches "
                  f"{straggler['cluster_clean_breaches']}, "
                  f"attribution_ok="
                  f"{straggler['straggler_attribution_ok']}",
                  file=sys.stderr)
        except MultihostLaunchError as e:
            print(f"multihost elastic chaos arm FAILED: {e}",
                  file=sys.stderr)
            deaths_total += 1
            chaos = {"error": str(e), "survivor_deaths": None,
                     "bitwise_after_death_ok": False}

    # v14 compress arm (ISSUE 16): price the compressed + overlapped
    # carry tier against the f32 serial baseline at the SAME block
    # partition (2 processes, 2 blocks).  Four spawned clusters:
    #   f32 serial   — the PR-13 wire bytes and digest baseline
    #   f32 +overlap — the escape hatch MUST stay byte-identical to
    #                  serial (overlap reorders nothing: frames
    #                  concatenate in global block order)
    #   int8 / int8_ef +overlap — the compressed rows; wire bytes are
    #                  the CHANNEL's per-round delta (measured on the
    #                  wire), accuracy rides eval at rank 0
    compress = None
    if "compress" in arms:
        def _wire_b(docs):
            return max(docs[r]["carry_wire_sent_bytes_per_round"]
                       for r in docs)

        try:
            ev = {"eval": True}
            f32_docs, _ = run_arm(2, 2, args.mh_rounds, ["streaming"],
                                  extra_cfg=ev)
            f32_ov_docs, _ = run_arm(
                2, 2, args.mh_rounds, ["streaming"],
                extra_cfg={**ev, "carry_codec": "f32",
                           "overlap_exchange": True})
            escape_ok = all(
                f32_ov_docs[r]["digests"] == f32_docs[0]["digests"]
                for r in f32_ov_docs)
            f32_rps = f32_docs[0]["rounds_per_sec"]
            f32_wire = _wire_b(f32_docs)
            f32_acc = f32_docs[0].get("eval", {}).get("streaming")
            codec_rows = []
            for codec in ("int8", "int8_ef"):
                docs, _ = run_arm(
                    2, 2, args.mh_rounds, ["streaming"],
                    extra_cfg={**ev, "carry_codec": codec,
                               "overlap_exchange": True})
                d0 = docs[0]
                wire = _wire_b(docs)
                rps = d0["rounds_per_sec"]
                acc = d0.get("eval", {}).get("streaming")
                reduction = (round(f32_wire / wire, 4)
                             if wire > 0 else None)
                crow = {
                    "codec": codec,
                    "rounds_per_sec": round(rps, 4),
                    "carry_wire_bytes_per_round": round(wire, 1),
                    "carry_payload_bytes_per_round": round(
                        d0["carry_payload_bytes_per_round"], 1),
                    "carry_raw_bytes_per_round": round(
                        d0["carry_raw_bytes_per_round"], 1),
                    "carry_compression_ratio": round(
                        d0["carry_compression_ratio"], 4),
                    "wire_reduction_vs_f32": reduction,
                    "overlap_fraction": round(
                        d0["overlap_fraction"], 4),
                    "ranks_agree": all(
                        docs[r]["digests"] == d0["digests"]
                        for r in docs),
                    "eval_acc": (round(acc, 4)
                                 if acc is not None else None),
                    "acc_delta_vs_f32": (
                        round(abs(acc - f32_acc), 4)
                        if acc is not None and f32_acc is not None
                        else None),
                    "efficiency_at_constant_bytes": (
                        round((rps / f32_rps) * reduction, 4)
                        if f32_rps > 0 and reduction else None),
                }
                codec_rows.append(crow)
                print(f"multihost compress {codec}: "
                      f"{crow['carry_wire_bytes_per_round']:.0f} "
                      f"B/round on the wire "
                      f"({crow['wire_reduction_vs_f32']}x vs f32), "
                      f"overlap {crow['overlap_fraction']}, "
                      f"acc_delta {crow['acc_delta_vs_f32']}",
                      file=sys.stderr)
            compress = {
                "procs": 2,
                "rounds": args.mh_rounds,
                "f32_rounds_per_sec": round(f32_rps, 4),
                "f32_wire_bytes_per_round": round(f32_wire, 1),
                "f32_eval_acc": (round(f32_acc, 4)
                                 if f32_acc is not None else None),
                "f32_overlap_fraction": round(
                    f32_ov_docs[0]["overlap_fraction"], 4),
                "bitwise_f32_escape_ok": bool(escape_ok),
                "codecs": codec_rows,
            }
            print(f"multihost f32 escape hatch under overlap: "
                  f"{'OK' if escape_ok else 'MISMATCH'} (overlap "
                  f"fraction "
                  f"{compress['f32_overlap_fraction']})",
                  file=sys.stderr)
        except MultihostLaunchError as e:
            print(f"multihost compress arm FAILED: {e}",
                  file=sys.stderr)
            deaths_total += 1
            compress = {"error": str(e),
                        "bitwise_f32_escape_ok": False}

    # v17 sparse arm (ISSUE 19): same paired 2-process protocol as the
    # compress arm, but the codec rows are the SPARSE flavors (topk,
    # topk_ef; fixed k = dim/16 per block).  The wire bytes are the
    # channel's measured per-round delta, so wire_reduction_vs_f32 is
    # the honest bytes-on-the-wire ratio the ISSUE-19 >= 6x gate rides
    # on (bench_diff v17).  f32 stays the escape hatch: its bitwise
    # pin is re-asserted here under overlap so a sparse-era regression
    # in the fold path can't hide behind the compress arm being off.
    sparse = None
    if "sparse" in arms:
        def _wire_sb(docs):
            return max(docs[r]["carry_wire_sent_bytes_per_round"]
                       for r in docs)

        try:
            # topk_ef's reconstruction mirror needs ~topk_ratio rounds
            # of warm-up before every coordinate has shipped once —
            # judging convergence at the 10-round default would
            # measure the transient, not the codec, so the arm floors
            # its round count well past the warm-up (the chaos arm's
            # round-floor precedent)
            sp_rounds = max(8 * 16, args.mh_rounds)
            ev = {"eval": True}
            f32_docs, _ = run_arm(2, 2, sp_rounds, ["streaming"],
                                  extra_cfg=ev)
            f32_ov_docs, _ = run_arm(
                2, 2, sp_rounds, ["streaming"],
                extra_cfg={**ev, "carry_codec": "f32",
                           "overlap_exchange": True})
            escape_ok = all(
                f32_ov_docs[r]["digests"] == f32_docs[0]["digests"]
                for r in f32_ov_docs)
            f32_rps = f32_docs[0]["rounds_per_sec"]
            f32_wire = _wire_sb(f32_docs)
            f32_acc = f32_docs[0].get("eval", {}).get("streaming")
            codec_rows = []
            for codec in ("topk", "topk_ef"):
                docs, _ = run_arm(
                    2, 2, sp_rounds, ["streaming"],
                    extra_cfg={**ev, "carry_codec": codec,
                               "overlap_exchange": True})
                d0 = docs[0]
                wire = _wire_sb(docs)
                rps = d0["rounds_per_sec"]
                acc = d0.get("eval", {}).get("streaming")
                reduction = (round(f32_wire / wire, 4)
                             if wire > 0 else None)
                crow = {
                    "codec": codec,
                    "rounds_per_sec": round(rps, 4),
                    "carry_wire_bytes_per_round": round(wire, 1),
                    "carry_payload_bytes_per_round": round(
                        d0["carry_payload_bytes_per_round"], 1),
                    "carry_raw_bytes_per_round": round(
                        d0["carry_raw_bytes_per_round"], 1),
                    "carry_compression_ratio": round(
                        d0["carry_compression_ratio"], 4),
                    "wire_reduction_vs_f32": reduction,
                    "overlap_fraction": round(
                        d0["overlap_fraction"], 4),
                    "ranks_agree": all(
                        docs[r]["digests"] == d0["digests"]
                        for r in docs),
                    "eval_acc": (round(acc, 4)
                                 if acc is not None else None),
                    "acc_delta_vs_f32": (
                        round(abs(acc - f32_acc), 4)
                        if acc is not None and f32_acc is not None
                        else None),
                    "efficiency_at_constant_bytes": (
                        round((rps / f32_rps) * reduction, 4)
                        if f32_rps > 0 and reduction else None),
                }
                codec_rows.append(crow)
                print(f"multihost sparse {codec}: "
                      f"{crow['carry_wire_bytes_per_round']:.0f} "
                      f"B/round on the wire "
                      f"({crow['wire_reduction_vs_f32']}x vs f32), "
                      f"overlap {crow['overlap_fraction']}, "
                      f"acc_delta {crow['acc_delta_vs_f32']}",
                      file=sys.stderr)
            sparse = {
                "procs": 2,
                "rounds": sp_rounds,
                "topk_ratio": 16,
                "f32_rounds_per_sec": round(f32_rps, 4),
                "f32_wire_bytes_per_round": round(f32_wire, 1),
                "f32_eval_acc": (round(f32_acc, 4)
                                 if f32_acc is not None else None),
                "f32_overlap_fraction": round(
                    f32_ov_docs[0]["overlap_fraction"], 4),
                "bitwise_f32_escape_ok": bool(escape_ok),
                "codecs": codec_rows,
            }
            print(f"multihost f32 escape hatch under overlap "
                  f"(sparse arm): "
                  f"{'OK' if escape_ok else 'MISMATCH'} (overlap "
                  f"fraction "
                  f"{sparse['f32_overlap_fraction']})",
                  file=sys.stderr)
        except MultihostLaunchError as e:
            print(f"multihost sparse arm FAILED: {e}",
                  file=sys.stderr)
            deaths_total += 1
            sparse = {"error": str(e),
                      "bitwise_f32_escape_ok": False}

    head = (rows[-1] if rows and "error" not in rows[-1] else
            (base or (rows[-1] if rows else {})))
    doc = _stamp({
        "metric": "multihost_weak_scaling_rounds_per_sec",
        "value": round(head.get("rounds_per_sec", 0.0), 4),
        "unit": "rounds/sec",
        "vs_baseline": None,
        "mode": "multihost",
        "overlap_fraction": None,
        "h2d_bytes_per_round": None,
        "rounds": [],
        "async": None,
        "ingest": None,
        "chaos": None,
        "attack": None,
        "serve": None,
        "connections": None,
        "multihost": {
            "rows": rows,
            "weak_efficiency_2p": _eff(2),
            "weak_efficiency_4p": _eff(4),
            "bitwise_2proc_ok": bitwise_ok,
            "chaos": chaos,
            "straggler": straggler,
            "compress": compress,
            "sparse": sparse,
            "process_deaths": deaths_total,
            "k_per_block": args.mh_k_per_block,
            "clients_per_block": args.mh_clients_per_block,
            "dim": args.mh_dim,
            "local_devices": args.mh_local_devices,
            "rounds": args.mh_rounds,
            "warmup": args.mh_warmup,
            "seed": args.mh_seed,
        },
        "cluster": None,
        "secure": None,
        "critical_path": _critical_path_doc(),
        "slo": _slo_doc({"sweep": _slo_close(slo_eng)}),
        "programs": _programs_doc(),
    })
    if obs.enabled():
        obs.export()
        doc["obs"] = obs.rollup()
    print(json.dumps(doc))


CLUSTER_WARMUP_COMMITS = 2
CLUSTER_GOODPUT_FLOOR = 0.5


def _bench_cluster(args) -> None:
    """Fused serving cluster bench (ISSUE 18, fedml_tpu/scale/
    cluster.py): H spawned hosts each bind a reactor endpoint and
    serve live-socket uplinks into their registry-shard lanes, folding
    lane partials cross-host through the ElasticChannel at every
    commit barrier; ONE connswarm fleet (subprocess, own fd budget)
    stripes its connections across the H endpoints, pacing uplinks
    along the PR-10 diurnal profile.  Rows sweep host counts —
    cluster committed-updates/sec, p95 admission (max over ranks), and
    ranks_agree (the live-ingest cross-rank digest pin).  The
    chaos_everything arm composes EVERY fault layer at once:
    connection storm + reconnect churn + seeded wire faults + rank 1
    killed mid-run — survivors must keep >= 0.5x the clean row's
    goodput, agree bitwise after the death, lose no recv threads, and
    account every shed/evicted/dropped uplink."""
    import dataclasses
    import tempfile

    import numpy as np

    from fedml_tpu import obs
    from fedml_tpu.async_.torture import _swarm_subprocess
    from fedml_tpu.comm.connswarm import SwarmConfig
    from fedml_tpu.parallel.multihost import (MultihostLaunchError,
                                              free_port,
                                              spawn_cluster_report)
    from fedml_tpu.scale.arrivals import ArrivalConfig
    from fedml_tpu.scale.cluster import make_uplink_frame

    hosts_list = sorted(int(h) for h in str(args.cluster_hosts).split(",")
                        if h.strip())
    if not hosts_list or hosts_list[0] < 1:
        raise SystemExit(
            f"--cluster_hosts must be a comma-separated list of "
            f"positive host counts, got {args.cluster_hosts!r}")
    if args.cluster_commits <= CLUSTER_WARMUP_COMMITS:
        raise SystemExit(
            f"--cluster_commits ({args.cluster_commits}) must exceed "
            f"the warmup ({CLUSTER_WARMUP_COMMITS})")
    cluster_arms = {a.strip()
                    for a in str(args.cluster_arms).split(",")
                    if a.strip()}
    bad_cluster_arms = cluster_arms - {"clean", "sparse"}
    if bad_cluster_arms:
        raise SystemExit(
            f"--cluster_arms must be a subset of clean,sparse; got "
            f"{args.cluster_arms!r}")
    rng = np.random.default_rng(args.cluster_seed)
    uplink_row = rng.standard_normal(
        args.cluster_row_dim).astype(np.float32)
    frame = make_uplink_frame(uplink_row, sender=1, weight=1.0,
                              version=0)

    def run_arm(hosts, *, tag, storm=False, chaos=None, die_at=None,
                expect_ranks=None, commits=None, uplink_frame=None,
                sparse_uplink=False):
        ports = [free_port() for _ in range(hosts)]
        # weak scaling: --cluster_rate is PER HOST, so the fleet's
        # aggregate offer grows with the host count (each row asks
        # "did adding hosts add committed throughput").  The flash
        # profile bursts ABOVE that (the push-notification stampede),
        # it does not scale it down: offered_rate is the profile's
        # PEAK, so the storm arm's peak is boost x the sustained rate
        offered = args.cluster_rate * hosts * (3.0 if storm else 1.0)
        sc = {"population": args.cluster_population,
              "commits": int(commits or args.cluster_commits),
              "warmup_commits": CLUSTER_WARMUP_COMMITS,
              "buffer_k": args.cluster_buffer_k,
              "row_dim": args.cluster_row_dim,
              "connections": args.cluster_connections,
              "ingest_pool": args.cluster_ingest_pool,
              "window_deadline_s": 5.0, "timeout_s": 600.0,
              "ports": ports}
        if sparse_uplink:
            sc["sparse_uplink"] = True
        if chaos:
            sc["chaos"] = dict(chaos)
            sc["chaos_seed"] = args.cluster_seed
        if die_at is not None:
            sc["die_rank"] = 1
            sc["die_at_commit"] = die_at
        cfg = {"serve_cluster": sc, "channel_timeout_s": 300.0,
               "hb_timeout_s": 1.0, "hb_interval_s": 0.25}
        arrival = dataclasses.asdict(ArrivalConfig(
            mode="flash" if storm else "diurnal",
            rate=args.cluster_rate, period_s=30.0, amplitude=0.5,
            flash_at_s=2.0, flash_duration_s=5.0, flash_boost=3.0,
            seed=args.cluster_seed))
        swarm_cfg = SwarmConfig(
            n_connections=hosts * args.cluster_connections,
            offered_rate=offered, storm=storm,
            churn_lifetime_s=(CONN_CHURN_LIFETIME_S if storm else 0.0),
            duration_s=600.0, seed=args.cluster_seed,
            targets=[["127.0.0.1", p] for p in ports],
            arrival=arrival, burst_cap_s=0.05)
        # swarm first: the fleet retries refused connects until the
        # workers' reactors bind, so startup order is not a race
        sw_finish = _swarm_subprocess(
            swarm_cfg, frame if uplink_frame is None else uplink_frame)
        path = None
        try:
            with tempfile.NamedTemporaryFile(
                    "w", suffix=".json", delete=False) as f:
                json.dump(cfg, f)
                path = f.name
            outs, rep = spawn_cluster_report(
                [sys.executable, "-m", "fedml_tpu.parallel.mh_worker",
                 path], hosts, timeout_s=900.0, elastic=(hosts > 1))
        finally:
            sw = sw_finish()
            if path:
                try:
                    os.unlink(path)
                except OSError:
                    pass
        docs = {}
        for r, out in enumerate(outs):
            for line in out.splitlines():
                if line.startswith("{"):
                    docs[r] = json.loads(line)["serve_cluster"]
        expect = (set(expect_ranks) if expect_ranks is not None
                  else set(range(hosts)))
        if not expect <= set(docs):
            raise MultihostLaunchError(
                f"cluster arm {tag!r}: missing rank report(s) "
                f"{sorted(expect - set(docs))} "
                f"(ranks: {rep['ranks']})")
        r0 = docs[min(docs)]
        p95_ms = max(d["admission_p95_s"] for d in docs.values()) * 1e3
        print(f"{tag}: {r0['cluster_updates_per_sec']:.1f} cluster "
              f"updates/s  p95 admission {p95_ms:.1f} ms  swarm sent "
              f"{sw.get('frames_sent', 0)} frames "
              f"({sw.get('connects', 0)} connects)", file=sys.stderr)
        return docs, rep, sw

    def steady_rate(doc, skip):
        """Sustained committed-updates/sec over the tail of the
        per-commit walls/wsums ledger — at least the last half of the
        commits, and never earlier than `skip`.  The early commits are
        regime transients, excluded by construction: the startup
        backlog drain (frames that landed while jit warmed up replay
        at decode speed, not at the offered pace) and, in the chaos
        arm, the kill + heartbeat-eviction window — a one-time stall
        that must not masquerade as steady-state goodput loss."""
        n = len(doc["commit_walls_s"])
        skip = max(int(skip), n // 2)
        walls = doc["commit_walls_s"][skip:]
        wsums = doc["commit_wsums"][skip:]
        tw = sum(walls)
        return (sum(wsums) / tw) if tw > 0 else 0.0

    def arm_doc(docs, sw, steady_skip=CLUSTER_WARMUP_COMMITS):
        digests = [d["committed_digest"] for d in docs.values()]
        return {
            "cluster_updates_per_sec": round(
                docs[min(docs)]["cluster_updates_per_sec"], 4),
            "steady_updates_per_sec": round(
                steady_rate(docs[min(docs)], steady_skip), 4),
            "admission_p50_s": round(max(
                d["admission_p50_s"] for d in docs.values()), 6),
            "admission_p95_s": round(max(
                d["admission_p95_s"] for d in docs.values()), 6),
            "ranks_agree": len(set(digests)) == 1,
            "committed_updates": int(sum(
                d["committed_updates"] for d in docs.values())),
            "commits": max(d["commits"] for d in docs.values()),
            "evicted": {k: sum(d["evicted"][k] for d in docs.values())
                        for k in next(iter(docs.values()))["evicted"]},
            "uplinks_shed": sum(d["uplinks_shed"]
                                for d in docs.values()),
            "shed_reasons": {
                k: sum(d["shed_reasons"][k] for d in docs.values())
                for k in next(iter(docs.values()))["shed_reasons"]},
            "lane_overflow_dropped": sum(
                d["lane_overflow_dropped"] for d in docs.values()),
            "deadline_windows": sum(d["deadline_windows"]
                                    for d in docs.values()),
            "recv_thread_deaths": sum(d["recv_thread_deaths"]
                                      for d in docs.values()),
            "quarantined": sum(d["quarantined"] for d in docs.values()),
            "open_connections_peak": sum(
                d["open_connections_peak"] for d in docs.values()),
            "registry_bytes": sum(d["registry_bytes"]
                                  for d in docs.values()),
            "swarm": {"frames_sent": sw.get("frames_sent"),
                      "connects": sw.get("connects"),
                      "refused": sw.get("refused"),
                      "per_target": sw.get("per_target")},
        }

    rows = []
    slo_arms: dict = {}
    clean_by_hosts: dict = {}
    for hosts in hosts_list:
        docs, _rep, sw = run_arm(hosts, tag=f"hosts={hosts} clean")
        clean_by_hosts[hosts] = docs
        slo_arms[f"h{hosts}_clean"] = docs[min(docs)].get("slo_arm")
        row = {"hosts": hosts,
               "connections": hosts * args.cluster_connections,
               **arm_doc(docs, sw)}
        rows.append(row)

    # the chaos-everything arm: storm + churn + wire faults + rank
    # kill, all in the same run, at the widest clean host count >= 2
    chaos_arm = None
    hmax = max(hosts_list)
    if hmax >= 2:
        # more commits than the clean rows: the one-time eviction
        # stall (heartbeat timeout + view change) must amortize over
        # the post-kill steady state, same shape as the multihost
        # chaos arm's round count
        chaos_commits = max(12, 2 * args.cluster_commits)
        die_at = CLUSTER_WARMUP_COMMITS + 1
        survivors = set(range(hmax)) - {1}
        docs, rep, sw = run_arm(
            hmax, tag=f"hosts={hmax} chaos-everything", storm=True,
            chaos=dict(CONN_CHAOS), die_at=die_at,
            expect_ranks=survivors, commits=chaos_commits)
        sdocs = {r: docs[r] for r in survivors if r in docs}
        digests = [d["committed_digest"] for d in sdocs.values()]
        # goodput on the STEADY rates: clean tail vs the survivors'
        # post-eviction tail (commit die_at absorbs the heartbeat
        # timeout + view change; the floor judges the regime after it)
        clean_ups = steady_rate(
            clean_by_hosts[hmax][min(clean_by_hosts[hmax])],
            CLUSTER_WARMUP_COMMITS)
        killed_ups = steady_rate(sdocs[min(sdocs)], die_at + 1)
        slo_arms[f"h{hmax}_chaos_everything"] = \
            sdocs[min(sdocs)].get("slo_arm")
        chaos_arm = {
            "hosts": hmax,
            "killed_rank": 1,
            "die_at_commit": die_at,
            "survivor_goodput_ratio": round(
                killed_ups / clean_ups, 4) if clean_ups > 0 else None,
            "bitwise_after_death_ok": len(set(digests)) == 1,
            "survivor_deaths": sum(
                1 for r, st in rep["ranks"].items()
                if int(r) != 1 and st["rc"] != 0),
            **arm_doc(sdocs, sw, steady_skip=die_at + 1),
        }
        print(f"chaos-everything: survivor goodput "
              f"{chaos_arm['survivor_goodput_ratio']}x  bitwise "
              f"{chaos_arm['bitwise_after_death_ok']}  sheds "
              f"{chaos_arm['uplinks_shed']:.0f}", file=sys.stderr)

    # v17 sparse uplink arm (ISSUE 19): the paired dense-vs-sparse
    # run at the widest clean host count.  Same offered rate, same
    # population, same connections — the ONLY change is the wire: the
    # fleet ships sparse_topk v2 frames (k = dim/16 pairs) and the
    # servers opt their lanes into the scatter-fold ingest path
    # (sparse_uplink=True).  throughput_ratio_vs_dense rides the
    # ISSUE-19 >= 0.9x gate in bench_diff; uplink_reduction_vs_dense
    # is honest len(frame) bytes including the envelope.  The
    # digests_equal pin replays a <=k-sparse row through the sparse
    # codec in-process — sparse_topk ships exact f32 (index, value)
    # pairs, so a row with <= k nonzeros must decode bitwise-equal
    # (truncation only bites when MORE than k coordinates are live;
    # that lossy case is priced by the multihost sparse arm's
    # acc_delta, not pinned here).
    sparse_arm = None
    if "sparse" in cluster_arms:
        from fedml_tpu.comm.message import MessageCodec
        k = max(1, args.cluster_row_dim // 16)
        sp_row = np.zeros(args.cluster_row_dim, np.float32)
        sp_idx = rng.choice(args.cluster_row_dim, size=k,
                            replace=False)
        sp_row[sp_idx] = rng.standard_normal(k).astype(np.float32)
        replay = MessageCodec.decode(make_uplink_frame(
            sp_row, sender=1, weight=1.0, version=0,
            transport="sparse_topk"))
        replay_row = np.asarray(replay.get("model_params")["w"])
        digests_equal = bool(
            replay_row.dtype == np.float32
            and np.array_equal(
                replay_row.view(np.uint32),
                sp_row.view(np.uint32)))
        sparse_frame = make_uplink_frame(
            uplink_row, sender=1, weight=1.0, version=0,
            transport="sparse_topk")
        docs, _rep, sw = run_arm(
            hmax, tag=f"hosts={hmax} sparse",
            uplink_frame=sparse_frame, sparse_uplink=True)
        dense_docs = clean_by_hosts[hmax]
        dense_ups = steady_rate(dense_docs[min(dense_docs)],
                                CLUSTER_WARMUP_COMMITS)
        sparse_ups = steady_rate(docs[min(docs)],
                                 CLUSTER_WARMUP_COMMITS)
        slo_arms[f"h{hmax}_sparse"] = docs[min(docs)].get("slo_arm")
        sparse_arm = {
            "hosts": hmax,
            "topk_ratio": 16,
            "k": k,
            "uplink_bytes_per_update": len(sparse_frame),
            "dense_uplink_bytes_per_update": len(frame),
            "uplink_reduction_vs_dense": round(
                len(frame) / len(sparse_frame), 4),
            "throughput_ratio_vs_dense": (
                round(sparse_ups / dense_ups, 4)
                if dense_ups > 0 else None),
            "digests_equal": digests_equal,
            **arm_doc(docs, sw),
        }
        print(f"sparse uplink: {sparse_arm['uplink_bytes_per_update']}"
              f" B/update "
              f"({sparse_arm['uplink_reduction_vs_dense']}x vs dense "
              f"{len(frame)} B), throughput ratio "
              f"{sparse_arm['throughput_ratio_vs_dense']}x, "
              f"k-sparse replay "
              f"{'EXACT' if digests_equal else 'MISMATCH'}",
              file=sys.stderr)

    head = rows[-1]
    doc = _stamp({
        "metric": (f"cluster_{head['hosts']}hosts_"
                   "committed_updates_per_sec"),
        "value": head["cluster_updates_per_sec"],
        "unit": "updates/sec",
        "vs_baseline": None,
        "mode": "cluster",
        "overlap_fraction": None,
        "h2d_bytes_per_round": None,
        "rounds": [],
        "async": None,
        "ingest": None,
        "chaos": None,
        "attack": None,
        "serve": None,
        "connections": None,
        "multihost": None,
        "secure": None,
        "cluster": {
            "rows": rows,
            "chaos_everything": chaos_arm,
            "sparse": sparse_arm,
            "goodput_floor": CLUSTER_GOODPUT_FLOOR,
            "commits": args.cluster_commits,
            "buffer_k": args.cluster_buffer_k,
            "row_dim": args.cluster_row_dim,
            "population": args.cluster_population,
            "connections_per_host": args.cluster_connections,
            "offered_rate": args.cluster_rate,
            "ingest_pool": args.cluster_ingest_pool,
            "chaos_rates": dict(CONN_CHAOS),
            "seed": args.cluster_seed,
        },
        "critical_path": _critical_path_doc(),
        "slo": _slo_doc(slo_arms),
        "programs": _programs_doc(),
    })
    if obs.enabled():
        obs.export()
        doc["obs"] = obs.rollup()
    print(json.dumps(doc))


if __name__ == "__main__":
    main()
