#!/bin/bash
# Chip queue: the backlog of on-chip measurements in priority order,
# one process per step (a chip belongs to one process at a time).  Each
# step is independently time-boxed so a failure mid-queue still banks
# the earlier artifacts (bench JSON, convergence artifact, SCALING
# rows).  Run it on the chip host through the builder's chip tool:
#
#   chiprun --timeout 3600 -- bash scripts/run_chip_queue.sh [outdir]
#
# Artifacts land under chiprun_out/ (what the tool copies back).  Steps
# 15-20 drive the MULTI-PROCESS tier, which is host-level and CPU-only
# today (parallel/mh_worker.py runs every rank on JAX_PLATFORMS=cpu):
# they measure sockets, codecs and process scheduling on that host, not
# the chip.
#
# Priority (VERDICT r4 next-round #1/#4 + SCALING backlog):
#   1. bench.py              — re-land the driver-verified rounds/sec
#   2. nwp_convergence       — LSTM vs TransformerLM chip training
#   3. profile_bench C4096B  — 4096-client block-streamed round
#   4. profile_bench OS256/OSB256 — order-stat resident vs streamed
#   5. profile_bench DN128   — donate on/off + restructured-carry A/B
#      (ISSUE 4: prices the scan-carry/donation copy category the
#      round-2b trace measured at ~0.13 s/round)
#   6. profile_bench PF512/SD512 — prefetch + stack-dtype A/Bs (PR 1/3
#      backlog, never run on a chip)
#   7. profile_bench ASYNC   — async federation A/B (ISSUE 5): buffered
#      staleness-aware commits at K=8 vs K=32, committed-updates/sec +
#      staleness percentiles on chip
#   8. profile_bench INGEST  — concurrent-uplink ingestion A/B (ISSUE 6):
#      legacy inline-decode+drain vs decode-into+streaming at pool
#      1/4/8, 32 TCP clients — prices the server's host-side ingestion
#      with the chip-attached jax runtime dispatching the fold/commit
#   9. profile_bench TRACE   — federation-tracing overhead A/B (ISSUE 7):
#      traced (span tracer + trace-stamped frames + clock sync) vs
#      untraced ingest torture, overhead gate < 5%, plus the traced
#      arm's round critical-path attribution table
#  10. profile_bench CHAOS   — chaos goodput A/B (ISSUE 8): reliable
#      ingest torture under seeded wire faults (clean / 5% / 20% loss /
#      mixed 5%+1%+0.5%), gate >= 0.5x clean goodput on the mixed arm
#      with zero recv-thread deaths
#  11. profile_bench ATTACK   — adversarial robustness (ISSUE 9): the
#      attack x defense accuracy matrix (defended-in-band gate, zero
#      honest quarantines) + the admission-screen ingest overhead pair
#      (>= 0.9x throughput gate) on the chip-attached runtime
#  12. profile_bench SERVE    — million-client serving spine (ISSUE 10):
#      committed-updates/sec + registry bytes/client at 10k/100k/1M
#      simulated clients, stratified vs reservoir cohort sampling, with
#      the chip-attached runtime dispatching the streaming fold/commit
#      (gates: <= ~100 B/client registry, 1M arm sustains >= 0.5x 10k)
#  13. profile_bench CONN     — live-connection reactor A/B (ISSUE 11):
#      256/1k live sockets on the selector reactor transport, clean vs
#      storm (mixed chaos + connection storm + reconnect churn) — gates
#      >= 0.5x clean goodput under storm, zero recv-thread deaths,
#      zero leaked FDs
#  14. bench_diff              — cross-run regression differ (ISSUE 12):
#      the fresh bench.json vs the committed baseline snapshot
#      (benchmarks/bench_baseline_2core.json), per-mode verdicts with
#      the encoded noise bands — regressions are NAMED in the queue log
#      instead of waiting for a human PERF.md re-read
#  15. profile_bench POD      — multi-host weak-scaling sweep (ISSUE 13):
#      bench.py --mode multihost — per-process local-mesh training
#      (CPU workers) + HostChannel carry allreduce at 1/2/4 processes;
#      gates: bitwise 1-vs-2-process commit pin, zero process deaths,
#      measured weak-scaling efficiency on this host
#  16. profile_bench POD compress — compressed-carry arm under exp_POD
#      (ISSUE 16): bytes-on-wire per round measured ON the channel,
#      int8/int8_ef compression ratio + efficiency-at-constant-bytes,
#      overlap fraction, and the f32 escape hatch staying bitwise under
#      --overlap_exchange (loopback frames)
#  17. profile_bench ELASTIC  — elastic-chaos arm
#      (ISSUE 14): a 3-process ELASTIC cluster with a seeded kill of
#      rank 1 mid-run vs the clean elastic run — gates: survivors
#      finish (zero survivor deaths), survivor goodput >= 0.5x clean,
#      bitwise_after_death_ok (re-adopted blocks commit the same
#      bits), view-change latency on this host's loopback
#  18. profile_bench ELASTIC straggler — cluster observatory arm
#      (ISSUE 17): the SAME elastic chaos run with per-rank obs dirs —
#      barrier-wait ledger,
#      straggler_attribution_ok naming the killed rank, cluster SLO
#      pack green on the clean arm, merged per-rank timeline via
#      tools/trace_timeline.py with gating-rank annotations
#  19. profile_bench CLUSTER  — fused serving cluster (ISSUE 18):
#      bench.py --mode cluster — live connswarm fleets over real
#      sockets feeding registry-sharded lanes on 1/2/4 hosts, lane
#      partials folding through ElasticChannel at each commit barrier,
#      plus the chaos-everything arm (storm + wire faults + rank kill)
#      — gates: survivor goodput >= 0.5x clean, zero recv-thread
#      deaths, bitwise_after_death_ok + ranks_agree pins
set -u
cd "$(dirname "$0")/.."
OUT="${1:-chiprun_out/chip_queue}"
mkdir -p "$OUT"
export PYTHONPATH="$PWD"

echo "== device check"
if ! timeout 180 python -c "import jax; assert jax.devices()[0].platform == 'tpu'"; then
  echo "no TPU attached; aborting queue"; exit 1
fi

echo "== 1/21 bench.py"
timeout 1500 python bench.py 2>"$OUT/bench.err" | tee "$OUT/bench.json"

echo "== 2/21 nwp_convergence (600 rounds, vocab 10004 — must match the"
echo "   600-round band pinned in test_quality_regression.py)"
# written under $OUT (the only directory the chip tool copies back);
# commit it as benchmarks/nwp_convergence_r5.json to update the band
timeout 3600 python tools/nwp_convergence.py 600 \
    --out "$OUT/nwp_convergence_r5.json" 2>"$OUT/nwp.err" \
    | tee "$OUT/nwp.log"

echo "== 3/21 profile_bench C4096B (block-streamed 4096 clients)"
timeout 5400 python tools/profile_bench.py C4096B 2>&1 | tee "$OUT/c4096b.log"

echo "== 4/21 profile_bench OS256 OSB256 (order-stat timing)"
timeout 3600 python tools/profile_bench.py OS256 OSB256 2>&1 | tee "$OUT/os.log"

echo "== 5/21 profile_bench DN128 (donate on/off + restructured carry A/B)"
timeout 1800 python tools/profile_bench.py DN128 2>&1 | tee "$OUT/dn128.log"

echo "== 6/21 profile_bench PF512 SD512 (prefetch + stack-dtype A/Bs)"
timeout 3600 python tools/profile_bench.py PF512 SD512 2>&1 | tee "$OUT/pfsd.log"

echo "== 7/21 profile_bench ASYNC (async federation K=8 vs K=32 A/B)"
timeout 3600 python tools/profile_bench.py ASYNC 2>&1 | tee "$OUT/async.log"

echo "== 8/21 profile_bench INGEST (uplink ingestion legacy-vs-streaming A/B)"
timeout 1800 python tools/profile_bench.py INGEST 2>&1 | tee "$OUT/ingest.log"

echo "== 9/21 profile_bench TRACE (traced-vs-untraced ingest overhead gate)"
timeout 1200 python tools/profile_bench.py TRACE 2>&1 | tee "$OUT/trace.log"

echo "== 10/21 profile_bench CHAOS (chaos goodput under seeded wire faults)"
timeout 1800 python tools/profile_bench.py CHAOS 2>&1 | tee "$OUT/chaos.log"

echo "== 11/21 profile_bench ATTACK (adversarial attack x defense matrix)"
timeout 3600 python tools/profile_bench.py ATTACK 2>&1 | tee "$OUT/attack.log"

echo "== 12/21 profile_bench SERVE (million-client serving spine)"
timeout 1800 python tools/profile_bench.py SERVE 2>&1 | tee "$OUT/serve.log"

echo "== 13/21 profile_bench CONN (live-connection reactor A/B)"
timeout 1800 python tools/profile_bench.py CONN 2>&1 | tee "$OUT/conn.log"

echo "== 14/21 bench_diff (cross-run regression verdicts, ISSUE 12)"
# judge the fresh chip record against the committed baseline: named
# regression/improvement verdicts with the encoded noise bands; a
# nonzero exit flags the queue log, it does not abort banked artifacts.
# pipefail inside the subshell: without it tee's 0 would mask the
# differ's exit 1 and the flag line below would be dead code
( set -o pipefail; timeout 300 python tools/bench_diff.py \
    benchmarks/bench_baseline_2core.json "$OUT/bench.json" \
    --json "$OUT/bench_diff.json" \
    2>&1 | tee "$OUT/bench_diff.log" ) \
    || echo "bench_diff: REGRESSIONS NAMED ABOVE (see $OUT/bench_diff.json)"

echo "== 15/21 profile_bench POD (multi-host weak-scaling sweep, ISSUE 13)"
# exp_POD = bench.py --mode multihost: per-process local-mesh training
# (CPU workers) + HostChannel carry allreduce; FEDML_POD_PROCS
# overrides the 1,2,4 process sweep
timeout 1800 python tools/profile_bench.py POD 2>&1 | tee "$OUT/pod.log"

echo "== 16/21 profile_bench POD compress (compressed-carry arm, ISSUE 16)"
# the compressed-carry arm under exp_POD, isolated: f32 escape hatch
# bitwise under overlap, int8/int8_ef wire reduction (>= 3x gate rides
# bench_diff), overlap fraction
FEDML_POD_ARMS=compress timeout 1800 python tools/profile_bench.py POD \
    2>&1 | tee "$OUT/pod_compress.log"

echo "== 17/21 profile_bench ELASTIC (elastic-chaos survivor arm, ISSUE 14)"
# exp_ELASTIC = bench.py --mode multihost --mh_arms chaos: the elastic
# 3-process kill-a-rank arm — survivor goodput, view-change latency,
# bitwise_after_death_ok
timeout 1800 python tools/profile_bench.py ELASTIC 2>&1 | tee "$OUT/elastic.log"

echo "== 18/21 profile_bench ELASTIC straggler (cluster observatory, ISSUE 17)"
# the same elastic chaos arm with the observatory ON: per-rank obs dirs
# under $OUT/obs_elastic (rank0/rank1/... + a rejoiner's rank1-pid*),
# rank 0's barrier ledger, cluster SLO
# verdicts (clean green / killed breaching with rank 1 named), and the
# merged per-rank Chrome timeline with gating-rank annotations
mkdir -p "$OUT/obs_elastic"
FEDML_OBS_DIR="$OUT/obs_elastic" timeout 1800 \
    python tools/profile_bench.py ELASTIC 2>&1 \
    | tee "$OUT/elastic_straggler.log"
timeout 300 python tools/trace_timeline.py "$OUT/obs_elastic" \
    --out "$OUT/obs_elastic/merged.chrome.json" \
    --report "$OUT/obs_elastic/critical_path.json" 2>&1 \
    | tee "$OUT/straggler_timeline.log" \
    || echo "trace_timeline: no per-rank traces banked (obs dirs empty?)"

echo "== 19/21 profile_bench CLUSTER (fused serving cluster, ISSUE 18)"
# exp_CLUSTER = bench.py --mode cluster: striped connswarm fleet over
# real sockets against H reactor-fronted hosts, registry-sharded lanes
# folding cross-host per commit barrier; the chaos-everything arm
# (connection storm + wire faults + seeded rank kill in ONE arm) must
# hold survivor goodput >= 0.5x clean with bitwise_after_death_ok —
# verdicts ride bench_diff v16 against the banked bench.json
timeout 1800 python tools/profile_bench.py CLUSTER 2>&1 \
    | tee "$OUT/cluster.log"

echo "== 20/21 profile_bench sparse exchange (top-k codecs, ISSUE 19)"
# the ISSUE-19 sparse arms on both wires: exp_POD with
# FEDML_POD_ARMS=sparse prices the topk/topk_ef carry codecs
# (>= 6x wire reduction at k=P/16 rides bench_diff v17,
# f32 escape hatch stays bitwise under overlap), then exp_CLUSTER with
# FEDML_CLUSTER_ARMS=clean,sparse prices the sparse_topk uplink A/B
# over real sockets (committed-updates/sec >= 0.9x dense gate,
# digests_equal boolean pin)
FEDML_POD_ARMS=sparse timeout 1800 python tools/profile_bench.py POD \
    2>&1 | tee "$OUT/pod_sparse.log"
FEDML_CLUSTER_ARMS=clean,sparse timeout 1800 \
    python tools/profile_bench.py CLUSTER 2>&1 \
    | tee "$OUT/cluster_sparse.log"

echo "== 21/21 profile_bench SECAGG (pairwise-mask secure agg, ISSUE 20)"
# exp_SECAGG = bench.py --mode secure: the privacy-tax table on the
# live async FSM with the chip-attached runtime driving the u32 field
# fold — plain vs masked committed-updates/sec (>= 0.5x floor rides
# bench_diff v18), plain/secure/dp accuracy (the end-to-end private
# mode in the +-0.04 band), masks_cancel_bitwise_ok (exact-integer
# pin), zero below-threshold commits on the clean arms, and the
# masked-byzantine pair (blinded screen vs quantizer range refusal)
timeout 1800 python tools/profile_bench.py SECAGG 2>&1 \
    | tee "$OUT/secagg.log"

echo "== queue complete; artifacts in $OUT"
