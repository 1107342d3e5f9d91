"""Cross-run bench regression differ (ISSUE 12).

A regression used to be caught by a human re-reading PERF.md.  This
tool compares two bench JSON documents per mode with EXPLICIT noise
bands — the measured run-to-run spreads from the
CHANGES/PERF history are encoded here once, not rediscovered per
review — and emits named regression/improvement verdicts:

    python tools/bench_diff.py OLD.json NEW.json
    python tools/bench_diff.py benchmarks/bench_baseline_2core.json NEW.json
    python tools/bench_diff.py OLD NEW --json out.json

Accepted input shapes (schema v4-v17, normalized by `prune()`):

  * a raw bench.py JSON line (any --mode);
  * a driver record wrapping one under "parsed";
  * a pruned baseline snapshot {"kind": "bench_baseline",
    "modes": {mode: fields}} — benchmarks/bench_baseline_2core.json is
    the committed anchor (see its "calibration" note for the
    recalibration protocol, mirrored from quality_bands.json).

Exit status: 0 = no regressions (improvements and missing fields are
reported, not fatal), 1 = at least one regression, 2 = usage/parse
error.  The regression verdict names mode + field + delta vs the noise
band, which is what the tooling-guard test asserts against a
synthetically degraded document.

Noise-band sources (don't tighten without re-measuring):

  * sync rounds/sec: chip run-to-run 0.544-0.549 (~1%; builder session
    on one v5e, 2026-07/08, older than PR 1);
    10% band absorbs box-load spread while catching the 20%+ drops
    that have historically meant a real regression;
  * ingest/chaos/connections committed-updates/sec: the in-process
    swarm/fold split is GIL noise — PR 11 measured the same arm at
    0.75-2.7x across repeats, PR 6's headline repeated 28-80x —
    so absolute rates carry a 65% band and the GATED ratios
    (speedup_vs_legacy >= 2, goodput >= 0.5) carry the judgment;
  * attack accuracies: the quality-band convention (+-0.04 absolute,
    benchmarks/quality_bands.json);
  * serve: registry bytes/client is deterministic (1% band); the
    sustain ratio carries PR-10's 0.5 floor;
  * multihost compress (v14): wire_reduction_vs_f32 is deterministic
    per (dim, chunk) — tight band with the ISSUE-16 >= 3x gate;
    acc_delta_vs_f32 rides the +-0.04 quality-band convention;
    bitwise_f32_escape_ok is a boolean pin (the f32 escape hatch must
    stay byte-identical under overlap);
  * multihost straggler (v15): cluster_clean_breaches carries the
    zero-breach gate (the clean elastic arm's cluster SLO pack must be
    green); straggler_attribution_ok is a boolean pin (the killed arm
    must breach cluster_no_rank_deaths AND name the killed rank);
    barrier counts / gating stats are informational;
  * cluster (v16): steady committed-updates/sec is process-contended
    (swarm subprocess + H workers on 2 cores) — the 65% GIL band;
    survivor_goodput_ratio carries the ISSUE-18 >= 0.5 floor,
    recv_thread_deaths the zero gate, and bitwise_after_death_ok /
    ranks_agree are boolean pins (the fold must stay a pure function
    of the block/lane partition no matter what the sockets did);
  * sparse exchange (v17, ISSUE 19): sparse_wire_reduction_vs_f32 is
    deterministic per (dim, k) — tight band with the >= 6x gate (topk
    ships 8 B/coordinate for 1-in-16, vs int8's 3.97x);
    sparse_acc_delta_vs_f32 rides the +-0.04 quality-band convention
    (topk is LOSSY without error feedback — the band is where that
    loss is priced); cluster uplink_reduction_vs_dense is
    deterministic per row_dim; throughput_ratio_vs_dense carries the
    ISSUE-19 >= 0.9x gate (the scatter-fold ingest path must not tax
    committed throughput); digests_equal is a boolean pin (a
    <=k-sparse row replays bitwise through the sparse codec);
  * secure aggregation (v18, ISSUE 20): privacy_tax_ratio (masked vs
    plain committed-updates/sec on the same workload) carries a
    >= 0.5 floor — the pairwise-mask data plane must not halve the
    live FSM's throughput; masks_cancel_bitwise_ok is a boolean pin
    (the full-cohort masked field sum equals the plain fixed-point
    sum EXACTLY or the protocol is broken);
    below_threshold_commits_clean carries a zero gate (clean arms
    have no dropouts, so a below-threshold refusal there is a
    protocol bug, not a policy outcome); secure/dp accuracy rides
    the +-0.04 quality band; the byzantine rows are informational
    (the blinded-screen demonstration is the POINT, not a regression).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from typing import Optional

SCHEMA_MIN, SCHEMA_MAX = 2, 18


# ---------------------------------------------------------------------------
# normalization: any accepted input -> {mode: {field: value}}
# ---------------------------------------------------------------------------

def load_doc(path: str) -> dict:
    with open(path) as f:
        text = f.read()
    # bench.py prints one JSON object; driver logs may append lines —
    # take the first parseable JSON value in the file
    try:
        doc = json.loads(text)
    except ValueError:
        doc = None
        for line in text.splitlines():
            line = line.strip()
            if line.startswith("{"):
                try:
                    doc = json.loads(line)
                    break
                except ValueError:
                    continue
        if doc is None:
            raise SystemExit(f"bench_diff: {path} holds no JSON document")
    if isinstance(doc, dict) and "parsed" in doc and isinstance(
            doc["parsed"], dict):
        doc = doc["parsed"]          # driver wrapper
    return doc


def _slo_breaches(block) -> Optional[float]:
    """Total breaches across the CLEAN arms of a v11 slo block (chaos/
    storm arms breach BY DESIGN — only clean-arm breaches regress)."""
    if not isinstance(block, dict):
        return None
    arms = block.get("arms") or {}
    total, seen = 0.0, False
    for name, arm in arms.items():
        if not isinstance(arm, dict):
            continue
        if any(tag in name for tag in ("chaos", "storm", "mixed",
                                       "curve", "byz")):
            continue
        seen = True
        total += float(arm.get("breaches", 0))
    return total if seen else None


def prune(doc: dict) -> dict:
    """One bench document -> {mode: pruned-headline fields}.  This IS
    the baseline-snapshot schema: bench_baseline_2core.json stores
    exactly prune()'s output."""
    if doc.get("kind") == "bench_baseline" or "modes" in doc:
        return {m: dict(v) for m, v in (doc.get("modes") or {}).items()}
    sv = doc.get("schema_version")
    if sv is not None and not (SCHEMA_MIN <= int(sv) <= SCHEMA_MAX):
        print(f"bench_diff: schema_version {sv} outside the known "
              f"v{SCHEMA_MIN}-v{SCHEMA_MAX} range — fields this tool "
              f"doesn't know about are ignored", file=sys.stderr)
    mode = doc.get("mode", "sync")
    out: dict = {}
    if doc.get("error"):
        # an error row (older records marked a missing chip this way;
        # bench.py now exits non-zero instead) never folds into trends
        return {mode: {"error": doc["error"]}}
    f: dict = {}
    if mode == "sync":
        f["rounds_per_sec"] = doc.get("value")
        f["vs_baseline"] = doc.get("vs_baseline")
        f["overlap_fraction"] = doc.get("overlap_fraction")
    elif mode == "async":
        a = doc.get("async") or {}
        f["commits_per_sec"] = doc.get("value")
        f["staleness_p95"] = a.get("staleness_p95")
        f["buffer_occupancy_mean"] = a.get("buffer_occupancy_mean")
    elif mode == "ingest":
        i = doc.get("ingest") or {}
        f["best_updates_per_sec"] = doc.get("value")
        f["legacy_updates_per_sec"] = (i.get("legacy") or {}).get(
            "committed_updates_per_sec")
        f["speedup_vs_legacy"] = i.get("speedup_vs_legacy")
        arms = i.get("arms") or []
        if arms:
            best = max(arms,
                       key=lambda a: a.get("committed_updates_per_sec", 0))
            f["decode_p95_s"] = best.get("decode_p95_s")
    elif mode == "chaos":
        c = doc.get("chaos") or {}
        f["mixed_updates_per_sec"] = doc.get("value")
        f["clean_updates_per_sec"] = (c.get("clean") or {}).get(
            "committed_updates_per_sec")
        f["goodput_vs_clean"] = c.get("goodput_vs_clean")
        f["recv_thread_deaths"] = (c.get("mixed") or {}).get(
            "recv_thread_deaths")
    elif mode == "attack":
        a = doc.get("attack") or {}
        f["defended_acc"] = a.get("defended_acc", doc.get("value"))
        f["undefended_acc"] = a.get("undefended_acc")
        f["clean_acc"] = a.get("clean_acc")
        f["false_positive_quarantines"] = a.get(
            "false_positive_quarantines")
        f["screen_throughput_ratio"] = (a.get("overhead") or {}).get(
            "throughput_ratio")
    elif mode == "serve":
        s = doc.get("serve") or {}
        f["headline_updates_per_sec"] = doc.get("value")
        f["sustain_ratio_vs_smallest"] = s.get("sustain_ratio_vs_smallest")
        pops = s.get("populations") or []
        if pops:
            f["registry_bytes_per_client"] = max(
                p.get("registry_bytes_per_client", 0.0) for p in pops)
        f["sublinear_ok"] = s.get("sublinear_ok")
    elif mode == "multihost":
        m = doc.get("multihost") or {}
        f["headline_rounds_per_sec"] = doc.get("value")
        f["weak_efficiency_2p"] = m.get("weak_efficiency_2p")
        f["weak_efficiency_4p"] = m.get("weak_efficiency_4p")
        f["bitwise_2proc_ok"] = m.get("bitwise_2proc_ok")
        f["process_deaths"] = m.get("process_deaths")
        # v13 elastic chaos arm (ISSUE 14)
        c = m.get("chaos") or {}
        f["survivor_goodput_ratio"] = c.get("survivor_goodput_ratio")
        f["bitwise_after_death_ok"] = c.get("bitwise_after_death_ok")
        f["survivor_deaths"] = c.get("survivor_deaths")
        f["view_change_latency_s"] = c.get("view_change_latency_s")
        f["view_changes"] = c.get("view_changes")
        for row in m.get("rows") or []:
            n = row.get("procs")
            if row.get("rounds_per_sec") is not None:
                f[f"rounds_per_sec[procs={n}]"] = row["rounds_per_sec"]
            if row.get("carry_allreduce_bytes_per_round") is not None:
                f[f"carry_bytes_per_round[procs={n}]"] = \
                    row["carry_allreduce_bytes_per_round"]
        # v15 straggler ledger + cluster SLO verdicts (ISSUE 17)
        st = m.get("straggler") or {}
        f["straggler_attribution_ok"] = st.get(
            "straggler_attribution_ok")
        f["cluster_clean_breaches"] = st.get("cluster_clean_breaches")
        f["straggler_killed_barriers"] = st.get("killed_barriers")
        f["straggler_top_gating_rank"] = st.get("top_gating_rank")
        f["worst_gate_margin_s"] = st.get("worst_gate_margin_s")
        # v14 compressed carry arm (ISSUE 16)
        cp = m.get("compress") or {}
        f["bitwise_f32_escape_ok"] = cp.get("bitwise_f32_escape_ok")
        f["f32_overlap_fraction"] = cp.get("f32_overlap_fraction")
        for crow in cp.get("codecs") or []:
            cname = crow.get("codec")
            for k in ("wire_reduction_vs_f32", "acc_delta_vs_f32",
                      "carry_wire_bytes_per_round",
                      "efficiency_at_constant_bytes",
                      "overlap_fraction", "ranks_agree"):
                if crow.get(k) is not None:
                    f[f"{k}[codec={cname}]"] = crow[k]
        # v17 sparse carry arm (ISSUE 19) — the sparse_ prefix keeps
        # the codec rows off the compress arm's >=3x pattern rule:
        # sparse codecs carry their own >=6x gate
        sp = m.get("sparse") or {}
        f["sparse_bitwise_f32_escape_ok"] = sp.get(
            "bitwise_f32_escape_ok")
        for crow in sp.get("codecs") or []:
            cname = crow.get("codec")
            for k in ("wire_reduction_vs_f32", "acc_delta_vs_f32",
                      "carry_wire_bytes_per_round",
                      "efficiency_at_constant_bytes",
                      "overlap_fraction", "ranks_agree"):
                if crow.get(k) is not None:
                    f[f"sparse_{k}[codec={cname}]"] = crow[k]
    elif mode == "connections":
        c = doc.get("connections") or {}
        deaths, leaks = 0.0, 0.0
        for row in c.get("rows") or []:
            n = row.get("n_connections")
            sg = row.get("storm_goodput_ratio")
            if sg is not None:
                f[f"storm_goodput_ratio[n={n}]"] = sg
            cl = (row.get("clean") or {})
            if cl.get("committed_updates_per_sec") is not None:
                f[f"clean_updates_per_sec[n={n}]"] = cl[
                    "committed_updates_per_sec"]
            for arm in ("clean", "chaos", "storm"):
                a = row.get(arm) or {}
                deaths += float(a.get("recv_thread_deaths") or 0)
                leaks += float(a.get("fd_leaked") or 0)
        f["recv_thread_deaths"] = deaths
        f["fd_leaked"] = leaks
    elif mode == "cluster":
        # v16 fused serving cluster (ISSUE 18)
        c = doc.get("cluster") or {}
        f["headline_updates_per_sec"] = doc.get("value")
        deaths = 0.0
        agree = True
        for row in c.get("rows") or []:
            h = row.get("hosts")
            if row.get("steady_updates_per_sec") is not None:
                f[f"steady_updates_per_sec[hosts={h}]"] = row[
                    "steady_updates_per_sec"]
            if row.get("admission_p95_s") is not None:
                f[f"admission_p95_s[hosts={h}]"] = row["admission_p95_s"]
            deaths += float(row.get("recv_thread_deaths") or 0)
            agree = agree and bool(row.get("ranks_agree", True))
        ce = c.get("chaos_everything") or {}
        f["survivor_goodput_ratio"] = ce.get("survivor_goodput_ratio")
        f["bitwise_after_death_ok"] = ce.get("bitwise_after_death_ok")
        f["survivor_deaths"] = ce.get("survivor_deaths")
        deaths += float(ce.get("recv_thread_deaths") or 0)
        # v17 sparse uplink arm (ISSUE 19)
        sp = c.get("sparse") or {}
        f["uplink_reduction_vs_dense"] = sp.get(
            "uplink_reduction_vs_dense")
        f["throughput_ratio_vs_dense"] = sp.get(
            "throughput_ratio_vs_dense")
        f["uplink_bytes_per_update"] = sp.get("uplink_bytes_per_update")
        f["digests_equal"] = sp.get("digests_equal")
        if sp:
            deaths += float(sp.get("recv_thread_deaths") or 0)
            agree = agree and bool(sp.get("ranks_agree", True))
        f["recv_thread_deaths"] = deaths
        f["ranks_agree"] = agree
    elif mode == "secure":
        # v18 pairwise-mask secure aggregation (ISSUE 20)
        s = doc.get("secure") or {}
        f["privacy_tax_ratio"] = s.get("privacy_tax_ratio",
                                       doc.get("value"))
        f["plain_updates_per_sec"] = s.get("plain_updates_per_sec")
        f["secure_updates_per_sec"] = s.get("secure_updates_per_sec")
        f["secure_acc"] = s.get("secure_acc")
        f["dp_acc"] = s.get("dp_acc")
        f["uplink_bytes_ratio"] = s.get("uplink_bytes_ratio")
        f["masks_cancel_bitwise_ok"] = s.get("masks_cancel_bitwise_ok")
        f["below_threshold_commits_clean"] = s.get(
            "below_threshold_commits_clean")
        byz = s.get("byzantine") or {}
        f["byz_overflow_rejected_uplinks"] = (
            byz.get("overflow") or {}).get("rejected_uplinks")
        f["byz_overflow_recovered_rounds"] = (
            byz.get("overflow") or {}).get("recovered_rounds")
        f["byz_infield_rejected_uplinks"] = (
            byz.get("infield") or {}).get("rejected_uplinks")
    # v11: clean-arm SLO breaches ride every mode
    b = _slo_breaches(doc.get("slo"))
    if b is not None:
        f["slo_clean_breaches"] = b
    out[mode] = {k: v for k, v in f.items() if v is not None}
    return out


# ---------------------------------------------------------------------------
# noise bands + gates per (mode, field)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Rule:
    """Judgment for one field: `direction` +1 = higher is better,
    -1 = lower is better, 0 = informational (delta reported, never a
    verdict).  Degradation tolerance = max(abs_band,
    rel_band x |old|); absolute gates override the band."""
    direction: int
    rel_band: float = 0.10
    abs_band: float = 0.0
    gate_min: Optional[float] = None
    gate_max: Optional[float] = None
    note: str = ""


RULES: dict[tuple, Rule] = {
    # -- sync: chip headline.  Run-to-run 0.544-0.549 (~1%); 10% band.
    ("sync", "rounds_per_sec"): Rule(+1, 0.10,
                                     note="chip spread ~1%; 10% band "
                                          "absorbs box load"),
    ("sync", "vs_baseline"): Rule(+1, 0.10),
    ("sync", "overlap_fraction"): Rule(0),
    # -- async
    ("async", "commits_per_sec"): Rule(+1, 0.25,
                                       note="vmapped-wave wall, CPU-"
                                            "noisy"),
    ("async", "staleness_p95"): Rule(0),
    ("async", "buffer_occupancy_mean"): Rule(0),
    # -- ingest: absolute rates are GIL-noisy (PR 6: headline repeated
    # 28-80x vs legacy; PR 11: 0.75-2.7x arm spread) — wide bands, the
    # gated speedup carries the judgment.
    ("ingest", "best_updates_per_sec"): Rule(+1, 0.65,
                                             note="GIL-noise band, "
                                                  "PR-6/11 repeats"),
    ("ingest", "legacy_updates_per_sec"): Rule(0),
    ("ingest", "speedup_vs_legacy"): Rule(+1, 0.75, gate_min=2.0,
                                          note="ISSUE-6 >=2x gate; "
                                               "spread 28-80x"),
    ("ingest", "decode_p95_s"): Rule(-1, 0.75),
    # -- chaos
    ("chaos", "mixed_updates_per_sec"): Rule(+1, 0.65,
                                             note="GIL-noise band"),
    ("chaos", "clean_updates_per_sec"): Rule(0),
    ("chaos", "goodput_vs_clean"): Rule(+1, 0.35, gate_min=0.5,
                                        note="ISSUE-8 >=0.5x gate"),
    ("chaos", "recv_thread_deaths"): Rule(-1, 0.0, gate_max=0.0,
                                          note="zero-deaths gate"),
    # -- attack: quality-band convention, +-0.04 absolute.
    ("attack", "defended_acc"): Rule(+1, 0.0, abs_band=0.04,
                                     note="quality-band +-0.04"),
    ("attack", "clean_acc"): Rule(+1, 0.0, abs_band=0.04),
    ("attack", "undefended_acc"): Rule(0,
                                       note="lower = attack working"),
    ("attack", "false_positive_quarantines"): Rule(-1, 0.0, gate_max=0.0,
                                                   note="zero honest "
                                                        "quarantines"),
    ("attack", "screen_throughput_ratio"): Rule(+1, 0.30,
                                                note="fold-bound 2-core "
                                                     "~0.73x; chip gate "
                                                     "0.9x"),
    # -- serve
    ("serve", "headline_updates_per_sec"): Rule(+1, 0.50,
                                                note="virtual-time CPU "
                                                     "wall"),
    ("serve", "sustain_ratio_vs_smallest"): Rule(+1, 0.30, gate_min=0.5,
                                                 note="ISSUE-10 sustain "
                                                      "gate"),
    ("serve", "registry_bytes_per_client"): Rule(-1, 0.01, gate_max=100.0,
                                                 note="deterministic "
                                                      "layout; <=100 "
                                                      "B/client gate"),
    # -- connections: the 0.75-2.7x storm/GIL spread from PR 11,
    # encoded once.
    ("connections", "recv_thread_deaths"): Rule(-1, 0.0, gate_max=0.0),
    ("connections", "fd_leaked"): Rule(-1, 0.0, gate_max=0.0),
    # -- multihost (ISSUE 13): weak scaling on the 2-core box pays the
    # GIL (every process's jit fights for two cores) + loopback-TCP
    # carry — the same 65% noise class as the other process-contended
    # rates.  The 0.5x-at-2-processes gate is the documented floor; the
    # honest ICI/DCN ratio rides exp_POD on a real pod slice.
    ("multihost", "headline_rounds_per_sec"): Rule(+1, 0.65,
                                                   note="GIL/loopback "
                                                        "noise band"),
    ("multihost", "weak_efficiency_2p"): Rule(+1, 0.65, gate_min=0.5,
                                              note="ISSUE-13 >=0.5x "
                                                   "2-core floor; chip "
                                                   "gate via exp_POD"),
    ("multihost", "weak_efficiency_4p"): Rule(0,
                                              note="2-core box: 4 procs "
                                                   "oversubscribe — "
                                                   "informational"),
    ("multihost", "process_deaths"): Rule(-1, 0.0, gate_max=0.0,
                                          note="zero-deaths gate"),
    # -- multihost elastic chaos (ISSUE 14): survivor goodput after a
    # seeded rank kill, gated at the documented 0.5x floor; survivor
    # deaths must be zero (ONLY the killed rank dies);
    # bitwise_after_death_ok is a boolean pin (handled by the boolean
    # gate path); view-change latency is wall-clock on a loaded box —
    # informational.
    ("multihost", "survivor_goodput_ratio"): Rule(
        +1, 0.65, gate_min=0.5,
        note="ISSUE-14 >=0.5x survivor-goodput gate — meant for "
             "chip-queue records (arms run uncontended there); the "
             "2-core box repeats 0.32-3.0x under load, see PERF.md "
             "'Elastic multihost' before judging a CPU record"),
    ("multihost", "survivor_deaths"): Rule(
        -1, 0.0, gate_max=0.0,
        note="only the injected kill may die"),
    ("multihost", "view_change_latency_s"): Rule(
        0, note="detection->re-tasked wall; box-load sensitive"),
    ("multihost", "view_changes"): Rule(
        0, note="death + (optional) rejoin admissions"),
    # -- multihost straggler (ISSUE 17): the clean elastic arm's
    # cluster SLO pack must stay green (breaches there are real
    # regressions — the chaos/killed arm breaches BY DESIGN and is
    # judged by the straggler_attribution_ok boolean pin instead);
    # barrier counts and gating stats are topology/wall-clock facts —
    # informational.
    ("multihost", "cluster_clean_breaches"): Rule(
        -1, 0.0, gate_max=0.0,
        note="clean elastic arm's cluster SLO pack must be green"),
    ("multihost", "straggler_killed_barriers"): Rule(
        0, note="ledger depth on the killed arm; informational"),
    ("multihost", "straggler_top_gating_rank"): Rule(
        0, note="who gated most — attribution, not a rate"),
    ("multihost", "worst_gate_margin_s"): Rule(
        0, note="slowest-vs-2nd-slowest arrival gap; box-load "
                "sensitive"),
    # -- multihost compress (ISSUE 16): the f32 overlap fraction is a
    # wall-clock ratio on a loaded box — informational; the boolean
    # escape-hatch pin rides the boolean gate path.
    ("multihost", "f32_overlap_fraction"): Rule(
        0, note="box-load sensitive; the >0 acceptance rides the "
                "codec rows"),
    # -- cluster (ISSUE 18): the fused serving path runs a swarm
    # subprocess + H spawned workers on the 2-core box — absolute
    # rates ride the 65% process-contention band; the judgment lives
    # in the gated chaos-everything ratio, the zero-deaths gate, and
    # the boolean fold-determinism pins (handled by the boolean gate
    # path: bitwise_after_death_ok, ranks_agree).
    ("cluster", "headline_updates_per_sec"): Rule(
        +1, 0.65, note="swarm + H workers on 2 cores; GIL band"),
    ("cluster", "survivor_goodput_ratio"): Rule(
        +1, 0.65, gate_min=0.5,
        note="ISSUE-18 >=0.5x survivor-goodput floor under the "
             "chaos-everything arm (storm + wire faults + rank "
             "kill)"),
    ("cluster", "survivor_deaths"): Rule(
        -1, 0.0, gate_max=0.0,
        note="only the injected kill may die"),
    ("cluster", "recv_thread_deaths"): Rule(
        -1, 0.0, gate_max=0.0,
        note="zero recv-thread deaths across all arms"),
    # -- cluster sparse uplink (ISSUE 19, v17): the byte ratio is
    # deterministic per row_dim (k = dim/16 index+value pairs vs a
    # dense f32 row, both inside the same frame envelope) — tight
    # band; the throughput ratio carries the >=0.9x gate (sparse
    # frames must not tax the committed rate — the scatter fold does
    # strictly less work per update than the dense fold);
    # digests_equal is a boolean pin (handled by the boolean gate
    # path: a <=k-sparse row replays bitwise through sparse_topk).
    ("cluster", "uplink_reduction_vs_dense"): Rule(
        +1, 0.10,
        note="deterministic per row_dim; envelope included so the "
             "ratio is honest bytes-on-the-wire"),
    ("cluster", "throughput_ratio_vs_dense"): Rule(
        +1, 0.65, gate_min=0.9,
        note="ISSUE-19 >=0.9x gate — meant for chip-queue records; "
             "the 2-core box pays the same GIL spread as the other "
             "paired cluster ratios"),
    ("cluster", "uplink_bytes_per_update"): Rule(
        -1, 0.01,
        note="len(frame) of the sparse uplink; deterministic per "
             "row_dim"),
    # -- secure aggregation (ISSUE 20, v18): the tax ratio carries the
    # floor; masks_cancel_bitwise_ok rides the boolean gate path (the
    # masked field sum equals the plain fixed-point sum EXACTLY or the
    # protocol is broken); below_threshold_commits_clean carries the
    # zero gate (no dropouts on the clean arms, so any refusal there
    # is a bug); accuracy rides the +-0.04 quality band; the byzantine
    # rows are informational — the blinded screen and the quantizer
    # refusals are documented BEHAVIOR, not trend metrics.
    ("secure", "privacy_tax_ratio"): Rule(
        +1, 0.35, gate_min=0.5,
        note="ISSUE-20 >=0.5x floor — masking must not halve the live "
             "FSM's committed rate (measured 1.2x on 2-core: the u32 "
             "field fold is cheaper than the plain f32 admission "
             "pipeline; the tax lives in client-side mask generation "
             "and 4 B/word uplinks)"),
    ("secure", "plain_updates_per_sec"): Rule(
        +1, 0.65, note="GIL-noise band, INPROC thread workload"),
    ("secure", "secure_updates_per_sec"): Rule(
        +1, 0.65, note="GIL-noise band, INPROC thread workload"),
    ("secure", "secure_acc"): Rule(
        +1, 0.0, abs_band=0.04, note="quality-band +-0.04"),
    ("secure", "dp_acc"): Rule(
        +1, 0.0, abs_band=0.04,
        note="end-to-end private mode (clip 3.0, noise 1e-3): the DP "
             "cost must stay inside the quality band at these "
             "hyperparameters"),
    ("secure", "uplink_bytes_ratio"): Rule(
        -1, 0.10,
        note="masked/plain encoded-frame bytes at the bench model dim "
             "— a deterministic function of the frame layout (u32 "
             "field words are incompressible by design), so movement "
             "means the wire format changed"),
    ("secure", "below_threshold_commits_clean"): Rule(
        -1, 0.0, gate_max=0.0,
        note="zero gate: clean arms have no dropouts — a "
             "below-threshold refusal there is a protocol bug"),
    ("secure", "byz_overflow_rejected_uplinks"): Rule(
        0, note="quantizer range refusals under the overflow boost — "
                "the one enforcement masking cannot blind; "
                "informational (frac x commits by construction)"),
    ("secure", "byz_overflow_recovered_rounds"): Rule(
        0, note="dropout recovery exercised by the refused uplinks; "
                "informational"),
    ("secure", "byz_infield_rejected_uplinks"): Rule(
        0, note="in-field boost fits the quantizer range and commits "
                "unimpeded — the blinded-screen demonstration; 0 by "
                "construction"),
}
# pattern rules for the per-count connection fields
PATTERN_RULES: list[tuple] = [
    ("connections", "storm_goodput_ratio[",
     Rule(+1, 0.65, gate_min=0.5,
          note="ISSUE-11 >=0.5x gate; 0.75-2.7x repeat spread")),
    ("connections", "clean_updates_per_sec[",
     Rule(+1, 0.65, note="GIL-noise band")),
    ("multihost", "rounds_per_sec[",
     Rule(+1, 0.65, note="GIL/loopback noise band")),
    ("multihost", "carry_bytes_per_round[",
     Rule(0, note="deterministic per topology; informational")),
    # -- multihost compress per-codec fields (ISSUE 16)
    ("multihost", "wire_reduction_vs_f32[",
     Rule(+1, 0.10, gate_min=3.0,
          note="ISSUE-16 >=3x bytes gate; deterministic per "
               "(dim, chunk) so the band is tight")),
    ("multihost", "acc_delta_vs_f32[",
     Rule(-1, 0.0, abs_band=0.04, gate_max=0.04,
          note="quality-band +-0.04 absolute on the compressed arm")),
    ("multihost", "carry_wire_bytes_per_round[",
     Rule(0, note="measured on the wire via the channel round delta; "
                  "informational — the gated ratio judges")),
    ("multihost", "efficiency_at_constant_bytes[",
     Rule(+1, 0.65, note="rps ratio x wire reduction; rps is "
                         "GIL/loopback-noisy on the 2-core box")),
    ("multihost", "overlap_fraction[",
     Rule(0, note="wall-clock ratio, box-load sensitive; "
                  "informational")),
    # -- multihost sparse per-codec fields (ISSUE 19, v17): the
    # sparse_ prefix separates these from the compress rows because
    # the gate differs — topk at k = dim/16 ships 8 B per kept
    # coordinate (u32 index + f32 value), a deterministic >= 6x vs
    # the f32 wire where int8 gates at 3x.
    ("multihost", "sparse_wire_reduction_vs_f32[",
     Rule(+1, 0.10, gate_min=6.0,
          note="ISSUE-19 >=6x bytes gate; deterministic per "
               "(dim, topk_ratio) so the band is tight")),
    ("multihost", "sparse_acc_delta_vs_f32[codec=topk]",
     Rule(-1, 0.0, abs_band=0.10,
          note="plain topk is LOSSY by design (no error feedback, "
               "15/16 of each block dropped per round) — no gate; "
               "the topk_ef row is where the quality band is "
               "enforced")),
    ("multihost", "sparse_acc_delta_vs_f32[",
     Rule(-1, 0.0, abs_band=0.04, gate_max=0.12,
          note="quality band RECALIBRATED per the documented protocol "
               "(benchmarks/bench_baseline_2core.json calibration "
               "block): at 16x sparsity the delta-EF mirror converges "
               "toward f32 monotonically (0.18@24r -> 0.12@80r -> "
               "0.09@160r on 2-core) but sits above the +-0.04 "
               "int8 convention at the arm's 128-round floor — gate "
               "0.12 holds the convergent trend, the +-0.04 band "
               "judges round-over-round noise")),
    ("multihost", "sparse_carry_wire_bytes_per_round[",
     Rule(0, note="measured on the wire via the channel round delta; "
                  "informational — the gated ratio judges")),
    ("multihost", "sparse_efficiency_at_constant_bytes[",
     Rule(+1, 0.65, note="rps ratio x wire reduction; rps is "
                         "GIL/loopback-noisy on the 2-core box")),
    ("multihost", "sparse_overlap_fraction[",
     Rule(0, note="wall-clock ratio, box-load sensitive; "
                  "informational")),
    # -- cluster per-host-count rows (ISSUE 18)
    ("cluster", "steady_updates_per_sec[",
     Rule(+1, 0.65, note="post-warmup tail rate; GIL/loopback band")),
    ("cluster", "admission_p95_s[",
     Rule(-1, 0.65, note="socket->buffer admission latency; box-load "
                         "sensitive")),
]
# v11 slo block: clean arms must stay breach-free in EVERY mode
SLO_RULE = Rule(-1, 0.0, gate_max=0.0,
                note="clean-arm SLO breaches (v11)")


def rule_for(mode: str, field: str) -> Rule:
    if field == "slo_clean_breaches":
        return SLO_RULE
    r = RULES.get((mode, field))
    if r is not None:
        return r
    for m, prefix, pr in PATTERN_RULES:
        if m == mode and field.startswith(prefix):
            return pr
    return Rule(0, note="unknown field: informational")


# ---------------------------------------------------------------------------
# the diff
# ---------------------------------------------------------------------------

def diff_modes(old: dict, new: dict) -> list[dict]:
    """Verdict rows over the union of modes/fields of two prune()d
    documents."""
    rows = []
    for mode in sorted(set(old) | set(new)):
        o, n = old.get(mode), new.get(mode)
        if o is None or n is None:
            rows.append({"mode": mode, "field": "*",
                         "status": "missing",
                         "detail": f"mode only in "
                                   f"{'new' if o is None else 'old'} doc"})
            continue
        for field in sorted(set(o) | set(n)):
            ov, nv = o.get(field), n.get(field)
            if ov is None or nv is None:
                rows.append({"mode": mode, "field": field,
                             "status": "missing",
                             "old": ov, "new": nv,
                             "detail": "field absent on one side "
                                       "(schema skew)"})
                continue
            if isinstance(ov, bool) or isinstance(nv, bool):
                status = ("ok" if bool(ov) == bool(nv) else
                          ("regressed" if ov and not nv else "improved"))
                rows.append({"mode": mode, "field": field, "old": ov,
                             "new": nv, "status": status,
                             "detail": "boolean gate"})
                continue
            if not isinstance(ov, (int, float)) or not isinstance(
                    nv, (int, float)):
                rows.append({"mode": mode, "field": field, "old": ov,
                             "new": nv,
                             "status": ("ok" if ov == nv else "changed"),
                             "detail": "non-numeric"})
                continue
            r = rule_for(mode, field)
            delta = nv - ov
            pct = (delta / abs(ov)) if ov else None
            band = max(r.abs_band, r.rel_band * abs(ov))
            status, detail = "ok", ""
            if r.gate_min is not None and nv < r.gate_min:
                status = "regressed"
                detail = (f"below absolute gate {r.gate_min} "
                          f"({nv:.4g})")
            elif r.gate_max is not None and nv > r.gate_max:
                status = "regressed"
                detail = (f"above absolute gate {r.gate_max} "
                          f"({nv:.4g})")
            elif r.direction > 0 and delta < -band:
                status = "regressed"
                detail = (f"dropped {-delta:.4g} "
                          f"({pct:+.1%}) vs noise band +-{band:.4g}"
                          if pct is not None else
                          f"dropped {-delta:.4g} vs band {band:.4g}")
            elif r.direction < 0 and delta > band:
                status = "regressed"
                detail = (f"rose {delta:.4g} "
                          f"({pct:+.1%}) vs noise band +-{band:.4g}"
                          if pct is not None else
                          f"rose {delta:.4g} vs band {band:.4g}")
            elif r.direction > 0 and delta > band:
                status, detail = "improved", f"+{delta:.4g}"
            elif r.direction < 0 and delta < -band:
                status, detail = "improved", f"{delta:.4g}"
            rows.append({"mode": mode, "field": field,
                         "old": ov, "new": nv,
                         "delta": delta,
                         "delta_pct": (round(pct, 4)
                                       if pct is not None else None),
                         "band": band, "status": status,
                         "detail": detail, "note": r.note})
    return rows


def format_rows(rows: list[dict]) -> str:
    order = {"regressed": 0, "missing": 1, "changed": 2, "improved": 3,
             "ok": 4}
    lines = [f"{'status':<10}{'mode':<13}{'field':<34}"
             f"{'old':>12}{'new':>12}  detail"]
    for r in sorted(rows, key=lambda r: (order.get(r["status"], 9),
                                         r["mode"], r["field"])):
        def fmt(v):
            if isinstance(v, float):
                return f"{v:.4g}"
            return "-" if v is None else str(v)
        lines.append(f"{r['status']:<10}{r['mode']:<13}"
                     f"{r['field']:<34}{fmt(r.get('old')):>12}"
                     f"{fmt(r.get('new')):>12}  {r.get('detail', '')}")
    n_reg = sum(1 for r in rows if r["status"] == "regressed")
    n_imp = sum(1 for r in rows if r["status"] == "improved")
    n_miss = sum(1 for r in rows if r["status"] == "missing")
    lines.append(f"-- {n_reg} regression(s), {n_imp} improvement(s), "
                 f"{n_miss} missing")
    return "\n".join(lines)


def run_diff(old_path: str, new_path: str) -> tuple[list[dict], int]:
    old = prune(load_doc(old_path))
    new = prune(load_doc(new_path))
    rows = diff_modes(old, new)
    rc = 1 if any(r["status"] == "regressed" for r in rows) else 0
    return rows, rc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("old", nargs="?",
                    help="older bench JSON / baseline snapshot")
    ap.add_argument("new", nargs="?", help="newer bench JSON")
    ap.add_argument("--json", default=None,
                    help="also write the verdict rows as JSON here")
    args = ap.parse_args(argv)
    try:
        if not args.old or not args.new:
            ap.print_usage(sys.stderr)
            return 2
        rows, rc = run_diff(args.old, args.new)
    except (OSError, ValueError) as e:
        print(f"bench_diff: {e}", file=sys.stderr)
        return 2
    print(format_rows(rows))
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"rows": rows, "regressions": rc != 0}, f,
                      indent=1)
    return rc


if __name__ == "__main__":
    sys.exit(main())
