"""Performance-observatory tests (ISSUE 12): the SLO engine
(fedml_tpu/obs/slo.py), the per-program-family profile registry
(fedml_tpu/obs/programs.py) and the httpd endpoint semantics.

Pinned invariants:

* SLO specs evaluate as WINDOWED deltas over the live registry: green
  windows stay green, a quarantine/eviction delta breaches with named
  attribution, breaches increment slo_breaches_total{slo} and fire ONE
  throttled flight dump;
* the default serving-spine pack is green on a clean ingest arm and
  counts >= 1 breach on a chaos arm (the ISSUE-12 acceptance shape);
* instrumented programs count dispatches + dispatch walls per family,
  attribute backend compiles to the registering family (fallback
  `unattributed`), join the HLO flop/byte census into MFU, and NEVER
  change results (the jit passes through untouched — `lower` included,
  so the hlo audit keeps working).
"""
import glob
import json
import os
import signal
import time

import numpy as np
import pytest

from fedml_tpu import obs
from fedml_tpu.obs import programs, slo


@pytest.fixture
def clean_obs():
    prev = signal.getsignal(signal.SIGUSR1)
    obs.reset()
    yield
    obs.reset()
    signal.signal(signal.SIGUSR1, prev)


# -- SLO engine --------------------------------------------------------------

def test_slo_spec_validation():
    with pytest.raises(ValueError):
        slo.spec("x", "m", "nope", 1.0)
    with pytest.raises(ValueError):
        slo.spec("x", "m", "quantile_max", 1.0, q=1.5)
    with pytest.raises(ValueError):
        slo.spec("x", "m", "rate_min", 1.0, burn_windows=0)
    with pytest.raises(ValueError):
        slo.SloEngine([slo.spec("dup", "m", "delta_max", 0.0)] * 2)


def test_slo_green_then_breach_with_attribution(clean_obs):
    eng = slo.SloEngine([
        slo.spec("floor", "work_total", "rate_min", 1.0),
        slo.spec("no_bad", "bad_total", "delta_max", 0.0),
    ], dump_min_interval_s=1e9)
    eng.prime()
    obs.counter("work_total").inc(100)
    time.sleep(0.02)
    rep = eng.evaluate()
    assert rep["healthy"] and rep["breached"] == []
    # a breach names its spec and lands in the counter
    obs.counter("work_total").inc(100)
    obs.counter("bad_total", backend="tcp").inc(2)     # label-subset match
    rep = eng.evaluate()
    assert rep["breached"] == ["no_bad"]
    assert obs.counter("slo_breaches_total", slo="no_bad").value == 1
    assert obs.gauge("slo_healthy", slo="no_bad").value == 0.0
    row = next(r for r in rep["slos"] if r["name"] == "no_bad")
    assert row["value"] == 2.0 and row["status"] == "breach"
    # the NEXT window is clean again: deltas, not cumulative state
    obs.counter("work_total").inc(100)
    rep = eng.evaluate()
    row = next(r for r in rep["slos"] if r["name"] == "no_bad")
    assert row["status"] == "ok"


def test_slo_quantile_window_and_no_data(clean_obs):
    eng = slo.SloEngine([
        slo.spec("p95", "lat_seconds", "quantile_max", 0.1, q=0.95),
        slo.spec("ghost", "never_registered_total", "delta_max", 0.0),
    ])
    eng.prime()
    h = obs.histogram("lat_seconds", buckets=(0.01, 0.1, 1.0))
    for _ in range(50):
        h.observe(0.005)
    rep = eng.evaluate()
    assert rep["healthy"]
    ghost = next(r for r in rep["slos"] if r["name"] == "ghost")
    assert ghost["status"] == "no_data"      # absent metric: not a breach
    # a slow window breaches on the WINDOW's p95, not all-time
    for _ in range(200):
        h.observe(0.5)
    rep = eng.evaluate()
    assert rep["breached"] == ["p95"]
    # ... and an idle window has nothing to judge (empty delta)
    rep = eng.evaluate()
    p95 = next(r for r in rep["slos"] if r["name"] == "p95")
    assert p95["status"] == "no_data"


def test_slo_burn_windows(clean_obs):
    eng = slo.SloEngine([
        slo.spec("slowburn", "bad2_total", "delta_max", 0.0,
                 burn_windows=2),
    ])
    eng.prime()
    obs.counter("bad2_total").inc()
    rep = eng.evaluate()                     # 1st breaching window: budget
    assert rep["breaches"] == 0
    assert next(r for r in rep["slos"])["burn"] == 1
    obs.counter("bad2_total").inc()
    rep = eng.evaluate()                     # 2nd consecutive: fires
    assert rep["breaches"] == 1 and rep["breached"] == ["slowburn"]
    obs.counter("bad2_total").inc()
    rep = eng.evaluate()                     # still burning: fires again
    assert rep["breaches"] == 2


def test_slo_breach_flight_dump_throttled(clean_obs, tmp_path):
    obs.configure(str(tmp_path), install_signal=False,
                  export_at_exit=False)
    eng = slo.SloEngine([
        slo.spec("no_bad", "bad3_total", "delta_max", 0.0),
    ], dump_min_interval_s=60.0)
    eng.prime()
    obs.counter("bad3_total").inc()
    eng.evaluate()
    obs.counter("bad3_total").inc()
    eng.evaluate()                           # breaches again, inside throttle
    dumps = glob.glob(str(tmp_path / "flight-*.json"))
    assert len(dumps) == 1, "breach storm must not storm the recorder"
    doc = json.load(open(dumps[0]))
    assert doc["reason"].startswith("slo_breach:no_bad")
    assert doc["slo"]["breached"] == ["no_bad"]


def test_slo_rollup_and_httpd_endpoints(clean_obs, tmp_path):
    import urllib.request
    eng = slo.SloEngine([slo.spec("ok", "x_total", "delta_max", 10.0)])
    eng.prime()
    eng.evaluate()
    slo.install(eng)
    ru = obs.rollup()
    assert ru["slo"]["pack"] == slo.DEFAULT_PACK_NAME
    assert ru["slo"]["healthy"]
    srv = obs.serve_http(0)
    base = f"http://127.0.0.1:{srv.port}"
    hz = json.loads(urllib.request.urlopen(f"{base}/healthz").read())
    assert hz["status"] == "ok" and hz["pid"] == os.getpid()
    assert hz["uptime_s"] >= 0
    sl = json.loads(urllib.request.urlopen(f"{base}/slo").read())
    assert sl["healthy"] and sl["slos"][0]["name"] == "ok"
    # no engine installed -> 503, not a bogus empty 200
    slo.install(None)
    import urllib.error
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(f"{base}/slo")
    assert ei.value.code == 503


def test_slo_background_evaluator_installs_and_stops(clean_obs):
    eng = slo.SloEngine([slo.spec("ok", "y_total", "delta_max", 10.0)])
    eng.start(period_s=0.05)
    assert slo.active() is eng
    time.sleep(0.2)
    eng.stop()
    assert eng.report()["windows_evaluated"] >= 2


# -- default pack vs real ingest arms ----------------------------------------

def test_default_pack_green_on_clean_breach_on_chaos(clean_obs):
    """The ISSUE-12 acceptance shape at test scale: one clean INPROC
    ingest arm evaluates green, one corrupt-chaos arm counts >= 1
    breach with named attribution (one SLO window per arm, the
    `slo_arm` block of the torture report)."""
    from fedml_tpu.async_.torture import run_ingest_torture
    clean = run_ingest_torture(
        n_clients=3, backend="INPROC", p=4096, buffer_k=4, commits=5,
        warmup_commits=2, ingest_pool=2, decode_into=True,
        streaming=True)
    assert clean["slo_arm"]["healthy"]
    assert clean["slo_arm"]["breaches"] == 0
    chaos = run_ingest_torture(
        n_clients=3, backend="INPROC", p=4096, buffer_k=4, commits=5,
        warmup_commits=2, ingest_pool=2, decode_into=True,
        streaming=True, chaos={"corrupt": 0.3})
    assert chaos["slo_arm"]["breaches"] >= 1
    assert "no_quarantines" in chaos["slo_arm"]["breached"]
    # pool-path corrupt frames land in the SAME quarantine counter the
    # inline path uses (the ISSUE-12 accounting fix)
    assert chaos["quarantined"] >= 1


# -- program profile registry ------------------------------------------------

def test_programs_instrument_counts_walls_and_passthrough(clean_obs):
    import jax
    calls = []

    def f(x):
        calls.append(1)
        return x * 2.0
    prog = programs.instrument("async_commit", jax.jit(f))
    x = np.arange(8, dtype=np.float32)
    snap = programs.snapshot()
    for _ in range(3):
        out = prog(x)
    np.testing.assert_array_equal(np.asarray(out), x * 2.0)
    ctr = obs.counter("program_dispatches_total", family="async_commit")
    assert ctr.value == 3
    rep = programs.report(snap)
    row = next(r for r in rep["families"]
               if r["family"] == "async_commit")
    assert row["dispatches"] == 3
    assert row["stage"] == "commit"          # the timeline stage mapping
    assert row["dispatch_p95_s"] > 0
    # `lower` passes through (the hlo audit's AOT path)
    assert prog.lower(x).compile() is not None
    # double-instrumentation re-tags instead of double-timing
    again = programs.instrument("async_commit", prog)
    assert again.inner is prog.inner


def test_programs_compile_attribution(clean_obs):
    """A backend compile triggered inside an instrumented dispatch
    books under the family's labeled compile counters; one triggered
    outside books as `unattributed`."""
    import jax
    prog = programs.instrument(
        "async_fold", jax.jit(lambda x: x + 1.0))
    prog(np.zeros((17,), np.float32))        # unique shape -> compile
    fam = obs.registry().counter("jit_compile_total", family="async_fold")
    assert fam.value >= 1
    base = obs.registry().counter("jit_compile_total",
                                  family="unattributed").value
    jax.jit(lambda x: x - 1.0)(np.zeros((19,), np.float32))
    un = obs.registry().counter("jit_compile_total",
                                family="unattributed")
    assert un.value >= base + 1
    assert obs.registry().counter("jit_compile_seconds_total",
                                  family="async_fold").value > 0


def test_programs_census_and_mfu(clean_obs):
    """A family's census — here the compiled program's own cost analysis,
    attached by hand — and report() turns dispatch counts into FLOP/byte
    totals (the 64x64 matmul's flops are exactly 2·64^3 on this backend).
    The "MFU" this report once derived (census FLOPs over HOST dispatch
    wall) is gone: no field, no gauge."""
    import jax
    fn = jax.jit(lambda x: x @ x)
    prog = programs.instrument("fedavg_resident", fn)
    a = np.zeros((64, 64), np.float32)
    flops, nbytes = programs.cost_analysis_of(fn.lower(a).compile())
    programs.register("fedavg_resident").attach_census(
        flops=flops, bytes_accessed=nbytes)
    snap = programs.snapshot()
    for _ in range(4):
        prog(a)
    rep = programs.report(snap)
    row = next(r for r in rep["families"]
               if r["family"] == "fedavg_resident")
    assert row["flops_per_dispatch"] == 2 * 64 ** 3
    assert row["flops_total"] == 4 * 2 * 64 ** 3
    assert row["bytes_per_dispatch"] > 0
    assert row["stage"] == "train"
    assert rep["total"]["flops_total"] == row["flops_total"]
    assert obs.gauge("program_bytes_moved_total",
                     family="fedavg_resident").value \
        == row["bytes_total"]
    assert "mfu" not in row and "mfu" not in rep["total"]
    assert not any(m.name == "program_mfu"
                   for m in obs.registry().metrics())
    assert "MFU" not in programs.format_table(rep)


def test_programs_census_from_audit_artifact(clean_obs):
    """load_census joins a tools/hlo_copy_audit.py artifact's
    flops/bytes into already-registered families."""
    report = {"families": {
        "async_stream_commit": {"programs": {
            "stream_commit": {"flops": 1000.0, "bytes_accessed": 4000.0},
        }},
        "no_census_family": {"programs": {"p": {"copy_ops": 0}}},
    }}
    assert programs.load_census(report) == 1
    fam = programs.register("async_stream_commit")
    assert fam.flops_per_dispatch == 1000.0
    assert fam.census_source == "hlo_copy_audit"


def test_programs_report_per_process_breakdown(clean_obs):
    """ISSUE 13: a multihost run folds each rank's metric deltas into
    rank 0's registry under origin="host<i>" (the PR-7 remote-fold
    shape); programs.report() surfaces those merged series as
    per-process breakdown rows — so an N-process run's per-rank
    dispatch counts/walls are visible instead of last-writer-wins."""
    from fedml_tpu.obs.metrics import CANONICAL_BUCKETS
    reg = obs.registry()
    # a local dispatch too, so local rows and process rows coexist
    import jax
    prog = programs.instrument("fedavg_twolevel",
                               jax.jit(lambda x: x + 1))
    prog(1.0)
    ladder = list(CANONICAL_BUCKETS["program_dispatch_seconds"])
    counts = [0] * (len(ladder) + 1)
    counts[6] = 3                      # three sub-ms dispatches
    delta = {"schema": 1, "metrics": [
        {"name": "program_dispatches_total",
         "labels": {"family": "fedavg_twolevel"}, "kind": "counter",
         "value": 3},
        {"name": "program_dispatch_seconds",
         "labels": {"family": "fedavg_twolevel"}, "kind": "histogram",
         "buckets": ladder, "counts": counts, "sum": 0.0015,
         "count": 3},
    ]}
    reg.merge_delta(delta, origin="host1")
    rep = programs.report()
    assert any(r["family"] == "fedavg_twolevel"
               for r in rep["families"]), "local row lost"
    procs = rep["processes"]
    assert len(procs) == 1
    row = procs[0]
    assert row["family"] == "fedavg_twolevel"
    assert row["process"] == "host1"
    assert row["dispatches"] == 3
    assert row["dispatch_wall_s"] == pytest.approx(0.0015)
    assert row["dispatch_p95_s"] > 0
    # the merged series must NOT double into the local family rows
    local = [r for r in rep["families"]
             if r["family"] == "fedavg_twolevel"]
    assert local[0]["dispatches"] == 1


def test_engine_round_dispatches_profiled(clean_obs):
    """The sync engine's round program books its dispatches under the
    engine's program family (the ISSUE-12 acceptance table's sync-engine
    row), and the family name follows the audit taxonomy."""
    import jax
    from parallel_case import _mnist_like_cfg, _setup
    from fedml_tpu.parallel import MeshFedAvgEngine
    from fedml_tpu.parallel.mesh import make_mesh
    cfg = _mnist_like_cfg(comm_round=1)
    trainer, data = _setup(cfg)
    eng = MeshFedAvgEngine(trainer, data, cfg, mesh=make_mesh(8))
    assert eng.program_family == "fedavg_resident"
    variables = eng._prepare_variables(eng.init_variables())
    server_state = eng.server_init(variables)
    snap = programs.snapshot()
    stack, stack_w = eng._device_stack()
    ids, wmask = eng.sample_padded(0)
    eng.round_fn(variables, server_state, stack, stack_w, ids, wmask,
                 jax.random.PRNGKey(0))
    rep = programs.report(snap)
    row = next(r for r in rep["families"]
               if r["family"] == "fedavg_resident")
    assert row["dispatches"] == 1


# -- overhead gate -----------------------------------------------------------

def test_slo_evaluator_cost_bound(clean_obs):
    """The >= 0.99x acceptance gate, argued by construction: the SLO
    engine runs ONLY at evaluation time (snapshot diffs over the
    registry — no per-event hook anywhere on the hot path), so its e2e
    tax is evaluations/sec x cost/evaluation.  Bound the cost directly
    over a realistically-populated registry: at the default 5 s period
    an evaluation must stay well under 50 ms (1% of one window) — the
    measured cost is ~1 ms, so the bound is 50x slack against box
    noise, and a regression that makes evaluation do real work (a
    per-event path, an O(series^2) scan) trips it immediately."""
    # populate the registry like a busy server: 200 counter series,
    # 40 histograms with observations
    for i in range(200):
        obs.counter("busy_total", backend=f"b{i % 8}",
                    reason=f"r{i}").inc(i)
    for i in range(40):
        h = obs.histogram("busy_seconds", shard=f"s{i}")
        for k in range(50):
            h.observe(0.001 * (k + 1))
    eng = slo.SloEngine(slo.default_slo_pack())
    eng.prime()
    obs.counter("async_updates_committed_total").inc(100)
    t0 = time.perf_counter()
    n = 20
    for _ in range(n):
        eng.evaluate()
    per_eval = (time.perf_counter() - t0) / n
    assert per_eval < 0.05, (
        f"SLO evaluation costs {per_eval * 1e3:.1f} ms — at the 5 s "
        f"default period that breaks the >= 0.99x overhead gate")


@pytest.mark.slow
def test_slo_engine_overhead_paired(clean_obs):
    """The e2e half of the overhead gate, PR-7's paired protocol
    (alternating order, median of per-pair ratios, warmup pair
    discarded): torture rate with the default pack evaluating at an
    AGGRESSIVE 0.25 s period vs SLO-off.  The CI-box tripwire gates at
    the DOCUMENTED arm-noise floor (>= 0.75 — these INPROC arms repeat
    at 0.75-2.7x on 2 cores under suite load, the PR-11 GIL spread, so
    any tighter gate here measures the box, not the evaluator; 0.99 is
    only resolvable on the chip-attached runtime — the same CI-vs-chip
    split PR 9 used for its 0.9x screen gate).  It exists to catch a
    GROSS regression (an accidental per-event hook would halve the
    rate); the deterministic per-evaluation cost bound above carries
    the tight 0.99x argument."""
    from fedml_tpu.async_.torture import run_ingest_torture

    def arm(with_slo: bool, tag: int) -> float:
        eng = None
        if with_slo:
            eng = slo.SloEngine(slo.default_slo_pack()).start(0.25)
        try:
            rep = run_ingest_torture(
                n_clients=4, backend="INPROC", p=262144, buffer_k=8,
                commits=16, warmup_commits=4, ingest_pool=2,
                decode_into=True, streaming=True)
            return rep["committed_updates_per_sec"]
        finally:
            if eng is not None:
                eng.stop()
                slo.install(None)
    arm(True, -1), arm(False, -1)            # discarded warmup pair
    ratios = []
    for pair in range(5):
        if pair % 2:
            on = arm(True, pair)
            off = arm(False, pair)
        else:
            off = arm(False, pair)
            on = arm(True, pair)
        ratios.append(on / off)
    med = sorted(ratios)[len(ratios) // 2]
    assert med >= 0.75, f"SLO-on/off paired ratios {ratios}"
