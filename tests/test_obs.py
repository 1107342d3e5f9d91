"""Observability-subsystem tests (fedml_tpu/obs: span tracer + metrics
registry + flight recorder).

Pinned invariants:

* registry thread-safety: concurrent increments/observations from many
  threads lose nothing (comm recv loops + prefetch workers + the round
  loop all write concurrently in production);
* the Chrome-trace exporter emits loadable trace-event JSON (ts/dur/ph/
  pid/tid complete events), with background-thread spans on their own
  tid rows of the SAME timeline;
* the flight recorder dumps on SIGUSR1 and on a round-deadline overrun,
  and the dump carries the ring + per-thread stacks + a metrics
  snapshot;
* observability on vs off is BITWISE result-neutral on the block-stream
  engine path (same discipline as tests/test_prefetch.py), while the
  enabled run leaves a loadable trace and a Prometheus snapshot behind;
* comm byte counters land per backend label (the inproc messaging sim).
"""
import glob
import json
import os
import signal
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu import obs
from fedml_tpu.obs.metrics import MetricsRegistry
from fedml_tpu.obs.tracer import SpanTracer

from parallel_case import _mnist_like_cfg, _setup


@pytest.fixture
def clean_obs():
    """Fresh disabled obs state around each test; restores the process
    SIGUSR1 disposition (configure() installs a dump handler)."""
    prev = signal.getsignal(signal.SIGUSR1)
    obs.reset()
    yield
    obs.reset()
    signal.signal(signal.SIGUSR1, prev)


# -- metrics registry --------------------------------------------------------

def test_registry_concurrent_increments_lose_nothing():
    reg = MetricsRegistry()
    c = reg.counter("hits_total", backend="test")
    h = reg.histogram("lat_seconds", buckets=(0.5, 1.0))
    g = reg.gauge("peak")
    N_THREADS, N_OPS = 8, 5000

    def work(i):
        for k in range(N_OPS):
            c.inc()
            h.observe(0.25 if k % 2 else 2.0)
            g.set_max(i * N_OPS + k)

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(N_THREADS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == N_THREADS * N_OPS
    assert h.count == N_THREADS * N_OPS
    cum = dict(h.cumulative())
    assert cum[0.5] == N_THREADS * N_OPS // 2          # the 0.25 half
    assert cum[float("inf")] == N_THREADS * N_OPS
    assert g.value == N_THREADS * N_OPS - 1            # max survived races


def test_registry_identity_and_kind_conflicts():
    reg = MetricsRegistry()
    a = reg.counter("x_total", backend="tcp")
    assert reg.counter("x_total", backend="tcp") is a      # get-or-create
    assert reg.counter("x_total", backend="grpc") is not a  # label split
    with pytest.raises(TypeError):
        reg.gauge("x_total", backend="tcp")                # kind conflict
    with pytest.raises(TypeError):
        # kind is per NAME (one # TYPE line per name): a different
        # label set cannot smuggle a second kind into the exposition
        reg.gauge("x_total", backend="mqtt")
    with pytest.raises(ValueError):
        a.inc(-1)                                          # counters go up
    h = reg.histogram("h_seconds", buckets=(1.0, 2.0))
    assert reg.histogram("h_seconds") is h                 # no-buckets ok
    with pytest.raises(ValueError):
        reg.histogram("h_seconds", buckets=(5.0,))         # bucket clash


def test_prometheus_text_and_json_snapshot():
    reg = MetricsRegistry()
    reg.counter("bytes_total", backend="inproc").inc(42)
    reg.histogram("wall_seconds", buckets=(1.0, 5.0)).observe(3.0)
    text = reg.to_prometheus()
    assert "# TYPE bytes_total counter" in text
    assert 'bytes_total{backend="inproc"} 42' in text
    assert 'wall_seconds_bucket{le="1.0"} 0' in text
    assert 'wall_seconds_bucket{le="+Inf"} 1' in text
    assert "wall_seconds_sum 3.0" in text
    snap = reg.snapshot()
    assert snap['bytes_total{backend="inproc"}'] == 42
    assert snap["wall_seconds"]["count"] == 1
    json.dumps(snap)                                   # JSON-able


# -- span tracer -------------------------------------------------------------

def test_chrome_trace_export_shape_and_nesting(tmp_path):
    tr = SpanTracer()
    with tr.span("outer", round=1):
        with tr.span("inner", phase="aggregate"):
            time.sleep(0.005)
    tr.instant("marker", note="x")
    path = tr.export_chrome(str(tmp_path / "trace.json"))
    doc = json.load(open(path))
    events = doc["traceEvents"]
    by_name = {e["name"]: e for e in events if e.get("ph") in ("X", "i")}
    for name in ("outer", "inner", "marker"):
        assert name in by_name
    for e in (by_name["outer"], by_name["inner"]):
        assert e["ph"] == "X"
        for key in ("ts", "dur", "pid", "tid"):       # loadable shape
            assert isinstance(e[key], (int, float))
    # nesting: inner contained in outer on the same tid
    o, i = by_name["outer"], by_name["inner"]
    assert o["tid"] == i["tid"]
    assert o["ts"] <= i["ts"]
    assert i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1e-3
    assert i["args"] == {"phase": "aggregate"}
    # jsonl twin: a __meta__ header line (pid/epoch for the timeline
    # merge tool), then one object per event
    jl = tr.export_jsonl(str(tmp_path / "trace.jsonl"))
    lines = [json.loads(ln) for ln in open(jl)]
    assert len(lines) == 4
    meta = lines[0]["__meta__"]
    assert meta["pid"] == os.getpid()
    assert meta["dropped_events"] == 0
    assert abs(meta["epoch_unix"] - time.time()) < 60


def test_tracer_background_thread_lands_on_same_timeline(tmp_path):
    """The prefetch requirement: spans produced on a worker thread share
    the tracer's epoch — they interleave with the main thread's spans
    on the one timeline, on a distinct tid row."""
    tr = SpanTracer()

    def work():
        with tr.span("bg.upload"):
            time.sleep(0.002)

    with tr.span("fg.round"):
        t = threading.Thread(target=work, name="h2d-test")
        t.start()
        t.join()
    ev = {e["name"]: e for e in tr.events()}
    assert ev["bg.upload"]["tid"] != ev["fg.round"]["tid"]
    fg, bg = ev["fg.round"], ev["bg.upload"]
    assert fg["ts"] <= bg["ts"] <= fg["ts"] + fg["dur"]   # same epoch


def test_tracer_ring_bound_counts_drops():
    tr = SpanTracer(max_events=10)
    for i in range(25):
        with tr.span(f"s{i}"):
            pass
    assert len(tr.events()) == 10
    assert tr.dropped == 15
    assert tr.events()[-1]["name"] == "s24"            # newest retained


def test_span_disabled_records_nothing(clean_obs):
    """With no obs.configure() and no profiler session a span is only a
    profiler annotation that nobody listens to: no tracer exists, nothing
    is buffered, any attribute value is accepted."""
    s1, s2 = obs.span("a", x=1, what={"k": None}), obs.span("b")
    with s1:
        with s2:
            pass
    assert obs.tracer() is None and not obs.enabled()
    assert obs.rollup()["spans_recorded"] == 0


# -- flight recorder ---------------------------------------------------------

def test_flight_dump_on_deadline_overrun(clean_obs, tmp_path):
    """Simulated round-deadline overrun: the watchdog fires mid-block,
    dumping ring + stacks while the 'round' is still stuck."""
    obs.configure(str(tmp_path), install_signal=False)
    with obs.span("round", round=3):
        with obs.deadline("round3", 0.05):
            time.sleep(0.4)               # the overrunning round
    dumps = glob.glob(str(tmp_path / "flight-*.json"))
    assert len(dumps) == 1
    doc = json.load(open(dumps[0]))
    assert doc["reason"] == "deadline_overrun:round3"
    assert doc["thread_stacks"]           # per-thread Python stacks
    assert any("time.sleep" in "".join(fr) or "test_obs" in "".join(fr)
               for fr in doc["thread_stacks"].values())
    assert "metrics" in doc               # snapshot rides along


def test_flight_deadline_cancelled_when_round_finishes(clean_obs,
                                                       tmp_path):
    obs.configure(str(tmp_path), install_signal=False)
    with obs.deadline("fast", 5.0):
        pass                              # well under deadline
    time.sleep(0.05)
    assert not glob.glob(str(tmp_path / "flight-*.json"))


def test_flight_dump_on_sigusr1(clean_obs, tmp_path):
    """kill -USR1 <pid> (what an operator sends to a stuck run)
    produces a dump with the recent event ring."""
    obs.configure(str(tmp_path))          # installs the handler
    with obs.span("round.blockstream", round=7):
        pass
    os.kill(os.getpid(), signal.SIGUSR1)
    deadline = time.monotonic() + 5.0
    dumps = []
    while time.monotonic() < deadline and not dumps:
        dumps = glob.glob(str(tmp_path / "flight-*.json"))
        time.sleep(0.01)
    assert dumps, "SIGUSR1 produced no flight dump"
    doc = json.load(open(dumps[0]))
    assert doc["reason"] == "SIGUSR1"
    assert any(e.get("name") == "round.blockstream"
               for e in doc["events"])


def test_engine_error_dumps_flight(clean_obs, tmp_path):
    """An unhandled error inside the run loop leaves a dump behind
    before propagating."""
    from fedml_tpu.algorithms import FedAvgEngine
    cfg = _mnist_like_cfg(comm_round=2)
    trainer, data = _setup(cfg)
    eng = FedAvgEngine(trainer, data, cfg, donate=False)
    obs.configure(str(tmp_path), install_signal=False)

    def boom(*a, **kw):
        raise RuntimeError("round exploded")

    eng.round_fn = boom
    with pytest.raises(RuntimeError, match="round exploded"):
        eng.run(rounds=1)
    dumps = glob.glob(str(tmp_path / "flight-*.json"))
    assert len(dumps) == 1
    assert "engine_error" in json.load(open(dumps[0]))["reason"]


# -- obs on/off result parity + artifact acceptance --------------------------

def _assert_trees_bitwise(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_blockstream_bitwise_obs_on_vs_off(clean_obs, tmp_path):
    """Acceptance pin: the block-stream round under --obs_dir, and under
    a profiler session, produces BITWISE the variables of the run with
    neither (spans/counters are pure host bookkeeping), the profiler's
    trace holds the program spans, and the enabled run exports a loadable
    Chrome trace whose upload spans sit on the prefetch worker's tid,
    plus a Prometheus snapshot carrying the engine walls."""
    from fedml_tpu.parallel import MeshFedAvgEngine
    from fedml_tpu.parallel.mesh import make_mesh
    cfg = _mnist_like_cfg(client_num_per_round=12, comm_round=2)
    trainer, data = _setup(cfg)
    ref = MeshFedAvgEngine(trainer, data, cfg, mesh=make_mesh(8),
                           donate=False, stream_block=8)
    v0 = ref.init_variables()
    v_off = ref.run(variables=jax.tree.map(jnp.copy, v0), rounds=2)

    # a profiler session alone (no obs.configure()): the same spans land in
    # /host:CPU of the profiler's trace, each with the round it belongs to,
    # the uploads on the prefetch worker's line — and the bits do not move
    prof = MeshFedAvgEngine(trainer, data, cfg, mesh=make_mesh(8),
                            donate=False, stream_block=8)
    jax.profiler.start_trace(str(tmp_path / "prof"))
    try:
        v_prof = jax.block_until_ready(
            prof.run(variables=jax.tree.map(jnp.copy, v0), rounds=2))
    finally:
        jax.profiler.stop_trace()
    _assert_trees_bitwise(v_off, v_prof)
    assert obs.tracer() is None
    xplane = glob.glob(str(tmp_path / "prof" / "**" / "*.xplane.pb"),
                       recursive=True)[0]
    host = next(p for p in jax.profiler.ProfileData.from_file(xplane).planes
                if p.name == "/host:CPU")
    seen = {}
    for line in host.lines:
        for e in line.events:
            if e.name.startswith(("h2d.", "round.")):
                seen.setdefault(e.name, []).append(dict(e.stats))
    assert {"round.blockstream", "round.block_step", "round.sample",
            "h2d.upload_block", "h2d.gather", "h2d.put",
            "h2d.wait"} <= set(seen)
    for name in ("h2d.upload_block", "h2d.gather", "h2d.put", "h2d.wait",
                 "round.sample", "round.blockstream"):
        assert {st["round"] for st in seen[name]} == {0, 1}, name

    obs.configure(str(tmp_path), install_signal=False)
    eng = MeshFedAvgEngine(trainer, data, cfg, mesh=make_mesh(8),
                           donate=False, stream_block=8)
    v_on = eng.run(variables=jax.tree.map(jnp.copy, v0), rounds=2)
    _assert_trees_bitwise(v_off, v_on)

    paths = obs.export()
    doc = json.load(open(paths["chrome_trace"]))       # loadable
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    names = {e["name"] for e in spans}
    assert {"round", "round.blockstream", "round.block_step",
            "h2d.upload_block"} <= names
    # prefetch uploads ran on a background thread, same timeline
    rnd = next(e for e in spans if e["name"] == "round.blockstream")
    ups = [e for e in spans if e["name"] == "h2d.upload_block"]
    assert any(u["tid"] != rnd["tid"] for u in ups)
    prom = open(paths["prometheus"]).read()
    assert "engine_round_wall_seconds_count" in prom
    assert "engine_upload_wall_seconds_total" in prom
    # metrics are always-on: ALL THREE runs' rounds landed in the registry
    line = next(ln for ln in prom.splitlines()
                if ln.startswith("engine_rounds_total"))
    assert float(line.split()[-1]) == 6.0, line


def test_messaging_comm_counters_per_backend(clean_obs, tmp_path):
    """The acceptance snapshot: after an inproc messaging-FedAvg run,
    the Prometheus text carries non-zero comm byte counters labeled
    with the active backend."""
    from fedml_tpu.comm.fedavg_messaging import run_messaging_fedavg
    cfg = _mnist_like_cfg(client_num_in_total=4, client_num_per_round=2,
                          comm_round=1)
    trainer, data = _setup(cfg)
    obs.configure(str(tmp_path), install_signal=False)
    run_messaging_fedavg(trainer, data, cfg, worker_num=2)
    prom = obs.registry().to_prometheus()
    for name in ("comm_sent_bytes_total", "comm_received_bytes_total"):
        line = next(ln for ln in prom.splitlines()
                    if ln.startswith(f'{name}{{backend="inproc"}}'))
        assert float(line.split()[-1]) > 0, line
    # model-exchange FSM spans landed on the trace too
    names = {e["name"] for e in obs.tracer().events()}
    assert "comm.send" in names and "comm.handle" in names


def test_cli_obs_dir_writes_artifacts(tmp_path, clean_obs):
    """--obs_dir through the launcher: the run leaves trace + metrics
    artifacts (the operator-facing contract README documents)."""
    from fedml_tpu.cli import main
    obs_dir = tmp_path / "obs"
    rc = main(["--algorithm", "fedavg", "--dataset", "mnist", "--model",
               "lr", "--synthetic_scale", "0.001",
               "--client_num_in_total", "4", "--client_num_per_round",
               "4", "--comm_round", "2", "--batch_size", "4",
               "--frequency_of_the_test", "1",
               "--run_dir", str(tmp_path / "runs"),
               "--obs_dir", str(obs_dir)])
    assert rc == 0
    doc = json.load(open(obs_dir / "trace.chrome.json"))
    assert any(e.get("name") == "round" for e in doc["traceEvents"])
    assert "jit_compile_total" in open(obs_dir / "metrics.prom").read()
    json.load(open(obs_dir / "metrics.json"))


def test_ingest_instruments_and_spans(clean_obs, tmp_path):
    """ISSUE-6 instruments: a torture run under an enabled tracer lands
    comm_decode_seconds observations (the decode-bucket ladder that
    resolves sub-ms frames), the async_ingest_pool_depth gauge (back to
    0 once the pool drains), the async_lock_wait_seconds counter, and
    ingest.* spans in the exported trace — so the flight recorder can
    show an ingestion stall."""
    obs.configure(str(tmp_path))
    from fedml_tpu.async_ import run_ingest_torture
    r = run_ingest_torture(n_clients=2, backend="INPROC", p=256,
                           buffer_k=2, commits=3, warmup_commits=1,
                           ingest_pool=2, decode_into=True,
                           streaming=True, timeout_s=60)
    assert r["finite"]
    h = obs.histogram("comm_decode_seconds",
                      buckets=obs.metrics.DECODE_SECONDS_BUCKETS,
                      backend="inproc")
    cum = h.cumulative()
    assert cum[-1][1] > 0                       # decodes observed
    # the sub-ms ladder actually resolves: for 1 KiB inproc frames at
    # least one observation lands below the default ladder's 1 ms floor
    assert any(le < 0.001 and c > 0 for le, c in cum)
    assert obs.gauge("async_ingest_pool_depth").value == 0
    assert obs.counter("async_lock_wait_seconds").value >= 0.0
    paths = obs.export()
    events = json.load(open(paths["chrome_trace"]))["traceEvents"]
    names = {e["name"] for e in events}
    assert "ingest.torture" in names
    assert "ingest.decode" in names and "ingest.fold" in names


# -- ISSUE 7: mergeable telemetry --------------------------------------------

def _toy_registry(c=0.0, g=0.0, obs_vals=()):
    reg = MetricsRegistry()
    if c:
        reg.counter("t_total", backend="x").inc(c)
    if g:
        reg.gauge("t_peak").set(g)
    for v in obs_vals:
        reg.histogram("t_seconds", buckets=(0.5, 1.0, 2.0)).observe(v)
    return reg


def _merged(*deltas):
    reg = MetricsRegistry()
    for d in deltas:
        reg.merge_delta(d, origin="remote")
    return reg.snapshot()


def test_registry_merge_laws():
    """The merge protocol's algebra (ISSUE 7): counters add, gauges
    max, histograms bucket-wise add — so the fold is commutative and
    associative (uplink arrival order cannot change the rollup) and an
    empty delta is the identity."""
    da, _ = _toy_registry(c=3, g=5.0, obs_vals=(0.25, 1.5)).delta_snapshot()
    db, _ = _toy_registry(c=4, g=2.0, obs_vals=(0.75,)).delta_snapshot()
    dc, _ = _toy_registry(c=1, g=9.0, obs_vals=(3.0,)).delta_snapshot()
    # commutative
    assert _merged(da, db) == _merged(db, da)
    # associative: (a+b)+c == a+(b+c) — re-export the partial fold as a
    # delta (include_merged=True: the hierarchical-aggregator path) and
    # fold the remaining one in, both groupings
    ab_reg = MetricsRegistry()
    ab_reg.merge_delta(da, origin="remote")
    ab_reg.merge_delta(db, origin="remote")
    ab, _ = ab_reg.delta_snapshot(include_merged=True)
    bc_reg = MetricsRegistry()
    bc_reg.merge_delta(db, origin="remote")
    bc_reg.merge_delta(dc, origin="remote")
    bc, _ = bc_reg.delta_snapshot(include_merged=True)
    assert _merged(ab, dc) == _merged(da, bc) == _merged(da, db, dc)
    # echo-loop guard: by DEFAULT a fold is never re-shipped — a shared
    # in-process registry (sim: client and server ranks share one) must
    # not ship the server's own rollup back as "client" telemetry
    echo, _ = ab_reg.delta_snapshot()
    assert echo["metrics"] == []
    # identity: the empty delta changes nothing (idempotent fold)
    empty, _ = MetricsRegistry().delta_snapshot()
    assert empty["metrics"] == []
    assert _merged(da, empty) == _merged(da)
    # the merged values are what the semantics promise
    snap = _merged(da, db, dc)
    assert snap['t_total{backend="x",origin="remote"}'] == 8.0
    assert snap['t_peak{origin="remote"}'] == 9.0          # max, not last
    assert snap['t_seconds{origin="remote"}']["count"] == 4


def test_registry_delta_is_compact_and_windowed():
    """delta_snapshot ships only what MOVED since the baseline — an
    idle client's uplink carries an empty metrics block."""
    reg = MetricsRegistry()
    c = reg.counter("moves_total")
    h = reg.histogram("h_seconds", buckets=(1.0,))
    c.inc(2)
    h.observe(0.5)
    d1, state = reg.delta_snapshot()
    assert {e["name"] for e in d1["metrics"]} == {"moves_total",
                                                 "h_seconds"}
    d2, state = reg.delta_snapshot(state)
    assert d2["metrics"] == []                 # nothing moved
    c.inc(5)
    d3, state = reg.delta_snapshot(state)
    assert d3["metrics"] == [{"name": "moves_total", "labels": {},
                              "kind": "counter", "value": 5.0}]
    # histogram deltas are window counts, not cumulative re-ships
    h.observe(3.0)
    d4, _ = reg.delta_snapshot(state)
    (entry,) = d4["metrics"]
    assert entry["count"] == 1 and entry["sum"] == 3.0


def _legacy_quantile(before, after, q):
    """The exact PR-6 hand-rolled torture implementation, kept here as
    the bitwise pin for the deduped obs.metrics.quantile_from_cumulative
    (and Histogram.quantile) — same numbers, to the bit."""
    deltas = [(le, a - b) for (le, a), (_, b) in zip(after, before)]
    total = deltas[-1][1]
    if total <= 0:
        return 0.0
    target = q * total
    prev_le, prev_c = 0.0, 0
    for le, c in deltas:
        if c >= target:
            if le == float("inf"):
                return prev_le
            span = c - prev_c
            frac = (target - prev_c) / span if span > 0 else 1.0
            return prev_le + frac * (le - prev_le)
        prev_le, prev_c = (0.0 if le == float("inf") else le), c
    return prev_le


def test_histogram_quantile_matches_legacy_torture_math():
    from fedml_tpu.obs.metrics import quantile_from_cumulative
    reg = MetricsRegistry()
    h = reg.histogram("q_seconds", buckets=(0.001, 0.01, 0.1, 1.0))
    rs = np.random.RandomState(7)
    before = h.cumulative()
    for v in rs.lognormal(-4.0, 2.0, size=500):
        h.observe(float(v))
    after = h.cumulative()
    for q in (0.0, 0.25, 0.5, 0.9, 0.95, 0.99, 1.0):
        assert (quantile_from_cumulative(before, after, q)
                == _legacy_quantile(before, after, q))      # bitwise
        assert h.quantile(q, since=before) == _legacy_quantile(
            before, after, q)
    # all-time quantile == since-empty window
    assert h.quantile(0.5) == quantile_from_cumulative(None, after, 0.5)
    # empty window stays 0.0, not NaN
    assert h.quantile(0.95, since=after) == 0.0


# -- ISSUE 12: histogram edge cases the SLO evaluator leans on ---------------

def test_quantile_empty_delta_window_and_extremes():
    """The SLO engine's quantile_max spec evaluates windowed deltas: an
    EMPTY window (no new observations) must read 0.0 — never NaN, never
    a stale all-time value — and q=0.0/1.0 must stay inside the bucket
    ladder at both extremes."""
    from fedml_tpu.obs.metrics import quantile_from_cumulative
    reg = MetricsRegistry()
    h = reg.histogram("edge_seconds", buckets=(0.01, 0.1, 1.0))
    snap0 = h.cumulative()
    # empty delta: before == after (both all-zero and mid-run)
    assert quantile_from_cumulative(snap0, snap0, 0.95) == 0.0
    h.observe(0.05)
    h.observe(0.5)
    snap1 = h.cumulative()
    assert quantile_from_cumulative(snap1, snap1, 0.5) == 0.0
    # q extremes on a populated window: 0.0 sits at the window's floor
    # (the first populated bucket's lower edge, interpolated from 0),
    # 1.0 at its populated ceiling — both finite, ordered, in-ladder
    q0 = quantile_from_cumulative(snap0, snap1, 0.0)
    q1 = quantile_from_cumulative(snap0, snap1, 1.0)
    assert 0.0 <= q0 <= q1 <= 1.0
    assert q1 >= 0.1                 # the 0.5 observation's bucket


def test_quantile_single_bucket_ladder():
    """A one-bucket ladder (everything <= le or overflow) still
    interpolates sanely: in-bucket mass reads inside [0, le], overflow
    mass clamps to the last finite edge (the +Inf bucket has no upper
    edge to interpolate toward)."""
    from fedml_tpu.obs.metrics import quantile_from_cumulative
    reg = MetricsRegistry()
    h = reg.histogram("one_bucket_seconds", buckets=(1.0,))
    before = h.cumulative()
    for _ in range(10):
        h.observe(0.25)
    after = h.cumulative()
    q = quantile_from_cumulative(before, after, 0.5)
    assert 0.0 <= q <= 1.0
    # overflow-only window: every observation past the ladder
    before = after
    for _ in range(10):
        h.observe(5.0)
    after = h.cumulative()
    assert quantile_from_cumulative(before, after, 0.95) == 1.0


def test_quantile_merge_law():
    """merge_counts then quantile == quantile of the union: the
    federation's rollup (merge_delta is bucket-wise add) must report
    the same percentiles as one registry that saw every observation —
    the law the SLO evaluator's cross-series merge relies on."""
    from fedml_tpu.obs.metrics import quantile_from_cumulative
    buckets = (0.001, 0.01, 0.1, 1.0)
    reg = MetricsRegistry()
    ha = reg.histogram("m_seconds", side="a", buckets=buckets)
    hb = reg.histogram("m_seconds", side="b", buckets=buckets)
    hu = reg.histogram("m_seconds", side="union", buckets=buckets)
    rs = np.random.RandomState(3)
    xs = rs.lognormal(-3.0, 1.5, size=400)
    for i, v in enumerate(xs):
        (ha if i % 2 else hb).observe(float(v))
        hu.observe(float(v))
    counts, vsum, vcount = hb.raw_state()
    ha.merge_counts(counts, vsum, vcount)
    for q in (0.0, 0.5, 0.95, 1.0):
        assert ha.quantile(q) == hu.quantile(q)      # bitwise
    # and a ladder-mismatched merge refuses loudly
    with pytest.raises(ValueError):
        ha.merge_counts([0, 0], 0.0, 0)


# -- ISSUE 7: tracer spill + digest ------------------------------------------

def test_tracer_spill_keeps_head_ring_keeps_tail(tmp_path):
    """Satellite: a tiny ring drops the head, but the spill JSONL keeps
    it (up to the byte cap) — together nothing is lost, and the drop /
    spill accounting is surfaced in the export meta."""
    spill = str(tmp_path / "spill.jsonl")
    tr = SpanTracer(max_events=5, spill_path=spill)
    for i in range(20):
        with tr.span(f"s{i}"):
            pass
    assert tr.dropped == 15 and tr.spilled == 20
    names = [json.loads(ln)["name"] for ln in open(spill)]
    assert names[:5] == ["s0", "s1", "s2", "s3", "s4"]      # head kept
    assert len(names) == 20
    jl = tr.export_jsonl(str(tmp_path / "t.jsonl"))
    meta = json.loads(open(jl).readline())["__meta__"]
    assert meta["dropped_events"] == 15
    assert meta["spilled_events"] == 20 and meta["spill_truncated"] == 0
    tr.close()


def test_tracer_spill_cap_counts_truncation(tmp_path):
    tr = SpanTracer(max_events=100,
                    spill_path=str(tmp_path / "s.jsonl"),
                    spill_limit_bytes=300)
    for i in range(50):
        tr.instant(f"e{i}")
    assert tr.spill_truncated > 0
    assert tr.spilled + tr.spill_truncated == 50
    # the cap bounds the file: nothing written past it
    assert os.path.getsize(tmp_path / "s.jsonl") <= 300 + 200
    tr.close()


def test_tracer_digest_aggregates_without_walking_the_ring():
    tr = SpanTracer(max_events=4)          # evictions must not lose agg
    for _ in range(10):
        with tr.span("hot"):
            pass
    with tr.span("cold"):
        time.sleep(0.002)
    d = tr.digest(top=8)
    assert d["hot"][0] == 10
    assert d["cold"][0] == 1 and d["cold"][1] >= 1000      # >= 1ms in us
    assert list(d) == sorted(d, key=lambda k: -d[k][1])    # by total


def test_rollup_surfaces_drops(clean_obs, tmp_path):
    obs.configure(str(tmp_path), install_signal=False,
                  export_at_exit=False, max_events=3)
    for i in range(9):
        with obs.span(f"r{i}"):
            pass
    ru = obs.rollup()
    assert ru["spans_dropped"] == 6
    assert ru["spans_recorded"] == 9


# -- ISSUE 7: http introspection endpoint ------------------------------------

def test_http_endpoint_metrics_rollup_flight(clean_obs, tmp_path):
    import urllib.request
    obs.configure(str(tmp_path), install_signal=False,
                  export_at_exit=False)
    obs.counter("http_hits_total", backend="t").inc(3)
    srv = obs.serve_http(0)
    assert srv is obs.serve_http(0)            # idempotent singleton
    base = f"http://127.0.0.1:{srv.port}"
    prom = urllib.request.urlopen(f"{base}/metrics").read().decode()
    assert 'http_hits_total{backend="t"} 3' in prom
    ru = json.loads(urllib.request.urlopen(f"{base}/rollup").read())
    assert ru["http_port"] == srv.port
    # ISSUE 12: GET /flight is READ-ONLY (a scraper or browser prefetch
    # must never trigger dumps) — the dump trigger moved to POST
    fl = json.loads(urllib.request.urlopen(f"{base}/flight").read())
    assert fl["last_dump"] is None and fl["dumps"] == 0
    assert not glob.glob(str(tmp_path / "flight-*.json"))
    fl = json.loads(urllib.request.urlopen(
        urllib.request.Request(f"{base}/flight", method="POST"),
        data=b"").read())
    assert fl["dump"] and os.path.exists(fl["dump"])       # dump trigger
    assert json.load(open(fl["dump"]))["reason"] == "http_trigger"
    # and the GET now reports that dump without adding another
    fl2 = json.loads(urllib.request.urlopen(f"{base}/flight").read())
    assert fl2["last_dump"] == fl["dump"] and fl2["dumps"] == 1
    try:
        urllib.request.urlopen(f"{base}/nope")
        assert False, "unknown path must 404"
    except urllib.error.HTTPError as e:
        assert e.code == 404
    # clean_obs reset() closes the server; verify it actually dies
    obs.reset()
    try:
        urllib.request.urlopen(f"{base}/metrics", timeout=2)
        assert False, "server survived reset()"
    except Exception:
        pass


# -- ISSUE 7: trace propagation ----------------------------------------------

def test_trace_block_propagates_and_aligns_clocks(clean_obs, tmp_path):
    """Stamped frames carry rank/timestamps/digest + the clock echo;
    the receiver strips the block before the FSM sees it, estimates the
    peer offset (≈0 in-process), and records the trace.recv instant
    with the shipped digest."""
    from fedml_tpu.comm.inproc import InProcBackend, InProcRouter
    from fedml_tpu.comm.message import Message
    from fedml_tpu.obs import propagate
    obs.configure(str(tmp_path), install_signal=False,
                  export_at_exit=False)
    router = InProcRouter()
    a, b = InProcBackend(0, router), InProcBackend(1, router)
    got = []
    b._on_message = lambda m: got.append(m)
    a._on_message = lambda m: got.append(m)
    with obs.span("warm"):
        pass
    a.send_message(Message(1, 0, 1))
    b.send_message(Message(1, 1, 0))           # echo direction
    a.send_message(Message(1, 0, 1))           # now carries the echo
    assert len(got) == 3
    assert all(propagate.TRACE_KEY not in m.msg_params for m in got)
    assert obs.counter("trace_frames_total",
                       backend="inproc").value == 3
    recvs = [e for e in obs.tracer().events()
             if e["name"] == "trace.recv"]
    assert len(recvs) == 3
    assert recvs[0]["args"]["peer"] == 0
    assert "warm" in recvs[0]["args"]["digest"]            # shipped spans
    # same process, same clock: the symmetric estimate lands near zero
    offs = b._clock.offsets()
    assert 0 in offs and abs(offs[0]) < 0.5
    # exported for the timeline tool
    paths = obs.export()
    clocks = json.load(open(paths["clock_offsets"]))
    assert any(c["rank"] == 0 and "1" in c["offsets_s"] for c in clocks)


def test_metrics_delta_piggyback_folds_as_cohort(clean_obs, tmp_path):
    """An uplink's __fedml_metrics__ delta folds into the receiving
    registry under origin="remote" — ONE label set regardless of how
    many peers ship (the million-client memory constraint)."""
    from fedml_tpu.comm.inproc import InProcBackend, InProcRouter
    from fedml_tpu.comm.message import Message
    from fedml_tpu.obs import propagate
    obs.configure(str(tmp_path), install_signal=False,
                  export_at_exit=False)
    router = InProcRouter()
    a, b = InProcBackend(0, router), InProcBackend(1, router)
    b._on_message = lambda m: None
    for sender_rank in (3, 4):                 # two "clients", one label
        reg = MetricsRegistry()
        reg.counter("client_steps_total").inc(7)
        delta, _ = reg.delta_snapshot()
        m = Message(1, sender_rank, 1)
        m.add_params(propagate.METRICS_KEY, delta)
        a.send_message(m)
    folded = obs.counter("client_steps_total", origin="remote")
    assert folded.value == 14                  # cohort rollup, summed
    keys = [k for k in obs.registry().snapshot()
            if k.startswith("client_steps_total")]
    assert len(keys) == 1                      # no per-client labels


def test_obs_disabled_send_receive_adds_nothing(clean_obs):
    """With obs disabled, stamp/note are no-ops: no trace params appear
    and no spans/instants are recorded (frame byte-identity is pinned
    in test_wire_codec.py)."""
    from fedml_tpu.comm.inproc import InProcBackend, InProcRouter
    from fedml_tpu.comm.message import Message
    from fedml_tpu.obs import propagate
    router = InProcRouter()
    a, b = InProcBackend(0, router), InProcBackend(1, router)
    got = []
    b._on_message = lambda m: got.append(m)
    a.send_message(Message(1, 0, 1))
    assert propagate.TRACE_KEY not in got[0].msg_params
    assert obs.tracer() is None
    assert obs.counter("trace_frames_total", backend="inproc").value == 0


# -- ISSUE 7: round critical-path analyzer -----------------------------------

def _mk_span(name, ts_ms, dur_ms, tid=1, **args):
    return {"name": name, "ph": "X", "ts": ts_ms * 1000.0,
            "dur": dur_ms * 1000.0, "pid": 1, "tid": tid, "args": args}


def test_critical_path_stage_claims_and_wait_residual():
    """Synthetic two-round async trace: nesting attributes to the most
    specific stage, the unclaimed remainder books as wait, and stage
    sums equal round walls exactly (the acceptance's <=10% bound is met
    by construction)."""
    from fedml_tpu.obs import timeline
    events = [
        # round 0: train 0-40, decode 45-50 nested in fold 45-55,
        # commit 55-60 -> wait = 60 - 40 - 10 - 5 - 5
        _mk_span("async.wave", 0, 40, wave=0),
        _mk_span("ingest.fold", 45, 10, tid=2),
        _mk_span("ingest.decode", 45, 5, tid=3),
        _mk_span("async.commit", 55, 5, version=0),
        # round 1: two CONCURRENT decodes (union, not sum), commit
        _mk_span("ingest.decode", 70, 10, tid=2),
        _mk_span("ingest.decode", 75, 10, tid=3),
        _mk_span("async.commit", 90, 10, version=1),
    ]
    rep = timeline.critical_path(events)
    assert rep["n_rounds"] == 2
    r0, r1 = rep["rounds"]
    assert r0["round"] == 0 and r1["round"] == 1
    s0 = r0["stages"]
    assert abs(s0["train"] - 0.040) < 1e-9
    assert abs(s0["decode"] - 0.005) < 1e-9        # nested: decode wins
    assert abs(s0["fold"] - 0.005) < 1e-9          # fold keeps the rest
    assert abs(s0["commit"] - 0.005) < 1e-9
    assert abs(s0["wait"] - 0.005) < 1e-9
    s1 = r1["stages"]
    assert abs(s1["decode"] - 0.015) < 1e-9        # union of overlap
    for r in rep["rounds"]:
        assert abs(sum(r["stages"].values()) - r["wall_s"]) < 1e-9
    assert rep["p95_attribution"]["stage"] in ("train", "wait")


def test_critical_path_sync_round_spans():
    from fedml_tpu.obs import timeline
    events = [
        _mk_span("round", 0, 100, round=0),
        _mk_span("round.block_step", 10, 80, tid=2),
        _mk_span("round", 100, 50, round=1),
    ]
    rep = timeline.critical_path(events)
    assert rep["n_rounds"] == 2
    assert rep["rounds"][0]["stages"]["train"] == 0.08
    assert rep["rounds"][0]["dominant"] == "train"


def test_timeline_merge_rebases_processes_onto_one_clock(tmp_path):
    """Two processes' jsonl exports (distinct epochs) merge onto the
    unix clock; the clock-offset correction shifts the peer."""
    from fedml_tpu.obs import timeline
    ja, jb = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    with open(ja, "w") as f:
        f.write(json.dumps({"__meta__": {"pid": 1,
                                         "epoch_unix": 1000.0}}) + "\n")
        f.write(json.dumps(_mk_span("async.commit", 0, 10,
                                    version=0)) + "\n")
    with open(jb, "w") as f:
        f.write(json.dumps({"__meta__": {"pid": 2,
                                         "epoch_unix": 999.0}}) + "\n")
        f.write(json.dumps(_mk_span("async.local_train", 500, 400,
                                    tid=9)) + "\n")
    (ma, ea), (mb, eb) = (timeline.load_trace_jsonl(ja),
                          timeline.load_trace_jsonl(jb))
    merged = timeline.merge_traces([(ma, ea, 0.0), (mb, eb, 0.5)])
    by = {e["name"]: e for e in merged}
    # a's commit at unix 1000.000s; b's train at 999 + 0.5 + 0.5 = 1000s
    assert abs(by["async.commit"]["ts"] - 1000.0 * 1e6) < 1
    assert abs(by["async.local_train"]["ts"] - 1000.0 * 1e6) < 1
