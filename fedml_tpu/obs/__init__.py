"""Unified observability: span tracer + metrics registry + flight recorder.

The process-global facade every layer instruments against:

  with obs.span("round.blockstream", round=r): ...      # tracing
  obs.counter("comm_sent_bytes_total", backend="tcp").inc(n)   # metrics
  with obs.deadline("round3", 120): ...                 # hang watchdog
  kill -USR1 <pid>                                      # flight dump

Two tiers, by cost:

* **Metrics are always on.**  A counter increment is one lock + one
  float add; comm backends, the prefetch pipeline, and jax compile
  events write through unconditionally so a later `obs.configure()`
  (or a test poking `obs.registry()`) sees history, not a cold start.
* **Tracing/flight-recording is opt-in** via `configure(obs_dir)` (the
  CLI's `--obs_dir`, or the FEDML_OBS_DIR env var for tools/workers).
  Until then nothing is buffered.
* **`span()` always annotates.**  Every span enters a
  `jax.profiler.TraceAnnotation` of the same name and attributes: with
  a profiler session active (`jax.profiler.start_trace`, the CLI's
  `--profile_dir`, the benchmark's `--trace 1`) it lands in `/host:CPU`
  of the same `.xplane.pb` as the device ops, on the profiler's clock,
  from whichever thread opened it; with no session the annotation is a
  flag check and records nothing.  The `SpanTracer` keeps its own
  `perf_counter` epoch (obs/tracer.py) — the device trace never sees
  that clock, which is why the annotation exists.

`configure()` also installs the SIGUSR1 flight-dump handler (main
thread only) and an atexit export, so any obs-enabled run leaves a
loadable Chrome trace + Prometheus snapshot behind even if nobody
called `export()` explicitly.  Everything here is pure-host and never
touches values inside jit — results are bitwise identical with
observability on or off (pinned by tests/test_obs.py).
"""
from __future__ import annotations

import atexit
import contextlib
import json
import os
import signal
import threading
import time
from typing import Iterator, Optional

from jax.profiler import TraceAnnotation

from fedml_tpu.obs.flight import FlightRecorder, thread_stacks
from fedml_tpu.obs.metrics import (Counter, Gauge, Histogram,
                                   MetricsRegistry)
from fedml_tpu.obs.tracer import SpanTracer

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "SpanTracer",
    "FlightRecorder", "configure", "configure_from_env", "enabled",
    "obs_dir", "span", "instant", "counter", "gauge", "histogram",
    "registry", "tracer", "flight", "deadline", "dump_flight", "export",
    "sample_device_memory", "reset", "serve_http", "http_server",
]

ENV_VAR = "FEDML_OBS_DIR"
ENV_HTTP = "FEDML_OBS_HTTP_PORT"
ENV_SPILL = "FEDML_OBS_SPILL"

_lock = threading.Lock()
_registry = MetricsRegistry()
_tracer: Optional[SpanTracer] = None
_flight: Optional[FlightRecorder] = None
_dir: Optional[str] = None
_http = None
_prev_sigusr1 = None
_atexit_registered = False


# -- lifecycle ---------------------------------------------------------------

def enabled() -> bool:
    return _dir is not None


def obs_dir() -> Optional[str]:
    return _dir


def configure(directory: str, *, flight_capacity: int = 4096,
              max_events: int = 200_000, install_signal: bool = True,
              export_at_exit: bool = True,
              spill_events: Optional[bool] = None,
              http_port: Optional[int] = None) -> None:
    """Enable tracing + flight recording, writing artifacts under
    `directory`.  Idempotent-ish: reconfiguring swaps in a fresh tracer
    and ring (old events already exported stay on disk).

    `spill_events` (or FEDML_OBS_SPILL=1) streams every span to
    `directory`/trace.spill.jsonl up to a byte cap — long async runs
    keep the trace head the ring would evict.  `http_port` (or
    FEDML_OBS_HTTP_PORT) starts the loopback introspection endpoint
    (/metrics, /rollup, /flight — fedml_tpu/obs/httpd.py)."""
    global _tracer, _flight, _dir, _atexit_registered
    os.makedirs(directory, exist_ok=True)
    if spill_events is None:
        spill_events = os.environ.get(ENV_SPILL, "") not in ("", "0")
    with _lock:
        old = _tracer
        _flight = FlightRecorder(capacity=flight_capacity)
        _tracer = SpanTracer(
            max_events=max_events,
            spill_path=(os.path.join(directory, "trace.spill.jsonl")
                        if spill_events else None))
        # dumps read the tracer's tail — spans don't write-through to a
        # second ring (that doubled the hot-path cost)
        t = _tracer
        _flight.source = lambda: t.tail(flight_capacity)
        _dir = directory
        if export_at_exit and not _atexit_registered:
            _atexit_registered = True
            atexit.register(_atexit_export)
    if old is not None:
        old.close()
    if install_signal:
        _install_sigusr1()
    if http_port is None:
        port = os.environ.get(ENV_HTTP)
        http_port = int(port) if port else None
    if http_port is not None:
        serve_http(http_port)


def configure_from_env() -> bool:
    """Enable from FEDML_OBS_DIR when set (tools / spawned worker
    processes).  No-op if already enabled."""
    d = os.environ.get(ENV_VAR)
    if d and not enabled():
        configure(d)
        return True
    return False


def reset() -> None:
    """Test hook: back to the disabled-by-default state with a fresh
    registry.  Metric handles cached by already-constructed objects
    keep writing to the OLD registry — tests reset() before building
    the objects under test."""
    global _registry, _tracer, _flight, _dir, _http
    with _lock:
        old_tracer, old_http = _tracer, _http
        _registry = MetricsRegistry()
        _tracer = None
        _flight = None
        _dir = None
        _http = None
    if old_tracer is not None:
        old_tracer.close()
    if old_http is not None:
        old_http.close()
    from fedml_tpu.obs import propagate
    propagate.reset_clocks()
    from fedml_tpu.obs import cluster, programs, slo
    programs.reset()
    slo.reset()
    cluster.reset()


# -- tracing -----------------------------------------------------------------

def span(name: str, **attrs):
    """Nestable wall-clock span: always a profiler annotation (recorded
    only while a profiler session is active), and a `SpanTracer` event
    too once `configure()` ran."""
    ann = TraceAnnotation(name, **attrs)
    t = _tracer
    if t is None:
        return ann
    return _both(ann, t.span(name, **attrs))


@contextlib.contextmanager
def _both(annotation, tracer_span) -> Iterator[None]:
    with annotation, tracer_span:
        yield


def instant(name: str, **attrs) -> None:
    t = _tracer
    if t is not None:
        t.instant(name, **attrs)


def tracer() -> Optional[SpanTracer]:
    return _tracer


# -- metrics -----------------------------------------------------------------

def registry() -> MetricsRegistry:
    return _registry


def counter(name: str, **labels) -> Counter:
    return _registry.counter(name, **labels)


def gauge(name: str, **labels) -> Gauge:
    return _registry.gauge(name, **labels)


def histogram(name: str, buckets=None, **labels) -> Histogram:
    return _registry.histogram(name, buckets=buckets, **labels)


def sample_device_memory() -> None:
    """Live/peak HBM gauges per local device, when the backend exposes
    allocator stats (TPU/GPU do; XLA:CPU returns None — skipped).
    Call sites gate on `enabled()`: polling every device per round is
    pointless when nothing exports the result."""
    try:
        import jax
        devices = jax.local_devices()
    except Exception:                       # pragma: no cover - no backend
        return
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        live = stats.get("bytes_in_use")
        if live is not None:
            gauge("device_bytes_in_use", device=str(d.id)).set(live)
            gauge("device_peak_bytes_in_use",
                  device=str(d.id)).set_max(
                      stats.get("peak_bytes_in_use", live))


# -- http introspection ------------------------------------------------------

def serve_http(port: int = 0):
    """Start (or return the already-running) loopback introspection
    endpoint — /metrics (Prometheus text), /rollup (JSON), /flight
    (dump trigger).  Works with metrics alone (no --obs_dir needed);
    /flight answers 503 until configure() arms the recorder.  Returns
    the ObsHttpServer (its `.port` is the bound port — pass 0 for an
    ephemeral one)."""
    global _http
    with _lock:
        if _http is not None:
            if port not in (0, _http.port):
                import sys
                print(f"obs.serve_http: endpoint already on port "
                      f"{_http.port}; ignoring request for {port}",
                      file=sys.stderr)
            return _http
    from fedml_tpu.obs.httpd import ObsHttpServer
    server = ObsHttpServer(port=port)
    with _lock:
        if _http is None:
            _http = server
            return server
    server.close()                    # lost a concurrent-start race
    return _http


def http_server():
    return _http


# -- flight recorder ---------------------------------------------------------

def flight() -> Optional[FlightRecorder]:
    return _flight


def dump_flight(reason: str, extra: Optional[dict] = None) -> Optional[str]:
    """Dump the ring + thread stacks + a metrics snapshot; returns the
    path (None when disabled)."""
    f, d = _flight, _dir
    if f is None or d is None:
        return None
    payload = {"metrics": _registry.snapshot()}
    if extra:
        payload.update(extra)
    return f.dump(d, reason, extra=payload)


def deadline(tag: str, seconds: Optional[float]):
    """Round-deadline watchdog: a flight dump fires if the with-block
    overruns `seconds`.  No-op when disabled or seconds is None."""
    f, d = _flight, _dir
    if f is None or d is None or seconds is None:
        return contextlib.nullcontext()
    return f.watchdog(seconds, tag, d,
                      extra_fn=lambda: {"metrics": _registry.snapshot()})


def _install_sigusr1() -> None:
    """SIGUSR1 -> flight dump.  Only installable from the main thread
    (signal module restriction); elsewhere — e.g. an engine built on a
    worker thread — the caller keeps its current handler."""
    global _prev_sigusr1
    if not hasattr(signal, "SIGUSR1"):       # pragma: no cover - windows
        return

    def _dump_async():
        # settle briefly so the main thread has returned from the
        # handler (and its Thread.start() wait) back to wherever it is
        # actually stuck — the captured stack then shows the park site
        time.sleep(0.05)
        dump_flight("SIGUSR1")

    def handler(signum, frame):
        # dump from a SEPARATE thread, never inline: the handler runs on
        # the main thread between bytecodes, possibly while that thread
        # holds the (non-reentrant) ring or a metric lock — an inline
        # dump would deadlock the process it came to diagnose.  A side
        # benefit: the main thread's captured stack then shows where it
        # is actually parked, not these handler frames.
        threading.Thread(target=_dump_async, name="obs-sigusr1-dump",
                         daemon=True).start()
        prev = _prev_sigusr1
        if callable(prev) and prev not in (signal.SIG_IGN, signal.SIG_DFL):
            prev(signum, frame)              # pragma: no cover - chained

    handler._fedml_obs = True                 # reconfigure: don't self-chain
    try:
        prev = signal.signal(signal.SIGUSR1, handler)
    except ValueError:                        # not the main thread
        return
    if not getattr(prev, "_fedml_obs", False):
        _prev_sigusr1 = prev


# -- exporters ---------------------------------------------------------------

def export() -> dict[str, str]:
    """Write every artifact into obs_dir:

        trace.chrome.json   Chrome trace-event file (chrome://tracing,
                            ui.perfetto.dev)
        trace.jsonl         same spans, one JSON object per line, led
                            by a __meta__ line (pid/epoch/drops) —
                            tools/trace_timeline.py's merge input
        metrics.prom        Prometheus text exposition
        metrics.json        JSON metrics snapshot
        clock_offsets.json  per-comm-manager peer clock offsets
                            (obs/propagate.py), when any traffic was
                            trace-stamped — the timeline tool's
                            cross-process alignment input
        barrier_ledger.json per-barrier arrival/wait ledger
                            (obs/cluster.py), written on the
                            coordinator when any barrier was recorded
                            — trace_timeline's straggler annotations

    Returns {artifact: path}.  No-op ({}) when disabled."""
    t, d = _tracer, _dir
    if d is None:
        return {}
    out = {}
    if t is not None:
        out["chrome_trace"] = t.export_chrome(
            os.path.join(d, "trace.chrome.json"))
        out["jsonl_trace"] = t.export_jsonl(os.path.join(d, "trace.jsonl"))
    prom = os.path.join(d, "metrics.prom")
    with open(prom, "w") as f:
        f.write(_registry.to_prometheus())
    out["prometheus"] = prom
    mj = os.path.join(d, "metrics.json")
    with open(mj, "w") as f:
        f.write(_registry.to_json())
    out["metrics_json"] = mj
    from fedml_tpu.obs import propagate
    clocks = propagate.clock_exports()
    if clocks:
        cj = os.path.join(d, "clock_offsets.json")
        with open(cj, "w") as f:
            json.dump(clocks, f, indent=1)
        out["clock_offsets"] = cj
    from fedml_tpu.obs import cluster
    cluster.export_dir(d)
    bl = os.path.join(d, "barrier_ledger.json")
    if os.path.exists(bl):
        out["barrier_ledger"] = bl
    return out


def _atexit_export() -> None:                # pragma: no cover - exit path
    try:
        export()
    except Exception:
        pass


def rollup() -> dict:
    """Small summary for embedding in a report: where the artifacts
    are plus the headline counters."""
    t = _tracer
    from fedml_tpu.obs import programs, slo
    eng = slo.active()
    return {
        # ISSUE 12: the judgment layer's verdict rides every rollup —
        # the installed SLO engine's pack state (None when no engine
        # runs) plus the process-total breach count either way
        "slo": (eng.report() if eng is not None else None),
        "slo_breaches_total": sum(
            m.value for m in _registry.metrics()
            if m.name == "slo_breaches_total"),
        "program_families": sorted(programs.families()),
        "obs_dir": _dir,
        "spans_recorded": (0 if t is None
                           else len(t.events()) + t.dropped),
        # ring evictions, surfaced here so a truncated trace can never
        # masquerade as a complete one (ISSUE-7 satellite) — with the
        # spill accounting that says how much of the head survived
        "spans_dropped": 0 if t is None else t.dropped,
        "spans_spilled": 0 if t is None else t.spilled,
        "spill_truncated": 0 if t is None else t.spill_truncated,
        "http_port": None if _http is None else _http.port,
        "jit_compile_total": counter("jit_compile_total").value,
        "jit_compile_seconds_total":
            counter("jit_compile_seconds_total").value,
        "flight_dumps": [] if _flight is None else list(_flight.dumps),
    }


# -- jax compile accounting --------------------------------------------------
# jax.monitoring publishes per-compile duration events
# ("/jax/core/compile/backend_compile_duration" on this jaxlib); one
# listener turns them into jit_compile_total / jit_compile_seconds_total.
# Registered at import, once per process; the listener resolves the
# registry through the module global so reset() redirects it too.

def _on_jax_duration_event(event: str, duration: float, **kw) -> None:
    if event.endswith("backend_compile_duration"):
        _registry.counter("jit_compile_total").inc()
        _registry.counter("jit_compile_seconds_total").inc(duration)
        # compile-accounting attribution (ISSUE 12): when the compile
        # was triggered from inside an instrumented program family's
        # dispatch (obs/programs.py marks the calling thread), the
        # labeled series name the culprit — a recompile storm then
        # reads "fedavg_streaming recompiled 40x", not one global
        # counter ticking.  The unlabeled pair above stays the
        # process-total (rollup() and older consumers read it).
        fam = _program_family_of_thread()
        _registry.counter("jit_compile_total",
                          family=fam or "unattributed").inc()
        _registry.counter("jit_compile_seconds_total",
                          family=fam or "unattributed").inc(duration)
        t = _tracer
        if t is not None:
            t.instant("jit.backend_compile", seconds=duration,
                      family=fam)


def _program_family_of_thread():
    try:
        from fedml_tpu.obs import programs
        return programs.current()
    except Exception:                         # pragma: no cover - import
        return None


def _register_jax_listener() -> None:
    try:
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(
            _on_jax_duration_event)
    except Exception:                         # pragma: no cover - old jax
        pass


_register_jax_listener()
