"""Profiling/tracing — first-class replacement for the reference's coarse
wall-clock timers (SURVEY.md §5: aggregation timers FedAVGAggregator.py:60,
TRPC latency microbench).

`trace(dir)` captures a full XLA/TPU profile viewable in TensorBoard or
Perfetto; `annotate(name)` scopes a named region inside it; `StepTimer`
gives the reference-style wall-clock numbers (rounds/sec, per-phase means)
without any profiler overhead.
"""
from __future__ import annotations

import contextlib
import threading
import time
from collections import defaultdict
from typing import Iterator, Optional

import jax
import numpy as np

from fedml_tpu import obs
from fedml_tpu.obs import scopes


@contextlib.contextmanager
def trace(log_dir: str) -> Iterator[None]:
    """jax.profiler trace of everything inside the block (device + host)."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str):
    """Named region inside a trace (shows up on the TraceMe timeline)."""
    return jax.profiler.TraceAnnotation(name)


class StepTimer:
    """Accumulates wall-clock per named phase; blocking-safe (call `stop`
    after block_until_ready for honest device timings)."""

    def __init__(self):
        self.totals: dict[str, float] = defaultdict(float)
        self.counts: dict[str, int] = defaultdict(int)
        self._open: dict[str, float] = {}

    @contextlib.contextmanager
    def phase(self, name: str) -> Iterator[None]:
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.totals[name] += time.perf_counter() - t0
            self.counts[name] += 1

    def mean(self, name: str) -> float:
        return self.totals[name] / max(self.counts[name], 1)

    def report(self) -> dict[str, float]:
        return {f"{k}_mean_s": self.mean(k) for k in self.totals}


def _overlap_fraction(upload_wall: float, wait_wall: float) -> float:
    """Fraction of the upload wall that hid behind compute.  1.0 when
    there were no uploads (nothing left unhidden — bench's resident
    cohort path reports this by definition)."""
    if upload_wall <= 0.0:
        return 1.0
    return max(0.0, min(1.0, (upload_wall - wait_wall) / upload_wall))


class TransferOverlapStats:
    """Host→device transfer vs compute overlap accounting for the
    streaming/block-stream engine paths (the PR-1 prefetch pipeline).

    Producers — whichever thread runs the host gather + cast +
    `jax.device_put` — time each upload with `uploading()`; the round
    loop times its blocking prefetch waits with `waiting()` (which also
    opens the `h2d.wait` program span) and brackets each round with
    `round_start()`/`round_end()`.  Per round (and cumulatively since
    `reset()`):

        upload_wall_s     Σ wall of upload calls, any thread: HOST time
                          of gather + cast + the device_put ENQUEUE (the
                          put is asynchronous) — not transfer time; the
                          h2d.gather / h2d.put spans split it
        wait_wall_s       wall the round loop spent blocked on uploads
        round_wall_s      wall of the whole round
        compute_wall_s    round_wall_s − wait_wall_s (dispatch + device)
        overlap_fraction  (upload_wall − wait_wall)/upload_wall ∈ [0, 1]

    With perfect overlap the loop never waits for a transfer
    (overlap 1.0); a fully transfer-bound round waits out almost every
    upload (overlap ≈ compute/upload).  Uploads are attributed to the
    round window they occur in by wall time (a next-round prefetch that
    starts during round r lands in r's window); the cumulative numbers
    are window-free.  Thread-safe; overhead is two perf_counter calls
    per event, so it stays on for every streaming round
    (PERF.md §"Prefetch pipeline" has the measurement recipe).

    The metrics registry (fedml_tpu/obs) is the exported system of
    record: every upload/wait/round event writes through to the shared
    engine_* counters and histograms below, so a Prometheus snapshot
    carries the same walls this object reports.  The instance keeps its
    own cumulative state too — per-engine round windows (and `reset()`)
    must not be corrupted by another engine in the same process, and
    prometheus counters never reset."""

    def __init__(self):
        self._lock = threading.Lock()
        # write-through registry handles (shared across engines; the
        # per-instance fields below stay the per-engine view)
        self._m_upload_total = obs.counter(
            "engine_upload_wall_seconds_total")
        self._m_wait_total = obs.counter("engine_wait_wall_seconds_total")
        self._m_rounds = obs.counter("engine_rounds_total")
        # per-event histograms: upload tail = the straggler blocks of a
        # block-streamed round; round wall = the cohort wall-time
        self._h_upload = obs.histogram("engine_upload_wall_seconds")
        self._h_wait = obs.histogram("engine_wait_wall_seconds")
        self._h_round = obs.histogram("engine_round_wall_seconds")
        self._h_overlap = obs.histogram(
            "engine_round_overlap_fraction",
            buckets=(0.1, 0.25, 0.5, 0.75, 0.9, 0.99, 1.0))
        # byte accounting (transfer-compression layer): every host
        # buffer the engine hands to device_put counts here, so the
        # stack-dtype tiers (f32/bf16/uint8) are comparable as BYTES,
        # not just walls — round_records() carries h2d_bytes per round,
        # and the registry counter is the Prometheus view
        self._m_h2d_bytes = obs.counter("engine_h2d_bytes_total")
        # batch-loop trips of the round programs dispatched since
        # reset(), and the trips a loop over every batch of the stack
        # would have run: their ratio is how far the bounded batch loop
        # of a ragged cohort engages (parallel/engine.py order_by_trips;
        # the benchmark's batch_trips_pct)
        self._m_batch_trips = obs.counter("engine_batch_trips_total")
        self._m_batch_trips_static = obs.counter(
            "engine_batch_trips_static_total")
        # what the round programs' models counted (obs/scopes.py COUNTERS):
        # the programs' own device arrays, kept as they come and summed
        # when somebody reads them
        self._m_program_counters = {
            name: [obs.counter(metric, **labels) for labels in each]
            for name, (metric, each) in scopes.METRIC_OF_COUNTER.items()}
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self._upload_wall = 0.0
            self._wait_wall = 0.0
            self._h2d_bytes = 0
            self.batch_trips = 0
            self.batch_trips_static = 0
            self._counted: dict = {}          # name -> summed numpy array
            self._uncounted: list = []        # (name, device array)
            self._round_t0: Optional[float] = None
            self._snap = (0.0, 0.0, 0)
            self.rounds: list[dict] = []

    def add_h2d_bytes(self, nbytes: int) -> None:
        """Record host→device payload bytes (called by the engine upload
        paths where the host buffer sizes are known — any thread)."""
        n = int(nbytes)
        with self._lock:
            self._h2d_bytes += n
        self._m_h2d_bytes.inc(n)

    def add_batch_trips(self, ran: int, static: int) -> None:
        """Record one round program's batch-loop trips (the engine's
        host-side count, made where the round's ids are known)."""
        with self._lock:
            self.batch_trips += ran
            self.batch_trips_static += static
        self._m_batch_trips.inc(ran)
        self._m_batch_trips_static.inc(static)

    def add_program_counters(self, counters: dict) -> None:
        """Keep one round program's counters (its metrics' device
        arrays).  Nothing is waited for here: once the backlog is long,
        the arrays of rounds that have finished are summed and let go,
        those of rounds still in flight stay."""
        with self._lock:
            self._uncounted.extend(counters.items())
            backlog = len(self._uncounted) > 256
        if backlog:
            self._sum_counters(finished_only=True)

    def program_counters(self) -> dict:
        """{name: numpy array} summed over the round programs dispatched
        since reset(); waits for those still running."""
        self._sum_counters(finished_only=False)
        with self._lock:
            return dict(self._counted)

    def _sum_counters(self, finished_only: bool) -> None:
        with self._lock:
            fresh, kept = [], []
            for entry in self._uncounted:
                running = finished_only and not entry[1].is_ready()
                (kept if running else fresh).append(entry)
            self._uncounted = kept
        for name, value in fresh:
            value = np.asarray(value, np.float64)
            with self._lock:
                self._counted[name] = self._counted.get(name, 0.0) + value
            metrics = self._m_program_counters.get(name)
            if metrics:                   # one for each entry of the last axis
                totals = value.reshape((-1, len(metrics))).sum(axis=0)
                for metric, total in zip(metrics, totals):
                    metric.inc(float(total))

    @property
    def h2d_bytes(self) -> int:
        """Cumulative H2D payload bytes since reset() (per-engine view;
        engine_h2d_bytes_total is the process-wide counter)."""
        with self._lock:
            return self._h2d_bytes

    @contextlib.contextmanager
    def uploading(self) -> Iterator[None]:
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            with self._lock:
                self._upload_wall += dt
            self._m_upload_total.inc(dt)
            self._h_upload.observe(dt)

    @contextlib.contextmanager
    def waiting(self, **span_attrs) -> Iterator[None]:
        t0 = time.perf_counter()
        try:
            with obs.span(scopes.SPAN_WAIT, **span_attrs):
                yield
        finally:
            dt = time.perf_counter() - t0
            with self._lock:
                self._wait_wall += dt
            self._m_wait_total.inc(dt)
            self._h_wait.observe(dt)

    def round_start(self) -> None:
        """Open a round window (auto-closes a window left open).  The
        block-stream rounds bracket themselves with round_start/
        round_end (try/finally); the per-round streaming path records
        cumulative walls only — its round body runs in the base run()
        loop, outside the engine hooks' sight."""
        if self._round_t0 is not None:
            self.round_end()
        with self._lock:
            self._snap = (self._upload_wall, self._wait_wall,
                          self._h2d_bytes)
        self._round_t0 = time.perf_counter()

    def round_end(self) -> Optional[dict]:
        """Close the open round window and record it; no-op when none
        is open."""
        if self._round_t0 is None:
            return None
        wall = time.perf_counter() - self._round_t0
        self._round_t0 = None
        with self._lock:
            up = self._upload_wall - self._snap[0]
            wait = self._wait_wall - self._snap[1]
            h2d = self._h2d_bytes - self._snap[2]
        rec = {"round_wall_s": wall, "upload_wall_s": up,
               "wait_wall_s": wait,
               "compute_wall_s": max(wall - wait, 0.0),
               "overlap_fraction": _overlap_fraction(up, wait),
               "h2d_bytes": h2d}
        self.rounds.append(rec)
        self._m_rounds.inc()
        self._h_round.observe(wall)
        self._h_overlap.observe(rec["overlap_fraction"])
        return rec

    def overlap_fraction(self) -> float:
        with self._lock:
            return _overlap_fraction(self._upload_wall, self._wait_wall)

    def report(self) -> dict:
        with self._lock:
            up, wait = self._upload_wall, self._wait_wall
            h2d = self._h2d_bytes
        return {"upload_wall_s": up, "wait_wall_s": wait,
                "overlap_fraction": _overlap_fraction(up, wait),
                "h2d_bytes": h2d, "rounds": len(self.rounds)}
