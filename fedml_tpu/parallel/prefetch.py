"""Double-buffered host→device prefetch pipeline (streaming engines).

The streaming and block-stream rounds are transfer-bound at large
cohorts (builder session on one v5e, 2026-07/08, older than PR 1: the
4096-client block-streamed round ran at its upload rate, ~17 MB/s; not
measured on the current machine): each client block is gathered, cast, and
uploaded, and only then does the round loop dispatch compute on it.
`jax.device_put` and jit dispatch are asynchronous, but the HOST side of
an upload — the `np.take` gather over the client stack, the stack_dtype
cast, the flat_stack reshape — runs on the dispatching thread and
serializes with the round loop.  `Prefetcher` moves production to a
background thread with a depth-bounded handoff: while the device trains
on block k, the host prepares and uploads block k+1.  At the default
depth=2 this is classic double buffering — the item the consumer holds
plus one in flight — so device data memory keeps the same
O(2·block bytes) bound the synchronous loop had (pinned by
tests/test_parallel_stream.py's live-bytes tests).

`InlineFetcher` is the `--no_prefetch` escape hatch: the identical
iteration contract with production inlined into `get()` — strictly
synchronous gather→upload→compute, kept for bitwise comparison against
the pipelined path (tests/test_prefetch.py) and for debugging.

`AsyncValue` is the one-shot variant the per-round streaming path uses:
round r+1's whole-cohort gather+upload runs on a background thread
while round r computes.

Thread-safety: jax dispatch (device_put included) is thread-safe; the
producer thread touches only host numpy data and enqueue-side jax
calls.  Every upload lands in the engine's TransferOverlapStats
(utils/profiling.py) from whichever thread runs it — walls AND payload
bytes (`add_h2d_bytes`, the transfer-compression accounting: the engine
counts each host buffer it hands to device_put, so uint8/bf16 stacks
report their real H2D reduction per round) — and consumer-side blocking
waits are recorded so overlap_fraction is measurable.

The pipeline is dtype-agnostic by construction: a uint8-quantized block
(stack_dtype=uint8) rides the same produce()/get() contract at 1/4 the
f32 bytes, which shrinks exactly the upload wall this double buffer
exists to hide.
"""
from __future__ import annotations

import contextlib
import logging
import queue
import threading
from typing import Any, Callable, Optional, Sequence

from fedml_tpu.utils.profiling import TransferOverlapStats

log = logging.getLogger(__name__)
_SENTINEL = object()


class Prefetcher:
    """Run `produce(item)` for each work item on a background thread,
    delivering results in order via `get()`, with at most `depth`
    results materialized at once (the one the consumer last took plus
    `depth-1` queued/in-flight).  A producer exception is re-raised
    from the next `get()`.  `close()` (also via context manager exit)
    always stops the worker, joins it, and drops undelivered results —
    an aborted round can never leak a worker thread or hand a stale
    uploaded buffer to the next round."""

    def __init__(self, produce: Callable[[Any], Any], items: Sequence,
                 depth: int = 2, stats: Optional[TransferOverlapStats] = None,
                 name: str = "h2d-prefetch",
                 wait_attrs: Optional[dict] = None):
        if depth < 2:
            raise ValueError(f"depth must be >= 2 (double buffer), got "
                             f"{depth}")
        self._produce = produce
        self._items = list(items)
        self._stats = stats
        self._wait_attrs = wait_attrs or {}     # ride the h2d.wait spans
        self._q: queue.Queue = queue.Queue()
        # permits = how far the producer may run ahead of the consumer;
        # acquired before each produce, released on each get
        self._slots = threading.Semaphore(depth - 1)
        self._stop = threading.Event()
        self._err: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._work, name=name,
                                        daemon=True)
        self._thread.start()

    def _work(self) -> None:
        try:
            for item in self._items:
                self._slots.acquire()
                if self._stop.is_set():
                    return
                out = self._produce(item)
                if self._stop.is_set():
                    # closed mid-produce (close()'s join may even have
                    # timed out on a slow upload): DROP the
                    # result — enqueueing it would park a stale
                    # uploaded block past the drain, breaking the
                    # O(2·block) bound for the next round
                    return
                self._q.put(out)
        except BaseException as e:          # surfaced from get()
            self._err = e
            self._q.put(_SENTINEL)

    def get(self):
        """Next result, blocking until the worker has produced it (the
        block recorded as wait_wall in `stats`)."""
        wait = (self._stats.waiting(**self._wait_attrs)
                if self._stats is not None else contextlib.nullcontext())
        with wait:
            while True:
                try:
                    out = self._q.get(timeout=5.0)
                    break
                except queue.Empty:
                    if not self._thread.is_alive():
                        # the worker may have put its final result and
                        # exited between the timeout and the liveness
                        # check — drain once more before declaring it
                        # dead (on a slow upload path every block
                        # takes multiple timeout cycles)
                        try:
                            out = self._q.get_nowait()
                            break
                        except queue.Empty:
                            raise RuntimeError(
                                "prefetch worker died without a result"
                            ) from self._err
        if out is _SENTINEL:
            raise self._err
        self._slots.release()
        return out

    def close(self) -> None:
        """Stop the worker, join it, drop undelivered buffers."""
        self._stop.set()
        # unblock a worker parked in acquire (twice is enough: it checks
        # _stop right after acquiring and never re-acquires before that)
        self._slots.release()
        self._slots.release()
        self._thread.join(timeout=60.0)
        if self._thread.is_alive():
            # a single block upload can exceed the join timeout on a
            # slow host→device link; the worker will see _stop after its
            # produce returns and drop the result (never enqueue it)
            log.warning("prefetch worker still mid-upload after close() "
                        "join timeout; it will discard its result")
        while True:                         # drop queued results
            try:
                self._q.get_nowait()
            except queue.Empty:
                break

    def __enter__(self) -> "Prefetcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class InlineFetcher:
    """The --no_prefetch path: `get()` runs `produce(item)` inline —
    the strictly synchronous upload→compute ordering.  Same contract as
    Prefetcher so the round loops are knob-agnostic.  The inline
    produce IS consumer blocking, so it is recorded as wait_wall: the
    synchronous path correctly reports overlap_fraction ≈ 0 (nothing
    hidden), not a vacuous 1.0."""

    def __init__(self, produce: Callable[[Any], Any], items: Sequence,
                 depth: int = 2, stats: Optional[TransferOverlapStats] = None,
                 name: str = "h2d-inline",
                 wait_attrs: Optional[dict] = None):
        self._produce = produce
        self._it = iter(list(items))
        self._stats = stats
        self._wait_attrs = wait_attrs or {}

    def get(self):
        item = next(self._it)
        wait = (self._stats.waiting(**self._wait_attrs)
                if self._stats is not None else contextlib.nullcontext())
        with wait:
            return self._produce(item)

    def close(self) -> None:
        pass

    def __enter__(self) -> "InlineFetcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class AsyncValue:
    """One value computed on a background thread — the streaming path's
    next-round cohort gather+upload.  `result()` joins and re-raises;
    recorded as a consumer wait in `stats` when the value is not ready
    yet."""

    def __init__(self, fn: Callable, *args,
                 stats: Optional[TransferOverlapStats] = None,
                 name: str = "h2d-prefetch-round",
                 wait_attrs: Optional[dict] = None):
        self._out = None
        self._err: Optional[BaseException] = None
        self._stats = stats
        self._wait_attrs = wait_attrs or {}

        def work():
            try:
                self._out = fn(*args)
            except BaseException as e:
                self._err = e

        self._thread = threading.Thread(target=work, name=name, daemon=True)
        self._thread.start()

    def result(self):
        if self._thread.is_alive() and self._stats is not None:
            with self._stats.waiting(**self._wait_attrs):
                self._thread.join()
        else:
            self._thread.join()
        if self._err is not None:
            raise self._err
        return self._out
