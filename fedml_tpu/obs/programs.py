"""Per-jit-program-family profile registry — live attribution of device
time, compiles, FLOPs and bytes to the program families the engines
hand-assemble (ISSUE 12).

The engine layer compiles 10+ distinct jitted program families
({resident, streaming, block-stream} x {fedavg, fednova, robust,
orderstat} + the async fold/commit/screened-fold pipeline — ROADMAP
item 5's matrix), but until now the only per-family numbers were
one-off manual ``jax.profiler`` sessions (the 47% MFU headline, the
PERF.md stage table).  This registry makes them STANDING artifacts:

* ``instrument(family, jitted_fn)`` wraps a compiled program so every
  dispatch counts (``program_dispatches_total{family}``) and times its
  host-side dispatch wall (``program_dispatch_seconds{family}`` on the
  sub-ms canonical ladder).  The wrapper passes ``lower``/attribute
  access through to the wrapped jit, so AOT consumers
  (tools/hlo_copy_audit.py) keep working, and it NEVER touches values
  — obs-on/off results stay bitwise identical (the existing pins);
* while a wrapped program runs, its family is the thread's CURRENT
  family — the ``jax.monitoring`` compile listener
  (fedml_tpu/obs/__init__.py) reads it to attribute backend-compile
  counts/seconds per family instead of one global pair (fallback label
  ``unattributed``), so a recompile storm names its culprit;
* an HLO flop/byte census joins in: either live (``enable_census()``
  — one extra AOT lower+compile per family on its first dispatch,
  reading ``compiled.cost_analysis()``; default OFF so the hot paths
  and tier-1 pay nothing) or from a ``tools/hlo_copy_audit.py --out``
  artifact (``load_census()``), giving per-family and whole-run
  MFU/bytes-moved gauges;
* every family maps to a canonical timeline stage
  (obs/timeline.py PROGRAM_FAMILY_STAGES), so the profile table groups
  into the same taxonomy as the round critical path.

``report(since=snapshot())`` is the standing replacement for the
manual profile session: per-family dispatch counts, wall p50/p95,
compile seconds, flops/bytes per dispatch, and MFU against
``peak_flops()`` (the published per-chip peak of the attached
``device_kind``; a documented order-of-magnitude heuristic on the CPU
backend) — bench.py's schema-v11
``programs`` block and PERF.md's "Performance observatory" table both
read it.
"""
from __future__ import annotations

import os
import threading
import time
from typing import Any, Optional

from fedml_tpu.obs.metrics import quantile_from_cumulative

ENV_CENSUS = "FEDML_OBS_CENSUS"

_lock = threading.Lock()
_families: dict[str, "ProgramFamily"] = {}
_tls = threading.local()
_census_enabled: Optional[bool] = None      # None = resolve env lazily


def _stage_of(family: str) -> str:
    from fedml_tpu.obs.timeline import PROGRAM_FAMILY_STAGES
    return PROGRAM_FAMILY_STAGES.get(family, "other")


class ProgramFamily:
    """Profile state of one program family.  Metric handles re-resolve
    when obs.reset() swapped the registry (identity check per call —
    cheaper than a registry lookup, correct across test resets)."""

    def __init__(self, name: str):
        self.name = name
        self.stage = _stage_of(name)
        self.flops_per_dispatch: Optional[float] = None
        self.bytes_per_dispatch: Optional[float] = None
        self.census_source: Optional[str] = None
        self._reg = None
        self._ctr = None
        self._hist = None

    def _handles(self):
        from fedml_tpu import obs
        reg = obs.registry()
        if reg is not self._reg:
            # a registry swap means obs.reset() ran: re-enter the family
            # table too, so a pre-reset wrapper's next dispatch shows up
            # in families()/snapshot()/report() again — without this the
            # fresh registry's dispatch counters would tick while the
            # profile report silently omitted the family
            with _lock:
                _families.setdefault(self.name, self)
            self._reg = reg
            self._ctr = reg.counter("program_dispatches_total",
                                    family=self.name)
            self._hist = reg.histogram("program_dispatch_seconds",
                                       family=self.name)
        return self._ctr, self._hist

    def observe_dispatch(self, seconds: float) -> None:
        ctr, hist = self._handles()
        hist.observe(seconds)
        ctr.inc()

    def attach_census(self, flops: Optional[float] = None,
                      bytes_accessed: Optional[float] = None,
                      source: str = "attached") -> None:
        if flops is not None:
            self.flops_per_dispatch = float(flops)
        if bytes_accessed is not None:
            self.bytes_per_dispatch = float(bytes_accessed)
        self.census_source = source


def register(family: str) -> ProgramFamily:
    with _lock:
        fam = _families.get(family)
        if fam is None:
            fam = _families[family] = ProgramFamily(family)
        return fam


def families() -> dict[str, ProgramFamily]:
    with _lock:
        return dict(_families)


def current() -> Optional[str]:
    """The family whose wrapped program is executing on THIS thread
    (the compile listener's attribution source), or None."""
    return getattr(_tls, "family", None)


def reset() -> None:
    """Test hook (obs.reset() calls through): fresh family table +
    cleared thread-local.  Wrappers built before the reset re-register
    their family on next dispatch."""
    with _lock:
        _families.clear()
    _tls.family = None


# -- census ------------------------------------------------------------------

def enable_census(on: bool = True) -> None:
    global _census_enabled
    _census_enabled = bool(on)


def census_enabled() -> bool:
    global _census_enabled
    if _census_enabled is None:
        _census_enabled = os.environ.get(ENV_CENSUS, "") not in ("", "0")
    return _census_enabled


def cost_analysis_of(compiled) -> tuple[Optional[float], Optional[float]]:
    """(flops, bytes_accessed) from a jax Compiled's cost analysis —
    handles the dict and the per-partition-list shapes across jax
    versions; (None, None) when the backend exposes nothing."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None, None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None, None
    flops = ca.get("flops")
    nbytes = ca.get("bytes accessed")
    return (float(flops) if flops is not None else None,
            float(nbytes) if nbytes is not None else None)


def load_census(report: Any) -> int:
    """Join an hlo_copy_audit artifact (path or loaded dict) into the
    registry: per family, flops/bytes summed over the family's
    programs.  Returns how many families gained census numbers."""
    import json
    if isinstance(report, str):
        with open(report) as f:
            report = json.load(f)
    n = 0
    for family, doc in (report.get("families") or {}).items():
        progs = doc.get("programs") or {}
        flops = [p.get("flops") for p in progs.values()
                 if p.get("flops") is not None]
        nbytes = [p.get("bytes_accessed") for p in progs.values()
                  if p.get("bytes_accessed") is not None]
        if not flops and not nbytes:
            continue
        register(family).attach_census(
            flops=sum(flops) if flops else None,
            bytes_accessed=sum(nbytes) if nbytes else None,
            source="hlo_copy_audit")
        n += 1
    return n


# Published per-chip peaks, keyed by `jax.devices()[0].device_kind`.
# v5e: 197 TFLOP/s bf16 (Google Cloud documentation, "TPU v5e").  A
# device that is not in the table is an error, not a default.
PEAK_FLOPS_BY_DEVICE_KIND = {
    "TPU v5 lite": 197e12,
}


def peak_flops() -> float:
    """Peak-FLOP/s denominator for MFU, from the device JAX reports.
    On an accelerator: the published per-chip peak of its
    `device_kind` (ValueError for an unknown kind — no silent
    default).  On the CPU backend: a documented
    order-of-magnitude heuristic — cores x 3.2 GHz x 16 f32 FLOP/cycle
    (one AVX2 FMA port's worth) — good enough to rank families and
    watch trends on a CI box, NOT a calibrated utilization claim."""
    import jax
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return float(os.cpu_count() or 1) * 3.2e9 * 16
    try:
        return PEAK_FLOPS_BY_DEVICE_KIND[dev.device_kind]
    except KeyError:
        raise ValueError(
            f"no published peak FLOP/s for device_kind "
            f"{dev.device_kind!r}: add it (with its source) to "
            f"obs/programs.py PEAK_FLOPS_BY_DEVICE_KIND") from None


# -- the dispatch wrapper ----------------------------------------------------

class InstrumentedProgram:
    """Transparent wrapper around one jitted program: counts + times
    each dispatch, marks the thread's current family for compile
    attribution, and (census mode) runs a one-time AOT cost analysis.
    `lower` and every other attribute delegate to the wrapped jit, so
    AOT consumers (hlo_copy_audit's ``fn.lower(*args).compile()``) see
    the real thing."""

    __slots__ = ("_fn", "_family", "_census_tried")

    def __init__(self, fn, family: ProgramFamily):
        self._fn = fn
        self._family = family
        self._census_tried = False

    @property
    def inner(self):
        return self._fn

    @property
    def family(self) -> str:
        return self._family.name

    def __call__(self, *args, **kwargs):
        fam = self._family
        if (not self._census_tried and fam.flops_per_dispatch is None
                and census_enabled()):
            self._try_census(args, kwargs)
        prev = getattr(_tls, "family", None)
        _tls.family = fam.name
        t0 = time.perf_counter()
        try:
            return self._fn(*args, **kwargs)
        finally:
            dt = time.perf_counter() - t0
            _tls.family = prev
            fam.observe_dispatch(dt)

    def _try_census(self, args, kwargs) -> None:
        """One-time AOT lower+compile with the live call's args (shapes
        only are read — donation happens at execution, so the caller's
        buffers are untouched).  Census mode is opt-in: this pays one
        extra compile per family, amortized by the persistent compile
        cache."""
        self._census_tried = True
        fn = self._fn
        if not hasattr(fn, "lower"):
            return
        try:
            compiled = fn.lower(*args, **kwargs).compile()
        except Exception:
            return
        flops, nbytes = cost_analysis_of(compiled)
        if flops is not None or nbytes is not None:
            self._family.attach_census(flops=flops, bytes_accessed=nbytes,
                                       source="live")

    def lower(self, *args, **kwargs):
        return self._fn.lower(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._fn, name)

    def __repr__(self):
        return (f"InstrumentedProgram({self._family.name}, "
                f"{self._fn!r})")


def instrument(family: str, fn) -> InstrumentedProgram:
    """Wrap one jitted program under `family`.  Idempotent-ish: an
    already-instrumented fn is re-tagged, not double-wrapped (double
    timing would inflate the family's dispatch walls)."""
    if isinstance(fn, InstrumentedProgram):
        fn = fn.inner
    return InstrumentedProgram(fn, register(family))


# -- windowed reporting ------------------------------------------------------

def snapshot() -> dict:
    """Opaque window baseline for report(since=...): per-family
    dispatch counts + histogram cumulative states + a wall-clock
    stamp."""
    from fedml_tpu import obs
    reg = obs.registry()
    state: dict = {"t": time.perf_counter(), "families": {}}
    for name, fam in families().items():
        ctr = reg.counter("program_dispatches_total", family=name)
        hist = reg.histogram("program_dispatch_seconds", family=name)
        state["families"][name] = {
            "dispatches": ctr.value,
            "cumulative": hist.cumulative(),
            "wall": hist.sum,
            "compile_seconds": reg.counter("jit_compile_seconds_total",
                                           family=name).value,
        }
    return state


def report(since: Optional[dict] = None, *,
           peak: Optional[float] = None,
           publish_gauges: bool = True) -> dict:
    """Per-family profile over the window since `since` (a snapshot();
    None = since process start / family registration).  Returns

        {"window_s", "peak_flops", "families": [
            {family, stage, dispatches, dispatch_wall_s,
             dispatch_p50_s, dispatch_p95_s, compile_seconds,
             flops_per_dispatch, bytes_per_dispatch, flops_total,
             bytes_total, mfu}, ...],
         "processes": [...],        # per-process breakdown rows from a
                                    # multihost run's origin-labeled
                                    # merged series (ISSUE 13)
         "total": {...}}            # the whole-run row

    MFU = flops_total / (window_s x peak_flops) — null without census
    numbers.  `publish_gauges` mirrors the rows into
    ``program_mfu{family}`` / ``program_bytes_moved_total{family}``
    gauges (the "live MFU accounting" surface)."""
    from fedml_tpu import obs
    reg = obs.registry()
    if peak is None:
        peak = peak_flops()
    t0 = (since or {}).get("t")
    window_s = (time.perf_counter() - t0) if t0 is not None else None
    prev = (since or {}).get("families", {})
    rows = []
    for name, fam in sorted(families().items()):
        ctr = reg.counter("program_dispatches_total", family=name)
        hist = reg.histogram("program_dispatch_seconds", family=name)
        p = prev.get(name, {})
        dispatches = ctr.value - p.get("dispatches", 0.0)
        wall = hist.sum - p.get("wall", 0.0)
        before = p.get("cumulative")
        after = hist.cumulative()
        if dispatches <= 0:
            continue                 # idle family: not in this window
        flops_total = (fam.flops_per_dispatch * dispatches
                       if fam.flops_per_dispatch is not None else None)
        bytes_total = (fam.bytes_per_dispatch * dispatches
                       if fam.bytes_per_dispatch is not None else None)
        mfu = None
        if (flops_total is not None and peak and window_s
                and window_s > 0):
            mfu = flops_total / (window_s * peak)
        # windowed like everything else in the row: compiles BEFORE the
        # snapshot (the cold-start storm) must not re-report in later
        # windows' recompile attribution
        compile_s = (reg.counter("jit_compile_seconds_total",
                                 family=name).value
                     - p.get("compile_seconds", 0.0))
        rows.append({
            "family": name,
            "stage": fam.stage,
            "dispatches": int(dispatches),
            "dispatch_wall_s": round(wall, 6),
            "dispatch_p50_s": quantile_from_cumulative(before, after, 0.5),
            "dispatch_p95_s": quantile_from_cumulative(before, after,
                                                       0.95),
            "compile_seconds": round(compile_s, 4),
            "flops_per_dispatch": fam.flops_per_dispatch,
            "bytes_per_dispatch": fam.bytes_per_dispatch,
            "flops_total": flops_total,
            "bytes_total": bytes_total,
            "mfu": (round(mfu, 6) if mfu is not None else None),
            "census_source": fam.census_source,
        })
        if publish_gauges:
            if mfu is not None:
                obs.gauge("program_mfu", family=name).set(mfu)
            if bytes_total is not None:
                obs.gauge("program_bytes_moved_total",
                          family=name).set(bytes_total)
    total_flops = [r["flops_total"] for r in rows
                   if r["flops_total"] is not None]
    total_bytes = [r["bytes_total"] for r in rows
                   if r["bytes_total"] is not None]
    total_mfu = None
    if total_flops and peak and window_s and window_s > 0:
        total_mfu = sum(total_flops) / (window_s * peak)
    total = {
        "dispatches": sum(r["dispatches"] for r in rows),
        "dispatch_wall_s": round(sum(r["dispatch_wall_s"]
                                     for r in rows), 6),
        "flops_total": sum(total_flops) if total_flops else None,
        "bytes_total": sum(total_bytes) if total_bytes else None,
        "mfu": (round(total_mfu, 6) if total_mfu is not None else None),
    }
    if publish_gauges and total_mfu is not None:
        obs.gauge("program_mfu", family="_total").set(total_mfu)
    return {
        "window_s": (round(window_s, 3) if window_s is not None
                     else None),
        "peak_flops": peak,
        "families": rows,
        "processes": _per_process_rows(reg),
        "total": total,
    }


def _per_process_rows(reg) -> list:
    """Per-process breakdown (ISSUE 13): an N-process multihost run
    folds each rank's metric deltas into rank 0's registry under an
    ``origin`` label (MultihostRunner._rollup_metrics — the PR-7
    remote-fold shape, so no gauge is last-writer-wins across
    processes); these rows surface the merged per-family dispatch
    series per origin.  All-time, not windowed: the fold happens once
    at run end, so a window baseline taken mid-run has nothing to
    subtract."""
    from fedml_tpu.obs.metrics import MERGE_ORIGIN_LABEL
    counts: dict[tuple, float] = {}
    hists: dict[tuple, object] = {}
    for m in reg.metrics():
        labels = dict(m.labels)
        fam = labels.get("family")
        org = labels.get(MERGE_ORIGIN_LABEL)
        if fam is None or org is None:
            continue
        if m.name == "program_dispatches_total":
            counts[(fam, org)] = m.value
        elif m.name == "program_dispatch_seconds":
            hists[(fam, org)] = m
    rows = []
    for (fam, org) in sorted(counts):
        row = {"family": fam, "process": org,
               "dispatches": int(counts[(fam, org)]),
               "dispatch_wall_s": None, "dispatch_p50_s": None,
               "dispatch_p95_s": None}
        h = hists.get((fam, org))
        if h is not None:
            after = h.cumulative()
            row.update(
                dispatch_wall_s=round(h.sum, 6),
                dispatch_p50_s=quantile_from_cumulative(None, after,
                                                        0.5),
                dispatch_p95_s=quantile_from_cumulative(None, after,
                                                        0.95))
        rows.append(row)
    return rows


def format_table(rep: dict) -> str:
    """Human-readable per-family table (PERF.md's standing artifact)."""
    lines = [f"{'family':<24}{'stage':<8}{'disp':>8}{'wall s':>10}"
             f"{'p95 ms':>9}{'GFLOP/disp':>12}{'MFU':>8}"]
    for r in rep["families"]:
        gf = (f"{r['flops_per_dispatch'] / 1e9:.3f}"
              if r["flops_per_dispatch"] is not None else "-")
        mfu = f"{r['mfu']:.2%}" if r["mfu"] is not None else "-"
        lines.append(
            f"{r['family']:<24}{r['stage']:<8}{r['dispatches']:>8}"
            f"{r['dispatch_wall_s']:>10.3f}"
            f"{r['dispatch_p95_s'] * 1e3:>9.2f}{gf:>12}{mfu:>8}")
    t = rep["total"]
    mfu = f"{t['mfu']:.2%}" if t["mfu"] is not None else "-"
    lines.append(f"{'TOTAL':<24}{'':<8}{t['dispatches']:>8}"
                 f"{t['dispatch_wall_s']:>10.3f}{'':>9}{'':>12}{mfu:>8}")
    return "\n".join(lines)
