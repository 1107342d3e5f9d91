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
* an HLO flop/byte census joins in from a ``tools/hlo_copy_audit.py
  --out`` artifact (``load_census()``) or a caller's own numbers
  (``ProgramFamily.attach_census``), giving per-family and whole-run
  FLOP/bytes-moved totals (no utilization: dispatch wall is HOST time
  of an asynchronous enqueue, so the "MFU" this module once derived
  from it measured nothing — the device trace's ``round_roofline`` is
  that number, PERF.md §3);
* ``scope_map()`` and ``phase_map()`` name the scopes and the phases of
  the compiled program: HLO instruction name -> ``fed_*`` scope label /
  forward, recompute, backward or other (obs/scopes.py), which is what
  lets a reader split a device trace whose events carry bare HLO names
  by layer and by pass;
* every family maps to a canonical timeline stage
  (obs/timeline.py PROGRAM_FAMILY_STAGES), so the profile table groups
  into the same taxonomy as the round critical path.

``report(since=snapshot())`` is the standing replacement for the
manual profile session: per-family dispatch counts, wall p50/p95,
compile seconds and flops/bytes per dispatch.
"""
from __future__ import annotations

import os
import re
import threading
import time
from typing import Any, Optional

from fedml_tpu.obs import scopes
from fedml_tpu.obs.metrics import quantile_from_cumulative

_lock = threading.Lock()
_families: dict[str, "ProgramFamily"] = {}
_tls = threading.local()


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
    """Peak FLOP/s of the device JAX reports.
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
    each dispatch (and opens the ``program.dispatch`` span around it)
    and marks the thread's current family for compile attribution.
    `lower` and every other attribute delegate to the wrapped jit, so
    AOT consumers (hlo_copy_audit's ``fn.lower(*args).compile()``) see
    the real thing."""

    __slots__ = ("_fn", "_family", "_signature", "_maps", "_on_result")

    def __init__(self, fn, family: ProgramFamily, on_result=None):
        self._fn = fn
        self._family = family
        # called with what a dispatch returned, before the caller sees it
        # (the engine keeps the round's counters: no device sync here)
        self._on_result = on_result
        self._signature = None      # abstract (args, kwargs), 1st dispatch
        self._maps = None           # (scope map, phase map), on demand

    @property
    def inner(self):
        return self._fn

    @property
    def family(self) -> str:
        return self._family.name

    def __call__(self, *args, **kwargs):
        fam = self._family
        if self._signature is None:
            self._signature = _abstract_signature(args, kwargs)
        prev = getattr(_tls, "family", None)
        _tls.family = fam.name
        from fedml_tpu import obs
        t0 = time.perf_counter()
        try:
            with obs.span(scopes.SPAN_DISPATCH, family=fam.name,
                          n=int(fam._handles()[0].value)):
                out = self._fn(*args, **kwargs)
            if self._on_result is not None:
                self._on_result(out)
            return out
        finally:
            dt = time.perf_counter() - t0
            _tls.family = prev
            fam.observe_dispatch(dt)

    def scope_map(self) -> Optional[dict]:
        """{HLO instruction name -> scope label} of the compiled program
        (labels: obs/scopes.py LABELS; the labelling rule:
        ``maps_of_hlo_text``), or None before the first dispatch /
        for a callable that cannot be lowered.

        Nothing is computed until this or ``phase_map()`` is called: the
        first dispatch only remembered the abstract signature (shape,
        dtype, sharding); here it is lowered and compiled again (the
        persistent compile cache has the executable) and the optimized
        module's text is walked once, for both maps.  The cache keys on
        the module WITHOUT its metadata, so the executable it returns may
        carry another build's names (the same program before it had
        scopes): where the text lacks a scope that the lowering has, it
        is compiled once more past the cache.  Instruction names do not
        depend on metadata, so either text names the ops of the
        executable that ran.  Every instruction of every computation is
        listed (fused bodies too); a device trace names only the ones
        that ran as ops of their own."""
        maps = self._both_maps()
        return None if maps is None else maps[0]

    def phase_map(self) -> Optional[dict]:
        """{HLO instruction name -> phase} of the same executable
        (phases: obs/scopes.py PHASES, the rule ``scopes.phase_of``), from
        the same compile and the same walk as ``scope_map()``: asking for
        both costs one of each, in either order.

        A fusion has its root's phase, as it has its root's scope: a
        recomputed elementwise op that XLA fuses into a backward consumer
        is booked ``backward``.  So ``recompute`` is exact for matrix
        products, custom calls and fusions rooted in them, and a floor
        for elementwise work (``maps_of_hlo_text`` has the rule for an
        instruction without a traced name)."""
        maps = self._both_maps()
        return None if maps is None else maps[1]

    def _both_maps(self):
        if self._maps is None:
            if self._signature is None or not hasattr(self._fn, "lower"):
                return None
            args, kwargs = self._signature
            lowered = self._fn.lower(*args, **kwargs)
            text = lowered.compile().as_text()
            missing = [s for s in scopes.LABEL_OF_SCOPE if s not in text]
            if missing:
                traced = lowered.as_text(debug_info=True)
                if any(s in traced for s in missing):
                    text = _compile_past_the_cache(lowered).as_text()
            self._maps = maps_of_hlo_text(text)
        return self._maps

    def lower(self, *args, **kwargs):
        return self._fn.lower(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._fn, name)

    def __repr__(self):
        return (f"InstrumentedProgram({self._family.name}, "
                f"{self._fn!r})")


def _compile_past_the_cache(lowered):
    """Compile with the persistent cache off, so the executable's
    metadata is this lowering's own; nothing is written to the cache.
    A `Lowered` keeps the executable it was first given unless compiler
    options are passed: the one passed here is XLA's default."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return lowered.compile(
            compiler_options={"xla_dump_hlo_as_text": False})
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


def _abstract_signature(args, kwargs):
    """The call's arguments as `jax.ShapeDtypeStruct`s — what
    ``lower()`` needs to rebuild the same program without holding a
    (donated) buffer.  An uncommitted array keeps no sharding, as jit
    treats it; non-array leaves pass through."""
    import jax

    def abstract(a):
        if not isinstance(a, jax.Array):
            return a
        sharding = a.sharding if getattr(a, "committed", True) else None
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)

    return jax.tree.map(abstract, (args, kwargs))


# an HLO module's text: computations `[ENTRY ]%name (...) -> ... {` at
# column 0, their instructions `  [ROOT ]%name = ...` indented
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")
_LOOP_COMPUTATION = re.compile(r"(?:body|condition)=%?([\w.\-]+)")
_COMPILER_KERNEL = 'custom_call_target="tpu_custom_call"'


def scope_map_of_hlo_text(text: str) -> dict:
    """{instruction name -> scope label} from an optimized HLO module's
    text: the first of ``maps_of_hlo_text``."""
    return maps_of_hlo_text(text)[0]


def maps_of_hlo_text(text: str) -> tuple:
    """({instruction name -> scope label}, {instruction name -> phase})
    from one walk of an optimized HLO module's text (see
    InstrumentedProgram.scope_map / .phase_map).

    An instruction whose ``op_name`` is a traced op's name stack
    (``jit(...)/...``) is labelled by it: the innermost ``fed_*``
    component (``scopes.label_of``) and the pass the outermost
    ``fed_forward`` runs in (``scopes.phase_of``).  A fusion carries the
    ``op_name`` of its root: that is the granularity — a fusion that
    merged ops of two scopes, or of two phases, is booked whole to its
    root's.  One without (no metadata, or only an argument's name) was put
    there by the compiler — an async copy-start/-done or slice into
    faster memory, a relayout copy of an argument, the tuple plumbing of a
    while: it reads the name of the nearest named instruction that
    consumes it — the layer and the pass it moves data for — else of the
    nearest that produces its operands, else of the ``while`` whose body
    it sits in (a carry that is only copied through), else it is
    ``unscoped`` / ``other``.

    One kind of nameless instruction is work, not data movement: a kernel
    XLA:TPU builds itself from an op of the program and names itself (the
    grouped product: ``jax.lax.ragged_dot`` becomes a ``tpu_custom_call``
    called ``ragged-dot-none``).  The SCOPES follow the rule above, as
    they did before there were phases.  For the PHASES such a kernel
    first reads the name of its nearest named producer, and then counts
    as named for the instructions around it.  Values only flow forward ->
    recompute -> backward, so a nameless op runs no earlier than its
    producers and no later than its consumers: a re-run grouped product
    takes rows gathered in the re-run and feeds the hand-written backward
    rule — its consumer would call it, and the copy of the experts made
    for it, ``backward``.  The same pass un-names what carries the name of
    a ``jax.checkpoint`` call itself (``…/remat2``: the barrier in front
    of the re-run and of the layer's backward pass, and the copies the
    compiler hangs on it — the experts' weights again): it is the
    checkpoint's plumbing, and has the phase of what it feeds."""
    own, operands, users, comp_of, loop_of, kernels = {}, {}, {}, {}, {}, set()
    comp = None
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            h = _COMPUTATION.match(line)
            comp = h.group(1) if h else comp
            continue
        name = m.group(1)
        op = _OP_NAME.search(line, m.end())
        own[name] = (op.group(1)
                     if op and op.group(1).startswith("jit(") else None)
        operands[name] = _OPERAND.findall(line, m.end())
        comp_of[name] = comp
        if " while(" in line:
            for c in _LOOP_COMPUTATION.findall(line, m.end()):
                loop_of[c] = name
        if own[name] is None and _COMPILER_KERNEL in line:
            kernels.add(name)
    for name, ops in operands.items():
        # computations named by calls=/body= are not instructions
        operands[name] = ops = [o for o in ops if o in own]
        for o in ops:
            users.setdefault(o, []).append(name)

    def nearest(named, name, edges):
        seen, frontier = {name}, [name]
        while frontier:
            nxt = []
            for n in frontier:
                for e in edges.get(n, ()):
                    if named[e] is not None:
                        return named[e]
                    if e not in seen:
                        seen.add(e)
                        nxt.append(e)
            frontier = nxt
        return None

    def names_read(named):
        """The op_name each instruction reads: its own, or a neighbour's."""
        read = {name: (op if op is not None else nearest(named, name, users)
                       or nearest(named, name, operands))
                for name, op in named.items()}

        def resolved(name):
            while name is not None and read[name] is None:
                name = loop_of.get(comp_of[name])
            return "" if name is None else read[name]

        return {name: resolved(name) for name in read}

    def labelled(read, label_of):
        label = {op: label_of(op) for op in set(read.values())}
        return {name: label[op] for name, op in read.items()}

    read = names_read(own)
    scope_map = labelled(read, scopes.label_of)
    call = "/" + scopes.REMAT_CALL
    barriers = [name for name, op in own.items() if op and op.endswith(call)]
    if kernels or barriers:
        named = {**own, **dict.fromkeys(barriers)}
        for name in kernels:
            named[name] = nearest(named, name, operands) or read[name]
        read = names_read(named)
    return scope_map, labelled(read, scopes.phase_of)


def instrument(family: str, fn, on_result=None) -> InstrumentedProgram:
    """Wrap one jitted program under `family`.  Idempotent-ish: an
    already-instrumented fn is re-tagged, not double-wrapped (double
    timing would inflate the family's dispatch walls)."""
    if isinstance(fn, InstrumentedProgram):
        fn = fn.inner
    return InstrumentedProgram(fn, register(family), on_result)


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
           publish_gauges: bool = True) -> dict:
    """Per-family profile over the window since `since` (a snapshot();
    None = since process start / family registration).  Returns

        {"window_s", "families": [
            {family, stage, dispatches, dispatch_wall_s,
             dispatch_p50_s, dispatch_p95_s, compile_seconds,
             flops_per_dispatch, bytes_per_dispatch, flops_total,
             bytes_total}, ...],
         "processes": [...],        # per-process breakdown rows from a
                                    # multihost run's origin-labeled
                                    # merged series (ISSUE 13)
         "total": {...}}            # the whole-run row

    flops/bytes are null without census numbers.  `publish_gauges`
    mirrors the rows' bytes into ``program_bytes_moved_total{family}``."""
    from fedml_tpu import obs
    reg = obs.registry()
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
            "census_source": fam.census_source,
        })
        if publish_gauges and bytes_total is not None:
            obs.gauge("program_bytes_moved_total",
                      family=name).set(bytes_total)
    total_flops = [r["flops_total"] for r in rows
                   if r["flops_total"] is not None]
    total_bytes = [r["bytes_total"] for r in rows
                   if r["bytes_total"] is not None]
    total = {
        "dispatches": sum(r["dispatches"] for r in rows),
        "dispatch_wall_s": round(sum(r["dispatch_wall_s"]
                                     for r in rows), 6),
        "flops_total": sum(total_flops) if total_flops else None,
        "bytes_total": sum(total_bytes) if total_bytes else None,
    }
    return {
        "window_s": (round(window_s, 3) if window_s is not None
                     else None),
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
             f"{'p95 ms':>9}{'GFLOP/disp':>12}"]
    for r in rep["families"]:
        gf = (f"{r['flops_per_dispatch'] / 1e9:.3f}"
              if r["flops_per_dispatch"] is not None else "-")
        lines.append(
            f"{r['family']:<24}{r['stage']:<8}{r['dispatches']:>8}"
            f"{r['dispatch_wall_s']:>10.3f}"
            f"{r['dispatch_p95_s'] * 1e3:>9.2f}{gf:>12}")
    t = rep["total"]
    lines.append(f"{'TOTAL':<24}{'':<8}{t['dispatches']:>8}"
                 f"{t['dispatch_wall_s']:>10.3f}")
    return "\n".join(lines)
