"""Device time of the KERNELS under a scope: the ops of the round program
that are custom calls (a Pallas kernel, XLA:TPU's own grouped product) and
carry a given scope label, apart from the fusions, copies and reductions
around them that the scope's label also claims.

The trace names a device op by its HLO text, so it says which ops are custom
calls (``trace_reduce.classify``); ``engine.round_fn.scope_map()`` says which
scope each belongs to.  This module reduces the cell's trace once more with the
scope map in which a custom call's label is ``<scope>.kernel``, through
``phase_trace.reduce`` (and so ``program_trace.reduce``): the same join, the
same executions of the round, the same medians as the scope split, so a
scope's kernels are a part of its ``scope_ms`` - and, where the program has a
``phase_map``, split by the pass they run in.  The table (scope or
``<scope>.kernel`` -> {phase -> device self time per round, ms}) is left as
``kernel_trace.json`` beside ``program_trace.json``: the scope x phase table of
a cell that lists no ``phase_*`` reader.  A program without a scope map, a
trace without the kernel, or a map that is not of the executable that ran
reads as None.
"""
from __future__ import annotations

import glob
import json
import os

from fedbench.harness import manifest, phase_trace, program_trace, trace_reduce as tr

MARK = ".kernel"


def read(ctx) -> dict | None:
    """{scope label -> device self time per round of its custom calls, ms},
    once per run (kept in ``ctx``)."""
    if "kernel_trace" not in ctx:
        ctx["kernel_trace"] = _read(ctx)
    return ctx["kernel_trace"]


def _read(ctx):
    engine = ctx["engine"]
    round_fn = getattr(engine, "round_fn", None)
    get_map = getattr(round_fn, "scope_map", None)
    if ctx.get("trace") is None or not callable(get_map):
        return None
    scope_map = get_map()
    get_phases = getattr(round_fn, "phase_map", None)
    phase_map = (get_phases() if callable(get_phases) else None) or {}
    # the trace, the family and the resident stack's leading dimensions as
    # program_trace._read finds them (it keeps none of them in ``ctx``)
    trace_dir = os.path.join(manifest.ROOT, ".fedbench_out", "trace", ctx["cell"].name)
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not found or scope_map is None:
        return None
    kernels = {tr.classify(e.name)[1]
               for plane in tr.load(found[-1]).planes if plane.name == "/device:TPU:0"
               for line in plane.lines if line.name == "XLA Ops"
               for e in line.events if tr.classify(e.name)[0] == "custom-call"}
    marked = {name: label + MARK if name in kernels else label
              for name, label in scope_map.items()}
    stack = getattr(engine, "_stack", None) or {}
    resident = next((a.sharding.shard_shape(a.shape)[:2] for a in stack.values()
                     if a.ndim >= 2), None)
    out = phase_trace.reduce(found[-1], marked, phase_map,
                             family=getattr(engine, "program_family", None),
                             resident_dims=resident)
    if "table" not in out or out["unknown_share"] > program_trace.UNKNOWN_LIMIT:
        return None
    with open(os.path.join(trace_dir, "kernel_trace.json"), "w") as f:
        json.dump({"rounds": out.get("rounds"), "table": out["table"]}, f, indent=1)
    return {scope[:-len(MARK)]: sum(row.values())
            for scope, row in out["table"].items() if scope.endswith(MARK)}


def kernel_ms(ctx, label: str):
    """Device self time per round of the custom calls under one scope label,
    ms, or None where there is nothing to read."""
    kt = read(ctx)
    return None if not kt else kt.get(label) or None
