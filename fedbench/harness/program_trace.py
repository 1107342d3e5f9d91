"""What the program itself says about a traced run: device time per named
scope of the round program, and the program's own host spans.

The trace (``--trace 1``) names a device op by its bare HLO text, without
metadata, so it cannot say which layer an op belongs to.  The program can:
``engine.round_fn.scope_map()`` gives {HLO instruction name -> scope label}
for the executable that ran (``fedml_tpu/obs/programs.py``; labels in
``fedml_tpu/obs/scopes.py``).  This module joins the two, on device 0,
inside the executions of the round module:

    scope_ms    label -> device SELF time per round, ms (median over the
                traced executions).  Every op is counted once, so the labels
                partition the round's busy time (``round_busy_ms``).
    take        is claimed first, by two rules: (1) the op's label is
                ``take`` (traced under ``fed_take``, or compiler-inserted
                data movement feeding such an op); (2) the op runs outside
                every ``while`` and its result or an operand leads with the
                resident stack's first two dimensions (clients a shard,
                batches a client) - the passes over the WHOLE stack that
                XLA hoists out of local training (PR 22's
                ``fusion.4014`` convert and ``copy.5376/.5377`` relayouts),
                whatever scope their root was traced under.  ``take_ops``
                lists what each rule counted.
    unknown     ops whose names the map does not hold.  Over 1 % of the
                round's self time means the map is not of the executable that
                ran: the device numbers are then withheld (None), not guessed.
    spans_ms    program span name -> median duration, ms (``/host:CPU``, every
                thread); ``program.dispatch`` only of the round's family.

A program without ``scope_map`` or without the spans (the parent of the PR
that added them) reads as None / no span: the metric is left out of the line.
"""
from __future__ import annotations

import collections
import glob
import json
import os
import re
import statistics

from fedbench.harness import manifest, trace_reduce as tr

UNKNOWN_LIMIT = 0.01


def reduce(path: str, scope_map, *, family=None, resident_dims=None) -> dict:
    """The join described above for one ``.xplane.pb`` (see module doc).
    ``scope_map`` None reads the spans only; ``resident_dims`` = the first two
    dimensions of a device's shard of the resident stack."""
    from fedml_tpu.obs import scopes
    ops, modules, spans = [], [], collections.defaultdict(list)
    for plane in tr.load(path).planes:
        if plane.name == "/device:TPU:0":
            lines = {ln.name: ln for ln in plane.lines}
            ops, modules = (sorted((e.start_ns, e.duration_ns, e.name)
                                   for e in lines[key].events) if key in lines else []
                            for key in ("XLA Ops", "XLA Modules"))
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                for e in ln.events:
                    if e.name in scopes.SPANS:
                        spans[e.name].append((e.start_ns, e.duration_ns, dict(e.stats)))
    spans_ms = {}
    for name, events in spans.items():
        if name == scopes.SPAN_DISPATCH and family is not None:
            events = [ev for ev in events if ev[2].get("family") == family]
        if events:
            spans_ms[name] = statistics.median(d for _, d, _ in events) / 1e6
    out = {"spans": dict(spans), "spans_ms": spans_ms}
    if scope_map is None or not ops or not modules:
        return out
    by_module = collections.Counter()
    for _, d, name in modules:
        by_module[name] += d
    program = by_module.most_common(1)[0][0]
    runs = [(s, s + d) for s, d, name in modules if name == program]
    axis = (re.compile(r"\[%d,%d[,\]]" % tuple(resident_dims))
            if resident_dims else None)
    per_run = [collections.Counter() for _ in runs]
    take_ops, unscoped_ops = collections.Counter(), collections.Counter()
    stack, i = [], 0                  # open ancestors: (end, is a while)
    selfs = tr.self_times(ops)
    for (s, d, hlo), own in zip(ops, selfs):
        while stack and stack[-1][0] <= s:
            stack.pop()
        in_loop = any(w for _, w in stack)
        category, name = tr.classify(hlo)
        stack.append((s + d, category == "control flow" and " while(" in hlo))
        while i < len(runs) and runs[i][1] <= s:
            i += 1
        if i == len(runs) or s < runs[i][0]:
            continue                  # not inside an execution of the round
        label = scope_map.get(name, "unknown")
        if label == "take":
            take_ops[(name, "fed_take")] += own
        elif axis is not None and not in_loop and axis.search(hlo):
            take_ops[(name, "resident axis, was " + label)] += own
            label = "take"
        elif label == scopes.UNSCOPED:
            unscoped_ops[name] += own
        per_run[i][label] += own
    if not runs:
        return out
    total = sum(sum(c.values()) for c in per_run)
    out["rounds"] = len(runs)
    out["unknown_share"] = (sum(c["unknown"] for c in per_run) / total) if total else 1.0
    labels = set().union(*per_run) | set(scopes.LABELS)
    out["scope_ms"] = {lb: statistics.median(c[lb] for c in per_run) / 1e6
                       for lb in labels}
    out["round_self_ms"] = statistics.median(sum(c.values()) for c in per_run) / 1e6
    n = len(runs)
    out["take_ops"] = [[name, rule, v / n / 1e6]
                       for (name, rule), v in take_ops.most_common(20)]
    out["unscoped_ops"] = [[name, v / n / 1e6] for name, v in unscoped_ops.most_common(20)]
    return out


def read(ctx) -> dict | None:
    """``reduce`` of the cell's trace with the engine's scope map, once per
    run (kept in ``ctx``); None without ``--trace 1`` or before the program
    has the spans' names."""
    if "program_trace" not in ctx:
        ctx["program_trace"] = _read(ctx)
    return ctx["program_trace"]


def _read(ctx):
    try:
        from fedml_tpu.obs import scopes  # noqa: F401  (the parent has none)
    except ImportError:
        return None
    if ctx.get("trace") is None:
        return None
    trace_dir = os.path.join(manifest.ROOT, ".fedbench_out", "trace", ctx["cell"].name)
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        return None
    engine = ctx["engine"]
    get_map = getattr(getattr(engine, "round_fn", None), "scope_map", None)
    scope_map = get_map() if callable(get_map) else None
    stack = getattr(engine, "_stack", None) or {}
    resident = next((a.sharding.shard_shape(a.shape)[:2] for a in stack.values()
                     if a.ndim >= 2), None)
    out = reduce(found[-1], scope_map, family=getattr(engine, "program_family", None),
                 resident_dims=resident)
    # beside the trace, for whoever looks at it by hand (PERF.md §5)
    with open(os.path.join(trace_dir, "program_trace.json"), "w") as f:
        json.dump({k: v for k, v in out.items() if k != "spans"}, f, indent=1)
    if scope_map is not None:
        with open(os.path.join(trace_dir, "scope_map.json"), "w") as f:
            json.dump(scope_map, f)
    return out


def scope_ms(ctx, label: str):
    """Device self time per round of one scope label, ms, or None."""
    pt = read(ctx)
    if not pt or "scope_ms" not in pt or pt["unknown_share"] > UNKNOWN_LIMIT:
        return None
    return pt["scope_ms"].get(label, 0.0)


def span_ms(ctx, name: str):
    """Median duration of one program span, ms, or None where the trace has
    none."""
    pt = read(ctx)
    return None if not pt else pt["spans_ms"].get(name)
