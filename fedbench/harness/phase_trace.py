"""Device time per named scope AND per phase of the training step: which pass
of ``value_and_grad`` an op of the round program belongs to.

``engine.round_fn.phase_map()`` gives {HLO instruction name -> phase} for the
executable that ran, beside ``scope_map()`` and from the same compile
(``fedml_tpu/obs/programs.py``; the rule, ``fedml_tpu/obs/scopes.py``):

    forward     under ``fed_forward``, not transposed
    recompute   under ``transpose(jvp(fed_forward))`` and, further in,
                ``rematted_computation``: what ``jax.checkpoint`` runs again
                inside the backward pass
    backward    under ``transpose(jvp(fed_forward))`` otherwise
    other       not under ``fed_forward`` (take, optimizer, aggregate, ...)

This module reduces the cell's trace once more with the PRODUCT of the two
maps - an instruction's label is its scope's where the phase is ``other`` and
``<scope>|<phase>`` elsewhere - through ``program_trace.reduce``: the same
join, the same take rules, the same medians, so the product refines what
``program_trace`` books (a scope's phases sum to the scope).

    table       scope label -> {phase -> device SELF time per round, ms}
    phase_ms    phase -> the sum of its column.  The three phases partition
                what descends from ``fed_forward``.

A fusion has its root's phase: a recomputed elementwise op that XLA fuses into
a backward consumer is booked ``backward``, so ``recompute`` is exact for
matrix products, custom calls and fusions rooted in them, and a floor for
elementwise work.  (A kernel the compiler names itself - XLA:TPU's grouped
product, ``ragged-dot-none`` - has the phase of its producers, and the
checkpoint's own barrier that of what it feeds:
``programs.maps_of_hlo_text``.)  A program without ``phase_map`` (the parent
of the PR that added it) reads as None: the metric is left out of the line.
"""
from __future__ import annotations

import collections
import glob
import json
import os
import time

from fedbench.harness import manifest, program_trace

SEPARATOR = "|"
OTHER = "other"


def product_map(scope_map: dict, phase_map: dict) -> dict:
    """{instruction -> scope label, or ``scope|phase`` under ``fed_forward``}."""
    return {name: scope if phase_map.get(name, OTHER) == OTHER
            else scope + SEPARATOR + phase_map[name]
            for name, scope in scope_map.items()}


def reduce(path: str, scope_map: dict, phase_map: dict, **take_rules) -> dict:
    """``program_trace.reduce`` of one ``.xplane.pb`` with the product map,
    and its ``scope_ms`` laid out as the scope x phase table."""
    out = program_trace.reduce(path, product_map(scope_map, phase_map), **take_rules)
    if "scope_ms" not in out:
        return out
    table = collections.defaultdict(dict)
    for label, ms in out["scope_ms"].items():
        scope, _, phase = label.partition(SEPARATOR)
        if ms or phase:
            table[scope][phase or OTHER] = ms
    phase_ms = collections.Counter()
    for row in table.values():
        phase_ms.update(row)
    out["table"], out["phase_ms"] = dict(table), dict(phase_ms)
    return out


def read(ctx) -> dict | None:
    """``reduce`` of the cell's trace, once per run (kept in ``ctx``); None
    without a trace, for a program without ``phase_map``, or where the map is
    not of the executable that ran (``program_trace.UNKNOWN_LIMIT``)."""
    if "phase_trace" not in ctx:
        ctx["phase_trace"] = _read(ctx)
    return ctx["phase_trace"]


def _read(ctx):
    engine = ctx["engine"]
    round_fn = getattr(engine, "round_fn", None)
    get_phases = getattr(round_fn, "phase_map", None)
    if ctx.get("trace") is None or not callable(get_phases):
        return None
    # the trace, the family and the resident stack's leading dimensions as
    # program_trace._read finds them (it keeps none of them in ``ctx``)
    trace_dir = os.path.join(manifest.ROOT, ".fedbench_out", "trace", ctx["cell"].name)
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    scope_map, phase_map = round_fn.scope_map(), get_phases()
    if not found or scope_map is None or phase_map is None:
        return None
    t0 = time.perf_counter()
    stack = getattr(engine, "_stack", None) or {}
    resident = next((a.sharding.shard_shape(a.shape)[:2] for a in stack.values()
                     if a.ndim >= 2), None)
    out = reduce(found[-1], scope_map, phase_map,
                 family=getattr(engine, "program_family", None), resident_dims=resident)
    if "table" not in out or out["unknown_share"] > program_trace.UNKNOWN_LIMIT:
        return None
    # what tracing on costs here, after the window: the second pass over the
    # trace (the maps were made when program_trace's readers asked)
    out["reduce_s"] = time.perf_counter() - t0
    # beside program_trace.json and scope_map.json, for whoever looks at the
    # trace by hand (PERF.md §5)
    with open(os.path.join(trace_dir, "phase_trace.json"), "w") as f:
        json.dump({k: out[k] for k in ("rounds", "round_self_ms", "phase_ms", "table",
                                       "reduce_s")}, f, indent=1)
    with open(os.path.join(trace_dir, "phase_map.json"), "w") as f:
        json.dump(phase_map, f)
    return out


def phase_ms(ctx, phase: str):
    """Device self time per round of one phase, all scopes, ms, or None."""
    pt = read(ctx)
    return None if pt is None else pt["phase_ms"].get(phase, 0.0)
