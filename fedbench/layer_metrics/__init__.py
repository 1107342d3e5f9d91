"""Metric readers, one small module per metric, found by the metric's name in
BENCHMARK.json: ``fedbench/layer_metrics/<name>.py`` declares

    LAYER, UNIT, SOURCE, MOVES       what the manifest entry must repeat
    read(ctx) -> float | None        None = nothing to read; the line omits it

``ctx`` holds the window's record (``window``), the reduced trace (``trace``,
None without ``--trace 1``), the engine, the cell and the device.  A later PR
adds a metric by adding a file here and an entry in BENCHMARK.json.
"""
from __future__ import annotations

import importlib


def module(name: str):
    return importlib.import_module(f"fedbench.layer_metrics.{name}")


def read(entry: dict, ctx: dict):
    """The metric's value, or None.  Off the chip (JAX_PLATFORMS=cpu) only
    counts are reported: a time, a rate or a share measured on a CPU is never
    printed under the name of a device metric."""
    mod = module(entry["name"])
    if not ctx["on_chip"] and mod.SOURCE != "program_counter":
        return None
    value = mod.read(ctx)
    return None if value is None else float(value)


def per_round_ms(seconds: list) -> float | None:
    """Median of per-round host times, in ms."""
    if not seconds:
        return None
    ordered = sorted(seconds)
    return 1e3 * ordered[len(ordered) // 2]
