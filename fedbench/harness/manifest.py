"""Resolve a cell of BENCHMARK.json to its files, by name.

A cell names a configuration (-> ``configs[].file``) and a traffic mix
(-> ``<first path>/traffic/<traffic>.json``); a metric names its reader
(-> ``fedbench/layer_metrics/<name>.py``).  There is one size: the files'.
The CPU tests run a scratch copy of the benchmark whose files they cut down
themselves (tests/fedbench/fedbench_tiny.py).
"""
from __future__ import annotations

import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "fedbench")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with its configuration, traffic and the
    metrics that are reported in it."""

    def __init__(self, name: str):
        self.manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.manifest["workloads"]}
        if name not in cells:
            raise SystemExit(f"fedbench: unknown workload {name!r}; "
                             f"BENCHMARK.json has {sorted(cells)}")
        self.entry = cells[name]
        self.name, self.chips = name, int(self.entry["chips"])
        cfg_entry = next(c for c in self.manifest["configs"]
                         if c["name"] == self.entry["config"])
        self.config = load_json(os.path.join(ROOT, cfg_entry["file"]))
        self.traffic = load_json(os.path.join(
            ROOT, self.manifest["paths"][0], "traffic",
            self.entry["traffic"] + ".json"))

    def metrics(self, group: str) -> list[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics reported in this
        cell: those without a ``workloads`` list, or that list this cell."""
        return [m for m in self.manifest[group]
                if "workloads" not in m or self.name in m["workloads"]]
