"""Every cell of BENCHMARK.json, and every cell held back from it, end to end
at the tests' tiny sizes, on the CPU by explicit choice: the printed line has
the contract's keys and, off the chip, not one device metric: a traced run
prints the one count that needs no device, ``real_slot_pct``."""
import json

import pytest

from fedbench_tiny import REPO, held_back, load, run_cell, tiny_checkout

CELLS = [(w["name"], w["chips"])
         for w in load(REPO + "/BENCHMARK.json")["workloads"]
         + [c["workload"] for c in held_back()]]


@pytest.mark.parametrize("name,chips", CELLS)
def test_cell_runs_tiny_and_prints_the_contracts_line(name, chips, tmp_path):
    trace = 1 if chips == 4 else 0          # the traced path, once
    r = run_cell(tiny_checkout(tmp_path, with_held_back=True), name,
                 trace=trace, chips=chips)
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == chips
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    # no device metric off the chip; the x4 tiny clients hold 6 of 2 x 4 slots
    assert line["metrics"] == ({"real_slot_pct": {"value": 75.0, "unit": "%"}}
                               if trace else {})
