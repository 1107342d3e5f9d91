"""BENCHMARK.json against the contract's limits and against the files it names."""
import importlib
import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _metrics(manifest):
    return manifest["end_to_end"] + manifest["per_layer"]


def test_keys_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    assert 2 <= len(manifest["workloads"]) <= 24
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 4)
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    for path in manifest["paths"]:
        assert os.path.isdir(os.path.join(REPO, path))


def test_every_name_and_unit_is_in_the_allowed_characters(manifest):
    names = [m["name"] for m in _metrics(manifest)]
    names += [w["name"] for w in manifest["workloads"]]
    names += [w["traffic"] for w in manifest["workloads"]]
    names += [c["name"] for c in manifest["configs"]]
    names += [k for c in manifest["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.match(n), n
    for m in _metrics(manifest):
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for group in ("end_to_end", "per_layer", "workloads", "configs"):
        seen = [e["name"] for e in manifest[group]]
        assert len(seen) == len(set(seen))
    for e in manifest["workloads"] + manifest["configs"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"], e["name"]


def test_entries_have_exactly_the_contracts_keys(manifest):
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in {e["name"] for e in manifest["end_to_end"]}
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}


def test_every_cell_resolves_to_its_files_and_reports_enough(manifest):
    from fedbench.harness.manifest import Cell
    pairs = set()
    for w in manifest["workloads"]:
        cell = Cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        e2e = [m["name"] for m in cell.metrics("end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = cell.metrics("per_layer")
        assert layer and all(m["moves"] in e2e for m in layer)
        for key in ("source", "reduced", "assumed", "reference", "model", "check"):
            assert key in cell.config
    used = {w["config"] for w in manifest["workloads"]}
    assert used == {c["name"] for c in manifest["configs"]}


def test_the_command_has_one_size():
    """No size switch on the benchmark's command: what the driver runs is
    what the files say."""
    import fedbench.run
    with pytest.raises(SystemExit):
        fedbench.run.main(["--workload", "resnet18gn.xdev10of4000", "--scale", "tiny"])


def test_held_back_cells_resolve_to_files_and_are_not_in_the_manifest(manifest):
    with open(os.path.join(REPO, "fedbench", "held_back.json")) as f:
        cells = json.load(f)["cells"]
    for cell in cells:
        w, c = cell["workload"], cell["config"]
        assert w["name"] not in {x["name"] for x in manifest["workloads"]}
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert w["config"] == c["name"] and cell["why_held_back"]
        assert os.path.isfile(os.path.join(REPO, c["file"]))
        assert os.path.isfile(os.path.join(REPO, "fedbench", "traffic",
                                           w["traffic"] + ".json"))


def test_each_metric_has_a_reader_that_declares_the_same(manifest):
    for group in ("end_to_end", "per_layer"):
        for m in manifest[group]:
            mod = importlib.import_module("fedbench.layer_metrics." + m["name"])
            assert mod.UNIT == m["unit"] and mod.SOURCE == m["source"], m["name"]
            if group == "per_layer":
                assert (mod.LAYER, mod.MOVES) == (m["layer"], m["moves"]), m["name"]
