"""``fedbench/harness/phase_trace.py`` and its three readers: the split of
device time by scope AND phase refines the split by scope, checked on the trace
recorded on the chip; off the chip, and on a program without ``phase_map()``,
the readers read nothing."""
import gzip
import json
import os
import shutil

import pytest

from fedbench import layer_metrics
from fedbench.harness import manifest, phase_trace, program_trace
from fedbench_tiny import REPO, load, run_cell, tiny_checkout

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "tiny_xdev_scopes_v5e.xplane.pb.gz")
SCOPE_MAP = os.path.join(HERE, "tiny_xdev_scopes_v5e.scope_map.json.gz")
RULES = dict(family="fedavg_resident", resident_dims=(12, 2))
READERS = ("phase_forward_ms", "phase_recompute_ms", "phase_backward_ms")
LM_CELLS = ["ouro2p6b.silo4of256t1024", "lfm2moe24b.lora4of256t2048"]
UNDER_FED_FORWARD = ("forward", "backward")        # the recorded ResNet's labels


@pytest.fixture(scope="module")
def maps():
    """The recorded scope map (PR 23: a ResNet, no inner scope, no checkpoint)
    and the phase map that program has: its two ``fed_forward`` labels."""
    with gzip.open(SCOPE_MAP, "rt") as f:
        scope_map = json.load(f)
    return scope_map, {name: label if label in UNDER_FED_FORWARD else "other"
                       for name, label in scope_map.items()}


@pytest.fixture(scope="module")
def by_scope(maps):
    return program_trace.reduce(FIXTURE, maps[0], **RULES)


@pytest.fixture(scope="module")
def by_phase(maps):
    return phase_trace.reduce(FIXTURE, *maps, **RULES)


def _ns(ms: float) -> int:
    """Whole half-nanoseconds: a median of four rounds' integer ns."""
    return round(ms * 2e6)


def test_phases_partition_what_the_scopes_book_under_fed_forward(by_scope, by_phase):
    assert by_phase["rounds"] == 4 and by_phase["unknown_share"] == 0.0
    assert _ns(by_phase["round_self_ms"]) == _ns(by_scope["round_self_ms"])
    for label in UNDER_FED_FORWARD:
        assert by_phase["table"][label] == {label: by_scope["scope_ms"][label]}
        assert _ns(by_phase["phase_ms"][label]) == _ns(by_scope["scope_ms"][label])
    assert "recompute" not in by_phase["phase_ms"]
    # every other scope keeps its time, in the column without a phase
    outside = {lb: ms for lb, ms in by_scope["scope_ms"].items()
               if ms and lb not in UNDER_FED_FORWARD}
    assert set(outside) == {"take", "optimizer", "local_other", "aggregate"}
    for label, ms in outside.items():
        assert by_phase["table"][label] == {"other": ms}
    assert _ns(by_phase["phase_ms"]["other"]) == sum(map(_ns, outside.values()))
    assert sum(map(_ns, by_phase["phase_ms"].values())) \
        == sum(map(_ns, by_scope["scope_ms"].values()))
    assert by_phase["take_ops"] == by_scope["take_ops"]


@pytest.mark.parametrize("label,ms", [
    ("take", 0.0029075), ("forward", 0.0676995), ("backward", 0.0956635),
    ("optimizer", 0.000206), ("local_other", 0.0358375), ("aggregate", 0.028992),
])
def test_the_recorded_numbers_stand(by_scope, by_phase, label, ms):
    """What ``test_fedbench_program_trace.py`` pins, read through both."""
    assert by_scope["scope_ms"][label] == pytest.approx(ms, rel=1e-9)
    assert sum(by_phase["table"][label].values()) == pytest.approx(ms, rel=1e-9)


def test_a_recomputed_half_of_the_backward_pass_gets_its_own_column(maps, by_scope):
    """The recorded program recomputes nothing; calling every second backward
    instruction ``recompute`` splits that label's time in two and moves
    nothing else (medians over four rounds: the halves add up nearly)."""
    scope_map, phase_map = maps
    backward = sorted(n for n, lb in scope_map.items() if lb == "backward")
    marked = dict(phase_map, **{n: "recompute" for n in backward[::2]})
    out = phase_trace.reduce(FIXTURE, scope_map, marked, **RULES)
    row = out["table"]["backward"]
    assert set(row) == {"backward", "recompute"} and min(row.values()) > 0
    assert sum(row.values()) == pytest.approx(by_scope["scope_ms"]["backward"], rel=1e-2)
    assert out["phase_ms"]["recompute"] == row["recompute"]
    assert out["table"]["forward"] == {"forward": by_scope["scope_ms"]["forward"]}


class _RoundFn:
    def __init__(self, scope_map, phase_map):
        self.scope_map, self.phase_map = (lambda: scope_map), (lambda: phase_map)


class _Engine:
    program_family = "fedavg_resident"

    def __init__(self, round_fn):
        self.round_fn = round_fn


class _Cell:
    name = "recorded"


def test_read_joins_the_cells_trace_and_leaves_the_table_beside_it(
        maps, by_scope, tmp_path, monkeypatch):
    monkeypatch.setattr(manifest, "ROOT", str(tmp_path))
    trace_dir = tmp_path / ".fedbench_out" / "trace" / _Cell.name
    (trace_dir / "plugins" / "profile" / "t").mkdir(parents=True)
    with gzip.open(FIXTURE) as src, open(
            trace_dir / "plugins" / "profile" / "t" / "h.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    ctx = {"engine": _Engine(_RoundFn(*maps)), "cell": _Cell(), "trace": {},
           "on_chip": True}
    pt = phase_trace.read(ctx)
    assert pt is ctx["phase_trace"] is phase_trace.read(ctx)         # once a run
    entries = {m["name"]: m for m in load(REPO + "/BENCHMARK.json")["per_layer"]}
    got = {name: layer_metrics.read(entries[name], ctx) for name in READERS}
    # (no resident stack on this engine: the take's second rule is off)
    want = program_trace.reduce(str(next(trace_dir.rglob("*.xplane.pb"))), maps[0],
                                family="fedavg_resident")["scope_ms"]
    assert got == {"phase_forward_ms": want["forward"], "phase_recompute_ms": 0.0,
                   "phase_backward_ms": want["backward"]}
    doc = load(str(trace_dir / "phase_trace.json"))
    assert doc["table"] == pt["table"] and doc["rounds"] == 4
    assert doc["reduce_s"] > 0
    # a map that is not of the executable that ran: withheld, not guessed
    stale = {"engine": _Engine(_RoundFn({"no_such_op": "forward"}, {"no_such_op": "forward"})),
             "cell": _Cell(), "trace": {}, "on_chip": True}
    assert phase_trace.read(stale) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_repeats_its_entry_and_reads_nothing_without_chip_trace_or_phase_map(name):
    entry = next(m for m in load(REPO + "/BENCHMARK.json")["per_layer"]
                 if m["name"] == name)
    mod = layer_metrics.module(name)
    assert entry["workloads"] == LM_CELLS and entry["better"] == "lower"
    assert (entry["layer"], entry["unit"], entry["source"], entry["moves"]) \
        == (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) \
        == ("local training", "ms/round", "device_trace", "rounds_per_s")

    class Parent:                       # a program with scope_map() alone
        round_fn = _RoundFn({}, {})
    del Parent.round_fn.phase_map
    has_both = _Engine(_RoundFn({}, {}))
    for ctx in ({"engine": has_both, "cell": _Cell(), "trace": {}, "on_chip": False},
                {"engine": has_both, "cell": _Cell(), "trace": None, "on_chip": True},
                {"engine": Parent(), "cell": _Cell(), "trace": {}, "on_chip": True}):
        assert layer_metrics.read(entry, ctx) is None


@pytest.mark.parametrize("cell,counts", [
    (LM_CELLS[0], {"real_slot_pct", "batch_trips_pct"}),
    (LM_CELLS[1], {"real_slot_pct", "expert_load_max_over_mean"})])
def test_tiny_traced_line_on_the_cpu_is_the_counters_alone(cell, counts, tmp_path):
    """The two cells that list the phase readers, traced, off the chip: the
    line carries the names it carried (program counters only)."""
    r = run_cell(tiny_checkout(tmp_path), cell, trace=1)
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and set(line["metrics"]) == counts
