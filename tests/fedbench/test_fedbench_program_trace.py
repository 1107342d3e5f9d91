"""``fedbench/harness/program_trace.py``: the program's spans share the
profiler's clock with the harness's annotations (pinned on a CPU trace of the
harness's own loop), and the per-scope split of device time is checked on a
trace recorded on the chip."""
import collections
import copy
import glob
import gzip
import json
import os

import jax
import pytest

from fedbench.harness import build, loop, program_trace, trace_reduce
from fedbench_tiny import tiny_doc

HERE = os.path.dirname(os.path.abspath(__file__))
ROUNDS = 3


@pytest.fixture(scope="module", params=[False, True], ids=["resident", "streamed"])
def traced(request, tmp_path_factory):
    """(engine, path of a CPU trace of ROUNDS rounds of the harness's window,
    every /host:CPU event by name)."""
    traffic = copy.deepcopy(tiny_doc("traffic", "xdev10of4000"))
    traffic["engine"]["args"]["streaming"] = request.param
    data = build.make_data(traffic, seed=3)
    eng = build.make_engine(tiny_doc("configs", "resnet18gn_cifar"), traffic,
                            data, seed=3)
    state = loop.State(eng, eng.init_variables(), seed=3)
    loop.run_rounds(state, depth=2, rounds=2)                  # compile
    d = str(tmp_path_factory.mktemp("trace"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(d, profiler_options=options)
    try:
        loop.run_rounds(state, depth=2, rounds=ROUNDS)
    finally:
        jax.profiler.stop_trace()
    loop.join_prefetch(eng)
    path = glob.glob(d + "/**/*.xplane.pb", recursive=True)[0]
    events = collections.defaultdict(list)
    for plane in trace_reduce.load(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    events[e.name].append((e.start_ns, e.start_ns + e.duration_ns,
                                           dict(e.stats)))
    return eng, path, events


def _inside(inner, outers):
    return sum(o[0] <= inner[0] and inner[1] <= o[1] for o in outers) == 1


def test_program_spans_lie_inside_the_harness_annotations(traced):
    eng, _, ev = traced
    assert len(ev["dispatch"]) == len(ev["sample+args"]) == ROUNDS
    mine = [e for e in ev["program.dispatch"]
            if e[2]["family"] == eng.program_family]
    assert len(mine) == ROUNDS
    assert all(_inside(e, ev["dispatch"]) for e in mine)
    assert len(ev["round.sample"]) >= ROUNDS
    assert all(_inside(e, ev["sample+args"]) for e in ev["round.sample"])
    if eng.streaming:
        # a cohort is put for the round it was sampled for, the next one
        assert {e[2]["round"] for e in ev["h2d.put"]} \
            <= {e[2]["round"] for e in ev["round.sample"]}
        assert len(ev["h2d.put"]) == len(ev["h2d.gather"]) >= ROUNDS
    else:
        assert len(ev["round.args_put"]) == ROUNDS
        assert all(_inside(e, ev["sample+args"]) for e in ev["round.args_put"])


def test_span_readers_read_below_the_harness_own_times(traced):
    """``sample_ms + args_put_ms <= sample_args_ms`` and
    ``program_dispatch_ms <= dispatch_ms`` hold by construction: the spans
    nest inside what the harness times from outside."""
    eng, path, ev = traced
    out = program_trace.reduce(path, None, family=eng.program_family)
    ms = out["spans_ms"]
    assert "scope_ms" not in out                     # a CPU trace: no device plane
    outer = {k: sorted(e[1] - e[0] for e in ev[k])[ROUNDS // 2] / 1e6
             for k in ("sample+args", "dispatch")}
    assert 0 < ms["program.dispatch"] <= outer["dispatch"]
    put = 0.0 if eng.streaming else ms["round.args_put"]
    assert 0 < ms["round.sample"] + put <= outer["sample+args"]
    if eng.streaming:
        assert {"h2d.gather", "h2d.put"} <= set(ms)


def test_a_program_without_scope_map_or_trace_reads_as_nothing():
    """What the parent of the PR that added the scopes gives the readers."""
    class Engine:                       # no scope_map on round_fn, no stack
        round_fn = staticmethod(lambda *a: None)

    assert program_trace.scope_ms({"trace": None, "engine": Engine()}, "take") is None
    assert program_trace.span_ms({"trace": None, "engine": Engine()}, "round.sample") is None
    from fedbench.layer_metrics import module
    for name in ("take_ms", "unscoped_pct", "sample_ms", "program_dispatch_ms"):
        assert module(name).read({"trace": None, "engine": Engine()}) is None


# -- the split of device time, on a trace recorded on the chip ---------------

FIXTURE = os.path.join(HERE, "tiny_xdev_scopes_v5e.xplane.pb.gz")
SCOPE_MAP = os.path.join(HERE, "tiny_xdev_scopes_v5e.scope_map.json.gz")


@pytest.fixture(scope="module")
def split():
    with gzip.open(SCOPE_MAP, "rt") as f:
        scope_map = json.load(f)
    return program_trace.reduce(FIXTURE, scope_map, family="fedavg_resident",
                                resident_dims=(12, 2))


def test_scopes_partition_the_rounds_device_time(split):
    """Recorded on one v5e (PR 23): four rounds of the tiny
    ``resnet18gn.xdev10of4000`` with scopes, and the ``scope_map()`` of the
    program that ran.  Every op is counted once: the labels sum to the round's
    self time, which is the reducer's busy union of one execution."""
    assert split["rounds"] == 4 and split["unknown_share"] == 0.0
    ms = split["scope_ms"]
    assert sum(ms.values()) == pytest.approx(split["round_self_ms"], rel=1e-3)
    reduced = trace_reduce.reduce_file(FIXTURE, 1)
    assert split["round_self_ms"] == pytest.approx(reduced["round_busy_ms"], rel=1e-3)
    assert split["round_self_ms"] == pytest.approx(0.2313145, rel=1e-9)


@pytest.mark.parametrize("label,ms", [
    ("take", 0.0029075), ("forward", 0.0676995), ("backward", 0.0956635),
    ("optimizer", 0.000206), ("local_other", 0.0358375), ("aggregate", 0.028992),
])
def test_each_scope_reads_what_was_read_on_the_chip(split, label, ms):
    assert split["scope_ms"][label] == pytest.approx(ms, rel=1e-9) and ms > 0


def test_unscoped_and_server_update_read_zero_for_fedavg(split):
    """``unscoped_pct`` as read on the chip: 0.0 - every op of the round that
    ran has a scope; FedAvg's server update installs the mean (no op)."""
    assert split["scope_ms"]["unscoped"] == 0.0 == split["scope_ms"]["server_update"]
    assert split["unscoped_ops"] == []


def test_take_lists_what_each_rule_counted(split):
    ops = split["take_ops"]
    assert ops and all(rule == "fed_take" for _, rule, _ in ops)
    assert sum(v for _, _, v in ops) == pytest.approx(split["scope_ms"]["take"], rel=0.05)
    # the second rule, on the same trace: without the map's labels for them,
    # the ops that touch the resident stack (12 clients x 2 batches) outside the
    # scan are
    # still the take's
    with gzip.open(SCOPE_MAP, "rt") as f:
        scope_map = json.load(f)
    blind = {k: ("unscoped" if v == "take" else v) for k, v in scope_map.items()}
    again = program_trace.reduce(FIXTURE, blind, resident_dims=(12, 2))
    by_axis = [o for o in again["take_ops"] if o[1].startswith("resident axis")]
    assert by_axis and again["scope_ms"]["take"] > 0
    # (medians over the four rounds, so the two parts add up only nearly)
    assert again["scope_ms"]["take"] + again["scope_ms"]["unscoped"] \
        == pytest.approx(split["scope_ms"]["take"], rel=1e-2)


def test_the_chip_trace_holds_the_program_spans_on_the_device_clock(split):
    """Same clock, on the chip: every ``program.dispatch`` of the round's
    family lies inside a harness ``dispatch`` annotation, every
    ``round.sample`` inside a ``sample+args``."""
    ms = split["spans_ms"]
    assert ms["round.sample"] == pytest.approx(0.1134, rel=1e-6)
    assert ms["round.args_put"] == pytest.approx(0.5661145, rel=1e-6)
    assert ms["program.dispatch"] == pytest.approx(0.5759005, rel=1e-6)
    outer = collections.defaultdict(list)
    for plane in trace_reduce.load(FIXTURE).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in trace_reduce.ANNOTATIONS:
                        outer[e.name].append((e.start_ns, e.start_ns + e.duration_ns))
    spans = split["spans"]
    mine = [(s, s + d) for s, d, st in spans["program.dispatch"]
            if st["family"] == "fedavg_resident"]
    assert len(mine) == 4 and all(_inside(e, outer["dispatch"]) for e in mine)
    assert all(_inside((s, s + d), outer["sample+args"])
               for name in ("round.sample", "round.args_put")
               for s, d, _ in spans[name])
