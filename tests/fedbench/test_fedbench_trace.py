"""The trace reducer on a small trace recorded on the chip (PR 22, one v5e:
four rounds of ``resnet18gn.xdev10of4000 --trace 1`` at the tests' tiny size, gzipped),
numbers pinned, and its pieces on hand-made inputs."""
import os

import pytest

from fedbench.harness import trace_reduce as tr

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "tiny_xdev_v5e.xplane.pb.gz")


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce_file(FIXTURE, 1)


def test_busy_union_window_and_idle_share(reduced):
    assert reduced["rounds"] == 4
    assert reduced["window_s"] == pytest.approx(0.01279828, rel=1e-9)
    assert reduced["busy_s"] == pytest.approx(0.000928393, rel=1e-9)
    assert reduced["device0_busy_s"] == reduced["busy_s"]       # one chip
    assert reduced["round_busy_ms"] == pytest.approx(0.231559, rel=1e-9)
    idle = 1 - reduced["busy_s"] / reduced["window_s"]
    assert idle == pytest.approx(0.92746, abs=1e-5)             # a toy: all idle
    # busy + every idle gap = the window
    gaps = sum(reduced["idle_by_span_s"].values())
    assert reduced["busy_s"] + gaps == pytest.approx(reduced["window_s"], rel=1e-9)


def test_category_split_is_by_self_time(reduced):
    cats = reduced["categories_s"]
    assert cats["data movement"] == pytest.approx(0.000360928, rel=1e-9)
    assert cats["matmul/conv fusion"] == pytest.approx(0.000249476, rel=1e-9)
    assert cats["copy"] == pytest.approx(4.3398e-05, rel=1e-9)
    assert reduced["copy_s"] == cats["copy"] and reduced["collective_s"] == 0.0
    # self times partition the busy union: a while's body is not counted twice
    assert sum(cats.values()) == pytest.approx(reduced["busy_s"], rel=1e-6)


def test_gaps_are_attributed_to_the_harness_span_that_covers_them(reduced):
    by = reduced["idle_by_span_s"]
    assert set(by) == {"none", "sample+args", "dispatch", "wait_round"}
    assert by["sample+args"] == pytest.approx(0.005199714, rel=1e-6)
    assert by["dispatch"] == pytest.approx(0.001367298, rel=1e-6)
    top = reduced["breakdown"]["idle_gaps"]
    assert top[0] == ["none", pytest.approx(0.002058589, rel=1e-9)]
    assert top[1] == ["sample+args", pytest.approx(0.00183949, rel=1e-9)]
    assert len(top) == 10 == len(reduced["breakdown"]["device_ops"])
    assert reduced["breakdown"]["device_ops"][0][0] == "data movement"


def test_a_trace_with_no_device_plane_reduces_to_nothing():
    assert tr.reduce_file(FIXTURE, 0) is None


def test_merge_and_self_times():
    assert tr.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    # a while [0, 10) with two body ops and a nested pair inside the second
    events = [(0, 10, "while"), (1, 3, "a"), (5, 4, "b"), (6, 2, "c"), (12, 1, "d")]
    assert tr.self_times(events) == [3, 3, 2, 2, 1]


@pytest.mark.parametrize("hlo,category,name", [
    ("%copy.34 = s32[65536,8,16,20]{3,2,1,0:T(8,128)} copy(s32[65536,8,16,20]{0,2,3,1:T(8,128)} %stack__x__.1)",
     "copy", "copy.34"),
    ("%fusion.1130 = (f32[2,4,8]{2,1,0}, f32[2]{0}) fusion(f32[8]{0} %p), kind=kLoop, calls=%fc",
     "loop fusion", "fusion.1130"),
    ("%copy_bitcast_fusion.2 = bf16[2,2]{1,0} fusion(bf16[2,2]{1,0} %r), kind=kLoop, calls=%fc",
     "copy", "copy_bitcast_fusion.2"),
    ("%fusion.7 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %a), kind=kOutput, calls=%fc",
     "matmul/conv fusion", "fusion.7"),
    ("%all-reduce.1 = f32[11173962]{0} all-reduce(f32[11173962]{0} %x), replica_groups={}",
     "collective", "all-reduce.1"),
    ("%all-reduce-start.2 = f32[4]{0} all-reduce-start(f32[4]{0} %x)", "collective",
     "all-reduce-start.2"),
    ("%while.486 = (s32[]{:T(128)}, f32[4050748]{0}) while((s32[], f32[4050748]) %t), condition=%c, body=%b",
     "control flow", "while.486"),
    ("%reshape.1728 = bf16[2,2,4,8,8,3]{5,4,3,2,1,0} reshape(bf16[1,2,2,4,192]{4,3,2,1,0} %c)",
     "data movement", "reshape.1728"),
])
def test_classify_reads_the_hlo_text(hlo, category, name):
    assert tr.classify(hlo) == (category, name)
