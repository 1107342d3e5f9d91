"""The harness's window is the loop body of ``FedAvgEngine.run()``: after n
rounds both hold bitwise-equal variables, resident and streamed."""
import copy

import jax
import numpy as np
import pytest

from fedbench.harness import build, loop
from fedbench_tiny import tiny_doc


def _engine(streaming: bool):
    traffic = copy.deepcopy(tiny_doc("traffic", "xdev10of4000"))
    traffic["engine"]["args"]["streaming"] = streaming
    data = build.make_data(traffic, seed=3)
    return build.make_engine(tiny_doc("configs", "resnet18gn_cifar"), traffic,
                             data, seed=3)


@pytest.mark.parametrize("streaming", [False, True])
def test_window_equals_run_bitwise(streaming):
    rounds = 3
    a, b = _engine(streaming), _engine(streaming)
    v0 = jax.tree.map(np.asarray, a.init_variables())
    want = a.run(variables=jax.tree.map(jax.numpy.asarray, v0), rounds=rounds)
    state = loop.State(b, jax.tree.map(jax.numpy.asarray, v0), seed=3)
    win = loop.run_rounds(state, depth=2, rounds=rounds)
    loop.join_prefetch(b)
    assert win["attempted"] == rounds and win["failed"] == 0
    assert len(win["done_t"]) == rounds and win["done_t"] == sorted(win["done_t"])
    for x, y in zip(jax.tree.leaves(want), jax.tree.leaves(state.variables)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert b.streaming is streaming


def test_window_stops_dispatching_when_the_clock_passes():
    eng = _engine(False)
    state = loop.State(eng, eng.init_variables(), seed=3)
    loop.run_rounds(state, depth=2, rounds=2)            # compile
    win = loop.run_rounds(state, depth=2, seconds=0.3)
    assert win["attempted"] == len(win["done_t"]) >= 1
    assert win["elapsed_s"] >= 0.3 or win["attempted"] >= 1
    assert state.next_round == 2 + win["attempted"]
