"""The resident cohort take (fedml_tpu/parallel/engine.py::take_cohort).

Where the client axis is not partitioned the cohort is built from one
`dynamic_index_in_dim` per slot, not a gather: on the TPU a gather's
lowering converts and relays the WHOLE resident stack every round
(PERF.md §6 d).  Pinned here, on the CPU:

* the one-shard round with the sliced take equals the same round fed a
  `jnp.take` cohort BITWISE — image stacks (flat and not), a uint8 stack
  with its dequant, an int32 token stack; distinct ids, duplicates, padded
  zero-weight slots; and the two-level resident partial likewise;
* the partitioned path is what it was: 2- and 8-shard rounds still lower
  to a gather under `fed_take` and agree with the gather-fed round bitwise;
* the one-shard program has no gather over the resident x, and everything
  that touches a resident-shaped array is labelled `take` by
  `round_fn.scope_map()` — what the benchmark's `take_ms` sums.

The structural pin at the benchmark's real shapes, compiled for the
described v5e, is tests/test_tpu_compile.py (`-m slow`).
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.core.trainer import ClientTrainer
from fedml_tpu.data.loaders import load_data
from fedml_tpu.models import create_model
from fedml_tpu.parallel import MeshFedAvgEngine
from fedml_tpu.parallel.engine import take_cohort
from fedml_tpu.parallel.mesh import (client_sharding, make_mesh,
                                     stack_leaf_sharding)
from fedml_tpu.utils.config import FedConfig

from parallel_case import hlo_instructions

POPULATION, COHORT = 24, 8      # 24 leads no other array of these programs

# name -> (dataset, load_data kwargs, model, model kwargs, engine kwargs)
STACKS = {
    "image_flat": ("femnist", {"synthetic_scale": 0.004}, "cnn", {}, {}),
    "image_unflat": ("femnist", {"synthetic_scale": 0.004}, "cnn", {},
                     {"flat_stack": False}),
    "uint8": ("femnist", {"synthetic_scale": 0.004}, "cnn", {},
              {"stack_dtype": jnp.uint8}),
    "tokens": ("shakespeare", {"synthetic_scale": 0.01}, "rnn",
               {"last_only": True}, {}),
}
# name -> (ids, wmask): what `pad_ids` can hand the round
IDS = {
    "distinct": ([5, 1, 23, 0, 9, 17, 2, 11], [1] * 8),
    "duplicates": ([5, 1, 5, 5, 23, 23, 0, 0], [1] * 8),
    "padded": ([5, 1, 23, 9, 17, 0, 0, 0], [1] * 5 + [0] * 3),
}


@functools.lru_cache(maxsize=None)
def _data_trainer(stack: str):
    dataset, data_kw, model, model_kw, _ = STACKS[stack]
    data = load_data(dataset, client_num_in_total=POPULATION, batch_size=4,
                     max_batches_per_client=2, seed=0, **data_kw)
    cfg = FedConfig(model=model, dataset=dataset,
                    client_num_in_total=POPULATION,
                    client_num_per_round=COHORT, comm_round=1, epochs=1,
                    batch_size=4, lr=0.1, frequency_of_the_test=100)
    trainer = ClientTrainer(
        create_model(model, output_dim=data.class_num, **model_kw), lr=0.1)
    return data, cfg, trainer


@functools.lru_cache(maxsize=None)
def _built(stack: str, n_shards: int):
    """(engine, placed variables, the resident stack and weights, the two
    jitted rounds: resident, and fed a cohort)."""
    data, cfg, trainer = _data_trainer(stack)
    eng = MeshFedAvgEngine(trainer, data, cfg, mesh=make_mesh(n_shards),
                           donate=False, **STACKS[stack][4])
    variables = eng._prepare_variables(eng.init_variables())
    resident, resident_w = eng._device_stack()
    return (eng, variables, resident, resident_w,
            jax.jit(eng._mesh_round), jax.jit(eng._mesh_round_streaming))


def _gathered(eng, resident, resident_w, ids, wmask):
    """The cohort as a plain gather outside the program gives it, placed
    as a host-gathered cohort is."""
    cohort = {k: jax.device_put(jnp.take(v, ids, axis=0),
                                stack_leaf_sharding(eng.mesh, v))
              for k, v in resident.items()}
    weights = jax.device_put(jnp.take(resident_w, ids) * wmask,
                             client_sharding(eng.mesh))
    return cohort, weights


def _both_rounds(stack, n_shards, ids_case):
    eng, variables, resident, resident_w, round_resident, round_fed = \
        _built(stack, n_shards)
    ids = jnp.asarray(np.array(IDS[ids_case][0], np.int32))
    wmask = jnp.asarray(np.array(IDS[ids_case][1], np.float32))
    rng = jax.random.PRNGKey(3)
    got = round_resident(variables, (), resident, resident_w, ids, wmask,
                         rng)
    cohort, weights = _gathered(eng, resident, resident_w, ids, wmask)
    want = round_fed(variables, (), cohort, weights, rng)
    return got, want


def _assert_bitwise(got, want):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want) > 2
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("ids_case", list(IDS))
@pytest.mark.parametrize("stack", list(STACKS))
def test_sliced_take_round_is_bitwise_the_gather_fed_round(stack, ids_case):
    got, want = _both_rounds(stack, 1, ids_case)
    _assert_bitwise(got, want)                 # params, state, train loss
    assert np.isfinite(float(got[2]["train_loss"]))
    # the stack on the device is what the case says it is
    x = _built(stack, 1)[2]["x"]
    assert x.dtype == {"uint8": jnp.uint8, "tokens": jnp.int32}.get(
        stack, jnp.float32)
    assert x.ndim == (6 if stack == "image_unflat" else 4)


@pytest.mark.parametrize("n_shards", [2, 8])
def test_partitioned_round_still_equals_the_gather_fed_round(n_shards):
    got, want = _both_rounds("image_flat", n_shards, "padded")
    _assert_bitwise(got, want)
    # and the sharded mean is the one-shard sliced round's, up to the order
    # of the cross-shard sums
    one = _both_rounds("image_flat", 1, "padded")[0]
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(one)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-6)


def test_twolevel_resident_partial_is_bitwise_the_cohort_fed_partial():
    eng, variables, resident, resident_w, _, _ = _built("image_flat", 1)
    ids = jnp.asarray(np.array(IDS["duplicates"][0], np.int32))
    wmask = jnp.asarray(np.array(IDS["padded"][1], np.float32))
    rngs = jax.random.split(jax.random.PRNGKey(5), COHORT)
    got = jax.jit(eng._twolevel_partial_resident_impl)(
        variables, resident, resident_w, ids, wmask, rngs)
    cohort, weights = _gathered(eng, resident, resident_w, ids, wmask)
    want = jax.jit(eng._twolevel_partial_impl)(variables, cohort, weights,
                                               rngs)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert got.ndim == 1 and float(jnp.abs(got).sum()) > 0


@pytest.mark.parametrize("n_shards", [1, 4])
def test_take_cohort_values_and_padding(n_shards):
    """The helper alone, on every leaf dtype: values of `np.take`, weights
    masked; one shard and a partitioned client axis."""
    rs = np.random.RandomState(0)
    host = {"x": rs.rand(POPULATION, 2, 4, 6).astype(np.float32),
            "y": rs.randint(0, 9, (POPULATION, 2, 4)).astype(np.int32),
            "mask": (rs.rand(POPULATION, 2, 4) > 0.3).astype(np.float32),
            "q": rs.randint(0, 255, (POPULATION, 2, 4, 6)).astype(np.uint8)}
    host_w = rs.rand(POPULATION).astype(np.float32)
    ids, wmask = (np.array(v) for v in IDS["padded"])
    mesh = make_mesh(n_shards)
    stack = {k: jax.device_put(v, stack_leaf_sharding(mesh, v))
             for k, v in host.items()}
    stack_w = jax.device_put(host_w, client_sharding(mesh))
    cohort, weights = jax.jit(
        lambda s, w, i, m: take_cohort(mesh, s, w, i, m))(
            stack, stack_w, jnp.asarray(ids, jnp.int32),
            jnp.asarray(wmask, jnp.float32))
    for k, v in host.items():
        assert cohort[k].dtype == v.dtype
        np.testing.assert_array_equal(np.asarray(cohort[k]),
                                      np.take(v, ids, axis=0))
    np.testing.assert_array_equal(
        np.asarray(weights),
        np.take(host_w, ids) * wmask.astype(np.float32))


# -- the lowered programs ----------------------------------------------------

@functools.lru_cache(maxsize=None)
def _program(n_shards: int):
    """One dispatched round of the engine's own `round_fn`: (its compiled
    text's instructions as {name: (result type, opcode, operand names)},
    its scope map, the text)."""
    eng = _built("image_flat", n_shards)[0]
    eng.run(rounds=1)
    args, kwargs = eng.round_fn._signature
    text = eng.round_fn.lower(*args, **kwargs).compile().as_text()
    instructions = {
        name: (result, opcode, re.findall(r"%([\w.\-]+)", rest))
        for name, result, opcode, rest in hlo_instructions(text)}
    return instructions, eng.round_fn.scope_map(), text


def _resident(result_type: str) -> bool:
    return re.search(r"\[%d," % POPULATION, result_type) is not None


def test_one_shard_program_has_no_gather_over_the_resident_stack():
    instructions, smap, _ = _program(1)
    n_slices = 0
    for name, (result, opcode, operands) in instructions.items():
        reads_resident = any(_resident(instructions[o][0])
                             for o in operands if o in instructions)
        if opcode == "gather" and reads_resident:
            # the [C] weights vector is gathered still; no stack leaf is
            assert re.fullmatch(r"f32\[%d\]\S*" % POPULATION,
                                instructions[operands[0]][0]), (name, result)
        if opcode == "dynamic-slice" and reads_resident:
            n_slices += 1
            assert smap[name] == "take", (name, smap[name])
        # no instruction produces a resident-sized array: the stack is read
        # in place, a cohort slot at a time
        assert opcode == "parameter" or not _resident(result), (name, result)
    assert n_slices == 3 * COHORT          # x, y, mask: one slice a slot


@pytest.mark.parametrize("n_shards", [1, 2, 8])
def test_every_op_of_the_take_sits_under_fed_take(n_shards):
    """Every instruction that reads the resident stack is labelled `take`.
    Fused bodies are walked instruction by instruction; a fusion as a whole
    has its root's label, and which ops the CPU compiler fuses with the
    slices is its own affair (the TPU's fusions are pinned in
    tests/test_tpu_compile.py)."""
    instructions, smap, _ = _program(n_shards)
    shard = POPULATION // n_shards
    touched = 0
    for name, (result, opcode, operands) in instructions.items():
        if opcode in ("parameter", "fusion", "bitcast", "tuple"):
            continue
        if any(re.search(r"\[%d[,\]]" % shard, instructions[o][0])
               and instructions[o][1] == "parameter"
               for o in operands if o in instructions):
            touched += 1
            assert smap[name] == "take", (name, opcode, smap[name])
    assert touched >= 4                     # x, y, mask and the weights
    labels = set(smap.values())
    assert {"take", "forward", "backward", "aggregate"} <= labels


@pytest.mark.parametrize("n_shards", [2, 8])
def test_partitioned_program_still_gathers_under_fed_take(n_shards):
    instructions, smap, text = _program(n_shards)
    gathers = [n for n, (result, opcode, _) in instructions.items()
               if opcode == "gather" and smap[n] == "take"
               and re.search(r"\[%d,1,2,4,784\]" % COHORT, result)]
    assert gathers, "the cross-shard take of x is no gather any more"
    assert "fed_take/dynamic_slice" not in text     # the one-shard body
    # the cross-shard exchange of the gathered rows is booked to the take
    assert any(opcode == "all-reduce" and smap[n] == "take"
               for n, (_, opcode, _) in instructions.items())
