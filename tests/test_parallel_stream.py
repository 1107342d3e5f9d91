"""Streaming and block-streamed mesh-engine tests (split out of
test_parallel.py): the cohort-on-host paths — per-round streaming
uploads, block-streamed rounds (linear engines + the two-phase
order-statistic defenses), and their device-memory bounds.  Oracles:
each path must reproduce the HBM-resident round exactly.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.core.trainer import ClientTrainer
from fedml_tpu.data.loaders import load_data
from fedml_tpu.models import create_model
from fedml_tpu.parallel import (MeshFedAvgEngine, MeshFedOptEngine,
                                MeshRobustEngine)
from fedml_tpu.parallel.mesh import make_mesh
from fedml_tpu.utils.config import FedConfig

from parallel_case import _mnist_like_cfg, _setup, run_donate_pair


def _buffers(a):
    """The addresses of a live array's buffers, one a shard; none where the
    array was deleted after `jax.live_arrays()` listed it."""
    try:
        return tuple(s.data.unsafe_buffer_pointer()
                     for s in a.addressable_shards)
    except RuntimeError:
        return ()


def _live_bytes():
    """Total bytes across all live device arrays, each buffer once — the
    one accounting every memory-bound test in this file shares.
    `np.asarray` of a sharded array leaves single-device views of its
    shards alive until the array is deleted, and the prefetch thread can
    sample in between: a view is its array's buffer, not more memory."""
    held = [(a.nbytes, _buffers(a)) for a in jax.live_arrays()]
    shards = {p for _, ptrs in held if len(ptrs) > 1 for p in ptrs}
    return sum(n for n, ptrs in held
               if len(ptrs) > 1 or ptrs and ptrs[0] not in shards)


def _spy_live_bytes(obj, attr, peaks):
    """Wrap obj.attr so each call first appends _live_bytes() to peaks."""
    orig = getattr(obj, attr)
    setattr(obj, attr,
            lambda *a: (peaks.append(_live_bytes()), orig(*a))[1])


def test_streaming_matches_resident():
    """Streaming cohort upload (host-gather, VERDICT r1 #5) must reproduce
    the HBM-resident path exactly — same sampling, same chunked round."""
    cfg = _mnist_like_cfg(client_num_per_round=12, comm_round=3)
    trainer, data = _setup(cfg)
    res = MeshFedAvgEngine(trainer, data, cfg, mesh=make_mesh(8),
                           donate=False)
    v0 = res.init_variables()
    v_res = res.run(variables=jax.tree.map(jnp.copy, v0), rounds=3)
    stream = MeshFedAvgEngine(trainer, data, cfg, mesh=make_mesh(8),
                              donate=False, streaming=True)
    v_str = stream.run(variables=jax.tree.map(jnp.copy, v0), rounds=3)
    for a, b in zip(jax.tree.leaves(v_res), jax.tree.leaves(v_str)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def _assert_blockstream_matches(engine_cls, cfg, trainer, data,
                                stream_block=8, rounds=2):
    """Shared oracle body: block-streamed == whole-cohort streaming for
    one engine class (same sampling, same per-client rngs — split
    prefixes are stable — zero-weight pad lanes contribute exactly 0)."""
    stream = engine_cls(trainer, data, cfg, mesh=make_mesh(8),
                        donate=False, streaming=True)
    v0 = stream.init_variables()
    v_str = stream.run(variables=jax.tree.map(jnp.copy, v0), rounds=rounds)
    blk = engine_cls(trainer, data, cfg, mesh=make_mesh(8),
                     donate=False, stream_block=stream_block)
    assert blk.streaming        # stream_block implies streaming
    v_blk = blk.run(variables=jax.tree.map(jnp.copy, v0), rounds=rounds)
    for a, b in zip(jax.tree.leaves(v_str), jax.tree.leaves(v_blk)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_blockstream_matches_streaming():
    """12 sampled clients in blocks of 8 on an 8-shard mesh exercises the
    final block's shard-level zero-weight padding."""
    cfg = _mnist_like_cfg(client_num_per_round=12, comm_round=3)
    trainer, data = _setup(cfg)
    _assert_blockstream_matches(MeshFedAvgEngine, cfg, trainer, data,
                                rounds=3)


def test_blockstream_block_multiple_padding():
    """stream_block=16 on the 8-shard mesh with 12 sampled clients: ids
    are shard-padded 12->16 by _sample_padded_np and the BLOCK padding
    branch (pad to a stream_block multiple with zero-weight repeated-id
    lanes) is a no-op at 16... so use 20 sampled of 24: shard-pad
    20->24, block-pad 24->32 — the branch the block-equals-streaming
    oracle must also survive (differing rng split counts are prefix-
    stable; pad lanes carry weight 0)."""
    cfg = _mnist_like_cfg(client_num_in_total=24, client_num_per_round=20,
                          comm_round=2)
    trainer, data = _setup(cfg)
    _assert_blockstream_matches(MeshFedAvgEngine, cfg, trainer, data,
                                stream_block=16)


def test_blockstream_fedopt_and_gates():
    """FedOpt server state threads through the block finalize; the
    block-multiple gates hold."""
    cfg = _mnist_like_cfg(server_optimizer="adam", server_lr=0.05,
                          comm_round=2)
    trainer, data = _setup(cfg)
    _assert_blockstream_matches(MeshFedOptEngine, cfg, trainer, data)

    r_cfg = FedConfig(**{**cfg.__dict__, "norm_bound": 0.5})
    # order statistics cannot ignore padded lanes: the cohort (16) must
    # be a stream_block multiple (32 is not a divisor -> refuse)
    with pytest.raises(ValueError, match="block multiple"):
        MeshRobustEngine(trainer, data, r_cfg, defense="krum",
                         mesh=make_mesh(8), donate=False, stream_block=32)
    # norm_clip is per-client and streams fine
    MeshRobustEngine(trainer, data, r_cfg, defense="norm_clip",
                     mesh=make_mesh(8), donate=False, stream_block=8)
    with pytest.raises(ValueError, match="multiple"):
        MeshFedAvgEngine(trainer, data, cfg, mesh=make_mesh(8),
                         donate=False, stream_block=3)


def test_blockstream_orderstat_device_memory_is_bounded():
    """SCALING.md "Order statistics beyond HBM": a 32-client median
    round in 8-client blocks must hold device data O(block) in phase 1
    and O(K x Pb) in phase 2 — never the O(K x P) cohort matrix, which
    stays in host RAM.  Same live-bytes harness as the linear-path
    bound test.  (Sizes chosen for CI cost: the bound is scale-free —
    both phases still run multiple steps per round, and round 2 guards
    cross-round accumulation.)"""
    n = 32
    cfg = _mnist_like_cfg(client_num_in_total=n, client_num_per_round=n,
                          comm_round=2, frequency_of_the_test=100,
                          norm_bound=0.5)
    data = load_data("femnist", client_num_in_total=n, batch_size=20,
                     synthetic_scale=0.0, seed=0)
    model = create_model("cnn", output_dim=data.class_num)
    trainer = ClientTrainer(model, lr=0.05)
    # param_block_bytes small enough that phase 2 still runs MANY
    # slices: the engine sizes each device slice [K, pb] to
    # param_block_bytes total, i.e. pb = param_block_bytes/(K*4)
    # elements — 4 MiB at K=32 gives pb=32768 and ~52 slices over the
    # 1.69M-param CNN
    eng = MeshRobustEngine(trainer, data, cfg, defense="median",
                           n_byzantine=1, mesh=make_mesh(8),
                           stream_block=8, param_block_bytes=4 << 20)

    block = eng._upload_block(np.arange(8), np.ones(8, np.float32),
                              np.asarray(jax.random.split(
                                  jax.random.PRNGKey(0), 8)))
    block_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                      for a in jax.tree.leaves(block))
    del block
    v = eng.init_variables()
    v = eng._prepare_variables(v)
    var_bytes = sum(int(np.prod(a.shape)) * 4 for a in jax.tree.leaves(v))
    # flats [B, P] per block-step + the phase-2 [K, Pb] slice + result
    P_flat = var_bytes // 4    # f32 leaves -> element count upper bound
    flats_bytes = 8 * P_flat * 4
    slice_bytes = 2 * (4 << 20)
    baseline = _live_bytes() + block_bytes

    peaks = []
    # sample BOTH phases: phase 1 at every block upload, phase 2 at
    # every param-slice colstat call (a regression that materializes the
    # whole [K, P] matrix on device in either phase must land in peaks)
    _spy_live_bytes(eng, "_upload_block", peaks)
    _spy_live_bytes(eng, "_colstat", peaks)
    v = eng.run(variables=v, rounds=2)
    assert eng._stack is None
    assert len(peaks) >= 2 * (n // 8)
    eval_bytes = sum(np.asarray(x).nbytes
                     for shard in (data.train_global, data.test_global)
                     for x in shard.values())
    # new_flat [P] + host->device result assembly ride the var_bytes term
    bound = (baseline + 2 * block_bytes + 2 * var_bytes + flats_bytes
             + slice_bytes + eval_bytes + (8 << 20))
    assert max(peaks) <= bound, (max(peaks), bound)
    # the bound must itself sit well below resident-cohort scale, or the
    # test guards nothing
    cohort_matrix_bytes = n * P_flat * 4     # what the resident path holds
    assert bound < baseline + cohort_matrix_bytes // 2, (
        bound, cohort_matrix_bytes)
    assert all(np.isfinite(np.asarray(x)).all() for x in jax.tree.leaves(v))


def test_blockstream_orderstat_refuses_multiprocess(monkeypatch):
    """The two-phase path offloads client-sharded flats with np.asarray,
    which a multi-process mesh cannot address — refusal must land at
    CONSTRUCTION, not mid-round after training work."""
    cfg = _mnist_like_cfg(comm_round=2, norm_bound=0.5)
    trainer, data = _setup(cfg)
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    with pytest.raises(ValueError, match="single-process"):
        MeshRobustEngine(trainer, data, cfg, defense="median",
                         mesh=make_mesh(8), donate=False, stream_block=8)


@pytest.mark.parametrize("defense", ["median", "trimmed_mean", "krum",
                                     "multi_krum"])
def test_blockstream_orderstat_matches_resident(defense):
    """VERDICT r4 #3: the two-phase block-streamed order-stat defenses
    (client-major training blocks -> host [K, P] matrix -> param-major
    [K, Pb] device slices) must reproduce the HBM-resident defense.
    median/trimmed_mean are bitwise-equal (same values, same per-column
    sort); krum matches the same selected client.  param_block_bytes is
    shrunk so phase 2 actually runs MULTIPLE param slices."""
    cfg = _mnist_like_cfg(comm_round=2, norm_bound=0.5)
    trainer, data = _setup(cfg)
    res = MeshRobustEngine(trainer, data, cfg, defense=defense,
                           n_byzantine=1, mesh=make_mesh(8), donate=False)
    v0 = res.init_variables()
    v_res = res.run(variables=jax.tree.map(jnp.copy, v0), rounds=2)
    blk = MeshRobustEngine(trainer, data, cfg, defense=defense,
                           n_byzantine=1, mesh=make_mesh(8), donate=False,
                           stream_block=8, param_block_bytes=16 * 64)
    assert blk.round_fn == blk._round_blockstream_orderstat
    v_blk = blk.run(variables=jax.tree.map(jnp.copy, v0), rounds=2)
    for a, b in zip(jax.tree.leaves(v_res), jax.tree.leaves(v_blk)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)


def test_blockstream_fednova_matches_streaming():
    """FedNova's extra linear sums (tau-normalized d, Σ w·τ) thread
    through the generic block accumulators — block-streamed FedNova must
    match the whole-cohort streaming round."""
    from fedml_tpu.parallel import MeshFedNovaEngine
    cfg = _mnist_like_cfg(client_num_per_round=12, comm_round=2)
    trainer, data = _setup(cfg)
    _assert_blockstream_matches(MeshFedNovaEngine, cfg, trainer, data)


def test_blockstream_fedprox_matches_streaming():
    """The prox term (global_params anchor inside local_train) rides the
    block path unchanged."""
    from fedml_tpu.parallel import MeshFedProxEngine
    cfg = _mnist_like_cfg(client_num_per_round=12, comm_round=2,
                          prox_mu=0.1)
    trainer, data = _setup(cfg, prox_mu=0.1)
    _assert_blockstream_matches(MeshFedProxEngine, cfg, trainer, data)


def test_streaming_matches_resident_fedopt():
    """The shared _train_and_update tail must apply subclass server_update
    overrides identically on both cohort paths (FedOpt's optimizer state
    persists across rounds)."""
    cfg = _mnist_like_cfg(server_optimizer="adam", server_lr=0.05,
                          comm_round=3)
    trainer, data = _setup(cfg)
    res = MeshFedOptEngine(trainer, data, cfg, mesh=make_mesh(8),
                           donate=False)
    v0 = res.init_variables()
    v_res = res.run(variables=jax.tree.map(jnp.copy, v0), rounds=3)
    stream = MeshFedOptEngine(trainer, data, cfg, mesh=make_mesh(8),
                              donate=False, streaming=True)
    v_str = stream.run(variables=jax.tree.map(jnp.copy, v0), rounds=3)
    for a, b in zip(jax.tree.leaves(v_res), jax.tree.leaves(v_str)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_streaming_large_client_count():
    """Femnist-shaped scale proxy: many clients, tiny per-round cohort —
    the streaming path never uploads the full stack."""
    cfg = _mnist_like_cfg(client_num_in_total=96, client_num_per_round=8,
                          comm_round=2)
    data = load_data("mnist", client_num_in_total=96, batch_size=8,
                     synthetic_scale=0.02, seed=0)
    model = create_model("lr", output_dim=data.class_num)
    trainer = ClientTrainer(model, lr=0.1)
    eng = MeshFedAvgEngine(trainer, data, cfg, mesh=make_mesh(8),
                           streaming=True)
    assert eng._stack is None
    v = eng.run(rounds=2)
    assert eng._stack is None          # full stack never touched the device
    assert all(np.isfinite(np.asarray(x)).all() for x in jax.tree.leaves(v))


@pytest.mark.slow   # ~2 min XLA:CPU (3,400-client host stack): the
#                     O(block)/O(cohort) device bounds stay tier-1 via
#                     the two blockstream live-bytes tests above/below;
#                     this reference-scale proxy runs in full suites
def test_streaming_reference_scale_memory_bound():
    """The reference's FEMNIST benchmark client count — 3,400 clients
    (benchmark/README.md:54) — through the streaming engine, with a
    device-residency assertion: across all rounds the live device bytes
    never exceed the pre-round baseline (model + optimizer + eval shards)
    plus TWO padded cohorts (the double-buffer prefetch) — i.e. device
    memory is O(cohort), not O(client_num_in_total)."""
    n = 3400
    cfg = _mnist_like_cfg(client_num_in_total=n, client_num_per_round=10,
                          comm_round=3, frequency_of_the_test=100)
    data = load_data("femnist", client_num_in_total=n, batch_size=20,
                     synthetic_scale=0.0, seed=0)
    assert data.client_num == n
    stack_bytes = sum(np.asarray(v).nbytes
                      for v in data.client_shards.values())
    model = create_model("cnn", output_dim=data.class_num)
    trainer = ClientTrainer(model, lr=0.05)
    eng = MeshFedAvgEngine(trainer, data, cfg, mesh=make_mesh(8),
                           streaming=True)

    cohort, w = eng.stream_cohort(0)
    cohort_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                       for a in jax.tree.leaves(cohort)) + w.nbytes
    del cohort, w
    v = eng.init_variables()
    v = eng._prepare_variables(v)
    baseline = _live_bytes() + cohort_bytes  # v + anything engine init left

    peaks = []
    # spy the upload half (_stream_gather): the prefetched rounds call
    # it directly on the background thread — sampling stays on the
    # round loop's thread (engine._round_args) and stream_cohort only
    # fronts it for unprefetched gathers
    _spy_live_bytes(eng, "_stream_gather", peaks)
    v = eng.run(variables=v, rounds=3)
    assert eng._stack is None          # resident stack never built
    assert len(peaks) >= 3
    # every observation: <= baseline + 2 cohorts (prefetch double buffer)
    # + the uploaded eval shards + slack; crucially O(cohort), never
    # O(stack): the full stack is >100x a cohort at this scale
    eval_bytes = sum(np.asarray(x).nbytes
                     for shard in (data.train_global, data.test_global)
                     for x in shard.values())
    bound = baseline + 2 * cohort_bytes + eval_bytes + (8 << 20)
    assert max(peaks) <= bound, (max(peaks), bound)
    assert stack_bytes > 20 * cohort_bytes   # the bound is meaningful
    assert all(np.isfinite(np.asarray(x)).all() for x in jax.tree.leaves(v))


@pytest.mark.slow   # 74 s XLA:CPU (the heaviest streaming test —
#                     ISSUE-4 fast/nightly split): the O(block) device
#                     bound stays tier-1-guarded by the orderstat
#                     live-bytes test above (same harness, both phases,
#                     46 s); this linear-path twin runs in the nightly
#                     profile — zero coverage loss across the two
def test_blockstream_device_memory_is_o_block():
    """stream_block's point: a round over a 64-client cohort in 8-client
    blocks must never hold device bytes O(cohort) — only O(block)
    (current + prefetched next + accumulators), even though the cohort
    is 8x the block."""
    n = 64
    cfg = _mnist_like_cfg(client_num_in_total=n, client_num_per_round=n,
                          comm_round=2, frequency_of_the_test=100)
    data = load_data("femnist", client_num_in_total=n, batch_size=20,
                     synthetic_scale=0.0, seed=0)
    model = create_model("cnn", output_dim=data.class_num)
    trainer = ClientTrainer(model, lr=0.05)
    eng = MeshFedAvgEngine(trainer, data, cfg, mesh=make_mesh(8),
                           stream_block=8)

    block = eng._upload_block(np.arange(8),
                              np.ones(8, np.float32),
                              np.asarray(jax.random.split(
                                  jax.random.PRNGKey(0), 8)))
    block_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                      for a in jax.tree.leaves(block))
    del block
    v = eng.init_variables()
    v = eng._prepare_variables(v)
    # num accumulator = one f32 copy of the variables
    var_bytes = sum(int(np.prod(a.shape)) * 4
                    for a in jax.tree.leaves(v))
    baseline = _live_bytes() + block_bytes

    peaks = []
    _spy_live_bytes(eng, "_upload_block", peaks)
    v = eng.run(variables=v, rounds=2)
    assert eng._stack is None
    assert len(peaks) >= 2 * (n // 8)      # every block observed
    eval_bytes = sum(np.asarray(x).nbytes
                     for shard in (data.train_global, data.test_global)
                     for x in shard.values())
    bound = baseline + 2 * block_bytes + var_bytes + eval_bytes + (8 << 20)
    assert max(peaks) <= bound, (max(peaks), bound)
    cohort_bytes = 8 * block_bytes          # full participation, 64 clients
    assert cohort_bytes > 4 * block_bytes   # the bound is meaningful
    assert all(np.isfinite(np.asarray(x)).all() for x in jax.tree.leaves(v))



def test_donate_bitwise_streaming():
    """The run-loop streaming variant donates the per-round cohort
    (engine._round_fn_streaming_consume); the public replay entry must
    stay un-donated so a caller that uploads one cohort and replays it
    (chip_smoke.py::HeadlineRun) survives."""
    cfg = _mnist_like_cfg(client_num_per_round=12, comm_round=2)
    trainer, data = _setup(cfg)
    run_donate_pair(lambda donate: MeshFedAvgEngine(
        trainer, data, cfg, mesh=make_mesh(8), donate=donate,
        streaming=True))
    # replay safety: round_fn_streaming does NOT donate the cohort — the
    # same uploaded cohort must survive two calls (HeadlineRun's pattern)
    eng = MeshFedAvgEngine(trainer, data, cfg, mesh=make_mesh(8),
                           donate=True, streaming=True)
    v = eng._prepare_variables(eng.init_variables())
    ss = eng.server_init(v)
    cohort, weights = eng.stream_cohort(0)
    rng = jax.random.PRNGKey(0)
    v, ss, _ = eng.round_fn_streaming(v, ss, cohort, weights, rng)
    v, ss, _ = eng.round_fn_streaming(v, ss, cohort, weights, rng)
    assert all(np.isfinite(np.asarray(x)).all() for x in jax.tree.leaves(v))


def test_donate_bitwise_blockstream():
    cfg = _mnist_like_cfg(client_num_per_round=12, comm_round=2)
    trainer, data = _setup(cfg)
    run_donate_pair(lambda donate: MeshFedAvgEngine(
        trainer, data, cfg, mesh=make_mesh(8), donate=donate,
        stream_block=8))


def test_donate_bitwise_blockstream_orderstat():
    """Two-phase order-stat rounds with donation end-to-end (flats block
    step, donated phase-2 slices, donated finalize) == the non-donating
    compile, bitwise."""
    cfg = _mnist_like_cfg(comm_round=2, norm_bound=0.5)
    trainer, data = _setup(cfg)
    run_donate_pair(lambda donate: MeshRobustEngine(
        trainer, data, cfg, defense="median", n_byzantine=1,
        mesh=make_mesh(8), donate=donate, stream_block=8,
        param_block_bytes=16 * 64))


def test_blockstream_uint8_h2d_byte_reduction():
    """Transfer-compression acceptance (ISSUE 3): on the SAME
    block-streamed round, the uint8 cohort stack must cross host→device
    in ≥3.5x fewer bytes than the f32 stack and ≥1.9x fewer than bf16
    (x dominates; y/mask/weights/rngs ride uncompressed), the byte
    counters must land in the per-round records, and the uint8 round
    must still train close to f32."""
    cfg = _mnist_like_cfg(client_num_per_round=16, comm_round=1)
    trainer, data = _setup(cfg)
    bytes_per, results = {}, {}
    v0 = None
    for sd, tag in ((None, "f32"), (jnp.bfloat16, "bf16"),
                    (jnp.uint8, "u8")):
        eng = MeshFedAvgEngine(trainer, data, cfg, mesh=make_mesh(8),
                               donate=False, stream_block=8,
                               stack_dtype=sd)
        if v0 is None:
            v0 = eng.init_variables()
        results[tag] = eng.run(variables=jax.tree.map(jnp.copy, v0),
                               rounds=1)
        bytes_per[tag] = eng.transfer_stats.h2d_bytes
        assert bytes_per[tag] > 0
        # per-round records carry the byte accounting
        assert eng.transfer_stats.rounds[0]["h2d_bytes"] > 0
    assert bytes_per["f32"] / bytes_per["u8"] >= 3.5, bytes_per
    assert bytes_per["bf16"] / bytes_per["u8"] >= 1.9, bytes_per
    for a, b in zip(jax.tree.leaves(results["f32"]),
                    jax.tree.leaves(results["u8"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0.05, atol=0.02)
