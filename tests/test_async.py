"""Async federation subsystem tests (fedml_tpu/async_ — the ISSUE-5
tentpole's virtual-time path).

Anchors, in order of importance:

* Degenerate equivalence pin: async with zero latency, zero dropout,
  buffer_k == cohort, constant staleness weight, mix 1.0 is BITWISE the
  synchronous FedAvg engine (same style as the test_prefetch.py /
  donate-pair pins) — the async numerics are anchored to the rest of
  the repo, not merely plausible.
* Seeded determinism: two runs with the same --async_seed produce
  identical event traces (arrival order, crashes, rejoins, commits)
  and identical variables.
* Staleness math: the weight families, the zero-weight pad-lane
  exactness of partial (deadline) commits, buffer hygiene.
* Quality band: the staleness-discounted path on the synthetic MNIST
  task stays in the band calibrated in benchmarks/quality_bands.json
  (same RECALIBRATE protocol as the other bands).
* Checkpoint: the async server state (buffer contents + per-client
  staleness counters) round-trips through FedCheckpointManager's
  extra_state and a resumed run continues committing.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.algorithms.fedavg import FedAvgEngine
from fedml_tpu.async_ import (AsyncBuffer, AsyncFedAvgEngine,
                              LifecycleConfig, make_commit_fn,
                              staleness_weight)
from fedml_tpu.async_.staleness import (flat_dim, flatten_vars_row,
                                        unflatten_rows)
from fedml_tpu.core.pytree import tree_weighted_mean

from parallel_case import _mnist_like_cfg, _setup
from test_quality_regression import _assert_band


def _assert_trees_bitwise(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# -- staleness weight families ----------------------------------------------

def test_staleness_weight_families():
    s = jnp.asarray([0.0, 1.0, 3.0, 4.0, 10.0])
    np.testing.assert_array_equal(np.asarray(
        staleness_weight("constant", s)), np.ones(5, np.float32))
    poly = np.asarray(staleness_weight("polynomial", s, a=0.5))
    np.testing.assert_allclose(poly, (1.0 + np.asarray(s)) ** -0.5,
                               rtol=1e-6)
    assert np.all(np.diff(poly) < 0)          # strictly discounting
    hinge = np.asarray(staleness_weight("hinge", s, a=1.0, b=4.0))
    np.testing.assert_allclose(hinge[:4], 1.0)    # flat up to the knee
    np.testing.assert_allclose(hinge[4], 1.0 / 7.0, rtol=1e-6)
    with pytest.raises(ValueError, match="unknown staleness mode"):
        staleness_weight("linear", s)


def test_commit_constant_full_buffer_is_weighted_mean_bitwise():
    """α=1 + constant weights + full buffer: the commit IS
    tree_weighted_mean — bitwise, the degenerate pin's algebraic core."""
    rs = np.random.RandomState(0)
    template = {"params": {"w": jnp.asarray(rs.randn(4, 3), jnp.float32),
                           "b": jnp.asarray(rs.randn(3), jnp.float32)}}
    K, P = 5, flat_dim(template)
    rows = rs.randn(K, P).astype(np.float32)
    w = rs.rand(K).astype(np.float32) + 0.5
    stacked = unflatten_rows(jnp.asarray(rows), template)
    # compiled vs compiled: every production caller runs
    # tree_weighted_mean inside a jit, and XLA:CPU's fused multiply-add
    # differs from the op-by-op eager result in the last ulp
    want = jax.jit(tree_weighted_mean)(stacked, jnp.asarray(w))
    commit = make_commit_fn(template, mode="constant", donate=False)
    got, stats = commit(template, jnp.asarray(rows), jnp.asarray(w),
                        jnp.zeros(K, jnp.float32), jnp.float32(1.0))
    _assert_trees_bitwise(got, want)
    assert float(stats["discount_mass"]) == pytest.approx(1.0)


def test_commit_zero_weight_pad_lanes_are_exact():
    """A deadline commit drains a part-full buffer padded with
    zero-weight lanes: the padded commit must equal the unpadded one
    BITWISE (one compiled program serves both shapes only because the
    pad lanes are numeric no-ops)."""
    rs = np.random.RandomState(1)
    template = {"params": {"w": jnp.zeros((6, 2), jnp.float32)}}
    P = flat_dim(template)
    rows3 = rs.randn(3, P).astype(np.float32)
    w3 = rs.rand(3).astype(np.float32) + 0.1
    s3 = np.asarray([0.0, 2.0, 1.0], np.float32)
    commit = make_commit_fn(template, mode="polynomial", a=0.5,
                            donate=False)
    bare, _ = commit(template, jnp.asarray(rows3), jnp.asarray(w3),
                     jnp.asarray(s3), jnp.float32(0.7))
    rows5 = np.concatenate([rows3, rs.randn(2, P).astype(np.float32)])
    w5 = np.concatenate([w3, np.zeros(2, np.float32)])
    s5 = np.concatenate([s3, np.zeros(2, np.float32)])
    padded, _ = commit(template, jnp.asarray(rows5), jnp.asarray(w5),
                       jnp.asarray(s5), jnp.float32(0.7))
    _assert_trees_bitwise(bare, padded)


def test_buffer_hygiene():
    buf = AsyncBuffer(2, 4)
    assert not buf.add(np.ones(4, np.float32), 1.0, 0.0)
    assert buf.add(np.full(4, 2.0, np.float32), 2.0, 1.0)   # full
    with pytest.raises(RuntimeError, match="overflow"):
        buf.add(np.ones(4, np.float32), 1.0, 0.0)
    rows, w, s, n = buf.drain()
    assert n == 2 and buf.count == 0
    np.testing.assert_array_equal(w, [1.0, 2.0])
    np.testing.assert_array_equal(s, [0.0, 1.0])
    assert np.all(buf.rows == 0.0)            # reset for the next window
    with pytest.raises(ValueError, match="shape mismatch"):
        buf.load_state({"rows": np.zeros((3, 4), np.float32),
                        "weights": np.zeros(3), "staleness": np.zeros(3),
                        "count": 0})


def test_flat_row_layout_matches_engine_flat_carry():
    """The buffer row layout must stay the engine flat-carry layout
    (ravel + concat in jax leaf order) — flatten_vars_row and
    parallel.engine.flatten_carry_f32 agree element for element."""
    from fedml_tpu.parallel.engine import flatten_carry_f32
    rs = np.random.RandomState(2)
    tree = {"params": {"a": jnp.asarray(rs.randn(3, 2), jnp.float32),
                       "b": jnp.asarray(rs.randn(5), jnp.float32)}}
    np.testing.assert_array_equal(flatten_vars_row(tree),
                                  np.asarray(flatten_carry_f32(tree)[0]))


# -- the virtual-time scheduler ---------------------------------------------

def test_async_degenerate_bitwise_matches_sync_fedavg():
    """THE equivalence pin: zero latency, zero dropout, buffer_k ==
    cohort, constant staleness, mix 1.0 — the async engine's dispatch
    waves reproduce the sync engine's rounds (same cohorts, same
    per-client rngs, same vmap width, same weighted mean) BITWISE."""
    cfg = _mnist_like_cfg(comm_round=3)
    trainer, data = _setup(cfg)
    sync = FedAvgEngine(trainer, data, cfg, donate=False)
    v0 = sync.init_variables()
    v_sync = sync.run(variables=jax.tree.map(jnp.copy, v0), rounds=3)
    a = AsyncFedAvgEngine(trainer, data, cfg, buffer_k=16, donate=False)
    v_async = a.run(variables=jax.tree.map(jnp.copy, v0), rounds=3)
    _assert_trees_bitwise(v_sync, v_async)
    rep = a.async_report()
    assert rep["committed_updates"] == 3
    assert rep["staleness_p95"] == 0.0        # nothing was ever stale
    assert rep["buffer_occupancy_mean"] == 16.0


def test_async_seeded_determinism():
    """Two engines with the same async seed produce IDENTICAL event
    traces (dispatch/arrive/crash/rejoin/commit with virtual times and
    staleness) and identical variables — the satellite's contract."""
    cfg = _mnist_like_cfg(client_num_per_round=8, comm_round=8)
    trainer, data = _setup(cfg)
    lc = LifecycleConfig(latency="lognormal", latency_scale=1.0,
                         latency_sigma=0.8, heterogeneity=0.5,
                         dropout_prob=0.2, rejoin_prob=1.0,
                         rejoin_delay_s=2.0, seed=7)

    def run_once():
        eng = AsyncFedAvgEngine(trainer, data, cfg, buffer_k=4,
                                concurrency=8, staleness="polynomial",
                                lifecycle_cfg=lc, donate=False)
        v = eng.run(rounds=8)
        return eng, v

    e1, v1 = run_once()
    e2, v2 = run_once()
    assert e1.trace == e2.trace
    _assert_trees_bitwise(v1, v2)
    # the fault machinery actually fired under this seed, so the
    # determinism claim covers crashes/rejoins, not just happy paths
    kinds = {t[0] for t in e1.trace}
    assert {"dispatch", "arrive", "crash", "rejoin", "commit"} <= kinds
    # staleness histogram identical too
    assert e1.staleness_committed == e2.staleness_committed
    assert e1.async_report()["staleness_p95"] > 0.0


def test_async_seed_changes_trace():
    """Different seeds must actually change the fault schedule —
    otherwise the determinism pin would pass vacuously."""
    cfg = _mnist_like_cfg(client_num_per_round=8, comm_round=4)
    trainer, data = _setup(cfg)

    def run_seed(seed):
        lc = LifecycleConfig(latency="lognormal", latency_scale=1.0,
                             dropout_prob=0.2, seed=seed)
        eng = AsyncFedAvgEngine(trainer, data, cfg, buffer_k=4,
                                concurrency=8, lifecycle_cfg=lc,
                                donate=False)
        eng.run(rounds=4)
        return eng.trace

    assert run_seed(1) != run_seed(2)


def test_async_deadline_commits_partial_buffer():
    """A permanently-crashing straggler cohort cannot fill the buffer;
    the round deadline commits the partial buffer and the run still
    reaches its commit budget (deadline commits counted)."""
    cfg = _mnist_like_cfg(client_num_in_total=4, client_num_per_round=4,
                          comm_round=4)
    trainer, data = _setup(cfg)
    lc = LifecycleConfig(latency="lognormal", latency_scale=1.0,
                         dropout_prob=0.5, rejoin_prob=1.0,
                         rejoin_delay_s=10.0, seed=3)
    eng = AsyncFedAvgEngine(trainer, data, cfg, buffer_k=4,
                            round_deadline_s=2.0, lifecycle_cfg=lc,
                            donate=False)
    eng.run(rounds=4)
    rep = eng.async_report()
    assert rep["committed_updates"] == 4
    assert rep["deadline_commits"] > 0
    assert rep["buffer_occupancy_mean"] < 4.0     # genuinely partial


def test_async_scheduler_deadlock_dumps_and_raises(tmp_path):
    """Everything crashes and nobody rejoins: the scheduler must fail
    LOUDLY with a flight-recorder dump (the ISSUE-5 diagnosis artifact),
    not spin or hang."""
    from fedml_tpu import obs
    cfg = _mnist_like_cfg(client_num_in_total=4, client_num_per_round=4,
                          comm_round=2)
    trainer, data = _setup(cfg)
    lc = LifecycleConfig(dropout_prob=1.0, rejoin_prob=0.0, seed=1)
    eng = AsyncFedAvgEngine(trainer, data, cfg, buffer_k=4,
                            lifecycle_cfg=lc, donate=False)
    obs.reset()
    obs.configure(str(tmp_path), install_signal=False,
                  export_at_exit=False)
    try:
        with pytest.raises(RuntimeError, match="async scheduler deadlock"):
            eng.run(rounds=2)
        import json
        reasons = [json.load(open(d))["reason"]
                   for d in obs.flight().dumps]
        # exactly ONE dump, with the sharp reason — the generic
        # engine-error handler must not write a duplicate
        assert reasons == ["async_scheduler_deadlock"], reasons
    finally:
        obs.reset()


def test_async_fedasync_k1_pure_async():
    """buffer_k=1 is pure FedAsync: every arrival commits immediately,
    mix<1 keeps a server fraction, and the run still learns."""
    cfg = _mnist_like_cfg(client_num_per_round=8, comm_round=12)
    trainer, data = _setup(cfg)
    lc = LifecycleConfig(latency="lognormal", latency_scale=1.0,
                         latency_sigma=0.6, seed=5)
    eng = AsyncFedAvgEngine(trainer, data, cfg, buffer_k=1, concurrency=8,
                            staleness="polynomial", mix=0.5,
                            lifecycle_cfg=lc, donate=False)
    v = eng.run(rounds=12)
    rep = eng.async_report()
    assert rep["committed_updates"] == 12
    assert rep["buffer_occupancy_mean"] == 1.0
    assert rep["staleness_p95"] > 0.0         # concurrency 8 over K=1
    assert np.isfinite(eng.evaluate(v)["test_loss"])


def test_async_metrics_registered():
    """The ISSUE-5 obs contract: buffer occupancy gauge + staleness
    histogram land in the metrics registry."""
    from fedml_tpu import obs
    cfg = _mnist_like_cfg(comm_round=2)
    trainer, data = _setup(cfg)
    eng = AsyncFedAvgEngine(trainer, data, cfg, buffer_k=16, donate=False)
    before = obs.counter("async_commits_total").value
    eng.run(rounds=2)
    assert obs.counter("async_commits_total").value == before + 2
    h = obs.histogram("async_staleness",
                      buckets=obs.metrics.STALENESS_BUCKETS)
    assert h.count >= 32                      # 2 full 16-buffers arrived


# -- quality band (staleness-discounted path on the synthetic task) ---------

def test_async_staleness_quality_band():
    """The staleness-discounted async path on the MNIST-row-shaped
    synthetic task (1000 clients, lr 0.03, bs 10): concurrency 2x the
    buffer under lognormal latency produces real staleness, and the
    polynomial-discounted run must land in the band calibrated in
    benchmarks/quality_bands.json (RECALIBRATE protocol on toolchain
    skew — see test_quality_regression.py)."""
    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.data.loaders import load_data
    from fedml_tpu.models import create_model
    from fedml_tpu.utils.config import FedConfig
    data = load_data("mnist", client_num_in_total=1000, batch_size=10,
                     synthetic_scale=0.2, seed=0)
    assert data.synthetic
    cfg = FedConfig(client_num_in_total=1000, client_num_per_round=10,
                    comm_round=16, epochs=1, batch_size=10, lr=0.03,
                    frequency_of_the_test=10_000)
    trainer = ClientTrainer(create_model("lr", output_dim=10), lr=cfg.lr)
    lc = LifecycleConfig(latency="lognormal", latency_scale=1.0,
                         latency_sigma=0.8, heterogeneity=0.5, seed=0)
    eng = AsyncFedAvgEngine(trainer, data, cfg, buffer_k=5, concurrency=10,
                            staleness="polynomial", staleness_a=0.5,
                            lifecycle_cfg=lc, donate=False)
    v = eng.run(rounds=16)
    assert eng.async_report()["staleness_p95"] > 0.0   # discount exercised
    _assert_band("async_mnist_lr_acc", eng.evaluate(v)["test_acc"])


# -- checkpoint round-trip ---------------------------------------------------

def test_async_checkpoint_roundtrips_server_state(tmp_path):
    """FedCheckpointManager extra_state carries the async server state
    (buffer contents + per-client staleness counters) through orbax
    bit-exactly, and a resumed run continues committing from the saved
    version."""
    from fedml_tpu.utils.checkpoint import FedCheckpointManager
    cfg = _mnist_like_cfg(client_num_per_round=8, comm_round=4)
    trainer, data = _setup(cfg)
    lc = LifecycleConfig(latency="lognormal", latency_scale=1.0,
                         dropout_prob=0.2, seed=9)

    def make():
        return AsyncFedAvgEngine(trainer, data, cfg, buffer_k=4,
                                 concurrency=8, staleness="polynomial",
                                 lifecycle_cfg=lc, donate=False)

    ck = FedCheckpointManager(str(tmp_path / "ack"))
    eng = make()
    eng.run(rounds=4, ckpt=ck, ckpt_every=2)
    assert ck.latest_round() is not None
    saved = eng.async_state()     # state at the LAST checkpointed commit
    fresh = make()
    step, v, _ss, extra = ck.restore(
        fresh.init_variables(), (), extra_template=fresh.async_state())
    # the per-client staleness counters + buffer round-tripped bit-exactly
    # (the final checkpoint fired at the last commit, so the saved state
    # equals the engine's end-of-run state)
    assert int(extra["version"]) == step + 1
    for k in ("rows", "weights", "staleness", "count"):
        np.testing.assert_array_equal(np.asarray(extra["buffer"][k]),
                                      np.asarray(saved["buffer"][k]))
    # ISSUE 10: the sharded client registry rides the checkpoint —
    # participation/staleness/quarantine shards round-trip bit-exactly
    for k in ("participation", "last_staleness", "quarantined",
              "last_seen"):
        np.testing.assert_array_equal(np.asarray(extra["registry"][k]),
                                      np.asarray(saved["registry"][k]))
    assert int(np.asarray(
        extra["registry"]["participation"]).sum()) > 0
    fresh.load_async_state(extra)
    assert fresh.version == step + 1
    # restored registry serves the same counters the saved one did
    ids = np.arange(fresh.registry.n_clients)
    np.testing.assert_array_equal(
        fresh.registry.participation(ids), eng.registry.participation(ids))
    np.testing.assert_array_equal(
        fresh.registry.last_staleness(ids),
        eng.registry.last_staleness(ids))
    # and the restored engine keeps committing from there
    out = fresh.run(variables=v, rounds=fresh.version + 2)
    assert fresh.version == step + 3
    assert np.isfinite(fresh.evaluate(out)["test_loss"])
    ck.close()


# -- ISSUE 6: streaming aggregation-on-arrival ------------------------------

def _rand_rows(seed, k, p):
    rs = np.random.RandomState(seed)
    rows = rs.randn(k, p).astype(np.float32)
    w = rs.randint(1, 40, k).astype(np.float32)
    s = rs.randint(0, 6, k).astype(np.float32)
    return rows, w, s


@pytest.mark.parametrize("mode,n_real", [
    ("constant", 6), ("constant", 3),          # full + partial (deadline)
    ("polynomial", 6), ("polynomial", 3),
])
def test_streaming_commit_matches_drained_commit_bitwise(mode, n_real):
    """The ISSUE-6 bitwise pin: a streaming AsyncBuffer (per-arrival
    jitted folds) committed through make_stream_commit_fn equals the
    drained [K, P] commit — the compiled drain-fold twin over the
    capacity-padded matrix fed to the SAME commit program — bit for
    bit, for constant and polynomial staleness weights, full and
    partial (deadline, zero-weight pad lanes) buffers."""
    from fedml_tpu.async_.staleness import (AsyncBuffer, make_drain_fold_fn,
                                            make_fold_fn,
                                            make_stream_commit_fn)
    K, P = 6, 37
    rows, w, s = _rand_rows(11 + n_real, n_real, P)
    template = {"params": {"a": jnp.zeros((5, 7), jnp.float32),
                           "b": jnp.zeros((2,), jnp.float32)}}
    rs = np.random.RandomState(99)
    variables = jax.tree.map(
        lambda l: jnp.asarray(rs.randn(*l.shape), jnp.float32), template)

    # arm 1: streaming buffer — per-arrival folds, O(P) commit
    buf = AsyncBuffer(K, P, streaming=True, staleness_mode=mode,
                      staleness_a=0.5)
    for i in range(n_real):
        buf.add(rows[i], float(w[i]), float(s[i]))
    acc, wsum, bw, bs, n, raw = buf.take_stream()
    assert n == n_real and raw == float(np.sum(w))
    np.testing.assert_array_equal(bw[:n_real], w)
    commit = make_stream_commit_fn(variables, donate=False)
    new_stream, st = commit(variables, acc, wsum, jnp.float32(0.7))

    # arm 2: drained replay — one compiled scan over the padded matrix
    padded = np.zeros((K, P), np.float32)
    padded[:n_real] = rows
    pw = np.zeros((K,), np.float32)
    pw[:n_real] = w
    ps = np.zeros((K,), np.float32)
    ps[:n_real] = s
    drain = make_drain_fold_fn(mode, a=0.5)
    dacc, dwsum = drain(jnp.asarray(padded), jnp.asarray(pw),
                        jnp.asarray(ps))
    np.testing.assert_array_equal(np.asarray(acc), np.asarray(dacc))
    np.testing.assert_array_equal(np.asarray(wsum), np.asarray(dwsum))
    new_drain, _ = commit(variables, dacc, dwsum, jnp.float32(0.7))
    _assert_trees_bitwise(new_stream, new_drain)
    # the arrival fold alone pins too (the scan body == the fold body)
    fold = make_fold_fn(mode, a=0.5)
    facc = jnp.zeros((P,), jnp.float32)
    fwsum = jnp.zeros((), jnp.float32)
    for i in range(n_real):
        facc, fwsum = fold(facc, fwsum, rows[i], jnp.float32(w[i]),
                           jnp.float32(s[i]))
    np.testing.assert_array_equal(np.asarray(facc), np.asarray(dacc))
    np.testing.assert_array_equal(np.asarray(fwsum), np.asarray(dwsum))


def test_async_buffer_add_sparse_matches_dense_add_bitwise():
    """ISSUE 19: add_sparse folds the k (index, value) pairs through
    the jitted sparse twin — the accumulator and wsum stay BITWISE the
    dense add() of the densified rows (the sparse fold scatters into
    an in-program zero row and reuses the dense fold's exact
    multiply-add expression), and the guards route misuse to
    RuntimeError instead of a silent wrong fold."""
    from fedml_tpu.async_.staleness import AsyncBuffer

    K, P, k = 5, 64, 4
    rs = np.random.RandomState(2)
    dense = AsyncBuffer(K, P, streaming=True,
                        staleness_mode="polynomial", staleness_a=0.5)
    sparse = AsyncBuffer(K, P, streaming=True,
                         staleness_mode="polynomial", staleness_a=0.5)
    for i in range(K):
        idx = np.sort(rs.choice(P, k, replace=False)).astype(np.int64)
        vals = rs.randn(k).astype(np.float32)
        row = np.zeros(P, np.float32)
        row[idx] = vals
        full_d = dense.add(row, 1.0 + i, float(i))
        full_s = sparse.add_sparse(idx, vals, 1.0 + i, float(i))
        assert full_d == full_s
    da, dw = dense.take_stream()[:2]
    sa, sw = sparse.take_stream()[:2]
    np.testing.assert_array_equal(np.asarray(da), np.asarray(sa))
    np.testing.assert_array_equal(np.asarray(dw), np.asarray(sw))
    # guards: drain mode and bucketed buffers have no sparse fold
    import pytest as _pytest
    drain = AsyncBuffer(2, P)
    with _pytest.raises(RuntimeError, match="drain-mode"):
        drain.add_sparse(np.zeros(1, np.int64),
                         np.zeros(1, np.float32), 1.0, 0.0)
    bucketed = AsyncBuffer(4, P, streaming=True, buckets=2)
    with _pytest.raises(RuntimeError, match="bucket"):
        bucketed.add_sparse(np.zeros(1, np.int64),
                            np.zeros(1, np.float32), 1.0, 0.0)


def test_async_buffer_thread_safe_adds_and_snapshots():
    """ISSUE-6 satellite: AsyncBuffer is internally thread-safe — 8
    threads racing adds against state() snapshots never tear a
    (count, weights, accumulator) triple, in both modes."""
    import threading

    for streaming in (False, True):
        K, P = 64, 16
        buf = AsyncBuffer(K, P, streaming=streaming)
        rows = np.random.RandomState(3).randn(K, P).astype(np.float32)
        torn = []

        def snapshotter(stop):
            while not stop.is_set():
                st = buf.state()
                n = int(st["count"])
                # a torn snapshot would show a filled row/weight beyond
                # count or a count beyond capacity
                if n > K or np.count_nonzero(st["weights"]) > n:
                    torn.append(st)

        stop = threading.Event()
        snap = threading.Thread(target=snapshotter, args=(stop,))
        snap.start()
        threads = [threading.Thread(
            target=lambda lo: [buf.add(rows[i], 1.0 + i, float(i % 3))
                               for i in range(lo, lo + 8)],
            args=(lo,)) for lo in range(0, K, 8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stop.set()
        snap.join()
        assert not torn
        assert buf.count == K
        if streaming:
            acc, wsum, w, s, n, raw = buf.take_stream()
            assert n == K
            # fold order is thread-scheduled, so compare to tolerance
            # (the bitwise pin lives in the deterministic test above);
            # row i always folded with weight 1+i regardless of slot
            expect = (rows * (1.0 + np.arange(K,
                                              dtype=np.float32))[:, None]
                      ).sum(0)
            np.testing.assert_allclose(np.asarray(acc), expect,
                                       rtol=2e-4, atol=2e-4)
            assert float(wsum) == pytest.approx(float(np.sum(w)))
        else:
            got_rows, w, s, n = buf.drain()
            assert n == K
            # every row landed exactly once (weight 1+i names row i)
            np.testing.assert_array_equal(got_rows[np.argsort(w)], rows)


def test_async_buffer_streaming_checkpoint_roundtrip(tmp_path):
    """ISSUE-6 satellite: the streaming accumulator fields (acc, wsum,
    raw_wsum) round-trip through FedCheckpointManager extra_state
    bit-exactly, a drain-mode checkpoint REPLAYS into a streaming
    buffer bitwise, and a streaming checkpoint refuses to restore into
    a drain-mode buffer (the rows are gone)."""
    from fedml_tpu.utils.checkpoint import FedCheckpointManager
    from fedml_tpu.async_.staleness import make_fold_fn

    K, P = 4, 23
    rows, w, s = _rand_rows(21, 3, P)
    buf = AsyncBuffer(K, P, streaming=True, staleness_mode="polynomial",
                      staleness_a=0.5)
    for i in range(3):
        buf.add(rows[i], float(w[i]), float(s[i]))
    state = buf.state()
    assert state["acc"].shape == (P,)

    # through orbax (0-d ndarray count/wsum/raw_wsum must survive)
    ck = FedCheckpointManager(str(tmp_path / "ing"))
    v = {"params": jnp.zeros((2,), jnp.float32)}
    ck.save(0, v, (), extra_state={"buffer": state})
    _, _, _, extra = ck.restore(v, (), extra_template={"buffer": state})
    ck.close()

    fresh = AsyncBuffer(K, P, streaming=True, staleness_mode="polynomial",
                        staleness_a=0.5)
    fresh.load_state(extra["buffer"])
    a0, w0, bw0, bs0, n0, raw0 = buf.take_stream()
    a1, w1, bw1, bs1, n1, raw1 = fresh.take_stream()
    np.testing.assert_array_equal(np.asarray(a0), np.asarray(a1))
    np.testing.assert_array_equal(np.asarray(w0), np.asarray(w1))
    np.testing.assert_array_equal(bw0, bw1)
    np.testing.assert_array_equal(bs0, bs1)
    assert n0 == n1 == 3 and raw0 == raw1

    # drain-mode checkpoint -> streaming buffer: replay == live folds
    dbuf = AsyncBuffer(K, P)
    for i in range(3):
        dbuf.add(rows[i], float(w[i]), float(s[i]))
    sbuf = AsyncBuffer(K, P, streaming=True, staleness_mode="polynomial",
                       staleness_a=0.5)
    sbuf.load_state(dbuf.state())
    a2, w2, *_ = sbuf.take_stream()
    np.testing.assert_array_equal(np.asarray(a0), np.asarray(a2))
    np.testing.assert_array_equal(np.asarray(w0), np.asarray(w2))

    # streaming checkpoint -> drain-mode buffer: explicit refusal
    buf2 = AsyncBuffer(K, P, streaming=True)
    buf2.add(rows[0], 1.0, 0.0)
    with pytest.raises(ValueError, match="not reconstructible"):
        AsyncBuffer(K, P).load_state(buf2.state())
