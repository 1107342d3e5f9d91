"""Serving-spine tests (fedml_tpu/scale — the ISSUE-10 tentpole).

Anchors, in order of importance:

* Degenerate sampling pin: the streaming cohort sampler in uniform mode
  with a fully-eligible registry reproduces the existing ClientSampler
  cohorts BITWISE, and ClientSampler.sample is the reference's two
  lines bit for bit, from a private generator that leaves the global
  numpy RNG alone — the new spine is anchored to the old sampler, not
  merely plausible.
* Statistical pins: reservoir and stratified draws are chi-square
  uniform at a fixed seed, deterministic per seed, two seeds differ
  (the chaos/adversary seeded-stream convention).
* Registry memory: lazy shard growth (touching k clients allocates
  O(k/shard) shards, not the population), <= ~100 bytes/client fully
  allocated, orbax checkpoint round-trip through a SHAPE-STABLE state.
* ShardStore: on-demand cohorts bitwise-equal to the materialized
  all-client stack (mmap and generator backends), feeding the PR-1
  prefetcher and the async scheduler unchanged.
* Serve smoke: the 100k-client virtual-time serve loop sustains
  commits with sub-linear server memory; the 1M arm is slow/nightly.
"""
import os

import jax
import numpy as np
import pytest

from fedml_tpu.core.sampling import ClientSampler
from fedml_tpu.scale import (BYTES_PER_CLIENT, ArrivalConfig,
                             ClientRegistry, ConstantArrivals,
                             DiurnalArrivals, FlashCrowdArrivals,
                             GeneratorShardStore, MaterializedShardStore,
                             MmapShardStore, StreamingCohortSampler,
                             TraceArrivals, make_arrivals, run_serve_sim)
from fedml_tpu.scale import registry as R

from parallel_case import _mnist_like_cfg, _setup


# -- ClientSampler: the reference's draw from a private generator ------------

def _reference_draw(n, k, r):
    """The reference's two lines (FedAVGAggregator.client_sampling),
    written out: the oracle `ClientSampler.sample` is held to."""
    np.random.seed(r)
    return np.random.choice(range(n), k, replace=False)


@pytest.mark.parametrize("n,k", [
    (100, 10), (1000, 16),
    # the five benchmark cells' populations and cohorts
    (256, 4), (1024, 128), (4000, 10), (4096, 128), (342_477, 50)])
def test_sample_bitwise_matches_reference_oracle(n, k):
    """A private legacy RandomState seeded with the round walks the
    stream np.random.seed + the global choice(range(N)) walk: the same
    cohort bit for bit, over populations and rounds."""
    s = ClientSampler(n, k)
    for r in (0, 1, 7, 12345):
        got = s.sample(r)
        assert got.dtype == np.int64
        np.testing.assert_array_equal(got, _reference_draw(n, k, r))


@pytest.mark.parametrize("n,k", [(8, 16), (16, 16)])
def test_sample_full_participation_is_arange(n, k):
    """k >= N draws nothing: every client, in id order (the reference's
    own full-participation branch)."""
    got = ClientSampler(n, k).sample(5)
    assert got.dtype == np.int64
    np.testing.assert_array_equal(got, np.arange(n))


def test_sample_does_not_mutate_global_rng():
    np.random.seed(4242)
    before = np.random.get_state()
    ClientSampler(10_000, 64).sample(7)
    after = np.random.get_state()
    assert before[0] == after[0]
    np.testing.assert_array_equal(before[1], after[1])
    assert before[2:] == after[2:]
    # ...while the reference's two lines famously do
    _reference_draw(10_000, 64, 7)
    assert not np.array_equal(before[1], np.random.get_state()[1])


def test_sample_k_override():
    s = ClientSampler(1000, 16)
    a = s.sample(3, k=5)
    assert a.shape == (5,) and len(np.unique(a)) == 5
    np.testing.assert_array_equal(a, _reference_draw(1000, 5, 3))
    np.testing.assert_array_equal(s.sample(3, k=16), s.sample(3))
    np.testing.assert_array_equal(s.sample(3, k=1000), np.arange(1000))


def test_sample_builds_no_python_range():
    """Structural, not a timing: at the StackOverflow population one
    draw's scratch is the int64 permutation (2.7 MB); the reference's
    range(N) is ~12 MB of boxed ints on top of it."""
    import tracemalloc
    s = ClientSampler(342_477, 50)
    s.sample(0)     # this thread's generator exists before the count
    tracemalloc.start()
    try:
        s.sample(1)
        _, peak = tracemalloc.get_traced_memory()
        tracemalloc.reset_peak()
        _reference_draw(342_477, 50, 1)
        _, ref_peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert peak < 8e6, peak
    assert ref_peak > 8e6, ref_peak   # the bound tells the two apart


def test_engine_sample_padded_np_is_the_oracle_padded():
    """Every benchmark cell's round draws through this: the oracle's
    ids, padded to a mesh multiple with zero-weight lanes."""
    from fedml_tpu.parallel.engine import MeshFedAvgEngine
    from fedml_tpu.parallel.mesh import make_mesh
    cfg = _mnist_like_cfg(client_num_in_total=16, client_num_per_round=5)
    trainer, data = _setup(cfg)
    eng = MeshFedAvgEngine(trainer, data, cfg, mesh=make_mesh(2),
                           donate=False)
    assert type(eng.sampler) is ClientSampler
    for r in (0, 3, 11):
        ids, wmask = eng._sample_padded_np(r)
        np.testing.assert_array_equal(ids[:5], _reference_draw(16, 5, r))
        np.testing.assert_array_equal(ids[5:], [0])
        np.testing.assert_array_equal(wmask, [1, 1, 1, 1, 1, 0])


def test_sample_threads_draw_their_own_rounds():
    """One sampler, more threads than cores, each drawing its own
    rounds with the switch interval shortened: a generator shared
    between them would be re-seeded between one thread's seed and its
    choice, and a cohort would be another round's."""
    import sys
    import threading
    s = ClientSampler(5000, 32)
    n_threads, draws = 2 * (os.cpu_count() or 4), 60
    want = {r: _reference_draw(5000, 32, r)
            for r in range(n_threads * draws)}
    wrong, start = [], threading.Barrier(n_threads)

    def worker(t):
        start.wait(timeout=60)
        for r in range(t * draws, (t + 1) * draws):
            if not np.array_equal(s.sample(r), want[r]):
                wrong.append(r)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(th.is_alive() for th in threads)
    assert wrong == []


# -- registry ----------------------------------------------------------------

def test_registry_lifecycle_counters():
    reg = ClientRegistry(100, shard_size=16)
    reg.note_dispatch(np.asarray([3, 17, 99]), version=2)
    assert reg.count_in_flight == 3 and reg.count_free == 97
    np.testing.assert_array_equal(reg.outstanding_of([3, 17, 99]),
                                  [2, 2, 2])
    np.testing.assert_array_equal(np.sort(reg.outstanding_ids()),
                                  [3, 17, 99])
    assert reg.note_return(17) == 2
    reg.note_contribution(17, staleness=1.5, version=3)
    assert reg.count_in_flight == 2
    assert reg.participation([17])[0] == 1
    assert reg.last_staleness([17])[0] == np.float32(1.5)
    reg.note_crash(3, rejoins=True)
    reg.note_crash(99, rejoins=False)
    assert reg.count_crashed == 1 and reg.count_dead == 1
    assert reg.count_in_flight == 0
    reg.note_rejoin(3)
    assert reg.count_crashed == 0 and reg.count_free == 99
    reg.note_quarantine(17)
    assert reg.quarantines([17])[0] == 1
    reg.ban([5, 6])
    assert reg.count_banned == 2
    assert not reg.eligible([5])[0] and reg.eligible([7])[0]
    assert reg.total_participation() == 1


def test_registry_lazy_memory_growth():
    """The O(1)-memory-growth pin: touching a handful of clients in a
    2M-client registry allocates only their shards; even fully
    allocated, the field set stays <= ~100 bytes/client (acceptance
    bound) — 29 today."""
    assert BYTES_PER_CLIENT <= 100
    reg = ClientRegistry(2_000_000)
    assert reg.nbytes == 0 and reg.n_shards == 31
    reg.note_dispatch(np.asarray([0, 1, 2]), 0)          # shard 0
    reg.note_contribution(1_999_999, 0.0, 0)             # last shard
    assert len(reg._shards) == 2
    assert reg.nbytes <= 2 * reg.shard_size * BYTES_PER_CLIENT
    assert reg.bytes_per_client < 2.0                    # sub-linear
    # fully-allocated worst case still under the gate
    assert (reg.n_clients * BYTES_PER_CLIENT / reg.n_clients) <= 100


def test_registry_quarantine_ban_threshold():
    """Below the threshold a quarantined client returns to the pool
    (the PR-9 redispatch contract — one false positive never exiles an
    honest client); at the threshold it auto-BANs and leaves the
    sampler's eligibility mask for good."""
    reg = ClientRegistry(50, quarantine_ban_threshold=3)
    assert not reg.note_quarantine(7)
    assert not reg.note_quarantine(7)
    assert reg.eligible([7])[0]                 # still in the pool
    assert reg.note_quarantine(7)               # third strike: banned
    assert not reg.eligible([7])[0]
    assert reg.count_banned == 1
    # threshold 0 (default) never bans — counter only
    reg0 = ClientRegistry(50)
    for _ in range(10):
        assert not reg0.note_quarantine(7)
    assert reg0.eligible([7])[0] and reg0.quarantines([7])[0] == 10


def test_registry_ban_is_sticky_and_dupes_dont_corrupt_counters():
    """A ban survives every lifecycle transition (dispatch/rejoin/
    crash cannot silently un-ban — only unban() can), and duplicated
    ids in the vectorized transition APIs count once."""
    reg = ClientRegistry(64, shard_size=16)
    reg.ban([9])
    reg.note_dispatch(np.asarray([9, 10]), 3)
    assert int(reg.status_of([9])[0]) == R.BANNED
    assert reg.outstanding_of([9])[0] == -1        # no dispatch marker
    assert reg.count_in_flight == 1                # only 10 moved
    reg.note_dispatch_one(9, 4)
    assert int(reg.status_of([9])[0]) == R.BANNED
    reg.note_rejoin(9)
    assert int(reg.status_of([9])[0]) == R.BANNED
    reg.unban([9])
    assert reg.eligible([9])[0] and reg.count_banned == 0
    # duplicate ids: one distinct client, one counter increment
    reg2 = ClientRegistry(64, shard_size=16)
    reg2.note_dispatch(np.asarray([1, 1, 2]), 0)
    assert reg2.count_in_flight == 2
    reg2.ban(np.asarray([5, 5, 5]))
    assert reg2.count_banned == 1
    assert reg2.count_free == 64 - 2 - 1


def test_scheduler_migrates_legacy_checkpoint_arrays(small_data=None):
    """A pre-PR-10 async_state (client_last_staleness/client_contribs
    arrays, no 'registry') still restores: the arrays migrate into
    registry counters instead of raising KeyError."""
    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.models import create_model
    from fedml_tpu.async_ import AsyncFedAvgEngine
    cfg = _mnist_like_cfg(client_num_in_total=16, client_num_per_round=4)
    _t, data = _setup(cfg)
    trainer = ClientTrainer(create_model("lr", output_dim=10), lr=cfg.lr)
    eng = AsyncFedAvgEngine(trainer, data, cfg, buffer_k=4,
                            concurrency=4, donate=False)
    legacy = eng.async_state()
    legacy.pop("registry")
    contribs = np.zeros(16, np.int64)
    contribs[[2, 7]] = [3, 1]
    stale = np.zeros(16, np.float32)
    stale[2] = 2.0
    legacy["client_contribs"] = contribs
    legacy["client_last_staleness"] = stale
    eng.load_async_state(legacy)
    assert eng.registry.participation([2, 7]).tolist() == [3, 1]
    assert eng.registry.last_staleness([2])[0] == np.float32(2.0)
    legacy.pop("client_contribs")
    with pytest.raises(ValueError, match="neither 'registry'"):
        eng.load_async_state(legacy)


def test_registry_free_ids_skips_ineligible():
    reg = ClientRegistry(40, shard_size=8)
    reg.note_dispatch(np.arange(0, 4), 0)
    reg.ban([4, 5])
    np.testing.assert_array_equal(reg.free_ids(5), [6, 7, 8, 9, 10])
    assert reg.eligible_per_shard()[0] == 2       # 6, 7 of shard 0


def test_registry_state_shape_stable_and_sparse_restore():
    """state() from a fresh registry and a touched one have identical
    tree shapes (the orbax-template requirement), and load_state
    re-sparsifies — all-default shards stay unallocated."""
    a = ClientRegistry(1000, shard_size=64)
    b = ClientRegistry(1000, shard_size=64)
    b.note_dispatch(np.asarray([100, 700]), 5)
    b.note_return(100)
    b.note_contribution(100, 2.0, 6)
    sa, sb = a.state(), b.state()
    assert set(sa) == set(sb)
    for k in sa:
        assert np.asarray(sa[k]).shape == np.asarray(sb[k]).shape, k
    c = ClientRegistry(1000, shard_size=64)
    c.load_state(sb)
    assert len(c._shards) == 2                    # shards 1 and 10 only
    assert c.count_in_flight == 1
    assert c.participation([100])[0] == 1
    np.testing.assert_array_equal(c.state()["participation"],
                                  sb["participation"])
    with pytest.raises(ValueError, match="registry shape mismatch"):
        ClientRegistry(1000, shard_size=32).load_state(sb)


def test_registry_roundtrips_through_orbax(tmp_path):
    """The checkpoint path the scheduler/manager use: registry shards
    ride FedCheckpointManager extra_state bit-exactly."""
    from fedml_tpu.utils.checkpoint import FedCheckpointManager
    reg = ClientRegistry(200, shard_size=32)
    reg.note_dispatch(np.asarray([1, 33, 199]), 4)
    reg.note_return(33)
    reg.note_contribution(33, 1.0, 5)
    reg.note_quarantine(199)
    v = {"w": np.zeros(3, np.float32)}
    ck = FedCheckpointManager(str(tmp_path / "reg"))
    ck.save(0, v, (), extra_state={"registry": reg.state()})
    _s, _v, _ss, extra = ck.restore(
        v, (), extra_template={"registry": ClientRegistry(
            200, shard_size=32).state()})
    fresh = ClientRegistry(200, shard_size=32)
    fresh.load_state(jax.tree.map(np.asarray, extra["registry"]))
    assert fresh.participation([33])[0] == 1
    assert fresh.quarantines([199])[0] == 1
    assert fresh.count_in_flight == 2
    ck.close()


def test_registry_obs_gauges():
    from fedml_tpu import obs
    reg = ClientRegistry(5000, shard_size=512)
    assert obs.gauge("registry_clients_total").value == 5000
    reg.note_dispatch(np.asarray([0]), 0)
    assert obs.gauge("registry_bytes").value == reg.nbytes > 0


# -- streaming cohort sampler ------------------------------------------------

@pytest.mark.parametrize("mode", ("uniform", "reservoir", "stratified"))
def test_sampler_deterministic_and_seeds_differ(mode):
    reg = ClientRegistry(5000, shard_size=512)
    s0 = StreamingCohortSampler(reg, 64, seed=0, mode=mode)
    a, b = s0.sample(3), s0.sample(3)
    np.testing.assert_array_equal(a, b)
    assert a.size == 64 and np.unique(a).size == 64
    c = StreamingCohortSampler(reg, 64, seed=1, mode=mode).sample(3)
    if mode != "uniform":      # uniform ignores the sampler seed by design
        assert not np.array_equal(np.sort(a), np.sort(c))
    assert not np.array_equal(s0.sample(4), a)       # rounds differ


@pytest.mark.parametrize("mode", ("uniform", "reservoir", "stratified"))
def test_sampler_excludes_ineligible(mode):
    reg = ClientRegistry(2000, shard_size=256)
    banned = np.arange(0, 2000, 7)
    reg.ban(banned)
    inflight = np.asarray([1, 2, 3, 500, 1500])
    reg.note_dispatch(inflight, 0)
    dead = np.asarray([10, 1000])
    for d in dead:
        reg.note_crash(int(d), rejoins=False)
    samp = StreamingCohortSampler(reg, 128, seed=0, mode=mode)
    for r in range(6):
        ids = samp.sample(r)
        bad = np.union1d(np.union1d(banned, inflight), dead)
        assert np.intersect1d(ids, bad).size == 0, mode
        assert np.unique(ids).size == ids.size == 128


def test_sampler_uniform_degenerate_reproduces_client_sampler():
    """THE acceptance pin: small-N uniform sampling over a fully-
    eligible registry reproduces the existing ClientSampler cohorts
    exactly (order included)."""
    for n, k in ((100, 10), (1000, 16)):
        reg = ClientRegistry(n)
        samp = StreamingCohortSampler(reg, k, seed=9, mode="uniform")
        ref = ClientSampler(n, k)
        for r in range(8):
            np.testing.assert_array_equal(samp.sample(r), ref.sample(r))


def _inclusion_chi2(mode, n=2000, shard=128, k=50, rounds=400, seed=0):
    reg = ClientRegistry(n, shard_size=shard)
    samp = StreamingCohortSampler(reg, k, seed=seed, mode=mode)
    counts = np.zeros(n, np.int64)
    for r in range(rounds):
        ids = samp.sample(r)
        assert ids.size == k
        counts[ids] += 1
    exp = rounds * k / n
    return float(((counts - exp) ** 2 / exp).sum() / (n - 1)), counts


@pytest.mark.parametrize("mode", ("reservoir", "stratified"))
def test_sampler_chi_square_uniformity(mode):
    """Chi-square-style uniformity at fixed seed: per-client inclusion
    counts over 400 rounds have chi2/dof ~ 1 (the 0.8-1.25 band is
    generous: dof=1999, a biased sampler lands far outside; the
    stratified mode exercises the MAX_STRATA shard-subset rotation —
    2000/128 = 16 shards > 8)."""
    stat, counts = _inclusion_chi2(mode)
    assert 0.8 < stat < 1.25, (mode, stat)
    assert counts.min() >= 0 and counts.max() < 40


def test_stratified_scratch_stays_shard_bounded():
    """The streaming-memory claim: a 1M-client stratified draw's peak
    numpy scratch is O(k + shard), nowhere near the population."""
    reg = ClientRegistry(1_000_000)
    samp = StreamingCohortSampler(reg, 64, seed=0, mode="stratified")
    for r in range(4):
        samp.sample(r)
    assert samp.peak_scratch_bytes < reg.shard_size * 8
    res = StreamingCohortSampler(reg, 64, seed=0, mode="reservoir")
    res.sample(0)
    # reservoir materializes one shard's keys+ids at a time, never the
    # population's
    assert res.peak_scratch_bytes < 4 * reg.shard_size * 16


# -- shard stores ------------------------------------------------------------

@pytest.fixture(scope="module")
def small_data():
    cfg = _mnist_like_cfg(client_num_in_total=12, client_num_per_round=4)
    _trainer, data = _setup(cfg)
    return cfg, data


def _assert_cohort_bitwise(a, b):
    ca, wa = a
    cb, wb = b
    assert set(ca) == set(cb)
    for k in ca:
        np.testing.assert_array_equal(np.asarray(ca[k]), np.asarray(cb[k]))
    np.testing.assert_array_equal(np.asarray(wa), np.asarray(wb))


def test_mmap_store_cohort_bitwise_matches_stack(small_data, tmp_path):
    """The shardstore-vs-materialized-stack pin: an MmapShardStore built
    from the same source hands back bitwise-identical cohorts (values
    AND weights) to the device-resident stack's gather."""
    _cfg, data = small_data
    store = MmapShardStore.build(data, str(tmp_path / "shards"),
                                 cache_clients=4)
    for ids in ([0, 3, 7], [11, 2], [5]):
        _assert_cohort_bitwise(store.cohort(np.asarray(ids)),
                               data.cohort(np.asarray(ids)))
    # cache path returns the same bits too
    _assert_cohort_bitwise(store.cohort(np.asarray([0, 3])),
                           data.cohort(np.asarray([0, 3])))
    # reopen from disk: no rebuild, same bits
    store2 = MmapShardStore(str(tmp_path / "shards"))
    _assert_cohort_bitwise(store2.cohort(np.asarray([7, 0])),
                           data.cohort(np.asarray([7, 0])))


def test_materialized_store_delegates(small_data):
    _cfg, data = small_data
    store = MaterializedShardStore(data)
    _assert_cohort_bitwise(store.cohort(np.asarray([1, 8])),
                           data.cohort(np.asarray([1, 8])))


def test_generator_store_deterministic_without_population_state():
    store = GeneratorShardStore(1_000_000, seed=3, cache_clients=2)
    a = store.client_shard(999_999)
    b = GeneratorShardStore(1_000_000, seed=3).client_shard(999_999)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
    # weights are their own stream: identical whether or not the shard
    # was fetched first
    w1 = store._weight(123_456)
    w2 = GeneratorShardStore(1_000_000, seed=3)._weight(123_456)
    assert w1 == w2
    c = GeneratorShardStore(1_000_000, seed=4).client_shard(999_999)
    assert not np.array_equal(a["x"], c["x"])
    # LRU: second fetch of a cached client hits
    from fedml_tpu import obs
    h0 = obs.counter("shardstore_cache_hits_total").value
    store.client_shard(999_999)
    assert obs.counter("shardstore_cache_hits_total").value == h0 + 1


def test_shardstore_feeds_prefetcher(small_data, tmp_path):
    """The PR-1 double buffer consumes a shard store unchanged: the
    prefetched cohort stream equals direct cohort() calls bitwise."""
    _cfg, data = small_data
    store = MmapShardStore.build(data, str(tmp_path / "pf"))
    cohorts = [np.asarray([0, 1]), np.asarray([9, 4]), np.asarray([2])]
    with store.prefetcher(cohorts) as pf:
        got = [pf.get() for _ in cohorts]
    for ids, g in zip(cohorts, got):
        _assert_cohort_bitwise(g, data.cohort(ids))


def test_async_scheduler_runs_on_shardstore_bitwise(small_data):
    """End-to-end wiring pin: the async engine fed by an on-demand
    shard store produces BITWISE the run it produces on the resident
    stack (the store pin lifted to the full scheduler)."""
    cfg, data = small_data
    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.models import create_model
    from fedml_tpu.async_ import AsyncFedAvgEngine

    def run(shardstore):
        trainer = ClientTrainer(create_model("lr", output_dim=10),
                                lr=cfg.lr)
        eng = AsyncFedAvgEngine(trainer, data, cfg, buffer_k=4,
                                concurrency=4, donate=False,
                                shardstore=shardstore)
        v = eng.run(rounds=2)
        return jax.tree.map(np.asarray, v), eng.trace

    v_stack, t_stack = run(None)
    v_store, t_store = run(MaterializedShardStore(data))
    assert t_stack == t_store
    for a, b in zip(jax.tree.leaves(v_stack), jax.tree.leaves(v_store)):
        np.testing.assert_array_equal(a, b)


# -- arrival processes -------------------------------------------------------

def test_arrivals_deterministic_and_seeds_differ():
    proc = DiurnalArrivals(rate=50.0, period_s=60.0, amplitude=0.8)

    def take(seed, n=200):
        it = proc.arrivals(0.0, np.random.default_rng([seed, 1]))
        return np.asarray([next(it) for _ in range(n)])

    np.testing.assert_array_equal(take(0), take(0))
    assert not np.array_equal(take(0), take(1))
    t = take(0)
    assert np.all(np.diff(t) > 0)                 # strictly increasing


def test_diurnal_rate_modulates_arrivals():
    proc = DiurnalArrivals(rate=100.0, period_s=100.0, amplitude=0.9)
    it = proc.arrivals(0.0, np.random.default_rng(0))
    ts = np.asarray([next(it) for _ in range(4000)])
    ts = ts[ts < 100.0]
    peak = np.count_nonzero((ts >= 15.0) & (ts < 35.0))    # sin ~ +1
    trough = np.count_nonzero((ts >= 65.0) & (ts < 85.0))  # sin ~ -1
    assert peak > 4 * trough, (peak, trough)
    # slowdown mirrors the curve: trough responds slower than peak
    assert proc.slowdown(75.0) > 3.0 * proc.slowdown(25.0)
    assert proc.slowdown(25.0) >= 1.0


def test_flash_crowd_bursts():
    proc = FlashCrowdArrivals(rate=50.0, period_s=1e9, amplitude=0.0,
                              flash_at_s=10.0, flash_duration_s=5.0,
                              flash_boost=8.0)
    it = proc.arrivals(0.0, np.random.default_rng(7))
    ts = np.asarray([next(it) for _ in range(3000)])
    ts = ts[ts < 30.0]
    inside = np.count_nonzero((ts >= 10.0) & (ts < 15.0))
    before = np.count_nonzero(ts < 5.0)
    assert inside > 4 * before, (inside, before)


def test_trace_replay_exact(tmp_path):
    times = np.asarray([0.5, 1.25, 2.0, 2.0, 9.5])
    proc = TraceArrivals(times)
    assert list(proc.arrivals(0.0)) == [0.5, 1.25, 2.0, 2.0, 9.5]
    assert list(proc.arrivals(1.0)) == [1.25, 2.0, 2.0, 9.5]
    p = tmp_path / "trace.txt"
    p.write_text("".join(f"{t}\n" for t in times))
    assert list(TraceArrivals.from_file(str(p)).arrivals(0.0)) == \
        list(proc.arrivals(0.0))
    cfg = ArrivalConfig(mode="trace", trace_path=str(p))
    assert isinstance(make_arrivals(cfg), TraceArrivals)


def test_arrival_config_validation():
    with pytest.raises(ValueError, match="unknown arrival mode"):
        ArrivalConfig(mode="tidal")
    with pytest.raises(ValueError, match="amplitude"):
        ArrivalConfig(mode="diurnal", amplitude=1.5)
    with pytest.raises(ValueError, match="trace_path"):
        make_arrivals(ArrivalConfig(mode="trace"))
    assert make_arrivals(ArrivalConfig(mode="none")) is None
    assert isinstance(make_arrivals(ArrivalConfig(mode="constant")),
                      ConstantArrivals)


def test_scheduler_arrivals_shape_trace_deterministically(small_data):
    """The scheduler wiring: a diurnal arrival process changes the
    event trace (latencies stretch at the trough) but stays
    deterministic — two runs with the same seed+process produce
    identical traces, like every other seeded stream."""
    cfg, data = small_data
    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.models import create_model
    from fedml_tpu.async_ import AsyncFedAvgEngine, LifecycleConfig

    def run(arrivals):
        trainer = ClientTrainer(create_model("lr", output_dim=10),
                                lr=cfg.lr)
        lc = LifecycleConfig(latency="lognormal", latency_scale=1.0,
                             seed=5)
        eng = AsyncFedAvgEngine(trainer, data, cfg, buffer_k=4,
                                concurrency=8, lifecycle_cfg=lc,
                                donate=False, arrivals=arrivals)
        eng.run(rounds=3)
        return eng.trace

    arr = ArrivalConfig(mode="diurnal", rate=100.0, period_s=10.0,
                        amplitude=0.9)
    t1, t2 = run(arr), run(arr)
    assert t1 == t2
    assert t1 != run(None)                  # the load curve is visible


# -- the serve loop ----------------------------------------------------------

def test_serve_smoke_100k_clients():
    """Fast virtual-time serve smoke at 100k clients: every commit
    lands, the registry stays under the byte gate with only touched
    shards allocated, eligibility holds (banned clients never
    contribute), and the report is reproducible per seed."""
    arr = ArrivalConfig(mode="diurnal", rate=1000.0, period_s=30.0,
                        amplitude=0.8)
    rep = run_serve_sim(100_000, commits=8, warmup_commits=2,
                        buffer_k=16, row_dim=256, arrival=arr,
                        dropout_prob=0.05, banned_frac=0.01, seed=0)
    assert rep["commits"] == 8
    assert rep["committed_updates"] == 8 * 16
    assert rep["committed_updates_per_sec"] > 0
    assert rep["registry_bytes_per_client"] <= 100.0
    assert rep["registry_bytes"] <= 100_000 * BYTES_PER_CLIENT
    assert rep["banned"] > 0 and rep["crashed"] > 0
    assert rep["sampler_peak_scratch_bytes"] < 1 << 20
    rep2 = run_serve_sim(100_000, commits=8, warmup_commits=2,
                         buffer_k=16, row_dim=256, arrival=arr,
                         dropout_prob=0.05, banned_frac=0.01, seed=0)
    # virtual-time trajectory is a pure function of the seed
    assert rep2["virtual_time_s"] == rep["virtual_time_s"]
    assert rep2["crashed"] == rep["crashed"]


def test_serve_loop_has_no_per_client_python_objects():
    """The no-per-client-Python-objects acceptance: after a serve run
    at 200k clients, the registry holds only numpy shards (no dict/
    set/list keyed by client) and the biggest Python container in the
    subsystem is O(shards), not O(population)."""
    reg = ClientRegistry(200_000)
    samp = StreamingCohortSampler(reg, 32, seed=0, mode="stratified")
    for r in range(20):
        ids = samp.sample(r)
        reg.note_dispatch(ids, r)
        for c in ids:
            reg.note_return(int(c))
            reg.note_contribution(int(c), 0.0, r)
    for container in (reg._shards, samp.__dict__):
        assert len(container) < 64
    for sh in reg._shards.values():
        for arr in sh.values():
            assert isinstance(arr, np.ndarray)


@pytest.mark.slow
def test_serve_sustains_1m_clients():
    """NIGHTLY acceptance (ISSUE 10): the 1M-client arm sustains
    committed-updates/sec (>= 0.4x of a 10k-client run of the same
    shape — the fold is the floor, the spine must not add O(N) work)
    with registry memory <= ~100 bytes/client."""
    arr = ArrivalConfig(mode="diurnal", rate=2000.0, period_s=600.0,
                        amplitude=0.8)
    kw = dict(commits=30, warmup_commits=4, buffer_k=32, row_dim=4096,
              arrival=arr, dropout_prob=0.02, banned_frac=0.01, seed=0)
    small = run_serve_sim(10_000, **kw)
    big = run_serve_sim(1_000_000, **kw)
    assert big["registry_bytes_per_client"] <= 100.0
    assert big["committed_updates"] == 30 * 32
    assert (big["committed_updates_per_sec"]
            >= 0.4 * small["committed_updates_per_sec"]), (small, big)


def test_serve_validation():
    with pytest.raises(ValueError, match="commits"):
        run_serve_sim(1000, commits=2, warmup_commits=2)


def test_serve_arrival_seed_changes_trace():
    """ArrivalConfig.seed is consumed: two serve runs differing only in
    the arrival seed walk different virtual-time traces."""
    kw = dict(commits=4, warmup_commits=1, buffer_k=8, row_dim=64, seed=0)
    a = run_serve_sim(1000, arrival=ArrivalConfig(
        mode="constant", rate=500.0, seed=0), **kw)
    b = run_serve_sim(1000, arrival=ArrivalConfig(
        mode="constant", rate=500.0, seed=1), **kw)
    assert a["virtual_time_s"] != b["virtual_time_s"]


def test_serve_exhausted_trace_names_the_problem(tmp_path):
    p = tmp_path / "short.txt"
    p.write_text("0.1\n0.2\n0.3\n")
    with pytest.raises(ValueError, match="arrival trace exhausted"):
        run_serve_sim(1000, commits=4, warmup_commits=1, buffer_k=8,
                      row_dim=64,
                      arrival=ArrivalConfig(mode="trace",
                                            trace_path=str(p)))


def test_serve_host_sharded_partition_commits_identically():
    """ISSUE 13: the serve loop sharded across two ranks by client-id
    range — each rank owns HALF the population's registry shards,
    samples/folds its own range, and the commit folds the partial
    aggregates upward over the HostChannel (rank-ordered sum).  Both
    ranks must commit the IDENTICAL global mix (committed_digest), and
    each rank's registry holds only its range."""
    import threading

    from fedml_tpu.parallel.multihost import (HostChannel,
                                              MultihostContext,
                                              free_port)
    port = free_port()
    pop = 4096
    reports: dict = {}
    errs: list = []

    def rank(r):
        try:
            ctx = MultihostContext(rank=r, world=2,
                                   coordinator=f"localhost:{port}")
            ch = HostChannel(ctx, timeout_s=60, connect_timeout_s=30)
            try:
                reports[r] = run_serve_sim(
                    pop, commits=4, warmup_commits=1, buffer_k=8,
                    row_dim=64,
                    arrival=ArrivalConfig(mode="constant", rate=500.0,
                                          seed=0),
                    seed=0, partition=(r, 2), channel=ch)
            finally:
                ch.close()
        except Exception as e:          # surfaced below, never hangs
            errs.append((r, e))

    ts = [threading.Thread(target=rank, args=(r,)) for r in (0, 1)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=180)
    assert not errs, errs
    assert set(reports) == {0, 1}
    a, b = reports[0], reports[1]
    assert a["committed_digest"] == b["committed_digest"], (
        "host-sharded serve ranks committed different global mixes")
    assert a["local_population"] == b["local_population"] == pop // 2
    assert a["partition"] == [0, 2] and b["partition"] == [1, 2]
    # the partial aggregates really crossed ranks
    assert a["carry_allreduce_bytes"] > 0
    assert b["carry_allreduce_bytes"] > 0
    # world > 1 without a channel is a loud error
    with pytest.raises(ValueError, match="HostChannel"):
        run_serve_sim(100, commits=2, warmup_commits=1,
                      partition=(0, 2))


def test_serve_elastic_readopts_dead_ranks_range():
    """ISSUE 14: the host-sharded serve loop survives a rank death —
    rank 2 of 3 vanishes mid-run (crash_at_commit closes its channel),
    the survivors' next exchange evicts it (one view change), the
    window where the death lands folds deterministic ZEROS for the
    dead range, and at the next commit barrier the view's new owner
    re-adopts the range as a fresh lane.  The survivors must finish
    every commit with IDENTICAL committed_digest (they fold the same
    allgathered bytes every window), host every range exactly once
    between them, and report the adoption."""
    import threading

    from fedml_tpu.parallel.multihost import (ElasticChannel,
                                              MultihostContext,
                                              free_port)
    port = free_port()
    pop, world = 3072, 3
    reports: dict = {}
    errs: list = []

    def rank(r):
        try:
            ctx = MultihostContext(rank=r, world=world,
                                   coordinator=f"localhost:{port}")
            ch = ElasticChannel(ctx, n_items=world,
                                config_digest="serve-elastic",
                                timeout_s=60, connect_timeout_s=30,
                                hb_interval_s=0.1, hb_timeout_s=1.0)
            try:
                reports[r] = run_serve_sim(
                    pop, commits=8, warmup_commits=1, buffer_k=8,
                    row_dim=64,
                    arrival=ArrivalConfig(mode="constant", rate=500.0,
                                          seed=0),
                    seed=0, partition=(r, world), channel=ch,
                    elastic=True,
                    crash_at_commit=3 if r == 2 else None)
            finally:
                ch.close()
        except Exception as e:          # surfaced below, never hangs
            errs.append((r, e))

    ts = [threading.Thread(target=rank, args=(r,))
          for r in range(world)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=180)
    assert not errs, errs
    assert set(reports) == {0, 1, 2}
    a, b, c = reports[0], reports[1], reports[2]
    assert a["committed_digest"] == b["committed_digest"], (
        "survivors committed different global mixes after the death")
    assert a["commits"] == b["commits"] == 8
    assert c["commits"] == 3 and c["elastic"]["crashed_at_commit"] == 3
    # the dead range was re-adopted, and every range has EXACTLY one
    # host among the survivors (no double-hosting)
    hosted = sorted(a["elastic"]["lanes"] + b["elastic"]["lanes"])
    assert hosted == [0, 1, 2], hosted
    adopted = a["elastic"]["adopted_items"] + b["elastic"]["adopted_items"]
    assert 2 in adopted, f"range 2 never re-adopted: {adopted}"
    assert a["elastic"]["view_changes"] >= 1
    assert a["elastic"]["epoch"] >= 1
    # elastic=True without an ElasticChannel is a loud error
    with pytest.raises(ValueError, match="ElasticChannel"):
        run_serve_sim(100, commits=2, warmup_commits=1,
                      partition=(0, 2), channel=object(), elastic=True)


def test_serve_uniform_sampler_not_low_id_biased():
    """The legacy uniform draw is prefix-stable in k at a fixed round;
    the serve loop must advance the sampler round per DRAW, or every
    refill would re-select in-flight ids and fall back to ascending
    free_ids — concentrating cohorts at low ids."""
    rep = run_serve_sim(
        20_000, commits=8, warmup_commits=1, buffer_k=16, row_dim=64,
        sampler_mode="uniform",
        arrival=ArrivalConfig(mode="constant", rate=1000.0), seed=0)
    assert rep["commits"] == 8
    # with 8*16 = 128 admitted updates over 20k clients a uniform draw
    # almost never reuses a client; the old bug concentrated refills on
    # the lowest free ids (max participation >> 1, few distinct)
    assert rep["distinct_contributors"] >= 100
    assert rep["max_client_participation"] <= 3


# -- scheduler registry integration ------------------------------------------

def test_scheduler_registry_tracks_participation(small_data):
    cfg, data = small_data
    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.models import create_model
    from fedml_tpu.async_ import AsyncFedAvgEngine
    trainer = ClientTrainer(create_model("lr", output_dim=10), lr=cfg.lr)
    eng = AsyncFedAvgEngine(trainer, data, cfg, buffer_k=4,
                            concurrency=4, donate=False)
    eng.run(rounds=3)
    reg = eng.registry
    # 3 commits x 4 admitted results each, all in registry counters
    assert reg.total_participation() == 12
    assert reg.n_clients == data.client_num
    ids = np.arange(reg.n_clients)
    assert reg.participation(ids).sum() == 12
    assert np.all(reg.last_staleness(ids) >= 0.0)
