"""Mesh-engine tests on the 8-device virtual CPU mesh (conftest.py).

The key invariants:
  * MeshFedAvgEngine == single-device FedAvgEngine bit-for-bit-ish (the psum
    aggregation must reproduce the tree weighted mean to float tolerance).
  * The equivalence oracle survives sharding: full-batch E=1 full
    participation == centralized (CI-script-fedavg.sh:41-47).
  * Hierarchical grouping does not change the one-inner-round result
    (CI-script-fedavg.sh:51-59).
  * Gossip reaches consensus-ish accuracy on an easy task.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.algorithms.fedavg import FedAvgEngine
from fedml_tpu.core.trainer import ClientTrainer
from fedml_tpu.data.loaders import load_data
from fedml_tpu.models import create_model
from fedml_tpu.parallel import (MeshFedAvgEngine, MeshFedOptEngine,
                                MeshGossipEngine, MeshHierarchicalEngine,
                                MeshRobustEngine)
from fedml_tpu.parallel.mesh import make_mesh, make_mesh_2d
from fedml_tpu.utils.config import FedConfig

from parallel_case import _mnist_like_cfg, _setup, run_donate_pair


def test_mesh_matches_single_device():
    cfg = _mnist_like_cfg()
    trainer, data = _setup(cfg)
    ref = FedAvgEngine(trainer, data, cfg, donate=False)
    v0 = ref.init_variables()
    v_ref = ref.run(variables=jax.tree.map(jnp.copy, v0), rounds=3)

    mesh = make_mesh(8)
    eng = MeshFedAvgEngine(trainer, data, cfg, mesh=mesh, donate=False)
    v_mesh = eng.run(variables=jax.tree.map(jnp.copy, v0), rounds=3)
    for a, b in zip(jax.tree.leaves(v_ref), jax.tree.leaves(v_mesh)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_mesh_partial_participation_padding():
    # 10 of 16 clients -> cohort padded to 16 with zero-weight repeats
    cfg = _mnist_like_cfg(client_num_per_round=10)
    trainer, data = _setup(cfg)
    ref = FedAvgEngine(trainer, data, cfg, donate=False)
    v0 = ref.init_variables()
    v_ref = ref.run(variables=jax.tree.map(jnp.copy, v0), rounds=2)
    eng = MeshFedAvgEngine(trainer, data, cfg, mesh=make_mesh(8),
                           donate=False)
    v_mesh = eng.run(variables=jax.tree.map(jnp.copy, v0), rounds=2)
    for a, b in zip(jax.tree.leaves(v_ref), jax.tree.leaves(v_mesh)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_mesh_fedopt_runs_and_learns():
    cfg = _mnist_like_cfg(server_optimizer="adam", server_lr=0.05,
                          comm_round=6)
    trainer, data = _setup(cfg)
    eng = MeshFedOptEngine(trainer, data, cfg, mesh=make_mesh(8))
    v = eng.run(rounds=6)
    acc = eng.evaluate(v)["train_acc"]
    assert acc > 0.5, acc


def test_mesh_robust_clipping_runs():
    cfg = _mnist_like_cfg(norm_bound=0.5, stddev=1e-3, comm_round=2)
    trainer, data = _setup(cfg)
    eng = MeshRobustEngine(trainer, data, cfg, mesh=make_mesh(8))
    v = eng.run(rounds=2)
    assert all(np.isfinite(np.asarray(x)).all() for x in jax.tree.leaves(v))


def test_hierarchical_equals_flat_for_one_inner_round():
    # oracle: one inner round, full participation => grouping-invariant
    # == plain FedAvg (CI-script-fedavg.sh:51-59 generalization). The
    # hierarchical engine caps the per-silo cohort at clients_per_silo (8),
    # which with client_num_per_round=16 means full participation both ways.
    cfg = _mnist_like_cfg(client_num_per_round=16)
    trainer, data = _setup(cfg)
    flat = FedAvgEngine(trainer, data, cfg, donate=False)
    v0 = flat.init_variables()
    v_flat = flat.run(variables=jax.tree.map(jnp.copy, v0), rounds=2)

    mesh = make_mesh_2d(n_silos=2, per_silo=4)
    eng = MeshHierarchicalEngine(trainer, data, cfg, mesh=mesh,
                                 group_comm_round=1, donate=False)
    v_h = eng.run(variables=jax.tree.map(jnp.copy, v0), rounds=2)
    for a, b in zip(jax.tree.leaves(v_flat), jax.tree.leaves(v_h)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)


def test_hierarchical_multi_inner_rounds_learn():
    cfg = _mnist_like_cfg(client_num_per_round=8, comm_round=3)
    trainer, data = _setup(cfg)
    eng = MeshHierarchicalEngine(trainer, data, cfg,
                                 mesh=make_mesh_2d(n_silos=4, per_silo=2),
                                 group_comm_round=3)
    v = eng.run(rounds=3)
    assert eng.evaluate(v)["train_acc"] > 0.5


def test_gossip_learns():
    cfg = _mnist_like_cfg(comm_round=6, lr=0.2)
    trainer, data = _setup(cfg)
    eng = MeshGossipEngine(trainer, data, cfg, mesh=make_mesh(8))
    wv = eng.run(rounds=6)
    acc = eng.evaluate(eng.consensus_variables(wv))["train_acc"]
    assert acc > 0.5, acc


def test_gossip_flat_stack_image_matches_unflattened():
    """The gossip stack stores image data FLAT by default (engine.py
    flat_stack; restored per worker inside the shard body) — results
    must be identical to the unflattened stack (a reshape is exact)."""
    cfg = _mnist_like_cfg(dataset="femnist", model="cnn",
                          client_num_in_total=8, client_num_per_round=8,
                          comm_round=2, batch_size=4)
    data = load_data("femnist", client_num_in_total=8, batch_size=4,
                     synthetic_scale=0.001, max_batches_per_client=1,
                     seed=0)
    model = create_model("cnn", output_dim=data.class_num)
    trainer = ClientTrainer(model, lr=0.1)
    flat = MeshGossipEngine(trainer, data, cfg, mesh=make_mesh(8),
                            donate=False)
    assert flat.flat_stack
    wv_f = flat.run(rounds=2)
    assert flat._x_image_shape == (28, 28, 1)
    plain = MeshGossipEngine(trainer, data, cfg, mesh=make_mesh(8),
                             donate=False, flat_stack=False)
    wv_p = plain.run(rounds=2)
    for a, b in zip(jax.tree.leaves(wv_f), jax.tree.leaves(wv_p)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
    # ADVICE r4: evaluate_local(split="train") reuses the resident FLAT
    # stack — the gossip _local_eval_transform override must restore
    # images in-program or the conv model crashes on [B, bs, h*w*c] x.
    ev_f = flat.evaluate_local(flat.consensus_variables(wv_f), "train")
    ev_p = plain.evaluate_local(plain.consensus_variables(wv_p), "train")
    assert ev_f["local_train_acc"] == pytest.approx(
        ev_p["local_train_acc"], abs=1e-6)


def test_prime_cohort_chunk_padding():
    """A 13-client cohort on a 1-shard mesh forces the in-program
    zero-weight chunk padding (13 -> 16 lanes at cap 8); results must match
    the unchunked single-device engine exactly."""
    cfg = _mnist_like_cfg(client_num_in_total=13, client_num_per_round=13,
                          comm_round=2)
    trainer, data = _setup(cfg)
    ref = FedAvgEngine(trainer, data, cfg, donate=False)
    v0 = ref.init_variables()
    v_ref = ref.run(variables=jax.tree.map(jnp.copy, v0), rounds=2)
    eng = MeshFedAvgEngine(trainer, data, cfg, mesh=make_mesh(1),
                           donate=False)
    v_mesh = eng.run(variables=jax.tree.map(jnp.copy, v0), rounds=2)
    for a, b in zip(jax.tree.leaves(v_ref), jax.tree.leaves(v_mesh)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_large_leaves_accumulate_outside_the_packed_carry(monkeypatch):
    """A leaf of at least BIG_CARRY_LEAF elements keeps a Σw·v accumulator
    of its own shape in the chunk scan, the others share the packed
    vector: each element sees the same adds in the same order wherever
    the line is drawn (the lr model's 7,840-element kernel on one side,
    its 10 biases on the other; both packed; both on their own), so the
    rounds agree to the last bits — XLA contracts the multiply-add of a
    leaf on its own differently from the concatenated one (1e-6 of a
    value here); a model with no large leaf compiles to the program it
    had (tests/test_hlo_copy_audit.py pins the census)."""
    from fedml_tpu.parallel import engine as engine_mod
    cfg = _mnist_like_cfg(client_num_per_round=6, comm_round=2)
    trainer, data = _setup(cfg)
    got = []
    for line in (engine_mod.BIG_CARRY_LEAF, 1000, 1):
        monkeypatch.setattr(engine_mod, "BIG_CARRY_LEAF", line)
        eng = MeshFedAvgEngine(trainer, data, cfg, mesh=make_mesh(2),
                               donate=False, chunk=2)
        got.append(eng.run(variables=eng.init_variables(), rounds=2))
    for other in got[1:]:
        for a, b in zip(jax.tree.leaves(got[0]), jax.tree.leaves(other)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-8)


def test_chunk_size_invariance():
    """The chunked cohort scan (perf: bounds live model replicas) must not
    change results vs one full-width chunk."""
    cfg = _mnist_like_cfg(comm_round=2)
    trainer, data = _setup(cfg)
    wide = MeshFedAvgEngine(trainer, data, cfg, mesh=make_mesh(8),
                            donate=False, chunk=16)
    v0 = wide.init_variables()
    v_w = wide.run(variables=jax.tree.map(jnp.copy, v0), rounds=2)
    narrow = MeshFedAvgEngine(trainer, data, cfg, mesh=make_mesh(8),
                              donate=False, chunk=1)
    v_n = narrow.run(variables=jax.tree.map(jnp.copy, v0), rounds=2)
    for a, b in zip(jax.tree.leaves(v_w), jax.tree.leaves(v_n)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_mesh_fednova_matches_single_device():
    """MeshFedNovaEngine's psum'd normalized averaging must reproduce the
    single-device FedNovaEngine (same d = Σ p(g−w)/τ, w_new = g − τ_eff·d)."""
    from fedml_tpu.algorithms import FedNovaEngine
    from fedml_tpu.parallel import MeshFedNovaEngine
    cfg = _mnist_like_cfg(comm_round=3, epochs=2)
    trainer, data = _setup(cfg)
    ref = FedNovaEngine(trainer, data, cfg, donate=False)
    v0 = ref.init_variables()
    v_ref = ref.run(variables=jax.tree.map(jnp.copy, v0), rounds=3)
    eng = MeshFedNovaEngine(trainer, data, cfg, mesh=make_mesh(8),
                            donate=False)
    v_mesh = eng.run(variables=jax.tree.map(jnp.copy, v0), rounds=3)
    for a, b in zip(jax.tree.leaves(v_ref), jax.tree.leaves(v_mesh)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_mesh_fednova_partial_participation():
    """Ragged cohorts: padded zero-weight lanes contribute nothing to d,
    τ_eff or the loss."""
    from fedml_tpu.algorithms import FedNovaEngine
    from fedml_tpu.parallel import MeshFedNovaEngine
    cfg = _mnist_like_cfg(client_num_per_round=10, comm_round=2)
    trainer, data = _setup(cfg)
    ref = FedNovaEngine(trainer, data, cfg, donate=False)
    v0 = ref.init_variables()
    v_ref = ref.run(variables=jax.tree.map(jnp.copy, v0), rounds=2)
    eng = MeshFedNovaEngine(trainer, data, cfg, mesh=make_mesh(8),
                            donate=False)
    v_mesh = eng.run(variables=jax.tree.map(jnp.copy, v0), rounds=2)
    for a, b in zip(jax.tree.leaves(v_ref), jax.tree.leaves(v_mesh)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_mesh_fednova_matches_single_device_with_stats():
    """Same oracle but with a BatchNorm model: the stats collections take
    the SAMPLE-weighted mean on both paths (a plain mean would also count
    zero-weight padded lanes)."""
    import flax.linen as nn
    from fedml_tpu.algorithms import FedNovaEngine
    from fedml_tpu.data.federated import (FederatedData, build_client_shards,
                                          build_eval_shard)
    from fedml_tpu.parallel import MeshFedNovaEngine

    class TinyBN(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            x = nn.Conv(4, (3, 3))(x)
            x = nn.BatchNorm(use_running_average=not train)(x)
            x = nn.relu(x).mean(axis=(1, 2))
            return nn.Dense(4)(x)

    rs = np.random.RandomState(0)
    C, hw = 6, 8
    sizes = [8, 12, 4, 8, 8, 12]          # heterogeneous client sizes
    n = sum(sizes)
    x = rs.rand(n, hw, hw, 3).astype(np.float32)
    y = rs.randint(0, 4, n).astype(np.int64)
    off, idx = 0, {}
    for i, s in enumerate(sizes):
        idx[i] = np.arange(off, off + s); off += s
    data = FederatedData(
        train_data_num=n, test_data_num=n,
        train_global=build_eval_shard(x, y, 4),
        test_global=build_eval_shard(x, y, 4),
        client_shards=build_client_shards(x, y, idx, 4),
        client_num_samples=np.asarray(sizes, np.float32),
        test_client_shards=None, class_num=4, synthetic=True)
    cfg = FedConfig(client_num_in_total=C, client_num_per_round=5,
                    comm_round=2, epochs=1, batch_size=4, lr=0.05,
                    frequency_of_the_test=100)
    trainer = ClientTrainer(TinyBN(), lr=cfg.lr)
    ref = FedNovaEngine(trainer, data, cfg, donate=False)
    v0 = ref.init_variables()
    v_ref = ref.run(variables=jax.tree.map(jnp.copy, v0), rounds=2)
    eng = MeshFedNovaEngine(trainer, data, cfg, mesh=make_mesh(8),
                            donate=False)
    v_mesh = eng.run(variables=jax.tree.map(jnp.copy, v0), rounds=2)
    assert "batch_stats" in v_ref
    for a, b in zip(jax.tree.leaves(v_ref), jax.tree.leaves(v_mesh)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("opt,kw", [("adam", {}), ("sgd", {"momentum": 0.9})])
def test_mesh_stateful_client_optimizer(opt, kw):
    """Regression: STATEFUL client optimizers (adam moments, momentum
    trace, schedule counts) under the mesh chunked loop used to hit a
    scan-carry vma mismatch — the empty-batch guard varies opt_state
    after step 1 while the fresh init was replicated-typed."""
    cfg = _mnist_like_cfg(comm_round=2, client_num_per_round=10)
    data = load_data("mnist", client_num_in_total=16, batch_size=16,
                     synthetic_scale=0.02, seed=0)
    trainer = ClientTrainer(create_model("lr", data.class_num), lr=0.05,
                            optimizer=opt, **kw)
    ref = FedAvgEngine(trainer, data, cfg, donate=False)
    v0 = ref.init_variables()
    v_ref = ref.run(variables=jax.tree.map(jnp.copy, v0), rounds=2)
    eng = MeshFedAvgEngine(trainer, data, cfg, mesh=make_mesh(8),
                           donate=False)
    v_mesh = eng.run(variables=jax.tree.map(jnp.copy, v0), rounds=2)
    for a, b in zip(jax.tree.leaves(v_ref), jax.tree.leaves(v_mesh)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-5)


def test_local_dtype_bf16_close_to_f32():
    """bf16 local masters (the bench's measured v5e win, PERF.md): globals
    stay f32, results stay close to the f32 local path, and the model still
    learns."""
    cfg = _mnist_like_cfg(comm_round=3)
    trainer, data = _setup(cfg)
    ref = MeshFedAvgEngine(trainer, data, cfg, mesh=make_mesh(8),
                           donate=False)
    v0 = ref.init_variables()
    v_f32 = ref.run(variables=jax.tree.map(jnp.copy, v0), rounds=3)
    eng = MeshFedAvgEngine(trainer, data, cfg, mesh=make_mesh(8),
                           donate=False, local_dtype=jnp.bfloat16)
    v_bf16 = eng.run(variables=jax.tree.map(jnp.copy, v0), rounds=3)
    for a, b in zip(jax.tree.leaves(v_f32), jax.tree.leaves(v_bf16)):
        assert a.dtype == b.dtype       # globals keep the f32 grid
        # bf16 has ~3 decimal digits; after 3 rounds the trees must agree
        # to bf16 resolution, not diverge
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0.05, atol=0.02)


def test_stack_dtype_bf16_close_to_f32():
    """bf16 cohort storage (the >512-clients-per-chip HBM lever, PERF.md):
    only the input leaf is cast — y stays integral, mask stays f32 (its
    0/1 sums feed aggregation weights and lose exactness past 256 in
    bf16) — and training stays close to the f32-stack run.  Covers both
    the resident and streaming upload paths."""
    cfg = _mnist_like_cfg(comm_round=3)
    trainer, data = _setup(cfg)
    ref = MeshFedAvgEngine(trainer, data, cfg, mesh=make_mesh(8),
                           donate=False)
    v0 = ref.init_variables()
    v_f32 = ref.run(variables=jax.tree.map(jnp.copy, v0), rounds=3)
    for streaming in (False, True):
        eng = MeshFedAvgEngine(trainer, data, cfg, mesh=make_mesh(8),
                               donate=False, streaming=streaming,
                               stack_dtype=jnp.bfloat16)
        if streaming:
            cohort, _w = eng.stream_cohort(0)
            assert cohort["x"].dtype == jnp.bfloat16
            assert cohort["mask"].dtype == jnp.float32
        else:
            stack, _w = eng._device_stack()
            assert stack["x"].dtype == jnp.bfloat16
            assert stack["mask"].dtype == jnp.float32
        v_bf = eng.run(variables=jax.tree.map(jnp.copy, v0), rounds=3)
        for a, b in zip(jax.tree.leaves(v_f32), jax.tree.leaves(v_bf)):
            assert a.dtype == b.dtype       # globals keep the f32 grid
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=0.05, atol=0.02)

    # INTEGER inputs (token ids on text datasets) must never be cast:
    # bf16 is exact only to 256, so casting ids silently remaps vocab
    int_data = _setup(cfg)[1]
    int_data.client_shards["x"] = np.asarray(
        (np.abs(int_data.client_shards["x"][..., :1]) * 1000),
        np.int32)
    eng = MeshFedAvgEngine(trainer, int_data, cfg, mesh=make_mesh(8),
                           donate=False, streaming=True,
                           stack_dtype=jnp.bfloat16)
    cohort, _w = eng.stream_cohort(0)
    assert cohort["x"].dtype == jnp.int32


def test_stack_dtype_uint8_close_to_f32():
    """uint8 cohort storage (the transfer-compression tier below bf16,
    PERF.md 'Transfer compression'): the input leaf is quantized ONCE on
    host to uint8 + an affine DequantSpec, crosses H2D at 1/4 the f32
    bytes, and the dequantize is fused into the jitted round program as
    the first op of the chunk scan — training stays close to the
    f32-stack run on both the resident and streaming paths.  The data
    object itself must stay untouched (sibling engines share it), and
    integer token-id inputs must never be quantized."""
    cfg = _mnist_like_cfg(comm_round=3)
    trainer, data = _setup(cfg)
    ref = MeshFedAvgEngine(trainer, data, cfg, mesh=make_mesh(8),
                           donate=False)
    v0 = ref.init_variables()
    v_f32 = ref.run(variables=jax.tree.map(jnp.copy, v0), rounds=3)
    for streaming in (False, True):
        eng = MeshFedAvgEngine(trainer, data, cfg, mesh=make_mesh(8),
                               donate=False, streaming=streaming,
                               stack_dtype=jnp.uint8)
        assert eng._x_dequant is not None
        if streaming:
            cohort, _w = eng.stream_cohort(0)
        else:
            cohort, _w = eng._device_stack()
        assert cohort["x"].dtype == jnp.uint8
        assert cohort["mask"].dtype == jnp.float32
        # the shared data object keeps its float stack — quantization
        # lives in the engine's private view
        assert np.issubdtype(np.asarray(data.client_shards["x"]).dtype,
                             np.floating)
        v_u8 = eng.run(variables=jax.tree.map(jnp.copy, v0), rounds=3)
        for a, b in zip(jax.tree.leaves(v_f32), jax.tree.leaves(v_u8)):
            assert a.dtype == b.dtype       # globals keep the f32 grid
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=0.05, atol=0.02)

    # loader-quantized stacks (load_data store_uint8) carry their spec
    # on the data object and pass through without a second quantization
    from fedml_tpu.data.loaders import load_data
    u8_data = load_data(cfg.dataset,
                        client_num_in_total=cfg.client_num_in_total,
                        batch_size=cfg.batch_size, synthetic_scale=0.02,
                        seed=cfg.seed, store_uint8=True)
    assert u8_data.client_shards["x"].dtype == np.uint8
    assert u8_data.x_dequant is not None
    # eval shards stay float (they never ride the cohort path)
    assert np.issubdtype(u8_data.test_global["x"].dtype, np.floating)
    eng = MeshFedAvgEngine(trainer, u8_data, cfg, mesh=make_mesh(8),
                           donate=False, stack_dtype=jnp.uint8)
    assert eng._host_shards() is u8_data.client_shards
    v_ld = eng.run(variables=jax.tree.map(jnp.copy, v0), rounds=3)
    for a, b in zip(jax.tree.leaves(v_f32), jax.tree.leaves(v_ld)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0.05, atol=0.02)

    # INTEGER inputs: uint8 quantization is refused, not applied
    int_data = _setup(cfg)[1]
    int_data.client_shards["x"] = np.asarray(
        (np.abs(int_data.client_shards["x"][..., :1]) * 1000), np.int32)
    eng = MeshFedAvgEngine(trainer, int_data, cfg, mesh=make_mesh(8),
                           donate=False, streaming=True,
                           stack_dtype=jnp.uint8)
    assert eng._x_dequant is None
    cohort, _w = eng.stream_cohort(0)
    assert cohort["x"].dtype == jnp.int32


@pytest.mark.parametrize("defense", ["median", "krum", "trimmed_mean",
                                     "multi_krum"])
def test_mesh_orderstat_defense_matches_single_device(defense):
    """krum/multi-krum/median/trimmed-mean on the mesh (flatten +
    all_gather + order statistic) must reproduce the single-device
    FedAvgRobustEngine."""
    from fedml_tpu.algorithms.fedavg_robust import FedAvgRobustEngine
    cfg = _mnist_like_cfg(comm_round=2)
    trainer, data = _setup(cfg)
    ref = FedAvgRobustEngine(trainer, data, cfg, defense=defense,
                             n_byzantine=1, donate=False)
    v0 = ref.init_variables()
    v_ref = ref.run(variables=jax.tree.map(jnp.copy, v0), rounds=2)
    eng = MeshRobustEngine(trainer, data, cfg, defense=defense,
                           n_byzantine=1, mesh=make_mesh(8), donate=False)
    v_mesh = eng.run(variables=jax.tree.map(jnp.copy, v0), rounds=2)
    for a, b in zip(jax.tree.leaves(v_ref), jax.tree.leaves(v_mesh)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_mesh_orderstat_defense_honors_prox_term():
    """The order-stat shard body shares the FedAvg chunked loop, so a
    prox_mu trainer applies the proximal term identically to the
    single-device robust engine."""
    from fedml_tpu.algorithms.fedavg_robust import FedAvgRobustEngine
    cfg = _mnist_like_cfg(comm_round=2)
    trainer, data = _setup(cfg, prox_mu=0.5)
    ref = FedAvgRobustEngine(trainer, data, cfg, defense="median",
                             donate=False)
    v0 = ref.init_variables()
    v_ref = ref.run(variables=jax.tree.map(jnp.copy, v0), rounds=2)
    eng = MeshRobustEngine(trainer, data, cfg, defense="median",
                           mesh=make_mesh(8), donate=False)
    v_mesh = eng.run(variables=jax.tree.map(jnp.copy, v0), rounds=2)
    for a, b in zip(jax.tree.leaves(v_ref), jax.tree.leaves(v_mesh)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_mesh_orderstat_defense_rejects_ragged_cohort():
    cfg = _mnist_like_cfg(client_num_per_round=10)   # 10 % 8 != 0
    trainer, data = _setup(cfg)
    with pytest.raises(ValueError, match="divide evenly"):
        MeshRobustEngine(trainer, data, cfg, defense="median",
                         mesh=make_mesh(8))


# NOTE: run_scanned (whole-block in-program rounds) was cut after the chip
# measurement showed the jitted per-round loop 9x faster even at ms-scale
# rounds (PERF.md round-3 table, exp_SCAN); its equivalence tests went with
# it.  sample_jax, which it exercised, keeps a direct unit test in
# test_core.py.


def test_donate_bitwise_fedavg_resident():
    cfg = _mnist_like_cfg(comm_round=2)
    trainer, data = _setup(cfg)
    run_donate_pair(lambda donate: MeshFedAvgEngine(
        trainer, data, cfg, mesh=make_mesh(8), donate=donate))


def test_donate_bitwise_robust_flats():
    """The order-stat shard body (emit_flat_params chunked loop + the
    flats scatter/psum) under donation: bitwise-identical to the
    non-donating compile."""
    cfg = _mnist_like_cfg(comm_round=2)
    trainer, data = _setup(cfg)
    run_donate_pair(lambda donate: MeshRobustEngine(
        trainer, data, cfg, defense="median", n_byzantine=1,
        mesh=make_mesh(8), donate=donate))


def test_multihost_mesh_helpers():
    """Single-process: helpers still build valid meshes over local devices
    (multi-host wiring is a no-op here)."""
    from fedml_tpu.parallel.multihost import (init_multihost,
                                              make_global_mesh,
                                              make_hierarchical_host_mesh)
    init_multihost()          # must be safe on a single host
    mesh = make_global_mesh()
    assert mesh.devices.size == len(jax.devices())
    h = make_hierarchical_host_mesh(silos=2)
    assert h.shape["silo"] == 2
    assert h.shape["silo"] * h.shape["clients"] == len(jax.devices())
