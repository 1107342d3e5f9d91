"""The shared model/data/config for the multi-host SPMD oracle test:
both the worker processes (multihost_worker.py) and the single-process
oracle (test_multihost_spmd.py) build EXACTLY these engines, so any
digest difference is attributable to the process boundary, not the
workload."""
import numpy as np


def _case_data_cfg(comm_round: int):
    """One data+config construction shared by the flat and hierarchical
    cases — the worker/oracle digest comparison relies on both sides
    building bit-identical workloads, so this must not be duplicated."""
    # imports deferred: workers must set the jax platform before these
    from fedml_tpu.data.federated import (FederatedData, build_client_shards,
                                          build_eval_shard)
    from fedml_tpu.utils.config import FedConfig

    C, spc, bs, dim = 16, 24, 8, 32
    rs = np.random.RandomState(7)
    n = C * spc
    w = rs.randn(dim, 10)
    x = rs.randn(n, dim).astype(np.float32)
    y = np.argmax(x @ w + 0.2 * rs.randn(n, 10), axis=1).astype(np.int64)
    idx = {i: np.arange(i * spc, (i + 1) * spc) for i in range(C)}
    data = FederatedData(
        train_data_num=n, test_data_num=n,
        train_global=build_eval_shard(x, y, n),
        test_global=build_eval_shard(x, y, n),
        client_shards=build_client_shards(x, y, idx, bs),
        client_num_samples=np.full(C, spc, np.float32),
        test_client_shards=None, class_num=10)
    cfg = FedConfig(client_num_in_total=C, client_num_per_round=8,
                    comm_round=comm_round, epochs=1, batch_size=bs, lr=0.1,
                    frequency_of_the_test=100)
    return data, cfg


def build_case():
    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.models import create_model
    from fedml_tpu.parallel import MeshFedAvgEngine
    from fedml_tpu.parallel.mesh import make_mesh

    data, cfg = _case_data_cfg(comm_round=3)
    model = create_model("lr", output_dim=10)
    return MeshFedAvgEngine(ClientTrainer(model, lr=cfg.lr), data, cfg,
                            mesh=make_mesh(8), donate=False)


def build_hier_case(multihost: bool, silos: int = 2):
    """Two-tier hierarchical engine over a (silo × clients) mesh: with
    multihost=True the mesh comes from make_hierarchical_host_mesh (one
    silo per PROCESS — the inner psum stays host-local, only the silo
    tier crosses the process boundary, i.e. the DCN layout); the
    single-process oracle uses the same silos×(8//silos) logical mesh
    over its 8 local devices (device order is process-sorted on both
    sides, so the silo grouping is identical and the digests are
    comparable).  Same data as build_case (shared _case_data_cfg);
    fewer global rounds — each runs group_comm_round inner rounds."""
    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.models import create_model
    from fedml_tpu.parallel import (MeshHierarchicalEngine,
                                    make_hierarchical_host_mesh)
    from fedml_tpu.parallel.mesh import make_mesh_2d

    data, cfg = _case_data_cfg(comm_round=2)
    mesh = (make_hierarchical_host_mesh(silos=silos) if multihost
            else make_mesh_2d(n_silos=silos))
    model = create_model("lr", output_dim=10)
    return MeshHierarchicalEngine(ClientTrainer(model, lr=cfg.lr), data,
                                  cfg, mesh=mesh, group_comm_round=2,
                                  donate=False)


def build_fedopt_streaming_case():
    """Streaming cohort + FedOpt server state across the process
    boundary (VERDICT r3 weak-#6): per-round host-gathered cohort upload
    (stream_cohort's global device_put) AND an adam server-optimizer
    state that persists on device between rounds — the two pieces of
    round state the flat resident case never exercises multi-host."""
    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.models import create_model
    from fedml_tpu.parallel import MeshFedOptEngine
    from fedml_tpu.parallel.mesh import make_mesh

    data, cfg = _case_data_cfg(comm_round=3)
    cfg = type(cfg)(**{**cfg.__dict__, "server_optimizer": "adam",
                       "server_lr": 0.05})
    model = create_model("lr", output_dim=10)
    return MeshFedOptEngine(ClientTrainer(model, lr=cfg.lr), data, cfg,
                            mesh=make_mesh(8), streaming=True,
                            donate=False)


def build_blockstream_case():
    """Block-streamed FedAvg (stream_block) across the process boundary:
    every block upload is a global device_put and the accumulated linear
    sums psum across processes each block step — the round-5 cohort
    machinery on the DCN layout.  Cohort 16 in blocks of 8 = TWO real
    block steps per round, so cross-block accumulation and the
    double-buffer prefetch both cross the boundary."""
    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.models import create_model
    from fedml_tpu.parallel import MeshFedAvgEngine
    from fedml_tpu.parallel.mesh import make_mesh

    data, cfg = _case_data_cfg(comm_round=2)
    cfg = type(cfg)(**{**cfg.__dict__, "client_num_per_round": 16})
    model = create_model("lr", output_dim=10)
    return MeshFedAvgEngine(ClientTrainer(model, lr=cfg.lr), data, cfg,
                            mesh=make_mesh(8), donate=False,
                            stream_block=8)


def build_ckpt_case():
    """Checkpoint/resume across the process boundary (VERDICT r4 #5):
    FedOpt so a NONTRIVIAL server_state (adam moments) must round-trip
    through orbax in the multiprocess cluster — resume correctness shows
    up in the continued rounds' digests, not just the restored
    variables."""
    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.models import create_model
    from fedml_tpu.parallel import MeshFedOptEngine
    from fedml_tpu.parallel.mesh import make_mesh

    data, cfg = _case_data_cfg(comm_round=4)
    cfg = type(cfg)(**{**cfg.__dict__, "server_optimizer": "adam",
                       "server_lr": 0.05})
    model = create_model("lr", output_dim=10)
    return MeshFedOptEngine(ClientTrainer(model, lr=cfg.lr), data, cfg,
                            mesh=make_mesh(8), donate=False)


def digest(variables):
    """Order-stable scalar digest of a params tree (sum of |params|)."""
    import jax

    return float(sum(float(np.abs(np.asarray(a)).sum())
                     for a in jax.tree.leaves(variables)))
