"""Mesh-sharding overhead / scaling proxy on the virtual CPU mesh.

A structural proxy that needs no chip (ICI scaling itself is measured
on the four-chip host: `chip_smoke.py --chips 4`).  Two proxies:

1. OVERHEAD (fixed total cohort, 1/2/4/8 shards): the host has ONE core, so
   ideal behavior is FLAT time — any growth is sharding overhead (psum
   lowering, cross-shard gather, program partitioning).
2. WEAK (per-shard cohort fixed, shards grow): on a 1-core host the ideal
   is LINEAR time growth; the interesting output is the deviation factor
   (overhead of the n-shard program beyond n x the 1-shard work).

Writes SCALING.md at the repo root.

Usage: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
       python tools/mesh_scaling.py
"""
from __future__ import annotations

import os
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

import jax
import numpy as np

jax.config.update("jax_platforms", "cpu")

from fedml_tpu.core.trainer import ClientTrainer
from fedml_tpu.data.loaders import load_data
from fedml_tpu.models import create_model
from fedml_tpu.parallel import MeshFedAvgEngine
from fedml_tpu.parallel.mesh import make_mesh
from fedml_tpu.utils import compile_cache
from fedml_tpu.utils.config import FedConfig


def time_round(n_shards: int, n_clients: int, iters: int = 5) -> float:
    cfg = FedConfig(model="lr", dataset="mnist",
                    client_num_in_total=n_clients,
                    client_num_per_round=n_clients, epochs=1, batch_size=8,
                    lr=0.1, frequency_of_the_test=10_000)
    data = load_data("mnist", client_num_in_total=n_clients, batch_size=8,
                     synthetic_scale=0.01, seed=0)
    trainer = ClientTrainer(create_model("lr", output_dim=10), lr=0.1)
    eng = MeshFedAvgEngine(trainer, data, cfg, mesh=make_mesh(n_shards),
                           donate=False)
    v = eng.init_variables()
    v = eng._prepare_variables(v)
    s = eng.server_init(v)
    args = eng._round_args(0)
    rng = jax.random.PRNGKey(0)
    out = eng.round_fn(v, s, *args, rng)          # compile + warm
    jax.block_until_ready(out[0])
    t0 = time.perf_counter()
    for _ in range(iters):
        out = eng.round_fn(v, s, *args, rng)
    jax.block_until_ready(out[0])
    return (time.perf_counter() - t0) / iters


def time_round_batch(n_c: int, n_b: int, n_clients: int = 8,
                     iters: int = 5) -> float:
    """One round on a clients×batch mesh (per-client sample parallelism):
    fixed cohort and batch size, the per-step batch split n_b ways.  On
    the 1-core host total work is fixed ⇒ flat is ideal; growth is the
    per-step psum + partitioning overhead of the batch axis."""
    from fedml_tpu.parallel.mesh import make_mesh_batch
    cfg = FedConfig(model="cnn", dataset="femnist",
                    client_num_in_total=n_clients,
                    client_num_per_round=n_clients, epochs=1, batch_size=16,
                    lr=0.1, frequency_of_the_test=10_000)
    data = load_data("femnist", client_num_in_total=n_clients, batch_size=16,
                     synthetic_scale=0.01, seed=0)
    trainer = ClientTrainer(create_model("cnn", output_dim=data.class_num),
                            lr=0.1)
    eng = MeshFedAvgEngine(trainer, data, cfg,
                           mesh=make_mesh_batch(n_c, n_b), donate=False)
    v = eng.init_variables()
    v = eng._prepare_variables(v)
    s = eng.server_init(v)
    args = eng._round_args(0)
    rng = jax.random.PRNGKey(0)
    out = eng.round_fn(v, s, *args, rng)          # compile + warm
    jax.block_until_ready(out[0])
    t0 = time.perf_counter()
    for _ in range(iters):
        out = eng.round_fn(v, s, *args, rng)
    jax.block_until_ready(out[0])
    return (time.perf_counter() - t0) / iters


def time_gkt_server(n_shards: int, iters: int = 3) -> float:
    """One GKT server distillation epoch over fixed client uploads
    (8 clients × bs 256 — the reference's own DataParallel scaling row
    runs the GKT server at bs 256, GKTServerTrainer.py:19-24), the step
    batch axis sharded over `n_shards`.  Per-step compute must dominate
    the per-step collective for the proxy to say anything: at toy sizes
    the table measures only GSPMD overhead."""
    import flax.linen as nn

    from fedml_tpu.algorithms.fedgkt import MeshFedGKTEngine

    class TC(nn.Module):
        @nn.compact
        def __call__(self, x):
            h = nn.relu(nn.Dense(64)(x.reshape((x.shape[0], -1))))
            return h, nn.Dense(10)(h)

    class TS(nn.Module):
        @nn.compact
        def __call__(self, f):
            h = f
            for _ in range(4):
                h = nn.relu(nn.Dense(512)(h))
            return nn.Dense(10)(h)

    cfg = FedConfig(client_num_in_total=8, client_num_per_round=8,
                    comm_round=1, epochs=1, batch_size=256, lr=0.05,
                    frequency_of_the_test=100)
    data = load_data("mnist", client_num_in_total=8, batch_size=256,
                     synthetic_scale=0.2, seed=0)
    eng = MeshFedGKTEngine(TC(), TS(), data, cfg,
                           mesh=make_mesh(n_shards))
    cp0, sp = eng.init_params()
    C = eng.data.client_num
    cp_stack = jax.tree.map(
        lambda a: np.broadcast_to(a[None], (C,) + a.shape).copy(), cp0)
    shards, y_srv, m_srv = eng._setup_device_data()
    B, bs = shards["mask"].shape[1:3]
    slog = np.zeros((C, B, bs, eng.data.class_num), np.float32)
    opt = eng.server_tx.init(sp)
    _, feats, logits, _ = eng._client_phase_v(cp_stack, shards, slog)
    out = eng._server_phase_j(sp, opt, feats, logits, y_srv, m_srv)
    jax.block_until_ready(out[0])          # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = eng._server_phase_j(sp, opt, feats, logits, y_srv, m_srv)
    jax.block_until_ready(out[0])
    return (time.perf_counter() - t0) / iters


def main() -> None:
    compile_cache.configure()
    lines = ["# Mesh scaling (8 virtual CPU devices, ONE physical core)",
             "",
             "Structural proxy for ICI scaling — see tools/mesh_scaling.py "
             "header for what flat/linear mean here.", ""]

    lines += ["## Sharding overhead — fixed total cohort (16 clients)", "",
              "| shards | s/round | vs 1 shard |", "|---|---|---|"]
    base = None
    for n in (1, 2, 4, 8):
        dt = time_round(n, 16)
        base = base or dt
        lines.append(f"| {n} | {dt:.3f} | {dt / base:.2f}x |")
        print(lines[-1], flush=True)

    lines += ["", "## Weak scaling — 4 clients per shard", "",
              "| shards | clients | s/round | time vs ideal-linear |",
              "|---|---|---|---|"]
    base = None
    for n in (1, 2, 4, 8):
        dt = time_round(n, 4 * n)
        base = base or dt
        lines.append(f"| {n} | {4 * n} | {dt:.3f} | "
                     f"{dt / (base * n):.2f}x |")
        print(lines[-1], flush=True)

    lines += ["", "## Per-client batch parallelism — 8 clients, "
              "per-step batch split over the batch axis", "",
              "(clients×batch mesh, make_mesh_batch; fixed total work ⇒ "
              "flat is ideal on the 1-core host — growth is the per-step "
              "grad-psum + partitioning overhead)", "",
              "| mesh (c×b) | s/round | vs 8×1 |", "|---|---|---|"]
    base = None
    for n_c, n_b in ((8, 1), (4, 2), (2, 4), (1, 8)):
        dt = time_round_batch(n_c, n_b)
        base = base or dt
        lines.append(f"| {n_c}x{n_b} | {dt:.3f} | {dt / base:.2f}x |")
        print(lines[-1], flush=True)

    lines += ["", "## FedGKT server distillation — fixed uploads, "
              "batch axis sharded", "",
              "(the reference's GKT-server DataParallel analog; fixed "
              "total work ⇒ flat is ideal on the 1-core host — growth "
              "is GSPMD partitioning overhead)", "",
              "| shards | s/epoch | vs 1 shard |", "|---|---|---|"]
    base = None
    for n in (1, 2, 4, 8):
        dt = time_gkt_server(n)
        base = base or dt
        lines.append(f"| {n} | {dt:.3f} | {dt / base:.2f}x |")
        print(lines[-1], flush=True)

    path = os.path.join(os.path.dirname(__file__), "..", "SCALING.md")
    # preserve the manually-recorded reference-scale section (342k
    # stackoverflow / 3,400 femnist results from other tools)
    keep = ""
    if os.path.exists(path):
        old = open(path).read()
        marker = "## Reference-scale"
        if marker in old:
            keep = "\n" + old[old.index(marker):]
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n" + keep)
    print("wrote SCALING.md", flush=True)


if __name__ == "__main__":
    main()
