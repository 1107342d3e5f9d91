"""Decompose the north-star bench round cost on the real chip.

Experiments (all CIFAR10-shaped, ResNet-18-GN, bf16 compute, 128 clients,
bs=32, 13 batches/client = 50k samples/round):
  A. full bench round via MeshFedAvgEngine (reference point, = bench.py)
  B. centralized ceiling: SAME total FLOPs with ONE shared-weight model,
     13 steps of effective batch 4096 -- what XLA can do when the conv
     kernels are NOT per-client
  F8/F16/F32. chunked cohort: lax.scan over client chunks of size k,
     vmap(local_train) inside the chunk, weighted-sum accumulated in the
     scan carry -- peak HBM ~ O(k * params) instead of O(128 * params)

Usage: python tools/profile_bench.py [A B F16 ...]
"""
from __future__ import annotations

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.core.trainer import ClientTrainer
from fedml_tpu.models import create_model

N_CLIENTS = 128
BS = 32
SPC = 50_000 // N_CLIENTS
N_BATCHES = (SPC + BS - 1) // BS  # 13


def force(x):
    """Completion barrier: a one-element device->host fetch (it waits
    for the producing program, like block_until_ready, and moves 4
    bytes)."""
    return float(jax.device_get(jax.tree.leaves(x)[0]).ravel()[0])


def timeit(fn, warmup=2, iters=5):
    for _ in range(warmup):
        out = fn()
    force(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    force(out)
    return (time.perf_counter() - t0) / iters


def _bench_child(args: list, who: str) -> str:
    """Run `bench.py <args>` as a child and return its stdout.  A chip
    belongs to one process: the child attaches it, so this parent must
    not have initialised a jax backend — run these experiments in an
    invocation of their own, not after an in-process one."""
    import subprocess

    from jax._src import xla_bridge
    if xla_bridge.backends_are_initialized():
        raise SystemExit(
            f"exp_{who}: this process already initialised a jax backend "
            f"(an in-process experiment ran first) and would hold the "
            f"chip the bench.py child needs; run `profile_bench.py "
            f"{who}` on its own")
    bench = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "..", "bench.py")
    r = subprocess.run([sys.executable, bench] + args, text=True,
                       capture_output=True, timeout=3600)
    sys.stderr.write(r.stderr)
    if r.returncode != 0:
        raise SystemExit(f"exp_{who}: bench.py {' '.join(args)} failed "
                         f"(rc={r.returncode})")
    return r.stdout


def client_batches(rs, n_clients=N_CLIENTS, n_batches=N_BATCHES, bs=BS,
                   valid=None):
    """Synthetic per-client batch stacks.  `valid` marks only the first
    `valid` slots per client real (engine-style ragged padding); padded
    slots still run full conv compute — masks gate the loss/update math,
    not the FLOPs — so timing is slot-driven either way."""
    x = rs.rand(n_clients, n_batches, bs, 32, 32, 3).astype(np.float32)
    y = rs.randint(0, 10, (n_clients, n_batches, bs)).astype(np.int32)
    m = np.ones((n_clients, n_batches * bs), np.float32)
    if valid is not None:
        m[:, valid:] = 0.0
    m = m.reshape(n_clients, n_batches, bs)
    return {"x": jnp.asarray(x), "y": jnp.asarray(y), "mask": jnp.asarray(m)}


def _bench_workload(C: int, batch_unroll: int = 8):
    """The bench workload at a C-client cohort: cfg + synthetic
    CIFAR10-shaped data (SPC samples/client) + bf16-compute trainer with
    the committed batch_unroll — ONE definition so exp_A,
    exp_C512/exp_C1024 and bench.py-shaped runs all measure the same
    per-client work at the same recipe."""
    from fedml_tpu.data.federated import (FederatedData, build_client_shards,
                                          build_eval_shard)
    from fedml_tpu.utils.config import FedConfig

    cfg = FedConfig(model="resnet18_gn", dataset="cifar10",
                    client_num_in_total=C, client_num_per_round=C,
                    epochs=1, batch_size=BS, lr=0.1,
                    frequency_of_the_test=10_000)
    rs = np.random.RandomState(0)
    n = C * SPC
    x = rs.rand(n, 32, 32, 3).astype(np.float32)
    y = rs.randint(0, 10, n).astype(np.int64)
    idx = {i: np.arange(i * SPC, (i + 1) * SPC) for i in range(C)}
    ev = build_eval_shard(x[:BS], y[:BS], BS)
    data = FederatedData(
        train_data_num=n, test_data_num=n, train_global=ev, test_global=ev,
        client_shards=build_client_shards(x, y, idx, BS),
        client_num_samples=np.full(C, SPC, np.float32),
        test_client_shards=None, class_num=10, synthetic=True)
    model = create_model("resnet18_gn", output_dim=10)
    trainer = ClientTrainer(model, lr=0.1, train_dtype=jnp.bfloat16,
                            batch_unroll=batch_unroll)
    return cfg, data, trainer


def exp_A():
    """Full bench round via MeshFedAvgEngine (same code path as bench.py)."""
    from fedml_tpu.parallel import MeshFedAvgEngine
    from fedml_tpu.parallel.mesh import make_mesh

    cfg, data, trainer = _bench_workload(N_CLIENTS)
    engine = MeshFedAvgEngine(trainer, data, cfg, mesh=make_mesh(),
                              donate=False)
    variables = engine._prepare_variables(engine.init_variables())
    server_state = engine.server_init(variables)
    stack, stack_w = engine._device_stack()
    ids, wmask = engine.sample_padded(0)
    rng = jax.random.PRNGKey(0)

    def round_once():
        v, s, m = engine.round_fn(variables, server_state, stack, stack_w,
                                  ids, wmask, rng)
        return m["train_loss"]

    dt = timeit(round_once, warmup=2, iters=3)
    print(f"A full_round: {dt:.3f}s/round", flush=True)


# measured bench-128 standalone round at the committed recipe (chunk 2,
# bf16 masters, batch_unroll=8; the L2U8 row below) — the per-client
# parity denominator for the cohort-scale experiments.  UPDATE when the
# bench recipe moves.  (The SCALING.md C512/C1024 rows were measured at
# the earlier unroll-1 recipe against its 1.851 denominator — ratios are
# recipe-consistent either way since both sides share the trainer.)
BENCH_128_S = 1.806


def _cohort_scale_round(C: int, data_dtype=None):
    """One streaming round at a C-client full-participation cohort with the
    bench recipe (chunk 2, bf16 masters, unroll 8), SAME per-client work
    as bench (13 batches x bs 32): measures cohort-scaling ON CHIP — time
    should be linear in C because the chunked scan keeps HBM O(chunk),
    not O(C).  `data_dtype` stores the cohort x in that dtype on device
    (exp_C1024H)."""
    from fedml_tpu.parallel import MeshFedAvgEngine
    from fedml_tpu.parallel.mesh import make_mesh

    cfg, data, trainer = _bench_workload(C)
    engine = MeshFedAvgEngine(trainer, data, cfg, mesh=make_mesh(), chunk=2,
                              local_dtype=jnp.bfloat16, streaming=True,
                              stack_dtype=data_dtype, donate=False)
    variables = engine._prepare_variables(engine.init_variables())
    server_state = engine.server_init(variables)
    t0 = time.perf_counter()
    cohort, weights = engine.stream_cohort(0)
    # completion barrier: a scalar on-device slice then a scalar fetch —
    # computing the slice needs the uploaded buffer resident, and the
    # device_get moves one element, not the cohort (force(cohort["x"])
    # would download the whole multi-GB array)
    x = cohort["x"]
    force(x[(0,) * x.ndim])
    t_up = time.perf_counter() - t0
    rng = jax.random.PRNGKey(0)

    def round_once():
        v, s, m = engine.round_fn_streaming(variables, server_state, cohort,
                                            weights, rng)
        return m["train_loss"]

    dt = timeit(round_once, warmup=1, iters=4)
    gb = cohort["x"].nbytes / 1e9
    tag = "bf16-stack" if data_dtype is not None else "f32-stack"
    print(f"C{C} cohort-scale ({tag}, 4 timed rounds): {dt:.3f}s/round  "
          f"upload {t_up:.1f}s ({gb:.2f} GB)  vs bench-128 "
          f"{dt / BENCH_128_S * 128 / C:.2f}x/client "
          f"(denominator: standalone L2U8 {BENCH_128_S}s, "
          f"chunk2/bf16-masters/unroll8)", flush=True)


def exp_C512():
    _cohort_scale_round(512)


def exp_C1024():
    _cohort_scale_round(1024)


def exp_C1024H():
    """C1024 with the cohort x stored bf16 on device: compute was
    measured dtype-neutral at 128 clients (H16), but at 1024 the f32
    cohort is a third of HBM — halving it probes whether the 1.32×
    per-client knee is capacity/bandwidth pressure from the data stack."""
    _cohort_scale_round(1024, data_dtype=jnp.bfloat16)


def exp_C2048H():
    """Extend the cohort curve past 1024: 2048 clients with bf16 cohort
    storage (4.9 GB on device; f32 would be 9.8 GB and contend with the
    model chunk) — where does the bf16 stack knee? (VERDICT r3 next-#5)."""
    _cohort_scale_round(2048, data_dtype=jnp.bfloat16)


def _overlap_line(engine) -> str:
    """One-line upload/compute overlap summary from the engine's
    TransferOverlapStats (the PR-1 prefetch pipeline metric)."""
    r = engine.transfer_stats.report()
    return (f"upload {r['upload_wall_s']:.1f}s wait {r['wait_wall_s']:.1f}s "
            f"overlap_fraction {r['overlap_fraction']:.2f}")


def exp_C4096B():
    """4096 bench-shaped clients on ONE chip via block-streamed rounds
    (stream_block): the 10.5 GB bf16 cohort can never be device-resident
    (HBM 15.75 GB minus working set), so the round streams 512-client
    blocks (2 live blocks ≈ 2.7 GB device data) with sums accumulating
    on device.  One timed round — an existence proof of the unbounded
    cohort axis.  The one recorded run (builder session on one v5e,
    2026-07/08, older than PR 1) was upload-bound at ~17 MB/s; the H2D
    rate of the current machine is not measured (SCALING.md) — the
    printed overlap_fraction says how much of the upload wall the
    prefetch pipeline hid behind compute."""
    import jax
    from fedml_tpu.parallel import MeshFedAvgEngine
    from fedml_tpu.parallel.mesh import make_mesh

    C, BLOCK = 4096, 512
    cfg, data, trainer = _bench_workload(C)
    engine = MeshFedAvgEngine(trainer, data, cfg, mesh=make_mesh(), chunk=2,
                              local_dtype=jnp.bfloat16,
                              stack_dtype=jnp.bfloat16, stream_block=BLOCK,
                              donate=False)
    variables = engine._prepare_variables(engine.init_variables())
    server_state = engine.server_init(variables)
    t0 = time.perf_counter()
    variables, server_state, m = engine.round_fn(
        variables, server_state, 0, jax.random.PRNGKey(0))
    loss = float(m["train_loss"])
    dt = time.perf_counter() - t0
    gb = C * N_BATCHES * BS * 32 * 32 * 3 * 2 / 1e9   # padded slots cross
    print(f"C4096B block-stream({BLOCK}/block): one full round over "
          f"{C} clients ({gb:.1f} GB bf16 crossed H2D) in {dt:.1f}s  "
          f"{_overlap_line(engine)}  train_loss {loss:.4f}", flush=True)


def exp_PF512():
    """Prefetch pipeline A/B (the PR-1 tentpole acceptance): the SAME
    512-client block-streamed round (block 64, bf16 stack, bench
    recipe) with the background double-buffered uploader vs the
    --no_prefetch synchronous path.  The pipelined round must be no
    slower, and overlap_fraction reports how much of the upload wall
    hid behind compute (PERF.md §"Prefetch pipeline" records the
    measurement recipe)."""
    import jax
    from fedml_tpu.parallel import MeshFedAvgEngine
    from fedml_tpu.parallel.mesh import make_mesh

    C, BLOCK, ROUNDS = 512, 64, 2
    for prefetch in (False, True):
        cfg, data, trainer = _bench_workload(C)
        engine = MeshFedAvgEngine(trainer, data, cfg, mesh=make_mesh(),
                                  chunk=2, local_dtype=jnp.bfloat16,
                                  stack_dtype=jnp.bfloat16,
                                  stream_block=BLOCK, donate=False,
                                  prefetch=prefetch)
        variables = engine._prepare_variables(engine.init_variables())
        server_state = engine.server_init(variables)
        rng = jax.random.PRNGKey(0)
        engine.round_fn(variables, server_state, 0, rng)   # compile
        engine.transfer_stats.reset()
        t0 = time.perf_counter()
        for r in range(ROUNDS):
            v, s, m = engine.round_fn(variables, server_state, r, rng)
        loss = float(m["train_loss"])                      # sync barrier
        dt = (time.perf_counter() - t0) / ROUNDS
        tag = "prefetch" if prefetch else "no_prefetch"
        print(f"PF512 {tag} block-stream({BLOCK}/block): {dt:.3f}s/round  "
              f"{_overlap_line(engine)}  loss {loss:.4f}", flush=True)


def exp_SD512():
    """Stack-dtype A/B (the transfer-compression tentpole acceptance):
    the SAME 512-client block-streamed round (block 64, bench recipe)
    with f32 vs bf16 vs uint8 cohort storage.  uint8 should halve the
    H2D bytes again vs bf16 (4x vs f32 on the x leaf; the engine's
    byte counter reports the exact payload); where the round is
    transfer-bound the wall should track bytes, otherwise the ratio
    prices in as cohort-per-chip headroom (PERF.md).  Not measured on
    the current machine."""
    import jax
    from fedml_tpu.parallel import MeshFedAvgEngine
    from fedml_tpu.parallel.mesh import make_mesh

    C, BLOCK, ROUNDS = 512, 64, 2
    for sd, tag in ((None, "f32"), (jnp.bfloat16, "bf16"),
                    (jnp.uint8, "u8")):
        cfg, data, trainer = _bench_workload(C)
        engine = MeshFedAvgEngine(trainer, data, cfg, mesh=make_mesh(),
                                  chunk=2, local_dtype=jnp.bfloat16,
                                  stack_dtype=sd, stream_block=BLOCK,
                                  donate=False)
        variables = engine._prepare_variables(engine.init_variables())
        server_state = engine.server_init(variables)
        rng = jax.random.PRNGKey(0)
        engine.round_fn(variables, server_state, 0, rng)   # compile
        engine.transfer_stats.reset()
        t0 = time.perf_counter()
        for r in range(ROUNDS):
            v, s, m = engine.round_fn(variables, server_state, r, rng)
        loss = float(m["train_loss"])                      # sync barrier
        dt = (time.perf_counter() - t0) / ROUNDS
        gb = engine.transfer_stats.h2d_bytes / ROUNDS / 1e9
        print(f"SD512 {tag} block-stream({BLOCK}/block): {dt:.3f}s/round  "
              f"{gb:.3f} GB/round H2D  {_overlap_line(engine)}  "
              f"loss {loss:.4f}", flush=True)


def exp_DN128():
    """Donation/carry A/B (ISSUE 4 tentpole; VERDICT r5 next-#2): the
    bench's 128-client resident round (chunk 2, bf16 masters, unroll 8)
    compiled donate-OFF vs donate-ON, with the restructured flat chunk
    carry in both — the round-2b chip trace priced scan-carry/donation
    copies at ~0.13 s/round (7% of leaf time), and the static HLO audit
    (tools/hlo_copy_audit.py) shows the flat carry removing the donated-
    kernel staging copies; this prices the remaining gap in wall-clock.
    Results are bitwise donate-independent (pinned in
    tests/test_parallel.py::test_donate_bitwise_fedavg_resident)."""
    import jax
    from fedml_tpu.parallel import MeshFedAvgEngine
    from fedml_tpu.parallel.mesh import make_mesh

    ITERS = 5
    for donate in (False, True):
        cfg, data, trainer = _bench_workload(128)
        engine = MeshFedAvgEngine(trainer, data, cfg, mesh=make_mesh(),
                                  chunk=2, local_dtype=jnp.bfloat16,
                                  donate=donate)
        v = engine._prepare_variables(engine.init_variables())
        s = engine.server_init(v)
        stack, stack_w = engine._device_stack()
        ids, wmask = engine.sample_padded(0)
        rng = jax.random.PRNGKey(0)
        v, s, m = engine.round_fn(v, s, stack, stack_w, ids, wmask, rng)
        force(m["train_loss"])                             # compile+warm
        t0 = time.perf_counter()
        for _ in range(ITERS):
            # donated variables/server_state thread through round to
            # round exactly like the run() loop
            v, s, m = engine.round_fn(v, s, stack, stack_w, ids, wmask,
                                      rng)
        force(m["train_loss"])
        dt = (time.perf_counter() - t0) / ITERS
        tag = "donate" if donate else "no_donate"
        print(f"DN128 {tag} resident round (chunk 2, bf16 masters, "
              f"flat carry): {dt:.3f}s/round", flush=True)


def _robust_workload(C: int):
    """CNN-femnist-shaped workload for the order-stat experiments (the
    model class these defenses are used with — MeshRobustEngine
    docstring): ~1.7M params, so a 256-client flats matrix is ~1.7 GB,
    small enough for the two-phase D2H/H2D traversal."""
    from fedml_tpu.data.loaders import load_data
    from fedml_tpu.utils.config import FedConfig

    cfg = FedConfig(model="cnn", dataset="femnist",
                    client_num_in_total=C, client_num_per_round=C,
                    epochs=1, batch_size=20, lr=0.05, norm_bound=0.5,
                    frequency_of_the_test=10_000)
    data = load_data("femnist", client_num_in_total=C, batch_size=20,
                     synthetic_scale=0.0, seed=0)
    model = create_model("cnn", output_dim=data.class_num)
    trainer = ClientTrainer(model, lr=cfg.lr, train_dtype=jnp.bfloat16)
    return cfg, data, trainer


def _orderstat_round(C: int, stream_block=None, defense="median"):
    from fedml_tpu.parallel import MeshRobustEngine
    from fedml_tpu.parallel.mesh import make_mesh

    cfg, data, trainer = _robust_workload(C)
    engine = MeshRobustEngine(trainer, data, cfg, defense=defense,
                              n_byzantine=max(1, C // 8),
                              mesh=make_mesh(), chunk=2,
                              local_dtype=jnp.bfloat16,
                              stream_block=stream_block, donate=False)
    variables = engine._prepare_variables(engine.init_variables())
    server_state = engine.server_init(variables)
    if stream_block is None:
        stack, stack_w = engine._device_stack()
        ids, wmask = engine.sample_padded(0)
        args = (stack, stack_w, ids, wmask)
    else:
        args = (0,)
    rng = jax.random.PRNGKey(0)

    def round_once():
        v, s, m = engine.round_fn(variables, server_state, *args, rng)
        return m["train_loss"]

    if stream_block is not None:
        # compile outside the overlap window, then reset: a compile-
        # round upload never waits, which would inflate the printed
        # steady-state overlap_fraction.  Resident rounds record no
        # uploads — skip the extra round there
        round_once()
        engine.transfer_stats.reset()
    dt = timeit(round_once, warmup=1, iters=3)
    mode = ("resident" if stream_block is None
            else f"blockstream({stream_block})")
    extra = ("" if stream_block is None
             else f"  {_overlap_line(engine)}")
    print(f"OS {defense} C={C} {mode}: {dt:.3f}s/round{extra}", flush=True)
    return dt


def exp_OS256():
    """Resident order-stat defenses at a 256-client CNN cohort (the
    replicated [K, P] matrix path): median and krum, 3 timed rounds."""
    _orderstat_round(256, defense="median")
    _orderstat_round(256, defense="krum")


def exp_OSB256():
    """The SAME 256-client rounds via the two-phase block stream
    (host [K, P] matrix, param-major slices): the resident-vs-streamed
    overhead is the chip cost of the beyond-HBM path (SCALING.md
    'Order statistics beyond HBM')."""
    _orderstat_round(256, stream_block=32, defense="median")
    _orderstat_round(256, stream_block=32, defense="krum")


def exp_B(batch_unroll: int = 1, bs: int = BS, n_batches: int = None,
          tag: str = "B"):
    """Centralized ceiling: shared weights, ceil(SPC/bs) steps (or an
    explicit `n_batches` for slot-matched variants) of effective batch
    bs*128.  `batch_unroll` must match the recipe of the round it
    anchors (exp_BU8 for the committed unroll-8 recipe) — comparing a U8
    round against a U1 ceiling would conflate the unroll win with the
    grouped-conv cost."""
    if n_batches is None:
        n_batches = (SPC + bs - 1) // bs
    model = create_model("resnet18_gn", output_dim=10)
    trainer = ClientTrainer(model, lr=0.1, train_dtype=jnp.bfloat16,
                            batch_unroll=batch_unroll)
    rs = np.random.RandomState(0)
    x = rs.rand(n_batches, bs * N_CLIENTS, 32, 32, 3).astype(np.float32)
    y = rs.randint(0, 10, (n_batches, bs * N_CLIENTS)).astype(np.int32)
    shard = {"x": jnp.asarray(x), "y": jnp.asarray(y),
             "mask": jnp.ones((n_batches, bs * N_CLIENTS), np.float32)}
    variables = trainer.init(jax.random.PRNGKey(0), shard["x"][0, :1])
    fn = jax.jit(lambda v, s, r: trainer.local_train(v, s, r, 1)[1])
    rng = jax.random.PRNGKey(1)
    dt = timeit(lambda: fn(variables, shard, rng))
    print(f"{tag} centralized_ceiling(unroll={batch_unroll},bs={bs},"
          f"{n_batches}x{bs * N_CLIENTS} slots): "
          f"{dt:.3f}s/round-equivalent", flush=True)


def exp_BU8():
    exp_B(batch_unroll=8)


def _chunked_round(chunk, data_dtype=None, master_dtype=None,
                   model_fn=None, unroll=1, bs=BS, valid=None):
    """THE chunked-round harness (every experiment row shares this exact
    accumulation + timing protocol):
      chunk        -- live client replicas per scan trip
      data_dtype   -- stored dtype of the client stack (H rows)
      master_dtype -- dtype of the LOCAL master weights (L rows; the
                      engine's local_dtype — aggregation stays f32)
      model_fn     -- alternative model constructor (G rows)
      unroll       -- lax.scan unroll depth for the batch loop (U rows)
      bs/valid     -- per-step batch size and real-sample count (BS rows:
                      same SPC real samples/client, ceil(SPC/bs) padded
                      batches — the padding slots are part of the recipe's
                      cost, exactly as the engine would pay them)
    """
    n_batches = (SPC + bs - 1) // bs
    model = model_fn() if model_fn else create_model("resnet18_gn",
                                                     output_dim=10)
    trainer = ClientTrainer(model, lr=0.1, train_dtype=jnp.bfloat16)
    rs = np.random.RandomState(0)
    shard = client_batches(rs, n_batches=n_batches, bs=bs, valid=valid)
    if data_dtype is not None:
        shard = {"x": shard["x"].astype(data_dtype), "y": shard["y"],
                 "mask": shard["mask"]}
    weights = jnp.full((N_CLIENTS,), float(SPC), jnp.float32)
    variables = trainer.init(jax.random.PRNGKey(0), shard["x"][0, 0, :1])
    if master_dtype is not None:
        variables = jax.tree.map(
            lambda a: a.astype(master_dtype)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, variables)
    rngs = jax.random.split(jax.random.PRNGKey(1), N_CLIENTS)
    n_chunks = N_CLIENTS // chunk

    def local_train(v, s, r):
        # the engine's ACTUAL client loop (unroll is a pass-through knob),
        # so the harness always measures the shipped code path
        nv, loss, _n = trainer.local_train(v, s, r, 1, unroll=unroll)
        return nv, loss

    def round_fn(variables, shard, weights, rngs):
        sh = jax.tree.map(
            lambda a: a.reshape((n_chunks, chunk) + a.shape[1:]), shard)
        w = weights.reshape(n_chunks, chunk)
        r = rngs.reshape(n_chunks, chunk, -1)

        def chunk_body(carry, xs):
            num, den, lsum = carry
            cs, cw, cr = xs
            vs, losses = jax.vmap(local_train,
                                  in_axes=(None, 0, 0))(variables, cs, cr)
            num = jax.tree.map(
                lambda acc, v: acc + jnp.einsum(
                    "k,k...->...", cw, v.astype(jnp.float32)), num, vs)
            return (num, den + jnp.sum(cw),
                    lsum + jnp.sum(losses * cw)), None

        zeros = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32),
                             variables)
        (num, den, lsum), _ = jax.lax.scan(
            chunk_body, (zeros, jnp.float32(0), jnp.float32(0)), (sh, w, r))
        avg = jax.tree.map(lambda s, ref: (s / den).astype(ref.dtype),
                           num, variables)
        return avg, lsum / den

    fn = jax.jit(round_fn)
    return timeit(lambda: fn(variables, shard, weights, rngs)[1])


def _bf16_master_round(chunk):
    return _chunked_round(chunk, master_dtype=jnp.bfloat16)


def exp_F4():
    print(f"F4 chunked(4): {_chunked_round(4):.3f}s/round", flush=True)


def exp_F8():
    print(f"F8 chunked(8): {_chunked_round(8):.3f}s/round", flush=True)


def exp_F16():
    print(f"F16 chunked(16): {_chunked_round(16):.3f}s/round", flush=True)


def exp_F32():
    print(f"F32 chunked(32): {_chunked_round(32):.3f}s/round", flush=True)


def exp_F64():
    print(f"F64 chunked(64): {_chunked_round(64):.3f}s/round", flush=True)


def exp_H16():
    """chunked(16) with the data stack stored bf16 (halves HBM reads)."""
    print(f"H16 chunked(16,bf16 data): "
          f"{_chunked_round(16, data_dtype=jnp.bfloat16):.3f}s/round",
          flush=True)


def exp_H32():
    print(f"H32 chunked(32,bf16 data): "
          f"{_chunked_round(32, data_dtype=jnp.bfloat16):.3f}s/round",
          flush=True)


def exp_L1():
    print(f"L1 chunked(1,bf16 masters): "
          f"{_bf16_master_round(1):.3f}s/round", flush=True)


def exp_L2():
    print(f"L2 chunked(2,bf16 masters): "
          f"{_bf16_master_round(2):.3f}s/round", flush=True)


def exp_L2U2():
    print(f"L2U2 chunked(2,bf16 masters,unroll=2): "
          f"{_chunked_round(2, master_dtype=jnp.bfloat16, unroll=2):.3f}"
          f"s/round", flush=True)


def exp_L2U4():
    print(f"L2U4 chunked(2,bf16 masters,unroll=4): "
          f"{_chunked_round(2, master_dtype=jnp.bfloat16, unroll=4):.3f}"
          f"s/round", flush=True)


def exp_L2U8():
    print(f"L2U8 chunked(2,bf16 masters,unroll=8): "
          f"{_chunked_round(2, master_dtype=jnp.bfloat16, unroll=8):.3f}"
          f"s/round", flush=True)


def exp_L2U13():
    print(f"L2U13 chunked(2,bf16 masters,unroll=13 = full): "
          f"{_chunked_round(2, master_dtype=jnp.bfloat16, unroll=13):.3f}"
          f"s/round", flush=True)


def _bs_variant_round(bs, unroll):
    """The committed round recipe (chunk 2, bf16 masters) at an alternate
    per-step batch size — VERDICT r3 next-#1: the reference's own CIFAR10
    cross-silo recipe runs bs=64 (reference benchmark/README.md:102-105),
    and the shared-weight ceiling is bandwidth-bound at bs-per-replica 32,
    so a larger batch plausibly lifts both the round and the ceiling.
    Same SPC=390 real samples/client; ceil(390/bs) padded batches."""
    n_batches = (SPC + bs - 1) // bs
    dt = _chunked_round(2, master_dtype=jnp.bfloat16, unroll=unroll,
                        bs=bs, valid=SPC)
    slots = n_batches * bs * N_CLIENTS
    print(f"BS{bs} chunked(2,bf16 masters,unroll={unroll},"
          f"{n_batches}x{bs}/client,{slots} slots): {dt:.3f}s/round",
          flush=True)


def exp_BS64():
    _bs_variant_round(64, unroll=7)        # 7 batches -> full unroll


def exp_BS64C():
    exp_B(batch_unroll=7, bs=64)


def exp_BS128():
    _bs_variant_round(128, unroll=4)       # 4 batches -> full unroll


def exp_BS128C():
    exp_B(batch_unroll=4, bs=128)


def exp_BS32():
    """bs=32 control at valid=SPC masks, same session as the BS rows."""
    _bs_variant_round(32, unroll=8)


def exp_BS256():
    """bs=256: 2 batches of 256/client — same 512 slots/client as bs=128
    but per-step conv batch 512 (chunk 2 x 256)."""
    _bs_variant_round(256, unroll=2)


def exp_BS128K1():
    """bs=128 at chunk 1: per-step conv batch 128 (vs 256 at chunk 2),
    half the live-replica HBM — does the chunk L-curve move with bs?"""
    n_batches = (SPC + 128 - 1) // 128
    dt = _chunked_round(1, master_dtype=jnp.bfloat16, unroll=4,
                        bs=128, valid=SPC)
    print(f"BS128K1 chunked(1,bf16 masters,unroll=4,"
          f"{n_batches}x128/client): {dt:.3f}s/round", flush=True)


def exp_BS128K4():
    """bs=128 at chunk 4: per-step conv batch 512."""
    n_batches = (SPC + 128 - 1) // 128
    dt = _chunked_round(4, master_dtype=jnp.bfloat16, unroll=4,
                        bs=128, valid=SPC)
    print(f"BS128K4 chunked(4,bf16 masters,unroll=4,"
          f"{n_batches}x128/client): {dt:.3f}s/round", flush=True)


def exp_BS390K1():
    """bs=390 = the whole shard as ONE batch (zero padding slots, 49,920
    total — fewer than bs=32's 53,248), conv batch 390 at chunk 1.
    Statistically a different optimizer (1 step/epoch); measured to map
    the envelope, not as a bench candidate."""
    dt = _chunked_round(1, master_dtype=jnp.bfloat16, unroll=1,
                        bs=390, valid=SPC)
    print(f"BS390K1 chunked(1,bf16 masters,1x390/client,49920 slots): "
          f"{dt:.3f}s/round", flush=True)


def exp_BS128K1U2():
    """chunk1/bs128 at unroll 2 — is the 1.611 optimum unroll-sensitive?"""
    dt = _chunked_round(1, master_dtype=jnp.bfloat16, unroll=2,
                        bs=128, valid=SPC)
    print(f"BS128K1U2 chunked(1,bf16 masters,unroll=2,4x128/client): "
          f"{dt:.3f}s/round", flush=True)


def exp_BS128C8():
    """Slot-matched shared-weight ceiling for the bs=128 round: the true
    4x16384 geometry OOMs v5e HBM (measured 16.59G/15.75G — itself a
    datum: the grouped round FITS where the monolithic batch does not),
    so the ceiling is taken at 8 steps of 8192 = the same 65,536 slots,
    at the round's unroll (4)."""
    exp_B(batch_unroll=4, bs=64, n_batches=8, tag="BS128C8")


def exp_L1U8():
    print(f"L1U8 chunked(1,bf16 masters,unroll=8): "
          f"{_chunked_round(1, master_dtype=jnp.bfloat16, unroll=8):.3f}"
          f"s/round", flush=True)


def exp_L4U8():
    print(f"L4U8 chunked(4,bf16 masters,unroll=8): "
          f"{_chunked_round(4, master_dtype=jnp.bfloat16, unroll=8):.3f}"
          f"s/round", flush=True)


def exp_L4():
    print(f"L4 chunked(4,bf16 masters): "
          f"{_bf16_master_round(4):.3f}s/round", flush=True)


def exp_L8():
    print(f"L8 chunked(8,bf16 masters): "
          f"{_bf16_master_round(8):.3f}s/round", flush=True)


def exp_L16():
    print(f"L16 chunked(16,bf16 masters): "
          f"{_bf16_master_round(16):.3f}s/round", flush=True)


def exp_L32():
    print(f"L32 chunked(32,bf16 masters): "
          f"{_bf16_master_round(32):.3f}s/round", flush=True)


def _conv_formulation(kind, k=8, b=32, h=32, w=32, cin=64, cout=64,
                      iters=20):
    """Per-client conv formulations: vmap-over-weights (what the engine
    does today) vs im2col + batched matmul (explicit MXU tiling).
    Forward + backward (the training cost), timed per iteration."""
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.rand(k, b, h, w, cin).astype(np.float32)).astype(jnp.bfloat16)
    wt = jnp.asarray(rs.rand(k, 3, 3, cin, cout).astype(np.float32)).astype(jnp.bfloat16)

    if kind == "vmap":
        def conv1(xi, wi):
            return jax.lax.conv_general_dilated(
                xi, wi, (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
        f = jax.vmap(conv1)
    elif kind == "fgc":
        def f(xs, ws):
            # feature-group-count merge: client i's batch slots share the
            # batch dim with every other client (conv is per-sample
            # independent), while its channels live in block i — one
            # grouped conv with k*cin inputs / k*cout outputs, so the
            # channel dims fill the MXU even when cin=cout=64
            xg = xs.transpose(1, 2, 3, 0, 4).reshape(b, h, w, k * cin)
            wg = ws.transpose(1, 2, 3, 0, 4).reshape(3, 3, cin, k * cout)
            out = jax.lax.conv_general_dilated(
                xg, wg, (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                feature_group_count=k)
            return out.reshape(b, h, w, k, cout).transpose(3, 0, 1, 2, 4)
    else:
        def f(xs, ws):
            # im2col: [k, b*h*w, 9*cin] patches, then one batched matmul
            patches = jax.lax.conv_general_dilated_patches(
                xs.reshape(k * b, h, w, cin), (3, 3), (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            # conv_general_dilated_patches emits channel-major patches
            # ([cin*9] with cin outer), so order the weights to match
            pat = patches.reshape(k, b * h * w, cin * 9)
            wm = ws.transpose(0, 3, 1, 2, 4).reshape(k, cin * 9, cout)
            out = jnp.einsum("kpc,kcd->kpd", pat, wm)
            return out.reshape(k, b, h, w, cout)

    def loss(ws):
        return jnp.sum(f(x, ws).astype(jnp.float32) ** 2)

    g = jax.jit(jax.value_and_grad(loss))
    for _ in range(3):
        out = g(wt)
    force(out[0])
    t0 = time.perf_counter()
    for _ in range(iters):
        out = g(wt)
    force(out[0])
    return (time.perf_counter() - t0) / iters


def exp_CONV():
    """Grouped-conv penalty microbenchmark: is im2col+batched-matmul faster
    than the vmapped conv XLA emits for per-client weights?"""
    for cin, cout, hw in [(64, 64, 32), (128, 128, 16), (256, 256, 8)]:
        tv = _conv_formulation("vmap", cin=cin, cout=cout, h=hw, w=hw)
        ti = _conv_formulation("im2col", cin=cin, cout=cout, h=hw, w=hw)
        print(f"CONV {cin}x{cout}@{hw}: vmap {tv*1e3:.2f}ms  "
              f"im2col {ti*1e3:.2f}ms  ratio {tv/ti:.2f}x", flush=True)


def exp_PAD():
    """Absolute cost of widening cout 64->128 on the stem shape (VERDICT r2
    next-#2 cout-padding lever): a padded-channel model variant only wins if
    the 128-wide conv costs ~the same wall time as the 64-wide one (the MXU
    columns were half-idle).  2x time = exactly proportional = padding loses."""
    for k in [4, 2]:
        t64 = _conv_formulation("vmap", k=k, cin=64, cout=64, h=32, w=32)
        t128 = _conv_formulation("vmap", k=k, cin=64, cout=128, h=32, w=32)
        tw = _conv_formulation("vmap", k=k, cin=128, cout=128, h=32, w=32)
        print(f"PAD k={k}@32: cout64 {t64*1e3:.2f}ms  cout128 "
              f"{t128*1e3:.2f}ms ({t128/t64:.2f}x)  both128 "
              f"{tw*1e3:.2f}ms ({tw/t64:.2f}x)", flush=True)


def exp_FGC():
    """Per-client conv as ONE feature-group-count conv (clients side-by-side
    in the channel dim) vs the vmapped conv — the block-diagonal-matmul
    formulation of the per-client grouped conv (VERDICT r2 next-#2)."""
    for k in [4, 8]:
        for cin, cout, hw in [(64, 64, 32), (128, 128, 16), (256, 256, 8)]:
            tv = _conv_formulation("vmap", k=k, cin=cin, cout=cout,
                                   h=hw, w=hw)
            tf = _conv_formulation("fgc", k=k, cin=cin, cout=cout,
                                   h=hw, w=hw)
            print(f"FGC k={k} {cin}x{cout}@{hw}: vmap {tv*1e3:.2f}ms  "
                  f"fgc {tf*1e3:.2f}ms  ratio {tv/tf:.2f}x", flush=True)


def _barrier_gn_model():
    """ResNet-18-GN with norm_fusion_barrier=True (models/resnet_gn.py):
    optimization_barriers before every GroupNorm stop XLA from output-
    fusing the conv with the GN statistics reduces (the trace shows those
    fusions dominating at low MFU; does unfusing let the conv run clean?)."""
    return create_model("resnet18_gn", output_dim=10,
                        norm_fusion_barrier=True)


def exp_G4():
    """chunk-4 bf16-masters round with conv/GN fusion barriers."""
    dt = _chunked_round(4, master_dtype=jnp.bfloat16,
                        model_fn=_barrier_gn_model)
    print(f"G4 chunked(4,bf16 masters,GN fusion barrier): "
          f"{dt:.3f}s/round", flush=True)


def exp_R():
    """Robust aggregation: XLA tree pipeline (core/robust.py norm-diff
    clip per client + weighted mean) vs the fused pallas kernel
    (ops/aggregate.py) over a 128-client ResNet-18-GN param stack — the
    measurement VERDICT r1 weak-#2 asked for before the kernel can
    default on.  Both compute  g + Σᵢ ŵᵢ·clipᵢ·(xᵢ−g)."""
    import functools
    from fedml_tpu.core import robust as robust_ops
    from fedml_tpu.ops import robust_weighted_mean_pallas

    # 64 clients: the 128-stack + the pallas kernel's padded temps exceed
    # v5e HBM (measured 16.03G/15.75G, 2026-07-30) — the XLA pipeline alone
    # fits 128, which is itself a datum for the kernel-default question
    K = 64
    model = create_model("resnet18_gn", output_dim=10)
    g = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)),
                   train=False)["params"]
    stacked = jax.tree.map(
        lambda a: a[None] + 0.01 * jnp.arange(K).reshape(
            (K,) + (1,) * a.ndim).astype(a.dtype), g)
    w = jnp.full((K,), float(SPC), jnp.float32)
    tau = 5.0

    def xla_pipeline(stacked, w, g):
        clipped = jax.vmap(
            lambda cv: robust_ops.norm_diff_clip(cv, g, tau))(stacked)
        num = jax.tree.map(
            lambda s: jnp.einsum("k,k...->...", w, s.astype(jnp.float32)),
            clipped)
        return jax.tree.map(lambda s: s / jnp.sum(w), num)

    f_xla = jax.jit(xla_pipeline)
    f_pal = jax.jit(functools.partial(robust_weighted_mean_pallas,
                                      norm_bound=tau))
    # same math: cross-check before timing
    a = f_xla(stacked, w, g)
    b = f_pal(stacked, w, g)
    err = max(float(jnp.max(jnp.abs(x - y)))
              for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    tx = timeit(lambda: f_xla(stacked, w, g), warmup=2, iters=10)
    tp = timeit(lambda: f_pal(stacked, w, g), warmup=2, iters=10)
    print(f"R robust-agg {K}xResNet18: xla {tx*1e3:.1f}ms  "
          f"pallas {tp*1e3:.1f}ms  ratio {tx/tp:.2f}x  maxerr {err:.2e}",
          flush=True)


# exp_SCAN (removed 2026-07-31): run_scanned vs the jitted per-round loop
# at ms-scale rounds (LR/MNIST, 1000 clients, 10/round, R=100, blocks of
# 50 — the regime where amortizing per-round dispatch should pay if it
# ever does).  Measured on the v5e chip: loop 2.56 ms/round, scanned
# 23.81 ms/round (eval-corrected) — the scanned path lost 9.3x, so
# run_scanned was cut from the engine (VERDICT r2 next-#6; PERF.md).


def exp_NWP():
    """StackOverflow-NWP per-client local epoch: reference LSTM
    (RNNStackOverflow, 4.1M total params, sequential scan over 20 tokens)
    vs the beyond-reference TransformerLM at ~2× the total params
    (d256/4L/ff1024, 8.4M): does attention's batched-matmul formulation
    beat the LSTM's length-T dependency chain on the MXU?  (Both printed
    counts are TOTALS over all param leaves, embeddings included.)"""
    import jax.numpy as jnp

    B, bs, T = 13, 16, 20
    rs = np.random.RandomState(0)
    shard = {
        "x": jnp.asarray(rs.randint(0, 10004, (B, bs, T)), jnp.int32),
        "y": jnp.asarray(rs.randint(0, 10004, (B, bs, T)), jnp.int64),
        "mask": jnp.ones((B, bs), jnp.float32),
    }
    for name, kw in (("rnn_stackoverflow", {}),
                     ("transformer", dict(d_model=256, n_heads=4,
                                          n_layers=4, d_ff=1024))):
        model = create_model(name, 10004, **kw)
        trainer = ClientTrainer(model, lr=0.3, has_time_axis=True,
                                train_dtype=jnp.bfloat16)
        v = trainer.init(jax.random.PRNGKey(0), shard["x"][0, :1])
        n_params = sum(int(np.prod(a.shape))
                       for a in jax.tree.leaves(v["params"]))
        fn = jax.jit(lambda vv, s, r: trainer.local_train(vv, s, r, 1)[1])
        rng = jax.random.PRNGKey(1)
        dt = timeit(lambda: fn(v, shard, rng), warmup=2, iters=10)
        print(f"NWP {name} ({n_params/1e6:.1f}M params): "
              f"{dt*1e3:.2f} ms per 13-step local epoch", flush=True)


def exp_ASYNC():
    """Async federation A/B (ISSUE 5): committed-updates/sec of the
    buffered staleness-aware scheduler (fedml_tpu/async_) on the bench
    workload, at two buffer sizes against the same dispatch width —
    K=8 (semi-async, 4x concurrency/K => genuine staleness under the
    seeded lognormal lifecycle) vs K=32 (buffer == concurrency, the
    near-synchronous end).  Latencies are SIMULATED (virtual clock), so
    the wall prices the compute: dispatch-wave vmapped training + the
    jitted flat-carry commit.  One async commit aggregates K results;
    an A-row round aggregates all 128 — compare samples/sec, not raw
    rates (the printout carries both)."""
    import jax
    from fedml_tpu.async_ import AsyncFedAvgEngine, LifecycleConfig

    CONC, WARMUP, TIMED = 32, 2, 8
    for K in (8, 32):
        cfg, data, trainer = _bench_workload(N_CLIENTS)
        cfg.frequency_of_the_test = 1        # wall_time per commit
        lc = LifecycleConfig(latency="lognormal", latency_scale=1.0,
                             latency_sigma=0.5, heterogeneity=0.5, seed=0)
        engine = AsyncFedAvgEngine(trainer, data, cfg, buffer_k=K,
                                   concurrency=CONC,
                                   staleness="polynomial", staleness_a=0.5,
                                   lifecycle_cfg=lc, donate=False)
        total = WARMUP + TIMED
        v = engine.run(rounds=total)
        jax.block_until_ready(v)
        walls = [m["wall_time"] for m in engine.metrics_history]
        dt = (walls[total - 1] - walls[WARMUP - 1]) / TIMED
        rep = engine.async_report()
        print(f"ASYNC K={K} conc={CONC}: {dt:.3f}s/commit "
              f"({K * SPC / dt:.0f} samples/s)  staleness p50/p95 "
              f"{rep['staleness_p50']:.0f}/{rep['staleness_p95']:.0f}  "
              f"buffer fill {rep['buffer_occupancy_mean'] / K:.2f}",
              flush=True)


def exp_INGEST():
    """Concurrent-uplink ingestion A/B (ISSUE 6): sustained
    committed-updates/sec of the async server's decode+aggregate path
    under 32 saturating TCP clients (fedml_tpu/async_/torture.py — no
    training, pre-encoded 1 MiB frames, so the wall prices ingestion
    alone).  Arms: the PR-5 legacy path faithfully (inline decode on
    recv threads, unbounded inbox, drained O(K·P) commit), the same
    path with only the inbox backpressure (queue-discipline isolation),
    and decode-into + streaming aggregation-on-arrival at pool 1/4/8.
    On a many-core server the pool sweep shows decode scaling; on a
    2-core box it shows the lock becoming the next bottleneck (PERF.md
    "Uplink ingestion")."""
    from fedml_tpu.async_.torture import run_ingest_torture

    arms = [("legacy pool=0", dict(ingest_pool=0, decode_into=False,
                                   streaming=False)),
            ("legacy bounded-inbox", dict(ingest_pool=0, decode_into=False,
                                          streaming=False,
                                          inbox_bound=64))]
    arms += [(f"decode-into pool={p}",
              dict(ingest_pool=p, decode_into=True, streaming=True))
             for p in (1, 4, 8)]
    base = None
    for i, (tag, kw) in enumerate(arms):
        r = run_ingest_torture(n_clients=32, backend="TCP", buffer_k=8,
                               commits=30, warmup_commits=5,
                               base_port=53500 + i, timeout_s=300, **kw)
        ups = r["committed_updates_per_sec"]
        base = ups if base is None else base
        print(f"INGEST {tag}: {ups:.1f} updates/s "
              f"({ups / base:.1f}x legacy)  decode p50/p95 "
              f"{r['decode_p50_s'] * 1e3:.2f}/"
              f"{r['decode_p95_s'] * 1e3:.2f} ms  lock wait "
              f"{r['lock_wait_seconds']:.2f}s", flush=True)


def exp_TRACE(reps: int = 4):
    """Federation-tracing overhead A/B (ISSUE 7): the ingest torture
    (32 TCP clients, decode-into + streaming, pool 8) untraced vs under
    a live span tracer WITH trace-stamped frames (every uplink carries
    the trace block, every receive feeds the clock-offset estimator and
    records spans) — the acceptance gate is < 5% throughput regression.

    Identical back-to-back torture arms have measured 20%+ apart on the
    shared CPU box (PERF.md "Uplink ingestion" saw 28-80x spreads on
    its headline too), and the FIRST arms of a process run 30-50% slow
    (jit compile, allocator/TCP warmup) regardless of tracing.  A
    single sequential pair cannot price a 5% effect, so the protocol
    is PAIRED: one discarded warmup arm of each flavor, then `reps`
    (untraced, traced) pairs alternating which arm goes first each rep
    so slow drift cancels, and the headline is the MEDIAN of the
    per-pair overhead ratios.  Prints the last traced arm's
    critical-path attribution table, the same stage breakdown
    bench.py's schema-v6 `critical_path` block records."""
    import statistics
    import tempfile
    from fedml_tpu import obs
    from fedml_tpu.obs import timeline
    from fedml_tpu.async_.torture import run_ingest_torture

    if obs.enabled():
        print("TRACE: obs already enabled — the 'untraced' arm would be "
              "traced too; unset FEDML_OBS_DIR", flush=True)
        return
    kw = dict(n_clients=32, backend="TCP", buffer_k=8, commits=30,
              warmup_commits=5, ingest_pool=8, decode_into=True,
              streaming=True, timeout_s=300)
    obs_dir = tempfile.mkdtemp(prefix="fedml_trace_ab_")
    port = [53700]

    def run_arm(traced: bool):
        port[0] += 1
        if not traced:
            return run_ingest_torture(base_port=port[0], **kw)
        obs.configure(obs_dir, install_signal=False,
                      export_at_exit=False)
        try:
            r = run_ingest_torture(base_port=port[0], **kw)
            obs.export()
        finally:
            obs.reset()
        return r

    run_arm(False)                   # process warmup, both flavors —
    run_arm(True)                    # timings discarded
    ratios, traced_last = [], None
    for rep in range(reps):
        order = (False, True) if rep % 2 == 0 else (True, False)
        pair = {}
        for traced in order:
            pair[traced] = run_arm(traced)
        if pair[True].get("critical_path"):
            traced_last = pair[True]
        u0 = pair[False]["committed_updates_per_sec"]
        u1 = pair[True]["committed_updates_per_sec"]
        ratios.append(1.0 - u1 / u0 if u0 > 0 else 0.0)
        print(f"TRACE pair {rep + 1}/{reps} "
              f"({'U,T' if order[0] is False else 'T,U'}): "
              f"untraced {u0:.1f}  traced {u1:.1f} updates/s  "
              f"overhead {ratios[-1]:+.1%}", flush=True)
    med = statistics.median(ratios)
    print(f"TRACE median overhead {med:+.1%} over {reps} paired reps "
          f"(gate < 5%; artifacts in {obs_dir})", flush=True)
    if traced_last:
        print(timeline.format_report(traced_last["critical_path"]),
              flush=True)


def exp_CHAOS():
    """Chaos goodput A/B (ISSUE 8): the reliable ingest torture (32 TCP
    clients, FMLR envelopes, decode-into + streaming, pool 4) under
    seeded wire-level fault injection (fedml_tpu/comm/chaos.py) at the
    server's receive chokepoint.  Arms: clean reliable baseline, 5% and
    20% frame loss, and the acceptance-shaped mixed arm (5% loss + 1%
    dup + 0.5% corrupt).  The gate is goodput >= 0.5x clean on the
    mixed arm with ZERO recv-thread deaths — the `bench.py --mode
    chaos` curve, priced with the chip-attached jax runtime driving
    the fold/commit."""
    from fedml_tpu.async_.torture import run_ingest_torture

    arms = [("clean", None),
            ("loss_5", {"drop": 0.05}),
            ("loss_20", {"drop": 0.20}),
            ("mixed", {"drop": 0.05, "dup": 0.01, "corrupt": 0.005})]
    base = None
    for i, (tag, chaos) in enumerate(arms):
        r = run_ingest_torture(n_clients=32, backend="TCP", buffer_k=8,
                               commits=20, warmup_commits=3,
                               ingest_pool=4, decode_into=True,
                               streaming=True, base_port=53900 + i,
                               timeout_s=600, reliable=True, chaos=chaos)
        ups = r["committed_updates_per_sec"]
        base = ups if base is None else base
        print(f"CHAOS {tag}: {ups:.1f} updates/s "
              f"({ups / base:.2f}x clean)  retries {r['retries']:.0f}  "
              f"dups suppressed {r['dups_suppressed']:.0f}  "
              f"quarantined {r['quarantined']:.0f}  recv deaths "
              f"{r['recv_thread_deaths']:.0f}  injected "
              f"{r['chaos_injected']}", flush=True)


def exp_ATTACK():
    """Adversarial-robustness A/B (ISSUE 9): the attack x defense
    accuracy matrix on the async MNIST-LR band workload (clean /
    mixed-undefended / mixed-defended — the defended arm must stay in
    band while undefended degrades, with zero honest quarantines), plus
    the admission-overhead ingest pair (screen on vs off, 32 TCP
    clients — the >=0.9x throughput gate) priced with the chip-attached
    jax runtime driving the screen + fold + bucketed commit.  The same
    sweep `bench.py --mode attack` runs; this entry queues it for chip
    windows."""
    import json as _json
    out = _bench_child(["--mode", "attack"], "ATTACK")
    line = (out.strip().splitlines() or ["{}"])[-1]
    doc = _json.loads(line)
    atk = doc.get("attack") or {}
    print(f"ATTACK clean {atk.get('clean_acc')}  undefended "
          f"{atk.get('undefended_acc')}  defended {atk.get('defended_acc')}"
          f"  false-positives {atk.get('false_positive_quarantines')}  "
          f"overhead ratio "
          f"{(atk.get('overhead') or {}).get('throughput_ratio')}",
          flush=True)


def exp_SERVE():
    """Million-client serving-spine A/B (ISSUE 10): sustained
    committed-updates/sec and server registry memory vs simulated
    population (10k / 100k / 1M), stratified vs reservoir cohort
    sampling, under the diurnal arrival process — the chip-side rerun
    of `bench.py --mode serve` with the chip-attached jax runtime
    dispatching the streaming fold/commit.  Gates: registry <= ~100
    bytes/client at every population, and the 1M arm sustains (>= 0.5x
    the 10k arm — sub-linear server cost is the headline, the fold is
    the floor)."""
    from fedml_tpu.scale import ArrivalConfig, run_serve_sim

    arr = ArrivalConfig(mode="diurnal", rate=2000.0, period_s=600.0,
                        amplitude=0.8)
    for mode in ("stratified", "reservoir"):
        base = None
        for pop in (10_000, 100_000, 1_000_000):
            r = run_serve_sim(pop, commits=40, warmup_commits=4,
                              buffer_k=32, row_dim=4096,
                              sampler_mode=mode, arrival=arr,
                              dropout_prob=0.02, banned_frac=0.01)
            ups = r["committed_updates_per_sec"]
            base = ups if base is None else base
            print(f"SERVE {mode} pop={pop}: {ups:.0f} updates/s "
                  f"({ups / base:.2f}x vs 10k)  registry "
                  f"{r['registry_bytes'] / 1e6:.1f} MB "
                  f"({r['registry_bytes_per_client']:.1f} B/client)  "
                  f"rss {r['rss_bytes'] / 1e6:.0f} MB", flush=True)


def exp_CONN():
    """Live-connection reactor A/B (ISSUE 11): 256 and 1k live sockets
    against the selector reactor transport, clean vs storm (mixed
    chaos 5%+1%+0.5% + connection storm + reconnect churn) — the
    chip-side rerun of `bench.py --mode connections` with the
    chip-attached jax runtime dispatching the fold/commit.  Gates:
    storm >= 0.5x clean committed-updates/sec, zero recv-thread
    deaths, zero leaked FDs."""
    from fedml_tpu.async_.torture import run_connection_torture

    port = 53760
    for n in (256, 1000):
        base = None
        for tag, kw in (("clean", {}),
                        ("storm", dict(
                            chaos={"drop": 0.05, "dup": 0.01,
                                   "corrupt": 0.005},
                            storm=True, churn_lifetime_s=5.0))):
            port += 2
            r = run_connection_torture(
                n_connections=n, buffer_k=32, commits=24,
                warmup_commits=3, ingest_pool=4, offered_rate=2000.0,
                base_port=port, timeout_s=900, **kw)
            ups = r["committed_updates_per_sec"]
            base = ups if base is None else base
            print(f"CONN n={n} {tag}: {ups:.1f} updates/s "
                  f"({ups / base:.2f}x vs clean)  admission p95 "
                  f"{r['admission_p95_s'] * 1e3:.1f} ms  evicted "
                  f"{r['evicted']}  shed {r['uplinks_shed']:.0f}  "
                  f"fd leak {r['fd_leaked']}  recv deaths "
                  f"{r['recv_thread_deaths']:.0f}", flush=True)


def exp_POD():
    """Multi-host weak-scaling sweep (ISSUE 13): a rerun of `bench.py
    --mode multihost` — N processes on THIS host (FEDML_POD_PROCS
    overrides the 1,2,4 default), each training its client block on a
    local CPU mesh (the multi-process tier is host-level and CPU-only
    today: parallel/mh_worker.py), the P-sized flat f32 carry
    allreduced across processes over the HostChannel.  Gates: the
    1-vs-2-process same-block-partition commit digests bitwise equal,
    zero process deaths, and weak-scaling efficiency at 2 processes
    (the 2-core CPU floor is 0.5x).

    Since schema v14 the default arm set includes the COMPRESSED-carry
    arm (ISSUE 16): bytes-on-wire per round, compression ratio,
    efficiency-at-constant-bytes and overlap fraction measured on the
    channel itself (loopback frames here).  FEDML_POD_ARMS narrows the
    arm set (e.g. `FEDML_POD_ARMS=compress` reruns just the wire-tier
    A/B)."""
    args = ["--mode", "multihost",
            "--mh_procs", os.environ.get("FEDML_POD_PROCS", "1,2,4")]
    arms = os.environ.get("FEDML_POD_ARMS")
    if arms:
        args += ["--mh_arms", arms]
    print(_bench_child(args, "POD"), flush=True)


def exp_ELASTIC():
    """Elastic-chaos arm (ISSUE 14; CPU workers, like exp_POD):
    `bench.py --mode multihost --mh_arms chaos` — a 3-process ELASTIC
    cluster (FEDML_POD_ELASTIC_PROCS overrides) with a seeded kill
    of rank 1 mid-run vs the clean elastic run.  Gates: the survivors
    FINISH (zero survivor deaths — the elastic launch policy + view
    change + block re-adoption), survivor goodput >= 0.5x clean, and
    bitwise_after_death_ok — the re-adopted blocks commit the same
    bits, because every block partial is a pure function of [seed,
    round, block]."""
    print(_bench_child(
        ["--mode", "multihost", "--mh_arms", "chaos", "--mh_chaos_procs",
         os.environ.get("FEDML_POD_ELASTIC_PROCS", "3")], "ELASTIC"),
        flush=True)


def exp_CLUSTER():
    """Fused serving cluster (ISSUE 18): `bench.py
    --mode cluster` — H spawned hosts each binding a reactor endpoint
    over the host's registry-shard range, a striped connswarm fleet
    replaying the diurnal/flash arrival processes over real sockets,
    lane partials folding cross-host through ElasticChannel at every
    commit barrier.  FEDML_CLUSTER_HOSTS overrides the 1,2,4 sweep;
    FEDML_CLUSTER_RATE the per-host offered rate;
    FEDML_CLUSTER_ARMS widens the arm set (e.g.
    `FEDML_CLUSTER_ARMS=clean,sparse` adds the ISSUE-19 sparse-uplink
    A/B).  Gates ride bench_diff v16+: chaos-everything survivor
    goodput >= 0.5x clean, zero recv-thread deaths,
    bitwise_after_death_ok + ranks_agree boolean pins; the sparse arm
    adds the v17 >= 0.9x committed-updates/sec gate.  The serving
    hosts are spawned CPU workers (parallel/mh_worker.py), so this is
    a host-level measurement on any machine."""
    args = ["--mode", "cluster",
            "--cluster_hosts", os.environ.get("FEDML_CLUSTER_HOSTS", "1,2,4"),
            "--cluster_rate", os.environ.get("FEDML_CLUSTER_RATE", "2000")]
    arms = os.environ.get("FEDML_CLUSTER_ARMS")
    if arms:
        args += ["--cluster_arms", arms]
    print(_bench_child(args, "CLUSTER"), flush=True)


def exp_SECAGG():
    """Pairwise-mask secure aggregation chip-attached (ISSUE 20):
    `bench.py --mode secure` — the privacy-tax table on the live async
    messaging FSM with the chip-attached runtime driving the jitted
    u32 field fold (plain vs masked committed-updates/sec), the
    plain/secure/dp accuracy triple (end-to-end private mode), the
    masks-cancel bitwise pin, and the masked-byzantine pair (the
    in-field boost that sails past the blinded screen vs the overflow
    boost the client-side quantizer range refusal drops).  Gates ride
    bench_diff v18: privacy_tax_ratio >= 0.5, zero below-threshold
    commits on the clean arms, masks_cancel_bitwise_ok.
    FEDML_SECURE_COHORT / FEDML_SECURE_COMMITS override the workload
    shape."""
    import json as _json
    args = ["--mode", "secure"]
    cohort = os.environ.get("FEDML_SECURE_COHORT")
    if cohort:
        args += ["--secure_cohort", cohort]
    commits = os.environ.get("FEDML_SECURE_COMMITS")
    if commits:
        args += ["--secure_commits", commits]
    out = _bench_child(args, "SECAGG")
    print(out, flush=True)
    line = (out.strip().splitlines() or ["{}"])[-1]
    sec = (_json.loads(line).get("secure") or {})
    print(f"SECAGG tax {sec.get('privacy_tax_ratio')}  "
          f"masks_cancel {sec.get('masks_cancel_bitwise_ok')}  "
          f"below_threshold_clean "
          f"{sec.get('below_threshold_commits_clean')}  "
          f"secure_acc {sec.get('secure_acc')}  "
          f"dp_acc {sec.get('dp_acc')}", flush=True)


def exp_U8():
    print(f"U8 chunked(8,unroll=2): "
          f"{_chunked_round(8, unroll=2):.3f}s/round", flush=True)


def exp_U8x4():
    print(f"U8x4 chunked(8,unroll=4): "
          f"{_chunked_round(8, unroll=4):.3f}s/round", flush=True)


if __name__ == "__main__":
    from fedml_tpu.utils import compile_cache
    compile_cache.configure()
    which = sys.argv[1:] or ["A", "B", "F16"]
    for name in which:
        globals()[f"exp_{name}"]()
