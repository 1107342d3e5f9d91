"""Chip-measured convergence at the committed silo recipe (VERDICT r3
next-#2).

The benchmark times the committed recipe (MeshFedAvgEngine, chunk 2,
bf16 local masters, batch_unroll 8, bf16 compute) for a 20 s window —
evidence of speed, not of training quality; the recipe's numerics were
pinned only by CPU closeness tests.  This script runs the same recipe
on the real chip over a LEARNABLE synthetic CIFAR stand-in (class
templates + noise, data/synthetic.py) — the silo cohort (128 clients x
390 samples, full participation, streaming) — for a few hundred
rounds, recording the held-out accuracy curve.

The endpoint is pinned in PERF.md; tests/test_quality_regression.py
pins the same recipe's CPU behavior.  Usage:

    PYTHONPATH=. python tools/chip_convergence.py [rounds] \
        [--out chiprun_out/artifact.json]
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np

N_CLIENTS = 128
BS = 32
SPC = 50_000 // N_CLIENTS
N_TEST = 2_000
EVAL_EVERY = 10


def main() -> None:
    rounds = int(sys.argv[1]) if len(sys.argv) > 1 and sys.argv[1].isdigit() \
        else 300
    out_path = None
    if "--out" in sys.argv:
        out_path = sys.argv[sys.argv.index("--out") + 1]

    import jax
    import jax.numpy as jnp

    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.data.federated import (FederatedData, build_client_shards,
                                          build_eval_shard)
    from fedml_tpu.data.synthetic import synthetic_classification_images
    from fedml_tpu.models import create_model
    from fedml_tpu.parallel import MeshFedAvgEngine
    from fedml_tpu.parallel.mesh import make_mesh
    from fedml_tpu.utils import compile_cache
    from fedml_tpu.utils.config import FedConfig

    compile_cache.configure()
    print(f"devices: {jax.devices()}", file=sys.stderr)

    n = N_CLIENTS * SPC + N_TEST
    x, y = synthetic_classification_images(n, (32, 32), 3, 10, seed=0)
    xt, yt, x, y = x[:N_TEST], y[:N_TEST], x[N_TEST:], y[N_TEST:]
    idx = {i: np.arange(i * SPC, (i + 1) * SPC) for i in range(N_CLIENTS)}
    data = FederatedData(
        train_data_num=len(y), test_data_num=N_TEST,
        train_global=build_eval_shard(x[:N_TEST], y[:N_TEST], 200),
        test_global=build_eval_shard(xt, yt, 200),
        client_shards=build_client_shards(x, y, idx, BS),
        client_num_samples=np.full(N_CLIENTS, SPC, np.float32),
        test_client_shards=None, class_num=10, synthetic=True)

    cfg = FedConfig(model="resnet18_gn", dataset="cifar10",
                    client_num_in_total=N_CLIENTS,
                    client_num_per_round=N_CLIENTS,
                    epochs=1, batch_size=BS, lr=0.1,
                    frequency_of_the_test=10_000)
    model = create_model("resnet18_gn", output_dim=10)
    # the committed silo recipe (chip_smoke.py::build_headline):
    # bf16 compute, unroll 8, chunk 2, bf16 local masters, bf16
    # cohort storage
    trainer = ClientTrainer(model, lr=cfg.lr, train_dtype=jnp.bfloat16,
                            batch_unroll=8)
    engine = MeshFedAvgEngine(trainer, data, cfg, mesh=make_mesh(), chunk=2,
                              local_dtype=jnp.bfloat16,
                              stack_dtype=jnp.bfloat16)

    variables = engine._prepare_variables(engine.init_variables())
    server_state = engine.server_init(variables)
    cohort, weights = engine.stream_cohort(0)
    rng = jax.random.PRNGKey(0)
    curve = []
    t0 = time.time()
    for r in range(rounds):
        rng, rr = jax.random.split(rng)
        variables, server_state, m = engine.round_fn_streaming(
            variables, server_state, cohort, weights, rr)
        if (r + 1) % EVAL_EVERY == 0 or r == rounds - 1:
            stats = engine.evaluate(variables)
            row = {"round": r + 1, "test_acc": round(stats["test_acc"], 4),
                   "test_loss": round(stats["test_loss"], 4),
                   "train_loss": round(float(m["train_loss"]), 4)}
            curve.append(row)
            print(json.dumps(row), flush=True)
    wall = time.time() - t0
    result = {"recipe": "chunk2/bf16-masters/unroll8/bf16-stack",
              "rounds": rounds, "wall_s": round(wall, 1),
              "final_test_acc": curve[-1]["test_acc"],
              "curve": curve}
    print(json.dumps({k: v for k, v in result.items() if k != "curve"}))
    if out_path:
        with open(out_path, "w") as f:
            json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
