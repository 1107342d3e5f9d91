"""Worker for test_multihost_spmd's checkpoint/resume case (argv: pid
port nprocs ndev mode ckpt_dir).  Two modes:

  interrupt — run the first 2 of 4 rounds with per-round orbax
              checkpointing, then EXIT (the "kill" in save→kill→resume:
              rounds 2-3 never run in this cluster).
  resume    — in a FRESH cluster: first run the uninterrupted 4-round
              oracle (same processes, same gloo topology — the digest
              comparison isolates the resume mechanics from any
              cross-topology reduction-order noise), then resume from
              the checkpoint and continue rounds 2-3.  Prints both
              digests; the test asserts they are identical.

The reference has no FL-state resume at all (SURVEY.md §5) — this is
the framework's own bar: round-level orbax checkpointing that survives
a multi-process SPMD cluster's death.
"""
import os
import sys

pid, port, nprocs, ndev, mode, ckpt_dir = (
    int(sys.argv[1]), sys.argv[2], int(sys.argv[3]), int(sys.argv[4]),
    sys.argv[5], sys.argv[6])
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"

import jax  # noqa: E402

# no persistent compile cache in a rank: one rank loading an entry while
# its peers compile skews them, and a skewed round can hang (ROADMAP D10)

from fedml_tpu.parallel.multihost import init_multihost  # noqa: E402

init_multihost(coordinator_address=f"localhost:{port}",
               num_processes=nprocs, process_id=pid, required=True)

from fedml_tpu.utils.checkpoint import FedCheckpointManager  # noqa: E402
from tests.multihost_case import build_ckpt_case, digest  # noqa: E402

assert jax.device_count() == nprocs * ndev

if mode == "interrupt":
    eng = build_ckpt_case()
    mgr = FedCheckpointManager(ckpt_dir)
    eng.run(rounds=2, ckpt=mgr, ckpt_every=1)
    saved = mgr.latest_round()
    mgr.close()
    print(f"SAVED {saved}", flush=True)
elif mode == "resume":
    full = build_ckpt_case()
    v_full = full.run(rounds=4)
    print(f"CKFULL {digest(v_full):.10e}", flush=True)
    eng = build_ckpt_case()
    mgr = FedCheckpointManager(ckpt_dir)
    v_res = eng.run(rounds=4, ckpt=mgr, resume=True)
    mgr.close()
    print(f"CKRES {digest(v_res):.10e}", flush=True)
else:
    raise SystemExit(f"unknown mode {mode!r}")
