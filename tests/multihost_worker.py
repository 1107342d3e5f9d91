"""Worker process for test_multihost_spmd: joins an N-process
jax.distributed CPU cluster (argv: pid port nprocs ndev), forming an
(nprocs * ndev)-device GLOBAL mesh, runs the shared oracle cases over
it, and prints digests of the trained parameters.

This is the DCN story executed for real: the same global-view SPMD
engine code that runs single-host runs here across process boundaries,
with the aggregation psum crossing between processes (gloo carries the
CPU collectives; on a TPU pod the same program rides ICI/DCN).  Cases:

  DIGEST/ACC    flat MeshFedAvgEngine over the global 1-D mesh
  HDIGEST/HACC  hierarchical, one silo per PROCESS (inner psum
                host-local, silo tier crosses the boundary)
  SDIGEST/SACC  streaming cohort + FedOpt adam server state: per-round
                global device_put upload AND persistent on-device
                server state crossing rounds

Not a test file itself — launched by test_multihost_spmd.py.
"""
import os
import sys

pid, port, nprocs, ndev = (int(sys.argv[1]), sys.argv[2],
                           int(sys.argv[3]), int(sys.argv[4]))
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"

import jax  # noqa: E402

# no persistent compile cache in a rank: one rank loading an entry while
# its peers compile skews them, and a skewed round can hang (ROADMAP D10)
# no explicit gloo config here: on current jaxlib the option already
# defaults to "gloo"; init_multihost's fallback covers builds where it
# doesn't (that branch is a no-op in this CI)

from fedml_tpu.parallel.multihost import init_multihost  # noqa: E402

init_multihost(coordinator_address=f"localhost:{port}",
               num_processes=nprocs, process_id=pid, required=True)


from tests.multihost_case import (build_blockstream_case, build_case,  # noqa: E402
                                  build_fedopt_streaming_case,
                                  build_hier_case, digest)

assert jax.device_count() == nprocs * ndev
assert jax.local_device_count() == ndev
engine = build_case()
v = engine.run()
m = engine.evaluate(v)
print(f"DIGEST {digest(v):.10e} ACC {m['test_acc']:.6f}", flush=True)

# two-tier hierarchical over one-silo-per-PROCESS: the inner FedAvg psum
# stays inside each process's devices, the silo tier crosses the boundary
h = build_hier_case(multihost=True, silos=nprocs)
hv = h.run()
hm = h.evaluate(hv)
print(f"HDIGEST {digest(hv):.10e} HACC {hm['test_acc']:.6f}", flush=True)

# streaming cohort + FedOpt server state across the boundary
s = build_fedopt_streaming_case()
sv = s.run()
sm = s.evaluate(sv)
print(f"SDIGEST {digest(sv):.10e} SACC {sm['test_acc']:.6f}", flush=True)

# block-streamed round: per-block global device_put + per-block psum of
# the accumulated linear sums, crossing the process boundary
b = build_blockstream_case()
bv = b.run()
bm = b.evaluate(bv)
print(f"BDIGEST {digest(bv):.10e} BACC {bm['test_acc']:.6f}", flush=True)
