"""Device self time per round of every op in phase ``recompute``, all scopes: what
``jax.checkpoint`` costs.  Ops under ``transpose(jvp(fed_forward))`` with a later
``rematted_computation`` in their ``op_name``: the forward pass of a checkpointed layer
run again inside the backward pass.  A fusion has its root's phase, so this is exact for
matrix products and custom calls and a floor for elementwise work
(``fedml_tpu/obs/scopes.py::phase_of``, read through ``round_fn.phase_map()``)."""
from fedbench.harness import phase_trace

LAYER, UNIT, SOURCE, MOVES = "local training", "ms/round", "device_trace", "rounds_per_s"


def read(ctx):
    return phase_trace.phase_ms(ctx, "recompute")
