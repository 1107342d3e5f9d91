"""Device self time per round of every op in phase ``backward``, all scopes: ops under
``transpose(jvp(fed_forward))`` with no ``rematted_computation`` after it in their
``op_name`` - the gradient's own work, hand-written backward rules included
(``fedml_tpu/obs/scopes.py::phase_of``, read through ``round_fn.phase_map()``)."""
from fedbench.harness import phase_trace

LAYER, UNIT, SOURCE, MOVES = "local training", "ms/round", "device_trace", "rounds_per_s"


def read(ctx):
    return phase_trace.phase_ms(ctx, "backward")
