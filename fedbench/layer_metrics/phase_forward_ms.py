"""Device self time per round of every op in phase ``forward``, all scopes: ops whose
``op_name`` descends from ``fed_forward`` in a part that is not wrapped in
``transpose(`` - the first run of each layer, the embedding, the head and the loss
(``fedml_tpu/obs/scopes.py::phase_of``, read through ``round_fn.phase_map()``)."""
from fedbench.harness import phase_trace

LAYER, UNIT, SOURCE, MOVES = "local training", "ms/round", "device_trace", "rounds_per_s"


def read(ctx):
    return phase_trace.phase_ms(ctx, "forward")
