"""Device self time per round of the grouped products over the experts held (``fed_moe_experts``: the three
products and the gate between them): forward, backward and rematerialised ops alike
(``fedml_tpu/obs/scopes.py``)."""
from fedbench.harness import program_trace

LAYER, UNIT, SOURCE, MOVES = "local training", "ms/round", "device_trace", "rounds_per_s"


def read(ctx):
    return program_trace.scope_ms(ctx, "moe_experts")
