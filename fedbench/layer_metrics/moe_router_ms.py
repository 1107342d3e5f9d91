"""Device self time per round of the expert layers' routing (``fed_moe_router``: the layer's norm, scores, bias,
top-k, the gate's normalisation, the sort of token slots by expert, the un-sort and the combine): forward,
backward and rematerialised ops alike (``fedml_tpu/obs/scopes.py``)."""
from fedbench.harness import program_trace

LAYER, UNIT, SOURCE, MOVES = "local training", "ms/round", "device_trace", "rounds_per_s"


def read(ctx):
    return program_trace.scope_ms(ctx, "moe_router")
