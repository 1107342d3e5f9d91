"""Device self time per round of the shared experts (``fed_shared_expert``: the gated MLP of twice the expert width that every
token of an expert layer visits, and its sum with the routed part): forward, backward and rematerialised ops alike
(``fedml_tpu/obs/scopes.py``)."""
from fedbench.harness import program_trace

LAYER, UNIT, SOURCE, MOVES = "local training", "ms/round", "device_trace", "rounds_per_s"


def read(ctx):
    return program_trace.scope_ms(ctx, "shared_expert")
