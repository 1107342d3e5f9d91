"""Device self time per round of the vocabulary head and the loss on its logits (``fed_lm_head``): forward, backward and rematerialised
ops alike (``fedml_tpu/obs/scopes.py``)."""
from fedbench.harness import program_trace

LAYER, UNIT, SOURCE, MOVES = "local training", "ms/round", "device_trace", "rounds_per_s"


def read(ctx):
    return program_trace.scope_ms(ctx, "lm_head")
