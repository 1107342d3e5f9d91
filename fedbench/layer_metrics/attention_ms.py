"""Device self time per round of a transformer block's attention (``fed_attention``: both norms, the
projections, rotary, scores, softmax, output): forward, backward and rematerialised
ops alike (``fedml_tpu/obs/scopes.py``)."""
from fedbench.harness import program_trace

LAYER, UNIT, SOURCE, MOVES = "local training", "ms/round", "device_trace", "rounds_per_s"


def read(ctx):
    return program_trace.scope_ms(ctx, "attention")
