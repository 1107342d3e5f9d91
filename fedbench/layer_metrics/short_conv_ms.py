"""Device self time per round of the gated short-convolution mixers (``fed_short_conv``: operator norm,
``in_proj``, both gates, the depthwise causal convolution, ``out_proj``, their adapters): forward, backward and
rematerialised ops alike (``fedml_tpu/obs/scopes.py``); nothing where the program has no such scope."""
from fedbench.harness import program_trace

LAYER, UNIT, SOURCE, MOVES = "local training", "ms/round", "device_trace", "rounds_per_s"


def read(ctx):
    return program_trace.scope_ms(ctx, "short_conv")
