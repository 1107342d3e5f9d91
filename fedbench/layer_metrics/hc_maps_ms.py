"""Device self time per round of the hyper-connections' maps (``fed_hc_maps``: the RMS over a token's four streams, the 24-wide
projection, the sigmoids, the 2 x 20 Sinkhorn normalisations and their backward passes): forward, backward and rematerialised ops alike
(``fedml_tpu/obs/scopes.py``).  A program without the scope reads as nothing."""
from fedbench.harness import program_trace

LAYER, UNIT, SOURCE, MOVES = "local training", "ms/round", "device_trace", "rounds_per_s"


def read(ctx):
    return program_trace.scope_ms(ctx, "hc_maps") or None
