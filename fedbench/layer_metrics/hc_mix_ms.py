"""Device self time per round of the hyper-connections' mixing (``fed_hc_mix``: the sublayer's input ``u = H_pre X`` read from the
four streams and the streams written back, ``X' = H_res X + H_post^T F(u)``, with their backward passes): forward, backward and
rematerialised ops alike (``fedml_tpu/obs/scopes.py``).  A program without the scope reads as nothing."""
from fedbench.harness import program_trace

LAYER, UNIT, SOURCE, MOVES = "local training", "ms/round", "device_trace", "rounds_per_s"


def read(ctx):
    return program_trace.scope_ms(ctx, "hc_mix") or None
