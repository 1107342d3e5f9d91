"""Device self time per round of latent attention's low-rank side (``fed_mla_latent``: the input norm, the query and
key/value compressions, both latent norms, the expansions to heads, the rotary embedding and their adapters): forward, backward and
rematerialised ops alike (``fedml_tpu/obs/scopes.py``)."""
from fedbench.harness import program_trace

LAYER, UNIT, SOURCE, MOVES = "local training", "ms/round", "device_trace", "rounds_per_s"


def read(ctx):
    return program_trace.scope_ms(ctx, "mla_latent")
