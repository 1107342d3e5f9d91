"""Device self time per round of latent attention's low-rank side (``fed_mla_latent``: the input norm, the 768-wide query and 512 + 64
wide key/value compressions, both latent norms, the expansions to 32 heads, the rotary embedding and their adapters): the label
``mla_latent_ms`` reads, under a name of this configuration's cell because that entry lists another cell and an accepted entry is not
edited (PERF.md section 7)."""
from fedbench.harness import program_trace

LAYER, UNIT, SOURCE, MOVES = "local training", "ms/round", "device_trace", "rounds_per_s"


def read(ctx):
    return program_trace.scope_ms(ctx, "mla_latent") or None
