"""Device self time per round of the output norm, the untied vocabulary projection over this chip's slice and the loss on its
logits (``fed_lm_head``): the label ``lm_head_ms`` and ``tied_head_ms`` read, under a name of this configuration's cell because
those entries list other cells and an accepted entry is not edited (PERF.md section 7)."""
from fedbench.harness import program_trace

LAYER, UNIT, SOURCE, MOVES = "local training", "ms/round", "device_trace", "rounds_per_s"


def read(ctx):
    return program_trace.scope_ms(ctx, "lm_head")
