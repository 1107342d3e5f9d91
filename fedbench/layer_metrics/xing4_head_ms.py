"""Device self time per round of the sum of the four streams, the output norm, the untied vocabulary projection over this chip's
32,768 rows and the loss on its logits (``fed_lm_head``): the label ``lm_head_ms`` reads, under a name of this configuration's cell
(PERF.md section 7)."""
from fedbench.harness import program_trace

LAYER, UNIT, SOURCE, MOVES = "local training", "ms/round", "device_trace", "rounds_per_s"


def read(ctx):
    return program_trace.scope_ms(ctx, "lm_head") or None
