"""Device self time per round of the output norm, the tied vocabulary projection and the loss on its logits
(``fed_lm_head``): the label ``lm_head_ms`` reads, under the name this configuration's cell reports it by."""
from fedbench.harness import program_trace

LAYER, UNIT, SOURCE, MOVES = "local training", "ms/round", "device_trace", "rounds_per_s"


def read(ctx):
    return program_trace.scope_ms(ctx, "lm_head")
