"""Device self time per round of the output LayerNorm, the tied vocabulary projection over this chip's 32,768 rows and the loss on its logits
(``fed_lm_head``): the label ``lm_head_ms``, ``tied_head_ms`` and ``dsv2_head_ms`` read, under a name of this configuration's cell
(PERF.md section 7)."""
from fedbench.harness import program_trace

LAYER, UNIT, SOURCE, MOVES = "local training", "ms/round", "device_trace", "rounds_per_s"


def read(ctx):
    return program_trace.scope_ms(ctx, "lm_head") or None
