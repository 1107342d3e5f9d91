"""Device self time per round of the grouped products over the experts HELD here, one routing group of the published eight
(``fed_moe_experts``: the three products, the gate between them, and what the compiler puts around them, its relayout copy of the
expert matrices included): the label ``moe_experts_ms`` reads, under the name this configuration's cell reports it by."""
from fedbench.harness import program_trace

LAYER, UNIT, SOURCE, MOVES = "local training", "ms/round", "device_trace", "rounds_per_s"


def read(ctx):
    return program_trace.scope_ms(ctx, "moe_experts")
