"""Device self time per round of the grouped products over the 8 experts HELD here of each layer's 128 (``fed_moe_experts``: the three
products, the gate between them, and what the compiler puts around them, its relayout copy of the expert matrices included): the label
``moe_experts_ms`` and ``held_experts_ms`` read, under a name of this configuration's cell (PERF.md section 7)."""
from fedbench.harness import program_trace

LAYER, UNIT, SOURCE, MOVES = "local training", "ms/round", "device_trace", "rounds_per_s"


def read(ctx):
    return program_trace.scope_ms(ctx, "moe_experts") or None
