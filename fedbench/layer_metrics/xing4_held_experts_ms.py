"""Device self time per round of the grouped products over the 16 experts held here (``fed_moe_experts``: the ``ragged_dot``s on a
block's rows, the gate between them, the compiler's relayout copies): the label ``moe_experts_ms`` reads, under a name of this
configuration's cell (PERF.md section 7)."""
from fedbench.harness import program_trace

LAYER, UNIT, SOURCE, MOVES = "local training", "ms/round", "device_trace", "rounds_per_s"


def read(ctx):
    return program_trace.scope_ms(ctx, "moe_experts") or None
