"""Device self time per round of the expert layers' routing (``fed_moe_router``: float32 sigmoid scores over all 128 experts, the top-8, the
gates' renormalisation, the count, the sort of the 8 x tokens slots with the absent experts' behind the held ones', un-sort, combine): the label
``moe_router_ms`` and ``group_router_ms`` read, under a name of this configuration's cell because those entries list other cells and an
accepted entry is not edited (PERF.md section 7)."""
from fedbench.harness import program_trace

LAYER, UNIT, SOURCE, MOVES = "local training", "ms/round", "device_trace", "rounds_per_s"


def read(ctx):
    return program_trace.scope_ms(ctx, "moe_router") or None
