"""Device self time per round of the expert layers' routing (``fed_moe_router``: the post norm, float32 sigmoid scores over all 64
experts, the bias, the top-4, the gates' normalisation, the count, the sort of the 4 x tokens slots with the absent experts' behind
the held ones', and for the held ones' rows gather and combine): the label ``moe_router_ms`` reads, under a name of this
configuration's cell (PERF.md section 7)."""
from fedbench.harness import program_trace

LAYER, UNIT, SOURCE, MOVES = "local training", "ms/round", "device_trace", "rounds_per_s"


def read(ctx):
    return program_trace.scope_ms(ctx, "moe_router") or None
