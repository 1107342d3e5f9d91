"""Device self time per round of the group-limited routing of every expert layer (``fed_moe_router``: the layer's norm, the
softmax scores over all experts, the groups' best scores, both top-k selections, the gates, the sort of the 6 x tokens slots by
expert - those of absent experts behind the held ones' -, the un-sort and the combine): the label ``moe_router_ms`` reads, under
the name this configuration's cell reports it by."""
from fedbench.harness import program_trace

LAYER, UNIT, SOURCE, MOVES = "local training", "ms/round", "device_trace", "rounds_per_s"


def read(ctx):
    return program_trace.scope_ms(ctx, "moe_router")
