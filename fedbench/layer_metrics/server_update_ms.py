"""Device self time per round of ``engine.server_update`` (``fed_server_update``);
FedAvg installs the average, so it reads 0 there."""
from fedbench.harness import program_trace

LAYER, UNIT, SOURCE, MOVES = "server update", "ms/round", "device_trace", "rounds_per_s"


def read(ctx):
    return program_trace.scope_ms(ctx, "server_update")
