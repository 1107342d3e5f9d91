"""Device self time per round of the clients' backward passes: ops whose
``op_name`` reads ``transpose(jvp(fed_forward))``."""
from fedbench.harness import program_trace

LAYER, UNIT, SOURCE, MOVES = "local training", "ms/round", "device_trace", "rounds_per_s"


def read(ctx):
    return program_trace.scope_ms(ctx, "backward")
