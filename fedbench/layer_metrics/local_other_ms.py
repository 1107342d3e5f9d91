"""Device self time per round under ``fed_local_train`` that is neither forward,
backward nor optimizer: chunking, the scans themselves, batch slicing, casts,
rng, carry copies, the flat_stack restore."""
from fedbench.harness import program_trace

LAYER, UNIT, SOURCE, MOVES = "local training", "ms/round", "device_trace", "rounds_per_s"


def read(ctx):
    return program_trace.scope_ms(ctx, "local_other")
