"""Device self time per round of the one shared expert (``fed_shared_expert``: the 1,024-wide gated MLP every token of an expert
layer visits, and its sum with the routed part): the label ``shared_expert_ms`` reads, under a name of this configuration's cell
(PERF.md section 7)."""
from fedbench.harness import program_trace

LAYER, UNIT, SOURCE, MOVES = "local training", "ms/round", "device_trace", "rounds_per_s"


def read(ctx):
    return program_trace.scope_ms(ctx, "shared_expert") or None
