"""Device self time per round of the four shared experts every token visits, stored side by side as one 16,384-wide gated MLP, and their
average's sum with the routed part (``fed_shared_expert``): the label ``shared_expert_ms`` reads, under a name of this configuration's cell
(PERF.md section 7)."""
from fedbench.harness import program_trace

LAYER, UNIT, SOURCE, MOVES = "local training", "ms/round", "device_trace", "rounds_per_s"


def read(ctx):
    return program_trace.scope_ms(ctx, "shared_expert") or None
