"""Device self time per round of the aggregation: ``fed_aggregate`` (the client
transform and the fold of w.v into the flat carry inside the chunk scan, the
psums, the weighted mean), collectives included."""
from fedbench.harness import program_trace

LAYER, UNIT, SOURCE, MOVES = "aggregation", "ms/round", "device_trace", "rounds_per_s"


def read(ctx):
    return program_trace.scope_ms(ctx, "aggregate")
