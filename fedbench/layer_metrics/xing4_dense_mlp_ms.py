"""Device self time per round of the two leading dense layers' gated MLP of width 9,216 with its norm (``fed_mlp``): the label
``mlp_ms`` reads, under a name of this configuration's cell (PERF.md section 7)."""
from fedbench.harness import program_trace

LAYER, UNIT, SOURCE, MOVES = "local training", "ms/round", "device_trace", "rounds_per_s"


def read(ctx):
    return program_trace.scope_ms(ctx, "mlp") or None
