"""Device self time per round of the leading dense layer's gated MLP with its norm (``fed_mlp``): the label ``mlp_ms`` and
``dense_mlp_ms`` read, under a name of this configuration's cell because those entries list other cells and an accepted entry
is not edited (merging the names is a benchmark PR's: PERF.md section 7)."""
from fedbench.harness import program_trace

LAYER, UNIT, SOURCE, MOVES = "local training", "ms/round", "device_trace", "rounds_per_s"


def read(ctx):
    return program_trace.scope_ms(ctx, "mlp")
