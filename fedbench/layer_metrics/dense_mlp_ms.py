"""Device self time per round of the leading dense layer's gated MLP with its norm (``fed_mlp``): the label
``mlp_ms`` reads, under the name this configuration's cell reports it by."""
from fedbench.harness import program_trace

LAYER, UNIT, SOURCE, MOVES = "local training", "ms/round", "device_trace", "rounds_per_s"


def read(ctx):
    return program_trace.scope_ms(ctx, "mlp")
