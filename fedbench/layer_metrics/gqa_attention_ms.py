"""Device self time per round of grouped-query attention (``fed_attention``: operator norm, projections with their
adapters, per-head q/k norms, rotary, scores, softmax, output): the label ``attention_ms`` reads, under the name
this configuration's cell reports it by."""
from fedbench.harness import program_trace

LAYER, UNIT, SOURCE, MOVES = "local training", "ms/round", "device_trace", "rounds_per_s"


def read(ctx):
    return program_trace.scope_ms(ctx, "attention")
