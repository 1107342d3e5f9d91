"""Device self time per round of the client step: ``fed_optimizer``
(``tx.update``, ``apply_updates``, the has-data selects in ``train_step``)."""
from fedbench.harness import program_trace

LAYER, UNIT, SOURCE, MOVES = "local training", "ms/round", "device_trace", "rounds_per_s"


def read(ctx):
    return program_trace.scope_ms(ctx, "optimizer")
