"""Device self time per round of the clients' forward passes: ops whose
``op_name`` reads ``jvp(fed_forward)`` (model apply + loss under
``value_and_grad``, ``ClientTrainer._loss``)."""
from fedbench.harness import program_trace

LAYER, UNIT, SOURCE, MOVES = "local training", "ms/round", "device_trace", "rounds_per_s"


def read(ctx):
    return program_trace.scope_ms(ctx, "forward")
