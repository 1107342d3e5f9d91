"""Device self time per round of the cohort take: the ops traced under ``fed_take``
(``jnp.take`` of the cohort and its weights, the sharding constraint, the
compiler's data movement that feeds them) plus the ops outside every ``while``
whose result or operand leads with the resident stack's ``[clients, batches`` -
the convert and relayout passes over the WHOLE resident stack that XLA hoists
out of local training.  ``fedbench/harness/program_trace.py`` says which rule counted what
(``take_ops`` in ``program_trace.json`` beside the trace)."""
from fedbench.harness import program_trace

LAYER, UNIT, SOURCE, MOVES = "cohort take", "ms/round", "device_trace", "rounds_per_s"


def read(ctx):
    return program_trace.scope_ms(ctx, "take")
