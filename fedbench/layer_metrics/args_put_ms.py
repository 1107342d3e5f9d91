"""Median host time of ``round.args_put``: ``jnp.asarray`` of the cohort's ids and
mask, the resident round's only per-round host-to-device put."""
from fedbench.harness import program_trace

LAYER, UNIT, SOURCE, MOVES = "host data", "ms/round", "program_span", "rounds_per_s"


def read(ctx):
    return program_trace.span_ms(ctx, "round.args_put")
