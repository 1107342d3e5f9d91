"""Median host time of ``program.dispatch`` of the round's program family: the
wrapped jit call inside ``InstrumentedProgram.__call__``, enqueue only."""
from fedbench.harness import program_trace

LAYER, UNIT, SOURCE, MOVES = "entry", "ms/round", "program_span", "rounds_per_s"


def read(ctx):
    return program_trace.span_ms(ctx, "program.dispatch")
