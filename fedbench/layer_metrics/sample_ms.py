"""Median host time of ``round.sample``: ``sampler.sample`` + ``pad_ids``
(``engine._sample_padded_np``), on the profiler's clock."""
from fedbench.harness import program_trace

LAYER, UNIT, SOURCE, MOVES = "host data", "ms/round", "program_span", "rounds_per_s"


def read(ctx):
    return program_trace.span_ms(ctx, "round.sample")
