"""Device self time per round of the sliding-window layers' attention (``fed_window_attention``: the parallel block's one LayerNorm, the
q, k, v projections with their adapters, the rotary on all 128 + 8 heads, the fused core under its window - forward and backward kernels,
``delta``, the sum of ``dk`` / ``dv`` over a group's 16 query heads - and ``W_o`` with its adapter), all three passes.  A program without the
scope reads as nothing."""
from fedbench.harness import program_trace

LAYER, UNIT, SOURCE, MOVES = "local training", "ms/round", "device_trace", "rounds_per_s"


def read(ctx):
    return program_trace.scope_ms(ctx, "window_attention") or None
