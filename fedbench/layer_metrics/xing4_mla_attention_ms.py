"""Device self time per round of latent attention's core and its output projection (``fed_attention``: the fused two-part causal
attention on 32 heads, forward and backward kernels, ``delta``, the sum of the shared rotary key's gradient over the heads, the layout
changes of the narrow rotary operands, ``W_o`` and its adapter): the label ``attention_ms`` and ``mla_attention_ms`` read, under a name
of this configuration's cell (PERF.md section 7)."""
from fedbench.harness import program_trace

LAYER, UNIT, SOURCE, MOVES = "local training", "ms/round", "device_trace", "rounds_per_s"


def read(ctx):
    return program_trace.scope_ms(ctx, "attention") or None
