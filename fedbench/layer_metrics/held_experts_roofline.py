"""The least time the chip could take for a round's grouped products over the
experts held here over the device time of the grouped-product kernels themselves.

The count is ``moe_experts_roofline``'s, from the configuration's plain reference
(``expert_flops``, ``expert_bytes``): three products for each of a token's
experts that is held here (the held share of the 6), forward and with respect to
activations, and every held expert's matrices read once forward and once
backward per local step of each group of clients the program trains side by side
(``engine.chunk``).  The time is not that metric's whole ``fed_moe_experts``
label but the device self time of the custom calls under it
(``fedbench/harness/kernel_trace.py``: XLA:TPU's ``ragged-dot`` kernels), without
the gate, the casts and the relayout copies that ``held_experts_ms`` also holds.
The checkpoint's re-run of the three forward products is in the time and not in
the count, so the share cannot pass 100."""
from fedbench.harness import kernel_trace, program_trace
from fedbench.layer_metrics import moe_experts_roofline

LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "rounds_per_s"


def read(ctx):
    kernels = kernel_trace.kernel_ms(ctx, "moe_experts")
    if not kernels:
        return None
    over_the_label = moe_experts_roofline.read(ctx)
    if over_the_label is None:
        return None
    return over_the_label * program_trace.scope_ms(ctx, "moe_experts") / kernels
