"""The least time the chip could take for a round's sliding-window attention cores over
the device time of the fused attention kernels of the sliding layers.

Least time = max(FLOPs / peak FLOP/s, bytes / peak B/s), both from the configuration's
plain reference (``core_flops``, ``core_bytes``, the sliding layers' alone) at the cell's
shapes: forward ``q k^T`` and ``p v``; backward ``p`` again, ``dv``, ``dp``, ``dk``,
``dq`` - seven products over the pairs INSIDE THE BAND (key j, query i, 0 <= i - j <
window: the same count whatever implements it), operands read and results written once.
The kernel time is the device self time of the custom calls under
``fed_window_attention`` (``fedbench/harness/kernel_trace.py``): the forward kernel, its
re-run inside the backward pass where the layer's checkpoint keeps no output, and the
backward kernel.  A re-run, the tiles the diagonal crosses and the tiles the band's far
edge crosses, which the kernels compute whole, are in the time and not in the count, so
the share cannot pass 100; a kernel that visited the blocks outside the band would read
a quarter lower at T = 8,192.  A program without the scope reads as nothing."""
from fedbench import reference
from fedbench.harness import kernel_trace, peaks

LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "rounds_per_s"


def read(ctx):
    ms = kernel_trace.kernel_ms(ctx, "window_attention")
    ref = reference.resolve(ctx["cell"].config["reference"])
    if not ms or not hasattr(ref, "SLIDING") or not ctx["window"]["attempted"]:
        return None
    t = ctx["data"].client_shards["x"].shape[-1]
    tokens = ctx["samples"] / ctx["window"]["attempted"] * t
    compute = ctx["cell"].config["trainer"].get("train_dtype", "float32")
    itemsize = 2 if compute == "bfloat16" else 4
    pk = peaks.peaks(ctx["device"]["kind"])
    sliding = (ref.SLIDING,)
    least = max(ref.core_flops(ctx["params"], tokens, t, kinds=sliding) / pk["flops_per_s"],
                ref.core_bytes(ctx["params"], tokens, itemsize, kinds=sliding) / pk["bytes_per_s"])
    return 100.0 * least / (ms / 1e3)
