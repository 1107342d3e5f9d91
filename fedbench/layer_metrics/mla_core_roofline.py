"""The least time the chip could take for a round's attention cores over the
device time of the fused attention kernels themselves.

Least time = max(FLOPs / peak FLOP/s, bytes / peak B/s), both from the
configuration's plain reference (``core_flops``, ``core_bytes``) at the cell's
shapes: forward ``q k^T`` (as deep as the keys: content and rotary parts
together) and ``p v``; backward ``p`` again, ``dv``, ``dp``, ``dk``, ``dq`` -
the mathematics of one forward and one backward pass over the causal half of
every [T, T] square, operands read and results written once.  The kernel time
is the device self time of the custom calls under ``fed_attention``
(``fedbench/harness/kernel_trace.py``): the forward kernel, its re-run inside
the backward pass (each layer is a ``jax.checkpoint``) and the backward kernel.
The re-run and the tiles on the diagonal, which the kernels compute whole, are
in the time and not in the count, so the share cannot pass 100."""
from fedbench import reference
from fedbench.harness import kernel_trace, peaks

LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "rounds_per_s"


def read(ctx):
    ms = kernel_trace.kernel_ms(ctx, "attention")
    ref = reference.resolve(ctx["cell"].config["reference"])
    if not ms or not hasattr(ref, "core_flops") or not ctx["window"]["attempted"]:
        return None
    t = ctx["data"].client_shards["x"].shape[-1]
    tokens = ctx["samples"] / ctx["window"]["attempted"] * t
    compute = ctx["cell"].config["trainer"].get("train_dtype", "float32")
    itemsize = 2 if compute == "bfloat16" else 4
    pk = peaks.peaks(ctx["device"]["kind"])
    least = max(ref.core_flops(ctx["params"], tokens, t) / pk["flops_per_s"],
                ref.core_bytes(ctx["params"], tokens, itemsize) / pk["bytes_per_s"])
    return 100.0 * least / (ms / 1e3)
