"""The least time the chip could take to move a round's hyper-connection streams over the device time the program spends
on the hyper-connections.

Least time = ``hc_bytes`` / peak B/s, from the configuration's plain reference at the cell's shapes: a token of one sublayer reads
its four streams, writes the sublayer's input, reads the sublayer's output and writes the four streams forward - (2 n + 2) C values -
and backward reads the streams, their gradient, the output and the input's gradient and writes the output's and the streams'
gradients - (3 n + 3) C -, every operand once, in the stream's dtype.  The time is the device self time of BOTH labels,
``fed_hc_maps`` and ``fed_hc_mix`` (``hc_maps_ms`` + ``hc_mix_ms``): the maps' projection reads the streams too, and a fused pass
would do both in one reading.  The checkpoint's re-run, the Sinkhorn loop and every second reading are in the time and not in the
count, so the share cannot pass 100.  A program without the scopes, or a reference without the count, reads as nothing."""
from fedbench import reference
from fedbench.harness import peaks, program_trace

LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "rounds_per_s"


def read(ctx):
    maps, mix = (program_trace.scope_ms(ctx, label) for label in ("hc_maps", "hc_mix"))
    ref = reference.resolve(ctx["cell"].config["reference"])
    if not maps or not mix or not hasattr(ref, "hc_bytes") or not ctx["window"]["attempted"]:
        return None
    t = ctx["data"].client_shards["x"].shape[-1]
    tokens = ctx["samples"] / ctx["window"]["attempted"] * t
    compute = ctx["cell"].config["trainer"].get("train_dtype", "float32")
    itemsize = 2 if compute == "bfloat16" else 4
    least = ref.hc_bytes(ctx["params"], tokens, itemsize) / peaks.peaks(ctx["device"]["kind"])["bytes_per_s"]
    return 100.0 * least / ((maps + mix) / 1e3)
