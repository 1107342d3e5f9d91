"""The least time the chip could take for a round's grouped expert products over
``moe_experts_ms``.

Least time = max(FLOPs / peak FLOP/s, bytes / peak B/s), both from the
configuration's plain reference (``expert_flops``, ``expert_bytes``): three
products for each of a token's experts, forward and with respect to
activations (the experts are frozen: no weight gradient), and every held
expert's matrices read once forward and once backward per local step of each
group of clients the program trains side by side (``engine.chunk``: a chunk
shares one read).  Recomputation is not counted, so the share cannot pass 100."""
import numpy as np

from fedbench import reference
from fedbench.harness import peaks, program_trace

LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "rounds_per_s"


def read(ctx):
    ms = program_trace.scope_ms(ctx, "moe_experts")
    ref = reference.resolve(ctx["cell"].config["reference"])
    if not ms or not hasattr(ref, "expert_flops") or not ctx["window"]["attempted"]:
        return None
    traffic, data = ctx["cell"].traffic, ctx["data"]
    tokens = (ctx["samples"] / ctx["window"]["attempted"]
              * data.client_shards["x"].shape[-1])
    cohort, bs = int(traffic["cohort"]), int(traffic["batch_size"])
    steps = int(traffic["epochs"]) * float(np.mean(np.ceil(data.client_num_samples / bs)))
    reads = steps * np.ceil(cohort / min(ctx["engine"].chunk, cohort))
    pk = peaks.peaks(ctx["device"]["kind"])
    least = max(ref.expert_flops(ctx["params"], tokens) / pk["flops_per_s"],
                ref.expert_bytes(ctx["params"], reads) / pk["bytes_per_s"])
    return 100.0 * least / (ms / 1e3)
