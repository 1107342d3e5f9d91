"""The least time the chip could take for one round over ``round_busy_ms``.

Least time = max(FLOPs / peak FLOP/s, bytes / peak B/s) per chip, with
    FLOPs = 3 x forward FLOPs x real samples of a round (fedbench/harness/flops.py)
    bytes = per local step, the client's weights read forward and backward and
            read + written by the update, in the local dtype; activations and
            the cohort's data are not counted, so the byte bound is a floor.
``ctx["roofline_bound"]`` says which of the two bounds it."""
from fedbench.harness import flops, peaks

LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "rounds_per_s"


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr.get("round_busy_ms"):
        return None
    need = flops.round_needs(ctx)
    pk = peaks.peaks(ctx["device"]["kind"])
    chips = ctx["cell"].chips
    t_flops = need["flops"] / pk["flops_per_s"] / chips
    t_bytes = need["bytes"] / pk["bytes_per_s"] / chips
    ctx["roofline_bound"] = "FLOP/s" if t_flops >= t_bytes else "B/s"
    return 100.0 * max(t_flops, t_bytes) / (tr["round_busy_ms"] / 1e3)
