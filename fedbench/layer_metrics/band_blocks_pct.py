"""Key blocks the windowed attention calls visit over the key blocks on and below the
diagonal, in tiles of scores, as the program counted them when it traced the calls
(``attention_band_blocks_total{blocks="visited" | "causal"}``, ``fedml_tpu/ops/attention.py``:
a count over every windowed call of the process that took the fused path, so the check's
float32 round is in it at the same ratio).  100 would be a kernel that computes a sliding
layer as a full one; at T = 8,192 with tiles of 512 and a window of 4,096 the band is 108
of 136 blocks, 79.4.  A program that keeps no such count - or traced no windowed call on
the fused path, as on a CPU - reads as nothing."""
LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "program_counter", "rounds_per_s"


def read(ctx):
    try:
        from fedml_tpu import obs
    except ImportError:
        return None
    count = lambda blocks: obs.counter("attention_band_blocks_total", blocks=blocks).value
    return 100.0 * count("visited") / count("causal") if count("causal") else None
