"""95th percentile of the interval between successive round completions;
needs 200 intervals so that ten samples lie beyond it."""
import numpy as np

LAYER, UNIT, SOURCE, MOVES = "end to end", "ms", "host_clock", None
MIN_INTERVALS = 200


def read(ctx):
    t = ctx["window"]["done_t"]
    gaps = [b - a for a, b in zip(t, t[1:])]
    if len(gaps) < MIN_INTERVALS:
        return None
    return 1e3 * float(np.percentile(gaps, 95))
