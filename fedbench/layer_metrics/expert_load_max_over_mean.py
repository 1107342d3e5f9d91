"""The busiest expert's routed tokens over the mean expert's, in the worst expert
layer, over the window's rounds: 1 is a balanced router, the number of experts
all tokens on one.  The round program counts the tokens it routes to every
(expert layer, expert) and the engine keeps the sums
(``engine.transfer_stats.program_counters()``, reset at the window's start); a
program that keeps no such count reads as nothing."""
import numpy as np

LAYER, UNIT, SOURCE, MOVES = "local training", "x", "program_counter", "rounds_per_s"


def read(ctx):
    stats = getattr(ctx["engine"], "transfer_stats", None)
    read_counters = getattr(stats, "program_counters", None)
    tokens = read_counters().get("moe_expert_tokens") if read_counters else None
    if tokens is None or not np.sum(tokens):
        return None
    return float(np.max(np.max(tokens, axis=-1) / np.mean(tokens, axis=-1)))
