"""Share of the window's routed token slots that belong to experts held here:
tokens routed to the held experts / all tokens routed (6 x tokens x expert
layers: the router scores every expert, the chip computes its own experts' part).
With one routing group of eight held and a seeded router it is near 12.5; a
router that favoured or starved this chip's group would show here first.  The
round program counts the tokens it routes to every (expert layer, expert), held
or not, and the engine keeps the sums
(``engine.transfer_stats.program_counters()``, reset at the window's start); which
experts are held is the configuration's (``model.kwargs.held`` = first, how
many).  A program that keeps no such count, or a configuration that holds every
expert, reads as nothing."""
import numpy as np

LAYER, UNIT, SOURCE, MOVES = "local training", "%", "program_counter", "rounds_per_s"


def read(ctx):
    stats = getattr(ctx["engine"], "transfer_stats", None)
    read_counters = getattr(stats, "program_counters", None)
    tokens = read_counters().get("moe_expert_tokens") if read_counters else None
    held = ctx["cell"].config.get("model", {}).get("kwargs", {}).get("held")
    if tokens is None or not held or not np.sum(tokens):
        return None
    first, n = held
    return 100.0 * float(np.sum(tokens[:, first:first + n]) / np.sum(tokens))
