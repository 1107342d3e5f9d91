"""Share of the window's routed token slots that belong to experts held here: tokens
routed to the held experts / all tokens routed (8 x tokens x layers: the router scores
all 128 experts, the chip computes its own experts' part).  With 8 of 128 held and a
seeded router it is near 6.25; a router that favoured or starved this chip's experts
would show here first.  The count is ``held_slot_pct``'s (``moe_expert_tokens``, summed by
``engine.transfer_stats.program_counters()``, reset at the window's start); which experts
are held is the configuration's, here ``model.kwargs.held`` = (first, PAST-LAST), as
``models/cohere2_moe.py`` takes it.  A program that keeps no such count, or a
configuration that holds every expert, reads as nothing."""
import numpy as np

LAYER, UNIT, SOURCE, MOVES = "local training", "%", "program_counter", "rounds_per_s"


def read(ctx):
    stats = getattr(ctx["engine"], "transfer_stats", None)
    read_counters = getattr(stats, "program_counters", None)
    tokens = read_counters().get("moe_expert_tokens") if read_counters else None
    held = ctx["cell"].config.get("model", {}).get("kwargs", {}).get("held")
    if tokens is None or not held or not np.sum(tokens):
        return None
    first, last = held
    return 100.0 * float(np.sum(tokens[:, first:last]) / np.sum(tokens))
