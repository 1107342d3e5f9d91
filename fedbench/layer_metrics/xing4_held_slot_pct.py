"""``held_slot_pct``'s reading in this configuration's cell: tokens the window routed to the 16 experts held here / all it routed
(4 x tokens x 8 expert layers; ``model.kwargs.held`` = first, how many); 25 is a quarter.  That reader is called, not copied; its
entry lists another cell and an accepted entry is not edited (PERF.md section 7)."""
from fedbench.layer_metrics import held_slot_pct

LAYER, UNIT, SOURCE, MOVES = "local training", "%", "program_counter", "rounds_per_s"


def read(ctx):
    return held_slot_pct.read(ctx)
