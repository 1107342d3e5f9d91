"""Host time of one ``round_fn(...)`` call returning: the enqueue only."""
from fedbench.layer_metrics import per_round_ms

LAYER, UNIT, SOURCE, MOVES = "entry", "ms/round", "host_clock", "rounds_per_s"


def read(ctx):
    return per_round_ms(ctx["window"]["dispatch_s"])
