"""Host time of ``fold_in`` + ``engine._round_args(r)``: the sampler and,
resident, the upload of the cohort's ids; streamed, the hand-over of the
prefetched cohort."""
from fedbench.layer_metrics import per_round_ms

LAYER, UNIT, SOURCE, MOVES = "host data", "ms/round", "host_clock", "rounds_per_s"


def read(ctx):
    return per_round_ms(ctx["window"]["args_s"])
