"""How far the columns of the hyper-connections' residual maps are from summing to 1 after the last Sinkhorn iteration: the largest
``|colsum(H_res) - 1|`` over a local step's tokens, averaged over the window's steps, in the worst (layer, sublayer).  The model sows
a step's maximum (``hc_sinkhorn_err`` ``[held layers, 2 sublayers, 2]``: rows, columns - the last pass normalises rows, so the row
part reads ``hc_eps`` and rounding and the column part is the informative one), the trainer and the engine SUM counters over steps,
clients and rounds (``engine.transfer_stats.program_counters()``, reset at the window's start), and this reader divides by the steps
it counts from the cell's traffic: rounds dispatched x cohort x epochs x batches a client.  0 is a doubly-stochastic map; it grows
when 20 iterations no longer suffice for the maps the weights give.  A program that keeps no such count reads as nothing."""
import numpy as np

LAYER, UNIT, SOURCE, MOVES = "local training", "abs", "program_counter", "rounds_per_s"


def read(ctx):
    stats = getattr(ctx["engine"], "transfer_stats", None)
    read_counters = getattr(stats, "program_counters", None)
    err = read_counters().get("hc_sinkhorn_err") if read_counters else None
    if err is None or not ctx["window"]["attempted"]:
        return None
    traffic, sizes = ctx["cell"].traffic, ctx["data"].client_num_samples
    steps = (ctx["window"]["attempted"] * int(traffic["cohort"]) * int(traffic["epochs"])
             * float(np.mean(np.ceil(sizes / int(traffic["batch_size"])))))
    return float(np.max(np.asarray(err)[..., 1]) / steps)
