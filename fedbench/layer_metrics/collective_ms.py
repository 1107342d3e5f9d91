"""Device time of all-reduce / all-gather / collective-permute / all-to-all
ops per round on device 0.  Exists only across chips."""
LAYER, UNIT, SOURCE, MOVES = "carry exchange", "ms/round", "device_trace", "rounds_per_s"


def read(ctx):
    tr = ctx["trace"]
    if tr is None or ctx["cell"].chips < 2 or not tr.get("rounds"):
        return None
    return 1e3 * tr["collective_s"] / tr["rounds"]
