"""Device time of one round program: the union of the device-op intervals
inside one execution of the round's XLA module, median over the traced
rounds, on device 0.  Local training, aggregation and server update are one
layer until the program has scopes."""
LAYER, UNIT, SOURCE, MOVES = "local training", "ms/round", "device_trace", "rounds_per_s"


def read(ctx):
    tr = ctx["trace"]
    return None if tr is None else tr.get("round_busy_ms")
