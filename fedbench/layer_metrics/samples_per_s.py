"""Real (mask = 1) client training samples consumed per second."""
LAYER, UNIT, SOURCE, MOVES = "end to end", "samples/s", "host_clock", None


def read(ctx):
    win = ctx["window"]
    return ctx["samples"] / win["elapsed_s"] if win["elapsed_s"] > 0 else None
