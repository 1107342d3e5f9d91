"""Committed global rounds per second over the window."""
LAYER, UNIT, SOURCE, MOVES = "end to end", "rounds/s", "host_clock", None


def read(ctx):
    win = ctx["window"]
    return ctx["completed"] / win["elapsed_s"] if win["elapsed_s"] > 0 else None
