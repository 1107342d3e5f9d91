"""Everything before the window: imports, data, engine, placement, the
reference check, compilation (or the cache look-up) and warm-up."""
LAYER, UNIT, SOURCE, MOVES = "end to end", "s", "host_clock", None


def read(ctx):
    return ctx["setup_s"]
