"""``memory_stats()["peak_bytes_in_use"]`` after the window, fullest chip."""
LAYER, UNIT, SOURCE, MOVES = "device", "GB", "program_counter", "rounds_per_s"


def read(ctx):
    return ctx["memory_peak_bytes"] / 1e9 if ctx["memory_peak_bytes"] else None
