"""Device time of copy and data-formatting ops over busy time, device 0."""
LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "rounds_per_s"


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr["busy_s"]:
        return None
    return 100.0 * tr["copy_s"] / tr["device0_busy_s"]
