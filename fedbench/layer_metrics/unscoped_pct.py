"""Share of the round's device self time that no scope claims: ops traced under
no ``fed_*`` scope and not reached by the take's rule.  The guard that the
per-scope split is whole."""
from fedbench.harness import program_trace

LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "rounds_per_s"


def read(ctx):
    unscoped = program_trace.scope_ms(ctx, "unscoped")
    if unscoped is None:
        return None
    return 100.0 * unscoped / ctx["program_trace"]["round_self_ms"]
