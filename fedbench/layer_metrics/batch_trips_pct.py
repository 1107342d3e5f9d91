"""Batch-loop trips the window's round programs ran over the trips a loop over
every batch of the stack would have run: 100 where every client fills all the
stack's batches (the program is the static loop), under it where the program
ends each chunk's batch loop at the chunk's own longest client.  The engine
counts both on the host from the sizes of the ids it sampled
(``engine.transfer_stats``, reset at the window's start); a program that keeps
no such count reads as nothing."""
LAYER, UNIT, SOURCE, MOVES = "local training", "%", "program_counter", "rounds_per_s"


def read(ctx):
    stats = getattr(ctx["engine"], "transfer_stats", None)
    static = getattr(stats, "batch_trips_static", 0)
    return 100.0 * stats.batch_trips / static if static else None
