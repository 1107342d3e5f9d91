"""Real (mask = 1) samples of the window over the slots its rounds trained:
every client of a cohort occupies the stack's full ``batches x batch size``,
so clients smaller than the largest leave padded slots that the device
computes and masks.  The yardstick of a sample-balanced packing."""
LAYER, UNIT, SOURCE, MOVES = "local training", "%", "program_counter", "samples_per_s"


def read(ctx):
    batches, batch_size = ctx["data"].client_shards["mask"].shape[1:3]
    slots = (ctx["window"]["attempted"] * int(ctx["cell"].traffic["cohort"])
             * batches * batch_size)
    return 100.0 * ctx["samples"] / slots if slots else None
