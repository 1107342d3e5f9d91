"""``mla_core_roofline``'s reading in this configuration's cell: the least time the chip could take for a round's latent-attention
cores (``core_flops`` / ``core_bytes`` of ``fedbench/reference/xing4_0_29b_a4b.py``: seven products over the pairs key <= query of 32
heads, keys 192 and values 128 deep) over the device time of the custom calls under ``fed_attention``
(``fedbench/harness/kernel_trace.py``, which also leaves this cell's scope x phase table, ``kernel_trace.json``).  That reader is
called, not copied; its entry lists another cell and an accepted entry is not edited (PERF.md section 7)."""
from fedbench.layer_metrics import mla_core_roofline

LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "rounds_per_s"


def read(ctx):
    return mla_core_roofline.read(ctx)
