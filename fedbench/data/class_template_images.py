"""Learnable image stand-in: per-class templates plus unit noise.

The arithmetic of fedml_tpu/data/synthetic.py::synthetic_classification_images
(x = 0.5 * template[y] + N(0, 1)), written straight into the program's
stacked client layout and filled in bulk on a thread pool, one client (a few
MB, cache-resident) at a time, because a cell makes gigabytes of it in every
run.  No dataset file is read.
"""
from __future__ import annotations

import numpy as np

from fedbench.data import alloc, fill_blocks, slot_mask

BLOCK = 16          # clients per generator; part of the stream, do not change


def make(seed, sizes, batch_size, n_batches, hw, channels, classes):
    C, cap = len(sizes), n_batches * batch_size
    feat = int(hw[0]) * int(hw[1]) * int(channels)
    root = np.random.default_rng(np.random.SeedSequence([seed, 0x7E]))
    half_templates = 0.5 * root.standard_normal((classes, feat), dtype=np.float32)
    x = alloc((C, cap, feat), np.float32)
    y = np.empty((C, cap), np.int32)
    mask = slot_mask(sizes, batch_size, n_batches)
    real = mask.reshape(C, cap) > 0

    def fill(lo, hi, g):
        buf = np.empty((cap, feat), np.float32)
        for c in range(lo, hi):
            yy = g.integers(0, classes, cap, dtype=np.int32)
            g.standard_normal(out=buf, dtype=np.float32)
            buf += half_templates[yy]
            if not real[c].all():
                buf[~real[c]] = 0
                yy[~real[c]] = 0
            x[c] = buf
            y[c] = yy

    fill_blocks(seed, C, BLOCK, fill)
    shape = (C, n_batches, batch_size)
    return ({"x": x.reshape(shape + (int(hw[0]), int(hw[1]), int(channels))),
             "y": y.reshape(shape), "mask": mask}, int(classes))
