"""Learnable next-word stand-in: a rank-``classes`` Markov chain over tokens.

The process of fedml_tpu/data/synthetic.py::synthetic_sequences_classed
(tokens assigned to classes at random; the next-token row depends only on the
current token's class; total Dirichlet concentration ``row_alpha_total`` so
sharpness does not depend on the vocabulary).  Each class row's inverse CDF is
tabulated at 2^16 points, so a step is two table look-ups per token, in bulk
on a thread pool: the full StackOverflow population is hundreds of millions
of tokens in every run.  x = seq[:-1], y = seq[1:].
"""
from __future__ import annotations

import numpy as np

from fedbench.data import alloc, fill_blocks, slot_mask

BLOCK = 2048        # clients per generator; part of the stream, do not change
GRID = 1 << 16      # points of each tabulated inverse CDF


def make(seed, sizes, batch_size, n_batches, seq_len, vocab, classes=64,
         row_alpha_total=10.0):
    C, cap = len(sizes), n_batches * batch_size
    root = np.random.default_rng(np.random.SeedSequence([seed, 0x5E]))
    cls = root.integers(0, classes, vocab).astype(np.int32)
    rows = root.dirichlet(np.full(vocab, row_alpha_total / vocab), size=classes)
    cdf = np.cumsum(rows, axis=1)
    mid = (np.arange(GRID) + 0.5) / GRID
    inverse = np.stack([np.minimum(np.searchsorted(c, mid), vocab - 1)
                        for c in cdf]).astype(np.int32).ravel()
    x = alloc((C, cap, seq_len), np.int32)
    y = alloc((C, cap, seq_len), np.int32)
    mask = slot_mask(sizes, batch_size, n_batches)
    real = mask.reshape(C, cap) > 0

    def fill(lo, hi, g):
        sel = real[lo:hi]
        n = int(sel.sum())
        seqs = np.empty((seq_len + 1, n), np.int32)
        seqs[0] = g.integers(0, vocab, n, dtype=np.int32)
        for t in range(seq_len):
            u = g.integers(0, GRID, n, dtype=np.int32)
            seqs[t + 1] = inverse[cls[seqs[t]] * GRID + u]
        seqs = seqs.T
        x[lo:hi] = 0
        y[lo:hi] = 0
        x[lo:hi][sel] = seqs[:, :-1]
        y[lo:hi][sel] = seqs[:, 1:]

    fill_blocks(seed, C, BLOCK, fill)
    shape = (C, n_batches, batch_size, seq_len)
    return {"x": x.reshape(shape), "y": y.reshape(shape), "mask": mask}, int(vocab)
