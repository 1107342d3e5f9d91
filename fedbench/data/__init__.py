"""Seeded data-set generators of the benchmark, found by name.

A traffic file names its generator as ``"dataset": {"generator": "<module>",
...}``; the module ``fedbench/data/<module>.py`` provides

    make(seed, sizes, batch_size, n_batches, **args) -> (shards, class_num)

where ``sizes`` is the [C] array of real samples per client and ``shards`` is
the program's stacked layout ``{"x": [C, B, bs, ...], "y": [C, B, bs, ...],
"mask": [C, B, bs]}`` (fedml_tpu/data/federated.py), padding slots zeroed.
A later PR adds a generator by adding a file here.
"""
from __future__ import annotations

import importlib
import mmap
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np


def resolve(name: str):
    return importlib.import_module(f"fedbench.data.{name}").make


def n_workers() -> int:
    """Threads for the bulk fills (numpy's generators and ufuncs release
    the GIL); few enough to stay inside a one-chip machine's cores."""
    return max(1, min(24, (os.cpu_count() or 2) - 1))


def alloc(shape, dtype) -> np.ndarray:
    """Uninitialised array on transparent huge pages where the kernel grants
    them: the first touch of gigabytes of fresh 4 KiB pages costs several
    times the fill itself (measured 0.33 s against 0.05 s per 82 MB)."""
    n = int(np.prod(shape)) * np.dtype(dtype).itemsize
    if n < (1 << 22) or not hasattr(mmap, "MADV_HUGEPAGE"):
        return np.empty(shape, dtype)
    buf = mmap.mmap(-1, n)
    try:
        buf.madvise(mmap.MADV_HUGEPAGE)
    except OSError:
        pass
    return np.frombuffer(buf, dtype).reshape(shape)


def fill_blocks(seed: int, n_clients: int, block: int, fill) -> None:
    """Run ``fill(lo, hi, generator)`` over client blocks on a thread pool.
    Every block owns a generator spawned from the seed by its index, so the
    data depend on the seed and the block size only, never on the thread
    count or the schedule."""
    starts = list(range(0, n_clients, block))
    children = np.random.SeedSequence(seed).spawn(len(starts))

    def one(i):
        lo = starts[i]
        fill(lo, min(lo + block, n_clients), np.random.default_rng(children[i]))

    with ThreadPoolExecutor(n_workers()) as pool:
        list(pool.map(one, range(len(starts))))


def slot_mask(sizes: np.ndarray, batch_size: int, n_batches: int) -> np.ndarray:
    """[C, B, bs] f32 mask: the first sizes[c] slots of client c are real."""
    cap = n_batches * batch_size
    m = np.arange(cap)[None, :] < np.minimum(sizes, cap)[:, None]
    return m.astype(np.float32).reshape(len(sizes), n_batches, batch_size)
