"""Client-size laws of the traffic mixes, found by name.

A traffic file names its law as ``"client_sizes": {"law": "<module>", ...}``;
the module ``fedbench/client_sizes/<module>.py`` provides

    sizes(law, population) -> [population] int64, real samples per client
    cap(law) -> int, the most samples one client can hold

from the law's own parameters alone.  The sizes are part of the mix, not of
the run: the program's sampler draws the same client ids in round r of every
run, so with fixed sizes every run does the same real work per round and
``samples_per_s`` does not move with ``--seed`` (it spread 2 % when the sizes
were drawn from it).  A later PR adds a law by adding a file here.
"""
from __future__ import annotations

import importlib


def resolve(name: str):
    return importlib.import_module(f"fedbench.client_sizes.{name}")
