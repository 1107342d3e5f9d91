"""Deterministic per-round client sampling.

Reproduces the reference's sampling semantics exactly
(FedAVGAggregator.client_sampling, reference
fedml_api/distributed/fedavg/FedAVGAggregator.py:90-98):
``np.random.seed(round_idx); np.random.choice(range(N), k, replace=False)``
— so runs are comparable round-for-round with the reference, and the
equivalence oracle (BASELINE.md) stays valid.  The semantics are the
reference's, the generator is private: the same cohorts from a legacy
`RandomState` of this module's own, without the reference's re-seed of
the process-global numpy RNG and without its Python `range(N)`.  A
JAX-native sampler is also provided for fully-jitted round loops.
"""
from __future__ import annotations

import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


_generators = threading.local()


def _thread_generator() -> np.random.RandomState:
    """This thread's legacy generator, which `ClientSampler.sample`
    re-seeds before every draw.  Held, because building a RandomState
    costs ten times a small population's draw (0.15 ms on the
    builder's CPU) for a state that `seed(r)` overwrites whole; per
    thread, because the server paths draw from real threads
    (comm/fedavg_messaging.py) and a shared generator could be
    re-seeded between another thread's seed and its choice.  It carries
    nothing from one draw to the next."""
    rs = getattr(_generators, "rs", None)
    if rs is None:
        rs = _generators.rs = np.random.RandomState(0)
    return rs


class ClientSampler:
    """Seeded-by-round sampler with the reference's numpy semantics."""

    def __init__(self, client_num_in_total: int, client_num_per_round: int):
        self.client_num_in_total = client_num_in_total
        self.client_num_per_round = client_num_per_round

    @classmethod
    def for_data(cls, data, cfg) -> "ClientSampler":
        """Sampler over the clients the DATA actually has: real-file
        loaders honor the file's natural client count, which can differ
        from cfg.client_num_in_total — sampling cfg's range would gather
        out-of-range ids (silently clamped by jnp.take) and train wrong
        shards under wrong weights.  Every engine must construct its
        sampler through this."""
        n_total = data.client_num
        if n_total != cfg.client_num_in_total:
            import logging
            logging.getLogger(__name__).warning(
                "dataset has %d clients but client_num_in_total=%d; "
                "sampling over the dataset's %d",
                n_total, cfg.client_num_in_total, n_total)
        return cls(n_total, cfg.client_num_per_round)

    def sample(self, round_idx: int,
               k: Optional[int] = None) -> np.ndarray:
        """The reference's draw for `round_idx`, bit for bit, from a
        private generator.  `np.random.seed(r); np.random.choice(
        range(N), k, replace=False)` delegates to the global legacy
        RandomState, so a private legacy `RandomState` seeded with `r`
        walks the identical Mersenne-Twister stream, and `choice` on
        the INTEGER N indexes the same permutation the range-array path
        takes (pinned against the two lines in tests/test_scale.py).
        It must stay the legacy RandomState: `default_rng` walks
        another stream.  Still one O(N) numpy permutation a draw, but
        ndarray scratch (2.7 MB of int64 at N = 342,477), not N boxed
        Python ints, and the global numpy RNG is left alone: nothing
        else in the process loses its state, and any thread may draw.
        `k` overrides the cohort size (the streaming sampler's
        variable-width draws)."""
        k = self.client_num_per_round if k is None else int(k)
        # >= (not ==): per_round beyond the population is full
        # participation too, and must agree with sample_jax's branch so
        # cohort ordering (and thus rng-lane pairing) matches
        if k >= self.client_num_in_total:
            return np.arange(self.client_num_in_total, dtype=np.int64)
        rs = _thread_generator()
        rs.seed(round_idx)
        return np.asarray(
            rs.choice(self.client_num_in_total, k, replace=False),
            dtype=np.int64,
        )

    def sample_jax(self, round_idx: jax.Array) -> jax.Array:
        """Traceable variant for fully-jitted round loops: derives a fold-in
        key from the round index and takes the first k of a permutation.
        (Not bit-identical to numpy — use `sample` when oracle comparability
        with the reference matters.)  Full participation returns arange,
        mirroring `sample` — so client→rng-lane pairing matches the Python
        loop exactly in that regime."""
        if self.client_num_per_round >= self.client_num_in_total:
            return jnp.arange(self.client_num_in_total, dtype=jnp.int32)
        key = jax.random.fold_in(jax.random.PRNGKey(0), round_idx)
        perm = jax.random.permutation(key, self.client_num_in_total)
        return perm[: self.client_num_per_round]
