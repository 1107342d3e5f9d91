"""Federated dataset container — the TPU-native replacement for the
reference's dict-of-DataLoaders 8-tuple contract
(e.g. cifar10/data_loader.py:235-269).

Instead of per-client torch DataLoaders pulled by Python loops, all client
shards live as ONE stacked, padded array set

    x    [C, B, bs, ...]    C = clients, B = batches/client, bs = batch size
    y    [C, B, bs, ...]
    mask [C, B, bs]         1.0 for real samples, 0.0 for padding

resident in HBM (or sharded over a mesh axis).  A round's cohort is a
`jnp.take` along axis 0 — so client selection, local training, and
aggregation all happen device-side with static shapes (SURVEY.md §7 hard
part #1: unequal client sizes become padding+masking, not control flow).

`as_8tuple()` provides the reference-shaped view for API parity.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def pad_to_batches(x: np.ndarray, y: np.ndarray, batch_size: int,
                   n_batches: Optional[int] = None):
    """Pad (x, y) up to n_batches full batches; returns (x, y, mask) with
    leading shape [B, bs]."""
    n = x.shape[0]
    need = n_batches if n_batches is not None else max(1, -(-n // batch_size))
    total = need * batch_size
    pad = total - n
    mask = np.concatenate([np.ones(n, np.float32), np.zeros(pad, np.float32)])
    if pad > 0:
        x = np.concatenate([x, np.zeros((pad,) + x.shape[1:], x.dtype)])
        y = np.concatenate([y, np.zeros((pad,) + y.shape[1:], y.dtype)])
    rs = lambda a: a.reshape((need, batch_size) + a.shape[1:])
    return rs(x), rs(y), mask.reshape(need, batch_size)


def build_client_shards(x: np.ndarray, y: np.ndarray,
                        net_dataidx_map: dict[int, np.ndarray],
                        batch_size: int,
                        max_batches: Optional[int] = None,
                        shuffle_seed: Optional[int] = None) -> dict[str, np.ndarray]:
    """Stack every client's padded shard into one array set [C, B, bs, ...].

    B = max batches over clients (optionally capped at `max_batches`; clients
    with more data are truncated to B*bs samples — cap consciously).

    Vectorized as one [C, B*bs] index matrix + one gather: per-client
    Python assembly costs ~7.5 ms/client, which at reference cross-device
    scale (342,477 stackoverflow clients, benchmark/README.md:57) is ~40
    minutes; this path builds the same stack in seconds.  The per-client
    rng draws happen in the same order as the historical loop, so the
    output is bit-identical for any shuffle_seed.
    """
    n_clients = len(net_dataidx_map)
    sizes = np.fromiter((len(net_dataidx_map[i]) for i in range(n_clients)),
                        np.int64, n_clients)
    B = max(1, int(np.max(-(-sizes // batch_size))))
    if max_batches is not None:
        B = min(B, max_batches)
    cap = B * batch_size
    keep = np.minimum(sizes, cap)
    rng = (np.random.RandomState(shuffle_seed)
           if shuffle_seed is not None else None)
    idx = np.zeros((n_clients, cap), np.int64)
    for i in range(n_clients):          # cheap: index bookkeeping only
        ci = np.asarray(net_dataidx_map[i])
        if rng is not None:
            ci = ci[rng.permutation(len(ci))]
        idx[i, :keep[i]] = ci[:keep[i]]
    mask = (np.arange(cap)[None, :] < keep[:, None])
    gx = x[idx.reshape(-1)].reshape((n_clients, cap) + x.shape[1:])
    gy = y[idx.reshape(-1)].reshape((n_clients, cap) + y.shape[1:])
    # padding rows pointed at sample 0 for the gather; zero them to match
    # pad_to_batches' zero padding
    gx[~mask] = 0
    gy[~mask] = 0
    if gy.dtype == np.int64:
        # without x64 the device holds int32 either way: cast ONCE here,
        # so every upload ships (and the H2D accounting counts) the
        # bytes the program uses, not twice as many
        gy = gy.astype(np.int32)
    rs = lambda a: a.reshape((n_clients, B, batch_size) + a.shape[2:])
    return {"x": rs(gx), "y": rs(gy),
            "mask": rs(mask.astype(np.float32))}


def build_eval_shard(x: np.ndarray, y: np.ndarray, batch_size: int) -> dict[str, np.ndarray]:
    """Single padded shard [B, bs, ...] for global eval."""
    cx, cy, cm = pad_to_batches(x, y, batch_size)
    return {"x": cx, "y": cy, "mask": cm}


@dataclasses.dataclass
class FederatedData:
    """All state the algorithms need; mirrors the reference 8-tuple."""
    train_data_num: int
    test_data_num: int
    train_global: dict[str, np.ndarray]      # padded eval shard
    test_global: dict[str, np.ndarray]       # padded eval shard
    client_shards: dict[str, np.ndarray]     # stacked [C, B, bs, ...]
    client_num_samples: np.ndarray           # [C] true sample counts
    test_client_shards: Optional[dict[str, np.ndarray]]  # [C, Bt, bs, ...] or None
    class_num: int
    synthetic: bool = False   # True when a stand-in replaced missing files
    # set when client_shards["x"] is stored uint8 (data/quant.py): the
    # affine spec (x_f32 = u*scale + offset) the mesh engines fuse into
    # the jitted round program as its first op.  Eval shards
    # (train_global/test_global/test_client_shards) always stay float.
    x_dequant: Optional[object] = None
    _device_cache: dict = dataclasses.field(default_factory=dict, repr=False)

    @property
    def client_num(self) -> int:
        return int(self.client_shards["mask"].shape[0])

    def device_shards(self) -> tuple[dict, jnp.ndarray]:
        """Client shards + weights as device arrays, uploaded ONCE and cached
        (HBM-resident; per-round cohort gather is then device-side)."""
        if "shards" not in self._device_cache:
            self._device_cache["shards"] = {
                k: jnp.asarray(v) for k, v in self.client_shards.items()}
            self._device_cache["weights"] = jnp.asarray(self.client_num_samples)
        return self._device_cache["shards"], self._device_cache["weights"]

    def cohort(self, client_indices: np.ndarray) -> tuple[dict, jnp.ndarray]:
        """Gather a round's cohort: ({x,y,mask} [K, B, bs, ...], weights [K]).
        A `jnp.take` on the cached device-resident stack — no host↔device
        traffic beyond the index vector."""
        shards, weights = self.device_shards()
        idx = jnp.asarray(client_indices)
        return ({k: jnp.take(v, idx, axis=0) for k, v in shards.items()},
                jnp.take(weights, idx))

    def as_8tuple(self):
        """Reference-shaped view (train_data_num, test_data_num, train_global,
        test_global, local_num_dict, train_local_dict, test_local_dict,
        class_num) — cifar10/data_loader.py:235-269."""
        C = self.client_num
        local_num = {i: int(self.client_num_samples[i]) for i in range(C)}
        train_local = {i: jax.tree.map(lambda v, i=i: v[i], self.client_shards)
                       for i in range(C)}
        if self.test_client_shards is not None:
            test_local = {i: jax.tree.map(lambda v, i=i: v[i], self.test_client_shards)
                          for i in range(C)}
        else:
            test_local = {i: None for i in range(C)}
        return (self.train_data_num, self.test_data_num, self.train_global,
                self.test_global, local_num, train_local, test_local,
                self.class_num)
