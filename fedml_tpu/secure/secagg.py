"""Pairwise-mask additive secure aggregation (ISSUE 20).

The protocol shape is Bonawitz-style pairwise masking over the
TurboAggregate field primitives (core/mpc.py):

* every ordered client pair (i, j) owns a DH shared secret
  ``shared_key(pk_j, sk_i) == shared_key(pk_i, sk_j)`` which seeds a
  counter-mode PRG stream (numpy Philox: key = the pairwise secret,
  counter HIGH word = the round index, so per-round streams are 2^192
  blocks apart and can never overlap for any row length) of field
  elements;
* client i uploads ``quantize(weight·update) + Σ_{j>i} m_ij −
  Σ_{j<i} m_ij  (mod p)`` — every pair's mask appears once with each
  sign, so the COHORT SUM cancels every mask exactly in the integer
  field and the masked aggregate is BITWISE the plain fixed-point sum
  (the anchor pin, tests/test_secagg.py);
* the sample weight rides as ONE EXTRA masked field word appended to
  the row, so sample-weighted FedAvg survives masking without leaking
  per-client sample counts in the clear;
* dropout recovery: each client's DH secret key is BGW-shared across
  the cohort (threshold = the round's minimum survivor count) and
  escrowed at dispatch.  At the commit barrier the surviving set
  reconstructs a dead client's ``sk`` from ≥ threshold shares, replays
  its pairwise streams, and subtracts the uncancelled masks; a round
  with fewer survivors than the threshold fails BY NAME
  (:class:`SecAggBelowThreshold`) instead of committing garbage.

Trust model (simulation-grade, stated precisely): the keyring draws
every client's secret key from one seeded generator and the server
process holds the escrowed shares directly.  That preserves the
protocol ARITHMETIC — mask cancellation, threshold reconstruction,
below-threshold failure — which is what the tests pin, but not the
cryptographic trust boundary of a real deployment (where each share
would travel encrypted to its holder and only return at the barrier,
and keys would never co-reside).  Multi-process deployments rebuild
the same keyring from ``SecAggConfig.seed`` on every rank.

What masking costs the defense stack: the PR-9 admission screen
(norm z-score, cosine direction) reads PLAINTEXT rows and is therefore
BLINDED through masks — a masked byzantine row is indistinguishable
from an honest one at ingest.  The only per-update enforcement that
survives is the norm bound built into quantization itself:
``mpc.quantize`` raises on any row whose fixed-point magnitude exceeds
the field's signed half-range, so a boosted model-replacement larger
than ±(p−1)/(2·scale) cannot even be encoded
(tests/test_secagg.py holds the refusal by name).

Arithmetic bounds (ENFORCED at quantization, see mpc.quantize and
client_row): every per-client word and the K-client field SUM must
stay within ±(p−1)//2, i.e. K·max|weight·x|·scale ≤ (p−1)//2.
client_row passes ``max_abs=(p−1)//(2K)`` so each client's slice of
that budget is checked a priori — the sum cannot alias, and the check
cannot be deferred to commit because a wrapped field value is
indistinguishable from a legitimate one post hoc.  With the default
scale 2^16, p = 2^31−1 and a 5-client cohort that is
|weight·x| < 3276.8 per coordinate per client.
"""
from __future__ import annotations

import dataclasses
import logging
import threading
from typing import Iterable, Optional

import numpy as np

from fedml_tpu.core import mpc

log = logging.getLogger(__name__)

DEFAULT_SCALE = 2 ** 16


class SecAggBelowThreshold(RuntimeError):
    """A secure round's surviving set fell below the share-reconstruction
    threshold: the uncancelled masks of the dead clients cannot be
    rebuilt, so the round fails by name instead of committing a
    mask-polluted aggregate."""


@dataclasses.dataclass
class SecAggConfig:
    """Knobs of the secure-aggregation data plane (CLI --secure_*).

    threshold: minimum SURVIVING clients for a round to commit — also
    the BGW share count needed to reconstruct a dead client's key
    (polynomial degree threshold−1).  0 = majority of the cohort.
    dp_clip/dp_noise: the end-to-end private mode (--secure_agg --dp):
    each client clips its weighted update to dp_clip (the shared
    norm-clip definition) and adds Gaussian noise sigma = dp_noise ·
    dp_clip BEFORE quantize+mask, so the server only ever sees masked
    words of an already-noised update."""
    threshold: int = 0
    scale: int = DEFAULT_SCALE
    prime: int = mpc.DEFAULT_PRIME
    seed: int = 0
    dp_clip: Optional[float] = None
    dp_noise: float = 0.0

    def __post_init__(self):
        if self.threshold < 0:
            raise ValueError(f"threshold must be >= 0, got {self.threshold}")
        if self.scale < 2:
            raise ValueError(f"scale must be >= 2, got {self.scale}")
        if self.dp_noise > 0.0 and self.dp_clip is None:
            raise ValueError("dp_noise needs dp_clip: the noise sigma is "
                             "calibrated to the per-client clip")

    def resolve_threshold(self, n_clients: int) -> int:
        t = self.threshold if self.threshold > 0 else n_clients // 2 + 1
        if not 1 <= t <= n_clients:
            raise ValueError(
                f"secagg threshold {t} outside [1, {n_clients}] for a "
                f"{n_clients}-client cohort")
        return t


def pairwise_mask(pair_key: int, round_idx: int, n_words: int,
                  p: int = mpc.DEFAULT_PRIME) -> np.ndarray:
    """Counter-mode PRG stream of `n_words` field elements for one
    ordered pair at one round: Philox keyed by the DH pairwise secret
    with the round index in the counter's HIGH (most-significant) word.
    Generating a W-word row advances the 256-bit counter ~W/8 blocks
    from the LOW word up, so rounds that start 2^192 blocks apart can
    never overlap for any row length — with the round in the low word,
    round r+1's stream was round r's shifted by 8 words, and the
    difference of one client's consecutive masked uplinks leaked
    plaintext quantized-update deltas.  Same (key, round) → same
    stream, which is exactly what dropout recovery replays from a
    reconstructed secret key.  Returns int64 residues in [0, p)."""
    key = int(pair_key)
    bg = np.random.Philox(key=np.array([key & 0xFFFFFFFFFFFFFFFF,
                                        0x5EC466], dtype=np.uint64),
                          counter=np.array([0, 0, 0, int(round_idx)],
                                           dtype=np.uint64))
    return np.random.Generator(bg).integers(0, p, size=n_words,
                                            dtype=np.int64)


class SecAggKeyring:
    """Per-cohort DH key material + the escrowed seed shares.

    Client ids are the federation ranks (1..N).  ``escrow(cid)``
    materializes the BGW shares of that client's secret key — called at
    dispatch time, which is when a real deployment would ship each
    share to its holder.  ``reconstruct_sk(dead, survivors)`` rebuilds
    a dead client's key from the survivors' shares and raises
    :class:`SecAggBelowThreshold` by name below the threshold."""

    def __init__(self, client_ids: Iterable[int], threshold: int,
                 cfg: SecAggConfig):
        self.cfg = cfg
        self.ids = sorted(int(c) for c in client_ids)
        if len(set(self.ids)) != len(self.ids):
            raise ValueError(f"duplicate client ids in {self.ids}")
        self.threshold = int(threshold)
        p = cfg.prime
        rs = np.random.RandomState(cfg.seed)
        # secret keys in [2, p-2]: exclude the degenerate exponents
        self.sk = {c: int(rs.randint(2, p - 1)) for c in self.ids}
        self.pk = {c: mpc.pk_gen(self.sk[c], p=p) for c in self.ids}
        # escrowed BGW shares of sk, by owner: shares[owner][slot] where
        # slot k belongs to self.ids[k] (lazy — built at dispatch)
        self._shares: dict[int, np.ndarray] = {}
        self._pos = {c: k for k, c in enumerate(self.ids)}

    def pair_key(self, a: int, b: int) -> int:
        """The symmetric DH pairwise secret of clients a and b."""
        return mpc.shared_key(self.pk[b], self.sk[a], self.cfg.prime)

    def escrow(self, cid: int) -> None:
        """Materialize the BGW shares of `cid`'s secret key (threshold−1
        degree polynomial: any `threshold` shares reconstruct, fewer
        cannot).  Idempotent; seeded off (cfg.seed, cid) so every rank
        of a multi-process deployment escrows identical shares."""
        if cid in self._shares:
            return
        self._shares[cid] = mpc.BGW_encoding(
            np.array([self.sk[cid]], np.int64), len(self.ids),
            self.threshold - 1, self.cfg.prime,
            seed=(self.cfg.seed * 1_000_003 + cid) % (2 ** 31))

    def reconstruct_sk(self, dead: int, survivors: Iterable[int]) -> int:
        """Rebuild a dead client's secret key from the surviving set's
        escrowed shares.  Fails by name below the threshold."""
        self.escrow(dead)
        surv = sorted(int(s) for s in set(survivors) if s != dead
                      and s in self._pos)
        if len(surv) < self.threshold:
            raise SecAggBelowThreshold(
                f"cannot reconstruct client {dead}'s pairwise masks: "
                f"{len(surv)} survivors hold shares, threshold is "
                f"{self.threshold} — the round must not commit")
        idx = np.array([self._pos[s] for s in surv[:self.threshold]],
                       np.int64)
        shares = self._shares[dead][idx]
        return int(mpc.BGW_decoding(shares, idx, self.cfg.prime)[0])


class SecureAggregator:
    """THE aggregation-stage seam of the secure data plane — one object
    serving both the async server (AsyncServerManager, masked uplinks
    on the live wire) and the sync FSM (fedavg_messaging's aggregate
    barrier), plus the in-process clients of either path.

    Client side: :meth:`client_row` quantizes the weighted flat update
    (flatten_vars_row layout) plus the weight word and adds the
    pairwise masks.  Server side: :meth:`fold` is the jitted
    mask-and-fold at arrival (staleness.make_field_fold_fn — mod-p adds
    on the u32 row, O(W) per uplink like the plain streaming fold);
    the arrived row is also retained until the barrier, because
    excluding an uploaded-then-died client from a pure running sum is
    otherwise impossible.  :meth:`commit` runs the unmask barrier:
    subtract excluded uploaders' retained rows, reconstruct every
    non-included client's masks from escrowed shares, dequantize, and
    hand back the (acc, wsum) pair the existing O(P) stream commit
    consumes unchanged."""

    def __init__(self, cfg: SecAggConfig, client_ids: Iterable[int],
                 flat_dim: int):
        self.cfg = cfg
        self.dim = int(flat_dim)
        self.words = self.dim + 1            # + the masked weight word
        self.ids = sorted(int(c) for c in client_ids)
        self.threshold = cfg.resolve_threshold(len(self.ids))
        self.keyring = SecAggKeyring(self.ids, self.threshold, cfg)
        self._fold_fn = None                 # jitted, built lazily
        self._acc = None                     # device u32 running field sum
        self._rows: dict[int, np.ndarray] = {}   # unmask-window retention
        self._lock = threading.Lock()
        self.below_threshold_rounds = 0
        self.recovered_rounds = 0            # commits that rebuilt masks

    # -- client side ---------------------------------------------------------
    def client_row(self, cid: int, round_idx: int, flat: np.ndarray,
                   weight: float) -> np.ndarray:
        """One client's masked uplink row: [quantize(weight·flat),
        quantize(weight)] + pairwise masks, as uint32 field words.
        The DP stage (end-to-end private mode) clips and noises the
        weighted update BEFORE quantization, so no un-noised value ever
        reaches the field encoding; the noise generator is derived per
        (seed, client, round), so draws are thread-safe and
        byte-deterministic no matter how concurrent uploads interleave.
        Quantization enforces the per-client slice of the aggregate
        bound, |q| ≤ (p−1)//(2K) for a K-client cohort, so the folded
        field SUM can never cross the signed half-range and alias at
        dequantize — aliasing is undetectable post hoc, so the guard
        must run a priori, here."""
        p = self.cfg.prime
        x = np.asarray(flat, np.float64) * float(weight)
        if x.shape != (self.dim,):
            raise ValueError(f"client_row expects a [{self.dim}] flat "
                             f"row, got {x.shape}")
        if self.cfg.dp_clip is not None:
            nrm = float(np.linalg.norm(x))
            if nrm > self.cfg.dp_clip:
                x = x * (self.cfg.dp_clip / nrm)
            if self.cfg.dp_noise > 0.0:
                rng = np.random.default_rng(
                    (self.cfg.seed, 41, int(cid), int(round_idx)))
                x = x + rng.normal(
                    0.0, self.cfg.dp_noise * self.cfg.dp_clip, x.shape)
        head = (p - 1) // (2 * len(self.ids))
        q = np.empty((self.words,), np.int64)
        q[:self.dim] = mpc.quantize(x, self.cfg.scale, p, max_abs=head)
        q[self.dim] = mpc.quantize(np.array([float(weight)]),
                                   self.cfg.scale, p, max_abs=head)[0]
        for j in self.ids:
            if j == cid:
                continue
            m = pairwise_mask(self.keyring.pair_key(cid, j), round_idx,
                              self.words, p)
            q = (q + m) % p if cid < j else (q - m) % p
        return q.astype(np.uint32)

    # -- server side ---------------------------------------------------------
    @property
    def arrived(self) -> list[int]:
        with self._lock:
            return sorted(self._rows)

    @property
    def count(self) -> int:
        return len(self._rows)

    def escrow(self, cid: int) -> None:
        """Dispatch-time share escrow (see SecAggKeyring.escrow)."""
        self.keyring.escrow(cid)

    def fold(self, cid: int, row: np.ndarray) -> int:
        """Jitted mask-and-fold at arrival; returns the arrived count.
        A client's re-upload within one round replaces its retained row
        (the duplicate is backed out of the field sum first — exactly
        once semantics at the aggregation stage)."""
        import jax.numpy as jnp
        from fedml_tpu.async_.staleness import make_field_fold_fn
        row = np.ascontiguousarray(row, np.uint32)
        if row.shape != (self.words,):
            raise ValueError(f"secagg row must be [{self.words}] u32 "
                             f"words, got {row.shape}")
        if int(cid) not in self.keyring._pos:
            raise ValueError(f"unknown secagg client id {cid} "
                             f"(cohort is {self.ids})")
        with self._lock:
            if self._fold_fn is None:
                self._fold_fn = make_field_fold_fn(self.cfg.prime)
            if self._acc is None:
                self._acc = jnp.zeros((self.words,), jnp.uint32)
            prev = self._rows.pop(int(cid), None)
            if prev is not None:
                # additive inverse in the field: acc + (p - prev) mod p
                inv = ((self.cfg.prime - prev.astype(np.int64))
                       % self.cfg.prime).astype(np.uint32)
                self._acc = self._fold_fn(self._acc, jnp.asarray(inv))
            self._acc = self._fold_fn(self._acc, jnp.asarray(row))
            self._rows[int(cid)] = row.copy()
            return len(self._rows)

    def field_sum(self, round_idx: int,
                  survivors: Iterable[int]) -> tuple[np.ndarray, list[int]]:
        """The unmask barrier in the integer field: returns (words i64
        in [0, p), included ids).  Included = arrived ∩ survivors; an
        uploaded-then-died client's retained row is subtracted whole,
        then every non-included cohort member's pairwise masks against
        the included set are reconstructed (escrowed shares → sk →
        replayed PRG streams) and backed out.  What remains is exactly
        Σ_{i∈included} quantize(w_i·x_i) mod p — bitwise the maskless
        fixed-point sum.  Raises SecAggBelowThreshold by name when the
        surviving set cannot reconstruct."""
        p = self.cfg.prime
        with self._lock:
            rows = dict(self._rows)
            acc = (np.zeros((self.words,), np.int64) if self._acc is None
                   else np.asarray(self._acc, np.uint32).astype(np.int64))
        survivors = sorted(int(s) for s in set(survivors))
        included = sorted(set(rows) & set(survivors))
        if len(survivors) < self.threshold:
            self.below_threshold_rounds += 1
            raise SecAggBelowThreshold(
                f"secure round {round_idx}: {len(survivors)} survivors "
                f"< threshold {self.threshold} — refusing to commit a "
                f"mask-polluted aggregate")
        for d in set(rows) - set(included):
            # uploaded then excluded (died pre-commit): back the whole
            # masked row out, leaving only survivor-side pair residues
            acc = (acc - rows[d].astype(np.int64)) % p
        dead = [c for c in self.ids if c not in included]
        if dead and included:
            self.recovered_rounds += 1
        for d in dead:
            # the included rows each carry one uncancelled mask for the
            # pair (i, d); replay d's streams from the reconstructed key
            sk_d = self.keyring.reconstruct_sk(d, survivors)
            for i in included:
                s = mpc.shared_key(self.keyring.pk[i], sk_d, p)
                m = pairwise_mask(s, round_idx, self.words, p)
                # client i applied +m if i < d else −m; subtract that
                acc = (acc - m) % p if i < d else (acc + m) % p
        return acc, included

    def commit(self, round_idx: int, survivors: Iterable[int],
               reset: bool = True) -> tuple[np.ndarray, float, list[int]]:
        """Unmask + dequantize: returns (acc f32 [dim] = Σ w_i·x_i,
        wsum = Σ w_i, included ids) — the exact (acc, wsum) shape
        make_stream_commit_fn consumes, so the O(P) commit program is
        untouched by masking.  `reset` clears the round window."""
        words, included = self.field_sum(round_idx, survivors)
        total = mpc.dequantize(words, self.cfg.scale, self.cfg.prime)
        acc = total[:self.dim].astype(np.float32)
        wsum = float(total[self.dim])
        if reset:
            self.reset()
        return acc, wsum, included

    def reset(self) -> None:
        with self._lock:
            self._rows.clear()
            self._acc = None

    def report(self) -> dict:
        return {"cohort": len(self.ids), "threshold": self.threshold,
                "below_threshold_rounds": self.below_threshold_rounds,
                "recovered_rounds": self.recovered_rounds}
