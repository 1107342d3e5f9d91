"""Update admission pipeline — the defense half of ISSUE 9.

PR 8's reliability layer guarantees a frame arrives exactly once and
uncorrupted; nothing yet asks whether its CONTENTS should be trusted.
This module is the defense-in-depth gate at the async server's ONE
insert path (``AsyncServerManager._ingest_row`` and the virtual-time
scheduler's arrival handler): every uplink row passes, in order,

    1. finite canary      — NaN/Inf anywhere in the row quarantines it
                            (one poisoned fold is irreversible: the
                            streaming accumulator has no undo);
    2. norm-bound clip    — the update delta (row − global) is clipped
                            to ``norm_bound`` through THE shared
                            clip definition (core/robust.clip_row ==
                            norm_diff_clip's factor), so a boosted
                            model-replacement
                            contributes at most a clean-sized step;
    3. anomaly screen     — robust z-score of the delta norm against an
                            exponentially-weighted running reference
                            of ACCEPTED updates, plus cosine similarity against an
                            EMA of accepted delta directions (sign-flip
                            rides a clean-sized norm; only direction
                            betrays it).  The screen arms after
                            ``screen_warmup`` accepted updates so cold
                            starts cannot quarantine the first honest
                            cohort.

Everything numeric runs in ONE jitted program per arrival (O(P), the
same order as the PR-6 fold itself), so the hot ingest path keeps its
throughput — ``run_ingest_torture(defense=...)`` is the screen-on
overhead arm.  Rejected rows are quarantined, never folded: counted in
``async_updates_quarantined_total{reason}``, timed into
``defense_screen_seconds``, traced as ``defense.quarantine`` instants
(the flight recorder's ring, so a dump names WHO was rejected and
why).

The DP-FedAvg configuration (ROADMAP item 4's first server transform)
reuses stage 2 as the per-client clip and adds Gaussian noise inside
the bucketed commit (staleness.make_bucket_commit_fn).
"""
from __future__ import annotations

import dataclasses
import logging
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu import obs
from fedml_tpu.obs import programs as obs_programs
from fedml_tpu.core.robust import clip_scale

log = logging.getLogger(__name__)
Pytree = Any

QUARANTINE_REASONS = ("nonfinite", "norm_z", "cosine")


@dataclasses.dataclass
class DefenseConfig:
    """Knobs of the admission pipeline + bucketed robust commit (CLI
    --defense_*).  The degenerate config — buckets=1, combine
    trimmed_mean/trim 0, no clip, no screen, dp off — reproduces the
    PR-6 streaming commit BITWISE (the tentpole's anchor pin)."""
    norm_bound: Optional[float] = None   # admission clip τ (None = off)
    screen: bool = False                 # z/cosine anomaly screen
    z_max: float = 4.0                   # robust z threshold on ‖Δ‖
    cos_min: float = -1.0                # cosine floor vs ref (-1 = off)
    screen_warmup: int = 8               # accepted updates before arming
    ref_ema: float = 0.1                 # EW rate: direction ref + norm stats
    buckets: int = 1                     # B bucket accumulators
    combine: str = "trimmed_mean"        # mean | trimmed_mean | median
    trim_k: int = 0                      # buckets trimmed per side
    dp_clip: Optional[float] = None      # DP-FedAvg per-client clip S
    dp_noise: float = 0.0                # DP noise multiplier z
    seed: int = 0                        # bucket-assignment seed

    def __post_init__(self):
        from fedml_tpu.async_.staleness import BUCKET_COMBINE_MODES
        if self.combine not in BUCKET_COMBINE_MODES:
            raise ValueError(f"unknown bucket combine {self.combine!r} "
                             f"(choose one of {BUCKET_COMBINE_MODES})")
        if self.buckets < 1:
            raise ValueError(f"buckets must be >= 1, got {self.buckets}")
        if self.dp_noise > 0.0 and self.dp_clip is None:
            raise ValueError("dp_noise needs dp_clip: the DP guarantee "
                             "is calibrated to the per-client clip S")

    @property
    def clip_bound(self) -> Optional[float]:
        """The effective per-client clip: DP's S wins when set (the DP
        accounting requires it), else the admission norm bound."""
        return self.dp_clip if self.dp_clip is not None else self.norm_bound

    def active(self) -> bool:
        """Whether any admission stage beyond the finite canary is on."""
        return (self.clip_bound is not None or self.screen
                or self.dp_noise > 0.0)


def make_flatten_fn():
    """Jitted device-side flatten of a variables pytree into the ONE
    flat-row layout (flatten_vars_row's element order: ravel + concat
    in jax leaf order) — the admission screen compares uplink rows
    against the current global in this layout."""
    def flatten(tree):
        leaves = [jnp.ravel(l).astype(jnp.float32)
                  for l in jax.tree.leaves(tree)]
        if not leaves:
            return jnp.zeros((0,), jnp.float32)
        return leaves[0] if len(leaves) == 1 else jnp.concatenate(leaves)
    return jax.jit(flatten)


def _make_stage_fn(cfg: DefenseConfig):
    """THE admission stage math, shared by the standalone screen
    (make_admission_fn) and the fused hot path (make_screened_fold_fn)
    — one definition so the two compiled programs cannot drift:

        stages(row, g, ref, n_acc, mu, m2)
            -> (clipped, ok, reason, new_ref, new_n, new_mu, new_m2)

    Stages: finite canary on the raw row; delta Δ = row − g; clip
    factor via the shared clip_scale (with no clip configured the
    INPUT row passes through untouched — g + 1.0·Δ would not be
    bitwise `row`, and the degenerate-config pin needs exactness);
    ONE-SIDED robust z of ‖Δ‖ vs exponentially-weighted running
    (mu, m2 = EW variance) norm stats; cosine of Δ vs the accepted-
    direction EMA `ref` (python-gated OFF at cos_min <= -1, so the
    disabled stage costs no O(P) passes and `ref` stays frozen).

    Reason codes index QUARANTINE_REASONS + 1 (0 = admitted); the
    canary outranks the z screen outranks cosine, so a NaN row is
    always reported as "nonfinite" even though its z/cos compare
    false too.

    Design notes, all empirically forced (see PERF.md "Adversarial
    robustness"):

    * the clip bound gates TEACHING: the norm stats learn only from
      rows whose raw norm respects the bound — a boosted cohort
      accepted during warmup still folds (clipped, bounded harm) but
      cannot inflate mu/std enough for later boosted rows to slip
      under any z_max;
    * EW stats, not Welford: honest norms drift as training converges;
      an all-history estimator reads the drift as variance or pins mu
      at the warmup level;
    * the norm stats learn from every finite bound-respecting row
      INCLUDING z/cos-rejected ones — accepted-only teaching froze the
      stats whenever the honest distribution shifted faster than the
      EW rate and livelocked the federation quarantining everyone;
    * the z test is one-sided (too-LARGE only): small norms are not an
      attack surface, and honest norms legitimately decay below mu;
      the 10%-of-mean std floor keeps a collapsed variance from
      flagging ordinary fluctuation;
    * the direction reference learns from fully ACCEPTED rows only — a
      sign-flipped cohort (honest-sized norm) must not drag the cosine
      reference toward itself by being rejected."""
    clip_bound = cfg.clip_bound
    z_max = float(cfg.z_max)
    cos_min = float(cfg.cos_min)
    warmup = float(max(1, cfg.screen_warmup))
    ema = float(cfg.ref_ema)
    screen = bool(cfg.screen)
    cos_on = screen and cos_min > -1.0

    def stages(row, g, ref, n_acc, mu, m2):
        d = row - g
        sq = jnp.sum(d * d)
        # the finite canary rides the Σd² reduction instead of paying
        # its own O(P) isfinite pass: any NaN/±Inf element of `row`
        # makes d² non-finite and non-finiteness is absorbing under
        # sum (squares are non-negative, so no cancellation can hide
        # it); an overflowing-but-finite row flags too, which is the
        # right call for a garbage uplink.  The screened fold is the
        # ingest hot path — every pass counts (PERF.md table).
        finite = jnp.isfinite(sq)
        nd = jnp.sqrt(jnp.maximum(sq, 1e-24))
        if clip_bound is not None:
            clipped = g + clip_scale(sq, jnp.float32(clip_bound)) * d
            teaches = nd <= jnp.float32(clip_bound)
        else:
            clipped = row
            teaches = jnp.bool_(True)
        if screen:
            warm = n_acc >= warmup
            std = jnp.sqrt(jnp.maximum(m2, 0.0))
            z = (nd - mu) / jnp.maximum(std, 0.1 * mu + 1e-12)
            ok_z = jnp.logical_or(~warm, z <= z_max)
        else:
            ok_z = jnp.bool_(True)
        if cos_on:
            refn = jnp.sqrt(jnp.sum(ref * ref))
            cos = jnp.sum(d * ref) / (nd * refn + 1e-12)
            ok_cos = jnp.logical_or(n_acc < warmup, cos >= cos_min)
        else:
            ok_cos = jnp.bool_(True)
        ok = finite & ok_z & ok_cos
        reason = jnp.where(
            ~finite, 1, jnp.where(~ok_z, 2, jnp.where(~ok_cos, 3, 0)))
        teach_stats = finite & teaches
        delta = nd - mu
        incr = jnp.float32(ema) * delta
        mu1 = jnp.where(n_acc > 0.0, mu + incr, nd)
        m21 = jnp.where(n_acc > 0.0,
                        (1.0 - jnp.float32(ema)) * (m2 + delta * incr),
                        jnp.float32(0.0))
        new_n = jnp.where(teach_stats, n_acc + 1.0, n_acc)
        new_mu = jnp.where(teach_stats, mu1, mu)
        new_m2 = jnp.where(teach_stats, m21, m2)
        if cos_on:
            ref1 = jnp.where(n_acc > 0.0, (1.0 - ema) * ref + ema * d, d)
            new_ref = jnp.where(ok, ref1, ref)
        else:
            new_ref = ref
        return clipped, ok, reason, new_ref, new_n, new_mu, new_m2

    return stages


def make_admission_fn(cfg: DefenseConfig):
    """Build the standalone jitted admission step (unit tests and
    callers without a streaming buffer; production ingestion uses the
    fused make_screened_fold_fn):

        admit(row [P], g [P], ref [P], n_acc, mu, m2)
            -> (clipped_row [P], admit_flag, reason_code,
                new_ref, new_n_acc, new_mu, new_m2)

    The stage math is _make_stage_fn — ONE definition with the fused
    path.  The reference state (ref, n_acc, mu, m2) is donated."""
    stages = _make_stage_fn(cfg)
    return obs_programs.instrument(
        "async_admission",
        jax.jit(stages, donate_argnums=(2, 3, 4, 5)))


def make_screened_fold_fn(cfg: DefenseConfig, staleness_mode: str,
                          staleness_a: float, staleness_b: float):
    """Fused admission + streaming fold — the production hot path:

        sfold(acc, wsum, row, g, ref, n_acc, mu, m2, weight, staleness)
            -> (acc', wsum', ok, reason, ref', n', mu', m2')

    One jitted dispatch per arrival instead of screen-then-fold: the
    _make_stage_fn stages run fused with the staleness-discounted
    accumulate, and the accumulator update is conditional IN-program
    (``where(ok, acc + w̃·clipped, acc)``), so a quarantined row costs
    the same single dispatch and leaves the accumulator bit-untouched.
    Measured: the unfused two-dispatch pipeline cost ~0.5x of the PR-6
    ingest rate (two serialized O(P) programs + two host syncs under
    the manager lock); fused, the screen rides the fold's pass and the
    ≥0.9x overhead gate holds.  `acc`, `wsum` and the reference state
    are donated — everything updates in place."""
    from fedml_tpu.async_.staleness import staleness_weight
    stages = _make_stage_fn(cfg)

    def sfold(acc, wsum, row, g, ref, n_acc, mu, m2, weight, staleness):
        clipped, ok, reason, new_ref, new_n, new_mu, new_m2 = stages(
            row, g, ref, n_acc, mu, m2)
        # the PR-6 fold, gated: bitwise staleness.make_fold_fn's ops on
        # the accepted path (same λ, same multiply-add)
        lam = staleness_weight(staleness_mode, staleness, staleness_a,
                               staleness_b)
        wt = jnp.asarray(weight, jnp.float32) * lam
        # a quarantined row's (possibly NaN) contribution is computed
        # then discarded by the select — acc stays bit-identical
        acc1 = jnp.where(ok, acc + wt * clipped, acc)
        wsum1 = jnp.where(ok, wsum + wt, wsum)
        return acc1, wsum1, ok, reason, new_ref, new_n, new_mu, new_m2

    # ISSUE 12: the fused screen+fold is its own profile family —
    # its dispatch wall vs async_fold's IS the admission tax, live
    return obs_programs.instrument(
        "async_screened_fold",
        jax.jit(sfold, donate_argnums=(0, 1, 4, 5, 6, 7)))


class UpdateAdmission:
    """Stateful admission gate: wraps the jitted step with the running
    reference, the quarantine accounting, and the obs wiring.  One
    instance per server; callers serialize under the server lock (the
    running-reference state is ordered, like the fold it guards).

    Staleness-aware (the ROADMAP item-4 "stale adversarial updates"
    edge): the gate keeps the last `GLOBAL_WINDOW` committed globals
    (flat rows) and screens each uplink against the global its sender
    TRAINED FROM (the echoed dispatch version) — a stale honest
    update's delta is then its actual local step, not local step plus
    several commits of server drift.  Without this, stale honest
    updates read as norm/direction anomalies (false positives) while
    the drift-inflated statistics let genuinely hostile rows through;
    with it, the accepted-norm distribution stays tight across
    staleness and a boosted row is an unambiguous outlier.  Memory is
    O(GLOBAL_WINDOW·P); versions older than the window fall back to
    the oldest kept global (bounded drift, conservative)."""

    GLOBAL_WINDOW = 16

    def __init__(self, cfg: DefenseConfig, p: int):
        self.cfg = cfg
        self.p = p
        self._admit = make_admission_fn(cfg)
        self._sfold = None               # fused hot path, bound lazily
        self._ref = jnp.zeros((p,), jnp.float32)
        self._n = jnp.zeros((), jnp.float32)
        self._mu = jnp.zeros((), jnp.float32)
        self._m2 = jnp.zeros((), jnp.float32)
        self._globals: "dict[int, jax.Array]" = {}
        self.accepted = 0
        self.quarantined: dict[str, int] = {}
        self.quarantine_log: list[tuple] = []       # (sender, reason)
        self._m_hist = obs.histogram(
            "defense_screen_seconds",
            buckets=obs.metrics.DECODE_SECONDS_BUCKETS)
        self._m_quar = {
            r: obs.counter("async_updates_quarantined_total", reason=r)
            for r in QUARANTINE_REASONS}

    def note_global(self, version: int, global_row) -> None:
        """Record the flat global at `version` (call at init and after
        every commit); evicts beyond GLOBAL_WINDOW."""
        self._globals[int(version)] = global_row
        while len(self._globals) > self.GLOBAL_WINDOW:
            del self._globals[min(self._globals)]

    def _global_for(self, version: Optional[int]):
        if version is not None and int(version) in self._globals:
            return self._globals[int(version)]
        if self._globals:
            # older than the window (or unknown): the oldest kept
            # global bounds the drift better than the newest
            return self._globals[min(self._globals)]
        return jnp.zeros((self.p,), jnp.float32)

    def screen(self, row, global_row=None, sender: int = -1,
               version: Optional[int] = None):
        """Run one row through the pipeline.  Returns (admitted: bool,
        reason: str — "ok" or a QUARANTINE_REASONS entry, clipped_row)
        — clipped_row is a device array ready for the buffer fold
        (None when quarantined).  `version` selects the recorded
        global the sender trained from (preferred); `global_row`
        overrides it explicitly."""
        if global_row is None:
            global_row = self._global_for(version)
        t0 = time.perf_counter()
        with obs.span("defense.screen", sender=sender):
            out_row, ok, reason, self._ref, self._n, self._mu, self._m2 = \
                self._admit(jnp.asarray(row, jnp.float32), global_row,
                            self._ref, self._n, self._mu, self._m2)
            admitted = bool(ok)
        self._m_hist.observe(time.perf_counter() - t0)
        if admitted:
            self.accepted += 1
            return True, "ok", out_row
        return False, self._quarantine(sender, reason), None

    def _quarantine(self, sender: int, reason) -> str:
        """ONE quarantine-accounting path (counter + reason-labeled obs
        + bounded log + flight-recorder instant) for both the
        standalone screen and the fused fold."""
        why = QUARANTINE_REASONS[int(reason) - 1]
        self.quarantined[why] = self.quarantined.get(why, 0) + 1
        if len(self.quarantine_log) < 50_000:
            self.quarantine_log.append((int(sender), why))
        self._m_quar[why].inc()
        # the flight recorder's ring picks this up, so a dump names the
        # quarantined sender and the stage that rejected it
        obs.instant("defense.quarantine", sender=sender, reason=why)
        log.debug("quarantined update from %s: %s", sender, why)
        return why

    def bind_fold(self, staleness_mode: str, staleness_a: float,
                  staleness_b: float) -> None:
        """Build the fused admission+fold program (make_screened_fold_fn)
        for the buffer's staleness family — called once by the server
        that owns both."""
        self._sfold = make_screened_fold_fn(self.cfg, staleness_mode,
                                            staleness_a, staleness_b)

    def screened_fold(self, acc, wsum, row, weight: float,
                      staleness: float, sender: int = -1,
                      version: Optional[int] = None):
        """The fused hot path: one dispatch screens `row` and folds the
        (clipped) accepted contribution into (acc, wsum).  Returns
        (ok, reason, acc', wsum') — on quarantine acc'/wsum' carry the
        UNCHANGED values (in freshly-donated buffers) and the
        accounting mirrors screen()."""
        assert self._sfold is not None, "bind_fold() first"
        g = self._global_for(version)
        t0 = time.perf_counter()
        with obs.span("defense.screen", sender=sender):
            (acc1, wsum1, ok, reason, self._ref, self._n, self._mu,
             self._m2) = self._sfold(
                acc, wsum, jnp.asarray(row, jnp.float32), g, self._ref,
                self._n, self._mu, self._m2, np.float32(weight),
                np.float32(staleness))
            admitted = bool(ok)
        self._m_hist.observe(time.perf_counter() - t0)
        if admitted:
            self.accepted += 1
            return True, "ok", acc1, wsum1
        return False, self._quarantine(sender, reason), acc1, wsum1

    def state(self) -> dict:
        """Checkpointable running-reference snapshot (crash-resume: a
        resumed server keeps its armed screen instead of re-warming
        against a possibly-hostile cohort).  The quarantine counters
        ride along so the attack accounting (reports, bench gates)
        survives a resume too — only the bounded (sender, reason) debug
        log resets."""
        return {"ref": np.asarray(self._ref, np.float32).copy(),
                "n_acc": np.asarray(self._n, np.float32).copy(),
                "mu": np.asarray(self._mu, np.float32).copy(),
                "m2": np.asarray(self._m2, np.float32).copy(),
                "accepted": np.asarray(self.accepted, np.int64),
                "quarantined": np.asarray(
                    [self.quarantined.get(r, 0)
                     for r in QUARANTINE_REASONS], np.int64)}

    def load_state(self, state: dict) -> None:
        ref = np.asarray(state["ref"], np.float32)
        if ref.shape != (self.p,):
            raise ValueError(f"admission state shape mismatch: checkpoint "
                             f"ref {ref.shape} vs configured ({self.p},)")
        # copy=True: the donated admission step must never free orbax's
        # buffer (same alias hazard as AsyncBuffer.load_state)
        self._ref = jnp.array(ref, copy=True)
        self._n = jnp.array(np.asarray(state["n_acc"], np.float32),
                            copy=True)
        self._mu = jnp.array(np.asarray(state["mu"], np.float32), copy=True)
        self._m2 = jnp.array(np.asarray(state["m2"], np.float32), copy=True)
        self.accepted = int(state["accepted"])
        if "quarantined" in state:
            counts = np.asarray(state["quarantined"], np.int64)
            self.quarantined = {
                r: int(c) for r, c in zip(QUARANTINE_REASONS, counts)
                if int(c) > 0}

    def report(self) -> dict:
        return {"accepted": self.accepted,
                "quarantined": dict(self.quarantined),
                "quarantined_total": sum(self.quarantined.values())}
