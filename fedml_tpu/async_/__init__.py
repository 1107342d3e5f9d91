"""Async federation subsystem — buffered staleness-aware aggregation.

Six layers (module docstrings have the full design):

  staleness.py   staleness-discount weight families (constant /
                 polynomial / hinge), the flat-carry [K, P] buffer —
                 drain mode and streaming aggregation-on-arrival (the
                 jitted donated fold + O(P) stream commit, ISSUE 6),
                 the SEEDED bucketed robust streaming commit (ISSUE 9)
                 — and the RowLayout the decode-into fast path targets
  adversary.py   seeded adversarial client simulator (ISSUE 9):
                 sign-flip / boosted model-replacement / gaussian /
                 label-flip / backdoor / colluding / stale-timed
                 byzantine cohorts riding the PR-5 lifecycle
  defense.py     update admission pipeline at the ONE insert path:
                 finite canary -> shared-definition norm clip ->
                 z/cosine anomaly screen, quarantine accounting, and
                 the DP-FedAvg configuration
  scheduler.py   AsyncFedAvgEngine — event-driven virtual-time
                 scheduler (FedBuff semi-async; FedAsync at K=1) with
                 dispatch-wave vmapped training
  lifecycle.py   seeded client-lifecycle simulator (latency / dropout /
                 rejoin / crash) + the AsyncServerManager /
                 AsyncClientManager FSM pair over the comm backends,
                 with the bounded parallel-decode ingest pool
  torture.py     concurrent-uplink ingestion torture harness
                 (run_ingest_torture; tests/test_async_messaging.py)
"""
from fedml_tpu.async_.adversary import (ATTACK_MODES, AdversarySim,
                                        AttackConfig, apply_data_attack)
from fedml_tpu.async_.defense import (DefenseConfig, QUARANTINE_REASONS,
                                      UpdateAdmission)
from fedml_tpu.async_.lifecycle import (AsyncClientManager, AsyncMessage,
                                        AsyncServerManager, ClientLifecycle,
                                        LifecycleConfig,
                                        run_async_messaging)
from fedml_tpu.async_.scheduler import AsyncFedAvgEngine
from fedml_tpu.async_.staleness import (AsyncBuffer, BUCKET_COMBINE_MODES,
                                        RowLayout, STALENESS_MODES,
                                        make_bucket_commit_fn,
                                        make_commit_fn, make_drain_fold_fn,
                                        make_fold_fn, make_stream_commit_fn,
                                        staleness_weight)
from fedml_tpu.async_.torture import run_ingest_torture

__all__ = [
    "ATTACK_MODES", "AdversarySim", "AsyncBuffer", "AsyncClientManager",
    "AsyncFedAvgEngine", "AsyncMessage", "AsyncServerManager",
    "AttackConfig", "BUCKET_COMBINE_MODES", "ClientLifecycle",
    "DefenseConfig", "LifecycleConfig", "QUARANTINE_REASONS", "RowLayout",
    "STALENESS_MODES", "UpdateAdmission", "apply_data_attack",
    "make_bucket_commit_fn", "make_commit_fn", "make_drain_fold_fn",
    "make_fold_fn", "make_stream_commit_fn", "run_async_messaging",
    "run_ingest_torture", "staleness_weight",
]
