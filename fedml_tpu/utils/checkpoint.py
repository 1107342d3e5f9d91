"""Round-level checkpoint/resume via orbax.

The reference has essentially no FL-state checkpointing (SURVEY.md §5:
FedGKT saves a server .pth.tar, DARTS saves genotypes, nothing resumes a
round).  Here any engine's (variables, server_state, round_idx) checkpoints
atomically every N rounds and training resumes exactly — the deterministic
per-round client sampler (the reference's draw for round_idx, from a
private generator: core/sampling.py) makes a resumed run
bitwise-identical to an uninterrupted one.
"""
from __future__ import annotations

import os
from typing import Any, Optional

import numpy as np

Pytree = Any

try:
    import orbax.checkpoint as ocp
    _HAVE_ORBAX = True
except Exception:                      # pragma: no cover
    _HAVE_ORBAX = False


class FedCheckpointManager:
    """Save/restore (round_idx, variables, server_state) under `directory`.

    Thin wrapper over orbax's CheckpointManager: keeps `max_to_keep`
    newest rounds, atomic renames, async-safe.  `server_state` may be any
    pytree (optax states included); restore needs the matching template
    structure, which every engine can produce via server_init."""

    def __init__(self, directory: str, max_to_keep: int = 3):
        if not _HAVE_ORBAX:
            raise RuntimeError("orbax is not available in this environment")
        self.directory = os.path.abspath(directory)
        self._mgr = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(max_to_keep=max_to_keep,
                                                 create=True))

    def save(self, round_idx: int, variables: Pytree,
             server_state: Pytree = (),
             extra_state: Optional[Pytree] = None) -> None:
        """`extra_state` carries engine-specific round state beyond the
        (variables, server_state) pair — the async engine checkpoints
        its aggregation-buffer contents and per-client staleness
        counters through it (fedml_tpu/async_/scheduler.py
        async_state()).  Only written when provided, so synchronous
        checkpoints keep their existing on-disk structure."""
        state = {"variables": variables,
                 "server_state": _wrap_empty(server_state)}
        if extra_state is not None:
            state["extra_state"] = extra_state
        self._mgr.save(round_idx, args=ocp.args.StandardSave(state))
        self._mgr.wait_until_finished()

    def latest_round(self) -> Optional[int]:
        return self._mgr.latest_step()

    def restore(self, variables_template: Pytree,
                server_state_template: Pytree = (),
                round_idx: Optional[int] = None,
                extra_template: Optional[Pytree] = None):
        """Returns (round_idx, variables, server_state); templates define
        the pytree structure/dtypes (pass engine.init_variables() /
        engine.server_init(v)).  With `extra_template` the checkpoint's
        extra_state is restored too and a 4-tuple is returned — only
        for checkpoints that were saved with one."""
        step = round_idx if round_idx is not None else self.latest_step_or_raise()
        template = {"variables": variables_template,
                    "server_state": _wrap_empty(server_state_template)}
        if extra_template is not None:
            template["extra_state"] = extra_template
        out = self._mgr.restore(step, args=ocp.args.StandardRestore(template))
        if extra_template is not None:
            return (step, out["variables"],
                    _unwrap_empty(out["server_state"]), out["extra_state"])
        return step, out["variables"], _unwrap_empty(out["server_state"])

    def latest_step_or_raise(self) -> int:
        step = self._mgr.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self.directory}")
        return step

    def close(self):
        self._mgr.close()


def _wrap_empty(tree: Pytree):
    # orbax rejects totally-empty pytrees (e.g. FedAvg's () server state);
    # carry a sentinel leaf alongside
    return {"state": tree, "_nonempty": np.zeros((1,), np.int32)}


def _unwrap_empty(wrapped):
    return wrapped["state"]
