"""The measured window: the loop body of ``FedAvgEngine.run()`` and nothing
else, with a bounded number of rounds in flight.

    rng  = fold_in(PRNGKey(seed + 1), r)
    args = engine._round_args(r)
    variables, server_state, m = engine.round_fn(variables, server_state,
                                                 *args, rng)

``run()`` never blocks between evaluations; the harness blocks on round
r's train loss (not donated) before it dispatches round r + depth, and stamps
the completion there.  That is the one place it is stricter than ``run()``.
"""
from __future__ import annotations

import collections
import math
import time
import traceback

import jax


class State:
    """What the loop carries from call to call: warm-up, traced window and
    measured window are one sequence of rounds on one engine."""

    def __init__(self, engine, variables, seed: int):
        self.engine = engine
        self.variables = engine._prepare_variables(variables)
        self.server_state = engine.server_init(self.variables)
        self.rng_base = jax.random.PRNGKey(seed + 1)
        self.next_round = 0


def run_rounds(state: State, depth: int, *, seconds=None, rounds=None) -> dict:
    """Dispatch rounds until the clock passes ``seconds`` (or ``rounds`` were
    dispatched), wait for the last one, and return the window's record."""
    eng, Ann = state.engine, jax.profiler.TraceAnnotation
    pending = collections.deque()
    done_t, losses, t_args, t_dispatch, t_wait = [], [], [], [], []
    failed = attempted = 0

    def wait_oldest():
        loss = pending.popleft()
        t = time.perf_counter()
        with Ann("wait_round"):
            losses.append(float(loss))
        now = time.perf_counter()
        t_wait.append(now - t)
        done_t.append(now)

    t0 = time.perf_counter()
    while True:
        if len(pending) >= depth:
            wait_oldest()
        if rounds is not None and attempted >= rounds:
            break
        if seconds is not None and time.perf_counter() - t0 >= seconds:
            break
        r = state.next_round
        t1 = time.perf_counter()
        try:
            with Ann("sample+args"):
                rng = jax.random.fold_in(state.rng_base, r)
                args = eng._round_args(r)
            t2 = time.perf_counter()
            with Ann("dispatch"):
                state.variables, state.server_state, m = eng.round_fn(
                    state.variables, state.server_state, *args, rng)
            t3 = time.perf_counter()
        except Exception:
            # a round that raises cannot be retried on donated buffers: the
            # window ends here and the run reports it as failed
            failed += 1
            attempted += 1
            traceback.print_exc()
            break
        t_args.append(t2 - t1)
        t_dispatch.append(t3 - t2)
        pending.append(m["train_loss"])
        attempted += 1
        state.next_round = r + 1
    while pending:
        wait_oldest()
    failed += sum(not math.isfinite(v) for v in losses)
    return {"t0": t0, "done_t": done_t, "losses": losses,
            "attempted": attempted, "failed": failed,
            "elapsed_s": (done_t[-1] - t0) if done_t else 0.0,
            "args_s": t_args, "dispatch_s": t_dispatch, "wait_s": t_wait}


def join_prefetch(engine) -> None:
    """The window leaves ``_rounds_limit`` unset so the streaming prefetch
    never stops; join the upload that is still in flight before exit."""
    pre = getattr(engine, "_prefetched", None)
    if pre is not None and hasattr(pre[1], "result"):
        try:
            jax.block_until_ready(pre[1].result())
        except Exception:
            # the upload was for a round the window never ran: report it,
            # it cannot change a result
            traceback.print_exc()
        engine._prefetched = None
