"""Async messaging FSM tests (fedml_tpu/async_/lifecycle.py) + the
comm-manager shutdown satellite.

The real-thread path: AsyncServerManager/AsyncClientManager over the
in-proc router — frames go through MessageCodec, so the wire codec and
the per-backend byte/message counters see genuine async traffic; the
lifecycle simulator injects crashes (dropped replies) and latencies
(real, millisecond-scale sleeps here).  Ordering is thread-scheduled,
so these tests assert PROTOCOL invariants (commit counts, staleness
recorded, recovery under loss), not bitwise values — the deterministic
pins live in test_async.py's virtual-time path.
"""
import threading
import time

import jax
import numpy as np
import pytest

from fedml_tpu import obs
from fedml_tpu.async_ import (ClientLifecycle, LifecycleConfig,
                              run_async_messaging)
from fedml_tpu.comm import ClientManager, InProcRouter, Message

from parallel_case import _mnist_like_cfg, _setup


def _small_setup(n_clients=4):
    cfg = _mnist_like_cfg(client_num_in_total=n_clients,
                          client_num_per_round=n_clients, comm_round=4)
    trainer, data = _setup(cfg)
    return cfg, trainer, data


def test_async_messaging_commits_and_staleness_over_wire():
    """4 workers, buffer of 2: the server reaches its commit budget and
    the staleness accounting sees the version lag a 2-of-4 buffer
    necessarily produces; every payload crossed the codec (byte
    counters moved)."""
    cfg, trainer, data = _small_setup()
    sent0 = obs.counter("comm_sent_bytes_total", backend="inproc").value
    v, server = run_async_messaging(trainer, data, cfg, buffer_k=2,
                                    total_commits=4, timeout_s=120)
    assert server.version == 4
    assert len(server.staleness_seen) >= 8      # 4 commits x K=2
    assert all(s >= 0.0 for s in server.staleness_seen)
    assert np.isfinite(float(jax.tree.leaves(v)[0].ravel()[0]))
    sent1 = obs.counter("comm_sent_bytes_total", backend="inproc").value
    assert sent1 > sent0                        # real frames, real codec


def test_async_messaging_crash_recovers_via_deadline():
    """One worker crashes on EVERY dispatch while the healthy one is
    slow relative to the deadline: the buffer can never reach K inside
    a deadline window, so every commit is a deadline (partial) commit —
    and the federation still reaches its budget.  Crash-mid-round is
    survivable, not fatal."""
    cfg, trainer, data = _small_setup(n_clients=2)

    class CrashOne(ClientLifecycle):
        def draw_crash(self, client_id):
            return client_id == 1               # a permanently dead device

        def draw_latency(self, client_id):
            return 0.4                          # slow vs the 0.05 deadline

    lc = CrashOne(LifecycleConfig(seed=0), 2)
    v, server = run_async_messaging(trainer, data, cfg, buffer_k=2,
                                    total_commits=3, worker_num=2,
                                    deadline_s=0.05, timeout_s=60,
                                    lifecycle=lc)
    assert server.version == 3
    assert server.partial_commits >= 1          # deadline path exercised
    assert server.buffer.count == 0


def test_async_messaging_stall_dumps_flight_and_raises(tmp_path):
    """EVERY worker crashes on every dispatch and no deadline is set:
    the launcher must dump the flight recorder (scheduler-deadlock
    artifact) and raise, never hang."""
    cfg, trainer, data = _small_setup(n_clients=2)

    class CrashAll(ClientLifecycle):
        def draw_crash(self, client_id):
            return True

    obs.reset()
    obs.configure(str(tmp_path), install_signal=False,
                  export_at_exit=False)
    try:
        with pytest.raises(TimeoutError, match="async federation stalled"):
            run_async_messaging(
                trainer, data, cfg, buffer_k=2, total_commits=2,
                worker_num=2, timeout_s=1.5,
                lifecycle=CrashAll(LifecycleConfig(seed=0), 2))
        import json
        reasons = [json.load(open(d))["reason"]
                   for d in obs.flight().dumps]
        assert any("async_scheduler_deadlock" in r for r in reasons), reasons
    finally:
        obs.reset()


# -- comm-manager shutdown satellite ----------------------------------------

class _Echo(ClientManager):
    def register_message_receive_handlers(self):
        self.register_message_receive_handler(1, lambda msg: None)


def test_manager_finish_joins_thread_and_guards_sends():
    """ISSUE-5 satellite: finish() must JOIN the run_async() receive
    thread (bounded), be idempotent, and close the manager so a late
    send fails loudly instead of racing the closed transport."""
    router = InProcRouter()
    m = _Echo(0, 1, "INPROC", router=router)
    t = m.run_async()
    assert t.is_alive()
    m.send_message(Message(1, 0, 0))            # open manager: sends fine
    m.finish()
    assert not t.is_alive(), "finish() did not join the receive thread"
    with pytest.raises(RuntimeError, match="after finish"):
        m.send_message(Message(1, 0, 0))
    m.finish()                                  # idempotent, no raise
    assert not t.is_alive()


def test_manager_finish_mid_handler_drops_send_not_crash():
    """The one benign closed-send race: finish() lands while a handler
    is still in flight; the handler's reply must be DROPPED with a log
    (pre-guard behavior), not raise through the receive loop and kill
    the thread mid-teardown."""
    router = InProcRouter()
    entered = threading.Event()
    sent_after_close = {"raised": False}

    class SlowEcho(ClientManager):
        def register_message_receive_handlers(self):
            self.register_message_receive_handler(5, self._echo)

        def _echo(self, msg):
            entered.set()
            time.sleep(0.3)                  # finish() lands here
            try:
                self.send_message(Message(5, 0, 0))
            except BaseException:
                sent_after_close["raised"] = True
                raise

    m = SlowEcho(0, 1, "INPROC", router=router)
    t = m.run_async()
    router.route(Message(5, 0, 0))
    assert entered.wait(2.0)
    m.finish()                               # while the handler sleeps
    t.join(timeout=5.0)
    assert not t.is_alive()                  # loop exited cleanly
    assert sent_after_close["raised"]        # the guard did fire...
    # ...but was downgraded at the dispatch chokepoint — the thread
    # died by sentinel, not by exception (join above proves it)


def test_manager_finish_from_handler_thread_does_not_self_join():
    """A manager that finishes ITSELF from inside its own handler (the
    async client's STOP path) must not deadlock trying to join its own
    thread — the loop exits and the thread dies on its own."""
    router = InProcRouter()
    done = threading.Event()

    class SelfStop(ClientManager):
        def register_message_receive_handlers(self):
            self.register_message_receive_handler(9, self._stop)

        def _stop(self, msg):
            self.finish()
            done.set()

    m = SelfStop(0, 1, "INPROC", router=router)
    t = m.run_async()
    router.route(Message(9, 0, 0))
    assert done.wait(timeout=5.0)
    t.join(timeout=5.0)
    assert not t.is_alive()


# -- ISSUE 6: parallel ingest + the torture bench ---------------------------

def test_async_messaging_ingest_pool_commits_over_wire():
    """The decode-pool path end-to-end over the inproc wire: raw frames
    reach the sink on the router's delivery path, decode-into fills
    scratch rows off the FSM thread, streaming folds commit — protocol
    invariants hold and the pool drains to depth 0 at the end."""
    cfg, trainer, data = _small_setup()
    v, server = run_async_messaging(trainer, data, cfg, buffer_k=2,
                                    total_commits=4, streaming=True,
                                    ingest_pool=2, decode_into=True,
                                    timeout_s=120)
    assert server.version == 4
    assert server.updates_committed >= 8
    assert np.isfinite(float(jax.tree.leaves(v)[0].ravel()[0]))
    assert obs.gauge("async_ingest_pool_depth").value == 0
    # the ingest path timed its decodes
    h = obs.histogram("comm_decode_seconds", backend="inproc")
    assert h.cumulative()[-1][1] > 0


def test_async_messaging_streaming_tracks_legacy_drain():
    """Streaming aggregation-on-arrival and the PR-5 drain path agree
    on the protocol outcome over the wire (same commit budget reached,
    finite variables, comparable discount accounting).  The BITWISE
    streaming-vs-drain pin lives in test_async.py; thread scheduling
    makes wire-path arrival ORDER nondeterministic, so this asserts
    invariants, not bits."""
    cfg, trainer, data = _small_setup()
    outs = {}
    for streaming in (False, True):
        v, server = run_async_messaging(trainer, data, cfg, buffer_k=2,
                                        total_commits=3,
                                        streaming=streaming, timeout_s=120)
        assert server.version == 3
        outs[streaming] = np.asarray(jax.tree.leaves(v)[0])
    assert np.isfinite(outs[False]).all() and np.isfinite(outs[True]).all()


def _torture_kw(**over):
    kw = dict(n_clients=3, backend="INPROC", p=512, buffer_k=2, commits=4,
              warmup_commits=1, ingest_pool=2, decode_into=True,
              streaming=True, timeout_s=90)
    kw.update(over)
    return kw


def test_ingest_torture_smoke_streaming():
    """Fast torture smoke (3 inproc clients, 512-element rows): the
    harness reaches its commit budget, reports the ISSUE-6 metrics, and
    the committed variables stay finite under concurrent folds."""
    from fedml_tpu.async_ import run_ingest_torture
    r = run_ingest_torture(**_torture_kw())
    assert r["finite"]
    assert r["committed_updates_per_sec"] > 0
    assert r["updates_committed"] >= 4 * 2 - 2   # commits x K, pads allowed
    assert r["decode_p95_s"] >= r["decode_p50_s"] >= 0.0
    assert r["lock_wait_seconds"] >= 0.0
    assert r["p"] == 512 and r["n_clients"] == 3


def test_ingest_torture_smoke_legacy_arm():
    """The A/B's legacy arm (inline decode + drained O(K·P) commit)
    still runs green — the ingest A/B needs both arms."""
    from fedml_tpu.async_ import run_ingest_torture
    r = run_ingest_torture(**_torture_kw(ingest_pool=0, decode_into=False,
                                         streaming=False))
    assert r["finite"] and r["committed_updates_per_sec"] > 0
    assert not r["decode_into"] and not r["streaming"]


@pytest.mark.slow
def test_ingest_torture_32_clients_tcp_speedup():
    """NIGHTLY: the acceptance-gate shape — 32 concurrent TCP uplinks,
    decode-into + streaming vs the PR-5 legacy path (faithfully
    unbounded inbox and all).  The gate demands >=2x sustained
    committed-updates/sec; on the 2-core CI box the measured gap is
    >25x in every repeat (PERF.md "Uplink ingestion"), so 2x has huge
    margin without being timing-flaky."""
    from fedml_tpu.async_ import run_ingest_torture
    legacy = run_ingest_torture(n_clients=32, backend="TCP", buffer_k=8,
                                commits=10, warmup_commits=2,
                                ingest_pool=0, decode_into=False,
                                streaming=False, base_port=53270,
                                timeout_s=300)
    fast = run_ingest_torture(n_clients=32, backend="TCP", buffer_k=8,
                              commits=10, warmup_commits=2,
                              ingest_pool=1, decode_into=True,
                              streaming=True, base_port=53271,
                              timeout_s=300)
    assert legacy["finite"] and fast["finite"]
    assert (fast["committed_updates_per_sec"]
            >= 2.0 * legacy["committed_updates_per_sec"]), (legacy, fast)


# -- ISSUE 7: federation-wide tracing acceptance -----------------------------

def _timeline_tool(*argv):
    """Invoke tools/trace_timeline.py's main() in-process."""
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "trace_timeline.py")
    spec = importlib.util.spec_from_file_location("trace_timeline", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.main(list(argv))


def _traced_async_acceptance(tmp_path, backend, **backend_kw):
    """ISSUE-7 acceptance body: a traced async run over `backend`, then
    tools/trace_timeline.py on its obs dir — the merged Chrome trace
    must load, the critical path must cover every commit, and each
    round's stage sum must land within 10% of the measured round wall
    (exact by construction: the residual books as `wait`)."""
    import json
    import os
    obs.reset()
    obs.configure(str(tmp_path), install_signal=False,
                  export_at_exit=False)
    try:
        cfg, trainer, data = _small_setup(n_clients=2)
        v, server = run_async_messaging(
            trainer, data, cfg, buffer_k=2, total_commits=3,
            worker_num=2, backend=backend, timeout_s=120, **backend_kw)
        assert server.version == 3
        assert np.isfinite(float(jax.tree.leaves(v)[0].ravel()[0]))
        # trace blocks crossed the wire and were stripped + accounted
        bname = server.com_manager.backend_name
        assert obs.counter("trace_frames_total",
                           backend=bname).value > 0
        # the clients' piggybacked metric deltas folded as ONE cohort
        # label set (origin="remote"), not per-client labels
        remote = [k for k in obs.registry().snapshot()
                  if 'origin="remote"' in k]
        assert remote, "no piggybacked client metrics folded"
        paths = obs.export()
        assert "jsonl_trace" in paths
        rc = _timeline_tool(str(tmp_path))
        assert rc == 0
        merged = json.load(open(tmp_path / "merged.chrome.json"))
        names = {e.get("name") for e in merged["traceEvents"]}
        assert "async.commit" in names and "trace.recv" in names
        # the synthetic critical-path lanes render next to raw spans
        assert any(
            e.get("ph") == "M"
            and (e.get("args") or {}).get("name") == "round critical path"
            for e in merged["traceEvents"])
        report = json.load(open(tmp_path / "critical_path.json"))
        assert report["n_rounds"] == 3
        for r in report["rounds"]:
            stage_sum = sum(r["stages"].values())
            assert abs(stage_sum - r["wall_s"]) <= 0.10 * r["wall_s"], r
        # the federated stages appear: client train + server commit
        assert report["stage_totals_s"].get("train", 0) > 0
        assert report["stage_totals_s"].get("commit", 0) > 0
        assert report["p95_attribution"]["stage"] in report[
            "stage_totals_s"]
        return report
    finally:
        obs.reset()


def test_trace_timeline_acceptance_inproc(tmp_path):
    _traced_async_acceptance(tmp_path, "INPROC")


def test_trace_timeline_acceptance_tcp(tmp_path):
    """The same acceptance over real sockets: trace blocks ride TCP
    frames, the per-peer clock sync sees both directions (server
    dispatches + client uplinks), and the timeline tool merges the
    single-process trace of a multi-socket run."""
    report = _traced_async_acceptance(
        tmp_path, "TCP", force_python_tcp=True,
        ip_config={0: "127.0.0.1", 1: "127.0.0.1", 2: "127.0.0.1"},
        base_port=53290)
    # sockets add genuine transit: some wall books as wait
    assert "wait" in report["stage_totals_s"]


# -- ISSUE 8: chaos-hardened federation --------------------------------------

def test_chaos_torture_smoke_reliable_tcp():
    """Fast chaos smoke over real sockets: 3 reliable uplink pushers vs
    10% loss + 5% dup + 5% corrupt injected at the server's receive
    chokepoint — every commit lands, the variables stay finite, faults
    were actually injected, and ZERO recv threads died (quarantine +
    resend carried the faults)."""
    from fedml_tpu.async_ import run_ingest_torture
    from fedml_tpu.comm.reliability import BackoffPolicy
    r = run_ingest_torture(
        n_clients=3, backend="TCP", p=512, buffer_k=2, commits=4,
        warmup_commits=1, ingest_pool=2, decode_into=True,
        streaming=True, base_port=53340, timeout_s=120, reliable=True,
        chaos={"drop": 0.10, "dup": 0.05, "corrupt": 0.05},
        reliable_backoff=BackoffPolicy(base_s=0.05, max_s=0.5))
    assert r["finite"]
    assert r["committed_updates_per_sec"] > 0
    assert r["recv_thread_deaths"] == 0, r
    assert sum(r["chaos_injected"].values()) >= 1, r["chaos_injected"]
    assert r["acks"] > 0                    # the envelope round-tripped
    assert r["reliable"] and r["chaos"]["drop"] == 0.10


def test_chaos_torture_dedup_protects_commit_count():
    """dup-heavy chaos (30% duplicate) with the ledger on: every commit
    still aggregates exactly buffer_k DISTINCT updates — duplicates are
    suppressed at the chokepoint (counted), never folded twice."""
    from fedml_tpu.async_ import run_ingest_torture
    from fedml_tpu.comm.reliability import BackoffPolicy
    r = run_ingest_torture(
        n_clients=3, backend="INPROC", p=512, buffer_k=2, commits=4,
        warmup_commits=1, ingest_pool=0, decode_into=False,
        streaming=True, timeout_s=90, reliable=True,
        chaos={"dup": 0.30},
        reliable_backoff=BackoffPolicy(base_s=0.05, max_s=0.5))
    assert r["finite"]
    assert r["dups_suppressed"] >= 1, r
    assert r["recv_thread_deaths"] == 0


@pytest.mark.slow
def test_chaos_torture_32_clients_tcp_goodput_gate():
    """NIGHTLY acceptance (ISSUE 8): 32 reliable TCP uplink clients
    under 5% loss + 1% dup + 0.5% corrupt — all rounds commit,
    committed-updates/sec >= 0.5x the clean reliable arm, and zero
    recv-thread deaths."""
    from fedml_tpu.async_ import run_ingest_torture
    kw = dict(n_clients=32, backend="TCP", buffer_k=8, commits=10,
              warmup_commits=2, ingest_pool=4, decode_into=True,
              streaming=True, timeout_s=600, reliable=True)
    clean = run_ingest_torture(base_port=53350, **kw)
    fault = run_ingest_torture(
        base_port=53352,
        chaos={"drop": 0.05, "dup": 0.01, "corrupt": 0.005}, **kw)
    assert clean["finite"] and fault["finite"]
    assert fault["recv_thread_deaths"] == 0, fault
    assert sum(fault["chaos_injected"].values()) >= 1
    assert (fault["committed_updates_per_sec"]
            >= 0.5 * clean["committed_updates_per_sec"]), (clean, fault)


def test_async_crash_resume_over_tcp(tmp_path):
    """ISSUE-8 crash-resume e2e over real TCP: kill the async server
    mid-round (no STOP broadcast, transport torn down), rebuild it on
    the SAME port from the orbax checkpoint, and the surviving clients
    re-handshake — the run completes its full commit budget with finite
    params.  The clients' reliable resends carry the dead-server
    window."""
    import tempfile
    cfg, trainer, data = _small_setup(n_clients=2)
    import jax.numpy as jnp
    from fedml_tpu.async_.lifecycle import (AsyncClientManager,
                                            AsyncServerManager)
    init_vars = trainer.init(jax.random.PRNGKey(cfg.seed),
                             jnp.asarray(data.client_shards["x"][0, 0]))
    ip = {0: "127.0.0.1", 1: "127.0.0.1", 2: "127.0.0.1"}
    kw = dict(ip_config=ip, base_port=53360, force_python_tcp=True)
    ckpt = str(tmp_path / "ckpt")

    server1 = AsyncServerManager(init_vars, 6, 2, 0, 3, "TCP",
                                 deadline_s=3.0, reliable=True,
                                 checkpoint_dir=ckpt, checkpoint_every=1,
                                 **kw)
    clients = [AsyncClientManager(trainer, data, cfg.epochs, r, 3, "TCP",
                                  reliable=True, **kw) for r in (1, 2)]
    threads = [c.run_async() for c in clients]
    server1.run_async()
    server1.send_start()
    try:
        deadline = time.time() + 90
        while server1.version < 2 and time.time() < deadline:
            time.sleep(0.02)
        assert server1.version >= 2, "server never reached crash point"
        server1.crash()                     # mid-round, no STOP, no commit
        time.sleep(0.3)

        # the rebind can race the dying listener's last accept for a
        # moment — retry briefly, like a process supervisor would
        server2 = None
        for _ in range(20):
            try:
                server2 = AsyncServerManager(
                    init_vars, 6, 2, 0, 3, "TCP", deadline_s=3.0,
                    reliable=True, checkpoint_dir=ckpt,
                    checkpoint_every=1, resume=True, **kw)
                break
            except OSError:
                time.sleep(0.5)
        assert server2 is not None, "same-port rebind never succeeded"
        assert server2.version >= 2, "resume lost the committed rounds"
        # ISSUE 10: the sharded client registry rode the checkpoint —
        # at a commit boundary the buffer is empty, so every admitted
        # uplink has been committed and the restored per-rank
        # participation counters must sum to the restored
        # updates_committed exactly
        assert (server2.registry.total_participation()
                == server2.updates_committed), (
            server2.registry.total_participation(),
            server2.updates_committed)
        server2.run_async()
        server2.send_start()                # re-handshake every client
        assert server2.done.wait(timeout=180), (
            f"resumed run stalled at version {server2.version}/6")
        assert server2.version == 6
        assert server2.updates_committed > 0
        assert all(np.isfinite(np.asarray(l)).all()
                   for l in jax.tree.leaves(server2.variables))
    finally:
        for c in clients:
            c.finish()
        server2 = locals().get("server2")
        if server2 is not None:
            server2.finish()
        server1.finish()
