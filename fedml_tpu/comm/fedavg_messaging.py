"""Message-driven FedAvg — the cross-silo deployment path.

This is the reference's distributed 6-file pattern
(fedml_api/distributed/fedavg/: message_define.py, FedAvgServerManager.py,
FedAvgClientManager.py, FedAVGAggregator.py) collapsed into one module,
running over any comm backend (INPROC for simulation, GRPC/TCP across
machines).  Participants here are genuinely remote — in-mesh cohorts use
fedml_tpu/parallel/ instead (SURVEY.md §7 design stance).

FSM (msg types 1-4, message_define.py:5-10):

  server --S2C_INIT_CONFIG(model, client_idx)--> every client
  client: local_train (jitted) --C2S_SEND_MODEL(model, n)--> server
  server: all received? weighted average; round+1 or finish
          --S2C_SYNC_MODEL(model, client_idx)--> every client
"""
from __future__ import annotations

import logging
import threading
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu import obs
from fedml_tpu.comm.managers import ClientManager, ServerManager
from fedml_tpu.comm.message import Message
from fedml_tpu.core.pytree import tree_weighted_mean
from fedml_tpu.core.sampling import ClientSampler
from fedml_tpu.secure.secagg import SecAggBelowThreshold

log = logging.getLogger(__name__)
Pytree = Any


class MyMessage:
    """Message-type constants (message_define.py:5-33)."""
    MSG_TYPE_S2C_INIT_CONFIG = 1
    MSG_TYPE_S2C_SYNC_MODEL_TO_CLIENT = 2
    MSG_TYPE_C2S_SEND_MODEL_TO_SERVER = 3
    MSG_TYPE_C2S_SEND_STATS_TO_SERVER = 4

    MSG_ARG_KEY_MODEL_PARAMS = "model_params"
    MSG_ARG_KEY_CLIENT_INDEX = "client_idx"
    MSG_ARG_KEY_NUM_SAMPLES = "num_samples"
    MSG_ARG_KEY_LOCAL_LOSS = "local_loss"
    MSG_ARG_KEY_ROUND = "round_idx"
    # ISSUE 20: masked-uplink marker — same contract as the async
    # protocol's key (a secure server rejects plain uploads by name,
    # a plain server rejects masked ones)
    MSG_ARG_KEY_SECAGG = "secagg"


def _to_numpy(tree: Pytree) -> Pytree:
    return jax.tree.map(lambda a: np.asarray(a), tree)


class FedAvgAggregator:
    """Server-side round state (FedAVGAggregator.py:24-108): receive slots,
    all-received barrier, sample-weighted average, deterministic per-round
    client sampling (:90-98: the reference's draw for round_idx, from a
    private generator — core/sampling.py).

    `secure` (ISSUE 20) swaps the plaintext slots for the secure data
    plane's SecureAggregator: uploads arrive as masked field rows and
    fold on arrival; aggregate() runs the unmask barrier (with dropout
    reconstruction for absent ranks under a straggler timeout) instead
    of the plaintext tree_weighted_mean.  Slot index i is rank i+1 —
    the same cohort ids the async path and the keyring use."""

    def __init__(self, init_variables: Pytree, worker_num: int,
                 client_num_in_total: int, client_num_per_round: int,
                 secure=None):
        self.variables = _to_numpy(init_variables)
        self.worker_num = worker_num
        self.sampler = ClientSampler(client_num_in_total, client_num_per_round)
        self.model_dict: dict[int, Pytree] = {}
        self.sample_num_dict: dict[int, float] = {}
        self.flag_client_model_uploaded = [False] * worker_num
        self._lock = threading.Lock()
        self.secure = secure
        self.secure_below_threshold = 0
        if secure is not None:
            for r in range(1, worker_num + 1):
                secure.escrow(r)        # shares escrowed before round 0

    def add_local_trained_result(self, index: int, variables: Pytree,
                                 sample_num: float) -> bool:
        with self._lock:
            if self.secure is not None:
                # masked row: fold into the field accumulator, never
                # store plaintext (there is none to store)
                self.secure.fold(index + 1,
                                 np.ascontiguousarray(variables, np.uint32))
            else:
                self.model_dict[index] = variables
                self.sample_num_dict[index] = sample_num
            self.flag_client_model_uploaded[index] = True
            return all(self.flag_client_model_uploaded)

    def aggregate(self, round_idx: int = 0) -> Pytree:
        """Aggregate over every slot that uploaded this round.  With the
        all-received barrier that is all of them; under a straggler
        timeout it is the received subset (sample-weighted, so absent
        clients simply drop out of the mean).

        Secure mode: the received subset IS the survivor set — the
        unmask barrier subtracts the absent ranks' reconstructed masks
        (round_idx is the mask PRG counter, so the caller must pass its
        true round).  Raises SecAggBelowThreshold by name when too few
        survived; the round state is kept so late uploads can still
        close the round."""
        with self._lock:
            got = [i for i in range(self.worker_num)
                   if self.flag_client_model_uploaded[i]]
            if self.secure is not None:
                acc, wsum, _inc = self.secure.commit(
                    int(round_idx), [i + 1 for i in got])
                mean = jnp.asarray(acc, jnp.float32) / jnp.float32(wsum)
                from fedml_tpu.async_.staleness import unflatten_rows
                self.variables = _to_numpy(jax.tree.map(
                    lambda a: a[0],
                    unflatten_rows(mean[None, :], self.variables)))
            else:
                stacked = jax.tree.map(
                    lambda *xs: np.stack(xs),
                    *[self.model_dict[i] for i in got])
                w = np.asarray([self.sample_num_dict[i] for i in got],
                               np.float32)
                self.variables = _to_numpy(
                    tree_weighted_mean(stacked, jnp.asarray(w)))
            self.flag_client_model_uploaded = [False] * self.worker_num
            self.model_dict.clear()
            self.sample_num_dict.clear()
            return self.variables

    def received_count(self) -> int:
        with self._lock:
            return sum(self.flag_client_model_uploaded)

    def client_sampling(self, round_idx: int) -> np.ndarray:
        return self.sampler.sample(round_idx)


class FedAvgServerManager(ServerManager):
    """FedAvgServerManager.py:14-95 over the new comm layer."""

    def __init__(self, aggregator: FedAvgAggregator, comm_round: int,
                 rank: int = 0, size: int = 1, backend: str = "INPROC",
                 on_round_done: Optional[Callable[[int, Pytree], None]] = None,
                 straggler_timeout: Optional[float] = None,
                 model_transport: Optional[str] = None,
                 wire_compress: bool = False, **kw):
        """straggler_timeout: seconds to wait for the full cohort after a
        round's first upload; then aggregate the received subset and move
        on.  None = the reference's hang-forever barrier
        (check_whether_all_receive, FedAVGAggregator.py:50-57).

        model_transport: opt-in lossy wire dtype ("bf16"/"int8", wire
        codec v2) for the DOWNLINK model_params payload only — the
        client→server uploads feed the weighted average and stay exact
        regardless; the synced model is a broadcast the next local round
        re-trains anyway.  None (default) keeps every payload exact.
        wire_compress: zlib the frame head (codec v2)."""
        super().__init__(rank, size, backend, **kw)
        self.aggregator = aggregator
        self.model_transport = model_transport
        self.wire_compress = wire_compress
        self.round_num = comm_round
        self.round_idx = 0
        self.on_round_done = on_round_done
        self.straggler_timeout = straggler_timeout
        self._round_lock = threading.Lock()
        self._watchdog: Optional[threading.Timer] = None
        self.partial_rounds = 0           # observability: timed-out rounds
        # ranks whose uplinks are config-skew quarantined (ISSUE 20):
        # skew is a config property, not a transient, so a quarantined
        # rank is treated as dead for the all-received barrier — without
        # this, one misconfigured client deadlocks the federation
        self._quarantined: set[int] = set()
        self.done = threading.Event()

    def send_init_msg(self) -> None:
        client_indexes = self.aggregator.client_sampling(self.round_idx)
        for rank in range(1, self.size):
            self._send_model(rank, MyMessage.MSG_TYPE_S2C_INIT_CONFIG,
                             int(client_indexes[rank - 1]))

    def _send_model(self, receiver: int, msg_type: int, client_idx: int):
        msg = Message(msg_type, self.rank, receiver)
        msg.add_params(MyMessage.MSG_ARG_KEY_MODEL_PARAMS,
                       self.aggregator.variables)
        msg.add_params(MyMessage.MSG_ARG_KEY_CLIENT_INDEX, client_idx)
        msg.add_params(MyMessage.MSG_ARG_KEY_ROUND, self.round_idx)
        if self.model_transport:
            msg.set_wire_transport(MyMessage.MSG_ARG_KEY_MODEL_PARAMS,
                                   self.model_transport)
        msg.wire_compress = self.wire_compress
        self.send_message(msg)

    def register_message_receive_handlers(self) -> None:
        self.register_message_receive_handler(
            MyMessage.MSG_TYPE_C2S_SEND_MODEL_TO_SERVER,
            self._handle_model_from_client)

    def _handle_model_from_client(self, msg: Message) -> None:
        sender = msg.get_sender_id()
        upload_round = msg.get(MyMessage.MSG_ARG_KEY_ROUND)
        marker = msg.get(MyMessage.MSG_ARG_KEY_SECAGG)
        secure = self.aggregator.secure is not None
        if secure != (marker is not None):
            # ISSUE 20: plain uplink to a secure server (or masked
            # words to a plain one) — quarantine BY NAME, never fold.
            # The sender's slot can never fill (skew is config, not
            # luck), so mark it dead for the barrier and close the
            # round if everyone else already uploaded — otherwise the
            # all-received barrier waits on this rank forever.
            log.warning(
                "%s server: %s uplink from rank %d quarantined "
                "(--secure_agg config skew between server and client)",
                "secure" if secure else "plain",
                "PLAIN" if secure else "MASKED", sender)
            with self._round_lock:
                self._quarantined.add(sender)
                if not self._quorum_met():
                    return
                last = self._finish_round()
            if last:
                self.finish()
            return
        with self._round_lock:
            if (upload_round is not None
                    and int(upload_round) != self.round_idx):
                return    # straggler from a round already closed by timeout
            all_received = self.aggregator.add_local_trained_result(
                sender - 1, msg.get(MyMessage.MSG_ARG_KEY_MODEL_PARAMS),
                msg.get(MyMessage.MSG_ARG_KEY_NUM_SAMPLES))
            done = all_received or self._quorum_met()
            if self.straggler_timeout is not None and self._watchdog is None \
                    and not done:
                self._arm_watchdog(self.round_idx)
            if not done:
                return
            last = self._finish_round()
        if last:       # finish() outside _round_lock: it joins the receive
            self.finish()   # thread, which may be waiting on that lock

    def _quorum_met(self) -> bool:
        """All non-quarantined slots received (caller holds _round_lock).
        A config-skew-quarantined rank never fills its slot, so the
        all-received barrier discounts it; at least one genuine upload
        is still required — an all-skew cohort has nothing to commit
        (the launcher's overall timeout reports that by name)."""
        got = self.aggregator.received_count()
        return (got > 0
                and got + len(self._quarantined) >= self.aggregator.worker_num)

    def _arm_watchdog(self, armed_round: int) -> None:
        self._watchdog = threading.Timer(
            self.straggler_timeout, self._on_straggler_timeout,
            args=(armed_round,))
        self._watchdog.daemon = True
        self._watchdog.start()

    def _on_straggler_timeout(self, armed_round: int) -> None:
        with self._round_lock:
            self._watchdog = None
            if self.round_idx != armed_round:
                return                      # round completed normally
            # the watchdog is armed only after a first upload, so at least
            # one slot is filled whenever we get here
            self.partial_rounds += 1
            last = self._finish_round()
        if last:
            self.finish()

    def _finish_round(self) -> bool:
        """Aggregate + advance; caller holds _round_lock.  Returns True
        when this was the last round — the caller must then call finish()
        AFTER releasing the lock (finish joins the receive thread, which
        may itself be blocked on _round_lock)."""
        if self._watchdog is not None:
            self._watchdog.cancel()
            self._watchdog = None
        # commit-family delimiter: fedml_tpu/obs/timeline.py windows the
        # FSM deployment's rounds aggregate-to-aggregate, exactly like
        # the async path's async.commit spans
        with obs.span("fsm.aggregate", round=self.round_idx,
                      node="server"):
            try:
                self.aggregator.aggregate(self.round_idx)
            except SecAggBelowThreshold as e:
                # ISSUE 20: the round fails BY NAME — keep it open (the
                # arrived folds survive), re-arm the straggler watchdog,
                # and wait for late uploads to clear the threshold;
                # committing would bake unerasable mask noise into the
                # model
                self.aggregator.secure_below_threshold += 1
                log.warning("secure round %d did not aggregate: %s",
                            self.round_idx, e)
                if self.straggler_timeout is not None:
                    self._arm_watchdog(self.round_idx)
                return False
        if self.on_round_done is not None:
            self.on_round_done(self.round_idx, self.aggregator.variables)
        self.round_idx += 1
        if self.round_idx >= self.round_num:
            self.done.set()
            return True
        client_indexes = self.aggregator.client_sampling(self.round_idx)
        for rank in range(1, self.size):
            self._send_model(rank,
                             MyMessage.MSG_TYPE_S2C_SYNC_MODEL_TO_CLIENT,
                             int(client_indexes[rank - 1]))
        return False


class FedAvgClientManager(ClientManager):
    """FedAvgClientManager.py:14-75: on init/sync → update model+dataset,
    train locally (the jitted ClientTrainer hot loop), upload."""

    def __init__(self, trainer, data, epochs: int, rank: int, size: int,
                 backend: str = "INPROC", total_rounds: Optional[int] = None,
                 wire_compress: bool = False, secure=None, **kw):
        """total_rounds: in multi-PROCESS deployments the client must stop
        itself — it counts model syncs (the server sends exactly one per
        round, reference FedAvgClientManager.py:60-66) and finishes after
        uploading the last one.  None (in-process simulation) leaves
        shutdown to the launcher.

        The client's model upload is aggregation-critical (it feeds the
        server's weighted average) and deliberately has NO transport
        knob — it always rides exact; wire_compress only zlibs the
        frame head (lossless)."""
        super().__init__(rank, size, backend, **kw)
        self.wire_compress = wire_compress
        # ISSUE 20: the client's view of the secure data plane (masking
        # only — reads the seed-derived keyring, holds no server state)
        self.secure = secure
        self.secagg_rejected = 0
        self.trainer = trainer
        self.data = data
        self.epochs = epochs
        self.total_rounds = total_rounds
        self.rounds_seen = 0
        self.done = threading.Event()
        self._local_train = jax.jit(
            lambda v, shard, rng: trainer.local_train(
                v, shard, rng, self.epochs),
            static_argnames=())
        self._rng = jax.random.PRNGKey(1000 + rank)

    def register_message_receive_handlers(self) -> None:
        self.register_message_receive_handler(
            MyMessage.MSG_TYPE_S2C_INIT_CONFIG, self._handle_sync)
        self.register_message_receive_handler(
            MyMessage.MSG_TYPE_S2C_SYNC_MODEL_TO_CLIENT, self._handle_sync)

    def _handle_sync(self, msg: Message) -> None:
        variables = msg.get(MyMessage.MSG_ARG_KEY_MODEL_PARAMS)
        client_idx = int(msg.get(MyMessage.MSG_ARG_KEY_CLIENT_INDEX))
        round_idx = msg.get(MyMessage.MSG_ARG_KEY_ROUND)
        shard = jax.tree.map(lambda a: jnp.asarray(a[client_idx]),
                             self.data.client_shards)
        self._rng, rng = jax.random.split(self._rng)
        # the round's client-side train wall — the stage the timeline
        # analyzer books as `train` when this client's trace is merged
        # with the server's (fedml_tpu/obs/timeline.py)
        with obs.span("fsm.local_train", rank=self.rank,
                      client=client_idx, round=round_idx):
            new_vars, loss, n = self._local_train(
                jax.tree.map(jnp.asarray, variables), shard, rng)
            n.block_until_ready()
        out = Message(MyMessage.MSG_TYPE_C2S_SEND_MODEL_TO_SERVER,
                      self.rank, 0)
        if self.secure is not None:
            # ISSUE 20: quantize + pairwise-mask the weighted flat row;
            # the sample weight rides as the masked trailing word, so
            # NUM_SAMPLES ships a constant 1.0 and per-client sample
            # counts stay private.  A quantizer refusal (fixed-point
            # field overflow — the one bound masking cannot blind)
            # drops the uplink: the straggler timeout carries the round.
            from fedml_tpu.async_.staleness import flatten_vars_row
            try:
                masked = self.secure.client_row(
                    self.rank, int(round_idx or 0),
                    np.asarray(flatten_vars_row(_to_numpy(new_vars)),
                               np.float64),
                    float(n))
            except ValueError as e:
                self.secagg_rejected += 1
                obs.counter("secagg_rejected_uplinks_total").inc()
                log.warning(
                    "secagg client %d: round %d uplink refused at "
                    "quantization (norm-bound enforcement): %s",
                    self.rank, int(round_idx or 0), e)
                self.rounds_seen += 1
                return
            out.add_params(MyMessage.MSG_ARG_KEY_MODEL_PARAMS, masked)
            out.add_params(MyMessage.MSG_ARG_KEY_NUM_SAMPLES, 1.0)
            out.add_params(MyMessage.MSG_ARG_KEY_SECAGG,
                           {"round": int(round_idx or 0)})
            out.set_wire_transport(
                MyMessage.MSG_ARG_KEY_MODEL_PARAMS, "secagg",
                scale=self.secure.cfg.scale, p=self.secure.cfg.prime)
        else:
            out.add_params(MyMessage.MSG_ARG_KEY_MODEL_PARAMS,
                           _to_numpy(new_vars))
            out.add_params(MyMessage.MSG_ARG_KEY_NUM_SAMPLES, float(n))
        out.add_params(MyMessage.MSG_ARG_KEY_LOCAL_LOSS, float(loss))
        if round_idx is not None:       # echo for stale-upload rejection
            out.add_params(MyMessage.MSG_ARG_KEY_ROUND, int(round_idx))
        out.wire_compress = self.wire_compress
        self.send_message(out)
        self.rounds_seen += 1
        if (self.total_rounds is not None
                and self.rounds_seen >= self.total_rounds):
            self.done.set()
            self.finish()


def run_messaging_fedavg(trainer, data, cfg, backend: str = "INPROC",
                         worker_num: Optional[int] = None, **backend_kw):
    """Launch server + workers (threads for INPROC; one rank per process for
    GRPC/TCP — then call the managers directly instead).  Returns the final
    variables after cfg.comm_round rounds."""
    from fedml_tpu.comm.inproc import InProcRouter

    worker_num = worker_num or cfg.client_num_per_round
    size = worker_num + 1
    straggler_timeout = backend_kw.pop("straggler_timeout", None)
    model_transport = backend_kw.pop("model_transport", None)
    wire_compress = backend_kw.pop("wire_compress", False)
    secure_cfg = backend_kw.pop("secure", None)
    router = backend_kw.pop("router", None)
    if backend.upper() == "INPROC" and router is None:
        router = InProcRouter()
    kw = dict(backend_kw)
    if router is not None:
        kw["router"] = router

    init_vars = trainer.init(jax.random.PRNGKey(cfg.seed),
                             jnp.asarray(data.client_shards["x"][0, 0]))
    secagg = None
    if secure_cfg is not None:
        # one shared SecureAggregator (ISSUE 20): the aggregator folds/
        # unmasks, the clients only read the seed-derived keyring
        from fedml_tpu.async_.staleness import flat_dim
        from fedml_tpu.secure.secagg import SecureAggregator
        secagg = SecureAggregator(secure_cfg, range(1, size),
                                  flat_dim(_to_numpy(init_vars)))
    agg = FedAvgAggregator(init_vars, worker_num,
                           cfg.client_num_in_total, worker_num,
                           secure=secagg)
    server = FedAvgServerManager(agg, cfg.comm_round, 0, size, backend,
                                 straggler_timeout=straggler_timeout,
                                 model_transport=model_transport,
                                 wire_compress=wire_compress, **kw)
    clients = [FedAvgClientManager(trainer, data, cfg.epochs, r, size,
                                   backend, wire_compress=wire_compress,
                                   secure=secagg, **kw)
               for r in range(1, size)]
    threads = [c.run_async() for c in clients] + [server.run_async()]
    server.send_init_msg()
    if not server.done.wait(timeout=600):
        for c in clients:
            c.finish()
        server.finish()   # close the server backend too (frees its port)
        raise TimeoutError(
            f"messaging FedAvg did not finish {cfg.comm_round} rounds in "
            f"600s (stalled at round {server.round_idx}; a client likely "
            "died mid-round)")
    for c in clients:
        c.finish()
    for t in threads:
        t.join(timeout=10)
    return jax.tree.map(jnp.asarray, agg.variables)
