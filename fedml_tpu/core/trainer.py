"""ClientTrainer — the functional replacement for the reference's
ModelTrainer ABC (fedml_core/trainer/model_trainer.py:4-37).

The reference's operator is an object with ``get/set_model_params, train,
test``.  TPU-native, the operator is a set of *pure functions* closed over
the model definition:

  init(rng, sample)                 -> variables pytree
  train_step(state, batch)          -> state            (one SGD step)
  local_train(variables, shard)     -> (variables, metrics)   lax.scan'd
  eval_step(variables, batch)       -> metric sums

so that an entire federated round — local epochs for a whole cohort of
clients — is one jit-compiled XLA program (vmap over the client axis,
shard_map over the mesh).  Batches carry an explicit ``mask`` channel so
unequal client dataset sizes become padding, not data-dependent control flow
(SURVEY.md §7 hard-part #1).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Callable, Optional

import chex
import jax
import jax.numpy as jnp
import optax

from fedml_tpu import obs
from fedml_tpu.obs import scopes
from fedml_tpu.core.pytree import (tree_advance_counts, tree_merge_counts,
                                   tree_select, tree_vary_noop)

Pytree = Any


@chex.dataclass
class TrainState:
    variables: Pytree          # {"params": ..., ["batch_stats": ...]}
    opt_state: Pytree
    rng: jax.Array
    # running sums of the model's counters over the steps so far
    # (ClientTrainer.counters); empty for a model that counts nothing
    counts: Pytree = dataclasses.field(default_factory=dict)


def _split_variables(variables):
    params = variables["params"]
    rest = {k: v for k, v in variables.items() if k != "params"}
    return params, rest


def merge_params(trained, frozen):
    """The whole parameter tree from its two parts (split_frozen): nested
    dicts that share no leaf."""
    out = dict(frozen)
    for k, v in trained.items():
        out[k] = merge_params(v, out[k]) if k in out else v
    return out


def make_lr_schedule(mode: str, base_lr: float, total_steps: int,
                     iters_per_epoch: int = 1, lr_step_epochs: int = 0,
                     warmup_steps: int = 0):
    """The reference's LR_Scheduler (fedseg/utils.py:114-157) as an optax
    schedule over the LOCAL step count T (the reference recreates its
    scheduler per train() call, so per-round restart is parity):

      poly: lr·(1−T/N)^0.9 · cos: 0.5·lr·(1+cos(πT/N)) ·
      step: lr·0.1^(epoch//lr_step) · linear warmup for T < warmup_steps.
    """
    if mode not in ("poly", "cos", "step"):
        raise ValueError(f"unknown lr schedule {mode!r}")
    if mode == "step" and not lr_step_epochs:
        raise ValueError("step schedule needs lr_step_epochs")
    N = max(total_steps, 1)

    def schedule(count):
        T = jnp.minimum(count, N).astype(jnp.float32)
        if mode == "poly":
            lr = base_lr * (1.0 - T / N) ** 0.9
        elif mode == "cos":
            lr = 0.5 * base_lr * (1.0 + jnp.cos(jnp.pi * T / N))
        else:
            epoch = count // iters_per_epoch
            lr = base_lr * 0.1 ** (epoch // lr_step_epochs)
        if warmup_steps > 0:
            lr = jnp.where(T < warmup_steps, lr * T / warmup_steps, lr)
        return lr

    return schedule


def make_optimizer(name: str, lr, momentum: float = 0.0,
                   weight_decay: float = 0.0) -> optax.GradientTransformation:
    """Client optimizer factory (reference exposes sgd/adam via --client_optimizer,
    my_model_trainer_classification.py:25-35).  `lr` may be a float or an
    optax schedule (make_lr_schedule)."""
    if name == "adamw":   # adamw owns its decay — do not chain it twice
        return optax.adamw(lr, weight_decay=weight_decay)
    txs = []
    if weight_decay:
        txs.append(optax.add_decayed_weights(weight_decay))
    if name == "sgd":
        txs.append(optax.sgd(lr, momentum=momentum if momentum else None))
    elif name == "adam":
        txs.append(optax.adam(lr))
    else:
        raise ValueError(f"unknown optimizer {name!r}")
    return optax.chain(*txs)


def broadcast_mask(mask, target):
    """Broadcast a per-sample mask over any trailing label axes (sequence
    time, segmentation H/W): [bs] → target.shape."""
    if mask.ndim < target.ndim:
        mask = mask.reshape(mask.shape + (1,) * (target.ndim - mask.ndim))
    return jnp.broadcast_to(mask, target.shape)


def masked_cross_entropy(logits, labels, mask):
    """Mean softmax CE over valid (mask=1) samples. Labels are int class ids;
    if labels has a trailing time axis (NWP models) the mask must match."""
    ce = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    mask = mask.astype(ce.dtype)
    return jnp.sum(ce * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def masked_bce(logits, targets, mask):
    """Multi-label sigmoid BCE (stackoverflow_lr's BCELoss path,
    my_model_trainer_tag_prediction.py)."""
    bce = optax.sigmoid_binary_cross_entropy(logits, targets).mean(axis=-1)
    mask = mask.astype(bce.dtype)
    return jnp.sum(bce * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def focal_from_ce(ce, gamma: float = 2.0, alpha: float = 0.5):
    """α·(1−pt)^γ·CE with pt = exp(−CE), elementwise."""
    return alpha * (1.0 - jnp.exp(-ce)) ** gamma * ce


def masked_focal_loss(logits, labels, mask, gamma: float = 2.0,
                      alpha: float = 0.5):
    """Per-element focal loss (fedseg SegmentationLosses.FocalLoss,
    utils.py:97-111, defaults γ=2 α=0.5).  The reference applies the focal
    transform to the already-averaged CE (a scalar); per-element is the
    published formulation and strictly more useful — documented
    deviation."""
    ce = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    focal = focal_from_ce(ce, gamma, alpha)
    mask = mask.astype(focal.dtype)
    return jnp.sum(focal * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def masked_accuracy_sums(logits, labels, mask):
    """Returns (n_correct, n_valid) so accuracies aggregate exactly across
    clients/batches (the reference sums correct/total the same way,
    my_model_trainer_classification.py:57-77)."""
    pred = jnp.argmax(logits, axis=-1)
    ok = (pred == labels).astype(jnp.float32) * mask.astype(jnp.float32)
    return jnp.sum(ok), jnp.sum(mask.astype(jnp.float32))


class ClientTrainer:
    """Functional train/eval operator for one model family.

    Args:
      model: a flax linen Module.
      loss: "ce" | "bce".
      optimizer / lr / momentum / weight_decay: client-side SGD config.
      prox_mu: FedProx proximal coefficient; when > 0, local_train receives
        the round's global params and adds (mu/2)||w - w_global||^2.
      has_time_axis: labels have a trailing sequence axis (char/word LMs).
      eval_ignore_id: label id excluded from EVAL metrics (the TFF
        NWP/shakespeare convention: accuracy ignores <pad> positions,
        google-research/federated stackoverflow_dataset; pad=0 in both
        data/text.py vocab layouts).  Training loss is untouched — the
        reference trains plain CE over all positions.
      train_ignore_id: label id excluded from the TRAINING loss too
        (segmentation void label, reference SegmentationLosses
        ignore_index=255, fedseg/utils.py:72).
      lr: float, or an optax schedule from make_lr_schedule (the
        reference's poly/cos/step LR_Scheduler; restarts per local round
        because opt state is re-initialized per local_train — parity).
      loss: "ce" | "bce" | "focal" (focal: fedseg utils.py:97, γ=2 α=0.5).
      batch_axes: shard_map mesh axis names that split each per-step
        batch's SAMPLE dim across devices (parallel/mesh.py BATCH_AXIS).
        When set, every train step computes the full-batch gradient with
        one psum: the loss normalizes by the GLOBAL valid-sample count,
        grads/loss are psum'd and the empty-batch guard keys on the
        global count — so the trained weights are those of the unsplit
        batch (bit-level up to reduction order) PROVIDED the step is
        deterministic given the batch: with augment or dropout the
        per-shard rng fold-in deliberately decorrelates those draws
        from the unsplit run, so results differ by the augmentation
        noise (not an error).  Mesh engines set this automatically when
        their mesh has a "batch" axis.
    """

    def __init__(self, model, loss: str = "ce", optimizer: str = "sgd",
                 lr=0.03, momentum: float = 0.0,
                 weight_decay: float = 0.0, prox_mu: float = 0.0,
                 has_time_axis: bool = False,
                 train_dtype=jnp.float32,
                 augment: Optional[Callable] = None,
                 eval_ignore_id: Optional[int] = None,
                 train_ignore_id: Optional[int] = None,
                 batch_axes: tuple = (),
                 batch_unroll: int = 1):
        self.model = model
        self.loss_name = loss
        if loss not in ("ce", "bce", "focal"):
            raise ValueError(f"unknown loss {loss!r}")
        self.tx = make_optimizer(optimizer, lr, momentum, weight_decay)
        self.has_schedule = callable(lr)
        self.prox_mu = prox_mu
        self.has_time_axis = has_time_axis
        self.train_dtype = train_dtype
        # training-time augmentation (rng, x) -> x, applied ONLY in the
        # train-step loss (data/augment.py); eval paths never see it
        self.augment = augment
        self.eval_ignore_id = eval_ignore_id
        self.train_ignore_id = train_ignore_id
        self.batch_axes = tuple(batch_axes)
        # default unroll of the batch scan in local_train (perf knob;
        # see local_train docstring for the measured story)
        if int(batch_unroll) < 1:
            raise ValueError(f"batch_unroll must be >= 1, got {batch_unroll}")
        self.batch_unroll = int(batch_unroll)

    def _revary(self, tree):
        """psum over batch_axes makes a value invariant along them; cast it
        back to varying so it composes with the (pvary'd) params/opt state
        under shard_map's vma type check.  Values are unchanged."""
        return jax.tree.map(
            lambda a: jax.lax.pcast(a, self.batch_axes, to="varying"), tree)

    # -- init ---------------------------------------------------------------
    def init(self, rng: jax.Array, sample_input: jax.Array) -> Pytree:
        return self.model.init(rng, sample_input, train=False)

    def init_opt(self, variables: Pytree) -> Pytree:
        return self.tx.init(variables["params"])

    # -- trained and frozen leaves -------------------------------------------
    def split_frozen(self, params):
        """(trained, frozen): the leaves local training updates and the
        leaves it only reads, as two trees that `merge_params` joins.  A
        model names what it trains by path prefixes under ``params``
        (``trainable``, e.g. ``("lora",)``: models/lfm2_moe.py); a model
        that names nothing trains every leaf, and its tree comes back as
        it is, beside an empty one.  Frozen leaves are not cast, not
        differentiated, not stepped, carry no optimizer state and are not
        in the TrainState the batch loop carries; they are read where
        they were put, by every client of a vmapped chunk alike."""
        prefixes = getattr(self.model, "trainable", None)
        if prefixes is None:
            return params, {}

        def part(tree, path, want):
            out = {}
            for k, v in tree.items():
                name = f"{path}/{k}" if path else k
                named = any(name == p or name.startswith(p + "/")
                            for p in prefixes)
                if named or not isinstance(v, dict):
                    if named == want:
                        out[k] = v
                else:
                    sub = part(v, name, want)
                    if sub:
                        out[k] = sub
            return out

        return part(params, "", True), part(params, "", False)

    def trained_variables(self, variables):
        """``variables`` without the frozen leaves of ``params``: what the
        round trains, folds and averages (the whole of it for a model
        that freezes nothing)."""
        trained, frozen = self.split_frozen(variables["params"])
        return {**variables, "params": trained} if frozen else variables

    def with_frozen(self, trained_variables, variables):
        """`trained_variables` put back beside the frozen leaves of
        ``variables``, which come back as the objects they went in as."""
        _, frozen = self.split_frozen(variables["params"])
        if not frozen:
            return trained_variables
        return {**trained_variables, "params": merge_params(
            trained_variables["params"], frozen)}

    @property
    def counters(self) -> dict:
        """{name: shape} of what the model counts in a forward pass
        (obs/scopes.py COUNTERS); empty for a model that counts nothing."""
        return dict(getattr(self.model, "counters", None) or {})

    # -- mixed precision ----------------------------------------------------
    def _cast_floats(self, tree, dtype):
        return jax.tree.map(
            lambda a: a.astype(dtype)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)

    # -- loss ---------------------------------------------------------------
    @jax.named_scope(scopes.FED_FORWARD)
    def _loss(self, params, rest, batch, rng, global_params=None, frozen=None):
        """Masters (params/opt state/stats) stay float32; when train_dtype
        is bfloat16 the forward/backward compute runs through bf16 casts —
        the MXU recipe: bf16 matmuls, f32 accumulation and update.
        ``frozen`` (split_frozen) joins the parameters as it is stored.
        Returns (loss, (new stats collections, the step's counters))."""
        x, y, mask = batch["x"], batch["y"], batch["mask"]
        if self.batch_axes:
            # decorrelate the sample-wise randomness (augment offsets,
            # dropout masks) across batch shards: the carried rng is
            # replicated along the batch axes, and augment draws (bs,)
            # vectors from it — without the fold-in, sample i on every
            # shard would share its crop/flip/cutout draw
            for ax in self.batch_axes:
                if jax.lax.axis_size(ax) > 1:   # size-1 axis: stay a no-op
                    rng = jax.random.fold_in(rng, jax.lax.axis_index(ax))
        if self.augment is not None:
            rng, aug_rng = jax.random.split(rng)
            x = self.augment(aug_rng, x)
        rngs = {"dropout": rng}
        half = self.train_dtype != jnp.float32
        apply_params = self._cast_floats(params, self.train_dtype) if half else params
        if frozen:
            apply_params = merge_params(apply_params, frozen)
        # stats collections (BatchNorm running mean/var) are NOT cast: the
        # EMA must accumulate on the f32 master or sub-0.4%-ulp increments
        # vanish on the bf16 grid near convergence
        apply_rest = rest
        if half and jnp.issubdtype(x.dtype, jnp.floating):
            x = x.astype(self.train_dtype)
        counts = {}
        mutable = list(apply_rest.keys()) + (
            [scopes.COUNTERS] if self.counters else [])
        if mutable:
            logits, new_rest = self.model.apply(
                {"params": apply_params, **apply_rest}, x, train=True,
                mutable=mutable, rngs=rngs)
            if self.counters:
                new_rest = dict(new_rest)
                counts = new_rest.pop(scopes.COUNTERS)
        else:
            logits = self.model.apply({"params": apply_params}, x, train=True,
                                      rngs=rngs)
            new_rest = apply_rest
        if half:
            logits = logits.astype(jnp.float32)      # loss math in f32
            new_rest = self._cast_floats(new_rest, jnp.float32)
        if self.has_time_axis and mask.ndim < y.ndim:
            mask = broadcast_mask(mask, y)
        if self.train_ignore_id is not None:
            valid = y != self.train_ignore_id
            mask = mask * valid.astype(mask.dtype)
            # void ids may be out of the class range (255): remap to 0 so
            # the gather inside CE stays in-bounds (0*NaN would poison the
            # masked sum otherwise)
            y = jnp.where(valid, y, 0)
        # a model whose output layer has a scope of its own (a vocabulary
        # head: obs/scopes.py) names it, and the loss on its logits is
        # traced under that name; any other model's loss stays where it was
        head_scope = getattr(self.model, "loss_scope", None)
        with (jax.named_scope(head_scope) if head_scope
              else contextlib.nullcontext()):
            if self.loss_name == "ce":
                loss = masked_cross_entropy(logits, y, mask)
            elif self.loss_name == "bce":
                loss = masked_bce(logits, y, mask)
            else:
                loss = masked_focal_loss(logits, y, mask)
        if self.batch_axes:
            # batch-split normalization: the masked losses divide by this
            # SHARD's valid count; rescale to S_l / C_g so the psum over
            # the batch axes (train_step) yields the unsplit batch's mean
            c_l = jnp.sum(mask.astype(jnp.float32))
            c_g = self._revary(jax.lax.psum(c_l, self.batch_axes))
            loss = loss * c_l / jnp.maximum(c_g, 1.0)
        if self.prox_mu > 0.0 and global_params is not None:
            sq = jax.tree.map(lambda a, b: jnp.sum(jnp.square(a - b)),
                              params, global_params)
            prox = 0.5 * self.prox_mu * jnp.sum(
                jnp.stack(jax.tree.leaves(sq)))
            if self.batch_axes:
                # the prox term is computed identically on every batch
                # shard; divide by the axis size so its psum counts once
                prox = prox / self._revary(
                    jax.lax.psum(jnp.float32(1), self.batch_axes))
            loss = loss + prox
        return loss, (new_rest, counts)

    # -- one SGD step -------------------------------------------------------
    def train_step(self, state: TrainState, batch, global_params=None,
                   frozen=None):
        """One step on ``state.variables`` (the trained leaves; ``frozen``
        beside them); the step's counters join ``state.counts`` unless
        the batch holds no real sample."""
        params, rest = _split_variables(state.variables)
        rng, step_rng = jax.random.split(state.rng)
        (loss, (new_rest, counts)), grads = jax.value_and_grad(
            self._loss, has_aux=True)(
            params, rest, batch, step_rng, global_params, frozen)
        n_valid = jnp.sum(batch["mask"])
        if self.batch_axes:
            # the full-batch gradient: each shard computed S_l/C_g-normalized
            # grads over its sample slice; one psum per step completes them.
            # Every batch shard then applies the IDENTICAL update, keeping
            # the per-client weights replicated along the batch axes.
            counts = self._revary(jax.lax.psum(counts, self.batch_axes))
            grads = self._revary(jax.lax.psum(grads, self.batch_axes))
            loss = self._revary(jax.lax.psum(loss, self.batch_axes))
            new_rest = self._revary(jax.lax.pmean(new_rest, self.batch_axes))
            n_valid = self._revary(jax.lax.psum(n_valid, self.batch_axes))
        # empty-batch guard: for params, scaling the UPDATES by the has-data
        # flag is exactly equivalent to a post-hoc select (additive updates;
        # u*0 leaves params bitwise unchanged) but fuses into apply_updates
        # instead of costing an extra full-tree pass per step.  Stats
        # collections and optimizer state are not additive, so they keep the
        # select (core/pytree.py:tree_select).  Under batch_axes the guard
        # keys on the GLOBAL count — a shard whose slice is all padding must
        # still apply the other shards' gradient contribution.
        with jax.named_scope(scopes.FED_OPTIMIZER):
            updates, opt_state = self.tx.update(grads, state.opt_state,
                                                params)
            has_data = n_valid > 0
            g = has_data.astype(jnp.float32)
            new_params = optax.apply_updates(
                params,
                jax.tree.map(lambda u: u * g.astype(u.dtype), updates))
            keep = functools.partial(tree_select, has_data)
            kept_opt = keep(opt_state, state.opt_state)
            if self.has_schedule:
                # padded batches still advance the schedule's step count so
                # ragged clients share one LR trajectory (tree_merge_counts)
                kept_opt = tree_merge_counts(kept_opt, opt_state)
            kept_rest = keep(new_rest, rest)
        return TrainState(
            variables={"params": new_params, **kept_rest},
            opt_state=kept_opt,
            rng=rng,
            counts=jax.tree.map(lambda a, c: a + c * g, state.counts, counts)
            ), jnp.where(has_data, loss, 0.0)

    # -- local training: epochs x batches under lax.scan --------------------
    def local_train(self, variables: Pytree, shard, rng: jax.Array,
                    epochs: int, global_params=None,
                    unroll: Optional[int] = None, batch_bound=None):
        """`local_train_counted` without the counters: (new_variables,
        mean_loss, n_samples)."""
        return self.local_train_counted(
            variables, shard, rng, epochs, global_params=global_params,
            unroll=unroll, batch_bound=batch_bound)[:3]

    def local_train_counted(self, variables: Pytree, shard, rng: jax.Array,
                            epochs: int, global_params=None,
                            unroll: Optional[int] = None, batch_bound=None):
        """Run E local epochs of SGD over one client's padded shard.

        Returns (new_variables, mean_loss, n_samples, counters): the last
        holds the model's counters summed over this client's real steps
        ({} for a model that counts nothing).  Where the model freezes
        part of its parameters (`split_frozen`) the TrainState, the
        gradient and the optimizer hold the trained leaves alone, and
        ``new_variables`` holds the frozen ones as they were handed in —
        under `vmap` they stay un-mapped until the caller returns them,
        so a vmapped caller returns `trained_variables(new_variables)`.

        shard: {"x": [B, bs, ...], "y": [B, bs, ...], "mask": [B, bs]}
        This is the reference's
        client hot loop (my_model_trainer_classification.py:19-53) as a single
        scanned XLA program.  `unroll` (default: the constructor's
        batch_unroll) unrolls the batch scan — measured on v5e at the
        bench shape: neutral at chunk 8, and at the chunk-2 optimum a
        full-shard unroll wins ~1-2% (PERF.md §6 "Before PR 22").

        `batch_bound` (a traced int32 scalar, or None) ends each epoch's
        batch loop after that many batches: the caller promises that
        every batch from `batch_bound` on is all padding, so the steps
        left out are the ones the empty-batch guard turns into no-ops.
        The loop is then a `fori_loop` that reads batch i by a dynamic
        index, and the trained weights are bitwise those of the scan over
        all B batches: what the skipped steps still did there — split
        the carried rng, advance a schedule's step count — is made up
        after the loop.  Under `vmap` pass ONE bound for all lanes (not
        a mapped axis: a per-lane bound makes the loop's predicate a
        vector and every step a select of the whole TrainState); under
        `batch_axes` it must agree across the batch shards, whose psums
        meet inside the step (chunked_weighted_train gives both).
        Without it the code is the scan it was, `unroll` included.

        The obs span fires at TRACE time only (this function runs under
        jit): it measures how long building the local-training scan
        takes per compile — never the device execution — and, being a
        host-side no-op outside the traced dataflow, cannot perturb the
        compiled program (results stay bitwise obs-on/off).
        """
        unroll = self.batch_unroll if unroll is None else unroll
        trained, frozen = self.split_frozen(variables["params"])
        if frozen:
            variables = {**variables, "params": trained}
            if global_params is not None:
                global_params = self.split_frozen(global_params)[0]
        with obs.span("trace.local_train", epochs=epochs, unroll=unroll):
            # tree_vary_noop: align the fresh (replicated-typed) optimizer
            # state with the varying type it takes after step 1 under
            # shard_map (core/pytree.py)
            state = TrainState(
                variables=variables,
                opt_state=tree_vary_noop(self.init_opt(variables), shard),
                rng=rng)
            if self.counters:
                state = state.replace(counts=tree_vary_noop(
                    {name: jnp.zeros(shape, jnp.float32)
                     for name, shape in self.counters.items()}, shard))
            # NOTE on the carry layout (PR-4 copy audit): packing this
            # TrainState carry's float leaves into per-dtype flat
            # vectors (the engine.py flatten_carry_f32 treatment) was
            # built and MEASURED here, and kept OUT: it removes the
            # per-leaf donated-param staging copies at scan entry (once
            # per chunk trip) but forces every conv wgrad through a
            # relayout copy FEEDING the concat (per step) — audited on
            # the CNN round program at +224 KB static copy bytes net
            # (tools/hlo_copy_audit.py; per-step > per-entry).  The
            # chunked cohort loops DO pack their accumulator carries,
            # where the update is a plain elementwise add and packing
            # only removes copies.

            def batch_body(state, batch):
                state, loss = self.train_step(state, batch, global_params,
                                              frozen)
                cnt = jnp.sum(batch["mask"])
                if self.batch_axes:   # loss is global; weight it globally
                    cnt = self._revary(jax.lax.psum(cnt, self.batch_axes))
                return state, (loss, cnt)

            def bounded_epoch_body(state, _):
                def trip(i, carry):
                    state, loss_sum, count = carry
                    batch = jax.tree.map(
                        lambda a: jax.lax.dynamic_index_in_dim(
                            a, i, 0, keepdims=False), shard)
                    state, (loss, cnt) = batch_body(state, batch)
                    return state, loss_sum + loss * cnt, count + cnt

                # the sums start with the type a trip gives them (shard-
                # varying under shard_map, like the optimizer state above)
                zero = jnp.sum(shard["mask"][:0].astype(jnp.float32))
                state, loss_sum, count = jax.lax.fori_loop(
                    0, batch_bound, trip, (state, zero, zero))
                skipped = shard["mask"].shape[0] - batch_bound
                if epochs > 1:
                    # the next epoch starts from the rng the full scan
                    # would hand it: one split a skipped batch
                    state = state.replace(rng=jax.lax.fori_loop(
                        0, skipped, lambda _, r: jax.random.split(r)[0],
                        state.rng))
                if self.has_schedule:
                    state = state.replace(opt_state=tree_advance_counts(
                        state.opt_state, skipped))
                return state, loss_sum / jnp.maximum(count, 1.0)

            def epoch_body(state, _):
                state, (losses, counts) = jax.lax.scan(
                    batch_body, state, shard, unroll=unroll)
                # sample-weighted epoch loss: padding batches add nothing
                return state, jnp.sum(losses * counts) / jnp.maximum(
                    jnp.sum(counts), 1.0)

            state, epoch_losses = jax.lax.scan(
                epoch_body if batch_bound is None else bounded_epoch_body,
                state, None, length=epochs)
            n = jnp.sum(shard["mask"])
            if self.batch_axes:   # client's TOTAL sample count (agg weight)
                n = self._revary(jax.lax.psum(n, self.batch_axes))
            new_variables = state.variables
            if frozen:
                new_variables = {**new_variables, "params": merge_params(
                    new_variables["params"], frozen)}
            return new_variables, jnp.mean(epoch_losses), n, state.counts

    # -- eval ---------------------------------------------------------------
    def eval_step(self, variables: Pytree, batch):
        """Returns dict of sums: loss_sum, correct, count (mask-aware)."""
        params, rest = _split_variables(variables)
        x, y, mask = batch["x"], batch["y"], batch["mask"]
        logits = self.model.apply({"params": params, **rest}, x, train=False)
        if self.has_time_axis and mask.ndim < y.ndim:
            mask = broadcast_mask(mask, y)
        if self.eval_ignore_id is not None:
            mask = mask * (y != self.eval_ignore_id).astype(mask.dtype)
        if self.train_ignore_id is not None:   # void label: never scored
            valid = y != self.train_ignore_id
            mask = mask * valid.astype(mask.dtype)
            y = jnp.where(valid, y, 0)         # keep the CE gather in-bounds
        if self.loss_name in ("ce", "focal"):
            ce = optax.softmax_cross_entropy_with_integer_labels(logits, y)
            if self.loss_name == "focal":
                # eval with the train criterion, like the reference
                ce = focal_from_ce(ce)
            loss_sum = jnp.sum(ce * mask)
            correct, count = masked_accuracy_sums(logits, y, mask)
        else:
            bce = optax.sigmoid_binary_cross_entropy(logits, y).mean(-1)
            loss_sum = jnp.sum(bce * mask)
            # multi-label: count a hit when the top predicted tag is present
            pred = jnp.argmax(logits, axis=-1)
            hit = jnp.take_along_axis(y, pred[..., None], axis=-1)[..., 0]
            correct = jnp.sum(hit * mask)
            count = jnp.sum(mask)
        return {"loss_sum": loss_sum, "correct": correct, "count": count}

    def evaluate(self, variables: Pytree, shard):
        """Scan eval over batches of a padded shard; returns summed metrics.
        (Span = trace-time only, like local_train.)"""
        def body(carry, batch):
            m = self.eval_step(variables, batch)
            return jax.tree.map(jnp.add, carry, m), None

        init = {"loss_sum": jnp.float32(0), "correct": jnp.float32(0),
                "count": jnp.float32(0)}
        with obs.span("trace.evaluate"):
            sums, _ = jax.lax.scan(body, init, shard)
        return sums
