"""The names the round program carries into its HLO, and the program spans
of the hot path — one module, so the program that opens them and the
readers that look for them cannot drift apart.

**Scopes** are ``jax.named_scope`` names.  They change the ``op_name``
metadata of the ops traced beneath them and nothing else; the optimized
HLO is the same but for metadata (tests/test_hlo_copy_audit.py pins the
copy census of the touched families exactly).  ``label_of`` turns one
``op_name`` into the label the benchmark splits device time by:

    fed_take            parallel/engine.py::take_cohort  take
    fed_local_train     the chunk scan of per-client     local_other
                        training and its plumbing        (what no inner
                        (chunked_weighted_train; for a   scope claims)
                        ragged population also the
                        cohort's ordering by batch
                        trips, each chunk's bound and
                        the batch loop that runs to it)
    fed_forward         ClientTrainer._loss              forward; under
                        value_and_grad the backward ops read
                        transpose(jvp(fed_forward))   -> backward
    fed_optimizer       ClientTrainer.train_step         optimizer
    fed_aggregate       Σ w·v fold, psums, weighted mean aggregate
    fed_server_update   engine.server_update             server_update
    fed_attention       a transformer block's attention  attention
                        (norms, projections, rotary,
                        scores, softmax, output)
    fed_mlp             its gated MLP with both norms    mlp
    fed_lm_head         the vocabulary projection and    lm_head
                        the loss on its logits
    fed_short_conv      a gated short-convolution mixer  short_conv
                        (operator norm, in_proj, both
                        gates, the depthwise causal
                        convolution, out_proj, adapters)
    fed_moe_router      an expert layer's routing: its   moe_router
                        norm, scores, bias, top-k, the
                        gate's normalisation, the sort
                        of token slots by expert and
                        the un-sort / combine
    fed_moe_experts     the grouped products over the    moe_experts
                        experts held, and their gate
    fed_mla_latent      latent attention's low-rank      mla_latent
                        side: the query and key/value
                        compressions, their norms, the
                        expansions to heads, rotary,
                        their adapters (the core and
                        the output projection stay
                        fed_attention's)
    fed_shared_expert   the gated MLP every token of an  shared_expert
                        expert layer visits
    fed_window_attention  a sliding-window layer's       window_attention
                        attention in a model that mixes
                        window and full layers (the
                        parallel block's one norm,
                        projections, rotary, the banded
                        core, output projection)
    fed_full_attention  the same of a full layer of      full_attention
                        such a model (no positional
                        term)
    fed_hc_maps         a hyper-connection's maps: the   hc_maps
                        RMS over a token's streams, the
                        projection to the three maps,
                        the sigmoids and the Sinkhorn
                        normalisations
    fed_hc_mix          its mixing: the sublayer's       hc_mix
                        input read from the streams,
                        and the streams written back
                        (H_res X + H_post^T y)

An op under several scopes belongs to the innermost one (a forward op
is inside fed_local_train too); an op under none is ``unscoped``.  One
exception: ``fed_attention`` inside ``fed_window_attention`` or
``fed_full_attention`` yields to it - the fused attention's backward rule
opens ``fed_attention`` itself (ops/attention.py), whichever kind of
layer called it.
The last twelve sit inside fed_forward and claim their ops forward,
backward and rematerialised alike, so in a model that has them
``forward`` / ``backward`` read what lies outside them (embedding,
residual stream between blocks, the final norm); a model without them
reads as before.

**Phases** are the second coordinate of the same name stack: which pass
of ``value_and_grad`` over ``fed_forward`` an op belongs to, whatever its
scope.  ``phase_of`` reads it from what jax itself writes into the stack
(read on jax 0.9.0; no scope of the program's is involved):

    forward     the part that holds fed_forward is   jvp(fed_forward)/…
                not wrapped in ``transpose(``
    backward    it is, and no later part of the      transpose(jvp(fed_forward))/…
                stack is ``rematted_computation``    /checkpoint/fed_mlp/…
    recompute   it is, and a later part is: what     transpose(jvp(fed_forward))/…
                ``jax.checkpoint`` runs again        /checkpoint/rematted_computation
                inside the backward pass             /fed_mlp/…
    other       the stack holds no fed_forward:
                take, optimizer, aggregate, server
                update, chunk plumbing, unscoped

A ``custom_vjp``'s rules are traced under the pass that calls them (the
forward rule under ``jvp(…)`` or, re-run, under ``rematted_computation``;
the backward rule under ``transpose(…)``), so hand-written backward
passes need nothing of their own.

**Spans** are ``obs.span`` names: host intervals that land in the
``SpanTracer`` when ``obs.configure()`` ran and, always, in the
profiler's own trace (``/host:CPU`` of the ``.xplane.pb``) when a
profiler session is active.
"""
from __future__ import annotations

import re

FED_TAKE = "fed_take"
FED_LOCAL_TRAIN = "fed_local_train"
FED_FORWARD = "fed_forward"
FED_OPTIMIZER = "fed_optimizer"
FED_AGGREGATE = "fed_aggregate"
FED_SERVER_UPDATE = "fed_server_update"
FED_ATTENTION = "fed_attention"
FED_MLP = "fed_mlp"
FED_LM_HEAD = "fed_lm_head"
FED_SHORT_CONV = "fed_short_conv"
FED_MOE_ROUTER = "fed_moe_router"
FED_MOE_EXPERTS = "fed_moe_experts"
FED_MLA_LATENT = "fed_mla_latent"
FED_SHARED_EXPERT = "fed_shared_expert"
FED_WINDOW_ATTENTION = "fed_window_attention"
FED_FULL_ATTENTION = "fed_full_attention"
FED_HC_MAPS = "fed_hc_maps"
FED_HC_MIX = "fed_hc_mix"

UNSCOPED = "unscoped"
BACKWARD = "backward"
FORWARD, RECOMPUTE, OTHER = "forward", "recompute", "other"
PHASES = (FORWARD, RECOMPUTE, BACKWARD, OTHER)
REMATTED = "rematted_computation"     # jax.checkpoint's name for its re-run
REMAT_CALL = "remat2"       # its primitive: the name of the call itself
LABEL_OF_SCOPE = {
    FED_TAKE: "take",
    FED_LOCAL_TRAIN: "local_other",
    FED_FORWARD: "forward",
    FED_OPTIMIZER: "optimizer",
    FED_AGGREGATE: "aggregate",
    FED_SERVER_UPDATE: "server_update",
    FED_ATTENTION: "attention",
    FED_MLP: "mlp",
    FED_LM_HEAD: "lm_head",
    FED_SHORT_CONV: "short_conv",
    FED_MOE_ROUTER: "moe_router",
    FED_MOE_EXPERTS: "moe_experts",
    FED_MLA_LATENT: "mla_latent",
    FED_SHARED_EXPERT: "shared_expert",
    FED_WINDOW_ATTENTION: "window_attention",
    FED_FULL_ATTENTION: "full_attention",
    FED_HC_MAPS: "hc_maps",
    FED_HC_MIX: "hc_mix",
}
# the kinds of attention layer a model may tell apart: an enclosing one of
# these claims what ``fed_attention`` inside it holds
_ATTENTION_KINDS = (FED_WINDOW_ATTENTION, FED_FULL_ATTENTION)
LABELS = tuple(LABEL_OF_SCOPE.values()) + (BACKWARD, UNSCOPED)

# **Counters**: a model may count what its forward pass did in a step
# (``self.sow(COUNTERS, name, value)``, shapes declared in its ``counters``
# attribute); the trainer sums them over a client's real steps, the engine
# over the round's clients, and the round program returns them in its
# metrics under their names (core/trainer.py, parallel/engine.py)
COUNTERS = "counters"
MOE_EXPERT_TOKENS = "moe_expert_tokens"    # [expert layers, experts]
# [expert layers, 2]: (rows the grouped expert products ran over, slots
# routed) — how far a layer that holds a share of its experts skips the
# slots of the others (models/lfm2_moe.py::held_share)
MOE_SLOT_ROWS = "moe_slot_rows"
# [held layers, 2 sublayers, 2]: a step's largest |rowsum(H_res) - 1| and
# |colsum(H_res) - 1| over its tokens after the last Sinkhorn iteration
# (models/xing4.py).  Summed over steps like the others, so a reader divides
# by the steps it counted; no obs counter takes it (a sum of maxima is no
# total)
HC_SINKHORN_ERR = "hc_sinkhorn_err"
# the obs counters that take a program counter's totals when it is read
# (utils/profiling.py::TransferOverlapStats.program_counters): (metric, the
# labels of one counter for each entry of the LAST axis) — one unlabelled
# counter takes the whole array's sum
METRIC_OF_COUNTER = {
    MOE_EXPERT_TOKENS: ("moe_expert_tokens_total", ({},)),
    MOE_SLOT_ROWS: ("moe_slot_rows_total",
                    ({"rows": "run"}, {"rows": "routed"})),
}

SPAN_SAMPLE = "round.sample"
SPAN_ARGS_PUT = "round.args_put"
SPAN_DISPATCH = "program.dispatch"
SPAN_GATHER = "h2d.gather"
SPAN_PUT = "h2d.put"
SPAN_WAIT = "h2d.wait"
SPANS = (SPAN_SAMPLE, SPAN_ARGS_PUT, SPAN_DISPATCH, SPAN_GATHER, SPAN_PUT,
         SPAN_WAIT)

_SCOPE = re.compile("|".join(sorted(LABEL_OF_SCOPE, key=len, reverse=True)))


def label_of(op_name: str) -> str:
    """The label of one HLO instruction from its ``op_name``: the
    innermost ``fed_*`` component of the "/"-separated name stack
    (``fed_forward`` wrapped in ``transpose(`` is the backward pass)."""
    generic = False
    for part in reversed(op_name.split("/")):
        m = _SCOPE.search(part)
        if m:
            if m.group() == FED_ATTENTION:
                generic = True        # a kind of attention outside it wins
                continue
            if generic and m.group() not in _ATTENTION_KINDS:
                break
            if m.group() == FED_FORWARD and "transpose(" in part:
                return BACKWARD
            return LABEL_OF_SCOPE[m.group()]
    return LABEL_OF_SCOPE[FED_ATTENTION] if generic else UNSCOPED


def phase_of(op_name: str) -> str:
    """The phase of one HLO instruction from its ``op_name`` (one of
    ``PHASES``; the rule: module docstring).  Like ``label_of`` it looks
    inside each "/"-separated part, so the wrappers around a part
    (``vmap(…)`` over a chunk's clients) and the parts between
    (``shard_map``, ``while/body/closed_call``, ``custom_vjp`` frames) do
    not matter."""
    parts = op_name.split("/")
    for i, part in enumerate(parts):
        if FED_FORWARD in part:
            if "transpose(" not in part:
                return FORWARD
            return RECOMPUTE if REMATTED in parts[i + 1:] else BACKWARD
    return OTHER
