"""Scopes inside the round program and program spans on the profiler's clock
(fedml_tpu/obs/scopes.py, obs/programs.py::scope_map, obs.span).

* ``scope_map()`` names every scope the engine's round program has, and the
  chunk scan's body is scoped (the device time of a round splits by layer);
* a CPU ``jax.profiler`` trace of two tiny rounds holds every program span of
  the hot path in ``/host:CPU`` with its identifier (``round``; ``family`` for
  ``program.dispatch``), a prefetched upload carrying the round it is FOR;
* ``label_of`` / ``phase_of`` / ``scope_map_of_hlo_text`` on hand-made inputs;
* ``phase_map()`` beside it: on tiny language-model rounds every instruction
  has a phase, what ``jax.checkpoint`` re-runs reads ``recompute`` under each
  checkpointed scope and nowhere else, and both maps come from one compile.

The copy census of the touched families is pinned where it always was
(tests/test_hlo_copy_audit.py, exact ceilings): the scopes change metadata.
"""
import collections
import functools
import glob
import re

import jax
import pytest

from fedml_tpu.obs import programs, scopes
from fedml_tpu.parallel import MeshFedAvgEngine
from fedml_tpu.parallel.engine import MeshFedOptEngine
from fedml_tpu.parallel.mesh import make_mesh

from parallel_case import _mnist_like_cfg, _setup

CASES = {
    # engine class, engine kwargs, the labels its round program must name
    "resident": (MeshFedAvgEngine, {}, {"take", "local_other", "forward",
                                        "backward", "optimizer", "aggregate"}),
    "streaming": (MeshFedAvgEngine, {"streaming": True},
                  {"local_other", "forward", "backward", "optimizer",
                   "aggregate"}),
    "fedopt": (MeshFedOptEngine, {}, {"take", "local_other", "forward",
                                      "backward", "optimizer", "aggregate",
                                      "server_update"}),
}


def _engine(case: str, **kw):
    cls, args, _ = CASES[case]
    cfg = _mnist_like_cfg(client_num_per_round=8, comm_round=2)
    trainer, data = _setup(cfg)
    # chunk 1: two clients a shard make two trips of the chunk scan
    return cls(trainer, data, cfg, mesh=make_mesh(4), chunk=1, **args, **kw)


@functools.lru_cache(maxsize=None)
def _ran(case: str):
    """(case, engine after two rounds under a profiler session, the trace's
    /host:CPU spans by name)."""
    import tempfile
    eng = _engine(case)
    assert eng.round_fn.scope_map() is None          # nothing dispatched yet
    with tempfile.TemporaryDirectory() as d:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=options)
        try:
            jax.block_until_ready(eng.run(rounds=2))
        finally:
            jax.profiler.stop_trace()
        path = glob.glob(d + "/**/*.xplane.pb", recursive=True)[0]
        spans = collections.defaultdict(list)
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if plane.name == "/host:CPU":
                for line in plane.lines:
                    for e in line.events:
                        spans[e.name].append(
                            (e.start_ns, e.start_ns + e.duration_ns,
                             dict(e.stats)))
    return case, eng, spans


@pytest.fixture(params=list(CASES))
def ran(request):
    return _ran(request.param)


def test_scope_map_names_every_scope_of_the_round(ran):
    case, eng, _ = ran
    labels = collections.Counter(eng.round_fn.scope_map().values())
    assert CASES[case][2] <= set(labels), labels
    assert set(labels) <= set(scopes.LABELS)
    if case != "fedopt":          # FedAvg installs the average: no op
        assert "server_update" not in labels
    assert eng.round_fn.scope_map() is eng.round_fn.scope_map()   # computed once


def test_chunk_scan_body_is_scoped(ran):
    """The instructions of the chunk scan's body that do work (not
    parameters, constants, bitcasts or tuple plumbing) carry a scope:
    >= 90 %."""
    _, eng, _ = ran
    args, kwargs = eng.round_fn._signature
    text = eng.round_fn.lower(*args, **kwargs).compile().as_text()
    smap = eng.round_fn.scope_map()
    # the chunk scan is the outermost while under fed_local_train
    body = None
    for line in text.splitlines():
        op = re.search(r'op_name="([^"]*)"', line)
        if (" while(" in line and op and op.group(1).count("while") == 1
                and op.group(1).endswith(scopes.FED_LOCAL_TRAIN + "/while")):
            body = re.search(r"body=%?([\w.\-]+)", line).group(1)
    assert body is not None
    inside, work = False, []
    for line in text.splitlines():
        if re.match(r"%?" + re.escape(body) + r" \(", line):
            inside = True
        elif inside and line.startswith("}"):
            break
        elif inside:
            m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", line)
            if m and not re.search(
                    r" (parameter|constant|tuple|get-tuple-element|bitcast)\(",
                    line):
                work.append(m.group(1))
    assert len(work) >= 5
    scoped = [n for n in work if smap[n] != scopes.UNSCOPED]
    assert len(scoped) >= 0.9 * len(work), (len(scoped), len(work))


def test_trace_holds_every_program_span_with_its_identifier(ran):
    case, eng, spans = ran
    want = {scopes.SPAN_SAMPLE, scopes.SPAN_DISPATCH, "round"}
    want |= ({scopes.SPAN_GATHER, scopes.SPAN_PUT, scopes.SPAN_WAIT,
              "h2d.upload_cohort"} if case == "streaming"
             else {scopes.SPAN_ARGS_PUT})
    assert want <= set(spans), set(spans)
    for name in want - {scopes.SPAN_DISPATCH}:
        assert all("round" in st for _, _, st in spans[name]), name
    rounds = [st for _, _, st in spans[scopes.SPAN_DISPATCH]
              if st.get("family") == eng.program_family]
    assert len(rounds) == 2
    # n is the family's dispatch count in this process (other tests may
    # have dispatched the family before)
    assert rounds[1]["n"] == rounds[0]["n"] + 1
    # the streaming round also samples the cohort it prefetches (round 2
    # never runs: the run's limit is known)
    assert sorted(st["round"] for _, _, st in spans[scopes.SPAN_SAMPLE]) \
        == [0, 1]


def test_streamed_upload_carries_the_round_it_is_for():
    """Round 1's cohort is gathered and put while round 0 runs, on the
    prefetch thread, and says round=1; the spans beneath one upload agree."""
    _, _, spans = _ran("streaming")
    ups = sorted(spans["h2d.upload_cohort"])
    assert [st["round"] for _, _, st in ups] == [0, 1]
    disp = sorted(spans[scopes.SPAN_DISPATCH])
    # the upload FOR round 1 starts before round 1 is dispatched
    assert ups[1][0] < disp[1][0]
    for name in (scopes.SPAN_GATHER, scopes.SPAN_PUT):
        for s, e, st in spans[name]:
            up = next(u for u in ups if u[0] <= s and e <= u[1])
            assert st["round"] == up[2]["round"]
    # the inline gather of round 0 is the one the consumer waited for
    assert {st["round"] for _, _, st in spans[scopes.SPAN_WAIT]} <= {0, 1}
    assert 0 in {st["round"] for _, _, st in spans[scopes.SPAN_WAIT]}


@pytest.mark.parametrize("op_name,label", [
    ("jit(_mesh_round)/fed_take/jit(_take)/gather", "take"),
    ("jit(r)/shard_map/fed_local_train/while/body/vmap(jvp(fed_forward))/conv",
     "forward"),
    ("jit(r)/fed_local_train/while/body/transpose(jvp(fed_forward))/mul",
     "backward"),
    ("jit(r)/fed_local_train/while/body/fed_optimizer/sub", "optimizer"),
    ("jit(r)/fed_local_train/while/body/fed_aggregate/dot_general",
     "aggregate"),
    ("jit(r)/fed_local_train/while", "local_other"),
    ("jit(r)/fed_server_update/add", "server_update"),
    ("jit(r)/shard_map/random_split", "unscoped"),
    # a transformer block's scopes claim forward, backward and the
    # rematerialised forward alike (models/looped_lm.py)
    ("jit(r)/fed_local_train/while/body/vmap(jvp(fed_forward))/LoopedDecoderLM"
     "/while/body/checkpoint/fed_attention/bqhd,bkhd->bhqk/dot_general",
     "attention"),
    ("jit(r)/fed_local_train/while/body/transpose(jvp(fed_forward))/while/body"
     "/checkpoint/fed_attention/transpose", "attention"),
    ("jit(r)/fed_local_train/while/body/transpose(jvp(fed_forward))/while/body"
     "/checkpoint/rematted_computation/fed_attention/exp", "attention"),
    ("jit(r)/fed_local_train/while/body/jvp(fed_forward)/while/body/checkpoint"
     "/fed_mlp/jit(silu)/logistic", "mlp"),
    ("jit(r)/fed_local_train/while/body/transpose(jvp(fed_forward))/while/body"
     "/checkpoint/rematted_computation/fed_mlp/...a,ab->...b/dot_general",
     "mlp"),
    ("jit(r)/fed_local_train/while/body/transpose(jvp(fed_forward))/while/body"
     "/checkpoint/fed_mlp/mul", "mlp"),
    ("jit(r)/fed_local_train/while/body/jvp(fed_forward)/LoopedDecoderLM"
     "/fed_lm_head/...a,ab->...b/dot_general", "lm_head"),
    ("jit(r)/fed_local_train/while/body/transpose(jvp(fed_forward))"
     "/fed_lm_head/reduce_max", "lm_head"),
    # a gated short convolution, an expert layer's routing and its grouped
    # products (models/lfm2_moe.py): forward, rematerialised and - the
    # product's backward pass is written out - transposed alike
    ("jit(r)/fed_local_train/while/body/vmap(jvp(fed_forward))/Lfm2MoeLM"
     "/checkpoint/fed_short_conv/...a,ab->...b/dot_general", "short_conv"),
    ("jit(r)/fed_local_train/while/body/transpose(jvp(fed_forward))/Lfm2MoeLM"
     "/checkpoint/rematted_computation/fed_short_conv/mul", "short_conv"),
    ("jit(r)/fed_local_train/while/body/jvp(fed_forward)/Lfm2MoeLM/checkpoint"
     "/fed_moe_router/top_k", "moe_router"),
    ("jit(r)/fed_local_train/while/body/transpose(jvp(fed_forward))/Lfm2MoeLM"
     "/checkpoint/rematted_computation/fed_moe_router/sort", "moe_router"),
    ("jit(r)/fed_local_train/while/body/jvp(fed_forward)/Lfm2MoeLM/checkpoint"
     "/fed_moe_experts/ragged_dot_general", "moe_experts"),
    ("jit(r)/fed_local_train/while/body/transpose(jvp(fed_forward))/Lfm2MoeLM"
     "/checkpoint/fed_moe_experts/transpose", "moe_experts"),
    # outside the three, the model's ops stay forward / backward
    ("jit(r)/fed_local_train/while/body/jvp(fed_forward)/LoopedDecoderLM"
     "/while/body/rsqrt", "forward"),
    ("jit(r)/fed_local_train/while/body/transpose(jvp(fed_forward))"
     "/LoopedDecoderLM/while/body/mul", "backward"),
])
def test_label_is_the_innermost_scope(op_name, label):
    assert scopes.label_of(op_name) == label


def test_compiler_inserted_instructions_inherit_from_their_consumer():
    text = """HloModule m
ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0), metadata={op_name="stack['x']"}
  %copy.1 = f32[8]{0} copy(%p), metadata={op_name="stack['x']"}
  %copy-start.2 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%copy.1)
  %copy-done.2 = f32[8]{0} copy-done(%copy-start.2)
  %fusion.3 = f32[8]{0} fusion(%copy-done.2), kind=kLoop, calls=%fc, metadata={op_name="jit(r)/fed_take/gather"}
  %add.4 = f32[8]{0} add(%fusion.3, %fusion.3), metadata={op_name="jit(r)/add"}
  %copy.5 = f32[8]{0} copy(%add.4)
  ROOT %tuple.6 = (f32[8]{0}) tuple(%copy.5)
}
"""
    smap = programs.scope_map_of_hlo_text(text)
    assert smap == {"p": "take", "copy.1": "take", "copy-start.2": "take",
                    "copy-done.2": "take", "fusion.3": "take",
                    "add.4": "unscoped",       # traced, under no scope
                    "copy.5": "unscoped",      # no consumer: its producer's
                    "tuple.6": "unscoped"}


def test_a_kernel_the_compiler_named_itself_has_its_producers_phase():
    """XLA:TPU turns `jax.lax.ragged_dot` into a `tpu_custom_call` named
    `ragged-dot-none`: no traced name.  The re-run grouped product of a
    checkpointed expert layer takes rows gathered in the re-run and feeds the
    backward rule: its scope is its consumer's, as every nameless
    instruction's, its phase its producer's — `recompute`, not `backward` —
    and the data moved for it is moved for the re-run, the copy that carries
    the name of the checkpoint's own call included."""
    step = "jit(r)/fed_local_train/while/body/transpose(jvp(fed_forward))/M"
    rerun = step + "/checkpoint/rematted_computation/fed_moe_router/gather"
    rule = step + "/checkpoint/fed_moe_experts/mul"
    text = f"""HloModule m
ENTRY %main (p: f32[8]) -> f32[8] {{
  %p = f32[8]{{0}} parameter(0), metadata={{op_name="stack['x']"}}
  %fusion.1 = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%fc, metadata={{op_name="{rerun}"}}
  %copy.2 = f32[8]{{0}} copy(%fusion.1)
  %w = f32[8]{{0}} parameter(1), metadata={{op_name="variables['w1']"}}
  %copy.7 = f32[8]{{0}} copy(%w), metadata={{op_name="{step}/jvp(fed_forward)/M/remat2"}}
  %ragged-dot-none = f32[8]{{0}} custom-call(%copy.2, %copy.7), custom_call_target="tpu_custom_call", metadata={{op_name="ragged-dot-none"}}
  %copy.3 = f32[8]{{0}} copy(%ragged-dot-none)
  ROOT %fusion.4 = f32[8]{{0}} fusion(%copy.3), kind=kLoop, calls=%fd, metadata={{op_name="{rule}"}}
}}
"""
    smap, pmap = programs.maps_of_hlo_text(text)
    assert smap == {"p": "moe_router", "fusion.1": "moe_router",
                    "copy.2": "moe_experts", "ragged-dot-none": "moe_experts",
                    "copy.3": "moe_experts", "fusion.4": "moe_experts",
                    # the copy of the experts hung on the checkpoint's barrier
                    # keeps the scope of its own name, as before
                    "w": "forward", "copy.7": "forward"}
    assert pmap == {"p": "recompute", "fusion.1": "recompute",
                    "copy.2": "recompute", "ragged-dot-none": "recompute",
                    "copy.3": "backward", "fusion.4": "backward",
                    "w": "recompute", "copy.7": "recompute"}


def test_scope_map_sees_past_a_cached_executable_without_the_scopes(tmp_path):
    """The persistent cache keys on the module without metadata: a program
    that gained scopes loads the executable its scope-less twin left there,
    stale names and all.  scope_map() notices and compiles past the cache."""
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.compilation_cache import compilation_cache

    def make(scoped: bool):
        def f(x):
            with jax.named_scope(scopes.FED_AGGREGATE if scoped else "plain"):
                return jnp.tanh(x @ x).sum(0)
        return programs.instrument("fedavg_resident", jax.jit(f))

    old_dir = jax.config.jax_compilation_cache_dir
    old_min = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compilation_cache.reset_cache()
    try:
        x = np.ones((64, 64), np.float32)
        make(False)(x)                         # fills the cache, no scope
        assert any(tmp_path.iterdir())
        prog = make(True)
        prog(x)
        labels = set(prog.scope_map().values())
    finally:
        jax.config.update("jax_compilation_cache_dir", old_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", old_min)
        compilation_cache.reset_cache()
    assert "aggregate" in labels


def test_lstm_sequence_vjp_keeps_the_scopes():
    """`models/rnn.py::lstm_sequence` has a hand-written VJP.  Its ops still
    carry the trainer's scope: the reverse time loop and, outside it, the
    three kernel-shaped products and the bias sum are `backward`; the
    forward loop and the input projection of all steps `forward`; no traced
    op of the model is `unscoped` (`backward_ms` / `forward_ms` read them)."""
    from parallel_case import _token_setup, hlo_instructions
    trainer, data, cfg = _token_setup(
        "stackoverflow_nwp", "rnn_stackoverflow",
        dict(embedding_dim=12, hidden_size=24), True)
    eng = MeshFedAvgEngine(trainer, data, cfg, mesh=make_mesh(1), chunk=2)
    eng.run(rounds=1)
    args, kwargs = eng.round_fn._signature
    text = eng.round_fn.lower(*args, **kwargs).compile().as_text()
    smap = programs.scope_map_of_hlo_text(text)
    seen = collections.Counter()
    for inst, _, opcode, rest in hlo_instructions(text):
        op = re.search(r'op_name="(jit\([^"]*/RNNStackOverflow/[^"]*)"', rest)
        if not op:
            continue
        want = ("backward" if "transpose(jvp(fed_forward))" in op.group(1)
                else "forward")
        assert smap[inst] == want, (inst, op.group(1), smap[inst])
        # where the op sits in the model; the layer's own ops sit right
        # under the model's name (the Dense layers' under `Dense_k/`)
        where = op.group(1).split("/RNNStackOverflow/")[1]
        where = re.sub(r"^while/body/.*/", "while/body/", where)
        seen[want, opcode, where] += 1
    for want in ("forward", "backward"):
        assert seen[want, "while", "while"] == 1                 # the loop
        assert seen[want, "dot", "while/body/dot_general"] == 1  # h . W_h
    # outside the loops: x . W_i for all steps; dW_i, dW_h, dx and db
    assert seen["forward", "dot", "dot_general"] == 1
    assert seen["backward", "dot", "dot_general"] == 3
    assert seen["backward", "reduce", "reduce_sum"] >= 1


def test_fused_attention_kernels_carry_the_attention_scope():
    """`ops/attention.py::causal_attention` in a tiny looped model (T = 128,
    heads of 64: a shape the kernels take), lowered for the TPU platform:
    the forward kernel, its rematerialised twin and the backward kernel —
    a `custom_vjp` rule, which opens the scope itself — all carry
    `fed_attention` in their name, and `label_of` reads `attention`: the
    labels still partition `round_busy_ms` (`attention_ms` /
    `gqa_attention_ms` read the scope, not a kernel's name)."""
    import jax.numpy as jnp
    from fedml_tpu.models import create_model
    model = create_model("looped_lm", output_dim=50, d_model=128, n_heads=2,
                         head_dim=64, d_ff=128, n_layers=1, n_passes=2)
    x = jnp.zeros((1, 128), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), x))["params"]

    def loss(p, x):
        with jax.named_scope(scopes.FED_FORWARD):
            return jnp.mean(model.apply({"params": p}, x))

    text = jax.jit(jax.grad(loss)).trace(params, x).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    kernels = [names[m.group(1)] for m in re.finditer(
        r"@tpu_custom_call.*loc\((#loc\d+)\)$", text, re.M)]
    assert len(kernels) == 3, kernels
    assert sum("rematted_computation" in k for k in kernels) == 1
    for k in kernels:
        assert scopes.FED_ATTENTION in k and k.endswith("pallas_call"), k
        assert scopes.label_of("jit(f)/transpose(jvp(fed_forward))/" + k) \
            == "attention"


# -- phases: the second coordinate of the same name stack --------------------

_STEP = "jit(r)/shard_map/fed_local_train/while/body/closed_call/"


@pytest.mark.parametrize("op_name,phase", [
    # plain
    ("jit(r)/fed_local_train/while/body/jvp(fed_forward)/conv", "forward"),
    ("jit(r)/fed_local_train/while/body/transpose(jvp(fed_forward))/mul",
     "backward"),
    ("jit(r)/fed_local_train/while/body/transpose(jvp(fed_forward))/while/body"
     "/checkpoint/rematted_computation/fed_mlp/dot_general", "recompute"),
    # under the vmap over a chunk's clients
    (_STEP + "vmap(jvp(fed_forward))/fed_lm_head/dot_general", "forward"),
    (_STEP + "vmap(transpose(jvp(fed_forward)))/checkpoint/fed_mlp/mul",
     "backward"),
    (_STEP + "vmap(transpose(jvp(fed_forward)))/checkpoint"
     "/rematted_computation/fed_attention/exp", "recompute"),
    # under the scans' while/body/closed_call, the engine's and the model's
    (_STEP + "vmap()/while/body/closed_call/jvp(fed_forward)/LoopedDecoderLM"
     "/while/body/closed_call/fed_attention/div", "forward"),
    (_STEP + "vmap()/while/body/closed_call/transpose(jvp(fed_forward))"
     "/LoopedDecoderLM/while/body/closed_call/checkpoint/fed_mlp/mul",
     "backward"),
    (_STEP + "vmap()/while/body/closed_call/transpose(jvp(fed_forward))"
     "/LoopedDecoderLM/while/body/closed_call/checkpoint"
     "/rematted_computation/fed_mlp/...a,ab->...b/dot_general", "recompute"),
    # a checkpoint outside a scan: jax writes the stack the layer was traced
    # under after the one it runs under; the OUTER fed_forward is the pass
    (_STEP + "transpose(jvp(fed_forward))/Lfm2MoeLM/jvp(fed_forward)/Lfm2MoeLM"
     "/checkpoint/Lfm2MoeLM._layer/reshape", "backward"),
    (_STEP + "transpose(jvp(fed_forward))/Lfm2MoeLM/jvp(fed_forward)/Lfm2MoeLM"
     "/checkpoint/rematted_computation/Lfm2MoeLM._layer/fed_moe_router/sort",
     "recompute"),
    # inside a custom_vjp: the backward rule, and the forward rule re-run
    (_STEP + "transpose(jvp(fed_forward))/Lfm2MoeLM/jvp(fed_forward)/Lfm2MoeLM"
     "/checkpoint/Lfm2MoeLM._layer/fed_moe_experts/custom_vjp_call"
     "/ragged_dot_general", "backward"),
    ("jit(r)/transpose(jvp(fed_forward))/checkpoint/fed_attention"
     "/pallas_call", "backward"),
    ("jit(r)/transpose(jvp(fed_forward))/checkpoint/rematted_computation"
     "/fed_attention/custom_vjp_call/pallas_call", "recompute"),
    # outside fed_forward
    ("jit(_mesh_round)/fed_take/jit(_take)/gather", "other"),
    (_STEP + "vmap()/while/body/closed_call/fed_optimizer/sub", "other"),
    (_STEP + "fed_aggregate/dot_general", "other"),
    ("jit(r)/fed_server_update/add", "other"),
    ("jit(r)/shard_map/random_split", "other"),
    ("", "other"),
    # a loop-invariant op jax hoists out of a differentiated scan keeps the
    # stack of the scan's body alone: no fed_forward, so no phase
    (_STEP + "vmap()/while/body/closed_call/fed_attention/jit(_where)"
     "/broadcast_in_dim", "other"),
])
def test_phase_is_read_from_the_outermost_fed_forward(op_name, phase):
    assert scopes.phase_of(op_name) == phase and phase in scopes.PHASES


LFM2_SMALL = dict(d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=96,
                  d_expert=32, n_experts=8, experts_per_token=2,
                  layers=[0, 2, 3], num_dense_layers=1, lora_rank=4,
                  lora_alpha=8.0)                  # tests/test_lfm2_moe.py
OURO_SMALL = dict(d_model=64, n_heads=16, head_dim=4, d_ff=96, n_layers=2,
                  n_passes=2)                      # tests/fedbench/tiny
MODELS = {
    # model, keywords, the scopes its jax.checkpoint wraps
    "looped_lm": ("looped_lm", OURO_SMALL, {"attention", "mlp"}),
    "lfm2_moe": ("lfm2_moe", LFM2_SMALL, {"attention", "mlp", "short_conv",
                                          "moe_router", "moe_experts"}),
    "looped_lm_unrolled": ("looped_lm", dict(OURO_SMALL, unrolled=True),
                           set()),
}


@functools.lru_cache(maxsize=None)
def _lm_round(case: str):
    """(checkpointed scopes, round_fn after one dispatch, its optimized
    text): four clients on two shards, a chunk of two under the vmap."""
    from parallel_case import _token_setup
    model, kw, checkpointed = MODELS[case]
    trainer, data, cfg = _token_setup("stackoverflow_nwp", model, kw, True)
    eng = MeshFedAvgEngine(trainer, data, cfg, mesh=make_mesh(2), chunk=2)
    eng.run(rounds=1)
    args, kwargs = eng.round_fn._signature
    text = eng.round_fn.lower(*args, **kwargs).compile().as_text()
    return checkpointed, eng.round_fn, text


@pytest.fixture(params=list(MODELS))
def lm_round(request):
    return _lm_round(request.param)


def test_every_instruction_has_a_phase_and_the_product_refines_the_scopes(
        lm_round):
    """One walk gives both maps: they name the same instructions, the
    scope map is what it was before there were phases, and an instruction
    with a traced name has the phase of that name — but the checkpoint's
    own call (`…/remat2`, the barrier in front of the re-run), which has
    the phase of what it feeds."""
    _, round_fn, text = lm_round
    smap, pmap = round_fn.scope_map(), round_fn.phase_map()
    assert pmap is round_fn.phase_map()                      # computed once
    assert set(pmap) == set(smap) and set(pmap.values()) <= set(scopes.PHASES)
    assert (smap, pmap) == programs.maps_of_hlo_text(text)
    assert smap == programs.scope_map_of_hlo_text(text)
    from parallel_case import hlo_instructions
    for inst, _, _, rest in hlo_instructions(text):
        op = re.search(r'op_name="(jit\([^"]*)"', rest)
        if op:
            assert smap[inst] == scopes.label_of(op.group(1))
            if not op.group(1).endswith("/" + scopes.REMAT_CALL):
                assert pmap[inst] == scopes.phase_of(op.group(1))
    # what lies outside fed_forward has no phase, and the reverse, but for
    # what jax hoists out of a differentiated scan (loop invariants of the
    # model's body: masks, rotary tables)
    outside = {"take", "local_other", "optimizer", "aggregate",
               "server_update", "unscoped"}
    for name, phase in pmap.items():
        if smap[name] in outside:
            assert phase == "other", (name, smap[name], phase)
    hoisted = [n for n, ph in pmap.items()
               if ph == "other" and smap[n] not in outside]
    assert len(hoisted) <= 0.01 * len(pmap), len(hoisted)


def test_what_checkpoint_reruns_reads_recompute_under_each_of_its_scopes(
        lm_round):
    checkpointed, round_fn, text = lm_round
    smap, pmap = round_fn.scope_map(), round_fn.phase_map()
    from parallel_case import hlo_instructions
    marked = 0
    for inst, _, _, rest in hlo_instructions(text):
        op = re.search(r'op_name="(jit\([^"]*)"', rest)
        if op and scopes.REMATTED in op.group(1).split("/"):
            assert pmap[inst] == "recompute", (inst, op.group(1))
            marked += 1
    assert (marked > 0) == bool(checkpointed)
    seen = collections.defaultdict(set)
    for name, phase in pmap.items():
        seen[smap[name]].add(phase)
    for scope in checkpointed:
        assert {"forward", "recompute", "backward"} <= seen[scope], scope
    # the head, the embedding and the residual stream lie outside the
    # checkpoint (its own barrier, which no inner scope claims, is the
    # re-run's where it feeds the re-run); a model without one recomputes
    # nothing
    recomputing = {sc for sc, phases in seen.items() if "recompute" in phases}
    assert recomputing - {"forward", "backward"} == checkpointed
    assert bool(recomputing) == bool(checkpointed)
    assert seen["lm_head"] == {"forward", "backward"}
    assert "backward" in seen["forward"] | seen["backward"]


class _CountingLower:
    """A jitted function that counts how often it is lowered."""

    def __init__(self, fn):
        self.fn, self.lowers = fn, 0

    def lower(self, *args, **kwargs):
        self.lowers += 1
        return self.fn.lower(*args, **kwargs)


def test_resnet_round_has_no_recompute_and_both_maps_cost_one_compile():
    """A model without inner scopes or checkpoint: `forward` / `backward` /
    `other` only, the `forward` phase is the `forward` label, instruction
    for instruction; and `phase_map()` after `scope_map()` compiles nothing
    (nor the other way round: one lowering serves both)."""
    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.data.loaders import load_data
    from fedml_tpu.models import create_model
    cfg = _mnist_like_cfg(model="resnet18_gn", dataset="cifar10",
                          client_num_in_total=8, client_num_per_round=4,
                          batch_size=4)
    data = load_data("cifar10", client_num_in_total=8, batch_size=4,
                     synthetic_scale=0.002, seed=0)
    trainer = ClientTrainer(create_model(
        "resnet18_gn", data.class_num, num_filters=8,
        stage_sizes=[1, 1, 1, 1]), lr=0.1)
    eng = MeshFedAvgEngine(trainer, data, cfg, mesh=make_mesh(2), chunk=2)
    assert eng.round_fn.phase_map() is None          # nothing dispatched yet
    eng.run(rounds=1)
    from fedbench.harness.device import CompileCounter
    compiles = CompileCounter()
    jax.jit(lambda x: x * 3 + 1)(1.0)
    assert compiles.count >= 1                       # the counter counts
    smap = eng.round_fn.scope_map()
    after_first = compiles.count
    pmap = eng.round_fn.phase_map()
    assert compiles.count == after_first
    assert set(pmap.values()) == {"forward", "backward", "other"}
    for phase in ("forward", "backward"):
        assert {n for n, p in pmap.items() if p == phase} \
            == {n for n, lb in smap.items() if lb == phase}
    # the other order, on a wrapper that has not been asked yet
    spy = _CountingLower(eng.round_fn.inner)
    fresh = programs.instrument(eng.program_family, spy)
    fresh._signature = eng.round_fn._signature
    assert fresh.phase_map() == pmap and spy.lowers == 1
    assert fresh.scope_map() == smap and spy.lowers == 1
