"""Scopes inside the round program and program spans on the profiler's clock
(fedml_tpu/obs/scopes.py, obs/programs.py::scope_map, obs.span).

* ``scope_map()`` names every scope the engine's round program has, and the
  chunk scan's body is scoped (the device time of a round splits by layer);
* a CPU ``jax.profiler`` trace of two tiny rounds holds every program span of
  the hot path in ``/host:CPU`` with its identifier (``round``; ``family`` for
  ``program.dispatch``), a prefetched upload carrying the round it is FOR;
* ``label_of`` / ``scope_map_of_hlo_text`` on hand-made inputs.

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
