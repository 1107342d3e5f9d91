"""``deepseek_v2``: the reference check's control for the configuration (one
adapter-only FedAvg round of the engine against ``reference.fedavg_round`` with
``check.trainable``, at the tests' tiny size on the CPU), the counts kept with
the benchmark at the published widths, the cut as the file states it, the
tiny cell end to end through the command, and the cell's readers where the
program gives them nothing to read."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedbench import layer_metrics, reference
from fedbench_tiny import REPO, load, run_cell, tiny_checkout, tiny_doc
from fedml_tpu.models import create_model

CELL = "deepseekv2.lora4of256t4096"
CONFIG, TRAFFIC = "deepseek_v2", "lora4of256t4096"
NEW = ["mla_latent_ms", "mla_attention_ms", "group_router_ms", "held_experts_ms",
       "shared_expert_ms", "dsv2_dense_mlp_ms", "dsv2_head_ms",
       "mla_core_roofline", "held_experts_roofline", "held_slot_pct"]


@pytest.mark.parametrize("train_dtype,passes", [("float32", True),
                                                ("bfloat16", False)])
def test_adapter_round_matches_the_reference_and_a_bfloat16_round_does_not(
        monkeypatch, train_dtype, passes):
    """The file's tolerance holds the float32 round and refuses the bfloat16
    one; every frozen leaf comes back from the reference as the object that
    was handed in."""
    from fedbench.harness import build, correctness
    config, traffic = tiny_doc("configs", CONFIG), tiny_doc("traffic", TRAFFIC)
    assert config["check"]["trainable"] == ["lora"]
    data = build.make_data(traffic, 4)
    seen = {}
    real_engine, real_round = build.make_engine, reference.fedavg_round

    def engine(*a, **k):
        seen["engine"] = real_engine(*a, **{**k, "train_dtype": train_dtype})
        return seen["engine"]

    def fedavg_round(ref, variables, *a, **k):
        seen["before"] = variables["params"]
        seen["after"], loss = real_round(ref, variables, *a, **k)
        return seen["after"], loss

    monkeypatch.setattr(build, "make_engine", engine)
    monkeypatch.setattr(reference, "fedavg_round", fedavg_round)
    got = correctness.check_round(config, traffic, data, 4,
                                  {"clients": 4, "batches": 2})
    tol = config["check"]["param_tol"]
    assert got["ok"] is passes
    if passes:
        assert got["max_abs_delta"] <= 0.1 * tol * got["max_abs_update"]
    else:
        assert got["max_abs_delta"] > 2 * tol * got["max_abs_update"]
    model = seen["engine"].trainer.model
    assert model.trainable == ("lora",) and seen["engine"].chunk == config["engine"]["chunk"]
    for name, leaf in seen["before"].items():
        if name != "lora":
            for a, b in zip(jax.tree.leaves(leaf), jax.tree.leaves(seen["after"][name])):
                assert a is b, name
    moved = jax.tree.map(lambda a, b: not np.array_equal(a, b),
                         seen["before"]["lora"], seen["after"]["lora"])
    assert all(jax.tree.leaves(moved))               # A and B of every matrix


@pytest.fixture(scope="module")
def published():
    config = load(REPO + "/fedbench/configs/deepseek_v2.json")
    model = create_model(config["model"]["name"], config["vocab_size"],
                         **config["model"]["kwargs"])
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    return config, model, params


def test_counts_at_the_published_widths(published):
    config, model, params = published
    ref = reference.resolve(config["reference"])
    size = lambda tree: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    w = config["widths"]
    layer = params["layer_1"]
    pick = lambda lp, names: [lp[k] for k in names]
    mla = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")
    assert [size(layer[k]) for k in mla] == [
        7_864_320, 37_748_736, 2_949_120, 16_777_216, 83_886_080]
    assert size(pick(layer, mla)) == w["parameters_mla_matrices"] == 149_225_472
    norms = ("in_norm", "q_norm", "kv_norm", "post_norm")
    assert size(pick(layer, norms)) == w["parameters_layer_norms"] == 12_288
    assert size(pick(layer, ("s1", "s3", "s2"))) == w["parameters_shared_experts"] \
        == 47_185_920
    assert size(layer["router"]) == w["parameters_router"] == 819_200
    assert size(pick(layer, ("w1", "w3", "w2"))) == 20 * w["parameters_one_expert"] \
        == 471_859_200
    assert size(layer) == w["parameters_expert_layer"] == 669_102_080
    assert size(pick(params["layer_0"], ("w1", "w3", "w2"))) \
        == w["parameters_dense_mlp"] == 188_743_680
    assert size(params["layer_0"]) == w["parameters_dense_layer"] == 337_981_440
    assert size([params["embed"], params["head"], params["out_norm"]]) \
        == w["parameters_embedding_head_and_output_norm"] == 131_077_120
    assert size(params) - size(params["lora"]) == w["parameters_held"] \
        == 337_981_440 + 4 * 669_102_080 + 131_077_120 == 3_145_466_880
    assert size(params["lora"]) == w["parameters_trained"] == 5 * 1_491_968
    frozen = {k: v for k, v in params.items() if k != "lora"}
    assert {a.dtype for a in jax.tree.leaves(frozen)} == {jnp.dtype(jnp.bfloat16)}
    assert ref.head_sizes(layer) == (128, 64, 128)
    # matrix parameters a token meets in one forward pass: 6 experts a token,
    # 20 of 160 of them here; the head, not the embedding
    touched = (5 * 149_225_472 + 188_743_680
               + 4 * (47_185_920 + 819_200 + 6 * 23_592_960 * 20 // 160)
               + 12_800 * 5120)
    assert touched == w["parameters_touched_per_token_forward"]
    t = 4096
    attention = 2.0 * t * t * 5 * 128 * (192 + 128) / 2
    assert ref.forward_flops(params, (t,)) == \
        2.0 * t * (touched + size(params["lora"])) + attention
    assert ref.train_flops(params, (t,)) == \
        2 * 2.0 * t * touched + 3 * 2.0 * t * size(params["lora"]) + 3 * attention
    # ISSUE 39's shares of a token's forward matrix work at T = 4,096
    per_token = ref.forward_flops(params, (t,)) / t
    assert 3.3e9 < per_token < 3.4e9
    assert abs(attention / t / per_token - 0.25) < 0.01          # the core
    assert abs((attention / t + 2.0 * 5 * 149_225_472) / per_token - 0.69) < 0.01
    # the base read forward and backward as stored + the adapters' four passes
    assert ref.step_bytes(params, 4) == \
        2.0 * 2 * w["parameters_held"] + 4.0 * 4 * w["parameters_trained"]
    # the fused core: 4 products 192 deep and 3 products 128 deep over the
    # causal half, 128 heads, 5 layers
    assert ref.core_flops(params, 32768.0, t) == \
        2.0 * 32768 * t / 2 * 5 * 128 * (4 * 192 + 3 * 128)
    assert ref.core_bytes(params, 32768.0, 2) == \
        32768.0 * 2 * 5 * (3 * (128 * 448 + 64) + 3 * 128 * 128)
    # the held experts: 3 matrices x 6 x 20/160 experts a token x 4 layers,
    # forward and with respect to activations; read twice a step
    assert ref.expert_flops(params, 32768.0) == \
        2 * 2.0 * 32768 * 6 * 23_592_960 * 20 / 160 * 4
    assert ref.expert_bytes(params, 8.0) == 2.0 * 2 * 4 * 471_859_200 * 8


def test_the_file_states_the_published_widths_and_the_cut(published):
    config, model, params = published
    kw = config["model"]["kwargs"]
    assert (kw["d_model"], kw["n_heads"], kw["q_rank"], kw["kv_rank"],
            kw["nope_dim"], kw["rope_dim"], kw["v_dim"], kw["d_ff"], kw["d_expert"],
            kw["experts_per_token"], kw["n_group"], kw["topk_group"], kw["n_shared"]) \
        == (config["hidden_size"], config["num_attention_heads"],
            config["q_lora_rank"], config["kv_lora_rank"],
            config["qk_nope_head_dim"], config["qk_rope_head_dim"],
            config["v_head_dim"], config["intermediate_size"],
            config["moe_intermediate_size"], config["num_experts_per_tok"],
            config["n_group"], config["topk_group"], config["n_shared_experts"]) \
        == (5120, 128, 1536, 512, 128, 64, 128, 12288, 1536, 6, 8, 3, 2)
    rope = config["rope_scaling"]
    assert (kw["rope_theta"], kw["rope_factor"], kw["rope_beta_fast"],
            kw["rope_beta_slow"], kw["rope_original"], kw["rope_mscale"],
            kw["rope_mscale_all_dim"], kw["norm_eps"], kw["routed_scaling_factor"],
            kw["first_dense"]) \
        == (config["rope_theta"], rope["factor"], rope["beta_fast"],
            rope["beta_slow"], rope["original_max_position_embeddings"],
            rope["mscale"], rope["mscale_all_dim"], config["rms_norm_eps"],
            config["routed_scaling_factor"], config["first_k_dense_replace"]) \
        == (1e4, 40, 32, 1, 4096, 0.707, 0.707, 1e-6, 16, 1)
    # the cut: depth, the experts held, the vocabulary slice - each with the
    # published value beside it and inside the guide's floors
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert (config["published_num_hidden_layers"], config["published_n_routed_experts"],
            config["published_vocab_size"]) == (60, 160, 102400)
    assert kw["n_layers"] == 60 and kw["n_experts"] == 160     # the router's width
    assert kw["layers"] == config["held_layers"] == [0, 1, 2, 3, 4]
    assert config["num_hidden_layers"] == len(kw["layers"]) >= 1 + 4
    assert kw["held"] == config["held_experts"] == [0, config["n_routed_experts"]]
    assert config["n_routed_experts"] == 160 // config["n_group"] == 20 >= 8
    assert config["vocab_size"] == 102400 // 8 == 12800
    assert model.expert_layers == (1, 2, 3, 4) and model.held_experts == (0, 20)
    assert params["layer_1"]["router"].shape == (5120, 160)
    assert params["layer_1"]["w1"].shape == (20, 5120, 1536)
    assert "eight chips" in config["deployment"]
    assert config["assumed"] and config["check"]["why"] and config["cut"]
    assert config["engine"]["chunk"] in (1, 2)
    # the traffic is the issue's: 4 of 256 silos, 2 sequences of 4,096, bs 1
    traffic = load(REPO + "/fedbench/traffic/lora4of256t4096.json")
    assert (traffic["population"], traffic["cohort"], traffic["client_sizes"]["samples"],
            traffic["batch_size"], traffic["epochs"], traffic["mesh_devices"]) \
        == (256, 4, 2, 1, 1, 1)
    assert traffic["dataset"]["args"] == {"seq_len": 4096, "vocab": 12800,
                                          "classes": 256, "row_alpha_total": 1000.0}
    assert traffic["engine"] == {"class": "fedml_tpu.parallel.MeshFedAvgEngine",
                                 "args": {"streaming": False}}
    assert traffic["lr"] in (1.0, 0.3, 0.1, 0.03) and traffic["lr_why"]
    # the reference module states what the tree's shapes do not
    ref = reference.resolve(config["reference"])
    assert (ref.N_HEADS, ref.TOP_K, ref.N_GROUP, ref.TOPK_GROUP, ref.FIRST_HELD,
            ref.SCALING, ref.LORA_ALPHA, ref.EPS) \
        == (kw["n_heads"], kw["experts_per_token"], kw["n_group"], kw["topk_group"],
            kw["held"][0], kw["routed_scaling_factor"], kw["lora_alpha"], kw["norm_eps"])
    assert ref.ROPE == dict(theta=1e4, factor=40.0, beta_fast=32.0, beta_slow=1.0,
                            original=4096, mscale=0.707, mscale_all_dim=0.707)


def test_the_file_holds_every_number_of_the_catalog_row():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog in this image")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "DeepSeek-V2")
    config = load(REPO + "/fedbench/configs/deepseek_v2.json")
    manifest = load(REPO + "/BENCHMARK.json")
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert config["source"] == entry["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if config.get(k, "absent") != v]
    assert sorted(differs) == sorted(config["reduced"]) == sorted(entry["reduced"])
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, TRAFFIC, 1)
    assert manifest["workloads"][-1] == cell and manifest["configs"][-1] == entry


def test_the_reference_shares_no_code_with_the_program():
    with open(REPO + "/fedbench/reference/deepseek_v2.py") as f:
        text = f.read()
    assert "fedml_tpu" not in text.split('"""', 2)[2]
    for name in ("ragged_dot", "top_k(", "argsort", "custom_v", "pallas", "sort("):
        assert name not in text, name


def test_the_tiny_cell_runs_through_the_command(tmp_path):
    """The benchmark's command on a cut-down scratch copy: one line, correct,
    with the counter's metric (a CPU run prints counts only) - and the eight
    device metrics and the two rooflines are listed for the cell."""
    root = tiny_checkout(str(tmp_path))
    r = run_cell(root, CELL, trace=1)
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    # one routing group of eight: near an eighth of the slots
    assert 5.0 < line["metrics"]["held_slot_pct"]["value"] < 25.0
    assert set(line["metrics"]) <= {"real_slot_pct", "held_slot_pct"}
    detail = json.loads(r.stdout.split("fedbench detail ", 1)[1].splitlines()[0])
    assert detail["check"]["ok"] and detail["window_compiles"] == 0
    from fedbench.harness import manifest
    listed = [m["name"] for m in json.load(open(root + "/BENCHMARK.json"))["per_layer"]
              if CELL in m.get("workloads", [CELL])]
    assert listed[-len(NEW):] == NEW and manifest.ROOT == REPO


@pytest.mark.parametrize("name", NEW)
def test_new_readers_match_their_entries_and_read_nothing_from_a_program_without(name):
    """Each of the ten is listed for the new cell only, repeats its module's
    declaration, and on a program that lacks the scope, the kernel or the
    counter returns None."""
    manifest = load(REPO + "/BENCHMARK.json")
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    mod = layer_metrics.module(name)
    assert entry["workloads"] == [CELL]
    assert (entry["layer"], entry["unit"], entry["source"], entry["moves"]) \
        == (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES)

    class Engine:            # a program with no counters and no scope map
        chunk = 1
        transfer_stats = object()

    class Cell:
        name, chips = CELL, 1
        config = {"reference": "resnet18gn_cifar", "trainer": {}}
        traffic = {"cohort": 4, "batch_size": 1, "epochs": 1}

    ctx = {"engine": Engine(), "cell": Cell(), "trace": None, "on_chip": True,
           "window": {"attempted": 3}, "samples": 24.0, "params": {},
           "device": {"kind": "TPU v5 lite"}, "data": None}
    assert layer_metrics.read(entry, ctx) is None


def test_held_slot_pct_is_the_held_share_of_the_counter():
    from fedbench.layer_metrics import held_slot_pct
    tokens = np.arange(2 * 16, dtype=np.float64).reshape(2, 16)

    class Stats:
        def program_counters(self):
            return {"moe_expert_tokens": tokens}

    class Engine:
        transfer_stats = Stats()

    class Cell:
        config = {"model": {"kwargs": {"held": [4, 2]}}}

    got = held_slot_pct.read({"engine": Engine(), "cell": Cell()})
    assert got == 100.0 * tokens[:, 4:6].sum() / tokens.sum()
    Cell.config = {"model": {"kwargs": {}}}              # holds every expert
    assert held_slot_pct.read({"engine": Engine(), "cell": Cell()}) is None


def test_kernel_time_is_the_custom_calls_of_a_scope(monkeypatch, tmp_path):
    """``kernel_trace`` marks the ops that the trace shows as custom calls and
    reduces the trace with the marked map and the phases: the kernels' time is
    a part of their scope's, other ops of the scope are not in it, and the
    scope x phase table is left beside the trace."""
    from fedbench.harness import kernel_trace, manifest, program_trace, trace_reduce

    class Event:
        def __init__(self, name):
            self.name = name

    class Line:
        name = "XLA Ops"
        events = [Event('%branch_0_fun.2 = (bf16[8]{0}, f32[8]{0}) custom-call(%a, %b), '
                        'custom_call_target="tpu_custom_call"'),
                  Event("%fusion.7 = f32[8]{0} fusion(%c), kind=kLoop"),
                  Event('%ragged-dot-none.3 = f32[8]{0} custom-call(%d), '
                        'custom_call_target="tpu_custom_call"')]

    class Plane:
        name, lines = "/device:TPU:0", [Line()]

    class Trace:
        planes = [Plane()]

    seen = {}

    def reduce(path, scope_map, **rules):
        seen["map"] = scope_map
        return {"unknown_share": 0.0, "rounds": 2, "scope_ms": {
            "attention.kernel|forward": 3.0, "attention.kernel|backward": 4.0,
            "attention|forward": 3.0, "moe_experts.kernel": 2.0, "mlp": 1.0}}

    class RoundFn:
        phases = {"branch_0_fun.2": "forward", "fusion.7": "forward"}

        def scope_map(self):
            return {"branch_0_fun.2": "attention", "fusion.7": "attention",
                    "ragged-dot-none.3": "moe_experts", "fusion.9": "mlp"}

        def phase_map(self):
            return self.phases

    class Engine:
        round_fn, program_family, _stack = RoundFn(), "fam", {}

    class Cell:
        name = "cell"

    trace_dir = tmp_path / ".fedbench_out" / "trace" / "cell"
    trace_dir.mkdir(parents=True)
    (trace_dir / "t.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(manifest, "ROOT", str(tmp_path))
    monkeypatch.setattr(trace_reduce, "load", lambda path: Trace())
    monkeypatch.setattr(program_trace, "reduce", reduce)
    ctx = {"engine": Engine(), "cell": Cell(), "trace": {}}
    assert kernel_trace.kernel_ms(ctx, "attention") == 7.0
    assert kernel_trace.kernel_ms(ctx, "moe_experts") == 2.0
    assert kernel_trace.kernel_ms(ctx, "mlp") is None
    assert seen["map"] == {"branch_0_fun.2": "attention.kernel|forward",
                           "fusion.7": "attention|forward",
                           "ragged-dot-none.3": "moe_experts.kernel", "fusion.9": "mlp"}
    table = json.load(open(trace_dir / "kernel_trace.json"))["table"]
    assert table["attention.kernel"] == {"forward": 3.0, "backward": 4.0}
    assert table["moe_experts.kernel"] == {"other": 2.0}
    # a program without a phase map (the parent of PR 37) still reads its kernels
    RoundFn.phase_map = None
    assert kernel_trace.kernel_ms({"engine": Engine(), "cell": Cell(), "trace": {}},
                                  "attention") == 7.0
    assert seen["map"]["branch_0_fun.2"] == "attention.kernel"
    assert kernel_trace.kernel_ms({"engine": Engine(), "cell": Cell(), "trace": None},
                                  "attention") is None
