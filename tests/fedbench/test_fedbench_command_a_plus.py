"""``command_a_plus``: the reference check's control for the configuration (one
adapter-only FedAvg round of the engine against ``reference.fedavg_round`` with
``check.trainable``, at the tests' tiny size on the CPU), the counts kept with
the benchmark at the published widths, the cut as the file states it, the file
against the catalog's row, the tiny cell end to end through the command, and the
cell's readers where the program gives them nothing to read."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedbench import layer_metrics, reference
from fedbench_tiny import REPO, load, run_cell, tiny_checkout, tiny_doc
from fedml_tpu.models import create_model

CELL = "cmdaplus.lora4of256long"
CONFIG, TRAFFIC = "command_a_plus", "lora4of256long"
NEW = ["window_attention_ms", "full_attention_ms", "cmda_router_ms",
       "cmda_held_experts_ms", "cmda_shared_experts_ms", "cmda_head_ms",
       "window_core_roofline", "band_blocks_pct", "cmda_held_slot_pct"]


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
    config = load(REPO + "/fedbench/configs/command_a_plus.json")
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
    layer = params["layer_0"]
    pick = lambda lp, names: [lp[k] for k in names]
    assert [size(layer[k]) for k in ("wq", "wk", "wv", "wo")] == [
        67_108_864, 4_194_304, 4_194_304, 67_108_864]
    assert size(pick(layer, ("wq", "wk", "wv", "wo"))) \
        == w["parameters_attention_matrices"] == 142_606_336
    assert size(pick(layer, ("s1", "s3", "s2"))) == w["parameters_shared_experts"] \
        == 4 * 3 * 4096 * 4096 == 201_326_592
    assert size(layer["router"]) == w["parameters_router"] == 524_288
    assert size(layer["norm"]) == w["parameters_layer_norm"] == 4096
    assert size(pick(layer, ("w1", "w3", "w2"))) == 8 * w["parameters_one_expert"] \
        == 8 * 50_331_648
    assert size(layer) == w["parameters_layer"] == 747_114_496
    assert size([params["embed"], params["out_norm"]]) \
        == w["parameters_embedding_and_output_norm"] == 134_217_728 + 4096
    assert size(params) - size(params["lora"]) == w["parameters_held"] \
        == 4 * 747_114_496 + 134_221_824 == 3_122_679_808
    assert size(params["lora"]) == w["parameters_trained"] == 4 * 819_200 == 3_276_800
    frozen = {k: v for k, v in params.items() if k != "lora"}
    assert {a.dtype for a in jax.tree.leaves(frozen)} == {jnp.dtype(jnp.bfloat16)}
    # matrix parameters a token meets in one forward pass: 8 experts a token,
    # 8 of 128 of them here; the tied head over the held rows
    touched = (4 * (142_606_336 + 524_288 + 201_326_592 + 8 * 50_331_648 * 8 // 128)
               + 32_768 * 4096)
    assert touched == w["parameters_touched_per_token_forward"] == 1_612_709_888
    t = 8192
    band, causal = ref.pairs(t, 4096), ref.pairs(t)
    assert (band, causal) == (25_167_872, 33_558_528)          # 75 % of the causal pairs
    assert ref.pairs(4096, 4096) == ref.pairs(4096) and ref.pairs(5, 2) == 1 + 2 * 4
    depth = 128 * 128 * (3 * band + causal)                  # heads x head size x pairs
    assert ref.forward_flops(params, (t,)) == \
        2.0 * t * (touched + size(params["lora"])) + 4.0 * depth
    assert ref.train_flops(params, (t,)) == \
        2 * 2.0 * t * touched + 3 * 2.0 * t * size(params["lora"]) + 3 * 4.0 * depth
    # ISSUE 41's forward FLOPs a sequence: 26.4 T of matrices with the head,
    # 1.65 T a sliding core, 2.20 T the full one, 33.6 T in all
    assert abs(2.0 * t * touched / 1e12 - 26.42) < 0.01
    assert abs(4.0 * 128 * 128 * band / 1e12 - 1.65) < 0.01
    assert abs(4.0 * 128 * 128 * causal / 1e12 - 2.20) < 0.01
    assert abs(ref.forward_flops(params, (t,)) / 1e12 - 33.6) < 0.1
    # the base read forward and backward as stored + the adapters' four passes
    assert ref.step_bytes(params, 4) == \
        2.0 * 2 * w["parameters_held"] + 4.0 * 4 * w["parameters_trained"]
    # the fused cores: seven products 128 deep over the pairs each kind of layer
    # has; the sliding layers' alone are what window_core_roofline counts
    tokens = 65536.0
    assert ref.core_flops(params, tokens, t) == 14.0 * tokens / t * depth
    assert ref.core_flops(params, tokens, t, kinds=(ref.SLIDING,)) \
        == 14.0 * tokens / t * 128 * 128 * 3 * band
    assert ref.core_flops(params, tokens, t, kinds=(ref.FULL,)) \
        == 14.0 * tokens / t * 128 * 128 * causal
    assert ref.core_bytes(params, tokens, 2) == \
        tokens * 2 * 4 * (3 * (128 + 16) * 128 + 3 * 128 * 128)
    assert ref.core_bytes(params, tokens, 2, kinds=(ref.SLIDING,)) \
        == 0.75 * ref.core_bytes(params, tokens, 2)
    # the held experts: 3 matrices x 8 x 8/128 experts a token x 4 layers,
    # forward and with respect to activations; read twice a step
    assert ref.expert_flops(params, tokens) == \
        2 * 2.0 * tokens * 8 * 50_331_648 * 8 / 128 * 4
    assert ref.expert_bytes(params, 8.0) == 2.0 * 2 * 4 * 8 * 50_331_648 * 8
    assert [ref.kind_of(f"layer_{i}") for i in range(8)] == \
        [ref.SLIDING] * 3 + [ref.FULL] + [ref.SLIDING] * 3 + [ref.FULL]


def test_the_file_states_the_published_widths_and_the_cut(published):
    config, model, params = published
    kw = config["model"]["kwargs"]
    assert (kw["d_model"], kw["n_heads"], kw["n_kv_heads"], kw["head_dim"],
            kw["d_expert"], kw["experts_per_token"], kw["n_shared"],
            kw["sliding_window"], kw["layer_types"], kw["rope_theta"],
            kw["norm_eps"], kw["logit_scale"]) \
        == (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["intermediate_size"], config["num_experts_per_tok"],
            config["num_shared_experts"], config["sliding_window"],
            config["layer_types"], config["rope_theta"], config["layer_norm_eps"],
            config["logit_scale"]) \
        == (4096, 128, 8, 128, 4096, 8, 4, 4096,
            (["sliding_attention"] * 3 + ["full_attention"]) * 8, 50000, 1e-5, 1)
    assert config["first_k_dense_replace"] == 0 and config["use_parallel_block"] is True
    assert config["tie_word_embeddings"] is True and "head" not in params
    assert config["expert_selection_fn"] == "sigmoid" and config["norm_topk_prob"] is True
    assert config["shared_expert_combination_strategy"] == "average"
    # the cut: depth, the experts held, the vocabulary slice - each with the
    # published value beside it and at the guide's floors
    assert config["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert (config["published_num_hidden_layers"], config["published_num_experts"],
            config["published_vocab_size"]) == (32, 128, 262144)
    assert len(kw["layer_types"]) == 32 and kw["n_experts"] == 128   # the router's width
    assert kw["layers"] == config["held_layers"] == [0, 1, 2, 3]     # one whole period
    assert config["num_hidden_layers"] == len(kw["layers"]) == config["layer_switch"] == 4
    assert kw["held"] == config["held_experts"] == [0, config["num_experts"]]
    assert config["num_experts"] == 128 // 16 == 8
    assert config["vocab_size"] == 262144 // 8 == 32768
    assert model.held_layers == (0, 1, 2, 3) and model.held_experts == (0, 8)
    assert [model.layer_types[i] for i in model.held_layers] == \
        ["sliding_attention"] * 3 + ["full_attention"]
    assert params["layer_1"]["router"].shape == (4096, 128)
    assert params["layer_1"]["w1"].shape == (8, 4096, 4096)
    assert params["layer_1"]["s1"].shape == (4096, 4 * 4096)
    assert "sixteen chips" in config["deployment"] and "sixteen" in config["cut"]
    assert config["assumed"] and config["check"]["why"] and config["cut"]
    assert config["engine"]["chunk"] == 1
    # the traffic is the issue's: 4 of 256 silos, 2 sequences, bs 1, over the window
    traffic = load(REPO + "/fedbench/traffic/lora4of256long.json")
    assert (traffic["population"], traffic["cohort"], traffic["client_sizes"]["samples"],
            traffic["batch_size"], traffic["epochs"], traffic["mesh_devices"]) \
        == (256, 4, 2, 1, 1, 1)
    args = traffic["dataset"]["args"]
    assert args == {"seq_len": args["seq_len"], "vocab": 32768, "classes": 256,
                    "row_alpha_total": 1000.0}
    assert args["seq_len"] in (8192, 6144, 5120) and args["seq_len"] % 512 == 0
    assert args["seq_len"] == config["widths"]["sequence_length"] > kw["sliding_window"] + 512
    assert traffic["engine"] == {"class": "fedml_tpu.parallel.MeshFedAvgEngine",
                                 "args": {"streaming": False}}
    assert traffic["lr"] in (1.0, 0.3, 0.1, 0.03) and traffic["lr_why"]
    # the reference module states what the tree's shapes do not
    ref = reference.resolve(config["reference"])
    assert (ref.N_HEADS, ref.N_KV_HEADS, ref.PERIOD, ref.WINDOW, ref.TOP_K, ref.N_SHARED,
            ref.FIRST_HELD, ref.LORA_ALPHA, ref.THETA, ref.EPS, ref.LOGIT_SCALE) \
        == (kw["n_heads"], kw["n_kv_heads"], config["layer_switch"], kw["sliding_window"],
            kw["experts_per_token"], kw["n_shared"], kw["held"][0], kw["lora_alpha"],
            kw["rope_theta"], kw["norm_eps"], kw["logit_scale"])


def test_the_file_holds_every_number_of_the_catalog_row():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog in this image")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "command-a-plus-05-2026")
    config = load(REPO + "/fedbench/configs/command_a_plus.json")
    manifest = load(REPO + "/BENCHMARK.json")
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert config["source"] == entry["source"] == row["source_url"]
    assert len(entry["source"]) <= 200
    differs = [k for k, v in row["config"].items() if config.get(k, "absent") != v]
    assert sorted(differs) == sorted(config["reduced"]) == sorted(entry["reduced"])
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, TRAFFIC, 1)
    assert entry["file"] == "fedbench/configs/command_a_plus.json"
    assert sum(w["config"] == CONFIG for w in manifest["workloads"]) == 1


def test_the_reference_shares_no_code_with_the_program():
    with open(REPO + "/fedbench/reference/command_a_plus.py") as f:
        text = f.read()
    assert "fedml_tpu" not in text.split('"""', 2)[2]
    for name in ("ragged_dot", "top_k(", "argsort", "custom_v", "pallas", "sort(",
                 "causal_attention"):
        assert name not in text, name


def test_the_tiny_cell_runs_through_the_command(tmp_path):
    """The benchmark's command on a cut-down scratch copy: one line, correct,
    with the counter's metric (a CPU run prints counts only: the plain path
    visits no block, so `band_blocks_pct` has nothing to read) - and the six
    device times, the roofline and the two counts are listed for the cell."""
    root = tiny_checkout(str(tmp_path))
    r = run_cell(root, CELL, trace=1)
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    # 8 of 128 held: near a sixteenth of the slots
    assert 2.0 < line["metrics"]["cmda_held_slot_pct"]["value"] < 12.5
    assert set(line["metrics"]) <= {"real_slot_pct", "cmda_held_slot_pct"}
    detail = json.loads(r.stdout.split("fedbench detail ", 1)[1].splitlines()[0])
    assert detail["check"]["ok"] and detail["window_compiles"] == 0
    listed = [m["name"] for m in json.load(open(root + "/BENCHMARK.json"))["per_layer"]
              if CELL in m.get("workloads", [CELL])]
    assert listed[-len(NEW):] == NEW


@pytest.mark.parametrize("name", NEW)
def test_new_readers_match_their_entries_and_read_nothing_from_a_program_without(
        monkeypatch, name):
    """Each of the nine is listed for the new cell only, repeats its module's
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
    # `band_blocks_pct` reads the process's own count: a program that traced no
    # windowed call
    from fedml_tpu import obs
    monkeypatch.setattr(obs, "counter", lambda *a, **k: type("C", (), {"value": 0.0})())
    assert layer_metrics.read(entry, ctx) is None


def test_a_scope_the_program_lacks_reads_as_nothing_not_as_zero(monkeypatch):
    """`program_trace.scope_ms` gives 0.0 for a label no op carries (the parent's
    program under this PR's benchmark files): the six device times leave the
    metric out instead."""
    from fedbench.harness import program_trace
    monkeypatch.setattr(program_trace, "read", lambda ctx: {
        "unknown_share": 0.0, "scope_ms": {"attention": 5.0, "moe_router": 2.5}})
    times = {n: layer_metrics.module(n).read({}) for n in NEW[:6]}
    assert times == {"window_attention_ms": None, "full_attention_ms": None,
                     "cmda_router_ms": 2.5, "cmda_held_experts_ms": None,
                     "cmda_shared_experts_ms": None, "cmda_head_ms": None}


def test_the_two_counts_read_what_the_program_counted(monkeypatch):
    from fedbench.layer_metrics import band_blocks_pct, cmda_held_slot_pct
    from fedml_tpu import obs
    tokens = np.arange(2 * 16, dtype=np.float64).reshape(2, 16)

    class Stats:
        def program_counters(self):
            return {"moe_expert_tokens": tokens}

    class Engine:
        transfer_stats = Stats()

    class Cell:
        config = {"model": {"kwargs": {"held": [4, 6]}}}      # (first, past-last)

    got = cmda_held_slot_pct.read({"engine": Engine(), "cell": Cell()})
    assert got == 100.0 * tokens[:, 4:6].sum() / tokens.sum()
    Cell.config = {"model": {"kwargs": {}}}              # holds every expert
    assert cmda_held_slot_pct.read({"engine": Engine(), "cell": Cell()}) is None
    counts = {"visited": 108.0 * 128, "causal": 136.0 * 128}
    monkeypatch.setattr(obs, "counter", lambda name, blocks: type(
        "C", (), {"value": counts[blocks]})())
    assert abs(band_blocks_pct.read({}) - 79.41) < 0.01
