"""``lfm2_24b_a2b``: the reference check's control for the configuration (one
adapter-only FedAvg round of the engine against ``reference.fedavg_round`` with
``check.trainable``, at the tests' tiny size on the CPU), the counts kept with
the benchmark at the published widths, and the cell's readers where the
program gives them nothing to read."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedbench import layer_metrics, reference
from fedbench_tiny import REPO, load, tiny_doc
from fedml_tpu.models import create_model

CELL = "lfm2moe24b.lora4of256t2048"


@pytest.mark.parametrize("train_dtype,passes", [("float32", True),
                                                ("bfloat16", False)])
def test_adapter_round_matches_the_reference_and_a_bfloat16_round_does_not(
        monkeypatch, train_dtype, passes):
    """The file's tolerance holds the float32 round and refuses the bfloat16
    one; every frozen leaf comes back from the reference as the object that
    was handed in, and from the engine bit for bit."""
    from fedbench.harness import build, correctness
    config = tiny_doc("configs", "lfm2_24b_a2b")
    traffic = tiny_doc("traffic", "lora4of256t2048")
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
        seen["trainable"] = a[-1] if a else k.get("trainable")
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
    assert seen["engine"].trainer.model.trainable == ("lora",)
    for name, leaf in seen["before"].items():
        if name != "lora":
            for a, b in zip(jax.tree.leaves(leaf), jax.tree.leaves(seen["after"][name])):
                assert a is b, name
    moved = jax.tree.map(lambda a, b: not np.array_equal(a, b),
                         seen["before"]["lora"], seen["after"]["lora"])
    assert all(jax.tree.leaves(moved))               # A and B of every matrix


@pytest.fixture(scope="module")
def published():
    config = load(REPO + "/fedbench/configs/lfm2_24b_a2b.json")
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
    conv = {k: v for k, v in params["layer_3"].items()
            if k in ("in_proj", "out_proj", "conv_kernel")}
    attn = {k: v for k, v in params["layer_2"].items()
            if k in ("wq", "wk", "wv", "wo", "q_norm", "k_norm")}
    assert size(conv) == w["parameters_conv_operator"] == 16_783_360
    assert size(attn) == w["parameters_attention_operator"] == 10_485_888
    dense = [params["layer_0"][k] for k in ("w1", "w3", "w2")]
    assert size(dense) == w["parameters_dense_mlp"] == 72_351_744
    experts = [params["layer_2"][k] for k in ("w1", "w3", "w2")]
    assert size(experts) == 64 * w["parameters_one_expert"] == 603_979_776
    assert size([params["layer_2"]["router"], params["layer_2"]["expert_bias"]]) \
        == w["parameters_router_and_bias"] == 131_136
    assert size([params["embed"], params["out_norm"]]) \
        == w["parameters_embedding_and_output_norm"] == 134_219_776
    assert size(params["layer_0"]) == 89_139_200
    # one period: attention, conv, conv, conv, each with all 64 experts
    period = sum(size(params[f"layer_{i}"]) for i in (2, 3, 4, 5))
    assert period == 2_477_296_000
    assert size(params) - size(params["lora"]) == w["parameters_held"] \
        == 89_139_200 + period + 134_219_776
    assert size(params["lora"]) == w["parameters_trained"] \
        == 4 * 196_608 + 1 * 212_992
    frozen = {k: v for k, v in params.items() if k != "lora"}
    assert {a.dtype for a in jax.tree.leaves(frozen)} == {jnp.dtype(jnp.bfloat16)}
    # matrix parameters a token meets in one forward pass, the tied head once
    touched = (16_777_216 + 72_351_744 + 10_485_760 + 3 * 16_777_216
               + 4 * 131_072 + 4 * 4 * 9_437_184 + 134_217_728)
    assert abs(touched - w["parameters_touched_per_token_forward"]) < 5e5
    t = 2048
    attention = 2 * 2.0 * t * t * 2048 / 2
    assert ref.forward_flops(params, (t,)) == \
        2.0 * t * (touched + size(params["lora"])) + attention
    assert ref.train_flops(params, (t,)) == \
        2 * 2.0 * t * touched + 3 * 2.0 * t * size(params["lora"]) + 3 * attention
    # the base read forward and backward as stored + the adapters' four passes
    assert ref.step_bytes(params, 4) == \
        2.0 * 2 * w["parameters_held"] + 4.0 * 4 * w["parameters_trained"]
    # the grouped products: 3 matrices x 4 experts a token x 4 layers, forward
    # and with respect to activations; the experts read twice a step
    assert ref.expert_flops(params, 16384.0) == \
        2 * 2.0 * 16384 * 4 * 9_437_184 * 4
    assert ref.expert_bytes(params, 8.0) == 2.0 * 2 * 4 * 603_979_776 * 8


def test_the_file_states_the_published_widths_and_cuts_depth_only(published):
    config, model, params = published
    kw = config["model"]["kwargs"]
    assert (kw["d_model"], kw["n_heads"], kw["n_kv_heads"], kw["head_dim"],
            kw["d_ff"], kw["d_expert"], kw["n_experts"], kw["experts_per_token"]) \
        == (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], 64, config["intermediate_size"],
            config["moe_intermediate_size"], config["num_experts"],
            config["num_experts_per_tok"]) == (2048, 32, 8, 64, 11776, 1536, 64, 4)
    assert (kw["conv_kernel"], kw["rope_theta"], kw["norm_eps"], kw["num_dense_layers"]) \
        == (config["conv_L_cache"], config["rope_parameters"]["rope_theta"],
            config["norm_eps"], config["num_dense_layers"]) == (3, 1e6, 1e-5, 2)
    assert kw["layer_types"] == config["layer_types"] and len(kw["layer_types"]) == 40
    assert config["vocab_size"] == 65536 and config["published_num_hidden_layers"] == 40
    assert config["reduced"] == ["num_hidden_layers"]
    assert kw["layers"] == config["held_layers"] == [0, 2, 3, 4, 5]
    assert config["num_hidden_layers"] == len(kw["layers"])
    # a whole period after one leading dense layer, in the published ratio
    kinds = [config["layer_types"][i] for i in kw["layers"][1:]]
    assert kinds == ["full_attention", "conv", "conv", "conv"]
    assert model.expert_layers == (2, 3, 4, 5) and model.held_experts is None
    assert config["assumed"] and config["deployment"] and config["check"]["why"]
    # the reference module states what the tree's shapes do not
    ref = reference.resolve(config["reference"])
    assert (ref.N_HEADS, ref.N_KV_HEADS, ref.TOP_K, ref.LORA_ALPHA, ref.EPS) \
        == (kw["n_heads"], kw["n_kv_heads"], kw["experts_per_token"],
            kw["lora_alpha"], kw["norm_eps"])


def test_the_file_holds_every_number_of_the_catalog_row():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog in this image")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "LFM2-24B-A2B")
    config = load(REPO + "/fedbench/configs/lfm2_24b_a2b.json")
    manifest = load(REPO + "/BENCHMARK.json")
    entry = next(c for c in manifest["configs"] if c["name"] == "lfm2_24b_a2b")
    assert config["source"] == entry["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if config.get(k, "absent") != v]
    assert differs == config["reduced"] == entry["reduced"] == ["num_hidden_layers"]


def test_the_reference_shares_no_code_with_the_program():
    with open(REPO + "/fedbench/reference/lfm2_24b_a2b.py") as f:
        text = f.read()
    assert "fedml_tpu" not in text.split('"""', 2)[2]
    for name in ("ragged_dot", "top_k(", "argsort", "custom_v"):
        assert name not in text, name


@pytest.mark.parametrize("name", [
    "short_conv_ms", "moe_router_ms", "moe_experts_ms", "gqa_attention_ms",
    "dense_mlp_ms", "tied_head_ms", "moe_experts_roofline",
    "expert_load_max_over_mean"])
def test_new_readers_match_their_entries_and_read_nothing_from_a_program_without(name):
    """Each of the eight is listed for the new cell only, repeats its module's
    declaration, and on a program that lacks the scope or the counter (the
    parent of this PR under this PR's benchmark files) returns None."""
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
        config = {"reference": "resnet18gn_cifar"}
        traffic = {"cohort": 4, "batch_size": 1, "epochs": 1}

    ctx = {"engine": Engine(), "cell": Cell(), "trace": None, "on_chip": True,
           "window": {"attempted": 3}, "samples": 24.0, "params": {},
           "device": {"kind": "TPU v5 lite"}, "data": None}
    assert layer_metrics.read(entry, ctx) is None
