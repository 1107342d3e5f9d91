"""``xing4_0_29b_a4b``: the reference check's control for the configuration (one
adapter-only FedAvg round of the engine against ``reference.fedavg_round`` with
``check.trainable``, at the tests' tiny size on the CPU), the counts kept with
the benchmark at the published widths, the cut as the file states it, the file
against the catalog's row, the tiny cell end to end through the command, and the
cell's readers where the program gives them nothing to read.  No test here pins
a position in ``BENCHMARK.json``."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedbench import layer_metrics, reference
from fedbench_tiny import REPO, load, run_cell, tiny_checkout, tiny_doc
from fedml_tpu.models import create_model

CELL = "xing4.lora4of256long"
CONFIG = "xing4_0_29b_a4b"
NEW = ["hc_maps_ms", "hc_mix_ms", "hc_roofline", "hc_col_err",
       "xing4_mla_latent_ms", "xing4_mla_attention_ms", "xing4_mla_core_roofline",
       "xing4_router_ms", "xing4_held_experts_ms", "xing4_shared_expert_ms",
       "xing4_dense_mlp_ms", "xing4_head_ms", "xing4_held_slot_pct"]
TIMES = {"hc_maps_ms": "hc_maps", "hc_mix_ms": "hc_mix",
         "xing4_mla_latent_ms": "mla_latent", "xing4_mla_attention_ms": "attention",
         "xing4_router_ms": "moe_router", "xing4_held_experts_ms": "moe_experts",
         "xing4_shared_expert_ms": "shared_expert", "xing4_dense_mlp_ms": "mlp",
         "xing4_head_ms": "lm_head"}


def _cell_entry():
    manifest = load(REPO + "/BENCHMARK.json")
    return manifest, next(w for w in manifest["workloads"] if w["name"] == CELL)


@pytest.mark.parametrize("train_dtype,passes", [("float32", True),
                                                ("bfloat16", False)])
def test_adapter_round_matches_the_reference_and_a_bfloat16_round_does_not(
        monkeypatch, train_dtype, passes):
    """The file's tolerance holds the float32 round and refuses the bfloat16
    one; every frozen leaf - the hyper-connection maps among them - comes back
    from the reference as the object that was handed in."""
    from fedbench.harness import build, correctness
    _, cell = _cell_entry()
    config, traffic = tiny_doc("configs", CONFIG), tiny_doc("traffic", cell["traffic"])
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
    assert "hc_attn_phi" in seen["before"]["layer_0"]
    moved = jax.tree.map(lambda a, b: not np.array_equal(a, b),
                         seen["before"]["lora"], seen["after"]["lora"])
    assert all(jax.tree.leaves(moved))               # A and B of every matrix


@pytest.fixture(scope="module")
def published():
    config = load(REPO + "/fedbench/configs/xing4_0_29b_a4b.json")
    model = create_model(config["model"]["name"], config["vocab_size"],
                         **config["model"]["kwargs"])
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    return config, model, params


def test_counts_at_the_published_widths(published):
    """ISSUE 45's arithmetic, layer by layer, at the depth the file holds."""
    config, model, params = published
    ref = reference.resolve(config["reference"])
    size = lambda tree: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    w = config["widths"]
    n_layers = len(config["held_layers"])
    dense, expert = params["layer_0"], params["layer_2"]
    pick = lambda lp, names: [lp[k] for k in names]
    mla = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")
    assert [size(expert[k]) for k in mla] == [
        2_752_512, 4_718_592, 2_064_384, 4_194_304, 14_680_064]
    assert size(pick(expert, mla)) == w["parameters_mla_matrices"] == 28_409_856
    norms = ("in_norm", "q_norm", "kv_norm", "post_norm")
    assert size(pick(expert, norms)) == w["parameters_layer_norms"] == 8_448
    hc = [f"hc_{s}_{k}" for s in ("attn", "mlp") for k in ("phi", "b", "a")]
    assert size(pick(expert, hc)) == w["parameters_hyper_connections"] \
        == 2 * (14_336 * 24 + 27) == 688_182
    assert expert["hc_attn_phi"].shape == (4 * 3584, 4 + 4 + 16)
    assert size(pick(expert, ("s1", "s3", "s2"))) == w["parameters_shared_expert"] \
        == w["parameters_one_expert"] == 3 * 3584 * 1024 == 11_010_048
    assert size(pick(expert, ("router", "expert_bias"))) \
        == w["parameters_router_and_bias"] == 229_376 + 64
    assert size(pick(expert, ("w1", "w3", "w2"))) == 16 * 11_010_048
    assert size(pick(dense, ("w1", "w3", "w2"))) == w["parameters_dense_mlp"] \
        == 3 * 3584 * 9216 == 99_090_432
    assert size(expert) == w["parameters_expert_layer"] == 216_506_742
    assert size(dense) == w["parameters_dense_layer"] == 128_196_918
    assert size([params["embed"], params["head"], params["out_norm"]]) \
        == w["parameters_embedding_head_and_output_norm"] == 234_881_024 + 3584
    held = 2 * 128_196_918 + (n_layers - 2) * 216_506_742 + 234_884_608
    assert size(params) - size(params["lora"]) == w["parameters_held"] == held
    assert size(params["lora"]) == w["parameters_trained"] == n_layers * 508_928
    frozen = {k: v for k, v in params.items() if k != "lora"}
    assert {a.dtype for a in jax.tree.leaves(frozen)} == {jnp.dtype(jnp.bfloat16)}
    # matrix parameters a token meets in one forward pass: 4 experts a token,
    # 16 of 64 of them here; both 24-wide projections; the head over the slice
    per_layer = 28_409_856 + 2 * 14_336 * 24
    touched = (2 * (per_layer + 99_090_432)
               + (n_layers - 2) * (per_layer + 229_376 + 11_010_048
                                   + 4 * 11_010_048 * 16 // 64)
               + 32_768 * 3584)
    assert touched == w["parameters_touched_per_token_forward"]
    t = w["sequence_length"]
    assert t == 8192 and ref.pairs(t) == 33_558_528
    depth = n_layers * 32 * (128 + 64 + 128)
    assert ref.forward_flops(params, (t,)) == \
        2.0 * t * (touched + size(params["lora"])) + 2.0 * ref.pairs(t) * depth
    assert ref.train_flops(params, (t,)) == \
        2 * 2.0 * t * touched + 3 * 2.0 * t * size(params["lora"]) \
        + 3 * 2.0 * ref.pairs(t) * depth
    # ISSUE 45's shares of an expert layer's forward matrix work at T 8,192:
    # the core 6.9e11 of 1.52e12 FLOPs
    core = 2.0 * ref.pairs(t) * 32 * 320
    layer = core + 2.0 * t * (per_layer + 229_376 + 2 * 11_010_048 + 508_928)
    assert abs(core / 1e11 - 6.87) < 0.01 and abs(layer / 1e12 - 1.52) < 0.02
    assert ref.step_bytes(params, 4) == \
        2.0 * 2 * w["parameters_held"] + 4.0 * 4 * w["parameters_trained"]
    tokens = 65536.0
    assert ref.core_flops(params, tokens, t) == \
        2.0 * tokens / t * ref.pairs(t) * n_layers * 32 * (4 * 192 + 3 * 128)
    assert ref.core_bytes(params, tokens, 2) == tokens * 2 * n_layers * (
        3 * (32 * (2 * 128 + 64 + 128) + 64) + 3 * 32 * 128)
    assert ref.expert_flops(params, tokens) == \
        2 * 2.0 * tokens * 4 * 11_010_048 * 16 / 64 * (n_layers - 2)
    assert ref.expert_bytes(params, 8.0) == \
        2.0 * 2 * (n_layers - 2) * 16 * 11_010_048 * 8
    # the hyper-connections' floor: (2 n + 2) C forward and (3 n + 3) C backward
    # a token of a sublayer, the very first sublayer without its dX
    assert ref.hc_bytes(params, tokens, 2) == \
        tokens * 2 * (n_layers * 2 * 25 * 3584 - 4 * 3584)
    # ISSUE 45: a sublayer's forward mixing moves at least 587 MB at T 8,192
    assert (2 * 4 + 2) * 3584 * 8192 * 2 == 587_202_560


def test_the_file_states_the_published_widths_and_the_cut(published):
    config, model, params = published
    kw = config["model"]["kwargs"]
    assert (kw["d_model"], kw["n_streams"], kw["sinkhorn_iters"], kw["hc_eps"],
            kw["res_clamp"], kw["n_heads"], kw["q_rank"], kw["kv_rank"],
            kw["nope_dim"], kw["rope_dim"], kw["v_dim"], kw["d_ff"], kw["d_expert"],
            kw["experts_per_token"], kw["n_shared"], kw["first_dense"],
            kw["rope_theta"], kw["norm_eps"], kw["routed_scaling_factor"]) \
        == (config["hidden_size"], config["hc_mult"], config["hc_sinkhorn_iters"],
            config["hc_eps"],
            [config["mhc_h_res_clamp_min"], config["mhc_h_res_clamp_max"]],
            config["num_attention_heads"], config["q_lora_rank"],
            config["kv_lora_rank"], config["qk_nope_head_dim"],
            config["qk_rope_head_dim"], config["v_head_dim"],
            config["intermediate_size"], config["moe_intermediate_size"],
            config["num_experts_per_tok"], config["n_shared_experts"],
            config["first_k_dense_replace"], config["rope_theta"],
            config["rms_norm_eps"], config["routed_scaling_factor"]) \
        == (3584, 4, 20, 1e-6, [-30, 30], 32, 768, 512, 128, 64, 128, 9216, 1024,
            4, 1, 2, 10000, 1e-6, 2)
    scaling = config["rope_scaling"]
    assert (kw["rope_factor"], kw["rope_beta_fast"], kw["rope_beta_slow"],
            kw["rope_original"], kw["rope_mscale"], kw["rope_mscale_all_dim"]) \
        == (scaling["factor"], scaling["beta_fast"], scaling["beta_slow"],
            scaling["original_max_position_embeddings"], scaling["mscale"],
            scaling["mscale_all_dim"]) == (64, 32, 1, 4096, 1, 1)
    assert config["tie_word_embeddings"] is False and "head" in params
    assert config["scoring_func"] == "sigmoid" and config["norm_topk_prob"] is True
    assert config["topk_method"] == "noaux_tc" and config["n_group"] == 1
    # the cut: depth, the experts held, the vocabulary slice - each with the
    # published value beside it and inside the guide's floors
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert (config["published_num_hidden_layers"], config["published_n_routed_experts"],
            config["published_vocab_size"]) == (40, 64, 131072)
    assert kw["n_layers"] == 40 and kw["n_experts"] == 64          # the router's width
    held = config["held_layers"]
    assert kw["layers"] == held == list(range(len(held))) and 6 <= len(held) <= 10
    assert config["num_hidden_layers"] == len(held)
    assert kw["held"] == config["held_experts"] == [0, config["n_routed_experts"]]
    assert config["n_routed_experts"] == 64 // 4 == 16
    assert config["vocab_size"] == 131072 // 4 == 32768
    assert model.held_layers == tuple(held) and model.held_experts == (0, 16)
    assert model.expert_layers == tuple(held[2:]) and len(model.expert_layers) >= 4
    assert params["layer_2"]["router"].shape == (3584, 64)
    assert params["layer_2"]["w1"].shape == (16, 3584, 1024)
    assert params["layer_2"]["s1"].shape == (3584, 1024)
    assert "four chips" in config["deployment"]
    # the multi-token-prediction module: stated, not held, and said to be a departure
    assert config["num_nextn_predict_layers"] == 1
    assert "DEPARTURE" in config["loss"] and "multi-token-prediction" in config["loss"]
    assert not any("mtp" in k or "nextn" in k for k in params)
    assert config["assumed"] and config["check"]["why"] and config["cut"]
    assert config["engine"]["chunk"] == 1
    # the cell's traffic: ISSUE 45's file, or its twin with another lr
    manifest, cell = _cell_entry()
    assert cell["traffic"] in ("lora4of256long", "lora4of256long_xing4")
    traffic = load(REPO + f"/fedbench/traffic/{cell['traffic']}.json")
    base = load(REPO + "/fedbench/traffic/lora4of256long.json")
    assert {k: v for k, v in traffic.items() if k not in ("lr", "lr_why")} \
        == {k: v for k, v in base.items() if k not in ("lr", "lr_why")}
    assert traffic["dataset"]["args"]["seq_len"] == config["widths"]["sequence_length"]
    assert traffic["dataset"]["args"]["vocab"] == config["vocab_size"]
    # the reference module states what the tree's shapes do not
    ref = reference.resolve(config["reference"])
    assert (ref.N_HEADS, ref.TOP_K, ref.FIRST_HELD, ref.SCALING, ref.SINKHORN_ITERS,
            ref.HC_EPS, list(ref.CLAMP), ref.LORA_ALPHA, ref.EPS) \
        == (kw["n_heads"], kw["experts_per_token"], kw["held"][0],
            kw["routed_scaling_factor"], kw["sinkhorn_iters"], kw["hc_eps"],
            kw["res_clamp"], kw["lora_alpha"], kw["norm_eps"])
    assert ref.ROPE == dict(theta=kw["rope_theta"], factor=kw["rope_factor"],
                            beta_fast=kw["rope_beta_fast"], beta_slow=kw["rope_beta_slow"],
                            original=kw["rope_original"], mscale=kw["rope_mscale"],
                            mscale_all_dim=kw["rope_mscale_all_dim"])
    assert ref.n_streams(params) == kw["n_streams"]


def test_the_file_holds_every_number_of_the_catalog_row():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog in this image")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Xing4.0-29B-A4B")
    config = load(REPO + "/fedbench/configs/xing4_0_29b_a4b.json")
    manifest, cell = _cell_entry()
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert config["source"] == entry["source"] == row["source_url"]
    assert len(entry["source"]) <= 200
    differs = [k for k, v in row["config"].items() if config.get(k, "absent") != v]
    assert sorted(differs) == sorted(config["reduced"]) == sorted(entry["reduced"])
    assert (cell["config"], cell["chips"]) == (CONFIG, 1)
    assert entry["file"] == "fedbench/configs/xing4_0_29b_a4b.json"
    assert sum(w["config"] == CONFIG for w in manifest["workloads"]) == 1
    assert all(len(x["why"]) <= 200 for x in (entry, cell))
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1


def test_the_reference_shares_no_code_with_the_program():
    with open(REPO + "/fedbench/reference/xing4_0_29b_a4b.py") as f:
        text = f.read()
    assert "fedml_tpu" not in text.split('"""', 2)[2]
    for name in ("ragged_dot", "top_k(", "argsort", "custom_v", "pallas", "sort(",
                 "causal_attention", "moveaxis", "named_scope"):
        assert name not in text, name


def test_the_tiny_cell_runs_through_the_command(tmp_path):
    """The benchmark's command on a cut-down scratch copy: one line, correct,
    with the two counters' metrics (a CPU run prints counts only) - and the
    thirteen new readers are listed for the cell."""
    root = tiny_checkout(str(tmp_path))
    r = run_cell(root, CELL, trace=1)
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    # 16 of 64 held: near a quarter of the slots
    assert 10.0 < line["metrics"]["xing4_held_slot_pct"]["value"] < 45.0
    # the columns of a seeded model's maps after 20 iterations
    assert 0 < line["metrics"]["hc_col_err"]["value"] < 1e-2
    assert set(line["metrics"]) == {"real_slot_pct", "hc_col_err", "xing4_held_slot_pct"}
    detail = json.loads(r.stdout.split("fedbench detail ", 1)[1].splitlines()[0])
    assert detail["check"]["ok"] and detail["window_compiles"] == 0
    listed = [m["name"] for m in json.load(open(root + "/BENCHMARK.json"))["per_layer"]
              if CELL in m.get("workloads", [CELL])]
    assert set(NEW) <= set(listed)


@pytest.mark.parametrize("name", NEW)
def test_new_readers_match_their_entries_and_read_nothing_from_a_program_without(
        monkeypatch, name):
    """Each of the thirteen is listed for the new cell only, repeats its
    module's declaration, moves ``rounds_per_s`` and on a program that lacks
    the scope, the kernel or the counter returns None."""
    manifest = load(REPO + "/BENCHMARK.json")
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    mod = layer_metrics.module(name)
    assert entry["workloads"] == [CELL]
    assert (entry["layer"], entry["unit"], entry["source"], entry["moves"]) \
        == (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES)
    assert entry["moves"] == "rounds_per_s"
    assert entry["better"] == ("higher" if name.endswith(("roofline", "slot_pct"))
                               else "lower")

    class Engine:            # a program with no counters and no scope map
        chunk = 1
        transfer_stats = object()

    class Cell:
        name, chips = CELL, 1
        config = {"reference": "resnet18gn_cifar", "trainer": {},
                  "model": {"kwargs": {"held": [0, 16]}}}
        traffic = {"cohort": 4, "batch_size": 1, "epochs": 1}

    ctx = {"engine": Engine(), "cell": Cell(), "trace": None, "on_chip": True,
           "window": {"attempted": 3}, "samples": 24.0, "params": {},
           "device": {"kind": "TPU v5 lite"}, "data": None}
    assert layer_metrics.read(entry, ctx) is None


def test_a_scope_the_program_lacks_reads_as_nothing_not_as_zero(monkeypatch):
    """`program_trace.scope_ms` gives 0.0 for a label no op carries (the
    parent's program under this PR's benchmark files): the nine device times
    and the share of the hyper-connections' floor leave the metric out."""
    from fedbench.harness import program_trace
    monkeypatch.setattr(program_trace, "read", lambda ctx: {
        "unknown_share": 0.0, "scope_ms": {"attention": 5.0, "moe_router": 2.5}})
    times = {n: layer_metrics.module(n).read({}) for n in TIMES}
    assert times == {**dict.fromkeys(TIMES), "xing4_mla_attention_ms": 5.0,
                     "xing4_router_ms": 2.5}
    assert layer_metrics.module("hc_roofline").read(
        {"cell": type("C", (), {"config": {"reference": CONFIG}})}) is None


def test_the_hyper_connection_readers_read_what_the_program_gave(monkeypatch):
    """`hc_col_err` divides the summed counter by the steps of the window's
    rounds and takes the worst (layer, sublayer)'s column part; `hc_roofline`
    is the reference's bytes over the peak over both labels' time."""
    from fedbench.harness import peaks, program_trace
    from fedbench.layer_metrics import hc_col_err, hc_roofline
    err = np.zeros((3, 2, 2))
    err[..., 0] = 1e-6 * 48
    err[..., 1] = 48 * 1e-4 * np.arange(1, 7).reshape(3, 2)

    class Stats:
        def program_counters(self):
            return {"hc_sinkhorn_err": err}

    class Cell:
        traffic = {"cohort": 4, "batch_size": 1, "epochs": 1}
        config = {"reference": CONFIG, "trainer": {"train_dtype": "bfloat16"}}

    data = type("D", (), {"client_num_samples": np.full(8, 2.0),
                          "client_shards": {"x": np.zeros((8, 2, 1, 16))}})
    ctx = {"engine": type("E", (), {"transfer_stats": Stats()}), "cell": Cell(),
           "data": data, "window": {"attempted": 6}, "samples": 48.0,
           "device": {"kind": "TPU v5 lite"}}
    assert abs(hc_col_err.read(ctx) - 6e-4) < 1e-12      # 6 rounds x 4 x 2 steps
    Stats.program_counters = lambda self: {}
    assert hc_col_err.read(ctx) is None
    config = tiny_doc("configs", CONFIG)
    model = create_model("xing4", 128, **config["model"]["kwargs"])
    ctx["params"] = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    monkeypatch.setattr(program_trace, "read", lambda ctx: {
        "unknown_share": 0.0, "scope_ms": {"hc_maps": 3.0, "hc_mix": 1.0}})
    tokens = 48.0 / 6 * 16
    want = reference.resolve(CONFIG).hc_bytes(ctx["params"], tokens, 2)
    assert want == tokens * 2 * (3 * 2 * 25 * 64 - 4 * 64)
    got = hc_roofline.read(ctx)
    assert abs(got - 100.0 * want / peaks.peaks("TPU v5 lite")["bytes_per_s"] / 4e-3) \
        < 1e-9 * got
