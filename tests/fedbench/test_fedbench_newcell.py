"""The harness takes a new cell as data, and refuses to run where it cannot
measure."""
import json
import os
import subprocess
import sys
import textwrap

from fedbench_tiny import REPO, run_cell, tiny_checkout


def _run(cwd, workload, env=None, chips=1):
    return run_cell(cwd, workload, seed=2, trace=1, chips=chips, env=env)


def test_no_tpu_and_no_explicit_cpu_exits_nonzero_and_prints_no_line():
    """A CPU that jax merely fell back to (forced through the config, so the
    test means the same on any machine) is refused."""
    code = ("import jax, runpy, sys; jax.config.update('jax_platforms', 'cpu');"
            "sys.argv = ['fedbench.run', '--workload', 'resnet18gn.xdev10of4000',"
            " '--seconds', '1'];"
            "runpy.run_module('fedbench.run', run_name='__main__')")
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU attached" in r.stderr


def test_fewer_chips_than_the_cell_asks_for_is_refused(tmp_path):
    r = _run(tiny_checkout(tmp_path), "resnet18gn.silo128of4096.x4", chips=2)
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "needs 4 chips" in r.stderr


def test_without_the_program_it_exits_nonzero_and_prints_no_line(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under
    ``paths`` has no system to measure."""
    root = tiny_checkout(tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-m", "fedbench.run", "--workload",
         "resnet18gn.xdev10of4000", "--seconds", "1"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "not in this checkout" in r.stderr


def test_a_cell_made_only_of_new_files_is_found_and_runs(tmp_path):
    """New configuration, traffic mix (streamed, another engine class), data
    generator, client-size law, plain reference and two per-layer metrics (one
    reads the window, one the program's own counters): seven new files and
    four manifest entries, no edit to a file that was there."""
    root = tiny_checkout(tmp_path)
    bench = root / "fedbench"
    (bench / "configs" / "logreg.json").write_text(json.dumps({
        "name": "logreg", "source": "test", "reduced": [], "assumed": [],
        "model": {"factory": "fedml_tpu.models.create_model", "name": "lr",
                  "kwargs": {}},
        "trainer": {"loss": "ce", "optimizer": "sgd", "train_dtype": "float32"},
        "engine": {"local_dtype": None, "chunk": 2}, "reference": "logreg",
        "check": {"param_tol": 1e-4, "why": "one dense layer in float32"}}))
    (bench / "traffic" / "stream3of9.json").write_text(json.dumps({
        "dataset": {"generator": "blobs", "args": {"dim": 6, "classes": 3}},
        "population": 9, "cohort": 3,
        "client_sizes": {"law": "stairs", "top": 8},
        "batch_size": 4, "epochs": 1, "lr": 0.1, "mesh_devices": 1,
        "engine": {"class": "fedml_tpu.parallel.engine.MeshFedOptEngine",
                   "args": {"streaming": True}}}))
    (bench / "data" / "blobs.py").write_text(textwrap.dedent('''
        import numpy as np
        from fedbench.data import slot_mask

        def make(seed, sizes, batch_size, n_batches, dim, classes):
            g = np.random.default_rng(seed)
            mask = slot_mask(sizes, batch_size, n_batches)
            y = g.integers(0, classes, mask.shape).astype(np.int32) * (mask > 0)
            x = g.normal(size=mask.shape + (dim,)).astype(np.float32)
            x = (x + y[..., None]) * mask[..., None]
            return {"x": x, "y": y.astype(np.int32), "mask": mask}, classes
        '''))
    (bench / "client_sizes" / "stairs.py").write_text(textwrap.dedent('''
        import numpy as np

        def sizes(law, population):
            return 1 + np.arange(population, dtype=np.int64) % int(law["top"])

        def cap(law):
            return int(law["top"])
        '''))
    (bench / "reference" / "logreg.py").write_text(textwrap.dedent('''
        import jax.numpy as jnp

        def forward(params, x):
            d = params["Dense_0"]
            return jnp.dot(x.reshape(len(x), -1), d["kernel"],
                           precision="highest") + d["bias"]

        def forward_flops(params, x_shape):
            return 2.0 * params["Dense_0"]["kernel"].size
        '''))
    (bench / "layer_metrics" / "window_rounds.py").write_text(textwrap.dedent('''
        LAYER, UNIT, SOURCE, MOVES = "entry", "rounds", "program_counter", "rounds_per_s"

        def read(ctx):
            return ctx["window"]["attempted"]
        '''))
    (bench / "layer_metrics" / "h2d_MB.py").write_text(textwrap.dedent('''
        LAYER, UNIT, SOURCE, MOVES = "H2D upload", "MB/round", "program_counter", "rounds_per_s"

        def read(ctx):
            if not ctx["engine"].streaming:
                return None
            return ctx["engine"].transfer_stats.h2d_bytes / 1e6 / ctx["window"]["attempted"]
        '''))
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append({"name": "logreg", "source": "test", "reduced": [],
                                "file": "fedbench/configs/logreg.json", "why": "t"})
    manifest["workloads"].append({"name": "logreg.stream3of9", "config": "logreg",
                                  "traffic": "stream3of9", "chips": 1, "why": "t"})
    for name, layer, unit in (("window_rounds", "entry", "rounds"),
                              ("h2d_MB", "H2D upload", "MB/round")):
        manifest["per_layer"].append({
            "name": name, "unit": unit, "better": "lower", "layer": layer,
            "source": "program_counter", "moves": "rounds_per_s",
            "workloads": ["logreg.stream3of9"]})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    r = _run(root, "logreg.stream3of9")
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    # counts are the only metrics a CPU run prints: the window's, and the
    # streamed path's exact byte count from the program's transfer_stats
    assert line["metrics"]["window_rounds"]["value"] == line["attempted"]
    assert line["metrics"]["h2d_MB"]["value"] > 0
    assert set(line["metrics"]) == {"window_rounds", "h2d_MB", "real_slot_pct"}
