"""The sizes the CPU tests run at, owned by the tests.

The benchmark's command has one size, the files'.  ``tiny/configs/<name>.json``
and ``tiny/traffic/<name>.json`` beside this module hold overrides that cut a
configuration or a traffic mix down to seconds on a CPU; a test merges them
over the benchmark's files in memory (``tiny_doc``) or in a scratch copy of
the benchmark (``tiny_checkout``) that it then runs with the repo on
``PYTHONPATH`` as the system under test.  A later PR that adds a cell adds its
override files here.
"""
import glob
import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))


def load(path):
    with open(path) as f:
        return json.load(f)


def merge(base: dict, over: dict) -> dict:
    """Recursive dict merge; ``over`` wins, lists and scalars are replaced."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def tiny_doc(kind: str, name: str, root: str = REPO) -> dict:
    """``fedbench/<kind>/<name>.json`` (kind: configs | traffic) cut down."""
    return merge(load(os.path.join(root, "fedbench", kind, name + ".json")),
                 load(os.path.join(HERE, "tiny", kind, name + ".json")))


def held_back() -> list:
    """Cells whose files ship but which BENCHMARK.json does not list."""
    return load(os.path.join(REPO, "fedbench", "held_back.json"))["cells"]


def tiny_checkout(dst, with_held_back: bool = False):
    """BENCHMARK.json and ``fedbench/`` copied into ``dst``, every file that
    has an override cut down; optionally with the held-back cells listed."""
    manifest = load(os.path.join(REPO, "BENCHMARK.json"))
    if with_held_back:
        for cell in held_back():
            manifest["configs"].append(cell["config"])
            manifest["workloads"].append(cell["workload"])
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    shutil.copytree(os.path.join(REPO, "fedbench"), os.path.join(dst, "fedbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for path in glob.glob(os.path.join(HERE, "tiny", "*", "*.json")):
        kind, name = path.split(os.sep)[-2:]
        with open(os.path.join(dst, "fedbench", kind, name), "w") as f:
            json.dump(tiny_doc(kind, name[:-len(".json")]), f)
    return dst


def run_cell(root, workload: str, *, seed: int = 5, trace: int = 0,
             chips: int = 1, env=None):
    """The benchmark's command in ``root``, on the CPU by explicit choice."""
    env = dict(os.environ if env is None else env)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, "-m", "fedbench.run", "--workload", workload, "--seed",
         str(seed), "--seconds", "1", "--trace", str(trace)],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)
