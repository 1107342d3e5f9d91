"""The repo has one instrument, `python3 -m fedbench.run` (ISSUE 28).

Two things hold that in place.  The second benchmark and the tools
around it are gone and nothing that is run or read day to day still
points at them.  And chip_smoke.py's headline phase, which says it
proves the program the benchmark times, builds the silo cell's recipe:
every field is compared between the two engines, the smoke's own and
the one fedbench's builder makes from the cell's files."""
import copy
import json
import os
import re
import sys

import numpy as np
import pytest

from repo_tree import REPO, source_files

sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

# -- nothing names what was retired ------------------------------------------

# spelled in two pieces, so that this file does not name them either
RETIRED = {
    "second_benchmark": r"(?<!\w)bench" r"\.py",
    "experiment_runner": "profile" "_bench",
    "record_differ": "bench" "_diff",
    "chip_queue": "run_chip" "_queue",
}
SWEPT = ("fedml_tpu", "tools", "tests", "chip_smoke.py", "README.md",
         "benchmarks/README.md", ".claude/skills/verify/SKILL.md")


@pytest.mark.parametrize("name", sorted(RETIRED))
def test_retired_instrument_is_not_referenced(name):
    pattern = re.compile(RETIRED[name])
    files = source_files(*SWEPT)
    assert len(files) > 200, len(files)     # the sweep saw the tree
    hits = []
    for rel in files:
        with open(os.path.join(REPO, rel), errors="ignore") as f:
            hits += [f"{rel}:{i}: {line.strip()[:100]}"
                     for i, line in enumerate(f, 1) if pattern.search(line)]
    assert not hits, "\n".join(hits[:20])


# -- the smoke's headline is the silo cell ------------------------------------

def _cell_files():
    with open(os.path.join(REPO, "fedbench", "configs",
                           "resnet18gn_cifar.json")) as f:
        config = json.load(f)
    with open(os.path.join(REPO, "fedbench", "traffic",
                           "silo128of1024.json")) as f:
        traffic = json.load(f)
    return config, traffic


@pytest.fixture(scope="module")
def engines():
    """(the smoke's headline engine, the cell's engine, the cell's traffic
    file).  Both at the real widths and batch size over a few small
    clients: an engine is built, nothing is compiled."""
    from fedbench.harness import build
    from fedml_tpu.parallel.mesh import make_mesh
    config, traffic = _cell_files()
    sz = chip_smoke.Sizes()
    n_clients, spc = 2, sz.batch_size
    rs = np.random.RandomState(0)
    x = rs.rand(n_clients * spc, sz.image_hw, sz.image_hw, 3).astype(
        np.float32)
    y = rs.randint(0, 10, n_clients * spc)
    smoke = chip_smoke.headline_engine(
        *chip_smoke.build_headline(x, y, n_clients=n_clients),
        mesh=make_mesh(1))
    small = copy.deepcopy(traffic)
    small.update(population=n_clients, cohort=n_clients,
                 client_sizes={"law": "equal", "samples": spc})
    cell = build.make_engine(config, small, build.make_data(small, seed=0),
                             seed=0)
    return smoke, cell, traffic


FIELDS = {
    "model": lambda e: e.cfg.model,
    "batch_size": lambda e: e.cfg.batch_size,
    "lr": lambda e: e.cfg.lr,
    "epochs": lambda e: e.cfg.epochs,
    "train_dtype": lambda e: np.dtype(e.trainer.train_dtype),
    "local_dtype": lambda e: np.dtype(e.local_dtype),
    "chunk": lambda e: e.chunk,
    "batch_unroll": lambda e: e.trainer.batch_unroll,
    "engine_class": lambda e: type(e),
    "image_shape": lambda e: tuple(e.data.client_shards["x"].shape[-3:]),
}


@pytest.mark.parametrize("field", sorted(FIELDS))
def test_smoke_headline_is_the_silo_cell(engines, field):
    smoke, cell, _ = engines
    assert FIELDS[field](smoke) == FIELDS[field](cell), field


@pytest.mark.parametrize("size, where", [
    ("n_clients", lambda t: t["cohort"]),
    ("samples_per_client", lambda t: t["client_sizes"]["samples"]),
    ("batch_size", lambda t: t["batch_size"]),
], ids=["n_clients", "samples_per_client", "batch_size"])
def test_smoke_default_sizes_are_the_silo_cells(engines, size, where):
    assert getattr(chip_smoke.Sizes(), size) == where(engines[2])
