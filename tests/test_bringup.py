"""Bring-up contracts (ISSUE 21): the one placeable compile cache, the
published-peaks table and the headline stepper's one compile.  The
no-fallback device check is held for chip_smoke.py by
tests/test_chip_smoke.py and for the benchmark by tests/fedbench/."""
import os
import sys

import jax
import pytest

from fedml_tpu.obs import programs
from fedml_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- compile cache ---------------------------------------------------------

@pytest.fixture
def cache_dir_restored():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_env_var_wins_and_code_sets_no_directory(
        monkeypatch, cache_dir_restored):
    jax.config.update("jax_compilation_cache_dir", "sentinel-untouched")
    monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/else")
    assert compile_cache.configure() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == "sentinel-untouched"


def test_cache_default_is_the_fixed_in_checkout_path(
        monkeypatch, cache_dir_restored):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    assert compile_cache.configure() == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == os.path.join(
        REPO, ".jax_cache")


def test_cache_default_is_never_a_temp_or_home_path():
    d = compile_cache.DEFAULT_DIR
    assert not d.startswith(("/tmp", os.path.expanduser("~/.cache")))
    assert str(os.getpid()) not in os.path.basename(d)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_spawned_ranks_get_an_explicit_cpu_platform(monkeypatch):
    """A parent that holds the chip hands its cluster children
    JAX_PLATFORMS=cpu explicitly (not via a child's setdefault)."""
    from fedml_tpu.parallel.multihost import spawn_cluster
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")       # the parent's choice
    outs = spawn_cluster(
        [sys.executable, "-c",
         "import os; print(os.environ['JAX_PLATFORMS'])"], 2, timeout_s=60)
    assert [o.strip() for o in outs] == ["cpu", "cpu"]


# -- published peaks -------------------------------------------------------

class _FakeDevice:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def test_peak_flops_reads_the_v5e_row_from_the_table(monkeypatch):
    monkeypatch.setattr(jax, "devices",
                        lambda: [_FakeDevice("tpu", "TPU v5 lite")])
    assert programs.peak_flops() == 197e12


def test_peak_flops_unknown_accelerator_is_an_error(monkeypatch):
    monkeypatch.setattr(jax, "devices",
                        lambda: [_FakeDevice("tpu", "TPU v99")])
    with pytest.raises(ValueError, match="TPU v99"):
        programs.peak_flops()


def test_peak_flops_ignores_the_removed_env_override(monkeypatch):
    monkeypatch.setenv("FEDML_PEAK_FLOPS", "1")
    assert programs.peak_flops() == float(os.cpu_count() or 1) * 3.2e9 * 16


# -- the headline stepper compiles its round once ---------------------------

def test_headline_run_compiles_the_round_once():
    """Fresh single-device variables made the first call a program of
    its own: two ~2-minute compiles (and two 80 MB cache entries) of one
    round on the chip.  HeadlineRun places them like every later
    round's inputs, so three rounds are ONE compiled program."""
    import numpy as np
    sys.path.insert(0, REPO)
    import chip_smoke
    rs = np.random.RandomState(0)
    x = rs.rand(4 * 16, 8, 8, 3).astype(np.float32)
    y = rs.randint(0, 10, 4 * 16)
    engine = chip_smoke.headline_engine(
        *chip_smoke.build_headline(x, y, n_clients=4, model_name="lr",
                                   batch_size=8))
    run = chip_smoke.HeadlineRun(engine)
    for _ in range(3):
        variables, _ = run.step()
    jax.block_until_ready(variables)
    assert engine.round_fn_streaming.inner._cache_size() == 1
