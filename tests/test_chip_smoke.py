"""chip_smoke.py's plumbing, on the CPU: the no-fallback contract (no
TPU => non-zero exit and no `"ok": true` line) and every phase function
driven at a tiny size — the ops' plain paths (nothing is lowered for a
TPU here), the `--chips 4` phase on four of the virtual CPU devices.
What only the chip can show (Mosaic-compiled kernels, the real widths,
times) is the chip run's job; tests/test_tpu_compile.py compiles the
kernels for it."""
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY = chip_smoke.Sizes(
    model="lr", n_clients=8, samples_per_client=16, batch_size=8,
    image_hw=8, warmup_rounds=1, timed_rounds=2, oracle_clients=4,
    attn_shapes=((1, 128, 2, 1, 64),), rotary_shapes=((1, 128, 2, 128),),
    hc_shapes=((1, 128, 4, 128),), platform="cpu")


def _lines(capsys) -> list:
    return [json.loads(l) for l in capsys.readouterr().out.splitlines()
            if l.startswith("{")]


def _run_script(cwd: str):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_means_nonzero_exit_and_no_ok_line():
    r = _run_script(REPO)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    device = json.loads(r.stdout.splitlines()[0])
    assert device["phase"] == "device" and device["platform"] == "cpu"
    assert "headline" not in r.stdout        # ended before phase (b)


def test_script_alone_in_a_directory_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    r = _run_script(str(tmp_path))
    assert r.returncode != 0 and '"ok"' not in r.stdout


def test_phase_device_refuses_a_wrong_device_count():
    with pytest.raises(SystemExit, match="needs 3 device"):
        chip_smoke.phase_device("cpu", 3)


def test_phase_headline_tiny(capsys):
    chip_smoke.phase_headline(TINY, seed=0)
    (line,) = _lines(capsys)
    assert line["phase"] == "headline" and line["clients"] == 8
    assert len(line["train_loss"]) == 3
    assert line["train_loss"][-1] < line["train_loss"][0]
    assert line["variables_platform"] == ["cpu"]


def test_phase_headline_fails_off_platform():
    with pytest.raises(AssertionError):
        chip_smoke.phase_headline(
            chip_smoke.dataclasses.replace(TINY, platform="tpu"), seed=0)


def test_phase_oracle_tiny(capsys):
    chip_smoke.phase_oracle(TINY, seed=0)
    (line,) = _lines(capsys)
    assert line["matmul_precision"] == "highest"
    assert line["max_abs_param_diff"] <= line["tolerance"]
    assert line["max_abs_update"] > 100 * line["tolerance"]


def test_phase_kernels_tiny_interpret_mode(capsys):
    chip_smoke.phase_kernels(TINY, seed=0)
    lines = _lines(capsys)
    assert [l["op"] for l in lines] == ["causal_attention", "rotate_half",
                                        "hyper_connection"]
    assert not any(l["compiled"] for l in lines)   # no Mosaic on the CPU
    assert lines[1]["max_bf16_ulps_y_dx"] == [0.0, 0.0]   # the plain body
    # the rules' plain bodies: u, ht, X', dX, dy as near the float32 oracle
    # as the plain path's own derivative
    hc = lines[2]
    assert hc["kernels"] == 0 and len(hc["fused_l2_u_ht_out_dX_dy"]) == 5
    assert all(f <= 1.5 * p + 1e-6 for f, p in zip(
        hc["fused_l2_u_ht_out_dX_dy"], hc["plain_l2_u_ht_out_dX_dy"]))


@pytest.mark.parametrize("op, others", [
    ("causal_attention", ("rotary_shapes", "hc_shapes")),
    ("rotate_half", ("attn_shapes", "hc_shapes")),
    ("hyper_connection", ("attn_shapes", "rotary_shapes"))])
def test_phase_kernels_demands_the_compiled_path_on_tpu(op, others):
    """On a TPU the kernel path is asserted, never assumed: a run that
    claims the platform but lowers no tpu_custom_call fails, at each op
    (the other ones' shapes taken out, so that this one is reached)."""
    with pytest.raises(AssertionError, match=f"{op}: kernel path"):
        chip_smoke.phase_kernels(chip_smoke.dataclasses.replace(
            TINY, platform="tpu", **{k: () for k in others}), seed=0)


def test_phase_cli_round_trip(capsys):
    chip_smoke.phase_cli()
    line = [l for l in _lines(capsys) if l.get("phase") == "cli"][0]
    assert line["rc"] == 0 and line["rounds"] == 4


def test_phase_four_chip_tiny_on_virtual_devices(capsys):
    chip_smoke.phase_four_chip(TINY, seed=0)
    lines = _lines(capsys)
    four, one, cmp_ = lines
    assert four["chips"] == 4 and four["cohort_rows_per_device"] == [2] * 4
    assert four["all_reduce"] is True
    assert one["chips"] == 1 and one["cohort_rows_per_device"] == [8]
    assert one["all_reduce"] is None          # checked on the mesh only
    assert cmp_["max_abs_diff_over_max_abs"] < cmp_["tolerance"]
