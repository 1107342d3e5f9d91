"""Copy-audit regression gate (the PR-4 tentpole's enforcement arm).

tools/hlo_copy_audit.py compiles every engine family's round program on
the 8-device virtual CPU mesh and censuses the optimized HLO for
`copy`/`copy-start` instructions.  These tests pin that census:

* per-family copy-bytes/ops CEILINGS (benchmarks/hlo_copy_ceilings.json)
  — a carry-layout or donation regression shows up as new copies here
  long before a chip window can price it in wall-clock;
* donation floors — the alias maps (donated args XLA actually aliased
  into outputs) must not shrink;
* the FedAvg reduction vs the committed pre-PR baseline
  (benchmarks/hlo_copy_baseline.json, generated from the seed engines) —
  the flat chunk-carry restructure removed the donated-conv-kernel
  staging copy, and that win must not silently evaporate;
* the obs gauge (`engine_copy_bytes_compiled{family=...}`) the audit
  publishes.

Recalibration protocol (same as benchmarks/quality_bands.json): the
optimized HLO is deterministic per jax/jaxlib build, so the pins are
EXACT — but if a pin trips and the running toolchain differs from the
calibration env recorded in the ceilings file, the failure names the
version skew and says "recalibrate" instead of pointing at the training
code.
"""
import json
import os
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import hlo_copy_audit  # noqa: E402

CEILINGS_PATH = os.path.join(REPO, "benchmarks", "hlo_copy_ceilings.json")
BASELINE_PATH = os.path.join(REPO, "benchmarks", "hlo_copy_baseline.json")


def _toolchain_skew(calibration: dict) -> list[str]:
    import jaxlib
    skew = []
    if calibration.get("jax") != jax.__version__:
        skew.append(f"jax {calibration.get('jax')} -> {jax.__version__}")
    if calibration.get("jaxlib") != jaxlib.__version__:
        skew.append(
            f"jaxlib {calibration.get('jaxlib')} -> {jaxlib.__version__}")
    return skew


def _pin_failure(what: str, calibration: dict):
    """Band-violation failure that names a toolchain skew when there is
    one (VERDICT next-#7 protocol: a version-skew failure must say
    'recalibrate', not masquerade as a code regression)."""
    skew = _toolchain_skew(calibration)
    if skew:
        pytest.fail(
            f"{what} — AND the toolchain moved since calibration "
            f"({', '.join(skew)}): RECALIBRATE benchmarks/"
            f"hlo_copy_ceilings.json on this build (python tools/"
            f"hlo_copy_audit.py) instead of hunting an engine regression")
    pytest.fail(
        f"{what} on the CALIBRATED toolchain (jax {jax.__version__}) — "
        f"a real carry-layout/donation regression in the round programs")


@pytest.fixture(scope="module")
def audit():
    """One full-family census per test run (~16 s of tiny-CNN compiles;
    the jitted programs land in the persistent compile cache)."""
    return hlo_copy_audit.audit_families()


@pytest.fixture(scope="module")
def ceilings():
    return json.load(open(CEILINGS_PATH))


def test_ceilings_artifact_shape(ceilings):
    """The committed artifact must carry the calibration env machine-
    readably and one ceiling row per audited family."""
    cal = ceilings["calibration"]
    for key in ("jax", "jaxlib", "backend", "n_devices", "model", "date"):
        assert key in cal, f"calibration lost {key!r}"
    assert set(ceilings["families"]) == set(hlo_copy_audit.ALL_FAMILIES)


def test_copy_audit_ceilings_artifact_exists(ceilings):
    """ISSUE 4: the copy-regression gate needs its pinned artifacts —
    the per-family ceilings (with a machine-readable calibration env)
    and the committed pre-PR baseline the FedAvg reduction is asserted
    against.  Losing either silently disarms the gate."""
    assert ceilings["families"], "ceilings artifact carries no families"
    for fam, pins in ceilings["families"].items():
        assert pins["copy_bytes_ceiling"] >= 0, fam
    for key in ("jax", "jaxlib", "date"):
        assert key in ceilings["calibration"], (
            f"ceilings calibration env lost {key!r} (the recalibrate "
            "protocol needs it to name version skew)")
    with open(BASELINE_PATH) as f:
        base = json.load(f)
    assert "fedavg_resident" in base["families"]


def test_copy_bytes_under_ceilings(audit, ceilings):
    cal = ceilings["calibration"]
    over = []
    for fam, pins in ceilings["families"].items():
        got = audit["families"][fam]
        if got["copy_bytes"] > pins["copy_bytes_ceiling"]:
            over.append(f"{fam}: copy_bytes {got['copy_bytes']} > "
                        f"ceiling {pins['copy_bytes_ceiling']}")
        if got["copy_ops"] > pins["copy_ops_ceiling"]:
            over.append(f"{fam}: copy_ops {got['copy_ops']} > "
                        f"ceiling {pins['copy_ops_ceiling']}")
    if over:
        _pin_failure("copy-audit ceilings exceeded: " + "; ".join(over),
                     cal)


def test_donation_alias_floors(audit, ceilings):
    """Donation completeness must not regress: the alias map (donated
    args XLA aliased into outputs) per family stays at or above the
    pinned floors."""
    cal = ceilings["calibration"]
    under = []
    for fam, pins in ceilings["families"].items():
        got = audit["families"][fam]
        if got["donated_args"] < pins["donated_args_floor"]:
            under.append(f"{fam}: donated_args {got['donated_args']} < "
                         f"floor {pins['donated_args_floor']}")
        if got["aliased_outputs"] < pins["aliased_outputs_floor"]:
            under.append(f"{fam}: aliased_outputs "
                         f"{got['aliased_outputs']} < floor "
                         f"{pins['aliased_outputs_floor']}")
    if under:
        _pin_failure("donation alias floors violated: " +
                     "; ".join(under), cal)


def test_fedavg_copy_bytes_reduced_vs_baseline(audit):
    """ISSUE-4 acceptance: the FedAvg round program's copy bytes are
    REDUCED vs the committed pre-PR baseline (the flat chunk-carry
    restructure removed the donated-conv-kernel staging copy — 204.8 KB
    on the census model)."""
    base = json.load(open(BASELINE_PATH))
    cal = base["meta"]
    now = audit["families"]["fedavg_resident"]["copy_bytes"]
    was = base["families"]["fedavg_resident"]["copy_bytes"]
    if not now < was:
        _pin_failure(
            f"fedavg_resident copy_bytes {now} not reduced vs the pre-PR "
            f"baseline {was} (benchmarks/hlo_copy_baseline.json)",
            {"jax": cal["jax"], "jaxlib": cal["jaxlib"]})
    # streaming shares the round body and must hold the reduction too
    assert (audit["families"]["fedavg_streaming"]["copy_bytes"]
            < base["families"]["fedavg_streaming"]["copy_bytes"])


def test_audit_publishes_obs_gauge(audit):
    from fedml_tpu import obs
    for fam in hlo_copy_audit.ALL_FAMILIES:
        g = obs.gauge("engine_copy_bytes_compiled", family=fam)
        assert g.value == audit["families"][fam]["copy_bytes"], fam
