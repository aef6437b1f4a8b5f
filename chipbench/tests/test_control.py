"""The check's control at a size a CPU holds: the plain reference solver in
the program's place passes at full float32 and fails one precision step
below (three bfloat16 passes), and one bfloat16 pass fails further."""
import pytest

from chipbench import control, harness

SIZES = {"hpcg_aug_dense_n8000": (8, 8, 8),
         "hpcg_matfree_n8000": (16, 8, 8)}


@pytest.mark.parametrize("name", sorted(SIZES))
def test_control_fails_and_the_reference_passes(name):
    spec = harness.load_spec()
    config = dict(harness.load_config(spec, name))
    config["nx"], config["ny"], config["nz"] = SIZES[name]
    n = config["nx"] * config["ny"] * config["nz"]
    config["n"], config["m"] = n, n * config["m"] // config["n"]
    out = {r["precision"]: r for r in control.readings(
        config, harness.load_traffic("batch8"), 7, 45.0, 64,
        ["highest", "high", "default"])}
    assert out["highest"]["passes"], out["highest"]["check"]
    assert not out["high"]["passes"], out["high"]["check"]
    assert not out["default"]["passes"], out["default"]["check"]
    assert out["default"]["check"]["residual"]["value"] > 100
