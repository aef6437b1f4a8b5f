"""The Gram-inverse control at a size a CPU holds: it matches the dense
control's reference, passes at full float32, and fails one precision step
below."""
import numpy as np
import pytest

from chipbench import control_gram, harness, reference
from chipbench import system as sysmod


def _mesh_config(grid=(8, 8, 16)):
    spec = harness.load_spec()
    cell = harness.find(spec["workloads"], "matfree_mesh4_batch", "workload")
    config = dict(harness.load_config(spec, cell["config"]))
    config["nx"], config["ny"], config["nz"] = grid
    config["n"] = config["m"] = int(np.prod(grid))
    return config


def test_same_answers_as_the_dense_reference():
    config = _mesh_config((4, 4, 8))
    system, X, B, tol = sysmod.inputs(config, 3, 8)
    args = (B, 2.0, 1.9, tol, 600, "highest")
    x_gram, conv_gram = control_gram.reference_gram.solve(
        control_gram.reference_gram.factor(system.core, 8), *args)
    x_dense, conv_dense = reference.solve(
        reference.factor(system.dense(np.float64), 8), *args)
    assert conv_gram.all() and conv_dense.all()
    # each answer stops within tol of b, ≈ 1e-5 from x_true at this size,
    # so the two agree to a few times that and not closer
    assert sysmod.relerr(x_gram, x_dense).max() < 3e-5


def test_control_fails_and_the_reference_passes():
    out = {r["precision"]: r for r in control_gram.readings(
        _mesh_config(), harness.load_traffic("batch8"), 7, 45.0, 64,
        ["highest", "high"])}
    assert out["highest"]["passes"], out["highest"]["check"]
    assert not out["high"]["passes"], out["high"]["check"]


def test_refuses_a_system_it_cannot_hold_square():
    with pytest.raises(ValueError):
        control_gram.reference_gram.factor(
            sysmod.hpcg_matrix(2, 2, 3)[:8], 4)
