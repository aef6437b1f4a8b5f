"""A whole run of each cell, at a size a CPU holds, past the look for a
chip: sound, it comes out correct; with the timed path broken underneath,
``correct`` comes out false, once for each fault the cell can have."""
import dataclasses
import json
import time

import jax
import numpy as np
import pytest

from chipbench import harness

SIZES = {  # configuration -> (nx, ny, nz) at test size
    "hpcg_aug_dense_n8000": (8, 8, 4),
    "hpcg_matfree_n8000": (8, 8, 8),
    "hpcg_matfree_n16000_mesh4": (8, 8, 8),
}


def _tiny(cell):
    config = json.loads(
        (harness.HERE / "configs" / f"{cell['config']}.json").read_text())
    config["nx"], config["ny"], config["nz"] = SIZES[cell["config"]]
    n = config["nx"] * config["ny"] * config["nz"]
    config["n"], config["m"] = n, n * config["m"] // config["n"]
    config.pop("expect", None)  # the Gram-solver cut-off depends on size
    traffic = dict(harness.load_traffic(cell["traffic"]))
    if traffic["kind"] == "open_poisson":
        traffic["rate_per_s"] = 100.0
    return config, traffic


def _stale(solve):
    """The epochs leave the state where it started (one epoch runs)."""
    def run(B, num_epochs=100, **kw):
        return solve(B, num_epochs=1, **kw)
    return run


def _half(solve):
    """Half of the batch's systems are left out; their answers are the mean
    of the rest's (zero columns, the server's padding, are not systems)."""
    def run(B, **kw):
        real = np.flatnonzero(np.abs(B).sum(axis=0) > 0)
        keep, drop = real[: real.size // 2], real[real.size // 2:]
        res = solve(B, **kw)
        x = np.array(res.x)
        x[:, drop] = x[:, keep].mean(axis=1, keepdims=True) if keep.size \
            else 0.0
        return dataclasses.replace(res, x=x)
    return run


def _altered(solve):
    """One answer is altered where it is produced (1e-3 of its norm)."""
    def run(B, **kw):
        res = solve(B, **kw)
        x = np.array(res.x)
        x[0, 0] += 1e-3 * np.linalg.norm(x[:, 0])
        return dataclasses.replace(res, x=x)
    return run


FAULTS = {"stale": _stale, "half": _half, "altered": _altered}


def _patch_prepare(monkeypatch, wrap):
    import repro.core
    import repro.serving.queue

    prepare = repro.core.prepare

    def faulty(A, **kw):
        prep = prepare(A, **kw)
        prep.solve = wrap(prep.solve)
        return prep

    monkeypatch.setattr(repro.core, "prepare", faulty)
    monkeypatch.setattr(repro.serving.queue, "prepare", faulty)


CELLS = {  # cell -> (configuration, traffic); the mesh cell runs on four
    # virtual CPU devices
    "dense_batch": ("hpcg_aug_dense_n8000", "batch8"),
    "matfree_batch": ("hpcg_matfree_n8000", "batch8"),
    "dense_served": ("hpcg_aug_dense_n8000", "poisson_single"),
    "matfree_mesh4_batch": ("hpcg_matfree_n16000_mesh4", "batch8"),
}


def _run(cell_name, monkeypatch, fault):
    config_name, traffic_name = CELLS[cell_name]
    cell = {"name": cell_name, "config": config_name,
            "traffic": traffic_name}
    config, traffic = _tiny(cell)
    if fault in FAULTS:
        _patch_prepare(monkeypatch, FAULTS[fault])
    elif fault == "no_exchange":
        monkeypatch.setattr(jax.lax, "pmean", lambda x, *a, **k: x)
    run, answers, info = harness.run_cell(
        cell, config, traffic, 2**31 + 3, 1.5, False, time.perf_counter()
    )
    return harness.result_line(harness.load_spec(), run, answers, info,
                               False)


CASES = [
    (cell, fault)
    for cell in ("dense_batch", "matfree_batch", "dense_served",
                 "matfree_mesh4_batch")
    for fault in ("none", "stale", "half", "altered")
] + [("matfree_mesh4_batch", "no_exchange")]


@pytest.mark.parametrize("cell,fault", CASES)
def test_fault_makes_the_run_incorrect(cell, fault, monkeypatch):
    line = _run(cell, monkeypatch, fault)
    assert line["attempted"] > 0
    assert line["correct"] is (fault == "none"), line["check"]
    assert list(line)[-1] == "check"
