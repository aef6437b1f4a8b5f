"""``chip_smoke.py``'s phases at tiny sizes on the CPU (Pallas kernels in
interpret mode), so its control flow and checks are covered here; the
entry point itself must refuse any platform but a TPU."""
import importlib.util
import pathlib
import sys

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py"
    )
    module = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = module  # dataclasses resolve it by name
    spec.loader.exec_module(module)
    yield module
    del sys.modules["chip_smoke"]


@pytest.fixture(scope="module")
def cfg(smoke):
    return smoke.Config(
        dense_n=96, dense_m=192, matfree_n=256, k=2, blocks=4, epochs=300,
        requests=5, rate=2000.0, max_wait_ms=5.0,
    )


@pytest.fixture(scope="module")
def dense(smoke, cfg):
    return smoke.phase_dense(cfg)


@pytest.fixture(scope="module")
def matfree(smoke, cfg):
    return smoke.phase_matfree(cfg)


def test_dense_phase(dense):
    rec, failures, state = dense
    assert failures == []
    assert rec["converged"] == 2 and rec["relerr_x_true"] <= 1e-4
    assert rec["host_residual"] <= 1e-4 and rec["resident_bytes"] > 0
    assert state["x"].shape == (96, 2)


def test_matfree_phase(matfree):
    rec, failures, state = matfree
    assert failures == []
    assert rec["path"] == "matfree" and rec["relerr_x_true"] <= 1e-4
    assert rec["epochs_to_tol"] < rec["epoch_budget"]


def test_served_phase_reuses_the_pool_entry(smoke, cfg, matfree):
    rec, failures, _ = smoke.phase_served(cfg, matfree[2])
    assert failures == []
    assert rec["converged"] == cfg.requests
    assert rec["pool_prepares_total"] == 1 and rec["pool_paths"] == ["matfree"]


def test_kernels_phase_matches_xla(smoke, cfg, dense, matfree):
    rec, failures, _ = smoke.phase_kernels(cfg, dense[2], matfree[2])
    assert failures == []
    assert rec["dense_relerr_vs_xla"] <= 1e-4
    assert rec["matfree_relerr_vs_xla"] <= 1e-4


def test_four_chip_phase_on_one_device(smoke, cfg):
    rec, failures, _ = smoke.phase_four_chips(cfg, devices=1)
    assert failures == []
    assert rec["epoch_collectives"] == 1
    assert rec["epoch_payload_elems"] == cfg.matfree_n * cfg.k


def test_checks_catch_a_wrong_answer(smoke, cfg, monkeypatch):
    monkeypatch.setattr(smoke, "RELERR_GATE", -1.0)  # nothing can pass
    _, failures, _ = smoke.phase_dense(cfg)
    assert "relerr vs x_true" in failures


def test_entry_point_refuses_a_non_tpu_platform(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    assert smoke.main(["--four-chips"]) != 0
    assert capsys.readouterr().out == ""
