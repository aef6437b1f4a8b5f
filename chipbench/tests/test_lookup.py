"""Configurations, traffic mixes and metrics are found by name, and a new
one is added by adding files and entries, with no edit to a file there."""
import hashlib
import json
import pathlib
import shutil

import pytest

from chipbench import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]


def test_every_cell_resolves():
    spec = harness.load_spec(ROOT)
    for cell in spec["workloads"]:
        config = harness.load_config(spec, cell["config"], ROOT)
        assert config["name"] == cell["config"]
        assert config["chips"] == cell["chips"]
        entry = harness.find(spec["configs"], cell["config"], "configuration")
        assert set(entry["reduced"]) <= set(config["reduced"])
        harness.load_traffic(cell["traffic"])
        assert harness.cell_metrics(spec, cell["name"], False)
        assert harness.cell_metrics(spec, cell["name"], True)
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(harness.load_metric(m["name"]))


def test_unknown_names_are_errors():
    spec = harness.load_spec(ROOT)
    with pytest.raises(KeyError):
        harness.find(spec["workloads"], "no_such_cell", "workload")
    with pytest.raises(FileNotFoundError):
        harness.load_traffic("no_such_mix")
    with pytest.raises((FileNotFoundError, KeyError)):
        harness.load_metric("no_such_metric")


def _digest(root: pathlib.Path) -> dict:
    return {
        str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
        for p in sorted(root.rglob("*"))
        if p.is_file() and "__pycache__" not in p.parts
    }


def test_adding_a_cell_touches_no_existing_file(tmp_path):
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = harness.load_spec(ROOT)
    before = _digest(tmp_path / "chipbench")
    here = tmp_path / "chipbench"

    config = harness.load_config(spec, "hpcg_matfree_n8000", ROOT)
    config = dict(config, name="hpcg_matfree_n4096", nx=16, ny=16, nz=16,
                  n=4096, m=4096)
    (here / "configs" / "hpcg_matfree_n4096.json").write_text(
        json.dumps(config))
    (here / "traffic" / "batch32.json").write_text(json.dumps(
        {"kind": "closed_batch", "k": 32, "pool_batches": 4}))
    (here / "metrics" / "columns_per_batch.py").write_text(
        "def read(run):\n"
        "    return run.sizes['k'] if run.batches else None\n")
    spec["configs"].append({
        "name": "hpcg_matfree_n4096", "source": "https://example.org",
        "file": "chipbench/configs/hpcg_matfree_n4096.json",
        "reduced": ["nx", "ny", "nz", "n", "m"], "why": "a smaller grid"})
    spec["workloads"].append({
        "name": "matfree_batch32", "config": "hpcg_matfree_n4096",
        "traffic": "batch32", "chips": 1, "why": "wider batches"})
    spec["per_layer"].append({
        "name": "columns_per_batch", "unit": "columns", "better": "higher",
        "source": "program_counter", "layer": "consensus epoch",
        "moves": "solves_per_s", "workloads": ["matfree_batch32"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    spec2 = harness.load_spec(tmp_path)
    cell = harness.find(spec2["workloads"], "matfree_batch32", "workload")
    assert harness.load_config(spec2, cell["config"], tmp_path)["n"] == 4096
    assert harness.load_traffic(cell["traffic"], here)["k"] == 32
    names = [m["name"] for m in
             harness.cell_metrics(spec2, "matfree_batch32", True)]
    assert "columns_per_batch" in names
    run = harness.Run(cell=cell, config={}, traffic={"kind": "closed_batch"},
                      device={}, peak={}, sizes={"k": 32},
                      batches=[object()])
    line = harness.read_metrics(spec2, run, True, here)
    assert line["columns_per_batch"] == {"value": 32.0, "unit": "columns"}

    after = _digest(here)
    assert {k: v for k, v in after.items() if k in before} == before
