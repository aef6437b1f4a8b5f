"""The benchmark refuses any platform but a TPU, and a checkout without the
program: it exits non-zero and prints no result."""
import os
import pathlib
import shutil
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
ARGS = ["--workload", "dense_batch", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chipbench/run.py", *ARGS], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120,
    )


def test_refuses_the_cpu():
    out = _run(ROOT)
    assert out.returncode == 2
    assert out.stdout == ""
    assert "needs a TPU" in out.stderr


def test_refuses_a_directory_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path)
    assert out.returncode != 0
    assert out.stdout == ""
