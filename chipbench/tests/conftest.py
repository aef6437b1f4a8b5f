"""CPU tests of the benchmark harness: ``python -m pytest chipbench/tests``.

Four virtual CPU devices stand in for a four-chip host; the flag has to be
set before jax starts, so it is set here, before any test imports jax.
"""
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=4"
).strip()

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
