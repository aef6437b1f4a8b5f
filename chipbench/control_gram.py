"""Readings of the check's control for a square configuration too large to
densify: ``chipbench.reference_gram`` in the program's place, at the
cell's own sizes, as ``chipbench/control.py`` gives them for the others.

    python3 chipbench/control_gram.py --workload matfree_mesh4_batch \\
        --seeds 1 --columns 160 --precisions highest high

One JSON line per (seed, precision) with the check's numbers
(``harness.check``) and whether they pass the configuration's limits.
``high`` is the control and must not pass; ``highest`` shows that the
reference itself does. Needs a TPU.
"""
from __future__ import annotations

import json
import pathlib
import sys
import time

sys.path[0] = str(pathlib.Path(__file__).resolve().parents[1])

import numpy as np  # noqa: E402

from chipbench import harness, reference_gram  # noqa: E402
from chipbench import system as sysmod  # noqa: E402
from chipbench import traffic as trafficmod  # noqa: E402


def readings(config: dict, traffic: dict, seed: int, seconds: float,
             columns: int, precisions) -> list:
    mix = trafficmod.make(traffic, seed, seconds)
    system, X, B, tol = sysmod.inputs(config, seed, mix.columns)
    if system.mixing is not None:
        raise ValueError("reference_gram solves square systems only")
    cols = np.arange(mix.columns)[-columns:]
    t0 = time.perf_counter()
    factors = reference_gram.factor(
        system.core, config["prepare"]["num_blocks"]
    )
    factor_s = time.perf_counter() - t0
    out = []
    for precision in precisions:
        t0 = time.perf_counter()
        x, conv = reference_gram.solve(
            factors, B[:, cols], config["prepare"]["gamma"],
            config["prepare"]["eta"], tol,
            int(config["solve"]["num_epochs"]), precision,
        )
        checks, extra = harness.check(config, system, x, X[:, cols], conv, tol)
        out.append({**extra,
            "seed": seed, "precision": precision, "columns": int(cols.size),
            "converged": int(conv.sum()), "factor_s": factor_s,
            "solve_s": time.perf_counter() - t0,
            "passes": all(c["value"] <= c["limit"] for c in checks.values()),
            "check": checks,
        })
    return out


def main(argv) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Readings of the control.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--columns", type=int, default=160)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--precisions", nargs="+", default=["high", "default"])
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("control_gram: needs a TPU", file=sys.stderr)
        return 2
    spec = harness.load_spec()
    cell = harness.find(spec["workloads"], args.workload, "workload")
    config = harness.load_config(spec, cell["config"])
    traffic = harness.load_traffic(cell["traffic"])
    for seed in args.seeds:
        for rec in readings(config, traffic, seed, args.seconds,
                            args.columns, args.precisions):
            print(json.dumps(dict(rec, workload=args.workload)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
