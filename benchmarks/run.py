"""Benchmark harness — one section per paper table/figure + roofline + serving.

Prints ``name,us_per_call,derived`` CSV and writes a machine-readable
``BENCH_<section>.json`` per executed section (uploaded by CI's bench-smoke
as a workflow artifact — the per-commit perf record). ``--quick`` shrinks
problem sizes. ``--only`` takes a comma-separated subset of sections.
``--repeat N`` re-runs each section N times and records the BEST-OF (per
row, min ``us_per_call`` matched by name; checks from the fastest run) —
single-shot numbers on shared CI runners are too noisy for the regression
gates that compare against committed baselines. A run that raises its gate
assertion is tolerated as noise if any sibling run passes. Exits nonzero
when a section (every repeat of it) raises, so the CI bench-smoke job fails
loudly on regressions instead of printing an ERROR row and passing.
"""
from __future__ import annotations

import argparse
import pathlib
import sys

# self-bootstrapping: `python benchmarks/run.py` works without PYTHONPATH
_ROOT = pathlib.Path(__file__).resolve().parent.parent
for _p in (str(_ROOT), str(_ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def _best_of(runs: list[tuple[list[dict], dict]]) -> tuple[list[dict], dict]:
    """Merge repeated section runs: per-row min us_per_call (matched by
    name, first run's row order), checks from the fastest run overall."""
    rows_best: dict[str, dict] = {}
    order: list[str] = []
    for rows, _ in runs:
        for row in rows:
            name = row["name"]
            if name not in rows_best:
                order.append(name)
                rows_best[name] = row
            elif row["us_per_call"] < rows_best[name]["us_per_call"]:
                rows_best[name] = row
    fastest = min(runs, key=lambda r: sum(row["us_per_call"] for row in r[0]))
    return [rows_best[name] for name in order], fastest[1]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument(
        "--only", default=None, metavar="SECTION[,SECTION...]",
        help="run only these sections (comma-separated)",
    )
    ap.add_argument(
        "--repeat", type=int, default=1, metavar="N",
        help="run each section N times, record best-of per row",
    )
    args = ap.parse_args()
    if args.repeat < 1:
        ap.error("--repeat must be >= 1")

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        chaos,
        convergence,
        heterogeneity,
        kernels,
        multirhs,
        record,
        roofline,
        serving_qos,
        serving_queue,
        sparse,
        sparse_sharded,
        speedup,
        streaming,
    )

    # every section returns rows, or (rows, checks) when it has gate metrics
    # (convergence's second element is raw per-epoch curves, not checks)
    sections = {
        "convergence": lambda: convergence.run(quick=args.quick)[0],
        "speedup": lambda: speedup.run(quick=args.quick),
        "kernels": lambda: kernels.run(quick=args.quick),
        "roofline": lambda: roofline.run(quick=args.quick),
        "multirhs": lambda: multirhs.run(quick=args.quick),
        "serving": lambda: serving_queue.run(quick=args.quick),
        "serving_qos": lambda: serving_qos.run(quick=args.quick),
        "sparse": lambda: sparse.run(quick=args.quick),
        "sparse_sharded": lambda: sparse_sharded.run(quick=args.quick),
        "streaming": lambda: streaming.run(quick=args.quick),
        "chaos": lambda: chaos.run(quick=args.quick),
        "heterogeneity": lambda: heterogeneity.run(quick=args.quick),
    }
    if args.only:
        names = [s.strip() for s in args.only.split(",") if s.strip()]
        unknown = [s for s in names if s not in sections]
        if unknown:
            ap.error(
                f"unknown section(s): {', '.join(unknown)} "
                f"(valid: {', '.join(sections)})"
            )
        sections = {name: sections[name] for name in names}

    failed = []
    print("name,us_per_call,derived")
    for name, fn in sections.items():
        runs: list[tuple[list[dict], dict]] = []
        error = None
        for _ in range(args.repeat):
            try:
                out = fn()
                rows, checks = out if isinstance(out, tuple) else (out, {})
                runs.append((rows, checks))
            except Exception as e:  # noisy gate trip: fine if a sibling passes
                error = e
        if runs:
            rows, checks = _best_of(runs)
            for row in rows:
                print(f"{row['name']},{row['us_per_call']:.1f},{row['derived']}")
            record.write_record(
                name, rows, checks, quick=args.quick, repeat=args.repeat,
            )
        else:  # report the failure, keep later sections running
            failed.append(name)
            print(f"{name}/ERROR,0.0,{type(error).__name__}: {error}")
            import traceback

            traceback.print_exception(error, file=sys.stderr)
    if failed:
        sys.exit(f"benchmark sections failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
