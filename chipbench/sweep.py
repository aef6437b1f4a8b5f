"""The one sweep that fixes an open-loop cell's offered rate.

    python3 chipbench/sweep.py --workload dense_served --seed 5 \\
        --seconds 45 --rates 2.5 3.0 3.5 4.0

Sets the cell up once (its configuration and server settings), then offers
each rate in turn for ``--seconds`` and prints one JSON line per rate: the
requests, latency median and 90th percentile, how late the generator ran,
and whether latency trends over the window: the last third's mean latency
over the first third's by more than a tenth of one batch's solve time
(``TREND_SHARE``). A rate is sustained when latency does not trend and
every request converged. Needs a TPU.
"""
from __future__ import annotations

import asyncio
import json
import pathlib
import sys
import time

sys.path[0] = str(pathlib.Path(__file__).resolve().parents[1])

import numpy as np  # noqa: E402

from chipbench import harness, stats  # noqa: E402
from chipbench import system as sysmod  # noqa: E402
from chipbench import traffic as trafficmod  # noqa: E402

# latency may rise over a window by this share of one batch's solve time
# before the backlog counts as growing
TREND_SHARE = 0.1


def main(argv) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Sweep an open-loop cell's rate.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    sys.path.insert(0, str(harness.SRC))
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving.queue import PreparedPool, SolveServer

    enable_compile_cache()

    spec = harness.load_spec()
    cell = harness.find(spec["workloads"], args.workload, "workload")
    config = harness.load_config(spec, cell["config"])
    traffic = harness.load_traffic(cell["traffic"])
    mixes = [trafficmod.make(dict(traffic, rate_per_s=r), args.seed,
                             args.seconds) for r in args.rates]
    system, _, B, tol = sysmod.inputs(
        config, args.seed, 1 + sum(m.arrivals.size for m in mixes)
    )
    B32 = B.astype(np.float32)
    pool = PreparedPool(**harness._prepare_kwargs(config))
    fp = pool.register(harness._matrix(config, system))
    pool.get(fp)

    async def sweep():
        async with SolveServer(
            pool=pool, num_epochs=int(config["solve"]["num_epochs"]),
            tol=tol, **traffic["server"],
        ) as server:
            await server.submit(fp, B32[:, 0])  # compiles or loads
            t0 = time.perf_counter()
            await server.submit(fp, B32[:, 0])
            batch_s = time.perf_counter() - t0
            first = 1
            for rate, mix in zip(args.rates, mixes):
                server.reset_stats()
                sel = np.arange(first, first + mix.arrivals.size)
                first += sel.size
                reqs = await harness.open_loop(
                    server, fp, B32[:, sel], mix.arrivals, time.perf_counter()
                )
                lat = np.array([r.latency_s for r in reqs])
                third = max(1, lat.size // 3)
                grew = (lat[-third:].mean() - lat[:third].mean()
                        > TREND_SHARE * batch_s)
                conv = all(not r.failed for r in reqs)
                print(json.dumps({
                    "rate_per_s": rate, "requests": len(reqs),
                    "p50_ms": 1e3 * stats.percentile(lat, 50),
                    "p90_ms": 1e3 * stats.percentile(lat, 90),
                    "late_max_ms": 1e3 * max(r.submitted - r.due
                                             for r in reqs),
                    "first_third_ms": 1e3 * lat[:third].mean(),
                    "last_third_ms": 1e3 * lat[-third:].mean(),
                    "backlog_grew": bool(grew), "all_converged": conv,
                    "sustained": bool(conv and not grew),
                    "mean_batch": server.stats()["mean_batch_size"],
                    "batch_s": batch_s,
                }), flush=True)

    asyncio.run(sweep())
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
