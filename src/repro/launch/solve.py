"""Distributed-solver driver (the paper's workload as a launchable job).

Usage:
  PYTHONPATH=src python -m repro.launch.solve --n 1024 --m 4096 --blocks 8 \
      --method dapc --epochs 100
  ... --rhs 32   # serve a 32-RHS batch against one prepared factorization
  ... --mode matfree --mesh 4   # blocked-ELL shards over a 4-device mesh
"""
from __future__ import annotations

import argparse
import json


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1024)
    ap.add_argument("--m", type=int, default=4096)
    ap.add_argument("--blocks", type=int, default=8)
    ap.add_argument("--method", default="dapc",
                    choices=["apc", "dapc", "dgd", "cgnr"])
    ap.add_argument("--epochs", type=int, default=100)
    ap.add_argument("--gamma", type=float, default=1.0)
    ap.add_argument("--eta", type=float, default=0.9)
    ap.add_argument("--rhs", type=int, default=1,
                    help="number of right-hand sides solved as one batch "
                         "against the prepared factorization")
    ap.add_argument("--mode", default="auto",
                    choices=["auto", "dense", "matfree"],
                    help="execution path: dense blocks, matrix-free sparse "
                         "operator, or auto (nnz/memory estimate)")
    ap.add_argument("--mesh", type=int, default=0, metavar="D",
                    help="shard the matfree operator over a D-device mesh: "
                         "the first D chips on a TPU host, or D virtual "
                         "devices under JAX_PLATFORMS=cpu (requires --mode "
                         "matfree)")
    ap.add_argument("--implicit-p", action="store_true",
                    help="pin the implicit projector (default: the form "
                         "that reads fewer bytes an epoch)")
    ap.add_argument("--kernels", action="store_true",
                    help="route through the Pallas TPU kernels")
    args = ap.parse_args()

    if args.mesh:
        if args.mode != "matfree":
            ap.error("--mesh shards the matfree path; pass --mode matfree")
        if args.blocks % args.mesh:
            ap.error(f"--blocks {args.blocks} must divide over --mesh "
                     f"{args.mesh} devices")
        # must land before jax initializes its backends — hence the
        # deferred repro/jax imports below
        from repro.launch.mesh import reserve_mesh_devices

        reserve_mesh_devices(args.mesh)

    import numpy as np

    from repro.core import prepare
    from repro.sparse import make_problem

    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_block_mesh

        mesh = make_block_mesh(args.mesh)

    prob = make_problem(n=args.n, m=args.m, seed=0, dtype=np.float32)
    kw = {}
    if args.method == "dapc":
        kw = {
            "materialize_p": False if args.implicit_p else None,
            "use_kernels": args.kernels,
        }
    # square systems stay sparse end to end: hand prepare the COO so the
    # matfree path (picked or forced) never sees a dense copy
    A = prob.coo if prob.shape[0] == prob.shape[1] else prob.A
    prep = prepare(
        A, method=args.method, num_blocks=args.blocks, mode=args.mode,
        gamma=args.gamma, eta=args.eta, mesh=mesh, **kw,
    )
    if args.rhs > 1:
        rng = np.random.default_rng(1)
        xs = rng.standard_normal((args.n, args.rhs)).astype(np.float32)
        b, x_ref = prob.A @ xs, xs
    else:
        b, x_ref = prob.b, prob.x_true
    res = prep.solve(b, num_epochs=args.epochs, x_ref=x_ref)
    mse = np.asarray(res.final_mse)
    out = {
        "method": res.method, "mode": res.mode, "blocks": res.num_blocks,
        "epochs": res.num_epochs, "num_rhs": res.num_rhs,
        "path": prep.path,
        "setup_seconds": round(prep.setup_seconds, 3),
        "solve_seconds": round(res.wall_seconds, 3),
        "initial_mse": float(np.max(np.asarray(res.history["initial"]["mse"]))),
        "final_mse_max": float(mse.max()),
        "final_residual_sq_max": float(np.max(np.asarray(res.final_residual))),
    }
    if mesh is not None:
        out["mesh_devices"] = args.mesh
        out["per_device_mb"] = round(prep.per_device_memory_bytes / 1e6, 3)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
