"""Sharded matrix-free path vs single-host matfree on a four-device mesh.

ISSUE 5's tentpole: the blocked-ELL shards ride ``shard_map`` — one group
of partition blocks per device — so sparse systems larger than any single
device serve from the same mesh path as the dense solver. This benchmark
runs the paper-scale Schenk-like system through
``prepare(coo, mode="matfree", mesh=...)`` on a 4-device CPU mesh and
gates the three claims that make the configuration real (enforced in CI
bench-smoke):

  * parity — the mesh solver matches the single-host matfree solution
    (relerr gate mirrors benchmarks/sparse.py: two f32 trajectories that
    differ only in block-mean reduction order);
  * memory — per-device resident operator bytes ≈ 1/D of the single-host
    matfree operator (measured off the placed arrays' shards);
  * communication — the per-epoch collective payload stays within the
    n·k consensus ``pmean`` plus the k-length residual ``psum``, verified
    by walking the traced programs: every ``psum``-family primitive
    inside the epoch ``lax.scan`` is found and its payload summed, so a
    regression that sneaks an extra collective into the epoch fails
    loudly. Both programs are audited: the reporting-only solve (tol
    unset — residual partials ride the out_specs, ONE n·k collective per
    epoch) and the tol-armed serving solve (the early-exit gate needs the
    global residual in-scan: n·k + k);
  * wall-clock — within 1.2x of the single-host matfree solve at equal J
    (on a virtual CPU mesh the collectives are memcpys; the gate bounds
    the sharding overhead, it does not claim a CPU speedup).

On a TPU host the harness process already holds the chips, so ``run()``
measures in process on the first four. On the CPU platform the mesh needs
``--xla_force_host_platform_device_count`` set before jax initializes, so
``run()`` executes the measurement in a ``JAX_PLATFORMS=cpu`` subprocess
(the harness process keeps its single device) and parses one JSON line
back.

The batch width is k=32 — the coalesced-batch regime the sharded path
exists to serve (SolveServer dispatches (m, k) batches; the n·k consensus
collective is latency-bound on a virtual CPU mesh, so a single-RHS solve
measures the barrier, not the path). Wall times are best-of-5 per path
with the two paths' reps INTERLEAVED: 2-core CI runners swing 2x+ on
scheduling noise alone, and interleaving keeps load drift from landing
on one side of the ratio.

Standalone:  PYTHONPATH=src python benchmarks/sparse_sharded.py --quick
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import time

_SRC = str(pathlib.Path(__file__).resolve().parent.parent / "src")
if _SRC not in sys.path:  # standalone `python benchmarks/sparse_sharded.py`
    sys.path.insert(0, _SRC)

import numpy as np  # noqa: E402

DEVICES = 4
SPARSITY = 0.9985  # the Schenk_IBMNA c-* family's (matches sparse.py)
GAMMA, ETA = 2.0, 1.9
RELERR_GATE = {True: 1e-4, False: 2.5e-4}  # quick / paper scale (sparse.py)
WALL_GATE = 1.2
# per-device resident fraction: 1/D plus slack for the replicated-metadata
# crumbs (tile shape padding differences across shards)
DEVICE_FRACTION_GATE = 1.15 / DEVICES


# ---------------------------------------------------------------------------
# collective-payload audit (runs on the traced program, not on wall clock)
# — the walker lives in repro.obs.convergence so any deployment can assert
# the same per-epoch comms budget this benchmark gates on
# ---------------------------------------------------------------------------


def epoch_collective_payload(prep, bvecs, num_epochs, tol=None):
    """(elements per epoch, op count per epoch) of the sharded program's
    in-scan collectives — the communication an epoch actually pays.
    Thin wrapper over ``repro.obs.convergence.audit_epoch_collectives``."""
    from repro.obs.convergence import audit_epoch_collectives

    audit = audit_epoch_collectives(prep, None, num_epochs, tol=tol,
                                    bvecs=bvecs)
    return audit["payload_elems"], audit["ops"]


# ---------------------------------------------------------------------------
# the measurement (runs inside the 4-device subprocess)
# ---------------------------------------------------------------------------


def _steady_solve_pair(preps, B, epochs, reps=5):
    """Best-of-``reps`` steady-state wall per solver, reps INTERLEAVED:
    the wall gate is a ratio, and alternating the two paths inside the
    same measurement window keeps machine-load drift (CI neighbors, GC)
    from landing on one side of it."""
    results, bests = [], []
    for prep in preps:  # warm the compiled programs
        results.append(prep.solve(B, num_epochs=epochs, gamma=GAMMA, eta=ETA))
        bests.append(float("inf"))
    for _ in range(reps):
        for i, prep in enumerate(preps):
            t0 = time.perf_counter()
            results[i] = prep.solve(B, num_epochs=epochs, gamma=GAMMA, eta=ETA)
            bests[i] = min(bests[i], time.perf_counter() - t0)
    return results, bests


def run_inprocess(quick: bool, num_rhs: int):
    import jax

    assert jax.device_count() >= DEVICES, (
        f"need {DEVICES} devices, got {jax.device_count()} — a four-chip "
        "host, or on the CPU run() (which starts a virtual-device "
        "subprocess); standalone CPU use must export XLA_FLAGS"
    )
    from repro.core import prepare
    from repro.sparse import generate_schenk_like

    n, epochs = (768, 150) if quick else (2327, 300)
    num_blocks = 8
    mesh = jax.make_mesh((DEVICES,), ("data",))
    coo = generate_schenk_like(n, sparsity=SPARSITY, seed=5)
    A = coo.to_dense().astype(np.float32)
    rng = np.random.default_rng(11)
    xs = rng.standard_normal((n, num_rhs)).astype(np.float32)
    B = A @ xs

    t0 = time.perf_counter()
    single = prepare(coo, mode="matfree", num_blocks=num_blocks)
    t_single_setup = time.perf_counter() - t0

    t0 = time.perf_counter()
    sharded = prepare(coo, mode="matfree", num_blocks=num_blocks, mesh=mesh)
    t_sharded_setup = time.perf_counter() - t0

    (single_res, sharded_res), (t_single, t_sharded) = _steady_solve_pair(
        (single, sharded), B, epochs
    )

    scale = np.abs(single_res.x).max() + 1e-30
    relerr = float(np.abs(sharded_res.x - single_res.x).max() / scale)
    wall_ratio = t_sharded / t_single
    per_device = sharded.per_device_memory_bytes
    device_fraction = per_device / single.memory_bytes
    bvecs = sharded.op.block_rhs(B)
    # audit BOTH programs: reporting-only (tol unset: residual partials
    # ride the out_specs — one n·k pmean per epoch) and tol-armed (the
    # serving shape: + the k-length residual psum gating the early exit)
    payload, n_collectives = epoch_collective_payload(sharded, bvecs, epochs)
    payload_tol, n_collectives_tol = epoch_collective_payload(
        sharded, bvecs, epochs, tol=1e-3
    )
    budget = n * num_rhs + num_rhs  # the n·k consensus pmean + residual psum

    rows = [
        {
            "name": f"sparse_sharded/matfree_single_{n}x{n}_J{num_blocks}",
            "us_per_call": t_single / num_rhs * 1e6,
            "derived": (
                f"setup={t_single_setup:.3f}s solve={t_single:.3f}s "
                f"resident={single.memory_bytes / 1e6:.2f}MB"
            ),
        },
        {
            "name": (
                f"sparse_sharded/matfree_sharded_{n}x{n}"
                f"_J{num_blocks}_D{DEVICES}"
            ),
            "us_per_call": t_sharded / num_rhs * 1e6,
            "gated": True,
            "derived": (
                f"setup={t_sharded_setup:.3f}s solve={t_sharded:.3f}s "
                f"per_device={per_device / 1e6:.2f}MB "
                f"device_fraction={device_fraction:.3f} "
                f"wall_ratio_vs_single={wall_ratio:.2f}x "
                f"relerr_vs_single={relerr:.1e} "
                f"epoch_collectives={n_collectives} "
                f"epoch_payload_elems={payload} "
                f"tol_payload_elems={payload_tol} (budget {budget})"
            ),
        },
    ]
    checks = {
        "devices": DEVICES,
        "relerr_vs_single": relerr,
        "wall_ratio_vs_single": float(wall_ratio),
        "per_device_bytes": int(per_device),
        "device_fraction": float(device_fraction),
        "epoch_payload_elems": int(payload),
        "epoch_payload_elems_tol": int(payload_tol),
        "epoch_payload_budget": int(budget),
        "epoch_collectives": int(n_collectives),
        "epoch_collectives_tol": int(n_collectives_tol),
    }
    # acceptance gates — raise so run.py (and CI) exits nonzero
    assert relerr <= RELERR_GATE[quick], (
        f"sharded/single relative error {relerr:.1e} > "
        f"{RELERR_GATE[quick]:.1e} gate"
    )
    # the no-tol program's invariant is EXACTLY one collective (the n·k
    # consensus pmean — residual partials ride the out_specs); the
    # tol-armed program may add only the k-length residual psum
    assert payload <= n * num_rhs and n_collectives <= 1, (
        f"no-tol epoch pays {n_collectives} collectives / {payload} elems "
        f"> the single n·k consensus pmean ({n * num_rhs}) — the "
        "partial-residual out_specs path regressed"
    )
    assert payload_tol <= budget and n_collectives_tol <= 2, (
        f"tol-armed epoch pays {n_collectives_tol} collectives / "
        f"{payload_tol} elems > n·k + residual budget {budget} — a "
        "collective snuck into the epoch"
    )
    assert device_fraction <= DEVICE_FRACTION_GATE, (
        f"per-device resident fraction {device_fraction:.3f} > "
        f"{DEVICE_FRACTION_GATE:.3f} gate (~1/{DEVICES} of the single-host "
        "operator)"
    )
    assert wall_ratio <= WALL_GATE, (
        f"sharded wall-clock {wall_ratio:.2f}x single-host matfree > "
        f"{WALL_GATE}x gate"
    )
    return rows, checks


# ---------------------------------------------------------------------------
# harness entry: subprocess wrapper (multi-device XLA_FLAGS isolation)
# ---------------------------------------------------------------------------


def run(quick: bool = False, num_rhs: int = 32):
    import jax

    if jax.default_backend() != "cpu":  # this process holds the devices
        return run_inprocess(quick=quick, num_rhs=num_rhs)
    from repro.launch.mesh import force_host_device_count

    env = force_host_device_count(DEVICES, dict(os.environ))
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [sys.executable, str(pathlib.Path(__file__).resolve()), "--json",
           "--rhs", str(num_rhs)] + (["--quick"] if quick else [])
    out = subprocess.run(
        cmd, capture_output=True, text=True, env=env, timeout=1800,
    )
    payload = None
    for line in reversed(out.stdout.splitlines()):
        if line.startswith("{"):
            payload = json.loads(line)
            break
    if out.returncode != 0 or payload is None:
        tail = "\n".join((out.stderr or out.stdout).splitlines()[-15:])
        raise AssertionError(
            f"sparse_sharded subprocess failed (rc={out.returncode}):\n{tail}"
        )
    return payload["rows"], payload["checks"]


def main():
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--rhs", type=int, default=32)
    ap.add_argument("--json", action="store_true",
                    help="measure in THIS process (needs the multi-device "
                         "XLA_FLAGS) and emit one JSON line")
    args = ap.parse_args()

    if args.json:
        rows, checks = run_inprocess(quick=args.quick, num_rhs=args.rhs)
        print(json.dumps({"rows": rows, "checks": checks}))
        return

    try:
        rows, checks = run(quick=args.quick, num_rhs=args.rhs)
    except AssertionError as e:
        raise SystemExit(f"acceptance: FAIL — {e}")
    print("name,us_per_call,derived")
    for row in rows:
        print(f"{row['name']},{row['us_per_call']:.1f},{row['derived']}")
    print(
        f"acceptance: relerr={checks['relerr_vs_single']:.1e} "
        f"(need <={RELERR_GATE[args.quick]:.1e}), "
        f"wall_ratio={checks['wall_ratio_vs_single']:.2f}x "
        f"(need <={WALL_GATE}x), "
        f"device_fraction={checks['device_fraction']:.3f} "
        f"(need <={DEVICE_FRACTION_GATE:.3f}), "
        f"epoch_payload={checks['epoch_payload_elems']} elems "
        f"(budget {checks['epoch_payload_budget']}) -> PASS"
    )


if __name__ == "__main__":
    main()
