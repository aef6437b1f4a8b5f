"""Run the solver's main path once on a TPU chip and check what comes out.

    python chip_smoke.py               # one chip: dense, matfree, served, kernels
    python chip_smoke.py --four-chips  # four chips: sharded matfree vs one chip

Every system is consistent by construction (b = A·x_true, with x_true drawn
from ``--seed``), so x_true is the reference: each phase reports its
relative error against it, and the float64 residual ‖Ax−b‖/‖b‖ of the
sparse core computed on the host from the COO. Each phase prints one JSON
line (sizes, resident bytes, prepare and solve seconds, epochs to
tolerance, errors); the last line is ``{"ok": true, "device": ...}`` only
when every phase passed. The script refuses to run anywhere but a TPU.

Phases (one chip):

1. dense — ``prepare(A, method="dapc")`` on an eq. (8)-augmented
   Schenk-like system (m = 16384, n = 8192, J = 8; materialized
   projectors, ~3 GiB resident) solving a k = 8 batch to tolerance;
2. matfree — ``prepare(coo, mode="matfree")`` on a Schenk-like n = 16384
   system through the serving pool (balanced blocked-ELL, PCG Gram
   solver), k = 8 batch to tolerance. (At n = 32768 one v5e took 126 s
   for this solve — 0.6 s per epoch on the XLA gather path — so the
   served phase's 64 requests alone would outlast the run's budget);
3. served — a ``SolveServer`` over that same pool entry (no second
   prepare) replays 64 open-loop Poisson requests; every request must
   converge and match its x_true with no failure, retry or fallback;
4. kernels — the Pallas kernels on the chip next to their XLA twins: the
   dense system with ``use_kernels=True, materialize_p=False`` (project +
   trisolve) to tolerance, and the matfree system with ``use_kernels=True``
   (blocked-ELL SpMM) for a short fixed budget (see ``phase_kernels``);
   the lowered programs must contain each kernel.

``--four-chips`` runs only the sharded phase: ``prepare(coo,
mode="matfree", mesh=<4-chip mesh>)`` against the one-chip matfree solve of
the same system (parity, one n·k collective per epoch, per-device bytes,
peak bytes on every chip).

The generated matrices take ``cond_boost=8`` (a stronger diagonal ridge):
at the default ridge a Schenk-like core of n = 4096 already has a
condition number of 5·10⁴, and no f32 solve can come within 1e-4 of
x_true.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import pathlib
import re
import sys
import time
import traceback

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
SRC = ROOT / "src"

GAMMA, ETA = 2.0, 1.9  # the consensus pair of the repo's sparse benchmarks
COND_BOOST = 8.0  # diagonal ridge scale: condition number ~10 (see docstring)
TOL_REL = 1e-5  # residual tolerance, relative to ‖b‖: ≤ κ·1e-5 error
RELERR_GATE = 1e-4  # against x_true, and between a kernel and its XLA twin
KERNEL_EPOCHS, KERNEL_INNER = 2, 4  # fixed matfree kernel budget (see phase)
FOUR = 4


@dataclasses.dataclass(frozen=True)
class Config:
    """Sizes of every phase (the defaults are the chip run's)."""

    dense_n: int = 8192
    dense_m: int = 16384
    matfree_n: int = 16384
    k: int = 8
    blocks: int = 8
    epochs: int = 600  # epoch budget of the tolerance solves
    requests: int = 64
    rate: float = 50.0  # Poisson arrivals per second
    max_wait_ms: float = 20.0
    seed: int = 0


def _check(failures: list, ok: bool, what: str) -> None:
    if not ok:
        failures.append(what)


def _x_true(n: int, k: int, seed: int) -> np.ndarray:
    return np.random.default_rng(seed + 101).standard_normal((n, k))


def _coo_apply(coo, X: np.ndarray) -> np.ndarray:
    """Float64 host product A @ X from the COO triplets."""
    X = np.asarray(X, np.float64)
    return np.stack([coo.matvec(X[:, i]) for i in range(X.shape[1])], axis=1)


def _relerr(x: np.ndarray, ref: np.ndarray) -> float:
    """Worst column's ‖x − ref‖ / ‖ref‖."""
    x = np.asarray(x, np.float64).reshape(ref.shape)
    return float(np.max(
        np.linalg.norm(x - ref, axis=0) / np.linalg.norm(ref, axis=0)
    ))


def _host_residual(coo, x: np.ndarray, b: np.ndarray) -> float:
    """Worst column's float64 ‖A x − b‖ / ‖b‖ on the sparse core."""
    return _relerr(_coo_apply(coo, x), b)


def _tol(B: np.ndarray) -> float:
    """Absolute per-column residual tolerance the solvers take."""
    return TOL_REL * float(np.min(np.linalg.norm(B, axis=0)))


def _peak_bytes(device):
    stats = device.memory_stats()
    return None if not stats else int(stats.get("peak_bytes_in_use", 0))


def _timed_solves(prep, B, warm: bool = True, **kw):
    """(first call s incl. compile, warm call s or None, last result);
    ``solve`` blocks on its result before returning."""
    t0 = time.perf_counter()
    res = prep.solve(B, **kw)
    first = time.perf_counter() - t0
    if not warm:
        return first, None, res
    t0 = time.perf_counter()
    res = prep.solve(B, **kw)
    return first, time.perf_counter() - t0, res


def _kernel_names(text: str) -> set:
    return set(re.findall(r'kernel_name = "([^"]+)"', text))


def _dense_program_text(prep, B, num_epochs: int, tol: float) -> str:
    """Lowered text of the program ``prep.solve(B, num_epochs, tol=tol)``
    runs on the dense path."""
    import jax.numpy as jnp

    from repro.core.partition import block_rhs

    run = prep._consensus_program(num_epochs, {"tol": tol})
    bvecs = block_rhs(prep.mixer, B, np.dtype(prep.blocks.dtype))
    return run.lower(
        prep.blocks, prep.factors, prep.projector[1], bvecs,
        jnp.asarray(GAMMA), jnp.asarray(ETA), None, None, None,
    ).as_text()


def _matfree_program_text(prep, B, num_epochs: int, inner: int) -> str:
    """Lowered text of ``prep.solve(B, num_epochs, inner_iters=inner)``."""
    dtype = prep.op.fwd_data.dtype
    run = prep._solve_program(num_epochs, inner, False, None)
    gamma, eta = prep._dynamics_operands(GAMMA, ETA, dtype, False)
    return run.lower(
        prep.op, prep.diag_inv, prep.gram_inv, prep.block_rhs(B),
        gamma, eta, None, None,
    ).as_text()


# ---------------------------------------------------------------------------
# phases: each returns (record, state for the phases after it)
# ---------------------------------------------------------------------------


def phase_dense(cfg: Config):
    import jax

    from repro.core import prepare
    from repro.sparse import make_problem

    prob = make_problem(
        n=cfg.dense_n, m=cfg.dense_m, seed=cfg.seed, cond_boost=COND_BOOST
    )
    X = _x_true(cfg.dense_n, cfg.k, cfg.seed)
    B = prob.A @ X
    A32, B32 = prob.A.astype(np.float32), B.astype(np.float32)
    tol = _tol(B)
    t0 = time.perf_counter()
    prep = prepare(
        A32, method="dapc", num_blocks=cfg.blocks, gamma=GAMMA, eta=ETA
    )
    jax.block_until_ready((prep.factors, prep.projector[1]))
    prepare_s = time.perf_counter() - t0
    first_s, solve_s, res = _timed_solves(
        prep, B32, num_epochs=cfg.epochs, tol=tol
    )
    cols = res.per_column(tol)
    rec = {
        "phase": "dense", "n": cfg.dense_n, "m": cfg.dense_m, "k": cfg.k,
        "blocks": cfg.blocks, "block_mode": prep.mode, "path": prep.path,
        "resident_bytes": prep.memory_bytes,
        "peak_bytes_in_use": _peak_bytes(jax.devices()[0]),
        "prepare_s": prepare_s, "first_solve_s": first_s, "solve_s": solve_s,
        "epochs_to_tol": max(c.iterations for c in cols),
        "epoch_budget": cfg.epochs, "tol": tol,
        "converged": sum(c.converged for c in cols),
        "relerr_x_true": _relerr(res.x, X),
        "host_residual": _host_residual(
            prob.coo, res.x, _coo_apply(prob.coo, X)
        ),
    }
    failures: list = []
    _check(failures, rec["converged"] == cfg.k, "not every column converged")
    _check(failures, rec["relerr_x_true"] <= RELERR_GATE, "relerr vs x_true")
    state = {"A32": A32, "B32": B32, "X": X, "tol": tol, "x": res.x}
    return rec, failures, state


def phase_matfree(cfg: Config):
    import jax

    from repro.obs.metrics import MetricsRegistry
    from repro.serving.queue import PreparedPool
    from repro.sparse import generate_schenk_like

    coo = generate_schenk_like(
        cfg.matfree_n, seed=cfg.seed, cond_boost=COND_BOOST
    )
    X = _x_true(cfg.matfree_n, cfg.k, cfg.seed)
    B = _coo_apply(coo, X)
    B32 = B.astype(np.float32)
    tol = _tol(B)
    registry = MetricsRegistry()
    pool = PreparedPool(
        metrics=registry, mode="matfree", num_blocks=cfg.blocks,
        gamma=GAMMA, eta=ETA,
    )
    fp = pool.register(coo)
    t0 = time.perf_counter()
    prep = pool.get(fp)  # the one prepare of this system
    prepare_s = time.perf_counter() - t0
    first_s, solve_s, res = _timed_solves(
        prep, B32, num_epochs=cfg.epochs, tol=tol
    )
    cols = res.per_column(tol)
    slots, mean_slots = prep.op.slot_occupancy()
    rec = {
        "phase": "matfree", "n": cfg.matfree_n, "nnz": coo.nnz, "k": cfg.k,
        "blocks": cfg.blocks, "path": prep.path,
        "gram_solver": prep.gram_solver,
        "ell_block_rows": int(prep.op.fwd_indices.shape[1]),
        "ell_slots": slots, "ell_mean_slots": mean_slots,
        "resident_bytes": prep.memory_bytes,
        "peak_bytes_in_use": _peak_bytes(jax.devices()[0]),
        "prepare_s": prepare_s, "first_solve_s": first_s, "solve_s": solve_s,
        "epochs_to_tol": max(c.iterations for c in cols),
        "epoch_budget": cfg.epochs, "tol": tol,
        "inner_iters_max": int(np.max(res.history["inner_iters"])),
        "converged": sum(c.converged for c in cols),
        "relerr_x_true": _relerr(res.x, X),
        "host_residual": _host_residual(coo, res.x, B),
    }
    failures: list = []
    _check(failures, prep.path == "matfree", "path is not matfree")
    _check(failures, rec["converged"] == cfg.k, "not every column converged")
    _check(failures, rec["relerr_x_true"] <= RELERR_GATE, "relerr vs x_true")
    state = {
        "coo": coo, "pool": pool, "registry": registry, "prep": prep,
        "B32": B32, "X": X, "tol": tol, "x": res.x,
    }
    return rec, failures, state


def phase_served(cfg: Config, mf: dict):
    from repro.serving.queue import SolveServer, replay_trace

    coo, pool, registry, tol = mf["coo"], mf["pool"], mf["registry"], mf["tol"]
    rng = np.random.default_rng(cfg.seed + 202)
    X = rng.standard_normal((cfg.matfree_n, cfg.requests))
    R32 = _coo_apply(coo, X).astype(np.float32)
    gaps = rng.exponential(1.0 / cfg.rate, size=cfg.requests)
    gaps[0] = 0.0

    async def serve():
        async with SolveServer(
            pool=pool, metrics=registry, max_batch=cfg.k,
            max_wait_ms=cfg.max_wait_ms, num_epochs=cfg.epochs, tol=tol,
        ) as server:
            fp = server.register(coo)
            t0 = time.perf_counter()
            results = await replay_trace(server, fp, R32, gaps)
            return results, time.perf_counter() - t0, server.stats()

    results, wall, stats = asyncio.run(serve())
    lat_ms = np.array([r.queue_ms + r.solve_ms for r in results])
    errs = [_relerr(r.x, X[:, i:i + 1]) for i, r in enumerate(results)]
    resident = pool.resident()
    rec = {
        "phase": "served", "n": cfg.matfree_n, "requests": cfg.requests,
        "rate_per_s": cfg.rate, "max_batch": cfg.k, "wall_s": wall,
        "served_per_s": cfg.requests / wall,
        "latency_ms_p50": float(np.percentile(lat_ms, 50)),
        "latency_ms_p99": float(np.percentile(lat_ms, 99)),
        "batches": stats["batches"], "mean_batch": stats["mean_batch_size"],
        "converged": sum(r.converged for r in results),
        "relerr_x_true_max": max(errs),
        "server_failures_total": registry.total("server_failures_total"),
        "server_retries_total": registry.total("server_retries_total"),
        "pool_fallbacks_total": registry.value("pool_fallbacks_total"),
        "pool_prepares_total": registry.value("pool_prepares_total"),
        "pool_paths": [e["path"] for e in resident],
    }
    failures: list = []
    _check(failures, rec["converged"] == cfg.requests, "unconverged requests")
    _check(failures, rec["relerr_x_true_max"] <= RELERR_GATE,
           "relerr vs x_true")
    for key in ("server_failures_total", "server_retries_total",
                "pool_fallbacks_total"):
        _check(failures, rec[key] == 0, f"{key} != 0")
    _check(failures, rec["pool_prepares_total"] == 1, "system prepared twice")
    _check(failures, rec["pool_paths"] == ["matfree"], "pool path not matfree")
    return rec, failures, None


def phase_kernels(cfg: Config, dense: dict, mf: dict):
    """The Pallas kernels next to their XLA twins.

    Dense: phase 1's system with ``use_kernels=True, materialize_p=False``
    to the same tolerance, against phase 1's solution. Matfree: phase 2's
    system prepared with ``use_kernels=True`` (``balance=False``: the
    balance search is host time that changes no product). The SpMM grid
    makes one step per 8×8 tile, too slow for a full solve here, so the
    kernel solver and phase 2's XLA solver run the same short fixed budget
    (``KERNEL_EPOCHS`` epochs, ``KERNEL_INNER`` inner CG steps) and are
    compared iterate to iterate. The kernels' operands keep the 8×8 tiles
    as their two minor dims, which the chip's HBM tiling pads 16×: the
    kernel program takes ~11 GiB at n = 16384 and asks for 31 GB at
    n = 32768."""
    import jax

    from repro.core import prepare

    on_tpu = jax.default_backend() == "tpu"
    failures: list = []
    t0 = time.perf_counter()
    dk = prepare(
        dense["A32"], method="dapc", num_blocks=cfg.blocks, gamma=GAMMA,
        eta=ETA, use_kernels=True, materialize_p=False,
    )
    jax.block_until_ready(dk.factors)
    dense_prepare_s = time.perf_counter() - t0
    d_first, d_solve, dres = _timed_solves(
        dk, dense["B32"], num_epochs=cfg.epochs, tol=dense["tol"]
    )
    dense_names = _kernel_names(
        _dense_program_text(dk, dense["B32"], cfg.epochs, dense["tol"])
    )
    del dk  # its HBM goes to the matfree kernel program

    t0 = time.perf_counter()
    mk = prepare(
        mf["coo"], mode="matfree", num_blocks=cfg.blocks, gamma=GAMMA,
        eta=ETA, use_kernels=True, balance=False,
    )
    matfree_prepare_s = time.perf_counter() - t0
    budget = {"num_epochs": KERNEL_EPOCHS, "inner_iters": KERNEL_INNER}
    m_first, m_solve, mres = _timed_solves(mk, mf["B32"], **budget)
    _, x_solve, xres = _timed_solves(mf["prep"], mf["B32"], **budget)
    matfree_names = _kernel_names(
        _matfree_program_text(mk, mf["B32"], KERNEL_EPOCHS, KERNEL_INNER)
    )
    rec = {
        "phase": "kernels",
        "dense_prepare_s": dense_prepare_s,
        "dense_first_solve_s": d_first, "dense_solve_s": d_solve,
        "dense_epochs_to_tol": int(dres.iterations_to_tol(dense["tol"]).max()),
        "dense_relerr_vs_xla": _relerr(dres.x, dense["x"]),
        "dense_relerr_x_true": _relerr(dres.x, dense["X"]),
        "dense_kernels": sorted(dense_names),
        "matfree_n": cfg.matfree_n,
        "matfree_prepare_s": matfree_prepare_s,
        "matfree_epochs": KERNEL_EPOCHS, "matfree_inner_iters": KERNEL_INNER,
        "matfree_first_solve_s": m_first, "matfree_solve_s": m_solve,
        "matfree_xla_solve_s": x_solve,
        "matfree_relerr_vs_xla": _relerr(mres.x, xres.x),
        "matfree_kernels": sorted(matfree_names),
        "peak_bytes_in_use": _peak_bytes(jax.devices()[0]),
    }
    _check(failures, rec["dense_relerr_vs_xla"] <= RELERR_GATE,
           "dense kernels vs XLA")
    _check(failures, rec["dense_relerr_x_true"] <= RELERR_GATE,
           "dense kernels vs x_true")
    _check(failures, rec["matfree_relerr_vs_xla"] <= RELERR_GATE,
           "matfree kernels vs XLA")
    if on_tpu:  # interpret mode (CPU) lowers no TPU kernel
        want_dense = {"_trisolve_kernel", "_matvec_kernel", "_update_kernel"}
        want_matfree = {"_spmm_kernel", "_spmm_fused_kernel"}
        _check(failures, want_dense <= dense_names, "dense kernel missing")
        _check(failures, want_matfree <= matfree_names,
               "matfree kernel missing")
    return rec, failures, None


def phase_four_chips(cfg: Config, devices: int = FOUR):
    """Sharded matfree over ``devices`` chips vs the one-chip solve.

    The sharded solver runs FIRST, so the per-device peaks it leaves show
    whether its state spread over the mesh or piled onto device 0 (the
    one-chip twin placed afterwards lives on device 0 by design). Both
    prepare with ``balance=False``: the balance search is single-threaded
    host Python, paid here per chip-second on every device."""
    import jax

    from repro.core import prepare
    from repro.launch.mesh import make_block_mesh
    from repro.obs.convergence import audit_epoch_collectives
    from repro.sparse import generate_schenk_like

    coo = generate_schenk_like(
        cfg.matfree_n, seed=cfg.seed, cond_boost=COND_BOOST
    )
    X = _x_true(cfg.matfree_n, cfg.k, cfg.seed)
    B = _coo_apply(coo, X)
    B32 = B.astype(np.float32)
    tol = _tol(B)
    kw = dict(mode="matfree", num_blocks=cfg.blocks, gamma=GAMMA, eta=ETA,
              balance=False)
    mesh = make_block_mesh(devices)
    t0 = time.perf_counter()
    sharded = prepare(coo, mesh=mesh, **kw)
    sharded_prepare_s = time.perf_counter() - t0
    s_first, s_solve, sres = _timed_solves(
        sharded, B32, num_epochs=cfg.epochs, tol=tol
    )
    # the collective budget is audited on the plain (no-tol) program: tol
    # adds the k-length residual psum its early exit needs
    audit = audit_epoch_collectives(
        sharded, None, cfg.epochs, bvecs=sharded.block_rhs(B32)
    )
    peaks = [_peak_bytes(d) for d in mesh.devices.flat]

    t0 = time.perf_counter()
    single = prepare(coo, **kw)
    single_prepare_s = time.perf_counter() - t0
    o_first, _, ores = _timed_solves(
        single, B32, warm=False, num_epochs=cfg.epochs, tol=tol
    )
    nk = cfg.matfree_n * cfg.k
    fraction = sharded.per_device_memory_bytes / single.memory_bytes
    rec = {
        "phase": "four_chips", "devices": devices, "n": cfg.matfree_n,
        "k": cfg.k, "blocks": cfg.blocks, "epochs": cfg.epochs,
        "sharded_prepare_s": sharded_prepare_s,
        "sharded_first_solve_s": s_first, "sharded_solve_s": s_solve,
        "single_prepare_s": single_prepare_s, "single_first_solve_s": o_first,
        "epochs_to_tol": int(sres.iterations_to_tol(tol).max()),
        "relerr_sharded_vs_single": _relerr(sres.x, ores.x),
        "relerr_x_true": _relerr(sres.x, X),
        "host_residual": _host_residual(coo, sres.x, B),
        "epoch_collectives": audit["ops"],
        "epoch_payload_elems": audit["payload_elems"],
        "per_device_bytes": sharded.per_device_memory_bytes,
        "single_bytes": single.memory_bytes,
        "device_fraction": fraction,
        "peak_bytes_in_use_after_sharded": peaks,
    }
    failures: list = []
    _check(failures, rec["relerr_sharded_vs_single"] <= RELERR_GATE,
           "sharded vs single")
    _check(failures, audit["ops"] == 1 and audit["payload_elems"] == nk,
           "not one n·k collective per epoch")
    _check(failures, fraction <= 1.15 / devices, "per-device bytes")
    if None not in peaks and len(peaks) > 1:
        # every chip holds its share of the operator and the same solve
        # temporaries; device 0 adds only the host-put right-hand sides
        _check(failures, peaks[0] <= 1.1 * max(peaks[1:]), "device 0 piled up")
    return rec, failures, None


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def _refuse(why: str) -> int:
    print(f"chip_smoke: {why}", file=sys.stderr)
    return 2


class _CompileClock:
    """Backend compile seconds and persistent-cache hits, from jax's
    monitoring events (process-wide; read as deltas around a phase)."""

    def __init__(self, jax):
        self.seconds, self.hits, self.misses = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.seconds, self.hits, self.misses


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded four-chip phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return _refuse(f"needs a TPU; jax found {devices[0].platform}")
    if args.four_chips and len(devices) < FOUR:
        return _refuse(f"--four-chips needs {FOUR} chips, found {len(devices)}")
    if not (SRC / "repro").is_dir():
        return _refuse(f"{SRC / 'repro'} not found: run from a checkout")
    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    clock = _CompileClock(jax)
    cfg = Config(seed=args.seed)

    # (phase, the phases whose state it builds on, runner)
    if args.four_chips:
        plan = [("four_chips", (), lambda s: phase_four_chips(cfg))]
    else:
        plan = [
            ("dense", (), lambda s: phase_dense(cfg)),
            ("matfree", (), lambda s: phase_matfree(cfg)),
            ("served", ("matfree",), lambda s: phase_served(cfg, s["matfree"])),
            ("kernels", ("dense", "matfree"),
             lambda s: phase_kernels(cfg, s["dense"], s["matfree"])),
        ]
    states: dict = {}
    failed: list = []
    t_start = time.perf_counter()
    for name, needs, run in plan:
        c0 = clock.snapshot()
        t0 = time.perf_counter()
        if not all(n in states for n in needs):
            rec, failures, state = {"phase": name}, ["skipped"], None
        else:
            try:
                rec, failures, state = run(states)
            except Exception:  # reported and failed below; later phases run
                traceback.print_exc()
                rec, failures, state = {"phase": name}, ["raised"], None
        c1 = clock.snapshot()
        rec.update(
            phase_s=time.perf_counter() - t0,
            compile_s=c1[0] - c0[0], cache_hits=c1[1] - c0[1],
            cache_misses=c1[2] - c0[2], failures=failures,
        )
        print(json.dumps(rec), flush=True)
        if state is not None and not failures:
            states[name] = state
        if failures:
            failed.append(name)
        del state
    print(json.dumps({
        "phase": "summary", "wall_s": time.perf_counter() - t_start,
        "compile_s": clock.seconds, "cache_hits": clock.hits,
        "cache_misses": clock.misses, "cache_dir": cache_dir,
        "failed": failed,
    }), flush=True)
    if failed:
        return 1
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
