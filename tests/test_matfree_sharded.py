"""Sharded matrix-free solver (ISSUE 5 tentpole): single-host parity,
mesh placement, and serving-pool routing.

Like the dense shard_map tests, the in-process tests run the FULL SPMD
program on a 1-device mesh (shard_map + pmean/psum all exercised); the
multi-device checks spawn a subprocess with
``--xla_force_host_platform_device_count`` so this process keeps its
single device.
"""
import asyncio
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core import ShardedMatrixFreeSolver, prepare
from repro.serving.queue import SolveServer
from repro.sparse import generate_schenk_like
from repro.testing import given, settings, st

GAMMA, ETA = 2.0, 1.9  # the square-sparse consensus hyperparameters


def _mesh1():
    return jax.make_mesh((1,), ("data",))


def _problem(n=192, k=4, seed=5):
    coo = generate_schenk_like(n, sparsity=0.998, seed=seed)
    A = coo.to_dense().astype(np.float32)
    rng = np.random.default_rng(seed + 100)
    xs = rng.standard_normal((n, k)).astype(np.float32)
    return coo, (A @ xs).astype(np.float32), xs


@pytest.mark.parametrize("gram_solver", ["direct", "pcg"])
def test_sharded_matches_single_host(gram_solver):
    """Acceptance: prepare(A, mode='matfree', mesh=...) tracks the
    single-host MatrixFreePreparedSolver trajectory through BOTH inner
    Gram solvers — same x̄, same residual history, same history contract."""
    coo, B, xs = _problem()
    sh = prepare(
        coo, mode="matfree", num_blocks=8, mesh=_mesh1(),
        gram_solver=gram_solver,
    )
    s1 = prepare(coo, mode="matfree", num_blocks=8, gram_solver=gram_solver)
    assert isinstance(sh, ShardedMatrixFreeSolver)
    assert sh.path == "matfree_sharded" and sh.mode == "matfree"
    assert sh.gram_solver == gram_solver
    r_sh = sh.solve(B, num_epochs=120, gamma=GAMMA, eta=ETA, x_ref=xs)
    r_s1 = s1.solve(B, num_epochs=120, gamma=GAMMA, eta=ETA, x_ref=xs)
    scale = np.abs(r_s1.x).max() + 1e-30
    assert float(np.abs(r_sh.x - r_s1.x).max() / scale) <= 1e-5
    np.testing.assert_allclose(
        np.asarray(r_sh.history["residual_sq"]),
        np.asarray(r_s1.history["residual_sq"]),
        rtol=1e-3, atol=1e-6,
    )
    assert np.asarray(r_sh.history["inner_iters"]).shape == (120, xs.shape[1])
    assert np.asarray(r_sh.history["mse"]).shape == (120, xs.shape[1])
    assert float(np.max(np.asarray(r_sh.history["mse"])[-1])) < 1e-5
    # per-column scatter works on sharded results (serving contract)
    cols = r_sh.per_column(tol=1e3)
    assert len(cols) == xs.shape[1]


@pytest.mark.parametrize("gram_solver", ["direct", "pcg"])
def test_sharded_iterations_to_tol_parity(gram_solver):
    """The masked early exit freezes the same columns at the same epochs as
    the single-host solver (per-column iterations_to_tol parity)."""
    coo, B, xs = _problem(seed=7)
    sh = prepare(
        coo, mode="matfree", num_blocks=8, mesh=_mesh1(),
        gram_solver=gram_solver,
    )
    s1 = prepare(coo, mode="matfree", num_blocks=8, gram_solver=gram_solver)
    free = s1.solve(B, num_epochs=120, gamma=GAMMA, eta=ETA)
    trace = np.asarray(free.history["residual_sq"])
    tol = float(np.sqrt(trace[-1].max()) * 3.0)
    r_sh = sh.solve(B, num_epochs=120, gamma=GAMMA, eta=ETA, tol=tol)
    r_s1 = s1.solve(B, num_epochs=120, gamma=GAMMA, eta=ETA, tol=tol)
    np.testing.assert_array_equal(
        r_sh.iterations_to_tol(tol), r_s1.iterations_to_tol(tol)
    )
    assert (r_sh.iterations_to_tol(tol) < 120).all()


def test_sharded_balance_stays_shard_local():
    """balance=True (the matfree default) keeps its ext_pos/int_pos
    permutation inside the shards: the balanced sharded solver matches the
    UNBALANCED single-host one — the permutation is externally invisible."""
    coo, B, _ = _problem(seed=9)
    sh = prepare(coo, mode="matfree", num_blocks=8, mesh=_mesh1(), balance=True)
    s1 = prepare(coo, mode="matfree", num_blocks=8, balance=False)
    assert sh.op.ext_pos is not None and s1.op.ext_pos is None
    r_sh = sh.solve(B, num_epochs=60, gamma=GAMMA, eta=ETA)
    r_s1 = s1.solve(B, num_epochs=60, gamma=GAMMA, eta=ETA)
    scale = np.abs(r_s1.x).max() + 1e-30
    assert float(np.abs(r_sh.x - r_s1.x).max() / scale) <= 1e-5


@settings(max_examples=5, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 5), st.booleans())
def test_sharded_parity_property(seed, k, direct):
    """Property: across problem draws, RHS widths, and both Gram solvers,
    the mesh solver reproduces the single-host solution."""
    coo, B, _ = _problem(n=128, k=k, seed=seed)
    gram_solver = "direct" if direct else "pcg"
    sh = prepare(
        coo, mode="matfree", num_blocks=4, mesh=_mesh1(),
        gram_solver=gram_solver,
    )
    s1 = prepare(coo, mode="matfree", num_blocks=4, gram_solver=gram_solver)
    r_sh = sh.solve(B, num_epochs=50, gamma=GAMMA, eta=ETA)
    r_s1 = s1.solve(B, num_epochs=50, gamma=GAMMA, eta=ETA)
    scale = np.abs(r_s1.x).max() + 1e-30
    assert float(np.abs(r_sh.x - r_s1.x).max() / scale) <= 1e-5


def test_sharded_memory_reporting():
    coo, _, _ = _problem()
    sh = prepare(coo, mode="matfree", num_blocks=8, mesh=_mesh1())
    s1 = prepare(coo, mode="matfree", num_blocks=8)
    # global bytes match the single-host operator; on a 1-device mesh the
    # whole thing lives on that device (the 1/D check is the subprocess's)
    assert sh.memory_bytes == s1.memory_bytes
    assert sh.per_device_memory_bytes == sh.memory_bytes
    assert sh.num_shards == 1
    assert sh.dense_memory_bytes == s1.dense_memory_bytes


def test_prepare_mesh_requires_matfree_path():
    coo, _, _ = _problem()
    A = coo.to_dense().astype(np.float32)
    with pytest.raises(ValueError, match="matfree"):
        prepare(A, mode="dense", num_blocks=8, mesh=_mesh1())
    # auto resolving dense must refuse too, not silently ignore the mesh
    with pytest.raises(ValueError, match="matfree"):
        prepare(A, mode="auto", num_blocks=8, mesh=_mesh1())


def test_prepare_mesh_validates_layout():
    coo, _, _ = _problem()
    with pytest.raises(ValueError, match="missing"):
        prepare(coo, mode="matfree", num_blocks=8, mesh=_mesh1(),
                block_axes=("model",))


def test_serving_pool_routes_sharded():
    """ROADMAP item: coalesced serving batches ride the sharded path — a
    SolveServer whose pool prepares with mesh= dispatches (m, k) batches
    through the ShardedMatrixFreeSolver and scatters per-request results
    identical to the single-host path."""
    coo, B, _ = _problem()

    async def main():
        async with SolveServer(
            max_batch=3, max_wait_ms=20.0, num_epochs=100,
            prepare_kwargs=dict(
                num_blocks=8, mode="matfree", mesh=_mesh1(),
                gamma=GAMMA, eta=ETA,
            ),
        ) as srv:
            fp = srv.register(coo)
            results = await asyncio.gather(
                *(srv.submit(fp, B[:, i]) for i in range(3))
            )
            return results, srv.pool.resident(), srv.pool.get(fp)

    results, resident, pooled = asyncio.run(main())
    assert isinstance(pooled, ShardedMatrixFreeSolver)
    assert resident[0]["path"] == "matfree_sharded"
    s1 = prepare(coo, mode="matfree", num_blocks=8, gamma=GAMMA, eta=ETA)
    want = s1.solve(B[:, :3], num_epochs=100).x
    for i, r in enumerate(results):
        np.testing.assert_allclose(r.x, want[:, i], atol=1e-5)


# HPCG's stencil with the inner PCG capped short of an exact Gram solve:
# the grids and caps of test_matfree_solver.py's HPCG_CAPPED
HPCG_CAPPED = [((2, 2, 4), 1), ((4, 4, 4), 4)]
HPCG_KW = dict(mode="matfree", num_blocks=8, gamma=GAMMA, eta=ETA,
               balance=False, gram_solver="pcg")
HPCG_TOL, HPCG_EPOCHS = 1e-5, 600


@pytest.mark.parametrize("grid,cap", HPCG_CAPPED)
def test_sharded_capped_pcg_matches_single_host(hpcg, grid, cap):
    """The sharded epoch folds the same block-local defect back into its
    Gram solves: capped PCG on the mesh reaches tol at the single-host
    epochs, with the single-host answer and Gram SpMV count."""
    coo, _, B = hpcg(*grid)
    B = B.astype(np.float32)
    sh = prepare(coo, mesh=_mesh1(), inner_iters=cap, **HPCG_KW)
    s1 = prepare(coo, inner_iters=cap, **HPCG_KW)
    r_sh = sh.solve(B, num_epochs=HPCG_EPOCHS, tol=HPCG_TOL)
    r_s1 = s1.solve(B, num_epochs=HPCG_EPOCHS, tol=HPCG_TOL)
    its = r_s1.iterations_to_tol(HPCG_TOL)
    assert (its < HPCG_EPOCHS).all()
    np.testing.assert_array_equal(r_sh.iterations_to_tol(HPCG_TOL), its)
    scale = np.abs(r_s1.x).max()
    assert float(np.abs(r_sh.x - r_s1.x).max() / scale) <= 1e-5
    assert r_sh.gram_iters == r_s1.gram_iters
    assert r_sh.epochs_run == r_s1.epochs_run


MULTI_DEVICE_HPCG_SCRIPT = textwrap.dedent(
    """
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, numpy as np
    from repro.core import prepare
    from repro.obs import audit_epoch_collectives
    from repro.sparse import COOMatrix

    assert jax.device_count() == 4, jax.device_count()
    mesh = jax.make_mesh((4,), ("data",))
    tol, epochs = float(sys.argv[2]), int(sys.argv[3])
    cases = np.load(sys.argv[1])
    for name in sorted({key.split(".")[0] for key in cases.files}):
        a = {f: cases[name + "." + f] for f in ("rows", "cols", "vals", "B")}
        n, k = a["B"].shape
        coo = COOMatrix(a["rows"], a["cols"], a["vals"], (n, n))
        kw = dict(mode="matfree", num_blocks=8, gamma=2.0, eta=1.9,
                  balance=False, gram_solver="pcg",
                  inner_iters=int(cases[name + ".cap"]))
        sh = prepare(coo, mesh=mesh, **kw)
        s1 = prepare(coo, **kw)
        r_sh = sh.solve(a["B"], num_epochs=epochs, tol=tol)
        r_s1 = s1.solve(a["B"], num_epochs=epochs, tol=tol)
        its = r_s1.iterations_to_tol(tol)
        assert (its < epochs).all(), its
        # the pmean sums the four shards' means where one device sums all
        # eight blocks: float32 rounding apart, so a column that meets tol
        # within that rounding may meet it one epoch apart, its answer one
        # step (at the scale of tol) apart
        slip = np.abs(r_sh.iterations_to_tol(tol) - its)
        assert slip.max() <= 1, slip
        relerr = float(np.abs(r_sh.x - r_s1.x).max() / np.abs(r_s1.x).max())
        assert relerr <= 1e-4, relerr
        # the slowest shard's Gram SpMVs are the single device's, up to
        # one capped epoch for each epoch of slip
        more = abs(r_sh.epochs_run - r_s1.epochs_run)
        assert more <= 1
        assert abs(r_sh.gram_iters - r_s1.gram_iters) <= kw["inner_iters"] * more
        # one n·k pmean an epoch, and the k-length psum only under tol
        audit_epoch_collectives(sh, a["B"], num_epochs=6, max_ops=1,
                                max_payload_elems=n * k)
        audit_epoch_collectives(sh, a["B"], num_epochs=6, tol=tol,
                                max_ops=2, max_payload_elems=n * k + k)
        print(name, "OK relerr", relerr, "gram_iters", r_sh.gram_iters)
    """
)


def test_multi_device_capped_pcg_subprocess(hpcg, tmp_path):
    """On a real 4-device CPU mesh (two blocks a device), capped PCG on
    HPCG's stencil reaches tol with the single-device answer, epochs and
    Gram SpMV count, inside the epoch's collective budget."""
    cases = {}
    for grid, cap in HPCG_CAPPED:
        coo, _, B = hpcg(*grid)
        name = "x".join(map(str, grid))
        cases.update({
            f"{name}.rows": coo.rows, f"{name}.cols": coo.cols,
            f"{name}.vals": coo.vals, f"{name}.B": B.astype(np.float32),
            f"{name}.cap": np.int64(cap),
        })
    np.savez(tmp_path / "cases.npz", **cases)
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", MULTI_DEVICE_HPCG_SCRIPT,
         str(tmp_path / "cases.npz"), str(HPCG_TOL), str(HPCG_EPOCHS)],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(__file__)), timeout=600,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert out.stdout.count(" OK ") == len(HPCG_CAPPED)


MULTI_DEVICE_DIA_SCRIPT = textwrap.dedent(
    """
    import dataclasses, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, numpy as np
    from repro.core import prepare
    from repro.sparse import COOMatrix
    from repro.sparse.bsr import PartitionedBSR

    assert jax.device_count() == 4, jax.device_count()
    mesh = jax.make_mesh((4,), ("data",))
    tol, epochs = float(sys.argv[2]), int(sys.argv[3])
    a = np.load(sys.argv[1])
    B = a["B"]
    n, k = B.shape
    coo = COOMatrix(a["rows"], a["cols"], a["vals"], (n, n))
    sh = prepare(coo, mesh=mesh, mode="matfree", num_blocks=8, gamma=2.0,
                 eta=1.9, balance=False, gram_solver="pcg")
    assert sh.gram_form == "dia", sh.gram_form
    assert sh.gram_bytes == sh.op.gram_diags.nbytes // 4
    # the same solver on the ELL Gram shards, placed the same way
    ell_op = PartitionedBSR.from_coo(coo, 8, with_gram=True,
                                     with_transpose=True, host=True)
    ell_op = dataclasses.replace(ell_op, tra_indices=None, tra_data=None)
    ell = dataclasses.replace(sh, op=ell_op.place(mesh, ("data",)),
                              _jit_cache={})
    assert ell.gram_form == "ell" and ell.gram_bytes > sh.gram_bytes
    r_dia = sh.solve(B.astype(np.float32), num_epochs=epochs, tol=tol)
    r_ell = ell.solve(B.astype(np.float32), num_epochs=epochs, tol=tol)
    its_dia = r_dia.iterations_to_tol(tol)
    its_ell = r_ell.iterations_to_tol(tol)
    assert (its_dia < epochs).all() and (its_ell < epochs).all()
    slip = np.abs(its_dia - its_ell)
    assert slip.max() <= 1, (its_dia, its_ell)
    # float64 residual of the whole system, in units of tol (min ||b|| = 1)
    Ax = np.zeros((n, k))
    np.add.at(Ax, a["rows"], a["vals"][:, None] * r_dia.x[a["cols"]])
    resid = float(np.linalg.norm(Ax - B, axis=0).max() / tol)
    assert resid <= 1.05, resid
    print("dia OK epochs", its_dia.max(), "ell", its_ell.max(), "resid", resid)
    """
)


def test_multi_device_dia_gram_subprocess(hpcg, tmp_path):
    """On a 4-device CPU mesh, HPCG's 8×8×16 stencil takes the diagonal
    Gram form: the capped PCG reaches tol within one epoch of the ELL
    form, and the answer passes the float64 residual check."""
    coo, _, B = hpcg(8, 8, 16)
    np.savez(tmp_path / "case.npz", rows=coo.rows, cols=coo.cols,
             vals=coo.vals, B=B)
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", MULTI_DEVICE_DIA_SCRIPT,
         str(tmp_path / "case.npz"), str(HPCG_TOL), str(HPCG_EPOCHS)],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(__file__)), timeout=600,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "dia OK" in out.stdout


@pytest.mark.parametrize("mesh", [False, True])
@pytest.mark.parametrize(
    "gram_solver, form", [("pcg", "dia"), ("direct", "none")]
)
def test_solve_span_reports_gram_form(hpcg, profile, mesh, gram_solver, form):
    """``repro.solve`` carries ``gram_form`` and ``gram_bytes``: the
    stored diagonals' bytes on the PCG path, none on the direct path."""
    coo, _, B = hpcg(8, 8, 16)
    kw = dict(HPCG_KW, gram_solver=gram_solver)
    prep = prepare(coo, mesh=_mesh1() if mesh else None, **kw)
    with profile() as events:
        prep.solve(B.astype(np.float32), num_epochs=5)
    (stats,) = [e[3] for e in events if e[0] == "repro.solve"]
    assert stats["gram_form"] == prep.gram_form == form
    assert stats["gram_bytes"] == prep.gram_bytes
    want = prep.op.gram_diags.nbytes if form == "dia" else 0
    assert prep.gram_bytes == want


MULTI_DEVICE_MATFREE_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, numpy as np
    from repro.core import prepare
    from repro.sparse import generate_schenk_like

    assert jax.device_count() == 4, jax.device_count()
    mesh = jax.make_mesh((4,), ("data",))
    coo = generate_schenk_like(256, sparsity=0.998, seed=5)
    A = coo.to_dense().astype(np.float32)
    rng = np.random.default_rng(11)
    xs = rng.standard_normal((256, 4)).astype(np.float32)
    B = (A @ xs).astype(np.float32)

    for gram_solver in ("direct", "pcg"):
        sh = prepare(coo, mode="matfree", num_blocks=8, mesh=mesh,
                     gram_solver=gram_solver)
        s1 = prepare(coo, mode="matfree", num_blocks=8,
                     gram_solver=gram_solver)
        r_sh = sh.solve(B, num_epochs=120, gamma=2.0, eta=1.9)
        r_s1 = s1.solve(B, num_epochs=120, gamma=2.0, eta=1.9)
        scale = np.abs(r_s1.x).max()
        relerr = float(np.abs(r_sh.x - r_s1.x).max() / scale)
        assert relerr <= 1e-4, (gram_solver, relerr)
        # per-column iterations_to_tol parity on the 4-device mesh
        trace = np.asarray(r_s1.history["residual_sq"])
        tol = float(np.sqrt(trace[-1].max()) * 3.0)
        np.testing.assert_array_equal(
            sh.solve(B, 120, gamma=2.0, eta=1.9, tol=tol)
              .iterations_to_tol(tol),
            s1.solve(B, 120, gamma=2.0, eta=1.9, tol=tol)
              .iterations_to_tol(tol),
        )
        # one group of partition blocks per device: ~1/4 resident each
        frac = sh.per_device_memory_bytes / s1.memory_bytes
        assert frac <= 0.30, frac
        print(gram_solver, "OK relerr", relerr, "per-device frac", frac)

    # J must split evenly over the block-axis devices
    try:
        prepare(coo, mode="matfree", num_blocks=6, mesh=mesh)
    except ValueError as e:
        assert "divisible" in str(e), e
        print("divisibility check OK")
    else:
        raise AssertionError("num_blocks=6 over 4 devices did not raise")
    """
)


def test_multi_device_mesh_subprocess():
    """Acceptance: the sharded solver on a real 4-device CPU mesh matches
    the single-host matfree solution with ~1/4 resident bytes per device,
    for both Gram solvers."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", MULTI_DEVICE_MATFREE_SCRIPT],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(__file__)), timeout=600,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "pcg OK" in out.stdout
