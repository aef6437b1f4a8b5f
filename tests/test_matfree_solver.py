"""Matrix-free prepared solver: dense-path parity, path selection, serving
integration (ISSUE 3 tentpole acceptance).

The matfree path applies the SAME consensus iteration as the dense path —
only the projector application differs (inner CG vs QR factors) — so with
an accurate inner solve the two trajectories must agree to float tolerance,
not just both converge.
"""
import asyncio

import numpy as np
import pytest
from scipy.sparse.linalg import spsolve

from repro.core import (
    MatrixFreePreparedSolver,
    PreparedSolver,
    prepare,
    resolve_path,
    solve,
)
from repro.serving.queue import PreparedPool, SolveServer
from repro.sparse import generate_schenk_like, make_problem


@pytest.fixture(scope="module")
def problem():
    # square system: the core stays sparse (augmentation would densify it)
    return make_problem(n=96, m=96, sparsity=0.95, seed=3, dtype=np.float32)


@pytest.fixture(scope="module")
def rhs_batch(problem):
    rng = np.random.default_rng(17)
    xs = rng.standard_normal((96, 6)).astype(np.float32)
    return problem.A @ xs, xs


@pytest.mark.parametrize("gram_solver", ["direct", "pcg"])
def test_matfree_matches_dense_batched(problem, rhs_batch, gram_solver):
    """Acceptance: prepare(A, mode='matfree').solve(B) == dense to tol,
    through BOTH inner Gram solvers (precomputed pinv / Jacobi-PCG)."""
    B, xs = rhs_batch
    mf = prepare(
        problem.coo, mode="matfree", num_blocks=8, gram_solver=gram_solver
    )
    assert mf.gram_solver == gram_solver
    dn = prepare(problem.A, mode="dense", num_blocks=8, materialize_p=False)
    r_mf = mf.solve(B, num_epochs=150)
    r_dn = dn.solve(B, num_epochs=150)
    assert r_mf.x.shape == r_dn.x.shape == xs.shape
    scale = np.abs(r_dn.x).max() + 1e-30
    assert float(np.abs(r_mf.x - r_dn.x).max() / scale) <= 1e-4
    # residual histories agree per column as well
    np.testing.assert_allclose(
        np.asarray(r_mf.history["residual_sq"]),
        np.asarray(r_dn.history["residual_sq"]),
        rtol=1e-2, atol=1e-4,
    )


def test_matfree_fused_kernel_path_matches(problem, rhs_batch):
    """use_kernels=True routes the epoch through the fused Pallas pass
    (interpret mode off-TPU) — same trajectory as the jnp fused path."""
    B, _ = rhs_batch
    plain = prepare(problem.coo, mode="matfree", num_blocks=8)
    kern = prepare(
        problem.coo, mode="matfree", num_blocks=8, use_kernels=True
    )
    a = plain.solve(B[:, :2], num_epochs=25)
    b = kern.solve(B[:, :2], num_epochs=25)
    np.testing.assert_allclose(a.x, b.x, atol=1e-4, rtol=1e-4)


def test_matfree_single_rhs_and_accuracy(problem):
    mf = prepare(problem.coo, mode="matfree", num_blocks=8)
    dn = prepare(problem.A, mode="dense", num_blocks=8, materialize_p=False)
    r_mf = mf.solve(problem.b, num_epochs=150, x_ref=problem.x_true)
    r_dn = dn.solve(problem.b, num_epochs=150, x_ref=problem.x_true)
    assert r_mf.x.shape == (96,)
    np.testing.assert_allclose(r_mf.x, r_dn.x, atol=1e-4)
    assert np.asarray(r_mf.history["mse"]).shape == (150,)


def test_matfree_from_dense_array_matches_coo(problem, rhs_batch):
    """A dense ndarray input converts internally — same result as COO."""
    B, _ = rhs_batch
    a = prepare(problem.coo, mode="matfree", num_blocks=8).solve(B, 40)
    b = prepare(
        problem.A.astype(np.float32), mode="matfree", num_blocks=8
    ).solve(B, 40)
    np.testing.assert_allclose(a.x, b.x, atol=1e-5)


def test_matfree_inner_iterations_surfaced(problem, rhs_batch):
    B, xs = rhs_batch
    mf = prepare(problem.coo, mode="matfree", num_blocks=8)
    res = mf.solve(B, num_epochs=30)
    inner = np.asarray(res.history["inner_iters"])
    assert inner.shape == (30, xs.shape[1])  # per epoch, per column
    assert inner.min() >= 1 and inner.max() <= mf.inner_iters
    # the setup substitution reports its inner depth too
    assert np.asarray(res.history["initial"]["inner_iters"]).shape == (6,)
    # per-column scatter still works on matfree results
    cols = res.per_column(tol=1e3)
    assert len(cols) == xs.shape[1]
    assert all(c.x.shape == (96,) for c in cols)


def test_matfree_rejects_non_consensus_methods(problem):
    with pytest.raises(ValueError, match="consensus"):
        prepare(problem.coo, mode="matfree", method="cgnr")


def test_auto_keeps_non_consensus_methods_dense():
    """Regression: mode='auto' past the matfree thresholds must fall back
    to dense for dgd/cgnr instead of raising."""
    coo = generate_schenk_like(256, sparsity=0.9985, seed=1)
    for method in ("cgnr", "dgd"):
        prep = prepare(
            coo, method=method, mode="auto", num_blocks=8,
            matfree_threshold_bytes=0,
        )
        assert isinstance(prep, PreparedSolver)


def test_resolve_path_auto_rules(problem):
    # small + not sparse enough: stays dense whatever the threshold
    assert resolve_path(problem.A, 8, "auto") == "dense"
    assert resolve_path(problem.A, 8, "auto", matfree_threshold_bytes=0) == "dense"
    # 99.85% sparse + tiny threshold: auto goes matfree
    coo = generate_schenk_like(256, sparsity=0.9985, seed=1)
    assert resolve_path(coo, 8, "auto", matfree_threshold_bytes=0) == "matfree"
    # ... but an explicit mode always wins
    assert resolve_path(coo, 8, "dense", matfree_threshold_bytes=0) == "dense"
    assert resolve_path(problem.A, 8, "matfree") == "matfree"
    # default threshold keeps small systems dense even at high sparsity
    assert resolve_path(coo, 8, "auto") == "dense"
    with pytest.raises(ValueError, match="mode"):
        resolve_path(problem.A, 8, "bogus")


def test_prepare_auto_picks_matfree_past_threshold():
    coo = generate_schenk_like(256, sparsity=0.9985, seed=1)
    prep = prepare(coo, mode="auto", num_blocks=8, matfree_threshold_bytes=0)
    assert isinstance(prep, MatrixFreePreparedSolver)
    assert prep.path == "matfree" and prep.mode == "matfree"
    dense = prepare(coo, mode="auto", num_blocks=8)  # default 64 MiB floor
    assert isinstance(dense, PreparedSolver)
    # the sparse operator really is smaller than the dense factors
    assert prep.memory_bytes * 5 < dense.memory_bytes


def test_one_shot_solve_threads_mode(problem, rhs_batch):
    B, _ = rhs_batch
    res = solve(problem.coo, B, mode="matfree", num_blocks=8, num_epochs=40)
    assert res.mode == "matfree"
    ref = solve(problem.A, B, mode="dense", num_blocks=8, num_epochs=40,
                materialize_p=False)
    np.testing.assert_allclose(res.x, ref.x, atol=1e-4)


def test_pool_holds_both_kinds(problem):
    pool = PreparedPool(max_size=4, num_blocks=8)
    fp_dense = pool.register(problem.A, mode="dense", materialize_p=False)
    fp_mat = pool.register(problem.coo, mode="matfree")
    assert fp_dense != fp_mat  # sparse registration fingerprints differently
    assert isinstance(pool.get(fp_dense), PreparedSolver)
    assert isinstance(pool.get(fp_mat), MatrixFreePreparedSolver)
    resident = {e["fingerprint"]: e for e in pool.resident()}
    assert resident[fp_dense]["path"] == "dense"
    assert resident[fp_mat]["path"] == "matfree"
    assert resident[fp_mat]["memory_bytes"] > 0


def _straggler_batch(problem, scale=80.0):
    rng = np.random.default_rng(17)
    xs = rng.standard_normal((96, 6)).astype(np.float32)
    xs[:, 2] *= scale  # column 2 is the straggler under an ABSOLUTE tol
    return (problem.A @ xs).astype(np.float32)


@pytest.mark.parametrize("path", ["dense", "matfree"])
def test_masked_early_exit_straggler_matches_solo(problem, path):
    """ISSUE 4 acceptance: a batch with one slow column reports per-column
    iterations_to_tol identical to solo solves (±1 epoch) on BOTH paths,
    with converged columns frozen in-scan under the mask."""
    B = _straggler_batch(problem)
    tol = 1.0
    if path == "dense":
        prep = prepare(
            problem.A, mode="dense", num_blocks=8, materialize_p=False,
            gamma=2.0, eta=1.9,
        )
    else:
        prep = prepare(
            problem.coo, mode="matfree", num_blocks=8, gamma=2.0, eta=1.9
        )
    batched = prep.solve(B, num_epochs=200, tol=tol)
    it_batched = batched.iterations_to_tol(tol)
    it_solo = np.array([
        prep.solve(B[:, i], num_epochs=200, tol=tol).iterations_to_tol(tol)[0]
        for i in range(B.shape[1])
    ])
    assert np.abs(it_batched - it_solo).max() <= 1
    # the straggler really is the straggler, and some column froze early
    assert it_batched[2] == it_batched.max()
    assert it_batched.min() < 200
    # frozen columns stop moving: their residual history holds its value
    trace = np.asarray(batched.history["residual_sq"])
    i_fast = int(np.argmin(it_batched))
    e = int(it_batched[i_fast])
    np.testing.assert_allclose(
        trace[e:-1, i_fast], trace[e - 1, i_fast], rtol=1e-5
    )


def test_masked_early_exit_accuracy_preserved(problem, rhs_batch):
    """Freezing at tol must not disturb the still-active columns: the
    masked solve agrees with the unmasked one wherever the unmasked
    residual is still above tol."""
    B, _ = rhs_batch
    mf = prepare(problem.coo, mode="matfree", num_blocks=8, gamma=2.0, eta=1.9)
    free = mf.solve(B, num_epochs=150)
    tol = float(np.sqrt(np.asarray(free.history["residual_sq"])[-1].max()) * 5)
    masked = mf.solve(B, num_epochs=150, tol=tol)
    # all columns reached tol, and the frozen solutions still satisfy it
    final = np.asarray(masked.history["residual_sq"])[-1]
    assert (final <= tol * tol).all()
    assert (masked.iterations_to_tol(tol) < 150).all()


def test_serving_queue_with_matfree_system(problem, rhs_batch):
    """End to end: coalesced requests against a matfree-pooled system."""
    B, xs = rhs_batch

    async def main():
        async with SolveServer(
            max_batch=3, max_wait_ms=20.0, num_epochs=150,
            prepare_kwargs=dict(num_blocks=8, mode="matfree"),
        ) as srv:
            fp = srv.register(problem.coo)
            return await asyncio.gather(
                *(srv.submit(fp, B[:, i]) for i in range(3))
            )

    results = asyncio.run(main())
    mf = prepare(problem.coo, mode="matfree", num_blocks=8)
    want = mf.solve(B[:, :3], num_epochs=150).x
    for i, r in enumerate(results):
        np.testing.assert_allclose(r.x, want[:, i], atol=1e-5)


# ---------------------------------------------------------------------------
# program spans and the epochs_run counter
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("gram_solver", ["direct", "pcg"])
def test_epochs_run_counts_live_epochs(problem, gram_solver):
    """With ``tol`` the epoch body is skipped once every column froze, so
    ``epochs_run`` is the slowest column's epochs to tolerance (plus at
    most one); without it every epoch runs."""
    B = _straggler_batch(problem, scale=1.0)
    tol = 1.0
    prep = prepare(
        problem.coo, mode="matfree", num_blocks=8, gamma=2.0, eta=1.9,
        gram_solver=gram_solver,
    )
    res = prep.solve(B, num_epochs=300, tol=tol)
    slowest = int(res.iterations_to_tol(tol).max())
    assert slowest < 300
    assert slowest <= res.epochs_run <= slowest + 1
    if gram_solver == "direct":
        # the frozen branch writes zero inner depths, the live one ones
        live = (np.asarray(res.history["inner_iters"]) != 0).any(axis=1)
        assert res.epochs_run == int(live.sum())
    assert prep.solve(B, num_epochs=60).epochs_run == 60


@pytest.mark.parametrize("variant", ["direct", "pcg", "pcg_warm"])
def test_gram_iters_counts_gram_spmvs(problem, variant):
    """``gram_iters`` is the Gram SpMVs the inner solves ran: on PCG the
    set-up depth plus each live epoch's deepest column (frozen epochs add
    nothing), plus one warm residual per live epoch under ``warm_start``;
    on the direct path, one application per live epoch."""
    B = _straggler_batch(problem, scale=1.0)
    tol = 1.0
    gram_solver = variant.split("_")[0]
    warm_start = variant == "pcg_warm"
    prep = prepare(
        problem.coo, mode="matfree", num_blocks=8, gamma=2.0, eta=1.9,
        gram_solver=gram_solver, warm_start=warm_start,
    )
    res = prep.solve(B, num_epochs=300, tol=tol)
    if gram_solver == "direct":
        assert res.gram_iters == res.epochs_run
        return
    hist = res.history
    depth = np.asarray(hist["inner_iters"])
    start = np.concatenate([
        hist["initial"]["residual_sq"][None], hist["residual_sq"][:-1]
    ])
    live = (start > tol**2).any(axis=1)
    assert live.sum() == res.epochs_run < 300
    assert (depth[~live] == 0).all()
    setup = int(np.max(hist["initial"]["inner_iters"]))
    warm = res.epochs_run if warm_start else 0
    assert res.gram_iters == setup + int(depth[live].max(axis=1).sum()) + warm
    assert res.gram_iters > res.epochs_run  # PCG: several SpMVs an epoch


def test_server_counts_epochs_and_gram_iters(problem, monkeypatch):
    """The server's registry sums each batch solve's ``gram_iters``; on the
    direct path that is the epochs the batches ran."""
    B = _straggler_batch(problem, scale=1.0)
    solved = []
    solve = MatrixFreePreparedSolver.solve

    def recording(self, *args, **kwargs):
        res = solve(self, *args, **kwargs)
        solved.append(res)
        return res

    monkeypatch.setattr(MatrixFreePreparedSolver, "solve", recording)

    async def main():
        async with SolveServer(
            max_batch=3, max_wait_ms=20.0, num_epochs=300, tol=1.0,
            prepare_kwargs=dict(
                num_blocks=8, mode="matfree", gamma=2.0, eta=1.9,
                gram_solver="direct",
            ),
        ) as srv:
            fp = srv.register(problem.coo)
            await asyncio.gather(*(srv.submit(fp, B[:, i]) for i in range(3)))
            return srv.metrics

    metrics = asyncio.run(main())
    epochs = sum(res.epochs_run for res in solved)
    assert 0 < epochs == metrics.value("server_gram_iters_total")
    assert epochs == sum(res.gram_iters for res in solved)


def test_epochs_run_single_rhs(problem):
    """An unbatched solve counts the same epochs as its one-column batch."""
    B = _straggler_batch(problem, scale=1.0)
    prep = prepare(problem.coo, mode="matfree", num_blocks=8, gamma=2.0, eta=1.9)
    one = prep.solve(B[:, 5], num_epochs=300, tol=1.0)
    batch = prep.solve(B[:, 5:6], num_epochs=300, tol=1.0)
    assert one.epochs_run == batch.epochs_run < 300


def test_matfree_solve_spans_under_profiler(problem, profile):
    """``repro.solve`` on the matfree path carries the live epoch count
    and the Gram applications the result reports; x and the history are
    bit-identical to a solve with the profiler off."""
    B = _straggler_batch(problem, scale=1.0)
    prep = prepare(problem.coo, mode="matfree", num_blocks=8, gamma=2.0, eta=1.9)
    plain = prep.solve(B, num_epochs=300, tol=1.0)
    with profile() as events:
        traced = prep.solve(B, num_epochs=300, tol=1.0)
    np.testing.assert_array_equal(traced.x, plain.x)
    for key in ("residual_sq", "inner_iters"):
        np.testing.assert_array_equal(traced.history[key], plain.history[key])
    assert traced.epochs_run == plain.epochs_run

    assert [e[0] for e in events] == [
        "repro.solve", "repro.solve.rhs", "repro.solve.run",
        "repro.solve.fetch",
    ]
    assert events[0][3] == {
        "path": "matfree", "k": B.shape[1], "num_epochs": 300,
        "epochs_run": traced.epochs_run, "gram_iters": traced.gram_iters,
    }
    assert traced.gram_iters == traced.epochs_run  # direct Gram (auto)
    run = events[2]
    assert traced.wall_seconds == pytest.approx(
        (run[2] - run[1]) * 1e-9, abs=2e-3
    )


# ---------------------------------------------------------------------------
# PCG capped short of an exact Gram solve, on HPCG's stencil
# ---------------------------------------------------------------------------

# (grid, PCG cap) at which the consensus stalled short of tol while each
# block projected onto its carried A_j x_j instead of b_j: 2×2×4 at cap 1
# is the smallest such grid (two rows a block), 4×4×4 at cap 4 the deepest
# cap at which 4³ (eight rows a block) stalled
HPCG_CAPPED = [((2, 2, 4), 1), ((4, 4, 4), 4)]
HPCG_KW = dict(mode="matfree", num_blocks=8, gamma=2.0, eta=1.9, balance=False)
HPCG_TOL, HPCG_EPOCHS = 1e-5, 600  # the four-chip configuration's guarantee
# the benchmark's residual limit: tol plus 5% for A and b rounded to float32
RESIDUAL_LIMIT = 1.05


@pytest.mark.parametrize("variant", ["cold", "x0", "per_block"])
@pytest.mark.parametrize("grid,cap", HPCG_CAPPED)
def test_capped_pcg_reaches_tol_on_hpcg(hpcg, grid, cap, variant):
    """With the inner PCG stopped at its cap every epoch, each column still
    reaches ‖A x − b‖ ≤ tol (float64) within the epoch budget — cold, from
    an ``x0`` warm start, and under per-block (γ_j, η_j) — and lands on the
    float64 direct solve and on the direct-Gram path's answer."""
    coo, A, B = hpcg(*grid)
    kw = dict(HPCG_KW, dynamics="per_block" if variant == "per_block"
              else "global")
    pcg = prepare(coo, gram_solver="pcg", inner_iters=cap, **kw)
    direct = prepare(coo, gram_solver="direct", **kw)
    x_star = spsolve(A.tocsc(), B)
    x0 = None
    if variant == "x0":
        noise = np.random.default_rng(1).standard_normal(x_star.shape)
        x0 = (x_star + 0.1 * noise).astype(np.float32)
    B32 = B.astype(np.float32)
    res = pcg.solve(B32, num_epochs=HPCG_EPOCHS, tol=HPCG_TOL, x0=x0)
    # the inner solve is cut short: the set-up solve ran to the cap
    assert int(np.max(res.history["initial"]["inner_iters"])) == cap
    assert (res.iterations_to_tol(HPCG_TOL) < HPCG_EPOCHS).all()
    x = res.x.astype(np.float64)
    assert np.linalg.norm(A @ x - B, axis=0).max() <= RESIDUAL_LIMIT * HPCG_TOL
    # ‖x − x*‖ ≤ ‖A⁻¹‖·‖A x − b‖, so the residual limit over A's least
    # singular value bounds the distance to the float64 solution
    sigma_min = np.linalg.svd(A.toarray(), compute_uv=False).min()
    bound = RESIDUAL_LIMIT * HPCG_TOL / sigma_min
    assert np.linalg.norm(x - x_star, axis=0).max() <= bound
    ref = direct.solve(B32, num_epochs=HPCG_EPOCHS, tol=HPCG_TOL, x0=x0)
    assert (ref.iterations_to_tol(HPCG_TOL) < HPCG_EPOCHS).all()
    # both answers lie within the bound of x*
    assert np.linalg.norm(x - ref.x, axis=0).max() <= 2 * bound


# sha256 of the traced direct-Gram program on 4×4×4 (k = 4, 600 epochs):
# the direct epoch keeps A_j x_j = b_j exactly and must not pay for the PCG
# path's defect fold. Taken again when the epoch loop moved into
# consensus.consensus_scan, whose direct epoch adds no operation of the fold
DIRECT_PROGRAM_SHA256 = {
    (None, "global"): "f70bf4837266176f",
    (None, "per_block"): "ecfaf81bea68e294",
    (HPCG_TOL, "global"): "5f138630f3946d3c",
    (HPCG_TOL, "per_block"): "4ea27c5d3ee6f3bc",
}


@pytest.mark.parametrize("tol,dynamics", list(DIRECT_PROGRAM_SHA256))
def test_direct_gram_program_unchanged(hpcg, tol, dynamics):
    """The direct-Gram solve traces to the same program, op for op."""
    import hashlib

    import jax

    coo, _, B = hpcg(4, 4, 4)
    prep = prepare(
        coo, mode="matfree", num_blocks=8, gamma=2.0, eta=1.9,
        gram_solver="direct", dynamics=dynamics,
    )
    per_block = dynamics == "per_block"
    run = prep._solve_program(
        HPCG_EPOCHS, prep.inner_iters, False, tol, per_block=per_block
    )
    gamma, eta = prep._dynamics_operands(
        prep.gamma, prep.eta, np.float32, per_block
    )
    text = str(jax.make_jaxpr(run)(
        prep.op, prep.diag_inv, prep.gram_inv,
        prep.block_rhs(B.astype(np.float32)), gamma, eta, None, None,
    ))
    digest = hashlib.sha256(text.encode()).hexdigest()[:16]
    assert digest == DIRECT_PROGRAM_SHA256[(tol, dynamics)]
