"""Prepare/solve split + batched multi-RHS contract tests (ISSUE 1 tentpole).

(a) prepare-once + repeated solves must be BITWISE identical to fresh
    one-shot solves (same compiled programs, same operands);
(b) a batched (m, k) solve must match the per-column sequential solves;
(c) the QR setup must run exactly once per prepare(), never per solve.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import PrepareConfig, dapc, prepare, solve
from repro.core.solver_api import _PREPARE_KWARGS, _SHARED_KWARGS
from repro.sparse import make_problem


@pytest.fixture(scope="module")
def problem():
    return make_problem(n=96, m=384, seed=3, dtype=np.float32)


@pytest.fixture(scope="module")
def rhs_batch(problem):
    rng = np.random.default_rng(17)
    xs = rng.standard_normal((96, 6)).astype(np.float32)
    return problem.A @ xs, xs


def test_prepared_matches_fresh_solve_bitwise(problem):
    prep = prepare(problem.A, num_blocks=8, materialize_p=False)
    r1 = prep.solve(problem.b, num_epochs=60, x_ref=problem.x_true)
    r2 = prep.solve(problem.b, num_epochs=60, x_ref=problem.x_true)
    f1 = solve(problem.A, problem.b, num_blocks=8, num_epochs=60,
               x_ref=problem.x_true, materialize_p=False)
    f2 = solve(problem.A, problem.b, num_blocks=8, num_epochs=60,
               x_ref=problem.x_true, materialize_p=False)
    np.testing.assert_array_equal(r1.x, r2.x)
    np.testing.assert_array_equal(r1.x, f1.x)
    np.testing.assert_array_equal(f1.x, f2.x)
    np.testing.assert_array_equal(
        np.asarray(r1.history["mse"]), np.asarray(f1.history["mse"])
    )
    assert prep.num_solves == 2


@pytest.mark.parametrize("method", ["dapc", "apc", "cgnr", "dgd"])
def test_batched_matches_per_column(problem, rhs_batch, method):
    B, xs = rhs_batch
    prep = prepare(problem.A, method=method, num_blocks=8)
    batched = prep.solve(B, num_epochs=120)
    assert batched.x.shape == xs.shape
    assert batched.num_rhs == xs.shape[1]
    cols = np.stack(
        [prep.solve(B[:, i], num_epochs=120).x for i in range(xs.shape[1])],
        axis=1,
    )
    scale = np.abs(cols).max() + 1e-30
    assert float(np.abs(batched.x - cols).max() / scale) <= 1e-5
    # per-epoch history rows are per-system in the batched form
    assert np.asarray(batched.history["residual_sq"]).shape == (120, xs.shape[1])


def test_batched_consensus_recovers_truth(problem, rhs_batch):
    B, xs = rhs_batch
    prep = prepare(problem.A, num_blocks=8, materialize_p=False)
    res = prep.solve(B, num_epochs=200, x_ref=xs)
    assert float(np.max(np.asarray(res.final_mse))) < 1e-9
    np.testing.assert_allclose(res.x, xs, atol=1e-4)


def test_setup_runs_once_per_prepare(problem):
    before = dapc.SETUP_STATS["qr_calls"]
    prep = prepare(problem.A, num_blocks=8, materialize_p=False)
    assert dapc.SETUP_STATS["qr_calls"] == before + 1
    for _ in range(3):
        prep.solve(problem.b, num_epochs=10)
    assert dapc.SETUP_STATS["qr_calls"] == before + 1  # cached, not recomputed
    # while every fresh one-shot solve pays it again
    solve(problem.A, problem.b, num_blocks=8, num_epochs=10)
    assert dapc.SETUP_STATS["qr_calls"] == before + 2


def test_batched_through_one_shot_wrapper(problem, rhs_batch):
    B, xs = rhs_batch
    res = solve(problem.A, B, num_blocks=8, num_epochs=200)
    assert res.x.shape == xs.shape
    np.testing.assert_allclose(res.x, xs, atol=1e-4)


def test_per_column_reporting(problem, rhs_batch):
    """Per-column scatter: each ColumnResult carries its own solution slice,
    final residual, and epochs-to-tolerance."""
    B, xs = rhs_batch
    prep = prepare(problem.A, num_blocks=8, materialize_p=False)
    res = prep.solve(B, num_epochs=200)
    cols = res.per_column(tol=1e-2)
    assert len(cols) == xs.shape[1]
    for i, col in enumerate(cols):
        assert col.index == i
        np.testing.assert_array_equal(col.x, res.x[:, i])
        assert col.converged
        assert 1 <= col.iterations <= 200
        assert col.residual_sq <= 1e-4
    # the tolerance sweep agrees with the per-column history
    iters = res.iterations_to_tol(1e-2)
    trace = np.asarray(res.history["residual_sq"])
    for i, col in enumerate(cols):
        assert iters[i] == col.iterations
        assert trace[col.iterations - 1, i] <= 1e-4
        if col.iterations > 1:
            assert trace[col.iterations - 2, i] > 1e-4


def test_per_column_flags_straggler_column(problem, rhs_batch):
    """A column whose RHS is 1000x larger needs more epochs to reach the
    same ABSOLUTE tolerance — the early-exit report must single it out
    instead of letting the batch hide it."""
    B, xs = rhs_batch
    scaled = B.copy()
    scaled[:, 2] *= 1e3  # consistent system, much larger residual scale
    prep = prepare(problem.A, num_blocks=8, materialize_p=False)
    res = prep.solve(scaled, num_epochs=60)
    iters = res.iterations_to_tol(1e-2)
    others = [i for i in range(xs.shape[1]) if i != 2]
    assert iters[2] > max(iters[i] for i in others)
    cols = res.per_column(tol=1e-2)
    assert all(cols[i].converged for i in others)
    # batchmates are NOT degraded: their solutions still match truth
    for i in others:
        np.testing.assert_allclose(cols[i].x, xs[:, i], atol=1e-3)


def test_per_column_single_rhs(problem):
    """per_column on an unbatched solve degrades to one column."""
    prep = prepare(problem.A, num_blocks=8, materialize_p=False)
    res = prep.solve(problem.b, num_epochs=100)
    (col,) = res.per_column(tol=1e-2)
    assert col.index == 0 and col.x.shape == problem.b.shape[:0] + (96,)
    np.testing.assert_array_equal(col.x, res.x)
    assert col.converged


def test_prepare_config_equivalent_to_kwargs(problem):
    """prepare(A, PrepareConfig(...)) is the same call as the kwargs form —
    the dataclass is a single source of truth, not a second code path."""
    cfg = PrepareConfig(num_blocks=8, materialize_p=False)
    p1 = prepare(problem.A, cfg)
    p2 = prepare(problem.A, num_blocks=8, materialize_p=False)
    r1 = p1.solve(problem.b, num_epochs=40)
    r2 = p2.solve(problem.b, num_epochs=40)
    np.testing.assert_array_equal(r1.x, r2.x)
    assert p1.method == p2.method and p1.num_blocks == p2.num_blocks


def test_prepare_config_is_prepares_signature():
    """Every PrepareConfig field is a real prepare() keyword (and nothing
    in the derived solver-API split is hand-maintained): the config fields
    partition exactly into solve()-shared names + _PREPARE_KWARGS."""
    import inspect

    sig = inspect.signature(prepare)
    for name in PrepareConfig.field_names():
        assert name in sig.parameters, f"config field {name} not in prepare()"
    assert set(PrepareConfig.field_names()) == (
        set(_SHARED_KWARGS) | set(_PREPARE_KWARGS)
    )
    assert not (set(_SHARED_KWARGS) & set(_PREPARE_KWARGS))
    # kwargs() round-trips the field values
    cfg = PrepareConfig(num_blocks=4, gamma=2.0)
    kw = cfg.kwargs()
    assert kw["num_blocks"] == 4 and kw["gamma"] == 2.0
    assert set(kw) == set(PrepareConfig.field_names())
    assert dataclasses.is_dataclass(cfg)


def test_one_shot_wrapper_routes_prepare_kwargs(problem):
    """Regression for the derived kwarg split: a prepare-time kwarg passed
    through the one-shot wrapper must reach prepare(), not the method."""
    res = solve(problem.A, problem.b, num_blocks=8, num_epochs=10,
                materialize_p=False, warm_start=False)
    assert res.x.shape == (96,)


def test_explicit_matfree_with_non_consensus_method_raises(problem):
    """Regression (ISSUE bugfix): an EXPLICIT mode='matfree' with a
    non-consensus method must raise a clear ValueError at prepare time;
    mode='auto' silently keeps those methods dense instead."""
    for method in ("cgnr", "dgd"):
        with pytest.raises(ValueError, match="matfree.*consensus"):
            prepare(problem.A, method=method, mode="matfree")
        prep = prepare(problem.A, method=method, mode="auto",
                       matfree_threshold_bytes=0)
        assert prep.path == "dense"


def test_prepared_solver_reports_setup_and_solves(problem):
    prep = prepare(problem.A, num_blocks=8)
    assert prep.setup_seconds > 0.0
    assert prep.num_solves == 0
    prep.solve(problem.b, num_epochs=5)
    assert prep.num_solves == 1
    assert prep.num_blocks == 8 and prep.num_cols == 96


# ---------------------------------------------------------------------------
# program spans and the epochs_run counter
# ---------------------------------------------------------------------------


def _tree_equal(a, b):
    if isinstance(a, dict):
        assert a.keys() == b.keys()
        for key in a:
            _tree_equal(a[key], b[key])
    else:
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize(
    "method,kw",
    [("dapc", {"tol": 1e-2}), ("dapc", {}), ("apc", {"tol": 1e-2}),
     ("cgnr", {}), ("dgd", {})],
)
def test_epochs_run_is_the_whole_budget(problem, rhs_batch, method, kw):
    """The dense path's scans never skip an epoch, so ``epochs_run`` is
    ``num_epochs`` even where every column reached ``tol`` long before."""
    B, _ = rhs_batch
    prep = prepare(problem.A, num_blocks=8, method=method, materialize_p=False)
    res = prep.solve(B, num_epochs=120, **kw)
    assert res.epochs_run == 120
    if kw:
        assert res.iterations_to_tol(kw["tol"]).max() < 120


def test_solve_spans_under_profiler(problem, rhs_batch, profile):
    """Under ``jax.profiler`` a solve leaves ``repro.solve`` with its args
    and the ``epochs_run`` stat added at close, and its three phases nested
    inside it in order; x and the history are bit-identical to a solve
    with the profiler off, and ``wall_seconds`` is the run phase's span."""
    B, _ = rhs_batch
    prep = prepare(problem.A, num_blocks=8, materialize_p=False)
    plain = prep.solve(B, num_epochs=50, tol=1e-2)
    with profile() as events:
        traced = prep.solve(B, num_epochs=50, tol=1e-2)
    np.testing.assert_array_equal(traced.x, plain.x)
    _tree_equal(traced.history, plain.history)

    names = [e[0] for e in events]
    assert names == [
        "repro.solve", "repro.solve.rhs", "repro.solve.run",
        "repro.solve.fetch",
    ]
    (_, t0, t1, stats, line), *phases = events
    assert stats == {
        "path": "dense", "k": B.shape[1], "num_epochs": 50, "epochs_run": 50,
    }
    ends = t0
    for _, s, e, _, phase_line in phases:
        assert phase_line == line and ends <= s <= e <= t1
        ends = e
    run_s = (phases[1][2] - phases[1][1]) * 1e-9
    assert 0 < traced.wall_seconds <= (t1 - t0) * 1e-9 + 1e-3
    assert traced.wall_seconds == pytest.approx(run_s, abs=2e-3)
