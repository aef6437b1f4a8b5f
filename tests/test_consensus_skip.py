"""The dense consensus scan skips its epoch body once every column met tol.

``run_consensus`` gates the body on ``any(residual > tol²)`` under a
``lax.cond``: the frozen branch runs no projector apply and no residual
pass, passes the state through and repeats the carried history row, so
results are those of the masked scan. Without ``tol`` no cond is traced.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import consensus, dapc, partition_system, prepare
from repro.sparse import make_problem

TOL = 1e-2
EPOCHS = 120


@pytest.fixture(scope="module")
def problem():
    return make_problem(n=96, m=384, seed=3, dtype=np.float32)


@pytest.fixture(scope="module")
def rhs_batch(problem):
    rng = np.random.default_rng(17)
    xs = rng.standard_normal((96, 6)).astype(np.float32)
    xs[:, 2] *= 1e-3  # one column freezes long before the others
    return problem.A @ xs, xs


@pytest.fixture(scope="module")
def consensus_inputs(problem, rhs_batch):
    B, _ = rhs_batch
    part = partition_system(problem.A, B, 8)
    x0s, Ws = dapc.setup_decomposed(part.blocks, part.bvecs, part.mode)
    return part, x0s, Ws


def _counting_apply(Ws, calls):
    """dapc's implicit projector apply, counting its executions."""
    apply_fn = dapc.make_apply(Ws, False)

    def counted(v):
        jax.debug.callback(lambda: calls.append(1))
        return apply_fn(v)

    return counted


@pytest.mark.parametrize("tol", [TOL, None])
def test_projector_applied_once_per_live_epoch(consensus_inputs, tol):
    """Under ``tol`` the projector runs in exactly the live epochs that
    ``live_epochs`` counts, fewer than the budget; without it in every
    epoch."""
    part, x0s, Ws = consensus_inputs
    calls = []
    _, hist = consensus.run_consensus(
        x0s, _counting_apply(Ws, calls), 1.0, 0.9, EPOCHS,
        blocks=part.blocks, bvecs=part.bvecs, tol=tol,
    )
    jax.block_until_ready(hist)
    jax.effects_barrier()
    live = consensus.live_epochs(hist, EPOCHS, tol)
    assert len(calls) == live
    if tol is None:
        assert live == EPOCHS
    else:
        assert 0 < live < EPOCHS


@pytest.mark.parametrize("tol", [TOL, None])
def test_cond_traced_only_with_tol(consensus_inputs, tol):
    part, x0s, Ws = consensus_inputs
    jaxpr = jax.make_jaxpr(
        lambda x0s: consensus.run_consensus(
            x0s, dapc.make_apply(Ws, False), 1.0, 0.9, 5,
            blocks=part.blocks, bvecs=part.bvecs, tol=tol,
        )
    )(x0s)
    assert (re.search(r"\bcond\[", str(jaxpr)) is not None) == (tol is not None)


@pytest.mark.parametrize("materialize_p", [False, True])
def test_frozen_tail_repeats_the_last_row(problem, rhs_batch, materialize_p):
    """A batch that converges early: every history row after the last
    live epoch is that epoch's row (residual, mse, per-block residual),
    and each column's report is its solo solve's. Batched and solo float32
    products round apart, so a column that lands within rounding of tol²
    may cross it one epoch apart."""
    B, xs = rhs_batch
    prep = prepare(problem.A, num_blocks=8, materialize_p=materialize_p)
    res = prep.solve(B, num_epochs=EPOCHS, x_ref=xs, tol=TOL,
                     block_history=True)
    live = res.epochs_run
    assert live == res.iterations_to_tol(TOL).max() < EPOCHS
    for key in ("residual_sq", "mse", "block_residual_sq"):
        rows = np.asarray(res.history[key])
        tail = rows[live - 1:]
        np.testing.assert_array_equal(
            tail, np.broadcast_to(rows[live - 1], tail.shape)
        )
    for col in res.per_column(TOL):
        alone = prep.solve(B[:, col.index], num_epochs=EPOCHS, tol=TOL)
        (solo,) = alone.per_column(TOL)
        assert alone.epochs_run == solo.iterations
        assert col.converged and solo.converged
        assert abs(col.iterations - solo.iterations) <= 1
        np.testing.assert_allclose(col.x, solo.x, atol=1e-4)


def test_all_frozen_at_start_runs_no_epoch(consensus_inputs):
    """Columns already within tol at x̄₀ never enter the body: x̄ is x̄₀
    and every history row is the initial one."""
    part, x0s, Ws = consensus_inputs
    calls = []
    xbar, hist = consensus.run_consensus(
        x0s, _counting_apply(Ws, calls), 1.0, 0.9, 10,
        blocks=part.blocks, bvecs=part.bvecs, tol=1e9,
    )
    jax.block_until_ready(hist)
    jax.effects_barrier()
    assert calls == [] and consensus.live_epochs(hist, 10, 1e9) == 0
    np.testing.assert_array_equal(xbar, jnp.mean(x0s, axis=0))
    want = np.broadcast_to(hist["initial"]["residual_sq"], (10, x0s.shape[-1]))
    np.testing.assert_array_equal(hist["residual_sq"], want)
