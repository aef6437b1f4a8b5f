"""Every consensus solver runs the one epoch loop, ``consensus_scan``.

One case per caller — dense ``run_consensus`` with the materialized and
the factored projector, the matrix-free solver with direct and PCG Gram
solves, the matrix-free solver under ``shard_map`` on the in-process
1-device mesh, ``solve_sharded`` and ``solve_sharded_2d`` — without and,
where the caller takes it, with ``tol``. Each must trace exactly one epoch
``scan``, emit the same history layout (``(E, k)`` rows, an ``initial``
row of the same keys), and under ``tol`` repeat its last live row over the
frozen tail while ``live_epochs`` counts the epochs whose body ran.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import consensus, dapc, distributed, partition_system, prepare
from repro.sparse import generate_schenk_like, make_problem

K = 4


def _dense_problem():
    prob = make_problem(n=64, m=256, seed=2, dtype=np.float32)
    xs = np.random.default_rng(5).standard_normal((64, K)).astype(np.float32)
    return partition_system(prob.A, prob.A @ xs, 8), jnp.asarray(xs)


def _run_consensus(materialize_p):
    part, xs = _dense_problem()
    x0s, Ws = dapc.setup_decomposed(part.blocks, part.bvecs, part.mode)
    apply_fn = dapc.make_apply(Ws, materialize_p)

    def solve(tol):
        return consensus.run_consensus(
            x0s, apply_fn, 1.0, 0.9, 60, x_ref=xs,
            blocks=part.blocks, bvecs=part.bvecs, tol=tol,
        )

    return solve, 60


def _matfree(gram_solver, mesh=None):
    coo = generate_schenk_like(192, sparsity=0.998, seed=5)
    xs = np.random.default_rng(105).standard_normal((192, K))
    xs = xs.astype(np.float32)
    B = (coo.to_dense().astype(np.float32) @ xs).astype(np.float32)
    prep = prepare(
        coo, mode="matfree", num_blocks=8, gram_solver=gram_solver,
        mesh=mesh,
    )
    gamma, eta = prep._dynamics_operands(2.0, 1.9, np.float32, False)

    def solve(tol):
        run = prep._solve_program(120, prep.inner_iters, True, tol)
        return run(
            prep.op, prep.diag_inv, prep.gram_inv, prep.block_rhs(B),
            gamma, eta, jnp.asarray(xs), None,
        )

    return solve, 120


def _solve_sharded():
    part, xs = _dense_problem()

    def solve(tol):
        assert tol is None
        return distributed.solve_sharded(
            part.blocks, part.bvecs, jax.make_mesh((1,), ("data",)),
            part.mode, num_epochs=60, x_ref=xs,
        )

    return solve, 60


def _solve_sharded_2d():
    part, xs = _dense_problem()

    def solve(tol):
        assert tol is None
        return distributed.solve_sharded_2d(
            jnp.swapaxes(part.blocks, 1, 2), part.bvecs,
            jax.make_mesh((1, 1), ("data", "model")), num_epochs=60,
            x_ref=xs,
        )

    return solve, 60


CALLERS = {
    "run_consensus dense": lambda: _run_consensus(True),
    "run_consensus implicit": lambda: _run_consensus(False),
    "matfree direct": lambda: _matfree("direct"),
    "matfree pcg": lambda: _matfree("pcg"),
    "matfree sharded": lambda: _matfree(
        "direct", jax.make_mesh((1,), ("data",))
    ),
    "solve_sharded": _solve_sharded,
    "solve_sharded_2d": _solve_sharded_2d,
}
TAKES_TOL = {name for name in CALLERS if not name.startswith("solve_")}
CASES = [(name, False) for name in CALLERS] + [
    (name, True) for name in CALLERS if name in TAKES_TOL
]


def _count(jaxpr, name) -> int:
    total = 0
    for eqn in jaxpr.eqns:
        total += eqn.primitive.name == name
        for v in eqn.params.values():
            for u in v if isinstance(v, (list, tuple)) else (v,):
                sub = getattr(u, "jaxpr", u)
                if hasattr(sub, "eqns"):
                    total += _count(sub, name)
    return total


@pytest.mark.parametrize("caller,with_tol", CASES)
def test_epoch_loop_contract(caller, with_tol, monkeypatch):
    calls = []
    loop = consensus.consensus_scan

    def counted_loop(x0s, xbar0, project, *args, **kwargs):
        def counted(*a):
            jax.debug.callback(lambda: calls.append(1))
            return project(*a)

        return loop(x0s, xbar0, counted, *args, **kwargs)

    # every caller reaches the loop through the module attribute
    monkeypatch.setattr(consensus, "consensus_scan", counted_loop)
    solve, epochs = CALLERS[caller]()
    tol = None
    if with_tol:  # a tol every column meets well inside the budget
        _, free = solve(None)
        tol = float(np.sqrt(np.asarray(free["residual_sq"])[-1].max()) * 3)
    calls.clear()
    xbar, hist = solve(tol)
    jax.block_until_ready((xbar, hist))
    jax.effects_barrier()

    assert _count(jax.make_jaxpr(lambda: solve(tol))().jaxpr, "scan") == 1
    hist = jax.tree.map(np.asarray, hist)
    initial = hist.pop("initial")
    assert set(initial) == set(hist) >= {"residual_sq", "mse"}
    for key, rows in hist.items():
        assert rows.shape == (epochs, K), key
        assert initial[key].shape == (K,), key

    live = consensus.live_epochs({**hist, "initial": initial}, epochs, tol)
    assert len(calls) == live
    if tol is None:
        assert live == epochs
        return
    assert 0 < live < epochs
    for key, rows in hist.items():
        if key in ("residual_sq", "mse"):  # metrics of the frozen x̄
            want = rows[live - 1]
        else:  # per-epoch counters: a frozen epoch does no work
            want = np.zeros_like(rows[live])
        np.testing.assert_array_equal(
            rows[live:], np.broadcast_to(want, rows[live:].shape), key
        )
