"""The dense dapc projector's form, chosen from the block shape at prepare.

``prepare(..., materialize_p=None)`` (the default) applies each block
projector as the implicit v − Wᵀ(W v) when that reads fewer bytes an epoch
than the materialized n×n P_j, i.e. when 2p < n; otherwise it builds P_j.
An explicit ``materialize_p`` pins the form. The two forms are one operator,
so a solve gives the same answer and the same epochs to tolerance; the
``repro.solve`` span reports which form ran and the bytes it reads an epoch;
a checkpoint restores the form it was saved with.
"""
import numpy as np
import pytest

from repro.core import dapc, prepare
from repro.core.prepared import PreparedSolver
from repro.serving.checkpoint import CheckpointStore
from repro.sparse import make_problem

J = 8
# name: (n, m); J = 8 row blocks of p = m / 8 rows each
SHAPES = {
    "wide_narrow": (96, 256),  # p = 32: 2p < n, the implicit form reads less
    "wide_half": (96, 384),  # p = 48: 2p = n, P_j reads no more
    "tall": (32, 384),  # p = 48 >= n: P_j ≈ 0, W_j is at least n×n
}
BY_SHAPE = {"wide_narrow": "implicit", "wide_half": "dense", "tall": "dense"}


@pytest.fixture(scope="module")
def problems():
    cache: dict = {}

    def get(name):
        if name not in cache:
            n, m = SHAPES[name]
            prob = make_problem(n=n, m=m, seed=3, dtype=np.float32)
            xs = np.random.default_rng(17).standard_normal((n, 6))
            B = (prob.A @ xs.astype(np.float32)).astype(np.float32)
            cache[name] = (prob, B)
        return cache[name]

    return get


def _form_bytes(form: str, p: int, n: int) -> int:
    return J * n * n * 4 if form == "dense" else 2 * J * p * n * 4


@pytest.mark.parametrize(
    "p, n, materialize_p, use_kernels, form",
    [
        (32, 96, None, False, "implicit"),
        (47, 96, None, False, "implicit"),
        (48, 96, None, False, "dense"),
        (2000, 8000, None, False, "implicit"),
        (4000, 8000, None, False, "dense"),
        (48, 32, None, False, "dense"),
        (32, 96, None, True, "kernels"),
        (48, 96, None, True, "dense"),
        (32, 96, True, False, "dense"),
        (48, 96, False, False, "implicit"),
        (48, 96, False, True, "kernels"),
    ],
)
def test_projector_form_rule(p, n, materialize_p, use_kernels, form):
    assert dapc.projector_form(p, n, materialize_p, use_kernels) == form


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_prepare_resolves_form_by_shape(problems, shape):
    prob, _ = problems(shape)
    prep = prepare(prob.A, num_blocks=J)
    _, p, n = prep.blocks.shape
    form = BY_SHAPE[shape]
    assert prep.projector_form == form
    assert prep.projector_bytes == _form_bytes(form, p, n)
    # the implicit form holds no n×n projector: blocks, W and R only
    Ws, Rs = prep.factors
    held = prep.blocks.nbytes + Ws.nbytes + Rs.nbytes
    extra = J * n * n * 4 if form == "dense" else 0
    assert prep.memory_bytes == held + extra


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("materialize_p", [True, False])
def test_explicit_materialize_p_pins_form(problems, shape, materialize_p):
    prob, _ = problems(shape)
    prep = prepare(prob.A, num_blocks=J, materialize_p=materialize_p)
    _, p, n = prep.blocks.shape
    form = "dense" if materialize_p else "implicit"
    assert prep.projector_form == form
    assert prep.projector_bytes == _form_bytes(form, p, n)


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_forms_give_the_same_solve(problems, shape):
    """Both forms are one operator: x agrees to 1e-5 relative and every
    column reaches tolerance within one epoch of the other form; the
    default solver is bit for bit the pinned form it resolved to."""
    prob, B = problems(shape)
    tol = 1e-4 * float(np.linalg.norm(B, axis=0).min())
    res = {
        mp: prepare(prob.A, num_blocks=J, materialize_p=mp).solve(
            B, num_epochs=400, tol=tol
        )
        for mp in (None, True, False)
    }
    dense, implicit = res[True], res[False]
    scale = float(np.abs(dense.x).max())
    assert float(np.abs(dense.x - implicit.x).max()) <= 1e-5 * scale
    it_d, it_i = dense.iterations_to_tol(tol), implicit.iterations_to_tol(tol)
    assert (it_d < 400).all() and np.abs(it_d - it_i).max() <= 1
    pinned = res[BY_SHAPE[shape] == "dense"]
    np.testing.assert_array_equal(res[None].x, pinned.x)


@pytest.mark.parametrize(
    "method, materialize_p, form",
    [
        ("dapc", None, "implicit"),
        ("dapc", True, "dense"),
        ("apc", None, "dense"),
        ("dgd", None, None),
        ("cgnr", None, None),
    ],
)
def test_solve_span_reports_projector(
    problems, profile, method, materialize_p, form
):
    """``repro.solve`` carries ``projector`` and ``projector_bytes`` for
    the consensus methods, and no projector stat where there is none."""
    prob, B = problems("wide_narrow")
    prep = prepare(
        prob.A, num_blocks=J, method=method, materialize_p=materialize_p
    )
    assert prep.projector_form == form
    with profile() as events:
        prep.solve(B, num_epochs=20)
    (stats,) = [e[3] for e in events if e[0] == "repro.solve"]
    if form is None:
        assert "projector" not in stats and "projector_bytes" not in stats
        return
    _, p, n = prep.blocks.shape
    assert stats["projector"] == form
    assert stats["projector_bytes"] == prep.projector_bytes
    assert prep.projector_bytes == _form_bytes(form, p, n)


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_checkpoint_keeps_the_resolved_form(problems, shape, tmp_path):
    """A save/load round trip through the checkpoint store restores the
    form ``prepare`` resolved, and solves bit for bit as the original."""
    prob, B = problems(shape)
    prep = prepare(prob.A, num_blocks=J)
    store = CheckpointStore(tmp_path)
    kw = {"num_blocks": J}
    assert store.save("fp", prep, kw)
    back = store.load("fp", kw)
    assert isinstance(back, PreparedSolver)
    assert back.projector_form == prep.projector_form == BY_SHAPE[shape]
    assert back.projector_bytes == prep.projector_bytes
    np.testing.assert_array_equal(
        back.solve(B, num_epochs=30).x, prep.solve(B, num_epochs=30).x
    )
