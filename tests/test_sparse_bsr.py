"""Blocked-ELL / BSR format tests: conversion, slicing, products, edges.

Covers the ISSUE 3 satellite cases explicitly — negative-index validation
in COOMatrix, empty row blocks, and single-nnz blocks — plus property-style
conversion roundtrips across shapes and block sizes via ``repro.testing``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.sparse import COOMatrix, generate_schenk_like
from repro.sparse.bsr import (
    BlockEll,
    PartitionedBSR,
    _ell_matmul_stacked,
    _pad_cols,
)
from repro.testing import given, settings, st


def _random_coo(m, n, density, seed):
    rng = np.random.default_rng(seed)
    nnz = max(int(density * m * n), 1)
    rows = rng.integers(0, m, size=nnz).astype(np.int32)
    cols = rng.integers(0, n, size=nnz).astype(np.int32)
    # dedupe so COO scatter and blocked scatter agree exactly
    key = rows.astype(np.int64) * n + cols
    _, keep = np.unique(key, return_index=True)
    vals = rng.standard_normal(keep.size)
    return COOMatrix(rows[keep], cols[keep], vals, (m, n))


def test_coo_rejects_negative_indices():
    """Regression: rows.min() < 0 used to scatter silently from the end."""
    with pytest.raises(ValueError, match="negative"):
        COOMatrix(
            np.array([-1], np.int32), np.array([0], np.int32),
            np.array([1.0]), (4, 4),
        )
    with pytest.raises(ValueError, match="negative"):
        COOMatrix(
            np.array([0], np.int32), np.array([-2], np.int32),
            np.array([1.0]), (4, 4),
        )


@settings(max_examples=10)
@given(
    st.integers(min_value=1, max_value=90),
    st.integers(min_value=1, max_value=90),
    st.integers(min_value=0, max_value=3),
)
def test_blockell_roundtrip_property(m, n, seed):
    coo = _random_coo(m, n, density=0.05, seed=seed)
    for bshape in ((8, 8), (4, 16), (8, 128)):
        be = BlockEll.from_coo(coo, bshape)
        np.testing.assert_allclose(be.to_dense(), coo.to_dense())


def test_blockell_empty_matrix():
    """No nonzeros at all: one zero padding slot per block-row, zero dense."""
    coo = COOMatrix(
        np.empty(0, np.int32), np.empty(0, np.int32), np.empty(0), (20, 12)
    )
    be = BlockEll.from_coo(coo, (8, 8))
    assert be.slots == 1
    np.testing.assert_array_equal(be.to_dense(), 0.0)
    # and an empty-slice matmul returns exact zeros
    out = be.matmul(jnp.ones((12, 3), jnp.float32))
    np.testing.assert_array_equal(np.asarray(out), 0.0)


def test_blockell_single_nnz():
    """One entry: exactly one real tile; everything else stays padding."""
    coo = COOMatrix(
        np.array([13], np.int32), np.array([21], np.int32),
        np.array([2.5]), (32, 32),
    )
    be = BlockEll.from_coo(coo, (8, 8))
    dense = be.to_dense()
    assert dense[13, 21] == 2.5
    assert np.count_nonzero(dense) == 1
    assert np.count_nonzero(np.asarray(be.data)) == 1


def test_blockell_row_block_slicing_matches_dense():
    coo = _random_coo(64, 40, density=0.1, seed=7)
    be = BlockEll.from_coo(coo, (8, 8))
    dense = coo.to_dense()
    for start, stop in ((0, 16), (16, 48), (56, 64)):
        sl = be.slice_row_blocks(start, stop)
        np.testing.assert_allclose(sl.to_dense(), dense[start:stop])
    with pytest.raises(ValueError, match="multiples"):
        be.slice_row_blocks(4, 12)
    with pytest.raises(ValueError, match="out of range"):
        be.slice_row_blocks(0, 128)


def _dense_blocks(coo, J, p, p_pad, dtype=np.float32):
    """Zero-padded (J, p_pad, n) dense oracle of the partition layout."""
    A = coo.to_dense().astype(dtype)
    blocks = np.zeros((J, p_pad, coo.shape[1]), dtype)
    for j in range(J):
        rows = A[j * p:(j + 1) * p]
        blocks[j, : rows.shape[0]] = rows
    return blocks


@pytest.mark.parametrize("num_blocks", [1, 3, 8])
def test_partitioned_products_match_dense(num_blocks):
    coo = generate_schenk_like(100, sparsity=0.96, seed=2)
    op = PartitionedBSR.from_coo(coo, num_blocks, (8, 8), with_gram=True)
    blocks = _dense_blocks(coo, num_blocks, op.p, op.p_pad)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((100, 4)).astype(np.float32))
    got = np.asarray(op.matvec(x))
    np.testing.assert_allclose(
        got, np.einsum("jpn,nk->jpk", blocks, np.asarray(x)), atol=1e-4
    )
    y = jnp.asarray(
        rng.standard_normal((num_blocks, op.p_pad, 4)).astype(np.float32)
    )
    np.testing.assert_allclose(
        np.asarray(op.rmatvec(y)),
        np.einsum("jpn,jpk->jnk", blocks, np.asarray(y)),
        atol=1e-4,
    )
    np.testing.assert_allclose(
        np.asarray(op.gram_mv(y)),
        np.einsum("jpn,jqn,jqk->jpk", blocks, blocks, np.asarray(y)),
        rtol=1e-5, atol=1e-2,
    )
    np.testing.assert_allclose(
        np.asarray(op.gram_diag()),
        np.einsum("jpn,jpn->jp", blocks, blocks),
        rtol=1e-5, atol=1e-2,
    )


def test_partitioned_empty_row_block():
    """A partition block with zero nonzeros must still convert and multiply
    (its products are exactly zero)."""
    # all entries in rows < 25: blocks 2 and 3 of a 4-way split are empty
    coo = _random_coo(25, 48, density=0.1, seed=11)
    coo = COOMatrix(coo.rows, coo.cols, coo.vals, (100, 48))
    op = PartitionedBSR.from_coo(coo, 4, (8, 8), with_gram=True)
    x = jnp.ones((48, 2), jnp.float32)
    out = np.asarray(op.matvec(x))
    np.testing.assert_array_equal(out[2:], 0.0)
    assert np.abs(out[0]).max() > 0
    y = jnp.ones((4, op.p_pad, 2), jnp.float32)
    np.testing.assert_array_equal(np.asarray(op.gram_mv(y))[2:], 0.0)


def test_partitioned_single_nnz_block():
    coo = COOMatrix(
        np.array([30], np.int32), np.array([5], np.int32),
        np.array([3.0]), (64, 16),
    )
    op = PartitionedBSR.from_coo(coo, 4, (8, 8))
    x = jnp.asarray(np.eye(16, dtype=np.float32))
    out = np.asarray(op.matvec(x))  # (4, p_pad, 16)
    j, local = 30 // op.p, 30 % op.p
    assert out[j, local, 5] == 3.0
    assert np.count_nonzero(out) == 1


def test_duplicate_coordinates_resolve_last_wins_everywhere():
    """Regression: duplicates must resolve identically (last-wins, matching
    COOMatrix.to_dense) in the forward shards AND the Gram shards — the
    Gram builder sums per-coordinate contributions, so an up-front dedupe
    is what keeps the inner-CG operator consistent with A_j."""
    coo = COOMatrix(
        np.array([0, 0, 1], np.int32), np.array([0, 0, 1], np.int32),
        np.array([5.0, 2.0, 3.0]), (8, 8),
    )
    op = PartitionedBSR.from_coo(coo, 1, (8, 8), with_gram=True)
    dense = coo.to_dense()  # A[0,0] == 2.0 (last wins)
    assert dense[0, 0] == 2.0
    y = jnp.asarray(np.eye(8, dtype=np.float32)[None])
    np.testing.assert_allclose(
        np.asarray(op.gram_mv(y))[0], dense @ dense.T, atol=1e-5
    )
    x = jnp.asarray(np.eye(8, dtype=np.float32))
    np.testing.assert_allclose(np.asarray(op.matvec(x))[0], dense, atol=1e-5)


def test_transpose_shards_match_scatter_path():
    coo = generate_schenk_like(80, sparsity=0.95, seed=4)
    plain = PartitionedBSR.from_coo(coo, 4, (8, 8))
    withT = PartitionedBSR.from_coo(coo, 4, (8, 8), with_transpose=True)
    assert plain.tra_indices is None and withT.tra_indices is not None
    assert plain.nbytes < withT.nbytes  # the default really is leaner
    rng = np.random.default_rng(5)
    y = jnp.asarray(rng.standard_normal((4, plain.p_pad, 3)).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(plain.rmatvec(y)), np.asarray(withT.rmatvec(y)), atol=1e-4
    )


def test_block_rhs_layout():
    coo = generate_schenk_like(50, sparsity=0.9, seed=6)
    op = PartitionedBSR.from_coo(coo, 4, (8, 8))  # p=13 -> p_pad=16
    b = np.arange(50, dtype=np.float32)
    out = np.asarray(op.block_rhs(b))
    assert out.shape == (4, op.p_pad, 1)
    for j in range(4):
        seg = b[j * op.p:(j + 1) * op.p]
        np.testing.assert_array_equal(out[j, : seg.size, 0], seg)
        np.testing.assert_array_equal(out[j, seg.size:, 0], 0.0)


# -- balance permutation (ISSUE 4) -------------------------------------------


@settings(max_examples=10, deadline=None)
@given(
    st.integers(min_value=16, max_value=120),
    st.integers(min_value=1, max_value=4),
    st.integers(min_value=0, max_value=4),
)
def test_balanced_products_match_unbalanced_property(m, k, seed):
    """The balance permutation must be externally invisible: matvec /
    rmatvec / gram_mv of the permuted operator agree with the unpermuted
    one to 1e-6 (ISSUE 4 satellite)."""
    coo = _random_coo(m, m, density=0.08, seed=seed)
    plain = PartitionedBSR.from_coo(coo, 2, (8, 8), with_gram=True)
    bal = PartitionedBSR.from_coo(coo, 2, (8, 8), with_gram=True, balance=True)
    rng = np.random.default_rng(seed + 50)
    x = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal((2, plain.p_pad, k)).astype(np.float32))
    for name, a, b in (
        ("matvec", plain.matvec(x), bal.matvec(x)),
        ("rmatvec", plain.rmatvec(y), bal.rmatvec(y)),
        ("gram_mv", plain.gram_mv(y), bal.gram_mv(y)),
        ("gram_diag", plain.gram_diag(), bal.gram_diag()),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-6, rtol=1e-6,
            err_msg=name,
        )


def test_balance_never_pads_more_slots():
    """The local search starts from the identity grouping, so the balanced
    layout can never be WORSE than the unbalanced one."""
    for seed in range(3):
        coo = generate_schenk_like(256, sparsity=0.985, seed=seed)
        plain = PartitionedBSR.from_coo(coo, 4, (8, 8))
        bal = PartitionedBSR.from_coo(coo, 4, (8, 8), balance=True)
        assert bal.slot_occupancy()[0] <= plain.slot_occupancy()[0]


def test_balance_tightens_slots_on_schenk_bench_matrix():
    """ISSUE 4 acceptance: ELL slots within 1.2x of the per-block-row mean
    on the (paper-scale) Schenk-like bench matrix — was 1.5-2x unbalanced."""
    coo = generate_schenk_like(2327, sparsity=0.9985, seed=5)
    plain = PartitionedBSR.from_coo(coo, 8, (8, 8))
    bal = PartitionedBSR.from_coo(coo, 8, (8, 8), balance=True)
    s0, m0 = plain.slot_occupancy()
    s1, m1 = bal.slot_occupancy()
    assert s0 / m0 >= 1.5  # the problem the permutation exists to fix
    assert s1 / m1 <= 1.2
    assert s1 < s0


def test_balanced_pytree_roundtrip_through_jit():
    """The permutation arrays ride the pytree: a balanced operator passed
    as a jit OPERAND keeps its external product contract (ISSUE 4
    satellite)."""
    coo = generate_schenk_like(96, sparsity=0.95, seed=7)
    bal = PartitionedBSR.from_coo(
        coo, 4, (8, 8), with_gram=True, balance=True
    )
    leaves, treedef = jax.tree_util.tree_flatten(bal)
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert np.asarray(rebuilt.ext_pos).shape == (4, bal.p_pad)
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.standard_normal((96, 3)).astype(np.float32))

    @jax.jit
    def through(op, x):
        return op.matvec(x), op.rmatvec(op.matvec(x)), op.gram_diag()

    got_mv, got_rmv, got_diag = through(bal, x)
    plain = PartitionedBSR.from_coo(coo, 4, (8, 8), with_gram=True)
    np.testing.assert_allclose(
        np.asarray(got_mv), np.asarray(plain.matvec(x)), atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(got_rmv),
        np.asarray(plain.rmatvec(plain.matvec(x))), rtol=1e-5, atol=1e-4,
    )
    np.testing.assert_allclose(
        np.asarray(got_diag), np.asarray(plain.gram_diag()), atol=1e-4
    )


def test_fused_project_matches_separate_products():
    """One tile pass == the two separate contractions, balanced or not."""
    coo = generate_schenk_like(100, sparsity=0.96, seed=2)
    rng = np.random.default_rng(3)
    for balance in (False, True):
        op = PartitionedBSR.from_coo(coo, 4, (8, 8), balance=balance)
        x = jnp.asarray(rng.standard_normal((100, 4)).astype(np.float32))
        y = jnp.asarray(
            rng.standard_normal((4, op.p_pad, 4)).astype(np.float32)
        )
        f, g = op.fused_project(x, y)
        np.testing.assert_allclose(
            np.asarray(f), np.asarray(op.matvec(x)), atol=1e-5
        )
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(op.rmatvec(y)), atol=1e-5
        )


def test_jacobi_weights_relative_clamp():
    """ISSUE 4 satellite: near-zero but NONZERO Gram diagonals must not
    explode the Jacobi weights on badly scaled matrices; exactly-zero
    (padding) diagonals still weigh 0."""
    # one well-scaled row, one tiny-but-nonzero row, padding rows
    coo = COOMatrix(
        np.array([0, 1], np.int32), np.array([0, 1], np.int32),
        np.array([1.0, 1e-18]), (4, 8),
    )
    op = PartitionedBSR.from_coo(coo, 1, (8, 8), with_gram=True)
    w = np.asarray(op.jacobi_weights())[0, :, 0]
    diag = np.asarray(op.gram_diag())[0]
    assert diag[1] > 0  # genuinely nonzero, would have exploded pre-fix
    assert np.isfinite(w).all()
    # clamp: bounded by 1 / (max_diag * eps) instead of 1 / 1e-36
    assert w[1] <= 1.0 / (diag.max() * 1e-10) * (1 + 1e-6)
    np.testing.assert_array_equal(w[2:], 0.0)  # padding rows stay pinned


# -- Gram form: diagonals or blocked ELL, chosen from the Gram's structure --


def _hpcg_coo(nx, ny, nz):
    from conftest import hpcg_stencil

    A = hpcg_stencil(nx, ny, nz).tocoo()
    return COOMatrix(
        A.row.astype(np.int32), A.col.astype(np.int32), A.data, A.shape
    )


GRAM_CASES = {
    # HPCG z-slab blocks: two end blocks and an interior one
    "hpcg_slabs": (lambda: _hpcg_coo(6, 6, 12), 3, "dia"),
    # 214-row blocks padded to 216 (the last holds 212 rows)
    "hpcg_padded": (lambda: _hpcg_coo(8, 8, 10), 3, "dia"),
    # an unstructured matrix: its offsets outnumber the ELL slots of a row
    "random": (lambda: _random_coo(96, 96, density=0.08, seed=3), 2, "ell"),
}


@pytest.mark.parametrize("k", [1, 8])
@pytest.mark.parametrize("case", sorted(GRAM_CASES))
def test_gram_form_products(case, k):
    """The stored Gram form follows the matrix: a stencil's Gram goes by
    its diagonals and matches the ELL product and a float64 A_j A_jᵀ y; an
    unstructured Gram keeps the ELL shards the parent built, and their
    product bit for bit."""
    make, J, form = GRAM_CASES[case]
    coo = make()
    op = PartitionedBSR.from_coo(coo, J, (8, 8), with_gram=True)
    # the kernel layout always streams ELL tiles: the parent's Gram shards
    ell = PartitionedBSR.from_coo(
        coo, J, (8, 8), with_gram=True, with_transpose=True
    )
    assert op.gram_form == form and ell.gram_form == "ell"
    if case == "hpcg_padded":
        assert op.p_pad > op.p
    rng = np.random.default_rng(k)
    y = jnp.asarray(
        rng.standard_normal((J, op.p_pad, k)).astype(np.float32)
    )
    blocks = _dense_blocks(coo, J, op.p, op.p_pad, dtype=np.float64)
    want = np.einsum(
        "jpn,jqn,jqk->jpk", blocks, blocks, np.asarray(y, np.float64)
    )
    got = np.asarray(op.gram_mv(y), np.float64)
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 1e-6 * scale
    np.testing.assert_array_equal(op.gram_dense(), ell.gram_dense())
    if form == "dia":
        assert len(op.gram_offsets) <= ell.gram_indices.shape[-1] * 8
        assert op.gram_bytes < ell.gram_bytes
        ell_out = np.asarray(ell.gram_mv(y), np.float64)
        assert np.abs(got - ell_out).max() <= 1e-6 * scale
    else:
        assert op.gram_offsets is None and op.gram_diags is None
        np.testing.assert_array_equal(op.gram_indices, ell.gram_indices)
        np.testing.assert_array_equal(op.gram_data, ell.gram_data)
        yb = jax.vmap(lambda v: _pad_cols(v, op.p_pad, 8))(y)
        np.testing.assert_array_equal(
            np.asarray(op.gram_mv(y)),
            np.asarray(_ell_matmul_stacked(op.gram_indices, op.gram_data, yb)),
        )


def test_dia_gram_roundtrips():
    """to_arrays/from_arrays and a pytree round trip keep the diagonal
    form and its static offsets; as a jit operand the rebuilt operator
    gives the original's product bit for bit."""
    op = PartitionedBSR.from_coo(_hpcg_coo(6, 6, 12), 3, with_gram=True)
    assert op.gram_form == "dia"
    y = jnp.asarray(
        np.random.default_rng(0).standard_normal((3, op.p_pad, 4)),
        jnp.float32,
    )
    def product(o):
        return jax.jit(lambda o, v: o.gram_mv(v))(o, y)

    want = np.asarray(product(op))
    np.testing.assert_allclose(
        np.asarray(op.gram_mv(y)), want, rtol=1e-6, atol=1e-6 * np.abs(want).max()
    )
    arrays, meta = op.to_arrays()
    assert "op_gram_offsets" not in arrays and "op_gram_diags" in arrays
    restored = PartitionedBSR.from_arrays(arrays, meta)
    leaves, treedef = jax.tree_util.tree_flatten(op)
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    for back in (restored, rebuilt):
        assert back.gram_form == "dia"
        assert back.gram_offsets == op.gram_offsets
        np.testing.assert_array_equal(np.asarray(product(back)), want)
