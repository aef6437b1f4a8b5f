"""A plain APC solver for square systems too large to densify: the check's
control for the four-chip configuration, whose dense float64 matrix alone
would take 34 GB (n = 65536).

The algorithm is ``chipbench.reference``'s (eqs. 5–7, the same per-column
freeze); only each block's projector is applied in another form,
``d − A_jᵀ (A_j A_jᵀ)⁻¹ A_j d``. The rows ``A_j`` are kept as padded
sparse rows (column indices and values, ``w`` slots a row) and each Gram
inverse dense; both are made on the host in float64 from the sparse core
and held in float32. Every contraction of an epoch (``A_j d``, the Gram
inverse, the residual ``A x̄``) runs at the precision asked for
(``chipbench.reference.contract``); the transposed product ``A_jᵀ y``
scatters single products and has no sum of a contraction to round.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp

from chipbench.reference import _HIGHEST, contract


def factor(core: sp.csr_matrix, num_blocks: int):
    """Padded rows ``(cols (J, p, w), vals (J, p, w))`` of the square
    sparse core and the Gram inverses ``(J, p, p)``, in float64."""
    m, n = core.shape
    if m != n or m % num_blocks:
        raise ValueError(f"a square core in {num_blocks} blocks, not {core.shape}")
    core = sp.csr_matrix(core)
    core.sort_indices()
    counts = np.diff(core.indptr)
    width = int(counts.max())
    row = np.repeat(np.arange(m), counts)
    slot = np.arange(core.nnz) - np.repeat(core.indptr[:-1], counts)
    cols = np.zeros((m, width), np.int32)
    vals = np.zeros((m, width))
    cols[row, slot] = core.indices
    vals[row, slot] = core.data
    p = m // num_blocks
    ginv = np.stack([
        np.linalg.inv((blk @ blk.T).toarray())
        for blk in (core[j * p:(j + 1) * p] for j in range(num_blocks))
    ])
    shape = (num_blocks, p, width)
    return cols.reshape(shape), vals.reshape(shape), ginv


def _rows(cols, vals, v, precision):
    """``A_j v_j`` per block: (J, p, k) from per-block vectors (J, n, k)."""
    picked = jax.vmap(lambda c, vb: vb[c])(cols, v)  # (J, p, w, k)
    return contract("jpw,jpwk->jpk", vals, picked, precision)


def _cols(cols, vals, y, n):
    """``A_jᵀ y_j`` per block: (J, n, k) from (J, p, k)."""
    def one(c, a, yb):
        terms = a[:, :, None] * yb[:, None, :]  # (p, w, k)
        return jnp.zeros((n, yb.shape[-1]), yb.dtype).at[c].add(terms)
    return jax.vmap(one)(cols, vals, y)


@jax.jit
def _start(cols, vals, ginv, bb):
    """Each block's minimum-norm solution ``A_jᵀ (A_j A_jᵀ)⁻¹ b_j``."""
    y = jnp.einsum("jqp,jpk->jqk", ginv, bb, precision=_HIGHEST)
    return _cols(cols, vals, y, cols.shape[0] * cols.shape[1])


def _epochs(cols, vals, ginv, bb, xs, gamma, eta, tol, num_epochs,
            precision):
    J, p, _ = cols.shape
    n = J * p
    flat_cols, flat_vals = cols.reshape(1, n, -1), vals.reshape(1, n, -1)

    def residual_sq(xbar):
        r = _rows(flat_cols, flat_vals, xbar[None], precision)[0]
        r = r - bb.reshape(n, -1)
        return jnp.sum(r * r, axis=0)

    def body(state):
        xs, xbar, rsq, t = state
        active = rsq > tol * tol
        d = xbar[None] - xs
        y = contract("jqp,jpk->jqk", ginv, _rows(cols, vals, d, precision),
                     precision)
        proj = d - _cols(cols, vals, y, n)
        xs_new = jnp.where(active, xs + gamma * proj, xs)
        xbar_new = eta * jnp.mean(xs_new, axis=0) + (1.0 - eta) * xbar
        xbar_new = jnp.where(active, xbar_new, xbar)
        return xs_new, xbar_new, residual_sq(xbar_new), t + 1

    def cond(state):
        return jnp.any(state[2] > tol * tol) & (state[3] < num_epochs)

    xbar = jnp.mean(xs, axis=0)
    xs, xbar, rsq, t = jax.lax.while_loop(
        cond, body, (xs, xbar, residual_sq(xbar), 0)
    )
    return xbar, rsq, t


_epochs_jit = jax.jit(_epochs, static_argnames=("num_epochs", "precision"))


def solve(factors, B: np.ndarray, gamma: float, eta: float, tol: float,
          num_epochs: int, precision: str, chunk: int = 64):
    """APC on the host-made factors for the columns of ``B`` (n, K),
    ``chunk`` columns at a time; a column freezes once its residual is
    within ``tol``. Returns (x (n, K), converged (K,))."""
    cols, vals, ginv = factors
    J, p, _ = cols.shape
    cols = jnp.asarray(cols)
    vals32 = jnp.asarray(vals, jnp.float32)
    ginv32 = jnp.asarray(ginv, jnp.float32)
    xs_out, conv = [], []
    for c0 in range(0, B.shape[1], chunk):
        bb = jnp.asarray(B[:, c0:c0 + chunk].reshape(J, p, -1), jnp.float32)
        xs = _start(cols, vals32, ginv32, bb)
        x, rsq, _ = _epochs_jit(
            cols, vals32, ginv32, bb, xs, jnp.float32(gamma),
            jnp.float32(eta), jnp.float32(tol), num_epochs=num_epochs,
            precision=precision,
        )
        xs_out.append(np.asarray(x))
        conv.append(np.asarray(rsq) <= tol * tol)
    return np.concatenate(xs_out, axis=1), np.concatenate(conv)
