"""Projection operators onto ``null(A_j)`` — classical vs decomposed forms.

Unified representation: a factor ``W ∈ R^{p×n}`` such that ``P = I_n − WᵀW``.

  * tall blocks (p >= n): ``A_j = Q1_j R_j`` (reduced QR), ``W = Q1_j``
    — exactly the paper's eq. (4) ``P_j = I_n − Q1ᵀQ1``.
  * wide blocks (p < n): ``A_jᵀ = Q_j R_j`` (reduced QR), ``W = Q_jᵀ``
    — ``P_j = I_n − Q Qᵀ``, the same decomposition idea in the regime where
    the nullspace is non-trivial (DESIGN.md §1.1).

``apply_projection`` is the beyond-paper *implicit* application
``P v = v − Wᵀ(W v)`` (never materializes the n×n ``P``); ``materialize``
builds the dense ``P`` exactly as the paper's reference implementation does.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# full-f32 dots: a TPU's default runs an f32 dot as one bf16 pass (~3
# significant digits), below what the consensus tolerances ask for
_HIGHEST = jax.lax.Precision.HIGHEST


def qr_factor(block: jnp.ndarray, mode: str) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Reduced QR per paper eq. (1). Returns (W, R).

    tall: block (p,n) -> Q1 (p,n), R (n,n), W = Q1.
    wide: blockᵀ (n,p) -> Q (n,p), R (p,p), W = Qᵀ (p,n).
    """
    if mode == "tall":
        q, r = jnp.linalg.qr(block, mode="reduced")
        return q, r
    q, r = jnp.linalg.qr(block.mT, mode="reduced")
    return q.mT, r


def apply_projection(W: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """Implicit ``(I − WᵀW) v`` — two tall-skinny matmuls, no n×n temp."""
    return v - jnp.matmul(
        W.mT, jnp.matmul(W, v, precision=_HIGHEST), precision=_HIGHEST
    )


def materialize(W: jnp.ndarray) -> jnp.ndarray:
    """Dense ``P = I − WᵀW`` (paper-faithful; O(n²) memory)."""
    n = W.shape[-1]
    return jnp.eye(n, dtype=W.dtype) - jnp.matmul(W.mT, W, precision=_HIGHEST)


def classical_projection(block: jnp.ndarray, mode: str) -> jnp.ndarray:
    """Inverse-based classical-APC projector (test oracle / baseline).

    wide: P = I − Aᵀ(AAᵀ)⁻¹A. tall: P = I − A⁺A (≈ 0 for full column rank).
    """
    n = block.shape[-1]
    eye = jnp.eye(n, dtype=block.dtype)
    if mode == "wide":
        gram = block @ block.mT
        return eye - block.mT @ jnp.linalg.solve(gram, block)
    return eye - jnp.linalg.pinv(block) @ block


def classical_initial(block: jnp.ndarray, bvec: jnp.ndarray, mode: str) -> jnp.ndarray:
    """Classical init via pseudoinverse (SVD — the cost the paper removes).

    wide: min-norm solution Aᵀ(AAᵀ)⁻¹b; tall: least-squares A⁺b.
    """
    return jnp.linalg.pinv(block) @ bvec
