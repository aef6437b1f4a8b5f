"""Decomposed APC — THE PAPER's contribution (Algorithm 1).

Setup replaces every inversion with reduced QR + triangular substitution:
  eq. (1)  A_j = Q1_j R_j           (reduced QR)
  eq. (2–3) x_j(0) by back-substitution on R_j      — O(n²) not O(n³)
  eq. (4)  P_j = I − Q1ᵀQ1          (projector from the orthogonal factor)
The consensus iteration (eqs. 5–7) is unchanged from classical APC.

The setup is split along its data dependencies so the prepare/solve API can
amortize it across right-hand sides:
  * ``qr_blocks``            — eq. (1)/(4) factors (W_j, R_j); depends on A only.
  * ``initial_from_factors`` — eq. (2–3) substitution; the only b-dependent
    step, O(n²) per block, and batched over a trailing RHS axis.
``setup_decomposed`` composes the two (the original single-shot path).

Two projector forms:
  * materialized — paper-faithful: dense n×n P_j built per block.
  * implicit — beyond-paper: P v = v − Wᵀ(W v) (two tall-skinny MXU
    matmuls; O(np) memory; README §Solver API).
``projector_form`` picks the one that reads fewer bytes an epoch from the
block shape; ``materialize_p=True``/``False`` pins it (``solve_dapc``, the
paper-faithful one-shot path, pins the materialized form by default).
``use_kernels=True`` routes the triangular solve and the fused consensus
update through the Pallas TPU kernels (interpret mode on CPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

from repro.core import consensus, projections
from repro.core.partition import Partition

_HIGHEST = jax.lax.Precision.HIGHEST  # full f32 (see repro.core.projections)

# observability for the prepare/solve split: how many times the QR setup
# (the cost prepare() exists to amortize) actually ran in this process
SETUP_STATS = {"qr_calls": 0}


@functools.partial(jax.jit, static_argnames=("mode",))
def _qr_blocks_jit(blocks: jnp.ndarray, mode: str):
    return jax.vmap(lambda a: projections.qr_factor(a, mode))(blocks)


def qr_blocks(blocks: jnp.ndarray, mode: str):
    """Paper eq. (1)/(4): per-block reduced QR. Returns (Ws (J,p,n), Rs).

    ``Rs`` is (J, n, n) in the tall regime, (J, p, p) in the wide regime.
    b-independent — this is the factorization ``prepare()`` caches.
    """
    SETUP_STATS["qr_calls"] += 1
    return _qr_blocks_jit(blocks, mode)


def _trisolve(r, y, lower: bool, use_kernels: bool):
    """Triangular solve of (n, n) against (n,) or a batched (n, k)."""
    if not use_kernels:
        return solve_triangular(r, y, lower=lower)
    from repro.kernels.trisolve import ops as trisolve_ops

    if y.ndim == 1:
        return trisolve_ops.trisolve(r, y, lower=lower)
    return jax.vmap(
        lambda col: trisolve_ops.trisolve(r, col, lower=lower),
        in_axes=1, out_axes=1,
    )(y)


@functools.partial(jax.jit, static_argnames=("mode", "use_kernels"))
def initial_from_factors(
    Ws: jnp.ndarray,
    Rs: jnp.ndarray,
    bvecs: jnp.ndarray,  # (J, p) or (J, p, k)
    mode: str,
    use_kernels: bool = False,
):
    """Paper eqs. (2–3): x_j(0) by substitution on cached factors.

    tall: x0 = R⁻¹ Q1ᵀ b (back-substitution); wide: min-norm x0 = Q R⁻ᵀ b
    (forward substitution). Batched over a trailing RHS axis: bvecs
    (J, p, k) → x0s (J, n, k).
    """
    if mode == "tall":
        y = jnp.einsum("jpn,jp...->jn...", Ws, bvecs, precision=_HIGHEST)  # Q1ᵀ b
        return jax.vmap(lambda r, yy: _trisolve(r, yy, False, use_kernels))(Rs, y)
    z = jax.vmap(lambda r, b: _trisolve(r.mT, b, True, use_kernels))(Rs, bvecs)
    return jnp.einsum("jpn,jp...->jn...", Ws, z, precision=_HIGHEST)  # Qᵀᵀ z = Q z


def setup_decomposed(
    blocks: jnp.ndarray, bvecs: jnp.ndarray, mode: str, use_kernels: bool = False
):
    """Algorithm 1 steps 2–3, decomposed. Returns (x0s (J,n), Ws (J,p,n))."""
    Ws, Rs = qr_blocks(blocks, mode)
    x0s = initial_from_factors(Ws, Rs, bvecs, mode, use_kernels)
    return x0s, Ws


def projector_form(
    p: int, n: int, materialize_p: bool | None = None, use_kernels: bool = False
) -> str:
    """How an epoch applies P_j: ``"dense"`` (the materialized n×n P_j) or
    the factored v − Wᵀ(W v), ``"implicit"`` or ``"kernels"`` (Pallas).

    ``materialize_p=None`` takes the form that reads fewer bytes an epoch:
    the factored form reads the p×n W_j twice, P_j is n×n, so it wins when
    2p < n (wide blocks; FLOPs are in the same ratio, at every batch
    width). ``True``/``False`` pin the form.
    """
    if materialize_p is None:
        materialize_p = 2 * p >= n
    if materialize_p:
        return "dense"
    return "kernels" if use_kernels else "implicit"


def make_apply(Ws: jnp.ndarray, materialize_p: bool, use_kernels: bool = False):
    """Projector application for a (J, n) or batched (J, n, k) consensus
    difference — the batched form feeds the MXU with (p,n)×(n,k) matmuls."""
    if materialize_p:
        Ps = jax.vmap(projections.materialize)(Ws)  # paper-faithful dense P_j
        return lambda v: jnp.einsum(
            "jmn,jn...->jm...", Ps, v, precision=_HIGHEST
        )
    if use_kernels:
        from repro.kernels.project import ops as project_ops

        def project_one(w, v):  # v (n,) or (n, k)
            if v.ndim == 1:
                return project_ops.project(w, v)
            return jax.vmap(
                lambda col: project_ops.project(w, col), in_axes=1, out_axes=1
            )(v)

        return lambda v: jax.vmap(project_one)(Ws, v)
    return lambda v: v - jnp.einsum(
        "jpn,jp...->jn...", Ws,
        jnp.einsum("jpn,jn...->jp...", Ws, v, precision=_HIGHEST),
        precision=_HIGHEST,
    )


def solve_dapc(
    part: Partition,
    gamma: float = 1.0,
    eta: float = 0.9,
    num_epochs: int = 100,
    x_ref: jnp.ndarray | None = None,
    materialize_p: bool = True,
    use_kernels: bool = False,
    avg_every: int = 1,
    compress: str | None = None,
    xbar0: jnp.ndarray | None = None,
):
    """Decomposed APC end-to-end (paper Algorithm 1). Returns (x̄, history)."""
    x0s, Ws = setup_decomposed(part.blocks, part.bvecs, part.mode, use_kernels)
    apply_fn = make_apply(Ws, materialize_p, use_kernels)
    return consensus.run_consensus(
        x0s,
        apply_fn,
        gamma,
        eta,
        num_epochs,
        x_ref=x_ref,
        blocks=part.blocks,
        bvecs=part.bvecs,
        avg_every=avg_every,
        compress=compress,
        xbar0=xbar0,
    )
