"""Classical Accelerated Projection-Based Consensus (Azizan-Ruhi et al. 2017).

The baseline the paper accelerates: per-block setup uses SVD-based
pseudoinverses / Gram-matrix inverses (the exact costs the decomposition
removes), and the projector is materialized densely.

Mirrors dapc's prepare/solve split: ``classical_factors`` (pseudoinverse +
dense projector, b-independent) and ``initial_from_pinv`` (one matmul per
RHS), so classical APC amortizes setup across right-hand sides too — the
amortized baseline the multi-RHS benchmark compares against.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import consensus, projections
from repro.core.partition import Partition

_HIGHEST = jax.lax.Precision.HIGHEST  # full f32 (see repro.core.projections)


@functools.partial(jax.jit, static_argnames=("mode",))
def classical_factors(blocks: jnp.ndarray, mode: str):
    """Per-block (A_j⁺ (J,n,p), P_j (J,n,n)) — the classical setup costs."""
    pinvs = jax.vmap(jnp.linalg.pinv)(blocks)
    Ps = jax.vmap(lambda a: projections.classical_projection(a, mode))(blocks)
    return pinvs, Ps


def initial_from_pinv(pinvs: jnp.ndarray, bvecs: jnp.ndarray) -> jnp.ndarray:
    """x_j(0) = A_j⁺ b_j for one RHS (J, p) or a batch (J, p, k)."""
    return jnp.einsum("jnp,jp...->jn...", pinvs, bvecs, precision=_HIGHEST)


@functools.partial(jax.jit, static_argnames=("mode",))
def setup_classical(blocks: jnp.ndarray, bvecs: jnp.ndarray, mode: str):
    """Per-block (x_j(0), P_j) via pseudoinverse — Algorithm 1 steps 2–3,
    classical variant. Returns (x0s (J,n), Ps (J,n,n))."""
    x0s = jax.vmap(lambda a, b: projections.classical_initial(a, b, mode))(
        blocks, bvecs
    )
    Ps = jax.vmap(lambda a: projections.classical_projection(a, mode))(blocks)
    return x0s, Ps


def make_apply(Ps: jnp.ndarray):
    """Dense projector application, batched over a trailing RHS axis."""
    return lambda v: jnp.einsum(
        "jmn,jn...->jm...", Ps, v, precision=_HIGHEST
    )


def solve_apc(
    part: Partition,
    gamma: float = 1.0,
    eta: float = 0.9,
    num_epochs: int = 100,
    x_ref: jnp.ndarray | None = None,
):
    """Classical APC end-to-end. Returns (x̄, history)."""
    x0s, Ps = setup_classical(part.blocks, part.bvecs, part.mode)
    return consensus.run_consensus(
        x0s,
        make_apply(Ps),
        gamma,
        eta,
        num_epochs,
        x_ref=x_ref,
        blocks=part.blocks,
        bvecs=part.bvecs,
    )
