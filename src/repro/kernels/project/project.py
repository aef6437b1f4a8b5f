"""Fused consensus-update Pallas kernel (paper eqs. 4 + 6, implicit P).

Computes ``out = x + γ · (v − Wᵀ(W v))`` with ``v = x̄ − x`` for a single
block's factor ``W ∈ R^{p×n}`` WITHOUT materializing the n×n projector the
paper's reference implementation builds.

TPU mapping: ``n`` (the solution dimension, large) is tiled along lanes in
``TILE_N``-wide VMEM blocks; ``p`` (block rows, small) stays resident. Two
sequential passes over the same tiling:

  pass 1 (``_matvec_kernel``):  u ← Σ_tiles W[:, tile] @ (x̄ − x)[tile]
     — MXU (p × TILE_N)·(TILE_N × 1) matmuls accumulated into a VMEM-resident
       f32 output revisited by every grid step.
  pass 2 (``_update_kernel``):  out[tile] ← x[tile] + γ(v[tile] − W[:,tile]ᵀ u)

Working set per grid step: p·TILE_N weights + O(TILE_N + p) vectors — with
p ≤ 2048, TILE_N = 512, f32: ~4.2 MB ≪ VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_TILE_N = 512


def _matvec_kernel(w_ref, x_ref, xbar_ref, u_ref):
    """Grid (n_tiles,): accumulate u = W (x̄ − x) into the revisited block."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        u_ref[...] = jnp.zeros_like(u_ref)

    v = (xbar_ref[...] - x_ref[...]).astype(jnp.float32)
    u_ref[...] += jnp.dot(
        w_ref[...].astype(jnp.float32), v,
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )


def _update_kernel(gamma, w_ref, x_ref, xbar_ref, u_ref, o_ref):
    """Grid (n_tiles,): out = x + γ(v − W[:,tile]ᵀ u)."""
    x = x_ref[...].astype(jnp.float32)
    v = xbar_ref[...].astype(jnp.float32) - x
    proj = jnp.dot(
        w_ref[...].astype(jnp.float32).T, u_ref[...],
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )
    o_ref[...] = (x + gamma * (v - proj)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("gamma", "tile_n", "interpret")
)
def consensus_update_padded(
    w: jnp.ndarray,  # (p_pad, n_pad) — p_pad % 128 == 0, n_pad % tile_n == 0
    x: jnp.ndarray,  # (n_pad, 1)
    xbar: jnp.ndarray,  # (n_pad, 1)
    gamma: float,
    tile_n: int = DEFAULT_TILE_N,
    interpret: bool = False,
) -> jnp.ndarray:
    p_pad, n_pad = w.shape
    if n_pad % tile_n or p_pad % 8:
        raise ValueError(f"padded shapes required, got {w.shape} tile_n={tile_n}")
    n_tiles = n_pad // tile_n

    u = pl.pallas_call(
        _matvec_kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((p_pad, tile_n), lambda i: (0, i)),
            pl.BlockSpec((tile_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((tile_n, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((p_pad, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((p_pad, 1), jnp.float32),
        interpret=interpret,
    )(w, x, xbar)

    return pl.pallas_call(
        functools.partial(_update_kernel, float(gamma)),
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((p_pad, tile_n), lambda i: (0, i)),
            pl.BlockSpec((tile_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((tile_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((p_pad, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tile_n, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, 1), x.dtype),
        interpret=interpret,
    )(w, x, xbar, u)
