"""Blocked triangular-substitution Pallas kernel (paper eqs. 2–3).

Solves ``R x = y`` for upper-triangular ``R`` (back-substitution) or
lower-triangular (forward), the O(n²) substitution the paper uses instead of
O(n³) Gauss–Jordan inversion.

TPU adaptation (DESIGN.md §2): plain scalar substitution is
VPU-serial and hostile to the MXU, so we re-block it:

  * grid over ``B×B`` diagonal blocks, iterated in solve order (reverse for
    upper) via the BlockSpec index_map — Pallas TPU grids execute
    sequentially on a core, so a VMEM scratch carries the partial solution
    across steps;
  * vectors are ROWS ``(1, n)``: lane-dense, so the solution scratch costs
    8·n·4 B of VMEM (sublane padding) where an ``(n, 1)`` column would pad
    its lanes to 128·n·4 B;
  * the off-diagonal update ``Σ_{k>i} R[i,k] x[k]`` is one (1 × n)·(n × B)
    MXU matmul of the zero-initialized scratch against the row block
    (uncomputed entries are exactly 0, so no masking is needed);
  * the B×B diagonal block is a ref slice at a B-aligned lane offset, and
    its solve uses log₂B Neumann doublings:
    ``R_d = D(I − M)`` with M strictly triangular (nilpotent, Mᴮ = 0) ⇒
    ``R_d⁻¹ = (Σ_{k<B} Mᵏ) D⁻¹``, and ``Σ Mᵏ`` builds in log₂B squarings —
    7 MXU matmuls for B = 128 instead of B scalar steps.

VMEM per step: the double-buffered row block (2 · B × n · 4 B = 1 KiB · n
for B = 128) plus the scratch, so n up to ~12k fits the 16 MiB default
scoped VMEM of a v5e core.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK = 128


_HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b, acc, contract_rhs: int = 0):
    """a @ b (``contract_rhs=0``) or a @ bᵀ (``contract_rhs=1``), full f32."""
    return jax.lax.dot_general(
        a, b, (((1,), (contract_rhs,)), ((), ())),
        precision=_HIGHEST, preferred_element_type=acc,
    )


def _neumann_tri_solve(rdd: jnp.ndarray, rhs: jnp.ndarray, lower: bool):
    """Solve the B×B triangular diagonal block via log-doubling (all MXU).

    ``rhs`` and the result are (1, B) rows: x = (D⁻¹ rhs)·Sᵀ with
    S = Σ Mᵏ, i.e. the row form of x = S D⁻¹ rhs."""
    b = rdd.shape[0]
    acc = rdd.dtype
    rows = jax.lax.broadcasted_iota(jnp.int32, (b, b), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (b, b), 1)
    on_diag = jnp.where(rows == cols, rdd, 0.0)
    dinv_col = 1.0 / jnp.sum(on_diag, axis=1, keepdims=True)  # (B, 1)
    dinv_row = 1.0 / jnp.sum(on_diag, axis=0, keepdims=True)  # (1, B)
    strict = cols > rows if not lower else cols < rows
    # M = I − D⁻¹R restricted to the strict triangle (nilpotent)
    m = jnp.where(strict, -dinv_col * rdd, 0.0)
    s = (rows == cols).astype(acc)
    p = m
    for _ in range(max(1, (b - 1).bit_length())):  # ⌈log₂B⌉ doublings
        s = s + _mm(p, s, acc)
        p = _mm(p, p, acc)
    return _mm(dinv_row * rhs, s, acc, contract_rhs=1)


def _trisolve_kernel(lower, nb, block, r_ref, y_ref, x_ref, xs_ref):
    """Grid (nb,). r_ref: (B, n) row block in solve order; y_ref/x_ref
    (1, B); xs_ref (1, n) accumulator of the solution so far."""
    g = pl.program_id(0)
    i = g if lower else nb - 1 - g  # solve order → block-row index

    @pl.when(g == 0)
    def _init():
        xs_ref[...] = jnp.zeros_like(xs_ref)

    acc_dtype = xs_ref.dtype  # f32, or f64 when x64 is enabled
    start = pl.multiple_of(i * block, block)
    acc = _mm(xs_ref[...], r_ref[...].astype(acc_dtype), acc_dtype, 1)
    rhs = y_ref[...].astype(acc_dtype) - acc
    rdd = r_ref[:, pl.ds(start, block)].astype(acc_dtype)
    xi = _neumann_tri_solve(rdd, rhs, lower)
    xs_ref[:, pl.ds(start, block)] = xi
    x_ref[...] = xi.astype(x_ref.dtype)


@functools.partial(jax.jit, static_argnames=("lower", "block", "interpret"))
def trisolve_padded(
    r: jnp.ndarray,  # (n_pad, n_pad), n_pad % block == 0, unit-extended diag
    y: jnp.ndarray,  # (1, n_pad)
    lower: bool = False,
    block: int = DEFAULT_BLOCK,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns x (1, n_pad) with R xᵀ = yᵀ."""
    n_pad = r.shape[0]
    if n_pad % block:
        raise ValueError(f"padded size required: {n_pad} % {block}")
    nb = n_pad // block
    rows = (lambda g: (g, 0)) if lower else (lambda g: (nb - 1 - g, 0))
    lanes = (lambda g: (0, g)) if lower else (lambda g: (0, nb - 1 - g))
    return pl.pallas_call(
        functools.partial(_trisolve_kernel, lower, nb, block),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block, n_pad), rows),  # full row block, solve order
            pl.BlockSpec((1, block), lanes),
        ],
        out_specs=pl.BlockSpec((1, block), lanes),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), y.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, n_pad), jnp.promote_types(r.dtype, jnp.float32))
        ],
        interpret=interpret,
    )(r, y)
