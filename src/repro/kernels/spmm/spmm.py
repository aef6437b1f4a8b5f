"""Blocked-ELL SpMM Pallas kernel (the matfree path's A_j x / A_jᵀ y).

Layout (repro.sparse.bsr): per shard j, block-row r stores S dense
``(bp, bn)`` tiles and the column-block id of each (padding slots: id 0,
zero data). The product is

  out[j, r] = Σ_s data[j, r, s] @ x[j, indices[j, r, s]]

TPU mapping: the J·R block-rows are flattened into one row axis and the
tile-id table drives the x ``BlockSpec`` index_map as a SCALAR-PREFETCH
operand (``pltpu.PrefetchScalarGridSpec``), so each grid step's x tile is
DMA'd from its gathered column block — the kernel body never sees the ids.
Scalar prefetch lands in SMEM, which holds about 1 MiB: a real operator's
table (thousands of block-rows × hundreds of slots) cannot sit there
whole. So the rows are cut into chunks of at most ``CHUNK_IDS`` ids and a
``lax.map`` runs one ``pallas_call`` per chunk, each prefetching only its
own slice of the table plus the chunk's first row. Within a chunk the grid
is ``(rows, S)``; the output block (one ``(bp, k)`` row stripe) is revisited
across the s axis (innermost), accumulating in VMEM in f32 and initialized
at s == 0.

Padding slots multiply a zero tile against column block 0 — they add
exactly 0.0, so no masking is needed anywhere. Rows past the end of the
last chunk re-read the final row and are sliced off.

``spmm_fused_padded`` is the projection-epoch variant: the SAME grid pass
additionally takes a row-space operand y (J, R, bp, k) and emits, next to
the accumulated forward product, the per-slot transposed tile products
``data[j, r, s]ᵀ @ y[j, r]`` — the tile is read from VMEM once and feeds
both MXU contractions. The caller scatter-adds the staged (J, R, S, bn, k)
contributions into the column space (``repro.sparse.bsr``), completing
A_jᵀ y without a second pass over the tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# tile ids prefetched per pallas_call: 64 KiB of int32, well inside SMEM
CHUNK_IDS = 1 << 14

_HIGHEST = jax.lax.Precision.HIGHEST


def _dot(a, b):
    return jnp.dot(a, b, precision=_HIGHEST, preferred_element_type=jnp.float32)


def _chunked_rows(indices, x):
    """Flatten the (J, R, S) table to global column-block ids over the
    (J·C, bn, k) stacked x and pad it to whole chunks.

    Returns (ids (chunks, rows_per_chunk·S), rows_per_chunk, J·R)."""
    J, R, S = indices.shape
    C = x.shape[1]
    rows = J * R
    per = max(1, min(rows, CHUNK_IDS // S))
    chunks = -(-rows // per)
    ids = (indices + (jnp.arange(J, dtype=indices.dtype) * C)[:, None, None])
    ids = jnp.pad(ids.reshape(rows, S), ((0, chunks * per - rows), (0, 0)))
    return ids.reshape(chunks, per * S), per, rows


def _map_chunks(call, ids, per):
    """Run ``call(chunk_ids, first_row)`` over every chunk; stack the
    per-chunk outputs back into one row axis."""

    def one(args):
        chunk_ids, c = args
        return call(chunk_ids, (c * per)[None])

    out = jax.lax.map(one, (ids, jnp.arange(ids.shape[0], dtype=jnp.int32)))
    return jax.tree.map(lambda a: a.reshape(-1, *a.shape[2:]), out)


def _spmm_kernel(idx_ref, row0_ref, data_ref, x_ref, o_ref):
    """Grid (rows, S): accumulate one tile product into the row stripe."""
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    w = data_ref[0, 0].astype(jnp.float32)  # (bp, bn)
    xb = x_ref[0].astype(jnp.float32)  # (bn, k)
    o_ref[0] += _dot(w, xb)


@functools.partial(jax.jit, static_argnames=("interpret",))
def spmm_padded(
    indices: jnp.ndarray,  # (J, R, S) int32 column-block ids
    data: jnp.ndarray,  # (J, R, S, bp, bn)
    x: jnp.ndarray,  # (J, C, bn, k) tile view of the column space
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns (J, R, bp, k) f32 — caller reshapes/casts."""
    J, R, S = indices.shape
    bp, bn = data.shape[-2:]
    k = x.shape[-1]
    ids, per, rows = _chunked_rows(indices, x)
    data_f = data.reshape(rows, S, bp, bn)
    x_f = x.reshape(-1, bn, k)

    def row(i, r0):
        return jnp.minimum(r0[0] + i, rows - 1)

    def call(chunk_ids, row0):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(per, S),
            in_specs=[
                pl.BlockSpec(
                    (1, 1, bp, bn), lambda i, s, idx, r0: (row(i, r0), s, 0, 0)
                ),
                pl.BlockSpec(
                    (1, bn, k), lambda i, s, idx, r0: (idx[i * S + s], 0, 0)
                ),
            ],
            out_specs=pl.BlockSpec((1, bp, k), lambda i, s, idx, r0: (i, 0, 0)),
        )
        return pl.pallas_call(
            _spmm_kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((per, bp, k), jnp.float32),
            interpret=interpret,
        )(chunk_ids, row0, data_f, x_f)

    out = _map_chunks(call, ids, per)
    return out[:rows].reshape(J, R, bp, k)


def _spmm_fused_kernel(
    idx_ref, row0_ref, data_ref, x_ref, y_ref, fwd_ref, ctr_ref
):
    """Grid (rows, S): one tile read feeds both MXU contractions.

    The forward row stripe accumulates across the s axis exactly like
    ``_spmm_kernel``; the transposed contribution of this (r, s) tile is
    written once to its own staging slot (no revisit, no accumulation).
    """
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        fwd_ref[...] = jnp.zeros_like(fwd_ref)

    w = data_ref[0, 0].astype(jnp.float32)  # (bp, bn)
    xb = x_ref[0].astype(jnp.float32)  # (bn, k)
    yb = y_ref[0].astype(jnp.float32)  # (bp, k)
    fwd_ref[0] += _dot(w, xb)
    ctr_ref[0, 0] = _dot(w.T, yb)


@functools.partial(jax.jit, static_argnames=("interpret",))
def spmm_fused_padded(
    indices: jnp.ndarray,  # (J, R, S) int32 column-block ids
    data: jnp.ndarray,  # (J, R, S, bp, bn)
    x: jnp.ndarray,  # (J, C, bn, k) tile view of the column space
    y: jnp.ndarray,  # (J, R, bp, k) row-space operand for the A_jᵀ pass
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (fwd (J, R, bp, k), contrib (J, R, S, bn, k)) in f32.

    ``fwd`` is A_j x (padded rows included); ``contrib[j, r, s]`` is
    ``data[j, r, s]ᵀ @ y[j, r]`` awaiting the caller's scatter-add into
    column block ``indices[j, r, s]``.
    """
    J, R, S = indices.shape
    bp, bn = data.shape[-2:]
    k = x.shape[-1]
    ids, per, rows = _chunked_rows(indices, x)
    data_f = data.reshape(rows, S, bp, bn)
    x_f = x.reshape(-1, bn, k)
    y_f = y.reshape(rows, bp, k)

    def row(i, r0):
        return jnp.minimum(r0[0] + i, rows - 1)

    def call(chunk_ids, row0):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(per, S),
            in_specs=[
                pl.BlockSpec(
                    (1, 1, bp, bn), lambda i, s, idx, r0: (row(i, r0), s, 0, 0)
                ),
                pl.BlockSpec(
                    (1, bn, k), lambda i, s, idx, r0: (idx[i * S + s], 0, 0)
                ),
                pl.BlockSpec(
                    (1, bp, k), lambda i, s, idx, r0: (row(i, r0), 0, 0)
                ),
            ],
            out_specs=[
                pl.BlockSpec((1, bp, k), lambda i, s, idx, r0: (i, 0, 0)),
                pl.BlockSpec(
                    (1, 1, bn, k), lambda i, s, idx, r0: (i, s, 0, 0)
                ),
            ],
        )
        return pl.pallas_call(
            _spmm_fused_kernel,
            grid_spec=grid_spec,
            out_shape=(
                jax.ShapeDtypeStruct((per, bp, k), jnp.float32),
                jax.ShapeDtypeStruct((per, S, bn, k), jnp.float32),
            ),
            interpret=interpret,
        )(chunk_ids, row0, data_f, x_f, y_f)

    fwd, contrib = _map_chunks(call, ids, per)
    return (
        fwd[:rows].reshape(J, R, bp, k),
        contrib[:rows].reshape(J, R, S, bn, k),
    )
