"""Device-resident blocked-sparse (blocked-ELL / BSR) format.

The dense path decompresses every row block to a dense ``(p, n)`` array
before QR (``COOMatrix.row_block``), so its memory scales as O(J·p·n)
regardless of sparsity — at the paper's Schenk_IBMNA sparsity (~99.85%)
that is ~700x more than the nonzeros need. This module keeps the matrix
blocked-sparse ON DEVICE:

  * ``BlockEll`` — a padded blocked-ELL layout: the rows are cut into
    ``bp``-row block-rows, each storing a fixed number ``S`` of dense
    ``(bp, bn)`` tiles plus the column-block index of every tile.
    ``S`` is the maximum tile count over block-rows; short rows are padded
    with index-0 tiles whose data is all zero, so padding contributes
    nothing to a product (padding-aware indexing, no masks needed).
  * ``BlockEll.slice_row_blocks`` — per-row-block slicing as a pure array
    slice of ``(indices, data)``; a worker's shard is carved out without
    ever materializing a dense block.
  * ``PartitionedBSR`` — the J-way row partition of a ``COOMatrix`` as
    stacked blocked-ELL shards for A_j and A_jᵀ, with the SpMM/SpMV
    contractions (gather + einsum by default, the Pallas kernel under
    ``use_kernels=True``) that the matrix-free solver builds its
    projections from (``repro.core.matfree``).

The uniform partition pads each block to ``p_pad`` rows with ZERO rows
(b is padded with zeros at the same positions): a zero row is the trivially
consistent equation 0·x = 0, so the block's solution set — and therefore
its projection — is unchanged, and no dense mixing rows are needed.

``from_coo(..., balance=True)`` additionally reorders the rows WITHIN each
partition block before tiling, packing rows that share column blocks into
the same ``bp``-row block-row so the slot count ``S`` (a max over
block-rows) tightens toward the mean. The permutation is applied purely
internally: ``matvec``/``rmatvec``/``fused_project`` translate between the
external (original) row order and the internal (balanced) tile layout, so
every public product — and therefore the solver contract — is bit-for-bit
order-identical to the unbalanced operator.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.sparse.matrix import COOMatrix

_HIGHEST = jax.lax.Precision.HIGHEST  # full f32 (see repro.core.projections)

DEFAULT_BLOCK_SHAPE = (8, 8)


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _ell_arrays(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    m: int,
    n: int,
    bp: int,
    bn: int,
    dtype,
) -> tuple[np.ndarray, np.ndarray]:
    """Host-side COO -> blocked-ELL (indices (R, S), data (R, S, bp, bn)).

    ``S`` is max(nonzero tiles per block-row, 1) — even an all-zero matrix
    keeps one (zero) padding slot so downstream shapes stay static.
    Duplicate (row, col) entries resolve last-wins, matching
    ``COOMatrix.to_dense``'s scatter semantics.
    """
    R, C = _ceil_div(m, bp), _ceil_div(n, bn)
    if rows.size == 0:  # empty (or empty-slice) matrix: one zero pad slot
        return (
            np.zeros((R, 1), np.int32),
            np.zeros((R, 1, bp, bn), dtype),
        )
    br, bc = rows // bp, cols // bn
    order = np.lexsort((cols, rows))  # stable: later duplicates win
    rows, cols, vals = rows[order], cols[order], vals[order]
    br, bc = br[order], bc[order]
    key = br.astype(np.int64) * C + bc
    ukey, inv = np.unique(key, return_inverse=True)
    ubr, ubc = (ukey // C).astype(np.int64), (ukey % C).astype(np.int64)
    per_row = np.bincount(ubr, minlength=R)
    starts = np.concatenate(([0], np.cumsum(per_row)))[:-1]
    slot = np.arange(ukey.size) - starts[ubr]  # rank of tile within its row
    S = max(int(per_row.max()), 1)
    indices = np.zeros((R, S), np.int32)
    indices[ubr, slot] = ubc
    data = np.zeros((R, S, bp, bn), dtype)
    data[br, slot[inv], rows % bp, cols % bn] = vals
    return indices, data


def _balance_perm(
    local: np.ndarray,  # entry rows, external padded-local ids in [0, p_pad)
    col_blocks: np.ndarray,  # entry column-block ids
    p_pad: int,
    bp: int,
    max_sweeps: int = 50,
) -> np.ndarray:
    """Row order tightening the blocked-ELL slot count of ONE partition block.

    ``S`` is max over block-rows ("bins" of ``bp`` rows) of the number of
    DISTINCT column blocks the bin's rows touch. The identity order is
    already a strong clustering for diagonal-ridge matrices (consecutive
    rows share their diagonal column block), so instead of rebuilding the
    grouping from scratch this runs steepest-descent row SWAPS from the
    identity: every bin sitting at the current maximum tries the exchange
    that pulls BOTH affected bins strictly below it (ties broken toward
    the fewest total tiles), and the max ratchets down until no heavy bin
    can shed a tile. The result can therefore never pad more slots than
    the unbalanced layout.

    Returns ``ext_pos`` (p_pad,) int32: the external row occupying each
    internal position.
    """
    nbins = p_pad // bp
    row_tiles: dict[int, frozenset] = {}
    for r, c in zip(local.tolist(), col_blocks.tolist()):
        row_tiles.setdefault(r, set()).add(c)  # type: ignore[arg-type]
    row_tiles = {r: frozenset(t) for r, t in row_tiles.items()}
    empty = frozenset()
    tiles_of = [row_tiles.get(r, empty) for r in range(p_pad)]

    members = [list(range(b * bp, (b + 1) * bp)) for b in range(nbins)]
    # per-bin tile -> number of member rows carrying it (multiplicity lets a
    # candidate removal know which tiles it would actually free)
    mult: list[dict] = []
    for b in range(nbins):
        m: dict = {}
        for r in members[b]:
            for t in tiles_of[r]:
                m[t] = m.get(t, 0) + 1
        mult.append(m)
    counts = [len(m) for m in mult]

    def swap_delta(b1, r1, b2, r2):
        """Bin tile counts after exchanging r1 (in b1) with r2 (in b2)."""
        t1, t2 = tiles_of[r1], tiles_of[r2]
        gone1 = sum(1 for t in t1 if mult[b1][t] == 1 and t not in t2)
        new1 = sum(1 for t in t2 if t not in mult[b1] and t not in t1)
        gone2 = sum(1 for t in t2 if mult[b2][t] == 1 and t not in t1)
        new2 = sum(1 for t in t1 if t not in mult[b2] and t not in t2)
        return counts[b1] - gone1 + new1, counts[b2] - gone2 + new2

    def apply_swap(b1, i1, b2, i2):
        r1, r2 = members[b1][i1], members[b2][i2]
        members[b1][i1], members[b2][i2] = r2, r1
        for b, out_r, in_r in ((b1, r1, r2), (b2, r2, r1)):
            m = mult[b]
            for t in tiles_of[out_r]:
                m[t] -= 1
                if not m[t]:
                    del m[t]
            for t in tiles_of[in_r]:
                m[t] = m.get(t, 0) + 1
            counts[b] = len(m)

    for _ in range(max_sweeps):
        improved = False
        worst = max(counts)
        for b1 in sorted(range(nbins), key=lambda b: -counts[b]):
            if counts[b1] < worst:
                break
            # lightest bins first: that's where a heavy row can land without
            # raising the max, and scanning a handful keeps the sweep cheap
            targets = sorted(
                (b for b in range(nbins) if b != b1 and counts[b] < counts[b1]),
                key=lambda b: counts[b],
            )[:8]
            best = None
            for i1 in range(bp):
                for b2 in targets:
                    for i2 in range(bp):
                        c1, c2 = swap_delta(
                            b1, members[b1][i1], b2, members[b2][i2]
                        )
                        if max(c1, c2) >= worst:
                            continue  # must pull BOTH bins under the max
                        key = (max(c1, c2), c1 + c2)
                        if best is None or key < best[0]:
                            best = (key, i1, b2, i2)
            if best is not None:
                _, i1, b2, i2 = best
                apply_swap(b1, i1, b2, i2)
                improved = True
        if not improved:
            break
    return np.concatenate([np.asarray(m) for m in members]).astype(np.int32)


@dataclasses.dataclass(frozen=True)
class BlockEll:
    """Blocked-ELL matrix: (R, S) tile indices + (R, S, bp, bn) tile data.

    Logical shape is ``shape``; rows/cols are zero-padded up to the tile
    grid (``R*bp``, ``C*bn``). Padding slots carry index 0 and zero data.
    """

    indices: jnp.ndarray  # (R, S) int32 column-block ids
    data: jnp.ndarray  # (R, S, bp, bn)
    shape: tuple[int, int]  # logical (m, n)

    @property
    def block_shape(self) -> tuple[int, int]:
        return tuple(self.data.shape[-2:])

    @property
    def num_block_rows(self) -> int:
        return self.indices.shape[0]

    @property
    def slots(self) -> int:
        return self.indices.shape[1]

    @property
    def nbytes(self) -> int:
        return int(self.indices.nbytes + self.data.nbytes)

    @property
    def dense_bytes(self) -> int:
        """What a densified copy of the logical matrix would cost."""
        m, n = self.shape
        return int(m * n * self.data.dtype.itemsize)

    @staticmethod
    def from_coo(
        coo: COOMatrix,
        block_shape: tuple[int, int] = DEFAULT_BLOCK_SHAPE,
        dtype=np.float32,
    ) -> "BlockEll":
        """Convert host COO to device blocked-ELL."""
        m, n = coo.shape
        bp, bn = block_shape
        idx, data = _ell_arrays(
            coo.rows.astype(np.int64), coo.cols.astype(np.int64),
            coo.vals, m, n, bp, bn, np.dtype(dtype),
        )
        return BlockEll(jnp.asarray(idx), jnp.asarray(data), (m, n))

    def slice_row_blocks(self, start: int, stop: int) -> "BlockEll":
        """Rows [start, stop) as a new BlockEll — a pure array slice.

        Both bounds must sit on block-row boundaries; nothing is densified
        and the tile data is shared (a jnp slice) with the parent.
        """
        bp = self.block_shape[0]
        if start % bp or stop % bp:
            raise ValueError(
                f"slice bounds ({start}, {stop}) must be multiples of bp={bp}"
            )
        r0, r1 = start // bp, stop // bp
        if not 0 <= r0 <= r1 <= self.num_block_rows:
            raise ValueError(f"slice ({start}, {stop}) out of range")
        return BlockEll(
            self.indices[r0:r1], self.data[r0:r1], (stop - start, self.shape[1])
        )

    def matmul(self, x: jnp.ndarray) -> jnp.ndarray:
        """Blocked-ELL @ x for x (n, k); returns (R*bp, k) (padded rows kept)."""
        xb = _pad_cols(x, self.shape[1], self.block_shape[1])
        return _ell_matmul(self.indices, self.data, xb)

    def to_dense(self) -> np.ndarray:
        """Densify (tests/debug only) — the logical (m, n) matrix."""
        idx = np.asarray(self.indices)
        data = np.asarray(self.data)
        R, S = idx.shape
        bp, bn = data.shape[-2:]
        C = _ceil_div(self.shape[1], bn)
        out = np.zeros((R, C, bp, bn), data.dtype)
        r = np.repeat(np.arange(R), S)
        # padding slots all target block 0 with zero data: += keeps them inert
        np.add.at(out, (r, idx.ravel()), data.reshape(R * S, bp, bn))
        dense = out.transpose(0, 2, 1, 3).reshape(R * bp, C * bn)
        return dense[: self.shape[0], : self.shape[1]]


def _pad_cols(x: jnp.ndarray, n: int, bn: int) -> jnp.ndarray:
    """(n, k) -> (C, bn, k) tile view of the zero-padded column space."""
    n_pad = _ceil_div(n, bn) * bn
    x = jnp.pad(x, ((0, n_pad - n), (0, 0)))
    return x.reshape(n_pad // bn, bn, x.shape[-1])


def _ell_matmul(indices, data, xb):
    """One shard: indices (R, S), data (R, S, bp, bn), xb (C, bn, k)."""
    g = xb[indices]  # gather: (R, S, bn, k)
    out = jnp.einsum("rspb,rsbk->rpk", data, g, precision=_HIGHEST)
    R, _, bp, _ = data.shape
    return out.reshape(R * bp, -1).astype(data.dtype)


@jax.jit
def _ell_matmul_stacked(indices, data, xb):
    """J stacked shards: (J, R, S), (J, R, S, bp, bn), (J, C, bn, k)."""
    return jax.vmap(_ell_matmul)(indices, data, xb)


def _ell_rmatmul(indices, data, yb, num_col_blocks):
    """Transposed product from the FORWARD layout, one shard.

    indices (R, S), data (R, S, bp, bn), yb (R, bp, k) -> (C*bn, k):
    each tile contributes dataᵀ @ y_rowtile, scatter-added into its column
    block. Padding slots target block 0 with zero data — they add 0.
    """
    contrib = jnp.einsum("rspb,rpk->rsbk", data, yb, precision=_HIGHEST)
    C = num_col_blocks
    out = jnp.zeros((C, *contrib.shape[-2:]), data.dtype)
    out = out.at[indices].add(contrib)
    return out.reshape(C * contrib.shape[-2], -1)


@functools.partial(jax.jit, static_argnames=("num_col_blocks",))
def _ell_rmatmul_stacked(indices, data, yb, num_col_blocks):
    return jax.vmap(
        lambda i, d, y: _ell_rmatmul(i, d, y, num_col_blocks)
    )(indices, data, yb)


def _scatter_contrib(indices, contrib, num_col_blocks):
    """Scatter-add per-slot transpose contributions into the column space.

    indices (R, S), contrib (R, S, bn, k) -> (C*bn, k). Padding slots target
    column block 0 with zero data — they add exactly 0.
    """
    C = num_col_blocks
    out = jnp.zeros((C, *contrib.shape[-2:]), contrib.dtype)
    out = out.at[indices].add(contrib)
    return out.reshape(C * contrib.shape[-2], -1)


def _ell_fused(indices, data, xb, yb, num_col_blocks):
    """One shard, one pass over the tiles: (A x, Aᵀ y).

    indices (R, S), data (R, S, bp, bn), xb (C, bn, k), yb (R, bp, k) ->
    (R*bp, k) forward product and (C*bn, k) transposed product. The tile
    data feeds BOTH contractions from a single read — the jnp counterpart
    of the fused Pallas kernel (``repro.kernels.spmm``), which emits the
    identical pair from one grid pass.
    """
    g = xb[indices]  # gather: (R, S, bn, k)
    fwd = jnp.einsum("rspb,rsbk->rpk", data, g, precision=_HIGHEST)
    contrib = jnp.einsum("rspb,rpk->rsbk", data, yb, precision=_HIGHEST)
    R, _, bp, _ = data.shape
    return (
        fwd.reshape(R * bp, -1).astype(data.dtype),
        _scatter_contrib(indices, contrib, num_col_blocks),
    )


@functools.partial(jax.jit, static_argnames=("num_col_blocks",))
def _ell_fused_stacked(indices, data, xb, yb, num_col_blocks):
    return jax.vmap(
        lambda i, d, x, y: _ell_fused(i, d, x, y, num_col_blocks)
    )(indices, data, xb, yb)


def _gram_coo(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray):
    """Host-side COO of G = A Aᵀ for one sparse block.

    G[i, i'] = Σ_c A[i, c] A[i', c]: group the entries by column; every
    column with t entries contributes a t×t outer product. Schenk-like
    blocks share few columns across rows, so the pair count stays near the
    diagonal's. Duplicate coordinates are pre-summed (``_ell_arrays``
    assigns last-wins, which would drop accumulations otherwise).
    """
    order = np.argsort(cols, kind="stable")
    r, c, v = rows[order], cols[order], vals[order]
    gi, gj, gv = [np.empty(0, np.int64)], [np.empty(0, np.int64)], [np.empty(0)]
    if c.size:
        starts = np.flatnonzero(np.r_[True, c[1:] != c[:-1]])
        ends = np.r_[starts[1:], c.size]
        sizes = ends - starts
        single = sizes == 1
        s1 = starts[single]
        gi.append(r[s1])
        gj.append(r[s1])
        gv.append(v[s1] ** 2)
        for s, e in zip(starts[~single], ends[~single]):
            t = e - s
            gi.append(np.repeat(r[s:e], t))
            gj.append(np.tile(r[s:e], t))
            gv.append(np.outer(v[s:e], v[s:e]).ravel())
    gi, gj, gv = map(np.concatenate, (gi, gj, gv))
    if gi.size == 0:
        return gi, gj, gv
    p_span = int(gi.max()) + 1
    key = gi * p_span + gj
    ukey, inv = np.unique(key, return_inverse=True)
    summed = np.zeros(ukey.size, gv.dtype)
    np.add.at(summed, inv, gv)
    return ukey // p_span, ukey % p_span, summed


def _stack_shards(shards: list[tuple[np.ndarray, np.ndarray]]):
    """Pad per-shard ELL arrays to a common slot count and stack them."""
    S = max(idx.shape[1] for idx, _ in shards)
    J, R = len(shards), shards[0][0].shape[0]
    tile = shards[0][1].shape[-2:]
    idx_out = np.zeros((J, R, S), np.int32)
    data_out = np.zeros((J, R, S, *tile), shards[0][1].dtype)
    for j, (idx, data) in enumerate(shards):
        idx_out[j, :, : idx.shape[1]] = idx
        data_out[j, :, : idx.shape[1]] = data
    return idx_out, data_out


@dataclasses.dataclass(frozen=True)
class PartitionedBSR:
    """J-way uniform row partition of a sparse matrix, blocked-ELL per shard.

    ``fwd_*`` holds the A_j shards ((J, Rp, S) tiles of (bp, bn)) — the only
    mandatory representation: ``rmatvec`` scatter-adds transposed tile
    products straight from it, so A_jᵀ costs no extra memory by default.
    ``with_transpose=True`` additionally materializes the A_jᵀ shards
    (``tra_*``, (J, Rn, T) tiles of (bn, bp)) for the Pallas kernel path,
    whose gather-driven DMA needs a contiguous streaming layout in both
    directions. ``with_gram=True`` stores the Gram operators
    G_j = A_j A_jᵀ as (p, p) blocked-ELL shards (``gram_*``) — near-diagonal
    for Schenk-like matrices, so they cost a few percent of the forward
    shards and make each inner-CG iteration one SMALL SpMV instead of two
    full ones. Blocks are padded to ``p_pad`` rows with zero rows
    (consistent 0·x = 0 equations; see module docstring).

    ``balance=True`` stores the forward/transpose tiles in a per-block
    balanced row order (``_balance_perm``): ``ext_pos[j, q]`` is the
    external row at internal position q and ``int_pos[j, q]`` its inverse.
    The Gram shards and every public product keep the EXTERNAL row order —
    the permutation never escapes this class.
    """

    fwd_indices: jnp.ndarray  # (J, Rp, S) int32
    fwd_data: jnp.ndarray  # (J, Rp, S, bp, bn)
    shape: tuple[int, int]  # logical (m, n) of the whole system
    p: int  # logical rows per partition block (ceil(m / J))
    p_pad: int  # block rows padded to the tile grid
    tra_indices: jnp.ndarray | None = None  # (J, Rn, T) int32
    tra_data: jnp.ndarray | None = None  # (J, Rn, T, bn, bp)
    gram_indices: jnp.ndarray | None = None  # (J, Rp, Sg) int32
    gram_data: jnp.ndarray | None = None  # (J, Rp, Sg, bp, bp)
    ext_pos: jnp.ndarray | None = None  # (J, p_pad) int32: internal -> external
    int_pos: jnp.ndarray | None = None  # (J, p_pad) int32: external -> internal
    planned: bool = False  # built from a non-uniform PartitionPlan

    @property
    def num_blocks(self) -> int:
        return self.fwd_indices.shape[0]

    @property
    def num_cols(self) -> int:
        return self.shape[1]

    @property
    def block_shape(self) -> tuple[int, int]:
        return tuple(self.fwd_data.shape[-2:])

    @property
    def nbytes(self) -> int:
        """Device-resident bytes of the sparse operator (all present parts)."""
        arrs = (
            self.fwd_indices, self.fwd_data, self.tra_indices, self.tra_data,
            self.gram_indices, self.gram_data, self.ext_pos, self.int_pos,
        )
        return int(sum(a.nbytes for a in arrs if a is not None))

    @property
    def dense_bytes(self) -> int:
        """What the dense path's (J, p, n) ``blocks`` array would cost."""
        return int(
            self.num_blocks * self.p_pad * self.shape[1]
            * self.fwd_data.dtype.itemsize
        )

    @staticmethod
    def from_coo(
        coo: COOMatrix,
        num_blocks: int,
        block_shape: tuple[int, int] = DEFAULT_BLOCK_SHAPE,
        dtype=np.float32,
        with_transpose: bool = False,
        with_gram: bool = False,
        balance: bool = False,
        plan=None,
        host: bool = False,
    ) -> "PartitionedBSR":
        """Partition + convert, entirely without densifying.

        Builds one global BlockEll over the zero-padded (J·p_pad, n) row
        space and carves the J forward shards out with
        ``slice_row_blocks``. ``with_transpose`` adds the A_jᵀ shards (only
        the Pallas kernel path needs them); ``with_gram`` adds the sparse
        G_j = A_j A_jᵀ shards (the inner-CG operator). ``balance`` stores
        the tiles in a per-block load-balanced row order (the ELL slot
        count ``S`` is a max over block-rows; see ``_balance_perm``) while
        keeping every public product in the original row order.

        ``plan`` (a ``repro.core.partition.PartitionPlan``) overrides the
        uniform contiguous row→block map: block heights become the plan's
        max count and ragged blocks absorb the slack as zero padding rows
        (exactly the existing remainder convention, so everything
        downstream — balance permutation, Gram shards, mesh placement —
        is untouched). A planned operator's ``block_rhs`` is plan-order;
        use the owning solver's plan-aware ``block_rhs`` for original-order
        right-hand sides.

        ``host=True`` leaves every array in host memory (numpy), for
        ``place`` to send straight to its mesh shards; by default they are
        device arrays.
        """
        m, n = coo.shape
        bp, bn = block_shape
        J = num_blocks
        use_plan = plan is not None and plan.kind != "uniform"
        if use_plan and (plan.m != m or plan.num_blocks != J):
            raise ValueError(
                f"plan is for (m={plan.m}, J={plan.num_blocks}), "
                f"got (m={m}, J={J})"
            )
        p = plan.max_rows if use_plan else _ceil_div(m, J)
        p_pad = _ceil_div(p, bp) * bp
        dtype = np.dtype(dtype)

        rows = coo.rows.astype(np.int64)
        cols = coo.cols.astype(np.int64)
        vals = coo.vals
        # dedupe coordinates up front (last-wins, matching to_dense): the
        # Gram builder SUMS per-coordinate contributions, so duplicates
        # must be resolved once here or the inner-CG operator would
        # disagree with the forward shards
        if rows.size:
            key = rows * n + cols
            order = np.argsort(key, kind="stable")
            keep = np.ones(order.size, dtype=bool)
            keep[:-1] = key[order][1:] != key[order][:-1]
            sel = order[keep]
            rows, cols, vals = rows[sel], cols[sel], vals[sel]
        coo = COOMatrix(rows, cols, vals, (m, n))
        if use_plan:
            blk = plan.assignment.astype(np.int64)[rows]
            local = plan.slots[rows]
        else:
            blk = rows // p
            local = rows % p

        ext_pos = int_pos = None
        tile_local = local  # internal (tile-layout) row of every entry
        if balance:
            ext_np = np.stack(
                [
                    _balance_perm(
                        local[blk == j], cols[blk == j] // bn, p_pad, bp
                    )
                    for j in range(J)
                ]
            )
            int_np = np.empty_like(ext_np)
            np.put_along_axis(
                int_np, ext_np, np.broadcast_to(
                    np.arange(p_pad, dtype=np.int32), (J, p_pad)
                ), axis=1,
            )
            tile_local = int_np[blk, local].astype(np.int64)
            ext_pos, int_pos = ext_np, int_np

        # global padded layout: block j owns rows [j*p_pad, j*p_pad + p_pad)
        padded = COOMatrix(
            (blk * p_pad + tile_local).astype(np.int64), cols, coo.vals,
            (J * p_pad, n),
        )
        full = BlockEll(
            *_ell_arrays(
                padded.rows, padded.cols, padded.vals, J * p_pad, n, bp, bn,
                dtype,
            ),
            padded.shape,
        )
        shards = [
            full.slice_row_blocks(j * p_pad, (j + 1) * p_pad) for j in range(J)
        ]
        # shards of one parent share S, so they stack without re-padding
        fwd_idx = np.stack([s.indices for s in shards])
        fwd_data = np.stack([s.data for s in shards])

        tra_idx = tra_data = None
        if with_transpose:
            tra_idx, tra_data = _stack_shards(
                [
                    _ell_arrays(
                        cols[blk == j], tile_local[blk == j],
                        coo.vals[blk == j], n, p_pad, bn, bp, dtype,
                    )
                    for j in range(J)
                ]
            )

        # Gram shards stay in the EXTERNAL row order: the inner CG runs on
        # unpermuted vectors, so its hot loop never touches the permutation
        gram_idx = gram_data = None
        if with_gram:
            gram_idx, gram_data = _stack_shards(
                [
                    _ell_arrays(
                        *_gram_coo(
                            local[blk == j], cols[blk == j], coo.vals[blk == j]
                        ),
                        p_pad, p_pad, bp, bp, dtype,
                    )
                    for j in range(J)
                ]
            )

        op = PartitionedBSR(
            fwd_idx, fwd_data, (m, n), p, p_pad,
            tra_indices=tra_idx, tra_data=tra_data,
            gram_indices=gram_idx, gram_data=gram_data,
            ext_pos=ext_pos, int_pos=int_pos, planned=use_plan,
        )
        return op if host else jax.tree.map(jnp.asarray, op)

    # -- mesh placement ------------------------------------------------------

    def shard_spec(self, axes: tuple[str, ...]) -> "PartitionedBSR":
        """Pytree of ``PartitionSpec``s sharding every tile array's leading
        J axis over the mesh axes ``axes``.

        Every child array of this operator — forward/transpose/Gram ELL
        tiles and the balance permutations — stacks its per-block shards on
        axis 0, so one spec shape covers the whole pytree. The result has
        the same pytree STRUCTURE as ``self`` (absent children stay None),
        which is exactly what ``shard_map``'s ``in_specs`` wants for an
        operator-valued argument.
        """
        from jax.sharding import PartitionSpec

        spec = PartitionSpec(tuple(axes))
        children, aux = _bsr_flatten(self)
        return _bsr_unflatten(
            aux, tuple(None if c is None else spec for c in children)
        )

    def place(self, mesh, axes: tuple[str, ...]) -> "PartitionedBSR":
        """Copy of the operator with every tile array ``device_put`` onto
        ``mesh``, block axis 0 sharded over ``axes`` (one group of partition
        blocks per device) — per-device resident bytes drop to ~1/D."""
        from jax.sharding import NamedSharding, PartitionSpec

        sharding = NamedSharding(mesh, PartitionSpec(tuple(axes)))
        children, aux = _bsr_flatten(self)
        return _bsr_unflatten(
            aux,
            tuple(
                None if c is None else jax.device_put(c, sharding)
                for c in children
            ),
        )

    # -- balanced-layout translation -----------------------------------------

    def _to_external(self, rows: jnp.ndarray) -> jnp.ndarray:
        """Internal (tile-layout) block rows (J, p_pad, k) -> external order."""
        if self.int_pos is None:
            return rows
        return rows[jnp.arange(rows.shape[0])[:, None], self.int_pos]

    def _to_internal(self, rows: jnp.ndarray) -> jnp.ndarray:
        """External block rows (J, p_pad, k) -> internal tile-layout order."""
        if self.ext_pos is None:
            return rows
        return rows[jnp.arange(rows.shape[0])[:, None], self.ext_pos]

    # -- products -----------------------------------------------------------

    def matvec(self, x: jnp.ndarray, use_kernels: bool = False) -> jnp.ndarray:
        """A_j x_j for every block: x (J, n, k) — or (n, k), broadcast to all
        blocks — returns (J, p_pad, k). Padded rows come back exactly zero."""
        J, n = self.num_blocks, self.shape[1]
        if x.ndim == 2:
            x = jnp.broadcast_to(x[None], (J, *x.shape))
        xb = jax.vmap(lambda v: _pad_cols(v, n, self.block_shape[1]))(x)
        if use_kernels:
            from repro.kernels.spmm import ops as spmm_ops

            out = spmm_ops.spmm(self.fwd_indices, self.fwd_data, xb)
        else:
            out = _ell_matmul_stacked(self.fwd_indices, self.fwd_data, xb)
        return self._to_external(out)

    def rmatvec(self, y: jnp.ndarray, use_kernels: bool = False) -> jnp.ndarray:
        """A_jᵀ y_j for every block: y (J, p_pad, k) -> (J, n, k).

        Runs off the transposed shards when they are materialized (the
        kernel path requires them); otherwise scatter-adds transposed tile
        products straight from the forward shards — zero extra memory.
        """
        n = self.shape[1]
        bp, bn = self.block_shape
        y = self._to_internal(y)
        if use_kernels or self.tra_indices is not None:
            if self.tra_indices is None:
                raise ValueError(
                    "kernel rmatvec needs the transposed shards: build with "
                    "PartitionedBSR.from_coo(..., with_transpose=True)"
                )
            xb = jax.vmap(lambda v: _pad_cols(v, self.p_pad, bp))(y)
            if use_kernels:
                from repro.kernels.spmm import ops as spmm_ops

                out = spmm_ops.spmm(self.tra_indices, self.tra_data, xb)
            else:
                out = _ell_matmul_stacked(self.tra_indices, self.tra_data, xb)
            return out[:, :n]
        J = self.num_blocks
        yb = y.reshape(J, self.p_pad // bp, bp, -1)
        out = _ell_rmatmul_stacked(
            self.fwd_indices, self.fwd_data, yb, _ceil_div(n, bn)
        )
        return out[:, :n]

    def fused_project(
        self, x: jnp.ndarray, y: jnp.ndarray, use_kernels: bool = False
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """(A_j x, A_jᵀ y_j) from ONE pass over the forward ELL tiles.

        x (n, k) (broadcast to every block) or (J, n, k); y (J, p_pad, k).
        Returns the forward product (J, p_pad, k) and the scatter-added
        transposed product (J, n, k). This is the matfree epoch's tile
        pass: each tile is read once and feeds both contractions (the
        Pallas kernel under ``use_kernels=True`` does the same from a
        single grid pass, staging per-slot transpose contributions that are
        scatter-added here).
        """
        J, n = self.num_blocks, self.shape[1]
        bp, bn = self.block_shape
        if x.ndim == 2:
            x = jnp.broadcast_to(x[None], (J, *x.shape))
        xb = jax.vmap(lambda v: _pad_cols(v, n, bn))(x)
        yb = self._to_internal(y).reshape(J, self.p_pad // bp, bp, -1)
        C = _ceil_div(n, bn)
        if use_kernels:
            from repro.kernels.spmm import ops as spmm_ops

            fwd, contrib = spmm_ops.spmm_fused(
                self.fwd_indices, self.fwd_data, xb, yb
            )
            tra = jax.vmap(
                lambda i, c: _scatter_contrib(i, c, C)
            )(self.fwd_indices, contrib)
        else:
            fwd, tra = _ell_fused_stacked(
                self.fwd_indices, self.fwd_data, xb, yb, C
            )
        return self._to_external(fwd), tra[:, :n]

    def gram_mv(self, y: jnp.ndarray, use_kernels: bool = False) -> jnp.ndarray:
        """(A_j A_jᵀ) y_j via the stored sparse Gram shards (or, without
        them, as rmatvec-then-matvec): (J, p_pad, k) -> (J, p_pad, k)."""
        if self.gram_indices is None:
            return self.matvec(self.rmatvec(y, use_kernels), use_kernels)
        bp = self.block_shape[0]
        yb = jax.vmap(lambda v: _pad_cols(v, self.p_pad, bp))(y)
        if use_kernels:
            from repro.kernels.spmm import ops as spmm_ops

            return spmm_ops.spmm(self.gram_indices, self.gram_data, yb)
        return _ell_matmul_stacked(self.gram_indices, self.gram_data, yb)

    def gram_diag(self) -> jnp.ndarray:
        """diag(A_j A_jᵀ) per block — (J, p_pad) row sums of squares, the
        Jacobi preconditioner for the inner CG (zero on padded rows)."""
        sq = jnp.sum(self.fwd_data.astype(jnp.float32) ** 2, axis=(2, 4))
        sq = sq.reshape(self.num_blocks, self.p_pad)
        if self.int_pos is None:
            return sq
        return jnp.take_along_axis(sq, self.int_pos, axis=1)

    def jacobi_weights(self, eps: float = 1e-10) -> jnp.ndarray:
        """Inverse Gram diagonal (J, p_pad, 1), the inner-CG Jacobi weights.

        The clamp is RELATIVE — near-zero but nonzero diagonals (badly
        scaled rows) are bounded at ``1 / (max_block_diag * eps)`` instead
        of exploding toward 1/tiny, which overflowed the CG step-size
        arithmetic on badly scaled matrices. Exactly-zero diagonals (the
        padding rows) keep weight 0 so their iterates stay pinned at zero.
        """
        diag = self.gram_diag()
        floor = jnp.max(diag, axis=1, keepdims=True) * eps
        return jnp.where(
            diag > 0, 1.0 / jnp.maximum(diag, floor), 0.0
        )[..., None]

    def slot_occupancy(self) -> tuple[int, float]:
        """(S, mean occupied slots per block-row) of the forward shards.

        ``S`` is the padded slot count every block-row pays for;
        the mean counts tiles with any nonzero data. Their ratio is the ELL
        padding overhead that ``balance=True`` exists to shrink.
        """
        occupied = np.asarray(
            jnp.any(self.fwd_data != 0, axis=(-1, -2))
        ).sum(axis=-1)  # (J, Rp) occupied tiles per block-row
        return int(self.fwd_indices.shape[-1]), float(occupied.mean())

    # -- checkpoint serialization (repro.serving.checkpoint) -----------------

    def to_arrays(self, prefix: str = "op_") -> tuple[dict, dict]:
        """Flatten to plain numpy arrays + JSON-able metadata.

        The split is DERIVED from the dataclass fields: every array child
        (present ones only — absent transpose/gram/balance parts are simply
        omitted) lands in ``arrays`` under ``prefix + field_name``, and the
        static shape metadata lands in ``meta``. ``from_arrays`` inverts it
        bit-for-bit — the restored operator's products are identical.
        """
        arrays: dict = {}
        meta: dict = {}
        for f in dataclasses.fields(self):
            value = getattr(self, f.name)
            if f.name == "planned":
                meta[f.name] = bool(value)
            elif f.name in ("shape", "p", "p_pad"):
                meta[f.name] = list(value) if f.name == "shape" else int(value)
            elif value is not None:
                arrays[prefix + f.name] = np.asarray(value)
        return arrays, meta

    @classmethod
    def from_arrays(cls, arrays, meta: dict, prefix: str = "op_"):
        """Rebuild from ``to_arrays`` output (extra keys in ``arrays`` are
        ignored, so the caller can pool several objects in one archive)."""
        kwargs = {
            f.name: jnp.asarray(arrays[prefix + f.name])
            for f in dataclasses.fields(cls)
            if prefix + f.name in arrays
        }
        return cls(
            shape=tuple(meta["shape"]), p=int(meta["p"]),
            p_pad=int(meta["p_pad"]),
            planned=bool(meta.get("planned", False)), **kwargs,
        )

    def block_rhs(self, b: np.ndarray) -> jnp.ndarray:
        """RHS (m,) or (m, k) -> (J, p_pad, k), zero-padded like the rows."""
        if self.planned:
            # the uniform rows//p scatter below would misplace entries; the
            # owning solver holds the plan and does the plan-aware scatter
            raise ValueError(
                "operator was built from a non-uniform PartitionPlan; use "
                "the prepared solver's block_rhs (it owns the plan)"
            )
        b = np.asarray(b)
        squeeze = b.ndim == 1
        if squeeze:
            b = b[:, None]
        m = self.shape[0]
        if b.shape[0] != m:
            raise ValueError(f"expected {m} rows, got {b.shape[0]}")
        out = np.zeros(
            (self.num_blocks * self.p_pad, b.shape[1]), self.fwd_data.dtype
        )
        rows = np.arange(m)
        out[(rows // self.p) * self.p_pad + rows % self.p] = b
        return jnp.asarray(out.reshape(self.num_blocks, self.p_pad, -1))


def _bsr_flatten(op: PartitionedBSR):
    children = (
        op.fwd_indices, op.fwd_data, op.tra_indices, op.tra_data,
        op.gram_indices, op.gram_data, op.ext_pos, op.int_pos,
    )
    return children, (op.shape, op.p, op.p_pad, op.planned)


def _bsr_unflatten(aux, children):
    shape, p, p_pad, planned = aux
    (
        fwd_idx, fwd_data, tra_idx, tra_data, gram_idx, gram_data,
        ext_pos, int_pos,
    ) = children
    return PartitionedBSR(
        fwd_idx, fwd_data, shape=shape, p=p, p_pad=p_pad,
        tra_indices=tra_idx, tra_data=tra_data,
        gram_indices=gram_idx, gram_data=gram_data,
        ext_pos=ext_pos, int_pos=int_pos, planned=planned,
    )


# pytree registration: the operator rides through jax.jit as an operand
# (arrays traced, shape metadata static), exactly like the dense factors
jax.tree_util.register_pytree_node(PartitionedBSR, _bsr_flatten, _bsr_unflatten)
