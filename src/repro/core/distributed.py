"""SPMD distributed DAPC/APC via ``jax.shard_map``.

The paper's Dask task graph maps onto a static SPMD program:

  * block index ``j``  → the (``pod``,) ``data`` mesh axes (one or more row
    blocks per shard; ``vmap`` over the local blocks),
  * consensus average → ``lax.pmean`` over those axes (hierarchical ICI/DCN
    all-reduce instead of a scheduler round-trip),
  * epochs            → ``repro.core.consensus.consensus_scan`` inside one
    jit: both solvers here set up on their shard and hand the shared epoch
    loop their projection, their residual and ``pmean``/``psum`` hooks.

Beyond-paper features:

  * **2D parallelism** (``col_axis``): the solution dimension ``n`` is sharded
    over the ``model`` axis. Per-block QR becomes a **TSQR** (local QR +
    all-gathered R-stack + small replicated QR), the projector factor ``W`` is
    column-sharded, and the iteration needs exactly one p-length ``psum`` over
    ``model`` plus the n/ms-length consensus ``pmean`` over ``data`` per epoch.
    The paper replicates ``x`` and materializes P per worker; this scales to
    n far beyond single-chip HBM.
  * **Straggler-tolerant (stale) consensus** (``straggler_prob``): each epoch
    every block publishes its update only with probability 1−q; the average
    re-uses the last published state otherwise. The η-EMA of eq. (7) absorbs
    the staleness (validated in tests) — this is the async/straggler story at
    1000+ nodes where per-epoch barriers on every worker are unaffordable.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import consensus
from repro.core.apc import setup_classical
from repro.core.dapc import setup_decomposed


def _pmean(x, axes):
    return jax.lax.pmean(x, axes if len(axes) > 1 else axes[0])


def _psum(x, axes):
    return jax.lax.psum(x, axes if len(axes) > 1 else axes[0])


def _epoch_keys(seed: int, block_axes: Sequence[str], num_epochs: int):
    """Per-shard, per-epoch PRNG keys for the straggler simulation.

    Folds in the index of EVERY axis in ``block_axes``: on a multi-axis
    block mesh (e.g. ``("pod", "data")``), shards sharing only their first
    axis index must still draw independent drop patterns.
    """
    key = jax.random.PRNGKey(seed)
    for ax in block_axes:
        key = jax.random.fold_in(key, jax.lax.axis_index(ax))
    return jax.random.split(key, num_epochs)


# ---------------------------------------------------------------------------
# Row-sharded solver (the paper's layout: every worker holds full-width rows)
# ---------------------------------------------------------------------------


def solve_sharded(
    blocks: jnp.ndarray,  # (J, p, n) — J divisible by prod(mesh[block_axes])
    bvecs: jnp.ndarray,  # (J, p) one RHS, or (J, p, k) coalesced batch
    mesh: Mesh,
    mode: str,
    block_axes: Sequence[str] = ("data",),
    method: str = "dapc",
    gamma: float = 1.0,
    eta: float = 0.9,
    num_epochs: int = 100,
    straggler_prob: float = 0.0,
    seed: int = 0,
    x_ref: jnp.ndarray | None = None,
    compress: str | None = None,  # "bf16_delta" halves psum payload
):
    """Distributed consensus solve, row-sharded blocks. Returns (x̄, history).

    ``bvecs`` with a trailing RHS axis ``(J, p, k)`` — the shape the serving
    queue's coalesced batches arrive in — runs all k consensus iterations in
    the same sharded program: state becomes ``(J_loc, n, k)``, the projector
    application feeds the MXU as (p,n)×(n,k) matmuls, and every collective
    (the consensus ``pmean``, the residual ``psum``) carries k columns per
    round trip instead of one. ``x̄`` comes back ``(n, k)`` and the history
    rows per-system ``(k,)``. A straggling worker goes stale for ALL of its
    columns at once (one mask per block, as a real slow worker would).
    """
    block_axes = tuple(block_axes)
    spec_in = P(block_axes)
    q = float(straggler_prob)

    pmean = functools.partial(_pmean, axes=block_axes)
    psum = functools.partial(_psum, axes=block_axes)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(spec_in, spec_in, P(None) if x_ref is not None else P()),
        out_specs=(P(), P()),
    )
    def run(local_blocks, local_bvecs, ref):
        # Algorithm 1 steps 2–3, vmapped over this shard's blocks; all
        # einsums take `...` so a trailing RHS axis k rides along unchanged
        if method == "dapc":
            x0s, Ws = setup_decomposed(local_blocks, local_bvecs, mode)
            apply_fn = lambda v: v - jnp.einsum(
                "jpn,jp...->jn...", Ws, jnp.einsum("jpn,jn...->jp...", Ws, v)
            )
        else:  # classical APC
            x0s, Ps = setup_classical(local_blocks, local_bvecs, mode)
            apply_fn = lambda v: jnp.einsum("jmn,jn...->jm...", Ps, v)

        def residual(xbar, aux):
            return jnp.einsum("jpn,n...->jp...", local_blocks, xbar) - local_bvecs

        return consensus.consensus_scan(
            x0s, pmean(jnp.mean(x0s, axis=0)),  # eq. (5)
            consensus.projection(apply_fn), residual,
            gamma=gamma, eta=eta, num_epochs=num_epochs,
            pmean=pmean, psum=psum,
            x_ref=ref if x_ref is not None else None,
            compress=compress, straggler_prob=q,
            # one mask per block, shared across the RHS columns it serves
            keys=_epoch_keys(seed, block_axes, num_epochs) if q > 0.0 else None,
        )

    ref = (
        jnp.asarray(x_ref, blocks.dtype)
        if x_ref is not None
        else jnp.zeros((blocks.shape[-1],), blocks.dtype)
    )
    return run(blocks, bvecs, ref)


# ---------------------------------------------------------------------------
# 2D-parallel solver: row blocks on `data`, solution dimension on `model`
# ---------------------------------------------------------------------------


def _tsqr(b_loc: jnp.ndarray, col_axis: str, col_shards: int):
    """TSQR of the tall matrix B (n × p) row-sharded over ``col_axis``.

    Returns (Q_loc (n_loc, p), R (p, p) replicated).
    """
    q1, r1 = jnp.linalg.qr(b_loc, mode="reduced")  # local (n_loc,p),(p,p)
    rs = jax.lax.all_gather(r1, col_axis)  # (ms, p, p) replicated
    p = r1.shape[-1]
    q2, r = jnp.linalg.qr(rs.reshape(col_shards * p, p), mode="reduced")
    idx = jax.lax.axis_index(col_axis)
    q2_loc = jax.lax.dynamic_slice_in_dim(q2, idx * p, p, axis=0)  # (p, p)
    return q1 @ q2_loc, r


def solve_sharded_2d(
    blocks_t: jnp.ndarray,  # (J, n, p): per-block A_jᵀ (wide mode only)
    bvecs: jnp.ndarray,  # (J, p) one RHS, or (J, p, k) coalesced batch
    mesh: Mesh,
    block_axes: Sequence[str] = ("data",),
    col_axis: str = "model",
    gamma: float = 1.0,
    eta: float = 0.9,
    num_epochs: int = 100,
    x_ref: jnp.ndarray | None = None,
):
    """2D-parallel decomposed APC (wide regime): TSQR setup + column-sharded
    consensus. ``n`` must divide evenly by mesh.shape[col_axis].

    Like ``solve_sharded``, a trailing RHS axis ``(J, p, k)`` batches all k
    systems through the same program: the TSQR factor is shared (b-independent),
    the substitution and every psum/pmean carry k columns, and x̄ returns
    ``(n, k)`` with per-system ``(k,)`` history rows."""
    block_axes = tuple(block_axes)
    col_shards = mesh.shape[col_axis]
    n = blocks_t.shape[1]
    if n % col_shards:
        raise ValueError(f"n={n} not divisible by {col_axis}={col_shards}")

    pmean = functools.partial(_pmean, axes=block_axes)
    psum = functools.partial(_psum, axes=block_axes)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(
            P(block_axes, col_axis),
            P(block_axes),
            P(col_axis) if x_ref is not None else P(),
        ),
        out_specs=(P(col_axis), P()),
    )
    def run(bt_loc, b_loc, ref_loc):
        # bt_loc: (J_loc, n_loc, p); b_loc: (J_loc, p[, k])
        def setup_one(bt, b):
            q_loc, r = _tsqr(bt, col_axis, col_shards)  # W = q_locᵀ col-shard
            z = jax.scipy.linalg.solve_triangular(r.mT, b, lower=True)
            return q_loc @ z, q_loc  # x0 (n_loc[, k]), factor (n_loc, p)

        x0s, Qs = jax.vmap(setup_one)(bt_loc, b_loc)  # (J_loc, n_loc[, k])

        def apply_fn(v):  # v (J_loc, n_loc[, k]): P v = v − Q psum(Qᵀ v)
            u = _psum(jnp.einsum("jnp,jn...->jp...", Qs, v), (col_axis,))
            return v - jnp.einsum("jnp,jp...->jn...", Qs, u)

        def residual(xbar_loc, aux):  # A_j x = psum_model(B_locᵀ x_loc)
            ax = _psum(
                jnp.einsum("jnp,n...->jp...", bt_loc, xbar_loc), (col_axis,)
            )
            return ax - b_loc

        return consensus.consensus_scan(
            x0s, pmean(jnp.mean(x0s, axis=0)),  # eq. (5)
            consensus.projection(apply_fn), residual,
            gamma=gamma, eta=eta, num_epochs=num_epochs,
            pmean=pmean, psum=psum,
            coord_mean=lambda a: _pmean(jnp.mean(a, axis=0), (col_axis,)),
            x_ref=ref_loc if x_ref is not None else None,
        )

    ref = (
        jnp.asarray(x_ref, blocks_t.dtype)
        if x_ref is not None
        else jnp.zeros((n,), blocks_t.dtype)
    )
    return run(blocks_t, bvecs, ref)


# ---------------------------------------------------------------------------
# Elastic re-partitioning (worker count changes between runs / after failure)
# ---------------------------------------------------------------------------


def repartition(blocks: jnp.ndarray, bvecs: jnp.ndarray, new_num_blocks: int):
    """Re-split the same global system for a different worker count.

    APC state is reconstructible from (A, b) alone — after elastic scale-up or
    scale-down, re-run setup on the new layout and warm-start the consensus
    from any previous x̄ (consensus is a fixed-point iteration, warm starts
    are sound).

    ``bvecs`` may be a single RHS ``(J, p)`` or a coalesced batch
    ``(J, p, k)`` — the trailing RHS axis rides through the re-split
    unchanged."""
    num_blocks, p, n = blocks.shape
    m = num_blocks * p
    if m % new_num_blocks:
        raise ValueError(f"m={m} rows not divisible into {new_num_blocks} blocks")
    flat_a = blocks.reshape(m, n)
    tail = bvecs.shape[2:]  # () single RHS, (k,) coalesced batch
    flat_b = bvecs.reshape(m, *tail)
    p2 = m // new_num_blocks
    return (
        flat_a.reshape(new_num_blocks, p2, n),
        flat_b.reshape(new_num_blocks, p2, *tail),
    )
