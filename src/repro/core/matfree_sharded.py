"""Sharded matrix-free solver: blocked-ELL shards under ``shard_map``.

This closes the gap between the repo's two scaling stories: the matfree
path (repro.core.matfree) fits sparse systems that would never densify,
but ran single-host; the ``shard_map`` path (repro.core.distributed)
spans a mesh, but densifies every row block. Here the ``PartitionedBSR``
tile arrays are placed on the mesh (one group of partition blocks per
device, ``PartitionedBSR.place``), and the fused-projection epoch runs
as one SPMD program per solve.

Communication profile (the point of the exercise — Azizan-Ruhi et al.'s
block projection P_j x = x − A_jᵀ(A_jA_jᵀ)⁻¹A_jx is defined purely in
per-worker products, and Tutunov et al.'s distributed Newton keeps all
heavy linear algebra worker-local the same way):

  * per epoch, exactly ONE n·k ``pmean`` — the consensus average of
    eq. 5/7, via the carried block mean (see ``consensus_epochs``). The
    k-length residual is REPORTING when ``tol`` is unset: each shard
    emits its partial sums through the ``out_specs`` and one post-scan
    reduction collapses them, so the plain solve's epoch pays a single
    collective. ``solve(..., tol=...)`` adds the k-length residual
    ``psum`` back into the epoch — the early-exit freeze is a replicated
    predicate, every shard must agree on it in-scan;
  * BOTH inner Gram solvers are strictly shard-local: ``"direct"``
    applies the per-block pseudo-inverses as a local einsum, ``"pcg"``
    iterates on the local sparse Gram shards with a shard-local stopping
    test (its ``while_loop`` trip count may differ per device — that is
    why the program runs under ``shard_map_unchecked``). A capped PCG
    solve's carried defect b_j − A_j x_j is folded back per block
    (``consensus_epochs``), so it stays shard-local as well. The PCG
    depth counts of ``history["inner_iters"]`` are reporting too: each
    shard emits its own, and the max over shards is taken after the
    scan (``SolveResult.gram_iters`` counts each epoch's slowest shard).

``prepare(A, mode="matfree", mesh=...)`` builds one of these; the solve
contract (``SolveResult``, batched RHS, per-column early exit, serving
pool compatibility) is inherited from ``MatrixFreePreparedSolver``
unchanged — only ``_solve_program`` differs.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map_unchecked
from repro.core import consensus
from repro.core.matfree import MatrixFreePreparedSolver, consensus_epochs


def mesh_block_devices(mesh, block_axes) -> int:
    """Number of shards the block axis is split over (product of the mesh
    extents of ``block_axes``); raises for axes the mesh does not have."""
    missing = [a for a in block_axes if a not in mesh.shape]
    if missing:
        raise ValueError(
            f"block_axes {tuple(block_axes)} not in mesh axes "
            f"{tuple(mesh.shape)}: missing {missing}"
        )
    return math.prod(mesh.shape[a] for a in block_axes)


@dataclasses.dataclass
class ShardedMatrixFreeSolver(MatrixFreePreparedSolver):
    """``MatrixFreePreparedSolver`` whose solve program is a ``shard_map``
    over ``mesh``: the operator/Gram/weight arrays live block-sharded on
    the mesh and an epoch's collectives are the n·k consensus ``pmean``
    plus — only under ``tol`` — the k-length residual ``psum`` (see
    module docstring).

    Produced by ``prepare(A, mode="matfree", mesh=...)``. ``solve`` and
    the result contract are inherited; ``memory_bytes`` still reports the
    GLOBAL operator bytes (across the mesh), ``per_device_memory_bytes``
    the worst single device's resident share (~1/D).
    """

    mesh: object = None  # jax.sharding.Mesh
    block_axes: tuple[str, ...] = ("data",)

    path = "matfree_sharded"

    @property
    def num_shards(self) -> int:
        return mesh_block_devices(self.mesh, self.block_axes)

    @property
    def per_device_memory_bytes(self) -> int:
        """Worst-device resident bytes of the prepared state — what one
        worker actually holds (ELL tiles + Gram inverse + Jacobi weights),
        measured off the placed arrays' shards, not inferred."""
        arrs = list(jax.tree.leaves(self.op)) + [self.diag_inv]
        if self.gram_inv is not None:
            arrs.append(self.gram_inv)
        per: dict = {}
        for a in arrs:
            for s in a.addressable_shards:
                per[s.device.id] = per.get(s.device.id, 0) + int(s.data.nbytes)
        return max(per.values())

    def _axes(self):
        axes = tuple(self.block_axes)
        return axes, (axes if len(axes) > 1 else axes[0])

    def _solve_program(
        self,
        num_epochs: int,
        inner_iters: int,
        has_ref: bool,
        tol: float | None,
        warm_kind: str | None = None,
        block_history: bool = False,
        per_block: bool = False,
    ):
        key = (num_epochs, inner_iters, has_ref, tol, warm_kind,
               block_history, per_block)
        run = self._jit_cache.get(key)
        if run is None:
            axes, red = self._axes()
            num_shards = self.num_shards
            sharded = P(axes)
            # the x0 warm start (sessions) is a REPLICATED (n, k) predicted
            # solution — every shard projects it onto its own blocks; the
            # masked serving pair replicates both halves
            warm_spec = (P(), P()) if warm_kind == "masked" else P()
            # per-block dynamics: γ is a (J,) vector sharded like the
            # blocks (each shard reads only its own γ_j slice) and η the
            # pair (η_vec (J,) sharded, η̄ replicated scalar) — the
            # weighted eq. 7 runs on local slices, no new collectives
            in_specs = (
                self.op.shard_spec(axes),  # operator pytree, block-sharded
                sharded,  # diag_inv (J, p_pad, 1)
                sharded if self.gram_inv is not None else P(),  # gram_inv
                sharded,  # bvecs (J, p_pad, k)
                sharded if per_block else P(),  # gamma
                (sharded, P()) if per_block else P(),  # eta
                P(),  # ref (replicated) or None
                warm_spec,  # x0 (replicated) or None
            )
            # Without tol, the k-length residual is REPORTING only: emit
            # each shard's partial sum through the out_specs (stacked on
            # axis 0) and collapse them in ONE post-scan reduction, so the
            # epoch pays a single collective — the n·k consensus pmean.
            # With tol armed, the in-scan early exit needs the global
            # residual every epoch to gate the freeze (a replicated
            # predicate — every shard must take the same cond branch), so
            # the k-length psum stays in the epoch.
            partial_resid = tol is None
            rs = sharded if partial_resid else P()
            # the inner-CG depth counts are reporting only, tol or not:
            # per-shard counts ride out the same way, max taken post-scan
            hist_spec = {
                "residual_sq": rs,
                "inner_iters": sharded,
                "initial": {"residual_sq": rs, "inner_iters": sharded},
            }
            if block_history:
                # per-block rows are block-SHARDED by construction: each
                # shard's (E, J_loc, k) trace concatenates along the block
                # axis into the global (E, J, k) — diagnostics ride the
                # out_specs with ZERO extra in-scan collectives
                hist_spec["block_residual_sq"] = P(None, axes)
                hist_spec["initial"]["block_residual_sq"] = P(axes)
            if has_ref:
                hist_spec["mse"] = P()
                hist_spec["initial"]["mse"] = P()

            def solve_phase(op, diag_inv, gram_inv, bvecs, gamma, eta, ref,
                            x0):
                return consensus_epochs(
                    op, diag_inv, gram_inv, bvecs, gamma, eta, ref,
                    direct=self.gram_solver == "direct",
                    inner_iters=inner_iters,
                    inner_tol=self.inner_tol,
                    use_kernels=self.use_kernels,
                    warm_start=self.warm_start,
                    tol2=None if tol is None else float(tol) ** 2,
                    num_epochs=num_epochs,
                    # the loop means over the LOCAL blocks, pmean over the
                    # mesh: the global consensus average in ONE n·k
                    # collective
                    pmean=lambda a: jax.lax.pmean(a, red),
                    psum=(
                        consensus.identity if partial_resid
                        else (lambda a: jax.lax.psum(a, red))
                    ),
                    x0=x0,
                    block_history=block_history,
                )

            inner = shard_map_unchecked(
                solve_phase,
                mesh=self.mesh,
                in_specs=in_specs,
                out_specs=(P(), hist_spec),
            )

            def run_fn(op, diag_inv, gram_inv, bvecs, gamma, eta, ref, x0):
                xbar, hist = inner(
                    op, diag_inv, gram_inv, bvecs, gamma, eta, ref, x0
                )
                # per-shard values came back stacked on axis 0: (D·E, k) /
                # (D·k,) — collapse them to the global ones
                k = bvecs.shape[-1]
                initial = dict(hist["initial"])
                hist["inner_iters"] = jnp.max(
                    hist["inner_iters"].reshape(num_shards, num_epochs, k),
                    axis=0,
                )
                initial["inner_iters"] = jnp.max(
                    initial["inner_iters"].reshape(num_shards, k), axis=0
                )
                if partial_resid:
                    hist["residual_sq"] = jnp.sum(
                        hist["residual_sq"].reshape(
                            num_shards, num_epochs, k
                        ),
                        axis=0,
                    )
                    initial["residual_sq"] = jnp.sum(
                        initial["residual_sq"].reshape(num_shards, k), axis=0
                    )
                hist["initial"] = initial
                return xbar, hist

            run = jax.jit(run_fn)
            self._jit_cache[key] = run
        return run
