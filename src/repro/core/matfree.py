"""Matrix-free prepared solver: block projections via SpMV + inner Gram solves.

The dense path densifies every row block before QR. At 99%+ sparsity that
densification IS the memory wall — the factors (W_j, R_j) cost O(J·p·n)
dense no matter how sparse A is. Azizan-Ruhi et al. (arXiv:1708.01413)
define the block projection directly as

    P_j x = x − A_jᵀ (A_j A_jᵀ)⁻¹ A_j x

which needs only sparse products with A_j / A_jᵀ plus an inner solve of the
(p, p) Gram system. This module runs exactly that: blocked-ELL SpMV
(``repro.sparse.bsr``) feeding an inner solve of (A_j A_jᵀ) y = A_j v — no
dense row blocks, no n×n anything. Two inner solvers share the epoch:

  * ``gram_solver="direct"`` — a per-block pseudo-inverse of the (p, p)
    Gram, precomputed once at prepare time and applied as ONE batched
    einsum per epoch. O(J·p²) memory, the same order the paper's own QR
    factors cost — tiny next to the O(J·p·n) dense blocks — and on small
    Gram systems it replaces the whole inner iteration with a single MXU
    contraction.
  * ``gram_solver="pcg"`` — the Jacobi-preconditioned CG on the sparse
    blocked-ELL Gram shards, batched across all J blocks and k columns,
    for systems whose p² dense Gram inverse would not fit. One iteration
    is one small (p, p) SpMV.

``"auto"`` (the default) picks "direct" while the stacked inverses stay
under ``DIRECT_GRAM_BYTES`` and "pcg" beyond.

The HOT-LOOP STRUCTURE (this file's perf contract) makes one outer epoch a
single fused pass over the forward tiles plus the inner Gram solve, by
carrying the probe ``z_j = A_j x̄`` through the epoch loop
(``repro.core.consensus.consensus_scan``):

  * ``z`` doubles as the residual metric AND the projection input: the
    paper's iterates keep A_j x_j = b_j invariant (every update moves
    inside the block solution set), so A_j(x̄ − x_j) = z_j − b_j — no
    second forward product. The PCG inner solve stops at a residual r_cg
    (at ``inner_tol`` or at the ``inner_iters`` cap), so that path holds
    the invariant A_j x_j = b_j + γ·r_cg instead, carrying ``w_j = A_j
    x_j`` for FREE from the CG residual. Each epoch folds the carried
    defect back into its Gram right-hand side, v_j = z_j − w_j − (b_j −
    w_j)/γ: the step x_j ← x_j + γ(x̄ − x_j − A_jᵀy_j) then lands on
    A_j x_j⁺ = b_j + γ·r_cg, so the blocks keep projecting onto
    {A_j x = b_j} and the inner error shrinks with the step instead of
    accumulating into a nearby system the consensus would settle on.
    Elementwise only: no tile pass, no collective. (γ is γ_j under
    per-block dynamics.)
  * ``z`` is reconstructed each epoch from the identity
    x̄⁺ = KNOWN − (ηγ/J)·Σ_j A_jᵀy_j, where KNOWN depends only on state
    available BEFORE the transpose product. That is what makes the two
    tile products of an epoch — A_j·KNOWN (forward) and A_jᵀy_j
    (transpose) — simultaneously available, so
    ``PartitionedBSR.fused_project`` (and the fused Pallas kernel under
    ``use_kernels=True``) computes both from ONE pass over the ELL tiles
    instead of the three separate passes (projection matvec, scatter-add
    rmatvec, residual matvec) the pre-fusion epoch paid.

Zero padding rows (see ``PartitionedBSR``) make the Gram matrix singular on
the padded coordinates; both inner solvers return exact zeros there (the
pseudo-inverse by masked construction, the CG because its iterates stay
pinned at zero under zero RHS rows and zero Jacobi weights), so ``A_jᵀ y``
— the only quantity the projection uses — is unique regardless (the Gram
nullspace is annihilated by A_jᵀ).

The outer consensus iteration is the paper's eqs. (5)–(7) unchanged;
``inner_iters`` caps the CG depth per projection (a (p, p) SPD system: CG
is exact at p steps, and with the Jacobi preconditioner on
diagonally-dominant Schenk-like Grams it converges far earlier). Per-column
effective inner iteration counts are recorded every epoch in
``history["inner_iters"]`` (the direct solver reports depth 1 — one exact
application) — the matfree analogue of the dense path's per-column epoch
reporting.

``solve(..., tol=...)`` arms the loop's masked in-scan early exit: each
epoch the per-column residual (read off the carried probe ``z``) gates the
consensus update under ``jnp.where``, so converged columns freeze — their
projector work stops, and for the PCG path they stop driving the inner-CG
depth — while the batch keeps its one compiled shape; once EVERY column is
frozen the whole epoch body short-circuits to a carry-through
(``lax.cond``), so trailing epochs cost vector ops only.
"""
from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import consensus
from repro.core.prepared import SolveOptions, SolveResult
from repro.core.spectra import (
    dynamics_arrays as _dynamics_arrays,
    dynamics_meta as _dynamics_meta,
    dynamics_state as _dynamics_state,
)
from repro.obs.trace import span
from repro.sparse.bsr import DEFAULT_BLOCK_SHAPE, PartitionedBSR
from repro.sparse.matrix import COOMatrix

_HIGHEST = jax.lax.Precision.HIGHEST  # full f32 (see repro.core.projections)

# matfree applies the SAME projection for classical and decomposed APC (the
# two differ only in how the DENSE path factorizes it)
MATFREE_METHODS = ("apc", "dapc")

GRAM_SOLVERS = ("auto", "direct", "pcg")
# auto goes direct while the stacked (J, p_pad, p_pad) Gram inverses fit
DIRECT_GRAM_BYTES = 64 * 1024 * 1024


def _coldot(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """⟨a, b⟩ over the row axis, kept broadcastable: (J, p, k) -> (J, 1, k)."""
    return jnp.sum(a * b, axis=1, keepdims=True)


def _pcg_gram(
    op: PartitionedBSR,
    rhs: jnp.ndarray,  # (J, p_pad, k)
    diag_inv: jnp.ndarray,  # (J, p_pad, 1) Jacobi weights (0 on padded rows)
    iters: int,
    tol: float,
    use_kernels: bool,
    warm: jnp.ndarray | None = None,  # previous epoch's solution, same shape
    active: jnp.ndarray | None = None,  # (k,) bool: columns that still count
):
    """Solve (A_j A_jᵀ) Y = rhs per block and column.

    One iteration is one SMALL SpMV with the stored sparse Gram shards
    (``op.gram_mv``). The loop exits as soon as every ACTIVE column's
    worst-block relative residual drops below ``tol`` (``iters`` is the
    hard cap) — on diagonally-dominant Schenk-like Grams the
    Jacobi-preconditioned iteration converges in a handful of steps, and a
    ``while_loop`` lets the compiled program actually stop there instead of
    burning the cap. ``warm`` seeds the iteration with the previous outer
    epoch's solution; ``active`` masks converged outer columns out of the
    stopping test, so frozen batchmates stop forcing depth on everyone.

    Returns (Y, iters_used (k,), final residual rhs − G·Y). The residual
    is what makes the caller's ``w = A_j x_j`` tracking free — see the
    module docstring.
    """
    rhs_sq = jnp.maximum(_coldot(rhs, rhs), 1e-30)

    def rel_resid(r):  # (k,): worst-block relative residual per column
        return jnp.max(_coldot(r, r) / rhs_sq, axis=0)[0]

    def not_done(rel):  # (k,): columns still above tolerance (and active)
        live = rel > tol * tol
        return live if active is None else live & active

    if warm is None:
        y = jnp.zeros_like(rhs)
        r = rhs
    else:
        y = warm
        r = rhs - op.gram_mv(warm, use_kernels)
    z = diag_inv * r
    p = z
    rz = _coldot(r, z)
    it0 = jnp.zeros((), jnp.int32)
    counts0 = jnp.zeros(rhs.shape[-1], jnp.int32)

    def cond(state):
        _, r, _, _, it, _ = state
        return (it < iters) & jnp.any(not_done(rel_resid(r)))

    def body(state):
        y, r, p, rz, it, counts = state
        ap = op.gram_mv(p, use_kernels)
        alpha = rz / jnp.maximum(_coldot(p, ap), 1e-30)
        y = y + alpha * p
        r = r - alpha * ap
        z = diag_inv * r
        rz_new = _coldot(r, z)
        beta = rz_new / jnp.maximum(rz, 1e-30)
        p = z + beta * p
        counts = counts + not_done(rel_resid(r)).astype(jnp.int32)
        return (y, r, p, rz_new, it + 1, counts)

    y, r, _, _, it, counts = jax.lax.while_loop(
        cond, body, (y, r, p, rz, it0, counts0)
    )
    # report the depth at which each column's worst block first converged; a
    # column that never entered the loop (warm start already below tol, or
    # masked inactive) reports a true 0
    used = jnp.minimum(counts + jnp.minimum(it, 1), iters)
    return y, used, r


def pcg_gram_iters(
    hist: dict, num_epochs: int, epochs_run: int, warm_start: bool
) -> int:
    """Gram SpMVs a PCG-path solve ran, read off its history: the set-up
    solve's depth plus each epoch's deepest column (a frozen epoch of a
    ``tol`` solve records zeros, so only live epochs add). A sharded
    history already holds each column's deepest shard, so each epoch
    counts its slowest shard: the SpMVs between two all-reduces. A
    ``warm_start`` solve runs one more in each of its ``epochs_run`` live
    epochs, the warm residual, which no depth count records."""
    per_epoch = np.reshape(hist["inner_iters"], (num_epochs, -1)).max(axis=1)
    setup = np.max(hist["initial"]["inner_iters"])
    warm = epochs_run if warm_start else 0
    return int(setup + per_epoch.sum() + warm)


def _gram_pinv(op: PartitionedBSR, dtype) -> np.ndarray:
    """Per-block dense pseudo-inverse of the Gram shards, (J, p_pad, p_pad),
    as a host array (the caller places it).

    Built host-side in float64 from the (near-diagonal) sparse Gram, in
    whichever form it is stored (``PartitionedBSR.gram_dense``), and
    restricted to the nonsingular sub-block (padding rows — and any exactly
    dependent rows — are annihilated by the pseudo-inverse, matching the CG
    iterates staying pinned at zero there). O(J·p³) once at prepare time.

    The rank cutoff is pinned to the TILE dtype's noise floor, not pinv's
    1e-15 default: a rank-deficient block (more rows than columns — a tall
    block of a ragged ``PartitionPlan``) has true zero eigenvalues that
    float32 tile products smear up to ~ε₃₂·λmax, and inverting that noise
    turns the projector into garbage. Full-rank Grams have no eigenvalues
    near either cutoff, so their inverse is unchanged bit for bit.
    """
    rcond = float(np.finfo(op.fwd_data.dtype).eps) * op.p_pad
    dense = op.gram_dense()
    out = np.zeros_like(dense)
    for j, G in enumerate(dense):
        live = np.flatnonzero(np.diag(G) > 0)
        if live.size:
            sub = np.linalg.pinv(
                G[np.ix_(live, live)], rcond=rcond, hermitian=True
            )
            out[j][np.ix_(live, live)] = sub
    return out.astype(dtype)


def consensus_epochs(
    op: PartitionedBSR,
    diag_inv: jnp.ndarray,
    gram_inv: jnp.ndarray | None,
    bvecs: jnp.ndarray,  # (J_loc, p_pad, k)
    gamma,
    eta,
    ref,  # (n,) | (n, k) | None
    *,
    direct: bool,
    inner_iters: int,
    inner_tol: float,
    use_kernels: bool,
    warm_start: bool,
    tol2: float | None,
    num_epochs: int,
    pmean=consensus.identity,
    psum=consensus.identity,
    x0=None,  # (n, k) predicted solution, or masked pair ((n, k), (k,))
    block_history: bool = False,  # per-block residual diagnostics
):
    """The fused-projection consensus iteration, mesh-agnostic: the
    matrix-free caller of ``repro.core.consensus.consensus_scan``.

    ``op``/``bvecs`` hold whatever set of partition blocks this caller owns
    — ALL J blocks on a single host, or one shard's J_loc blocks inside a
    ``shard_map`` (repro.core.matfree_sharded). The two reduction hooks
    are the only places global information enters, and the loop applies
    them: ``pmean`` to the local block mean of the consensus average
    (eqs. 5/7), the ONE n·k-payload collective of a sharded epoch, and
    ``psum`` to the k-length residual partial sums. A sharded caller with
    no in-scan use for the global residual (no ``tol``) keeps ``psum`` the
    identity and collapses the emitted partials after the scan instead,
    dropping the epoch to ONE collective.

    What this function owns is what is matrix-free (module docstring):
    the initial iterates, both Gram solvers, the fused tile pass, and the
    carried state ``aux = (q, w, z, y)`` —

      * ``q``, the global block mean eq. (7) formed last epoch (η_j-
        weighted under per-block dynamics): the next epoch's fused operand
        KNOWN needs it, and recomputing it would double the payload;
      * ``w_j = A_j x_j``, which the PCG path's defect fold reads;
      * the probe ``z_j = A_j x̄``, which is both the Gram right-hand side
        and the residual the loop records;
      * the last Gram solution ``y``, the PCG warm start.

    The inner-CG depth counts in ``history["inner_iters"]`` are this
    caller's own blocks'; a sharded caller takes their max over shards
    after the scan. ``block_history`` rows read the same probe, no extra
    tile pass; sharded callers ride them through their ``out_specs``.

    Returns ``(x̄ (n, k), history)`` with the same history contract as
    ``MatrixFreePreparedSolver.solve`` documents.
    """
    ones = jnp.ones(bvecs.shape[-1], jnp.int32)
    gam, eta_col, eta_bar, per_block = consensus.dynamics(gamma, eta, 3)

    # eqs. (2-3) matfree: min-norm x_j(0) = A_jᵀ (A_jA_jᵀ)⁻¹ b_j — or, with
    # an ``x0`` warm start (sessions), the PROJECTION of the prediction
    # onto each block's solution set: x_j(0) = x0 + A_jᵀ(A_jA_jᵀ)⁻¹(b_j −
    # A_j x0). Shard-local except the one forward product; the masked pair
    # zeroes cold columns' shift so they take the plain init exactly, and
    # the carried-probe algebra is untouched: A_j x_j(0) = b_j − r0 holds
    # for any shift (the shift's forward product cancels), so w0 below is
    # unchanged.
    if x0 is not None:
        xq, mk = x0 if isinstance(x0, tuple) else (x0, None)
        if mk is not None:
            xq = jnp.where(mk, xq, jnp.zeros((), xq.dtype))
        u0 = bvecs - op.matvec(xq, use_kernels)
    else:
        xq, u0 = None, bvecs
    if direct:
        y0 = jnp.einsum("jqp,jpk->jqk", gram_inv, u0, precision=_HIGHEST)
        setup_iters, r0 = ones, jnp.zeros_like(bvecs)
    else:
        y0, setup_iters, r0 = _pcg_gram(
            op, u0, diag_inv, inner_iters, inner_tol, use_kernels,
        )
    x0s = op.rmatvec(y0, use_kernels)
    if xq is not None:
        x0s = x0s + xq
    # the CG residual hands back w0 = A_j x_j(0) = b_j − r0 for free (an x0
    # shift included); the first epoch folds the defect r0 back in
    w0 = bvecs - r0
    xbar0 = pmean(jnp.mean(x0s, axis=0))  # eq. (5)
    z0 = op.matvec(xbar0, use_kernels)  # probe of x̄_0
    # per-block: q carries the weighted mean — one extra collective at
    # INIT only, outside the scan (the per-epoch budget is untouched)
    q0 = pmean(jnp.mean(eta_col * x0s, axis=0)) if per_block else xbar0

    def project(xs, xbar, aux, active):
        q, w, z, ywarm = aux
        u = z - w  # A_j (x̄ − x_j)
        if direct:
            y = jnp.einsum("jqp,jpk->jqk", gram_inv, u, precision=_HIGHEST)
            used, w_new = ones, w
        else:
            # fold the carried defect b_j − w_j back in, so the step lands
            # A_j x_j⁺ on b_j + γ·r and not on w_j + γ·r (module docstring)
            u = u - (bvecs - w) / gam
            y, used, r = _pcg_gram(
                op, u, diag_inv, inner_iters, inner_tol, use_kernels,
                warm=ywarm if warm_start else None, active=active,
            )
            w_new = bvecs + gam * r  # A_j x_j⁺ = b_j + γ·r
        # x̄⁺ = KNOWN − (ηγ/J)·Σ_j A_jᵀy_j in exact arithmetic, and KNOWN
        # needs no transpose product — so the epoch's two tile
        # contractions run in ONE fused pass. The trajectory itself stays
        # float-CANONICAL (the loop's eqs. 6–7); KNOWN only serves as the
        # fused forward operand, and ``advance`` patches the probe with the
        # exact float difference x̄⁺ − KNOWN, keeping z accurate to ULP
        # instead of compounding reassociation noise across epochs.
        if per_block:  # KNOWN is only a linearization point: the probe
            # patch restores exactness for any approximation here
            known = q + (1.0 - eta_bar) * xbar
        else:
            known = eta * q + eta * gamma * (xbar - q) + (1.0 - eta) * xbar
        f, g = op.fused_project(known, y, use_kernels)
        return xbar[None] - xs - g, (known, f, w_new, y, used)

    def advance(mid, xbar_new, q_new):
        known, f, w_new, y, used = mid
        z_new = f + op.matvec(xbar_new - known, use_kernels)
        return (q_new, w_new, z_new, y), {"inner_iters": used}

    return consensus.consensus_scan(
        x0s, xbar0, project, lambda xbar, aux: aux[2] - bvecs,
        gamma=gamma, eta=eta, num_epochs=num_epochs,
        aux=(q0, w0, z0, jnp.zeros_like(y0)), advance=advance,
        counts={"inner_iters": setup_iters},
        pmean=pmean, psum=psum, x_ref=ref, tol2=tol2,
        block_history=block_history,
    )


@dataclasses.dataclass
class MatrixFreePreparedSolver:
    """Sparse-operator counterpart of ``PreparedSolver``.

    Produced by ``prepare(A, mode="matfree")`` (or mode="auto" past the
    memory threshold); reusable across any number of ``solve`` calls and
    pool-compatible with the serving queue (same ``solve`` contract, same
    ``SolveResult``).
    """

    op: PartitionedBSR
    method: str
    gamma: float
    eta: float
    inner_iters: int
    inner_tol: float
    use_kernels: bool
    setup_seconds: float
    diag_inv: jnp.ndarray = dataclasses.field(repr=False, default=None)
    gram_solver: str = "direct"  # resolved: "direct" | "pcg"
    gram_inv: jnp.ndarray | None = dataclasses.field(repr=False, default=None)
    warm_start: bool = False
    partition: str = "uniform"  # "uniform" | "cost_aware"
    dynamics: str = "global"  # default solve dynamics: "global" | "per_block"
    plan: object | None = dataclasses.field(repr=False, default=None)
    block_gamma_weights: np.ndarray | None = dataclasses.field(
        repr=False, default=None
    )
    block_eta_weights: np.ndarray | None = dataclasses.field(
        repr=False, default=None
    )
    block_spectra: dict | None = dataclasses.field(repr=False, default=None)
    num_solves: int = 0
    _jit_cache: dict = dataclasses.field(default_factory=dict, repr=False)

    path = "matfree"

    @property
    def mode(self) -> str:
        return "matfree"

    @property
    def num_blocks(self) -> int:
        return self.op.num_blocks

    @property
    def num_cols(self) -> int:
        return self.op.num_cols

    @property
    def block_rows(self) -> int:
        return self.op.p_pad

    @property
    def memory_bytes(self) -> int:
        """Device-resident operator bytes (the matfree 'factors')."""
        total = self.op.nbytes + int(self.diag_inv.nbytes)
        if self.gram_inv is not None:
            total += int(self.gram_inv.nbytes)
        return total

    @property
    def dense_memory_bytes(self) -> int:
        """What the dense path's (J, p, n) blocks alone would cost."""
        return self.op.dense_bytes

    @property
    def num_shards(self) -> int:
        """Devices the blocks are split over: one on a single host."""
        return 1

    @property
    def gram_form(self) -> str:
        """How a solve applies the Gram product: the PCG path's stored form,
        "dia" or "ell" (``PartitionedBSR.gram_form``), or "none" on the
        direct path, which applies the Gram inverses instead."""
        return "none" if self.gram_solver == "direct" else self.op.gram_form

    @property
    def gram_bytes(self) -> int:
        """Bytes one Gram product reads on each device: its share of the
        stored form (0 on the direct path)."""
        if self.gram_solver == "direct":
            return 0
        return self.op.gram_bytes // self.num_shards

    def block_rhs(self, b) -> jnp.ndarray:
        """RHS (m,) or (m, k) -> (J, p_pad, k), plan-aware.

        With a cost-aware ``plan`` the original-order rows scatter to their
        plan slots (the operator's own uniform scatter would misplace
        them); without one this is exactly ``op.block_rhs``.
        """
        if self.plan is None:
            return self.op.block_rhs(b)
        b = np.asarray(b)
        if b.ndim == 1:
            b = b[:, None]
        m = self.op.shape[0]
        if b.shape[0] != m:
            raise ValueError(f"expected {m} rows, got {b.shape[0]}")
        out = np.zeros(
            (self.num_blocks * self.op.p_pad, b.shape[1]),
            self.op.fwd_data.dtype,
        )
        out[self.plan.flat_slots(self.op.p_pad)] = b
        return jnp.asarray(out.reshape(self.num_blocks, self.op.p_pad, -1))

    def _resolve_dynamics(self, dynamics: str | None) -> bool:
        """Map a solve-time ``dynamics`` override to the per-block flag."""
        dyn = self.dynamics if dynamics is None else dynamics
        if dyn not in ("global", "per_block"):
            raise ValueError(f"dynamics must be 'global'|'per_block', got {dyn!r}")
        if dyn == "per_block" and self.block_eta_weights is None:
            raise ValueError(
                "per-block dynamics need spectral weights: prepare with "
                "dynamics='per_block'"
            )
        return dyn == "per_block"

    def _dynamics_operands(self, gamma, eta, dtype, per_block: bool):
        """(γ, η) scan operands: scalars, or per-block vectors scaled by the
        prepared spectral weights (η arrives as the (vector, mean) pair the
        weighted eq. 7 consumes — the mean is precomputed host-side so the
        sharded path adds zero collectives)."""
        if not per_block:
            return jnp.asarray(gamma, dtype), jnp.asarray(eta, dtype)
        gv = np.asarray(self.block_gamma_weights, np.float64) * float(gamma)
        ev = np.asarray(self.block_eta_weights, np.float64) * float(eta)
        return jnp.asarray(gv, dtype), (
            jnp.asarray(ev, dtype), jnp.asarray(ev.mean(), dtype)
        )

    def _warm_operand(self, x0, batched: bool, dtype):
        """Normalize an ``x0`` warm start to the internal batched-k shape
        ((n, k) even for a single RHS — matching ``block_rhs``)."""
        if x0 is None:
            return None
        if isinstance(x0, tuple):
            arr, mask = x0
            return (jnp.asarray(arr, dtype), jnp.asarray(mask, bool))
        arr = np.asarray(x0)
        if not batched and arr.ndim == 1:
            arr = arr[:, None]
        return jnp.asarray(arr, dtype)

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
                    x0=x0,
                    block_history=block_history,
                )

            run = jax.jit(solve_phase)
            self._jit_cache[key] = run
        return run

    def solve(
        self,
        b: np.ndarray,  # (m,) single RHS or (m, k) column batch
        num_epochs: int = 100,
        gamma: float | None = None,
        eta: float | None = None,
        x_ref: np.ndarray | None = None,
        inner_iters: int | None = None,
        tol: float | None = None,
        x0: np.ndarray | tuple | None = None,
        block_history: bool = False,
        dynamics: str | None = None,
    ) -> SolveResult:
        """Consensus solve against the cached sparse operator.

        Matches the dense ``PreparedSolver.solve`` contract (batched RHS,
        per-epoch ``residual_sq``/``mse`` history, ``per_column`` scatter);
        additionally records the per-column inner solve depth each epoch in
        ``history["inner_iters"]``. ``tol`` arms the masked in-scan early
        exit: a column whose residual satisfies ``residual_sq <= tol²``
        freezes (its consensus update and projector work stop) while the
        batch keeps its one compiled shape — per-column epochs-to-tolerance
        still read out of ``iterations_to_tol`` exactly as without masking.

        ``x0`` warm-starts the consensus state at a predicted solution
        (the ``Session`` hook, same contract as the dense path): block
        initial iterates become projections of ``x0`` onto each block's
        solution set — one extra forward product plus the usual inner Gram
        solve. ``(n,)``/``(n, k)``, or the masked ``(x0, mask)`` pair for
        mixed warm/cold serving batches.

        ``num_epochs`` may be a ``SolveOptions``: ``solve(b,
        SolveOptions(...))`` is the typed equivalent of the kwargs form
        (same declared surface on every path, including sharded).

        ``block_history=True`` records ``history["block_residual_sq"]``
        (per-epoch per-block residuals off the carried probe — no extra
        tile pass; see ``repro.obs.convergence`` for the diagnostics
        built on it). The default leaves the compiled program untouched.

        ``dynamics`` overrides the prepared default per call: ``"global"``
        runs the scalar (γ, η) program (bit-identical to a global-prepared
        solver), ``"per_block"`` scales them by the prepared per-block
        spectral weights (requires ``prepare(..., dynamics="per_block")``).
        """
        if isinstance(num_epochs, SolveOptions):
            return self.solve(b, **num_epochs.kwargs())
        gamma = self.gamma if gamma is None else gamma
        eta = self.eta if eta is None else eta
        inner_iters = self.inner_iters if inner_iters is None else inner_iters
        per_block = self._resolve_dynamics(dynamics)
        b = np.asarray(b)
        batched = b.ndim == 2
        dtype = self.op.fwd_data.dtype
        tol = None if tol is None else float(tol)
        with span(
            "solve", path=self.path, k=b.shape[1] if batched else 1,
            num_epochs=num_epochs, gram_form=self.gram_form,
            gram_bytes=self.gram_bytes,
        ) as solve_span:
            with span("solve.rhs"):
                bvecs = self.block_rhs(b)  # (J, p_pad, k); k=1 for one RHS
                ref = None if x_ref is None else jnp.asarray(x_ref, dtype)
                warm = self._warm_operand(x0, batched, dtype)
                gamma_op, eta_op = self._dynamics_operands(
                    gamma, eta, dtype, per_block
                )
            with span("solve.run") as run_span:
                run = self._solve_program(
                    num_epochs, inner_iters, ref is not None, tol,
                    warm_kind=None if warm is None else (
                        "masked" if isinstance(warm, tuple) else "x0"
                    ),
                    block_history=bool(block_history),
                    per_block=per_block,
                )
                x, hist = run(
                    self.op, self.diag_inv, self.gram_inv, bvecs,
                    gamma_op, eta_op, ref, warm,
                )
                x = jax.block_until_ready(x)
            with span("solve.fetch"):
                x = np.asarray(x)
                hist = jax.tree.map(np.asarray, hist)
                if not batched:  # collapse the internal k=1 axis like dense
                    x = x[:, 0]
                    hist = jax.tree.map(
                        lambda a: a[..., 0] if a.ndim and a.shape[-1] == 1
                        else a, hist,
                    )
            epochs_run = consensus.live_epochs(hist, num_epochs, tol)
            # the direct path applies its Gram inverse once per live epoch
            gram_iters = (
                epochs_run if self.gram_solver == "direct"
                else pcg_gram_iters(
                    hist, num_epochs, epochs_run, self.warm_start
                )
            )
            solve_span.set(epochs_run=epochs_run, gram_iters=gram_iters)
        self.num_solves += 1

        return SolveResult(
            x=x,
            method=self.method,
            mode="matfree",
            num_blocks=self.num_blocks,
            num_epochs=num_epochs,
            history=hist,
            wall_seconds=run_span.seconds,
            gamma=gamma,
            eta=eta,
            num_rhs=b.shape[1] if batched else 1,
            epochs_run=epochs_run,
            gram_iters=gram_iters,
        )

    def open_session(self, **kwargs):
        """Open a streaming prediction-correction ``Session`` over this
        solver (``repro.core.session``) — same contract as the dense
        ``PreparedSolver.open_session``; the sharded solver inherits it."""
        from repro.core.session import Session

        return Session(self, **kwargs)

    # -- checkpoint serialization (repro.serving.checkpoint) -----------------

    def to_state(self) -> tuple[dict, dict]:
        """``(arrays, meta)`` capturing everything ``prepare_matfree`` built:
        the partitioned ELL operator (tiles, balance permutation, Gram
        shards), the Jacobi weights, and the direct path's Gram
        pseudo-inverses — i.e. the whole setup cost, so ``from_state`` is a
        warm restore. Mesh placement is NOT captured (the sharded subclass
        is rejected by the checkpoint store and re-prepared instead)."""
        arrays, op_meta = self.op.to_arrays()
        arrays["diag_inv"] = np.asarray(self.diag_inv)
        if self.gram_inv is not None:
            arrays["gram_inv"] = np.asarray(self.gram_inv)
        arrays.update(_dynamics_arrays(self))
        meta = {
            "path": "matfree",
            "method": self.method,
            "gamma": float(self.gamma),
            "eta": float(self.eta),
            "inner_iters": int(self.inner_iters),
            "inner_tol": float(self.inner_tol),
            "use_kernels": bool(self.use_kernels),
            "setup_seconds": float(self.setup_seconds),
            "gram_solver": self.gram_solver,
            "warm_start": bool(self.warm_start),
            "op": op_meta,
            **_dynamics_meta(self),
        }
        return arrays, meta

    @classmethod
    def from_state(cls, arrays, meta: dict) -> "MatrixFreePreparedSolver":
        """Rebuild from ``to_state`` output — same operator bytes, so
        ``solve`` results are bit-identical to the saved solver's."""
        return cls(
            op=PartitionedBSR.from_arrays(arrays, meta["op"]),
            method=meta["method"],
            gamma=meta["gamma"],
            eta=meta["eta"],
            inner_iters=int(meta["inner_iters"]),
            inner_tol=float(meta["inner_tol"]),
            use_kernels=meta["use_kernels"],
            setup_seconds=meta["setup_seconds"],
            diag_inv=jnp.asarray(arrays["diag_inv"]),
            gram_solver=meta["gram_solver"],
            gram_inv=(
                jnp.asarray(arrays["gram_inv"]) if "gram_inv" in arrays
                else None
            ),
            warm_start=meta["warm_start"],
            **_dynamics_state(arrays, meta),
        )


def prepare_matfree(
    A,
    method: str = "dapc",
    num_blocks: int = 8,
    dtype=None,
    gamma: float = 1.0,
    eta: float = 0.9,
    block_shape: tuple[int, int] = DEFAULT_BLOCK_SHAPE,
    inner_iters: int | None = None,
    inner_tol: float = 1e-6,
    use_kernels: bool = False,
    balance: bool = True,
    gram_solver: str = "auto",
    warm_start: bool = False,
    mesh=None,
    block_axes: tuple[str, ...] = ("data",),
    partition: str = "uniform",
    dynamics: str = "global",
    plan=None,
) -> MatrixFreePreparedSolver:
    """Matfree setup: COO -> partitioned blocked-ELL + inner Gram solver.

    ``A`` may be a ``COOMatrix`` (never densified) or a dense array
    (converted). ``gram_solver="auto"`` precomputes the per-block Gram
    pseudo-inverses while they fit ``DIRECT_GRAM_BYTES`` and falls back to
    the Jacobi-PCG on the sparse Gram shards beyond; "direct"/"pcg" force a
    path. ``inner_iters=None`` resolves to min(p_pad, 32) — the PCG cap;
    CG on the (p, p) Gram is exact at p steps, and the preconditioned
    iteration converges much earlier on diagonally-dominant systems.
    ``balance`` stores the ELL tiles in the slot-minimizing row order (a
    pure setup cost; the operator contract is order-invariant), and
    ``warm_start`` seeds each epoch's inner CG with the previous epoch's
    Gram solution (PCG path only).

    ``mesh`` places the prepared state block-sharded over the mesh's
    ``block_axes`` and returns a ``ShardedMatrixFreeSolver`` (same solve
    contract, shard_map execution — see ``repro.core.matfree_sharded``);
    ``num_blocks`` must divide evenly over the block-axis devices.

    ``partition="cost_aware"`` assigns rows to blocks via
    ``PartitionPlan.cost_aware`` (nnz-balanced, spectrally grouped — see
    ``repro.core.partition``) instead of the uniform contiguous split;
    ``dynamics="per_block"`` estimates per-block Gram spectra
    (``repro.core.spectra``) at prepare time and defaults ``solve`` to the
    per-block (γ_j, η_j) consensus. Both default off and leave the
    historical path bit-identical. ``plan`` injects a prebuilt plan
    (overrides ``partition``).
    """
    if method not in MATFREE_METHODS:
        raise ValueError(
            f"matfree path supports the consensus methods {MATFREE_METHODS}; "
            f"got {method!r} (use the dense path for it)"
        )
    if gram_solver not in GRAM_SOLVERS:
        raise ValueError(f"gram_solver must be one of {GRAM_SOLVERS}")
    if partition not in ("uniform", "cost_aware"):
        raise ValueError(
            f"partition must be 'uniform'|'cost_aware', got {partition!r}"
        )
    if dynamics not in ("global", "per_block"):
        raise ValueError(
            f"dynamics must be 'global'|'per_block', got {dynamics!r}"
        )
    t0 = time.perf_counter()
    coo = A if isinstance(A, COOMatrix) else COOMatrix.from_dense(np.asarray(A))
    dtype = np.dtype(dtype or np.float32)
    if plan is None and partition == "cost_aware":
        from repro.core.partition import PartitionPlan

        plan = PartitionPlan.cost_aware(coo, num_blocks)
    elif plan is not None:
        partition = "uniform" if plan.kind == "uniform" else "cost_aware"
    if plan is not None and plan.kind == "uniform":
        plan = None  # uniform plans take the historical path exactly
    cls, placement_kw, place = MatrixFreePreparedSolver, {}, jnp.asarray
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec

        from repro.core.matfree_sharded import (
            ShardedMatrixFreeSolver,
            mesh_block_devices,
        )

        block_axes = tuple(block_axes)
        num_devices = mesh_block_devices(mesh, block_axes)
        if num_blocks % num_devices:
            raise ValueError(
                f"num_blocks={num_blocks} not divisible over the "
                f"{num_devices} devices of mesh axes {block_axes}"
            )
        cls = ShardedMatrixFreeSolver
        placement_kw = {"mesh": mesh, "block_axes": block_axes}
        sharding = NamedSharding(mesh, PartitionSpec(block_axes))
        place = functools.partial(jax.device_put, device=sharding)
    # a mesh-bound operator is built in host memory and goes from there
    # straight to its shards, so no device ever holds it whole; everything
    # derived from it below is computed on the placed shards
    op = PartitionedBSR.from_coo(
        coo, num_blocks, block_shape, dtype,
        with_transpose=use_kernels,  # only the Pallas path streams A_jᵀ tiles
        with_gram=True,  # the inner-solve operator (near-diagonal, few % extra)
        balance=balance,
        plan=plan,
        host=mesh is not None,
    )
    if mesh is not None:
        op = op.place(mesh, block_axes)
    # relative-epsilon Jacobi clamp: padded rows stay 0, near-zero Gram
    # diagonals are bounded instead of exploding (see jacobi_weights)
    diag_inv = place(op.jacobi_weights())
    block_gamma_w = block_eta_w = spectra = None
    if dynamics == "per_block":
        from repro.core import spectra as spectra_mod

        spectra = spectra_mod.block_spectra_matfree(op)
        block_gamma_w, block_eta_w = spectra_mod.derive_dynamics(spectra)
    if gram_solver == "auto":
        inv_bytes = num_blocks * op.p_pad * op.p_pad * dtype.itemsize
        gram_solver = "direct" if inv_bytes <= DIRECT_GRAM_BYTES else "pcg"
    gram_inv = place(_gram_pinv(op, dtype)) if gram_solver == "direct" else None
    if inner_iters is None:
        inner_iters = min(op.p_pad, 32)
    jax.block_until_ready(diag_inv)
    setup_seconds = time.perf_counter() - t0

    return cls(
        op=op,
        method=method,
        gamma=gamma,
        eta=eta,
        inner_iters=int(inner_iters),
        inner_tol=float(inner_tol),
        use_kernels=use_kernels,
        setup_seconds=setup_seconds,
        diag_inv=diag_inv,
        gram_solver=gram_solver,
        gram_inv=gram_inv,
        warm_start=warm_start,
        partition=partition,
        dynamics=dynamics,
        plan=plan,
        block_gamma_weights=block_gamma_w,
        block_eta_weights=block_eta_w,
        block_spectra=spectra,
        **placement_kw,
    )
