"""Two-phase solver API: ``prepare(A) -> PreparedSolver``, then
``prepared.solve(b | B)`` — setup amortized across right-hand sides.

The paper's acceleration is precisely that setup (reduced QR + triangular
substitution, Algorithm 1 eqs. 1–4) is cheap relative to classical
inversion; serving many requests against the same system should not pay it
per request at all. ``prepare`` runs Algorithm 1 steps 1 (partition) and
the b-independent half of 2–3 (the QR factors W_j, R_j — or pseudoinverse +
dense projector for classical APC, or the Lipschitz step for DGD) exactly
once; every subsequent ``solve(b)`` performs only the O(n²) substitution
plus the consensus iteration.

``solve`` accepts one RHS ``(m,)`` or a column batch ``(m, k)``; the batched
form iterates all k systems in one compiled program — the projector
application becomes (J, p, n) × (J, n, k) einsums feeding the MXU — which is
how request batching in the serving path gets its throughput
(benchmarks/multirhs.py measures both effects).

The dapc projector's form is chosen at prepare time from the block shape
(``dapc.projector_form``): the implicit v − Wᵀ(W v) where it reads fewer
bytes an epoch than the materialized n×n P_j, else P_j; ``materialize_p``
pins either. ``PreparedSolver.projector_form`` reports the choice.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import apc, cg, consensus, dapc, dgd, projections
from repro.core.partition import (
    BlockMode,
    Partition,
    PartitionPlan,
    block_rhs,
    partition_matrix,
)
from repro.obs.trace import span
from repro.sparse.matrix import COOMatrix

_HIGHEST = jax.lax.Precision.HIGHEST  # full f32 (see repro.core.projections)

METHODS = ("apc", "dapc", "dgd", "cgnr")

# ``prepare(..., mode=...)`` accepts the dense block modes (tall/wide/auto)
# plus the execution-path selectors: "dense" forces the densified path,
# "matfree" the sparse-operator path (repro.core.matfree), and "auto" picks
# from the nnz/memory estimate below.
MATFREE_AUTO_DENSITY = 0.01  # auto never goes matfree below 99% sparsity
MATFREE_AUTO_BYTES = 64 * 1024 * 1024  # ... or when dense blocks fit easily


@dataclasses.dataclass(frozen=True)
class PrepareConfig:
    """The single source of truth for ``prepare()``'s keyword surface.

    ``prepare(A, PrepareConfig(...))`` and ``prepare(A, method=..., ...)``
    are equivalent; the dataclass exists so the keyword set is declared
    ONCE — the one-shot ``solve()`` derives its prepare/solve kwarg split
    from these fields instead of a hand-maintained tuple (which silently
    rotted every time ``prepare`` grew a knob), and serving code can pass
    a typed config around instead of a loose dict.

    Fields mirror ``prepare``'s parameters exactly; see its docstring for
    semantics. ``kwargs()`` flattens back to the keyword form (no deep
    copy — mesh objects pass through by reference).
    """

    method: str = "dapc"
    num_blocks: int = 8
    mode: str = "auto"  # BlockMode | "dense" | "matfree"
    dtype: Any = None
    gamma: float = 1.0
    eta: float = 0.9
    materialize_p: bool | None = None  # None: dapc.projector_form by shape
    use_kernels: bool = False
    block_shape: tuple[int, int] | None = None
    inner_iters: int | None = None
    inner_tol: float = 1e-6
    matfree_threshold_bytes: int | None = None
    balance: bool = True
    gram_solver: str = "auto"
    warm_start: bool = False
    mesh: Any = None
    block_axes: tuple[str, ...] = ("data",)
    partition: str = "uniform"  # "uniform" | "cost_aware" row->block plan
    dynamics: str = "global"  # "global" | "per_block" (γ_j, η_j) dynamics

    def kwargs(self) -> dict:
        """The equivalent ``prepare(A, **kwargs)`` keyword dict."""
        return {
            f.name: getattr(self, f.name) for f in dataclasses.fields(self)
        }

    @classmethod
    def field_names(cls) -> tuple[str, ...]:
        """Every keyword ``prepare`` consumes (the derived split's base)."""
        return tuple(f.name for f in dataclasses.fields(cls))


@dataclasses.dataclass(frozen=True)
class SolveOptions:
    """The single source of truth for ``solve()``'s keyword surface.

    The solve-side mirror of ``PrepareConfig``: ``prep.solve(b,
    SolveOptions(...))`` and ``prep.solve(b, num_epochs=..., ...)`` are
    equivalent on every execution path (dense, matfree, sharded — the
    options object is accepted POSITIONALLY where ``num_epochs`` sits, so
    no call site changes shape). Declaring the keyword set once lets the
    serving layer derive which request fields key a coalesced batch
    (``repro.serving.policy``) instead of hand-maintaining a twin list.

    ``None`` means "unset — use the solver's default"; only set fields are
    forwarded, so an option inapplicable to a path (``inner_iters`` on the
    dense solver) costs nothing unless explicitly set. ``method_kwargs``
    carries method-specific extras (``lr`` for dgd, ``avg_every``/
    ``compress``/``xbar0`` for the consensus methods) verbatim.
    """

    num_epochs: int = 100
    tol: float | None = None
    gamma: float | None = None
    eta: float | None = None
    x0: Any = None  # (n,) | (n, k) | (x0, mask) warm start (consensus only)
    x_ref: Any = None
    inner_iters: int | None = None  # matfree paths only
    block_history: bool | None = None  # per-block residual diagnostics
    # (consensus methods; see repro.obs.convergence)
    dynamics: str | None = None  # "global" | "per_block" override (consensus)
    method_kwargs: dict = dataclasses.field(default_factory=dict)

    def kwargs(self) -> dict:
        """The equivalent ``solve(b, **kwargs)`` keyword dict (set fields
        only; ``num_epochs`` always — it is the positional slot)."""
        out: dict = {}
        for f in dataclasses.fields(self):
            if f.name == "method_kwargs":
                continue
            value = getattr(self, f.name)
            if f.name == "num_epochs" or value is not None:
                out[f.name] = value
        out.update(self.method_kwargs)
        return out

    @classmethod
    def field_names(cls) -> tuple[str, ...]:
        """Every keyword ``solve`` consumes (the derived surface; excludes
        the ``method_kwargs`` passthrough)."""
        return tuple(
            f.name for f in dataclasses.fields(cls)
            if f.name != "method_kwargs"
        )


def _density(A) -> float:
    if isinstance(A, COOMatrix):
        m, n = A.shape
        return A.nnz / float(m * n)
    A = np.asarray(A)
    return np.count_nonzero(A) / float(A.size)


def resolve_path(
    A,
    num_blocks: int,
    mode: str,
    matfree_threshold_bytes: int | None = None,
) -> str:
    """Pick "dense" vs "matfree" from the mode plus an nnz/memory estimate.

    mode="auto" goes matfree only when BOTH hold: density <= 1% (blocked
    sparse formats lose to dense below that) and the dense path's resident
    arrays (blocks + factors, ~2 copies of (J, p, n)) would exceed the
    threshold (default 64 MiB) — small systems stay dense regardless.
    """
    if mode in ("tall", "wide", "dense"):
        return "dense"
    if mode == "matfree":
        return "matfree"
    if mode != "auto":
        raise ValueError(
            f"mode must be tall/wide/auto/dense/matfree, got {mode!r}"
        )
    threshold = (
        MATFREE_AUTO_BYTES if matfree_threshold_bytes is None
        else matfree_threshold_bytes
    )
    m, n = A.shape
    p = -(-m // num_blocks)
    dense_bytes = 2 * num_blocks * p * n * 4  # blocks + factors, f32
    if _density(A) <= MATFREE_AUTO_DENSITY and dense_bytes > threshold:
        return "matfree"
    return "dense"


@dataclasses.dataclass(frozen=True)
class ColumnResult:
    """Per-column view of a batched solve — what the serving queue scatters
    back to the request that contributed this column."""

    index: int  # column position in the (m, k) batch
    x: np.ndarray  # (n,)
    residual_sq: float  # final ||A x − b_i||²
    iterations: int  # epochs until residual_sq <= tol² (num_epochs if never)
    converged: bool  # True iff tolerance reached within the epoch budget


@dataclasses.dataclass(frozen=True)
class SolveResult:
    x: np.ndarray  # (n,) — or (n, k) for a batched solve
    method: str
    mode: str
    num_blocks: int
    num_epochs: int
    history: dict[str, Any]  # per-epoch metrics (mse / residual_sq)
    wall_seconds: float
    gamma: float | None = None
    eta: float | None = None
    num_rhs: int = 1
    # epochs in which the device ran the epoch body: the live epochs of an
    # apc/dapc or matfree ``tol`` solve (all-frozen epochs skip the body),
    # else ``num_epochs``
    epochs_run: int | None = None
    # matfree only: Gram SpMVs the inner solves ran (PCG: set-up depth plus
    # each live epoch's deepest column; direct: ``epochs_run``)
    gram_iters: int | None = None

    def _last(self, h):
        v = np.asarray(h[-1])
        return float(v) if v.ndim == 0 else v

    @property
    def final_mse(self):
        h = self.history.get("mse")
        return self._last(h) if h is not None else None

    @property
    def final_residual(self):
        return self._last(self.history["residual_sq"])

    def _residual_trace(self) -> np.ndarray:
        """Per-epoch residual_sq as (num_epochs, k) — k=1 for a single RHS."""
        h = self.history.get("residual_sq")
        if h is None:
            raise ValueError(f"method {self.method!r} recorded no residual history")
        trace = np.asarray(h)
        return trace[:, None] if trace.ndim == 1 else trace

    def iterations_to_tol(self, tol: float) -> np.ndarray:
        """Per-column epochs needed to reach ``residual_sq <= tol²``.

        A batched solve runs every column for the full epoch budget (one
        compiled scan), so a hard column cannot make its batchmates wrong —
        but it can hide that the easy columns were done long before the
        scan ended. This is the early-exit *report*: columns that never
        reach tolerance come back as ``num_epochs`` and are flagged
        ``converged=False`` in ``per_column``, so the serving layer can
        surface stragglers per request instead of per batch.
        """
        trace = self._residual_trace()  # (E, k)
        reached = trace <= float(tol) ** 2
        return np.where(
            reached.any(axis=0), reached.argmax(axis=0) + 1, self.num_epochs
        ).astype(np.int64)

    def per_column(self, tol: float | None = None) -> list[ColumnResult]:
        """Scatter a (possibly batched) result into per-column records.

        ``tol=None`` skips the tolerance sweep: every column reports the
        full ``num_epochs`` with ``converged`` judged against the final
        residual being finite.
        """
        x = self.x if self.x.ndim == 2 else self.x[:, None]
        trace = self._residual_trace()
        final = trace[-1]
        if tol is None:
            iters = np.full(x.shape[1], self.num_epochs, dtype=np.int64)
            conv = np.isfinite(final)
        else:
            iters = self.iterations_to_tol(tol)
            conv = iters < self.num_epochs
            conv |= final <= float(tol) ** 2  # converged exactly at the budget
        return [
            ColumnResult(
                index=i,
                x=np.asarray(x[:, i]),
                residual_sq=float(final[i]),
                iterations=int(iters[i]),
                converged=bool(conv[i]),
            )
            for i in range(x.shape[1])
        ]

    def assess_health(self, tol: float | None = None, watchdog=None):
        """Per-column NaN/stall verdict (``repro.core.guard.SolveHealth``).

        Host-side only: reads the residual history this result already
        carries — assessing (or not) never changes the solve program, so
        guarded and un-guarded solves are bit-identical.
        """
        from repro.core.guard import assess

        return assess(self, tol=tol, watchdog=watchdog)


def _as_warm_operand(x0, dtype):
    """Normalize a solve-time ``x0`` warm start to device operands.

    Accepts an ``(n,)``/``(n, k)`` prediction or the masked pair
    ``(x0, mask)`` the serving layer uses for mixed warm/cold batches
    (``mask`` is ``(k,)`` bool — True columns take the warm start)."""
    if x0 is None:
        return None
    if isinstance(x0, tuple):
        arr, mask = x0
        return (jnp.asarray(arr, dtype), jnp.asarray(mask, bool))
    return jnp.asarray(x0, dtype)


@dataclasses.dataclass
class PreparedSolver:
    """Partition + per-block factors + jitted projector, cached.

    Produced by ``prepare``; reusable (and read-only) across any number of
    ``solve`` calls. ``num_solves`` counts them (observability for serving).
    """

    blocks: jnp.ndarray  # (J, p, n)
    mode: str
    mixer: Any  # RowMixer: blocks new b's with the same padding rows as A
    method: str
    gamma: float
    eta: float
    use_kernels: bool
    factors: tuple  # method-specific cached setup (see prepare())
    projector: tuple  # ("dense"|"implicit"|"kernels", operand array) or ()
    setup_seconds: float
    # heterogeneity-aware partitioning + per-block dynamics (see
    # repro.core.partition / repro.core.spectra); all off by default —
    # the default solver is bit-identical to the historical one
    partition: str = "uniform"
    dynamics: str = "global"
    plan: Any = dataclasses.field(default=None, repr=False)  # PartitionPlan
    block_gamma_weights: Any = dataclasses.field(default=None, repr=False)
    block_eta_weights: Any = dataclasses.field(default=None, repr=False)
    block_spectra: Any = dataclasses.field(default=None, repr=False)
    num_solves: int = 0
    # consensus programs jitted per (epochs, options) — repeat solves of the
    # same request shape hit the XLA executable cache directly
    _jit_cache: dict = dataclasses.field(default_factory=dict, repr=False)

    path = "dense"  # the matfree counterpart lives in repro.core.matfree

    @property
    def num_blocks(self) -> int:
        return self.blocks.shape[0]

    @property
    def num_cols(self) -> int:
        return self.blocks.shape[2]

    @property
    def memory_bytes(self) -> int:
        """Device-resident bytes of the cached state (blocks + factors +
        projector), deduplicated — the cost the LRU pool bounds and the
        number ``benchmarks/sparse.py`` compares against the matfree path."""
        arrs = [self.blocks, *jax.tree.leaves(self.factors)]
        if self.projector:
            arrs.append(self.projector[1])
        seen: set[int] = set()
        total = 0
        for a in arrs:
            if hasattr(a, "nbytes") and id(a) not in seen:
                seen.add(id(a))
                total += int(a.nbytes)
        return total

    @property
    def projector_form(self) -> str | None:
        """How an epoch applies P_j: "dense", "implicit", "kernels", or
        None for the methods with no projector (dgd, cgnr)."""
        return self.projector[0] if self.projector else None

    @property
    def projector_bytes(self) -> int:
        """Bytes the projector reads per live epoch: each dense P_j once, or
        each factor W_j twice (W v, then Wᵀ u)."""
        if not self.projector:
            return 0
        kind, operand = self.projector
        return int(operand.nbytes) * (1 if kind == "dense" else 2)

    def _resolve_dynamics(self, dynamics: str | None) -> bool:
        """Resolve a solve-time ``dynamics`` override against the prepared
        state; returns True when the solve runs per-block (γ_j, η_j)."""
        mode = self.dynamics if dynamics is None else dynamics
        if mode not in ("global", "per_block"):
            raise ValueError(
                f"dynamics must be 'global' or 'per_block', got {mode!r}"
            )
        if mode == "global":
            return False
        if self.method not in ("apc", "dapc"):
            raise ValueError(
                "dynamics='per_block' needs a consensus method (apc/dapc); "
                f"this solver runs {self.method!r}"
            )
        if self.block_eta_weights is None:
            raise ValueError(
                "dynamics='per_block' needs per-block spectra — prepare "
                "with dynamics='per_block' to estimate them"
            )
        return True

    def _dynamics_operands(self, gamma, eta, per_block: bool):
        """(γ, η) device operands: scalars, or mean-preserving per-block
        vectors scaled by the prepared spectral weights."""
        if not per_block:
            return jnp.asarray(gamma), jnp.asarray(eta)
        dt = self.blocks.dtype
        gv = np.asarray(self.block_gamma_weights, np.float64) * float(gamma)
        ev = np.asarray(self.block_eta_weights, np.float64) * float(eta)
        return jnp.asarray(gv, dt), jnp.asarray(ev, dt)

    def _consensus_program(self, num_epochs: int, kwargs: dict):
        """Jitted substitution + consensus for the apc/dapc methods.

        The eager ``lax.scan`` re-traces its body on every call — fine for a
        one-shot solve, but it dominates per-request latency when serving.
        Jitting the whole solve phase keys the trace on (epochs, options);
        repeat requests of the same shape run straight from the executable
        cache. γ/η enter as traced scalars (retuning them is free) and the
        optional x_ref/xbar0 operands as pytrees (None = absent structure).
        """
        key = (num_epochs, tuple(sorted(kwargs.items())))
        run = self._jit_cache.get(key)
        if run is None:
            proj_kind = self.projector[0]

            # factor arrays enter as jit OPERANDS, not closure constants, so
            # they are never baked into the executable (compile-time + memory)
            def solve_phase(
                blocks, factors, proj, bvecs, gamma, eta, ref, warm, x0
            ):
                # x0 warm start (sessions): the per-block initial solutions
                # become the PROJECTION of the prediction onto each block's
                # solution set, x_j(0) = x0 + A_j⁺(b_j − A_j x0) — the
                # substitution is linear in its RHS, so this reuses the
                # cached factors on the shifted residual and the whole
                # consensus state (xs AND x̄) starts near the fixed point.
                # The masked form (x0, mask) zeroes cold columns' shift, so
                # they reduce to the plain eq. (2–3) init exactly — one
                # compiled program serves mixed warm/cold batches.
                if x0 is not None:
                    xq, mk = x0 if isinstance(x0, tuple) else (x0, None)
                    if mk is not None:
                        xq = jnp.where(mk, xq, jnp.zeros((), xq.dtype))
                    bv_eff = bvecs - jnp.einsum(
                        "jpn,n...->jp...", blocks, xq, precision=_HIGHEST
                    )
                else:
                    xq, bv_eff = None, bvecs
                if self.method == "dapc":
                    Ws, Rs = factors
                    x0s = dapc.initial_from_factors(
                        Ws, Rs, bv_eff, self.mode, self.use_kernels
                    )
                else:
                    x0s = apc.initial_from_pinv(factors[0], bv_eff)
                if xq is not None:
                    x0s = x0s + xq
                if proj_kind == "dense":
                    apply_fn = apc.make_apply(proj)
                else:
                    apply_fn = dapc.make_apply(
                        proj, False, use_kernels=proj_kind == "kernels"
                    )
                return consensus.run_consensus(
                    x0s,
                    apply_fn,
                    gamma,
                    eta,
                    num_epochs,
                    x_ref=ref,
                    blocks=blocks,
                    bvecs=bvecs,
                    xbar0=warm,
                    **kwargs,
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
        x0: np.ndarray | tuple | None = None,
        dynamics: str | None = None,
        **kwargs,
    ) -> SolveResult:
        """Solve A x = b against the cached factors (Algorithm 1 steps 5–8
        plus the per-b substitution); never re-partitions or re-factorizes.

        ``x0`` (consensus methods only) warm-starts the WHOLE consensus
        state at a predicted solution: each block's initial iterate is the
        projection of ``x0`` onto its solution set (exact substitution on
        the cached factors), so a good prediction converges in a handful
        of epochs — this is the ``Session`` prediction-correction hook.
        ``x0`` is ``(n,)`` / ``(n, k)``; the serving layer passes the
        masked pair ``(x0, mask)`` so warm session columns and cold
        one-shot columns share one compiled batch.

        kwargs are forwarded to the method (``avg_every``/``compress``/
        ``xbar0``/``tol``/``block_history`` for the consensus methods,
        ``tol`` for cgnr, ``lr`` for dgd). ``block_history=True``
        (apc/dapc) records per-epoch PER-BLOCK residuals in
        ``history["block_residual_sq"]`` — the convergence diagnostic
        ``repro.obs.convergence`` consumes; the default leaves the
        compiled program untouched. For apc/dapc, ``tol`` arms the masked per-column
        early exit: columns that reach ``residual_sq <= tol²`` freeze
        in-scan (``repro.core.consensus``) while the batch keeps one
        compiled shape, and the epoch body is skipped once all have —
        matching the matfree path's ``solve(tol=...)``.

        ``dynamics`` overrides the prepared default per solve:
        ``"per_block"`` runs eqs. (6)-(7) with the spectral per-block
        (γ_j, η_j) vectors estimated at prepare time (requires
        ``prepare(..., dynamics="per_block")``), ``"global"`` forces the
        scalar pair. The per-block weights are mean-1, so γ/η keep their
        global meaning (see ``repro.core.spectra``).

        ``num_epochs`` may be a ``SolveOptions`` — ``solve(b,
        SolveOptions(...))`` is the typed equivalent of the keyword form
        (the dataclass is the single source of truth for this signature).
        """
        if isinstance(num_epochs, SolveOptions):
            return self.solve(b, **num_epochs.kwargs())
        gamma = self.gamma if gamma is None else gamma
        eta = self.eta if eta is None else eta
        per_block = self._resolve_dynamics(dynamics)
        b = np.asarray(b)
        batched = b.ndim == 2
        if x0 is not None and self.method not in ("apc", "dapc"):
            raise ValueError(
                f"x0 warm start needs a consensus method (apc/dapc); "
                f"this solver runs {self.method!r}"
            )
        proj_stats = (
            {"projector": self.projector_form,
             "projector_bytes": self.projector_bytes}
            if self.projector else {}
        )
        with span(
            "solve", path=self.path, k=b.shape[1] if batched else 1,
            num_epochs=num_epochs, **proj_stats,
        ) as solve_span:
            with span("solve.rhs"):
                bvecs = block_rhs(self.mixer, b, np.dtype(self.blocks.dtype))
                ref = (
                    None if x_ref is None
                    else jnp.asarray(x_ref, self.blocks.dtype)
                )
            with span("solve.run") as run_span:
                if self.method in ("apc", "dapc"):
                    xbar0 = kwargs.pop("xbar0", None)
                    run = self._consensus_program(num_epochs, kwargs)
                    gamma_op, eta_op = self._dynamics_operands(
                        gamma, eta, per_block
                    )
                    x, hist = run(
                        self.blocks, self.factors, self.projector[1], bvecs,
                        gamma_op, eta_op, ref, xbar0,
                        _as_warm_operand(x0, self.blocks.dtype),
                    )
                elif self.method == "cgnr":
                    part = Partition(self.blocks, bvecs, self.mode)
                    x, hist = cg.solve_cgnr(
                        part, num_epochs=num_epochs, x_ref=ref, **kwargs
                    )
                else:  # dgd
                    part = Partition(self.blocks, bvecs, self.mode)
                    kwargs.setdefault("lr", self.factors[0])
                    x, hist = dgd.solve_dgd(
                        part, num_epochs=num_epochs, x_ref=ref, **kwargs
                    )
                x = jax.block_until_ready(x)
            with span("solve.fetch"):
                x = np.asarray(x)
                hist = jax.tree.map(np.asarray, hist)
            # a consensus scan skips its body once every column met tol;
            # cgnr's and dgd's scans run their whole budget
            epochs_run = (
                consensus.live_epochs(hist, num_epochs, kwargs.get("tol"))
                if self.method in ("apc", "dapc") else num_epochs
            )
            solve_span.set(epochs_run=epochs_run)
        self.num_solves += 1

        return SolveResult(
            x=x,
            method=self.method,
            mode=self.mode,
            num_blocks=self.num_blocks,
            num_epochs=num_epochs,
            history=hist,
            wall_seconds=run_span.seconds,
            gamma=gamma if self.method in ("apc", "dapc") else None,
            eta=eta if self.method in ("apc", "dapc") else None,
            num_rhs=b.shape[1] if batched else 1,
            epochs_run=epochs_run,
        )

    def open_session(self, **kwargs):
        """Open a streaming prediction-correction ``Session`` over this
        solver: each ``session.update(b_t)`` predicts the drifted solution
        from the stream history and corrects with a warm-started consensus
        solve (``repro.core.session``). Consensus methods only."""
        from repro.core.session import Session

        return Session(self, **kwargs)

    # -- checkpoint serialization (repro.serving.checkpoint) -----------------

    def to_state(self) -> tuple[dict, dict]:
        """Everything needed to rebuild this solver without re-factorizing:
        ``(arrays, meta)`` with plain numpy arrays and JSON-able metadata.

        The arrays ARE the expensive part of ``prepare`` (partition + QR /
        pseudo-inverse factors); restoring them via ``from_state`` costs
        file IO instead of the O(J·p·n²) factorization. When the projector
        operand aliases a factor array (implicit/kernels dapc, classical
        apc) only the reference is recorded, never a second copy.
        """
        arrays: dict = {"blocks": np.asarray(self.blocks)}
        factors_meta: list[dict] = []
        for i, f in enumerate(self.factors):
            if hasattr(f, "shape"):
                arrays[f"factor_{i}"] = np.asarray(f)
                factors_meta.append({"kind": "array", "key": f"factor_{i}"})
            else:
                factors_meta.append({"kind": "scalar", "value": float(f)})
        projector_meta = None
        if self.projector:
            kind, operand = self.projector
            ref = next(
                (i for i, f in enumerate(self.factors) if f is operand), None
            )
            if ref is None:
                arrays["projector"] = np.asarray(operand)
                projector_meta = {"kind": kind, "key": "projector"}
            else:
                projector_meta = {"kind": kind, "factor": ref}
        if self.mixer.g is not None:
            arrays["mixer_g"] = np.asarray(self.mixer.g)
        mixer_meta = {
            "m": int(self.mixer.m),
            "num_blocks": int(self.mixer.num_blocks),
            "p": int(self.mixer.p),
            "kind": "uniform",
        }
        if hasattr(self.mixer, "gather"):  # PlanMixer (cost-aware plan)
            mixer_meta["kind"] = "plan"
            arrays["mixer_gather"] = np.asarray(self.mixer.gather)
        from repro.core import spectra as _spectra

        arrays.update(_spectra.dynamics_arrays(self))
        meta = {
            "path": "dense",
            "method": self.method,
            "mode": self.mode,
            "gamma": float(self.gamma),
            "eta": float(self.eta),
            "use_kernels": bool(self.use_kernels),
            "setup_seconds": float(self.setup_seconds),
            "mixer": mixer_meta,
            "factors": factors_meta,
            "projector": projector_meta,
            **_spectra.dynamics_meta(self),
        }
        return arrays, meta

    @classmethod
    def from_state(cls, arrays, meta: dict) -> "PreparedSolver":
        """Rebuild a solver from ``to_state`` output (warm restore).

        The restored solver is functionally identical to the one saved —
        same factor bytes, so ``solve`` results are bit-identical — with a
        fresh jit cache and a zeroed ``num_solves``.
        """
        from repro.core import spectra as _spectra
        from repro.sparse.matrix import PlanMixer, RowMixer

        factors = tuple(
            jnp.asarray(arrays[spec["key"]])
            if spec["kind"] == "array" else spec["value"]
            for spec in meta["factors"]
        )
        projector: tuple = ()
        spec = meta["projector"]
        if spec is not None:
            operand = (
                factors[spec["factor"]] if "factor" in spec
                else jnp.asarray(arrays[spec["key"]])
            )
            projector = (spec["kind"], operand)
        mx = meta["mixer"]
        g = np.asarray(arrays["mixer_g"]) if "mixer_g" in arrays else None
        if mx.get("kind", "uniform") == "plan":
            mixer: Any = PlanMixer(
                m=int(mx["m"]), num_blocks=int(mx["num_blocks"]),
                p=int(mx["p"]), gather=np.asarray(arrays["mixer_gather"]),
                g=g,
            )
        else:
            mixer = RowMixer(
                m=int(mx["m"]), num_blocks=int(mx["num_blocks"]),
                p=int(mx["p"]), g=g,
            )
        return cls(
            blocks=jnp.asarray(arrays["blocks"]),
            mode=meta["mode"],
            mixer=mixer,
            method=meta["method"],
            gamma=meta["gamma"],
            eta=meta["eta"],
            use_kernels=meta["use_kernels"],
            factors=factors,
            projector=projector,
            setup_seconds=meta["setup_seconds"],
            **_spectra.dynamics_state(arrays, meta),
        )


def prepare(
    A,  # dense (m, n) array or host COOMatrix
    method: str | PrepareConfig = "dapc",
    num_blocks: int = 8,
    mode: str = "auto",  # BlockMode | "dense" | "matfree"
    dtype=None,
    gamma: float = 1.0,
    eta: float = 0.9,
    materialize_p: bool | None = None,
    use_kernels: bool = False,
    block_shape: tuple[int, int] | None = None,
    inner_iters: int | None = None,
    inner_tol: float = 1e-6,
    matfree_threshold_bytes: int | None = None,
    balance: bool = True,
    gram_solver: str = "auto",
    warm_start: bool = False,
    mesh=None,
    block_axes: tuple[str, ...] = ("data",),
    partition: str = "uniform",
    dynamics: str = "global",
):  # -> PreparedSolver | repro.core.matfree.MatrixFreePreparedSolver
    """Algorithm 1 steps 1–4, b-independent: partition A, factorize every
    block, build the jitted projector. Returns the reusable PreparedSolver.

    ``method`` may be a ``PrepareConfig`` — ``prepare(A, PrepareConfig(...))``
    is the typed equivalent of the keyword form (the dataclass is the
    single source of truth for this signature).

    ``mode`` selects the execution path on top of the block regime:
    tall/wide/auto keep their dense-path meaning; ``"dense"`` forces the
    densified path with auto block regime; ``"matfree"`` returns a
    ``MatrixFreePreparedSolver`` (sparse blocked-ELL operator + fused
    projection epochs, never densifying a block); ``"auto"`` also picks
    matfree when the nnz/memory estimate says the dense blocks would not
    pay off (``resolve_path``). ``block_shape``/``inner_iters``/
    ``inner_tol``/``balance``/``gram_solver``/``warm_start`` only apply to
    the matfree path (see ``repro.core.matfree.prepare_matfree``).

    ``mesh`` (matfree path only) places the blocked-ELL shards over the
    mesh's ``block_axes`` and returns a ``ShardedMatrixFreeSolver`` whose
    solve program runs under ``shard_map`` — sparse systems larger than
    one device, same solve contract (repro.core.matfree_sharded).

    ``partition="cost_aware"`` replaces the uniform contiguous row split
    with a heterogeneity-aware ``PartitionPlan`` (balanced nnz load +
    spectral grouping, ``repro.core.partition``); ``dynamics="per_block"``
    (consensus methods only) estimates per-block spectral bounds during
    prepare and runs eqs. (6)-(7) with per-block (γ_j, η_j) — see
    ``repro.core.spectra``. Both default off and the defaults are
    bit-identical to the historical solver.

    Cached per method (dense path):
      * dapc — (W_j, R_j) reduced-QR factors (paper eqs. 1/4), and the
               projector in the form ``dapc.projector_form`` picks:
               ``materialize_p=None`` (default) takes the materialized P_j
               or the implicit v − Wᵀ(W v), whichever reads fewer bytes
               an epoch for the block shape; ``True``/``False`` pin it;
      * apc  — (A_j⁺, P_j) pseudoinverse + dense projector (the classical
               setup the paper's decomposition replaces);
      * dgd  — the 1/λ_max(AᵀA) step size (power iteration);
      * cgnr — nothing beyond the partition (zero-setup baseline).
    """
    if isinstance(method, PrepareConfig):
        # prepare(A, PrepareConfig(...)): the dataclass IS the kwargs
        return prepare(A, **method.kwargs())
    if method not in METHODS:
        raise ValueError(f"method must be one of {METHODS}")
    if partition not in ("uniform", "cost_aware"):
        raise ValueError(
            f"partition must be 'uniform' or 'cost_aware', got {partition!r}"
        )
    if dynamics not in ("global", "per_block"):
        raise ValueError(
            f"dynamics must be 'global' or 'per_block', got {dynamics!r}"
        )
    if dynamics == "per_block" and method not in ("apc", "dapc"):
        raise ValueError(
            "dynamics='per_block' needs a consensus method (apc/dapc); "
            f"got method={method!r}"
        )
    plan = (
        PartitionPlan.cost_aware(A, num_blocks)
        if partition == "cost_aware" else None
    )
    path = resolve_path(A, num_blocks, mode, matfree_threshold_bytes)
    if path == "matfree" and method not in ("apc", "dapc"):
        if mode == "auto":
            path = "dense"  # matfree covers the consensus methods only;
            # auto must not turn a working dgd/cgnr solve into an error
        else:
            raise ValueError(
                f"mode='matfree' supports the consensus methods "
                f"('apc', 'dapc'); got method={method!r} — use one of "
                "those, or mode='dense'/'auto' for this method"
            )
    if mesh is not None and path != "matfree":
        raise ValueError(
            "mesh= shards the matrix-free path; this prepare resolved "
            f"path={path!r} (use mode='matfree', or solve_sharded for "
            "dense mesh solves)"
        )
    if path == "matfree":
        from repro.core import matfree  # deferred: matfree imports SolveResult

        kw = {} if block_shape is None else {"block_shape": tuple(block_shape)}
        return matfree.prepare_matfree(
            A, method=method, num_blocks=num_blocks, dtype=dtype,
            gamma=gamma, eta=eta, inner_iters=inner_iters,
            inner_tol=inner_tol, use_kernels=use_kernels, balance=balance,
            gram_solver=gram_solver, warm_start=warm_start,
            mesh=mesh, block_axes=block_axes,
            partition=partition, dynamics=dynamics, plan=plan, **kw,
        )
    if isinstance(A, COOMatrix):
        A = A.to_dense()  # the dense path's per-block decompress, up front
    block_mode: BlockMode = mode if mode in ("tall", "wide") else "auto"
    t0 = time.perf_counter()
    blocks, resolved, mixer = partition_matrix(
        A, num_blocks, block_mode, dtype, plan=plan
    )

    factors: tuple = ()
    projector: tuple = ()
    if method == "dapc":
        Ws, Rs = dapc.qr_blocks(blocks, resolved)
        factors = (Ws, Rs)
        form = dapc.projector_form(
            blocks.shape[1], blocks.shape[2], materialize_p, use_kernels
        )
        if form == "dense":
            # paper-faithful dense P_j, built ONCE here (not per solve)
            Ps = jax.vmap(projections.materialize)(Ws)
            projector = ("dense", Ps)
        else:
            projector = (form, Ws)
    elif method == "apc":
        pinvs, Ps = apc.classical_factors(blocks, resolved)
        factors = (pinvs, Ps)
        projector = ("dense", Ps)
    elif method == "dgd":
        factors = (float(dgd.estimate_lipschitz(blocks)) ** -1,)
    block_gamma_w = block_eta_w = spectra_d = None
    if dynamics == "per_block":
        from repro.core import spectra as spectra_mod

        spectra_d = spectra_mod.block_spectra_dense(
            np.asarray(blocks), plan=plan
        )
        block_gamma_w, block_eta_w = spectra_mod.derive_dynamics(spectra_d)
    jax.block_until_ready(blocks if not factors else factors[0])
    setup_seconds = time.perf_counter() - t0

    return PreparedSolver(
        blocks=blocks,
        mode=resolved,
        mixer=mixer,
        method=method,
        gamma=gamma,
        eta=eta,
        use_kernels=use_kernels,
        factors=factors,
        projector=projector,
        setup_seconds=setup_seconds,
        partition=partition,
        dynamics=dynamics,
        plan=plan,
        block_gamma_weights=block_gamma_w,
        block_eta_weights=block_eta_w,
        block_spectra=spectra_d,
    )
