"""The APC consensus iteration (paper eqs. 6–7): the one epoch loop.

``consensus_scan`` is the only ``lax.scan`` over consensus epochs in the
tree. Every solver is a caller of it that supplies what is its own — the
block projection, the per-block residual, its private carried state and
the cross-shard reductions — and inherits everything else: eq. (6) and
the eq. (7) average (scalar or per-block γ_j/η_j, ``avg_every``,
``bf16_delta``, the straggler publish mask), the ``tol`` freeze mask and
the all-frozen skip, and the history layout.

  * ``run_consensus`` (here): the dense projector P_j or its factored
    form v − Wᵀ(W v), the residual as an einsum over the row blocks;
    classical and decomposed APC differ only in how the per-block initial
    solutions and projectors are produced (Algorithm 1 steps 2–3);
  * ``repro.core.matfree.consensus_epochs``: the Gram solve with the fused
    tile pass, single host or inside ``shard_map``;
  * ``repro.core.distributed.solve_sharded`` / ``solve_sharded_2d``: the
    dense solvers under ``shard_map``.

Every function here is shape-polymorphic over a trailing RHS axis: state is
``(J, n)`` for one right-hand side or ``(J, n, k)`` for a k-system batch.
The batched form runs all k consensus iterations in ONE compiled program —
the projector application becomes ``(J, p, n) × (J, n, k)`` einsums (MXU
matmuls instead of k matvec dispatches), which is where the multi-RHS
serving throughput comes from (benchmarks/multirhs.py).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

_HIGHEST = jax.lax.Precision.HIGHEST  # full f32 (see repro.core.projections)


def _match_rhs(bvecs: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Broadcast unbatched (J, p) bvecs against batched (…, k) state."""
    if x.ndim > bvecs.ndim - 1:
        return bvecs[..., None]
    return bvecs


def block_residual(blocks: jnp.ndarray, bvecs: jnp.ndarray, x: jnp.ndarray):
    """Per-block residual vectors A_j x − b_j, (J, p) or (J, p, k)."""
    return jnp.einsum(
        "jpn,n...->jp...", blocks, x, precision=_HIGHEST
    ) - _match_rhs(bvecs, x)


def block_residual_sq(blocks: jnp.ndarray, bvecs: jnp.ndarray, x: jnp.ndarray):
    """Global residual ||A x − b||² computed block-wise (no A reassembly).

    Scalar for x (n,); per-system vector (k,) for a batched x (n, k)."""
    r = block_residual(blocks, bvecs, x)
    return jnp.sum(r * r, axis=(0, 1))


def _block_col(v, ndim: int):
    """Reshape a per-block (J,) vector for broadcasting against (J, n[, k])
    state; scalars pass through untouched."""
    if getattr(v, "ndim", 0) >= 1:
        return v.reshape(v.shape + (1,) * (ndim - v.ndim))
    return v


def dynamics(gamma, eta, ndim: int):
    """The eq. (6)/(7) coefficients for ``ndim``-rank block state:
    ``(γ, η, η̄, per_block)``.

    ``gamma`` is a scalar or a per-block ``(J,)`` vector; ``eta`` a scalar,
    a ``(J,)`` vector, or the pair ``(η_j, η̄)`` that a sharded caller
    passes, its η̄ taken over ALL blocks (a shard holds only its own η_j).
    Vectors come back shaped to broadcast against the state."""
    eta, eta_bar = eta if isinstance(eta, tuple) else (eta, None)
    per_block = (
        eta_bar is not None
        or getattr(gamma, "ndim", 0) >= 1
        or getattr(eta, "ndim", 0) >= 1
    )
    if eta_bar is None:
        eta_bar = jnp.mean(eta) if getattr(eta, "ndim", 0) >= 1 else eta
    return _block_col(gamma, ndim), _block_col(eta, ndim), eta_bar, per_block


def identity(a):
    return a


def projection(apply_fn: Callable[[jnp.ndarray], jnp.ndarray]):
    """The ``consensus_scan`` ``project`` hook of a projector applied as a
    function of the stacked differences, ``apply_fn(v)_j = P_j v_j``."""
    return lambda xs, xbar, aux, active: (apply_fn(xbar[None] - xs), None)


def _coord_mean(a):
    return jnp.mean(a, axis=0)


def consensus_scan(
    x0s: jnp.ndarray,  # (J, n[, k]) this caller's per-block initial iterates
    xbar0: jnp.ndarray,  # (n[, k]) eq. (5) average, or a warm start
    project: Callable,  # (xs, x̄, aux, active) -> (P_j(x̄ − x_j), mid)
    residual: Callable | None = None,  # (x̄, aux) -> A_j x̄ − b_j per block
    *,
    gamma,
    eta,
    num_epochs: int,
    aux=(),  # the caller's own carried state
    advance: Callable | None = None,  # (mid, x̄⁺, q⁺) -> (aux⁺, counts)
    counts: dict | None = None,  # the initial entry's per-epoch counters
    pmean: Callable = identity,  # cross-shard mean of a shard-local value
    psum: Callable = identity,  # cross-shard sum of a shard-local value
    coord_mean: Callable = _coord_mean,  # mean over x̄'s coordinates
    x_ref: jnp.ndarray | None = None,
    tol2: float | None = None,  # freeze columns with residual_sq <= tol2
    block_history: bool = False,
    avg_every: int = 1,
    compress: str | None = None,  # None | "bf16_delta"
    straggler_prob: float = 0.0,
    keys: jnp.ndarray | None = None,  # (num_epochs, 2) straggler PRNG keys
):
    """Paper eqs. (6)–(7) for ``num_epochs`` epochs, as one ``lax.scan``.
    Returns ``(x̄_final, history)``.

    **Epoch.** ``project`` returns P_j(x̄ − x_j) for each of the caller's
    blocks (plus ``mid``, anything it hands on to ``advance``); eq. (6)
    steps x_j ← x_j + γ_j·P_j(x̄ − x_j), and eq. (7) averages
    x̄⁺ = η·q⁺ + (1−η)·x̄ with q⁺ = ``pmean``(mean_j x_j⁺) the global block
    mean. Per-block dynamics (``dynamics``) weight the mean instead:
    q⁺ = mean_j(η_j·x_j⁺) and x̄⁺ = q⁺ + (1−η̄)·x̄, which reduces EXACTLY to
    the scalar form when all η_j are equal; scalar inputs keep the scalar
    program. ``advance(mid, x̄⁺, q⁺)`` then returns the caller's next
    ``aux`` and the epoch's counters (e.g. Gram solve depths). The hooks
    ``pmean``/``psum`` are the only places cross-shard information
    enters: identity on one host, ``lax.pmean``/``lax.psum`` over the
    block axes under ``shard_map``, so the average costs one n·k
    collective an epoch and the residual one k-length one.

    **Average variants.** ``compress="bf16_delta"`` halves the consensus
    all-reduce payload by communicating the DELTA mean(x)−x̄ in bf16
    (x̄ += η·Δ; η_j folded into Δ under per-block dynamics). The
    quantization error is relative to the shrinking delta, so the
    trajectory matches f32 to the final MSE (tests/test_core_solvers.py),
    unlike quantizing x̄ itself, which floors at bf16 ULP.
    ``straggler_prob = q > 0`` publishes each block's update only with
    probability 1−q an epoch (one draw per block from ``keys[t]``, shared
    by its RHS columns) and averages the last published states: stale
    consensus, which the η-EMA absorbs. ``avg_every > 1`` runs the
    average only every that many epochs; between averages the blocks
    take local projection steps against the stale x̄.

    **History.** Each row holds, of the x̄ (and ``aux``) an epoch ends
    with: ``mse`` to ``x_ref`` (``coord_mean`` over coordinates; the paper's
    Fig. 2 metric), ``residual_sq`` = ``psum``(Σ ||A_j x̄ − b_j||²) when a
    ``residual`` is given, the per-block ``block_residual_sq`` (J_loc[, k])
    with ``block_history`` (the same squares, no second residual pass);
    plus the epoch's ``counts``. Rows stack to ``(E,)`` or ``(E, k)``;
    ``history["initial"]`` is the row of x̄₀ with the initial ``counts``.

    **tol.** ``tol2`` arms the masked in-scan early exit: a column whose
    residual of the x̄ the epoch STARTS from (the carried row) satisfies
    ``residual_sq <= tol2`` freezes — its whole state (x_j, x̄, the
    published states, ``aux``) stops updating under a ``jnp.where`` mask
    and its counters read 0 — while the batch keeps its one compiled
    shape. Once EVERY column is frozen the epoch body short-circuits
    under a ``lax.cond``: no projection and no residual pass, the state
    passes through and the row repeats the carried one with zero
    counters, so results and history are those of the masked scan and
    ``live_epochs`` counts the epochs that ran the body. Without ``tol2``
    no mask or cond is traced.
    """
    if (tol2 is not None or block_history) and residual is None:
        raise ValueError("tol and block_history need the residual")
    gam, eta_col, eta_bar, per_block = dynamics(gamma, eta, x0s.ndim)
    counts = {} if counts is None else counts

    def row_of(xbar, aux):
        row = {}
        if x_ref is not None:
            ref = x_ref[..., None] if xbar.ndim > x_ref.ndim else x_ref
            d = xbar - ref
            row["mse"] = coord_mean(d * d)
        if residual is not None:
            r = residual(xbar, aux)
            sq = r * r
            row["residual_sq"] = psum(jnp.sum(sq, axis=(0, 1)))
            if block_history:
                row["block_residual_sq"] = jnp.sum(sq, axis=1)
        return row

    def average(pub, xbar):  # eq. (7) -> (x̄⁺, q⁺)
        if compress == "bf16_delta":
            d = pub - xbar[None]
            if per_block:
                d = eta_col * d
            q = pmean(jnp.mean(d, axis=0).astype(jnp.bfloat16))
            q = q.astype(xbar.dtype)
            return (xbar + q if per_block else xbar + eta * q), q
        if per_block:
            q = pmean(jnp.mean(eta_col * pub, axis=0))
            return q + (1.0 - eta_bar) * xbar, q
        q = pmean(jnp.mean(pub, axis=0))
        return eta * q + (1.0 - eta) * xbar, q

    def epoch(state, active, t):
        xs, xbar, pub, aux = state
        pv, mid = project(xs, xbar, aux, active)
        xs_new = xs + gam * pv  # eq. (6), every block in parallel
        if straggler_prob > 0.0:
            shape = (xs.shape[0],) + (1,) * (xs.ndim - 1)
            alive = jax.random.uniform(keys[t], shape) >= straggler_prob
            alive = alive.astype(xs.dtype)
            pub = alive * xs_new + (1.0 - alive) * pub
            xbar_new, q = average(pub, xbar)
        else:
            xbar_new, q = average(xs_new, xbar)
        if avg_every > 1:
            xbar_new = jnp.where((t + 1) % avg_every == 0, xbar_new, xbar)
        aux_new, out = (aux, {}) if advance is None else advance(
            mid, xbar_new, q
        )
        new = (xs_new, xbar_new, pub, aux_new)
        if active is not None:
            new = jax.tree.map(
                lambda a, b: jnp.where(active, a, b), new, state
            )
            out = jax.tree.map(lambda c: jnp.where(active, c, 0), out)
        return new, {**row_of(new[1], new[3]), **out}

    row0 = {**row_of(xbar0, aux), **counts}
    # the published states are carried only when stragglers make them lag
    init = (x0s, xbar0, x0s if straggler_prob > 0.0 else (), aux)
    if tol2 is None:
        def body(state, t):
            return epoch(state, None, t)
    else:
        # the carry also holds the last row, whose residual arms the mask;
        # an all-frozen epoch would recompute that row from an unchanged
        # x̄, so it repeats it instead
        def live(carry, t):
            state, row = carry
            state, row = epoch(state, row["residual_sq"] > tol2, t)
            return (state, row), row

        def frozen(carry, t):
            idle = jax.tree.map(jnp.zeros_like, counts)
            return carry, {**carry[1], **idle}

        def body(carry, t):
            any_active = jnp.any(carry[1]["residual_sq"] > tol2)
            return jax.lax.cond(any_active, live, frozen, carry, t)

        init = (init, row0)
    carry, hist = jax.lax.scan(body, init, jnp.arange(num_epochs))
    xbar = (carry if tol2 is None else carry[0])[1]
    hist["initial"] = row0
    return xbar, hist


def run_consensus(
    x0s: jnp.ndarray,  # (J, n) or (J, n, k) per-block initial solutions
    apply_fn: Callable[[jnp.ndarray], jnp.ndarray],  # x0s-shaped: P_j v_j
    gamma: float,
    eta: float,
    num_epochs: int,
    x_ref: jnp.ndarray | None = None,
    blocks: jnp.ndarray | None = None,
    bvecs: jnp.ndarray | None = None,
    avg_every: int = 1,
    compress: str | None = None,  # None | "bf16_delta"
    xbar0: jnp.ndarray | None = None,  # warm start (elastic restart)
    tol: float | None = None,  # masked per-column early exit
    block_history: bool = False,  # per-block residual diagnostics
):
    """Paper eqs. (5)–(7) on one host with a dense block projector.
    Returns (x̄_final, history dict).

    ``apply_fn`` applies every P_j (materialized, or v − Wᵀ(W v)); the
    residual, when ``(blocks, bvecs)`` are supplied, is one einsum over
    the row blocks after each epoch's update. ``xbar0`` replaces the
    eq. (5) average (a warm start; an unbatched x̄₀ broadcasts over the
    batch). ``tol`` (which needs the residual) arms the masked early exit
    and the all-frozen skip, ``block_history`` records the per-block
    residual rows that ``repro.obs.convergence`` summarizes; both, with
    ``gamma``/``eta`` as per-block ``(J,)`` vectors, ``avg_every`` and
    ``compress``, are ``consensus_scan``'s."""
    if xbar0 is None:
        xbar0 = jnp.mean(x0s, axis=0)  # eq. (5)
    elif xbar0.ndim < x0s.ndim - 1:
        xbar0 = jnp.broadcast_to(xbar0[..., None], x0s.shape[1:])
    residual = None
    if blocks is not None and bvecs is not None:
        def residual(xbar, aux):
            return block_residual(blocks, bvecs, xbar)

    return consensus_scan(
        x0s, xbar0, projection(apply_fn), residual,
        gamma=gamma, eta=eta, num_epochs=num_epochs, x_ref=x_ref,
        tol2=None if tol is None else tol * tol,
        block_history=block_history, avg_every=avg_every, compress=compress,
    )


def live_epochs(hist: dict, num_epochs: int, tol: float | None) -> int:
    """Epochs in which ``consensus_scan`` ran its epoch body, counted on the
    host from the history a solve returns.

    Without ``tol`` every epoch is live. With it, epoch t runs the body iff
    some column's residual at the epoch's start exceeds ``tol²`` — the
    program's own freeze predicate, applied to the very residuals it
    emitted (entry t − 1 of ``residual_sq``, the initial one for t = 0) in
    their own dtype. The frozen branch also writes zero counters (the
    matrix-free inner depths), but a live PCG epoch whose warm-started
    inner solves start below their tolerance reports zero depth too, so
    ``inner_iters`` alone would undercount.
    """
    if tol is None:
        return num_epochs
    resid = np.asarray(hist["residual_sq"])
    start = np.concatenate([
        np.reshape(hist["initial"]["residual_sq"], (1, -1)),
        np.reshape(resid, (num_epochs, -1))[:-1],
    ])
    threshold = start.dtype.type(float(tol) ** 2)
    return int((start > threshold).any(axis=1).sum())


def evaluate_candidates(
    x0s: jnp.ndarray,
    apply_fn: Callable[[jnp.ndarray], jnp.ndarray],
    blocks: jnp.ndarray,
    bvecs: jnp.ndarray,
    gammas: jnp.ndarray,  # (C,) scalar or (C, J) per-block candidates
    etas: jnp.ndarray,  # (C,) scalar or (C, J) per-block candidates
    probe_epochs: int = 20,
    block_history: bool = False,
):
    """The single vectorized probe-evaluation path behind hyperparameter
    tuning: run every (γ, η) candidate for ``probe_epochs`` in one vmapped
    compiled program and score it by final global residual.

    Candidates may be scalars ``(C,)`` or per-block vectors ``(C, J)`` —
    ``run_consensus`` handles both, so global and per-block dynamics share
    this one evaluation path instead of duplicating the step logic.
    Returns ``(scores, block_hist)``; ``block_hist`` is the per-epoch
    per-block residual history ``(C, E, J[, k])`` when ``block_history``
    is set, else None.
    """

    def probe(g, e):
        xbar, hist = run_consensus(
            x0s, apply_fn, g, e, probe_epochs,
            blocks=blocks if block_history else None,
            bvecs=bvecs if block_history else None,
            block_history=block_history,
        )
        score = block_residual_sq(blocks, bvecs, xbar)
        return score, hist["block_residual_sq"] if block_history else None

    return jax.vmap(probe)(jnp.asarray(gammas), jnp.asarray(etas))


def tune_hyperparams(
    x0s: jnp.ndarray,
    apply_fn: Callable[[jnp.ndarray], jnp.ndarray],
    blocks: jnp.ndarray,
    bvecs: jnp.ndarray,
    gammas: jnp.ndarray,
    etas: jnp.ndarray,
    probe_epochs: int = 20,
    plan=None,
):
    """Grid-search (γ, η) by residual after a short probe run (vmapped).

    The paper chooses these "heuristically"; this makes the heuristic
    reproducible. Cheap: probe runs are vmapped into one compiled program
    (``evaluate_candidates``).

    Returns ``(gamma, eta)``. With a ``PartitionPlan`` supplied, the
    winning probe additionally reports how each of the plan's blocks
    converged: the return becomes ``(gamma, eta, rates)`` with ``rates``
    the per-block geometric decay rate over the probe window — the
    heterogeneity diagnostic feeding per-block dynamics.
    """
    gg, ee = jnp.meshgrid(gammas, etas, indexing="ij")
    pairs = jnp.stack([gg.ravel(), ee.ravel()], axis=1)
    scores, block_hist = evaluate_candidates(
        x0s, apply_fn, blocks, bvecs, pairs[:, 0], pairs[:, 1],
        probe_epochs, block_history=plan is not None,
    )
    scores = jnp.where(jnp.isfinite(scores), scores, jnp.inf)
    if plan is None:
        best = pairs[jnp.argmin(scores)]
        return float(best[0]), float(best[1])
    flat = scores.reshape(scores.shape[0], -1).sum(axis=1)  # fold RHS cols
    idx = int(jnp.argmin(flat))
    hist = block_hist[idx]  # (E, J[, k])
    epochs = hist.shape[0]
    rates = (
        hist[-1] / jnp.maximum(hist[0], 1e-30)
    ) ** (1.0 / (2.0 * max(epochs - 1, 1)))
    return float(pairs[idx, 0]), float(pairs[idx, 1]), rates
