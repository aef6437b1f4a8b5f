"""The APC consensus iteration (paper eqs. 6–7) as a jitted ``lax.scan``.

Shared by classical APC and decomposed APC — the two differ only in how the
per-block initial solutions and projectors are produced (Algorithm 1 steps
2–3), not in the iteration itself (steps 5–8).

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


def block_residual_sq(blocks: jnp.ndarray, bvecs: jnp.ndarray, x: jnp.ndarray):
    """Global residual ||A x − b||² computed block-wise (no A reassembly).

    Scalar for x (n,); per-system vector (k,) for a batched x (n, k)."""
    r = jnp.einsum(
        "jpn,n...->jp...", blocks, x, precision=_HIGHEST
    ) - _match_rhs(bvecs, x)
    return jnp.sum(r * r, axis=(0, 1))


def _block_col(v, ndim: int):
    """Reshape a per-block (J,) vector for broadcasting against (J, n[, k])
    state; scalars pass through untouched."""
    if getattr(v, "ndim", 0) >= 1:
        return v.reshape(v.shape + (1,) * (ndim - v.ndim))
    return v


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
    """Paper eqs. (5)–(7). Returns (x̄_final, history dict).

    history carries per-epoch MSE to ``x_ref`` (paper Fig. 2 metric) and the
    global residual when (blocks, bvecs) are supplied; with a batched
    ``(J, n, k)`` input both metrics are per-system ``(k,)`` rows.

    ``block_history=True`` additionally records the PER-BLOCK residual
    ``history["block_residual_sq"]`` — ``(J,)`` per epoch, ``(J, k)``
    batched — the convergence diagnostic ``repro.obs.convergence``
    summarizes (which block drags, per-block decay rates). It reuses the
    residual pass's per-block partials, so enabling it adds reductions
    only, never another projector application; disabled (the default) the
    program is untouched.

    ``tol`` arms the masked in-scan early exit: a column whose residual
    reaches ``residual_sq <= tol²`` FREEZES — its xs/x̄ columns stop
    updating under a ``jnp.where`` mask — while the batch keeps its one
    compiled shape, so one slow column no longer drags converged
    batchmates through further consensus motion. The mask reads the
    residual carried from the previous epoch (no extra einsum). Requires
    (blocks, bvecs); the frozen column's residual history simply repeats
    its converged value, so ``iterations_to_tol`` reports are unchanged.
    Once EVERY column is frozen the epoch body short-circuits under a
    ``lax.cond``: no projector apply and no residual pass, the state
    passes through, and the history row repeats the carried one (the
    last computed from this same x̄), so results and history are those of
    the masked scan. ``live_epochs`` counts the epochs that ran the body.
    Without ``tol`` no cond is traced.

    ``compress="bf16_delta"`` halves the consensus all-reduce payload by
    communicating the DELTA mean(x)−x̄ in bf16 (eq. 7 rewritten as
    x̄ += η·Δ). The quantization error is relative to the shrinking delta,
    so the trajectory matches f32 to the final MSE (validated in
    tests/test_core_solvers.py; EXPERIMENTS.md §Perf solver iteration 3) —
    unlike quantizing x̄ itself, which floors at bf16 ULP.

    ``gamma``/``eta`` accept per-block ``(J,)`` vectors (heterogeneity-aware
    dynamics): eq. (6) steps block j with γ_j and eq. (7) becomes the
    weighted mean x̄⁺ = mean_j(η_j·xs_j⁺) + (1−η̄)·x̄ with η̄ = mean(η_j),
    which reduces EXACTLY to the scalar form when all η_j are equal. With
    scalar inputs the program is the historical one, bit for bit.

    ``avg_every > 1`` is a beyond-paper collective optimization: the
    consensus average (the only cross-worker collective) runs every k-th
    epoch; between averages workers take local projection steps against the
    stale x̄. Cuts the all-reduce count by k× — at 512+ chips the per-epoch
    n-vector psum is the latency floor of the whole algorithm
    (EXPERIMENTS.md §Perf, solver)."""
    if xbar0 is None:
        xbar0 = jnp.mean(x0s, axis=0)  # eq. (5)
    elif xbar0.ndim < x0s.ndim - 1:
        xbar0 = jnp.broadcast_to(xbar0[..., None], x0s.shape[1:])
    if tol is not None and (blocks is None or bvecs is None):
        raise ValueError("tol early exit needs (blocks, bvecs) for residuals")

    if block_history and (blocks is None or bvecs is None):
        raise ValueError("block_history needs (blocks, bvecs) for residuals")

    def metrics(xbar):
        out = {}
        if x_ref is not None:
            ref = x_ref[..., None] if xbar.ndim > x_ref.ndim else x_ref
            d = xbar - ref
            out["mse"] = jnp.mean(d * d, axis=0)
        if blocks is not None and bvecs is not None:
            if block_history:
                r = (
                    jnp.einsum(
                        "jpn,n...->jp...", blocks, xbar, precision=_HIGHEST
                    )
                    - _match_rhs(bvecs, xbar)
                )
                per_block = jnp.sum(r * r, axis=1)  # (J,) or (J, k)
                out["block_residual_sq"] = per_block
                out["residual_sq"] = jnp.sum(per_block, axis=0)
            else:
                out["residual_sq"] = block_residual_sq(blocks, bvecs, xbar)
        return out

    init_metrics = metrics(xbar0)

    per_block = (
        getattr(gamma, "ndim", 0) >= 1 or getattr(eta, "ndim", 0) >= 1
    )
    gam = _block_col(gamma, x0s.ndim)
    if per_block:
        eta_col = _block_col(eta, x0s.ndim)
        eta_bar = (
            jnp.mean(eta) if getattr(eta, "ndim", 0) >= 1 else eta
        )

    def step(xs, xbar, resid, t):
        xs_new = xs + gam * apply_fn(xbar[None] - xs)  # eq. (6), parallel j
        do_avg = (t + 1) % avg_every == 0
        if compress == "bf16_delta":
            if per_block:  # Δ = mean(η_j (xs_j − x̄)), η folded into the wire
                delta = jnp.mean(eta_col * (xs_new - xbar[None]), axis=0)
                delta = delta.astype(jnp.bfloat16).astype(xbar.dtype)
                xbar_new = xbar + delta
            else:
                delta = jnp.mean(xs_new - xbar[None], axis=0)  # wire payload
                delta = delta.astype(jnp.bfloat16).astype(xbar.dtype)
                xbar_new = xbar + eta * delta  # eq. (7), delta form
        elif per_block:  # eq. (7), η_j-weighted mean (reduces to scalar form)
            xbar_new = (
                jnp.mean(eta_col * xs_new, axis=0) + (1.0 - eta_bar) * xbar
            )
        else:
            xbar_new = (
                eta * jnp.mean(xs_new, axis=0) + (1.0 - eta) * xbar
            )  # eq. (7)
        xbar_new = jnp.where(do_avg, xbar_new, xbar)
        if tol is not None:
            # residual of the x̄ this epoch STARTED from, carried from the
            # previous metrics pass — frozen columns stop moving entirely
            active = resid > tol * tol  # (k,) batched, scalar otherwise
            xs_new = jnp.where(active, xs_new, xs)
            xbar_new = jnp.where(active, xbar_new, xbar)
        return xs_new, xbar_new, metrics(xbar_new)

    if tol is None:
        def epoch(carry, t):
            xs, xbar, out = step(*carry, None, t)
            return (xs, xbar), out

        init = (x0s, xbar0)
    else:
        # the carry holds the last history row, whose residual arms the
        # mask; an all-frozen epoch would recompute that row from an
        # unchanged x̄, so it repeats it instead
        def live(carry, t):
            xs, xbar, row = carry
            xs, xbar, out = step(xs, xbar, row["residual_sq"], t)
            return (xs, xbar, out), out

        def frozen(carry, t):
            return carry, carry[2]

        def epoch(carry, t):
            any_active = jnp.any(carry[2]["residual_sq"] > tol * tol)
            return jax.lax.cond(any_active, live, frozen, carry, t)

        init = (x0s, xbar0, init_metrics)
    (_, xbar, *_), hist = jax.lax.scan(epoch, init, jnp.arange(num_epochs))
    hist["initial"] = init_metrics
    return xbar, hist


def live_epochs(hist: dict, num_epochs: int, tol: float | None) -> int:
    """Epochs in which a consensus scan ran its epoch body (``run_consensus``
    here, ``matfree.consensus_epochs`` on the matrix-free path), counted on
    the host from the history a solve returns.

    Without ``tol`` every epoch is live. With it, epoch t runs the body iff
    some column's residual at the epoch's start exceeds ``tol²`` — the
    program's own freeze predicate, applied to the very residuals it
    emitted (entry t − 1 of ``residual_sq``, the initial one for t = 0) in
    their own dtype. The matrix-free frozen branch also writes zero inner
    depths, but a live PCG epoch whose warm-started inner solves start
    below their tolerance reports zero depth too, so ``inner_iters`` alone
    would undercount.
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
