"""A plain APC solver in ``jax.numpy``: the check's control.

The program's answer is judged against ``x_true`` and the float64 residual
(``chipbench.system``). The control shows that this judgement has teeth:
the same algorithm, written plainly, with every contraction of its epochs
(projector and residual) computed one precision step below what the
configurations state (float32 at ``HIGHEST``), has to come out not correct.

``precision`` takes three values:

* ``"highest"`` — full float32 products (``Precision.HIGHEST``);
* ``"high"`` — three bfloat16 passes (``Precision.HIGH``);
* ``"default"`` — one bfloat16 pass (``Precision.DEFAULT``).

On a TPU these are the chip's own precisions. Elsewhere, where a float32
product is always full, the two lower ones are emulated by splitting each
operand into bfloat16 parts with ``reduce_precision`` (hi·hi + hi·lo +
lo·hi for three passes, hi·hi for one), which is what the TPU computes.

The block factorization is the one-off part and is done on the host in
float64 (each block's reduced QR of ``A_jᵀ``), then held in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("highest", "high", "default")
_HIGHEST = jax.lax.Precision.HIGHEST


_NATIVE = {"highest": jax.lax.Precision.HIGHEST,
           "high": jax.lax.Precision.HIGH,
           "default": jax.lax.Precision.DEFAULT}


def _split(a):
    hi = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    lo = jax.lax.reduce_precision(a - hi, exponent_bits=8, mantissa_bits=7)
    return hi, lo


def contract(spec: str, a, b, precision: str):
    """``einsum(spec, a, b)`` at one of ``PRECISIONS``."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}")
    if jax.default_backend() == "tpu":
        return jnp.einsum(spec, a, b, precision=_NATIVE[precision])

    def dot(x, y):
        return jnp.einsum(spec, x, y, precision=_HIGHEST)

    if precision == "highest":
        return dot(a, b)
    a_hi, a_lo = _split(a)
    b_hi, b_lo = _split(b)
    if precision == "default":
        return dot(a_hi, b_hi)
    return dot(a_hi, b_hi) + dot(a_hi, b_lo) + dot(a_lo, b_hi)


def factor(A: np.ndarray, num_blocks: int):
    """Blocks ``A_j`` (J, p, n), and per block ``Q_j`` (n, p) and ``R_j``
    (p, p) with ``A_jᵀ = Q_j R_j``, in float64 on the host."""
    m, n = A.shape
    if m % num_blocks:
        raise ValueError(f"{m} rows do not split into {num_blocks} blocks")
    blocks = A.reshape(num_blocks, m // num_blocks, n)
    qs, rs = zip(*(np.linalg.qr(blk.T) for blk in blocks))
    return blocks, np.stack(qs), np.stack(rs)


@jax.jit
def _start(Q, R, bb):
    """Each block's minimum-norm solution ``Q_j R_j⁻ᵀ b_j`` (J, n, k)."""
    y = jax.vmap(
        lambda r, b: jax.scipy.linalg.solve_triangular(r.T, b, lower=True)
    )(R, bb)
    return jnp.einsum("jnp,jpk->jnk", Q, y, precision=_HIGHEST)


def _epochs(blocks, Q, bb, xs, gamma, eta, tol, num_epochs, precision):
    def residual_sq(xbar):
        r = contract("jpn,nk->jpk", blocks, xbar, precision) - bb
        return jnp.sum(r * r, axis=(0, 1))

    def body(state):
        xs, xbar, rsq, t = state
        active = rsq > tol * tol
        d = xbar[None] - xs
        proj = d - contract(
            "jnp,jpk->jnk", Q, contract("jnp,jnk->jpk", Q, d, precision),
            precision,
        )
        xs_new = jnp.where(active, xs + gamma * proj, xs)
        xbar_new = eta * jnp.mean(xs_new, axis=0) + (1.0 - eta) * xbar
        xbar_new = jnp.where(active, xbar_new, xbar)
        return xs_new, xbar_new, residual_sq(xbar_new), t + 1

    def cond(state):
        return jnp.any(state[2] > tol * tol) & (state[3] < num_epochs)

    xbar = jnp.mean(xs, axis=0)
    xs, xbar, rsq, t = jax.lax.while_loop(
        cond, body, (xs, xbar, residual_sq(xbar), 0)
    )
    return xbar, rsq, t


_epochs_jit = jax.jit(_epochs, static_argnames=("num_epochs", "precision"))


def solve(factors, B: np.ndarray, gamma: float, eta: float, tol: float,
          num_epochs: int, precision: str, chunk: int = 64):
    """APC (eqs. 5–7) on the host-factored blocks for the columns of ``B``
    (m, K), ``chunk`` columns at a time; a column freezes once its
    residual is within ``tol``. Returns (x (n, K), converged (K,))."""
    blocks, Q, R = factors
    J, p, _ = blocks.shape
    blocks32 = jnp.asarray(blocks, jnp.float32)
    Q32, R32 = jnp.asarray(Q, jnp.float32), jnp.asarray(R, jnp.float32)
    xs_out, conv = [], []
    for c0 in range(0, B.shape[1], chunk):
        bb = jnp.asarray(
            B[:, c0:c0 + chunk].reshape(J, p, -1), jnp.float32
        )
        xs = _start(Q32, R32, bb)
        x, rsq, _ = _epochs_jit(
            blocks32, Q32, bb, xs, jnp.float32(gamma), jnp.float32(eta),
            jnp.float32(tol), num_epochs=num_epochs, precision=precision,
        )
        xs_out.append(np.asarray(x))
        conv.append(np.asarray(rsq) <= tol * tol)
    return np.concatenate(xs_out, axis=1), np.concatenate(conv)
