"""Jit'd public wrapper for the blocked triangular solve.

Pads n to a block multiple by extending the triangle with an identity
diagonal (solves the padded system exactly: extra components are 0), and
selects interpret mode off-TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.trisolve import trisolve as _kernel


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("lower", "block", "interpret"))
def trisolve(
    r: jnp.ndarray,  # (n, n) triangular
    y: jnp.ndarray,  # (n,)
    lower: bool = False,
    block: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    if interpret is None:
        interpret = _interpret_default()
    n = r.shape[0]
    if block is None:
        block = min(_kernel.DEFAULT_BLOCK, max(8, 1 << (n - 1).bit_length()))
    n_pad = -(-n // block) * block
    pad = n_pad - n
    r_p = jnp.pad(r, ((0, pad), (0, pad)))
    # identity-extend the diagonal so the padded triangle stays non-singular
    if pad:
        idx = jnp.arange(n, n_pad)
        r_p = r_p.at[idx, idx].set(1.0)
    y_p = jnp.pad(y, (0, pad))[None, :]
    out = _kernel.trisolve_padded(
        r_p, y_p, lower=lower, block=block, interpret=interpret
    )
    return out[0, :n].astype(y.dtype)
