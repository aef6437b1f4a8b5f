"""Shape/dtype sweeps: blocked triangular-solve Pallas kernel vs oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.trisolve import ops
from repro.kernels.trisolve.ref import trisolve_ref


def _mk(n, seed=0, dtype=np.float32, diag_boost=3.0):
    rng = np.random.default_rng(seed)
    m = rng.standard_normal((n, n)).astype(dtype)
    r = np.triu(m)
    di = np.arange(n)
    r[di, di] = np.sign(r[di, di] + 0.5) * (diag_boost + np.abs(r[di, di]))
    y = rng.standard_normal(n).astype(dtype)
    return jnp.asarray(r), jnp.asarray(y)


def _relclose(got, want, rtol):
    scale = max(float(jnp.max(jnp.abs(want))), 1.0)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=rtol * scale, rtol=rtol
    )


SIZES = [1, 3, 8, 64, 100, 128, 130, 257, 512, 777]


@pytest.mark.parametrize("n", SIZES)
def test_upper(n):
    r, y = _mk(n, seed=n)
    _relclose(ops.trisolve(r, y, lower=False), trisolve_ref(r, y, lower=False), 1e-4)


@pytest.mark.parametrize("n", SIZES)
def test_lower(n):
    r, y = _mk(n, seed=n + 1)
    l = r.T
    _relclose(ops.trisolve(l, y, lower=True), trisolve_ref(l, y, lower=True), 1e-4)


@pytest.mark.parametrize("block", [8, 32, 128])
def test_block_sweep(block):
    r, y = _mk(300, seed=block)
    got = ops.trisolve(r, y, lower=False, block=block)
    _relclose(got, trisolve_ref(r, y, lower=False), 1e-4)


def test_solves_the_system():
    """Residual check against the system itself, not just the oracle."""
    r, y = _mk(256, seed=42)
    x = ops.trisolve(r, y, lower=False)
    scale = max(float(jnp.max(jnp.abs(x))), 1.0)
    np.testing.assert_allclose(np.asarray(r @ x), np.asarray(y), atol=2e-4 * scale)


def test_vmapped_over_blocks():
    J, n = 3, 192
    rs, ys = zip(*[_mk(n, seed=j) for j in range(J)])
    rs, ys = jnp.stack(rs), jnp.stack(ys)
    got = jax.vmap(lambda r, y: ops.trisolve(r, y))(rs, ys)
    want = jax.vmap(lambda r, y: trisolve_ref(r, y))(rs, ys)
    _relclose(got, want, 1e-4)


def test_f64_when_enabled():
    with jax.enable_x64(True):
        rng = np.random.default_rng(0)
        n = 96
        r = np.triu(rng.standard_normal((n, n))) + np.eye(n) * 4.0
        y = rng.standard_normal(n)
        got = ops.trisolve(jnp.asarray(r), jnp.asarray(y))
        want = trisolve_ref(jnp.asarray(r), jnp.asarray(y))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-9)


def test_dapc_end_to_end_with_kernels():
    """Full DAPC solve routed through BOTH Pallas kernels matches pure-jnp."""
    from repro.core import dapc, partition_system
    from repro.sparse import make_problem

    prob = make_problem(n=64, m=256, seed=11, dtype=np.float32)
    part = partition_system(prob.A, prob.b, 8)  # wide: p=32 < n=64
    ref = jnp.asarray(prob.x_true)
    x_k, h_k = dapc.solve_dapc(
        part, 1.0, 0.9, 60, x_ref=ref, materialize_p=False, use_kernels=True
    )
    x_j, h_j = dapc.solve_dapc(
        part, 1.0, 0.9, 60, x_ref=ref, materialize_p=False, use_kernels=False
    )
    np.testing.assert_allclose(np.asarray(x_k), np.asarray(x_j), atol=1e-4)
    assert float(h_k["mse"][-1]) < 1e-9
