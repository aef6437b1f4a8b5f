"""Distributed (shard_map) solver tests.

In-process tests run on the single CPU device (1-device mesh exercises the
full SPMD code path). The multi-device tests spawn a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the main test
process keeps seeing exactly one device (required by the smoke tests).
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dapc, distributed, partition_system
from repro.sparse import make_problem


def _mesh1():
    return jax.make_mesh((1,), ("data",))


def test_sharded_matches_single_host():
    prob = make_problem(n=64, m=256, seed=2, dtype=np.float32)
    part = partition_system(prob.A, prob.b, 8)
    ref = jnp.asarray(prob.x_true)
    x_s, h_s = distributed.solve_sharded(
        part.blocks, part.bvecs, _mesh1(), part.mode,
        num_epochs=60, x_ref=ref,
    )
    x_l, h_l = dapc.solve_dapc(part, 1.0, 0.9, 60, x_ref=ref, materialize_p=False)
    np.testing.assert_allclose(np.asarray(x_s), np.asarray(x_l), atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(h_s["mse"]), np.asarray(h_l["mse"]), rtol=1e-3, atol=1e-10
    )


def test_sharded_classical_apc():
    prob = make_problem(n=48, m=192, seed=4, dtype=np.float32)
    part = partition_system(prob.A, prob.b, 8)
    x, hist = distributed.solve_sharded(
        part.blocks, part.bvecs, _mesh1(), part.mode,
        method="apc", num_epochs=80, x_ref=jnp.asarray(prob.x_true),
    )
    assert float(hist["mse"][-1]) < 1e-8


def test_straggler_consensus_converges():
    """Stale consensus (30% dropped updates/epoch) must still converge —
    the η-EMA absorbs missing contributions (straggler mitigation story)."""
    prob = make_problem(n=64, m=256, seed=6, dtype=np.float32)
    part = partition_system(prob.A, prob.b, 8)
    x, hist = distributed.solve_sharded(
        part.blocks, part.bvecs, _mesh1(), part.mode,
        num_epochs=250, straggler_prob=0.3, x_ref=jnp.asarray(prob.x_true),
    )
    assert float(hist["mse"][-1]) < 1e-7
    # and it costs extra epochs vs the synchronous run (sanity of simulation)
    _, h_sync = distributed.solve_sharded(
        part.blocks, part.bvecs, _mesh1(), part.mode,
        num_epochs=250, x_ref=jnp.asarray(prob.x_true),
    )
    assert float(h_sync["mse"][60]) <= float(hist["mse"][60]) * 1.01


def _batched_problem(n=64, m=256, k=4, seed=5):
    prob = make_problem(n=n, m=m, seed=2, dtype=np.float32)
    rng = np.random.default_rng(seed)
    xs = rng.standard_normal((n, k)).astype(np.float32)
    part = partition_system(prob.A, prob.A @ xs, 8)
    return part, xs


def test_sharded_batched_matches_per_column():
    """A coalesced (J, p, k) batch through solve_sharded must agree with k
    independent single-RHS sharded solves, column for column."""
    part, xs = _batched_problem()
    assert part.bvecs.ndim == 3  # (J, p, k)
    x_b, h_b = distributed.solve_sharded(
        part.blocks, part.bvecs, _mesh1(), part.mode,
        num_epochs=120, x_ref=jnp.asarray(xs),
    )
    assert np.asarray(x_b).shape == xs.shape
    # per-system history rows
    assert np.asarray(h_b["mse"]).shape == (120, xs.shape[1])
    assert np.asarray(h_b["residual_sq"]).shape == (120, xs.shape[1])
    assert float(np.max(np.asarray(h_b["mse"])[-1])) < 1e-9
    for i in range(xs.shape[1]):
        x_i, _ = distributed.solve_sharded(
            part.blocks, part.bvecs[:, :, i], _mesh1(), part.mode,
            num_epochs=120,
        )
        np.testing.assert_allclose(
            np.asarray(x_b)[:, i], np.asarray(x_i), atol=1e-5
        )


@pytest.mark.parametrize("method", ["dapc", "apc"])
def test_sharded_batched_recovers_truth(method):
    part, xs = _batched_problem()
    x_b, h_b = distributed.solve_sharded(
        part.blocks, part.bvecs, _mesh1(), part.mode,
        method=method, num_epochs=150, x_ref=jnp.asarray(xs),
    )
    np.testing.assert_allclose(np.asarray(x_b), xs, atol=1e-4)


def test_sharded_batched_straggler_converges():
    """Straggler simulation under batching: one stale worker delays ALL of
    its columns (a per-block mask), and the η-EMA still absorbs it."""
    part, xs = _batched_problem()
    _, hist = distributed.solve_sharded(
        part.blocks, part.bvecs, _mesh1(), part.mode,
        num_epochs=250, straggler_prob=0.3, x_ref=jnp.asarray(xs),
    )
    final = np.asarray(hist["mse"])[-1]
    assert final.shape == (xs.shape[1],)
    assert float(final.max()) < 1e-7


def test_sharded_batched_bf16_delta_matches_f32():
    """Delta-compressed consensus must track the f32 trajectory per column."""
    part, xs = _batched_problem()
    x_c, h_c = distributed.solve_sharded(
        part.blocks, part.bvecs, _mesh1(), part.mode,
        num_epochs=150, compress="bf16_delta", x_ref=jnp.asarray(xs),
    )
    assert float(np.max(np.asarray(h_c["mse"])[-1])) < 1e-9
    x_f, _ = distributed.solve_sharded(
        part.blocks, part.bvecs, _mesh1(), part.mode,
        num_epochs=150, x_ref=jnp.asarray(xs),
    )
    np.testing.assert_allclose(np.asarray(x_c), np.asarray(x_f), atol=1e-4)


def test_sharded_2d_batched_matches_per_column():
    """The 2D TSQR path with a (J, p, k) batch: shared b-independent TSQR,
    per-column agreement with the single-RHS 2D solves."""
    part, xs = _batched_problem()
    blocks_t = jnp.swapaxes(part.blocks, 1, 2)  # (J, n, p) wide-mode layout
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    x_b, h_b = distributed.solve_sharded_2d(
        blocks_t, part.bvecs, mesh, num_epochs=120, x_ref=jnp.asarray(xs),
    )
    assert np.asarray(x_b).shape == xs.shape
    assert np.asarray(h_b["mse"]).shape == (120, xs.shape[1])
    assert float(np.max(np.asarray(h_b["mse"])[-1])) < 1e-9
    for i in range(xs.shape[1]):
        x_i, _ = distributed.solve_sharded_2d(
            blocks_t, part.bvecs[:, :, i], mesh, num_epochs=120,
        )
        np.testing.assert_allclose(
            np.asarray(x_b)[:, i], np.asarray(x_i), atol=1e-5
        )


def test_repartition_elastic():
    """8-worker partition re-split to 4 (scale-down) keeps the solution."""
    prob = make_problem(n=64, m=512, seed=8, dtype=np.float32)
    part = partition_system(prob.A, prob.b, 8)
    b2, v2 = distributed.repartition(part.blocks, part.bvecs, 4)
    assert b2.shape == (4, 128, 64)
    assert v2.shape == (4, 128)  # single-RHS shape unchanged by the fix
    x, hist = distributed.solve_sharded(
        b2, v2, _mesh1(), "tall", num_epochs=5, x_ref=jnp.asarray(prob.x_true)
    )
    assert float(hist["mse"][-1]) < 1e-6  # tall blocks: exact block solves


def test_repartition_batched_multi_rhs():
    """Regression (ISSUE 5): ``repartition`` crashed on coalesced (J, p, k)
    batches — the documented RHS shape every other sharded entry point
    accepts — by reshaping ``bvecs`` as if it were (J, p). The trailing k
    axis must ride through the re-split unchanged."""
    prob = make_problem(n=64, m=512, seed=8, dtype=np.float32)
    rng = np.random.default_rng(5)
    xs = rng.standard_normal((64, 3)).astype(np.float32)
    part = partition_system(prob.A, prob.A @ xs, 8)
    assert part.bvecs.shape == (8, 64, 3)
    b2, v2 = distributed.repartition(part.blocks, part.bvecs, 4)
    assert b2.shape == (4, 128, 64)
    assert v2.shape == (4, 128, 3)
    # the re-split is a pure re-grouping: flattening back gives the same rows
    np.testing.assert_array_equal(
        np.asarray(v2).reshape(512, 3), np.asarray(part.bvecs).reshape(512, 3)
    )
    _, hist = distributed.solve_sharded(
        b2, v2, _mesh1(), "tall", num_epochs=5, x_ref=jnp.asarray(xs)
    )
    assert float(np.max(np.asarray(hist["mse"])[-1])) < 1e-6


MULTI_DEVICE_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import dapc, distributed, partition_system
    from repro.sparse import make_problem

    assert jax.device_count() == 8, jax.device_count()
    prob = make_problem(n=64, m=256, seed=2, dtype=np.float32)
    part = partition_system(prob.A, prob.b, 8)
    ref = jnp.asarray(prob.x_true)

    # --- row-sharded over data=4 (2 local blocks per shard) -----------------
    mesh = jax.make_mesh((4, 2), ("data", "model"))
    x_s, h_s = distributed.solve_sharded(
        part.blocks, part.bvecs, mesh, part.mode, num_epochs=60, x_ref=ref)
    x_l, h_l = dapc.solve_dapc(part, 1.0, 0.9, 60, x_ref=ref, materialize_p=False)
    np.testing.assert_allclose(np.asarray(x_s), np.asarray(x_l), atol=1e-5)
    print("row-sharded OK", float(h_s["mse"][-1]))

    # --- 8-way block sharding over both axes --------------------------------
    mesh8 = jax.make_mesh((8,), ("data",))
    x_8, h_8 = distributed.solve_sharded(
        part.blocks, part.bvecs, mesh8, part.mode, num_epochs=60, x_ref=ref)
    np.testing.assert_allclose(np.asarray(x_8), np.asarray(x_l), atol=1e-5)
    print("8-way OK", float(h_8["mse"][-1]))

    # --- 2D: blocks on data=4, solution dim on model=2 ----------------------
    blocks_t = jnp.swapaxes(part.blocks, 1, 2)  # (J, n, p)
    x_2d, h_2d = distributed.solve_sharded_2d(
        blocks_t, part.bvecs, mesh, num_epochs=60, x_ref=ref)
    np.testing.assert_allclose(np.asarray(x_2d), np.asarray(x_l), atol=1e-4)
    assert float(h_2d["mse"][-1]) < 1e-9
    print("2D TSQR OK", float(h_2d["mse"][-1]))

    # --- coalesced (J, p, k) batch, row-sharded over 8 real shards ----------
    rng = np.random.default_rng(5)
    xs = rng.standard_normal((64, 4)).astype(np.float32)
    partk = partition_system(prob.A, prob.A @ xs, 8)
    x_bk, h_bk = distributed.solve_sharded(
        partk.blocks, partk.bvecs, mesh8, partk.mode,
        num_epochs=150, x_ref=jnp.asarray(xs))
    assert np.asarray(x_bk).shape == (64, 4)
    np.testing.assert_allclose(np.asarray(x_bk), xs, atol=1e-4)
    print("batched row-sharded OK", float(np.max(np.asarray(h_bk["mse"])[-1])))
    """
)


STRAGGLER_RNG_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import functools
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    shard_map = jax.shard_map
    from repro.core import distributed

    # the failure mode: with block_axes=("pod", "data"), every shard that
    # shares a pod index used to fold the SAME axis index into the PRNG key
    # and therefore drew an identical straggler drop pattern
    mesh = jax.make_mesh((2, 2), ("pod", "data"))
    axes = ("pod", "data")

    @functools.partial(
        shard_map, mesh=mesh, in_specs=(P(axes),), out_specs=P(axes)
    )
    def draw(x):
        keys = distributed._epoch_keys(0, axes, 16)
        # the per-epoch alive mask solve_sharded draws for one local block
        mask = jax.vmap(lambda k: jax.random.uniform(k, (1,)) >= 0.3)(keys)
        return mask.reshape(1, 16).astype(jnp.float32) + 0.0 * jnp.sum(x)

    masks = np.asarray(draw(jnp.zeros((4, 1), jnp.float32)))  # (shard, epoch)
    for i in range(4):
        for j in range(i + 1, 4):
            assert not np.array_equal(masks[i], masks[j]), (
                f"shards {i} and {j} drew identical straggler masks:\\n{masks}"
            )
    print("straggler masks distinct OK")
    """
)


def test_straggler_rng_decorrelated_across_mesh_axes():
    """Regression (ISSUE 5): the straggler PRNG key folded in only
    ``block_axes[0]``, so on a 2-axis block mesh every shard sharing a
    first-axis index replayed the same drop pattern. Every axis index must
    enter the key."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", STRAGGLER_RNG_SCRIPT],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(__file__)), timeout=300,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "straggler masks distinct OK" in out.stdout


@pytest.mark.slow
def test_multi_device_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", MULTI_DEVICE_SCRIPT],
        capture_output=True, text=True, env=env, cwd=os.path.dirname(os.path.dirname(__file__)),
        timeout=600,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "2D TSQR OK" in out.stdout


def test_elastic_restart_mid_solve():
    """Fault-tolerance for the solver workload itself: crash after 40
    epochs, scale from 8 workers down to 4 (elastic repartition), restore
    x̄ from the 'checkpoint', and converge to the same answer — APC state
    is reconstructible from (A, b) + x̄ alone (DESIGN.md §7)."""
    from repro.core import dapc as dapc_mod

    prob = make_problem(n=64, m=512, seed=13, dtype=np.float32)
    part8 = partition_system(prob.A, prob.b, 8)
    ref = jnp.asarray(prob.x_true)
    # phase 1: 8 workers, 40 epochs, then "crash" (keep only x̄)
    xbar_ckpt, h1 = dapc_mod.solve_dapc(
        part8, 1.0, 0.9, 40, x_ref=ref, materialize_p=False
    )
    # phase 2: rebuild on 4 workers (different block layout), warm start
    b4, v4 = distributed.repartition(part8.blocks, part8.bvecs, 4)
    part4 = dataclasses.replace(part8, blocks=b4, bvecs=v4)
    x_final, h2 = dapc_mod.solve_dapc(
        part4, 1.0, 0.9, 120, x_ref=ref, materialize_p=False,
        xbar0=jnp.asarray(xbar_ckpt),
    )
    assert float(h2["mse"][-1]) < 1e-9
    # warm start must not regress below the checkpointed accuracy
    assert float(h2["mse"][0]) < float(h1["mse"][0])

