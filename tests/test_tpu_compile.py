"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Nothing runs: ``jax.jit(...).lower(...).compile()`` against shapes placed on
a device of a described ``v5e:2x2`` topology raises what the chip's compiler
would raise (SMEM/VMEM overruns, unsupported lowerings). Widths are those of
``chip_smoke.py``'s kernel phase: the matfree system's blocked-ELL operator
(n = 16384, J = 8, 8×8 tiles, 224 slots per block-row unbalanced; its Gram
shards 248 slots over 256 column blocks) at a k = 8 batch, and the dense
phase's blocks (m = 16384, n = 8192, J = 8 → p = 2048, wide regime), whose
whole consensus solve program is compiled too, with the implicit projector
``prepare`` picks there.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and each test worker imports every test
file.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import prepare

from repro.kernels.project.project import consensus_update_padded
from repro.kernels.spmm.spmm import spmm_fused_padded, spmm_padded
from repro.kernels.trisolve.trisolve import trisolve_padded

# matfree phase: J blocks of R block-rows, tiles of TILE², k RHS; each
# product has its own slot count S and column-block count C
J, R, TILE, K = 8, 256, 8, 8
PRODUCTS = {"forward": (224, 2048), "gram": (248, 256)}  # name: (S, C)
# dense phase: p_pad rows per block, n columns
P_PAD, N = 2048, 8192


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args, **static):
    return jax.jit(lambda *a: fn(*a, **static)).lower(*args).compile().as_text()


@pytest.mark.parametrize("product", sorted(PRODUCTS))
def test_spmm_compiles_at_matfree_width(one_chip, product):
    S, C = PRODUCTS[product]
    text = _compiled_text(
        spmm_padded,
        _shape(one_chip, (J, R, S), jnp.int32),
        _shape(one_chip, (J, R, S, TILE, TILE)),
        _shape(one_chip, (J, C, TILE, K)),
    )
    assert "tpu_custom_call" in text


def test_spmm_fused_compiles_at_matfree_width(one_chip):
    S, C = PRODUCTS["forward"]
    text = _compiled_text(
        spmm_fused_padded,
        _shape(one_chip, (J, R, S), jnp.int32),
        _shape(one_chip, (J, R, S, TILE, TILE)),
        _shape(one_chip, (J, C, TILE, K)),
        _shape(one_chip, (J, R, TILE, K)),
    )
    assert "tpu_custom_call" in text


def test_consensus_update_compiles_at_dense_width(one_chip):
    text = _compiled_text(
        consensus_update_padded,
        _shape(one_chip, (P_PAD, N)),
        _shape(one_chip, (N, 1)),
        _shape(one_chip, (N, 1)),
        gamma=1.0,
    )
    assert text.count("tpu_custom_call") >= 2  # matvec pass + update pass


@pytest.mark.parametrize("lower", [False, True])
def test_trisolve_compiles_at_dense_block_width(one_chip, lower):
    text = _compiled_text(
        trisolve_padded,
        _shape(one_chip, (P_PAD, P_PAD)),
        _shape(one_chip, (1, P_PAD)),
        lower=lower,
    )
    assert "tpu_custom_call" in text


def test_dense_solve_reads_the_factor_as_stored(one_chip):
    """The dense consensus solve at the dense phase's width takes the
    implicit projector, and its program holds no copy or transpose of the
    (J, p, n) factor W and no n×n projector: W v and Wᵀ u both stream W in
    its stored layout."""
    a = np.random.default_rng(0).standard_normal((128, 64)).astype(np.float32)
    prep = prepare(a, num_blocks=J)  # p = 16 < n / 2: wide, implicit
    assert prep.mode == "wide" and prep.projector_form == "implicit"
    run = prep._consensus_program(600, {"tol": 1e-5})
    factor = _shape(one_chip, (J, P_PAD, N))
    scalar = _shape(one_chip, ())
    text = run.lower(
        factor, (factor, _shape(one_chip, (J, P_PAD, P_PAD))), factor,
        _shape(one_chip, (J, P_PAD, K)), scalar, scalar, None, None, None,
    ).compile().as_text()
    assert " while(" in text
    moved = re.escape(f"f32[{J},{P_PAD},{N}]")
    moved += r"\S* (copy|copy-start|transpose)\("
    assert not re.search(moved, text)
    assert f"f32[{J},{N},{P_PAD}]" not in text  # no Wᵀ anywhere
    assert f"f32[{J},{N},{N}]" not in text  # no materialized P_j
