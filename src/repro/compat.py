"""Two jax spellings the solver needs that the plain API does not default to.

``jax.make_mesh`` builds Explicit-typed axes; the solvers want Auto axes
(sharding propagated by the compiler). ``jax.shard_map`` checks
replication statically; the matfree sharded solver needs that check off.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def shard_map_unchecked(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with static replication checking disabled.

    The matfree sharded solver runs ``lax.while_loop``s with SHARD-LOCAL
    stopping conditions (each device's inner CG exits on its own blocks'
    residuals), which the varying-manual-axes check cannot type.
    """
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def make_mesh(
    axis_shapes: Sequence[int], axis_names: Sequence[str], devices=None
):
    """``jax.make_mesh`` with Auto axis types, over ``devices`` (default:
    the first ``prod(axis_shapes)`` devices of the default backend)."""
    names = tuple(axis_names)
    return jax.make_mesh(
        tuple(axis_shapes), names,
        axis_types=(AxisType.Auto,) * len(names), devices=devices,
    )
