"""Logical-axis sharding rules → PartitionSpecs (MaxText-style).

Every parameter is declared once as a ``ParamSpec`` (shape + logical axis
names); the same declaration yields the initialized array, the
``jax.ShapeDtypeStruct`` stand-in for dry-runs, and the ``PartitionSpec``.

Rules (production mesh ``(pod, data, model)``):
  * ``batch``      → (pod, data)   — data parallelism
  * ``embed``      → data          — FSDP-style weight shard of d_model dims
  * ``vocab/ff/heads_flat/experts/inner`` → model — tensor/expert parallelism
  * ``layers``     → None          — scan-stacked depth dim stays unsharded
  * ``seq``        → None by default; long-context cells shard it over data
                     (sequence parallelism) via an override.

Axes that do not divide the mesh axis size are dropped (replicated) — e.g.
8 KV heads on a 16-way model axis fall back to replication, which is the
standard Megatron behaviour; flattened head dims are used in the weight
layout so this almost never triggers (DESIGN.md §7).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

SHARDING_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "embed": ("data",),
    "vocab": ("model",),
    "ff": ("model",),
    "heads_flat": ("model",),
    "kv_flat": ("model",),
    "experts": ("model",),
    "inner": ("model",),
    "state": (),
    "seq": (),
    "seq_kv": ("pod", "data", "model"),
    "layers": (),
    "conv": (),
}


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """Declaration of one parameter tensor."""

    shape: tuple[int, ...]
    axes: tuple[str | None, ...]
    init: str = "normal"  # normal | zeros | ones
    scale: float | None = None  # std for normal; default 0.02

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(f"shape/axes rank mismatch: {self}")


def _mesh_axes_for(logical: str | None, mesh: Mesh, dim: int,
                   rules: dict[str, tuple[str, ...]]) -> tuple[str, ...]:
    if logical is None:
        return ()
    names = tuple(a for a in rules.get(logical, ()) if a in mesh.shape)
    if not names:
        return ()
    total = math.prod(mesh.shape[a] for a in names)
    if dim % total:
        # drop trailing axes until divisible (replicate what doesn't fit)
        while names and dim % math.prod(mesh.shape[a] for a in names):
            names = names[:-1]
    return names


def logical_to_spec(
    axes: tuple[str | None, ...],
    shape: tuple[int, ...],
    mesh: Mesh,
    rules: dict[str, tuple[str, ...]] | None = None,
) -> P:
    rules = rules or SHARDING_RULES
    used: set[str] = set()
    parts = []
    for dim, logical in zip(shape, axes):
        names = tuple(
            a for a in _mesh_axes_for(logical, mesh, dim, rules) if a not in used
        )
        used.update(names)
        if len(names) == 0:
            parts.append(None)
        elif len(names) == 1:
            parts.append(names[0])
        else:
            parts.append(names)
    return P(*parts)


def tree_pspecs(spec_tree: Any, mesh: Mesh, rules=None) -> Any:
    """ParamSpec tree → PartitionSpec tree."""
    return jax.tree.map(
        lambda s: logical_to_spec(s.axes, s.shape, mesh, rules),
        spec_tree,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


def shape_structs(spec_tree: Any, dtype=jnp.float32) -> Any:
    """ParamSpec tree → ShapeDtypeStruct tree (dry-run stand-ins)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype),
        spec_tree,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


def init_from_specs(spec_tree: Any, key: jax.Array, dtype=jnp.float32) -> Any:
    """ParamSpec tree → initialized parameter tree (deterministic per-path)."""
    leaves, treedef = jax.tree.flatten(
        spec_tree, is_leaf=lambda x: isinstance(x, ParamSpec)
    )

    def init_one(i: int, s: ParamSpec):
        if s.init == "zeros":
            return jnp.zeros(s.shape, dtype)
        if s.init == "ones":
            return jnp.ones(s.shape, dtype)
        std = 0.02 if s.scale is None else s.scale
        return std * jax.random.normal(jax.random.fold_in(key, i), s.shape, dtype)

    return jax.tree.unflatten(treedef, [init_one(i, s) for i, s in enumerate(leaves)])


import os

ACT_SEQ_AXIS: str | None = (
    None if os.environ.get("REPRO_ACT_SEQ", "model") in ("none", "")
    else os.environ.get("REPRO_ACT_SEQ", "model")
)


def maybe_shard_activations(
    x, batch_axes=("pod", "data"), seq_axis: str | None = None
):
    if seq_axis is None:
        seq_axis = ACT_SEQ_AXIS
    """Sequence-parallel sharding constraint on a (B, S, D) residual stream.

    Active only when lowering under ``jax.sharding.set_mesh`` (the launcher
    does this); a no-op in CPU tests. Sharding the scanned carry makes the
    remat-saved per-layer activations 1/model_ways the size — the difference
    between fitting and not fitting HBM for the big train cells (DESIGN.md
    §7, EXPERIMENTS.md §Perf)."""
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names or getattr(x, "ndim", 0) != 3:
        return x
    names = set(mesh.axis_names)
    ba = tuple(a for a in batch_axes if a in names)
    if ba and x.shape[0] % math.prod(mesh.shape[a] for a in ba):
        ba = ()
    sa = seq_axis if (seq_axis in names) else None
    if sa and x.shape[1] % mesh.shape[sa]:
        sa = None
    if not ba and sa is None:
        return x
    spec = P(ba if ba else None, sa, None)
    return jax.lax.with_sharding_constraint(x, spec)


def constrain(x, axes: tuple[str | None, ...], rules=None):
    """``with_sharding_constraint`` from logical axis names, active only when
    lowering under ``jax.sharding.set_mesh`` (no-op in CPU tests).

    Used inside blocks whose internal reshapes defeat SPMD propagation —
    e.g. the SSD (B,nc,L,H,P) chunk tensors must keep H on the ``model``
    axis or they silently replicate 16× (EXPERIMENTS.md §Perf, zamba2)."""
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names or getattr(x, "ndim", 0) != len(axes):
        return x
    spec = logical_to_spec(axes, x.shape, mesh, rules)
    if all(p is None for p in spec):
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def named_shardings(spec_tree: Any, mesh: Mesh, rules=None) -> Any:
    return jax.tree.map(
        lambda p: NamedSharding(mesh, p),
        tree_pspecs(spec_tree, mesh, rules),
        is_leaf=lambda x: isinstance(x, P),
    )
