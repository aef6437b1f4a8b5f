"""Production mesh construction.

FUNCTIONS, not module-level constants — importing this module touches no
jax state at all (jax enters via deferred imports), so CLI drivers can
parse arguments, adjust ``XLA_FLAGS`` (``reserve_mesh_devices``), and
only then pull in the solver stack.
"""
from __future__ import annotations

import os


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; multi_pod adds a leading 2-pod axis."""
    from repro import compat

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_debug_mesh():
    """Single-device mesh with the production axis names (CPU tests)."""
    from repro import compat

    return compat.make_mesh((1, 1), ("data", "model"))


def force_host_device_count(devices: int, env=None):
    """Split the host CPU into ``devices`` XLA devices (appends
    ``--xla_force_host_platform_device_count`` to ``XLA_FLAGS``).

    The flag reaches the CPU backend only, and only if it lands before
    jax initializes its backends. Pass a mapping via ``env`` to mutate
    that (a child process's environment) instead of ``os.environ``.
    Returns the mutated mapping.
    """
    if env is None:
        env = os.environ
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={devices}"
    ).strip()
    return env


def reserve_mesh_devices(devices: int) -> None:
    """The ``--mesh D`` bootstrap, called before anything imports jax.

    On the CPU platform (``JAX_PLATFORMS=cpu``) the mesh needs ``devices``
    virtual host devices, so this sets the flag. Anywhere else the mesh
    spans the accelerator's real devices and nothing is set.
    """
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        force_host_device_count(devices)


def make_block_mesh(devices: int):
    """``(devices,)``-shaped ``("data",)`` mesh over the first ``devices``
    devices of the default backend — the chips of a TPU host, or the
    virtual devices ``reserve_mesh_devices`` made on the CPU. This is the
    block-sharded layout the sharded matfree path places its ELL shards
    over."""
    import jax

    from repro import compat

    found = jax.devices()
    if len(found) < devices:
        raise ValueError(
            f"a {devices}-device mesh needs {devices} devices; jax found "
            f"{len(found)} {found[0].platform} device(s) (on a CPU-only "
            "host, run with JAX_PLATFORMS=cpu to get virtual devices)"
        )
    return compat.make_mesh((devices,), ("data",), devices=found[:devices])
