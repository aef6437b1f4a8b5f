"""Deterministic property-test runs.

When hypothesis is installed, load a derandomized profile so every CI run
replays the same examples (no flaky shrink sessions, reproducible failures).
Without hypothesis, repro.testing's fallback runner is seeded per-test and
is deterministic by construction.

Also here: the ``profile`` fixture, which runs a block under
``jax.profiler`` and hands back the program's host spans.
"""
import contextlib
import glob
import os
import warnings

import pytest

try:
    from hypothesis import settings
except ModuleNotFoundError:
    pass
else:
    settings.register_profile("ci", derandomize=True, deadline=None)
    settings.load_profile("ci")


def read_program_events(directory) -> list:
    """The program's host events (``repro.*``) in the one ``.xplane.pb``
    under a ``jax.profiler.start_trace`` directory, in start order:
    ``(name, start ns, end ns, {stat: value}, thread line name)``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(
        os.path.join(str(directory), "**", "*.xplane.pb"), recursive=True
    )
    out = []
    # jax's stats iterator warns about its own type on every read
    warnings.filterwarnings("ignore", "builtin type event_stats")
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    out.append((
                        ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                        dict(ev.stats), line.name,
                    ))
    return sorted(out, key=lambda e: (e[1], -e[2]))


@pytest.fixture
def profile(tmp_path):
    """``with profile() as events:`` runs the block under
    ``jax.profiler``; after it, ``events`` holds ``read_program_events``
    of the trace."""
    import jax

    @contextlib.contextmanager
    def run():
        events: list = []
        directory = tmp_path / f"profile{len(list(tmp_path.iterdir()))}"
        jax.profiler.start_trace(str(directory))
        try:
            yield events
        finally:
            jax.profiler.stop_trace()
        events.extend(read_program_events(directory))

    return run


def hpcg_stencil(nx: int, ny: int, nz: int):
    """HPCG's matrix (Heroux, Dongarra, Luszczek, "HPCG Technical
    Specification", SAND2013-8752) on an ``nx × ny × nz`` grid, as a float64
    scipy CSR: a 27-point stencil, 26 on the diagonal and −1 for each
    neighbour inside the grid, so it is symmetric positive definite. Point
    ``(ix, iy, iz)`` is row ``ix + nx * (iy + ny * iz)``."""
    import numpy as np
    import scipy.sparse as sp

    index = np.arange(nx * ny * nz).reshape(nz, ny, nx)
    rows, cols = [], []
    for dz in (-1, 0, 1):
        for dy in (-1, 0, 1):
            for dx in (-1, 0, 1):
                src = index[max(0, -dz):nz - max(0, dz),
                            max(0, -dy):ny - max(0, dy),
                            max(0, -dx):nx - max(0, dx)]
                rows.append(src.ravel())
                cols.append((src + dx + nx * (dy + ny * dz)).ravel())
    rows, cols = np.concatenate(rows), np.concatenate(cols)
    vals = np.where(rows == cols, 26.0, -1.0)
    n = nx * ny * nz
    return sp.csr_matrix((vals, (rows, cols)), shape=(n, n))


@pytest.fixture(scope="session")
def hpcg():
    """``hpcg(nx, ny, nz, k=4, seed=0)`` -> ``(coo, A, B)``: the stencil as
    the solver's ``COOMatrix`` and as a float64 CSR, and ``k`` consistent
    float64 right-hand sides ``B = A X`` (X ~ N(0, 1) from the seed), scaled
    so that the smallest ‖b‖ is 1, as the benchmark scales them."""
    import numpy as np

    from repro.sparse import COOMatrix

    def make(nx, ny, nz, k=4, seed=0):
        A = hpcg_stencil(nx, ny, nz)
        c = A.tocoo()
        coo = COOMatrix(
            c.row.astype(np.int32), c.col.astype(np.int32), c.data, A.shape
        )
        B = A @ np.random.default_rng(seed).standard_normal((A.shape[1], k))
        return coo, A, B / np.linalg.norm(B, axis=0).min()

    return make
