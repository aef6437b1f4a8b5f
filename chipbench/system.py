"""The linear systems the cells solve, and their float64 host reference.

The core of every system is the HPCG benchmark's 27-point stencil matrix
(Heroux, Dongarra and Luszczek, "HPCG Technical Specification", Sandia
report SAND2013-8752), built here from its definition. The eq. (8)
augmentation and ``chip_smoke``'s float64 helpers are copied from the
program on purpose, so that a later change to the program cannot move the
yardstick. The eq. (8) rows and the solutions are made from the run's
seed; the reference is ``x_true`` itself (each system is consistent and of
full column rank, so ``x_true`` is its unique solution) together with the
float64 residual of the sparse core.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import scipy.sparse as sp

# independent random streams drawn from one seed
STREAM_MIXING, STREAM_X, STREAM_ORDER = 2, 3, 4


def rng(seed: int, stream: int) -> np.random.Generator:
    """The generator of one stream of one seed (any integer seed)."""
    return np.random.default_rng([seed % 2**64, stream])


def hpcg_matrix(nx: int, ny: int, nz: int) -> sp.csr_matrix:
    """The HPCG benchmark's matrix on an ``nx × ny × nz`` grid.

    A 27-point stencil: point ``(ix, iy, iz)`` is row
    ``ix + nx * (iy + ny * iz)``, with 26 on the diagonal and −1 for each
    of its (up to 26) neighbours inside the grid; points outside the grid
    are left out, so boundary rows are strictly diagonally dominant and the
    matrix is symmetric positive definite."""
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


@dataclasses.dataclass
class System:
    """One consistent system ``[A; G A] x = [b; G b]`` (``G`` absent when
    ``m == n``). ``core`` is the float64 sparse square core, ``mixing`` the
    float32 eq. (8) rows ``G`` (``(m - n, n)``) or None."""

    core: sp.csr_matrix
    mixing: np.ndarray | None

    @property
    def n(self) -> int:
        return self.core.shape[1]

    @property
    def m(self) -> int:
        return self.n + (0 if self.mixing is None else self.mixing.shape[0])

    def rhs(self, X: np.ndarray) -> np.ndarray:
        """Float64 right-hand sides ``A_aug @ X`` for solutions ``X`` (n, k)."""
        top = self.core @ X
        if self.mixing is None:
            return top
        return np.concatenate([top, self.mixing.astype(np.float64) @ top])

    def dense(self, dtype=np.float32) -> np.ndarray:
        """The whole (m, n) system, the dense path's input; the eq. (8)
        rows are formed as ``G·A`` against the sparse core, in float64."""
        top = self.core.toarray()
        if self.mixing is None:
            return top.astype(dtype)
        low = (self.core.T @ self.mixing.T.astype(np.float64)).T
        return np.concatenate([top, low]).astype(dtype)

    def coo(self):
        """Rows, cols and float64 values of the core, sorted by row."""
        c = self.core.tocoo()
        return c.row.astype(np.int32), c.col.astype(np.int32), c.data


def make_system(config: dict, seed: int) -> System:
    """The system a configuration describes: the HPCG matrix on its
    ``nx × ny × nz`` grid (``n`` unknowns), with ``m − n`` eq. (8) rows
    below it when ``m > n``."""
    nx, ny, nz = (int(config[k]) for k in ("nx", "ny", "nz"))
    n, m = int(config["n"]), int(config["m"])
    if n != nx * ny * nz:
        raise ValueError(f"n = {n} is not the grid's {nx}·{ny}·{nz} points")
    core = hpcg_matrix(nx, ny, nz)
    mixing = None
    if m > n:
        mixing = rng(seed, STREAM_MIXING).standard_normal(
            (m - n, n), dtype=np.float32
        ) / np.float32(np.sqrt(n))
    return System(core, mixing)


def inputs(config: dict, seed: int, columns: int):
    """A run's system, its ``x_true`` (n, columns), the float64 right-hand
    sides, and the absolute tolerance every column is solved to.

    ``x_true`` and ``b`` are scaled together so that the smallest ‖b‖ is 1:
    the tolerance ``tol_rel · min ‖b‖`` is then ``tol_rel`` for every seed.
    The program compiles a solve for each tolerance value, so a tolerance
    that moved with the seed would compile anew in every run's set-up."""
    system = make_system(config, seed)
    X = x_true(system.n, columns, seed)
    B = system.rhs(X)
    scale = 1.0 / float(np.min(np.linalg.norm(B, axis=0)))
    return system, X * scale, B * scale, float(config["solve"]["tol_rel"])


def x_true(n: int, k: int, seed: int, stream: int = STREAM_X) -> np.ndarray:
    """The seeded solutions, one column per system: N(0, 1) entries
    (``inputs`` scales them)."""
    return rng(seed, stream).standard_normal((n, k))


def relerr(x: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per column ‖x − ref‖ / ‖ref‖ in float64."""
    x = np.asarray(x, np.float64).reshape(ref.shape)
    return np.linalg.norm(x - ref, axis=0) / np.linalg.norm(ref, axis=0)


def residual(system: System, x: np.ndarray, X: np.ndarray) -> np.ndarray:
    """Per column float64 ‖A x − b‖ of the whole system that was solved
    (the eq. (8) rows included), with b = A x_true."""
    x = np.asarray(x, np.float64).reshape(X.shape)
    return np.linalg.norm(system.rhs(x) - system.rhs(X), axis=0)
