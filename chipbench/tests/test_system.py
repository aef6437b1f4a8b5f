"""The HPCG matrix the cells solve, against its definition point by point."""
import itertools

import numpy as np

from chipbench import system


def test_hpcg_matrix_is_the_27_point_stencil():
    nx, ny, nz = 3, 4, 5
    A = system.hpcg_matrix(nx, ny, nz).toarray()
    want = np.zeros_like(A)
    for iz, iy, ix in itertools.product(range(nz), range(ny), range(nx)):
        row = ix + nx * (iy + ny * iz)
        for dz, dy, dx in itertools.product((-1, 0, 1), repeat=3):
            jx, jy, jz = ix + dx, iy + dy, iz + dz
            if 0 <= jx < nx and 0 <= jy < ny and 0 <= jz < nz:
                col = jx + nx * (jy + ny * jz)
                want[row, col] = 26.0 if col == row else -1.0
    np.testing.assert_array_equal(A, want)
    assert np.linalg.eigvalsh(A).min() > 0


def test_system_follows_the_grid_and_the_seed():
    config = {"nx": 4, "ny": 4, "nz": 2, "n": 32, "m": 64}
    a, b = system.make_system(config, 7), system.make_system(config, 7)
    assert a.core.shape == (32, 32) and a.m == 64
    np.testing.assert_array_equal(a.mixing, b.mixing)
    assert not np.array_equal(a.mixing, system.make_system(config, 8).mixing)
    np.testing.assert_array_equal(
        a.core.toarray(), system.make_system(config, 8).core.toarray())
