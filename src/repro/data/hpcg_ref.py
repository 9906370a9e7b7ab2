"""Plain loop-level reference of HPCG 3.1's problem and multigrid
preconditioner, in numpy and SciPy, for checking the library's 3-D path.

It follows the reference code's ``GenerateProblem``,
``GenerateCoarseProblem`` (the coarse problem is ``GenerateProblem`` on
the grid halved in each dimension, with ``f2c`` mapping coarse cell
(i, j, k) to fine cell (2i, 2j, 2k)), ``ComputeSYMGS_ref``,
``ComputeRestriction_ref``, ``ComputeProlongation_ref`` and
``ComputeMG_ref``, one row at a time.  It departs from the published code
in one respect only: Gauss–Seidel visits the rows in the 8-colour order
(colour ``4 (z mod 2) + 2 (y mod 2) + (x mod 2)``, rows of a colour in
index order; the forward sweep over colours 0→7, the backward sweep
7→0) in place of lexicographic order — the order the library's kernels
use.  Nothing here imports the library.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp

LEVELS = 4


def generate_problem(nx: int, ny: int, nz: int) -> sp.csr_matrix:
    """HPCG's ``GenerateProblem`` on one process's ``nx × ny × nz`` grid:
    row ``iz·ny·nx + iy·nx + ix``, 26 on the diagonal and −1 for every
    neighbour inside the grid."""
    rows, cols, vals = [], [], []
    for iz in range(nz):
        for iy in range(ny):
            for ix in range(nx):
                row = (iz * ny + iy) * nx + ix
                for sz in (-1, 0, 1):
                    for sy in (-1, 0, 1):
                        for sx in (-1, 0, 1):
                            z, y, x = iz + sz, iy + sy, ix + sx
                            if 0 <= z < nz and 0 <= y < ny and 0 <= x < nx:
                                rows.append(row)
                                cols.append((z * ny + y) * nx + x)
                                vals.append(26.0 if (sz, sy, sx) == (0, 0, 0)
                                            else -1.0)
    n = nx * ny * nz
    return sp.csr_matrix((vals, (rows, cols)), shape=(n, n))


def f2c(nx: int, ny: int, nz: int) -> np.ndarray:
    """Fine row of each coarse row of the grid halved in each dimension."""
    out = []
    for iz in range(nz // 2):
        for iy in range(ny // 2):
            for ix in range(nx // 2):
                out.append((2 * iz * ny + 2 * iy) * nx + 2 * ix)
    return np.asarray(out)


def colours(nx: int, ny: int, nz: int) -> np.ndarray:
    """Each row's colour, ``4 (z mod 2) + 2 (y mod 2) + (x mod 2)``."""
    z, y, x = np.meshgrid(np.arange(nz), np.arange(ny), np.arange(nx),
                          indexing="ij")
    return (4 * (z % 2) + 2 * (y % 2) + (x % 2)).ravel()


class Level:
    def __init__(self, nx: int, ny: int, nz: int):
        self.dims = (nx, ny, nz)
        self.A = generate_problem(nx, ny, nz)
        c = colours(nx, ny, nz)
        self.order = [np.nonzero(c == k)[0] for k in range(8)]


def hierarchy(nx: int, ny: int, nz: int, levels: int = LEVELS):
    out = [Level(nx, ny, nz)]
    for _ in range(levels - 1):
        nx, ny, nz = nx // 2, ny // 2, nz // 2
        out.append(Level(nx, ny, nz))
    return out


def symgs(level: Level, r: np.ndarray, x: np.ndarray) -> np.ndarray:
    """``ComputeSYMGS_ref`` in the 8-colour order: a forward sweep, then a
    backward sweep, each row updated from the newest values."""
    A = level.A
    x = x.copy()

    def relax(i):
        lo, hi = A.indptr[i], A.indptr[i + 1]
        cols, vals = A.indices[lo:hi], A.data[lo:hi]
        diag = vals[cols == i][0]
        s = r[i] - vals @ x[cols] + diag * x[i]
        x[i] = s / diag

    for k in range(8):
        for i in level.order[k]:
            relax(i)
    for k in reversed(range(8)):
        for i in level.order[k][::-1]:
            relax(i)
    return x


def compute_mg(levels, r: np.ndarray, level: int = 0) -> np.ndarray:
    """``ComputeMG_ref``: x = 0; one pre-smoothing SymGS, restriction of
    r − A x by injection, the coarse problem's MG, prolongation by
    injection-add, one post-smoothing SymGS; the coarsest level runs one
    SymGS and nothing else."""
    lv = levels[level]
    x = np.zeros_like(r)
    if level == len(levels) - 1:
        return symgs(lv, r, x)
    x = symgs(lv, r, x)
    idx = f2c(*lv.dims)
    rc = (r - lv.A @ x)[idx]
    xc = compute_mg(levels, rc, level + 1)
    x[idx] += xc
    return symgs(lv, r, x)
