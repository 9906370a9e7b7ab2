"""Plain reference for the HPCG configurations.

Imports nothing of the program.  HPCG's operator on an nx × ny × nz grid
(26 on the diagonal, −1 for each neighbour inside the grid) is exactly

    A = 27 I − K_z ⊗ K_y ⊗ K_x,    K_m = tridiag(1, 1, 1) of size m,

and the orthonormal type-I sine transform diagonalises each K_m, with
eigenvalues 1 + 2 cos(kπ / (m + 1)).  So x = S Λ⁻¹ S b, S the 3-D
``scipy.fft.dstn(type=1, norm="ortho")`` (its own inverse), is the exact
answer up to float64 rounding.  In bfloat16 (the control) b, each
transform's result, the scaled spectrum and x are rounded through
bfloat16.
"""
from __future__ import annotations

import functools

import ml_dtypes
import numpy as np
from scipy.fft import dstn


@functools.lru_cache(maxsize=2)
def _spectrum(dims: tuple) -> np.ndarray:
    nx, ny, nz = dims
    e = [1.0 + 2.0 * np.cos(np.arange(1, m + 1) * np.pi / (m + 1))
         for m in (nz, ny, nx)]
    return 27.0 - (e[0][:, None, None] * e[1][None, :, None]
                   * e[2][None, None, :])


def _round(a: np.ndarray, dtype: str) -> np.ndarray:
    if dtype == "bfloat16":
        return a.astype(ml_dtypes.bfloat16).astype(np.float64)
    return a


def solve(data: dict, b: np.ndarray, dtype: str = "float64") -> np.ndarray:
    """A⁻¹ b for the configuration's grid; float64, flat."""
    nx, ny, nz = data["dims"]
    lam = _spectrum((nx, ny, nz))
    r = lambda a: _round(a, dtype)
    bb = r(np.asarray(b, np.float64).reshape(nz, ny, nx))
    y = r(dstn(bb, type=1, norm="ortho", workers=8))
    y = r(y / lam)
    return r(dstn(y, type=1, norm="ortho", workers=8)).ravel()
