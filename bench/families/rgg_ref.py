"""Plain reference for the random geometric graph configurations.

Imports nothing of the program.  The shifted Laplacian is rebuilt from the
edge list as a SciPy CSR matrix in float64 and solved by Jacobi-
preconditioned CG to a relative residual of 1e-12.  The control computes
the same CG with every stored value rounded to bfloat16 after each
operation (products accumulated in float32, as the chip's vector unit
does).
"""
from __future__ import annotations

import numpy as np

TOL = 1e-12
MAXITER = 20000


def matrix(data: dict):
    import scipy.sparse as sp
    n, ei, ej = data["n"], data["ei"], data["ej"]
    deg = np.bincount(np.concatenate([ei, ej]), minlength=n).astype(np.float64)
    gamma = data["shift"] * max(float(deg.mean()), 1.0)
    w = sp.coo_matrix((np.ones(len(ei)), (ei, ej)), shape=(n, n))
    w = (w + w.T).tocsr()
    return (sp.diags(deg + gamma) - w).tocsr()


def _pcg(matvec, dinv, b, tol, maxiter, rnd):
    x = np.zeros_like(b)
    r = b.copy()
    z = rnd(dinv * r)
    p = z.copy()
    rz = rnd(np.dot(r, z))
    bn = np.linalg.norm(b)
    for _ in range(maxiter):
        if np.linalg.norm(r) <= tol * bn:
            break
        ap = rnd(matvec(p))
        alpha = rnd(rz / rnd(np.dot(p, ap)))
        x = rnd(x + rnd(alpha * p))
        r = rnd(r - rnd(alpha * ap))
        z = rnd(dinv * r)
        rz1 = rnd(np.dot(r, z))
        p = rnd(z + rnd((rz1 / rz) * p))
        rz = rz1
    return x


def solve(data: dict, b: np.ndarray, dtype: str = "float64") -> np.ndarray:
    A = matrix(data)
    if dtype == "float64":
        dinv = 1.0 / A.diagonal()
        return _pcg(A.dot, dinv, np.asarray(b, np.float64), TOL, MAXITER,
                    lambda a: a)
    import ml_dtypes
    low = np.dtype(getattr(ml_dtypes, dtype))

    def rnd(a):
        return np.asarray(a, np.float32).astype(low).astype(np.float32)

    A32 = A.astype(np.float32)
    A32.data = rnd(A32.data)
    dinv = rnd(1.0 / A32.diagonal())
    return _pcg(A32.dot, dinv, rnd(b), 1e-6, 1000, rnd).astype(np.float64)
