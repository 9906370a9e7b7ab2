"""Plain reference for the variable-coefficient 2-D Poisson configurations.

Imports nothing of the program.  The operator is rebuilt here from the
conductivity field by its definition:

    A = sum over interior faces f = (a, b) of k_f (e_a - e_b)(e_a - e_b)^T
      + sum over boundary faces of cell p of kappa_p e_p e_p^T,
    k_f = 2 kappa_a kappa_b / (kappa_a + kappa_b)     (harmonic mean),

the unit-scaled cell-centred 5-point discretisation of -div(kappa grad u)
with u = 0 on the boundary.

Solves are preconditioned CG on the device with the constant-coefficient
operator (kappa = 1, exactly the 5-point Dirichlet Laplacian) inverted by
its sine transform as the preconditioner, the transform done as two dense
matrix products.  In float32 the solve is refined against residuals taken
in float64 on the host, so the answer is accurate far beyond float32's own
reach; in bfloat16 (the control) everything, residuals included, stays in
bfloat16.
"""
from __future__ import annotations

import functools
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

INNER_TOL = 1e-6
INNER_MAXITER = 300
ROUNDS = 6
REFINED = 1e-7           # stop once the error left, estimated as the last
                         # correction times its ratio to the one before, is
                         # this small (a round gains about four digits)


def faces(kappa: np.ndarray):
    """Diagonal, vertical-face and horizontal-face coefficients (float64)."""
    k = np.asarray(kappa, np.float64)
    kv = 2.0 * k[:-1] * k[1:] / (k[:-1] + k[1:])
    kh = 2.0 * k[:, :-1] * k[:, 1:] / (k[:, :-1] + k[:, 1:])
    nb = np.zeros_like(k)
    nb[0] += 1
    nb[-1] += 1
    nb[:, 0] += 1
    nb[:, -1] += 1
    diag = nb * k
    diag[:-1] += kv
    diag[1:] += kv
    diag[:, :-1] += kh
    diag[:, 1:] += kh
    return diag, kv, kh


def apply(op, x: np.ndarray, workers: int = 8) -> np.ndarray:
    """y = A x on the host in float64; ``x`` is (ng, ng).  Bands of rows go
    to a few threads (NumPy lets go of the interpreter lock in them)."""
    diag, kv, kh = op
    n = x.shape[0]
    y = np.empty_like(x)

    def band(i0, i1):
        yy = diag[i0:i1] * x[i0:i1]
        hi = min(i1, n - 1)                  # y[i] -= kv[i] x[i+1]
        yy[:hi - i0] -= kv[i0:hi] * x[i0 + 1:hi + 1]
        lo = max(i0, 1)                      # y[i] -= kv[i-1] x[i-1]
        yy[lo - i0:] -= kv[lo - 1:i1 - 1] * x[lo - 1:i1 - 1]
        yy[:, :-1] -= kh[i0:i1] * x[i0:i1, 1:]
        yy[:, 1:] -= kh[i0:i1] * x[i0:i1, :-1]
        y[i0:i1] = yy

    edges = np.linspace(0, n, workers + 1).astype(int)
    with ThreadPoolExecutor(workers) as pool:
        for f in [pool.submit(band, a, b) for a, b in zip(edges, edges[1:])
                  if b > a]:
            f.result()
    return y


def _apply_dev(diag, kv, kh, x):
    zr = jnp.zeros((1, x.shape[1]), x.dtype)
    zc = jnp.zeros((x.shape[0], 1), x.dtype)
    y = diag * x
    y = y - jnp.concatenate([kv * x[1:], zr], 0)
    y = y - jnp.concatenate([zr, kv * x[:-1]], 0)
    y = y - jnp.concatenate([kh * x[:, 1:], zc], 1)
    y = y - jnp.concatenate([zc, kh * x[:, :-1]], 1)
    return y


@functools.lru_cache(maxsize=2)
def _sine_basis(ng: int, dtype: str):
    """Sine transform matrix S (S S = (ng+1)/2 I) and the eigenvalues of
    the 5-point Dirichlet Laplacian, 4 sin^2(a/2) + 4 sin^2(b/2)."""
    j = np.arange(1, ng + 1)
    m = (j[:, None] * j[None, :]) % (2 * (ng + 1))
    s = np.sin(np.pi * m / (ng + 1))
    e = 4.0 * np.sin(np.pi * j / (2.0 * (ng + 1))) ** 2
    lam = e[:, None] + e[None, :]
    scale = (2.0 / (ng + 1)) ** 2
    return (jnp.asarray(s, dtype), jnp.asarray(scale / lam, dtype))


@functools.partial(jax.jit, static_argnames=("maxiter",))
def _pcg(diag, kv, kh, s, lam_inv, b, tol, maxiter: int):
    def prec(r):
        return s @ ((s @ r @ s) * lam_inv) @ s

    def cond(c):        # stop on the preconditioned residual (r, M^-1 r):
        k, _, _, _, _, rz = c       # it weighs the smooth modes as the error does
        return (k < maxiter) & (rz > tol * tol * rz0)

    def body(c):
        k, x, r, z, p, rz = c
        ap = _apply_dev(diag, kv, kh, p)
        alpha = rz / jnp.vdot(p, ap)
        x = x + alpha * p
        r = r - alpha * ap
        z = prec(r)
        rz1 = jnp.vdot(r, z)
        p = z + (rz1 / rz) * p
        return k + 1, x, r, z, p, rz1

    z = prec(b)
    rz0 = jnp.vdot(b, z)
    c = (jnp.int32(0), jnp.zeros_like(b), b, z, z, rz0)
    k, x, r, _, _, _ = jax.lax.while_loop(cond, body, c)
    return x, k


class Solver:
    """Reference solves against one conductivity field."""

    def __init__(self, kappa: np.ndarray, dtype: str = "float32"):
        self.op = faces(kappa)
        self.ng = self.op[0].shape[0]
        self.dtype = dtype
        kbar = float(np.mean(kappa))
        self.dev = tuple(jnp.asarray(a, dtype) for a in self.op)
        s, lam_inv = _sine_basis(self.ng, dtype)
        self.s = s
        self.lam_inv = (lam_inv / kbar).astype(dtype)
        self.inner_iters = []

    def _inner(self, r: np.ndarray) -> np.ndarray:
        x, k = _pcg(*self.dev, self.s, self.lam_inv,
                    jnp.asarray(r, self.dtype), INNER_TOL, INNER_MAXITER)
        self.inner_iters.append(int(k))
        return np.asarray(x.astype(jnp.float32), np.float64)

    def solve(self, b: np.ndarray) -> np.ndarray:
        """x = A^-1 b for a flat or (ng, ng) ``b``; returns float64, flat."""
        b = np.asarray(b, np.float64).reshape(self.ng, self.ng)
        if self.dtype != "float32":          # the control: no refinement
            return self._inner(b).ravel()
        t0 = time.perf_counter()
        x = np.zeros_like(b)
        r = b
        steps = []
        for _ in range(ROUNDS):
            s = float(np.max(np.abs(r)))
            if s == 0.0:
                break
            d = self._inner(r / s) * s
            x += d
            r = b - apply(self.op, x)
            steps.append(float(np.max(np.abs(d)) / np.max(np.abs(x))))
            if len(steps) > 1 and steps[-1] * steps[-1] / steps[-2] <= REFINED:
                break
        print(f"[reference] {len(steps)} rounds, inner iterations "
              f"{self.inner_iters[-len(steps):]}, corrections "
              f"{', '.join(f'{c:.1e}' for c in steps)}, "
              f"{time.perf_counter() - t0:.2f} s", file=sys.stderr, flush=True)
        return x.ravel()


def solve(data: dict, b: np.ndarray, dtype: str = "float32") -> np.ndarray:
    """A^-1 b for the configuration's data; the solver is kept in ``data``
    so that solves against one field share its set-up."""
    key = "_solver_" + dtype
    if key not in data:
        data[key] = Solver(data["kappa"], dtype)
    return data[key].solve(b)
