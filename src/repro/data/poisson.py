"""Poisson problem generators — the paper's benchmark workload (§4).

``poisson2d``      : constant-coefficient 5-point Laplacian, COO, Dirichlet.
``poisson2d_vc``   : variable-coefficient −∇·(κ∇u) cell-centered FD assembly
                     (the §4.4 inverse-coefficient operator), differentiable
                     in κ, with both COO and stencil-kernel layouts.
``poisson1d``      : tridiagonal, for cheap unit tests.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.sparse import SparseTensor
from ..kernels.stencil5 import OFFSETS, Stencil5Meta


def poisson1d(n: int, dtype=np.float64) -> SparseTensor:
    i = np.arange(n)
    rows = np.concatenate([i, i[1:], i[:-1]])
    cols = np.concatenate([i, i[1:] - 1, i[:-1] + 1])
    vals = np.concatenate([np.full(n, 2.0), np.full(n - 1, -1.0),
                           np.full(n - 1, -1.0)]).astype(dtype)
    return SparseTensor(vals, rows, cols, (n, n))


def poisson2d(ng: int, dtype=np.float64, build_kernel_layout: bool = False
              ) -> SparseTensor:
    """(ng×ng interior points, h=1/(ng+1), scaled by 1/h² omitted — the paper
    benchmarks the unit-scaled stencil)."""
    n = ng * ng
    idx = np.arange(n).reshape(ng, ng)
    rows = [idx.ravel()]
    cols = [idx.ravel()]
    vals = [np.full(n, 4.0, dtype)]
    for (di, dj) in ((-1, 0), (1, 0), (0, -1), (0, 1)):
        src = idx[max(0, -di):ng - max(0, di), max(0, -dj):ng - max(0, dj)]
        dst = idx[max(0, di):ng - max(0, -di), max(0, dj):ng - max(0, -dj)]
        rows.append(src.ravel())
        cols.append(dst.ravel())
        vals.append(np.full(src.size, -1.0, dtype))
    return SparseTensor(np.concatenate(vals), np.concatenate(rows),
                        np.concatenate(cols), (n, n),
                        build_kernel_layout=build_kernel_layout)


# ---------------------------------------------------------------------------
# variable-coefficient assembly (differentiable in κ) — paper §4.4
# ---------------------------------------------------------------------------

def vc_pattern(ng: int) -> Tuple[np.ndarray, np.ndarray, Stencil5Meta]:
    """COO pattern matching the (5, ng, ng) signed coefficient planes of the
    stencil kernel: entry order = planes (C, N, S, W, E) × row-major cells;
    out-of-domain neighbours keep a slot with a structurally-zero value (and
    a clamped in-range column) so COO and stencil layouts share one ``val``."""
    idx = np.arange(ng * ng).reshape(ng, ng)
    rows, cols = [], []
    for (di, dj) in OFFSETS:
        r = idx
        ii = np.clip(np.arange(ng)[:, None] + di, 0, ng - 1)
        jj = np.clip(np.arange(ng)[None, :] + dj, 0, ng - 1)
        c = idx[ii, jj]
        rows.append(r.ravel())
        cols.append(c.ravel())
    meta = Stencil5Meta(nx=ng, ny=ng)
    return np.concatenate(rows), np.concatenate(cols), meta


def vc_coefficients(kappa: jax.Array) -> jax.Array:
    """κ (ng, ng) cell conductivities → signed planes (5, ng, ng), flattened.

    Face coefficient = harmonic mean of adjacent cells; Dirichlet u=0 via
    boundary faces with coefficient κ_cell (ghost κ = κ_cell).  Fully
    differentiable in κ — this is the assembly inside the §4.4 training loop.
    """
    ng = kappa.shape[0]

    def hmean(a, b):
        return 2.0 * a * b / (a + b + 1e-30)

    kN = jnp.where(jnp.arange(ng)[:, None] > 0,
                   hmean(kappa, jnp.roll(kappa, 1, 0)), kappa)
    kS = jnp.where(jnp.arange(ng)[:, None] < ng - 1,
                   hmean(kappa, jnp.roll(kappa, -1, 0)), kappa)
    kW = jnp.where(jnp.arange(ng)[None, :] > 0,
                   hmean(kappa, jnp.roll(kappa, 1, 1)), kappa)
    kE = jnp.where(jnp.arange(ng)[None, :] < ng - 1,
                   hmean(kappa, jnp.roll(kappa, -1, 1)), kappa)
    C = kN + kS + kW + kE
    # neighbour couplings: zero at the domain boundary (Dirichlet)
    N = jnp.where(jnp.arange(ng)[:, None] > 0, -kN, 0.0)
    S = jnp.where(jnp.arange(ng)[:, None] < ng - 1, -kS, 0.0)
    W = jnp.where(jnp.arange(ng)[None, :] > 0, -kW, 0.0)
    E = jnp.where(jnp.arange(ng)[None, :] < ng - 1, -kE, 0.0)
    return jnp.stack([C, N, S, W, E]).reshape(-1)


def poisson2d_vc(kappa: jax.Array, *, use_stencil_kernel: bool = False
                 ) -> SparseTensor:
    """Assemble A(κ) as a SparseTensor (differentiable values)."""
    ng = kappa.shape[0]
    rows, cols, meta = vc_pattern(ng)
    val = vc_coefficients(kappa)
    props = {"symmetric": True, "spd_hint": True, "sorted_rows": False}
    return SparseTensor(val, rows, cols, (ng * ng, ng * ng), props=props,
                        stencil=meta if use_stencil_kernel else None,
                        validate=False)
