"""HPCG's problem — the operator of ``GenerateProblem`` in the HPCG 3.1
reference code (https://www.hpcg-benchmark.org/).

On a local ``nx × ny × nz`` grid (row ``iz·ny·nx + iy·nx + ix``) every row
has the 27-point stencil: 26 on the diagonal and −1 for each neighbour inside
the grid.  Neighbours outside the grid are absent, so boundary rows hold
fewer couplings and keep 26.  ``A = 27 I − K⊗K⊗K`` with ``K`` the
``tridiag(1, 1, 1)`` of each dimension: symmetric positive definite.

``hpcg_problem`` builds it as a :class:`SparseTensor` whose values are the
27 signed coefficient planes of the 3-D stencil layout
(:mod:`repro.kernels.stencil27`): the constants live in the values, and
every kernel reads them.  The COO view holds one slot per plane and cell,
its column clamped into the grid on structural zeros, as ``vc_pattern``
does in 2-D, so COO and stencil views share one ``val``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core.sparse import SparseTensor
from ..kernels.stencil27 import (CENTRE, OFFSETS, Stencil27Meta, grid_coords,
                                  inside)


@functools.partial(jax.jit, static_argnums=(0,))
def hpcg_planes(meta: Stencil27Meta) -> jax.Array:
    """HPCG's operator as (27, nz, ny, nx) signed float32 planes."""
    planes = []
    for p, d in enumerate(OFFSETS):
        if p == CENTRE:
            planes.append(jnp.full(meta.shape, 26.0, jnp.float32))
        else:
            planes.append(jnp.where(inside(meta, *d), -1.0, 0.0)
                          .astype(jnp.float32))
    return jnp.stack(planes)


@functools.partial(jax.jit, static_argnums=(0,))
def stencil27_pattern(meta: Stencil27Meta):
    """COO ``(row, col)`` of the 27 planes, plane-major: slot
    ``p · n + cell``; a neighbour outside the grid keeps its slot with the
    column clamped into the grid (its value is a structural zero)."""
    z, y, x = grid_coords(meta)
    nz, ny, nx = meta.shape
    cell = (z * ny + y) * nx + x
    rows, cols = [], []
    for dz, dy, dx in OFFSETS:
        cz = jnp.clip(z + dz, 0, nz - 1)
        cy = jnp.clip(y + dy, 0, ny - 1)
        cx = jnp.clip(x + dx, 0, nx - 1)
        rows.append(cell.reshape(-1))
        cols.append(((cz * ny + cy) * nx + cx).reshape(-1))
    return jnp.concatenate(rows), jnp.concatenate(cols)


def hpcg_problem(nx: int, ny: int, nz: int, *,
                 use_stencil_kernel: bool = True) -> SparseTensor:
    """HPCG's ``GenerateProblem`` operator on an ``nx × ny × nz`` grid, in
    float32.

    With ``use_stencil_kernel`` the tensor carries the 3-D stencil layout,
    which routes its SpMV to the 27-point kernel and ``precond="mg"`` to
    HPCG's Gauss–Seidel V-cycle (every dimension divisible by 8); without
    it, it is a plain COO tensor."""
    meta = Stencil27Meta(int(nx), int(ny), int(nz))
    val = hpcg_planes(meta).reshape(-1)
    row, col = stencil27_pattern(meta)
    props = {"symmetric": True, "spd_hint": True, "sorted_rows": False}
    return SparseTensor(val, row, col, (meta.n, meta.n), props=props,
                        stencil=meta if use_stencil_kernel else None,
                        validate=False)
