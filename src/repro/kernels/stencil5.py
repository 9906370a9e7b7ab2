"""Pallas TPU kernel: variable-coefficient 5-point stencil SpMV.

TPU adaptation of the paper's structured-grid SpMV (its entire 2D-Poisson
benchmark suite is this operator).  Instead of a GPU scatter/gather CSR
kernel we tile the grid into **row bands** resident in VMEM and realize the
stencil with VPU shifts; the row halo is obtained by *also* mapping the
neighbouring row-band blocks of the same input array (overlapping reads are
legal in Pallas) — boundary bands clamp their halo index and the clamped
values are annihilated by the zero boundary coefficients, so the kernel body
is branch-free.

Block layout (per grid step i):
    val5  (5, bm, ny_pad)  — coefficient planes for band i
    x_up  (bm, ny_pad)     — band i-1 (clamped at 0)
    x_c   (bm, ny_pad)     — band i
    x_dn  (bm, ny_pad)     — band i+1 (clamped at n_bands-1)
    y     (bm, ny_pad)

VMEM footprint: 9 · bm · ny_pad · 4 B  (bm=8, ny≤16384 → ≤4.7 MB).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .solve_step import I0, check_kernel_dtype, default_interpret

# (di, dj) of the coefficient planes C, N, S, W, E over an (nx, ny) grid,
# in value order
OFFSETS = ((0, 0), (-1, 0), (1, 0), (0, -1), (0, 1))


@dataclasses.dataclass(frozen=True)
class Stencil5Meta:
    nx: int
    ny: int
    bm: int = 8
    lane: int = 128  # column padding multiple

    @property
    def nx_pad(self) -> int:
        return -(-self.nx // self.bm) * self.bm

    @property
    def ny_pad(self) -> int:
        return -(-self.ny // self.lane) * self.lane

    @property
    def n_bands(self) -> int:
        return self.nx_pad // self.bm


def _kernel(val_ref, xu_ref, xc_ref, xd_ref, y_ref):
    xc = xc_ref[...]
    xu = xu_ref[...]
    xd = xd_ref[...]
    # row shifts across band boundaries (halo rows come from neighbour bands)
    x_north = jnp.concatenate([xu[-1:], xc[:-1]], axis=0)   # x[i-1, j]
    x_south = jnp.concatenate([xc[1:], xd[:1]], axis=0)     # x[i+1, j]
    # column shifts stay within the band (full width resident)
    zcol = jnp.zeros_like(xc[:, :1])
    x_west = jnp.concatenate([zcol, xc[:, :-1]], axis=1)    # x[i, j-1]
    x_east = jnp.concatenate([xc[:, 1:], zcol], axis=1)     # x[i, j+1]
    v = val_ref[...]
    y_ref[...] = (v[0] * xc + v[1] * x_north + v[2] * x_south
                  + v[3] * x_west + v[4] * x_east)


@functools.partial(jax.jit, static_argnums=(0, 3))
def stencil5_pallas(meta: Stencil5Meta, val5: jax.Array, x: jax.Array,
                    interpret: bool = None) -> jax.Array:
    """Apply the stencil.  ``val5``: (5, nx, ny) planes; ``x``: (nx, ny).

    ``interpret=None`` compiles on TPU/GPU and emulates elsewhere."""
    if interpret is None:
        interpret = default_interpret()
    check_kernel_dtype("stencil5", x.dtype, interpret)
    nxp, nyp, bm = meta.nx_pad, meta.ny_pad, meta.bm
    nb = meta.n_bands
    vp = jnp.pad(val5, ((0, 0), (0, nxp - meta.nx), (0, nyp - meta.ny)))
    xp = jnp.pad(x, ((0, nxp - meta.nx), (0, nyp - meta.ny)))

    grid = (nb,)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((5, bm, nyp), lambda i: (I0, i, I0)),
            pl.BlockSpec((bm, nyp), lambda i: (jnp.maximum(i - 1, 0), I0)),
            pl.BlockSpec((bm, nyp), lambda i: (i, I0)),
            pl.BlockSpec((bm, nyp), lambda i: (jnp.minimum(i + 1, nb - 1), I0)),
        ],
        out_specs=pl.BlockSpec((bm, nyp), lambda i: (i, I0)),
        out_shape=jax.ShapeDtypeStruct((nxp, nyp), x.dtype),
        interpret=interpret,
    )(vp, xp, xp, xp)
    return out[:meta.nx, :meta.ny]
