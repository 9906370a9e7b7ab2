"""Pallas TPU kernels: 27-point stencil SpMV and the 8-colour symmetric
Gauss–Seidel sweep on a 3-D grid.

Layout.  A grid of ``nz × ny × nx`` cells is held as an ``(nz, ny, nx)``
array (x fastest, the row order of HPCG's ``GenerateProblem``); the
operator is 27 signed coefficient planes ``(27, nz, ny, nx)``, plane
``p = 9 (dz+1) + 3 (dy+1) + (dx+1)`` holding each cell's coupling to its
neighbour at ``(z+dz, y+dy, x+dx)`` (``CENTRE`` = 13 is the diagonal).
Couplings that leave the grid are stored as zeros, and every kernel reads
the planes it is given: no coefficient is folded into a kernel.

Tiling.  A grid step holds one z-plane of the planes, ``(27, ny, nx)``, in
VMEM, and maps the x blocks of the planes below and above it (clamped at
the ends) as its z halo, as ``stencil5.py`` maps neighbouring row bands.
The y and x neighbours are rotations of the resident plane
(``pltpu.roll``); what wraps round lands only where the coupling is zero,
so the kernel bodies are branch-free.

Gauss–Seidel.  Colour ``c = 4 (z mod 2) + 2 (y mod 2) + (x mod 2)``.  No
two cells of one colour are 27-point neighbours, so a colour is updated at
once from the newest values of the others.  The four colours of one z
parity lie in every other z-plane, and their neighbours in the planes
between are not touched meanwhile, so one launch updates a z parity's
four colours in turn, plane by plane, with each plane's coefficients read
once: a forward sweep (colours 0→7) is two launches, a backward sweep
(7→0) the same two reversed.  The other parity's steps of a launch copy
x through and map the planes to the block already resident, so they move
no coefficients.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .solve_step import I0, check_kernel_dtype, default_interpret

OFFSETS = tuple((dz, dy, dx) for dz in (-1, 0, 1) for dy in (-1, 0, 1)
                for dx in (-1, 0, 1))
CENTRE = OFFSETS.index((0, 0, 0))
# in-plane colour order of one z parity's launch, forward sweep
COLOURS = ((0, 0), (0, 1), (1, 0), (1, 1))


@dataclasses.dataclass(frozen=True)
class Stencil27Meta:
    """Dimensions of a 3-D grid held as an ``(nz, ny, nx)`` array."""
    nx: int
    ny: int
    nz: int

    @property
    def shape(self) -> tuple:
        return (self.nz, self.ny, self.nx)

    @property
    def n(self) -> int:
        return self.nx * self.ny * self.nz

    def coarse(self) -> "Stencil27Meta":
        return Stencil27Meta(self.nx // 2, self.ny // 2, self.nz // 2)


def grid_coords(meta: Stencil27Meta):
    """(z, y, x) index arrays of the grid, each (nz, ny, nx)."""
    shape = meta.shape
    return tuple(jax.lax.broadcasted_iota(jnp.int32, shape, a)
                 for a in range(3))


def inside(meta: Stencil27Meta, dz: int, dy: int, dx: int) -> jax.Array:
    """(nz, ny, nx) mask of the cells whose neighbour at (dz, dy, dx) lies
    in the grid."""
    ok = None
    for c, d, n in zip(grid_coords(meta), (dz, dy, dx), meta.shape):
        if d:
            t = (c + d >= 0) & (c + d < n)
            ok = t if ok is None else ok & t
    return jnp.ones(meta.shape, bool) if ok is None else ok


def _vmem_limit(ny: int, nx: int) -> int:
    """Scoped VMEM for one step: the double-buffered planes and x/b/out
    blocks plus room for the kernel body's plane-sized temporaries."""
    plane = ny * nx * 4
    need = plane * (2 * (27 + 5) + 24)
    return int(min(max(need, 16 << 20), 100 << 20))


def _nbr(a, dy: int, dx: int):
    """``a[y + dy, x + dx]`` at ``(y, x)``, wrapping round at the edges."""
    ny, nx = a.shape
    # shifts as int32: Mosaic's rotate refuses the i64 a Python int becomes
    # under x64
    if dy:
        a = pltpu.roll(a, np.int32((-dy) % ny), 0)
    if dx:
        a = pltpu.roll(a, np.int32((-dx) % nx), 1)
    return a


def _apply(v_ref, xs, dzs):
    """Σ over the offsets with ``dz`` in ``dzs`` of plane · neighbour;
    ``xs[dz + 1]`` is the (ny, nx) x plane at ``z + dz``."""
    acc = None
    for p, (dz, dy, dx) in enumerate(OFFSETS):
        if dz not in dzs:
            continue
        t = v_ref[p] * _nbr(xs[dz + 1], dy, dx)
        acc = t if acc is None else acc + t
    return acc


def _spmv_kernel(v_ref, xl_ref, xc_ref, xh_ref, y_ref):
    y_ref[...] = _apply(v_ref, (xl_ref[...], xc_ref[...], xh_ref[...]),
                        (-1, 0, 1))


def _z_specs(meta: Stencil27Meta, zmap):
    """BlockSpecs of (planes, x below, x, x above) for the step mapped to
    z-plane ``zmap(i)``."""
    nz, ny, nx = meta.shape
    sq = pl.Squeezed()

    def at(d):
        return lambda i: (jnp.minimum(jnp.maximum(zmap(i) + d, 0), nz - 1),
                          I0, I0)

    return [pl.BlockSpec((27, sq, ny, nx), lambda i: (I0, zmap(i), I0, I0)),
            pl.BlockSpec((sq, ny, nx), at(-1)),
            pl.BlockSpec((sq, ny, nx), at(0)),
            pl.BlockSpec((sq, ny, nx), at(1))]


def _check(meta: Stencil27Meta, planes, x, kernel: str, interpret):
    if interpret is None:
        interpret = default_interpret()
    check_kernel_dtype(kernel, x.dtype, interpret)
    if planes.shape != (27,) + meta.shape or x.shape != meta.shape:
        raise ValueError(f"{kernel}: planes {planes.shape} and x {x.shape} "
                         f"do not match the grid {meta.shape}")
    return interpret


@functools.partial(jax.jit, static_argnames=("meta", "name", "interpret"))
def stencil27_pallas(meta: Stencil27Meta, planes: jax.Array, x: jax.Array,
                     name: str = "stencil27", interpret: bool = None
                     ) -> jax.Array:
    """``y = A x`` for ``planes`` (27, nz, ny, nx) and ``x`` (nz, ny, nx).

    ``name`` names the launch (its custom call is ``name.N`` in a profile);
    ``interpret=None`` compiles on TPU/GPU and emulates elsewhere."""
    interpret = _check(meta, planes, x, "stencil27", interpret)
    nz, ny, nx = meta.shape
    with jax.named_scope(name):
        return pl.pallas_call(
            _spmv_kernel,
            grid=(nz,),
            in_specs=_z_specs(meta, lambda i: i),
            out_specs=pl.BlockSpec((pl.Squeezed(), ny, nx),
                                   lambda i: (i, I0, I0)),
            out_shape=jax.ShapeDtypeStruct(meta.shape, x.dtype),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_vmem_limit(ny, nx)),
            interpret=interpret,
            name=name,
        )(planes, x, x, x)


def _gs_kernel(pz: int, colours, v_ref, xl_ref, xc_ref, xh_ref, b_ref,
               o_ref):
    active = jax.lax.rem(pl.program_id(0), np.int32(2)) == np.int32(pz)

    @pl.when(active)
    def _update():
        xl, xc, xh = xl_ref[...], xc_ref[...], xh_ref[...]
        # the neighbouring z-planes hold the other parity: fixed meanwhile
        r0 = b_ref[...] - _apply(v_ref, (xl, xc, xh), (-1, 1))
        inv = 1.0 / v_ref[CENTRE]
        yy = jax.lax.broadcasted_iota(jnp.int32, xc.shape, 0) & 1
        xx = jax.lax.broadcasted_iota(jnp.int32, xc.shape, 1) & 1
        for py, px in colours:
            r = r0 - _apply(v_ref, (None, xc, None), (0,))
            xc = xc + jnp.where((yy == py) & (xx == px), r * inv, 0.0)
        o_ref[...] = xc

    @pl.when(jnp.logical_not(active))
    def _copy():
        o_ref[...] = xc_ref[...]


def _gs_launch(meta: Stencil27Meta, planes, x, b, pz: int, colours,
               name: str, interpret: bool):
    """One z parity's colours, in ``colours`` order, over the whole grid."""
    nz, ny, nx = meta.shape
    sq = pl.Squeezed()

    def own(i):          # the parity's plane at or next to step i
        return jnp.minimum(jax.lax.div(i, np.int32(2)) * 2 + pz, nz - 1)

    return pl.pallas_call(
        functools.partial(_gs_kernel, pz, colours),
        grid=(nz,),
        in_specs=(_z_specs(meta, own)[:1] + _z_specs(meta, lambda i: i)[1:]
                  + [pl.BlockSpec((sq, ny, nx), lambda i: (own(i), I0, I0))]),
        out_specs=pl.BlockSpec((sq, ny, nx), lambda i: (i, I0, I0)),
        out_shape=jax.ShapeDtypeStruct(meta.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(ny, nx)),
        interpret=interpret,
        name=name,
    )(planes, x, x, x, b)


@functools.partial(jax.jit, static_argnames=("meta", "name", "interpret"))
def symgs27_pallas(meta: Stencil27Meta, planes: jax.Array, x: jax.Array,
                   b: jax.Array, name: str = "symgs27",
                   interpret: bool = None) -> jax.Array:
    """One symmetric Gauss–Seidel step for ``A x = b`` in the 8-colour
    order: a forward sweep over colours 0→7, then a backward sweep over
    7→0, each colour updated from the newest values.  Four launches, each
    named ``name`` (one z parity's four colours apiece)."""
    interpret = _check(meta, planes, x, "symgs27", interpret)
    back = COLOURS[::-1]
    with jax.named_scope(name):
        for pz, colours in ((0, COLOURS), (1, COLOURS), (1, back), (0, back)):
            x = _gs_launch(meta, planes, x, b, pz, colours, name, interpret)
    return x

