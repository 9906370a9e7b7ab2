"""Public jit'd wrappers over the Pallas kernels.

On CPU (this container) the kernels execute in interpret mode — the kernel
body runs in Python for correctness validation; on TPU they compile to
Mosaic.  Both wrappers are differentiable: value assembly (COO → kernel
layout) is a pure gather/scatter, and the kernel itself is linear in (val, x),
so JAX's builtin transpose rules suffice — the O(1)-graph adjoint in
core/adjoint.py wraps the *solver*, not the matvec.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core.sparse import BellMeta
from . import ref as _ref
from .spmv_bell import bell_spmv_pallas
from .stencil5 import Stencil5Meta, stencil5_pallas
from .stencil27 import OFFSETS, Stencil27Meta, stencil27_pallas


def _interpret() -> bool:
    """Default Pallas interpret flag: emulate only off compiled backends."""
    from .solve_step import default_interpret
    return default_interpret()


# ---------------------------------------------------------------------------
# block-ELL
# ---------------------------------------------------------------------------

def bell_assemble(meta: BellMeta, perm: jax.Array, val: jax.Array) -> jax.Array:
    """Scatter COO values into the dense (n_rb, k, bm, bn) block tensor.

    ``perm[e] == -1`` marks entries dropped by a max_k cap; they scatter a
    zero into slot 0 (harmless).  Differentiable (transpose = gather)."""
    size = meta.n_rb * meta.k * meta.bm * meta.bn
    safe = jnp.where(perm >= 0, perm, 0)
    contrib = jnp.where(perm >= 0, val, jnp.zeros_like(val))
    flat = jnp.zeros((size,), val.dtype).at[safe].add(contrib)
    return flat.reshape(meta.n_rb, meta.k, meta.bm, meta.bn)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 5, 6))
def bell_matvec(meta: BellMeta, block_cols: jax.Array, perm: jax.Array,
                val: jax.Array, x: jax.Array, n: int,
                interpret: bool = None) -> jax.Array:
    """``interpret=None`` resolves to the platform default; the plan engine
    threads its analyze-time flag through here (kernel plans)."""
    with jax.named_scope("spmv.bell"):
        bv = bell_assemble(meta, perm, val)
        y = bell_spmv_pallas(meta, block_cols, bv, x, interpret)
        return y[:n]


def _bell_mv_fwd(meta, block_cols, perm, val, x, n, interpret):
    return (bell_matvec(meta, block_cols, perm, val, x, n, interpret),
            (block_cols, perm, val, x))


def _bell_mv_bwd(meta, n, interpret, res, g):
    """The op is bilinear: ∂/∂x = Aᵀg (scatter over column blocks);
    ∂/∂val_e = g[row_e]·x[col_e], realized through the bell layout."""
    block_cols, perm, val, x = res
    bv = bell_assemble(meta, perm, val)
    gp = jnp.pad(g, (0, meta.n_pad - n)).reshape(meta.n_rb, meta.bm)
    xp = jnp.pad(x, (0, meta.m_pad - x.shape[0])).reshape(meta.n_cb, meta.bn)
    # grad wrt x: scatter-add blkᵀ·g_band into each block column
    contrib = jnp.einsum("rkab,ra->rkb", bv, gp)            # (n_rb, k, bn)
    gx = jnp.zeros((meta.n_cb, meta.bn), x.dtype).at[block_cols].add(contrib)
    gx = gx.reshape(meta.m_pad)[: x.shape[0]]
    # grad wrt val: outer(g_band, x_block) gathered back through perm
    gathered = xp[block_cols]                               # (n_rb, k, bn)
    gbell = jnp.einsum("ra,rkb->rkab", gp, gathered).reshape(-1)
    safe = jnp.where(perm >= 0, perm, 0)
    gval = jnp.where(perm >= 0, gbell[safe], jnp.zeros_like(val))
    return None, None, gval, gx


bell_matvec.defvjp(_bell_mv_fwd, _bell_mv_bwd)


def bell_matvec_ref(meta: BellMeta, block_cols: jax.Array, perm: jax.Array,
                    val: jax.Array, x: jax.Array, n: int) -> jax.Array:
    bv = bell_assemble(meta, perm, val)
    xp = jnp.pad(x, (0, meta.m_pad - x.shape[0]))
    return _ref.bell_matvec_ref(bv, block_cols, xp, n)


# ---------------------------------------------------------------------------
# 5-point stencil
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def stencil5_matvec(meta: Stencil5Meta, val: jax.Array, x: jax.Array) -> jax.Array:
    """``val``: (5·nx·ny,) flattened signed planes; ``x``: (nx·ny,)."""
    # the scope stays outside the kernel's jit: its op keeps the name
    # ``stencil5_pallas.N``
    with jax.named_scope("spmv.stencil"):
        v5 = val.reshape(5, meta.nx, meta.ny)
        x2 = x.reshape(meta.nx, meta.ny)
        y = stencil5_pallas(meta, v5, x2, _interpret())
        return y.reshape(meta.nx * meta.ny)


def _stencil_transpose_planes(v5: jax.Array) -> jax.Array:
    """Planes of Aᵀ: (Aᵀy)[c] = Σ_d val_d[c−off_d]·y[c−off_d] — each neighbour
    plane swaps with its mirror and shifts by its own offset."""
    C, N, S, W, E = v5
    Nt = jnp.pad(S, ((1, 0), (0, 0)))[:-1, :]   # S shifted down   → plays N
    St = jnp.pad(N, ((0, 1), (0, 0)))[1:, :]    # N shifted up     → plays S
    Wt = jnp.pad(E, ((0, 0), (1, 0)))[:, :-1]   # E shifted right  → plays W
    Et = jnp.pad(W, ((0, 0), (0, 1)))[:, 1:]    # W shifted left   → plays E
    return jnp.stack([C, Nt, St, Wt, Et])


def _stencil_fwd(meta, val, x):
    return stencil5_matvec(meta, val, x), (val, x)


def _stencil_bwd(meta, res, g):
    val, x = res
    v5 = val.reshape(5, meta.nx, meta.ny)
    x2 = x.reshape(meta.nx, meta.ny)
    g2 = g.reshape(meta.nx, meta.ny)
    # ∂/∂x = Aᵀ g — reuse the kernel with transposed planes
    vt = _stencil_transpose_planes(v5)
    gx = stencil5_pallas(meta, vt, g2, _interpret()).reshape(-1)
    # ∂/∂val_d[i,j] = g[i,j] · x[i+off_d, j+off_d]
    xn = jnp.pad(x2, ((1, 0), (0, 0)))[:-1, :]
    xs = jnp.pad(x2, ((0, 1), (0, 0)))[1:, :]
    xw = jnp.pad(x2, ((0, 0), (1, 0)))[:, :-1]
    xe = jnp.pad(x2, ((0, 0), (0, 1)))[:, 1:]
    gval = jnp.stack([g2 * x2, g2 * xn, g2 * xs, g2 * xw, g2 * xe]).reshape(-1)
    return gval, gx


stencil5_matvec.defvjp(_stencil_fwd, _stencil_bwd)


def stencil5_matvec_ref(meta: Stencil5Meta, val: jax.Array, x: jax.Array) -> jax.Array:
    v5 = val.reshape(5, meta.nx, meta.ny)
    x2 = x.reshape(meta.nx, meta.ny)
    return _ref.stencil5_ref(v5, x2).reshape(meta.nx * meta.ny)


# ---------------------------------------------------------------------------
# 27-point stencil (3-D)
# ---------------------------------------------------------------------------

def stencil27_planes(meta: Stencil27Meta, val: jax.Array) -> jax.Array:
    """Values as the kernel's (27, nz, ny, nx) planes: flat values are
    reshaped (a copy on TPU), planes already in that layout pass through."""
    return val.reshape((27,) + meta.shape)


def _shift3(a: jax.Array, d) -> jax.Array:
    """``a[c + d]`` at each cell ``c`` of an (nz, ny, nx) array, zero where
    ``c + d`` leaves the grid."""
    pads, idx = [], []
    for k, n in zip(d, a.shape):
        pads.append((max(-k, 0), max(k, 0)))
        idx.append(slice(max(k, 0), max(k, 0) + n))
    return jnp.pad(a, pads)[tuple(idx)]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def stencil27_matvec(meta: Stencil27Meta, val: jax.Array, x: jax.Array
                     ) -> jax.Array:
    """``val``: flat (27·n,) signed planes, or the (27, nz, ny, nx) planes a
    plan's setup holds; ``x``: (n,).  The launch is ``stencil27_l0``: the
    operator's own (finest) level."""
    with jax.named_scope("spmv.stencil"):
        y = stencil27_pallas(meta, stencil27_planes(meta, val),
                             x.reshape(meta.shape), "stencil27_l0",
                             _interpret())
        return y.reshape(meta.n)


def _stencil27_fwd(meta, val, x):
    return stencil27_matvec(meta, val, x), (val, x)


def _stencil27_bwd(meta, res, g):
    val, x = res
    planes = stencil27_planes(meta, val)
    x3 = x.reshape(meta.shape)
    g3 = g.reshape(meta.shape)
    # ∂/∂x = Aᵀ g: the coupling c → c+d of Aᵀ is A's coupling c+d → c,
    # the mirror plane read at the neighbour
    mirror = len(OFFSETS) - 1            # index of -d is 26 - index of d
    pt = jnp.stack([_shift3(planes[mirror - p], d)
                    for p, d in enumerate(OFFSETS)])
    gx = stencil27_pallas(meta, pt, g3, "stencil27_t", _interpret())
    # ∂/∂val_d[c] = g[c] · x[c + d]
    gval = jnp.stack([g3 * _shift3(x3, d) for d in OFFSETS])
    return gval.reshape(val.shape), gx.reshape(-1)


stencil27_matvec.defvjp(_stencil27_fwd, _stencil27_bwd)
