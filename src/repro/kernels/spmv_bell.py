"""Pallas TPU kernel: block-ELLPACK SpMV with scalar-prefetch column gather.

TPU adaptation of general (unstructured) sparse SpMV.  GPU CSR kernels key on
warp-per-row scalar gathers; the TPU equivalent is **dense value tiles +
scalar-prefetched block indices**: the (n_rb, k) column-block table is
prefetched into SMEM before the grid runs, so each x block arrives via the
BlockSpec ``index_map`` (a DMA the compiler can pipeline), and the inner
product is a dense (bm, bn)·(bn,) contraction on VMEM-resident tiles.

Grid: (n_rb, k) — the output band is revisited across the k slot dimension
and accumulated in place (out index_map constant in k, initialized at slot 0).

TPU tiling: every block's last two dims are either (8, 128)-aligned or equal
to the array's.  So x is viewed as (n_cb, 1, bn) and fetched as a (1, bn)
row, and y as (n_rb, bm, 1): each band's partial sums are a lane reduction
of the (bm, bn) tile times that row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.sparse import BellMeta
from .solve_step import I0, check_kernel_dtype, default_interpret


# int32 slots of the column-block table per launch: the prefetched table
# lives in SMEM (1 MiB on v5e), so larger tables are cut into row-band chunks,
# each one launch over the same value and x operands.
SMEM_TABLE_SLOTS = 1 << 17


def _kernel(off_ref, cols_ref, vals_ref, x_ref, y_ref):
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    blk = vals_ref[0, 0]            # (bm, bn)
    xv = x_ref[0]                   # (1, bn)
    y_ref[0] += jnp.sum(blk * xv, axis=1, keepdims=True)     # (bm, 1)


@functools.partial(jax.jit, static_argnums=(0, 4))
def bell_spmv_pallas(meta: BellMeta, block_cols: jax.Array,
                     bell_vals: jax.Array, x: jax.Array,
                     interpret: bool = None) -> jax.Array:
    """y = A @ x with A in block-ELL form.

    ``block_cols``: (n_rb, k) int32 column-block table (scalar-prefetched);
    ``bell_vals``: (n_rb, k, bm, bn); ``x``: (m,) — padded internally.
    Returns the padded y (n_pad,); ops.py truncates to n.

    ``interpret=None`` auto-detects: compile on TPU/GPU, emulate elsewhere.
    (Static argnum, so None resolves once at trace time.)
    """
    if interpret is None:
        interpret = default_interpret()
    check_kernel_dtype("bell_spmv", x.dtype, interpret)
    bm, bn, k, n_rb = meta.bm, meta.bn, meta.k, meta.n_rb
    xp = jnp.pad(x, (0, meta.m_pad - x.shape[0]))
    x3 = xp.reshape(meta.n_cb, 1, bn)
    cols = block_cols.astype(jnp.int32)
    rows_per = max(1, SMEM_TABLE_SLOTS // k)
    outs = []
    for r0 in range(0, n_rb, rows_per):
        nr = min(rows_per, n_rb - r0)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nr, k),
            in_specs=[
                pl.BlockSpec((1, 1, bm, bn),
                             lambda r, s, off, c: (off[0] + r, s, I0, I0)),
                pl.BlockSpec((1, 1, bn),
                             lambda r, s, off, c: (c[r * k + s], I0, I0)),
            ],
            out_specs=pl.BlockSpec((1, bm, 1),
                                   lambda r, s, off, c: (r, I0, I0)),
        )
        outs.append(pl.pallas_call(
            _kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((nr, bm, 1), x.dtype),
            interpret=interpret,
        )(jnp.full((1,), r0, jnp.int32), cols[r0:r0 + nr].reshape(-1),
          bell_vals, x3))
    return jnp.concatenate(outs).reshape(meta.n_pad)
