"""Pallas TPU kernels: supernodal panel factorize / triangular solve.

The supernodal direct path (``core/direct.py``) groups columns into
fundamental supernodes and buckets them by padded panel shape; each bucket is
a batch of identically-shaped dense panels gathered from the packed factor
vector.  The kernels here run one supernode per grid lane:

- :func:`panel_factor` — right-looking dense factorization of the
  (wb+rb, wb) panel (diagonal-block elimination + L-panel divide + trailing
  update), including the static Bunch–Kaufman 2x2 pivot pairs;
- :func:`schur_update` — the extend-add GEMM ``S = Lpanel @ Upanel`` whose
  result is scatter-subtracted into ancestor slots (the MXU-bound step that
  replaces O(w·r²) scalar packed-scan multiply-adds);
- :func:`block_trsv` — dense triangular solves on the diagonal block for the
  four sweep modes (L, Lᵀ, U, Uᵀ).

Each kernel's math lives in a single-lane ``sn_*_body`` function in
``kernels/ref.py`` — the pure-jnp oracles vmap those bodies, and the Pallas
kernels call the very same bodies on their per-lane VMEM blocks, so
kernel-vs-ref parity is structural.  Per-lane true sizes (w, r) are
scalar-prefetched into SMEM;
pad rows/columns are masked inside the body (gathered pads hold scratch
garbage).  Every kernel declares its traffic model via a
``passes = (reads, writes)`` attribute in units of full operand arrays.

On CPU the direct driver calls the jnp oracles directly (interpret-mode
Pallas emulation would serialize the python loop); the kernels are still
exercised under ``interpret=True`` by the parity tests.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref as _ref
from .solve_step import I0, check_kernel_dtype, default_interpret

__all__ = ["panel_factor", "schur_update", "block_trsv", "default_interpret"]


def _lane_spec(*block):
    """Block ``(1,) + block`` of lane i; index maps return int32.  Each
    block has the full array's last two dims, so the (8, 128) tiling rule
    holds at any bucket shape."""
    zeros = (I0,) * len(block)
    return pl.BlockSpec((1,) + block, lambda i, *_: (i,) + zeros)


def panel_factor(P, Q, wvec, rvec, tau, bkm, *, pairs=False, guard=True,
                 interpret=None):
    """Factorize a bucket of supernode panels in place.

    ``P`` (k, wb+rb, wb) gathered [D-block; L-panel] columns, ``Q``
    (k, wb, rb) gathered U-panel rows, ``wvec``/``rvec`` (k,) true
    width/sub-row counts, ``tau`` the 1x1 pivot clamp, ``bkm`` (k, wb) bool
    pair-start flags.  Returns (P, Q, nbad) with L divided, U raw, clamped
    pivots persisted — bit-identical storage semantics to the scalar path.
    """
    if interpret is None:
        interpret = default_interpret()
    k, m, wb = P.shape
    rb = Q.shape[2]
    dtype = P.dtype
    check_kernel_dtype("panel_factor", dtype, interpret)

    def kern(wv, rv, tv, bk, p, q, po, qo, nb):
        i = pl.program_id(0)
        Pn, Qn, bad = _ref.sn_panel_factor_body(
            p[0], q[0], wv[i], rv[i], tv[...], bk[0], pairs=pairs,
            guard=guard)
        po[0] = Pn
        qo[0] = Qn
        nb[0] = bad

    Po, Qo, nbad = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(k,),          # wvec, rvec → SMEM
            in_specs=[pl.BlockSpec((1, 1), lambda i, *_: (I0, I0)),
                      _lane_spec(1, wb), _lane_spec(m, wb),
                      _lane_spec(wb, rb)],
            out_specs=[_lane_spec(m, wb), _lane_spec(wb, rb),
                       _lane_spec(1, 1)]),
        out_shape=[
            jax.ShapeDtypeStruct((k, m, wb), dtype),
            jax.ShapeDtypeStruct((k, wb, rb), dtype),
            jax.ShapeDtypeStruct((k, 1, 1), dtype),
        ],
        interpret=interpret,
    )(wvec.astype(jnp.int32), rvec.astype(jnp.int32),
      jnp.asarray(tau, dtype).reshape(1, 1),
      bkm.astype(dtype).reshape(k, 1, wb), P, Q)
    return Po, Qo, jnp.sum(nbad)


panel_factor.passes = (2, 2)


def schur_update(P, Q, *, interpret=None):
    """Extend-add GEMM: S[l] = Lpanel[l] @ Upanel[l] per lane on the MXU.

    ``P`` (k, wb+rb, wb) factored panels (rows wb.. hold divided L),
    ``Q`` (k, wb, rb) raw U rows.  Returns S (k, rb, rb); the driver
    scatter-subtracts it into the ancestors' packed slots (extend-add).
    """
    if interpret is None:
        interpret = default_interpret()
    k, m, wb = P.shape
    rb = Q.shape[2]
    dtype = P.dtype
    check_kernel_dtype("schur_update", dtype, interpret)

    def kern(p, q, s):
        s[0] = jax.lax.dot_general(
            p[0][wb:, :], q[0], (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=dtype)

    return pl.pallas_call(
        kern,
        grid=(k,),
        in_specs=[_lane_spec(m, wb), _lane_spec(wb, rb)],
        out_specs=_lane_spec(rb, rb),
        out_shape=jax.ShapeDtypeStruct((k, rb, rb), dtype),
        interpret=interpret,
    )(P, Q)


schur_update.passes = (2, 1)


def block_trsv(D, y, wvec, bkm, *, mode, pairs=False, interpret=None):
    """Dense triangular solve on a bucket of diagonal blocks.

    ``D`` (k, wb, wb) packed blocks (strict lower = unit-L, diagonal =
    pivots, strict upper = U), ``y`` (k, wb) right-hand sides, ``mode`` one
    of ``"l"``/``"lt"``/``"u"``/``"ut"`` (see ``ref.sn_trsv_body``).
    Returns x (k, wb).
    """
    if interpret is None:
        interpret = default_interpret()
    k, wb = y.shape
    dtype = D.dtype
    check_kernel_dtype("block_trsv", dtype, interpret)
    # the body's orientation: a column where D's columns are subtracted
    vec = (wb, 1) if mode in ("l", "u") else (1, wb)

    def kern(wv, bk, d, yy, xo):
        xo[0] = _ref.sn_trsv_body(d[0], yy[0], wv[pl.program_id(0)], bk[0],
                                  mode=mode, pairs=pairs)

    x = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(k,),          # wvec → SMEM
            in_specs=[_lane_spec(1, wb), _lane_spec(wb, wb),
                      _lane_spec(*vec)],
            out_specs=_lane_spec(*vec)),
        out_shape=jax.ShapeDtypeStruct((k,) + vec, dtype),
        interpret=interpret,
    )(wvec.astype(jnp.int32), bkm.astype(dtype).reshape(k, 1, wb), D,
      y.reshape((k,) + vec))
    return x.reshape(k, wb)


block_trsv.passes = (2, 1)
