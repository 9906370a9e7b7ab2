"""Pallas TPU kernels: fused iterative-solver step passes.

One CG/BiCGStab iteration in the seed is ~five separate memory-bound passes
over n-length vectors (axpy updates, preconditioner apply, reduction dots,
plus the convergence-check dot re-read in ``cond``).  Each kernel here fuses
one group of those passes into a single sweep: vectors stream through VMEM in
(8, 128) tiles over a 1-D grid, scalar coefficients ride in SMEM, and the
reduction dots accumulate into an SMEM output across the sequential grid
(initialized at step 0 — TPU grids execute in order, so in-place accumulation
into a revisited output block is well defined).

Every kernel declares its traffic model via a ``passes = (reads, writes)``
attribute (units of n-length vectors); ``launch/roofline.py`` consumes these
for the fused-step byte assertion in the bench suite.

The fused CG path uses the merged (Chronopoulos/Gear) recurrence: with
M-orthogonal residuals, <p', A p'> = <w, z> - (beta/alpha)·<r', z'>, so the
standalone p·Ap reduction pass disappears — both dots fall out of passes that
stream the vectors anyway (see ``core/solvers.py::cg_fused``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BM, BN = 8, 128          # f32/f64 min tile; vectors are viewed as (nb, 8, 128)
BLK = BM * BN
# Block indices must lower to i32: a bare Python ``0`` in an index map becomes
# i64 under ``jax_enable_x64``, and Mosaic refuses the kernel.
I0 = np.int32(0)


def default_interpret() -> bool:
    """Interpret (emulate) only off compiled backends — the satellite fix for
    the old ``interpret=True`` default that silently emulated on TPU."""
    return jax.default_backend() not in ("tpu", "gpu")


def check_kernel_dtype(kernel: str, dtype, interpret: bool) -> None:
    """A compiled Pallas TPU kernel takes float32 operands only (Mosaic has
    no f64 vector or SMEM scalar type); emulation accepts any dtype.  Raises
    rather than emulate or reroute, so a wrong dtype never hides the chip."""
    if not interpret and jnp.dtype(dtype) != jnp.float32:
        raise ValueError(
            f"Pallas kernel(s) {kernel} cannot take {jnp.dtype(dtype).name} "
            f"operands when compiled for {jax.default_backend()}; "
            f"pass float32 values")


def _make_kernel(body, n_in: int, n_sc: int, n_out: int, n_dots: int):
    def kernel(*refs):
        vin = refs[:n_in]
        pos = n_in
        sc = ()
        if n_sc:
            sref = refs[pos]
            pos += 1
            sc = tuple(sref[0, j] for j in range(n_sc))
        vout = refs[pos:pos + n_out]
        dref = refs[pos + n_out] if n_dots else None
        if n_dots:
            @pl.when(pl.program_id(0) == 0)
            def _init():
                for j in range(n_dots):
                    dref[0, j] = jnp.zeros((), dref.dtype)
        outs, dots = body(tuple(r[...] for r in vin), sc)
        for r, v in zip(vout, outs):
            r[...] = v
        for j in range(n_dots):
            dref[0, j] += dots[j]
    return kernel


def _run(body, vecs, scalars, n_out: int, n_dots: int, interpret):
    """Launch one fused vector pass.

    ``vecs``: n-length arrays, tiled to (nb, 8, 128) blocks (zero-padded —
    every body below maps pad zeros to zeros, so dots are exact); ``scalars``:
    loop coefficients, stacked into one SMEM row.  Returns the n_out output
    vectors (truncated to n) followed by the n_dots reduction scalars.
    """
    if interpret is None:
        interpret = default_interpret()
    n = vecs[0].shape[0]
    dtype = vecs[0].dtype
    check_kernel_dtype("fused step", dtype, interpret)
    nb = max(1, -(-n // BLK))
    pad = nb * BLK - n
    vb = [jnp.pad(v, (0, pad)).reshape(nb, BM, BN) for v in vecs]
    n_in, n_sc = len(vecs), len(scalars)
    vspec = pl.BlockSpec((1, BM, BN), lambda i: (i, I0, I0))
    in_specs = [vspec] * n_in
    args = list(vb)
    if n_sc:
        in_specs.append(pl.BlockSpec((1, n_sc), lambda i: (I0, I0),
                                     memory_space=pltpu.SMEM))
        args.append(jnp.stack([jnp.asarray(s, dtype) for s in scalars])
                    .reshape(1, n_sc))
    out_specs = [vspec] * n_out
    out_shape = [jax.ShapeDtypeStruct((nb, BM, BN), dtype)] * n_out
    if n_dots:
        out_specs.append(pl.BlockSpec((1, n_dots), lambda i: (I0, I0),
                                      memory_space=pltpu.SMEM))
        out_shape.append(jax.ShapeDtypeStruct((1, n_dots), dtype))
    res = pl.pallas_call(
        _make_kernel(body, n_in, n_sc, n_out, n_dots),
        grid=(nb,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*args)
    outs = tuple(r.reshape(nb * BLK)[:n] for r in res[:n_out])
    dots = tuple(res[n_out][0, j] for j in range(n_dots)) if n_dots else ()
    return outs + dots


# ---------------------------------------------------------------------------
# CG (merged recurrence, diagonal preconditioner)
# ---------------------------------------------------------------------------

def fused_cg_update(x, r, p, s, dinv, alpha, *, interpret=None):
    """x' = x + α·p;  r' = r − α·s;  z' = dinv·r';  ρ' = <r',z'>;  rr' = <r',r'>.

    Replaces the x-axpy, r-axpy, preconditioner apply, r·z dot, and the
    convergence-check r·r dot (s = A p)."""
    def body(v, sc):
        x_, r_, p_, s_, d_ = v
        (a,) = sc
        xn = x_ + a * p_
        rn = r_ - a * s_
        zn = d_ * rn
        return (xn, rn, zn), (jnp.sum(rn * zn), jnp.sum(rn * rn))
    return _run(body, (x, r, p, s, dinv), (alpha,), 3, 2, interpret)


fused_cg_update.passes = (5, 3)


def fused_cg_direction(z, w, p, s, beta, *, interpret=None):
    """p' = z + β·p;  s' = w + β·s;  δ = <w,z>  (w = A z).

    δ feeds the merged-CG α recurrence one iteration later, so there is no
    reduction barrier inside the pass and no standalone p·Ap dot at all."""
    def body(v, sc):
        z_, w_, p_, s_ = v
        (b,) = sc
        return (z_ + b * p_, w_ + b * s_), (jnp.sum(w_ * z_),)
    return _run(body, (z, w, p, s), (beta,), 2, 1, interpret)


fused_cg_direction.passes = (4, 2)


def fused_cg_halfstep(x, r, p, s, alpha, *, interpret=None):
    """x' = x + α·p;  r' = r − α·s;  rr' = <r',r'> — the partial fusion used
    when the preconditioner apply is not a diagonal scale (AMG, MG, ILU)."""
    def body(v, sc):
        x_, r_, p_, s_ = v
        (a,) = sc
        xn = x_ + a * p_
        rn = r_ - a * s_
        return (xn, rn), (jnp.sum(rn * rn),)
    return _run(body, (x, r, p, s), (alpha,), 2, 1, interpret)


fused_cg_halfstep.passes = (4, 2)


def fused_cheb_step(x, dk, rk, c1, c2, *, interpret=None):
    """d' = c1·d + c2·r;  x' = x + d' — one inner step of the Chebyshev
    polynomial apply (two axpy passes fused; the residual update rides the
    matvec that follows)."""
    def body(v, sc):
        x_, d_, r_ = v
        a, b = sc
        dn = a * d_ + b * r_
        return (x_ + dn, dn), ()
    return _run(body, (x, dk, rk), (c1, c2), 2, 0, interpret)


fused_cheb_step.passes = (3, 2)


def fused_dots2(u, v, *, interpret=None):
    """(Σ u·v, Σ u·u) in one read of each operand (BiCGStab ω numerator and
    denominator, computed together)."""
    def body(vv, sc):
        u_, v_ = vv
        return (), (jnp.sum(u_ * v_), jnp.sum(u_ * u_))
    return _run(body, (u, v), (), 0, 2, interpret)


fused_dots2.passes = (2, 0)


# ---------------------------------------------------------------------------
# BiCGStab
# ---------------------------------------------------------------------------

def fused_bicg_p(r, p, v, dinv, beta, omega, restart, *, interpret=None):
    """p' = r + β·(p − ω·v)  (p' = r when the restart flag is set);
    p̂ = dinv·p'."""
    def body(vv, sc):
        r_, p_, v_, d_ = vv
        b, w, rs = sc
        pn = jnp.where(rs != 0, r_, r_ + b * (p_ - w * v_))
        return (pn, d_ * pn), ()
    return _run(body, (r, p, v, dinv), (beta, omega, restart), 2, 0, interpret)


fused_bicg_p.passes = (4, 2)


def fused_bicg_s(r, v, dinv, alpha, *, interpret=None):
    """s = r − α·v;  ŝ = dinv·s."""
    def body(vv, sc):
        r_, v_, d_ = vv
        (a,) = sc
        sn = r_ - a * v_
        return (sn, d_ * sn), ()
    return _run(body, (r, v, dinv), (alpha,), 2, 0, interpret)


fused_bicg_s.passes = (3, 2)


def fused_bicg_tail(x, s, t, phat, shat, rhat, alpha, omega, *, interpret=None):
    """x' = x + α·p̂ + ω·ŝ;  r' = s − ω·t;  ρ' = <r̂,r'>;  rr' = <r',r'>.

    ρ' is next iteration's head dot computed for free while r' is resident;
    rr' makes the convergence check read-free."""
    def body(vv, sc):
        x_, s_, t_, ph_, sh_, rh_ = vv
        a, w = sc
        xn = x_ + a * ph_ + w * sh_
        rn = s_ - w * t_
        return (xn, rn), (jnp.sum(rh_ * rn), jnp.sum(rn * rn))
    return _run(body, (x, s, t, phat, shat, rhat), (alpha, omega), 2, 2,
                interpret)


fused_bicg_tail.passes = (6, 2)


def traffic_bytes(kernel, n: int, itemsize: int = 8) -> int:
    """Modeled HBM traffic of one fused pass: (reads + writes) · n · itemsize,
    from the kernel's declared ``passes`` attribute (dots are O(1))."""
    reads, writes = kernel.passes
    return (reads + writes) * n * itemsize
