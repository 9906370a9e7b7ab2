"""Solver kernels (paper §3.1, Appendix A).

Every iterative solver is *matvec-parametric*: it takes a closure
``matvec(x) -> Ax`` so the same loop serves the ``jnp`` (COO segment-sum),
``pallas`` (block-ELL kernel), ``stencil`` (matrix-free) and ``dist``
(halo-exchange) backends.  All loops are ``lax.while_loop`` — they are *not*
reverse-differentiable, which is exactly the point: gradients always come from
the O(1)-graph adjoint in :mod:`repro.core.adjoint`.

``cg_scan`` is the deliberately-naive fixed-k differentiable CG used as the
O(k)-graph baseline of paper Fig. 2 / Table 7.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from .spans import scoped, span

__all__ = [
    "SolveInfo", "SolveResult", "cg", "cg_fused", "bicgstab",
    "bicgstab_fused", "block_cg", "gmres", "cg_scan", "eigh_pinv_solve",
    "dense_solve", "newton_solve", "picard_solve", "anderson_solve",
    "lobpcg", "lanczos",
]


class SolveInfo(NamedTuple):
    iters: jax.Array       # iterations executed
    resnorm: jax.Array     # final ‖r‖₂
    converged: jax.Array   # bool


class SolveResult(NamedTuple):
    """Typed solve payload, uniform across iterative/direct/distributed
    backends — what :func:`repro.sla.solve_with_info` returns, and what the
    serving driver reports per request.

    ``iterations``/``residual``/``converged`` mirror :class:`SolveInfo`
    (per-rhs vectors for multi-rhs/batched solves, scalars otherwise);
    ``reason`` is a static string: ``"converged"``, ``"maxiter"``, or
    ``"unknown"`` when the result is still a tracer (inside jit) and the
    outcome is not concretely decidable.

    ``converged`` means ``‖r‖₂ ≤ max(tol·‖b‖₂, atol)`` for the iterative
    backends.  A direct solve is judged by its normwise backward error
    instead: ``‖r‖∞ / (‖A‖∞‖x‖∞ + ‖b‖∞) ≤ tol`` or ``‖r‖∞ ≤ atol``
    (:func:`repro.core.sparse.backward_error`), which a backward-stable
    factorization meets in float32 at any size.  ``residual`` is ``‖r‖₂``
    for every backend.
    """
    x: jax.Array
    iterations: jax.Array
    residual: jax.Array
    converged: jax.Array
    reason: str


def as_solve_result(x, info: SolveInfo,
                    reason: Optional[str] = None) -> SolveResult:
    """Wrap a backend's ``(x, SolveInfo)`` pair into a :class:`SolveResult`."""
    if reason is None:
        try:
            with span("solve.wait"):   # the host waits for the device here
                done = bool(jnp.all(info.converged))
            reason = "converged" if done else "maxiter"
        except Exception:      # traced under jit/vmap: not concretely known
            reason = "unknown"
    return SolveResult(x=x, iterations=info.iters, residual=info.resnorm,
                       converged=info.converged, reason=reason)


def _identity(x):
    return x


def eigh_pinv_solve(G, rhs, *, ridge: float = 1e-12):
    """Solve the (near-)singular symmetric system ``G x = rhs`` by a
    symmetric-eigendecomposition pseudo-inverse with a RELATIVE cutoff.

    ``G`` is symmetrized, eigenvalues below ``max(ridge, m·10·eps) ·
    max|w|`` are zeroed instead of inverted, so rank-deficient directions
    (converged/duplicate columns in :func:`block_cg`'s Gram systems, stale
    difference columns in :func:`anderson_solve`'s window) become inert
    no-ops rather than amplified roundoff.  Unlike a FIXED ridge, the cutoff
    scales with the dtype: in f32 roundoff noise sits at ~1e-7·‖G‖, far
    above a 1e-12 ridge — the fixed-ridge normal-equations solve there
    returns garbage coefficients and stagnates (the PR-7 f32 Anderson bug).
    ``rhs`` may be a vector ``(m,)`` or a matrix ``(m, k)``.
    """
    m = G.shape[0]
    cutoff = jnp.maximum(jnp.asarray(ridge, G.dtype),
                         m * 10 * jnp.finfo(G.dtype).eps)
    w, V = jnp.linalg.eigh(0.5 * (G + G.T))
    cut = jnp.max(jnp.abs(w)) * cutoff
    winv = jnp.where(jnp.abs(w) > cut, 1.0 / w, 0.0)
    if rhs.ndim == 1:
        return V @ (winv * (V.T @ rhs))
    return V @ (winv[:, None] * (V.T @ rhs))


# ---------------------------------------------------------------------------
# Krylov solvers
# ---------------------------------------------------------------------------

def cg(matvec: Callable, b: jax.Array, x0: Optional[jax.Array] = None, *,
       M: Callable = _identity, tol: float = 1e-6, atol: float = 0.0,
       maxiter: int = 1000, min_iter: int = 0,
       dot: Optional[Callable] = None):
    """Preconditioned conjugate gradient (Hestenes–Stiefel).

    Two inner products per iteration — the textbook form used by the paper
    (Alg. 1).  See ``pipelined_cg`` in core/distributed.py for the
    reduced-latency variant (beyond-paper).  ``dot`` is injectable so the
    distributed backend can psum across the mesh (paper Alg. 1 all_reduce).
    """
    x0 = jnp.zeros_like(b) if x0 is None else x0
    dot = dot or (lambda u, v: jnp.sum(u * v))
    bnorm = jnp.sqrt(dot(b, b))
    target = jnp.maximum(tol * bnorm, atol)

    r0 = b - matvec(x0)
    z0 = M(r0)
    p0 = z0
    rz0 = dot(r0, z0)

    def cond(state):
        x, r, p, rz, k = state
        return (k < maxiter) & ((jnp.sqrt(dot(r, r)) > target) | (k < min_iter))

    def body(state):
        x, r, p, rz, k = state
        Ap = matvec(p)
        alpha = rz / dot(p, Ap)
        x = x + alpha * p
        r = r - alpha * Ap
        z = M(r)
        rz_new = dot(r, z)
        p = z + (rz_new / rz) * p
        return (x, r, p, rz_new, k + 1)

    x, r, p, rz, k = lax.while_loop(cond, scoped("krylov.cg", body),
                                    (x0, r0, p0, rz0, jnp.array(0)))
    rn = jnp.sqrt(dot(r, r))
    return x, SolveInfo(k, rn, rn <= target)


def bicgstab(matvec: Callable, b: jax.Array, x0: Optional[jax.Array] = None, *,
             M: Callable = _identity, tol: float = 1e-6, atol: float = 0.0,
             maxiter: int = 1000, dot: Optional[Callable] = None):
    """BiCGStab (van der Vorst 1992) for general (non-symmetric) systems."""
    x0 = jnp.zeros_like(b) if x0 is None else x0
    dot = dot or (lambda u, v: jnp.sum(u * v))
    bnorm = jnp.sqrt(dot(b, b))
    target = jnp.maximum(tol * bnorm, atol)
    eps = jnp.asarray(1e-30, b.dtype)

    r0 = b - matvec(x0)

    def cond(st):
        x, r, rhat, p, v, rho, alpha, omega, k, fresh = st
        return (k < maxiter) & (jnp.sqrt(dot(r, r)) > target)

    def body(st):
        x, r, rhat, p, v, rho_prev, alpha, omega, k, fresh = st
        rho = dot(rhat, r)
        rr = dot(r, r)
        # ρ-breakdown (r ⟂ r̂): restart with r̂ ← r (PETSc-style) instead of
        # stagnating — BiCGStab otherwise stalls once <r̂,r> underflows.
        restart = (jnp.abs(rho) < 1e-12 * rr) | fresh
        rhat = jnp.where(restart, r, rhat)
        rho = jnp.where(restart, rr, rho)
        beta = (rho / (rho_prev + eps)) * (alpha / (omega + eps))
        beta = jnp.where(restart, 0.0, beta)
        p = jnp.where(restart, r, r + beta * (p - omega * v))
        phat = M(p)
        v = matvec(phat)
        alpha = rho / (dot(rhat, v) + eps)
        s = r - alpha * v
        shat = M(s)
        t = matvec(shat)
        omega_new = dot(t, s) / (dot(t, t) + eps)
        x = x + alpha * phat + omega_new * shat
        r = s - omega_new * t
        return (x, r, rhat, p, v, rho, alpha, omega_new, k + 1,
                jnp.array(False))

    z = jnp.zeros_like(b)
    one = jnp.asarray(1.0, b.dtype)
    st0 = (x0, r0, r0, z, z, one, one, one, jnp.array(0), jnp.array(True))
    x, r, *_, k, _ = lax.while_loop(cond, scoped("krylov.bicgstab", body),
                                    st0)
    rn = jnp.sqrt(dot(r, r))
    return x, SolveInfo(k, rn, rn <= target)


def cg_fused(matvec: Callable, b: jax.Array, x0: Optional[jax.Array] = None, *,
             dinv: Optional[jax.Array] = None, M: Callable = _identity,
             tol: float = 1e-6, atol: float = 0.0, maxiter: int = 1000,
             min_iter: int = 0, interpret: Optional[bool] = None):
    """CG with the iteration fused into Pallas step kernels (single device).

    With a diagonal preconditioner (``dinv`` given) this is the merged
    Chronopoulos–Gear recurrence: α comes from α' = ρ'/(δ − βρ'/α) with
    δ = <Az, z>, so the standalone p·Ap reduction pass vanishes and each
    iteration is one matvec plus exactly two fused vector sweeps
    (``fused_cg_update`` and ``fused_cg_direction``).  The recurrence is
    algebraically identical to Hestenes–Stiefel (same iterates in exact
    arithmetic); the residual-based stopping rule absorbs the small
    floating-point divergence.

    Without ``dinv`` (external preconditioner closure ``M``) the textbook
    recurrence is kept and only the axpy/convergence-dot passes fuse
    (``fused_cg_halfstep``).
    """
    from ..kernels import solve_step as _fk

    x0 = jnp.zeros_like(b) if x0 is None else x0
    dot = lambda u, v: jnp.sum(u * v)
    bnorm = jnp.sqrt(dot(b, b))
    target = jnp.maximum(tol * bnorm, atol)
    eps = jnp.asarray(1e-30, b.dtype)

    r0 = b - matvec(x0)
    rr0 = dot(r0, r0)

    if dinv is not None:
        z0 = dinv * r0
        p0 = z0
        s0 = matvec(p0)
        rho0 = dot(r0, z0)
        alpha0 = rho0 / (dot(p0, s0) + eps)

        def cond(st):
            x, r, p, s, rho, rr, alpha, k = st
            return (k < maxiter) & ((jnp.sqrt(rr) > target) | (k < min_iter))

        def body(st):
            x, r, p, s, rho, rr, alpha, k = st
            x, r, z, rho_new, rr_new = _fk.fused_cg_update(
                x, r, p, s, dinv, alpha, interpret=interpret)
            w = matvec(z)
            beta = rho_new / (rho + eps)
            p, s, delta = _fk.fused_cg_direction(
                z, w, p, s, beta, interpret=interpret)
            alpha_new = rho_new / (delta - beta * rho_new / (alpha + eps) + eps)
            return (x, r, p, s, rho_new, rr_new, alpha_new, k + 1)

        st0 = (x0, r0, p0, s0, rho0, rr0, alpha0, jnp.array(0))
        x, r, p, s, rho, rr, alpha, k = lax.while_loop(
            cond, scoped("krylov.cg_fused", body), st0)
    else:
        z0 = M(r0)
        p0 = z0
        rz0 = dot(r0, z0)

        def cond(st):
            x, r, p, rz, rr, k = st
            return (k < maxiter) & ((jnp.sqrt(rr) > target) | (k < min_iter))

        def body(st):
            x, r, p, rz, rr, k = st
            Ap = matvec(p)
            alpha = rz / (dot(p, Ap) + eps)
            x, r, rr_new = _fk.fused_cg_halfstep(
                x, r, p, Ap, alpha, interpret=interpret)
            z = M(r)
            rz_new = dot(r, z)
            p = z + (rz_new / (rz + eps)) * p
            return (x, r, p, rz_new, rr_new, k + 1)

        st0 = (x0, r0, p0, rz0, rr0, jnp.array(0))
        x, r, p, rz, rr, k = lax.while_loop(
            cond, scoped("krylov.cg_fused", body), st0)

    rn = jnp.sqrt(rr)
    return x, SolveInfo(k, rn, rn <= target)


def bicgstab_fused(matvec: Callable, b: jax.Array,
                   x0: Optional[jax.Array] = None, *,
                   dinv: Optional[jax.Array] = None, M: Callable = _identity,
                   tol: float = 1e-6, atol: float = 0.0, maxiter: int = 1000,
                   interpret: Optional[bool] = None):
    """BiCGStab with fused Pallas step kernels (single device).

    Same recurrence as :func:`bicgstab`; the vector passes fuse into
    ``fused_bicg_p`` / ``fused_bicg_s`` (diagonal preconditioner folded in),
    ``fused_dots2`` (ω numerator+denominator in one read), and
    ``fused_bicg_tail`` (x/r updates plus next iteration's head dot <r̂,r'>
    and the convergence dot <r',r'>, carried through the loop state).
    """
    from ..kernels import solve_step as _fk

    x0 = jnp.zeros_like(b) if x0 is None else x0
    dot = lambda u, v: jnp.sum(u * v)
    bnorm = jnp.sqrt(dot(b, b))
    target = jnp.maximum(tol * bnorm, atol)
    eps = jnp.asarray(1e-30, b.dtype)

    r0 = b - matvec(x0)
    rr0 = dot(r0, r0)

    def cond(st):
        x, r, rhat, p, v, rho_prev, rho_c, alpha, omega, rr, k, fresh = st
        return (k < maxiter) & (jnp.sqrt(rr) > target)

    def body(st):
        x, r, rhat, p, v, rho_prev, rho_c, alpha, omega, rr, k, fresh = st
        # ρ = <r̂, r> was computed by last iteration's tail pass (rho_c).
        restart = (jnp.abs(rho_c) < 1e-12 * rr) | fresh
        rhat = jnp.where(restart, r, rhat)
        rho = jnp.where(restart, rr, rho_c)
        beta = (rho / (rho_prev + eps)) * (alpha / (omega + eps))
        beta = jnp.where(restart, 0.0, beta)
        if dinv is not None:
            p, phat = _fk.fused_bicg_p(r, p, v, dinv, beta, omega,
                                       restart.astype(b.dtype),
                                       interpret=interpret)
        else:
            p = jnp.where(restart, r, r + beta * (p - omega * v))
            phat = M(p)
        v = matvec(phat)
        alpha = rho / (dot(rhat, v) + eps)
        if dinv is not None:
            s, shat = _fk.fused_bicg_s(r, v, dinv, alpha, interpret=interpret)
        else:
            s = r - alpha * v
            shat = M(s)
        t = matvec(shat)
        ts, tt = _fk.fused_dots2(t, s, interpret=interpret)
        omega_new = ts / (tt + eps)
        x, r, rho_next, rr_new = _fk.fused_bicg_tail(
            x, s, t, phat, shat, rhat, alpha, omega_new, interpret=interpret)
        return (x, r, rhat, p, v, rho, rho_next, alpha, omega_new, rr_new,
                k + 1, jnp.array(False))

    z = jnp.zeros_like(b)
    one = jnp.asarray(1.0, b.dtype)
    st0 = (x0, r0, r0, z, z, one, rr0, one, one, rr0, jnp.array(0),
           jnp.array(True))
    x, r, *_, rr, k, _ = lax.while_loop(
        cond, scoped("krylov.bicgstab_fused", body), st0)
    rn = jnp.sqrt(rr)
    return x, SolveInfo(k, rn, rn <= target)


def block_cg(matvec: Callable, B: jax.Array,
             X0: Optional[jax.Array] = None, *, M: Callable = _identity,
             tol: float = 1e-6, atol: float = 0.0, maxiter: int = 1000,
             ridge: float = 1e-12):
    """Block conjugate gradient (O'Leary 1980) for multiple right-hand sides.

    ``B`` is ``(k, n)`` — k right-hand sides sharing ONE SPD matrix.  The k
    Krylov directions are coupled through (k, k) Gram solves each iteration,
    so hard right-hand sides borrow search directions from easy ones
    (iteration count tracks the HARDEST rhs, not the sum), and every
    iteration runs its k matvecs as one ``vmap`` sweep — the same
    multi-rhs amortization the serving driver's batched dispatch exploits.
    ``matvec``/``M`` are single-vector closures, vmapped here, so every
    kernel-plan matvec and every preconditioner apply works unchanged.

    Convergence targets are per-rhs (``max(tol·‖bᵢ‖, atol)``); the loop runs
    until EVERY rhs meets its target or ``maxiter``.  Converged or linearly
    dependent directions make the Gram matrices singular — those are solved
    through a symmetric eigendecomposition pseudo-inverse with a relative
    cutoff (``ridge`` above dtype eps), so a finished/duplicate column
    becomes an inert no-op instead of amplified roundoff or NaNs
    (breakdown-free in the O'Leary rank-deficient sense).

    Returns ``(X, SolveInfo)`` with per-rhs ``resnorm``/``converged``
    vectors of length k and a scalar shared iteration count.
    """
    if B.ndim != 2:
        raise ValueError(f"block_cg expects B of shape (k, n), got {B.shape}")
    k = B.shape[0]
    X0 = jnp.zeros_like(B) if X0 is None else X0
    mv = jax.vmap(matvec)
    Mv = jax.vmap(M)
    target = jnp.maximum(tol * jnp.linalg.norm(B, axis=1), atol)

    def gram_solve(G, rhs):
        # both Gram matrices (PᵀAP and ZᵀR) are symmetric for SPD A and
        # symmetric M, up to roundoff — symmetrize and pseudo-invert
        return eigh_pinv_solve(G, rhs, ridge=ridge)

    R0 = B - mv(X0)
    Z0 = Mv(R0)
    rho0 = Z0 @ R0.T

    def cond(st):
        X, R, P, rho, it = st
        return (it < maxiter) & jnp.any(jnp.linalg.norm(R, axis=1) > target)

    def body(st):
        X, R, P, rho, it = st
        Q = mv(P)
        alpha = gram_solve(P @ Q.T, rho)       # (PᵀAP)⁻¹ ZᵀR, row convention
        X = X + alpha.T @ P
        R = R - alpha.T @ Q
        Z = Mv(R)
        rho_new = Z @ R.T
        beta = gram_solve(rho, rho_new)
        P = Z + beta.T @ P
        return (X, R, P, rho_new, it + 1)

    X, R, P, rho, it = lax.while_loop(
        cond, scoped("krylov.block_cg", body),
        (X0, R0, Z0, rho0, jnp.array(0)))
    rn = jnp.linalg.norm(R, axis=1)
    return X, SolveInfo(it, rn, rn <= target)


def gmres(matvec: Callable, b: jax.Array, x0: Optional[jax.Array] = None, *,
          M: Callable = _identity, tol: float = 1e-6, atol: float = 0.0,
          restart: int = 32, maxiter: int = 50):
    """Restarted GMRES(m) with modified Gram–Schmidt Arnoldi.

    ``maxiter`` counts outer restarts.  Static Krylov dimension ``restart``
    keeps shapes fixed for jit.  The true residual (and its norm) is carried
    through the loop state — one matvec per restart cycle pays for both the
    convergence check and the next cycle's start vector.
    """
    x0 = jnp.zeros_like(b) if x0 is None else x0
    n = b.shape[-1]
    m = restart
    dtype = b.dtype
    bnorm = jnp.linalg.norm(b)
    target = jnp.maximum(tol * bnorm, atol)

    def arnoldi_cycle(x, r_true):
        r = M(r_true)
        beta = jnp.linalg.norm(r)
        V = jnp.zeros((m + 1, n), dtype).at[0].set(r / (beta + 1e-30))
        H = jnp.zeros((m + 1, m), dtype)

        def step(carry, j):
            V, H = carry
            w = M(matvec(V[j]))

            def mgs(i, w_h):
                w, h = w_h
                hij = jnp.where(i <= j, jnp.sum(w * V[i]), 0.0)
                return (w - hij * V[i], h.at[i].set(hij))

            w, hcol = lax.fori_loop(0, m + 1, mgs, (w, jnp.zeros(m + 1, dtype)))
            hn = jnp.linalg.norm(w)
            hcol = hcol.at[j + 1].set(hn)
            V = V.at[j + 1].set(w / (hn + 1e-30))
            H = H.at[:, j].set(hcol)
            return (V, H), None

        (V, H), _ = lax.scan(step, (V, H), jnp.arange(m))
        # least squares min ‖βe₁ − Hy‖
        e1 = jnp.zeros(m + 1, dtype).at[0].set(beta)
        y, *_ = jnp.linalg.lstsq(H, e1, rcond=None)
        return x + V[:m].T @ y

    r0 = b - matvec(x0)

    def cond(st):
        x, r, rn, k = st
        return (k < maxiter) & (rn > target)

    def body(st):
        x, r, rn, k = st
        x = arnoldi_cycle(x, r)
        r = b - matvec(x)
        return (x, r, jnp.linalg.norm(r), k + 1)

    x, r, rn, k = lax.while_loop(
        cond, scoped("krylov.gmres", body),
        (x0, r0, jnp.linalg.norm(r0), jnp.array(0)))
    return x, SolveInfo(k * m, rn, rn <= target)


def cg_scan(matvec: Callable, b: jax.Array, k: int,
            M: Callable = _identity, x0: Optional[jax.Array] = None):
    """Fixed-k CG via ``lax.scan`` — fully reverse-differentiable.

    This is the *naive O(k)-graph baseline* of paper §4.2: reverse-mode
    through the scan stores every per-iteration residual (O(k·n) memory),
    exactly like autograd-tracked PyTorch CG.  Never used by the adjoint path.
    """
    x0 = jnp.zeros_like(b) if x0 is None else x0
    dot = lambda u, v: jnp.sum(u * v)
    r0 = b - matvec(x0)
    z0 = M(r0)

    rz0 = dot(r0, z0)
    eps = jnp.finfo(b.dtype).eps
    tiny = jnp.asarray((100 * eps) ** 2, b.dtype) * rz0

    def step(carry, _):
        x, r, p, rz = carry
        Ap = matvec(p)
        pAp = dot(p, Ap)
        # guard: once converged (rz → 0) iterate as a no-op instead of 0/0.
        # double-where keeps reverse-mode NaN-free (the unselected branch's
        # denominator must be safe too) — the forced-k sweep of paper Fig. 2
        # runs past convergence by design.
        live = rz > tiny
        pAp_safe = jnp.where(live, pAp, 1.0)
        rz_safe = jnp.where(live, rz, 1.0)
        alpha = jnp.where(live, rz / pAp_safe, 0.0)
        x = x + alpha * p
        r = r - alpha * Ap
        z = M(r)
        rz_new = dot(r, z)
        beta = jnp.where(live, rz_new / rz_safe, 0.0)
        p = z + beta * p
        return (x, r, p, rz_new), None

    (x, r, _, _), _ = lax.scan(step, (x0, r0, z0, dot(r0, z0)), None, length=k)
    return x


# ---------------------------------------------------------------------------
# dense direct backend (TPU: batched LU/Cholesky on the MXU)
# ---------------------------------------------------------------------------

def dense_solve(A_dense: jax.Array, b: jax.Array, method: str = "lu"):
    if method == "cholesky":
        L = jnp.linalg.cholesky(A_dense)
        x = jax.scipy.linalg.cho_solve((L, True), b)
    else:
        x = jnp.linalg.solve(A_dense, b)
    return x, SolveInfo(jnp.array(1), jnp.asarray(0.0, b.dtype), jnp.array(True))


# ---------------------------------------------------------------------------
# nonlinear solvers (paper §3.2.2, "Nonlinear systems")
# ---------------------------------------------------------------------------

def newton_solve(residual: Callable, x0: jax.Array, *, tol: float = 1e-8,
                 maxiter: int = 50, dense_jacobian_budget: int = 2048,
                 inner_tol: float = 1e-8, inner_maxiter: int = 500,
                 damping: float = 1.0, linear_solver=None, jac_pattern=None,
                 assemble_jacobian: Optional[Callable] = None):
    """Newton's method.  Small systems use a dense Jacobian (MXU solve);
    large systems use matrix-free JVP-Krylov (BiCGStab) inner solves.

    Declaring the Jacobian sparsity (``jac_pattern`` — a
    :class:`~repro.core.sparse.SparseTensor` or ``(row, col, n)`` triple)
    routes every inner solve through the plan engine instead: one symbolic
    analysis serves the whole sweep, values refreshed per step
    (:class:`repro.core.nonlinear.SparseNewton`).  ``linear_solver`` is the
    inner :class:`~repro.core.dispatch.SolverConfig` (``backend="direct"``,
    ``precond="amg"``, ...); ``assemble_jacobian(u) -> values`` overrides
    the coloring-based jvp assembly.
    """
    if linear_solver is not None or jac_pattern is not None:
        if jac_pattern is None:
            raise ValueError("linear_solver= needs jac_pattern= declaring "
                             "the Jacobian sparsity")
        from .nonlinear import SparseNewton   # lazy: avoids a module cycle
        sn = SparseNewton(lambda u: residual(u), jac_pattern,
                          linear_solver=linear_solver,
                          assemble_jacobian=(
                              None if assemble_jacobian is None
                              else lambda u: assemble_jacobian(u)))
        return sn.solve(x0, tol=tol, maxiter=maxiter, damping=damping)
    n = x0.shape[-1]
    use_dense = n <= dense_jacobian_budget

    def cond(st):
        x, k, rn = st
        return (k < maxiter) & (rn > tol)

    def body(st):
        x, k, _ = st
        F = residual(x)
        if use_dense:
            J = jax.jacfwd(residual)(x)
            dx = jnp.linalg.solve(J, -F)
        else:
            mv = lambda v: jax.jvp(residual, (x,), (v,))[1]
            dx, _ = bicgstab(mv, -F, tol=inner_tol, maxiter=inner_maxiter)
        x = x + damping * dx
        rn = jnp.linalg.norm(residual(x))
        return (x, k + 1, rn)

    rn0 = jnp.linalg.norm(residual(x0))
    x, k, rn = lax.while_loop(cond, body, (x0, jnp.array(0), rn0))
    return x, SolveInfo(k, rn, rn <= tol)


def picard_solve(fixed_point: Callable, x0: jax.Array, *, tol: float = 1e-8,
                 maxiter: int = 500, relax: float = 1.0):
    """Damped fixed-point (Picard) iteration x ← (1−ω)x + ω G(x)."""
    def cond(st):
        x, k, rn = st
        return (k < maxiter) & (rn > tol)

    def body(st):
        x, k, _ = st
        x_new = (1 - relax) * x + relax * fixed_point(x)
        rn = jnp.linalg.norm(x_new - x)
        return (x_new, k + 1, rn)

    x, k, rn = lax.while_loop(cond, body, (x0, jnp.array(0), jnp.inf))
    return x, SolveInfo(k, rn, rn <= tol)


def anderson_solve(fixed_point: Callable, x0: jax.Array, *, m: int = 5,
                   tol: float = 1e-8, maxiter: int = 200, beta: float = 1.0,
                   ridge: float = 1e-12, gram_solver: str = "pinv"):
    """Anderson acceleration, type-II difference form (Walker & Ni 2011):

        f_k = G(x_k) − x_k
        γ   = argmin ‖f_k − ΔF γ‖²  (windowed least squares, window m)
        x⁺  = x_k + β f_k − (ΔX + β ΔF) γ

    Convergence is checked on ‖f_k‖ (the true fixed-point residual).

    The normal-equations Gram matrix ΔF ΔFᵀ is structurally rank-deficient
    whenever the window is degenerate — fewer iterations than ``m``
    (zero-padded rows), duplicate residual columns, or a residual space of
    dimension < m (any affine map).  ``gram_solver="pinv"`` (default) solves
    it through :func:`eigh_pinv_solve`, the relative-cutoff pseudo-inverse
    :func:`block_cg` uses for exactly the same breakdown; ``"ridge"`` keeps
    the legacy fixed-ridge ``solve(G + ridge·I)`` path, which stagnates in
    f32 (roundoff ~1e-7·‖G‖ swamps the 1e-12 ridge) — retained only as the
    A/B baseline for the regression test."""
    if gram_solver not in ("pinv", "ridge"):
        raise ValueError(f"gram_solver must be 'pinv'|'ridge', "
                         f"got {gram_solver!r}")
    n = x0.shape[-1]
    dtype = x0.dtype
    Xh = jnp.zeros((m + 1, n), dtype)   # iterate history (last row = newest)
    Fh = jnp.zeros((m + 1, n), dtype)   # residual history

    def cond(st):
        x, Xh, Fh, k, rn = st
        return (k < maxiter) & (rn > tol)

    def body(st):
        x, Xh, Fh, k, _ = st
        f = fixed_point(x) - x
        rn = jnp.linalg.norm(f)
        Xh = jnp.roll(Xh, -1, axis=0).at[-1].set(x)
        Fh = jnp.roll(Fh, -1, axis=0).at[-1].set(f)
        dX = Xh[1:] - Xh[:-1]                    # (m, n) rows: Δx_i
        dF = Fh[1:] - Fh[:-1]
        mk = jnp.minimum(k, m)                   # number of valid diffs
        valid = (jnp.arange(m) >= (m - mk))[:, None]
        dXv = jnp.where(valid, dX, 0.0)
        dFv = jnp.where(valid, dF, 0.0)
        if gram_solver == "pinv":
            gamma = eigh_pinv_solve(dFv @ dFv.T, dFv @ f, ridge=ridge)
        else:
            gram = dFv @ dFv.T + ridge * jnp.eye(m, dtype=dtype)
            gamma = jnp.linalg.solve(gram, dFv @ f)
        x_new = x + beta * f - gamma @ (dXv + beta * dFv)
        return (x_new, Xh, Fh, k + 1, rn)

    x, Xh, Fh, k, rn = lax.while_loop(
        cond, body, (x0, Xh, Fh, jnp.array(0), jnp.asarray(jnp.inf, dtype)))
    return x, SolveInfo(k, rn, rn <= tol)


# ---------------------------------------------------------------------------
# eigensolvers (paper §3.2.2 "Eigenvalue problems", §4.3 LOBPCG/Lanczos)
# ---------------------------------------------------------------------------

def lobpcg_general(matvec: Callable, X0: jax.Array, *,
                   gram: Optional[Callable] = None, M: Callable = _identity,
                   tol: float = 1e-6, maxiter: int = 200,
                   largest: bool = False):
    """Locally optimal block preconditioned CG (Knyazev 2001), block form.

    ``X0``: (k, n_local) initial block (rows are vectors).  ``gram(S1, S2)``
    computes S1 S2ᵀ with a global reduction — inject a psum'd version for the
    distributed backend (all row-space arithmetic is s×s and replicated).

    Robustness: the [X | W | P] subspace is orthonormalized by pseudo-inverse
    whitening of its Gram matrix (rank-deficient directions are masked and
    their Ritz values pushed to +inf), and the conjugate block P uses the
    classical coefficient split (its component in the non-X blocks).
    """
    k, n = X0.shape
    dtype = X0.dtype
    sign = -1.0 if largest else 1.0
    mv = (lambda v: sign * matvec(v))
    gram = gram or (lambda S1, S2: S1 @ S2.T)
    BIG = jnp.asarray(1e30, dtype)

    def rr(S):
        """Rayleigh–Ritz on the (possibly rank-deficient) row space of S.

        Whitening in the *eigenbasis* of the Gram matrix (Q = Λ^{-1/2}Vᵀ S)
        makes Q's rows exactly orthonormal on the good directions and exactly
        zero on null ones, so rank deficiency reduces to masking diagonal
        slots of the projected T."""
        G = gram(S, S)
        e, V = jnp.linalg.eigh(G)
        good = e > jnp.maximum(e[-1], 1e-30) * 1e-10
        isq = jnp.where(good, 1.0 / jnp.sqrt(jnp.maximum(e, 1e-300)), 0.0)
        W_ = isq[:, None] * V.T                    # Λ^{-1/2} Vᵀ
        Q = W_ @ S                                  # QQᵀ = diag(good)
        AQ = jax.vmap(mv)(Q)
        T = gram(Q, AQ)
        T = 0.5 * (T + T.T)
        T = T + jnp.diag(jnp.where(good, 0.0, BIG))
        w, U = jnp.linalg.eigh(T)
        C = V @ (isq[:, None] * U[:, :k])           # coefficients in S rows
        X_new = C.T @ S
        return w[:k], X_new, C

    w0, X, _ = rr(X0)
    P = jnp.zeros_like(X)

    def cond(st):
        X, w, P, k_it, rn = st
        return (k_it < maxiter) & (rn > tol)

    def body(st):
        X, w, P, k_it, _ = st
        AX = jax.vmap(mv)(X)
        R = AX - w[:, None] * X
        rr_norms = jnp.sqrt(jnp.diag(gram(R, R)))
        rn = jnp.max(rr_norms / (jnp.abs(w) + 1.0))
        Wp = jax.vmap(M)(R)
        # explicit inter-block orthogonalization (conditioning of S):
        Wp = Wp - gram(Wp, X) @ X
        Wn = jnp.sqrt(jnp.maximum(jnp.diag(gram(Wp, Wp)), 1e-300))
        Wp = Wp / Wn[:, None]
        P = P - gram(P, X) @ X
        Pn = jnp.sqrt(jnp.diag(gram(P, P)))
        P = jnp.where(Pn[:, None] > 1e-150, P / jnp.maximum(Pn, 1e-300)[:, None], P)
        S = jnp.concatenate([X, Wp, P], axis=0)
        w_new, X_new, C = rr(S)
        P_new = C[k:].T @ S[k:]                    # non-X component
        return (X_new, w_new, P_new, k_it + 1, rn)

    X, w, P, k_it, rn = lax.while_loop(
        cond, body, (X, w0, P, jnp.array(0), jnp.asarray(jnp.inf, dtype)))
    nrm = jnp.sqrt(jnp.diag(gram(X, X)))
    X = X / nrm[:, None]
    return sign * w, X, SolveInfo(k_it, rn, rn <= tol)


def lobpcg(matvec: Callable, X0: jax.Array, *, M: Callable = _identity,
           tol: float = 1e-6, maxiter: int = 200, largest: bool = False):
    """Single-device LOBPCG — see :func:`lobpcg_general`."""
    return lobpcg_general(matvec, X0, M=M, tol=tol, maxiter=maxiter,
                          largest=largest)


def lanczos(matvec: Callable, v0: jax.Array, num_steps: int):
    """Lanczos tridiagonalization with full reorthogonalization (small m).

    Returns (alphas, betas, V) — eigenvalues of T approximate extremal
    eigenvalues of A.  Used for Chebyshev-bound estimation and as an
    alternative ``eigsh`` method.
    """
    n = v0.shape[-1]
    m = num_steps
    dtype = v0.dtype
    V = jnp.zeros((m + 1, n), dtype)
    V = V.at[0].set(v0 / jnp.linalg.norm(v0))
    alphas = jnp.zeros(m, dtype)
    betas = jnp.zeros(m, dtype)

    def step(carry, j):
        V, alphas, betas = carry
        w = matvec(V[j])
        alpha = jnp.sum(w * V[j])
        w = w - alpha * V[j] - jnp.where(j > 0, betas[jnp.maximum(j - 1, 0)], 0.0) * V[jnp.maximum(j - 1, 0)]
        # full reorthogonalization (numerical hygiene at small m)
        proj = V @ w                       # (m+1,)
        mask = (jnp.arange(m + 1) <= j)
        w = w - (jnp.where(mask, proj, 0.0)[None, :] @ V).reshape(n)
        beta = jnp.linalg.norm(w)
        V = V.at[j + 1].set(w / (beta + 1e-30))
        return (V, alphas.at[j].set(alpha), betas.at[j].set(beta)), None

    (V, alphas, betas), _ = lax.scan(step, (V, alphas, betas), jnp.arange(m))
    return alphas, betas, V


def eigsh_lanczos(matvec: Callable, n: int, k: int, *, num_steps: int = 64,
                  dtype=jnp.float32, seed: int = 0):
    """k smallest eigenpairs via Lanczos + dense eigh of T, Ritz vectors."""
    v0 = jax.random.normal(jax.random.PRNGKey(seed), (n,), dtype)
    alphas, betas, V = lanczos(matvec, v0, num_steps)
    T = (jnp.diag(alphas) + jnp.diag(betas[:-1], 1) + jnp.diag(betas[:-1], -1))
    w, U = jnp.linalg.eigh(T)
    ritz = (V[:num_steps].T @ U[:, :k]).T      # (k, n)
    ritz = ritz / jnp.linalg.norm(ritz, axis=1, keepdims=True)
    return w[:k], ritz
