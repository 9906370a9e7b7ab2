"""O(1)-graph adjoint differentiation framework (paper §3.2).

Every solve is wrapped in ``jax.custom_vjp`` so the autodiff graph contains a
single node regardless of solver iterations or backend — the JAX rendering of
torch-sla's ``torch.autograd.Function`` layer.  Instances of Eq. (2):

* linear   (Eq. 3):  Aᵀλ = ∂L/∂x;   ∂L/∂b = λ,  ∂L/∂A_ij = −λ_i x_j  (pattern only)
* nonlinear:         Jᵀλ = ∂L/∂u*;  ∂L/∂θ = −λᵀ ∂F/∂θ  (via jax.vjp, matrix-free)
* eigen    (Eq. 4):  ∂λ_k/∂A_ij = v_ki v_kj (Hellmann–Feynman); eigenvector
                     cotangents take one deflated linear solve per pair.

Only (A, x*) are stashed by the forward — O(n + nnz) residency; intermediate
Krylov iterates are never referenced (paper Table 2).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from . import dispatch as _dispatch
from . import options as _options
from . import solvers as _solvers
from ..kernels import ops as _kops
from .dispatch import SolverConfig
from .spans import count
from .sparse import SparseTensor

__all__ = ["sparse_solve", "dist_sparse_solve", "nonlinear_solve",
           "sparse_eigsh", "sparse_slogdet"]


def _sum_to_shape(x: jax.Array, shape) -> jax.Array:
    """Reverse broadcasting: sum x down to ``shape``."""
    if x.shape == tuple(shape):
        return x
    extra = x.ndim - len(shape)
    x = x.sum(axis=tuple(range(extra))) if extra else x
    axes = tuple(i for i, (a, b) in enumerate(zip(x.shape, shape)) if a != b)
    return x.sum(axis=axes, keepdims=True) if axes else x


# ---------------------------------------------------------------------------
# linear solve (paper §3.2.2 "Linear systems")
# ---------------------------------------------------------------------------

def _has_plane_layout(meta, val: jax.Array, n: int) -> bool:
    """True when ``val`` holds a stencil layout's planes, flat: the matrix
    gradient can then be formed plane by plane.  ``meta`` is static pytree
    data, so this holds under jit where the pattern arrays are tracers."""
    layout = _kops.stencil_offsets(meta)
    if layout is None:
        return False
    offsets, grid = layout
    return math.prod(grid) == n and val.shape[-1:] == (len(offsets) * n,)


def sparse_solve(cfg: SolverConfig, A: SparseTensor, b: jax.Array,
                 x0: Optional[jax.Array] = None) -> jax.Array:
    """Differentiable A.solve(b).  ``cfg`` must already be resolved.

    The forward fetches (or analyzes once) the pattern's cached
    :class:`~repro.core.dispatch.SolverPlan`; the backward solves Aᵀλ = g
    through ``plan.transpose()`` — the SAME plan object for symmetric
    patterns (kernel layout + preconditioner build reused); for the direct
    backend a shared-artifact transpose plan that runs the mirrored (Uᵀ, Lᵀ)
    sweeps on the FORWARD numeric factors (the per-values setup memo is
    shared, so the backward refactorizes nothing); a once-analyzed transposed
    sibling otherwise.  No re-dispatch, no re-analysis per call.

    The matrix gradient ∂L/∂A_ij = −λ_i x_j is formed on the pattern.  A
    tensor made with ``stencil=`` (a ``Stencil5Meta`` or ``Stencil27Meta``)
    promises the stencil layout: values plane-major, slot ``p·n + cell``,
    planes in ``vc_pattern`` / ``stencil27_pattern`` offset order; ``row``
    of a slot is its cell and ``col`` the neighbour at the plane's offset,
    clamped into the grid where it leaves it (a structurally-zero value).
    On that layout the gradient is formed plane by plane, with no gather
    (``kernels.ops.stencil_matrix_grad``, counted as
    ``adjoint_plane_grad``); every other layout gathers over (row, col).
    """
    plan = _dispatch.get_plan(A, cfg)
    row, col = plan.row, plan.col
    plane_grad = _has_plane_layout(plan.stencil, A.val, plan.shape[0])

    @jax.custom_vjp
    def solve_fn(val, rhs):
        x, _ = plan.solve(plan.matrix(val), rhs, x0, cfg=cfg)
        return x

    def fwd(val, rhs):
        x, _ = plan.solve(plan.matrix(val), rhs, x0, cfg=cfg)
        x = jax.lax.stop_gradient(x)
        return x, (val, x)

    def bwd(res, g):
        val, x = res
        # adjoint system Aᵀ λ = g — forward plan's transpose view (§3.2.3).
        # ``val`` is the identical array object the forward saw (custom_vjp
        # residual), so backends with a per-values setup memo (direct) reuse
        # the forward factorization here instead of re-running setup.
        tplan = plan.transpose()
        lam, _ = tplan.solve(tplan.matrix(val), g, None, cfg=tplan.adapt(cfg))
        # ∂L/∂A_ij = −λ_i x_j  on the sparsity pattern — O(nnz): plane by
        # plane on a stencil layout, a gather over (row, col) otherwise
        if plane_grad:
            count("adjoint_plane_grad")
            gval_full = _kops.stencil_matrix_grad(plan.stencil, lam, x)
        else:
            gval_full = -(lam[..., row] * x[..., col])
        gval = _sum_to_shape(gval_full, val.shape)
        gb = _sum_to_shape(lam, b.shape)
        return gval, gb

    solve_fn.defvjp(fwd, bwd)
    return solve_fn(A.val, b)


def sparse_solve_with_info(cfg: SolverConfig, A: SparseTensor, b, x0=None):
    """Non-differentiable variant that also returns SolveInfo."""
    return _dispatch.solve_impl(cfg, A, b, x0)


# ---------------------------------------------------------------------------
# distributed linear solve (paper §3.3) — same plan discipline on a mesh
# ---------------------------------------------------------------------------

def dist_sparse_solve(cfg: SolverConfig, D, b, x0=None) -> jax.Array:
    """Differentiable ``DSparseTensor.solve`` through the plan engine.

    The forward fetches (or analyzes once) the distributed plan — halo
    program, partition bounds, preconditioner build, and for non-symmetric
    patterns the Aᵀ partition, all frozen as plan artifacts.  The backward
    solves Aᵀλ = g through ``plan.transpose()``: the SAME plan object for
    symmetric patterns (halo program + preconditioner build + per-values
    setup memo reused), a shared-artifact transposed sibling otherwise whose
    stacked Aᵀ values are derived from the forward values by the plan's
    cached gather map — never rebuilt per call, never instance state.  The
    matrix gradient is the local O(nnz) assembly −λ_i x_j with halo'd x.
    """
    from . import distributed as _dist
    plan = _dispatch.get_plan(D, cfg)

    @jax.custom_vjp
    def solve_fn(lval, rhs):
        x, _ = plan.solve(D.with_values(lval), rhs, x0, cfg=cfg)
        return x

    def fwd(lval, rhs):
        x, _ = plan.solve(D.with_values(lval), rhs, x0, cfg=cfg)
        x = jax.lax.stop_gradient(x)
        return x, (lval, x)

    def bwd(res, g):
        lval, x = res
        tplan = plan.transpose()
        if tplan is plan:
            # symmetric: same plan, same values — the setup memo makes the
            # adjoint preconditioner refresh a reuse, not a re-trace
            lam, _ = tplan.solve(D.with_values(lval), g, None,
                                 cfg=tplan.adapt(cfg))
        else:
            lval_t = _dist.transpose_values(plan, lval)
            At = _dist.transpose_view(tplan, lval_t)
            lam, _ = tplan.solve(At, g, None, cfg=tplan.adapt(cfg))
        lam = jax.lax.stop_gradient(lam)
        gval = _dist.assemble_matrix_grad(plan, lam, x)
        return gval, lam

    solve_fn.defvjp(fwd, bwd)
    return solve_fn(D.lval, b)


# ---------------------------------------------------------------------------
# nonlinear solve (paper §3.2.2 "Nonlinear systems")
# ---------------------------------------------------------------------------

def nonlinear_solve(residual: Callable, x0: jax.Array, *theta,
                    method: str = "newton", tol: float = 1e-8,
                    maxiter: int = 50, inner_tol: float = 1e-10,
                    inner_maxiter: int = 1000, damping: float = 1.0,
                    anderson_m: int = 5, linear_solver=None,
                    jac_pattern=None, assemble_jacobian=None,
                    symmetric: Optional[bool] = None):
    """Solve F(u, θ) = 0 for u with O(1)-graph adjoint gradients w.r.t. θ.

    ``residual(u, *theta)`` is any JAX-traceable function.  The forward may
    take many Newton/Picard/Anderson iterations (each with inner linear
    solves); the backward is ONE adjoint solve Jᵀλ = g plus one VJP into θ.

    Default (matrix-free) path: Newton inner solves and the adjoint run
    BiCGStab on ``jax.jvp``/``jax.vjp`` of the residual — no pattern needed.

    SparseNewton path (paper §3.2.2): pass ``jac_pattern=`` — the mesh-fixed
    Jacobian sparsity as a :class:`~repro.core.sparse.SparseTensor` or
    ``(row, col[, n])`` index arrays — and optionally ``linear_solver=``, a
    :class:`~repro.core.dispatch.SolverConfig` steering the inner solves
    through the plan engine (``backend="direct"``, ``precond="amg"``, any
    registered backend).  The pattern is colored once, ONE analyzed plan
    serves every Newton step, and the IFT backward solves Jᵀλ = g through
    ``plan.transpose()`` on the converged step's factors/hierarchy — zero
    extra factorizations (see :class:`repro.core.nonlinear.SparseNewton`).
    ``assemble_jacobian(u, *theta) -> values`` overrides the coloring-based
    assembly; ``symmetric=`` overrides the pattern's symmetry detection.
    For ``method="picard"``/``"anderson"`` the forward stays fixed-point
    iteration but the IFT backward still runs through the plan (one
    assembly + setup at the converged point).
    """
    theta = tuple(theta)
    sn = None
    if jac_pattern is not None:
        from .nonlinear import SparseNewton
        cfg = linear_solver if linear_solver is not None else \
            SolverConfig(tol=inner_tol, maxiter=inner_maxiter)
        sn = SparseNewton(residual, jac_pattern, linear_solver=cfg,
                          assemble_jacobian=assemble_jacobian,
                          symmetric=symmetric)
    elif linear_solver is not None:
        raise ValueError("linear_solver= requires jac_pattern= declaring "
                         "the Jacobian sparsity")

    @jax.custom_vjp
    def nl(theta):
        u, _ = _forward(theta)
        return u

    def _forward(theta):
        F = lambda u: residual(u, *theta)
        if method == "newton":
            if sn is not None:
                u, _, vals = sn._solve_full(x0, *theta, tol=tol,
                                            maxiter=maxiter, damping=damping)
                return u, vals
            u, _ = _solvers.newton_solve(F, x0, tol=tol, maxiter=maxiter,
                                         damping=damping,
                                         inner_tol=inner_tol,
                                         inner_maxiter=inner_maxiter)
        elif method == "picard":
            u, _ = _solvers.picard_solve(lambda u: u - F(u), x0, tol=tol,
                                         maxiter=maxiter)
        elif method == "anderson":
            u, _ = _solvers.anderson_solve(lambda u: u - F(u), x0, tol=tol,
                                           maxiter=maxiter, m=anderson_m)
        else:
            raise ValueError(f"unknown nonlinear method {method!r}")
        if sn is not None:
            # fixed-point forward, plan-engine backward: one assembly at u*
            # (its setup is memoized, so the bwd transpose solve reuses it)
            return u, sn.assemble(u, *theta)
        return u, None

    def fwd(theta):
        u, vals = _forward(theta)
        # NOTE: ``vals`` is stashed as the identical array object the plan's
        # setup memo keyed on — do not stop_gradient it (fresh array object,
        # memo miss → a spurious refactorization in the backward)
        return jax.lax.stop_gradient(u), (theta, u, vals)

    def bwd(res, g):
        theta, u, vals = res
        if vals is not None:
            # Jᵀ λ = g on the transpose view of the step plan — converged
            # factors/hierarchy reused, zero refactorization (Eq. 2)
            lam, _ = sn.solve_adjoint(vals, g)
        else:
            # matrix-free via vjp (paper: exact only once F(u*,θ) ≈ 0;
            # early termination biases the gradient)
            _, vjp_u = jax.vjp(lambda uu: residual(uu, *theta), u)
            JT = lambda v: vjp_u(v)[0]
            lam, _ = _solvers.bicgstab(JT, g, tol=inner_tol,
                                       maxiter=inner_maxiter)
        # ∂L/∂θ = −λᵀ ∂F/∂θ
        _, vjp_th = jax.vjp(lambda *th: residual(u, *th), *theta)
        gtheta = jax.tree.map(lambda t: -t, vjp_th(lam))
        return (tuple(gtheta),)

    nl.defvjp(fwd, bwd)
    return nl(theta)


# ---------------------------------------------------------------------------
# symmetric eigensolve (paper §3.2.2 "Eigenvalue problems")
# ---------------------------------------------------------------------------

def sparse_eigsh(A: SparseTensor, k: int = 6, *, method: str = "lobpcg",
                 tol: float = 1e-6, maxiter: int = 200,
                 compute_vector_grads: bool = True, largest: bool = False,
                 precond: Optional[str] = None, seed: int = 0):
    """k extremal eigenpairs of symmetric A with Hellmann–Feynman adjoint.

    Returns ``(w (…,k), V (…,k,n))``.  Eigenvalue cotangents cost one O(nnz)
    outer product; eigenvector cotangents one deflated CG solve per pair.
    Simple (non-degenerate) eigenvalues assumed — paper §5.

    ``precond`` (``"amg"``, ``"jacobi"``, ``"block_jacobi"``, ``"ilu"``, ...;
    LOBPCG only) routes the residual preconditioner through the plan engine:
    the pattern's cached plan builds the hierarchy/factors ONCE at analyze
    time, the per-values refresh goes through the plan's setup memo —
    shared with any linear solves on the same tensor — and the backward's
    deflated CG reuses the same apply (for ``largest=False``, where the
    deflated operator A − λ_k I is positive on the complement; the
    ``largest=True`` backward stays unpreconditioned).
    """
    row, col, n = A.row, A.col, A.shape[0]

    pplan = None
    if precond is not None:
        if method != "lobpcg":
            raise ValueError(f"precond= requires method='lobpcg', "
                             f"got method={method!r}")
        pcfg = SolverConfig(backend="jnp", method="cg", tol=tol,
                            maxiter=maxiter, precond=precond)
        pplan = _dispatch.get_plan(A, pcfg)

    def _make_M(val, mv):
        """Single-vector preconditioner apply from the plan's memoized
        values-setup — LOBPCG vmaps it over the residual block."""
        _, pstate, _ = pplan.setup(pplan.matrix(val))
        return pplan.artifacts["precond"].make_apply(pstate, mv)

    def _impl(val):
        mv = _dispatch.make_matvec(A.with_values(val))
        if method == "lobpcg":
            X0 = jax.random.normal(jax.random.PRNGKey(seed), (k, n), val.dtype)
            M = _make_M(val, mv) if pplan is not None else _solvers._identity
            w, V, _ = _solvers.lobpcg(mv, X0, M=M, tol=tol, maxiter=maxiter,
                                      largest=largest)
            return w, V
        if method == "lanczos":
            mv2 = mv if not largest else (lambda v: -mv(v))
            w, V = _solvers.eigsh_lanczos(mv2, n, k,
                                          num_steps=min(max(4 * k, 32), n),
                                          dtype=val.dtype, seed=seed)
            return (-w[::-1], V[::-1]) if largest else (w, V)
        raise ValueError(f"unknown eig method {method!r}")

    @jax.custom_vjp
    def eig_fn(val):
        return _impl(val)

    def fwd(val):
        w, V = jax.tree.map(jax.lax.stop_gradient, _impl(val))
        return (w, V), (val, w, V)

    def bwd(res, cot):
        val, w, V = res
        gw, gV = cot
        # Hellmann–Feynman eigenvalue term: Σ_k gw_k v_ki v_kj on the pattern
        gval = jnp.einsum("k,ke,ke->e", gw, V[:, row], V[:, col])
        if compute_vector_grads:
            # eigenvector term: y v_kᵀ with y = (λ_k I − A)⁺ (I − v_k v_kᵀ) g.
            # Contributions from the OTHER COMPUTED pairs are analytic
            # (gᵀv_j/(λ_k−λ_j)); the uncomputed complement — where A − λ_k I
            # is definite for extremal pairs — takes one deflated CG solve.
            mv = _dispatch.make_matvec(A.with_values(val))
            # plan-engine preconditioner for the deflated solves: ``val`` is
            # the identical array the forward set up → setup-memo hit, the
            # SAME hierarchy/factors serve forward and backward.  Skipped for
            # largest=True (the deflated operator is negative there, an SPD
            # M ≈ A⁻¹ would break CG).
            Mp = _make_M(val, mv) if (pplan is not None and not largest) \
                else None

            def pair_grad(i, acc):
                lam_i = w[i]
                v_i = V[i]
                gv = gV[i]
                # analytic part over computed pairs j ≠ i (simple eigenvalues
                # assumed — paper §5; degenerate clusters are out of scope)
                dif = lam_i - w
                coeff = jnp.where(jnp.arange(k) == i, 0.0,
                                  (V @ gv) / jnp.where(jnp.abs(dif) < 1e-12,
                                                       jnp.inf, dif))
                y_comp = coeff @ V
                # deflated solve on the complement of ALL computed pairs
                proj = lambda z: z - V.T @ (V @ z)
                op = lambda z: proj(mv(proj(z)) - lam_i * proj(z))
                rhs = -proj(gv)
                Mdef = _solvers._identity if Mp is None else \
                    (lambda z: proj(Mp(proj(z))))
                y_rest, _ = _solvers.cg(op, rhs, M=Mdef, tol=tol,
                                        maxiter=maxiter * 4)
                y = y_comp + proj(y_rest)
                # the solver sees sym(A): differentiate the symmetrized map
                return acc + 0.5 * (y[row] * v_i[col] + v_i[row] * y[col])

            gval = jax.lax.fori_loop(0, k, pair_grad, gval)
        return (gval,)

    eig_fn.defvjp(fwd, bwd)
    return eig_fn(A.val)


# ---------------------------------------------------------------------------
# log-determinant (paper §3.3) — sparse via cached LDLᵀ/LU factors within
# the direct_budget option, dense fallback beyond
# ---------------------------------------------------------------------------

def _slogdet_direct_plan(A: SparseTensor):
    """The direct-backend plan for a slogdet, or None when the sparse path
    does not apply (batched values, traced/oversize pattern, missing
    structural diagonal)."""
    n, m = A.shape
    if n != m or A.batch_shape:
        return None
    if isinstance(A.row, jax.core.Tracer) or isinstance(A.col, jax.core.Tracer):
        return None
    if n > _options.current().direct_budget:
        return None
    if not _dispatch.BACKENDS["direct"].applicable(A):
        return None
    cfg = SolverConfig(backend="direct", method="auto").resolved(A)
    return _dispatch.get_plan(A, cfg)


def sparse_slogdet(A: SparseTensor):
    """(sign, log|det|) of A with gradients on the sparsity pattern.

    For concrete square patterns within the ``direct_budget`` option the
    forward runs
    on the *cached* LDLᵀ/LU factors of the plan engine (the same numeric
    factorization a ``backend="direct"`` solve memoizes): with the symmetric
    fill-reducing permutation det(P A Pᵀ) = det(A) and unit-diagonal L, the
    determinant is the product of the stored pivots — Σ log |d_i| with sign
    tracking, O(nnz_L) work and memory, no densification.  The backward
    solves Aᵀ X = I column-by-column on the SAME factors (vmapped
    transposed sweeps) to evaluate d log|det| / dA_ij = (A⁻ᵀ)_ij on the
    pattern.  Batched values, oversize or diagonal-deficient patterns keep
    the dense fallback.
    """
    row, col = A.row, A.col
    plan = _slogdet_direct_plan(A)

    if plan is not None:
        from . import direct as _direct
        art = plan.artifacts["direct"]
        n = A.shape[0]

        @jax.custom_vjp
        def sld(val):
            C = plan.setup(plan.matrix(val))      # memoized numeric factors
            # pivot-block aware: 2x2 Bunch–Kaufman pairs contribute their
            # block determinant, not the raw diagonal product
            return _direct.factor_slogdet(art, C)

        def fwd(val):
            return sld(val), (val,)

        def bwd(res, cot):
            (val,) = res
            _, glog = cot
            C = plan.setup(plan.matrix(val))      # memo hit — zero refactor
            # columns of A⁻ᵀ from the forward factors: Aᵀ x_j = e_j
            X = jax.vmap(lambda e: _direct.factored_solve(
                art, C, e, transposed=not plan.artifacts["transposed"]))(
                    jnp.eye(n, dtype=val.dtype))
            # d log|det| / dA_ij = (A⁻ᵀ)_ij = X[j, i] on the pattern
            return (glog * X[col, row],)

        sld.defvjp(fwd, bwd)
        return sld(A.val)

    @jax.custom_vjp
    def sld(val):
        dense = A.with_values(val).todense()
        sign, logabs = jnp.linalg.slogdet(dense)
        return sign, logabs

    def fwd(val):
        out = sld(val)
        return out, (val,)

    def bwd(res, cot):
        (val,) = res
        _, glog = cot
        dense = A.with_values(val).todense()
        inv_T = jnp.linalg.inv(dense).T
        # d logdet / dA_ij = (A⁻ᵀ)_ij restricted to the pattern
        gval = glog * inv_T[..., row, col]
        return (_sum_to_shape(gval, val.shape),)

    sld.defvjp(fwd, bwd)
    return sld(A.val)
