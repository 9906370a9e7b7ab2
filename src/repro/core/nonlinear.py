"""SparseNewton — nonlinear solves through the plan engine (paper §3.2.2).

The Jacobian sparsity of a mesh-based residual is FIXED: Newton changes the
values, never the pattern.  SparseNewton exploits that exactly the way the
linear plan engine does — analyze once, refresh values every step:

* **coloring** (analyze-time, eager): a Curtis–Powell–Reid distance-1
  coloring of the declared pattern's column-intersection graph
  (:func:`repro.core.sparse.color_pattern`) compresses the Jacobian to
  ``n_colors`` probe directions, counted once in
  ``PLAN_STATS["jac_color"]``.  Each Newton step then recovers the exact
  nnz values with ONE vmapped ``jax.jvp`` sweep
  (``PLAN_STATS["jac_assemble"]``) — or a user ``assemble_jacobian``
  callback when the residual has a cheaper closed-form Jacobian.
* **one plan serves every step**: the inner solve dispatches through the
  same cached :class:`~repro.core.dispatch.SolverPlan` — sparse-direct
  (supernodal) factorization, ``precond="amg"``, block-Jacobi, any
  registered backend — so ``PLAN_STATS["analyze"] == 1`` across a whole
  Newton sweep.  Per-step numeric refreshes go through the plan's setup
  memo: a fresh values array per step means ``factorize == n_steps`` for
  the direct backend (``galerkin == n_steps`` for AMG), never more.
* **IFT backward on the converged step's factors**:
  :meth:`SparseNewton.solve_adjoint` runs Jᵀλ = g through
  ``plan.transpose()`` on the SAME values array the last forward step set
  up — the shared setup memo turns the backward's factorization into a
  reuse (``transpose_shared == 1``, zero extra ``factorize``/``galerkin``,
  O(1) autodiff graph nodes, paper Eq. 2).

The differentiable entry point is
:func:`repro.core.adjoint.nonlinear_solve` with ``jac_pattern=`` /
``linear_solver=``; this module is the engine underneath.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import dispatch as _dispatch
from . import options as _options
from .dispatch import SolverConfig
from .solvers import SolveInfo
from .sparse import SparseTensor, color_pattern, detect_properties
from .spans import count

__all__ = ["SparseNewton"]


def _is_staging() -> bool:
    # same ambient-trace probe as SolverPlan._memo_store: does an op on a
    # fresh constant come back traced?  (eager jax.grad says no)
    return isinstance(jnp.zeros(()) + 0.0, jax.core.Tracer)


class SparseNewton:
    """Newton's method with a mesh-fixed sparse Jacobian through the plan
    engine — analyze once, one symbolic factorization (or AMG hierarchy)
    for every step, per-step values through the setup memo.

    Parameters
    ----------
    residual
        ``residual(u, *theta) -> F`` with ``F.shape == u.shape == (n,)``.
    pattern
        The Jacobian sparsity, fixed across steps: a
        :class:`~repro.core.sparse.SparseTensor` (its pattern, props, and —
        crucially — its plan cache are reused, so a mesh tensor you already
        solve with shares its analyzed plans), or a ``(row, col)`` /
        ``(row, col, n)`` tuple of concrete index arrays.  Entries of the
        true Jacobian outside the declared pattern are silently dropped —
        declare a superset when unsure.
    linear_solver
        Inner-solve :class:`~repro.core.dispatch.SolverConfig`
        (``backend="direct"``, ``precond="amg"``, tolerances, ...).
        ``None`` → auto-dispatch on the first assembled values.
    assemble_jacobian
        Optional ``assemble_jacobian(u, *theta) -> values`` returning the
        nnz values on the declared pattern, replacing the coloring-based
        jvp sweep (use it when a closed form is cheaper, or when the
        pattern needs more colors than ``options.jac_coloring_budget``).
    symmetric
        Override the symmetry detection — controls whether the adjoint
        shares the forward plan outright.  Default: inherited from a
        tensor ``pattern``, else detected from the first concretely
        assembled values (``False`` when assembly only ever runs traced —
        the safe choice, at the cost of a transposed sibling plan).
    """

    def __init__(self, residual: Callable, pattern, *,
                 linear_solver: Optional[SolverConfig] = None,
                 assemble_jacobian: Optional[Callable] = None,
                 symmetric: Optional[bool] = None):
        self.residual = residual
        self.assemble_jacobian = assemble_jacobian
        self._symmetric = symmetric
        self._cfg0 = linear_solver
        self._cfg: Optional[SolverConfig] = None
        self._plan = None

        if isinstance(pattern, SparseTensor):
            n, m = pattern.shape
            if n != m:
                raise ValueError(f"Jacobian pattern must be square, "
                                 f"got {pattern.shape}")
            self.row, self.col, self.n = pattern.row, pattern.col, n
            self._template = pattern
            if symmetric is not None and symmetric != bool(
                    pattern.props.get("symmetric", False)):
                # different props change plan selection/sharing: give the
                # override its own template so the tensor's cached plans
                # (keyed on config only, not props) are not reused unsoundly
                t = SparseTensor(pattern.val, pattern.row, pattern.col,
                                 pattern.shape,
                                 props=dict(pattern.props), validate=False)
                t.props["symmetric"] = symmetric
                if not symmetric:
                    t.props["spd_hint"] = False
                self._template = t
        else:
            if len(pattern) == 2:
                row, col = pattern
                n = int(max(np.asarray(row).max(), np.asarray(col).max())) + 1
            else:
                row, col, n = pattern
            self.row = jnp.asarray(row, jnp.int32)
            self.col = jnp.asarray(col, jnp.int32)
            self.n = int(n)
            self._template = None

        if assemble_jacobian is None:
            color, n_colors = color_pattern(self.row, self.col, self.n)
            budget = _options.current().jac_coloring_budget
            if n_colors > budget:
                raise ValueError(
                    f"Jacobian pattern needs {n_colors} colors (jvp probes "
                    f"per assembly) > jac_coloring_budget ({budget}); pass "
                    f"assemble_jacobian= or raise the option "
                    f"(sla.set_options(jac_coloring_budget=...))")
            count("jac_color")
            self.n_colors = n_colors
            probes = np.zeros((n_colors, self.n))
            probes[color, np.arange(self.n)] = 1.0
            self._probes = jnp.asarray(probes)
            # entry e of the pattern reads probe-sweep slot
            # (color[col[e]], row[e]):  J[r,c] == (J @ p_color[c])[r]
            self._slot = jnp.asarray(color[np.asarray(self.col)], jnp.int32)
        else:
            self.n_colors = 0

    # -- Jacobian values on the pattern --------------------------------------
    def assemble(self, u, *theta):
        """Numeric Jacobian values on the declared pattern at ``u`` — one
        vmapped jvp sweep over the color probes (or the user callback)."""
        count("jac_assemble")
        if self.assemble_jacobian is not None:
            return self.assemble_jacobian(u, *theta)
        F = lambda x: self.residual(x, *theta)
        P = self._probes.astype(u.dtype)
        Jp = jax.vmap(lambda p: jax.jvp(F, (u,), (p,))[1])(P)  # (colors, n)
        return Jp[self._slot, self.row]

    # -- plan resolution (once) ----------------------------------------------
    def _ensure_plan(self, vals=None):
        if self._plan is not None:
            return self._plan
        tmpl = self._template
        if tmpl is None:
            concrete = vals is not None and \
                not isinstance(vals, jax.core.Tracer)
            if concrete:
                props = detect_properties(vals, self.row, self.col,
                                          (self.n, self.n))
            else:
                # never-concrete assembly: symmetry unknowable — default to
                # the safe transposed-sibling adjoint unless overridden
                props = detect_properties(jnp.ones(self.row.shape[0]),
                                          self.row, self.col,
                                          (self.n, self.n),
                                          check_values=False)
                props["symmetric"] = False
                props["spd_hint"] = False
            if self._symmetric is not None:
                props["symmetric"] = self._symmetric
                if not self._symmetric:
                    props["spd_hint"] = False
            vv = vals if concrete else jnp.ones(self.row.shape[0])
            tmpl = SparseTensor(vv, self.row, self.col, (self.n, self.n),
                                props=props, validate=False)
            self._template = tmpl
        cfg = self._cfg0 if self._cfg0 is not None else SolverConfig()
        if cfg.backend in (None, "auto") or cfg.method in (None, "auto"):
            cfg = cfg.resolved(tmpl)
        self._cfg = cfg
        self._plan = _dispatch.get_plan(tmpl, cfg)
        return self._plan

    @property
    def plan(self):
        """The analyzed :class:`~repro.core.dispatch.SolverPlan` (None until
        the first solve resolves auto-dispatch against real values)."""
        return self._plan

    # -- Newton driver -------------------------------------------------------
    def solve(self, u0, *theta, tol: float = 1e-8, maxiter: int = 50,
              damping: float = 1.0):
        """Newton sweep: assemble values → plan.solve(J, −F) → update.

        Eager inputs run a Python loop (each step's fresh values array is a
        setup-memo miss, so ``factorize``/``galerkin`` count the steps);
        traced inputs fall back to a ``lax.while_loop``.  Returns
        ``(u, SolveInfo)``.  For gradients w.r.t. ``theta`` use
        :func:`repro.core.adjoint.nonlinear_solve` — this entry point is
        un-differentiated, like ``plan.solve``.
        """
        u, info, _ = self._solve_full(u0, *theta, tol=tol, maxiter=maxiter,
                                      damping=damping)
        return u, info

    def _solve_full(self, u0, *theta, tol, maxiter, damping):
        """(u, info, vals_last) — vals_last is the values array whose setup
        the plan memoized, handed to :meth:`solve_adjoint` by the IFT
        backward so the adjoint refactorizes nothing."""
        u0 = jnp.asarray(u0)
        leaves = jax.tree_util.tree_leaves((u0,) + theta)
        traced = _is_staging() or any(
            isinstance(l, jax.core.Tracer) for l in leaves)
        if traced:
            return self._solve_traced(u0, theta, tol, maxiter, damping)
        return self._solve_eager(u0, theta, tol, maxiter, damping)

    def _solve_eager(self, u0, theta, tol, maxiter, damping):
        u = u0
        Fu = self.residual(u, *theta)
        rn = float(jnp.linalg.norm(Fu))
        vals = None
        k = 0
        while k < maxiter and rn > tol:
            vals = self.assemble(u, *theta)
            plan = self._ensure_plan(vals)
            dx, _ = plan.solve(plan.matrix(vals), -Fu, cfg=self._cfg)
            u = u + damping * dx
            Fu = self.residual(u, *theta)
            rn = float(jnp.linalg.norm(Fu))
            k += 1
        if vals is None:
            # converged at u0: assemble (and set up) once so the adjoint
            # still has factors to reuse
            vals = self.assemble(u, *theta)
            self._ensure_plan(vals)
        info = SolveInfo(jnp.asarray(k), jnp.asarray(rn, u.dtype),
                         jnp.asarray(rn <= tol))
        return u, info, vals

    def _solve_traced(self, u0, theta, tol, maxiter, damping):
        vals0 = self.assemble(u0, *theta)
        plan = self._ensure_plan(vals0)
        Fu0 = self.residual(u0, *theta)

        def cond(st):
            u, vals, Fu, rn, k = st
            return (k < maxiter) & (rn > tol)

        def body(st):
            u, _, Fu, _, k = st
            vals = self.assemble(u, *theta)
            dx, _ = plan.solve(plan.matrix(vals), -Fu, cfg=self._cfg)
            u = u + damping * dx
            Fu = self.residual(u, *theta)
            return (u, vals, Fu, jnp.linalg.norm(Fu), k + 1)

        st0 = (u0, vals0, Fu0, jnp.linalg.norm(Fu0), jnp.asarray(0))
        u, vals, Fu, rn, k = jax.lax.while_loop(cond, body, st0)
        return u, SolveInfo(k, rn, rn <= tol), vals

    # -- IFT adjoint ---------------------------------------------------------
    def solve_adjoint(self, vals, g):
        """λ from Jᵀλ = g on the transpose view of the step plan.

        Pass the IDENTICAL values array the last forward step set up (the
        custom_vjp residual does) and the shared setup memo serves the
        backward: symmetric patterns reuse the plan outright, the direct
        backend runs mirrored Uᵀ/Lᵀ sweeps on the forward factors — zero
        refactorizations either way.  Exact once F(u*, θ) ≈ 0 and J is
        evaluated at the converged root; with a tight forward ``tol`` the
        last-step J is within that tolerance of J(u*).
        """
        plan = self._ensure_plan(vals)
        tplan = plan.transpose()
        lam, info = tplan.solve(tplan.matrix(vals), g, None,
                                cfg=tplan.adapt(self._cfg))
        return lam, info
