"""Preconditioners, split into eager ``build(pattern)`` + traced ``refresh(values)``.

The paper's pytorch-native backend supports only Jacobi (its stated
limitation, §5).  We reproduce Jacobi faithfully and add *beyond-paper*
preconditioners: block-Jacobi (dense MXU-sized diagonal blocks), Chebyshev
polynomial, a geometric multigrid V-cycle (``precond="mg"``, stencil
operators only), smoothed-aggregation algebraic multigrid
(``precond="amg"``, any COO pattern — coarsening and the Galerkin triple
product live as static index programs on the plan, see
:mod:`repro.core.multigrid`), and an incomplete factorization
(``precond="ilu"``, ILU(0)/IC(0)) that shares the direct backend's symbolic
machinery (:mod:`repro.core.direct`): the zero-fill elimination structures
and the packed level schedule are computed once per pattern in ``build``,
and the numeric refactorization + two level-scheduled triangular sweeps are
traced-safe ``lax.scan`` kernels.

Plan protocol (used by :class:`repro.core.dispatch.SolverPlan`):

* :class:`PreconditionerPlan` — constructed once per sparsity pattern by the
  backend's ``analyze`` stage.  Everything that only depends on the *pattern*
  (diagonal-block membership, scatter indices, level sizes) is computed here,
  eagerly, with numpy when the pattern is concrete.
* ``PreconditionerPlan.refresh(A, matvec)`` — called by the ``setup(values)``
  stage with the current (possibly traced) values.  Only traced-safe jnp ops
  run here, so the same plan works under ``jit``/``grad``/``vmap`` and is
  shared by the forward and adjoint solves.

The legacy functional constructors (``jacobi``, ``block_jacobi``,
``chebyshev``) remain for direct use and are themselves traced-safe now.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "identity", "jacobi", "block_jacobi", "chebyshev",
    "PreconditionerPlan", "DistPreconditionerPlan", "make_preconditioner",
]

PRECONDITIONERS = ("none", "identity", "jacobi", "block_jacobi", "chebyshev",
                   "mg", "amg", "ilu")
DIST_PRECONDITIONERS = ("none", "identity", "jacobi", "schwarz", "schwarz2")


def identity():
    return lambda r: r


def jacobi(diag: jax.Array, eps: float = 1e-30):
    """M⁻¹ = D⁻¹ — the paper's default for the pytorch-native backend."""
    inv = jnp.where(jnp.abs(diag) > eps, 1.0 / diag, 1.0)
    return lambda r: inv * r


def _bj_indices(row, col, block: int):
    """(scatter target, in-diagonal-block mask) for COO entries — the
    pattern-only half of block-Jacobi.  Works on numpy or jnp index arrays."""
    rb = row // block
    same = rb == (col // block)
    flat = (rb * block + row % block) * block + col % block
    return jnp.where(same, flat, 0), same


def _bj_assemble(val, safe, same, nb: int, block: int):
    """Scatter diagonal-block entries into (nb, B, B) — traced-safe (the
    off-block entries scatter an explicit zero into slot 0)."""
    contrib = jnp.where(same, val, jnp.zeros_like(val))
    blocks = jnp.zeros((nb * block * block,), val.dtype).at[safe].add(contrib)
    blocks = blocks.reshape(nb, block, block)
    # regularize structurally-empty diagonal slots (padded tail rows)
    ar = jnp.arange(block)
    d = blocks[:, ar, ar]
    return blocks.at[:, ar, ar].set(jnp.where(jnp.abs(d) < 1e-12, 1.0, d))


def _bj_apply(inv, n: int, nb: int, block: int):
    def apply(rvec):
        rp = jnp.pad(rvec, (0, nb * block - n)).reshape(nb, block)
        out = jnp.einsum("bij,bj->bi", inv, rp).reshape(nb * block)
        return out[:n]
    return apply


def block_jacobi(val, row, col, n: int, block: int = 128):
    """Dense-block diagonal inverse.  Blocks are MXU-aligned (default 128):
    application is one batched matmul.  Beyond-paper: no TPU-hostile
    triangular solves, still much stronger than point Jacobi on PDE matrices.
    Traced-safe — works on tracer ``val`` inside jit/grad."""
    nb = -(-n // block)
    safe, same = _bj_indices(row, col, block)
    inv = jnp.linalg.inv(_bj_assemble(val, safe, same, nb, block))
    return _bj_apply(inv, n, nb, block)


def chebyshev(matvec: Callable, lam_min: float, lam_max: float, degree: int = 8,
              fused: bool = False, interpret: Optional[bool] = None):
    """Chebyshev-polynomial approximation of A⁻¹ on [lam_min, lam_max].

    Pure matvec recurrence — ideal for TPU and for the distributed backend
    (no extra reductions).  Beyond-paper addition.  With ``fused=True`` the
    inner d/x axpy pair runs as one Pallas pass per degree
    (:func:`repro.kernels.solve_step.fused_cheb_step`); the recurrence is
    unchanged."""
    theta = 0.5 * (lam_max + lam_min)
    delta = 0.5 * (lam_max - lam_min)
    sigma = theta / delta

    def apply(r):
        # 3-term Chebyshev smoother recurrence approximating x ≈ A⁻¹ r
        x = r / theta
        rk = r - matvec(x)
        rho_k = 1.0 / sigma
        dk = x
        if fused:
            from ..kernels import solve_step as _fk
        for _ in range(degree - 1):
            rho_k1 = 1.0 / (2.0 * sigma - rho_k)
            if fused:
                x, dk = _fk.fused_cheb_step(x, dk, rk, rho_k1 * rho_k,
                                            2.0 * rho_k1 / delta,
                                            interpret=interpret)
            else:
                dk = rho_k1 * rho_k * dk + (2.0 * rho_k1 / delta) * rk
                x = x + dk
            rk = rk - matvec(dk)
            rho_k = rho_k1
        return x

    return apply


def estimate_spectrum(matvec: Callable, n: int, dtype=jnp.float32,
                      steps: int = 16, seed: int = 0):
    """Lanczos-based extremal eigenvalue estimate for Chebyshev bounds.

    Traced-safe (pure jnp) — runs once per ``setup(values)``, not per solve.
    """
    from .solvers import lanczos
    v0 = jax.random.normal(jax.random.PRNGKey(seed), (n,), dtype)
    a, b_, _ = lanczos(matvec, v0, steps)
    T = jnp.diag(a) + jnp.diag(b_[:-1], 1) + jnp.diag(b_[:-1], -1)
    w = jnp.linalg.eigvalsh(T)
    return w[0], w[-1]


# ---------------------------------------------------------------------------
# plan protocol: build(pattern) eager / refresh(values) traced
# ---------------------------------------------------------------------------

class PreconditionerPlan:
    """Pattern-level preconditioner state, reusable across values refreshes.

    ``__init__`` is the eager ``build(pattern)`` stage: it validates the
    choice against the pattern and precomputes every values-independent
    artifact.  ``refresh`` is the traced ``setup(values)`` stage returning the
    apply closure consumed by the Krylov loops.
    """

    def __init__(self, name: Optional[str], row, col, shape, *,
                 stencil=None, block: int = 128, degree: int = 8):
        self.name = "none" if name in (None, "none", "identity") else name
        if self.name not in PRECONDITIONERS:
            raise ValueError(f"unknown preconditioner {name!r}")
        self.row, self.col = row, col
        self.shape = tuple(shape)
        self.stencil = stencil
        self.block = block
        self.degree = degree
        if self.name == "mg":
            if stencil is None:
                raise ValueError(
                    "precond='mg' needs a stencil-layout SparseTensor "
                    "(structured-grid operator)")
            if stencil.nx != stencil.ny:
                raise ValueError("precond='mg' requires a square grid")
        if self.name == "block_jacobi":
            # eager pattern part: diagonal-block membership + scatter targets
            self.nb = -(-self.shape[0] // block)
            try:
                r = np.asarray(row).astype(np.int64)
                c = np.asarray(col).astype(np.int64)
            except Exception:  # traced pattern — fall back to jnp in refresh
                self._bj_idx = None
            else:
                self._bj_idx = _bj_indices(r, c, block)
        if self.name == "ilu":
            # eager pattern part: the direct backend's symbolic stage in
            # zero-fill (ILU(0)) mode — structures + packed level schedule
            from . import direct as _direct
            try:
                r = np.asarray(row).astype(np.int64)
                c = np.asarray(col).astype(np.int64)
            except Exception:
                raise ValueError(
                    "precond='ilu' needs a concrete sparsity pattern "
                    "(symbolic analysis is eager)")
            self._ilu = _direct.symbolic_factor(r, c, self.shape[0],
                                                incomplete=True)
        if self.name == "amg":
            # eager pattern part: smoothed-aggregation coarsening + the
            # Galerkin index programs + the coarsest level's LDLᵀ/LU program
            # (core/multigrid.amg_symbolic) — once per pattern, cached here
            from . import multigrid as _mg
            try:
                r = np.asarray(row).astype(np.int64)
                c = np.asarray(col).astype(np.int64)
            except Exception:
                raise ValueError(
                    "precond='amg' needs a concrete sparsity pattern "
                    "(aggregation and the Galerkin programs are eager)")
            self._amg = _mg.amg_symbolic(r, c, self.shape[0])

    def fused_diag(self, A) -> Optional[jax.Array]:
        """Diagonal-inverse vector for the fused step kernels
        (:mod:`repro.kernels.solve_step`), or None when the apply is not a
        pure diagonal scale — the fused solvers then keep the ``refresh``
        closure outside the fused pass (partial fusion)."""
        if self.name == "none":
            return jnp.ones(self.shape[0], A.dtype)
        if self.name == "jacobi":
            d = A.diagonal()
            return jnp.where(jnp.abs(d) > 1e-30, 1.0 / d, 1.0)
        return None

    def refresh_state(self, A, matvec: Callable) -> tuple:
        """values-dependent stage, ARRAYS ONLY — traced-safe AND vmappable.

        Returns a pytree of arrays (no closures), so a whole stacked batch of
        shared-pattern matrices can run ``jax.vmap(refresh_state)`` through
        one trace — the engine half of the serving tentpole.  The apply
        closure is assembled from this state at solve time by
        :meth:`make_apply` (cheap, no array work)."""
        if self.name == "none":
            return ()
        if self.name == "jacobi":
            d = A.diagonal()
            return (jnp.where(jnp.abs(d) > 1e-30, 1.0 / d, 1.0),)
        if self.name == "block_jacobi":
            block = self.block
            if self._bj_idx is None:      # traced pattern: derive per refresh
                safe, same = _bj_indices(A.row, A.col, block)
            else:
                safe, same = self._bj_idx
            inv = jnp.linalg.inv(_bj_assemble(A.val, safe, same, self.nb, block))
            return (inv,)
        if self.name == "chebyshev":
            lmin, lmax = estimate_spectrum(matvec, self.shape[0], A.dtype)
            lmin = jnp.maximum(lmin, lmax * 1e-4)
            return (lmin, lmax)
        if self.name == "mg":
            from .multigrid import MultigridPreconditioner
            nx, ny = self.stencil.nx, self.stencil.ny
            v5 = A.val.reshape(5, nx, ny)
            return MultigridPreconditioner.from_planes(v5).state()
        if self.name == "ilu":
            from . import direct as _direct
            return (_direct.numeric_factor(self._ilu, A.val),)
        if self.name == "amg":
            from . import multigrid as _mg
            return _mg.amg_numeric(self._amg, A.val)  # traced-safe Galerkin
        raise ValueError(f"unknown preconditioner {self.name!r}")

    def make_apply(self, state, matvec: Callable, fused: bool = False,
                   interpret: Optional[bool] = None) -> Callable:
        """Apply closure over a :meth:`refresh_state` pytree (solve stage).

        Pure closure assembly — no array computation happens here, so it can
        run inside a per-instance ``vmap`` lane of a batched solve.  ``fused``
        routes multi-pass applies (Chebyshev) through the fused step kernels
        where they have one; it is a solve-time decision, never baked into
        the state."""
        if self.name == "none":
            return identity()
        if self.name == "jacobi":
            (inv,) = state
            return lambda r: inv * r
        if self.name == "block_jacobi":
            (inv,) = state
            return _bj_apply(inv, self.shape[0], self.nb, self.block)
        if self.name == "chebyshev":
            lmin, lmax = state
            return chebyshev(matvec, lmin, lmax, degree=self.degree,
                             fused=fused, interpret=interpret)
        if self.name == "mg":
            from .multigrid import MultigridPreconditioner
            return MultigridPreconditioner.from_state(state)
        if self.name == "ilu":
            from . import direct as _direct
            art = self._ilu
            (C,) = state
            return lambda r: _direct.factored_solve(art, C, r)
        if self.name == "amg":
            from . import multigrid as _mg
            return _mg.AMGPreconditioner(self._amg, state)
        raise ValueError(f"unknown preconditioner {self.name!r}")

    def refresh(self, A, matvec: Callable, fused: bool = False) -> Callable:
        """values-dependent stage — traced-safe; one call per solver setup.
        Composition of :meth:`refresh_state` + :meth:`make_apply`, kept for
        callers that want the one-shot closure."""
        return self.make_apply(self.refresh_state(A, matvec), matvec,
                               fused=fused)


class DistPreconditionerPlan:
    """Distributed preconditioner, split like :class:`PreconditionerPlan`:
    eager ``build(pattern)`` in ``__init__`` + traced ``refresh(values)``.

    Operates on the stacked ``(P, ·)`` storage of a ``DSparseTensor``.  The
    build stage only sees the pattern (stacked local row/col indices +
    ``DistMeta``) and precomputes every values-free artifact eagerly:

    * ``jacobi`` — nothing of its own: ``refresh`` reads each row's
      diagonal entries from the plan's row-slot table
      (:class:`~repro.core.distributed.EllLayout`, passed as ``ell``), a
      gather and a sum with no scatter.
    * ``schwarz`` — shard-local overlapping Schwarz: each shard's extended
      matrix ``A[ext, ext]`` (owned rows ∪ halo-overlap rows, Dirichlet
      truncation at the extended boundary — a principal submatrix, so SPD
      inputs stay SPD) is analyzed ONCE through the direct machinery's
      union-pattern ILU(0)/IC(0) program (:func:`repro.core.direct.
      schwarz_symbolic`); ``refresh`` is a vmapped numeric refactorization,
      and the per-iteration apply is gather-halos → local triangular sweeps →
      transposed-halo combine (Σ Rᵀ A_ext⁻¹ R — the additive-Schwarz sum).
    * ``schwarz2`` — the two-level variant: the one-level sum above PLUS an
      additive coarse correction ``T A_c⁻¹ Tᵀ r``.  The coarse level is the
      AMG machinery's tentative (piecewise-constant) aggregation of the
      GLOBAL pattern (:func:`repro.core.sparse.tentative_coarse_pattern`),
      its Galerkin matrix assembled by ONE segment-sum from the stacked
      values and factored through :func:`repro.core.direct.symbolic_factor`
      — a distributed direct coarse solve on cached factors.  The
      per-iteration apply is all_gather residual → aggregate → coarse
      triangular sweeps → scatter correction, all through frozen index maps
      (nothing queries the axis environment at trace time).

    ``refresh(lval)`` returns a tuple of state arrays — stacked ``(P, ·)``
    leaves sharded over the mesh axis, plus replicated leaves (the coarse
    factor) flagged by :meth:`state_sharded` — that the solve stage ships
    through ``shard_map``; ``local_closure`` turns the per-shard slice of
    that state into the apply closure used inside the Krylov loop.  Halo
    application is injected by the caller (``halo_fwd``/``halo_bwd``) so
    this module stays mesh-agnostic.
    """

    def __init__(self, name: Optional[str], lrow, lcol, meta, *,
                 bounds=None, ell=None, coarsest: int = 160):
        self.name = "none" if name in (None, "none", "identity") else name
        if self.name not in DIST_PRECONDITIONERS:
            raise ValueError(
                f"unknown distributed preconditioner {name!r} "
                f"(supported: {DIST_PRECONDITIONERS})")
        self.meta = meta
        lr = np.asarray(lrow)
        lc = np.asarray(lcol)
        p, nnz_loc = lr.shape
        if self.name == "jacobi":
            if ell is None:
                raise ValueError("jacobi build needs the plan's EllLayout")
            self._ell = ell
        if self.name in ("schwarz", "schwarz2"):
            from . import direct as _direct
            from .distributed import global_entries
            if bounds is None:
                raise ValueError("schwarz build needs partition bounds")
            h_lo, h_hi, n_loc = meta.h_lo, meta.h_hi, meta.n_loc
            n_ext = h_lo + n_loc + h_hi
            # global entry list (shard-major) + each entry's flat value slot
            row_g, col_g, fa = global_entries(lr, lc, meta, bounds)
            # each shard's extended window [bounds[q]-h_lo, bounds[q+1]+h_hi)
            # in local extended coordinates — overlap rows included, entries
            # leaving the window dropped (Dirichlet truncation)
            entries = []
            for q in range(p):
                lo = bounds[q] - h_lo
                hi = bounds[q] + n_loc + h_hi     # uniform n_ext window
                m = ((row_g >= lo) & (row_g < hi) &
                     (col_g >= lo) & (col_g < hi))
                entries.append((row_g[m] - lo, col_g[m] - lo, fa[m]))
            self._schwarz = _direct.schwarz_symbolic(
                entries, n_ext, n_src=p * nnz_loc)
        if self.name == "schwarz2":
            from . import direct as _direct
            from .sparse import tentative_coarse_pattern
            agg, n_c, e2c, crow, ccol = tentative_coarse_pattern(
                row_g, col_g, meta.n, coarsest=coarsest)
            self._coarse_art = _direct.symbolic_factor(crow, ccol, n_c)
            self._n_c = n_c
            self._c_nnz = len(crow)
            # value-assembly program: c_val = Σ flat[fa] into coarse slots
            self._c_fa = jnp.asarray(fa, jnp.int32)
            self._c_e2c = jnp.asarray(e2c, jnp.int32)
            # owned-row → coarse-node map, padded tail rows → dump slot n_c
            own = np.full((p, n_loc), n_c, np.int64)
            for q in range(p):
                cnt = int(bounds[q + 1] - bounds[q])
                own[q, :cnt] = agg[bounds[q]:bounds[q + 1]]
            self._own2coarse = jnp.asarray(own, jnp.int32)

    def state_sharded(self) -> tuple:
        """Per-leaf sharding of :meth:`refresh`'s output: True → stacked
        ``(P, ·)`` sharded over the mesh axis, False → replicated (the
        two-level coarse factor, identical on every shard)."""
        if self.name == "none":
            return ()
        if self.name == "schwarz2":
            return (True, False)
        return (True,)

    def refresh(self, lval) -> tuple:
        """values-dependent stage — traced-safe; returns stacked state."""
        if self.name == "none":
            return ()
        if self.name == "jacobi":
            from .distributed import ell_values
            own = jnp.arange(self.meta.n_loc, dtype=jnp.int32) + self.meta.h_lo

            def one(v, src, col):
                ve = ell_values(v, src)
                d = jnp.sum(jnp.where(col == own, ve, jnp.zeros_like(ve)), 0)
                return jnp.where(jnp.abs(d) > 1e-30, 1.0 / d, 1.0)

            return (jax.vmap(one)(lval, self._ell.src, self._ell.col),)
        if self.name in ("schwarz", "schwarz2"):
            from . import direct as _direct
            C = _direct.schwarz_numeric(self._schwarz, lval.reshape(-1))
            if self.name == "schwarz":
                return (C,)
            # coarse Galerkin values Tᵀ A T: every tentative-prolongator
            # entry is 1, so the triple product is ONE segment-sum of the
            # flat values through the frozen entry→coarse-slot map
            c_val = jax.ops.segment_sum(lval.reshape(-1)[self._c_fa],
                                        self._c_e2c,
                                        num_segments=self._c_nnz)
            Cc = _direct.numeric_factor(self._coarse_art, c_val)
            return (C, Cc)
        raise ValueError(f"unknown distributed preconditioner {self.name!r}")

    def local_closure(self, state_q, halo_fwd: Callable,
                      halo_bwd: Callable,
                      matvec: Optional[Callable] = None) -> Callable:
        """Per-shard apply closure (inside ``shard_map``; state pre-sliced).
        ``matvec`` (the shard-local halo'd SpMV) is only required by the
        two-level mode's deflation products."""
        if self.name == "none":
            return identity()
        if self.name == "jacobi":
            (inv,) = state_q
            return lambda r: inv * r
        if self.name in ("schwarz", "schwarz2"):
            from . import direct as _direct
            from jax import lax
            C = state_q[0]
            art = self._schwarz.art

            def apply(r):
                r_ext = halo_fwd(r)
                z_ext = _direct.factored_solve(art, C, r_ext)
                return halo_bwd(z_ext)     # Σ Rᵀ A_ext⁻¹ R: overlap summed

            if self.name == "schwarz":
                return apply

            if matvec is None:
                raise ValueError("schwarz2 needs the shard-local matvec")
            Cc = state_q[1]
            c_art = self._coarse_art
            own = self._own2coarse
            n_c = self._n_c
            axis = self.meta.axis

            def coarse(r):
                # Q r = T A_c⁻¹ Tᵀ r: gather the global residual (frozen
                # axis name; all_gather orders shards by axis index),
                # aggregate, solve on the cached coarse factors, scatter
                r_all = lax.all_gather(r, axis)          # (P, n_loc)
                rc = jax.ops.segment_sum(
                    r_all.reshape(-1), own.reshape(-1),
                    num_segments=n_c + 1)[:n_c]
                zc = _direct.factored_solve(c_art, Cc, rc)
                zc_pad = jnp.concatenate([zc, jnp.zeros((1,), zc.dtype)])
                return zc_pad[own[lax.axis_index(axis)]]

            def apply2(r):
                # symmetric deflated two-level (BNN/ADEF-2 form):
                #   M = Q + (I − Q A) M_AS (I − A Q)
                # — the coarse space is solved exactly and REMOVED from the
                # Schwarz sweep's workload instead of added on top (a purely
                # additive T A_c⁻¹ Tᵀ term double-counts the low modes the
                # exact subdomain solves already resolve)
                zc = coarse(r)
                w = apply(r - matvec(zc))
                return zc + w - coarse(matvec(w))

            return apply2
        raise ValueError(f"unknown distributed preconditioner {self.name!r}")


def make_preconditioner(name: str, A, matvec: Callable):
    """One-shot factory: build(pattern) + refresh(values) in one call.

    Name ∈ {none, jacobi, block_jacobi, chebyshev, mg, ilu}.  Prefer going through
    a :class:`~repro.core.dispatch.SolverPlan` so the build stage is cached.
    """
    plan = PreconditionerPlan(name, A.row, A.col, A.shape, stencil=A.stencil)
    return plan.refresh(A, matvec)
