"""Multigrid preconditioners — one level-hierarchy abstraction, two builders.

The paper's stated limitation (§5): the pytorch-native backend supports only
Jacobi preconditioning, "insufficient at large DOF — hence the 1e-2
residuals in our multi-GPU runs"; AMG (AmgX/hypre) is named as future work.
This module closes that gap twice over:

* **Geometric** (``precond="mg"``, stencil operators), matrix-free, with
  one builder per stencil layout.  On 2-D 5-point planes: weighted-Jacobi
  smoothing, full-weighting restriction of both residual and coefficient
  field, bilinear prolongation, dense coarse solve — shifts, pooling and
  small matmuls only.  On 3-D 27-point planes: HPCG's ``ComputeMG`` —
  four levels rediscretised as HPCG's ``GenerateCoarseProblem`` does,
  injection transfers, and one 8-colour symmetric Gauss–Seidel step before
  and after each coarse correction (Pallas kernels,
  :mod:`repro.kernels.stencil27`).

* **Algebraic** (``precond="amg"``, any COO pattern): smoothed-aggregation
  AMG as a first-class citizen of the plan engine.  The *analyze* half
  (:func:`amg_symbolic` — eager, numpy, values-free, cached on the
  ``SolverPlan``) runs greedy aggregation over the sparsity pattern
  (:func:`repro.core.sparse.aggregate_pattern`), freezes the smoothed-
  prolongator fill pattern, and packs the Galerkin triple product R·A·P into
  static gather/segment-sum index programs
  (:func:`repro.core.sparse.spgemm_program` — the same discipline as
  ``core/direct.py``'s step programs); the coarsest level gets a cached
  LDLᵀ/LU program from :func:`repro.core.direct.symbolic_factor`.  The
  *setup* half (:func:`amg_numeric` — traced-safe) evaluates filtered-matrix
  weights, prolongator smoothing and the triple product through those
  programs, so it jits/vmaps and is memoized per values array by the plan's
  setup stage (``PLAN_STATS["coarsen"]``/``["galerkin"]`` count the two
  halves).

Both builders produce a tuple of :class:`Level` closures consumed by the
shared :func:`v_cycle` driver, so the solve stage is one code path.
"""
from __future__ import annotations

import functools
from typing import Callable, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..data.poisson import vc_coefficients
from ..kernels.ref import stencil5_ref
from ..kernels.stencil27 import OFFSETS, Stencil27Meta, inside
from .sparse import aggregate_pattern, coo_matvec, spgemm_program
from .spans import count, span


# ---------------------------------------------------------------------------
# the shared hierarchy abstraction: Level closures + one V-cycle driver
# ---------------------------------------------------------------------------

class Level(NamedTuple):
    """One level of a multigrid hierarchy, as closures over the (possibly
    traced) numeric state.  The coarsest level only needs ``coarse_solve``;
    every other level supplies the smoother/transfer quadruple.
    ``post_smooth`` defaults to ``smooth`` when None."""
    matvec: Callable            # x -> A_l @ x
    smooth: Callable            # (x, b) -> relaxed x (pre-smoother)
    restrict: Optional[Callable] = None    # r_l -> r_{l+1}
    prolong: Optional[Callable] = None     # e_{l+1} -> e_l
    coarse_solve: Optional[Callable] = None  # b -> A_l^{-1} b (last level)
    post_smooth: Optional[Callable] = None


def v_cycle(levels: Tuple[Level, ...], b, level: int = 0):
    """One V(pre, post)-cycle over ``levels`` — the recursion is Python
    (static level count), every op inside is traced-safe."""
    lv = levels[level]
    if lv.coarse_solve is not None:
        with jax.named_scope("mg.coarse"):
            return lv.coarse_solve(b)
    with jax.named_scope("mg.smooth"):
        x = lv.smooth(jnp.zeros_like(b), b)
    with jax.named_scope("mg.restrict"):
        rc = lv.restrict(b - lv.matvec(x))
    ec = v_cycle(levels, rc, level + 1)
    with jax.named_scope("mg.prolong"):
        x = x + lv.prolong(ec)
    with jax.named_scope("mg.smooth"):
        return (lv.post_smooth or lv.smooth)(x, b)


# ---------------------------------------------------------------------------
# geometric builder (structured 5-point stencil planes)
# ---------------------------------------------------------------------------

def _smooth(v5, x, b, omega: float = 0.8, iters: int = 2):
    """Weighted-Jacobi smoothing on the 5-point stencil planes."""
    diag = v5[0]
    inv = jnp.where(jnp.abs(diag) > 1e-30, omega / diag, 0.0)
    for _ in range(iters):
        r = b - stencil5_ref(v5, x)
        x = x + inv * r
    return x


_LANE_BLOCK = 256


def _halve_lanes(x, w_even: float, w_odd: float):
    """Halve the minor axis: ``w_even·x[..., 2j] + w_odd·x[..., 2j+1]``.

    A stride-2 reshape or slice of the minor axis is a relayout on the TPU
    (the minor 2 pads to 128 lanes, or the slice gathers across them), so
    each block of ``k`` lanes is multiplied by a static (k, k/2) matrix
    holding the two weights instead: ``k = 256`` where the width divides
    by it, else the whole width.  The dot runs at ``HIGHEST`` precision,
    since the TPU's default f32 dot rounds its operands to bfloat16; with
    weights 1 and 0 the result is then the even lanes exactly."""
    n = x.shape[-1]
    k = _LANE_BLOCK if n % _LANE_BLOCK == 0 else n
    lane = (jax.lax.broadcasted_iota(jnp.int32, (k, k // 2), 0)
            - 2 * jax.lax.broadcasted_iota(jnp.int32, (k, k // 2), 1))
    w = jnp.where(lane == 0, w_even,
                  jnp.where(lane == 1, w_odd, 0.0)).astype(x.dtype)
    y = jnp.einsum("...bk,kj->...bj", x.reshape(*x.shape[:-1], n // k, k), w,
                   precision=jax.lax.Precision.HIGHEST)
    return y.reshape(*x.shape[:-1], n // 2)


def _restrict(r):
    """Full-weighting 2×2 restriction (cell-centered): row pairs summed as
    the two lane halves of ``(ng/2, 2·ng)``, then lane pairs averaged."""
    ng = r.shape[0]
    s = r.reshape(ng // 2, 2 * ng)
    return _halve_lanes(s[:, :ng] + s[:, ng:], 0.25, 0.25)


def _prolong(e):
    """Piecewise-constant/bilinear-ish prolongation (transpose of restrict)."""
    return jnp.repeat(jnp.repeat(e, 2, axis=0), 2, axis=1)


def _build_levels(kappa: jax.Array, coarsest: int,
                  fine_planes: Optional[jax.Array] = None
                  ) -> Tuple[List[jax.Array], List[int]]:
    """Level hierarchy by 2×2-averaging κ (rediscretization coarsening).

    ``fine_planes``, when given, is used verbatim as the finest operator (so
    the smoother sees the *actual* assembled matrix, not a rediscretization);
    coarser levels always come from ``vc_coefficients`` of the restricted κ.
    All ops are traced-safe; only level *sizes* (static, from shapes) steer
    the Python loop.
    """
    levels: List[jax.Array] = []
    sizes: List[int] = []
    ng = kappa.shape[0]
    k = kappa

    def level_op(k, ng):
        if fine_planes is not None and not levels:
            return fine_planes
        return vc_coefficients(k).reshape(5, ng, ng)

    while ng >= coarsest and ng % 2 == 0:
        levels.append(level_op(k, ng))
        sizes.append(ng)
        k = _restrict(k)
        ng //= 2
    levels.append(level_op(k, ng))
    sizes.append(ng)
    return levels, sizes


class MultigridPreconditioner:
    """One V-cycle per application, built from a κ field (paper §4.4 operator).

    Levels are built eagerly by 2×2-averaging κ (rediscretization
    coarsening); the coarsest level solves densely.  All per-level operators
    are the same signed (5, n, n) planes the stencil kernel consumes; the
    cycle itself runs through the shared :func:`v_cycle` driver.
    """

    def __init__(self, kappa: Optional[jax.Array] = None, *,
                 coarsest: int = 16, pre_smooth: int = 2,
                 post_smooth: int = 2, omega: float = 0.8,
                 _levels: Optional[List[jax.Array]] = None,
                 _sizes: Optional[List[int]] = None):
        self.pre, self.post, self.omega = pre_smooth, post_smooth, omega
        if _levels is None:
            _levels, _sizes = _build_levels(kappa, coarsest)
        self.levels, self.sizes = _levels, _sizes
        # dense coarse operator (assembled once per setup; traced-safe)
        ng = self.sizes[-1]
        nc = ng * ng
        eye = jnp.eye(nc).reshape(nc, ng, ng)
        Ac = jax.vmap(lambda col: stencil5_ref(self.levels[-1], col))(eye)
        self.A_coarse = Ac.reshape(nc, nc).T
        # h-scaling between levels: rediscretized coarse operator acts on a
        # 2×-coarser grid — the restricted residual needs a 4× factor to
        # keep the two-grid correction consistent (h² scaling of the stencil)
        self.scale = 4.0
        self._hier = self._build_hierarchy()

    @classmethod
    def from_planes(cls, v5: jax.Array, *, coarsest: int = 16,
                    **kw) -> "MultigridPreconditioner":
        """Build from assembled (5, ng, ng) stencil planes (traced-safe).

        Recovers a κ proxy from the centre plane (C = ΣkN,kS,kW,kE ≈ 4κ for
        the variable-coefficient Poisson family), keeps the given planes as
        the finest operator, and rediscretizes the restricted proxy below.
        This is the ``precond="mg"`` entry point of the plan factory.
        """
        if v5.ndim != 3 or v5.shape[0] != 5 or v5.shape[1] != v5.shape[2]:
            raise ValueError(f"from_planes expects (5, ng, ng), got {v5.shape}")
        kappa_proxy = v5[0] / 4.0
        levels, sizes = _build_levels(kappa_proxy, coarsest, fine_planes=v5)
        return cls(_levels=levels, _sizes=sizes, **kw)

    def _build_hierarchy(self) -> Tuple[Level, ...]:
        out = []
        last = len(self.levels) - 1
        for l, v5 in enumerate(self.levels):
            if l == last:
                ng = self.sizes[l]
                nc = ng * ng
                out.append(Level(
                    matvec=functools.partial(stencil5_ref, v5),
                    smooth=lambda x, b: x,
                    coarse_solve=lambda b, A=self.A_coarse, ng=ng, nc=nc:
                        jnp.linalg.solve(A, b.reshape(nc)).reshape(b.shape)))
            else:
                out.append(Level(
                    matvec=functools.partial(stencil5_ref, v5),
                    smooth=lambda x, b, v5=v5, it=self.pre:
                        _smooth(v5, x, b, self.omega, it),
                    restrict=lambda r: _restrict(r) * self.scale,
                    prolong=_prolong,
                    post_smooth=lambda x, b, v5=v5, it=self.post:
                        _smooth(v5, x, b, self.omega, it)))
        return tuple(out)

    def state(self) -> tuple:
        """Array-only pytree of the hierarchy: per-level stencil planes plus
        the dense coarse operator.  Everything static (grid sizes, smoother
        counts) is shape metadata or defaults, so a stacked batch of these
        states vmaps cleanly; :meth:`from_state` rehydrates per lane."""
        return (tuple(self.levels), self.A_coarse)

    @classmethod
    def from_state(cls, state: tuple, *, pre_smooth: int = 2,
                   post_smooth: int = 2,
                   omega: float = 0.8) -> "MultigridPreconditioner":
        """Rebuild the apply from a :meth:`state` pytree — closure assembly
        only, no array work (the coarse operator rides along in the state),
        so it is safe inside a ``vmap`` lane of a batched solve."""
        levels, A_coarse = state
        mg = cls.__new__(cls)
        mg.pre, mg.post, mg.omega = pre_smooth, post_smooth, omega
        mg.levels = list(levels)
        mg.sizes = [int(v5.shape[1]) for v5 in levels]
        mg.A_coarse = A_coarse
        mg.scale = 4.0
        mg._hier = mg._build_hierarchy()
        return mg

    def __call__(self, r: jax.Array) -> jax.Array:
        ng = self.sizes[0]
        return v_cycle(self._hier, r.reshape(ng, ng)).reshape(-1)


def make_mg_preconditioner(kappa: jax.Array, **kw):
    """Factory matching the core.precond interface."""
    mg = MultigridPreconditioner(kappa, **kw)
    return lambda r: mg(r)


# ---------------------------------------------------------------------------
# geometric builder, 3-D: HPCG's ComputeMG on 27-point stencil planes
# ---------------------------------------------------------------------------

HPCG_LEVELS = 4          # HPCG 3.1: three coarsenings by 2 in each dimension


def check_grid(meta) -> None:
    """Refuse a stencil layout the geometric builder cannot coarsen: the
    2-D builder needs square planes, the 3-D one dimensions divisible by
    ``2 ** (HPCG_LEVELS - 1)``."""
    if isinstance(meta, Stencil27Meta):
        step = 2 ** (HPCG_LEVELS - 1)
        if any(d % step for d in meta.shape):
            raise ValueError(
                f"precond='mg' on a 3-D stencil needs every dimension "
                f"divisible by {step}, got nx, ny, nz = "
                f"{meta.nx}, {meta.ny}, {meta.nz}")
    elif meta.nx != meta.ny:
        raise ValueError(f"precond='mg' on a 2-D stencil needs square "
                         f"planes, got {meta.nx} x {meta.ny}")


def hpcg_metas(meta: Stencil27Meta) -> Tuple[Stencil27Meta, ...]:
    """The grids of HPCG's hierarchy, finest first."""
    metas = [meta]
    for _ in range(HPCG_LEVELS - 1):
        metas.append(metas[-1].coarse())
    return tuple(metas)


def hpcg_coarse_planes(coarse: Stencil27Meta, planes: jax.Array) -> jax.Array:
    """HPCG's ``GenerateCoarseProblem`` from the fine planes: each coarse
    cell (i, j, k) takes the row of the fine cell (2i, 2j, 2k), and the
    couplings that leave the coarse grid are zeroed (traced-safe)."""
    sub = planes[:, ::2, ::2, ::2]
    return jnp.stack([jnp.where(inside(coarse, *d), sub[p], 0.0)
                      for p, d in enumerate(OFFSETS)])


def hpcg_state(meta: Stencil27Meta, planes: jax.Array) -> tuple:
    """Array-only hierarchy: the planes of every level, finest first (the
    given ``planes`` are level 0, shared, not copied)."""
    with span("setup.mg3d"):
        levels = [planes]
        for m in hpcg_metas(meta)[1:]:
            levels.append(hpcg_coarse_planes(m, levels[-1]))
        count("mg3d_build")
        count("mg3d_levels", len(levels))
        return tuple(levels)


def _inject(r):
    """Injection restriction: the fine values at (2i, 2j, 2k), the planes
    and rows taken by stride on the major and sublane axes, the lanes by
    :func:`_halve_lanes`."""
    return _halve_lanes(r[::2, ::2], 1.0, 0.0)


def _inject_add(e):
    """Transpose of :func:`_inject`: ``e`` at the even fine points, zero
    between (the V-cycle adds it to x)."""
    return jax.lax.pad(e, jnp.zeros((), e.dtype), [(0, 1, 1)] * 3)


def hpcg_hierarchy(meta: Stencil27Meta, state: tuple,
                   interpret: Optional[bool] = None) -> Tuple[Level, ...]:
    """:class:`Level` closures of HPCG's V-cycle over the planes of
    :func:`hpcg_state`: one symmetric Gauss–Seidel (8-colour) before and
    one after the coarse correction on every level, injection transfers,
    and one SymGS from zero as the coarsest level's solve.  Level ``l``'s
    launches are named ``stencil27_l<l>`` and ``symgs27_l<l>``."""
    from ..kernels.stencil27 import stencil27_pallas, symgs27_pallas
    levels = []
    last = len(state) - 1
    for l, (m, planes) in enumerate(zip(hpcg_metas(meta), state)):
        mv = functools.partial(stencil27_pallas, m, planes,
                               name=f"stencil27_l{l}", interpret=interpret)
        gs = functools.partial(symgs27_pallas, m, planes,
                               name=f"symgs27_l{l}", interpret=interpret)
        if l == last:
            levels.append(Level(matvec=mv, smooth=gs,
                                coarse_solve=lambda b, gs=gs:
                                    gs(jnp.zeros_like(b), b)))
        else:
            levels.append(Level(matvec=mv, smooth=gs, restrict=_inject,
                                prolong=_inject_add))
    return tuple(levels)


def hpcg_preconditioner(meta: Stencil27Meta, state: tuple,
                        interpret: Optional[bool] = None) -> Callable:
    """One HPCG V-cycle per application, on flat vectors."""
    levels = hpcg_hierarchy(meta, state, interpret)
    return lambda r: v_cycle(levels, r.reshape(meta.shape)).reshape(-1)


# ---------------------------------------------------------------------------
# algebraic builder — smoothed-aggregation AMG in the plan engine
# ---------------------------------------------------------------------------

class AMGLevelSymbolic(NamedTuple):
    """Pattern-only artifacts of one AMG level (products of ``analyze``).

    ``a2p`` scatters every A entry into its smoothed-prolongator slot
    (entry (i,j) → P slot (i, agg[j]), always structurally present); the
    ``g1_*``/``g2_*`` arrays are the two :func:`spgemm_program` halves of the
    Galerkin triple product Pᵀ·(A·P), so the numeric setup is two gathers +
    two segment-sums per level — no dynamic sparse-sparse matmul ever runs.
    """
    n: int                       # fine size of this level
    n_c: int                     # coarse size (number of aggregates)
    arow: jax.Array              # this level's pattern (level 0 = input A)
    acol: jax.Array
    diag_mask: jax.Array         # (nnz,) bool — diagonal entries of A_l
    agg: jax.Array               # (n,) aggregate id per fine node
    p_row: jax.Array             # smoothed-prolongator pattern
    p_col: jax.Array
    a2p: jax.Array               # (nnz,) A entry → P slot
    tent: jax.Array              # (nnzP,) 1.0 on tentative slots (i, agg[i])
    g1_a: jax.Array              # A·P product program
    g1_p: jax.Array
    g1_dst: jax.Array
    nnz_ap: int
    g2_p: jax.Array              # Pᵀ·(A·P) product program
    g2_ap: jax.Array
    g2_dst: jax.Array
    nnz_c: int


class AMGArtifacts(NamedTuple):
    """Product of :func:`amg_symbolic` — the pattern-time half of the AMG
    plan, shared by every ``with_values`` refresh and the adjoint."""
    levels: Tuple[AMGLevelSymbolic, ...]
    coarse: "object"             # DirectArtifacts of the coarsest level
    n_coarse: int
    theta: float
    omega: float
    smooth_omega: float
    pre: int
    post: int
    stats: dict


def amg_symbolic(row, col, n: int, *, theta: float = 0.08,
                 omega: float = 2.0 / 3.0, smooth_omega: float = 2.0 / 3.0,
                 coarsest: int = 64, max_levels: int = 12,
                 pre_smooth: int = 1, post_smooth: int = 1) -> AMGArtifacts:
    """Analyze one sparsity pattern for smoothed-aggregation AMG (eager).

    Values-free by contract (plans outlive any single trace): aggregation,
    the smoothed-prolongator fill pattern and both Galerkin product programs
    depend only on the graph.  ``theta`` (strength threshold) and ``omega``
    (prolongator-smoothing damping) are *numeric* knobs consumed later by
    :func:`amg_numeric`.  The coarsest level's pattern goes through
    :func:`repro.core.direct.symbolic_factor`, so the V-cycle bottoms out in
    the cached-LDLᵀ machinery instead of a dense solve.
    """
    from . import direct as _direct
    with span("amg.coarsen"), jax.ensure_compile_time_eval():
        r = np.asarray(row, np.int64)
        c = np.asarray(col, np.int64)
        levels: List[AMGLevelSymbolic] = []
        n_l = n
        for _ in range(max_levels):
            if n_l <= coarsest:
                break
            agg, n_c = aggregate_pattern(r, c, n_l)
            if n_c >= n_l:                   # aggregation stalled — stop
                break
            # smoothed-prolongator pattern: P = (I − ω D⁻¹ Ā) T has slots
            # {(i, agg[j]) : (i,j) ∈ A} ∪ {(i, agg[i])}
            pkeys = np.unique(np.concatenate(
                [r * np.int64(n_c) + agg[c],
                 np.arange(n_l, dtype=np.int64) * np.int64(n_c) + agg]))
            p_row = (pkeys // n_c).astype(np.int64)
            p_col = (pkeys % n_c).astype(np.int64)
            a2p = np.searchsorted(pkeys, r * np.int64(n_c) + agg[c])
            tent = (p_col == agg[p_row]).astype(np.float64)
            # Galerkin R·A·P as two static spgemm programs: AP = A·P, then
            # A_c = Pᵀ·AP (R = Pᵀ — symmetric-pattern Galerkin)
            g1_a, g1_p, g1_dst, ap_row, ap_col = spgemm_program(
                r, c, p_row, p_col, (n_l, n_c))
            g2_p, g2_ap, g2_dst, c_row, c_col = spgemm_program(
                p_col, p_row, ap_row, ap_col, (n_c, n_c))
            levels.append(AMGLevelSymbolic(
                n=n_l, n_c=n_c,
                arow=jnp.asarray(r, jnp.int32), acol=jnp.asarray(c, jnp.int32),
                diag_mask=jnp.asarray(r == c),
                agg=jnp.asarray(agg, jnp.int32),
                p_row=jnp.asarray(p_row, jnp.int32),
                p_col=jnp.asarray(p_col, jnp.int32),
                a2p=jnp.asarray(a2p, jnp.int32),
                tent=jnp.asarray(tent),
                g1_a=jnp.asarray(g1_a, jnp.int32),
                g1_p=jnp.asarray(g1_p, jnp.int32),
                g1_dst=jnp.asarray(g1_dst, jnp.int32), nnz_ap=len(ap_row),
                g2_p=jnp.asarray(g2_p, jnp.int32),
                g2_ap=jnp.asarray(g2_ap, jnp.int32),
                g2_dst=jnp.asarray(g2_dst, jnp.int32), nnz_c=len(c_row)))
            r, c, n_l = c_row, c_col, n_c
        coarse = _direct.symbolic_factor(r, c, n_l)
        count("coarsen")
        stats = {"n_levels": len(levels) + 1, "n_coarse": n_l,
                 "sizes": [lv.n for lv in levels] + [n_l]}
        return AMGArtifacts(levels=tuple(levels), coarse=coarse, n_coarse=n_l,
                            theta=theta, omega=omega,
                            smooth_omega=smooth_omega,
                            pre=pre_smooth, post=post_smooth, stats=stats)


def _amg_level_numeric(lev: AMGLevelSymbolic, aval, theta: float,
                       omega: float):
    """One level of the numeric setup (traced-safe): filtered-matrix weights,
    prolongator smoothing, Galerkin triple product through the index
    programs.  Returns ``(dinv, p_val, c_val)``."""
    d = jax.ops.segment_sum(jnp.where(lev.diag_mask, aval, 0.0),
                            lev.arow, num_segments=lev.n)
    # strength filtering: keep |a_ij| ≥ θ √|a_ii a_jj|, lump dropped mass
    # into the diagonal (Vaněk's filtered matrix Ā) — numeric, not symbolic,
    # so the SAME pattern program serves every values refresh
    offd = lev.arow != lev.acol
    strong = jnp.abs(aval) >= theta * jnp.sqrt(
        jnp.abs(d[lev.arow] * d[lev.acol]) + 1e-300)
    keep = (~offd) | strong
    a_f = jnp.where(keep, aval, 0.0)
    lump = jax.ops.segment_sum(jnp.where(keep, 0.0, aval), lev.arow,
                               num_segments=lev.n)
    d_f = d - lump
    dinv_f = jnp.where(jnp.abs(d_f) > 1e-30, 1.0 / d_f, 0.0)
    # P = (I − ω D̄⁻¹ Ā) T: scatter Ā through a2p, subtract the lumped mass
    # at the tentative slot (it is Ā's diagonal adjustment), add T
    p_sum = jax.ops.segment_sum(a_f, lev.a2p, num_segments=len(lev.p_row))
    p_sum = p_sum - lev.tent * lump[lev.p_row]
    p_val = lev.tent.astype(aval.dtype) - omega * dinv_f[lev.p_row] * p_sum
    # Galerkin A_c = Pᵀ (A P) — two gathers + two segment-sums, UNfiltered A
    ap = jax.ops.segment_sum(aval[lev.g1_a] * p_val[lev.g1_p], lev.g1_dst,
                             num_segments=lev.nnz_ap)
    c_val = jax.ops.segment_sum(p_val[lev.g2_p] * ap[lev.g2_ap], lev.g2_dst,
                                num_segments=lev.nnz_c)
    dinv = jnp.where(jnp.abs(d) > 1e-30, 1.0 / d, 0.0)
    return dinv, p_val, c_val


def amg_numeric(art: AMGArtifacts, val: jax.Array):
    """The jit/vmap-safe numeric half of the AMG plan (the ``setup`` stage):
    per-level smoothing weights + prolongator values + Galerkin coarse
    values, and the coarsest level's numeric LDLᵀ/LU refactorization.
    Memoized per values array by ``SolverPlan.setup``."""
    from . import direct as _direct
    count("galerkin")
    state = []
    aval = val
    for lev in art.levels:
        dinv, p_val, c_val = _amg_level_numeric(lev, aval, art.theta,
                                                art.omega)
        state.append((aval, dinv, p_val))
        aval = c_val
    C = _direct.numeric_factor(art.coarse, aval)
    return tuple(state), C


def amg_hierarchy(art: AMGArtifacts, state) -> Tuple[Level, ...]:
    """Assemble the shared-driver :class:`Level` tuple from symbolic
    artifacts + numeric state — flat-vector transfers via the prolongator
    COO pattern (restrict = Pᵀ r, prolong = P e)."""
    from . import direct as _direct
    per_level, C = state
    levels = []
    for lev, (aval, dinv, p_val) in zip(art.levels, per_level):
        mv = functools.partial(coo_matvec, aval, lev.arow, lev.acol,
                               n_rows=lev.n)

        def make_smooth(mv, dinv, it, om=art.smooth_omega):
            def smooth(x, b):
                for _ in range(it):
                    x = x + om * dinv * (b - mv(x))
                return x
            return smooth

        levels.append(Level(
            matvec=mv,
            smooth=make_smooth(mv, dinv, art.pre),
            restrict=lambda r, lev=lev, p_val=p_val:
                jax.ops.segment_sum(p_val * r[lev.p_row], lev.p_col,
                                    num_segments=lev.n_c),
            prolong=lambda e, lev=lev, p_val=p_val:
                jax.ops.segment_sum(p_val * e[lev.p_col], lev.p_row,
                                    num_segments=lev.n),
            post_smooth=make_smooth(mv, dinv, art.post)))
    levels.append(Level(
        matvec=lambda x: x,
        smooth=lambda x, b: x,
        coarse_solve=lambda b: _direct.factored_solve(art.coarse, C, b)))
    return tuple(levels)


class AMGPreconditioner:
    """Apply closure for ``precond="amg"``: one V-cycle per application over
    the plan's frozen hierarchy.  Built by ``PreconditionerPlan.refresh``
    from (symbolic artifacts, numeric state)."""

    def __init__(self, art: AMGArtifacts, state):
        self.art = art
        self.levels = amg_hierarchy(art, state)

    def __call__(self, r: jax.Array) -> jax.Array:
        return v_cycle(self.levels, r)
