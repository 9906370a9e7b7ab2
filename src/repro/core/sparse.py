"""Typed sparse tensors (paper §3.1).

``SparseTensor``      — one matrix, or a batch sharing one sparsity pattern (COO).
``SparseTensorList``  — a batch with *distinct* patterns (ragged dispatch).

Distributed variants (``DSparseTensor``) live in :mod:`repro.core.distributed`.

The COO triplet ``(val, row, col)`` is the canonical storage; auxiliary
TPU-friendly forms (block-ELL for the Pallas SpMV kernel, structured-stencil
metadata) are attached at construction time when the pattern allows it.
``val`` may carry leading batch dimensions — the pattern is shared across the
batch and a single symbolic setup (BELL layout / dispatch decision) is reused,
mirroring torch-sla's shared-pattern batching.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "SparseTensor",
    "SparseTensorList",
    "coo_matvec",
    "backward_error",
    "coo_to_dense",
    "detect_properties",
    "has_full_diagonal",
    "build_bell",
    "aggregate_pattern",
    "spgemm_program",
    "tentative_coarse_pattern",
    "color_pattern",
]


def has_full_diagonal(row, col, n: int) -> bool:
    """True when every diagonal position is structurally present — the pivot
    prerequisite of the no-pivoting direct factorization (core/direct.py).
    ``row``/``col`` must be concrete."""
    r = np.asarray(row)
    c = np.asarray(col)
    return bool(np.unique(r[r == c]).size == n)


# ---------------------------------------------------------------------------
# low-level COO kernels (autodiff-safe, XLA-fused)
# ---------------------------------------------------------------------------

def coo_matvec(val: jax.Array, row: jax.Array, col: jax.Array, x: jax.Array,
               n_rows: int) -> jax.Array:
    """y = A @ x for COO A.  Supports leading batch dims on ``val``/``x``.

    Uses ``segment_sum`` (sorted-by-row patterns get the fast path; unsorted
    still correct).  This is the ``jnp`` backend's SpMV and the oracle for the
    Pallas kernels.
    """
    if val.ndim == 1 and x.ndim == 1:
        return jax.ops.segment_sum(val * x[col], row, num_segments=n_rows)
    # broadcast batch dims: val (..., nnz), x (..., n)
    batch_shape = jnp.broadcast_shapes(val.shape[:-1], x.shape[:-1])
    val = jnp.broadcast_to(val, batch_shape + val.shape[-1:])
    x = jnp.broadcast_to(x, batch_shape + x.shape[-1:])
    flat_v = val.reshape((-1, val.shape[-1]))
    flat_x = x.reshape((-1, x.shape[-1]))
    y = jax.vmap(lambda v, xx: jax.ops.segment_sum(v * xx[col], row,
                                                   num_segments=n_rows))(flat_v, flat_x)
    return y.reshape(batch_shape + (n_rows,))


def backward_error(val, row, col, n_rows: int, x: jax.Array, b: jax.Array,
                   r: Optional[jax.Array] = None) -> jax.Array:
    """Normwise backward error ``‖b − Ax‖∞ / (‖A‖∞ ‖x‖∞ + ‖b‖∞)`` of a
    solution ``x`` (infinity norms throughout, over every element of a
    batched ``x``/``b``).

    The smallest relative perturbation of A and b that ``x`` solves exactly
    (Rigal–Gaches); a backward-stable solve makes it a small multiple of the
    dtype's eps at any size and condition, where ``‖r‖/‖b‖`` grows with
    ‖A‖‖x‖/‖b‖.  The direct backend's ``converged`` flag compares it with
    ``tol``.  ``r`` is the residual if the caller has it; otherwise it is
    formed through :func:`coo_matvec`, independent of any kernel a solve
    used."""
    if r is None:
        r = b - coo_matvec(val, row, col, x, n_rows)
    a_inf = jnp.max(jax.ops.segment_sum(jnp.abs(val), row,
                                        num_segments=n_rows))
    den = a_inf * jnp.max(jnp.abs(x)) + jnp.max(jnp.abs(b))
    return jnp.max(jnp.abs(r)) / den


def coo_rmatvec(val, row, col, y, n_cols):
    """x = Aᵀ @ y — transpose is a row/col swap (paper Eq. 6 uses this)."""
    return coo_matvec(val, col, row, y, n_cols)


def coo_to_dense(val, row, col, shape):
    n, m = shape
    base = jnp.zeros(val.shape[:-1] + (n, m), dtype=val.dtype)
    return base.at[..., row, col].add(val)


def coo_diagonal(val, row, col, n):
    mask = (row == col)
    return jax.ops.segment_sum(jnp.where(mask, val, 0.0), row, num_segments=n)


# ---------------------------------------------------------------------------
# pattern-level coarsening / product helpers (eager / numpy — the symbolic
# half of the algebraic-multigrid plan, see core/multigrid.py)
# ---------------------------------------------------------------------------

def aggregate_pattern(row, col, n: int):
    """Greedy aggregation of the (symmetrized) pattern graph.

    The values-free half of smoothed-aggregation coarsening: pass 1 seeds an
    aggregate at every node whose whole neighbourhood is still free (node ∪
    neighbours become one aggregate — the standard Vaněk sweep); pass 2
    attaches leftover nodes to the neighbouring aggregate they touch most;
    pass 3 turns isolated stragglers into singletons.  Returns ``(agg, n_agg)``
    with ``agg[i]`` the aggregate id of fine node ``i``.
    """
    r = np.asarray(row, dtype=np.int64)
    c = np.asarray(col, dtype=np.int64)
    mask = r != c
    rr = np.concatenate([r[mask], c[mask]])
    cc = np.concatenate([c[mask], r[mask]])
    order = np.lexsort((cc, rr))
    rr, cc = rr[order], cc[order]
    keep = np.ones(len(rr), bool)
    keep[1:] = (rr[1:] != rr[:-1]) | (cc[1:] != cc[:-1])
    rr, cc = rr[keep], cc[keep]
    ptr = np.searchsorted(rr, np.arange(n + 1))

    agg = np.full(n, -1, dtype=np.int64)
    n_agg = 0
    for i in range(n):                     # pass 1: free-neighbourhood seeds
        if agg[i] >= 0:
            continue
        nb = cc[ptr[i]:ptr[i + 1]]
        if nb.size and (agg[nb] >= 0).any():
            continue
        agg[i] = n_agg
        agg[nb] = n_agg
        n_agg += 1
    for i in range(n):                     # pass 2: attach to busiest neighbour
        if agg[i] >= 0:
            continue
        nb_agg = agg[cc[ptr[i]:ptr[i + 1]]]
        nb_agg = nb_agg[nb_agg >= 0]
        if nb_agg.size:
            agg[i] = np.bincount(nb_agg).argmax()
    for i in range(n):                     # pass 3: isolated singletons
        if agg[i] < 0:
            agg[i] = n_agg
            n_agg += 1
    return agg, int(n_agg)


def spgemm_program(arow, acol, brow, bcol, shape_c):
    """Static index program for the sparse product C = A·B (pattern-level).

    Enumerates every structurally-nonzero pair (entry ``e`` of A, entry ``f``
    of B with ``brow[f] == acol[e]``), assigns each its slot in the unique
    pattern of C, and returns ``(ga, gb, gdst, crow, ccol)``: the numeric
    product is ONE gather + segment-sum, ``c_val = segment_sum(
    a_val[ga] * b_val[gb], gdst, num_segments=len(crow))`` — the same
    static-index discipline as ``core/direct.py``'s step programs, reused by
    the Galerkin triple product of the AMG plan.
    """
    arow = np.asarray(arow, np.int64); acol = np.asarray(acol, np.int64)
    brow = np.asarray(brow, np.int64); bcol = np.asarray(bcol, np.int64)
    ob = np.argsort(brow, kind="stable")
    # CSR-ish grouping of B by row (row range = A's column space)
    n_mid = int(max(acol.max(initial=-1), brow.max(initial=-1))) + 1
    bptr = np.searchsorted(brow[ob], np.arange(n_mid + 1))
    cnt = (bptr[acol + 1] - bptr[acol])            # pairs per A entry
    total = int(cnt.sum())
    ga = np.repeat(np.arange(len(arow), dtype=np.int64), cnt)
    grp = np.repeat(np.cumsum(cnt) - cnt, cnt)
    loc = np.arange(total, dtype=np.int64) - grp
    gb = ob[np.repeat(bptr[acol], cnt) + loc]
    keys = arow[ga] * np.int64(shape_c[1]) + bcol[gb]
    ukeys, gdst = np.unique(keys, return_inverse=True)
    crow = (ukeys // shape_c[1]).astype(np.int64)
    ccol = (ukeys % shape_c[1]).astype(np.int64)
    return ga, gb, gdst.astype(np.int64), crow, ccol


def tentative_coarse_pattern(row, col, n: int, *, coarsest: int = 48,
                             max_levels: int = 12):
    """Repeated pattern aggregation down to ``coarsest`` nodes (values-free).

    Composes the per-level aggregate maps into ONE fine→coarse map and the
    coarse Galerkin pattern Tᵀ·A·T of the *tentative* (piecewise-constant)
    prolongator: because every T entry is 1, the numeric coarse matrix is a
    single segment-sum of the fine values through ``e2c``.  Returns
    ``(agg, n_c, e2c, crow, ccol)``.  This is the coarse level of the
    two-level Schwarz preconditioner (core/precond.py).
    """
    agg = np.arange(n, dtype=np.int64)
    n_c = n
    r = np.asarray(row, np.int64)
    c = np.asarray(col, np.int64)
    for _ in range(max_levels):
        if n_c <= coarsest:
            break
        a, na = aggregate_pattern(r, c, n_c)
        if na >= n_c:                       # aggregation stalled
            break
        agg = a[agg]
        keys = np.unique(a[r] * np.int64(na) + a[c])
        r = (keys // na).astype(np.int64)
        c = (keys % na).astype(np.int64)
        n_c = na
    keys = agg[np.asarray(row, np.int64)] * np.int64(n_c) + \
        agg[np.asarray(col, np.int64)]
    ukeys, e2c = np.unique(keys, return_inverse=True)
    crow = (ukeys // n_c).astype(np.int64)
    ccol = (ukeys % n_c).astype(np.int64)
    return agg, int(n_c), e2c.astype(np.int64), crow, ccol


def color_pattern(row, col, n_cols: int):
    """Greedy column coloring of a Jacobian pattern (Curtis–Powell–Reid).

    Two columns get different colors whenever they share a structurally
    nonzero row — a distance-1 coloring of the column-intersection graph —
    so ONE ``jax.jvp`` probe per color recovers every pattern entry exactly:
    ``J[r, c] == (J @ p_{color[c]})[r]`` because no other column of c's
    color touches row r.  Eager numpy, run once per pattern by
    :class:`repro.core.nonlinear.SparseNewton` — the symbolic half of sparse
    Jacobian assembly, the same analyze-once discipline as the direct
    backend's AMD/etree pass.  Columns are visited largest-degree first
    (the classic LF ordering keeps the color count near the max row count).
    Returns ``(color, n_colors)`` with ``color[j] in [0, n_colors)``.
    """
    r = np.asarray(row, np.int64)
    c = np.asarray(col, np.int64)
    if r.size == 0:
        return np.zeros(n_cols, np.int64), 1 if n_cols else 0
    n_rows = int(r.max()) + 1
    orow = np.argsort(r, kind="stable")
    cols_sorted = c[orow]
    rptr = np.searchsorted(r[orow], np.arange(n_rows + 1))
    row_cols = np.split(cols_sorted, rptr[1:-1])
    ocol = np.argsort(c, kind="stable")
    rows_sorted = r[ocol]
    cptr = np.searchsorted(c[ocol], np.arange(n_cols + 1))

    color = np.full(n_cols, -1, np.int64)
    n_colors = 1
    deg = cptr[1:] - cptr[:-1]
    for j in np.argsort(-deg, kind="stable"):
        rows_j = rows_sorted[cptr[j]:cptr[j + 1]]
        if rows_j.size == 0:
            color[j] = 0          # structurally empty column: any color
            continue
        nb = np.concatenate([row_cols[i] for i in rows_j])
        used = np.zeros(n_colors + 1, bool)
        seen = color[nb]
        used[seen[seen >= 0]] = True
        free = int(np.flatnonzero(~used)[0])
        color[j] = free
        n_colors = max(n_colors, free + 1)
    return color, int(n_colors)


# ---------------------------------------------------------------------------
# pattern analysis (eager / numpy — runs once at construction)
# ---------------------------------------------------------------------------

def detect_properties(val, row, col, shape, check_values: bool = True) -> dict:
    """Detect structural symmetry / SPD-likelihood.

    Mirrors torch-sla's automatic upgrade of LU → Cholesky/LDLT.  Value-level
    checks only run when ``val`` is a concrete (non-traced) array.
    """
    props = {"symmetric": False, "spd_hint": False, "sorted_rows": False}
    if shape[0] != shape[1]:
        return props
    try:
        r = np.asarray(row)
        c = np.asarray(col)
    except Exception:  # traced
        return props
    props["sorted_rows"] = bool(np.all(np.diff(r) >= 0))
    # pivot availability for the no-pivoting direct backend (core/direct.py)
    props["struct_full_diag"] = has_full_diagonal(r, c, shape[0])
    key_f = (r.astype(np.int64) * shape[1] + c)
    key_t = (c.astype(np.int64) * shape[1] + r)
    of, ot = np.argsort(key_f), np.argsort(key_t)
    if not np.array_equal(key_f[of], key_t[ot]):
        return props  # pattern not symmetric
    sym = True
    if check_values:
        try:
            v = np.asarray(val)
        except Exception:
            v = None
        if v is not None and not isinstance(val, jax.core.Tracer):
            vf = v[..., of]
            vt = v[..., ot]
            sym = bool(np.allclose(vf, vt, rtol=1e-12, atol=1e-12))
            if sym:
                # cheap SPD hint: all diagonal entries present and positive
                dmask = r == c
                diag = np.zeros(v.shape[:-1] + (shape[0],), v.dtype)
                flat = diag.reshape(-1, shape[0])
                vflat = v.reshape(-1, v.shape[-1])
                for b in range(flat.shape[0]):
                    np.add.at(flat[b], r[dmask], vflat[b][dmask])
                props["spd_hint"] = bool(np.all(flat > 0))
    props["symmetric"] = sym
    return props


# ---------------------------------------------------------------------------
# block-ELL construction for the Pallas SpMV kernel
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BellMeta:
    """Static layout of a block-ELL matrix (see kernels/spmv_bell.py)."""
    bm: int            # rows per row-band
    bn: int            # cols per column block (128-aligned)
    n_rb: int          # number of row bands
    n_cb: int          # number of column blocks
    k: int             # blocks per row band (padded)
    n_pad: int         # padded row count
    m_pad: int         # padded col count
    fill: float        # nnz / (n_rb*k*bm*bn) — padding efficiency


def build_bell(row, col, shape, bm: int = 8, bn: int = 128,
               max_k: Optional[int] = None):
    """Build block-ELLPACK layout: per row-band, the list of non-empty column
    blocks (padded to k) plus a scatter map from COO nnz → dense block slots.

    Returns ``(meta, block_cols[int32 (n_rb,k)], perm[int32 (nnz,)])`` where
    ``perm[e]`` is the flat index into the (n_rb,k,bm,bn) value tensor for COO
    entry e.  Values are materialized per-call with a scatter so gradients flow
    through the same COO ``val`` regardless of kernel.
    """
    r = np.asarray(row).astype(np.int64)
    c = np.asarray(col).astype(np.int64)
    n, m = shape
    n_rb = -(-n // bm)
    n_cb = -(-m // bn)
    rb = r // bm
    cb = c // bn
    # unique (row-band, col-block) pairs
    key = rb * n_cb + cb
    uniq, inv = np.unique(key, return_inverse=True)
    u_rb = uniq // n_cb
    u_cb = uniq % n_cb
    counts = np.bincount(u_rb, minlength=n_rb)
    k = int(counts.max()) if counts.size else 1
    if max_k is not None:
        k = min(k, max_k)
    # slot index of each unique block within its row band
    order = np.argsort(u_rb, kind="stable")
    slot = np.zeros_like(u_rb)
    slot_sorted = np.concatenate([np.arange(cnt) for cnt in counts]) if counts.size else np.zeros(0, np.int64)
    slot[order] = slot_sorted
    block_cols = np.zeros((n_rb, k), np.int32)
    block_cols[u_rb, np.minimum(slot, k - 1)] = u_cb.astype(np.int32)
    # scatter map: COO entry e → flat slot in (n_rb, k, bm, bn)
    e_slot = slot[inv]
    keep = e_slot < k
    e_rb = rb
    e_lr = r % bm
    e_lc = c % bn
    perm = ((e_rb * k + e_slot) * bm + e_lr) * bn + e_lc
    perm = np.where(keep, perm, -1).astype(np.int64)
    fill = float(len(r)) / float(max(n_rb * k * bm * bn, 1))
    meta = BellMeta(bm=bm, bn=bn, n_rb=int(n_rb), n_cb=int(n_cb), k=int(k),
                    n_pad=int(n_rb * bm), m_pad=int(n_cb * bn), fill=fill)
    return meta, jnp.asarray(block_cols), jnp.asarray(perm)


# ---------------------------------------------------------------------------
# SparseTensor
# ---------------------------------------------------------------------------

def _plan_cache():
    """Fresh bounded-LRU plan cache (:class:`repro.core.dispatch.PlanCache`).
    Imported lazily: dispatch imports this module at module level, so the
    cycle must break here."""
    from .dispatch import PlanCache
    return PlanCache()


@jax.tree_util.register_pytree_node_class
class SparseTensor:
    """A sparse matrix (or shared-pattern batch) with autograd-aware solvers.

    Construction is eager w.r.t. the *pattern* (row/col as concrete arrays);
    values may later be replaced by traced arrays (``with_values``) so the
    same object works inside jit/grad — mirroring torch-sla, where the pattern
    defines one symbolic setup reused across a batch or a training loop.
    """

    def __init__(self, val, row, col, shape: Sequence[int], *,
                 props: Optional[dict] = None,
                 bell: Optional[tuple] = None,
                 stencil: Optional[Any] = None,
                 build_kernel_layout: bool = False,
                 validate: bool = True):
        val = jnp.asarray(val) if not isinstance(val, jax.core.Tracer) else val
        self.val = val
        self.row = jnp.asarray(row, dtype=jnp.int32)
        self.col = jnp.asarray(col, dtype=jnp.int32)
        self.shape = tuple(int(s) for s in shape)
        if validate and not isinstance(val, jax.core.Tracer):
            assert val.shape[-1] == self.row.shape[0] == self.col.shape[0], (
                f"nnz mismatch: val {val.shape}, row {self.row.shape}")
        self.props = props if props is not None else detect_properties(
            val, self.row, self.col, self.shape)
        self.stencil = stencil
        self._plans = _plan_cache()  # plan_key → SolverPlan (bounded LRU)
        if bell is not None:
            self.bell = bell
        elif build_kernel_layout:
            self.bell = build_bell(self.row, self.col, self.shape)
        else:
            self.bell = None

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        bell_children = self.bell[1:] if self.bell is not None else ()
        children = (self.val, self.row, self.col) + tuple(bell_children)
        aux = (self.shape, _freeze(self.props),
               self.bell[0] if self.bell is not None else None, self.stencil)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        shape, props, bell_meta, stencil = aux
        val, row, col = children[:3]
        obj = cls.__new__(cls)
        obj.val, obj.row, obj.col = val, row, col
        obj.shape = shape
        obj.props = dict(props)
        obj.stencil = stencil
        obj.bell = (bell_meta,) + tuple(children[3:]) if bell_meta is not None else None
        obj._plans = _plan_cache()
        return obj

    # -- basic ops ----------------------------------------------------------
    @property
    def nnz(self) -> int:
        return self.row.shape[0]

    @property
    def n(self) -> int:
        return self.shape[0]

    @property
    def batch_shape(self):
        return self.val.shape[:-1]

    @property
    def dtype(self):
        return self.val.dtype

    @property
    def T(self) -> "SparseTensor":
        return SparseTensor(self.val, self.col, self.row,
                            (self.shape[1], self.shape[0]),
                            props=self.props, validate=False)

    def with_values(self, val) -> "SparseTensor":
        """Same pattern, new (possibly traced) values.  The plan cache is
        SHARED with the parent — the jit/grad hot path re-solves without
        re-analyzing (paper §3.2.3: one symbolic setup per pattern)."""
        obj = SparseTensor.__new__(SparseTensor)
        obj.val, obj.row, obj.col = val, self.row, self.col
        obj.shape, obj.props = self.shape, dict(self.props)
        obj.bell, obj.stencil = self.bell, self.stencil
        obj._plans = self._plans
        return obj

    def matvec(self, x, *, backend: Optional[str] = None):
        from . import dispatch
        return dispatch.matvec(self, x, backend=backend)

    def __matmul__(self, x):
        return self.matvec(x)

    def rmatvec(self, y):
        return coo_rmatvec(self.val, self.row, self.col, y, self.shape[1])

    def todense(self):
        return coo_to_dense(self.val, self.row, self.col, self.shape)

    def diagonal(self):
        return coo_diagonal(self.val, self.row, self.col, self.shape[0])

    # -- solvers (autograd-aware; see core/adjoint.py) ----------------------
    def plan(self, **solve_kwargs):
        """Analyze (or fetch the cached) :class:`~repro.core.dispatch.SolverPlan`
        for this pattern + solver options — the analyze stage of
        analyze → setup → solve."""
        from . import dispatch
        return dispatch.get_plan(self, dispatch.make_config(self, **solve_kwargs))

    def solve(self, b, *, backend: Optional[str] = None,
              method: Optional[str] = None, tol: float = 1e-6,
              atol: float = 0.0, maxiter: Optional[int] = None,
              precond: str = "jacobi", x0=None):
        """Differentiable solve of ``A x = b`` through the plan engine.

        ``backend`` ∈ {auto, dense, direct, jnp, pallas, stencil}: ``direct``
        is the sparse LDLᵀ/LU path with a cached symbolic factorization
        (methods ``ldlt``/``lu``); auto prefers it for mid-size systems and
        whenever ``props["illcond_hint"]`` is set.  ``precond`` ∈ {none,
        jacobi, block_jacobi, chebyshev, mg, amg, ilu} applies to the
        iterative backends; ``ilu`` is ILU(0)/IC(0) built on the same
        symbolic machinery, ``mg`` the geometric V-cycle (stencil layouts),
        ``amg`` smoothed-aggregation algebraic multigrid for any pattern
        (coarsening and Galerkin programs cached on the plan).  Multiple
        right-hand sides (leading batch dims on ``b``) share one setup — a
        single factorization serves the whole batch.
        """
        from . import adjoint, dispatch
        cfg = dispatch.make_config(self, backend=backend, method=method,
                                   tol=tol, atol=atol, maxiter=maxiter,
                                   precond=precond)
        return adjoint.sparse_solve(cfg, self, b, x0)

    def eigsh(self, k: int = 6, *, method: str = "lobpcg", tol: float = 1e-6,
              maxiter: int = 200, compute_vector_grads: bool = True,
              largest: bool = False, precond: Optional[str] = None,
              seed: int = 0):
        from . import adjoint
        return adjoint.sparse_eigsh(self, k, method=method, tol=tol,
                                    maxiter=maxiter,
                                    compute_vector_grads=compute_vector_grads,
                                    largest=largest, precond=precond,
                                    seed=seed)

    def slogdet(self):
        """(sign, log|det|): sparse via the plan engine's cached LDLᵀ/LU
        factors (Σ log |d_i| with sign tracking) for concrete patterns
        within the ``direct_budget`` option; dense fallback beyond
        (paper §3.3)."""
        from . import adjoint
        return adjoint.sparse_slogdet(self)

    def __repr__(self):
        return (f"SparseTensor(shape={self.shape}, nnz={self.nnz}, "
                f"batch={self.batch_shape}, dtype={self.dtype}, "
                f"sym={self.props.get('symmetric')}, bell={self.bell is not None})")


def _freeze(d: dict):
    return tuple(sorted(d.items()))


# ---------------------------------------------------------------------------
# SparseTensorList — distinct sparsity patterns
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
class SparseTensorList:
    """A batch of matrices with *distinct* patterns (GNN minibatches, irregular
    meshes).  Each element dispatches independently with an isolated adjoint —
    semantics match torch-sla's SparseTensorList."""

    def __init__(self, tensors: Sequence[SparseTensor]):
        self.tensors = list(tensors)

    def tree_flatten(self):
        return tuple(self.tensors), len(self.tensors)

    @classmethod
    def tree_unflatten(cls, aux, children):
        obj = cls.__new__(cls)
        obj.tensors = list(children)
        return obj

    def __len__(self):
        return len(self.tensors)

    def __getitem__(self, i):
        return self.tensors[i]

    def solve(self, bs, **kw):
        assert len(bs) == len(self.tensors)
        return [A.solve(b, **kw) for A, b in zip(self.tensors, bs)]

    def matvec(self, xs):
        return [A.matvec(x) for A, x in zip(self.tensors, xs)]

    def eigsh(self, k: int = 6, **kw):
        return [A.eigsh(k, **kw) for A in self.tensors]
