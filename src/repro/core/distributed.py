"""Distributed layer with autograd-compatible halo exchange (paper §3.3, App. C).

Domain decomposition follows the PETSc/Trilinos/OpenFOAM pattern the paper
adapts: each shard owns a contiguous row block ``O_p`` plus halo metadata
``H_p``; a halo exchange runs before each local SpMV; global inner products
are ``all_reduce`` (here ``lax.psum``).  The halo exchange ``H`` is a
``jax.custom_vjp`` whose backward is the **transposed** exchange ``Hᵀ`` —
reversed sender/receiver roles with *summation* at the receive site
(paper Eq. 5–6) — so every distributed solve composes with autodiff.

JAX rendering: NCCL isend/irecv → ``lax.ppermute`` inside ``shard_map``;
torch.distributed process groups → a named mesh axis.  The whole solver runs
as one SPMD program; data lives as stacked ``(P, n_loc)`` arrays sharded on
the leading axis.

Plan lifecycle (PR 3): ``DSparseTensor`` is a first-class citizen of the
plan engine — ``solve`` routes through the ``dist`` backend's
analyze(pattern) → setup(values) → solve(b) split (:mod:`repro.core.
dispatch`).  ``analyze`` runs ONCE per (global pattern, mesh, partition)
and freezes everything eager: partition bounds, the :class:`HaloProgram`
(axis size and ppermute perms baked in — nothing queries the axis
environment at trace time), the Aᵀ partition for non-symmetric adjoints,
and a :class:`~repro.core.precond.DistPreconditionerPlan` (``jacobi`` or
shard-local overlapping-Schwarz ``schwarz``).  ``setup`` is the traced-safe
per-values half, memoized per values array; ``solve`` is the shard_map'd
Krylov loop.  Plans are cached on the tensor and shared by ``with_values``,
mirroring the single-device contract.

Beyond-paper: ``pipelined_cg`` (Ghysels–Vanroose) fuses the two per-iteration
reductions into ONE length-2 psum — the roadmap item of paper App. C.
"""
from __future__ import annotations

import dataclasses
import functools
from functools import partial
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from . import dispatch as _dispatch
from . import solvers as _solvers
from .sparse import SparseTensor
from .spans import count, scoped, span

__all__ = ["halo_exchange", "HaloProgram", "halo_program", "halo_apply",
           "DSparseTensor", "DSparseTensorList",
           "partition_simple", "partition_coordinate", "pipelined_cg"]


# ---------------------------------------------------------------------------
# the paper's H / Hᵀ pair — driven by an eagerly-frozen HaloProgram
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class HaloProgram:
    """Frozen halo-exchange schedule: axis size and ppermute perms are plan
    artifacts computed once at analyze time, never re-derived inside a trace
    (``lax``'s axis environment is not consulted at all)."""
    h_lo: int
    h_hi: int
    axis: str
    p: int
    perm_up: Tuple[Tuple[int, int], ...]   # i → i+1 (left-tail delivery)
    perm_dn: Tuple[Tuple[int, int], ...]   # i → i-1 (right-head delivery)


@functools.lru_cache(maxsize=None)
def halo_program(h_lo: int, h_hi: int, axis: str, p: int) -> HaloProgram:
    return HaloProgram(
        h_lo=h_lo, h_hi=h_hi, axis=axis, p=p,
        perm_up=tuple((i, (i + 1) % p) for i in range(p)),
        perm_dn=tuple((i, (i - 1) % p) for i in range(p)))


def _halo_run(prog: HaloProgram, x: jax.Array) -> jax.Array:
    """H: scatter owned boundary values into neighbours' halo slots.

    ``x``: (..., n_loc) owned values (inside shard_map over ``prog.axis``).
    Returns (..., h_lo + n_loc + h_hi): [left-neighbour tail | own | right-
    neighbour head].  Non-periodic: edge shards see zeros.
    """
    idx = lax.axis_index(prog.axis)
    parts = []
    if prog.h_lo > 0:
        # receive left neighbour's tail:  i-1 → i
        lo = lax.ppermute(x[..., -prog.h_lo:], prog.axis,
                          perm=list(prog.perm_up))
        lo = jnp.where(idx == 0, jnp.zeros_like(lo), lo)
        parts.append(lo)
    parts.append(x)
    if prog.h_hi > 0:
        # receive right neighbour's head:  i+1 → i
        hi = lax.ppermute(x[..., :prog.h_hi], prog.axis,
                          perm=list(prog.perm_dn))
        hi = jnp.where(idx == prog.p - 1, jnp.zeros_like(hi), hi)
        parts.append(hi)
    return jnp.concatenate(parts, axis=-1)


def _halo_run_t(prog: HaloProgram, g: jax.Array) -> jax.Array:
    """Hᵀ: same neighbour graph and message sizes, reversed roles,
    sum-at-receive (paper Eq. 6)."""
    idx = lax.axis_index(prog.axis)
    n_loc = g.shape[-1] - prog.h_lo - prog.h_hi
    g_lo = g[..., :prog.h_lo]
    g_own = g[..., prog.h_lo:prog.h_lo + n_loc]
    g_hi = g[..., prog.h_lo + n_loc:]
    gx = g_own
    if prog.h_lo > 0:
        # my lo-halo grads belong to the LEFT neighbour's tail: send i → i-1
        back = lax.ppermute(
            jnp.where(idx == 0, jnp.zeros_like(g_lo), g_lo), prog.axis,
            perm=list(prog.perm_dn))
        gx = gx.at[..., -prog.h_lo:].add(back)
    if prog.h_hi > 0:
        # my hi-halo grads belong to the RIGHT neighbour's head: send i → i+1
        back = lax.ppermute(
            jnp.where(idx == prog.p - 1, jnp.zeros_like(g_hi), g_hi),
            prog.axis, perm=list(prog.perm_up))
        gx = gx.at[..., :prog.h_hi].add(back)
    return gx


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def halo_apply(prog: HaloProgram, x: jax.Array) -> jax.Array:
    """Differentiable H with the frozen program; backward is Hᵀ."""
    return _halo_run(prog, x)


def _halo_apply_fwd(prog, x):
    return _halo_run(prog, x), None


def _halo_apply_bwd(prog, _, g):
    return (_halo_run_t(prog, g),)


halo_apply.defvjp(_halo_apply_fwd, _halo_apply_bwd)


def halo_exchange(x: jax.Array, h_lo: int, h_hi: int, axis: str) -> jax.Array:
    """H with the program derived from the ambient mesh axis (inside
    ``shard_map``).  Prefer :func:`halo_apply` with a plan-cached
    :func:`halo_program` on hot paths."""
    return halo_apply(halo_program(h_lo, h_hi, axis, lax.axis_size(axis)), x)


# ---------------------------------------------------------------------------
# partitioning utilities (paper: contiguous rows, RCB, METIS)
# ---------------------------------------------------------------------------

def partition_simple(n: int, p: int) -> np.ndarray:
    """Contiguous row-block ownership boundaries (paper partition_simple)."""
    base = n // p
    sizes = np.full(p, base)
    sizes[: n - base * p] += 1
    return np.concatenate([[0], np.cumsum(sizes)])


def partition_coordinate(coords: np.ndarray, p: int) -> np.ndarray:
    """Recursive coordinate bisection (Berger–Bokhari 1987): returns a
    permutation making each partition contiguous, so the banded halo
    machinery applies after relabeling.  METIS edge-cut minimization would
    slot in identically (permutation in, contiguous blocks out) but is not
    available offline — documented in DESIGN.md."""
    n = coords.shape[0]
    order = np.arange(n)

    def rcb(idx, parts):
        if parts == 1:
            return [idx]
        d = int(np.argmax(coords[idx].max(0) - coords[idx].min(0)))
        srt = idx[np.argsort(coords[idx, d], kind="stable")]
        half = parts // 2
        cut = len(idx) * half // parts
        return rcb(srt[:cut], half) + rcb(srt[cut:], parts - half)

    groups = rcb(order, p)
    return np.concatenate(groups)


def _partition_pattern(row: np.ndarray, col: np.ndarray, bounds: np.ndarray):
    """Row-block partition of one COO pattern (eager, values-free).

    Returns ``(lrow, lcol, src, h_lo, h_hi, nnz_loc, counts)`` where ``src``
    maps each padded local slot back to its global entry index (pads → -1).
    Shared by ``from_global`` and the plan's Aᵀ-partition build, so both
    sides use identical padding and halo conventions.
    """
    p = len(bounds) - 1
    n_loc = int(np.max(np.diff(bounds)))
    masks = [(row >= bounds[q]) & (row < bounds[q + 1]) for q in range(p)]
    h_lo = h_hi = 0
    for q, m in enumerate(masks):
        if m.any():
            h_lo = max(h_lo, int(max(0, bounds[q] - col[m].min())))
            h_hi = max(h_hi, int(max(0, col[m].max() - (bounds[q + 1] - 1))))
    if h_lo > n_loc or h_hi > n_loc:
        raise ValueError(
            "halo wider than one neighbour shard — repartition or add hops")
    counts = [int(m.sum()) for m in masks]
    nnz_loc = max(max(counts), 1)
    lrow = np.zeros((p, nnz_loc), np.int32)
    lcol = np.zeros((p, nnz_loc), np.int32)
    src = np.full((p, nnz_loc), -1, np.int64)
    for q, m in enumerate(masks):
        idx = np.nonzero(m)[0]
        lrow[q, :idx.size] = row[idx] - bounds[q]
        lcol[q, :idx.size] = col[idx] - bounds[q] + h_lo
        src[q, :idx.size] = idx
    return lrow, lcol, src, h_lo, h_hi, nnz_loc, counts


# ---------------------------------------------------------------------------
# DSparseTensor
# ---------------------------------------------------------------------------

def auto_mesh(mesh: Mesh) -> Mesh:
    """The same devices and axis names with every axis ``Auto``.

    ``jax.make_mesh`` gives ``Explicit`` axes, under which an array's
    sharding is part of its type: a vmap or concatenate that mixes the
    sharded stacked values with replicated analyze-time tables is refused.
    The distributed layer shards only through ``NamedSharding`` and
    ``shard_map``, which is what ``Auto`` axes mean."""
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


@dataclasses.dataclass(frozen=True)
class DistMeta:
    n: int
    p: int
    n_loc: int          # padded local rows (uniform)
    h_lo: int
    h_hi: int
    nnz_loc: int        # padded local nnz (uniform)
    axis: str
    symmetric: bool
    shard_nnz: Optional[Tuple[int, ...]] = None   # true nnz per shard


@jax.tree_util.register_pytree_node_class
class DSparseTensor:
    """Row-block distributed sparse matrix (paper §3.3).

    Storage: stacked per-shard arrays with leading dim P, sharded over the
    mesh axis — ``lval (P, nnz_loc)``, ``lrow`` local row ids, ``lcol``
    indices into the halo-extended local vector.  Single-neighbour halos
    (h_lo, h_hi ≤ n_loc) are asserted at construction; wider stencils would
    add ppermute hops (documented, not needed for the paper's workloads).

    Solves route through the plan engine's ``dist`` backend: the first call
    analyzes the (pattern, mesh, partition) once — halo program, Aᵀ
    partition, preconditioner build — and every later solve (tolerance
    sweeps, ``with_values`` refreshes, the adjoint backward) reuses the
    cached :class:`~repro.core.dispatch.SolverPlan`.
    """

    def __init__(self, meta: DistMeta, lval, lrow, lcol, mesh: Mesh,
                 lval_t=None, lrow_t=None, lcol_t=None):
        self.meta = meta
        self.lval, self.lrow, self.lcol = lval, lrow, lcol
        # legacy slots: the Aᵀ partition is a PLAN artifact now (built once
        # per pattern by analyze); kept only for constructor/pytree compat
        self.lval_t, self.lrow_t, self.lcol_t = lval_t, lrow_t, lcol_t
        self.mesh = auto_mesh(mesh)
        from .sparse import _plan_cache
        self._plans = _plan_cache()

    def tree_flatten(self):
        return ((self.lval, self.lrow, self.lcol, self.lval_t, self.lrow_t,
                 self.lcol_t), (self.meta, self.mesh))

    @classmethod
    def tree_unflatten(cls, aux, children):
        meta, mesh = aux
        return cls(meta, children[0], children[1], children[2], mesh,
                   children[3], children[4], children[5])

    # -- plan-engine protocol (duck-typed SparseTensor pattern surface) ------
    @property
    def val(self):
        return self.lval

    @property
    def row(self):
        return self.lrow

    @property
    def col(self):
        return self.lcol

    @property
    def shape(self):
        return (self.meta.n, self.meta.n)

    @property
    def props(self):
        return {"symmetric": self.meta.symmetric}

    bell = None
    stencil = None
    batch_shape = ()

    @property
    def dtype(self):
        return self.lval.dtype

    def plan_key_extra(self) -> tuple:
        """Mesh-aware plan-cache key suffix: one pattern partitioned over a
        different axis (or shard count) must analyze separately."""
        return (self.meta.axis, self.meta.p, self.meta.n_loc)

    def with_values(self, lval) -> "DSparseTensor":
        """Same partition + pattern, new (possibly traced) stacked values.
        The plan cache is SHARED with the parent, so shared-pattern batches
        and tolerance sweeps do ONE analysis — the single-device contract."""
        obj = DSparseTensor.__new__(DSparseTensor)
        obj.meta, obj.mesh = self.meta, self.mesh
        obj.lval, obj.lrow, obj.lcol = lval, self.lrow, self.lcol
        obj.lval_t = obj.lrow_t = obj.lcol_t = None
        obj._plans = self._plans
        return obj

    def plan(self, **solve_kwargs) -> "_dispatch.SolverPlan":
        """Analyze (or fetch) the cached plan — the analyze stage of
        analyze → setup → solve on the mesh."""
        return _dispatch.get_plan(self, self._make_config(**solve_kwargs))

    def _make_config(self, *, method: str = "auto", tol: float = 1e-6,
                     atol: float = 0.0, maxiter: int = 1000,
                     precond: str = "jacobi", pipelined: bool = False,
                     x0=None) -> "_dispatch.SolverConfig":
        # x0 is a solve-stage argument, accepted here only so callers can
        # forward one kwargs dict; anything else unknown raises (a typo'd
        # knob must not silently run with defaults)
        del x0
        if method == "auto":
            method = "cg" if self.meta.symmetric else "bicgstab"
        if pipelined and method == "cg":
            method = "pipelined_cg"
        return _dispatch.SolverConfig(backend="dist", method=method, tol=tol,
                                      atol=atol, maxiter=maxiter,
                                      precond=precond)

    # -- construction --------------------------------------------------------
    @classmethod
    def from_global(cls, val, row, col, shape, mesh: Mesh, axis: str = "data",
                    symmetric: Optional[bool] = None):
        """Partition a global COO matrix across ``mesh[axis]`` (eager)."""
        val = np.asarray(val); row = np.asarray(row); col = np.asarray(col)
        n = shape[0]
        mesh = auto_mesh(mesh)
        p = mesh.shape[axis]
        if symmetric is None:
            from .sparse import detect_properties
            symmetric = detect_properties(val, row, col, shape)["symmetric"]
        bounds = partition_simple(n, p)
        lrow, lcol, src, h_lo, h_hi, nnz_loc, counts = _partition_pattern(
            row, col, bounds)
        rowsz = np.diff(bounds)
        if (h_lo > 0 or h_hi > 0) and rowsz.min() != rowsz.max():
            raise ValueError(
                "halo exchange indexes neighbour tails positionally — "
                "coupled (h>0) partitions need uniform shard sizes "
                f"(n={n} not divisible by P={p})")
        # leading shard axis, batch dims (if any) behind it — the mesh axis
        # must be the one NamedSharding splits
        lval = np.moveaxis(
            np.where(src >= 0, val[..., np.clip(src, 0, None)], 0.0), -2, 0)
        meta = DistMeta(n=n, p=p, n_loc=int(np.max(np.diff(bounds))),
                        h_lo=h_lo, h_hi=h_hi, nnz_loc=nnz_loc, axis=axis,
                        symmetric=bool(symmetric), shard_nnz=tuple(counts))
        shard = NamedSharding(mesh, P(axis))
        dev = lambda a: jax.device_put(jnp.asarray(a), shard)
        return cls(meta, dev(lval), dev(lrow), dev(lcol), mesh)

    # -- stacked <-> global --------------------------------------------------
    def stack_vector(self, x_global):
        """(n,) → (P, n_loc) padded+sharded."""
        n, p, n_loc = self.meta.n, self.meta.p, self.meta.n_loc
        bounds = partition_simple(n, p)
        rowsz = np.diff(bounds)
        parts = [np.pad(np.asarray(x_global)[bounds[q]:bounds[q + 1]],
                        (0, n_loc - rowsz[q])) for q in range(p)]
        arr = jnp.asarray(np.stack(parts, 0))
        return jax.device_put(arr, NamedSharding(self.mesh, P(self.meta.axis)))

    def gather_global(self, x_stacked):
        """(P, n_loc) → (n,) on host."""
        n, p, n_loc = self.meta.n, self.meta.p, self.meta.n_loc
        bounds = partition_simple(n, p)
        xs = np.asarray(jax.device_get(x_stacked))
        return np.concatenate([xs[q][: bounds[q + 1] - bounds[q]]
                               for q in range(p)])

    def gather_values(self):
        """Stacked local storage → global COO triplet on host (eager).

        Padding is trimmed via ``meta.shard_nnz``; legacy metas without
        counts fall back to keeping every in-matrix slot (pads carry zero
        values, so they only add numerically-inert duplicate entries)."""
        m = self.meta
        bounds = partition_simple(m.n, m.p)
        row_g, col_g, fa = global_entries(self.lrow, self.lcol, m, bounds)
        flat = np.asarray(jax.device_get(self.lval)).reshape(-1)
        return flat[fa], row_g, col_g

    # -- distributed ops ------------------------------------------------------
    def _halo(self) -> HaloProgram:
        m = self.meta
        return halo_program(m.h_lo, m.h_hi, m.axis, m.p)

    def matvec(self, x_stacked):
        m = self.meta
        prog = self._halo()
        spec = P(m.axis)

        @partial(jax.shard_map, mesh=self.mesh,
                 in_specs=(spec, spec, spec, spec), out_specs=spec,
                 check_vma=False)
        def run(lval, lrow, lcol, x):
            y = _local_matvec(prog, m.n_loc, lval[0], lrow[0], lcol[0], x[0],
                              differentiable=True)
            return y[None]

        return run(self.lval, self.lrow, self.lcol, x_stacked)

    def solve(self, b_stacked, *, method: str = "auto", tol: float = 1e-6,
              atol: float = 0.0, maxiter: int = 1000, precond: str = "jacobi",
              pipelined: bool = False, x0=None):
        """Distributed, differentiable solve through the plan engine.

        Forward: analyze-once (halo program, partition, preconditioner
        build, Aᵀ partition) → per-values setup (memoized per values array)
        → shard_map'd Krylov loop.  Backward: one distributed solve of
        Aᵀλ = g through ``plan.transpose()`` — the SAME plan for symmetric
        patterns, a shared-artifact transposed sibling otherwise — plus
        local O(nnz) gradient assembly with halo'd x (paper §3.3).

        ``precond`` ∈ {none, jacobi, schwarz, schwarz2}: ``schwarz`` is
        shard-local overlapping Schwarz with ILU(0)/IC(0) subdomain solves
        built on the direct backend's symbolic machinery
        (:mod:`repro.core.direct`); ``schwarz2`` adds an additive coarse
        correction (aggregated global Galerkin matrix, cached direct
        factors) so CG iteration counts stay flat as the shard count grows.
        """
        from . import adjoint as _adjoint
        cfg = self._make_config(method=method, tol=tol, atol=atol,
                                maxiter=maxiter, precond=precond,
                                pipelined=pipelined)
        return _adjoint.dist_sparse_solve(cfg, self, b_stacked, x0)

    def solve_with_info(self, b_stacked, **kw):
        """Non-differentiable solve that also returns :class:`SolveInfo`
        (psum'd residual norm + iteration count — replicated scalars)."""
        cfg = self._make_config(**kw)
        plan = _dispatch.get_plan(self, cfg)
        return plan.solve(self, b_stacked, kw.get("x0"), cfg=cfg)

    def eigsh(self, k: int = 4, *, tol: float = 1e-6, maxiter: int = 200,
              seed: int = 0):
        """Distributed LOBPCG: Gram-matrix Rayleigh–Ritz (psum'd s×s),
        halo-exchange matvecs.  Hellmann–Feynman adjoint assembled locally."""
        m = self.meta
        prog = self._halo()
        spec = P(m.axis)

        def impl(lval):
            @partial(jax.shard_map, mesh=self.mesh,
                     in_specs=(spec, spec, spec), out_specs=(P(None), spec),
                     check_vma=False)
            def run(lval, lrow, lcol):
                lv, lr, lc = lval[0], lrow[0], lcol[0]
                mv = lambda x: _local_matvec(prog, m.n_loc, lv, lr, lc, x)
                key = jax.random.PRNGKey(seed + lax.axis_index(m.axis))
                X0 = jax.random.normal(key, (k, m.n_loc), lval.dtype)
                pgram = lambda S1, S2: lax.psum(S1 @ S2.T, m.axis)
                w, X, _ = _solvers.lobpcg_general(mv, X0, gram=pgram, tol=tol,
                                                  maxiter=maxiter)
                return w, jnp.swapaxes(X, 0, 1)[None]  # (P, n_loc, k)

            return run(lval, self.lrow, self.lcol)

        @jax.custom_vjp
        def deig(lval):
            return impl(lval)

        def fwd(lval):
            w, V = jax.tree.map(lax.stop_gradient, impl(lval))
            return (w, V), (lval, w, V)

        def bwd(res, cot):
            lval, w, V = res
            gw, _ = cot  # eigenvector cotangents: deflated solves — local-only
                         # variant omitted in distributed mode (paper exposes
                         # eigenvalue grads; vector grads are a single-device
                         # feature here)

            @partial(jax.shard_map, mesh=self.mesh,
                     in_specs=(P(None), spec, spec, spec), out_specs=spec,
                     check_vma=False)
            def assemble(gw, V, lrow, lcol):
                Vq = V[0]                      # (n_loc, k)
                Vx = jnp.swapaxes(Vq, 0, 1)    # (k, n_loc)
                V_ext = jax.vmap(lambda v: _halo_run(prog, v))(Vx)
                lr, lc = lrow[0], lcol[0]
                gval = jnp.einsum("k,ke,ke->e", gw, Vx[:, lr], V_ext[:, lc])
                return gval[None]

            return (assemble(gw, V, self.lrow, self.lcol),)

        deig.defvjp(fwd, bwd)
        return deig(self.lval)

    def slogdet(self):
        """Gather-based fallback (paper §3.3 'Scope of distributed
        gradients'): pulls the global matrix onto ONE host, rebuilds a
        :class:`SparseTensor`, and delegates to its slogdet — which is the
        sparse cached-LDLᵀ path (Σ log |d_i| with sign tracking, O(nnz_L)
        memory) for patterns within the ``direct_budget`` option and the
        dense O(n²)
        fallback beyond.  The full gather is runtime-warned either way, and
        the host round-trip breaks gradient flow into the stacked values."""
        import warnings
        warnings.warn("DSparseTensor.slogdet gathers the global matrix onto "
                      "one process — not distributed-scalable (sparse LDLT "
                      "within the direct_budget option, dense O(n^2) "
                      "beyond).")
        val, row, col = self.gather_values()
        return SparseTensor(val, row, col, self.shape).slogdet()


# ---------------------------------------------------------------------------
# plan-engine stages (called by dispatch.DistBackend)
# ---------------------------------------------------------------------------

def global_entries(lrow, lcol, meta: DistMeta, bounds):
    """Stacked local pattern → global COO coordinates (eager, values-free).

    Returns ``(row_g, col_g, fa)`` where ``fa`` is each entry's flat index
    into the ``(P·nnz_loc,)`` value storage — the one reconstruction shared
    by the Aᵀ-partition build, ``gather_values`` and the Schwarz extended-
    matrix assembly.  Padding is trimmed via ``meta.shard_nnz``; legacy
    metas without counts drop only the off-matrix pad columns."""
    lr = np.asarray(lrow)
    lc = np.asarray(lcol)
    p, nnz_loc = lr.shape
    rows, cols, fa = [], [], []
    for q in range(p):
        cnt = meta.shard_nnz[q] if meta.shard_nnz is not None else nnz_loc
        rows.append(lr[q, :cnt].astype(np.int64) + bounds[q])
        cols.append(lc[q, :cnt].astype(np.int64) - meta.h_lo + bounds[q])
        fa.append(q * nnz_loc + np.arange(cnt, dtype=np.int64))
    row_g = np.concatenate(rows)
    col_g = np.concatenate(cols)
    fa = np.concatenate(fa)
    ok = (col_g >= 0) & (col_g < meta.n)
    return row_g[ok], col_g[ok], fa[ok]


def _local_matvec(prog: HaloProgram, n_loc: int, lv, lr, lc, x,
                  differentiable: bool = False):
    """halo exchange + purely local SpMV (paper Eq. 5) — inside shard_map."""
    H = halo_apply if differentiable else _halo_run
    x_ext = H(prog, x)
    return jax.ops.segment_sum(lv * x_ext[lc], lr, num_segments=n_loc)


class EllLayout(NamedTuple):
    """Row-slot (ELL) layout of the stacked local pattern (analyze-time
    artifact), the one table the Krylov loop's SpMV and the jacobi refresh
    read.

    ``src[q, k, r]`` is the local value slot of row ``r``'s ``k``-th entry on
    shard ``q`` (``nnz_loc``, which reads as a zero, where the row has fewer
    than ``k + 1``) and ``col[q, k, r]`` its index into the halo'd x.  The
    SpMV is then a gather and a sum over ``k``, with no scatter: on one TPU
    v5e at 2^22 rows and 2^24 entries the gather-plus-``segment_sum``
    product took 413 ms a call, this gather 155 ms.  Storage is the longest
    row times the rows, so a pattern with a few very long rows pays for
    them on every row."""
    src: jax.Array                 # (P, k, n_loc) int32, sharded
    col: jax.Array                 # (P, k, n_loc) int32, sharded


def ell_layout(lrow, lcol, meta: DistMeta, mesh: Mesh) -> EllLayout:
    """The stacked pattern's :class:`EllLayout` (eager, values-free)."""
    lr = np.asarray(lrow)
    lc = np.asarray(lcol)
    p, nnz_loc = lr.shape
    counts = meta.shard_nnz or (nnz_loc,) * p
    orders = [np.argsort(lr[q, :counts[q]], kind="stable") for q in range(p)]
    k = max(max(np.bincount(lr[q, :counts[q]]).max(initial=0)
                for q in range(p)), 1)
    src = np.full((p, k, meta.n_loc), nnz_loc, np.int32)
    col = np.zeros((p, k, meta.n_loc), np.int32)
    for q, o in enumerate(orders):
        r = lr[q, o]
        slot = np.arange(r.size) - np.searchsorted(r, r)   # rank in its row
        src[q, slot, r] = o
        col[q, slot, r] = lc[q, o]
    shard = NamedSharding(mesh, P(meta.axis))
    return EllLayout(jax.device_put(src, shard), jax.device_put(col, shard))


def ell_values(lv, src_q):
    """One shard's values in row-slot order, ``(k, n_loc)`` (pads zero);
    gathered once per solve or refresh, outside the Krylov loop."""
    return jnp.append(lv, jnp.zeros((), lv.dtype))[src_q]


def _ell_matvec(prog: HaloProgram, ve, col_q, x):
    """halo exchange + local SpMV as a row-slot gather — inside shard_map.
    Slots are added one by one in row order, as a scatter-add would: a
    ``jnp.sum`` over them rounds differently, and pipelined CG, which
    carries that rounding through four recurrences, left residuals up to
    3x larger after a stalled run."""
    xg = _halo_run(prog, x)[col_q]
    y = ve[0] * xg[0]
    for k in range(1, ve.shape[0]):
        y = y + ve[k] * xg[k]
    return y


def dist_analyze(cfg, plan) -> dict:
    """analyze(pattern): freeze every eager artifact for one
    (global pattern, mesh, partition) — runs once, cached on the plan."""
    from .precond import DistPreconditionerPlan
    meta = plan.dmeta
    bounds = partition_simple(meta.n, meta.p)
    prog = halo_program(meta.h_lo, meta.h_hi, meta.axis, meta.p)
    ell = ell_layout(plan.row, plan.col, meta, plan.mesh)
    return {
        "halo": prog,
        "bounds": bounds,
        "ell": ell,
        "precond": DistPreconditionerPlan(cfg.precond, plan.row, plan.col,
                                          meta, bounds=bounds, ell=ell),
        "transposed": False,
        # non-symmetric only: the Aᵀ partition is a plan artifact, built
        # lazily on the FIRST plan.transpose() (forward-only solves never
        # pay for it) and cached here for the plan's lifetime
        **({"t": None} if not meta.symmetric else {}),
    }


def _build_t_partition(cfg, plan, meta: DistMeta, bounds) -> dict:
    """The Aᵀ partition as a plan artifact (eager numpy, once per pattern).

    Rebuilds the global COO pattern from the stacked local arrays, row-block
    partitions its transpose with its OWN halo widths/padding, and records a
    gather map from the forward ``lval`` layout so the adjoint derives the
    Aᵀ values without any per-call partitioning."""
    from .precond import DistPreconditionerPlan
    count("t_partition")
    p, nnz_loc = np.asarray(plan.row).shape
    row_g, col_g, fa = global_entries(plan.row, plan.col, meta, bounds)

    lrow_t, lcol_t, src_t, h_lo_t, h_hi_t, nnz_loc_t, counts_t = \
        _partition_pattern(col_g, row_g, bounds)
    gather = np.where(src_t >= 0, fa[np.clip(src_t, 0, None)],
                      p * nnz_loc).astype(np.int64)
    t_meta = DistMeta(n=meta.n, p=meta.p, n_loc=meta.n_loc, h_lo=h_lo_t,
                      h_hi=h_hi_t, nnz_loc=nnz_loc_t, axis=meta.axis,
                      symmetric=False, shard_nnz=tuple(counts_t))
    shard = NamedSharding(plan.mesh, P(meta.axis))
    dev = lambda a: jax.device_put(jnp.asarray(a), shard)
    lrow_t, lcol_t = dev(lrow_t), dev(lcol_t)
    ell = ell_layout(lrow_t, lcol_t, t_meta, plan.mesh)
    return {
        "meta": t_meta,
        "lrow": lrow_t,
        "lcol": lcol_t,
        "gather": jnp.asarray(gather),
        "halo": halo_program(h_lo_t, h_hi_t, meta.axis, meta.p),
        "ell": ell,
        "precond": DistPreconditionerPlan(cfg.precond, lrow_t, lcol_t,
                                          t_meta, bounds=bounds, ell=ell),
    }


def dist_transpose_plan(plan):
    """Adjoint plan from the forward plan's own artifacts — zero re-analysis.
    Symmetric patterns never reach here (``SolverPlan.transpose`` returns the
    forward plan itself); non-symmetric ones get a sibling whose pattern IS
    the plan's cached Aᵀ partition (built on first use, then an artifact)."""
    if "t" not in plan.artifacts:
        return None           # not a dist plan
    if plan.artifacts["t"] is None:
        with jax.ensure_compile_time_eval():   # may run inside a bwd trace
            plan.artifacts["t"] = _build_t_partition(
                plan.cfg, plan, plan.dmeta, plan.artifacts["bounds"])
    t = plan.artifacts["t"]
    SolverPlan = _dispatch.SolverPlan
    tp = SolverPlan.__new__(SolverPlan)
    tp.cfg = plan.cfg
    tp.backend = plan.backend
    tp.row, tp.col = t["lrow"], t["lcol"]
    tp.shape = (plan.shape[1], plan.shape[0])
    tp.props = dict(plan.props)
    tp.bell = tp.stencil = None
    tp.mesh = plan.mesh
    tp.dmeta = t["meta"]
    # key with the mesh suffix get_plan composes from plan_key_extra, so a
    # transpose view routed through get_plan hits THIS plan, not a re-analysis
    tmeta = t["meta"]
    tp._cache = {tp.cfg.plan_key() + (tmeta.axis, tmeta.p, tmeta.n_loc): tp}
    tp._tplan = plan
    tp._setup_memo = {}     # Aᵀ values differ from the forward values
    tp.artifacts = {"halo": t["halo"], "ell": t["ell"],
                    "bounds": plan.artifacts["bounds"],
                    "precond": t["precond"], "transposed": True}
    return tp


def transpose_values(plan, lval):
    """Forward stacked values → Aᵀ-partition stacked values via the plan's
    cached gather map (the values counterpart of the Aᵀ partition)."""
    t = plan.artifacts["t"]
    flat = jnp.concatenate([lval.reshape(-1),
                            jnp.zeros((1,), lval.dtype)])
    return flat[t["gather"]]


def transpose_view(tplan, lval_t) -> DSparseTensor:
    """DSparseTensor view of the Aᵀ partition carrying derived values —
    what the adjoint feeds back into ``tplan.solve``."""
    D = DSparseTensor.__new__(DSparseTensor)
    D.meta = tplan.dmeta
    D.mesh = tplan.mesh
    D.lval, D.lrow, D.lcol = lval_t, tplan.row, tplan.col
    D.lval_t = D.lrow_t = D.lcol_t = None
    D._plans = tplan._cache
    return D


def dist_setup(plan, A) -> tuple:
    """setup(values): the traced-safe per-values half — preconditioner
    refresh on the stacked values.  Memoized per values array by
    ``SolverPlan.setup`` (``PLAN_STATS['setup_reuse']``)."""
    return plan.artifacts["precond"].refresh(A.lval)


def dist_solve(plan, state, A, b, x0, cfg):
    """solve(b): the shard_map'd Krylov loop over frozen artifacts."""
    meta = plan.dmeta
    prog = plan.artifacts["halo"]
    pplan = plan.artifacts["precond"]
    spec = P(meta.axis)
    state = tuple(state)
    have_x0 = x0 is not None
    method = cfg.method
    if method not in ("cg", "bicgstab", "pipelined_cg"):
        raise ValueError(f"unknown distributed method {method!r}")
    stacked = (meta.p, meta.n_loc)
    for name, v in (("b", b), ("x0", x0)):
        if v is not None and tuple(v.shape) != stacked:
            # a global (n,) vector would reach the halo exchange as 0-d
            # per-shard slices
            raise ValueError(
                f"distributed {name} must be stacked (P, n_loc) = {stacked}, "
                f"got {tuple(v.shape)}; use DSparseTensor.stack_vector")

    # state leaves may be stacked-and-sharded (P, ·) or replicated (the
    # two-level Schwarz coarse factor) — the preconditioner plan says which
    sharded = pplan.state_sharded()
    ell = plan.artifacts["ell"]
    in_specs = (spec,) * (4 + (1 if have_x0 else 0)) + (P(),) * 3 + \
        tuple(spec if sh else P() for sh in sharded)

    # one compiled program per (method, warm start) on this plan: run
    # eagerly, shard_map would compile and dispatch its body op by op.
    # tol, atol and maxiter are arguments, so a tolerance sweep reuses it
    programs = plan.artifacts.setdefault("programs", {})
    key = (method, have_x0)
    if key not in programs:
        @partial(jax.shard_map, mesh=plan.mesh, in_specs=in_specs,
                 out_specs=(spec, P()), check_vma=False)
        def run(lval, src, col, bq, *rest):
            x0q = rest[0][0] if have_x0 else None
            rest = rest[1:] if have_x0 else rest
            tol, atol, maxiter = rest[:3]
            sleaves = tuple(s[0] if sh else s
                            for s, sh in zip(rest[3:], sharded))
            ve = ell_values(lval[0], src[0])
            mv = lambda xv: _ell_matvec(prog, ve, col[0], xv)
            pdot = lambda u, v: lax.psum(jnp.sum(u * v), meta.axis)
            M = pplan.local_closure(sleaves,
                                    lambda r: _halo_run(prog, r),
                                    lambda z: _halo_run_t(prog, z),
                                    matvec=mv)
            if method == "pipelined_cg":
                if x0q is None:
                    x, info = pipelined_cg(mv, bq[0], M=M, tol=tol,
                                           atol=atol, maxiter=maxiter,
                                           axis=meta.axis)
                else:
                    # warm start by shift — but keep the convergence
                    # target relative to the ORIGINAL b, matching the
                    # cg/bicgstab paths
                    target = jnp.maximum(
                        tol * jnp.sqrt(pdot(bq[0], bq[0])), atol)
                    x, info = pipelined_cg(mv, bq[0] - mv(x0q), M=M,
                                           tol=jnp.zeros_like(tol),
                                           atol=target, maxiter=maxiter,
                                           axis=meta.axis)
                    x = x + x0q
            else:
                solver = _solvers.cg if method == "cg" else _solvers.bicgstab
                x, info = solver(mv, bq[0], x0q, M=M, tol=tol, atol=atol,
                                 maxiter=maxiter, dot=pdot)
            return x[None], info

        programs[key] = jax.jit(run)

    dt = A.lval.dtype
    args = (A.lval, ell.src, ell.col, b) + ((x0,) if have_x0 else ()) + (
        jnp.asarray(cfg.tol, dt), jnp.asarray(cfg.atol, dt),
        jnp.asarray(cfg.maxiter, jnp.int32))
    with span("dist.solve"):
        return programs[key](*(args + state))


def assemble_matrix_grad(plan, lam, x):
    """Local O(nnz) matrix-gradient assembly: −λ_i x_j with halo'd x
    (paper §3.3) — runs on the FORWARD partition's pattern."""
    meta = plan.dmeta
    prog = plan.artifacts["halo"]
    spec = P(meta.axis)

    @partial(jax.shard_map, mesh=plan.mesh, in_specs=(spec, spec, spec, spec),
             out_specs=spec, check_vma=False)
    def assemble(lamq, xq, lrow, lcol):
        x_ext = _halo_run(prog, xq[0])
        gval = -(lamq[0][lrow[0]] * x_ext[lcol[0]])
        return gval[None]

    return assemble(lam, x, plan.row, plan.col)


# ---------------------------------------------------------------------------
# DSparseTensorList
# ---------------------------------------------------------------------------

class DSparseTensorList:
    """Distributed batch with distinct patterns — per-element dispatch, but
    members sharing one partitioned pattern (same stacked index arrays +
    meta + mesh) are routed through ONE plan cache, so a shared-pattern
    batch analyzes once."""

    def __init__(self, tensors):
        self.tensors = list(tensors)

    def _share_plans(self):
        seen = {}
        for A in self.tensors:
            key = (id(A.lrow), id(A.lcol), A.meta, id(A.mesh))
            if key in seen:
                # merge, don't overwrite: a member that already analyzed a
                # plan on its own contributes it to the shared cache
                seen[key].update(A._plans)
                A._plans = seen[key]
            else:
                seen[key] = A._plans

    def solve(self, bs, **kw):
        self._share_plans()
        return [A.solve(b, **kw) for A, b in zip(self.tensors, bs)]

    def solve_with_info(self, bs, **kw):
        self._share_plans()
        return [A.solve_with_info(b, **kw)
                for A, b in zip(self.tensors, bs)]


# ---------------------------------------------------------------------------
# pipelined CG — beyond-paper (paper App. C names this as the roadmap item)
# ---------------------------------------------------------------------------

def pipelined_cg(matvec: Callable, b: jax.Array, *, M: Callable = lambda r: r,
                 tol: float = 1e-6, atol: float = 0.0, maxiter: int = 1000,
                 axis: Optional[str] = None):
    """Ghysels–Vanroose pipelined CG: ONE fused length-2 reduction per
    iteration instead of two separate all_reduces, and the reduction can
    overlap the SpMV.  Halves the latency term of the collective roofline at
    large P (see EXPERIMENTS.md §Perf)."""
    psum = (lambda v: lax.psum(v, axis)) if axis else (lambda v: v)
    dot2 = lambda a, b_, c, d: psum(jnp.stack([jnp.sum(a * b_), jnp.sum(c * d)]))

    x = jnp.zeros_like(b)
    r = b - matvec(x)
    u = M(r)
    w = matvec(u)
    gd = dot2(r, u, w, u)
    gamma, delta = gd[0], gd[1]
    bnorm = jnp.sqrt(psum(jnp.sum(b * b)))
    target = jnp.maximum(tol * bnorm, atol)
    z = jnp.zeros_like(b); q = jnp.zeros_like(b)
    s = jnp.zeros_like(b); p = jnp.zeros_like(b)
    one = jnp.asarray(1.0, b.dtype)

    def cond(st):
        *_, k = st
        r = st[1]
        rn = jnp.sqrt(psum(jnp.sum(r * r)))
        return (k < maxiter) & (rn > target)

    def body(st):
        (x, r, u, w, z, q, s, p, gamma, delta, gamma_prev, alpha_prev, k) = st
        m_ = M(w)
        n_ = matvec(m_)
        beta = jnp.where(k == 0, 0.0, gamma / gamma_prev)
        alpha = jnp.where(
            k == 0, gamma / delta,
            gamma / (delta - beta * gamma / jnp.where(alpha_prev == 0.0, one,
                                                      alpha_prev)))
        z = n_ + beta * z
        q = m_ + beta * q
        s = w + beta * s
        p = u + beta * p
        x = x + alpha * p
        r = r - alpha * s
        u = u - alpha * q
        w = w - alpha * z
        gd = dot2(r, u, w, u)
        return (x, r, u, w, z, q, s, p, gd[0], gd[1], gamma, alpha, k + 1)

    st0 = (x, r, u, w, z, q, s, p, gamma, delta, one, jnp.asarray(0.0, b.dtype),
           jnp.array(0))
    st = lax.while_loop(cond, scoped("krylov.pipelined_cg", body), st0)
    x, r = st[0], st[1]
    rn = jnp.sqrt(psum(jnp.sum(r * r)))
    return x, _solvers.SolveInfo(st[-1], rn, rn <= target)
