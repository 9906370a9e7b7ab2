"""Backend registry + plan-cached auto-dispatch (paper §3.1, §3.2.3, App. A).

Five built-in backends behind one API — the TPU/JAX analogue of torch-sla's
{scipy, eigen, cudss, cupy, pytorch}:

| backend   | device  | methods                      | regime                         |
|-----------|---------|------------------------------|--------------------------------|
| dense     | MXU     | lu, cholesky                 | direct; n ≤ dense budget       |
| direct    | any     | ldlt, lu                     | sparse direct (cuDSS analogue):|
|           |         |                              | cached symbolic factorization  |
| jnp       | any     | cg, bicgstab, gmres          | general COO, segment-sum SpMV  |
| pallas    | TPU     | cg, bicgstab, gmres          | block-ELL Pallas SpMV          |
| stencil   | TPU     | cg, bicgstab                 | matrix-free structured grids   |
| dist      | mesh    | cg, bicgstab, pipelined_cg   | DSparseTensor (core/distributed)|

The ``direct`` backend (:mod:`repro.core.direct`) is the paper's headline
path: ``analyze`` computes the fill-reducing ordering (quotient-graph AMD by
default) + the etree-derived static fill pattern ONCE per pattern, ``setup``
is a jit/vmap-safe numeric refactorization memoized per values array
(``PLAN_STATS["factorize"]``/``["setup_reuse"]``), and the adjoint reuses
the forward factors — LDLᵀ is self-adjoint, LU swaps the triangular sweeps
via a shared-artifact transpose plan.

Plan lifecycle (paper §3.2.3 "one symbolic setup per pattern")
--------------------------------------------------------------
Every solve goes through a three-stage split::

    plan  = get_plan(A, cfg)        # ❶ analyze(pattern)  — eager, cached
    state = plan.setup(A)           # ❷ setup(values)     — traced-safe
    x, info = plan.solve(A, b, x0)  # ❸ solve(b)          — runs ❷ then Krylov/LU

❶ ``analyze`` runs ONCE per (sparsity pattern, backend/method/precond): it
picks the backend class, freezes the kernel layout (block-ELL / stencil
metadata), and builds the pattern-level half of the preconditioner
(:class:`repro.core.precond.PreconditionerPlan` — for ``precond="amg"``
that includes the smoothed-aggregation coarsening and the packed Galerkin
index programs of :mod:`repro.core.multigrid`, counted by
``PLAN_STATS["coarsen"]``/``["galerkin"]``).  Plans are cached on the
``SparseTensor`` keyed by ``SolverConfig.plan_key()`` — solve-loop knobs
(tol/atol/maxiter/restart) are NOT part of the key, so a tolerance sweep or
continuation loop reuses one plan — and the cache dict is *shared* by
``with_values``, so the jit/grad hot path and every solve in a
shared-pattern batch reuse one analysis.

❷ ``setup`` consumes the current (possibly traced) values: preconditioner
refresh (block inverses, Chebyshev spectrum bounds, MG hierarchy), dense
materialization.  It never touches numpy, so it is safe under jit/grad/vmap.

❸ ``solve`` executes the configured method.  The adjoint layer
(:mod:`repro.core.adjoint`) fetches ``plan.transpose()`` for the backward
system Aᵀλ = g: for symmetric patterns that is the SAME plan object (BELL
layout and preconditioner build reused); for non-symmetric patterns a
transposed sibling plan is analyzed once and cached on the forward plan.

``PLAN_STATS`` counts analyze/setup/cache events so tests (and profiles) can
assert reuse; ``register_backend`` adds a custom ``Backend`` subclass.
"""
from __future__ import annotations

import collections
import copy
import dataclasses
import weakref
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import direct as _direct
from . import options as _options
from . import precond as _precond
from . import solvers as _solvers
from . import spans as _spans
from .spans import count, span, spanned
from .sparse import (SparseTensor, backward_error, build_bell, coo_matvec,
                     has_full_diagonal)

# The dispatch knobs (dense/direct budgets, BELL fill floor, fused-step mode,
# plan-cache bounds) live in repro.core.options — one immutable record
# behind sla.set_options() / sla.options(...) / REPRO_SLA_* env vars.
DEFAULT_MAXITER = 2000

# observable analyze/setup/cache/compile counters, defined beside the span
# recorder (``core/spans.py``) whose ``count`` is their only writer
PLAN_STATS = _spans.PLAN_STATS


def reset_plan_stats() -> None:
    """Zero every ``PLAN_STATS`` counter and drop the completed solve
    records (tests and benchmarks call this before a measured region)."""
    for k in PLAN_STATS:
        PLAN_STATS[k] = 0
    _spans.clear_records()


class PlanCache(collections.OrderedDict):
    """Pattern-keyed plan cache: LRU entry cap + optional byte budget.

    Plans are cheap-ish to hold, but a long-running server sweeping configs
    on one tensor would otherwise grow the dict without bound — and plans
    are NOT all the same size: BELL slot tables and direct/ILU/AMG factor
    programs scale with the pattern, so the cache additionally tracks each
    plan's :meth:`SolverPlan.nbytes` estimate and evicts LRU-first until the
    resident total fits ``plan_cache_bytes`` (``None`` = entry-count-only).
    Both bounds are live reads of :mod:`repro.core.options` unless pinned by
    the constructor; evictions count in ``PLAN_STATS["evictions"]``.  Shared
    by ``with_values`` views exactly like the plain dict it replaces."""

    def __init__(self, cap: Optional[int] = None,
                 max_bytes: Optional[int] = None):
        super().__init__()
        self._cap = cap
        self._max_bytes = max_bytes
        self._sizes: Dict[Any, int] = {}
        self.total_bytes = 0

    @property
    def cap(self) -> int:
        return self._cap if self._cap is not None \
            else _options.current().plan_cache_cap

    @property
    def max_bytes(self) -> Optional[int]:
        return self._max_bytes if self._max_bytes is not None \
            else _options.current().plan_cache_bytes

    @staticmethod
    def _nbytes_of(value) -> int:
        try:
            return int(value.nbytes())
        except Exception:
            return 0

    def get(self, key, default=None):
        if key in self:
            self.move_to_end(key)
            return super().get(key)
        return default

    def _evict_oldest(self) -> None:
        old, _ = self.popitem(last=False)
        self.total_bytes -= self._sizes.pop(old, 0)
        count("evictions")

    def __setitem__(self, key, value):
        if key in self:            # replace = delete + fresh LRU insert
            super().__delitem__(key)
            self.total_bytes -= self._sizes.pop(key, 0)
        nb = self._nbytes_of(value)
        budget = self.max_bytes
        # the `while self` guard keeps at least the incoming entry resident:
        # a single plan larger than the whole budget still gets cached (and
        # evicts everything else) rather than thrashing on every get_plan
        while self and (len(self) >= self.cap or
                        (budget is not None and
                         self.total_bytes + nb > budget)):
            self._evict_oldest()
        super().__setitem__(key, value)
        self._sizes[key] = nb
        self.total_bytes += nb

    def __delitem__(self, key):
        super().__delitem__(key)
        self.total_bytes -= self._sizes.pop(key, 0)

    def clear(self):
        super().clear()
        self._sizes.clear()
        self.total_bytes = 0


@dataclasses.dataclass
class KernelPlan:
    """Analyze-time matvec kernel choice — a frozen plan artifact.

    ``choice``: "bell" | "stencil" | "coo"; ``reason`` records why (fill
    ratio, traced pattern, interpret-mode platform) for observability.
    ``interpret`` is the platform-resolved Pallas flag threaded into every
    kernel launch; ``bell``/``t_bell`` are the (meta, block_cols, perm)
    layouts of A and Aᵀ built in the same analyze pass so the adjoint's
    backward matvec shares the conversion (``t_bell is bell`` for symmetric
    patterns)."""
    choice: str
    reason: str
    interpret: bool
    bell: Optional[tuple] = None
    t_bell: Optional[tuple] = None


@spanned("analyze.kernel_plan")
def _build_kernel_plan(pattern, prefer: str) -> KernelPlan:
    """Freeze the matvec kernel for one analyzed pattern.

    ``prefer`` is the backend's kernel preference: "stencil" (stencil
    backend), "bell" (pallas backend — explicit opt-in, adopted even in
    interpret mode), "auto" (jnp backend — BELL only where it is profitable
    AND compiles), "coo" (never convert).  Runs inside ``analyze``'s
    ``ensure_compile_time_eval`` so the slot tables are concrete."""
    from ..kernels.solve_step import default_interpret
    interp = default_interpret()
    if prefer == "stencil":
        if pattern.stencil is not None:
            return KernelPlan("stencil", "stencil layout present", interp)
        prefer = "auto"
    if prefer == "coo":
        return KernelPlan("coo", "backend prefers segment-sum", interp)
    if prefer == "auto" and interp:
        # interpret-mode Pallas is an emulation — segment_sum wins on CPU
        return KernelPlan("coo", "interpret-mode platform", interp)
    concrete = not isinstance(pattern.row, jax.core.Tracer)
    bell = pattern.bell                     # construction-time layout, if any
    if bell is None:
        if not concrete:
            return KernelPlan("coo", "traced pattern (no eager conversion)",
                              interp)
        bell = build_bell(pattern.row, pattern.col, pattern.shape)
        count("kernel_plan")
    meta = bell[0]
    # minimum BELL fill (nnz over padded slot capacity) for the kernel plan
    # to adopt the block-ELL layout on its own; below it the padding work
    # outweighs the dense-tile win and the plan records a segment-sum
    # fallback.  The default (1/64) keeps 2-D Poisson (fill ≈ 0.02 at bm=8,
    # bn=128) on the kernel path.
    min_fill = _options.current().bell_min_fill
    if prefer != "bell" and meta.fill < min_fill:
        return KernelPlan(
            "coo", f"bell fill {meta.fill:.4f} < {min_fill:.4f}", interp)
    n, m = pattern.shape
    if n == m and pattern.props.get("symmetric", False):
        t_bell = bell                       # Aᵀ shares A's layout outright
    elif concrete:
        t_bell = build_bell(pattern.col, pattern.row, (m, n))
        count("kernel_plan")
    else:
        t_bell = None          # traced indices: adjoint takes the generic path
    return KernelPlan("bell", f"fill={meta.fill:.4f}", interp, bell, t_bell)


def _fuse_enabled(kp: Optional[KernelPlan]) -> bool:
    """Fused CG/BiCGStab step kernels (kernels/solve_step.py): "auto"
    enables them when the Pallas kernels compile (TPU/GPU) and keeps the
    plain XLA loops in interpret mode (CPU), where an emulated kernel per
    iteration would be a slowdown; "on"/"off" force either path.  Read at
    solve-trace time, not frozen into the plan."""
    mode = _options.current().fused_step
    if mode == "on":
        return True
    if mode == "off" or kp is None:
        return False
    return not kp.interpret


def _plan_matvec(plan: "SolverPlan", kp: KernelPlan, val) -> Callable:
    """Single-instance matvec closure through the kernel plan's choice."""
    n = plan.shape[0]
    if kp.choice == "stencil" and plan.stencil is not None:
        fn = _stencil_matvec(plan.stencil)
        return lambda x: fn(val, x)
    if kp.choice == "bell" and kp.bell is not None:
        from ..kernels import ops as kops
        meta, block_cols, perm = kp.bell
        interp = kp.interpret
        return lambda x: kops.bell_matvec(meta, block_cols, perm, val, x, n,
                                          interp)
    row, col = plan.row, plan.col
    return lambda x: coo_matvec(val, row, col, x, n)


class _Slot:
    """Stand-in for the ``i``-th array of a plan's lifted arrays."""
    __slots__ = ("i",)

    def __init__(self, i: int):
        self.i = i


def _map_leaves(obj, leaf: type, fn: Callable):
    """``obj`` with every ``leaf`` instance inside it replaced by
    ``fn(leaf)``.  Tuples, named tuples, lists, dicts and plain objects are
    rebuilt (objects by shallow copy) where something inside them changed,
    and come back as the same object where nothing did."""
    if isinstance(obj, leaf):
        return fn(obj)
    if isinstance(obj, (tuple, list)):
        parts = [_map_leaves(v, leaf, fn) for v in obj]
        if all(p is v for p, v in zip(parts, obj)):
            return obj
        return type(obj)._make(parts) if hasattr(obj, "_fields") \
            else type(obj)(parts)
    if isinstance(obj, dict):
        parts = {k: _map_leaves(v, leaf, fn) for k, v in obj.items()}
        if all(parts[k] is v for k, v in obj.items()):
            return obj
        return parts
    if hasattr(obj, "__dict__") and not callable(obj):
        parts = {k: _map_leaves(v, leaf, fn) for k, v in vars(obj).items()}
        if all(parts[k] is v for k, v in vars(obj).items()):
            return obj
        new = copy.copy(obj)
        vars(new).update(parts)
        return new
    return obj


def _plan_arrays(plan: "SolverPlan"):
    """``(skeleton, arrays)``: the plan attributes the solve stage reads
    (pattern, BELL layout, analyze artifacts) with each array replaced by a
    :class:`_Slot` into ``arrays``, so the solve program takes them as
    arguments.  Arrays a jitted function closes over are written into its
    program as literals: an AMG hierarchy's index programs or a segment-sum
    pattern would be lowered as megabytes of constants.  Cached on the
    plan, except where an array is a tracer (a pattern traced with its
    tensor): such a plan lives only as long as its trace."""
    if plan._program_args is not None:
        return plan._program_args
    arrays, slots = [], {}

    def lift(a):
        if id(a) not in slots:
            slots[id(a)] = _Slot(len(arrays))
            arrays.append(a)
        return slots[id(a)]

    view = {"row": plan.row, "col": plan.col, "bell": plan.bell,
            "artifacts": {k: v for k, v in plan.artifacts.items()
                          if k != "programs"}}
    lifted = (_map_leaves(view, jax.Array, lift), tuple(arrays))
    if not any(isinstance(a, jax.core.Tracer) for a in arrays):
        plan._program_args = lifted
    return lifted


@dataclasses.dataclass(frozen=True)
class SolverConfig:
    """Hashable solver configuration (goes through custom_vjp nondiff args)."""
    backend: str = "auto"
    method: str = "auto"
    tol: float = 1e-6
    atol: float = 0.0
    maxiter: int = DEFAULT_MAXITER
    precond: str = "jacobi"
    restart: int = 32            # gmres

    def resolved(self, A: SparseTensor) -> "SolverConfig":
        b, m = select_backend(A, self.backend, self.method)
        return dataclasses.replace(self, backend=b, method=m)

    def plan_key(self) -> Tuple[str, str, str]:
        """Plan-cache key: only the fields the analyze stage depends on.
        tol/atol/maxiter/restart steer the solve loop, not the symbolic
        setup — a tolerance sweep reuses one plan."""
        return (self.backend, self.method, self.precond)


# ---------------------------------------------------------------------------
# kernel (matvec) selection — shared by backends and the public ``matvec``
# ---------------------------------------------------------------------------

def _select_kernel(A: SparseTensor, backend: Optional[str] = None) -> str:
    if backend in (None, "auto"):
        if A.stencil is not None:
            return "stencil"
        if A.bell is not None and jax.default_backend() == "tpu":
            return "bell"
        return "coo"
    if backend == "stencil" and A.stencil is not None:
        return "stencil"
    if backend == "pallas" and A.bell is not None:
        return "bell"
    return "coo"


def _stencil_matvec(meta) -> Callable:
    """The stencil kernel's SpMV as a function of (val, x): the layout's
    metadata picks the 2-D 5-point or the 3-D 27-point kernel."""
    from ..kernels import ops as kops
    from ..kernels.stencil27 import Stencil27Meta
    if isinstance(meta, Stencil27Meta):
        return partial(kops.stencil27_matvec, meta)
    return partial(kops.stencil5_matvec, meta)


def _kernel_values(plan, val):
    """Values as the solve stage holds them: the 3-D stencil kernel's
    planes are laid out once here, in setup, not on every SpMV."""
    kp = plan.artifacts.get("kernel")
    if kp is not None and kp.choice == "stencil":
        from ..kernels.ops import stencil27_planes
        from ..kernels.stencil27 import Stencil27Meta
        if isinstance(plan.stencil, Stencil27Meta):
            return stencil27_planes(plan.stencil, val)
    return val


def _kernel_fn(A: SparseTensor, kernel: str) -> Callable:
    """Single-instance SpMV as a function of (val, x) — vmap-able."""
    if kernel == "stencil" and A.stencil is not None:
        return _stencil_matvec(A.stencil)
    if kernel == "bell" and A.bell is not None:
        from ..kernels import ops as kops
        meta, block_cols, perm = A.bell
        n = A.shape[0]
        return lambda v, x: kops.bell_matvec(meta, block_cols, perm, v, x, n)
    row, col, n = A.row, A.col, A.shape[0]
    return lambda v, x: coo_matvec(v, row, col, x, n)


def make_matvec(A: SparseTensor, backend: Optional[str] = None) -> Callable:
    """Closure ``x ↦ A @ x`` through the selected kernel (unbatched)."""
    fn = _kernel_fn(A, _select_kernel(A, backend))
    return lambda x: fn(A.val, x)


def matvec(A: SparseTensor, x, backend: Optional[str] = None):
    """A @ x — batched values and/or rhs route through the SAME selected
    kernel via vmap (shared-pattern batching keeps the kernel layout)."""
    kernel = _select_kernel(A, backend)
    batched = bool(A.batch_shape) or (hasattr(x, "ndim") and x.ndim > 1)
    if not batched:
        return _kernel_fn(A, kernel)(A.val, x)
    if kernel == "coo":
        return coo_matvec(A.val, A.row, A.col, x, A.shape[0])
    fn = _kernel_fn(A, kernel)
    batch = jnp.broadcast_shapes(A.batch_shape, x.shape[:-1])
    val = jnp.broadcast_to(A.val, batch + A.val.shape[-1:])
    xx = jnp.broadcast_to(x, batch + x.shape[-1:])
    y = jax.vmap(fn)(val.reshape((-1, val.shape[-1])),
                     xx.reshape((-1, xx.shape[-1])))
    return y.reshape(batch + (A.shape[0],))


# ---------------------------------------------------------------------------
# backend classes — each exposes the analyze/setup/solve stages
# ---------------------------------------------------------------------------

class Backend:
    """A solver backend.  Subclasses implement the three plan stages.

    ``analyze(cfg, pattern)`` — eager, values-free; returns the artifact dict
    stored on the plan.  ``setup(plan, A)`` — traced-safe, values-dependent.
    ``solve(plan, state, A, b, x0)`` — one un-differentiated solve.
    """
    name: str = "abstract"
    methods: Tuple[str, ...] = ()
    handles_batch = False       # True: backend does its own batch vmapping
    cache_setup = False         # True: memoize setup() per values array

    def applicable(self, A: SparseTensor) -> bool:
        return True

    def transpose_plan(self, plan: "SolverPlan") -> Optional["SolverPlan"]:
        """Optionally build the adjoint plan from this plan's own artifacts
        (zero re-analysis).  ``None`` falls back to analyzing a transposed
        sibling pattern — the generic non-symmetric path."""
        return None

    def default_method(self, A: SparseTensor) -> str:
        sym = A.props.get("symmetric", False)
        spd = A.props.get("spd_hint", False)
        return "cg" if (spd or sym) else "bicgstab"

    def analyze(self, cfg: SolverConfig, pattern) -> dict:
        return {}

    def pallas_kernels(self, plan: "SolverPlan") -> Tuple[str, ...]:
        """The compiled Pallas kernels this plan launches (none by default);
        setup checks the values' dtype against them."""
        return ()

    def setup(self, plan: "SolverPlan", A: SparseTensor):
        return None

    def solve(self, plan: "SolverPlan", state, A: SparseTensor, b, x0,
              cfg: SolverConfig):
        raise NotImplementedError


class DenseBackend(Backend):
    name = "dense"
    methods = ("lu", "cholesky")
    # setup is just the (vmappable) densification — memoizing it makes a
    # stacked-values batch densify once per stack instead of once per solve
    cache_setup = True

    def applicable(self, A):
        return A.shape[0] == A.shape[1]

    def default_method(self, A):
        return "cholesky" if A.props.get("spd_hint", False) else "lu"

    def setup(self, plan, A):
        return A.todense()

    def solve(self, plan, dense, A, b, x0, cfg):
        return _solvers.dense_solve(dense, b, cfg.method)


class DirectBackend(Backend):
    """Sparse direct LDLᵀ/LU with a cached symbolic factorization — the
    cuDSS-analogue path (paper §3.1/§3.2.3).  ``analyze`` runs the eager
    symbolic stage of :mod:`repro.core.direct` once per pattern; ``setup``
    is the jit/vmap-safe numeric refactorization (memoized per values array
    via ``cache_setup``); ``solve`` is two level-scheduled triangular sweeps.
    The adjoint reuses the forward factors: symmetric patterns share the plan
    outright, non-symmetric ones get a shared-artifact transpose plan whose
    solve runs the mirrored (Uᵀ, Lᵀ) sweeps — zero refactorizations either way.
    """
    name = "direct"
    methods = ("ldlt", "lu")
    cache_setup = True

    def applicable(self, A):
        n, m = A.shape
        if n != m:
            return False
        if isinstance(A.row, jax.core.Tracer) or \
                isinstance(A.col, jax.core.Tracer):
            return False        # symbolic analysis needs a concrete pattern
        if "struct_full_diag" not in A.props:
            A.props["struct_full_diag"] = has_full_diagonal(A.row, A.col, n)
        return A.props["struct_full_diag"]   # no pivoting: pivots must exist

    def default_method(self, A):
        return "ldlt" if A.props.get("symmetric", False) else "lu"

    def analyze(self, cfg, pattern):
        if cfg.method == "ldlt" and not pattern.props.get("symmetric", False):
            raise ValueError(
                "method='ldlt' needs symmetric values; use method='lu'")
        art = _direct.symbolic_factor(
            np.asarray(pattern.row), np.asarray(pattern.col),
            pattern.shape[0],
            # indefinite-hinted systems get static Bunch–Kaufman 2x2 pivot
            # blocks (chosen at analyze time) instead of relying on the
            # zero-pivot perturbation stopgap at factor time
            pivot_blocks=("auto" if pattern.props.get("indefinite_hint")
                          else None))
        return {"direct": art, "transposed": False}

    def pallas_kernels(self, plan):
        if plan.artifacts["direct"].snode is None or \
                not _direct._sn_use_pallas():
            return ()
        return ("panel_factor", "schur_update", "block_trsv")

    def setup(self, plan, A):
        count("factorize")
        return _direct.numeric_factor(plan.artifacts["direct"], A.val)

    def solve(self, plan, C, A, b, x0, cfg):
        x = _direct.factored_solve(plan.artifacts["direct"], C, b,
                                   transposed=plan.artifacts["transposed"])
        r = b - coo_matvec(A.val, A.row, A.col, x, A.shape[0])
        # a direct solve is judged by its normwise backward error, which
        # f32 reaches at any size; ||r||/||b|| stalls near eps·||A||·||x||
        berr = backward_error(A.val, A.row, A.col, A.shape[0], x, b, r=r)
        converged = (berr <= cfg.tol) | (jnp.max(jnp.abs(r)) <= cfg.atol)
        return x, _solvers.SolveInfo(iters=jnp.asarray(1),
                                     resnorm=jnp.linalg.norm(r),
                                     converged=converged)

    def transpose_plan(self, plan):
        """Adjoint plan sharing THIS plan's symbolic artifacts and numeric
        factors (the setup memo is shared): solving Aᵀλ = g runs the Uᵀ/Lᵀ
        sweeps on the forward factorization."""
        tp = SolverPlan.__new__(SolverPlan)
        tp.cfg = plan.cfg
        tp.backend = plan.backend
        tp.row, tp.col = plan.col, plan.row
        tp.shape = (plan.shape[1], plan.shape[0])
        tp.props = dict(plan.props)
        tp.bell, tp.stencil = None, None
        tp._cache = {tp.cfg.plan_key(): tp}
        tp._tplan = plan
        tp._setup_memo = plan._setup_memo       # forward factors reused
        tp.artifacts = dict(plan.artifacts,
                            transposed=not plan.artifacts["transposed"])
        return tp


class IterativeBackend(Backend):
    """Shared machinery for Krylov backends: kernel matvec + preconditioner.

    ``cache_setup``: the preconditioner refresh (block inverses, Lanczos
    spectrum bounds, ILU refactorization, MG hierarchy) is memoized per
    values array exactly like the direct backend's numeric factorization —
    a tolerance sweep or the symmetric adjoint backward re-traces nothing
    (``PLAN_STATS['setup_reuse']``); new values still refresh.
    """
    kernel = "auto"             # kernel-plan preference (see _build_kernel_plan)
    methods = ("cg", "bicgstab", "gmres", "block_cg")
    cache_setup = True

    def analyze(self, cfg, pattern):
        return {
            "kernel": _build_kernel_plan(pattern, self.kernel),
            "precond": _precond.PreconditionerPlan(
                cfg.precond, pattern.row, pattern.col, pattern.shape,
                stencil=pattern.stencil)}

    def pallas_kernels(self, plan):
        kp = plan.artifacts.get("kernel")
        names = ()
        if kp is not None and kp.choice != "coo" and not kp.interpret:
            names += (kp.choice,)
        if _fuse_enabled(kp) and plan.cfg.method in ("cg", "bicgstab"):
            names += ("fused step",)
        return names

    def _matvec_from_val(self, plan, val) -> Callable:
        kp = plan.artifacts.get("kernel")
        if kp is not None:
            return _plan_matvec(plan, kp, val)
        # plan built without a kernel artifact: plan carries the same
        # row/col/bell/stencil attributes _kernel_fn reads off a tensor
        fn = _kernel_fn(plan, self.kernel)
        return lambda x: fn(val, x)

    def setup(self, plan, A):
        """Values-dependent setup as an ARRAYS-ONLY pytree.

        Returns ``(val, pstate, dinv)`` — the (possibly transpose-remapped)
        values in the kernel's layout (:func:`_kernel_values`), the
        preconditioner's refresh_state pytree (block inverses, spectrum
        bounds, MG/AMG hierarchy arrays, ILU factors), and the
        diagonal-inverse vector for the fused step kernels (None when the
        apply is not a diagonal scale).  No closures: a stacked batch of
        shared-pattern instances runs ONE ``jax.vmap`` of this method
        (:meth:`SolverPlan.setup_batch`) and the solve stage rebuilds the
        matvec/apply closures per lane.  The fuse decision itself stays a
        solve-time read of ``options.fused_step``."""
        val = _kernel_values(plan, A.val)
        mv = self._matvec_from_val(plan, val)
        pre = plan.artifacts["precond"]
        with span("precond.refresh"):
            pstate = pre.refresh_state(A, mv, val)
        dinv = pre.fused_diag(A)
        return val, pstate, dinv

    def solve(self, plan, state, A, b, x0, cfg):
        program, args = self.solve_program(plan, state, b, x0, cfg)
        return program(*args)

    def solve_program(self, plan, state, b, x0, cfg):
        """The solve stage as ``(program, args)``; ``program(*args)`` returns
        ``(x, SolveInfo)``.

        ``program`` is one ``jax.jit`` function per plan and key (method,
        fuse flag, interpret flag, warm start, gmres restart), kept in
        ``plan.artifacts["programs"]``: it builds the matvec and the
        preconditioner apply from the state and runs the Krylov call,
        prologue included, so a warm solve dispatches one executable and
        traces nothing.  Every array it reads enters as an argument — the
        setup state, ``b``, ``x0``, the plan's analyze-time index arrays
        (:func:`_plan_arrays`) — and tol, atol and maxiter are traced
        scalars, so a tolerance sweep reuses the program.  Under an outer
        trace the call inlines."""
        kp = plan.artifacts.get("kernel")
        fuse = _fuse_enabled(kp)
        interp = kp.interpret if kp is not None else None
        method = cfg.method
        if method not in ("cg", "bicgstab", "gmres", "block_cg"):
            raise ValueError(
                f"unknown method {method!r} for backend {cfg.backend!r}")
        restart = cfg.restart if method == "gmres" else None
        maxiter = max(cfg.maxiter // restart, 1) if restart else cfg.maxiter
        loop = (float(cfg.tol), float(cfg.atol), int(maxiter))
        skeleton, arrays = _plan_arrays(plan)
        programs = plan.artifacts.setdefault("programs", {})
        key = (method, fuse, interp, x0 is not None, restart)
        if key not in programs:
            def run(arrays, *args):
                count("solve_program_build")
                view = copy.copy(plan)
                vars(view).update(_map_leaves(
                    skeleton, _Slot, lambda s: arrays[s.i]))
                return self._stage(view, method, fuse, interp, restart, *args)

            programs[key] = jax.jit(run)
        count("solve_program_call")
        return programs[key], (arrays, state, b, x0) + loop

    def _stage(self, plan, method, fuse, interp, restart, state, b, x0, tol,
               atol, maxiter):
        val, pstate, dinv = state
        # rebuild from the STATE's values, not A.val: transpose plans remap
        # the forward values in setup (_StencilTransposeBackend) and batched
        # solves feed per-lane state slices
        mv = self._matvec_from_val(plan, val)
        with span("precond.make_apply"):
            M = plan.artifacts["precond"].make_apply(pstate, mv, fused=fuse,
                                                     interpret=interp)
        kw = dict(M=M, tol=tol, atol=atol, maxiter=maxiter)
        if method == "block_cg":
            single = b.ndim == 1
            B = b[None] if single else b
            X0 = None if x0 is None else (x0[None] if single else x0)
            with span("krylov.block_cg"):
                X, info = _solvers.block_cg(mv, B, X0, **kw)
            if single:
                return X[0], _solvers.SolveInfo(info.iters, info.resnorm[0],
                                                info.converged[0])
            return X, info
        if method == "gmres":
            fn = _solvers.gmres
            kw.update(restart=restart)
        elif fuse:
            fn = getattr(_solvers, method + "_fused")
            kw.update(dinv=dinv, interpret=interp)
        else:
            fn = getattr(_solvers, method)
        with span("krylov." + fn.__name__):
            return fn(mv, b, x0, **kw)

    def transpose_plan(self, plan):
        """Adjoint plan sharing THIS plan's kernel layouts: the kernel plan
        built Aᵀ's block-ELL slot table in the same analyze pass (``t_bell``),
        so the backward matvec hits the same Pallas kernel with zero
        re-analysis.  Only for plans that adopted BELL — COO-choice plans
        have no layout to share and fall back to the generic transposed
        sibling; ``mg`` needs the stencil view the sibling would drop."""
        kp = plan.artifacts.get("kernel")
        if kp is None or kp.choice != "bell" or kp.t_bell is None:
            return None
        n, m = plan.shape
        if n != m or plan.cfg.precond == "mg":
            return None
        tp = SolverPlan.__new__(SolverPlan)
        tp.cfg = plan.cfg
        tp.backend = plan.backend
        tp.row, tp.col = plan.col, plan.row
        tp.shape = (m, n)
        tp.props = dict(plan.props)
        tp.bell, tp.stencil = kp.t_bell, None
        tp._cache = {tp.cfg.plan_key(): tp}
        tp._tplan = plan
        tp._setup_memo = {}      # Aᵀ preconditioner state differs
        with jax.ensure_compile_time_eval():
            tp.artifacts = {
                "kernel": dataclasses.replace(kp, bell=kp.t_bell,
                                              t_bell=kp.bell),
                "precond": _precond.PreconditionerPlan(
                    plan.cfg.precond, tp.row, tp.col, tp.shape,
                    stencil=None)}
        return tp


class JnpBackend(IterativeBackend):
    """General COO backend.  Its kernel plan is "auto": segment-sum on
    interpret-mode platforms (CPU) and for low-fill patterns, block-ELL
    Pallas where the conversion pays off on compiled hardware."""
    name = "jnp"
    kernel = "auto"


class PallasBackend(IterativeBackend):
    """Explicit block-ELL opt-in: the kernel plan adopts BELL regardless of
    fill or platform (interpret mode included — parity tests run here)."""
    name = "pallas"
    kernel = "bell"

    def applicable(self, A):
        # a construction-time layout OR a concrete pattern the kernel plan
        # can convert at analyze time
        return A.bell is not None or not isinstance(A.row, jax.core.Tracer)


class StencilBackend(IterativeBackend):
    name = "stencil"
    kernel = "stencil"
    methods = ("cg", "bicgstab")

    def applicable(self, A):
        return A.stencil is not None

    def transpose_plan(self, plan):
        """Adjoint plan that KEEPS the fast stencil kernel (no COO fallback):
        Aᵀ of a 5-point stencil operator is the same operator with its
        coupling planes exchanged and shifted (N'↔S, W'↔E, values taken from
        the neighbour's opposing slot).  The shift is a pure gather frozen at
        analyze time (``tmap``, with a zero slot for the domain boundary);
        the transpose plan's setup maps the FORWARD values through it and
        then runs the ordinary stencil setup — the same kernel, the same
        preconditioner machinery (``precond='mg'`` included), zero
        re-analysis."""
        from ..kernels.stencil5 import Stencil5Meta
        meta = plan.stencil
        if not isinstance(meta, Stencil5Meta) or meta.nx != meta.ny:
            return None
        ng = meta.nx
        if plan.shape != (ng * ng, ng * ng):
            return None
        idx = np.arange(5 * ng * ng).reshape(5, ng, ng)
        zslot = 5 * ng * ng
        tmap = np.empty_like(idx)
        tmap[0] = idx[0]                       # C' = C
        # plane order (C, N, S, W, E), N = coupling to (x-1, y):
        # Aᵀ[i, i_north] = A[i_north, i] = S-plane at the north neighbour
        tmap[1, 1:, :] = idx[2, :-1, :]
        tmap[1, 0, :] = zslot
        tmap[2, :-1, :] = idx[1, 1:, :]        # S' from N shifted up
        tmap[2, -1, :] = zslot
        tmap[3, :, 1:] = idx[4, :, :-1]        # W' from E shifted right
        tmap[3, :, 0] = zslot
        tmap[4, :, :-1] = idx[3, :, 1:]        # E' from W shifted left
        tmap[4, :, -1] = zslot

        tp = SolverPlan.__new__(SolverPlan)
        tp.cfg = plan.cfg
        tp.backend = _STENCIL_T
        # the transposed operator in PLANE layout shares the forward's
        # pattern arrays (vc_pattern of the same grid): values are remapped,
        # indices are not — COO and stencil views stay consistent
        tp.row, tp.col = plan.row, plan.col
        tp.shape = plan.shape
        tp.props = dict(plan.props)
        tp.bell, tp.stencil = None, plan.stencil
        tp._cache = {tp.cfg.plan_key(): tp}
        tp._tplan = plan
        tp._setup_memo = {}        # Aᵀ values differ from the forward values
        with jax.ensure_compile_time_eval():
            tp.artifacts = {
                "tmap": jnp.asarray(tmap.reshape(-1), jnp.int32),
                "kernel": _build_kernel_plan(tp, "stencil"),
                "precond": _precond.PreconditionerPlan(
                    plan.cfg.precond, plan.row, plan.col, plan.shape,
                    stencil=plan.stencil)}
        return tp


class _StencilTransposeBackend(StencilBackend):
    """Internal backend of the stencil transpose plan: identical solve path,
    but setup first remaps the forward values into transposed planes."""
    name = "stencil"            # reported name matches the forward backend

    def setup(self, plan, A):
        padded = jnp.concatenate([A.val, jnp.zeros((1,), A.val.dtype)])
        return super().setup(plan, plan.matrix(padded[plan.artifacts["tmap"]]))


_STENCIL_T = _StencilTransposeBackend()


class DistBackend(Backend):
    """Distributed mesh backend (paper §3.3) — ``DSparseTensor`` as a
    first-class citizen of the plan engine.

    ``analyze`` runs ONCE per (global pattern, mesh, partition) and freezes
    everything eager: partition bounds, the halo program (axis size +
    ppermute perms), the Aᵀ partition for non-symmetric adjoints
    (``PLAN_STATS['t_partition']``), and a
    :class:`~repro.core.precond.DistPreconditionerPlan` (``jacobi`` or
    shard-local overlapping-Schwarz ``schwarz`` sharing the direct
    machinery's ILU(0)/IC(0) programs).  ``setup`` is the traced-safe
    preconditioner refresh on the stacked values, memoized per values array
    (``cache_setup``); ``solve`` is the shard_map'd Krylov loop.  The heavy
    lifting lives in :mod:`repro.core.distributed` (imported lazily: that
    module imports this registry at module level, so the cycle must break
    here — and plain single-device use never loads the mesh machinery)."""
    name = "dist"
    methods = ("cg", "bicgstab", "pipelined_cg")
    handles_batch = True        # (P, n_loc) stacking is sharding, not batch
    cache_setup = True

    def applicable(self, A):
        return getattr(A, "mesh", None) is not None

    def default_method(self, A):
        return "cg" if A.props.get("symmetric", False) else "bicgstab"

    def analyze(self, cfg, pattern):
        from . import distributed as _dist
        return _dist.dist_analyze(cfg, pattern)

    def setup(self, plan, A):
        from . import distributed as _dist
        return _dist.dist_setup(plan, A)

    def solve(self, plan, state, A, b, x0, cfg):
        from . import distributed as _dist
        return _dist.dist_solve(plan, state, A, b, x0, cfg)

    def transpose_plan(self, plan):
        from . import distributed as _dist
        return _dist.dist_transpose_plan(plan)


BACKENDS: Dict[str, Backend] = {
    b.name: b for b in (DenseBackend(), DirectBackend(), JnpBackend(),
                        PallasBackend(), StencilBackend(), DistBackend())}


def register_backend(name: str, backend: Backend):
    """Register a :class:`Backend` instance under ``name`` (it becomes
    selectable as ``backend=name``)."""
    backend.name = name
    BACKENDS[name] = backend


def select_backend(A: SparseTensor, backend: str, method: str):
    """Device- and size-aware auto-dispatch (paper §3.1 rules, TPU constants):
    (i) honor explicit overrides; (ii) dense-direct below the dense budget;
    (iii) sparse-direct (cached symbolic factorization) for mid-size systems
    and whenever the caller hints ill-conditioning (Krylov stalls there);
    (iv) iterative above, preferring the Pallas/stencil SpMV when the tensor
    carries that layout; CG when SPD-ish, BiCGStab otherwise."""
    n = A.shape[0]
    platform = jax.default_backend()
    opts = _options.current()
    if backend == "auto":
        if A.stencil is not None:
            backend = "stencil"
        elif n <= opts.dense_budget and not A.batch_shape and \
                BACKENDS["dense"].applicable(A):
            backend = "dense"
        elif A.props.get("illcond_hint", False) \
                and n <= 4 * opts.direct_budget \
                and BACKENDS["direct"].applicable(A):
            # the hint is an explicit opt-in, so it buys a wider direct
            # window — the caller accepts the one-time (minutes-scale at the
            # ceiling) symbolic analysis over a stalling Krylov solve
            backend = "direct"
        elif A.bell is not None and platform == "tpu":
            backend = "pallas"
        elif n <= opts.direct_budget and BACKENDS["direct"].applicable(A):
            backend = "direct"
        else:
            backend = "jnp"
    if method == "auto":
        method = BACKENDS[backend].default_method(A) \
            if backend in BACKENDS else "cg"
    return backend, method


def make_config(A: SparseTensor, *, backend=None, method=None, tol=1e-6,
                atol=0.0, maxiter=None, precond="jacobi", restart=32) -> SolverConfig:
    cfg = SolverConfig(backend=backend or "auto", method=method or "auto",
                       tol=tol, atol=atol,
                       maxiter=maxiter or DEFAULT_MAXITER,
                       precond=precond, restart=restart)
    return cfg.resolved(A)


# ---------------------------------------------------------------------------
# SolverPlan — the analyze(pattern) product
# ---------------------------------------------------------------------------

class SolverPlan:
    """Reusable symbolic setup for one (sparsity pattern, SolverConfig).

    Holds only pattern-level state — row/col indices, shape, detected
    properties, kernel layouts, and the backend's analyze artifacts — never
    values, so one plan serves every ``with_values`` refresh, every element
    of a shared-pattern batch, and the adjoint solve of ``jax.grad``.

    Mesh-aware: for distributed tensors the plan additionally freezes the
    ``Mesh`` and ``DistMeta`` (``mesh``/``dmeta``) so the ``dist`` backend's
    stages never re-derive partition state; single-device plans carry None.
    """

    mesh = None          # jax.sharding.Mesh for dist-backed plans
    dmeta = None         # repro.core.distributed.DistMeta for dist plans
    _program_args = None  # the solve program's lifted arrays (_plan_arrays)

    @spanned("plan.analyze")
    def __init__(self, cfg: SolverConfig, A: SparseTensor,
                 cache: Optional[dict] = None):
        if cfg.backend not in BACKENDS:
            raise ValueError(f"unknown backend {cfg.backend!r}")
        self.cfg = cfg              # first-seen config; solve-loop knobs
        self.backend = BACKENDS[cfg.backend]   # (tol/maxiter) may be overridden per call
        if self.backend.methods and cfg.method not in self.backend.methods:
            raise ValueError(
                f"method {cfg.method!r} not supported by backend "
                f"{cfg.backend!r} (supported: {self.backend.methods})")
        self.row, self.col = A.row, A.col
        self.shape = tuple(A.shape)
        self.props = dict(A.props)
        self.bell = A.bell
        self.stencil = A.stencil
        self.mesh = getattr(A, "mesh", None)
        self.dmeta = getattr(A, "meta", None)
        self._cache = cache if cache is not None else {cfg.plan_key(): self}
        self._tplan: Optional["SolverPlan"] = None
        self._setup_memo: dict = {}
        count("analyze")
        # analyze is eager BY CONTRACT: plans outlive any single trace, so
        # artifact arrays built here must be concrete even when the first
        # solve happens inside jit/grad — a traced constant stored on the
        # plan would leak into (and break) every later trace
        with jax.ensure_compile_time_eval():
            self.artifacts = self.backend.analyze(cfg, self)

    # -- stage ❷: values-dependent setup (traced-safe) ----------------------
    def _memo_lookup(self, slot: str, key_array):
        """Per-values-array memo hit: identity of the array is the key."""
        hit = self._setup_memo.get(slot)
        if hit is not None and hit[0]() is key_array:
            count("setup_reuse")
            return hit[1]
        return None

    def _memo_store(self, slot: str, key_array, state) -> None:
        # memo-poisoning guard: when a CONCRETE values array is set up
        # inside a staging trace (a jitted solve closing over the matrix),
        # the state embeds tracers — possibly hidden inside matvec or
        # preconditioner closures, invisible to any leaf inspection — and
        # storing it would leak them into the next eager solve.  The probe
        # asks the ambient trace directly: does an op on a fresh constant
        # come back traced?  (Eager jax.grad says no — its fwd runs ops on
        # concrete primals concretely, so that state stays cacheable.)
        staging = isinstance(jnp.zeros(()) + 0.0, jax.core.Tracer)
        if staging and not isinstance(key_array, jax.core.Tracer):
            return
        memo = self._setup_memo
        box = {}

        def _drop(_, m=memo, b=box, s=slot):
            # evict ONLY our own entry: a dead values array must not pop
            # a successor that already replaced it (the old entry's ref
            # can die between the successor's fwd store and bwd lookup)
            if m.get(s) is b.get("entry"):
                m.pop(s, None)

        box["entry"] = (weakref.ref(key_array, _drop), state)
        memo[slot] = box["entry"]

    def check_dtype(self, dtype) -> None:
        """Refuse values the plan's compiled Pallas kernels cannot take
        (f64 on TPU), naming the dtype and the kernels."""
        from ..kernels.solve_step import check_kernel_dtype, default_interpret
        kernels = self.backend.pallas_kernels(self)
        if kernels:
            check_kernel_dtype(", ".join(kernels), dtype, default_interpret())

    @spanned("plan.setup")
    def setup(self, A: SparseTensor):
        """Run (or reuse) the backend's values-dependent setup.

        Backends with ``cache_setup`` (the direct backend's numeric
        factorization, the iterative preconditioner refresh, the distributed
        backend) memoize the state per values *array*: a tolerance sweep, a
        continuation loop, and the adjoint backward all reuse ONE setup —
        identity of ``A.val`` is the key, which holds across custom_vjp
        forward/backward in both eager and jit traces.  The memo is
        single-slot per kind (latest values win), shared with the transpose
        plan where that is sound (direct: Aᵀ solves never refactorize), and
        holds the values array weakly: a dead array can never produce a hit,
        so a stale entry is harmless.  The weak eviction only actually fires
        when the state does not itself capture the values array; setup
        states are array pytrees that keep the LATEST values array (or trace
        tracer) alive per plan until the next setup replaces it — a bounded,
        single-slot residency."""
        if self.backend.cache_setup:
            hit = self._memo_lookup("state", A.val)
            if hit is not None:
                return hit
        self.check_dtype(A.val.dtype)
        count("setup")
        state = self.backend.setup(self, A)
        if self.backend.cache_setup:
            self._memo_store("state", A.val, state)
        return state

    @spanned("plan.setup")
    def setup_batch(self, A: SparseTensor):
        """Batched setup over stacked values — ONE vmapped trace, memoized.

        ``A.val`` carries leading batch dims ``(..., nnz)`` sharing this
        plan's pattern.  The per-values memo is batch-aware: it keys on the
        STACKED array's identity (slot ``"batch_state"``), so a tolerance
        sweep or the adjoint backward over the same batch reuses one setup,
        and ``PLAN_STATS["setup"]`` counts one setup for the whole batch.
        The backend's per-instance setup runs under ``jax.vmap`` directly —
        numeric factorizations, block inverses, Galerkin products, and MG
        hierarchies all batch through their array-only state pytrees."""
        val = A.val
        if self.backend.cache_setup:
            hit = self._memo_lookup("batch_state", val)
            if hit is not None:
                return hit
        self.check_dtype(val.dtype)
        count("setup")
        flat = val.reshape((-1, val.shape[-1]))
        state = jax.vmap(
            lambda v: self.backend.setup(self, self.matrix(v)))(flat)
        if self.backend.cache_setup:
            self._memo_store("batch_state", val, state)
        return state

    # -- stage ❸: solve ------------------------------------------------------
    def _run(self, state, A: SparseTensor, b, x0, cfg: SolverConfig):
        with span("plan.solve"):
            return self.backend.solve(self, state, A, b, x0, cfg)

    def solve_single(self, A: SparseTensor, b, x0=None, state=None,
                     cfg: Optional[SolverConfig] = None):
        cfg = cfg if cfg is not None else self.cfg
        state = self.setup(A) if state is None else state
        return self._run(state, A, b, x0, cfg)

    def solve(self, A: SparseTensor, b, x0=None,
              cfg: Optional[SolverConfig] = None):
        """One un-differentiated solve; shared-pattern batches are vmapped
        here so the adjoint layer never needs to care.  ``cfg`` overrides the
        solve-loop knobs (tol/atol/maxiter/restart) without re-analyzing."""
        cfg = cfg if cfg is not None else self.cfg
        if self.backend.handles_batch:
            return self._run(self.setup(A), A, b, x0, cfg)
        batch = jnp.broadcast_shapes(A.batch_shape, b.shape[:-1])
        if batch and not A.batch_shape:
            # multi-rhs on ONE matrix: a single setup (one factorization /
            # preconditioner build) serves every right-hand side.
            state = self.setup(A)
            fb = b.reshape((-1, b.shape[-1]))
            if cfg.method == "block_cg":
                # the whole (k, n) block goes down in ONE coupled solve —
                # k matvecs per iteration as one batched sweep, Krylov
                # directions shared across right-hand sides
                fx0 = None if x0 is None else jnp.broadcast_to(
                    x0, batch + x0.shape[-1:]).reshape(fb.shape)
                xs, infos = self._run(state, A, fb, fx0, cfg)
                return xs.reshape(batch + (b.shape[-1],)), infos

            def one(rhs, xx0=None):
                return self._run(state, A, rhs, xx0, cfg)

            if x0 is None:
                xs, infos = jax.vmap(lambda rhs: one(rhs))(fb)
            else:
                fx0 = jnp.broadcast_to(x0, batch + x0.shape[-1:]).reshape(fb.shape)
                xs, infos = jax.vmap(one)(fb, fx0)
            return xs.reshape(batch + (b.shape[-1],)), infos
        if batch:
            val = jnp.broadcast_to(A.val, batch + A.val.shape[-1:])
            bb = jnp.broadcast_to(b, batch + b.shape[-1:])
            fv = val.reshape((-1, val.shape[-1]))
            fb = bb.reshape((-1, bb.shape[-1]))
            fx0 = None if x0 is None else jnp.broadcast_to(
                x0, batch + x0.shape[-1:]).reshape(fb.shape)
            if self.backend.cache_setup:
                # batched values: ONE vmapped setup over the stack (memoized
                # on the stacked array — see setup_batch), then a vmapped
                # solve over per-lane state slices.  Setup never re-runs
                # inside the solve vmap, so a batch costs one traced
                # factorization/preconditioner build, not B of them.
                Ab = A if A.val.ndim > 1 and A.val.shape[:-1] == batch \
                    else self.matrix(fv)
                states = self.setup_batch(Ab)

                def one(st, v, rhs, xx0=None):
                    return self._run(st, self.matrix(v), rhs, xx0, cfg)

                if fx0 is None:
                    xs, infos = jax.vmap(
                        lambda st, v, rhs: one(st, v, rhs))(states, fv, fb)
                else:
                    xs, infos = jax.vmap(one)(states, fv, fb, fx0)
            else:
                def one_nostate(v, rhs, xx0=None):
                    return self.solve_single(self.matrix(v), rhs, xx0,
                                             cfg=cfg)

                if fx0 is None:
                    xs, infos = jax.vmap(
                        lambda v, rhs: one_nostate(v, rhs))(fv, fb)
                else:
                    xs, infos = jax.vmap(one_nostate)(fv, fb, fx0)
            return xs.reshape(batch + (b.shape[-1],)), infos
        return self.solve_single(A, b, x0, cfg=cfg)

    # -- pattern helpers -----------------------------------------------------
    def nbytes(self) -> int:
        """Estimated resident bytes of this plan's analyze artifacts — BELL
        slot tables, direct/ILU symbolic programs, AMG index programs, plus
        the pattern arrays they reference.  This is the size the
        :class:`PlanCache` byte budget (``options.plan_cache_bytes``) counts
        against; an estimate (arrays shared between plans are counted in
        each), not an allocator measurement."""
        seen = set()
        total = 0

        def visit(obj):
            nonlocal total
            if obj is None or callable(obj) or isinstance(
                    obj, (int, float, bool, str, bytes, complex)):
                return
            if id(obj) in seen:
                return
            seen.add(id(obj))
            nb = getattr(obj, "nbytes", None)
            if isinstance(nb, (int, np.integer)):
                total += int(nb)
                return
            if isinstance(obj, dict):
                for v in obj.values():
                    visit(v)
            elif isinstance(obj, (tuple, list)):
                for v in obj:
                    visit(v)
            elif dataclasses.is_dataclass(obj):
                for f in dataclasses.fields(obj):
                    visit(getattr(obj, f.name))
            elif hasattr(obj, "__dict__"):
                for v in vars(obj).values():
                    visit(v)

        try:
            visit(self.artifacts)
            visit(self.bell)
            visit((self.row, self.col))
        except Exception:
            pass
        return total

    def matrix(self, val) -> SparseTensor:
        """SparseTensor view of this plan's pattern carrying ``val`` —
        shares the plan cache, so nested solves hit this plan."""
        obj = SparseTensor.__new__(SparseTensor)
        obj.val = val
        obj.row, obj.col = self.row, self.col
        obj.shape = self.shape
        obj.props = dict(self.props)
        obj.bell, obj.stencil = self.bell, self.stencil
        obj._plans = self._cache
        return obj

    def transpose(self) -> "SolverPlan":
        """Plan for the adjoint system Aᵀλ = g (paper §3.2.3).

        Symmetric pattern → the SAME plan (layouts + preconditioner build
        shared).  A backend may instead derive the adjoint plan from its own
        artifacts (``Backend.transpose_plan`` — the direct backend shares its
        symbolic factorization AND numeric factors, swapping the triangular
        sweeps).  Otherwise a transposed sibling is analyzed once and cached
        here; its block-ELL layout is rebuilt eagerly when the pattern is
        concrete, and the stencil kernel (whose values encode A, not Aᵀ) is
        dropped in favour of the COO path — matching the forward numerics.
        """
        if self._tplan is not None:
            return self._tplan
        n, m = self.shape
        if n == m and self.props.get("symmetric", False):
            count("transpose_shared")
            self._tplan = self
            return self
        tp = self.backend.transpose_plan(self)
        if tp is not None:
            count("transpose_shared")
            self._tplan = tp
            return tp

        tbell = None
        if self.bell is not None and not isinstance(self.row, jax.core.Tracer):
            tbell = build_bell(self.col, self.row, (m, n))
        tcfg = self.cfg
        if tcfg.backend == "stencil" or (tcfg.backend == "pallas" and
                                         tbell is None):
            tcfg = dataclasses.replace(tcfg, backend="jnp")
            if tcfg.precond == "mg":   # V-cycle needs the dropped stencil view
                tcfg = dataclasses.replace(tcfg, precond="jacobi")
        At = SparseTensor.__new__(SparseTensor)
        At.val = None
        At.row, At.col = self.col, self.row
        At.shape = (m, n)
        At.props = dict(self.props)
        At.bell, At.stencil = tbell, None
        At._plans = {}
        tplan = SolverPlan(tcfg, At, cache=At._plans)
        At._plans[tcfg.plan_key()] = tplan
        tplan._tplan = self       # (Aᵀ)ᵀ = A
        self._tplan = tplan
        return tplan

    def adapt(self, cfg: SolverConfig) -> SolverConfig:
        """Project a caller's config onto this plan's analyze-stage choices
        (backend/method/precond), keeping the caller's solve-loop knobs —
        used by the adjoint so tol/maxiter follow the forward request even
        when the transpose plan rewrote the backend."""
        return dataclasses.replace(cfg, backend=self.cfg.backend,
                                   method=self.cfg.method,
                                   precond=self.cfg.precond)


@spanned("plan.get")
def get_plan(A: SparseTensor, cfg: Optional[SolverConfig] = None,
             **kw) -> SolverPlan:
    """Fetch (or analyze-and-cache) the plan for ``A``'s pattern + ``cfg``.

    The cache lives on the SparseTensor and is SHARED by ``with_values``
    views, so repeated solves on one pattern — including inside jit/grad —
    analyze exactly once."""
    if cfg is None:
        cfg = make_config(A, **kw)
    elif cfg.backend in (None, "auto") or cfg.method in (None, "auto"):
        cfg = cfg.resolved(A)
    cache = getattr(A, "_plans", None)
    if cache is None:
        cache = PlanCache()
        try:
            A._plans = cache
        except AttributeError:
            pass
    extra = getattr(A, "plan_key_extra", None)
    key = cfg.plan_key() + (tuple(extra()) if extra is not None else ())
    plan = cache.get(key)
    if plan is not None:
        count("cache_hit")
        return plan
    count("cache_miss")
    plan = SolverPlan(cfg, A, cache=cache)
    cache[key] = plan
    return plan


# ---------------------------------------------------------------------------
# legacy free-function API (kept for callers/benchmarks; plan-backed now)
# ---------------------------------------------------------------------------

def solve_impl(cfg: SolverConfig, A: SparseTensor, b: jax.Array,
               x0: Optional[jax.Array] = None):
    """One un-differentiated solve through the cached plan."""
    return get_plan(A, cfg).solve(A, b, x0, cfg=cfg)
