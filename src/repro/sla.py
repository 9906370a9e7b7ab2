"""repro.sla — the stable public surface of the sparse linear algebra engine.

This is the supported way in: a curated namespace over the plan-cached solver
engine (:mod:`repro.core`) and the request-batching serving driver
(:mod:`repro.launch.solve_serve`).  Internal modules remain importable but
undocumented and unstable; everything listed in ``__all__`` here is covered
by the API-surface snapshot test and the generated reference
(``docs/api.md``, built by ``tools/gen_api_ref.py``).

Quick start::

    import jax.numpy as jnp
    from repro import sla

    A = sla.SparseTensor(val, row, col, (n, n))   # COO, differentiable vals
    x = sla.solve(A, b)                           # auto-dispatch + adjoint
    res = sla.solve_with_info(A, b, tol=1e-10)    # typed SolveResult
    print(res.iterations, res.residual, res.reason)

Options (the former ``repro.core.dispatch`` module globals)::

    sla.set_options(fused_step="on")              # process-wide
    with sla.options(direct_budget=10**5):        # scoped, exception-safe
        x = sla.solve(A, b)
    sla.get_options().plan_cache_bytes            # the active record

Every option also has a ``REPRO_SLA_*`` environment override read at import
(e.g. ``REPRO_SLA_FUSED_STEP=off``, ``REPRO_SLA_PLAN_CACHE_BYTES=1e8``).

Serving::

    from repro.sla import SolveServer
    server = SolveServer()
    results = server.submit_batch(requests)       # grouped + vmapped dispatch

The engine's contract, in one line: ``analyze`` (pattern → plan) is eager
and cached, ``setup`` (values → state) is traced-safe and memoized per
values array, ``solve`` (rhs → x) is where gradients attach — see
CONTRIBUTING.md for why that split is load-bearing.
"""
from __future__ import annotations

from .core.dispatch import (PLAN_STATS, SolverConfig, SolverPlan, get_plan,
                            make_config, register_backend, reset_plan_stats)
from .core.options import Options
from .core.options import current as get_options
from .core.options import options, set_options
from .core.solvers import SolveInfo, SolveResult, as_solve_result
from .core import spans as _spans
from .core.spans import span
from .core.sparse import SparseTensor

__all__ = [
    "SparseTensor",
    "DSparseTensor",
    "SparseNewton",
    "nonlinear_solve",
    "eigsh",
    "SolverConfig",
    "SolverPlan",
    "SolveResult",
    "Options",
    "solve",
    "solve_with_info",
    "get_plan",
    "register_backend",
    "set_options",
    "options",
    "get_options",
    "serve",
    "SolveServer",
    "PLAN_STATS",
    "reset_plan_stats",
    "solve_records",
]

# lazily bound: the distributed layer pulls in mesh/shard_map machinery and
# the serving driver pulls in the launch package — single-device library use
# should not pay either import
_LAZY = {
    "DSparseTensor": ("repro.core.distributed", "DSparseTensor"),
    "serve": ("repro.launch.solve_serve", "serve"),
    "SolveServer": ("repro.launch.solve_serve", "SolveServer"),
    # nonlinear/eigen layer: pulls in the adjoint + coloring machinery
    "SparseNewton": ("repro.core.nonlinear", "SparseNewton"),
    "nonlinear_solve": ("repro.core.adjoint", "nonlinear_solve"),
    "eigsh": ("repro.core.adjoint", "sparse_eigsh"),
}


def __getattr__(name: str):
    target = _LAZY.get(name)
    if target is not None:
        from importlib import import_module
        return getattr(import_module(target[0]), target[1])
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(__all__))


def solve(A, b, **kw):
    """Solve ``A @ x = b`` with adjoint gradients (paper §3.2).

    ``A`` is a :class:`SparseTensor` (or :class:`DSparseTensor`); ``b`` may
    carry leading batch dimensions, and ``A`` may carry stacked values
    sharing one pattern — both batch through ONE analyzed plan and one
    vmapped setup.  Keyword options: ``backend`` ("auto", "dense", "direct",
    "jnp", "pallas", "stencil"), ``method`` (backend-specific; "block_cg"
    solves a multi-rhs batch as one coupled block), ``precond``, ``tol``,
    ``atol``, ``maxiter``, ``x0``.  Returns ``x`` only; gradients flow
    through the O(1)-graph adjoint solve.  Use :func:`solve_with_info` for
    convergence diagnostics."""
    with span("sla.solve"):
        return A.solve(b, **kw)


def solve_with_info(A, b, *, x0=None, **kw) -> SolveResult:
    """Like :func:`solve`, returning a typed :class:`SolveResult`.

    Works uniformly across the iterative, direct, and distributed backends:
    ``x`` (solution), ``iterations``, ``residual`` (final ‖r‖₂, per-rhs for
    batches), ``converged``, and a static ``reason`` string ("converged",
    "maxiter", or "unknown" under a trace).  This entry point is
    un-differentiated — it is the serving/diagnostics path; use
    :func:`solve` when gradients matter."""
    with span("sla.solve"):
        if getattr(A, "mesh", None) is not None:      # distributed tensor
            x, info = A.solve_with_info(b, x0=x0, **kw)
        else:
            from .core.dispatch import make_config, solve_impl
            cfg = make_config(A, **kw)
            x, info = solve_impl(cfg, A, b, x0)
        return as_solve_result(x, info)


def solve_records(n=None) -> list:
    """The per-solve view for operators: the newest ``n`` completed solve
    records (all that are kept, up to 1024, when ``n`` is None), oldest
    first, as plain dicts of numbers and strings.

    Every :func:`solve`, :func:`solve_with_info` and
    ``SolveServer.submit_batch`` call leaves one record, named after its
    outermost span (``"sla.solve"``, ``"sla.serve_batch"``; a bare
    :func:`get_plan` leaves a ``"plan.get"`` record).  A record holds the
    call's host seconds (``seconds``), the inclusive and self seconds of
    each stage span inside it (``incl_s``, ``self_s``: ``plan.get``,
    ``plan.analyze``, ``plan.setup``, ``precond.refresh``, ``plan.solve``,
    ``krylov.<method>``, ``solve.wait``, ...), the ``PLAN_STATS`` counters
    it moved (``counters``, with JAX's ``jax_traces``, ``jax_lowerings``,
    ``jax_compiles`` and ``jax_cache_hits``), the names of the programs it
    lowered (``lowered``) and whether it ran under a JAX trace (``traced``:
    its times are then trace-time times).  Read it to see where a slow
    solve spent its host time: ``seconds - incl_s["solve.wait"]`` is the
    time outside the wait for the device, and ``jax_lowerings > 0`` on a
    warm solve means a program was built again.  ``reset_plan_stats()``
    clears the records."""
    return _spans.solve_records(n)
