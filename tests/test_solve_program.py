"""The single-device solve program: one compiled Krylov stage per plan.

A warm solve on an analyzed plan dispatches the plan's cached ``jax.jit``
program once: no jaxpr is traced, nothing is lowered.  A tolerance sweep
reuses the program (tol, atol and maxiter are arguments), outer ``jax.jit``
callers inline it without leaving tracers in the cache, and the plan's
index arrays enter the program as arguments rather than as constants
written into its text.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import sla
from repro.core import get_plan, make_config, reset_plan_stats, solvers
from repro.core.dispatch import PLAN_STATS
from repro.data.poisson import poisson2d, poisson2d_vc


def _stencil(ng=16):
    kappa = jnp.asarray(np.exp(0.3 * np.random.default_rng(0).normal(
        size=(ng, ng))))
    return poisson2d_vc(kappa, use_stencil_kernel=True)


def _coo():
    return poisson2d(16)


WARM_CASES = {
    "stencil-mg-cg-fused": (_stencil, dict(precond="mg", tol=1e-8), "on"),
    "stencil-mg-cg-unfused": (_stencil, dict(precond="mg", tol=1e-8), "off"),
    "jnp-amg-cg": (_coo, dict(backend="jnp", method="cg", precond="amg"),
                   "auto"),
    "jnp-jacobi-cg": (_coo, dict(backend="jnp", method="cg"), "auto"),
    "jnp-jacobi-bicgstab": (_coo, dict(backend="jnp", method="bicgstab"),
                            "auto"),
    "jnp-jacobi-gmres": (_coo, dict(backend="jnp", method="gmres"), "auto"),
    "jnp-jacobi-block_cg": (_coo, dict(backend="jnp", method="block_cg"),
                            "auto"),
}


@pytest.mark.parametrize("case", list(WARM_CASES))
def test_warm_solve_traces_and_lowers_nothing(case):
    make, kw, fused = WARM_CASES[case]
    A = make()
    b = jnp.ones(A.shape[0])
    with sla.options(fused_step=fused):
        reset_plan_stats()
        sla.solve_with_info(A, b, **kw)             # analyze, set up, compile
        cold = sla.solve_records()[-1]["counters"]
        assert cold["solve_program_build"] == 1
        assert cold["solve_program_call"] == 1
        reset_plan_stats()
        res = sla.solve_with_info(A, b, **kw)
    recs = sla.solve_records()
    assert len(recs) == 1
    warm = recs[0]["counters"]
    assert warm.get("jax_lowerings", 0) == 0, recs[0]["lowered"]
    assert warm.get("jax_traces", 0) == 0
    assert warm.get("solve_program_build", 0) == 0
    assert warm["solve_program_call"] == 1
    assert bool(jnp.all(res.converged))
    assert float(jnp.linalg.norm(A @ res.x - b)) <= 1e-5 * float(
        jnp.linalg.norm(b))


SWEEP_CASES = {
    "jnp-jacobi-cg": dict(backend="jnp", method="cg", precond="jacobi"),
    "jnp-amg-cg": dict(backend="jnp", method="cg", precond="amg"),
    "stencil-mg-cg": dict(backend="stencil", method="cg", precond="mg"),
}


@pytest.mark.parametrize("case", list(SWEEP_CASES))
def test_tolerance_sweep_builds_one_program(case):
    kw = SWEEP_CASES[case]
    A = _stencil() if kw["backend"] == "stencil" else _coo()
    b = jnp.asarray(np.random.default_rng(1).normal(size=A.shape[0]))
    reset_plan_stats()
    got = {tol: sla.solve_with_info(A, b, tol=tol, **kw)
           for tol in (1e-4, 1e-8)}
    assert PLAN_STATS["solve_program_build"] == 1
    assert PLAN_STATS["solve_program_call"] == 2
    # the same solve, run as a plain call of the solver on the same closures
    cfg = make_config(A, **kw)
    plan = get_plan(A, cfg)
    val, pstate, _ = plan.setup(A)
    mv = plan.backend._matvec_from_val(plan, val)
    M = plan.artifacts["precond"].make_apply(pstate, mv)
    for tol, res in got.items():
        x, info = solvers.cg(mv, b, M=M, tol=tol, maxiter=cfg.maxiter)
        assert int(res.iterations) == int(info.iters)
        scale = float(jnp.max(jnp.abs(x)))
        np.testing.assert_allclose(np.asarray(res.x), np.asarray(x),
                                   rtol=0, atol=1e-6 * scale)


def _has_tracer(obj):
    leaves = jax.tree_util.tree_leaves(obj)
    return any(isinstance(v, jax.core.Tracer) for v in leaves)


@pytest.mark.parametrize("precond", ["jacobi", "amg"])
def test_outer_jit_then_eager_leaves_no_tracer_in_the_cache(precond):
    A = _coo()
    b = jnp.ones(A.shape[0])
    kw = dict(backend="jnp", method="cg", precond=precond, tol=1e-10)
    x_jit = jax.jit(lambda rhs: sla.solve(A, rhs, **kw))(b)
    res = sla.solve_with_info(A, b, **kw)
    plan = get_plan(A, make_config(A, **kw))
    programs = plan.artifacts["programs"]
    assert programs and all(callable(p) and not _has_tracer(p)
                            for p in programs.values())
    _, arrays = plan._program_args
    assert not _has_tracer(arrays)
    assert bool(res.converged)
    np.testing.assert_allclose(np.asarray(x_jit), np.asarray(res.x),
                               rtol=1e-8, atol=1e-10)


def _largest_literal_bytes(text: str) -> int:
    """Bytes of the largest hex-written dense constant in an MLIR module."""
    return max((len(h) // 2 for h in re.findall(r'dense<"0x([0-9A-Fa-f]*)">',
                                                text)), default=0)


@pytest.mark.parametrize("precond", ["amg", "ilu", "jacobi"])
def test_solve_program_takes_plan_arrays_as_arguments(precond):
    """Lowering the cached program writes none of the plan's index arrays
    (AMG hierarchy, ILU schedule, the segment-sum pattern) into its text;
    the same stage closing over them would (the control)."""
    A = poisson2d(48)
    cfg = make_config(A, backend="jnp", method="cg", precond=precond)
    plan = get_plan(A, cfg)
    state = plan.setup(A)
    b = jnp.ones(A.shape[0])
    program, args = plan.backend.solve_program(plan, state, b, None, cfg)
    assert _largest_literal_bytes(program.lower(*args).as_text()) < 4096
    closed = jax.jit(lambda rhs: program(args[0], state, rhs, None,
                                         *args[4:]))
    # the control: the lifted arrays closed over are written as constants
    assert _largest_literal_bytes(closed.lower(b).as_text()) > 4096
