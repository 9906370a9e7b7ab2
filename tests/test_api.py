"""Public API surface (repro.sla), options API, and typed results.

The surface snapshot is the contract: adding or removing a public name must
be a deliberate edit to EXPECTED_SURFACE here (and to docs/api.md via
tools/gen_api_ref.py), never an accident.
"""
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro import sla
from repro.core import dispatch
from repro.core import options as _options
from repro.data.poisson import poisson2d

# the checked-in public surface — keep sorted
EXPECTED_SURFACE = sorted([
    "DSparseTensor",
    "Options",
    "PLAN_STATS",
    "SolveResult",
    "SolveServer",
    "SolverConfig",
    "SolverPlan",
    "SparseNewton",
    "SparseTensor",
    "eigsh",
    "get_options",
    "nonlinear_solve",
    "get_plan",
    "options",
    "register_backend",
    "reset_plan_stats",
    "serve",
    "set_options",
    "solve",
    "solve_records",
    "solve_with_info",
])


# ---------------------------------------------------------------------------
# surface snapshot
# ---------------------------------------------------------------------------

def test_api_surface_snapshot():
    assert sorted(sla.__all__) == EXPECTED_SURFACE


def test_api_surface_resolvable_and_documented():
    for name in sla.__all__:
        obj = getattr(sla, name)     # lazy names must resolve too
        assert obj is not None
        if callable(obj) and not isinstance(obj, dict):
            assert getattr(obj, "__doc__", None), f"{name} lacks a docstring"


def test_repro_reexports_sla():
    assert repro.sla is sla
    assert "sla" in repro.__all__


# ---------------------------------------------------------------------------
# options API
# ---------------------------------------------------------------------------

def test_set_options_roundtrip():
    base = sla.get_options()
    try:
        new = sla.set_options(fused_step="off", direct_budget=1234)
        assert new.fused_step == "off" and new.direct_budget == 1234
        assert sla.get_options() is new
    finally:
        sla.set_options(fused_step=base.fused_step,
                        direct_budget=base.direct_budget)
    assert sla.get_options().fused_step == base.fused_step


def test_options_context_scoped_and_exception_safe():
    base = sla.get_options()
    with sla.options(dense_budget=7):
        assert sla.get_options().dense_budget == 7
        with sla.options(dense_budget=9):     # nesting: innermost wins
            assert sla.get_options().dense_budget == 9
        assert sla.get_options().dense_budget == 7
    assert sla.get_options().dense_budget == base.dense_budget
    with pytest.raises(RuntimeError):
        with sla.options(dense_budget=7):
            raise RuntimeError("boom")
    assert sla.get_options().dense_budget == base.dense_budget


def test_options_validation():
    with pytest.raises(ValueError):
        sla.set_options(fused_step="maybe")
    with pytest.raises(ValueError):
        sla.set_options(plan_cache_cap=0)
    with pytest.raises(ValueError):
        sla.set_options(bell_min_fill=2.0)
    with pytest.raises(TypeError):
        sla.set_options(not_an_option=1)


def test_env_var_parsing():
    parsed = _options._parse_env({
        "REPRO_SLA_FUSED_STEP": "OFF",
        "REPRO_SLA_PLAN_CACHE_BYTES": "1e8",
        "REPRO_SLA_DIRECT_BUDGET": "50000",
        "UNRELATED": "x",
    })
    assert parsed == {"fused_step": "off", "plan_cache_bytes": 10 ** 8,
                      "direct_budget": 50000}
    assert _options._parse_env({"REPRO_SLA_PLAN_CACHE_BYTES": "none"}) == \
        {"plan_cache_bytes": None}
    with pytest.raises(ValueError):
        _options._parse_env({"REPRO_SLA_TYPO": "1"})


def test_options_read_at_use_time():
    """Budgets apply at dispatch time, not frozen at import/plan time."""
    A = poisson2d(8)    # n=64: auto → dense under the default budget
    assert dispatch.select_backend(A, "auto", "auto")[0] == "dense"
    with sla.options(dense_budget=1, direct_budget=1):
        assert dispatch.select_backend(A, "auto", "auto")[0] == "jnp"


def test_register_backend_takes_a_backend_instance():
    class CountingDense(dispatch.DenseBackend):
        calls = 0

        def solve(self, plan, dense, A, b, x0, cfg):
            CountingDense.calls += 1
            return super().solve(plan, dense, A, b, x0, cfg)

    A = poisson2d(6)
    b = jnp.ones(A.shape[0])
    sla.register_backend("counting_dense", CountingDense())
    try:
        assert dispatch.BACKENDS["counting_dense"].name == "counting_dense"
        x = sla.solve(A, b, backend="counting_dense")
    finally:
        del dispatch.BACKENDS["counting_dense"]
    assert CountingDense.calls == 1
    np.testing.assert_allclose(np.asarray(x), np.asarray(sla.solve(A, b)),
                               rtol=1e-10)


def test_unknown_dispatch_attribute_still_raises():
    with pytest.raises(AttributeError):
        dispatch.NO_SUCH_KNOB


# ---------------------------------------------------------------------------
# typed results
# ---------------------------------------------------------------------------

def test_solve_result_fields_iterative():
    A = poisson2d(8)
    b = jnp.ones(A.shape[0])
    res = sla.solve_with_info(A, b, backend="jnp", method="cg", tol=1e-10)
    assert isinstance(res, sla.SolveResult)
    assert res._fields == ("x", "iterations", "residual", "converged",
                           "reason")
    assert res.reason == "converged" and bool(res.converged)
    assert float(res.residual) <= 1e-10 * np.linalg.norm(np.asarray(b)) * 1.01
    x_ref = np.linalg.solve(np.asarray(A.todense()), np.asarray(b))
    np.testing.assert_allclose(np.asarray(res.x), x_ref, rtol=1e-8)


def test_solve_result_fields_direct_and_dense():
    A = poisson2d(8)
    b = jnp.ones(A.shape[0])
    for backend in ("direct", "dense"):
        res = sla.solve_with_info(A, b, backend=backend)
        assert isinstance(res, sla.SolveResult)
        assert res.reason == "converged", (backend, res)


def test_solve_result_maxiter_reason():
    A = poisson2d(8)
    b = jnp.ones(A.shape[0])
    res = sla.solve_with_info(A, b, backend="jnp", method="cg", tol=1e-14,
                              maxiter=2)
    assert res.reason == "maxiter" and not bool(res.converged)
