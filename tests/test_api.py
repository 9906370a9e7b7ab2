"""Public API surface (repro.sla), options API, and deprecated aliases.

The surface snapshot is the contract: adding or removing a public name must
be a deliberate edit to EXPECTED_SURFACE here (and to docs/api.md via
tools/gen_api_ref.py), never an accident.  These are also the ONLY tests
allowed to touch the deprecated dispatch globals.
"""
import warnings

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


# ---------------------------------------------------------------------------
# deprecated aliases (the ONLY tests that may touch them)
# ---------------------------------------------------------------------------

@pytest.fixture()
def _fresh_warn_state():
    saved = set(_options._warned)
    _options._warned.clear()
    yield
    _options._warned.clear()
    _options._warned.update(saved)


def test_deprecated_global_read_warns_once(_fresh_warn_state):
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        v = dispatch.DIRECT_BUDGET
        assert v == sla.get_options().direct_budget
        _ = dispatch.DIRECT_BUDGET
    deps = [x for x in w if issubclass(x.category, DeprecationWarning)]
    assert len(deps) == 1, [str(x.message) for x in w]
    assert "direct_budget" in str(deps[0].message)


def test_deprecated_global_write_warns_and_forwards(_fresh_warn_state):
    base = sla.get_options().fused_step
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        dispatch.FUSED_STEP = "off"
    try:
        assert sla.get_options().fused_step == "off"
        assert dispatch.FUSED_STEP == "off"
    finally:
        sla.set_options(fused_step=base)
    deps = [x for x in w if issubclass(x.category, DeprecationWarning)]
    assert len(deps) == 1
    assert "fused_step" in str(deps[0].message)


def test_new_plan_cache_bytes_alias(_fresh_warn_state):
    base = sla.get_options().plan_cache_bytes
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        dispatch.PLAN_CACHE_BYTES = 12345
    try:
        assert sla.get_options().plan_cache_bytes == 12345
    finally:
        sla.set_options(plan_cache_bytes=base)
    assert any(issubclass(x.category, DeprecationWarning) for x in w)


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
