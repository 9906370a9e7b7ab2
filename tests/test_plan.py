"""Plan-cached solver engine (paper §3.2.3: one symbolic setup per pattern).

Proves the analyze(pattern) → setup(values) → solve(b) split is actually
reused: ``with_values`` re-solves and ``jax.grad`` backward passes perform
zero additional pattern analyses, the adjoint shares (symmetric) or caches
(non-symmetric) the transpose plan, and the values-dependent preconditioner
refreshes are traced-safe under jit/grad.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (SparseTensor, PLAN_STATS, get_plan, make_config,
                        reset_plan_stats)
from repro.core import dispatch
from repro.core import options as sla_options
from repro.data.poisson import poisson1d, poisson2d, poisson2d_vc


@pytest.fixture()
def A():
    return poisson2d(8)     # 64 dof, SPD


def _convection_diffusion(n, c=0.3):
    A1 = poisson1d(n)
    val = np.asarray(A1.val).copy()
    val[np.asarray(A1.col) == np.asarray(A1.row) - 1] = -1.0 - c
    val[np.asarray(A1.col) == np.asarray(A1.row) + 1] = -1.0 + c
    return SparseTensor(val, A1.row, A1.col, (n, n))


# ---------------------------------------------------------------------------
# plan-cache observability (the tentpole's acceptance criterion)
# ---------------------------------------------------------------------------

def test_with_values_solves_analyze_once(A):
    b = jnp.ones(A.shape[0])
    reset_plan_stats()
    A.solve(b, backend="jnp", method="cg", tol=1e-12)
    A.with_values(A.val * 2.0).solve(b, backend="jnp", method="cg", tol=1e-12)
    A.with_values(A.val * 0.5).solve(b, backend="jnp", method="cg", tol=1e-12)
    assert PLAN_STATS["analyze"] == 1, PLAN_STATS
    assert PLAN_STATS["cache_hit"] == 2, PLAN_STATS
    # values-dependent setup still ran per solve
    assert PLAN_STATS["setup"] == 3, PLAN_STATS


def test_grad_adds_zero_analyzes_symmetric(A):
    """Backward pass reuses the forward plan's transpose view (same object)."""
    b = jnp.ones(A.shape[0])

    def loss(val):
        x = A.with_values(val).solve(b, backend="jnp", method="cg", tol=1e-13)
        return jnp.sum(x ** 2)

    reset_plan_stats()
    jax.grad(loss)(A.val)
    assert PLAN_STATS["analyze"] == 1, PLAN_STATS
    assert PLAN_STATS["transpose_shared"] == 1, PLAN_STATS


def test_grad_transpose_plan_cached_nonsymmetric():
    """Non-symmetric: the transposed sibling is analyzed once, then cached."""
    B = _convection_diffusion(40)
    assert not B.props["symmetric"]
    b = jnp.ones(40)

    def loss(val):
        x = B.with_values(val).solve(b, backend="jnp", method="bicgstab",
                                     tol=1e-13, maxiter=4000)
        return jnp.sum(x ** 2)

    reset_plan_stats()
    jax.grad(loss)(B.val)
    first = PLAN_STATS["analyze"]
    assert first == 2, PLAN_STATS       # forward plan + transpose plan
    jax.grad(loss)(B.val * 1.5)
    assert PLAN_STATS["analyze"] == first, PLAN_STATS   # fully cached now


def test_batched_shared_pattern_single_analysis(A):
    vals = jnp.stack([A.val, 2.0 * A.val, 0.5 * A.val])
    Ab = SparseTensor(vals, A.row, A.col, A.shape, props=A.props)
    bs = jnp.ones((3, A.shape[0]))
    reset_plan_stats()
    xs = Ab.solve(bs, backend="jnp", method="cg", tol=1e-12)
    assert PLAN_STATS["analyze"] == 1, PLAN_STATS
    for i, s in enumerate((1.0, 2.0, 0.5)):
        r = A.with_values(A.val * s) @ xs[i] - bs[i]
        assert float(jnp.linalg.norm(r)) < 1e-8


def test_tolerance_sweep_shares_one_plan(A):
    """tol/atol/maxiter are solve-loop knobs, not part of the plan key."""
    b = jnp.ones(A.shape[0])
    reset_plan_stats()
    for tol in (1e-4, 1e-8, 1e-12):
        A.solve(b, backend="jnp", method="cg", tol=tol)
    assert PLAN_STATS["analyze"] == 1, PLAN_STATS
    assert PLAN_STATS["cache_hit"] == 2, PLAN_STATS
    # and the tighter tolerance was actually honored, not the cached one
    x = A.solve(b, backend="jnp", method="cg", tol=1e-12)
    assert float(jnp.linalg.norm(A @ x - b)) < 1e-9


def _nonsym_stencil(ng=8):
    from repro.data.poisson import vc_pattern, vc_coefficients
    rows, cols, meta = vc_pattern(ng)
    kappa = jnp.ones((ng, ng))
    val = vc_coefficients(kappa).reshape(5, ng, ng)
    val = val.at[1].mul(1.3).at[2].mul(0.7).reshape(-1)   # break symmetry
    return SparseTensor(val, rows, cols, (ng * ng, ng * ng),
                        props={"symmetric": False, "spd_hint": False},
                        stencil=meta, validate=False)


def test_grad_nonsymmetric_stencil_mg_stays_on_stencil():
    """Backward of a non-symmetric stencil-layout solve with precond='mg':
    the transpose plan used to drop to COO (and mg to jacobi); it now keeps
    the stencil kernel via the transposed-planes view, mg included — and the
    gradients still match dense autodiff."""
    B = _nonsym_stencil(8)
    b = jnp.ones(B.shape[0])

    def loss(v):
        x = B.with_values(v).solve(b, method="bicgstab", tol=1e-13,
                                   maxiter=8000, precond="mg")
        return jnp.sum(x ** 2)

    def loss_dense(v):
        return jnp.sum(jnp.linalg.solve(B.with_values(v).todense(), b) ** 2)

    g = jax.grad(loss)(B.val)
    gd = jax.grad(loss_dense)(B.val)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gd),
                               rtol=1e-6, atol=1e-8)


def test_stencil_transpose_plan_keeps_kernel():
    """The adjoint plan of a non-symmetric stencil operator is a
    shared-artifact transposed-planes view: stencil layout retained, same
    backend, counted as transpose_shared — and Aᵀ numerics are exact."""
    B = _nonsym_stencil(8)
    plan = B.plan(method="bicgstab", tol=1e-12)
    assert plan.cfg.backend == "stencil"
    reset_plan_stats()
    tp = plan.transpose()
    assert tp.stencil is not None                 # kernel view survived
    assert tp.cfg.backend == "stencil"            # no jnp/COO rewrite
    assert PLAN_STATS["analyze"] == 0, PLAN_STATS  # zero re-analysis
    assert PLAN_STATS["transpose_shared"] == 1, PLAN_STATS
    assert tp.transpose() is plan                 # (Aᵀ)ᵀ = A
    # the transposed-planes matvec equals dense Aᵀ
    g = jnp.asarray(np.random.default_rng(0).normal(size=B.shape[0]))
    lam, info = tp.solve(tp.matrix(B.val), g)
    res = np.asarray(B.todense()).T @ np.asarray(lam) - np.asarray(g)
    assert np.abs(res).max() < 1e-7, np.abs(res).max()


def test_plan_api_stages(A):
    """analyze → setup → solve stages are individually addressable."""
    plan = A.plan(backend="jnp", method="cg", tol=1e-12)
    assert plan is A.plan(backend="jnp", method="cg", tol=1e-12)  # cached
    state = plan.setup(A)
    x, info = plan.solve_single(A, jnp.ones(A.shape[0]), state=state)
    assert bool(info.converged)
    assert plan.transpose() is plan     # symmetric pattern


def test_iterative_setup_memoized_per_values(A):
    """PR-2 leftover closed: the iterative backends memoize setup(values)
    per values array like the direct backend — a tolerance sweep refreshes
    the preconditioner ONCE, new values still refresh."""
    b = jnp.ones(A.shape[0])
    reset_plan_stats()
    for tol in (1e-4, 1e-8, 1e-12):
        A.solve(b, backend="jnp", method="cg", tol=tol)
    assert PLAN_STATS["setup"] == 1, PLAN_STATS
    assert PLAN_STATS["setup_reuse"] == 2, PLAN_STATS
    # a with_values refresh is NOT served from the memo (different array)
    A.with_values(A.val * 2.0).solve(b, backend="jnp", method="cg", tol=1e-8)
    assert PLAN_STATS["setup"] == 2, PLAN_STATS
    # and the sweep honored the tightest tolerance despite the shared state
    x = A.solve(b, backend="jnp", method="cg", tol=1e-12)
    assert float(jnp.linalg.norm(A @ x - b)) < 1e-9


def test_jitted_solve_over_captured_matrix_does_not_poison_memo(A):
    """A jitted solve that CLOSES OVER the matrix computes setup state from
    a concrete values array inside the trace; memoizing that traced state
    under the concrete key used to leak tracers into the next eager solve
    (UnexpectedTracerError).  The staging probe now skips the store — and
    eager-grad setups (concrete state under a dirty trace stack) still
    memoize."""
    b = jnp.ones(A.shape[0])
    for kw in (dict(backend="jnp", method="cg", tol=1e-10),
               dict(backend="direct")):
        cfg = make_config(A, **kw)
        plan = dispatch.get_plan(A, cfg)
        x1 = jax.jit(lambda bb: plan.solve(A, bb)[0])(b)
        x2, _ = plan.solve(A, b)           # used to raise
        np.testing.assert_allclose(np.asarray(x1), np.asarray(x2),
                                   rtol=1e-10, atol=1e-10)


def test_symmetric_backward_reuses_iterative_setup(A):
    """The adjoint of a symmetric iterative solve hits the per-values memo:
    forward and backward share one preconditioner refresh."""
    b = jnp.ones(A.shape[0])

    def loss(val):
        x = A.with_values(val).solve(b, backend="jnp", method="cg",
                                     tol=1e-13, precond="block_jacobi")
        return jnp.sum(x ** 2)

    reset_plan_stats()
    jax.grad(loss)(A.val)
    assert PLAN_STATS["setup"] == 1, PLAN_STATS
    assert PLAN_STATS["setup_reuse"] >= 1, PLAN_STATS


# ---------------------------------------------------------------------------
# kernel plans: analyze-time BELL conversion, transpose sharing, fused step
# ---------------------------------------------------------------------------

def test_kernel_plan_one_bell_conversion_serves_everything(A):
    """One analyze-time BELL conversion serves the forward solve, the
    backward adjoint, and a with_values sweep (the tentpole's counter)."""
    b = jnp.ones(A.shape[0])

    def loss(val):
        x = A.with_values(val).solve(b, backend="pallas", method="cg",
                                     tol=1e-13)
        return jnp.sum(x ** 2)

    reset_plan_stats()
    jax.grad(loss)(A.val)
    A.with_values(A.val * 2.0).solve(b, backend="pallas", method="cg",
                                     tol=1e-12)
    A.with_values(A.val * 0.5).solve(b, backend="pallas", method="cg",
                                     tol=1e-12)
    assert PLAN_STATS["analyze"] == 1, PLAN_STATS
    assert PLAN_STATS["kernel_plan"] == 1, PLAN_STATS   # symmetric: Aᵀ shares
    assert PLAN_STATS["transpose_shared"] == 1, PLAN_STATS
    kp = A.plan(backend="pallas", method="cg").artifacts["kernel"]
    assert kp.choice == "bell"
    assert kp.t_bell is kp.bell


def test_kernel_plan_transpose_shares_layout_nonsymmetric():
    """Non-symmetric pallas plan: A and Aᵀ BELL layouts are built in the SAME
    analyze pass, and the adjoint plan is a shared-artifact sibling — zero
    additional analyzes, gradients still exact."""
    B = _convection_diffusion(40)
    b = jnp.ones(40)

    def loss(val):
        x = B.with_values(val).solve(b, backend="pallas", method="bicgstab",
                                     tol=1e-13, maxiter=4000)
        return jnp.sum(x ** 2)

    def loss_dense(val):
        return jnp.sum(jnp.linalg.solve(B.with_values(val).todense(), b) ** 2)

    reset_plan_stats()
    g = jax.grad(loss)(B.val)
    assert PLAN_STATS["analyze"] == 1, PLAN_STATS       # NOT 2: layout shared
    assert PLAN_STATS["kernel_plan"] == 2, PLAN_STATS   # A + Aᵀ, one pass
    assert PLAN_STATS["transpose_shared"] == 1, PLAN_STATS
    jax.grad(loss)(B.val * 1.5)
    assert PLAN_STATS["analyze"] == 1, PLAN_STATS
    assert PLAN_STATS["kernel_plan"] == 2, PLAN_STATS
    plan = B.plan(backend="pallas", method="bicgstab")
    tp = plan.transpose()
    assert tp.artifacts["kernel"].bell is plan.artifacts["kernel"].t_bell
    gd = jax.grad(loss_dense)(B.val)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gd),
                               rtol=1e-6, atol=1e-8)


def test_kernel_plan_auto_falls_back_on_interpret_platform(A):
    """The jnp backend's "auto" kernel plan records a segment-sum fallback
    (with its reason) on platforms where Pallas would only be emulated."""
    if jax.default_backend() in ("tpu", "gpu"):
        pytest.skip("compiled-Pallas platform: auto plan may adopt BELL")
    reset_plan_stats()
    plan = A.plan(backend="jnp", method="cg")
    kp = plan.artifacts["kernel"]
    assert kp.choice == "coo"
    assert "interpret" in kp.reason
    assert PLAN_STATS["kernel_plan"] == 0, PLAN_STATS   # no conversion ran


def test_plan_cache_lru_eviction(A):
    """Satellite: the per-tensor plan cache is a bounded LRU — overflowing
    it evicts the oldest plan and counts it."""
    A._plans = dispatch.PlanCache(cap=2)
    reset_plan_stats()
    A.plan(backend="jnp", method="cg")
    A.plan(backend="jnp", method="bicgstab")
    assert PLAN_STATS["evictions"] == 0, PLAN_STATS
    A.plan(backend="jnp", method="gmres")              # evicts the cg plan
    assert PLAN_STATS["evictions"] == 1, PLAN_STATS
    assert PLAN_STATS["cache_miss"] == 3, PLAN_STATS
    A.plan(backend="jnp", method="bicgstab")           # still resident
    assert PLAN_STATS["cache_hit"] == 1, PLAN_STATS
    A.plan(backend="jnp", method="cg")                 # re-analyzed
    assert PLAN_STATS["cache_miss"] == 4, PLAN_STATS
    assert PLAN_STATS["evictions"] == 2, PLAN_STATS


def test_fused_step_solve_matches_plain_and_grad(A):
    """fused_step='on' routes CG/BiCGStab through the fused Pallas step
    kernels: same solution as the plain loops, gradients still match dense
    autodiff (the adjoint solve runs fused too)."""
    b = jnp.asarray(np.random.default_rng(7).normal(size=A.shape[0]))

    def loss(val):
        x = A.with_values(val).solve(b, backend="pallas", method="cg",
                                     tol=1e-13)
        return jnp.sum(x ** 2)

    def loss_dense(val):
        return jnp.sum(jnp.linalg.solve(A.with_values(val).todense(), b) ** 2)

    x_plain = A.solve(b, backend="pallas", method="cg", tol=1e-12)
    with sla_options.options(fused_step="on"):
        x_fused = A.solve(b, backend="pallas", method="cg", tol=1e-12)
        g = jax.grad(loss)(A.val)
    np.testing.assert_allclose(np.asarray(x_fused), np.asarray(x_plain),
                               rtol=1e-9, atol=1e-11)
    gd = jax.grad(loss_dense)(A.val)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gd),
                               rtol=1e-6, atol=1e-8)


def test_fused_step_bicgstab_nonsymmetric_grad():
    B = _convection_diffusion(40, c=0.4)
    b = jnp.asarray(np.random.default_rng(8).normal(size=40))

    def loss(val):
        x = B.with_values(val).solve(b, backend="pallas", method="bicgstab",
                                     tol=1e-13, maxiter=4000)
        return jnp.sum(x ** 2)

    def loss_dense(val):
        return jnp.sum(jnp.linalg.solve(B.with_values(val).todense(), b) ** 2)

    with sla_options.options(fused_step="on"):
        g = jax.grad(loss)(B.val)
    gd = jax.grad(loss_dense)(B.val)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gd),
                               rtol=1e-6, atol=1e-8)


def test_fused_chebyshev_precond_matches_plain(A):
    """The fused Chebyshev inner step threads through the preconditioner
    refresh without changing the polynomial."""
    b = jnp.ones(A.shape[0])
    x_plain = A.solve(b, backend="pallas", method="cg", tol=1e-12,
                      precond="chebyshev")
    with sla_options.options(fused_step="on"):
        x_fused = A.solve(b, backend="pallas", method="cg", tol=1e-12,
                          precond="chebyshev")
    np.testing.assert_allclose(np.asarray(x_fused), np.asarray(x_plain),
                               rtol=1e-9, atol=1e-11)


# ---------------------------------------------------------------------------
# gradients: forward-vs-adjoint plan reuse must not change the math
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend,method", [("jnp", "cg"), ("dense", "lu"),
                                            ("dense", "cholesky")])
def test_gradcheck_symmetric_matches_dense_autodiff(A, backend, method):
    rng = np.random.default_rng(0)
    b = jnp.asarray(rng.normal(size=A.shape[0]))

    def loss(val, rhs):
        x = A.with_values(val).solve(rhs, backend=backend, method=method,
                                     tol=1e-13, maxiter=8000)
        return jnp.sum(x ** 2)

    def loss_dense(val, rhs):
        return jnp.sum(jnp.linalg.solve(A.with_values(val).todense(), rhs) ** 2)

    g = jax.grad(loss, (0, 1))(A.val, b)
    gd = jax.grad(loss_dense, (0, 1))(A.val, b)
    np.testing.assert_allclose(np.asarray(g[0]), np.asarray(gd[0]),
                               rtol=1e-6, atol=1e-8)
    np.testing.assert_allclose(np.asarray(g[1]), np.asarray(gd[1]),
                               rtol=1e-6, atol=1e-8)


@pytest.mark.parametrize("backend,method", [("jnp", "bicgstab"), ("dense", "lu")])
def test_gradcheck_nonsymmetric_matches_dense_autodiff(backend, method):
    B = _convection_diffusion(48, c=0.4)
    rng = np.random.default_rng(1)
    b = jnp.asarray(rng.normal(size=48))

    def loss(val, rhs):
        x = B.with_values(val).solve(rhs, backend=backend, method=method,
                                     tol=1e-13, maxiter=8000)
        return jnp.sum(x ** 3)

    def loss_dense(val, rhs):
        return jnp.sum(jnp.linalg.solve(B.with_values(val).todense(), rhs) ** 3)

    g = jax.grad(loss, (0, 1))(B.val, b)
    gd = jax.grad(loss_dense, (0, 1))(B.val, b)
    np.testing.assert_allclose(np.asarray(g[0]), np.asarray(gd[0]),
                               rtol=1e-6, atol=1e-8)
    np.testing.assert_allclose(np.asarray(g[1]), np.asarray(gd[1]),
                               rtol=1e-6, atol=1e-8)


# ---------------------------------------------------------------------------
# preconditioner plans: traced-safe refresh (regression for the jit crash)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("precond", ["block_jacobi", "chebyshev"])
def test_preconditioned_solve_differentiable(precond):
    """block_jacobi used to call np.asarray on tracers; chebyshev re-ran the
    Lanczos bound inside every solve.  Both now refresh inside setup(values)
    and work under jit + grad."""
    A = poisson2d(12)
    b = jnp.ones(A.shape[0])

    def loss(val):
        x = A.with_values(val).solve(b, backend="jnp", method="cg",
                                     tol=1e-13, precond=precond)
        return jnp.sum(x ** 2)

    def loss_dense(val):
        return jnp.sum(jnp.linalg.solve(A.with_values(val).todense(), b) ** 2)

    g = jax.jit(jax.grad(loss))(A.val)
    gd = jax.grad(loss_dense)(A.val)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gd),
                               rtol=1e-6, atol=1e-8)


def test_mg_first_class_precond_option():
    """precond='mg' builds the V-cycle from the stencil planes inside setup."""
    xs = jnp.linspace(0, 1, 32)
    X, Y = jnp.meshgrid(xs, xs, indexing="ij")
    kappa = 1.0 + 0.5 * jnp.sin(2 * jnp.pi * X) * jnp.sin(2 * jnp.pi * Y)
    A = poisson2d_vc(kappa, use_stencil_kernel=True)
    b = jnp.ones(A.shape[0])
    x = A.solve(b, method="cg", tol=1e-10, precond="mg", maxiter=200)
    assert float(jnp.linalg.norm(A @ x - b)) < 1e-7
    cfg = make_config(A, method="cg", tol=1e-10, precond="mg", maxiter=200)
    assert cfg.backend == "stencil"     # auto-dispatch kept the kernel


def test_mg_precond_requires_stencil():
    A = poisson2d(8)
    with pytest.raises(ValueError, match="mg"):
        A.solve(jnp.ones(A.shape[0]), backend="jnp", precond="mg")


# ---------------------------------------------------------------------------
# batched matvec kernel routing (regression: used to silently fall to COO)
# ---------------------------------------------------------------------------

def test_batched_matvec_routes_through_stencil_kernel(monkeypatch):
    xs = jnp.linspace(0, 1, 16)
    X, Y = jnp.meshgrid(xs, xs, indexing="ij")
    kappa = 1.0 + 0.3 * jnp.cos(2 * jnp.pi * X) * jnp.cos(2 * jnp.pi * Y)
    A = poisson2d_vc(kappa, use_stencil_kernel=True)
    import repro.kernels.ops as kops
    calls = {"n": 0}
    orig = kops.stencil5_matvec

    def counting(*args, **kw):
        calls["n"] += 1
        return orig(*args, **kw)

    monkeypatch.setattr(kops, "stencil5_matvec", counting)
    rng = np.random.default_rng(0)
    xb = jnp.asarray(rng.normal(size=(3, A.shape[0])))
    y = A @ xb
    assert calls["n"] > 0, "batched matvec bypassed the stencil kernel"
    dense = np.asarray(A.todense())
    np.testing.assert_allclose(np.asarray(y), np.asarray(xb) @ dense.T,
                               rtol=1e-10, atol=1e-10)


# ---------------------------------------------------------------------------
# gmres residual carry (regression: 2 extra matvecs per restart cycle)
# ---------------------------------------------------------------------------

def test_gmres_reports_true_carried_residual():
    from repro.core import solvers
    B = _convection_diffusion(60, c=0.4)
    rng = np.random.default_rng(2)
    b = jnp.asarray(rng.normal(size=60))
    mv = lambda v: B @ v
    x, info = solvers.gmres(mv, b, tol=1e-10, restart=20, maxiter=100)
    assert bool(info.converged)
    true_rn = float(jnp.linalg.norm(mv(x) - b))
    np.testing.assert_allclose(float(info.resnorm), true_rn, rtol=1e-10)


def test_gmres_matvec_count_per_cycle():
    """Trace-level matvec count: restart(m) Arnoldi steps + ONE residual
    update per cycle — the convergence check rides on the carried residual."""
    from repro.core import solvers
    B = _convection_diffusion(40)
    b = jnp.ones(40)
    calls = {"n": 0}

    def mv(v):
        calls["n"] += 1
        return B @ v

    m = 10
    jax.make_jaxpr(lambda rhs: solvers.gmres(mv, rhs, tol=1e-10, restart=m,
                                             maxiter=50)[0])(b)
    # trace-level count: init residual (1) + cond (0 — carried norm) +
    # body (1 scan-traced Arnoldi step + 1 residual update) = 3.  The old
    # loop re-derived the residual in cond and at exit → 5 traced matvecs.
    assert calls["n"] <= 3, calls["n"]


@pytest.mark.parametrize("backend,ng,kernels", [
    ("pallas", 16, "bell, fused step"),
    ("direct", 40, "panel_factor, schur_update, block_trsv"),
])
def test_compiled_platform_refuses_f64_values(monkeypatch, backend, ng,
                                              kernels):
    """Where the Pallas kernels compile (TPU), f64 values are refused at
    setup with the dtype and the kernels named — never emulated or rerouted.
    The platform probe is steered here; nothing is compiled."""
    from repro.kernels import solve_step
    monkeypatch.setattr(solve_step, "default_interpret", lambda: False)
    A = poisson2d(ng)                         # float64 values
    plan = get_plan(A, make_config(A, backend=backend))
    plan.check_dtype(jnp.float32)             # the chip path passes
    with pytest.raises(ValueError, match=f"{kernels}.*float64"):
        plan.setup(A)
