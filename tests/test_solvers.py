"""Solver correctness vs scipy/dense references (paper Table 3 behaviours)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp
import scipy.sparse.linalg as spla

from repro.core import SparseTensor, solvers
from repro.core.dispatch import make_config, select_backend
from repro.core import precond
from repro.data.poisson import poisson1d, poisson2d


@pytest.fixture(scope="module")
def A2d():
    return poisson2d(16)   # 256 dof


def to_scipy(A):
    return sp.coo_matrix((np.asarray(A.val), (np.asarray(A.row),
                                              np.asarray(A.col))),
                         shape=A.shape).tocsr()


def test_cg_matches_scipy(A2d):
    b = np.random.default_rng(0).normal(size=A2d.shape[0])
    x_ref = spla.spsolve(to_scipy(A2d), b)
    x = A2d.solve(jnp.asarray(b), backend="jnp", method="cg", tol=1e-12)
    np.testing.assert_allclose(np.asarray(x), x_ref, rtol=1e-8)


def _convection_diffusion(n, c=0.3):
    """tridiag(−1−c, 2, −1+c): non-symmetric, positive spectrum."""
    A1 = poisson1d(n)
    val = np.asarray(A1.val).copy()
    val[np.asarray(A1.col) == np.asarray(A1.row) - 1] = -1.0 - c
    val[np.asarray(A1.col) == np.asarray(A1.row) + 1] = -1.0 + c
    return SparseTensor(val, A1.row, A1.col, (n, n))


def test_bicgstab_nonsymmetric():
    rng = np.random.default_rng(1)
    n = 80
    A = _convection_diffusion(n)
    assert not A.props["symmetric"]
    b = rng.normal(size=n)
    x = A.solve(jnp.asarray(b), backend="jnp", method="bicgstab", tol=1e-12,
                maxiter=4000)
    np.testing.assert_allclose(np.asarray(A @ x), b, atol=1e-8)


def test_gmres():
    rng = np.random.default_rng(2)
    n = 60
    A = _convection_diffusion(n, c=0.4)
    b = rng.normal(size=n)
    x = A.solve(jnp.asarray(b), backend="jnp", method="gmres", tol=1e-10,
                maxiter=2000)
    np.testing.assert_allclose(np.asarray(A @ x), b, atol=1e-6)


def test_dense_backend_cholesky(A2d):
    b = np.random.default_rng(3).normal(size=A2d.shape[0])
    x = A2d.solve(jnp.asarray(b), backend="dense", method="cholesky")
    np.testing.assert_allclose(np.asarray(A2d @ x), b, atol=1e-9)


def test_auto_dispatch_policy(A2d):
    # small SPD → dense cholesky
    b, m = select_backend(A2d, "auto", "auto")
    assert (b, m) == ("dense", "cholesky")
    # mid-size → sparse-direct LDLᵀ (cached symbolic factorization)
    mid = poisson2d(80)    # 6400: DENSE_BUDGET < n ≤ DIRECT_BUDGET
    b2, m2 = select_backend(mid, "auto", "auto")
    assert (b2, m2) == ("direct", "ldlt")
    # large → iterative cg (symmetric)
    big = poisson2d(320)   # 102400 > DIRECT_BUDGET (raised to 10⁵ with the
    b3, m3 = select_backend(big, "auto", "auto")   # supernodal panel kernels)
    assert (b3, m3) == ("jnp", "cg")
    # ... unless the caller hints ill-conditioning (Krylov stalls there)
    big.props["illcond_hint"] = True
    b4, m4 = select_backend(big, "auto", "auto")
    assert (b4, m4) == ("direct", "ldlt")
    # explicit override honored
    b5, m5 = select_backend(A2d, "jnp", "bicgstab")
    assert (b5, m5) == ("jnp", "bicgstab")


def test_batched_shared_pattern_solve(A2d):
    rng = np.random.default_rng(4)
    vals = jnp.stack([A2d.val, A2d.val * 2.0])
    Ab = SparseTensor(vals, A2d.row, A2d.col, A2d.shape, props=A2d.props)
    bs = jnp.asarray(rng.normal(size=(2, A2d.shape[0])))
    xs = Ab.solve(bs, backend="jnp", method="cg", tol=1e-12)
    for i, scale in enumerate((1.0, 2.0)):
        Ai = SparseTensor(np.asarray(A2d.val) * scale, A2d.row, A2d.col,
                          A2d.shape)
        np.testing.assert_allclose(np.asarray(Ai @ xs[i]), np.asarray(bs[i]),
                                   atol=1e-8)


@pytest.mark.parametrize("name", ["jacobi", "block_jacobi", "chebyshev"])
def test_preconditioners_accelerate(A2d, name):
    b = jnp.ones(A2d.shape[0])
    from repro.core.dispatch import make_matvec
    mv = make_matvec(A2d)
    M = precond.make_preconditioner(name, A2d, mv)
    x, info = solvers.cg(mv, b, M=M, tol=1e-10, maxiter=2000)
    x0, info0 = solvers.cg(mv, b, tol=1e-10, maxiter=2000)
    assert bool(info.converged)
    assert float(jnp.linalg.norm(A2d @ x - b)) < 1e-7
    if name != "jacobi":   # Poisson diagonal is constant → jacobi = identity
        assert int(info.iters) <= int(info0.iters)


def test_nonlinear_newton_picard_anderson():
    n = 32
    A = poisson1d(n)
    b = jnp.linspace(0.5, 1.5, n)

    def F(u):
        return A @ u + 0.1 * u ** 3 - b

    for method, tol in (("newton", 1e-12), ("picard", 1e-10),
                        ("anderson", 1e-10)):
        if method == "newton":
            u, info = solvers.newton_solve(F, jnp.zeros(n), tol=tol)
        elif method == "picard":
            u, info = solvers.picard_solve(lambda u: u - 0.2 * F(u),
                                           jnp.zeros(n), tol=tol, maxiter=5000)
        else:
            u, info = solvers.anderson_solve(lambda u: u - 0.2 * F(u),
                                             jnp.zeros(n), tol=tol,
                                             maxiter=2000)
        assert float(jnp.linalg.norm(F(u))) < 1e-6, method


def aniso_poisson2d(ng, cy=0.6):
    """2D Poisson with anisotropic y-coupling — breaks the square-grid
    eigenvalue degeneracy (the paper targets simple eigenvalues, §5)."""
    A = poisson2d(ng)
    val = np.asarray(A.val).copy()
    row, col = np.asarray(A.row), np.asarray(A.col)
    y_edge = np.abs(row - col) == 1
    val[y_edge] *= cy
    val[row == col] = 2.0 + 2.0 * cy
    return SparseTensor(val, row, col, A.shape)


def test_lobpcg_and_lanczos_eigenvalues():
    A = aniso_poisson2d(10)
    w_ref = np.sort(np.linalg.eigvalsh(np.asarray(A.todense())))
    w, V = A.eigsh(k=4, method="lobpcg", tol=1e-11, maxiter=2000)
    np.testing.assert_allclose(np.asarray(w), w_ref[:4], atol=1e-7)
    # residuals ‖Av − λv‖ small
    for i in range(4):
        r = A @ V[i] - w[i] * V[i]
        assert float(jnp.linalg.norm(r)) < 1e-6
    w2, V2 = A.eigsh(k=3, method="lanczos")
    np.testing.assert_allclose(np.asarray(w2), w_ref[:3], atol=1e-6)


def test_largest_eigenpairs():
    A = aniso_poisson2d(8)
    w_ref = np.sort(np.linalg.eigvalsh(np.asarray(A.todense())))
    from repro.core.adjoint import sparse_eigsh
    w, V = sparse_eigsh(A, 2, largest=True, tol=1e-11, maxiter=1500,
                        compute_vector_grads=False)
    np.testing.assert_allclose(np.sort(np.asarray(w)), w_ref[-2:], atol=1e-6)


def test_solve_info_reports_convergence():
    A = poisson2d(12)
    from repro.core.adjoint import sparse_solve_with_info
    cfg = make_config(A, backend="jnp", method="cg", tol=1e-10)
    x, info = sparse_solve_with_info(cfg, A, jnp.ones(A.shape[0]))
    assert bool(info.converged)
    assert int(info.iters) > 0
    assert float(info.resnorm) < 1e-7 * np.linalg.norm(np.ones(A.shape[0])) * 10


# ---------------------------------------------------------------------------
# nonlinear fixed-point solvers: property-based coverage + the PR-10
# Anderson least-squares regression
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_compat import given, settings, st


def _contraction(seed, n, L):
    """Random affine map G(x) = c + M x with ‖M‖₂ = L < 1 — the Banach
    fixed point x* = (I − M)⁻¹ c is unique and known."""
    rng = np.random.default_rng(seed)
    M = rng.normal(size=(n, n))
    M *= L / np.linalg.norm(M, 2)
    c = rng.normal(size=n)
    x_star = np.linalg.solve(np.eye(n) - M, c)
    Mj, cj = jnp.asarray(M), jnp.asarray(c)
    return (lambda x: cj + Mj @ x), x_star


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(2, 24),
       L=st.floats(0.05, 0.9))
def test_picard_converges_on_random_contractions(seed, n, L):
    G, x_star = _contraction(seed, n, L)
    tol = 1e-10
    x, info = solvers.picard_solve(G, jnp.zeros(n), tol=tol, maxiter=5000)
    assert bool(info.converged) == bool(float(info.resnorm) <= tol)
    assert bool(info.converged)
    np.testing.assert_allclose(np.asarray(x), x_star, atol=1e-8)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(2, 24),
       L=st.floats(0.05, 0.9), m=st.integers(1, 12))
def test_anderson_converges_on_random_contractions(seed, n, L, m):
    G, x_star = _contraction(seed, n, L)
    tol = 1e-10
    x, info = solvers.anderson_solve(G, jnp.zeros(n), m=m, tol=tol,
                                     maxiter=2000)
    assert bool(info.converged) == bool(float(info.resnorm) <= tol)
    assert bool(info.converged), (seed, n, L, m)
    np.testing.assert_allclose(np.asarray(x), x_star, atol=1e-7)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(2, 12))
def test_anderson_degenerate_windows_no_nan(seed, n):
    """m > iteration count AND duplicate residual columns (an affine map on
    a rank-1 M makes successive differences collinear): the window's Gram
    matrix is singular from step one — the pinv path must stay finite."""
    rng = np.random.default_rng(seed)
    u = rng.normal(size=n)
    v = rng.normal(size=n)
    M = 0.5 * np.outer(u, v) / (np.linalg.norm(u) * np.linalg.norm(v))
    c = rng.normal(size=n)
    x_star = np.linalg.solve(np.eye(n) - M, c)
    Mj, cj = jnp.asarray(M), jnp.asarray(c)
    G = lambda x: cj + Mj @ x
    # window far larger than the iterations the solve will ever take
    x, info = solvers.anderson_solve(G, jnp.zeros(n), m=4 * n, tol=1e-11,
                                     maxiter=500)
    assert bool(jnp.all(jnp.isfinite(x)))
    assert bool(info.converged) == bool(float(info.resnorm) <= 1e-11)
    np.testing.assert_allclose(np.asarray(x), x_star, atol=1e-8)


def test_anderson_f32_pinv_regression():
    """A fixed-ridge (1e-12) Gram solve underflows f32 roundoff
    (~1e-7·‖G‖ ≫ ridge) and yields NaN iterates on large-scale
    rank-deficient windows; the relative-cutoff eigh pseudo-inverse (the
    same ``eigh_pinv_solve`` block_cg uses) stays finite and converges."""
    rng = np.random.default_rng(0)
    n, m = 6, 8
    M = rng.normal(size=(n, n)).astype(np.float32)
    M = 0.5 * M / np.linalg.norm(M, 2)
    U, S, Vt = np.linalg.svd(M)
    S[2:] = 0.0                      # rank-2: degenerate difference window
    M = (U * S) @ Vt
    c = (rng.normal(size=n) * 1e3).astype(np.float32)   # amplify roundoff
    x_star = np.linalg.solve(np.eye(n) - M, c)
    Mj, cj = jnp.asarray(M, jnp.float32), jnp.asarray(c, jnp.float32)
    G = lambda x: cj + Mj @ x
    x0 = jnp.zeros(n, jnp.float32)

    x_new, info = solvers.anderson_solve(G, x0, m=m, tol=1e-3, maxiter=100)
    assert bool(jnp.all(jnp.isfinite(x_new)))
    assert bool(info.converged)
    np.testing.assert_allclose(np.asarray(x_new), x_star, atol=1e-2)


def test_eigh_pinv_solve_relative_cutoff():
    rng = np.random.default_rng(5)
    Q, _ = np.linalg.qr(rng.normal(size=(6, 6)))
    w = np.array([1e4, 2e3, 50.0, 1.0, 1e-12, 0.0])    # hard rank-4 @ f64
    G = jnp.asarray((Q * w) @ Q.T)
    y = jnp.asarray(rng.normal(size=6))
    rhs = G @ y
    x = solvers.eigh_pinv_solve(G, rhs)
    # exact on range(G), zero on the null space
    np.testing.assert_allclose(np.asarray(G @ x), np.asarray(rhs), atol=1e-6)
    null = jnp.asarray(Q[:, 4:])
    assert float(jnp.linalg.norm(null.T @ x)) < 1e-8
    # multi-rhs shape
    X = solvers.eigh_pinv_solve(G, jnp.stack([rhs, rhs], 1))
    assert X.shape == (6, 2)
