"""HPCG's problem on the 3-D stencil path, against plain references.

The constructor and the 27-point kernel against a SciPy CSR of HPCG's
``GenerateProblem``; each coarse level against HPCG's coarse problem
generated directly; one V-cycle against the plain loop-level
``ComputeMG`` with the same colour order; the V-cycle's symmetry; and
``solve_with_info(..., precond="mg")`` against the exact solve by the 3-D
sine transform.  Seeded float32 data on a 16³ grid and an 8 × 16 × 24
grid (every dimension divisible by 8, as HPCG's four levels need); the
Pallas kernels run interpreted.

Each tolerance says why it is what it is.  Float32 arithmetic leaves
relative errors of ~1e-6 here; bfloat16 keeps 8 bits (a relative step of
2⁻⁸ ≈ 4e-3), so the tolerances of 1e-5 and below fail a bfloat16 answer —
``test_solve_matches_sine_transform`` shows it for the solve.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp
from scipy.fft import dstn

from repro import sla
from repro.core import multigrid as mg
from repro.core.dispatch import reset_plan_stats
from repro.core.precond import PreconditionerPlan
from repro.data import hpcg_ref
from repro.data.hpcg import hpcg_problem, stencil27_pattern
from repro.kernels import ops
from repro.kernels.stencil5 import Stencil5Meta
from repro.kernels.stencil27 import OFFSETS, Stencil27Meta, inside

GRIDS = [(16, 16, 16), (8, 16, 24)]          # (nx, ny, nz)
KW = dict(method="cg", precond="mg", tol=1e-6, maxiter=500)


def _vec(dims, seed, low=None):
    rng = np.random.default_rng(seed)
    n = int(np.prod(dims))
    v = rng.uniform(0.5, 1.5, n) if low else rng.standard_normal(n)
    return jnp.asarray(v, jnp.float32)


def _csr(meta, val):
    """SciPy CSR of the COO view of stencil planes (structural zeros of
    the clamped slots dropped)."""
    row, col = stencil27_pattern(meta)
    m = sp.coo_matrix((np.asarray(val, np.float64).ravel(),
                       (np.asarray(row), np.asarray(col))),
                      shape=(meta.n, meta.n)).tocsr()
    m.eliminate_zeros()
    return m


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def dst_solve(dims, b):
    """Exact float64 solve of HPCG's problem: A = 27 I − K⊗K⊗K with
    K = tridiag(1, 1, 1), diagonalised by the orthonormal 3-D DST-I."""
    nx, ny, nz = dims
    e = [1.0 + 2.0 * np.cos(np.arange(1, n + 1) * np.pi / (n + 1))
         for n in (nz, ny, nx)]
    lam = 27.0 - (e[0][:, None, None] * e[1][None, :, None]
                  * e[2][None, None, :])
    bb = np.asarray(b, np.float64).reshape(nz, ny, nx)
    x = dstn(dstn(bb, type=1, norm="ortho") / lam, type=1, norm="ortho")
    return x.ravel()


@pytest.mark.parametrize("dims", GRIDS)
def test_constructor_matches_generate_problem(dims):
    A = hpcg_problem(*dims)
    assert A.stencil == Stencil27Meta(*dims)
    assert A.val.dtype == jnp.float32 and A.nnz == 27 * A.shape[0]
    assert A.props["symmetric"] and A.props["spd_hint"]
    assert hpcg_problem(*dims, use_stencil_kernel=False).stencil is None
    # exact: every value is a small integer, held exactly in float32
    diff = _csr(A.stencil, A.val) - hpcg_ref.generate_problem(*dims)
    assert abs(diff).max() == 0.0


@pytest.mark.parametrize("dims", GRIDS)
def test_stencil27_kernel_matches_csr(dims):
    A = hpcg_problem(*dims)
    x = _vec(dims, 1)
    y = ops.stencil27_matvec(A.stencil, A.val, x)
    ref = hpcg_ref.generate_problem(*dims) @ np.asarray(x, np.float64)
    # float32 sums of 27 terms: ~1e-7 of the largest term; bf16 gives ~4e-3
    assert _rel(y, ref) <= 1e-6
    # the tensor's own SpMV routes to the same kernel
    assert _rel(A @ x, ref) <= 1e-6


def test_stencil27_vjp_matches_coo():
    """The kernel's VJP against autodiff of the COO SpMV, on random planes
    that are zero where a coupling leaves the grid."""
    meta = Stencil27Meta(8, 8, 16)
    rng = np.random.default_rng(2)
    mask = jnp.stack([inside(meta, *d) for d in OFFSETS])
    planes = jnp.where(mask, jnp.asarray(
        rng.standard_normal((27,) + meta.shape), jnp.float32), 0.0)
    val = planes.reshape(-1)
    x = _vec(meta.shape, 3)
    w = _vec(meta.shape, 4)
    row, col = stencil27_pattern(meta)
    from repro.core.sparse import coo_matvec

    def loss(mv):
        return lambda v, xx: jnp.vdot(w, mv(v, xx))

    gk = jax.grad(loss(lambda v, xx: ops.stencil27_matvec(meta, v, xx)),
                  (0, 1))(val, x)
    gr = jax.grad(loss(lambda v, xx: coo_matvec(v, row, col, xx, meta.n)),
                  (0, 1))(val, x)
    keep = mask.reshape(-1)        # a clamped slot's gradient is not defined
    assert _rel(jnp.where(keep, gk[0], 0), jnp.where(keep, gr[0], 0)) <= 1e-6
    assert _rel(gk[1], gr[1]) <= 1e-6


@pytest.mark.parametrize("dims", GRIDS)
def test_coarse_levels_match_generated_coarse_problems(dims):
    A = hpcg_problem(*dims)
    meta = A.stencil
    levels = mg.hpcg_state(meta, ops.stencil27_planes(meta, A.val))
    metas = mg.hpcg_metas(meta)
    assert len(levels) == len(metas) == hpcg_ref.LEVELS
    for m, planes in zip(metas[1:], levels[1:]):
        diff = _csr(m, planes) - hpcg_ref.generate_problem(m.nx, m.ny, m.nz)
        assert abs(diff).max() == 0.0          # exact, as above


@pytest.mark.parametrize("dims", [(8, 8, 8), (16, 24, 40), (64, 64, 64)])
def test_inject_is_bit_exact(dims):
    """Injection keeps the fine values at the even points, bit for bit."""
    r = jnp.asarray(np.random.default_rng(sum(dims)).standard_normal(dims),
                    jnp.float32)
    got = mg._inject(r)
    assert got.dtype == jnp.float32
    assert np.array_equal(np.asarray(got), np.asarray(r[::2, ::2, ::2]))


@pytest.fixture(scope="module", params=GRIDS,
                ids=lambda d: "x".join(map(str, d)))
def vcycle(request):
    dims = request.param
    A = hpcg_problem(*dims)
    meta = A.stencil
    state = mg.hpcg_state(meta, ops.stencil27_planes(meta, A.val))
    return dims, jax.jit(mg.hpcg_preconditioner(meta, state))


def test_vcycle_matches_plain_compute_mg(vcycle):
    dims, M = vcycle
    r = _vec(dims, 5)
    ref = hpcg_ref.compute_mg(hpcg_ref.hierarchy(*dims),
                              np.asarray(r, np.float64))
    # float32 against float64 through 14 sweeps: ~1e-6; bf16 gives ~1e-2
    assert _rel(M(r), ref) <= 1e-5


def test_vcycle_is_symmetric(vcycle):
    dims, M = vcycle
    u, v = _vec(dims, 6), _vec(dims, 7)
    uMv = float(jnp.vdot(u, M(v)))
    vMu = float(jnp.vdot(v, M(u)))
    # both sides are float32 dot products of order 1e3 terms: ~1e-6
    assert abs(uMv - vMu) <= 1e-5 * abs(uMv)


@pytest.mark.parametrize("dims", GRIDS)
def test_solve_matches_sine_transform(dims):
    A = hpcg_problem(*dims)
    b = _vec(dims, 8, low=True)
    res = sla.solve_with_info(A, b, **KW)
    assert bool(res.converged)
    plan = sla.get_plan(A, **KW)
    assert plan.cfg.backend == "stencil"
    assert plan.artifacts["kernel"].choice == "stencil"
    xr = dst_solve(dims, b)
    tol = 1e-5       # f32 CG at a relative residual of 1e-6, cond ≈ 1e2 here
    assert _rel(res.x, xr) <= tol
    # the same answer rounded through bfloat16 fails the tolerance
    xb = np.asarray(jnp.asarray(xr, jnp.bfloat16), np.float64)
    assert _rel(xb, xr) > tol


def test_warm_3d_solve_traces_and_lowers_nothing():
    A = hpcg_problem(16, 16, 16)
    b = _vec((16, 16, 16), 9, low=True)
    with sla.options(fused_step="on"):          # the chip's fused CG steps
        reset_plan_stats()
        sla.solve_with_info(A, b, **KW)
        cold = sla.solve_records()[-1]["counters"]
        assert cold["mg3d_build"] == 1 and cold["mg3d_levels"] == 4
        res = sla.solve_with_info(A, b, **KW)
    warm = sla.solve_records()[-1]["counters"]
    assert warm.get("jax_lowerings", 0) == 0
    assert warm.get("jax_traces", 0) == 0
    assert warm["solve_program_call"] == 1
    assert warm.get("solve_program_build", 0) == 0
    assert warm.get("mg3d_build", 0) == 0               # setup memoized
    assert _rel(res.x, dst_solve((16, 16, 16), b)) <= 1e-5


@pytest.mark.parametrize("meta,ok", [
    (Stencil5Meta(nx=16, ny=16), True),
    (Stencil5Meta(nx=16, ny=8), False),        # 2-D builder: square planes
    (Stencil27Meta(8, 16, 24), True),
    (Stencil27Meta(16, 16, 12), False),        # 3-D: dimensions % 8
])
def test_mg_checks_what_each_builder_needs(meta, ok):
    n = meta.nx * meta.ny * getattr(meta, "nz", 1)
    idx = np.arange(n)
    build = lambda: PreconditionerPlan("mg", idx, idx, (n, n), stencil=meta)
    if ok:
        build()
    else:
        with pytest.raises(ValueError, match="precond='mg'"):
            build()
