"""Matrix gradient of stencil-layout solves, formed plane by plane.

``sparse_solve``'s backward forms ∂L/∂A_ij = −λ_i·x_j.  On a stencil
layout (``poisson2d_vc(..., use_stencil_kernel=True)``, ``hpcg_problem``)
it takes each plane as λ times x shifted by the plane's offset, clamped
into the grid; on every other layout it gathers λ over ``row`` and x over
``col``.  Both give the same float products, so the tests below ask for
bitwise equality with the gather formula built from the same λ and x —
slots whose neighbour leaves the grid included.  λ is read as the gradient
with respect to b, x as the solve's output.
"""
import jax
import jax.extend.core as jex
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import PLAN_STATS
from repro.core.dispatch import reset_plan_stats
from repro.data.hpcg import hpcg_problem
from repro.data.poisson import poisson2d, poisson2d_vc, vc_coefficients

KW = dict(method="cg", precond="mg", tol=1e-10, maxiter=500)


def _kappa(ng, seed=0):
    return jnp.asarray(np.random.default_rng(seed).uniform(0.5, 2.0, (ng, ng)))


def _problem(case):
    kind, dims = case
    if kind == "vc":
        return poisson2d_vc(_kappa(dims), use_stencil_kernel=True), KW
    # float32 operator: a tolerance float32 CG reaches
    return hpcg_problem(*dims), dict(KW, tol=1e-5)


def _vec(n, seed, dtype):
    return jnp.asarray(np.random.default_rng(seed).normal(size=n), dtype)


def _grads(A, kw, b, w):
    """(∂L/∂val, λ, x) of L = w·x, A x = b."""
    def loss(v, bb):
        x = A.with_values(v).solve(bb, **kw)
        return jnp.sum(w * x), x
    (gv, lam), x = jax.grad(loss, (0, 1), has_aux=True)(A.val, b)
    return gv, lam, x


@pytest.mark.parametrize("case", [("vc", 32), ("vc", 64),
                                  ("hpcg", (16, 16, 16)),
                                  ("hpcg", (8, 16, 24))],
                         ids=["vc32", "vc64", "hpcg16", "hpcg8x16x24"])
def test_per_entry_grad_equals_gather(case):
    A, kw = _problem(case)
    n = A.shape[0]
    b, w = _vec(n, 1, A.dtype), _vec(n, 2, A.dtype)
    reset_plan_stats()
    gv, lam, x = _grads(A, kw, b, w)
    assert PLAN_STATS["adjoint_plane_grad"] == 1, dict(PLAN_STATS)
    ref = -(lam[A.row] * x[A.col])
    assert gv.shape == A.val.shape
    np.testing.assert_array_equal(np.asarray(gv), np.asarray(ref))
    # the clamped slots (column aliased onto another cell) are covered
    off_grid = np.asarray(A.val == 0)
    assert off_grid.any() and np.any(np.asarray(gv)[off_grid] != 0)


def test_kappa_grad_matches_gather():
    """Through ``vc_coefficients``: ∂L/∂κ equals the VJP of the gather
    formula's per-entry gradient."""
    ng = 32
    kappa = _kappa(ng, 3)
    A = poisson2d_vc(kappa, use_stencil_kernel=True)
    b, w = _vec(ng * ng, 4, kappa.dtype), _vec(ng * ng, 5, kappa.dtype)

    def loss(kap, bb):
        x = A.with_values(vc_coefficients(kap)).solve(bb, **KW)
        return jnp.sum(w * x), x

    (gk, lam), x = jax.grad(loss, (0, 1), has_aux=True)(kappa, b)
    _, vjp = jax.vjp(vc_coefficients, kappa)
    (gk_ref,) = vjp(-(lam[A.row] * x[A.col]))
    np.testing.assert_allclose(np.asarray(gk), np.asarray(gk_ref),
                               rtol=1e-6, atol=0)


def test_batched_rhs_grad_is_sum_of_single():
    """b of shape (3, n): the per-entry gradient sums the three right-hand
    sides' gradients."""
    ng = 32
    A = poisson2d_vc(_kappa(ng, 6), use_stencil_kernel=True)
    n = ng * ng
    B = jnp.stack([_vec(n, 7 + k, A.dtype) for k in range(3)])
    w = _vec(n, 11, A.dtype)
    kw = dict(KW, tol=1e-12, maxiter=1000)
    gv, lam, x = _grads(A, kw, B, w)
    assert lam.shape == B.shape and gv.shape == A.val.shape
    np.testing.assert_array_equal(
        np.asarray(gv), np.asarray((-(lam[:, A.row] * x[:, A.col])).sum(0)))
    singles = sum(_grads(A, kw, B[k], w)[0] for k in range(3))
    np.testing.assert_allclose(np.asarray(gv), np.asarray(singles),
                               rtol=1e-8, atol=1e-10 * float(
                                   jnp.max(jnp.abs(singles))))


def _gather_sizes(jaxpr):
    """Output sizes of every gather in a jaxpr, nested jaxprs included."""
    sizes = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            sizes.append(int(np.prod(eqn.outvars[0].aval.shape)))
        for p in eqn.params.values():
            for sub in p if isinstance(p, (list, tuple)) else (p,):
                if isinstance(sub, jex.ClosedJaxpr):
                    sizes += _gather_sizes(sub.jaxpr)
                elif isinstance(sub, jex.Jaxpr):
                    sizes += _gather_sizes(sub)
    return sizes


@pytest.mark.parametrize("layout", ["stencil", "coo"])
def test_plane_grad_engages_on_stencil_layout_only(layout):
    """A stencil-layout solve's backward takes no planes·n gather and counts
    ``adjoint_plane_grad`` once; a COO solve keeps the gather, uncounted."""
    if layout == "stencil":
        A = poisson2d_vc(_kappa(16, 8), use_stencil_kernel=True)
        kw = dict(KW)
    else:
        A = poisson2d(16)
        kw = dict(backend="jnp", method="cg", tol=1e-10)
    b = jnp.ones(A.shape[0], A.dtype)
    loss = lambda v: jnp.sum(A.with_values(v).solve(b, **kw) ** 2)
    reset_plan_stats()
    jaxpr = jax.make_jaxpr(jax.grad(loss))(A.val)
    nnz_gathers = [s for s in _gather_sizes(jaxpr.jaxpr) if s == A.nnz]
    if layout == "stencil":
        assert PLAN_STATS["adjoint_plane_grad"] == 1, dict(PLAN_STATS)
        assert not nnz_gathers
    else:
        assert PLAN_STATS["adjoint_plane_grad"] == 0, dict(PLAN_STATS)
        assert nnz_gathers
