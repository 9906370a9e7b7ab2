"""Pallas-kernel validation: hypothesis sweeps over shapes/dtypes, allclose
against the ref.py pure-jnp oracles (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:       # bare env: seeded-sweep fallback, suite still collects
    from _hypothesis_compat import given, settings, st

from repro.core.sparse import build_bell, coo_matvec
from repro.kernels import ops
from repro.kernels.stencil5 import Stencil5Meta


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == np.float16 else (
        dict(rtol=2e-5, atol=2e-5) if dtype == np.float32 else
        dict(rtol=1e-12, atol=1e-12))


@settings(max_examples=15, deadline=None)
@given(nx=st.integers(3, 70), ny=st.integers(3, 300),
       dtype=st.sampled_from([np.float32, np.float64]),
       seed=st.integers(0, 99))
def test_stencil5_kernel_sweep(nx, ny, dtype, seed):
    rng = np.random.default_rng(seed)
    val5 = rng.normal(size=(5, nx, ny)).astype(dtype)
    val5[1, 0, :] = 0; val5[2, -1, :] = 0
    val5[3, :, 0] = 0; val5[4, :, -1] = 0
    x = rng.normal(size=(nx * ny,)).astype(dtype)
    meta = Stencil5Meta(nx=nx, ny=ny)
    v = jnp.asarray(val5.reshape(-1))
    xk = jnp.asarray(x)
    y_k = ops.stencil5_matvec(meta, v, xk)
    y_r = ops.stencil5_matvec_ref(meta, v, xk)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), **_tol(dtype))


@settings(max_examples=15, deadline=None)
@given(n=st.integers(4, 200), m=st.integers(4, 300),
       density=st.floats(0.01, 0.3),
       dtype=st.sampled_from([np.float32, np.float64]),
       seed=st.integers(0, 99))
def test_bell_kernel_sweep(n, m, density, dtype, seed):
    rng = np.random.default_rng(seed)
    nnz = max(1, int(n * m * density))
    row = rng.integers(0, n, nnz)
    col = rng.integers(0, m, nnz)
    keys = np.unique(row.astype(np.int64) * m + col)
    row = (keys // m).astype(np.int32)
    col = (keys % m).astype(np.int32)
    val = rng.normal(size=len(row)).astype(dtype)
    meta, bcols, perm = build_bell(row, col, (n, m))
    v, x = jnp.asarray(val), jnp.asarray(rng.normal(size=m).astype(dtype))
    y_k = ops.bell_matvec(meta, bcols, perm, v, x, n)
    y_r = ops.bell_matvec_ref(meta, bcols, perm, v, x, n)
    y_c = coo_matvec(v, jnp.asarray(row), jnp.asarray(col), x, n)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_c), **_tol(dtype))


def test_stencil5_gradients_vs_ref():
    rng = np.random.default_rng(0)
    nx, ny = 21, 83
    val5 = rng.normal(size=(5, nx, ny))
    val5[1, 0, :] = 0; val5[2, -1, :] = 0
    val5[3, :, 0] = 0; val5[4, :, -1] = 0
    v = jnp.asarray(val5.reshape(-1))
    x = jnp.asarray(rng.normal(size=nx * ny))
    w = jnp.asarray(rng.normal(size=nx * ny))
    meta = Stencil5Meta(nx=nx, ny=ny)
    Lk = lambda vv, xx: jnp.sum(w * ops.stencil5_matvec(meta, vv, xx))
    Lr = lambda vv, xx: jnp.sum(w * ops.stencil5_matvec_ref(meta, vv, xx))
    gk = jax.grad(Lk, (0, 1))(v, x)
    gr = jax.grad(Lr, (0, 1))(v, x)
    np.testing.assert_allclose(np.asarray(gk[0]), np.asarray(gr[0]), rtol=1e-10)
    np.testing.assert_allclose(np.asarray(gk[1]), np.asarray(gr[1]), rtol=1e-10)


def test_bell_gradients_vs_coo():
    rng = np.random.default_rng(1)
    n, m = 120, 90
    nnz = 900
    row = rng.integers(0, n, nnz)
    col = rng.integers(0, m, nnz)
    keys = np.unique(row.astype(np.int64) * m + col)
    row = (keys // m).astype(np.int32)
    col = (keys % m).astype(np.int32)
    val = jnp.asarray(rng.normal(size=len(row)))
    x = jnp.asarray(rng.normal(size=m))
    w = jnp.asarray(rng.normal(size=n))
    meta, bcols, perm = build_bell(row, col, (n, m))
    Lk = lambda v, xx: jnp.sum(w * ops.bell_matvec(meta, bcols, perm, v, xx, n))
    Lc = lambda v, xx: jnp.sum(w * coo_matvec(v, jnp.asarray(row),
                                              jnp.asarray(col), xx, n))
    gk = jax.grad(Lk, (0, 1))(val, x)
    gc = jax.grad(Lc, (0, 1))(val, x)
    np.testing.assert_allclose(np.asarray(gk[0]), np.asarray(gc[0]), rtol=1e-10)
    np.testing.assert_allclose(np.asarray(gk[1]), np.asarray(gc[1]), rtol=1e-10)


def test_bell_fill_and_padding_invariants():
    """BELL layout bookkeeping: every COO entry lands in exactly one slot."""
    rng = np.random.default_rng(2)
    n = m = 64
    row = rng.integers(0, n, 300)
    col = rng.integers(0, m, 300)
    keys = np.unique(row.astype(np.int64) * m + col)
    row = (keys // m).astype(np.int32)
    col = (keys % m).astype(np.int32)
    meta, bcols, perm = build_bell(row, col, (n, m))
    p = np.asarray(perm)
    kept = p[p >= 0]
    assert len(np.unique(kept)) == len(kept)           # injective
    assert meta.fill <= 1.0
    assert kept.max() < meta.n_rb * meta.k * meta.bm * meta.bn


def test_stencil_solve_path_matches_jnp():
    """End-to-end: stencil-kernel CG solve == COO CG solve."""
    from repro.data.poisson import poisson2d_vc
    ng = 24
    kappa = jnp.asarray(1.0 + 0.3 * np.random.default_rng(3).random((ng, ng)))
    f = jnp.ones(ng * ng)
    A_k = poisson2d_vc(kappa, use_stencil_kernel=True)
    A_j = poisson2d_vc(kappa, use_stencil_kernel=False)
    x_k = A_k.solve(f, backend="stencil", method="cg", tol=1e-12)
    x_j = A_j.solve(f, backend="jnp", method="cg", tol=1e-12)
    np.testing.assert_allclose(np.asarray(x_k), np.asarray(x_j), rtol=1e-8)


def test_bell_empty_rows_and_cols():
    """Rows/cols with no entries: the BELL slot table must still produce
    exact zeros there, forward and transpose."""
    rng = np.random.default_rng(4)
    n, m = 200, 150
    # entries confined to a band; rows 0–9 and 180–199, cols 140–149 empty
    row = rng.integers(10, 180, 400).astype(np.int32)
    col = rng.integers(0, 140, 400).astype(np.int32)
    keys = np.unique(row.astype(np.int64) * m + col)
    row = (keys // m).astype(np.int32)
    col = (keys % m).astype(np.int32)
    val = jnp.asarray(rng.normal(size=len(row)))
    x = jnp.asarray(rng.normal(size=m))
    meta, bcols, perm = build_bell(row, col, (n, m))
    y = ops.bell_matvec(meta, bcols, perm, val, x, n)
    y_c = coo_matvec(val, jnp.asarray(row), jnp.asarray(col), x, n)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_c), atol=1e-12)
    assert float(jnp.abs(y[:10]).max()) == 0.0
    assert float(jnp.abs(y[180:]).max()) == 0.0
    # transpose layout (the kernel plan's t_bell): empty columns of A are
    # empty rows of Aᵀ
    tmeta, tbcols, tperm = build_bell(col, row, (m, n))
    g = jnp.asarray(rng.normal(size=n))
    yt = ops.bell_matvec(tmeta, tbcols, tperm, val, g, m)
    yt_c = coo_matvec(val, jnp.asarray(col), jnp.asarray(row), g, m)
    np.testing.assert_allclose(np.asarray(yt), np.asarray(yt_c), atol=1e-12)
    assert float(jnp.abs(yt[140:]).max()) == 0.0


# ---------------------------------------------------------------------------
# fused solver-step kernels (kernels/solve_step.py) vs the pure-jnp oracles
# ---------------------------------------------------------------------------

from repro.kernels import ref as _fref
from repro.kernels import solve_step as _fk

# kernel name → (vector-argument count, scalar-argument count)
_FUSED_SIGS = {
    "fused_cg_update": (5, 1),
    "fused_cg_direction": (4, 1),
    "fused_cg_halfstep": (4, 1),
    "fused_cheb_step": (3, 2),
    "fused_dots2": (2, 0),
    "fused_bicg_p": (4, 3),
    "fused_bicg_s": (3, 1),
    "fused_bicg_tail": (6, 2),
}


def _fused_parity_case(name, n, dtype, seed):
    n_vec, n_sc = _FUSED_SIGS[name]
    rng = np.random.default_rng(seed)
    vecs = [jnp.asarray(rng.normal(size=n).astype(dtype))
            for _ in range(n_vec)]
    scalars = [jnp.asarray(dtype(rng.normal())) for _ in range(n_sc)]
    out_k = getattr(_fk, name)(*vecs, *scalars)
    out_r = getattr(_fref, name + "_ref")(*vecs, *scalars)
    assert len(out_k) == len(out_r)
    for a, b in zip(out_k, out_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   **_tol(dtype))


@settings(max_examples=20, deadline=None)
@given(name=st.sampled_from(sorted(_FUSED_SIGS)),
       n=st.integers(3, 3000),
       dtype=st.sampled_from([np.float32, np.float64]),
       seed=st.integers(0, 99))
def test_fused_step_kernel_sweep(name, n, dtype, seed):
    """Every fused kernel matches its ref oracle across sizes (ragged last
    blocks included — n is rarely a multiple of the 1024 tile) and dtypes."""
    _fused_parity_case(name, n, dtype, seed)


@pytest.mark.parametrize("name", sorted(_FUSED_SIGS))
def test_fused_step_kernel_edges(name):
    """Deterministic coverage of every kernel at the tile edges the sweep
    may miss: exact one-tile n, the 8×128 sub-tile boundary, and ragged."""
    for n in (5, 128, 1024, 1029):
        for dtype in (np.float32, np.float64):
            _fused_parity_case(name, n, dtype, seed=0)


def test_fused_dots_exclude_padding():
    """The in-kernel reductions must not pick up the zero-padded tail — the
    padding contributes exact zeros, so dots over a size-5 vector padded to
    1024 equal the length-5 dots."""
    u = jnp.asarray([1.0, 2.0, 3.0, 4.0, 5.0])
    v = jnp.asarray([1.0, -1.0, 1.0, -1.0, 1.0])
    uv, uu = _fk.fused_dots2(u, v)
    np.testing.assert_allclose(float(uv), float(jnp.dot(u, v)), rtol=1e-14)
    np.testing.assert_allclose(float(uu), float(jnp.dot(u, u)), rtol=1e-14)


def test_fused_cg_solver_matches_plain():
    """cg_fused (merged Chronopoulos–Gear recurrence) produces the same
    iterates as the textbook loop — identical solution AND iteration count."""
    from repro.core import solvers
    from repro.data.poisson import poisson2d
    A = poisson2d(20)
    b = jnp.asarray(np.random.default_rng(5).normal(size=A.shape[0]))
    mv = lambda x: A @ x
    dinv = 1.0 / A.diagonal()
    M = lambda r: dinv * r
    x_p, i_p = solvers.cg(mv, b, M=M, tol=1e-11)
    x_f, i_f = solvers.cg_fused(mv, b, dinv=dinv, tol=1e-11)
    assert bool(i_f.converged)
    assert int(i_f.iters) == int(i_p.iters)
    np.testing.assert_allclose(np.asarray(x_f), np.asarray(x_p),
                               rtol=1e-9, atol=1e-11)
    # M-callable branch (no diagonal): fused axpy passes, plain recurrence
    x_m, i_m = solvers.cg_fused(mv, b, M=M, tol=1e-11)
    assert bool(i_m.converged)
    np.testing.assert_allclose(np.asarray(x_m), np.asarray(x_p),
                               rtol=1e-9, atol=1e-11)


def test_fused_bicgstab_solver_matches_plain():
    from repro.core import solvers
    from repro.data.poisson import poisson1d
    from repro.core.sparse import SparseTensor
    n = 80
    A1 = poisson1d(n)
    val = np.asarray(A1.val).copy()
    val[np.asarray(A1.col) == np.asarray(A1.row) - 1] = -1.4
    val[np.asarray(A1.col) == np.asarray(A1.row) + 1] = -0.6
    B = SparseTensor(val, A1.row, A1.col, (n, n))
    b = jnp.asarray(np.random.default_rng(6).normal(size=n))
    mv = lambda x: B @ x
    dinv = 1.0 / B.diagonal()
    x_p, i_p = solvers.bicgstab(mv, b, M=lambda r: dinv * r, tol=1e-11)
    x_f, i_f = solvers.bicgstab_fused(mv, b, dinv=dinv, tol=1e-11)
    assert bool(i_f.converged)
    np.testing.assert_allclose(np.asarray(x_f), np.asarray(x_p),
                               rtol=1e-8, atol=1e-10)


def test_default_interpret_matches_platform():
    """Satellite: the interpret flag auto-detects the platform instead of
    defaulting to emulation everywhere."""
    expect = jax.default_backend() not in ("tpu", "gpu")
    assert _fk.default_interpret() == expect
    # and the kernel-plan artifact records the same resolution
    from repro.core import dispatch
    from repro.data.poisson import poisson2d
    A = poisson2d(8)
    plan = A.plan(backend="pallas", method="cg")
    assert plan.artifacts["kernel"].interpret == expect


def test_compiled_kernels_refuse_f64():
    """Compiled (``interpret=False``), every solve-path kernel names a
    float64 operand instead of lowering it; Mosaic has no f64 type."""
    from repro.kernels import supernode as ksn
    from repro.kernels.spmv_bell import bell_spmv_pallas
    from repro.kernels.stencil5 import stencil5_pallas
    from repro.core.sparse import BellMeta
    f64 = jnp.float64
    x = jnp.zeros(1024, f64)
    meta = BellMeta(bm=8, bn=128, n_rb=128, n_cb=8, k=1, n_pad=1024,
                    m_pad=1024, fill=1.0)
    P, Q = jnp.zeros((4, 6, 2), f64), jnp.zeros((4, 2, 4), f64)
    w, m = jnp.ones(4, jnp.int32), jnp.zeros((4, 2), bool)
    calls = [
        lambda: _fk.fused_cg_halfstep(x, x, x, x, f64(1), interpret=False),
        lambda: stencil5_pallas(Stencil5Meta(nx=8, ny=8),
                                jnp.zeros((5, 8, 8), f64),
                                jnp.zeros((8, 8), f64), False),
        lambda: bell_spmv_pallas(meta, jnp.zeros((128, 1), jnp.int32),
                                 jnp.zeros((128, 1, 8, 128), f64), x, False),
        lambda: ksn.panel_factor(P, Q, w, w, 1.0, m, interpret=False),
        lambda: ksn.schur_update(P, Q, interpret=False),
        lambda: ksn.block_trsv(P[:, :2], P[:, 0], w, m, mode="l",
                               interpret=False),
    ]
    for call in calls:
        with pytest.raises(ValueError, match="float64"):
            call()
