"""Supernodal direct factorization (PR 9 — dense panel kernels).

Covers: fundamental-supernode partition validity across structured and
unstructured patterns (the panel program reproduces the scalar packed-scan
factors in the SAME storage); panel-path solve parity vs the scalar path and
vs the dense backend at 1e-8; the static Bunch–Kaufman 2x2 pivot blocks on a
genuinely indefinite saddle-point system — solve + gradcheck through the
pair kernels with NO zero-pivot perturbation warning; slogdet through the
pair determinants; the ``supernodal`` option knob and its env override; plan
counters proving ONE symbolic analysis serves the solve, slogdet, and the
batched path; and the dense backend's batched-setup memo.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import PLAN_STATS, SparseTensor, reset_plan_stats
from repro.core import options as _options
from repro.core.direct import factor_slogdet, factored_solve, \
    numeric_factor, symbolic_factor
from repro.data.graphs import graph_laplacian
from repro.data.poisson import poisson1d, poisson2d


def _random_pattern(n, nnz_per_row, seed):
    """Unsymmetric random sparse matrix with a dominant full diagonal."""
    rng = np.random.default_rng(seed)
    row = np.repeat(np.arange(n), nnz_per_row)
    col = rng.integers(0, n, size=row.size)
    row = np.concatenate([row, np.arange(n)])
    col = np.concatenate([col, np.arange(n)])
    val = rng.standard_normal(row.size)
    val[-n:] = 4.0 * nnz_per_row          # diagonal dominance
    return row, col, val, n


def _saddle(m, k, seed=1):
    """Indefinite saddle-point KKT system [[H, Bᵀ], [B, 0]] with the zero
    block kept structurally present (explicit zero diagonal values)."""
    rng = np.random.default_rng(seed)
    H = rng.standard_normal((m, m))
    H = H @ H.T + m * np.eye(m)
    B = rng.standard_normal((k, m))
    A = np.block([[H, B.T], [B, np.zeros((k, k))]])
    n = m + k
    mask = (np.abs(A) > 1e-12) | np.eye(n, dtype=bool)
    row, col = np.nonzero(mask)
    return row, col, A[row, col], A, n


# ---------------------------------------------------------------------------
# partition validity: the panel program reproduces the scalar factors in the
# same packed storage, across structured / unstructured / random patterns
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", ["poisson2d", "graph", "random"])
def test_partition_factor_parity_vs_scalar(case):
    if case == "poisson2d":
        A = poisson2d(16)
        row, col, val = (np.asarray(A.row), np.asarray(A.col),
                         np.asarray(A.val))
        n = A.shape[0]
    elif case == "graph":
        A = graph_laplacian(300, seed=3)
        row, col, val = (np.asarray(A.row), np.asarray(A.col),
                         np.asarray(A.val))
        n = A.shape[0]
    else:
        row, col, val, n = _random_pattern(200, 4, seed=7)

    art_on = symbolic_factor(row, col, n, supernodal="on")
    art_off = symbolic_factor(row, col, n, supernodal="off")
    assert art_on.snode is not None and art_off.snode is None
    st = art_on.stats
    assert st["n_snodes"] >= 1
    assert 1.0 <= st["mean_snode_width"]
    assert 0.0 <= st["panel_fraction"] <= 1.0

    v = jnp.asarray(val)
    C_on = numeric_factor(art_on, v)
    C_off = numeric_factor(art_off, v)
    # identical storage layout — the panel path writes the SAME C vector
    np.testing.assert_allclose(np.asarray(C_on), np.asarray(C_off),
                               rtol=1e-10, atol=1e-10)

    b = jnp.asarray(np.random.default_rng(0).standard_normal(n))
    for transposed in (False, True):
        x_on = factored_solve(art_on, C_on, b, transposed=transposed)
        x_off = factored_solve(art_off, C_off, b, transposed=transposed)
        np.testing.assert_allclose(np.asarray(x_on), np.asarray(x_off),
                                   rtol=1e-9, atol=1e-9)


@pytest.mark.parametrize("case", ["poisson2d", "graph"])
def test_capped_launches_and_pieces_match_scalar(case, monkeypatch):
    """A bucket wider than the lane cap (``SN_MAX_LANES`` where the Pallas
    panel kernels run) runs as several launches of one program, and the
    factor storage is scattered in ``_SCATTER_PIECE`` pieces; the factors
    match the scalar path's and both sweeps solve."""
    from repro.core import direct as _direct
    A = poisson2d(16) if case == "poisson2d" else graph_laplacian(300, seed=3)
    row, col, n = np.asarray(A.row), np.asarray(A.col), A.shape[0]
    monkeypatch.setattr(_direct, "_lane_cap", lambda: 4)
    art_cap = symbolic_factor(row, col, n, supernodal="on")
    launches = [bk for lvl in art_cap.snode.schedule for bk in lvl]
    assert max(bk.pidx.shape[0] for bk in launches) == 4
    art_off = symbolic_factor(row, col, n, supernodal="off")

    v = jnp.asarray(A.val)
    C_off = numeric_factor(art_off, v)
    monkeypatch.setattr(_direct, "_SCATTER_PIECE", 512)
    assert art_cap.nnzF > 2 * 512
    C_cap = numeric_factor(art_cap, v)
    np.testing.assert_allclose(np.asarray(C_cap), np.asarray(C_off),
                               rtol=1e-10, atol=1e-10)
    Ad = np.zeros((n, n))
    np.add.at(Ad, (row, col), np.asarray(A.val))
    b = np.random.default_rng(0).standard_normal(n)
    for transposed in (False, True):
        x = factored_solve(art_cap, C_cap, jnp.asarray(b),
                           transposed=transposed)
        np.testing.assert_allclose(
            np.asarray(x), np.linalg.solve(Ad.T if transposed else Ad, b),
            rtol=1e-9, atol=1e-9)


def test_panel_solve_matches_dense_1e8():
    A = poisson2d(20)          # 400 dof
    b = jnp.asarray(np.random.default_rng(1).standard_normal(A.shape[0]))
    with _options.options(supernodal="on"):
        x = A.solve(b, backend="direct")
    xd = A.solve(b, backend="dense", method="cholesky")
    np.testing.assert_allclose(np.asarray(x), np.asarray(xd),
                               rtol=1e-10, atol=1e-8)


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_direct_converged_is_the_backward_error(dtype):
    """``converged`` of a direct solve is ``backward_error <= tol`` (or
    ``||r||_inf <= atol``): the factors of the matrix pass, in f32 as in
    f64, and factors of a clearly different matrix fail."""
    from repro import sla
    from repro.core.sparse import backward_error
    A = poisson2d(24, dtype=dtype)
    b = jnp.asarray(np.random.default_rng(2).uniform(0.5, 1.5, A.shape[0])
                    .astype(dtype))
    berr = lambda x: float(backward_error(A.val, A.row, A.col, A.shape[0],
                                          x, b))
    with _options.options(supernodal="on"):
        res = sla.solve_with_info(A, b, backend="direct", tol=1e-5)
        plan = sla.get_plan(A, backend="direct", tol=1e-5)
        wrong = plan.setup(A.with_values(A.val * 1.5))
    assert bool(res.converged) and berr(res.x) <= 1e-5
    xw, info = plan.backend.solve(plan, wrong, A, b, None, plan.cfg)
    assert not bool(info.converged) and berr(xw) > 1e-4


def test_auto_gate_declines_sequential_chain():
    # tridiagonal: every supernode is its own elimination level — one lane
    # per kernel launch would serialize; auto must keep the scalar scan
    A = poisson1d(2048)
    art = symbolic_factor(np.asarray(A.row), np.asarray(A.col), A.shape[0])
    assert art.snode is None
    # 2-D Poisson batches many lanes per level — auto emits
    B = poisson2d(40)
    art2 = symbolic_factor(np.asarray(B.row), np.asarray(B.col), B.shape[0])
    assert art2.snode is not None


# ---------------------------------------------------------------------------
# static Bunch–Kaufman 2x2 pivot blocks on an indefinite system
# ---------------------------------------------------------------------------

def test_bk_pairs_indefinite_no_perturbation_warning():
    row, col, val, A, n = _saddle(18, 8)
    b = np.random.default_rng(2).standard_normal(n)
    with warnings.catch_warnings():
        warnings.simplefilter("error")      # any perturbation warning fails
        art = symbolic_factor(row, col, n, pivot_blocks="auto")
        assert art.snode is not None
        assert art.snode.stats["n_pair_pivots"] > 0
        C = numeric_factor(art, jnp.asarray(val))
        x = factored_solve(art, C, jnp.asarray(b))
        xt = factored_solve(art, C, jnp.asarray(b), transposed=True)
    np.testing.assert_allclose(np.asarray(x), np.linalg.solve(A, b),
                               rtol=1e-8, atol=1e-8)
    np.testing.assert_allclose(np.asarray(xt), np.linalg.solve(A.T, b),
                               rtol=1e-8, atol=1e-8)


def test_bk_pairs_slogdet_sign():
    row, col, val, A, n = _saddle(14, 6, seed=5)
    art = symbolic_factor(row, col, n, pivot_blocks="auto")
    C = numeric_factor(art, jnp.asarray(val))
    s, l = factor_slogdet(art, C)
    sd, ld = np.linalg.slogdet(A)
    assert float(s) == sd
    np.testing.assert_allclose(float(l), ld, rtol=1e-10)


def test_bk_pairs_gradcheck_vs_dense():
    row, col, val, A, n = _saddle(12, 5, seed=9)
    b = jnp.asarray(np.random.default_rng(3).standard_normal(n))
    art = symbolic_factor(row, col, n, pivot_blocks="auto")

    def f_sparse(v):
        C = numeric_factor(art, v)
        return jnp.sum(factored_solve(art, C, b) ** 2)

    def f_dense(v):
        Ad = jnp.zeros((n, n)).at[row, col].add(v)
        return jnp.sum(jnp.linalg.solve(Ad, b) ** 2)

    g_s = jax.grad(f_sparse)(jnp.asarray(val))
    g_d = jax.grad(f_dense)(jnp.asarray(val))
    assert bool(jnp.all(jnp.isfinite(g_s)))
    np.testing.assert_allclose(np.asarray(g_s), np.asarray(g_d),
                               rtol=1e-6, atol=1e-8)


def test_indefinite_hint_routes_to_pairs():
    row, col, val, A, n = _saddle(16, 7, seed=11)
    T = SparseTensor(val, row, col, (n, n),
                     props={"indefinite_hint": True})
    b = jnp.asarray(np.random.default_rng(4).standard_normal(n))
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        x = T.solve(b, backend="direct", method="lu")
    np.testing.assert_allclose(np.asarray(x),
                               np.linalg.solve(A, np.asarray(b)),
                               rtol=1e-8, atol=1e-8)


def test_pairs_require_supernodal():
    A = poisson2d(8)
    with pytest.raises(ValueError, match="pivot_blocks"):
        symbolic_factor(np.asarray(A.row), np.asarray(A.col), A.shape[0],
                        supernodal="off", pivot_blocks="auto")


# ---------------------------------------------------------------------------
# options knob + env override
# ---------------------------------------------------------------------------

def test_supernodal_option_knob():
    assert _options.current().supernodal == "auto"
    with _options.options(supernodal="off"):
        A = poisson2d(24)
        art = symbolic_factor(np.asarray(A.row), np.asarray(A.col),
                              A.shape[0])
        assert art.snode is None
    with pytest.raises(ValueError, match="supernodal"):
        _options.Options(supernodal="sometimes")._validate()


def test_supernodal_env_override():
    out = _options._parse_env({"REPRO_SLA_SUPERNODAL": "ON"})
    assert out == {"supernodal": "on"}


# ---------------------------------------------------------------------------
# plan counters: one analysis serves solve + slogdet + batched; dense memo
# ---------------------------------------------------------------------------

def test_one_analysis_serves_solve_slogdet_batch():
    A = poisson2d(24)
    n = A.shape[0]
    b = jnp.asarray(np.random.default_rng(5).standard_normal(n))
    with _options.options(supernodal="on"):
        reset_plan_stats()
        x = A.solve(b, backend="direct")
        s, l = A.slogdet()
        g = jax.grad(lambda v: A.with_values(v).slogdet()[1])(A.val)
        V = jnp.stack([A.val, A.val * 2.0])
        XB = A.with_values(V).solve(jnp.stack([b, b]), backend="direct")
        assert PLAN_STATS["analyze"] == 1, dict(PLAN_STATS)
        # one factorization for the sweep+slogdet+backward, one for the batch
        assert PLAN_STATS["factorize"] == 2, dict(PLAN_STATS)
    assert float(jnp.linalg.norm(A @ x - b)) < 1e-9
    assert float(jnp.linalg.norm(A @ XB[0] - b)) < 1e-9
    assert float(jnp.linalg.norm(2.0 * (A @ XB[1]) - b)) < 1e-9
    assert bool(jnp.all(jnp.isfinite(g)))


def test_dense_backend_batched_setup_memo():
    A = poisson2d(6)            # tiny → dense backend
    n = A.shape[0]
    V = jnp.stack([A.val, A.val * 3.0])
    B = jnp.asarray(np.random.default_rng(6).standard_normal((2, n)))
    reset_plan_stats()
    X1 = A.with_values(V).solve(B, backend="dense", method="lu")
    setups_after_first = PLAN_STATS["setup"]
    X2 = A.with_values(V).solve(B, backend="dense", method="lu")
    # second call with the SAME stacked values array reuses the memoized
    # vmapped densification — no new setup
    assert PLAN_STATS["setup"] == setups_after_first, dict(PLAN_STATS)
    np.testing.assert_allclose(np.asarray(A @ X1[0]), np.asarray(B[0]),
                               atol=1e-9)
    np.testing.assert_allclose(np.asarray(3.0 * (A @ X1[1])),
                               np.asarray(B[1]), atol=1e-9)
    np.testing.assert_allclose(np.asarray(X1), np.asarray(X2))
