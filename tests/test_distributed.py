"""Distributed-layer tests.  Multi-device cases run in a subprocess with 8
forced host devices (XLA device count locks at first jax use, so the main
test process must keep its single real device)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_forced(src: str, n_dev: int = 8) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n_dev}",
               PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run([sys.executable, "-c", src], capture_output=True,
                          text=True, env=env, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return proc.stdout


PREAMBLE = """
import jax, numpy as np, jax.numpy as jnp
jax.config.update("jax_enable_x64", True)
from jax.sharding import Mesh
from repro.core import PLAN_STATS, reset_plan_stats
from repro.core.distributed import (DSparseTensor, DSparseTensorList,
                                    halo_exchange, partition_simple,
                                    partition_coordinate, pipelined_cg)
from repro.core.sparse import SparseTensor
from repro.data.poisson import poisson1d

n = 192
A1 = poisson1d(n)
vals, rows, cols = np.asarray(A1.val), np.asarray(A1.row), np.asarray(A1.col)
mesh = jax.make_mesh((8,), ("data",))
D = DSparseTensor.from_global(vals, rows, cols, (n, n), mesh)
As = SparseTensor(vals, rows, cols, (n, n))
b = np.linspace(0.5, 1.5, n)
bs = D.stack_vector(b)
"""


def test_distributed_solve_matches_single_device():
    out = run_forced(PREAMBLE + textwrap.dedent("""
        x = D.gather_global(D.solve(bs, tol=1e-12, maxiter=4000))
        x_ref = np.asarray(As.solve(jnp.asarray(b), backend="jnp",
                                    method="cg", tol=1e-12, maxiter=4000))
        print("ERR", np.abs(x - x_ref).max() / np.abs(x_ref).max())
    """))
    assert float(out.split("ERR")[1]) < 1e-9


def test_distributed_matvec_and_halo_adjoint():
    out = run_forced(PREAMBLE + textwrap.dedent("""
        # matvec
        xt = np.random.default_rng(0).normal(size=n)
        yd = D.gather_global(D.matvec(D.stack_vector(xt)))
        ys = np.asarray(As @ jnp.asarray(xt))
        print("MV", np.abs(yd - ys).max())

        # halo exchange: Hᵀ is the true adjoint (⟨Hx, y⟩ = ⟨x, Hᵀy⟩)
        from jax.sharding import PartitionSpec as P
        from functools import partial
        n_loc = n // 8
        @partial(jax.shard_map, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
                 check_vma=False)
        def H(x):
            return halo_exchange(x, 2, 3, "data")
        x = jnp.asarray(np.random.default_rng(1).normal(size=n))
        y = jnp.asarray(np.random.default_rng(2).normal(size=8 * (n_loc + 5)))
        Hx = H(x)
        lhs = float(jnp.vdot(Hx, y))
        g = jax.vjp(H, x)[1](y)[0]
        rhs = float(jnp.vdot(x, g))
        print("ADJ", abs(lhs - rhs) / abs(lhs))
    """))
    assert float(out.split("MV")[1].split()[0]) < 1e-12
    assert float(out.split("ADJ")[1]) < 1e-12


def test_distributed_gradients_match_single_device():
    out = run_forced(PREAMBLE + textwrap.dedent("""
        def loss_dist(lval, bstack):
            return jnp.sum(D.with_values(lval).solve(bstack, tol=1e-13,
                                                     maxiter=4000) ** 2)
        gd_val, gd_b = jax.grad(loss_dist, (0, 1))(D.lval, bs)
        def loss_single(v, bb):
            x = As.with_values(v).solve(bb, backend="jnp", method="cg",
                                        tol=1e-13, maxiter=4000)
            return jnp.sum(x ** 2)
        gs_val, gs_b = jax.grad(loss_single, (0, 1))(jnp.asarray(vals),
                                                     jnp.asarray(b))
        bounds = partition_simple(n, 8)
        gv = np.zeros(len(vals))
        for q in range(8):
            s, e = bounds[q], bounds[q + 1]
            m = (rows >= s) & (rows < e)
            gv[m] = np.asarray(gd_val)[q][:m.sum()]
        rel = np.abs(gv - np.asarray(gs_val)) / (np.abs(gs_val) + 1e-30)
        print("GV", rel.max())
        print("GB", np.abs(D.gather_global(gd_b) - np.asarray(gs_b)).max()
              / np.abs(np.asarray(gs_b)).max())
    """))
    assert float(out.split("GV")[1].split()[0]) < 1e-9
    assert float(out.split("GB")[1]) < 1e-9


def test_pipelined_cg():
    out = run_forced(PREAMBLE + textwrap.dedent("""
        xp = D.gather_global(D.solve(bs, tol=1e-11, maxiter=4000,
                                     pipelined=True))
        res = np.abs(np.asarray(As @ jnp.asarray(xp)) - b).max()
        print("PIPE", res)
        # a target the recurrence residual reaches: converged, not capped
        xp, info = D.solve_with_info(bs, tol=1e-10, maxiter=4000,
                                     pipelined=True)
        xp = D.gather_global(xp)
        res = np.abs(np.asarray(As @ jnp.asarray(xp)) - b).max()
        print("PIPE10", res, bool(info.converged))
    """))
    assert float(out.split("PIPE")[1].split()[0]) < 1e-7
    res10, converged10 = out.split("PIPE10")[1].split()[:2]
    assert float(res10) < 1e-7 and converged10 == "True"


def test_distributed_eigsh():
    out = run_forced(PREAMBLE + textwrap.dedent("""
        w, V = D.eigsh(k=3, tol=1e-10, maxiter=3000)
        wr = np.sort(np.linalg.eigvalsh(np.asarray(As.todense())))[:3]
        print("EW", np.abs(np.asarray(w) - wr).max())
    """))
    assert float(out.split("EW")[1]) < 1e-7


def test_partition_utilities():
    from repro.core.distributed import partition_coordinate, partition_simple
    b = partition_simple(103, 8)
    assert b[0] == 0 and b[-1] == 103 and len(b) == 9
    assert (np.diff(b) >= 103 // 8).all()
    rng = np.random.default_rng(0)
    coords = rng.normal(size=(64, 2))
    perm = partition_coordinate(coords, 4)
    assert sorted(perm.tolist()) == list(range(64))


def test_nonsymmetric_distributed_solve():
    out = run_forced(PREAMBLE + textwrap.dedent("""
        v2 = vals.copy()
        v2[cols == rows - 1] = -1.3
        v2[cols == rows + 1] = -0.7
        Dn = DSparseTensor.from_global(v2, rows, cols, (n, n), mesh)
        assert not Dn.meta.symmetric
        xs = Dn.solve(Dn.stack_vector(b), tol=1e-11, maxiter=6000)
        An = SparseTensor(v2, rows, cols, (n, n))
        res = np.abs(np.asarray(An @ jnp.asarray(Dn.gather_global(xs))) - b).max()
        print("NS", res)
        # gradient through the plan's Aᵀ-partition adjoint
        def loss(lval):
            return jnp.sum(Dn.with_values(lval).solve(
                Dn.stack_vector(b), tol=1e-12, maxiter=6000) ** 2)
        g = jax.grad(loss)(Dn.lval)
        def loss_s(v):
            x = An.with_values(v).solve(jnp.asarray(b), backend="jnp",
                                        method="bicgstab", tol=1e-12,
                                        maxiter=6000)
            return jnp.sum(x ** 2)
        gs = jax.grad(loss_s)(jnp.asarray(v2))
        bounds = partition_simple(n, 8)
        gv = np.zeros(len(v2))
        for q in range(8):
            s, e = bounds[q], bounds[q + 1]
            m = (rows >= s) & (rows < e)
            gv[m] = np.asarray(g)[q][:m.sum()]
        rel = np.abs(gv - np.asarray(gs)) / (np.abs(np.asarray(gs)).max())
        print("NG", rel.max())
    """))
    assert float(out.split("NS")[1].split()[0]) < 1e-7
    assert float(out.split("NG")[1]) < 1e-6


# ---------------------------------------------------------------------------
# plan-engine path (PR 3): analyze-once across sweeps, backward, with_values
# ---------------------------------------------------------------------------

def test_distributed_plan_reuse_counters():
    """A tolerance sweep (3 solves) + one backward on a NON-symmetric
    DSparseTensor performs exactly ONE analyze and builds the Aᵀ partition
    once; the per-values setup memo serves the repeat solves."""
    out = run_forced(PREAMBLE + textwrap.dedent("""
        v2 = vals.copy()
        v2[cols == rows - 1] = -1.3
        v2[cols == rows + 1] = -0.7
        Dn = DSparseTensor.from_global(v2, rows, cols, (n, n), mesh)
        bn = Dn.stack_vector(b)
        reset_plan_stats()
        for tol in (1e-4, 1e-8, 1e-11):
            Dn.solve(bn, tol=tol, maxiter=6000)
        jax.grad(lambda lv: jnp.sum(Dn.with_values(lv).solve(
            bn, tol=1e-11, maxiter=6000) ** 2))(Dn.lval)
        print("ANALYZE", PLAN_STATS["analyze"])
        print("TPART", PLAN_STATS["t_partition"])
        print("HITS", PLAN_STATS["cache_hit"])
        print("REUSE", PLAN_STATS["setup_reuse"])
        print("TSHARED", PLAN_STATS["transpose_shared"])
    """))
    assert int(out.split("ANALYZE")[1].split()[0]) == 1, out
    assert int(out.split("TPART")[1].split()[0]) == 1, out
    assert int(out.split("HITS")[1].split()[0]) >= 3, out
    assert int(out.split("REUSE")[1].split()[0]) >= 2, out
    assert int(out.split("TSHARED")[1].split()[0]) == 1, out


def test_distributed_with_values_shares_plan_cache():
    """with_values views re-solve without re-analyzing, and the symmetric
    backward adds zero analyzes (transpose is the same plan object)."""
    out = run_forced(PREAMBLE + textwrap.dedent("""
        reset_plan_stats()
        x1 = D.solve(bs, tol=1e-10, maxiter=4000)
        x2 = D.with_values(2.0 * D.lval).solve(bs, tol=1e-10, maxiter=4000)
        jax.grad(lambda lv: jnp.sum(D.with_values(lv).solve(
            bs, tol=1e-12, maxiter=4000) ** 2))(D.lval)
        print("REL", float(jnp.abs(2.0 * x2 - x1).max() / jnp.abs(x1).max()))
        print("ANALYZE", PLAN_STATS["analyze"])
        print("TSHARED", PLAN_STATS["transpose_shared"])
    """))
    assert float(out.split("REL")[1].split()[0]) < 1e-8
    assert int(out.split("ANALYZE")[1].split()[0]) == 1, out
    assert int(out.split("TSHARED")[1].split()[0]) == 1, out


def test_nonsymmetric_distributed_gradcheck_vs_dense_adjoint():
    """Distributed non-symmetric gradient against the DENSE autodiff adjoint
    (jnp.linalg.solve), not just the single-device sparse path."""
    out = run_forced(PREAMBLE + textwrap.dedent("""
        v2 = vals.copy()
        v2[cols == rows - 1] = -1.4
        v2[cols == rows + 1] = -0.6
        Dn = DSparseTensor.from_global(v2, rows, cols, (n, n), mesh)
        bn = Dn.stack_vector(b)
        g = jax.grad(lambda lv: jnp.sum(Dn.with_values(lv).solve(
            bn, tol=1e-13, maxiter=8000) ** 2))(Dn.lval)
        def loss_dense(v):
            dense = jnp.zeros((n, n)).at[rows, cols].add(v)
            return jnp.sum(jnp.linalg.solve(dense, jnp.asarray(b)) ** 2)
        gd = jax.grad(loss_dense)(jnp.asarray(v2))
        bounds = partition_simple(n, 8)
        gv = np.zeros(len(v2))
        for q in range(8):
            s, e = bounds[q], bounds[q + 1]
            m = (rows >= s) & (rows < e)
            gv[m] = np.asarray(g)[q][:m.sum()]
        print("DG", (np.abs(gv - np.asarray(gd))
                     / np.abs(np.asarray(gd)).max()).max())
    """))
    assert float(out.split("DG")[1]) < 1e-6


def test_schwarz_converges_in_fewer_iterations_than_jacobi():
    """precond='schwarz' (shard-local overlapping Schwarz, ILU(0) subdomain
    solves on the direct machinery) beats point Jacobi on the 2-shard
    Poisson problem — strictly fewer CG iterations at the same tolerance."""
    out = run_forced(PREAMBLE + textwrap.dedent("""
        mesh2 = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("data",))
        D2 = DSparseTensor.from_global(vals, rows, cols, (n, n), mesh2)
        b2 = D2.stack_vector(b)
        xj, ij = D2.solve_with_info(b2, tol=1e-10, maxiter=4000)
        xs, isz = D2.solve_with_info(b2, tol=1e-10, maxiter=4000,
                                     precond="schwarz")
        print("JIT", int(ij.iters), bool(ij.converged))
        print("SIT", int(isz.iters), bool(isz.converged))
        print("SRES", float(jnp.abs(jnp.asarray(
            As @ jnp.asarray(D2.gather_global(xs))) - jnp.asarray(b)).max()))
    """))
    jit_, jconv = out.split("JIT")[1].split()[:2]
    sit, sconv = out.split("SIT")[1].split()[:2]
    assert jconv == "True" and sconv == "True"
    assert int(sit) < int(jit_), (sit, jit_)
    assert float(out.split("SRES")[1]) < 1e-7


def test_schwarz_distributed_gradients():
    """Gradients flow through a schwarz-preconditioned distributed solve
    (the preconditioner state is setup(values) output, not traced-through)."""
    out = run_forced(PREAMBLE + textwrap.dedent("""
        g = jax.grad(lambda lv: jnp.sum(D.with_values(lv).solve(
            bs, tol=1e-13, maxiter=4000, precond="schwarz") ** 2))(D.lval)
        def loss_single(v):
            x = As.with_values(v).solve(jnp.asarray(b), backend="jnp",
                                        method="cg", tol=1e-13, maxiter=4000)
            return jnp.sum(x ** 2)
        gs = jax.grad(loss_single)(jnp.asarray(vals))
        bounds = partition_simple(n, 8)
        gv = np.zeros(len(vals))
        for q in range(8):
            s, e = bounds[q], bounds[q + 1]
            m = (rows >= s) & (rows < e)
            gv[m] = np.asarray(g)[q][:m.sum()]
        print("SG", (np.abs(gv - np.asarray(gs))
                     / np.abs(np.asarray(gs)).max()).max())
    """))
    assert float(out.split("SG")[1]) < 1e-8


def test_two_level_schwarz_beats_one_level_and_scales():
    """precond='schwarz2' (symmetric deflated two-level: aggregated global
    coarse matrix, cached direct factors) needs FEWER CG iterations than
    one-level schwarz at 8 shards on 2-D Poisson, and its count grows
    sublinearly from 2 → 8 shards."""
    out = run_forced(PREAMBLE + textwrap.dedent("""
        from repro.data.poisson import poisson2d
        ng = 48
        A2 = poisson2d(ng)
        n2 = ng * ng
        v2, r2, c2 = (np.asarray(A2.val), np.asarray(A2.row),
                      np.asarray(A2.col))
        b2v = np.random.default_rng(3).normal(size=n2)
        its = {}
        for p in (2, 8):
            meshp = jax.sharding.Mesh(np.array(jax.devices()[:p]), ("data",))
            Dp = DSparseTensor.from_global(v2, r2, c2, (n2, n2), meshp)
            bp = Dp.stack_vector(b2v)
            _, i1 = Dp.solve_with_info(bp, tol=1e-8, maxiter=4000,
                                       precond="schwarz")
            _, i2 = Dp.solve_with_info(bp, tol=1e-8, maxiter=4000,
                                       precond="schwarz2")
            assert bool(i1.converged) and bool(i2.converged)
            its[p] = (int(i1.iters), int(i2.iters))
        print("IT2", its[2][0], its[2][1])
        print("IT8", its[8][0], its[8][1])
    """))
    one2, two2 = map(int, out.split("IT2")[1].split()[:2])
    one8, two8 = map(int, out.split("IT8")[1].split()[:2])
    assert two8 < one8, (two8, one8)                 # two-level wins at P=8
    # sublinear growth 2 → 8 shards (4× shards, far less than 4× iters)
    assert two8 <= 2 * two2, (two2, two8)


def test_two_level_schwarz_gradients_and_plan_reuse():
    """Gradients flow through schwarz2 (replicated coarse factor rides the
    shard_map state), match the single-device reference, and the sweep +
    backward still analyze once."""
    out = run_forced(PREAMBLE + textwrap.dedent("""
        reset_plan_stats()
        for tol in (1e-6, 1e-10):
            D.solve(bs, tol=tol, maxiter=4000, precond="schwarz2")
        g = jax.grad(lambda lv: jnp.sum(D.with_values(lv).solve(
            bs, tol=1e-13, maxiter=4000, precond="schwarz2") ** 2))(D.lval)
        print("ANALYZE", PLAN_STATS["analyze"])
        print("REUSE", PLAN_STATS["setup_reuse"])
        def loss_single(v):
            x = As.with_values(v).solve(jnp.asarray(b), backend="jnp",
                                        method="cg", tol=1e-13, maxiter=4000)
            return jnp.sum(x ** 2)
        gs = jax.grad(loss_single)(jnp.asarray(vals))
        bounds = partition_simple(n, 8)
        gv = np.zeros(len(vals))
        for q in range(8):
            s, e = bounds[q], bounds[q + 1]
            m = (rows >= s) & (rows < e)
            gv[m] = np.asarray(g)[q][:m.sum()]
        print("SG", (np.abs(gv - np.asarray(gs))
                     / np.abs(np.asarray(gs)).max()).max())
    """))
    assert int(out.split("ANALYZE")[1].split()[0]) == 1, out
    assert int(out.split("REUSE")[1].split()[0]) >= 1, out
    assert float(out.split("SG")[1]) < 1e-8


def test_dsparse_list_shared_pattern_single_analysis():
    """DSparseTensorList members sharing one partitioned pattern route
    through ONE plan (a single analyze serves the whole batch)."""
    out = run_forced(PREAMBLE + textwrap.dedent("""
        batch = DSparseTensorList([D, D.with_values(2.0 * D.lval),
                                   D.with_values(0.5 * D.lval)])
        reset_plan_stats()
        xs = batch.solve([bs, bs, bs], tol=1e-11, maxiter=4000)
        print("ANALYZE", PLAN_STATS["analyze"])
        for s, x in zip((1.0, 2.0, 0.5), xs):
            r = np.abs(s * np.asarray(As @ jnp.asarray(D.gather_global(x)))
                       - b).max()
            assert r < 1e-7, (s, r)
        print("LIST_OK")
    """))
    assert int(out.split("ANALYZE")[1].split()[0]) == 1, out
    assert "LIST_OK" in out


def test_distributed_slogdet_gather_fallback():
    """slogdet gathers to one host, rebuilds a SparseTensor, delegates —
    within DIRECT_BUDGET that is now the sparse cached-LDLᵀ path (no
    densification; PLAN_STATS['factorize'] proves it), and the gather is
    still warned about."""
    out = run_forced(PREAMBLE + textwrap.dedent("""
        import warnings
        reset_plan_stats()
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            sign, logabs = D.slogdet()
        assert any("slogdet" in str(w.message) for w in rec), rec
        print("FACT", PLAN_STATS["factorize"])
        sr, lr_ = np.linalg.slogdet(np.asarray(As.todense()))
        print("SLD", abs(float(sign) - sr) + abs(float(logabs) - lr_) /
              abs(lr_))
    """))
    assert int(out.split("FACT")[1].split()[0]) == 1, out   # LDLᵀ, not dense
    assert float(out.split("SLD")[1]) < 1e-10


@pytest.mark.parametrize("case", ["poisson2d", "scattered_band"])
def test_loop_spmv_layout_matches_single_device(case):
    """The Krylov loop's row-slot (ELL) layout is as wide as the longest
    row, duplicates included, on a stencil pattern and on entries scattered
    over a band; both solve and differentiate like the single-device
    plan."""
    out = run_forced(textwrap.dedent(f"""
        import jax, numpy as np, jax.numpy as jnp
        jax.config.update("jax_enable_x64", True)
        from repro.core.distributed import DSparseTensor
        from repro.core.sparse import SparseTensor
        from repro.data.poisson import poisson2d
        if "{case}" == "poisson2d":
            A = poisson2d(12)
        else:       # SPD, entries on ~60 of the 61 diagonals of a band,
                    # the main diagonal stored as two duplicate halves
            m = 144
            i = np.arange(m)
            near = np.abs(i[:, None] - i[None, :]) <= 30
            keep = np.triu(np.random.default_rng(1).random((m, m)) < 0.08, 1)
            r, c = np.nonzero(keep & near)
            w = np.ones(r.size)
            half = 0.5 * (np.bincount(r, w, m) + np.bincount(c, w, m) + 1.0)
            A = SparseTensor(np.concatenate([-w, -w, half, half]),
                             np.concatenate([r, c, i, i]),
                             np.concatenate([c, r, i, i]), (m, m))
        n = A.shape[0]
        vals, rows, cols = (np.asarray(A.val), np.asarray(A.row),
                            np.asarray(A.col))
        D = DSparseTensor.from_global(vals, rows, cols, (n, n),
                                      jax.make_mesh((4,), ("data",)))
        kw = dict(tol=1e-12, maxiter=4000)
        print("WIDTH", D.plan(**kw).artifacts["ell"].src.shape[1],
              np.bincount(rows).max())
        b = np.random.default_rng(0).uniform(0.5, 1.5, n)
        bs = D.stack_vector(b)
        x = D.gather_global(D.solve(bs, **kw))
        xr = np.linalg.solve(np.asarray(A.todense()), b)
        print("ERR", np.abs(x - xr).max() / np.abs(xr).max())
        g = jax.grad(lambda lv: jnp.sum(D.with_values(lv).solve(bs, **kw)))(
            D.lval)
        gs = jax.grad(lambda v: jnp.sum(A.with_values(v).solve(
            jnp.asarray(b), backend="jnp", method="cg", **kw)))(A.val)
        gv, rg, cg = D.with_values(g).gather_values()
        Gd, Gs = np.zeros((n, n)), np.zeros((n, n))
        np.add.at(Gd, (rg, cg), gv)
        np.add.at(Gs, (rows, cols), np.asarray(gs))
        print("GERR", np.abs(Gd - Gs).max() / np.abs(Gs).max())
    """), n_dev=4)
    width, longest = out.split("WIDTH")[1].split()[:2]
    assert width == longest
    assert float(out.split("ERR")[1].split()[0]) < 1e-9
    assert float(out.split("GERR")[1]) < 1e-8


def test_unstacked_rhs_is_refused():
    """A global (n,) right-hand side is refused with the stacked shape named
    (it used to reach the halo exchange as 0-d per-shard slices)."""
    import jax
    import jax.numpy as jnp
    from repro.core.distributed import DSparseTensor
    from repro.data.poisson import poisson1d
    A = poisson1d(16)
    mesh = jax.make_mesh((1,), ("data",))
    D = DSparseTensor.from_global(np.asarray(A.val), np.asarray(A.row),
                                  np.asarray(A.col), (16, 16), mesh)
    with pytest.raises(ValueError, match=r"stacked \(P, n_loc\) = \(1, 16\)"):
        D.solve(jnp.ones(16))
    x = D.gather_global(D.solve(D.stack_vector(np.ones(16)), tol=1e-10))
    np.testing.assert_allclose(np.asarray(A @ jnp.asarray(x)), 1.0,
                               atol=1e-8)
