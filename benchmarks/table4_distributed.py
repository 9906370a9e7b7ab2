"""Paper Table 4: distributed CG throughput/memory under a fixed iteration
budget (the paper runs 1000 Jacobi-CG iterations at 1e8–4e8 DOF on H200s;
here: every device of the process, CPU-scaled DOF, 200-iteration budget).

Reports time, per-shard memory estimate, residual-after-budget — plus the
pipelined-CG variant (beyond-paper: one fused reduction/iteration) and the
halo-byte count per iteration.  PR 3 adds the plan-engine columns: analyze
count and setup reuse across a 3-solve tolerance sweep (``PLAN_STATS``) and
the setup-amortization ratio (first solve incl. analyze+setup vs steady-state
re-solve on the cached plan).

Runs in-process over ``jax.devices()``: on a host without several
accelerators, give the CPU backend virtual devices on the command line::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m benchmarks.run --only table4 --smoke
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import PLAN_STATS, reset_plan_stats
from repro.core.distributed import DSparseTensor
from repro.data.poisson import poisson2d


def run(full: bool = False, smoke: bool = False):
    rows = []
    grids = (48, 96) if smoke else (64, 128, 256)
    budget = 100 if smoke else 200

    p = jax.device_count()
    mesh = jax.make_mesh((p,), ("data",))
    for ng in grids:
        n = ng * ng
        A = poisson2d(ng, dtype=np.float64)
        D = DSparseTensor.from_global(np.asarray(A.val), np.asarray(A.row),
                                      np.asarray(A.col), A.shape, mesh)
        b = D.stack_vector(np.ones(n))
        for pipelined in (False, True):
            solve = jax.jit(lambda bb: D.solve(bb, tol=0.0, atol=1e-300,
                                               maxiter=budget,
                                               pipelined=pipelined))
            jax.block_until_ready(solve(b))
            t0 = time.perf_counter()
            x = solve(b)
            jax.block_until_ready(x)
            dt = time.perf_counter() - t0
            xg = D.gather_global(x)
            res = float(np.abs(np.asarray(
                poisson2d(ng, dtype=np.float64) @ jnp.asarray(xg))
                - 1.0).max())
            shard_mem = (D.meta.nnz_loc * 16 + 6 * D.meta.n_loc * 8)
            halo = (D.meta.h_lo + D.meta.h_hi) * 8
            tag = "pipelined" if pipelined else "standard"
            rows.append(f"table4/{tag}/dof={n},{dt/budget*1e6:.1f},"
                        f"residual_after_budget={res:.1e};"
                        f"mem_per_shard={shard_mem/2**20:.2f}MiB;"
                        f"halo_bytes_per_iter={halo};dof_per_s={n*budget/dt:.2e}")

        # Schwarz ladder (PR 4): one-level vs two-level (deflated coarse
        # correction on cached direct factors) — iterations + per-solve time
        # at a fixed tolerance; the coarse solve must BUY its extra
        # all_gather per iteration with fewer iterations
        bsz = D.stack_vector(np.random.default_rng(3).normal(size=n))
        for pname in ("jacobi", "schwarz", "schwarz2"):
            solve = jax.jit(lambda lv, bb, pname=pname: D.with_values(lv)
                            .solve_with_info(bb, tol=1e-8, maxiter=4000,
                                             precond=pname))
            jax.block_until_ready(solve(D.lval, bsz))  # warm (incl. analyze)
            t0 = time.perf_counter()
            x, info = solve(D.lval, bsz)
            jax.block_until_ready(x)
            dt = time.perf_counter() - t0
            rows.append(f"table4/{pname}/dof={n},{dt*1e6:.1f},"
                        f"iters={int(info.iters)};converged={bool(info.converged)}")

        # plan-engine amortization: cold first solve (analyze + setup) vs
        # steady-state re-solves on the cached plan, counters proving the
        # tolerance sweep analyzes once and reuses the per-values setup
        Dp = DSparseTensor.from_global(np.asarray(A.val), np.asarray(A.row),
                                       np.asarray(A.col), A.shape, mesh)
        reset_plan_stats()
        t0 = time.perf_counter()
        jax.block_until_ready(Dp.solve(b, tol=1e-8, maxiter=budget))
        t_first = time.perf_counter() - t0
        t0 = time.perf_counter()
        for tol in (1e-6, 1e-8):
            jax.block_until_ready(Dp.solve(b, tol=tol, maxiter=budget))
        t_steady = (time.perf_counter() - t0) / 2
        rows.append(f"table4/plan/dof={n},{t_steady*1e6:.1f},"
                    f"analyze={PLAN_STATS['analyze']};"
                    f"setup_reuse={PLAN_STATS['setup_reuse']};"
                    f"cache_hit={PLAN_STATS['cache_hit']};"
                    f"amortization=x{t_first/max(t_steady,1e-9):.1f};"
                    f"t_first_us={t_first*1e6:.1f}")
    return rows


if __name__ == "__main__":
    import sys
    print("\n".join(run(smoke="--smoke" in sys.argv)))
