"""SpMV kernel-plan comparison: segment-sum COO vs block-ELL Pallas, plus
the fused CG step vs the separate-pass loop.

Three row families on the 2D Poisson ladder (CPU: Pallas runs in interpret
mode, so the BELL/fused timings are correctness-trajectory rows, not perf —
the perf claim is carried by the roofline byte model, asserted below):

* ``spmv/segment_sum`` / ``spmv/bell`` — one matvec through each kernel.
* ``spmv/cg_plain`` / ``spmv/cg_fused`` — one full CG solve with the fused
  step kernels forced off/on through the SAME pallas kernel plan.
* ``spmv/fused_step_model`` — the roofline byte model of one CG iteration:
  ``launch.roofline.assert_fused_step_savings`` raises (→ suite fails, CI
  red) unless the fused step stays under 0.5× the separate-pass baseline
  and the baseline matches XLA's byte count for the compiled passes.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dispatch as _dispatch
from repro.core import options as _options
from repro.core.adjoint import sparse_solve_with_info
from repro.core.dispatch import get_plan, make_config
from repro.core.sparse import coo_matvec
from repro.data.poisson import poisson2d
from repro.launch.roofline import assert_fused_step_savings

from .common import csv_row, timeit

SMOKE_LADDER = [16]                 # 256 DOF — interpret-mode Pallas is slow
LADDER = [16, 32]
FULL_LADDER = [16, 32, 64]


def run(full: bool = False, smoke: bool = False):
    rows = []
    ladder = SMOKE_LADDER if smoke else (FULL_LADDER if full else LADDER)
    for ng in ladder:
        n = ng * ng
        A = poisson2d(ng, dtype=np.float64)
        b = jnp.ones(n)

        t, _ = timeit(jax.jit(
            lambda v, x: coo_matvec(v, A.row, A.col, x, n)), A.val, b)
        rows.append(csv_row(f"spmv/segment_sum/dof={n}", t * 1e6,
                            f"nnz={A.nnz}"))

        cfg = make_config(A, backend="pallas", method="cg", tol=1e-8,
                          maxiter=2000)
        plan = get_plan(A, cfg)                  # analyze: BELL built once
        kp = plan.artifacts["kernel"]
        mv = jax.jit(lambda x: _dispatch._plan_matvec(plan, kp, A.val)(x))
        t, y = timeit(mv, b)
        err = float(jnp.linalg.norm(y - coo_matvec(A.val, A.row, A.col, b, n)))
        rows.append(csv_row(f"spmv/bell/dof={n}", t * 1e6,
                            f"fill={kp.bell[0].fill:.4f};err={err:.1e}"))

        for label, mode in (("cg_plain", "off"), ("cg_fused", "on")):
            with _options.options(fused_step=mode):
                t, (x, info) = timeit(jax.jit(
                    lambda val, bb: sparse_solve_with_info(
                        cfg, A.with_values(val), bb)), A.val, b)
            rows.append(csv_row(
                f"spmv/{label}/dof={n}", t * 1e6,
                f"residual={float(info.resnorm):.1e};iters={int(info.iters)}"))

    model = assert_fused_step_savings()          # raises → CI red
    rows.append(csv_row(
        "spmv/fused_step_model", 0.0,
        f"ratio={model['ratio']:.3f};"
        f"baseline_bytes={model['baseline_bytes']:.0f};"
        f"fused_bytes={model['fused_step_bytes']:.0f};"
        f"iteration_ratio={model['iteration_ratio']:.3f};"
        f"measured_baseline={model['measured_baseline_bytes']:.0f}"))
    return rows


if __name__ == "__main__":
    print("\n".join(run()))
